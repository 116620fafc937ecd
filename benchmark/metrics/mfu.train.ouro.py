"""Step program: the whole step's share of the chip's peak, in percent, read
as ``mfu.train.lfm2-moe`` reads it — the benchmark's own count of the
operations the forward and backward passes need (``flops_ouro.
train_step_flops``: the stack and the head once a pass, recomputation not
counted) for every step of the window, over all their time, over chips x the
published bf16 peak; the steps the profiler touched are left out with their
time."""

import jax

from flops_ouro import train_step_flops
from peaks import peaks_of
from xtrace import profiled_steps


def read(run: dict):
    if not run["model"].get("stack_passes"):
        return None
    ends = run["step_ends"]
    skip = profiled_steps(run)
    times = [b - a for i, (a, b) in enumerate(zip([0.0] + ends[:-1], ends), 1) if i not in skip]
    if not times:
        return None
    rows = int(run["workload"]["traffic"]["rows"])
    per_step = train_step_flops(run["model"], rows, run["model"]["max_seq_len"])
    peak = peaks_of(jax.devices()[0].device_kind)["bf16_flops"] * run["chips"]
    return 100.0 * per_step * len(times) / sum(times) / peak
