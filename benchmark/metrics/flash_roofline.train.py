"""Attention kernel, trace: the least time the chip could take for the flash
forward and backward calls of the traced steps — the larger of operations
over peak and bytes over peak bandwidth, both from shapes
(``flops.flash_step_flops`` / ``flash_step_bytes``) — over the summed device
time of those kernels' events, in percent, on the device that spent longest
in them. The kernels' events are found by the instruction names the cell's
workload file lists under ``kernel_names.flash``. Which bound applies is
kept in ``run["trace"]["flash_bound"]``."""

import jax

from flops import flash_step_bytes, flash_step_flops
from peaks import peaks_of
from xtrace import kernel_seconds


def read(run: dict):
    t = run.get("trace")
    names = run["workload"].get("kernel_names", {}).get("flash")
    if not t or not names or not t.get("steps"):
        return None
    flash_s = kernel_seconds(t, names)
    if not flash_s:
        return None
    peak = peaks_of(jax.devices()[0].device_kind)
    rows = int(run["workload"]["traffic"]["rows"]) // run["chips"]  # one device's share
    seq = run["model"]["max_seq_len"]
    by_flops = flash_step_flops(run["model"], rows, seq) / peak["bf16_flops"]
    by_bytes = flash_step_bytes(run["model"], rows, seq) / peak["hbm_bytes_per_s"]
    t["flash_bound"] = "flops" if by_flops >= by_bytes else "bytes"
    return 100.0 * max(by_flops, by_bytes) * t["steps"] / flash_s
