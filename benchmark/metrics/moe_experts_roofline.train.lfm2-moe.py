"""Expert matmuls, trace: ``moe_experts_roofline.train`` with this family's
count — the least time for the held experts' three matmuls over the held
assignments that the traced steps computed (the program's ``moe_counters``
events; the expected count under even routing only where a run has none),
forward and backward, in one step (``flops_lfm2_moe.moe_experts_step_flops``
/ ``_bytes``) over the device time a step spends under the scopes the cell's
workload file names under ``kernel_names.moe_experts``, in percent."""

from flops_lfm2_moe import counted_assignments, moe_experts_step_bytes, moe_experts_step_flops
from scopes import roofline_pct
from xtrace import profiled_steps


def read(run: dict):
    def held():  # roofline_pct asks for one chip's rows: so one chip's assignments
        counted = counted_assignments(run, profiled_steps(run))
        return None if counted is None else counted / run["chips"]

    return roofline_pct(
        run, "moe_experts",
        lambda model, rows, seq: moe_experts_step_flops(model, rows, seq, held()),
        lambda model, rows, seq: moe_experts_step_bytes(model, rows, seq, assignments=held()))
