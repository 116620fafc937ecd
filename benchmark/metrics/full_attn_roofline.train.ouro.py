"""Full-attention kernel, trace: ``full_attn_roofline.train`` with this
family's count — the least time for the flash forward and backward of every
layer application in one step (7 causal matmuls at 16 heads of 128, no KV
groups, 32 applications; ``flops_ouro``) over the device time a step spends
under the scopes the cell's workload file names under
``kernel_names.full_attn``, in percent. At head size 128 a matmul fills the
128 x 128 unit; the cell's per-layer remat runs the forward kernel twice and
the count holds it once, so 7/9 of the kernels' own efficiency is the
ceiling (PERF.md section 3)."""

from flops_ouro import full_attn_step_bytes, full_attn_step_flops
from scopes import roofline_pct


def read(run: dict):
    if not run["model"].get("stack_passes"):
        return None
    return roofline_pct(run, "full_attn", full_attn_step_flops, full_attn_step_bytes)
