"""Full-attention kernel, trace: ``full_attn_roofline.train`` with this
family's count — the least time for the flash forward and backward of every
attention layer in one step (7 causal matmuls at 32 query heads of 64; K and
V read at their own 8 heads; ``flops_lfm2_moe``) over the device time a step
spends under the scopes the cell's workload file names under
``kernel_names.full_attn``, in percent. At head size 64 every matmul fills
half of the 128 x 128 unit, so about 50 is the ceiling (PERF.md section 3)."""

from flops_lfm2_moe import full_attn_step_bytes, full_attn_step_flops
from scopes import roofline_pct


def read(run: dict):
    return roofline_pct(run, "full_attn", full_attn_step_flops, full_attn_step_bytes)
