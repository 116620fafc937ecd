"""Full-attention kernel, trace: the least time for the flash forward and
backward of every full-attention layer in one step (7 causal matmuls at the
configuration's head size; K and V read at their own head count;
``flops_qwen3_next``) over the device time a step spends under the scopes the
cell's workload file names under ``kernel_names.full_attn`` — not under
``.flash``, whose count is GPT-2's — in percent."""

from flops_qwen3_next import full_attn_step_bytes, full_attn_step_flops
from scopes import roofline_pct


def read(run: dict):
    return roofline_pct(run, "full_attn", full_attn_step_flops, full_attn_step_bytes)
