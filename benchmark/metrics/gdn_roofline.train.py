"""Gated DeltaNet scan, trace: the least time the chip could take for the
recurrence of every DeltaNet layer in one step (``flops_qwen3_next.
gdn_step_flops`` / ``gdn_step_bytes``: the token recurrence's work, not the
chunked form's) over the device time a step spends under the scopes the
cell's workload file names under ``kernel_names.gdn``, in percent."""

from flops_qwen3_next import gdn_step_bytes, gdn_step_flops
from scopes import roofline_pct


def read(run: dict):
    return roofline_pct(run, "gdn", gdn_step_flops, gdn_step_bytes)
