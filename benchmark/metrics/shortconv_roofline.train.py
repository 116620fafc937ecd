"""Short convolution, trace: the least time the chip could take for what
lies between the two projections of every short-convolution layer in one
step — ``y = C * conv(B * u)``, forward, the layer remat's recomputed
forward and the backward (``flops_lfm2_moe.shortconv_step_flops`` /
``shortconv_step_bytes``: the bytes bound it) — over the device time a step
spends under the scopes the cell's workload file names under
``kernel_names.shortconv``, in percent. The same work whatever implements
it: fusions of XLA today."""

from flops_lfm2_moe import shortconv_step_bytes, shortconv_step_flops
from scopes import roofline_pct


def read(run: dict):
    return roofline_pct(run, "shortconv", shortconv_step_flops, shortconv_step_bytes)
