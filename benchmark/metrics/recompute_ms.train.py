"""Step program, trace:
self time of the device ops of the recomputed forward (``rematted_computation``
in the op-name path): device time that ``mfu.train`` does not count. An op the
compiler fused into a backward fusion goes with that fusion's root, to ``bwd``.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "recompute_ms")
