"""Step program, trace:
self time of the device ops of the backward pass (``transpose(`` in the op-name
path, not under ``rematted_computation``).
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "bwd_ms")
