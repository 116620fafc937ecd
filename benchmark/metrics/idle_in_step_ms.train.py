"""Step program, trace:
device idle inside the step program's own execution (bubbles, DMA waits):
nothing the host does changes it.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "idle_in_step_ms")
