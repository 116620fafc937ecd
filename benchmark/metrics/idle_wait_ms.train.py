"""Trainer loop, program spans on the device's clock:
device idle between two executions of the step program while the host is inside
``train.block``: the completion notice's way up to Python. Only a loop that
does not sync every step removes it. Takes what a launch's latency really is
too, where the reader's shift is the least causal one (``spans.py``).
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "idle_wait_ms")
