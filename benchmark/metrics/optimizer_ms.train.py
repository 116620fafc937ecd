"""Step program, trace:
self time of the device ops under the scope ``optimizer`` (or ``clip``): the
global norm, the clip and the AdamW update.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "optimizer_ms")
