"""Step program, trace:
self time of the device ops whose op-name path puts them in the forward pass
(``jvp(`` or a segment ``fwd``; not ``transpose(``, not recomputed).
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "fwd_ms")
