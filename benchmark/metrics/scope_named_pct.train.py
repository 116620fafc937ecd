"""Step program, trace:
the share of the device's busy time that went to a phase (fwd, bwd, recompute,
optimizer) or is a collective, in percent. Healthy at 95 or more: below that
the split by phase leaves too much out to be argued from.
Mean over the kept periods of the traced window (percent); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "scope_named_pct")
