"""Step program, trace:
self time of the device ops under the module ``head`` in both passes: the final
LayerNorm, the LM head and the fused cross-entropy with its backward rule.
Counted in ``fwd_ms`` / ``bwd_ms`` too.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "head_ce_ms")
