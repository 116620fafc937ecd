"""Short-convolution mixer, trace: self time of the device ops under the
module ``shortconv`` in every pass (the input projection, the gate - taps -
gate between the projections, the output projection, their backward and
recomputation). Counted in ``fwd_ms`` / ``bwd_ms`` / ``recompute_ms`` too.
Mean over the kept periods of the traced window (ms a step); ``scopes.py``."""

from scopes import scope_ms


def read(run: dict):
    return scope_ms(run, ["shortconv"])
