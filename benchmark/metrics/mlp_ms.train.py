"""Dense feed-forward layer, trace: self time of the device ops under the
module ``mlp`` in every pass (a leading layer's SwiGLU: three matmuls and
the gate, their backward and recomputation). Counted in ``fwd_ms`` /
``bwd_ms`` / ``recompute_ms`` too. Mean over the kept periods of the traced
window (ms a step); ``scopes.py``."""

from scopes import scope_ms


def read(run: dict):
    return scope_ms(run, ["mlp"])
