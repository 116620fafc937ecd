"""Expert layer, trace: self time of the device ops under the module ``moe``
in every pass (router, dispatch, expert matmuls, combine, shared expert).
Counted in ``fwd_ms`` / ``bwd_ms`` / ``recompute_ms`` too. Mean over the kept
periods of the traced window (ms a step); ``scopes.py``."""

from scopes import scope_ms


def read(run: dict):
    return scope_ms(run, ["moe"])
