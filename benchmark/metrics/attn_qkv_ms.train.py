"""Step program, trace: self time of the device ops under ``attn_full/attn_qkv``
in every pass — an attention layer's q / k / v projections and what stands
between them and the attention kernel (q / k norms where the model has them,
rotary positions, converts, layout changes), their backward and
recomputation. Counted in ``fwd_ms`` / ``bwd_ms`` / ``recompute_ms`` too. Mean
over the kept periods of the traced window (ms a step); ``scopes.py``."""

from scopes import scope_ms


def read(run: dict):
    return scope_ms(run, ["attn_full/attn_qkv"])
