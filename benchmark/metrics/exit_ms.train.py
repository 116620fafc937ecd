"""Exit gate and loss, trace: self time of the device ops under the scope
``exit`` in every pass — a looped stack's exit gate after each pass (a
Linear(d -> 1) on the normed state), and after the last pass the exit
distribution, its entropy and the loss over the passes' per-token
cross-entropies, with their backward. The gate's part is counted in
``head_ce_ms.train`` too (it sits under ``head``). Mean over the kept
periods of the traced window (ms a step); ``scopes.py``. A program without
the scope reads nothing."""

from scopes import scope_ms


def read(run: dict):
    return scope_ms(run, ["exit"])
