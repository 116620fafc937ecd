"""Nearest-rank percentile (a copy of the program's ``utils/percentile.
nearest_rank``): always a value that was measured, never an interpolation."""

from __future__ import annotations

import math
from typing import Iterable


def nearest_rank(vals: Iterable[float], q: float) -> float | None:
    vals = sorted(vals)
    if not vals:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    return vals[max(1, math.ceil(q * len(vals))) - 1]
