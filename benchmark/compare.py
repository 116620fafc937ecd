"""The comparison that decides ``correct`` for a training cell.

Numbers read; those with a limit in the cell's workload file (``limits``)
are compared, each against its own, and the others are only recorded
(PERF.md section 2 says which have none, and why):

- ``loss1`` ``loss2`` ``loss3`` — |program loss - reference loss| / reference
  loss, for each of the first three steps;
- ``grad1`` — the first gradient as the optimizer gets it (after the clip),
  by the worst leaf: |program norm - reference norm| over the larger of the
  reference's norm of that leaf and of the median leaf. The program's norm
  is worked out from AdamW's first moment after one step, mu / (1 - b1);
- ``dparam3`` — the parameters' change over the three steps, by the worst
  leaf, the same gap of norms. Leaves whose reference gradient is under a
  thousandth of the median leaf's (a key's bias under softmax: nought to
  rounding) move under Adam by round-off alone and are left out, by that
  rule and not by name.

A "leaf" is one layer's slice of a stacked leaf. A reading that is not
finite fails its limit.
"""

from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE_GRAD = 1e-3  # of the median leaf's gradient norm


def _flat(norms: dict[str, np.ndarray]) -> tuple[list[str], np.ndarray]:
    names, vals = [], []
    for k in sorted(norms):
        a = np.atleast_1d(np.asarray(norms[k], np.float64))
        names += [k if a.size == 1 else f"{k}[{i}]" for i in range(a.size)]
        vals.append(a.ravel())
    return names, np.concatenate(vals)


def worst_leaf_gap(program: dict, reference: dict, keep: np.ndarray | None = None):
    """(gap, leaf name) of the worst leaf; see the module docstring."""
    names, ref = _flat(reference)
    names_p, prog = _flat(program)
    if names != names_p:
        raise ValueError("program and reference name different leaves")
    floor = float(np.median(ref))
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def readings(program: dict, reference: dict) -> dict[str, dict]:
    """Every number compared, as ``{name: {"value": v, "at": where}}``."""
    out: dict[str, dict] = {}
    for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"]), 1):
        v = abs(lp - lr) / abs(lr) if math.isfinite(lp) and lr else math.inf
        out[f"loss{i}"] = {"value": v, "program": lp, "reference": lr}
    gap, leaf = worst_leaf_gap(program["grad1"], reference["grad1"])
    out["grad1"] = {"value": gap, "at": leaf}
    _, gref = _flat(reference["grad1"])
    keep = gref >= NEGLIGIBLE_GRAD * float(np.median(gref))
    gap, leaf = worst_leaf_gap(program["dparam"], reference["dparam"], keep)
    out[f"dparam{len(reference['losses'])}"] = {
        "value": gap, "at": leaf, "left_out": int((~keep).sum())}
    return out


def judge(read: dict[str, dict], limits: dict[str, float]) -> tuple[bool, dict]:
    """``correct`` and the checks as printed: every limit needs its reading;
    a reading without a limit is not compared."""
    if not limits or set(limits) - set(read):
        raise ValueError(f"limits {sorted(limits)} without readings {sorted(read)}")
    checks = {}
    ok = True
    for name in sorted(limits):
        v, lim = read[name]["value"], float(limits[name])
        passed = bool(math.isfinite(v) and v <= lim)
        ok &= passed
        checks[name] = {**read[name], "limit": lim, "ok": passed}
    return ok, checks
