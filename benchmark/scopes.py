"""Device time under a scope of the program, from a traced run.

``spans.py`` splits the step's device time by phase and by the one path
segment ``head``; the metrics of a layer-pattern cell ask the same of other
segments (``gdn``, ``moe``, ``experts``, …). This reader answers for any:
the self time of the leaf device ops whose op-name path has ALL the segments
of a name such as ``"gdn/scan"`` (each a whole segment of the path, in any
pass: forward, backward, recomputation), per step, as a mean over the
periods ``spans.py`` keeps on the device that was busy longest. It decodes
the trace with ``spans.py``'s functions, once a run.

Where the program has no such scope (the parent of the PR that adds one),
nothing matches and the answer is None: the metric is left out of the line.
"""

from __future__ import annotations

import spans
from xtrace import MODULES_LINE, find_xplane, op_rows, total, union


#: trace file -> what :func:`_kept` made of it (a run reads several metrics)
_DECODED: dict = {}


def _kept(run: dict):
    """(ops of the busiest device, their self times, kept periods) or None."""
    if not run.get("profile_dir"):
        return None
    path = find_xplane(run["profile_dir"])
    if path in _DECODED:
        return _DECODED[path]
    got = None
    if path:
        rows = [r for r in spans.rows_from_xspace(spans.read_xspace(path)) if r.device != spans.HOST]
        by_dev: dict[int, list] = {}
        for r in op_rows(rows):
            by_dev.setdefault(r.device, []).append(r)
        if by_dev:
            busiest = max(by_dev, key=lambda d: total(union([(r.t0, r.t0 + r.dur) for r in by_dev[d]])))
            ops = sorted(by_dev[busiest], key=lambda r: r.t0)
            modules = [r for r in rows if r.device == busiest and r.line == MODULES_LINE]
            seconds: dict[str, float] = {}
            for r in modules:
                seconds[r.name] = seconds.get(r.name, 0.0) + r.dur
            if seconds:
                program = max(seconds, key=seconds.get)
                execs = sorted((r for r in modules if r.name == program), key=lambda r: r.t0)
                if len(execs) >= 3:
                    got = (ops, spans.self_times(ops), list(zip(execs[1:-1], execs[2:])))
    _DECODED[path] = got
    return got


def scope_seconds(run: dict, names: list[str]) -> float | None:
    """Seconds a step under any of ``names`` (see the module docstring);
    None where the trace, or the program, has nothing under them."""
    got = _kept(run)
    if got is None or not names:
        return None
    ops, selfs, kept = got
    wanted = [set(n.split("/")) for n in names]
    seconds, found = 0.0, False
    for ex, nxt in kept:
        for r, s in zip(ops, selfs):
            if ex.t0 <= r.t0 < nxt.t0 and any(w <= set(r.path.split("/")) for w in wanted):
                seconds += s
                found = True
    return seconds / len(kept) if found else None


def scope_ms(run: dict, names: list[str]) -> float | None:
    s = scope_seconds(run, names)
    return None if s is None else 1e3 * s


def roofline_pct(run: dict, kernel: str, flops_fn, bytes_fn) -> float | None:
    """Least time of a kernel's work in one step (the larger of operations
    over the peak and bytes over the peak bandwidth, from shapes) over the
    device time a step spends under the scopes the cell's workload file
    names under ``kernel_names.<kernel>``, in percent."""
    import jax

    from peaks import peaks_of

    names = run["workload"].get("kernel_names", {}).get(kernel)
    spent = scope_seconds(run, names) if names else None
    if not spent:
        return None
    peak = peaks_of(jax.devices()[0].device_kind)
    rows = int(run["workload"]["traffic"]["rows"]) // run["chips"]
    seq = run["model"]["max_seq_len"]
    least = max(flops_fn(run["model"], rows, seq) / peak["bf16_flops"],
                bytes_fn(run["model"], rows, seq) / peak["hbm_bytes_per_s"])
    return 100.0 * least / spent
