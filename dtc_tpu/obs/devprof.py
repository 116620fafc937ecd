"""Device-time observatory (ISSUE 8): where DEVICE time goes, per component.

PR 7 answered "where did the host wall-clock go" with span timelines; this
module adds the device-side leg so the two merge into one Perfetto view and
device-time attribution becomes a programmatic, regression-gated metric
instead of a hand-driven profiling round transcribed into PERF.md by a
human. Three layers:

- **Parser** — backend-free (pure string/JSON processing, no JAX imports at
  module level) reader of the profiler's ``*.trace.json.gz`` output into
  typed :class:`OpRow` records: duration, trace-local start, scope path,
  collective-or-compute kind. Device events are selected from device
  processes (``/device:TPU:N`` pids — the PERF.md methodology) with a CPU
  fallback (the TFRT CPU backend has no device pid; its XLA op events carry
  an ``hlo_op`` arg instead). Umbrella events (``jit_*`` module spans, bare
  step-number markers) are skipped on device pids — they nest the real
  op events and would double-count.

- **Attribution** — rolls op durations up to model components (embed /
  attn_qkv / attn_kernel / attn_proj / mlp-or-moe / ln / head) and phases
  (fwd / bwd / optimizer) from each op's scope path. Scope comes from the
  event's own args when the backend provides them (TPU traces carry the
  HLO ``op_name`` metadata as ``tf_op``/``long_name``) or from a caller-
  supplied optimized-HLO scope map (:func:`scope_map_from_hlo` — the
  dynamic counterpart of the graph auditor's text parsing: the CPU backend
  emits bare ``hlo_op`` names, and joining them against the compiled
  module's per-instruction ``op_name`` metadata recovers full provenance).
  The pass also derives device-time MFU, the comm/compute overlap ratio
  (collective intervals intersected with the union of concurrent compute
  intervals — the item-3 overlap metric), and the unattributed share that
  the structural bench gate bounds.

- **Capture** — programmatic trace windows reusing the hardened
  :class:`~dtc_tpu.obs.profiling.StepWindowProfiler` (warn-and-disable:
  telemetry must never kill the run). :class:`DeviceProfiler` fires on
  cadence (``obs.devprof_every``), on demand (``request()``), and from the
  PR 7 trigger points (SLO breach, hung-step watchdog — wired in
  :mod:`dtc_tpu.obs.telemetry`); each window lands in its own artifact dir
  with a ``devprof_meta.json`` sidecar carrying the wall-clock anchors the
  merged export aligns on, the ``peak_hbm_bytes`` watermark sampled at
  window close, and (when the runtime provides them) step FLOPs + chip
  peak for offline device-MFU derivation. A ``devprof`` event rides the
  registry, so artifacts appear in flight-recorder dumps.

Clock alignment for the merged view: host spans are stamped with
``time.time()``; trace events use the profiler's own microsecond timebase.
The capture records ``t_wall_start`` immediately before ``start_trace``,
and the trace itself contains the host-side ``start_trace`` call event on
the python thread — anchoring that event's trace timestamp to
``t_wall_start`` maps every device op onto the host clock to within the
start_trace call overhead (:func:`trace_wall_anchor`).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from dataclasses import dataclass, field
from typing import Any, Callable

# ---------------------------------------------------------------------------
# typed op rows


@dataclass(frozen=True)
class OpRow:
    """One device-side op execution from the trace."""

    name: str            # trace event name (e.g. "fusion.130", "dot.4")
    hlo_op: str          # HLO instruction name (args.hlo_op, or name)
    hlo_module: str      # owning module (args.hlo_module, "" if absent)
    scope: str           # op_name metadata path ("" when unknown)
    t0_s: float          # start, trace-local seconds
    dur_s: float         # duration, seconds
    pid: int
    tid: int
    kind: str            # "collective" | "compute"


def find_trace_file(trace_dir: str) -> str | None:
    """Newest ``*.trace.json.gz`` under ``trace_dir`` (the profiler nests
    them under ``plugins/profile/<date>/``), or None."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def load_trace(path: str) -> dict[str, Any]:
    """Load one Chrome-trace JSON (gzipped or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


#: name tokens of the ISSUE 12 overlapped-collectives Pallas kernels
#: (ops/overlap_collectives.py): ops carrying one are comm+compute FUSED
#: in a single launch — the ring DMA rides inside the matmul kernel, so
#: there is no XLA-level collective interval left to measure. They are
#: attributed as compute (the MXU time is real) and totalled separately
#: (``Attribution.fused_collective_s``) so the overlap story stays
#: visible: XLA-level ``overlap_ratio`` measures the decomposed
#: transport's collective-permutes; the fused kernels' overlap is
#: structural (asserted by construction, not by interval intersection).
FUSED_COLLECTIVE_TOKENS = ("overlap_ag_matmul", "overlap_rs_matmul")


def _is_fused_collective(name: str, hlo_op: str, scope: str) -> bool:
    hay = f"{name} {hlo_op} {scope}".lower()
    return any(tok in hay for tok in FUSED_COLLECTIVE_TOKENS)


def _is_collective(hlo_op: str) -> bool:
    # Lazy import: the census op list is one tuple, and a module-level
    # import would drag the whole analysis package (flax, models.gpt)
    # into every `import dtc_tpu.obs` — this module's parser half is
    # deliberately light.
    from dtc_tpu.analysis.hlo import COLLECTIVE_OPS

    base = hlo_op.lower()
    return any(base.startswith(c) for c in COLLECTIVE_OPS)


def trace_process_names(events: list[dict[str, Any]]) -> dict[int, str]:
    """pid -> process name from the trace's metadata events."""
    out: dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            out[e["pid"]] = (e.get("args") or {}).get("name", "")
    return out


def device_pids(events: list[dict[str, Any]]) -> set[int]:
    """Processes whose events are DEVICE op executions (TPU device
    streams)."""
    return {
        p for p, n in trace_process_names(events).items()
        if "TPU" in n or "/device" in n.lower()
    }


def device_op_rows(trace: dict[str, Any]) -> list[OpRow]:
    """Typed device-op rows from one loaded trace.

    Selection: complete (``ph: X``) events on device pids, skipping the
    umbrella events (``jit_*`` module spans and bare step-number markers)
    that nest real ops. When the trace has NO device pid (the TFRT CPU
    backend), falls back to the XLA executor's op events — the ones
    carrying an ``hlo_op`` arg — so CPU captures attribute identically.
    """
    events = trace.get("traceEvents", [])
    dev = device_pids(events)
    rows: list[OpRow] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        if dev:
            if e.get("pid") not in dev:
                continue
            name = str(e.get("name", ""))
            if name.startswith("jit_") or name.isdigit():
                continue
        else:
            if "hlo_op" not in args:
                continue
            name = str(e.get("name", ""))
        hlo_op = str(args.get("hlo_op") or name)
        # TPU device events carry the HLO op_name metadata under one of
        # these arg keys depending on the tool version; "" means "join
        # against a compiled-HLO scope map instead".
        scope = str(
            args.get("tf_op") or args.get("long_name") or args.get("op_name")
            or ""
        )
        rows.append(OpRow(
            name=name,
            hlo_op=hlo_op,
            hlo_module=str(args.get("hlo_module") or ""),
            scope=scope,
            t0_s=float(e.get("ts", 0.0)) / 1e6,
            dur_s=float(e.get("dur", 0.0)) / 1e6,
            pid=int(e.get("pid", 0)),
            tid=int(e.get("tid", 0)),
            kind="collective" if _is_collective(hlo_op) else "compute",
        ))
    return rows


# ---------------------------------------------------------------------------
# scope recovery: optimized-HLO op_name metadata join

#: instruction name -> op_name metadata, one line per HLO instruction.
_HLO_OP_NAME = re.compile(
    r"%?([\w.\-]+) = [^\n]*?metadata=\{[^}]*op_name=\"([^\"]+)\""
)


def scope_map_from_hlo(hlo_text: str) -> dict[str, str]:
    """``instruction name -> op_name scope path`` from optimized-HLO text
    (``compiled.as_text()`` — the same artifact the graph auditor parses).

    The CPU backend's trace events name instructions without provenance
    (``dot.4``); this map recovers the full named-scope path XLA recorded
    at trace time (``jit(train_step)/.../fwd/stage/blocks/attn_qkv/...``).
    """
    return {m.group(1): m.group(2) for m in _HLO_OP_NAME.finditer(hlo_text)}


def scope_for(row: OpRow, scope_map: dict[str, str] | None) -> str:
    """Best-known scope path for one op row: the event's own scope arg,
    else the HLO metadata join (tolerating the executor's ``.clone`` /
    ``.remat`` suffix decorations), else ''."""
    if row.scope:
        return row.scope
    if not scope_map:
        return ""
    # Exact lookup first; then strip trailing ``.suffix`` decorations the
    # executor appends (``tanh.5.clone`` -> ``tanh.5``) one at a time.
    name = row.hlo_op
    while name:
        hit = scope_map.get(name)
        if hit:
            return hit
        base, dot, _ = name.rpartition(".")
        if not dot:
            return ""
        name = base
    return ""


# ---------------------------------------------------------------------------
# component / phase classification

#: model components the named-scope annotation establishes (ISSUE 8) plus
#: the flax module names that imply them when explicit scopes are absent
#: (older checkpoints, foreign traces). Matched right-to-left along the
#: scope path so the innermost component wins (ln inside head -> ln).
_COMPONENT_TOKENS: dict[str, str] = {
    "embed": "embed", "wte": "embed", "wpe": "embed",
    "attn_qkv": "attn_qkv", "q_proj": "attn_qkv", "k_proj": "attn_qkv",
    "v_proj": "attn_qkv",
    "attn_kernel": "attn_kernel",
    "attn_proj": "attn_proj", "out_proj": "attn_proj",
    "moe": "moe", "router": "moe",
    "mlp": "mlp", "fc1": "mlp", "fc2": "mlp",
    "ln": "ln", "ln_1": "ln", "ln_2": "ln", "ln_f": "ln",
    "head": "head", "lm_head": "head",
    "optimizer": "optimizer",
    "prefill": "prefill", "decode": "decode",
}

#: prefix-matched fallbacks for model glue no specific component claims:
#: the residual adds live at Block level, dropout is its own flax module.
_COMPONENT_PREFIXES: tuple[tuple[str, str], ...] = (
    ("Dropout", "dropout"),
    ("Block", "residual"),
    ("blocks", "residual"),
)

#: HLO op families that are pure data movement — layout copies, padding,
#: broadcasts XLA inserts with no source-op metadata. Attributed to an
#: explicit ``data_movement`` component (standard profiler practice: %copy
#: is a number you watch, not noise to hide in "unattributed").
_DATA_MOVEMENT_OPS = (
    "copy", "bitcast", "broadcast", "transpose", "reshape", "pad",
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "tuple", "get-tuple-element", "parameter", "constant", "iota",
    "convert",
)

#: components expected of every dense GPT train-step attribution — the
#: structural completeness set the bench gate checks against.
MODEL_COMPONENTS = (
    "embed", "attn_qkv", "attn_kernel", "attn_proj", "mlp", "moe", "ln",
    "head", "optimizer",
)


def _data_movement(hlo_op: str) -> bool:
    """True when the op — or every op fused into it — is pure data
    movement. CPU fusion names compound their constituents
    (``copy_bitcast_fusion``, ``dynamic-update-slice_convert_fusion`` —
    the bf16 weight-convert + layout traffic that dominates scope-less
    time on the flagship), so a fusion qualifies only if ALL of its
    underscore-joined parts are movement ops."""
    base = hlo_op.lower().split(".", 1)[0]
    if base in _DATA_MOVEMENT_OPS:
        return True
    if not base.endswith("_fusion"):
        return False
    parts = [p for p in base[: -len("_fusion")].split("_") if p]
    return bool(parts) and all(p in _DATA_MOVEMENT_OPS for p in parts)


def classify_scope(scope: str) -> tuple[str, str]:
    """``(component, phase)`` of one scope path; either may be ''.

    Phase: ``bwd`` when the path crosses an autodiff ``transpose(...)``
    wrapper, ``optimizer`` under the train step's optimizer scope, ``fwd``
    for the primal model pass (a ``jvp(...)`` wrapper or the explicit
    ``fwd`` scope), '' otherwise (input pipeline, infeed, glue).
    """
    if not scope:
        return "", ""
    segs = scope.split("/")
    component = ""
    for seg in reversed(segs):
        hit = _COMPONENT_TOKENS.get(seg)
        if hit:
            component = hit
            break
    if not component:
        for seg in reversed(segs):
            for prefix, comp in _COMPONENT_PREFIXES:
                if seg.startswith(prefix):
                    component = comp
                    break
            if component:
                break
    if not component and ("while" in segs or "body" in segs or "cond" in segs):
        # Inside the layer scan's while loop but owned by no model
        # component: the loop's own machinery — induction updates, carry
        # stacking writes, the trip-count predicate.
        component = "scan"
    if any(s.startswith("transpose(") for s in segs):
        phase = "bwd"
    elif "optimizer" in segs:
        phase = "optimizer"
    elif "fwd" in segs or any(s.startswith("jvp(") for s in segs):
        phase = "fwd"
    else:
        phase = ""
    # The attention kernel is the same dot/softmax work in both passes;
    # optimizer component implies optimizer phase even without the wrapper.
    if component == "optimizer" and not phase:
        phase = "optimizer"
    return component, phase


# ---------------------------------------------------------------------------
# attribution


def _interval_union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [intervals[0]]
    for lo, hi in intervals[1:]:
        if lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _overlap_s(
    collectives: list[tuple[float, float]], compute: list[tuple[float, float]]
) -> float:
    """Seconds of collective time overlapped by ANY compute interval."""
    total = 0.0
    union = _interval_union(compute)
    for lo, hi in collectives:
        for ulo, uhi in union:
            if uhi <= lo:
                continue
            if ulo >= hi:
                break
            total += min(hi, uhi) - max(lo, ulo)
    return total


@dataclass
class Attribution:
    """Rolled-up device-time attribution for one capture.

    All ``*_s`` totals are summed over the whole captured window; divide
    by the window's step count (the meta sidecar's ``steps``) for
    per-step numbers. ``unattributed_s`` is the device time whose scope
    recovered no known component — the share the structural gate bounds.
    """

    components: dict[str, float] = field(default_factory=dict)
    phases: dict[str, float] = field(default_factory=dict)
    total_s: float = 0.0
    compute_s: float = 0.0
    collective_s: float = 0.0
    overlap_s: float = 0.0
    #: device time inside the ISSUE 12 fused ring kernels (comm+compute
    #: in ONE launch — counted in ``compute_s`` too; their comm share is
    #: hidden by construction, not measurable as interval overlap).
    fused_collective_s: float = 0.0
    unattributed_s: float = 0.0
    n_ops: int = 0
    #: dot/fusion op names that recovered NO component — the "every
    #: dot-fusion attributed" structural gate's evidence list.
    unattributed_dot_fusions: list[str] = field(default_factory=list)
    #: busiest single device line's busy seconds (the device-time MFU
    #: denominator on one chip).
    busy_s: float = 0.0

    @property
    def attributed_share(self) -> float:
        """Fraction of device time attributed to a known component."""
        if self.total_s <= 0:
            return 0.0
        return 1.0 - self.unattributed_s / self.total_s

    @property
    def overlap_ratio(self) -> float:
        """Fraction of collective time hidden under concurrent compute
        (0.0 when the capture has no collectives)."""
        return self.overlap_s / self.collective_s if self.collective_s > 0 else 0.0

    def component_table(self, steps: int = 1) -> list[dict[str, Any]]:
        """Per-component rows (seconds + share), largest first, with the
        unattributed remainder as an explicit final row."""
        steps = max(int(steps), 1)
        rows = [
            {
                "component": c,
                "s_per_step": round(s / steps, 6),
                "share": round(s / self.total_s, 4) if self.total_s else 0.0,
            }
            for c, s in sorted(self.components.items(), key=lambda kv: -kv[1])
        ]
        if self.unattributed_s > 0 or not rows:
            rows.append({
                "component": "(unattributed)",
                "s_per_step": round(self.unattributed_s / steps, 6),
                "share": (
                    round(self.unattributed_s / self.total_s, 4)
                    if self.total_s else 0.0
                ),
            })
        return rows

    def device_mfu(
        self, step_flops: float | None, peak_flops: float | None,
        steps: int = 1,
    ) -> float | None:
        """Device-time MFU: model FLOPs per step over the busiest device
        line's busy time — utilization of the time the chip was actually
        executing, the denominator the roofline gaps in ROADMAP items
        2-4 are phrased in. None when FLOPs/peak are unknown (CPU)."""
        if not step_flops or not peak_flops or self.busy_s <= 0:
            return None
        return step_flops / (self.busy_s / max(int(steps), 1)) / peak_flops


def self_times(rows: list[OpRow]) -> list[float]:
    """Per-row SELF duration: each op's wall time minus the ops nested
    inside it on the same (pid, tid) line.

    Trace lines nest — a ``while`` loop op wraps every op its body
    executes, a ``call`` wraps the callee's thunks: raw rows are NOT
    additive. Attribution needs ADDITIVE numbers, so each event's immediate children are
    subtracted from it; parents of fully-traced children end up with
    just their own overhead."""
    order = sorted(range(len(rows)), key=lambda i: (
        rows[i].pid, rows[i].tid, rows[i].t0_s, -rows[i].dur_s
    ))
    self_s = [r.dur_s for r in rows]
    stack: list[int] = []  # indices of open ancestors on the current line
    line: tuple[int, int] | None = None
    for i in order:
        r = rows[i]
        if (r.pid, r.tid) != line:
            line = (r.pid, r.tid)
            stack = []
        while stack and (
            rows[stack[-1]].t0_s + rows[stack[-1]].dur_s <= r.t0_s
        ):
            stack.pop()
        if stack:
            self_s[stack[-1]] -= r.dur_s
        stack.append(i)
    return [max(s, 0.0) for s in self_s]


def attribute(
    rows: list[OpRow], scope_map: dict[str, str] | None = None
) -> Attribution:
    """Roll device-op SELF durations up to components/phases + ratios."""
    att = Attribution()
    per_line: dict[tuple[int, int], float] = {}
    coll_iv: list[tuple[float, float]] = []
    comp_iv: list[tuple[float, float]] = []
    selfs = self_times(rows)
    for r, dur in zip(rows, selfs):
        att.n_ops += 1
        att.total_s += dur
        per_line[(r.pid, r.tid)] = per_line.get((r.pid, r.tid), 0.0) + dur
        # Overlap detection uses the raw WALL intervals (a collective is
        # hidden when compute runs anywhere during it, children included).
        iv = (r.t0_s, r.t0_s + r.dur_s)
        scope = scope_for(r, scope_map)
        if r.kind == "collective":
            att.collective_s += dur
            coll_iv.append(iv)
        else:
            att.compute_s += dur
            comp_iv.append(iv)
            if _is_fused_collective(r.name, r.hlo_op, scope):
                att.fused_collective_s += dur
        component, phase = classify_scope(scope)
        if not component:
            if r.kind == "collective":
                # A collective outside any named scope is still a known
                # bucket — the census cross-check reads this row.
                component = "collectives"
            elif _data_movement(r.hlo_op):
                component = "data_movement"
        if component:
            att.components[component] = att.components.get(component, 0.0) + dur
        else:
            att.unattributed_s += dur
            # The structural gate's evidence: matmul-class work (dots,
            # convs, and the fusions built around them — CPU fusion names
            # are descriptive, TPU fusions carry tf_op scope instead)
            # must ALWAYS recover a model component. "convert" is dtype
            # traffic, not a convolution — strip it before matching.
            low = r.hlo_op.lower().replace("convert", "")
            if "dot" in low or "conv" in low:
                att.unattributed_dot_fusions.append(r.hlo_op)
        if phase:
            att.phases[phase] = att.phases.get(phase, 0.0) + dur
    att.overlap_s = _overlap_s(coll_iv, comp_iv)
    att.busy_s = max(per_line.values(), default=0.0)
    return att


def overlap_breakdown(
    rows: list[OpRow], scope_map: dict[str, str] | None = None,
    top: int = 3,
) -> list[dict[str, Any]]:
    """Per-collective overlap intervals: WHICH collective overlapped
    WHICH compute ops — the debugging view for tuning ring block sizes
    (a scalar overlap_ratio says a permute is exposed; this says what it
    failed to hide under). One dict per collective op, longest-exposed
    first:

    ``{op, scope, dur_s, overlapped_s, exposed_s, under: [(compute op,
    seconds), ...]}`` — ``under`` lists the ``top`` compute ops whose wall
    intervals covered this collective the most. Fused ring kernels
    (FUSED_COLLECTIVE_TOKENS) are reported as their own rows with
    ``fused: True`` and full structural overlap — their DMA has no
    XLA-level interval to intersect."""
    colls: list[tuple[OpRow, str]] = []
    comps: list[OpRow] = []
    fused: list[tuple[OpRow, str]] = []
    for r in rows:
        scope = scope_for(r, scope_map)
        if r.kind == "collective":
            colls.append((r, scope))
        else:
            comps.append(r)
            if _is_fused_collective(r.name, r.hlo_op, scope):
                fused.append((r, scope))
    out: list[dict[str, Any]] = []
    for r, scope in colls:
        lo, hi = r.t0_s, r.t0_s + r.dur_s
        under: dict[str, float] = {}
        covered: list[tuple[float, float]] = []
        for c in comps:
            clo, chi = c.t0_s, c.t0_s + c.dur_s
            ov = min(hi, chi) - max(lo, clo)
            if ov > 0:
                under[c.hlo_op] = under.get(c.hlo_op, 0.0) + ov
                covered.append((max(lo, clo), min(hi, chi)))
        overlapped = sum(b - a for a, b in _interval_union(covered))
        out.append({
            "op": r.hlo_op,
            "scope": scope,
            "dur_s": r.dur_s,
            "overlapped_s": overlapped,
            "exposed_s": max(r.dur_s - overlapped, 0.0),
            "under": sorted(under.items(), key=lambda kv: -kv[1])[:top],
            "fused": False,
        })
    out.sort(key=lambda d: -d["exposed_s"])
    for r, scope in fused:
        out.append({
            "op": r.hlo_op,
            "scope": scope,
            "dur_s": r.dur_s,
            "overlapped_s": r.dur_s,
            "exposed_s": 0.0,
            "under": [(r.hlo_op, r.dur_s)],
            "fused": True,
        })
    return out


def structural_gates(
    att: Attribution, *, max_unattributed_share: float = 0.10
) -> dict[str, Any]:
    """The bench gate (ISSUE 8e): structural checks that hold on any
    backend — every dot/fusion attributed to a component and the
    unattributed share bounded — rather than raw CPU timings, which swing
    ±30% on the CI host. Returns the verdicts plus the evidence."""
    return {
        "all_dot_fusions_attributed": not att.unattributed_dot_fusions,
        "unattributed_dot_fusions": sorted(set(att.unattributed_dot_fusions))[:8],
        "unattributed_share": round(1.0 - att.attributed_share, 4),
        "unattributed_share_ok": (
            att.total_s > 0
            and (1.0 - att.attributed_share) <= max_unattributed_share
        ),
    }


def census_crosscheck(
    att: Attribution, comm_estimate: dict[str, float] | None
) -> list[str]:
    """Warn-band cross-check against the static collective census
    (utils/metrics.comm_bytes_per_step, the graph auditor's rule-1
    estimate): a program the census says moves no bytes should not spend
    meaningful device time in collectives, and a comm-heavy program
    should show SOME collective time. Warnings, never failures — the
    census estimates bytes, the trace measures seconds, and only gross
    disagreement is signal."""
    warnings: list[str] = []
    est = float((comm_estimate or {}).get("total", 0.0) or 0.0)
    coll_share = att.collective_s / att.total_s if att.total_s else 0.0
    if est == 0.0 and coll_share > 0.05:
        warnings.append(
            f"census expects no collective traffic but {coll_share:.1%} of "
            "device time is collectives"
        )
    if est > 0.0 and att.total_s > 0 and att.collective_s == 0.0:
        warnings.append(
            f"census expects ~{est / 1e6:.1f} MB/step of collective traffic "
            "but the capture measured no collective device time"
        )
    return warnings


# ---------------------------------------------------------------------------
# merged host+device export


def trace_wall_anchor(
    trace: dict[str, Any], t_wall_start: float | None
) -> tuple[float, float]:
    """``(trace_t0_s, wall_t0_s)``: the trace-local timestamp that
    corresponds to the wall clock ``t_wall_start`` the capture recorded
    immediately before ``start_trace``.

    The trace contains the host-side ``start_trace`` call as an event on
    the python thread — its trace timestamp IS the moment the capture
    stamped. Falls back to the earliest event when the marker is absent
    (foreign traces), and to a zero anchor when no wall clock was
    recorded (the merged view is then trace-local, still monotonic)."""
    events = trace.get("traceEvents", [])
    marker = None
    earliest = None
    for e in events:
        if e.get("ph") != "X":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        if earliest is None or ts < earliest:
            earliest = ts
        if marker is None and str(e.get("name", "")).endswith("start_trace"):
            marker = ts
    t0 = (marker if marker is not None else earliest or 0.0) / 1e6
    return t0, (t_wall_start if t_wall_start is not None else 0.0)


def device_rows_to_events(
    rows: list[OpRow],
    *,
    anchor: tuple[float, float] = (0.0, 0.0),
    scope_map: dict[str, str] | None = None,
    proc: int = 0,
) -> list[dict[str, Any]]:
    """Device op rows as registry-style span events, wall-aligned via
    ``anchor`` — feed them to :func:`dtc_tpu.obs.trace.to_chrome_trace`
    together with the run's host events for the single merged Perfetto
    file (host spans and device ops on one clock)."""
    trace_t0, wall_t0 = anchor
    out = []
    for r in rows:
        component, phase = classify_scope(scope_for(r, scope_map))
        track = f"device.{r.pid}.{r.tid}"
        out.append({
            "etype": "span",
            "name": r.name,
            "cat": "device",
            "tid": track,
            "ph": "X",
            "t0": round(wall_t0 + (r.t0_s - trace_t0), 6),
            "dur_s": round(r.dur_s, 9),
            "proc": proc,
            "component": component or None,
            "phase": phase or None,
            "kind": r.kind,
        })
    return out


# ---------------------------------------------------------------------------
# capture windows

META_NAME = "devprof_meta.json"


def _write_meta(trace_dir: str, meta: dict[str, Any]) -> str:
    """Atomic meta sidecar next to the trace (PR 2 tmp+replace discipline)."""
    path = os.path.join(trace_dir, META_NAME)
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(trace_dir, exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=1)
    os.replace(tmp, path)
    return path


def load_meta(trace_dir: str) -> dict[str, Any] | None:
    try:
        with open(os.path.join(trace_dir, META_NAME)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def find_captures(base_dir: str) -> list[str]:
    """Capture artifact dirs under a run's ``obs/devprof/``, oldest first
    (a dir counts once it has a meta sidecar — half-written windows from
    a crashed run are skipped)."""
    if not os.path.isdir(base_dir):
        return []
    out = [
        d for d in sorted(glob.glob(os.path.join(base_dir, "*")))
        if os.path.isfile(os.path.join(d, META_NAME))
    ]
    return out


class CaptureWindow:
    """Context manager for one programmatic capture around code the
    caller drives (bench legs, the devprof smoke): brackets
    ``jax.profiler`` start/stop with wall anchors, samples the HBM
    watermark at close, writes the meta sidecar. Warn-and-disable on
    profiler failure — ``self.ok`` says whether a trace was captured."""

    def __init__(self, trace_dir: str, *, steps: int = 1, reason: str = "manual",
                 step_flops: float | None = None,
                 peak_flops: float | None = None,
                 comm_estimate: dict[str, float] | None = None):
        self.trace_dir = trace_dir
        self.steps = max(int(steps), 1)
        self.reason = reason
        self.step_flops = step_flops
        self.peak_flops = peak_flops
        self.comm_estimate = comm_estimate
        self.meta: dict[str, Any] | None = None
        self.ok = False

    def __enter__(self) -> "CaptureWindow":
        from dtc_tpu.obs.profiling import StepWindowProfiler

        self._prof = StepWindowProfiler(0, 1, self.trace_dir)
        self.t_wall_start = time.time()
        self._prof.step(0)  # start_trace (warn-and-disable on failure)
        self.ok = self._prof.failed is None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._prof.close()  # stop_trace if active; warn-and-disable
        t_wall_stop = time.time()
        self.ok = self.ok and self._prof.failed is None
        if not self.ok:
            return
        from dtc_tpu.obs.device import hbm_watermark

        self.meta = {
            "reason": self.reason,
            "steps": self.steps,
            "t_wall_start": round(self.t_wall_start, 6),
            "t_wall_stop": round(t_wall_stop, 6),
            "step_flops": self.step_flops,
            "peak_flops": self.peak_flops,
            "comm_estimate": self.comm_estimate,
            **hbm_watermark(),
        }
        try:
            _write_meta(self.trace_dir, self.meta)
        except OSError as e:
            print(f"[dtc_tpu] WARNING: devprof meta write failed ({e})")


def _safe_label(reason: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", reason)[:40] or "capture"


class DeviceProfiler:
    """Step-driven programmatic capture windows for the training runtime.

    Owned by :class:`~dtc_tpu.obs.telemetry.Telemetry`; the trainer never
    sees it directly. ``on_step`` is called once per step from
    ``Telemetry.on_step_start``; windows open on cadence
    (``every > 0``, every N steps) or on a pending ``request()`` (on
    demand, SLO breach, hung-step flag) and span ``n_steps`` steps. One
    window at a time; requests during a window (or while the legacy
    ``StepWindowProfiler`` window is active — ``busy``) defer to the next
    eligible step. A failed start/stop warns and disables future windows
    for the run (the telemetry-never-kills-the-run ethos, inherited from
    the hardened profiler this reuses).

    ``max_captures`` bounds windows per run: a capture makes its own step
    slow (``start_trace`` costs seconds on some hosts), which can itself
    trip the hung-step watchdog whose trigger would request the NEXT
    capture — without a cap a watchdog-armed run could alternate capture
    and flag forever.
    """

    def __init__(
        self,
        base_dir: str,
        *,
        registry: Any = None,
        every: int = 0,
        n_steps: int = 2,
        step_flops: float | None = None,
        peak_flops: float | None = None,
        comm_estimate: dict[str, float] | None = None,
        max_captures: int = 8,
    ):
        self.base_dir = base_dir
        self.registry = registry
        self.every = max(int(every), 0)
        self.n_steps = max(int(n_steps), 1)
        self.max_captures = max(int(max_captures), 1)
        # Optional run context for the meta sidecar (the trainer sets
        # these once; offline tools derive device-time MFU from them).
        self.step_flops = step_flops
        self.peak_flops = peak_flops
        self.comm_estimate = comm_estimate
        self._prof: Any = None
        self._stop_step = 0
        self._start_step = 0
        self._reason = ""
        self._dir = ""
        self._t_wall_start = 0.0
        self._pending: str | None = None
        self.disabled = False
        self.captures = 0
        self.last_artifact: str | None = None

    # -- triggers ----------------------------------------------------------
    def request(self, reason: str) -> bool:
        """Arm a capture window at the next step (on-demand / SLO breach /
        hung-step). No-op while disabled or already pending/active."""
        if (
            self.disabled
            or self.captures >= self.max_captures
            or self._pending is not None
            or self._prof is not None
        ):
            return False
        self._pending = reason
        return True

    # -- step hook ---------------------------------------------------------
    def on_step(self, step: int, *, busy: bool = False) -> None:
        if self._prof is not None:
            self._prof.step(step)  # stops the trace at the window's stop step
            if self._prof.failed:
                self._finalize(step, failed=True)
            elif step >= self._stop_step:
                self._finalize(step)
            return
        if self.disabled or busy or self.captures >= self.max_captures:
            return
        reason = self._pending
        if reason is None and self.every and step % self.every == 0:
            reason = "cadence"
        if reason is None:
            return
        self._pending = None
        self._start(step, reason)

    def _start(self, step: int, reason: str) -> None:
        from dtc_tpu.obs.profiling import StepWindowProfiler

        d = os.path.join(
            self.base_dir, f"step{step:06d}_{_safe_label(reason)}"
        )
        prof = StepWindowProfiler(step, step + self.n_steps, d)
        self._t_wall_start = time.time()
        prof.step(step)  # start_trace; warn-and-disable inside on failure
        if prof.failed:
            self.disabled = True
            return
        self._prof = prof
        self._start_step = step
        self._stop_step = step + self.n_steps
        self._reason = reason
        self._dir = d

    def _finalize(self, step: int, failed: bool = False) -> None:
        prof, self._prof = self._prof, None
        if failed or prof.failed:
            self.disabled = True
            return
        t_wall_stop = time.time()
        from dtc_tpu.obs.device import hbm_watermark

        watermark = hbm_watermark()
        meta = {
            "reason": self._reason,
            "step_start": self._start_step,
            "step_stop": step,
            "steps": step - self._start_step,
            "t_wall_start": round(self._t_wall_start, 6),
            "t_wall_stop": round(t_wall_stop, 6),
            "step_flops": self.step_flops,
            "peak_flops": self.peak_flops,
            "comm_estimate": self.comm_estimate,
            **watermark,
        }
        try:
            _write_meta(self._dir, meta)
        except OSError as e:
            print(f"[dtc_tpu] WARNING: devprof meta write failed ({e})")
        self.captures += 1
        self.last_artifact = self._dir
        if self.registry is not None:
            # Rides the JSONL shards AND the flight-recorder ring, so a
            # post-mortem dump names the capture artifact that covers it.
            self.registry.emit(
                "devprof", step=step, reason=self._reason, dir=self._dir,
                steps=meta["steps"], peak_hbm_bytes=watermark.get("peak_hbm_bytes"),
            )

    def close(self) -> None:
        """End-of-run: close a window still open (run ended mid-window)."""
        if self._prof is None:
            return
        self._prof.close()
        self._reason += ":truncated"
        self._finalize(self._stop_step)


# ---------------------------------------------------------------------------
# one-call report (shared by trace_report --device, the smoke, and bench)


def analyze_capture(
    trace_dir: str, *, hlo_text: str | None = None
) -> dict[str, Any] | None:
    """Parse + attribute one capture dir: returns ``{rows, attribution,
    meta, anchor, scope_map, trace_path}`` or None when the dir holds no
    trace (a capture that warn-disabled, or an empty CPU environment)."""
    path = find_trace_file(trace_dir)
    if path is None:
        return None
    trace = load_trace(path)
    meta = load_meta(trace_dir) or {}
    rows = device_op_rows(trace)
    scope_map = scope_map_from_hlo(hlo_text) if hlo_text else None
    att = attribute(rows, scope_map=scope_map)
    anchor = trace_wall_anchor(trace, meta.get("t_wall_start"))
    return {
        "trace_path": path,
        "rows": rows,
        "attribution": att,
        "meta": meta,
        "anchor": anchor,
        "scope_map": scope_map,
    }
