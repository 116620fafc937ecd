"""The trainer loop's spans on the device's clock, and the step's device time
by phase: from a traced run's ``.xplane.pb`` to thirteen per-layer metrics.

Where ``xtrace.py`` reads the trace through ``jax.profiler.ProfileData``, this
reader decodes the file itself (``google.protobuf``, the few XSpace messages
declared below), because two things it needs are not surfaced there:

- **the op-name path of a device op**. Each ``XLA Ops`` event points at an
  event-metadata record whose stat ``tf_op`` holds ``<path>:<op type>``, the
  path being the HLO instruction's ``op_name``: ``jit(train_step)/transpose(
  jvp(fwd))/GPT/stage/while/body/closed_call/checkpoint/rematted_computation/
  blocks/Block_0/mlp/mlp/fc1/dot_general`` (looked at by hand on a v5e trace,
  PR 26: 266 of the 739 instructions of device 0 carry one, and they hold
  98 % of the device's busy time; a fusion carries its root's). The
  program's ``jax.named_scope`` names, flax's module names and JAX's own
  ``jvp(`` / ``transpose(`` / ``rematted_computation`` are segments of it.
- **the program's own host spans**: ``train.<phase>`` events with a ``step``
  stat (``dtc_tpu/obs/stepclock.py``), found by name on whatever line of
  ``/host:CPU`` holds them, not by the thread's name.

**The window.** On the device that was busy longest a *period* runs from the
start of one execution of the step program on ``XLA Modules`` to the start of
the next (the step program is the module with the most device time). The
first period is dropped: starting the trace costs its step some 0.1 s. The
stall of ``stop_trace`` comes after the last execution and is in no period.
Every metric is a mean over the kept periods, per step.

**Classes.** A leaf op's self time goes to exactly one of ``recompute`` (path
has ``rematted_computation``), else ``bwd`` (``transpose(``), else
``optimizer`` (a segment ``optimizer`` or ``clip``), else ``fwd`` (a segment
``fwd``, or ``jvp(``), else ``collective`` (by the instruction's name), else
unnamed; and, independently, to ``head`` if the path has a segment ``head``.

**One clock.** The profiler lays the device's timeline beside the host's with
an error of a millisecond or two (PR 25's trace: the step program starts on
the device 1.3 ms *before* the host calls the runtime to enqueue it). Every
traced step bounds the shift ``d`` of the device's timeline from both sides:
its execution starts no earlier than the runtime's enqueue under its
``train.launch`` span (``tpu::System::Execute`` where the trace has it, else
the span's start), which gives ``d_lo``; it ends no later than the completion
notice inside its ``train.block`` span (the last ``ReadSyncFlag``, else
``tpu::System::Execute=>Done``, else the span's end), which gives ``d_hi``.
The reader shifts the device by the ``d`` of least magnitude in ``[d_lo,
d_hi]`` (0 where the trace is causal as it stands) and reads nothing for the
idle metrics where ``d_lo > d_hi``. Where ``d = d_lo`` the latency of a launch
is taken as zero, and whatever it really is lands in ``wait``; where the true
shift lies nearer ``d_hi``, ``wait`` is smaller and ``launch`` larger by up to
``d_hi - d_lo``. Both ends are kept in the record (``run.json``: ``trace.spans``).

**Idle.** Idle is what the union of the leaf ops leaves of a period. Inside
the step program's execution it is the program's own (bubbles, DMA waits).
Between one execution's end and the next one's start it is the host's:
shifted by ``d``, cut at the borders of the ``train.*`` spans and each piece
charged to the innermost span over it. The two small programs of the loop's
eager ``fold_in`` are ops, not idle.
"""

from __future__ import annotations

import functools
import gzip
from dataclasses import dataclass

from xtrace import (HOST, MODULES_LINE, OPS_LINE, SPAN, Row, base_op, charge, find_xplane,
                    is_collective, op_rows, subtract, total, union)

#: The runtime's own host events the alignment reads, by name.
ENQUEUE = "tpu::System::Execute"
NOTICES = ("ReadSyncFlag", "tpu::System::Execute=>Done")
#: Host phase -> the idle metric it is charged to.
CHARGE = {"block": "wait", "rng": "rng", "launch": "launch",
          "data_wait": "loop", "dispatch": "loop", "obs": "loop", "tail": "loop"}
PHASES = ("fwd", "bwd", "recompute", "optimizer")


@dataclass(frozen=True)
class Span(Row):
    """A row that also carries a device op's op-name path, or a host span's
    step number."""
    path: str = ""
    step: int | None = None


# ---- the file -------------------------------------------------------------


@functools.cache
def _xspace():
    """The message class of XSpace, declared as far as this reader goes
    (field numbers of tsl/profiler/protobuf/xplane.proto)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="dtc_bench_xplane.proto",
                                            package="dtc_bench_xplane", syntax="proto3")

    def message(name, *fields, oneof=None):
        m = fd.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, *rest in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=T.LABEL_REPEATED if "repeated" in rest else T.LABEL_OPTIONAL)
            for r in rest:
                if r.startswith("."):
                    f.type_name = r
                elif r == "oneof":
                    f.oneof_index = 0

    pkg = ".dtc_bench_xplane."
    message("XStat", ("metadata_id", 1, T.TYPE_INT64),
            ("double_value", 2, T.TYPE_DOUBLE, "oneof"), ("uint64_value", 3, T.TYPE_UINT64, "oneof"),
            ("int64_value", 4, T.TYPE_INT64, "oneof"), ("str_value", 5, T.TYPE_STRING, "oneof"),
            ("bytes_value", 6, T.TYPE_BYTES, "oneof"), ("ref_value", 7, T.TYPE_UINT64, "oneof"),
            oneof="value")
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64), ("offset_ps", 2, T.TYPE_INT64),
            ("duration_ps", 3, T.TYPE_INT64), ("stats", 4, T.TYPE_MESSAGE, pkg + "XStat", "repeated"))
    message("XLine", ("id", 1, T.TYPE_INT64), ("name", 2, T.TYPE_STRING),
            ("timestamp_ns", 3, T.TYPE_INT64),
            ("events", 4, T.TYPE_MESSAGE, pkg + "XEvent", "repeated"))
    message("XEventMetadata", ("id", 1, T.TYPE_INT64), ("name", 2, T.TYPE_STRING),
            ("stats", 5, T.TYPE_MESSAGE, pkg + "XStat", "repeated"))
    message("XStatMetadata", ("id", 1, T.TYPE_INT64), ("name", 2, T.TYPE_STRING))
    # a proto map is a repeated entry message with key = 1, value = 2
    message("EventMetadataEntry", ("key", 1, T.TYPE_INT64),
            ("value", 2, T.TYPE_MESSAGE, pkg + "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, T.TYPE_INT64),
            ("value", 2, T.TYPE_MESSAGE, pkg + "XStatMetadata"))
    message("XPlane", ("id", 1, T.TYPE_INT64), ("name", 2, T.TYPE_STRING),
            ("lines", 3, T.TYPE_MESSAGE, pkg + "XLine", "repeated"),
            ("event_metadata", 4, T.TYPE_MESSAGE, pkg + "EventMetadataEntry", "repeated"),
            ("stat_metadata", 5, T.TYPE_MESSAGE, pkg + "StatMetadataEntry", "repeated"))
    message("XSpace", ("planes", 1, T.TYPE_MESSAGE, pkg + "XPlane", "repeated"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("dtc_bench_xplane.XSpace"))


def _value(stat, stat_names: dict[int, str]):
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    v = getattr(stat, kind)
    return stat_names.get(v, "") if kind == "ref_value" else v


def read_xspace(path: str):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = _xspace()()
        space.ParseFromString(f.read())
    return space


def rows_from_xspace(space) -> list[Span]:
    """Device rows (``XLA Ops`` with their paths, ``XLA Modules``) of every
    ``/device:TPU:<n>`` plane; of ``/host:CPU`` the ``train.*`` spans with
    their step and the runtime's enqueue / completion events. Seconds on the
    trace's clock, as ``xtrace.rows_from_xplane`` gives them."""
    rows: list[Span] = []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1].split()[0])
        elif plane.name == "/host:CPU":
            dev = HOST
        else:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        paths: dict[int, str] = {}
        if dev != HOST:
            for key, md in meta.items():
                for s in md.stats:
                    if stat_names.get(s.metadata_id) == "tf_op":
                        paths[key] = str(_value(s, stat_names)).rsplit(":", 1)[0]
        for line in plane.lines:
            if dev != HOST and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                name = meta[e.metadata_id].name if e.metadata_id in meta else ""
                step = None
                if dev == HOST:
                    if name.startswith(SPAN):
                        for s in e.stats:
                            if stat_names.get(s.metadata_id) == "step":
                                step = int(_value(s, stat_names))
                    elif name != ENQUEUE and name not in NOTICES:
                        continue
                rows.append(Span(dev, line.name, name,
                                 line.timestamp_ns * 1e-9 + e.offset_ps * 1e-12,
                                 e.duration_ps * 1e-12,
                                 path=paths.get(e.metadata_id, ""), step=step))
    return rows


# ---- classes --------------------------------------------------------------


def classify(path: str, name: str = "") -> str | None:
    """The one phase of a device op, from its op-name path (and, for an op
    without one, whether its instruction is a collective)."""
    segments = path.split("/")
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "bwd"
    if "optimizer" in segments or "clip" in segments:
        return "optimizer"
    if "fwd" in segments or "jvp(" in path:
        return "fwd"
    if name and is_collective(name):
        return "collective"
    return None


def in_head(path: str) -> bool:
    return "head" in path.split("/")


def self_times(ops: list[Row]) -> list[float]:
    """Each op's duration less what other ops of the list cover inside it
    (events of one line nest or are disjoint). In ``ops``' order."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].t0, -ops[i].dur))
    out = [r.dur for r in ops]
    stack: list[int] = []
    for i in order:
        r = ops[i]
        while stack and ops[stack[-1]].t0 + ops[stack[-1]].dur <= r.t0:
            stack.pop()
        if stack:
            out[stack[-1]] -= r.dur
        stack.append(i)
    return out


# ---- one clock ------------------------------------------------------------


def least_shift(lo: float, hi: float) -> float | None:
    """The shift of least magnitude in [lo, hi]; None where the interval is
    empty (no shift makes the trace causal)."""
    if lo > hi:
        return None
    return 0.0 if lo <= 0.0 <= hi else (lo if lo > 0.0 else hi)


def _starts_inside(rows: list[Row], name: str, span: Row) -> list[float]:
    return [r.t0 for r in rows if r.name == name and span.t0 <= r.t0 <= span.t0 + span.dur]


def shift_bounds(execs: list[Row], host: list[Span]) -> tuple[float, float] | None:
    """(d_lo, d_hi) from every traced step, see the module docstring; None
    where the trace does not hold one ``train.launch`` span per execution
    (the parent of PR 26 writes none, and an unsynced loop's last launches
    have not run when the trace stops)."""
    launches = sorted((h for h in host if h.name == SPAN + "launch"), key=lambda h: h.t0)
    if not launches or len(launches) != len(execs):
        return None
    blocks = {h.step: h for h in host if h.name == SPAN + "block"}
    lo, hi = float("-inf"), float("inf")
    for ex, launch in zip(execs, launches):
        lo = max(lo, min(_starts_inside(host, ENQUEUE, launch), default=launch.t0) - ex.t0)
        block = blocks.get(launch.step)
        if block is None:
            continue
        seen = next(filter(None, (_starts_inside(host, n, block) for n in NOTICES)), None)
        notice = max(seen) if seen else block.t0 + block.dur
        hi = min(hi, notice - (ex.t0 + ex.dur))
    return lo, hi


# ---- rows to metrics ------------------------------------------------------


def reduce_rows(rows: list[Span]) -> dict | None:
    """The metrics (ms a step, %, means over the kept periods) and what
    stood behind them; None where the trace holds fewer than three
    executions of a step program on a device (no period to keep)."""
    device_rows = [r for r in rows if r.device != HOST]
    ops_by_dev: dict[int, list[Span]] = {}
    for r in op_rows(device_rows):
        ops_by_dev.setdefault(r.device, []).append(r)
    if not ops_by_dev:
        return None
    busiest = max(ops_by_dev, key=lambda d: total(union([(r.t0, r.t0 + r.dur) for r in ops_by_dev[d]])))
    ops = sorted(ops_by_dev[busiest], key=lambda r: r.t0)
    modules = [r for r in device_rows if r.device == busiest and r.line == MODULES_LINE]
    by_program: dict[str, float] = {}
    for r in modules:
        by_program[r.name] = by_program.get(r.name, 0.0) + r.dur
    if not by_program:
        return None
    program = max(by_program, key=by_program.get)
    execs = sorted((r for r in modules if r.name == program), key=lambda r: r.t0)
    if len(execs) < 3:
        return None
    kept = list(zip(execs[1:-1], execs[2:]))  # (this execution, the next): one period
    n = len(kept)
    busy_u = union([(r.t0, r.t0 + r.dur) for r in ops])
    selfs = self_times(ops)

    phase_s = dict.fromkeys((*PHASES, "collective", "unnamed", "head"), 0.0)
    unnamed: dict[str, float] = {}
    busy_s = in_step_s = 0.0
    gaps: list[tuple[float, float]] = []
    for ex, nxt in kept:
        end = ex.t0 + ex.dur
        busy_s += (nxt.t0 - ex.t0) - total(subtract([(ex.t0, nxt.t0)], busy_u))
        in_step_s += total(subtract([(ex.t0, end)], busy_u))
        gaps += subtract([(end, nxt.t0)], busy_u)
        for r, s in zip(ops, selfs):
            if not ex.t0 <= r.t0 < nxt.t0:
                continue
            kind = classify(r.path, r.name) or "unnamed"
            phase_s[kind] += s
            if kind == "unnamed":
                unnamed[base_op(r.name)] = unnamed.get(base_op(r.name), 0.0) + s
            if in_head(r.path):
                phase_s["head"] += s
    between_s = total(gaps)
    op_s = sum(phase_s[k] for k in (*PHASES, "collective", "unnamed"))

    ms = lambda s: 1e3 * s / n  # noqa: E731
    out = {
        "idle_in_step_ms": ms(in_step_s),
        "fwd_ms": ms(phase_s["fwd"]), "bwd_ms": ms(phase_s["bwd"]),
        "recompute_ms": ms(phase_s["recompute"]), "optimizer_ms": ms(phase_s["optimizer"]),
        "head_ce_ms": ms(phase_s["head"]),
        "scope_named_pct": 100.0 * (op_s - phase_s["unnamed"]) / op_s if op_s else None,
    }
    detail = {
        "device": busiest, "program": program, "periods": n,
        "period_ms": ms(kept[-1][1].t0 - kept[0][0].t0), "busy_ms": ms(busy_s),
        "idle_between_ms": ms(between_s), "collective_ms": ms(phase_s["collective"]),
        "unnamed_ms": ms(phase_s["unnamed"]),
        "unnamed_ops_ms": {k: ms(v) for k, v in sorted(unnamed.items(), key=lambda kv: -kv[1])[:6]},
    }

    host = [r for r in rows if r.device == HOST]
    bounds = shift_bounds(execs, host)
    if bounds is not None:
        d = least_shift(*bounds)
        detail["shift_lo_ms"], detail["shift_hi_ms"] = 1e3 * bounds[0], 1e3 * bounds[1]
        if d is not None:
            spans = [h for h in host if h.name.startswith(SPAN)]
            charged = charge([(a + d, b + d) for a, b in gaps], spans)
            idle = dict.fromkeys(("wait", "rng", "launch", "loop"), 0.0)
            for phase, s in charged.items():
                if phase:
                    idle[CHARGE.get(phase, "loop")] += s
            out.update({f"idle_{k}_ms": ms(v) for k, v in idle.items()})
            out["idle_named_pct"] = (100.0 * (between_s - charged.get("", 0.0)) / between_s
                                     if between_s else None)
            out["host_device_skew_ms"] = 1e3 * abs(d)
            detail["idle_by_span_ms"] = {k or "(no span)": ms(v) for k, v in sorted(charged.items())}
    return {"metrics": out, "detail": detail}


def read(run: dict) -> dict | None:
    """``reduce_rows`` of a traced run's newest profile, made once a run and
    kept beside the rest of the reduced trace (``run.json``: ``trace.spans``)."""
    if not run.get("profile_dir"):
        return None
    trace = run.get("trace")
    if isinstance(trace, dict) and "spans" in trace:
        return trace["spans"]
    path = find_xplane(run["profile_dir"])
    got = reduce_rows(rows_from_xspace(read_xspace(path))) if path else None
    if isinstance(trace, dict):
        trace["spans"] = got
    return got


def metric(run: dict, name: str):
    """What ``benchmark/metrics/<name>.train.py`` returns: the number, or
    None where the trace or the program has nothing to read it from."""
    got = read(run)
    return None if got is None else got["metrics"].get(name)
