"""From a profiler trace to device rows, and from rows to the trace metrics.

The trainer's own profiler window (``obs.profile_start/stop``) writes an
``.xplane.pb`` under ``<output_dir>/profile/plugins/profile/<time>/``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. What a v5e trace
looks like (looked at by hand, PR 25): one plane ``/device:TPU:<n>`` per
chip, whose line ``XLA Ops`` holds one event per executed HLO instruction
(a ``while`` and the ops of its body both appear, nested in time), line
``XLA Modules`` one event per executed program and line ``Steps`` one per
step. Host threads are planes ``/host:CPU``.

The reduction is backend-free and works on plain rows, so a test can feed it
rows written by hand, or the rows of a Chrome-trace capture
(``rows_from_chrome``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from dataclasses import dataclass

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: HLO instructions that only contain other instructions' time.
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast")
#: A Mosaic (Pallas) kernel's event is a custom call with this target. Its
#: instruction takes the name of the innermost scope around the call: the
#: flash kernels are ``%attn_kernel.13`` / ``.14`` on one chip (the
#: ``jax.named_scope("attn_kernel")`` of ``models/gpt.py``) and
#: ``%shard_map.290`` / ``.291`` on four (``ops/attention._flash_per_shard``
#: wraps them). So which names are which kernel is the cell's to say: its
#: workload file's ``kernel_names``.
MOSAIC = 'custom_call_target="tpu_custom_call"'


@dataclass(frozen=True)
class Row:
    device: int
    line: str
    name: str
    t0: float   # seconds, trace clock
    dur: float  # seconds


def profiled_steps(run: dict) -> range:
    """The timed steps (numbered from 1) that the trainer's profiler window
    touched in a traced run: starting the trace costs its first step some
    0.1 s and writing it out stalls its last for seconds (13 s on four
    chips). Readers of the host's clock leave these steps out, so that a
    traced run reads what an untraced one does. Empty for an untraced run."""
    if not run.get("profile_dir"):
        return range(0)
    start, stop = run["workload"]["trace_steps"]
    return range(int(start), int(stop) + 1)


def find_xplane(profile_dir: str) -> str | None:
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


HOST = -1  # Row.device of a host thread's events
SPAN = "train."  # the program's own host spans (``dtc_tpu/obs/stepclock.py``)


def rows_from_xplane(path: str) -> list[Row]:
    """Device rows of every ``/device:TPU:<n>`` plane, and as rows of device
    HOST the interpreter's threads of plane ``/host:CPU``: every line that
    holds one of the program's ``train.*`` spans or a Python frame, whatever
    the line is called (the thread's name is the interpreter's: ``python``,
    or ``python3`` under the driver). They are on the same clock and name
    what the host did in an idle gap."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1].split()[0])
        elif plane.name == "/host:CPU":
            dev = HOST
        else:
            continue
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]
            if dev == HOST and not any(n.startswith((SPAN, "$", "PjitFunction(")) for n, _, _ in events):
                continue
            name = f"python#{i}" if dev == HOST else line.name
            rows += [Row(dev, name, n, t0, dur) for n, t0, dur in events]
    return rows


def main_thread(host: list[Row]) -> list[Row]:
    """The host thread that launches the programs: the Python line with the
    most ``PjitFunction(...)`` frames (the prefetch worker has none)."""
    launches: dict[str, int] = {}
    for r in host:
        if r.name.startswith("PjitFunction("):
            launches[r.line] = launches.get(r.line, 0) + 1
    if not launches:
        return host
    line = max(launches, key=launches.get)
    return [r for r in host if r.line == line]


def charge(pieces: list[tuple[float, float]], spans: list[Row]) -> dict[str, float]:
    """Seconds of ``pieces`` (host clock) under each phase: every piece is
    cut at the spans' borders and goes to the innermost (shortest) span over
    it, under its phase's name; to ``""`` where no span is."""
    out: dict[str, float] = {}
    for lo, hi in pieces:
        cuts = sorted({lo, hi, *(t for s in spans for t in (s.t0, s.t0 + s.dur) if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            over = [s for s in spans if s.t0 <= mid < s.t0 + s.dur]
            name = min(over, key=lambda s: s.dur).name[len(SPAN):] if over else ""
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def host_name_for(gap: tuple[float, float], host: list[Row]) -> str | None:
    """What the host was doing in ``gap``: the program's ``train.<phase>``
    span that holds most of it (the innermost span at each instant, as
    ``spans.py`` charges the ``idle_*`` rows); where no such span is over it,
    of the Python frames that cover at least half of it the two shortest,
    inner < outer. The gap is taken where the device's clock puts it:
    ``spans.py`` first shifts the device's timeline by the skew it finds
    (0.04 to 1.3 ms), so a gap that reaches over a span's border by less
    than that may carry the neighbouring phase's name here. The ``idle_*``
    rows are the measure; these names point at the longest gaps."""
    lo, hi = gap
    held = charge([gap], [r for r in host if r.name.startswith(SPAN)])
    held.pop("", None)
    if held:
        return SPAN + max(held, key=held.get)
    over = [r for r in host if min(hi, r.t0 + r.dur) - max(lo, r.t0) >= 0.5 * (hi - lo)]
    over.sort(key=lambda r: r.dur)
    return " < ".join(r.name.lstrip("$") for r in over[:2]) or None


def rows_from_chrome(path: str) -> list[Row]:
    """Rows of a Chrome-trace JSON capture (the program's
    ``tests/fixtures/devprof_capture`` is one, taken on the CPU): complete
    events that carry an ``hlo_op``, or that sit on a device process."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    dev_pids = {e["pid"] for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and ("TPU" in (e.get("args") or {}).get("name", "")
                     or "/device" in (e.get("args") or {}).get("name", "").lower())}
    rows = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        if (e.get("pid") in dev_pids) if dev_pids else ("hlo_op" in args):
            name = str(args.get("hlo_op") or e.get("name", ""))
            if name.startswith("jit_") or name.isdigit():
                continue
            rows.append(Row(int(e.get("pid", 0)), OPS_LINE, name,
                            float(e.get("ts", 0.0)) * 1e-6, float(e.get("dur", 0.0)) * 1e-6))
    return rows


def base_op(name: str) -> str:
    """``%all-gather-start.12`` -> ``all-gather-start``."""
    name = name.lstrip("%").split(" ", 1)[0]
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def is_container(name: str) -> bool:
    return base_op(name) in CONTAINERS


def is_collective(name: str) -> bool:
    return base_op(name).startswith(COLLECTIVES)


def is_kernel(name: str) -> bool:
    return MOSAIC in name


def kernel_seconds(trace: dict, names: list[str]) -> float | None:
    """Summed device time of the kernels whose instruction name starts with
    one of ``names``; None where the trace holds none."""
    found = [s for k, s in (trace.get("kernels_s") or {}).items() if k.startswith(tuple(names))]
    return sum(found) if found else None


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def op_rows(rows: list[Row]) -> list[Row]:
    """Leaf device operations: the ops line without the containers."""
    return [r for r in rows if r.line == OPS_LINE and not is_container(r.name)]


def reduce_rows(rows: list[Row]) -> dict:
    """Busy, idle, exposed-collective and kernel time from device rows.

    ``window_s`` is the traced window as the devices saw it: first device
    operation's start to the last one's end, over all devices. ``busy_s`` is
    the union of operation intervals, averaged over the devices.
    An idle gap is named by the host's Python frame that covers it, where
    the trace has host rows, and else by the operations on either side.
    """
    host = main_thread([r for r in rows if r.device == HOST])
    ops = op_rows([r for r in rows if r.device != HOST])
    if not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "per_device_busy_s": [], "steps": 0,
                "collective_exposed_s": None, "kernels_s": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    t_lo = min(r.t0 for r in ops)
    t_hi = max(r.t0 + r.dur for r in ops)
    devices = sorted({r.device for r in ops})
    busy, exposed, gaps = [], [], []
    kernels: dict[str, float] = {}  # instruction name -> seconds, on the device that spent longest in it
    for d in devices:
        mine = [r for r in ops if r.device == d]
        all_u = union([(r.t0, r.t0 + r.dur) for r in mine])
        coll_u = union([(r.t0, r.t0 + r.dur) for r in mine if is_collective(r.name)])
        comp_u = union([(r.t0, r.t0 + r.dur) for r in mine if not is_collective(r.name)])
        busy.append(total(all_u))
        exposed.append(total(subtract(coll_u, comp_u)) if coll_u else None)
        here: dict[str, float] = {}
        for r in mine:
            if is_kernel(r.name):
                here[base_op(r.name)] = here.get(base_op(r.name), 0.0) + r.dur
        for k, s in here.items():
            kernels[k] = max(kernels.get(k, 0.0), s)
        gaps += [(b_lo - a_hi, a_hi, d) for (_, a_hi), (b_lo, _) in zip(all_u, all_u[1:])]
    busiest = max(range(len(devices)), key=lambda i: busy[i])
    by_op: dict[str, float] = {}
    for r in ops:
        if r.device == devices[busiest]:
            key = base_op(r.name)
            by_op[key] = by_op.get(key, 0.0) + r.dur
    gaps.sort(reverse=True)
    named = []
    for dur, at, d in gaps[:10]:  # only the longest are named: naming scans the rows
        name = host_name_for((at, at + dur), host)
        if name is None:
            mine = [r for r in ops if r.device == d]
            before = max((r for r in mine if r.t0 + r.dur <= at + 1e-12),
                         key=lambda r: r.t0 + r.dur, default=None)
            after = min((r for r in mine if r.t0 >= at + dur - 1e-12),
                        key=lambda r: r.t0, default=None)
            name = (f"dev{d}: after {base_op(before.name) if before else '?'}, "
                    f"before {base_op(after.name) if after else '?'}")
        named.append([name, dur])
    modules = [r for r in rows if r.line == MODULES_LINE and r.device == devices[busiest]]
    longest = max((r.dur for r in modules), default=0.0)
    steps = sum(1 for r in modules if r.dur > 0.5 * longest)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": t_hi - t_lo,
        "per_device_busy_s": busy,
        "steps": steps,
        "collective_exposed_s": exposed[busiest],
        "kernels_s": kernels,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": named,
        },
    }


def reduce_profile(profile_dir: str) -> dict | None:
    path = find_xplane(profile_dir)
    if path is None:
        return None
    return reduce_rows(rows_from_xplane(path))


def summarize(path: str, top: int = 25) -> str:
    """A trace by hand: planes, lines, event counts and the longest names."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            by: dict[str, list[float]] = {}
            for e in events:
                by.setdefault(e.name, []).append(e.duration_ns * 1e-9)
            span = (max(e.start_ns + e.duration_ns for e in events) - min(e.start_ns for e in events)) * 1e-9
            out.append(f"  line {line.name!r}: {len(events)} events over {span:.4f}s")
            for name, ds in sorted(by.items(), key=lambda kv: -sum(kv[1]))[:top]:
                out.append(f"    {sum(ds):.6f}s x{len(ds)} {name[:140]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    p = sys.argv[1]
    print(summarize(p if p.endswith(".pb") else find_xplane(p)))
