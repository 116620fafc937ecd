"""Step-time breakdown, profiler annotations and compile tracking.

Answers the question the paper's comparison hangs on but the seed repo
could not: *where does a step's wall-clock go?* ``StepClock`` is the one
timing source of the trainer loop. Every phase passes through
``StepClock.phase(name)``, which takes the phase's start stamp and its
duration (two ``perf_counter`` reads) and brackets it with a
``jax.profiler.TraceAnnotation("train.<name>", step=<step>)``; ``begin``
opens a ``StepTraceAnnotation("train", step_num=<step>)`` around the
iteration. With no profiler session open none is built
(``TraceAnnotation.is_enabled()``); inside the trainer's profiler window they
are the host spans that ``benchmark/spans.py`` lays against the device's
timeline.

Phases of one pass through the loop, in order (inner ones indented):

- ``data_wait``  — blocked on ``next(data_it)``: host tokenization /
                   packing that prefetch failed to hide, plus the
                   host->device transfer for synchronous feeding;
- ``dispatch``   — everything from the batch to the launched step:
    - ``rng``    — the eager ``jax.random.fold_in(key, step)``: two small
                   device programs launched from Python every step
                   (2.2-2.4 ms under the profiler, PERF.md);
    - ``launch`` — the ``train_step(...)`` call returning: executable
                   launch in steady state (~1 ms; a spike = recompile);
- ``block``      — blocked on the device finishing (only when the
                   trainer syncs per step, else absent);
- ``obs``        — the telemetry's own work: the body of
                   ``Telemetry.on_step_end`` after the clock has closed,
                   and ``on_step_start``'s profiler / devprof calls;
- ``tail``       — the rest of the loop body, up to the next
                   ``on_step_start``.

``end()`` closes the clocked part of the step. Its fields: ``data_wait_s``,
``dispatch_s``, ``block_s``, ``step_time_s`` (begin -> end), ``other_s``
(step time outside those three phases), ``rng_s`` and ``launch_s`` (inside
``dispatch_s``), and ``between_s``: the previous step's ``end()`` to this
``begin()``, the loop's time in no step (``obs`` + ``tail``), so that
``step_time_s + between_s`` summed over steps is the wall-clock between
their ends. That sum is the step's **period**: what differences of the
trainer's stamps see.

**Before the first step** the same clock runs from the first line of
``trainer.train``: ``startup(name)`` opens ``train.startup.<name>`` (a
``phase`` like any other, so a ``TraceAnnotation`` under a profiler
session) and ends the start-up phase before it; the first ``begin`` ends
the last. Every second from ``t_enter`` to the first timed step therefore
lies under a named phase; ``startup_phases`` keeps each one's start (seconds
after ``t_enter``) and seconds, and ``Telemetry`` writes them as the
``startup`` event once a sink exists.

**What the host was doing** (``watch_host()``; ``Telemetry`` turns it on at
the first timed step under ``obs.enabled``): ``end()`` also reads, over the
step's period (the previous ``end()`` to this one; the first step's from its
``begin``), ``cpu_s`` (``time.process_time``: the process's CPU seconds, all
threads), ``gc_s`` / ``gc_n`` (seconds inside the garbage collector and
collections by generation, from ``gc.callbacks``: cost only when a
collection runs) and ``host_late_s``: the largest lateness of a canary, a
daemon thread that sleeps ``CANARY_SLEEP_S`` and records how much later than
that it woke. A host, VM or process that stood still for 3 s reads
``host_late_s`` about 3; a device, driver or tunnel that answered 3 s late
with the host alive reads about 0. ``SlowSteps`` is the detector that reads
them: a period over ``SLOW_FACTOR`` x the trailing median of the periods
before it (``TrailingMedian``, the one outlier rule, which
``resilience.watchdog.StepWatchdog`` shares at its own factor).

Compile time comes from ``jax.monitoring``: ``CompileWatcher`` listens to
``/jax/core/compile/backend_compile_duration`` (``compile_or_get_cached``:
the XLA compile or the load from the persistent cache — what ``drain()``
returns, as ever), ``.../jaxpr_trace_duration`` and
``.../jaxpr_to_mlir_module_duration`` (the host's Python and MLIR work) and
the persistent cache's ``cache_hits``, ``cache_misses`` and
``cache_retrieval_time_sec``. The first observation window is the run's
compile cost; any later one is a **recompile** (a shape or donation mismatch
silently eating a step) and is flagged.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Callable

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: The further ``jax.monitoring`` streams (JAX 0.9.0) and the total each
#: feeds. ``backend_compile_duration`` wraps ``compile_or_get_cached``, so
#: it CONTAINS ``cache_retrieval_time_sec`` (a hit's load) and is the XLA
#: compile itself only on a miss.
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
#: ``cache_misses`` fires where an entry is WRITTEN (``compilation_cache.
#: put_executable_and_time``): a program compiled anew that also passed
#: ``jax_persistent_cache_min_compile_time_secs``. The small programs under
#: that threshold are compiled on every run and appear in neither stream;
#: ``CompileWatcher.totals["compiled_anew"]`` counts them (requests less hits).
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

# One process-wide listener, registered lazily on first CompileWatcher
# activation: jax.monitoring has no per-listener deregistration, so the
# listener is permanent and routes to whichever watcher is active (or
# drops the event when none is).
_active_watcher: "CompileWatcher | None" = None
_listener_registered = False


def _on_event_duration(name: str, duration: float, **kw) -> None:
    w = _active_watcher
    if w is None:
        return
    if name == _BACKEND_COMPILE:
        w._on_program(duration, str(kw.get("fun_name", "?")))
    elif name in _DURATIONS:
        w.totals[_DURATIONS[name]] += duration


def _on_event(name: str, **kw) -> None:
    w = _active_watcher
    if w is not None and name in _EVENTS:
        w.totals[_EVENTS[name]] += 1


def _ensure_listener() -> None:
    global _listener_registered
    if _listener_registered:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)
    _listener_registered = True


class CompileWatcher:
    """Accumulates XLA backend-compile seconds while active.

    ``drain()`` returns and resets the window — callers attribute the
    drained seconds to whatever phase just ran (init, warmup, step N).
    ``totals`` is never reset: seconds and counts of every stream since the
    watcher was made, which the ``startup`` event reads when the timed loop
    begins. ``programs`` keeps, until ``take_programs()``, one record per
    backend-compile request: (``perf_counter`` at its end, seconds, the
    listener's ``fun_name``, whether the persistent cache served it).
    """

    def __init__(self):
        self._drained = (0.0, 0)   # backend-compile seconds and count at the last drain
        self.totals: dict[str, float] = dict.fromkeys(
            ("backend_compile_s", "trace_s", "lower_s", "cache_retrieval_s"), 0.0
        ) | dict.fromkeys(
            ("compiles", "cache_hits", "cache_misses", "compiled_anew"), 0
        )
        self.programs: list[tuple[float, float, str, bool]] | None = []
        self._hits_seen = 0

    def _on_program(self, seconds: float, fun_name: str) -> None:
        # a hit's ``cache_hits`` event fires inside the request, before the
        # request's own duration event: a count that moved marks this one
        hit = self.totals["cache_hits"] > self._hits_seen
        self._hits_seen = self.totals["cache_hits"]
        self.totals["backend_compile_s"] += seconds
        self.totals["compiles"] += 1
        self.totals["compiled_anew"] += not hit
        if self.programs is not None:
            self.programs.append((time.perf_counter(), seconds, fun_name, hit))

    def take_programs(self) -> list[tuple[float, float, str, bool]]:
        """The start-up's per-program records; none are kept from here on."""
        out, self.programs = self.programs or [], None
        return out

    def activate(self) -> "CompileWatcher":
        global _active_watcher
        _ensure_listener()
        _active_watcher = self
        return self

    def deactivate(self) -> None:
        global _active_watcher
        if _active_watcher is self:
            _active_watcher = None

    def drain(self) -> tuple[float, int]:
        s0, c0 = self._drained
        self._drained = s, c = self.totals["backend_compile_s"], self.totals["compiles"]
        return (s - s0 if c > c0 else 0.0), c - c0

class TrailingMedian:
    """The one outlier rule: a value over ``factor`` x the median of the up
    to ``window`` values before it, once ``min_samples`` are held. An outlier
    is NOT added to the history — one hang must not license the next. The
    slow-step detector uses it at ``SLOW_FACTOR``,
    ``resilience.watchdog.StepWatchdog`` at its configured ``factor``."""

    def __init__(self, factor: float, min_samples: int = 5, window: int = 64):
        self.factor = float(factor)
        self.min_samples = max(int(min_samples), 1)
        self.values: deque[float] = deque(maxlen=window)

    def median(self) -> float | None:
        if len(self.values) < self.min_samples:
            return None
        vals = sorted(self.values)
        return vals[len(vals) // 2]

    def observe(self, value: float) -> float | None:
        """The median ``value`` stands out from (and stays out of), else
        None with ``value`` added to the history."""
        med = self.median()
        if med is not None and med > 0 and value > self.factor * med:
            return med
        self.values.append(value)
        return None


#: A step stands out when its period passes this multiple of the trailing
#: median period. A constant: 17.7 ms on the shortest cell's 176.7 ms step.
SLOW_FACTOR = 1.1
#: The parts of a period the detector names; they add up to it (``other``
#: here also holds what of ``dispatch`` is neither ``rng`` nor ``launch``).
SLOW_PHASES = ("data_wait", "rng", "launch", "block", "other", "between")


class SlowSteps:
    """Names every step that stands out from the steps before it.

    ``observe(breakdown)`` takes ``StepClock.end()``'s fields and returns
    None, or the ``slow_step`` event's fields: the period, the trailing
    median it was judged by, each phase's seconds and its excess over that
    phase's OWN trailing median, ``held_by`` (the phase with the largest
    excess) and ``owner``: ``host_frozen`` where the canary's lateness
    covers at least half the excess, ``gc`` where the collector's seconds
    do, else ``device_or_driver`` when ``block`` held it, else
    ``host_phase``. A slow step's phases stay out of every history.
    """

    def __init__(self):
        self.period = TrailingMedian(SLOW_FACTOR)
        self.phases = {p: TrailingMedian(SLOW_FACTOR) for p in SLOW_PHASES}

    @staticmethod
    def parts(b: dict) -> dict[str, float]:
        named = {p: b[f"{p}_s"] for p in SLOW_PHASES if p != "other"}
        named["other"] = max(
            b["step_time_s"] + b["between_s"] - sum(named.values()), 0.0
        )
        return named

    def observe(self, b: dict) -> dict | None:
        parts = self.parts(b)
        period = b["step_time_s"] + b["between_s"]
        med = self.period.observe(period)
        if med is None:
            for p, v in parts.items():
                self.phases[p].values.append(v)
            return None
        excess = period - med
        over = {p: v - (self.phases[p].median() or 0.0) for p, v in parts.items()}
        held_by = max(over, key=over.get)
        late, gc_s = b.get("host_late_s", 0.0), b.get("gc_s", 0.0)
        if late >= 0.5 * excess:
            owner = "host_frozen"
        elif gc_s >= 0.5 * excess:
            owner = "gc"
        else:
            owner = "device_or_driver" if held_by == "block" else "host_phase"
        out = {
            "period_s": round(period, 6), "median_s": round(med, 6),
            "excess_s": round(excess, 6), "held_by": held_by, "owner": owner,
        }
        for p in SLOW_PHASES:
            out[f"{p}_s"] = round(parts[p], 6)
            out[f"{p}_excess_s"] = round(over[p], 6)
        for k in ("cpu_s", "gc_s", "gc_n", "host_late_s"):
            if k in b:
                out[k] = b[k]
        return out


#: The canary's sleep. 20 ms: fifty wake-ups a second, microseconds each.
CANARY_SLEEP_S = 0.02


class Canary(threading.Thread):
    """Says whether the HOST stood still: sleeps ``interval`` again and
    again and keeps the largest lateness of a wake-up. ``take(now)`` returns
    that maximum since the take before — a sleep still overdue at ``now``
    counts up to ``now``, and only its remainder goes to the next period —
    and resets it. It wakes late too while another thread holds the GIL
    without a break (a C call that keeps it; pure Python is switched out
    every 5 ms), so a phase that sleeps or waits on the device reads 0 and
    one stuck in such a call does not. ``clock`` and ``sleep`` are
    injectable for tests."""

    def __init__(self, interval: float = CANARY_SLEEP_S, *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], object] | None = None):
        super().__init__(name="dtc-host-canary", daemon=True)
        self.interval = interval
        self._clock = clock
        self._halt = threading.Event()
        self._sleep = sleep if sleep is not None else self._halt.wait
        self._max = 0.0
        self._due = clock() + interval   # when the sleep in hand should end
        self._taken = self._due

    def run(self) -> None:
        while not self._halt.is_set():
            self._due = self._clock() + self.interval
            self._sleep(self.interval)
            self._note(self._clock())

    def _note(self, now: float) -> None:
        late = now - max(self._due, self._taken)
        if late > self._max:
            self._max = late

    def take(self, now: float) -> float:
        self._note(now)
        late, self._max, self._taken = self._max, 0.0, now
        return max(late, 0.0)

    def stop(self) -> None:
        self._halt.set()
        if self.is_alive():
            self.join(timeout=2.0)


class StepClock:
    """Phase timer and profiler annotations for the trainer loop.

    Usage in the trainer loop (``Telemetry`` makes the first and last
    three calls)::

        clock.close()                  # the pass before: tail, `train` group
        clock.begin(step)
        with clock.phase("data_wait"): x, y = next(data_it)
        with clock.phase("dispatch"):
            with clock.phase("rng"):    k = fold_in(key, step)
            with clock.phase("launch"): state, loss = train_step(...)
        with clock.phase("block"):     jax.block_until_ready(loss)
        breakdown = clock.end()        # dict of *_s floats
        with clock.phase("obs"): ...   # telemetry's own work
        clock.tail()                   # open until the next close()
    """

    #: Top-level phases of the clocked step: ``other_s`` is what they leave.
    PHASES = ("data_wait", "dispatch", "block")
    #: Phases nested inside ``dispatch``: reported, never summed with it.
    NESTED = ("rng", "launch")

    def __init__(self):
        #: ``perf_counter`` at construction: the first line of ``train()``.
        self.t_enter = time.perf_counter()
        #: ``{name: (first start after t_enter, seconds)}`` of the start-up
        #: phases, and the first timed step's ``begin`` after ``t_enter``;
        #: both None until that ``begin``. ``startup_segments`` keeps every
        #: stretch (name, start after t_enter, seconds) in order: a name
        #: opened twice (``data``) has two.
        self.startup_phases: dict[str, tuple[float, float]] | None = None
        self.startup_total_s: float | None = None
        self.startup_segments: list[tuple[str, float, float]] = []
        self._startup: _Phase | None = None
        self._canary: Canary | None = None
        self._gc = [0.0, 0, 0, 0, 0.0]   # seconds, collections by generation, start
        self._cpu0 = 0.0
        self.step: int | None = None
        #: ``perf_counter`` stamps of the step in hand: its ``begin`` and
        #: each phase's first entry. The JSONL spans are made from these.
        self.t0: float | None = None
        self.starts: dict[str, float] = {}
        self._acc: dict[str, float] = {}
        self._t_end: float | None = None
        self._between = 0.0
        self._group = None   # the open StepTraceAnnotation
        self._tail: _Phase | None = None

    def startup(self, name: str) -> None:
        """Open the start-up phase ``name`` (``train.startup.<name>``) and
        end the one before it: every second up to the first ``begin`` lies
        under one. A name used again adds to its seconds."""
        if self.startup_phases is not None:
            return   # the timed loop has begun: no start-up any more
        name = f"startup.{name}"
        if self._startup is not None and self._startup._name == name:
            return
        self._end_startup()
        self._startup = self.phase(name)
        self._startup.__enter__()

    def _end_startup(self) -> None:
        ph = self._startup
        if ph is not None:
            ph.__exit__(None, None, None)
            self.startup_segments.append((
                ph._name[len("startup."):], ph._t0 - self.t_enter,
                time.perf_counter() - ph._t0,
            ))
            self._startup = None

    def freeze_startup(self, now: float) -> None:
        """The start-up ends at ``now``: at the first ``begin``, or where a
        run closes without one."""
        if self.startup_phases is not None:
            return
        self._end_startup()
        self.startup_phases = {}
        for name, a, sec in self.startup_segments:
            first, total = self.startup_phases.get(name, (a, 0.0))
            self.startup_phases[name] = (first, total + sec)
        self.startup_total_s = now - self.t_enter

    def watch_host(self) -> None:
        """From here on ``end()`` also reports what the host was doing over
        the step's period: ``cpu_s``, ``gc_s``, ``gc_n``, ``host_late_s``.
        Starts the canary thread and hooks ``gc.callbacks``, once;
        ``shutdown()`` undoes both."""
        if self._canary is not None:
            return
        self._canary = Canary()
        self._canary.start()
        gc.callbacks.append(self._on_gc)
        self._cpu0 = time.process_time()

    def _on_gc(self, phase: str, info: dict) -> None:
        g = self._gc
        if phase == "start":
            g[4] = time.perf_counter()
        else:
            g[0] += time.perf_counter() - g[4]
            g[1 + min(int(info.get("generation", 2)), 2)] += 1

    def shutdown(self) -> None:
        """End of the run: the last pass closed, the canary joined, the
        collector's hook removed, a start-up phase a raise left open ended."""
        self.close()
        self._end_startup()
        if self._canary is not None:
            self._canary.stop()
            self._canary = None
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass

    def begin(self, step: int) -> None:
        self.close()
        if self.startup_phases is None:
            # the first timed step: the start-up ends here, microseconds
            # before this step's own t0
            self.freeze_startup(time.perf_counter())
        self.step = step
        self._acc = dict.fromkeys(self.PHASES + self.NESTED, 0.0)
        self.starts = {}
        if TraceAnnotation.is_enabled():
            self._group = StepTraceAnnotation("train", step_num=step)
            self._group.__enter__()
        self.t0 = time.perf_counter()
        self._between = 0.0 if self._t_end is None else self.t0 - self._t_end

    def phase(self, name: str) -> "_Phase":
        return _Phase(self, name)

    def end(self) -> dict[str, float]:
        now = time.perf_counter()
        total = now - (self.t0 if self.t0 is not None else now)
        self._t_end = now
        out = {f"{p}_s": round(v, 6) for p, v in self._acc.items()}
        out["step_time_s"] = round(total, 6)
        # Whatever the three phases don't cover is host-side loop overhead
        # (chaos hooks, the watchdog's arm) — worth seeing when it grows.
        top = sum(self._acc.get(p, 0.0) for p in self.PHASES)
        out["other_s"] = round(max(0.0, total - top), 6)
        out["between_s"] = round(self._between, 6)
        if self._canary is not None:
            # Over the period that ends here (the reads reset what they read).
            cpu, g = time.process_time(), self._gc
            out["cpu_s"] = round(cpu - self._cpu0, 6)
            out["gc_s"] = round(g[0], 6)
            out["gc_n"] = g[1:4]
            out["host_late_s"] = round(self._canary.take(now), 6)
            self._cpu0, g[0], g[1], g[2], g[3] = cpu, 0.0, 0, 0, 0
        return out

    def tail(self) -> None:
        """Open ``train.tail``: the loop body after the telemetry's work.
        It has no ``with`` block to end it (the body leaves by ``continue``
        and ``break`` too); the next ``close()`` does."""
        self._tail = self.phase("tail")
        self._tail.__enter__()

    def close(self) -> None:
        """End the pass through the loop: the tail and the ``train`` group.
        Called before the profiler's own start / stop of the next step, so
        a window that stops there holds its last iteration whole."""
        if self._tail is not None:
            self._tail.__exit__(None, None, None)
            self._tail = None
        if self._group is not None:
            self._group.__exit__(None, None, None)
            self._group = None


class _Phase:
    __slots__ = ("_clock", "_name", "_t0", "_span")

    def __init__(self, clock: StepClock, name: str):
        self._clock = clock
        self._name = name

    def __enter__(self) -> "_Phase":
        clock = self._clock
        # Built only where a profiler session would record it.
        # step 0: before the run's first begin() (the trainer counts from 1)
        self._span = None
        if TraceAnnotation.is_enabled():
            self._span = TraceAnnotation(f"train.{self._name}", step=clock.step or 0)
            self._span.__enter__()
        self._t0 = time.perf_counter()
        clock.starts.setdefault(self._name, self._t0)
        return self

    def __exit__(self, *exc) -> None:
        acc = self._clock._acc
        acc[self._name] = acc.get(self._name, 0.0) + (time.perf_counter() - self._t0)
        if self._span is not None:
            self._span.__exit__(*exc)
