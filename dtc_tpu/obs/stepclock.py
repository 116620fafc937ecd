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
their ends.

Compile time comes from ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` stream — the actual XLA
backend-compile seconds, not a timing heuristic. The first observation
window is the run's compile cost; any later one is a **recompile** (a
shape or donation mismatch silently eating a step) and is flagged.
"""

from __future__ import annotations

import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# One process-wide listener, registered lazily on first CompileWatcher
# activation: jax.monitoring has no per-listener deregistration, so the
# listener is permanent and routes to whichever watcher is active (or
# drops the event when none is).
_active_watcher: "CompileWatcher | None" = None
_listener_registered = False


def _on_event_duration(name: str, duration: float, **kw) -> None:
    w = _active_watcher
    if w is not None and name == _BACKEND_COMPILE:
        w._seconds += duration
        w._count += 1


def _ensure_listener() -> None:
    global _listener_registered
    if _listener_registered:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    _listener_registered = True


class CompileWatcher:
    """Accumulates XLA backend-compile seconds while active.

    ``drain()`` returns and resets the window — callers attribute the
    drained seconds to whatever phase just ran (init, warmup, step N).
    """

    def __init__(self):
        self._seconds = 0.0
        self._count = 0

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
        s, c = self._seconds, self._count
        self._seconds, self._count = 0.0, 0
        return s, c


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

    def begin(self, step: int) -> None:
        self.close()
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
