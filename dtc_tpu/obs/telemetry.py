"""Telemetry facade: the hook surface runtimes emit through.

One object owns the registry, sinks, step clock, compile watcher, memory
sampler, and profiler; the trainer (and any future runtime — pipeline,
generate) talks to it through a small hook interface::

    tele.on_run_start(...)
    tele.on_step_start(step)
    with tele.clock.phase("data_wait"): ...      # and dispatch > rng, launch; block
    tele.on_step_end(step, elapsed_s=...)
    tele.on_eval(step, loss, duration_s)
    tele.on_run_end(...); tele.close()

so new runtimes get the full event stream by registering hooks instead of
threading CSV loggers and profilers through their loops.

Event stream schema (JSONL, one shard per process — see README
"Observability"):

- ``run_start``    — config fingerprint: strategy, mesh, batch, devices;
- ``compile``      — first XLA backend-compile window (init + warmup),
                     labeled step 0;
- ``startup``      — once, when the timed loop begins: ``t_enter`` (the
                     ``perf_counter`` stamp of ``train()``'s first line),
                     ``phases`` (``{name: [start after t_enter, seconds]}``
                     of the ``train.startup.*`` phases: distributed, mesh,
                     model, state, restore, step_build, data, obs,
                     eval_setup, warmup_first, warmup_rest), ``named_s`` /
                     ``total_s`` (entry to the first timed step's begin),
                     and what ``jax.monitoring`` counted meanwhile:
                     ``trace_s``, ``lower_s``, ``backend_compile_s`` (which
                     holds ``cache_retrieval_s``), ``compiles``,
                     ``cache_hits``, ``cache_misses`` (programs compiled
                     anew: requests less hits), ``cache_writes`` (those of
                     them the persistent cache kept: a step program here on
                     a warm run says a key moved), ``compiled`` (``{program:
                     [count, seconds]}`` of the programs compiled anew);
- ``recompile``    — any later compile: something changed shape mid-run;
- ``step``         — per-step breakdown: ``data_wait_s``, ``dispatch_s``
                     (of which ``rng_s`` the eager key fold and
                     ``launch_s`` the step call), ``block_s``, ``other_s``,
                     ``step_time_s``, ``between_s`` (the loop's time
                     between the step before's end and this one's begin),
                     cumulative ``elapsed_s``; under ``obs.enabled`` also
                     what the host was doing over the step's period
                     (``between_s + step_time_s``): ``cpu_s``, ``gc_s``,
                     ``gc_n`` (collections by generation), ``host_late_s``
                     (the canary's largest lateness: ``obs/stepclock.py``);
- ``slow_step``    — a step whose period passed 1.1 x the trailing median of
                     the up to 64 before it: ``period_s``, ``median_s``,
                     ``excess_s``, every phase's seconds and ``*_excess_s``
                     over its own trailing median (data_wait, rng, launch,
                     block, other, between), ``held_by``, ``cpu_s``,
                     ``gc_s``, ``host_late_s``, ``owner`` (``host_frozen`` /
                     ``gc`` / ``device_or_driver`` / ``host_phase``) and
                     ``after`` (the boundary work the loop did since the
                     step before: log_boundary, eval, checkpoint); the lead
                     prints one line, the tracer gets a ``slow_step`` span
                     of the excess on the ``train.phase`` track;
- ``train_row``    — the CSV-schema row (step, elapsed_time, loss), also
                     bridged to ``log.csv`` by the CSV sink;
- ``window``       — log-boundary throughput: avg step time, tokens/s, MFU;
- ``eval``         — held-out eval loss (bridged to ``eval_log.csv``);
- ``memory``       — per-device HBM sample (``null`` stats on CPU);
- ``hosts``        — cross-host reduction + straggler flags (lead only);
- ``chaos``        — a fault-injection hook fired (``kind``: data_error,
                     data_stall, ckpt_corrupt, nan_loss, sigterm);
- ``anomaly``      — the guard detected an unhealthy loss window
                     (``reason``, chosen ``action``);
- ``recovery``     — a recovery action executed (``action``: stream_retry,
                     ckpt_fallback, rollback, tolerate, abort);
- ``hung_step``    — watchdog flag: a step exceeded the configured multiple
                     of the trailing median step time (``runtime: serve``
                     when the serving scheduler's watchdog flagged it);
- ``run_summary``  — totals: tokens/s, MFU, peak HBM, compile/recompile
                     counts, est. comm bytes per step, the ``startup``
                     event's totals (``startup``), ``slow_steps`` and
                     ``slow_step_excess_s`` by owner;
- ``counter``      — online goodput gauge sample (``name``: goodput_pct,
                     ``value``) — the Perfetto counter track (ISSUE 16);
                     the offline truth is the goodput ledger
                     (``dtc_tpu/obs/goodput.py``) over this same stream.

Serving events (``dtc_tpu/serve/`` — SLO accounting rides the same
registry: ``serve_queue_wait_s`` / ``serve_ttft_s`` /
``serve_ms_per_token`` histograms plus shed/evict/expire/reject/retry
counters land in the run summary):

- ``serve_request``    — one terminal record per request: state, token
                         count, typed error name, queue-wait/TTFT/
                         ms-per-token, eviction/retry counts — the
                         no-silent-drops contract (every submitted rid
                         emits exactly one);
- ``serve_admit``      — request entered a slot (slot, resident tokens,
                         shared-prefix length);
- ``serve_evict``      — eviction for recovery/pressure (``reason``:
                         cache_pressure, admission_pressure, preempted,
                         corruption) — the request re-queues and resumes
                         bit-exactly via re-prefill;
- ``serve_reject``     — typed admission rejection (queue_full /
                         too_large), raised to the submitter;
- ``serve_corruption`` — a completed KV page failed its integrity
                         checksum (chaos or real) before eviction healed
                         it.
"""

from __future__ import annotations

import json
import os
from typing import Any

import time

from dtc_tpu.obs.aggregate import reduce_shards, shard_path
from dtc_tpu.obs.device import peak_hbm_bytes, sample_memory
from dtc_tpu.obs.devprof import DeviceProfiler
from dtc_tpu.obs.profiling import StepWindowProfiler
from dtc_tpu.obs.goodput import OnlineGoodput
from dtc_tpu.obs.registry import CsvSink, JsonlSink, MetricsRegistry
from dtc_tpu.obs.slo import SloMonitor
from dtc_tpu.obs.stepclock import CompileWatcher, SlowSteps, StepClock
from dtc_tpu.obs.trace import FlightRecorder, Tracer


class Telemetry:
    def __init__(
        self,
        obs_cfg: Any = None,
        *,
        output_dir: str = "",
        lead: bool = True,
        process_index: int = 0,
        profiler: StepWindowProfiler | None = None,
        append: bool = False,
        slo_cfg: Any = None,
        clock: StepClock | None = None,
        compiles: CompileWatcher | None = None,
    ):
        from dtc_tpu.config.schema import ObsConfig

        self.cfg = obs_cfg if obs_cfg is not None else ObsConfig()
        self.output_dir = output_dir
        self.lead = lead
        self.registry = MetricsRegistry(process_index=process_index)
        # The trainer makes both at the first line of train(), long before
        # a sink may open: the start-up's phases and compiles are kept in
        # memory and written as the `startup` event once the loop begins.
        self.clock = clock if clock is not None else StepClock()
        self.compiles = compiles if compiles is not None else CompileWatcher()
        self.slow = SlowSteps()
        self._slow_excess: dict[str, float] = {}
        self._since_last_step: list[str] = []   # boundary work, for `after`
        self._startup: dict[str, Any] | None = None
        self.profiler = profiler or StepWindowProfiler(0, 0, "")
        self.obs_dir = ""
        # False until the first timed step completes: compile seconds
        # observed before then are startup cost (init, warmup, the first
        # step's own trace), never flagged as recompiles.
        self._steady = False
        self._jsonl: JsonlSink | None = None
        self._closed = False
        # Even with JSONL off, anomaly dumps need a destination.
        self._dump_dir = (
            self.cfg.dir or (os.path.join(output_dir, "obs") if output_dir else "")
        )
        if self.cfg.enabled and self.cfg.jsonl and output_dir:
            self.obs_dir = self.cfg.dir or os.path.join(output_dir, "obs")
            try:
                self._jsonl = self.registry.add_sink(
                    JsonlSink(
                        shard_path(self.obs_dir, process_index), append=append,
                        max_bytes=int(self.cfg.rotate_mb * 1e6),
                    )
                )
            except OSError as e:  # unwritable dir: observe-or-ignore, never crash
                print(f"[dtc_tpu] WARNING: telemetry JSONL disabled ({e})")
                self.obs_dir = ""
        # Spans + flight recorder (ISSUE 7). Span events ride the same
        # sinks; the recorder is a bounded in-memory ring dumped only at
        # anomaly time, so "always on" costs one deque append per event.
        self.tracer = Tracer(
            self.registry, enabled=self.cfg.enabled and self.cfg.trace,
            clock=time.time, tid="train",
        )
        # The step clock stamps on perf_counter; spans live on the tracer's
        # clock. One offset, taken once, carries stamps from one to the other.
        self._clock_offset = self.tracer.clock() - time.perf_counter()
        self.recorder: FlightRecorder | None = None
        if self.cfg.enabled and self.cfg.flight_recorder > 0:
            self.recorder = self.registry.add_sink(
                FlightRecorder(self.cfg.flight_recorder)
            )
        # Online SLO monitor (training objectives); None with all off.
        self.slo = SloMonitor.from_config(
            slo_cfg, self.registry, runtime="train"
        )
        self._slo_check_every = getattr(slo_cfg, "check_every", 8) or 8
        # Online goodput gauge (ISSUE 16): fed per-class seconds from
        # the step breakdown / the serving scheduler's iteration clock —
        # timestamps already taken, never a new sync. The serving engine
        # shares this instance (its registry IS this registry).
        self.goodput: OnlineGoodput | None = None
        if self.cfg.enabled and getattr(self.cfg, "goodput", True):
            self.goodput = OnlineGoodput(
                self.registry,
                counter_every=getattr(self.cfg, "goodput_counter_every", 8),
            )
        # Device-time observatory (ISSUE 8): programmatic jax.profiler
        # capture windows — cadence via obs.devprof_every, on-demand via
        # request_device_profile(), plus the SLO-breach / hung-step
        # triggers below when obs.devprof_on_trigger. Artifacts land under
        # <obs dir>/devprof/ with meta sidecars; `trace_report.py --device`
        # is the offline leg. Inert (no windows) until a cadence/trigger
        # fires; warn-and-disable on profiler failure.
        # Constructed whenever obs is on (inert until a cadence, trigger,
        # or explicit request fires): gating on the knobs would silently
        # kill the documented on-demand path for devprof_every=0 +
        # devprof_on_trigger=false configs.
        self.devprof: DeviceProfiler | None = None
        if self.cfg.enabled and self._dump_dir:
            self.devprof = DeviceProfiler(
                os.path.join(self._dump_dir, "devprof"),
                registry=self.registry,
                every=self.cfg.devprof_every,
                n_steps=self.cfg.devprof_steps,
            )
        self.compiles.activate()

    # -- construction -----------------------------------------------------
    @classmethod
    def for_training(
        cls, train_cfg, *, lead: bool, process_index: int, resumed: bool = False,
        clock: StepClock | None = None, compiles: CompileWatcher | None = None,
    ) -> "Telemetry":
        """Build the trainer's telemetry from its config block.

        The profiler window comes from ``ObsConfig`` when set there,
        falling back to the legacy top-level ``profile_start/profile_stop``
        fields so existing configs keep capturing traces. ``resumed`` runs
        APPEND to the existing JSONL shard — truncating would destroy the
        preempted run's events, the prefix crash-survival just preserved.
        (The CSV bridges intentionally keep the legacy rewrite-from-
        restored-step semantics documented in config.schema: log.csv is a
        derived artifact; the JSONL stream is the durable history.)
        """
        obs = train_cfg.obs
        start, stop = obs.profile_start, obs.profile_stop
        if stop <= start:
            start, stop = train_cfg.profile_start, train_cfg.profile_stop
        profiler = StepWindowProfiler(
            start, stop, os.path.join(train_cfg.output_dir, "profile")
        )
        return cls(
            obs,
            output_dir=train_cfg.output_dir,
            lead=lead,
            process_index=process_index,
            profiler=profiler,
            append=resumed,
            slo_cfg=getattr(train_cfg, "slo", None),
            clock=clock,
            compiles=compiles,
        )

    @classmethod
    def for_serving(
        cls, output_dir: str, *, obs_cfg: Any = None, process_index: int = 0
    ) -> "Telemetry":
        """Telemetry for a :class:`dtc_tpu.serve.engine.ServingEngine`:
        the engine emits its SLO instruments and ``serve_*`` events
        through ``.registry``, landing in the same JSONL shard layout the
        trainer uses (``<output_dir>/obs/events.r<k>.jsonl``) so the
        multi-host reducer and existing tooling read serving runs
        unchanged."""
        return cls(
            obs_cfg, output_dir=output_dir, lead=process_index == 0,
            process_index=process_index,
        )

    def add_csv(self, path: str, fieldnames: tuple[str, ...], etype: str) -> CsvSink:
        """Attach a back-compat CSV bridge (log.csv / eval_log.csv). CSV
        output is NOT gated on ``obs.enabled`` — it predates the subsystem
        and the committed artifacts depend on it."""
        return self.registry.add_sink(CsvSink(path, fieldnames, etype))

    # -- hooks ------------------------------------------------------------
    def on_run_start(self, **meta: Any) -> None:
        self.registry.emit("run_start", **meta)

    def on_step_start(self, step: int) -> None:
        # The pass before ends here, ahead of the profiler's own start /
        # stop, so that a window holds its last iteration's spans whole.
        self.clock.close()
        with self.clock.phase("obs"):
            self.profiler.step(step)
            if self.devprof is not None:
                # One jax profiler session per process: defer devprof windows
                # while the legacy configured window is mid-capture.
                self.devprof.on_step(step, busy=self.profiler._active)
        if self._startup is None:
            # The timed loop begins: the start-up's one event, and from here
            # the canary and the collector's hook (never a second time).
            self._emit_startup(loop_began=True)
            if self.cfg.enabled:
                self.clock.watch_host()
        self.clock.begin(step)

    def on_step_end(self, step: int, *, elapsed_s: float) -> dict:
        """Close the step's clock, fold in any compile the step triggered,
        emit the ``step`` event, and sample memory on cadence. Everything
        after the clock has closed is the ``obs`` phase; the rest of the
        loop body, up to the next ``on_step_start``, is ``tail``."""
        breakdown = self.clock.end()
        with self.clock.phase("obs"):
            self._after_step(step, breakdown, elapsed_s)
        self.clock.tail()
        return breakdown

    def _after_step(self, step: int, breakdown: dict, elapsed_s: float) -> None:
        self.registry.histogram("step_time_s").observe(breakdown["step_time_s"])
        compile_s, n = self.compiles.drain()
        extra: dict[str, Any] = {}
        if n:
            extra["compile_s"] = round(compile_s, 4)
            if self._steady:
                # Same executable should serve every step — a mid-run
                # compile means a shape/dtype/donation change slipped in.
                self.registry.counter("recompiles").inc(n)
                extra["recompile"] = True
                self.registry.emit(
                    "recompile", step=step, compile_s=round(compile_s, 4), count=n
                )
            else:
                # First timed step: with warmup_steps=0 the train step's
                # cold compile lands HERE, not in record_startup_compile —
                # still startup cost, never a recompile.
                self._note_startup_compile(compile_s, n)
        self._steady = True
        self.registry.emit(
            "step",
            step=step,
            elapsed_s=round(elapsed_s, 6),
            **breakdown,
            **extra,
        )
        slow = self.slow.observe(breakdown)
        if slow is not None:
            self._on_slow_step(step, slow, breakdown)
        self._since_last_step.clear()
        # Step/phase spans from the clock's own start stamps (no extra
        # clock read, no sync), moved onto the tracer's clock by the one
        # offset taken at construction.
        if self.tracer.enabled:
            off = self._clock_offset
            t0 = self.clock.t0 + off
            t1 = t0 + breakdown["step_time_s"]
            self.tracer.emit_span(
                "step", t0, t1, cat="train", tid="train", step=step
            )
            for ph in self.clock.PHASES:
                d = breakdown[f"{ph}_s"]
                if d > 0:
                    p0 = self.clock.starts[ph] + off
                    self.tracer.emit_span(
                        ph, p0, p0 + d, cat="train",
                        tid="train.phase", step=step,
                    )
            if n:
                # A step that compiled did so inside its launch: the span
                # starts where that phase did. (What compiled BEFORE the
                # loop has its spans from `_emit_startup`, each program's
                # own stamps; the two never cover the same seconds.)
                c0 = self.clock.starts.get("launch", self.clock.t0) + off
                recompile = bool(extra.get("recompile"))
                self.tracer.emit_span(
                    "compile", c0, c0 + compile_s, cat="train",
                    tid="train.compile", step=step if recompile else 0,
                    recompile=recompile,
                )
        if self.goodput is not None:
            # Per-class attribution from numbers the clock already
            # measured: compile and data-wait seconds are badput, the
            # remainder of the step is productive training.
            dw = breakdown["data_wait_s"]
            cs = float(extra.get("compile_s", 0.0) or 0.0)
            self.goodput.note("data_wait", dw)
            self.goodput.note("compile", cs)
            self.goodput.note(
                "productive_train",
                max(breakdown["step_time_s"] - dw - cs, 0.0),
            )
            pct = self.goodput.update(step=step)
            if self.slo is not None:
                self.slo.observe("goodput_pct", pct)
        if self.slo is not None:
            self.slo.observe("step_time_s", breakdown["step_time_s"])
            self.slo.observe("data_wait_s", breakdown["data_wait_s"])
            if step % self._slo_check_every == 0:
                # evaluate() RETURNS every currently-breaching objective
                # (level); only objectives newly entering the active set
                # (edge) arm a capture — a persistently-breaching run must
                # not re-capture every check until max_captures burns out.
                prev_active = set(self.slo.active)
                breaches = self.slo.evaluate(step=step)
                fresh = [
                    b for b in breaches if b["objective"] not in prev_active
                ]
                if fresh and self.devprof is not None and self.cfg.devprof_on_trigger:
                    # PR 7 told you the SLO broke; PR 8 captures WHERE the
                    # device time went while it was breaking.
                    self.devprof.request(
                        f"slo_breach:{fresh[0]['objective']}"
                    )
        every = self.cfg.memory_sample_every
        if self.cfg.enabled and every > 0 and step % every == 0:
            self.sample_memory(step)

    def record_aux_compile(self, step: int, what: str) -> None:
        """Drain compile seconds attributable to auxiliary host-side
        computations (the log-boundary loss stack, the eval step) so they
        are NOT misflagged as train-step recompiles at the next step. The
        loop calls this after each piece of boundary work, so ``what`` is
        also what a ``slow_step`` event's ``after`` names."""
        self._since_last_step.append(what)
        compile_s, n = self.compiles.drain()
        if not n:
            return
        self.registry.counter("aux_compiles").inc(n)
        self.registry.emit(
            "aux_compile", step=step, what=what,
            compile_s=round(compile_s, 4), count=n,
        )

    def record_startup_compile(self) -> None:
        """Attribute everything compiled so far (init, warmup, resume
        pre-compile) to 'step 0' — the compile-time-on-first-step number
        the acceptance criteria pin."""
        compile_s, n = self.compiles.drain()
        if n:
            self._note_startup_compile(compile_s, n)

    def _note_startup_compile(self, compile_s: float, n: int) -> None:
        """Accumulating, not last-writer-wins: warmup's compile and a
        warmup-less first step's compile are both startup cost. The spans
        are not made here: `_emit_startup` places what compiled before the
        loop by each program's own stamps, `_after_step` a first step's."""
        g = self.registry.gauge("compile_time_s")
        total = round((g.value or 0.0) + compile_s, 4)
        g.set(total)
        self.registry.emit(
            "compile", step=0, compile_time_s=round(compile_s, 4), count=n
        )

    def _emit_startup(self, *, loop_began: bool) -> None:
        """The ``startup`` event, once: where ``train()``'s entry to the
        first timed step went, by the clock's ``train.startup.*`` phases,
        and what ``jax.monitoring`` counted meanwhile. Written when the loop
        begins (or at ``close()`` for a run that never got there) because
        the sinks open long after the clock starts. Nothing is emitted for
        a runtime that names no start-up phase (serving)."""
        clock, now = self.clock, time.perf_counter()
        clock.freeze_startup(now)   # a no-op after the first begin()
        phases = clock.startup_phases
        self._startup = {}
        if not phases:
            return
        t = self.compiles.totals
        compiled: dict[str, list] = {}
        programs = self.compiles.take_programs()
        for _, sec, name, hit in programs:
            if not hit:
                c = compiled.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] = round(c[1] + sec, 4)
        self._startup = {
            "named_s": round(sum(sec for _, sec in phases.values()), 6),
            "total_s": round(clock.startup_total_s, 6),
            "trace_s": round(t["trace_s"], 4),
            "lower_s": round(t["lower_s"], 4),
            "backend_compile_s": round(t["backend_compile_s"], 4),
            "cache_retrieval_s": round(t["cache_retrieval_s"], 4),
            "compiles": t["compiles"],
            "cache_hits": t["cache_hits"],
            "cache_misses": t["compiled_anew"],
            "cache_writes": t["cache_misses"],
        }
        self.registry.emit(
            "startup", t_enter=round(clock.t_enter, 6),
            loop_began=loop_began,
            phases={k: [round(a, 6), round(sec, 6)] for k, (a, sec) in phases.items()},
            compiled=compiled, **self._startup,
        )
        if not self.tracer.enabled:
            return
        # The phases on a track of their own, and on the compile track what
        # each stretch compiled or loaded: from the first such program's
        # start, as long as the stretch's programs took together.
        base = clock.t_enter + self._clock_offset
        for name, a, sec in clock.startup_segments:
            self.tracer.emit_span(
                f"startup.{name}", base + a, base + a + sec, cat="train",
                tid="train.startup", step=0,
            )
            mine = [(te - clock.t_enter, d) for te, d, _, _ in programs
                    if a < te - clock.t_enter <= a + sec]
            if mine:
                c0 = base + max(min(te - d for te, d in mine), a)
                self.tracer.emit_span(
                    "compile", c0, c0 + sum(d for _, d in mine), cat="train",
                    tid="train.compile", step=0, count=len(mine), phase=name,
                )

    def _on_slow_step(self, step: int, slow: dict, breakdown: dict) -> None:
        self.registry.counter("slow_steps").inc()
        owner = slow["owner"]
        self._slow_excess[owner] = self._slow_excess.get(owner, 0.0) + slow["excess_s"]
        self.registry.emit(
            "slow_step", step=step, after=list(self._since_last_step), **slow
        )
        held = slow["held_by"]
        if self.lead:
            print(
                f"[dtc_tpu] slow step {step}: {slow['period_s']:.3g} s for "
                f"{slow['median_s']:.3g} — {held} +{slow[held + '_excess_s']:.3g} s"
                + (f", host late {slow['host_late_s']:.3g} s" if "host_late_s" in slow else "")
                + (f", gc {slow['gc_s']:.3g} s" if slow.get("gc_s") else "")
                + f": {owner}"
            )
        if self.tracer.enabled:
            # The excess itself, from where the phase that held the step
            # should have ended.
            off, t0 = self._clock_offset, self.clock.t0
            h0 = (t0 - breakdown["between_s"] if held == "between"
                  else self.clock.starts.get(held, t0))
            h0 += off + slow[f"{held}_s"] - slow[f"{held}_excess_s"]
            self.tracer.emit_span(
                "slow_step", h0, h0 + slow[f"{held}_excess_s"], cat="train",
                tid="train.phase", step=step, held_by=held, owner=owner,
            )

    def on_window(self, step: int, *, avg_step_s: float, tokens_per_sec: float,
                  mfu: float | None) -> None:
        self.registry.gauge("tokens_per_sec").set(tokens_per_sec)
        self.registry.gauge("mfu").set(mfu)
        self.registry.emit(
            "window",
            step=step,
            avg_step_s=round(avg_step_s, 6),
            tokens_per_sec=round(tokens_per_sec, 1),
            mfu=None if mfu is None else round(mfu, 4),
        )

    def emit_train_row(self, step: int, elapsed_time: float, loss: float) -> None:
        self.registry.emit(
            "train_row", step=step, elapsed_time=elapsed_time, loss=loss
        )

    def on_eval(self, step: int, loss: float, duration_s: float | None = None) -> None:
        self.registry.emit(
            "eval",
            step=step,
            loss=loss,
            **({} if duration_s is None else {"duration_s": round(duration_s, 4)}),
        )
        if duration_s is not None and self.tracer.enabled:
            t1 = time.time()
            self.tracer.emit_span(
                "eval", t1 - duration_s, t1, cat="train", tid="eval",
                step=step, loss=round(loss, 4),
            )

    def span(self, name: str, **attrs: Any):
        """Bracket a trainer phase (checkpoint save, rollback) as a span —
        a no-op context manager when tracing is off."""
        return self.tracer.span(name, cat="train", **attrs)

    # -- flight recorder ---------------------------------------------------
    def dump_flight(self, reason: str, **meta: Any) -> str | None:
        """Dump the flight-recorder ring to ``<obs dir>/flight.r<k>.json``
        (atomic; last dump wins the filename, every dump records its
        reason). None when the recorder is off or there is nowhere to
        write."""
        if self.recorder is None or not self._dump_dir:
            return None
        path = os.path.join(
            self._dump_dir, f"flight.r{self.registry.process_index}.json"
        )
        if self.devprof is not None and self.devprof.last_artifact:
            # The newest device-profile capture rides every post-mortem:
            # the dump names the trace artifact covering (or nearest to)
            # the failure window.
            meta.setdefault("devprof_artifact", self.devprof.last_artifact)
        try:
            return self.recorder.dump(path, reason=reason, **meta)
        except OSError as e:  # post-mortem aid must never kill the run
            print(f"[dtc_tpu] WARNING: flight-recorder dump failed ({e})")
            return None

    # -- resilience hooks --------------------------------------------------
    def on_anomaly(self, step: int, *, reason: str, action: str) -> None:
        self.registry.counter("anomalies").inc()
        self.registry.emit("anomaly", step=step, reason=reason, action=action)
        self.dump_flight(f"anomaly: {reason}", step=step, action=action)

    def on_recovery(self, step: int, *, action: str, **fields: Any) -> None:
        self.registry.counter("recoveries").inc()
        self.registry.emit("recovery", step=step, action=action, **fields)
        self._note_restore_badput(
            "rollback_replay" if action == "rollback" else "degraded",
            fields, step,
        )

    def _note_restore_badput(
        self, klass: str, fields: dict[str, Any], step: int
    ) -> None:
        """Feed the online gauge the detect->restored gap when the event
        carries the enriched timestamps (the offline ledger additionally
        bills the discarded step executions — too retroactive for a
        streaming gauge)."""
        if self.goodput is None:
            return
        td, tr = fields.get("t_detect"), fields.get("t_restored")
        if isinstance(td, (int, float)) and isinstance(tr, (int, float)):
            self.goodput.note(klass, max(float(tr) - float(td), 0.0))
            pct = self.goodput.update(step=step)
            if self.slo is not None:
                self.slo.observe("goodput_pct", pct)

    def on_elastic(self, step: int, kind: str, **fields: Any) -> None:
        """Typed elastic-layer events (ISSUE 15): ``host_lost`` /
        ``host_slow`` / ``elastic_resize`` / ``elastic_spill`` land in
        the JSONL stream (and from there the Perfetto instant set and
        the cross-host reducer). A host loss additionally dumps the
        flight recorder — the post-mortem starts from a timeline, not a
        silent restart."""
        name = kind if kind.startswith("elastic_") else f"elastic_{kind}"
        self.registry.counter(name).inc()
        self.registry.emit(kind, step=step, **fields)
        if kind == "elastic_resize":
            self._note_restore_badput("elastic_resize", fields, step)
        if kind == "host_lost":
            self.dump_flight("host_lost", step=step)

    def on_hung_step(self, step: int, **fields: Any) -> None:
        self.registry.counter("hung_steps").inc()
        self.registry.emit("hung_step", step=step, **fields)
        if self.devprof is not None and self.cfg.devprof_on_trigger:
            self.devprof.request("hung_step")
        self.dump_flight("hung_step", step=step)

    def drain_recovery_bus(self, bus: Any, step: int) -> None:
        """Move pending chaos/recovery records (posted from threads and
        layers with no telemetry handle — see resilience.events) into the
        event stream, stamped with the step they surfaced at."""
        for etype, fields in bus.drain():
            if etype == "chaos":
                self.registry.counter("chaos_injections").inc()
            elif etype == "recovery":
                self.registry.counter("recoveries").inc()
            # Keep the poster's own step (e.g. a chaos trigger step) when it
            # recorded one; otherwise stamp the boundary it surfaced at.
            fields.setdefault("step", step)
            self.registry.emit(etype, **fields)

    def arm_profile_window(self, start_step: int, n_steps: int = 2) -> bool:
        """Point the profiler at ``[start_step, start_step + n_steps)`` —
        used by the watchdog to capture a trace after a hung-step flag.
        No-op (False) when a window is already configured/active or the
        profiler previously failed."""
        p = self.profiler
        if p.enabled or p.failed or not p.log_dir:
            return False
        p.start, p.stop = start_step, start_step + n_steps
        p.enabled = True
        return True

    def request_device_profile(self, reason: str = "on_demand") -> bool:
        """Arm an on-demand devprof capture window at the next step of
        a live run. False when the observatory is off, disabled, or
        already capturing/pending."""
        if self.devprof is None:
            return False
        return self.devprof.request(reason)

    def set_device_profile_context(
        self,
        *,
        step_flops: float | None = None,
        peak_flops: float | None = None,
        comm_estimate: dict[str, float] | None = None,
    ) -> None:
        """Attach run context to future capture metas so the offline leg
        (``trace_report.py --device``) can derive device-time MFU and run
        the collective-census cross-check without rebuilding the model."""
        if self.devprof is None:
            return
        self.devprof.step_flops = step_flops
        self.devprof.peak_flops = peak_flops
        self.devprof.comm_estimate = comm_estimate

    def sample_memory(self, step: int) -> None:
        samples = sample_memory()
        peak = peak_hbm_bytes(samples)
        if peak is not None:
            g = self.registry.gauge("peak_hbm_bytes")
            g.set(peak if g.value is None else max(g.value, peak))
        self.registry.emit("memory", step=step, devices=samples)

    def on_run_end(self, **summary: Any) -> dict[str, Any]:
        """Emit the run summary (+ cross-host reduction on the lead) and
        write ``summary.json`` next to the shards."""
        self.sample_memory(step=-1)
        # Force the key into the summary even when the backend never
        # reported stats: an explicit null (CPU) reads differently from a
        # missing field (telemetry broken).
        self.registry.gauge("peak_hbm_bytes")
        body = dict(self.registry.snapshot())
        body.update(summary)
        if self._startup:
            body["startup"] = self._startup
        body.setdefault("slow_steps", 0)
        body["slow_step_excess_s"] = {
            k: round(v, 6) for k, v in self._slow_excess.items()
        }
        self.registry.emit("run_summary", **body)
        self.registry.flush()
        self._barrier()
        hosts = None
        if self.lead and self.obs_dir:
            hosts = reduce_shards(self.obs_dir, self.cfg.straggler_threshold)
            if hosts is not None:
                self.registry.emit("hosts", **hosts)
                if hosts["stragglers"]:
                    print(
                        f"[dtc_tpu] WARNING: straggler host(s) {hosts['stragglers']} "
                        f"(mean step time > {self.cfg.straggler_threshold}x "
                        "cross-host median)"
                    )
            try:
                with open(os.path.join(self.obs_dir, "summary.json"), "w") as f:
                    json.dump({"summary": body, "hosts": hosts}, f, indent=2)
            except OSError as e:
                print(f"[dtc_tpu] WARNING: could not write summary.json ({e})")
        return {"summary": body, "hosts": hosts}

    def _barrier(self) -> None:
        """Cross-host sync between shard flush and reduction: without it
        the lead reduces while slower hosts' shard tails — exactly the
        straggler evidence — are still unflushed."""
        import jax

        if jax.process_count() < 2:
            return
        try:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("dtc_tpu_obs_reduce")
        except Exception as e:
            print(f"[dtc_tpu] WARNING: obs pre-reduce barrier failed ({e})")

    # -- lifecycle --------------------------------------------------------
    def flush(self) -> None:
        self.registry.flush()

    def close(self) -> None:
        if self._closed:
            return
        if self._startup is None:
            self._emit_startup(loop_began=False)   # a run that ended inside its start-up
        self._closed = True
        self.clock.shutdown()  # the last pass's tail and group, the canary, the gc hook
        self.profiler.close()
        if self.devprof is not None:
            self.devprof.close()  # finalize a window the run ended inside
        self.compiles.deactivate()
        self.registry.close()
