"""Telemetry subsystem tests (ISSUE 1): registry round-trip, CSV
back-compat, step-time breakdown on a real 2-step CPU trainer run, the
multi-host reducer on synthetic shards, profiler/CSVLogger hardening, and
the acceptance-criteria end-to-end run of main.py."""

import json
import os

import pytest

from dtc_tpu.obs import (
    CsvSink,
    JsonlSink,
    MemorySink,
    MetricsRegistry,
    StepClock,
    StepWindowProfiler,
    read_jsonl,
    reduce_shards,
    shard_path,
)
from tests.conftest import make_train_cfg


# ---- registry -------------------------------------------------------------


def test_registry_jsonl_round_trip(tmp_path):
    """emit -> JSONL shard -> parse recovers every event with its stamps."""
    reg = MetricsRegistry(process_index=3)
    reg.add_sink(JsonlSink(str(tmp_path / "events.r3.jsonl")))
    reg.emit("step", step=1, step_time_s=0.25, data_wait_s=0.01)
    reg.emit("memory", step=1, devices=None)
    reg.close()
    events = read_jsonl(str(tmp_path / "events.r3.jsonl"))
    assert [e["etype"] for e in events] == ["step", "memory"]
    assert events[0]["step_time_s"] == 0.25
    assert events[0]["proc"] == 3 and "ts" in events[0]
    assert events[1]["devices"] is None


def test_registry_instruments_snapshot():
    reg = MetricsRegistry()
    reg.counter("recompiles").inc(2)
    reg.gauge("mfu").set(0.41)
    reg.gauge("peak_hbm_bytes")  # created but never set -> null
    for v in (0.1, 0.2, 0.3):
        reg.histogram("step_time_s").observe(v)
    snap = reg.snapshot()
    assert snap["recompiles"] == 2
    assert snap["mfu"] == 0.41
    assert snap["peak_hbm_bytes"] is None
    assert snap["step_time_s"]["count"] == 3
    assert snap["step_time_s"]["mean"] == pytest.approx(0.2)
    assert snap["step_time_s"]["min"] == 0.1 and snap["step_time_s"]["max"] == 0.3


def test_read_jsonl_skips_torn_tail(tmp_path):
    p = tmp_path / "events.r0.jsonl"
    p.write_text('{"etype": "step", "step": 1}\n{"etype": "step", "st')
    events = read_jsonl(str(p))
    assert len(events) == 1 and events[0]["step"] == 1


def test_csv_sink_back_compat_schema(tmp_path):
    """The CSV bridge writes exactly the reference's log.csv schema from
    train_row events and ignores every other event type."""
    reg = MetricsRegistry()
    reg.add_sink(CsvSink(str(tmp_path / "log.csv"), ("step", "elapsed_time", "loss"), "train_row"))
    reg.emit("step", step=1, step_time_s=0.5)  # must NOT become a row
    reg.emit("train_row", step=1, elapsed_time=0.5, loss=4.2)
    reg.emit("train_row", step=2, elapsed_time=1.0, loss=4.1)
    reg.close()
    rows = (tmp_path / "log.csv").read_text().strip().splitlines()
    assert rows[0] == "step,elapsed_time,loss"
    assert rows[1:] == ["1,0.5,4.2", "2,1.0,4.1"]


def test_jsonl_sink_append_preserves_prior_run(tmp_path):
    """Resumed runs reopen their shard in append mode — the preempted
    run's events survive."""
    p = str(tmp_path / "events.r0.jsonl")
    reg1 = MetricsRegistry()
    reg1.add_sink(JsonlSink(p))
    reg1.emit("step", step=1, step_time_s=0.1)
    reg1.close()
    reg2 = MetricsRegistry()
    reg2.add_sink(JsonlSink(p, append=True))
    reg2.emit("step", step=2, step_time_s=0.2)
    reg2.close()
    assert [e["step"] for e in read_jsonl(p)] == [1, 2]


def test_first_timed_step_compile_is_startup_not_recompile(tmp_path):
    """With warmup_steps=0 the first step's cold compile (and any tiny
    device_put compiles before it) must land in the step-0 `compile`
    event, never as a phantom `recompile`."""
    import jax
    import jax.numpy as jnp

    from dtc_tpu.obs import Telemetry

    tele = Telemetry(output_dir=str(tmp_path))
    try:
        # Pre-loop compiles (e.g. eval-set device_puts) drain here.
        tele.record_startup_compile()
        tele.on_step_start(1)
        jax.jit(lambda v: v * 2 + tmp_path.stat().st_mode)(jnp.ones(3)).block_until_ready()
        tele.on_step_end(1, elapsed_s=0.1)
        # Steady state reached: the NEXT fresh compile is a real recompile.
        tele.on_step_start(2)
        jax.jit(lambda v: v * 3 - 1)(jnp.ones((2, 2))).block_until_ready()
        tele.on_step_end(2, elapsed_s=0.2)
        tele.flush()
    finally:
        tele.close()
    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    by_step = {e["step"]: e for e in events if e["etype"] == "step"}
    assert "recompile" not in by_step[1], "first-step compile misflagged"
    compiles = [e for e in events if e["etype"] == "compile"]
    assert compiles and all(e["step"] == 0 for e in compiles)
    assert by_step[2].get("recompile") is True


def test_memory_sink_collects():
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    reg.emit("bench_config", label="x", tokens_per_sec=100.0)
    assert sink.events[0]["label"] == "x"


# ---- CSVLogger hardening (satellite) --------------------------------------


def test_csvlogger_unknown_key_raises_clearly(tmp_path):
    from dtc_tpu.utils.logging import CSVLogger

    log = CSVLogger(str(tmp_path / "x.csv"), fieldnames=("step", "loss"))
    with pytest.raises(ValueError, match=r"unknown field.*elapsed.*valid fields.*step"):
        log.log(step=1, elapsed=0.5)
    log.close()


def test_csvlogger_missing_key_fills_blank_and_close_idempotent(tmp_path):
    from dtc_tpu.utils.logging import CSVLogger

    log = CSVLogger(str(tmp_path / "x.csv"), fieldnames=("step", "loss"))
    log.log(step=1)  # loss column left blank
    log.close()
    log.close()  # idempotent
    log.flush()  # safe after close
    with pytest.raises(ValueError, match="closed"):
        log.log(step=2)
    assert (tmp_path / "x.csv").read_text().strip().splitlines()[1] == "1,"


# ---- step clock -----------------------------------------------------------


def test_step_clock_breakdown_sums():
    import time

    clock = StepClock()
    clock.begin(7)
    with clock.phase("data_wait"):
        time.sleep(0.02)
    with clock.phase("dispatch"):
        time.sleep(0.01)
    out = clock.end()
    assert out["data_wait_s"] >= 0.02
    assert out["dispatch_s"] >= 0.01
    assert out["block_s"] == 0.0
    assert out["step_time_s"] >= out["data_wait_s"] + out["dispatch_s"]
    assert out["other_s"] >= 0.0


# ---- profiler hardening (satellite) ---------------------------------------


def test_profiler_unwritable_dir_warns_and_disables(tmp_path, capsys):
    blocker = tmp_path / "file.txt"
    blocker.write_text("x")
    # log_dir nested under a regular FILE. jax validates nothing at
    # start_trace; the failure surfaces at stop_trace — which must
    # warn-and-disable (not crash the run) AND clear jax's wedged global
    # session so later profiler windows in the process still work.
    p = StepWindowProfiler(1, 2, str(blocker / "nested" / "profile"))
    p.step(1)
    p.step(2)  # stop_trace fails here
    assert not p.enabled and p.failed is not None
    p.close()
    assert "disabling trace capture" in capsys.readouterr().out

    # The process can still profile afterwards.
    p2 = StepWindowProfiler(1, 2, str(tmp_path / "ok"))
    p2.step(1)
    p2.step(2)
    assert p2.enabled and p2.failed is None


def test_profiler_already_active_session_disables(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        p = StepWindowProfiler(1, 2, str(tmp_path / "inner"))
        p.step(1)  # second start_trace raises inside -> warn-and-disable
        assert not p.enabled and p.failed is not None
    finally:
        jax.profiler.stop_trace()


# ---- multi-host reducer ---------------------------------------------------


def _write_shard(obs_dir, proc, step_times):
    os.makedirs(obs_dir, exist_ok=True)
    with open(shard_path(str(obs_dir), proc), "w") as f:
        for step, t in enumerate(step_times, start=1):
            f.write(json.dumps({"etype": "step", "proc": proc, "step": step,
                                "step_time_s": t}) + "\n")
        f.write(json.dumps({"etype": "run_summary", "proc": proc}) + "\n")


def test_reducer_flags_straggler(tmp_path):
    obs = tmp_path / "obs"
    _write_shard(obs, 0, [0.10, 0.10, 0.10])
    _write_shard(obs, 1, [0.11, 0.09, 0.10])
    _write_shard(obs, 2, [0.30, 0.32, 0.31])  # 3x the median host
    red = reduce_shards(str(obs), straggler_threshold=1.5)
    assert red["n_hosts"] == 3
    assert red["stragglers"] == [2]
    assert red["hosts"]["2"]["straggler"] is True
    assert red["hosts"]["0"]["straggler"] is False
    assert red["step_time_s"]["min"] == pytest.approx(0.1)
    assert red["step_time_s"]["max"] == pytest.approx(0.31, abs=1e-3)


def test_reducer_single_shard_degrades_gracefully(tmp_path):
    obs = tmp_path / "obs"
    _write_shard(obs, 0, [0.1, 0.2])
    red = reduce_shards(str(obs))
    assert red["n_hosts"] == 1
    assert red["stragglers"] == []  # no peer to lag behind
    assert red["hosts"]["0"]["steps"] == 2


def test_reducer_no_step_events_returns_none(tmp_path):
    obs = tmp_path / "obs"
    os.makedirs(obs)
    with open(shard_path(str(obs), 0), "w") as f:
        f.write(json.dumps({"etype": "run_start"}) + "\n")
    assert reduce_shards(str(obs)) is None
    assert reduce_shards(str(tmp_path / "missing")) is None


# ---- config block ---------------------------------------------------------


def test_obs_config_validation():
    from dtc_tpu.config.schema import ObsConfig

    with pytest.raises(ValueError, match="memory_sample_every"):
        ObsConfig(memory_sample_every=-1)
    with pytest.raises(ValueError, match="straggler_threshold"):
        ObsConfig(straggler_threshold=0.5)


def test_obs_config_loads_from_nested_yaml(tmp_path):
    from dtc_tpu.config.loader import load_yaml_dataclass
    from dtc_tpu.config.schema import TrainConfig

    p = tmp_path / "train.yaml"
    p.write_text(
        "seed: 0\nparallel: dp\nbatch: 8\nsteps: 2\nlog_every: 1\n"
        "output_dir: ''\nobs:\n  memory_sample_every: 5\n  straggler_threshold: 2.0\n"
    )
    cfg = load_yaml_dataclass(p, TrainConfig)
    assert cfg.obs.memory_sample_every == 5
    assert cfg.obs.straggler_threshold == 2.0
    assert cfg.obs.enabled is True


# ---- trainer integration (2-step CPU smoke) -------------------------------


def test_trainer_step_breakdown_smoke(tiny_model_cfg, opt_cfg, tmp_path):
    """A 2-step run emits per-step breakdown events, a step-0 compile
    event, and a run summary — and log.csv keeps the reference schema."""
    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg(
        "dp", steps=2, log_every=1, output_dir=str(tmp_path), warmup_steps=1
    )
    res = train(cfg, tiny_model_cfg, opt_cfg)
    assert len(res.losses) == 2

    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    by_type = {}
    for e in events:
        by_type.setdefault(e["etype"], []).append(e)

    steps = by_type["step"]
    assert [e["step"] for e in steps] == [1, 2]
    for e in steps:
        for k in ("data_wait_s", "dispatch_s", "block_s", "other_s", "step_time_s", "elapsed_s"):
            assert isinstance(e[k], float) and e[k] >= 0.0
        assert e["step_time_s"] >= e["data_wait_s"] + e["dispatch_s"]

    # Warmup compiled the step -> the startup compile event, labeled step 0.
    compiles = by_type["compile"]
    assert compiles[0]["step"] == 0 and compiles[0]["compile_time_s"] > 0

    summary = by_type["run_summary"][-1]
    assert summary["steps"] == 2
    assert summary["tokens_per_sec"] > 0
    assert summary["peak_hbm_bytes"] is None  # CPU: explicit null
    assert summary["est_comm_bytes_per_step"]["total"] > 0  # DP grad all-reduce
    assert summary["step_time_s"]["count"] == 2

    # hosts reduction ran in single-process mode.
    assert by_type["hosts"][0]["n_hosts"] == 1

    # Back-compat: log.csv schema and row count unchanged.
    rows = (tmp_path / "log.csv").read_text().strip().splitlines()
    assert rows[0] == "step,elapsed_time,loss"
    assert len(rows) == 3

    # summary.json mirrors the stream for dashboards.
    sj = json.loads((tmp_path / "obs" / "summary.json").read_text())
    assert sj["summary"]["steps"] == 2 and sj["hosts"]["n_hosts"] == 1


def test_trainer_obs_disabled_writes_no_stream(tiny_model_cfg, opt_cfg, tmp_path):
    from dataclasses import replace

    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg("dp", steps=2, output_dir=str(tmp_path))
    cfg = replace(cfg, obs=replace(cfg.obs, enabled=False))
    train(cfg, tiny_model_cfg, opt_cfg)
    assert not (tmp_path / "obs").exists()
    # CSV logging is independent of the obs switch.
    assert (tmp_path / "log.csv").exists()


# ---- acceptance: main.py end-to-end ---------------------------------------


def test_main_two_step_run_emits_telemetry(tmp_path):
    """ISSUE 1 acceptance: a 2-step CPU run of main.py produces a JSONL
    stream with per-step data_wait_s/step_time_s, compile time on step 0,
    and a final run summary (tokens/s; peak HBM null on CPU) — while
    outputs/<run>/log.csv keeps the existing format."""
    from click.testing import CliRunner

    import main as main_mod

    out = tmp_path / "out"
    (tmp_path / "model_config.yaml").write_text(
        "vocab_size: 97\nd_model: 64\nn_layers: 2\nn_heads: 4\nd_ff: 128\n"
        "max_seq_len: 32\ndropout: 0.0\nparam_dtype: float32\n"
        "compute_dtype: float32\nattention: dense\n"
    )
    (tmp_path / "optim_config.yaml").write_text(
        "lr: 0.001\nweight_decay: 0.1\ngrad_clip: 1.0\n"
    )
    (tmp_path / "train.yaml").write_text(
        f"seed: 0\nparallel: dp\nbatch: 8\nsteps: 2\nlog_every: 1\n"
        f"output_dir: {out}\ndataset: synthetic\nwarmup_steps: 2\nprefetch: 0\n"
    )
    res = CliRunner().invoke(
        main_mod.main,
        ["--train_config_path", str(tmp_path / "train.yaml"), "--steps", "2"],
        catch_exceptions=False,
    )
    assert res.exit_code == 0, res.output

    events = read_jsonl(str(out / "obs" / "events.r0.jsonl"))
    etypes = [e["etype"] for e in events]
    assert etypes[0] == "run_start"
    assert etypes[-1] == "hosts" and "run_summary" in etypes

    steps = [e for e in events if e["etype"] == "step"]
    assert [e["step"] for e in steps] == [1, 2]
    assert all("data_wait_s" in e and "step_time_s" in e for e in steps)

    compile_ev = next(e for e in events if e["etype"] == "compile")
    assert compile_ev["step"] == 0 and compile_ev["compile_time_s"] > 0

    summary = next(e for e in events if e["etype"] == "run_summary")
    assert summary["tokens_per_sec"] > 0
    assert summary["peak_hbm_bytes"] is None

    rows = (out / "log.csv").read_text().strip().splitlines()
    assert rows[0] == "step,elapsed_time,loss" and len(rows) == 3


# ---- the trainer's clock from the first line of train() (ISSUE 40) ---------

STARTUP_STATE = ("distributed", "mesh", "model", "state", "restore")
STARTUP_FIRST_STEP = ("step_build", "warmup_first")
STARTUP_REST = ("data", "obs", "eval_setup", "warmup_rest")


def _events_of(tmp_path, etype):
    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    return [e for e in events if e["etype"] == etype]


def _slow_feed(train_cfg, model_cfg, slow_at: int, sleep_s: float):
    """The trainer's own synthetic feed, asleep once: before its
    ``slow_at``-th batch (warm-up batches count)."""
    import time

    from dtc_tpu.train.trainer import make_host_iterator

    for i, batch in enumerate(make_host_iterator(train_cfg, model_cfg), 1):
        if i == slow_at:
            time.sleep(sleep_s)
        yield batch


def test_startup_event_names_the_whole_start_up(tiny_model_cfg, opt_cfg, tmp_path):
    """One `startup` event when the timed loop begins: its phases cover at
    least 95 % of train()'s entry to the first timed step, the three groups
    the benchmark reads add up to what they name, and `run_summary` carries
    the same totals."""
    import jax

    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg("dp", steps=2, log_every=2, output_dir=str(tmp_path),
                         warmup_steps=3)
    jax.clear_caches()   # what earlier tests of this process traced and compiled
    train(cfg, tiny_model_cfg, opt_cfg)
    (ev,) = _events_of(tmp_path, "startup")
    assert ev["loop_began"] is True
    phases = ev["phases"]
    assert set(phases) == set(STARTUP_STATE + STARTUP_FIRST_STEP + STARTUP_REST)
    starts = [phases[k][0] for k in STARTUP_STATE + ("step_build", "data", "obs", "eval_setup")]
    assert starts == sorted(starts) and starts[0] < 0.01
    groups = [sum(phases[k][1] for k in g)
              for g in (STARTUP_STATE, STARTUP_FIRST_STEP, STARTUP_REST)]
    assert sum(groups) == pytest.approx(ev["named_s"], abs=1e-4)
    assert 0.95 * ev["total_s"] <= ev["named_s"] <= ev["total_s"] + 1e-6
    # the first step is traced, lowered and compiled inside `warmup_first`
    assert phases["warmup_first"][1] > phases["warmup_rest"][1]
    assert ev["trace_s"] > 0 and ev["lower_s"] > 0 and ev["backend_compile_s"] > 0
    # compiled anew, or loaded where an earlier test left a persistent cache on
    assert ev["compiles"] > 0 and ev["cache_misses"] == ev["compiles"] - ev["cache_hits"]
    assert sum(n for n, _ in ev["compiled"].values()) == ev["cache_misses"]
    assert "jit(train_step)" in ev["compiled"] or ev["cache_hits"] > 0
    (compile_ev,) = _events_of(tmp_path, "compile")
    assert compile_ev["compile_time_s"] == pytest.approx(ev["backend_compile_s"], abs=1e-3)
    summary = _events_of(tmp_path, "run_summary")[-1]
    assert summary["startup"]["total_s"] == ev["total_s"]
    assert summary["startup"]["cache_misses"] == ev["cache_misses"]
    # the phases are spans on a track of their own, and what they compiled
    # lies inside them (placed by the programs' own stamps)
    spans = _events_of(tmp_path, "span")
    stretches = [s for s in spans if s["tid"] == "train.startup"]
    assert {s["name"] for s in stretches} == {f"startup.{k}" for k in phases}
    compiled = [s for s in spans if s["name"] == "compile" and s["step"] == 0]
    assert sum(s["dur_s"] for s in compiled) == pytest.approx(ev["backend_compile_s"], abs=1e-3)
    for s in compiled:   # a name opened twice (`data`, `warmup_rest`) has two stretches
        assert any(ph["name"] == f"startup.{s['phase']}"
                   and ph["t0"] - 1e-3 <= s["t0"] <= ph["t0"] + ph["dur_s"]
                   for ph in stretches), s


def test_startup_event_reads_the_persistent_cache(tiny_model_cfg, opt_cfg, tmp_path):
    """Two runs in one process with the compile cache in a temporary
    directory and the caching threshold at 0: the first traces and compiles
    everything anew and writes it, the second loads it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from dtc_tpu.train.trainer import train

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    events = []
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        for run in ("first", "second"):
            jax.clear_caches()   # in-process executables: the disk has to serve
            out = tmp_path / run
            cfg = make_train_cfg("dp", steps=1, log_every=1, output_dir=str(out),
                                 warmup_steps=1)
            train(cfg, tiny_model_cfg, opt_cfg)
            (ev,) = _events_of(out, "startup")
            events.append(ev)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()
    first, second = events
    assert first["trace_s"] > 0
    assert first["cache_writes"] > 0 and first["cache_misses"] >= first["cache_writes"]
    assert second["cache_hits"] > 0 and second["cache_retrieval_s"] > 0
    assert second["cache_misses"] < first["cache_misses"]
    assert second["backend_compile_s"] >= second["cache_retrieval_s"]


def test_sleeping_feed_is_one_slow_step_held_by_data_wait(tiny_model_cfg, opt_cfg, tmp_path, capsys):
    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg("dp", steps=12, log_every=12, output_dir=str(tmp_path),
                         warmup_steps=1, prefetch=0)
    feed = _slow_feed(cfg, tiny_model_cfg, slow_at=1 + 9, sleep_s=0.4)
    train(cfg, tiny_model_cfg, opt_cfg, host_iterator=feed)
    # one event for the step the feed slept in (a loaded CPU may add others)
    (ev,) = [e for e in _events_of(tmp_path, "slow_step") if e["step"] == 9]
    assert ev["held_by"] == "data_wait" and ev["owner"] == "host_phase"
    assert ev["data_wait_excess_s"] == pytest.approx(0.4, abs=0.05)
    assert ev["excess_s"] == pytest.approx(ev["period_s"] - ev["median_s"], abs=1e-5)
    assert ev["host_late_s"] < 0.1 and ev["gc_s"] < 0.1 and ev["after"] == []
    step9 = next(e for e in _events_of(tmp_path, "step") if e["step"] == 9)
    assert ev["cpu_s"] == step9["cpu_s"] and step9["data_wait_s"] >= 0.4
    assert "slow step 9:" in capsys.readouterr().out
    summary = _events_of(tmp_path, "run_summary")[-1]
    assert summary["slow_steps"] >= 1
    assert summary["slow_step_excess_s"]["host_phase"] >= 0.35
    span = next(s for s in _events_of(tmp_path, "span")
                if s["name"] == "slow_step" and s["step"] == 9)
    assert span["tid"] == "train.phase" and span["dur_s"] == pytest.approx(0.4, abs=0.05)


def test_slow_step_stays_out_of_the_next_median():
    from dtc_tpu.obs.stepclock import SLOW_PHASES, SlowSteps

    def step(block, between=0.001):
        return {"data_wait_s": 0.001, "rng_s": 0.001, "launch_s": 0.001, "block_s": block,
                "step_time_s": 0.003 + block, "between_s": between}

    det = SlowSteps()
    assert all(det.observe(step(0.100)) is None for _ in range(5))
    ev = det.observe(step(2.100))
    assert ev["held_by"] == "block" and ev["owner"] == "device_or_driver"
    assert ev["excess_s"] == pytest.approx(2.0) and ev["block_excess_s"] == pytest.approx(2.0)
    assert sum(ev[f"{p}_s"] for p in SLOW_PHASES) == pytest.approx(ev["period_s"])
    assert max(det.period.values) < 0.2 and max(det.phases["block"].values) < 0.2
    # the next slow step is judged by the same median, and the canary and the
    # collector decide its owner
    frozen = det.observe({**step(1.100), "host_late_s": 0.9, "gc_s": 0.0})
    assert frozen["median_s"] == ev["median_s"] and frozen["owner"] == "host_frozen"
    assert det.observe({**step(0.100, between=0.5), "gc_s": 0.3})["owner"] == "gc"
    late = det.observe(step(0.100, between=0.5))
    assert late["held_by"] == "between" and late["owner"] == "host_phase"
    assert det.observe(step(0.108)) is None   # under 1.1 x: no slow step


def test_forced_collection_shows_in_the_steps_gc_fields():
    import gc

    clock = StepClock()
    clock.watch_host()
    try:
        clock.begin(1)
        with clock.phase("dispatch"):
            gc.collect()
        out = clock.end()
        clock.begin(2)
        quiet = clock.end()
    finally:
        clock.shutdown()
    assert out["gc_n"][2] >= 1 and out["gc_s"] > 0 and out["cpu_s"] > 0
    assert quiet["gc_n"] == [0, 0, 0] and quiet["gc_s"] == 0.0
    assert clock._on_gc not in gc.callbacks
    assert "gc_s" not in StepClock().end()   # not watching: the fields are absent


def test_canary_reports_the_lateness_it_was_given():
    from dtc_tpu.obs.stepclock import Canary

    now = [100.0]
    lateness = [0.0, 0.25, 0.0, 3.0, 0.01]

    def sleep(interval):
        now[0] += interval + lateness.pop(0)
        if not lateness:
            canary._halt.set()

    canary = Canary(0.02, clock=lambda: now[0], sleep=sleep)
    canary.run()   # on this thread: the injected sleep ends it
    assert canary.take(now[0]) == pytest.approx(3.0)
    assert canary.take(now[0]) == 0.0   # taken: the next period starts clean
    # a sleep still overdue when the step ends counts up to there, and only
    # what is left of it goes to the next period
    canary._due = now[0] + 0.02
    assert canary.take(now[0] + 1.02) == pytest.approx(1.0)
    canary._note(now[0] + 1.52)
    assert canary.take(now[0] + 1.52) == pytest.approx(0.5)


def test_canary_is_not_alive_after_close(tmp_path):
    from dtc_tpu.obs import Telemetry

    tele = Telemetry(output_dir=str(tmp_path))
    try:
        assert tele.clock._canary is None   # not before the first timed step
        tele.on_step_start(1)
        canary = tele.clock._canary
        assert canary.is_alive() and canary.daemon
        out = tele.on_step_end(1, elapsed_s=0.1)
        assert {"cpu_s", "gc_s", "gc_n", "host_late_s"} <= set(out)
        tele.on_step_start(2)
        assert tele.clock._canary is canary   # started once
    finally:
        tele.close()
    assert not canary.is_alive() and tele.clock._canary is None


def test_watchdog_and_slow_step_detector_share_one_rule():
    """One trailing-median rule, two factors: at the detector's factor the
    watchdog flags the same steps of a series, at its own only the hang."""
    from dtc_tpu.config.schema import WatchdogConfig
    from dtc_tpu.obs.stepclock import SLOW_FACTOR, SlowSteps, TrailingMedian
    from dtc_tpu.resilience import StepWatchdog

    series = [0.55, 0.5, 0.5, 0.51, 0.5, 0.56, 0.5, 2.3, 0.5, 0.549, 0.552, 4.1, 0.5]
    det = SlowSteps()
    same = StepWatchdog(WatchdogConfig(enabled=True, factor=SLOW_FACTOR, min_samples=5))
    hang = StepWatchdog(WatchdogConfig(enabled=True, factor=8.0, min_samples=5))
    assert isinstance(same._history, TrailingMedian) and isinstance(det.period, TrailingMedian)
    flagged = {"det": [], "same": [], "hang": []}
    for i, v in enumerate(series):
        b = {"data_wait_s": 0.0, "rng_s": 0.0, "launch_s": 0.0, "block_s": v,
             "step_time_s": v, "between_s": 0.0}
        ev = det.observe(b)
        flag = same.observe(i, v)
        if ev is not None:
            flagged["det"].append(i)
            assert ev["median_s"] == pytest.approx(flag["median_s"], abs=1e-4)
        if flag is not None:
            flagged["same"].append(i)
        if hang.observe(i, v) is not None:
            flagged["hang"].append(i)
    assert flagged["det"] == flagged["same"] == [5, 7, 10, 11]
    assert flagged["hang"] == [11]
