"""Flight recorder & end-to-end tracing tests (ISSUE 7): span API,
Perfetto export schema, quantile-histogram parity with the shared
nearest-rank oracle, JSONL rotation, serving-aware shard reduction, the
online SLO monitor, and the flight-recorder dump paths (chaos anomaly,
watchdog fire, SIGTERM) — plus the serving chaos acceptance run whose
trace must show the failing request's full span chain in order."""

import json
import math
import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dtc_tpu.config.schema import (
    ChaosConfig,
    ObsConfig,
    ResilienceConfig,
    ServeConfig,
    SloConfig,
    WatchdogConfig,
)
from dtc_tpu.obs import (
    FlightRecorder,
    JsonlSink,
    MemorySink,
    MetricsRegistry,
    SloMonitor,
    Telemetry,
    Tracer,
    load_flight_dump,
    read_jsonl,
    reduce_shards,
    shard_path,
    to_chrome_trace,
)
from dtc_tpu.obs.registry import HIST_BUCKET_GROWTH, Histogram
from dtc_tpu.utils.percentile import nearest_rank
from tests.conftest import make_train_cfg

VOCAB = 97


# ---------------------------------------------------------------------------
# shared percentile (satellite): the exact oracle
# ---------------------------------------------------------------------------


def test_nearest_rank_edge_cases():
    assert nearest_rank([], 0.5) is None
    assert nearest_rank([7.0], 0.0) == 7.0
    assert nearest_rank([7.0], 0.5) == 7.0
    assert nearest_rank([7.0], 1.0) == 7.0
    assert nearest_rank([3, 1, 2, 4], 0.0) == 1   # q=0 -> min
    assert nearest_rank([3, 1, 2, 4], 1.0) == 4   # q=1 -> max
    assert nearest_rank([1, 2, 3, 4], 0.5) == 2   # ceil(0.5*4)=2nd
    assert nearest_rank([1, 2, 3, 4], 0.51) == 3
    assert nearest_rank([1, 2, 3, 4], 0.99) == 4
    assert nearest_rank([3.0], 0.99) == 3.0
    assert nearest_rank(range(1, 101), 0.99) == 99
    with pytest.raises(ValueError):
        nearest_rank([1.0], 1.5)


# ---------------------------------------------------------------------------
# quantile histograms (tentpole 3)
# ---------------------------------------------------------------------------


def test_histogram_summary_back_compat_plus_percentiles():
    h = Histogram("t")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    s = h.summary()
    # Existing keys byte-compatible for current consumers...
    assert s["count"] == 3
    assert s["mean"] == pytest.approx(0.2)
    assert s["min"] == 0.1 and s["max"] == 0.3
    assert s["total"] == pytest.approx(0.6)
    # ...plus the quantile keys the SLO questions are phrased in.
    for k in ("p50", "p90", "p99"):
        assert isinstance(s[k], float)
    empty = Histogram("e").summary()
    assert empty["p50"] is None and empty["count"] == 0


def test_histogram_percentiles_within_one_bucket_of_nearest_rank():
    """Parity satellite: bucketed pNN vs the exact nearest-rank oracle on
    identical samples, within one (~10%) bucket width — across scales,
    including zeros."""
    rng = random.Random(7)
    for scale in (1e-4, 1.0, 3e2):
        vals = [rng.lognormvariate(math.log(scale), 1.5) for _ in range(400)]
        h = Histogram("x")
        for v in vals:
            h.observe(v)
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            exact = nearest_rank(vals, q)
            got = h.percentile(q)
            assert got is not None
            ratio = got / exact
            assert 1 / HIST_BUCKET_GROWTH <= ratio <= HIST_BUCKET_GROWTH, (
                scale, q, got, exact,
            )
    h = Histogram("z")
    for v in (0.0, 0.0, 0.0, 5.0):
        h.observe(v)
    assert h.percentile(0.5) == 0.0
    assert h.percentile(1.0) == pytest.approx(5.0, rel=0.1)


def test_histogram_reset_drops_warmup_samples():
    h = Histogram("x")
    h.observe(100.0)
    h.reset()
    assert h.count == 0 and h.percentile(0.5) is None
    h.observe(1.0)
    assert h.summary()["count"] == 1 and h.max == 1.0


# ---------------------------------------------------------------------------
# JSONL rotation (satellite)
# ---------------------------------------------------------------------------


def test_jsonl_rotation_segments_and_discovery(tmp_path):
    p = str(tmp_path / "events.r0.jsonl")
    reg = MetricsRegistry()
    reg.add_sink(JsonlSink(p, max_bytes=256))
    for i in range(60):
        reg.emit("step", step=i, step_time_s=0.1)
    reg.close()
    segs = sorted(os.listdir(tmp_path))
    assert "events.r0.jsonl" in segs
    assert "events.r0.jsonl.1" in segs and len(segs) > 3  # actually rotated
    # read_jsonl stitches the segments back in chronological order.
    events = read_jsonl(p)
    assert [e["step"] for e in events] == list(range(60))
    # The reducer sees the whole rotated history as one shard.
    red = reduce_shards(str(tmp_path))
    assert red["hosts"]["0"]["steps"] == 60
    # Rotation keyed per shard: a sibling shard's segments are separate.
    reg2 = MetricsRegistry(process_index=1)
    reg2.add_sink(JsonlSink(str(tmp_path / "events.r1.jsonl")))
    reg2.emit("step", step=0, step_time_s=0.5)
    reg2.close()
    assert reduce_shards(str(tmp_path))["n_hosts"] == 2


def test_jsonl_no_rotation_by_default(tmp_path):
    p = str(tmp_path / "events.r0.jsonl")
    reg = MetricsRegistry()
    reg.add_sink(JsonlSink(p))
    for i in range(50):
        reg.emit("step", step=i)
    reg.close()
    assert os.listdir(tmp_path) == ["events.r0.jsonl"]
    assert len(read_jsonl(p)) == 50


# ---------------------------------------------------------------------------
# serving-aware shard reduction (satellite)
# ---------------------------------------------------------------------------


def _write_shard(obs_dir, proc, events):
    os.makedirs(obs_dir, exist_ok=True)
    with open(shard_path(str(obs_dir), proc), "w") as f:
        for e in events:
            f.write(json.dumps({"proc": proc, **e}) + "\n")


def test_reduce_shards_serving_only(tmp_path):
    """A serving-only run reduces to a typed summary, not silent None."""
    _write_shard(tmp_path, 0, [
        {"etype": "serve_request", "state": "done", "iteration": 9},
        {"etype": "serve_request", "state": "shed", "iteration": 11},
        {"etype": "serve_admit", "iteration": 2},
    ])
    red = reduce_shards(str(tmp_path))
    assert red is not None
    assert red["training_steps"] == 0
    assert red["serve"]["requests"] == 2
    assert red["serve"]["iterations"] == 11
    assert red["serve"]["by_state"] == {"done": 1, "shed": 1}
    assert red["hosts"]["0"]["steps"] == 0
    assert red["hosts"]["0"]["serve_requests"] == 2
    assert red["stragglers"] == [] and red["n_hosts"] == 1


def test_reduce_shards_mixed_training_and_serving(tmp_path):
    """Mixed fleet: step reduction unchanged, serve section added, and
    the serving-only host still appears in the table."""
    _write_shard(tmp_path, 0, [
        {"etype": "step", "step": 1, "step_time_s": 0.1},
        {"etype": "step", "step": 2, "step_time_s": 0.2},
    ])
    _write_shard(tmp_path, 1, [
        {"etype": "serve_request", "state": "done", "iteration": 4},
    ])
    red = reduce_shards(str(tmp_path))
    assert red["hosts"]["0"]["steps"] == 2
    assert red["hosts"]["1"]["steps"] == 0
    assert red["hosts"]["1"]["serve_requests"] == 1
    assert red["serve"]["requests"] == 1
    assert red["n_hosts"] == 2
    assert red["step_time_s"]["mean"] == pytest.approx(0.15)


def test_reduce_shards_empty_still_none(tmp_path):
    _write_shard(tmp_path, 0, [{"etype": "run_start"}])
    assert reduce_shards(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# span API + Perfetto export (tentpole 1)
# ---------------------------------------------------------------------------


def test_tracer_span_context_manager_and_attrs():
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    tr = Tracer(reg, clock=iter([1.0, 3.5]).__next__)
    with tr.span("work", cat="test", foo=1) as sp:
        sp.set(bar="x")
    (e,) = sink.events
    assert e["etype"] == "span" and e["name"] == "work"
    assert e["t0"] == 1.0 and e["dur_s"] == 2.5
    assert e["foo"] == 1 and e["bar"] == "x" and e["ph"] == "X"


def test_tracer_explicit_start_end_cross_scope():
    """The serving pattern: a request span opened at one iteration and
    closed many iterations later, by handle."""
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    t = {"v": 0.0}
    tr = Tracer(reg, clock=lambda: t["v"])
    h = tr.start("req", tid="r1", rid="r1")
    t["v"] = 5.0
    tr.end(h, outcome="done")
    tr.end(h)  # double-end is a no-op
    (e,) = sink.events
    assert e["tid"] == "r1" and e["dur_s"] == 5.0 and e["outcome"] == "done"


def test_tracer_span_records_exception_and_instant():
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    tr = Tracer(reg)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    tr.instant("mark", tid="r1", t=2.0, rid="r1")
    assert sink.events[0]["error"] == "RuntimeError"
    assert sink.events[1]["ph"] == "i" and sink.events[1]["dur_s"] == 0.0


def test_tracer_disabled_is_silent():
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    tr = Tracer(reg, enabled=False)
    with tr.span("a") as sp:
        sp.set(x=1)
    tr.emit_span("b", 0.0, 1.0)
    tr.instant("c")
    assert tr.start("d") is None
    assert sink.events == []


def test_perfetto_export_schema():
    """Acceptance satellite: required keys ph/ts/dur/pid/tid/name on
    every trace event, monotonic ts, instants attached to the owning
    request's track, thread-name metadata present."""
    reg = MetricsRegistry(process_index=2)
    sink = reg.add_sink(MemorySink())
    tr = Tracer(reg, clock=lambda: 0.0)
    tr.emit_span("req.queued", 10.0, 11.0, tid="r1", rid="r1")
    tr.emit_span("req.prefill", 11.0, 11.5, tid="r1", rid="r1")
    tr.emit_span("req.decode", 11.5, 14.0, tid="r1", rid="r1")
    reg.emit("serve_evict", rid="r1", reason="preempted")  # ts-stamped
    tr.instant("req.done", tid="r1", t=14.0, rid="r1")
    out = to_chrome_trace(sink.events)
    rows = [e for e in out["traceEvents"] if e["ph"] != "M"]
    assert len(rows) == 5
    for e in rows:
        for k in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert k in e, e
        assert e["pid"] == 2
    ts = [e["ts"] for e in rows]
    assert ts == sorted(ts) and ts[0] == 0.0  # normalized + monotonic
    # All five share the request track (the evict instant has no tid
    # field — its rid routes it), and metadata names the track.
    assert len({e["tid"] for e in rows}) == 1
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert any(m["args"]["name"] == "r1" for m in meta)
    xs = [e for e in rows if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["req.queued", "req.prefill", "req.decode"]
    assert xs[0]["dur"] == pytest.approx(1e6)


# ---------------------------------------------------------------------------
# flight recorder (tentpole 2)
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_bound_and_dump(tmp_path):
    reg = MetricsRegistry()
    rec = reg.add_sink(FlightRecorder(capacity=8))
    for i in range(30):
        reg.emit("step", step=i)
    assert len(rec.events) == 8
    assert [e["step"] for e in rec.events] == list(range(22, 30))
    path = rec.dump(str(tmp_path / "flight.json"), reason="test", step=29)
    body = load_flight_dump(path)
    assert body["reason"] == "test" and body["step"] == 29
    assert body["n_events"] == 8
    assert body["events"][-1]["step"] == 29  # last event = failing step
    assert not [p for p in os.listdir(tmp_path) if ".tmp" in p]  # atomic


def test_warmupless_first_step_emits_one_compile_span(tmp_path):
    """A warmup-less first step's cold compile drains through the
    startup path; the step-span synthesis must NOT emit a second
    'compile' span for the same seconds (the attribution table sums per
    name). A steady-state recompile still gets its own span."""
    import jax.numpy as jnp

    tele = Telemetry(output_dir=str(tmp_path))
    try:
        tele.on_step_start(1)
        jax.jit(lambda v: v * 2 + tmp_path.stat().st_mode)(jnp.ones(3)).block_until_ready()
        tele.on_step_end(1, elapsed_s=0.1)
        tele.on_step_start(2)
        jax.jit(lambda v: v * 3 - 1)(jnp.ones((2, 2))).block_until_ready()
        tele.on_step_end(2, elapsed_s=0.2)
        tele.flush()
    finally:
        tele.close()
    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    compile_spans = [e for e in events
                     if e["etype"] == "span" and e["name"] == "compile"]
    assert [e["step"] for e in compile_spans] == [0, 2]
    assert compile_spans[1].get("recompile") is True


def test_telemetry_dump_on_anomaly_and_hung_step(tmp_path):
    tele = Telemetry(output_dir=str(tmp_path))
    try:
        tele.on_step_start(1)
        tele.on_step_end(1, elapsed_s=0.1)
        tele.on_anomaly(1, reason="non-finite loss", action="warn")
        p = os.path.join(str(tmp_path), "obs", "flight.r0.json")
        body = load_flight_dump(p)
        assert body["reason"].startswith("anomaly")
        assert any(e["etype"] == "anomaly" for e in body["events"])
        # The per-step spans made it into the ring before the trip.
        assert any(e["etype"] == "span" and e["name"] == "step"
                   for e in body["events"])
        tele.on_hung_step(2, duration_s=9.9)
        assert load_flight_dump(p)["reason"] == "hung_step"
    finally:
        tele.close()


# ---------------------------------------------------------------------------
# SLO monitor (tentpole 4)
# ---------------------------------------------------------------------------


def test_slo_config_validation():
    with pytest.raises(ValueError):
        SloConfig(window=1)
    with pytest.raises(ValueError):
        SloConfig(check_every=0)
    with pytest.raises(ValueError):
        SloConfig(ttft_p99_s=-1.0)
    with pytest.raises(ValueError):
        SloConfig(shed_rate=1.5)


def test_slo_monitor_edge_triggered_breach_and_recovery():
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    mon = SloMonitor.from_config(
        SloConfig(ttft_p99_s=0.5, window=8, min_samples=2), reg,
        runtime="serve",
    )
    assert mon is not None
    mon.observe("serve_ttft_s", 0.9)
    mon.observe("serve_ttft_s", 0.95)
    assert mon.evaluate(iteration=1) and mon.degrade_active
    mon.evaluate(iteration=2)  # still breaching: NO second breach event
    breaches = [e for e in sink.events if e["etype"] == "slo_breach"]
    assert len(breaches) == 1
    b = breaches[0]
    assert b["objective"] == "ttft_p99_s" and b["value"] > b["threshold"]
    assert b["iteration"] == 1
    assert reg.snapshot()["slo_breaches"] == 1
    for _ in range(8):
        mon.observe("serve_ttft_s", 0.01)
    assert not mon.evaluate(iteration=3) and not mon.degrade_active
    assert [e["etype"] for e in sink.events][-1] == "slo_recovered"


def test_slo_monitor_rate_objective_and_off_by_default():
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())
    assert SloMonitor.from_config(SloConfig(), reg) is None  # all off
    assert SloMonitor.from_config(None, reg) is None
    mon = SloMonitor.from_config(
        SloConfig(shed_rate=0.25, window=8, min_samples=4), reg,
        runtime="serve",
    )
    for bad in (True, True, False, False):
        mon.observe_outcome("serve_outcome_shed", bad)
    (b,) = mon.evaluate(iteration=5)
    assert b["kind"] == "rate" and b["value"] == 0.5
    # A rate breach alone must NOT activate latency degradation.
    assert not mon.degrade_active
    assert [e for e in sink.events if e["etype"] == "slo_breach"]


# ---------------------------------------------------------------------------
# serving integration: spans, SLO wiring, chaos acceptance
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_model():
    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.models.gpt import GPT

    cfg = ModelConfig(
        vocab_size=VOCAB, d_model=64, n_layers=4, n_heads=4, d_ff=128,
        max_seq_len=32, dropout=0.0, param_dtype="float32",
        compute_dtype="float32", attention="dense",
    )
    model = GPT(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    return model, params


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, size=n).tolist() for n in sizes]


def test_serve_clean_run_waterfall_matches_slo_timings(served_model):
    """Acceptance (clean leg): every completed request shows a full
    queued→prefill→decode chain whose span edges reproduce the
    TTFT/queue-wait the registry histograms observed — same clock, same
    numbers."""
    from dtc_tpu.serve import Request, RequestState, ServingEngine

    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=8, max_new_tokens=4,
        prefill_bucket=8,
    ))
    sink = eng.reg.add_sink(MemorySink())
    for i, p in enumerate(_prompts(0, [5, 7, 6])):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=4))
    res = eng.run(max_steps=200)
    assert all(r.state is RequestState.DONE for r in res.values())

    spans = [e for e in sink.events if e["etype"] == "span"]
    by_rid = {}
    for e in spans:
        if "rid" in e:
            by_rid.setdefault(e["rid"], []).append(e)
    ttfts, qwaits = [], []
    for rid, r in res.items():
        mine = {e["name"]: e for e in by_rid[rid]}
        assert {"req.queued", "req.prefill", "req.decode", "req.done"} <= set(mine)
        queued, prefill = mine["req.queued"], mine["req.prefill"]
        # Span-derived SLO numbers == the engine's own (one clock).
        ttft = prefill["t0"] + prefill["dur_s"] - queued["t0"]
        qwait = queued["dur_s"]
        assert ttft == pytest.approx(r.ttft_s, abs=1e-4)
        # queue wait ends at admission START; the engine stamps
        # admitted_t after the prefill returns, so the span's queue wait
        # plus the prefill duration is the recorded queue_wait_s.
        assert qwait + prefill["dur_s"] == pytest.approx(
            r.queue_wait_s, abs=1e-4
        )
        assert mine["req.decode"]["n_tokens"] == len(r.tokens)
        ttfts.append(r.ttft_s)
        qwaits.append(r.queue_wait_s)
    # Registry-histogram percentiles match nearest-rank on the same
    # population to within one bucket.
    h50 = eng.reg.histogram("serve_ttft_s").percentile(0.5)
    exact = nearest_rank(ttfts, 0.5)
    assert h50 == pytest.approx(exact, rel=HIST_BUCKET_GROWTH - 1 + 1e-6)
    # decode_step scheduler spans exist, one per working iteration.
    assert any(e["name"] == "decode_step" for e in spans)


def test_serve_chaos_acceptance_dump_and_ordered_trace(served_model, tmp_path):
    """ISSUE 7 acceptance: serve preemption + poisoned logits (+ a tight
    TTFT SLO) yield (a) a flight-recorder dump, (b) a Perfetto-loadable
    trace where the preempted request's chain queued→prefill→evict→
    requeued→prefill→decode→done is present and ordered, and (c)
    slo_breach + recovery events in the same stream."""
    from dtc_tpu.serve import Request, RequestState, ServingEngine

    model, params = served_model
    tele = Telemetry.for_serving(str(tmp_path))
    scfg = ServeConfig(
        slots=1, page_size=4, queue_depth=8, max_new_tokens=6,
        prefill_bucket=8,
        chaos=ChaosConfig(
            enabled=True, serve_preempt_at_step=2,
            serve_poison_logits_at_step=4,
        ),
        slo=SloConfig(ttft_p99_s=1e-9, window=8, min_samples=1,
                      check_every=1),
        # Watchdog off so the LAST flight dump is deterministically the
        # chaos one (a retry-slowed iteration could otherwise flag).
        watchdog=WatchdogConfig(enabled=False),
    )
    eng = ServingEngine(model, params, scfg, telemetry=tele)
    for i, p in enumerate(_prompts(1, [5, 6])):
        eng.submit(Request(rid=f"c{i}", prompt=p, max_new_tokens=6))
    res = eng.run(max_steps=300)
    tele.flush()
    assert all(r.state is RequestState.DONE for r in res.values())
    snap = eng.reg.snapshot()
    assert snap["serve_preemptions"] == 1 and snap["chaos_injections"] == 2
    assert snap["serve_retries"] >= 1
    assert snap["slo_breaches"] >= 1
    victim = next(rid for rid, r in res.items() if r.n_evictions == 1)

    # (a) the chaos run dumped a flight record with the chaos evidence.
    dump = load_flight_dump(str(tmp_path / "obs" / "flight.r0.json"))
    assert dump["reason"].startswith("chaos:")
    assert any(e["etype"] == "chaos" for e in dump["events"])

    tele.close()
    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    etypes = {e["etype"] for e in events}
    assert {"span", "chaos", "serve_evict", "slo_breach", "recovery"} <= etypes

    # (b) the victim's chain, ordered: two queued/prefill pairs around
    # the evict mark, decode after the first token, terminal last.
    mine = [
        e for e in events
        if e.get("rid") == victim and (
            e["etype"] == "span" or e["etype"] == "serve_evict"
        )
    ]
    mine.sort(key=lambda e: e.get("t0", e.get("ts")))
    names = [e.get("name", e["etype"]) for e in mine]
    assert names.count("req.queued") == 2 and names.count("req.prefill") == 2
    assert names.index("req.queued") < names.index("serve_evict")
    assert names[-1] == "req.done"
    assert names.index("serve_evict") < len(names) - 1 - names[::-1].index(
        "req.prefill"
    ), "re-prefill must follow the eviction"
    assert "req.decode" in names

    # (c) Perfetto export of the whole run loads with monotonic ts and
    # carries the breach + chaos instants.
    out = to_chrome_trace(events)
    rows = [e for e in out["traceEvents"] if e["ph"] != "M"]
    ts = [e["ts"] for e in rows]
    assert ts == sorted(ts)
    row_names = {e["name"] for e in rows}
    assert "slo_breach" in row_names and "chaos" in row_names
    assert {"req.queued", "req.prefill", "req.decode"} <= row_names


def test_serve_watchdog_fire_dumps_flight(served_model, tmp_path):
    """Satellite dump path: a chaos scheduler stall trips the serving
    watchdog; the dump is loadable and its last decode_step span is the
    flagged iteration's."""
    from dtc_tpu.serve import Request, ServingEngine

    model, params = served_model
    tele = Telemetry.for_serving(str(tmp_path))
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=10,
        prefill_bucket=8,
        watchdog=WatchdogConfig(enabled=True, factor=4.0, min_samples=3),
        chaos=ChaosConfig(enabled=True, serve_stall_at_step=8, stall_s=1.0),
    ), telemetry=tele)
    eng.submit(Request(rid="w", prompt=_prompts(2, [6])[0], max_new_tokens=10))
    eng.run(max_steps=100)
    tele.flush()
    assert eng.reg.snapshot().get("serve_hung_steps", 0) >= 1
    dump = load_flight_dump(str(tmp_path / "obs" / "flight.r0.json"))
    assert dump["reason"] == "hung_step"
    flagged = dump["iteration"]
    dsteps = [e for e in dump["events"]
              if e.get("etype") == "span" and e.get("name") == "decode_step"]
    assert dsteps and dsteps[-1]["iteration"] == flagged
    tele.close()


def test_serve_slo_breach_activates_degrade(served_model):
    """The scheduler reacts to the monitor: with a breaching latency SLO
    and degrade enabled, new admissions get the degraded token cap even
    though the queue watermark was never crossed."""
    from dtc_tpu.serve import Request, RequestState, ServingEngine

    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=16, max_new_tokens=8,
        prefill_bucket=8, degrade_watermark=0.0, degrade_max_new_tokens=2,
        slo=SloConfig(ttft_p99_s=1e-9, window=8, min_samples=1,
                      check_every=1),
    ))
    p0, p1 = _prompts(3, [5, 6])
    eng.submit(Request(rid="a", prompt=p0, max_new_tokens=8))
    eng.run(max_steps=100)
    assert not eng.results["a"].degraded  # no samples yet at its admission
    eng.submit(Request(rid="b", prompt=p1, max_new_tokens=8))
    res = eng.run(max_steps=100)
    assert res["b"].state is RequestState.DONE
    assert res["b"].degraded and len(res["b"].tokens) == 2
    assert eng.reg.snapshot()["serve_degraded"] == 1


# ---------------------------------------------------------------------------
# trainer integration: spans in the shard, dumps on chaos paths
# ---------------------------------------------------------------------------


def test_trainer_emits_step_spans_and_slo_breach(tiny_model_cfg, opt_cfg, tmp_path):
    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg(
        "dp", steps=3, log_every=1, output_dir=str(tmp_path),
        warmup_steps=1,
        slo=SloConfig(step_time_p99_s=1e-9, window=8, min_samples=1,
                      check_every=1),
    )
    train(cfg, tiny_model_cfg, opt_cfg)
    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    spans = [e for e in events if e["etype"] == "span"]
    steps = [e for e in spans if e["name"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3]
    by_step = {e["step"]: e for e in events if e["etype"] == "step"}
    for e in steps:
        # Span duration == the step event's measured step time.
        assert e["dur_s"] == pytest.approx(
            by_step[e["step"]]["step_time_s"], abs=2e-6
        )
    assert any(e["name"] == "dispatch" for e in spans)
    # An impossible step-time objective breached online, during the run.
    assert any(e["etype"] == "slo_breach" for e in events)


def test_trainer_trace_off_emits_no_spans(tiny_model_cfg, opt_cfg, tmp_path):
    from dataclasses import replace

    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg("dp", steps=2, output_dir=str(tmp_path))
    cfg = replace(cfg, obs=replace(cfg.obs, trace=False))
    train(cfg, tiny_model_cfg, opt_cfg)
    events = read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))
    assert events and not [e for e in events if e["etype"] == "span"]


def test_trainer_chaos_nan_anomaly_dumps_flight(tiny_model_cfg, opt_cfg, tmp_path):
    """Satellite dump path: a chaos NaN poison trips the anomaly guard
    (no checkpoint -> warn) and the dump's timeline ends at the failing
    step."""
    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg(
        "dp", steps=2, log_every=2, output_dir=str(tmp_path),
        resilience=ResilienceConfig(
            chaos=ChaosConfig(enabled=True, nan_at_step=2),
        ),
    )
    train(cfg, tiny_model_cfg, opt_cfg)
    dump = load_flight_dump(str(tmp_path / "obs" / "flight.r0.json"))
    assert dump["reason"].startswith("anomaly: non-finite loss")
    assert dump["step"] == 2
    anomalies = [e for e in dump["events"] if e["etype"] == "anomaly"]
    assert anomalies and anomalies[-1]["step"] == 2
    step_spans = [e for e in dump["events"]
                  if e["etype"] == "span" and e["name"] == "step"]
    assert step_spans and step_spans[-1]["step"] == 2  # last span = failing step


def test_trainer_chaos_sigterm_dumps_flight(tiny_model_cfg, opt_cfg, tmp_path):
    """Satellite dump path: simulated preemption (real SIGTERM through
    the real handler) leaves a dump before the graceful stop."""
    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg(
        "dp", steps=6, log_every=2, output_dir=str(tmp_path),
        checkpoint_every=2,
        resilience=ResilienceConfig(
            chaos=ChaosConfig(enabled=True, sigterm_at_step=3),
        ),
    )
    res = train(cfg, tiny_model_cfg, opt_cfg)
    assert len(res.losses) == 3  # stopped at the preemption step
    dump = load_flight_dump(str(tmp_path / "obs" / "flight.r0.json"))
    assert dump["reason"] == "sigterm" and dump["step"] == 3
    assert any(e["etype"] == "chaos" and e.get("kind") == "sigterm"
               for e in dump["events"])


# ---------------------------------------------------------------------------
# trace_report (offline leg)
# ---------------------------------------------------------------------------


def test_trace_report_table_waterfall_compare(tmp_path, capsys):
    from scripts.trace_report import (
        compare_runs, load_events, request_waterfalls, span_table,
    )

    def fake_run(d, scale):
        os.makedirs(d)
        reg = MetricsRegistry()
        reg.add_sink(JsonlSink(os.path.join(d, "events.r0.jsonl")))
        tr = Tracer(reg, clock=lambda: 0.0)
        t = 0.0
        for step in range(4):
            tr.emit_span("step", t, t + scale, cat="train", step=step)
            t += scale
        tr.emit_span("req.queued", t, t + 1, tid="q1", rid="q1")
        tr.emit_span("req.prefill", t + 1, t + 2, tid="q1", rid="q1")
        reg.emit("serve_evict", rid="q1", reason="preempted")
        reg.close()

    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    fake_run(a, 0.1)
    fake_run(b, 0.2)
    ev = load_events(a)
    table = span_table(ev)
    step_row = next(r for r in table if r["name"] == "step")
    assert step_row["count"] == 4
    assert step_row["p50_s"] == pytest.approx(0.1)
    falls = request_waterfalls(ev)
    assert "q1" in falls
    assert [x["name"] for x in falls["q1"]][:2] == ["req.queued", "req.prefill"]
    assert any(x["name"].startswith("serve_evict") for x in falls["q1"])
    rows = compare_runs(ev, load_events(b))
    step_cmp = next(r for r in rows if r["name"] == "train/step")
    assert step_cmp["p50_delta_pct"] == pytest.approx(100.0, abs=1.0)


def test_trace_report_resolves_obs_subdir(tmp_path):
    from scripts.trace_report import load_events

    obs = tmp_path / "run" / "obs"
    os.makedirs(obs)
    reg = MetricsRegistry()
    reg.add_sink(JsonlSink(str(obs / "events.r0.jsonl")))
    reg.emit("run_start")
    reg.close()
    assert load_events(str(tmp_path / "run"))[0]["etype"] == "run_start"
    assert load_events(str(obs))[0]["etype"] == "run_start"
    with pytest.raises(SystemExit):
        load_events(str(tmp_path / "empty"))
