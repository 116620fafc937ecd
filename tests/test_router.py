"""Fleet-router tests (ISSUE 13): tenant-aware routing over N engine
replicas with chaos-verified failover and zero silent drops.

The anchor invariant, lifted from test_serve.py to the fleet: the router
is a pure REORDERING of single-stream greedy decode — whatever dies
(replica kill, partition, stall), every COMPLETED request's tokens are
token-for-token ``generate()``'s, and every non-completed request
carries a typed error plus an obs event. Plus the engine-level satellite
contracts: graceful shutdown/drain, cross-replica resume accounting, and
the AdapterStore eviction/queued-request race.
"""

import json
import os
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dtc_tpu.config.schema import (
    AdapterConfig,
    ChaosConfig,
    ModelConfig,
    RouterConfig,
    ServeConfig,
    StreamRetryConfig,
)
from dtc_tpu.generate import generate
from dtc_tpu.models.gpt import GPT
from dtc_tpu.obs import MemorySink, reduce_shards
from dtc_tpu.serve import (
    EngineClosedError,
    FleetRouter,
    FleetSaturatedError,
    QueueFullError,
    ReplicaState,
    Request,
    RequestFailedError,
    RequestState,
    ServingEngine,
    UnknownAdapterError,
)

VOCAB = 61


def _model_and_params(adapter_rank: int = 0):
    cfg = ModelConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dropout=0.0, param_dtype="float32",
        compute_dtype="float32", attention="dense",
        adapter=AdapterConfig(rank=adapter_rank),
    )
    model = GPT(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def fleet_model():
    return _model_and_params()


@pytest.fixture(scope="module")
def lora_model():
    from dtc_tpu.adapters import init_lora

    model, params = _model_and_params(adapter_rank=4)
    factors = {
        "t1": init_lora(model, seed=1), "t2": init_lora(model, seed=2),
    }
    return model, params, factors


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, size=n).tolist() for n in sizes]


def _refs(model, params, prompts, n, lora=None):
    return [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None], n, lora=lora,
        ))[0].tolist()
        for p in prompts
    ]


def _rcfg(n_replicas=3, serve=None, **kw):
    kw.setdefault("retry", StreamRetryConfig(
        max_attempts=2, backoff_s=0.0, backoff_max_s=0.0, jitter=0.0))
    return RouterConfig(
        n_replicas=n_replicas,
        serve=serve or ServeConfig(
            slots=1, page_size=4, queue_depth=4, max_new_tokens=8,
            prefill_bucket=8,
        ),
        **kw,
    )


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_router_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(n_replicas=0)
    with pytest.raises(ValueError):
        RouterConfig(placement="coin_flip")
    with pytest.raises(ValueError):
        RouterConfig(heartbeat_miss_limit=0)
    # ISSUE 17: a chaos victim beyond the CONSTRUCTION-time fleet size is
    # legal config now — the replica set is dynamic (spawn/retire), so
    # the bound is judged when the fault fires (see
    # test_chaos_stale_target_is_typed_error_at_fire_time).
    RouterConfig(n_replicas=2, chaos=ChaosConfig(
        enabled=True, fleet_kill_replica_at_step=3, fleet_target_replica=5))
    RouterConfig(n_replicas=2, chaos=ChaosConfig(
        enabled=True, fleet_kill_replica_at_step=3, fleet_target_replica=1))


def test_chaos_stale_target_is_typed_error_at_fire_time(fleet_model):
    """Satellite (ISSUE 17): a fleet-chaos victim that does not exist at
    FIRE time raises a typed ChaosTargetError — never a silent no-op or
    a clamp onto some other replica — while a target only reachable via
    a later spawn fires correctly."""
    from dtc_tpu.resilience.errors import ChaosTargetError

    model, params = fleet_model
    # Stale target: replica 5 never exists in a 2-replica fleet.
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2,
        chaos=ChaosConfig(enabled=True, fleet_kill_replica_at_step=1,
                          fleet_target_replica=5),
    ))
    router.submit(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=4))
    with pytest.raises(ChaosTargetError, match="fleet_target_replica 5"):
        router.step()
    router.close()

    # The same victim id is LEGAL once a spawn has minted it: the drill
    # fires on the spawned replica (construction would have rejected it
    # under the old construction-time check).
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2,
        chaos=ChaosConfig(enabled=True, fleet_kill_replica_at_step=1,
                          fleet_target_replica=2),
    ), router_proc=64)
    router.spawn_replica()
    router.submit(Request(rid="b", prompt=[1, 2, 3], max_new_tokens=4))
    router.run()
    assert router.replicas[2].state is ReplicaState.DEAD
    assert router.results["b"].state is RequestState.DONE
    router.close()


def test_fleet_chaos_config_validation():
    with pytest.raises(ValueError):
        ChaosConfig(fleet_partition_iters=0)
    with pytest.raises(ValueError):
        ChaosConfig(fleet_target_replica=-1)


def test_router_config_yaml_loads():
    """The committed configs/router_config.yaml round-trips through the
    loader with the committed model config."""
    from dtc_tpu.config.loader import load_router_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rcfg, mcfg = load_router_config(
        os.path.join(root, "configs", "router_config.yaml"),
        os.path.join(root, "configs", "model_config.yaml"),
    )
    assert rcfg.n_replicas == 3 and rcfg.placement == "affinity"
    assert rcfg.serve.slots == 4 and rcfg.watchdog.enabled
    assert mcfg.d_model > 0


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def test_tenant_affinity_routes_to_residency(lora_model):
    """Adapter residency IS cache affinity: the first tenant request
    lazy-loads the factors somewhere; every later one follows them (one
    load total), while base requests spread by least-loaded."""
    model, params, factors = lora_model
    router = FleetRouter(model, params, _rcfg(
        serve=ServeConfig(slots=2, page_size=4, queue_depth=8,
                          max_new_tokens=4, prefill_bucket=8,
                          max_adapters=4)))
    router.register_adapter("t1", factors["t1"])
    prompts = _prompts(0, (4, 5, 6, 4, 5, 6))
    homes = []
    for i in range(3):
        router.submit(Request(rid=f"a{i}", prompt=prompts[i],
                              max_new_tokens=4, adapter="t1"))
        homes.append(router.records[f"a{i}"].replica)
    assert len(set(homes)) == 1, f"tenant spread across {homes}"
    assert router.reg.counter("router_adapter_loads").value == 1
    base_homes = []
    for i in range(3, 6):
        router.submit(Request(rid=f"b{i}", prompt=prompts[i],
                              max_new_tokens=4))
        base_homes.append(router.records[f"b{i}"].replica)
    # Least-loaded spreads the base requests off the tenant's busy home.
    assert len(set(base_homes)) > 1
    res = router.run(max_steps=300)
    assert all(r.state is RequestState.DONE for r in res.values())


def test_prefix_affinity_routes_to_prefix_store(fleet_model):
    """A shared system prompt routes to the replica whose prefix store
    already holds its KV — even when that replica is more loaded."""
    model, params = fleet_model
    router = FleetRouter(model, params, _rcfg(
        serve=ServeConfig(slots=2, page_size=4, queue_depth=8,
                          max_new_tokens=4, prefill_bucket=8)))
    rng = np.random.RandomState(3)
    prefix = rng.randint(0, VOCAB, size=6).tolist()
    p1 = prefix + rng.randint(0, VOCAB, size=3).tolist()
    p2 = prefix + rng.randint(0, VOCAB, size=4).tolist()
    router.submit(Request(rid="p1", prompt=p1, max_new_tokens=4,
                          shared_prefix_len=len(prefix)))
    home = router.records["p1"].replica
    router.step()  # admission builds the prefix store entry on `home`
    router.submit(Request(rid="p2", prompt=p2, max_new_tokens=4,
                          shared_prefix_len=len(prefix)))
    assert router.records["p2"].replica == home
    res = router.run(max_steps=200)
    assert all(r.state is RequestState.DONE for r in res.values())
    # The prefix was built once, fleet-wide.
    builds = sum(
        rep.engine.reg.counter("serve_prefix_builds").value
        for rep in router.replicas
    )
    hits = sum(
        rep.engine.reg.counter("serve_prefix_hits").value
        for rep in router.replicas
    )
    assert builds == 1 and hits >= 1


def test_round_robin_placement(fleet_model):
    model, params = fleet_model
    router = FleetRouter(model, params, _rcfg(placement="round_robin"))
    prompts = _prompts(1, (4, 4, 4))
    reps = []
    for i, p in enumerate(prompts):
        router.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=2))
        reps.append(router.records[f"r{i}"].replica)
    assert len(set(reps)) == 3
    router.run(max_steps=200)


# ---------------------------------------------------------------------------
# fleet backpressure
# ---------------------------------------------------------------------------

def test_fleet_backpressure_is_typed_and_coordinated(fleet_model):
    """The router routes AROUND full replicas (coordinating, not
    overriding, per-replica admission); only when every live queue is
    full does submit raise — typed FleetSaturatedError (a
    QueueFullError), never a silent drop. Every accepted rid still
    reaches a terminal result."""
    model, params = fleet_model
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2,
        serve=ServeConfig(slots=1, page_size=4, queue_depth=2,
                          max_new_tokens=4, prefill_bucket=8,
                          shed_watermark=0.0)))
    prompts = _prompts(2, tuple([4] * 8))
    accepted, rejected = [], 0
    for i, p in enumerate(prompts):
        try:
            router.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=4))
            accepted.append(f"r{i}")
        except FleetSaturatedError as e:
            assert isinstance(e, QueueFullError)
            rejected += 1
    assert rejected > 0 and len(accepted) == 4  # 2 replicas x queue 2
    # Accepted work spread over BOTH replicas (routed around the full one).
    assert len({router.records[r].replica for r in accepted}) == 2
    assert router.reg.counter("router_rejected").value == rejected
    res = router.run(max_steps=300)
    assert sorted(res) == sorted(accepted)
    assert all(r.state is RequestState.DONE for r in res.values())


# ---------------------------------------------------------------------------
# health state machine
# ---------------------------------------------------------------------------

def test_stall_degrades_then_recovers(fleet_model):
    """An injected fleet stall (outside the engine's timed iteration —
    the replica-level watchdog's job) marks the victim DEGRADED: new
    placements avoid it while peers have room, and it recovers HEALTHY
    after the hold window."""
    model, params = fleet_model
    # Real clock: the replica watchdog judges real step durations (the
    # healthy median is milliseconds of tiny-model decode; the 1 s stall
    # is a ~100x outlier — far past the default 8x factor).
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2, degraded_hold_iters=3,
        serve=ServeConfig(slots=2, page_size=4, queue_depth=8,
                          max_new_tokens=24, prefill_bucket=8),
        # Step 12: past the replica watchdog's min_samples=8 default, so
        # the trailing median is armed when the stall lands.
        chaos=ChaosConfig(enabled=True, fleet_stall_replica_at_step=12,
                          fleet_target_replica=0, stall_s=1.0),
    ))
    # Keep the victim working so the watchdog has a healthy-median
    # baseline of real decode iterations before the stall lands.
    p = _prompts(4, (4,))[0]
    router.submit(Request(rid="warm", prompt=p, max_new_tokens=24))
    victim = router.replicas[0]
    sink = router.reg.add_sink(MemorySink())
    for _ in range(20):
        router.step()
        if victim.state is ReplicaState.DEGRADED:
            break
    assert victim.state is ReplicaState.DEGRADED
    assert victim.hung_flags >= 1
    # New work lands on the healthy peer while it has room.
    router.submit(Request(rid="after", prompt=p, max_new_tokens=4))
    assert router.records["after"].replica == 1
    # ...and the victim recovers after the hold window.
    for _ in range(40):
        router.step()
        if victim.state is ReplicaState.HEALTHY:
            break
    assert victim.state is ReplicaState.HEALTHY
    states = [e for e in sink.events if e["etype"] == "router_replica_state"]
    assert [e["state"] for e in states][:2] == ["degraded", "healthy"]


def test_partition_short_heals_in_place(fleet_model):
    """A partition shorter than the heartbeat-miss budget: missed beats
    counted, nobody dies, nothing fails over, everything completes."""
    model, params = fleet_model
    prompts = _prompts(5, (4, 5))
    refs = _refs(model, params, prompts, 8)
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2, heartbeat_miss_limit=3,
        chaos=ChaosConfig(enabled=True, fleet_partition_at_step=2,
                          fleet_partition_iters=2, fleet_target_replica=0),
    ))
    for i, p in enumerate(prompts):
        router.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=8))
    res = router.run(max_steps=300)
    assert router.reg.counter("router_missed_heartbeats").value == 2
    assert router.reg.counter("router_replica_deaths").value == 0
    assert router.replicas[0].state is ReplicaState.HEALTHY
    for i in range(len(prompts)):
        assert res[f"r{i}"].state is RequestState.DONE
        assert res[f"r{i}"].tokens == refs[i]
        assert res[f"r{i}"].n_hops == 0


def test_partition_sustained_escalates_to_failover(fleet_model):
    """A partition outliving the miss budget: the replica is declared
    dead and its requests fail over — completed token-identical on the
    survivor."""
    model, params = fleet_model
    prompts = _prompts(6, (4, 5, 6, 4))
    refs = _refs(model, params, prompts, 8)
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2, heartbeat_miss_limit=2,
        serve=ServeConfig(slots=2, page_size=4, queue_depth=8,
                          max_new_tokens=8, prefill_bucket=8),
        chaos=ChaosConfig(enabled=True, fleet_partition_at_step=3,
                          fleet_partition_iters=50, fleet_target_replica=0),
    ))
    for i, p in enumerate(prompts):
        router.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=8))
    res = router.run(max_steps=400)
    assert router.replicas[0].state is ReplicaState.DEAD
    assert "heartbeat" in (router.replicas[0].dead_reason or "")
    assert router.reg.counter("router_failovers").value >= 1
    for i in range(len(prompts)):
        assert res[f"r{i}"].state is RequestState.DONE, res[f"r{i}"].error
        assert res[f"r{i}"].tokens == refs[i]


# ---------------------------------------------------------------------------
# failover accounting (satellite: requeue timing across hops)
# ---------------------------------------------------------------------------

def test_multi_hop_failover_restarts_queued_span_and_keeps_ttft(fleet_model):
    """The requeue-timing fix, regression-tested over a multi-hop chain:
    each hop restarts the ``req.queued`` span (span durations measure
    THIS hop's wait, not submit-to-now), while ``submitted_t`` — and so
    TTFT — stays anchored at the ORIGINAL submit, so fleet TTFT
    histograms include the full failover cost."""
    model, params = fleet_model
    clock = FakeClock()
    router = FleetRouter(model, params, _rcfg(
        n_replicas=3,
        serve=ServeConfig(slots=1, page_size=4, queue_depth=4,
                          max_new_tokens=10, prefill_bucket=8),
    ), clock=clock, sleep=clock.advance)
    sinks = [rep.engine.reg.add_sink(MemorySink()) for rep in router.replicas]
    p = _prompts(7, (5,))[0]
    ref = _refs(model, params, [p], 10)[0]

    router.submit(Request(rid="r0", prompt=p, max_new_tokens=10))
    first = router.records["r0"].replica
    clock.advance(100.0)          # 100 fake seconds queued on hop 0
    router.kill_replica(first, reason="test")   # hop 1: still queued
    assert router.records["r0"].hops == 1
    second = router.records["r0"].replica
    for _ in range(3):            # admit + a few tokens on the survivor
        clock.advance(0.01)
        router.step()
    assert len(router.records["r0"].tokens) >= 1
    clock.advance(5.0)
    router.kill_replica(second, reason="test")  # hop 2: mid-decode
    res = router.run(max_steps=200)["r0"]

    assert res.state is RequestState.DONE
    assert res.tokens == ref      # token-identical across two failovers
    assert res.n_hops == 2
    # TTFT anchored at the ORIGINAL submit: it must include the 100 s
    # spent before the first failover (the under-reporting this fixes).
    assert res.submitted_t == 0.0
    assert res.ttft_s is not None and res.ttft_s >= 100.0
    # Each admitted hop emitted its own restarted req.queued span whose
    # duration covers THIS hop's wait only (< the 100 s original wait).
    spans = [
        e for s in sinks for e in s.events
        if e["etype"] == "span" and e.get("name") == "req.queued"
        and e.get("rid") == "r0"
    ]
    assert len(spans) == 2        # one per admitted hop (hop 0 never admitted)
    assert all(e["dur_s"] < 100.0 for e in spans)


def test_failover_budget_exhaustion_is_typed(fleet_model):
    """Past failover_max_hops the request ends typed (RequestFailedError)
    — bounded ping-pong, zero silent drops."""
    model, params = fleet_model
    router = FleetRouter(model, params, _rcfg(
        n_replicas=3, failover_max_hops=1,
        serve=ServeConfig(slots=1, page_size=4, queue_depth=4,
                          max_new_tokens=16, prefill_bucket=8),
    ))
    p = _prompts(8, (5,))[0]
    router.submit(Request(rid="r0", prompt=p, max_new_tokens=16))
    router.kill_replica(router.records["r0"].replica, reason="test")
    assert router.records["r0"].hops == 1
    router.step()
    router.kill_replica(router.records["r0"].replica, reason="test")
    res = router.results["r0"]
    assert res.state is RequestState.FAILED
    assert isinstance(res.error, RequestFailedError)
    assert "failover budget" in str(res.error)


# ---------------------------------------------------------------------------
# tenants under failover (satellite: AdapterStore race)
# ---------------------------------------------------------------------------

def test_tenant_failover_reloads_factors_on_survivor(lora_model):
    """Killing a tenant's home replica re-routes its requests to a
    survivor WITHOUT the factors resident: the router re-loads them from
    its registry and the output stays token-identical to generate() with
    the adapter — never a silent slot-0 base-weight decode."""
    model, params, factors = lora_model
    refs_prompt = _prompts(9, (5,))[0]
    ref = _refs(model, params, [refs_prompt], 8, lora=factors["t1"])[0]
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2,
        serve=ServeConfig(slots=1, page_size=4, queue_depth=4,
                          max_new_tokens=8, prefill_bucket=8,
                          max_adapters=4)))
    router.register_adapter("t1", factors["t1"])
    router.submit(Request(rid="r0", prompt=refs_prompt, max_new_tokens=8,
                          adapter="t1"))
    home = router.records["r0"].replica
    router.step()
    router.kill_replica(home, reason="test")
    res = router.run(max_steps=200)["r0"]
    assert res.state is RequestState.DONE
    assert res.n_hops == 1
    assert res.tokens == ref
    survivor = router.replicas[1 - home]
    assert "t1" in survivor.resident_adapters()
    assert router.reg.counter("router_adapter_loads").value == 2


def test_unregistered_tenant_failover_fails_typed_never_base(lora_model):
    """The UnknownAdapterError path: factors loaded engine-direct on one
    replica only (NOT registered with the router). When that replica
    dies, no survivor can serve the tenant — the request must end typed
    with UnknownAdapterError as the cause, not complete on base weights."""
    model, params, factors = lora_model
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2,
        serve=ServeConfig(slots=1, page_size=4, queue_depth=4,
                          max_new_tokens=8, prefill_bucket=8,
                          max_adapters=4)))
    sink = router.reg.add_sink(MemorySink())
    router.replicas[0].engine.load_adapter("t2", factors["t2"])
    p = _prompts(10, (5,))[0]
    router.submit(Request(rid="r0", prompt=p, max_new_tokens=8, adapter="t2"))
    assert router.records["r0"].replica == 0  # affinity found the residency
    router.step()
    router.kill_replica(0, reason="test")
    res = router.results["r0"]
    assert res.state is RequestState.FAILED
    assert isinstance(res.error, RequestFailedError)
    assert isinstance(res.error.__cause__, UnknownAdapterError)
    # Typed terminal event in the stream — the no-silent-drop backstop.
    terminal = [e for e in sink.events if e["etype"] == "serve_request"]
    assert [e["rid"] for e in terminal] == ["r0"]
    assert terminal[0]["error"] == "RequestFailedError"


def test_adapter_store_eviction_cannot_race_queued_request(lora_model):
    """Engine-level satellite: a tenant with a request sitting in the
    queue is refcount-pinned — loading more tenants into a full store
    raises typed AdapterStoreFullError instead of evicting it, and the
    queued request decodes under ITS factors (token-identical). After
    the tenant drains, eviction may proceed; a new request for the
    evicted tenant is typed-rejected, never served on base weights."""
    from dtc_tpu.serve import AdapterStoreFullError

    model, params, factors = lora_model
    prompts = _prompts(11, (5, 4))
    ref = _refs(model, params, [prompts[0]], 6, lora=factors["t1"])[0]
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=6,
        prefill_bucket=8, max_adapters=2,  # exactly ONE tenant slot
    ))
    eng.load_adapter("t1", factors["t1"])
    eng.submit(Request(rid="q", prompt=prompts[0], max_new_tokens=6,
                       adapter="t1"))
    # Queued (not yet admitted): the refcount pin must block eviction.
    with pytest.raises(AdapterStoreFullError):
        eng.load_adapter("t2", factors["t2"])
    res = eng.run(max_steps=100)
    assert res["q"].state is RequestState.DONE
    assert res["q"].tokens == ref  # decoded under t1, not base
    # Drained: now the LRU eviction is legal...
    eng.load_adapter("t2", factors["t2"])
    # ...and the evicted tenant is typed-unknown, never silently base.
    with pytest.raises(UnknownAdapterError):
        eng.submit(Request(rid="q2", prompt=prompts[1], max_new_tokens=6,
                           adapter="t1"))


# ---------------------------------------------------------------------------
# graceful shutdown / drain (satellite)
# ---------------------------------------------------------------------------

def test_engine_shutdown_drain_finishes_and_refuses(fleet_model):
    """ServingEngine.shutdown(mode="drain"): in-flight requests finish
    (token-identical), later submits raise typed EngineClosedError, the
    bus is drained and the flight recorder dumped once."""
    model, params = fleet_model
    prompts = _prompts(12, (5, 6))
    refs = _refs(model, params, prompts, 6)
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=4, max_new_tokens=6,
        prefill_bucket=8))
    sink = eng.reg.add_sink(MemorySink())
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=6))
    res = eng.shutdown(mode="drain")
    for i in range(len(prompts)):
        assert res[f"r{i}"].state is RequestState.DONE
        assert res[f"r{i}"].tokens == refs[i]
    with pytest.raises(EngineClosedError):
        eng.submit(Request(rid="late", prompt=[1, 2], max_new_tokens=2))
    assert any(e["etype"] == "serve_shutdown" for e in sink.events)
    assert len(eng.recorder.events) > 0  # ring captured the run
    # Idempotent.
    assert eng.shutdown() is res or eng.shutdown() == res


def test_engine_shutdown_evict_is_typed_with_partial_tokens(fleet_model):
    """mode="evict" (hard preemption): queued AND mid-decode requests end
    FAILED + EngineClosedError with partial tokens preserved — typed,
    zero silent drops, one serve_request event each."""
    model, params = fleet_model
    prompts = _prompts(13, (5, 6, 4))
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=12,
        prefill_bucket=8))
    sink = eng.reg.add_sink(MemorySink())
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=12))
    for _ in range(4):
        eng.step()  # r0 mid-decode, r1/r2 queued
    res = eng.shutdown(mode="evict", reason="preemption notice")
    states = {rid: r.state for rid, r in res.items()}
    assert all(s is RequestState.FAILED for s in states.values())
    assert all(isinstance(r.error, EngineClosedError) for r in res.values())
    assert len(res["r0"].tokens) >= 1  # partial progress preserved
    terminal = [e for e in sink.events if e["etype"] == "serve_request"]
    assert sorted(e["rid"] for e in terminal) == sorted(res)


def test_router_drain_on_sigterm(fleet_model):
    """SIGTERM = fleet drain: the handler flags, run() drains every
    replica through the engine shutdown contract, every accepted request
    terminal, every replica retired DEAD("drained")."""
    model, params = fleet_model
    prompts = _prompts(14, (5, 6, 4))
    refs = _refs(model, params, prompts, 6)
    router = FleetRouter(model, params, _rcfg(
        n_replicas=2,
        serve=ServeConfig(slots=1, page_size=4, queue_depth=4,
                          max_new_tokens=6, prefill_bucket=8)))
    router.install_sigterm()
    try:
        for i, p in enumerate(prompts):
            router.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=6))
        os.kill(os.getpid(), signal.SIGTERM)
        res = router.run(max_steps=300)
    finally:
        router.restore_sigterm()
    for i in range(len(prompts)):
        assert res[f"r{i}"].state is RequestState.DONE
        assert res[f"r{i}"].tokens == refs[i]
    assert all(r.state is ReplicaState.DEAD for r in router.replicas)
    assert all(r.dead_reason == "drained" for r in router.replicas)
    assert all(r.engine.closed for r in router.replicas)


# ---------------------------------------------------------------------------
# THE fleet chaos acceptance test (ISSUE 13 acceptance criterion)
# ---------------------------------------------------------------------------

def test_fleet_chaos_acceptance_kill_mid_decode(fleet_model, tmp_path):
    """Seeded Poisson traffic on a 3-replica fleet; chaos kills one
    replica mid-decode. (a) every completed request token-identical to
    the clean single-stream reference; (b) every non-completed request
    terminal with a typed ServeResult + obs event — zero silent drops,
    verified by reconciling submitted rids against drained results;
    (c) the mixed-fleet reducer over the per-replica shards shows the
    fleet AND per-replica p99 rows, failover hops included."""
    model, params = fleet_model
    obs_dir = str(tmp_path / "obs")
    n_req = 10
    rng = np.random.RandomState(21)
    arrivals = np.cumsum(rng.exponential(0.02, size=n_req))
    prompts = [rng.randint(0, VOCAB, size=4 + i % 4).tolist()
               for i in range(n_req)]
    refs = _refs(model, params, prompts, 8)

    router = FleetRouter(model, params, _rcfg(
        n_replicas=3,
        serve=ServeConfig(slots=2, page_size=4, queue_depth=16,
                          max_new_tokens=8, prefill_bucket=8),
        chaos=ChaosConfig(enabled=True, fleet_kill_replica_at_step=4,
                          fleet_target_replica=0),
    ), obs_dir=obs_dir)
    sinks = [rep.engine.reg.add_sink(MemorySink())
             for rep in router.replicas]
    sinks.append(router.reg.add_sink(MemorySink()))

    import time as _time

    submitted = []
    i = 0
    t0 = _time.perf_counter()
    for _ in range(500):
        now = _time.perf_counter() - t0
        while i < n_req and arrivals[i] <= now:
            router.submit(Request(rid=f"r{i}", prompt=prompts[i],
                                  max_new_tokens=8))
            submitted.append(f"r{i}")
            i += 1
        busy = router.step()
        if i >= n_req and not busy:
            break
    res = router.results
    router.close()

    # The kill fired mid-traffic and work failed over.
    assert router.replicas[0].state is ReplicaState.DEAD
    summ = router.fleet_summary()
    assert summ["replica_deaths"] == 1
    assert summ["failovers"] >= 1
    hopped = [r for r in res.values() if r.n_hops > 0]
    assert hopped, "kill exercised no failover"

    # (b) zero silent drops: submitted == terminal, all typed.
    assert sorted(res) == sorted(submitted)
    for r in res.values():
        assert r.state in (
            RequestState.DONE, RequestState.SHED, RequestState.EXPIRED,
            RequestState.FAILED,
        )
        assert (r.error is None) == (r.state is RequestState.DONE)
    events = [e for s in sinks for e in s.events
              if e["etype"] == "serve_request"]
    assert sorted({e["rid"] for e in events}) == sorted(submitted)

    # (a) token identity vs the clean single-stream reference for every
    # completed request — INCLUDING the failover hops.
    for i, rid in enumerate(submitted):
        if res[rid].state is RequestState.DONE:
            assert res[rid].tokens == refs[i], rid
    assert any(r.n_hops > 0 and r.state is RequestState.DONE
               for r in res.values())

    # (c) fleet metrics reduced across the per-replica shards: per-host
    # p99 rows + pooled fleet percentiles + the failover evidence.
    red = reduce_shards(obs_dir)
    assert red is not None and red["serve"]["requests"] >= n_req
    assert red["serve"].get("ttft_p99_s") is not None
    assert red["serve"].get("failover_hops", 0) >= 1
    per_replica = [h for k, h in red["hosts"].items()
                   if int(k) < 3 and h.get("serve_requests")]
    assert len(per_replica) >= 2  # survivors + the dead replica's record
    assert any(h.get("ttft_p99_s") is not None for h in per_replica)


# ---------------------------------------------------------------------------
# reducer + drift-guard satellites
# ---------------------------------------------------------------------------

def test_reducer_fleet_percentiles(tmp_path):
    """The mixed-fleet reducer derives per-host AND pooled fleet p50/p99
    from serve_request terminals (plus tokens/s and failover hops)."""
    from dtc_tpu.obs import shard_path

    def write(proc, events):
        with open(shard_path(str(tmp_path), proc), "w") as f:
            for e in events:
                f.write(json.dumps({"proc": proc, **e}) + "\n")

    write(0, [
        {"etype": "serve_request", "state": "done", "iteration": 5,
         "ts": 1.0, "ttft_s": 0.1, "ms_per_token": 10.0, "n_tokens": 8,
         "n_hops": 0},
        {"etype": "serve_request", "state": "done", "iteration": 9,
         "ts": 3.0, "ttft_s": 0.3, "ms_per_token": 30.0, "n_tokens": 8,
         "n_hops": 1},
    ])
    write(1, [
        {"etype": "serve_request", "state": "shed", "iteration": 7,
         "ts": 2.0, "ttft_s": 0.2, "n_tokens": 0, "n_hops": 0},
    ])
    red = reduce_shards(str(tmp_path))
    assert red["serve"]["requests"] == 3
    # Pooled percentiles come from merged histograms (ISSUE 16): exact
    # to within one log-bucket (growth 1.1), so assert rel=0.1 — the
    # per-host values below stay exact nearest-rank.
    assert red["serve"]["ttft_p50_s"] == pytest.approx(0.2, rel=0.1)
    assert red["serve"]["ttft_p99_s"] == pytest.approx(0.3, rel=0.1)
    assert red["serve"]["ms_per_token_p99"] == pytest.approx(30.0, rel=0.1)
    assert red["serve"]["failover_hops"] == 1
    assert red["serve"]["tokens_per_sec"] == 8.0  # 16 tokens / 2 s span
    assert red["hosts"]["0"]["ttft_p99_s"] == 0.3
    assert red["hosts"]["0"]["failover_hops"] == 1
    assert "ms_per_token_p99" not in red["hosts"]["1"]  # no samples


def test_resume_submit_engine_level(fleet_model):
    """The engine's cross-replica resume primitive in isolation: partial
    progress on engine A resumes on engine B token-identically, with
    submitted_t preserved and the hop counted."""
    model, params = fleet_model
    p = _prompts(15, (5,))[0]
    ref = _refs(model, params, [p], 8)[0]
    scfg = ServeConfig(slots=1, page_size=4, queue_depth=4,
                       max_new_tokens=8, prefill_bucket=8)
    a = ServingEngine(model, params, scfg)
    a.submit(Request(rid="r", prompt=p, max_new_tokens=8))
    for _ in range(4):
        a.step()
    partial = a.results["r"]
    assert partial.state is RequestState.DECODE
    assert 0 < len(partial.tokens) < 8

    b = ServingEngine(model, params, scfg)
    b.submit(Request(rid="r", prompt=p, max_new_tokens=8), resume=partial)
    res = b.run(max_steps=100)["r"]
    assert res.state is RequestState.DONE
    assert res.tokens == ref
    assert res.n_hops == 1
    assert res.submitted_t == partial.submitted_t
    # A resume that should already be complete is a caller bug.
    done = b.results if "r" in b.results else {}
    with pytest.raises(ValueError, match="resume"):
        b.drain_results()
        b.submit(Request(rid="r2", prompt=p, max_new_tokens=2),
                 resume=res)
