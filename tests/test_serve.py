"""Serving-runtime tests (ISSUE 6): continuous batching over the paged KV
cache, with every robustness path chaos-verified on CPU.

The anchor invariant throughout: the scheduler is a pure REORDERING of
single-stream greedy decode — whatever faults land (preemption, cache
corruption, pool exhaustion, retries), every completed request's tokens
are token-for-token identical to ``generate()`` on the same prompt, and
every non-completed request carries a typed error plus an obs event.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dtc_tpu.config.schema import (
    ChaosConfig,
    ServeConfig,
    StreamRetryConfig,
    WatchdogConfig,
)
from dtc_tpu.generate import generate
from dtc_tpu.models.gpt import GPT
from dtc_tpu.obs import MemorySink
from dtc_tpu.serve import (
    DeadlineExceededError,
    PageAllocator,
    QueueFullError,
    Request,
    RequestState,
    RequestTooLargeError,
    ServingEngine,
    ShedError,
    pages_for,
)

VOCAB = 97


@pytest.fixture(scope="module")
def served_model():
    """One tiny GPT + params shared by every engine test in the module
    (init is the expensive part; engines are cheap). Dimensions match
    conftest's tiny_model_cfg (module scope forbids reusing the
    function-scoped fixture directly)."""
    from dtc_tpu.config.schema import ModelConfig

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


def _refs(model, params, prompts, n):
    return [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None], n
        ))[0].tolist()
        for p in prompts
    ]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# host-side units: allocator, request model, retry satellite
# ---------------------------------------------------------------------------

def test_pages_for():
    assert pages_for(0, 4) == 0
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2


def test_page_allocator_accounting():
    a = PageAllocator(total_pages=8, page_size=4)
    assert a.alloc("r1", 3) and a.held("r1") == 3 and a.free_pages == 5
    assert a.ensure("r1", 5) and a.held("r1") == 5
    assert a.ensure("r1", 2) and a.held("r1") == 5  # never shrinks
    assert not a.alloc("r2", 4)  # only 3 free
    assert a.free_pages == 3     # failed alloc changes nothing
    assert a.free("r1") == 5 and a.free_pages == 8
    assert a.free("r1") == 0     # idempotent


def test_page_allocator_prefix_lru():
    a = PageAllocator(total_pages=6, page_size=4)
    assert a.pin_prefix(("a",), 2) and a.pin_prefix(("b",), 2)
    assert a.free_pages == 2
    a.touch_prefix(("a",))       # "b" becomes LRU
    assert not a.pin_prefix(("c",), 4)
    assert a.evict_prefix_lru() == ("b",)
    assert a.pin_prefix(("c",), 4) and a.free_pages == 0
    assert a.has_prefix(("a",)) and not a.has_prefix(("b",))


def test_request_validation():
    with pytest.raises(ValueError):
        Request(rid="x", prompt=[], max_new_tokens=1)
    with pytest.raises(ValueError):
        Request(rid="x", prompt=[1], max_new_tokens=0)
    with pytest.raises(ValueError):
        Request(rid="x", prompt=[1, 2], max_new_tokens=1, shared_prefix_len=3)


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(slots=0)
    with pytest.raises(ValueError):
        ServeConfig(shed_policy="coin_flip")
    with pytest.raises(ValueError):
        ServeConfig(shed_watermark=1.5)
    # Injected page corruption without the verifier would NEVER be
    # detected — the damaged request would complete with wrong tokens.
    with pytest.raises(ValueError, match="verify_pages_every"):
        ServeConfig(chaos=ChaosConfig(enabled=True,
                                      serve_corrupt_page_at_step=3),
                    verify_pages_every=0)
    ServeConfig(chaos=ChaosConfig(enabled=True, serve_corrupt_page_at_step=3),
                verify_pages_every=1)  # coherent: accepted


def test_retry_call_max_elapsed_caps_episode():
    """Satellite: the elapsed cap ends a fault episode that bounded
    attempts alone would let stall for attempts x backoff_max_s."""
    from dtc_tpu.resilience.retry import retry_call

    clock = FakeClock()
    sleeps = []

    def sleep(d):
        sleeps.append(d)
        clock.advance(d)

    calls = []

    def fn():
        calls.append(1)
        clock.advance(1.0)  # each attempt burns a second
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(
            fn, max_attempts=100, backoff_s=1.0, backoff_max_s=1.0,
            jitter=0.0, max_elapsed_s=5.0, transient=(OSError,),
            sleep=sleep, clock=clock,
        )
    # attempts 1..2 fit (1s call + 1s backoff each); attempt 3 at t=4s
    # would need +1s call +1s backoff > 5s -> raise on attempt 3.
    assert len(calls) == 3
    assert clock.t <= 7.0  # never slept past the cap's neighborhood


def test_retry_call_success_after_transient():
    from dtc_tpu.resilience.retry import retry_call

    state = {"n": 0}

    def fn():
        state["n"] += 1
        if state["n"] < 3:
            raise OSError("flaky")
        return "ok"

    events = []
    assert retry_call(
        fn, max_attempts=5, backoff_s=0.0, jitter=0.0, transient=(OSError,),
        sleep=lambda d: None, on_event=lambda e, **f: events.append(f),
    ) == "ok"
    assert len(events) == 2  # one recovery record per re-attempt


def test_resilient_iterator_max_elapsed(monkeypatch):
    """The stream wrapper honors the same episode cap: a limping source
    dies with DataStreamError once the episode outlives max_elapsed_s,
    even with attempts to spare."""
    from dtc_tpu.resilience.errors import DataStreamError
    from dtc_tpu.resilience.retry import resilient_iterator

    clock = FakeClock()

    def factory(index):
        def gen():
            clock.advance(2.0)
            raise OSError("stalled dependency")
            yield  # pragma: no cover
        return gen()

    it = resilient_iterator(
        factory, max_attempts=50, backoff_s=1.0, backoff_max_s=1.0,
        jitter=0.0, max_elapsed_s=3.0, transient=(OSError,),
        sleep=lambda d: clock.advance(d), clock=clock,
    )
    with pytest.raises(DataStreamError) as ei:
        next(it)
    assert "max_elapsed_s" in str(ei.value)


# ---------------------------------------------------------------------------
# engine: continuous batching, paged cache, robustness
# ---------------------------------------------------------------------------

def test_continuous_batching_parity_and_no_silent_drops(served_model):
    """More requests than slots, staggered admissions: every output is
    token-for-token generate()'s, every submitted rid reaches a terminal
    state, and one serve_request event exists per rid."""
    model, params = served_model
    prompts = _prompts(0, (5, 9, 7, 6, 11))
    refs = _refs(model, params, prompts, 8)
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=8, max_new_tokens=8,
        prefill_bucket=8,
    ))
    sink = eng.reg.add_sink(MemorySink())
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=8))
    res = eng.run(max_steps=400)
    for i in range(len(prompts)):
        assert res[f"r{i}"].state is RequestState.DONE
        assert res[f"r{i}"].tokens == refs[i]
        assert res[f"r{i}"].error is None
    # With 2 slots and 5 requests, batching had to be continuous.
    assert eng._it > 3
    terminal = [e for e in sink.events if e["etype"] == "serve_request"]
    assert sorted(e["rid"] for e in terminal) == sorted(res)
    snap = eng.reg.snapshot()
    assert snap["serve_done"] == 5 and snap["serve_submitted"] == 5


def test_prefix_sharing_prefills_once(served_model):
    """Shared system prompt: the prefix store builds once, later
    admissions hit it, outputs stay exact — including a prefix whose
    length is NOT page- or bucket-aligned (the stored frontier must pin
    to the valid length, not the padded one)."""
    model, params = served_model
    rng = np.random.RandomState(3)
    prefix = rng.randint(0, VOCAB, size=7).tolist()  # deliberately odd
    prompts = [prefix + rng.randint(0, VOCAB, size=k).tolist() for k in (3, 5, 4)]
    refs = _refs(model, params, prompts, 6)
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=8, max_new_tokens=6,
        prefill_bucket=4,
    ))
    for i, p in enumerate(prompts):
        eng.submit(Request(
            rid=f"s{i}", prompt=p, max_new_tokens=6,
            shared_prefix_len=len(prefix),
        ))
    res = eng.run(max_steps=300)
    for i in range(3):
        assert res[f"s{i}"].tokens == refs[i]
    snap = eng.reg.snapshot()
    assert snap["serve_prefix_builds"] == 1
    assert snap["serve_prefix_hits"] == 2


def test_eviction_under_page_pressure_is_bit_exact(served_model):
    """A pool too small for all in-flight requests forces
    eviction-and-re-prefill mid-decode; evicted requests resume and still
    produce generate()-identical tokens (eviction is a RECOVERY path)."""
    model, params = served_model
    prompts = _prompts(1, (6, 8, 5, 7))
    refs = _refs(model, params, prompts, 10)
    eng = ServingEngine(model, params, ServeConfig(
        slots=3, page_size=4, total_pages=9, queue_depth=8,
        max_new_tokens=10, prefill_bucket=8,
    ))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=10))
    res = eng.run(max_steps=500)
    assert sum(r.n_evictions for r in res.values()) > 0
    for i in range(4):
        assert res[f"r{i}"].state is RequestState.DONE
        assert res[f"r{i}"].tokens == refs[i]
    # Pool fully reclaimed at the end — no page leaks.
    assert eng.alloc.free_pages == eng.alloc.total_pages


def test_admission_control_typed_rejection(served_model):
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=2, max_new_tokens=4,
        prefill_bucket=8,
    ))
    sink = eng.reg.add_sink(MemorySink())
    eng.submit(Request(rid="a", prompt=[1, 2], max_new_tokens=4))
    eng.submit(Request(rid="b", prompt=[3, 4], max_new_tokens=4))
    with pytest.raises(QueueFullError):
        eng.submit(Request(rid="c", prompt=[5, 6], max_new_tokens=4))
    with pytest.raises(RequestTooLargeError):
        eng.submit(Request(rid="d", prompt=[1] * 30, max_new_tokens=4))
    rejects = [e for e in sink.events if e["etype"] == "serve_reject"]
    assert {(e["rid"], e["reason"]) for e in rejects} == {
        ("c", "queue_full"), ("d", "too_large"),
    }
    assert eng.reg.snapshot()["serve_rejected"] == 2


def test_overload_sheds_lowest_priority(served_model):
    """Past the shed watermark the policy drops the lowest-priority /
    longest-queued requests with a typed ShedError; survivors complete
    exactly. No request vanishes silently."""
    model, params = served_model
    prompts = _prompts(2, (4, 4, 4, 4, 4, 4))
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=4,
        prefill_bucket=8, shed_watermark=0.5,
    ))
    # priorities: r0/r1 high, rest low — low ones past the watermark shed.
    for i, p in enumerate(prompts):
        try:
            eng.submit(Request(
                rid=f"r{i}", prompt=p, max_new_tokens=4,
                priority=1 if i < 2 else 0,
            ))
        except QueueFullError:
            pass
    res = eng.run(max_steps=300)
    states = {rid: r.state for rid, r in res.items()}
    assert states["r0"] is RequestState.DONE
    assert states["r1"] is RequestState.DONE
    shed = [rid for rid, s in states.items() if s is RequestState.SHED]
    assert shed and all(isinstance(res[r].error, ShedError) for r in shed)
    assert all(s in (RequestState.DONE, RequestState.SHED)
               for s in states.values())
    refs = _refs(model, params, [prompts[0], prompts[1]], 4)
    assert res["r0"].tokens == refs[0] and res["r1"].tokens == refs[1]


def test_deadline_expires_queued_and_mid_decode(served_model):
    """TTL cancellation in both places it can land: still queued, and
    mid-decode (slot + pages reclaimed immediately)."""
    model, params = served_model
    clock = FakeClock()
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=8, max_new_tokens=12,
        prefill_bucket=8,
    ), clock=clock, sleep=lambda d: clock.advance(d))
    eng.submit(Request(rid="slow", prompt=[1, 2, 3], max_new_tokens=12,
                       deadline_s=5.0))
    eng.submit(Request(rid="waiting", prompt=[4, 5], max_new_tokens=4,
                       deadline_s=3.0))
    for _ in range(20):
        clock.advance(1.0)
        if not eng.step():
            break
    res = eng.results
    assert res["waiting"].state is RequestState.EXPIRED
    assert isinstance(res["waiting"].error, DeadlineExceededError)
    assert res["slow"].state is RequestState.EXPIRED  # cancelled mid-decode
    assert isinstance(res["slow"].error, DeadlineExceededError)
    assert 0 < len(res["slow"].tokens) < 12  # partial progress, then cancel
    assert eng.alloc.free_pages == eng.alloc.total_pages


def test_degradation_caps_new_tokens(served_model):
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=12,
        prefill_bucket=8, shed_watermark=0.0, degrade_watermark=0.25,
        degrade_max_new_tokens=3,
    ))
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", prompt=[i + 1, i + 2],
                           max_new_tokens=12))
    res = eng.run(max_steps=300)
    degraded = [r for r in res.values() if r.degraded]
    assert degraded and all(len(r.tokens) == 3 for r in degraded)
    assert eng.reg.snapshot()["serve_degraded"] == len(degraded)
    # Reusing a degraded rid under NO load must not inherit the stale
    # degraded cap from the previous submission.
    rid = next(r.rid for r in res.values() if r.degraded)
    eng.submit(Request(rid=rid, prompt=[9, 10], max_new_tokens=12))
    res2 = eng.run(max_steps=300)
    assert len(res2[rid].tokens) == 12 and not res2[rid].degraded


def test_run_budget_is_per_call_and_state_is_reclaimed(served_model):
    """run(max_steps) is a per-call budget (not the lifetime iteration
    counter), and terminal requests leave no per-request host state
    behind except the drainable result."""
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=4,
        prefill_bucket=8,
    ))
    eng.submit(Request(rid="a", prompt=[1, 2], max_new_tokens=4))
    eng.run(max_steps=100)
    for _ in range(10):
        eng.step()  # idle iterations inflate the lifetime counter
    burned = eng._it
    # Second round: a budget SMALLER than the lifetime counter but ample
    # for the request itself must still complete it.
    eng.submit(Request(rid="b", prompt=[3, 4], max_new_tokens=4))
    res = eng.run(max_steps=8)
    assert burned > 8 and eng._it > burned
    assert res["b"].state is RequestState.DONE
    # Terminal bookkeeping reclaimed; results drainable.
    assert eng.requests == {} and eng._eff_max_new == {}
    drained = eng.drain_results()
    assert sorted(drained) == ["a", "b"] and eng.results == {}


def test_engine_rejects_debug_checks_model(served_model):
    """The model's checkify guard must be functionalized before jit
    (generate.py's debug path); the engine jits decode_step directly, so
    it refuses the config with a clear error instead of dying mid-trace."""
    import dataclasses

    model, params = served_model
    dbg_model = GPT(dataclasses.replace(model.cfg, debug_checks=True))
    with pytest.raises(ValueError, match="debug_checks"):
        ServingEngine(dbg_model, params, ServeConfig(slots=1))


def test_serving_step_never_recompiles_across_admissions(served_model):
    """The compiled-shape invariant the graph audit pins (serve_decode
    baseline): admitting into / evicting from fixed slots reuses ONE
    decode executable — steady-state compiles stay zero."""
    from dtc_tpu.obs.stepclock import CompileWatcher

    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=8, max_new_tokens=6,
        prefill_bucket=8,
    ))
    # Warm every compiled surface (prefill/insert/step/fingerprint).
    eng.submit(Request(rid="warm", prompt=[1, 2, 3], max_new_tokens=6))
    eng.run(max_steps=50)
    w = CompileWatcher().activate()
    try:
        w.drain()
        eng.submit(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=6))
        eng.step()
        eng.submit(Request(rid="b", prompt=[4, 5], max_new_tokens=6))
        eng.step()  # admitted mid-flight: batch 1 -> 2, same executable
        eng.run(max_steps=100)
        eng.submit(Request(rid="c", prompt=[6], max_new_tokens=3))
        eng.run(max_steps=100)  # slot reuse after completion
        _, steady = w.drain()
    finally:
        w.deactivate()
    assert steady == 0, f"{steady} recompile(s) across admissions/evictions"


def test_prefix_prefill_retry_exhaustion_fails_typed(served_model):
    """A retry-exhausted prefill DURING A PREFIX-STORE BUILD must end the
    request typed (FAILED + RequestFailedError), return its pages, and
    un-account the never-stored prefix — not escape the scheduler."""
    from dtc_tpu.serve import RequestFailedError

    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=4,
        prefill_bucket=8,
        retry=StreamRetryConfig(max_attempts=2, backoff_s=0.0,
                                backoff_max_s=0.0, jitter=0.0),
    ))
    sink = eng.reg.add_sink(MemorySink())
    orig = eng._prefill_fn

    def poisoned(*a, **k):
        cache, tok, _fin = orig(*a, **k)
        return cache, tok, jnp.asarray(False)

    eng._prefill_fn = poisoned
    eng.submit(Request(rid="p", prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=4,
                       shared_prefix_len=4))
    res = eng.run(max_steps=50)
    assert res["p"].state is RequestState.FAILED
    assert isinstance(res["p"].error, RequestFailedError)
    assert eng.alloc.free_pages == eng.alloc.total_pages  # nothing leaked
    assert eng.alloc.snapshot()["prefix_entries"] == 0
    terminal = [e for e in sink.events if e["etype"] == "serve_request"]
    assert [e["rid"] for e in terminal] == ["p"]  # typed, no silent drop


def test_persistent_slot_fault_fails_only_that_slot(served_model):
    """Decode-retry exhaustion is localized: only the slot whose logits
    actually read non-finite fails typed; a co-scheduled healthy request
    keeps its slot and completes with exact tokens (no collateral batch
    kill)."""
    from dtc_tpu.serve import RequestFailedError

    model, params = served_model
    prompts = _prompts(6, (4, 5))
    refs = _refs(model, params, prompts, 6)
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=4, max_new_tokens=6,
        prefill_bucket=8,
        retry=StreamRetryConfig(max_attempts=2, backoff_s=0.0,
                                backoff_max_s=0.0, jitter=0.0),
    ))
    orig = eng._step_fn

    def bad(params_, cache, toks):
        cache, nxt, fin = orig(params_, cache, toks)
        fin = np.asarray(fin).copy()
        fin[0] = False  # slot 0's logits persistently read non-finite
        return cache, nxt, jnp.asarray(fin)

    eng._step_fn = bad
    eng.submit(Request(rid="bad", prompt=prompts[0], max_new_tokens=6))
    eng.submit(Request(rid="good", prompt=prompts[1], max_new_tokens=6))
    res = eng.run(max_steps=200)
    assert res["bad"].state is RequestState.FAILED
    assert isinstance(res["bad"].error, RequestFailedError)
    assert res["good"].state is RequestState.DONE
    assert res["good"].tokens == refs[1]


def test_duplicate_rid_rejected_while_in_flight(served_model):
    """Resubmitting an in-flight rid would silently merge two requests
    into one record; it must raise. Reuse AFTER a terminal state is
    allowed (the new result replaces the old)."""
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=4,
        prefill_bucket=8,
    ))
    eng.submit(Request(rid="a", prompt=[1, 2], max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.submit(Request(rid="a", prompt=[3, 4], max_new_tokens=4))
    assert eng.run(max_steps=100)["a"].state is RequestState.DONE
    eng.submit(Request(rid="a", prompt=[5, 6], max_new_tokens=4))
    assert eng.run(max_steps=100)["a"].state is RequestState.DONE


def test_chaos_preempt_defers_until_actionable(served_model):
    """A preemption shot landing on iterations with nothing to preempt is
    NOT consumed (no phantom chaos event); it fires once at the first
    iteration with an active request, which still completes exactly."""
    model, params = served_model
    prompts = _prompts(5, (3,))
    refs = _refs(model, params, prompts, 4)
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=4,
        prefill_bucket=8,
        chaos=ChaosConfig(enabled=True, serve_preempt_at_step=1),
    ))
    eng.step()  # idle iterations at/after the configured step:
    eng.step()  # the shot must survive them
    snap = eng.reg.snapshot()
    assert snap.get("serve_preemptions", 0) == 0
    assert snap.get("chaos_injections", 0) == 0
    eng.submit(Request(rid="r", prompt=prompts[0], max_new_tokens=4))
    res = eng.run(max_steps=100)
    snap = eng.reg.snapshot()
    assert snap["serve_preemptions"] == 1
    assert snap["chaos_injections"] == 1
    assert res["r"].state is RequestState.DONE
    assert res["r"].n_evictions == 1
    assert res["r"].tokens == refs[0]


def test_fingerprint_detects_magnitude_preserving_corruption(served_model):
    """The page checksum is a position-weighted SIGNED sum: sign-bit
    flips and intra-page value swaps — realistic memory faults a plain
    sum(|x|) is blind to — must change the fingerprint."""
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=6,
        prefill_bucket=8, verify_pages_every=1,
    ))
    eng.submit(Request(rid="r", prompt=[1, 2, 3, 4, 5], max_new_tokens=6))
    eng.step()  # admission: 5 resident tokens -> page 0 is complete

    def mutate(fn):
        leaves, treedef = jax.tree.flatten(eng.cache)
        out, done = [], False
        for leaf in leaves:
            if not done and leaf.ndim >= 4:
                a = np.asarray(leaf).copy()
                fn(a)
                leaf = jnp.asarray(a)
                done = True
            out.append(leaf)
        eng.cache = jax.tree.unflatten(treedef, out)
        eng._fps_memo = None

    fps0 = eng._page_fps().copy()
    kv = next(l for l in jax.tree.leaves(eng.cache) if l.ndim >= 4)
    assert float(kv[0, 0, 1, 0]) != 0.0  # real K/V bytes at page 0

    def flip(a):
        a[0, 0, 1, 0] = -a[0, 0, 1, 0]

    mutate(flip)
    fps1 = eng._page_fps().copy()
    assert fps1[0, 0] != fps0[0, 0], "sign flip went undetected"

    assert float(kv[0, 0, 0, 0]) != float(kv[0, 0, 2, 0])

    def swap(a):
        a[0, 0, 0, 0], a[0, 0, 2, 0] = (
            float(a[0, 0, 2, 0]), float(a[0, 0, 0, 0]),
        )

    mutate(swap)
    fps2 = eng._page_fps().copy()
    assert fps2[0, 0] != fps1[0, 0], "intra-page swap went undetected"


def test_idle_iterations_do_not_poison_watchdog(served_model):
    """Interleaved submit()/step() callers spin idle iterations between
    arrivals; those microsecond spins must not enter the watchdog's
    trailing median and flag every healthy decode iteration as hung."""
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=6,
        prefill_bucket=8,
        watchdog=WatchdogConfig(enabled=True, factor=8.0, min_samples=3),
    ))
    for _ in range(20):
        eng.step()  # idle spins — would collapse the median if observed
    eng.submit(Request(rid="r", prompt=[1, 2, 3], max_new_tokens=6))
    res = eng.run(max_steps=100)
    assert res["r"].state is RequestState.DONE
    assert eng.reg.snapshot().get("serve_hung_steps", 0) == 0


def test_chaos_stall_flags_hung_step(served_model):
    """An injected scheduler stall is a real outlier iteration; the
    serving watchdog flags it through telemetry."""
    model, params = served_model
    eng = ServingEngine(model, params, ServeConfig(
        slots=1, page_size=4, queue_depth=4, max_new_tokens=10,
        prefill_bucket=8,
        watchdog=WatchdogConfig(enabled=True, factor=4.0, min_samples=3),
        chaos=ChaosConfig(enabled=True, serve_stall_at_step=8, stall_s=1.0),
    ))
    sink = eng.reg.add_sink(MemorySink())
    eng.submit(Request(rid="r", prompt=[1, 2, 3], max_new_tokens=10))
    eng.run(max_steps=100)
    flags = [e for e in sink.events if e["etype"] == "hung_step"]
    assert flags and flags[0]["runtime"] == "serve"
    assert eng.reg.snapshot()["serve_hung_steps"] >= 1
    assert eng.results["r"].state is RequestState.DONE


def test_chaos_acceptance_faulted_run_matches_clean_run(served_model):
    """THE acceptance test (ISSUE 6): one seeded multi-request run with
    injected mid-request preemption + KV cache-block corruption + poisoned
    logits + a deadline timeout produces token-for-token identical
    outputs to an uninjected run for every non-shed/non-expired request,
    and typed errors + obs events for the rest — no silent drops."""
    model, params = served_model
    prompts = _prompts(4, (6, 8, 5, 7))

    def build(chaos: ChaosConfig | None):
        return ServingEngine(model, params, ServeConfig(
            slots=2, page_size=4, queue_depth=8, max_new_tokens=10,
            prefill_bucket=8,
            verify_pages_every=1,  # catch corruption before tokens leak
            chaos=chaos or ChaosConfig(),
        ))

    def drive(eng, with_doomed: bool):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=f"c{i}", prompt=p, max_new_tokens=10))
        if with_doomed:
            # The injected timeout: a request whose TTL cannot be met.
            eng.submit(Request(rid="doomed", prompt=[1, 2, 3],
                               max_new_tokens=10, deadline_s=1e-9))
        return eng.run(max_steps=600)

    clean = drive(build(None), with_doomed=False)
    chaos = ChaosConfig(
        enabled=True,
        serve_preempt_at_step=4,
        serve_corrupt_page_at_step=6,
        serve_poison_logits_at_step=8,
    )
    eng = build(chaos)
    sink = eng.reg.add_sink(MemorySink())
    faulted = drive(eng, with_doomed=True)

    # Every injected fault actually fired and was recovered.
    snap = eng.reg.snapshot()
    assert snap["chaos_injections"] == 3
    assert snap["serve_preemptions"] == 1
    assert snap["serve_corruptions"] == 1
    assert snap["serve_retries"] >= 1

    # Token-for-token parity for every completed request.
    for i in range(len(prompts)):
        rid = f"c{i}"
        assert faulted[rid].state is RequestState.DONE
        assert clean[rid].state is RequestState.DONE
        assert faulted[rid].tokens == clean[rid].tokens, rid

    # The timed-out request: typed error, no silent drop.
    assert faulted["doomed"].state is RequestState.EXPIRED
    assert isinstance(faulted["doomed"].error, DeadlineExceededError)

    # One terminal serve_request event per submitted rid; chaos +
    # recovery evidence in the same stream.
    etypes = {e["etype"] for e in sink.events}
    assert {"serve_request", "chaos", "serve_evict",
            "serve_corruption"} <= etypes
    terminal = [e for e in sink.events if e["etype"] == "serve_request"]
    assert sorted(e["rid"] for e in terminal) == sorted(faulted)
    assert all(e["error"] is not None or e["state"] == "done"
               for e in terminal)


# ---------------------------------------------------------------------------
# model/op level: the per-slot (vector frontier) decode path
# ---------------------------------------------------------------------------

def test_decode_attention_vector_start_matches_scalar_rows():
    """The XLA decode oracle with a (B,) frontier vector must equal
    per-row scalar calls — the primitive the per-slot cache rides on."""
    from dtc_tpu.ops.attention import decode_attention

    b, s, h, d = 3, 16, 2, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, 1, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, h, d), jnp.float32)
    starts = jnp.asarray([2, 7, 11], jnp.int32)
    out_vec = decode_attention(q, k, v, starts)
    for i in range(b):
        out_i = decode_attention(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], starts[i]
        )
        np.testing.assert_allclose(
            np.asarray(out_vec[i]), np.asarray(out_i[0]), rtol=1e-6
        )


def test_fused_decode_attention_per_row_matches_oracle():
    """The fused kernel's per-row SMEM frontier path (interpret mode on
    CPU) against the vector-start oracle."""
    from dtc_tpu.ops import decode_attention as fused
    from dtc_tpu.ops.attention import decode_attention

    b, s, h, d = 3, 32, 4, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (b, 1, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, h, d), jnp.float32)
    starts = jnp.asarray([0, 13, 31], jnp.int32)
    got = fused.fused_decode_attention(
        q.reshape(b, 1, h * d), k.reshape(b, s, h * d),
        v.reshape(b, s, h * d), starts, h=h, d=d,
    ).reshape(b, 1, h, d)
    want = decode_attention(q, k, v, starts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
