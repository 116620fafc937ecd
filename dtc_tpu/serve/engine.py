"""Continuous-batching serving engine over the paged KV cache.

Resilience-first by construction: overload, stragglers, mid-request
preemption, and cache exhaustion are the *steady state* of a loaded
server, so every one of them is a first-class, chaos-testable path here —
not an exception handler bolted on later.

Shape of the runtime (Orca-style iteration-level scheduling over a
vLLM-style paged budget, adapted to the model-native packed cache):

- ONE compiled decode step over ``cfg.slots`` fixed batch slots (the
  shared :func:`dtc_tpu.generate.decode_step`, driven with a per-slot
  ``(B,)`` cache-index vector). Requests enter and leave slots at
  iteration boundaries via a jitted cache-surgery ``insert`` whose slot
  argument is *traced* — admission and eviction NEVER recompile the step
  (audited: analysis baseline ``serve_decode``, cold==1 steady==0).
- Admission = per-request prefill on a side (batch-1) cache, padded to
  ``prefill_bucket`` so prefill compilations are bounded, then one
  device-side copy into the slot row. A shared system prompt
  (``Request.shared_prefix_len``) is prefilled once into the prefix store
  and reused by every admission that matches it — the prefix-sharing win
  is prefill compute (see paged_cache.py's honesty note on the dense
  layout).
- The paged allocator accounts every resident token in ``page_size``
  blocks against one pool; exhaustion triggers *eviction-and-re-prefill*
  (victim re-queues with its generated tokens and resumes bit-exactly —
  greedy decode over prompt+generated reproduces the continuation), the
  same recovery path mid-request preemption and detected cache-block
  corruption take.
- Robustness layer: bounded queue with typed rejection (QueueFullError),
  shed-under-overload (lowest priority / longest queued past the
  watermark, typed ShedError), per-request deadlines with mid-decode
  cancellation (DeadlineExceededError), transient-fault retry from the
  pre-step cache (``resilience.retry.retry_call`` + the logits finite
  check), page-checksum verification on a cadence, and a serving-mode
  hung-step watchdog. Chaos (``resilience.chaos`` serve hooks) injects
  faults at iteration boundaries ON these production paths.
- SLO accounting through ``obs``: queue-wait / TTFT / ms-per-token
  histograms, shed/evict/expire/reject/retry counters, and one
  ``serve_request`` event per terminal request — no silent drops.
- Speculative decoding (``ServeConfig.spec``, ISSUE 19): a resident
  shallow DRAFT rung (the target's bottom ``draft_layers``, extracted at
  construction — ``dtc_tpu/spec/draft.py``) proposes ``spec_k - 1``
  tokens per iteration and ONE k-query verify launch accepts a prefix of
  them, so an iteration emits 1..spec_k tokens per slot instead of
  exactly one. Greedy acceptance keeps the output token-identical to
  plain decode by construction. The draft's KV rides the SAME page pool
  (a proportional ``draft_layers / n_layers`` surcharge in
  ``_pages_needed``); rounds are atomic in-jit, so eviction / failover /
  corruption recovery land at iteration boundaries exactly as before —
  re-admission re-prefills BOTH caches and resumes token-identically.
  Honesty plumbing: rejected-draft wall time is a typed badput class
  (``spec_rejected_draft``, never productive_decode), the SLO monitor is
  fed ACCEPTED-tokens/s (a collapsing accept rate degrades admissions
  like a latency breach), and every ServeResult carries
  ``n_spec_proposed/accepted`` so accept_rate is per-request observable.
- Multi-tenant LoRA adapters (``dtc_tpu/adapters/``, model config
  ``adapter.rank > 0``): one resident ``(max_adapters, ...)`` stacked
  factor buffer over ONE base model — slot 0 pinned to the all-zero base
  adapter — with per-slot adapter indices gathered inside the jitted
  step, so admitting a new tenant (or ``load_adapter`` writing factors at
  a traced stack slot) never recompiles. Requests name their tenant
  (``Request.adapter``); the store pins it (refcount) from submit to
  terminal; per-tenant TTFT/ms-per-token histograms and ``adapter_*``
  events ride the same registry.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from dtc_tpu.adapters import (
    BASE_SLOT,
    AdapterStore,
    gather_slot_lora,
    init_lora_stack,
    lora_enabled,
    validate_lora_tree,
)
from dtc_tpu.generate import decode_step, init_cache
from dtc_tpu.obs.goodput import SPEC_REJECTED_DRAFT, OnlineGoodput
from dtc_tpu.obs.registry import MetricsRegistry
from dtc_tpu.obs.slo import SloMonitor
from dtc_tpu.obs.trace import FlightRecorder, Tracer
from dtc_tpu.resilience.chaos import ChaosInjector
from dtc_tpu.resilience.events import RecoveryBus
from dtc_tpu.resilience.retry import retry_call
from dtc_tpu.resilience.watchdog import StepWatchdog
from dtc_tpu.serve.paged_cache import PageAllocator, kv_token_bytes, pages_for
from dtc_tpu.spec import check_spec_backend, extract_draft, serve_round
from dtc_tpu.serve.request import (
    TERMINAL_STATES,
    DeadlineExceededError,
    EngineClosedError,
    QueueFullError,
    Request,
    RequestFailedError,
    RequestState,
    RequestTooLargeError,
    ServeResult,
    ShedError,
    TransientStepError,
    UnknownAdapterError,
)

PyTree = Any


def init_slot_cache(model, slots: int) -> PyTree:
    """Decode cache for ``slots`` independent slots: the standard cache
    with the scalar write frontier replaced by a ``(slots,)`` per-slot
    vector — the model branches on the index's static rank, so this one
    swap turns whole-batch decode into continuous-batching decode."""
    cache = dict(init_cache(model, slots))
    cache["index"] = jnp.zeros((slots,), jnp.int32)
    return cache


def _pad_to_bucket(tokens: list[int], bucket: int, limit: int) -> list[int]:
    """Right-pad to the next bucket multiple, clamped to ``limit`` (the
    remaining cache room — padding past it would make the prefill's
    dynamic_update_slice clamp its start and smear pad garbage over valid
    positions)."""
    n = len(tokens)
    padded = min(((n + bucket - 1) // bucket) * bucket, limit)
    return tokens + [0] * (padded - n)


class _Slot:
    """Host-side per-slot record: who occupies it, the write frontier
    (tokens RESIDENT in the cache row), and fingerprints of completed
    pages for the integrity verifier."""

    __slots__ = ("rid", "frontier", "page_fp")

    def __init__(self) -> None:
        self.rid: str | None = None
        self.frontier = 0
        self.page_fp: dict[int, float] = {}


class ServingEngine:
    """See module docstring. Construct once per (model, params, config);
    ``submit()`` requests, then drive ``step()`` (or ``run()``) —
    iteration boundaries are where admission, eviction, deadlines,
    shedding, verification, and chaos all land."""

    def __init__(
        self,
        model,
        params: PyTree,
        cfg,
        *,
        telemetry=None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.mcfg = model.cfg
        if getattr(self.mcfg, "layer_pattern", ()):
            from dtc_tpu.models.pattern import NOT_SERVED

            raise NotImplementedError(NOT_SERVED)
        if getattr(self.mcfg, "debug_checks", False):
            # The model would emit checkify.check guards that must be
            # functionalized before jit (see generate.py's debug path);
            # the engine jits decode_step directly, and the per-slot
            # overflow guard is the engine's own page/frontier accounting
            # here — fail clearly instead of erroring mid-trace.
            raise ValueError(
                "ServingEngine does not support model debug_checks=True "
                "(unfunctionalized checkify under jit); serve a config "
                "with debug_checks=False and use generate() for dev-mode "
                "assertions"
            )
        self.clock = clock
        self.sleep = sleep
        self.telemetry = telemetry
        self.reg: MetricsRegistry = (
            telemetry.registry if telemetry is not None else MetricsRegistry()
        )
        # ONE timebase for the whole serving record: event ts stamps,
        # span t0s, and the SLO timings on ServeResult all read the
        # scheduler clock (injected fake clocks stay coherent in tests).
        # Emission adds a constant epoch offset so the scheduler's
        # monotonic seconds land on the wall clock the TRAINER's shards
        # use — cross-host / mixed train+serve timeline merges sort by
        # raw timestamp, and a monotonic-since-boot base would place
        # every serve event decades before every train event. A constant
        # shift cancels in every duration/difference, so span-derived
        # TTFT/queue-wait still equal the ServeResult values exactly.
        self._epoch0 = time.time() - self.clock()
        emit_clock = lambda: self.clock() + self._epoch0  # noqa: E731
        self.reg.set_clock(emit_clock)
        if telemetry is not None:
            self.tracer = telemetry.tracer
            self.tracer.clock = emit_clock
            self.recorder = telemetry.recorder
        else:
            # Engine used bare (tests, bench): spans still emit to the
            # registry and the flight recorder still rings in memory.
            self.tracer = Tracer(self.reg, clock=emit_clock, tid="sched")
            self.recorder = self.reg.add_sink(FlightRecorder(256))
        # Online SLO monitor — evaluated at iteration boundaries; a
        # breaching latency objective activates graceful degradation.
        slo_cfg = getattr(cfg, "slo", None)
        self.slo = SloMonitor.from_config(slo_cfg, self.reg, runtime="serve")
        self._slo_check_every = getattr(slo_cfg, "check_every", 8) or 8
        # Online goodput gauge (ISSUE 16): share the telemetry facade's
        # instance (its registry IS this registry), or a private one for
        # bare engines (tests, bench). Fed below from the iteration
        # timestamps the scheduler already takes — never a device sync.
        self.goodput: OnlineGoodput | None = (
            getattr(telemetry, "goodput", None)
            if telemetry is not None else OnlineGoodput(self.reg)
        )
        self._gp_work = 0.0  # attributed seconds, current iteration
        self.bus = RecoveryBus()
        self.chaos = (
            ChaosInjector(cfg.chaos, self.bus) if cfg.chaos.enabled else None
        )
        self.watchdog = (
            StepWatchdog(cfg.watchdog) if cfg.watchdog.enabled else None
        )
        # Page checksums cost a device reduction + blocking transfer per
        # collection; only pay it when someone will read them (the
        # verifier cadence, or injected page corruption the verifier must
        # catch — other chaos kinds never touch the checksums).
        self._track_pages = cfg.verify_pages_every > 0 or (
            cfg.chaos.enabled and cfg.chaos.serve_corrupt_page_at_step > 0
        )

        if cfg.pool_hbm_bytes > 0:
            # Byte-budget sizing: the pool is however many pages of KV
            # payload fit the budget at the model's kv_cache_dtype —
            # int8 holds 2× the pages of bf16 (4× of fp32) in the same
            # bytes, i.e. quantization buys resident tenants/prefixes,
            # not just bandwidth (see paged_cache.kv_token_bytes for the
            # scale-sidecar honesty note).
            pool = max(
                1,
                cfg.pool_hbm_bytes
                // (cfg.page_size * kv_token_bytes(self.mcfg)),
            )
        else:
            pool = cfg.total_pages or cfg.slots * pages_for(
                self.mcfg.max_seq_len, cfg.page_size
            )
        self.alloc = PageAllocator(pool, cfg.page_size)

        # Multi-tenant adapters (dtc_tpu/adapters/): with an adapter-
        # enabled model, ONE resident (max_adapters, ...) stacked-factor
        # buffer serves every tenant — slot 0 is the all-zero base
        # adapter, per-request indices gather per-SLOT factors inside the
        # jitted step, and load_adapter() writes a tenant's factors at a
        # TRACED stack slot. Values change, shapes never do: tenant churn
        # cannot recompile (audited: serve_decode baseline).
        self.lora_on = lora_enabled(self.mcfg)
        if self.lora_on:
            self.adapter_store = AdapterStore(cfg.max_adapters)
            self.lora_stack = init_lora_stack(model, cfg.max_adapters)
            self.slot_adapter = np.zeros((cfg.slots,), np.int32)
        else:
            self.adapter_store = None
            self.lora_stack = None
            self.slot_adapter = None

        # Speculative decoding (ISSUE 19): extract the resident draft
        # rung ONCE at construction (a zero-copy layer slice of the
        # target params) and give it its own per-slot cache next to the
        # target's. Spec is adapter-free by design: the draft shares the
        # target's embed/head by reference and verify runs the BASE
        # model, so a per-tenant adapter would fork draft and target
        # distributions silently — fail typed at construction instead.
        spec_cfg = getattr(cfg, "spec", None)
        self.spec_on = spec_cfg is not None and spec_cfg.enabled
        if self.spec_on and self.lora_on:
            raise ValueError(
                "speculative decoding (serve.spec) does not compose with "
                "multi-tenant adapters (model adapter.rank > 0): the draft "
                "rung proposes under base weights while each tenant's "
                "verify would run adapted weights — acceptance would "
                "collapse and the draft KV surcharge would be priced "
                "wrong; serve an adapter-free config"
            )
        if self.spec_on:
            check_spec_backend(self.mcfg)  # token-identity needs one path
            self.draft_model, self.draft_params = extract_draft(
                model, params, spec_cfg.draft_layers
            )
            self.draft_cache = init_slot_cache(self.draft_model, cfg.slots)
        else:
            self.draft_model = self.draft_params = self.draft_cache = None
        # Accepted-token throughput window for the SLO floor: emitted
        # tokens and round count since the last SLO check (host ints).
        self._spec_emitted_since = 0
        self._spec_rounds_since = 0
        self._spec_rate_t0 = self.clock()

        self.cache = init_slot_cache(model, cfg.slots)
        self.slots = [_Slot() for _ in range(cfg.slots)]
        self.last_tok = np.zeros((cfg.slots,), np.int32)

        self.closed = False  # shutdown()/drain: submit() refuses typed
        self._in_shutdown = False  # one flight dump for the whole drain
        self.queue: list[Request] = []
        self.requests: dict[str, Request] = {}
        self.results: dict[str, ServeResult] = {}
        self._eff_max_new: dict[str, int] = {}
        self._deadline: dict[str, float] = {}
        self._prefix_store: dict[tuple, tuple[PyTree, int]] = {}
        self._retry_scope: list[str] = []  # rids charged for in-flight retries
        self._it = 0
        self._worked = False  # did this iteration run the model
        self._fps_memo: Any = None  # checksum table for the CURRENT cache

        self._build_fns()
        if self.spec_on:
            self._build_spec_fns()
        self._settle_cache_sharding()

    def _settle_cache_sharding(self) -> None:
        """Kill the PR 9 gotcha at construction: an engine fed
        GSPMD-sharded base params (a trainer-produced base) used to pay
        one EXTRA ``insert_fn`` compile on the first decode — the step's
        output cache settles its GSPMD-normalized sharding only then, so
        an insert compiled against the construction-time (uncommitted)
        cache stopped matching and silently recompiled inside the first
        compile-sensitive window (the two-admission warmup in
        adapter_smoke worked around it).

        Fix: when (and only when) the params carry NamedShardings, run
        ONE throwaway decode step here and adopt its output cache — the
        step's cold compile moves to construction (it was inevitable)
        and every later ``insert_fn``/``step_fn`` call sees the settled
        layout. Unsharded params (every CPU test, the audit's lowered
        entries) skip this entirely: no extra compile, baselines
        unchanged. The warm step writes garbage k/v at position 0 of
        every slot and advances the per-slot index once — both idle-slot
        states the scheduler already treats as meaningless (admission
        surgery overwrites the full row and pins the frontier)."""
        sharded = any(
            isinstance(getattr(leaf, "sharding", None), jax.sharding.NamedSharding)
            for leaf in jax.tree.leaves(self.params)
        )
        if not sharded:
            return
        toks = jnp.zeros((self.cfg.slots,), jnp.int32)
        if self.lora_on:
            warmed, _, _ = self._step_fn(
                self.params, self.lora_stack,
                jnp.asarray(self.slot_adapter), self.cache, toks,
            )
        else:
            warmed, _, _ = self._step_fn(self.params, self.cache, toks)
        self.cache = warmed
        self._fps_memo = None

    # ------------------------------------------------------------------
    # jitted device functions (each compiles ONCE; every per-request
    # quantity — slot, frontier, valid length — is a traced argument)
    # ------------------------------------------------------------------
    #: (model, page_size) -> the jitted fn set. Flax modules hash by
    #: structure, so N in-process replicas serving the SAME model (the
    #: fleet router's configuration) share ONE set of executables instead
    #: of compiling step/prefill/insert once per replica — the honest
    #: reading of "in-process replicas share host compute". The fns close
    #: over nothing engine-specific (params/cache/config all arrive as
    #: arguments), so sharing cannot couple replica state.
    _FN_CACHE: dict = {}

    def _build_fns(self) -> None:
        cache_key = (self.model, self.cfg.page_size)
        cached = ServingEngine._FN_CACHE.get(cache_key)
        if cached is not None:
            (self._step_fn, self._prefill_fn, self._insert_fn,
             self._fingerprint_fn, self._corrupt_fn, adapter_insert) = cached
            if adapter_insert is not None:
                self._adapter_insert_fn = adapter_insert
            return
        model = self.model
        lora_on = self.lora_on

        # ONE decode/prefill core shared by both compiled flavors — the
        # post-processing (greedy argmax matching generate()'s fast path,
        # the per-slot finite flag that detects poisoned logits, the
        # n_valid row selection) must never diverge between the lora and
        # adapter-free programs; only the signature (and the per-slot
        # factor gather) differs per branch below.
        def step_core(params, cache, toks, lora):
            """One continuous-batching decode iteration over ALL slots
            (idle slots compute garbage that is masked/overwritten before
            any read — fixed shapes are what keep this recompile-free)."""
            cache, logits = decode_step(model, params, cache, toks[:, None], lora)
            last = logits[:, -1]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            finite = jnp.all(jnp.isfinite(last.astype(jnp.float32)), axis=-1)
            return cache, nxt, finite

        def prefill_core(params, cache, prompt, n_valid, lora):
            """Batch-1 prefill over a bucket-padded prompt chunk starting
            at the cache's current scalar frontier. Samples the next token
            from the last VALID row (pad rows' outputs are discarded; pad
            K/V lands beyond the frontier the insert below pins, so it is
            masked until real decode overwrites it)."""
            cache, logits = decode_step(model, params, cache, prompt, lora)
            row = logits[0, n_valid - 1]
            tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
            finite = jnp.all(jnp.isfinite(row.astype(jnp.float32)))
            return cache, tok, finite

        if lora_on:
            # Adapter mode: the step/prefill signatures grow the resident
            # factor stack + per-slot adapter indices, gathered INSIDE the
            # one compiled step — tenant admission is a value change,
            # never a shape change (the recompile-free invariant the
            # serve_decode audit baseline pins across adapter load +
            # mixed-tenant admission).
            @jax.jit
            def step_fn(params, stack, aids, cache, toks):
                return step_core(
                    params, cache, toks, gather_slot_lora(stack, aids)
                )

            @jax.jit
            def prefill_fn(params, stack, aid, cache, prompt, n_valid):
                return prefill_core(
                    params, cache, prompt, n_valid,
                    gather_slot_lora(stack, aid),  # aid: (1,) index
                )

            @jax.jit
            def adapter_insert_fn(stack, factors, slot):
                """Hot adapter load: write one tenant's factors into stack
                row ``slot``. ``slot`` is traced — loading into any slot
                reuses this one executable (the stack-side twin of the
                cache-surgery ``insert_fn`` below)."""
                def leaf(s, f):
                    return jax.lax.dynamic_update_slice(
                        s, f[None].astype(s.dtype), (slot,) + (0,) * f.ndim
                    )

                return jax.tree.map(leaf, stack, factors)

            self._adapter_insert_fn = adapter_insert_fn
        else:
            @jax.jit
            def step_fn(params, cache, toks):
                return step_core(params, cache, toks, None)

            @jax.jit
            def prefill_fn(params, cache, prompt, n_valid):
                return prefill_core(params, cache, prompt, n_valid, None)

        @jax.jit
        def insert_fn(batch_cache, row_cache, slot, n_tokens):
            """Admission surgery: copy a prefilled batch-1 cache into slot
            row ``slot`` and pin that slot's frontier to ``n_tokens`` (the
            VALID length — not the padded length the prefill advanced its
            scalar index by). ``slot`` is traced: admitting into any slot
            reuses this one executable."""
            n = jnp.asarray(n_tokens, jnp.int32)

            def leaf(b, r):
                if b.ndim == 1:  # the (slots,) frontier vector
                    return jax.lax.dynamic_update_slice(b, n[None], (slot,))
                start = (0, slot) + (0,) * (b.ndim - 2)
                return jax.lax.dynamic_update_slice(b, r, start)

            return jax.tree.map(leaf, batch_cache, row_cache)

        psize = self.cfg.page_size

        @jax.jit
        def fingerprint_fn(cache):
            """Integrity checksums of EVERY completed-page candidate in
            one launch: a (slots, n_pages) fp32 table, one device call
            and ONE transfer per use — never a host round-trip per page
            (the hot-loop host-sync pattern analysis/hostsync.py lints
            against in the trainer). Position-weighted SIGNED sums, not
            sum(|x|): a plain magnitude sum is blind to sign-bit flips
            and to value permutations within a page — realistic memory
            faults the verifier exists to catch. Deterministic for
            identical bytes (fixed weights, fixed reduction order), so
            the verifier recomputes bit-equal unless the page changed."""
            total = None
            for leaf in jax.tree.leaves(cache):
                if leaf.ndim < 4:
                    continue
                l, b_, s_, hd_ = leaf.shape
                n_pages = s_ // psize
                blk = leaf[:, :, : n_pages * psize, :].reshape(
                    l, b_, n_pages, psize, hd_
                ).astype(jnp.float32)
                w_l = 1.0 + 0.127 * jnp.arange(l, dtype=jnp.float32)
                w_p = 1.0 + 0.3183 * jnp.arange(psize, dtype=jnp.float32)
                w_f = 1.0 + 0.0721 * jnp.arange(hd_, dtype=jnp.float32)
                w = (
                    w_l[:, None, None, None, None]
                    * w_p[None, None, None, :, None]
                    * w_f[None, None, None, None, :]
                )
                fp = jnp.sum(blk * w, axis=(0, 3, 4))
                total = fp if total is None else total + fp
            return total

        @functools.partial(jax.jit, static_argnames=("size",))
        def corrupt_fn(cache, slot, start, size):
            """Chaos-only: overwrite one page of the first KV leaf with a
            constant — finite (so the logits check cannot catch it; only
            the checksum verifier can), device-side, on the real cache."""
            leaves, treedef = jax.tree.flatten(cache)
            done = False
            out = []
            for leaf in leaves:
                if not done and leaf.ndim >= 4:
                    blk = jnp.full(
                        (leaf.shape[0], 1, size, leaf.shape[3]), 123.25,
                        leaf.dtype,
                    )
                    leaf = jax.lax.dynamic_update_slice(
                        leaf, blk, (0, slot, start, 0)
                    )
                    done = True
                out.append(leaf)
            return jax.tree.unflatten(treedef, out)

        self._step_fn = step_fn
        self._prefill_fn = prefill_fn
        self._insert_fn = insert_fn
        self._fingerprint_fn = fingerprint_fn
        self._corrupt_fn = corrupt_fn
        ServingEngine._FN_CACHE[cache_key] = (
            step_fn, prefill_fn, insert_fn, fingerprint_fn, corrupt_fn,
            getattr(self, "_adapter_insert_fn", None),
        )

    def _build_spec_fns(self) -> None:
        """The draft-side jitted fn for spec mode: a batch-1 prefill over
        the SAME padded prompt shapes the target prefill uses (so the
        two caches' frontiers agree at admission). Cached per
        (model, page_size, draft_layers) for the same replica-sharing
        reason as ``_FN_CACHE``; the round itself is the module-level
        :func:`dtc_tpu.spec.serve_round` (shared process-wide via jit's
        own cache — flax modules hash by structure). No finite check /
        retry on the draft: a poisoned draft can only lower acceptance
        (the verify re-derives every emitted token from TARGET logits),
        never corrupt output — the target verify's finite flag is the
        retry trigger. Insert/rollback reuse the generic tree-map
        ``insert_fn`` and the in-round index decrement respectively, so
        the draft cache adds no new surgery paths."""
        key = (
            self.model, self.cfg.page_size, "spec_prefill",
            self.cfg.spec.draft_layers,
        )
        fn = ServingEngine._FN_CACHE.get(key)
        if fn is None:
            draft_model = self.draft_model

            @jax.jit
            def draft_prefill_fn(params, cache, prompt):
                cache, _ = decode_step(draft_model, params, cache, prompt)
                return cache

            ServingEngine._FN_CACHE[key] = fn = draft_prefill_fn
        self._draft_prefill_fn = fn

    # ------------------------------------------------------------------
    # submission (admission control)
    # ------------------------------------------------------------------
    def _pages_needed(self, n_tokens: int) -> int:
        """Page-pool footprint for ``n_tokens`` resident TARGET tokens —
        plus the draft rung's proportional KV surcharge under speculation
        (ISSUE 19): the draft cache holds the same positions at
        ``draft_layers`` of ``n_layers`` depth and rides the SAME pool,
        so every admission/decode reservation prices it or the pool
        over-commits exactly when speculation is on."""
        pages = pages_for(n_tokens, self.cfg.page_size)
        if self.spec_on:
            dl, nl = self.cfg.spec.draft_layers, self.mcfg.n_layers
            pages += (pages * dl + nl - 1) // nl
        return pages

    def submit(self, req: Request, *, resume: ServeResult | None = None) -> str:
        """Enqueue one request. Typed backpressure — raises
        :class:`QueueFullError` past ``queue_depth`` and
        :class:`RequestTooLargeError` for requests that could never run;
        neither is ever dropped silently. A ``rid`` may only be reused
        after its previous submission reached a terminal state (the new
        result then replaces the old one) — resubmitting an in-flight rid
        is a caller bug that would silently merge two requests into one
        record, so it raises ``ValueError`` like the Request validators.

        ``resume`` is the cross-replica failover path (the router's PR 6
        re-prefill lifted fleet-wide): a prior partial :class:`ServeResult`
        whose ``tokens`` are prompt-continuation generated elsewhere. The
        new record starts with those tokens, so admission re-prefills
        prompt+generated and greedy decode continues token-for-token
        identically. Timing accounting is the load-bearing part:
        ``submitted_t`` / ``first_token_t`` carry over (TTFT stays
        anchored at the ORIGINAL submit — fleet histograms must include
        failover cost, not hide it), ``requeued_t`` restarts the
        ``req.queued`` span at THIS hop, and ``n_hops`` increments."""
        if self.closed:
            self.reg.counter("serve_rejected").inc()
            self.reg.emit("serve_reject", rid=req.rid, reason="closed")
            raise EngineClosedError(
                f"request {req.rid}: engine is shut down / draining"
            )
        if resume is not None and len(resume.tokens) >= req.max_new_tokens:
            raise ValueError(
                f"request {req.rid}: resume carries {len(resume.tokens)} "
                f"tokens >= max_new_tokens {req.max_new_tokens} — the prior "
                "hop should have completed it (caller bug)"
            )
        if req.rid in self.requests:  # present == not yet terminal
            raise ValueError(
                f"request {req.rid}: rid already in flight "
                f"(state {self.results[req.rid].state.value})"
            )
        now = self.clock()
        total = len(req.prompt) + req.max_new_tokens
        # Speculation headroom (ISSUE 19): the verify window physically
        # writes spec_k positions from the frontier before rolling back,
        # so the last round still needs spec_k - 1 slots past the final
        # token — a request admitted without them would clamp its verify
        # writes mid-flight. Priced at submit, typed, never mid-decode.
        spec_pad = self.cfg.spec.spec_k - 1 if self.spec_on else 0
        if total + spec_pad > self.mcfg.max_seq_len:
            self.reg.counter("serve_rejected").inc()
            self.reg.emit("serve_reject", rid=req.rid, reason="too_large")
            raise RequestTooLargeError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens})"
                + (f" + spec_k-1 verify headroom ({spec_pad})" if spec_pad
                   else "")
                + f" exceeds max_seq_len ({self.mcfg.max_seq_len})"
            )
        if self._pages_needed(total + spec_pad) > self.alloc.total_pages:
            self.reg.counter("serve_rejected").inc()
            self.reg.emit("serve_reject", rid=req.rid, reason="too_large")
            raise RequestTooLargeError(
                f"request {req.rid}: footprint "
                f"{self._pages_needed(total + spec_pad)} pages"
                + (" (incl. draft KV surcharge)" if self.spec_on else "")
                + f" exceeds the pool ({self.alloc.total_pages})"
            )
        if req.adapter is not None and (
            not self.lora_on or req.adapter not in self.adapter_store
        ):
            self.reg.counter("serve_rejected").inc()
            self.reg.emit(
                "serve_reject", rid=req.rid, reason="unknown_adapter",
                adapter=req.adapter,
            )
            raise UnknownAdapterError(
                f"request {req.rid}: adapter {req.adapter!r} is not resident"
                + ("" if self.lora_on else
                   " (model has no adapter support: adapter.rank == 0)")
            )
        if len(self.queue) >= self.cfg.queue_depth:
            self.reg.counter("serve_rejected").inc()
            self.reg.emit("serve_reject", rid=req.rid, reason="queue_full")
            raise QueueFullError(
                f"request {req.rid}: queue at depth {self.cfg.queue_depth}"
            )
        if req.adapter is not None:
            # Pinned from submit to terminal: an in-flight tenant's
            # factors can never be LRU-evicted out from under it (the
            # eviction→re-prefill recovery path depends on this).
            self.adapter_store.acquire(req.adapter)
        self.requests[req.rid] = req
        res = ServeResult(
            rid=req.rid, state=RequestState.QUEUED, tokens=[],
            submitted_t=now, adapter=req.adapter,
        )
        if resume is not None:
            res.tokens = list(resume.tokens)
            if resume.submitted_t is not None:
                res.submitted_t = resume.submitted_t
            res.first_token_t = resume.first_token_t
            res.n_evictions = resume.n_evictions
            res.n_retries = resume.n_retries
            res.n_hops = resume.n_hops + 1
            res.degraded = resume.degraded
            # Acceptance telemetry carries over: per-request accept_rate
            # must cover the whole request, not just the last hop.
            res.n_spec_proposed = resume.n_spec_proposed
            res.n_spec_accepted = resume.n_spec_accepted
            res.requeued_t = now  # this hop's req.queued span starts here
        self.results[req.rid] = res
        ttl = self.cfg.deadline_s if req.deadline_s is None else req.deadline_s
        # Deadlines anchor at the ORIGINAL submit (== now for a fresh
        # request): a failover hop must not grant a request a fresh TTL.
        self._deadline[req.rid] = (
            res.submitted_t + ttl if ttl and ttl > 0 else float("inf")
        )
        self.queue.append(req)
        self.reg.counter("serve_submitted").inc()
        return req.rid

    # -- load/occupancy introspection (the router's placement inputs) ----
    @property
    def queue_room(self) -> int:
        """Admissions ``submit()`` would still accept before typed
        QueueFullError backpressure — the fleet router's per-replica
        admission-coordination signal (it routes around a full replica
        instead of overriding its bound)."""
        return max(0, self.cfg.queue_depth - len(self.queue))

    @property
    def active_count(self) -> int:
        """Slots currently decoding."""
        return sum(1 for s in self.slots if s.rid is not None)

    @property
    def load(self) -> int:
        """Queued + in-flight requests (the least-loaded placement key)."""
        return len(self.queue) + self.active_count

    @property
    def over_shed_watermark(self) -> bool:
        """Queue occupancy past the shed watermark — the replica is about
        to shed; the router prefers peers with headroom."""
        wm = self.cfg.shed_watermark
        return wm > 0 and len(self.queue) > int(wm * self.cfg.queue_depth)

    def drain_results(self) -> dict[str, ServeResult]:
        """Remove and return every TERMINAL result — the long-running
        caller's memory-reclamation API (``results`` otherwise holds
        each terminal record, tokens included, until drained)."""
        done = {
            rid: r for rid, r in self.results.items()
            if r.state in TERMINAL_STATES
        }
        for rid in done:
            del self.results[rid]
        return done

    # ------------------------------------------------------------------
    # multi-tenant adapters
    # ------------------------------------------------------------------
    def load_adapter(self, name: str, factors: PyTree) -> int:
        """Make tenant ``name``'s LoRA factors resident; returns its stack
        slot. ``factors`` is the per-adapter "lora" tree (the finetune
        export — :func:`dtc_tpu.adapters.load_adapter_file` with the
        engine's stack as ``like``, or a ``TrainResult.state.params``).

        Loading is a device-side write at a TRACED slot index into the
        fixed-shape resident stack, so it NEVER recompiles the decode
        step, even mid-flight with other tenants decoding (audited:
        serve_decode baseline). A full store evicts the least-recently-
        used idle tenant (``adapter_evict`` event); when every tenant has
        in-flight requests the load fails typed
        (:class:`AdapterStoreFullError`). Re-loading a resident name
        overwrites its factors in place (a hot adapter update) and drops
        any prefix KV built under the old factors; it raises ValueError
        while that tenant has in-flight requests (their decode would fork
        from the KV already computed)."""
        if not self.lora_on:
            raise ValueError(
                "load_adapter on a lora-free engine (model adapter.rank == "
                "0); serve an adapter-enabled model config"
            )
        validate_lora_tree(self.lora_stack, factors)
        slot, evicted = self.adapter_store.register(name)
        if evicted is not None:
            self.reg.counter("adapter_evictions").inc()
            self.reg.emit(
                "adapter_evict", name=evicted, slot=slot, iteration=self._it,
                reason="store_lru",
            )
            # The evicted tenant is fully retired: its prefix KV is
            # unreachable-by-correctness (a later SAME-NAME load may carry
            # different factors) and its per-tenant histograms must not
            # accrete forever under tenant churn.
            self._drop_adapter_prefixes(evicted)
            self.reg.drop_histogram(f"serve_ttft_s.{evicted}")
            self.reg.drop_histogram(f"serve_ms_per_token.{evicted}")
        # A (re)load changes the factors behind the name, so any prefix KV
        # built under the OLD factors is stale — reusing it would decode
        # the suffix under new factors against old-prefix KV bytes. Drop
        # the name's entries; the next admission rebuilds them.
        self._drop_adapter_prefixes(name)
        self.lora_stack = self._adapter_insert_fn(
            self.lora_stack, factors, jnp.int32(slot)
        )
        self.reg.counter("adapter_loads").inc()
        self.reg.emit(
            "adapter_load", name=name, slot=slot, iteration=self._it,
            params=int(sum(np.prod(np.shape(f)) for f in jax.tree.leaves(factors))),
        )
        return slot

    # ------------------------------------------------------------------
    # the scheduler iteration
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One iteration: faults/expiry/shed/admit at the boundary, then
        one decode step over the in-flight batch. Returns True while any
        request is queued or in flight."""
        self._it += 1
        self._worked = False  # set by _do_admit/_decode (model ran)
        self._gp_work = 0.0
        t0 = self.clock()
        if self.chaos is not None:
            stall = self.chaos.serve_stall(self._it)
            if stall > 0:
                self.sleep(stall)  # inside the timed iteration, on purpose
        self._expire()
        self._shed()
        self._admit()
        # Condition-dependent chaos shots are consulted ONLY when the
        # engine can act (a completed page / an active request exists) —
        # otherwise the fire-once shot would be consumed, and a chaos
        # event emitted, for an injection that never physically happened.
        if (
            self.chaos is not None
            and self._corruption_candidates()
            and self.chaos.serve_corrupt_page(self._it)
        ):
            self._inject_corruption()
        if (
            self.cfg.verify_pages_every > 0
            and self._it % self.cfg.verify_pages_every == 0
        ):
            self._verify_pages()
        if (
            self.chaos is not None
            and any(s.rid is not None for s in self.slots)
            and self.chaos.serve_preempt(self._it)
        ):
            self._preempt_newest()
        self._ensure_pages()
        self._decode()
        # Only WORKING iterations (a prefill or decode ran) feed the
        # watchdog: idle polling spins are microsecond-scale, and letting
        # them into the trailing median would flag every healthy decode
        # iteration of an interleaved submit()/step() caller as hung.
        # Bus drain BEFORE the watchdog verdict: chaos/recovery records
        # posted during this iteration land in the stream (and their
        # flight dumps fire) first, so a stall-then-flag iteration's LAST
        # dump carries the most diagnostic reason (hung_step).
        self._drain_bus()
        now_it = self.clock()
        if self.goodput is not None:
            # The iteration's unattributed remainder (scheduler
            # bookkeeping, chaos stalls, pure polling spins) is idle —
            # or degraded while a latency objective is breaching.
            idle = max((now_it - t0) - self._gp_work, 0.0)
            self.goodput.note(
                "degraded"
                if self.slo is not None and self.slo.degrade_active
                else "shed_or_idle",
                idle,
            )
            if self._it % self._slo_check_every == 0:
                pct = self.goodput.update(iteration=self._it)
                if self.slo is not None:
                    self.slo.observe("goodput_pct", pct)
        if self.watchdog is not None and self._worked:
            flag = self.watchdog.observe(self._it, now_it - t0)
            if flag is not None:
                self.reg.counter("serve_hung_steps").inc()
                self.reg.emit("hung_step", runtime="serve", **flag)
                self.dump_flight("hung_step", iteration=self._it)
        if self.slo is not None and self._it % self._slo_check_every == 0:
            if self.spec_on and self._spec_rounds_since > 0:
                # Feed the SLO floor ACCEPTED-tokens/s over the window
                # since the last check (only when rounds actually ran —
                # an idle engine's zero-rate must not fake a breach).
                # This is the "price accepted tokens, not proposals"
                # contract: a draft whose acceptance collapses breaches
                # the floor and degrades admissions (degrade_active)
                # even while launches-per-second looks healthy.
                rate = self._spec_emitted_since / max(
                    now_it - self._spec_rate_t0, 1e-9
                )
                self.reg.gauge("serve_accepted_tokens_per_s").set(rate)
                self.slo.observe("serve_accepted_tokens_per_s", rate)
                self._spec_emitted_since = 0
                self._spec_rounds_since = 0
                self._spec_rate_t0 = now_it
            self.slo.evaluate(iteration=self._it)
        return bool(self.queue) or any(s.rid is not None for s in self.slots)

    def run(self, *, max_steps: int = 100_000) -> dict[str, ServeResult]:
        """Drive ``step()`` until idle (every submitted request terminal)
        or ``max_steps`` iterations THIS CALL (a per-call budget, not the
        engine-lifetime counter — interleaved ``submit()``/``run()``
        callers get the full budget every time). Batch-mode entry point;
        interactive callers interleave ``submit()`` with their own
        ``step()`` loop."""
        for _ in range(max_steps):
            if not self.step():
                break
        return self.results

    def shutdown(
        self, *, mode: str = "drain", max_steps: int = 512,
        reason: str = "shutdown",
    ) -> dict[str, ServeResult]:
        """Graceful stop — the serving side of the trainer's SIGTERM
        contract (PR 2/7): stop admitting (``submit()`` raises a typed
        :class:`EngineClosedError` from here on), then

        - ``mode="drain"``: keep stepping until every queued/in-flight
          request is terminal or ``max_steps`` runs out; anything still
          unfinished at the budget is typed-evicted (FAILED +
          EngineClosedError — partial tokens preserved on the result);
        - ``mode="evict"``: typed-evict immediately (the hard-deadline
          SIGTERM path — e.g. a preemption notice too short to drain).

        Either way the recovery bus is drained (pending chaos/recovery
        records land in the stream), the flight recorder dumps ONCE with
        the shutdown reason — previously serving only dumped on crash
        paths — and sinks are flushed. Idempotent; returns ``results``.
        """
        if mode not in ("drain", "evict"):
            raise ValueError(f"unknown shutdown mode {mode!r}")
        if self.closed:
            return self.results
        self.closed = True
        self._in_shutdown = True  # per-request FAILED dumps collapse into
        try:                      # the single shutdown dump below
            if mode == "drain":
                for _ in range(max_steps):
                    if not self.step():
                        break
            for req in list(self.queue):
                self.queue.remove(req)
                self._finish(
                    req.rid, RequestState.FAILED,
                    EngineClosedError(
                        f"request {req.rid}: engine shut down while queued "
                        f"({reason})"
                    ),
                )
            for slot in self.slots:
                if slot.rid is None:
                    continue
                rid = slot.rid
                self._release_slot(rid)
                self._finish(
                    rid, RequestState.FAILED,
                    EngineClosedError(
                        f"request {rid}: engine shut down mid-decode "
                        f"({reason}; partial tokens preserved)"
                    ),
                )
        finally:
            self._in_shutdown = False
        self._drain_bus()
        self.reg.emit(
            "serve_shutdown", reason=reason, mode=mode, iteration=self._it,
        )
        self.dump_flight(f"shutdown: {reason}", iteration=self._it)
        self.reg.flush()
        return self.results

    # ------------------------------------------------------------------
    # boundary phases
    # ------------------------------------------------------------------
    def _expire(self) -> None:
        now = self.clock()
        for req in list(self.queue):
            if now > self._deadline[req.rid]:
                self.queue.remove(req)
                self._finish(
                    req.rid, RequestState.EXPIRED,
                    DeadlineExceededError(
                        f"request {req.rid} expired after "
                        f"{now - self.results[req.rid].submitted_t:.3f}s in queue"
                    ),
                )
        for slot in self.slots:
            if slot.rid is not None and now > self._deadline[slot.rid]:
                rid = slot.rid
                self._release_slot(rid)
                self._finish(
                    rid, RequestState.EXPIRED,
                    DeadlineExceededError(
                        f"request {rid} expired mid-decode (cancelled)"
                    ),
                )

    def _shed(self) -> None:
        wm = self.cfg.shed_watermark
        if wm <= 0 or not self.queue:
            return
        target = int(wm * self.cfg.queue_depth)
        while len(self.queue) > target:
            if self.cfg.shed_policy == "longest_queued":
                victim = min(
                    self.queue, key=lambda r: self.results[r.rid].submitted_t
                )
            else:  # priority: lowest first, longest-queued within
                victim = min(
                    self.queue,
                    key=lambda r: (r.priority, self.results[r.rid].submitted_t),
                )
            self.queue.remove(victim)
            self._finish(
                victim.rid, RequestState.SHED,
                ShedError(
                    f"request {victim.rid} shed under overload (queue "
                    f"{len(self.queue) + 1} > watermark {target} of "
                    f"{self.cfg.queue_depth})"
                ),
            )

    def _admit(self) -> None:
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if s.rid is None]
            if not free:
                return
            # Highest priority first, FIFO within a priority.
            cand = max(
                self.queue,
                key=lambda r: (r.priority, -self.results[r.rid].submitted_t),
            )
            seq = list(cand.prompt) + self.results[cand.rid].tokens
            # Reserve through the FIRST decode write: +1 token plain,
            # +spec_k under speculation (the verify window), with the
            # draft surcharge folded in by _pages_needed.
            first_write = self.cfg.spec.spec_k if self.spec_on else 1
            need = self._pages_needed(len(seq) + first_write)
            if not self._make_room(need, cand.priority):
                return  # pool-bound: wait (deadlines/shedding keep it honest)
            # Reserve BEFORE the prefix store can pin pages out from under
            # this admission — the store competes for whatever remains.
            self.alloc.alloc(cand.rid, need)
            self.queue.remove(cand)
            self._do_admit(cand, free[0], seq)

    def _make_room(self, need: int, priority: int) -> bool:
        """Free pages for an admission: drop LRU prefix-store entries
        first, then evict strictly-lower-priority active requests (never
        equals — admission must not thrash same-priority work)."""
        while not self.alloc.can_fit(need):
            key = self.alloc.evict_prefix_lru()
            if key is None:
                break
            self._prefix_store.pop(key, None)
            self.reg.counter("serve_prefix_evictions").inc()
        while not self.alloc.can_fit(need):
            victims = [
                s.rid for s in self.slots
                if s.rid is not None and self.requests[s.rid].priority < priority
            ]
            if not victims:
                return False
            victim = min(
                victims,
                key=lambda r: (
                    self.requests[r].priority,
                    -(self.results[r].admitted_t or 0.0),
                ),
            )
            self._evict(victim, reason="admission_pressure")
        return True

    @staticmethod
    def prefix_key(req: Request) -> tuple | None:
        """The shared-prefix store key this request would hit (None when
        it declares no usable prefix). ONE definition — the engine's
        store lookups and the router's prefix-affinity placement must
        agree on it or affinity silently routes to misses. Keys are
        scoped PER ADAPTER: the same token prefix under two tenants
        yields different KV bytes (the adapter reshapes the k/v
        projections), so each (adapter, tokens) pair is its own entry."""
        plen = min(req.shared_prefix_len, len(req.prompt) - 1)
        if plen <= 0:
            return None
        return (req.adapter,) + tuple(int(t) for t in req.prompt[:plen])

    def has_prefix(self, req: Request) -> bool:
        """Whether this engine's prefix store already holds the request's
        shared prefix (the router's cache-affinity signal)."""
        key = self.prefix_key(req)
        return key is not None and key in self._prefix_store

    def _prefix_base(self, req: Request) -> tuple[PyTree, int]:
        """(base cache, base length) for this request's prefill: the
        shared-prefix store entry when one matches (prefilled once,
        reused by every admission), else a fresh batch-1 cache."""
        key = self.prefix_key(req)
        if key is None:
            return init_cache(self.model, 1), 0
        plen = len(key) - 1  # key = (adapter, *prefix tokens)
        if key in self._prefix_store:
            self.alloc.touch_prefix(key)
            self.reg.counter("serve_prefix_hits").inc()
            return self._prefix_store[key]
        n_pages = pages_for(plen, self.cfg.page_size)
        fits = self.alloc.pin_prefix(key, n_pages)
        while not fits:
            lru = self.alloc.evict_prefix_lru()
            if lru is None:
                break
            self._prefix_store.pop(lru, None)
            fits = self.alloc.pin_prefix(key, n_pages)
        if not fits:
            return init_cache(self.model, 1), 0  # no room: skip sharing
        padded = _pad_to_bucket(
            [int(t) for t in req.prompt[:plen]], self.cfg.prefill_bucket,
            self.mcfg.max_seq_len,
        )
        try:
            cache, _tok, _fin = self._checked_prefill(
                init_cache(self.model, 1), padded, plen,
                adapter_slot=self._adapter_slot(req),
            )
        except TransientStepError:
            # The entry was never stored: un-account its pinned pages or
            # they leak from the pool with no store key to evict.
            self.alloc.drop_prefix(key)
            raise
        # Pin the stored frontier to the VALID prefix length — the prefill
        # advanced it by the padded length, and a suffix prefill resuming
        # from the padded position would shift every later position (the
        # pad garbage beyond plen is overwritten/masked, but the index
        # must not count it).
        cache = dict(cache)
        cache["index"] = jnp.asarray(plen, jnp.int32)
        self._prefix_store[key] = (cache, plen)
        self.reg.counter("serve_prefix_builds").inc()
        return self._prefix_store[key]

    def _drop_adapter_prefixes(self, name: str) -> None:
        """Invalidate every shared-prefix store entry built under adapter
        ``name`` (prefix keys are ``(adapter, *tokens)``), returning their
        pages to the pool."""
        for key in [k for k in self._prefix_store if k and k[0] == name]:
            self._prefix_store.pop(key, None)
            self.alloc.drop_prefix(key)

    def _adapter_slot(self, req: Request) -> int:
        """The request's stack slot (BASE_SLOT for un-adapted requests or
        a lora-free engine). Submit-time validation + the store refcount
        guarantee residency from submit to terminal, so a miss here is an
        engine bug, not a race."""
        if not self.lora_on or req.adapter is None:
            return BASE_SLOT
        slot = self.adapter_store.slot_of(req.adapter)
        if slot is None:  # pragma: no cover — refcount pins residency
            raise UnknownAdapterError(
                f"request {req.rid}: adapter {req.adapter!r} vanished from "
                "the store while in flight"
            )
        return slot

    def _checked_prefill(self, base: PyTree, padded: list[int], n_valid: int,
                         adapter_slot: int = BASE_SLOT):
        """Prefill + finite check under the transient-fault retry (the
        production path poisoned logits and injected device faults take)."""
        prompt = jnp.asarray(np.asarray(padded, np.int32)[None])

        def attempt():
            if self.lora_on:
                cache, tok, fin = self._prefill_fn(
                    self.params, self.lora_stack,
                    jnp.asarray([adapter_slot], jnp.int32), base, prompt,
                    jnp.int32(n_valid),
                )
            else:
                cache, tok, fin = self._prefill_fn(
                    self.params, base, prompt, jnp.int32(n_valid)
                )
            if not bool(np.asarray(fin)):
                raise TransientStepError("prefill produced non-finite logits")
            self.reg.counter("serve_prefills").inc()
            return cache, tok, fin

        r = self.cfg.retry
        try:
            return retry_call(
                attempt, transient=(TransientStepError,),
                max_attempts=r.max_attempts, backoff_s=r.backoff_s,
                backoff_max_s=r.backoff_max_s, jitter=r.jitter,
                max_elapsed_s=r.max_elapsed_s, on_event=self._on_retry_event,
                sleep=self.sleep, clock=self.clock,
            )
        finally:
            self._retry_scope = []

    def _do_admit(self, req: Request, slot_i: int, seq: list[int]) -> None:
        self._worked = True  # a prefill runs whatever the outcome
        t_adm = self.clock()
        res = self.results[req.rid]
        res.state = RequestState.PREFILL
        if req.rid not in self._eff_max_new:
            eff = req.max_new_tokens
            over_queue = (
                self.cfg.degrade_watermark > 0
                and (len(self.queue) + 1) / self.cfg.queue_depth
                > self.cfg.degrade_watermark
            )
            # A breaching latency SLO degrades new admissions exactly like
            # crossing the queue watermark — the scheduler reacting to the
            # online monitor instead of a post-hoc bench row. A resumed
            # (failover) request that was ALREADY degraded stays capped:
            # a hop must never un-shrink a promise made to shed load.
            slo_hot = self.slo is not None and self.slo.degrade_active
            if self.cfg.degrade_max_new_tokens > 0 and (
                over_queue or slo_hot or res.degraded
            ):
                eff = min(eff, self.cfg.degrade_max_new_tokens)
                if eff < req.max_new_tokens:
                    res.degraded = True
                    self.reg.counter("serve_degraded").inc()
            self._eff_max_new[req.rid] = eff

        try:
            # The prefix-store build is INSIDE the guarded region: a
            # retry-exhausted prefix prefill must end this request typed
            # (FAILED) with its pages returned, not escape the scheduler.
            self._retry_scope = [req.rid]
            base, base_len = self._prefix_base(req)
            suffix = seq[base_len:]
            padded = _pad_to_bucket(
                suffix, self.cfg.prefill_bucket, self.mcfg.max_seq_len - base_len
            )
            self._retry_scope = [req.rid]
            cache1, tok, _fin = self._checked_prefill(
                base, padded, len(suffix), adapter_slot=self._adapter_slot(req)
            )
        except TransientStepError as e:
            self._release_slot(req.rid)  # return the reserved pages
            err = RequestFailedError(
                f"request {req.rid}: prefill retries exhausted"
            )
            err.__cause__ = e
            self._finish(req.rid, RequestState.FAILED, err)
            return
        self.cache = self._insert_fn(
            self.cache, cache1, jnp.int32(slot_i), jnp.int32(len(seq))
        )
        self._fps_memo = None
        if self.spec_on:
            # Prefill the draft rung over the FULL sequence (no prefix
            # store on the draft — its prefill is draft_layers/n_layers
            # of the target's, and sharing target-built prefix KV is
            # shape-impossible) and land its frontier at len(seq), the
            # same place the target insert pinned. Re-admission after
            # eviction/failover passes through here too, so a recovered
            # request resumes with BOTH caches rebuilt — no mid-rollback
            # state can survive a recovery (rounds are atomic in-jit).
            dpad = _pad_to_bucket(
                seq, self.cfg.prefill_bucket, self.mcfg.max_seq_len
            )
            dcache1 = self._draft_prefill_fn(
                self.draft_params, init_cache(self.draft_model, 1),
                jnp.asarray(np.asarray(dpad, np.int32)[None]),
            )
            self.draft_cache = self._insert_fn(
                self.draft_cache, dcache1, jnp.int32(slot_i),
                jnp.int32(len(seq)),
            )
        slot = self.slots[slot_i]
        slot.rid = req.rid
        slot.frontier = len(seq)
        slot.page_fp = {}
        if self.lora_on:
            # The slot now decodes under this request's adapter: one host
            # int per slot, shipped to the step as the (slots,) gather
            # index vector (same lifecycle as last_tok).
            self.slot_adapter[slot_i] = self._adapter_slot(req)
        if self._track_pages and len(seq) >= self.cfg.page_size:
            fps = self._page_fps()
            for p in range(len(seq) // self.cfg.page_size):
                slot.page_fp[p] = float(fps[slot_i, p])
        now = self.clock()
        res.admitted_t = now
        res.state = RequestState.DECODE
        tok = int(np.asarray(tok))
        res.tokens.append(tok)
        if res.first_token_t is None:
            res.first_token_t = now
            self.reg.histogram("serve_ttft_s").observe(res.ttft_s or 0.0)
            self.reg.histogram("serve_queue_wait_s").observe(
                res.queue_wait_s or 0.0
            )
            if self.lora_on:
                # Per-tenant TTFT: one histogram per adapter name ("base"
                # for un-adapted requests) next to the aggregate — the
                # SLO surface a noisy-neighbor tenant shows up on.
                self.reg.histogram(
                    f"serve_ttft_s.{req.adapter or 'base'}"
                ).observe(res.ttft_s or 0.0)
            if self.slo is not None:
                self.slo.observe("serve_ttft_s", res.ttft_s)
                self.slo.observe("serve_queue_wait_s", res.queue_wait_s)
        # Request waterfall spans: queued (submit — or last eviction — to
        # this admission) then prefill, on the request's own track. All
        # edges are timestamps already taken above: zero extra clock work
        # beyond t_adm. Explicit None checks: an injected clock may
        # legitimately read 0.0 at submit.
        q0 = res.requeued_t
        if q0 is None:
            q0 = res.submitted_t if res.submitted_t is not None else t_adm
        self.tracer.emit_span(
            "req.queued", self._ts(q0), self._ts(t_adm),
            cat="serve", tid=req.rid, rid=req.rid, iteration=self._it,
        )
        res.requeued_t = None
        self.tracer.emit_span(
            "req.prefill", self._ts(t_adm), self._ts(now), cat="serve",
            tid=req.rid, rid=req.rid,
            resident=len(seq), prefix_len=base_len, slot=slot_i,
        )
        if self.goodput is not None:
            # A re-prefill after an eviction or a failover hop is the
            # incident's recompute, not fresh productive prefill.
            self.goodput.note(
                "failover_replay"
                if (res.n_evictions or res.n_hops) else "prefill",
                now - t_adm,
            )
            self._gp_work += now - t_adm
        self.last_tok[slot_i] = tok
        self.reg.counter("serve_admissions").inc()
        self.reg.emit(
            "serve_admit", rid=req.rid, slot=slot_i, resident=len(seq),
            prefix_len=base_len, iteration=self._it, adapter=req.adapter,
        )
        self._maybe_complete(slot_i)

    def _ensure_pages(self) -> None:
        """Before decoding, every active slot needs pages covering its
        NEXT write (frontier + 1 plain; frontier + spec_k under
        speculation — the verify writes the whole window before rolling
        back, and the draft surcharge rides along via _pages_needed).
        Exhaustion evicts the lowest-priority, most-recently-admitted
        request — possibly the grower itself."""
        step_write = self.cfg.spec.spec_k if self.spec_on else 1
        for i, slot in enumerate(self.slots):
            if slot.rid is None:
                continue
            need = self._pages_needed(slot.frontier + step_write)
            while not self.alloc.ensure(slot.rid, need):
                key = self.alloc.evict_prefix_lru()
                if key is not None:
                    self._prefix_store.pop(key, None)
                    self.reg.counter("serve_prefix_evictions").inc()
                    continue
                active = [s.rid for s in self.slots if s.rid is not None]
                victim = min(
                    active,
                    key=lambda r: (
                        self.requests[r].priority,
                        -(self.results[r].admitted_t or 0.0),
                    ),
                )
                self._evict(victim, reason="cache_pressure")
                if victim == slot.rid:
                    break

    def _decode(self) -> None:
        if self.spec_on:
            return self._decode_spec()
        active = [
            (i, s.rid) for i, s in enumerate(self.slots) if s.rid is not None
        ]
        if not active:
            return
        self._worked = True
        t_dec = self.clock()
        prev_cache = self.cache  # kept alive so a retry re-runs bit-exactly
        toks = jnp.asarray(self.last_tok)
        last_fin = np.ones((self.cfg.slots,), bool)

        aids = (
            jnp.asarray(self.slot_adapter) if self.lora_on else None
        )

        def attempt():
            nonlocal last_fin
            if self.lora_on:
                cache, nxt, fin = self._step_fn(
                    self.params, self.lora_stack, aids, prev_cache, toks
                )
            else:
                cache, nxt, fin = self._step_fn(self.params, prev_cache, toks)
            nxt = np.asarray(nxt)
            fin = np.asarray(fin).copy()
            if self.chaos is not None and self.chaos.serve_poison_logits(
                self._it
            ):
                fin[:] = False  # the observed device buffer reads back NaN
            last_fin = fin
            if not all(bool(fin[i]) for i, _ in active):
                raise TransientStepError(
                    f"non-finite logits in decode step (iteration {self._it})"
                )
            return cache, nxt

        r = self.cfg.retry
        self._retry_scope = [rid for _, rid in active]
        try:
            cache, nxt = retry_call(
                attempt, transient=(TransientStepError,),
                max_attempts=r.max_attempts, backoff_s=r.backoff_s,
                backoff_max_s=r.backoff_max_s, jitter=r.jitter,
                max_elapsed_s=r.max_elapsed_s, on_event=self._on_retry_event,
                sleep=self.sleep, clock=self.clock,
            )
        except TransientStepError as e:
            # Localize the blast radius: only slots whose logits actually
            # read non-finite on the LAST attempt fail; co-scheduled
            # healthy requests keep their slots and retry next iteration
            # (the step's outputs were discarded, so nothing advanced —
            # their pre-step cache is intact).
            for i, rid in active:
                if bool(last_fin[i]):
                    continue
                self._release_slot(rid)
                err = RequestFailedError(
                    f"request {rid}: decode step retries exhausted"
                )
                err.__cause__ = e
                self._finish(rid, RequestState.FAILED, err)
            return
        finally:
            self._retry_scope = []
        self.cache = cache
        self._fps_memo = None
        now = self.clock()
        # Scheduler-side decode-iteration span (one per iteration over
        # the whole in-flight batch — the Orca iteration waterfall).
        self.tracer.emit_span(
            "decode_step", self._ts(t_dec), self._ts(now), cat="serve",
            tid="sched", iteration=self._it, batch=len(active),
        )
        if self.goodput is not None:
            self.goodput.note("productive_decode", now - t_dec)
            self._gp_work += now - t_dec
        completed_pages = []  # (slot_i, page) finished this step
        for i, rid in active:
            slot = self.slots[i]
            res = self.results[rid]
            tok = int(nxt[i])
            res.tokens.append(tok)
            self.last_tok[i] = tok
            slot.frontier += 1  # the step's input token is now resident
            if self._track_pages and slot.frontier % self.cfg.page_size == 0:
                completed_pages.append((i, slot.frontier // self.cfg.page_size - 1))
        if completed_pages:
            fps = self._page_fps()
            for i, p in completed_pages:
                self.slots[i].page_fp[p] = float(fps[i, p])
        for i, _rid in active:
            self._maybe_complete(i, now=now)
        self.reg.counter("serve_decode_steps").inc()
        self.reg.histogram("serve_batch_occupancy").observe(len(active))

    def _decode_spec(self) -> None:
        """One speculative iteration over the in-flight batch: ONE round
        (draft propose + single k-verify launch + greedy accept +
        rollback — :func:`dtc_tpu.spec.serve_round`) emits 1..spec_k
        tokens per active slot. Same retry / poison-localization /
        page-fingerprint contract as :meth:`_decode`; the extras are the
        honesty plumbing — emitted-vs-window goodput split, per-request
        proposal/acceptance counts, and the accepted-tokens/s SLO feed."""
        active = [
            (i, s.rid) for i, s in enumerate(self.slots) if s.rid is not None
        ]
        if not active:
            return
        self._worked = True
        t_dec = self.clock()
        spec_k = self.cfg.spec.spec_k
        # Retry re-runs bit-exactly from the PRE-round caches (greedy, no
        # rng) — both references held until the round is accepted.
        prev_cache, prev_draft = self.cache, self.draft_cache
        toks = jnp.asarray(self.last_tok)[:, None]
        remaining = np.zeros((self.cfg.slots,), np.int32)
        for i, rid in active:
            remaining[i] = max(
                self._eff_max_new[rid] - len(self.results[rid].tokens), 0
            )
        rem = jnp.asarray(remaining)  # 0 freezes idle slots' frontiers
        last_fin = np.ones((self.cfg.slots,), bool)

        def attempt():
            nonlocal last_fin
            tcache, dcache, _tok_next, emit, n_emit, fin = serve_round(
                self.model, self.draft_model, spec_k, self.params,
                self.draft_params, prev_cache, prev_draft, toks, rem,
            )
            emit = np.asarray(emit)
            n_emit = np.asarray(n_emit)
            fin = np.asarray(fin).copy()
            if self.chaos is not None and self.chaos.serve_poison_logits(
                self._it
            ):
                fin[:] = False  # the observed device buffer reads back NaN
            last_fin = fin
            if not all(bool(fin[i]) for i, _ in active):
                raise TransientStepError(
                    f"non-finite logits in spec verify (iteration {self._it})"
                )
            return tcache, dcache, emit, n_emit

        r = self.cfg.retry
        self._retry_scope = [rid for _, rid in active]
        try:
            tcache, dcache, emit, n_emit = retry_call(
                attempt, transient=(TransientStepError,),
                max_attempts=r.max_attempts, backoff_s=r.backoff_s,
                backoff_max_s=r.backoff_max_s, jitter=r.jitter,
                max_elapsed_s=r.max_elapsed_s, on_event=self._on_retry_event,
                sleep=self.sleep, clock=self.clock,
            )
        except TransientStepError as e:
            # Same blast-radius localization as _decode: only slots whose
            # verify logits read non-finite on the LAST attempt fail; the
            # round's outputs were discarded, so healthy co-scheduled
            # requests retry next iteration from intact pre-round caches
            # (no frontier moved — rounds are atomic).
            for i, rid in active:
                if bool(last_fin[i]):
                    continue
                self._release_slot(rid)
                err = RequestFailedError(
                    f"request {rid}: spec verify retries exhausted"
                )
                err.__cause__ = e
                self._finish(rid, RequestState.FAILED, err)
            return
        finally:
            self._retry_scope = []
        self.cache, self.draft_cache = tcache, dcache
        self._fps_memo = None
        now = self.clock()
        n_active = len(active)
        emitted = int(sum(int(n_emit[i]) for i, _ in active))
        # Goodput honesty (the ISSUE 19 accounting contract): the round's
        # wall time is split by the fraction of the verify window that
        # EMITTED — the rest is the draft-proposal/verify work the target
        # rejected, billed to the typed spec_rejected_draft badput class
        # (never productive_decode) in both the online gauge and the
        # offline span-ledger (a paired decode_step + spec_reject span).
        dur = now - t_dec
        frac = emitted / float(max(n_active * spec_k, 1))
        t_split = t_dec + dur * frac
        self.tracer.emit_span(
            "decode_step", self._ts(t_dec), self._ts(t_split), cat="serve",
            tid="sched", iteration=self._it, batch=n_active,
            spec_k=spec_k, emitted=emitted,
        )
        if dur * (1.0 - frac) > 0.0:
            self.tracer.emit_span(
                "spec_reject", self._ts(t_split), self._ts(now), cat="serve",
                tid="sched", iteration=self._it,
                rejected=n_active * spec_k - emitted,
            )
        if self.goodput is not None:
            self.goodput.note("productive_decode", dur * frac)
            self.goodput.note(SPEC_REJECTED_DRAFT, dur * (1.0 - frac))
            self._gp_work += dur
        completed_pages = []  # (slot_i, page) finished this round
        for i, rid in active:
            slot = self.slots[i]
            res = self.results[rid]
            ne = int(n_emit[i])
            new_toks = [int(t) for t in emit[i, :ne]]
            res.n_spec_proposed += spec_k - 1
            res.n_spec_accepted += max(ne - 1, 0)
            req = self.requests[rid]
            if req.eos_id is not None and req.eos_id in new_toks:
                # Plain decode would have stopped AT the eos — truncate
                # the emission there so the result is token-identical
                # (the slot completes below; its frontier/cache state
                # past the eos is idle-slot garbage from then on).
                new_toks = new_toks[: new_toks.index(req.eos_id) + 1]
            res.tokens.extend(new_toks)
            if new_toks:
                self.last_tok[i] = new_toks[-1]
            old_pages = slot.frontier // self.cfg.page_size
            slot.frontier += ne
            if self._track_pages:
                completed_pages.extend(
                    (i, p) for p in range(
                        old_pages, slot.frontier // self.cfg.page_size
                    )
                )
            self.reg.histogram("serve_accepted_per_launch").observe(ne)
        if completed_pages:
            fps = self._page_fps()
            for i, p in completed_pages:
                self.slots[i].page_fp[p] = float(fps[i, p])
        for i, _rid in active:
            self._maybe_complete(i, now=now)
        self._spec_emitted_since += emitted
        self._spec_rounds_since += 1
        self.reg.counter("serve_decode_steps").inc()
        self.reg.counter("serve_spec_rounds").inc()
        self.reg.counter("serve_spec_proposed").inc(n_active * (spec_k - 1))
        self.reg.counter("serve_spec_accepted").inc(emitted - n_active)
        self.reg.counter("serve_spec_rejected").inc(
            n_active * (spec_k - 1) - (emitted - n_active)
        )
        self.reg.histogram("serve_batch_occupancy").observe(n_active)

    # ------------------------------------------------------------------
    # recovery paths
    # ------------------------------------------------------------------
    def _evict(self, rid: str, *, reason: str) -> None:
        """Evict one active request: free pages + slot, requeue at the
        head with its generated tokens intact. Re-admission re-prefills
        prompt+generated and resumes — greedy decode makes the
        continuation token-for-token identical (asserted in tests)."""
        self._release_slot(rid)
        res = self.results[rid]
        res.state = RequestState.EVICTED  # observable until re-admission
        res.n_evictions += 1
        # The next req.queued span starts HERE, not at submit — the
        # waterfall shows the evict→requeue→re-prefill chain as segments.
        res.requeued_t = self.clock()
        self.queue.insert(0, self.requests[rid])
        self.reg.counter("serve_evictions").inc()
        self.reg.emit(
            "serve_evict", rid=rid, reason=reason, iteration=self._it,
            generated=len(res.tokens),
        )

    def _preempt_newest(self) -> None:
        active = [s.rid for s in self.slots if s.rid is not None]
        if not active:
            return
        victim = max(active, key=lambda r: self.results[r].admitted_t or 0.0)
        self.reg.counter("serve_preemptions").inc()
        self._evict(victim, reason="preempted")

    def _corruption_candidates(self) -> list:
        """Slots with a completed (fingerprinted) page — what chaos
        corruption and the verifier can act on."""
        return [
            (i, s) for i, s in enumerate(self.slots)
            if s.rid is not None and s.page_fp
        ]

    def _inject_corruption(self) -> None:
        """Chaos: damage a completed page of the oldest active request on
        the real device cache (the verifier must catch it)."""
        cands = self._corruption_candidates()
        if not cands:
            return
        i, slot = min(
            cands, key=lambda t: self.results[t[1].rid].admitted_t or 0.0
        )
        page = min(slot.page_fp)
        self.cache = self._corrupt_fn(
            self.cache, jnp.int32(i), jnp.int32(page * self.cfg.page_size),
            size=self.cfg.page_size,
        )
        self._fps_memo = None

    def _verify_pages(self) -> None:
        """Recompute completed-page checksums for every active slot; a
        mismatch is cache-block corruption — typed event + evict for
        bit-exact re-prefill (run every iteration to guarantee no token
        computed from a damaged page is ever emitted)."""
        if not any(s.rid is not None and s.page_fp for s in self.slots):
            return
        fps = self._page_fps()
        for i, slot in enumerate(self.slots):
            if slot.rid is None:
                continue
            for p, fp in slot.page_fp.items():
                if float(fps[i, p]) != fp:
                    self.reg.counter("serve_corruptions").inc()
                    self.reg.emit(
                        "serve_corruption", rid=slot.rid, slot=i, page=p,
                        iteration=self._it,
                    )
                    rid = slot.rid
                    self._evict(rid, reason="corruption")
                    self.dump_flight(
                        "serve_corruption", rid=rid, iteration=self._it
                    )
                    break

    # ------------------------------------------------------------------
    # bookkeeping
    # ------------------------------------------------------------------
    def _page_fps(self) -> np.ndarray:
        """The (slots, n_pages) checksum table — one call, one transfer,
        memoized per cache version (every site that replaces self.cache
        resets ``_fps_memo``), so a decode that completes a page and the
        next iteration's verifier pass share ONE reduction."""
        if self._fps_memo is None:
            self._fps_memo = np.asarray(self._fingerprint_fn(self.cache))
        return self._fps_memo

    def _maybe_complete(self, slot_i: int, now: float | None = None) -> None:
        slot = self.slots[slot_i]
        rid = slot.rid
        if rid is None:
            return
        req = self.requests[rid]
        res = self.results[rid]
        done = len(res.tokens) >= self._eff_max_new[rid] or (
            req.eos_id is not None and res.tokens and res.tokens[-1] == req.eos_id
        )
        if done:
            self._release_slot(rid)
            self._finish(rid, RequestState.DONE, None, now=now)

    def _release_slot(self, rid: str) -> None:
        for i, slot in enumerate(self.slots):
            if slot.rid == rid:
                slot.rid = None
                slot.frontier = 0
                slot.page_fp = {}
                if self.lora_on:
                    self.slot_adapter[i] = BASE_SLOT
        self.alloc.free(rid)

    def _finish(
        self, rid: str, state: RequestState, error, now: float | None = None
    ) -> None:
        res = self.results[rid]
        res.state = state
        res.error = error
        res.finished_t = self.clock() if now is None else now
        # Terminal: drop all per-request host state except the result
        # itself (kept until the caller reads/drains it) — a long-running
        # server must not grow with total requests served.
        self._deadline.pop(rid, None)
        self._eff_max_new.pop(rid, None)
        req = self.requests.pop(rid, None)
        if (
            self.lora_on and req is not None and req.adapter is not None
        ):
            self.adapter_store.release(req.adapter)  # unpin at terminal
        self.reg.counter(f"serve_{state.value}").inc()
        if state is RequestState.DONE and res.ms_per_token is not None:
            self.reg.histogram("serve_ms_per_token").observe(res.ms_per_token)
            if self.lora_on:
                self.reg.histogram(
                    f"serve_ms_per_token.{res.adapter or 'base'}"
                ).observe(res.ms_per_token)
            if self.slo is not None:
                self.slo.observe("serve_ms_per_token", res.ms_per_token)
        if res.accept_rate is not None:
            # Per-request acceptance (ISSUE 19) — every terminal outcome,
            # not just DONE: a shed/expired request's acceptance is still
            # real telemetry about the draft's fit to the workload.
            self.reg.histogram("serve_accept_rate").observe(res.accept_rate)
        if self.slo is not None:
            self.slo.observe_outcome(
                "serve_outcome_shed", state is RequestState.SHED
            )
        # Close the request's span chain: the decode span (first token →
        # terminal, spanning any eviction gaps — the evict instants mark
        # those) and a terminal instant naming the outcome.
        if res.first_token_t is not None:
            self.tracer.emit_span(
                "req.decode", self._ts(res.first_token_t),
                self._ts(res.finished_t), cat="serve",
                tid=rid, rid=rid, n_tokens=len(res.tokens),
            )
        self.tracer.instant(
            f"req.{state.value}", cat="serve", tid=rid,
            t=self._ts(res.finished_t),
            rid=rid, error=type(error).__name__ if error else None,
        )
        self.reg.emit("serve_request", iteration=self._it, **res.summary())
        if state is RequestState.FAILED and not self._in_shutdown:
            self.dump_flight(f"request_failed: {rid}", rid=rid)

    def _on_retry_event(self, etype: str, **fields: Any) -> None:
        self.reg.counter("serve_retries").inc()
        for rid in self._retry_scope:
            self.results[rid].n_retries += 1
        self.bus.post(etype, **fields)

    def _ts(self, t: float) -> float:
        """Scheduler-clock timestamp -> the emission (epoch) timebase —
        the constant shift that makes serve spans sortable against
        trainer shards (see __init__); durations are unaffected."""
        return t + self._epoch0

    def dump_flight(self, reason: str, **meta: Any) -> str | None:
        """Dump the flight-recorder ring (telemetry owns the file path;
        bare engines keep the ring in memory for the caller/tests)."""
        if self.telemetry is not None:
            return self.telemetry.dump_flight(reason, **meta)
        return None

    def _drain_bus(self) -> None:
        for etype, fields in self.bus.drain():
            if etype == "chaos":
                self.reg.counter("chaos_injections").inc()
                # Every injected fault leaves a timeline: the post-mortem
                # the flight recorder exists for, exercised by chaos.
                self.dump_flight(
                    f"chaos: {fields.get('kind', '?')}", iteration=self._it
                )
            elif etype == "recovery":
                self.reg.counter("recoveries").inc()
            fields.setdefault("iteration", self._it)
            self.reg.emit(etype, **fields)
