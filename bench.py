"""Headline benchmark: flagship GPT-89.6M train-step throughput on real hardware.

Two measured configs:

1. **Reference workload** (batch 8 × seq 512 = 4,096 tokens/step, AdamW,
   dropout 0.1 — BASELINE.md): the apples-to-apples comparison against the
   reference's ~27.9k tokens/s. This is the headline JSON line.
2. **Tuned workload** (batch 32, remat, rbg dropout PRNG): same model and
   optimizer, bigger per-step token count — the per-chip-utilization number
   (a 4,096-token step cannot saturate a v5e; see PERF.md).

Prints ONE JSON line:

    {"metric": "tokens_per_sec", "value": ..., "unit": "tokens/s", "vs_baseline": ...}

vs_baseline is relative to the reference's best strategy throughput,
~27.9k tokens/s for DP/TP on its (unspecified) CUDA-12 GPUs
(`/root/reference/outputs/dp/log.csv`, SURVEY.md §6).
"""

from __future__ import annotations

import json
import time

BASELINE_TOKENS_PER_SEC = 27_900.0  # reference DP/TP, SURVEY.md §6

#: Flagship GPT-89.6M dims shared by every bench config (heads/seq vary
#: per config; these do not — one definition so decode and train rows
#: cannot silently drift onto different models).
FLAGSHIP_DIMS = dict(vocab_size=50258, d_model=512, n_layers=12, d_ff=2048)


def flagship_model_cfg(heads=16, max_seq_len=512, dropout=0.1, remat=True,
                       block_q=512, block_kv=512, block_q_bwd=0,
                       block_kv_bwd=0, moe_experts=0, moe_dispatch="einsum",
                       moe_capacity_factor=1.25):
    """The flagship ModelConfig with the sweepable knobs — ONE definition
    (scripts/bench_common.py re-exports it), so bench rows, the step
    sweeps, and sweeps deriving MFU from a config cannot drift onto
    different models."""
    from dtc_tpu.config.schema import ModelConfig

    return ModelConfig(
        **FLAGSHIP_DIMS, n_heads=heads,
        max_seq_len=max_seq_len, dropout=dropout, param_dtype="float32",
        compute_dtype="bfloat16", attention="auto", remat=remat,
        attention_block_q=block_q, attention_block_kv=block_kv,
        attention_block_q_bwd=block_q_bwd, attention_block_kv_bwd=block_kv_bwd,
        moe_experts=moe_experts, moe_dispatch=moe_dispatch,
        moe_capacity_factor=moe_capacity_factor,
    )


def run_config(
    batch: int,
    remat: bool,
    prng_impl: str,
    bench_steps: int = 30,
    n_heads: int = 16,
    max_seq_len: int = 512,
    moe_experts: int = 0,
    moe_dispatch: str = "einsum",
    attention_block_q: int = 512,
    attention_block_kv: int = 512,
    attention_block_q_bwd: int = 0,
    attention_block_kv_bwd: int = 0,
):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn

    from dtc_tpu.config.schema import MeshConfig, OptimConfig, TrainConfig
    from dtc_tpu.data.synthetic import synthetic_batch_iterator
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.parallel.mesh import mesh_from_config
    from dtc_tpu.parallel.sharding import DEFAULT_RULES
    from dtc_tpu.train.train_step import Batch, create_train_step
    from dtc_tpu.train.trainer import init_state
    from dtc_tpu.utils.metrics import mfu

    model_cfg = flagship_model_cfg(
        heads=n_heads, max_seq_len=max_seq_len, remat=remat,
        moe_experts=moe_experts, moe_dispatch=moe_dispatch,
        block_q=attention_block_q, block_kv=attention_block_kv,
        block_q_bwd=attention_block_q_bwd, block_kv_bwd=attention_block_kv_bwd,
    )
    opt_cfg = OptimConfig(lr=3e-4, weight_decay=0.1, grad_clip=1.0)
    train_cfg = TrainConfig(
        seed=0, parallel="dp", batch=batch, steps=1, log_every=1, output_dir="",
        dataset="synthetic", warmup_steps=0, prefetch=0, mesh=MeshConfig(),
    )
    mesh = mesh_from_config("dp", train_cfg.mesh)
    model = GPT(model_cfg)
    warmup_steps = 8

    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        state = init_state(model, model_cfg, train_cfg, opt_cfg, mesh, DEFAULT_RULES)
        step_fn = create_train_step(mesh, model=model, state=state)
        # One fixed device-resident batch: the bench measures the train step,
        # not host tokenization (the trainer's prefetch pipeline covers that).
        tok = next(synthetic_batch_iterator(batch, model_cfg.max_seq_len + 1, model_cfg.vocab_size))
        x, y = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])
        key = jax.random.key(0, impl=prng_impl)

        for i in range(warmup_steps):
            state, loss = step_fn(state, Batch(x=x, y=y), jax.random.fold_in(key, i))
        # Sync via value fetch: on some remote-execution platforms
        # block_until_ready returns before device work completes, but a
        # host transfer of the result cannot.
        float(np.asarray(loss))

        # Best-of-3 timed loops: the minimum of three windows (ROADMAP A0
        # replaces this with a median and its spread). Each window also
        # splits host dispatch from blocked-on-device time (the obs
        # subsystem's step breakdown, at bench granularity): dispatch is
        # the async step_fn calls returning, blocked is the window
        # remainder spent waiting on the final value fetch.
        elapsed = float("inf")
        dispatch = 0.0
        for _ in range(3):
            disp = 0.0
            start = time.perf_counter()
            for i in range(bench_steps):
                t0 = time.perf_counter()
                state, loss = step_fn(
                    state, Batch(x=x, y=y), jax.random.fold_in(key, warmup_steps + i)
                )
                disp += time.perf_counter() - t0
            final_loss = float(np.asarray(loss))
            window = time.perf_counter() - start
            if window < elapsed:
                elapsed, dispatch = window, disp

        # Live working set, sampled while state/batch are still resident.
        # (The allocator's PEAK is process-lifetime-monotone, so a
        # per-config peak would echo whichever earlier config was largest;
        # the single process-wide peak is reported once at bench level.)
        from dtc_tpu.obs.device import max_stat, sample_memory

        in_use = max_stat(sample_memory(), "bytes_in_use")

    step_time = elapsed / bench_steps
    tokens_per_sec = batch * model_cfg.max_seq_len / step_time
    u = mfu(model_cfg, batch, model_cfg.max_seq_len, step_time, jax.device_count())
    res = {
        "step_time_s": round(step_time, 5),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(u, 4) if u is not None else None,
        "final_loss": round(final_loss, 4),
        # Step-time breakdown + device memory (None on backends without
        # PJRT memory accounting).
        "dispatch_s": round(dispatch / bench_steps, 6),
        "blocked_s": round(max(0.0, elapsed - dispatch) / bench_steps, 6),
        "hbm_bytes_in_use": in_use,
    }
    if moe_experts > 0:
        # The dispatch A/B is judged on the useful basis (k·T routed
        # tokens, dispatch uncounted — implementation-independent); the
        # hardware basis above additionally credits the einsum path's
        # structural work. See utils/metrics.py.
        uu = mfu(model_cfg, batch, model_cfg.max_seq_len, step_time,
                 jax.device_count(), moe_basis="useful")
        res["mfu_useful"] = round(uu, 4) if uu is not None else None
        res["moe_dispatch"] = moe_dispatch
    return res


def decode_bench(
    batch: int = 8,
    prompt_len: int = 32,
    new_tokens: int = 128,
    decode_attention: str = "fused",
    kv_cache_dtype: str = "auto",
) -> dict:
    """KV-cache autoregressive decode throughput on the flagship model —
    the serving surface (the reference trains and plots only; SURVEY §1
    lists no sampling path). Random params: decode cost is shape-, not
    value-, dependent.

    ``decode_attention`` selects the attention backend (``fused_layers``
    = the layer-fused megakernel, one Pallas launch per TOKEN —
    ops/decode_fused.py; ``fused`` = the single-launch-per-layer kernel;
    ``xla`` = the oracle) and ``kv_cache_dtype`` the cache storage
    (``int8`` = quantized payload + per-head scales) — the A/Bs that
    isolate launch count and KV bytes from each other. Every row carries
    the memory-bandwidth roofline for its shape
    (utils/metrics.decode_roofline_ms at the run's MEAN cache length,
    DTYPE-CORRECT byte model: the int8 rows are scored against the
    smaller int8 floor, so their pct_of_roofline is not flattered) and
    ``pct_of_roofline`` = floor/measured, so the serving numbers are
    always read against the same floor PERF.md derives.

    ``ms_per_token`` is decode-scan-only (a timed prefill-only leg is
    subtracted, so the prompt-length A/B measures cache-length
    sensitivity, not prefill size); ``wall_s``/``tokens_per_sec`` stay
    end-to-end, the serving-shaped throughput.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.generate import generate
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.utils.metrics import decode_roofline_ms

    model_cfg = ModelConfig(
        **FLAGSHIP_DIMS, n_heads=16,
        max_seq_len=512, dropout=0.0, param_dtype="float32",
        compute_dtype="bfloat16", attention="auto",
        decode_attention=decode_attention, kv_cache_dtype=kv_cache_dtype,
    )
    model = GPT(model_cfg)
    x = jnp.ones((batch, 1), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)["params"]
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, model_cfg.vocab_size, jnp.int32
    )
    out = generate(model, params, prompt, new_tokens)  # compile
    np.asarray(out)
    # Prefill-only leg: max_new_tokens=1 returns before the token scan,
    # so best - best_prefill isolates the scan and ms_per_token measures
    # the decode kernel, not prompt processing — otherwise the p256 row's
    # 8x-larger prefill would masquerade as cache-length sensitivity.
    np.asarray(generate(model, params, prompt, 1))  # compile
    best = best_prefill = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = generate(model, params, prompt, new_tokens)
        np.asarray(out)  # sync by value fetch
        best = min(best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(generate(model, params, prompt, 1))
        best_prefill = min(best_prefill, time.perf_counter() - t0)
    decode_s = max(best - best_prefill, 0.0)
    ms_per_token = decode_s / max(new_tokens - 1, 1) * 1e3
    # Roofline at the mean write frontier over the measured run; a decode
    # "token" here is one STEP of the whole batch, matching ms_per_token.
    floor_ms = decode_roofline_ms(
        model_cfg, batch, prompt_len + new_tokens // 2
    )
    return {
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "decode_attention": decode_attention,
        "kv_cache_dtype": kv_cache_dtype,
        "wall_s": round(best, 4),
        "prefill_s": round(best_prefill, 4),
        "tokens_per_sec": round(batch * new_tokens / best, 1),
        "ms_per_token": round(ms_per_token, 3),
        "roofline_ms_per_token": round(floor_ms, 4),
        "pct_of_roofline": round(floor_ms / ms_per_token, 4),
    }


def spec_decode_bench(
    spec_k: int = 2,
    batch: int = 8,
    prompt_len: int = 32,
    new_tokens: int = 128,
    draft_layers: int | None = None,
    model_cfg=None,
    model_label: str = "flagship",
) -> dict:
    """One speculative-decoding row (ISSUE 19): ``spec_generate`` on the
    layer-fused megakernel backend — a resident ``draft_layers``-deep
    rung of the target proposes ``spec_k - 1`` tokens per round, ONE
    k-query verify launch accepts or rolls back. Scored on the
    launch-economy metrics, not raw ms/token:

    - ``ms_per_accepted_token`` — wall ms per EMITTED token (proposals
      never enter the denominator; the A/B partner is a plain
      ``decode_*`` row's ms_per_token at the same batch/backend);
    - ``tokens_accepted_per_launch`` — mean emitted per verify launch,
      in [1, spec_k]; the plain-decode equivalent is 1.0 by definition;
    - ``accept_rate`` — draft proposals the verify kept.

    Greedy acceptance only (the row is exactness-gated: fused_layers on
    BOTH draft and verify — ``check_spec_backend``). ``draft_layers``
    defaults to n_layers // 3 (the shallow-rung operating point).
    Random params: launch economy is shape-dependent; accept_rate on
    random weights is REAL but pessimistic (a trained target's layers
    are more redundant), so the row's accept_rate is a floor, not the
    deployment number."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.spec import extract_draft, spec_generate
    from dtc_tpu.utils.metrics import (
        ms_per_accepted_token, tokens_accepted_per_launch,
    )

    model_cfg = model_cfg or ModelConfig(
        **FLAGSHIP_DIMS, n_heads=16,
        max_seq_len=512, dropout=0.0, param_dtype="float32",
        compute_dtype="bfloat16", attention="auto",
        decode_attention="fused_layers",
    )
    dl = draft_layers or max(1, model_cfg.n_layers // 3)
    model = GPT(model_cfg)
    x = jnp.ones((batch, 1), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)["params"]
    draft_model, draft_params = extract_draft(model, params, dl)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, model_cfg.vocab_size,
        jnp.int32,
    )
    run = lambda: spec_generate(  # noqa: E731
        model, params, draft_model, draft_params, prompt, new_tokens,
        spec_k=spec_k, return_stats=True,
    )
    out, stats = run()  # compile
    np.asarray(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out, stats = run()
        np.asarray(out)  # sync by value fetch
        best = min(best, time.perf_counter() - t0)
    emitted = batch * new_tokens  # every row completes exactly new_tokens
    launches = int(stats["rounds"])
    rate = int(stats["accepted"]) / max(int(stats["proposed"]), 1)
    mspa = ms_per_accepted_token(best, emitted)
    # Per ROW per launch (one launch verifies the whole batch), so the
    # number lands in [1, spec_k] and plain decode's equivalent is 1.0.
    tapl = tokens_accepted_per_launch(emitted, launches * batch)
    return {
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "decode_attention": model_cfg.decode_attention,
        "kv_cache_dtype": model_cfg.kv_cache_dtype,
        "spec_k": spec_k,
        "draft_layers": dl,
        "spec_acceptance": "greedy",
        "spec_model": model_label,
        "platform": jax.devices()[0].platform,
        "wall_s": round(best, 4),
        "verify_launches": launches,
        "accept_rate": round(rate, 4),
        "tokens_accepted_per_launch": (
            None if tapl is None else round(tapl, 3)
        ),
        "ms_per_accepted_token": (
            None if mspa is None else round(mspa, 3)
        ),
        "tokens_per_sec": round(emitted / best, 1),
    }


from dtc_tpu.utils.percentile import nearest_rank as _pct  # noqa: E402
# _pct: shared nearest-rank percentile (ISSUE 7 satellite) — one
# definition for bench, scripts/trace_report.py, and the registry-
# histogram parity tests. Serving-row percentiles below now come from
# the registry's log-bucketed histograms instead of private sample
# lists; _pct remains the exact oracle for small host-side samples
# (trace_overhead_bench).


def trace_overhead_bench(steps: int = 200) -> dict:
    """Measure the tracing substrate's per-step host cost: the full
    telemetry hook cycle (step clock + step event + span synthesis +
    JSONL write) with spans ON vs OFF, p50 over ``steps`` iterations.
    Pure host-side — the span path adds zero device syncs by design, so
    per-step microseconds here over the benched step time IS the
    tracing overhead (PERF.md records the %)."""
    import tempfile
    import time as _t

    from dtc_tpu.config.schema import ObsConfig
    from dtc_tpu.obs import Telemetry

    def loop(trace: bool) -> float:
        times = []
        with tempfile.TemporaryDirectory(prefix="dtc_trace_ovh_") as d:
            tele = Telemetry(
                ObsConfig(trace=trace, memory_sample_every=0), output_dir=d,
            )
            try:
                for s in range(1, steps + 1):
                    t0 = _t.perf_counter()
                    tele.on_step_start(s)
                    with tele.clock.phase("data_wait"):
                        pass
                    with tele.clock.phase("dispatch"):
                        pass
                    tele.on_step_end(s, elapsed_s=0.0, synced=True)
                    times.append(_t.perf_counter() - t0)
            finally:
                tele.close()
        return float(_pct(times, 0.5))

    on, off = loop(True), loop(False)
    return {
        "steps": steps,
        "us_per_step_traced": round(on * 1e6, 2),
        "us_per_step_untraced": round(off * 1e6, 2),
        "span_overhead_us_per_step": round((on - off) * 1e6, 2),
    }


def devprof_bench(capture_steps: int = 3) -> dict:
    """Device-time attribution row for the b8 reference train step
    (ISSUE 8): a programmatic devprof capture around ``capture_steps``
    steps of the SAME flagship b8 workload as ``reference_workload_b8``,
    rolled up to components via the compiled module's op_name metadata.

    Gated STRUCTURALLY, not on raw timings (CPU wall clocks swing ±30%
    on the CI host; op structure does not): every dot/conv-class op must
    attribute to a model component and the unattributed share must stay
    under 10% — plus the warn-band cross-check against the static
    collective census (``comm_bytes_per_step``), the dynamic counterpart
    of the graph auditor's collective rules.
    """
    import jax
    from flax import linen as nn

    from dtc_tpu.obs import devprof
    from dtc_tpu.utils.metrics import (
        comm_bytes_per_step, gpt_step_flops, peak_flops_per_chip,
    )
    from scripts.bench_common import build_step

    step_fn, state, batch, key, (mesh, rules), model_cfg = build_step(
        batch=8, remat=False
    )
    import tempfile

    with tempfile.TemporaryDirectory(prefix="dtc_devprof_bench_") as trace_dir:
        with mesh, nn.logical_axis_rules(rules):
            # AOT lower+compile: the SAME executable runs the capture and
            # yields the optimized-HLO text whose per-instruction op_name
            # metadata recovers scope paths for the trace's bare op names.
            rng = jax.random.fold_in(key, 0)
            compiled = step_fn.lower(state, batch, rng).compile()
            hlo_text = compiled.as_text()
            out = compiled(state, batch, rng)  # warmup (donates state)
            jax.block_until_ready(out[1])
            comm = comm_bytes_per_step(
                model_cfg, 8, model_cfg.max_seq_len,
                {k: int(v) for k, v in mesh.shape.items()}, "dp",
            )
            with devprof.CaptureWindow(
                trace_dir, steps=capture_steps, reason="bench_b8",
                step_flops=gpt_step_flops(model_cfg, 8, model_cfg.max_seq_len),
                peak_flops=peak_flops_per_chip(),
                comm_estimate=comm,
            ) as cap:
                for _ in range(capture_steps):
                    out = compiled(out[0], batch, rng)
                jax.block_until_ready(out[1])
        if not cap.ok:
            return {"error": "profiler capture failed (see warning above)"}
        analysis = devprof.analyze_capture(trace_dir, hlo_text=hlo_text)
        if analysis is None:
            return {"error": "capture produced no trace file"}
    att = analysis["attribution"]
    gates = devprof.structural_gates(att)
    warnings = devprof.census_crosscheck(att, comm)
    for w in warnings:
        print(f"# devprof census warning: {w}")
    meta = analysis["meta"]
    mfu_dev = att.device_mfu(
        meta.get("step_flops"), meta.get("peak_flops"), capture_steps
    )
    return {
        "capture_steps": capture_steps,
        "device_s_per_step": round(att.total_s / capture_steps, 6),
        "device_busy_s_per_step": round(att.busy_s / capture_steps, 6),
        "component_share": {
            r["component"]: r["share"] for r in att.component_table()
        },
        "phase_share": {
            k: round(v / att.total_s, 4) for k, v in sorted(att.phases.items())
        } if att.total_s else {},
        "overlap_ratio": round(att.overlap_ratio, 4),
        "unattributed_share": gates["unattributed_share"],
        "all_dot_fusions_attributed": gates["all_dot_fusions_attributed"],
        "unattributed_share_ok": gates["unattributed_share_ok"],
        "census_warnings": warnings,
        "device_mfu": None if mfu_dev is None else round(mfu_dev, 4),
        "peak_hbm_bytes": meta.get("peak_hbm_bytes"),
    }


def fsdp_overlap_bench(
    collectives: str = "xla", batch: int = 8, bench_steps: int = 20,
    capture_steps: int = 2,
) -> dict:
    """One leg of the ISSUE 12 A/B: the flagship train step under
    ``parallel: fsdp`` over ALL local devices with ``collectives`` set,
    timed (tokens/s) AND devprof-captured for the comm/compute
    ``overlap_ratio`` — the ROADMAP item-2 headline number (xla leg
    measures 0.0 by construction; the overlapped leg's target is ≥0.5).

    Same-config drift rule (the PR 10 pattern): the row carries
    ``collectives``/``platform``/``devices``, and the guard only compares
    rows whose config matches. Requires a real ring: on a single-device
    platform (one chip, a plain CPU) this raises — the row then records
    the error (and the bench exits non-zero), never a fake number."""
    import jax
    import numpy as np
    from flax import linen as nn

    from dtc_tpu.obs import devprof
    from dtc_tpu.utils.metrics import (
        comm_bytes_per_step, gpt_step_flops, mfu, peak_flops_per_chip,
    )
    from scripts.bench_common import build_step

    if jax.device_count() < 2:
        raise RuntimeError(
            "fsdp_overlap_ab needs >= 2 devices (an FSDP ring of 1 is "
            "inert); run on a multi-chip slice"
        )
    step_fn, state, batch_obj, key, (mesh, rules), model_cfg = build_step(
        batch=batch, remat=False, parallel="fsdp", collectives=collectives,
    )
    import tempfile

    with tempfile.TemporaryDirectory(prefix="dtc_fsdp_overlap_") as trace_dir:
        with mesh, nn.logical_axis_rules(rules):
            rng = jax.random.fold_in(key, 0)
            compiled = step_fn.lower(state, batch_obj, rng).compile()
            hlo_text = compiled.as_text()
            out = compiled(state, batch_obj, rng)
            jax.block_until_ready(out[1])
            for i in range(4):  # warmup
                out = compiled(out[0], batch_obj, rng)
            float(np.asarray(out[1]))
            start = time.perf_counter()
            for _ in range(bench_steps):
                out = compiled(out[0], batch_obj, rng)
            float(np.asarray(out[1]))
            elapsed = time.perf_counter() - start
            comm = comm_bytes_per_step(
                model_cfg, batch, model_cfg.max_seq_len,
                {k: int(v) for k, v in mesh.shape.items()}, "fsdp",
            )
            with devprof.CaptureWindow(
                trace_dir, steps=capture_steps, reason="fsdp_overlap_ab",
                step_flops=gpt_step_flops(model_cfg, batch, model_cfg.max_seq_len),
                peak_flops=peak_flops_per_chip(),
                comm_estimate=comm,
            ) as cap:
                for _ in range(capture_steps):
                    out = compiled(out[0], batch_obj, rng)
                jax.block_until_ready(out[1])
        analysis = (
            devprof.analyze_capture(trace_dir, hlo_text=hlo_text)
            if cap.ok else None
        )
        att = analysis["attribution"] if analysis else None
    step_time = elapsed / bench_steps
    u = mfu(model_cfg, batch, model_cfg.max_seq_len, step_time,
            jax.device_count())
    res = {
        "collectives": collectives,
        "platform": jax.default_backend(),
        "devices": jax.device_count(),
        "step_time_s": round(step_time, 5),
        "tokens_per_sec": round(batch * model_cfg.max_seq_len / step_time, 1),
        "mfu": round(u, 4) if u is not None else None,
        "final_loss": round(float(np.asarray(out[1])), 4),
        "comm_bytes_per_step": round(comm["total"]),
    }
    if att is not None:
        res.update(
            overlap_ratio=round(att.overlap_ratio, 4),
            collective_ms_per_step=round(
                att.collective_s / capture_steps * 1e3, 4
            ),
            fused_collective_ms_per_step=round(
                att.fused_collective_s / capture_steps * 1e3, 4
            ),
        )
    else:
        res["overlap_ratio"] = None  # capture failed: timing still real
    return res


def precision_ab_bench(
    precision: str = "fp32", batch: int = 8, bench_steps: int = 20,
) -> dict:
    """One leg of the ISSUE 14 mixed-precision A/B: the flagship dp train
    step under ``OptimConfig.precision`` — tokens/s PLUS the analytic
    per-device HBM budget (``utils/metrics.train_memory_bytes``), so the
    row carries both the speed and the byte story the static memory audit
    pins (params halved, +4 B/param fp32 masters, bf16 grads on the
    wire). Same-config drift rule: the row carries precision/platform/
    devices. CPU legs are shape-only (this host EMULATES bf16 — often
    slower than fp32); the TPU A/B is the real number (not measured,
    PERF.md ISSUE-14 round)."""
    import jax

    from dtc_tpu.config.schema import OptimConfig
    from dtc_tpu.train.train_step import resolve_precision
    from dtc_tpu.utils.metrics import mfu as mfu_fn
    from dtc_tpu.utils.metrics import train_memory_bytes
    from scripts.bench_common import time_step

    ms = time_step(
        steps=bench_steps, warmup=4, batch=batch, parallel="dp",
        precision=precision, remat=False, dropout=0.0,
    )
    model_cfg = resolve_precision(
        OptimConfig(lr=3e-4, weight_decay=0.1, grad_clip=1.0,
                    precision=precision),
        flagship_model_cfg(remat=False, dropout=0.0),
    )
    mesh_shape = {"data": jax.device_count()}
    mem = train_memory_bytes(
        model_cfg, batch, model_cfg.max_seq_len, mesh_shape, "dp",
        precision=precision,
    )
    step_time = ms / 1e3
    u = mfu_fn(model_cfg, batch, model_cfg.max_seq_len, step_time,
               jax.device_count())
    return {
        "precision": precision,
        "platform": jax.default_backend(),
        "devices": jax.device_count(),
        "step_time_s": round(step_time, 5),
        "tokens_per_sec": round(batch * model_cfg.max_seq_len / step_time, 1),
        "mfu": round(u, 4) if u is not None else None,
        "hbm_params_bytes": round(mem["params"]),
        "hbm_master_bytes": round(mem["master"]),
        "hbm_moments_bytes": round(mem["moments"]),
        "hbm_grads_bytes": round(mem["grads"]),
        "hbm_total_bytes": round(mem["total"]),
    }


def serve_bench(
    rps: float | None,
    *,
    model_cfg=None,
    model_label: str = "flagship",
    n_requests: int = 32,
    slots: int = 4,
    prompt_len: int = 32,
    max_new_tokens: int = 32,
    seed: int = 0,
    queue_depth: int | None = None,
    shed_watermark: float = 0.75,
    deadline_s: float = 0.0,
    max_wall_s: float = 600.0,
    n_tenants: int = 0,
    adapter_rank: int = 8,
    spec_k: int = 0,
    draft_layers: int = 0,
) -> dict:
    """One serving-scheduler row: Poisson arrivals at ``rps`` offered
    requests/s through the continuous-batching engine (dtc_tpu/serve/),
    measuring the SLO surface — sustained tokens/s, p50/p99 TTFT and
    ms/token, queue wait, and the shed/expired/rejected counts that keep
    the tail bounded past saturation.

    ``n_tenants > 0`` is the multi-tenant LoRA leg (ISSUE 10): the model
    gains a rank-``adapter_rank`` adapter config, N tenants' factors are
    loaded into the engine's resident stack, and requests round-robin
    across the tenants plus the un-adapted base — all co-scheduled in the
    same in-flight batch over ONE set of base weights. Everything else
    (arrival process, prompts, SLO accounting) is identical to the
    adapter-free rows, so the serve_lora vs serve row delta IS the
    multi-tenant overhead (the per-row factor gather + low-rank matmuls).

    Arrivals are DETERMINISTIC per ``seed`` (one seeded exponential
    inter-arrival sequence + fixed per-index prompts), so a row reproduces
    on the same machine run-to-run. ``rps=None`` is the closed-loop
    calibration row: every request submitted at t=0, which saturates the
    slots and measures the engine's token capacity — the offered loads
    for the open-loop rows are set relative to it. The past-saturation
    row exists to show overload POLICY, not throughput: bounded queue
    wait and non-exploding p99 ms/token via shedding, never silent drops.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.config.schema import ServeConfig
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.serve import QueueFullError, Request, RequestState, ServingEngine
    from dtc_tpu.utils.arrivals import arrival_schedule

    model_cfg = model_cfg or flagship_model_cfg(dropout=0.0)
    if n_tenants > 0:
        import dataclasses

        from dtc_tpu.config.schema import AdapterConfig

        model_cfg = dataclasses.replace(
            model_cfg, adapter=AdapterConfig(rank=adapter_rank)
        )
    model = GPT(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    # Speculative serving leg (ISSUE 19): spec_k > 0 turns serve.spec on
    # — the engine extracts the resident draft rung at construction and
    # every decode iteration becomes one draft-propose + k-verify round.
    # Exactness-gated by the engine itself (fused_layers backend, no
    # adapters), so a misconfigured row errors instead of measuring a
    # token-forking scheduler.
    spec_kw = {}
    if spec_k > 0:
        from dtc_tpu.config.schema import SpecConfig

        spec_kw["spec"] = SpecConfig(spec_k=spec_k, draft_layers=draft_layers)
    scfg = ServeConfig(
        slots=slots,
        page_size=16,
        queue_depth=queue_depth or 4 * slots,
        max_new_tokens=max_new_tokens,
        prefill_bucket=prompt_len,
        shed_watermark=shed_watermark,
        deadline_s=deadline_s,
        max_adapters=max(n_tenants + 1, 2),
        **spec_kw,
    )
    eng = ServingEngine(model, params, scfg)
    tenant_names: list = [None]
    if n_tenants > 0:
        from dtc_tpu.adapters import init_lora

        # Real (A random / B zero) factor trees: values don't change the
        # schedule, shapes and the per-row gather are what's measured.
        factors = init_lora(model, seed=1)
        for t in range(n_tenants):
            eng.load_adapter(f"tenant{t}", factors)
            tenant_names.append(f"tenant{t}")

    arrivals, prompts = arrival_schedule(
        seed, n_requests, prompt_len, model_cfg.vocab_size, rps,
    )
    # Warm the compiled surfaces outside the measured window (one
    # admission + one decode step), so row 1 doesn't pay the jit tax —
    # then drop the warm request's samples from the SLO histograms so
    # the measured percentiles cover only the row's own requests.
    eng.submit(Request(
        rid="warm", prompt=prompts[0], max_new_tokens=2,
        adapter=tenant_names[-1],
    ))
    eng.run(max_steps=16)
    for name in ("serve_ttft_s", "serve_ms_per_token", "serve_queue_wait_s"):
        eng.reg.histogram(name).reset()

    rejected = 0
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n_requests and arrivals[i] <= now:
            try:
                eng.submit(Request(
                    rid=f"q{i}", prompt=prompts[i],
                    max_new_tokens=max_new_tokens,
                    adapter=tenant_names[i % len(tenant_names)],
                ))
            except QueueFullError:
                rejected += 1  # typed backpressure — counted, not dropped
            i += 1
        busy = eng.step()
        if now > max_wall_s:
            break
        if not busy:
            if i >= n_requests:
                break
            time.sleep(max(0.0, min(arrivals[i] - (time.perf_counter() - t0), 0.01)))
    wall = time.perf_counter() - t0

    res = [r for rid, r in eng.results.items() if rid != "warm"]
    done = [r for r in res if r.state is RequestState.DONE]
    by_state = lambda s: sum(1 for r in res if r.state.value == s)  # noqa: E731
    tokens_out = sum(len(r.tokens) for r in done)
    # Percentiles from the REGISTRY histograms — the same log-bucketed
    # instruments serve/telemetry reports live — not private sample
    # lists (ISSUE 7). ttft/queue-wait cover every request that reached
    # a first token (the SLO population); ms/token covers completed
    # requests (matching the old done-only list). Values are within one
    # ~10% bucket of exact nearest-rank (parity-tested in test_trace).
    q = lambda name, p: eng.reg.histogram(name).percentile(p)  # noqa: E731
    r4 = lambda v: None if v is None else round(v, 4)  # noqa: E731
    # Speculative acceptance aggregates (spec rows only): accepted-token
    # throughput IS sustained_tokens_per_sec (every delivered token was
    # accepted — the exactness gate), so the extra numbers are the
    # acceptance economics behind it.
    spec_fields: dict = {
        "spec_k": spec_k,
        "draft_layers": draft_layers if spec_k > 0 else 0,
        "spec_acceptance": "greedy" if spec_k > 0 else "off",
    }
    if spec_k > 0:
        prop = sum(r.n_spec_proposed for r in res)
        acc = sum(r.n_spec_accepted for r in res)
        spec_fields["spec_accept_rate"] = (
            round(acc / prop, 4) if prop else None
        )
    return {
        **spec_fields,
        "rps": None if rps is None else round(rps, 3),
        "offered_tokens_per_sec": (
            None if rps is None else round(rps * max_new_tokens, 1)
        ),
        "n_requests": n_requests,
        "slots": slots,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "seed": seed,
        "completed": len(done),
        "shed": by_state("shed"),
        "expired": by_state("expired"),
        "rejected": rejected,
        "evictions": sum(r.n_evictions for r in res),
        "wall_s": round(wall, 3),
        "sustained_tokens_per_sec": round(tokens_out / wall, 1) if wall else None,
        "ttft_p50_s": r4(q("serve_ttft_s", 0.50)),
        "ttft_p99_s": r4(q("serve_ttft_s", 0.99)),
        "ms_per_token": r4(q("serve_ms_per_token", 0.50)),
        "ms_per_token_p99": r4(q("serve_ms_per_token", 0.99)),
        "queue_wait_p50_s": r4(q("serve_queue_wait_s", 0.50)),
        "queue_wait_p99_s": r4(q("serve_queue_wait_s", 0.99)),
        "platform": jax.devices()[0].platform,
        "serve_model": model_label,
        "decode_attention": model_cfg.decode_attention,
        "kv_cache_dtype": model_cfg.kv_cache_dtype,
        "n_tenants": n_tenants,
        "adapter_rank": adapter_rank if n_tenants > 0 else 0,
    }


def _calibrated_serve_rows(
    emit, model_cfg, seed: int, prefix: str,
    load_fracs: tuple[tuple[str, float], ...], **kw
) -> None:
    """Shared calibrate-then-load skeleton for every serving row family:
    one closed-loop calibration row (queue deep enough for the whole
    burst, shedding OFF — capacity must be measured with nothing
    dropped), then open-loop Poisson rows at the given fractions of the
    calibrated request capacity. ONE definition so a calibration fix
    applies to the adapter-free and lora families alike."""
    n_req = kw.get("n_requests", 32)
    cal_label = f"{prefix}_cal_closed_loop"
    cal = emit(cal_label, _safe(cal_label, lambda: serve_bench(
        None, model_cfg=model_cfg, seed=seed, queue_depth=n_req,
        shed_watermark=0.0, **kw)))
    cap_tps = cal.get("sustained_tokens_per_sec")
    if not cap_tps:
        print(f"# {prefix} bench: calibration failed; skipping load rows")
        return
    cap_rps = cap_tps / cal["max_new_tokens"]
    for suffix, frac in load_fracs:
        label = f"{prefix}_{suffix}"
        emit(label, _safe(label, lambda f=frac: serve_bench(
            cap_rps * f, model_cfg=model_cfg, seed=seed, **kw)))


def serve_bench_rows(emit, model_cfg=None, *, seed: int = 0, **kw) -> None:
    """The serving row set: closed-loop calibration, then open-loop
    Poisson rows at 0.5x / 0.9x / 3x the calibrated request capacity —
    the 3x row is deliberately past saturation so the recorded
    shed/expired counts and bounded p99 demonstrate the overload policy
    holding (the acceptance criterion), not raw throughput. (3x, not
    1.2x: the closed-loop calibration UNDERestimates steady-state
    capacity — its wall clock includes the serialized prefill ramp — so
    a mild multiplier can land under true saturation and show nothing;
    3x is decisively past it on every platform measured.)"""
    _calibrated_serve_rows(
        emit, model_cfg, seed, "serve",
        (("load50", 0.5), ("load90", 0.9), ("sat300", 3.0)), **kw,
    )


def serve_int8_row(emit, serve_cfg_kw: dict, *, seed: int = 0) -> None:
    """The ISSUE 11 serving row: one closed-loop capacity measurement on
    the layer-fused megakernel + int8 KV cache. A/B against
    ``serve_cal_closed_loop`` (same arrival shape, fp-cache model) reads
    the quantized cache's scheduler-level price; the ``*_int8``
    serve_model label + config fields keep the drift guard comparing
    like to like."""
    import dataclasses

    kw = dict(serve_cfg_kw)
    kw["model_cfg"] = dataclasses.replace(
        kw.pop("model_cfg", None) or flagship_model_cfg(dropout=0.0),
        kv_cache_dtype="int8", decode_attention="fused_layers",
    )
    kw["model_label"] = kw.get("model_label", "flagship") + "_int8"
    emit("serve_int8_closed_loop", _safe("serve_int8_closed_loop",
         lambda: serve_bench(
             None, seed=seed, queue_depth=kw.get("n_requests", 32),
             shed_watermark=0.0, **kw)))


def serve_spec_row(
    emit, serve_cfg_kw: dict, *, seed: int = 0, spec_k: int = 4,
    draft_layers: int | None = None,
) -> None:
    """The ISSUE 19 serving row: one closed-loop capacity measurement
    with ``serve.spec`` ON (layer-fused backend — the exactness gate's
    requirement — and the draft rung resident). A/B against
    ``serve_cal_closed_loop`` (same arrival shape, spec off) reads
    speculation's scheduler-level value: the delta in sustained
    tokens/s is pure launch economy, because the emitted tokens are
    token-identical by construction. The ``*_spec`` serve_model label +
    the spec config fields keep the drift guard comparing like to
    like."""
    import dataclasses

    kw = dict(serve_cfg_kw)
    base_cfg = kw.pop("model_cfg", None) or flagship_model_cfg(dropout=0.0)
    kw["model_cfg"] = dataclasses.replace(
        base_cfg, decode_attention="fused_layers", dropout=0.0
    )
    dl = draft_layers or max(1, base_cfg.n_layers // 3)
    kw["model_label"] = kw.get("model_label", "flagship") + "_spec"
    emit("serve_spec_closed_loop", _safe("serve_spec_closed_loop",
         lambda: serve_bench(
             None, seed=seed, queue_depth=kw.get("n_requests", 32),
             shed_watermark=0.0, spec_k=spec_k, draft_layers=dl, **kw)))


def serve_lora_rows(
    emit, model_cfg=None, *, seed: int = 0, n_tenants: int = 4, **kw
) -> None:
    """The multi-tenant LoRA row set (ISSUE 10): ``n_tenants`` adapters
    sharing ONE resident base model, requests round-robining tenants +
    base under Poisson arrivals — tokens/s and p99 ms/token land next to
    the adapter-free ``serve_*`` rows so the per-token multi-tenant
    overhead is one table read. Distinct ``serve_lora_*`` labels keep the
    decode drift guard's same-model comparison rule working: lora rows
    only ever compare against committed lora rows."""
    _calibrated_serve_rows(
        emit, model_cfg, seed, "serve_lora",
        (("load50", 0.5), ("load90", 0.9)), n_tenants=n_tenants, **kw,
    )


def fleet_bench(
    rps: float | None,
    *,
    model_cfg=None,
    model_label: str = "flagship",
    n_replicas: int = 3,
    n_requests: int = 48,
    slots: int = 2,
    prompt_len: int = 16,
    max_new_tokens: int = 16,
    seed: int = 0,
    queue_depth: int | None = None,
    shed_watermark: float = 0.75,
    kill_replica_at: int = 0,
    max_wall_s: float = 600.0,
    obs_dir: str | None = None,
) -> dict:
    """One serving-FLEET row (ISSUE 13): Poisson arrivals at ``rps``
    offered requests/s through the tenant-aware router over
    ``n_replicas`` in-process engine replicas, measuring the fleet SLO
    surface — sustained tokens/s, fleet-level p50/p99 TTFT + ms/token
    (the router's pooled histograms), AND the per-replica percentile
    rows (each replica's own registry) the fleet view is reduced from.

    ``kill_replica_at > 0`` is the chaos leg: replica 0 is declared dead
    at that router iteration mid-traffic, its queued + in-flight
    requests fail over to survivors (prompt+generated re-prefill), and
    the row records failovers/replica_deaths plus ``zero_silent_drops``
    — accepted submits reconciled against terminal results, the fleet
    acceptance criterion.

    Honesty: in-process replicas time-slice ONE host's compute, so CPU
    fleet wall-clocks are SHAPE-only (scheduling/failover/accounting are
    real; absolute throughput is not — compare fleet rows only against
    fleet rows with the same replica count, which the drift guard
    enforces)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.config.schema import ChaosConfig, RouterConfig, ServeConfig
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.serve import FleetRouter, QueueFullError, Request, RequestState
    from dtc_tpu.utils.arrivals import arrival_schedule

    model_cfg = model_cfg or flagship_model_cfg(dropout=0.0)
    model = GPT(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    rcfg = RouterConfig(
        n_replicas=n_replicas,
        serve=ServeConfig(
            slots=slots,
            page_size=16,
            queue_depth=queue_depth or 4 * slots,
            max_new_tokens=max_new_tokens,
            prefill_bucket=prompt_len,
            shed_watermark=shed_watermark,
        ),
        chaos=ChaosConfig(
            enabled=kill_replica_at > 0,
            fleet_kill_replica_at_step=kill_replica_at,
            fleet_target_replica=0,
        ),
    )
    router = FleetRouter(model, params, rcfg, obs_dir=obs_dir or "")
    arrivals, prompts = arrival_schedule(
        seed, n_requests, prompt_len, model_cfg.vocab_size, rps,
    )
    router.warmup(prompts[0])

    rejected = 0
    accepted = 0
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n_requests and arrivals[i] <= now:
            try:
                router.submit(Request(
                    rid=f"q{i}", prompt=prompts[i],
                    max_new_tokens=max_new_tokens,
                ))
                accepted += 1
            except QueueFullError:
                rejected += 1  # typed fleet backpressure — counted
            i += 1
        busy = router.step()
        if now > max_wall_s:
            break
        if not busy:
            if i >= n_requests:
                break
            time.sleep(max(0.0, min(
                arrivals[i] - (time.perf_counter() - t0), 0.01)))
    wall = time.perf_counter() - t0

    res = list(router.results.values())
    done = [r for r in res if r.state is RequestState.DONE]
    by_state = lambda s: sum(1 for r in res if r.state.value == s)  # noqa: E731
    summ = router.fleet_summary()
    row = {
        "rps": None if rps is None else round(rps, 3),
        "n_requests": n_requests,
        "n_replicas": n_replicas,
        "slots": slots,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "seed": seed,
        "kill_replica_at": kill_replica_at,
        "completed": len(done),
        "shed": by_state("shed"),
        "expired": by_state("expired"),
        "failed": by_state("failed"),
        "rejected": rejected,
        "failovers": summ["failovers"],
        "replica_deaths": summ["replica_deaths"],
        # Zero-silent-drops reconciliation: every ACCEPTED submit must
        # reach a terminal fleet result (the acceptance criterion — a
        # False here is a bug, not a bench observation).
        "zero_silent_drops": accepted == len(res),
        "wall_s": round(wall, 3),
        "sustained_tokens_per_sec": (
            round(sum(len(r.tokens) for r in done) / wall, 1) if wall else None
        ),
        "ttft_p50_s": summ["ttft_p50_s"],
        "ttft_p99_s": summ["ttft_p99_s"],
        "ms_per_token": summ["ms_per_token_p50"],
        "ms_per_token_p99": summ["ms_per_token_p99"],
        "per_replica": {
            k: {kk: v[kk] for kk in (
                "state", "done", "ttft_p99_s", "ms_per_token_p99")}
            for k, v in summ["replicas"].items()
        },
        "platform": jax.devices()[0].platform,
        "serve_model": model_label,
        "decode_attention": model_cfg.decode_attention,
        "kv_cache_dtype": model_cfg.kv_cache_dtype,
    }
    router.close()
    return row


def serve_fleet_rows(
    emit, model_cfg=None, *, seed: int = 0, n_replicas: int = 3, **kw
) -> None:
    """The fleet row set (ISSUE 13): closed-loop calibration over
    ``n_replicas`` replicas, open-loop Poisson at 0.9x and 3x the
    calibrated fleet request capacity (same rationale as
    serve_bench_rows: 3x is decisively past saturation — the row that
    shows FLEET backpressure holding typed), and the replica-kill chaos
    leg at 0.9x — failover mid-traffic with zero silent drops, per-
    replica AND fleet percentiles recorded."""
    import tempfile

    n_req = kw.get("n_requests", 48)
    cal = emit("serve_fleet_cal_closed_loop", _safe(
        "serve_fleet_cal_closed_loop",
        lambda: fleet_bench(
            None, model_cfg=model_cfg, seed=seed, n_replicas=n_replicas,
            queue_depth=n_req, shed_watermark=0.0, **kw)))
    cap_tps = cal.get("sustained_tokens_per_sec")
    if not cap_tps:
        print("# fleet bench: calibration failed; skipping load rows")
        return
    cap_rps = cap_tps / cal["max_new_tokens"]
    for suffix, frac, kill in (
        ("load90", 0.9, 0), ("sat300", 3.0, 0), ("kill", 0.9, 8),
    ):
        label = f"serve_fleet_{suffix}"
        obs_dir = tempfile.mkdtemp(prefix=f"dtc_bench_{suffix}_")
        row = emit(label, _safe(label, lambda f=frac, k=kill, d=obs_dir:
                                fleet_bench(
            cap_rps * f, model_cfg=model_cfg, seed=seed,
            n_replicas=n_replicas, kill_replica_at=k, obs_dir=d, **kw)))
        # Goodput companion rows (ISSUE 16): the load and chaos legs
        # report effective-tokens/s (tokens delivered in COMPLETED
        # requests over the ledger extent) next to the raw tokens/s,
        # plus the fleet goodput % and incident count — so a recovery
        # path that burns wall-clock shows up as a bench number, not
        # just a log line.
        if suffix in ("load90", "kill") and "error" not in row:
            glabel = f"goodput_fleet_{suffix}"
            emit(glabel, _safe(glabel, lambda r=row, d=obs_dir:
                               goodput_row_from_obs(d, r)))


def goodput_row_from_obs(obs_dir: str, base_row: dict) -> dict:
    """One ``goodput_*`` row from a leg's event shards: the ledger's
    fleet goodput %, effective-tokens/s next to the leg's raw tokens/s,
    the badput split, and the incident bill count. Carries the SAME
    config fields as its base leg (platform/model/replicas/chaos) so the
    drift guard's same-config rule can pair rows across rounds."""
    from dtc_tpu.obs.goodput import GoodputLedger

    s = GoodputLedger.from_dir(obs_dir).summary()
    if s is None:
        return {"error": "no classifiable events in obs shards"}
    tokens = s["tokens"]
    eff = tokens.get("effective_serve_tokens_per_sec")
    if eff is None:
        eff = tokens.get("effective_train_tokens_per_sec")
    sec = s["fleet"]["seconds"]
    badput = {
        k: v for k, v in sorted(sec.items(), key=lambda kv: -kv[1])
        if k not in ("productive_train", "productive_decode", "prefill")
    }
    return {
        "goodput_pct": s["fleet"]["goodput_pct"],
        "effective_tokens_per_sec": eff,
        "raw_tokens_per_sec": base_row.get("sustained_tokens_per_sec"),
        "effective_serve_tokens": tokens.get("effective_serve_tokens"),
        "badput_serve_tokens": tokens.get("badput_serve_tokens"),
        "badput_s": {k: round(v, 4) for k, v in badput.items()},
        "incidents": len(s["incidents"]),
        # Same-config drift fields, copied from the measured leg.
        **{k: base_row.get(k) for k in (
            "platform", "serve_model", "n_replicas", "kill_replica_at",
            "slots", "max_new_tokens", "decode_attention",
            "kv_cache_dtype",
        )},
    }


def pool_bench(chaos: bool = True) -> dict:
    """Resource-pool row (ISSUE 17): one scripts/pool_smoke.py leg in a
    subprocess — the pool needs the 8-virtual-device mesh, which the
    bench process (single device) cannot host. The smoke's own gates
    (typed transitions, zero silent drops, loss parity, exactly one
    recompile per mesh change, goodput billing) all hold or the row is
    an error; the row itself is the machine-readable '# pool-smoke:'
    summary (train tokens/s under arbitration, fleet completions,
    transition/resize/recompile counts, goodput %)."""
    import os
    import re
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8"
    )
    cmd = [sys.executable, "scripts/pool_smoke.py", "--json"]
    if chaos:
        cmd.append("--chaos")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if proc.returncode != 0:
        return {
            "error": f"pool_smoke rc={proc.returncode}",
            "tail": (proc.stdout + proc.stderr)[-400:],
        }
    m = re.search(r"# pool-smoke: (\{.*\})", proc.stdout)
    if not m:
        return {"error": "pool_smoke printed no '# pool-smoke:' row"}
    return json.loads(m.group(1))


def pool_diurnal_rows(emit) -> None:
    """The pool row family: the clean diurnal leg and the combined-chaos
    leg (spike-mid-grow abort + kill-mid-shrink) side by side — the
    delta in train tokens/s is the measured price of surviving chaos
    under arbitration."""
    emit("pool_diurnal", _safe(
        "pool_diurnal", lambda: pool_bench(chaos=False)))
    emit("pool_diurnal_chaos", _safe(
        "pool_diurnal_chaos", lambda: pool_bench(chaos=True)))


def _bench_detail(path: str) -> dict:
    """Parsed ``# bench-detail:`` dict of one committed BENCH file, or {}.

    Tolerates any malformed/foreign file shape — the guard is advisory
    and must never be the reason a bench run dies."""
    import re

    try:
        with open(path) as f:
            prev_raw = json.load(f)
        # The committed files wrap the run: the detail dict lives on the
        # "# bench-detail:" line inside "tail".
        m = re.search(r"# bench-detail: (\{.*\})", prev_raw.get("tail", ""))
        return json.loads(m.group(1)) if m else {}
    except (OSError, ValueError, AttributeError, TypeError):
        return {}


def decode_drift_guard(extra: dict, repo_dir: str | None = None) -> list[str]:
    """Compare this run's decode rows against the newest committed
    ``BENCH_r*.json`` that HAS decode rows and flag any ms/token
    regression > 20% — the same drift discipline the training rows get
    from round-over-round BENCH comparison, applied automatically so a
    serving regression cannot ship silently inside an otherwise-green
    bench. Returns human-readable flag strings (also stored under
    ``extra["decode_regressions"]``).

    Serving rows (labels ``serve_*``, ISSUE 6) ride the same guard with
    their own newest-file-with-serve-rows fallback; a serve comparison is
    additionally skipped when the committed row was measured on a
    different platform (comparing TPU ms/token against a CPU-measured
    row would be noise, not drift).

    Same-CONFIG comparisons only (ISSUE 11, the same rule as the PR 6
    same-platform rule): rows are compared only when their
    ``decode_attention`` and ``kv_cache_dtype`` labels match — a label
    whose config changed meaning across rounds (e.g. decode_b8 re-pointed
    at a different backend) must not be judged against its old self.
    Rows committed before these fields existed default to the config
    every pre-ISSUE-11 row actually ran ("fused"/"auto").

    Degrades gracefully: a newest file without decode rows (e.g. a round
    whose decode configs all ``_safe``-errored) falls back to older
    files, and when NO committed file carries a decode ms/token the guard
    prints a warning and compares nothing — it never raises."""
    import glob
    import os

    repo_dir = repo_dir or os.path.dirname(os.path.abspath(__file__))
    paths = sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json")))
    flags: list[str] = []
    if not paths:
        return flags

    def compare(prefix: str, metric: str, comparable,
                higher_is_better: bool = False) -> None:
        """One guarded row family: walk committed files newest-first,
        stop at the first file holding at least one COMPARABLE row —
        a newest file whose rows are all incomparable (different
        platform/model/config, e.g. TPU rows committed during a CPU
        round) must not deactivate the guard while an older comparable
        file exists — and flag metric regressions > 20%.
        ``comparable(old, row)`` is the family's same-config rule.
        ``higher_is_better`` flips the regression direction (the goodput
        family: a DROP in effective-tokens/s is the regression)."""

        def has_rows(detail: dict) -> bool:
            return any(
                label.startswith(prefix) and isinstance(row, dict)
                and metric in row
                for label, row in detail.items()
            )

        if not has_rows(extra):
            return  # this run measured no such rows: nothing to guard
        for path in reversed(paths):
            prev = _bench_detail(path)
            if not has_rows(prev):
                continue
            compared = False
            for label, row in extra.items():
                if not (isinstance(row, dict) and label.startswith(prefix)):
                    continue
                old = prev.get(label)
                if not (isinstance(old, dict) and metric in old):
                    continue
                if not comparable(old, row):
                    continue
                compared = True
                new_v, old_v = row.get(metric), old[metric]
                if not (
                    isinstance(new_v, (int, float)) and isinstance(old_v, (int, float))
                    and new_v and old_v
                ):
                    continue
                worse = (new_v < old_v / 1.2 if higher_is_better
                         else new_v > 1.2 * old_v)
                if worse:
                    flags.append(
                        f"{label}: {new_v} {metric} vs {old_v} in "
                        f"{os.path.basename(path)} ({(new_v / old_v - 1) * 100:+.0f}%)"
                    )
            if compared:
                return
        if prefix == "decode":
            print(
                "# decode drift guard: no committed BENCH_r*.json carries "
                "decode rows — nothing to compare against (guard inactive "
                "this run)"
            )

    # Same-config rule per family. Decode: decode_attention/kv_cache_dtype
    # must match (pre-ISSUE-11 rows lack the fields and ran the then-only
    # config — normalize so history stays guarded). Serve: additionally
    # same platform AND serve model (tiny vs flagship rows share
    # labels). fsdp_overlap (ISSUE 12): collectives/platform/devices must
    # all match — an overlapped row must never be judged against an xla
    # row, nor a multi-chip row against a 1-chip one.
    def decode_cfg(r):
        # Spec keys (ISSUE 19) ride the same rule: a speculative row must
        # never be judged against a plain one (their ms/token means
        # different things — accepted vs sequential tokens). Pre-ISSUE-19
        # rows lack the fields and were all spec-off — normalize, same
        # pattern as the ISSUE-11 kv_cache_dtype default above.
        return (
            r.get("decode_attention", "fused"),
            r.get("kv_cache_dtype", "auto"),
            r.get("spec_k", 0),
            r.get("draft_layers", 0),
            r.get("spec_acceptance", "off"),
        )

    compare("decode", "ms_per_token", lambda o, r: decode_cfg(o) == decode_cfg(r))
    # Speculative rows (ISSUE 19, labels spec_*): guarded on
    # ms-per-ACCEPTED-token — the launch-economy metric a spec row is
    # scored by (raw ms/token would reward rejected work) — under the
    # decode rule, whose spec keys keep k2 vs k4 vs plain apart.
    compare("spec", "ms_per_accepted_token", lambda o, r: (
        decode_cfg(o) == decode_cfg(r)
        # A CPU-measured spec row (tiny model) must never be judged against a TPU flagship one — the same
        # platform/model rule the serve family carries.
        and o.get("platform") == r.get("platform")
        and o.get("spec_model") == r.get("spec_model")
    ))
    # Fleet rows (serve_fleet_*, ISSUE 13) ride the serve family via the
    # shared "serve" prefix; their extra same-config requirement is the
    # replica count (absent on both sides for single-engine rows) — a
    # 3-replica row must never be judged against a 1-replica one, and
    # the chaos kill leg only against kill legs (kill_replica_at match).
    compare("serve", "ms_per_token", lambda o, r: (
        decode_cfg(o) == decode_cfg(r)
        and o.get("platform") == r.get("platform")
        and o.get("serve_model") == r.get("serve_model")
        and o.get("n_replicas") == r.get("n_replicas")
        and o.get("kill_replica_at") == r.get("kill_replica_at")
    ))
    compare("fsdp_overlap", "step_time_s", lambda o, r: all(
        o.get(k) == r.get(k) for k in ("collectives", "platform", "devices")
    ))
    # Goodput rows (ISSUE 16): effective-tokens/s is higher-is-better —
    # a >20% DROP is the regression. Same-config rule: platform + model
    # + replica count + the chaos config (kill_replica_at) must all
    # match, so a clean leg is never judged against a kill leg.
    compare("goodput", "effective_tokens_per_sec", lambda o, r: all(
        o.get(k) == r.get(k) for k in (
            "platform", "serve_model", "n_replicas", "kill_replica_at")
    ), higher_is_better=True)
    # Pool rows (ISSUE 17): train tokens/s under arbitration is
    # higher-is-better. Same-config rule: platform + model + chaos leg
    # must match — the clean diurnal leg is never judged against the
    # combined-chaos one.
    compare("pool", "train_tokens_per_sec", lambda o, r: all(
        o.get(k) == r.get(k) for k in ("platform", "serve_model", "chaos")
    ), higher_is_better=True)

    if flags:
        extra["decode_regressions"] = flags
    return flags


def ring_block_smoke() -> dict:
    """Execute the zigzag-ring Pallas BLOCK kernels on the real chip.

    The ring itself needs >= 2 devices (the whole-ring VJP short-circuits
    to dense on this 1-chip box, and CPU tests run the kernels in
    interpret mode), but the four per-device kernel flavors the ring is
    built from — fwd/bwd x causal/cross-chunk — are ordinary single-chip
    pallas_calls. Compiling and running them here pins the Mosaic path
    every round (round-4 VERDICT weak #5): parity vs an fp32 jnp oracle,
    on-device.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.ops import flash_attention as fa

    b, tc, h, d = 2, 512, 16, 32
    g = fa._packed_group(d, h)
    scale = float(d**-0.5)
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(kq, (b, tc, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, tc, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, tc, h, d), jnp.float32)
    do = jax.random.normal(kd, (b, tc, h, d), jnp.float32)
    pk = lambda x: x.reshape(b, tc, h * d)

    def oracle(q, k, v, causal):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            mask = jnp.tril(jnp.ones((tc, tc), bool))
            s = jnp.where(mask, s, -1e9)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    res = {}
    for causal in (True, False):
        tag = "causal" if causal else "cross"
        fwd = jax.jit(lambda q, k, v, c=causal: fa._block_call(
            pk(q), pk(k), pk(v), scale, c, g, d))
        out, lse = fwd(q, k, v)
        ref = oracle(q, k, v, causal)
        res[f"fwd_{tag}_err"] = float(
            jnp.max(jnp.abs(out.reshape(b, tc, h, d) - ref))
        )
        bwd = jax.jit(lambda q, k, v, do, o, lse, c=causal: fa._block_call(
            pk(q), pk(k), pk(v), scale, c, g, d, do=pk(do), o=o, lse=lse))
        dq, dk, dv = bwd(q, k, v, do, out, lse)
        g_ref = jax.jit(jax.grad(
            lambda q, k, v, c=causal: jnp.sum(oracle(q, k, v, c) * do),
            argnums=(0, 1, 2),
        ))(q, k, v)
        for name, got, ref_g in zip("qkv", (dq, dk, dv), g_ref):
            err = float(jnp.max(jnp.abs(
                got.reshape(b, tc, h, d) - ref_g
            )) / (jnp.max(jnp.abs(ref_g)) + 1e-8))
            res[f"bwd_{tag}_d{name}_err"] = round(err, 6)
        res[f"fwd_{tag}_err"] = round(res[f"fwd_{tag}_err"], 6)
    # Tolerance: on TPU, fp32 dots run as bf16 MXU passes at DEFAULT
    # precision on BOTH sides of the comparison, so kernel-vs-oracle
    # differences land at ~1e-2 (measured max 0.0104; exact-arithmetic
    # parity at 2e-5 is pinned by the CPU interpret-mode tests). A real
    # mask/lse/layout bug shows up as O(1) error.
    res["ok"] = bool(np.all([e < 5e-2 for kk_, e in res.items() if kk_ != "ok"]))
    return res


def _safe(label: str, fn, retries: int = 1):
    """Run one bench config; an exception becomes an ``{"error": ...}``
    row so the remaining rows still run — ``main`` exits non-zero when
    any row carries one."""
    err = "unknown error"  # bound before the loop: `retries` could be -1,
    # and leaving it to the except-branch makes the return below depend on
    # loop-iteration order (round-5 ADVICE fragile-binding cleanup).
    for attempt in range(retries + 1):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — robustness surface
            first = (str(e).splitlines() or [""])[0]
            err = f"{type(e).__name__}: {first[:120]}"
            print(f"# bench config {label} attempt {attempt + 1} failed: {err}")
    return {"error": err}


def main(argv: list[str] | None = None) -> None:
    """Run the rows; exit non-zero if any emitted row carries "error"
    (``_safe`` lets the remaining rows run, it does not make a crashed
    row a pass)."""
    errored = _run(argv)
    if errored:
        raise SystemExit(f"bench: {len(errored)} row(s) failed: {errored}")


def _run(argv: list[str] | None = None) -> list[str]:
    import argparse

    import jax

    from dtc_tpu.obs import MemorySink, MetricsRegistry
    from dtc_tpu.utils.dist import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser(description="dtc_tpu benchmark")
    ap.add_argument(
        "--serve-only", action="store_true",
        help="run ONLY the serving-scheduler rows (the full bench still "
        "includes them)",
    )
    ap.add_argument(
        "--fleet-only", action="store_true",
        help="run ONLY the serving-fleet rows (calibration, load, the "
        "replica-kill chaos leg) plus their goodput_* companion rows "
        "(ISSUE 16 — effective-tokens/s next to raw tokens/s)",
    )
    ap.add_argument(
        "--pool-only", action="store_true",
        help="run ONLY the resource-pool rows (ISSUE 17 — the diurnal "
        "and combined-chaos pool_smoke legs in subprocesses; train "
        "tokens/s under arbitration next to fleet completions and the "
        "transition/recompile counts)",
    )
    ap.add_argument(
        "--spec-only", action="store_true",
        help="run ONLY the speculative-decoding rows (ISSUE 19 — the "
        "spec_b8_k{2,4} launch-economy rows + the serve_spec closed-loop "
        "capacity row and its spec-off calibration partner)",
    )
    ap.add_argument(
        "--devprof-only", action="store_true",
        help="run ONLY the device-time attribution row + trace overhead "
        "(ISSUE 8; the full bench still includes them)",
    )
    ap.add_argument(
        "--serve-model", default="flagship", choices=("flagship", "tiny"),
        help="model for the serving rows: flagship (TPU-scale) or tiny "
        "(the audit/test model — scheduler metrics are model-agnostic and "
        "this keeps a CPU run in minutes)",
    )
    ap.add_argument("--serve-seed", type=int, default=0,
                    help="arrival-process seed (rows reproduce per seed)")
    args = ap.parse_args(argv)

    # Every per-config result flows through the metrics registry — the
    # same funnel the trainer emits through — so the BENCH json is a view
    # over registry events, not a hand-assembled dict.
    reg = MetricsRegistry()
    sink = reg.add_sink(MemorySink())

    errored: list[str] = []

    def emit(label: str, res: dict) -> dict:
        if "error" in res:
            errored.append(label)
        reg.emit("bench_config", label=label, **res)
        return res

    if args.serve_model == "tiny":
        from dtc_tpu.analysis.lowering import audit_model_cfg

        serve_cfg_kw = dict(
            model_cfg=audit_model_cfg(), model_label="tiny", prompt_len=8,
            max_new_tokens=8, slots=4, n_requests=32,
        )
    else:
        serve_cfg_kw = dict(model_cfg=None, model_label="flagship")

    if args.spec_only:
        # The spec_* rows on the chosen model (tiny fits the 1-core CPU
        # host in minutes; flagship is the TPU row set). Tiny shapes
        # respect the audit model's max_seq_len=32 headroom
        # (prompt + new + spec_k - 1 <= 32).
        if args.serve_model == "tiny":
            from dtc_tpu.analysis.lowering import audit_model_cfg

            spec_gen_kw = dict(
                model_cfg=audit_model_cfg(decode_attention="fused_layers"),
                model_label="tiny", prompt_len=8, new_tokens=16,
                draft_layers=2,
            )
        else:
            spec_gen_kw = dict()
        for k in (2, 4):
            emit(f"spec_b8_k{k}", _safe(f"spec_b8_k{k}",
                 lambda k=k: spec_decode_bench(spec_k=k, **spec_gen_kw)))
        # The closed-loop A/B pair: spec-off calibration + spec-on row,
        # same arrival shape — the delta IS the launch economy.
        cal_label = "serve_cal_closed_loop"
        n_req = serve_cfg_kw.get("n_requests", 32)
        emit(cal_label, _safe(cal_label, lambda: serve_bench(
            None, seed=args.serve_seed, queue_depth=n_req,
            shed_watermark=0.0, **serve_cfg_kw)))
        serve_spec_row(emit, serve_cfg_kw, seed=args.serve_seed)
        extra = {
            "devices": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
            "serve_model": args.serve_model,
        }
        for ev in sink.events:
            if ev["etype"] != "bench_config":
                continue
            extra[ev["label"]] = {
                k: v for k, v in ev.items()
                if k not in ("etype", "ts", "proc", "label")
            }
        for flag in decode_drift_guard(extra):
            print(f"# DECODE REGRESSION: {flag}")
        print("# bench-detail:", json.dumps(extra))
        reg.close()
        return errored

    if args.devprof_only:
        emit("devprof_b8", _safe("devprof_b8", devprof_bench))
        emit("trace_overhead", _safe("trace_overhead", trace_overhead_bench))
        extra = {
            "devices": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
        }
        for ev in sink.events:
            if ev["etype"] != "bench_config":
                continue
            extra[ev["label"]] = {
                k: v for k, v in ev.items()
                if k not in ("etype", "ts", "proc", "label")
            }
        print("# bench-detail:", json.dumps(extra))
        reg.close()
        return errored

    if args.pool_only:
        pool_diurnal_rows(emit)
        extra = {
            "devices": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
        }
        for ev in sink.events:
            if ev["etype"] != "bench_config":
                continue
            extra[ev["label"]] = {
                k: v for k, v in ev.items()
                if k not in ("etype", "ts", "proc", "label")
            }
        for flag in decode_drift_guard(extra):
            print(f"# DECODE REGRESSION: {flag}")
        print("# bench-detail:", json.dumps(extra))
        reg.close()
        return errored

    if args.fleet_only:
        serve_fleet_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
        extra = {
            "devices": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
            "serve_model": args.serve_model,
        }
        for ev in sink.events:
            if ev["etype"] != "bench_config":
                continue
            extra[ev["label"]] = {
                k: v for k, v in ev.items()
                if k not in ("etype", "ts", "proc", "label")
            }
        for flag in decode_drift_guard(extra):
            print(f"# DECODE REGRESSION: {flag}")
        print("# bench-detail:", json.dumps(extra))
        reg.close()
        return errored

    if args.serve_only:
        serve_bench_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
        serve_lora_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
        serve_int8_row(emit, serve_cfg_kw, seed=args.serve_seed)
        # Speculative serving row (ISSUE 19): closed-loop capacity with
        # serve.spec ON — A/B partner of serve_cal_closed_loop.
        serve_spec_row(emit, serve_cfg_kw, seed=args.serve_seed)
        # Fleet rows (ISSUE 13): router over 3 in-process replicas —
        # calibration, 0.9x/3x offered load, replica-kill chaos leg.
        serve_fleet_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
        emit("trace_overhead", _safe("trace_overhead", trace_overhead_bench))
        extra = {
            "devices": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
            "serve_model": args.serve_model,
        }
        for ev in sink.events:
            if ev["etype"] != "bench_config":
                continue
            extra[ev["label"]] = {
                k: v for k, v in ev.items()
                if k not in ("etype", "ts", "proc", "label")
            }
        for flag in decode_drift_guard(extra):
            print(f"# DECODE REGRESSION: {flag}")
        print("# bench-detail:", json.dumps(extra))
        reg.close()
        return errored

    ref = emit("reference_workload_b8", run_config(batch=8, remat=False, prng_impl="rbg"))
    tuned = emit(
        "tuned_b32_remat",
        run_config(batch=32, remat="block_save_flash", prng_impl="rbg"),
    )
    # Same 89.6M-class budget with an MXU-friendly attention shape
    # (head_dim=128): demonstrates the framework, not the workload, sets the
    # ceiling (PERF.md "Why 40% is out of reach for THIS model shape").
    hd128 = emit("mxu_hd128_b32_remat", _safe("hd128", lambda: run_config(
        batch=32, remat="block_save_flash", prng_impl="rbg", n_heads=4)))
    # Long-context: 8x the flagship sequence through the flash kernel.
    # Tiling from the round-5 on-chip sweep (PERF.md): the forward wants
    # wide KV blocks, the fused backward a square 512 tile.
    long_ctx = emit("long_context_t4096_b4", _safe("long_ctx", lambda: run_config(
        batch=4, remat="block_save_flash", prng_impl="rbg", max_seq_len=4096,
        bench_steps=10, attention_block_kv=1024,
        attention_block_q_bwd=512, attention_block_kv_bwd=512,
    )))
    # T=8192: exercises the packed SPLIT backward (fused dk/dv scratches
    # exceed VMEM past T=4096) — the shape that had no packed path before
    # round 5.
    long_ctx_8k = emit("long_context_t8192_b2", _safe("long_ctx_8k", lambda: run_config(
        batch=2, remat="block_save_flash", prng_impl="rbg", max_seq_len=8192,
        bench_steps=8, attention_block_kv=1024,
        attention_block_q_bwd=512, attention_block_kv_bwd=1024,
    )))
    # Same long-context budget at an MXU-friendly head shape (head_dim=128):
    # the hd32 row's gap to peak is the workload's lane bound, not the
    # kernels' (PERF.md round-5 ceiling analysis).
    long_ctx_hd128 = emit(
        "long_context_t4096_b4_hd128", _safe("long_ctx_hd128", lambda: run_config(
            batch=4, remat="block_save_flash", prng_impl="rbg", max_seq_len=4096,
            bench_steps=10, n_heads=4, attention_block_kv=1024,
        )))
    # MoE: flagship dims with top-2 expert FFNs — the dispatch-backend A/B
    # (ops/moe_dispatch.py): einsum vs sort at E=8 and E=16, identical
    # routing, so step-time deltas are pure dispatch cost. Rows report
    # both MFU bases ("mfu" = hardware/einsum-structural, "mfu_useful" =
    # k·T routed tokens — the A/B-honest number); PERF.md MoE section
    # carries the resulting tables.
    moe = emit("moe_e8_top2_b32", _safe("moe", lambda: run_config(
        batch=32, remat="block_save_flash", prng_impl="rbg", moe_experts=8,
        bench_steps=15,
    )))
    emit("moe_e8_top2_b32_sort", _safe("moe_sort", lambda: run_config(
        batch=32, remat="block_save_flash", prng_impl="rbg", moe_experts=8,
        moe_dispatch="sort", bench_steps=15,
    )))
    emit("moe_e16_top2_b32", _safe("moe_e16", lambda: run_config(
        batch=32, remat="block_save_flash", prng_impl="rbg", moe_experts=16,
        bench_steps=15,
    )))
    emit("moe_e16_top2_b32_sort", _safe("moe_e16_sort", lambda: run_config(
        batch=32, remat="block_save_flash", prng_impl="rbg", moe_experts=16,
        moe_dispatch="sort", bench_steps=15,
    )))

    result = {
        "metric": "tokens_per_sec",
        "value": ref["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": round(ref["tokens_per_sec"] / BASELINE_TOKENS_PER_SEC, 3),
    }
    print(json.dumps(result))
    # Decode (serving) rows: b8 kept for round-over-round continuity, the
    # batch sweep amortizes the weight read (Pope et al.'s lever — the
    # roofline says b64 costs ~1.5x b8 per step for 8x the tokens), the
    # xla row is the fused-kernel A/B oracle, and the p256 row is the
    # prompt-length leg (cache_len sensitivity: mean write frontier 320
    # vs the p32 row's 96 — 3.3x the KV read through the same kernel).
    emit("decode_b8", _safe("decode_b8", decode_bench))
    emit("decode_b8_xla", _safe("decode_b8_xla", lambda: decode_bench(
        decode_attention="xla")))
    emit("decode_b32", _safe("decode_b32", lambda: decode_bench(batch=32)))
    emit("decode_b64", _safe("decode_b64", lambda: decode_bench(batch=64)))
    emit("decode_b8_p256", _safe("decode_b8_p256", lambda: decode_bench(
        prompt_len=256, new_tokens=128)))
    # ISSUE 11 rows: the layer-fused megakernel (one launch per token —
    # the launch-count lever) and megakernel+int8 (the KV-bytes lever on
    # top; pct_of_roofline is computed against the int8 byte model, so
    # the two levers are separable in the table).
    emit("decode_b8_fused_layers", _safe("decode_b8_fused_layers",
         lambda: decode_bench(decode_attention="fused_layers")))
    emit("decode_b8_int8", _safe("decode_b8_int8", lambda: decode_bench(
        decode_attention="fused_layers", kv_cache_dtype="int8")))
    # ISSUE 19 rows: speculative decoding on the megakernel — scored on
    # ms per ACCEPTED token and tokens/launch (the A/B partner is
    # decode_b8_fused_layers' ms_per_token; a draft earns its keep when
    # ms_per_accepted_token comes in under it).
    emit("spec_b8_k2", _safe("spec_b8_k2",
         lambda: spec_decode_bench(spec_k=2)))
    emit("spec_b8_k4", _safe("spec_b8_k4",
         lambda: spec_decode_bench(spec_k=4)))
    # Serving-scheduler rows (ISSUE 6): Poisson arrivals through the
    # continuous-batching engine at calibrated offered loads, including
    # one past saturation — the row that shows shedding holds p99.
    serve_bench_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
    # int8-KV serving row (ISSUE 11): the closed-loop capacity shape on
    # the megakernel + int8 cache — see serve_int8_row.
    serve_int8_row(emit, serve_cfg_kw, seed=args.serve_seed)
    # Speculative serving row (ISSUE 19): closed-loop capacity with
    # serve.spec ON — A/B partner of serve_cal_closed_loop.
    serve_spec_row(emit, serve_cfg_kw, seed=args.serve_seed)
    # Multi-tenant LoRA rows (ISSUE 10): N tenants on one resident base;
    # the delta vs the serve_* rows is the per-token multi-tenant price.
    serve_lora_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
    # Fleet rows (ISSUE 13): tenant-aware router over 3 in-process
    # replicas — calibration, 0.9x/3x offered load, and the replica-kill
    # chaos leg (failover mid-traffic, zero silent drops).
    serve_fleet_rows(emit, seed=args.serve_seed, **serve_cfg_kw)
    # Resource-pool rows (ISSUE 17): the diurnal and combined-chaos
    # pool_smoke legs, each in a subprocess with its own 8-virtual-device
    # mesh — train tokens/s under arbitration next to fleet completions
    # and the transition/recompile counts.
    pool_diurnal_rows(emit)
    # Tracing substrate cost (ISSUE 7): host-side span-emission µs per
    # step, A/B traced vs untraced — PERF.md reads the % off this row.
    emit("trace_overhead", _safe("trace_overhead", trace_overhead_bench))
    # Device-time attribution (ISSUE 8): component breakdown + overlap%
    # for the b8 reference step, gated structurally (every dot attributed,
    # unattributed share bounded) with the census cross-check.
    emit("devprof_b8", _safe("devprof_b8", devprof_bench))
    # Overlapped-collectives A/B (ISSUE 12): the SAME fsdp config with
    # collectives xla vs overlapped — tokens/s plus the devprof
    # overlap_ratio (ROADMAP item 2's 0.0 -> >=0.5 headline). Needs a
    # multi-chip slice; on one chip both legs record the typed error.
    emit("fsdp_overlap_ab_xla", _safe("fsdp_overlap_ab_xla",
         lambda: fsdp_overlap_bench(collectives="xla")))
    emit("fsdp_overlap_ab_overlapped", _safe("fsdp_overlap_ab_overlapped",
         lambda: fsdp_overlap_bench(collectives="overlapped")))
    # Mixed-precision A/B (ISSUE 14): the SAME flagship dp step under
    # precision fp32 vs bf16_mixed — tokens/s + the analytic HBM budget
    # (params/masters/moments/grads). CPU legs are shape-only (bf16 is
    # emulated here); the TPU pair is the real speed number.
    emit("precision_ab_fp32", _safe("precision_ab_fp32",
         lambda: precision_ab_bench(precision="fp32")))
    emit("precision_ab_bf16", _safe("precision_ab_bf16",
         lambda: precision_ab_bench(precision="bf16_mixed")))
    emit("ring_block_smoke", _safe("ring_block_smoke", ring_block_smoke))

    # Assemble the detail line FROM the registry's event stream: each
    # bench_config event becomes one keyed entry, existing keys unchanged
    # (new per-config fields ride along: dispatch_s/blocked_s/peak_hbm_bytes).
    extra = {
        "devices": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
    }
    for ev in sink.events:
        if ev["etype"] != "bench_config":
            continue
        body = {k: v for k, v in ev.items() if k not in ("etype", "ts", "proc", "label")}
        extra[ev["label"]] = body
    extra["mfu"] = tuned["mfu"]  # honest per-chip utilization on the REFERENCE shape
    extra["mfu_hd128"] = hd128.get("mfu")  # None if the _safe config errored
    # Process-lifetime HBM peak (across ALL configs — per-config peaks are
    # not separable; per-config live working sets are hbm_bytes_in_use).
    from dtc_tpu.obs import peak_hbm_bytes, sample_memory

    extra["peak_hbm_bytes"] = peak_hbm_bytes(sample_memory())
    for flag in decode_drift_guard(extra):
        print(f"# DECODE REGRESSION: {flag}")
    print("# bench-detail:", json.dumps(extra))
    reg.close()
    return errored


if __name__ == "__main__":
    main()
