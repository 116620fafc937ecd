#!/usr/bin/env python
"""Speculative-decoding end-to-end smoke — the tier-1 pre-gate for
ISSUE 19.

Bounded (< ~2 min on the 1-core CI host), five legs, all through the
REAL code paths:

1. **Draft extract** — a 3-of-4-layer rung sliced from the tiny audit
   checkpoint (shared embed/head by reference).
2. **spec_generate token-identity** — greedy speculation vs plain
   ``generate()`` on the same prompts, token for token, with
   ``accept_rate > 0`` asserted (a draft that never lands a proposal
   makes the whole launch-economy story vacuous).
3. **Serve token-identity** — four requests through the continuous-
   batching engine with ``serve.spec`` ON vs spec-off ``generate()``
   refs; per-request accept_rate observable and > 0 in aggregate.
4. **One-launch-per-verify census** (structural, any platform): the
   jitted speculative round under ``decode_attention: fused_layers``
   must lower with strictly fewer HLO while loops than the identical
   round under the per-layer ``fused`` backend — the verify's layer
   scan leaving HLO IS the single-launch megakernel claim (same
   baseline and census style as devprof_smoke's decode cross-check;
   the ``xla`` oracle is NOT a usable baseline on CPU because
   interpret-mode Pallas grids lower as while loops one-for-one with
   the layer scan they replace).
5. **Goodput honesty** — the spec serve run's obs shards reconcile
   (interval sums >= 99% of wall-clock, unattributed <= 5%) and every
   rejected-proposal second is billed to the TYPED
   ``spec_rejected_draft`` class, never productive_decode.

    JAX_PLATFORMS=cpu python scripts/spec_smoke.py
"""

import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SPEC_K = 2
DRAFT_LAYERS = 3


def main() -> int:
    import jax

    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.analysis.lowering import audit_model_cfg
    from dtc_tpu.config.schema import ServeConfig, SpecConfig
    from dtc_tpu.generate import generate, init_cache, decode_step
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.obs import Telemetry
    from dtc_tpu.obs.goodput import SPEC_REJECTED_DRAFT
    from dtc_tpu.serve import Request, RequestState, ServingEngine
    from dtc_tpu.spec import extract_draft, spec_generate
    from dtc_tpu.spec.core import _reindex, spec_round
    from scripts.goodput_report import load_ledger

    mcfg = audit_model_cfg(decode_attention="fused_layers")
    model = GPT(mcfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]

    # ---- leg 1: draft extraction ----
    dmodel, dparams = extract_draft(model, params, DRAFT_LAYERS)
    assert dmodel.cfg.n_layers == DRAFT_LAYERS
    print(f"[spec-smoke] draft: {DRAFT_LAYERS}-of-{mcfg.n_layers} layer rung")

    # ---- leg 2: spec_generate token-identity + acceptance ----
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, mcfg.vocab_size, size=n).tolist()
               for n in (5, 8, 6, 7)]
    max_new = 6
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None], max_new
        ))[0].tolist()
        for p in prompts
    ]
    ok = True
    proposed = accepted = launches = 0
    for i, p in enumerate(prompts):
        out, stats = spec_generate(
            model, params, dmodel, dparams,
            jnp.asarray(p, jnp.int32)[None], max_new,
            spec_k=SPEC_K, return_stats=True,
        )
        match = np.asarray(out)[0].tolist() == refs[i]
        ok &= match
        proposed += stats["proposed"]
        accepted += stats["accepted"]
        launches += stats["rounds"]
        if not match:
            print(f"[spec-smoke] FAIL generate parity p{i}: "
                  f"{np.asarray(out)[0].tolist()} != {refs[i]}")
    rate = accepted / max(proposed, 1)
    print(f"[spec-smoke] spec_generate: {len(prompts)} prompts "
          f"token-identical={ok} accept_rate={rate:.2f} "
          f"({accepted}/{proposed} over {launches} launches)")
    assert rate > 0.0, (
        "draft landed ZERO proposals — acceptance plumbing or draft "
        "extraction is broken (a 3-of-4 rung shares the target's head; "
        "some argmaxes must coincide)"
    )

    # ---- leg 3: serve token-identity with spec ON ----
    serve_dir = tempfile.mkdtemp(prefix="dtc_spec_smoke_")
    tele = Telemetry.for_serving(serve_dir)
    eng = ServingEngine(model, params, ServeConfig(
        slots=2, page_size=4, queue_depth=8, max_new_tokens=max_new,
        prefill_bucket=8,
        spec=SpecConfig(spec_k=SPEC_K, draft_layers=DRAFT_LAYERS),
    ), telemetry=tele)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=max_new))
    results = eng.run(max_steps=300)
    tele.flush()
    tele.close()
    srv_prop = srv_acc = 0
    for i in range(len(prompts)):
        r = results[f"r{i}"]
        match = r.state is RequestState.DONE and r.tokens == refs[i]
        ok &= match
        srv_prop += r.n_spec_proposed
        srv_acc += r.n_spec_accepted
        print(f"[spec-smoke] r{i}: {r.state.value} "
              f"accept_rate={r.accept_rate} "
              f"{'OK' if match else f'MISMATCH (want {refs[i]})'}")
    snap = eng.reg.snapshot()
    assert srv_prop > 0 and srv_acc > 0, (
        f"serve acceptance never fired: {srv_acc}/{srv_prop}"
    )
    assert snap["serve_spec_rounds"] >= 1
    print(f"[spec-smoke] serve: rounds={snap['serve_spec_rounds']} "
          f"accepted={snap['serve_spec_accepted']}"
          f"/{snap['serve_spec_proposed']}")

    # ---- leg 4: one-launch-per-verify while-census ----
    # Baseline is the PER-LAYER "fused" backend (kernel call inside the
    # layer scan), exactly as in devprof_smoke's decode cross-check:
    # fused_layers folds the layer loop into the kernel grid, so its
    # round must lower with strictly fewer while loops.  spec_round is
    # not backend-gated (only spec_generate/engine call
    # check_spec_backend), so lowering it under "fused" for the census
    # is legal even though serving with it is not.
    whiles = {}
    for backend in ("fused", "fused_layers"):
        bcfg = audit_model_cfg(decode_attention=backend)
        bmodel = GPT(bcfg)
        bdraft, bdparams = extract_draft(bmodel, params, DRAFT_LAYERS)
        b = 2
        tcache = init_cache(bmodel, b)
        dcache = init_cache(bdraft, b)
        prompt = jnp.zeros((b, 4), jnp.int32)
        tcache, _ = decode_step(bmodel, params, tcache, prompt)
        dcache, _ = decode_step(bdraft, bdparams, dcache, prompt)
        vec = jnp.full((b,), 4, jnp.int32)
        tcache, dcache = _reindex(tcache, vec), _reindex(dcache, vec)
        lowered = jax.jit(spec_round, static_argnums=(0, 1, 2)).lower(
            bmodel, bdraft, SPEC_K, params, bdparams, tcache, dcache,
            jnp.zeros((b, 1), jnp.int32), jnp.full((b,), 8, jnp.int32),
        )
        hlo = lowered.compile().as_text()
        whiles[backend] = len(re.findall(r"\bwhile\(", hlo))
    print(f"[spec-smoke] verify while-census: fused={whiles['fused']} "
          f"fused_layers={whiles['fused_layers']} "
          "(the verify's layer scan must leave HLO for the megakernel)")
    assert whiles["fused_layers"] < whiles["fused"], (
        f"fused_layers spec round kept as many while loops as the "
        f"per-layer fused baseline ({whiles}) — the k-verify did not "
        "collapse into one launch"
    )

    # ---- leg 5: goodput reconciliation + typed rejected-draft bill ----
    ledger = load_ledger(serve_dir)
    summary = ledger.summary()
    assert summary is not None, "spec serve run produced no ledger intervals"
    for proc, host in ledger.hosts.items():
        rec = host.reconcile()
        assert rec["fraction"] >= 0.99, (
            f"host {proc}: interval sums cover only "
            f"{rec['fraction']:.1%} of wall-clock {rec['wall_s']:.3f}s"
        )
        assert host.unattributed_pct <= 5.0, (
            f"host {proc}: unattributed {host.unattributed_pct:.1f}% > 5%"
        )
    fleet_s = summary["fleet"]["seconds"]
    rejected_s = fleet_s.get(SPEC_REJECTED_DRAFT, 0.0)
    # srv_acc < srv_prop means rejected work existed — it must be billed
    # typed, never folded into productive_decode.
    if srv_acc < srv_prop:
        assert rejected_s > 0.0, (
            f"{srv_prop - srv_acc} rejected proposals but zero "
            f"spec_rejected_draft seconds: {fleet_s}"
        )
    assert fleet_s.get("productive_decode", 0.0) > 0.0, fleet_s
    print(f"[spec-smoke] goodput: productive_decode="
          f"{fleet_s.get('productive_decode', 0.0):.4f}s "
          f"{SPEC_REJECTED_DRAFT}={rejected_s:.4f}s (typed)")

    print(f"[spec-smoke] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
