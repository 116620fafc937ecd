#!/usr/bin/env python
"""Adapter-loop smoke — the tier-1 pre-gate's end-to-end check that the
finetune -> load -> multi-tenant-serve loop actually closes.

Two LoRA adapters are finetuned (3 steps each, different learning rates,
SAME seed => same frozen base) through the REAL trainer on the offline
synthetic stream, loaded into one serving engine over the shared base
via the adapter-artifact round-trip (save_adapter -> load_adapter_file),
and then two tenant requests plus one base request are co-scheduled in
one in-flight batch. Every output is asserted TOKEN-FOR-TOKEN identical
to solo ``generate()`` with the matching adapter — multi-tenant batching
must be a pure reordering of per-tenant decode, never a numerics fork.
Also asserts the two adapters actually diverged (different lrs) and that
no steady-state recompile happened across the mixed-tenant admissions.
~1-2 min on the 1-core CI host.

    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
      python scripts/adapter_smoke.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    import jax

    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.adapters import load_adapter_file, save_adapter
    from dtc_tpu.analysis.lowering import audit_model_cfg, audit_opt_cfg
    from dtc_tpu.config.schema import AdapterConfig, ServeConfig, TrainConfig
    from dtc_tpu.generate import generate
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.obs.stepclock import CompileWatcher
    from dtc_tpu.serve import Request, RequestState, ServingEngine
    from dtc_tpu.train.trainer import train

    model_cfg = audit_model_cfg(adapter=AdapterConfig(rank=4, alpha=8.0))
    model = GPT(model_cfg)

    def finetune(lr_scale: float):
        # 3 steps on the offline synthetic stream through the REAL
        # trainer: the TrainState (and anything it checkpoints) is the
        # adapter subtree only. Same seed both runs => bit-identical
        # frozen base; different lr => different adapters.
        tc = TrainConfig(
            seed=0, parallel="dp", batch=8, steps=3, log_every=1,
            output_dir="", dataset="synthetic", warmup_steps=0, prefetch=0,
        )
        oc = dataclasses.replace(audit_opt_cfg(), lr=1e-3 * lr_scale)
        return train(tc, model_cfg, oc)

    r1 = finetune(1.0)
    r2 = finetune(4.0)
    base = r1.base_params
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(r2.base_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "same-seed finetunes diverged in their FROZEN base"
    diverged = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(r1.state.params), jax.tree.leaves(r2.state.params)
        )
    )

    # Artifact round-trip: what the engine loads is the exported file.
    with tempfile.TemporaryDirectory(prefix="dtc_adapter_smoke_") as td:
        adapters = {}
        for name, res in (("t1", r1), ("t2", r2)):
            path = os.path.join(td, f"{name}.npz")
            save_adapter(path, res.state.params, {"name": name})
            adapters[name], _meta = load_adapter_file(
                path, like=res.state.params
            )

    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, model_cfg.vocab_size, size=n).tolist()
               for n in (5, 7, 6)]
    refs = [
        np.asarray(generate(
            model, base, jnp.asarray(prompts[0], jnp.int32)[None], 6,
            lora=adapters["t1"],
        ))[0].tolist(),
        np.asarray(generate(
            model, base, jnp.asarray(prompts[1], jnp.int32)[None], 6,
            lora=adapters["t2"],
        ))[0].tolist(),
        np.asarray(generate(
            model, base, jnp.asarray(prompts[2], jnp.int32)[None], 6,
        ))[0].tolist(),
    ]

    eng = ServingEngine(model, base, ServeConfig(
        slots=3, page_size=4, queue_depth=8, max_new_tokens=6,
        prefill_bucket=8, max_adapters=4,
    ))
    eng.load_adapter("t1", adapters["t1"])
    eng.load_adapter("t2", adapters["t2"])
    # NO warmup admissions (ISSUE 11 satellite — the PR 9 two-admission
    # workaround is dead): the engine auto-warms at CONSTRUCTION when
    # the base params are GSPMD-sharded (trainer-produced), settling the
    # cache sharding before any insert compiles. The watcher therefore
    # measures the honest lifecycle: window 1 (the first mixed-tenant
    # batch) pays each compiled surface's ONE cold compile; window 2 (an
    # identical second batch — same prompt buckets, same tenants) must
    # be recompile-free. Without the construction settle, window 2's
    # admissions would recompile insert_fn against the post-decode
    # settled cache layout and fail the steady==0 assert below.
    tenants = ("t1", "t2", None)
    w = CompileWatcher().activate()
    try:
        w.drain()
        for i in range(3):
            eng.submit(Request(rid=f"r{i}", prompt=prompts[i],
                               max_new_tokens=6, adapter=tenants[i]))
        res = eng.run(max_steps=200)
        _, cold = w.drain()
        for i in range(3):
            eng.submit(Request(rid=f"s{i}", prompt=prompts[i],
                               max_new_tokens=6, adapter=tenants[i]))
        res = eng.run(max_steps=200)
        _, steady = w.drain()
    finally:
        w.deactivate()

    ok = True
    for i in range(3):
        for batch_rid in (f"r{i}", f"s{i}"):
            r = res[batch_rid]
            match = r.state is RequestState.DONE and r.tokens == refs[i]
            ok &= match
            print(f"[adapter-smoke] {batch_rid} (adapter={r.adapter}): "
                  f"{r.state.value} tokens={r.tokens} "
                  f"{'OK' if match else f'MISMATCH (want {refs[i]})'}")
    print(f"[adapter-smoke] cold compiles (batch 1): {cold}")
    if not diverged:
        print("[adapter-smoke] FAIL: the two finetunes produced identical "
              "adapters — training never moved the lora subtree")
        ok = False
    if steady != 0:
        print(f"[adapter-smoke] FAIL: {steady} steady-state recompile(s) "
              "across mixed-tenant admissions (batch 2 after an identical "
              "batch 1 — the construction-time cache-sharding settle is "
              "broken if this fires)")
        ok = False
    snap = eng.reg.snapshot()
    print(f"[adapter-smoke] adapter_loads={snap.get('adapter_loads')} "
          f"tenant_hists="
          f"{sorted(k for k in snap if k.startswith('serve_ttft_s.'))}")
    if snap.get("adapter_loads") != 2:
        print("[adapter-smoke] FAIL: expected 2 adapter loads")
        ok = False
    print(f"[adapter-smoke] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
