#!/usr/bin/env python
"""Fast serving-scheduler smoke — the tier-1 audit pre-gate's end-to-end
check that the continuous-batching runtime actually serves.

Runs the tiny audit model through the real engine: four requests (two
sharing a system-prompt prefix) admitted into two slots, driven to
completion, and every output asserted TOKEN-FOR-TOKEN identical to
``generate()`` on the same prompts — the scheduler must be a pure
reordering of the single-stream decode, never a numerics fork. Also
asserts the prefix store built exactly once with one hit, and that at
least one admission happened mid-flight (continuous batching, not
batch-at-once). ~30 s on the 1-core CI host.

    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
      python scripts/serve_smoke.py [--serve_config_path configs/serve_config.yaml]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--serve_config_path", default="",
        help="optional serve_config.yaml to exercise the loader path "
        "(slots/pages stay smoke-sized regardless)",
    )
    args = p.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.analysis.lowering import audit_model_cfg
    from dtc_tpu.config.schema import ServeConfig
    from dtc_tpu.generate import generate
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.serve import Request, RequestState, ServingEngine

    if args.serve_config_path:
        from dtc_tpu.config.loader import load_yaml_dataclass

        base = load_yaml_dataclass(args.serve_config_path, ServeConfig)
        # Smoke-size the compiled shapes; every policy knob rides along.
        import dataclasses

        scfg = dataclasses.replace(
            base, slots=2, page_size=4, queue_depth=8, max_new_tokens=6,
            prefill_bucket=8, deadline_s=0.0, verify_pages_every=1,
        )
    else:
        scfg = ServeConfig(slots=2, page_size=4, queue_depth=8,
                           max_new_tokens=6, prefill_bucket=8,
                           verify_pages_every=1)

    model_cfg = audit_model_cfg()
    model = GPT(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]

    rng = np.random.RandomState(7)
    prefix = rng.randint(0, model_cfg.vocab_size, size=6).tolist()
    prompts = [
        rng.randint(0, model_cfg.vocab_size, size=5).tolist(),
        prefix + rng.randint(0, model_cfg.vocab_size, size=3).tolist(),
        prefix + rng.randint(0, model_cfg.vocab_size, size=4).tolist(),
        rng.randint(0, model_cfg.vocab_size, size=8).tolist(),
    ]
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None], 6
        ))[0].tolist()
        for p in prompts
    ]

    eng = ServingEngine(model, params, scfg)
    for i, p in enumerate(prompts):
        eng.submit(Request(
            rid=f"r{i}", prompt=p, max_new_tokens=6,
            shared_prefix_len=len(prefix) if p[:len(prefix)] == prefix else 0,
        ))
    results = eng.run(max_steps=300)

    ok = True
    for i in range(len(prompts)):
        r = results[f"r{i}"]
        match = r.state is RequestState.DONE and r.tokens == refs[i]
        ok &= match
        print(f"[serve-smoke] r{i}: {r.state.value} tokens={r.tokens} "
              f"{'OK' if match else f'MISMATCH (want {refs[i]})'}")
    snap = eng.reg.snapshot()
    print(f"[serve-smoke] prefills={snap.get('serve_prefills')} "
          f"prefix_builds={snap.get('serve_prefix_builds')} "
          f"prefix_hits={snap.get('serve_prefix_hits')} "
          f"iterations={eng._it}")
    if snap.get("serve_prefix_builds") != 1 or snap.get("serve_prefix_hits", 0) < 1:
        print("[serve-smoke] FAIL: prefix store not shared as designed")
        ok = False
    if eng._it < 3:
        print("[serve-smoke] FAIL: everything ran in one shot — "
              "continuous batching never happened")
        ok = False
    print(f"[serve-smoke] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
