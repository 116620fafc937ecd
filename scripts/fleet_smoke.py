#!/usr/bin/env python
"""Serving-fleet smoke — the tier-1 pre-gate's end-to-end check that the
tenant-aware router actually runs a fleet (ISSUE 13).

Three in-process replicas of the tiny audit model with LoRA enabled, two
tenants (distinct factor trees registered with the router) plus base
requests, one shared system prompt — then a chaos replica-kill
mid-traffic. Asserts:

- zero silent drops: every accepted rid reaches a terminal fleet result
  (submits reconciled against results);
- survivor re-prefill token-identity: every COMPLETED request's tokens —
  including the failover hops' — are token-for-token ``generate()`` with
  the matching adapter (the scheduler+router are a pure reordering of
  single-stream decode, never a numerics fork);
- the kill actually exercised failover (>= 1 hop, 1 replica death) and
  tenant affinity actually routed (each tenant resident on exactly one
  LIVE replica before the kill).

~1-2 min on the 1-core CI host.

    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
      python scripts/fleet_smoke.py [--router_config_path configs/router_config.yaml]
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
        "--router_config_path", default="",
        help="optional router_config.yaml to exercise the loader path "
        "(replicas/slots stay smoke-sized regardless)",
    )
    args = p.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.adapters import init_lora
    from dtc_tpu.analysis.lowering import audit_model_cfg
    from dtc_tpu.config.schema import (
        AdapterConfig,
        ChaosConfig,
        RouterConfig,
        ServeConfig,
    )
    from dtc_tpu.generate import generate
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.serve import FleetRouter, ReplicaState, Request, RequestState

    serve = ServeConfig(
        slots=2, page_size=4, queue_depth=12, max_new_tokens=6,
        prefill_bucket=8, max_adapters=4,
    )
    # The kill targets replica 1 — tenant t1's affinity home (asserted
    # below) — so the failover leg also exercises the adapter-reload-on-
    # survivor path: a tenant request may never silently decode on
    # slot-0 base weights just because its factors' home died.
    chaos = ChaosConfig(
        enabled=True, fleet_kill_replica_at_step=6, fleet_target_replica=1,
    )
    if args.router_config_path:
        from dtc_tpu.config.loader import load_yaml_dataclass

        base = load_yaml_dataclass(args.router_config_path, RouterConfig)
        # Smoke-size the compiled shapes; every policy knob rides along.
        rcfg = dataclasses.replace(
            base, n_replicas=3, serve=serve, chaos=chaos,
        )
    else:
        rcfg = RouterConfig(n_replicas=3, serve=serve, chaos=chaos)

    model_cfg = audit_model_cfg(adapter=AdapterConfig(rank=4))
    model = GPT(model_cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    tenants = {"t1": init_lora(model, seed=1), "t2": init_lora(model, seed=2)}

    rng = np.random.RandomState(11)
    prefix = rng.randint(0, model_cfg.vocab_size, size=6).tolist()
    names = [None, "t1", "t2", None, "t1", "t2", None, "t1", "t2"]
    prompts = []
    for i in range(len(names)):
        body = rng.randint(0, model_cfg.vocab_size, size=4 + i % 3).tolist()
        prompts.append(prefix + body if i % 3 == 0 else body)
    refs = [
        np.asarray(generate(
            model, params, jnp.asarray(pr, jnp.int32)[None], 6,
            lora=tenants[nm] if nm else None,
        ))[0].tolist()
        for pr, nm in zip(prompts, names)
    ]

    router = FleetRouter(model, params, rcfg)
    for name, factors in tenants.items():
        router.register_adapter(name, factors)
    for i, (pr, nm) in enumerate(zip(prompts, names)):
        router.submit(Request(
            rid=f"r{i}", prompt=pr, max_new_tokens=6, adapter=nm,
            shared_prefix_len=len(prefix) if pr[:len(prefix)] == prefix else 0,
        ))
    # Tenant affinity check BEFORE the kill: each tenant resident on
    # exactly one replica (the router followed residency, it did not
    # spray factors fleet-wide).
    router.step()
    homes = {
        nm: [r.replica_id for r in router.replicas
             if nm in r.resident_adapters()]
        for nm in tenants
    }
    results = router.run(max_steps=400)
    summ = router.fleet_summary()

    ok = True
    for i in range(len(prompts)):
        r = results.get(f"r{i}")
        if r is None:
            print(f"[fleet-smoke] r{i}: SILENT DROP (no terminal result)")
            ok = False
            continue
        match = r.state is RequestState.DONE and r.tokens == refs[i]
        ok &= match
        print(f"[fleet-smoke] r{i}: {r.state.value} adapter={names[i]} "
              f"hops={r.n_hops} "
              f"{'OK' if match else f'MISMATCH (want {refs[i]}, got {r.tokens})'}")
    for nm, where in homes.items():
        print(f"[fleet-smoke] tenant {nm} resident on replicas {where}")
        if len(where) != 1:
            print(f"[fleet-smoke] FAIL: tenant affinity violated for {nm}")
            ok = False
    dead = [r for r in router.replicas if r.state is ReplicaState.DEAD]
    print(f"[fleet-smoke] deaths={summ['replica_deaths']} "
          f"failovers={summ['failovers']} routed={summ['routed']} "
          f"fleet_ttft_p99={summ['ttft_p99_s']}")
    if summ["replica_deaths"] != 1 or len(dead) != 1 or dead[0].replica_id != 1:
        print("[fleet-smoke] FAIL: chaos kill did not land on replica 1")
        ok = False
    if summ["failovers"] < 1:
        print("[fleet-smoke] FAIL: kill exercised no failover")
        ok = False
    # The kill took tenant t1's home with it; the token-identical hops
    # above therefore prove the router RE-LOADED the factors on a
    # survivor (base-weight decode would fork the tokens). Make the
    # residency move explicit too.
    if homes.get("t1") != [1]:
        print("[fleet-smoke] FAIL: t1's pre-kill home was not replica 1 "
              f"({homes.get('t1')}) — kill target no longer covers the "
              "adapter-reload path")
        ok = False
    t1_hops = [r for r in results.values()
               if r.adapter == "t1" and r.n_hops > 0]
    t1_alive = [r.replica_id for r in router.replicas
                if r.state is not ReplicaState.DEAD
                and "t1" in r.resident_adapters()]
    print(f"[fleet-smoke] t1 failover terminals={len(t1_hops)} "
          f"post-kill residency={t1_alive}")
    if not t1_hops or not t1_alive:
        print("[fleet-smoke] FAIL: tenant failover did not exercise the "
              "adapter-reload-on-survivor path")
        ok = False
    if len(results) != len(prompts):
        print("[fleet-smoke] FAIL: submits != terminal results "
              f"({len(prompts)} vs {len(results)})")
        ok = False
    router.close()
    print(f"[fleet-smoke] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
