#!/usr/bin/env python
"""Resource-pool smoke — the tier-1 pre-gate for ISSUE 17's PoolManager.

Drives the diurnal arbitration story end-to-end on the 8-virtual-device
CPU pool (4 hosts x 2 devices): low serving traffic drains -> the pool
GROWS the trainer 4 -> 8 devices (retire-drain both replicas, admit the
freed hosts, resize the mesh up, restore the newest complete snapshot
with fresh NamedShardings) -> a traffic spike arrives while grown (the
requests PARK — typed backpressure, never a drop) -> the pool reclaims
capacity (shrink 8 -> 4, spawn replicas with ZERO compiles via the
engine fn cache) -> the parked spike drains -> the training budget
finishes. Asserts, in order:

- both transitions walked the full typed state machine to ``steady``
  (every edge emitted as a ``pool_transition`` event);
- ZERO SILENT DROPS: every submitted rid — including every request that
  parked during the zero-replica phase — reconciles to a typed terminal;
- LOSS PARITY: the arbitrated trajectory tracks an uninterrupted
  fixed-mesh run of the same budget (prefix before the first resize
  bit-exact, suffix within float-reassociation tolerance — the global
  batch never changed, only its sharding);
- EXACTLY ONE RECOMPILE PER MESH CHANGE: the step executable recompiles
  once after each resize and never elsewhere (snapshot-copy and resize
  aux compiles are separately attributed, not excused);
- the goodput ledger bills every transition to a typed
  ``elastic_resize`` incident and leaves <= 5% of the train shard's
  wall-clock unattributed.

``--chaos`` runs the combined-chaos leg instead: ``pool_spike_mid_grow``
lands a burst while the first grow is mid-walk (the grow aborts and
rolls back cleanly — replicas resume/respawn, the mesh was never
touched), and ``pool_kill_mid_shrink`` kills a host mid-surrender (the
ring-mirrored snapshot makes the surrender safe; the dead host is never
leased back to serving). Same acceptance gates, plus the abort/kill
events. ``--json`` appends a machine-readable ``# pool-smoke:`` line
(the bench's ``pool_diurnal`` row reads it).

~2-4 min on the 1-core CI host.

    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
      python scripts/pool_smoke.py
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")

VOCAB = 61
TRAIN_STEPS = 30
GLOBAL_BATCH = 8
LOW_TRAFFIC = 2
SPIKE_BURST = 8
NEW_TOKENS = 4


def _model():
    import jax
    import jax.numpy as jnp

    from dtc_tpu.config.schema import AdapterConfig, ModelConfig
    from dtc_tpu.models.gpt import GPT

    mcfg = ModelConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dropout=0.0, param_dtype="float32",
        compute_dtype="float32", attention="dense",
        adapter=AdapterConfig(rank=0),
    )
    model = GPT(mcfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    return model, params, mcfg


def _pool_cfg(*, chaos: bool):
    from dtc_tpu.config.schema import (
        ChaosConfig, PoolConfig, RouterConfig, ServeConfig,
    )

    serve = ServeConfig(
        slots=2, page_size=8, queue_depth=8, max_new_tokens=NEW_TOKENS,
        prefill_bucket=8,
    )
    ch = ChaosConfig()
    if chaos:
        # Fire-once, deferred to the matching in-flight transition: the
        # spike lands inside the FIRST grow (pre-resize -> clean abort),
        # the kill inside the first shrink's surrender of host 1.
        ch = ChaosConfig(
            enabled=True,
            pool_spike_mid_grow_at=1, pool_spike_requests=6,
            pool_kill_mid_shrink_at=1, elastic_target_host=1,
        )
    return PoolConfig(
        n_hosts=4, train_hosts=2, min_serve_hosts=0, min_train_hosts=1,
        global_batch=GLOBAL_BATCH, train_steps=TRAIN_STEPS,
        snapshot_every=1, snapshot_keep=4,
        grow_after_idle_ticks=1, spike_queue_depth=3,
        router=RouterConfig(n_replicas=2, serve=serve),
        chaos=ch,
    )


def _reference_losses(model, mcfg, cfg) -> list:
    """The parity oracle: the same budget, seed, and GLOBAL batch on the
    pool's baseline train mesh, uninterrupted — built from the same
    primitives the pool's train tenant uses."""
    import jax

    from dtc_tpu.config.schema import OptimConfig, TrainConfig
    from dtc_tpu.data.prefetch import split_put
    from dtc_tpu.data.synthetic import synthetic_row_batches
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES, batch_spec
    from dtc_tpu.train.train_step import Batch, create_train_step
    from dtc_tpu.train.trainer import init_state

    devices = jax.devices()[-2 * cfg.train_hosts:]
    mesh = build_mesh((1, len(devices), 1), devices=devices)
    tc = TrainConfig(seed=0, parallel="dp", batch=cfg.global_batch,
                     steps=cfg.train_steps, log_every=1_000_000,
                     output_dir="")
    oc = OptimConfig(lr=1e-2, weight_decay=0.0, grad_clip=1.0)
    state = init_state(model, mcfg, tc, oc, mesh)
    step_fn = create_train_step(mesh, model=model, state=state)
    data = synthetic_row_batches(
        cfg.global_batch, mcfg.max_seq_len + 1, VOCAB, seed=0, start_row=0,
    )
    spec = batch_spec(DEFAULT_RULES)
    key = jax.random.PRNGKey(0)
    losses = []
    for step in range(1, cfg.train_steps + 1):
        x, y = split_put(next(data), mesh, spec)
        with mesh:
            state, loss = step_fn(
                state, Batch(x=x, y=y), jax.random.fold_in(key, step),
            )
        losses.append(float(jax.block_until_ready(loss)))
    return losses


def _run_diurnal(model, params, mcfg, cfg, obs_dir):
    """Drive the pool: LOW_TRAFFIC up front, SPIKE_BURST the moment a
    grow reaches steady (zero replicas -> every burst request parks)."""
    from dtc_tpu.pool import PoolManager
    from dtc_tpu.serve.request import Request
    from dtc_tpu.utils.arrivals import arrival_schedule

    _, prompts = arrival_schedule(
        11, LOW_TRAFFIC + SPIKE_BURST, 6, VOCAB, None,
    )
    pm = PoolManager(model, params, mcfg, cfg, obs_dir=obs_dir, seed=0)
    t0 = time.perf_counter()
    for i in range(LOW_TRAFFIC):
        pm.submit(Request(
            rid=f"low{i}", prompt=prompts[i], max_new_tokens=NEW_TOKENS,
        ))
    spike_sent = False
    ticks = 0
    alive = True
    while alive and ticks < 600:
        alive = pm.tick()
        ticks += 1
        if not spike_sent and any(
            t.kind == "grow" and t.state == "steady" for t in pm.transitions
        ):
            for i in range(SPIKE_BURST):
                pm.submit(Request(
                    rid=f"burst{i}", prompt=prompts[LOW_TRAFFIC + i],
                    max_new_tokens=NEW_TOKENS,
                ))
            spike_sent = True
    wall = time.perf_counter() - t0
    results = pm.close()
    assert spike_sent, "no grow ever reached steady — the diurnal never ran"
    assert not alive, f"pool still in flight after {ticks} ticks"
    return pm, results, ticks, wall


def _events(obs_dir: str) -> list:
    out = []
    for p in glob.glob(os.path.join(obs_dir, "events.r*.jsonl")):
        with open(p) as f:
            out += [json.loads(line) for line in f if line.strip()]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chaos", action="store_true",
                    help="combined-chaos leg: pool_spike_mid_grow + "
                    "pool_kill_mid_shrink on the same run")
    ap.add_argument("--json", action="store_true",
                    help="append a machine-readable '# pool-smoke:' line")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    assert len(jax.devices()) == 8, (
        f"pool smoke needs 8 virtual devices, got {len(jax.devices())}"
    )
    model, params, mcfg = _model()
    cfg = _pool_cfg(chaos=args.chaos)
    obs_dir = tempfile.mkdtemp(prefix="dtc_pool_smoke_")
    try:
        print(f"pool_smoke: parity reference ({TRAIN_STEPS} steps, "
              f"fixed {2 * cfg.train_hosts}-device mesh)")
        ref = _reference_losses(model, mcfg, cfg)
        leg = "combined-chaos" if args.chaos else "diurnal"
        print(f"pool_smoke: {leg} leg")
        pm, results, ticks, wall = _run_diurnal(
            model, params, mcfg, cfg, obs_dir,
        )
        summ = pm.summary()

        # -- gate 1: the typed state machine walked both directions ----
        steady = [t for t in pm.transitions if t.state == "steady"]
        kinds = {t.kind for t in steady}
        assert {"grow", "shrink"} <= kinds, (
            f"expected a steady grow AND shrink, got {summ['transitions']}"
        )
        if args.chaos:
            aborted = [t for t in pm.transitions if t.state == "aborted"]
            assert aborted and aborted[0].kind == "grow", (
                "pool_spike_mid_grow must abort the first (pre-resize) grow"
            )
            killed = [t for t in pm.transitions if t.dead_hosts]
            assert killed and killed[0].kind == "shrink", (
                "pool_kill_mid_shrink must land inside a shrink"
            )
            assert cfg.chaos.elastic_target_host not in pm.serve_lease, (
                "a chaos-killed host must never be leased back to serving"
            )
        print(f"pool_smoke: transitions OK "
              f"({[t.kind + ':' + t.state for t in pm.transitions]})")

        # -- gate 2: zero silent drops ---------------------------------
        n_sub = LOW_TRAFFIC + SPIKE_BURST + (
            cfg.chaos.pool_spike_requests if args.chaos else 0
        )
        assert len(results) == n_sub, (
            f"{n_sub} submitted, {len(results)} terminal — silent drop"
        )
        by_state = {}
        for r in results.values():
            by_state[r.state.value] = by_state.get(r.state.value, 0) + 1
        assert all(
            r.state.value in ("done", "shed", "expired", "failed")
            for r in results.values()
        ), by_state
        print(f"pool_smoke: zero silent drops OK ({by_state})")

        # -- gate 3: loss parity vs the uninterrupted reference --------
        losses = pm.trainer.losses
        assert len(losses) == TRAIN_STEPS, (
            f"budget not finished: {len(losses)}/{TRAIN_STEPS} steps"
        )
        resizes = [e for e in _events(obs_dir)
                   if e.get("etype") == "elastic_resize"]
        first_rs = min(e["to_step"] for e in resizes)
        np.testing.assert_array_equal(losses[:first_rs], ref[:first_rs])
        np.testing.assert_allclose(
            losses[first_rs:], ref[first_rs:], rtol=1e-3, atol=1e-5,
        )
        print(f"pool_smoke: loss parity OK (prefix exact to step "
              f"{first_rs}, suffix rtol<=1e-3)")

        # -- gate 4: exactly one recompile per mesh change -------------
        n_resize = len(resizes)
        assert n_resize >= 2, f"expected >= 2 resizes, got {n_resize}"
        assert pm.trainer.recompiles == n_resize, (
            f"{pm.trainer.recompiles} recompiles for {n_resize} mesh "
            "changes — the one-recompile-per-resize contract broke"
        )
        print(f"pool_smoke: recompiles OK ({n_resize} resizes, "
              f"{pm.trainer.recompiles} recompiles)")

        # -- gate 5: goodput bills every transition, typed -------------
        from dtc_tpu.obs.goodput import GoodputLedger

        s = GoodputLedger.from_dir(obs_dir).summary()
        assert s is not None, "goodput ledger found no classifiable events"
        inc = [i for i in s["incidents"] if i["kind"] == "elastic_resize"]
        assert len(inc) == n_resize, (
            f"{n_resize} resizes but {len(inc)} elastic_resize incidents "
            "billed"
        )
        from dtc_tpu.pool import POOL_TRAIN_PROC

        hosts = s["hosts"]
        train_shard = hosts.get(POOL_TRAIN_PROC, hosts.get(str(POOL_TRAIN_PROC)))
        assert train_shard is not None, f"train shard missing: {list(hosts)}"
        unattr = train_shard.get("unattributed_pct", 0.0) or 0.0
        assert unattr <= 5.0, (
            f"train shard unattributed {unattr}% > 5% — a pool transition "
            "is burning wall-clock outside the typed taxonomy"
        )
        gp = s["fleet"]["goodput_pct"]
        print(f"pool_smoke: goodput OK ({len(inc)} incidents billed, "
              f"train unattributed {unattr:.1f}%, fleet goodput {gp}%)")

        done = [r for r in results.values() if r.state.value == "done"]
        tokens_out = sum(len(r.tokens) for r in done)
        seq = mcfg.max_seq_len
        row = {
            "chaos": bool(args.chaos),
            "ticks": ticks,
            "wall_s": round(wall, 3),
            "train_steps": TRAIN_STEPS,
            "final_loss": round(losses[-1], 4),
            "train_tokens_per_sec": round(
                TRAIN_STEPS * GLOBAL_BATCH * seq / wall, 1),
            "completed": len(done),
            "serve_tokens_out": tokens_out,
            "n_transitions": len(pm.transitions),
            "n_resizes": n_resize,
            "recompiles": pm.trainer.recompiles,
            "zero_silent_drops": True,
            "goodput_pct": gp,
            "unattributed_pct": round(unattr, 2),
            "platform": jax.devices()[0].platform,
            "serve_model": "tiny",
        }
        if args.json:
            print("# pool-smoke: " + json.dumps(row))
        print(f"pool_smoke: PASS ({leg}, {ticks} ticks, {wall:.1f}s)")
        return 0
    finally:
        shutil.rmtree(obs_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
