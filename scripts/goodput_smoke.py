#!/usr/bin/env python
"""Goodput ledger end-to-end smoke — the tier-1 pre-gate for ISSUE 16.

Bounded (< ~3 min on the 1-core CI host): a 6-step synthetic CPU
training run with a chaos NaN poison at step 3 (checkpoint at step 2, so
the anomaly guard rolls back and replays), plus a 2-request serving run
— both through the REAL trainer/engine, zero hand-built events. Then the
ledger leg:

- the goodput report renders (per-host table, incident bills, waterfall,
  token ledger) from the run's shards alone;
- per-host interval sums reconcile with wall-clock within 1% and
  ``unattributed`` stays under 5%;
- the rollback incident is present with t_detect/t_restored and a
  non-zero bill, and every badput second carries a typed cause;
- the shard reducer attaches a ``goodput`` section;
- the Perfetto export carries the ``goodput_pct`` counter track
  (ph "C") with the required Chrome-trace keys.

    JAX_PLATFORMS=cpu python scripts/goodput_smoke.py
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

    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.analysis.lowering import audit_model_cfg
    from dtc_tpu.config.schema import (
        ChaosConfig, MeshConfig, ModelConfig, ObsConfig, OptimConfig,
        ResilienceConfig, ServeConfig, TrainConfig,
    )
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.obs import Telemetry, reduce_shards
    from dtc_tpu.obs.goodput import TYPED_BADPUT, UNATTRIBUTED
    from dtc_tpu.obs.trace import to_chrome_trace
    from dtc_tpu.serve import Request, RequestState, ServingEngine
    from dtc_tpu.train.trainer import train
    from scripts.goodput_report import load_ledger, print_report
    from scripts.trace_report import load_events

    root = tempfile.mkdtemp(prefix="dtc_goodput_smoke_")

    # ---- leg 1: train run with a real chaos NaN -> rollback -> replay ----
    train_dir = os.path.join(root, "train")
    model_cfg = ModelConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=16, dropout=0.0, param_dtype="float32",
        compute_dtype="float32", attention="dense",
    )
    train(
        TrainConfig(
            seed=0, parallel="dp", batch=8, steps=6, log_every=1,
            output_dir=train_dir, dataset="synthetic", warmup_steps=1,
            prefetch=0, mesh=MeshConfig(), checkpoint_every=2,
            checkpoint_dir=os.path.join(root, "ckpt"),
            # counter_every=1: every gauge update also lands a Perfetto
            # counter row, so the 6-step run carries a visible track.
            obs=ObsConfig(goodput_counter_every=1),
            resilience=ResilienceConfig(
                chaos=ChaosConfig(enabled=True, nan_at_step=3),
            ),
        ),
        model_cfg,
        OptimConfig(lr=1e-3, weight_decay=0.0, grad_clip=1.0),
    )
    tev = load_events(train_dir)
    rbs = [e for e in tev if e.get("etype") == "recovery"
           and e.get("action") == "rollback"]
    assert rbs, "chaos NaN did not produce a rollback recovery event"
    assert "t_detect" in rbs[0] and "t_restored" in rbs[0], rbs[0]

    # ---- leg 2: 2-request serving run through the real engine ----
    serve_dir = os.path.join(root, "serve")
    scfg = ServeConfig(slots=2, page_size=4, queue_depth=4,
                       max_new_tokens=4, prefill_bucket=8)
    mcfg = audit_model_cfg()
    model = GPT(mcfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    tele = Telemetry.for_serving(serve_dir)
    eng = ServingEngine(model, params, scfg, telemetry=tele)
    rng = np.random.RandomState(0)
    for i in range(2):
        eng.submit(Request(
            rid=f"s{i}", prompt=rng.randint(0, mcfg.vocab_size, 6).tolist(),
            max_new_tokens=4,
        ))
    res = eng.run(max_steps=100)
    tele.flush()
    tele.close()
    assert all(res[f"s{i}"].state is RequestState.DONE for i in range(2)), res

    # ---- leg 3: ledger reconciliation + report render on both runs ----
    for label, run_dir in (("train", train_dir), ("serve", serve_dir)):
        ledger = load_ledger(run_dir)
        summary = ledger.summary()
        assert summary is not None, f"{label}: ledger found no intervals"
        for proc, host in ledger.hosts.items():
            rec = host.reconcile()
            assert rec["fraction"] >= 0.99, (
                f"{label} host {proc}: interval sums cover only "
                f"{rec['fraction']:.1%} of wall-clock {rec['wall_s']:.3f}s"
            )
            assert host.unattributed_pct <= 5.0, (
                f"{label} host {proc}: unattributed "
                f"{host.unattributed_pct:.1f}% > 5%"
            )
            for iv in host.intervals:
                if iv.klass in TYPED_BADPUT:
                    assert iv.cause, f"{label}: untyped badput {iv}"
                assert iv.klass != UNATTRIBUTED or iv.cause, iv
        print(f"# {label}: goodput report")
        print_report(summary)

    tl = load_ledger(train_dir)
    ts = tl.summary()
    bills = [i for i in ts["incidents"] if i["kind"] == "rollback"]
    assert bills, f"no rollback incident bill: {ts['incidents']}"
    bill = bills[0]
    assert bill["wall_s"] > 0 and bill["t_detect"] is not None, bill
    assert bill["tokens_badput"] > 0, bill  # the discarded step's tokens
    assert ts["fleet"]["seconds"].get("rollback_replay", 0) > 0, ts["fleet"]
    assert ts["tokens"]["effective_train_tokens"] == 6 * 8 * 16, ts["tokens"]

    # ---- leg 4: reducer section + Perfetto counter-track schema ----
    red = reduce_shards(os.path.join(train_dir, "obs"))
    assert red and "goodput" in red, "reducer dropped the goodput section"
    assert red["goodput"]["fleet"]["goodput_pct"] is not None

    trace = to_chrome_trace(tev)
    counters = [e for e in trace["traceEvents"]
                if e.get("ph") == "C" and e.get("name") == "goodput_pct"]
    assert counters, "no goodput_pct counter track in the Perfetto export"
    for e in counters:
        for k in ("ph", "ts", "dur", "pid", "tid", "name", "args"):
            assert k in e, f"counter row missing {k}: {e}"
        assert isinstance(e["args"]["goodput_pct"], float), e
    print(f"# perfetto: {len(counters)} goodput_pct counter samples")

    print("GOODPUT SMOKE PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
