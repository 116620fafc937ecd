#!/usr/bin/env python
"""Elastic-training smoke — the tier-1 pre-gate for ISSUE 15's
shrink-and-continue layer.

Drives the real trainer through the flagship chaos drill on an 8-virtual-
device DP x FSDP CPU mesh: virtual host 0 is killed at step 6, heartbeat
detection fires, the run restores the last COMPLETE in-memory snapshot
(<= 1 step of lost work, ring-mirror sourced) onto a survivors-only
4-device mesh, re-seeks the row stream by tokens consumed, and finishes
the token budget. Asserts, in order:

- the BIT-EXACT gate: a shrunk restart (elastic.dead_hosts) resuming from
  the resize's cold spill replays the post-resize losses identically;
- the PARITY gate: the full chaos trajectory tracks an uninterrupted
  8-device run within the float-reassociation tolerance;
- typed events (host_lost / elastic_resize / elastic_spill / snapshot) —
  no silent restarts;
- exactly ONE recompile, at the first replayed step (the asserted cost of
  the mesh change), zero steady-state recompiles elsewhere.

~1-2 min on the 1-core CI host.

    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
      python scripts/elastic_smoke.py
"""

import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _events(output_dir: str) -> list[dict]:
    out = []
    for p in glob.glob(os.path.join(output_dir, "obs", "*.jsonl")):
        with open(p) as f:
            out += [json.loads(line) for line in f if line.strip()]
    return out


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from dtc_tpu.config.schema import (
        ChaosConfig,
        ElasticConfig,
        MeshConfig,
        ModelConfig,
        OptimConfig,
        ResilienceConfig,
        TrainConfig,
    )
    from dtc_tpu.train.trainer import train

    assert jax.device_count() == 8, (
        f"smoke needs 8 virtual CPU devices, got {jax.device_count()}"
    )
    model_cfg = ModelConfig(
        vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=32, dropout=0.0, param_dtype="float32",
        compute_dtype="float32", attention="dense",
    )
    opt_cfg = OptimConfig(lr=1e-3, weight_decay=0.1, grad_clip=1.0)
    root = tempfile.mkdtemp(prefix="elastic_smoke_")
    el = ElasticConfig(
        enabled=True, snapshot_every=1, keep=4, n_virtual_hosts=2
    )

    def cfg(name, *, resilience, resume=False, ckpt_dir=None):
        return TrainConfig(
            seed=0, parallel="fsdp", batch=8, steps=10, log_every=2,
            dataset="synthetic", warmup_steps=1, prefetch=0,
            mesh=MeshConfig(), overwrite=True, resume=resume,
            checkpoint_every=100,
            output_dir=os.path.join(root, name),
            checkpoint_dir=ckpt_dir or os.path.join(root, f"{name}_ckpt"),
            resilience=resilience,
        )

    try:
        # Leg 0: the uninterrupted parity reference (elastic on, no faults).
        clean = train(
            cfg("clean", resilience=ResilienceConfig(elastic=el)),
            model_cfg, opt_cfg,
        )

        # Leg 1: kill host 0 at step 6 -> detect -> restore -> shrink 8->4.
        chaos_cfg = cfg(
            "chaos",
            resilience=ResilienceConfig(
                elastic=el,
                chaos=ChaosConfig(
                    enabled=True, kill_host_at_step=6, elastic_target_host=0
                ),
            ),
        )
        chaotic = train(chaos_cfg, model_cfg, opt_cfg)
        assert len(chaotic.losses) == 10, "shrunk run must finish the budget"
        assert dict(chaotic.mesh.shape) == {"pipe": 1, "data": 4, "model": 1}
        np.testing.assert_array_equal(chaotic.losses[:5], clean.losses[:5])
        np.testing.assert_allclose(
            chaotic.losses[5:], clean.losses[5:], rtol=1e-3, atol=1e-5
        )
        print("elastic_smoke: parity gate OK (prefix exact, suffix rtol<=1e-3)")

        evs = _events(chaos_cfg.output_dir)
        lost = [e for e in evs if e["etype"] == "host_lost"]
        rz = [e for e in evs if e["etype"] == "elastic_resize"]
        assert len(lost) == 1 and lost[0]["host"] == 0, lost
        assert len(rz) == 1 and rz[0]["to_step"] == 5, (
            f"<= 1 step of lost work expected (kill at 6): {rz}"
        )
        assert rz[0]["tier"] == "memory" and rz[0]["used_mirror"] is True
        assert any(e["etype"] == "elastic_spill" for e in evs)
        assert any(e["etype"] == "snapshot" for e in evs)
        rc = [e for e in evs if e["etype"] == "recompile"]
        assert len(rc) == 1 and rc[0]["step"] == 6, (
            f"exactly one recompile, at the first replayed step: {rc}"
        )
        print("elastic_smoke: typed events + single asserted recompile OK")

        # Leg 2: BIT-EXACT gate — shrunk restart from the spilled cold
        # checkpoint replays the post-resize trajectory identically.
        replay_cfg = cfg(
            "replay",
            resilience=ResilienceConfig(
                elastic=ElasticConfig(
                    enabled=True, snapshot_every=1, keep=4,
                    n_virtual_hosts=2, dead_hosts=(0,),
                ),
            ),
            resume=True,
            ckpt_dir=chaos_cfg.checkpoint_dir,
        )
        replay = train(replay_cfg, model_cfg, opt_cfg)
        assert len(replay.losses) == 5, replay.losses
        np.testing.assert_array_equal(chaotic.losses[5:], replay.losses)
        assert not any(
            e["etype"] == "host_lost" for e in _events(replay_cfg.output_dir)
        ), "a host dead at startup must not be re-detected"
        print("elastic_smoke: bit-exact snapshot-replay gate OK")
        print("elastic_smoke: PASS")
        return 0
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
