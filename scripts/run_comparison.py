"""Produce the committed strategy-comparison artifact (reference headline).

The reference's thesis deliverable is its committed 5000-step
``outputs/{dp,tp,pp}/log.csv`` + ``loss.png`` + ``average_elapsed_time.png``
(`/root/reference/outputs/`, `/root/reference/README.md:44-49`). This script
produces the equivalent for this framework:

- ``outputs/{dp,tp,pp,3d}/log.csv`` — every strategy run to completion on
  the SAME 8-device mesh (virtual CPU devices when no 8-chip slice is
  attached) from identical seeds/batches, so the loss curves must overlap.
- ``outputs/tpu_dp/log.csv`` — the flagship GPT-89.6M reference workload on
  the real TPU chip.
- both PNGs via ``plot.py``.

Data is the deterministic synthetic stream (this environment has no
network egress for FineWeb streaming; the packing/tokenize path is
unit-tested separately). Run: ``python scripts/run_comparison.py [--steps N]``.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Small-but-real comparison model: big enough that the curves have shape,
# small enough that 4 strategies x N steps finish on 8 virtual CPU devices.
# n_heads=8 so TP can shard heads over model=8; n_layers=4 so auto-PP
# resolves to pipe=4 x data=2.
CPU_MODEL = dict(
    vocab_size=512, d_model=64, n_layers=4, n_heads=8, d_ff=256,
    max_seq_len=64, dropout=0.1, param_dtype="float32",
    compute_dtype="float32", attention="dense",
)

STRATEGIES = {
    "dp": dict(parallel="dp", pp_microbatches=1, mesh={}),
    "tp": dict(parallel="tp", pp_microbatches=1, mesh={}),
    "pp": dict(parallel="pp", pp_microbatches=4, mesh={}),
    "3d": dict(parallel="3d", pp_microbatches=4, mesh=dict(pipe=2, data=2, model=2)),
    "fsdp": dict(parallel="fsdp", pp_microbatches=1, mesh={}),
    # MoE/EP: E=8 experts sharded one-per-device over model=8 (Switch
    # top-2). A different model than the rows above — its loss curve is
    # NOT expected to overlap them; it demonstrates the EP training path
    # end-to-end at artifact scale.
    "moe": dict(
        parallel="tp", pp_microbatches=1, mesh={},
        model=dict(moe_experts=8, moe_top_k=2),
    ),
}


def run_cpu_strategy(name: str, steps: int) -> None:
    """One strategy to completion in a subprocess on 8 virtual CPU devices."""
    spec = STRATEGIES[name]
    model_kw = {**CPU_MODEL, **spec.get("model", {})}
    code = f"""
import jax
jax.config.update("jax_platforms", "cpu")
from dtc_tpu.config.schema import MeshConfig, ModelConfig, OptimConfig, TrainConfig
from dtc_tpu.train.trainer import train

model_cfg = ModelConfig(**{model_kw!r})
opt_cfg = OptimConfig(lr=3e-4, weight_decay=0.1, grad_clip=1.0)
train_cfg = TrainConfig(
    seed=0, parallel={spec['parallel']!r}, batch=8, steps={steps}, log_every=50,
    output_dir={os.path.join('outputs', name)!r},
    pp_microbatches={spec['pp_microbatches']}, mesh=MeshConfig(**{spec['mesh']!r}),
    dataset="synthetic", warmup_steps=5, prefetch=2, overwrite=True,
)
train(train_cfg, model_cfg, opt_cfg)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    print(f"=== {name}: {steps} steps on 8 virtual CPU devices ===", flush=True)
    subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, check=True)


def run_tpu_flagship(steps: int) -> None:
    """Flagship GPT-89.6M on the attached TPU chip, at the tuned round-4/5
    configuration (batch 32, ``remat="block_save_flash"``, fused head-CE,
    rbg dropout).
    Rows at log_every boundaries (and the final total) are device-synced
    times; intermediate rows are dispatch stamps (see sync_every_step
    below)."""
    code = f"""
from dtc_tpu.config.schema import MeshConfig, ModelConfig, OptimConfig, TrainConfig
from dtc_tpu.train.trainer import train

model_cfg = ModelConfig(
    vocab_size=50258, d_model=512, n_layers=12, n_heads=16, d_ff=2048,
    max_seq_len=512, dropout=0.1, param_dtype="float32",
    compute_dtype="bfloat16", attention="auto", remat="block_save_flash",
)
opt_cfg = OptimConfig(lr=3e-4, weight_decay=0.1, grad_clip=1.0)
train_cfg = TrainConfig(
    seed=0, parallel="dp", batch=32, steps={steps}, log_every=50,
    output_dir="outputs/tpu_dp", dataset="synthetic", warmup_steps=5,
    prefetch=2, prng_impl="rbg", overwrite=True,
    # No per-step device sync: the trainer still re-stamps every 50th
    # row (and the total) after a device sync; intermediate rows are
    # dispatch-stamped, as documented in README "Timing semantics".
    sync_every_step=False,
)
train(train_cfg, model_cfg, opt_cfg)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    print(f"=== tpu_dp: flagship {steps} steps on the real chip ===", flush=True)
    subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, check=True)


def run_tpu_longctx() -> None:
    """The committed ``outputs/longctx`` artifact: flagship at T=4096
    through ``main.py`` with the long-context configs (8x the reference's
    context cap; sweep-tuned flash tilings)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    print("=== longctx: flagship T=4096 on the real chip ===", flush=True)
    subprocess.run(
        [
            sys.executable, "main.py",
            "--train_config_path", "configs/train_config_longctx.yaml",
            "--model_config_path", "configs/model_config_longctx.yaml",
            "--dataset", "synthetic",
        ],
        env=env, cwd=REPO, check=True,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=2000, help="CPU-mesh steps per strategy")
    ap.add_argument("--tpu-steps", type=int, default=5000, help="flagship TPU steps")
    ap.add_argument("--only", choices=[*STRATEGIES, "tpu", "longctx", "plot"], default=None)
    args = ap.parse_args()

    if args.only in STRATEGIES:
        run_cpu_strategy(args.only, args.steps)
    elif args.only == "tpu":
        run_tpu_flagship(args.tpu_steps)
    elif args.only == "longctx":
        run_tpu_longctx()
    elif args.only == "plot":
        pass
    else:
        for name in STRATEGIES:
            run_cpu_strategy(name, args.steps)
        run_tpu_flagship(args.tpu_steps)
        run_tpu_longctx()

    sys.path.insert(0, REPO)
    import plot

    plot.main(os.path.join(REPO, "outputs"))


if __name__ == "__main__":
    main()
