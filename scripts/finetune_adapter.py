#!/usr/bin/env python
"""LoRA finetune CLI: train an adapter on one frozen base, gate it on
held-out eval loss, export the artifact the serving engine loads.

    JAX_PLATFORMS=cpu python scripts/finetune_adapter.py \
        --finetune_config configs/finetune_lora.yaml --out adapter_t0.npz

The run is an ordinary trainer run (checkpoint/resume, guard rollback,
SIGTERM graceful stop, chaos drills all apply) whose TrainState is the
ADAPTER SUBTREE ONLY — see dtc_tpu/adapters/ and README "Multi-tenant
adapters". The eval gate refuses to export an adapter whose final
held-out eval loss is worse than ``gate_ratio``x its FIRST eval point
(taken eval_every steps in — keep eval_every small so that anchor stays
near the base loss the B-zero init starts from; see
adapters/finetune.py). Serve the export with
``ServingEngine.load_adapter(name, factors)`` against the SAME base
(model config + seed, or the base checkpoint this run started from).

Exit status: 0 = trained, gated, exported; 1 = gate failed (no export
unless --no-gate); 2 = config error.
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
        "--finetune_config", default="configs/finetune_lora.yaml",
        help="TrainConfig YAML with the extra adapter: block "
        "(configs/finetune_lora.yaml)",
    )
    p.add_argument(
        "--model_config", default="",
        help="model config (default: sibling model_config.yaml)",
    )
    p.add_argument(
        "--optim_config", default="",
        help="optimizer config (default: sibling optim_config.yaml)",
    )
    p.add_argument(
        "--out", default="adapter.npz",
        help="adapter artifact path (.npz: factors + JSON meta)",
    )
    p.add_argument(
        "--gate-ratio", type=float, default=1.0,
        help="export only if final eval loss <= ratio * first eval loss "
        "(default 1.0: must not be worse than the base)",
    )
    p.add_argument(
        "--no-gate", action="store_true",
        help="export even when the eval gate fails or eval is disabled "
        "(the outcome is still recorded in the artifact meta)",
    )
    args = p.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from dtc_tpu.adapters import finetune_adapter, save_adapter
    from dtc_tpu.config.loader import load_finetune_config

    try:
        train_cfg, model_cfg, opt_cfg = load_finetune_config(
            args.finetune_config, args.model_config or None,
            args.optim_config or None,
        )
    except (ValueError, TypeError, OSError) as e:
        print(f"[finetune] config error: {e}", file=sys.stderr)
        return 2
    if model_cfg.adapter.rank <= 0:
        print(
            "[finetune] config error: adapter.rank must be > 0 "
            f"(got {model_cfg.adapter.rank})", file=sys.stderr,
        )
        return 2
    if train_cfg.eval_every <= 0 and not args.no_gate:
        print(
            "[finetune] config error: the eval gate needs eval_every > 0 "
            "(or pass --no-gate to export ungated)", file=sys.stderr,
        )
        return 2

    outcome = finetune_adapter(
        train_cfg, model_cfg, opt_cfg, gate_ratio=args.gate_ratio
    )
    print(
        f"[finetune] eval gate: first={outcome.eval_first} "
        f"final={outcome.eval_final} ratio={args.gate_ratio} -> "
        f"{'PASS' if outcome.gate_passed else 'FAIL'}"
    )
    if not outcome.gate_passed and not args.no_gate:
        print(
            "[finetune] gate failed — adapter NOT exported (the finetune "
            "made held-out loss worse; tune lr/steps/rank, or --no-gate "
            "to export anyway)", file=sys.stderr,
        )
        return 1
    save_adapter(
        args.out, outcome.adapter, outcome.meta(model_cfg, train_cfg)
    )
    print(f"[finetune] adapter exported: {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
