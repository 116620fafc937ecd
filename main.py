"""CLI entry point.

Flag-compatible with the reference (`/root/reference/main.py:10-12`):
``python main.py --train_config_path configs/train_config_dp.yaml``.
Unlike the reference's two-way dispatch (`main.py:38-57`), every strategy —
dp, tp, pp, and the new combined 3d — routes into the ONE trainer; strategy
is mesh shape.
"""

from __future__ import annotations

from dataclasses import replace

import click

from dtc_tpu.config.loader import load_config
from dtc_tpu.train.trainer import train


@click.command()
@click.option("--train_config_path", default="configs/train_config_dp.yaml")
@click.option("--model_config_path", default=None)
@click.option("--optim_config_path", default=None)
@click.option("--steps", type=int, default=None, help="override train steps (smoke runs)")
@click.option(
    "--dataset", default=None, type=click.Choice(["fineweb", "synthetic"]),
    help="override dataset",
)
@click.option(
    "--obs/--no-obs", "obs", default=None,
    help="force the telemetry subsystem on/off (default: ObsConfig from YAML)",
)
def main(
    train_config_path: str,
    model_config_path: str | None,
    optim_config_path: str | None,
    steps: int | None,
    dataset: str | None,
    obs: bool | None,
):
    from dtc_tpu.utils.dist import (
        configure_compile_cache, maybe_initialize_distributed,
    )

    configure_compile_cache()
    train_cfg, model_cfg, opt_cfg = load_config(
        train_config_path, model_config_path, optim_config_path
    )
    if steps is not None:
        train_cfg = replace(train_cfg, steps=steps)
    if dataset is not None:
        train_cfg = replace(train_cfg, dataset=dataset)
    if obs is not None:
        train_cfg = replace(train_cfg, obs=replace(train_cfg.obs, enabled=obs))

    # Multi-host init FIRST: jax.distributed.initialize() must run before
    # any backend-touching JAX API (including jax.device_count below).
    maybe_initialize_distributed(
        train_cfg.multihost, train_cfg.coordinator_timeout_s
    )

    if train_cfg.dataset == "fineweb":
        # vocab_size comes from the tokenizer, as in /root/reference/main.py:17-18.
        from dtc_tpu.data.tokenizer import get_tokenizer

        model_cfg = replace(model_cfg, vocab_size=len(get_tokenizer()))

    import jax

    print(f"Running `{train_cfg.parallel}` on {jax.device_count()} devices.")
    train(train_cfg, model_cfg, opt_cfg)
    if train_cfg.obs.enabled and train_cfg.obs.jsonl and train_cfg.output_dir:
        import os

        obs_dir = train_cfg.obs.dir or os.path.join(train_cfg.output_dir, "obs")
        print(f"Telemetry: {obs_dir}/events.r*.jsonl + summary.json")


if __name__ == "__main__":
    main()
