"""A layer-pattern model through the trainer and across the 8-device mesh.
Sizes and tolerances: ``tests/pattern_helpers.py``.
"""

import json
import os

import jax
import numpy as np
import pytest

from dtc_tpu.config.loader import load_config
from dtc_tpu.models import pattern
from tests.conftest import make_train_cfg
from tests.pattern_helpers import (  # noqa: F401  (cfg is a fixture)
    REPO, TOY_YAML, cfg, one_device_steps, program_params, weights,
)


def test_trainer_runs_three_steps_from_yaml_files(tmp_path, cfg):
    """``main.py``'s path: YAML files through ``load_config`` into
    ``trainer.train``; the events hold the two plans and the counters."""
    import yaml

    from dtc_tpu.train.trainer import train

    with open(os.path.join(REPO, "configs", "train_config_dp.yaml")) as f:
        train_yaml = yaml.safe_load(f)
    train_yaml.update(output_dir=str(tmp_path / "run"), steps=3, log_every=3, batch=8,
                      dataset="synthetic", warmup_steps=1, overwrite=True)
    path = tmp_path / "train.yaml"
    path.write_text(yaml.safe_dump(train_yaml))
    train_cfg, model_cfg, opt_cfg = load_config(
        str(path), TOY_YAML, os.path.join(REPO, "configs", "optim_config.yaml"))
    result = train(train_cfg, model_cfg, opt_cfg)
    assert len(result.losses) == 3 and all(np.isfinite(result.losses))
    with open(tmp_path / "run" / "obs" / "events.r0.jsonl") as f:
        events = [json.loads(line) for line in f if line.strip()]
    by_type = {e["etype"]: e for e in events}
    assert by_type["layer_plan"]["pattern"] == list(cfg.layer_pattern)
    assert by_type["layer_plan"]["gdn"]["chunks"] == 2 and by_type["layer_plan"]["gdn"]["chunk"] == 64
    assert by_type["layer_plan"]["gdn"]["kernel"] == "xla"  # key / value width 16: no lane tile
    assert by_type["moe_plan"]["experts_held"] == 4 and by_type["moe_plan"]["experts_published"] == 8
    counted = [e for e in events if e["etype"] == "moe_counters"]
    assert [e["step"] for e in counted] == [1, 2, 3]
    assert all(e["moe_dropped"] == 0 and len(e["moe_assigned_held"]) == 4 for e in counted)
    assert not [e for e in events if e["etype"] == "recompile"]


@pytest.mark.parametrize("parallel", ["dp", "fsdp"])
def test_eight_devices_equal_one(cfg, opt_cfg, parallel):
    """The trainer on the virtual 8-device mesh (each device routes its own
    row's tokens into its own buffer) against the same state and step on
    one device, fed the same rows."""
    from dtc_tpu.train.trainer import train

    rng = np.random.default_rng(1)
    batches = [rng.integers(0, cfg.vocab_size, (8, cfg.max_seq_len + 1), dtype=np.int32)
               for _ in range(3)]
    _, one, _ = one_device_steps(cfg, opt_cfg, batches)
    many = train(make_train_cfg(parallel, steps=3, log_every=3), cfg, opt_cfg,
                 host_iterator=iter(batches))
    np.testing.assert_allclose(many.losses, one, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(2, 1, 1), (1, 1, 2)])  # pipe > 1; an axis over experts
def test_pipeline_and_expert_axes_refuse(cfg, shape):
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.train.train_step import create_train_step

    mesh = build_mesh(shape, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="dp and fsdp only"):
        create_train_step(mesh, model=pattern.build_model(cfg))


def test_sharding_table_covers_every_leaf(cfg):
    from dtc_tpu.parallel.sharding import FSDP_RULES, param_specs

    w = weights(cfg)
    specs = param_specs(program_params(w), FSDP_RULES)
    layer = specs["stage"]["periods"]["layer_0"]
    assert tuple(layer["moe"]["w_gate"]) == (None, "model", "data", None)
    assert tuple(layer["gdn"]["in_proj_qkvz"]["kernel"]) == (None, "data", "model")
