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
from tests.pattern_helpers import (  # noqa: F401  (cfg, lfm2_cfg are fixtures)
    LFM2, OURO, QWEN3, REPO, TOY_YAML, cfg, lfm2_cfg, one_device_steps, program_params, weights,
)


def _three_steps_from_yaml_files(tmp_path, model_yaml) -> list[dict]:
    """``main.py``'s path: YAML files through ``load_config`` into
    ``trainer.train``, three steps; the run's events."""
    import yaml

    from dtc_tpu.train.trainer import train

    with open(os.path.join(REPO, "configs", "train_config_dp.yaml")) as f:
        train_yaml = yaml.safe_load(f)
    train_yaml.update(output_dir=str(tmp_path / "run"), steps=3, log_every=3, batch=8,
                      dataset="synthetic", warmup_steps=1, overwrite=True)
    path = tmp_path / "train.yaml"
    path.write_text(yaml.safe_dump(train_yaml))
    train_cfg, model_cfg, opt_cfg = load_config(
        str(path), model_yaml, os.path.join(REPO, "configs", "optim_config.yaml"))
    result = train(train_cfg, model_cfg, opt_cfg)
    assert len(result.losses) == 3 and all(np.isfinite(result.losses))
    with open(tmp_path / "run" / "obs" / "events.r0.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def test_trainer_runs_the_lfm2_preset_from_yaml_files(tmp_path, lfm2_cfg):
    """The preset of the new kinds through the same path: the plans name
    the leading layer, the short convolution and the router's form, and the
    counters carry what the selection bias chose."""
    events = _three_steps_from_yaml_files(tmp_path, LFM2.yaml)
    by_type = {e["etype"]: e for e in events}
    plan = by_type["layer_plan"]
    assert plan["leading"] == list(lfm2_cfg.leading_pattern) and plan["periods"] == 1
    assert plan["shortconv"]["width"] == 3 and plan["attn"]["kv_heads"] == 1
    assert (by_type["moe_plan"]["score"], by_type["moe_plan"]["selection_bias"]) == ("sigmoid", True)
    counted = [e for e in events if e["etype"] == "moe_counters"]
    assert [e["step"] for e in counted] == [1, 2, 3]
    assert all(e["moe_dropped"] == 0 and len(e["moe_assigned_held"]) == 4
               and len(e["moe_bias_swapped"]) == 4 and e["moe_flushes"] == [1.0] * 4 for e in counted)
    assert not [e for e in events if e["etype"] == "recompile"]


def test_trainer_runs_the_ouro_preset_from_yaml_files(tmp_path):
    """The looped preset through the same path: the plan states the passes,
    the head passes a step and the norm placement; every step carries the
    passes' readings and no expert counters."""
    events = _three_steps_from_yaml_files(tmp_path, OURO.yaml)
    by_type = {e["etype"]: e for e in events}
    plan = by_type["layer_plan"]
    assert (plan["passes"], plan["norm_placement"], plan["periods"]) == (4, "sandwich", 3)
    assert plan["exit"]["beta"] == 0.1 and plan["attn"]["qk_norm"] is False
    assert "moe_plan" not in by_type and "moe_counters" not in by_type
    counted = [e for e in events if e["etype"] == "pass_counters"]
    assert [e["step"] for e in counted] == [1, 2, 3]
    assert all(len(e["exit_p"]) == len(e["pass_ce"]) == 4 and abs(sum(e["exit_p"]) - 1.0) < 1e-5
               and 0.0 < e["exit_entropy"] < np.log(4.0) for e in counted)
    assert not [e for e in events if e["etype"] == "recompile"]


def test_trainer_runs_three_steps_from_yaml_files(tmp_path, cfg):
    """``main.py``'s path: YAML files through ``load_config`` into
    ``trainer.train``; the events hold the two plans and the counters."""
    events = _three_steps_from_yaml_files(tmp_path, TOY_YAML)
    by_type = {e["etype"]: e for e in events}
    assert by_type["layer_plan"]["pattern"] == list(cfg.layer_pattern)
    assert by_type["layer_plan"]["gdn"]["chunks"] == 2 and by_type["layer_plan"]["gdn"]["chunk"] == 64
    assert by_type["layer_plan"]["gdn"]["kernel"] == "xla"  # key / value width 16: no lane tile
    assert by_type["moe_plan"]["experts_held"] == 4 and by_type["moe_plan"]["experts_published"] == 8
    assert by_type["moe_plan"]["staged_rows"] == 1024
    counted = [e for e in events if e["etype"] == "moe_counters"]
    assert [e["step"] for e in counted] == [1, 2, 3]
    assert all(e["moe_dropped"] == 0 and len(e["moe_assigned_held"]) == 4
               and e["moe_flushes"] == [1.0] * 4 and "moe_bias_swapped" not in e for e in counted)
    assert not [e for e in events if e["etype"] == "recompile"]


@pytest.mark.parametrize("family", [QWEN3, LFM2, OURO], ids=["qwen3", "lfm2", "ouro"])
@pytest.mark.parametrize("parallel", ["dp", "fsdp"])
def test_eight_devices_equal_one(opt_cfg, parallel, family):
    """The trainer on the virtual 8-device mesh (each device routes its own
    row's tokens into its own buffer) against the same state and step on
    one device, fed the same rows."""
    from dtc_tpu.train.trainer import train

    cfg = family.cfg()
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


def test_sharding_table_covers_the_lfm2_leaves(lfm2_cfg):
    """Every leaf of the new kinds has a row; a leading layer's leaves are
    not stacked and take the same rows less the scan's axis."""
    from dtc_tpu.parallel.sharding import FSDP_RULES, param_specs

    specs = param_specs(program_params(weights(lfm2_cfg, family=LFM2), LFM2), FSDP_RULES)
    lead, layer = specs["stage"]["leading"]["layer_0"], specs["stage"]["periods"]["layer_1"]
    assert tuple(lead["shortconv"]["in_proj"]["kernel"]) == ("data", "model")
    assert tuple(layer["shortconv"]["in_proj"]["kernel"]) == (None, "data", "model")
    assert tuple(lead["mlp"]["down_proj"]["kernel"]) == ("model", "data")
    assert tuple(lead["shortconv"]["conv"]) == (None, None) and tuple(lead["norm_1"]["scale"]) == ("data",)
    assert tuple(layer["moe"]["expert_bias"]) == (None, None)
    assert tuple(layer["moe"]["w_down"]) == (None, "model", None, "data")
    assert "lm_head" not in specs["head"]
