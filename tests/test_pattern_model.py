"""Layer-pattern models (``models/pattern.py``) against the plain reference:
each layer kind, the whole model through the program's step, what a pattern
model refuses, the config rules and the counts. The operators are in
``test_pattern_ops.py``, the trainer and the mesh in
``test_pattern_parallel.py``; sizes and tolerances in ``tests/pattern_helpers.py``.
"""

import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.models import pattern
from tests.pattern_helpers import (  # noqa: F401  (cfg is a fixture)
    LFM2, LOOSE, QWEN3, REPO, TIGHT, as_model, cell_cfg, cfg, close, layer_of,
    load_by_path, normed_input, one_device_steps, ref, weights,
)


def test_plans_of_the_other_family_are_the_parent_s(cfg):
    moe = pattern.moe_plan(cfg, 256)
    assert (moe["score"], moe["selection_bias"], moe["shared_width"]) == ("softmax", False, 32)
    assert "leading" not in pattern.layer_plan(cfg) and "shortconv" not in pattern.layer_plan(cfg)
    # the staging: a quarter over the 256 of even routing in tiles of 512, and a tile; at the
    # benchmark cell's shape 1.25 x 10,240 and a tile = 109 MB of float32 rows
    assert (moe["expected_held"], moe["tile_rows"], moe["staged_rows"]) == (256, 512, 1024)
    cell = pattern.moe_plan(cell_cfg("qwen3-next-80b-a3b")[0], 2 * 8192)
    assert (cell["expected_held"], cell["staged_rows"]) == (10240, 13312)


@pytest.mark.parametrize("which", ["cell", "toy"])
def test_layer_plan_names_the_scan_implementation(cfg, which):
    """``layer_plan``'s ``gdn`` entry: at the benchmark cell's widths one
    Mosaic kernel a pass with the state carried in VMEM, its tile and
    ``vmem_limit_bytes``; at the toy's the ``jax.numpy`` form with XLA's
    scan carrying the state."""
    if which == "toy":
        toy = pattern.layer_plan(cfg)["gdn"]
        assert (toy["kernel"], toy["carry"]) == ("xla", "scan")
        assert "tile" not in toy and "vmem_limit_bytes" not in toy
        return
    cell, _ = cell_cfg("qwen3-next-80b-a3b")
    gdn = pattern.layer_plan(cell)["gdn"]
    assert (gdn["kernel"], gdn["carry"]) == ("mosaic", "vmem") and gdn["tile"] == [8, cell.gdn_chunk]
    assert set(gdn["vmem_limit_bytes"]) == {"fwd", "bwd"}
    assert all(16 * 2**20 <= v < 32 * 2**20 for v in gdn["vmem_limit_bytes"].values())


# ---------------------------------------------------------------------------
# each layer kind, forward and gradients


#: kind -> (family, the module's scope, where the layer is: position of the period, or leading)
_LAYERS = {
    "gdn": (QWEN3, "gdn", 0, False), "gated_attn": (QWEN3, "attn_full", 3, False),
    "moe_shared": (QWEN3, "moe", 0, False),
    "shortconv": (LFM2, "shortconv", 1, False), "attn": (LFM2, "attn_full", 0, False),
    "swiglu": (LFM2, "mlp", 0, True), "moe": (LFM2, "moe", 0, False),
}
_REF_LAYER = {"gdn": "gdn_layer", "gated_attn": "attn_layer", "moe_shared": "moe_layer",
              "shortconv": "shortconv_layer", "attn": "attn_layer", "swiglu": "swiglu_layer",
              "moe": "moe_layer"}


@pytest.mark.parametrize("kind,dtype,tol", [
    ("gdn", "float32", TIGHT), ("gdn", "bfloat16", LOOSE),
    ("gated_attn", "float32", TIGHT), ("gated_attn", "bfloat16", LOOSE),
    ("moe_shared", "float32", TIGHT),
    ("shortconv", "float32", TIGHT), ("shortconv", "bfloat16", LOOSE),
    ("attn", "float32", TIGHT), ("attn", "bfloat16", LOOSE),
    ("swiglu", "float32", TIGHT), ("swiglu", "bfloat16", LOOSE),
    ("moe", "float32", TIGHT),
])
def test_layer_kind_matches_reference(kind, dtype, tol):
    family, scope, position, leading = _LAYERS[kind]
    cfg = dataclasses.replace(family.cfg(), compute_dtype=dtype)
    ref_fn = getattr(family.ref, _REF_LAYER[kind])
    w = weights(cfg, family=family)
    tree, flat = layer_of(w, position, family, leading)
    x = normed_input(cfg)
    co = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    module = {**pattern.MIXERS, **pattern.FFNS}[kind][0](cfg, name=None)

    def program(p, x):
        return module.apply({"params": p}, x.astype(jnp.dtype(dtype))).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = ref_fn(flat, x, as_model(cfg))
        want_gp, want_gx = jax.grad(
            lambda p, x: jnp.sum(ref_fn(p, x, as_model(cfg)) * co), argnums=(0, 1))(flat, x)
    close(program(tree[scope], x), want, tol)
    got_gp, got_gx = jax.grad(lambda p, x: jnp.sum(program(p, x) * co), argnums=(0, 1))(tree[scope], x)
    close(got_gx, want_gx, tol)
    where = f"stage/leading/layer_{position}" if leading else f"stage/periods/layer_{position}"
    got_flat = {family.leaf_names[f"{where}/{scope}/" + "/".join(
        str(getattr(k, "key", k)) for k in path)].split(".", 2)[2]: leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(got_gp)}
    assert got_flat
    for name, g in got_flat.items():
        if name == "moe.bias":
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(want_gp[name]))
            continue
        close(g, want_gp[name], tol * (3 if dtype == "bfloat16" else 1))


# ---------------------------------------------------------------------------
# the share of the experts


def test_shares_add_up_to_the_uncut_layer(cfg):
    """The routed parts of both shares (2 x 4 experts) plus the shared
    expert once equal the reference layer that holds all 8."""
    whole = dataclasses.replace(cfg, moe_experts_held=0)
    w = weights(whole)
    _, flat = layer_of(w, 0)
    x = normed_input(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_layer(flat, x, as_model(whole))
    total = jnp.zeros_like(want)
    for rank in range(2):
        share = dataclasses.replace(cfg, moe_experts_held=4, moe_expert_rank=rank)
        tree, _ = layer_of(w, 0)
        p = dict(tree["moe"])
        for leaf in ("w_gate", "w_up", "w_down"):
            p[leaf] = p[leaf][rank * 4: rank * 4 + 4]
        y = pattern.FFNS["moe_shared"][0](share).apply({"params": p}, x)
        if rank:  # what every chip computes alike counts once
            zero_gate = {**flat, "moe.shared_gate.w": flat["moe.shared_gate.w"]}
            with jax.default_matmul_precision("highest"):
                shared = (ref.moe_layer(zero_gate, x, as_model(whole))
                          - ref.moe_layer(zero_gate, x, as_model(whole), shared=False))
            y = y - shared
        total = total + y
    close(total, want, TIGHT)


# ---------------------------------------------------------------------------
# the whole model through the program's step

@pytest.mark.parametrize("family", [QWEN3, LFM2], ids=["qwen3", "lfm2"])
def test_whole_model_loss_and_clipped_gradients(opt_cfg, family):
    """One step through ``create_train_step``: the loss, and the clipped
    gradient read back from AdamW's first moment (mu / (1 - b1)), as the
    benchmark's comparison reads it. ``lfm2``: with a leading layer before
    the scanned period, a tied head and six counters a layer."""
    cfg, ref = family.cfg(), family.ref
    w = weights(cfg, seed=5, family=family)
    batch = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, cfg.max_seq_len + 1), dtype=np.int32)
    state, (loss,), counters = one_device_steps(cfg, opt_cfg, [batch], w, family)
    assert list(counters) == ["moe"]
    counters = counters["moe"]
    assert counters.shape == (4, 6 if cfg.moe_selection_bias else 5) and float(counters[:, 3].sum()) == 0.0
    assert counters[:, 4].tolist() == [1.0] * 4  # one flush a layer
    optim = {"lr": opt_cfg.lr, "weight_decay": opt_cfg.weight_decay, "grad_clip": opt_cfg.grad_clip}
    out = ref.run_steps(as_model(cfg), optim, 5, [batch])
    assert abs(float(loss) - out["losses"][0]) <= 1e-4 * out["losses"][0]
    mu = next(s.mu for s in jax.tree.leaves(state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
              if hasattr(s, "mu"))
    flat = {family.leaf_names["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(mu)}
    assert set(flat) == set(out["grad1"])
    got = jax.device_get(ref.leaf_norms(flat))
    for name, want in out["grad1"].items():
        np.testing.assert_allclose(np.asarray(got[name]) / (1 - ref.B1), want, rtol=2e-3, atol=1e-7,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# what a pattern model refuses


def test_generate_refuses(cfg):
    from dtc_tpu.generate import generate

    with pytest.raises(NotImplementedError, match="no cache for recurrent state yet, nor a convolution's"):
        generate(pattern.build_model(cfg), {}, jnp.zeros((1, 4), jnp.int32), 4)


def test_serving_engine_refuses(cfg):
    from dtc_tpu.serve.engine import ServingEngine

    with pytest.raises(NotImplementedError, match="no cache for recurrent state yet, nor a convolution's"):
        ServingEngine(pattern.build_model(cfg), {}, None)

@pytest.mark.parametrize("change,message", [
    ({"layer_pattern": ("gdn+mlp",)}, "layer_pattern entry"),
    ({"n_layers": 6}, "whole number of periods"),
    ({"moe_experts_held": 3}, "must divide"),
    ({"max_seq_len": 72}, "multiple of the scan's chunk"),
])
def test_config_rules(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg, **change)


def test_the_schema_s_kinds_are_the_modules_kinds():
    """The schema lists the kinds' names (it knows no module); the model
    file maps each of them, and nothing else, to a module."""
    from dtc_tpu.config.schema import PATTERN_FFNS, PATTERN_MIXERS

    assert set(pattern.MIXERS) == set(PATTERN_MIXERS) and set(pattern.FFNS) == set(PATTERN_FFNS)


# ---------------------------------------------------------------------------
# the counts


def _published_cfg() -> tuple[ModelConfig, dict]:
    return cell_cfg("qwen3-next-80b-a3b")


def test_parameter_count_is_the_issue_s_sum():
    from dtc_tpu.models.gpt import param_count

    cfg, model = _published_cfg()
    padded = 2 * (cfg.padded_vocab_size - cfg.vocab_size) * cfg.d_model
    assert param_count(cfg) - padded == 625_667_136
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(model).values()) == param_count(cfg)


def test_the_two_operation_counts_are_equal():
    from dtc_tpu.utils.metrics import gdn_scan_flops, pattern_step_flops

    flops = load_by_path(os.path.join(REPO, "benchmark", "flops_qwen3_next.py"), "flops_qwen3_next")
    cfg, model = _published_cfg()
    for rows, seq in ((2, 8192), (8, 1024)):
        assert flops.train_step_flops(model, rows, seq) == pattern_step_flops(cfg, rows, seq)
    assert flops.gdn_step_flops(model, 2, 8192) == 3 * gdn_scan_flops(cfg, 2 * 8192)
    assert 22.5e12 < pattern_step_flops(cfg, 2, 8192) < 22.9e12


@pytest.mark.parametrize("held,steps", [([10240.0] * 4, None), ([9193.0, 9056.0, 22440.0, 3.0], None),
                                        ([0.0] * 4, [2])])
def test_the_benchmark_counts_the_assignments_a_run_computed(held, steps):
    """``mfu.train.qwen3-next`` and ``moe_experts_roofline.train`` take the
    experts' work from the run's ``moe_counters`` events: the expected count
    is what even routing gives, and a layer the routers left adds nothing."""
    flops = load_by_path(os.path.join(REPO, "benchmark", "flops_qwen3_next.py"), "flops_qwen3_next")
    _, model = _published_cfg()
    run = {"events": [{"etype": "moe_counters", "step": 1, "moe_assigned_held": [1.0] * 4},
                      {"etype": "moe_counters", "step": 2, "moe_assigned_held": held},
                      {"etype": "step", "step": 2}]}
    counted = flops.counted_assignments(run, steps or [2, 3])
    assert counted == sum(held)
    assert flops.counted_assignments({"events": []}) is None
    per = 3 * 2.0 * 2048 * 512
    assert flops.moe_experts_step_flops(model, 2, 8192, counted) == 3 * per * sum(held)
    expected = flops.moe_experts_step_flops(model, 2, 8192)
    assert expected == 3 * per * 4 * 10240
    step = flops.train_step_flops(model, 2, 8192, counted)
    np.testing.assert_allclose(step - flops.train_step_flops(model, 2, 8192),
                               3 * per * (sum(held) - 4 * 10240), rtol=1e-9, atol=1e6)
    if sum(held) == 4 * 10240:
        assert step == flops.train_step_flops(model, 2, 8192)


@pytest.mark.parametrize("flushes,want", [([[1.0] * 4, [1.0] * 4], 1.0), ([[1.0, 2.0, 1.0, 1.0], [1.0] * 4], 1.125),
                                          (None, None)])
def test_the_benchmark_reads_the_flushes_a_layer_ran(flushes, want):
    """``moe_flushes_per_layer.train``: the mean of the ``moe_counters``
    events' ``moe_flushes`` over layers and steps; a program that does not
    count them (the parent's) reads nothing and raises nothing. And the
    trainer's event carries what the step counted, a list a layer."""
    from dtc_tpu.train.trainer import _emit_counters

    reader = load_by_path(os.path.join(REPO, "benchmark", "metrics", "moe_flushes_per_layer.train.py"), "flushes")
    events = [{"etype": "step", "step": 1}]
    if flushes is None:
        events += [{"etype": "moe_counters", "step": 1, "moe_assigned_held": [10240.0] * 4}]
    else:
        emit = lambda etype, **fields: events.append({"etype": etype, **fields})  # noqa: E731
        tele = types.SimpleNamespace(registry=types.SimpleNamespace(emit=emit))
        counted = np.array([[[10240.0, 480.0, 320.0, 0.0, f] for f in row] for row in flushes])
        _emit_counters(tele, [1, 2], {"moe": counted})
        assert [e["moe_flushes"] for e in events[1:]] == flushes and events[1]["moe_dropped"] == 0.0
    assert reader.read({"events": events}) == want
