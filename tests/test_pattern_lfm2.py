"""The LFM2-MoE kinds of a layer-pattern model (``models/pattern.py``): the
router's forms, the selection bias, the shares of the ``moe`` kind, the tied
head, the config rules, the plans and the counts at the benchmark cell's
configuration. What both families share — each layer kind against its
reference, the whole model through the program's step — is parametrised in
``test_pattern_model.py``; sizes and tolerances in ``tests/pattern_helpers.py``.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.models import pattern
from tests.pattern_helpers import (  # noqa: F401  (lfm2_cfg is a fixture)
    LFM2, REPO, TIGHT, as_model, cell_cfg, close, layer_of, lfm2_cfg, load_by_path, normed_input,
    program_params, weights,
)


def test_plans_name_what_an_lfm2_model_runs(lfm2_cfg):
    """``layer_plan`` / ``moe_plan`` at the benchmark cell's configuration and
    at the toy's: the leading layers, the short convolution's width and
    implementation, the attention kind's heads and tiles, the router's form."""
    cell, _ = cell_cfg("lfm2-8b-a1b")
    plan = pattern.layer_plan(cell)
    assert plan["leading"] == ["shortconv+swiglu"] and plan["periods"] == 1
    assert plan["pattern"] == ["attn+moe"] + ["shortconv+moe"] * 3
    assert plan["shortconv"] == {"width": 3, "channels": 2048, "implementation": "xla"}
    assert {k: plan["attn"][k] for k in ("heads", "kv_heads", "head_dim", "block_q", "block_kv",
                                         "rotary_dims")} == {
        "heads": 32, "kv_heads": 8, "head_dim": 64, "block_q": 1024, "block_kv": 1024, "rotary_dims": 64}
    assert plan["attn"]["kernel"] == "dense"  # off the chip: `auto` is flash on a TPU only
    assert "gdn" not in plan and "gated_attn" not in plan
    moe = pattern.moe_plan(cell, 4 * 8192)
    assert (moe["score"], moe["selection_bias"], moe["shared_width"]) == ("sigmoid", True, 0)
    assert (moe["experts_published"], moe["experts_held"], moe["top_k"]) == (32, 8, 4)
    assert moe["expected_held"] == 32768 and moe["tile_rows"] == 512
    assert moe["staged_rows"] == 41472  # a quarter over the expected, and a tile: 340 MB of float32 rows
    toy = pattern.moe_plan(lfm2_cfg, 128)
    assert toy["expected_held"] == 64 and toy["first_expert"] == 0


def test_moe_shares_add_up_to_the_uncut_layer(lfm2_cfg):
    """The routed parts of all four shares (4 x 2 experts) of the ``moe``
    kind equal the reference layer that holds all 8: there is no shared
    expert, so nothing is counted twice. Every share scores all 8 and
    chooses with the whole selection bias."""
    whole = dataclasses.replace(lfm2_cfg, moe_experts_held=0)
    w = weights(whole, family=LFM2)
    tree, flat = layer_of(w, 1, LFM2)
    x = normed_input(lfm2_cfg)
    with jax.default_matmul_precision("highest"):
        want = LFM2.ref.moe_layer(flat, x, as_model(whole))
    total = jnp.zeros_like(want)
    for rank in range(4):
        share = dataclasses.replace(lfm2_cfg, moe_experts_held=2, moe_expert_rank=rank)
        p = dict(tree["moe"])
        for leaf in ("w_gate", "w_up", "w_down"):
            p[leaf] = p[leaf][rank * 2: rank * 2 + 2]
        y, mut = pattern.FFNS["moe"][0](share).apply({"params": p}, x, mutable=["counters"])
        assert mut["counters"]["moe"][0].shape == (6,)
        total = total + y
    close(total, want, TIGHT)


# ---------------------------------------------------------------------------
# the router's forms


def test_top_k_gates_softmax_form_is_the_parent_s_bit_for_bit():
    """Without a bias, an epsilon or a scale ``top_k_gates`` is the
    arithmetic it was before it grew them, eagerly and compiled."""
    from dtc_tpu.ops.moe_dispatch import top_k_gates

    def parent(probs, k):
        top, idx = jax.lax.top_k(probs, k)
        return top / jnp.sum(top, axis=-1, keepdims=True), idx.astype(jnp.int32)

    probs = jax.nn.softmax(3.0 * jax.random.normal(jax.random.PRNGKey(0), (512, 64)), axis=-1)
    for k in (1, 2, 10):
        for fn in (lambda f: f, jax.jit):
            got = fn(lambda p: top_k_gates(p, k))(probs)
            want = fn(lambda p: parent(p, k))(probs)
            assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(got, want))
    assert str(jax.make_jaxpr(lambda p: top_k_gates(p, 2))(probs)) == str(
        jax.make_jaxpr(lambda p: parent(p, 2))(probs))


def test_sigmoid_form_chooses_with_the_bias_and_gates_without_it():
    from dtc_tpu.ops.moe_dispatch import bias_swapped, top_k_gates

    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.1], [0.2, 0.6, 0.5, 0.4]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.75])        # lifts expert 3 into the first row's choice
    gates, idx = top_k_gates(scores, 2, bias=bias, eps=1e-6, scale=2.0)
    assert np.asarray(idx).tolist() == [[0, 3], [3, 1]]
    np.testing.assert_allclose(gates[0], 2.0 * np.array([0.9, 0.1]) / (1.0 + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(gates[1], 2.0 * np.array([0.4, 0.6]) / (1.0 + 1e-6), rtol=1e-6)
    assert float(bias_swapped(scores, idx)) == 2.0    # expert 3 twice: plain top-2 takes neither
    assert float(bias_swapped(scores, top_k_gates(scores, 2)[1])) == 0.0
    grad = jax.grad(lambda b: jnp.sum(top_k_gates(scores, 2, bias=b, eps=1e-6)[0] ** 2))(bias)
    assert not np.any(np.asarray(grad))


def test_expert_bias_changes_the_choice_and_has_a_zero_gradient(lfm2_cfg):
    """The ``moe`` kind at the drawn bias against the same layer at a zero
    bias: other experts are chosen (the counter says how many choices the
    bias made), the output differs, and no gradient reaches the bias."""
    w = weights(lfm2_cfg, family=LFM2)
    tree, _ = layer_of(w, 1, LFM2)
    x = normed_input(lfm2_cfg)
    module = pattern.FFNS["moe"][0](lfm2_cfg)
    with_bias = tree["moe"]
    without = {**with_bias, "expert_bias": jnp.zeros_like(with_bias["expert_bias"])}
    y1, c1 = module.apply({"params": with_bias}, x, mutable=["counters"])
    y0, c0 = module.apply({"params": without}, x, mutable=["counters"])
    swapped1, swapped0 = (float(c["counters"]["moe"][0][5]) for c in (c1, c0))
    choices = x.shape[0] * x.shape[1] * lfm2_cfg.moe_top_k
    assert swapped0 == 0.0 and 0.02 * choices < swapped1 < 0.5 * choices
    assert float(jnp.max(jnp.abs(y1 - y0))) > 1e-3
    grads = jax.grad(lambda p: jnp.sum(module.apply({"params": p}, x) ** 2))(with_bias)
    assert not np.any(np.asarray(grads["expert_bias"]))
    assert np.any(np.asarray(grads["router"]["kernel"]))


def test_the_tied_leaf_gets_the_embedding_s_and_the_head_s_gradients(lfm2_cfg):
    """The same weights in an untied model whose head is the embedding
    transposed: the tied leaf's gradient is the sum of that model's two."""
    w = weights(lfm2_cfg, seed=7, family=LFM2)
    tied_params = program_params(w, LFM2)
    untied = dataclasses.replace(lfm2_cfg, tie_embeddings=False)
    wte = tied_params["embed"]["wte"]["embedding"]
    untied_params = {**tied_params, "head": {**tied_params["head"], "lm_head": wte.T}}
    batch = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, lfm2_cfg.max_seq_len + 1)), jnp.int32)

    def loss(cfg):
        model = pattern.build_model(cfg)
        return lambda p: model.apply({"params": p}, batch[:, :-1], train=False, targets=batch[:, 1:],
                                     mutable=["counters"])[0]

    assert "lm_head" not in jax.eval_shape(
        lambda: pattern.build_model(lfm2_cfg).init(jax.random.PRNGKey(0), batch[:, :-1], train=False)
    )["params"]["head"]
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lfm2_cfg))(tied_params)
        want = jax.grad(loss(untied))(untied_params)
    both = want["embed"]["wte"]["embedding"] + want["head"]["lm_head"].T
    close(got["embed"]["wte"]["embedding"], both, TIGHT)
    assert float(jnp.max(jnp.abs(want["head"]["lm_head"]))) > 0
    close(got["stage"]["leading"]["layer_0"]["mlp"]["up_proj"]["kernel"],
          want["stage"]["leading"]["layer_0"]["mlp"]["up_proj"]["kernel"], TIGHT)


@pytest.mark.parametrize("change,message", [
    ({"n_layers": 6}, "1 leading layer"),                       # leading + periods x 4
    ({"n_layers": 1}, "1 leading layer"),                       # no period at all
    ({"leading_pattern": ("shortconv+mlp",)}, "layer_pattern entry"),
    ({"layer_pattern": ("attn+moe_shared",) * 4}, "need moe_shared_d_ff"),
    ({"moe_d_ff": 0}, "need moe_experts and moe_d_ff"),
    ({"norm_gain": "centred"}, "unknown norm_gain"),
    ({"moe_score": "tanh"}, "unknown moe_score"),
    ({"shortconv_width": 0}, "shortconv_width"),
])
def test_config_rules_of_the_new_kinds(lfm2_cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(lfm2_cfg, **change)


def test_config_accepts_what_the_new_kinds_allow(lfm2_cfg):
    """``moe`` needs no shared width; two leading layers and two periods
    add up; a GPT-2 model can be neither tied nor led."""
    assert lfm2_cfg.moe_shared_d_ff == 0 and lfm2_cfg.pattern_periods == 1
    deeper = dataclasses.replace(lfm2_cfg, n_layers=10, leading_pattern=("shortconv+swiglu",) * 2)
    assert deeper.pattern_periods == 2
    assert [n for _, _, n in deeper.layer_census()] == [1, 1, 2, 2, 2, 2]
    for change in ({"tie_embeddings": True}, {"leading_pattern": ("shortconv+swiglu",)}):
        with pytest.raises(ValueError, match="belong to a layer-pattern model"):
            ModelConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=2, d_ff=64, max_seq_len=32,
                        **change)


def test_parameter_count_of_the_lfm2_cell_is_the_issue_s_sum():
    from dtc_tpu.models.gpt import param_count

    cfg, model = cell_cfg("lfm2-8b-a1b")
    assert cfg.padded_vocab_size == cfg.vocab_size == 16384
    assert param_count(cfg) == 507_820_288
    assert param_count(cfg) == (60_827_648 + 98_635_936 + 3 * 104_933_408 + 33_556_480)
    assert sum(int(np.prod(s)) for s in LFM2.ref.leaf_shapes(model).values()) == param_count(cfg)
    shapes = jax.eval_shape(lambda: pattern.build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), train=False))["params"]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == param_count(cfg)


def test_the_two_operation_counts_of_the_lfm2_cell_are_equal():
    from dtc_tpu.utils.metrics import pattern_step_flops

    flops = load_by_path(os.path.join(REPO, "benchmark", "flops_lfm2_moe.py"), "flops_lfm2_moe")
    cfg, model = cell_cfg("lfm2-8b-a1b")
    for rows, seq in ((4, 8192), (2, 8192), (8, 1024)):
        assert flops.train_step_flops(model, rows, seq) == pattern_step_flops(cfg, rows, seq)
    assert 42.4e12 < pattern_step_flops(cfg, 4, 8192) < 42.6e12
    # the ISSUE's split of a token's forward operations
    per = flops.matmul_params(model)
    assert 2 * (per["shortconv"] + per["swiglu"]) == 121_634_816
    assert 2 * (per["attn"] + 3 * per["shortconv"]) == 121_634_816
    assert 2 * 4 * (per["moe"] - 2048 * 32) == 88_080_384 and 2 * per["head"] == 67_108_864
    counted = flops.train_step_flops(model, 4, 8192, 4 * 32768.0 + 1000)
    np.testing.assert_allclose(counted - flops.train_step_flops(model, 4, 8192),
                               6 * 3 * 2048 * 1792 * 1000.0, rtol=1e-9)
