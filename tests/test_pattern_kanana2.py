"""The Kanana-2 (DeepSeek-V3) kinds of a layer-pattern model
(``models/pattern.py``): multi-head latent attention (``mla``) through its
flash kernel pair and through the dense path, interleaved rotary pairs, the
expert layer beside an ungated shared expert (``moe_shared_ungated``), the
whole model through the program's step against ``benchmark/reference_kanana2.py``,
the share of the experts, the planted faults the comparison must catch, the
config rules, the plans and the counts at the benchmark cell's configuration.
Each kind against its reference layer is parametrised in
``test_pattern_model.py``; sizes and tolerances in ``tests/pattern_helpers.py``.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.models import pattern
from tests.pattern_helpers import (  # noqa: F401  (kanana2_cfg is a fixture)
    KANANA2, REPO, TIGHT, as_model, cell_cfg, close, kanana2_cfg, layer_of, load_by_path,
    normed_input, one_device_steps, program_params, weights,
)

ref = KANANA2.ref
CELL = "kanana-2-30b-a3b"


# ---------------------------------------------------------------------------
# interleaved rotary pairs


def test_interleaved_rotary_is_the_pair_rotation():
    """``rotary(..., "interleaved")`` turns (x_2i, x_2i+1) by t theta^(-2i/d)
    as the reference writes it, keeps the lanes past the rotated fraction,
    and is not the half-split form (whose program is left as it was)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 3, 16))
    got = pattern.rotary(x, 1e6, 1.0, "interleaved")
    close(got, ref.rotate_pairs(x, 1e6), 1e-6)
    assert float(jnp.max(jnp.abs(got - pattern.rotary(x, 1e6, 1.0)))) > 0.1
    part = pattern.rotary(x, 1e4, 0.5, "interleaved")
    close(part[..., :8], ref.rotate_pairs(x[..., :8], 1e4), 1e-6)
    np.testing.assert_array_equal(part[..., 8:], x[..., 8:])
    # a pair's norm is kept and position 0 is not turned
    np.testing.assert_allclose(jnp.linalg.norm(got.reshape(2, 64, 3, 8, 2), axis=-1),
                               jnp.linalg.norm(x.reshape(2, 64, 3, 8, 2), axis=-1), rtol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    assert str(jax.make_jaxpr(lambda a: pattern.rotary(a, 1e6, 1.0))(x)) == str(
        jax.make_jaxpr(lambda a: pattern.rotary(a, 1e6, 1.0, "half_split"))(x))


# ---------------------------------------------------------------------------
# the latent flash kernel pair, interpreted


def _dense_latent(qn, qp, kn, kp, v, h):
    from dtc_tpu.ops.attention import latent_causal_attention

    return latent_causal_attention(qn, qp, kn, kp, v, impl="dense", heads=h)


def _dense_lse(qn, qp, kn, kp, h):
    """Each head's logsumexp over its causal scores, (B, H, T), float32."""
    b, t, _ = qn.shape
    q = jnp.concatenate([qn.reshape(b, t, h, -1), qp.reshape(b, t, h, -1)], -1)
    k = jnp.concatenate([kn.reshape(b, t, h, -1),
                         jnp.broadcast_to(kp[:, :, None], (b, t, h, kp.shape[-1]))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s / q.shape[-1] ** 0.5, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


def _latent_operands(b, t, h, dn, dr, dv, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(b, t, h * dn), (b, t, h * dr), (b, t, h * dn), (b, t, dr), (b, t, h * dv)]
    args = [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, shapes)]
    return args, jax.random.normal(ks[5], (b, t, h * dv))


@pytest.mark.parametrize("dtype,tol,t,fwd,dv", [
    # forward q blocks of 128, one strip each, the second block one chunk below
    pytest.param("float32", 1e-5, 256, (128, 128, 128), 128, id="float32-1e-05"),
    pytest.param("bfloat16", 3e-2, 256, (128, 128, 128), 128, id="bfloat16-0.03"),
    # one q block, the whole sequence: two strips on the diagonal, nothing carried
    pytest.param("float32", 1e-5, 256, (256, 128, 128), 128, id="whole-block"),
    # three q blocks of two strips, the last with four chunks below (both buffers twice)
    pytest.param("float32", 1e-5, 768, (256, 128, 128), 128, id="chunks-below"),
    # values of two lane tiles a head: the statistics widened across them
    pytest.param("bfloat16", 3e-2, 512, (256, 128, 128), 256, id="chunks-below-v256"),
])
def test_latent_flash_pair_matches_dense_attention(dtype, tol, t, fwd, dv):
    """The kernels in interpret mode at q / k 192 (128 + 64) and v ``dv``,
    four heads (two grid steps of two): the forward at (q block, KV chunk,
    unit) ``fwd`` — its own square in row strips, the chunks below fetched
    by the kernel and carried over — and the backward in tiles of 128 (a
    tile on the diagonal masked, one below it unmasked, one above it
    skipped): the output, lse and all five gradients, the rope key's summed
    over the heads, against the dense path that tiles the rope key to every
    head."""
    from dtc_tpu.ops import flash_attention
    from dtc_tpu.ops.flash_attention import mla_flash_attention

    h = 4
    args, co = _latent_operands(1, t, h, 128, 64, dv, jnp.dtype(dtype))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * co)

    flash = lambda *a: mla_flash_attention(*a, 128, 128, fwd)  # noqa: E731
    dense = lambda *a: _dense_latent(*a, h)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        close(flash(*args), dense(*args), tol)
        qn, qp, kn, kp, v = args
        _, lse = flash_attention._mla_fwd_call(
            *args, *flash_attention._mla_static(qn, qp, kp, v, 128, 128, fwd))
        close(lse.transpose(0, 1, 3, 2).reshape(1, h, t), _dense_lse(qn, qp, kn, kp, h), tol)
        got = jax.grad(loss(flash), argnums=range(5))(*args)
        want = jax.grad(loss(dense), argnums=range(5))(*args)
    for name, g, w in zip(("q_nope", "q_pe", "k_nope", "k_pe", "v"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        close(g, w, 3 * tol)
    assert got[3].shape == (1, t, 64)   # one rope key, its gradient the sum over the heads


def test_latent_flash_pair_refuses_widths_it_cannot_tile():
    from dtc_tpu.ops.flash_attention import mla_flash_attention, mla_group

    assert mla_group(32, 128, 64, 128) == 2 and mla_group(32, 128, 128, 128) == 1
    assert mla_group(32, 96, 64, 128) is None and mla_group(3, 128, 64, 128) is None
    args, _ = _latent_operands(1, 256, 2, 64, 32, 64)
    with pytest.raises(ValueError, match="latent flash attention unsupported"):
        mla_flash_attention(*args)


# ---------------------------------------------------------------------------
# the whole model through the program's step


def _program_grads(cfg, params, batch):
    model = pattern.build_model(cfg)

    def loss(p):
        return model.apply({"params": p}, batch[:, :-1], train=False, targets=batch[:, 1:],
                           mutable=["counters"])[0]

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


def _reference_grads(model, w, batch, fault=None):
    if fault is not None:
        model = {**model, "fault": fault}
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(ref.loss_fn)(w, batch[:, :-1], batch[:, 1:], model)


@pytest.fixture(scope="module")
def sides(kanana2_cfg):
    """(program loss and gradients by reference name, the reference's,
    the model, its weights, the batch) at the toy size in float32."""
    w = weights(kanana2_cfg, seed=5, family=KANANA2)
    batch = jnp.asarray(np.random.default_rng(0).integers(
        0, kanana2_cfg.vocab_size, (2, kanana2_cfg.max_seq_len + 1)), jnp.int32)
    loss, g = _program_grads(kanana2_cfg, program_params(w, KANANA2), batch)
    flat = {KANANA2.leaf_names["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(g)}
    return (loss, flat), _reference_grads(as_model(kanana2_cfg), w, batch), as_model(kanana2_cfg), w, batch


def test_logits_are_the_reference_s(kanana2_cfg):
    w = weights(kanana2_cfg, seed=5, family=KANANA2)
    x = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, kanana2_cfg.max_seq_len)), jnp.int32)
    model = pattern.build_model(kanana2_cfg)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": program_params(w, KANANA2)}, x, train=False, mutable=["counters"])[0]
        m = as_model(kanana2_cfg)
        h = w["wte"][x]
        h = ref.layer(ref.layer_params(w, "lead.0."), h, m, ("mla", "swiglu"))
        for i in range(kanana2_cfg.pattern_periods):
            p = {k: v[i] for k, v in ref.layer_params(w, "blocks.0.").items()}
            h = ref.layer(p, h, m, ("mla", "moe_shared_ungated"))
        want = ref.rms(h, w["norm_f.g"], m["norm_eps"]) @ w["head.w"]
    close(got[..., :256], want[..., :256], TIGHT)


def test_loss_and_every_gradient_are_the_reference_s(sides):
    (loss, got), (want_loss, want), *_ = sides
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    assert set(got) == set(want)
    for name, g in got.items():
        if name.endswith("moe.bias"):   # the choice only: no gradient on either side
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(want[name]))
            continue
        close(g, want[name], TIGHT)


def test_the_program_s_step_follows_the_reference(kanana2_cfg, opt_cfg):
    """One step through ``create_train_step``: the loss, the counters, and
    the clipped gradient read back from AdamW's first moment, as the
    benchmark's comparison reads it."""
    w = weights(kanana2_cfg, seed=5, family=KANANA2)
    batch = np.random.default_rng(0).integers(0, 256, (2, kanana2_cfg.max_seq_len + 1), dtype=np.int32)
    state, (loss,), counters = one_device_steps(kanana2_cfg, opt_cfg, [batch], w, KANANA2)
    assert counters["moe"].shape == (2, 6) and float(counters["moe"][:, 3].sum()) == 0.0
    optim = {"lr": opt_cfg.lr, "weight_decay": opt_cfg.weight_decay, "grad_clip": opt_cfg.grad_clip}
    out = ref.run_steps(as_model(kanana2_cfg), optim, 5, [batch])
    assert abs(float(loss) - out["losses"][0]) <= 1e-4 * out["losses"][0]
    mu = next(s.mu for s in jax.tree.leaves(state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
              if hasattr(s, "mu"))
    flat = {KANANA2.leaf_names["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(mu)}
    got = jax.device_get(ref.leaf_norms(flat))
    for name, want in out["grad1"].items():
        np.testing.assert_allclose(np.asarray(got[name]) / (1 - ref.B1), want, rtol=2e-3, atol=1e-7,
                                   err_msg=name)


def _worst_gap(got: dict, want: dict) -> float:
    return max(float(np.max(np.abs(np.asarray(g) - np.asarray(want[k])))
                     / max(float(np.max(np.abs(np.asarray(want[k])))), 1e-30))
               for k, g in got.items() if not k.endswith("moe.bias"))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_of_the_reference_fails_the_comparison(sides, fault):
    """Half-split pairs, no latent norm, the scores at 1/sqrt(nope), an
    unrotated rope key: each moves some gradient by far more than the
    comparison's tolerance, which the sound program keeps to."""
    (_, got), (_, sound), model, w, batch = sides
    _, faulty = _reference_grads(model, w, batch, fault)
    assert _worst_gap(got, sound) <= TIGHT
    assert _worst_gap(got, faulty) > 10 * TIGHT, fault


def test_a_gated_shared_expert_fails_the_comparison(kanana2_cfg, sides):
    """The other fault, in the program: the shared expert behind Qwen3-Next's
    sigmoid gate (``moe_shared``, its gate drawn) in place of the ungated one."""
    (_, got), (_, sound), _, w, batch = sides
    gated = dataclasses.replace(kanana2_cfg, layer_pattern=("mla+moe_shared",))
    params = program_params(w, KANANA2)
    moe = params["stage"]["periods"]["layer_0"]["moe"]
    moe["shared_gate"] = {"kernel": 0.02 * jax.random.normal(jax.random.PRNGKey(4), (2, 64, 1))}
    _, g = _program_grads(gated, params, batch)
    g["stage"]["periods"]["layer_0"]["moe"].pop("shared_gate")
    flat = {KANANA2.leaf_names["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(g)}
    assert _worst_gap(flat, sound) > 10 * TIGHT


# ---------------------------------------------------------------------------
# the share of the experts


def test_shares_of_eight_chips_add_up_to_the_uncut_layer(kanana2_cfg):
    """The routed parts of all eight shares (8 x 1 expert), with the shared
    expert that every chip computes alike counted once, equal the reference
    layer that holds all 8. Every share scores all 8 and chooses with the
    whole selection bias."""
    whole = dataclasses.replace(kanana2_cfg, moe_experts_held=0)
    w = weights(whole, family=KANANA2)
    tree, flat = layer_of(w, 0, KANANA2)
    x = normed_input(kanana2_cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_layer(flat, x, as_model(whole))
    total = jnp.zeros_like(want)
    for rank in range(8):
        share = dataclasses.replace(kanana2_cfg, moe_experts_held=1, moe_expert_rank=rank)
        p = dict(tree["moe"])
        for leaf in ("w_gate", "w_up", "w_down"):
            p[leaf] = p[leaf][rank: rank + 1]
        kind = "moe_shared_ungated" if rank == 0 else "moe"
        if rank:
            p.pop("shared")
        y, mut = pattern.FFNS[kind][0](share).apply({"params": p}, x, mutable=["counters"])
        assert mut["counters"]["moe"][0].shape == (6,)
        total = total + y
    close(total, want, TIGHT)


# ---------------------------------------------------------------------------
# config rules, plans, counts


@pytest.mark.parametrize("change,message", [
    ({"mla_kv_rank": 0}, "mla layers need"),
    ({"mla_qk_rope_dim": 7}, "must be even"),
    ({"n_kv_heads": 2}, "no n_kv_heads"),
    ({"attn_head_dim": 24}, "no attn_head_dim"),
    ({"rope_pairing": "adjacent"}, "unknown rope_pairing"),
    ({"moe_shared_d_ff": 0}, "moe_shared_ungated layers need moe_shared_d_ff"),
    ({"n_layers": 5, "layer_pattern": ("mla+moe_shared_ungated", "attn+moe")}, "beside attn"),
])
def test_config_rules_of_the_new_kinds(kanana2_cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(kanana2_cfg, **change)


def test_head_dim_is_the_width_the_scores_are_taken_at(kanana2_cfg):
    cfg, _ = cell_cfg(CELL)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.latent_attention) == (192, 128, True)
    assert (kanana2_cfg.head_dim, kanana2_cfg.v_head_dim) == (24, 12)
    lfm2, _ = cell_cfg("lfm2-8b-a1b")
    assert (lfm2.head_dim, lfm2.v_head_dim, lfm2.latent_attention) == (64, 64, False)


def test_plans_name_what_the_kanana2_cell_runs(kanana2_cfg, monkeypatch):
    """``layer_plan`` names the latent kernel pair, both widths, the tiles,
    the pairing and that the rope key is read once; the toy runs the dense
    path with the key broadcast. ``flash_plan`` reports both widths and
    every pass's schedule and bytes, each inside the budget it states."""
    from dtc_tpu.ops import attention, vmem

    toy = pattern.layer_plan(kanana2_cfg)["mla"]
    assert (toy["kernel"], toy["rope_key"], toy["rotary_pairing"]) == ("dense", "broadcast", "interleaved")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg, _ = cell_cfg(CELL)
    plan = pattern.layer_plan(cfg)
    assert plan["leading"] == ["mla+swiglu"] and plan["pattern"] == ["mla+moe_shared_ungated"]
    mla = plan["mla"]
    assert (mla["kernel"], mla["rope_key"], mla["qk_dim"], mla["v_dim"], mla["kv_rank"]) == (
        "flash_mla", "once", 192, 128, 512)
    # dq and dk / dv walk the grid a 512 x 512 tile a step
    assert (mla["block_q"], mla["block_kv"]) == (512, 512)
    grid = vmem.flash_schedule(8192, 512, 512)
    assert (grid["updates"], grid["chunks_skipped"]) == (136, 120)
    # the forward walks the triangle: one grid step a q block of 1024 (eight a row and
    # head pair, none skipped), 28 chunks of 1024 below them and 32 strips of 256
    fwd = mla["fwd"]
    assert (fwd["schedule"], fwd["block_q"], fwd["kv_chunk"], fwd["unit"]) == (
        "triangle", 1024, 1024, [256, 256])
    assert fwd["updates"] == 28 + 32 < grid["updates"]
    assert fwd["covered_share"] == 0.515625
    event = attention.flash_plan_event(cfg)
    assert (event["qk_dim"], event["v_dim"], event["kernel"]) == (192, 128, "flash_mla")
    assert event["fwd"]["updates"] == 60 and event["fits"]
    for leg in ("fwd", "dq", "dkv"):
        assert event[leg]["bytes"] + event[leg]["modeled_transient_bytes"] <= event["budget_bytes"]
        assert 16 * 2**20 < mla["vmem_limit_bytes"][leg] <= (
            event["budget_bytes"] + vmem.VMEM_COMPILER_ALLOWANCE_BYTES)
    assert attention.resolve_impl(cfg.attention, cfg.max_seq_len, cfg.head_dim,
                                  cfg.attention_block_q, cfg.attention_block_kv) == "flash"
    moe = pattern.moe_plan(cfg, 2 * 8192)
    assert (moe["shared_width"], moe["shared_gate"], moe["experts_held"], moe["top_k"]) == (1536, False, 16, 6)
    assert moe["expected_held"] == 768 * 16   # 768 assignments a held expert and layer


def test_parameter_count_of_the_kanana2_cell_is_the_sum_of_its_layers():
    from dtc_tpu.models.gpt import param_count

    cfg, model = cell_cfg(CELL)
    assert (cfg.vocab_size, cfg.padded_vocab_size) == (16032, 16128)
    mla = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    assert mla == 26_345_984
    dense = mla + 3 * 2048 * 6144 + 2 * 2048
    expert = mla + 2048 * 128 + 128 + 16 * 3 * 2048 * 768 + 3 * 2048 * 1536 + 2 * 2048
    head = 2 * 16128 * 2048 + 2048
    assert param_count(cfg) == dense + 4 * expert + head == 576_349_184
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(model).values()) == param_count(cfg)
    shapes = jax.eval_shape(lambda: pattern.build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), train=False))["params"]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == param_count(cfg)


def test_the_two_operation_counts_of_the_kanana2_cell_are_equal():
    from dtc_tpu.utils.metrics import pattern_step_flops

    flops = load_by_path(os.path.join(REPO, "benchmark", "flops_kanana2.py"), "flops_kanana2")
    cfg, model = cell_cfg(CELL)
    for rows, seq in ((2, 8192), (4, 8192), (8, 1024)):
        assert flops.train_step_flops(model, rows, seq) == pattern_step_flops(cfg, rows, seq)
    step = pattern_step_flops(cfg, 2, 8192)
    assert 45.6e12 < step < 45.8e12
    per = flops.matmul_params(model)
    attention = 6.0 * 5 * 2 * 8192**2 * 32 * (192 + 128) / 2
    projections = 6.0 * 5 * per["mla"] * 2 * 8192
    assert 20.6e12 < attention < 20.7e12 and 12.9e12 < projections < 13.0e12
    assert 0.73 < (attention + projections) / step < 0.74
    counted = flops.train_step_flops(model, 2, 8192, 4 * 12288.0 + 1000)
    np.testing.assert_allclose(counted - flops.train_step_flops(model, 2, 8192),
                               6 * 3 * 2048 * 768 * 1000.0, rtol=1e-9)


def test_sharding_table_covers_the_kanana2_leaves():
    from dtc_tpu.parallel.sharding import logical_axes_for_path

    cfg, _ = cell_cfg(CELL)
    shapes = jax.eval_shape(lambda: pattern.build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32), train=False))["params"]
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        assert len(logical_axes_for_path(path)) == leaf.ndim, path

