"""A looped stack (``models/pattern.py``, "Passes"): sandwich-normed layers
run ``stack_passes`` times on the same leaves, a head pass and an exit gate
after every pass, the loss over the learned exit distribution — the Ouro
kind. The program against ``benchmark/reference_ouro.py`` at toy size in
float32 (``tests/pattern_helpers.OURO``), the weight sharing against an
unrolled model, the exit distribution, the per-token fused cross-entropy,
the config rules, the counts at the benchmark cell's configuration, and that
the defaults leave every other pattern model's step the parent's.
"""

import dataclasses
import hashlib
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.models import pattern
from tests.conftest import make_train_cfg
from tests.pattern_helpers import (  # noqa: F401  (ouro_cfg is a fixture)
    LFM2, OURO, QWEN3, REPO, TIGHT, as_model, cell_cfg, close, load_by_path, one_device_steps, ouro_cfg,
    program_params, weights,
)

LEAVES = sorted(OURO.leaf_names.values())
PASSES = 4


def _batch(cfg, rows=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (rows, cfg.max_seq_len + 1)),
                       jnp.int32)


def _by_ref(tree) -> dict:
    return {OURO.leaf_names["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _loss(cfg, batch):
    model = pattern.build_model(cfg)
    return lambda p: model.apply({"params": p}, batch[:, :-1], train=True, targets=batch[:, 1:],
                                 mutable=["counters"])


# ---------------------------------------------------------------------------
# the program against the reference


@pytest.fixture(scope="module")
def both(ouro_cfg):
    """Loss, gradients and counters of the program and of the reference on
    the same seeded weights and rows."""
    w = weights(ouro_cfg, seed=5, family=OURO)
    batch = _batch(ouro_cfg)
    with jax.default_matmul_precision("highest"):
        (loss, mut), grads = jax.value_and_grad(_loss(ouro_cfg, batch), has_aux=True)(program_params(w, OURO))
        want, ref_grads = jax.value_and_grad(OURO.ref.loss_fn)(w, batch[:, :-1], batch[:, 1:], as_model(ouro_cfg))
    return types.SimpleNamespace(loss=loss, grads=_by_ref(grads), counters=mut["counters"], want=want,
                                 ref_grads=ref_grads, w=w, batch=batch)


def test_loss_is_the_reference_s(both):
    assert float(both.loss) == pytest.approx(float(both.want), rel=1e-5)
    assert set(both.grads) == set(both.ref_grads) == set(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_is_the_reference_s(both, leaf):
    assert float(jnp.max(jnp.abs(both.ref_grads[leaf]))) > 0
    close(both.grads[leaf], both.ref_grads[leaf], TIGHT)


def test_pass_counters_are_what_the_reference_computes(both, ouro_cfg):
    """One row a pass: the tokens' mean exit probability and cross-entropy
    at that pass and the mean entropy, as the reference's pieces give them."""
    ref, model = OURO.ref, as_model(ouro_cfg)
    (rows,) = both.counters["passes"]
    assert rows.shape == (PASSES, len(pattern.PASS_COUNTERS)) and "stage" not in both.counters
    x, y = both.batch[:, :-1], both.batch[:, 1:]
    with jax.default_matmul_precision("highest"):
        h, ce, z = both.w["wte"][x], [], []
        for _ in range(PASSES):
            h = ref.rms(ref.stack(both.w, h, model), both.w["norm_f.g"], model["norm_eps"])
            ce.append(ref.token_losses(h, y, both.w["head.w"], model["vocab_size"]))
            z.append((h @ both.w["exit.w"] + both.w["exit.b"])[..., 0])
    logp = ref.exit_log_probs(z)
    np.testing.assert_allclose(rows[:, 0], [float(jnp.exp(lp).mean()) for lp in logp], rtol=1e-4)
    np.testing.assert_allclose(rows[:, 1], [float(c.mean()) for c in ce], rtol=1e-5)
    entropy = -sum(jnp.exp(lp) * lp for lp in logp).mean()
    np.testing.assert_allclose(rows[:, 2], [float(entropy)] * PASSES, rtol=1e-4)
    assert float(rows[:, 0].sum()) == pytest.approx(1.0, abs=1e-5)


@pytest.fixture(scope="module")
def three_steps(ouro_cfg, opt_cfg_module):
    """Three AdamW steps through the program's own state and compiled step,
    and through the reference."""
    cfg, ref = ouro_cfg, OURO.ref
    opt_cfg = opt_cfg_module
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab_size, (2, cfg.max_seq_len + 1), dtype=np.int32) for _ in range(3)]
    w = weights(cfg, seed=5, family=OURO)
    with jax.default_matmul_precision("highest"):
        state, losses, counters = one_device_steps(cfg, opt_cfg, batches, w, OURO)
        optim = {"lr": opt_cfg.lr, "weight_decay": opt_cfg.weight_decay, "grad_clip": opt_cfg.grad_clip}
        out = ref.run_steps(as_model(cfg), optim, 5, batches)
        w = weights(cfg, seed=5, family=OURO)          # the step donated the first draw's buffers
        moved = jax.device_get(ref.leaf_norms({k: v - w[k] for k, v in _by_ref(state.params).items()}))
    return types.SimpleNamespace(losses=losses, counters=counters, out=out, moved=moved)


@pytest.fixture(scope="module")
def opt_cfg_module():
    from dtc_tpu.config.schema import OptimConfig

    return OptimConfig(lr=3e-4, weight_decay=0.1, grad_clip=1.0)


@pytest.mark.parametrize("step", [0, 1, 2])
def test_three_adamw_steps_follow_the_reference(three_steps, step):
    assert three_steps.losses[step] == pytest.approx(three_steps.out["losses"][step], rel=1e-4)


def test_three_adamw_steps_move_every_leaf_as_the_reference_does(three_steps):
    assert list(three_steps.counters) == ["passes"]   # no expert layer: no "moe" rows
    for name, want in three_steps.out["dparam"].items():
        np.testing.assert_allclose(three_steps.moved[name], want, rtol=5e-3, atol=1e-7, err_msg=name)


# ---------------------------------------------------------------------------
# the passes share every leaf


def _unrolled(cfg, copies, batch):
    """The same model with pass t on its own copy ``copies[t]`` of the
    stack's, the head's and the gate's leaves: T x N untied layers. Built
    from the program's pieces, with no scan over passes."""
    stage, head = pattern.PatternStage(cfg), pattern.PatternHead(cfg)
    x, y = batch[:, :-1], batch[:, 1:]
    h = pattern.PatternEmbed(cfg).apply({"params": copies[0]["embed"]}, x)
    ce, z = [], []
    for p in copies:
        h = stage.apply({"params": p["stage"]}, h, train=False)
        h = head.apply({"params": p["head"]}, h, method="norm")
        ce.append(head.apply({"params": p["head"]}, h, y, method="token_losses"))
        z.append(head.apply({"params": p["head"]}, h, method="gate"))
    return pattern.exit_loss(jnp.stack(ce), jnp.stack(z))[0], h, head, copies[-1]["head"]


def test_looped_gradient_is_the_sum_over_the_copies_of_an_unrolled_model(both, ouro_cfg):
    params = program_params(both.w, OURO)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda cs: _unrolled(ouro_cfg, cs, both.batch)[0])([params] * PASSES)
    assert float(loss) == pytest.approx(float(both.loss), rel=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *grads)
    summed["embed"] = grads[0]["embed"]          # the embedding is used once, by the first copy
    for name, got in _by_ref(summed).items():
        close(both.grads[name], got, TIGHT)
    # and the copies do differ: a later pass's gradient is not the first's
    first, last = (g["stage"]["periods"]["layer_0"]["mlp"]["up_proj"]["kernel"] for g in (grads[0], grads[-1]))
    assert float(jnp.max(jnp.abs(first - last))) > 1e-3 * float(jnp.max(jnp.abs(first)))


def test_without_targets_the_last_pass_s_logits_come_back(both, ouro_cfg):
    params = program_params(both.w, OURO)
    with jax.default_matmul_precision("highest"):
        got = pattern.build_model(ouro_cfg).apply({"params": params}, both.batch[:, :-1], train=False)
        _, h, head, leaves = _unrolled(ouro_cfg, [params] * PASSES, both.batch)
        want = head.apply({"params": leaves}, h, method="logits")
    assert got.shape == (2, ouro_cfg.max_seq_len, ouro_cfg.padded_vocab_size)
    close(got, want, TIGHT)


def test_the_step_holds_one_scan_over_passes_around_one_over_layers(both, ouro_cfg):
    """The passes are one scanned body: the jaxpr of the loss holds the
    layers' scan once, inside the passes' scan, whatever the pass count."""
    sizes = {}
    for passes in (2, PASSES):
        cfg = dataclasses.replace(ouro_cfg, stack_passes=passes)
        text = str(jax.make_jaxpr(lambda p: _loss(cfg, both.batch)(p)[0])(program_params(both.w, OURO)))
        lengths = [int(n) for n in re.findall(r"\blength=(\d+)", text)]
        assert sorted(lengths) == sorted([passes, cfg.pattern_periods]), lengths
        sizes[passes] = len(text)
    assert sizes[PASSES] < 1.02 * sizes[2]


def test_expert_layers_in_a_looped_stack_count_once_a_pass_and_layer(lfm2_cfg_no_lead):
    """The expert layers' counters under the passes' scan: one row a pass and
    layer, beside the passes' own rows."""
    cfg = lfm2_cfg_no_lead
    model = pattern.build_model(cfg)
    batch = _batch(cfg, rows=2, seed=2)
    params = model.init(jax.random.PRNGKey(0), batch[:, :-1], train=False)["params"]
    from dtc_tpu.train.train_step import stack_counters

    (loss, mut), grads = jax.value_and_grad(_loss(cfg, batch), has_aux=True)(params)
    counted = stack_counters(mut)
    assert counted["moe"].shape == (2 * 4, 6) and counted["passes"].shape == (2, 3)
    assert np.isfinite(float(loss)) and all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(counted["moe"][:, 3].sum()) == 0.0      # nothing dropped in either pass


@pytest.fixture(scope="module")
def lfm2_cfg_no_lead():
    return dataclasses.replace(LFM2.cfg(), n_layers=4, leading_pattern=(), tie_embeddings=False,
                               stack_passes=2)


# ---------------------------------------------------------------------------
# the exit distribution


def test_exit_distribution_sums_to_one_whatever_the_gates_say():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (PASSES, 5, 7))
    p = jnp.exp(pattern.exit_distribution(z))
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-4)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-4)


def test_gates_at_zero_give_half_quarter_eighth_eighth():
    p = jnp.exp(pattern.exit_distribution(jnp.zeros((PASSES, 3))))
    np.testing.assert_allclose(p[:, 0], [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    loss, rows = pattern.exit_loss(jnp.ones((PASSES, 3)), jnp.zeros((PASSES, 3)), 0.1)
    assert float(loss) == pytest.approx(1.0 - 0.1 * 1.75 * np.log(2.0), rel=1e-6)   # H = 1.75 bits
    np.testing.assert_allclose(rows[:, 0] @ np.arange(1, PASSES + 1), 1.875, rtol=1e-6)


def test_the_last_gate_has_no_gradient_and_the_entropy_term_moves_only_the_gates(both):
    ce = 5.0 + jax.random.normal(jax.random.PRNGKey(1), (PASSES, 4, 6))
    z = jax.random.normal(jax.random.PRNGKey(2), (PASSES, 4, 6))
    for beta in (0.0, 0.1):
        dz = jax.grad(lambda z: pattern.exit_loss(ce, z, beta)[0])(z)
        assert not np.any(np.asarray(dz[-1])) and np.all(np.asarray(dz[:-1]) != 0)
    dce = [jax.grad(lambda c: pattern.exit_loss(c, z, beta)[0])(ce) for beta in (0.0, 0.5)]
    assert np.array_equal(np.asarray(dce[0]), np.asarray(dce[1]))       # the entropy does not see the losses
    term = jax.grad(lambda z: pattern.exit_loss(ce, z, 0.5)[0] - pattern.exit_loss(ce, z, 0.0)[0])(z)
    assert float(jnp.max(jnp.abs(term[:-1]))) > 1e-4
    # in the model: the gate's leaves get a gradient that is not negligible beside the median leaf's
    norms = {k: float(jnp.linalg.norm(v)) for k, v in both.ref_grads.items()}
    median = float(np.median(list(norms.values())))
    assert norms["exit.w"] > 1e-2 * median and norms["exit.b"] > 1e-2 * median


# ---------------------------------------------------------------------------
# the per-token fused head + cross-entropy


def _parent_fused_head_ce():
    """``fused_head_ce`` as it stood before the per-token form was cut out
    of it (``ops/fused_ce.py`` at the parent commit), for the jaxpr test."""
    import functools

    from flax import linen as nn

    from dtc_tpu.ops.fused_ce import head_logits

    def stats_loss(logits, y):
        l32 = logits.astype(jnp.float32)
        maxl = jax.lax.stop_gradient(jnp.max(l32, axis=-1, keepdims=True))
        shifted = l32 - maxl
        logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        gold = jnp.sum(jnp.where(iota == y[..., None], shifted, 0.0), axis=-1)
        return (logz - gold).mean(), (maxl, logz)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def op(h, w, b, y, vocab_size):
        return stats_loss(head_logits(h, w, b, vocab_size), y)[0]

    def fwd(h, w, b, y, vocab_size):
        logits = head_logits(h, w, b, vocab_size)
        loss, (maxl, logz) = stats_loss(logits, y)
        return loss, (h, w, y, logits, maxl, logz)

    def bwd(vocab_size, res, g):
        h, w, y, logits, maxl, logz = res
        *lead, v = logits.shape
        d = h.shape[-1]
        n = float(np.prod(lead))
        l32 = logits.astype(jnp.float32)
        p = jnp.exp(l32 - maxl - logz[..., None])
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        onehot = jnp.where(iota == y[..., None], 1.0, 0.0)
        dl = ((p - onehot) * (g / n)).astype(h.dtype)
        dl = nn.with_logical_constraint(dl, ("batch", "seq", "vocab_out"))
        dl2 = dl.reshape(-1, v)
        hb = jnp.concatenate([h, jnp.ones((*lead, 1), h.dtype)], axis=-1)
        dwb = jax.lax.dot_general(hb.reshape(-1, d + 1), dl2, (((0,), (0,)), ((), ())))
        dw = dwb[:d].astype(w.dtype)
        db = dwb[d].astype(w.dtype)
        dh = (jax.lax.dot_general(dl2, w.astype(h.dtype), (((1,), (1,)), ((), ())))
              .reshape(h.shape).astype(h.dtype))
        return dh, dw, db, np.zeros(y.shape, dtype=jax.dtypes.float0)

    op.defvjp(fwd, bwd)
    return op


def _head_inputs(dtype, vocab, padded):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(ks[0], (2, 24, 32)).astype(dtype)
    w = 0.3 * jax.random.normal(ks[1], (32, padded))
    b = 0.1 * jax.random.normal(ks[2], (padded,))
    y = jax.random.randint(ks[3], (2, 24), 0, vocab)
    return h, w, b, y, jax.random.normal(ks[4], (2, 24))


def test_scalar_fused_head_ce_keeps_the_parent_s_jaxpr():
    from dtc_tpu.ops.fused_ce import fused_head_ce

    h, w, b, y, _ = _head_inputs(jnp.bfloat16, 100, 128)
    texts = [re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(jax.value_and_grad(
        lambda h, w, b: op(h, w, b, y, 100), argnums=(0, 1, 2)))(h, w, b)))
        for op in (fused_head_ce, _parent_fused_head_ce())]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("vocab,padded", [(128, 128), (100, 128)], ids=["whole", "padded"])
def test_per_token_fused_ce_is_log_softmax_in_values_and_gradients(vocab, padded):
    """Values (B, T) float32, and dh, dW, db under a random per-token
    cotangent, against ``log_softmax`` over the unpadded columns."""
    from dtc_tpu.ops.fused_ce import fused_head_ce_tokens

    h, w, b, y, co = _head_inputs(jnp.float32, vocab, padded)

    def plain(h, w, b):
        logp = jax.nn.log_softmax((h @ w + b)[..., :vocab], axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        got = fused_head_ce_tokens(h, w, b, y, vocab)
        assert got.shape == (2, 24) and got.dtype == jnp.float32
        close(got, plain(h, w, b), 1e-6)
        grads = [jax.grad(lambda *a: jnp.sum(f(*a) * co), argnums=(0, 1, 2))(h, w, b)
                 for f in (lambda *a: fused_head_ce_tokens(*a, y, vocab), plain)]
    for g, want in zip(*grads):
        close(g, want, 1e-5)
    if padded != vocab:
        assert not np.any(np.asarray(grads[0][1][:, vocab:]))       # a padded column learns nothing
    # the mean of the tokens' losses is the scalar form
    from dtc_tpu.ops.fused_ce import fused_head_ce

    assert float(got.mean()) == pytest.approx(float(fused_head_ce(h, w, b, y, vocab)), rel=1e-6)


def test_per_token_fused_ce_keeps_no_logits_for_the_backward():
    """Its residuals are the activations, the weights and two statistics a
    token: nothing of the vocabulary's width but the head itself."""
    from dtc_tpu.ops.fused_ce import fused_head_ce_tokens

    h, w, b, y, _ = _head_inputs(jnp.bfloat16, 100, 128)
    _, vjp = jax.vjp(lambda h, w, b: fused_head_ce_tokens(h, w, b, y, 100), h, w, b)
    held = [a.shape for a in jax.tree.leaves(vjp) if hasattr(a, "shape")]
    assert (2, 24, 128) not in held and (48, 128) not in held, held


# ---------------------------------------------------------------------------
# the defaults leave every other pattern model alone


#: sha256 of the train step's jaxpr (memory addresses stripped) and of the
#: parameter tree's (path, shape, dtype) list, taken on the PARENT commit
#: (e6155d3) with /root/scratch's copy of this test's code: a pattern model
#: that states none of the new keys builds the parent's tree and step. A PR
#: that means to change those steps takes the pins again and says so.
PARENT_STEPS = {
    "qwen3": (QWEN3, "b18749c164aeb4e1", "ee153114e8f3e7e6"),
    "lfm2": (LFM2, "2ea39570d8371029", "32016b0585822347"),
}


@pytest.mark.parametrize("name", list(PARENT_STEPS))
def test_defaults_keep_the_parent_s_tree_and_step(name):
    from flax import linen as nn

    from dtc_tpu.config.schema import OptimConfig
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES
    from dtc_tpu.train.train_step import Batch, create_train_step
    from dtc_tpu.train.trainer import init_state

    family, step_pin, tree_pin = PARENT_STEPS[name]
    cfg = family.cfg()
    assert (cfg.stack_passes, cfg.exit_gate, cfg.norm_placement, cfg.qk_norm) == (1, False, "pre", True)
    mesh = build_mesh((1, 1, 1), devices=jax.devices()[:1])
    model = pattern.build_model(cfg)
    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        state = init_state(model, cfg, make_train_cfg("dp", batch=2),
                           OptimConfig(lr=1e-3, weight_decay=0.1, grad_clip=1.0), mesh)
        step = create_train_step(mesh, model=model, state=state)
        xy = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
        text = str(jax.make_jaxpr(step)(state, Batch(x=xy, y=xy), jax.random.PRNGKey(0)))
    text = re.sub(r"0x[0-9a-f]+", "", text)
    tree = str([(jax.tree_util.keystr(p), a.shape, str(a.dtype))
                for p, a in jax.tree_util.tree_leaves_with_path(state.params)])
    assert hashlib.sha256(tree.encode()).hexdigest()[:16] == tree_pin
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == step_pin


# ---------------------------------------------------------------------------
# the config rules, the plan, the counts


@pytest.mark.parametrize("change,message", [
    ({"stack_passes": 0}, "stack_passes=0 must be >= 1"),
    ({"n_layers": 4, "leading_pattern": ("attn+swiglu",)}, "passes over a stack with leading layers"),
    ({"tie_embeddings": True}, "with a tied head"),
    ({"norm_placement": "post"}, "unknown norm_placement"),
])
def test_config_rules_of_a_looped_stack(ouro_cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(ouro_cfg, **change)
    # the exit gate comes with the passes: one pass has none, two have one
    assert ouro_cfg.exit_gate and dataclasses.replace(ouro_cfg, stack_passes=2).exit_gate


@pytest.mark.parametrize("change", [{"stack_passes": 4}, {"norm_placement": "sandwich"},
                                    {"qk_norm": False}])
def test_a_gpt2_model_has_none_of_the_new_keys(change):
    with pytest.raises(ValueError, match="belong to a layer-pattern model"):
        ModelConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=2, d_ff=64, max_seq_len=32, **change)


def test_plan_names_what_the_ouro_cell_runs():
    cell, _ = cell_cfg("ouro-2.6b")
    plan = pattern.layer_plan(cell)
    assert (plan["pattern"], plan["periods"], plan["passes"]) == (["attn+swiglu"], 8, 4)
    assert plan["norm_placement"] == "sandwich" and plan["exit"] == {"beta": 0.1, "pass_logits": "recomputed"}
    assert {k: plan["attn"][k] for k in ("heads", "kv_heads", "head_dim", "rotary_dims", "qk_norm")} == {
        "heads": 16, "kv_heads": 16, "head_dim": 128, "rotary_dims": 128, "qk_norm": False}
    assert pattern.moe_plan(cell, 2 * 4096) is None
    # the other families' plans say one pass and pre-norm
    other = pattern.layer_plan(cell_cfg("lfm2-8b-a1b")[0])
    assert (other["passes"], other["norm_placement"]) == (1, "pre") and "exit" not in other


def test_flash_plan_of_the_ouro_cell_is_the_packed_family_s(monkeypatch):
    """16 heads of 128 without KV groups at 4096: one head a lane group of
    the packed kernels, the tiles from the shape, the triangle in the kernel."""
    from dtc_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    plan = attention.flash_plan_event(cell_cfg("ouro-2.6b")[0])
    assert (plan["seq_len"], plan["head_dim"], plan["heads"]) == (4096, 128, 16)
    assert plan["fwd"]["schedule"] == plan["bwd"]["schedule"] == "triangle"


def test_parameter_count_of_the_ouro_cell_is_the_issue_s_sum():
    from dtc_tpu.models.gpt import param_count

    cfg, model = cell_cfg("ouro-2.6b")
    assert cfg.padded_vocab_size == cfg.vocab_size == 49152
    assert param_count(cfg) == 612_438_017
    assert param_count(cfg) == 8 * 51_388_416 + 201_326_592 + 2048 + 2049
    assert sum(int(np.prod(s)) for s in OURO.ref.leaf_shapes(model).values()) == param_count(cfg)
    shapes = jax.eval_shape(lambda: pattern.build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.max_seq_len), jnp.int32), train=False))["params"]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == param_count(cfg)
    assert {"/".join(str(k.key) for k in p) for p, _ in jax.tree_util.tree_leaves_with_path(shapes)} == set(
        OURO.leaf_names)


def test_the_two_operation_counts_of_the_ouro_cell_are_equal():
    from dtc_tpu.utils.metrics import pattern_step_flops

    flops = load_by_path(os.path.join(REPO, "benchmark", "flops_ouro.py"), "flops_ouro")
    cfg, model = cell_cfg("ouro-2.6b")
    for rows, seq in ((2, 4096), (4, 4096), (8, 1024)):
        assert flops.train_step_flops(model, rows, seq) == pattern_step_flops(cfg, rows, seq)
    assert 113.7e12 < pattern_step_flops(cfg, 2, 4096) < 113.9e12
    # the stack and the head count once a pass: a fifth pass adds a quarter
    five = dataclasses.replace(cfg, stack_passes=5)
    assert pattern_step_flops(five, 2, 4096) == pytest.approx(1.25 * pattern_step_flops(cfg, 2, 4096), rel=1e-12)


def test_sharding_table_covers_the_ouro_leaves(both):
    from dtc_tpu.parallel.sharding import FSDP_RULES, param_specs

    specs = param_specs(program_params(both.w, OURO), FSDP_RULES)
    layer = specs["stage"]["periods"]["layer_0"]
    assert tuple(layer["norm_1_post"]["scale"]) == tuple(layer["norm_2_post"]["scale"]) == (None, "data")
    assert tuple(specs["head"]["exit_gate"]["kernel"]) == ("data", None)
    assert tuple(specs["head"]["exit_gate"]["bias"]) == (None,)
    assert "q_norm" not in layer["attn_full"]


def test_trainer_s_event_carries_the_passes_readings():
    from dtc_tpu.train.trainer import _emit_counters

    events = []
    emit = lambda etype, **fields: events.append({"etype": etype, **fields})  # noqa: E731
    tele = types.SimpleNamespace(registry=types.SimpleNamespace(emit=emit))
    rows = np.array([[0.5, 6.0, 1.2], [0.25, 5.9, 1.2], [0.125, 5.8, 1.2], [0.125, 5.7, 1.2]], np.float32)
    _emit_counters(tele, [7, 8], {"passes": np.stack([rows, rows])})
    assert [e["etype"] for e in events] == ["pass_counters"] * 2 and [e["step"] for e in events] == [7, 8]
    assert events[0]["exit_p"] == [0.5, 0.25, 0.125, 0.125] and events[0]["exit_entropy"] == pytest.approx(1.2)
    assert events[0]["pass_ce"] == pytest.approx([6.0, 5.9, 5.8, 5.7])
