"""Mixture-of-Experts with expert parallelism (beyond the reference —
SURVEY §2.2 lists EP/MoE absent upstream).

Dispatch correctness is pinned against a brute-force per-token reference
loop FOR BOTH dispatch backends (``moe_dispatch: einsum | sort``, see
ops/moe_dispatch.py), the E=1 degenerate case must equal a plain dense
FFN, capacity overflow must drop (zero-contribute) tokens, EP sharding
comes from the rule table, and the trainer must train end-to-end (aux
loss included) on a DP x EP mesh. The backends share one routing
implementation; the cross-backend tests assert that contract from the
outside: identical routing decisions at the router output, bitwise-equal
aux loss, loss-parity training curves.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.config.schema import MeshConfig, ModelConfig
from dtc_tpu.models.gpt import GPT, MoEMLP, param_count
from dtc_tpu.train.trainer import train


def _moe_cfg(tiny_model_cfg, **kw):
    base = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=2.0)
    base.update(kw)
    return dataclasses.replace(tiny_model_cfg, **base)


def _init_moe(cfg, b=2, t=16):
    mod = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, t, cfg.d_model), jnp.float32)
    variables = mod.init({"params": jax.random.PRNGKey(1)}, x)
    return mod, variables["params"], x


def _reference_moe(params, x, cfg, cap):
    """Brute-force per-token reference: same routing rules, Python loops.

    Capacity fills CHOICE-major (all top-1 assignments across the sequence
    claim slots before any top-2 — GShard's offset-by-previous-round
    semantics, which the einsum implementation reproduces via the running
    ``counts``). Dropped assignments still occupy positions."""
    e, k = cfg.moe_experts, cfg.moe_top_k
    logits = x @ params["router"]["kernel"]
    out = np.zeros_like(np.asarray(x))
    for b in range(x.shape[0]):
        fill = np.zeros(e, dtype=int)
        for j in range(k):
            for t in range(x.shape[1]):
                p = np.asarray(jax.nn.softmax(logits[b, t]))
                top = np.argsort(-p, kind="stable")[:k]
                gates = p[top] / p[top].sum()
                ei = top[j]
                kept = fill[ei] < cap
                fill[ei] += 1
                if not kept:
                    continue
                h = np.asarray(x[b, t]) @ np.asarray(params["wi"][ei]) + np.asarray(params["bi"][ei])
                h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
                y = h @ np.asarray(params["wo"][ei]) + np.asarray(params["bo"][ei])
                out[b, t] += gates[j] * y
    return out


@pytest.mark.parametrize("dispatch", ["einsum", "sort"])
@pytest.mark.parametrize("capacity_factor", [2.0, 0.4])
def test_moe_matches_brute_force_reference(tiny_model_cfg, capacity_factor, dispatch):
    """cf=2.0: no overflow; cf=0.4 with k=2: experts overflow, so WHICH
    assignments get dropped (choice-major order) is part of the contract —
    for BOTH dispatch backends."""
    from dtc_tpu.models.gpt import moe_capacity

    cfg = _moe_cfg(tiny_model_cfg, compute_dtype="float32",
                   moe_capacity_factor=capacity_factor, moe_dispatch=dispatch)
    mod, params, x = _init_moe(cfg, b=2, t=16)
    cap = moe_capacity(16, cfg)
    got = mod.apply({"params": params}, x)
    want = _reference_moe(params, x, cfg, cap)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("capacity_factor", [2.0, 0.6])
def test_sort_matches_einsum_outputs_grads_and_aux(tiny_model_cfg, capacity_factor):
    """The dispatch switch is a pure execution-strategy A/B: same params,
    same input -> same output (fp-roundoff tolerance: the k gate-weighted
    contributions sum in a different order), BITWISE-equal aux loss, and
    matching parameter gradients — including through the capacity-drop
    regime, where the two backends must drop the exact same assignments."""
    cfg_e = _moe_cfg(tiny_model_cfg, compute_dtype="float32",
                     moe_capacity_factor=capacity_factor)
    cfg_s = dataclasses.replace(cfg_e, moe_dispatch="sort")
    mod, params, x = _init_moe(cfg_e, b=2, t=16)
    y_e, mut_e = mod.apply({"params": params}, x, mutable=["aux_loss"])
    y_s, mut_s = MoEMLP(cfg_s).apply({"params": params}, x, mutable=["aux_loss"])
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=1e-6, atol=1e-6)
    aux_e = np.asarray(jax.tree.leaves(mut_e["aux_loss"])[0])
    aux_s = np.asarray(jax.tree.leaves(mut_s["aux_loss"])[0])
    np.testing.assert_array_equal(aux_s, aux_e)  # shared routing: bitwise

    def loss(p, cfg):
        return jnp.sum(MoEMLP(cfg).apply({"params": p}, x) ** 2)

    g_e = jax.grad(loss)(params, cfg_e)
    g_s = jax.grad(loss)(params, cfg_s)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_e), jax.tree.leaves(g_s)
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_routing_decisions_identical_across_backends(tiny_model_cfg):
    """The contract the config switch rests on, asserted at the router
    output: both backends consume ONE Routing (same expert ids, same slot
    positions, same keep mask) and the permutation encodings agree —
    slot_to_token (sort) is the transpose of the dispatch one-hots
    (einsum)."""
    from dtc_tpu.models.gpt import moe_capacity
    from dtc_tpu.ops import moe_dispatch as md

    cfg = _moe_cfg(tiny_model_cfg, compute_dtype="float32",
                   moe_capacity_factor=0.6)
    mod, params, x = _init_moe(cfg, b=2, t=16)
    cap = moe_capacity(16, cfg)
    logits = x @ params["router"]["kernel"]
    r = md.top_k_routing(jax.nn.softmax(logits, axis=-1), cfg.moe_top_k, cap)

    dispatch, combine = md.dispatch_combine_tensors(r, cap)
    src, filled = md.slot_to_token(r, cap)
    b, t, e = r.probs.shape
    disp = np.asarray(dispatch)
    src_n, filled_n = np.asarray(src).reshape(b, e, cap), np.asarray(filled)
    for bi in range(b):
        for ei in range(e):
            for c in range(cap):
                col = disp[bi, :, ei, c]
                if filled_n[bi, ei, c]:
                    # Exactly one token routed into this slot, and the
                    # sort backend's slot map names the same token.
                    assert col.sum() == 1.0
                    assert col[src_n[bi, ei, c]] == 1.0
                else:
                    assert col.sum() == 0.0
    # Combine weights are the gates of kept assignments only.
    np.testing.assert_allclose(
        np.asarray(combine).sum(axis=(2, 3)),
        np.asarray(jnp.sum(r.gates * r.keep, axis=-1)), rtol=1e-6)


def test_single_expert_equals_dense_ffn(tiny_model_cfg):
    """E=1, k=1, capacity >= T: the router must gate 1.0 into the one
    expert and the output equals the plain FFN with the same weights."""
    cfg = _moe_cfg(tiny_model_cfg, moe_experts=1, moe_top_k=1,
                   moe_capacity_factor=1.0, compute_dtype="float32")
    mod, params, x = _init_moe(cfg)
    got = mod.apply({"params": params}, x)
    want = jax.nn.gelu(x @ params["wi"][0] + params["bi"][0]) @ params["wo"][0] + params["bo"][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_capacity_overflow_drops_tokens(tiny_model_cfg):
    """With capacity 1 slot/expert almost all tokens must be dropped —
    dropped tokens contribute exactly zero (the residual carries them)."""
    cfg = _moe_cfg(tiny_model_cfg, moe_experts=2, moe_top_k=1,
                   moe_capacity_factor=0.01, compute_dtype="float32")
    mod, params, x = _init_moe(cfg, b=1, t=16)
    got = np.asarray(mod.apply({"params": params}, x))
    zero_rows = np.sum(np.all(got == 0.0, axis=-1))
    assert zero_rows >= 14, f"expected most tokens dropped, {zero_rows} zero rows"


def test_aux_loss_sowed_and_bounded(tiny_model_cfg):
    cfg = _moe_cfg(tiny_model_cfg)
    mod, params, x = _init_moe(cfg)
    _, mut = mod.apply({"params": params}, x, mutable=["aux_loss"])
    (aux,) = jax.tree.leaves(mut["aux_loss"])
    # Perfectly balanced top-k routing gives coef * E * sum(f*P) = coef;
    # collapse to one expert gives up to coef * E.
    assert 0.0 < float(aux) <= cfg.moe_aux_coef * cfg.moe_experts + 1e-6


def test_ep_param_specs(tiny_model_cfg):
    from jax.sharding import PartitionSpec as P

    from dtc_tpu.parallel.sharding import DEFAULT_RULES, param_specs

    cfg = _moe_cfg(tiny_model_cfg)
    model = GPT(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 8), jnp.int32), train=False
    )["params"]
    specs = param_specs(params, DEFAULT_RULES)
    moe = specs["stage"]["blocks"]["Block_0"]["moe"]
    assert moe["wi"] == P(None, "model", None, None)
    assert moe["wo"] == P(None, "model", None, None)
    assert moe["router"]["kernel"] == P(None, None, None)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == param_count(cfg)


@pytest.mark.parametrize("dispatch", ["einsum", "sort"])
def test_moe_trains_and_learns(tiny_model_cfg, opt_cfg, train_cfg_factory, dispatch):
    """End-to-end on a DP x EP mesh (experts sharded over model=2): loss
    must drop on the learnable synthetic stream and stay finite — both
    dispatch backends."""
    cfg = _moe_cfg(tiny_model_cfg, moe_dispatch=dispatch)
    tc = train_cfg_factory(
        "3d", steps=8, log_every=1, mesh=MeshConfig(pipe=1, data=4, model=2)
    )
    res = train(tc, cfg, opt_cfg)
    assert np.all(np.isfinite(res.losses))
    assert res.losses[-1] < res.losses[0], "MoE run failed to learn"


def test_sort_dispatch_trains_loss_parity_with_einsum(
    tiny_model_cfg, opt_cfg, train_cfg_factory
):
    """The A/B's correctness leg: a sort-dispatch run must reproduce the
    einsum run's loss curve to golden-class tolerance — same seed, same
    stream, same routing — on both a plain DP mesh and the DP x EP mesh
    (where the collectives differ too, tests/test_collectives_hlo.py)."""
    cfg_e = _moe_cfg(tiny_model_cfg)
    cfg_s = _moe_cfg(tiny_model_cfg, moe_dispatch="sort")
    dp_kw = dict(steps=5, log_every=1)
    r_e = train(train_cfg_factory("dp", **dp_kw), cfg_e, opt_cfg)
    r_s = train(train_cfg_factory("dp", **dp_kw), cfg_s, opt_cfg)
    np.testing.assert_allclose(r_s.losses, r_e.losses, rtol=5e-5, atol=5e-5)

    ep_kw = dict(steps=3, log_every=1, mesh=MeshConfig(pipe=1, data=4, model=2))
    e_e = train(train_cfg_factory("3d", **ep_kw), cfg_e, opt_cfg)
    e_s = train(train_cfg_factory("3d", **ep_kw), cfg_s, opt_cfg)
    np.testing.assert_allclose(e_s.losses, e_e.losses, rtol=5e-5, atol=5e-5)


#: `moe_dispatch: sort` under a `pipe > 1` mesh ABORTS the interpreter
#: inside XLA's SPMD partitioner on the installed jax (run=False: an abort
#: is a dead xdist worker that is handed the same test again, not a failed
#: test — it starved every test queued behind it). The check is in the
#: partitioner the TPU compile shares, so this is filed in ROADMAP.md as
#: a defect of sort dispatch under the pipeline, not as a CPU quirk.
_SORT_UNDER_PIPELINE = pytest.param(
    "sort",
    marks=pytest.mark.xfail(
        run=False,
        reason="aborts in XLA's SPMD partitioner: spmd_partitioner_util.cc:495 "
        "Check failed: partition_group_list.num_replica_groups() * ... == "
        "device_groups.num_devices_per_group() "
        "(PartitionGatherExplicitBatchDimensions)",
    ),
)


@pytest.mark.parametrize("dispatch", ["einsum", _SORT_UNDER_PIPELINE])
def test_moe_under_pipeline_matches_dp_at_m1(tiny_model_cfg, opt_cfg,
                                             train_cfg_factory, dispatch):
    """PP x EP: with one microbatch the pipeline's per-stage aux sum equals
    the GSPMD step's full-batch aux exactly, so losses must match a DP run
    (with M > 1 the aux is a mean over microbatch-local statistics — a
    different, equally valid estimator). Both dispatch backends must
    compose with the pipeline's partially-manual region."""
    cfg = _moe_cfg(tiny_model_cfg, moe_dispatch=dispatch)
    dp = train(train_cfg_factory("dp", steps=3, log_every=1), cfg, opt_cfg)
    pp = train(
        train_cfg_factory(
            "3d", steps=3, log_every=1, pp_microbatches=1,
            mesh=MeshConfig(pipe=2, data=2, model=2),
        ),
        cfg, opt_cfg,
    )
    np.testing.assert_allclose(pp.losses, dp.losses, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("dispatch", ["einsum", _SORT_UNDER_PIPELINE])
def test_moe_under_pipeline_1f1b_matches_gpipe(tiny_model_cfg, opt_cfg,
                                               train_cfg_factory, dispatch):
    """Both pipeline schedules thread the MoE aux loss (GPipe: through the
    clock scan; 1F1B: explicit vjp seed) — they must agree, for both
    dispatch backends."""
    cfg = _moe_cfg(tiny_model_cfg, moe_dispatch=dispatch)
    kw = dict(steps=3, log_every=1, pp_microbatches=2,
              mesh=MeshConfig(pipe=2, data=2, model=2))
    gp = train(train_cfg_factory("3d", **kw), cfg, opt_cfg)
    ob = train(train_cfg_factory("3d", pp_schedule="1f1b", **kw), cfg, opt_cfg)
    np.testing.assert_allclose(ob.losses, gp.losses, rtol=5e-4, atol=5e-4)


def test_moe_config_validation():
    base = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
                max_seq_len=32)
    with pytest.raises(ValueError, match="moe_top_k"):
        ModelConfig(**base, moe_experts=2, moe_top_k=3)
    with pytest.raises(ValueError, match="moe_experts"):
        ModelConfig(**base, moe_experts=-1)
    with pytest.raises(ValueError, match="moe_dispatch"):
        ModelConfig(**base, moe_experts=2, moe_dispatch="radix")


@pytest.mark.parametrize("dispatch", ["einsum", "sort"])
def test_moe_decode_matches_full_forward(tiny_model_cfg, dispatch):
    """KV-cache decode works with MoE blocks (per-token routing, capacity
    ceil(k*cf/E) >= 1): cached greedy generation must equal the no-cache
    full-forward oracle — both dispatch backends."""
    from dtc_tpu.generate import generate

    cfg = _moe_cfg(tiny_model_cfg, compute_dtype="float32",
                   moe_dispatch=dispatch)
    model = GPT(cfg)
    x = jnp.ones((2, 4), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(7)}, x, train=False)["params"]
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    got = generate(model, params, prompt, 6)

    toks = prompt
    want = []
    for _ in range(6):
        logits = model.apply({"params": params}, toks, train=False)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        want.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jnp.stack(want, 1)))
