"""Rotary positions in the packed layout (``ops/rotary.py``): the kernel,
interpreted on the CPU as the flash tests run theirs, against
``models/pattern.rotary`` — the oracle, and the path of every layer the
gate does not take; the gate and the plan; the Ouro toy model with the
packed path forced against ``benchmark/reference_ouro.py``.

Tolerance of the op. Both sides compute ``x * cos + x' * sin`` in float32
and round once. The values are equal to the last bit wherever the backend
keeps the two products and the sum apart; XLA's CPU backend contracts one
product into a fused multiply-add, and which one depends on the fusion it
sits in, so 2 elements of 262,144 (bf16, the first case below) land on the
other side of a rounding: the forward is held to 1 ulp of the dtype (and a
float32 ulp of the operands, where the two products cancel). The
gradient of ``rotary`` rounds the two products one by one (autodiff adds
``dy * cos`` and the transposed ``dy * sin`` as separate arrays), so the
gradients are held to an ulp at the cotangent's magnitude.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.models import pattern
from dtc_tpu.ops import rotary as rotary_ops
from dtc_tpu.ops.rotary import packed_rotary, supports_packed_rotary
from tests.pattern_helpers import (  # noqa: F401  (ouro_cfg is a fixture)
    OURO, TIGHT, as_model, cell_cfg, close, ouro_cfg, program_params, weights,
)

THETA, D = 1e6, 128
#: (rows, positions, heads): the issue's small case, and the cell's head
#: count at a length that takes two row tiles of the plan
SHAPES = {"2x256x4": (2, 256, 4), "1x512x16": (1, 512, 16)}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
CASES = [(s, d) for s in SHAPES for d in DTYPES]


def _oracle(x, h):
    b, t, n = x.shape
    return pattern.rotary(x.reshape(b, t, h, n // h), THETA, 1.0).astype(x.dtype).reshape(b, t, n)


def _draw(shape, dtype, seed):
    b, t, h = SHAPES[shape]
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, h * D), jnp.float32).astype(DTYPES[dtype])


def _ulp(x) -> np.ndarray:
    """The spacing of ``x``'s dtype at each element's magnitude."""
    f = np.abs(np.asarray(x.astype(jnp.float32)))
    return np.spacing(f) * (2.0 ** 16 if x.dtype == jnp.bfloat16 else 1.0)


def _within(got, want, ulps, of):
    """``got`` within ``ulps`` of ``want``'s dtype, and a float32 ulp at the
    magnitude of the operands ``of`` (where the two products cancel, the sum
    is small against the products' own rounding)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    gap = np.abs(np.asarray(got.astype(jnp.float32)) - np.asarray(want.astype(jnp.float32)))
    floor = 2.0 * np.spacing(np.float32(jnp.max(jnp.abs(of.astype(jnp.float32)))))
    assert np.all(gap <= ulps * _ulp(want) + floor)


@pytest.mark.parametrize("shape,dtype", CASES)
def test_packed_rotary_is_rotary_on_the_four_d_view(shape, dtype):
    h = SHAPES[shape][2]
    assert supports_packed_rotary(D, 1.0, h, SHAPES[shape][1], jnp.dtype(DTYPES[dtype]).itemsize)
    q, k = _draw(shape, dtype, 0), _draw(shape, dtype, 1)
    got = jax.jit(lambda q, k: packed_rotary(q, k, THETA, D))(q, k)
    for x, y in zip((q, k), got):
        want = jax.jit(lambda x: _oracle(x, h))(x)
        _within(y, want, 1, of=x)
        assert np.mean(np.asarray(y != want)) < 1e-4    # but for an FMA's rounding, the same bits


@pytest.mark.parametrize("shape,dtype", CASES)
def test_gradient_through_packed_rotary_is_the_gradient_through_rotary(shape, dtype):
    h = SHAPES[shape][2]
    q, k, cq, ck = (_draw(shape, dtype, s) for s in range(4))

    def loss(fn):
        def f(q, k):
            yq, yk = fn(q, k)
            return jnp.sum(yq.astype(jnp.float32) * cq) + jnp.sum(yk.astype(jnp.float32) * ck)
        return jax.jit(jax.grad(f, argnums=(0, 1)))

    got = loss(lambda q, k: packed_rotary(q, k, THETA, D))(q, k)
    want = loss(lambda q, k: (_oracle(q, h), _oracle(k, h)))(q, k)
    for g, w, c in zip(got, want, (cq, ck)):
        assert g.dtype == w.dtype == DTYPES[dtype]
        gap = np.abs(np.asarray(g.astype(jnp.float32)) - np.asarray(w.astype(jnp.float32)))
        assert np.max(gap) <= float(np.max(_ulp(c)))


@pytest.mark.parametrize("shape,dtype", CASES)
def test_the_backward_turns_the_forward_back(shape, dtype):
    """A rotation and its return are the identity to rounding: the VJP is the
    rotation by the negated angle, and keeps nothing of the forward."""
    q, k = _draw(shape, dtype, 0), _draw(shape, dtype, 1)
    out, back = jax.vjp(lambda q, k: packed_rotary(q, k, THETA, D), q, k)
    # two roundings to the dtype, one a pass, each at the magnitude of a pair's
    # larger element: the return mixes the pair's errors
    for x, y in zip((q, k), back(out)):
        assert y.dtype == x.dtype
        gap = np.abs(np.asarray(y.astype(jnp.float32)) - np.asarray(x.astype(jnp.float32)))
        assert np.max(gap) <= 2 * float(np.max(_ulp(x)))
    assert rotary_ops._fwd(q, k, THETA, D)[1] is None


@pytest.mark.parametrize("head_dim,fraction,heads,t,holds", [
    (128, 1.0, 16, 4096, True),      # the Ouro cell
    (128, 1.0, 4, 256, True),
    (128, 0.5, 16, 4096, False),     # a partial fraction
    (256, 0.25, 16, 8192, False),    # qwen3-next: 64 of 256
    (64, 1.0, 32, 8192, False),      # two heads to a lane tile: the halves' swap would cross heads
    (256, 1.0, 16, 4096, False),
    (128, 1.0, 16, 4100, False),     # no whole sublane tile of rows divides the positions
], ids=["ouro", "toy", "half", "qwen3", "head64", "head256", "ragged"])
def test_the_gate_takes_one_lane_tile_a_head_rotated_whole(head_dim, fraction, heads, t, holds):
    plan = supports_packed_rotary(head_dim, fraction, heads, t, 2)
    assert (plan is not None) == holds
    if holds:
        assert t % plan["rows"] == 0 and plan["rows"] % 16 == 0 and plan["fits"]
        assert plan["bytes"] <= plan["budget_bytes"] and plan["bytes"] < plan["vmem_limit_bytes"]


def test_the_plan_of_the_cell_is_256_rows_a_step():
    plan = supports_packed_rotary(128, 1.0, 16, 4096, 2)
    # q and k in and out, 16 heads of 128 in bf16, + the two float32 tables, double-buffered
    assert plan["rows"] == 256 and plan["bytes"] == 2 * (4 * 256 * 2048 * 2 + 2 * 256 * 128 * 4)


# ---------------------------------------------------------------------------
# the gate in the layer, and the plan


@pytest.mark.parametrize("config,kind,want", [
    ("ouro-2.6b", "attn", "packed"),
    ("qwen3-next-80b-a3b", "gated_attn", "xla"),    # q / k normed, KV groups, 64 of 256 rotated, an output gate
    ("lfm2-8b-a1b", "attn", "xla"),                 # q / k normed, KV groups, two heads to a lane tile
], ids=["ouro", "qwen3", "lfm2"])
def test_layer_plan_says_where_q_and_k_are_rotated(monkeypatch, config, kind, want):
    from dtc_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)     # `attention: auto` as on the chip
    plan = pattern.layer_plan(cell_cfg(config)[0])[kind]
    assert plan["kernel"] == "flash" and plan["rotary"] == want
    if want == "packed":
        assert plan["rotary_tile"] == {"rows": 256, "vmem_limit_bytes": 18_087_936}
    else:
        assert "rotary_tile" not in plan


@pytest.mark.parametrize("change", [
    {"attention": "dense"}, {"qk_norm": True}, {"n_kv_heads": 2}, {"rope_fraction": 0.5},
    {"attn_head_dim": 64}, {"layer_pattern": ("gated_attn+swiglu",)},
], ids=lambda c: next(iter(c)))
def test_off_the_gate_the_layer_builds_the_xla_rotary(ouro_cfg, change):
    """Each condition of the gate alone sends the layer back to ``rotary`` on
    the 4-D view: no kernel of this file in its jaxpr."""
    packed = dataclasses.replace(ouro_cfg, attention="flash", attn_head_dim=128)
    assert pattern.layer_plan(packed)["attn"]["rotary"] == "packed"
    cfg = dataclasses.replace(packed, **change)
    kind = cfg.layer_pattern[0].split("+")[0]
    assert pattern.layer_plan(cfg)[kind]["rotary"] == "xla"
    if cfg.attention != "dense":
        return      # the interpreted flash kernels are slow to trace; the gate is one function
    layer = pattern.MIXERS[kind][0](cfg)
    x = jnp.zeros((1, cfg.max_seq_len, cfg.d_model), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    assert "rotary_packed" not in str(jax.make_jaxpr(layer.apply)(params, x))


def test_on_a_mesh_the_kernel_sits_in_a_manual_region(ouro_cfg):
    """XLA cannot partition a Mosaic kernel (interpret mode never shows it):
    with the rows over two devices the call is inside a ``shard_map``."""
    from flax import linen as nn

    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    cfg = dataclasses.replace(ouro_cfg, attention="flash", attn_head_dim=128)
    layer = pattern.Attention(cfg, gated=False)
    x = jnp.zeros((2, cfg.max_seq_len, cfg.d_model), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x[:1])
    mesh = build_mesh((1, 2, 1), devices=jax.devices()[:2])
    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        jaxpr = jax.make_jaxpr(layer.apply)(params, x)
    outer = [e for e in jaxpr.eqns if "rotary_packed" in str(e)]
    assert outer and all(e.primitive.name == "shard_map" for e in outer)
    # each device on its own row of q and k (the two tables come in whole)
    assert [v.aval.shape for v in outer[0].params["jaxpr"].invars][-2:] == [(1, cfg.max_seq_len, 4 * 128)] * 2


# ---------------------------------------------------------------------------
# the toy model with the packed path taken, against the reference


LEAVES = sorted(OURO.leaf_names.values())


@pytest.fixture(scope="module")
def both(ouro_cfg):
    """The Ouro preset with flash attention forced and heads of 128 (the
    gate's shape; the preset's are 16), against the reference on the same
    weights and rows."""
    cfg = dataclasses.replace(ouro_cfg, attention="flash", attn_head_dim=128)
    assert pattern.layer_plan(cfg)["attn"]["rotary"] == "packed"
    w = weights(cfg, seed=5, family=OURO)
    batch = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, cfg.max_seq_len + 1)),
                        jnp.int32)
    model = pattern.build_model(cfg)

    def loss(p):
        return model.apply({"params": p}, batch[:, :-1], train=True, targets=batch[:, 1:],
                           mutable=["counters"])

    with jax.default_matmul_precision("highest"):
        assert "rotary_packed" in str(jax.make_jaxpr(loss)(program_params(w, OURO)))
        (got, _), grads = jax.value_and_grad(loss, has_aux=True)(program_params(w, OURO))
        want, ref_grads = jax.value_and_grad(OURO.ref.loss_fn)(w, batch[:, :-1], batch[:, 1:], as_model(cfg))
    grads = {OURO.leaf_names["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
             for path, leaf in jax.tree_util.tree_leaves_with_path(grads)}
    return types.SimpleNamespace(loss=got, want=want, grads=grads, ref_grads=ref_grads)


def test_loss_with_the_packed_rotary_is_the_reference_s(both):
    assert float(both.loss) == pytest.approx(float(both.want), rel=1e-5)
    assert set(both.grads) == set(both.ref_grads) == set(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_with_the_packed_rotary_is_the_reference_s(both, leaf):
    assert float(jnp.max(jnp.abs(both.ref_grads[leaf]))) > 0
    close(both.grads[leaf], both.ref_grads[leaf], TIGHT)
