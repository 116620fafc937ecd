"""Plain float32 reference of the Qwen3-Next block family and its AdamW step.

The interface of ``reference.py`` (``leaf_shapes``, ``make_weights``,
``leaf_norms``, ``run_steps``, the int8 control) for a model described by a
layer pattern: Gated DeltaNet layers and gated full-attention layers, each
followed by an expert layer with a shared expert. Nothing of the program is
imported; the clip and AdamW arithmetic is ``reference.train_step``'s, around
this file's loss. Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.

The equations, literally (x the residual stream, d its width, eps
``norm_eps``):

- ``rms(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)``. A layer is
  ``x += mixer(rms(x; w1)); x += moe(rms(x; w2))``; position i of a period is
  what ``layer_pattern[i]`` says. After the last layer a final ``rms`` and an
  untied head without bias; token embedding only.
- **Gated full attention.** ``q_proj`` gives, per query head, a query and a
  gate of ``head_dim`` each; ``k_proj``, ``v_proj`` the KV heads. q and k are
  ``rms``-normed over the head, rotated on the first ``rope_fraction`` of the
  head (``x cos + rotate_half(x) sin``, ``rotate_half(x) = [-x2, x1]``,
  theta ``rope_theta``), a dense causal softmax of ``q k^T / sqrt(head_dim)``
  with each KV head serving ``n_heads / n_kv_heads`` query heads;
  ``out = o_proj(attn * sigmoid(gate))``.
- **Gated DeltaNet**, as the token-by-token recurrence. ``in_proj_qkvz`` to
  (q, k, v, z), ``in_proj_ba`` to (b, a); a depthwise causal convolution of
  ``gdn_conv_width`` taps and SiLU over (q, k, v); ``beta = sigmoid(b)``,
  ``g = -exp(A_log) * softplus(a + dt_bias)``; q and k L2-normalised over the
  head (``x * rsqrt(sum x^2 + 1e-6)``), q scaled by ``1/sqrt(dk)``, each key
  head serving ``value_heads / key_heads`` value heads. Per head, S from 0:
  ``S <- S exp(g_t); delta = (v_t - S^T k_t) beta_t; S <- S + k_t delta^T;
  o_t = S^T q_t``. Then ``o * rsqrt(mean(o^2) + eps) * w_n`` over the head,
  times ``silu(z)``, then ``out_proj``.
- **Expert layer.** ``p = softmax(x W_r)`` over all ``moe_experts``; the
  ``moe_top_k`` largest renormalised to sum 1; the result is the sum over the
  chosen experts THAT ARE HELD (``moe_experts_held`` from ``moe_expert_rank *
  held`` on: the share of one chip of the deployment) of ``p_e down_e(silu(
  gate_e x) * up_e x)``, plus ``sigmoid(x w_s) * shared(x)``. No capacity.
  The held experts run as a loop over them, each on every token, weighted by
  a gate that is zero where the token did not choose it.

Column order inside the fused projections is this file's (q | k | v | z,
b | a, per query head query | gate); the configuration's ``assumed`` says so.

Memory at the timed size (2 x 8192 tokens): every layer under
``jax.checkpoint``; the recurrence in segments of ``SEGMENT`` positions, each
a checkpoint (8192 saved states would be 17 GB); attention over blocks of
``Q_BLOCK`` query rows; experts one at a time; the head over chunks. That is
blocking, not another algorithm.

``matmul="int8"``: the control, as in ``reference.py`` — every dense
projection (the mixers', the experts', the shared expert's, the head's; not
the router, which the configuration keeps in float32) takes operands rounded
to int8, one absmax scale per vector of the contraction.
"""

from __future__ import annotations

import functools
import os
import sys
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

import reference  # noqa: E402  (the GPT-2 reference: optimizer arithmetic, control, seeds)
from reference import B1, HEAD_CHUNK, _head_loss_sum, _mm, leaf_norms, padded_vocab, seed_words  # noqa: E402,F401

SEGMENT = 128   # positions of the recurrence per checkpointed segment
Q_BLOCK = 512   # query rows per block of the dense attention


def _kinds(model: dict) -> list[tuple[str, str]]:
    return [tuple(entry.split("+")) for entry in model["layer_pattern"]]


def _held(model: dict) -> int:
    return int(model.get("moe_experts_held") or model["moe_experts"])


def leaf_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Reference leaf name -> shape. Layer leaves are ``blocks.<position in
    the period>.<leaf>``, stacked over periods."""
    d, v = model["d_model"], padded_vocab(model)
    periods = model["n_layers"] // len(model["layer_pattern"])
    h, hk, hd = model["n_heads"], model.get("n_kv_heads") or model["n_heads"], model["attn_head_dim"]
    nk = model["gdn_key_heads"] * model["gdn_key_dim"]
    nv = model["gdn_value_heads"] * model["gdn_value_dim"]
    f, fs = model["moe_d_ff"], model["moe_shared_d_ff"]
    mixers = {
        "gated_attn": {
            "attn.q.w": (d, h * 2 * hd), "attn.k.w": (d, hk * hd), "attn.v.w": (d, hk * hd),
            "attn.q_norm.w": (hd,), "attn.k_norm.w": (hd,), "attn.o.w": (h * hd, d),
        },
        "gdn": {
            "gdn.qkvz.w": (d, 2 * nk + 2 * nv), "gdn.ba.w": (d, 2 * model["gdn_value_heads"]),
            "gdn.conv.w": (2 * nk + nv, model["gdn_conv_width"]),
            "gdn.A_log": (model["gdn_value_heads"],), "gdn.dt_bias": (model["gdn_value_heads"],),
            "gdn.norm.g": (model["gdn_value_dim"],), "gdn.out.w": (nv, d),
        },
    }
    ffns = {
        "moe_shared": {
            "moe.router.w": (d, model["moe_experts"]),
            "moe.gate.w": (_held(model), d, f), "moe.up.w": (_held(model), d, f),
            "moe.down.w": (_held(model), f, d),
            "moe.shared.gate.w": (d, fs), "moe.shared.up.w": (d, fs), "moe.shared.down.w": (fs, d),
            "moe.shared_gate.w": (d, 1),
        },
    }
    shapes: dict[str, tuple[int, ...]] = {"wte": (v, d), "norm_f.w": (d,), "head.w": (d, v)}
    for i, (mixer, ffn) in enumerate(_kinds(model)):
        layer = {"norm1.w": (d,), "norm2.w": (d,), **mixers[mixer], **ffns[ffn]}
        shapes.update({f"blocks.{i}.{k}": (periods, *s) for k, s in layer.items()})
    return shapes


def make_weights(model: dict, words: jax.Array) -> dict[str, jax.Array]:
    """Every weight from the seed, on the device, float32: normal(0, 0.02)
    for matrices, the embedding and the zero-centred norm weights (a trained
    model's are not 0, and 0 would hide a dropped ``1 +``); 1 + that for the
    DeltaNet output norm's plain gain; the convolution's taps normal(0, 0.3)
    (the published start is uniform(+-0.5)); ``A_log = log(uniform(1, 16))``
    as published, ``dt_bias`` uniform(-4, 0), so that heads forget at rates
    from a few percent to all of the state a token."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    out = {}
    for name, shape in leaf_shapes(model).items():
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if name.endswith("A_log"):
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            w = jax.random.uniform(k, shape, jnp.float32, -4.0, 0.0)
        elif name.endswith("conv.w"):
            w = 0.3 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
            if name.endswith(".g"):
                w = 1.0 + w
        out[name] = w
    return out


# ---------------------------------------------------------------------------
# the layers


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotate(x, theta: float, fraction: float):
    """Rotary positions on the first ``fraction`` of the head, (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    rot = int(d * fraction)
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)[None, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., : rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


def attn_layer(p: dict, x, model: dict, mm=jnp.matmul):
    """Gated full attention on normed input ``x`` (B, T, d)."""
    b, t, _ = x.shape
    h, hk, hd = model["n_heads"], model.get("n_kv_heads") or model["n_heads"], model["attn_head_dim"]
    eps = model["norm_eps"]
    qg = mm(x, p["attn.q.w"]).reshape(b, t, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = mm(x, p["attn.k.w"]).reshape(b, t, hk, hd)
    v = mm(x, p["attn.v.w"]).reshape(b, t, hk, hd)
    q = rotate(rms(q, p["attn.q_norm.w"], eps), model["rope_theta"], model["rope_fraction"])
    k = rotate(rms(k, p["attn.k_norm.w"], eps), model["rope_theta"], model["rope_fraction"])
    k, v = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / np.sqrt(hd)
        rows = i * qb + jnp.arange(qb)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    a = jax.lax.map(block, jnp.arange(t // qb))                    # (blocks, B, qb, H, hd)
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, h, hd)
    return mm((a * jax.nn.sigmoid(gate)).reshape(b, t, h * hd), p["attn.o.w"])


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: (B, T, H, *) in, (B, T, H, dv) out."""
    b, t, h, dk = q.shape
    seg = SEGMENT if t % SEGMENT == 0 else t

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None, None]
        delta = (vt - jnp.einsum("bhkv,bhk->bhv", state, kt)) * bt[..., None]
        state = state + kt[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(step, state, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(t // seg, seg, *a.shape[:1], *a.shape[2:])
               for a in (q, k, v, g, beta))
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(segment, s0, xs)                          # (T/seg, seg, B, H, dv)
    return jnp.moveaxis(out.reshape(t, b, h, -1), 0, 1)


def gdn_layer(p: dict, x, model: dict, mm=jnp.matmul):
    """Gated DeltaNet on normed input ``x`` (B, T, d)."""
    b, t, _ = x.shape
    hk, hv = model["gdn_key_heads"], model["gdn_value_heads"]
    dk, dv = model["gdn_key_dim"], model["gdn_value_dim"]
    nk, nv = hk * dk, hv * dv
    qkvz = mm(x, p["gdn.qkvz.w"])
    ba = mm(x, p["gdn.ba.w"])
    qkv, z = qkvz[..., : 2 * nk + nv], qkvz[..., 2 * nk + nv:].reshape(b, t, hv, dv)
    w = p["gdn.conv.w"]
    width = w.shape[1]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j: j + t] * w[:, j] for j in range(width)))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["gdn.A_log"]) * jax.nn.softplus(ba[..., hv:] + p["gdn.dt_bias"])

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)

    q = unit(qkv[..., :nk].reshape(b, t, hk, dk)) / np.sqrt(dk)
    k = unit(qkv[..., nk: 2 * nk].reshape(b, t, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    o = delta_rule(q, k, qkv[..., 2 * nk:].reshape(b, t, hv, dv), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + model["norm_eps"])
    o = o * p["gdn.norm.g"] * jax.nn.silu(z)
    return mm(o.reshape(b, t, nv), p["gdn.out.w"])


def routed_gates(p: dict, x, model: dict):
    """(tokens, experts) float32: the renormalised gate of each token's
    chosen experts, zero elsewhere. The router stays plain float32."""
    probs = jax.nn.softmax(jnp.matmul(x, p["moe.router.w"]), axis=-1)
    top, idx = jax.lax.top_k(probs, model["moe_top_k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32) * top[..., None], axis=-2)


def moe_layer(p: dict, x, model: dict, mm=jnp.matmul, *, first: int | None = None,
              shared: bool = True):
    """The expert layer on normed input ``x`` (B, T, d): the held experts'
    part of the routed sum (those of ``p``'s expert leaves, standing for
    experts ``first`` on; default: the configured share) plus, with
    ``shared``, the gated shared expert."""
    b, t, d = x.shape
    tokens = x.reshape(b * t, d)
    held = p["moe.gate.w"].shape[0]
    if first is None:
        first = int(model.get("moe_expert_rank", 0)) * held
    gates = jax.lax.dynamic_slice_in_dim(routed_gates(p, tokens, model), first, held, axis=1)

    @jax.checkpoint
    def one(acc, e):
        wg, wu, wd, ge = e
        return acc + ge[:, None] * mm(jax.nn.silu(mm(tokens, wg)) * mm(tokens, wu), wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(tokens),
                        (p["moe.gate.w"], p["moe.up.w"], p["moe.down.w"], gates.T))
    if shared:
        s = mm(jax.nn.silu(mm(tokens, p["moe.shared.gate.w"])) * mm(tokens, p["moe.shared.up.w"]),
               p["moe.shared.down.w"])
        y = y + jax.nn.sigmoid(jnp.matmul(tokens, p["moe.shared_gate.w"])) * s
    return y.reshape(b, t, d)


MIXERS = {"gated_attn": attn_layer, "gdn": gdn_layer}
FFNS = {"moe_shared": moe_layer}


def layer(p: dict, h, model: dict, kinds: tuple[str, str], mm=jnp.matmul):
    eps = model["norm_eps"]
    h = h + MIXERS[kinds[0]](p, rms(h, p["norm1.w"], eps), model, mm)
    return h + FFNS[kinds[1]](p, rms(h, p["norm2.w"], eps), model, mm)


def layer_params(params: dict, position: int) -> dict:
    """The stacked leaves of one position of the period, prefix removed."""
    prefix = f"blocks.{position}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def loss_fn(params: dict, x: jax.Array, y: jax.Array, model: dict,
            matmul: str = "float32") -> jax.Array:
    """Mean next-token cross-entropy of rows ``x`` against targets ``y``,
    over the unpadded vocabulary."""
    mm = _mm(matmul)
    b, t = x.shape
    h = params["wte"][x]
    kinds = _kinds(model)

    def period(h, stacked):
        for i, kind in enumerate(kinds):
            h = jax.checkpoint(functools.partial(layer, model=model, kinds=kind, mm=mm))(stacked[i], h)
        return h, None

    h, _ = jax.lax.scan(period, h, [layer_params(params, i) for i in range(len(kinds))])
    h = rms(h, params["norm_f.w"], model["norm_eps"])
    c = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t
    hs = jnp.moveaxis(h.reshape(b, t // c, c, -1), 1, 0)
    ys = jnp.moveaxis(y.reshape(b, t // c, c), 1, 0)
    no_bias = jnp.zeros((params["head.w"].shape[-1],), jnp.float32)
    chunk = jax.checkpoint(
        lambda hy: _head_loss_sum(hy[0], hy[1], params["head.w"], no_bias, model["vocab_size"], mm))
    return jnp.sum(jax.lax.map(chunk, (hs, ys))) / (b * t)


# ---------------------------------------------------------------------------
# the step and the run


def train_step(*args, **kwargs):
    """``reference.train_step`` — clip by global norm, then AdamW, the
    per-leaf norms of the clipped gradient — around this file's loss: its
    arithmetic is used, not copied."""
    theirs = reference.loss_fn
    reference.loss_fn = loss_fn
    try:
        return reference.train_step(*args, **kwargs)
    finally:
        reference.loss_fn = theirs


def run_steps(model: dict, optim: dict, seed: int, batches: list[np.ndarray],
              *, matmul: str = "float32", devices: list | None = None,
              rows: slice | None = None, frozen: bool = False) -> dict[str, Any]:
    """Follow ``len(batches)`` steps from the seed's weights on one device;
    see ``reference.run_steps`` for ``rows`` and ``frozen`` (the planted
    faults) and for what is returned. The jitted programs are this call's
    own: a step's reservation for its temporaries (7.8 GB at the cell's
    size) lives as long as its executable, and two flavours do not fit."""
    device = (devices or jax.devices())[0]
    words = jax.device_put(seed_words(seed), device)
    with jax.default_matmul_precision("highest"):
        make = jax.jit(functools.partial(make_weights, model))
        step = jax.jit(functools.partial(train_step, model=model, optim=optim, matmul=matmul),
                       donate_argnums=(0, 1, 2))
        delta = jax.jit(lambda p, w: leaf_norms(
            {k: p[k] - v for k, v in make_weights(model, w).items()}))
        params = make(words)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.int32)
        losses, grad1 = [], None
        for i, batch in enumerate(batches):
            if rows is not None:
                batch = batch[rows]
            x = jax.device_put(np.ascontiguousarray(batch[:, :-1]), device)
            y = jax.device_put(np.ascontiguousarray(batch[:, 1:]), device)
            params, mu, nu, count, loss, gn = step(params, mu, nu, count, x, y)
            if frozen:
                # unchanged parameters are the seed's: made again, not kept
                # beside the step's (a copy of 2.5 GB does not fit the cell)
                del params
                params = make(words)
            losses.append(float(loss))
            if i == 0:
                grad1 = jax.device_get(gn)
        dparam = jax.device_get(delta(params, words))
    del params, mu, nu
    return {"losses": losses, "grad1": grad1, "dparam": dparam}
