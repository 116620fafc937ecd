"""Plain float32 reference of the LFM2-MoE block family and its AdamW step.

The interface of ``reference.py`` (``leaf_shapes``, ``make_weights``,
``leaf_norms``, ``loss_fn``, ``run_steps``, the int8 control) for a model of
gated short-convolution layers among grouped-query attention layers, one or
more leading dense layers before the periods, experts chosen by sigmoid
scores with a selection bias, and a head tied to the embedding. Nothing of
the program is imported; the clip and AdamW arithmetic is
``reference.train_step``'s, around this file's loss. Everything is
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``.

The equations, literally (x the residual stream, d its width, eps
``norm_eps``; the dense family's published code is
``transformers/models/lfm2/modeling_lfm2.py``, the ``lfm2_moe`` block's is
the configuration's ``source``):

- ``rms(x; w) = w * x * rsqrt(mean(x^2) + eps)``, a plain gain. A layer is
  ``x += operator(rms(x; w1)); x += ffn(rms(x; w2))``. The leading layers
  (``leading_pattern``) come once, then position i of a period is what
  ``layer_pattern[i]`` says. After the last layer a final ``rms`` and the
  head ``logits = h E^T`` with E the token embedding; token embedding only.
- **Short convolution.** ``(B, C, u) = split3(in_proj(h))`` (d -> 3 d, that
  column order), ``y = out_proj(C * conv(B * u))`` with ``conv`` depthwise
  and causal over ``shortconv_width`` taps, written here as the sum of that
  many shifted copies: ``conv(a)_t = sum_j taps[:, W-1-j] * a_{t-j}``. No
  bias, no activation.
- **Attention.** ``q_proj`` to ``n_heads`` of ``attn_head_dim``, ``k_proj``
  and ``v_proj`` to ``n_kv_heads``; q and k ``rms``-normed over the head,
  rotated over the WHOLE head (``x cos + rotate_half(x) sin``,
  ``rotate_half(x) = [-x2, x1]``, theta ``rope_theta``); a dense causal
  softmax of ``q k^T / sqrt(head_dim)``, each KV head serving ``n_heads /
  n_kv_heads`` query heads; ``out_proj`` of the heads, no output gate.
- **Dense FFN.** ``w2(silu(w1 h) * w3 h)`` of width ``d_ff``.
- **Expert FFN.** ``s = sigmoid(h W_r)`` over all ``moe_experts``;
  ``sel = top_k(s + expert_bias)``: the bias enters the choice only;
  ``g = s[sel] / (sum(s[sel]) + 1e-6) * moe_routed_scale``; the result is
  the sum over the chosen experts THAT ARE HELD (``moe_experts_held`` from
  ``moe_expert_rank * held`` on: one chip's share) of ``g_e down_e(silu(
  gate_e h) * up_e h)``. No shared expert, no capacity. The held experts
  run as a loop over them, each on every token, weighted by a gate that is
  zero where the token did not choose it.

Departures from the published model, each ``assumed`` in the configuration
file: ``expert_bias`` is a constant leaf (the balancing rule that moves it
in the published training is no part of the config; here only AdamW's
decay touches it, its gradient being exactly zero) drawn at
:func:`expert_bias_scale`, which the configuration file states; the 1e-6 in
the gate's normalisation; no auxiliary router loss; random weights.

Memory at the timed size (4 x 8192 tokens): every layer under
``jax.checkpoint``; attention over blocks of ``Q_BLOCK`` query rows; experts
one at a time; the head over chunks. That is blocking, not another
algorithm.

``matmul="int8"``: the control, as in ``reference.py`` — every dense
projection (the mixers', the dense FFN's, the experts', the tied head's; not
the router, which the configuration keeps in float32) takes operands rounded
to int8, one absmax scale per vector of the contraction.
"""

from __future__ import annotations

import functools
import json
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

Q_BLOCK = 128     # query rows per block of the dense attention
GATE_EPS = 1e-6   # in the normalisation of the chosen experts' scores


@functools.cache
def expert_bias_scale() -> float:
    """Standard deviation of the drawn ``expert_bias``, as the family's
    configuration file states it (``expert_bias_scale``)."""
    with open(os.path.join(_HERE, "configs", "lfm2-8b-a1b.json")) as f:
        return float(json.load(f)["expert_bias_scale"])


def _kinds(entries) -> list[tuple[str, str]]:
    return [tuple(entry.split("+")) for entry in entries]


def _held(model: dict) -> int:
    return int(model.get("moe_experts_held") or model["moe_experts"])


def _periods(model: dict) -> int:
    return (model["n_layers"] - len(model.get("leading_pattern", ()))) // len(model["layer_pattern"])


def _layer_shapes(model: dict, kinds: tuple[str, str]) -> dict[str, tuple[int, ...]]:
    d, f = model["d_model"], model["moe_d_ff"]
    h, hk, hd = model["n_heads"], model.get("n_kv_heads") or model["n_heads"], model["attn_head_dim"]
    mixers = {
        "attn": {
            "attn.q.w": (d, h * hd), "attn.k.w": (d, hk * hd), "attn.v.w": (d, hk * hd),
            "attn.q_norm.g": (hd,), "attn.k_norm.g": (hd,), "attn.o.w": (h * hd, d),
        },
        "shortconv": {
            "conv.in.w": (d, 3 * d), "conv.taps": (d, model["shortconv_width"]), "conv.out.w": (d, d),
        },
    }
    ffns = {
        "swiglu": {"mlp.gate.w": (d, model["d_ff"]), "mlp.up.w": (d, model["d_ff"]),
                   "mlp.down.w": (model["d_ff"], d)},
        "moe": {
            "moe.router.w": (d, model["moe_experts"]), "moe.bias": (model["moe_experts"],),
            "moe.gate.w": (_held(model), d, f), "moe.up.w": (_held(model), d, f),
            "moe.down.w": (_held(model), f, d),
        },
    }
    return {"norm1.g": (d,), "norm2.g": (d,), **mixers[kinds[0]], **ffns[kinds[1]]}


def leaf_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Reference leaf name -> shape. A leading layer's leaves are
    ``lead.<i>.<leaf>``; a period's are ``blocks.<position in the
    period>.<leaf>``, stacked over periods. No head leaf: it is ``wte``."""
    shapes: dict[str, tuple[int, ...]] = {
        "wte": (padded_vocab(model), model["d_model"]), "norm_f.g": (model["d_model"],)}
    for i, kinds in enumerate(_kinds(model.get("leading_pattern", ()))):
        shapes.update({f"lead.{i}.{k}": s for k, s in _layer_shapes(model, kinds).items()})
    for i, kinds in enumerate(_kinds(model["layer_pattern"])):
        shapes.update({f"blocks.{i}.{k}": (_periods(model), *s)
                       for k, s in _layer_shapes(model, kinds).items()})
    return shapes


def make_weights(model: dict, words: jax.Array) -> dict[str, jax.Array]:
    """Every weight from the seed, on the device, float32: normal(0, 0.02)
    for matrices and the embedding; 1 + that for the norms' plain gains (a
    trained model's are not 1); the convolutions' taps normal(0, 0.3) (the
    usual start is uniform within 1 / sqrt(width)); ``expert_bias``
    normal(0, :func:`expert_bias_scale`) — a zero bias would test nothing:
    at this scale it changes the choice of about a third of the tokens."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    out = {}
    for name, shape in leaf_shapes(model).items():
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        w = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("conv.taps"):
            out[name] = 0.3 * w
        elif name.endswith("moe.bias"):
            out[name] = expert_bias_scale() * w
        else:
            out[name] = 1.0 + 0.02 * w if name.endswith(".g") else 0.02 * w
    return out


# ---------------------------------------------------------------------------
# the layers


def rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rotate(x, theta: float):
    """Rotary positions over the whole head, half-split pairing, (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def attn_layer(p: dict, x, model: dict, mm=jnp.matmul):
    """Grouped-query attention on normed input ``x`` (B, T, d)."""
    b, t, _ = x.shape
    h, hk, hd = model["n_heads"], model.get("n_kv_heads") or model["n_heads"], model["attn_head_dim"]
    eps = model["norm_eps"]
    q = mm(x, p["attn.q.w"]).reshape(b, t, h, hd)
    k = mm(x, p["attn.k.w"]).reshape(b, t, hk, hd)
    v = mm(x, p["attn.v.w"]).reshape(b, t, hk, hd)
    q = rotate(rms(q, p["attn.q_norm.g"], eps), model["rope_theta"])
    k = rotate(rms(k, p["attn.k_norm.g"], eps), model["rope_theta"])
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
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, h * hd)
    return mm(a, p["attn.o.w"])


def shortconv_layer(p: dict, x, model: dict, mm=jnp.matmul):
    """The gated short convolution on normed input ``x`` (B, T, d)."""
    t, d = x.shape[1], x.shape[2]
    bcu = mm(x, p["conv.in.w"])
    gate_in, gate_out, u = bcu[..., :d], bcu[..., d: 2 * d], bcu[..., 2 * d:]
    a = gate_in * u
    taps = p["conv.taps"]
    width = taps.shape[1]
    conv = sum(taps[:, width - 1 - j] * jnp.pad(a, ((0, 0), (j, 0), (0, 0)))[:, :t]
               for j in range(width))                              # a_{t-j}, zeros before the row
    return mm(gate_out * conv, p["conv.out.w"])


def swiglu_layer(p: dict, x, model: dict, mm=jnp.matmul):
    return mm(jax.nn.silu(mm(x, p["mlp.gate.w"])) * mm(x, p["mlp.up.w"]), p["mlp.down.w"])


def routed_gates(p: dict, x, model: dict):
    """(tokens, experts) float32: the gate of each token's chosen experts,
    zero elsewhere. The router stays plain float32."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["moe.router.w"]))
    _, sel = jax.lax.top_k(s + p["moe.bias"], model["moe_top_k"])
    chosen = jnp.take_along_axis(s, sel, axis=-1)
    g = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS) * model.get("moe_routed_scale", 1.0)
    return jnp.sum(jax.nn.one_hot(sel, s.shape[-1], dtype=jnp.float32) * g[..., None], axis=-2)


def moe_layer(p: dict, x, model: dict, mm=jnp.matmul, *, first: int | None = None):
    """The expert layer on normed input ``x`` (B, T, d): the held experts'
    part of the routed sum (those of ``p``'s expert leaves, standing for
    experts ``first`` on; default: the configured share)."""
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
    return y.reshape(b, t, d)


MIXERS = {"attn": attn_layer, "shortconv": shortconv_layer}
FFNS = {"swiglu": swiglu_layer, "moe": moe_layer}


def layer(p: dict, h, model: dict, kinds: tuple[str, str], mm=jnp.matmul):
    eps = model["norm_eps"]
    h = h + MIXERS[kinds[0]](p, rms(h, p["norm1.g"], eps), model, mm)
    return h + FFNS[kinds[1]](p, rms(h, p["norm2.g"], eps), model, mm)


def layer_params(params: dict, prefix: str) -> dict:
    """The leaves of one layer (``lead.<i>.``) or of one position of the
    period, stacked (``blocks.<i>.``), prefix removed."""
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def loss_fn(params: dict, x: jax.Array, y: jax.Array, model: dict,
            matmul: str = "float32") -> jax.Array:
    """Mean next-token cross-entropy of rows ``x`` against targets ``y``,
    over the unpadded vocabulary, the head tied to the embedding."""
    mm = _mm(matmul)
    b, t = x.shape
    h = params["wte"][x]

    def run(kinds, p, h):
        return jax.checkpoint(functools.partial(layer, model=model, kinds=kinds, mm=mm))(p, h)

    for i, kinds in enumerate(_kinds(model.get("leading_pattern", ()))):
        h = run(kinds, layer_params(params, f"lead.{i}."), h)
    period_kinds = _kinds(model["layer_pattern"])

    def period(h, stacked):
        for kinds, p in zip(period_kinds, stacked):
            h = run(kinds, p, h)
        return h, None

    h, _ = jax.lax.scan(period, h, [layer_params(params, f"blocks.{i}.")
                                    for i in range(len(period_kinds))])
    h = rms(h, params["norm_f.g"], model["norm_eps"])
    c = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t
    hs = jnp.moveaxis(h.reshape(b, t // c, c, -1), 1, 0)
    ys = jnp.moveaxis(y.reshape(b, t // c, c), 1, 0)
    head = params["wte"].T
    no_bias = jnp.zeros((head.shape[-1],), jnp.float32)
    chunk = jax.checkpoint(
        lambda hy: _head_loss_sum(hy[0], hy[1], head, no_bias, model["vocab_size"], mm))
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
    own: a step's reservation for its temporaries lives as long as its
    executable, and two flavours do not fit beside 8 GB of state."""
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
                # beside the step's (a copy of 2 GB does not fit the cell)
                del params
                params = make(words)
            losses.append(float(loss))
            if i == 0:
                grad1 = jax.device_get(gn)
        dparam = jax.device_get(delta(params, words))
    del params, mu, nu
    return {"losses": losses, "grad1": grad1, "dparam": dparam}
