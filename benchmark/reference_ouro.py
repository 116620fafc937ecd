"""Plain float32 reference of the Ouro looped language model and its AdamW step.

The interface of ``reference.py`` (``leaf_shapes``, ``make_weights``,
``leaf_norms``, ``loss_fn``, ``run_steps``, the int8 control) for a model
whose stack of layers runs ``stack_passes`` times on the same weights, with a
head pass and an exit gate after every pass and the loss taken over the
learned exit distribution. Nothing of the program is imported; the clip and
AdamW arithmetic is ``reference.train_step``'s, around this file's loss.
Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.

The equations, literally (T = ``stack_passes``, N = ``n_layers``, d the
residual width, eps ``norm_eps``; the published code is the ``modeling_ouro``
file beside the configuration's ``source`` and the paper "Scaling Latent
Reasoning via Looped Language Models", both as recalled: no network here)::

    rms(x; g) = g * x * rsqrt(mean(x^2) + eps)                 a plain gain
    h_0 = E[x]                                                 token embedding only
    for t = 1..T:                                              :func:`loss_fn`'s loop (a ``lax.scan``: memory)
        u = h_{t-1}
        for l = 1..N:                                          :func:`stack`, the SAME leaves every pass
            u = u + rms(Attn_l(rms(u; g1_l)); g2_l)            the sandwich: four norms a layer
            u = u + rms(SwiGLU_l(rms(u; g3_l)); g4_l)
        h_t      = rms(u; g_f)                                 after EVERY pass; h_t feeds pass t + 1
        logits_t = h_t W_head                                  untied, no bias
        z_t      = h_t . w_gate + b_gate                       the exit gate
    Attn:   q, k, v = x Wq, x Wk, x Wv (no bias, no norm of q or k), ``n_heads``
            heads of ``attn_head_dim``; rotary over the whole head (``x cos +
            rotate_half(x) sin``, ``rotate_half(x) = [-x2, x1]``, theta
            ``rope_theta``); causal softmax of ``q k^T / sqrt(head_dim)``; ``(.) Wo``
    SwiGLU: W_down(silu(x W_gate) * (x W_up)), width ``d_ff``
    per token n, CE_t(n) the cross-entropy of logits_t against the next token:
        log p_t = log_sigmoid(z_t) + sum_{j<t} log_sigmoid(-z_j)      t < T
        log p_T =                    sum_{j<T} log_sigmoid(-z_j)      what is left; z_T is unused
        loss = mean_n [ sum_t p_t CE_t  -  beta H(p) ],   H(p) = -sum_t p_t log p_t

Nothing is detached: the stack learns through every pass's loss and the gate
through both terms (the paper's first training stage).

Departures from the published model, each ``assumed`` in the configuration
file because the catalog's ``config`` cannot say: the four-norm sandwich
order; the final norm after every pass and fed back; no projection biases
and no q / k norm; the gate a ``Linear(d -> 1)`` with a bias; the remainder
rule for ``p_T``; ``beta`` = :data:`BETA` (0.1) and a uniform prior, which
makes the regulariser the entropy; no dropout; ``early_exit_threshold`` is
an inference-time key and unused. Random weights.

Memory at the timed size (2 x 4096 tokens beside 9.8 GB of state): the loop
over passes is a ``lax.scan`` over ONE body that calls :func:`stack` (a
Python loop compiles to 16.2 GiB of the chip's 15.75: each unrolled pass's
backward hands out its own set of gradients); every pass and, inside it,
every layer under ``jax.checkpoint``; attention over blocks of ``Q_BLOCK``
query rows; the head over chunks of the sequence. That is blocking, not
another formula: ``benchmark/tests/test_correct_ouro.py`` holds this file to
a second writing of the equations with plain Python loops and no blocking.

``matmul="int8"``: the control, as in ``reference.py`` — every dense
projection (the mixers', the SwiGLU's, the head's; not the exit gate, which
the configuration keeps in float32) takes operands rounded to int8, one
absmax scale per vector of the contraction. ``fault``: a planted fault, for
the tests and the limits (:data:`FAULTS`).
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
from reference import B1, HEAD_CHUNK, _mm, leaf_norms, padded_vocab, seed_words  # noqa: E402,F401

Q_BLOCK = 128  # query rows per block of the dense attention

#: The entropy term's weight (the program keeps its own: models/pattern.EXIT_BETA).
BETA = 0.1

#: The planted faults ``loss_fn`` can compute in place of the model: each a
#: mistake a builder of the mechanism could make and the limits must catch.
FAULTS = ("three_passes", "norm_not_fed_back", "gated_last_pass", "no_entropy", "no_post_norms")


def leaf_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Reference leaf name -> shape. A layer's leaves are ``blocks.0.<leaf>``
    (the period is one layer), stacked over the ``n_layers`` periods."""
    d, f, n = model["d_model"], model["d_ff"], model["n_layers"]
    hd = model["n_heads"] * model["attn_head_dim"]
    layer = {
        "norm1.g": (d,), "norm1_post.g": (d,), "norm2.g": (d,), "norm2_post.g": (d,),
        "attn.q.w": (d, hd), "attn.k.w": (d, hd), "attn.v.w": (d, hd), "attn.o.w": (hd, d),
        "mlp.gate.w": (d, f), "mlp.up.w": (d, f), "mlp.down.w": (f, d),
    }
    v = padded_vocab(model)
    return {"wte": (v, d), "norm_f.g": (d,), "head.w": (d, v), "exit.w": (d, 1), "exit.b": (1,),
            **{f"blocks.0.{k}": (n, *s) for k, s in layer.items()}}


def make_weights(model: dict, words: jax.Array) -> dict[str, jax.Array]:
    """Every weight from the seed, on the device, float32: normal(0, 0.02)
    for matrices, the embedding and the gate's weight; 1 + that for the
    norms' plain gains (a trained model's are not 1); the gate's bias 0 — so
    z scatters about 0 (standard deviation 0.02 sqrt(d)), the tokens' mean p
    is near [.5, .25, .125, .125], and the entropy term gives the gate a
    gradient."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    out = {}
    for name, shape in leaf_shapes(model).items():
        if name.endswith(".b"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        w = jax.random.normal(k, shape, jnp.float32)
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
    """Multi-head attention on normed input ``x`` (B, T, d), a block of
    query rows at a time."""
    b, t, _ = x.shape
    h, hd = model["n_heads"], model["attn_head_dim"]
    q, k, v = (mm(x, p[f"attn.{n}.w"]).reshape(b, t, h, hd) for n in "qkv")
    q, k = rotate(q, model["rope_theta"]), rotate(k, model["rope_theta"])
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


def swiglu_layer(p: dict, x, model: dict, mm=jnp.matmul):
    return mm(jax.nn.silu(mm(x, p["mlp.gate.w"])) * mm(x, p["mlp.up.w"]), p["mlp.down.w"])


def layer(p: dict, u, model: dict, mm=jnp.matmul, fault: str | None = None):
    """One sandwich block."""
    eps = model["norm_eps"]

    def post(y, g):
        return y if fault == "no_post_norms" else rms(y, g, eps)

    u = u + post(attn_layer(p, rms(u, p["norm1.g"], eps), model, mm), p["norm1_post.g"])
    return u + post(swiglu_layer(p, rms(u, p["norm2.g"], eps), model, mm), p["norm2_post.g"])


def layer_params(params: dict, prefix: str = "blocks.0.") -> dict:
    """The leaves of the layers, stacked, prefix removed."""
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def stack(params: dict, u, model: dict, mm=jnp.matmul, fault: str | None = None):
    """One pass over the ``n_layers`` layers, in order."""
    one = jax.checkpoint(functools.partial(layer, model=model, mm=mm, fault=fault))
    u, _ = jax.lax.scan(lambda u, p: (one(p, u), None), u, layer_params(params))
    return u


def token_losses(h, y, head, vocab: int, mm=jnp.matmul):
    """Every token's next-token cross-entropy (B, T) of normed ``h`` over
    the unpadded vocabulary, a chunk of the sequence at a time."""
    b, t, _ = h.shape
    c = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t

    @jax.checkpoint
    def chunk(hy):
        logits = mm(hy[0], head)[..., :vocab]
        gold = jnp.take_along_axis(logits, hy[1][..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    hs = jnp.moveaxis(h.reshape(b, t // c, c, -1), 1, 0)
    ys = jnp.moveaxis(y.reshape(b, t // c, c), 1, 0)
    return jnp.moveaxis(jax.lax.map(chunk, (hs, ys)), 0, 1).reshape(b, t)


def exit_log_probs(z: list, fault: str | None = None) -> list:
    """``log p_t`` of each pass from the gates' scores ``z_t``, each (B, T)."""
    last = len(z) - 1
    left = jnp.zeros_like(z[0])                      # sum_{j<t} log(1 - lambda_j)
    out = []
    for t, zt in enumerate(z):
        gated = t < last or fault == "gated_last_pass"
        out.append(left + (jax.nn.log_sigmoid(zt) if gated else 0.0))
        left = left + jax.nn.log_sigmoid(-zt)
    return out


def loss_fn(params: dict, x: jax.Array, y: jax.Array, model: dict,
            matmul: str = "float32", fault: str | None = None) -> jax.Array:
    """The loss of rows ``x`` against targets ``y`` (module docstring)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    mm = _mm(matmul)
    eps, beta = model["norm_eps"], 0.0 if fault == "no_entropy" else BETA
    passes = model["stack_passes"] - (fault == "three_passes")

    def one_pass(h, _):
        u = stack(params, h, model, mm, fault)
        h_t = rms(u, params["norm_f.g"], eps)
        ce_t = token_losses(h_t, y, params["head.w"], model["vocab_size"], mm)
        z_t = (jnp.matmul(h_t, params["exit.w"]) + params["exit.b"])[..., 0]
        return (u if fault == "norm_not_fed_back" else h_t), (ce_t, z_t)

    # The loop over passes, written as a scan for memory alone: unrolled,
    # every pass's backward hands out a whole set of gradients (4 x 2.45 GB
    # beside 9.8 GB of state does not fit the chip); the scan's transpose
    # adds them into one. Its body is one pass, checkpointed.
    _, (ce, z) = jax.lax.scan(jax.checkpoint(one_pass), params["wte"][x], None, length=passes)
    logp = exit_log_probs(list(z), fault)
    expected = sum(jnp.exp(lp) * c for lp, c in zip(logp, ce))
    entropy = -sum(jnp.exp(lp) * lp for lp in logp)
    return jnp.mean(expected - beta * entropy)


# ---------------------------------------------------------------------------
# the step and the run


def train_step(*args, fault: str | None = None, **kwargs):
    """``reference.train_step`` — clip by global norm, then AdamW, the
    per-leaf norms of the clipped gradient — around this file's loss: its
    arithmetic is used, not copied."""
    theirs = reference.loss_fn
    reference.loss_fn = functools.partial(loss_fn, fault=fault)
    try:
        return reference.train_step(*args, **kwargs)
    finally:
        reference.loss_fn = theirs


def run_steps(model: dict, optim: dict, seed: int, batches: list[np.ndarray],
              *, matmul: str = "float32", devices: list | None = None,
              rows: slice | None = None, frozen: bool = False,
              fault: str | None = None) -> dict[str, Any]:
    """Follow ``len(batches)`` steps from the seed's weights on one device;
    see ``reference.run_steps`` for ``rows`` and ``frozen`` (two planted
    faults of the step) and for what is returned; ``fault``: one of
    :data:`FAULTS`, of the model. The jitted programs are this call's own: a
    step's reservation for its temporaries lives as long as its executable."""
    device = (devices or jax.devices())[0]
    words = jax.device_put(seed_words(seed), device)
    with jax.default_matmul_precision("highest"):
        make = jax.jit(functools.partial(make_weights, model))
        step = jax.jit(functools.partial(train_step, model=model, optim=optim, matmul=matmul,
                                         fault=fault), donate_argnums=(0, 1, 2))
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
                # beside the step's (a copy of 2.4 GB does not fit the cell)
                del params
                params = make(words)
            losses.append(float(loss))
            if i == 0:
                grad1 = jax.device_get(gn)
        dparam = jax.device_get(delta(params, words))
    del params, mu, nu
    return {"losses": losses, "grad1": grad1, "dparam": dparam}
