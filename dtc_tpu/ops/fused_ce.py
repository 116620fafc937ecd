"""Fused LM-head + cross-entropy with an augmented-matmul backward.

The reference computes the LM head and the loss as separate ops
(`/root/reference/model/GPTModel.py:69-74` +
`/root/reference/train/create_train_step.py:30-34`) and lets autodiff derive
the backward. On TPU that backward costs one avoidable full pass over the
logits: XLA fuses the dlogits recomputation into the dW and dh matmuls, but
the *bias* gradient ``db = sum_rows(dlogits)`` becomes its own
bandwidth-bound reduction over the (B·T, V) logits — 2.3 ms/step at the
flagship b32 shape (PERF.md round 4).

This op folds db into the dW matmul by appending a ones-column to the
activations: ``[h; 1]^T @ dlogits`` yields dW in rows [:d] and db in row d,
one matmul instead of a matmul plus a separate logits pass. Forward numerics
are bitwise identical to the unfused path (same op sequence as
``dtc_tpu.train.train_step.cross_entropy_loss``); backward differs only in
reduction order (ulp-level).

:func:`fused_head_ce_tokens` is the same op before the mean: every token's
CE and a per-token cotangent, for a loss that weighs tokens or passes before
it sums (a looped stack's exit distribution, ``models/pattern.py``). It keeps
no logits between the passes and makes them again in its backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

NEG_INF = -1e9  # matches the reference's additive mask value


def head_logits(h: jax.Array, w: jax.Array, b: jax.Array, vocab_size: int) -> jax.Array:
    """LM-head logits with padded-vocab masking.

    Bitwise-matches ``nn.Dense`` (dot_general + bias in compute dtype)
    followed by the pad-column mask the model applied before this op
    existed — the non-fused eval/generate path calls this too, so the two
    paths cannot drift apart.
    """
    cdtype = h.dtype
    logits = jnp.dot(h, w.astype(cdtype)) + b.astype(cdtype)
    v = w.shape[-1]
    if v != vocab_size:
        # Pad columns contribute exp(-1e9) = 0 to any softmax, so losses and
        # samples over the padded vocab equal the unpadded ones.
        col = jax.lax.broadcasted_iota(jnp.int32, (v,), 0)
        logits = jnp.where(col < vocab_size, logits, NEG_INF).astype(logits.dtype)
    return nn.with_logical_constraint(logits, ("batch", "seq", "vocab_out"))


def _stats_tokens(logits: jax.Array, y: jax.Array):
    """Per-token CE (float32, ``y``'s shape) + softmax stats."""
    l32 = logits.astype(jnp.float32)
    maxl = jax.lax.stop_gradient(jnp.max(l32, axis=-1, keepdims=True))
    shifted = l32 - maxl
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == y[..., None], shifted, 0.0), axis=-1)
    return logz - gold, (maxl, logz)


def _stats_loss(logits: jax.Array, y: jax.Array):
    """Mean CE + softmax stats. Same op sequence as cross_entropy_loss."""
    ce, stats = _stats_tokens(logits, y)
    return ce.mean(), stats


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_head_ce(h, w, b, y, vocab_size):
    """Mean next-token CE of ``softmax([h @ w + b | mask])`` against ``y``.

    ``h``: (..., d) compute-dtype activations; ``w``: (d, V) / ``b``: (V,)
    master params; ``y``: (...) int32 targets aligned with ``h``'s leading
    dims. Returns a float32 scalar.
    """
    loss, _ = _stats_loss(head_logits(h, w, b, vocab_size), y)
    return loss


def _fhc_fwd(h, w, b, y, vocab_size):
    logits = head_logits(h, w, b, vocab_size)
    loss, (maxl, logz) = _stats_loss(logits, y)
    return loss, (h, w, y, logits, maxl, logz)


def _head_grads(h, w, y, logits, maxl, logz, g, per_token: bool):
    """(dh, dw, db) of the head under the cotangent ``g``: of the mean CE (a
    scalar) or, ``per_token``, of every token's CE (``y``'s shape)."""
    *lead, v = logits.shape
    d = h.shape[-1]
    n = float(np.prod(lead))
    # dlogits = (softmax - onehot) * g / N, recomputed from the saved logits
    # and stats. XLA duplicates this elementwise chain into both consumer
    # matmul fusions, so dlogits is never materialised in HBM (verified in
    # the round-4 trace: the dot fusions' byte counts equal a logits read).
    l32 = logits.astype(jnp.float32)
    p = jnp.exp(l32 - maxl - logz[..., None])
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    onehot = jnp.where(iota == y[..., None], 1.0, 0.0)
    diff = p - onehot
    dl = (diff * (g[..., None] if per_token else g / n)).astype(h.dtype)
    dl = nn.with_logical_constraint(dl, ("batch", "seq", "vocab_out"))
    dl2 = dl.reshape(-1, v)
    # The augmented matmul: db rides along as row d of [h; 1]^T @ dlogits.
    hb = jnp.concatenate([h, jnp.ones((*lead, 1), h.dtype)], axis=-1)
    dwb = jax.lax.dot_general(hb.reshape(-1, d + 1), dl2, (((0,), (0,)), ((), ())))
    dw = dwb[:d].astype(w.dtype)
    db = dwb[d].astype(w.dtype)
    dh = (
        jax.lax.dot_general(dl2, w.astype(h.dtype), (((1,), (1,)), ((), ())))
        .reshape(h.shape)
        .astype(h.dtype)
    )
    return dh, dw, db


def _fhc_bwd(vocab_size, res, g):
    h, w, y, logits, maxl, logz = res
    dh, dw, db = _head_grads(h, w, y, logits, maxl, logz, g, per_token=False)
    dy = np.zeros(y.shape, dtype=jax.dtypes.float0)
    return dh, dw, db, dy


fused_head_ce.defvjp(_fhc_fwd, _fhc_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_head_ce_tokens(h, w, b, y, vocab_size):
    """:func:`fused_head_ce` before the mean: every token's CE, float32 in
    ``y``'s shape, and a backward that takes a cotangent of that shape — the
    form a loss needs that weighs tokens (or passes over one head) before it
    sums. ``dW`` and ``dh`` as there: no ``(N, V)`` float32 tensor in HBM.

    Unlike the scalar form it keeps NO logits for the backward: it makes
    them again there from ``h`` (one more head matmul) and keeps the softmax
    statistics only. Its caller is a looped stack that reads out after every
    pass: T passes' compute-dtype logits (3.0 GiB at 4 x 8192 x 49152) do
    not fit beside that model's state, one pass's do (PERF.md section 4)."""
    return _stats_tokens(head_logits(h, w, b, vocab_size), y)[0]


def _fhct_fwd(h, w, b, y, vocab_size):
    ce, (maxl, logz) = _stats_tokens(head_logits(h, w, b, vocab_size), y)
    return ce, (h, w, b, y, maxl, logz)


def _fhct_bwd(vocab_size, res, g):
    h, w, b, y, maxl, logz = res
    logits = head_logits(h, w, b, vocab_size)
    dh, dw, db = _head_grads(h, w, y, logits, maxl, logz, g, per_token=True)
    return dh, dw, db, np.zeros(y.shape, dtype=jax.dtypes.float0)


fused_head_ce_tokens.defvjp(_fhct_fwd, _fhct_bwd)
