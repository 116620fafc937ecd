"""Gated DeltaNet's recurrence in chunked form, with its own backward.

Per head, with a key/query width ``dk``, a value width ``dv`` and a state
``S`` of ``(dk, dv)`` that starts at zero, the layer computes for every
position ``t`` (Yang et al., Gated Delta Networks, arXiv:2412.06464)::

    S <- S * exp(g_t)
    delta = (v_t - S^T k_t) * beta_t
    S <- S + k_t delta^T
    o_t = S^T q_t

Token by token that is 8192 dependent steps of tiny products. The chunked
form (the paper's section 3.3; the public kernels use chunks of 64) does
the work of ``C`` positions with matmuls: inside a chunk the ``delta``s
solve a unit lower-triangular system ``(I + A) delta = beta (v - ...)``
whose inverse is built once for all chunks at a time, and only the state
is carried from chunk to chunk:

    gamma_i = g_1 + ... + g_i                    (cumulative, inside the chunk)
    A_ij    = beta_i (k_i . k_j) exp(gamma_i - gamma_j)          for i > j
    T       = (I + A)^-1
    u = T (beta v),   w = T (beta k exp(gamma))
    per chunk:  v' = u - w S
                o  = (q exp(gamma)) S + tril(q k^T exp(gamma_i - gamma_j)) v'
                S <- S exp(gamma_C) + (k exp(gamma_C - gamma))^T v'

Every decay appears as ``exp`` of a difference that is never positive, so
nothing overflows however fast a head forgets. The decays, the cumulative
sums and the carried state are float32; every product takes its operands in
``dtype`` and accumulates in float32 (the first chip trace had the inverse
at ``highest`` precision: 254 ms of a 0.9 s step in batched 64 x 64
six-pass products, for an ``A`` that itself comes out of a ``dtype``
product).

**The backward keeps no per-token state.** The forward rule of the scan
over chunks keeps each chunk's incoming ``S`` (``T / C`` states, not
``T``); the backward rule walks the chunks in reverse, recomputes one
chunk's step from its saved ``S`` and pulls the cotangents of the outputs
and of the carried state through it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a: jax.Array, dtype=jnp.float32) -> jax.Array:
    """``(I + A)^-1`` for strictly lower-triangular ``A`` of ``(..., C, C)``:
    with ``N = -A`` nilpotent, ``sum_i N^i = (I + N)(I + N^2)(I + N^4)...`` —
    ``log2 C`` squarings and as many products, all MXU-shaped and batched
    over every chunk and head, where a substitution would be C dependent
    steps. The products take their operands in ``dtype`` and accumulate in
    float32; each factor is applied as ``out + out N^k``, so that no operand
    holds a ``1 + small`` whose small part the rounding would lose (the
    diagonal stays exactly 1). The backward is the inverse's own,
    ``dA = -T^T dT T^T``: two products and no saved power."""
    c = a.shape[-1]
    power = -a
    out = jnp.eye(c, dtype=a.dtype) + power
    span = 2
    while span < c:
        power = _mm(power, power, dtype)
        out = out + _mm(out, power, dtype)
        span *= 2
    return out


def _mm(x, y, dtype):
    return jnp.matmul(x.astype(dtype), y.astype(dtype), preferred_element_type=jnp.float32)


def _inverse_fwd(a, dtype):
    t = unit_lower_inverse(a, dtype)
    return t, t


def _inverse_bwd(dtype, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, dt, dtype), tt, dtype),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk_step(state, qg, w, u, local, kdec, decay, dtype):
    """One chunk: ``state`` (..., dk, dv) float32 in, (out, state) out.
    ``qg``, ``w``, ``local`` and ``kdec`` arrive in ``dtype`` (they are
    matmul operands only, and the loop reads them from HBM every chunk);
    ``u``, ``decay`` and the state stay float32."""
    s = state.astype(dtype)
    f32 = jnp.float32
    v_new = u - jnp.matmul(w, s, preferred_element_type=f32)
    out = jnp.matmul(qg, s, preferred_element_type=f32) + jnp.matmul(
        local, v_new.astype(dtype), preferred_element_type=f32)
    state = state * decay[..., None, None] + jnp.matmul(
        jnp.swapaxes(kdec, -1, -2), v_new.astype(dtype), preferred_element_type=f32)
    return out, state


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_chunks(qg, w, u, local, kdec, decay, dtype):
    """The carried part: chunk-major inputs ``(N, ..., C, *)``, outputs
    ``(N, ..., C, dv)``."""
    return _scan_fwd(qg, w, u, local, kdec, decay, dtype)[0]


def _scan_fwd(qg, w, u, local, kdec, decay, dtype):
    def body(state, xs):
        out, new = _chunk_step(state, *xs, dtype)
        return new, (out, state)

    s0 = jnp.zeros((*qg.shape[1:-2], qg.shape[-1], u.shape[-1]), jnp.float32)
    _, (out, states) = jax.lax.scan(body, s0, (qg, w, u, local, kdec, decay))
    return out, (qg, w, u, local, kdec, decay, states)


def _scan_bwd(dtype, res, dout):
    *xs, states = res

    def body(dstate, item):
        state, x, do = item
        _, pull = jax.vjp(lambda s, *a: _chunk_step(s, *a, dtype), state, *x)
        dstate, *dx = pull((do, dstate))
        return dstate, tuple(dx)

    _, dxs = jax.lax.scan(body, jnp.zeros_like(states[0]), (states, tuple(xs), dout),
                          reverse=True)
    return dxs


_scan_chunks.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _chunk_local(q, k, v, g, beta, dtype):
    """Everything of a chunk that needs no state, for all chunks at once
    (chunk-major ``(N, B, H, C, *)``): the scan's operands ``qg``, ``w``,
    ``local``, ``kdec`` in ``dtype``, ``u`` and ``decay`` in float32. Under
    ``jax.checkpoint``: the (C, C) intermediates (a dozen arrays the size of
    q each, padded to the lane width at C = 64) are recomputed in the
    backward, not kept."""
    f32 = jnp.float32
    chunk = q.shape[-2]
    q, k, v = (x.astype(f32) for x in (q, k, v))
    gamma = jnp.cumsum(g, axis=-1)                                      # (N, B, H, C)
    diff = gamma[..., :, None] - gamma[..., None, :]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp of a masked difference: the upper part would be exp(+x).
    ratio = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    kt = jnp.swapaxes(k, -1, -2)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    tri = unit_lower_inverse(jnp.where(strict, _mm(kb, kt, dtype) * ratio, 0.0), dtype)
    u = _mm(tri, v * beta[..., None], dtype)
    w = _mm(tri, kb * jnp.exp(gamma)[..., None], dtype)
    local = _mm(q, kt, dtype) * ratio
    qg = q * jnp.exp(gamma)[..., None]
    last = gamma[..., -1:]
    kdec = k * jnp.exp(last - gamma)[..., None]
    qg, w, local, kdec = (x.astype(dtype) for x in (qg, w, local, kdec))
    return qg, w, u, local, kdec, jnp.exp(last[..., 0])


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64, dtype=jnp.float32):
    """The recurrence of the module docstring over ``(B, T, H, *)`` inputs.

    ``q`` and ``k`` are ``(B, T, H, dk)`` (normalised and scaled by the
    caller), ``v`` is ``(B, T, H, dv)``, ``g`` (the log of the decay, never
    positive) and ``beta`` are ``(B, T, H)`` float32. ``T`` is a multiple of
    ``chunk``. Returns ``(B, T, H, dv)`` float32.
    """
    b, t, h, dk = q.shape
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the chunk {chunk}")
    n = t // chunk
    f32 = jnp.float32

    def chunks(x):  # (B, T, H, *) -> (N, B, H, C, *)
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v = (chunks(x) for x in (q, k, v))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    with jax.named_scope("chunk_local"):
        qg, w, u, local, kdec, decay = _chunk_local(q, k, v, g, beta, dtype)
    with jax.named_scope("chunk_carry"):
        out = _scan_chunks(qg, w, u, local, kdec, decay, dtype)        # (N, B, H, C, dv)
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1)                   # (B, N, C, H, dv)
    return out.reshape(b, t, h, out.shape[-1])
