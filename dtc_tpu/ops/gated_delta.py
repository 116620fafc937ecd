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

**The backward keeps no per-token state.** The forward rule keeps each
chunk's incoming ``S`` (``T / C`` states, not ``T``); the backward rule
walks the chunks in reverse, recomputes one chunk's step from its saved
``S`` and pulls the cotangents of the outputs and of the carried state
through it.

**One Mosaic kernel a pass where its tiles are legal**
(:func:`supports_chunk_kernel`; ``ops/vmem.py:gdn_chunk_plan``). The grid is
(rows, groups of value heads, chunks) with the chunks last and in order, and
the ``(dk, dv)`` float32 state of the step's heads lives in VMEM scratch
across a row's chunks, zeroed at the row's first. A grid step reads q, k and
v straight from the ``(B, T, H * d)`` layout — each value head's key head
chosen by the slice, so nothing is repeated in HBM — builds ``ratio``,
``A``, ``T``, ``u``, ``w`` and the rest of a chunk in VMEM, takes the
chunk's step on the state and writes the output alone, float32, as ``(B,
T, H, dv)``: neither the five operands of the step nor a (C, C) array ever
exists in HBM. Three kernels of one body: ``gdn_chunks_fwd``, the primal,
keeps nothing (under a layer's ``jax.checkpoint`` it is the first forward);
``gdn_chunks_fwd_res``, the forward rule, also writes each chunk's incoming
state ``(N, B, H, dk, dv)``; ``gdn_chunks_bwd`` walks the same grid from a
row's last chunk to its first with the state's cotangent in the scratch,
rebuilds a chunk from the inputs and its saved state, and writes ``dq``,
``dk`` (summed over the value heads a key head serves), ``dv`` and the
gradients for the cumulative decay and beta. Elsewhere (toy widths, a chunk
off the sublane count) the ``jax.numpy`` form (:func:`_chunk_local_xla`) and
an XLA ``scan`` over the chunks (:func:`_scan_chunks`) run, which are also
the kernels' oracle.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtc_tpu.ops import vmem


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a: jax.Array, dtype=jnp.float32) -> jax.Array:
    """``(I + A)^-1`` for strictly lower-triangular ``A`` of ``(..., C, C)``,
    block by block: a lower-triangular ``[[L11, 0], [L21, L22]]`` has the
    inverse ``[[T11, 0], [-T22 L21 T11, T22]]``, so with ``D`` the inverses
    of all diagonal blocks of ``m`` positions side by side and ``B`` the
    blocks of ``A`` under them, ``D - D B D`` holds the inverses of the
    blocks of ``2 m`` — ``log2 C`` levels of two products each, all
    MXU-shaped and batched over every chunk and head, where a substitution
    would be C dependent steps. The products take their operands in
    ``dtype`` and accumulate in float32. Every operand is an inverse of a
    part of the system (its diagonal exactly 1) or a part of ``A``: nothing
    larger than the answer is ever rounded. Not the squaring product
    ``(I + N)(I + N^2)(I + N^4)...`` of ``N = -A``, for all its shorter
    chain: its powers grow with the binomials where a chunk's keys align —
    rows of ``|A|`` summing to 10 put errors of 3 to 14 on a ``T`` whose
    entries are under 1, and the benchmark cell's loss was NaN three steps
    later. The backward is the inverse's own, ``dA = -T^T dT T^T``: two
    products and nothing saved but ``T``."""
    return _blocked_inverse(a, dtype, _mm)


def _blocked_inverse(a, dtype, mm):
    c = a.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def under(level):
        """The lower-left block of ``2 ** level`` positions in every
        diagonal block of twice that."""
        return ((row >> (level + 1)) == (col >> (level + 1))) & (((row >> level) & 1) == 1) & (
            ((col >> level) & 1) == 0)

    out = jnp.where(row == col, 1.0, 0.0).astype(a.dtype) - jnp.where(under(0), a, 0.0)
    level = 1
    while 1 << level < c:
        out = out - mm(mm(out, jnp.where(under(level), a, 0.0), dtype), out, dtype)
        level += 1
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


def _chunk_major(x, chunk):  # (B, T, H, *) -> (N, B, H, C, *)
    b, t, h = x.shape[:3]
    x = x.reshape(b, t // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)


def _chunk_local_xla(q, k, v, gamma, beta, dtype):
    """Everything of a chunk that needs no state, for all chunks at once,
    from the kernels' arguments — q, k ``(B, T, Hk, dk)``, v ``(B, T, H,
    dv)``, the decay's log summed up inside the chunk and beta ``(N, B, H,
    C)``: the scan's operands ``qg``, ``w``, ``local``, ``kdec`` in
    ``dtype`` and ``u`` in float32, chunk-major ``(N, B, H, C, *)``. The
    ``jax.numpy`` form: each key head repeated for the value heads it
    serves, a dozen (C, C) intermediates through HBM."""
    rep, chunk = v.shape[2] // q.shape[2], gamma.shape[-1]
    q, k = (jnp.repeat(x, rep, axis=2) if rep > 1 else x for x in (q, k))
    q, k, v = (_chunk_major(x, chunk).astype(jnp.float32) for x in (q, k, v))
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
    kdec = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    qg, w, local, kdec = (x.astype(dtype) for x in (qg, w, local, kdec))
    return qg, w, u, local, kdec


# ---------------------------------------------------------------------------
# chunk-local part and carry as one Mosaic kernel a pass


def supports_chunk_kernel(chunk: int, dk: int, dv: int, hv: int, hk: int,
                          itemsize: int = 2) -> dict | None:
    """The planner's plan where the fused kernels run — ``dk`` and ``dv``
    multiples of the lane width, the chunk a multiple of the sublane count,
    blocks and the state's scratch inside the budget — else None, and the
    ``jax.numpy`` form with XLA's scan runs. Off the TPU the kernels run
    interpreted."""
    plan = vmem.gdn_chunk_plan(chunk, dk, dv, hv, hk, itemsize)
    return plan if plan is not None and plan["fits"] else None


def _bmm(x, y, dtype, lhs=2, rhs=1):
    """Batched over the leading axis, contracting ``x``'s axis ``lhs`` with
    ``y``'s axis ``rhs`` ((2, 1): ``x y``; (2, 2): ``x y^T``; (1, 1): ``x^T
    y``); operands in ``dtype``, float32 out."""
    return jax.lax.dot_general(x.astype(dtype), y.astype(dtype), (((lhs,), (rhs,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _masks(c):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row == col, row >= col, row > col


def _heads(ref, n, width):
    """A ``(1, C, n * width)`` block as ``(n, C, width)`` float32: a lane
    tile a head."""
    return jnp.stack([ref[0, :, i * width:(i + 1) * width] for i in range(n)]).astype(jnp.float32)


def _decays(gam_ref, beta_ref, eye, lower):
    """Of the step's tiles ``(G, ...)``: beta and gamma as columns ``(G, C,
    1)``, ``exp(gamma)``, the decay to the chunk's end, and ``ratio`` — every
    ``exp`` of a difference that is never positive. A row ``(G, 1, C)``
    becomes a column without a transpose: its diagonal summed along the
    lanes."""
    col = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)  # noqa: E731
    g_row = gam_ref[0, 0]
    g_col, b_col = col(g_row), col(beta_ref[0, 0])
    ratio = jnp.where(lower, jnp.exp(jnp.where(lower, g_col - g_row, 0.0)), 0.0)
    return b_col, jnp.exp(g_col), jnp.exp(g_row[:, :, -1:] - g_col), ratio


def _tiles(q_ref, k_ref, v_ref, gam_ref, beta_ref, *, tiles, rep, dk, dv, dtype):
    """What the step's tiles are before any state, all at once and batched
    over the leading axis — a tile's ten dependent 64-wide products then lie
    beside the other tiles' and do not wait on the MXU's latency one after
    the other: ``_chunk_local_xla``'s arithmetic in VMEM. ``k k^T`` and ``q
    k^T`` once a key head (``q1``, ``k1``), for the value heads it serves."""
    eye, lower, strict = _masks(q_ref.shape[1])
    per = lambda x: jnp.repeat(x, rep, axis=0) if rep > 1 else x  # noqa: E731  key head -> value heads
    t = types.SimpleNamespace(eye=eye, strict=strict)
    t.q1, t.k1 = _heads(q_ref, tiles // rep, dk), _heads(k_ref, tiles // rep, dk)
    t.kk, t.qk = per(_bmm(t.k1, t.k1, dtype, 2, 2)), per(_bmm(t.q1, t.k1, dtype, 2, 2))
    t.q, t.k, t.v = per(t.q1), per(t.k1), _heads(v_ref, tiles, dv)
    t.b_col, t.e_col, t.x_col, t.ratio = _decays(gam_ref, beta_ref, eye, lower)
    t.decay = jnp.exp(gam_ref[0, 0][:, :, -1:])                    # (G, 1, 1): the chunk's whole decay
    t.tri = _blocked_inverse(jnp.where(strict, t.kk * t.b_col * t.ratio, 0.0), dtype, _bmm).astype(dtype)
    t.u = _bmm(t.tri, t.v * t.b_col, dtype)
    t.w = _bmm(t.tri, t.k * (t.b_col * t.e_col), dtype).astype(dtype)
    t.local, t.qg, t.kdec = ((t.qk * t.ratio).astype(dtype), (t.q * t.e_col).astype(dtype),
                             (t.k * t.x_col).astype(dtype))
    return t


def _fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, out_ref, *rest, tiles, rep, dk, dv, dtype):
    """One chunk of the step's (row, value heads): ``_chunk_step`` on the
    state the scratch carries from the row's chunk before. ``rest`` is the
    scratch alone (the primal) or, before it, the block that takes the
    chunk's incoming state for the backward."""
    *saved, s_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    t = _tiles(q_ref, k_ref, v_ref, gam_ref, beta_ref, tiles=tiles, rep=rep, dk=dk, dv=dv, dtype=dtype)
    s = s_ref[...]
    for ref in saved:
        ref[0, 0] = s
    sd = s.astype(dtype)
    v_new = (t.u - _bmm(t.w, sd, dtype)).astype(dtype)
    out = _bmm(t.qg, sd, dtype) + _bmm(t.local, v_new, dtype)
    s_ref[...] = s * t.decay + _bmm(t.kdec, v_new, dtype, 1, 1)
    for i in range(tiles):
        out_ref[0, :, i, :] = out[i]


def _bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, s_in_ref, dout_ref,
                dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref, ds_ref, *, tiles, rep, dk, dv, dtype):
    """The same grid from a row's last chunk to its first, the scratch
    carrying the state's cotangent. A step rebuilds its tiles and, from the
    chunk's saved incoming state, ``v'``; pulls ``dout`` and the carried
    cotangent through ``_chunk_step`` into the five cotangents of the
    chunk-local part — which never leave VMEM — and those through the tiles:
    ``dT = du (beta v)^T + dw (beta k e^gamma)^T``, ``dA = -T^T dT T^T``
    under the strict mask, then the products' own rules. ``ratio_ij =
    exp(gamma_i - gamma_j)`` gives ``+ds`` to row i's gamma and ``-ds`` to
    column j's, ``ds = d(ratio) * ratio``; the chunk's whole decay gives
    ``sum(S * dS)`` times itself to the last column's. ``dq`` and ``dk`` are
    summed over the value heads a key head serves."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    c = q_ref.shape[1]
    t = _tiles(q_ref, k_ref, v_ref, gam_ref, beta_ref, tiles=tiles, rep=rep, dk=dk, dv=dv, dtype=dtype)
    q, k, v, kk, qk, tri, ratio = t.q, t.k, t.v, t.kk, t.qk, t.tri, t.ratio
    b_col, e_col, x_col = t.b_col, t.e_col, t.x_col
    rowsum = lambda x: jnp.sum(x, axis=2, keepdims=True)                              # noqa: E731
    as_row = lambda col: jnp.sum(jnp.where(t.eye, col, 0.0), axis=1, keepdims=True)   # noqa: E731
    over = lambda x: x.reshape(tiles // rep, rep, *x.shape[1:]).sum(axis=1) if rep > 1 else x  # noqa: E731

    # the carry's pullback: out = qg S + local v', S' = S decay + kdec^T v', v' = u - w S
    s, dstate = s_in_ref[0, 0], ds_ref[...]
    sd, dsd = s.astype(dtype), dstate.astype(dtype)
    dout = jnp.stack([dout_ref[0, :, i, :] for i in range(tiles)]).astype(dtype)
    v_new = (t.u - _bmm(t.w, sd, dtype)).astype(dtype)
    du = _bmm(t.local, dout, dtype, 1, 1) + _bmm(t.kdec, dsd, dtype)                  # dv'
    dqg, dw = _bmm(dout, sd, dtype, 2, 2), -_bmm(du, sd, dtype, 2, 2)
    dlocal = _bmm(dout, v_new, dtype, 2, 2) * ratio
    dkdec = _bmm(v_new, dsd, dtype, 2, 2)
    ddecay = jnp.sum(rowsum(s * dstate), axis=1, keepdims=True) * t.decay             # (G, 1, 1)
    ds_ref[...] = dstate * t.decay + _bmm(t.qg, dout, dtype, 1, 1) - _bmm(t.w, du, dtype, 1, 1)

    # the tiles' pullback
    be = b_col * e_col
    dtri = _bmm(du, v * b_col, dtype, 2, 2) + _bmm(dw, k * be, dtype, 2, 2)
    dvb, dkbe = _bmm(tri, du, dtype, 1, 1), _bmm(tri, dw, dtype, 1, 1)
    da = jnp.where(t.strict, -_bmm(_bmm(tri, dtri, dtype, 1, 1), tri, dtype, 2, 2), 0.0) * ratio   # dA * ratio
    ds = da * (b_col * kk) + dlocal * qk
    z = rowsum(dkdec * k) * x_col                              # d(decay to the chunk's end), times it
    dkbe_k = rowsum(dkbe * k)
    dgam = rowsum(ds) + dkbe_k * be + rowsum(dqg * q) * e_col - z
    dbeta = rowsum(da * kk) + rowsum(dvb * v) + dkbe_k * e_col
    last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    dgam_ref[0, 0] = (as_row(dgam) - jnp.sum(ds, axis=1, keepdims=True)
                      + jnp.where(last, jnp.sum(z, axis=1, keepdims=True) + ddecay, 0.0))
    dbeta_ref[0, 0] = as_row(dbeta)
    dkk, dqk = over(da * b_col), over(dlocal)
    dq = over(dqg * e_col) + _bmm(dqk, t.k1, dtype)
    dk_ = (over(dkbe * be + dkdec * x_col) + _bmm(dqk, t.q1, dtype, 1, 1)
           + _bmm(dkk, t.k1, dtype) + _bmm(dkk, t.k1, dtype, 1, 1))
    dv_ = dvb * b_col
    for i in range(tiles // rep):
        dq_ref[0, :, i * dk:(i + 1) * dk] = dq[i].astype(dq_ref.dtype)
        dk_ref[0, :, i * dk:(i + 1) * dk] = dk_[i].astype(dk_ref.dtype)
    for i in range(tiles):
        dv_ref[0, :, i * dv:(i + 1) * dv] = dv_[i].astype(dv_ref.dtype)


def _launch(leg, q, k, v, gamma, beta, dtype, states=None, dout=None):
    """One kernel over the grid (rows, groups of value heads, chunks), the
    chunks last and in order — from the row's last for the backward — and
    the state, or its cotangent, in a float32 scratch that is zeroed at a
    row's first step. q / k and v are blocks of ``(B, T, H * d)``: a chunk's
    positions by the lanes of the step's heads. The output and its cotangent
    are float32 blocks of ``(B, T, Hv, dv)``, the step's heads along the
    sublanes — the layout the mixer's RMSNorm over ``dv`` reduces in, so XLA
    moves nothing between the kernel and it (as ``(B, T, Hv * dv)`` it did,
    twice a pass: 10 ms a step of the benchmark's cell); a head's rows go in
    and out by strided sublane accesses. The decay and beta rows are blocks
    of ``(N, B, H, 1, C)`` (a head's row a tile of its own), a chunk's
    incoming state of ``(N, B, H, dk, dv)``. ``fwd`` writes the output,
    ``fwd_res`` each chunk's incoming state beside it, ``bwd`` a gradient
    for every input, in the input's shape."""
    (b, t, hk, dk), (hv, dv), c = q.shape, v.shape[2:], gamma.shape[-1]
    n = t // c
    plan = supports_chunk_kernel(c, dk, dv, hv, hk, q.dtype.itemsize)
    tiles, kheads = plan["tiles"], plan["key_heads_per_step"]
    at = (lambda ni: n - 1 - ni) if leg == "bwd" else (lambda ni: ni)
    qk = pl.BlockSpec((1, c, kheads * dk), lambda bi, hi, ni: (bi, at(ni), hi))
    vs = pl.BlockSpec((1, c, tiles * dv), lambda bi, hi, ni: (bi, at(ni), hi))
    per_chunk = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1, tiles, *tail), lambda bi, hi, ni: (at(ni), bi, hi, 0, 0))
    inputs = (q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk), v.reshape(b, t, hv * dv),
              gamma[..., None, :], beta[..., None, :])
    in_specs = [qk, qk, vs, per_chunk(1, c), per_chunk(1, c)]
    outs = pl.BlockSpec((1, c, tiles, dv), lambda bi, hi, ni: (bi, at(ni), hi, 0))
    out_f32 = jax.ShapeDtypeStruct((b, t, hv, dv), jnp.float32)
    saved = jax.ShapeDtypeStruct((n, b, hv, dk, dv), jnp.float32)
    if leg == "bwd":
        kernel = _bwd_kernel
        out_specs, out_shape = in_specs, [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in inputs]
        in_specs, inputs = [*in_specs, per_chunk(dk, dv), outs], (*inputs, states, dout)
    else:
        kernel = _fwd_kernel
        out_specs, out_shape = [outs], [out_f32]
        if leg == "fwd_res":
            out_specs, out_shape = [outs, per_chunk(dk, dv)], [out_f32, saved]
    return pl.pallas_call(
        functools.partial(kernel, tiles=tiles, rep=tiles // kheads, dk=dk, dv=dv, dtype=dtype),
        name=f"gdn_chunks_{leg}",
        grid=(b, hv // tiles, n), in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tiles, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan["bwd" if leg == "bwd" else "fwd"]["vmem_limit_bytes"],
        ),
        interpret=_interpret(),
    )(*inputs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunks_fused(q, k, v, gamma, beta, dtype):
    """The whole recurrence ``(B, T, Hv, dv)`` float32 from q, k ``(B, T,
    Hk, dk)``, v ``(B, T, Hv, dv)`` and the chunks' cumulative decay and beta
    ``(N, B, Hv, C)``, one kernel a pass. This, the primal, keeps nothing:
    under a layer's ``jax.checkpoint`` the first forward runs it
    (``optimize_remat``), and only the forward that a backward follows writes
    each chunk's incoming state."""
    return _launch("fwd", q, k, v, gamma, beta, dtype)[0]


def _chunks_fused_fwd(q, k, v, gamma, beta, dtype):
    out, states = _launch("fwd_res", q, k, v, gamma, beta, dtype)
    return out, (q, k, v, gamma, beta, states)


def _chunks_fused_bwd(dtype, res, dout):
    *inputs, states = res
    grads = _launch("bwd", *inputs, dtype, states, dout)
    return tuple(g.reshape(x.shape) for g, x in zip(grads, inputs))


_chunks_fused.defvjp(_chunks_fused_fwd, _chunks_fused_bwd, optimize_remat=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunk_local(q, k, v, gamma, beta, dtype):
    """``_chunk_local_xla`` with the inputs as its residuals: the backward
    rebuilds what it needs, so no (C, C) array is kept."""
    return _chunk_local_xla(q, k, v, gamma, beta, dtype)


def _chunk_local_fwd(q, k, v, gamma, beta, dtype):
    return _chunk_local_xla(q, k, v, gamma, beta, dtype), (q, k, v, gamma, beta)


def _chunk_local_bwd(dtype, res, cts):
    return jax.vjp(lambda *a: _chunk_local_xla(*a, dtype), *res)[1](cts)


_chunk_local.defvjp(_chunk_local_fwd, _chunk_local_bwd)


def gated_delta_chunked(q, k, v, g, beta, *, chunk: int = 64, dtype=jnp.float32):
    """The recurrence of the module docstring over ``(B, T, H, *)`` inputs.

    ``q`` and ``k`` are ``(B, T, Hk, dk)`` (normalised and scaled by the
    caller), ``v`` is ``(B, T, H, dv)`` with ``H`` a multiple of ``Hk``
    (each key head serves ``H / Hk`` value heads), ``g`` (the log of the
    decay, never positive) and ``beta`` are ``(B, T, H)`` float32. ``T`` is
    a multiple of ``chunk``. Returns ``(B, T, H, dv)`` float32.
    """
    b, t, hk, dk = q.shape
    h, dv = v.shape[2:]
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the chunk {chunk}")
    if h % hk:
        raise ValueError(f"{h} value heads are not a multiple of {hk} key heads")
    f32 = jnp.float32
    with jax.named_scope("chunk_gates"):
        gamma = jnp.cumsum(_chunk_major(g.astype(f32), chunk), axis=-1)    # (N, B, H, C)
        beta = _chunk_major(beta.astype(f32), chunk)
    if supports_chunk_kernel(chunk, dk, dv, h, hk, q.dtype.itemsize) is not None:
        with jax.named_scope("chunk_kernel"):
            return _chunks_fused(q, k, v, gamma, beta, dtype)
    with jax.named_scope("chunk_local"):
        qg, w, u, local, kdec = _chunk_local(q, k, v, gamma, beta, dtype)
        decay = jnp.exp(gamma[..., -1])
    with jax.named_scope("chunk_carry"):
        out = _scan_chunks(qg, w, u, local, kdec, decay, dtype)        # (N, B, H, C, dv)
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1)                   # (B, N, C, H, dv)
    return out.reshape(b, t, h, out.shape[-1])
