"""MoE token dispatch/combine — the two interchangeable routing backends.

The MoE FFN decomposes into (routing) -> (dispatch) -> (expert FFN) ->
(combine). Routing — softmax over router logits, top-k choice, gate
normalization, choice-major capacity fill, the Switch load-balance loss —
is computed ONCE here (:func:`top_k_routing`) and shared by both dispatch
backends, so switching ``moe_dispatch`` can never change which tokens go
where, which assignments are dropped, or the aux loss: only how the
token<->slot permutation is *executed*.

Backends (``ModelConfig.moe_dispatch``):

- ``einsum`` — GShard/Switch-style static one-hot dispatch/combine tensors
  ``(B, T, E, cap)`` contracted over T. Gather-free, MXU-shaped, but the
  dispatch/combine work grows linearly with E·cap: measured ~25-30 ms
  (~18% of the 162 ms step) at E=8 on a v5e (PERF.md round 5), the cost
  this module's second backend exists to A/B against.
- ``sort`` — MegaBlocks-style (Gale et al., 2022) sorted/segmented
  routing on static capacity: each kept assignment's destination slot
  ``expert·cap + position`` is already known from routing, so dispatch is
  an int32 slot->token permutation (scatter of indices, O(B·T·k)) plus a
  row gather into per-expert contiguous groups ``(B, E, cap, d)``, and
  combine is a row gather back weighted by the gates. Data movement is
  O(B·T·k·d) regardless of E — no (B,T,E,cap) tensors anywhere.

Both backends produce the per-expert grouped activations the SAME shape
``(B, E, cap, d)``, run the identical grouped expert FFN
(:func:`expert_ffn` — einsum over the stacked ``(E, d, d_ff)`` weights,
contiguous per-expert token blocks: a blocked matmul), and carry the same
"experts" logical axis, so the EP rule row (experts -> "model",
``parallel/sharding.py``) and the all-to-all it induces hold for either.

Everything here is pure jnp — unit-tested against a brute-force per-token
reference in ``tests/test_moe.py``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

MOE_DISPATCH_MODES = ("einsum", "sort")


class Routing(NamedTuple):
    """Routing decisions for one MoE layer, shared by both backends.

    Shapes: B batch, T tokens/row, E experts, k choices/token, cap
    slots/expert. The capacity fill is CHOICE-major (every token's top-1
    claims slots across the sequence before any top-2 — GShard's
    offset-by-previous-round semantics), so ``pos``/``keep`` encode the
    drop policy exactly; backends must not re-derive it.
    """

    probs: jax.Array   # (B, T, E) fp32 router softmax
    gates: jax.Array   # (B, T, k) fp32 renormalized top-k gates
    idx: jax.Array     # (B, T, k) int32 expert choice per (token, rank)
    pos: jax.Array     # (B, T, k) int32 slot within the chosen expert
    keep: jax.Array    # (B, T, k) fp32 1.0 kept / 0.0 capacity-dropped
    picked: jax.Array  # (B, T, E) fp32 sum of choice one-hots (aux loss)
    counts: jax.Array  # (B, E) fp32 total assignments per expert (pre-drop)


def top_k_routing(probs: jax.Array, k: int, cap: int) -> Routing:
    """Top-k choices + choice-major static-capacity fill from router
    ``probs`` (fp32, softmaxed). One definition of the drop policy for
    every dispatch backend."""
    b, t, e = probs.shape
    gates, idx = jax.lax.top_k(probs, k)                     # (B,T,k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    counts = jnp.zeros((b, e), jnp.float32)
    picked = jnp.zeros((b, t, e), jnp.float32)
    pos_l, keep_l = [], []
    for j in range(k):
        m = jax.nn.one_hot(idx[..., j], e, dtype=jnp.float32)  # (B,T,E)
        picked = picked + m
        # Slot index within the expert: running count over the sequence
        # plus everything earlier routing choices already claimed.
        pos_e = jnp.cumsum(m, axis=1) - m + counts[:, None, :]
        keep_e = jnp.where(pos_e < cap, m, 0.0)
        # Collapse the (B,T,E) grids to per-assignment scalars: at most
        # one nonzero per (b,t) row (the chosen expert), so the sums are
        # exact picks, not reductions.
        pos_l.append(jnp.sum(pos_e * m, axis=-1).astype(jnp.int32))
        keep_l.append(jnp.sum(keep_e, axis=-1))
        counts = counts + jnp.sum(m, axis=1)

    return Routing(
        probs=probs, gates=gates, idx=idx,
        pos=jnp.stack(pos_l, axis=-1), keep=jnp.stack(keep_l, axis=-1),
        picked=picked, counts=counts,
    )


def load_balance_loss(r: Routing, k: int, coef: float) -> jax.Array:
    """Switch load-balance loss (Fedus et al. eq. 4-6), coefficient
    pre-applied: coef · E · Σ_e f_e · P_e. Pure function of the shared
    routing, so it is bitwise-identical whichever backend executes."""
    e = r.probs.shape[-1]
    f = jnp.mean(r.picked, axis=(0, 1)) / k
    p_mean = jnp.mean(r.probs, axis=(0, 1))
    return coef * e * jnp.sum(f * p_mean)


def expert_ffn(x_e, wi, bi, wo, bo):
    """Grouped expert FFN over ``(B, E, cap, d)`` token groups: each
    expert's ``cap`` tokens are contiguous, so the einsums over the
    stacked ``(E, d, d_ff)`` weights are blocked per-expert matmuls.
    Shared verbatim by both backends — only dispatch/combine differ."""
    h = jax.nn.gelu(
        jnp.einsum("becd,edf->becf", x_e, wi) + bi[None, :, None, :]
    )
    return jnp.einsum("becf,efd->becd", h, wo) + bo[None, :, None, :]


# ---------------------------------------------------------------------------
# einsum backend: one-hot (B,T,E,cap) dispatch/combine tensors
# ---------------------------------------------------------------------------


def dispatch_combine_tensors(r: Routing, cap: int) -> tuple[jax.Array, jax.Array]:
    """One-hot dispatch/combine tensors ``(B, T, E, cap)`` fp32 from the
    shared routing — the static einsum-backend permutation encoding.

    fp32 is deliberate: building them in bf16 measured 160.1 vs 158.5 ms
    (no change — XLA fuses the buildup into its consumers, PERF.md r5).
    """
    e = r.probs.shape[-1]
    k = r.idx.shape[-1]
    dispatch = None
    combine = None
    for j in range(k):
        m = jax.nn.one_hot(r.idx[..., j], e, dtype=jnp.float32)      # (B,T,E)
        # one_hot of an out-of-capacity pos is all-zero and keep is 0.0
        # there too, so dropped assignments vanish from both tensors.
        slot = (
            jax.nn.one_hot(r.pos[..., j], cap)                       # (B,T,cap)
            [..., None, :] * m[..., None] * r.keep[..., j][..., None, None]
        )                                                            # (B,T,E,cap)
        dispatch = slot if dispatch is None else dispatch + slot
        c = slot * r.gates[..., j][..., None, None]
        combine = c if combine is None else combine + c
    return dispatch, combine


def einsum_dispatch(x: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Gather-free dispatch: contract the one-hot ``dispatch`` tensor over
    T. Returns per-expert groups ``(B, E, cap, d)`` in ``x.dtype``.

    Takes the prebuilt tensor (not the Routing) so the caller builds the
    dispatch/combine pair ONCE per layer — the k-round one-hot buildup is
    ~18% of the E=8 step (PERF.md) and must not be traced twice.
    """
    return jnp.einsum("btec,btd->becd", dispatch.astype(x.dtype), x)


def einsum_combine(y_e: jax.Array, combine: jax.Array) -> jax.Array:
    """Combine ``(B, E, cap, d)`` expert outputs back to ``(B, T, d)``
    through the prebuilt gate-weighted ``combine`` tensor; dropped tokens
    contribute zero."""
    return jnp.einsum("btec,becd->btd", combine.astype(y_e.dtype), y_e)


# ---------------------------------------------------------------------------
# sort backend: slot->token permutation + segment gathers
# ---------------------------------------------------------------------------


def _dest_slots(r: Routing, cap: int) -> jax.Array:
    """Flat destination slot ``expert·cap + pos`` per assignment
    ``(B, T, k)`` int32; capacity-dropped assignments point one past the
    end (E·cap), where scatters drop and gathers are masked out."""
    e = r.probs.shape[-1]
    return jnp.where(
        r.keep > 0.0, r.idx * cap + r.pos, jnp.int32(e * cap)
    ).astype(jnp.int32)


def slot_to_token(r: Routing, cap: int) -> tuple[jax.Array, jax.Array]:
    """Invert the routing into the slot->token permutation.

    Returns ``(src, filled)``: ``src`` (B, E·cap) int32 maps each expert
    slot to the token index that fills it (0 where empty — masked by
    ``filled`` (B, E, cap) fp32). O(B·T·k) int32 scatter; kept slots are
    written exactly once (slot assignment is a bijection on kept
    assignments), drops fall off the end via ``mode="drop"``.
    """
    b, t, k = r.idx.shape
    e = r.probs.shape[-1]
    dest = _dest_slots(r, cap).reshape(b, t * k)
    tok = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None, :, None], (b, t, k)
    ).reshape(b, t * k)
    src = jnp.zeros((b, e * cap), jnp.int32)
    src = jax.vmap(lambda s, d, v: s.at[d].set(v, mode="drop"))(src, dest, tok)
    # A slot (e, c) is filled iff c < min(count_e, cap): per-expert fill
    # is sequential from 0, so filled slots are a prefix of each segment.
    filled = (
        jnp.arange(cap, dtype=jnp.float32)[None, None, :]
        < jnp.minimum(r.counts, float(cap))[:, :, None]
    ).astype(jnp.float32)
    return src, filled


def sort_dispatch(x: jax.Array, r: Routing, cap: int) -> jax.Array:
    """Dispatch by permutation: gather each slot's token row into its
    expert's contiguous segment. Data moved is O(B·E·cap·d) rows — no
    (B,T,E,cap) intermediates; empty slots are zeroed so the grouped FFN
    sees exactly what the einsum backend produces."""
    b, t, d = x.shape
    e = r.probs.shape[-1]
    src, filled = slot_to_token(r, cap)
    x_e = jnp.take_along_axis(x, src[..., None], axis=1)     # (B, E·cap, d)
    x_e = x_e * filled.reshape(b, e * cap, 1).astype(x.dtype)
    return x_e.reshape(b, e, cap, d)


def sort_combine(y_e: jax.Array, r: Routing, cap: int) -> jax.Array:
    """Combine by permutation: gather each assignment's expert output from
    its slot and sum the k gate-weighted contributions per token. Dropped
    assignments gather slot 0 of a clipped index but are zeroed by
    ``keep`` (the residual stream carries those tokens, Switch
    semantics)."""
    b, e, cap_, d = y_e.shape
    t, k = r.idx.shape[1], r.idx.shape[-1]
    dest = _dest_slots(r, cap)                               # (B, T, k)
    flat = y_e.reshape(b, e * cap, d)
    safe = jnp.minimum(dest, e * cap - 1).reshape(b, t * k)
    y_a = jnp.take_along_axis(flat, safe[..., None], axis=1).reshape(b, t, k, d)
    w = (r.gates * r.keep).astype(y_e.dtype)                 # (B, T, k)
    return jnp.sum(y_a * w[..., None], axis=2)


# ---------------------------------------------------------------------------
# Dropless routing over a held share of the experts (models/pattern.py,
# ffn kind ``moe_shared``). No capacity and no bound: every assignment that
# falls on an expert held here is computed. The router scores every
# published expert; this process holds ``held`` of them from ``first`` on
# and computes their part of the layer's sum. The held assignments are
# sorted by expert and run a tile of :data:`HELD_TILE_ROWS` rows of ONE
# expert at a time, in a loop whose trip count follows the tiles the step's
# routing fills: gather and the expert's three matmuls grow with the
# assignments there are — not with the published count, not with the room,
# and a layer the routers have left runs no tile. The tiles' rows are staged
# packed, in the sorted list's order, and added into the tokens' rows by one
# scatter for as many tiles as the staging holds: once a layer at routing
# near even, again for what an uneven step holds beyond that.
# ---------------------------------------------------------------------------

#: What :func:`held_experts` counts, in order (the trainer's per-step
#: counters; README "Observability"). ``moe_flushes``: the scatters into the
#: tokens' rows that the forward ran, 1 where the staging held the layer.
HELD_COUNTERS = ("moe_assigned_held", "moe_load_max", "moe_load_mean", "moe_dropped",
                 "moe_flushes")

#: Rows of one expert that go through its matmuls at a time. An expert's
#: last tile is part filled, so a step computes at most ``held`` tiles'
#: worth of rows more than it has assignments. A tile reads its expert's
#: weights and adds to their gradients whatever rows it holds, so few large
#: tiles beat many small ones (v5e, d 2048, width 512: 512 rows was the
#: fastest of 128 / 256 / 512, PERF.md section 6).
HELD_TILE_ROWS = 512

#: The staging's room over the held assignments of even routing. XLA's TPU
#: scatter-add updates its (tokens, d) operand in place, but every call sorts
#: its staged indices, permutes ALL staged rows into that order (written by a
#: tile or not) and then walks the operand: on a v5e at d 2048 a call costs
#: 0.56 ms for every 8,192 staged rows and 0.97 ms for every 134 MB of
#: operand, however few rows it adds (PERF.md section 6). So the loop flushes
#: as seldom as the staging allows and stages no row that holds nothing: a
#: quarter over even routing takes every layer of the benchmark's cells in one
#: flush (their fullest layers hold 1.05 x), and a step that holds more runs a
#: second. An eighth would save 0.3 ms a call there and a half would add 0.5.
HELD_STAGING_SLACK = 1.25


def held_staging_rows(assignments: int, held: int, published: int, tile: int) -> int:
    """Rows the loop stages between two scatters, from the shapes alone:
    the held share of ``assignments`` (token, choice) pairs at even routing
    and :data:`HELD_STAGING_SLACK` of it, in whole tiles, and one tile more
    for the last tile's unfilled rows."""
    even = assignments * held / published
    return (math.ceil(HELD_STAGING_SLACK * even / tile) + 1) * tile


def top_k_gates(scores: jax.Array, k: int, *, bias: jax.Array | None = None,
                eps: float = 0.0, scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """``k`` experts per row of ``scores`` (N, E) and their gates,
    renormalised: (gates (N, k) float32, expert ids (N, k) int32). The
    choice is by the largest ``scores`` — softmax probabilities, or
    per-expert sigmoid scores — plus, where given, a selection ``bias``
    (E,) that enters the choice ONLY: the gate is the chosen experts' own
    score over ``sum + eps``, times ``scale``, so no gradient reaches the
    bias. Without ``bias``, ``eps`` and ``scale`` the arithmetic is the
    softmax form's, operation for operation."""
    if bias is None:
        top, idx = jax.lax.top_k(scores, k)
    else:
        _, idx = jax.lax.top_k(scores + bias, k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    gates = top / (total + eps if eps else total)
    return (gates * scale if scale != 1.0 else gates), idx.astype(jnp.int32)


def bias_swapped(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """How many of the choices ``idx`` (N, k) plain top-``k`` of ``scores``
    (N, E) would not have made: those whose own score is under the row's
    ``k``-th largest. A float32 scalar."""
    kth = jax.lax.top_k(scores, idx.shape[1])[0][:, -1:]
    return jnp.sum(jnp.take_along_axis(scores, idx, axis=-1) < kth).astype(jnp.float32)


class _Tiles(NamedTuple):
    """The step's tiles, in expert order: per tile its expert, and the
    span of the expert-sorted assignment list it covers (``stop`` is where
    the expert's assignments end, so the last tile of an expert is cut
    there); ``count`` tiles hold an assignment."""

    expert: jax.Array  # (most,) int32
    start: jax.Array   # (most,) int32
    stop: jax.Array    # (most,) int32
    count: jax.Array   # () int32


def _plan_tiles(ends: jax.Array, slots: int, tile: int) -> _Tiles:
    """Tiles from the experts' cumulative loads ``ends`` (held,). ``most``
    is static: every expert's part-filled tile beside the full ones that
    ``slots`` assignments could make."""
    held = ends.shape[0]
    loads = jnp.diff(ends, prepend=0)
    last = jnp.cumsum((loads + tile - 1) // tile)           # tiles up to and with expert e
    at = jnp.arange(slots // tile + held, dtype=jnp.int32)
    expert = jnp.minimum(jnp.searchsorted(last, at, side="right"), held - 1).astype(jnp.int32)
    nth = at - jnp.concatenate([jnp.zeros(1, last.dtype), last])[expert]
    start = (ends - loads)[expert] + nth * tile
    return _Tiles(expert, start.astype(jnp.int32), ends[expert], last[-1].astype(jnp.int32))


def _tile_rows(t, tiles: _Tiles, order, k: int, tile: int):
    """Tile ``t``: (expert, slots into the flat (token, choice) list, their
    tokens, how many hold an assignment). Rows past the expert's end get
    indices past the arrays' ends, ascending, so every index list stays
    sorted and unique: such a row gathers zeros and its scatter is dropped."""
    start = tiles.start[t]
    spare = jnp.arange(tile, dtype=jnp.int32)
    valid = start + spare < tiles.stop[t]
    slots = jnp.where(valid, jax.lax.dynamic_slice_in_dim(order, start, tile), order.shape[0] + spare)
    return tiles.expert[t], slots, jnp.where(valid, slots // k, order.shape[0] + spare), jnp.sum(valid)


_SORTED = dict(indices_are_sorted=True, unique_indices=True)


def _take(a, index):
    return a.at[index].get(mode="fill", fill_value=0, **_SORTED)


def _mm(a, b, contract):
    """``a`` and ``b`` contracted over one axis each, float32 out."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _expert(w, e):
    return jax.lax.dynamic_index_in_dim(w, e, keepdims=False)


def _swiglu(xs, wg, wu):
    """(gate pre-activation, its sigmoid, up, hidden in the operands' type)."""
    a, b = _mm(xs, wg, (1, 0)), _mm(xs, wu, (1, 0))
    sig = jax.nn.sigmoid(a)
    return a, sig, b, (a * sig * b).astype(xs.dtype)


def _over_groups(tiles: _Tiles, tile: int, staged_rows: int, past, values, totals, one_tile, flush):
    """The loop over the step's tiles, as many at a time as ``staged_rows``
    hold. Tile ``t`` stages what it has for the tokens' rows at its place in
    the sorted list less the group's first tile's: packed, so the unfilled
    rows at an expert's end are overwritten by the next expert's first tile,
    and only a group's last tile leaves any. ``past()`` makes a group's index
    lists, every entry past the arrays' ends (dropped until a tile writes
    it); ``values`` are the staging arrays, carried over the groups and never
    cleared (a stale row's index drops it); ``one_tile(t, at, indices,
    values, totals)`` computes tile ``t`` and stages at row ``at``;
    ``flush(indices, values, totals)`` adds a group's rows into the totals.
    Returns the totals after the last group and how many groups there were."""

    def group(carry):
        first, groups, values, totals = carry
        base = tiles.start[first]

        def fits(c):
            return (c[0] < tiles.count) & (tiles.start[c[0]] + tile - base <= staged_rows)

        def step(c):
            t, *staged = c
            return t + 1, *one_tile(t, tiles.start[t] - base, *staged)

        t, indices, values, totals = jax.lax.while_loop(fits, step, (first, past(), values, totals))
        return t, groups + 1, values, flush(indices, values, totals)

    zero = jnp.zeros((), jnp.int32)
    _, groups, _, totals = jax.lax.while_loop(
        lambda c: c[0] < tiles.count, group, (zero, zero, values, totals))
    return totals, groups


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _held_tiles(x, gates, w_gate, w_up, w_down, order, tiles, k, tile, staged_rows):
    """``y[token] += down_e(silu(gate_e x) * up_e x) * gate`` over the tiles
    that hold assignments; also the assignments it computed and the scatters
    it took. Its own backward: the loop's trip count is the step's, which
    reverse-mode differentiation of a loop cannot follow; the backward walks
    the same tiles, recomputes each one's hidden and pulls ``dy`` through it,
    so nothing is kept per tile; the weights' gradients add up in float32."""
    return _held_tiles_fwd(x, gates, w_gate, w_up, w_down, order, tiles, k, tile, staged_rows)[0]


def _put(staged, value, at):
    return jax.lax.dynamic_update_slice_in_dim(staged, value, at, 0)


def _staging(*shape):
    """A staging array of float32 values, not cleared: a row counts only
    once a tile has written it and its index (on the TPU the buffer is
    allocated and nothing more; other backends hand out zeros)."""
    return jax.lax.empty(shape, jnp.float32)


def _held_tiles_fwd(x, gates, w_gate, w_up, w_down, order, tiles, k, tile, staged_rows):
    flat = gates.reshape(-1)

    def past():
        return jnp.full((staged_rows,), order.shape[0], jnp.int32)

    def one_tile(t, at, rows_all, ys_all, totals):
        y, done = totals
        with jax.named_scope("dispatch"):
            e, slots, rows, filled = _tile_rows(t, tiles, order, k, tile)
            xs, weight = _take(x, rows), _take(flat, slots)[:, None]
        with jax.named_scope("experts"):
            *_, h = _swiglu(xs, _expert(w_gate, e), _expert(w_up, e))
            ys = _mm(h, _expert(w_down, e), (1, 0)) * weight
        return _put(rows_all, rows, at), _put(ys_all, ys, at), (y, done + filled)

    def flush(rows_all, ys_all, totals):
        y, done = totals
        with jax.named_scope("combine"):
            return y.at[rows_all].add(ys_all, mode="drop"), done

    totals = (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32))
    (y, done), flushes = _over_groups(
        tiles, tile, staged_rows, past, _staging(staged_rows, x.shape[1]), totals, one_tile, flush)
    return (y, done, flushes), (x, gates, w_gate, w_up, w_down, order, tiles)


def _held_tiles_bwd(k, tile, staged_rows, res, cotangents):
    x, gates, w_gate, w_up, w_down, order, tiles = res
    dy, *_ = cotangents
    flat = gates.reshape(-1)
    dtype = x.dtype

    def past():
        index = jnp.full((staged_rows,), order.shape[0], jnp.int32)
        return index, index

    def add_at(acc, e, g):  # one expert's slice, in place
        return jax.lax.dynamic_update_index_in_dim(acc, _expert(acc, e) + g, e, 0)

    def one_tile(t, at, indices, values, totals):
        (rows_all, slots_all), (dxs_all, dweight_all), (dx, dflat, dwg, dwu, dwd) = indices, values, totals
        with jax.named_scope("dispatch"):
            e, slots, rows, _ = _tile_rows(t, tiles, order, k, tile)
            xs, weight, dys = _take(x, rows), _take(flat, slots)[:, None], _take(dy, rows)
        with jax.named_scope("experts"):
            wg_e, wu_e, wd_e = _expert(w_gate, e), _expert(w_up, e), _expert(w_down, e)
            a, sig, b, h = _swiglu(xs, wg_e, wu_e)
            dweight = jnp.sum(dys * _mm(h, wd_e, (1, 0)), axis=-1)
            do = (dys * weight).astype(dtype)
            dh = _mm(do, wd_e, (1, 1))
            da = (dh * b * sig * (1 + a * (1 - sig))).astype(dtype)
            db = (dh * a * sig).astype(dtype)
            dxs = _mm(da, wg_e, (1, 1)) + _mm(db, wu_e, (1, 1))
            dwg, dwu, dwd = (add_at(acc, e, _mm(lhs, rhs, (0, 0))) for acc, lhs, rhs in
                             ((dwg, xs, da), (dwu, xs, db), (dwd, h, do)))
        return ((_put(rows_all, rows, at), _put(slots_all, slots, at)),
                (_put(dxs_all, dxs, at), _put(dweight_all, dweight, at)),
                (dx, dflat, dwg, dwu, dwd))

    def flush(indices, values, totals):
        (rows_all, slots_all), (dxs_all, dweight_all), (dx, dflat, *dws) = indices, values, totals
        with jax.named_scope("combine"):
            return (dx.at[rows_all].add(dxs_all, mode="drop"),
                    dflat.at[slots_all].add(dweight_all, mode="drop"), *dws)

    values = (_staging(staged_rows, x.shape[1]), _staging(staged_rows))
    totals = (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(flat),
              *(jnp.zeros(w.shape, jnp.float32) for w in (w_gate, w_up, w_down)))
    (dx, dflat, dwg, dwu, dwd), _ = _over_groups(
        tiles, tile, staged_rows, past, values, totals, one_tile, flush)
    no = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731  (integer inputs)
    return (dx.astype(dtype), dflat.reshape(gates.shape),
            dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype), dwd.astype(w_down.dtype),
            no(order), jax.tree.map(no, tiles))


_held_tiles.defvjp(_held_tiles_fwd, _held_tiles_bwd)


def held_experts(x, gates, idx, w_gate, w_up, w_down, *, first: int, published: int):
    """``sum_e gate_e * down_e(silu(gate_e x) * up_e x)`` over the held
    experts ``[first, first + held)`` of the ``published`` ones the router
    scores, for tokens ``x`` (N, d) with their ``(N, k)`` gates and expert
    ids; the matmuls take their operands in ``x``'s type. Returns ``(y (N,
    d) float32, counters (5,) float32)`` in :data:`HELD_COUNTERS` order;
    ``moe_dropped`` is the held assignments less those the loop over tiles
    computed."""
    k = idx.shape[1]
    held = w_gate.shape[0]
    tile = HELD_TILE_ROWS
    staged_rows = held_staging_rows(idx.size, held, published, tile)
    with jax.named_scope("dispatch"):
        local = idx.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        # Loads from the sorted keys: expert e's assignments end where the
        # first key > e stands.
        ends = jnp.searchsorted(
            key[order], jnp.arange(1, held + 1, dtype=key.dtype), side="left").astype(jnp.int32)
        tiles = _plan_tiles(ends, key.shape[0], tile)
        # room for the last tile's slice to stay inside the list
        order = jnp.pad(order, (0, tile))
    y, done, flushes = _held_tiles(x, gates, w_gate, w_up, w_down, order, tiles, k, tile, staged_rows)
    loads = jnp.diff(ends, prepend=0).astype(jnp.float32)
    n_held = ends[-1]
    counters = jnp.stack([n_held.astype(jnp.float32), jnp.max(loads), jnp.mean(loads),
                          (n_held - done).astype(jnp.float32), flushes.astype(jnp.float32)])
    return y, counters
