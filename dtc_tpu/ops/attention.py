"""Attention ops with pluggable implementations.

The reference hard-codes one O(T²)-memory einsum attention that materialises
the full ``(B, H, T, T)`` score tensor and an additive ``-1e9`` mask built in
the embedding layer (`/root/reference/model/CausalSelfAttention.py:34-42`,
`/root/reference/model/GPTModel.py:50-51`). Here attention is an *op* with
three implementations behind one interface:

- ``dense``  — XLA einsum path, fp32 softmax, mask fused via ``where`` on an
  iota comparison (no (1,1,T,T) mask buffer travels through the model).
  Reference semantics; used for CPU tests and as the autodiff baseline.
- ``flash``  — blockwise Pallas TPU kernel (ops/flash_attention.py): O(T)
  memory, VMEM-tiled, for long sequences.
- ``ring``   — sequence-parallel ring attention (ops/ring_attention.py):
  KV blocks rotate over the mesh via ppermute while queries stay put.

``auto`` picks flash on TPU when shapes are tile-friendly, else dense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e9  # matches the reference's additive mask value


def _on_tpu() -> bool:
    # No try/except: a backend that fails to come up must fail the run,
    # not quietly route `attention: auto` to the dense path.
    return jax.devices()[0].platform == "tpu"


def dense_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Reference-semantics causal attention.

    Args are ``(B, T, H, D)``. Scores and softmax run in float32 regardless
    of input dtype (bf16-safe); output is cast back to the input dtype.
    Exactly :func:`decode_attention` with a zero offset and full-length
    keys — ONE masked-softmax core serves both training and decode, so
    their numerics cannot drift apart.
    """
    return decode_attention(q, k, v, jnp.int32(0))


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, start: jax.Array
) -> jax.Array:
    """Attention for KV-cache decode: ``q`` is ``(B, T_new, H, D)`` for the
    tokens being appended at position ``start``; ``k``/``v`` are the FULL
    cache ``(B, S, H, D)`` (valid through ``start + T_new``). Causality:
    query row r (global position start + r) sees cache columns
    ``col <= start + r``; columns beyond the write frontier are masked the
    same way. fp32 scores/softmax, same -1e9 semantics as training.

    ``start`` is a scalar (every batch row at the same position — the
    ``generate`` path) or a ``(B,)`` vector of per-row write frontiers
    (the serving runtime's continuous-batching slots, each request at its
    own position)."""
    b, t, h, d = q.shape
    s = k.shape[1]
    if k.shape[2] != h:
        # Grouped KV heads: q head i reads KV head i // (h / h_kv). The
        # plain path repeats them (the flash kernels index instead).
        k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    scale = d ** -0.5
    scores = jnp.einsum(
        "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
    ) * scale
    row = jax.lax.broadcasted_iota(jnp.int32, (t, s), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, s), 1)
    if getattr(start, "ndim", 0) == 1:
        # Per-row frontier: mask is (B, T, S), one frontier per batch row.
        mask = col[None] <= start[:, None, None] + row[None]
        scores = jnp.where(mask[:, None], scores, NEG_INF)
    else:
        mask = col <= start + row
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", weights.astype(v.dtype), v)
    return out.astype(q.dtype)


def resolve_impl(
    impl: str, t: int, d: int, block_q: int = 512, block_kv: int = 512
) -> str:
    """The implementation :func:`causal_attention` runs for ``impl`` at
    sequence length ``t`` and head_dim ``d``: ``auto`` is flash on a TPU
    when the tiling divides, else dense; anything else is itself."""
    if impl != "auto":
        return impl
    from dtc_tpu.ops import flash_attention

    # head_dim is zero-padded to the lane width inside the kernel, so the
    # flagship shape (head_dim=32, T=512) qualifies; only the sequence
    # tiling has to divide.
    if _on_tpu() and t >= 256 and flash_attention.supports(t, d, block_q, block_kv):
        return "flash"
    return "dense"


def flash_plan_event(cfg) -> dict | None:
    """Fields of the trainer's one ``flash_plan`` start-up event: what the
    flash kernel will do with the score square under ``cfg`` (a
    ModelConfig), or None where its attention does not resolve to flash
    on this backend. See :func:`flash_attention.schedule`."""
    from dtc_tpu.config.schema import DTYPE_BYTES
    from dtc_tpu.ops import flash_attention

    blocks = (
        cfg.attention_block_q, cfg.attention_block_kv,
        cfg.attention_block_q_bwd, cfg.attention_block_kv_bwd,
    )
    t, d = cfg.max_seq_len, cfg.head_dim
    if resolve_impl(cfg.attention, t, d, *blocks[:2]) != "flash":
        return None
    if cfg.kv_heads != cfg.n_heads:
        return None  # KV groups: the transposed family, tiled as configured
    plan = flash_attention.schedule(
        t, cfg.n_heads, d, DTYPE_BYTES.get(cfg.compute_dtype, 4), *blocks
    )
    if plan is None:
        return None
    return {"seq_len": t, "head_dim": d, "heads": cfg.n_heads, **plan}


def _flash_per_shard(q, k, v, spec: P | None, **blocks) -> jax.Array:
    """The flash kernel on each device's own (batch, heads) shard.

    XLA cannot partition a Mosaic kernel — on a mesh of more than one
    device the TPU lowering refuses it outright ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map";
    interpret mode on the CPU mesh never shows this). Attention is
    independent across batch rows and heads, so the kernel run per shard
    inside a region that is manual over every mesh axis IS the sharded
    op. A sharded sequence axis is not — that is ring / Ulysses — and is
    refused here. A dimension its mesh axis does not divide (the
    batch-1 ``model.init`` trace) is computed whole on every device of
    that axis instead."""
    from flax import linen as nn
    from jax._src.core import trace_state_clean

    from dtc_tpu.ops.flash_attention import flash_causal_attention
    from dtc_tpu.parallel.sharding import ambient_mesh

    flash = functools.partial(flash_causal_attention, **blocks)
    mesh = None if trace_state_clean() else ambient_mesh(allow_empty=True)
    free = set() if mesh is None else set(mesh.axis_names) - set(mesh.manual_axes)
    if mesh is None or mesh.size == 1 or not free:
        return flash(q, k, v)  # one device, or already fully manual
    if spec is None:
        rules = dict(nn.get_logical_axis_rules())
        spec = P(*(rules.get(ax) for ax in ("batch", "seq", "heads", "head_dim")))
    sizes = dict(mesh.shape)
    spec = P(*(
        ax if ax in free and dim % sizes[ax] == 0 else None
        for ax, dim in zip(tuple(spec) + (None,) * (4 - len(spec)), q.shape)
    ))
    if spec[1] is not None:
        raise ValueError(
            f"flash attention needs the whole sequence on each device, but "
            f"seq is sharded over {spec[1]!r}; use attention: ring or ulysses"
        )
    return shard_map(
        flash, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        axis_names=free, check_vma=False,
    )(q, k, v)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    block_q: int = 512,
    block_kv: int = 512,
    block_q_bwd: int = 0,
    block_kv_bwd: int = 0,
    spec: P | None = None,
) -> jax.Array:
    """Dispatch causal self-attention over ``(B, T, H, D)`` tensors.
    ``spec`` says how the caller laid q/k/v out over the ambient mesh
    (None: the active logical rules' batch/seq/heads/head_dim mapping);
    only the flash kernel needs to be told — see :func:`_flash_per_shard`."""
    impl = resolve_impl(impl, q.shape[1], q.shape[3], block_q, block_kv)
    if impl == "dense":
        return dense_causal_attention(q, k, v)
    if impl == "flash":
        return _flash_per_shard(
            q, k, v, spec, block_q=block_q, block_kv=block_kv,
            block_q_bwd=block_q_bwd, block_kv_bwd=block_kv_bwd,
        )
    if impl == "ring":
        from dtc_tpu.ops.ring_attention import ring_causal_attention

        return ring_causal_attention(q, k, v)
    if impl == "ulysses":
        from dtc_tpu.ops.ulysses_attention import ulysses_causal_attention

        return ulysses_causal_attention(
            q, k, v, block_q=block_q, block_kv=block_kv,
            block_q_bwd=block_q_bwd, block_kv_bwd=block_kv_bwd,
        )
    raise ValueError(f"unknown attention impl {impl!r}")
