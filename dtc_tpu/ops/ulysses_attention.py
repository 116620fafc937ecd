"""Ulysses sequence parallelism — all-to-all head-sharded attention.

The second sequence-parallel scheme next to ring attention
(ops/ring_attention.py), after DeepSpeed-Ulysses: activations travel the
network SEQUENCE-sharded over the "model" mesh axis (same RING_RULES layout
— LN/MLP/projections are embarrassingly sequence-parallel), and at the
attention boundary the shard axis is SWAPPED — sequence gathered, heads
scattered — so each device runs ordinary *local* causal attention over the
full sequence for its n_heads/P heads, then swaps back.

TPU-native design: the swap is NOT a hand-written collective. It is two
sharding constraints — seq-sharded -> head-sharded and back — and XLA's
SPMD partitioner emits the all-to-alls over ICI. Consequences the explicit
ring cannot have:

- The inner computation is just ``causal_attention(impl="auto")``: the
  packed Pallas flash kernel runs unchanged (ring needed dedicated
  block kernels and a whole-ring custom VJP).
- No nested ``shard_map``, so Ulysses composes with PIPELINE parallelism
  (the ring's manual region cannot nest inside the pipeline's — the
  trainer rejects that combination; Ulysses it accepts).
- Backward is plain autodiff; the all-to-alls transpose to all-to-alls.

Tradeoffs vs ring (when to use which): Ulysses moves 4 × activation-sized
all-to-alls per layer and needs n_heads % P == 0 (parallelism capped by
head count); ring moves 2 × KV per ring step with compute that hides the
transfers and scales to any P dividing the sequence. Reference anchor:
SURVEY §2.2 lists Ulysses as absent upstream ("not required for parity");
this implements it anyway for capability completeness.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def ulysses_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "model",
    block_q: int = 512,
    block_kv: int = 512,
    block_q_bwd: int = 0,
    block_kv_bwd: int = 0,
) -> jax.Array:
    """Causal attention over ``(B, T, H, D)`` with T sharded over
    ``axis_name`` on entry/exit and H sharded inside. Call under an active
    mesh; ``H`` must divide evenly by the axis size."""
    from jax._src.core import trace_state_clean

    from dtc_tpu.ops.attention import causal_attention, dense_causal_attention
    from dtc_tpu.ops.ring_attention import _ambient_mesh

    if trace_state_clean():
        # Eager call (flax model.init): constraints need a jit trace; the
        # dense path is numerically identical and init only needs shapes.
        return dense_causal_attention(q, k, v)

    mesh = _ambient_mesh()
    par = mesh.shape[axis_name]
    h = q.shape[2]
    if par > 1 and h % par != 0:
        raise ValueError(
            f"ulysses attention needs n_heads ({h}) divisible by the "
            f"'{axis_name}' axis size ({par})"
        )
    # seq-sharded -> head-sharded: XLA inserts the all-to-all.
    head_spec = P(None, None, axis_name, None)
    q, k, v = (jax.lax.with_sharding_constraint(x, head_spec) for x in (q, k, v))
    out = causal_attention(
        q, k, v, impl="auto", block_q=block_q, block_kv=block_kv,
        block_q_bwd=block_q_bwd, block_kv_bwd=block_kv_bwd, spec=head_spec,
    )
    # head-sharded -> seq-sharded: the inverse all-to-all.
    return jax.lax.with_sharding_constraint(out, P(None, axis_name, None, None))
