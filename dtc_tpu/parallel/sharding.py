"""Logical-axis sharding: one declarative rule table instead of per-strategy code.

The reference decides parameter sharding by substring-matching flax param
paths against a ``parallel: str`` (`/root/reference/parallel/sharding.py:17-62`)
and scatters per-strategy ``with_sharding_constraint`` branches through the
model (`/root/reference/model/MLP.py:16-24`). Here the model names its axes
*logically* and a single rule table maps logical -> mesh axes:

- DP is the mesh having ``data > 1`` (batch axis sharded, params replicated
  because ``model == 1`` makes every param spec a no-op),
- TP (Megatron-style) is ``model > 1`` (column-parallel qkv/fc1, row-parallel
  out_proj/fc2, vocab-parallel lm_head — XLA inserts the all-reduces),
- DP×TP needs no new rules at all.

The table below is data, exhaustively unit-tested in
``tests/test_sharding.py`` — an unknown param path is an error, so the table
can never silently drift from the model.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.tree_util import tree_map_with_path

PyTree = Any

# --------------------------------------------------------------------------
# Logical axis names. "Rules" map these to mesh axis names (or None).
# --------------------------------------------------------------------------

#: Canonical logical->mesh rules. Axes not listed map to None (replicated /
#: unsharded). This single table covers DP, TP, DP×TP and the GSPMD part of
#: 3D; strategy choice lives entirely in the mesh *shape*.
DEFAULT_RULES: tuple[tuple[str, str | None], ...] = (
    ("batch", "data"),        # batch dim of activations and inputs
    ("heads", "model"),       # attention head axis (activations)
    ("qkv", "model"),         # column-parallel projection outputs
    ("mlp", "model"),         # column-parallel MLP hidden
    ("vocab_out", "model"),   # vocab-parallel lm_head
    ("embed", None),          # d_model axis (activations)
    ("embed_p", None),        # d_model axis of PARAMS (FSDP shards this)
    ("seq", None),            # sequence axis (ring attention remaps this)
    ("head_dim", None),
    ("layers", None),         # scan-over-layers axis (PP reshapes it, see pipeline.py)
    ("stages", "pipe"),       # leading axis of stacked pipeline-stage params
    ("vocab_in", None),       # wte rows (gather-indexed; kept replicated)
    ("seqpos", None),         # wpe rows
    ("microbatch", None),     # leading microbatch axis of PP inputs
    # Expert parallelism (MoE): the expert axis of activations and of
    # expert params shards over "model" — XLA emits the token<->expert
    # all-to-alls from these two entries alone. The experts' d_ff axis
    # stays unsharded (one mesh axis cannot shard two axes of one tensor).
    # BOTH dispatch backends (ops/moe_dispatch.py einsum | sort) constrain
    # their (B, E, cap, d) expert groups with the same "experts" axis, so
    # these rows are the whole EP story for either; the all-to-alls'
    # presence per backend is pinned on compiled HLO in
    # tests/test_collectives_hlo.py.
    ("experts", "model"),     # expert axis of grouped-token activations
    ("experts_p", "model"),   # expert axis of expert PARAMS (EP memory win)
)

#: FSDP / ZeRO-3: every parameter's d_model axis shards over the SAME mesh
#: axis the batch uses ("data"), so per-device param+optimizer memory drops
#: by the data-parallel degree. No new collectives are written anywhere:
#: XLA's partitioner all-gathers each layer's weights at use (inside the
#: layer scan, so only one layer's worth is ever resident) and the
#: all-gather's transpose — a reduce-scatter — lands the gradient shards,
#: which is exactly the ZeRO-3 schedule. Activation axes are untouched.
FSDP_RULES: tuple[tuple[str, str | None], ...] = tuple(
    (name, "data") if name == "embed_p" else (name, axis)
    for name, axis in DEFAULT_RULES
)

def ring_rules_from(
    rules: tuple[tuple[str, str | None], ...],
) -> tuple[tuple[str, str | None], ...]:
    """Derive ring-attention / sequence-parallel rules from any base table:
    the sequence axis of activations shards over "model" and KV blocks
    rotate via ppermute (ops/ring_attention.py). The "model" mesh axis then
    carries SEQUENCE parallelism, so the Megatron TP mappings
    (heads/qkv/mlp/vocab_out) must come off it — one mesh axis cannot shard
    two logical axes of one tensor. Everything else (e.g. FSDP's embed_p ->
    data) passes through, so ring composes with DP and FSDP alike."""
    return tuple(
        (name, "model") if name == "seq"
        else (name, None) if name in ("heads", "qkv", "mlp", "vocab_out")
        else (name, axis)
        for name, axis in rules
    )


RING_RULES: tuple[tuple[str, str | None], ...] = ring_rules_from(DEFAULT_RULES)


def ambient_mesh(allow_empty: bool = False):
    """The mesh in scope for an op entering a nested ``shard_map``.

    Under a jit trace this is the ABSTRACT mesh — which carries per-axis
    Manual/Auto state, so a partial-manual region nests correctly inside
    another manual computation (e.g. the pipeline's shard_map over
    "pipe") — falling back to the physical mesh installed by the
    trainer's ``with mesh:`` context. One definition shared by ring
    attention and the overlapped-collectives ops (ISSUE 12), so every
    nested-manual op resolves its mesh identically."""
    from jax.sharding import get_abstract_mesh

    amesh = get_abstract_mesh()
    if not amesh.empty:
        return amesh
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    if mesh.empty:
        if allow_empty:
            return None
        raise RuntimeError(
            "this op needs an active mesh context (`with mesh:`); "
            "none is installed"
        )
    return mesh


def fsdp_axis_in_scope() -> str | None:
    """The mesh axis FSDP shards parameter storage over, visible from
    inside model code — or None when FSDP is not in effect.

    Reads the ACTIVE flax logical-axis rules (the trainer's
    ``nn.logical_axis_rules(rules)`` context): the "embed_p" logical axis
    maps to a mesh axis exactly when FSDP_RULES (or a derivation like
    ``ring_rules_from(FSDP_RULES)``) is installed, and that axis must be
    non-trivial on the ambient mesh. This is how the overlapped
    collectives (ops/overlap_collectives.py, ISSUE 12) find the ring: the
    rule table stays the single source of parallelism truth — no new
    config plumbing into the model."""
    from flax import linen as nn

    rules = dict(nn.get_logical_axis_rules())
    axis = rules.get("embed_p")
    if not isinstance(axis, str):
        return None
    mesh = ambient_mesh(allow_empty=True)
    if mesh is None:
        return None
    sizes = dict(zip(mesh.axis_names, (int(s) for s in mesh.shape.values())))
    seq = rules.get("seq")
    if isinstance(seq, str) and sizes.get(seq, 1) > 1:
        # Sequence-parallel rules (ring/ulysses derivations): activations
        # are seq-sharded between layers, which the overlap ring's
        # batch×full-seq region layout would silently re-gather. Defer to
        # SP — the serialized path runs; overlap+SP composition is future
        # work (README "Overlapped collectives").
        return None
    return axis if sizes.get(axis, 1) > 1 else None


def logical_to_spec(axes: Sequence[str | None], rules: Sequence[tuple[str, str | None]]) -> P:
    """Map a tuple of logical axis names to a PartitionSpec under ``rules``."""
    table = dict(rules)
    out = []
    for ax in axes:
        if ax is None:
            out.append(None)
        else:
            if ax not in table:
                raise KeyError(f"logical axis {ax!r} not covered by rules {sorted(table)}")
            out.append(table[ax])
    return P(*out)


def batch_spec(rules: Sequence[tuple[str, str | None]] = DEFAULT_RULES) -> P:
    """PartitionSpec for an int32 ``(batch, seq)`` token batch."""
    return logical_to_spec(("batch", "seq"), rules)


# --------------------------------------------------------------------------
# Param-path -> logical axes table for the GPT model in dtc_tpu.models.gpt.
#
# Keys match on the *suffix* of the flax param path; the scan-over-layers
# transform stacks every block param with a leading "layers" axis (mirroring
# the reference's rank-3 layout, /root/reference/model/GPTModel.py:57-65),
# which is what makes both TP specs and PP stage-chunking mechanical.
# --------------------------------------------------------------------------

PARAM_AXES_TABLE: tuple[tuple[tuple[str, ...], tuple[str | None, ...]], ...] = (
    # "embed_p" is the d_model axis of PARAMS — distinct from the
    # activation axis "embed" so FSDP can shard parameter storage without
    # touching activation layouts (both map to None outside FSDP).
    (("wte", "embedding"), ("vocab_in", "embed_p")),
    (("wpe", "embedding"), ("seqpos", "embed_p")),
    (("ln_f", "scale"), ("embed_p",)),
    (("ln_f", "bias"), ("embed_p",)),
    (("lm_head", "kernel"), ("embed_p", "vocab_out")),
    (("lm_head", "bias"), ("vocab_out",)),
    # --- per-block params; leading "layers" axis from nn.scan ---
    (("ln_1", "scale"), ("layers", "embed_p")),
    (("ln_1", "bias"), ("layers", "embed_p")),
    (("ln_2", "scale"), ("layers", "embed_p")),
    (("ln_2", "bias"), ("layers", "embed_p")),
    (("q_proj", "kernel"), ("layers", "embed_p", "qkv")),
    (("q_proj", "bias"), ("layers", "qkv")),
    (("k_proj", "kernel"), ("layers", "embed_p", "qkv")),
    (("k_proj", "bias"), ("layers", "qkv")),
    (("v_proj", "kernel"), ("layers", "embed_p", "qkv")),
    (("v_proj", "bias"), ("layers", "qkv")),
    (("out_proj", "kernel"), ("layers", "qkv", "embed_p")),
    (("out_proj", "bias"), ("layers", "embed_p")),
    (("fc1", "kernel"), ("layers", "embed_p", "mlp")),
    (("fc1", "bias"), ("layers", "mlp")),
    (("fc2", "kernel"), ("layers", "mlp", "embed_p")),
    (("fc2", "bias"), ("layers", "embed_p")),
    # --- MoE (moe_experts > 0): router replicated, experts EP-sharded ---
    (("moe", "router", "kernel"), ("layers", "embed_p", None)),
    (("moe", "wi"), ("layers", "experts_p", "embed_p", None)),
    (("moe", "bi"), ("layers", "experts_p", None)),
    (("moe", "wo"), ("layers", "experts_p", None, "embed_p")),
    (("moe", "bo"), ("layers", "experts_p", "embed_p")),
    # --- layer-pattern models (models/pattern.py): leaves stack over
    # PERIODS (the scan's axis, "layers" again); q/k/v/out_proj kernels and
    # the router take the rows above. Norm gains over a head, the
    # convolution taps, the per-head decay parameters and the router's
    # selection bias are replicated; so is the exit gate's bias. A leading
    # layer's leaves (under "leading") are not stacked: they take the same
    # rows less "layers". A looped stack's passes share every leaf.
    (("norm_1", "scale"), ("layers", "embed_p")),
    (("norm_2", "scale"), ("layers", "embed_p")),
    (("norm_1_post", "scale"), ("layers", "embed_p")),
    (("norm_2_post", "scale"), ("layers", "embed_p")),
    (("norm_f", "scale"), ("embed_p",)),
    (("lm_head",), ("embed_p", "vocab_out")),
    (("exit_gate", "kernel"), ("embed_p", None)),
    (("exit_gate", "bias"), (None,)),
    (("q_norm", "scale"), ("layers", None)),
    (("k_norm", "scale"), ("layers", None)),
    (("in_proj_qkvz", "kernel"), ("layers", "embed_p", "qkv")),
    (("in_proj_ba", "kernel"), ("layers", "embed_p", None)),
    (("gdn", "conv"), ("layers", None, None)),
    (("gdn", "A_log"), ("layers", None)),
    (("gdn", "dt_bias"), ("layers", None)),
    (("gdn", "norm", "scale"), ("layers", None)),
    (("shortconv", "in_proj", "kernel"), ("layers", "embed_p", "qkv")),
    (("shortconv", "conv"), ("layers", None, None)),
    (("moe", "expert_bias"), ("layers", None)),
    (("moe", "w_gate"), ("layers", "experts_p", "embed_p", None)),
    (("moe", "w_up"), ("layers", "experts_p", "embed_p", None)),
    (("moe", "w_down"), ("layers", "experts_p", None, "embed_p")),
    (("shared_gate", "kernel"), ("layers", "embed_p", None)),
    (("gate_proj", "kernel"), ("layers", "embed_p", "mlp")),
    (("up_proj", "kernel"), ("layers", "embed_p", "mlp")),
    (("down_proj", "kernel"), ("layers", "mlp", "embed_p")),
)


def _path_names(path: tuple) -> tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def logical_axes_for_path(path: tuple) -> tuple[str | None, ...]:
    names = _path_names(path)
    for suffix, axes in PARAM_AXES_TABLE:
        if names[-len(suffix):] == suffix:
            return axes[1:] if "leading" in names and axes[:1] == ("layers",) else axes
    raise KeyError(
        f"param path {'/'.join(names)} has no entry in PARAM_AXES_TABLE — "
        "add one (sharding must be explicit for every param)"
    )


def param_logical_axes(params: PyTree) -> PyTree:
    """Tree of logical-axes tuples, same structure as ``params``."""

    def get(path, leaf):
        axes = logical_axes_for_path(path)
        if len(axes) != leaf.ndim:
            raise ValueError(
                f"param {'/'.join(_path_names(path))} has rank {leaf.ndim} "
                f"but table gives axes {axes}"
            )
        return axes

    return tree_map_with_path(get, params)


def param_specs(params: PyTree, rules: Sequence[tuple[str, str | None]] = DEFAULT_RULES) -> PyTree:
    """Tree of PartitionSpecs for the param tree under ``rules``."""
    axes_tree = param_logical_axes(params)
    return jax.tree.map(
        lambda axes: logical_to_spec(axes, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def shard_params(
    params: PyTree, mesh: Mesh, rules: Sequence[tuple[str, str | None]] = DEFAULT_RULES
) -> tuple[PyTree, PyTree]:
    """Place ``params`` on the mesh per the rule table.

    Returns ``(sharded_params, spec_tree)`` — same contract as the
    reference's ``get_sharded_params`` (`/root/reference/parallel/sharding.py:11`).
    """
    specs = param_specs(params, rules)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    sharded = jax.device_put(params, shardings)
    return sharded, specs
