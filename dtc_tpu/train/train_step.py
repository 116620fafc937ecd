"""Compiled train steps.

One ``jax.jit`` step covers single-device, DP, TP, and DP×TP: the reference's
per-strategy input-constraint branch (`/root/reference/train/create_train_step.py:37-44`)
collapses into the logical batch spec, and XLA's SPMD partitioner derives
every collective (DP gradient all-reduce, TP all-gather / all-reduce) from
the sharding annotations — no hand-written communication.

Pipeline (and 3D) steps live in ``dtc_tpu.parallel.pipeline`` and are
selected by :func:`create_train_step` when the mesh's ``pipe`` axis is > 1.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from flax.training.train_state import TrainState
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dtc_tpu.parallel.sharding import DEFAULT_RULES

PyTree = Any


def normalize_spec(spec: P, mesh: Mesh) -> P:
    """Canonicalize a PartitionSpec the way GSPMD does: drop mesh axes of
    size 1 (sharding over them is a no-op) and strip trailing ``None``
    entries, so ``P(None, 'data', 'model')`` on a model=1 mesh becomes
    ``P(None, 'data')`` and ``P(None, None)`` becomes ``P()``.

    Initial placement and the step's out_shardings both use this form;
    without it they disagree with the compiler's normalized outputs and
    every run pays a second identical-program compile (see
    :func:`state_shardings`).
    """
    def keep(part):
        if part is None:
            return None
        if isinstance(part, str):
            return part if mesh.shape.get(part, 1) > 1 else None
        live = tuple(a for a in part if mesh.shape.get(a, 1) > 1)
        return live if live else None

    parts = [keep(p) for p in spec]
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def state_shardings(state: TrainState, mesh: Mesh) -> PyTree:
    """Per-leaf NamedShardings of a placed TrainState (replicated P() for
    any leaf not already carrying a mesh sharding — optax counts, step).

    Used as the step's ``out_shardings`` so the updated state leaves the
    executable with EXACTLY its input shardings. Without this, GSPMD
    normalizes degenerate specs (e.g. ``P(None, 'model')`` on a mesh where
    model=1 collapses to ``P()``), so the first step's donated output no
    longer matches the second step's input signature and XLA silently
    compiles a SECOND executable for the same step — a cold-start cost the
    obs subsystem's compile watcher surfaced (README "Observability").
    """
    def leaf(a: Any) -> NamedSharding:
        if isinstance(a, jax.Array) and isinstance(a.sharding, NamedSharding):
            return a.sharding
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf, state)


def canonicalize_state_placement(state: TrainState, mesh: Mesh) -> TrainState:
    """Commit every non-mesh leaf (optax counts on the default device,
    the Python-int ``step``) to a replicated NamedSharding with a strong
    dtype, so step N's input signature equals step 1's."""
    def leaf(a: Any) -> Any:
        if isinstance(a, jax.Array) and isinstance(a.sharding, NamedSharding):
            return a
        arr = jnp.asarray(a)
        if arr.weak_type:
            arr = jax.lax.convert_element_type(arr, arr.dtype)
        return jax.device_put(arr, NamedSharding(mesh, P()))

    return jax.tree.map(leaf, state)


def resolve_precision(opt_cfg, model_cfg):
    """Route ``OptimConfig.precision`` onto the model config — the exact
    pattern of :func:`resolve_collectives`, so every train-step consumer
    (trainer, bench, audit lowering) resolves the policy through ONE
    definition and the lowered-and-audited program cannot diverge from the
    trained one.

    - ``fp32`` (default): the model config passes through untouched —
      every existing program is byte-identical.
    - ``bf16_mixed``: the model stores bf16 params and runs bf16 matmuls
      (``param_dtype``/``compute_dtype`` both lifted to ``bfloat16``);
      the fp32 master weights + fp32 AdamW moments live in the optimizer
      (``train/optimizer.with_master_weights`` — create_optimizer reads
      the same knob). The model's fp32-mandatory islands (softmax, LN
      variance, CE loss) are fp32 by construction in models/gpt.py and
      certified by the graph auditor's numerics pass. float16 configs are
      rejected: fp16 needs loss scaling this repo does not implement, and
      silently training fp16 under a knob named bf16_mixed would be worse
      than an error.
    """
    import dataclasses

    if getattr(opt_cfg, "precision", "fp32") != "bf16_mixed":
        return model_cfg
    if "float16" in (model_cfg.param_dtype, model_cfg.compute_dtype):
        raise ValueError(
            "precision: bf16_mixed cannot combine with a float16 model "
            "config (fp16 would need loss scaling); use bfloat16/float32 "
            "model dtypes and let the policy lift them"
        )
    if (
        model_cfg.param_dtype == "bfloat16"
        and model_cfg.compute_dtype == "bfloat16"
    ):
        return model_cfg
    return dataclasses.replace(
        model_cfg, param_dtype="bfloat16", compute_dtype="bfloat16"
    )


def resolve_collectives(train_cfg, model_cfg, mesh: Mesh | None = None):
    """Route ``TrainConfig.collectives`` onto the model config (the dense
    layers are where the ring schedules live — ops/overlap_collectives.py,
    ISSUE 12), with the mode's validity checked HERE so every train-step
    consumer (trainer, bench, audit lowering) applies one rule:

    - ``overlapped`` + pipeline parallelism is rejected: the ring's
      shard_map over the FSDP axis cannot nest under the pipeline's
      manual region the way its collectives would need (same restriction
      as ring attention), and FSDP rules never combine with pipe > 1 in
      this repo anyway.
    - otherwise the model config comes back with ``collectives`` set; for
      rules that do not shard "embed_p" the mode is inert by design
      (OverlapDense falls back to the serialized dot per call).

    Either config may request the mode: the effective value is
    "overlapped" when EITHER TrainConfig or ModelConfig says so —
    ModelConfig.collectives is a public validated knob, and a train-level
    default of "xla" must not silently revert it.
    """
    import dataclasses

    train_mode = getattr(train_cfg, "collectives", "xla")
    mode = (
        "overlapped"
        if "overlapped" in (train_mode, model_cfg.collectives)
        else "xla"
    )
    pipe = (
        mesh.shape.get("pipe", 1) if mesh is not None
        else max(train_cfg.mesh.pipe, 1) * train_cfg.mesh.dcn_pipe
    )
    # The pipeline rejection must fire for EVERY route into the mode —
    # including a model-config-only request that needs no replace below.
    if mode == "overlapped" and (train_cfg.parallel == "pp" or pipe > 1):
        raise ValueError(
            "collectives: overlapped is not supported under pipeline "
            "parallelism (the FSDP ring's shard_map cannot nest inside "
            "the pipeline's manual region); use a mesh with pipe == 1 — "
            "overlapped composes with DP/FSDP/TP"
        )
    if mode == model_cfg.collectives:
        return model_cfg
    return dataclasses.replace(model_cfg, collectives=mode)


@struct.dataclass
class Batch:
    """Input/target token batch (same shape contract as the reference's
    Batch pytree, /root/reference/train/create_train_step.py:15-21)."""

    x: jax.Array
    y: jax.Array


def sum_aux_loss(mutated: dict) -> jax.Array:
    """Total of the sowed "aux_loss" collection (MoE load-balance terms,
    coefficient pre-applied; zero for dense models). One definition shared
    by the GSPMD step and both pipeline schedules."""
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(mutated.get("aux_loss", {})):
        total = total + jnp.sum(leaf)
    return total


def cross_entropy_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross entropy, float32, gather-free.

    Numerically identical to
    ``optax.softmax_cross_entropy_with_integer_labels`` but selects the gold
    logit with an iota-match + reduction instead of ``take_along_axis``:
    a vocab-*sharded* gather cannot be partitioned by XLA SPMD inside a
    partially-manual (shard_map) region — and the masked reduction shards
    cleanly over a vocab-parallel (TP) logits axis anyway.

    Delegates to the single CE implementation in ``ops/fused_ce.py`` so the
    eval path and the fused train path cannot drift apart.
    """
    from dtc_tpu.ops.fused_ce import _stats_loss

    return _stats_loss(logits, targets)[0]


def stack_counters(mutated: dict) -> dict[str, jax.Array]:
    """The sowed "counters" collection of a pattern model by the name each
    was sowed under (``models/pattern.py``, "Counters"): ``"moe"`` — one
    row of ``COUNTERS`` a layer that counts, ``(layers, n)`` float32 in the
    tree's order; ``"passes"`` — a looped stack's ``(passes, 3)``
    ``PASS_COUNTERS``. A name nothing sowed is absent."""
    rows: dict[str, list[jax.Array]] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(mutated.get("counters", {})):
        rows.setdefault(path[-2].key, []).append(leaf.reshape(-1, leaf.shape[-1]))
    return {name: jnp.concatenate(r, axis=0) for name, r in rows.items()}


def create_gspmd_train_step(
    mesh: Mesh,
    rules: Sequence[tuple[str, str | None]] = DEFAULT_RULES,
    state: TrainState | None = None,
    base_params: PyTree | None = None,
    counters: bool = False,
) -> Callable[[TrainState, Batch, jax.Array], tuple[TrainState, jax.Array]]:
    """Build the jitted DP/TP/DP×TP train step.

    The returned function must be called with ``mesh`` / ``rules`` contexts
    active (the trainer owns those); params/opt-state sharding flows in from
    the arguments, batch sharding from the logical ("batch","seq") constraint.

    Passing the (placed) initial ``state`` pins the step's out_shardings to
    the state's shardings, so every call hits ONE executable — see
    :func:`state_shardings` for the double-compile this avoids.

    With ``base_params`` (the LoRA finetune path, dtc_tpu/adapters/) the
    state holds ONLY the adapter ("lora") subtree: the frozen base rides
    in as a non-donated, non-differentiated argument, gradients and the
    optimizer update touch the adapter alone — which is exactly what makes
    adapter checkpoints/rollback operate on the tiny subtree for free.

    ``counters`` (a model that sows the "counters" collection): the step
    returns ``(state, loss, counters)``, the third a dict of small device
    arrays (:func:`stack_counters`) the caller fetches with the loss — never
    by a sync of its own.
    """
    jit_kwargs: dict[str, Any] = {"donate_argnums": (0,)}
    if state is not None:
        replicated = NamedSharding(mesh, P())
        jit_kwargs["out_shardings"] = (
            state_shardings(state, mesh), *(replicated,) * (2 if counters else 1)
        )

    # Donating the state lets XLA update params/opt-state in place instead of
    # allocating a second ~1.1 GB copy (fp32 master params + two AdamW moments)
    # and copying every step.
    @functools.partial(jax.jit, **jit_kwargs)
    def train_step(state: TrainState, batch: Batch, rng: jax.Array):
        x = nn.with_logical_constraint(batch.x, ("batch", "seq"))
        y = nn.with_logical_constraint(batch.y, ("batch", "seq"))

        def loss_fn(params: PyTree) -> jax.Array:
            # targets route the head through the fused head+CE op: same loss
            # value bitwise, one logits pass fewer in backward (fused_ce.py).
            # "aux_loss" carries MoE load-balance terms (coefficient already
            # applied at sow time); empty for dense models.
            # named_scope "fwd" (ISSUE 8): every primal op's HLO op_name
            # metadata carries .../fwd/..., the backward pass carries the
            # autodiff transpose(jvp(fwd)) wrapper — the devprof
            # attribution derives the fwd/bwd phase split from exactly
            # this (obs/devprof.classify_scope). Trace-time only; the
            # compiled program is unchanged.
            with jax.named_scope("fwd"):
                loss, mut = state.apply_fn(
                    {"params": params}, x, train=True, rngs={"dropout": rng},
                    targets=y, mutable=["aux_loss", "counters"],
                )
                return loss + sum_aux_loss(mut), mut

        (loss, mut), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            state = state.apply_gradients(grads=grads)
        if counters:
            return state, loss, stack_counters(mut)
        return state, loss

    if base_params is None:
        return train_step

    @functools.partial(jax.jit, **jit_kwargs)
    def lora_step(
        state: TrainState, base: PyTree, batch: Batch, rng: jax.Array
    ):
        x = nn.with_logical_constraint(batch.x, ("batch", "seq"))
        y = nn.with_logical_constraint(batch.y, ("batch", "seq"))

        def loss_fn(lora: PyTree) -> jax.Array:
            with jax.named_scope("fwd"):
                loss, mut = state.apply_fn(
                    {"params": base, "lora": lora}, x, train=True,
                    rngs={"dropout": rng}, targets=y, mutable=["aux_loss"],
                )
                return loss + sum_aux_loss(mut)

        # Differentiate ONLY the adapter subtree; base param gradients are
        # never formed (frozen base — not stop_gradient'd post hoc).
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        with jax.named_scope("optimizer"):
            state = state.apply_gradients(grads=grads)
        return state, loss

    # Bind the frozen base as an EXPLICIT (traced, undonated) argument —
    # not a closure constant, which would bake the full base weights into
    # the jaxpr — while keeping the trainer-facing (state, batch, rng)
    # signature every call site already uses.
    def step(state: TrainState, batch: Batch, rng: jax.Array):
        return lora_step(state, base_params, batch, rng)

    return step


def create_eval_step(
    mesh: Mesh,
    model,
    rules: Sequence[tuple[str, str | None]] = DEFAULT_RULES,
    base_params: PyTree | None = None,
) -> Callable[[PyTree, Batch], jax.Array]:
    """Jitted loss-only evaluation step (no dropout, no update).

    Takes bare params (not a TrainState) so the trainer can feed it
    unstacked pipeline params: eval always runs the plain GSPMD forward,
    whatever strategy training uses. With ``base_params`` (adapter runs)
    the first argument is the LoRA subtree instead — the same thing the
    trainer's ``state.params`` holds in that mode — and the frozen base
    rides in as a bound argument.
    """

    @jax.jit
    def eval_step(params: PyTree, base: PyTree | None, batch: Batch) -> jax.Array:
        x = nn.with_logical_constraint(batch.x, ("batch", "seq"))
        y = nn.with_logical_constraint(batch.y, ("batch", "seq"))
        variables = (
            {"params": params} if base is None
            else {"params": base, "lora": params}
        )
        logits = model.apply(variables, x, train=False)
        return cross_entropy_loss(logits, y)

    return lambda params, batch: eval_step(params, base_params, batch)


def create_train_step(
    mesh: Mesh,
    *,
    model=None,
    num_microbatches: int = 1,
    rules: Sequence[tuple[str, str | None]] = DEFAULT_RULES,
    pp_schedule: str = "gpipe",
    pp_virtual: int = 1,
    state: TrainState | None = None,
    base_params: PyTree | None = None,
):
    """Strategy-dispatching factory: GSPMD step, or pipeline step when the
    mesh has a non-trivial ``pipe`` axis (GPipe, or plain/interleaved 1F1B
    per ``pp_schedule`` / ``pp_virtual``). ``state`` (optional, GSPMD path)
    pins out_shardings to avoid the layout-churn double compile.
    ``base_params`` selects the LoRA-adapter step (state = adapter subtree,
    base frozen) — GSPMD modes only."""
    pattern = bool(getattr(getattr(model, "cfg", None), "layer_pattern", ()))
    if pattern and (mesh.shape.get("pipe", 1) > 1 or mesh.shape.get("model", 1) > 1):
        raise ValueError(
            "a layer-pattern model runs under dp and fsdp only: no pipeline "
            "stages (pipe > 1) and no mesh axis over heads or experts "
            f"(model > 1) yet; got mesh {dict(mesh.shape)}"
        )
    if mesh.shape.get("pipe", 1) > 1:
        if base_params is not None:
            raise ValueError(
                "LoRA adapter training (base_params) is not supported under "
                "pipeline parallelism; use a mesh with pipe == 1 (adapters "
                "compose with DP/TP/FSDP)"
            )
        assert model is not None, "pipeline step needs the model for staged apply"
        if pp_schedule == "1f1b":
            from dtc_tpu.parallel.pipeline import create_1f1b_train_step

            return create_1f1b_train_step(
                model, mesh, num_microbatches=num_microbatches, rules=rules,
                virtual=pp_virtual,
            )
        from dtc_tpu.parallel.pipeline import create_pp_train_step

        return create_pp_train_step(
            model, mesh, num_microbatches=num_microbatches, rules=rules
        )
    return create_gspmd_train_step(
        mesh, rules, state=state, base_params=base_params, counters=pattern
    )
