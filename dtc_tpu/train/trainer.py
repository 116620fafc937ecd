"""Training orchestration.

Capability parity with the reference's two driver loops
(`/root/reference/train/train.py:22-104` ``train_dp_tp`` and ``:107-233``
``train_pp``), unified: ONE driver serves single-device, DP, TP, DP×TP, PP,
and 3D DP×TP×PP — strategy is mesh shape, and the PP/GSPMD split lives in
:func:`dtc_tpu.train.train_step.create_train_step`, not here.

Matches the reference's measurement protocol so numbers are comparable:
N untimed warmup steps (default 5, `/root/reference/train/train.py:63-70`),
then a timed loop whose per-step cumulative ``elapsed_time`` and ``loss``
land in ``<output_dir>/log.csv`` with the reference's exact schema.

TPU-native extensions the reference lacks: host->device prefetch (no
synchronous tokenize-in-loop), loss fetched at log boundaries only (no
per-step device sync, `/root/reference/train/train.py:82` forces one every
step), tokens/sec + MFU reporting, Orbax checkpoint/resume, profiler
windows, and multi-host feeding.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax.training.train_state import TrainState
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dtc_tpu.config.schema import ModelConfig, OptimConfig, TrainConfig
from dtc_tpu.data.prefetch import ShardedPrefetchIterator
from dtc_tpu.data.synthetic import synthetic_batch_iterator, synthetic_row_batches
from dtc_tpu.models.gpt import GPT
from dtc_tpu.models.pattern import build_model
from dtc_tpu.parallel.mesh import mesh_from_config
from dtc_tpu.parallel.pipeline import pp_param_specs, pp_stack_params
from dtc_tpu.parallel.sharding import DEFAULT_RULES, batch_spec, param_specs
from dtc_tpu.train.optimizer import create_optimizer
from dtc_tpu.train.train_step import (
    Batch,
    canonicalize_state_placement,
    create_train_step,
    normalize_spec,
    resolve_collectives,
    resolve_precision,
)
from dtc_tpu.obs import CompileWatcher, StepClock, Telemetry
from dtc_tpu.utils.dist import is_lead_process, maybe_initialize_distributed
from dtc_tpu.utils.metrics import comm_bytes_per_step, mfu

PyTree = Any


@dataclass
class TrainResult:
    state: TrainState
    losses: list[float] = field(default_factory=list)
    elapsed_times: list[float] = field(default_factory=list)
    eval_losses: list[tuple[int, float]] = field(default_factory=list)
    mesh: Mesh | None = None
    # LoRA finetunes (model_cfg.adapter.rank > 0): the frozen base the
    # adapter (state.params) was trained against — callers exporting or
    # serving the adapter need exactly this pair. None for full training.
    base_params: PyTree | None = None


def _drop_yields(it: Iterator[np.ndarray], drops: set[int]) -> Iterator[np.ndarray]:
    """Skip the 0-based yield indices in ``drops`` (bounded set) — used to
    withhold not-yet-passed holdout batches from a resumed stream."""
    last = max(drops)
    for i, batch in enumerate(it):
        if i in drops:
            if i == last:
                break
            continue
        yield batch
    yield from it


def _per_process_batch(train_cfg: TrainConfig) -> int:
    n = jax.process_count()
    if n > 1 and train_cfg.batch % n != 0:
        raise ValueError(
            f"global batch {train_cfg.batch} not divisible by {n} processes"
        )
    return train_cfg.batch // n if n > 1 else train_cfg.batch


def make_host_iterator(
    train_cfg: TrainConfig,
    model_cfg: ModelConfig,
    skip_batches: int = 0,
    seed_offset: int = 0,
    stream_position: dict | None = None,
    history: int = 64,
    chaos=None,
    on_recovery=None,
    cancel=None,
    row_stream: bool = False,
) -> Iterator[np.ndarray]:
    """(batch, seq_len+1) token batches; per-process share in multi-host runs.

    Resume positioning: the synthetic stream seeks by ``skip_batches``
    (seeded, O(1)); fineweb seeks via ``stream_position`` (a checkpointed
    TokenPacker position — documents skipped at the source, buffer
    restored). ``skip_batches`` on fineweb is the drain-loop FALLBACK for
    checkpoints that predate position sidecars. ``seed_offset`` selects a
    disjoint synthetic stream (used by eval).

    The fineweb stream self-heals transient faults per
    ``train_cfg.resilience.stream_retry`` (position-preserving re-open with
    backoff); ``chaos`` threads the fault injector into the document source
    and ``on_recovery`` (a RecoveryBus post) receives retry records."""
    seq = model_cfg.max_seq_len + 1
    batch = _per_process_batch(train_cfg)
    if train_cfg.dataset == "synthetic":
        # Offset multi-host streams so processes contribute distinct data.
        seed = train_cfg.seed * 1000 + seed_offset + jax.process_index()
        if row_stream:
            # Elastic runs (ISSUE 15): the flat row stream whose token
            # accounting is batch-shape-independent, so a resize that
            # changes the batch geometry re-seeks by rows consumed —
            # ``skip_batches`` converts at THIS call's batch size.
            return synthetic_row_batches(
                batch, seq, model_cfg.vocab_size, seed=seed,
                start_row=skip_batches * batch,
            )
        return synthetic_batch_iterator(
            batch, seq, model_cfg.vocab_size, seed=seed, start=skip_batches
        )
    from dtc_tpu.data.fineweb import FinewebStream

    it = FinewebStream(
        batch,
        seq,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        position=stream_position,
        history=history,
        retry=train_cfg.resilience.stream_retry,
        chaos=chaos,
        on_recovery=on_recovery,
        cancel=cancel,
    )
    for _ in range(skip_batches):
        next(it)
    return it


def make_eval_iterator(
    train_cfg: TrainConfig, model_cfg: ModelConfig
) -> Iterator[np.ndarray]:
    """SYNTHETIC eval batches: a seed stream fully disjoint from training's
    (seed_offset=500; training streams use offsets < number of processes).
    FineWeb eval does not come through here — the trainer diverts held-out
    batches from the training stream instead (dtc_tpu/data/holdout.py)."""
    return make_host_iterator(train_cfg, model_cfg, seed_offset=500)


def _placed_gspmd_params(params: PyTree, mesh: Mesh, rules) -> PyTree:
    """Rule-table placement with GSPMD-normalized specs (degenerate axes
    and trailing Nones dropped) so the step's output shardings equal its
    input's — one executable, not two (train_step.state_shardings). The
    ONE placement definition both init_state flavors share: full training
    and the LoRA finetune's frozen base must place identically."""
    specs = jax.tree.map(
        lambda s: normalize_spec(s, mesh),
        param_specs(params, rules),
        is_leaf=lambda x: isinstance(x, P),
    )
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.device_put(params, shardings)


def _reshard_onto(tree: PyTree, mesh: Mesh) -> PyTree:
    """Re-place every array leaf of ``tree`` on ``mesh``, keeping its
    PartitionSpec axis NAMES (sizes re-resolve against the new mesh) —
    the cold-tier leg of an elastic resize, where the restored state's
    arrays still live on the pre-shrink device set."""
    def leaf(a: Any) -> Any:
        if not isinstance(a, jax.Array):
            return a
        spec = getattr(a.sharding, "spec", None)
        spec = normalize_spec(spec if spec is not None else P(), mesh)
        return jax.device_put(np.asarray(a), NamedSharding(mesh, spec))

    return jax.tree.map(leaf, tree)


def _emit_counters(tele, steps: list[int], counted: dict[str, np.ndarray]) -> None:
    """A step's counter events from what was fetched (``train_step.
    stack_counters``, each array with the steps in front): ``moe_counters``
    from the expert layers' ``(steps, layers, n)`` rows, ``pass_counters``
    from a looped stack's ``(steps, passes, 3)``."""
    from dtc_tpu.models.pattern import COUNTERS, PASS_COUNTERS

    for step, rows in zip(steps, counted.get("moe", ())):
        by_name = dict(zip(COUNTERS, rows.T))  # as many names as the rows are wide
        fields = dict(
            moe_assigned_held=[float(v) for v in by_name["moe_assigned_held"]],
            moe_load_max=float(by_name["moe_load_max"].max()),
            moe_load_mean=float(by_name["moe_load_mean"].mean()),
            moe_dropped=float(by_name["moe_dropped"].sum()),
            moe_flushes=[float(v) for v in by_name["moe_flushes"]],
        )
        if "moe_bias_swapped" in by_name:  # a router with a selection bias
            fields["moe_bias_swapped"] = [float(v) for v in by_name["moe_bias_swapped"]]
        tele.registry.emit("moe_counters", step=int(step), **fields)
    for step, rows in zip(steps, counted.get("passes", ())):
        by_name = dict(zip(PASS_COUNTERS, rows.T))
        tele.registry.emit(
            "pass_counters", step=int(step),
            exit_p=[float(v) for v in by_name["exit_p"]],
            pass_ce=[float(v) for v in by_name["pass_ce"]],
            exit_entropy=float(by_name["exit_entropy"][0]),
        )


def _guarded_optimizer(train_cfg: TrainConfig, opt_cfg: OptimConfig):
    """The optimizer with the anomaly guard's device-side knobs threaded
    in — shared so LoRA finetunes can never silently diverge from full
    training's optimizer/guard behavior."""
    guard_cfg = train_cfg.resilience.guard
    return create_optimizer(
        opt_cfg, total_steps=train_cfg.steps,
        skip_nonfinite=guard_cfg.skip_nonfinite_updates,
        max_consecutive_skips=guard_cfg.max_consecutive_skips,
    )


def init_state(
    model: GPT,
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    opt_cfg: OptimConfig,
    mesh: Mesh,
    rules=DEFAULT_RULES,
) -> TrainState:
    """Init params once (single logical model), place them on the mesh.

    Unlike the reference's PP path — which re-inits every stage with
    different keys (`/root/reference/train/train.py:143-161`) — PP here
    reshapes the one logical param tree, so all strategies start from
    bit-identical weights given the same seed.
    """
    dummy = jnp.ones((1, model_cfg.max_seq_len), dtype=jnp.int32)
    init_rng = jax.random.PRNGKey(train_cfg.seed)
    # Init under jit: ops that build partial-manual shard_map regions (ring
    # attention) only exist under a jit trace, and jit also avoids
    # materialising throwaway init activations eagerly.
    params = jax.jit(
        lambda rng, x: model.init({"params": rng, "dropout": rng}, x, train=False)
    )(init_rng, dummy)["params"]
    pp = mesh.shape.get("pipe", 1) > 1
    if pp:
        params = pp_stack_params(
            params, mesh.shape["pipe"], train_cfg.pp_virtual_stages
        )
        specs = pp_param_specs(params, rules)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        params = jax.device_put(params, shardings)
    else:
        params = _placed_gspmd_params(params, mesh, rules)
    tx = _guarded_optimizer(train_cfg, opt_cfg)
    # Eager tx.init on sharded params: zeros_like follows input sharding, so
    # the optimizer state lands correctly sharded without an _infer pass
    # (cf. /root/reference/train/train.py:44-52).
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    # Commit the stray scalar leaves (optax counts, step) to the mesh so the
    # step's input signature is identical every call — half of the
    # double-compile fix (see train_step.state_shardings for the other).
    return canonicalize_state_placement(state, mesh)


def init_adapter_state(
    model: GPT,
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    opt_cfg: OptimConfig,
    mesh: Mesh,
    rules=DEFAULT_RULES,
) -> tuple[TrainState, PyTree]:
    """:func:`init_state`'s LoRA twin: init the full variable set once,
    place the FROZEN base params exactly as init_state would (normalized
    rule-table shardings), and build the TrainState — optimizer and all —
    over the tiny "lora" subtree ONLY. Returns ``(state, base_params)``.

    Because the state IS the adapter subtree, everything downstream that
    operates on the state (sha256-verified checkpoints, stream sidecars,
    guard rollback, SIGTERM graceful stop) operates on the adapter alone,
    with zero adapter-specific code in the loop. Adapter factors are
    replicated on the mesh (they are tiny — ``adapter_param_count``;
    sharding them would buy nothing and cost a rule-table entry per
    site)."""
    dummy = jnp.ones((1, model_cfg.max_seq_len), dtype=jnp.int32)
    init_rng = jax.random.PRNGKey(train_cfg.seed)
    variables = jax.jit(
        lambda rng, x: model.init({"params": rng, "dropout": rng}, x, train=False)
    )(init_rng, dummy)
    params, lora = variables["params"], variables["lora"]
    params = _placed_gspmd_params(params, mesh, rules)
    lora = jax.device_put(lora, NamedSharding(mesh, P()))
    tx = _guarded_optimizer(train_cfg, opt_cfg)
    state = TrainState.create(apply_fn=model.apply, params=lora, tx=tx)
    return canonicalize_state_placement(state, mesh), params


def train(
    train_cfg: TrainConfig,
    model_cfg: ModelConfig,
    opt_cfg: OptimConfig,
    *,
    host_iterator: Iterator[np.ndarray] | None = None,
    rules=DEFAULT_RULES,
) -> TrainResult:
    # The run's one clock and compile watcher, from the first line: every
    # second up to the first timed step lies under a `train.startup.*` phase
    # (obs/stepclock.py). The Telemetry built further down, once a sink may
    # open, takes both over and writes them as the `startup` event.
    clock = StepClock()
    compiles = CompileWatcher().activate()
    try:
        return _train_in_mode(
            train_cfg, model_cfg, opt_cfg, host_iterator=host_iterator,
            rules=rules, clock=clock, compiles=compiles,
        )
    finally:
        # Telemetry.close() does both; this covers a raise before it exists.
        compiles.deactivate()
        clock.shutdown()


def _train_in_mode(train_cfg, model_cfg, opt_cfg, **kw) -> TrainResult:
    if not train_cfg.debug_nans:
        return _train(train_cfg, model_cfg, opt_cfg, **kw)
    # SURVEY §5 sanitizer row: the TPU-native analog of the reference
    # stack's device-side assert tooling. XLA re-runs any jitted
    # computation whose output contains NaN un-jitted and raises
    # FloatingPointError at the producing primitive — so a NaN in e.g.
    # the fused-CE backward surfaces as a traceback, not a silently
    # garbage loss. Dev-config only: the re-run check syncs every step.
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        return _train(train_cfg, model_cfg, opt_cfg, **kw)
    finally:
        jax.config.update("jax_debug_nans", prev)


def _train(
    train_cfg: TrainConfig,
    model_cfg: ModelConfig,
    opt_cfg: OptimConfig,
    *,
    host_iterator: Iterator[np.ndarray] | None = None,
    rules=DEFAULT_RULES,
    clock: StepClock,
    compiles: CompileWatcher,
) -> TrainResult:
    clock.startup("distributed")
    maybe_initialize_distributed(
        train_cfg.multihost, train_cfg.coordinator_timeout_s
    )
    num_devices = jax.device_count()
    clock.startup("mesh")
    mesh = mesh_from_config(
        train_cfg.parallel, train_cfg.mesh, n_layers=model_cfg.n_layers
    )
    from dtc_tpu.parallel.sharding import FSDP_RULES, ring_rules_from

    caller_rules = rules is not DEFAULT_RULES
    if train_cfg.parallel == "fsdp" and not caller_rules:
        # ZeRO-3 parameter sharding: same mesh, same batch layout, but
        # parameter storage shards over "data" (see sharding.FSDP_RULES).
        rules = FSDP_RULES
    if model_cfg.attention in ("ring", "ulysses"):
        if model_cfg.attention == "ring" and mesh.shape.get("pipe", 1) > 1:
            # The ring's inner shard_map over "model" cannot nest inside
            # the pipeline's manual region (Shardy rejects re-binding a
            # mesh whose "pipe" axis a parent manual computation owns).
            # Ring composes with DP/TP, not PP — Ulysses (pure GSPMD
            # constraints, no nested shard_map) composes with PP too.
            raise ValueError(
                "attention='ring' (sequence parallelism) cannot run under "
                "pipeline parallelism; use a mesh with pipe=1 (ring "
                "composes with the data axis) or attention='ulysses'"
            )
        if not caller_rules:
            # Both sequence-parallel schemes repurpose the "model" mesh
            # axis: derive the table from whatever base is active (DEFAULT
            # or FSDP), swapping seq onto "model" and the Megatron TP axes
            # off it. Ulysses re-shards heads over "model" INSIDE the
            # attention op only.
            rules = ring_rules_from(rules)

    # ------ elastic training (ISSUE 15): virtual hosts + shrunk restart --
    # The device set splits into n_virtual_hosts contiguous "hosts" (the
    # in-process emulation of pod hosts — see resilience/elastic.py for
    # the honesty note); a host named dead at STARTUP shrinks the mesh
    # before anything is placed, so a post-failure restart comes up
    # directly on the survivors — the same path the in-run resize takes,
    # minus the detection.
    el_cfg = train_cfg.resilience.elastic
    el_on = el_cfg.enabled
    hosts = None
    if el_on:
        from dtc_tpu.resilience.elastic import VirtualHosts, resize_mesh

        if jax.process_count() > 1:
            raise ValueError(
                "resilience.elastic emulates hosts in-process; real "
                "multi-process runs are not supported yet (the virtual-"
                "host seam is where a DCN transport would slot in)"
            )
        if train_cfg.dataset != "synthetic" or host_iterator is not None:
            raise ValueError(
                "resilience.elastic requires dataset: synthetic (the "
                "batch-shape-independent row stream is the re-seek "
                "contract); fineweb and caller-provided iterators cannot "
                "be re-positioned across a mesh resize"
            )
        if mesh.shape.get("pipe", 1) > 1:
            raise ValueError(
                "resilience.elastic does not support pipeline parallelism "
                "(stage-chunked params cannot re-shard onto fewer stages); "
                "use a mesh with pipe == 1"
            )
        if model_cfg.adapter.rank > 0:
            raise ValueError(
                "resilience.elastic does not support LoRA finetunes: the "
                "frozen base params are outside the snapshotted TrainState"
            )
        hosts = VirtualHosts(el_cfg.n_virtual_hosts)
        for h in el_cfg.dead_hosts:
            hosts.kill(h)
        if el_cfg.dead_hosts:
            mesh = resize_mesh(mesh, hosts)
            num_devices = len(hosts.survivor_devices())
        if train_cfg.batch % int(mesh.shape["data"]) != 0:
            raise ValueError(
                f"global batch {train_cfg.batch} must shard over the data "
                f"axis {int(mesh.shape['data'])} (elastic preserves the "
                "global batch and rescales the per-device batch)"
            )
    lead = is_lead_process()
    if lead:
        print(
            f"[dtc_tpu] strategy={train_cfg.parallel} mesh={dict(mesh.shape)} "
            f"devices={num_devices} processes={jax.process_count()}"
        )

    # Overlapped training collectives (ISSUE 12): the TrainConfig knob is
    # lifted onto the model config, because the ring schedules live at
    # the dense-matmul sites (models/gpt.py OverlapDense). Validity (no
    # pipeline) is resolve_collectives' one rule; inertness is surfaced
    # below (inside the mesh+rules contexts, via the SAME
    # fsdp_axis_in_scope resolution the matmul sites use — rule table,
    # axis size, and the sequence-parallel deferral all covered) so a
    # knob that will change nothing never passes silently.
    model_cfg = resolve_collectives(train_cfg, model_cfg, mesh)
    # Mixed precision (ISSUE 14): OptimConfig.precision lifts bf16
    # params/compute onto the model config through the one shared
    # definition; create_optimizer reads the same knob for the fp32
    # master-weight wrapper, so the pair can never half-apply.
    model_cfg = resolve_precision(opt_cfg, model_cfg)

    clock.startup("model")
    model = build_model(model_cfg)
    # LoRA finetune mode (dtc_tpu/adapters/): the TrainState is the
    # adapter subtree, the base is a frozen step input. One flag here —
    # the loop below is identical either way (that is the design).
    lora_on = model_cfg.adapter.rank > 0
    if lora_on and mesh.shape.get("pipe", 1) > 1:
        raise ValueError(
            "LoRA adapter training is not supported under pipeline "
            "parallelism (pipe > 1); adapters compose with DP/TP/FSDP"
        )

    # ------ resilience subsystem (SURVEY §5 failure-detection row) ------
    # Bus first: recovery actions fire from threads and layers that have no
    # telemetry handle (stream retry on the prefetch worker, checkpoint
    # fallback inside CheckpointManager); the trainer drains the bus into
    # the event stream at step/log boundaries.
    from dtc_tpu.resilience import (
        AnomalyAbort,
        AnomalyGuard,
        ChaosInjector,
        RecoveryBus,
        StepWatchdog,
        WatchdogTimeout,
    )

    res_cfg = train_cfg.resilience
    bus = RecoveryBus()
    chaos = ChaosInjector(res_cfg.chaos, bus) if res_cfg.chaos.enabled else None
    # Elastic detection + hot tier (ISSUE 15). Snapshot commits happen on
    # a worker thread, so their events ride the bus like every other
    # off-thread recovery source.
    monitor = None
    snap_store = None
    if el_on:
        from dtc_tpu.resilience import HostMonitor, SnapshotStore

        monitor = HostMonitor(hosts, miss_limit=el_cfg.heartbeat_miss_limit)
        snap_store = SnapshotStore(
            hosts, keep=el_cfg.keep, on_event=bus.post
        )
    if chaos is not None and (
        res_cfg.chaos.data_error_at_doc or res_cfg.chaos.data_stall_at_doc
    ) and not (train_cfg.dataset == "fineweb" and host_iterator is None):
        # The data-plane hooks live in the fineweb document source; on
        # synthetic (or a caller-provided iterator) they would silently
        # never fire — and a chaos drill that runs nothing reads as a pass.
        print(
            "[dtc_tpu] WARNING: chaos data faults (data_error_at_doc/"
            "data_stall_at_doc) only fire on dataset: fineweb; this run "
            "will not inject them"
        )

    with mesh, nn.logical_axis_rules(rules):
        # An elastic resize swaps the ambient mesh mid-run: the survivor
        # mesh is ENTERED onto this stack (nested inside the enclosing
        # ``with mesh``) and closed in the finally below, so the context
        # unwind stays LIFO even after one or more shrinks.
        resize_ctx = contextlib.ExitStack()
        if model_cfg.collectives == "overlapped" and lead:
            from dtc_tpu.parallel.sharding import fsdp_axis_in_scope

            if fsdp_axis_in_scope() is None:
                print(
                    "[dtc_tpu] WARNING: collectives: overlapped is inert "
                    "on this run — no usable FSDP ring in scope (the "
                    "active rules don't shard 'embed_p', its mesh axis "
                    "is size 1, or sequence-parallel rules own the "
                    "activations); every matmul keeps the serialized "
                    "XLA path"
                )
        # The init program's trace, its compile or load, and its run.
        clock.startup("state")
        base_params = None
        if lora_on:
            state, base_params = init_adapter_state(
                model, model_cfg, train_cfg, opt_cfg, mesh, rules
            )
        else:
            state = init_state(model, model_cfg, train_cfg, opt_cfg, mesh, rules)

        # ------ checkpoint / resume ------
        # With elastic on, the disk checkpoint is DEMOTED to the cold /
        # catastrophic tier: the in-memory snapshots are the hot recovery
        # path, so ``elastic.cold_every`` (when set) slows the Orbax
        # cadence without touching the TrainConfig knob.
        clock.startup("restore")
        checkpoint_every_eff = train_cfg.checkpoint_every
        if el_on and el_cfg.cold_every > 0 and train_cfg.checkpoint_every > 0:
            checkpoint_every_eff = el_cfg.cold_every
        ckpt = None
        start_step = 0
        if train_cfg.checkpoint_every > 0:
            from dtc_tpu.utils.checkpoint import CheckpointManager

            ckpt_dir = train_cfg.checkpoint_dir or os.path.join(
                train_cfg.output_dir, "checkpoints"
            )
            ckpt = CheckpointManager(
                ckpt_dir, verify=res_cfg.verify_checkpoints, on_event=bus.post,
                keep_n=res_cfg.checkpoint_keep_n,
            )
            # Gate on EXISTENCE only (all_steps) — restore_latest does the
            # single integrity verification; a latest_step() here would
            # sha256 the newest multi-GB step a second time back to back.
            if train_cfg.resume and ckpt.all_steps():
                # Verified resume: restore the newest INTACT step (corrupt
                # or partial checkpoints are skipped with a recovery event).
                # Checkpoint labels are LOOP steps. state.step also counts
                # warmup updates, so it reads warmup_steps ahead — using it
                # here would skip real work on resume.
                try:
                    state, start_step = ckpt.restore_latest(state)
                    if lead:
                        print(
                            f"[dtc_tpu] resumed from checkpoint step {start_step}"
                        )
                except FileNotFoundError as e:
                    # Every candidate step is corrupt. Silently starting
                    # fresh would discard real progress (and would trip the
                    # log.csv clobber guard anyway) — fail with the way out.
                    raise RuntimeError(
                        "resume requested but no checkpoint could be "
                        f"restored from {ckpt_dir}. Causes range from real "
                        "corruption to a model/optimizer config that no "
                        "longer matches the saved state (see the chained "
                        "error). Inspect the checkpoint dir, revert config "
                        "changes, or set resume: false (plus overwrite: true "
                        "if output_dir holds a previous log.csv) to "
                        "deliberately start fresh"
                    ) from e

        clock.startup("step_build")
        # Anomaly guard: rollback needs a checkpoint manager AND a stream
        # the trainer can rebuild (a caller-provided host_iterator cannot
        # be re-positioned).
        guard = (
            AnomalyGuard(
                res_cfg.guard,
                can_rollback=(ckpt is not None and host_iterator is None),
            )
            if res_cfg.guard.enabled
            else None
        )
        wd = StepWatchdog(res_cfg.watchdog) if res_cfg.watchdog.enabled else None

        train_step = create_train_step(
            mesh, model=model, num_microbatches=train_cfg.pp_microbatches,
            rules=rules, pp_schedule=train_cfg.pp_schedule,
            pp_virtual=train_cfg.pp_virtual_stages, state=state,
            base_params=base_params,
        )

        # Resume parity: the interrupted run consumed warmup_steps +
        # start_step batches before reaching step start_step+1 — position the
        # stream there (warmup itself is skipped on resume: running it
        # against the restored state would advance it past the checkpointed
        # step). FineWeb SEEKS via the checkpointed stream position when the
        # sidecar exists (drain loop only as pre-sidecar fallback).
        clock.startup("data")
        from dtc_tpu.data.holdout import (
            divert_holdout, diverted_indices, stream_index_for,
        )

        fineweb = train_cfg.dataset == "fineweb" and host_iterator is None
        holdout_n = train_cfg.eval_batches if (
            fineweb and train_cfg.eval_every > 0
        ) else 0
        holdout_every = train_cfg.eval_holdout_every
        proc = jax.process_index()
        # History must out-span prefetch look-ahead AND the holdout's
        # eager head consumption, or early checkpoints can't find their
        # position (review finding, round 4).
        span = (holdout_n - 1) * holdout_every + 1 if holdout_n else 0
        hist = span + 64

        host_it = None             # host-side batch iterator
        stream_obj = None          # FinewebStream (position bookkeeping)
        eval_host_batches = None   # held-out fineweb eval batches
        delivered = 0              # batches handed to warmup+train so far
        # 0-based source-yield indices withheld from training on THIS run's
        # stream: the holdout set for a head stream, or the not-yet-passed
        # remainder of it relative to a resumed stream's position.
        train_drops: set[int] = set()
        stream_base = 0  # absolute yield index where this run's stream starts
        stream_start_step = start_step  # loop step the stream is positioned at
        # Per-stream-generation teardown signal: set on rollback so a
        # prefetch worker parked in the retry backoff exits immediately
        # instead of out-sleeping close(), re-opening the dead stream, and
        # posting stale retry events through the captured bus.
        stream_cancel = threading.Event()

        def build_data(resume_from: int) -> None:
            """(Re)position the host stream as of checkpoint step
            ``resume_from`` (0 = stream head). Called once at startup and
            again on every guard rollback — a rollback IS a resume, minus
            the process restart, so both paths share this code."""
            nonlocal host_it, stream_obj, delivered, train_drops
            nonlocal stream_base, eval_host_batches, stream_start_step
            nonlocal stream_cancel
            stream_cancel = threading.Event()  # fresh generation
            stream_start_step = resume_from
            delivered = 0
            train_drops = set()
            stream_base = 0
            stream_obj = None
            skip = (
                train_cfg.warmup_steps + resume_from if resume_from > 0 else 0
            )
            if host_iterator is not None:
                host_it = host_iterator
                for _ in range(skip):
                    next(host_it)
                return
            if not fineweb:
                host_it = make_host_iterator(
                    train_cfg, model_cfg, skip_batches=skip, row_stream=el_on
                )
                return
            sidecar = (
                ckpt.load_stream(resume_from, proc)
                if (ckpt and resume_from > 0) else None
            )
            if sidecar is not None:
                stream_obj = make_host_iterator(
                    train_cfg, model_cfg,
                    stream_position=sidecar["position"], history=hist,
                    chaos=chaos, on_recovery=bus.post, cancel=stream_cancel,
                )
                host_it = stream_obj
                stream_base = sidecar["stream_index"]
                if holdout_n:
                    # Eval batches were diverted from the stream HEAD; any
                    # diverted index past the resume point must still be
                    # withheld from training. The eval set itself is kept
                    # from before the rollback, restored from its sidecar,
                    # or (pre-sidecar checkpoints) rebuilt from a fresh
                    # head stream.
                    train_drops = {
                        d - sidecar["stream_index"]
                        for d in diverted_indices(holdout_every, holdout_n)
                        if d + 1 > sidecar["stream_index"]
                    }
                    if train_drops:
                        host_it = _drop_yields(host_it, train_drops)
                    if eval_host_batches is None:
                        eval_host_batches = ckpt.load_eval_set(proc)
                    if eval_host_batches is None:
                        head = make_host_iterator(train_cfg, model_cfg)
                        _, eval_host_batches = divert_holdout(
                            head, holdout_every, holdout_n
                        )
            else:
                stream_obj = make_host_iterator(
                    train_cfg, model_cfg, history=hist,
                    chaos=chaos, on_recovery=bus.post, cancel=stream_cancel,
                )
                host_it = stream_obj
                if holdout_n:
                    train_drops = diverted_indices(holdout_every, holdout_n)
                    host_it, diverted = divert_holdout(
                        host_it, holdout_every, holdout_n
                    )
                    if eval_host_batches is None:
                        eval_host_batches = diverted
                        if ckpt:
                            ckpt.save_eval_set(eval_host_batches, proc)
                for _ in range(skip):  # pre-sidecar fallback: drain
                    next(host_it)
                delivered = skip

        build_data(start_step)
        data_it = ShardedPrefetchIterator(
            host_it, mesh, batch_spec(rules), queue_size=train_cfg.prefetch
        )

        def stream_position_sidecar(step: int) -> dict | None:
            """Resume point of the batch TRAINING consumed for ``step`` —
            looked up in the stream's bounded position history (prefetch
            may have pulled a few batches further ahead)."""
            if stream_obj is None:
                return None
            n = delivered + (step - stream_start_step)
            idx = stream_index_for(n, train_drops)  # relative to THIS stream
            return {
                "position": stream_obj.position_after(idx),
                # Absolute index so a second resume recomputes holdout drops
                # against the true head-stream coordinates.
                "stream_index": stream_base + idx,
            }
        # Per-step dropout keys are fold_in(key, step) — a resumed run
        # replays the identical RNG stream from any step, unlike a split
        # chain whose position would restart at 0 (round-1 ADVICE).
        key = jax.random.key(train_cfg.seed, impl=train_cfg.prng_impl)

        result = TrainResult(state=state, mesh=mesh, base_params=base_params)
        # Step the result lists start after (losses[0] is result_base+1's);
        # only a rollback below the resume point ever moves it.
        result_base = start_step
        log_path = os.path.join(train_cfg.output_dir, "log.csv")
        clobber = bool(
            train_cfg.output_dir
            and lead
            and start_step == 0
            and not train_cfg.overwrite
            and os.path.exists(log_path)
        )
        if jax.process_count() > 1:
            # Only the lead writes (and may see) the artifact; broadcast its
            # verdict so every host raises — a lead-only raise would leave
            # the others hung on the first training collective.
            from jax.experimental import multihost_utils

            clobber = bool(multihost_utils.broadcast_one_to_all(clobber))
        if clobber:
            raise ValueError(
                f"refusing to overwrite existing {log_path} on a fresh run; "
                "pass overwrite: true, pick another output_dir, or enable "
                "checkpointing so the run resumes instead (guards committed "
                "comparison artifacts against stray smoke runs)"
            )
        # Telemetry AFTER the clobber guard (a refused run writes nothing):
        # its sinks open here, its clock and compile watcher have run since
        # train()'s first line. All emission — JSONL events, the back-compat
        # log.csv / eval_log.csv bridges, profiler windows — funnels
        # through this one object via the hook interface.
        clock.startup("obs")
        tele = Telemetry.for_training(
            train_cfg, lead=lead, process_index=jax.process_index(),
            resumed=start_step > 0, clock=clock, compiles=compiles,
        )
        # Device-profile context (ISSUE 8): capture metas carry the step's
        # model FLOPs, the chip peak, and the static collective-census
        # estimate, so `trace_report.py --device` derives device-time MFU
        # and runs the census cross-check offline without the model.
        from dtc_tpu.utils.metrics import peak_flops_per_chip, step_flops

        tele.set_device_profile_context(
            step_flops=step_flops(
                model_cfg, train_cfg.batch, model_cfg.max_seq_len
            ),
            peak_flops=peak_flops_per_chip(),
            comm_estimate=comm_bytes_per_step(
                model_cfg, train_cfg.batch, model_cfg.max_seq_len,
                {k: int(v) for k, v in mesh.shape.items()},
                train_cfg.parallel, train_cfg.pp_microbatches,
            ),
        )
        # From here to the training loop's own handler, any raise must
        # close the telemetry: a leaked sink would hold the JSONL shard
        # open (run_start unflushed) and leave the process-global compile
        # listener pointed at a dead Telemetry.
        csv = bool(train_cfg.output_dir and lead)
        if csv:
            try:
                tele.add_csv(log_path, ("step", "elapsed_time", "loss"), "train_row")
            except BaseException:
                tele.close()
                raise
        tele.on_run_start(
            strategy=train_cfg.parallel,
            mesh={k: int(v) for k, v in mesh.shape.items()},
            devices=num_devices,
            processes=jax.process_count(),
            batch=train_cfg.batch,
            seq_len=model_cfg.max_seq_len,
            steps=train_cfg.steps,
            start_step=start_step,
            dataset=train_cfg.dataset,
        )
        # The flash kernel's schedule is static: say once what it will do
        # with the score square (tiles, chunks run / masked / skipped).
        from dtc_tpu.ops.attention import flash_plan_event

        flash_plan = flash_plan_event(model_cfg)
        if flash_plan is not None:
            tele.registry.emit("flash_plan", **flash_plan)
        if model_cfg.layer_pattern:
            # What a pattern model will run, once: the pattern with its
            # passes and norm placement, each mixer kind's kernel and tiles,
            # and the expert layer's share.
            from dtc_tpu.models.pattern import layer_plan, moe_plan

            tele.registry.emit("layer_plan", **layer_plan(model_cfg))
            per_device = (train_cfg.batch // max(mesh.shape.get("data", 1), 1)
                          ) * model_cfg.max_seq_len
            plan = moe_plan(model_cfg, per_device)
            if plan is not None:
                tele.registry.emit("moe_plan", **plan)
        # Auto timing semantics: when rows are being logged, sync each step
        # so elapsed_time is step time, not dispatch time (see schema.py).
        sync_every_step = train_cfg.sync_every_step
        if sync_every_step is None:
            sync_every_step = bool(train_cfg.output_dir)

        # ------ periodic held-out eval ------
        clock.startup("eval_setup")
        eval_fn = None
        if train_cfg.eval_every > 0:
            try:
                from dtc_tpu.data.prefetch import split_put
                from dtc_tpu.train.train_step import create_eval_step

                eval_fn = create_eval_step(
                    mesh, model, rules=rules, base_params=base_params
                )
                spec = batch_spec(rules)
                if eval_host_batches is not None:
                    # FineWeb: a REAL holdout — every eval_holdout_every-th
                    # batch from the stream head, diverted before training
                    # ever sees it (round-3 VERDICT weak #6; disjointness
                    # asserted in tests/test_data.py).
                    if lead:
                        print(
                            f"[dtc_tpu] fineweb eval: {len(eval_host_batches)} "
                            f"held-out batches (every {holdout_every}th from "
                            "the stream head), excluded from training"
                        )
                    eval_set = [
                        split_put(b, mesh, spec) for b in eval_host_batches
                    ]
                else:
                    eval_it = make_eval_iterator(train_cfg, model_cfg)
                    eval_set = [
                        split_put(next(eval_it), mesh, spec)
                        for _ in range(train_cfg.eval_batches)
                    ]
                if train_cfg.output_dir and lead:
                    tele.add_csv(
                        os.path.join(train_cfg.output_dir, "eval_log.csv"),
                        ("step", "loss"),
                        "eval",
                    )
            except BaseException:
                tele.close()
                raise

        def commit_and_truncate(
            target: int,
            window_rows: list[tuple[int, float]],
            window_losses: list[float],
        ) -> None:
            """Shared recovery bookkeeping (rollback AND elastic resize):
            COMMIT the detection window's prefix at or before the restored
            step (those steps will not be replayed — e.g. a target at 10
            inside a 9..16 window must still log 9 and 10), then drop the
            poisoned suffix from the in-memory results; the replayed
            steps re-append (and re-log) from the restored step.
            result_base is the step the lists currently start AFTER —
            start_step originally, but a recovery below the resume point
            moves it down, and a later truncation must count from where
            the lists now begin."""
            nonlocal result_base
            for (s, el), lo in zip(window_rows, window_losses):
                if s <= target:  # not replayed: commit now or lose it
                    result.losses.append(lo)
                    tele.emit_train_row(s, el, lo)
            keep = max(target - result_base, 0)
            del result.losses[keep:]
            del result.elapsed_times[keep:]
            result.eval_losses[:] = [
                e for e in result.eval_losses if e[0] <= target
            ]
            result_base = min(result_base, target)

        def restore_from_tiers(
            cur_step: int, max_step: int | None, target_mesh: Mesh
        ) -> tuple[PyTree | None, int | None, str, bool]:
            """Two-tier restore-source selection, shared by the guard
            rollback and the elastic resize so the two recoveries cannot
            drift: the newest COMPLETE in-memory snapshot at or before
            ``max_step`` (restored onto ``target_mesh`` via fresh
            NamedShardings), else the newest VERIFIED cold checkpoint
            (resharded only when the mesh actually changed). Returns
            ``(state, step, tier, used_mirror)`` — state None when no
            source exists; the callers decide whether that is a warning
            (rollback) or fatal (resize)."""
            if snap_store is not None:
                snap_store.drain()
                snap = snap_store.latest(max_step=max_step)
                if snap is not None:
                    from dtc_tpu.resilience import SnapshotIncompleteError

                    try:
                        restored, used_mirror = snap_store.restore(
                            snap, hosts.alive, target_mesh
                        )
                        return restored, snap.step, "memory", used_mirror
                    except SnapshotIncompleteError as e:
                        tele.on_recovery(
                            cur_step, action="snapshot_incomplete",
                            reason=str(e),
                        )
            if ckpt is None:
                return None, None, "cold", False
            try:
                state_cold, target = ckpt.restore_latest(state)
            except FileNotFoundError:
                return None, None, "cold", False
            if target_mesh is not mesh:
                state_cold = _reshard_onto(state_cold, target_mesh)
            return state_cold, target, "cold", False

        def do_rollback(
            cur_step: int,
            reason: str,
            window_losses: list[float],
            window_rows: list[tuple[int, float]],
        ) -> int | None:
            """Guard ladder rung 2: restore pre-anomaly state and re-seek
            the data stream, returning the restored step (the loop
            resumes from there). None when no restore source exists yet
            (the guard then only warns).

            Restore source order: the newest COMPLETE in-memory snapshot
            STRICTLY before the window's last healthy boundary (elastic
            hot tier — with the cold cadence demoted via ``cold_every``,
            the disk checkpoint alone would lose up to cold_every steps
            to a NaN), then the newest VERIFIED disk checkpoint. The
            bound keeps never-validated state out of reach: snapshots
            inside the anomalous window, and the one AT the boundary
            itself, whose update no observed loss has vouched for (see
            the comment at the ``latest`` call)."""
            nonlocal state, data_it
            # Goodput ledger (ISSUE 16): the detect->restored gap is a
            # wall-clock read at each end of work this path does anyway —
            # no new device syncs, and the ledger no longer has to infer
            # the window from neighboring spans.
            t_detect = time.time()
            # A step's loss is computed on the params going INTO it
            # (value_and_grad before the update), so the previous
            # window's healthy losses — through step `boundary` —
            # validate snapshots only through boundary-1: the snapshot
            # AT the boundary holds that step's never-validated update
            # (an anomaly born there first shows at boundary+1, inside
            # the poisoned window, and restoring it would replay
            # straight back into it).
            boundary = cur_step - len(window_losses)
            state_rb, target, tier, _ = restore_from_tiers(
                cur_step, boundary - 1, mesh
            )
            if state_rb is None:
                return None  # nothing intact yet: the guard only warns
            # Re-commit stray scalar leaves to the mesh so the restored
            # state's input signature matches the compiled step executable
            # exactly — a rollback must not trigger a recompile.
            state = canonicalize_state_placement(state_rb, mesh)
            stream_cancel.set()  # wake any retry backoff: the stream is dead
            data_it.close()  # stop the old prefetch worker before rebuilding
            build_data(target)
            data_it = ShardedPrefetchIterator(
                host_it, mesh, batch_spec(rules), queue_size=train_cfg.prefetch
            )
            guard.note_rollback()
            commit_and_truncate(target, window_rows, window_losses)
            tele.on_recovery(
                cur_step, action="rollback", to_step=target, reason=reason,
                tier=tier, rollbacks=guard.rollbacks_done,
                t_detect=round(t_detect, 6), t_restored=round(time.time(), 6),
            )
            tele.drain_recovery_bus(bus, cur_step)
            # The restore's host transfers may compile tiny executables —
            # attribute them here, not as a train-step recompile.
            tele.record_aux_compile(cur_step, "rollback")
            tele.flush()
            if lead:
                print(
                    f"[dtc_tpu] ROLLBACK: {reason} — restored {tier} "
                    f"snapshot step {target}, stream re-seeked "
                    f"({guard.rollbacks_done}/{res_cfg.guard.max_rollbacks})"
                )
            return target

        def do_elastic_resize(
            cur_step: int,
            lost: list[int],
            window_device_losses: list[jax.Array],
            window_rows: list[tuple[int, float]],
        ) -> int:
            """Shrink-and-continue (ISSUE 15): rebuild a smaller mesh from
            the surviving hosts, restore the newest complete in-memory
            snapshot onto it (cold tier as fallback when the peers cannot
            reconstruct), re-seek the row stream by tokens consumed, and
            return the restored step — the loop replays from there. The
            global batch is preserved; the per-device batch rescales.

            Everything here runs OUTSIDE the hot path (a host just died);
            the host syncs below are the recovery's, not the loop's."""
            nonlocal state, data_it, mesh, train_step, num_devices
            nonlocal result_base, eval_fn, eval_set, snap_dispatch_cold
            from dtc_tpu.resilience.elastic import resize_mesh
            from dtc_tpu.resilience.errors import ElasticAbort

            # Goodput ledger (ISSUE 16): explicit detect/restored stamps
            # — wall-clock reads on a path that just lost a host, never
            # a new sync in the hot loop.
            t_detect = time.time()

            # target_hosts=None -> the survivor set: the host-loss resize
            # is the shrink direction of the general resize (the pool's
            # GROW passes an explicit larger lease through the same
            # function).
            new_mesh = resize_mesh(mesh, hosts)
            new_data = int(new_mesh.shape["data"])
            if train_cfg.batch % new_data != 0:
                raise ElasticAbort(
                    f"global batch {train_cfg.batch} does not shard over "
                    f"the shrunk data axis {new_data}; no valid elastic "
                    "continuation exists"
                )
            # Restore source: newest COMPLETE hot-tier snapshot; the cold
            # (disk) tier only when the survivors cannot reconstruct it.
            restored, target, tier, used_mirror = restore_from_tiers(
                cur_step, None, new_mesh
            )
            if restored is None:
                raise ElasticAbort(
                    "no complete in-memory snapshot survives hosts "
                    f"{sorted(lost)} being lost and no intact cold-tier "
                    "checkpoint; elastic recovery is impossible — "
                    "restart from a reprovisioned slice"
                )
            # The window's losses are still on-device mid-window (unlike
            # do_rollback, which runs at a boundary with them fetched) —
            # fetch, then share the rollback's commit/truncate contract.
            fetched = [
                float(v)
                for v in jax.device_get(jnp.stack(window_device_losses))
            ] if window_device_losses else []
            commit_and_truncate(target, window_rows, fetched)
            # Swap the mesh and rebuild everything mesh-shaped. The ONE
            # new train-step executable this costs is asserted by the
            # elastic tests (exactly one recompile event, at the first
            # replayed step — not excused, counted).
            resize_ctx.enter_context(new_mesh)
            mesh = new_mesh
            num_devices = len(hosts.survivor_devices())
            state = canonicalize_state_placement(restored, mesh)
            train_step = create_train_step(
                mesh, model=model,
                num_microbatches=train_cfg.pp_microbatches, rules=rules,
                pp_schedule=train_cfg.pp_schedule,
                pp_virtual=train_cfg.pp_virtual_stages, state=state,
                base_params=None,
            )
            stream_cancel.set()
            data_it.close()
            build_data(target)
            data_it = ShardedPrefetchIterator(
                host_it, mesh, batch_spec(rules),
                queue_size=train_cfg.prefetch,
            )
            if eval_fn is not None:
                # Eval state is mesh-shaped too: rebuild the step and
                # re-place the (deterministic, synthetic) eval batches.
                from dtc_tpu.data.prefetch import split_put
                from dtc_tpu.train.train_step import create_eval_step

                eval_fn = create_eval_step(mesh, model, rules=rules)
                spec = batch_spec(rules)
                eval_it = make_eval_iterator(train_cfg, model_cfg)
                eval_set = [
                    split_put(next(eval_it), mesh, spec)
                    for _ in range(train_cfg.eval_batches)
                ]
            tele.on_elastic(
                cur_step, "elastic_resize", to_step=target, tier=tier,
                used_mirror=used_mirror, hosts_lost=sorted(lost),
                devices=num_devices,
                mesh={k: int(v) for k, v in mesh.shape.items()},
                per_device_batch=train_cfg.batch // new_data,
                t_detect=round(t_detect, 6), t_restored=round(time.time(), 6),
            )
            tele.drain_recovery_bus(bus, cur_step)
            # Spill the restored state to the cold tier immediately: a
            # second failure before the next cold save would otherwise be
            # unrecoverable, and a shrunk RESTART (elastic.dead_hosts)
            # resumes from exactly this step.
            if ckpt is not None and el_cfg.spill_on_resize:
                with tele.span("elastic_spill", step=target):
                    ckpt.save(target, state)
                sidecar_out = stream_position_sidecar(target)
                if sidecar_out is not None:
                    ckpt.save_stream(target, sidecar_out, jax.process_index())
                if chaos is not None:
                    # Torn spill: a preemption mid-write — the verified-
                    # checkpoint fallback must reject it on restore.
                    chaos.maybe_tear_cold_spill(target, ckpt.step_dir(target))
                tele.on_elastic(target, "elastic_spill", detected_at=cur_step)
            # The restore's host transfers / loss-stack fetch compile tiny
            # executables — attribute them to the resize, so the first
            # replayed step shows only the one real train-step recompile.
            # The NEW mesh also recompiles the snapshot copy executables
            # at the next dispatch; re-arm that tick's attribution.
            snap_dispatch_cold = True
            tele.record_aux_compile(cur_step, "elastic_resize")
            tele.flush()
            if lead:
                print(
                    f"[dtc_tpu] ELASTIC RESIZE: hosts {sorted(lost)} lost "
                    f"— restored {tier} snapshot step {target}"
                    f"{' (ring mirror)' if used_mirror else ''}, mesh -> "
                    f"{dict(mesh.shape)}, per-device batch "
                    f"{train_cfg.batch // new_data}, continuing"
                )
            return target

        def run_eval(step: int) -> float:
            """Returns the wall-clock the eval pass took, so the caller can
            keep it out of the cumulative training elapsed_time."""
            # Drain pending async training steps BEFORE the eval clock
            # starts: their device time must stay in training elapsed_time,
            # not be absorbed into (and subtracted as) eval time.
            if device_losses:
                jax.device_get(device_losses[-1])
            t0 = time.perf_counter()
            # Pipeline params are stacked (S, L/S, ...); eval runs the plain
            # GSPMD forward, so unstack a view first.
            from dtc_tpu.parallel.pipeline import pp_unstack_params

            params = state.params
            if mesh.shape.get("pipe", 1) > 1:
                params = pp_unstack_params(params, train_cfg.pp_virtual_stages)
            vals = [
                float(jax.device_get(eval_fn(params, Batch(x=x, y=y))))
                for x, y in eval_set
            ]
            el = float(np.mean(vals))
            result.eval_losses.append((step, el))
            if lead:
                print(f"Eval @ step {step}: loss {el:.4f}")
            dt = time.perf_counter() - t0
            tele.on_eval(step, el, duration_s=dt)
            tele.flush()
            return dt

        # ------ preemption safety (SURVEY §5 failure-detection row) ------
        # SIGTERM (the preemption signal on TPU VMs) requests a graceful
        # stop: the loop finishes the current step, saves a final
        # checkpoint (+ stream position), flushes the CSV, and returns.
        # resume=True then continues bit-exactly (scripts/resume_demo.py
        # proved the mechanism end-to-end on the real chip; this moves the
        # guarantee into every trainer run).
        import signal

        stop_requested = {"flag": False}
        prev_handler = None
        in_main_thread = threading.current_thread() is threading.main_thread()
        if in_main_thread:
            def _on_sigterm(signum, frame):
                stop_requested["flag"] = True
                if lead:
                    print("[dtc_tpu] SIGTERM received — will checkpoint and stop")
            prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)

        try:
            # ------ warmup (untimed, excluded from measurement; ref uses 5) ------
            warmup_steps = 0 if start_step > 0 else train_cfg.warmup_steps
            if lead and warmup_steps:
                print("Warmup")
            clock.startup("warmup_rest")  # the eager fold_in below compiles
            warm_key = jax.random.fold_in(key, 2**31 - 1)  # stream disjoint from steps
            if warmup_steps:
                # The first batch is the feed starting up (`data`); the first
                # step from its call to its loss fetched — trace, lower,
                # compile or cache load, first execution — is `warmup_first`.
                clock.startup("data")
                x, y = next(data_it)
                clock.startup("warmup_first")
                # a pattern model's step also returns its counters
                state, loss, *_ = train_step(state, Batch(x=x, y=y), jax.random.fold_in(warm_key, 0))
                jax.device_get(loss)
                clock.startup("warmup_rest")
            for i in range(1, warmup_steps):
                x, y = next(data_it)
                state, loss, *_ = train_step(state, Batch(x=x, y=y), jax.random.fold_in(warm_key, i))
            delivered += warmup_steps
            if warmup_steps > 1:
                # Sync via value fetch.
                jax.device_get(loss)

            if start_step > 0:
                clock.startup("warmup_first")
                # Warmup is skipped on resume, so the first timed step would pay
                # the full XLA compile and corrupt the first log window's
                # timings. Compile now by running the step once on a throwaway
                # COPY of the restored state with a dummy batch — same
                # shapes/shardings hit the same executable, and neither the real
                # state nor the data/RNG streams are touched.
                dummy = jax.device_put(
                    np.zeros((train_cfg.batch, model_cfg.max_seq_len), np.int32),
                    NamedSharding(mesh, batch_spec(rules)),
                )
                state_copy = jax.tree.map(
                    lambda v: jnp.copy(v) if isinstance(v, jax.Array) else v, state
                )
                _, compile_loss, *_ = train_step(
                    state_copy, Batch(x=dummy, y=dummy), jax.random.fold_in(key, 0)
                )
                jax.device_get(compile_loss)

            clock.startup("warmup_rest")
            # Everything compiled so far (warmup / resume pre-compile) is
            # the run's startup compile — emitted as the step-0 `compile`
            # event. With warmup_steps=0 the first timed step pays it and
            # on_step_end attributes it there instead.
            tele.record_startup_compile()

            # ------ timed loop ------
            if lead:
                print("Start measuring")
            device_losses: list[jax.Array] = []
            # A pattern model's per-step counters (train_step.stack_counters:
            # a dict of small arrays a step): kept on the device beside the
            # losses and fetched with them at the log boundary, never on
            # their own.
            device_counters: list[dict[str, jax.Array]] = []
            pending_rows: list[tuple[int, float]] = []
            # The snapshot dispatch's per-leaf copy executables compile on
            # the FIRST begin() for a given mesh; attribute that one tick
            # (and only it — blanket attribution every snapshot_every
            # steps would mask genuine train-step recompiles, the exact
            # signal the watcher exists for).
            snap_dispatch_cold = True
            window_start = time.perf_counter()
            window_steps = 0
            start_time = time.perf_counter()

            tokens_per_step = train_cfg.batch * model_cfg.max_seq_len

            if wd is not None:
                # The hard-timeout monitor aborts via interrupt_main — off
                # the main thread that lands in an unrelated thread and the
                # clean WatchdogTimeout path never fires (same reason the
                # SIGTERM handler above is main-thread-gated). Flag-only
                # observation still works from any thread.
                if in_main_thread:
                    wd.start()
                elif res_cfg.watchdog.hard_timeout_s > 0:
                    print(
                        "[dtc_tpu] WARNING: watchdog hard_timeout_s disabled "
                        "(trainer not on the main thread); flagging only"
                    )
            # while (not for): a guard rollback moves the step pointer
            # BACKWARD to the restored checkpoint and the loop replays.
            step = start_step
            while step < train_cfg.steps:
                step += 1
                tele.on_step_start(step)  # profiler window + step clock
                if wd is not None:
                    wd.arm(step)  # hard-timeout cover for data_wait+step
                with tele.clock.phase("data_wait"):
                    x, y = next(data_it)
                with tele.clock.phase("dispatch"):
                    # Eager: two small device programs launched from Python
                    # every step (PERF.md, `idle_rng_ms.train`).
                    with tele.clock.phase("rng"):
                        step_key = jax.random.fold_in(key, step)
                    with tele.clock.phase("launch"):
                        state, loss, *counters = train_step(state, Batch(x=x, y=y), step_key)
                if chaos is not None:
                    poisoned, loss = chaos.maybe_poison(step, state, loss)
                    if poisoned is not state:
                        state = poisoned
                        # The poison's eager per-leaf ops compile tiny
                        # executables — attribute them, don't let the next
                        # on_step_end flag a phantom train-step recompile.
                        tele.record_aux_compile(step, "chaos_poison")
                device_losses.append(loss)
                device_counters.extend(counters)
                if sync_every_step:
                    with tele.clock.phase("block"):
                        jax.block_until_ready(loss)
                now = time.perf_counter()
                result.elapsed_times.append(now - start_time)
                pending_rows.append((step, now - start_time))
                breakdown = tele.on_step_end(step, elapsed_s=now - start_time)
                stalled_flag = False
                if wd is not None:
                    flag = wd.observe(step, breakdown["step_time_s"])
                    if flag is not None:
                        tele.on_hung_step(**flag)
                        # A hung step is the collective-stall signal: the
                        # heartbeat poll below escalates (one missed beat
                        # then declares the host lost).
                        stalled_flag = True
                        if res_cfg.watchdog.profile_on_flag:
                            tele.arm_profile_window(step + 1)
                window_steps += 1

                if el_on:
                    # Emulation-side chaos lands BEFORE the heartbeat tick
                    # and the snapshot cadence: a host killed at step k
                    # contributes no beat and no stored shards from k on,
                    # so the last COMPLETE snapshot is k-1 — that is the
                    # <=1-step-lost-work bound the acceptance test pins.
                    if chaos is not None:
                        victim = chaos.kill_host(step)
                        if victim is not None:
                            hosts.kill(victim)
                        slow = chaos.slow_host(step)
                        if slow is not None:
                            monitor.mark_slow(slow[0], step + slow[1] - 1)
                        gone = chaos.lose_snapshot(step)
                        if gone is not None:
                            snap_store.drop_primary(gone)
                    monitor.tick(step)
                    if step % el_cfg.snapshot_every == 0:
                        # Async + double-buffered: device-side copies and
                        # a host transfer are DISPATCHED here; hashing and
                        # filing happen on the commit thread. No host
                        # sync on this path (hostsync lint covers it).
                        if snap_store.begin(step, state) and snap_dispatch_cold:
                            snap_dispatch_cold = False
                            tele.record_aux_compile(step, "snapshot_dispatch")
                    lost_now: list[int] = []
                    for ev in monitor.poll(step, stalled=stalled_flag):
                        kind = ev.pop("kind")
                        tele.on_elastic(step, kind, **ev)
                        if kind == "host_lost":
                            lost_now.append(ev["host"])
                    if lost_now:
                        target = do_elastic_resize(
                            step, lost_now, device_losses, pending_rows
                        )
                        # Replay from the restored step on the survivor
                        # mesh; the detection window's suffix was
                        # discarded by the resize (no rows, no eval, no
                        # checkpoint from it).
                        step = target
                        device_losses, pending_rows = [], []
                        device_counters = []
                        window_start = time.perf_counter()
                        window_steps = 0
                        if wd is not None:
                            wd.disarm()
                        continue

                if chaos is not None and chaos.should_preempt(step):
                    if in_main_thread:
                        # Simulated preemption: a REAL signal through the
                        # real handler (delivered synchronously here).
                        os.kill(os.getpid(), signal.SIGTERM)
                    else:
                        # No graceful handler was installed off the main
                        # thread — a raw SIGTERM would hit the default
                        # disposition and kill the process. Emulate the
                        # handler's effect instead.
                        stop_requested["flag"] = True
                stopping = stop_requested["flag"]
                if stopping:
                    # Preemption post-mortem: the last-N-events timeline,
                    # dumped before the checkpoint/flush work below (which
                    # the preemptor may not leave time for). Drain the bus
                    # first so the chaos/recovery records that triggered
                    # the stop are IN the dumped timeline.
                    tele.drain_recovery_bus(bus, step)
                    tele.dump_flight("sigterm", step=step)
                    if lead:
                        print(f"[dtc_tpu] stopping at step {step} (SIGTERM)")

                if step % train_cfg.log_every == 0 or step == train_cfg.steps or stopping:
                    # Re-arm the hard timeout for the boundary's loss
                    # fetch: with per-step sync OFF, dispatch is async and
                    # a wedged collective actually blocks HERE — not inside
                    # the step call the per-step arm covered. The healthy
                    # wait is the WHOLE dispatched window, so the budget
                    # scales by log_every. Disarmed once the fetch+guard
                    # section completes: eval and verified checkpoint saves
                    # scale with model size, not step time, and must not be
                    # judged by a step-scale budget.
                    if wd is not None:
                        wd.arm(
                            step,
                            budget_s=res_cfg.watchdog.hard_timeout_s
                            * max(train_cfg.log_every, 1),
                        )
                    # One stacked transfer, not len(window) scalar fetches.
                    fetched, counted = jax.device_get((
                        jnp.stack(device_losses),
                        jax.tree.map(lambda *a: jnp.stack(a), *device_counters)
                        if device_counters else None,
                    ))
                    losses = [float(v) for v in fetched]
                    now = time.perf_counter()  # after the device sync
                    if counted is not None:
                        _emit_counters(tele, [s for s, _ in pending_rows], counted)
                        device_counters = []
                    # Anomaly guard rides the losses ALREADY fetched for
                    # logging — zero additional per-step syncs.
                    if guard is not None:
                        decision = guard.check_window(step, losses)
                        if decision.anomalous:
                            tele.on_anomaly(
                                step, reason=decision.reason,
                                action=decision.action,
                            )
                            if lead:
                                print(
                                    f"[dtc_tpu] ANOMALY: {decision.reason} "
                                    f"-> {decision.action}"
                                )
                        if decision.action == "abort":
                            tele.on_recovery(
                                step, action="abort", reason=decision.reason
                            )
                            tele.drain_recovery_bus(bus, step)
                            raise AnomalyAbort(decision.reason)
                        if decision.action == "rollback":
                            target = do_rollback(
                                step, decision.reason, losses, pending_rows
                            )
                            if target is not None:
                                # Discard the poisoned window wholesale —
                                # no rows logged, no eval, no checkpoint —
                                # and replay from the restored step.
                                step = target
                                device_losses, pending_rows = [], []
                                device_counters = []
                                window_start = time.perf_counter()
                                window_steps = 0
                                if wd is not None:
                                    wd.disarm()  # continue skips loop bottom
                                continue
                            # No intact checkpoint to restore: burn a
                            # ladder rung anyway so persistent anomalies
                            # still reach the abort rung instead of
                            # re-deciding "rollback" forever.
                            guard.note_rollback_failed()
                            tele.on_recovery(
                                step, action="rollback_failed",
                                reason=decision.reason,
                            )
                    # With per-step sync OFF, rows are dispatch-stamped:
                    # re-stamp the window's last row post-fetch so every
                    # log_every-th elapsed_time (and the final total) reflects
                    # completed device work. With sync ON every row is already
                    # device-synced — re-stamping would add the loss-fetch RTT.
                    if not sync_every_step:
                        pending_rows[-1] = (pending_rows[-1][0], now - start_time)
                        result.elapsed_times[-1] = now - start_time
                    result.losses.extend(losses)
                    # train_row events feed the JSONL stream on every
                    # process and the log.csv bridge on the lead.
                    for (s, el), lo in zip(pending_rows, losses):
                        tele.emit_train_row(s, el, lo)
                    avg_step = (now - window_start) / max(window_steps, 1)
                    u = mfu(
                        model_cfg, train_cfg.batch, model_cfg.max_seq_len, avg_step, num_devices
                    )
                    tele.on_window(
                        step,
                        avg_step_s=avg_step,
                        tokens_per_sec=tokens_per_step / avg_step,
                        mfu=u,
                    )
                    # Surface recovery actions posted from other threads
                    # (stream retries, checkpoint fallbacks) at the boundary.
                    tele.drain_recovery_bus(bus, step)
                    tele.flush()
                    if lead:
                        msg = (
                            f"Step: {step} | Avg loss: {np.mean(losses):.4f} | "
                            f"Average step time: {avg_step:.4f} | "
                            f"tokens/s: {tokens_per_step / avg_step:,.0f}"
                        )
                        if u is not None:
                            msg += f" | MFU: {u * 100:.1f}%"
                        print(msg)
                    device_losses, pending_rows = [], []
                    # The loss-stack fetch compiles its own tiny executable
                    # on the first boundary — attribute it here, not as a
                    # phantom train-step recompile at the next step.
                    tele.record_aux_compile(step, "log_boundary")
                    window_start = time.perf_counter()
                    window_steps = 0
                    if wd is not None:
                        wd.disarm()  # before model-size-scale eval/save work

                if eval_fn is not None and (
                    step % train_cfg.eval_every == 0 or step == train_cfg.steps
                ):
                    eval_dt = run_eval(step)
                    tele.record_aux_compile(step, "eval")
                    # Keep eval out of both the cumulative elapsed_time (shift
                    # the epoch forward by the eval duration — rows stay pure
                    # training time, comparable to the eval-less reference) and
                    # the next window's step-time accounting.
                    start_time += eval_dt
                    window_start = time.perf_counter()
                    window_steps = 0

                if ckpt and (step % checkpoint_every_eff == 0 or stopping):
                    # Health-gate the save: between anomaly onset and the
                    # next log boundary the state may already be poisoned
                    # (NaN, or a finite spike in spike mode), and a
                    # poisoned-but-bit-intact checkpoint would become the
                    # rollback target (restoring it forever until the
                    # ladder aborts). One scalar fetch per checkpoint —
                    # noise next to the Orbax write it gates.
                    if guard is not None and not guard.healthy_loss(
                        float(jax.device_get(loss))
                    ):
                        tele.on_recovery(
                            step, action="skip_checkpoint",
                            reason="unhealthy loss at save point",
                        )
                        if lead:
                            print(
                                f"[dtc_tpu] skipping checkpoint at step {step}: "
                                "unhealthy loss at save point (see the "
                                "telemetry recovery event)"
                            )
                    else:
                        tele.registry.counter("checkpoints").inc()
                        with tele.span("checkpoint", step=step):
                            ckpt.save(step, state)  # waits + writes integrity manifest
                        sidecar_out = stream_position_sidecar(step)
                        if sidecar_out is not None:
                            # Per-process: each pod host's stream position
                            # differs.
                            ckpt.save_stream(
                                step, sidecar_out, jax.process_index()
                            )
                        if chaos is not None:
                            # Damage AFTER the verified write: later reads
                            # must detect the mismatch and fall back.
                            chaos.maybe_corrupt_checkpoint(
                                step, ckpt.step_dir(step)
                            )
                            # Torn cold-tier spill (ISSUE 15): truncated
                            # mid-write, rejected by the manifest check.
                            chaos.maybe_tear_cold_spill(
                                step, ckpt.step_dir(step)
                            )
                    tele.record_aux_compile(step, "checkpoint")

                if wd is not None:
                    wd.disarm()  # end of boundary-iteration blocking work
                if stopping:
                    break
        except KeyboardInterrupt as e:
            # The watchdog's hard-timeout monitor interrupts the main
            # thread; surface it as the typed abort, telemetry closed.
            tele.dump_flight(
                "watchdog_timeout" if (wd is not None and wd.timed_out)
                else "interrupt"
            )
            tele.close()
            if wd is not None and wd.timed_out:
                raise WatchdogTimeout(
                    f"step exceeded hard timeout "
                    f"({res_cfg.watchdog.hard_timeout_s}s)"
                ) from e
            raise
        except BaseException as e:
            # A crashed run still keeps its flushed JSONL prefix — same
            # crash-survival contract as the incremental CSV — plus a
            # flight-recorder dump so the post-mortem starts from a
            # timeline, not a truncated log.
            tele.dump_flight(f"crash: {type(e).__name__}")
            tele.close()
            raise
        finally:
            if wd is not None:
                wd.stop()
            if snap_store is not None:
                snap_store.close()
            # Unwind any survivor-mesh contexts entered by elastic resizes
            # BEFORE the enclosing ``with mesh`` exits (LIFO); the `mesh`
            # variable keeps pointing at the final mesh for run-end
            # reporting.
            resize_ctx.close()
            # Stop the prefetch worker (rollback may have already swapped
            # it once; close is idempotent) so no thread outlives the run.
            try:
                data_it.close()
            except Exception:
                pass
            # Restore even when the loop raises: a stale handler would
            # silently swallow a later (real) SIGTERM.
            if in_main_thread:
                signal.signal(signal.SIGTERM, prev_handler)
        total = time.perf_counter() - start_time
        timed_steps = len(result.elapsed_times)
        comm = comm_bytes_per_step(
            model_cfg, train_cfg.batch, model_cfg.max_seq_len,
            {k: int(v) for k, v in mesh.shape.items()},
            train_cfg.parallel, train_cfg.pp_microbatches,
        )
        tele.drain_recovery_bus(bus, step)  # tail actions (retry, fallback)
        tele.on_run_end(
            total_time_s=round(total, 4),
            steps=timed_steps,
            tokens_per_sec=(
                round(tokens_per_step * timed_steps / total, 1) if total > 0 else None
            ),
            mfu=(
                mfu(model_cfg, train_cfg.batch, model_cfg.max_seq_len,
                    total / timed_steps, num_devices)
                if timed_steps else None
            ),
            est_comm_bytes_per_step=comm,
        )
        tele.close()
        if lead:
            print(f"Total time: {total}")
            print("End")
        if ckpt:
            ckpt.wait()
            ckpt.close()
        result.state = state
        result.mesh = mesh  # an elastic resize swapped it mid-run
        return result
