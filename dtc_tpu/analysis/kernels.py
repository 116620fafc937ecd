"""Kernel auditor — DMA happens-before race detection, static VMEM
plans, and the kernel lint family (ISSUE 20; the PR 5/13 auditor
stack's fourth leg).

Why this exists: every Pallas kernel in the repo ships on hand-
maintained DMA discipline ("per-chunk recv slots, chained dma.wait()")
while interpret mode — how every CPU test runs them — has no barrier
primitive and **no races**: the emulator
sequences remote DMAs deterministically, so a slot-reuse bug or a
missing send wait is structurally invisible to every test we can run.
This module machine-checks the discipline the way happens-before race
detectors do (Lamport 1978; FastTrack, Flanagan & Freund 2009), and
turns VMEM from a hand-rolled estimate into a committed, drift-gated
static plan — the "certify each rung before a chip is spent" pattern.

Three families:

1. **DMA happens-before race detector.** The ring kernels in
   ``ops/overlap_collectives.py`` carry a recording seam
   (``_SCHED_LOG``): when :class:`capture_schedule` installs a list,
   every ``make_async_remote_copy`` start/wait and every shared-buffer
   load/store appends one STATIC event at kernel trace time (under
   shard_map the body traces once, with slots recorded symbolically —
   ("rel", off) = ``(device + off) % ring``, or ("abs", k)).
   :func:`check_ring_schedule` instantiates the events for every ring
   position, rebuilds the CONCURRENT schedule — a send is in flight
   from its ``start`` until the wait that covers it, overlapping the
   next step's compute — and vector-clock-checks:

   - ``kernel.race.recv_before_wait`` — a receive slot is read (or
     forwarded as a DMA source) without the wait covering its fill
     happening-before the access;
   - ``kernel.race.send_rewrite`` — a send's source buffer is
     rewritten while that send may still be reading it;
   - ``kernel.race.slot_reuse`` — two DMAs land in the same
     (device, buffer, slot): the per-chunk write-once discipline is
     what makes the ring safe without flow-control semaphores;
   - ``kernel.race.unwaited_dma`` — a DMA still in flight when the
     kernel returns;
   - ``kernel.race.unfilled_read`` / ``kernel.race.unmatched_wait`` —
     a receive-slot read no DMA ever fills / a wait no fill matches.

   Semaphore semantics modeled: ``dma.wait()`` is a chained FIFO wait —
   the device's k-th wait covers its OWN k-th send (send semaphore) and
   the k-th INCOMING fill (receive semaphore), exactly the discipline
   the kernels' comments promise. Fabricated broken schedules in
   tests/test_kernel_audit.py prove every rule fires; the shipped
   kernels must produce zero findings.

2. **Static VMEM plans across the model ladder.** The shared planner is
   :mod:`dtc_tpu.ops.vmem` (the kernels' own gates consult it; the
   megakernel's BlockSpecs are literally built from it). This module
   evaluates it per ladder rung — flagship, ~350M, ~1B
   (configs/model_ladder_*.yaml) — plus the analytic HBM plan
   (``utils.metrics.train_memory_bytes``), and commits the result as
   ``kernels_<rung>.json`` baselines under ``analysis/baselines/`` with
   the report.py drift gate, the megakernel's double-buffered bytes and
   the ``vmem_limit_bytes`` it states to Mosaic among them.

3. **Kernel lint family.** :func:`lint_grid_plan` checks index-map
   purity and the pipelining contract (weight blocks b-invariant —
   "weights re-fetch per layer, not per row" — row blocks actually
   advancing with the row coordinate, scalars in SMEM);
   :func:`lint_gate_coverage` AST-checks that every ops/ module
   launching a ``pallas_call`` gates it behind a ``supports*`` /
   ``_pallas_ok`` predicate that consults the shared planner, so gate
   and kernel cannot drift (no module is waived: flash attention's
   tiles come from the planner's ``flash_plan``, and a user's tiling is
   priced by ``flash_grid_tile_fits``).

``scripts/audit_graph.py --kernels`` is the CLI;
``scripts/verify_tier1.sh`` runs it as a pre-gate. Everything here is
CPU-only and static — it certifies schedule discipline and byte plans,
NOT hardware timing (no kernel time has been measured; what the chip's
compiler accepts is held by tests/test_chip_compile.py).
"""

from __future__ import annotations

import ast
import contextlib
import dataclasses
import json
import os
from typing import Any, Iterable, Iterator

from dtc_tpu.analysis.report import BASELINE_DIR, _baseline_path, _diff
from dtc_tpu.analysis.rules import Finding
from dtc_tpu.ops import vmem

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_OPS_DIR = os.path.join(_REPO_ROOT, "dtc_tpu", "ops")
_CONFIG_DIR = os.path.join(_REPO_ROOT, "configs")

#: The audited ladder rungs: the measured flagship plus the two
#: static-audit-only scale points (no training run — the point is to
#: certify the kernel plans BEFORE a chip is spent on them).
#: Each maps to its file under configs/ and the fields the audit sets over
#: it: the flagship is the model configs/model_config.yaml trains, priced
#: at its serving deployment (megakernel decode, remat on, no dropout).
LADDER_RUNGS = {
    "flagship": ("model_config.yaml", {
        "dropout": 0.0, "remat": True, "decode_attention": "fused_layers",
    }),
    "ladder_350m": ("model_ladder_350m.yaml", {}),
    "ladder_1b": ("model_ladder_1b.yaml", {}),
}

#: ops/ modules allowed to launch a pallas_call without consulting the
#: shared VMEM planner, with the reason (emitted as an info finding so
#: the waiver stays visible in every audit run). None since PR 27: the
#: flash kernels' tiles come from :func:`vmem.flash_plan`.
PALLAS_GATE_WAIVERS: dict[str, str] = {}


# ---------------------------------------------------------------------------
# 1. DMA happens-before race detector
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def capture_schedule() -> Iterator[list[dict]]:
    """Install the recording seam: inside the block, every ring-kernel
    trace appends its DMA/buffer events to the yielded list."""
    from dtc_tpu.ops import overlap_collectives as oc

    log: list[dict] = []
    prev = oc._SCHED_LOG
    oc._SCHED_LOG = log
    try:
        yield log
    finally:
        oc._SCHED_LOG = prev


def split_schedule_segments(log: Iterable[dict]) -> list[list[dict]]:
    """One segment per kernel trace: events belong to the most recent
    ``kind == "kernel"`` marker (jit may trace an op more than once —
    duplicate segments are checked independently and harmlessly)."""
    segments: list[list[dict]] = []
    for ev in log:
        if ev.get("kind") == "kernel":
            segments.append([ev])
        elif segments:
            segments[-1].append(ev)
    return segments


def _resolve_slot(expr: Any, device: int, ring: int) -> Any:
    if expr is None:
        return None
    tag, val = expr
    if tag == "rel":
        return (device + val) % ring
    if tag == "abs":
        return int(val)
    raise ValueError(f"unknown slot expr {expr!r}")


def _vc_leq(a: tuple[int, ...], b: tuple[int, ...]) -> bool:
    return all(x <= y for x, y in zip(a, b))


def check_ring_schedule(
    segment: list[dict], *, artifact: str | None = None,
) -> list[Finding]:
    """Happens-before audit of one recorded kernel schedule.

    The symbolic per-step events are instantiated at every ring position
    and replayed under the semaphore model (k-th wait covers the k-th
    own send and the k-th incoming fill, FIFO per the single incoming
    channel each ring device has), assigning every event a vector clock;
    the race rules are then pure VC comparisons — an access is safe iff
    the operation that makes it safe *happens-before* it, not merely
    precedes it in interpret mode's serialized execution."""
    if not segment or segment[0].get("kind") != "kernel":
        raise ValueError("segment must start with a 'kernel' event")
    head = segment[0]
    name = head.get("name", "?")
    ring = int(head["ring"])
    body = [e for e in segment[1:] if e.get("kind") != "kernel"]
    artifact = artifact or f"ops/overlap_collectives.py::{name}"
    findings: list[Finding] = []

    def race(rule: str, message: str) -> None:
        findings.append(
            Finding(f"kernel.race.{rule}", "error", artifact, message)
        )

    # --- instantiate the symbolic trace at every ring position --------
    events: list[list[dict]] = []
    for d in range(ring):
        devs = []
        for ev in body:
            e = dict(ev)
            if "slot" in e:
                e["slot"] = _resolve_slot(e["slot"], d, ring)
            if e["kind"] == "dma_start":
                e["src_slot"] = _resolve_slot(e.get("src_slot"), d, ring)
                e["dst_slot"] = _resolve_slot(e.get("dst_slot"), d, ring)
                e["receiver"] = (d + e.get("dst_device", 1)) % ring
            devs.append(e)
        events.append(devs)
    recv_bufs = {e["dst_buf"] for e in body if e["kind"] == "dma_start"}

    # --- replay: assign vector clocks under the semaphore model -------
    vc = [[0] * ring for _ in range(ring)]
    pc = [0] * ring
    waits_done = [0] * ring
    fills: list[list[dict]] = [[] for _ in range(ring)]  # arrival order
    sends: list[list[dict]] = [[] for _ in range(ring)]
    accesses: list[dict] = []  # every local read/write, with VC

    def step(d: int) -> None:
        ev = events[d][pc[d]]
        vc[d][d] += 1
        kind = ev["kind"]
        if kind in ("read", "write"):
            accesses.append({
                "device": d, "kind": kind, "buf": ev["buf"],
                "slot": ev.get("slot"), "step": ev.get("step"),
                "vc": tuple(vc[d]),
            })
        elif kind == "dma_start":
            snap = tuple(vc[d])
            # The DMA reads its source until the covering wait: model
            # the start as a read too (catches forwarding a slot whose
            # own fill has not landed).
            accesses.append({
                "device": d, "kind": "read", "buf": ev["src_buf"],
                "slot": ev.get("src_slot"), "step": ev.get("step"),
                "vc": snap, "via": "dma_src",
            })
            sends[d].append({
                "src": (ev["src_buf"], ev.get("src_slot")),
                "step": ev.get("step"), "start_vc": snap, "wait_vc": None,
            })
            fills[ev["receiver"]].append({
                "buf": ev["dst_buf"], "slot": ev.get("dst_slot"),
                "sender": d, "step": ev.get("step"),
                "start_vc": snap, "wait_vc": None,
            })
        elif kind == "dma_wait":
            k = waits_done[d]
            if k < len(fills[d]):
                fill = fills[d][k]
                vc[d] = [max(a, b) for a, b in zip(vc[d], fill["start_vc"])]
                fill["wait_vc"] = tuple(vc[d])
            else:
                race(
                    "unmatched_wait",
                    f"device {d} step {ev.get('step')}: dma.wait() #{k + 1} "
                    "has no matching incoming DMA — nothing ever signals "
                    "this semaphore (hardware would hang here)",
                )
            if k < len(sends[d]):
                sends[d][k]["wait_vc"] = tuple(vc[d])
            waits_done[d] += 1
        pc[d] += 1

    # Waits block until their fill exists (the sender must progress
    # first); everything else is non-blocking. If the whole ring is
    # stuck, the blocked wait is unmatched — flag it and force on.
    while True:
        progress = False
        for d in range(ring):
            while pc[d] < len(events[d]):
                ev = events[d][pc[d]]
                if (
                    ev["kind"] == "dma_wait"
                    and waits_done[d] >= len(fills[d])
                    and any(pc[o] < len(events[o]) for o in range(ring)
                            if o != d)
                ):
                    break
                step(d)
                progress = True
        if all(pc[d] >= len(events[d]) for d in range(ring)):
            break
        if not progress:
            stuck = next(d for d in range(ring) if pc[d] < len(events[d]))
            step(stuck)  # emits unmatched_wait, releases the deadlock

    # --- rule checks over the clocked schedule ------------------------
    # slot reuse: the per-chunk discipline is write-ONCE per slot.
    for d in range(ring):
        seen: dict[tuple, dict] = {}
        for fill in fills[d]:
            key = (fill["buf"], fill["slot"])
            if key in seen:
                race(
                    "slot_reuse",
                    f"device {d}: recv slot {fill['buf']}[{fill['slot']}] "
                    f"filled twice (sender step {seen[key]['step']} and "
                    f"step {fill['step']}) — per-chunk slots must be "
                    "written exactly once; reuse races the un-consumed "
                    "previous chunk",
                )
            else:
                seen[key] = fill

    # in-flight DMA at kernel end / send-source rewrite while in flight.
    for d in range(ring):
        for i, send in enumerate(sends[d]):
            if send["wait_vc"] is None:
                race(
                    "unwaited_dma",
                    f"device {d}: DMA started at step {send['step']} "
                    f"(send #{i + 1}) is never covered by a dma.wait() — "
                    "still in flight when the kernel returns",
                )
            buf, slot = send["src"]
            for acc in accesses:
                if (
                    acc["device"] == d and acc["kind"] == "write"
                    and (acc["buf"], acc["slot"]) == (buf, slot)
                    and acc["vc"][d] > send["start_vc"][d]
                    and (send["wait_vc"] is None
                         or acc["vc"][d] < send["wait_vc"][d])
                ):
                    race(
                        "send_rewrite",
                        f"device {d} step {acc['step']}: {buf}"
                        f"[{slot}] rewritten while the step-"
                        f"{send['step']} send is still reading it (no "
                        "covering dma.wait() between start and rewrite)",
                    )

    # recv-slot reads must happen-after the wait covering their fill.
    for acc in accesses:
        if acc["kind"] != "read" or acc["buf"] not in recv_bufs:
            continue
        d = acc["device"]
        matching = [
            f for f in fills[d]
            if (f["buf"], f["slot"]) == (acc["buf"], acc["slot"])
        ]
        what = (
            "forwarded as a DMA source" if acc.get("via") == "dma_src"
            else "read"
        )
        if not matching:
            race(
                "unfilled_read",
                f"device {d} step {acc['step']}: {acc['buf']}"
                f"[{acc['slot']}] {what} but no DMA ever fills that slot "
                "— the access observes uninitialized VMEM",
            )
        elif not any(
            f["wait_vc"] is not None and _vc_leq(f["wait_vc"], acc["vc"])
            for f in matching
        ):
            race(
                "recv_before_wait",
                f"device {d} step {acc['step']}: {acc['buf']}"
                f"[{acc['slot']}] {what} without the wait covering its "
                "fill happening-before the access — interpret mode "
                "serializes the DMA and hides this; hardware reads a "
                "partially-landed chunk",
            )
    return findings


def record_ring_schedules(ring: int = 4) -> list[list[dict]]:
    """Drive every shipped ring kernel under the recording seam and
    return the captured schedule segments.

    Runs the REAL kernels (interpret mode on the CPU mesh, the same path
    tests/test_overlap_collectives.py executes): the fused all-gather-
    matmul forward in both shard modes, both backward legs (dx re-gather
    + dw reduce-scatter) via ``jax.grad``, and the standalone
    matmul+reduce-scatter in both scatter modes — every ``pallas_call``
    site the module owns. Events are appended at trace time, so one jit
    per op suffices; shapes are tiny (the schedule is shape-independent:
    the ring length is the only structural parameter)."""
    import jax
    import jax.numpy as jnp

    from dtc_tpu.ops import overlap_collectives as oc

    if jax.device_count() < ring:
        raise RuntimeError(
            f"race audit needs {ring} devices, have {jax.device_count()} "
            "(run under the 8-virtual-device CPU mesh)"
        )
    mesh = jax.make_mesh((ring,), ("data",))
    k_full, n_full = 4 * ring, 2 * ring
    with capture_schedule() as log:
        with mesh:
            x = jnp.ones((ring, 2, k_full), jnp.float32)
            for shard_axis in (0, 1):
                def loss(xx, ww, _sa=shard_axis):
                    y = oc.overlap_dense_matmul(
                        xx, ww, shard_axis=_sa, axis_name="data",
                        mesh=mesh, backend="pallas",
                    )
                    return jnp.sum(y * y)

                w = jnp.ones((k_full, n_full), jnp.float32)
                jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
            a = jnp.ones((ring, 2, k_full), jnp.float32)
            b = jnp.ones((ring, 2, n_full), jnp.float32)
            for shard_axis in (0, 1):
                jax.jit(
                    lambda aa, bb, _sa=shard_axis: oc.reduce_scatter_matmul(
                        aa, bb, shard_axis=_sa, axis_name="data",
                        mesh=mesh, backend="pallas",
                    )
                )(a, b)
    return split_schedule_segments(log)


def audit_ring_kernels(ring: int = 4) -> list[Finding]:
    """Record + check every shipped ring kernel's schedule. The seam
    itself is asserted: a refactor that silently drops the recording
    hooks turns the race audit into a vacuous pass, so zero captured
    segments (or a missing kernel) is an error, not a clean bill."""
    segments = record_ring_schedules(ring=ring)
    findings: list[Finding] = []
    names = {seg[0].get("name") for seg in segments}
    for expected in ("ag_matmul", "rs_matmul"):
        if expected not in names:
            findings.append(Finding(
                "kernel.race.no_schedule", "error",
                f"ops/overlap_collectives.py::{expected}",
                "recording seam captured no schedule for this kernel — "
                "the _sched() hooks were dropped or the kernel no longer "
                "launches under the audit harness",
            ))
    for seg in segments:
        findings.extend(check_ring_schedule(seg))
    return findings


# ---------------------------------------------------------------------------
# 2. kernel lint family
# ---------------------------------------------------------------------------


def lint_grid_plan(
    plan: dict[str, Any], *, artifact: str = "ops/decode_fused.py::fused_layers",
) -> list[Finding]:
    """Index-map / SMEM lints over a symbolic grid plan (the structure
    :func:`dtc_tpu.ops.vmem.fused_layers_grid_plan` returns — also the
    structure the kernel's actual BlockSpecs are built from, so linting
    the plan IS linting the launch).

    - **purity**: an index map must be a pure function of the grid
      coords — same coords, same block index, with rank matching the
      block shape (Mosaic silently mis-tiles otherwise).
    - **b-invariance**: layer-streamed blocks (the 16 per-layer weights,
      shared LoRA factors) must NOT vary with the row coordinate —
      "weights re-fetch per layer, not per row" is the pipelining
      contract that keeps per-row grid steps weight-traffic-free — and
      MUST advance with the layer coordinate (else every layer reads
      layer 0's stacked block).
    - **row blocks** (x, cache rows, outputs) must advance with the row
      coordinate (else rows alias one block) — the b-variance dual.
    - **SMEM discipline**: scalar operands (the frontier) ride SMEM as
      whole-array scalar-prefetch specs; VMEM operands must carry a
      block shape + index map.
    """
    findings: list[Finding] = []

    def err(rule: str, msg: str) -> None:
        findings.append(Finding(rule, "error", artifact, msg))

    grid = plan.get("grid", ())
    if len(grid) != 2:
        err("kernel.lint.grid", f"expected a (layers, rows) grid, got {grid}")
        return findings
    n_l, n_b = int(grid[0]), int(grid[1])
    probe_l = 1 if n_l > 1 else 0
    probe_b = 1 if n_b > 1 else 0

    for io, specs in (("in", plan["in_specs"]), ("out", plan["out_specs"])):
        for entry in specs:
            name, shape, imap, space, _nbytes = entry
            label = f"{io}:{name}"
            if space == "smem":
                if shape is not None or imap is not None:
                    err(
                        "kernel.lint.smem",
                        f"{label}: SMEM operands are whole-array scalar "
                        "prefetch — a block shape/index map has no meaning "
                        "there",
                    )
                continue
            if shape is None or imap is None:
                err(
                    "kernel.lint.smem",
                    f"{label}: VMEM operand without a block shape + index "
                    "map — only SMEM scalars may omit them",
                )
                continue
            base = imap(0, 0)
            if imap(0, 0) != base:
                err(
                    "kernel.lint.index_map",
                    f"{label}: index map is impure — two calls with the "
                    "same grid coords returned different block indices",
                )
                continue
            if len(base) != len(shape):
                err(
                    "kernel.lint.index_map",
                    f"{label}: index map rank {len(base)} != block rank "
                    f"{len(shape)} — Mosaic would mis-tile the operand",
                )
                continue
            layer_streamed = name in vmem.WEIGHT_BLOCK_NAMES or (
                name.endswith(("_a", "_b")) and len(shape) == 3
            )
            if layer_streamed:
                if probe_b and imap(0, 0) != imap(0, probe_b):
                    err(
                        "kernel.lint.index_map",
                        f"{label}: weight block varies with the ROW "
                        "coordinate — weights must re-fetch per layer, "
                        "not per row (b-invariance is the megakernel's "
                        "pipelining contract; a b-variant map re-streams "
                        f"{name} for every row in the batch)",
                    )
                if probe_l and imap(0, 0) == imap(probe_l, 0):
                    err(
                        "kernel.lint.index_map",
                        f"{label}: weight block does not advance with the "
                        "layer coordinate — every layer would read layer "
                        "0's stacked block",
                    )
            else:
                if probe_b and imap(0, 0) == imap(0, probe_b):
                    err(
                        "kernel.lint.index_map",
                        f"{label}: row block does not advance with the row "
                        "coordinate — all rows would alias one block",
                    )
    smem_in = [e for e in plan["in_specs"] if e[3] == "smem"]
    if not smem_in:
        err(
            "kernel.lint.smem",
            "no SMEM scalar operand: the frontier lengths must ride SMEM "
            "scalar prefetch, not a VMEM block",
        )
    return findings


def lint_fused_layers(cfg, *, t: int = 1, b: int = 2) -> list[Finding]:
    """Lint the megakernel's grid plan for a concrete config (b=2 so
    b-invariance is actually probed; LoRA sites included when the config
    carries an adapter)."""
    plan = vmem.fused_layers_grid_plan(
        cfg, t=t, b=b, lora_sites=vmem.lora_sites_for(cfg),
    )
    return lint_grid_plan(plan)


def _module_calls_pallas(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "pallas_call":
            return True
        if isinstance(node, ast.Name) and node.id == "pallas_call":
            return True
    return False


def _module_imports_vmem(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module == "dtc_tpu.ops" and any(
                a.name == "vmem" for a in node.names
            ):
                return True
            if node.module == "dtc_tpu.ops.vmem":
                return True
        if isinstance(node, ast.Import) and any(
            a.name == "dtc_tpu.ops.vmem" for a in node.names
        ):
            return True
    return False


def _gate_functions(tree: ast.AST) -> list[ast.FunctionDef]:
    return [
        node for node in ast.walk(tree)
        if isinstance(node, ast.FunctionDef)
        and (node.name.startswith("supports") or node.name == "_pallas_ok")
    ]


def _references_vmem(fn: ast.FunctionDef) -> bool:
    return any(
        isinstance(node, ast.Name) and node.id == "vmem"
        for node in ast.walk(fn)
    )


def lint_gate_coverage(
    ops_dir: str = _OPS_DIR,
    waivers: dict[str, str] | None = None,
) -> list[Finding]:
    """Every ops/ module that launches a ``pallas_call`` must gate it:
    define a ``supports*`` / ``_pallas_ok`` predicate that consults the
    shared planner (:mod:`dtc_tpu.ops.vmem`). This is what keeps the
    gate and the kernel from drifting apart — the PR 11 bug class where
    the estimate said "fits" and Mosaic said otherwise. Waived modules
    surface as info findings so the waiver stays reviewed."""
    if waivers is None:
        waivers = PALLAS_GATE_WAIVERS
    findings: list[Finding] = []
    for fname in sorted(os.listdir(ops_dir)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(ops_dir, fname)
        artifact = f"ops/{fname}"
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        if not _module_calls_pallas(tree):
            continue
        if fname in waivers:
            findings.append(Finding(
                "kernel.lint.gate_coverage", "info", artifact,
                f"pallas_call without a planner-consulting gate — waived: "
                f"{waivers[fname]}",
            ))
            continue
        gates = _gate_functions(tree)
        if not gates:
            findings.append(Finding(
                "kernel.lint.gate_coverage", "error", artifact,
                "module launches a pallas_call but defines no supports*/"
                "_pallas_ok gate — the kernel is reachable with no VMEM "
                "fit check at all",
            ))
            continue
        if not _module_imports_vmem(tree) or not any(
            _references_vmem(g) for g in gates
        ):
            findings.append(Finding(
                "kernel.lint.gate_coverage", "error", artifact,
                "gate does not consult the shared planner "
                "(dtc_tpu.ops.vmem) — a hand-rolled estimate here is the "
                "drift the planner exists to end",
            ))
    return findings


# ---------------------------------------------------------------------------
# 3. static plans across the model ladder + the drift-gated baselines
# ---------------------------------------------------------------------------


def rung_config(name: str):
    """The ModelConfig of one ladder rung: its file under configs/ with
    the overrides :data:`LADDER_RUNGS` states."""
    from dtc_tpu.config.loader import load_yaml_dataclass
    from dtc_tpu.config.schema import ModelConfig

    filename, overrides = LADDER_RUNGS[name]
    cfg = load_yaml_dataclass(os.path.join(_CONFIG_DIR, filename), ModelConfig)
    return dataclasses.replace(cfg, **overrides)


#: The deployment shape all rung plans are priced at: the 8-device ring
#: of the audited train entries / the b8 reference, seq at the config
#: max, bf16 wire dtype (the bf16_mixed stack — fp32-sharded rings
#: simply double the itemsize term).
_PLAN_RING = 8
_PLAN_BATCH = 8


def _overlap_sites(cfg) -> dict[str, dict[str, Any]]:
    """Static overlap-ring plans for every OverlapDense site of one
    transformer layer, at the deployment shape: per-site fit answers
    "which matmuls ride the fused kernels at this rung" without a
    chip."""
    from dtc_tpu.config.schema import DTYPE_BYTES

    dm, ff = cfg.d_model, cfg.d_ff
    hd = cfg.n_heads * cfg.head_dim
    itemsize = DTYPE_BYTES.get(cfg.compute_dtype, 4)
    m = _PLAN_BATCH * cfg.max_seq_len // _PLAN_RING
    # (k, n, shard_axis) mirrors models/gpt.py's _dense sites: shard
    # axis 0 = contraction (d_model in), 1 = output (d_model out).
    sites = {
        "qkv_proj": (dm, hd, 0),
        "out_proj": (hd, dm, 1),
        "fc1": (dm, ff, 0),
        "fc2": (ff, dm, 1),
    }
    return {
        site: vmem.overlap_plan(m, k, n, _PLAN_RING, sa, itemsize)
        for site, (k, n, sa) in sites.items()
    }


def rung_fingerprint(name: str) -> dict[str, Any]:
    """The drift-gated static plan of one ladder rung: config dims,
    every kernel's VMEM plan (megakernel t=1 + the widest spec window,
    both per-layer decode kernels, every overlap site), and the
    analytic HBM plan at the deployment shape."""
    from dtc_tpu.utils.metrics import train_memory_bytes

    cfg = rung_config(name)
    dims = {
        "vocab_size": cfg.vocab_size,
        "d_model": cfg.d_model,
        "n_layers": cfg.n_layers,
        "n_heads": cfg.n_heads,
        "head_dim": cfg.head_dim,
        "d_ff": cfg.d_ff,
        "max_seq_len": cfg.max_seq_len,
        "param_dtype": cfg.param_dtype,
        "compute_dtype": cfg.compute_dtype,
        "kv_store_dtype": cfg.kv_store_dtype,
    }
    kernels = {
        "fused_layers_t1": vmem.fused_layers_plan(cfg, t=1, b=_PLAN_BATCH),
        f"fused_layers_spec_k{vmem.SPEC_MAX_K}": vmem.fused_layers_plan(
            cfg, t=vmem.SPEC_MAX_K, b=_PLAN_BATCH
        ),
        "decode_single": vmem.decode_single_plan(cfg),
        "decode_blocked": vmem.decode_blocked_plan(cfg),
    }
    flash = vmem.flash_plan(
        cfg.max_seq_len, cfg.head_dim, cfg.n_heads,
        vmem._dtype_bytes(cfg.compute_dtype),
    )
    if flash is not None:  # the packed layout applies: the kernel chooses
        kernels["flash_packed"] = flash
    for site, plan in _overlap_sites(cfg).items():
        kernels[f"overlap_{site}"] = plan
    hbm = train_memory_bytes(
        cfg, _PLAN_BATCH, cfg.max_seq_len, {"data": _PLAN_RING}, "fsdp",
        precision="bf16_mixed",
    )
    return {
        "config": dims,
        "kernels": kernels,
        "hbm_fsdp8_b8_bf16_mixed": {k: int(v) for k, v in hbm.items()},
    }


def kernel_report() -> dict[str, Any]:
    import jax

    return {
        "jax": jax.__version__,
        "platform": jax.devices()[0].platform,
        "rungs": {name: rung_fingerprint(name) for name in LADDER_RUNGS},
    }


def write_kernel_baselines(
    report: dict[str, Any] | None = None, directory: str = BASELINE_DIR,
) -> list[str]:
    """Bless the per-rung kernel plans as ``kernels_<rung>.json``
    baselines (same file format + drift semantics as the graph
    fingerprints)."""
    if report is None:
        report = kernel_report()
    os.makedirs(directory, exist_ok=True)
    written = []
    for name, fp in report["rungs"].items():
        path = _baseline_path(f"kernels_{name}", directory)
        with open(path, "w") as f:
            json.dump(
                {"jax": report["jax"], "platform": report["platform"],
                 "fingerprint": fp},
                f, indent=1, sort_keys=True,
            )
            f.write("\n")
        written.append(path)
    return written


def check_kernel_baselines(
    report: dict[str, Any] | None = None,
    directory: str = BASELINE_DIR,
    *,
    require: bool = True,
) -> list[Finding]:
    """Drift gate over the committed per-rung kernel plans. Unlike the
    graph baselines these are PURE ARITHMETIC over config dims — no XLA
    in the loop — so drift is an error regardless of jax version: if
    the bytes moved, someone changed a kernel layout or the planner, and
    the baseline must be consciously re-blessed."""
    if report is None:
        report = kernel_report()
    out: list[Finding] = []
    for name, fp in report["rungs"].items():
        label = f"kernels_{name}"
        path = _baseline_path(label, directory)
        if not os.path.exists(path):
            out.append(Finding(
                "baseline.missing", "error" if require else "warn", label,
                f"no committed kernel-plan baseline at {path} — bless with "
                "scripts/audit_graph.py --kernels --write-baseline",
            ))
            continue
        with open(path) as f:
            base = json.load(f)
        lines = _diff(base["fingerprint"], fp)
        if lines:
            out.append(Finding(
                "baseline.drift", "error", label,
                f"static kernel plan drifted from committed baseline "
                f"({len(lines)} field(s)):\n    " + "\n    ".join(lines)
                + "\n  re-bless with scripts/audit_graph.py --kernels "
                "--write-baseline if intended",
            ))
    return out


def run_kernel_audit(
    *,
    ring: int = 4,
    write_baseline: bool = False,
    require_baselines: bool = False,
    race: bool = True,
) -> tuple[list[Finding], dict[str, Any]]:
    """The full kernel audit: static plans (+ baseline gate or bless),
    the lint family per rung, and the happens-before race detector over
    every shipped ring kernel. Returns (findings, kernel report)."""
    findings: list[Finding] = []
    report = kernel_report()
    if write_baseline:
        report["written"] = write_kernel_baselines(report)
    else:
        findings.extend(
            check_kernel_baselines(report, require=require_baselines)
        )
    for name in LADDER_RUNGS:
        cfg = rung_config(name)
        for f in lint_fused_layers(cfg) + lint_fused_layers(
            cfg, t=vmem.SPEC_MAX_K
        ):
            findings.append(Finding(
                f.rule, f.severity, f"{f.artifact}@{name}", f.message
            ))
    findings.extend(lint_gate_coverage())
    if race:
        findings.extend(audit_ring_kernels(ring=ring))
    return findings, report
