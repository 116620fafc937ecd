"""Device-mesh construction from TPU slice topology.

This is the framework's "communication backend" in the sense of SURVEY.md
§2.3: on TPU there is no NCCL layer to manage — the backend IS the mesh.
Which collectives ride ICI vs DCN is decided entirely by how the mesh is
laid out over the physical topology, so this module is where that planning
lives:

- ``("pipe", "data", "model")`` named axes, with ``model`` (tensor
  parallelism, the most latency-sensitive collectives: per-layer
  all-reduce/all-gather) placed innermost so `mesh_utils.create_device_mesh`
  maps it onto nearest-neighbour ICI links.
- Multi-slice pods use `create_hybrid_device_mesh`, where the ``dcn_*``
  factors of :class:`MeshConfig` say which axes span the (slow) DCN between
  slices — conventionally ``data`` (gradient all-reduce once per step
  amortises over the step) and never ``model``.

The reference builds a 1-D mesh with a single axis named "data" and reuses
it to mean DP or TP depending on a string (`/root/reference/train/train.py:29`);
here every strategy — including combined 3D — is just a shape on this one
3-axis mesh.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from dtc_tpu.config.schema import MeshConfig

# Axis order: pipe outermost (stage handoffs are once per microbatch-clock),
# data middle (one gradient all-reduce per step), model innermost (per-layer
# collectives want the fastest links).
AXIS_NAMES = ("pipe", "data", "model")
PIPE, DATA, MODEL = AXIS_NAMES


def resolve_mesh_shape(
    parallel: str,
    num_devices: int,
    mesh: MeshConfig,
    n_layers: int | None = None,
    pipe_dcn: int = 1,
) -> tuple[int, int, int]:
    """Resolve ``(pipe, data, model)`` ICI axis sizes.

    Zero entries in ``mesh`` are auto-filled from the strategy: the strategy's
    own axis absorbs all devices not claimed by explicit entries. Validates
    that the product covers every device (a partially used slice wastes
    chips silently otherwise).

    ``n_layers`` makes pipeline resolution layer-aware: an auto-filled
    ``pipe`` axis is capped at the largest divisor of the device budget that
    also divides ``n_layers`` (leftover devices become data parallelism), and
    an explicit ``pipe`` that does not divide ``n_layers`` is a ValueError
    here instead of an error deep in the pipeline step. The reference
    instead silently truncates the model to ``n_layers // num_devices``
    stages' worth of layers (`/root/reference/train/train.py:118`).
    ``pipe_dcn`` is the DCN factor of the pipe axis: the stage count the
    pipeline actually sees is ``pipe * pipe_dcn``, so divisibility is
    checked against the total, not just the ICI part.
    """
    sizes = {PIPE: mesh.pipe, DATA: mesh.data, MODEL: mesh.model}
    primary = {
        "dp": DATA, "tp": MODEL, "pp": PIPE, "none": DATA, "3d": None,
        "fsdp": DATA,  # FSDP shards params over the same axis as the batch
    }[parallel]

    if parallel == "3d":
        # 3D requires explicit sizes; default unset axes to 1.
        sizes = {k: (v or 1) for k, v in sizes.items()}
    else:
        explicit = {k: v for k, v in sizes.items() if v > 0}
        known = math.prod(explicit.values()) if explicit else 1
        if primary in explicit:
            sizes = {k: explicit.get(k, 1) for k in sizes}
        else:
            if num_devices % known != 0:
                raise ValueError(
                    f"explicit mesh axes {explicit} do not divide device count {num_devices}"
                )
            sizes = {k: explicit.get(k, 1) for k in sizes}
            sizes[primary] = num_devices // known
            if primary == PIPE and n_layers is not None:
                # Largest stage count that divides both the device budget
                # and the layer count; surplus devices do data parallelism.
                pipe = sizes[PIPE]
                while n_layers % (pipe * pipe_dcn) != 0 or sizes[PIPE] % pipe != 0:
                    pipe -= 1
                    if pipe == 0:
                        raise ValueError(
                            f"no pipe size <= {sizes[PIPE]} satisfies "
                            f"n_layers={n_layers} % (pipe * dcn_pipe={pipe_dcn}) == 0"
                        )
                if pipe != sizes[PIPE]:
                    # Unconditional print (no jax.process_index(): this helper
                    # must stay backend-free so it can run before
                    # jax.distributed.initialize()): a user-pinned data degree
                    # changes here, which would otherwise be silent.
                    print(
                        f"mesh: auto-pp capped pipe {sizes[PIPE]} -> {pipe} "
                        f"(n_layers={n_layers}); data "
                        f"{sizes[DATA]} -> {sizes[DATA] * (sizes[PIPE] // pipe)}"
                    )
                sizes[DATA] = sizes[DATA] * (sizes[PIPE] // pipe)
                sizes[PIPE] = pipe

    total_pipe = sizes[PIPE] * pipe_dcn
    if n_layers is not None and total_pipe > 1 and n_layers % total_pipe != 0:
        raise ValueError(
            f"pipe={sizes[PIPE]} x dcn_pipe={pipe_dcn} = {total_pipe} stages do "
            f"not divide n_layers={n_layers}; set mesh.pipe/dcn_pipe so their "
            "product divides the layer count"
        )

    shape = (sizes[PIPE], sizes[DATA], sizes[MODEL])
    if math.prod(shape) != num_devices:
        raise ValueError(
            f"mesh shape pipe×data×model = {shape} (= {math.prod(shape)}) "
            f"must equal the device count {num_devices}"
        )
    return shape


def build_mesh(
    shape: tuple[int, int, int],
    *,
    devices: list | None = None,
    dcn_shape: tuple[int, int, int] | None = None,
) -> Mesh:
    """Build the 3-axis device mesh.

    ``shape`` is the ICI (intra-slice) shape. ``dcn_shape``, when any entry
    is > 1, is the DCN (inter-slice) factor per axis; the total axis size is
    the product, and `create_hybrid_device_mesh` keeps DCN hops on the
    outermost dimension of each axis so ICI collectives never cross slices.
    """
    devices = list(devices if devices is not None else jax.devices())
    if dcn_shape is not None and any(d > 1 for d in dcn_shape):
        try:
            device_array = mesh_utils.create_hybrid_device_mesh(
                shape, dcn_shape, devices=devices, allow_split_physical_axes=True
            )
        except ValueError:
            if getattr(devices[0], "platform", None) == "tpu":
                # On real TPU a hybrid-mesh failure is a genuine topology
                # error; a topology-unaware reshape here could silently place
                # DCN axes across slice boundaries (severe bandwidth
                # misplacement). Only non-TPU (virtual CPU) falls through.
                raise
            # Topology-unaware fallback (virtual CPU devices have no
            # slice_index). Keep the hybrid contract: per axis, the DCN
            # factor is the OUTER dimension, so ICI-contiguous device
            # groups stay contiguous within each axis.
            d0, d1, d2 = dcn_shape
            i0, i1, i2 = shape
            device_array = (
                np.asarray(devices)
                .reshape(d0, d1, d2, i0, i1, i2)
                .transpose(0, 3, 1, 4, 2, 5)
                .reshape(d0 * i0, d1 * i1, d2 * i2)
            )
    else:
        try:
            device_array = mesh_utils.create_device_mesh(
                shape, devices=devices, allow_split_physical_axes=True
            )
        except (ValueError, NotImplementedError):
            if getattr(devices[0], "platform", None) == "tpu":
                # Same rule as the hybrid branch: on a real TPU a mesh
                # the topology cannot carry is an error, never a plain
                # reshape that ignores which chips are neighbours.
                raise
            # Topology-unaware fallback (virtual CPU devices).
            device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, axis_names=AXIS_NAMES)


def mesh_from_config(
    parallel: str,
    mesh_cfg: MeshConfig,
    devices: list | None = None,
    n_layers: int | None = None,
) -> Mesh:
    """One-call mesh construction used by the trainer and tests."""
    devices = list(devices if devices is not None else jax.devices())
    dcn = (mesh_cfg.dcn_pipe, mesh_cfg.dcn_data, mesh_cfg.dcn_model)
    n_ici = len(devices) // math.prod(dcn)
    shape = resolve_mesh_shape(
        parallel, n_ici, mesh_cfg, n_layers=n_layers, pipe_dcn=mesh_cfg.dcn_pipe
    )
    return build_mesh(shape, devices=devices, dcn_shape=dcn)
