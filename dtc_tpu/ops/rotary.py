"""Rotary positions in the packed layout — one Mosaic kernel, its own VJP.

``models/pattern.rotary`` rotates ``(B, T, H, D)`` in float32: on the chip
that is a 4-D view of the projection's output with the heads along the
sublanes, a float32 copy of it in HBM, and a relayout back to ``(B, T, H*D)``
for the packed flash family. Here q and k stay as the projection wrote them,
``(B, T, H*D)`` in the compute dtype, and one pass over row tiles does the
same arithmetic in VMEM::

    y = x * cos + swap_halves(x) * sin±        (float32; one rounding to dtype)

with ``swap_halves`` a roll of each head's 128 lanes by 64 and ``sin±`` the
``(T, 128)`` sine table with the sign of ``concat([-x2, x1])`` folded in
(``-sin`` on a head's first half): the values of
``rotary(x.reshape(b, t, h, d), theta, 1.0).astype(dtype)`` to the last bit.

A rotation's transpose is the rotation back, so the backward is the same
kernel with the sine's sign turned; nothing is kept for it.

The gate (:func:`supports_packed_rotary`) takes a head of exactly one lane
tile rotated whole; every other shape runs ``models/pattern.rotary``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtc_tpu.ops import vmem


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports_packed_rotary(head_dim: int, rope_fraction: float, heads: int, t: int,
                           itemsize: int = 2) -> dict | None:
    """The planner's plan where the packed kernel holds the shape — the whole
    head rotated, a head one lane tile, a row tile inside the budget — else
    None, and ``models/pattern.rotary`` runs. Off the TPU the kernel runs
    interpreted."""
    if rope_fraction != 1.0:
        return None
    plan = vmem.rotary_plan(t, heads, head_dim, itemsize)
    return plan if plan is not None and plan["fits"] else None


def _tables(t: int, d: int, theta: float, sign: float) -> tuple[np.ndarray, np.ndarray]:
    """(cos, sin±) as ``(T, d)`` float32, the angles of ``models/pattern.rotary``
    (float64, then rounded); ``sign`` -1 turns the rotation back."""
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]          # (T, d/2)
    sin = np.sin(ang).astype(np.float32)
    return (np.concatenate([np.cos(ang), np.cos(ang)], -1).astype(np.float32),
            np.concatenate([-sign * sin, sign * sin], -1))


def _kernel(cos_ref, sin_ref, q_ref, k_ref, qo_ref, ko_ref, *, d):
    cos, sin = cos_ref[...], sin_ref[...]
    for ref, out in ((q_ref, qo_ref), (k_ref, ko_ref)):
        for lo in range(0, ref.shape[-1], d):
            x = ref[0, :, lo:lo + d].astype(jnp.float32)
            out[0, :, lo:lo + d] = (x * cos + pltpu.roll(x, d // 2, 1) * sin).astype(out.dtype)


def _launch(q, k, theta, d, sign):
    b, t, n = q.shape
    plan = vmem.rotary_plan(t, n // d, d, q.dtype.itemsize)
    rows = plan["rows"]
    # the rows innermost: a row tile's tables are fetched once for all of them
    rows_of = pl.BlockSpec((1, rows, n), lambda i, j: (j, i, 0))
    table = pl.BlockSpec((rows, d), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, d=d),
        name="rotary_packed",
        grid=(t // rows, b),
        in_specs=[table, table, rows_of, rows_of], out_specs=[rows_of, rows_of],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=plan["vmem_limit_bytes"],
        ),
        interpret=_interpret(),
    )(*_tables(t, d, theta, sign), q, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def packed_rotary(q: jax.Array, k: jax.Array, theta: float, head_dim: int):
    """Rotary positions over the whole head of q and k ``(B, T, H * head_dim)``,
    half-split pairing, positions ``0..T-1``: both rotated in one call, read
    and written in their own dtype, float32 inside. The caller asks
    :func:`supports_packed_rotary` first."""
    return tuple(_launch(q, k, theta, head_dim, 1.0))


def _fwd(q, k, theta, head_dim):
    return tuple(_launch(q, k, theta, head_dim, 1.0)), None


def _bwd(theta, head_dim, _, cotangents):
    return tuple(_launch(*cotangents, theta, head_dim, -1.0))


packed_rotary.defvjp(_fwd, _bwd)
