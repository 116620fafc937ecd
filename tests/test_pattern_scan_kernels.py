"""What only the fused Gated DeltaNet kernels have (``ops/gated_delta.py``:
chunk-local part and carry in one Mosaic kernel a pass, interpreted here): the
state carried in scratch across a row's chunks and reset between rows and head
groups, the primal and the residual-writing variant, the backward's walk from
a row's last chunk to its first. Values and gradients against the recurrence
and the ``jax.numpy`` form are in ``tests/test_pattern_ops.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.ops.gated_delta import gated_delta_chunked
from tests.pattern_helpers import LOOSE, TIGHT, close, out_and_grads, scan_inputs


@pytest.mark.parametrize("row", [0, 1])
def test_gdn_fused_state_is_reset_per_row_and_head_group(row):
    """Two different rows of three chunks and sixteen value heads — two
    groups of eight a row, so the grid passes from one (row, group) to the
    next four times with the scratch still holding the last one's state (or
    its cotangent): each row of the batch, values and all five gradients,
    equals that row run alone."""
    args, co = scan_inputs(2, 192, 8, 16, 128, 128, seed=7)
    fn = lambda *a: gated_delta_chunked(*a, chunk=64, dtype=jnp.float32)  # noqa: E731
    assert "pallas_call" in str(jax.make_jaxpr(fn)(*args))
    alone = lambda a: a[row:row + 1]  # noqa: E731
    got = out_and_grads(fn, args, co)
    want = out_and_grads(fn, tuple(map(alone, args)), alone(co))
    for a, b_ in zip(got, want):
        close(alone(a), b_, 1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gdn_fused_primal_and_residual_variants_agree(dtype):
    """The primal kernel (what a layer's first forward runs under
    ``jax.checkpoint``) and the forward rule's, which also writes each
    chunk's incoming state: the same ``out`` bit for bit, the first state
    zero, the others the oracle scan's."""
    from dtc_tpu.ops import gated_delta as gd

    (q, k, v, g, beta), _ = scan_inputs(1, 192, 1, 2, 128, 128, seed=3)
    dt = jnp.dtype(dtype)
    gamma = jnp.cumsum(gd._chunk_major(g, 64), axis=-1)
    beta = gd._chunk_major(beta, 64)
    out, (*_, states) = gd._chunks_fused_fwd(q, k, v, gamma, beta, dt)
    np.testing.assert_array_equal(out, gd._chunks_fused(q, k, v, gamma, beta, dt))
    assert states.shape == (3, 1, 2, 128, 128) and not np.asarray(states[0]).any()
    operands = gd._chunk_local_xla(q, k, v, gamma, beta, dt)
    want = gd._scan_fwd(*operands, jnp.exp(gamma[..., -1]), dt)[1][-1]
    close(states, want, TIGHT if dtype == "float32" else LOOSE)


def test_gdn_fused_under_checkpoint_keeps_no_state_in_the_first_forward():
    """Under a layer's ``jax.checkpoint`` the three kernels each appear once:
    the first forward is the primal (no saved states), the recomputed one
    writes them, the backward reads them — and the gradients are those
    without the checkpoint."""
    import re

    args, co = scan_inputs(1, 128, 1, 2, 128, 128, seed=4)
    loss = lambda *a: jnp.sum(gated_delta_chunked(*a, chunk=64, dtype=jnp.float32) * co)  # noqa: E731
    grad = jax.grad(jax.checkpoint(loss), argnums=range(5))
    names = re.findall(r"name=(gdn_chunks_\w+)", str(jax.make_jaxpr(grad)(*args)))
    assert names == ["gdn_chunks_fwd", "gdn_chunks_fwd_res", "gdn_chunks_bwd"]
    assert re.findall(r"name=(gdn_chunks_\w+)", str(jax.make_jaxpr(loss)(*args))) == ["gdn_chunks_fwd"]
    for a, b_ in zip(grad(*args), jax.grad(loss, argnums=range(5))(*args)):
        np.testing.assert_array_equal(a, b_)


@pytest.mark.parametrize("dtype,tol", [("float32", TIGHT), ("bfloat16", LOOSE)])
def test_gdn_fused_backward_carries_the_cotangent_to_the_first_chunk(monkeypatch, dtype, tol):
    """A cotangent on the last of four chunks alone: whatever gradient the
    first chunk's k, v, decay and beta get came through the state's
    cotangent, carried in scratch from the row's last chunk down — it is not
    zero, and it is the oracle's (XLA's scan walked in reverse)."""
    from dtc_tpu.ops import gated_delta as gd

    args, co = scan_inputs(1, 256, 1, 2, 128, 128, seed=6)
    args = (*args[:3], 0.01 * args[3], 0.1 * args[4])         # slow to forget and to overwrite: the first chunk still counts
    co = co.at[:, :192].set(0.0)

    def grads():
        return out_and_grads(lambda *a: gated_delta_chunked(*a, chunk=64, dtype=jnp.dtype(dtype)), args, co)

    got = grads()
    monkeypatch.setattr(gd, "supports_chunk_kernel", lambda *a: None)   # the jax.numpy form + XLA's scan
    want = grads()
    assert not np.asarray(got[1][:, :64]).any()               # a query reads the state, it writes nothing
    for a, b_ in zip(got[2:], want[2:]):
        assert float(jnp.max(jnp.abs(b_[:, :64]))) > 1e-3 * float(jnp.max(jnp.abs(b_)))
        close(a[:, :64], b_[:, :64], tol)
        close(a, b_, tol)
