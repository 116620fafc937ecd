"""Operations and bytes the algorithm needs, from shapes alone.

``train_step_flops`` is a copy of the program's ``utils/metrics.
gpt_step_flops`` (kept here so that a later PR cannot move the yardstick;
a test holds the two equal): 6 x matmul parameters x tokens, the head's
matmul counted, embedding gathers not, plus the causal attention term
12 L B T^2 d / 2. Recomputation is not counted.
"""

from __future__ import annotations

from reference import padded_vocab


def matmul_params(model: dict) -> int:
    d, f, L, v = model["d_model"], model["d_ff"], model["n_layers"], padded_vocab(model)
    per_block = 4 * (d * d + d) + (d * f + f) + (f * d + d) + 4 * d
    return L * per_block + 2 * d + (d * v + v)


def train_step_flops(model: dict, rows: int, seq_len: int) -> float:
    dense = 6.0 * matmul_params(model) * rows * seq_len
    attn = 12.0 * model["n_layers"] * rows * seq_len**2 * model["d_model"] / 2.0
    return dense + attn


def flash_step_flops(model: dict, rows: int, seq_len: int) -> float:
    """Causal attention proper, forward and backward, of one step: the
    forward is QK^T and PV over the lower triangle (2 matmuls), the backward
    the same two recomputed or reused plus dV, dP, dQ, dK — 4 more in the
    least algorithm that keeps no T x T matrix (the scores are recomputed
    from the saved statistics: 5 matmuls in all; the program's fused
    backward does 5, its split one 7). Each is 2 B H T^2 hd / 2 under the
    causal mask. Least work: 2 + 5 = 7 matmuls."""
    per_matmul = 2.0 * rows * seq_len**2 * model["d_model"] / 2.0
    return 7.0 * per_matmul * model["n_layers"]


def flash_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the same calls: forward reads q, k, v and writes
    out (+ one fp32 statistic per row and head); backward reads q, k, v,
    out, dout and the statistic and writes dq, dk, dv."""
    act = rows * seq_len * model["d_model"] * dtype_bytes
    stat = rows * seq_len * model["n_heads"] * 4
    return model["n_layers"] * ((4 * act + stat) + (8 * act + stat))
