"""Operations and bytes a step of the Ouro looped model needs, from shapes
alone (``model``: the configuration file's ``model`` group).

A step runs the ``n_layers`` layers ``stack_passes`` times and reads out
after every pass, so the stack AND the head count ``stack_passes`` times
while the parameters are there once. ``train_step_flops`` is a copy of the
program's ``utils/metrics.pattern_step_flops`` for this family (kept here so
that a later PR cannot move the yardstick; a test holds the two equal): 6 x
matmul parameters x tokens for every layer application and head pass, and
causal attention as ``flops.train_step_flops`` counts it (12 B T^2 H hd / 2
a layer application). The embedding gather, the norms, the exit gate's d
products a token and pass, the exit distribution and the loss over it are
not counted. Recomputation is not counted: neither a layer's second forward
under its remat nor the head matmul the per-token CE makes again in its
backward.

Per kernel, the least operations and bytes, for the roofline share:

- **full attention**: 7 causal matmuls of 2 B H T^2 hd / 2 a layer
  application (2 forward, 5 backward with the scores recomputed from the
  saved statistics); bytes: forward reads q, k, v and writes out and one
  float32 statistic a row and head, backward reads q, k, v, out, dout and
  the statistic and writes dq, dk, dv. K and V have the query's head count
  (no KV groups). Under the cell's per-layer remat the forward kernel runs
  a second time in every layer's backward; that is recomputation and is not
  in the count, so the share cannot pass 7/9 of what the kernels reach alone.
"""

from __future__ import annotations

import flops_lfm2_moe as one_pass  # its attention count: any `attn` layers of a pattern, KV heads their own
from reference import padded_vocab


def layer_applications(model: dict) -> int:
    return int(model["stack_passes"]) * int(model["n_layers"])


def matmul_params(model: dict) -> dict[str, float]:
    """Matmul parameters a token passes in one layer's mixer, its SwiGLU,
    and in one head pass."""
    d = model["d_model"]
    width = model["n_heads"] * model["attn_head_dim"]
    return {"attn": 4 * d * width, "swiglu": 3 * d * model["d_ff"], "head": d * padded_vocab(model)}


def train_step_flops(model: dict, rows: int, seq_len: int) -> float:
    tokens = rows * seq_len
    per = matmul_params(model)
    passes = model["stack_passes"]
    n_matmul = passes * (model["n_layers"] * (per["attn"] + per["swiglu"]) + per["head"])
    attn = (12.0 * layer_applications(model) * rows * seq_len**2
            * model["n_heads"] * model["attn_head_dim"] / 2.0)
    return 6.0 * n_matmul * tokens + attn


def head_share(model: dict, rows: int, seq_len: int) -> float:
    """The head passes' share of the step's operations."""
    head = 6.0 * model["stack_passes"] * matmul_params(model)["head"] * rows * seq_len
    return head / train_step_flops(model, rows, seq_len)


def full_attn_step_flops(model: dict, rows: int, seq_len: int) -> float:
    """``flops_lfm2_moe``'s count of one pass over the attention layers (7
    causal matmuls a layer), once a pass."""
    return model["stack_passes"] * one_pass.full_attn_step_flops(model, rows, seq_len)


def full_attn_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2) -> float:
    return model["stack_passes"] * one_pass.full_attn_step_bytes(model, rows, seq_len, dtype_bytes)
