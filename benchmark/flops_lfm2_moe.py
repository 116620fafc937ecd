"""Operations and bytes a step of the LFM2-MoE block family needs, from
shapes alone (``model``: the configuration file's ``model`` group).

``train_step_flops`` is a copy of the program's ``utils/metrics.
pattern_step_flops`` for this family's kinds (kept here so that a later PR
cannot move the yardstick; a test holds the two equal): 6 x matmul
parameters x tokens with the tied head counted once (one matmul) and a
routed expert at the share of tokens it expects (``top_k / experts``, for
each of the held ones), causal attention as ``flops.train_step_flops``
counts it (12 B T^2 H hd / 2 a layer). The embedding gather, the norms and
the depthwise convolutions are not matmuls. Recomputation is not counted.

That is the EXPECTED work. The held assignments a step really computes
follow the routers (and their selection bias): the metrics hand in what the
run counted (``flops_qwen3_next.counted_assignments``: the program's
``moe_counters`` events), and the expected count stands only where a run has
no such event.

Per kernel, the least operations and bytes, for the roofline shares:

- **short convolution** (what lies between the two projections of every
  short-convolution layer: ``y = C * conv(B * u)``): per token and channel
  one product, ``width`` multiply-adds and one product forward, the same
  once more where the layer's forward is recomputed, and twice that for the
  backward; bytes, in the compute type: forward reads B, C, u and writes y
  (4 d a token), the recomputed forward the same, backward reads B, C, u and
  dy and writes three gradients (7 d). The same work whatever implements it.
- **full attention**: 7 causal matmuls of 2 B H T^2 hd / 2 (2 forward, 5
  backward with the scores recomputed from the saved statistics); bytes:
  forward reads q, k, v and writes out and one float32 statistic a row and
  head, backward reads q, k, v, out, dout and the statistic and writes dq,
  dk, dv; K and V at their OWN head count.
- **expert matmuls**: three matmuls of d x f per held assignment (counted,
  or the expected tokens x top_k x held / experts a layer), forward and both
  backward products; bytes: the held experts' weights read in the compute
  type forward and backward and their gradient written, the assignments'
  rows read and written.
"""

from __future__ import annotations

from flops_qwen3_next import counted_assignments  # noqa: F401  (the readers take it from here)
from reference import padded_vocab


def census(model: dict) -> list[tuple[str, str, int]]:
    """(mixer kind, ffn kind, how many such layers): each leading layer
    once, each position of the period once a period."""
    leading = list(model.get("leading_pattern", ()))
    periods = (model["n_layers"] - len(leading)) // len(model["layer_pattern"])
    entries = [(e, 1) for e in leading] + [(e, periods) for e in model["layer_pattern"]]
    return [(*e.split("+"), n) for e, n in entries]


def _count(model: dict, kind: str) -> int:
    return sum(n for m, f, n in census(model) if kind in (m, f))


def _held(model: dict) -> int:
    return int(model.get("moe_experts_held") or model["moe_experts"])


def matmul_params(model: dict) -> dict[str, float]:
    """Matmul parameters a token passes in one layer of each kind, and in
    the head."""
    d = model["d_model"]
    hd = model["attn_head_dim"]
    q_out = model["n_heads"] * hd
    kv_out = (model.get("n_kv_heads") or model["n_heads"]) * hd
    return {
        "attn": d * q_out + 2 * d * kv_out + q_out * d,
        "shortconv": d * 3 * d + d * d,
        "swiglu": 3 * d * model["d_ff"],
        "moe": d * model["moe_experts"]
        + _held(model) * model["moe_top_k"] / model["moe_experts"] * 3 * d * model["moe_d_ff"],
        "head": d * padded_vocab(model),
    }


def expected_assignments(model: dict, rows: int, seq_len: int) -> float:
    """Held assignments a layer and step under even routing."""
    return rows * seq_len * model["moe_top_k"] * _held(model) / model["moe_experts"]


def _assignments(model: dict, rows: int, seq_len: int, assignments: float | None) -> float:
    if assignments is not None:
        return assignments
    return expected_assignments(model, rows, seq_len) * _count(model, "moe")


def train_step_flops(model: dict, rows: int, seq_len: int,
                     assignments: float | None = None) -> float:
    """``assignments``: the held assignments the step computed, all layers
    (None: the expected ones)."""
    tokens = rows * seq_len
    per = matmul_params(model)
    n_matmul = sum(n * (per[m] + per[f]) for m, f, n in census(model)) + per["head"]
    attn = (12.0 * _count(model, "attn") * rows * seq_len**2
            * model["n_heads"] * model["attn_head_dim"] / 2.0)
    flops = 6.0 * n_matmul * tokens + attn
    if assignments is not None:
        flops += 6.0 * 3 * model["d_model"] * model["moe_d_ff"] * (
            assignments - expected_assignments(model, rows, seq_len) * _count(model, "moe"))
    return flops


def shortconv_step_flops(model: dict, rows: int, seq_len: int) -> float:
    forward = (2 + 2 * model["shortconv_width"]) * model["d_model"]     # a token
    return 4.0 * forward * rows * seq_len * _count(model, "shortconv")  # fwd, recomputed fwd, 2 x bwd


def shortconv_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2) -> float:
    a_token = (4 + 4 + 7) * model["d_model"] * dtype_bytes
    return float(a_token * rows * seq_len * _count(model, "shortconv"))


def full_attn_step_flops(model: dict, rows: int, seq_len: int) -> float:
    per_matmul = 2.0 * rows * seq_len**2 * model["n_heads"] * model["attn_head_dim"] / 2.0
    return 7.0 * per_matmul * _count(model, "attn")


def full_attn_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2) -> float:
    hd = model["attn_head_dim"]
    q = rows * seq_len * model["n_heads"] * hd * dtype_bytes
    kv = rows * seq_len * (model.get("n_kv_heads") or model["n_heads"]) * hd * dtype_bytes
    stat = rows * seq_len * model["n_heads"] * 4
    forward = (q + 2 * kv) + (q + stat)
    backward = (q + 2 * kv + 2 * q + stat) + (q + 2 * kv)
    return float(_count(model, "attn") * (forward + backward))


def moe_experts_step_flops(model: dict, rows: int, seq_len: int,
                           assignments: float | None = None) -> float:
    per_assignment = 3 * 2.0 * model["d_model"] * model["moe_d_ff"]
    return 3.0 * per_assignment * _assignments(model, rows, seq_len, assignments)


def moe_experts_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2,
                           assignments: float | None = None) -> float:
    d, f = model["d_model"], model["moe_d_ff"]
    weights = _count(model, "moe") * _held(model) * 3 * d * f * dtype_bytes
    acts = _assignments(model, rows, seq_len, assignments) * (d + d) * dtype_bytes  # a row in, a row out
    forward = weights + acts
    backward = weights + weights + 2 * acts           # read again, gradient written; rows and their gradients
    return float(forward + backward)
