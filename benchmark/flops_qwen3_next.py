"""Operations and bytes a step of the Qwen3-Next block family needs, from
shapes alone (``model``: the configuration file's ``model`` group).

``train_step_flops`` is a copy of the program's ``utils/metrics.
pattern_step_flops`` (kept here so that a later PR cannot move the
yardstick; a test holds the two equal): 6 x matmul parameters x tokens with
the head counted and a routed expert at the share of tokens it expects
(``top_k / experts``, for each of the held ones), causal attention as
``flops.train_step_flops`` counts it (12 B T^2 H hd / 2 a layer), and the
Gated DeltaNet recurrence's least work. Recomputation is not counted.

That is the EXPECTED work, from shapes alone. The routers move while a run
trains, so the held assignments a step really computes differ from it (one
layer 22440, another 3, where even routing gives 10240): the metrics hand
in what the run counted (``counted_assignments``: the program's
``moe_counters`` events), and the expected count stands only where a run
has no such event.

Per kernel, the least operations and bytes, for the roofline shares:

- **full attention**: as ``flops.flash_step_flops`` — 7 causal matmuls of
  2 B H T^2 hd / 2 (2 forward, 5 backward with the scores recomputed from
  the saved statistics); bytes: forward reads q, k, v and writes out and one
  float32 statistic a row and head, backward reads q, k, v, out, dout and
  the statistic and writes dq, dk, dv; K and V at their OWN head count (the
  kernels read each KV head once for its group).
- **Gated DeltaNet scan**: per token and value head the state is decayed
  (dk dv multiplies), read twice (S^T k, S^T q: 2 dk dv each) and given a
  rank-one update (2 dk dv): 7 dk dv forward, twice that backward. The
  chunked form does more (the in-chunk triangle); the least algorithm is the
  recurrence. Bytes: forward reads q, k (key heads), v, the two float32
  gates and writes o; backward reads them and do and writes five gradients.
- **expert matmuls**: three matmuls of d x f per held assignment (counted,
  or the expected tokens x top_k x held / experts), forward and both
  backward products;
  bytes: the held experts' weights read in the compute type forward and
  backward and their gradient written, the assignments' rows read and
  written.
"""

from __future__ import annotations

from reference import padded_vocab


def _kinds(model: dict) -> list[tuple[str, str]]:
    return [tuple(entry.split("+")) for entry in model["layer_pattern"]]


def _count(model: dict, kind: str) -> int:
    periods = model["n_layers"] // len(model["layer_pattern"])
    return periods * sum(kind in pair for pair in _kinds(model))


def _held(model: dict) -> int:
    return int(model.get("moe_experts_held") or model["moe_experts"])


def matmul_params(model: dict) -> dict[str, float]:
    """Matmul parameters a token passes in one layer of each kind, and in
    the head."""
    d = model["d_model"]
    nk = model["gdn_key_heads"] * model["gdn_key_dim"]
    nv = model["gdn_value_heads"] * model["gdn_value_dim"]
    hd = model["attn_head_dim"]
    q_out = model["n_heads"] * hd
    kv_out = (model.get("n_kv_heads") or model["n_heads"]) * hd
    routed = _held(model) * model["moe_top_k"] / model["moe_experts"]
    return {
        "gdn": d * (2 * nk + 2 * nv) + d * 2 * model["gdn_value_heads"] + nv * d,
        "gated_attn": d * 2 * q_out + 2 * d * kv_out + q_out * d,
        "moe_shared": d * model["moe_experts"] + 3 * d * model["moe_shared_d_ff"] + d
        + routed * 3 * d * model["moe_d_ff"],
        "head": d * padded_vocab(model),
    }


def gdn_step_flops(model: dict, rows: int, seq_len: int) -> float:
    """Least work of ALL Gated DeltaNet layers' recurrences in one step."""
    per_token = 7.0 * model["gdn_key_dim"] * model["gdn_value_dim"] * model["gdn_value_heads"]
    return 3.0 * per_token * rows * seq_len * _count(model, "gdn")


def train_step_flops(model: dict, rows: int, seq_len: int,
                     assignments: float | None = None) -> float:
    """``assignments``: the held assignments the step computed, all layers
    (None: the expected ones)."""
    tokens = rows * seq_len
    per = matmul_params(model)
    periods = model["n_layers"] // len(model["layer_pattern"])
    n_matmul = periods * sum(per[m] + per[f] for m, f in _kinds(model)) + per["head"]
    attn = (12.0 * _count(model, "gated_attn") * rows * seq_len**2
            * model["n_heads"] * model["attn_head_dim"] / 2.0)
    flops = 6.0 * n_matmul * tokens + attn + gdn_step_flops(model, rows, seq_len)
    if assignments is not None:
        flops += 6.0 * 3 * model["d_model"] * model["moe_d_ff"] * (
            assignments - expected_assignments(model, rows, seq_len) * _count(model, "moe_shared"))
    return flops


def gdn_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2) -> float:
    tokens = rows * seq_len
    qk = 2 * tokens * model["gdn_key_heads"] * model["gdn_key_dim"] * dtype_bytes
    v = tokens * model["gdn_value_heads"] * model["gdn_value_dim"] * dtype_bytes
    gates = 2 * tokens * model["gdn_value_heads"] * 4
    forward = qk + v + gates + v                      # reads q, k, v, g, beta; writes o
    backward = (qk + v + gates + v) + (qk + v + gates)  # reads them and do; writes 5 gradients
    return float(_count(model, "gdn") * (forward + backward))


def full_attn_step_flops(model: dict, rows: int, seq_len: int) -> float:
    per_matmul = 2.0 * rows * seq_len**2 * model["n_heads"] * model["attn_head_dim"] / 2.0
    return 7.0 * per_matmul * _count(model, "gated_attn")


def full_attn_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2) -> float:
    hd = model["attn_head_dim"]
    q = rows * seq_len * model["n_heads"] * hd * dtype_bytes
    kv = rows * seq_len * (model.get("n_kv_heads") or model["n_heads"]) * hd * dtype_bytes
    stat = rows * seq_len * model["n_heads"] * 4
    forward = (q + 2 * kv) + (q + stat)
    backward = (q + 2 * kv + 2 * q + stat) + (q + 2 * kv)
    return float(_count(model, "gated_attn") * (forward + backward))


def expected_assignments(model: dict, rows: int, seq_len: int) -> float:
    """Held assignments a layer and step under even routing."""
    return rows * seq_len * model["moe_top_k"] * _held(model) / model["moe_experts"]


def counted_assignments(run: dict, steps=None) -> float | None:
    """Held assignments a step, all layers and chips, as the program counted
    them: the mean over the run's ``moe_counters`` events (of the timed steps
    ``steps`` where given and counted; else of every one). None where the
    run has no such event."""
    events = [e for e in run.get("events", ()) if e.get("etype") == "moe_counters"]
    chosen = [e for e in events if steps is not None and e.get("step") in steps] or events
    if not chosen:
        return None
    return sum(sum(e["moe_assigned_held"]) for e in chosen) / len(chosen)


def _assignments(model: dict, rows: int, seq_len: int, assignments: float | None) -> float:
    if assignments is not None:
        return assignments
    return expected_assignments(model, rows, seq_len) * _count(model, "moe_shared")


def moe_experts_step_flops(model: dict, rows: int, seq_len: int,
                           assignments: float | None = None) -> float:
    per_assignment = 3 * 2.0 * model["d_model"] * model["moe_d_ff"]
    return 3.0 * per_assignment * _assignments(model, rows, seq_len, assignments)


def moe_experts_step_bytes(model: dict, rows: int, seq_len: int, dtype_bytes: int = 2,
                           assignments: float | None = None) -> float:
    d, f = model["d_model"], model["moe_d_ff"]
    weights = _count(model, "moe_shared") * _held(model) * 3 * d * f * dtype_bytes
    acts = _assignments(model, rows, seq_len, assignments) * (d + d) * dtype_bytes  # a row in, a row out
    forward = weights + acts
    backward = weights + weights + 2 * acts           # read again, gradient written; rows and their gradients
    return float(forward + backward)
