"""Expert layer, program counter: the fullest held expert's load over the
mean load of the held experts, as a mean over the window's steps — the
program's ``moe_counters`` events (``moe_load_max`` over all layers and
``moe_load_mean``). 1 is even routing; the grouped matmuls' time follows the
sum, a deployment's exchange the maximum."""


def read(run: dict):
    ratios = [e["moe_load_max"] / e["moe_load_mean"] for e in run["events"]
              if e.get("etype") == "moe_counters" and e.get("moe_load_mean")]
    return sum(ratios) / len(ratios) if ratios else None
