"""Expert layer's router, program counter: of all the choices the routers
made over the window's steps (tokens x experts a token x expert layers), the
share that plain top-k of the scores would not have made — the work of the
selection bias (``moe_bias_swapped`` of the program's ``moe_counters``
events), in percent. 0 says the bias is not in the choice; it should stand
still over a run whose routers do."""


def read(run: dict):
    swapped = [e["moe_bias_swapped"] for e in run["events"]
               if e.get("etype") == "moe_counters" and "moe_bias_swapped" in e]
    if not swapped:
        return None
    choices = run["tokens_per_step"] * run["model"]["moe_top_k"] * sum(map(len, swapped))
    return 100.0 * sum(map(sum, swapped)) / choices
