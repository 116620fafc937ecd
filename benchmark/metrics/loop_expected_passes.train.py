"""Exit gate and loss, program counter: the pass a token is expected to
exit at, sum over passes t = 1..T of t x the tokens' mean exit probability
p_t (``exit_p`` of the program's ``pass_counters`` events), as a mean over
the window's first ``STEPS`` steps: the same steps of the same rows on
every tree, however far a window trains (a traced window reaches them: the
cell's ``trace_steps`` end there).

A health reading of the gate, and no more: in training every token runs
every pass whatever the gate says, so it moves nothing end to end in the
cell, and neither direction is better — ``BENCHMARK.json`` has to name one
and an end-to-end metric all the same. A gate that broke reads 1 (every
token leaves at once) or T (none before the last pass); 1.875 where every
score is 0. On seeded weights the cell reads 2.1–2.4: the scores scatter
widely about 0, those of a row's tokens and of its passes together, so a
single step reads 1.6–2.9 by the two rows it drew. A program that emits no
such event reads nothing."""

STEPS = 16


def read(run: dict):
    steps = [e["exit_p"] for e in run["events"] if e.get("etype") == "pass_counters"][:STEPS]
    if not steps:
        return None
    return sum(sum(t * p for t, p in enumerate(ps, 1)) for ps in steps) / len(steps)
