"""Expert layer, program counter: how far the routers moved inside a run —
over the window's timed steps, the largest less the smallest per-step total
of held assignments (``moe_assigned_held`` of the program's ``moe_counters``
events, summed over the layers), over their mean, in percent. The dropless
loop's time follows that total (0.70 ms a thousand, PERF.md section 6), so a
run whose total wanders times a different step at its end than at its start.
Under 6 is healthy (every step within 3 % of the run's mean). The floor is
not 0 and grows with the step count, since the window is set by the clock:
routers that stand still scatter a step's total binomially, 0.48 % of 40,960,
and the range of n such draws is about 0.48 x (3.9 at 25 steps, 4.6 at 56,
5.0 at 92, 5.5 at 200) = 1.9 / 2.2 / 2.4 / 2.6, a run within 0.6 of that
(read: 1.6 to 2.7 at 55 to 57 steps, 2.2 and 3.0 at 92). The same cell at a
from-scratch rate read 73 and 87 (PR 33). It sees the routers move inside a
run; it does not see which level a seed's routers put the step on
(``moe_ms.train``) nor a few steps a level up (``step_p95_over_median_pct.train``)."""


def read(run: dict):
    totals = [sum(e["moe_assigned_held"]) for e in run["events"]
              if e.get("etype") == "moe_counters"]
    mean = sum(totals) / len(totals) if totals else 0.0
    return 100.0 * (max(totals) - min(totals)) / mean if mean else None
