"""Trainer loop, program spans: mean over the window's ``step`` events of
everything that is not waiting for the device (data wait + dispatch + other
= step time - block). In a traced run the steps the profiler touched are
left out (``xtrace.profiled_steps``)."""

from xtrace import profiled_steps


def read(run: dict):
    skip = profiled_steps(run)
    steps = [e for e in run["events"] if e.get("etype") == "step" and e.get("step") not in skip]
    if not steps:
        return None
    return 1e3 * sum(e["step_time_s"] - e["block_s"] for e in steps) / len(steps)
