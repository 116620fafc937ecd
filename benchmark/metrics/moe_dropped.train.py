"""Expert layer, program counter: held assignments that the loop over tiles
did not compute (the step's held assignments less the rows its tiles
covered), summed over the window's steps — the program's ``moe_counters``
events. The layer has no capacity and no bound, so 0 is the only healthy
value: another says the tiling lost rows."""


def read(run: dict):
    got = [e["moe_dropped"] for e in run["events"] if e.get("etype") == "moe_counters"]
    return float(sum(got)) if got else None
