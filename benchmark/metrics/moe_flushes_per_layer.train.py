"""Expert layer, program counter: how many scatters into the tokens' rows
an expert layer's forward ran, as a mean over the layers and the window's
steps (``moe_flushes`` of the program's ``moe_counters`` events, one entry a
layer). The loop over the held experts' tiles stages their rows and flushes
them when the staging is full; every flush sorts and permutes all staged
rows and walks the tokens' rows, so it is paid by the call. 1.0 is healthy:
the staging, sized from the shapes for routing a quarter over even, held
every layer of every step. More says how often routing outgrew it (nothing
is dropped then: ``moe_dropped.train``). A program that does not count its
flushes reads nothing."""


def read(run: dict):
    flushes = [v for e in run["events"]
               if e.get("etype") == "moe_counters" for v in e.get("moe_flushes", ())]
    return sum(flushes) / len(flushes) if flushes else None
