"""End to end, host clock: process start (the first line of ``run.py``) to
the start of the first timed step — imports, mesh, the program's state, the
weights made from the seed, compile or cache load, the three set-up steps
and what the recorder reads from them."""


def read(run: dict):
    return run["t_window"] - run["t_process"]
