"""Trainer start-up, host clock: the process's first line (``run.py``'s
``T_PROCESS``) to the first line of ``trainer.train`` (the ``startup`` event's
``t_enter``, on the same ``perf_counter``): imports, configurations, reaching
the chip. The one part of ``setup_s`` timed from outside; the program's entry
is its inner edge. None where the run's events hold no ``startup`` event."""

from trainer_clock import event


def read(run: dict):
    e = event(run)
    return None if e is None else e["t_enter"] - run["t_process"]
