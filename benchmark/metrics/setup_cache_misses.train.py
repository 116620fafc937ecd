"""Trainer start-up, program counter: ``cache_misses`` of the ``startup``
event: programs compiled anew before the first timed step (compile requests
the persistent cache did not serve). On a warm run they are the small programs
under JAX's 1 s caching threshold and whatever cannot be cached; the event's
``compiled`` names them, its ``cache_writes`` says how many the cache then
kept (a step program among them: a key moved)."""

from trainer_clock import event


def read(run: dict):
    e = event(run)
    return None if e is None else e["cache_misses"]
