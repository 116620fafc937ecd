"""Trainer start-up, program spans: ``named_s / total_s`` of the ``startup``
event: the share of ``train()``'s entry to the first timed step that lies
under a ``train.startup.*`` phase. Healthy >= 95."""

from trainer_clock import event


def read(run: dict):
    e = event(run)
    return None if e is None or not e["total_s"] else 100.0 * e["named_s"] / e["total_s"]
