"""End to end, host clock: 95th percentile (nearest rank) of the synced wall
time of every timed step of the window, from the trainer's own per-step
stamps (differences of ``TrainResult.elapsed_times``)."""

from percentile import nearest_rank


def read(run: dict):
    ends = run["step_ends"]
    steps = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    p = nearest_rank(steps, 0.95)
    return None if p is None else 1e3 * p
