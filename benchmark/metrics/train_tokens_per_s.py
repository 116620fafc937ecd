"""End to end, host clock: the global tokens of every step that completed in
the window, over the whole window (the trainer's own stamps: loop start to
the last timed step's synced end). No medians of chunks."""


def read(run: dict):
    ends = run["step_ends"]
    return len(ends) * run["tokens_per_step"] / ends[-1] if ends else None
