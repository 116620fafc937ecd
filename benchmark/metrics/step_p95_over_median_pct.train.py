"""Step program, host clock: how far the window's 95th-percentile step lies
over its median step, in percent of the median — ``train_step_ms_p95``'s own
steps (differences of ``TrainResult.elapsed_times``, nearest rank), the steps
the profiler touched left out. A run whose steps all do the same work reads
the host's jitter (0.5 in GPT-2 medium's cell); a run in which a few steps
pay more than the rest reads what they pay. In the Qwen3-Next cell a third
group of tiles in one more layer is 3.7 ms = 0.67: the one seed of six whose
steps sit on one level reads 0.1, the others mix levels inside a run and
read 0.6 to 1.3 (PERF.md section 2). Between runs the level itself differs;
``moe_ms.train`` reads that."""

import statistics

from percentile import nearest_rank
from xtrace import profiled_steps


def read(run: dict):
    ends = run["step_ends"]
    skip = profiled_steps(run)
    steps = [b - a for i, (a, b) in enumerate(zip([0.0] + ends[:-1], ends), 1) if i not in skip]
    if len(steps) < 3:
        return None
    median = statistics.median(steps)
    return 100.0 * (nearest_rank(steps, 0.95) - median) / median
