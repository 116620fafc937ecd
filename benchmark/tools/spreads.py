"""Spreads of two sets of runs (``measure.sh``'s ``sets.jsonl``): for each
end-to-end metric the median of each set and each set's spread, read both
ways: the interquartile distance by ``statistics.quantiles(n=4)`` over the
median (what a bound is set from), and the range of the set with the run
farthest from the median left out, over the median (how the driver judges
whether a bound is too tight)."""
import json
import statistics
import sys


def trimmed_range(vals: list[float]) -> float:
    med = statistics.median(vals)
    kept = sorted(vals, key=lambda v: abs(v - med))[:-1]
    return (max(kept) - min(kept)) / med


lines = [json.loads(line) for line in open(sys.argv[1]) if line.strip()]
half = len(lines) // 2
for name in lines[0]["metrics"]:
    out = []
    for first, part in ((True, lines[:half]), (False, lines[half:])):
        vals = [r["metrics"][name]["value"] for r in part]
        if name == "setup_s" and first:
            vals = vals[1:]  # the first run of a side compiles
        q = statistics.quantiles(vals, n=4)
        med = statistics.median(vals)
        out.append((med, (q[2] - q[0]) / med, trimmed_range(vals), vals))
    print(name, "medians", out[0][0], out[1][0], "spreads", f"{out[0][1]:.4%}", f"{out[1][1]:.4%}",
          "range less the farthest", f"{out[0][2]:.4%}", f"{out[1][2]:.4%}",
          "second/first", f"{out[1][0] / out[0][0] - 1:+.4%}")
    print("   ", [round(v, 3) for v in out[0][3]], [round(v, 3) for v in out[1][3]])
print("correct:", [r["correct"] for r in lines])
