"""Spreads of two sets of runs (``measure.sh``'s ``sets.jsonl``): for each
end-to-end metric the median of each set, each set's spread (interquartile
distance by ``statistics.quantiles(n=4)`` over the median) and the wider."""
import json
import statistics
import sys

lines = [json.loads(line) for line in open(sys.argv[1]) if line.strip()]
half = len(lines) // 2
for name in lines[0]["metrics"]:
    out = []
    for first, part in ((True, lines[:half]), (False, lines[half:])):
        vals = [r["metrics"][name]["value"] for r in part]
        if name == "setup_s" and first:
            vals = vals[1:]  # the first run of a side compiles
        q = statistics.quantiles(vals, n=4)
        out.append((statistics.median(vals), (q[2] - q[0]) / statistics.median(vals), vals))
    print(name, "medians", out[0][0], out[1][0], "spreads", f"{out[0][1]:.4%}", f"{out[1][1]:.4%}",
          "second/first", f"{out[1][0] / out[0][0] - 1:+.4%}")
    print("   ", [round(v, 3) for v in out[0][2]], [round(v, 3) for v in out[1][2]])
print("correct:", [r["correct"] for r in lines])
