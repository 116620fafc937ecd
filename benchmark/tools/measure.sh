#!/bin/bash
# The measurements a new cell's bounds are set from, in one call on the chip:
#   bash benchmark/tools/measure.sh <cell> <seconds> <outdir> [first_seed]
# two sets of 6 runs (the same six seeds in both), then 3 runs with --trace 1.
W=$1; S=$2; O=$3; F=${4:-2500000001}; mkdir -p $O
for set in a b; do
  for i in 0 1 2 3 4 5; do
    python3 benchmark/run.py --workload $W --seed $((F + 104729 * i)) --seconds $S --trace 0 \
      > $O/$set$i.out 2> $O/$set$i.err; echo "set $set run $i rc=$?"
    tail -n 1 $O/$set$i.out >> $O/sets.jsonl
    cp benchmark/out/$W/train/log.csv $O/$set$i.log.csv
  done
done
for i in 6 7 8; do
  python3 benchmark/run.py --workload $W --seed $((F + 104729 * i)) --seconds $S --trace 1 \
    > $O/t$i.out 2> $O/t$i.err; echo "trace run $i rc=$?"
  tail -n 1 $O/t$i.out >> $O/traces.jsonl
  cp benchmark/out/$W/run.json $O/t$i.json
done
grep -h "^correct" $O/*.err | sort | uniq -c
echo "rows of a log.csv that are not finite: $(grep -ci "nan\|inf" $O/*.log.csv | grep -v ":0$" | wc -l) file(s)"
