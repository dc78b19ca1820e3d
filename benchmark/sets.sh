#!/bin/bash
# Two sets of runs of one cell, the same seeds in both, as the bound is set from:
#   bash benchmark/sets.sh <cell> <seconds> <seed> [<seed> ...]
# One result line per run goes to chiprun_out/sets/<cell>.<seconds>s.jsonl
# (set, seed, the run's last line); the per-step records stay in chiprun_out/bench/.
cell=$1; seconds=$2; shift 2
out=chiprun_out/sets; mkdir -p $out
for set in 1 2; do
  for seed in "$@"; do
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 > $out/last.out 2> $out/last.err
    rc=$?; line=$(tail -n 1 $out/last.out)
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": ${line:-null}}" >> "$out/$cell.${seconds}s.jsonl"
    cp chiprun_out/bench/pieces.$cell.seed$seed.trace0.json $out/pieces.$cell.${seconds}s.set$set.seed$seed.json 2>/dev/null
    tail -n 4 $out/last.err | cut -c1-300
    echo "$line" | cut -c1-400
  done
done
