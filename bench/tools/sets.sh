#!/usr/bin/env bash
# Two sets of runs of one cell on the same seeds, one process per run:
#   bash bench/tools/sets.sh <workload> <seconds> <out dir> <seed>...
# Writes <out dir>/<set>_<seed>.out (the result line) and .err, and one
# summary line per run to <out dir>/runs.jsonl.
set -u
workload=$1; seconds=$2; out=$3; shift 3
mkdir -p "$out"
for set in a b; do
  for seed in "$@"; do
    python3 bench/run.py --workload "$workload" --seed "$seed" \
      --seconds "$seconds" --trace 0 > "$out/${set}_${seed}.out" \
      2> "$out/${set}_${seed}.err"
    rc=$?
    printf '{"set": "%s", "seed": %s, "rc": %s, "line": %s}\n' "$set" "$seed" \
      "$rc" "$(tail -n 1 "$out/${set}_${seed}.out" | grep '^{' || echo null)" \
      >> "$out/runs.jsonl"
  done
done
