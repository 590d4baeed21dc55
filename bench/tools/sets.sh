#!/usr/bin/env bash
# Two sets of runs of one cell with the same seeds in both sets, then traced
# runs on further seeds: the measurements that set a cell's bounds and show
# it correct.  Each run's last stdout line goes to <out>/sets.<cell>.jsonl and
# its stderr lines to <out>/sets.<cell>.log (<out>: $SETS_OUT, by default
# bench/.results).
#
#   bash bench/tools/sets.sh <cell> <seconds> <seeds,...> [<traced seeds,...>]
set -u
cell=$1 seconds=$2 seeds=$3 traced=${4:-}
dir=${SETS_OUT:-bench/.results}
mkdir -p "$dir"
out=$dir/sets.$cell
for set in A B; do
  for seed in ${seeds//,/ }; do
    line=$(python3 -m bench.run --workload "$cell" --seed "$seed" \
      --seconds "$seconds" --trace 0 2>>"$out.log" | tail -n 1)
    echo "{\"set\": \"$set\", \"seed\": $seed, \"trace\": 0, \"out\": $line}" \
      | tee -a "$out.jsonl"
  done
done
for seed in ${traced//,/ }; do
  line=$(python3 -m bench.run --workload "$cell" --seed "$seed" \
    --seconds "$seconds" --trace 1 2>>"$out.log" | tail -n 1)
  echo "{\"set\": \"T\", \"seed\": $seed, \"trace\": 1, \"out\": $line}" \
    | tee -a "$out.jsonl"
  rm -rf bench/.trace
done
