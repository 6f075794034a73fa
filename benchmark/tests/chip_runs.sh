#!/bin/bash
# Runs of one cell in one chiprun call, each with its own seed; every result line
# and every run's stderr lands under chiprun_out/<tag>/.
#   chiprun -- bash benchmark/tests/chip_runs.sh <tag> <workload> <seconds> <trace> <seed>...
tag=$1; workload=$2; seconds=$3; trace=$4; shift 4
out=chiprun_out/$tag; mkdir -p "$out"
for seed in "$@"; do
  t0=$(date +%s.%N)
  python3 benchmark/run.py --workload "$workload" --seed "$seed" --seconds "$seconds" \
      --trace "$trace" > "$out/$seed.t$trace.out" 2> "$out/$seed.t$trace.err"
  rc=$?
  t1=$(date +%s.%N)
  echo "seed $seed trace $trace rc $rc wall $(python3 -c "print(round($t1 - $t0, 1))") s"
  tail -n 1 "$out/$seed.t$trace.out" | cut -c1-1500
  tail -n 12 "$out/$seed.t$trace.err" | cut -c1-600
done
du -sh benchmark/.cache 2>/dev/null
