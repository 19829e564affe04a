#!/bin/sh
# Two sets of runs of one cell, the same seeds in both, as the benchmark's
# contract measures a bound; then the spread rule over them.
#
#   sh bench/sets.sh <workload> "<seeds>" [seconds]
set -u
w=${1:?workload}; seeds=${2:?seeds}
secs=${3:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
mkdir -p chiprun_out
for set in A B; do
  for seed in $seeds; do
    log="chiprun_out/sets_${w}_${set}_${seed}.log"
    python3 bench/run.py --workload "$w" --seed "$seed" --seconds "$secs" \
      --trace 0 > "$log" 2>&1
    echo "== set $set seed $seed rc=$? $(grep -c FAILED "$log") comparisons failed"
    tail -n 1 "$log" | cut -c1-600
  done
done
python3 bench/lib/spread.py chiprun_out/sets_${w}_A_*.log -- chiprun_out/sets_${w}_B_*.log
