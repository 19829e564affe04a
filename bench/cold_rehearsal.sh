#!/bin/sh
# Rule 3 of the benchmark: a cell is closed under a cold compile cache.
#
#   sh bench/cold_rehearsal.sh <workload> [seed] [seconds]
#
# Runs the cell twice, each time from its own EMPTY compile-cache directory
# (under .jax_cache/, which git and the chip copy ignore), and prints what
# matters: how long the cold daemon took, the fetch shapes warmed, cache
# entries written inside the window (any is `correct: false`), failed
# checks and the result line.  Run it on the chip for every cell before its
# sets:  chiprun --timeout 1500 -- sh bench/cold_rehearsal.sh <workload>
set -u
w=${1:?workload}
seed=${2:-20240901}
secs=${3:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
mkdir -p chiprun_out
rc=0
for n in 1 2; do
  d="$PWD/.jax_cache/cold_rehearsal_$n"
  rm -rf "$d" && mkdir -p "$d"
  log="chiprun_out/cold_${w}_$n.log"
  echo "== cold run $n of $w from an empty $d"
  JAX_COMPILATION_CACHE_DIR="$d" python3 bench/run.py --workload "$w" \
    --seed $((seed + n)) --seconds "$secs" --trace 0 ${BENCH_EXTRA:-} > "$log" 2>&1 || rc=$?
  grep -E "daemon[.0-9]* ready|forward hop|compile-cache entries|compare compiled_in_window|FAILED|window:|^\{|no result" "$log" | cut -c1-1500
  echo "   entries in the cache afterwards: $(ls "$d" | grep -c -- '-cache$')"
  rm -rf "$d"
done
exit $rc
