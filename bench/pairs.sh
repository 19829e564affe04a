#!/bin/sh
# One untraced run of each cell on a parent checkout and on this one, the
# same seed: same result-line keys, `correct`, and the metrics side by side.
#
#   sh bench/pairs.sh <parent_dir> "<cells>" [seed] [seconds]
#
# <parent_dir> is the parent commit unpacked inside this checkout, in a
# directory .gitignore lists (git archive <commit> | tar -x -C .checkout/parent),
# so that one chip call measures both.  Order: parent, change.
set -u
parent=${1:?parent checkout}; cells=${2:?cells}; seed=${3:-20240937}
secs=${4:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
here=$PWD
mkdir -p chiprun_out/pairs
for w in $cells; do
  for side in parent change; do
    dir=$here; [ $side = parent ] && dir=$here/$parent
    log="$here/chiprun_out/pairs/$side.$w.$seed.log"
    (cd "$dir" && python3 bench/run.py --workload "$w" --seed "$seed" \
      --seconds "$secs" --trace 0 --out "$here/chiprun_out/pairs/$side.$w.$seed" \
      ${BENCH_EXTRA:-}) > "$log" 2>&1
    echo "== $side $w seed $seed rc=$? $(grep -c FAILED "$log") comparisons failed"
    tail -n 1 "$log" | python3 -c "
import json,sys
try:
    d=json.loads(sys.stdin.read())
    print('  ', {k: v['value'] for k, v in d['metrics'].items()}, 'correct', d['correct'], 'failed', d['failed'], 'keys', list(d), 'compared', list(d['compared']))
except Exception as e:
    print('   no result line:', e)"
  done
  seed=$((seed + 1))
done
