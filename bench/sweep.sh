#!/bin/sh
# The open cell's sweep: its traffic at a list of arrival rates, one short
# run each, to find the knee once (PERF.md keeps the table).
#
#   sh bench/sweep.sh <workload> "<rates>" [seconds] [seed]
#
# Untraced by default (the profiler's start and stop stall the daemon and
# would be read as tail latency); TRACE=1 adds the per-layer numbers.
set -u
w=${1:?workload}; rates=${2:?rates}; secs=${3:-10}; seed=${4:-424242}
mkdir -p chiprun_out
for r in $rates; do
  log="chiprun_out/sweep_${w}_$r.log"
  python3 bench/run.py --workload "$w" --seed "$seed" --seconds "$secs" \
    --trace "${TRACE:-0}" --rate "$r" > "$log" 2>&1
  echo "== $r RPC/s (rc=$?)"
  grep -E "rpc latency samples|window:|FAILED|trace:|no result" "$log" | grep -v not_the_cells_rate | cut -c1-400
  tail -n 1 "$log" | python3 -c "
import json,sys
d=json.loads(sys.stdin.read()); m={k:round(v['value'],3) for k,v in d['metrics'].items()}
dev=d['device']; print('   metrics', m, 'busy', round(dev.get('busy_s',0),3), 'of', round(dev.get('window_s',0),3), 'failed', d['failed'], 'of', d['attempted'])"
  seed=$((seed + 1))
done
