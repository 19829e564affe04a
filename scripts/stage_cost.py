#!/usr/bin/env python3
"""What one stage of the ledger costs the thread that times it.

    chiprun --timeout 300 -- python scripts/stage_cost.py \\
        --repo .bench_checkout/parent --repo .          # the chip's host
    python scripts/stage_cost.py                        # here

The stage ledger (runtime/tracing.py) has no switch, so its cost is paid
by every RPC and every drain.  In a tight loop, with no profiler session
and the span plane disarmed, per --repo (another checkout measures the
parent's ledger): `section_ns`, one `with ledger.stage("lane.pack", ...)`
enter -> exit; `pair_ns`, one `ledger.begin("lane.handoff", ...).end()`;
`thread_time_ns`, `perf_counter_ns`, the clocks themselves (the thread's
CPU clock is a system call of microseconds under gVisor, which is why no
stage reads it: PERF.md, PR 41); `thread_vars_us`, one render of the
`threads` block, which reads every thread's CPU clock from outside, where
the checkout has one.  Each is the median of --reps loops of --n calls.
One JSON object a checkout on stdout.

It runs no device program: JAX is imported for the profiler's TraceMe and
held to the CPU, so it may run beside a process that holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

CHILD = r"""
import json, statistics, sys, time, timeit
import jax  # noqa: F401 — the ledger's TraceMe
from gubernator_tpu.runtime import tracing

n, reps = int(sys.argv[1]), int(sys.argv[2])
ledger = tracing.StageLedger()


def section():
    with ledger.stage("lane.pack", "mach"):
        pass


def pair():
    ledger.begin("lane.handoff", "mach").end()


def ns(fn):
    return round(statistics.median(
        timeit.timeit(fn, number=n) / n * 1e9 for _ in range(reps)), 1)


render = getattr(tracing, "thread_vars", None)
print(json.dumps({
    "section_ns": ns(section), "pair_ns": ns(pair),
    "thread_vars_us": render and round(statistics.median(
        timeit.timeit(render, number=200) / 200 * 1e6
        for _ in range(reps)), 1),
    "thread_time_ns": ns(time.thread_time_ns),
    "perf_counter_ns": ns(time.perf_counter_ns),
    "row": ledger.debug_vars()["mach"]["pack"],
}))
"""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", action="append", default=[],
                    help="a checkout to measure (default: this one)")
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args()
    for repo in args.repo or [str(REPO)]:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(Path(repo).resolve()))
        out = subprocess.run(
            [sys.executable, "-c", CHILD, str(args.n), str(args.reps)],
            env=env, cwd=repo, check=True, capture_output=True, text=True,
        ).stdout.strip().splitlines()[-1]
        print(json.dumps({"repo": repo, **json.loads(out)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
