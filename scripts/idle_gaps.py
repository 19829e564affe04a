#!/usr/bin/env python3
"""Name the device's idle gaps by the stage ledger's own stages.

    python scripts/idle_gaps.py <trace.xplane.pb | trace dir>

A builder's tool beside the benchmark, not part of it.  It reads one
profiler capture of the serving daemon and, for the idle gaps of chip 0
(the same gaps, the same "most specific host event" rule and the same
2,000-gap cut as bench/lib/trace.py, whose functions it uses), prints one
JSON object:

  by_most_specific_event   seconds by the host event that overlaps each
                           gap most (the shortest on a tie), over EVERY
                           host thread;
  as_the_benchmark_reads   bench/lib/trace.py's own `idle_gaps` for the
                           same file (it keeps one line per thread NAME,
                           and every Python thread's line has the same
                           name, so it sees one of them: PERF.md §7);
  by_covering_stage        seconds by the shortest gub.* stage
                           (runtime/tracing.py) that covers the whole gap —
                           "none" where no stage does;
  cross                    the two joined: which stage the most specific
                           event sat inside;
  stages                   count and seconds of every gub.* event traced;
  largest_gaps             the dozen longest gaps with what overlapped
                           each: [name, event ms, share of the gap,
                           thread line].

bench/run.py removes its capture when it ends.  To keep one, run it with
TMPDIR set and copy `$TMPDIR/gubbench-*/trace/plugins/profile/*/*.xplane.pb`
while the run is still in its window (PR 25's chip calls did).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "bench"))

from lib import trace as T  # noqa: E402

MAX_LISTED = 14


def scan(path: str) -> dict:
    from jax.profiler import ProfileData

    host = []           # (name, start, end, thread line)
    dev = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for li, ln in enumerate(plane.lines):
                for ev in ln.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns, li))
        elif plane.name == "/device:TPU:0":
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    _n, s, e = T._events(ln)
                    dev = (s, e)
    if dev is None or not host:
        raise SystemExit("no /device:TPU:0 ops or no host events in "
                         + path)
    busy, ms, me = T.union_seconds(*dev)
    gs, ge = me[:-1], ms[1:]
    names = [h[0] for h in host]
    hs = np.array([h[1] for h in host], dtype=np.float64)
    he = np.array([h[2] for h in host], dtype=np.float64)
    hl = he - hs
    is_stage = np.array([n.startswith("gub.") for n in names])
    specific: dict = {}
    covering: dict = {}
    cross: dict = {}
    largest = []
    total = 0.0
    for g in np.argsort(ge - gs)[::-1][:T.MAX_GAPS]:
        a, b = gs[g], ge[g]
        d = (b - a) / 1e9
        total += d
        ov = np.minimum(he, b) - np.maximum(hs, a)
        winner = "no_host_span"
        if ov.max() > 0:
            cand = np.flatnonzero(ov >= ov.max() * 0.999)
            winner = T.stable(names[cand[np.argmin(hl[cand])]])
        full = np.flatnonzero(is_stage & (ov >= (b - a) * 0.999))
        stage = names[full[np.argmin(hl[full])]] if len(full) else "none"
        specific[winner] = specific.get(winner, 0.0) + d
        covering[stage] = covering.get(stage, 0.0) + d
        key = f"{winner} in {stage}"
        cross[key] = cross.get(key, 0.0) + d
        if len(largest) < 12:
            over = np.flatnonzero(ov > 0.2 * (b - a))
            over = over[np.argsort(hl[over])][:MAX_LISTED]
            largest.append({"gap_ms": d * 1e3, "over_it": [
                [names[i][:60], round(hl[i] / 1e6, 3),
                 round(float(ov[i] / (b - a)), 2), host[i][3]]
                for i in over
            ]})

    def top(table):
        return sorted(([k, round(v, 6)] for k, v in table.items()),
                      key=lambda kv: -kv[1])[:MAX_LISTED]

    stages: dict = {}
    for n, length in zip(names, hl):
        if n.startswith("gub."):
            row = stages.setdefault(n, [0, 0.0])
            row[0] += 1
            row[1] += length / 1e9
    return {
        "busy_s": busy, "gaps_s": total, "gaps": int(len(gs)),
        "by_most_specific_event": top(specific),
        "as_the_benchmark_reads": T.reduce_xplane(path)["idle_gaps"],
        "by_covering_stage": top(covering),
        "cross": top(cross),
        "stages": {k: [v[0], round(v[1], 4)]
                   for k, v in sorted(stages.items())},
        "largest_gaps": largest,
    }


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = T.find_xplane(target)
    print(json.dumps(scan(target)))
