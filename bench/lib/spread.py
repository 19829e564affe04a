"""The contract's spread rule over a cell's two sets of runs.

    python bench/lib/spread.py chiprun_out/sets_<cell>_A_*.log -- chiprun_out/sets_<cell>_B_*.log

Each log ends in a result line.  For each metric: each set's median and
spread (distance between the first and third quartile as Python's
statistics.quantiles(n=4) gives them, as a share of the median), the wider
spread, five times it (the bound the rule suggests, never under 1 %), and
how far the second set's median lies from the first's.
"""
from __future__ import annotations

import json
import statistics
import sys
from typing import Dict, List


def last_result(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    if not lines:
        raise SystemExit(f"{path}: no result line")
    return json.loads(lines[-1])


def spread(values: List[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def summarize(sets: List[List[dict]]) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for name in sets[0][0]["metrics"]:
        vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
        med = [statistics.median(v) for v in vals]
        spr = [spread(v) for v in vals]
        out[name] = {
            "values": vals, "medians": med, "spreads": spr,
            "widest": max(spr), "bound_by_rule": max(0.01, 5 * max(spr)),
            "second_vs_first": med[-1] / med[0] - 1 if len(med) > 1 else 0.0,
        }
    return out


def main(argv: List[str]) -> None:
    groups, cur = [], []
    for a in argv:
        if a == "--":
            groups.append(cur)
            cur = []
        else:
            cur.append(a)
    groups.append(cur)
    sets = [[last_result(p) for p in g] for g in groups if g]
    bad = [r for s in sets for r in s if not r["correct"] or r["failed"]]
    print(f"runs: {[len(s) for s in sets]}; not correct or with failed "
          f"checks: {len(bad)}")
    for name, row in summarize(sets).items():
        print(name)
        for v, m, sp in zip(row["values"], row["medians"], row["spreads"]):
            print("   set: median %.6g spread %.4f values %s" % (
                m, sp, [round(x, 4) for x in v]))
        print("   widest spread %.4f -> bound by the rule %.3f; second set's "
              "median %+.4f against the first's" % (
                  row["widest"], row["bound_by_rule"], row["second_vs_first"]))


if __name__ == "__main__":
    main(sys.argv[1:])
