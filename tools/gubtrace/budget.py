"""primitive-budget: golden per-kernel counts of expensive primitives.

The hot-path kernels earn their throughput by a known, reviewed set of
expensive XLA ops — apply_batch is "bucket gather → claim sort and scans →
lane arithmetic → scatter" and nothing else.  A refactor that quietly
adds one more `gather` (a stray fancy-index), a `sort`, or an extra
collective doubles a measured cost without any test failing.  Each
registered kernel's counts of the budgeted primitives are snapshotted
under tools/gubtrace/golden/<kernel>.json; a drift fails CI with a
diff, and an intentional change is re-snapshotted with
`python -m tools.gubtrace --update`.

Counts are static (loop bodies count once, not per iteration) and
recurse through every sub-jaxpr.
"""
from __future__ import annotations

import json
from collections import Counter
from typing import Dict, Iterable, List

from tools.gubtrace.core import (
    BuiltKernel,
    Checker,
    Finding,
    KernelSpec,
    RunContext,
    iter_eqns,
)

# The expensive-primitive watchlist: memory-bound data movement
# (gather/scatter), O(n log n) work (sort), running reductions along an
# axis (the claim rounds of ops/step.py are made of cummax/cummin),
# control flow that defeats fusion (while/scan/cond), and inter-chip
# collectives.
BUDGETED = (
    "gather",
    "scatter",
    "scatter-add",
    "sort",
    "cumsum",
    "cummax",
    "cummin",
    "while",
    "scan",
    "cond",
    "all_to_all",
    "all_gather",
    "psum",
    "pallas_call",
)


def count_budgeted(jaxpr) -> Dict[str, int]:
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in BUDGETED:
            c[name] += 1
    return {k: c[k] for k in sorted(c)}


class PrimitiveBudgetChecker(Checker):
    name = "primitive-budget"

    def check(self, spec: KernelSpec, built: BuiltKernel,
              ctx: RunContext) -> Iterable[Finding]:
        observed = {
            sig: count_budgeted(jaxpr)
            for sig, jaxpr in ctx.jaxprs[spec.name].items()
        }
        path = ctx.golden_dir / f"{spec.name}.json"
        if ctx.update_golden:
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(
                json.dumps({"primitives": observed}, indent=2,
                           sort_keys=True) + "\n",
                encoding="utf-8",
            )
            return ()
        if not path.is_file():
            return [Finding(
                checker=self.name, kernel=spec.name,
                message=(
                    "no golden snapshot; run "
                    "`python -m tools.gubtrace --update` and commit "
                    f"{path.name}"
                ),
            )]
        golden = json.loads(path.read_text(encoding="utf-8"))["primitives"]
        out: List[Finding] = []
        for sig in sorted(set(golden) | set(observed)):
            g, o = golden.get(sig), observed.get(sig)
            if g == o:
                continue
            if g is None or o is None:
                out.append(Finding(
                    checker=self.name, kernel=spec.name,
                    message=(
                        f"signature matrix drifted: '{sig}' "
                        f"{'added' if g is None else 'removed'} — "
                        "re-snapshot with --update"
                    ),
                ))
                continue
            diffs = [
                f"{p}: golden {g.get(p, 0)} -> observed {o.get(p, 0)}"
                for p in sorted(set(g) | set(o))
                if g.get(p, 0) != o.get(p, 0)
            ]
            out.append(Finding(
                checker=self.name, kernel=spec.name,
                message=(
                    f"[{sig}] expensive-primitive counts drifted "
                    "(intentional? re-snapshot with --update): "
                    + "; ".join(diffs)
                ),
            ))
        return out
