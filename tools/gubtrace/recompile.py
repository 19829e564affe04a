"""recompile: jit cache misses must match the declared budget.

An XLA recompile on the serving path is a multi-second stall — a weak
-type leak (python-scalar `now` instead of `np.int64`), a new implicit
static, or a signature that fails to normalize turns into a recompile
*storm* that blows the p99 budget ("Designing Scalable Rate Limiting
Systems" puts tail latency at the center of limiter SLOs).  The audit
replays each kernel across its canonical signature matrix TWICE (a
second pass must be all cache hits), then applies the registry's
perturbed variants (python-scalar/weak-type `now`), and asserts the
jit cache entry count equals the declared budget — every cache miss is
accounted for, none are surprises.

Runs real executions on CPU at the canonical (tiny) shapes.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from tools.gubtrace.core import (
    BuiltKernel,
    Checker,
    Finding,
    KernelSpec,
    RunContext,
)


def cache_size(fn) -> Optional[int]:
    """Jit cache entry count, None when this jax build hides it."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


def runtime_cache_report() -> Dict[str, Optional[int]]:
    """Live jit-cache entry counts for every module-level jitted kernel
    the registry watches, for a process that has already run them (a
    count above the expected tier/shape set means a kernel recompiled).
    The served daemon's runtime check is the benchmark's
    `compiled_in_window` (bench/run.py), which holds every cell to zero
    compiles inside its measured window."""
    import importlib
    from pathlib import Path

    from tools.gubtrace.completeness import (
        WATCHED_MODULES,
        module_level_jits,
    )

    report: Dict[str, Optional[int]] = {}
    for rel in WATCHED_MODULES:
        modname = rel[:-3].replace("/", ".")
        mod = importlib.import_module(modname)
        source = Path(mod.__file__).read_text(encoding="utf-8")
        for name, _line in module_level_jits(source):
            fn = getattr(mod, name, None)
            if fn is not None:
                report[f"{modname}.{name}"] = cache_size(fn)
    return report


class RecompileChecker(Checker):
    name = "recompile"

    def check(self, spec: KernelSpec, built: BuiltKernel,
              ctx: RunContext) -> Iterable[Finding]:
        import jax

        fn = built.fn
        if built.recompile_budget is None:
            return ()
        try:
            fn.clear_cache()
        except Exception:
            pass
        start = cache_size(fn)
        if start is None:
            return [Finding(
                checker=self.name, kernel=spec.name, severity="warning",
                message="jit cache size not introspectable on this "
                        "jax build; audit skipped",
            )]
        out: List[Finding] = []
        # Donated buffers die on first use: rebuild args per pass.
        for passno in range(2):
            for sig_name, make_args in built.signatures.items():
                res = fn(*make_args())
                jax.block_until_ready(res)
            after = cache_size(fn) - start
            if passno == 0:
                first = after
            elif after != first:
                out.append(Finding(
                    checker=self.name, kernel=spec.name,
                    message=(
                        "replaying the canonical signatures added "
                        f"{after - first} cache entr(y/ies) — the "
                        "cache key is unstable (every production call "
                        "would recompile)"
                    ),
                ))
        for pname, make_args in built.perturbations.items():
            res = fn(*make_args())
            jax.block_until_ready(res)
        total = cache_size(fn) - start
        if total != built.recompile_budget:
            out.append(Finding(
                checker=self.name, kernel=spec.name,
                message=(
                    f"compilation-cache misses: observed {total}, "
                    f"declared {built.recompile_budget} "
                    f"({len(built.signatures)} canonical signatures + "
                    f"{len(built.perturbations)} perturbations) — an "
                    "unexpected miss is a recompile storm in "
                    "production; either normalize the input (preferred)"
                    " or re-declare the budget with a justification"
                ),
            ))
        return out
