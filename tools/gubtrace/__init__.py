"""gubtrace: jaxpr-level static verification of every jitted kernel.

gubguard (tools/gubguard) polices the Python source; gubtrace polices
the *traced computation* — the jaxprs XLA actually compiles — where
the hot-path invariants hold or break.  Every registered kernel
(tools/gubtrace/registry.py) is traced over a canonical shape/dtype
matrix on CPU and checked for:

  dtype-taint       counter/timestamp int64 dataflow never silently
                    narrows or floats beyond the declared budget
  host-escape       no callback primitives compiled into a kernel
  donation          declared donate_argnums survive into the lowering
  primitive-budget  expensive-primitive counts match the golden
                    snapshots (tools/gubtrace/golden/)
  recompile         jit cache misses match the declared budget
  registry          every module-level jitted kernel is registered

Run:

    JAX_PLATFORMS=cpu python -m tools.gubtrace           # verify
    python -m tools.gubtrace --update                    # re-snapshot

Exit status 0 = clean (warnings allowed), 1 = errors.  The runtime
counterpart is the benchmark's `compiled_in_window` (bench/run.py:
compile-cache entries written inside a cell's measured window; any
is `correct: false`).
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Optional, Sequence

from tools.gubtrace.budget import PrimitiveBudgetChecker
from tools.gubtrace.completeness import RegistryCompletenessChecker
from tools.gubtrace.core import (
    Checker,
    Finding,
    KernelSpec,
    RunContext,
    run_kernels,
)
from tools.gubtrace.donation import DonationChecker
from tools.gubtrace.dtype import DtypeTaintChecker
from tools.gubtrace.hostescape import HostEscapeChecker
from tools.gubtrace.recompile import RecompileChecker

ALL_CHECKERS = (
    "dtype-taint",
    "host-escape",
    "donation",
    "primitive-budget",
    "recompile",
    "registry",
)

GOLDEN_DIR = Path(__file__).parent / "golden"


def make_checkers(
    select: Optional[Sequence[str]] = None,
    registered: Optional[Sequence[str]] = None,
) -> List[Checker]:
    factory = {
        "dtype-taint": DtypeTaintChecker,
        "host-escape": HostEscapeChecker,
        "donation": DonationChecker,
        "primitive-budget": PrimitiveBudgetChecker,
        "recompile": RecompileChecker,
        "registry": lambda: RegistryCompletenessChecker(registered or ()),
    }
    names = list(select) if select else list(ALL_CHECKERS)
    unknown = [n for n in names if n not in factory]
    if unknown:
        raise ValueError(f"unknown checkers: {unknown}")
    return [factory[n]() for n in names]


def run(
    select: Optional[Sequence[str]] = None,
    kernels: Optional[Sequence[str]] = None,
    root: Optional[Path] = None,
    golden_dir: Optional[Path] = None,
    update_golden: bool = False,
    specs: Optional[Sequence[KernelSpec]] = None,
    ctx_out: Optional[list] = None,
) -> List[Finding]:
    """Run the selected checkers over the registry; returns findings.

    `specs` overrides the registry (the seeded-violation fixtures use
    this); `ctx_out`, when given, receives the RunContext (the CLI
    dumps failing kernels' jaxprs from it).
    """
    from tools.gubtrace import registry as reg

    all_specs = list(specs) if specs is not None else reg.specs()
    if kernels:
        unknown = set(kernels) - {s.name for s in all_specs}
        if unknown:
            raise ValueError(f"unknown kernels: {sorted(unknown)}")
        all_specs = [s for s in all_specs if s.name in kernels]
    ctx = RunContext(
        root=root or Path.cwd(),
        golden_dir=golden_dir or GOLDEN_DIR,
        update_golden=update_golden,
    )
    if ctx_out is not None:
        ctx_out.append(ctx)
    checkers = make_checkers(
        select, registered=[s.name for s in all_specs]
    )
    return run_kernels(all_specs, checkers, ctx)
