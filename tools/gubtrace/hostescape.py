"""host-escape: no callback primitives inside hot-path kernels.

A `pure_callback` / `io_callback` / `debug_callback` inside a jitted
kernel inserts a device→host round-trip into the compiled computation
(docs/invariants.md §1) — a host transition inside the step the 2 ms
p99 budget is stated against.  gubguard's host-sync checker polices Python *call sites*; this
one polices the *traced computation*, where a callback smuggled in via
a library helper (e.g. `jax.debug.print` left in a kernel) still shows
up as a primitive.
"""
from __future__ import annotations

from typing import Iterable, List

from tools.gubtrace.core import (
    BuiltKernel,
    Checker,
    Finding,
    KernelSpec,
    RunContext,
    eqn_source,
    iter_eqns,
)

# Primitive names that imply a host transition inside the computation.
FORBIDDEN = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",  # what jax.debug.print lowers to since jax 0.9
    "host_callback_call",
    "outside_call",
    "infeed",
    "outfeed",
})


class HostEscapeChecker(Checker):
    name = "host-escape"

    def check(self, spec: KernelSpec, built: BuiltKernel,
              ctx: RunContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for sig_name, jaxpr in ctx.jaxprs[spec.name].items():
            for eqn in iter_eqns(jaxpr):
                name = eqn.primitive.name
                if name in FORBIDDEN or name.endswith("_callback"):
                    out.append(Finding(
                        checker=self.name, kernel=spec.name,
                        message=(
                            f"[{sig_name}] host callback primitive "
                            f"'{name}' compiled into the kernel"
                        ),
                        where=eqn_source(eqn),
                    ))
            break  # structure is signature-invariant
        return out
