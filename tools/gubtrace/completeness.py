"""registry: every module-level jitted kernel must be registered.

The registry is only a gate if it is complete — a new
`foo = jax.jit(...)` added to a kernel module without a registry entry
would silently skip every gubtrace invariant.  This checker AST-scans
the watched kernel modules for module-level `jax.jit(...)` assignments
and requires each bound name to appear in the registry (factory-built
kernels — the shard_map steps — are registered by hand and listed in
FACTORY_KERNELS for the same reason).

A deliberate exemption takes a `# gubtrace: ok[=registry]` pragma on
the assignment line.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set

from tools.gubtrace.core import _PRAGMA_RE, Checker, Finding, RunContext

# Modules whose module-level jits the registry must cover.  The mesh
# entrypoints (parallel/sharded.py, parallel/global_sync.py) are
# factory-built shard_map kernels — no module-level jits today — but
# watching them means a future `X = jax.jit(...)` there is flagged
# instead of silently shipping unverified.
WATCHED_MODULES = (
    "gubernator_tpu/ops/step.py",
    "gubernator_tpu/ops/sketch.py",
    "gubernator_tpu/ops/pallas/cms_kernel.py",
    "gubernator_tpu/parallel/sharded.py",
    "gubernator_tpu/parallel/global_sync.py",
)


def _is_jax_jit_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return (
        isinstance(f, ast.Attribute) and f.attr == "jit"
        and isinstance(f.value, ast.Name) and f.value.id == "jax"
    )


def module_level_jits(source: str) -> List[tuple]:
    """(name, line) for every module-level `X = jax.jit(...)`."""
    tree = ast.parse(source)
    out = []
    for node in tree.body:
        if isinstance(node, ast.Assign) and _is_jax_jit_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.append((t.id, node.lineno))
    return out


def _pragma_lines(source: str, checker: str) -> Set[int]:
    lines = set()
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if not m:
            continue
        names = m.group("names")
        if names is None or checker in names.split(","):
            lines.add(i)
    return lines


class RegistryCompletenessChecker(Checker):
    name = "registry"

    def __init__(self, registered: Iterable[str],
                 watched: Iterable[str] = WATCHED_MODULES) -> None:
        self.registered = set(registered)
        self.watched = tuple(watched)

    def finalize(self, ctx: RunContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for rel in self.watched:
            path = ctx.root / rel
            if not path.is_file():
                out.append(Finding(
                    checker=self.name, kernel="-", severity="warning",
                    message=f"watched kernel module missing: {rel}",
                ))
                continue
            source = path.read_text(encoding="utf-8")
            ok_lines = _pragma_lines(source, self.name)
            for name, line in module_level_jits(source):
                if name in self.registered or line in ok_lines:
                    continue
                out.append(Finding(
                    checker=self.name, kernel=name,
                    message=(
                        f"jitted entrypoint '{name}' ({rel}:{line}) is "
                        "not in the gubtrace registry — it ships with "
                        "ZERO device-side invariant coverage; register "
                        "it in tools/gubtrace/registry.py or pragma "
                        "the assignment"
                    ),
                    where=f"{rel}:{line}",
                ))
        return out
