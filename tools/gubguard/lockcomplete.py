"""lock-complete: every lock the codebase constructs is accounted for.

The lock-order ranking (lockorder.py) is only as good as its coverage:
a lock nobody registered is a lock the global order says nothing
about, and the lexical inversion checker will happily pass code that
deadlocks through it.  This checker closes the loop — every
`threading.Lock()` / `threading.RLock()` / `asyncio.Lock()` /
`threading.Condition()` CONSTRUCTED under the scanned tree must be

  * mapped to a canonical name by lockorder.CLASS_LOCK_MAP *and*
    ranked in lockorder.RANK, or
  * explicitly waived in WAIVERS with a reason (Conditions — which
    coordinate, not rank; function-local locks that never escape;
    module-level import guards taken alone).

Unaccounted construction is an error; so is a stale waiver that no
longer matches any construction site (a renamed lock must not leave a
dangling hall pass behind).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, List, Optional, Set, Tuple

from tools.gubguard.core import Checker, Finding, ModuleInfo, dotted_name
from tools.gubguard.lockorder import CLASS_LOCK_MAP, RANK

# Constructors that create a mutual-exclusion participant.
_LOCK_CTORS = {
    "threading.Lock", "threading.RLock", "asyncio.Lock",
}
# Constructors that create a coordination primitive — never ranked,
# always waived explicitly.
_COND_CTORS = {
    "threading.Condition", "asyncio.Condition",
}

# key -> reason.  Keys: "Class.attr" for instance attributes,
# "<relpath>::<name>" for module-level and function-local locks.
WAIVERS = {
    "PersistenceHost._wt_cond": (
        "writer-thread Condition: coordinates the snapshot writer's "
        "sleep/wake, never guards shared state against the request "
        "path (the data it signals about rides backend._lock)"
    ),
    "TierManager._cv": (
        "tier-worker Condition: demote/promote wakeup only; row state "
        "is guarded by coldtier._lock (rank 54), not by this"
    ),
    "gubernator_tpu/runtime/fastpath.py::gate": (
        "function-local Lock handed to one drain closure; never "
        "stored on an object, cannot participate in cross-path nesting"
    ),
    "gubernator_tpu/native/__init__.py::_load_lock": (
        "module-level import guard: serializes the one-time native "
        "library load, taken alone at first use, takes nothing while "
        "held"
    ),
}


def _ctor_kind(node: ast.AST) -> Optional[str]:
    """'lock' / 'cond' when `node` constructs a primitive we track."""
    if not isinstance(node, ast.Call):
        return None
    dn = dotted_name(node.func)
    if dn in _LOCK_CTORS:
        return "lock"
    if dn in _COND_CTORS:
        return "cond"
    return None


class _CtorVisitor(ast.NodeVisitor):
    def __init__(self, mod: ModuleInfo) -> None:
        self.mod = mod
        self.class_stack: List[str] = []
        self.fn_depth = 0
        # (key, line, kind, desc) per construction site
        self.sites: List[Tuple[str, int, str, str]] = []

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.fn_depth += 1
        self.generic_visit(node)
        self.fn_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def _record(self, target: ast.AST, kind: str, line: int) -> None:
        cls = self.class_stack[-1] if self.class_stack else None
        if (
            isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Name)
            and target.value.id == "self"
            and cls is not None
        ):
            self.sites.append(
                (f"{cls}.{target.attr}", line, kind,
                 f"self.{target.attr} in class {cls}")
            )
        elif isinstance(target, ast.Name):
            scope = "local" if self.fn_depth else "module-level"
            self.sites.append(
                (f"{self.mod.relpath}::{target.id}", line, kind,
                 f"{scope} name '{target.id}'")
            )
        else:
            self.sites.append(
                (f"{self.mod.relpath}::<anonymous>", line, kind,
                 "unrecognized assignment target")
            )

    def visit_Assign(self, node: ast.Assign) -> None:
        kind = _ctor_kind(node.value)
        if kind is not None:
            for t in node.targets:
                self._record(t, kind, node.lineno)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            kind = _ctor_kind(node.value)
            if kind is not None:
                self._record(node.target, kind, node.lineno)
        self.generic_visit(node)


class LockCompleteChecker(Checker):
    name = "lock-complete"

    def __init__(self) -> None:
        self.matched_waivers: Set[str] = set()
        self.saw_any = False

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        v = _CtorVisitor(mod)
        v.visit(mod.tree)
        out: List[Finding] = []
        for key, line, kind, desc in v.sites:
            self.saw_any = True
            if mod.suppressed(line, self.name):
                continue
            if key in WAIVERS:
                self.matched_waivers.add(key)
                continue
            if kind == "cond":
                out.append(Finding(
                    checker=self.name, path=mod.relpath, line=line,
                    message=(
                        f"Condition construction ({desc}) is not in the "
                        "lock-complete waiver list — conditions are "
                        "never ranked, so each needs an explicit waiver "
                        "stating what it coordinates "
                        "(tools/gubguard/lockcomplete.py WAIVERS)"
                    ),
                ))
                continue
            # instance-attribute lock: must resolve through
            # CLASS_LOCK_MAP into a RANKed canonical name.
            if "::" not in key:
                cls, _, attr = key.partition(".")
                canon = CLASS_LOCK_MAP.get((cls, attr))
                if canon is None:
                    out.append(Finding(
                        checker=self.name, path=mod.relpath, line=line,
                        message=(
                            f"lock {desc} is not registered: add "
                            f"('{cls}', '{attr}') to "
                            "lockorder.CLASS_LOCK_MAP and rank the "
                            "canonical name, or waive it with a reason"
                        ),
                    ))
                elif canon not in RANK:
                    out.append(Finding(
                        checker=self.name, path=mod.relpath, line=line,
                        message=(
                            f"lock {desc} maps to '{canon}' which has "
                            "no rank in lockorder.RANK — an unranked "
                            "lock is invisible to the global-order check"
                        ),
                    ))
            else:
                out.append(Finding(
                    checker=self.name, path=mod.relpath, line=line,
                    message=(
                        f"lock construction ({desc}) escapes the "
                        "class-attribute discipline — rank it or waive "
                        f"'{key}' in lockcomplete.WAIVERS with a reason"
                    ),
                ))
        return out

    def finalize(self, root: Path) -> Iterable[Finding]:
        if not self.saw_any:
            return []
        stale = sorted(set(WAIVERS) - self.matched_waivers)
        return [
            Finding(
                checker=self.name,
                path="tools/gubguard/lockcomplete.py", line=1,
                message=(
                    f"stale lock waiver '{key}' matches no construction "
                    "site — remove it (a renamed lock must not keep a "
                    "dangling hall pass)"
                ),
                severity="warning",
            )
            for key in stale
        ]
