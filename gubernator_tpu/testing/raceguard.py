"""raceguard: runtime lock-order + event-loop-stall detection for tests.

The static half (tools/gubguard) proves the LEXICAL lock nesting is
consistent; this pytest plugin catches what static analysis cannot — a
callee taking a lock while its caller holds another, across await
points, on the real asyncio locks under the real test workloads (the
functional cluster tests drive every serving path).

Two detectors, armed for the whole pytest session:

* **lock order** — `asyncio.Lock.acquire` is wrapped to maintain a
  per-task held-set and a global acquisition graph over lock
  *instances*.  An edge A->B is recorded when B is acquired while A is
  held; a new edge that closes a cycle is an inversion — two tasks
  interleaving those paths can deadlock — and FAILS the test that
  produced it.  Lock identity includes its creation site
  (`Lock.__init__` is wrapped too), so reports point at code, not ids.

* **event-loop stalls** — `asyncio.events.Handle._run` is timed; any
  single callback over ``GUBGUARD_STALL_MS`` (default 50) is recorded.
  One stray host fetch on the loop blocks every request behind a
  device sync, so stalls are the runtime shadow of the host-sync checker.
  Stalls are reported in the terminal summary (not failed: CI timing
  jitter would flap) — treat a growing stall list as a regression.

Arming: the plugin registers via ``pytest_plugins`` in tests/conftest.py
and is on by default; set ``GUBGUARD_RACE=0`` to disarm.
"""
from __future__ import annotations

import asyncio
import itertools
import os
import time
import traceback
from typing import Dict, List, Optional, Set, Tuple

import pytest

_STALL_MS_ENV = "GUBGUARD_STALL_MS"
_DISARM_ENV = "GUBGUARD_RACE"


class LockOrderGraph:
    """Acquisition-order graph over lock instances with incremental
    cycle detection.  Pure data structure — unit-testable without
    patching anything."""

    def __init__(self) -> None:
        self.edges: Dict[int, Set[int]] = {}
        self.labels: Dict[int, str] = {}
        self.inversions: List[str] = []

    def label(self, lock_id: int, label: str) -> None:
        self.labels[lock_id] = label

    def _name(self, lock_id: int) -> str:
        return self.labels.get(lock_id, f"<lock {lock_id:#x}>")

    def record(self, held_id: int, acquired_id: int, context: str = "") -> bool:
        """Record edge held->acquired; returns True (and logs an
        inversion) if the edge closes a cycle."""
        if held_id == acquired_id:
            return False
        succ = self.edges.setdefault(held_id, set())
        if acquired_id in succ:
            return False
        if self._reaches(acquired_id, held_id):
            path = self._path(acquired_id, held_id) or [
                acquired_id, held_id
            ]
            cycle = " -> ".join(self._name(n) for n in path + [acquired_id])
            self.inversions.append(
                f"lock-order inversion: acquiring {self._name(acquired_id)} "
                f"while holding {self._name(held_id)}, but the reverse "
                f"order exists: {cycle}"
                + (f"\n  at: {context}" if context else "")
            )
            succ.add(acquired_id)  # record anyway; report once
            return True
        succ.add(acquired_id)
        return False

    def _reaches(self, src: int, dst: int) -> bool:
        seen: Set[int] = set()
        stack = [src]
        while stack:
            n = stack.pop()
            if n == dst:
                return True
            if n in seen:
                continue
            seen.add(n)
            stack.extend(self.edges.get(n, ()))
        return False

    def _path(self, src: int, dst: int) -> Optional[List[int]]:
        stack: List[Tuple[int, List[int]]] = [(src, [src])]
        seen: Set[int] = set()
        while stack:
            n, path = stack.pop()
            if n == dst:
                return path
            if n in seen:
                continue
            seen.add(n)
            for m in self.edges.get(n, ()):
                stack.append((m, path + [m]))
        return None


class RaceGuard:
    """The armed detector: asyncio.Lock + Handle patches and their
    recorded evidence."""

    def __init__(self, stall_ms: float = 50.0) -> None:
        self.graph = LockOrderGraph()
        self.stall_ms = stall_ms
        self.stalls: List[str] = []
        self.max_stall_ms = 0.0
        # task id -> stack of held lock tokens (a task dies with its
        # locks released through our release wrapper, so no weakrefs
        # needed).
        self._held: Dict[int, List[int]] = {}
        # Lock identity: a monotonic token stamped at creation.  id()
        # would be recycled after gc and chain edges across unrelated
        # locks — a false-inversion source.
        self._tokens = itertools.count(1)
        self._armed = False
        self._saved: Dict[str, object] = {}

    def _token(self, lock) -> int:
        tok = getattr(lock, "_raceguard_token", None)
        if tok is None:
            # Lock created before arming: stamp lazily (the object is
            # alive right now, so the token is unique from here on).
            tok = next(self._tokens)
            try:
                lock._raceguard_token = tok
            except AttributeError:
                return id(lock)
        return tok

    # -- arming ----------------------------------------------------------
    def arm(self) -> None:
        if self._armed:
            return
        self._armed = True
        guard = self

        self._saved["lock_init"] = asyncio.Lock.__init__
        self._saved["lock_acquire"] = asyncio.Lock.acquire
        self._saved["lock_release"] = asyncio.Lock.release
        self._saved["handle_run"] = asyncio.events.Handle._run

        lock_init = asyncio.Lock.__init__
        lock_acquire = asyncio.Lock.acquire
        lock_release = asyncio.Lock.release
        handle_run = asyncio.events.Handle._run

        def init(self, *a, **kw):
            lock_init(self, *a, **kw)
            guard.graph.label(guard._token(self), _creation_site())

        async def acquire(self):
            task = asyncio.current_task()
            tid = id(task)
            tok = guard._token(self)
            held = guard._held.get(tid)
            if held:
                ctx = _call_site()
                for h in held:
                    guard.graph.record(h, tok, ctx)
            ok = await lock_acquire(self)
            guard._held.setdefault(tid, []).append(tok)
            return ok

        def release(self):
            task = asyncio.current_task()
            tok = guard._token(self)
            held = guard._held.get(id(task))
            if held and tok in held:
                held.remove(tok)
                if not held:
                    guard._held.pop(id(task), None)
            return lock_release(self)

        def timed_run(self):
            t0 = time.perf_counter()
            try:
                return handle_run(self)
            finally:
                dt_ms = (time.perf_counter() - t0) * 1e3
                if dt_ms > guard.stall_ms:
                    guard.max_stall_ms = max(guard.max_stall_ms, dt_ms)
                    if len(guard.stalls) < 50:
                        guard.stalls.append(
                            f"{dt_ms:.1f}ms in {self!r}"
                        )

        asyncio.Lock.__init__ = init  # type: ignore[method-assign]
        asyncio.Lock.acquire = acquire  # type: ignore[method-assign]
        asyncio.Lock.release = release  # type: ignore[method-assign]
        asyncio.events.Handle._run = timed_run  # type: ignore[method-assign]

    def disarm(self) -> None:
        if not self._armed:
            return
        asyncio.Lock.__init__ = self._saved["lock_init"]  # type: ignore
        asyncio.Lock.acquire = self._saved["lock_acquire"]  # type: ignore
        asyncio.Lock.release = self._saved["lock_release"]  # type: ignore
        asyncio.events.Handle._run = self._saved["handle_run"]  # type: ignore
        self._armed = False


def _creation_site() -> str:
    for frame in reversed(traceback.extract_stack(limit=8)[:-2]):
        if "raceguard" not in frame.filename and "asyncio" not in (
            frame.filename
        ):
            return f"Lock({frame.filename}:{frame.lineno})"
    return "Lock(?)"


def _call_site() -> str:
    for frame in reversed(traceback.extract_stack(limit=8)[:-2]):
        if "raceguard" not in frame.filename and "asyncio" not in (
            frame.filename
        ):
            return f"{frame.filename}:{frame.lineno}"
    return "?"


_guard: Optional[RaceGuard] = None


def active_guard() -> Optional[RaceGuard]:
    return _guard


# -- pytest hooks --------------------------------------------------------
def pytest_configure(config) -> None:
    global _guard
    if os.environ.get(_DISARM_ENV, "1") == "0":
        return
    _guard = RaceGuard(
        stall_ms=float(os.environ.get(_STALL_MS_ENV, "50"))
    )
    _guard.arm()


def pytest_unconfigure(config) -> None:
    global _guard
    if _guard is not None:
        _guard.disarm()
        _guard = None


@pytest.hookimpl(wrapper=True)
def pytest_runtest_makereport(item, call):
    report = yield
    if _guard is None or call.when != "call":
        return report
    count = getattr(item, "_raceguard_seen", 0)
    new = _guard.graph.inversions[count:]
    item._raceguard_seen = len(_guard.graph.inversions)
    if new:
        report.outcome = "failed"
        report.longrepr = (
            "raceguard detected lock-order inversion(s) during this "
            "test:\n" + "\n".join(new)
        )
    return report


def pytest_runtest_setup(item) -> None:
    # Snapshot BEFORE the test body so fixture-time inversions count too.
    if _guard is not None and not hasattr(item, "_raceguard_seen"):
        item._raceguard_seen = len(_guard.graph.inversions)


def pytest_terminal_summary(terminalreporter) -> None:
    if _guard is None:
        return
    tr = terminalreporter
    n_edges = sum(len(v) for v in _guard.graph.edges.values())
    tr.write_sep("-", "raceguard")
    tr.write_line(
        f"raceguard: {n_edges} lock-order edge(s) observed, "
        f"{len(_guard.graph.inversions)} inversion(s), "
        f"{len(_guard.stalls)} event-loop stall(s) "
        f"> {_guard.stall_ms:.0f}ms"
        + (
            f" (max {_guard.max_stall_ms:.0f}ms)"
            if _guard.stalls else ""
        )
    )
    for s in _guard.stalls[:10]:
        tr.write_line(f"  stall: {s}")
    for inv in _guard.graph.inversions:
        tr.write_line(f"  {inv}")
