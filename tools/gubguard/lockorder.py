"""lock-order: one global lock acquisition order, machine-checked.

The discipline documented at parallel/global_sync.py ("Lock order
everywhere: auth (backend) before cache (self)") generalizes to a single
global ranking; any two code paths that nest the same pair of locks in
opposite orders can deadlock under concurrency (the classic inversion a
race detector exists to catch).

The checker extracts every lexically nested acquisition site —
`with a._lock, b._lock:` items and `with` statements nested inside other
`with` statements, sync or async — canonicalizes each lock expression to
a lock CLASS, then verifies:

  1. no pair of lock classes is acquired in both orders anywhere;
  2. the merged acquisition graph is acyclic;
  3. edges between RANKED locks respect the declared global order:
       backend._keymap_lock < backend._lock < engine._lock
                            < sketch._lock  < store._lock
  4. no nested re-acquisition of the same (non-reentrant) lock class.

Canonicalization: `self._lock` resolves through the enclosing class
(DeviceBackend/MeshBackend -> backend._lock, GlobalEngine ->
engine._lock, ...); `self.b._lock` / `backend._lock` resolve through the
receiver variable name.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from tools.gubguard.core import Checker, Finding, ModuleInfo, dotted_name

# (enclosing class, attribute) -> canonical lock class
CLASS_LOCK_MAP = {
    ("PersistenceHost", "_lock"): "backend._lock",
    ("DeviceBackend", "_lock"): "backend._lock",
    ("MeshBackend", "_lock"): "backend._lock",
    ("PersistenceHost", "_keymap_lock"): "backend._keymap_lock",
    ("DeviceBackend", "_keymap_lock"): "backend._keymap_lock",
    ("MeshBackend", "_keymap_lock"): "backend._keymap_lock",
    ("GlobalEngine", "_lock"): "engine._lock",
    ("SketchBackend", "_lock"): "sketch._lock",
    ("Store", "_lock"): "store._lock",
    ("MockStore", "_lock"): "store._lock",
    ("HotKeyTracker", "_lock"): "hotkey._lock",
    ("LeaseManager", "_lock"): "lease._lock",
    ("_LeaseTable", "_lock"): "lease.client._lock",
    ("ReshardManager", "_lock"): "reshard._lock",
    ("RegionManager", "_lock"): "multiregion._lock",
    ("ColdTier", "_lock"): "coldtier._lock",
    ("TenantAccounting", "_lock"): "gubstat._lock",
    ("HdrRecorder", "_lock"): "loadgen.hdr._lock",
    ("FlightRecorder", "_lock"): "flightrec._lock",
    ("_TraceState", "_lock"): "tracing._lock",
    ("MemorySpanExporter", "_lock"): "tracing.exporter._lock",
    ("StageLedger", "_lock"): "tracing.stages._lock",
    ("SketchBackend", "_compile_lock"): "sketch._compile_lock",
    ("SketchBackend", "_spill_lock"): "sketch._spill_lock",
    ("Clock", "_lock"): "clock._lock",
    ("Daemon", "_set_peers_lock"): "daemon._set_peers_lock",
    ("Service", "_peer_lock"): "service._peer_lock",
    ("PeerClient", "_connect_lock"): "peer_client._connect_lock",
}
# receiver variable name -> canonical prefix
VAR_ALIAS = {
    "b": "backend",
    "backend": "backend",
    "be": "backend",
    "engine": "engine",
    "eng": "engine",
    "sketch": "sketch",
    "sb": "sketch",
    "store": "store",
    "hotkeys": "hotkey",
    "hk": "hotkey",
    "leases": "lease",
    "lm": "lease",
    "flightrec": "flightrec",
    "fr": "flightrec",
    "tenants": "gubstat",
    "ta": "gubstat",
    "cold": "coldtier",
    "coldtier": "coldtier",
    "ct": "coldtier",
    "regions": "multiregion",
    "rm": "multiregion",
}
# Declared global acquisition order (lower rank acquired first).
# flightrec._lock ranks LAST: any layer may record into the flight
# recorder while holding its own lock (e.g. under backend._lock in a
# drain), and the recorder never takes another lock while holding its own.
#
# The fast lane's pipelined-drain stage slots (_Coalescer._dispatch_sem /
# _fetch / _overlap, runtime/fastpath.py) are asyncio SEMAPHORES acquired
# on the event loop, ranked BEFORE every thread lock here: a drain takes
# fetch slot -> dispatch slot -> (on a pool thread) backend._lock, and
# nothing acquires a stage slot while holding a thread lock.  They are
# declared for the record; the lexical checker only sees `with` blocks
# over *_lock attributes, and raceguard's runtime graph covers
# asyncio.Lock — a future conversion of these slots to locks must keep
# this order.
RANK = {
    "coalescer._fetch_slot": 1,
    "coalescer._dispatch_slot": 2,
    # The event-loop asyncio.Locks rank with the coalescer slots,
    # BEFORE every thread lock: each is acquired on the loop while
    # holding no thread lock, and any thread lock taken inside runs on
    # a pool thread or in a short critical section entered afterwards.
    # set_peers flows Daemon -> Service, so the daemon's lock ranks
    # first; the peer-client connect gate is a leaf among them.
    "daemon._set_peers_lock": 3,
    "service._peer_lock": 4,
    "peer_client._connect_lock": 5,
    "backend._keymap_lock": 10,
    "backend._lock": 20,
    "engine._lock": 30,
    # sketch._compile_lock serializes first-compile of a new batch
    # shape against a throwaway state, deliberately OUTSIDE the
    # dispatch lock (sketch._lock) — callers fetch the compiled step
    # before taking _lock, so compile ranks before dispatch.
    "sketch._compile_lock": 39,
    "sketch._lock": 40,
    # sketch._spill_lock guards the dynamic-name spillover set; taken
    # alone from the pressure-report path, never nested with dispatch.
    "sketch._spill_lock": 41,
    "store._lock": 50,
    # coldtier._lock (runtime/coldtier.py cold-store rows + member
    # set) is a leaf taken alone: the request path's note_access probes
    # membership holding nothing, the tier worker's put/pop run between
    # (never across) device dispatches, and the store takes no other
    # lock while held.  Ranked before the routing-plane tails so a
    # future caller holding it cannot legally take backend/engine locks.
    "coldtier._lock": 54,
    # hotkey._lock (runtime/hotkey.py window/hot-set state) is acquired
    # from routing paths holding nothing and takes nothing while held
    # (pressure_fn reads lock-free peer/flightrec attrs; flight-recorder
    # records fire after release) — ranked just before the
    # record-anything tail locks.
    "hotkey._lock": 55,
    # lease._lock (runtime/lease.py holder dicts) sits with hotkey: it
    # is taken from grant/reconcile paths holding nothing, guards only
    # dict state, and is NEVER held across an await or device work (the
    # carve rides _check_local outside it).  The client-side twin
    # (lease.client._lock, client._LeaseTable) has the same contract.
    "lease._lock": 56,
    "lease.client._lock": 57,
    # reshard._lock (runtime/reshard.py handoff dicts) follows the
    # lease contract exactly: taken from remap/handoff paths holding
    # nothing, guards only dict state, never held across an await or
    # any device work (extraction/injection ride the device executor
    # outside it).
    "reshard._lock": 58,
    # multiregion._lock (runtime/multiregion.py burn ledger / carve
    # reset memory / drift counter) follows the reshard contract:
    # taken from the serve/flush/cutover paths and the gubstat census
    # (carve_slot_keys) holding nothing, never held across an await or
    # device work (carve checks ride _check_local outside it), and
    # takes nothing while held (drift gauge updates happen after
    # release).
    "multiregion._lock": 58.5,
    # gubstat._lock (runtime/gubstat.py tenant ledger) is a leaf: taken
    # from the _check_local tail (event loop) and fast-lane fetch
    # threads while holding nothing, guards only dict/CMS state, and
    # takes nothing while held (name decode closures touch no locks).
    "gubstat._lock": 59,
    "flightrec._lock": 60,
    # loadgen.hdr._lock (runtime/metrics.py HdrRecorder bucket counts)
    # is a leaf: record()/percentile()/merge() guard only the counts
    # dict and take nothing while held — merge() snapshots the OTHER
    # recorder's counts under its lock FIRST, releases, then takes its
    # own, so two merges never hold both locks at once.
    "loadgen.hdr._lock": 62,
    # tracing._lock (runtime/tracing.py counters/recent ring) ranks with
    # flightrec: span bookkeeping may run under ANY layer's lock (a span
    # ends inside a locked merge), and the tracing plane never takes
    # another lock while holding its own (exports run outside it).
    "tracing._lock": 70,
    "tracing.exporter._lock": 71,
    # tracing.stages._lock (runtime/tracing.py StageLedger rows) is a
    # leaf like the two above: a stage may end under ANY layer's lock
    # (backend.dispatch ends inside backend._lock), the critical section
    # is the row's integer adds, and the stalls and observers are fed
    # after release.  The garbage collector's row and the stalls' store
    # are NOT under it: a collection can start inside it, so host.gc and
    # host.stall are the process's cells, written without a lock.
    "tracing.stages._lock": 72,
    # clock._lock (core/clock.py frozen-time guard) ranks dead last:
    # now_ns() may be called under ANY other lock (timestamps are
    # taken everywhere), the critical section is two loads, and the
    # clock takes nothing while held.
    "clock._lock": 80,
}

Site = Tuple[str, int]  # (relpath, line)


def _is_lockish(attr: str) -> bool:
    return attr == "lock" or attr.endswith("_lock") or attr.endswith("lock_")


class _LockVisitor(ast.NodeVisitor):
    def __init__(self, checker: "LockOrderChecker", mod: ModuleInfo) -> None:
        self.checker = checker
        self.mod = mod
        self.class_stack: List[str] = []
        self.held: List[Tuple[str, int]] = []  # (canonical, line)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # A new function body starts with no lexically held locks (a
        # callee acquiring under a caller's lock is runtime raceguard's
        # job, not a lexical fact).
        saved, self.held = self.held, []
        self.generic_visit(node)
        self.held = saved

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def _canonical(self, expr: ast.AST) -> Optional[str]:
        dn = dotted_name(expr)
        if dn is None:
            return None
        parts = dn.split(".")
        attr = parts[-1]
        if not _is_lockish(attr):
            return None
        recv = parts[:-1]
        if recv == ["self"] or not recv:
            cls = self.class_stack[-1] if self.class_stack else "<module>"
            return CLASS_LOCK_MAP.get((cls, attr), f"{cls}.{attr}")
        base = recv[-1] if recv[-1] != "self" else (
            recv[-2] if len(recv) > 1 else "self"
        )
        if recv[0] == "self" and len(recv) > 1:
            base = recv[1]
        prefix = VAR_ALIAS.get(base, base)
        return CLASS_LOCK_MAP.get((prefix, attr), f"{prefix}.{attr}")

    def _visit_with(self, node) -> None:
        acquired: List[Tuple[str, int]] = []
        for item in node.items:
            canon = self._canonical(item.context_expr)
            if canon is None:
                continue
            if self.mod.suppressed(node.lineno, self.checker.name):
                continue
            site: Site = (self.mod.relpath, node.lineno)
            for held, _hl in self.held + acquired:
                self.checker.record_edge(held, canon, site)
            acquired.append((canon, node.lineno))
        self.held.extend(acquired)
        for child in node.body:
            self.visit(child)
        if acquired:
            del self.held[-len(acquired):]

    visit_With = _visit_with
    visit_AsyncWith = _visit_with


class LockOrderChecker(Checker):
    name = "lock-order"

    def __init__(self) -> None:
        # (held, acquired) -> first observed site
        self.edges: Dict[Tuple[str, str], Site] = {}

    def record_edge(self, held: str, acquired: str, site: Site) -> None:
        self.edges.setdefault((held, acquired), site)

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        _LockVisitor(self, mod).visit(mod.tree)
        return ()

    def finalize(self, root: Path) -> Iterable[Finding]:
        out: List[Finding] = []
        for (a, b), (path, line) in sorted(self.edges.items()):
            if a == b:
                out.append(Finding(
                    checker=self.name, path=path, line=line,
                    message=(
                        f"nested re-acquisition of '{a}' — "
                        "deadlock on a non-reentrant lock"
                    ),
                ))
                continue
            if (b, a) in self.edges:
                op, ol = self.edges[(b, a)]
                out.append(Finding(
                    checker=self.name, path=path, line=line,
                    message=(
                        f"lock-order inversion: '{a}' -> '{b}' here but "
                        f"'{b}' -> '{a}' at {op}:{ol}"
                    ),
                ))
            ra, rb = RANK.get(a), RANK.get(b)
            if ra is not None and rb is not None and ra > rb:
                out.append(Finding(
                    checker=self.name, path=path, line=line,
                    message=(
                        f"'{a}' acquired before '{b}' violates the "
                        "declared global order (see docs/invariants.md): "
                        + " < ".join(sorted(RANK, key=RANK.get))
                    ),
                ))
        out.extend(self._cycles())
        return out

    def _cycles(self) -> List[Finding]:
        graph: Dict[str, List[str]] = {}
        for (a, b) in self.edges:
            if a != b and (b, a) not in self.edges:
                graph.setdefault(a, []).append(b)
        # Iterative DFS cycle detection (2-cycles already reported above).
        WHITE, GREY, BLACK = 0, 1, 2
        color = {n: WHITE for n in graph}
        out: List[Finding] = []

        def dfs(start: str) -> Optional[List[str]]:
            stack: List[Tuple[str, Iterable[str]]] = [
                (start, iter(graph.get(start, ())))
            ]
            path = [start]
            color[start] = GREY
            while stack:
                node, it = stack[-1]
                nxt = next(it, None)
                if nxt is None:
                    color[node] = BLACK
                    stack.pop()
                    path.pop()
                    continue
                c = color.get(nxt, WHITE)
                if c == GREY:
                    return path[path.index(nxt):] + [nxt]
                if c == WHITE:
                    color[nxt] = GREY
                    path.append(nxt)
                    stack.append((nxt, iter(graph.get(nxt, ()))))
            return None

        for n in list(graph):
            if color.get(n, WHITE) == WHITE:
                cyc = dfs(n)
                if cyc:
                    site = self.edges.get((cyc[0], cyc[1]), ("<graph>", 0))
                    out.append(Finding(
                        checker=self.name, path=site[0], line=site[1],
                        message=(
                            "lock acquisition cycle: "
                            + " -> ".join(cyc)
                        ),
                    ))
                    break
        return out
