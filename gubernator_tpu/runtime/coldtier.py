"""Guberberg — the host-RAM cold tier under the HBM slot table.

The device table (ops/state.py) holds the HOT working set; this module
holds everybody else.  Two pieces:

* ``ColdTier`` — an open-addressed, linear-probed hash table over
  columnar numpy arrays in the ``MigratedRows`` field layout
  (proto/peers.proto), keyed by the int64 key fingerprint.  The same
  column set the reshard wire and the checkpoint payload already use,
  so serialization of the cold tier is a slice, not a format.

* ``TierManager`` — the residency policy, on two threads of its own.
  Demotion pressure comes from the occupancy watermark knobs (high/low
  water): when a tick finds the table over the high water mark it
  demotes what brings occupancy back to the low mark (hysteresis — no
  demotion starts below high water), in launches sized from that need.
  The device picks candidates by pseudo-LRU (``demote_extract``'s
  last-touch cut-off); the manager's own HostCMS then ranks the
  extracted candidates by estimated frequency and sends only the
  provably-coldest to the cold tier, re-injecting the rest.  Promotion
  is access-driven: the request path calls ``note_access`` with each
  batch on its way to be served; a fingerprint that hits the cold tier
  is queued, and the promote worker takes everything queued in one
  pass — one pop, launches of the size they carry — through the
  ``migrate_inject`` merge path: the request that observed the miss is
  served from a fresh row, a later one sees the merged history.  The
  inject retries once and on repeated failure the row goes back to the
  cold tier, so counters are conserved in every outcome.

Correctness bound (docs/tiering.md): a cold-resident key served
before its promote lands is admitted from a fresh row, so each
demote/promote cycle widens admission by at most one limit-window —
``migrate_inject`` merges by subtracting the consumed budget, clamped
at zero, the same algebra the reshard/mirror/lease planes prove.

Locking: ``coldtier._lock`` ranks BELOW every request-path lock
(tools/gubguard/lockorder.py rank 54) — it is only ever taken alone,
never across device work, and the request path's only use is the
one probe of a batch in ``note_access``.  ``TierManager._cv`` guards
the promote queue, the pending set and the noted batches; it is taken
alone too.

Protocol spec: tools/gubproof/specs/tier.json — residency moves are
tracked by their ColdTier calls (put_rows / pop_rows / prune_expired);
each call site must map to a declared hot/cold/dropped edge and the
explorer reproduces the per-cycle admission bound exactly.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from gubernator_tpu import native

log = logging.getLogger("gubernator.coldtier")

# Columnar field set — the MigratedRows wire layout (proto/peers.proto)
# and ops/step.BucketRows' field names, so cold rows flow verbatim into
# migrate_inject and out of demote_extract.
COLD_FIELDS: Tuple[str, ...] = (
    "key_hash", "algo", "limit", "duration", "remaining",
    "remaining_f", "t0", "status", "burst", "expire_at",
)

_DTYPES: Dict[str, np.dtype] = {
    "key_hash": np.dtype(np.int64),
    "algo": np.dtype(np.int32),
    "limit": np.dtype(np.int64),
    "duration": np.dtype(np.int64),
    "remaining": np.dtype(np.int64),
    "remaining_f": np.dtype(np.float64),
    "t0": np.dtype(np.int64),
    "status": np.dtype(np.int32),
    "burst": np.dtype(np.int64),
    "expire_at": np.dtype(np.int64),
}

_EMPTY, _FULL, _TOMB = 0, 1, 2

# A row of the store: one int64 word a field, in COLD_FIELDS order (the
# int32 fields widened, `remaining_f` as its binary64's bits), so that a
# row is 80 contiguous bytes and a put or a pop touches one place.
_W = len(COLD_FIELDS)
_KEY, _ALGO, _LIMIT, _REM, _REM_F, _EXPIRE = (
    COLD_FIELDS.index(f) for f in (
        "key_hash", "algo", "limit", "remaining", "remaining_f",
        "expire_at",
    )
)


def _to_rows(cols: Dict[str, np.ndarray]) -> np.ndarray:
    """COLD_FIELDS columns -> int64[n, _W] rows."""
    n = len(cols["key_hash"])
    rows = np.empty((n, _W), dtype=np.int64)
    for j, f in enumerate(COLD_FIELDS):
        col = np.asarray(cols[f], dtype=_DTYPES[f])
        rows[:, j] = col.view(np.int64) if f == "remaining_f" else col
    return rows


def _to_cols(rows: np.ndarray) -> Dict[str, np.ndarray]:
    """int64[n, _W] rows -> COLD_FIELDS columns in their own dtypes."""
    return {
        f: (rows[:, j].copy().view(np.float64) if f == "remaining_f"
            else rows[:, j].astype(_DTYPES[f]))
        for j, f in enumerate(COLD_FIELDS)
    }


def _merge_rows(new: np.ndarray, old: np.ndarray) -> np.ndarray:
    """`merge_cold` on rows."""
    leaky = old[:, _ALGO] == 1
    used_i = np.maximum(old[:, _LIMIT] - old[:, _REM], 0)
    used_f = np.maximum(
        old[:, _LIMIT].astype(np.float64)
        - old[:, _REM_F].copy().view(np.float64), 0.0
    )
    out = new.copy()
    out[:, _REM] = np.maximum(
        new[:, _REM] - np.where(leaky, 0, used_i), 0
    )
    out[:, _REM_F] = np.maximum(
        new[:, _REM_F].copy().view(np.float64)
        - np.where(leaky, used_f, 0.0), 0.0
    ).view(np.int64)
    return out


def merge_cold(new: Dict[str, np.ndarray],
               old: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """A row that meets the cold row of its own key still waiting for
    its merge: the row kept is `new` (the fresher one: the table's own
    row, with its status and stamps) at the LEAST budget, `max(r_new -
    consumed_old, 0)` — what `migrate_inject` would have left had the
    waiting row been promoted first (ops/state.py `migrate_inject_impl`,
    term for term: a token row's integer `remaining`, a leaky row's
    float `remaining_f`).  Overwriting instead would mint the waiting
    row's consumed budget (PERF.md section 7, PR 45 (4))."""
    return _to_cols(_merge_rows(_to_rows(new), _to_rows(old)))


class ColdTier:
    """Open-addressed cold store: linear probing over power-of-two
    capacity, a row of `_W` words and a state byte per slot (empty /
    full / tombstone).  No turn of the interpreter per key: a put, a
    pop and a probe are ONE native pass each with the GIL released
    (`native.cold_put` / `cold_pop` / `cold_probe`, native/gubtpu.cpp),
    and where the library did not load, array operations over the batch
    (`_put_numpy` / `_pop_numpy` / `_probe_numpy`: a probe advances all
    its lanes one slot a step, an insert settles one claimant a free
    slot a step) — the reference the native passes are held to.
    Membership is read from the table itself.

    Fixed capacity by design — host RAM is budgeted up front
    (``GUBER_TIER_COLD_CAPACITY``), and an insert into a full table is
    DROPPED and counted (``capacity_drops``), never grown: dropping a
    cold row only costs the bounded over-admission window the tier
    already documents, while unbounded growth would turn a keyspace
    storm into an OOM."""

    def __init__(self, capacity: int, stages: Optional[Any] = None) -> None:
        if capacity < 1:
            raise ValueError(
                f"cold tier capacity must be >= 1, got {capacity}"
            )
        # Probe math wants a power of two; size for the requested
        # residency at <= ~0.8 load so probes stay short.
        cap = 8
        while cap * 8 < capacity * 10:
            cap *= 2
        self.capacity = int(capacity)
        self._stages = stages  # the daemon's ledger: `tier.restore`
        self.native = native.available()  # False: the numpy forms
        self._cap = cap
        self._mask = cap - 1
        self._lock = threading.Lock()  # coldtier._lock, gubguard rank 54
        self.rows = np.zeros((cap, _W), dtype=np.int64)
        self._state = np.zeros(cap, dtype=np.uint8)
        self._n = 0
        self._tombstones = 0
        self.capacity_drops = 0
        self.cold_merges = 0

    # -- probe ---------------------------------------------------------
    def _home(self, fps: np.ndarray) -> np.ndarray:
        return (fps.view(np.uint64) & np.uint64(self._mask)).astype(
            np.int64
        )

    def _probe(self, fps: np.ndarray) -> np.ndarray:
        """int64[n]: the slot holding each fingerprint, -1 where it is
        not resident (0 is the empty sentinel: never resident)."""
        if not self._n:
            return np.full(len(fps), -1, dtype=np.int64)
        if self.native:
            return native.cold_probe(
                fps, self.rows, self._state, self._mask
            )
        return self._probe_numpy(fps)

    def _probe_numpy(self, fps: np.ndarray) -> np.ndarray:
        """Every lane walks its probe chain at once, past tombstones,
        to its key or to the first empty slot."""
        slot = np.full(len(fps), -1, dtype=np.int64)
        live = np.flatnonzero(fps != 0)
        want = fps[live]
        pos = self._home(want)
        key = self.rows[:, _KEY]
        for _ in range(self._cap):
            if not len(live):
                break
            st = self._state[pos]
            hit = (st == _FULL) & (key[pos] == want)
            slot[live[hit]] = pos[hit]
            go = (st != _EMPTY) & ~hit
            live, want = live[go], want[go]
            pos = (pos[go] + 1) & self._mask
        return slot

    def _insert(self, rows: np.ndarray) -> None:
        """Rows whose keys are absent, pairwise different and within
        the budget: each takes the first slot of its chain that is not
        full (a tombstone is reused), one claimant a slot a step."""
        pend = np.arange(len(rows))
        pos = self._home(np.ascontiguousarray(rows[:, _KEY]))
        while len(pend):
            free = np.flatnonzero(self._state[pos] != _FULL)
            slots, first = np.unique(pos[free], return_index=True)
            won = free[first]
            self._tombstones -= int(
                (self._state[slots] == _TOMB).sum()
            )
            self.rows[slots] = rows[pend[won]]
            self._state[slots] = _FULL
            keep = np.ones(len(pend), dtype=bool)
            keep[won] = False
            pend = pend[keep]
            pos = (pos[keep] + 1) & self._mask
        self._n += len(rows)

    def _vacate(self, slots: np.ndarray) -> None:
        """Slots whose rows left: a tombstone, so that later probe
        chains still pass through — or empty again where the next slot
        is empty, and no chain goes on from here."""
        ends = self._state[(slots + 1) & self._mask] == _EMPTY
        self._state[slots] = np.where(ends, _EMPTY, _TOMB)
        self._left(len(slots), int(len(slots) - ends.sum()))

    def _left(self, rows: int, tombstones: int) -> None:
        """`rows` left the store, `tombstones` of their slots stayed on
        their chains; past a quarter of the table they are compacted."""
        self._n -= rows
        self._tombstones += tombstones
        if self._tombstones > self._cap // 4:
            self._rebuild()

    def _rebuild(self) -> None:
        """Compact in place: re-insert live rows, dropping tombstones
        (probe chains shorten back to their no-deletion length)."""
        old = self.rows[self._state == _FULL]
        self.rows = np.zeros((self._cap, _W), dtype=np.int64)
        self._state[:] = _EMPTY
        self._tombstones = 0
        self._n = 0
        self._put(old)

    # -- bulk row traffic ---------------------------------------------
    def put_rows(self, cols: Dict[str, np.ndarray]) -> int:
        """Insert a batch of columnar rows (COLD_FIELDS layout;
        key_hash 0 lanes are padding and skipped); a row whose key is
        already cold MERGES into the waiting row (`merge_cold`: the
        least budget) and never overwrites it.  Returns the number of
        rows resident after the call that came from this batch; rows
        that found the table full are dropped and counted."""
        rows = _to_rows(cols)
        with self._lock:
            return self._put(rows)

    def _put(self, rows: np.ndarray) -> int:
        if not self.native:
            return self._put_numpy(rows)
        put, merges, drops, reused = native.cold_put(
            rows, self.rows, self._state, self._mask,
            self.capacity - self._n,
        )
        self._n += put - merges
        self._tombstones -= reused
        self.cold_merges += merges
        self.capacity_drops += drops
        return put

    def _put_numpy(self, rows: np.ndarray) -> int:
        fps = np.ascontiguousarray(rows[:, _KEY])
        # A key once a pass; its later occurrences meet the first as
        # a waiting row, in order.
        _, first = np.unique(fps, return_index=True)
        now = np.zeros(len(fps), dtype=bool)
        now[first] = True
        now &= fps != 0
        later = ~now & (fps != 0)
        slot = (self._probe_numpy(np.where(now, fps, 0)) if self._n
                else np.full(len(fps), -1, dtype=np.int64))
        met = np.flatnonzero(slot >= 0)
        if len(met):
            at = slot[met]
            self.rows[at] = _merge_rows(rows[met], self.rows[at])
            self.cold_merges += len(met)
        new = np.flatnonzero(now & (slot < 0))
        room = max(self.capacity - self._n, 0)
        if len(new) > room:
            self.capacity_drops += len(new) - room
            new = new[:room]
        if len(new):
            self._insert(rows[new])
        put = len(met) + len(new)
        if later.any():
            put += self._put_numpy(rows[later])
        return put

    def pop_rows(self, fps, with_index: bool = False):
        """Remove and return the rows for the fingerprints that are
        resident (columnar, COLD_FIELDS layout, in the order asked;
        absent fps simply don't appear).  `with_index`: (the columns,
        which entries of `fps` they answer)."""
        fps = np.ascontiguousarray(fps, dtype=np.int64).reshape(-1)
        with self._lock:
            if not self._n:
                rows = np.zeros((0, _W), dtype=np.int64)
                which = np.zeros(0, dtype=np.int64)
            elif self.native:
                rows, which, tombs = native.cold_pop(
                    fps, self.rows, self._state, self._mask
                )
                self._left(len(which), tombs)
            else:
                rows, which = self._pop_numpy(fps)
        cols = _to_cols(rows)
        return (cols, which) if with_index else cols

    def _pop_numpy(self, fps: np.ndarray):
        slot = self._probe_numpy(fps)
        which = np.flatnonzero(slot >= 0)
        # A fingerprint asked twice leaves once.
        _, first = np.unique(slot[which], return_index=True)
        which = which[np.sort(first)]
        at = slot[which]
        rows = self.rows[at]
        self._vacate(at)
        return rows, which

    def member_hits(self, fps: np.ndarray) -> np.ndarray:
        """bool[n]: which fingerprints are cold-resident right now.
        The request path's only cold-tier touch — one probe of the
        batch under the lock, no device work."""
        fps = np.ascontiguousarray(fps, dtype=np.int64).reshape(-1)
        with self._lock:
            return self._probe(fps) >= 0

    # -- census / lifecycle -------------------------------------------
    def residents(self) -> int:
        with self._lock:
            return self._n

    def prune_expired(self, now_ms: int) -> int:
        """Drop rows whose window already expired — a demoted bucket
        whose TTL lapsed carries no admission state worth promoting."""
        with self._lock:
            dead = np.flatnonzero(
                (self._state == _FULL)
                & (self.rows[:, _EXPIRE] <= np.int64(now_ms))
            )
            if len(dead):
                self._vacate(dead)
            return int(len(dead))

    def snapshot(self) -> Dict[str, np.ndarray]:
        """Compacted columnar copy of every resident row — the
        checkpoint payload's `coldtier` entry (COLD_FIELDS layout, so
        restore is geometry-independent re-insertion)."""
        with self._lock:
            return _to_cols(self.rows[self._state == _FULL])

    def restore(self, arrays: Dict[str, np.ndarray]) -> int:
        """Re-insert a snapshot's rows (capacity may differ from the
        saving daemon's — rows beyond the new budget are dropped and
        counted, same rule as live inserts)."""
        if self._stages is None:
            return self.put_rows(arrays)
        with self._stages.stage("tier.restore", "tier") as st:
            kept = self.put_rows(arrays)
            st.tally(rows=kept)
        return kept


# Lane `tier` of the stage ledger (runtime/tracing.py): the rows and
# the counters a TierManager creates at zero when it is built.
TIER_STAGES = (
    "tier.note_access", "tier.promote", "tier.demote", "tier.lock",
    "tier.restore",
)
TIER_COUNTERS = {
    "tier.note_access": ("keys", "cold_hits"),
    "tier.promote": (
        "rows_popped", "rows_injected", "rows_merged", "inject_launches",
        "inject_lanes", "pop_us", "dispatch_us", "fetch_us",
    ),
    "tier.demote": (
        "demote_rows", "demote_launches", "demote_lanes", "reinjected",
        "renoted", "cold_merges", "ticks_late", "select_us", "put_us",
    ),
    "tier.restore": ("rows",),
}


def demote_ladder(demote_batch: int, num_slots: int) -> Tuple[int, ...]:
    """The widths a demote launch comes in: `demote_batch`
    (GUBER_TIER_DEMOTE_BATCH) the smallest, then 16 and 256 times it
    while a rung stays within a 64th of the table — (256, 4096, 65536)
    at 2^24 slots, so that a tick's need (some 52,000 rows at 200,000
    checks a second, at most the band between the marks) rides ONE
    launch of about its size."""
    return tuple(
        demote_batch * m for m in (1, 16, 256)
        if m == 1 or demote_batch * m <= num_slots // 64
    )


class TierManager:
    """The two-tier residency policy: access-driven promotion on one
    worker thread, watermark-driven demotion on another, so that a tick
    runs on time whatever the promote queue holds.  One instance per
    daemon, armed by ``GUBER_TIER_ENABLED`` (daemon.py wires
    ``service.tier`` so the request path's ``note_traffic`` feeds it).
    """

    # A promote pass waits until a full launch is queued, or this long
    # after its oldest entry: 4,096 lanes cost the device the same
    # whether they carry 225 rows or 4,096 (PERF.md section 5.12).
    PROMOTE_LINGER_S = 0.05
    # What a launch takes beyond its need, for the sketch's second
    # opinion to send back (the hotter tail): as much again, up to this
    # many demote batches — one full inject launch at the defaults.
    SECOND_OPINION_BATCHES = 16
    # How long `note_access` remembers a batch whose caller never says
    # that it was served (`note_done`); one that does is forgotten
    # DONE_S after.  A row leaves the table a moment before it is a
    # cold row, and a key noted before that and served in between was
    # told "not cold": the demoter asks again (`_requeue_noted`).
    RECENT_S = 10.0
    DONE_S = 0.5
    # Expired cold rows are pruned one tick in this many: the scan
    # reads every slot of the store under its lock, which the request
    # path's probe waits for.
    PRUNE_TICKS = 16
    # Batches `note_access` may hand the sketch's feeder before it
    # feeds the sketch itself (a manager nobody ticks).
    UNSKETCHED_MOST = 8192

    def __init__(
        self,
        service: Any,
        cfg: Any,
        metrics: Optional[Any] = None,
    ) -> None:
        from gubernator_tpu.runtime import tracing
        from gubernator_tpu.runtime.metrics import LATENCY_BUCKETS
        from gubernator_tpu.runtime.sketch_backend import HostCMS

        self.service = service
        self.backend = service.backend
        self.cfg = cfg
        self.metrics = metrics
        self._stages = tracing.ledger_of(metrics)
        self.cold = ColdTier(cfg.cold_capacity, stages=self._stages)
        # The manager's OWN sketch: residency ranking must reflect
        # all-time-recent traffic at this node, independent of the
        # hot-key detector's tumbling windows.
        self.cms = HostCMS()
        self.promotes = 0
        self.demotes = 0
        self.cold_hits = 0
        self.promote_retries = 0
        self.promote_failures = 0
        self.demote_passes = 0
        self.ticks = 0
        self._stages.register("tier", TIER_STAGES)
        for stage, counters in TIER_COUNTERS.items():
            self._stages.declare("tier", stage, *counters)
        self._ladder = demote_ladder(
            int(cfg.demote_batch), int(self.backend.cfg.num_slots)
        )
        self._cursor = 0
        self._buckets = np.asarray(LATENCY_BUCKETS, dtype=np.float64)
        self._hist = np.zeros(len(self._buckets) + 1, dtype=np.int64)
        self._lat_sum = 0.0
        self._pending: set = set()
        self._recent: deque = deque()   # [time noted, key hashes, served]
        self._unsketched: deque = deque()   # (key hashes, hits)
        self._q: deque = deque()
        self._queued = 0
        self._cv = threading.Condition()
        self._stop = False
        self._halt = threading.Event()  # the ticker's: no queue wakes it
        self._threads: List[threading.Thread] = []

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        for name, target in (
            ("tier-promote", self._run),
            ("tier-demote", self._run_ticks),
        ):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._halt.set()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []

    # -- request-path hook (service.note_traffic) ----------------------
    def note_access(self, key_hashes: np.ndarray, hits):
        """One batch on its way to be served: hand it to the residency
        sketch's feeder, and schedule a promote for any fingerprint
        that is cold-resident.  Cheap by contract — a copy and one
        probe of the batch; the sketch is fed on the demoter's thread,
        the promote rides the worker's.  Returns the batch's entry
        among the noted ones, for `note_done`."""
        if not len(key_hashes):
            return None
        with self._stages.stage("tier.note_access", "tier") as st:
            # A copy: the batch is remembered past its caller's buffers.
            kh = np.array(key_hashes, dtype=np.int64)
            # The sketch is fed by the demoter, the one that asks it
            # (`_feed_sketch`): the request path only hands the batch
            # over.
            self._unsketched.append(
                (kh, None if hits is None else np.array(hits, np.int64))
            )
            if len(self._unsketched) > self.UNSKETCHED_MOST:
                self._feed_sketch()
            # Remembered BEFORE the probe: whichever of this probe and
            # a demotion's put comes second sees the other.
            t0 = time.monotonic()
            entry = [t0, kh, None]
            recent = self._recent
            with self._cv:
                recent.append(entry)
                while recent and (
                    recent[0][0] < t0 - self.RECENT_S
                    or (recent[0][2] or t0) < t0 - self.DONE_S
                ):
                    recent.popleft()
            hit = self.cold.member_hits(kh)
            n_hit = int(hit.sum())
            st.tally(keys=len(kh), cold_hits=n_hit)
            if n_hit:
                self._enqueue(kh[hit], t0)
                self.cold_hits += n_hit
            return entry

    @staticmethod
    def note_done(entry) -> None:
        """The batch `note_access` returned `entry` for has been
        served: its step ran before now."""
        if entry is not None:
            entry[2] = time.monotonic()

    def _feed_sketch(self) -> None:
        """The batches noted since the last call into the residency
        sketch, in one update."""
        keys, hits = [], []
        while self._unsketched:
            kh, w = self._unsketched.popleft()
            keys.append(kh)
            hits.append(np.ones(len(kh), np.int64) if w is None else w)
        if keys:
            self.cms.update(np.concatenate(keys), np.concatenate(hits))

    def _enqueue(self, fps: np.ndarray, t0: float) -> None:
        """Queue a promote for the fingerprints that wait for none."""
        fresh = set(fps.tolist())
        with self._cv:
            fresh -= self._pending
            if not fresh:
                return
            self._pending |= fresh
            self._q.append(
                (np.fromiter(fresh, dtype=np.int64, count=len(fresh)), t0)
            )
            self._queued += len(fresh)
            self._cv.notify_all()

    def _noted(self, fps: np.ndarray, since: float) -> np.ndarray:
        """bool[n]: which of `fps`, rows that left the table after
        `since`, have their key in a batch that was noted before now
        and not served before `since` — such a key may have been served
        from a fresh row in between, told "not cold".  (One served
        before the extract after all has no fresh row: its promote puts
        the row back, a wasted cycle and nothing else.)"""
        with self._cv:
            noted = [
                kh for _, kh, done in self._recent
                if done is None or done >= since
            ]
        if not noted:
            return np.zeros(len(fps), dtype=bool)
        return np.isin(fps, np.concatenate(noted))

    # -- promote worker ------------------------------------------------
    def _take_queue(self) -> Tuple[np.ndarray, np.ndarray]:
        """Everything queued, as one pass: (fingerprints, the time each
        was queued at).  Caller holds `_cv`."""
        batch = list(self._q)
        self._q.clear()
        self._queued = 0
        if not batch:
            return np.zeros(0, np.int64), np.zeros(0, np.float64)
        return (
            np.concatenate([f for f, _ in batch]),
            np.concatenate([np.full(len(f), t) for f, t in batch]),
        )

    def _run(self) -> None:
        full = int(self.backend.cfg.batch_size)
        while True:
            with self._cv:
                while not self._stop:
                    if self._q:
                        due = self._q[0][1] + self.PROMOTE_LINGER_S
                        wait = due - time.monotonic()
                        if self._queued >= full or wait <= 0:
                            break
                        self._cv.wait(timeout=wait)
                    else:
                        self._cv.wait(timeout=1.0)
                if self._stop:
                    return
                fps, t0 = self._take_queue()
            try:
                self._promote(fps, t0)
            except Exception:
                log.debug("promote failed", exc_info=True)

    # -- promote path --------------------------------------------------
    def _promote(self, fps, t0) -> int:
        """The one place where rows leave the cold store for the table:
        ONE pop of everything handed over, one `migrate_inject_dispatch`
        (launches of the size they carry), the latency of every row
        from the time ITS entry was queued (`t0`: a time, or one a
        fingerprint)."""
        fps = np.asarray(fps, dtype=np.int64).reshape(-1)
        try:
            with self._stages.stage("tier.promote", "tier") as st:
                t_a = time.perf_counter_ns()
                cols, which = self.cold.pop_rows(fps, with_index=True)
                t_b = time.perf_counter_ns()
                n = len(which)
                st.tally(rows_popped=n, pop_us=(t_b - t_a) // 1000)
                if n == 0:
                    return 0
                try:
                    self._inject(cols, st)
                except Exception:
                    # Retry ONCE; then conserve the rows back to cold.
                    self.promote_retries += 1
                    try:
                        self._inject(cols, st)
                    except Exception:
                        self.promote_failures += 1
                        self.cold.put_rows(cols)
                        raise
                self.promotes += n
                waited = time.monotonic() - np.broadcast_to(
                    np.asarray(t0, dtype=np.float64), fps.shape
                )[which]
                self._observe_latency(waited)
                return n
        finally:
            with self._cv:
                self._pending.difference_update(fps.tolist())

    def _inject(self, cols: Dict[str, np.ndarray], st) -> Tuple[int, int]:
        """One `migrate_inject_dispatch` of `cols` and its fetch, split
        for the ledger: the dispatch (the wait for `backend._lock` and
        the enqueue under it; the backend times the lock's hold as
        `tier.lock` and counts launches and lanes) and the wait for the
        device."""
        t_a = time.perf_counter_ns()
        fetch = self.backend.migrate_inject_dispatch(cols)
        t_b = time.perf_counter_ns()
        out = fetch()
        t_c = time.perf_counter_ns()
        st.tally(
            dispatch_us=(t_b - t_a) // 1000, fetch_us=(t_c - t_b) // 1000,
        )
        return out

    def drain_promotes_sync(self) -> int:
        """Synchronously promote everything queued — the test/smoke
        entry point (the daemon path drains on the worker thread)."""
        with self._cv:
            fps, t0 = self._take_queue()
        return self._promote(fps, t0) if len(fps) else 0

    # -- demote path ---------------------------------------------------
    def _run_ticks(self) -> None:
        interval = max(float(self.cfg.interval_s), 0.05)
        next_tick = time.monotonic() + interval
        while not self._halt.wait(
            timeout=max(next_tick - time.monotonic(), 0.0)
        ):
            late = time.monotonic() - next_tick > interval / 2
            next_tick = max(next_tick + interval, time.monotonic())
            try:
                self._tick(late)
            except Exception:
                # A closing backend mid-tick is expected at
                # shutdown; pressure returns next tick.
                log.debug("demote tick failed", exc_info=True)

    def _tick(self, late: bool = False) -> None:
        if self.ticks % self.PRUNE_TICKS == 0:
            self.cold.prune_expired(
                self.service.backend.clock.millisecond_now()
            )
        if late:
            self._stages.tally("tier", "tier.demote", ticks_late=1)
        self.demote_once_sync()
        self.publish()

    def _protect_grid(self) -> np.ndarray:
        """Derived-slot fingerprints (lease carves, mirrors, shadows)
        padded to a power of two >= 8 — the same recompile-tier rule as
        the gubstat shadow grid.  Derived slots never demote: they
        re-home by re-creation, not by copy."""
        fps = self.service.derived_slot_fps()
        cap = 1 << max(3, int(max(len(fps), 1) - 1).bit_length())
        grid = np.zeros(cap, dtype=np.int64)
        grid[: len(fps)] = fps
        return grid

    def demote_need(self, occ: int) -> int:
        """Watermark hysteresis as a pure function (pinned by
        tests/test_tiering.py against the pymodel oracle): no pressure
        below the high mark; above it, demote down to the LOW mark so
        occupancy oscillates between the marks instead of sawing at
        high water."""
        S = self.backend.cfg.num_slots
        high = int(self.cfg.high_water * S)
        low = int(self.cfg.low_water * S)
        if occ < high:
            return 0
        return max(occ - low, 0)

    def _next_cursor(self) -> int:
        """Where a launch takes tied stamps from: a golden-ratio walk
        of the table's blocks, so that successive launches spread over
        the buckets (ops/state.py `demote_extract_impl`)."""
        self._cursor = (self._cursor + 0x9E3779B1) & 0x7FFFFFFF
        return self._cursor

    def demote_once_sync(self) -> int:
        """One watermark evaluation: demote what `demote_need` asks, in
        launches sized from the need (`demote_ladder`), until it is met
        or the device runs out of eligible victims.  Returns rows
        demoted to cold.  (A drain spread over the following ticks, one
        launch of the widest rung a tick, was tried on the chip and not
        kept: the cell read 6 % lower and three times as unsteady;
        PERF.md section 6, PR 46.)"""
        self.ticks += 1
        self._feed_sketch()
        occ = self.backend.occupancy_dispatch()()
        need = self.demote_need(occ)
        if need <= 0:
            return 0
        total = 0
        with self._stages.stage("tier.demote", "tier") as st:
            merges0 = self.cold.cold_merges
            while need > 0:
                # The second opinion's share on top, in the smallest
                # rung that holds both (the largest, over and over,
                # where none does).
                take = need + min(
                    need,
                    self.SECOND_OPINION_BATCHES * int(self.cfg.demote_batch),
                )
                batch = next(
                    (b for b in self._ladder if b >= take),
                    self._ladder[-1],
                )
                take = min(take, batch)
                t_a = time.perf_counter_ns()
                since = time.monotonic()
                fetch = self.backend.demote_extract_dispatch(
                    self._protect_grid(), batch, take=take,
                    start=self._next_cursor(),
                )
                packed, rf = fetch()
                t_b = time.perf_counter_ns()
                self.demote_passes += 1
                sel = np.flatnonzero(packed[0] != 0)
                st.tally(demote_launches=1, demote_lanes=batch,
                         select_us=(t_b - t_a) // 1000)
                if not len(sel):
                    break
                fps = packed[0][sel]
                # The device ranked by last-touch; the sketch now ranks
                # by estimated frequency so only provably-colder rows
                # leave HBM — the hotter tail of the extract goes
                # straight back.
                order = sel[np.argsort(self.cms.estimate(fps),
                                       kind="stable")]
                ncold = min(need, len(order))
                cold_idx, tail = order[:ncold], order[ncold:]
                # A row was in neither tier from the extract until now.
                # The hotter tail goes straight back — but for keys a
                # batch in flight holds: a fresh row may stand in the
                # table, and rows merge in ONE place, `_promote`; they
                # go by way of the cold store, as the colder rows whose
                # keys were noted do.
                by_promote = self._noted(packed[0][tail], since)
                keep_idx = tail[~by_promote]
                to_cold = np.concatenate([cold_idx, tail[by_promote]])
                self.cold.put_rows(self._cols_from_packed(
                    packed, rf, to_cold
                ))
                # Asked AFTER the put: a batch noted from here on finds
                # the rows itself.
                went = packed[0][to_cold]
                ask = self._noted(went, since)
                ask[ncold:] = True
                again = went[ask]
                if len(again):
                    self._enqueue(again, time.monotonic())
                t_c = time.perf_counter_ns()
                self.demotes += int(ncold)
                st.tally(demote_rows=ncold, reinjected=len(keep_idx),
                         renoted=len(again), put_us=(t_c - t_b) // 1000)
                if len(keep_idx):
                    keep = self._cols_from_packed(packed, rf, keep_idx)
                    self.backend.migrate_inject_dispatch(keep)()
                need -= int(ncold)
                total += int(ncold)
                if len(sel) < take:
                    break       # the table has no more to give
            st.tally(cold_merges=self.cold.cold_merges - merges0)
        return total

    @staticmethod
    def _cols_from_packed(
        packed: np.ndarray, rf: np.ndarray, idx: np.ndarray
    ) -> Dict[str, np.ndarray]:
        """DEMOTE_ROW_FIELDS planes -> COLD_FIELDS columns (packed[1]
        is the kind plane — always KIND_BUCKET, the kernel's
        eligibility mask; dropped here)."""
        return {
            "key_hash": packed[0][idx],
            "algo": packed[2][idx].astype(np.int32),
            "limit": packed[3][idx],
            "duration": packed[4][idx],
            "remaining": packed[5][idx],
            "remaining_f": rf[idx],
            "t0": packed[6][idx],
            "status": packed[7][idx].astype(np.int32),
            "burst": packed[8][idx],
            "expire_at": packed[9][idx],
        }

    # -- observability -------------------------------------------------
    def _observe_latency(self, seconds, n: int = 1) -> None:
        """`n` observations of each of `seconds` (a time, or one a
        promoted row: each from the time its own entry was queued)."""
        seconds = np.atleast_1d(np.asarray(seconds, dtype=np.float64))
        self._hist += n * np.bincount(
            np.searchsorted(self._buckets, seconds, side="left"),
            minlength=len(self._hist),
        )
        self._lat_sum += n * float(seconds.sum())

    def promote_latency_cumulative(self) -> List[int]:
        """Cumulative bucket counts on LATENCY_BUCKETS (+Inf tail) —
        metrics.estimate_quantile's input shape."""
        return np.cumsum(self._hist).tolist()

    def debug_vars(self) -> dict:
        from gubernator_tpu.runtime.metrics import estimate_quantile

        cum = self.promote_latency_cumulative()
        return {
            "enabled": True,
            "cold_residents": self.cold.residents(),
            "cold_capacity": self.cold.capacity,
            "capacity_drops": self.cold.capacity_drops,
            "promotes": self.promotes,
            "demotes": self.demotes,
            "cold_hits": self.cold_hits,
            "promote_retries": self.promote_retries,
            "promote_failures": self.promote_failures,
            "demote_passes": self.demote_passes,
            "ticks": self.ticks,
            "high_water": float(self.cfg.high_water),
            "low_water": float(self.cfg.low_water),
            "demote_batch": int(self.cfg.demote_batch),
            "demote_ladder": list(self._ladder),
            "slots": int(self.backend.cfg.num_slots),
            "promote_latency": {
                "buckets": self._buckets.tolist(),
                "cumulative": cum,
                "sum_s": self._lat_sum,
                "p99_s": estimate_quantile(
                    self._buckets.tolist(), cum, 0.99
                ),
            },
        }

    def publish(self) -> None:
        """Push the tier block into the prometheus bundle (the worker
        does this after each tick; gubstat's sampler pattern)."""
        m = self.metrics
        if m is None:
            return
        m.tier_cold_residents.set(self.cold.residents())
        m.tier_capacity_drops.set(self.cold.capacity_drops)
        _set_counter(m.tier_promotes, self.promotes)
        _set_counter(m.tier_demotes, self.demotes)
        _set_counter(m.tier_cold_hits, self.cold_hits)
        for edge, c in zip(
            self._buckets.tolist(), self.promote_latency_cumulative()
        ):
            m.tier_promote_latency.labels(le=str(edge)).set(c)


def _set_counter(counter, value: int) -> None:
    """Advance a prometheus Counter to an absolute total (the manager
    keeps its own totals; the collector mirrors them)."""
    cur = counter._value.get()
    if value > cur:
        counter.inc(value - cur)
