"""Device backend: the intra-node engine behind the service instance.

Replaces the reference WorkerPool (workers.go:56-664).  Where the reference
shards the key space across NumCPU goroutine workers each owning a private
LRU, this backend owns ONE device-resident slot table and applies whole
batches in a single jitted step — intra-node parallelism comes from vector
lanes, not threads.  (The multi-chip version shards the same table over a
mesh axis; see gubernator_tpu.parallel.mesh.)

Synchronous by design: callers (the async batcher / service) serialize calls,
which preserves the reference's single-writer-per-shard discipline
(workers.go:19-37) at whole-table granularity.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

import gubernator_tpu.ops  # noqa: F401  (enables x64)
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.hashing import key_hash64
from gubernator_tpu.ops import f64bits
from gubernator_tpu.core.types import (
    CacheItem,
    RateLimitReq,
    RateLimitResp,
    Status,
)
from gubernator_tpu.ops.batch import DeviceBatch, pack_requests
from gubernator_tpu.ops.devices import device_info, platform_devices
from gubernator_tpu.ops.state import (
    SlotTable,
    init_table,
    read_rows,
    table_to_host,
)
from gubernator_tpu.ops.step import (
    BucketRows,
    CachedRows,
    apply_batch_packed_q,
    gather_rows,
    load_rows,
    probe_batch,
    store_cached_rows,
)
from gubernator_tpu.runtime import tracing


def pack_batch_q(db) -> np.ndarray:
    """Stack a [B] DeviceBatch into one int64[12, B] host array (single
    host->device transfer; bools/int32 widen)."""
    arrs = [np.asarray(a) for a in db]
    q = np.empty((len(arrs),) + arrs[0].shape, dtype=np.int64)
    for i, a in enumerate(arrs):
        q[i] = a
    return q


def round_words(db, tiers, pack=pack_batch_q) -> np.ndarray:
    """One round as the step program takes it: int64[12, .., t], t the
    smallest compiled tier that holds its lanes.  The compiled lane hands
    rounds over in that form already (native.pack_rounds wrote them so,
    once); a DeviceBatch (the object path's packers, a store repair) is
    packed here and cut to its tier."""
    if isinstance(db, np.ndarray):
        return db
    return pack(db)[..., : tier_of(db.active, tiers)]


def declare_launches(stages, tiers, *lanes: str) -> None:
    """`backend.dispatch`'s counters at zero on the rows of `lanes`: a run
    whose rounds never leave the 128 rung reads the other rungs 0, not
    nothing."""
    rungs = tuple(f"tier_{t}" for t in tiers)
    for lane in lanes:
        stages.declare(lane, "backend.dispatch", "launches", "lanes", *rungs)


def launch_rounds(stages, rounds, tiers, launch, pack=pack_batch_q,
                  shards: int = 1) -> list:
    """Every step launch passes here: one `backend.dispatch` section that
    cuts each round to its rung (round_words), hands the words to
    `launch` and counts the launch by the compiled width it rode —
    `launches`, `lanes` (x shards on the mesh) and `tier_<width>`.  Beside
    `backend.checks`, `lanes` over the lanes carried is the width a drain
    paid for and left empty.  Returns what `launch` returned, a round."""
    resps = []
    counts = {"lanes": 0}
    with stages.stage("backend.dispatch") as dispatch:
        for db in rounds:
            words = round_words(db, tiers, pack)
            t = words.shape[-1]
            counts["lanes"] += shards * t
            counts[f"tier_{t}"] = counts.get(f"tier_{t}", 0) + 1
            resps.append(launch(words))
        dispatch.tally(launches=len(resps), **counts)
    return resps


def default_tiers(batch_size: int) -> tuple:
    """The ladder of compiled batch widths where `batch_tiers` is unset:
    128, 1,024 where batch_size is wider, batch_size — (128, 1024, 4096)
    at 4096.  A round that overflows the full width by a few hundred
    lanes, or an owner's drain of a thousand, rides a launch of its own
    size; under 1,024 no cell's rounds are between the rungs, and a
    2,048-lane rung was measured and not kept (PERF.md section 5.11)."""
    return tuple(t for t in (128, 1024) if t < batch_size) + (batch_size,)


def resolve_tiers(cfg) -> tuple:
    """Sorted compiled batch tiers; batch_size is ALWAYS included so
    tier_of's fallback never truncates a full round."""
    tiers = cfg.batch_tiers or default_tiers(cfg.batch_size)
    return tuple(sorted(
        {min(t, cfg.batch_size) for t in tiers} | {cfg.batch_size}
    ))


def tier_of(active: np.ndarray, tiers) -> int:
    """Smallest compiled batch tier that holds this round's active lanes
    (the packer fills lanes contiguously from 0 per shard, so the max
    per-shard count bounds the highest used lane).  `active` is [B] or
    [n_shards, B]."""
    occ = int(np.asarray(active).sum(-1).max())
    for t in tiers:
        if occ <= t:
            return t
    return tiers[-1]


def _h64s(hashes: Sequence[int]) -> np.ndarray:
    """Unsigned 64-bit key fingerprints -> the int64 view stored on device."""
    return np.array(hashes, dtype=np.uint64).view(np.int64)


class PersistenceHost:
    """Host-side Store/Loader plumbing shared by DeviceBackend and
    MeshBackend (the SPI semantics of store.go:49-78 / workers.go:340-530).

    Backends provide the device hooks:
    - `_found_mask(keys, hashes, now)` -> bool[len(keys)] residency probe
      (caller holds `_lock`; `hashes` are unsigned 64-bit ints);
    - `_bulk_upsert(rows, hashes, now)` upserts row-field dicts (caller
      holds `_lock`);
    - `read_items_bulk(keys)` -> {key: CacheItem} (takes its own lock);
    - `snapshot()` -> host arrays of the whole table.
    Plus the attributes `cfg`, `clock`, `store`, `_keymap`, `_lock`, `table`.
    """

    def _maybe_prune_keymap(self) -> None:
        """Bound the fingerprint->key map: the table holds at most num_slots
        live rows, so once the map is 4x that, drop fingerprints no longer
        resident (evicted/expired keys would otherwise accumulate forever).
        The rebuild holds `_keymap_lock` — the object path's executor
        thread, the fast-lane pool, and the engine lane all write the map
        concurrently, and an unlocked rebuild would either crash on a
        concurrent insert or silently drop it."""
        assert self._keymap is not None
        if len(self._keymap) <= max(4 * self.cfg.num_slots, 65_536):
            return
        with self._lock:
            resident = set(
                np.asarray(self.table.key).view(np.uint64).tolist()
            )
        with self._keymap_lock:
            self._keymap = {
                fp: k for fp, k in self._keymap.items() if fp in resident
            }

    def _seed_from_store(self, reqs, packed, now: int) -> None:
        """Consult Store.get for batch keys not resident on device and bulk
        upsert the hits (the batched analog of algorithms.go:45-51).
        Caller holds `_lock`."""
        uniq: Dict[str, RateLimitReq] = {}
        for i, r in enumerate(reqs):
            if i not in packed.errors:
                uniq.setdefault(r.hash_key(), r)
        keys = list(uniq.keys())
        if not keys:
            return
        hashes = [key_hash64(k) for k in keys]
        self._seed_missing(keys, hashes, [uniq[k] for k in keys], now)

    def _seed_missing(self, keys, hashes, reqs, now: int) -> None:
        """Object-path seeding: one residency probe over `hashes`
        (unsigned), then the shared Store-consult core.  Caller holds
        `_lock`."""
        found = self._found_mask(keys, hashes, now)
        self._store_seed_misses(hashes, reqs, found, now)

    def _store_seed_misses(self, hashes, reqs, found, now: int):
        """Store-consult core shared by the object path (probe-derived
        `found`) and the fast lane's cold-key repair (the step's own
        `found` column): Store.get for each miss, one bulk upsert of the
        live items (algorithms.go:45-51 batched).  Caller holds `_lock`.
        Returns the indices (into the input lists) that were seeded."""
        from gubernator_tpu.runtime.store import item_to_row_fields

        rows: List[dict] = []
        row_hashes: List[int] = []
        seeded: List[int] = []
        for i, (h, r, f) in enumerate(zip(hashes, reqs, found)):
            if f:
                continue
            item = self.store.get(r)
            if item is None or item.is_expired(now):
                continue
            rows.append(item_to_row_fields(item))
            row_hashes.append(h)
            seeded.append(i)
        if rows:
            self._bulk_upsert(rows, row_hashes, now)
        return seeded

    def _init_write_through(self) -> None:
        """Write-through delivery ordering + keymap-writer state (backend
        __init__)."""
        self._wt_seq = 0
        self._wt_next = 0
        self._wt_cond = threading.Condition()
        # Guards every _keymap mutation: the step executor, the fast-lane
        # pool, and the engine lane write it from different threads.
        self._keymap_lock = threading.Lock()

    def _wt_ticket(self) -> int:
        """Next write-through delivery ticket (caller holds `_lock`).
        Tickets order Store.on_change delivery across concurrent batches:
        captures are per-batch-consistent, but without ordering a slower
        thread could deliver an OLDER captured state after a newer one and
        the store would diverge from the table (the reference orders
        delivery by calling OnChange inside the per-key worker).  Every
        ticket MUST be redeemed via _deliver_write_through (even with an
        empty capture) or later deliveries stall."""
        seq = self._wt_seq
        self._wt_seq = seq + 1
        return seq

    def _capture_write_through(
        self, reqs, packed, use_cached=None
    ) -> List[Tuple[RateLimitReq, CacheItem]]:
        """Read back post-step rows for persisted requests while the caller
        STILL HOLDS `_lock` — a concurrent batch must not mutate a key
        between this batch's step and its Store.on_change read-back (the
        reference calls OnChange synchronously inside the algorithm,
        algorithms.go:154-158).

        Lanes served from GLOBAL broadcast cache (use_cached) are excluded —
        their rows are replicated responses, not authoritative bucket state
        (the reference only runs OnChange inside the owner's algorithm)."""
        seen: set = set()
        key_req: List[Tuple[str, RateLimitReq]] = []
        for i, r in enumerate(reqs):
            if i in packed.errors:
                continue
            if use_cached is not None and use_cached[i]:
                continue
            key = r.hash_key()
            if key in seen:
                continue
            seen.add(key)
            key_req.append((key, r))
        if not key_req:
            return []
        items = self._read_items_locked([k for k, _ in key_req])
        return [(r, items[k]) for k, r in key_req if k in items]

    def _deliver_write_through(self, captured, seq: int) -> None:
        """Hand captured post-step items to Store.on_change, in capture
        order (`seq` from `_wt_ticket`).  Runs OUTSIDE `_lock` — on_change
        is user code and must not be able to deadlock against backend
        entry points — but a FIFO ticket wait preserves step order, so a
        stale capture can never overwrite a newer one in the store."""
        cond = self._wt_cond
        with cond:
            while self._wt_next != seq:
                cond.wait()
        try:
            for r, item in captured:
                self.store.on_change(r, item)
        finally:
            with cond:
                self._wt_next += 1
                cond.notify_all()

    # -- live slot migration (runtime/reshard.py; docs/resharding.md) ----
    def key_snapshot(self):
        """(key int64[S], kind int32[S], expire_at int64[S]) host view —
        the reshard plane's remap-delta input (one fetch, no full-table
        DMA)."""
        with self._lock:
            t = self.table
            return (
                np.asarray(t.key), np.asarray(t.kind),
                np.asarray(t.expire_at),
            )

    def migrate_extract_rows(self, fps: np.ndarray):
        """Atomically gather-and-clear the rows for int64 fingerprints
        `fps`: returns (int64[10, n] in ops.step.GATHER_ROW_FIELDS
        order — packed[0] is the found mask — and float64[n]
        remaining_f).  Cleared rows read as empty to every probe from
        the moment the lock releases, so the old owner can never serve
        a migrated key from an orphaned slot.

        Generic path (MeshBackend): a row gather plus an expire_at=0
        re-upsert in ONE critical section — two dispatches, same
        atomicity, riding the registered sharded gather/load kernels."""
        from gubernator_tpu.ops.step import GATHER_ROW_FIELDS

        n = len(fps)
        now = self.clock.millisecond_now()
        with self._lock:
            token = self._gather_rows_dispatch(
                np.asarray(fps, dtype=np.int64), now
            )
            packed, rf = self._gather_rows_finish(token, n)
            found = packed[0] != 0
            if found.any():
                rows = [
                    {
                        "algo": int(packed[2][j]),
                        "limit": int(packed[3][j]),
                        "duration": int(packed[4][j]),
                        "remaining": int(packed[5][j]),
                        "remaining_f": float(rf[j]),
                        "t0": int(packed[6][j]),
                        "status": int(packed[7][j]),
                        "burst": int(packed[8][j]),
                        "expire_at": 0,  # the clear
                    }
                    for j in np.flatnonzero(found)
                ]
                hashes = [
                    int(np.int64(fps[j]).view(np.uint64))
                    for j in np.flatnonzero(found)
                ]
                self._bulk_upsert(rows, hashes, now)
        assert packed.shape[0] == len(GATHER_ROW_FIELDS)
        return packed, rf

    def migrate_inject_rows(self, cols: Dict[str, np.ndarray]):
        """Upsert migrated row columns (BucketRows field names) where
        the key is absent; MERGE where it is resident — subtract the
        migrated row's consumed budget from the resident row, clamped
        at 0 (counters conserved, never inflated; a receiver may have
        served a moved key before its row arrived).  Returns
        (injected, merged).  The reshard manager guards chunk replays
        per handoff epoch — a re-delivered chunk never reaches this.

        Generic path (MeshBackend): probe + upsert + a gather/re-upsert
        merge in one critical section over the registered sharded
        kernels."""
        n = len(cols["key_hash"])
        now = self.clock.millisecond_now()
        h64 = np.asarray(cols["key_hash"], dtype=np.int64)
        hashes_u = [int(np.int64(h).view(np.uint64)) for h in h64]
        with self._lock:
            found = np.asarray(
                self._found_mask([""] * n, hashes_u, now)
            )
            absent = ~found

            def row_at(j, remaining, remaining_f):
                return {
                    "algo": int(cols["algo"][j]),
                    "limit": int(cols["limit"][j]),
                    "duration": int(cols["duration"][j]),
                    "remaining": int(remaining),
                    "remaining_f": float(remaining_f),
                    "t0": int(cols["t0"][j]),
                    "status": int(cols["status"][j]),
                    "burst": int(cols["burst"][j]),
                    "expire_at": int(cols["expire_at"][j]),
                }

            if absent.any():
                idx = np.flatnonzero(absent)
                self._bulk_upsert(
                    [
                        row_at(
                            j, cols["remaining"][j],
                            cols["remaining_f"][j],
                        )
                        for j in idx
                    ],
                    [hashes_u[j] for j in idx], now,
                )
            if found.any():
                idx = np.flatnonzero(found)
                token = self._gather_rows_dispatch(h64[idx], now)
                packed, rf = self._gather_rows_finish(token, len(idx))
                rows = []
                hashes = []
                for k, j in enumerate(idx):
                    consumed_i = max(
                        int(cols["limit"][j])
                        - int(cols["remaining"][j]), 0,
                    )
                    consumed_f = max(
                        float(cols["limit"][j])
                        - float(cols["remaining_f"][j]), 0.0,
                    )
                    leaky = int(cols["algo"][j]) == 1
                    rows.append({
                        # The RESIDENT row's fields, with the migrated
                        # consumption folded in.
                        "algo": int(packed[2][k]),
                        "limit": int(packed[3][k]),
                        "duration": int(packed[4][k]),
                        "remaining": max(
                            int(packed[5][k])
                            - (0 if leaky else consumed_i), 0,
                        ),
                        "remaining_f": max(
                            float(rf[k])
                            - (consumed_f if leaky else 0.0), 0.0,
                        ),
                        "t0": int(packed[6][k]),
                        "status": int(packed[7][k]),
                        "burst": int(packed[8][k]),
                        "expire_at": int(packed[9][k]),
                    })
                    hashes.append(hashes_u[j])
                self._bulk_upsert(rows, hashes, now)
        injected = int(absent.sum())
        return injected, n - injected

    def load_items(self, items) -> int:
        """Bulk upsert CacheItems (Loader restore, workers.go:340-426)."""
        from gubernator_tpu.runtime.store import item_to_row_fields

        chunk = 4 * self.cfg.batch_size
        now = self.clock.millisecond_now()
        n = 0
        rows: List[dict] = []
        hashes: List[int] = []
        for item in items:
            h = key_hash64(item.key)
            if self._keymap is not None:
                with self._keymap_lock:
                    self._keymap[h] = item.key
            rows.append(item_to_row_fields(item))
            hashes.append(h)
            n += 1
            if len(rows) >= chunk:
                with self._lock:
                    self._bulk_upsert(rows, hashes, now)
                rows, hashes = [], []
        if rows:
            with self._lock:
                self._bulk_upsert(rows, hashes, now)
        return n

    def live_items(self) -> List[CacheItem]:
        """All live rows as CacheItems (Loader save, workers.go:467-530).
        Requires key tracking (a Store/Loader attached at construction)."""
        if self._keymap is None:
            raise RuntimeError(
                "live_items() needs key tracking; construct the backend "
                "with a store or track_keys=True"
            )
        from gubernator_tpu.ops.state import KIND_CACHED_RESP

        snap = self.snapshot()
        now = self.clock.millisecond_now()
        out: List[CacheItem] = []
        # KIND_CACHED_RESP rows are replicated GLOBAL broadcast responses,
        # not authoritative bucket state — saving them would resurrect them
        # as owner buckets on restore.
        live = np.flatnonzero(
            (snap["key"] != 0)
            & (snap["expire_at"] > now)
            & (snap["kind"] != KIND_CACHED_RESP)
        )
        for s in live:
            fp = int(np.int64(snap["key"][s]).view(np.uint64))
            key = self._keymap.get(fp)
            if key is None:
                continue
            out.append(_row_to_item(snap, s, key))
        return out


class DeviceBackend(PersistenceHost):
    """Single-table rate-limit engine on one device (or CPU backend)."""

    def __init__(
        self,
        cfg: Optional[DeviceConfig] = None,
        clock: Optional[clock_mod.Clock] = None,
        store: Optional["Store"] = None,
        track_keys: bool = False,
        metrics=None,
    ) -> None:
        self.metrics = metrics
        self._stages = tracing.ledger_of(metrics)
        self.cfg = cfg or DeviceConfig()
        self.clock = clock or clock_mod.default_clock()
        self._lock = threading.Lock()
        self._init_write_through()
        # Every single-table backend in a process takes the platform's
        # device 0 (the in-process cluster fixture's daemons share it).
        self._device = platform_devices(self.cfg.platform)[0]
        with jax.default_device(self._device):
            self.table: SlotTable = init_table(self.cfg.num_slots)
        self._step_packed_q = functools.partial(
            apply_batch_packed_q, ways=self.cfg.ways
        )
        # Batch-shape tiers: a round with few active lanes rides a small
        # compiled shape instead of shipping the full [12, B] array — the
        # transfer scales with the traffic, not the configured max batch.
        # batch_size is always a tier so a full round can never be
        # truncated.
        self._tiers = resolve_tiers(self.cfg)
        declare_launches(self._stages, self._tiers, "mach", "direct")
        self._load_rows = functools.partial(load_rows, ways=self.cfg.ways)
        self._probe = functools.partial(probe_batch, ways=self.cfg.ways)
        # Module-level jits (apply_batch_packed_q/load_rows/probe_batch/
        # store_cached_rows) share one compile cache across backends — the
        # in-process cluster fixture runs many daemons per process and
        # per-instance jits would recompile per daemon.
        self._store_cached = functools.partial(
            store_cached_rows, ways=self.cfg.ways
        )
        self._gather_rows = functools.partial(
            gather_rows, ways=self.cfg.ways
        )
        self.store = store
        # fingerprint -> hash-key string, maintained when persistence needs
        # to reconstruct key strings from device rows (save path).
        self._keymap: Optional[Dict[int, str]] = (
            {} if (store is not None or track_keys) else None
        )
        # Running totals (metric parity: gubernator_over_limit_counter etc.)
        self.checks = 0
        self.over_limit = 0
        self.not_persisted = 0

    def device_info(self) -> dict:
        return device_info([self._device], self.cfg.platform)

    def _add_tally(self, tally: "Tally") -> None:
        with self._lock:
            self.checks += tally.checks
            self.over_limit += tally.over_limit
            self.not_persisted += tally.not_persisted
        m = self.metrics
        if m is not None:
            m.check_counter.inc(tally.checks)
            if tally.over_limit:
                m.over_limit_counter.inc(tally.over_limit)
            if tally.not_persisted:
                m.unexpired_evictions.inc(tally.not_persisted)
            m.cache_access_count.labels(type="hit").inc(tally.cache_hits)
            m.cache_access_count.labels(type="miss").inc(
                tally.checks - tally.cache_hits
            )

    # -- hot path --------------------------------------------------------
    def check(
        self,
        reqs: Sequence[RateLimitReq],
        use_cached: Optional[Sequence[bool]] = None,
    ) -> List[RateLimitResp]:
        """Apply a list of checks; returns responses in request order.

        The packer splits duplicate keys into sequential rounds so same-key
        requests observe each other's effects, like the reference's per-key
        worker serialization (workers.go:182-186).

        `use_cached[i]` marks request i to serve a live GLOBAL broadcast row
        verbatim (the non-owner read path, gubernator.go:434-447).
        """
        packed = pack_requests(
            reqs, self.cfg.batch_size, self.clock, use_cached
        )
        now = self.clock.millisecond_now()
        if self._keymap is not None:
            with self._keymap_lock:
                for i, r in enumerate(reqs):
                    if i not in packed.errors:
                        k = r.hash_key()
                        self._keymap[key_hash64(k)] = k
            self._maybe_prune_keymap()
        captured = None
        t_start = time.monotonic()
        lock_wait = self._stages.stage("backend.lock_wait")
        with self._lock:
            lock_wait.end()
            if self.store is not None:
                self._seed_from_store(reqs, packed, now)
            round_resps = self._dispatch_rounds_locked(packed.rounds, now)
            if self.store is not None:
                # Read-back inside the lock: a concurrent batch must not
                # mutate a key between this batch's step and on_change.
                captured = self._capture_write_through(
                    reqs, packed, use_cached
                )
                wt_seq = self._wt_ticket()
        try:
            step_s = time.monotonic() - t_start
            if self.metrics is not None:
                self.metrics.pool_queue_length.observe(len(reqs))
            # One packed sync per round (one transfer instead of six).
            out, tally = unmarshal_responses(
                len(reqs), packed.errors, packed.positions,
                packed_rounds_to_host(round_resps),
            )
            self._add_tally(tally)
            fr = getattr(self.metrics, "flightrec", None)
            if fr is not None:
                fr.record_batch(
                    len(reqs), step_s * 1e3,
                    over_limit=tally.over_limit,
                    errors=len(packed.errors),
                )
        finally:
            # The ticket MUST be redeemed even if unmarshal fails, or
            # every later delivery wedges in cond.wait (the step itself
            # already happened, so delivering the capture is correct).
            if captured is not None:
                self._deliver_write_through(captured, wt_seq)
        return out

    def step_rounds(
        self, rounds: Sequence[DeviceBatch], add_tally: bool = True
    ) -> List[Dict[str, np.ndarray]]:
        """Columnar hot path: apply pre-packed [B] DeviceBatch rounds with
        no per-request Python anywhere (the compiled fast lane,
        runtime/fastpath.py).  Persistence hooks are NOT run here — a
        store-attached drain runs them itself around
        _dispatch_rounds_locked (fastpath._process: seed inside the lock,
        capture dispatched inside, delivered outside); this entry serves
        the storeless plain merge.  Returns host response dicts per round;
        with add_tally, tallies update vectorized (the fast lane passes
        False and counts per REQUEST — cascade occurrences share device
        lanes)."""
        return self.step_rounds_begin(rounds, add_tally)()

    def step_rounds_begin(
        self, rounds: Sequence[DeviceBatch], add_tally: bool = True
    ):
        """Pipelined step_rounds: dispatch the rounds under the lock and
        return a zero-arg fetch closure producing the host response
        dicts.  The dispatched responses are this call's own output
        buffers pinned to this table version (jax arrays are immutable),
        so the caller may run the closure on a fetch stage while the
        next merge dispatches — the two-stage drain discipline
        (fastpath._Coalescer)."""
        t_start = time.monotonic()
        lock_wait = self._stages.stage("backend.lock_wait")
        with self._lock:
            lock_wait.end()
            round_resps = self._dispatch_rounds_locked(rounds)

        def fetch() -> List[Dict[str, np.ndarray]]:
            host = packed_rounds_to_host(round_resps)
            if add_tally:
                tally = tally_from_rounds(rounds, host)
                self._add_tally(tally)
                fr = getattr(self.metrics, "flightrec", None)
                if fr is not None:
                    fr.record_batch(
                        tally.checks, (time.monotonic() - t_start) * 1e3,
                        over_limit=tally.over_limit,
                    )
            return host

        return fetch

    def _dispatch_rounds_locked(self, rounds, now=None) -> list:
        """Dispatch pre-packed rounds; caller holds `_lock`.  Returns the
        device response handles WITHOUT syncing them — the fast lane's
        cascade section syncs inside the lock (its critical window spans
        the sync) while the plain path syncs after release.

        `now` (ms) is the clock the rounds run under.  ONE CLOCK A DRAIN:
        everything a drain dispatches under one hold of `_lock` — read
        rounds, the cascade's write-back rounds, a repair's rounds, the
        store capture — takes the reading its holder took once
        (fastpath._process_packed).  A write-back under a later reading
        finds a window ended or a leak completed that the answers it
        writes back never saw (PERF.md section 7, PR 33).  None: this
        dispatch is the hold's only one, and reads the clock itself."""
        now = np.int64(self.clock.millisecond_now() if now is None else now)

        def launch(words):
            self.table, packed_resp = self._step_packed_q(
                self.table, words, now
            )
            return packed_resp

        return launch_rounds(self._stages, rounds, self._tiers, launch)

    def _probe_padded(self, hashes: np.ndarray, now: int) -> np.ndarray:
        """found-mask for a host hash vector, probing in fixed batch_size
        chunks so the jitted probe never sees a new shape (the fixed-shape
        rule, core/config.py DeviceConfig).  All chunks dispatch before the
        first fetch — one round-trip of latency however many chunks."""
        B = self.cfg.batch_size
        devs = []
        for lo in range(0, len(hashes), B):
            chunk = hashes[lo:lo + B]
            padded = np.zeros(B, dtype=np.int64)
            padded[: len(chunk)] = chunk
            devs.append(self._probe(self.table, padded, np.int64(now))[0])
        out = np.zeros(len(hashes), dtype=bool)
        for i, d in enumerate(fetch_ravel(devs)):
            lo = i * B
            out[lo:lo + B] = d[: len(hashes) - lo]
        return out

    def _gather_rows_dispatch(self, h64: np.ndarray, now: int):
        """Dispatch columnar row gathers for int64 fingerprints (lock
        held).  Returns an opaque token for `_gather_rows_finish`: the
        dispatched reads are pinned to this table version (jax arrays are
        immutable), so the caller may release the lock before fetching."""
        B = self.cfg.batch_size
        token = []
        for lo in range(0, len(h64), B):
            chunk = h64[lo:lo + B]
            padded = np.zeros(B, dtype=np.int64)
            padded[: len(chunk)] = chunk
            token.append(
                self._gather_rows(self.table, padded, np.int64(now))
            )
        return token

    def _gather_rows_int_arrays(self, token) -> list:
        """The token's int64 device buffers — exposed so a caller can fold
        them into ONE fetch_ravel round-trip with its response buffers."""
        return [d for d, _rf in token]

    def _gather_rows_rf_arrays(self, token) -> list:
        """The token's remaining_f buffers, int64 bits (needed only when a
        leaky row may have been captured — token rows read remaining from
        the int columns)."""
        return [rf for _d, rf in token]

    def _gather_rows_build(self, token, m: int, int_hosts,
                           rf_hosts=None):
        """Assemble (int64[10, m] GATHER_ROW_FIELDS columns, float64[m]
        remaining_f) from pre-fetched host chunks.  rf_hosts=None means
        the caller proved no leaky row was captured (zeros)."""
        from gubernator_tpu.ops.step import GATHER_ROW_FIELDS

        if not token:
            return (
                np.zeros((len(GATHER_ROW_FIELDS), 0), dtype=np.int64),
                np.zeros(0),
            )
        packed = np.concatenate(int_hosts, axis=1)[:, :m]
        # The device hands remaining_f over as its bits (ops/f64bits.py).
        rf = (
            f64bits.from_bits(np.concatenate(rf_hosts)[:m])
            if rf_hosts is not None else np.zeros(m)
        )
        return packed, rf

    def _gather_rows_finish(self, token, m: int):
        """Fetch + assemble in two packed round-trips (ints, rf)."""
        return self._gather_rows_build(
            token, m,
            fetch_ravel(self._gather_rows_int_arrays(token)),
            fetch_ravel(self._gather_rows_rf_arrays(token)),
        )

    def migrate_extract_rows(self, fps: np.ndarray):
        """Fused single-device form of the generic gather-and-clear:
        each chunk is ONE donated ops/state.migrate_extract dispatch,
        so extraction and clearing are a per-row atomicity fact (the
        gubtrace-registered kernel), not a two-step protocol."""
        from gubernator_tpu.ops.state import migrate_extract

        B = self.cfg.batch_size
        now = np.int64(self.clock.millisecond_now())
        packed_devs = []
        rf_devs = []
        with self._lock:
            for lo in range(0, len(fps), B):
                chunk = np.asarray(fps[lo:lo + B], dtype=np.int64)
                padded = np.zeros(B, dtype=np.int64)
                padded[: len(chunk)] = chunk
                self.table, packed, rf = migrate_extract(
                    self.table, padded, now, ways=self.cfg.ways
                )
                packed_devs.append(packed)
                rf_devs.append(rf)
        if not packed_devs:
            return np.zeros((10, 0), dtype=np.int64), np.zeros(0)
        ints = fetch_ravel(packed_devs)
        rfs = fetch_ravel(rf_devs)
        n = len(fps)
        return (
            np.concatenate(ints, axis=1)[:, :n],
            f64bits.from_bits(np.concatenate(rfs)[:n]),
        )

    def migrate_inject_rows(self, cols: Dict[str, np.ndarray]):
        """Fused single-device inject-if-absent (ops/state
        .migrate_inject): one donated dispatch per chunk; returns
        (injected, skipped)."""
        from gubernator_tpu.ops.state import migrate_inject

        B = self.cfg.batch_size
        now = np.int64(self.clock.millisecond_now())
        n = len(cols["key_hash"])
        resident_devs = []
        actives = []
        with self._lock:
            for lo in range(0, n, B):
                rows = _bucket_rows(
                    cols, np.arange(lo, min(lo + B, n)), B
                )
                self.table, resident = migrate_inject(
                    self.table, rows, now, ways=self.cfg.ways
                )
                resident_devs.append(resident)
                actives.append(np.asarray(rows.key_hash) != 0)
        if not resident_devs:
            return 0, 0
        injected = skipped = 0
        for res, act in zip(fetch_ravel(resident_devs), actives):
            res = np.asarray(res)
            injected += int((act & ~res).sum())
            skipped += int((act & res).sum())
        return injected, skipped

    def warmup(self, tier=None) -> None:
        """Compile the hot-path executables with a synthetic batch that
        bypasses the Store/Loader hooks and the keymap — no persistence
        side effects (a real check() would leak the synthetic key into an
        attached store).  `tier`: the daemon's TierConfig where the
        two-tier table is enabled — its programs compile here too."""
        now = np.int64(self.clock.millisecond_now())
        packed = pack_requests(
            [RateLimitReq(name="__warmup__", unique_key="w", hits=0,
                          limit=1, duration=1)],
            self.cfg.batch_size,
            self.clock,
        )
        with self._lock:
            # Compile the packed step at EVERY batch tier — check()'s
            # actual hot path — so no client request ever pays a cold XLA
            # compile.
            for t in self._tiers:
                self.table, resp = self._step_packed_q(
                    self.table,
                    np.zeros((12, t), dtype=np.int64),
                    now,
                )
            for db in packed.rounds:
                t = tier_of(db.active, self._tiers)
                self.table, resp = self._step_packed_q(
                    self.table, pack_batch_q(db)[:, :t], now
                )
            # Fixed-shape probe + row-gather executables (store seeding /
            # write-through capture / bulk reads).
            self._probe(
                self.table,
                np.zeros(self.cfg.batch_size, dtype=np.int64),
                now,
            )
            self._gather_rows(
                self.table,
                np.zeros(self.cfg.batch_size, dtype=np.int64),
                now,
            )
            # Broadcast-receive executable (UpdatePeerGlobals path) — a
            # first compile inside a peer's RPC deadline would time out.
            B = self.cfg.batch_size
            self.table = self._store_cached(
                self.table,
                CachedRows(
                    key_hash=np.zeros(B, dtype=np.int64),
                    algo=np.zeros(B, dtype=np.int32),
                    limit=np.zeros(B, dtype=np.int64),
                    remaining=np.zeros(B, dtype=np.int64),
                    status=np.zeros(B, dtype=np.int32),
                    reset_time=np.zeros(B, dtype=np.int64),
                ),
                now,
            )
            # Gubstat census executable at the sampler's minimum shadow
            # pad tier (runtime/gubstat.py pads to powers of two from
            # 8) — the periodic sample should never pay a cold compile.
            from gubernator_tpu.ops.state import table_stats

            table_stats(
                self.table, np.zeros((4, 8), dtype=np.int64), now,
                ways=self.cfg.ways,
            )
        jax.block_until_ready(resp)
        if tier is not None:
            self.warmup_tier(tier)

    def warmup_tier(self, tier) -> None:
        """The two-tier table's programs (runtime/coldtier.py), every
        width each can launch at: the occupancy read, `migrate_inject`
        on the step's ladder and `demote_extract` on the demoter's — a
        daemon's first promote and its first tick over the mark then
        compile nothing under `backend._lock` (48-51 s on a v5e from an
        empty cache; PERF.md section 7, PR 45 (5)).  No call changes
        the table: the inject carries inactive lanes, the demote takes
        no row."""
        from gubernator_tpu.ops.state import migrate_inject
        from gubernator_tpu.runtime.coldtier import (
            COLD_FIELDS, demote_ladder,
        )

        self.occupancy_dispatch()()
        now = np.int64(self.clock.millisecond_now())
        no_rows = np.zeros(0, dtype=np.int64)
        idle = dict.fromkeys(COLD_FIELDS, no_rows)
        with self._lock:
            for t in self._tiers:
                self.table, resident = migrate_inject(
                    self.table, _bucket_rows(idle, no_rows, t), now,
                    ways=self.cfg.ways,
                )
        jax.block_until_ready(resident)
        grid = np.zeros(8, dtype=np.int64)
        for b in demote_ladder(tier.demote_batch, self.cfg.num_slots):
            self.demote_extract_dispatch(grid, b, take=0)()

    # -- persistence device hooks (PersistenceHost) ----------------------
    def _found_mask(self, keys, hashes, now: int) -> np.ndarray:
        return self._probe_padded(_h64s(hashes), now)

    def _bulk_upsert(
        self, rows: List[dict], hashes: List[int], now: int
    ) -> None:
        """Chunked load_rows over the fixed batch shape (lock held)."""
        B = self.cfg.batch_size
        h64 = _h64s(hashes)
        for lo in range(0, len(rows), B):
            chunk = rows[lo:lo + B]
            pad = B - len(chunk)
            cols = {
                f: np.array(
                    [c[f] for c in chunk] + [0] * pad,
                    dtype=np.float64 if f == "remaining_f" else (
                        np.int32 if f in ("algo", "status") else np.int64
                    ),
                )
                for f in (
                    "algo", "limit", "duration", "remaining",
                    "remaining_f", "t0", "status", "burst", "expire_at",
                )
            }
            cols["remaining_f"] = f64bits.to_bits(cols["remaining_f"])
            br = BucketRows(
                key_hash=np.concatenate([
                    h64[lo:lo + B], np.zeros(pad, dtype=np.int64)
                ]),
                **cols,
            )
            self.table = self._load_rows(self.table, br, np.int64(now))

    def read_items_bulk(
        self, keys: Sequence[str], include_cached: bool = False
    ) -> Dict[str, CacheItem]:
        """Batched point-reads: probe + device-side row gather in fixed-size
        chunks, one host sync per chunk.  KIND_CACHED_RESP rows (GLOBAL
        broadcast cache, not bucket state) are skipped unless asked for."""
        with self._lock:
            return self._read_items_locked(keys, include_cached)

    def _read_items_locked(
        self, keys: Sequence[str], include_cached: bool = False
    ) -> Dict[str, CacheItem]:
        """read_items_bulk body; caller holds `_lock` (write-through capture
        reads back rows within the same critical section as the step)."""
        from gubernator_tpu.ops.state import KIND_CACHED_RESP

        B = self.cfg.batch_size
        now = self.clock.millisecond_now()
        hashes = np.array(
            [np.uint64(key_hash64(k)) for k in keys], dtype=np.uint64
        ).view(np.int64)
        out: Dict[str, CacheItem] = {}
        for lo in range(0, len(keys), B):
            chunk_keys = keys[lo:lo + B]
            padded = np.zeros(B, dtype=np.int64)
            padded[: len(chunk_keys)] = hashes[lo:lo + B]
            found, slot = self._probe(self.table, padded, np.int64(now))
            rows = read_rows(self.table, slot)
            found = np.asarray(found)
            for j, k in enumerate(chunk_keys):
                if not found[j]:
                    continue
                if (
                    rows["kind"][j] == KIND_CACHED_RESP
                    and not include_cached
                ):
                    continue
                out[k] = _row_to_item(rows, j, k)
        return out

    # -- GLOBAL broadcast receive ----------------------------------------
    def apply_cached_rows(self, rows: List[tuple]) -> None:
        """Upsert owner-broadcast statuses: rows of
        (hash_key_str, algorithm, limit, remaining, status, reset_time) —
        the UpdatePeerGlobals receive path (gubernator.go:464-479)."""
        if not rows:
            return
        if self._keymap is not None:
            with self._keymap_lock:
                for key, *_ in rows:
                    self._keymap[key_hash64(key)] = key
        B = self.cfg.batch_size
        now = self.clock.millisecond_now()
        with self._lock:
            for lo in range(0, len(rows), B):
                chunk = rows[lo:lo + B]
                pad = B - len(chunk)
                cr = CachedRows(
                    key_hash=np.array(
                        [np.uint64(key_hash64(k)).view(np.int64)
                         for k, *_ in chunk] + [0] * pad,
                        dtype=np.int64,
                    ),
                    algo=np.array(
                        [c[1] for c in chunk] + [0] * pad, dtype=np.int32
                    ),
                    limit=np.array(
                        [c[2] for c in chunk] + [0] * pad, dtype=np.int64
                    ),
                    remaining=np.array(
                        [c[3] for c in chunk] + [0] * pad, dtype=np.int64
                    ),
                    status=np.array(
                        [c[4] for c in chunk] + [0] * pad, dtype=np.int32
                    ),
                    reset_time=np.array(
                        [c[5] for c in chunk] + [0] * pad, dtype=np.int64
                    ),
                )
                self.table = self._store_cached(self.table, cr, np.int64(now))

    # -- cache item access (GLOBAL path + persistence SPI) ---------------
    def get_cache_item(self, key: str) -> Optional[CacheItem]:
        """Point read of one key; reads only the key's bucket (`ways` slots),
        not the whole table."""
        ways = self.cfg.ways
        nb = self.cfg.num_slots // ways
        bucket = key_hash64(key) & (nb - 1)
        now = self.clock.millisecond_now()
        with self._lock:
            return probe_bucket(self.table, bucket * ways, ways, key, now)

    def snapshot(self) -> Dict[str, np.ndarray]:
        """Device->host DMA of the whole table (Loader save path,
        workers.go:467-530)."""
        with self._lock:
            return table_to_host(self.table)

    def _install_table(self, arrays: Dict[str, np.ndarray]) -> None:
        """Replace the live table from host arrays (checkpoint restore)."""
        from gubernator_tpu.ops.state import table_from_host

        if arrays["key"].shape[0] != self.cfg.num_slots:
            raise ValueError(
                f"checkpoint has {arrays['key'].shape[0]} slots, backend "
                f"expects {self.cfg.num_slots}"
            )
        with self._lock, jax.default_device(self._device):
            self.table = table_from_host(arrays)

    def occupancy(self) -> int:
        with self._lock:
            return int(np.asarray(self.table.occupancy()))

    def table_stats_dispatch(self, shadow_fps: np.ndarray):
        """Dispatch the gubstat census (ops/state.table_stats) against
        the live table under the lock and return a zero-arg fetch
        closure.  The kernel is read-only and NON-donated, so the
        serving table is untouched and the dispatched result buffers
        are pinned to this table version — the sampler fetches them
        on its own executor thread, off the request path, while the
        lock is long released.  Every leaf of the fetched
        TableStats carries a leading shard axis (length 1 here; the
        mesh backend returns one row per shard)."""
        from gubernator_tpu.ops.state import TableStats, table_stats

        now = np.int64(self.clock.millisecond_now())
        fps = np.asarray(shadow_fps, dtype=np.int64)
        with self._lock:
            st = table_stats(self.table, fps, now, ways=self.cfg.ways)

        def fetch() -> "TableStats":
            return TableStats(*[np.asarray(a)[None] for a in st])

        return fetch

    # -- tiered table (runtime/coldtier.py; docs/tiering.md) -------------
    def occupancy_dispatch(self):
        """Dispatch the resident-slot count under the lock and return a
        zero-arg fetch closure — the tier manager's watermark read.
        Split from occupancy() so the lock is not held through the
        device->host scalar sync (the manager fetches on its own
        executor thread, the gubstat discipline)."""
        with self._lock:
            occ = self.table.occupancy()

        def fetch() -> int:
            return int(np.asarray(occ))

        return fetch

    def demote_extract_dispatch(self, protect_fps: np.ndarray,
                                batch: int, take: Optional[int] = None,
                                start: int = 0):
        """ONE donated ops/state.demote_extract dispatch under the lock:
        the device picks up to `take` (`batch` where None) of the
        coldest unprotected live bucket rows — ties from block `start`
        on — gathers their fields, and clears the slots atomically.
        Returns a zero-arg fetch closure yielding (packed int64
        [10, batch] in DEMOTE_ROW_FIELDS order, float64[batch]
        remaining_f) — dispatched and fetched on the tier manager's
        thread; the lock's hold is the ledger's `tier.lock`."""
        from gubernator_tpu.ops.state import demote_extract

        now = np.int64(self.clock.millisecond_now())
        fps = np.asarray(protect_fps, dtype=np.int64)
        take = np.int32(batch if take is None else min(take, batch))
        start = np.int32(start)
        with self._lock, self._stages.stage("tier.lock", "tier"):
            self.table, packed, rf = demote_extract(
                self.table, fps, now, take, start,
                ways=self.cfg.ways, batch=batch,
            )

        def fetch():
            return (
                fetch_ravel([packed])[0].reshape(10, batch),
                f64bits.from_bits(fetch_ravel([rf])[0]),
            )

        return fetch

    def migrate_inject_dispatch(self, cols: Dict[str, np.ndarray]):
        """Dispatch-only form of migrate_inject_rows for the tier
        promote path: the donated upsert-or-merge chunks go out under
        the lock, each in a launch of the size it carries (the step's
        ladder of widths, `self._tiers`); the returned fetch closure
        resolves the (injected, merged) counts off the runner thread.
        Same kernel, same merge algebra — only the host sync moves.
        The ledger's lane `tier` gets the lock's hold (`tier.lock`) and,
        on `tier.promote`, the launches, their lanes, the rows they
        carry (`rows_injected`) and those that met a resident row
        (`rows_merged`)."""
        from gubernator_tpu.ops.state import migrate_inject

        now = np.int64(self.clock.millisecond_now())
        fps = np.asarray(cols["key_hash"], dtype=np.int64)
        live = np.flatnonzero(fps)      # 0 = padding, as everywhere
        fps = fps[live]
        n = len(fps)

        # locate_slots resolves at most INSERT_ROUNDS (= 3) same-bucket
        # insert conflicts per dispatch; a 4th contender ends transient
        # and load_rows drops it — losing the row's consumed budget.
        # Spread same-bucket rows across successive dispatches so every
        # lane can claim a slot: a row's wave is its rank among the rows
        # of its bucket, three a wave.
        nb = self.cfg.num_slots // self.cfg.ways
        bucket = fps.view(np.uint64) & np.uint64(nb - 1)
        order = np.argsort(bucket, kind="stable")
        sb = bucket[order]
        first = np.flatnonzero(np.r_[True, sb[1:] != sb[:-1]])
        rank = np.arange(n) - np.repeat(first, np.diff(np.r_[first, n]))
        wave = rank // 3
        B = self._tiers[-1]
        chunks = []
        for w in range(int(wave.max()) + 1 if n else 0):
            widx = live[order[wave == w]]
            chunks += [widx[lo:lo + B] for lo in range(0, len(widx), B)]
        # Built before the lock: the served path waits for the
        # dispatches alone.
        batches = [
            _bucket_rows(
                cols, sel, next(t for t in self._tiers if t >= len(sel))
            )
            for sel in chunks
        ]
        resident_devs = []
        if batches:
            with self._lock, self._stages.stage("tier.lock", "tier"):
                for rows in batches:
                    self.table, resident = migrate_inject(
                        self.table, rows, now, ways=self.cfg.ways
                    )
                    resident_devs.append(resident)
            self._stages.tally(
                "tier", "tier.promote", inject_launches=len(batches),
                inject_lanes=sum(len(r.key_hash) for r in batches),
                rows_injected=n,
            )

        def fetch():
            injected = merged = 0
            for res, rows in zip(fetch_ravel(resident_devs), batches):
                act = rows.key_hash != 0
                res = np.asarray(res)
                injected += int((act & ~res).sum())
                merged += int((act & res).sum())
            if merged:
                self._stages.tally("tier", "tier.promote",
                                   rows_merged=merged)
            return injected, merged

        return fetch


def _bucket_rows(cols: Dict[str, np.ndarray], sel: np.ndarray,
                 width: int):
    """Rows `sel` of COLD_FIELDS columns as one `width`-lane BucketRows
    (ops/step.py), the lanes past them inactive."""
    from gubernator_tpu.ops.step import BucketRows

    def col(f, dt):
        out = np.zeros(width, dtype=dt)
        out[:len(sel)] = np.asarray(cols[f], dtype=dt)[sel]
        return out

    return BucketRows(
        key_hash=col("key_hash", np.int64),
        algo=col("algo", np.int32),
        limit=col("limit", np.int64),
        duration=col("duration", np.int64),
        remaining=col("remaining", np.int64),
        remaining_f=f64bits.to_bits(col("remaining_f", np.float64)),
        t0=col("t0", np.int64),
        status=col("status", np.int32),
        burst=col("burst", np.int64),
        expire_at=col("expire_at", np.int64),
    )


class Tally(NamedTuple):
    """Per-call metric increments (gubernator.go:59-113 counters)."""

    checks: int
    over_limit: int
    not_persisted: int
    cache_hits: int = 0


def resp_rounds_to_host(round_resps) -> List[Dict[str, np.ndarray]]:
    """DMA one list of device Resp rounds to host numpy dicts (single sync)."""
    return [
        {
            "status": np.asarray(r.status),
            "remaining": np.asarray(r.remaining),
            "reset_time": np.asarray(r.reset_time),
            "limit": np.asarray(r.limit),
            "persisted": np.asarray(r.persisted),
            "found": np.asarray(r.found),
            "stored": np.asarray(r.stored),
            "cached": np.asarray(r.cached),
            "stored_status": np.asarray(r.stored_status),
        }
        for r in round_resps
    ]


def fetch_ravel(arrs) -> List[np.ndarray]:
    """Many device arrays to the host behind ONE wait: every copy is
    started before the first is read, so a merge's N response buffers
    travel together.  No program runs, on purpose: a concatenate on the
    device is one XLA program per SEQUENCE of shapes, compiled on the
    request path, inside the drain, the first time a drain has more
    rounds than any before it (PERF.md section 6, PR 39)."""
    if not arrs:
        return []
    with tracing.stage("backend.d2h_wait"):
        for a in arrs:
            a.copy_to_host_async()
        return [np.asarray(a) for a in arrs]


# apply_batch_packed_q's response rows, in order.
RESP_FIELDS = (
    "status", "limit", "remaining", "reset_time", "persisted", "found",
    "stored", "cached", "stored_status",
)


class PackedResp(dict):
    """_packed_resp_dict's named columns, with the fetched words they are
    views of: the compiled lane's native gather reads `words` in one
    pass (fastpath._resp_words) where the object path indexes columns."""

    __slots__ = ("words",)


def _packed_resp_dict(a: np.ndarray) -> Dict[str, np.ndarray]:
    """apply_batch_packed_q row order -> named host columns; `a` is
    [9, B] (single table) or [n, 9, B] (grid, leading shard dim)."""
    sl = (slice(None),) * (a.ndim - 2)
    out = PackedResp(
        (f, a[sl + (i,)]) for i, f in enumerate(RESP_FIELDS)
    )
    out.words = a
    return out


def packed_rounds_to_host(round_packed) -> List[Dict[str, np.ndarray]]:
    """Host view of packed int64[9, B] responses (apply_batch_packed_q row
    order) — ONE transfer for all rounds (fetch_ravel)."""
    return [
        _packed_resp_dict(a) for a in fetch_ravel(list(round_packed))
    ]


def tally_from_rounds(rounds, round_host) -> "Tally":
    """Vectorized Tally over packed rounds (active lanes only) — the
    columnar analog of unmarshal_responses' per-request counting.

    Host arrays may be tier-sliced narrower than the round's [.., B]
    masks; lanes beyond the tier are inactive by construction, so the
    mask is sliced to match."""
    checks = over = notp = hits = 0
    for db, h in zip(rounds, round_host):
        act = np.asarray(db.active)[..., : h["status"].shape[-1]]
        checks += int(act.sum())
        over += int(((h["status"] == 1) & act).sum())
        notp += int(((h["persisted"] == 0) & act).sum())
        hits += int(((h["found"] != 0) & act).sum())
    return Tally(checks, over, notp, hits)


def unmarshal_responses(
    n_reqs: int,
    errors: Dict[int, str],
    positions: Sequence[tuple],
    round_host: List[Dict[str, np.ndarray]],
) -> tuple:
    """Build per-request RateLimitResp from packed positions.

    `positions[i]` is (round, *index) where *index indexes the response
    arrays directly — (lane,) for the single-table backend, (shard, lane)
    for the mesh backend.  Returns (responses, Tally).
    """
    out: List[RateLimitResp] = []
    checks = over = notp = hits = 0
    for i in range(n_reqs):
        err = errors.get(i)
        if err is not None:
            out.append(RateLimitResp(error=err))
            continue
        rnd, *idx_l = positions[i]
        idx = tuple(idx_l)
        r = round_host[rnd]
        resp = RateLimitResp(
            status=Status(int(r["status"][idx])),
            limit=int(r["limit"][idx]),
            remaining=int(r["remaining"][idx]),
            reset_time=int(r["reset_time"][idx]),
        )
        out.append(resp)
        checks += 1
        if resp.status == Status.OVER_LIMIT:
            over += 1
        if not r["persisted"][idx]:
            notp += 1
        if r["found"][idx]:
            hits += 1
    return out, Tally(checks, over, notp, hits)


def probe_bucket(
    table: SlotTable,
    lo: int,
    ways: int,
    key: str,
    now: int,
    include_cached: bool = True,
) -> Optional[CacheItem]:
    """Host-side point read of one bucket: DMA `ways` rows starting at `lo`
    and return the live item for `key`, if any (the WorkerPool.GetCacheItem
    analog, workers.go:614-646; expired rows read as misses like
    lrucache.go:115-127).  With include_cached=False, GLOBAL broadcast rows
    (KIND_CACHED_RESP — replicated responses, not bucket state) read as
    misses."""
    from gubernator_tpu.ops.state import KIND_CACHED_RESP

    rows = read_rows(table, slice(lo, lo + ways))
    h = int(np.uint64(key_hash64(key)).view(np.int64))
    for w in range(ways):
        if rows["key"][w] == h and rows["expire_at"][w] > now:
            if not include_cached and rows["kind"][w] == KIND_CACHED_RESP:
                return None
            return _row_to_item(rows, w, key)
    return None


def _row_to_item(snap: Dict[str, np.ndarray], s: int, key: str) -> CacheItem:
    from gubernator_tpu.core.types import Algorithm

    algo = Algorithm(int(snap["algo"][s]))
    remaining: float
    if algo == Algorithm.LEAKY_BUCKET:
        remaining = float(snap["remaining_f"][s])
    else:
        remaining = int(snap["remaining"][s])
    return CacheItem(
        key=key,
        algorithm=algo,
        expire_at=int(snap["expire_at"][s]),
        limit=int(snap["limit"][s]),
        duration=int(snap["duration"][s]),
        remaining=remaining,
        created_at=int(snap["t0"][s]),
        status=Status(int(snap["status"][s])),
        burst=int(snap["burst"][s]),
    )
