"""Mesh-sharded slot table: the multi-chip engine.

The slot table's slot axis is sharded over the mesh `shard` axis
(`NamedSharding(mesh, P("shard"))`); each device owns `num_slots/n` slots and
is the single writer for the keys that hash to it — the same
single-writer-by-placement discipline as the reference worker pool
(workers.go:19-37) and peer ring (architecture.md:13-17), enforced here by
data placement instead of goroutine ownership.

One jitted `shard_map` step applies a [n_shards, batch_size] request block:
each device runs the same branchless kernel (ops/step.py) on its local shard.
The hot path needs NO collectives — routing already placed every request on
its owner — which is exactly why the table is sharded on hash bits rather
than consistent-hashed: placement is static, so the "network hop" of the
reference (peer_client.go) compiles away to local work on the right device.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import gubernator_tpu.ops  # noqa: F401  (enables x64)
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.hashing import key_hash64
from gubernator_tpu.core.types import CacheItem, RateLimitReq, RateLimitResp
from gubernator_tpu.ops import f64bits
from gubernator_tpu.ops.batch import PackedGrid, pack_requests_grid
from gubernator_tpu.ops.devices import device_info
from gubernator_tpu.ops.state import (
    SlotTable,
    init_table,
    read_rows,
    table_to_host,
)
from gubernator_tpu.ops.step import DeviceBatchJ, apply_batch_packed_impl
from gubernator_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_of_hash
from gubernator_tpu.runtime import tracing
from gubernator_tpu.runtime.backend import (
    PersistenceHost,
    _row_to_item,
    probe_bucket,
    declare_launches,
    launch_rounds,
    resolve_tiers,
    unmarshal_responses,
)


def pack_requests_sharded(
    reqs: Sequence[RateLimitReq],
    batch_size: int,
    n_shards: int,
    clock: Optional[clock_mod.Clock] = None,
    use_cached: Optional[Sequence[bool]] = None,
) -> PackedGrid:
    """Route each request to its owning shard and pack per-shard lanes.

    Same contract as ops.batch.pack_requests (validation, duplicate-key
    rounds) with one more coordinate: the shard.  A key's occurrences are
    serialized across rounds; capacity is batch_size lanes per (round, shard).
    """
    return pack_requests_grid(
        reqs,
        batch_size,
        n_shards,
        lambda key: int(shard_of_hash(key_hash64(key), n_shards)),
        clock,
        use_cached,
    )


# -- packed single-transfer hot path ------------------------------------
# A per-field path would cost 12 sharded host->device puts and 9
# device->host reads per round.  Here the whole DeviceBatch travels as
# ONE int64[12, n, B] array and the response returns as ONE
# int64[n, 9, B] array (the mesh analog of ops/step.apply_batch_packed_q).


def pack_grid_batch(db) -> np.ndarray:
    """Stack a [n, B] DeviceBatch into one int64[12, n, B] host array."""
    arrs = [np.asarray(a) for a in db]
    out = np.empty((len(arrs),) + arrs[0].shape, dtype=np.int64)
    for i, a in enumerate(arrs):
        out[i] = a
    return out


def unpack_grid_batch(q) -> DeviceBatchJ:
    """Device-side inverse of pack_grid_batch for one shard block [12, B]."""
    import jax.numpy as jnp

    return DeviceBatchJ(
        key_hash=q[0], hits=q[1], limit=q[2], duration=q[3],
        algo=q[4].astype(jnp.int32), burst=q[5],
        reset_remaining=q[6].astype(bool), is_greg=q[7].astype(bool),
        greg_expire=q[8], greg_duration=q[9],
        active=q[10].astype(bool), use_cached=q[11].astype(bool),
    )


def make_sharded_step_packed(mesh, ways: int):
    """Jitted multi-device step over packed transfers:
    table'[n·S], resp[n, 9, B] = step(table[n·S], batch[12, n, B], now).

    Response row order is apply_batch_packed_q's: status, limit, remaining,
    reset_time, persisted, found, stored, cached, stored_status (one
    shared packer, ops/step.py).
    """

    def _local(table: SlotTable, packed, now):
        b = unpack_grid_batch(packed[:, 0])
        t2, resp = apply_batch_packed_impl(table, b, now, ways=ways)
        return t2, resp[None]

    sharded = _shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def packed_grid_rounds_to_host(round_resps) -> List[Dict[str, np.ndarray]]:
    """Host view of packed [n, 9, B] responses — ONE transfer for all
    rounds (fetch_ravel).  Field arrays are [n, B], so (shard, lane)
    positions index directly."""
    from gubernator_tpu.runtime.backend import (
        _packed_resp_dict,
        fetch_ravel,
    )

    return [
        _packed_resp_dict(a) for a in fetch_ravel(list(round_resps))
    ]


def make_sharded_row_op(mesh, ways: int, impl, row_type):
    """Shared factory for row-upsert collectiveless steps: each shard
    applies `impl` to its routed [B] block of `row_type` rows.  Instances:
    - load_rows_impl/BucketRows — Loader restore / Store.get seeding
      (workers.go:340-426 over the mesh);
    - store_cached_rows_impl/CachedRows — GLOBAL broadcast receive
      (gubernator.go:464-479 over the mesh)."""

    def _local(table: SlotTable, rows, now):
        r = row_type(*[a[0] for a in rows])
        return impl(table, r, now, ways=ways)

    sharded = _shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=P(SHARD_AXIS),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_sharded_probe(mesh, ways: int):
    """Sharded read-only lookup: (found[n,B], local_slot[n,B]) for a
    shard-routed hash grid — one jitted call per chunk instead of per-key
    host probes (the mesh analog of ops/step.probe_batch)."""
    from gubernator_tpu.ops.step import probe_batch_impl

    def _local(table: SlotTable, h, now):
        f, s = probe_batch_impl(table, h[0], now, ways=ways)
        return f[None], s[None]

    sharded = _shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded)


def make_sharded_gather(mesh, ways: int):
    """Sharded columnar row read-back: (int64[n, 10, B] packed CacheItem
    fields in ops/step.GATHER_ROW_FIELDS order, int64[n, B]
    remaining_f bits) for a shard-routed hash grid — one sync where per-field
    fancy-index reads would cost a transfer each (the mesh analog of
    ops/step.gather_rows; the fast lane's Store.on_change capture)."""
    from gubernator_tpu.ops.step import gather_rows_impl

    def _local(table: SlotTable, h, now):
        packed, rf = gather_rows_impl(table, h[0], now, ways=ways)
        return packed[None], rf[None]

    sharded = _shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded)


def make_sharded_demote_extract(mesh, ways: int, batch: int):
    """Sharded tier demotion (docs/tiering.md): every shard runs
    ops/state.demote_extract_impl on its slice in the same donated
    dispatch — each picks its own `batch` coldest eligible residents
    (victim choice is slice-local, exactly like bucket-local pseudo-LRU
    is bucket-local), gathers and clears them atomically.  The protect
    fingerprint grid is replicated (P()): a shadow key only matches on
    its home shard, so protection is exact.  `take` (rows a shard,
    at most `batch`) and `start` (the block a shard takes tied stamps
    from) are replicated too.  Output carries the leading
    [n] shard axis: packed int64[n, 10, batch] (DEMOTE_ROW_FIELDS
    order), remaining_f bits int64[n, batch]."""
    from gubernator_tpu.ops.state import demote_extract_impl

    def _local(table: SlotTable, protect, take, start, now):
        t2, packed, rf = demote_extract_impl(
            table, protect, now, take, start, ways=ways, batch=batch
        )
        return t2, packed[None], rf[None]

    sharded = _shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(), P(), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_sharded_table_stats(mesh, ways: int):
    """Sharded state census (docs/observability.md): every shard runs
    ops/state.table_stats_impl on its slice in one read-only pass and
    keeps its own row — the output carries a leading [n] shard axis on
    every TableStats leaf, so the host gets per-shard occupancy/fill
    for free and sums for cluster totals.  The shadow fingerprint grid
    is replicated (P()): a derived key only matches on its home shard
    (inserts used the same bucket math), so per-class census sums
    across shards are exact, never double counted."""
    from gubernator_tpu.ops.state import TableStats, table_stats_impl

    def _local(table: SlotTable, shadow_fps, now):
        st = table_stats_impl(table, shadow_fps, now, ways=ways)
        return TableStats(*[a[None] for a in st])

    sharded = _shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()),
        out_specs=P(SHARD_AXIS),
    )
    return jax.jit(sharded)


@functools.lru_cache(maxsize=8)
def _sharded_init(sharding):
    return jax.jit(init_table, static_argnums=0, out_shardings=sharding)


def init_sharded_table(num_slots: int, sharding) -> SlotTable:
    """All-empty table laid out by `sharding`: each shard's slice is
    zero-filled on its own device, so the whole table never exists on
    device 0 first."""
    return _sharded_init(sharding)(num_slots)


def drain_to_grids(per_shard: List[list], B: int, make_grid, fill_lane):
    """Drain per-shard row lists into consecutive [n, B] grids (overflow
    chunks into extra grids).  `fill_lane(grid, shard, lane, row)` writes
    one row; yields each full grid."""
    while any(per_shard):
        grid = make_grid()
        for s in range(len(per_shard)):
            take, per_shard[s] = per_shard[s][:B], per_shard[s][B:]
            for lane, row in enumerate(take):
                fill_lane(grid, s, lane, row)
        yield grid


class MeshBackend(PersistenceHost):
    """Drop-in peer of runtime.backend.DeviceBackend over a device mesh."""

    def __init__(
        self,
        cfg: DeviceConfig,
        clock: Optional[clock_mod.Clock] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        metrics=None,
        store=None,
        track_keys: bool = False,
    ) -> None:
        self.metrics = metrics
        self._stages = tracing.ledger_of(metrics)
        self.store = store
        self._keymap: Optional[Dict[int, str]] = (
            {} if (store is not None or track_keys) else None
        )
        if cfg.num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        self.cfg = cfg
        self.clock = clock or clock_mod.default_clock()
        self._lock = threading.Lock()
        self._init_write_through()
        self.mesh = make_mesh(cfg.num_shards, devices, cfg.platform)
        self.local_slots = cfg.num_slots // cfg.num_shards
        nb_local = self.local_slots // cfg.ways
        if nb_local & (nb_local - 1):
            raise ValueError(
                f"buckets per shard ({nb_local}) must be a power of two"
            )
        self._tsharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self._bsharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self.table: SlotTable = init_sharded_table(
            cfg.num_slots, self._tsharding
        )
        from gubernator_tpu.ops.step import (
            BucketRows,
            CachedRows,
            load_rows_impl,
            store_cached_rows_impl,
        )

        self._step_packed = make_sharded_step_packed(self.mesh, cfg.ways)
        # Batch-shape tiers (see DeviceConfig.batch_tiers): sparse rounds
        # ship a sliced [12, n, t] block instead of the full batch shape.
        self._tiers = resolve_tiers(cfg)
        declare_launches(self._stages, self._tiers, "mach", "direct")
        # Batch input sharding: [12, n, B] split on the shard axis (dim 1).
        self._psharding = NamedSharding(self.mesh, P(None, SHARD_AXIS))
        self._cached_store = make_sharded_row_op(
            self.mesh, cfg.ways, store_cached_rows_impl, CachedRows
        )
        self._load_rows_sharded = make_sharded_row_op(
            self.mesh, cfg.ways, load_rows_impl, BucketRows
        )
        self._probe_sharded = make_sharded_probe(self.mesh, cfg.ways)
        self._gather_sharded = make_sharded_gather(self.mesh, cfg.ways)
        self._table_stats = make_sharded_table_stats(self.mesh, cfg.ways)
        self.checks = 0
        self.over_limit = 0
        self.not_persisted = 0

    def device_info(self) -> dict:
        return device_info(
            list(self.mesh.devices.flat), self.cfg.platform
        )

    def _add_tally(self, tally) -> None:
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
        packed = pack_requests_sharded(
            reqs, self.cfg.batch_size, self.cfg.num_shards, self.clock,
            use_cached,
        )
        now_ms = self.clock.millisecond_now()
        if self._keymap is not None:
            with self._keymap_lock:
                for i, r in enumerate(reqs):
                    if i not in packed.errors:
                        k = r.hash_key()
                        self._keymap[key_hash64(k)] = k
            self._maybe_prune_keymap()

        import time as time_mod

        captured = None
        t_start = time_mod.monotonic()
        lock_wait = self._stages.stage("backend.lock_wait")
        with self._lock:
            lock_wait.end()
            if self.store is not None:
                self._seed_from_store(reqs, packed, now_ms)
            # ONE sharded put a round, ONE packed readback.
            round_resps = self._dispatch_rounds_locked(
                packed.rounds, now_ms
            )
            if self.store is not None:
                # Read-back inside the lock: a concurrent batch must not
                # mutate a key between this batch's step and on_change.
                captured = self._capture_write_through(
                    reqs, packed, use_cached
                )
                wt_seq = self._wt_ticket()
        try:
            step_s = time_mod.monotonic() - t_start
            out, tally = unmarshal_responses(
                len(reqs), packed.errors, packed.positions,
                packed_grid_rounds_to_host(round_resps),
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
            # Redeem the ticket even if unmarshal fails (see
            # DeviceBackend.check) — unredeemed tickets wedge delivery.
            if captured is not None:
                self._deliver_write_through(captured, wt_seq)
        return out

    def step_rounds(
        self, rounds: Sequence, add_tally: bool = True
    ) -> List[Dict[str, np.ndarray]]:
        """Columnar hot path over the mesh: apply pre-packed [n, B] grid
        DeviceBatch rounds (the compiled fast lane, runtime/fastpath.py).
        No persistence hooks — the fast lane requires no attached Store.
        Returns [n, B]-shaped host response dicts per round."""
        return self.step_rounds_begin(rounds, add_tally)()

    def step_rounds_begin(self, rounds: Sequence, add_tally: bool = True):
        """Pipelined step_rounds (see DeviceBackend.step_rounds_begin):
        dispatch under the lock, return the host-fetch closure — the
        sharded responses are pinned to this table version, so the fetch
        may run while the next merge dispatches."""
        from gubernator_tpu.runtime.backend import tally_from_rounds

        lock_wait = self._stages.stage("backend.lock_wait")
        with self._lock:
            lock_wait.end()
            round_resps = self._dispatch_rounds_locked(rounds)

        def fetch() -> List[Dict[str, np.ndarray]]:
            host = packed_grid_rounds_to_host(round_resps)
            if add_tally:
                self._add_tally(tally_from_rounds(rounds, host))
            return host

        return fetch

    def _dispatch_rounds_locked(self, rounds, now=None) -> list:
        """Dispatch grid rounds under the clock `now`; caller holds
        `_lock` (see DeviceBackend._dispatch_rounds_locked: one clock a
        drain; None reads it here)."""
        now = np.int64(self.clock.millisecond_now() if now is None else now)

        def launch(words):
            batch = jax.device_put(words, self._psharding)
            self.table, resp = self._step_packed(self.table, batch, now)
            return resp

        return launch_rounds(
            self._stages, rounds, self._tiers, launch, pack_grid_batch,
            self.cfg.num_shards,
        )

    def warmup(self, tier=None) -> None:
        """Compile the sharded executables with a synthetic batch that
        BYPASSES the Store/keymap hooks and the tallies — a check() here
        would leak '__warmup__' keys into an attached store (the same
        bypass DeviceBackend.warmup applies).  `tier` (the daemon's
        TierConfig where the two-tier table is on) is DeviceBackend's:
        the mesh compiles its demote program a width at first use."""
        reqs = [
            RateLimitReq(name="__warmup__", unique_key=f"w{s}", hits=0,
                         limit=1, duration=1)
            for s in range(self.cfg.num_shards)
        ]
        packed = pack_requests_sharded(
            reqs, self.cfg.batch_size, self.cfg.num_shards, self.clock
        )
        now = np.int64(self.clock.millisecond_now())
        with self._lock:
            # Compile the sharded step at EVERY batch tier.
            for t in self._tiers:
                batch = jax.device_put(
                    np.zeros(
                        (12, self.cfg.num_shards, t), dtype=np.int64
                    ),
                    self._psharding,
                )
                self.table, resp = self._step_packed(self.table, batch, now)
            for db in packed.rounds:
                batch = jax.device_put(pack_grid_batch(db), self._psharding)
                self.table, resp = self._step_packed(self.table, batch, now)
            # Probe + broadcast-receive executables (store seeding,
            # UpdatePeerGlobals paths) — zero grids, no side effects.
            from gubernator_tpu.ops.step import CachedRows

            zeros = jax.device_put(
                np.zeros(
                    (self.cfg.num_shards, self.cfg.batch_size),
                    dtype=np.int64,
                ),
                self._bsharding,
            )
            self._probe_sharded(self.table, zeros, now)
            self._gather_sharded(self.table, zeros, now)
            # Gubstat census executable at the sampler's minimum shadow
            # pad tier (runtime/gubstat.py pads to powers of two from 8).
            self._table_stats(
                self.table, np.zeros((4, 8), dtype=np.int64), now
            )
            self.table = self._cached_store(
                self.table,
                CachedRows(*[
                    jax.device_put(a, self._bsharding)
                    for a in self._zero_cached_grid()
                ]),
                now,
            )
        jax.block_until_ready(resp)

    # -- GLOBAL broadcast receive ----------------------------------------
    def _zero_cached_grid(self):
        from gubernator_tpu.ops.step import CachedRows

        n, B = self.cfg.num_shards, self.cfg.batch_size
        return CachedRows(
            key_hash=np.zeros((n, B), dtype=np.int64),
            algo=np.zeros((n, B), dtype=np.int32),
            limit=np.zeros((n, B), dtype=np.int64),
            remaining=np.zeros((n, B), dtype=np.int64),
            status=np.zeros((n, B), dtype=np.int32),
            reset_time=np.zeros((n, B), dtype=np.int64),
        )

    def apply_cached_rows(self, rows: Sequence[tuple]) -> None:
        """Upsert owner-broadcast statuses, routed to their shards: rows of
        (hash_key_str, algorithm, limit, remaining, status, reset_time)."""
        n, B = self.cfg.num_shards, self.cfg.batch_size
        now = np.int64(self.clock.millisecond_now())
        if self._keymap is not None:
            with self._keymap_lock:
                for key, *_ in rows:
                    self._keymap[key_hash64(key)] = key
        per_shard: List[list] = [[] for _ in range(n)]
        for row in rows:
            h = key_hash64(row[0])
            per_shard[int(shard_of_hash(h, n))].append(row)

        def fill(grid, s, lane, row):
            key, algo, limit, rem, status, reset = row
            grid.key_hash[s, lane] = np.int64(
                np.uint64(key_hash64(key)).view(np.int64)
            )
            grid.algo[s, lane] = algo
            grid.limit[s, lane] = limit
            grid.remaining[s, lane] = rem
            grid.status[s, lane] = status
            grid.reset_time[s, lane] = reset

        for grid in drain_to_grids(per_shard, B, self._zero_cached_grid,
                                   fill):
            with self._lock:
                self.table = self._cached_store(
                    self.table,
                    type(grid)(*[
                        jax.device_put(a, self._bsharding) for a in grid
                    ]),
                    now,
                )

    # -- point reads / persistence ---------------------------------------
    def bucket_offset(self, key: str, shard: int) -> int:
        """Global row index of `key`'s bucket within `shard`'s table block."""
        nb_local = self.local_slots // self.cfg.ways
        bucket = key_hash64(key) & (nb_local - 1)
        return shard * self.local_slots + bucket * self.cfg.ways

    def get_cache_item(self, key: str) -> Optional[CacheItem]:
        shard = int(shard_of_hash(key_hash64(key), self.cfg.num_shards))
        lo = self.bucket_offset(key, shard)
        now = self.clock.millisecond_now()
        with self._lock:
            return probe_bucket(self.table, lo, self.cfg.ways, key, now)

    def _probe_nolock(
        self, key: str, now: int, include_cached: bool
    ) -> Optional[CacheItem]:
        shard = int(shard_of_hash(key_hash64(key), self.cfg.num_shards))
        lo = self.bucket_offset(key, shard)
        return probe_bucket(
            self.table, lo, self.cfg.ways, key, now,
            include_cached=include_cached,
        )

    # -- persistence device hooks (PersistenceHost) ----------------------
    def _probe_grid(
        self, keys: Sequence[str], hashes, now: int,
        table: Optional[SlotTable] = None, route=None,
    ):
        """Shard-routed batched probes: (found, global_slot) per key, in
        key order, one jitted probe per chunk (lock held).

        `table`/`route` default to the auth table with owner routing; the
        GlobalEngine passes its replicated cache table with arrival-device
        routing."""
        if table is None:
            table = self.table
        # Table geometry may differ from the auth table's (the GlobalEngine
        # cache can be smaller via global_cache_slots).
        local_slots = table.key.shape[0] // self.cfg.num_shards
        n, B = self.cfg.num_shards, self.cfg.batch_size
        if route is None:
            route = lambda h: int(shard_of_hash(h, n))  # noqa: E731
        per_shard: List[list] = [[] for _ in range(n)]
        for j, h in enumerate(hashes):
            per_shard[route(h)].append((j, h))

        found = np.zeros(len(keys), dtype=bool)
        gslot = np.zeros(len(keys), dtype=np.int64)

        def make_grid():
            return [
                np.zeros((n, B), dtype=np.int64),  # hashes
                np.full((n, B), -1, dtype=np.int64),  # original index
            ]

        def fill(grid, s, lane, row):
            j, h = row
            grid[0][s, lane] = np.int64(np.uint64(h).view(np.int64))
            grid[1][s, lane] = j

        for hv, jv in drain_to_grids(per_shard, B, make_grid, fill):
            f, slot = self._probe_sharded(
                table,
                jax.device_put(hv, self._bsharding),
                np.int64(now),
            )
            f, slot = np.asarray(f), np.asarray(slot)
            for s in range(n):
                sel = jv[s] >= 0
                js = jv[s][sel]
                found[js] = f[s][sel]
                gslot[js] = s * local_slots + slot[s][sel]
        return found, gslot

    def _found_mask(self, keys, hashes, now: int) -> np.ndarray:
        found, _ = self._probe_grid(keys, hashes, now)
        return found

    def _gather_rows_dispatch(self, h64: np.ndarray, now: int):
        """Dispatch shard-routed columnar row gathers for int64
        fingerprints (lock held).  Returns an opaque token for
        `_gather_rows_finish`: the dispatched reads are pinned to this
        table version (jax arrays are immutable), so the caller may
        release the lock before fetching."""
        n, B = self.cfg.num_shards, self.cfg.batch_size
        sh = shard_of_hash(h64, n)
        per_shard: List[list] = [[] for _ in range(n)]
        for j, h in enumerate(h64):
            per_shard[int(sh[j])].append((j, int(h)))

        def make_grid():
            return [
                np.zeros((n, B), dtype=np.int64),
                np.full((n, B), -1, dtype=np.int64),
            ]

        def fill(grid, s, lane, row):
            j, h = row
            grid[0][s, lane] = h
            grid[1][s, lane] = j

        token = []
        for hv, jv in drain_to_grids(per_shard, B, make_grid, fill):
            token.append((
                self._gather_sharded(
                    self.table,
                    jax.device_put(hv, self._bsharding),
                    np.int64(now),
                ),
                jv,
            ))
        return token

    def _gather_rows_int_arrays(self, token) -> list:
        """The token's int64 device buffers — exposed so a caller can fold
        them into ONE fetch_ravel round-trip with its response buffers."""
        return [d for (d, _rf), _jv in token]

    def _gather_rows_rf_arrays(self, token) -> list:
        return [rf for (_d, rf), _jv in token]

    def _gather_rows_build(self, token, m: int, int_hosts,
                           rf_hosts=None):
        """Assemble (int64[10, m] GATHER_ROW_FIELDS columns, float64[m]
        remaining_f) from pre-fetched host chunks via each chunk's
        shard/lane placement grid.  rf_hosts=None -> zeros (no leaky row
        captured)."""
        from gubernator_tpu.ops.step import GATHER_ROW_FIELDS

        out = np.zeros((len(GATHER_ROW_FIELDS), m), dtype=np.int64)
        rf = np.zeros(m, dtype=np.float64)
        for i, (_devs, jv) in enumerate(token):
            a = int_hosts[i]     # [n_shards, 10, B]
            # remaining_f arrives as its bits (ops/f64bits.py).
            f = (f64bits.from_bits(rf_hosts[i])
                 if rf_hosts is not None else None)
            for s in range(a.shape[0]):
                sel = jv[s] >= 0
                if sel.any():
                    out[:, jv[s][sel]] = a[s][:, sel]
                    if f is not None:
                        rf[jv[s][sel]] = f[s][sel]
        return out, rf

    def _gather_rows_finish(self, token, m: int):
        """Fetch + assemble in two packed round-trips (ints, rf)."""
        from gubernator_tpu.runtime.backend import fetch_ravel

        return self._gather_rows_build(
            token, m,
            fetch_ravel(self._gather_rows_int_arrays(token)),
            fetch_ravel(self._gather_rows_rf_arrays(token)),
        )

    def _bulk_upsert(
        self, rows: List[dict], hashes: List[int], now: int
    ) -> None:
        """Route row dicts to their shards and upsert via the sharded
        load_rows step (lock held)."""
        self.table = self._bulk_upsert_into(self.table, rows, hashes, now)

    def _bulk_upsert_into(
        self, table: SlotTable, rows: List[dict], hashes: List[int],
        now: int, route=None,
    ) -> SlotTable:
        """Upsert row dicts into `table` with `route` (defaults to owner
        routing); returns the new table.  The GlobalEngine seeds its cache
        table through this with arrival-device routing (lock held)."""
        from gubernator_tpu.ops.step import BucketRows

        n, B = self.cfg.num_shards, self.cfg.batch_size
        if route is None:
            route = lambda h: int(shard_of_hash(h, n))  # noqa: E731
        per_shard: List[list] = [[] for _ in range(n)]
        for row, h in zip(rows, hashes):
            per_shard[route(h)].append((h, row))
        fields = (
            "algo", "limit", "duration", "remaining", "remaining_f",
            "t0", "status", "burst", "expire_at",
        )

        def make_grid():
            return BucketRows(
                key_hash=np.zeros((n, B), dtype=np.int64),
                **{
                    f: np.zeros(
                        (n, B),
                        dtype=np.float64 if f == "remaining_f" else (
                            np.int32 if f in ("algo", "status") else np.int64
                        ),
                    )
                    for f in fields
                },
            )

        def fill(grid, s, lane, row):
            h, rd = row
            grid.key_hash[s, lane] = np.int64(np.uint64(h).view(np.int64))
            for f in fields:
                getattr(grid, f)[s, lane] = rd[f]

        for grid in drain_to_grids(per_shard, B, make_grid, fill):
            grid = grid._replace(
                remaining_f=f64bits.to_bits(grid.remaining_f)
            )
            table = self._load_rows_sharded(
                table,
                type(grid)(*[
                    jax.device_put(a, self._bsharding) for a in grid
                ]),
                np.int64(now),
            )
        return table

    def read_items_bulk(
        self, keys: Sequence[str], include_cached: bool = False
    ) -> Dict[str, CacheItem]:
        """Batched point-reads (write-through readback): one sharded probe
        per chunk + one fancy-index gather per table field."""
        with self._lock:
            return self._read_items_locked(keys, include_cached)

    def _read_items_locked(
        self, keys: Sequence[str], include_cached: bool = False
    ) -> Dict[str, CacheItem]:
        """read_items_bulk body; caller holds `_lock` (write-through capture
        reads back rows within the same critical section as the step)."""
        from gubernator_tpu.ops.state import KIND_CACHED_RESP

        now = self.clock.millisecond_now()
        hashes = [key_hash64(k) for k in keys]
        out: Dict[str, CacheItem] = {}
        found, gslot = self._probe_grid(keys, hashes, now)
        if not found.any():
            return out
        sel = np.flatnonzero(found)
        rows = read_rows(self.table, gslot[sel])
        for r_i, j in enumerate(sel):
            if rows["kind"][r_i] == KIND_CACHED_RESP and not include_cached:
                continue
            out[keys[j]] = _row_to_item(rows, r_i, keys[j])
        return out

    def snapshot(self) -> Dict[str, np.ndarray]:
        with self._lock:
            return table_to_host(self.table)

    def _install_table(self, arrays: Dict[str, np.ndarray]) -> None:
        """Replace the sharded table from host arrays (checkpoint restore):
        orbax round-trips the host copy; placement re-shards over the mesh.
        """
        from gubernator_tpu.ops.state import table_from_host

        if arrays["key"].shape[0] != self.cfg.num_slots:
            raise ValueError(
                f"checkpoint has {arrays['key'].shape[0]} slots, backend "
                f"expects {self.cfg.num_slots}"
            )
        with self._lock:
            self.table = jax.device_put(
                table_from_host(arrays), self._tsharding
            )

    def occupancy(self) -> int:
        with self._lock:
            return int(np.asarray(self.table.occupancy()))

    def shard_occupancy(self) -> List[int]:
        """Live rows PER SHARD (one device reduce + one [n] fetch) — the
        skew view the aggregate occupancy() hides: hash routing spreads
        keys uniformly in expectation, but a production key set can pile
        onto one shard, and only the per-shard counts show it
        (/debug/vars `shard_occupancy`, gubernator_shard_occupancy)."""
        import jax.numpy as jnp

        with self._lock:
            counts = jnp.sum(
                self.table.key.occupied().reshape(
                    self.cfg.num_shards, self.local_slots
                ),
                axis=1,
            )
        return [int(c) for c in np.asarray(counts)]

    def table_stats_dispatch(self, shadow_fps: np.ndarray):
        """Dispatch the sharded gubstat census under the lock and return
        a zero-arg fetch closure (DeviceBackend.table_stats_dispatch's
        contract: every fetched TableStats leaf carries a leading shard
        axis — here one row per mesh shard, so the sampler gets the
        per-shard occupancy skew for free and sums for totals)."""
        from gubernator_tpu.ops.state import TableStats

        now = np.int64(self.clock.millisecond_now())
        fps = np.asarray(shadow_fps, dtype=np.int64)
        with self._lock:
            st = self._table_stats(self.table, fps, now)

        def fetch() -> "TableStats":
            return TableStats(*[np.asarray(a) for a in st])

        return fetch

    # -- tiered table (runtime/coldtier.py; docs/tiering.md) -------------
    def occupancy_dispatch(self):
        """Dispatch the cluster resident count under the lock; the
        returned zero-arg fetch closure pulls the scalar off the runner
        (DeviceBackend.occupancy_dispatch's contract)."""
        with self._lock:
            occ = self.table.occupancy()

        def fetch() -> int:
            return int(np.asarray(occ))

        return fetch

    def demote_extract_dispatch(self, protect_fps: np.ndarray,
                                batch: int, take: Optional[int] = None,
                                start: int = 0):
        """Sharded demote: each shard picks its own share of `take`
        (`batch` where None) among its coldest unprotected rows (victim
        choice is slice-local, like the bucket-local pseudo-LRU), so
        one dispatch yields up to n_shards*batch candidates.  Fetch
        flattens the per-shard planes back to the DeviceBackend
        contract: (int64[10, n*batch], float64[n*batch])."""
        if not hasattr(self, "_demote_cache"):
            self._demote_cache = {}
        fn = self._demote_cache.get(batch)
        if fn is None:
            fn = make_sharded_demote_extract(
                self.mesh, self.cfg.ways, batch
            )
            self._demote_cache[batch] = fn

        now = np.int64(self.clock.millisecond_now())
        fps = np.asarray(protect_fps, dtype=np.int64)
        n = self.cfg.num_shards
        share = np.int32(
            batch if take is None else min(-(-take // n), batch)
        )
        with self._lock:
            self.table, packed, rf = fn(
                self.table, fps, share, np.int32(start), now
            )

        def fetch():
            p = np.asarray(packed)  # [n, 10, batch]
            r = f64bits.from_bits(np.asarray(rf))  # [n, batch]
            return (
                np.concatenate([p[s] for s in range(p.shape[0])],
                               axis=1),
                r.reshape(-1),
            )

        return fetch

    def migrate_inject_dispatch(self, cols: Dict[str, np.ndarray]):
        """Promote-path inject for the mesh: the generic
        PersistenceHost.migrate_inject_rows path already serializes on
        self._lock, so the whole probe+upsert+merge runs inside the
        fetch closure on the tier manager's executor thread — same
        lock discipline, same (injected, merged) result."""
        def fetch():
            return self.migrate_inject_rows(cols)

        return fetch
