"""GLOBAL behavior on the mesh: hot-key replication with collective sync.

Reference semantics (global.go:33-254, gubernator.go:420-479): a GLOBAL key
is served from the local cache on ANY peer — a live owner-broadcast status
answers verbatim; a miss is processed locally "like we own it" — while every
hit is queued, aggregated by key, flushed to the owning peer, applied there,
and the authoritative status broadcast back to all peers.  Stale-but-fast
reads; owner-authoritative eventual consistency.

TPU re-expression: devices are the peers.  Every device keeps a local CACHE
table (replicated serving state — any device can answer any GLOBAL key, which
is what lets a hot key scale past its owner's lanes); the authoritative state
lives in the owner's shard of the AUTH table (the same sharded table as the
non-GLOBAL path).  One jitted collective step replaces the reference's two
RPC loops (sendHits + broadcastPeers):

    all_to_all   hit deltas  ->  owner      (sendHits,  global.go:124-164)
    apply        merged hits ->  auth shard (GetPeerRateLimits server side)
    hits=0 read  broadcast rows              (broadcastPeers re-read :214-217)
    all_gather   rows -> every cache shard  (UpdatePeerGlobals, :464-479)

The DEFAULT sync collective (make_global_sync_step_psum) collapses the
first step further: because the host pending dict already merged
duplicate keys and the chunk builder gives each key a globally unique
(owner, lane) slot, hit aggregation is ONE `psum` over the shard axis —
no all_to_all, no device-side sort/segment merge.  Intra-mesh "peers"
never touch the network: UpdatePeerGlobals between shards IS the
all_gather, and the RPC plane (PeerClient) is engaged only for
cross-daemon peers (service._engine_synced) — the hybrid ring topology
where daemon-level arcs of the consistent-hash ring map to meshes and
mesh-level arcs map to shards.

One deliberate deviation from the reference: the owner device also serves
GLOBAL reads from its replicated cache rather than answering authoritatively
(reference gubernator.go:272-283 answers authoritatively on the owner node).
Routing GLOBAL traffic by owner would re-concentrate exactly the hot keys
GLOBAL exists to spread; the eventual-consistency contract is unchanged.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gubernator_tpu.core.types import RateLimitReq, RateLimitResp
from gubernator_tpu.ops.batch import pack_requests_grid
from gubernator_tpu.ops.state import SlotTable
from gubernator_tpu.ops.step import (
    CachedRows,
    DeviceBatchJ,
    apply_batch_impl,
    store_cached_rows_impl,
)
from gubernator_tpu.parallel.mesh import SHARD_AXIS, shard_of_hash
from gubernator_tpu.parallel.sharded import (
    MeshBackend,
    _shard_map,
    init_sharded_table,
    pack_grid_batch,
    packed_grid_rounds_to_host,
)
from gubernator_tpu.runtime.backend import (
    declare_launches,
    launch_rounds,
    unmarshal_responses,
)


class DeltaGrid(NamedTuple):
    """Per-(source, owner) aggregated hit deltas: arrays [n_src, n_dst, D].

    The device form of globalManager's `hits map[string]*RateLimitReq`
    (global.go:87-95), already partitioned by owning shard.
    """

    key_hash: np.ndarray   # int64
    hits: np.ndarray       # int64 (summed per key)
    limit: np.ndarray      # int64
    duration: np.ndarray   # int64
    algo: np.ndarray       # int32
    burst: np.ndarray      # int64
    is_greg: np.ndarray    # bool
    greg_expire: np.ndarray   # int64
    greg_duration: np.ndarray  # int64


def make_global_sync_step(mesh, ways: int):
    """Build the jitted collective sync:
    (auth, cache, delta, now) -> (auth', cache')."""

    def _global_sync_a2a(
        auth: SlotTable, cache: SlotTable, delta: DeltaGrid, now
    ):
        d = DeltaGrid(*[a[0] for a in delta])  # local [n_dst, D]
        # sendHits: deltas travel to their owning shard over ICI.
        recv = DeltaGrid(
            *[
                jax.lax.all_to_all(a, SHARD_AXIS, split_axis=0, concat_axis=0)
                for a in d
            ]
        )  # [n_src, D] — this device's keys, from every source
        key = recv.key_hash.reshape(-1)
        b2 = key.shape[0]

        # Merge duplicates across sources (same key hit on several devices):
        # sort by key, segment-sum hits into the first occurrence.
        order = jnp.argsort(key)
        ks = key[order]
        first = jnp.concatenate(
            [jnp.ones((1,), dtype=bool), ks[1:] != ks[:-1]]
        )
        seg = jnp.cumsum(first) - 1
        hsum = jax.ops.segment_sum(
            recv.hits.reshape(-1)[order], seg, num_segments=b2
        )
        act = first & (ks != 0)

        def pick(a):
            return a.reshape(-1)[order]

        batch = DeviceBatchJ(
            key_hash=ks,
            hits=hsum[seg],
            limit=pick(recv.limit),
            duration=pick(recv.duration),
            algo=pick(recv.algo),
            burst=pick(recv.burst),
            reset_remaining=jnp.zeros((b2,), dtype=bool),
            is_greg=pick(recv.is_greg),
            greg_expire=pick(recv.greg_expire),
            greg_duration=pick(recv.greg_duration),
            active=act,
            use_cached=jnp.zeros((b2,), dtype=bool),
        )
        # Owner applies the aggregated hits (server side of sendHits).
        auth, _ = apply_batch_impl(auth, batch, now, ways=ways)
        # Broadcast status is a hits=0 re-read (broadcastPeers clears GLOBAL
        # and zeroes Hits before getRateLimit, global.go:211-217).
        auth, resp0 = apply_batch_impl(
            auth, batch._replace(hits=jnp.zeros((b2,), dtype=jnp.int64)),
            now, ways=ways,
        )
        rows = CachedRows(
            key_hash=jnp.where(act, ks, 0),
            algo=batch.algo,
            limit=resp0.limit,
            remaining=resp0.remaining,
            status=resp0.status,
            reset_time=resp0.reset_time,
        )
        # UpdatePeerGlobals to every peer: all_gather the authoritative rows
        # and upsert them into this device's cache shard.
        gathered = CachedRows(
            *[
                jax.lax.all_gather(a, SHARD_AXIS).reshape(-1)
                for a in rows
            ]
        )
        cache = store_cached_rows_impl(cache, gathered, now, ways=ways)
        return auth, cache

    sharded = _shard_map(
        _global_sync_a2a,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


def _psum_mod64(a: jax.Array) -> jax.Array:
    """psum of an int64 lane over the shard axis, modulo 2^64, as ONE
    uint32 collective.  XLA:TPU lowers no 64-bit integer all-reduce
    ("UNIMPLEMENTED: Supported lowering only of Sum all reduce" on a
    uint64 psum, v5e, PR 21), so each value travels as four 16-bit limbs
    in uint32 lanes — a limb's sum over up to 2^16 shards cannot wrap —
    and the carries are propagated afterwards.  Bit-identical to the
    uint64 psum it replaces."""
    u = a.astype(jnp.uint64)
    mask = jnp.uint64(0xFFFF)
    limbs = jnp.stack([
        ((u >> jnp.uint64(16 * k)) & mask).astype(jnp.uint32)
        for k in range(4)
    ])
    sums = jax.lax.psum(limbs, SHARD_AXIS).astype(jnp.uint64)
    out = jnp.zeros_like(u)
    carry = jnp.zeros_like(u)
    for k in range(4):
        c = sums[k] + carry
        out = out | ((c & mask) << jnp.uint64(16 * k))
        carry = c >> jnp.uint64(16)  # the last carry is the mod 2^64
    return out.astype(jnp.int64)


def make_global_sync_step_psum(mesh, ways: int):
    """The single-collective form of the sync step: hit aggregation is
    ONE `psum` over the shard axis instead of an all_to_all followed by
    an O(B log B) sort + segment-sum merge (arXiv 2602.11741's framing:
    on a mesh, GLOBAL coordination should cost one collective, not a
    routing exchange plus a device-side merge).

    It leans on a host invariant the a2a step doesn't need: the engine's
    pending dict already merged duplicate keys (global.go:87-95 applied
    at queue time), and `_build_chunks` allocates each key ONE
    (dst, lane) slot globally — so a key occupies exactly one source
    shard's grid and every other source holds zeros there.  The psum of
    the per-source [n_dst, D] grids is then the full merged delta on
    every shard with no duplicate handling at all; each shard slices its
    own row (`axis_index`), applies it to its auth shard, and the
    broadcast rows all_gather into the replicated cache exactly as in
    the a2a step.  Differentially pinned bit-identical to the a2a step
    (tests/test_differential.py).

    The shard_map body's name is the program's in a profiler trace:
    `jit__global_sync`, apart from the serve step's `jit__local`
    (bench/layer_metrics/global_sync_device_ms.mesh.json reads it)."""

    def _global_sync(
        auth: SlotTable, cache: SlotTable, delta: DeltaGrid, now
    ):
        d = DeltaGrid(*[a[0] for a in delta])  # local [n_dst, D]

        # sendHits, as ONE collective: per-source grids are disjoint by
        # host construction, so the sum IS the merge (bool fields ride
        # as int32 — psum is an add reduction).  int64 lanes reduce
        # modulo 2^64: the fingerprint lane spans the full int64 range,
        # and if the disjointness invariant is ever violated its sum
        # must wrap modularly (a bogus key that matches nothing) rather
        # than hit signed overflow — two's-complement addition is
        # bit-identical either way, so behavior under the invariant is
        # unchanged (still pinned against the a2a step).
        def _psum_lane(a):
            if a.dtype == jnp.bool_:
                a = a.astype(jnp.int32)
            if a.dtype == jnp.int64:
                return _psum_mod64(a)
            return jax.lax.psum(a, SHARD_AXIS)

        merged = DeltaGrid(*[_psum_lane(a) for a in d])
        me = jax.lax.axis_index(SHARD_AXIS)
        mine = DeltaGrid(*[a[me] for a in merged])  # this shard's [D] row
        key = mine.key_hash
        b2 = key.shape[0]
        act = key != 0
        batch = DeviceBatchJ(
            key_hash=key,
            hits=mine.hits,
            limit=mine.limit,
            duration=mine.duration,
            algo=mine.algo,
            burst=mine.burst,
            reset_remaining=jnp.zeros((b2,), dtype=bool),
            is_greg=mine.is_greg != 0,
            greg_expire=mine.greg_expire,
            greg_duration=mine.greg_duration,
            active=act,
            use_cached=jnp.zeros((b2,), dtype=bool),
        )
        # Owner applies the aggregated hits (server side of sendHits).
        auth, _ = apply_batch_impl(auth, batch, now, ways=ways)
        # Broadcast status is a hits=0 re-read (global.go:211-217).
        auth, resp0 = apply_batch_impl(
            auth, batch._replace(hits=jnp.zeros((b2,), dtype=jnp.int64)),
            now, ways=ways,
        )
        rows = CachedRows(
            key_hash=jnp.where(act, key, 0),
            algo=batch.algo,
            limit=resp0.limit,
            remaining=resp0.remaining,
            status=resp0.status,
            reset_time=resp0.reset_time,
        )
        # UpdatePeerGlobals to every shard: all_gather the authoritative
        # rows and upsert them into this device's cache shard.
        gathered = CachedRows(
            *[
                jax.lax.all_gather(a, SHARD_AXIS).reshape(-1)
                for a in rows
            ]
        )
        cache = store_cached_rows_impl(cache, gathered, now, ways=ways)
        return auth, cache

    sharded = _shard_map(
        _global_sync,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


@dataclass
class _Pending:
    """One key's queued hits since the last sync (global.go:87-95)."""

    req: RateLimitReq
    hits: int
    src_dev: int


def zero_delta_grid(n: int, D: int) -> DeltaGrid:
    """All-zero [n, n, D] delta grid (key_hash=0 rows are inactive)."""
    z64 = lambda: np.zeros((n, n, D), dtype=np.int64)  # noqa: E731
    return DeltaGrid(
        key_hash=z64(), hits=z64(), limit=z64(), duration=z64(),
        algo=np.zeros((n, n, D), dtype=np.int32), burst=z64(),
        is_greg=np.zeros((n, n, D), dtype=bool),
        greg_expire=z64(), greg_duration=z64(),
    )


_ARRIVAL_SHIFT = 44  # disjoint from owner-routing bits (32..) and bucket bits


def arrival_dev(h64: int, n: int) -> int:
    """Serving device for a GLOBAL key: deterministic hash spread, using
    bits disjoint from both the owner shard and the bucket index.  Stateless
    (no per-key host memory) — a key's serving device never changes, but all
    broadcast rows exist on every device, so any assignment is correct."""
    return int((np.uint64(h64) >> np.uint64(_ARRIVAL_SHIFT)) % np.uint64(n))


class GlobalEngine:
    """Host-side globalManager: replicated serving + periodic collective sync.

    Owns the per-device cache tables (one sharded SlotTable) and the pending
    hit-delta aggregation; applies authoritative updates to the MeshBackend's
    sharded auth table inside the sync step.
    """

    def __init__(
        self,
        backend: MeshBackend,
        delta_slots: int = 256,
        batch_limit: int = 1000,
        collective: str = "psum",
    ) -> None:
        if collective not in ("psum", "a2a"):
            raise ValueError(
                f"unknown sync collective {collective!r}; expected "
                "'psum' or 'a2a'"
            )
        self.b = backend
        self.n = backend.cfg.num_shards
        declare_launches(backend._stages, backend._tiers, "engine")
        self.delta_slots = delta_slots
        self.batch_limit = batch_limit
        self.collective = collective
        self.clock = backend.clock
        # Replicated serving table: its OWN slot budget
        # (DeviceConfig.global_cache_slots; default = num_slots, which
        # doubles the table HBM footprint — size it to the GLOBAL working
        # set to reclaim memory).
        self.cache_slots = (
            backend.cfg.global_cache_slots
            if backend.cfg.global_cache_slots is not None
            else backend.cfg.num_slots
        )
        self.cache_local = self.cache_slots // self.n
        nb_local = self.cache_local // backend.cfg.ways
        if nb_local & (nb_local - 1):
            raise ValueError(
                f"global cache buckets per shard ({nb_local}) must be a "
                "power of two"
            )
        self.cache_table: SlotTable = init_sharded_table(
            self.cache_slots, backend._tsharding
        )
        # Same packed sharded step as the backend hot path, run on the
        # cache table (single-transfer in and out).
        self._ingest = backend._step_packed
        # Default sync collective: ONE psum over the shard axis (the
        # mesh's whole point — hit aggregation over ICI, no device-side
        # merge).  "a2a" keeps the all_to_all + sort/segment form as the
        # differential reference (tests pin the two bit-identical).
        self._sync_step = (
            make_global_sync_step_psum(backend.mesh, backend.cfg.ways)
            if collective == "psum"
            else make_global_sync_step(backend.mesh, backend.cfg.ways)
        )
        self._lock = threading.Lock()  # cache_table + pending + metrics
        self.pending: Dict[str, _Pending] = {}
        # Metrics (global.go:48-57 async/broadcast durations + counts).
        self.syncs = 0
        self.sync_keys = 0
        self.dropped = 0
        # Post-sync hook: called with the synced pending dict (may run on a
        # device-executor thread).  The service uses it to bridge collective
        # syncs to the RPC tier — broadcasting owner-authoritative statuses
        # to cross-NODE peers (global.go:167-250's second loop).
        self.on_synced = None

    # -- serving path ----------------------------------------------------
    def check(self, reqs: Sequence[RateLimitReq]) -> List[RateLimitResp]:
        """Serve GLOBAL checks from the replicated cache tables
        (getGlobalRateLimit, gubernator.go:420-460) and queue the hits.

        Duplicate keys within one call are pre-aggregated (hits summed, the
        reference's own global.go:87-95 aggregation applied at ingest), so a
        hot key costs one lane per batch; the duplicates share one response.
        This deviates from per-hit interim decrements in the pre-broadcast
        window but keeps the same eventual-consistency contract.
        """
        from gubernator_tpu.core.hashing import key_hash64

        agg_idx: Dict[str, int] = {}
        agg_reqs: List[RateLimitReq] = []
        idx_map: List[int] = []
        for r in reqs:
            if r.name and r.unique_key:
                key = r.hash_key()
                j = agg_idx.get(key)
                if j is not None:
                    a = agg_reqs[j]
                    agg_reqs[j] = RateLimitReq(
                        **{**a.__dict__, "hits": a.hits + r.hits}
                    )
                    idx_map.append(j)
                    continue
                agg_idx[key] = len(agg_reqs)
            idx_map.append(len(agg_reqs))
            agg_reqs.append(r)

        packed = pack_requests_grid(
            agg_reqs, self.b.cfg.batch_size, self.n,
            lambda key: arrival_dev(key_hash64(key), self.n),
            self.clock,
        )
        for db in packed.rounds:
            np.copyto(db.use_cached, db.active)
        now_ms = self.clock.millisecond_now()
        now = np.int64(now_ms)

        # Persistence hooks, same contract as the backend hot path: record
        # key strings for Loader save, and seed never-seen keys from the
        # Store (a persisted GLOBAL bucket must survive a restart instead of
        # resetting to full remaining until the first broadcast read-back).
        if self.b._keymap is not None:
            with self.b._keymap_lock:
                for j, r in enumerate(agg_reqs):
                    if j not in packed.errors:
                        k = r.hash_key()
                        self.b._keymap[key_hash64(k)] = k
            self.b._maybe_prune_keymap()
        if self.b.store is not None:
            # Lock order everywhere: auth (backend) before cache (self).
            with self.b._lock, self._lock:
                self._seed_from_store_engine(agg_reqs, packed, now_ms)

        with self._lock:
            round_resps = self._ingest_rounds_locked(packed.rounds, now)
            # Queue hits AFTER preparing the response (the deferred QueueHit,
            # gubernator.go:429-432).
            for j, r in enumerate(agg_reqs):
                if j in packed.errors:
                    continue
                key = r.hash_key()
                p = self.pending.get(key)
                if p is None:
                    self.pending[key] = _Pending(
                        req=r, hits=r.hits,
                        src_dev=arrival_dev(key_hash64(key), self.n),
                    )
                else:
                    p.hits += r.hits
                    p.req = r
            want_sync = len(self.pending) >= self.batch_limit

        agg_out, tally = unmarshal_responses(
            len(agg_reqs), packed.errors, packed.positions,
            packed_grid_rounds_to_host(round_resps),
        )
        self.b._add_tally(tally)
        if want_sync:
            self.sync()
        return [agg_out[j] for j in idx_map]

    def serve_packed(self, rounds, pend_items):
        """The compiled fast lane's entry: ingest pre-packed use_cached
        rounds into the replicated cache table and queue pending hits,
        under ONE lock hold with check()'s ordering (serve, then queue).
        `pend_items` is [(req, summed_hits, src_dev)] — one per unique
        key, decoded by the caller.  Returns (round_resps_device,
        want_sync); the caller fetches responses to host OUTSIDE the
        lock (merges pipeline) and calls sync() itself when want_sync —
        matching check()'s after-lock sync call.

        Persistence hooks run like check()'s: keymap registration and
        Store.get seeding for never-seen keys (write-through itself
        happens at sync(), the engine's store tier)."""
        from gubernator_tpu.core.hashing import key_hash64

        now_ms = self.clock.millisecond_now()
        if self.b._keymap is not None:
            with self.b._keymap_lock:
                for req, _h, _s in pend_items:
                    k = req.hash_key()
                    self.b._keymap[key_hash64(k)] = k
            self.b._maybe_prune_keymap()
        if self.b.store is not None and pend_items:
            uniq: Dict[str, RateLimitReq] = {}
            for req, _h, _s in pend_items:
                uniq.setdefault(req.hash_key(), req)
            # Lock order everywhere: auth (backend) before cache (self).
            with self.b._lock, self._lock:
                self._seed_uniq_from_store(uniq, now_ms)
        now = np.int64(now_ms)
        lock_wait = self.b._stages.stage("backend.lock_wait")
        with self._lock:
            lock_wait.end()
            resps = self._ingest_rounds_locked(rounds, now)
            for req, hits, src_dev in pend_items:
                key = req.hash_key()
                p = self.pending.get(key)
                if p is None:
                    self.pending[key] = _Pending(
                        req=req, hits=hits, src_dev=src_dev
                    )
                else:
                    p.hits += hits
                    p.req = req
            want_sync = len(self.pending) >= self.batch_limit
        return resps, want_sync

    def _ingest_rounds_locked(self, rounds, now) -> list:
        """Ingest grid rounds into the replicated cache under the clock
        `now`; caller holds `_lock`.  The object path's DeviceBatches
        and the engine lane's packed words alike."""

        def launch(words):
            batch = jax.device_put(words, self.b._psharding)
            self.cache_table, resp = self._ingest(
                self.cache_table, batch, now
            )
            return resp

        return launch_rounds(
            self.b._stages, rounds, self.b._tiers, launch, pack_grid_batch,
            self.n,
        )

    # -- sync path -------------------------------------------------------
    def _seed_from_store_engine(self, agg_reqs, packed, now_ms: int) -> None:
        """Store.get for batch keys with no live row in the replicated
        cache; hits upsert into BOTH tables — the auth table (owner-routed,
        where sync applies hits, the s.Get of algorithms.go:45-51) and the
        cache table (arrival-routed, so pre-sync serving reflects persisted
        state, not a fresh bucket).  Caller holds b._lock then self._lock."""
        uniq: Dict[str, RateLimitReq] = {}
        for j, r in enumerate(agg_reqs):
            if j not in packed.errors:
                uniq.setdefault(r.hash_key(), r)
        if uniq:
            self._seed_uniq_from_store(uniq, now_ms)

    def _seed_uniq_from_store(
        self, uniq: Dict[str, "RateLimitReq"], now_ms: int
    ) -> None:
        """_seed_from_store_engine body over a per-unique-key request dict
        (shared by check() and the fast lane's serve_packed).  Caller
        holds b._lock then self._lock."""
        from gubernator_tpu.core.hashing import key_hash64
        from gubernator_tpu.runtime.store import item_to_row_fields

        keys = list(uniq)
        hashes = [key_hash64(k) for k in keys]
        route = lambda h: arrival_dev(h, self.n)  # noqa: E731
        found, _ = self.b._probe_grid(
            keys, hashes, now_ms, table=self.cache_table, route=route
        )
        rows: List[dict] = []
        row_hashes: List[int] = []
        for k, h, f in zip(keys, hashes, found):
            if f:
                continue
            item = self.b.store.get(uniq[k])
            if item is None or item.is_expired(now_ms):
                continue
            rows.append(item_to_row_fields(item))
            row_hashes.append(h)
        if rows:
            self.b._bulk_upsert(rows, row_hashes, now_ms)
            self.cache_table = self.b._bulk_upsert_into(
                self.cache_table, rows, row_hashes, now_ms, route
            )

    def sync(self) -> int:
        """Run the collective hits->owner->broadcast step; returns #keys."""
        with self._lock:
            pending, self.pending = self.pending, {}
        if not pending:
            return 0
        # Low-rate, so it carries the clock anchor (time.time_ns() at
        # its start, as an argument of the profiler event).
        with self.b._stages.stage(
            "global.sync_tick", "global", anchor=True
        ) as tick:
            tick.tally(keys=len(pending))
            return self._sync_pending(pending, tick)

    def _sync_pending(self, pending, tick) -> int:
        stages = self.b._stages
        # Transfers don't read table state — stage them BEFORE taking the
        # locks so concurrent checks only block for the sync steps, not
        # the host->device puts.
        with stages.stage("global.build_chunks", "global"):
            chunks = self._build_chunks(pending, self.clock.now())
            now = np.int64(self.clock.millisecond_now())
            staged = [
                DeltaGrid(
                    *[jax.device_put(a, self.b._bsharding) for a in grid]
                )
                for grid in chunks
            ]
        tick.tally(chunks=len(chunks))
        cap_keys = cap_token = wt_seq = None
        # Lock order: auth (backend) before cache (self).
        # Under its own name: `backend.lock_wait` is what a drain waits.
        lock_wait = stages.stage("global.wait_locks", "global")
        with self.b._lock, self._lock:
            lock_wait.end()
            for sharded in staged:
                with stages.stage("global.sync_step", "global"):
                    self.b.table, self.cache_table = self._sync_step(
                        self.b.table, self.cache_table, sharded, now
                    )
            if self.b.store is not None:
                # Post-sync auth rows -> Store.on_change (the write-through
                # of algorithms.go:154-158, batch-granular at the sync
                # tier).  The row gathers are DISPATCHED inside the lock —
                # pinned to the post-sync table version (jax arrays are
                # immutable) — and FETCHED outside it, so concurrent
                # checks block only for the sync steps, never the
                # device->host readback (the pipelined-drain split,
                # docs/pipeline.md).
                from gubernator_tpu.core.hashing import key_hash64

                cap_keys = list(pending.keys())
                h64 = np.array(
                    [np.uint64(key_hash64(k)) for k in cap_keys],
                    dtype=np.uint64,
                ).view(np.int64)
                cap_token = self.b._gather_rows_dispatch(h64, int(now))
                wt_seq = self.b._wt_ticket()
            self.syncs += 1
            self.sync_keys += len(pending)
        if cap_keys is not None:
            captured: list = []
            try:
                a, rf = self.b._gather_rows_finish(
                    cap_token, len(cap_keys)
                )
                captured = self._captured_items(cap_keys, pending, a, rf)
            finally:
                # Redeem the ticket even if a fetch fails — an
                # unredeemed ticket wedges every later delivery
                # (PersistenceHost._deliver_write_through).
                self.b._deliver_write_through(captured, wt_seq)
        if self.on_synced is not None:
            self.on_synced(pending)
        return len(pending)

    def _captured_items(self, keys, pending, a, rf) -> list:
        """(req, CacheItem) pairs from packed GATHER_ROW_FIELDS columns —
        misses and KIND_CACHED_RESP rows are skipped exactly like
        MeshBackend._read_items_locked."""
        from gubernator_tpu.core.types import Algorithm, CacheItem, Status
        from gubernator_tpu.ops.state import KIND_CACHED_RESP

        out: list = []
        for j, key in enumerate(keys):
            if not a[0, j] or a[1, j] == KIND_CACHED_RESP:
                continue
            algo = Algorithm(int(a[2, j]))
            remaining = (
                float(rf[j]) if algo == Algorithm.LEAKY_BUCKET
                else int(a[5, j])
            )
            out.append((pending[key].req, CacheItem(
                key=key,
                algorithm=algo,
                expire_at=int(a[9, j]),
                limit=int(a[3, j]),
                duration=int(a[4, j]),
                remaining=remaining,
                created_at=int(a[6, j]),
                status=Status(int(a[7, j])),
                burst=int(a[8, j]),
            )))
        return out

    def _build_chunks(self, pending: Dict[str, _Pending], now_dt):
        """Pack pending deltas into [n, n, D] grids (chunked on overflow)."""
        from gubernator_tpu.core.hashing import key_hash64
        from gubernator_tpu.core.interval import (
            GregorianError,
            gregorian_duration,
            gregorian_expiration,
        )
        from gubernator_tpu.core.types import Behavior, has_behavior

        n, D = self.n, self.delta_slots
        chunks: List[DeltaGrid] = []
        # Lane counters are per (chunk, DST) — shared across sources —
        # so every key gets a GLOBALLY unique (dst, lane) slot within a
        # chunk.  The psum step's whole premise is that the per-source
        # grids are disjoint (the sum IS the merge); the a2a step
        # handles this layout too (its sort/segment merge degenerates to
        # a permutation), so one builder serves both collectives.
        fill: List[np.ndarray] = []  # [n_dst] lane counters per chunk

        def new_chunk() -> DeltaGrid:
            g = zero_delta_grid(n, D)
            chunks.append(g)
            fill.append(np.zeros(n, dtype=np.int64))
            return g

        def fill_lane(ci: int, lane: int, h64, p: _Pending, is_greg, ge, gd):
            g, r = chunks[ci], p.req
            src, dst = p.src_dev, int(shard_of_hash(h64, n))
            g.key_hash[src, dst, lane] = np.int64(np.uint64(h64).view(np.int64))
            g.hits[src, dst, lane] = p.hits
            g.limit[src, dst, lane] = r.limit
            g.duration[src, dst, lane] = r.duration
            g.algo[src, dst, lane] = int(r.algorithm)
            g.burst[src, dst, lane] = r.burst if r.burst != 0 else r.limit
            g.is_greg[src, dst, lane] = is_greg
            g.greg_expire[src, dst, lane] = ge
            g.greg_duration[src, dst, lane] = gd
            fill[ci][dst] = lane + 1

        for key, p in pending.items():
            r = p.req
            h64 = key_hash64(key)
            dst = int(shard_of_hash(h64, n))
            is_greg = has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN)
            ge = gd = 0
            if is_greg:
                try:
                    ge = gregorian_expiration(now_dt, r.duration)
                    gd = gregorian_duration(now_dt, r.duration)
                except GregorianError:
                    with self._lock:
                        self.dropped += 1
                    continue
            while True:
                for ci in range(len(chunks)):
                    lane = int(fill[ci][dst])
                    if lane < D:
                        fill_lane(ci, lane, h64, p, is_greg, ge, gd)
                        break
                else:
                    new_chunk()
                    continue
                break
        if not chunks:
            new_chunk()
        return chunks

    def warmup(self) -> None:
        """Compile the collective sync executable with an all-zero delta
        grid (key_hash=0 rows are inactive, so the tables are unchanged) —
        a first compile inside the serving cadence would stall every lane.
        """
        grid = zero_delta_grid(self.n, self.delta_slots)
        sharded = DeltaGrid(
            *[jax.device_put(a, self.b._bsharding) for a in grid]
        )
        now = np.int64(self.clock.millisecond_now())
        with self.b._lock, self._lock:
            self.b.table, self.cache_table = self._sync_step(
                self.b.table, self.cache_table, sharded, now
            )
            # Ingest executables for the CACHE table geometry (the jit
            # cache keys on table size, so the auth-table warmup doesn't
            # cover a global_cache_slots-sized table) at every tier.
            for t in self.b._tiers:
                batch = jax.device_put(
                    np.zeros((12, self.n, t), dtype=np.int64),
                    self.b._psharding,
                )
                self.cache_table, _ = self._ingest(
                    self.cache_table, batch, now
                )

    def debug_vars(self) -> dict:
        """The /debug/vars `global.engine` block: the tick's counts, and
        the geometry of one chunk's sync program."""
        with self._lock:
            out = {
                "syncs": self.syncs,
                "sync_keys": self.sync_keys,
                "dropped": self.dropped,
                "pending": len(self.pending),
            }
        out["sync_program"] = {
            "collective": self.collective,
            "shards": self.n,
            "delta_slots": self.delta_slots,
        }
        return out

    # -- point reads (tests / HealthCheck) -------------------------------
    def _cache_bucket_offset(self, key: str, shard: int) -> int:
        """Global row index of `key`'s bucket within the CACHE table (its
        geometry may differ from the auth table's via global_cache_slots).
        """
        from gubernator_tpu.core.hashing import key_hash64

        nb_local = self.cache_local // self.b.cfg.ways
        bucket = key_hash64(key) & (nb_local - 1)
        return shard * self.cache_local + bucket * self.b.cfg.ways

    def get_cached(self, key: str):
        """Read this key's row from its serving device's cache table."""
        from gubernator_tpu.core.hashing import key_hash64
        from gubernator_tpu.runtime.backend import probe_bucket

        dev = arrival_dev(key_hash64(key), self.n)
        lo = self._cache_bucket_offset(key, dev)
        now = self.clock.millisecond_now()
        with self._lock:
            return probe_bucket(
                self.cache_table, lo, self.b.cfg.ways, key, now
            )

    def cache_occupancy(self) -> int:
        """Live rows in the replicated serving table (HBM observability for
        the 2x-table cost; exported as gubernator_global_cache_size)."""
        with self._lock:
            return int(np.asarray(self.cache_table.occupancy()))
