"""GLOBAL where it does the work: thousands of tenant keys, drains that
hold the same key in several RPCs, ticks that flush more keys than one
chunk holds — the engine lane and the collective sync against a plain
reference, exactly.

The reference below restates the semantics (upstream global.go:33-254,
gubernator.go:420-479) with numpy and `core/pymodel.py` alone; nothing of
`parallel/global_sync.py` is imported by it:

  * one RPC's duplicates of a key are one check with their hits summed
    and one shared answer; the RPCs of a drain are served in order;
  * a replica that holds the owner's last broadcast answers it verbatim
    (stale but fast) and queues the hits; one that does not processes
    the check "as if it owned it", on a bucket of its own;
  * a sync sums the queued hits per key, applies each sum on the owner's
    bucket, re-reads it with hits 0 and stores that answer on EVERY
    shard's replica, so after one sync every replica equals the owner's
    row.

Four virtual CPU devices, 2^16 slots, 2,048 GLOBAL keys, chunks of 4 x 64
lanes.  The drains go through `FastPath._engine_process` (the engine
lane's own function) in a stated order, so that the comparison is exact.
"""
from __future__ import annotations

import asyncio
import threading
from dataclasses import replace

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.hashing import key_hash64
from gubernator_tpu.core.pymodel import PyRateLimiter
from gubernator_tpu.core.types import Behavior, RateLimitReq
from gubernator_tpu.ops.state import KIND_CACHED_RESP, table_to_host
from gubernator_tpu.parallel.sharded import init_sharded_table
from gubernator_tpu.runtime.fastpath import _EngineEntry
from gubernator_tpu.testing.cluster import Cluster

SHARDS, SLOTS, WAYS, BATCH = 4, 1 << 16, 8, 256
N_GLOBAL = 2048
DELTA_SLOTS = 64                    # one chunk holds 4 x 64 = 256 keys
CHUNK_KEYS = SHARDS * DELTA_SLOTS
DURATION = 3_600_000
NEVER = 1 << 30                     # a batch limit no drain reaches


# -- the plain reference ----------------------------------------------------

class Reference:
    """GLOBAL on a mesh of replicas, sequentially, on dicts."""

    def __init__(self, clock) -> None:
        self.owner = PyRateLimiter(clock)      # authoritative buckets
        self.interim = PyRateLimiter(clock)    # a replica's own buckets
        self.broadcast = {}                    # key -> the owner's answer
        self.pending = {}                      # key -> [request, hits]

    def serve_rpc(self, reqs):
        """One RPC's GLOBAL checks -> one answer per check."""
        first, total = {}, {}
        for r in reqs:
            k = r.hash_key()
            first.setdefault(k, r)
            total[k] = total.get(k, 0) + r.hits
        answers = {}
        for k, r in first.items():
            agg = replace(r, hits=total[k])
            if k in self.broadcast:
                answers[k] = self.broadcast[k]
            else:
                answers[k] = self.interim.get_rate_limit(agg)
            row = self.pending.setdefault(k, [agg, 0])
            row[0], row[1] = agg, row[1] + total[k]
        return [answers[r.hash_key()] for r in reqs]

    def sync(self) -> int:
        pending, self.pending = self.pending, {}
        for k, (r, hits) in pending.items():
            self.owner.get_rate_limit(replace(r, hits=hits))
            self.broadcast[k] = self.owner.get_rate_limit(replace(r, hits=0))
            self.interim.cache.pop(k, None)    # the broadcast replaces it
        return len(pending)


# -- the system under test --------------------------------------------------

@pytest.fixture(scope="module")
def mesh_daemon():
    clock_mod.freeze()
    c = Cluster.start(1, device=DeviceConfig(
        num_slots=SLOTS, ways=WAYS, batch_size=BATCH, num_shards=SHARDS,
    ))
    try:
        async def stop_loop():
            lp = c.daemons[0].service._collective_loop
            if lp._task is not None:
                lp._task.cancel()
                await asyncio.gather(lp._task, return_exceptions=True)
                lp._task = None

        c.run(stop_loop(), timeout=30)   # ticks are the test's to call
        eng = c.daemons[0].service.global_engine
        eng.delta_slots = DELTA_SLOTS
        yield c.daemons[0]
    finally:
        c.stop()
        clock_mod.unfreeze()


@pytest.fixture
def mesh(mesh_daemon):
    """The daemon with both tables empty: 2,048 keys fill a replica's
    2,048 buckets once, not once a case."""
    eng, back = mesh_daemon.service.global_engine, mesh_daemon.service.backend
    with back._lock, eng._lock:
        back.table = init_sharded_table(SLOTS, back._tsharding)
        eng.cache_table = init_sharded_table(eng.cache_slots, back._tsharding)
        eng.pending.clear()
    return mesh_daemon


def _req(key: int, hits: int, limit: int) -> RateLimitReq:
    return RateLimitReq(
        name="gh", unique_key=f"t{key:05d}", hits=hits, limit=limit,
        duration=DURATION, behavior=Behavior.GLOBAL,
    )


def _entry(reqs) -> _EngineEntry:
    """What check_raw hands the engine lane for one RPC of GLOBAL checks."""
    names = b"".join(r.name.encode() for r in reqs)
    keys = b"".join(r.unique_key.encode() for r in reqs)
    n = len(reqs)

    def col(f):
        return np.array([int(getattr(r, f)) for r in reqs], dtype=np.int64)

    payload = native.encode_req_columns(
        names, np.cumsum([0] + [len(r.name) for r in reqs]).astype(np.int64),
        keys,
        np.cumsum([0] + [len(r.unique_key) for r in reqs]).astype(np.int64),
        col("hits"), col("limit"), col("duration"), col("algorithm"),
        col("behavior"), np.zeros(n, dtype=np.int64),
    )
    cols = native.parse_reqs(payload)
    assert cols.n == n and not cols.err.any()
    z = np.zeros(n, dtype=np.int64)
    return _EngineEntry(payload, cols, np.arange(n), np.zeros(n, bool), z, z)


def _drain(daemon, rpcs):
    """One engine-lane drain of `rpcs` (lists of requests), in order:
    [(status, limit, remaining, reset_time) arrays per RPC]."""
    fetch = daemon.fastpath._engine_process([_entry(r) for r in rpcs])
    return fetch()


def _assert_answers(got, want_rpcs, where):
    for i, (out, want) in enumerate(zip(got, want_rpcs)):
        st, lm, rem, rst = (np.asarray(a) for a in out)
        exp = np.array([[int(w.status), w.limit, w.remaining, w.reset_time]
                        for w in want], dtype=np.int64).T
        for name, a, b in zip(("status", "limit", "remaining", "reset_time"),
                              (st, lm, rem, rst), exp):
            bad = np.flatnonzero(a != b)
            assert not len(bad), (
                f"{where}, RPC {i}: {name} differs at {bad[:5]}: "
                f"{a[bad[:5]]} != {b[bad[:5]]}"
            )


def _assert_tables(daemon, ref: Reference, keys, where):
    """The auth table's row of every key equals the reference owner's
    bucket, and EVERY shard's replica holds the owner's broadcast."""
    eng, back = daemon.service.global_engine, daemon.service.backend
    with back._lock, eng._lock:
        auth = table_to_host(back.table)
        cache = table_to_host(eng.cache_table)
    now = back.clock.millisecond_now()

    def rows_of(t, lo, hi):
        live = (t["key"][lo:hi] != 0) & (t["expire_at"][lo:hi] > now)
        idx = np.flatnonzero(live) + lo
        return dict(zip(t["key"][idx].tolist(), idx.tolist()))

    auth_at = rows_of(auth, 0, len(auth["key"]))
    replicas = [rows_of(cache, s * eng.cache_local, (s + 1) * eng.cache_local)
                for s in range(SHARDS)]
    for k in keys:
        h = int(np.uint64(key_hash64(k)).view(np.int64))
        item = ref.owner.cache[k]
        i = auth_at.get(h)
        assert i is not None, f"{where}: {k} has no owner row"
        assert (auth["remaining"][i], auth["status"][i], auth["limit"][i],
                auth["expire_at"][i]) == (
            item.remaining, int(item.status), item.limit, item.expire_at
        ), f"{where}: owner row of {k}"
        b = ref.broadcast[k]
        for s, at in enumerate(replicas):
            j = at.get(h)
            assert j is not None, f"{where}: {k} not on replica {s}"
            assert (cache["kind"][j], cache["remaining"][j],
                    cache["status"][j], cache["limit"][j],
                    cache["expire_at"][j]) == (
                KIND_CACHED_RESP, b.remaining, int(b.status), b.limit,
                b.reset_time,
            ), f"{where}: replica {s} of {k}"


def _traffic(seed, drains, rpcs, per_rpc, limit, hit_values):
    """drains x rpcs x per_rpc checks on the 2,048 keys; within an RPC a
    tenth of the checks repeat a key of that RPC, and every RPC after a
    drain's first takes a third of its keys from the RPC before it, so the
    same key sits in several RPCs of one drain."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(drains):
        drain, prev = [], None
        for _ in range(rpcs):
            k = rng.integers(0, N_GLOBAL, size=per_rpc)
            k[rng.random(per_rpc) < 0.1] = k[0]
            if prev is not None:
                again = rng.random(per_rpc) < 1 / 3
                k[again] = rng.choice(prev, size=int(again.sum()))
            prev = k
            drain.append([
                _req(int(x), int(rng.choice(hit_values)), limit)
                for x in k
            ])
        out.append(drain)
    return out


CASES = {
    # name: (seed, ticks, drains a tick, RPCs a drain, checks an RPC,
    #        limit, hits, the engine's batch limit)
    "four_chunks_a_tick": (11, 3, 2, 6, 150, 10**9, (1,), NEVER),
    "cross_rpc_duplicates": (12, 3, 1, 12, 60, 10**9, (1, 2, 3), NEVER),
    "peeks_among_spends": (13, 3, 2, 4, 200, 10**9, (0, 1), NEVER),
    "limits_that_run_out": (14, 4, 2, 4, 200, 7, (1, 2), NEVER),
    "a_drain_reaches_the_batch_limit": (15, 3, 2, 6, 150, 10**9, (1,), 300),
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_equals_the_reference_after_every_tick(mesh, case):
    seed, ticks, drains, rpcs, per_rpc, limit, hit_values, batch_limit = (
        CASES[case]
    )
    eng = mesh.service.global_engine
    eng.batch_limit = batch_limit
    ref = Reference(mesh.service.backend.clock)
    stages = mesh.metrics.stages
    before = stages.debug_vars().get("global", {}).get("sync_tick", {})
    touched, flushed, most_keys, cross = set(), 0, 0, 0
    for t in range(ticks):
        for d, drain in enumerate(_traffic(
            seed * 100 + t, drains, rpcs, per_rpc, limit, hit_values
        )):
            sets = [{r.hash_key() for r in rpc} for rpc in drain]
            cross += len(set.union(*sets)) < sum(len(s) for s in sets)
            want = [ref.serve_rpc(rpc) for rpc in drain]
            got = _drain(mesh, drain)
            _assert_answers(got, want, f"{case} tick {t} drain {d}")
            touched |= set.union(*sets)
            if len(ref.pending) >= batch_limit:
                # The drain itself syncs before it answers (want_sync).
                flushed += ref.sync()
                assert not eng.pending
        most_keys = max(most_keys, len(ref.pending))
        n = ref.sync()
        assert eng.sync() == n
        flushed += n
        _assert_tables(mesh, ref, sorted(touched), f"{case} after tick {t}")
    assert cross, "no drain held the same key in two RPCs"
    if batch_limit == NEVER:
        assert most_keys > CHUNK_KEYS, "no tick outgrew one chunk"
    # The tick's counters add up to what was flushed.
    after = stages.debug_vars()["global"]["sync_tick"]
    assert after["keys"] - before.get("keys", 0) == flushed
    assert after["chunks"] - before.get("chunks", 0) >= -(-flushed // CHUNK_KEYS)


def test_serving_runs_during_a_tick(mesh):
    """A tick of many chunks on one thread, plain checks on another: the
    plain answers equal the reference whatever the interleaving, a GLOBAL
    drain in the middle sees the replicas before or after the tick and
    nothing else, and once the ticks are through the tables are exact."""
    eng, back = mesh.service.global_engine, mesh.service.backend
    eng.batch_limit = NEVER
    ref = Reference(back.clock)
    warm = _traffic(77, 1, 4, 200, 10**9, (1,))[0]
    _assert_answers(_drain(mesh, warm), [ref.serve_rpc(r) for r in warm],
                    "warm drain")
    assert eng.sync() == ref.sync()
    load = _traffic(78, 1, 8, 200, 10**9, (1,))[0]
    _assert_answers(_drain(mesh, load), [ref.serve_rpc(r) for r in load],
                    "load drain")
    assert len(ref.pending) > 2 * CHUNK_KEYS
    before = dict(ref.broadcast)

    plain_model = PyRateLimiter(back.clock)
    plain = [
        RateLimitReq(name="gh_plain", unique_key=f"p{i % 300}", hits=1,
                     limit=50, duration=DURATION)
        for i in range(1200)
    ]
    result = {}
    tick = threading.Thread(target=lambda: result.update(n=eng.sync()))
    tick.start()
    got_plain = [back.check(plain[i:i + 100]) for i in range(0, 1200, 100)]
    mid = _traffic(79, 1, 2, 100, 10**9, (1,))[0]
    got_mid = _drain(mesh, mid)
    tick.join(60)
    assert result["n"] == ref.sync()

    want_plain = [plain_model.get_rate_limit(r) for r in plain]
    flat = [r for chunk in got_plain for r in chunk]
    assert [(r.status, r.remaining, r.reset_time) for r in flat] == [
        (r.status, r.remaining, r.reset_time) for r in want_plain
    ]
    # The drain in the middle: each key answered from one broadcast or
    # the other (both have the key, or the key is new and both agree).
    after = dict(ref.broadcast)
    mid_ref = Reference(back.clock)
    for rpc, out in zip(mid, got_mid):
        rem = np.asarray(out[2])
        for r, x in zip(rpc, rem):
            k = r.hash_key()
            if k in before:     # else a bucket of the replica's own
                legal = {before[k].remaining, after[k].remaining}
                assert int(x) in legal, (k, int(x), legal)
    # Its hits were queued whichever broadcast answered them.
    for rpc in mid:
        mid_ref.serve_rpc(rpc)
    for k, (r, hits) in mid_ref.pending.items():
        ref.pending[k] = [r, hits]
    assert eng.sync() == ref.sync()
    touched = {r.hash_key() for d in (warm, load, mid) for rpc in d
               for r in rpc}
    _assert_tables(mesh, ref, sorted(touched), "after the ticks")
