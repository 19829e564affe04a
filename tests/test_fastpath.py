"""The compiled host fast lane (runtime/fastpath.py + native wire codec).

Differential against the object path: identical responses for identical
traffic, byte-for-byte wire compatibility, correct fallback for the
behaviors the fast lane doesn't serve (VERDICT r2 #2; the reference's
compiled hot loop is workers.go:249-314 + generated pb marshalers).
"""
from __future__ import annotations

import asyncio

import pytest

from gubernator_tpu import native
from gubernator_tpu.client import V1Client
from gubernator_tpu.core.config import DaemonConfig, DeviceConfig
from gubernator_tpu.core.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
)
from gubernator_tpu.testing import Cluster

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


@pytest.fixture(scope="module")
def node():
    """Single-node daemon — the client-path fast-lane configuration."""
    c = Cluster.start(1)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def client(node):
    cl = V1Client(node.addresses()[0])
    yield cl
    cl.close()


def _fp(node):
    return node.daemons[0].fastpath


def test_fast_lane_serves_and_counts(node, client):
    fp = _fp(node)
    before = fp.served
    for i, want in [(0, Status.UNDER_LIMIT), (1, Status.UNDER_LIMIT),
                    (2, Status.OVER_LIMIT)]:
        r = client.get_rate_limits([
            RateLimitReq(
                name="fp_over", unique_key="k", hits=1, limit=2,
                duration=60_000,
            )
        ])[0]
        assert r.error == ""
        assert r.status == want, f"hit {i}"
        assert r.remaining == max(0, 1 - i)
        assert r.limit == 2
    assert fp.served == before + 3  # actually took the compiled lane


def test_fast_lane_duplicate_keys_serialize(node, client):
    """Duplicate keys in one batch observe each other's effects in order
    (the round-splitting contract, workers.go:182-186)."""
    fp = _fp(node)
    before = fp.served
    reqs = [
        RateLimitReq(name="fp_dup", unique_key="d", hits=2, limit=10,
                     duration=60_000)
        for _ in range(3)
    ]
    rs = client.get_rate_limits(reqs)
    assert [r.remaining for r in rs] == [8, 6, 4]
    assert fp.served == before + 3


def test_fast_lane_validation_errors(node, client):
    fp = _fp(node)
    before = fp.served
    rs = client.get_rate_limits([
        RateLimitReq(name="", unique_key="x", hits=1, limit=5,
                     duration=1000),
        RateLimitReq(name="x", unique_key="", hits=1, limit=5,
                     duration=1000),
        RateLimitReq(name="fp_ok", unique_key="ok", hits=1, limit=5,
                     duration=60_000),
    ])
    assert rs[0].error == "field 'namespace' cannot be empty"
    assert rs[1].error == "field 'unique_key' cannot be empty"
    assert rs[2].error == "" and rs[2].remaining == 4
    assert fp.served == before + 3
    # Error precedence: an empty key AND an invalid Gregorian duration
    # reports the validation error (the packer rejects before the
    # Gregorian is ever evaluated — object-path order).
    r = client.get_rate_limits([
        RateLimitReq(name="x", unique_key="", hits=1, limit=5,
                     duration=99,
                     behavior=Behavior.DURATION_IS_GREGORIAN),
    ])[0]
    assert r.error == "field 'unique_key' cannot be empty"


def test_fast_lane_leaky_and_gregorian(node, client):
    fp = _fp(node)
    before = fp.served
    rs = client.get_rate_limits([
        RateLimitReq(name="fp_leaky", unique_key="l", hits=1, limit=10,
                     duration=60_000, algorithm=Algorithm.LEAKY_BUCKET,
                     burst=5),
        RateLimitReq(name="fp_greg", unique_key="g", hits=1, limit=100,
                     duration=1,  # GregorianHours
                     behavior=Behavior.DURATION_IS_GREGORIAN),
        RateLimitReq(name="fp_greg", unique_key="bad", hits=1, limit=100,
                     duration=99,
                     behavior=Behavior.DURATION_IS_GREGORIAN),
    ])
    assert rs[0].error == "" and rs[0].remaining == 4  # burst capacity
    assert rs[1].error == "" and rs[1].remaining == 99
    assert rs[1].reset_time > 0
    assert rs[2].error != ""  # invalid Gregorian interval reports per-lane
    assert fp.served == before + 3


def test_global_serves_on_fast_lane(node, client):
    """GLOBAL on a single node = owner side: the compiled lane serves
    authoritatively and queues the broadcast update for the manager
    (the deferred QueueUpdate of gubernator.go:617)."""
    fp = _fp(node)
    before = fp.served
    mgr = node.daemons[0].service.global_mgr
    r = client.get_rate_limits([
        RateLimitReq(name="fp_glob", unique_key="g", hits=1, limit=10,
                     duration=60_000, behavior=Behavior.GLOBAL)
    ])[0]
    assert r.error == "" and r.remaining == 9
    assert fp.served == before + 1
    assert mgr is not None
    r2 = client.get_rate_limits([
        RateLimitReq(name="fp_glob", unique_key="g", hits=2, limit=10,
                     duration=60_000, behavior=Behavior.GLOBAL)
    ])[0]
    assert r2.remaining == 7


def test_global_replication_on_fast_lane():
    """Multi-node GLOBAL on the compiled lane: a non-owned key serves
    locally (owner metadata, no forward), the queued hits reach the
    owner, and the owner's broadcast comes back — the full
    hits-up/status-down loop of global.go:78-250 with zero per-request
    python on the serving path."""
    import time

    c = Cluster.start(3)
    try:
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        svc = c.daemons[0].service
        # Find a key NOT owned by daemon 0.
        key = next(
            k for k in (f"grep{i}" for i in range(50))
            if not svc.get_peer(f"g_{k}").info().is_owner
        )
        owner_addr = svc.get_peer(f"g_{key}").info().grpc_address
        owner_d = next(
            d for d in c.daemons if d.advertise_address() == owner_addr
        )
        req = RateLimitReq(name="g", unique_key=key, hits=3, limit=100,
                           duration=60_000, behavior=Behavior.GLOBAL)
        r = cl.get_rate_limits([req])[0]
        assert r.error == ""
        assert r.remaining == 97  # processed locally as-if-owner (miss)
        assert r.metadata == {"owner": owner_addr}
        assert fp.served >= 1 and fp.fallbacks == 0

        # The aggregated hit reaches the owner's authoritative bucket.
        deadline = time.monotonic() + 10.0
        while True:
            item = owner_d.service.backend.get_cache_item(f"g_{key}")
            if item is not None and item.remaining == 97:
                break
            assert time.monotonic() < deadline, item
            time.sleep(0.05)
        cl.close()
    finally:
        c.stop()


def test_oversized_batch_rejected(node, client):
    import grpc

    reqs = [
        RateLimitReq(name="fp_big", unique_key=f"k{i}", hits=1, limit=10,
                     duration=60_000)
        for i in range(1001)
    ]
    with pytest.raises(grpc.RpcError) as ei:
        client.get_rate_limits(reqs)
    assert ei.value.code() == grpc.StatusCode.OUT_OF_RANGE


def test_fast_lane_on_mesh_backend():
    """The fast lane routes by hash to mesh shards and serves from the
    sharded step (the multi-chip daemon configuration)."""
    c = Cluster.start(
        1,
        device=DeviceConfig(
            num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
        ),
    )
    try:
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        reqs = [
            RateLimitReq(name="fp_mesh", unique_key=f"m{i}", hits=1,
                         limit=10, duration=60_000)
            for i in range(100)
        ]
        r1 = cl.get_rate_limits(reqs)
        assert all(x.error == "" for x in r1)
        assert all(x.remaining == 9 for x in r1)
        r2 = cl.get_rate_limits(reqs)
        assert all(x.remaining == 8 for x in r2)
        assert fp.served == 200
        cl.close()
    finally:
        c.stop()


def test_store_served_on_fast_lane():
    """A Store-attached daemon STAYS on the compiled lane (the r3
    verdict's top ask): the drain bulk-seeds misses from Store.get,
    captures post-step rows columnarly, and delivers on_change — with
    the same store contents the object path would produce."""
    from gubernator_tpu.core.types import CacheItem
    from gubernator_tpu.runtime.store import MockStore

    store = MockStore()
    conf = DaemonConfig()
    conf.store = store
    c = Cluster.start(1, conf_template=conf)
    try:
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        r = cl.get_rate_limits([
            RateLimitReq(name="fp_store", unique_key="s", hits=1, limit=5,
                         duration=60_000)
        ])[0]
        assert r.error == "" and r.remaining == 4
        assert fp.served == 1 and fp.fallbacks == 0
        assert store.called["get"] == 1
        assert store.called["on_change"] == 1
        item = store.data["fp_store_s"]
        assert item.remaining == 4 and item.limit == 5
        # Second batch: key resident -> no further Store.get; duplicate
        # occurrences cascade on host yet the captured row is post-merge.
        rs = cl.get_rate_limits([
            RateLimitReq(name="fp_store", unique_key="s", hits=1, limit=5,
                         duration=60_000)
            for _ in range(3)
        ])
        assert [x.remaining for x in rs] == [3, 2, 1]
        assert fp.served == 4 and fp.fallbacks == 0
        assert store.called["get"] == 1
        assert store.data["fp_store_s"].remaining == 1
        # A store-persisted bucket seeds a FRESH daemon's table through
        # the lane (restart survival — the whole point of the SPI).
        seeded = MockStore()
        seeded.data["fp_store_s"] = CacheItem(
            key="fp_store_s",
            algorithm=item.algorithm,
            expire_at=item.expire_at,
            limit=5,
            duration=60_000,
            remaining=2,
            created_at=item.created_at,
        )
        conf2 = DaemonConfig()
        conf2.store = seeded
        c2 = Cluster.start(1, conf_template=conf2)
        try:
            cl2 = V1Client(c2.addresses()[0])
            r2 = cl2.get_rate_limits([
                RateLimitReq(name="fp_store", unique_key="s", hits=1,
                             limit=5, duration=60_000)
            ])[0]
            assert r2.remaining == 1  # 2 seeded - 1, not a fresh 4
            assert _fp(c2).served == 1 and _fp(c2).fallbacks == 0
            cl2.close()
        finally:
            c2.stop()
        cl.close()
    finally:
        c.stop()


def test_fastpath_differential_duplicate_heavy(frozen_clock):
    """Random duplicate-heavy streams through the compiled lane must be
    bit-identical to the object path — including the host-cascade path for
    hot keys and the round-machinery fallback for mixed-param groups
    (the regression tier of functional_test.go:1106, fastpath edition)."""
    import asyncio
    import random

    from gubernator_tpu.core.config import Config
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        dev = DeviceConfig(num_slots=4096, ways=8, batch_size=128)
        # A never-closing GLOBAL sync window keeps the async broadcast
        # loops from re-reading state mid-test (the hits=0 re-read
        # mutates leak timestamps and would race the clock advances).
        from gubernator_tpu.core.config import BehaviorConfig

        quiet = BehaviorConfig(global_sync_wait_s=3600.0)
        s_fast = Service(
            Config(device=dev, behaviors=quiet), clock=frozen_clock
        )
        s_ref = Service(
            Config(device=dev, behaviors=quiet), clock=frozen_clock
        )
        await s_fast.start()
        await s_ref.start()
        fp = FastPath(s_fast)
        rng = random.Random(42)
        for step in range(25):
            n = rng.randint(1, 60)
            reqs = []
            for _ in range(n):
                behavior = 0
                if rng.random() < 0.05:
                    behavior |= 8  # RESET_REMAINING
                if rng.random() < 0.10:
                    behavior |= 2  # GLOBAL (single node = owner side)
                reqs.append(pb.RateLimitReq(
                    name="diff",
                    unique_key=f"d{rng.randint(0, 7)}",  # hot duplicates
                    hits=rng.choice([0, 1, 1, 1, 2, 3, -1]),
                    limit=rng.choice([20, 20, 20, 30]),
                    duration=60_000,
                    algorithm=rng.choice([0, 1]),
                    behavior=behavior,
                    burst=rng.choice([0, 0, 25]),
                ))
            payload = pb.GetRateLimitsReq(
                requests=reqs
            ).SerializeToString()
            out = await fp.check_raw(payload, peer_rpc=False)
            assert out is not None
            got = pb.GetRateLimitsResp.FromString(out).responses
            want = await s_ref.get_rate_limits(reqs_from_pb(reqs))
            assert len(got) == len(reqs)
            for j, (g, w) in enumerate(zip(got, want)):
                assert g.error == w.error, (step, j)
                assert g.status == int(w.status), (step, j)
                assert g.limit == w.limit, (step, j)
                assert g.remaining == w.remaining, (step, j)
                assert g.reset_time == w.reset_time, (step, j)
            frozen_clock.advance(rng.choice([0, 100, 5_000]))
        assert fp.served > 0
        await s_fast.close()
        await s_ref.close()

    asyncio.run(scenario())


def test_sparse_overlap_drains():
    """`sparse_limit` > 0 (every daemon runs 64; 0 disables): small
    drains may overlap the in-flight merge on an overlap slot.
    Pin the concurrency path — overlap drains actually trigger under
    concurrent small batches, every response stays correct (each key's
    decrement sequence is exact), and close() during traffic neither
    hangs nor orphans waiters."""
    # Depth 1 makes the sparse slot the ONLY overlap mechanism, so any
    # drain arriving while the single fetch slot is busy is
    # overlap-eligible.  No setting reaches the depth: the daemon's lane
    # is swapped for one built at depth 1 through the constructor.
    from gubernator_tpu.runtime.fastpath import FastPath

    c = Cluster.start(1)
    try:
        d = c.daemons[0]

        async def at_depth_one():
            served, d.fastpath = d.fastpath, FastPath(
                d.service, sparse_limit=64, pipeline_depth=1
            )
            await served.close()

        c.run(at_depth_one())
        fp = _fp(c)
        assert fp._mach._sparse_limit == 64 and fp.pipeline_depth == 1

        async def hammer(rounds_done: int):
            from gubernator_tpu.client import AsyncV1Client

            cl = AsyncV1Client(c.addresses()[0])

            async def one_client(i: int):
                for _ in range(30):
                    rs = await cl.get_rate_limits([
                        RateLimitReq(
                            name="sp", unique_key=f"c{i}", hits=1,
                            limit=1_000_000, duration=60_000,
                        )
                    ])
                    assert rs[0].error == ""
                return i

            await asyncio.gather(*(one_client(i) for i in range(8)))
            # Exact per-key totals despite overlapped merges.
            rs = await cl.get_rate_limits([
                RateLimitReq(name="sp", unique_key=f"c{i}", hits=0,
                             limit=1_000_000, duration=60_000)
                for i in range(8)
            ])
            want = 1_000_000 - 30 * rounds_done
            assert [r.remaining for r in rs] == [want] * 8
            await cl.close()

        # Whether an overlap drain triggers depends on client wakeups
        # de-synchronizing against in-flight fetches — guaranteed in the
        # limit but racy per round (a loaded host can lock-step one
        # hammer round into strictly serial merges).  Correctness is
        # asserted EVERY round; only the scheduling property retries.
        for rnd in range(1, 5):
            c.run(hammer(rnd), timeout=120)
            if fp._mach.overlap_drains > 0:
                break
        assert fp._mach.drains > 0
        assert fp._mach.overlap_drains > 0, (
            "overlap slot never used: drains=%d waited=%d"
            % (fp._mach.drains, fp._mach.waited_drains)
        )

        # close() with entries still queued: waiters must FAIL, not hang.
        async def close_mid_flight():
            from gubernator_tpu.client import AsyncV1Client

            cl = AsyncV1Client(c.addresses()[0])
            tasks = [
                asyncio.ensure_future(cl.get_rate_limits([
                    RateLimitReq(name="sp", unique_key=f"x{i}", hits=1,
                                 limit=10, duration=60_000)
                ]))
                for i in range(16)
            ]
            await asyncio.sleep(0)
            await fp.close()
            out = await asyncio.gather(*tasks, return_exceptions=True)
            # Every task finished one way or the other (served before the
            # close, or failed through it) — nothing left pending.
            assert len(out) == 16
            await cl.close()

        c.run(close_mid_flight(), timeout=120)
    finally:
        c.stop()


def test_fastpath_store_differential(frozen_clock):
    """Store-attached differential: identical mixed streams through the
    compiled lane and the object path must leave identical STORE contents
    (Store.get seeding, columnar capture, ticketed on_change) as well as
    identical responses and stored device rows — token and leaky, hot
    duplicates (cascade + capture), expiring buckets, GLOBAL owner side."""
    import asyncio
    import random

    from gubernator_tpu.core.config import BehaviorConfig, Config
    from gubernator_tpu.core.types import CacheItem
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service
    from gubernator_tpu.runtime.store import MockStore

    async def scenario():
        dev = DeviceConfig(num_slots=4096, ways=8, batch_size=128)
        quiet = BehaviorConfig(global_sync_wait_s=3600.0)
        store_f, store_r = MockStore(), MockStore()
        # Pre-seed BOTH stores so Store.get seeding (miss -> restore)
        # is exercised from the first batch.
        t0 = frozen_clock.millisecond_now()
        for st in (store_f, store_r):
            st.data["diff_d0"] = CacheItem(
                key="diff_d0", algorithm=0, expire_at=t0 + 60_000,
                limit=20, duration=60_000, remaining=7, created_at=t0,
            )
        s_fast = Service(
            Config(device=dev, behaviors=quiet, store=store_f),
            clock=frozen_clock,
        )
        s_ref = Service(
            Config(device=dev, behaviors=quiet, store=store_r),
            clock=frozen_clock,
        )
        await s_fast.start()
        await s_ref.start()
        fp = FastPath(s_fast)
        rng = random.Random(1234)
        for step in range(20):
            n = rng.randint(1, 50)
            reqs = []
            for _ in range(n):
                behavior = 0
                if rng.random() < 0.10:
                    behavior |= 2   # GLOBAL (single node = owner side)
                if rng.random() < 0.03:
                    behavior |= 8   # RESET_REMAINING (machinery rounds)
                key = f"d{rng.randint(0, 7)}"
                if rng.random() < 0.03:
                    key = ""        # validation error: no store calls
                reqs.append(pb.RateLimitReq(
                    name="diff",
                    unique_key=key,
                    hits=rng.choice([0, 1, 1, 1, 2, 3, -1]),
                    limit=rng.choice([20, 20, 20, 30]),
                    duration=rng.choice([60_000, 1_000]),
                    algorithm=rng.choice([0, 1]),
                    behavior=behavior,
                    burst=rng.choice([0, 0, 25]),
                ))
            payload = pb.GetRateLimitsReq(
                requests=reqs
            ).SerializeToString()
            out = await fp.check_raw(payload, peer_rpc=False)
            assert out is not None
            got = pb.GetRateLimitsResp.FromString(out).responses
            want = await s_ref.get_rate_limits(reqs_from_pb(reqs))
            for j, (g, w) in enumerate(zip(got, want)):
                assert g.error == w.error, (step, j)
                assert g.status == int(w.status), (step, j)
                assert g.remaining == w.remaining, (step, j)
                assert g.reset_time == w.reset_time, (step, j)
            # Drive the GLOBAL broadcast at the same stream point on both
            # services: the fast side ships drain-captured rows while the
            # ref side runs the zero-hit re-read (which, store-attached,
            # rides the full seeding/write-through path) — rows and store
            # contents must still match bit-for-bit.
            for svc in (s_fast, s_ref):
                upd = svc.global_mgr._take_updates()
                if upd:
                    await svc.global_mgr._broadcast_peers(upd)
            # Device rows AND store contents must match bit-for-bit.
            for k in [f"diff_d{i}" for i in range(8)]:
                a = s_fast.backend.get_cache_item(k)
                b = s_ref.backend.get_cache_item(k)
                ta = (
                    (a.remaining, a.expire_at, int(a.status), a.limit)
                    if a else None
                )
                tb = (
                    (b.remaining, b.expire_at, int(b.status), b.limit)
                    if b else None
                )
                assert ta == tb, (step, k)
                ia, ib = store_f.data.get(k), store_r.data.get(k)
                assert (ia is None) == (ib is None), (step, k)
                if ia is not None:
                    assert ia == ib, (step, k)
            assert store_f.called["get"] == store_r.called["get"], step
            frozen_clock.advance(rng.choice([0, 100, 5_000]))
        assert fp.served > 0
        assert store_f.called["on_change"] > 0
        await fp.close()
        await s_fast.close()
        await s_ref.close()

    asyncio.run(scenario())


def test_fastpath_sticky_token_status(frozen_clock):
    """The token stored status is STICKY (te_resp_status = s_status):
    after an over-at-zero, a limit raise makes under-branch responses
    report OVER until reset — the cascade and its write-back must
    reproduce this across batches exactly like the object path."""
    import asyncio

    from gubernator_tpu.core.config import Config
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        dev = DeviceConfig(num_slots=1024, ways=8, batch_size=64)
        s_fast = Service(Config(device=dev), clock=frozen_clock)
        s_ref = Service(Config(device=dev), clock=frozen_clock)
        await s_fast.start()
        await s_ref.start()
        fp = FastPath(s_fast)

        def batch(limit, hits, n):
            return [
                pb.RateLimitReq(name="sticky", unique_key="k", hits=hits,
                                limit=limit, duration=60_000)
                for _ in range(n)
            ]

        # Batch 1: drain r0=2 with 3 duplicate hits -> the 3rd is
        # over-at-zero and flips the stored status.
        # Batch 2: raise the limit; under-branch responses must report the
        # sticky OVER on both paths.
        for reqs in [batch(2, 1, 3), batch(4, 1, 2)]:
            payload = pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            out = await fp.check_raw(payload, peer_rpc=False)
            got = pb.GetRateLimitsResp.FromString(out).responses
            want = await s_ref.get_rate_limits(reqs_from_pb(reqs))
            for j, (g, w) in enumerate(zip(got, want)):
                assert g.status == int(w.status), j
                assert g.remaining == w.remaining, j
        await s_fast.close()
        await s_ref.close()

    asyncio.run(scenario())


def test_fastpath_new_leaky_bucket_over_asked_in_a_duplicate_group(
    frozen_clock,
):
    """A NEW leaky bucket asked for more than its burst is stored empty
    (algorithms.go:470-476), unlike an existing bucket's over-ask, which
    mutates nothing.  The host cascade's read lane creates the bucket
    FULL, so its replay must take the new-item branch itself — found by
    chip_smoke.py's wire stream against core/pymodel.py (PR 21)."""
    import asyncio

    from gubernator_tpu.core.config import Config
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        dev = DeviceConfig(num_slots=1024, ways=8, batch_size=64)
        svc = Service(Config(device=dev), clock=frozen_clock)
        await svc.start()
        fp = FastPath(svc)
        oracle = PyRateLimiter(clock=frozen_clock)

        def batch(key, hits_list):
            return [
                pb.RateLimitReq(name="lk", unique_key=key, hits=h,
                                limit=10, burst=20, duration=3_600_000,
                                algorithm=pb.LEAKY_BUCKET)
                for h in hits_list
            ]

        for reqs in [
            batch("fresh", [40, 40]),      # new + over-asked: stored 0
            batch("fresh", [1, 1]),        # stays empty
            batch("warm", [5, 5]),         # new, under: lattice as before
            batch("warm", [40, 40, 1]),    # EXISTING over-ask: no mutation
        ]:
            payload = pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            out = await fp.check_raw(payload, peer_rpc=False)
            got = pb.GetRateLimitsResp.FromString(out).responses
            for j, (g, r) in enumerate(zip(got, reqs_from_pb(reqs))):
                w = oracle.get_rate_limit(r)
                assert (g.status, g.remaining, g.reset_time) == (
                    int(w.status), w.remaining, w.reset_time
                ), (reqs[0].unique_key, j)
        assert fp.fallbacks == 0
        await fp.close()
        await svc.close()

    asyncio.run(scenario())


_DAY = 86_400_000


def _B(*items, limit=10, duration=_DAY):
    """One RPC of the peek differential: hits on the case's key `k`, or
    (key, hits) for a bystander key."""
    return ("batch", items, (limit, duration))


def _A(ms):
    return ("advance", ms, None)


# id -> (algorithm, steps).  Token: limit 10 unless a batch says
# otherwise; leaky: limit 10, burst 100, a token leaks every 0.1 day.
# Every case ends on the NEXT batches — a lone peek and a lone spend,
# served by the plain machinery from the table row — so that a wrong or
# missing write-back shows.
_PEEK_CASES = {
    "token-resident-peek-first": (0, [
        _B(1), _B(0, 1, 1), _B(0), _B(1)]),
    "token-resident-peek-middle": (0, [
        _B(1), _B(2, 0, 3), _B(0), _B(1)]),
    "token-resident-peek-last": (0, [
        _B(1), _B(2, 3, 0), _B(0), _B(1)]),
    "token-resident-peeks-only": (0, [
        _B(3), _B(0, 0, 0), _B(1, 0), _B(0), _B(1)]),
    "token-new-peek-first": (0, [
        _B(0, 1, 0, 2), _B(0), _B(1)]),
    "token-new-peeks-only": (0, [
        _B(0, 0, 0), _B(1, 1, 1), _B(0), _B(1)]),
    "token-new-over-asked-after-a-peek": (0, [
        _B(0, 11, 1), _B(0), _B(1)]),
    # r reaches 0 mid-group, a spend flips the stored status, the peek
    # after it reports the flip; the raised limit then shows the sticky
    # OVER that only the flip lane can have written.
    "token-peek-after-the-flip": (0, [
        _B(1, 1, 0, 1, 0, limit=2), _B(0, 1, 0, limit=4), _B(0, limit=4),
        _B(1, limit=4)]),
    # A peek at r == 0 is not an over-at-zero: no flip, no flip lane.
    "token-peek-at-zero-does-not-flip": (0, [
        _B(1, 1, 0, 0, limit=2), _B(0, 1, 0, limit=4), _B(0, limit=4),
        _B(1, limit=4)]),
    # The first peek renews the bucket (a shorter duration, already
    # past): it answers the remaining from BEFORE the renewal, as the
    # read lane did; the occurrences after it see the renewed bucket.
    "token-peek-first-renews": (0, [
        _B(10), _A(_DAY * 6 // 10),
        _B(0, 1, 0, duration=_DAY // 2), _B(0, duration=_DAY // 2),
        _B(1, duration=_DAY // 2)]),
    "token-all-over-limit-with-a-peek": (0, [
        _B(8), _B(5, 0, 5), _B(0), _B(1)]),
    "token-two-groups-and-a-bystander": (0, [
        _B(1, ("b", 1)), _B(0, ("b", 2), 1, ("c", 1), ("b", 0), 0),
        _B(0), _B(("b", 0)), _B(1, ("b", 1))]),
    "leaky-resident-peek-first": (1, [
        _B(5), _B(0, 1, 1), _B(0), _B(1)]),
    "leaky-resident-peek-middle": (1, [
        _B(5), _B(2, 0, 3), _B(0), _B(1)]),
    "leaky-resident-peek-last": (1, [
        _B(5), _B(2, 3, 0), _B(0), _B(1)]),
    "leaky-new-peek-first": (1, [
        _B(0, 1, 0, 2), _B(0), _B(1)]),
    # The bucket is created FULL by the peek; the over-ask then meets an
    # existing bucket and mutates nothing (where it comes first it
    # stores the new bucket empty).
    "leaky-new-over-asked-after-a-peek": (1, [
        _B(0, 400, 1), _B(0), _B(1)]),
    "leaky-new-over-asked-first": (1, [
        _B(400, 0, 1), _B(0), _B(1)]),
    "leaky-drained-mid-group": (1, [
        _B(98), _B(1, 0, 1, 0, 1, 0), _B(0), _B(1)]),
    "leaky-peek-at-zero": (1, [
        _B(100), _B(0, 0, 0), _B(0, 1, 0), _B(0), _B(1)]),
    # Every spend over the limit, eff == 0: the touch lane refreshes the
    # sliding expiry, so past the OLD expiry the bucket still stands
    # (57 tokens: 45 + two leaks of 6) where a fresh one would hold 100.
    "leaky-all-over-limit-with-a-peek-touches": (1, [
        _B(50, 5), _A(_DAY * 6 // 10), _B(200, 0, 200),
        _A(_DAY * 6 // 10), _B(0, 0), _B(1)]),
    # Peeks alone refresh nothing: past the old expiry the bucket is
    # gone on both sides and the read creates a full one.
    "leaky-peeks-only-leave-the-expiry": (1, [
        _B(50, 5), _A(_DAY * 6 // 10), _B(0, 0, 0),
        _A(_DAY * 6 // 10), _B(0, 0), _B(1)]),
    # A negative hit keeps its group on the round-per-occurrence path.
    "token-negative-hits-take-rounds": (0, [
        _B(3), _B(1, -1, 0), _B(0), _B(1)]),
    "leaky-negative-hits-take-rounds": (1, [
        _B(5), _B(0, -2, 1), _B(0), _B(1)]),
    # A PAIR costs two launches either way (read + write-back, or a round
    # an occurrence): the tie goes to the rounds, whose fetch is outside
    # the lock (_cascade_or_rounds), and the device's order of the two IS
    # the reference's.
    "token-pair-takes-rounds": (0, [
        _B(0, 0), _B(1, 1), _B(1, 0), _B(0, 11), _B(0), _B(1)]),
    "token-pair-at-zero-takes-rounds": (0, [
        _B(2, limit=2), _B(1, 1, limit=2), _B(1, 0, limit=4),
        _B(0, limit=4), _B(1, limit=4)]),
    "leaky-pair-takes-rounds": (1, [
        _B(1, 400), _B(0, 0), _B(98, 1), _B(1, 0), _B(0), _B(1)]),
    "leaky-new-over-asked-pair-takes-rounds": (1, [
        _B(400, 1), _B(0), _B(1)]),
    # ... unless a group beside it comes three times: the whole plan
    # cascades, the pair with it.
    "token-pair-beside-a-triple-cascades": (0, [
        _B(1, ("b", 1), 0, ("b", 0), 1), _B(0), _B(("b", 0)),
        _B(1, ("b", 1))]),
}


@pytest.mark.parametrize("case", sorted(_PEEK_CASES))
def test_fastpath_peeks_in_a_duplicate_group(frozen_clock, case):
    """A duplicate group may hold peeks (hits == 0) and still be served
    by the host cascade: every occurrence, and every answer of the
    batches after it, equals core/pymodel.py's — status, remaining and
    reset_time — and the lane.cascade counters say the replay (or, for
    negative hits, the rounds) did the work."""
    import asyncio

    from gubernator_tpu.core.config import Config
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    algo, steps = _PEEK_CASES[case]

    async def scenario():
        dev = DeviceConfig(num_slots=1024, ways=8, batch_size=64)
        svc = Service(Config(device=dev), clock=frozen_clock)
        await svc.start()
        fp = FastPath(svc)
        oracle = PyRateLimiter(clock=frozen_clock)
        groups = occ = peeks = 0
        for n_step, (kind, arg, lim_dur) in enumerate(steps):
            if kind == "advance":
                frozen_clock.advance(arg)
                continue
            limit, duration = lim_dur
            items = [i if isinstance(i, tuple) else ("k", i) for i in arg]
            reqs = [
                pb.RateLimitReq(
                    name="peek", unique_key=key, hits=h,
                    limit=limit, duration=duration,
                    burst=100 if algo else 0, algorithm=algo,
                )
                for key, h in items
            ]
            by_key = {
                key: [h for k, h in items if k == key] for key, _ in items
            }
            took = [hs for hs in by_key.values()
                    if len(hs) > 1 and min(hs) >= 0]
            # The rule (one lane a shard here): the cascade's rounds are
            # the longest group it does NOT take, or one, and a write-back.
            left = [len(hs) for hs in by_key.values() if hs not in took]
            if took and max(map(len, by_key.values())) > max(left + [1]) + 1:
                groups += len(took)
                occ += sum(map(len, took))
                peeks += sum(hs.count(0) for hs in took)
            payload = pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            out = await fp.check_raw(payload, peer_rpc=False)
            got = pb.GetRateLimitsResp.FromString(out).responses
            for j, (g, r) in enumerate(zip(got, reqs_from_pb(reqs))):
                w = oracle.get_rate_limit(r)
                assert (g.error, g.status, g.limit, g.remaining,
                        g.reset_time) == (
                    "", int(w.status), w.limit, w.remaining, w.reset_time
                ), (n_step, j)
        row = fp._stages.debug_vars()["mach"]["cascade"]
        assert fp.fallbacks == 0
        await fp.close()
        await svc.close()
        return row, groups, occ, peeks

    row, groups, occ, peeks = asyncio.run(scenario())
    if "take-rounds" in case or "takes-rounds" in case:
        assert row["count"] == 0 and "groups" not in row
    else:
        assert peeks > 0
        assert (row["groups"], row["occ"], row["peeks"]) == (
            groups, occ, peeks)


def _plan(hits, **cols):
    """_plan_cascade over one column set: keys 7,7,... unless given."""
    import numpy as np

    from gubernator_tpu.runtime.fastpath import _plan_cascade

    n = len(hits)
    z = np.zeros(n, dtype=bool)
    lim = np.full(n, 10, dtype=np.int64)
    c = dict(
        h=np.full(n, 7, dtype=np.int64), reset_remaining=z, is_greg=z,
        lim=lim, dur=lim * 1000, algo=np.zeros(n, dtype=np.int32),
        burst=lim, use_cached=z,
    )
    c.update({k: np.asarray(v, dtype=c[k].dtype) for k, v in cols.items()})
    plan = _plan_cascade(
        c["h"], np.asarray(hits, dtype=np.int64), c["reset_remaining"],
        c["is_greg"], c["lim"], c["dur"], c["algo"], c["burst"],
        c["use_cached"],
    )
    return None if plan is None else plan.occ.tolist()


@pytest.mark.parametrize("hits,cols,occ", [
    ([1, 1], {}, [True, True]),
    ([0, 1], {}, [True, True]),                # a peek belongs
    ([1, 0, 2, 0], {}, [True] * 4),
    ([0, 0], {}, [True, True]),                # peeks alone too
    ([1], {}, None),                           # no duplicate
    ([1, 2, 0], {"h": [7, 8, 9]}, None),
    ([0, 0], {"h": [0, 0]}, None),             # errored lanes: h == 0
    ([1, -1], {}, None),                       # negative hits: rounds
    ([0, -1, 0], {}, None),
    ([0, 1], {"reset_remaining": [False, True]}, None),
    ([0, 1], {"is_greg": [True, False]}, None),
    ([0, 1], {"lim": [10, 11]}, None),
    ([0, 1], {"dur": [10_000, 20_000]}, None),
    ([0, 1], {"algo": [0, 1]}, None),
    ([0, 1], {"burst": [10, 20]}, None),
    ([0, 1], {"use_cached": [True, False]}, None),
    ([0, 1], {"use_cached": [True, True]}, [True, True]),
    # Two groups and a single: only the group without the negative hit.
    ([0, 1, -1, 1, 0, 5], {"h": [7, 7, 8, 8, 9, 7]},
     [True, True, False, False, False, True]),
])
def test_plan_cascade_eligibility(hits, cols, occ):
    """Which duplicate groups the host cascade takes: more than one
    occurrence, one set of limit / duration / algorithm / burst, a
    uniform use_cached, and no occurrence with negative hits,
    RESET_REMAINING or a Gregorian duration; hits == 0 is no bar."""
    assert _plan(hits, **cols) == occ


def _drain_cols(checks, mult, dup_at, cached, negative=False):
    """Columns of one drain: `checks` lanes; key i + 1 comes mult[i]
    times, together at the front or at the back, every other key once."""
    import numpy as np

    dups = [k + 1 for k, m in enumerate(mult) for _ in range(m)]
    rest = list(range(len(mult) + 1, len(mult) + 1 + checks - len(dups)))
    h = np.array(dups + rest if dup_at == "front" else rest + dups,
                 dtype=np.int64)
    hits = np.ones(checks, dtype=np.int64)
    if negative:
        hits[h == 1] = -1
    return h, hits, np.full(checks, cached, dtype=bool)


@pytest.mark.parametrize("checks,B,mult,dup_at,cached,shards,cascades", [
    # One pair among 5,000 at 4096 lanes: two rounds anyway, three with a
    # write-back.
    (5000, 4096, [2], "front", False, 1, False),
    # Both of the pair among the last 900: three rounds either way.
    (5000, 4096, [2], "back", False, 1, False),
    # A pair in one round: a second 128-lane launch either way -- a tie.
    (64, 4096, [2], "front", False, 1, False),
    (64, 64, [2, 2, 2], "back", False, 1, False),
    # Three occurrences: three rounds against read + write-back.
    (64, 4096, [3], "front", False, 1, True),
    (64, 4096, [2, 3], "back", False, 1, True),
    (5000, 4096, [3], "front", False, 1, False),   # ... unless it has them
    (4000, 4096, [3], "front", False, 1, True),
    # token1k: 1,000 keys six times each.
    (6000, 4096, [6] * 1000, "front", False, 1, True),
    # use_cached groups never write back: one launch against two.
    (64, 4096, [2], "front", True, 1, True),
    (5000, 4096, [2], "front", True, 1, False),
    # An ineligible group takes its rounds, as before.
    (64, 4096, [3], "front", False, 1, None),
    # The mesh: 4096 lanes a shard.
    (5000, 4096, [2], "front", False, 4, False),
    (5000, 4096, [3], "back", False, 4, True),
])
def test_a_drain_cascades_only_where_that_saves_a_launch(
    checks, B, mult, dup_at, cached, shards, cascades,
):
    """_cascade_or_rounds: the drain's own hashes take `plain` launches
    (assign_rounds puts occurrence k a round after k-1); the cascade takes
    its read rounds and a write-back.  Cascade iff strictly fewer."""
    import numpy as np

    from gubernator_tpu.runtime.fastpath import (
        _cascade_or_rounds,
        _plan_cascade,
        _read_lanes,
    )

    h, hits, use_cached = _drain_cols(
        checks, mult, dup_at, cached, negative=cascades is None)
    z = np.zeros(checks, dtype=bool)
    lim = np.full(checks, 10, dtype=np.int64)
    plan = _plan_cascade(h, hits, z, z, lim, lim * 1000,
                         np.zeros(checks, dtype=np.int32), lim, use_cached)
    if cascades is None:
        assert plan is None
        return
    assert int(plan.occ.sum()) == sum(mult)
    h_mach = _read_lanes(plan, h)
    sh = (h % shards).astype(np.int32) if shards > 1 else None
    got, (rnd, lane, n_rounds) = _cascade_or_rounds(
        plan, h, h_mach, use_cached, sh, shards, B)
    assert got is cascades
    # The assignment is the chosen path's: every lane of the drain where
    # it goes plain, one read lane a group where it cascades.
    lanes = int((rnd >= 0).sum())
    assert lanes == (checks - sum(mult) + len(mult) if cascades else checks)
    want = native.assign_rounds(h_mach if cascades else h, sh, shards, B)
    assert n_rounds == want[2] and (rnd == want[0]).all()
    assert (lane == want[1]).all()


@pytest.fixture(scope="module")
def lane4096():
    """A service at the benchmark's batch_size, its compiled lane, and the
    ledger rows of its machinery lane."""
    from gubernator_tpu.core import clock as clock_mod
    from gubernator_tpu.core.config import Config
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    loop = asyncio.new_event_loop()
    clk = clock_mod.Clock()
    clk.freeze(1_790_000_000_000 * 1_000_000)
    dev = DeviceConfig(num_slots=1 << 17, ways=8, batch_size=4096)
    svc = Service(Config(device=dev), clock=clk)
    loop.run_until_complete(svc.start())
    fp = FastPath(svc)
    yield loop, fp, clk
    loop.run_until_complete(fp.close())
    loop.run_until_complete(svc.close())
    loop.close()


_PAIRS = {
    # id -> (algorithm, limit, burst, an RPC before the drain, the pair)
    "a-spend-and-a-peek": (0, 10, 0, None, (1, 0)),
    "a-token-bucket-at-zero": (0, 2, 0, (2,), (1, 1)),
    "a-leaky-bucket-created-then-over-asked": (1, 10, 100, None, (1, 400)),
}


@pytest.mark.parametrize("case", sorted(_PAIRS))
def test_one_pair_among_5000_checks_rides_the_drains_rounds(lane4096, case):
    """Five RPCs of 1,000 checks in ONE drain, 4,999 keys, one of them
    twice: the drain has two rounds anyway, so the pair's second
    occurrence takes a lane of round two.  Every answer is
    core/pymodel.py's; the merge is a plain one -- no lane.cascade, the
    wait for the device on the fetch stage -- and lane.pack says so."""
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb

    loop, fp, clk = lane4096
    algo, limit, burst, before, pair = _PAIRS[case]
    oracle = PyRateLimiter(clock=clk)

    def rows():
        return fp._stages.debug_vars()["mach"]

    def req(key, hits, a=algo, lim=limit, b=burst):
        return pb.RateLimitReq(name="pair5k", unique_key=key, hits=hits,
                               limit=lim, duration=3_600_000, burst=b,
                               algorithm=a)

    def rpc(i):
        reqs = [req(f"{case}.{i}.{j}", j % 3, a=j % 2, lim=10, b=0)
                for j in range(1000)]
        if i == 0:
            # The pair, far apart in the drain's first RPC.
            reqs[3], reqs[900] = req(case, pair[0]), req(case, pair[1])
        return reqs

    async def ask(reqs):
        out = await fp.check_raw(
            pb.GetRateLimitsReq(requests=reqs).SerializeToString(),
            peer_rpc=False)
        return pb.GetRateLimitsResp.FromString(out).responses

    def check(got, reqs, where):
        for j, (g, r) in enumerate(zip(got, reqs_from_pb(reqs))):
            w = oracle.get_rate_limit(r)
            assert (g.error, g.status, g.limit, g.remaining,
                    g.reset_time) == (
                "", int(w.status), w.limit, w.remaining, w.reset_time
            ), (where, j)

    seen = []
    inner = fp._mach._process

    def spy(entries):
        res = inner(entries)
        seen.append((len(entries), res.__name__,
                     rows()["d2h_wait"]["count"]))
        return res

    async def scenario():
        if before:
            reqs = [req(case, h) for h in before]
            check(await ask(reqs), reqs, "before")
        r0 = rows()
        mach = fp._mach
        mach._process = spy
        # Hold the dispatch slot until all five RPCs are queued: they
        # leave it as one merge.
        await mach._dispatch_sem.acquire()
        try:
            rpcs = [rpc(i) for i in range(5)]
            tasks = [asyncio.ensure_future(ask(r)) for r in rpcs]
            while len(mach._waits) < 5 or not mach._queue.empty():
                await asyncio.sleep(0.001)
        finally:
            mach._dispatch_sem.release()
        answers = await asyncio.gather(*tasks)
        mach._process = inner
        r1 = rows()
        for i, (got, reqs) in enumerate(zip(answers, rpcs)):
            check(got, reqs, i)
        for reqs in ([req(case, 0)], [req(case, 1)]):
            check(await ask(reqs), reqs, "after")
        return r0, r1

    r0, r1 = loop.run_until_complete(scenario())

    def d(stage, k="count"):
        return r1[stage].get(k, 0) - r0[stage].get(k, 0)

    # One drain of five entries, handed back as the plain continuation
    # before anything waited for the device.
    assert seen == [(5, "fetch_plain", r0["d2h_wait"]["count"])]
    assert d("drain") == d("pack") == d("d2h_wait") == 1
    assert d("cascade") == 0 and d("cascade", "occ") == 0
    assert (d("pack", "dup_plain"), d("pack", "dup_lanes")) == (1, 1)
    assert fp.fallbacks == 0


def test_multinode_columnar_routing():
    """Multi-node client path on the compiled lane: vectorized ring
    lookup, zero-copy forwards to owners, owner metadata on forwarded
    responses, and consistent counting across the cluster."""
    c = Cluster.start(3)
    try:
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        keys = [f"rt{i}" for i in range(60)]
        reqs = [
            RateLimitReq(name="route", unique_key=k, hits=1, limit=10,
                         duration=60_000)
            for k in keys
        ]
        r1 = cl.get_rate_limits(reqs)
        assert all(x.error == "" for x in r1)
        assert all(x.remaining == 9 for x in r1)
        r2 = cl.get_rate_limits(reqs)
        assert all(x.remaining == 8 for x in r2)
        # The router served them (no object-path fallback).
        assert fp.served == 120
        assert fp.fallbacks == 0
        # Forwarded responses carry the owner address; local ones don't.
        me = c.daemons[0].advertise_address()
        others = {d.advertise_address() for d in c.daemons[1:]}
        forwarded = [x for x in r2 if x.metadata]
        local = [x for x in r2 if not x.metadata]
        assert forwarded and local  # 60 keys spread over 3 nodes
        assert {x.metadata["owner"] for x in forwarded} <= others
        assert me not in {x.metadata.get("owner") for x in forwarded}
        # The owner side rode the peer fast lane on the other daemons
        # (both calls forwarded the same key set).
        assert sum(d.fastpath.served for d in c.daemons[1:]) == 2 * len(
            forwarded
        )
        # Validation errors answer locally even on the routed path.
        bad = cl.get_rate_limits([
            RateLimitReq(name="", unique_key="x", hits=1, limit=1,
                         duration=1000)
        ])
        assert bad[0].error == "field 'namespace' cannot be empty"
        cl.close()
    finally:
        c.stop()


def test_multinode_routing_peer_failure_fallback():
    """A dead owner mid-forward must degrade exactly like the object
    path: the ownership-retry loop runs and reports the reference's
    error string instead of hanging or crashing the batch."""
    c = Cluster.start(2)
    try:
        cl = V1Client(c.addresses()[0])
        # Find keys owned by daemon 1, then kill it without telling
        # daemon 0 (no discovery update).
        keys = [f"dead{i}" for i in range(40)]
        svc = c.daemons[0].service
        other = c.daemons[1].advertise_address()
        victim_keys = [
            k for k in keys
            if svc.get_peer(f"route_{k}").info().grpc_address == other
        ]
        assert victim_keys
        c.run(c.daemons[1].close(), timeout=60)

        reqs = [
            RateLimitReq(name="route", unique_key=k, hits=1, limit=10,
                         duration=60_000)
            for k in keys
        ]
        rs = cl.get_rate_limits(reqs)
        by_key = dict(zip(keys, rs))
        for k in victim_keys:
            assert by_key[k].error != "", k
        # Locally-owned keys still served cleanly.
        for k in set(keys) - set(victim_keys):
            assert by_key[k].error == "" and by_key[k].remaining == 9, k
        cl.close()
    finally:
        c.stop()


# -- sketch tier on the compiled lane --------------------------------------

from gubernator_tpu.core.config import SketchTierConfig  # noqa: E402

# A 1-hour window: the sliding window aligns to wall-clock boundaries
# (window_start = now - now % window_ms), so cross-RPC remaining
# assertions with a short window flake whenever the test happens to
# straddle a boundary and the estimate decays mid-test.
SKETCH_TPL = DaemonConfig(
    sketch=SketchTierConfig(
        names=["per_ip"], width=1024, window_ms=3_600_000, batch_size=128
    )
)


@pytest.fixture(scope="module")
def sketch_node():
    """Single daemon with an approximate tier attached — previously the
    whole service fell off the fast lane; now sketch-named lanes ride it
    via the parser's name_hash column."""
    c = Cluster.start(1, conf_template=SKETCH_TPL)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def sketch_client(sketch_node):
    cl = V1Client(sketch_node.addresses()[0])
    yield cl
    cl.close()


def test_sketch_lanes_ride_fast_lane(sketch_node, sketch_client):
    """Mixed exact + sketch batch on the compiled lane: same responses
    as the object path (tests/test_sketch_tier.py scenario), tier
    metadata included, no fallback."""
    fp = _fp(sketch_node)
    before, fb = fp.served, fp.fallbacks
    r = sketch_client.get_rate_limits([
        RateLimitReq(name="per_ip", unique_key="1.2.3.4", hits=2,
                     limit=5, duration=60_000),
        RateLimitReq(name="exact", unique_key="acct", hits=1,
                     limit=10, duration=60_000),
        RateLimitReq(name="per_ip", unique_key="5.6.7.8", hits=1,
                     limit=5, duration=60_000),
    ])
    assert fp.served == before + 3
    assert fp.fallbacks == fb
    assert r[0].metadata.get("tier") == "sketch"
    assert r[0].status == Status.UNDER_LIMIT
    assert r[0].remaining == 3
    assert r[0].limit == 5
    assert r[0].reset_time > 0
    assert r[1].metadata.get("tier") is None
    assert r[1].remaining == 9
    assert r[2].metadata.get("tier") == "sketch"
    assert r[2].remaining == 4

    # Drive one IP over its limit; the other stays under.
    for _ in range(2):
        r = sketch_client.get_rate_limits([
            RateLimitReq(name="per_ip", unique_key="1.2.3.4", hits=2,
                         limit=5, duration=60_000)
        ])
    assert r[0].status == Status.OVER_LIMIT
    r = sketch_client.get_rate_limits([
        RateLimitReq(name="per_ip", unique_key="5.6.7.8", hits=1,
                     limit=5, duration=60_000)
    ])
    assert r[0].status == Status.UNDER_LIMIT


def test_sketch_strips_global_on_fast_lane(sketch_node, sketch_client):
    """GLOBAL on a sketch name must not queue an exact-table broadcast
    (the object path's routing strip, service.py)."""
    fp = _fp(sketch_node)
    svc = sketch_node.daemons[0].service
    before = fp.served
    upd_before = dict(svc.global_mgr._updates)
    r = sketch_client.get_rate_limits([
        RateLimitReq(name="per_ip", unique_key="9.9.9.9", hits=1,
                     limit=5, duration=60_000, behavior=Behavior.GLOBAL),
    ])
    assert fp.served == before + 1
    assert r[0].metadata.get("tier") == "sketch"
    assert r[0].remaining == 4
    assert "per_ip_9.9.9.9" not in svc.global_mgr._updates
    assert svc.global_mgr._updates == upd_before


def test_sketch_ignores_gregorian_on_fast_lane(sketch_node, sketch_client):
    """The sketch tier ignores duration entirely, so an out-of-range
    Gregorian duration must NOT error a sketch lane (SketchBackend.check
    never computes it) — while an exact lane with the same duration
    does."""
    r = sketch_client.get_rate_limits([
        RateLimitReq(name="per_ip", unique_key="g", hits=1, limit=5,
                     duration=99, behavior=Behavior.DURATION_IS_GREGORIAN),
        RateLimitReq(name="exact", unique_key="g", hits=1, limit=5,
                     duration=99, behavior=Behavior.DURATION_IS_GREGORIAN),
    ])
    assert r[0].error == ""
    assert r[0].metadata.get("tier") == "sketch"
    assert r[1].error != ""


def test_sketch_forwarded_keeps_tier_and_owner_metadata():
    """Multi-node: sketch lanes route to the key's owner like plain
    lanes; the forwarder splices the owner's tier metadata verbatim and
    appends its own owner annotation."""
    c = Cluster.start(3, conf_template=SKETCH_TPL)
    try:
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        keys = [f"10.0.0.{i}" for i in range(40)]
        reqs = [
            RateLimitReq(name="per_ip", unique_key=k, hits=1, limit=10,
                         duration=60_000)
            for k in keys
        ]
        rs = cl.get_rate_limits(reqs)
        assert fp.served == len(keys)
        assert fp.fallbacks == 0
        assert all(x.error == "" for x in rs)
        assert all(x.metadata.get("tier") == "sketch" for x in rs)
        me = c.daemons[0].advertise_address()
        others = {d.advertise_address() for d in c.daemons[1:]}
        forwarded = [x for x in rs if "owner" in x.metadata]
        local = [x for x in rs if "owner" not in x.metadata]
        assert forwarded and local  # keys spread over 3 nodes
        assert {x.metadata["owner"] for x in forwarded} <= others
        assert me not in {x.metadata.get("owner") for x in forwarded}
        # Each owner counted its keys on ITS sketch: re-sending the same
        # traffic decrements remaining everywhere (state lives at the
        # owner, once per key).
        rs2 = cl.get_rate_limits(reqs)
        assert all(x.remaining == y.remaining - 1 for x, y in zip(rs2, rs))
        cl.close()
    finally:
        c.stop()


def test_native_name_hash_and_meta_frames():
    """Wire-codec invariants for the sketch route key and metadata
    splicing: name_hash == XXH64(name), and pre-encoded meta frames
    round-trip through serialize -> parse with the span preserved."""
    import numpy as np

    from gubernator_tpu.proto import gubernator_pb2 as pb

    req = pb.GetRateLimitsReq()
    req.requests.add(name="per_ip", unique_key="k1", hits=1, limit=5,
                     duration=1000)
    req.requests.add(name="other", unique_key="k2", hits=1, limit=5,
                     duration=1000)
    cols = native.parse_reqs(req.SerializeToString())
    assert cols is not None
    want = native.hash_keys(["per_ip", "other"])
    assert list(cols.name_hash) == list(want)

    frame = native.meta_frame(b"tier", b"sketch")
    frames = [frame + native.meta_frame(b"owner", b"h:81"), b"", frame]
    off = np.zeros(4, dtype=np.int64)
    np.cumsum([len(f) for f in frames], out=off[1:])
    raw = native.serialize_resps(
        np.array([1, 0, 0], dtype=np.int64),
        np.array([5, 5, 5], dtype=np.int64),
        np.array([0, 1, 2], dtype=np.int64),
        np.array([9, 9, 9], dtype=np.int64),
        b"", np.zeros(4, dtype=np.int64),
        b"".join(frames), off,
    )
    # python-protobuf agrees on the metadata content...
    resp = pb.GetRateLimitsResp.FromString(raw)
    assert dict(resp.responses[0].metadata) == {
        "tier": "sketch", "owner": "h:81"
    }
    assert dict(resp.responses[1].metadata) == {}
    assert dict(resp.responses[2].metadata) == {"tier": "sketch"}
    # ...and the columnar parser recovers each item's exact frame span.
    rc = native.parse_resps(raw)
    assert rc is not None and rc.n == 3
    for j, f in enumerate(frames):
        got = (
            raw[int(rc.meta_off[j]):int(rc.meta_off[j]) + int(rc.meta_len[j])]
            if rc.meta_len[j] > 0 else b""
        )
        assert got == f, j


# -- MULTI_REGION on the compiled lane -------------------------------------

def _record_queue_hits(svc):
    rec = []
    orig = svc.multi_region_mgr.queue_hits

    def wrapper(r):
        rec.append(r)
        orig(r)

    svc.multi_region_mgr.queue_hits = wrapper
    return rec


def test_multiregion_serves_and_queues_on_fast_lane():
    """MULTI_REGION lanes serve like plain lanes on the compiled lane,
    with owner-side hits queued to the region manager — duplicates
    aggregated to one queued request per unique key (the manager
    aggregates by key anyway)."""
    c = Cluster.start(1)
    try:
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        svc = c.daemons[0].service
        rec = _record_queue_hits(svc)
        before = fp.served
        r = cl.get_rate_limits([
            RateLimitReq(name="mr", unique_key="a", hits=1, limit=10,
                         duration=60_000, behavior=Behavior.MULTI_REGION),
            RateLimitReq(name="mr", unique_key="a", hits=3, limit=10,
                         duration=60_000, behavior=Behavior.MULTI_REGION),
            RateLimitReq(name="plain", unique_key="b", hits=1, limit=10,
                         duration=60_000),
        ])
        assert fp.served == before + 3
        assert [x.error for x in r] == ["", "", ""]
        # Duplicate-key lanes decremented sequentially like the exact
        # machinery always does.
        assert r[0].remaining == 9
        assert r[1].remaining == 6
        assert r[2].remaining == 9
        # ONE queued request for the duplicate group, hits summed; the
        # plain lane queued nothing.
        assert len(rec) == 1
        assert rec[0].unique_key == "a" and rec[0].hits == 4
    finally:
        c.stop()


def test_multiregion_forwarded_queues_at_owner():
    """Multi-node: a non-owned MULTI_REGION lane forwards to the owner,
    which queues the cross-region hit; the forwarder queues nothing."""
    c = Cluster.start(2)
    try:
        cl = V1Client(c.addresses()[0])
        svc0 = c.daemons[0].service
        other = c.daemons[1].advertise_address()
        # Keys owned by daemon 1 (forwarded) and daemon 0 (local).
        keys = [f"mrfwd{i}" for i in range(30)]
        remote = [
            k for k in keys
            if svc0.get_peer(f"mr_{k}").info().grpc_address == other
        ]
        local = [k for k in keys if k not in remote]
        assert remote and local
        rec0 = _record_queue_hits(svc0)
        rec1 = _record_queue_hits(c.daemons[1].service)
        rs = cl.get_rate_limits([
            RateLimitReq(name="mr", unique_key=k, hits=1, limit=10,
                         duration=60_000, behavior=Behavior.MULTI_REGION)
            for k in keys
        ])
        assert all(x.error == "" and x.remaining == 9 for x in rs)
        assert sorted(r.unique_key for r in rec0) == sorted(local)
        assert sorted(r.unique_key for r in rec1) == sorted(remote)
        cl.close()
    finally:
        c.stop()


# -- mesh GLOBAL (collective engine) on the compiled lane ------------------

def _stop_collective_loop(c, daemon_idx=0):
    """Cancel a daemon's background sync loop (no final flush) so tests
    drive engine.sync() deterministically — serving opens sync windows
    (notify), and a mid-test background flush would race assertions on
    pending/remaining."""
    async def stop():
        lp = c.daemons[daemon_idx].service._collective_loop
        if lp is not None and lp._task is not None:
            lp._task.cancel()
            await asyncio.gather(lp._task, return_exceptions=True)
            lp._task = None

    c.run(stop(), timeout=30)


def test_mesh_global_engine_rides_fast_lane():
    """Node-owned GLOBAL lanes on a mesh daemon serve through the
    collective GlobalEngine ON the compiled lane: replicated-cache
    serving with duplicate lanes sharing one aggregated response
    (engine semantics), pending hits queued for the next collective
    sync, and sync applying them to the auth table."""
    c = Cluster.start(
        1,
        device=DeviceConfig(
            num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
        ),
    )
    try:
        _stop_collective_loop(c)
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        svc = c.daemons[0].service
        eng = svc.global_engine
        assert eng is not None
        before, fb = fp.served, fp.fallbacks
        r = cl.get_rate_limits([
            RateLimitReq(name="eng", unique_key="a", hits=1, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
            RateLimitReq(name="eng", unique_key="a", hits=3, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
            RateLimitReq(name="plain", unique_key="p", hits=1, limit=10,
                         duration=60_000),
        ])
        assert fp.served == before + 3
        assert fp.fallbacks == fb  # no object-path fallback
        assert [x.error for x in r] == ["", "", ""]
        # Engine dedup: duplicates share ONE aggregated response
        # (hits summed to 4), unlike the machinery's sequential cascade.
        assert r[0].remaining == 6
        assert r[1].remaining == 6
        assert r[2].remaining == 9
        # The hit queued for the collective sync with summed hits...
        assert eng.pending["eng_a"].hits == 4
        # ...served from the replicated cache, not the auth table yet.
        assert eng.get_cached("eng_a") is not None
        # Sync applies the pending hits to the auth table.
        eng.sync()
        assert eng.pending == {}
        assert svc.backend.checks >= 1
        # A later serve is a stale-but-fast CACHED read (no local
        # decrement — getGlobalRateLimit semantics); its hit queues.
        r2 = cl.get_rate_limits([
            RateLimitReq(name="eng", unique_key="a", hits=1, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
        ])
        assert r2[0].remaining == 6
        assert eng.pending["eng_a"].hits == 1
        # The next sync folds that hit into the authoritative bucket and
        # broadcasts it back to the replicated cache.
        eng.sync()
        r3 = cl.get_rate_limits([
            RateLimitReq(name="eng", unique_key="a", hits=1, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
        ])
        assert r3[0].remaining == 5
        cl.close()
    finally:
        c.stop()


def test_mesh_global_engine_wire_matches_object_path():
    """Differential through the WIRE: a mesh daemon's fast-lane GLOBAL
    responses must equal the object path's for the same stream (the
    object path forced by detaching the daemon's fastpath)."""
    import numpy as np

    dev = DeviceConfig(
        num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
    )
    rng = np.random.default_rng(11)

    def stream():
        out = []
        for step in range(6):
            ks = rng.integers(0, 12, size=24)
            out.append([
                RateLimitReq(
                    name="dg", unique_key=f"k{k}", hits=1, limit=50,
                    duration=60_000, behavior=Behavior.GLOBAL,
                )
                for k in ks
            ])
        return out

    rng = np.random.default_rng(11)
    batches_a = stream()
    rng = np.random.default_rng(11)
    batches_b = stream()

    got = {}
    for label, batches, disable_fp in (
        ("fast", batches_a, False), ("object", batches_b, True)
    ):
        c = Cluster.start(1, device=dev)
        try:
            # Both runs must sync at the same (never) points — an
            # uncorrelated background flush mid-stream would change
            # `remaining` in one run only.
            _stop_collective_loop(c)
            if disable_fp:
                c.daemons[0].fastpath = None
            cl = V1Client(c.addresses()[0])
            resps = []
            for b in batches:
                resps.append([
                    (x.status, x.limit, x.remaining) for x in
                    cl.get_rate_limits(b)
                ])
            got[label] = resps
            cl.close()
        finally:
            c.stop()
    assert got["fast"] == got["object"]


def test_mesh_global_engine_background_sync_fires():
    """A single fast-lane GLOBAL hit must open the collective sync
    window (notify) — low-traffic nodes converge on the sync cadence,
    not only at the batch limit."""
    import time

    c = Cluster.start(
        1,
        device=DeviceConfig(
            num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
        ),
    )
    try:
        cl = V1Client(c.addresses()[0])
        svc = c.daemons[0].service
        r = cl.get_rate_limits([
            RateLimitReq(name="bg", unique_key="one", hits=2, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
        ])
        assert r[0].error == ""
        assert _fp(c).fallbacks == 0
        deadline = time.monotonic() + 10.0
        while svc.global_engine.pending:
            assert time.monotonic() < deadline, "sync window never fired"
            time.sleep(0.05)
        assert svc.backend.checks >= 1  # auth table received the hit
        cl.close()
    finally:
        c.stop()


@pytest.mark.parametrize("seed", [31, 9, 1])
def test_fastpath_differential_mixed_behaviors(frozen_clock, seed):
    """Randomized wire-level differential across the WHOLE behavior
    surface the fast lane serves: exact token/leaky, GLOBAL,
    MULTI_REGION, RESET_REMAINING, Gregorian (valid and invalid),
    sketch-named lanes (including GLOBAL+sketch stripping), validation
    errors, hot duplicates, and zero/negative hits — responses
    (including metadata) must be identical to the object path under a
    frozen clock."""
    import asyncio
    import random

    from gubernator_tpu.core.config import Config, SketchTierConfig
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        dev = DeviceConfig(num_slots=4096, ways=8, batch_size=64)
        sketch = SketchTierConfig(
            names=["sk"], width=2048, window_ms=3_600_000, batch_size=64
        )
        s_fast = Service(Config(device=dev, sketch=sketch),
                         clock=frozen_clock)
        s_ref = Service(Config(device=dev, sketch=sketch),
                        clock=frozen_clock)
        await s_fast.start()
        await s_ref.start()
        # The GLOBAL broadcast's zero-hit re-read mutates on algorithm/
        # params switches, so background flushes at uncorrelated stream
        # positions would diverge the two services' states even with
        # identical queues.  Cancel the loops and flush BOTH services at
        # the same point each step — which also differentially tests the
        # queued update content itself.
        for svc in (s_fast, s_ref):
            for t in svc.global_mgr._tasks:
                t.cancel()
            await asyncio.gather(
                *svc.global_mgr._tasks, return_exceptions=True
            )
            svc.global_mgr._tasks = []

        async def flush_globals() -> None:
            for svc in (s_fast, s_ref):
                upd = svc.global_mgr._take_updates()
                if upd:
                    await svc.global_mgr._broadcast_peers(upd)
                hits = svc.global_mgr._take_hits()
                if hits:
                    await svc.global_mgr._send_hits(hits)

        fp = FastPath(s_fast)
        rng = random.Random(seed)
        for step in range(25):
            n = rng.randint(1, 60)
            reqs = []
            for _ in range(n):
                behavior = 0
                if rng.random() < 0.08:
                    behavior |= 8   # RESET_REMAINING
                if rng.random() < 0.15:
                    behavior |= 2   # GLOBAL
                if rng.random() < 0.15:
                    behavior |= 16  # MULTI_REGION
                name = rng.choice(["ex", "ex", "ex", "sk", "sk"])
                # Short durations + the 120s clock jumps below cross
                # bucket expiry mid-stream.
                duration = rng.choice([60_000, 60_000, 1_000, 100])
                if name == "ex" and rng.random() < 0.08:
                    behavior |= 4   # DURATION_IS_GREGORIAN
                    duration = rng.choice([1, 4, 99])  # 99 = invalid
                key = f"d{rng.randint(0, 7)}"
                if rng.random() < 0.03:
                    key = ""        # validation error
                reqs.append(pb.RateLimitReq(
                    name=name,
                    unique_key=key,
                    hits=rng.choice([0, 1, 1, 1, 2, 3, -1]),
                    limit=rng.choice([20, 20, 20, 30]),
                    duration=duration,
                    algorithm=rng.choice([0, 1]),
                    behavior=behavior,
                    burst=rng.choice([0, 0, 25]),
                ))
            payload = pb.GetRateLimitsReq(
                requests=reqs
            ).SerializeToString()
            out = await fp.check_raw(payload, peer_rpc=False)
            assert out is not None
            got = pb.GetRateLimitsResp.FromString(out).responses
            want = await s_ref.get_rate_limits(reqs_from_pb(reqs))
            assert len(got) == len(reqs)
            for j, (g, w) in enumerate(zip(got, want)):
                assert g.error == w.error, (step, j)
                assert g.status == int(w.status), (step, j)
                assert g.limit == w.limit, (step, j)
                assert g.remaining == w.remaining, (step, j)
                assert g.reset_time == w.reset_time, (step, j)
                assert dict(g.metadata) == dict(w.metadata), (step, j)
            await flush_globals()
            # Responses alone can mask divergence (a later occurrence's
            # response may be computed before an earlier lane's write
            # semantics differ) — the STORED rows must match too.
            for k in [f"ex_d{i}" for i in range(8)]:
                a = s_fast.backend.get_cache_item(k)
                b = s_ref.backend.get_cache_item(k)
                ta = (
                    (a.remaining, a.expire_at, int(a.status), a.limit)
                    if a else None
                )
                tb = (
                    (b.remaining, b.expire_at, int(b.status), b.limit)
                    if b else None
                )
                assert ta == tb, (step, k)
            frozen_clock.advance(rng.choice([0, 100, 5_000, 120_000]))
        assert fp.served > 0
        await fp.close()
        await s_fast.close()
        await s_ref.close()

    asyncio.run(scenario())


def test_mesh_global_engine_routed_multinode():
    """Two mesh daemons: node-OWNED GLOBAL lanes ride the collective
    engine on the routed fast lane, non-owned GLOBAL lanes serve as
    cached reads with hits queued toward the owning node — and no
    owner-side RPC update broadcast is queued (the engine's sync bridge
    owns replication)."""
    dev = DeviceConfig(
        num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
    )
    c = Cluster.start(2, device=dev)
    try:
        _stop_collective_loop(c, 0)
        _stop_collective_loop(c, 1)

        # Also cancel node 0's RPC-tier manager loops: the 50ms hits
        # flush would drain global_mgr._hits mid-assertion.
        async def stop_mgr():
            mgr = c.daemons[0].service.global_mgr
            for t in mgr._tasks:
                t.cancel()
            await asyncio.gather(*mgr._tasks, return_exceptions=True)
            mgr._tasks = []

        c.run(stop_mgr(), timeout=30)
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        svc0 = c.daemons[0].service
        me = c.daemons[0].advertise_address()
        keys = [f"rte{i}" for i in range(40)]
        owned = [
            k for k in keys
            if svc0.get_peer(f"g_{k}").info().grpc_address == me
        ]
        remote = [k for k in keys if k not in owned]
        assert owned and remote
        rs = cl.get_rate_limits([
            RateLimitReq(name="g", unique_key=k, hits=1, limit=50,
                         duration=60_000, behavior=Behavior.GLOBAL)
            for k in keys
        ])
        by_key = dict(zip(keys, rs))
        assert all(x.error == "" for x in rs)
        assert fp.served == len(keys) and fp.fallbacks == 0
        # Owned keys: engine pending on node 0, no owner metadata, and
        # crucially NO RPC-tier update broadcast queued.
        for k in owned:
            assert f"g_{k}" in svc0.global_engine.pending, k
            assert "owner" not in by_key[k].metadata, k
        assert svc0.global_mgr._updates == {}
        # Non-owned keys: cached read annotated with the owning node,
        # hit queued toward it via the RPC tier.
        other = c.daemons[1].advertise_address()
        for k in remote:
            assert by_key[k].metadata.get("owner") == other, k
            assert f"g_{k}" in svc0.global_mgr._hits, k
            assert f"g_{k}" not in svc0.global_engine.pending, k
        cl.close()
    finally:
        c.stop()


def test_multinode_store_on_fast_lane():
    """Store hooks on a 2-node cluster ride the lane on BOTH sides of a
    forward: the owner's peer-RPC drain seeds/captures into the OWNER's
    store (per-node persistence, like the reference's per-instance
    store) and the non-owner's store never sees the key.  (Restart
    survival itself is pinned by test_store_served_on_fast_lane and
    test_mesh_engine_store_on_fast_lane.)"""
    from gubernator_tpu.runtime.store import MockStore

    stores = [MockStore(), MockStore()]
    # conf_template is shared by all daemons; attach per-daemon stores by
    # starting with one template and swapping after boot is NOT possible
    # (store binds at backend construction) — so start two 1-node
    # clusters and join them manually instead.
    from gubernator_tpu.core.types import PeerInfo

    cs = []
    for st in stores:
        conf = DaemonConfig()
        conf.store = st
        cs.append(Cluster.start(1, conf_template=conf))
    try:
        d0, d1 = cs[0].daemons[0], cs[1].daemons[0]
        peers = [
            PeerInfo(grpc_address=d0.grpc_address),
            PeerInfo(grpc_address=d1.grpc_address),
        ]
        cs[0].run(d0.set_peers(peers), timeout=30)
        cs[1].run(d1.set_peers(peers), timeout=30)

        cl = V1Client(d0.grpc_address)
        keys = [f"mk{i}" for i in range(24)]
        rs = cl.get_rate_limits([
            RateLimitReq(name="mn", unique_key=k, hits=1, limit=9,
                         duration=60_000)
            for k in keys
        ])
        assert all(r.error == "" for r in rs)
        assert all(r.remaining == 8 for r in rs)
        # Ownership decides WHICH store captured each key.
        own0 = {
            k for k in keys
            if d0.service.get_peer(f"mn_{k}").info().grpc_address
            == d0.grpc_address
        }
        assert own0 and len(own0) < len(keys)  # both nodes own some
        for k in keys:
            key = f"mn_{k}"
            if k in own0:
                assert key in stores[0].data and key not in stores[1].data
                assert stores[0].data[key].remaining == 8
            else:
                assert key in stores[1].data and key not in stores[0].data
                assert stores[1].data[key].remaining == 8
        # Both daemons served their side on the lane.
        assert d0.fastpath.fallbacks == 0
        assert d1.fastpath.fallbacks == 0
        assert d0.fastpath.served > 0 and d1.fastpath.served > 0
        cl.close()
    finally:
        for c in cs:
            c.stop()


def test_mesh_engine_store_on_fast_lane():
    """A mesh daemon with a Store serves GLOBAL lanes on the engine fast
    lane: serve_packed seeds never-seen keys from Store.get (a persisted
    GLOBAL bucket survives restart instead of resetting), and the sync
    tier delivers write-through on_change for the synced keys."""
    from gubernator_tpu.core.types import CacheItem
    from gubernator_tpu.runtime.store import MockStore

    dev = DeviceConfig(
        num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
    )
    store = MockStore()
    conf = DaemonConfig()
    conf.store = store
    c = Cluster.start(1, device=dev, conf_template=conf)
    try:
        _stop_collective_loop(c, 0)
        svc = c.daemons[0].service
        now = svc.clock.millisecond_now()
        # Persisted GLOBAL bucket: 3 of 10 left from a previous process.
        store.data["g_k1"] = CacheItem(
            key="g_k1", algorithm=0, expire_at=now + 60_000, limit=10,
            duration=60_000, remaining=3, created_at=now,
        )
        cl = V1Client(c.addresses()[0])
        fp = _fp(c)
        rs = cl.get_rate_limits([
            RateLimitReq(name="g", unique_key="k1", hits=1, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
            RateLimitReq(name="g", unique_key="k2", hits=1, limit=10,
                         duration=60_000, behavior=Behavior.GLOBAL),
        ])
        assert [r.error for r in rs] == ["", ""]
        assert fp.served == 2 and fp.fallbacks == 0
        assert rs[0].remaining == 2   # seeded 3 - 1, not a fresh 9
        assert rs[1].remaining == 9
        assert store.called["get"] == 2
        # Write-through happens at the engine's sync tier.
        before = store.called["on_change"]
        c.run(_engine_sync(svc), timeout=60)
        assert store.called["on_change"] > before
        assert store.data["g_k1"].remaining == 2
        assert store.data["g_k2"].remaining == 9
        cl.close()
    finally:
        c.stop()


async def _engine_sync(svc):
    import asyncio as _a

    loop = _a.get_running_loop()
    await loop.run_in_executor(
        svc._dev_executor, svc.global_engine.sync
    )


def test_errored_global_queue_semantics(sketch_node, sketch_client):
    """Client-path queueing for errored GLOBAL requests mirrors the
    reference: VALIDATION errors are rejected before routing
    (gubernator.go:228-237) and queue NOTHING, sketch or exact name; a
    GREGORIAN failure happens inside the algorithm AFTER QueueUpdate
    (gubernator.go:617-619), so an exact-named Gregorian-errored GLOBAL
    request queues its update, while a sketch-named one (whose tier
    ignores duration entirely) queues nothing."""
    svc = sketch_node.daemons[0].service
    rs = sketch_client.get_rate_limits([
        RateLimitReq(name="per_ip", unique_key="", hits=1, limit=5,
                     duration=60_000, behavior=Behavior.GLOBAL),
        RateLimitReq(name="exactg", unique_key="", hits=1, limit=5,
                     duration=60_000, behavior=Behavior.GLOBAL),
    ])
    assert rs[0].error == rs[1].error == "field 'unique_key' cannot be empty"
    assert "per_ip_" not in svc.global_mgr._updates
    assert "exactg_" not in svc.global_mgr._updates
    greg = Behavior.GLOBAL | Behavior.DURATION_IS_GREGORIAN
    rs = sketch_client.get_rate_limits([
        RateLimitReq(name="exactg", unique_key="g", hits=1, limit=5,
                     duration=99, behavior=greg),      # 99 = invalid
        RateLimitReq(name="per_ip", unique_key="g", hits=1, limit=5,
                     duration=99, behavior=greg),      # sketch: no greg
    ])
    assert "not a valid gregorian interval" in rs[0].error
    assert rs[1].error == ""   # sketch tier ignores duration
    assert "exactg_g" in svc.global_mgr._updates
    assert "per_ip_g" not in svc.global_mgr._updates


def _free_ports(n):
    """Pick n currently-free TCP ports.  The wire differentials need the
    SAME ports across their two sequential runs (identical advertise
    addresses => identical vnode rings), but hardcoded ports collide
    when suites run in parallel on one host (pytest-xdist/CI) — so pick
    dynamically once per test and reuse for both runs.  A daemon binds
    its listeners only after its warm-up, tens of seconds after the
    pick (and the second run binds them again), so the ports are drawn
    BELOW the kernel's ephemeral range: there no other worker's port-0
    listener and no outgoing connection can take one meanwhile.  All n
    sockets stay bound until every port is collected so the picks are
    distinct."""
    import random
    import socket

    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            ephemeral_lo = int(f.read().split()[0])
    except (OSError, ValueError):
        ephemeral_lo = 32768
    socks = []
    try:
        for port in random.sample(range(10_000, ephemeral_lo), 512):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                s.close()
                continue
            socks.append(s)
            if len(socks) == n:
                break
        assert len(socks) == n, "no free ports below the ephemeral range"
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


async def _diff_pair_start(grpc_ports, http_ports, device, disable_fp,
                           picker_hash="xx"):
    """Two-daemon pair on caller-pinned ports (identical vnode rings
    across sequential runs), background flush loops cancelled for
    deterministic replication, fast lane optionally detached — the
    shared harness of the sequential wire differentials."""
    from gubernator_tpu.core.config import fast_test_behaviors
    from gubernator_tpu.core.types import PeerInfo
    from gubernator_tpu.daemon import Daemon, wait_for_connect

    daemons = []
    for i in range(2):
        conf = DaemonConfig(
            grpc_listen_address=f"127.0.0.1:{grpc_ports[i]}",
            http_listen_address=f"127.0.0.1:{http_ports[i]}",
            behaviors=fast_test_behaviors(),
            device=device,
            local_picker_hash=picker_hash,
        )
        d = Daemon(conf)
        await d.start()
        d.conf.advertise_address = d.grpc_address
        daemons.append(d)
    peers = [PeerInfo(grpc_address=d.grpc_address) for d in daemons]
    for d in daemons:
        await d.set_peers(peers)
    await wait_for_connect([d.grpc_address for d in daemons])
    for d in daemons:
        svc = d.service
        lp = svc._collective_loop
        if lp is not None and lp._task is not None:
            lp._task.cancel()
            await asyncio.gather(lp._task, return_exceptions=True)
            lp._task = None
        mgr = svc.global_mgr
        for t in mgr._tasks:
            t.cancel()
        await asyncio.gather(*mgr._tasks, return_exceptions=True)
        mgr._tasks = []
    if disable_fp:
        for d in daemons:
            d.fastpath = None
    return daemons


async def _diff_pair_flush_hits(daemons):
    for d in daemons:
        mgr = d.service.global_mgr
        hits = mgr._take_hits()
        if hits:
            await mgr._send_hits(hits)


async def _diff_pair_broadcast(daemons):
    for d in daemons:
        mgr = d.service.global_mgr
        upd = mgr._take_updates()
        if upd:
            await mgr._broadcast_peers(upd)


async def _diff_pair_finish(daemons, cl):
    await cl.close()
    served = sum(
        d.fastpath.served for d in daemons if d.fastpath is not None
    )
    fallbacks = sum(
        d.fastpath.fallbacks for d in daemons if d.fastpath is not None
    )
    for d in daemons:
        await d.close()
    return served, fallbacks


@pytest.mark.parametrize("picker_hash", ["xx", "fnv1", "fnv1a"])
def test_multinode_routed_wire_differential(frozen_clock, picker_hash):
    """Routed-path differential through REAL sockets: the same mixed
    stream against two sequential 2-daemon clusters on IDENTICAL fixed
    ports (=> identical vnode rings), one serving on the fast lane and
    one with it detached — responses AND every daemon's stored rows must
    match bit-for-bit, with GLOBAL hit/broadcast flushes driven at
    identical stream points.  Parameterized over the ring hash: fnv1 /
    fnv1a are the reference-placement interop rings, which the columnar
    router must keep serving (gub_fnv_hashkey_batch) with ZERO
    fallbacks."""
    import random

    from gubernator_tpu.client import AsyncV1Client
    from gubernator_tpu.core import clock as clock_mod

    t0 = frozen_clock.millisecond_now()
    keys = [f"rd{i}" for i in range(6)]
    ports = _free_ports(4)

    async def run_once(disable_fp):
        clock_mod.freeze(at_ns=t0 * 1_000_000)
        daemons = await _diff_pair_start(
            ports[:2], ports[2:],
            DeviceConfig(num_slots=4096, ways=8, batch_size=64),
            disable_fp, picker_hash=picker_hash,
        )
        cl = AsyncV1Client(daemons[0].grpc_address)
        rng = random.Random(77)
        outs = []
        for step in range(10):
            n = rng.randint(1, 40)
            reqs = []
            for _ in range(n):
                behavior = 0
                if rng.random() < 0.2:
                    behavior |= 2   # GLOBAL
                if rng.random() < 0.08:
                    behavior |= 8   # RESET_REMAINING
                key = rng.choice(keys)
                if rng.random() < 0.04:
                    key = ""
                reqs.append(RateLimitReq(
                    name="rt", unique_key=key,
                    hits=rng.choice([0, 1, 1, 2, -1]),
                    limit=rng.choice([20, 30]),
                    duration=rng.choice([60_000, 1_000]),
                    algorithm=Algorithm(rng.choice([0, 1])),
                    behavior=Behavior(behavior),
                    burst=rng.choice([0, 0, 25]),
                ))
            rs = await cl.get_rate_limits(reqs)
            outs.append([
                (r.error, int(r.status), r.limit, r.remaining,
                 r.reset_time, tuple(sorted(r.metadata.items())))
                for r in rs
            ])
            # Deterministic flushes: hits reach owners, then broadcasts.
            await _diff_pair_flush_hits(daemons)
            await _diff_pair_broadcast(daemons)
            state = []
            for d in daemons:
                for k in keys:
                    it = d.service.backend.get_cache_item(f"rt_{k}")
                    state.append(
                        (it.remaining, it.expire_at, int(it.status),
                         it.limit) if it else None
                    )
            outs.append(state)
            clock_mod.advance(rng.choice([0, 100, 5_000]))
        served, fallbacks = await _diff_pair_finish(daemons, cl)
        return outs, served, fallbacks

    async def scenario():
        fast, served, fallbacks = await run_once(disable_fp=False)
        assert served > 0  # the lane actually ran in run A
        assert fallbacks == 0, (
            f"{picker_hash} ring must be fast-lane served"
        )
        obj, _, _ = await run_once(disable_fp=True)
        for step, (a, b) in enumerate(zip(fast, obj)):
            assert a == b, f"divergence at record {step}"

    asyncio.run(scenario())


def test_mesh_cluster_wire_differential(frozen_clock):
    """Mesh-cluster differential through real sockets: two sequential
    2-daemon MESH clusters on identical fixed ports, fast lane on vs
    detached, GLOBAL-heavy traffic — responses, both auth tables, the
    engines' replicated caches, and pending queues must match, with
    hits-flush -> collective sync -> broadcast driven at identical
    stream points."""
    import random

    from gubernator_tpu.client import AsyncV1Client
    from gubernator_tpu.core import clock as clock_mod

    t0 = frozen_clock.millisecond_now()
    keys = [f"mg{i}" for i in range(6)]
    dev = DeviceConfig(
        num_slots=8 * 8 * 64, ways=8, batch_size=64, num_shards=8
    )
    ports = _free_ports(4)

    async def run_once(disable_fp):
        clock_mod.freeze(at_ns=t0 * 1_000_000)
        daemons = await _diff_pair_start(
            ports[:2], ports[2:], dev, disable_fp
        )
        cl = AsyncV1Client(daemons[0].grpc_address)
        rng = random.Random(55)
        loop = asyncio.get_running_loop()
        outs = []
        for step in range(8):
            n = rng.randint(1, 30)
            reqs = []
            for _ in range(n):
                behavior = 2 if rng.random() < 0.6 else 0  # GLOBAL-heavy
                reqs.append(RateLimitReq(
                    name="mg", unique_key=rng.choice(keys),
                    hits=rng.choice([1, 1, 2]),
                    limit=50, duration=60_000,
                    behavior=Behavior(behavior),
                ))
            rs = await cl.get_rate_limits(reqs)
            outs.append([
                (r.error, int(r.status), r.limit, r.remaining,
                 r.reset_time, tuple(sorted(r.metadata.items())))
                for r in rs
            ])
            # Deterministic replication: hits -> collective sync ->
            # bridge callbacks -> broadcasts, same points both runs.
            await _diff_pair_flush_hits(daemons)
            for d in daemons:
                await loop.run_in_executor(
                    d.service._dev_executor, d.service.global_engine.sync
                )
            await asyncio.sleep(0)  # let _engine_synced callbacks land
            await _diff_pair_broadcast(daemons)
            state = []
            for d in daemons:
                svc = d.service
                for k in keys:
                    it = svc.backend.get_cache_item(f"mg_{k}")
                    state.append(
                        (it.remaining, it.expire_at, int(it.status))
                        if it else None
                    )
                    state.append(svc.global_engine.get_cached(f"mg_{k}"))
                state.append(sorted(
                    (k, p.hits)
                    for k, p in svc.global_engine.pending.items()
                ))
            outs.append(state)
            clock_mod.advance(rng.choice([0, 100, 5_000]))
        served, _ = await _diff_pair_finish(daemons, cl)
        return outs, served

    async def scenario():
        fast, served = await run_once(disable_fp=False)
        assert served > 0
        obj, _ = await run_once(disable_fp=True)
        for step, (a, b) in enumerate(zip(fast, obj)):
            assert a == b, f"divergence at record {step}"

    asyncio.run(scenario())
