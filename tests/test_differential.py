"""Differential fuzzing: device kernel vs sequential oracle.

Drives randomized op streams (mixed algorithms, limit/duration changes,
resets, negative hits, time advances, duplicate keys) through both the
vectorized device step and the exact sequential model; every response must
match bit-for-bit while no evictions occur (table sized to hold the whole
key space).

This is the TPU analog of the reference's algorithm test tiers — instead of
goroutine-race coverage (`go test -race`), correctness-under-vectorization is
the thing to prove (SURVEY.md §7 "hard parts").
"""
import random

import pytest

from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.pymodel import PyRateLimiter
from gubernator_tpu.core.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
)
from gubernator_tpu.runtime.backend import DeviceBackend


def _random_req(rng: random.Random, n_keys: int) -> RateLimitReq:
    algo = rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET])
    behavior = Behavior.BATCHING
    if rng.random() < 0.05:
        behavior |= Behavior.RESET_REMAINING
    if rng.random() < 0.10:
        behavior |= Behavior.DURATION_IS_GREGORIAN
    hits = rng.choice([0, 1, 1, 1, 2, 5, -1, 100])
    limit = rng.choice([0, 1, 2, 10, 100, 2000])
    if behavior & Behavior.DURATION_IS_GREGORIAN:
        duration = rng.choice([0, 1, 2])  # minutes/hours/days
    else:
        duration = rng.choice([5, 1000, 30_000, 60_000])
    burst = rng.choice([0, 0, 0, 20])
    return RateLimitReq(
        name=f"diff_{rng.randrange(4)}",
        unique_key=f"k:{rng.randrange(n_keys)}",
        algorithm=algo,
        behavior=behavior,
        hits=hits,
        limit=limit,
        duration=duration,
        burst=burst,
    )


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_differential_random_stream(seed, frozen_clock):
    rng = random.Random(seed)
    n_keys = 40  # 4 names x 40 keys = up to 160 distinct hash keys
    oracle = PyRateLimiter(clock=frozen_clock)
    device = DeviceBackend(
        DeviceConfig(num_slots=2048, ways=8, batch_size=64),
        clock=frozen_clock,
    )

    for step in range(60):
        batch = [_random_req(rng, n_keys) for _ in range(rng.randrange(1, 48))]
        dev_resps = device.check(batch)
        for i, req in enumerate(batch):
            want = oracle.get_rate_limit(req)
            got = dev_resps[i]
            ctx = f"step={step} i={i} req={req}"
            assert got.status == want.status, ctx
            assert got.remaining == want.remaining, ctx
            assert got.limit == want.limit, ctx
            assert got.reset_time == want.reset_time, ctx
        # Random time advance, including past expiries.
        frozen_clock.advance(rng.choice([0, 1, 500, 3_000, 61_000]))


def test_eviction_under_pressure(frozen_clock):
    """Tiny table, many keys: decisions must stay sane (new-item semantics)
    even when state is evicted — the acceptable-loss contract
    (architecture.md:5-11)."""
    device = DeviceBackend(
        DeviceConfig(num_slots=32, ways=8, batch_size=64), clock=frozen_clock
    )
    for round_i in range(6):
        reqs = [
            RateLimitReq(
                name="evict",
                unique_key=f"k:{i}",
                limit=10,
                hits=1,
                duration=60_000,
            )
            for i in range(round_i * 40, round_i * 40 + 40)
        ]
        resps = device.check(reqs)
        for r in resps:
            assert r.error == ""
            assert r.remaining == 9  # all fresh keys
    occ = device.occupancy()
    assert occ <= 32


MESH_DEV = DeviceConfig(num_slots=8 * 8 * 64, ways=8, batch_size=64,
                        num_shards=8)


@pytest.mark.parametrize("seed", [1, 2])
def test_differential_mesh_stream(seed, frozen_clock):
    """The random op-stream oracle, run against the 8-shard MeshBackend
    (VERDICT r2 #3): shard routing + the grid packer must be bit-identical
    to the sequential model, round for round."""
    from gubernator_tpu.parallel.sharded import MeshBackend

    rng = random.Random(seed)
    n_keys = 40
    oracle = PyRateLimiter(clock=frozen_clock)
    device = MeshBackend(MESH_DEV, clock=frozen_clock)

    for step in range(40):
        batch = [_random_req(rng, n_keys) for _ in range(rng.randrange(1, 48))]
        dev_resps = device.check(batch)
        for i, req in enumerate(batch):
            want = oracle.get_rate_limit(req)
            got = dev_resps[i]
            ctx = f"step={step} i={i} req={req}"
            assert got.status == want.status, ctx
            assert got.remaining == want.remaining, ctx
            assert got.limit == want.limit, ctx
            assert got.reset_time == want.reset_time, ctx
        frozen_clock.advance(rng.choice([0, 1, 500, 3_000, 61_000]))


@pytest.mark.parametrize("kind", ["device", "mesh"])
def test_differential_zipfian_duplicates(kind, frozen_clock):
    """Duplicate-heavy Zipfian streams (the BASELINE config-2 shape):
    hot keys repeat many times per batch, so the round machinery carries
    most occurrences — every one must match the sequential oracle."""
    from gubernator_tpu.parallel.sharded import MeshBackend

    rng = random.Random(11)
    oracle = PyRateLimiter(clock=frozen_clock)
    if kind == "device":
        device = DeviceBackend(
            DeviceConfig(num_slots=2048, ways=8, batch_size=64),
            clock=frozen_clock,
        )
    else:
        device = MeshBackend(MESH_DEV, clock=frozen_clock)

    for step in range(20):
        batch = []
        for _ in range(rng.randrange(10, 60)):
            key = f"z{min(int(rng.paretovariate(0.8)), 30)}"
            batch.append(RateLimitReq(
                name="zipf",
                unique_key=key,
                hits=rng.choice([0, 1, 1, 1, 2]),
                limit=500,
                duration=60_000,
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
                burst=rng.choice([0, 0, 600]),
            ))
        dev_resps = device.check(batch)
        for i, req in enumerate(batch):
            want = oracle.get_rate_limit(req)
            got = dev_resps[i]
            ctx = f"step={step} i={i} req={req}"
            assert got.status == want.status, ctx
            assert got.remaining == want.remaining, ctx
            assert got.reset_time == want.reset_time, ctx
        frozen_clock.advance(rng.choice([0, 0, 250, 2_000]))


@pytest.mark.parametrize("collective", ["psum", "a2a"])
def test_differential_global_engine_sync_interleavings(
    collective, frozen_clock
):
    """GLOBAL collective engine vs the oracle, with random sync points
    (VERDICT r2 #3): between syncs hits aggregate per key (last request's
    params, summed hits — global.go:87-95); each sync must leave the AUTH
    table bit-identical to the oracle applying the same aggregates at the
    same frozen time.  Probed with hits=0 reads on both sides.  Runs
    under BOTH sync collectives — the one-psum default and the
    all_to_all reference form (parallel/global_sync.py)."""
    from dataclasses import replace as dc_replace

    from gubernator_tpu.parallel.global_sync import GlobalEngine
    from gubernator_tpu.parallel.sharded import MeshBackend

    rng = random.Random(7)
    b = MeshBackend(MESH_DEV, clock=frozen_clock)
    eng = GlobalEngine(b, collective=collective)
    oracle = PyRateLimiter(clock=frozen_clock)
    pend = {}  # key -> (last req, summed hits)
    seen = set()

    for step in range(40):
        for _ in range(rng.randrange(1, 24)):
            req = RateLimitReq(
                name="g",
                unique_key=f"k{rng.randrange(12)}",
                hits=rng.choice([1, 1, 2, 3]),
                limit=50,
                duration=60_000,
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
            )
            key = req.hash_key()
            cur = pend.get(key)
            pend[key] = (req, (cur[1] if cur else 0) + req.hits)
            seen.add(key)
            eng.check([req])
        if rng.random() < 0.5 and pend:
            assert eng.sync() == len(pend)
            for key, (req, h) in pend.items():
                oracle.get_rate_limit(dc_replace(req, hits=h))
            pend.clear()
            # Auth state must now match the oracle exactly: hits=0 probes
            # through both engines (same frozen now -> same reset_time).
            probes = [
                dc_replace(pend_req, hits=0)
                for pend_req in [
                    RateLimitReq(name="g", unique_key=k.split("_", 1)[1],
                                 hits=0, limit=50, duration=60_000)
                    for k in sorted(seen)
                ]
            ]
            got = b.check(probes)
            for probe, g in zip(probes, got):
                want = oracle.get_rate_limit(probe)
                ctx = f"step={step} key={probe.unique_key}"
                assert g.status == want.status, ctx
                assert g.remaining == want.remaining, ctx
                assert g.reset_time == want.reset_time, ctx
        frozen_clock.advance(rng.choice([0, 100, 2_000]))


def test_global_psum_vs_broadcast_reconvergence(frozen_clock):
    """The one-psum sync collective reconverges EXACTLY like the
    broadcast-plane reference form (the all_to_all + sort/segment step
    that models the RPC sendHits/UpdatePeerGlobals loops): the same
    GLOBAL traffic with interleaved syncs through two engines — psum vs
    a2a — must produce identical responses at every step, identical
    synced-key counts, and identical post-reconvergence auth rows and
    zero-hit reads for every key."""
    from gubernator_tpu.parallel.global_sync import GlobalEngine
    from gubernator_tpu.parallel.sharded import MeshBackend

    rng = random.Random(5)
    e_psum = GlobalEngine(
        MeshBackend(MESH_DEV, clock=frozen_clock), collective="psum"
    )
    e_a2a = GlobalEngine(
        MeshBackend(MESH_DEV, clock=frozen_clock), collective="a2a"
    )
    keys = [f"g{i}" for i in range(24)]
    for step in range(8):
        batch = [
            RateLimitReq(
                name="gx", unique_key=rng.choice(keys),
                hits=rng.choice([1, 1, 2, 3]), limit=50,
                duration=60_000, behavior=Behavior.GLOBAL,
                algorithm=rng.choice(
                    [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]
                ),
            )
            for _ in range(rng.randrange(4, 20))
        ]
        r1, r2 = e_psum.check(batch), e_a2a.check(batch)
        assert [(r.status, r.remaining, r.reset_time) for r in r1] == \
               [(r.status, r.remaining, r.reset_time) for r in r2], step
        if rng.random() < 0.6:
            assert e_psum.sync() == e_a2a.sync()
        frozen_clock.advance(rng.choice([0, 100, 1_000]))
    assert e_psum.sync() == e_a2a.sync()
    probes = [
        RateLimitReq(name="gx", unique_key=k, hits=0, limit=50,
                     duration=60_000, behavior=Behavior.GLOBAL)
        for k in keys
    ]
    p1, p2 = e_psum.check(probes), e_a2a.check(probes)
    assert [(r.status, r.remaining, r.reset_time) for r in p1] == \
           [(r.status, r.remaining, r.reset_time) for r in p2]
    for k in keys:
        i1 = e_psum.b.get_cache_item(f"gx_{k}")
        i2 = e_a2a.b.get_cache_item(f"gx_{k}")
        assert (i1 is None) == (i2 is None), k
        if i1 is not None:
            assert (i1.remaining, int(i1.status), i1.expire_at,
                    i1.limit) == \
                   (i2.remaining, int(i2.status), i2.expire_at,
                    i2.limit), k


def test_go_trunc_differential():
    """The `_go_trunc` contract (ops/step.py:102-113): the device
    kernel's float64->int64 truncation and the oracle's `_trunc`
    (core/pymodel.py) must agree bit-for-bit across the edge matrix —
    negatives (toward zero, NOT floor), exact +/-2^62, the largest
    float64 below 2^63, out-of-range saturation, infinities, and NaN.
    A divergence here silently skews leaky-bucket remaining/rate."""
    import math

    import jax.numpy as jnp
    import numpy as np

    from gubernator_tpu.core.pymodel import _trunc
    from gubernator_tpu.ops.step import _trunc_i64

    f64_below_2_63 = math.nextafter(2.0**63, 0.0)  # 9223372036854774784
    vals = [
        0.0, -0.0, 0.5, -0.5, 1.9, -1.5, -2.7, 2.999,
        2.0**62, -(2.0**62), 2.0**62 + 4096.0, -(2.0**62) - 4096.0,
        f64_below_2_63, -f64_below_2_63,
        2.0**63, -(2.0**63), 9.3e18, -9.3e18, 1e308, -1e308,
        float("inf"), float("-inf"), float("nan"),
        math.nextafter(1.0, 0.0), math.nextafter(-1.0, 0.0),
    ]
    kernel = np.asarray(_trunc_i64(jnp.asarray(vals, dtype=jnp.float64)))
    for v, got in zip(vals, kernel):
        want = _trunc(v)
        assert int(got) == want, (
            f"_go_trunc diverged at {v!r}: kernel {int(got)}, "
            f"oracle {want}"
        )


def test_pipeline_depth_differential(frozen_clock):
    """Pipelined drain is semantics-preserving: the same concurrent
    traffic through a depth-1 and a depth-3 compiled fast lane produces
    bit-identical responses and final table rows.  Workers own disjoint
    key spaces, so each key's history is deterministic no matter how the
    coalescer composes merges — any response difference is a real
    stale-table/ordering bug, not schedule noise."""
    import asyncio

    from gubernator_tpu import native
    from gubernator_tpu.core.config import Config
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    if not native.available():
        pytest.skip("native library unavailable")

    dev = DeviceConfig(num_slots=4096, ways=8, batch_size=64)
    n_workers, per_worker = 4, 12
    rng = random.Random(11)

    def worker_payloads(w: int):
        payloads = []
        for _ in range(per_worker):
            reqs = []
            for _ in range(rng.randrange(1, 12)):
                behavior = 0
                duration = rng.choice([60_000, 60_000, 1_000])
                if rng.random() < 0.10:
                    behavior |= int(Behavior.RESET_REMAINING)
                if rng.random() < 0.08:
                    behavior |= int(Behavior.DURATION_IS_GREGORIAN)
                    duration = rng.choice([1, 4])
                reqs.append(pb.RateLimitReq(
                    name=f"pd{w}",
                    unique_key=f"k{rng.randrange(6)}",
                    hits=rng.choice([0, 1, 1, 2, 3, -1]),
                    limit=rng.choice([20, 30]),
                    duration=duration,
                    algorithm=rng.choice([0, 1]),
                    behavior=behavior,
                    burst=rng.choice([0, 0, 25]),
                ))
            payloads.append(
                pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            )
        return payloads

    schedules = [worker_payloads(w) for w in range(n_workers)]

    def run_at_depth(depth: int):
        async def scenario():
            svc = Service(Config(device=dev), clock=frozen_clock)
            await svc.start()
            fp = FastPath(svc, pipeline_depth=depth)
            results: dict = {}

            async def worker(w: int):
                await asyncio.sleep(w * 0.003)
                got = []
                for payload in schedules[w]:
                    raw = await fp.check_raw(payload, peer_rpc=False)
                    assert raw is not None
                    got.append([
                        (r.status, r.limit, r.remaining, r.reset_time,
                         r.error)
                        for r in pb.GetRateLimitsResp.FromString(
                            raw
                        ).responses
                    ])
                results[w] = got

            await asyncio.gather(*(worker(w) for w in range(n_workers)))
            drains = fp._mach.drains
            rows = {}
            for w in range(n_workers):
                for k in range(6):
                    key = f"pd{w}_k{k}"
                    item = svc.backend.get_cache_item(key)
                    rows[key] = (
                        (item.remaining, item.expire_at, int(item.status),
                         item.limit, item.duration)
                        if item is not None else None
                    )
            await fp.close()
            await svc.close()
            return results, rows, drains

        return asyncio.run(scenario())

    base_results, base_rows, _ = run_at_depth(1)
    deep_results, deep_rows, deep_drains = run_at_depth(3)
    assert deep_results == base_results
    assert deep_rows == base_rows
    assert deep_drains >= 2  # traffic really coalesced into many merges


def test_pipeline_depth_differential_store_and_global(frozen_clock):
    """The depth differential above with a Store attached and GLOBAL
    keys in the mix (every merge takes the locked store arm): the same
    traffic through a depth-1 and a depth-2 compiled fast lane produces
    identical responses and final table rows."""
    import asyncio

    from gubernator_tpu import native
    from gubernator_tpu.core.config import Config
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service
    from gubernator_tpu.runtime.store import MockStore

    if not native.available():
        pytest.skip("native library unavailable")

    dev = DeviceConfig(num_slots=4096, ways=8, batch_size=64)
    n_workers, per_worker = 4, 10
    rng = random.Random(23)

    def worker_payloads(w: int):
        # GLOBAL keys (k6..k9) keep PER-KEY-constant params and plain
        # behavior: the GLOBAL manager's flush may re-read a key at a
        # composition-dependent moment (cap_ok differs when merges
        # compose differently), and a re-read with CHANGED params (or
        # RESET_REMAINING) mutates the row — that schedule noise would
        # make even two depth-1 runs diverge.  With constant params and
        # a frozen clock the re-read is a no-op, so any difference left
        # is a real pipelining bug.  Exact-tier keys (k0..k5) keep the full
        # op mix including param churn, resets, and Gregorian.
        payloads = []
        for _ in range(per_worker):
            reqs = []
            for _ in range(rng.randrange(1, 14)):
                if rng.random() < 0.30:
                    k = 6 + rng.randrange(4)
                    reqs.append(pb.RateLimitReq(
                        name=f"rg{w}",
                        unique_key=f"k{k}",
                        hits=rng.choice([0, 1, 1, 2]),
                        limit=20 + 10 * (k % 2),
                        duration=60_000,
                        algorithm=k % 2,
                        behavior=int(Behavior.GLOBAL),
                        burst=25 if k % 3 == 0 else 0,
                    ))
                    continue
                behavior = 0
                duration = rng.choice([60_000, 60_000, 1_000])
                if rng.random() < 0.10:
                    behavior |= int(Behavior.RESET_REMAINING)
                if rng.random() < 0.08:
                    behavior |= int(Behavior.DURATION_IS_GREGORIAN)
                    duration = rng.choice([1, 4])
                reqs.append(pb.RateLimitReq(
                    name=f"rg{w}",
                    unique_key=f"k{rng.randrange(6)}",
                    hits=rng.choice([0, 1, 1, 2, 3, -1]),
                    limit=rng.choice([20, 30]),
                    duration=duration,
                    algorithm=rng.choice([0, 1]),
                    behavior=behavior,
                    burst=rng.choice([0, 0, 25]),
                ))
            payloads.append(
                pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            )
        return payloads

    schedules = [worker_payloads(w) for w in range(n_workers)]

    def run_depth(depth: int):
        async def scenario():
            store = MockStore()
            svc = Service(
                Config(device=dev, store=store), clock=frozen_clock
            )
            await svc.start()
            fp = FastPath(svc, pipeline_depth=depth)
            results: dict = {}

            async def worker(w: int):
                await asyncio.sleep(w * 0.003)
                got = []
                for payload in schedules[w]:
                    raw = await fp.check_raw(payload, peer_rpc=False)
                    assert raw is not None
                    got.append([
                        (r.status, r.limit, r.remaining, r.reset_time,
                         r.error)
                        for r in pb.GetRateLimitsResp.FromString(
                            raw
                        ).responses
                    ])
                results[w] = got

            await asyncio.gather(*(worker(w) for w in range(n_workers)))
            rows = {}
            for w in range(n_workers):
                for k in range(10):
                    key = f"rg{w}_k{k}"
                    item = svc.backend.get_cache_item(key)
                    rows[key] = (
                        (item.remaining, item.expire_at, int(item.status),
                         item.limit, item.duration)
                        if item is not None else None
                    )
            dv = fp.debug_vars()
            await fp.close()
            await svc.close()
            return results, rows, dv

        return asyncio.run(scenario())

    base_results, base_rows, base_dv = run_depth(1)
    deep_results, deep_rows, deep_dv = run_depth(2)
    assert deep_results == base_results
    assert deep_rows == base_rows
    assert base_dv["pipeline_depth"] == 1 and deep_dv["pipeline_depth"] == 2
    # Both runs served on the lane, with their fetches on the request
    # path's fetch stage.
    for dv in (base_dv, deep_dv):
        assert dv["fallbacks"] == 0
        assert dv["blocking_fetches"]["mach"] > 0
