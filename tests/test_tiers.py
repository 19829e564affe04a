"""Pin the compiled batch-shape tier contract (runtime/backend.py).

A drain's round rides the smallest compiled shape that holds its active
lanes — the device transfer scales with traffic, not with the configured
max batch — and a full round must NEVER be truncated (batch_size is
always a tier).  These are the invariants the small-shape latency path
rests on.
"""
import asyncio

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.runtime.backend import DeviceBackend, resolve_tiers, tier_of

RUNGS = (128, 1024, 4096)


@pytest.mark.parametrize("batch_size,ladder", [
    (128, (128,)),
    (1024, (128, 1024)),
    (2048, (128, 1024, 2048)),
    (4096, RUNGS),
])
def test_the_default_ladder_is_a_function_of_batch_size(batch_size, ladder):
    """128, 1,024 where batch_size is wider, batch_size: an overflow of a
    few hundred lanes and an owner's thousand ride a launch of their own
    size."""
    cfg = DeviceConfig(num_slots=1 << 10, batch_size=batch_size)
    assert resolve_tiers(cfg) == ladder


def test_resolve_tiers_always_includes_batch_size():
    # `batch_tiers`, where a deployment sets it, wins over the ladder.
    cfg = DeviceConfig(
        num_slots=1 << 10, batch_size=4096, batch_tiers=(256, 1024)
    )
    assert resolve_tiers(cfg) == (256, 1024, 4096)


def test_resolve_tiers_clamps_and_dedupes():
    # A tier above batch_size clamps to it; duplicates collapse; order
    # is ascending regardless of the configured order.
    cfg = DeviceConfig(
        num_slots=1 << 10, batch_size=2048,
        batch_tiers=(8192, 512, 512, 2048),
    )
    assert resolve_tiers(cfg) == (512, 2048)


def test_tier_of_picks_smallest_holding_tier():
    tiers = (128, 1024, 4096)
    act = np.zeros(4096, dtype=bool)
    act[:5] = True
    assert tier_of(act, tiers) == 128
    act[:128] = True
    assert tier_of(act, tiers) == 128  # boundary: occ == tier fits
    act[:129] = True
    assert tier_of(act, tiers) == 1024
    act[:] = True
    assert tier_of(act, tiers) == 4096


def test_tier_of_sharded_uses_max_per_shard():
    # [n_shards, B]: lanes fill contiguously from 0 per shard, so the
    # busiest shard's count picks the tier for the whole round.
    tiers = (128, 4096)
    act = np.zeros((4, 4096), dtype=bool)
    act[0, :3] = True
    act[2, :200] = True
    assert tier_of(act, tiers) == 4096  # busiest shard (200) > 128
    act[2, :] = False
    act[2, :100] = True
    assert tier_of(act, tiers) == 128  # busiest shard now fits


def test_small_round_rides_small_tier_with_exact_responses():
    """End-to-end through DeviceBackend.check: a 3-request batch on a
    4096-lane config must produce exact token-bucket decrements (the
    small tier serves it — and the response unmarshal must address the
    sliced shape correctly)."""
    from gubernator_tpu.core.types import RateLimitReq

    be = DeviceBackend(
        DeviceConfig(num_slots=1 << 12, ways=4, batch_size=4096)
    )
    reqs = [
        RateLimitReq(name="t", unique_key=f"k{i}", hits=1, limit=10,
                     duration=60_000)
        for i in range(3)
    ]
    for expect_remaining in (9, 8, 7):
        for r in be.check(reqs):
            assert r.error == ""
            assert r.remaining == expect_remaining


# -- one round through every rung that holds it --------------------------
#
# A rung is the same step program at another static width, so the lanes a
# round carries must come back bit for bit, and leave the table's rows bit
# for bit, whichever rung it rode.  `batch_tiers=(t,)` makes t the one
# rung under batch_size, so a round of at most t lanes rides exactly it.

def _requests(n, behavior=0):
    from gubernator_tpu.core.types import RateLimitReq

    return [
        RateLimitReq(name="rung", unique_key=f"k{i}", hits=i % 4,
                     limit=5 + i % 7, duration=60_000 + 1000 * (i % 5),
                     algorithm=i % 2, burst=(i % 3) * 4,
                     behavior=behavior)
        for i in range(n)
    ]


def _one_chip(t, clock):
    from gubernator_tpu.ops.batch import pack_requests

    be = DeviceBackend(
        DeviceConfig(num_slots=1 << 14, ways=8, batch_size=4096,
                     batch_tiers=(t,)),
        clock=clock,
    )

    def run(reqs):
        rounds = pack_requests(reqs, 4096, clock).rounds
        return be.step_rounds(rounds, add_tally=False)

    return run, lambda: be.table, 1


def _mesh(t, clock):
    from gubernator_tpu.parallel.sharded import (
        MeshBackend,
        pack_requests_sharded,
    )

    be = MeshBackend(
        DeviceConfig(num_slots=1 << 14, ways=8, batch_size=4096,
                     num_shards=4, batch_tiers=(t,)),
        clock=clock,
    )

    def run(reqs):
        rounds = pack_requests_sharded(reqs, 4096, 4, clock).rounds
        return be.step_rounds(rounds, add_tally=False)

    return run, lambda: be.table, 4


def _engine_lane(t, clock):
    from gubernator_tpu.core.hashing import key_hash64
    from gubernator_tpu.ops.batch import pack_requests_grid
    from gubernator_tpu.parallel.global_sync import GlobalEngine, arrival_dev
    from gubernator_tpu.parallel.sharded import (
        MeshBackend,
        packed_grid_rounds_to_host,
    )

    be = MeshBackend(
        DeviceConfig(num_slots=1 << 14, ways=8, batch_size=4096,
                     num_shards=4, batch_tiers=(t,)),
        clock=clock,
    )
    eng = GlobalEngine(be)

    def run(reqs):
        rounds = pack_requests_grid(
            reqs, 4096, 4, lambda k: arrival_dev(key_hash64(k), 4), clock
        ).rounds
        for db in rounds:
            np.copyto(db.use_cached, db.active)
        resps, _sync = eng.serve_packed(rounds, [])
        return packed_grid_rounds_to_host(resps)

    return run, lambda: eng.cache_table, 4


def _dispatch_counters():
    from gubernator_tpu.runtime import tracing

    cell = tracing.PROCESS_LEDGER.cell("direct", "backend.dispatch")
    return dict(cell.counters)


@pytest.mark.parametrize("surface,lanes", [
    (_one_chip, 100), (_one_chip, 300), (_one_chip, 1000),
    (_mesh, 400), (_mesh, 3200),
    (_engine_lane, 400), (_engine_lane, 3200),
], ids=lambda v: getattr(v, "__name__", str(v)).strip("_"))
def test_a_round_is_the_same_on_every_rung_that_holds_it(
        frozen_clock, surface, lanes):
    """Responses and table rows, bit for bit, on one chip, on the mesh's
    virtual devices and on the engine lane's replicated cache table;
    and the dispatch row counts each launch at the width it rode."""
    from gubernator_tpu.core.types import Behavior
    from gubernator_tpu.ops.state import table_to_host

    behavior = int(Behavior.GLOBAL) if surface is _engine_lane else 0
    reqs = _requests(lanes, behavior)
    seen = {}
    for t in RUNGS:
        run, table, shards = surface(t, frozen_clock)
        before = _dispatch_counters()
        # Created, then spent from: both halves of the step's write-back.
        hosts = [run(reqs), run(reqs)]
        after = _dispatch_counters()
        widths = [h["status"].shape[-1] for hs in hosts for h in hs]
        grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
        assert grew["launches"] == len(widths) == 2
        assert grew["lanes"] == shards * sum(widths)
        assert grew[f"tier_{widths[0]}"] == 2
        seen[widths[0]] = (hosts, table_to_host(table()))
    # Every rung that holds the round carried it; the narrower ones sent
    # it to the full width.
    assert len(seen) >= 2 and 4096 in seen
    ref_hosts, ref_table = seen.pop(4096)
    for t, (hosts, rows) in seen.items():
        for hs, ref in zip(hosts, ref_hosts):
            for h, r in zip(hs, ref):
                for f in r:
                    assert np.array_equal(h[f], r[f][..., :t]), (t, f)
                    assert not r[f][..., t:].any(), (t, f)
        for f, col in ref_table.items():
            assert rows[f].tobytes() == col.tobytes(), (t, f)


def test_the_engine_s_object_path_launches_where_every_launch_is_counted(
        frozen_clock):
    """`GlobalEngine.check` (the object path: no lane packed its rounds)
    passes `backend.launch_rounds` like the lanes: the `direct` row counts
    its launch at the rung it rode, and the answers are the 4096-lane
    program's."""
    from gubernator_tpu.core.types import Behavior
    from gubernator_tpu.parallel.global_sync import GlobalEngine
    from gubernator_tpu.parallel.sharded import MeshBackend

    reqs = _requests(400, int(Behavior.GLOBAL))
    answers = {}
    for t in (1024, 4096):
        eng = GlobalEngine(MeshBackend(
            DeviceConfig(num_slots=1 << 14, ways=8, batch_size=4096,
                         num_shards=4, batch_tiers=(t,)),
            clock=frozen_clock,
        ))
        before = _dispatch_counters()
        answers[t] = eng.check(reqs)
        after = _dispatch_counters()
        grew = {k: after[k] - before.get(k, 0) for k in after}
        assert grew["launches"] == grew[f"tier_{t}"] == 1, grew
        assert grew["lanes"] == 4 * t, grew
    assert answers[1024] == answers[4096]
    assert all(r.error == "" for r in answers[4096])


# -- the compiled lane: a cascade's write-back round, and one drain whose
# rounds have three widths ------------------------------------------------

@pytest.fixture(scope="module")
def lanes():
    """Two services at the benchmark's batch_size on one frozen clock:
    the default ladder, and the two widths there were before it."""
    from gubernator_tpu.core import clock as clock_mod
    from gubernator_tpu.core.config import Config
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    loop = asyncio.new_event_loop()
    clk = clock_mod.Clock()
    clk.freeze(1_790_000_000_000 * 1_000_000)
    made = []
    for tiers in (None, (128,)):
        dev = DeviceConfig(num_slots=1 << 17, ways=8, batch_size=4096,
                           batch_tiers=tiers)
        svc = Service(Config(device=dev), clock=clk)
        loop.run_until_complete(svc.start())
        made.append((svc, FastPath(svc)))
    yield loop, clk, made
    for svc, fp in made:
        loop.run_until_complete(fp.close())
        loop.run_until_complete(svc.close())
    loop.close()


def _one_drain(loop, fp, rpcs):
    """`rpcs` (lists of pb requests) through the compiled lane as ONE
    drain: their answers, and what the machinery lane's rows grew by."""
    from gubernator_tpu.proto import gubernator_pb2 as pb

    def rows():
        return fp._stages.debug_vars()["mach"]

    async def ask(reqs):
        out = await fp.check_raw(
            pb.GetRateLimitsReq(requests=reqs).SerializeToString(),
            peer_rpc=False)
        return pb.GetRateLimitsResp.FromString(out).responses

    async def scenario():
        mach = fp._mach
        r0 = rows()
        # Hold the dispatch slot until every RPC is queued.
        await mach._dispatch_sem.acquire()
        try:
            tasks = [asyncio.ensure_future(ask(r)) for r in rpcs]
            while len(mach._waits) < len(rpcs) or not mach._queue.empty():
                await asyncio.sleep(0.001)
        finally:
            mach._dispatch_sem.release()
        answers = await asyncio.gather(*tasks)
        r1 = rows()
        grew = {
            (st, k): v - r0[st].get(k, 0)
            for st, row in r1.items() for k, v in row.items()
            if k not in ("ms_total", "ms_max", "max_at_ms")
        }
        return answers, grew

    return loop.run_until_complete(scenario())


def _three_widths():
    """5,000 checks of 4,998 keys, one of them three times under three
    limits (a group of mixed parameters keeps a device round an
    occurrence): 4,096 lanes, 903 and 1."""
    from gubernator_tpu.proto import gubernator_pb2 as pb

    def req(key, hits, limit=10, algo=0):
        return pb.RateLimitReq(name="w3", unique_key=key, hits=hits,
                               limit=limit, duration=3_600_000,
                               algorithm=algo)

    rpcs = [[req(f"{i}.{j}", j % 3, algo=j % 2) for j in range(1000)]
            for i in range(5)]
    rpcs[0][3] = req("thrice", 1, limit=10)
    rpcs[2][500] = req("thrice", 1, limit=11)
    rpcs[4][999] = req("thrice", 2, limit=12)
    return rpcs, {"launches": 3, "lanes": 4096 + 1024 + 128,
                  "tier_4096": 1, "tier_1024": 1, "tier_128": 1}, (
        "cascade", "count", 0)


def _write_back_round():
    """300 keys three times each: one read lane a key and one write-back
    lane a key, 300 lanes each on the 1024 rung, where the plain
    assignment would launch three."""
    from gubernator_tpu.proto import gubernator_pb2 as pb

    rpcs = [[pb.RateLimitReq(name="wb", unique_key=f"k{j}", hits=1 + i,
                             limit=20, duration=3_600_000, algorithm=j % 2)
             for j in range(300)] for i in range(3)]
    return rpcs, {"launches": 2, "lanes": 2048, "tier_4096": 0,
                  "tier_1024": 2, "tier_128": 0}, (
        "cascade", "wb_lanes", 300)


@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
@pytest.mark.parametrize("drain", [_three_widths, _write_back_round],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_drain_of_mixed_widths_answers_as_the_reference_does(lanes, drain):
    """Through `fetch`: the answers are core/pymodel.py's, the ladder's
    service and the two-width one give the same bytes and leave the same
    rows, and the dispatch row counts each launch at its rung."""
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.net.grpc_api import reqs_from_pb
    from gubernator_tpu.ops.state import table_to_host

    loop, clk, made = lanes
    rpcs, launched, (stage, counter, want) = drain()
    oracle = PyRateLimiter(clock=clk)
    (_svc, ladder), (_svc2, two) = made
    answers, grew = _one_drain(loop, ladder, rpcs)
    for i, (got, reqs) in enumerate(zip(answers, rpcs)):
        for j, (g, r) in enumerate(zip(got, reqs_from_pb(reqs))):
            w = oracle.get_rate_limit(r)
            assert (g.error, g.status, g.limit, g.remaining,
                    g.reset_time) == (
                "", int(w.status), w.limit, w.remaining, w.reset_time
            ), (i, j)
    assert {k: grew[("dispatch", k)] for k in launched} == launched
    assert grew[(stage, counter)] == want
    assert ladder.fallbacks == 0
    answers2, grew2 = _one_drain(loop, two, rpcs)
    assert grew2[("dispatch", "launches")] == launched["launches"]
    assert grew2[("dispatch", "lanes")] > launched["lanes"]
    assert [[a.SerializeToString() for a in rpc] for rpc in answers] == [
        [a.SerializeToString() for a in rpc] for rpc in answers2
    ]
    tables = [table_to_host(svc.backend.table) for svc, _fp in made]
    for f, col in tables[0].items():
        assert col.tobytes() == tables[1][f].tobytes(), f
