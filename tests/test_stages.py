"""The stage ledger (runtime/tracing.py): one primitive, three sinks.

Pinned here:

  * the ledger's arithmetic — count / total / max per (lane, stage),
    per-entry against per-drain weighting, the empty/occupied state
    clock including its open interval;
  * the two identities on an in-process daemon serving real RPCs: per
    RPC, handler = ingress + queue_wait + in_drain + wake + egress; per
    drain, dequeue -> results set = the pipeline's stages (residuals
    generous: this is a CPU under test load);
  * the series and /debug/vars numbers that used to be measured a second
    time are views of the ledger's rows;
  * the span plane: disarmed, a stage allocates no Span and no context;
    armed, `fastpath.merge` parents the stage spans;
  * the profiler: a short CPU trace holds `gub.*` events for a wait held
    across an await and for a stage on a pool thread, and the clock
    anchor's argument can be read back;
  * why a stage took that long (PR 41): the rows of lane `host` (the
    collector, the census, the hot-key sketch, the scrapes, the loop's
    lag), the `threads` / `process` blocks (every thread's CPU clock,
    read from outside at render: a pool's CPU beside its sections' wall),
    and the stalls ring with its rule.
"""
from __future__ import annotations

import asyncio
import gc
import glob
import json
import os
import threading
import time
import types
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.runtime import tracing
from gubernator_tpu.runtime.fastpath import _Coalescer
from gubernator_tpu.runtime.metrics import Metrics
from gubernator_tpu.testing.tracing import memory_tracing


EPOCH_NS = 1_700_000_000_000_000_000


class _Clock:
    """perf_counter_ns and the epoch clock under the test's control:
    `now` moves the wall, and the epoch with it."""

    def __init__(self) -> None:
        self.now = 1_000

    def perf_counter_ns(self) -> int:
        return self.now

    def time_ns(self) -> int:
        return EPOCH_NS + self.now


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter_ns=c.perf_counter_ns, time_ns=c.time_ns,
    ))
    return c


class _E:
    __slots__ = ("fut", "trace_ctx")

    def __init__(self) -> None:
        self.fut = None
        self.trace_ctx = None


# -- arithmetic -------------------------------------------------------------

def test_count_total_max(clock):
    ledger = tracing.StageLedger()
    for dt in (5, 40, 12):
        with ledger.stage("lane.pack", "mach"):
            clock.now += dt
    assert ledger.totals("mach", "lane.pack") == (3, 57, 40)
    assert ledger.totals("engine", "lane.pack") == (0, 0, 0)
    row = ledger.debug_vars()["mach"]["pack"]
    # The longest instance ended at wall 1,045: `max_at_ms` is that
    # moment on the epoch clock.
    assert row == {"count": 3, "ms_total": 57e-6, "ms_max": 40e-6,
                   "max_at_ms": (EPOCH_NS + 1_045) // 1_000_000}


def test_begin_end_pair_is_idempotent_and_returns_ns(clock):
    ledger = tracing.StageLedger()
    wait = ledger.begin("lane.handoff", "mach")
    clock.now += 250
    assert wait.end() == 250
    clock.now += 999
    assert wait.end() == 0          # already ended: nothing added
    assert ledger.totals("mach", "lane.handoff") == (1, 250, 250)


def test_unknown_stage_is_refused():
    ledger = tracing.StageLedger()
    with pytest.raises(KeyError):
        ledger.stage("lane.no_such_stage", "mach")
    with pytest.raises(KeyError):
        ledger.observe("lane.no_such_stage", lambda s: None)


def test_ambient_scope_names_the_lane(clock):
    """Code that serves many callers (backend dispatch, fetch_ravel)
    names no lane: the stage lands on whoever bound the scope."""
    ledger = tracing.StageLedger()
    with tracing.scope(ledger, "engine"):
        with tracing.stage("backend.d2h_wait"):
            clock.now += 7
    assert ledger.totals("engine", "backend.d2h_wait") == (1, 7, 7)
    before = tracing.PROCESS_LEDGER.totals("direct", "backend.d2h_wait")[0]
    with tracing.stage("backend.d2h_wait"):
        pass
    after = tracing.PROCESS_LEDGER.totals("direct", "backend.d2h_wait")[0]
    assert after == before + 1


def test_observer_is_fed_by_the_ledger(clock):
    """A Prometheus series stays a view: the observer gets the very
    duration the row got, in seconds, for the lane it was bound to."""
    ledger = tracing.StageLedger()
    seen = []
    ledger.observe("lane.slot_wait", seen.append, "mach")
    for lane in ("mach", "engine"):
        w = ledger.begin("lane.slot_wait", lane)
        clock.now += 2_000_000
        w.end()
    assert seen == [0.002]
    every = []
    ledger.observe("backend.dispatch", every.append)
    for lane in ("mach", "ring"):
        with ledger.stage("backend.dispatch", lane):
            clock.now += 1_000
    assert every == [1e-6, 1e-6]


def test_empty_occupied_state_clock(clock):
    ledger = tracing.StageLedger()
    assert "wire" not in ledger.debug_vars()    # starts with the first RPC
    clock.now = 100
    ledger.rpc_enter()
    clock.now = 150
    ledger.rpc_enter()                           # two inside
    clock.now = 260
    ledger.rpc_exit()
    clock.now = 300
    ledger.rpc_exit()                            # occupied 100..300
    clock.now = 450
    wire = ledger.debug_vars()["wire"]
    at = (EPOCH_NS + 300) // 1_000_000
    assert wire["occupied"] == {
        "count": 1, "ms_total": 200e-6, "ms_max": 200e-6, "max_at_ms": at,
    }
    # The open interval is counted up to "now", and is not yet a count.
    assert wire["empty"] == {
        "count": 0, "ms_total": 150e-6, "ms_max": 150e-6,
        "max_at_ms": (EPOCH_NS + 450) // 1_000_000,
    }
    clock.now = 500
    ledger.rpc_enter()                           # empty 300..500 closes
    clock.now = 530
    wire = ledger.debug_vars()["wire"]
    assert wire["empty"] == {
        "count": 1, "ms_total": 200e-6, "ms_max": 200e-6,
        "max_at_ms": (EPOCH_NS + 500) // 1_000_000,
    }
    assert wire["occupied"]["ms_total"] == 230e-6   # 200 + the open 30
    assert wire["occupied"]["count"] == 1
    ledger.rpc_exit()


def test_per_entry_against_per_drain_weighting():
    """Three entries ride ONE drain: the waits are counted per entry,
    the pipeline's stages per drain."""

    def process(entries):
        with tracing.stage("lane.pack"):
            time.sleep(0.002)

        def fetch():
            with tracing.stage("lane.unpack"):
                return [i for i, _ in enumerate(entries)]

        return fetch

    async def scenario():
        pool = ThreadPoolExecutor(2)
        co = _Coalescer(pool, process, lane="mach")
        outs = await asyncio.gather(*(co.do(_E()) for _ in range(3)))
        await co.close()
        pool.shutdown(wait=True)
        return co, outs

    co, outs = asyncio.run(scenario())
    assert sorted(outs) == [0, 1, 2]
    assert co.drains == 1
    count = lambda lane, st: co._stages.totals(lane, st)[0]  # noqa: E731
    for st in ("lane.queue_wait", "lane.in_drain"):
        assert count("mach", st) == 3, st
    assert count("wire", "wire.wake") == 3
    for st in ("lane.drain", "lane.pack", "lane.unpack",
               "lane.dispatch_stage", "lane.fetch_stage"):
        assert count("mach", st) == 1, st
    # Both stages crossed to the pool and back.
    assert count("mach", "lane.handoff") == 2
    assert count("mach", "lane.resume") == 2
    # Every entry was in the drain at least as long as its pack.
    assert co._stages.totals("mach", "lane.in_drain")[1] >= 3 * 2_000_000
    # The legacy accumulators are the ledger's rows.
    assert co.dispatch_s == co._stages.totals(
        "mach", "lane.dispatch_stage")[1] / 1e9
    assert co.dispatch_s >= 0.002 and co.fetch_s > 0.0
    assert co.debug_vars()["dispatch_ms_total"] == round(
        co.dispatch_s * 1e3, 3)
    assert not co._waits                         # nothing left open


def test_bubble_is_the_slot_wait_row():
    """Depth 1, a slow fetch, two drains: the second waits for the
    fetch slot.  One measurement feeds the row, the bubble counter and
    the flight recorder."""

    class _FR:
        def __init__(self):
            self.bubbles = []

        def record_bubble(self, lane, wait_ms):
            self.bubbles.append((lane, wait_ms))

    metrics = Metrics()
    metrics.flightrec = _FR()

    def process(entries):
        def fetch():
            time.sleep(0.05)
            return [0 for _ in entries]

        return fetch

    async def scenario():
        pool = ThreadPoolExecutor(3)
        co = _Coalescer(pool, process, pipeline_depth=1, metrics=metrics,
                        lane="mach")
        first = asyncio.ensure_future(co.do(_E()))
        await asyncio.sleep(0.01)        # its drain is in flight
        await co.do(_E())
        await first
        await co.close()
        pool.shutdown(wait=True)
        return co

    co = asyncio.run(scenario())
    n, ns, _mx = metrics.stages.totals("mach", "lane.slot_wait")
    assert n == co.waited_drains == 1
    assert ns > 10_000_000
    assert co.bubble_s == ns / 1e9
    (lane, wait_ms), = metrics.flightrec.bubbles
    assert lane == "mach" and wait_ms == pytest.approx(ns / 1e6)
    text = metrics.render().decode()
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("gubernator_fastpath_bubble_seconds_total{")
    )
    assert float(line.split()[-1]) == pytest.approx(ns / 1e9)


# -- the identities, on a daemon --------------------------------------------

def _payload(i: int, n: int = 6) -> bytes:
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(
            name="stages", unique_key=f"k{(i * n + j) % 97}", hits=1,
            limit=1_000_000, duration=60_000,
        )
        for j in range(n)
    ]).SerializeToString()


def _ms(stages: dict, lane: str, *names: str) -> float:
    return sum(stages[lane][n]["ms_total"] for n in names
               if n in stages.get(lane, {}))


# The stages a drain divides into (identity 2, docs/tracing.md).
_DRAIN_PARTS = ("slot_wait", "dispatch_wait", "handoff", "pack",
                "lock_wait", "dispatch", "cascade", "d2h_wait", "unpack",
                "resume")


def _drive(cluster, d, payload, n_rpcs: int, n_checks: int) -> None:
    """`n_rpcs` raw GetRateLimits of `payload(i)`, 8 in flight, over real
    gRPC; every answer whole and free of errors."""
    import grpc.aio

    async def drive():
        ch = grpc.aio.insecure_channel(d.grpc_address)
        rpc = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
        sem = asyncio.Semaphore(8)

        async def one(i):
            async with sem:
                resp = pb.GetRateLimitsResp.FromString(await rpc(payload(i)))
                assert [r.error for r in resp.responses] == [""] * n_checks

        try:
            await asyncio.gather(*(one(i) for i in range(n_rpcs)))
        finally:
            await ch.close()

    cluster.run(drive(), timeout=120)


def test_identities_and_views_on_a_daemon():
    """300 RPCs, 8 in flight, over real gRPC through the raw handler and
    check_raw: both identities close, and the numbers that used to be
    measured separately equal the ledger's rows."""
    from gubernator_tpu.testing.cluster import Cluster

    assert not tracing.enabled()
    cluster = Cluster.start(1)
    try:
        d = cluster.daemon_at(0)
        _drive(cluster, d, _payload, 300, 6)
        stages = d.metrics.stages.debug_vars()
        lanes = d.fastpath.debug_vars()["lanes"]
        metrics_text = d.metrics.render().decode()
        assert d.fastpath.fallbacks == 0
    finally:
        cluster.stop()

    wire, mach = stages["wire"], stages["mach"]
    assert wire["handler"]["count"] == 300
    assert wire["ingress"]["count"] == 300
    assert wire["egress"]["count"] == 300
    assert wire["rpc"]["count"] >= 300
    # One entry per RPC here: the waits are per entry.
    for row in (mach["queue_wait"], mach["in_drain"], wire["wake"]):
        assert row["count"] == 300
    drains = lanes["mach"]["drains"]
    assert mach["drain"]["count"] == drains > 0

    # Identity 1, per RPC.  The parts are disjoint and inside the
    # handler, so they can only fall short of it: by the hops between
    # them, which a loaded CPU stretches.
    handler = wire["handler"]["ms_total"]
    parts = (_ms(stages, "wire", "ingress", "wake", "egress")
             + _ms(stages, "mach", "queue_wait", "in_drain"))
    assert 0.80 * handler <= parts <= 1.001 * handler, (parts, handler)

    # Identity 2, per drain.
    drain = mach["drain"]["ms_total"]
    parts = _ms(stages, "mach", *_DRAIN_PARTS)
    assert 0.75 * drain <= parts <= 1.001 * drain, (parts, drain)
    assert mach["handoff"]["count"] == mach["resume"]["count"] == 2 * drains
    assert mach["pack"]["count"] == mach["unpack"]["count"] == drains

    # The state clock: the window divides into empty and occupied, and
    # the handlers overlapped (8 in flight), so occupied < sum(handler).
    occupied = wire["occupied"]["ms_total"]
    assert 0 < occupied <= handler
    assert wire["occupied"]["count"] >= 1

    # Views fed by the ledger, not second measurements.
    assert lanes["mach"]["dispatch_ms_total"] == round(
        mach["dispatch_stage"]["ms_total"], 3)
    assert lanes["mach"]["fetch_ms_total"] == round(
        mach["fetch_stage"]["ms_total"], 3)
    assert lanes["mach"]["bubble_ms_total"] == round(
        mach.get("slot_wait", {"ms_total": 0.0})["ms_total"], 3)

    def series(name, needle=""):
        return sum(
            float(ln.split()[-1]) for ln in metrics_text.splitlines()
            if ln.startswith(name) and needle in ln
        )

    dispatches = sum(
        lane["dispatch"]["count"] for lane in stages.values()
        if "dispatch" in lane
    )
    assert series("gubernator_tpu_device_step_duration_count") == dispatches
    assert series(
        "gubernator_grpc_request_duration_count", "V1/GetRateLimits"
    ) == 300
    assert series(
        "gubernator_grpc_request_duration_sum", "V1/GetRateLimits"
    ) <= wire["rpc"]["ms_total"] / 1e3 + 1e-9
    assert series(
        "gubernator_fastpath_stage_duration_count", 'stage="dispatch"'
    ) == drains
    # Compiles are visible to an operator (the daemon compiled to start).
    assert stages["xla"]["compile"]["count"] > 0
    assert stages["xla"]["compile"]["ms_total"] > 0
    # Disarmed: all of the above allocated no span.
    assert tracing.debug_vars() == {"enabled": False}


def test_the_cascade_row_counts_its_groups_and_peeks():
    """120 RPCs, 8 in flight, nine checks each on three hot keys, every
    other check a peek: every drain holds duplicate groups of three or
    more with peeks in them — a round an occurrence would be three
    launches, so the host cascade replays them (a PAIR would ride the
    rounds: fastpath._cascade_or_rounds).  The lane.cascade row says
    so — groups replayed, their occurrences, the peeks among those —
    and the per-drain identity closes on such drains as on any other."""
    from gubernator_tpu.testing.cluster import Cluster

    def payload(i: int) -> bytes:
        return pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(
                name="stages_peek", unique_key=f"hot{j % 3}",
                hits=(i + j) % 2, limit=1_000_000, duration=60_000,
                algorithm=j % 3 % 2,
            )
            for j in range(9)
        ]).SerializeToString()

    cluster = Cluster.start(1)
    try:
        d = cluster.daemon_at(0)
        _drive(cluster, d, payload, 120, 9)
        stages = d.metrics.stages.debug_vars()
        drains = d.fastpath.debug_vars()["lanes"]["mach"]["drains"]
        assert d.fastpath.fallbacks == 0
    finally:
        cluster.stop()

    mach = stages["mach"]
    row = mach["cascade"]
    # Every RPC holds each of its three keys three times, peek and spend
    # in turn (five peeks where i is even, four where odd): every drain is
    # a cascade merge of three groups, and none went plain.
    assert row["count"] == drains == mach["drain"]["count"] > 0
    assert row["groups"] == 3 * drains
    assert row["occ"] == 120 * 9
    assert row["peeks"] == 60 * 5 + 60 * 4
    assert (mach["pack"]["dup_plain"], mach["pack"]["dup_lanes"]) == (0, 0)
    assert mach["pack"]["count"] == mach["unpack"]["count"] == drains
    drain = mach["drain"]["ms_total"]
    parts = _ms(stages, "mach", *_DRAIN_PARTS)
    assert 0.75 * drain <= parts <= 1.001 * drain, (parts, drain)
    assert row["ms_total"] > 0


def test_counters_ride_on_a_stage(clock):
    """`tally` adds named whole numbers to the stage's row, rendered beside
    count / ms_total / ms_max; a row's own keys are refused."""
    ledger = tracing.StageLedger()
    for keys in (3, 5):
        with ledger.stage("global.sync_tick", "global") as tick:
            clock.now += 1_000_000
            tick.tally(keys=keys, chunks=1)
    row = ledger.debug_vars()["global"]["sync_tick"]
    assert row == {"count": 2, "ms_total": 2.0, "ms_max": 1.0,
                   "max_at_ms": (EPOCH_NS + 1_001_000) // 1_000_000,
                   "keys": 8, "chunks": 2}
    with pytest.raises(KeyError):
        tick = ledger.stage("global.sync_tick", "global")
        try:
            tick.tally(count=1)
        finally:
            tick.end()
    tick.tally(keys=1)          # ended: a no-op
    assert ledger.debug_vars()["global"]["sync_tick"]["keys"] == 8


def test_the_sync_tick_divides_and_its_counters_add_up():
    """GLOBAL checks over real gRPC into a four-shard mesh daemon: the
    tick is its chunk building, its wait for the locks and its steps; the
    tick's counters add up to the keys the engine flushed, the engine
    lane's to the checks the RPCs sent, and the owners' rows to their
    hits."""
    import grpc.aio

    from gubernator_tpu.core.config import DeviceConfig
    from gubernator_tpu.testing.cluster import Cluster

    rpcs, per_rpc, keys = 40, 50, 700
    cluster = Cluster.start(1, device=DeviceConfig(
        num_slots=1 << 14, ways=8, batch_size=128, num_shards=4,
    ))
    try:
        d = cluster.daemon_at(0)
        eng = d.service.global_engine
        eng.delta_slots = 32       # 128 keys a chunk: ticks of many chunks

        def payload(i):
            return pb.GetRateLimitsReq(requests=[
                pb.RateLimitReq(
                    name="tick", unique_key=f"t{(i * 37 + j * j) % keys}",
                    hits=1 + j % 3, limit=1_000_000_000, duration=600_000,
                    behavior=pb.GLOBAL,
                )
                for j in range(per_rpc)
            ]).SerializeToString()

        async def drive():
            ch = grpc.aio.insecure_channel(d.grpc_address)
            rpc = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
            sem = asyncio.Semaphore(8)

            async def one(i):
                async with sem:
                    raw = await rpc(payload(i))
                    resp = pb.GetRateLimitsResp.FromString(raw)
                    assert not any(r.error for r in resp.responses)

            try:
                await asyncio.gather(*(one(i) for i in range(rpcs)))
            finally:
                await ch.close()

        async def owners_rows(names):
            # The same keys without the flag: the owner's row, hits 0.
            ch = grpc.aio.insecure_channel(d.grpc_address)
            try:
                raw = await ch.unary_unary(
                    "/pb.gubernator.V1/GetRateLimits"
                )(pb.GetRateLimitsReq(requests=[
                    pb.RateLimitReq(name="tick", unique_key=k, hits=0,
                                    limit=1_000_000_000, duration=600_000)
                    for k in names
                ]).SerializeToString())
            finally:
                await ch.close()
            return pb.GetRateLimitsResp.FromString(raw)

        cluster.run(drive(), timeout=120)

        def scrape():
            return d.metrics.stages.debug_vars(), eng.debug_vars()

        def at_rest(stages, engine_vars):
            # No tick between its first line and its last: one that has
            # taken the pending dict has tallied its keys, one that has
            # counted its sync has yet to close its stage.
            tick = stages.get("global", {}).get("sync_tick", {})
            return (engine_vars["pending"] == 0
                    and tick.get("count") == engine_vars["syncs"]
                    and tick.get("keys") == engine_vars["sync_keys"])

        deadline = time.monotonic() + 60
        last = None
        while time.monotonic() < deadline:
            time.sleep(0.05)       # the background loop flushes the rest
            now = scrape()
            if at_rest(*now) and (now[0]["global"], now[1]) == last:
                break
            last = (now[0].get("global"), now[1])
        stages, engine_vars = now
        assert at_rest(stages, engine_vars), (stages.get("global"),
                                              engine_vars)
        # Every acknowledged hit is on its owner's row.
        spent = 0
        for lo in range(0, keys, 100):
            resp = cluster.run(owners_rows(
                [f"t{k}" for k in range(lo, min(keys, lo + 100))]
            ), timeout=60)
            assert not any(r.error for r in resp.responses)
            spent += sum(1_000_000_000 - r.remaining
                         for r in resp.responses)
        assert d.fastpath.fallbacks == 0
    finally:
        cluster.stop()

    hits_sent = rpcs * sum(1 + j % 3 for j in range(per_rpc))
    g, lane = stages["global"], stages["engine"]
    tick = g["sync_tick"]
    assert tick["count"] == engine_vars["syncs"] > 0
    assert tick["keys"] == engine_vars["sync_keys"] > 0
    assert spent == hits_sent
    assert g["build_chunks"]["count"] == tick["count"]
    assert g["sync_step"]["count"] == tick["chunks"] >= tick["count"]
    assert tick["chunks"] >= -(-tick["keys"] // 128)
    parts = _ms(stages, "global", "build_chunks", "wait_locks", "sync_step")
    assert "lock_wait" not in g      # backend.lock_wait is a drain's wait
    assert 0.75 * tick["ms_total"] <= parts <= 1.001 * tick["ms_total"], (
        parts, tick)
    # The engine lane under its own name: drains, checks, rounds.
    assert lane["pack"]["checks"] == rpcs * per_rpc
    assert lane["pack"]["rounds"] >= lane["drain"]["count"] > 0
    assert lane["pack"]["count"] == lane["drain"]["count"]
    # One chunk's sync program, as /debug/vars describes it: its
    # geometry, which is all the roofline reader takes (PR 31).
    prog = engine_vars["sync_program"]
    assert prog == {"collective": "psum", "shards": 4, "delta_slots": 32}


# -- why a stage took that long (PR 41) ---------------------------------------

def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _cpu_ms(block: dict, name: str) -> float:
    family = tracing._FAMILY.match(name).group(0)
    return block.get(family, {}).get(name, {"cpu_ms": 0.0})["cpu_ms"]


@pytest.mark.parametrize("kind", ["spin", "sleep", "ended"])
def test_a_pools_cpu_beside_the_wall_of_its_sections(kind):
    """No stage reads a CPU clock (a system call of microseconds on the
    chip's host): a pool thread's own clock, read from outside at render,
    beside the wall of the section it ran says how much of that wall it
    was running.  One that computes reads the CPU it burnt, within its
    wall; one that sleeps next to none; one that has ended keeps its last
    reading under its name."""
    ledger = tracing.StageLedger()
    name = f"tpu-fastlane-{kind}_0"
    done, leave = threading.Event(), threading.Event()

    def work():
        with ledger.stage("lane.pack", "mach"):
            if kind == "sleep":
                time.sleep(0.08)
            else:       # 60 ms of CPU, however loaded the machine
                until = time.thread_time() + 0.06
                while time.thread_time() < until:
                    pass
        done.set()
        leave.wait()

    t = threading.Thread(target=work, name=name)
    before = _cpu_ms(tracing.thread_vars(), name)
    t.start()
    done.wait()
    block = tracing.thread_vars()
    assert name in block["tpu-fastlane"]       # the pool's family
    cpu = _cpu_ms(block, name) - before
    leave.set()
    t.join()
    row = ledger.debug_vars()["mach"]["pack"]
    assert set(row) == {"count", "ms_total", "ms_max", "max_at_ms"}
    assert cpu <= 1.01 * row["ms_total"] + 10      # the clock's grain
    if kind == "sleep":
        assert row["ms_total"] >= 80 and cpu < 10, (cpu, row)
    else:
        assert row["ms_total"] >= 60 and cpu >= 55, (cpu, row)
    if kind == "ended":
        for _ in range(2):     # retired once, and not counted twice
            after = tracing.thread_vars()
            assert _cpu_ms(after, name) - before == pytest.approx(
                cpu, abs=5)
        assert all(n != name for n, _ in tracing._THREAD_CPU_NS.values())
        assert tracing._ENDED_CPU_NS[name] > 0


def test_a_threads_cpu_clock_is_read_by_its_kernel_id():
    """A thread's CPU clock is read from outside it by the kernel's id
    for that clock, computed from Thread.native_id: what
    pthread_getcpuclockid returns for a live thread, and a clean error —
    not a read of a freed handle — for one that has ended."""
    here = (~threading.get_native_id() << 3) | 6
    assert time.pthread_getcpuclockid(threading.get_ident()) == here
    assert abs(time.clock_gettime_ns(here) - time.thread_time_ns()) < 5e6
    t = threading.Thread(target=_spin, args=(0.01,))
    t.start()
    tid = t.native_id
    t.join()
    with pytest.raises(OSError):      # EINVAL, once the kernel's thread
        for _ in range(1000):         # has gone (join() returns a little
            time.clock_gettime_ns((~tid << 3) | 6)          # before it)
            time.sleep(0.001)
    mine = _cpu_ms(tracing.thread_vars(), threading.current_thread().name)
    assert abs(mine - time.thread_time_ns() / 1e6) < 50


@pytest.mark.parametrize("name,family", [
    ("tpu-fastlane_0", "tpu-fastlane"),
    ("tpu-fastlane-engine_1", "tpu-fastlane"),
    ("tpu-fastlane-sketch_0", "tpu-fastlane"),
    ("tpu-step_0", "tpu-step"),
    ("asyncio_3", "asyncio"),
    ("Thread-2 (_poll_wrapper)", "Thread"),
    ("MainThread", "MainThread"),
])
def test_a_threads_family_is_the_first_two_words_of_its_name(name, family):
    """`threads.<family>.<name>`: every lane's pool is one family, so a
    data file sums the coalescer's threads by one path."""
    assert tracing._FAMILY.match(name).group(0) == family


def test_two_spinning_threads_share_one_gil():
    """Two Python threads spin in sections at once: between them they
    had the CPU for at most about the wall that passed, not twice it —
    the GIL, whatever the machine's load — which is what
    `host_python_cores` reads near 1.0.  The bound is the upper one: on
    a loaded host the two get less of a core, never more than one."""
    ledger = tracing.StageLedger()
    start = threading.Barrier(3)
    seen = threading.Event()
    before = tracing.thread_vars()

    def work():
        start.wait()
        with ledger.stage("lane.unpack", "mach"):
            _spin(0.3)
            while not seen.is_set():
                pass

    threads = [threading.Thread(target=work, name=f"gil-spin-{i}")
               for i in range(2)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    start.wait()
    time.sleep(0.35)
    during = tracing.thread_vars()
    wall_ms = (time.perf_counter() - t0) * 1e3
    seen.set()
    for t in threads:
        t.join()
    row = ledger.debug_vars()["mach"]["unpack"]
    assert row["count"] == 2 and row["ms_total"] >= 2 * 300
    assert "gil-spin" not in before
    spun = [during["gil-spin"][f"gil-spin-{i}"]["cpu_ms"] for i in range(2)]
    assert all(ms > 0 for ms in spun), spun
    assert sum(spun) <= 1.15 * wall_ms + 20, (spun, wall_ms)
    # Once they have ended the block keeps their readings: no sum falls.
    after = tracing.thread_vars()
    assert [after["gil-spin"][f"gil-spin-{i}"]["cpu_ms"]
            for i in range(2)] == spun
    main = threading.current_thread().name
    assert _cpu_ms(after, main) >= _cpu_ms(before, main)
    assert tracing.process_vars()["cpu_ms"] >= _cpu_ms(after, main)


def test_a_collection_is_a_row_of_lane_host():
    """gc.collect() adds one to host.gc's count and to its `gen2`; a
    young collection to the count alone; the row is the process's, so
    every ledger renders it."""
    ledger = tracing.StageLedger()
    before = ledger.debug_vars()["host"]["gc"]
    gc.collect()
    after = tracing.StageLedger().debug_vars()["host"]["gc"]
    assert set(after) == {"count", "ms_total", "ms_max", "max_at_ms", "gen2"}
    assert after["count"] == before["count"] + 1
    assert after["gen2"] == before["gen2"] + 1
    assert after["ms_total"] > before["ms_total"]
    gc.collect(0)
    young = ledger.debug_vars()["host"]["gc"]
    assert young["count"] == after["count"] + 1
    assert young["gen2"] == after["gen2"]


def test_the_rows_of_lane_host_stand_at_zero_from_start_up():
    host = Metrics().stages.debug_vars()["host"]
    assert set(host) == {"gc", "census_dispatch", "census_fetch", "hotkey",
                         "scrape", "loop_lag", "stall"}
    for name, row in host.items():
        want = {"count", "ms_total", "ms_max", "max_at_ms"}
        if name == "gc":
            want = want | {"gen2"}
        if name == "hotkey":
            want = want | {"keys", "native"}
            assert (row["keys"], row["native"]) == (0, 0)
        assert set(row) == want, name
        if name in ("gc", "stall"):   # the process's: other tests have run
            continue
        assert (row["count"], row["ms_total"], row["ms_max"]) == (0, 0, 0)
    assert host["stall"]["count"] == tracing._STALL.count


@pytest.fixture
def stall_store(monkeypatch):
    """The process's stalls row and ring, empty for one test."""
    from collections import deque

    monkeypatch.setattr(tracing, "_STALL", tracing._Cell("host", "host.stall"))
    monkeypatch.setattr(tracing, "_STALLS", deque(maxlen=tracing.STALL_RING))


@pytest.mark.parametrize("ms,lands", [(30, True), (19, False), (24, True)])
def test_a_stall_has_a_time_and_a_neighbourhood(clock, stall_store, ms, lands):
    """An instance of a leaf row of at least 20 ms and 8 x its row's mean
    so far lands in the ring with its times, and in host.stall; a shorter
    one, or one that is long beside no mean, does not.  The row and the
    ring are the process's: every ledger renders them."""
    ledger = tracing.StageLedger()
    for _ in range(8):  # mean so far: 3 ms
        with ledger.stage("lane.unpack", "mach"):
            clock.now += 3_000_000
    assert tracing.stalls() == []
    with ledger.stage("lane.unpack", "mach"):
        clock.now += ms * 1_000_000
    t_end = (EPOCH_NS + clock.now) / 1e6
    stall = tracing.StageLedger().debug_vars()["host"]["stall"]
    if not lands:
        assert tracing.stalls() == [] and stall["count"] == 0
        return
    assert tracing.stalls() == [{
        "t_start_ms": t_end - ms, "t_end_ms": t_end, "lane": "mach",
        "stage": "unpack", "ms": float(ms),
        "thread": threading.current_thread().name,
    }]
    assert (stall["count"], stall["ms_total"]) == (1, pytest.approx(ms))
    assert stall["max_at_ms"] == int(t_end)
    # A wait across threads is no leaf, however long; a row's first
    # instance has no mean to stand out from.
    wait = ledger.begin("lane.handoff", "mach")
    clock.now += 1
    wait.end()
    wait = ledger.begin("lane.handoff", "mach")
    clock.now += 900_000_000
    wait.end()
    with ledger.stage("lane.cascade", "mach"):
        clock.now += 900_000_000
    assert len(tracing.stalls()) == 1
    # The ring keeps the newest STALL_RING, oldest first.
    for _ in range(tracing.STALL_RING + 5):
        with ledger.stage("lane.pack", "mach"):
            clock.now += 1_000
        with ledger.stage("lane.pack", "mach"):
            clock.now += 10_000_000_000
            ledger.cell("mach", "lane.pack").ns_total = 1_000   # keep the mean
    ring = tracing.stalls()
    assert len(ring) == tracing.STALL_RING
    assert ring == sorted(ring, key=lambda r: r["t_end_ms"])
    assert all(r["stage"] == "pack" for r in ring)
    assert tracing._STALL.count == tracing.STALL_RING + 6


def test_a_long_collection_is_a_stall_too(stall_store, monkeypatch):
    """The collector reaches the stalls without a ledger's lock (a
    collection can start inside it): one far over its row's mean lands in
    the ring and the row like any leaf's."""
    monkeypatch.setattr(tracing, "_GC", tracing._Cell("host", "host.gc"))
    tracing._GC.counters["gen2"] = 0
    tracing._trace_me()
    for _ in range(4):
        gc.collect(0)
    assert tracing.stalls() == []
    real = time.perf_counter_ns
    late = iter((0, 30_000_000))
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: real() + next(late, 30_000_000),
        time_ns=time.time_ns,
    ))
    gc.collect()
    (r,) = tracing.stalls()
    assert (r["lane"], r["stage"]) == ("host", "gc") and r["ms"] >= 30
    assert r["thread"] == threading.current_thread().name
    assert tracing._STALL.count == 1 and tracing._GC.counters["gen2"] == 1


def test_the_heartbeat_times_the_loop_and_feeds_its_views():
    """host.loop_lag: one sample a LOOP_LAG_INTERVAL_S, a callback that
    holds the loop shows in it; gubernator_event_loop_lag_seconds and the
    flight recorder's lag readings are views of the row — the recorder
    times nothing itself."""
    from gubernator_tpu.runtime.flightrec import FlightRecorder

    metrics = Metrics()
    fr = metrics.flightrec = FlightRecorder(metrics=metrics, stall_ms=40.0)

    async def scenario():
        beat = asyncio.ensure_future(metrics.stages.heartbeat())
        await asyncio.sleep(tracing.LOOP_LAG_INTERVAL_S / 2)
        time.sleep(tracing.LOOP_LAG_INTERVAL_S)      # holds the loop
        await asyncio.sleep(tracing.LOOP_LAG_INTERVAL_S * 1.5)
        beat.cancel()
        await asyncio.gather(beat, return_exceptions=True)

    asyncio.run(scenario())
    n, ns, mx = metrics.stages.totals("host", "host.loop_lag")
    assert n >= 2 and mx >= 0.4 * tracing.LOOP_LAG_INTERVAL_S * 1e9
    assert fr.max_lag_ms == pytest.approx(mx / 1e6)
    assert fr.last_lag_ms <= fr.max_lag_ms
    assert metrics.registry.get_sample_value(
        "gubernator_event_loop_lag_seconds"
    ) == pytest.approx(fr.last_lag_ms / 1e3)
    stalls = [r for r in fr.snapshot()["ring"] if r["kind"] == "loop_stall"]
    assert [r["lag_ms"] for r in stalls] == [round(mx / 1e6, 1)]
    assert not hasattr(fr, "_lag_task")


def test_the_interferers_add_to_their_rows_on_a_live_daemon():
    """The census, note_traffic and both scrape routes each add to their
    row of lane `host` on a daemon over real gRPC and HTTP; /debug/vars
    carries `threads`, `process` and `stalls`, and no row a CPU key."""
    from gubernator_tpu.testing.cluster import Cluster

    cluster = Cluster.start(1)
    try:
        d = cluster.daemon_at(0)

        def get(path):
            with urllib.request.urlopen(
                f"http://{d.http_address}{path}", timeout=30
            ) as r:
                return r.read()

        first = json.loads(get("/debug/vars"))
        _drive(cluster, d, _payload, 40, 6)
        cluster.run(d.stats_sampler.sample(), timeout=60)
        get("/metrics")
        time.sleep(2 * tracing.LOOP_LAG_INTERVAL_S)
        out = json.loads(get("/debug/vars"))
    finally:
        cluster.stop()

    host0, host = first["stages"]["host"], out["stages"]["host"]
    assert set(host0) == set(host) >= {
        "gc", "census_dispatch", "census_fetch", "hotkey", "scrape",
        "loop_lag", "stall",
    }
    for row in ("census_dispatch", "census_fetch"):
        assert host[row]["count"] > host0[row]["count"], row
        assert host[row]["ms_total"] > host0[row]["ms_total"], row
    # note_traffic: once an RPC.
    assert host["hotkey"]["count"] - host0["hotkey"]["count"] == 40
    # ... and the row says what it worked on: six fingerprints an RPC,
    # every update the native pass where the library loaded.
    from gubernator_tpu import native

    assert {k: host["hotkey"][k] - host0["hotkey"][k]
            for k in ("keys", "native")} == {
        "keys": 240, "native": 40 if native.available() else 0}
    # A scrape is timed as it ends: each /debug/vars was under way when
    # it rendered itself, so the second sees the first and the /metrics.
    assert (host0["scrape"]["count"], host["scrape"]["count"]) == (0, 2)
    assert host["scrape"]["ms_total"] > 0
    assert host["loop_lag"]["count"] >= 2
    # Who had the CPU: threads.<family>.<name>.cpu_ms.
    threads = out["threads"]
    assert threads["tpu-fastlane"], sorted(threads)
    assert all(name.startswith("tpu-fastlane")
               for name in threads["tpu-fastlane"])
    assert all(set(t) == {"cpu_ms"}
               for family in threads.values() for t in family.values())
    assert sum(t["cpu_ms"] for family in threads.values()
               for t in family.values()) > 0
    assert out["process"]["cpu_ms"] > 0
    assert isinstance(out["stalls"], list)
    for r in out["stalls"]:
        assert r["ms"] >= 20 and r["t_end_ms"] >= r["t_start_ms"]
    # No stage reads a CPU clock: a row is its four keys and its counters.
    seen = 0
    for lane, rows in out["stages"].items():
        for stage, row in rows.items():
            assert {"count", "ms_total", "ms_max", "max_at_ms"} <= set(row)
            assert not any("cpu" in key for key in row), (lane, stage, row)
            assert row["max_at_ms"] > 0 or row["ms_max"] == 0, (lane, stage)
            seen += row["count"] > 0
    assert seen >= 12


# -- the span plane ---------------------------------------------------------

def test_merge_parents_the_stage_spans_when_armed():
    def process(entries):
        with tracing.stage("lane.pack"):
            pass
        return [0 for _ in entries]

    async def scenario():
        pool = ThreadPoolExecutor(1)
        co = _Coalescer(pool, process, lane="mach")
        with tracing.span("req") as root:
            await co.do(_E())
        await co.close()
        pool.shutdown(wait=True)
        return root

    with memory_tracing() as exp:
        root = asyncio.run(scenario())
        by_name = {s.name: s for s in exp.spans()}
    merge = by_name["fastpath.merge"]
    assert merge.parent_id == root.context.span_id
    stage = by_name["gub.lane.dispatch_stage"]
    assert stage.parent_id == merge.context.span_id
    assert stage.attributes == {"lane": "mach"}
    for name in ("gub.lane.handoff", "gub.lane.pack", "gub.lane.resume"):
        assert by_name[name].parent_id == stage.context.span_id, name
    for name in ("gub.lane.queue_wait", "gub.lane.in_drain",
                 "gub.wire.wake"):
        assert by_name[name].parent_id == root.context.span_id, name
    # Span records keep the epoch clock (docs/tracing.md: the offset to
    # the profiler's clock is stated, not assumed).
    assert abs(stage.start_ns - time.time_ns()) < 60e9


def test_disarmed_stage_allocates_no_span_and_no_context():
    assert not tracing.enabled()
    ledger = tracing.StageLedger()
    with ledger.stage("lane.pack", "mach") as st:
        assert st.context is None
        assert tracing.current_context() is None
    w = ledger.begin("lane.queue_wait", "mach", parent=None)
    assert w.context is None
    w.end()
    with memory_tracing() as exp:
        assert len(exp) == 0


# -- the profiler -----------------------------------------------------------

def test_profiler_trace_holds_gub_events(tmp_path):
    """A wait held across an await, a stage on a pool thread and the
    clock anchor, read back from the .xplane.pb of a short CPU trace."""
    import jax
    from jax.profiler import ProfileData

    def process(entries):
        with tracing.stage("lane.pack"):
            time.sleep(0.002)
        return [0 for _ in entries]

    async def scenario(ledger):
        pool = ThreadPoolExecutor(1)
        co = _Coalescer(pool, process, lane="mach")
        held = ledger.begin("lane.slot_wait", "mach")
        await asyncio.sleep(0.005)               # held across an await
        held.end()
        await co.do(_E())
        await co.close()
        pool.shutdown(wait=True)

    ledger = tracing.StageLedger()
    jax.profiler.start_trace(str(tmp_path))
    try:
        t_prog = time.time_ns()
        with ledger.stage("global.sync_tick", "global", anchor=True):
            time.sleep(0.001)
        asyncio.run(scenario(ledger))
        gc.collect()                 # lane `host`: on the host plane too
        with ledger.stage("host.scrape", "host"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    threads = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):    # one line per thread
            for ev in line.events:
                if ev.name.startswith("gub."):
                    events.setdefault(ev.name, ev)
                    threads[ev.name] = i
    for name in ("gub.lane.slot_wait", "gub.lane.queue_wait",
                 "gub.lane.in_drain", "gub.wire.wake", "gub.lane.handoff",
                 "gub.lane.pack", "gub.lane.resume",
                 "gub.global.sync_tick", "gub.host.gc", "gub.host.scrape"):
        assert name in events, (name, sorted(events))
    assert events["gub.lane.slot_wait"].duration_ns >= 4_000_000
    assert events["gub.lane.pack"].duration_ns >= 1_500_000
    # The pool-thread stage is on another thread's line than the waits.
    assert threads["gub.lane.pack"] != threads["gub.lane.slot_wait"]
    # The anchor: the program's time.time_ns() at the event's start is an
    # argument of the event, readable beside the profiler's own stamp.
    anchor = events["gub.global.sync_tick"]
    stats = dict(anchor.stats)
    assert t_prog <= int(stats["t_ns"]) <= t_prog + 1_000_000_000
    assert anchor.start_ns > 0
