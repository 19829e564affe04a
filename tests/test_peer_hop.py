"""The forward hop of a cluster, as the benchmark's `peers4-10m` runs it.

(a) Four in-process daemons on the static ring: seeded RPCs of the
    `batch.closed` shape entered by every peer in turn; every answer is
    `core/pymodel.py`'s, rows lie on owners alone, the stage ledger's
    `peer.forward` `checks` is the ring's own count of non-owned checks
    and the `forward` series', and both identities of the ledger close on
    an entry daemon: the six per-RPC stages — ingress, queue_wait,
    in_drain, wake, peer_wait, egress — partition `wire.handler`, and a
    drain its ten.  A single daemon and a `peer_rpc` handler leave the
    hop's rows at zero.
(b) A forward is exactly-once across its own time limit (docs/cluster.md):
    an owner stalled for longer than the batch timeout and shorter than
    the client's deadline — before it applies, inside its drain, after it
    applied and before it answered — costs latency, never an error and
    never a hit spent twice: the entry daemon asks again under the
    forward's id (`reasked`), the owner applies an id once and a second
    arrival takes the first's answer (`joined`).  A peer that never said
    it applies an id once is not asked again.
(c) The ring the benchmark copied (`bench/lib/ring.py`) places every key
    where `net/replicated_hash.ReplicatedConsistentHash` does.
"""
from __future__ import annotations

import asyncio
import importlib.util
import sys
import time
from collections import Counter, defaultdict
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.core.config import DeviceConfig, fast_test_behaviors
from gubernator_tpu.core.types import RateLimitReq
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.proto import peers_pb2
from gubernator_tpu.testing.chaos import ChaosPlan, Rule
from gubernator_tpu.testing.cluster import Cluster

GET_RATE_LIMITS = "/pb.gubernator.V1/GetRateLimits"
GET_PEER_RATE_LIMITS = "/pb.gubernator.PeersV1/GetPeerRateLimits"
DAYS_30 = 2_592_000_000
KEYS = 3000
ROUNDS = 5
# peer.forward's counters beside `checks` (runtime/tracing.py).
HOP_EVENTS = ("timeouts", "reasked", "joined", "retried", "refused")
# ... and the peer batcher's (net/peer_client.py `forward_raw`, PR 43).
BATCH_EVENTS = ("batched", "flush_wait", "flush_limit")
# The stages a client RPC's handler divides into on a daemon that does not
# route (docs/tracing.md, identity 1); an entry daemon adds wire.peer_wait.
RPC_PARTS = (("wire", "ingress"), ("mach", "queue_wait"),
             ("mach", "in_drain"), ("wire", "wake"), ("wire", "egress"))
# The stages a drain divides into (docs/tracing.md, identity 2).
DRAIN_PARTS = ("slot_wait", "dispatch_wait", "handoff", "pack", "lock_wait",
               "dispatch", "cascade", "d2h_wait", "unpack", "resume")

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def _limit(k: int) -> int:
    return 4 if k % 50 == 0 else 1000      # a few keys run into their limit


def _req(k: int, hits: int = 1) -> pb.RateLimitReq:
    return pb.RateLimitReq(
        name="hop", unique_key=f"k{k}", hits=hits, limit=_limit(k),
        duration=DAYS_30, algorithm=k & 1,
    )


def _stages(d) -> dict:
    return d.metrics.stages.debug_vars()


def _calltype(d, kind: str) -> float:
    return d.metrics.getratelimit_counter.labels(kind)._value.get()


def _grown(after: dict, before: dict, lane: str, stage: str, key: str):
    return after[lane][stage][key] - before[lane][stage][key]


@pytest.fixture(scope="module")
def ring4():
    c = Cluster.start(4, device=DeviceConfig(
        num_slots=1 << 14, ways=8, batch_size=512,
    ), behaviors=replace(fast_test_behaviors(), batch_timeout_s=30.0))
    try:
        yield c
    finally:
        c.stop()


def test_every_answer_is_the_owners_and_the_ledger_names_the_hop(ring4):
    from gubernator_tpu.core.pymodel import PyRateLimiter

    c = ring4
    rng = np.random.default_rng(38)
    owner_of = np.array([
        c.daemons.index(c.owner_daemon_of(f"hop_k{k}")) for k in range(KEYS)
    ])
    # ROUNDS x (every peer enters two RPCs of 500-1000 uniform checks).
    plan = [
        [(entry, rng.integers(0, KEYS, int(rng.integers(500, 1001))))
         for entry in range(4) for _ in range(2)]
        for _ in range(ROUNDS)
    ]
    rows0 = [d.service.backend.occupancy() for d in c.daemons]
    lost0 = sum(d.service.backend.not_persisted for d in c.daemons)
    oracle = PyRateLimiter()

    # Every key's row is made first, a hundred keys an RPC: a launch that
    # inserts more new keys into one bucket than it has claim rounds
    # answers the last as transient (ops/step.py's contract), which is not
    # this test's subject.
    async def seed_rows():
        import grpc.aio

        ch = grpc.aio.insecure_channel(c.daemons[0].grpc_address)
        try:
            for lo in range(0, KEYS, 100):
                raw = await ch.unary_unary(GET_RATE_LIMITS)(
                    pb.GetRateLimitsReq(requests=[
                        _req(k, hits=0) for k in range(lo, lo + 100)
                    ]).SerializeToString())
                got = pb.GetRateLimitsResp.FromString(raw).responses
                assert [(r.error, r.remaining) for r in got] == [
                    ("", _limit(k)) for k in range(lo, lo + 100)]
        finally:
            await ch.close()

    c.run(seed_rows(), timeout=120)
    for k in range(KEYS):
        oracle.get_rate_limit(RateLimitReq(
            name="hop", unique_key=f"k{k}", hits=0, limit=_limit(k),
            duration=DAYS_30, algorithm=k & 1,
        ))
    before = [_stages(d) for d in c.daemons]
    forward0 = [_calltype(d, "forward") for d in c.daemons]
    local0 = [_calltype(d, "local") for d in c.daemons]

    async def drive():
        import grpc.aio

        chans = [grpc.aio.insecure_channel(d.grpc_address)
                 for d in c.daemons]
        rpcs = [ch.unary_unary(GET_RATE_LIMITS) for ch in chans]

        async def one(entry, keys):
            raw = await rpcs[entry](pb.GetRateLimitsReq(
                requests=[_req(int(k)) for k in keys]
            ).SerializeToString())
            return pb.GetRateLimitsResp.FromString(raw).responses

        try:
            return [
                await asyncio.gather(*(one(e, keys) for e, keys in rnd))
                for rnd in plan
            ]
        finally:
            for ch in chans:
                await ch.close()

    answers = c.run(drive(), timeout=300)
    after = [_stages(d) for d in c.daemons]

    # Every answer is the reference's, replayed per key.  The RPCs of a
    # round are in flight together, so a key's answers of one round are a
    # multiset; inside one RPC they come in order.
    for rnd, got in zip(plan, answers):
        seen = defaultdict(list)
        for (entry, keys), resps in zip(rnd, got):
            assert len(resps) == len(keys)
            last = {}
            for k, r in zip(keys.tolist(), resps):
                assert r.error == "" and r.limit == _limit(k), (k, r)
                assert r.metadata.get("owner", "") == (
                    "" if owner_of[k] == entry
                    else c.daemons[owner_of[k]].grpc_address
                ), (k, entry)
                assert r.remaining <= last.get(k, r.limit), (k, entry)
                last[k] = r.remaining
                seen[k].append((r.status, r.remaining))
        for k, got_k in seen.items():
            want = []
            for _ in got_k:
                w = oracle.get_rate_limit(RateLimitReq(
                    name="hop", unique_key=f"k{k}", hits=1, limit=_limit(k),
                    duration=DAYS_30, algorithm=k & 1,
                ))
                want.append((int(w.status), w.remaining))
            assert Counter(got_k) == Counter(want), k

    # Rows on owners alone.
    assert sum(d.service.backend.not_persisted for d in c.daemons) == lost0
    for i, d in enumerate(c.daemons):
        assert d.service.backend.occupancy() - rows0[i] == int(
            (owner_of == i).sum()
        ), i
    for k in range(0, KEYS, 125):
        for i, d in enumerate(c.daemons):
            item = d.service.backend.get_cache_item(f"hop_k{k}")
            assert (item is not None) == (owner_of[k] == i), (k, i)

    # The hop, counted three ways: the ring, the ledger, the series.
    forwards_sent = handlers = 0
    for i, d in enumerate(c.daemons):
        mine = [keys for rnd in plan for e, keys in rnd if e == i]
        not_owned = sum(int((owner_of[k] != i).sum()) for k in mine)
        owned = sum(int((owner_of[k] == i).sum()) for k in mine)
        fwd = {k: _grown(after[i], before[i], "peer", "forward", k)
               for k in ("count", "checks", *BATCH_EVENTS, *HOP_EVENTS)}
        # The two RPCs a peer enters in a round are in flight together:
        # where their forwards to one owner met in the batch window (PR
        # 43: 10 ms here, never the limit: 2 x ~190 checks) they shared
        # one GetPeerRateLimits, so a pair is one forward and two
        # `batched`.
        sent = 3 * len(mine) - fwd["batched"] // 2
        assert fwd["batched"] % 2 == 0 and fwd == dict(
            dict.fromkeys(HOP_EVENTS, 0), count=sent, checks=not_owned,
            batched=fwd["batched"], flush_wait=sent, flush_limit=0), i
        forwards_sent += sent
        assert _grown(after[i], before[i], "peer", "batch_wait", "count") == (
            3 * len(mine))
        assert _calltype(d, "forward") - forward0[i] == not_owned
        assert _calltype(d, "local") - local0[i] == owned
        for stage, n in (("route", 1), ("splice", 3), ("assemble", 3)):
            assert _grown(after[i], before[i], "peer", stage, "count") == (
                n * len(mine)), (i, stage)
        assert _grown(after[i], before[i], "wire", "peer_wait", "count") == (
            len(mine))

        # The routed RPC's identity, over this daemon's handlers (entry
        # and owner side: an owner's handler has no peer_wait).
        handlers += _grown(after[i], before[i], "wire", "handler", "count")
        handler = _grown(after[i], before[i], "wire", "handler", "ms_total")
        parts = sum(
            _grown(after[i], before[i], lane, stage, "ms_total")
            for lane, stage in RPC_PARTS + (("wire", "peer_wait"),)
        )
        assert 0.95 * handler <= parts <= 1.001 * handler, (i, parts, handler)
        without = parts - _grown(
            after[i], before[i], "wire", "peer_wait", "ms_total")
        assert without < parts          # the hop was waited for

        # The drain's identity, on a daemon that is entry and owner both.
        drain = _grown(after[i], before[i], "mach", "drain", "ms_total")
        stages_of_it = sum(
            _grown(after[i], before[i], "mach", st, "ms_total")
            for st in DRAIN_PARTS if st in after[i]["mach"])
        assert 0.75 * drain <= stages_of_it <= 1.001 * drain, (
            i, stages_of_it, drain)
    # A handler a client RPC and one a GetPeerRateLimits, cluster-wide.
    assert handlers == ROUNDS * 8 + forwards_sent


def test_an_rpc_that_owns_none_of_its_checks_ends_ingress_first(ring4):
    """Nothing enqueues for such an RPC, so check_raw's `finally` would
    stretch wire.ingress over the forwards: it ends before them, and
    wire.peer_wait starts there."""
    c = ring4
    d0 = c.daemons[0]
    keys = [k for k in range(KEYS, KEYS + 400)
            if c.owner_daemon_of(f"hop_k{k}") is not d0][:60]
    before = _stages(d0)

    async def drive():
        import grpc.aio

        ch = grpc.aio.insecure_channel(d0.grpc_address)
        try:
            raw = await ch.unary_unary(GET_RATE_LIMITS)(pb.GetRateLimitsReq(
                requests=[_req(k) for k in keys]
            ).SerializeToString())
        finally:
            await ch.close()
        return pb.GetRateLimitsResp.FromString(raw).responses

    resps = c.run(drive(), timeout=60)
    assert [r.error for r in resps] == [""] * len(keys)
    after = _stages(d0)
    assert _grown(after, before, "mach", "queue_wait", "count") == 0
    assert _grown(after, before, "wire", "peer_wait", "count") == 1
    handler = _grown(after, before, "wire", "handler", "ms_total")
    ingress = _grown(after, before, "wire", "ingress", "ms_total")
    waited = _grown(after, before, "wire", "peer_wait", "ms_total")
    egress = _grown(after, before, "wire", "egress", "ms_total")
    assert ingress < waited
    assert 0.95 * handler <= ingress + waited + egress <= 1.001 * handler


def test_a_peer_rpc_and_a_single_daemon_leave_the_hops_rows_at_zero(ring4):
    """`peer_rpc=True` and `_single_node()` never enter `_serve_routed`:
    the hop's rows do not move, the old rows count as before."""
    c = ring4
    owner = c.daemons[1]
    keys = [k for k in range(2 * KEYS, 2 * KEYS + 400)
            if c.owner_daemon_of(f"hop_k{k}") is owner][:40]
    before = _stages(owner)

    async def as_a_peer():
        import grpc.aio

        ch = grpc.aio.insecure_channel(owner.grpc_address)
        try:
            raw = await ch.unary_unary(GET_PEER_RATE_LIMITS)(
                peers_pb2.GetPeerRateLimitsReq(
                    requests=[_req(k) for k in keys]
                ).SerializeToString(), timeout=20,
            )
        finally:
            await ch.close()
        return peers_pb2.GetPeerRateLimitsResp.FromString(raw).rate_limits

    resps = c.run(as_a_peer(), timeout=60)
    assert [(r.error, r.remaining) for r in resps] == [
        ("", _limit(k) - 1) for k in keys]
    after = _stages(owner)
    assert after["peer"] == before["peer"]
    assert after["wire"]["peer_wait"] == before["wire"]["peer_wait"]
    for lane, stage in (("wire", "handler"),) + RPC_PARTS:
        assert _grown(after, before, lane, stage, "count") == 1, stage

    single = Cluster.start(1)
    try:
        d = single.daemon_at(0)
        rows0 = _stages(d)
        zero = {"count": 0, "ms_total": 0.0, "ms_max": 0.0, "max_at_ms": 0}
        assert rows0["peer"] == {
            "route": zero, "splice": zero, "assemble": zero,
            "batch_wait": zero,
            "forward": dict(zero, checks=0, **dict.fromkeys(
                BATCH_EVENTS + HOP_EVENTS, 0)),
        }
        assert rows0["wire"]["peer_wait"] == zero

        async def drive():
            import grpc.aio

            ch = grpc.aio.insecure_channel(d.grpc_address)
            try:
                for i in range(5):
                    await ch.unary_unary(GET_RATE_LIMITS)(
                        pb.GetRateLimitsReq(
                            requests=[_req(k) for k in range(i, i + 30)]
                        ).SerializeToString())
            finally:
                await ch.close()

        single.run(drive(), timeout=60)
        rows = _stages(d)
        assert rows["peer"] == rows0["peer"]
        assert rows["wire"]["peer_wait"] == zero
        for lane, stage in (("wire", "handler"),) + RPC_PARTS:
            assert rows[lane][stage]["count"] == 5, stage
        handler = rows["wire"]["handler"]["ms_total"]
        parts = sum(rows[lane][stage]["ms_total"]
                    for lane, stage in RPC_PARTS)
        assert 0.80 * handler <= parts <= 1.001 * handler
    finally:
        single.stop()


# -- (b) a forward is exactly-once across its own time limit ----------------

FORWARD_LIMIT_S = 0.4
STALL_S = 1.0           # 2.5 forward limits; the client waits 20 s


def _stall_plan(owner_addr: str, phase: str):
    return ChaosPlan(rules=[Rule(
        op="delay", where="server", phase=phase, delay_s=STALL_S,
        method="GetPeerRateLimits", target=owner_addr, max_count=1,
    )])


@pytest.fixture(scope="module")
def pair():
    """Two daemons, `entry` and `owner`, whose forwards time out after
    FORWARD_LIMIT_S, and the chaos plane (no rule yet) at their RPC
    boundaries."""
    from gubernator_tpu.core.config import DaemonConfig
    from gubernator_tpu.testing.chaos import ChaosInjector

    c = Cluster.start(
        2, conf_template=DaemonConfig(chaos=ChaosInjector()),
        behaviors=replace(
            fast_test_behaviors(), batch_timeout_s=FORWARD_LIMIT_S),
    )
    try:
        yield c
    finally:
        c.stop()


def _once_req(tag: str, k: int, hits: int) -> pb.RateLimitReq:
    return pb.RateLimitReq(
        name="once", unique_key=f"{tag}{k}", hits=hits, limit=10,
        duration=DAYS_30, algorithm=k & 1,
    )


def _ask(c, entry, reqs, timeout=20.0):
    """One client RPC by `entry`; (error, status, limit, remaining) a
    check."""
    async def go():
        import grpc.aio

        ch = grpc.aio.insecure_channel(entry.grpc_address)
        try:
            raw = await ch.unary_unary(GET_RATE_LIMITS)(
                pb.GetRateLimitsReq(requests=reqs).SerializeToString(),
                timeout=timeout,
            )
        finally:
            await ch.close()
        return pb.GetRateLimitsResp.FromString(raw).responses

    return [(r.error, int(r.status), r.limit, r.remaining)
            for r in c.run(go(), timeout=timeout + 40)]


def _reference(oracle, reqs):
    out = []
    for r in reqs:
        w = oracle.get_rate_limit(RateLimitReq(
            name=r.name, unique_key=r.unique_key, hits=r.hits,
            limit=r.limit, duration=r.duration, algorithm=r.algorithm,
        ))
        out.append((w.error, int(w.status), w.limit, w.remaining))
    return out


def _hop(d) -> dict:
    return dict(_stages(d)["peer"]["forward"])


def _warm(c, tag: str):
    """One forwarded check, so that the entry daemon's channel is READY
    and the owner has said it applies an id once."""
    entry, owner = c.daemons
    k = next(k for k in range(100, 1000)
             if c.owner_daemon_of(f"once_{tag}{k}") is owner)
    # A peek, so asking again spends nothing: on a loaded host the first
    # forward over a cold channel can outlast the forward limit, and a
    # peer that has never answered is not asked again by the program.
    for _ in range(5):
        got = _ask(c, entry, [_once_req(tag, k, 0)])
        if not got[0][0]:
            break
    assert got == [("", 0, 10, 10)]
    peer = entry.service.get_peer(f"once_{tag}{k}")
    assert peer.info().grpc_address == owner.grpc_address
    return peer


@pytest.mark.parametrize("where", ["before", "drain", "after"])
def test_an_owners_stall_costs_latency_and_never_an_error(pair, where):
    """The owner stalls for 2.5 forward limits — before its handler runs
    (nothing applied), inside its drain (the lane's dispatch slot held:
    the first ask's work is in progress when its caller gives up), after
    it applied and before it answered (the parent's hole: there the
    client read an error and the hits were spent).  Seeded checks with
    duplicates and `hits: 0` on keys of both daemons: every answer is the
    reference's, in order, and a read of every key afterwards shows each
    hit spent once."""
    from gubernator_tpu.core.pymodel import PyRateLimiter

    c = pair
    entry, owner = c.daemons
    inj = entry.chaos
    tag = f"{where}-"
    peer = _warm(c, tag)
    assert peer._applies_once
    # Twelve keys of each daemon, whatever ring the run's ports give:
    # how many of the seeded checks are forwarded does not depend on it.
    by_owner = {entry: [], owner: []}
    for k in range(1000):
        mine = by_owner[c.owner_daemon_of(f"once_{tag}{k}")]
        if len(mine) < 12:
            mine.append(k)
    ids = by_owner[entry] + by_owner[owner]
    assert len(ids) == 24
    rng = np.random.default_rng(39)
    reqs = [_once_req(tag, ids[int(rng.integers(24))],
                      int(rng.choice([0, 1, 1, 2, 5]))) for _ in range(160)]
    owned = [c.owner_daemon_of(f"once_{r.unique_key}") is owner
             for r in reqs]
    assert 40 < sum(owned) < 120        # both daemons own some
    oracle = PyRateLimiter()
    e0, o0 = _hop(entry), _hop(owner)
    t0 = time.monotonic()
    if where == "drain":
        async def hold():
            sem = owner.fastpath._mach._dispatch_sem
            await sem.acquire()
            try:
                await asyncio.sleep(STALL_S)
            finally:
                sem.release()

        held = asyncio.run_coroutine_threadsafe(hold(), c._loop)
        got = _ask(c, entry, reqs)
        held.result(30)
    else:
        inj.reset(_stall_plan(owner.grpc_address, where))
        try:
            got = _ask(c, entry, reqs)
            assert inj.injected[f"server_{where}"] == 1
        finally:
            inj.reset(ChaosPlan())
    took = time.monotonic() - t0
    # It was stalled: for the whole of it where the first ask's work is
    # what stalls; a handler stalled before it runs, or one that holds its
    # answer back, is left behind by the re-ask (the rule fires once).
    assert took >= (STALL_S if where == "drain" else FORWARD_LIMIT_S) - 0.05
    assert got == _reference(oracle, reqs)
    assert [g[0] for g in got] == [""] * len(reqs)
    # Each hit spent exactly once: what a read finds now.
    reads = [_once_req(tag, k, 0) for k in ids]
    assert _ask(c, entry, reads) == _reference(oracle, reads)
    e1, o1 = _hop(entry), _hop(owner)
    grown = {k: e1[k] - e0[k] for k in e0}
    assert grown["count"] == 2 and grown["checks"] == sum(owned) + 12
    assert grown["timeouts"] == grown["reasked"] >= 1, grown
    assert (grown["refused"], grown["retried"], grown["joined"]) == (0, 0, 0)
    joined = o1["joined"] - o0["joined"]
    if where != "before":
        # The first ask's work was done or in progress when the re-ask
        # came: it took that answer.  (A handler stalled BEFORE it ran
        # is cancelled with its caller, and the re-ask is the first to
        # apply the id.)
        assert joined >= 1, (where, joined)
    assert joined <= grown["reasked"]
    assert len(owner.forwards) >= 1


@pytest.mark.parametrize("rounds", [6, 7, 8])
def test_a_forward_of_more_rounds_than_any_before_compiles_nothing(
        pair, rounds):
    """What the stall WAS (PERF.md section 6, PR 39): the wire check sends
    an owner one key 6, 7 or 8 times with changing limits and peeks, so
    its drain takes that many rounds, and `fetch_ravel` brought the
    rounds' response buffers to the host through a concatenate that XLA
    compiled once per sequence of rounds — on the request path, after the
    rounds were applied, past the forward's time limit on the driver's
    machine.  Now no program runs there: a drain of more rounds than any
    before it compiles nothing, and answers as the reference does."""
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.runtime import tracing

    c = pair
    entry, owner = c.daemons
    tag = f"rounds{rounds}-"
    _warm(c, tag)
    k = next(k for k in range(100, 1000)
             if c.owner_daemon_of(f"once_{tag}{k}") is owner)

    def reqs(n):
        return [pb.RateLimitReq(
            name="once", unique_key=f"{tag}{k}", hits=i % 3,
            limit=10 + 5 * (i & 1), duration=DAYS_30,
        ) for i in range(n)]

    oracle = PyRateLimiter()
    # Two rounds first: whatever a first drain of several rounds loads.
    assert _ask(c, entry, reqs(2)) == _reference(oracle, reqs(2))
    launches = _stages(owner)["mach"]["dispatch"]["count"]
    compiles = tracing._COMPILES.count
    assert _ask(c, entry, reqs(rounds)) == _reference(oracle, reqs(rounds))
    assert _stages(owner)["mach"]["dispatch"]["count"] > launches
    assert tracing._COMPILES.count == compiles


def test_the_stall_plan_fires_where_it_says():
    """deploy/chaos/owner_stall.json, the plan of the chip run that shows
    the repair (docs/cluster.md): seeded draws a call, so that which calls
    to the owner it stalls follows from the file alone — two forwards of
    the wire check (one held before its handler, one after it), one
    inside the window."""
    from gubernator_tpu.testing.chaos import ChaosInjector, load_plan

    path = Path(__file__).resolve().parent.parent / (
        "deploy/chaos/owner_stall.json")
    inj = ChaosInjector(load_plan(str(path)))
    owner = "127.0.0.1:21053"
    fired = {"before": [], "after": []}
    for n in range(4000):
        for phase in fired:
            rule = inj.server_rule(owner, "GetPeerRateLimits", phase)
            if rule is not None:
                assert rule.op == "delay" and 0.6 <= rule.delay_s <= 1.5
                fired[phase].append(n)
    (early, late), (held,) = fired["before"], fired["after"]
    # 29 wire-check RPCs send the owner 12 forwards of ~62 checks and a
    # few of one key; the window opens some 1,400 forwards later.
    assert 4 <= early <= 7 and 9 <= held <= 11
    assert 2100 <= late <= 2400
    assert inj.server_rule("127.0.0.1:21052", "GetPeerRateLimits",
                           "before") is None
    assert inj.server_rule(owner, "UpdatePeerGlobals", "before") is None


def test_a_peer_that_never_said_it_applies_once_is_not_asked_again(
    pair, monkeypatch
):
    """Upstream's peer ignores the id and echoes nothing: a re-ask could
    spend the hits twice, so the timed-out forward is answered with
    today's error text, once."""
    c = pair
    entry, owner = c.daemons
    tag = "mute-"
    peer = _warm(c, tag)
    monkeypatch.setattr(peer, "_applies_once", False)
    monkeypatch.setattr(peer, "_note_trailing_md", lambda md: None)
    keys = [k for k in range(200)
            if c.owner_daemon_of(f"once_{tag}{k}") is owner][:5]
    mine = next(k for k in range(200)
                if c.owner_daemon_of(f"once_{tag}{k}") is entry)
    e0 = _hop(entry)
    entry.chaos.reset(_stall_plan(owner.grpc_address, "after"))
    try:
        got = _ask(c, entry, [_once_req(tag, k, 1) for k in keys + [mine]])
    finally:
        entry.chaos.reset(ChaosPlan())
    for err, *_ in got[:-1]:
        assert "Error while fetching rate limit from peer" in err
        assert "DEADLINE_EXCEEDED" in err
    assert got[-1] == ("", 0, 10, 9)            # its own check is served
    grown = {k: v - e0[k] for k, v in _hop(entry).items()}
    assert grown == dict(
        dict.fromkeys(BATCH_EVENTS + HOP_EVENTS, 0), count=1, checks=5,
        flush_wait=1, timeouts=1, refused=1, ms_total=grown["ms_total"], ms_max=grown["ms_max"],
        max_at_ms=grown["max_at_ms"])


@pytest.mark.parametrize("case,want", [
    ("no_id", None), ("never_echoed", None), ("deadline_gone", None),
    ("deadline_far", FORWARD_LIMIT_S), ("deadline_near", 0.1),
    ("no_deadline_first", FORWARD_LIMIT_S), ("no_deadline_spent", None),
])
def test_what_bounds_the_re_asks(case, want):
    """The client's own deadline where it set one, FORWARD_TRIES asks
    where it did not; never without an id or the peer's word."""
    from gubernator_tpu.core.config import BehaviorConfig
    from gubernator_tpu.core.types import PeerInfo
    from gubernator_tpu.net.peer_client import FORWARD_TRIES, PeerClient

    pc = PeerClient(PeerInfo(grpc_address="127.0.0.1:1"),
                    BehaviorConfig(batch_timeout_s=FORWARD_LIMIT_S))
    pc._applies_once = case != "never_echoed"
    now = time.monotonic()
    fid, deadline, asks = {
        "no_id": (None, now + 5, 1),
        "never_echoed": ("a-1", now + 5, 1),
        "deadline_gone": ("a-1", now - 0.01, 1),
        "deadline_far": ("a-1", now + 5, 7),
        "deadline_near": ("a-1", now + 0.1, 2),
        "no_deadline_first": ("a-1", None, 1),
        "no_deadline_spent": ("a-1", None, FORWARD_TRIES),
    }[case]
    got = pc._reask_budget(fid, deadline, asks)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, abs=0.02)


def test_forward_once_applies_an_id_once_and_shields_the_work():
    """net/forward_once.py alone: a second arrival awaits the first's
    work, the first caller's cancellation does not reach it, an error is
    the id's answer too, and an id is forgotten by the first arrival that
    comes `keep_s` after its work ended."""
    from gubernator_tpu.net.forward_once import KEEP_TIMEOUTS, ForwardOnce
    from gubernator_tpu.runtime.tracing import StageLedger

    async def scenario():
        stages = StageLedger()
        once = ForwardOnce(0.01, stages)
        ran = []
        gate = asyncio.Event()

        async def work():
            ran.append(1)
            await gate.wait()
            return b"answer"

        first = asyncio.ensure_future(once.apply("x-1", work))
        await asyncio.sleep(0)
        second = asyncio.ensure_future(once.apply("x-1", work))
        await asyncio.sleep(0)
        first.cancel()                  # its caller gave up
        await asyncio.sleep(0)
        gate.set()
        assert await second == b"answer" and ran == [1]
        assert await once.apply("x-1", work) == b"answer" and ran == [1]

        async def broken():
            ran.append(2)
            raise ValueError("as it failed the first time")

        for _ in range(2):
            with pytest.raises(ValueError):
                await once.apply("x-2", broken)
        assert ran == [1, 2] and len(once) == 2
        await asyncio.sleep(KEEP_TIMEOUTS * 0.01 + 0.05)
        # Forgotten by the next arrival, whose own id is looked up first
        # (a re-ask that was held up as long as its answer was kept).
        assert await once.apply("x-1", work) == b"answer"
        assert ran == [1, 2] and len(once) == 0
        assert await once.apply("x-1", work) == b"answer"
        assert ran == [1, 2, 1] and len(once) == 1
        return stages.debug_vars()["peer"]["forward"]["joined"]

    assert asyncio.run(scenario()) == 4


# -- (c) the benchmark's copied ring ---------------------------------------

def _bench_ring():
    """bench/lib/ring.py by its path: numpy, hashlib and xxhash alone."""
    path = Path(__file__).resolve().parent.parent / "bench/lib/ring.py"
    spec = importlib.util.spec_from_file_location("bench_lib_ring", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kind", ["xx", "fnv1", "fnv1a"])
@pytest.mark.parametrize("seed", [1, 2246822519])
def test_the_benchmarks_ring_places_every_key_where_the_programs_does(
    kind, seed
):
    from gubernator_tpu.net.replicated_hash import (
        HASH_FUNCTIONS,
        ReplicatedConsistentHash,
    )

    ring_mod = _bench_ring()
    rng = np.random.default_rng(seed)
    addrs = [f"10.{rng.integers(256)}.{rng.integers(256)}."
             f"{rng.integers(256)}:{rng.integers(1024, 65536)}"
             for _ in range(5)]
    theirs = ReplicatedConsistentHash(HASH_FUNCTIONS[kind],
                                      key_of=lambda a: a)
    for a in addrs:
        theirs.add(a)
    ours = ring_mod.build(addrs, kind)
    points, peer_idx, peers = theirs.ring_arrays()
    assert ring_mod.REPLICAS * len(addrs) == len(points)
    assert peers == addrs
    assert (ours.points == points).all() and (ours.peer == peer_idx).all()
    # 100k keys: by fingerprint on an xx ring (the router reads the owner
    # from the parsed fingerprint column), by the hash key's bytes on
    # upstream's.
    ids = rng.integers(0, 10**12, 100_000)
    strings = [f"bench_{int(i):013d}" for i in ids]
    if kind == "xx":
        h = native.hash_keys(strings).view(np.uint64)
    else:
        h = ring_mod.hash_rows(kind, np.frombuffer(
            "".join(strings).encode(), dtype=np.uint8).reshape(len(ids), 19))
    mine = ours.owner(h)
    step = 1 if kind == "xx" else 37          # Python's FNV is slow
    for i in range(0, len(ids), step):
        assert addrs[mine[i]] == theirs.get(strings[i]), i
    assert (ours.owner(ring_mod.hash_strings(kind, strings[:300]))
            == mine[:300]).all()
    edge = np.array([points[0], points[-1], points[-1] + np.uint64(1),
                     np.uint64(0)], dtype=np.uint64)
    assert ours.owner(edge).tolist() == [
        int(peer_idx[0]), int(peer_idx[-1]), int(peer_idx[0]),
        int(peer_idx[0])]
