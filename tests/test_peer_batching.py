"""The peer batcher on the compiled lane's forwards (PR 43).

Upstream coalesces the forwards of concurrent client RPCs to one owner into
one GetPeerRateLimits, inside `GUBER_BATCH_WAIT` or up to `GUBER_BATCH_LIMIT`
checks (peer_client.go:373-446).  `net/peer_client.py` `forward_raw` does
the same for the raw forward `runtime/fastpath.py` `_serve_routed` makes,
as bytes: spliced frames joined in arrival order under ONE forward id, the
answer handed back by count and in order (docs/cluster.md).  Held here, on
in-process clusters (`testing/cluster.py`), seeded:

(a) the benchmark's `peers4-10m.zipf99.rpc2.closed64` in small: rounds of
    64 concurrent two-check RPCs on zipfian keys by all four peers; every
    answer is `core/pymodel.py`'s per key — a key's answers a sequence
    over the rounds, the RPCs of a round a multiset, one RPC's duplicates
    in order — rows lie on owners alone, and fewer GetPeerRateLimits went
    than client RPCs had forwards;
(b) when a batch goes: forwards that meet in a window share one
    GetPeerRateLimits (`batched`, `flush_wait`), the limit sends one
    before its window ends (`flush_limit`), forwards a window apart go
    one each, and a NO_BATCHING check goes at once;
(c) a coalesced forward whose owner stalls under
    `deploy/chaos/owner_stall.json` is asked again under its id and its
    hits are spent once;
(d) a client RPC whose deadline ends inside the window — told by its
    `deadline`, or cancelled by the gRPC server — reads its own error and
    spends nothing, and the RPCs it shared the window with are served.
"""
from __future__ import annotations

import asyncio
import json
import time
from collections import Counter, defaultdict
from dataclasses import replace
from pathlib import Path

import grpc
import grpc.aio
import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.core.config import (
    DaemonConfig,
    DeviceConfig,
    fast_test_behaviors,
)
from gubernator_tpu.core.pymodel import PyRateLimiter
from gubernator_tpu.core.types import Behavior, RateLimitReq
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.testing.chaos import ChaosInjector, ChaosPlan
from gubernator_tpu.testing.cluster import Cluster

GET_RATE_LIMITS = "/pb.gubernator.V1/GetRateLimits"
DAYS_30 = 2_592_000_000
LIMIT = 20
FORWARD_LIMIT_S = 0.4
WINDOW_S = 0.05         # long enough for a loaded sandbox's loop to meet in
BATCH_KEYS = ("batched", "flush_wait", "flush_limit")

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def _req(name: str, k: int, hits: int = 1, behavior: int = 0):
    return pb.RateLimitReq(
        name=name, unique_key=f"k{k}", hits=hits, limit=LIMIT,
        duration=DAYS_30, algorithm=k & 1, behavior=behavior,
    )


def _want(oracle, r):
    w = oracle.get_rate_limit(RateLimitReq(
        name=r.name, unique_key=r.unique_key, hits=r.hits, limit=r.limit,
        duration=r.duration, algorithm=r.algorithm,
    ))
    return (w.error, int(w.status), w.limit, w.remaining)


def _got(r):
    return (r.error, int(r.status), r.limit, r.remaining)


def _peer_rows(d) -> dict:
    return d.metrics.stages.debug_vars()["peer"]


def _hop(d) -> dict:
    rows = _peer_rows(d)
    return dict(rows["forward"], waits=rows["batch_wait"]["count"])


def _grown(d, before: dict) -> dict:
    return {k: v - before[k] for k, v in _hop(d).items()}


async def _rpc(stub, reqs, timeout=20.0):
    raw = await stub(
        pb.GetRateLimitsReq(requests=reqs).SerializeToString(),
        timeout=timeout,
    )
    return pb.GetRateLimitsResp.FromString(raw).responses


# -- (a) the cell in small ---------------------------------------------------

@pytest.fixture(scope="module")
def ring4():
    c = Cluster.start(4, device=DeviceConfig(
        num_slots=1 << 14, ways=8, batch_size=512,
    ), behaviors=replace(
        fast_test_behaviors(), batch_timeout_s=30.0, batch_wait_s=WINDOW_S))
    try:
        yield c
    finally:
        c.stop()


@pytest.mark.parametrize("seed", [43, 3900200043])
def test_64_concurrent_two_check_rpcs_are_the_references(ring4, seed):
    c = ring4
    name = f"cell{seed}"
    universe, rounds, in_flight = 400, 5, 64
    rng = np.random.default_rng(seed)
    # YCSB's zipfian 0.99, scrambled: rank r has weight 1 / r^0.99 and the
    # ranks are dealt over the universe by a seeded permutation.
    w = 1.0 / np.arange(1, universe + 1) ** 0.99
    ids = rng.permutation(universe)
    plan = [
        [(i % 4, ids[rng.choice(universe, 2, p=w / w.sum())].tolist())
         for i in range(in_flight)]
        for _ in range(rounds)
    ]
    owner_of = {
        k: c.daemons.index(c.owner_daemon_of(f"{name}_k{k}"))
        for rnd in plan for _, keys in rnd for k in keys
    }
    before = [_hop(d) for d in c.daemons]
    routes0 = [_peer_rows(d)["route"]["count"] for d in c.daemons]

    async def drive():
        chans = [grpc.aio.insecure_channel(d.grpc_address)
                 for d in c.daemons]
        stubs = [ch.unary_unary(GET_RATE_LIMITS) for ch in chans]
        try:
            return [
                await asyncio.gather(*(
                    _rpc(stubs[e], [_req(name, k) for k in keys])
                    for e, keys in rnd))
                for rnd in plan
            ]
        finally:
            for ch in chans:
                await ch.close()

    answers = c.run(drive(), timeout=300)

    oracle = PyRateLimiter()
    over = 0
    for rnd, got in zip(plan, answers):
        seen = defaultdict(list)
        for (entry, keys), resps in zip(rnd, got):
            assert len(resps) == len(keys) == 2
            last = {}
            for k, r in zip(keys, resps):
                assert r.error == "" and r.limit == LIMIT, (k, r)
                assert r.metadata.get("owner", "") == (
                    "" if owner_of[k] == entry
                    else c.daemons[owner_of[k]].grpc_address), (k, entry)
                # One RPC's duplicates in order.
                assert r.remaining <= last.get(k, LIMIT), (k, entry)
                if k in last and r.status == 0:
                    assert r.remaining == last[k] - 1, (k, entry)
                last[k] = r.remaining
                seen[k].append((int(r.status), r.remaining))
        # The round's RPCs were in flight together: a multiset a key; the
        # rounds follow each other: the oracle carries on.
        for k, got_k in seen.items():
            want = [_want(oracle, _req(name, k))[1::2] for _ in got_k]
            assert Counter(got_k) == Counter(want), k
            over += sum(st for st, _ in got_k)
    assert over > 0                 # the hottest keys ran into their limit

    # A key is answered by its owner alone.
    for k, i in owner_of.items():
        for j, d in enumerate(c.daemons):
            item = d.service.backend.get_cache_item(f"{name}_k{k}")
            assert (item is not None) == (i == j), (k, j)

    # The hop: as many client forwards as the ring says, and fewer
    # GetPeerRateLimits, for forwards of one round met in their window.
    for i, d in enumerate(c.daemons):
        mine = [keys for rnd in plan for e, keys in rnd if e == i]
        g = _grown(d, before[i])
        assert g["waits"] == sum(
            len({owner_of[k] for k in keys} - {i}) for keys in mine)
        assert g["checks"] == sum(
            owner_of[k] != i for keys in mine for k in keys)
        assert _peer_rows(d)["route"]["count"] - routes0[i] == len(mine)
        assert g["count"] == g["flush_wait"] + g["flush_limit"]
        assert g["count"] < g["waits"] and g["batched"] > 0, g
        assert g["flush_limit"] == 0        # 32 checks a round and peer
        assert (g["timeouts"], g["refused"], g["retried"]) == (0, 0, 0)


# -- (b), (c), (d): two daemons, a chaos plane, a forward limit ---------------

@pytest.fixture(scope="module")
def pair():
    c = Cluster.start(
        2, conf_template=DaemonConfig(chaos=ChaosInjector()),
        behaviors=replace(
            fast_test_behaviors(), batch_timeout_s=FORWARD_LIMIT_S,
            batch_wait_s=WINDOW_S),
    )
    try:
        yield c
    finally:
        c.stop()


def _owned_by(c, d, name: str, n: int, start: int = 0):
    out = []
    k = start
    while len(out) < n:
        if c.owner_daemon_of(f"{name}_k{k}") is d:
            out.append(k)
        k += 1
    return out


def _warm(c, name: str):
    """The entry daemon's client of the owner, its channel READY and the
    owner's word that it applies an id once taken."""
    entry, owner = c.daemons
    (k,) = _owned_by(c, owner, name, 1, start=10_000)

    async def go():
        ch = grpc.aio.insecure_channel(entry.grpc_address)
        try:
            return await _rpc(ch.unary_unary(GET_RATE_LIMITS),
                              [_req(name, k, hits=0)])
        finally:
            await ch.close()

    assert [_got(r) for r in c.run(go())] == [("", 0, LIMIT, LIMIT)]
    peer = entry.service.get_peer(f"{name}_k{k}")
    assert peer.info().grpc_address == owner.grpc_address
    assert peer._applies_once
    return peer


@pytest.mark.parametrize("case", ["window", "limit", "apart", "no_batching"])
def test_when_a_batch_goes(pair, case, monkeypatch):
    """Six two-check client RPCs whose checks the other daemon owns."""
    c = pair
    entry, owner = c.daemons
    name = f"goes-{case}"
    peer = _warm(c, name)
    if case == "limit":
        # Upstream's rule: the batch goes when the limit is pending.
        monkeypatch.setattr(
            peer, "behavior", replace(peer.behavior, batch_limit=8))
    keys = _owned_by(c, owner, name, 12)
    rpcs = [keys[i:i + 2] for i in range(0, 12, 2)]
    behavior = int(Behavior.NO_BATCHING) if case == "no_batching" else 0
    e0, handlers0 = _hop(entry), owner.metrics.stages.debug_vars()[
        "wire"]["handler"]["count"]

    async def drive():
        ch = grpc.aio.insecure_channel(entry.grpc_address)
        stub = ch.unary_unary(GET_RATE_LIMITS)
        try:
            if case != "apart":
                return await asyncio.gather(*(
                    _rpc(stub, [_req(name, k, behavior=behavior) for k in ks])
                    for ks in rpcs))
            out = []
            for ks in rpcs:
                out.append(await _rpc(stub, [_req(name, k) for k in ks]))
                await asyncio.sleep(WINDOW_S)   # a window apart
            return out
        finally:
            await ch.close()

    t0 = time.monotonic()
    answers = c.run(drive())
    took = time.monotonic() - t0
    for resps in answers:
        assert [_got(r) for r in resps] == [("", 0, LIMIT, LIMIT - 1)] * 2
    g = _grown(entry, e0)
    sent = owner.metrics.stages.debug_vars()[
        "wire"]["handler"]["count"] - handlers0
    assert g["waits"] == 6 and g["checks"] == 12 and g["count"] == sent
    batch = {k: g[k] for k in BATCH_KEYS}
    if case == "window":
        # Met in the window: fewer GetPeerRateLimits than client RPCs.
        assert g["count"] < 6 and g["batched"] >= 2, g
        assert g["flush_limit"] == 0 and g["flush_wait"] == g["count"]
        assert took >= WINDOW_S
    elif case == "limit":
        # 8 checks pending send a batch before its window ends: the six
        # RPCs leave as 4 + (the rest) or finer, never more than 8 a batch.
        assert g["flush_limit"] >= 1 and g["count"] >= 2, g
        assert g["count"] == g["flush_limit"] + g["flush_wait"]
    elif case == "apart":
        # Sent one at a time, a window apart: exactly as many.
        assert batch == dict(batched=0, flush_wait=6, flush_limit=0), g
        assert g["count"] == 6
    else:
        # NO_BATCHING goes round the window (peer_client.go:168-192).
        assert batch == dict(batched=0, flush_wait=0, flush_limit=0), g
        assert g["count"] == 6
    assert (g["timeouts"], g["refused"], g["retried"]) == (0, 0, 0)


def test_the_limit_bounds_a_batch(pair, monkeypatch):
    """No GetPeerRateLimits carries more than GUBER_BATCH_LIMIT checks,
    and a client RPC's checks are never split over two."""
    c = pair
    entry, owner = c.daemons
    name = "bound"
    peer = _warm(c, name)
    monkeypatch.setattr(
        peer, "behavior", replace(peer.behavior, batch_limit=5))
    sizes = []
    ask = peer._ask_raw

    async def counting(payload, fid, members):
        sizes.append([fw.n for fw in members])
        return await ask(payload, fid, members)

    monkeypatch.setattr(peer, "_ask_raw", counting)
    keys = _owned_by(c, owner, name, 14)
    rpcs = [keys[0:2], keys[2:4], keys[4:7], keys[7:8], keys[8:14]]

    async def drive():
        ch = grpc.aio.insecure_channel(entry.grpc_address)
        stub = ch.unary_unary(GET_RATE_LIMITS)
        try:
            return await asyncio.gather(*(
                _rpc(stub, [_req(name, k) for k in ks]) for ks in rpcs))
        finally:
            await ch.close()

    for ks, resps in zip(rpcs, c.run(drive())):
        assert [_got(r) for r in resps] == [
            ("", 0, LIMIT, LIMIT - 1)] * len(ks)
    assert sorted(n for b in sizes for n in b) == [1, 2, 2, 3, 6]
    # Only a client RPC that alone exceeds the limit goes over it.
    assert all(sum(b) <= 5 or len(b) == 1 for b in sizes), sizes


def _retarget(plan_path: Path, addr: str) -> ChaosPlan:
    """deploy/chaos/owner_stall.json against this cluster's owner: its
    rules name the benchmark's fixed port.  The two stalls of the wire
    check, one before the handler and one after it applied; the draws are
    seeded a call, so which forwards they hold follows from the file."""
    d = json.loads(plan_path.read_text())
    for r in d["rules"]:
        assert r["target"] == "127.0.0.1:21053"
        r["target"] = addr
    return ChaosPlan.from_dict(d)


def test_a_coalesced_forward_under_the_owner_stall_plan_is_spent_once(pair):
    c = pair
    entry, owner = c.daemons
    inj = entry.chaos
    name = "stall"
    _warm(c, name)
    keys = _owned_by(c, owner, name, 6)
    mine = _owned_by(c, entry, name, 2)
    rng = np.random.default_rng(43)
    oracle = PyRateLimiter()
    plan = _retarget(Path(__file__).resolve().parent.parent / (
        "deploy/chaos/owner_stall.json"), owner.grpc_address)
    assert all(r.delay_s > FORWARD_LIMIT_S for r in plan.rules)
    e0, o0 = _hop(entry), _hop(owner)
    stalled_coalesced = 0

    async def round_(reqs_of):
        ch = grpc.aio.insecure_channel(entry.grpc_address)
        stub = ch.unary_unary(GET_RATE_LIMITS)
        try:
            return await asyncio.gather(*(_rpc(stub, r) for r in reqs_of))
        finally:
            await ch.close()

    def check(reqs_of, got) -> None:
        """RPCs in flight together: a key's answers are the reference's
        next ones, as a multiset."""
        seen = defaultdict(list)
        for reqs, resps in zip(reqs_of, got):
            assert len(reqs) == len(resps)
            for rq, rs in zip(reqs, resps):
                assert rs.error == "", rs
                seen[rq.unique_key].append((rq, _got(rs)))
        for pairs in seen.values():
            want = [_want(oracle, rq) for rq, _ in pairs]
            assert Counter(g for _, g in pairs) == Counter(want)

    inj.reset(plan)
    try:
        for _ in range(60):
            # Eight client RPCs in flight: a check the owner holds (a hot
            # key among them, which runs into its limit) and one of the
            # entry's own.
            reqs_of = [[
                _req(name, int(rng.choice(keys, p=[.5, .1, .1, .1, .1, .1]))),
                _req(name, int(rng.choice(mine))),
            ] for _ in range(8)]
            r0 = _hop(entry)
            check(reqs_of, c.run(round_(reqs_of), timeout=60))
            g = _grown(entry, r0)
            if g["reasked"]:
                assert g["timeouts"] == g["reasked"]
                stalled_coalesced += g["batched"] >= 2 and g["count"] < 8
            if (inj.injected["server_before"] >= 1
                    and inj.injected["server_after"] >= 1):
                break
        assert inj.injected["server_before"] >= 1
        assert inj.injected["server_after"] >= 1
    finally:
        inj.reset(ChaosPlan())
    g, go = _grown(entry, e0), _grown(owner, o0)
    assert g["reasked"] >= 2 and g["refused"] == 0 and g["retried"] == 0
    assert stalled_coalesced >= 1       # a batch of several was asked again
    assert 1 <= go["joined"] <= g["reasked"]
    # Each hit spent exactly once: what a read finds now.
    reads = [[_req(name, k, hits=0) for k in keys + mine]]
    check(reads, c.run(round_(reads), timeout=60))


@pytest.mark.parametrize("how", ["deadline", "cancelled"])
def test_a_deadline_that_ends_in_the_window_spends_nothing(
        pair, how, monkeypatch):
    """Two client RPCs share a window of 0.5 s; the deadline of one ends
    0.1 s into it.  Told by `deadline` (a caller of `check_raw`): its
    checks read their own error.  Over gRPC: the server cancels its
    handler and the client reads DEADLINE_EXCEEDED.  Either way it is
    taken out of the batch before the send, the other is served, and a
    read finds only the other's hits spent."""
    c = pair
    entry, owner = c.daemons
    name = f"ends-{how}"
    peer = _warm(c, name)
    monkeypatch.setattr(
        peer, "behavior", replace(peer.behavior, batch_wait_s=0.5))
    gone, stays = _owned_by(c, owner, name, 2), _owned_by(
        c, owner, name, 2, start=500)
    e0 = _hop(entry)

    async def drive():
        ch = grpc.aio.insecure_channel(entry.grpc_address)
        stub = ch.unary_unary(GET_RATE_LIMITS)
        try:
            other = asyncio.ensure_future(
                _rpc(stub, [_req(name, k) for k in stays]))
            payload = pb.GetRateLimitsReq(
                requests=[_req(name, k) for k in gone]).SerializeToString()
            if how == "deadline":
                raw = await entry.fastpath.check_raw(
                    payload, peer_rpc=False,
                    deadline=time.monotonic() + 0.1)
                mine = pb.GetRateLimitsResp.FromString(raw).responses
            else:
                with pytest.raises(grpc.aio.AioRpcError) as e:
                    await stub(payload, timeout=0.1)
                assert e.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
                mine = None
            return mine, await other
        finally:
            await ch.close()

    mine, others = c.run(drive())
    assert [_got(r) for r in others] == [("", 0, LIMIT, LIMIT - 1)] * 2
    if how == "deadline":
        for r in mine:
            assert "Error while fetching rate limit from peer" in r.error
            assert "deadline ended before the forward" in r.error
    g = _grown(entry, e0)
    # One GetPeerRateLimits, of the other RPC's two checks alone.
    assert (g["count"], g["flush_wait"], g["batched"]) == (1, 1, 0), g
    assert g["waits"] == 2
    assert g["refused"] == (1 if how == "deadline" else 0)

    async def read():
        ch = grpc.aio.insecure_channel(entry.grpc_address)
        try:
            return await _rpc(ch.unary_unary(GET_RATE_LIMITS), [
                _req(name, k, hits=0) for k in gone + stays])
        finally:
            await ch.close()

    assert [r.remaining for r in c.run(read())] == [
        LIMIT, LIMIT, LIMIT - 1, LIMIT - 1]
