"""Multi-daemon cluster integration tests.

The port of the reference's workhorse tier (functional_test.go:42-1200):
a real in-process cluster — 6 daemons in the default DC plus 2 in
"datacenter-1" — exercised over real gRPC through the client SDK, with
frozen/advanceable clock where bucket timing matters.
"""
from __future__ import annotations

import json
import time
import urllib.request
from dataclasses import replace

import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import fast_test_behaviors
from gubernator_tpu.core.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
)
from gubernator_tpu.net.grpc_api import PeersV1Stub, req_to_pb
from gubernator_tpu.proto import peers_pb2
from gubernator_tpu.testing import Cluster


@pytest.fixture(scope="module")
def cluster():
    c = Cluster.start_with([""] * 6 + ["datacenter-1"] * 2)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def client(cluster):
    cl = V1Client(cluster.addresses()[0])
    yield cl
    cl.close()


def until_pass(fn, timeout=10.0, interval=0.1):
    """Poll an assertion until it passes (holster testutil.UntilPass,
    functional_test.go:843-867)."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            return fn()
        except AssertionError:
            if time.monotonic() > deadline:
                raise
            time.sleep(interval)


def test_over_the_limit(cluster, client):
    """functional_test.go:64-111."""
    for i, want in [(0, Status.UNDER_LIMIT), (1, Status.UNDER_LIMIT),
                    (2, Status.OVER_LIMIT)]:
        r = client.get_rate_limits([
            RateLimitReq(
                name="test_over_limit", unique_key="account:1234",
                algorithm=Algorithm.TOKEN_BUCKET, duration=60_000,
                limit=2, hits=1,
            )
        ])[0]
        assert r.error == ""
        assert r.status == want, f"hit {i}"
        assert r.limit == 2
        assert r.remaining == max(0, 1 - i)


def test_token_bucket_expiry(cluster, client, frozen_clock):
    """Bucket resets after duration (functional_test.go:159-218)."""
    key = "token_expiry:1"
    req = RateLimitReq(
        name="test_token_bucket", unique_key=key, duration=5_000,
        limit=2, hits=1,
    )
    r = client.get_rate_limits([req])[0]
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 1)
    r = client.get_rate_limits([req])[0]
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 0)
    frozen_clock.advance(6_000)
    r = client.get_rate_limits([req])[0]
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 1)


def test_token_bucket_negative_hits(cluster, client):
    """Negative hits add tokens back (functional_test.go:295-365)."""
    req = RateLimitReq(
        name="test_token_negative", unique_key="k", duration=60_000,
        limit=3, hits=2,
    )
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 1
    req.hits = -1
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 2
    req.hits = 0
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 2


def test_leaky_bucket(cluster, client, frozen_clock):
    """Leak rate = duration/limit per token (functional_test.go:367-500)."""
    req = RateLimitReq(
        name="test_leaky", unique_key="acct:9", duration=10_000, limit=10,
        hits=5, algorithm=Algorithm.LEAKY_BUCKET,
    )
    r = client.get_rate_limits([req])[0]
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 5)
    # One token leaks back per duration/limit = 1000ms.
    frozen_clock.advance(2_000)
    req.hits = 0
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 7
    req.hits = 7
    r = client.get_rate_limits([req])[0]
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 0)
    req.hits = 1
    r = client.get_rate_limits([req])[0]
    assert r.status == Status.OVER_LIMIT


def test_change_limit_mid_flight(cluster, client):
    """Limit changes adjust remaining by the delta
    (functional_test.go:870-962, algorithms.go:112-119)."""
    req = RateLimitReq(
        name="test_change_limit", unique_key="u", duration=60_000,
        limit=10, hits=3,
    )
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 7
    req.limit = 20
    req.hits = 0
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 17
    assert r.limit == 20


def test_reset_remaining(cluster, client):
    """RESET_REMAINING refills the bucket (functional_test.go:965-1035)."""
    req = RateLimitReq(
        name="test_reset_remaining", unique_key="u", duration=60_000,
        limit=5, hits=5,
    )
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 0
    req.behavior = Behavior.RESET_REMAINING
    req.hits = 0
    r = client.get_rate_limits([req])[0]
    assert r.remaining == 5


def test_missing_fields(cluster, client):
    """Per-request validation errors (functional_test.go:737-798)."""
    cases = [
        (RateLimitReq(name="", unique_key="k", limit=1, hits=1,
                      duration=1000),
         "field 'namespace' cannot be empty"),
        (RateLimitReq(name="n", unique_key="", limit=1, hits=1,
                      duration=1000),
         "field 'unique_key' cannot be empty"),
    ]
    for req, want in cases:
        r = client.get_rate_limits([req])[0]
        assert r.error == want


def test_cross_peer_forwarding(cluster, client):
    """Keys owned by other peers are forwarded and answer identically
    (TestMultipleAsync, functional_test.go:113-157)."""
    reqs = [
        RateLimitReq(
            name="test_async", unique_key=f"k{i}", duration=60_000,
            limit=10, hits=1,
        )
        for i in range(30)
    ]
    resps = client.get_rate_limits(reqs)
    owners = set()
    for r in resps:
        assert r.error == ""
        assert r.remaining == 9
        owners.add(r.metadata.get("owner", "local"))
    assert len(owners) > 1, "expected keys spread over multiple peers"


def test_peer_rate_limits_order_preserved(cluster):
    """Peer batches answer in request order for sizes 1..1000
    (TestGetPeerRateLimits, functional_test.go:1175-1210)."""
    import grpc

    addr = cluster.addresses()[1]
    ch = grpc.insecure_channel(addr)
    stub = PeersV1Stub(ch)
    for n in (1, 5, 100, 1000):
        req = peers_pb2.GetPeerRateLimitsReq(
            requests=[
                req_to_pb(RateLimitReq(
                    name="test_order", unique_key=f"o{n}_{i}",
                    duration=60_000, limit=1_000_000, hits=i,
                ))
                for i in range(n)
            ]
        )
        resp = stub.GetPeerRateLimits(req)
        assert len(resp.rate_limits) == n
        for i, rl in enumerate(resp.rate_limits):
            assert rl.remaining == 1_000_000 - i, f"n={n} idx={i}"
    ch.close()


def test_global_rate_limits(cluster):
    """GLOBAL: non-owner answers locally, reports the owner, hits reach
    the owner async, statuses broadcast back
    (functional_test.go:800-867)."""
    key = "global:acct:77"
    req = RateLimitReq(
        name="test_global", unique_key=key, duration=60_000, limit=100,
        hits=1, behavior=Behavior.GLOBAL,
    )
    owner = cluster.owner_daemon_of(f"test_global_{key}")
    non_owners = [
        d for d in cluster.daemons
        if d is not owner and d.conf.data_center == ""
    ]
    d = non_owners[0]
    cl = V1Client(d.grpc_address)
    r = cl.get_rate_limits([req])[0]
    assert r.error == ""
    assert r.metadata.get("owner") == owner.grpc_address

    # Eventual consistency: the hit must reach the owner and the owner must
    # broadcast a status (asserted via manager counters, the metrics-scrape
    # analog of functional_test.go:843-867).
    def check():
        assert d.service.global_mgr.async_sends >= 1
        assert owner.service.global_mgr.broadcasts >= 1

    until_pass(check)

    # After broadcast, other non-owners serve the authoritative status from
    # local cache.
    def check_cached():
        d2 = non_owners[1]
        cl2 = V1Client(d2.grpc_address)
        try:
            r2 = cl2.get_rate_limits([
                RateLimitReq(
                    name="test_global", unique_key=key, duration=60_000,
                    limit=100, hits=0, behavior=Behavior.GLOBAL,
                )
            ])[0]
            assert r2.error == ""
            assert r2.remaining <= 99
        finally:
            cl2.close()

    until_pass(check_cached)
    cl.close()


def test_health_check_and_restart(cluster):
    """Killing a peer surfaces errors in HealthCheck; restart recovers
    (functional_test.go:1037-1103)."""
    victim_idx = len(cluster.daemons) - 1  # a datacenter-1 daemon
    victim_addr = cluster.daemons[victim_idx].grpc_address
    cluster.kill(victim_idx)

    # Drive forwarded traffic so some peer records an error.
    cl = V1Client(cluster.addresses()[0])
    for i in range(50):
        cl.get_rate_limits([
            RateLimitReq(
                name="test_health", unique_key=f"hk{i}", duration=60_000,
                limit=10, hits=1,
            )
        ])

    def check():
        unhealthy = 0
        for d in cluster.daemons[:6]:
            h = cluster.run(d.service.health_check())
            if h.status == "unhealthy":
                unhealthy += 1
        assert unhealthy >= 1

    # The dead daemon is in datacenter-1, so local-DC forwards don't hit
    # it; poke it directly through a region peer error by checking its
    # own clients... simplest: forwards from dc-1's sibling.
    sib = cluster.daemons[6]
    for i in range(50):
        try:
            cluster.run(
                sib.service.local_picker.get_by_address(
                    victim_addr
                ).get_peer_rate_limit(
                    RateLimitReq(
                        name="x", unique_key=f"v{i}", duration=1000,
                        limit=1, hits=1,
                    )
                )
            )
        except Exception:  # noqa: BLE001 — expected: peer is dead
            pass

    def check_sib():
        h = cluster.run(sib.service.health_check())
        assert h.status == "unhealthy"
        assert "Error" in h.message

    until_pass(check_sib, timeout=15.0)

    d = cluster.restart(victim_idx)
    assert d.grpc_address == victim_addr
    r = cl.get_rate_limits([
        RateLimitReq(
            name="test_health", unique_key="after_restart",
            duration=60_000, limit=10, hits=1,
        )
    ])[0]
    assert r.error == ""
    cl.close()


def test_http_gateway_contract(cluster):
    """REST gateway speaks under_score JSON (TestGRPCGateway,
    functional_test.go:1158-1173)."""
    addr = cluster.daemon_at(0).http_address
    body = json.dumps({
        "requests": [{
            "name": "test_gateway", "unique_key": "u", "hits": 1,
            "limit": 10, "duration": 60000,
        }]
    }).encode()
    req = urllib.request.Request(
        f"http://{addr}/v1/GetRateLimits", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read())
    assert "responses" in payload
    r = payload["responses"][0]
    assert "reset_time" in r, f"expected under_score fields, got {r}"
    assert int(r["remaining"]) == 9

    with urllib.request.urlopen(
        f"http://{addr}/v1/HealthCheck", timeout=10
    ) as resp:
        h = json.loads(resp.read())
    assert h["status"] == "healthy"
    assert h["peer_count"] == 8

    with urllib.request.urlopen(
        f"http://{addr}/metrics", timeout=10
    ) as resp:
        text = resp.read().decode()
    assert "gubernator_check_counter" in text
    assert "gubernator_tpu_device_step_duration" in text


def test_grpc_stats_cover_all_methods(cluster):
    """The stats interceptor records count + duration for EVERY RPC method
    — peers side included, where all forwarded traffic rides (the reference
    StatsHandler tags each RPC uniformly, grpc_stats.go:98-131)."""
    import grpc

    d = cluster.daemon_at(2)
    cl = V1Client(d.grpc_address)
    cl.get_rate_limits([
        RateLimitReq(
            name="test_stats", unique_key="s", hits=1, limit=10,
            duration=60_000,
        )
    ])
    cl.health_check()
    cl.close()
    ch = grpc.insecure_channel(d.grpc_address)
    stub = PeersV1Stub(ch)
    stub.GetPeerRateLimits(peers_pb2.GetPeerRateLimitsReq(
        requests=[req_to_pb(RateLimitReq(
            name="test_stats", unique_key="p", hits=1, limit=10,
            duration=60_000,
        ))]
    ))
    stub.UpdatePeerGlobals(peers_pb2.UpdatePeerGlobalsReq())
    ch.close()

    with urllib.request.urlopen(
        f"http://{d.http_address}/metrics", timeout=10
    ) as resp:
        text = resp.read().decode()
    assert "gubernator_grpc_request_counts" in text
    assert "gubernator_grpc_request_duration" in text
    for method in (
        "/pb.gubernator.V1/GetRateLimits",
        "/pb.gubernator.V1/HealthCheck",
        "/pb.gubernator.PeersV1/GetPeerRateLimits",
        "/pb.gubernator.PeersV1/UpdatePeerGlobals",
    ):
        assert f'method="{method}"' in text, method


def test_multi_region_hits_propagate(cluster):
    """MULTI_REGION hits flush to the owner in the other region (the tier
    the reference leaves stubbed, multiregion.go:96-98 — implemented
    here)."""
    key = "mr:acct:5"
    req = RateLimitReq(
        name="test_multiregion", unique_key=key, duration=60_000,
        limit=100, hits=2, behavior=Behavior.MULTI_REGION,
    )
    d = cluster.owner_daemon_of(f"test_multiregion_{key}")
    cl = V1Client(d.grpc_address)
    r = cl.get_rate_limits([req])[0]
    assert r.error == ""
    assert r.remaining == 98

    # Generous window: this runs right after the kill/restart test, so the
    # region peer may still be reconnecting.  Keep live traffic flowing —
    # if an early flush window dropped its hits against the reconnecting
    # peer, fresh hits re-open the window (real deployments are not
    # single-shot either).
    def check():
        cl.get_rate_limits([req])
        assert d.service.multi_region_mgr.region_sends >= 1

    until_pass(check, timeout=30.0, interval=0.5)
    # The datacenter-1 owner of the key saw the forwarded hits.
    dc1 = [dd for dd in cluster.daemons if dd.conf.data_center]
    def check_remote():
        total = sum(dd.service.backend.checks for dd in dc1)
        assert total >= 1

    until_pass(check_remote, timeout=30.0)
    cl.close()


def test_membership_change_under_fastlane_traffic():
    """Live membership change while routed fast-lane traffic flows
    (the SetPeers contract, gubernator.go:634-717): a peer JOINS and a
    peer is REMOVED mid-traffic with zero client-visible errors, removed
    peers drain in-flight batches (set_peers shuts their clients down
    gracefully), the ownership-retry path engages deterministically when
    an owner dies before the membership update lands, and the cluster-
    wide hit accounting balances exactly — no request lost, none double
    counted."""
    import asyncio

    from gubernator_tpu.client import AsyncV1Client
    from gubernator_tpu.core.types import PeerInfo
    from gubernator_tpu.daemon import Daemon

    # While a handoff is in flight the compiled lane steps aside and the
    # OBJECT path forwards (service._forward, the batched peer RPC), which
    # is not asked again when it times out — only the router's raw forward
    # is (tests/test_peer_hop.py, docs/cluster.md).  Under six workers this
    # sandbox's loop stalls for most of the 2 s the fast test windows allow
    # a forward, so this test of membership gives its cluster a time limit
    # a stall cannot reach.
    c = Cluster.start(2, behaviors=replace(
        fast_test_behaviors(), batch_timeout_s=30.0
    ))
    try:
        keys = [f"mv{i}" for i in range(16)]
        sent = {k: 0 for k in keys}
        LIMIT = 100_000

        async def scenario():
            cl = AsyncV1Client(c.addresses()[0])

            async def rounds(n, workers=4):
                async def one(w):
                    for _ in range(n):
                        rs = await cl.get_rate_limits([
                            RateLimitReq(
                                name="member", unique_key=k, hits=1,
                                limit=LIMIT, duration=3_600_000,
                            )
                            for k in keys
                        ])
                        assert all(r.error == "" for r in rs), rs
                        for k in keys:
                            sent[k] += 1

                await asyncio.gather(*(one(w) for w in range(workers)))

            async def reshard_quiesce():
                # Live resharding (docs/resharding.md): a remap streams
                # moved rows to their new owners, and hits admitted
                # through the bounded handoff shadow reconcile into the
                # authoritative rows at CUTOVER — the exact accounting
                # below must wait for every handoff window to close.
                for _ in range(400):
                    if all(
                        d.service.reshard is None
                        or (
                            not d.service.reshard._inbound
                            and d.service.reshard.handoffs_started
                            == d.service.reshard.handoffs_completed
                            + d.service.reshard.handoffs_aborted
                        )
                        for d in c.daemons
                    ):
                        return
                    await asyncio.sleep(0.05)
                raise AssertionError("resharding never quiesced")

            # Phase 1: steady 2-node traffic.
            await rounds(5)

            # Phase 2: JOIN a third daemon while traffic flows.
            conf = type(c.daemons[0].conf)(
                grpc_listen_address="127.0.0.1:0",
                http_listen_address="127.0.0.1:0",
                behaviors=c.daemons[0].conf.behaviors,
                device=c.daemons[0].conf.device,
            )
            traffic = asyncio.ensure_future(rounds(12))
            await asyncio.sleep(0.05)
            d3 = Daemon(conf)
            await d3.start()
            d3.conf.advertise_address = d3.grpc_address
            c.daemons.append(d3)
            await c._push_peers()
            await traffic
            # Some keys moved to the new daemon and it served them.
            assert d3.service.backend.checks > 0

            # Phase 3: REMOVE daemon 1 (graceful) while traffic flows —
            # remaining daemons swap it out of their rings and drain its
            # client (in-flight forwards complete; zero errors above).
            victim = c.daemons[1]
            keep = [c.daemons[0], d3]
            peers = [
                PeerInfo(grpc_address=d.grpc_address,
                         http_address=d.http_address)
                for d in keep
            ]
            traffic = asyncio.ensure_future(rounds(12))
            await asyncio.sleep(0.05)
            for d in keep:
                await d.set_peers(peers)
            await traffic
            await reshard_quiesce()

            # Accounting BEFORE closing the victim: every hit landed in
            # exactly one bucket somewhere.  Ownership moved twice: the
            # JOIN migrated moved rows to d3 (handoff shadow burns
            # reconciled at cutover — reshard_quiesce above); the
            # victim's removal re-homes its arcs without migration (it
            # never observes the remap), so its partial buckets stay
            # where they are and the sum still balances.
            for k in keys:
                total = 0
                for d in c.daemons:
                    it = d.service.backend.get_cache_item(f"member_{k}")
                    if it is not None:
                        total += LIMIT - int(it.remaining)
                assert total == sent[k], (k, total, sent[k])

            # Phase 4: deterministic ownership-retry — kill an OWNER
            # before the membership update lands; the in-flight forward
            # gets NotReady, backs off, re-resolves against the updated
            # ring, and succeeds (service._forward, ASYNC_RETRIES).
            target = None
            for k in keys:
                p = c.daemons[0].service.get_peer(f"member_{k}")
                if p.info().grpc_address == d3.grpc_address:
                    target = k
                    break
            assert target is not None
            retries0 = _retry_count(c.daemons[0], "member")
            await d3.close()

            async def late_update():
                await asyncio.sleep(0.04)
                only = [PeerInfo(grpc_address=c.daemons[0].grpc_address,
                                 http_address=c.daemons[0].http_address)]
                await c.daemons[0].set_peers(only)

            upd = asyncio.ensure_future(late_update())
            rs = await cl.get_rate_limits([
                RateLimitReq(name="member", unique_key=target, hits=1,
                             limit=LIMIT, duration=3_600_000)
            ])
            await upd
            assert rs[0].error == "", rs[0].error
            assert _retry_count(c.daemons[0], "member") > retries0
            await cl.close()

        def _retry_count(d, name):
            m = d.service.metrics.asyncrequest_retries.labels(name)
            return m._value.get()

        c.run(scenario(), timeout=120.0)
    finally:
        c.stop()
