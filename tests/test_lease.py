"""Client-side admission leases (ISSUE 10; docs/leases.md).

Unit tier: knob validation, grant/refusal mechanics, expiry sweeps,
renewal piggyback — on a bare Service with a frozen clock.

Cluster tier: the over-admission bound proven EXACTLY against the
closed-form model under concurrent leased clients + direct traffic,
ownership routing across daemons, and reconvergence of the owner's
authoritative row after reconcile.

Client tier: zero-RPC steady state, transparent degrade on refusal,
FastV1Client wire parity, and the V1Client channel-hardening
regressions (default deadline, tuned channel options).
"""
from __future__ import annotations

import asyncio
import time

import pytest

from gubernator_tpu.client import (
    DEFAULT_CHANNEL_OPTIONS,
    DEFAULT_RPC_TIMEOUT_S,
    AsyncV1Client,
    FastV1Client,
    LeasedClient,
    V1Client,
    channel_options,
)
from gubernator_tpu.core.config import (
    Config,
    DaemonConfig,
    DeviceConfig,
    LeaseConfig,
    lease_config_from_env,
)
from gubernator_tpu.core.types import (
    Behavior,
    RateLimitReq,
    ReconcileItem,
    Status,
)
from gubernator_tpu.runtime.lease import LEASE_SUFFIX
from gubernator_tpu.runtime.service import Service
from gubernator_tpu.testing.cluster import TEST_DEVICE, Cluster

LIMIT = 100
DURATION = 60_000


def until_pass(fn, timeout=20.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while True:
        try:
            return fn()
        except AssertionError:
            if time.monotonic() > deadline:
                raise
            time.sleep(interval)


def _req(key="k", name="lease", hits=1, limit=LIMIT, **kw) -> RateLimitReq:
    return RateLimitReq(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=DURATION, **kw,
    )


# ---------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------

def test_lease_config_validation():
    with pytest.raises(ValueError, match="fraction"):
        LeaseConfig(fraction=0.0)
    with pytest.raises(ValueError, match="fraction"):
        LeaseConfig(fraction=1.5)
    with pytest.raises(ValueError, match="max_holders"):
        LeaseConfig(max_holders=0)
    with pytest.raises(ValueError, match="low_water"):
        LeaseConfig(low_water=1.0)
    # TTL below the reconcile cadence means grants lapse between
    # reconciles — rejected, not silently degraded.
    with pytest.raises(ValueError, match="reconcile"):
        LeaseConfig(ttl_ms=100, reconcile_ms=500)
    # Boundary: ttl == reconcile is allowed.
    LeaseConfig(ttl_ms=500, reconcile_ms=500)


def test_lease_env_parse_names_env_surface(monkeypatch):
    monkeypatch.setenv("GUBER_LEASE_FRACTION", "1.7")
    with pytest.raises(ValueError, match="GUBER_LEASE_FRACTION"):
        lease_config_from_env()
    monkeypatch.setenv("GUBER_LEASE_FRACTION", "0.5")
    monkeypatch.setenv("GUBER_LEASE_TTL", "100ms")
    monkeypatch.setenv("GUBER_LEASE_RECONCILE", "1s")
    with pytest.raises(ValueError, match="GUBER_LEASE_TTL"):
        lease_config_from_env()
    monkeypatch.setenv("GUBER_LEASE_TTL", "5s")
    monkeypatch.setenv("GUBER_LEASE_MAX_HOLDERS", "3")
    cfg = lease_config_from_env()
    assert cfg.fraction == 0.5
    assert cfg.ttl_ms == 5000
    assert cfg.reconcile_ms == 1000
    assert cfg.max_holders == 3


# ---------------------------------------------------------------------
# unit tier: LeaseManager on a bare Service
# ---------------------------------------------------------------------

@pytest.fixture
def svc(frozen_clock):
    s = Service(Config(
        device=DeviceConfig(num_slots=2048, ways=8, batch_size=64),
        lease=LeaseConfig(
            fraction=0.25, ttl_ms=2000, max_holders=2, reconcile_ms=200,
        ),
    ), clock=frozen_clock)

    async def run(coro):
        await s.start()
        try:
            return await coro
        finally:
            await s.close()

    yield s, run


def test_grant_and_refusal_mechanics(svc):
    s, run = svc

    async def scenario():
        lm = s.leases
        # allowance = 0.25 * 100 = 25; slot limit = 2 * 25 = 50/window.
        g1 = (await lm.grant("a", [_req()]))[0]
        assert g1.granted and g1.allowance == 25 and g1.limit == LIMIT
        assert g1.expires_at > 0 and g1.reset_time > 0
        g2 = (await lm.grant("b", [_req()]))[0]
        assert g2.granted
        # Third holder: refused by the concurrent-holder gate.
        g3 = (await lm.grant("c", [_req()]))[0]
        assert not g3.granted and "max concurrent holders" in g3.refusal
        # Renewal by an existing holder is allowed — but the window's
        # carve budget (max_holders x allowance) is already spent.
        g4 = (await lm.grant("a", [_req()]))[0]
        assert not g4.granted and "exhausted" in g4.refusal
        # Non-leasable shapes refuse without touching holder state.
        for bad, why in (
            (_req(behavior=Behavior.GLOBAL), "behavior"),
            (_req(behavior=Behavior.RESET_REMAINING), "behavior"),
            (_req(behavior=Behavior.DURATION_IS_GREGORIAN), "behavior"),
            (_req(limit=0), "deny-all"),
            (_req(key=""), "unique_key"),
        ):
            g = (await lm.grant("z", [bad]))[0]
            assert not g.granted and why in g.refusal, (bad, g.refusal)
        # The carve slot lives under its own key in the device table.
        item = s.backend.get_cache_item("lease_k" + LEASE_SUFFIX)
        assert item is not None
        assert item.limit == 50 and int(item.remaining) == 0
        # The REAL key's row is untouched by grants.
        assert s.backend.get_cache_item("lease_k") is None
        return True

    assert asyncio.run(run(scenario()))


def test_expiry_sweep_drops_slot_and_reconcile_applies(svc):
    s, run = svc
    clock = s.clock

    async def scenario():
        lm = s.leases
        g = (await lm.grant("a", [_req()]))[0]
        assert g.granted
        # Burned hits reconcile into the authoritative row (peer-less
        # single node: direct apply).
        await lm.reconcile("a", [ReconcileItem(request=_req(hits=7))])
        await asyncio.sleep(0.05)  # spawned apply task

        def applied():
            item = s.backend.get_cache_item("lease_k")
            assert item is not None
            assert LIMIT - int(item.remaining) == 7

        for _ in range(100):
            try:
                applied()
                break
            except AssertionError:
                await asyncio.sleep(0.02)
        applied()
        assert lm.reconciled_hits == 7
        # Expiry: advance past TTL — the sweep revokes the holder and
        # drops the carve slot (RESET_REMAINING removes the token row).
        clock.advance(3000)
        dropped = await lm.sweep_apply()
        assert dropped == 1
        assert lm.revocations == 1
        assert s.backend.get_cache_item("lease_k" + LEASE_SUFFIX) is None
        # A fresh grant carves a fresh window.
        g2 = (await lm.grant("a", [_req()]))[0]
        assert g2.granted
        return True

    assert asyncio.run(run(scenario()))


def test_release_and_renew_piggyback(svc):
    s, run = svc

    async def scenario():
        lm = s.leases
        g = (await lm.grant("a", [_req()]))[0]
        assert g.granted
        # Renew piggyback: burned hits + renew=True in ONE reconcile —
        # refused while the window budget is spent by a and b...
        gb = (await lm.grant("b", [_req()]))[0]
        assert gb.granted
        out = await lm.reconcile("a", [
            ReconcileItem(request=_req(hits=25), renew=True)
        ])
        assert not out[0].granted and "exhausted" in out[0].refusal
        # ...but release from b frees the holder count, and after the
        # window rolls the budget refills.
        out = await lm.reconcile("b", [
            ReconcileItem(request=_req(hits=0), release=True)
        ])
        assert out[0].refusal == "released"
        assert lm.revocations == 1
        # Release of the LAST holder drops the carve slot.
        out = await lm.reconcile("a", [
            ReconcileItem(request=_req(hits=0), release=True)
        ])
        assert s.backend.get_cache_item("lease_k" + LEASE_SUFFIX) is None
        return True

    assert asyncio.run(run(scenario()))


def test_grants_refused_while_shedding(svc):
    s, run = svc

    async def scenario():
        # Force the shed gate on: shed_level() reads the hotkey config
        # + flightrec clock — stub it directly (the gate contract is
        # "shedding != 0 refuses", not the clock arithmetic).
        s.shed_level = lambda: 1
        g = (await s.leases.grant("a", [_req()]))[0]
        assert not g.granted
        assert "pressure" in g.refusal
        return True

    assert asyncio.run(run(scenario()))


def test_remap_drops_unowned_grants(frozen_clock):
    """ISSUE 11 satellite: a demoted owner must stop honoring grants
    and renewals against its stale carve slot — on any remap,
    unowned-key holder records are revoked, the carve slot drops, and
    a direct grant for an unowned key refuses outright (the renewal
    path lands here)."""
    from dataclasses import replace as dc_replace

    from gubernator_tpu.core.config import ReshardConfig
    from gubernator_tpu.core.types import PeerInfo
    from gubernator_tpu.net.replicated_hash import (
        ReplicatedConsistentHash,
        xx_64,
    )

    me, other = "10.0.0.1:1051", "10.0.0.2:1051"
    # Resharding off: this test isolates the LEASE invalidation (the
    # migration path has its own suite) and must not spawn handoffs
    # toward unreachable fake peers.
    s = Service(Config(
        device=DeviceConfig(num_slots=2048, ways=8, batch_size=64),
        lease=LeaseConfig(
            fraction=0.25, ttl_ms=60_000, max_holders=2,
            reconcile_ms=200,
        ),
        reshard=ReshardConfig(enabled=False),
    ), clock=frozen_clock)

    ring2 = ReplicatedConsistentHash(xx_64)

    class _P:
        def __init__(self, addr):
            self._i = PeerInfo(grpc_address=addr, is_owner=(addr == me))

        def info(self):
            return self._i

    for a in (me, other):
        ring2.add(_P(a))
    # A key we own under the 2-peer ring but NOT once a third joins.
    three = ReplicatedConsistentHash(xx_64)
    for a in (me, other, "10.0.0.3:1051"):
        three.add(_P(a))
    key = next(
        f"m{i}" for i in range(2000)
        if ring2.get(f"lease_m{i}").info().grpc_address == me
        and three.get(f"lease_m{i}").info().grpc_address != me
    )

    async def scenario():
        await s.start()
        try:
            await s.set_peers([
                PeerInfo(grpc_address=me, is_owner=True),
                PeerInfo(grpc_address=other),
            ])
            lm = s.leases
            g = (await lm.grant("holder", [_req(key)]))[0]
            assert g.granted
            slot_key = f"lease_{key}" + LEASE_SUFFIX
            assert s.backend.get_cache_item(slot_key) is not None
            # The remap demotes us for this key.
            await s.set_peers([
                PeerInfo(grpc_address=me, is_owner=True),
                PeerInfo(grpc_address=other),
                PeerInfo(grpc_address="10.0.0.3:1051"),
            ])
            assert not s._owns_key(f"lease_{key}")
            # A renewal/grant against the demoted owner refuses — no
            # more admission carved from a slot whose authoritative
            # row now lives (fully spendable) elsewhere.
            g2 = (await lm.grant("holder", [_req(key)]))[0]
            assert not g2.granted and "not the owner" in g2.refusal
            # The remap sweep revoked the holder and dropped the slot.
            dropped = await lm.drop_unowned()
            assert s.backend.get_cache_item(slot_key) is None
            with lm._lock:
                assert f"lease_{key}" not in lm._keys
            # Keys we STILL own are untouched.
            kept = next(
                f"m{i}" for i in range(2000)
                if s._owns_key(f"lease_m{i}")
            )
            g3 = (await lm.grant("holder", [_req(kept)]))[0]
            assert g3.granted
            assert await lm.drop_unowned() == 0
            with lm._lock:
                assert f"lease_{kept}" in lm._keys
            return dropped
        finally:
            await s.close()

    assert asyncio.run(scenario()) >= 0


def test_service_lease_disabled():
    s = Service(Config(
        device=DeviceConfig(num_slots=1024, ways=8, batch_size=64),
        lease=LeaseConfig(enabled=False),
    ))

    async def scenario():
        await s.start()
        try:
            grants = await s.lease("a", [_req()])
            assert not grants[0].granted
            assert grants[0].refusal == "leases disabled"
        finally:
            await s.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------
# cluster tier
# ---------------------------------------------------------------------

FRACTION = 0.25
HOLDERS = 2


@pytest.fixture(scope="module")
def lease_cluster():
    c = Cluster.start_with(
        ["", "", ""],
        conf_template=DaemonConfig(
            lease=LeaseConfig(
                fraction=FRACTION,
                # TTL long enough that nothing expires mid-test; the
                # reconcile cadence is the CLIENT knob under test.
                ttl_ms=60_000, max_holders=HOLDERS,
                reconcile_ms=60_000, low_water=0.0,
            ),
        ),
    )
    yield c
    c.stop()


def test_over_admission_bound_exact(lease_cluster):
    """The closed-form oracle: with reconcile quiesced (the partition-
    equivalent worst case), concurrent leased clients + direct traffic
    admit EXACTLY limit x (1 + holders x fraction) — the carve slot's
    budget plus the authoritative row — and never one hit more."""
    c = lease_cluster
    key = "bound"
    hash_key = f"lease_{key}"
    addr = c.daemons[0].grpc_address
    allowance = int(LIMIT * FRACTION)  # 25

    # reconcile_ms=60s: no burned hits reconcile during the test, so
    # every locally burned hit is over-admission the carve must bound.
    cfg = LeaseConfig(
        fraction=FRACTION, ttl_ms=60_000, max_holders=HOLDERS,
        reconcile_ms=60_000, low_water=0.0,
    )
    clients = [
        LeasedClient(addr, lease=cfg, client_id=f"h{i}")
        for i in range(HOLDERS)
    ]
    direct = V1Client(addr)
    admitted = 0
    try:
        # Acquire grants: the first check falls back (and queues the
        # grant); wait until both holders burn locally.
        for lc in clients:
            r = lc.get_rate_limits([_req(key=key)])[0]
            if r.error == "" and r.status == Status.UNDER_LIMIT:
                admitted += 1

        def granted():
            for lc in clients:
                assert any(
                    v.allowance_left > 0
                    for v in lc.table._leases.values()
                ), lc.stats()
        until_pass(granted, timeout=10.0)

        # Saturate every local allowance and the authoritative row.
        for lc in clients:
            for _ in range(allowance + 10):
                r = lc.get_rate_limits([_req(key=key)])[0]
                if r.error == "" and r.status == Status.UNDER_LIMIT:
                    admitted += 1
        for _ in range(LIMIT + 20):
            r = direct.get_rate_limits([_req(key=key)])[0]
            if r.error == "" and r.status == Status.UNDER_LIMIT:
                admitted += 1

        bound = int(LIMIT * (1 + HOLDERS * FRACTION))  # 150
        assert admitted == bound, (admitted, bound)

        # Post-saturation, EVERY path answers OVER_LIMIT.
        for cl in [direct] + clients:
            r = cl.get_rate_limits([_req(key=key)])[0]
            assert r.status == Status.OVER_LIMIT, (cl, r)

        # Differential against the device rows (the pymodel view of
        # the two buckets): authoritative row empty, carve slot empty.
        owner = c.owner_daemon_of(hash_key)
        row = owner.service.backend.get_cache_item(hash_key)
        assert row is not None and int(row.remaining) == 0
        slot = owner.service.backend.get_cache_item(
            hash_key + LEASE_SUFFIX
        )
        assert slot is not None
        assert slot.limit == HOLDERS * allowance
        assert int(slot.remaining) == 0
    finally:
        # Suppress the close-time release reconcile noise on admitted
        # accounting by closing AFTER all assertions.
        for lc in clients:
            lc.close()
        direct.close()


def test_ownership_routing_and_reconvergence(lease_cluster):
    """A leased key owned by ANOTHER daemon: the connected daemon
    proxies Lease/Reconcile to the owner, the grant state lives at the
    owner, and after reconcile the owner's authoritative row converges
    on the holder's local burn."""
    c = lease_cluster
    d0 = c.daemons[0]
    # A key d0 does NOT own.
    key = next(
        f"r{i}" for i in range(1000)
        if not d0.service.get_peer(f"lease_r{i}").info().is_owner
    )
    hash_key = f"lease_{key}"
    owner = c.owner_daemon_of(hash_key)
    assert owner is not d0

    cfg = LeaseConfig(
        fraction=FRACTION, ttl_ms=60_000, max_holders=HOLDERS,
        reconcile_ms=200, low_water=0.0,
    )
    lc = LeasedClient(d0.grpc_address, lease=cfg, client_id="prox")
    try:
        lc.get_rate_limits([_req(key=key)])

        def has_grant():
            assert any(
                v.allowance_left > 0 for v in lc.table._leases.values()
            ), lc.stats()
        until_pass(has_grant, timeout=10.0)

        # Grant state lives at the OWNER, not the proxy daemon.
        assert owner.service.leases.grants >= 1
        assert hash_key in owner.service.leases.debug_vars()["keys"]
        assert hash_key not in d0.service.leases.debug_vars()["keys"]
        # The carve slot is on the owner's device table.
        assert owner.service.backend.get_cache_item(
            hash_key + LEASE_SUFFIX
        ) is not None

        burned = 10
        for _ in range(burned):
            r = lc.get_rate_limits([_req(key=key)])[0]
            assert (r.metadata or {}).get("lease") == "local", r

        def converged():
            row = owner.service.backend.get_cache_item(hash_key)
            assert row is not None
            # The first fallback check burned 1 directly; the 10 local
            # burns land via reconcile -> queue_hit -> owner apply.
            assert LIMIT - int(row.remaining) == burned + 1
        until_pass(converged, timeout=15.0)
    finally:
        lc.close()


def test_leased_client_zero_rpc_steady_state(lease_cluster):
    """Steady single-key load burns locally: >=10x fewer RPCs per
    admitted check than per-call traffic (the ISSUE acceptance ratio;
    no cell of the benchmark drives a leased client yet: ROADMAP B13)."""
    c = lease_cluster
    addr = c.daemons[0].grpc_address
    cfg = LeaseConfig(
        fraction=0.25, ttl_ms=60_000, max_holders=2,
        reconcile_ms=500, low_water=0.25,
    )
    lc = LeasedClient(addr, lease=cfg, client_id="steady")
    try:
        big = _req(key="steady", limit=1_000_000)
        lc.get_rate_limits([big])

        def has_grant():
            assert any(
                v.allowance_left > 0 for v in lc.table._leases.values()
            )
        until_pass(has_grant, timeout=10.0)
        n = 400
        for _ in range(n):
            lc.get_rate_limits([big])
        stats = lc.stats()
        assert stats["local_admitted"] >= n
        # >= 10x fewer RPCs than checks (per-call issues 1 RPC/check).
        assert stats["rpcs"] * 10 <= stats["checks"], stats
    finally:
        lc.close()


def test_leased_client_degrades_transparently():
    """Against a daemon with leases disabled every check still answers
    authoritatively — per-call fallback, refusals counted, no errors."""
    c = Cluster.start_with([""], conf_template=DaemonConfig(
        lease=LeaseConfig(enabled=False),
    ))
    try:
        lc = LeasedClient(
            c.daemons[0].grpc_address,
            lease=LeaseConfig(reconcile_ms=100, ttl_ms=1000),
            client_id="deg",
        )
        try:
            for i in range(20):
                r = lc.get_rate_limits([_req(key="d")])[0]
                assert r.error == ""
                assert (r.metadata or {}).get("lease") is None

            def refused():
                assert lc.stats()["refusals"] >= 1
            until_pass(refused, timeout=10.0)
            stats = lc.stats()
            assert stats["local_admitted"] == 0
            assert stats["fallback_checks"] == stats["checks"]
        finally:
            lc.close()
    finally:
        c.stop()


# ---------------------------------------------------------------------
# client tier: compiled codec + channel hardening
# ---------------------------------------------------------------------

def test_fast_client_wire_parity(lease_cluster):
    """FastV1Client answers == V1Client answers for the same traffic,
    including validation-error lanes (the native codec round trip)."""
    from gubernator_tpu import native

    if not native.available():
        pytest.skip("native library not built")
    c = lease_cluster
    addr = c.daemons[0].grpc_address
    fc = FastV1Client(addr)
    vc = V1Client(addr)
    try:
        assert fc.codec == "native"
        reqs = [
            _req(key=f"fp{i}", name="fastpar", limit=50) for i in range(8)
        ] + [
            RateLimitReq(name="", unique_key="x", hits=1, limit=1,
                         duration=1000),
            RateLimitReq(name="y", unique_key="", hits=1, limit=1,
                         duration=1000),
        ]
        a = fc.get_rate_limits(list(reqs))
        b = vc.get_rate_limits(list(reqs))
        assert len(a) == len(b) == 10
        for ra, rb in zip(a, b):
            assert ra.status == rb.status
            assert ra.limit == rb.limit
            # Same key checked twice (once per client): remaining
            # differs by exactly the second pass's hit.
            assert ra.remaining == rb.remaining + 1 or (
                ra.error and ra.error == rb.error
            )
    finally:
        fc.close()
        vc.close()


def test_encode_reqs_matches_python_protobuf():
    from gubernator_tpu import native
    from gubernator_tpu.net import grpc_api
    from gubernator_tpu.proto import gubernator_pb2 as pb

    if not native.available():
        pytest.skip("native library not built")
    reqs = [
        RateLimitReq(name="n", unique_key="k", hits=-5, limit=2**45,
                     duration=0, behavior=Behavior.GLOBAL, burst=7),
        RateLimitReq(),  # all defaults — every field omitted
        RateLimitReq(name="ütf-8", unique_key="ключ", hits=1, limit=1,
                     duration=1),
    ]
    got = native.encode_reqs(reqs)
    want = pb.GetRateLimitsReq(
        requests=[grpc_api.req_to_pb(r) for r in reqs]
    ).SerializeToString()
    assert got == want


def test_client_default_deadline_regression():
    """get_rate_limits / health_check must carry a DEADLINE when the
    caller passes nothing — the timeout=None forever-hang was the
    pre-hardening default (both client variants)."""
    seen = {}

    class Recorder:
        def __call__(self, request, timeout=object()):
            seen["timeout"] = timeout
            from gubernator_tpu.proto import gubernator_pb2 as pb

            return pb.GetRateLimitsResp()

    cl = V1Client("127.0.0.1:1")  # never dialed — stub replaced below
    cl._stub.GetRateLimits = Recorder()
    cl.get_rate_limits([_req()])
    assert seen["timeout"] == DEFAULT_RPC_TIMEOUT_S
    # Explicit None opts back into no-deadline.
    cl.get_rate_limits([_req()], timeout=None)
    assert seen["timeout"] is None
    cl.close()

    class AsyncRecorder:
        async def __call__(self, request, timeout=object()):
            seen["timeout"] = timeout
            from gubernator_tpu.proto import gubernator_pb2 as pb

            return pb.GetRateLimitsResp()

    async def async_half():
        acl = AsyncV1Client("127.0.0.1:1")
        acl._stub.GetRateLimits = AsyncRecorder()
        await acl.get_rate_limits([_req()])
        assert seen["timeout"] == DEFAULT_RPC_TIMEOUT_S
        await acl.close()

    asyncio.run(async_half())


def test_channel_options_defaults_and_merge():
    opts = dict(channel_options())
    # Keepalive probes + 4MB caps are on by default.
    assert opts["grpc.keepalive_time_ms"] == 60_000
    assert opts["grpc.max_receive_message_length"] == 4 * 1024 * 1024
    assert opts["grpc.max_send_message_length"] == 4 * 1024 * 1024
    # A caller override replaces the default of the same name and
    # appends new options.
    merged = dict(channel_options([
        ("grpc.keepalive_time_ms", 5_000),
        ("grpc.enable_retries", 0),
    ]))
    assert merged["grpc.keepalive_time_ms"] == 5_000
    assert merged["grpc.enable_retries"] == 0
    assert len(dict(DEFAULT_CHANNEL_OPTIONS)) == len(
        dict(channel_options())
    )
