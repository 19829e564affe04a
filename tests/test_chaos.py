"""Chaos plane + degraded-mode resilience (ISSUE 4 acceptance).

The invariants that previously existed only as docstrings, asserted
under real injected fault sequences on a 3-daemon in-process cluster:

- retry-safe paths never double-count: with >=30% injected RPC failures
  (client-side unsent errors, server-side pre-apply rejections, drops,
  delays), every key's applied hits on its owner equal EXACTLY the
  successful responses the clients saw;
- over-admission under partition stays within the configured shadow
  bound (limit + peers * shadow_fraction * limit);
- breakers open / half-open / re-close on schedule, and every breaker
  opened by a fault plan re-closes after heal;
- GLOBAL broadcast state reconverges after heal (requeued hits apply
  exactly once; non-owners converge to the owner's authoritative row).

Everything is driven from a seeded ChaosPlan — per-(rule, src, dst)
decision sequences are pure functions of the seed (testing/chaos.py),
so a failure reproduces from the seed alone.
"""
from __future__ import annotations

import asyncio
import random
import time

import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.core.config import (
    CircuitConfig,
    Config,
    DaemonConfig,
    DeviceConfig,
    normalize_degraded_mode,
)
from gubernator_tpu.core.types import Behavior, PeerInfo, RateLimitReq, Status
from gubernator_tpu.net.breaker import CircuitBreaker, CircuitState
from gubernator_tpu.net.peer_client import PeerClient, PeerNotReadyError
from gubernator_tpu.runtime.service import (
    SHADOW_SUFFIX,
    Service,
    forward_backoff_s,
)
from gubernator_tpu.testing import ChaosInjector, ChaosPlan, Cluster, Rule

SEED = 1337
LIMIT = 1000
DURATION = 60_000
SHADOW_FRACTION = 0.25
# Fast breaker schedule so open -> half-open -> closed cycles fit the
# test budget: 3 consecutive failures trip, backoff 0.1s doubling to 1s.
CIRCUIT = CircuitConfig(
    failure_threshold=3, base_backoff_s=0.1, max_backoff_s=1.0, jitter=0.2
)


def until_pass(fn, timeout=20.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while True:
        try:
            return fn()
        except AssertionError:
            if time.monotonic() > deadline:
                raise
            time.sleep(interval)


# ---------------------------------------------------------------------
# unit tier: breaker schedule, backoff schedule, plan determinism
# ---------------------------------------------------------------------

def test_breaker_opens_half_opens_recloses_on_schedule():
    """The closed -> open -> half-open -> closed walk, on a fake clock
    with deterministic jitter."""
    t = [0.0]
    transitions = []
    b = CircuitBreaker(
        CircuitConfig(
            failure_threshold=3, base_backoff_s=0.5, max_backoff_s=4.0,
            jitter=0.0, half_open_probes=1,
        ),
        clock=lambda: t[0],
        rng=random.Random(SEED),
        on_transition=lambda old, new: transitions.append((old, new)),
    )
    # Two failures + a success: the consecutive count resets.
    b.record_failure()
    b.record_failure()
    b.record_success()
    assert b.state is CircuitState.CLOSED and b.trips == 0
    # Three consecutive failures trip it open for base_backoff_s.
    for _ in range(3):
        b.record_failure()
    assert b.state is CircuitState.OPEN
    assert b.trips == 1
    assert not b.would_allow() and not b.allow()
    assert b.fast_fail()
    assert b.remaining_open_s() == pytest.approx(0.5)
    # Backoff expiry: exactly one half-open probe is admitted.
    t[0] = 0.51
    assert b.would_allow()
    assert b.allow()
    assert b.state is CircuitState.HALF_OPEN
    assert not b.allow()  # probe budget spent
    # Failed probe re-opens with the backoff DOUBLED.
    b.record_failure()
    assert b.state is CircuitState.OPEN and b.trips == 2
    assert b.open_until - b.opened_at == pytest.approx(1.0)
    # Next probe succeeds: closed, streak reset.
    t[0] = b.open_until + 0.01
    assert b.allow()
    b.record_success()
    assert b.state is CircuitState.CLOSED
    # A fresh trip starts back at the base backoff (streak was reset).
    for _ in range(3):
        b.record_failure()
    assert b.open_until - b.opened_at == pytest.approx(0.5)
    assert transitions == [
        (CircuitState.CLOSED, CircuitState.OPEN),
        (CircuitState.OPEN, CircuitState.HALF_OPEN),
        (CircuitState.HALF_OPEN, CircuitState.OPEN),
        (CircuitState.OPEN, CircuitState.HALF_OPEN),
        (CircuitState.HALF_OPEN, CircuitState.CLOSED),
        (CircuitState.CLOSED, CircuitState.OPEN),
    ]


def test_breaker_abandoned_probe_expires_and_reprobes():
    """Regression: a half-open probe whose gated RPC never reports an
    outcome (e.g. torn down by CancelledError) must not wedge the
    breaker HALF_OPEN forever — probe_timeout_s after issue the probe
    counts as failed, the breaker re-opens with the backoff doubled,
    and the peer is probed again."""
    t = [0.0]
    b = CircuitBreaker(
        CircuitConfig(
            failure_threshold=1, base_backoff_s=0.5, max_backoff_s=4.0,
            jitter=0.0, half_open_probes=1, probe_timeout_s=5.0,
        ),
        clock=lambda: t[0],
        rng=random.Random(SEED),
    )
    b.record_failure()
    assert b.state is CircuitState.OPEN
    t[0] = 0.6
    assert b.allow()  # the probe token is consumed...
    assert b.state is CircuitState.HALF_OPEN
    # ...and its outcome never lands.  Before the probe timeout the
    # breaker sheds (probe budget spent), but does NOT shed forever:
    t[0] = 5.5
    assert not b.would_allow() and not b.allow()
    assert b.state is CircuitState.HALF_OPEN
    # Past the timeout the abandoned probe counts as a failure: the
    # breaker re-opens (trip counted, backoff doubled to 1.0s)...
    t[0] = 5.7
    assert not b.would_allow()
    assert b.state is CircuitState.OPEN and b.trips == 2
    assert b.open_until - b.opened_at == pytest.approx(1.0)
    assert b.fast_fail()  # degraded mode sees the re-open too
    # ...and after the backoff a fresh probe is admitted and can close.
    t[0] = b.open_until + 0.01
    assert b.allow()
    b.record_success()
    assert b.state is CircuitState.CLOSED


def test_breaker_backoff_caps_and_jitters():
    t = [0.0]
    cfg = CircuitConfig(
        failure_threshold=1, base_backoff_s=0.2, max_backoff_s=1.5,
        jitter=0.25,
    )
    b = CircuitBreaker(cfg, clock=lambda: t[0], rng=random.Random(SEED))
    for streak in range(1, 8):
        base = min(0.2 * (2 ** (streak - 1)), 1.5)
        for _ in range(32):
            v = b.backoff_s(streak)
            assert base * 0.75 <= v <= base * 1.25, (streak, v)


def test_forward_backoff_schedule_pinned():
    """The ownership-retry backoff: equal-jittered exponential, capped
    at the batch timeout (satellite: regression-pins the schedule)."""
    rng = random.Random(SEED)
    seen = []
    for attempt in range(1, 6):
        base = 0.01 * (2 ** (attempt - 1))
        v = forward_backoff_s(attempt, 0.5, rng)
        assert base / 2 <= v <= base, (attempt, v)
        seen.append(v)
    # Bases double: 10, 20, 40, 80, 160 ms — jitter never reorders the
    # envelope (each window's floor is the previous window's ceiling/2).
    assert seen == sorted(seen) or all(
        seen[i] <= 0.01 * (2 ** i) for i in range(5)
    )
    # The cap: a tiny batch timeout bounds every attempt.
    for attempt in range(1, 10):
        assert forward_backoff_s(attempt, 0.02, rng) <= 0.02
    # Deterministic given the rng: same seed, same schedule.
    a = [forward_backoff_s(i, 0.5, random.Random(7)) for i in range(1, 6)]
    b = [forward_backoff_s(i, 0.5, random.Random(7)) for i in range(1, 6)]
    assert a == b
    # Worst case stays within one RPC budget (0.5s batch timeout).
    assert sum(0.01 * (2 ** i) for i in range(5)) < 0.5


def test_chaos_plan_deterministic_and_serializable():
    plan_dict = {
        "seed": 99,
        "rules": [
            {"op": "error", "probability": 0.5,
             "message": "injected: failed to connect"},
            {"op": "delay", "probability": 0.2, "delay_s": 0.001},
        ],
    }

    async def drive(inj):
        outcomes = []
        for _ in range(200):
            try:
                await inj.on_client("a:1", "b:2", "GetPeerRateLimits")
                outcomes.append("ok")
            except Exception as e:  # noqa: BLE001
                outcomes.append(str(e.code()))
        return outcomes

    o1 = asyncio.run(drive(ChaosInjector(ChaosPlan.from_dict(plan_dict))))
    o2 = asyncio.run(drive(ChaosInjector(ChaosPlan.from_dict(plan_dict))))
    assert o1 == o2  # pure function of the seed
    assert "StatusCode.UNAVAILABLE" in o1
    frac = sum(1 for o in o1 if o != "ok") / len(o1)
    assert 0.3 < frac < 0.7
    # A different seed decides differently.
    plan_dict2 = dict(plan_dict, seed=100)
    o3 = asyncio.run(drive(ChaosInjector(ChaosPlan.from_dict(plan_dict2))))
    assert o3 != o1
    # max_count bounds a rule's firings.
    inj = ChaosInjector(ChaosPlan(seed=1, rules=[
        Rule(op="error", probability=1.0, max_count=3),
    ]))
    fails = 0
    async def bounded():
        nonlocal fails
        for _ in range(10):
            try:
                await inj.on_client("a:1", "b:2", "M")
            except Exception:  # noqa: BLE001
                fails += 1
    asyncio.run(bounded())
    assert fails == 3


def test_degraded_mode_validation():
    assert normalize_degraded_mode("") == "error"
    assert normalize_degraded_mode("Fail_Closed") == "fail_closed"
    with pytest.raises(ValueError):
        normalize_degraded_mode("fail_openn")


def test_degraded_fail_modes_shape():
    """fail_closed denies, fail_open admits; both tag metadata and
    neither touches the device table."""
    async def scenario(mode):
        svc = Service(Config(
            device=DeviceConfig(num_slots=1024, ways=8, batch_size=64),
            degraded_mode=mode,
        ))
        try:
            peer = PeerClient(PeerInfo(grpc_address="127.0.0.1:1"))
            req = RateLimitReq(
                name="deg", unique_key="k", hits=1, limit=10,
                duration=DURATION,
            )
            resp = await svc._degraded_response(
                req, req.hash_key(), peer, PeerNotReadyError("gone")
            )
            await peer.shutdown()
            return resp, svc
        finally:
            await svc.close()

    resp, svc = asyncio.run(scenario("fail_closed"))
    assert resp.status == Status.OVER_LIMIT
    assert resp.remaining == 0 and resp.limit == 10
    assert resp.metadata["degraded"] == "fail_closed"
    assert resp.metadata["owner"] == "127.0.0.1:1"
    assert resp.error == ""

    resp, svc = asyncio.run(scenario("fail_open"))
    assert resp.status == Status.UNDER_LIMIT
    assert resp.remaining == 9 and resp.limit == 10
    assert resp.metadata["degraded"] == "fail_open"

    resp, svc = asyncio.run(scenario("error"))
    assert "not connected" in resp.error
    assert "degraded" not in (resp.metadata or {})


def test_degraded_local_shadow_zero_limit_stays_deny_all():
    """Regression: a limit=0 (deny-all) key must not admit 1 hit per
    window from the shadow slot's max(1, ...) floor while degraded —
    it answers OVER_LIMIT directly and writes no shadow state."""
    async def scenario():
        svc = Service(Config(
            device=DeviceConfig(num_slots=1024, ways=8, batch_size=64),
            degraded_mode="local_shadow",
        ))
        try:
            peer = PeerClient(PeerInfo(grpc_address="127.0.0.1:1"))
            req = RateLimitReq(
                name="deg", unique_key="deny", hits=1, limit=0,
                duration=DURATION,
            )
            resp = await svc._degraded_response(
                req, req.hash_key(), peer, PeerNotReadyError("gone")
            )
            await peer.shutdown()
            assert resp.status == Status.OVER_LIMIT
            assert resp.remaining == 0 and resp.limit == 0
            assert resp.error == ""
            assert resp.metadata["degraded"] == "local_shadow"
            # No shadow slot was created for the deny-all key.
            assert not svc._shadow
            assert svc.backend.get_cache_item(
                req.hash_key() + SHADOW_SUFFIX
            ) is None
        finally:
            await svc.close()

    asyncio.run(scenario())


def test_degraded_reset_time_resolves_gregorian_durations():
    """Regression: fail_open/fail_closed degraded answers must not
    treat a Gregorian interval id (duration 0-5) as milliseconds —
    reset_time is the end of the current calendar interval, or omitted
    when the id is invalid."""
    from gubernator_tpu.core import clock as clock_mod
    from gubernator_tpu.core.interval import (
        GREGORIAN_HOURS,
        gregorian_expiration,
    )

    async def scenario(duration):
        clk = clock_mod.Clock()
        clk.freeze()
        svc = Service(
            Config(
                device=DeviceConfig(num_slots=1024, ways=8, batch_size=64),
                degraded_mode="fail_closed",
            ),
            clock=clk,
        )
        try:
            peer = PeerClient(PeerInfo(grpc_address="127.0.0.1:1"))
            req = RateLimitReq(
                name="deg", unique_key="greg", hits=1, limit=10,
                duration=duration,
                behavior=Behavior.DURATION_IS_GREGORIAN,
            )
            resp = await svc._degraded_response(
                req, req.hash_key(), peer, PeerNotReadyError("gone")
            )
            await peer.shutdown()
            expected = (
                gregorian_expiration(clk.now(), duration)
                if duration <= 5 else 0
            )
            return resp, expected
        finally:
            await svc.close()
            clk.unfreeze()

    resp, expected = asyncio.run(scenario(GREGORIAN_HOURS))
    assert resp.reset_time == expected
    # The end of the current hour, not the broken now + interval-id
    # arithmetic (now + 1ms for GREGORIAN_HOURS).
    assert expected > 1_000_000_000_000  # a real epoch-ms timestamp
    # Invalid Gregorian id: reset_time omitted, not garbage.
    resp, _ = asyncio.run(scenario(99))
    assert resp.reset_time == 0
    assert resp.status == Status.OVER_LIMIT


# ---------------------------------------------------------------------
# cluster tier: a seeded plan against 3 real daemons
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_cluster():
    injector = ChaosInjector(ChaosPlan(seed=SEED))
    injector.set_active(False)
    c = Cluster.start_with(
        ["", "", ""],
        conf_template=DaemonConfig(
            circuit=CIRCUIT,
            degraded_mode="local_shadow",
            shadow_fraction=SHADOW_FRACTION,
            chaos=injector,
        ),
    )
    yield c, injector
    c.stop()


def _owner_split(cluster, key):
    """(owner daemon, [non-owner daemons]) for a hash key."""
    owner = cluster.owner_daemon_of(key)
    others = [d for d in cluster.daemons if d is not owner]
    return owner, others


def _applied(daemon, hash_key):
    it = daemon.service.backend.get_cache_item(hash_key)
    return 0 if it is None else LIMIT - int(it.remaining)


def _quiesce(cluster, injector):
    """Heal and drive light traffic FROM EVERY daemon until every
    breaker re-closed — each (src, dst) pair needs its own half-open
    probe, and each scenario must leave the cluster whole for the next."""
    injector.heal()
    clients = [V1Client(addr) for addr in cluster.addresses()]
    try:
        def check():
            # Random keys fan the probes over every owner from every
            # sender; new keys each round until the probes land.
            for cl in clients:
                cl.get_rate_limits([
                    RateLimitReq(
                        name="quiesce", unique_key=f"q{random.random()}",
                        hits=1, limit=LIMIT, duration=DURATION,
                    )
                    for _ in range(4)
                ], timeout=30)
            for addr, states in cluster.breaker_states().items():
                for peer_addr, state in states.items():
                    assert state in ("closed", "disabled"), (
                        addr, peer_addr, state
                    )
        until_pass(check, timeout=20.0)
    finally:
        for cl in clients:
            cl.close()


def test_seeded_plan_no_double_count(chaos_cluster):
    """A third of the peer RPCs fail by the plan (unsent client errors,
    pre-apply server rejections, drops, delays); the run is held to what
    the seeded plan DECIDED, not to a share of a sample: every failure
    the injector counted reached a caller and no caller saw one it did
    not inject, and every key's applied count on its owner EQUALS the
    successful responses the client saw — retries driven by retry-safe
    classifications never double-apply, failures never half-apply."""
    c, inj = chaos_cluster
    t0 = time.monotonic()

    def reasked():
        return sum(
            d.metrics.stages.debug_vars()["peer"]["forward"]["reasked"]
            for d in c.daemons
        )

    reasked0 = reasked()
    inj.reset(ChaosPlan(seed=SEED, rules=[
        # Unsent client-side failure: raised before the RPC is issued,
        # wearing connect-phase wording (the retry-safe classification).
        Rule(op="error", where="client", method="GetPeerRateLimits",
             probability=0.22, status="UNAVAILABLE",
             message="injected: failed to connect to all addresses"),
        # Delivered-but-rejected BEFORE the handler: nothing applied.
        Rule(op="error", where="server", phase="before",
             method="GetPeerRateLimits", probability=0.12,
             status="UNAVAILABLE",
             message="injected: refused before apply"),
        # Vanished request: surfaces as DEADLINE_EXCEEDED (never
        # retried — a drop is not provably unsent).
        Rule(op="drop", where="client", method="GetPeerRateLimits",
             probability=0.04, delay_s=0.01),
        Rule(op="delay", where="client", method="GetPeerRateLimits",
             probability=0.10, delay_s=0.005),
    ]))

    keys = [f"storm{i}" for i in range(30)]
    ok = {k: 0 for k in keys}
    cl = V1Client(c.addresses()[0])
    try:
        for _round in range(5):
            for k in keys:
                r = cl.get_rate_limits([
                    RateLimitReq(
                        name="chaos", unique_key=k, hits=1, limit=LIMIT,
                        duration=DURATION,
                    )
                ], timeout=30)[0]
                if r.error == "" and "degraded" not in (r.metadata or {}):
                    ok[k] += 1
    finally:
        cl.close()

    # Both refusing rules fired, and each refusal they decided is in a
    # caller's error window once: none swallowed, none the plan did not
    # inject (every injected failure says so in its text).
    seen = [
        msg for d in c.daemons for p in d.service.peer_list()
        for ts, msg in p._errors if ts >= t0
    ]
    refused = inj.injected["client_error"] + inj.injected["server_before"]
    assert inj.injected["client_error"] and inj.injected["server_before"]
    assert sum("injected:" in m for m in seen) == refused, dict(inj.injected)
    # A drop is a DEADLINE_EXCEEDED: the raw forward asks again (no peer
    # error yet) or gives up; the batcher's caller records the one it
    # was handed a second time (peer_client.py get_peer_rate_limit after
    # _send_batch_inner: once or twice a drop, never none).
    gave_up = inj.injected["client_drop"] - (reasked() - reasked0)
    drops_seen = sum("injected drop" in m for m in seen)
    assert gave_up <= drops_seen <= 2 * gave_up, dict(inj.injected)
    assert len(seen) == refused + drops_seen, seen
    forwarded_keys = 0
    for k in keys:
        hash_key = f"chaos_{k}"
        owner, _ = _owner_split(c, hash_key)
        if owner is not c.daemons[0]:
            forwarded_keys += 1
        applied = _applied(owner, hash_key)
        assert applied == ok[k], (
            f"key {k}: owner applied {applied}, client saw {ok[k]} "
            f"successes — double count or lost hit"
        )
    assert forwarded_keys >= 10  # the plan actually exercised forwards
    # At least one breaker opened somewhere during the storm...
    trips = sum(
        p.breaker.trips
        for d in c.daemons
        for p in d.service.peer_list()
        if p.breaker is not None and not p.info().is_owner
    )
    assert trips >= 1
    # ...and every one of them re-closes after heal.
    _quiesce(c, inj)


def test_partition_over_admission_within_shadow_bound(chaos_cluster):
    """Partition the owner away: non-owners serve from local shadow
    slots at shadow_fraction of the limit, so cluster-wide admission is
    bounded by limit + peers * shadow_fraction * limit; shadow state is
    dropped when the owner heals."""
    c, inj = chaos_cluster
    inj.reset(ChaosPlan(seed=SEED))
    limit = 40
    shadow_limit = max(1, int(limit * SHADOW_FRACTION))  # 10
    key = "partme"
    hash_key = f"part_{key}"
    owner, others = _owner_split(c, hash_key)
    inj.partition(
        {owner.grpc_address},
        {d.grpc_address for d in others},
    )

    def drive(daemon, n):
        cl = V1Client(daemon.grpc_address)
        try:
            out = []
            for _ in range(n):
                out.append(cl.get_rate_limits([
                    RateLimitReq(
                        name="part", unique_key=key, hits=1, limit=limit,
                        duration=DURATION,
                    )
                ], timeout=30)[0])
            return out
        finally:
            cl.close()

    owner_resps = drive(owner, 50)
    other_resps = [drive(d, 30) for d in others]

    def admitted(resps):
        return sum(
            1 for r in resps
            if r.error == "" and r.status == Status.UNDER_LIMIT
        )

    total = admitted(owner_resps) + sum(admitted(rs) for rs in other_resps)
    bound = limit + len(others) * shadow_limit
    assert total <= bound, (total, bound)
    # The owner stayed authoritative for its own clients...
    assert admitted(owner_resps) == limit
    # ...and each partitioned node degraded to its shadow slot: tagged,
    # admitting at most (and eventually exactly) its shadow fraction.
    for d, resps in zip(others, other_resps):
        assert admitted(resps) <= shadow_limit
        degraded = [
            r for r in resps if (r.metadata or {}).get("degraded")
        ]
        assert degraded, "no degraded response from a partitioned node"
        assert all(
            r.metadata["degraded"] == "local_shadow" for r in degraded
        )
        assert all(
            r.metadata["owner"] == owner.grpc_address for r in degraded
        )
        # The shadow slot lives under its own key in the device table.
        shadow_item = d.service.backend.get_cache_item(
            hash_key + SHADOW_SUFFIX
        )
        assert shadow_item is not None
        assert d.service._shadow.get(owner.grpc_address)
    assert total > limit  # degraded service actually admitted something

    # Heal: forwards reach the owner again, shadow state is dropped
    # (the RESET_REMAINING re-fill) on every previously-degraded node.
    inj.heal()

    def healed():
        for d in others:
            cl = V1Client(d.grpc_address)
            try:
                r = cl.get_rate_limits([
                    RateLimitReq(
                        name="part", unique_key=key, hits=0, limit=limit,
                        duration=DURATION,
                    )
                ], timeout=30)[0]
            finally:
                cl.close()
            assert r.error == ""
            assert "degraded" not in (r.metadata or {}), r.metadata
            assert not d.service._shadow.get(owner.grpc_address)
            # The RESET_REMAINING drop REMOVES a token-bucket row
            # (algorithms.go:78-90): the shadow slot is gone, not just
            # re-filled — no stale shadow admission state survives.
            shadow_item = d.service.backend.get_cache_item(
                hash_key + SHADOW_SUFFIX
            )
            assert shadow_item is None

    until_pass(healed, timeout=20.0)
    _quiesce(c, inj)


def test_global_state_reconverges_after_heal(chaos_cluster):
    """GLOBAL hits queued behind a partition requeue (provably unsent)
    without double counting, and both the owner's authoritative row and
    the non-owners' broadcast replicas converge after heal."""
    c, inj = chaos_cluster
    inj.reset(ChaosPlan(seed=SEED))
    key = "globme"
    hash_key = f"glob_{key}"
    owner, others = _owner_split(c, hash_key)
    inj.partition(
        {owner.grpc_address},
        {d.grpc_address for d in others},
    )

    per_node = 10
    for d in others:
        cl = V1Client(d.grpc_address)
        try:
            for _ in range(per_node):
                r = cl.get_rate_limits([
                    RateLimitReq(
                        name="glob", unique_key=key, hits=1, limit=LIMIT,
                        duration=DURATION, behavior=Behavior.GLOBAL,
                    )
                ], timeout=30)[0]
                # Non-owner GLOBAL serves locally even while the owner
                # is unreachable — that's the stale-but-fast contract.
                assert r.error == "", r.error
        finally:
            cl.close()

    # Let a few flush windows fail against the partition (each failure
    # is provably unsent and requeues the aggregated hits).
    time.sleep(0.5)
    sent = per_node * len(others)
    assert _applied(owner, hash_key) < sent  # partition actually held

    inj.heal()

    def converged():
        # Owner applied every queued hit exactly once...
        assert _applied(owner, hash_key) == sent
        # ...and broadcast the authoritative row back to the others.
        for d in others:
            it = d.service.backend.get_cache_item(hash_key)
            assert it is not None
            assert LIMIT - int(it.remaining) == sent, (
                d.grpc_address, int(it.remaining)
            )

    until_pass(converged, timeout=25.0)
    # Stability: two more broadcast windows must not re-apply requeued
    # hits (the zero-double-count half of the invariant).
    time.sleep(0.5)
    assert _applied(owner, hash_key) == sent
    _quiesce(c, inj)
