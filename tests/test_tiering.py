"""Guberberg: the two-tier key table (ISSUE 15; docs/tiering.md).

Kernel tier: demote_extract picks the coldest unprotected bucket rows
(pinned against a numpy reference over the host table copy) and clears
the slots in the same dispatch; a demote -> inject round trip is
bit-identical (promote is the reshard merge algebra).

Policy tier: ColdTier open-addressing (put/pop/membership/tombstone
compaction/capacity drop-and-count/expiry pruning), the watermark
hysteresis as a pure function against a python oracle, and the
CMS second opinion (hot rows the device considered cold go straight
back).

Correctness tier: the demote -> touch -> promote race differentially
against the pymodel oracle — at most ONE extra limit window per cycle,
merge conserves budget bit-exactly; the request path stays
blocking-fetch-free through a full tier cycle; a checkpoint restores
BOTH tiers geometry-independently; the GUBER_TIER_* env surface
validates at startup.
"""
from __future__ import annotations

import asyncio

import numpy as np
import pytest

from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import (
    Config,
    DeviceConfig,
    TierConfig,
    tier_config_from_env,
)
from gubernator_tpu.core.types import (
    Algorithm,
    RateLimitReq,
    Status,
)
from gubernator_tpu.runtime.backend import DeviceBackend
from gubernator_tpu.runtime.coldtier import (
    COLD_FIELDS,
    ColdTier,
    TierManager,
)

LIMIT = 100
DURATION = 60_000

DEV = DeviceConfig(num_slots=2048, ways=8, batch_size=64)


def _req(key, name="t", hits=1, limit=LIMIT, **kw) -> RateLimitReq:
    return RateLimitReq(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=DURATION, **kw,
    )


def _fps_of(be, reqs):
    from gubernator_tpu.net.replicated_hash import xx_64

    return np.array(
        [
            int(np.uint64(xx_64(r.hash_key().encode())).view(np.int64))
            for r in reqs
        ],
        dtype=np.int64,
    )


def _no_protect() -> np.ndarray:
    return np.zeros(8, dtype=np.int64)


class _StubService:
    """The slice of Service the TierManager consumes for unit tests:
    a backend and an (empty) derived-slot protect list."""

    def __init__(self, backend) -> None:
        self.backend = backend
        self.tier = None

    def derived_slot_fps(self) -> np.ndarray:
        return np.zeros(0, dtype=np.int64)


# ---------------------------------------------------------------------
# knob validation (satellite: GUBER_TIER_* env surface)
# ---------------------------------------------------------------------

def test_tier_config_validation():
    with pytest.raises(ValueError, match="cold_capacity"):
        TierConfig(cold_capacity=0)
    with pytest.raises(ValueError, match="high_water"):
        TierConfig(high_water=0.0)
    with pytest.raises(ValueError, match="high_water"):
        TierConfig(high_water=1.5)
    with pytest.raises(ValueError, match="low_water"):
        TierConfig(low_water=0.0)
    with pytest.raises(ValueError, match="hysteresis"):
        TierConfig(high_water=0.5, low_water=0.5)
    with pytest.raises(ValueError, match="demote_batch"):
        TierConfig(demote_batch=0)
    with pytest.raises(ValueError, match="interval_s"):
        TierConfig(interval_s=0)


def test_tier_env_parse_names_env_surface(monkeypatch):
    monkeypatch.setenv("GUBER_TIER_LOW_WATER", "0.9")
    with pytest.raises(ValueError, match="GUBER_TIER_LOW_WATER"):
        tier_config_from_env()
    monkeypatch.setenv("GUBER_TIER_ENABLED", "true")
    monkeypatch.setenv("GUBER_TIER_COLD_CAPACITY", "4096")
    monkeypatch.setenv("GUBER_TIER_HIGH_WATER", "0.6")
    monkeypatch.setenv("GUBER_TIER_LOW_WATER", "0.4")
    monkeypatch.setenv("GUBER_TIER_DEMOTE_BATCH", "128")
    monkeypatch.setenv("GUBER_TIER_INTERVAL", "250ms")
    cfg = tier_config_from_env()
    assert cfg.enabled is True
    assert cfg.cold_capacity == 4096
    assert cfg.high_water == 0.6 and cfg.low_water == 0.4
    assert cfg.demote_batch == 128
    assert cfg.interval_s == 0.25


# ---------------------------------------------------------------------
# kernel tier: demote_extract vs a numpy reference
# ---------------------------------------------------------------------

def test_demote_extract_picks_coldest_vs_numpy_ref(frozen_clock):
    from gubernator_tpu.ops.state import table_to_host

    be = DeviceBackend(DEV, clock=frozen_clock)
    # Three waves, 8 keys each, clock advanced between waves so each
    # wave carries a distinct last-touch stamp.
    waves = []
    for w in range(3):
        reqs = [_req(f"w{w}k{i}") for i in range(8)]
        be.check(reqs)
        waves.append(reqs)
        frozen_clock.advance(1000)
    fps = {w: set(int(f) for f in _fps_of(be, waves[w]))
           for w in range(3)}
    host = table_to_host(be.table)
    occ0 = be.occupancy()
    assert occ0 == 24

    # Protect one wave-0 key: derived slots never demote.
    protected_fp = next(iter(fps[0]))
    protect = np.zeros(8, dtype=np.int64)
    protect[0] = protected_fp
    packed, rf = be.demote_extract_dispatch(protect, batch=8)()
    got = set(int(f) for f in packed[0][packed[0] != 0])
    assert len(got) == 8
    assert protected_fp not in got

    # Numpy reference invariant (order-free: stamps tie within a
    # wave): the extracted set must be the COLDEST eligible rows —
    # every extracted row's touched stamp <= every surviving eligible
    # row's stamp.
    key_h, touched_h = host["key"], host["touched"]
    stamp = {int(k): int(t) for k, t in zip(key_h, touched_h) if k}
    eligible = (fps[0] | fps[1] | fps[2]) - {protected_fp}
    survivors = eligible - got
    assert max(stamp[f] for f in got) <= min(
        stamp[f] for f in survivors
    )
    # 7 of wave 0 (all but the protected) + exactly 1 of wave 1.
    assert got & fps[0] == fps[0] - {protected_fp}
    assert len(got & fps[1]) == 1 and not (got & fps[2])

    # The same dispatch CLEARED the extracted slots.
    assert be.occupancy() == occ0 - 8
    for r in waves[0]:
        f = int(_fps_of(be, [r])[0])
        if f != protected_fp:
            assert be.get_cache_item(r.hash_key()) is None
    # Remaining/limit planes rode along (DEMOTE_ROW_FIELDS order).
    sel = packed[0] != 0
    assert (packed[3][sel] == LIMIT).all()
    assert (packed[5][sel] == LIMIT - 1).all()

    # Lanes past the eligible population come back empty and clear
    # nothing: a second big extract drains the rest, a third is a
    # no-op.
    packed2, _ = be.demote_extract_dispatch(protect, batch=64)()
    assert int((packed2[0] != 0).sum()) == 15  # 16 left, 1 protected
    assert be.occupancy() == 1
    packed3, _ = be.demote_extract_dispatch(protect, batch=64)()
    assert int((packed3[0] != 0).sum()) == 0
    assert be.occupancy() == 1
    assert be.get_cache_item(
        next(r for r in waves[0]
             if int(_fps_of(be, [r])[0]) == protected_fp).hash_key()
    ) is not None


def test_demote_inject_round_trip_bit_identity(frozen_clock):
    """Demote -> promote of untouched keys restores every row field
    bit-exactly (the resharding merge with nothing to merge), token
    and leaky algorithms alike."""
    be = DeviceBackend(DEV, clock=frozen_clock)
    reqs = [
        _req(f"tok{i}", hits=3 + i) for i in range(3)
    ] + [
        _req(f"leak{i}", hits=2 + i,
             algorithm=Algorithm.LEAKY_BUCKET)
        for i in range(3)
    ]
    be.check(reqs)
    before = {
        r.hash_key(): be.get_cache_item(r.hash_key()) for r in reqs
    }
    packed, rf = be.demote_extract_dispatch(_no_protect(), batch=8)()
    assert int((packed[0] != 0).sum()) == 6
    assert be.occupancy() == 0

    cold = ColdTier(capacity=64)
    idx = np.flatnonzero(packed[0] != 0)
    assert cold.put_rows(
        TierManager._cols_from_packed(packed, rf, idx)
    ) == 6
    rows = cold.pop_rows(packed[0][idx])
    assert cold.residents() == 0
    injected, merged = be.migrate_inject_dispatch(rows)()
    assert (injected, merged) == (6, 0)
    for r in reqs:
        a, b = before[r.hash_key()], be.get_cache_item(r.hash_key())
        assert b is not None
        assert a == b, f"{r.unique_key}: {a} != {b}"
    # The restored rows keep counting down exactly where they left
    # off.
    resp = be.check([_req("tok0", hits=1)])[0]
    assert resp.remaining == LIMIT - 3 - 1


# ---------------------------------------------------------------------
# policy tier: the cold store
# ---------------------------------------------------------------------

def _mkrows(fps, remaining=7, expire_at=10_000):
    n = len(fps)
    return {
        "key_hash": np.asarray(fps, dtype=np.int64),
        "algo": np.zeros(n, dtype=np.int32),
        "limit": np.full(n, LIMIT, dtype=np.int64),
        "duration": np.full(n, DURATION, dtype=np.int64),
        "remaining": np.full(n, remaining, dtype=np.int64),
        "remaining_f": np.zeros(n, dtype=np.float64),
        "t0": np.full(n, 5, dtype=np.int64),
        "status": np.zeros(n, dtype=np.int32),
        "burst": np.full(n, LIMIT, dtype=np.int64),
        "expire_at": np.full(n, expire_at, dtype=np.int64),
    }


def test_coldtier_put_pop_membership_overwrite():
    ct = ColdTier(capacity=100)
    assert ct._mask + 1 == 128  # next pow2 over capacity/0.8
    fps = np.arange(1, 51, dtype=np.int64)
    assert ct.put_rows(_mkrows(fps)) == 50
    assert ct.residents() == 50
    hits = ct.member_hits(np.array([1, 99, 50, 0], dtype=np.int64))
    assert hits.tolist() == [True, False, True, False]
    # fp 0 is the empty sentinel: never stored, never a member.
    assert ct.put_rows(_mkrows(np.array([0], dtype=np.int64))) == 0
    # A re-demotion MERGES into the row still waiting (the fresher
    # row at the least budget, never an overwrite: that would mint the
    # waiting row's consumed budget — PERF.md section 7, PR 45 (4)).
    ct.put_rows(_mkrows(fps[:5], remaining=LIMIT - 2))
    assert ct.cold_merges == 5
    got = ct.pop_rows(fps[:5])
    assert (got["remaining"] == 7 - 2).all()
    assert ct.residents() == 45
    # Absent fps simply don't appear.
    got = ct.pop_rows(np.array([1, 6, 7], dtype=np.int64))
    assert sorted(got["key_hash"].tolist()) == [6, 7]
    assert set(got) == set(COLD_FIELDS)


def test_coldtier_tombstone_compaction_and_capacity_drops():
    ct = ColdTier(capacity=64)
    fps = np.arange(1, 65, dtype=np.int64)
    assert ct.put_rows(_mkrows(fps)) == 64
    # At capacity: new demotions drop-and-count, residents hold.
    extra = np.arange(1000, 1010, dtype=np.int64)
    assert ct.put_rows(_mkrows(extra)) == 0
    assert ct.capacity_drops == 10
    assert ct.residents() == 64
    # Pop churn drives tombstones past cap/4 -> rebuild compacts; the
    # survivors stay probe-reachable afterwards.
    ct.pop_rows(fps[:40])
    assert ct.residents() == 24
    assert ct._tombstones <= ct._mask + 1
    assert ct.member_hits(fps[40:]).all()
    assert ct.put_rows(_mkrows(extra)) == 10
    assert ct.residents() == 34


def test_coldtier_prune_expired_and_snapshot_restore():
    ct = ColdTier(capacity=64)
    ct.put_rows(_mkrows(np.arange(1, 11, dtype=np.int64),
                        expire_at=1_000))
    ct.put_rows(_mkrows(np.arange(11, 21, dtype=np.int64),
                        expire_at=9_000))
    assert ct.prune_expired(now_ms=5_000) == 10
    assert ct.residents() == 10
    snap = ct.snapshot()
    assert len(snap["key_hash"]) == 10
    # Geometry-independent restore: a differently-sized store accepts
    # the snapshot verbatim.
    ct2 = ColdTier(capacity=500)
    assert ct2.restore(snap) == 10
    got = ct2.pop_rows(np.array([15], dtype=np.int64))
    assert got["remaining"].tolist() == [7]
    assert got["expire_at"].tolist() == [9_000]


# ---------------------------------------------------------------------
# policy tier: watermark hysteresis + the CMS second opinion
# ---------------------------------------------------------------------

def test_demote_need_hysteresis_vs_oracle(frozen_clock):
    be = DeviceBackend(
        DeviceConfig(num_slots=128, ways=8, batch_size=64),
        clock=frozen_clock,
    )
    tm = TierManager(
        _StubService(be),
        TierConfig(enabled=True, cold_capacity=256,
                   high_water=0.6, low_water=0.4,
                   demote_batch=64, interval_s=1.0),
    )
    S, high, low = 128, int(0.6 * 128), int(0.4 * 128)

    def oracle(occ: int) -> int:
        return 0 if occ < high else max(occ - low, 0)

    for occ in range(S + 1):
        assert tm.demote_need(occ) == oracle(occ), occ
    # The gap IS the hysteresis: right below high -> no pressure;
    # at high -> drain all the way to low, not to high.
    assert tm.demote_need(high - 1) == 0
    assert tm.demote_need(high) == high - low
    assert tm.demote_need(low) == 0


def test_watermark_loop_drains_to_low_water(frozen_clock):
    be = DeviceBackend(
        DeviceConfig(num_slots=128, ways=8, batch_size=64),
        clock=frozen_clock,
    )
    tm = TierManager(
        _StubService(be),
        TierConfig(enabled=True, cold_capacity=256,
                   high_water=0.6, low_water=0.4,
                   demote_batch=16, interval_s=1.0),
    )
    reqs = [_req(f"f{i}") for i in range(100)]
    be.check(reqs[:50])
    be.check(reqs[50:])
    occ0 = be.occupancy()
    need = tm.demote_need(occ0)
    assert need > 16
    demoted = tm.demote_once_sync()
    # Drained exactly to the LOW mark (multi-pass: batch 16 < need),
    # rows conserved into the cold store.
    assert demoted == need
    assert be.occupancy() == occ0 - need == int(0.4 * 128)
    assert tm.cold.residents() == need
    assert tm.demotes == need and tm.demote_passes >= 2
    # Hysteresis: at low water the next tick is a no-op.
    assert tm.demote_once_sync() == 0


def test_cms_second_opinion_keeps_hot_rows_resident(frozen_clock):
    """The device ranks by recency; the manager's sketch ranks by
    frequency — rows the sketch knows are hot go straight back even
    when the LRU word says otherwise."""
    be = DeviceBackend(
        DeviceConfig(num_slots=128, ways=8, batch_size=64),
        clock=frozen_clock,
    )
    tm = TierManager(
        _StubService(be),
        TierConfig(enabled=True, cold_capacity=256,
                   high_water=0.6, low_water=0.4,
                   demote_batch=128, interval_s=1.0),
    )
    reqs = [_req(f"f{i}") for i in range(100)]
    be.check(reqs[:50])
    be.check(reqs[50:])
    fps = _fps_of(be, reqs)
    hot = fps[:30]
    # Bucket-overflow at insert may have evicted a few keys; the claim
    # is about rows that were actually resident going into the tick.
    resident_hot = [
        r for r in reqs[:30]
        if be.get_cache_item(r.hash_key()) is not None
    ]
    tm.cms.update(hot, np.full(30, 1000, dtype=np.int64))
    need = tm.demote_need(be.occupancy())
    assert 0 < need <= 70
    tm.demote_once_sync()
    # Every demoted row is from the cold 70; every hot key that was
    # resident is STILL resident (the extract's hotter tail went
    # straight back).
    assert not tm.cold.member_hits(hot).any()
    assert tm.cold.member_hits(fps[30:]).sum() == need
    for r in resident_hot:
        assert be.get_cache_item(r.hash_key()) is not None


# ---------------------------------------------------------------------
# correctness tier: the demote -> touch -> promote race vs pymodel
# ---------------------------------------------------------------------

def _tier_service(frozen_clock, tcfg=None):
    from gubernator_tpu.runtime.service import Service

    svc = Service(Config(device=DEV), clock=frozen_clock)
    tm = TierManager(
        svc,
        tcfg or TierConfig(enabled=True, cold_capacity=4096,
                           high_water=0.6, low_water=0.4,
                           demote_batch=64, interval_s=1.0),
    )
    svc.tier = tm
    return svc, tm


@pytest.mark.parametrize("consumed,touch", [(4, 5), (8, 5), (10, 10)])
def test_tier_cycle_bound_and_merge_vs_pymodel(
    frozen_clock, consumed, touch
):
    """One full demote -> touch -> promote cycle, differentially: the
    fresh-window serve over-admits at most ONE limit window, and the
    promote merge lands bit-exactly on the oracle's clamped
    subtraction max(cold_remaining - consumed_fresh, 0)."""
    from gubernator_tpu.core.pymodel import PyRateLimiter

    limit = 10

    async def scenario():
        svc, tm = _tier_service(frozen_clock)
        await svc.start()
        # Expire the backend's __warmup__ probe row (duration
        # 1ms) so extractions see only the test's keys.
        frozen_clock.advance(5)
        try:
            req = _req("k", hits=consumed, limit=limit)
            r0 = (await svc.get_rate_limits([req]))[0]
            assert r0.status == Status.UNDER_LIMIT
            admitted = consumed
            cold_remaining = limit - consumed

            # Demote the (sole) row; budget moves to the cold store
            # verbatim.
            packed, rf = svc.backend.demote_extract_dispatch(
                _no_protect(), batch=8
            )()
            idx = np.flatnonzero(packed[0] != 0)
            assert len(idx) == 1
            assert int(packed[5][idx][0]) == cold_remaining
            tm.cold.put_rows(
                TierManager._cols_from_packed(packed, rf, idx)
            )
            assert svc.backend.get_cache_item(req.hash_key()) is None

            # Touch while cold: served IMMEDIATELY from a fresh HBM
            # row — the one extra window the bound allows.  note_traffic
            # (the request path) schedules the promote.
            r1 = (await svc.get_rate_limits(
                [_req("k", hits=touch, limit=limit)]
            ))[0]
            assert r1.status == Status.UNDER_LIMIT
            assert r1.remaining == limit - touch
            admitted += touch
            assert tm.cold_hits >= 1

            # The promote merges the cold budget back: remaining is
            # the oracle's clamped subtraction, never inflated.
            assert tm.drain_promotes_sync() == 1
            assert tm.promotes == 1
            assert tm.cold.residents() == 0
            item = svc.backend.get_cache_item(req.hash_key())
            expect = max(cold_remaining - touch, 0)
            assert int(item.remaining) == expect

            # Burn the merged remainder; the next hit must deny in
            # BOTH the system and the oracle continuation.
            if expect:
                r2 = (await svc.get_rate_limits(
                    [_req("k", hits=expect, limit=limit)]
                ))[0]
                assert r2.status == Status.UNDER_LIMIT
                admitted += expect
            r3 = (await svc.get_rate_limits(
                [_req("k", hits=1, limit=limit)]
            ))[0]
            assert r3.status == Status.OVER_LIMIT

            # The documented bound: ONE cycle, at most one extra
            # window (and zero extra when nothing raced).
            assert admitted <= 2 * limit
            assert admitted == consumed + touch + expect

            # Oracle cross-check: an undemoted PyRateLimiter admits
            # exactly `limit`; the cycle's overshoot is admitted -
            # limit <= limit.
            py = PyRateLimiter(clock=frozen_clock)
            py_admitted = 0
            for h in (consumed, touch, expect or 1, 1):
                pr = py.get_rate_limit(_req("k", hits=h, limit=limit))
                if pr.status == Status.UNDER_LIMIT:
                    py_admitted += h
            assert py_admitted == limit
            assert 0 <= admitted - py_admitted <= limit
        finally:
            await svc.close()

    asyncio.run(scenario())


def test_promote_failure_conserves_rows_back_to_cold(frozen_clock):
    """A promote whose inject dispatch keeps failing retries once and
    then conserves the rows back into the cold store — budget is never
    lost to an error path."""

    async def scenario():
        svc, tm = _tier_service(frozen_clock)
        await svc.start()
        # Expire the backend's __warmup__ probe row (duration
        # 1ms) so extractions see only the test's keys.
        frozen_clock.advance(5)
        try:
            await svc.get_rate_limits([_req("k", hits=4)])
            packed, rf = svc.backend.demote_extract_dispatch(
                _no_protect(), batch=8
            )()
            idx = np.flatnonzero(packed[0] != 0)
            fp = int(packed[0][idx][0])
            tm.cold.put_rows(
                TierManager._cols_from_packed(packed, rf, idx)
            )

            def boom(cols):
                raise RuntimeError("injected inject failure")

            orig = svc.backend.migrate_inject_dispatch
            svc.backend.migrate_inject_dispatch = boom
            try:
                tm.note_access(
                    np.array([fp], dtype=np.int64),
                    np.array([1], dtype=np.int64),
                )
                with pytest.raises(RuntimeError):
                    tm.drain_promotes_sync()
            finally:
                svc.backend.migrate_inject_dispatch = orig
            assert tm.promote_retries == 1
            assert tm.promote_failures == 1
            assert tm.cold.member_hits(
                np.array([fp], dtype=np.int64)
            ).all()
            # And the fingerprint is promotable again (the pending
            # set was released): the next access succeeds.
            tm.note_access(
                np.array([fp], dtype=np.int64),
                np.array([1], dtype=np.int64),
            )
            assert tm.drain_promotes_sync() == 1
            assert tm.cold.residents() == 0
        finally:
            await svc.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------
# correctness tier: the request path stays fetch-free
# ---------------------------------------------------------------------

def test_tier_request_path_fetch_free(frozen_clock):
    """A full tier cycle — demote, cold-hit serve, promote — leaves the
    fast lane's blocking_fetches ledger untouched: tier dispatches and
    their syncs run on the manager's own thread, off the request
    path."""
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        svc = Service(Config(device=DEV), clock=frozen_clock)
        await svc.start()
        # Expire the backend's __warmup__ probe row (duration
        # 1ms) so extractions see only the test's keys.
        frozen_clock.advance(5)
        fp = FastPath(svc)
        tm = TierManager(
            svc,
            TierConfig(enabled=True, cold_capacity=4096,
                       high_water=0.6, low_water=0.4,
                       demote_batch=64, interval_s=1.0),
        )
        svc.tier = tm
        try:
            reqs = [_req(f"k{i}", hits=3) for i in range(12)]
            await svc.get_rate_limits(reqs)
            before = dict(fp.blocking_fetches)

            # Demote everything, then touch the now-cold keys (served
            # from fresh rows) and drain the promotes.
            packed, rf = svc.backend.demote_extract_dispatch(
                tm._protect_grid(), 16
            )()
            idx = np.flatnonzero(packed[0] != 0)
            assert len(idx) == 12
            tm.cold.put_rows(
                TierManager._cols_from_packed(packed, rf, idx)
            )
            resps = await svc.get_rate_limits(
                [_req(f"k{i}", hits=1) for i in range(12)]
            )
            assert all(
                r.status == Status.UNDER_LIMIT for r in resps
            )
            assert tm.cold_hits >= 12
            assert tm.drain_promotes_sync() == 12
            # Merged continuation: 3 (pre-demote) + 1 (fresh) hits.
            item = svc.backend.get_cache_item(reqs[0].hash_key())
            assert int(item.remaining) == LIMIT - 4

            assert fp.blocking_fetches == before, (
                "tier cycle performed a request-path blocking fetch"
            )
        finally:
            await fp.close()
            await svc.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------
# correctness tier: checkpoint round-trips BOTH tiers
# ---------------------------------------------------------------------

def test_checkpoint_round_trip_both_tiers(frozen_clock, tmp_path):
    from gubernator_tpu.runtime.checkpoint import TableCheckpointer

    be = DeviceBackend(DEV, clock=frozen_clock)
    hot = [_req(f"hot{i}", hits=2 + i) for i in range(4)]
    colds = [_req(f"cold{i}", hits=5) for i in range(6)]
    be.check(hot + colds)
    cold_fps = _fps_of(be, colds)
    packed, rf = be.demote_extract_dispatch(_no_protect(), batch=16)()
    # Everything was extracted (one shared touch stamp); re-inject the
    # hot rows, keep the cold ones in the cold store — a realistic
    # split state.
    all_idx = np.flatnonzero(packed[0] != 0)
    cold_mask = np.isin(packed[0], cold_fps)
    ct = ColdTier(capacity=64)
    ct.put_rows(TierManager._cols_from_packed(
        packed, rf, np.flatnonzero(cold_mask)
    ))
    be.migrate_inject_dispatch(TierManager._cols_from_packed(
        packed, rf, np.setdiff1d(all_idx, np.flatnonzero(cold_mask))
    ))()
    assert be.occupancy() == 4 and ct.residents() == 6

    ck = TableCheckpointer(str(tmp_path / "ck"))
    ck.save(be, step=1, coldtier=ct)

    # A fresh daemon: same device geometry, DIFFERENT cold geometry.
    be2 = DeviceBackend(DEV, clock=frozen_clock)
    ct2 = ColdTier(capacity=500)
    step = TableCheckpointer(str(tmp_path / "ck")).restore(
        be2, coldtier=ct2
    )
    assert step == 1
    assert be2.occupancy() == 4
    assert ct2.residents() == 6
    # Hot rows restored bit-exactly...
    for r in hot:
        assert be2.get_cache_item(r.hash_key()) == be.get_cache_item(
            r.hash_key()
        )
    # ...and a restored-cold key continues its window, not a fresh
    # one: inject and check the countdown resumes at 5 consumed.
    rows = ct2.pop_rows(cold_fps[:1])
    assert be2.migrate_inject_dispatch(rows)() == (1, 0)
    resp = be2.check([_req("cold0", hits=1)])[0]
    assert resp.remaining == LIMIT - 6


# ---------------------------------------------------------------------
# observability: the tier debug block + histogram plumbing
# ---------------------------------------------------------------------

def test_tier_debug_vars_and_latency_histogram(frozen_clock):
    from gubernator_tpu.runtime.metrics import (
        LATENCY_BUCKETS,
        estimate_quantile,
    )

    be = DeviceBackend(
        DeviceConfig(num_slots=128, ways=8, batch_size=64),
        clock=frozen_clock,
    )
    tm = TierManager(
        _StubService(be),
        TierConfig(enabled=True, cold_capacity=256,
                   high_water=0.6, low_water=0.4,
                   demote_batch=64, interval_s=1.0),
    )
    be.check([_req(f"f{i}") for i in range(100)])
    tm.demote_once_sync()
    tm._observe_latency(0.002, 3)
    dv = tm.debug_vars()
    assert dv["enabled"] is True
    assert dv["cold_residents"] == tm.cold.residents() > 0
    assert dv["demotes"] == tm.demotes
    assert dv["high_water"] == 0.6 and dv["low_water"] == 0.4
    lat = dv["promote_latency"]
    assert lat["buckets"] == list(LATENCY_BUCKETS)
    assert lat["cumulative"][-1] == 3
    p99 = estimate_quantile(
        list(LATENCY_BUCKETS), lat["cumulative"], 0.99
    )
    assert 0 < p99 <= 0.01
    assert clock_mod is not None  # keep the import honest


# ---------------------------------------------------------------------
# ISSUE 46: the rebuilt tier — merge on demote, coalesced promotes, the
# threshold demoter, the warm-up
# ---------------------------------------------------------------------

SMALL = DeviceConfig(num_slots=65536, ways=8, batch_size=256)


def _demote_all(be, tm, n):
    """Every live row out of the table and into the cold store, as a
    tick does it (extract, then `put_rows`)."""
    packed, rf = be.demote_extract_dispatch(_no_protect(), 1024)()
    idx = np.flatnonzero(packed[0] != 0)
    assert len(idx) == n
    assert tm.cold.put_rows(
        TierManager._cols_from_packed(packed, rf, idx)) == n


@pytest.mark.parametrize("algorithm", [
    Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET,
])
def test_a_fresh_row_demoted_while_its_cold_row_waits_mints_nothing(
    frozen_clock, algorithm
):
    """PERF.md section 7, PR 45 (4): a key's fresh row is demoted while
    its cold row still waits for its merge.  The parent's `put_rows`
    overwrote the waiting row and minted its budget; the store now
    merges, so every answer is core/pymodel.py's from a state
    docs/tiering.md allows — here the merged one, which is the bucket
    continued: hits consumed in any tier stay consumed."""
    from gubernator_tpu.core.pymodel import PyRateLimiter

    n, c1, c2, c3 = 300, 3, 4, 2
    be = DeviceBackend(SMALL, clock=frozen_clock)
    tm = TierManager(_StubService(be), TierConfig(
        enabled=True, cold_capacity=4096, high_water=0.6, low_water=0.4,
        demote_batch=256, interval_s=1.0))
    py = PyRateLimiter(clock=frozen_clock)

    def reqs(hits):
        return [_req(f"k{i}", hits=hits, algorithm=algorithm)
                for i in range(n)]

    fps = _fps_of(be, reqs(1))
    for r in reqs(c1):
        py.get_rate_limit(r)
    be.check(reqs(c1))
    _demote_all(be, tm, n)                      # cold: LIMIT - c1
    # Touched while cold: fresh rows, the promotes queued, NOT run.
    tm.note_access(fps, np.full(n, c2))
    got = be.check(reqs(c2))
    assert all(r.remaining == LIMIT - c2 for r in got)
    for r in reqs(c2):
        py.get_rate_limit(r)
    # The fresh rows are demoted before their promote lands: each meets
    # its waiting cold row, and the row kept carries the least budget.
    _demote_all(be, tm, n)
    assert tm.cold.cold_merges == n and tm.cold.residents() == n
    assert tm.drain_promotes_sync() == n
    assert tm.cold.residents() == 0 and be.occupancy() == n
    # Now the answers are the reference's, continued: nothing minted.
    got = be.check(reqs(c3))
    for r, g in zip(reqs(c3), got):
        want = py.get_rate_limit(r)
        assert (g.status, g.remaining) == (want.status, want.remaining)
    assert got[0].remaining == LIMIT - c1 - c2 - c3


def test_coalesced_promotes_keep_the_cycle_bound(frozen_clock):
    """The demote -> touch -> promote race with the promotes of many
    RPCs coalesced into ONE pass: per key `allowed <= limit x (1 +
    cycles)`, every queued entry's rows observed from their own t0,
    launches of the size they carry, and more than three rows of one
    bucket in a pass (INSERT_ROUNDS a dispatch still holds)."""
    limit, cycles, n = 10, 3, 40
    be = DeviceBackend(
        DeviceConfig(num_slots=64, ways=8, batch_size=128),
        clock=frozen_clock,
    )                                           # 8 buckets: 5 keys each
    tm = TierManager(_StubService(be), TierConfig(
        enabled=True, cold_capacity=4096, high_water=0.9, low_water=0.4,
        demote_batch=64, interval_s=1.0))

    def reqs(hits):
        return [_req(f"k{i}", hits=hits, limit=limit) for i in range(n)]

    fps = _fps_of(be, reqs(1))
    allowed = np.zeros(n, dtype=np.int64)

    def ledger():       # the process's, shared by tests: read by difference
        return dict(tm._stages.debug_vars()["tier"]["promote"])

    row0 = ledger()

    def serve(hits):
        for j, r in enumerate(be.check(reqs(hits))):
            if r.status == Status.UNDER_LIMIT:
                allowed[j] += hits

    serve(4)
    most_waves = 0
    for cycle in range(cycles):
        resident = int(be.occupancy())
        packed, rf = be.demote_extract_dispatch(_no_protect(), 64)()
        idx = np.flatnonzero(packed[0] != 0)
        assert len(idx) == resident
        tm.cold.put_rows(TierManager._cols_from_packed(packed, rf, idx))
        # Four RPCs' worth of cold hits, queued one entry each ...
        for part in np.array_split(np.arange(n), 4):
            tm.note_access(fps[part], np.full(len(part), 4))
            frozen_clock.advance(1)
        assert len(tm._q) == 4
        serve(4)                                # ... served fresh ...
        before = int(np.sum(tm._hist))
        launches = ledger()["inject_launches"]
        cold = fps[tm.cold.member_hits(fps)]
        crowd = np.bincount(
            (cold.view(np.uint64) & np.uint64(7)).astype(np.int64))
        waves = -(-int(crowd.max()) // 3)
        most_waves = max(most_waves, waves)
        promoted = tm.drain_promotes_sync()     # ... merged in one pass
        assert promoted == resident and not tm._q and not tm._pending
        assert int(np.sum(tm._hist)) - before == promoted
        # Three rows of a bucket a launch, each launch of 128 lanes.
        assert ledger()["inject_launches"] - launches == waves
        serve(4)
    assert most_waves >= 2          # some bucket held more than three
    assert (allowed <= limit * (1 + cycles)).all(), allowed
    assert tm.promote_failures == 0 and tm.cold.capacity_drops == 0
    row = {k: v - row0[k] for k, v in ledger().items()}
    assert row["inject_lanes"] == 128 * row["inject_launches"]
    assert row["rows_injected"] == row["rows_popped"] == tm.promotes


def _random_table(rng, S, ways, now, stamps, tie=False):
    """A table of `S` slots as host arrays: live bucket rows with random
    last-touch stamps, and among them cached responses, expired rows
    and empty slots — none of which may ever be demoted."""
    from gubernator_tpu.ops.state import KIND_CACHED_RESP

    key = rng.integers(1, 1 << 62, S)
    key[rng.random(S) < 0.2] = 0                      # empty
    kind = np.where(rng.random(S) < 0.1, KIND_CACHED_RESP, 0)
    expire = np.where(rng.random(S) < 0.1, now - 5, now + 60_000)
    touched = (np.full(S, stamps[0]) if tie
               else rng.integers(stamps[0], stamps[1], S))
    z = np.zeros(S, dtype=np.int64)
    return {
        "key": key.astype(np.int64), "algo": z.astype(np.int32),
        "kind": kind.astype(np.int32), "limit": z + LIMIT,
        "duration": z + DURATION, "remaining": z + 7,
        "remaining_f": z.astype(np.float64), "t0": z + 5,
        "status": z.astype(np.int32), "burst": z + LIMIT,
        "expire_at": expire.astype(np.int64),
        "touched": touched.astype(np.int64),
    }


@pytest.mark.parametrize("take,start,batch,sample,tie", [
    (37, 0, 64, None, False),       # the exact cut-off (table = sample)
    (64, 5, 64, None, False),       # a full launch, from another block
    (500, 3, 512, None, False),     # more than a block's worth
    (4000, 0, 4096, None, False),   # more than are eligible: all leave
    (200, 9, 256, 512, False),      # a SAMPLED cut-off (1 bucket in 8)
    (300, 7, 512, None, True),      # every stamp ties: taken from `start`
])
def test_the_threshold_demoter_against_a_plain_argsort(
    monkeypatch, take, start, batch, sample, tie
):
    """`demote_extract` without its sort: the rows that leave are the
    ones a plain `argsort` of `touched` over the eligible rows would
    give, ties aside; protected fingerprints, cached responses, expired
    and empty slots never leave; the slots are cleared in the same
    dispatch and every field rides along."""
    from gubernator_tpu.ops import state as st

    if sample:
        monkeypatch.setattr(st, "DEMOTE_SAMPLE", sample)
        st.demote_extract.clear_cache() if hasattr(
            st.demote_extract, "clear_cache") else None
    rng = np.random.default_rng(take * 31 + start)
    S, ways, now = 4096, 8, 1_000_000
    host = _random_table(rng, S, ways, now, (now - 50_000, now), tie)
    live = (host["key"] != 0) & (host["expire_at"] > now)
    bucket_rows = np.flatnonzero(live & (host["kind"] == 0))
    protect = np.zeros(8, dtype=np.int64)
    protect[:5] = host["key"][rng.choice(bucket_rows, 5, replace=False)]
    eligible = np.flatnonzero(
        live & (host["kind"] == 0) & ~np.isin(host["key"], protect))
    table, packed, rf = st.demote_extract(
        st.table_from_host(host), protect, np.int64(now),
        np.int32(take), np.int32(start), ways=ways, batch=batch,
    )
    packed = np.asarray(packed)
    out = packed[0][packed[0] != 0]
    want_n = min(take, len(eligible))
    assert len(out) == want_n == len(set(out.tolist()))
    # Never: protected, cached, expired, empty.
    assert set(out.tolist()) <= set(host["key"][eligible].tolist())
    # The coldest: no row that stays is colder than one that left.
    stamp = dict(zip(host["key"].tolist(), host["touched"].tolist()))
    left = np.array([stamp[k] for k in out.tolist()])
    stay = np.array(sorted(
        stamp[k] for k in set(host["key"][eligible].tolist())
        - set(out.tolist())))
    order = np.sort(host["touched"][eligible])
    if not sample:
        # Ties aside, the very rows an argsort names.
        assert sorted(left.tolist()) == order[:want_n].tolist()
        assert not len(stay) or left.max() <= stay.min()
    else:
        # An estimated cut-off: all under it, within its margin of the
        # exact one (three standard deviations of the sample's count).
        assert left.max() <= order[min(len(order) - 1, 2 * take)]
    # Cleared in the same dispatch, and nothing else was.
    after = st.table_to_host(table)
    gone = np.isin(host["key"], out)
    assert (after["key"][gone] == 0).all()
    assert (after["expire_at"][gone] == 0).all()
    assert (after["key"][~gone] == host["key"][~gone]).all()
    # The fields ride along (DEMOTE_ROW_FIELDS order).
    sel = packed[0] != 0
    assert (packed[5][sel] == 7).all() and (packed[3][sel] == LIMIT).all()
    if tie:
        # A tie is taken from block `start` on, round the table.
        blk = min(st.DEMOTE_BLOCK, S)
        slot_of = {int(k): i for i, k in enumerate(host["key"]) if k}
        blocks = np.array([slot_of[int(k)] // blk for k in out])
        turned = (blocks - start) % (S // blk)
        assert (np.diff(turned) >= 0).all() and turned[0] == 0


def test_warmup_with_the_tier_on_leaves_nothing_to_compile(frozen_clock):
    """A daemon that is not the benchmark's: after `warmup(tier=...)` a
    first promote and a first tick over the mark compile nothing (48-51
    s under `backend._lock` on a v5e before; PERF.md section 7, PR 45
    (5))."""
    from gubernator_tpu.runtime import tracing

    tcfg = TierConfig(enabled=True, cold_capacity=4096, high_water=0.5,
                      low_water=0.3, demote_batch=16, interval_s=1.0)
    be = DeviceBackend(
        DeviceConfig(num_slots=16384, ways=8, batch_size=256),
        clock=frozen_clock,
    )
    tm = TierManager(_StubService(be), tcfg)
    be.warmup(tier=tcfg)
    # The served path's own shapes, before the count starts.
    reqs = [_req(f"w{i}") for i in range(9000)]
    for lo in range(0, len(reqs), 250):
        be.check(reqs[lo:lo + 250])
    fps = _fps_of(be, reqs)
    assert be.occupancy() > 0.5 * 16384
    before = tracing._COMPILES.count
    # The first tick over the mark: launches of the rung its need asks.
    assert tm._ladder == (16, 256)
    demoted = tm.demote_once_sync()
    assert demoted >= 0.2 * 16384 - 256 and tm.demote_passes > 1
    # The first promotes: a handful (the 128 rung), then hundreds.
    cold = fps[tm.cold.member_hits(fps)]
    assert demoted - 1 <= len(cold) <= demoted  # but the warm-up's own row
    tm.note_access(cold[:5], None)
    assert tm.drain_promotes_sync() == 5
    tm.note_access(cold[5:], None)
    assert tm.drain_promotes_sync() == len(cold) - 5
    assert tracing._COMPILES.count == before, (
        "the tier compiled on its first use after warm-up")
