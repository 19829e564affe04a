"""Hot-key survival plane (ISSUE 8 acceptance).

Unit tier: the host-side CMS estimator, promote/demote hysteresis
pinned against a pure-python pymodel oracle on seeded
hovering-at-the-threshold streams, the next-N-arcs mirror set, and the
GUBER_HOTKEY_* env parse.

Cluster tier (3 real daemons, one loop): owner SLO pressure advertised
on RPC trailing metadata activates mirroring on the key's next-arc
replica with admission bounded by limit x (1 + mirrors x fraction);
mirroring is provably inactive without measured pressure; SLO shedding
drops priority classes in order; and the hot-set collapses (mirror
slot dropped) after the pressure clears — the full lifecycle of
docs/hotkeys.md.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.core.config import (
    DaemonConfig,
    HotKeyConfig,
    hotkey_config_from_env,
)
from gubernator_tpu.core.hashing import key_hash64
from gubernator_tpu.core.types import RateLimitReq, Status
from gubernator_tpu.net.replicated_hash import ReplicatedConsistentHash
from gubernator_tpu.runtime.hotkey import (
    MIRROR_SUFFIX,
    RATIO_CAP,
    HotKeyTracker,
    fp64,
)
from gubernator_tpu.runtime.sketch_backend import HostCMS
from gubernator_tpu.testing.cluster import Cluster

LIMIT = 200
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


# ---------------------------------------------------------------------
# unit tier: HostCMS
# ---------------------------------------------------------------------

def test_host_cms_never_underestimates():
    rng = np.random.default_rng(7)
    cms = HostCMS(depth=4, width=256)  # small width: force collisions
    keys = rng.integers(1, 2**62, size=200, dtype=np.int64)
    weights = rng.integers(1, 50, size=200, dtype=np.int64)
    exact = {}
    for k, w in zip(keys, weights):
        exact[int(k)] = exact.get(int(k), 0) + int(w)
    cms.update(keys, weights)
    uniq = np.fromiter(exact, dtype=np.int64, count=len(exact))
    est = cms.estimate(uniq)
    for k, e in zip(uniq, est):
        assert e >= exact[int(k)], (k, e, exact[int(k)])
    cms.clear()
    assert not cms.estimate(uniq).any()


def test_host_cms_rejects_bad_geometry():
    with pytest.raises(ValueError):
        HostCMS(width=1000)  # not a power of two
    with pytest.raises(ValueError):
        HostCMS(depth=0)


# ---------------------------------------------------------------------
# unit tier: hysteresis vs a pymodel oracle
# ---------------------------------------------------------------------

class _HysteresisOracle:
    """Pure-python pymodel of the documented promote/demote window
    semantics (docs/hotkeys.md): score = exact_count/window x ratio;
    promote after `promote_windows` CONSECUTIVE windows at/over the
    threshold, demote after `demote_windows` consecutive below."""

    def __init__(self, cfg, ratio_of):
        self.cfg = cfg
        self.ratio_of = ratio_of
        self.hot = set()
        self.streak = {}
        self.miss = {}

    def window(self, counts):
        thr = self.cfg.threshold
        scores = {
            k: (c / self.cfg.window_s)
            * min(max(self.ratio_of(k), 0.0), RATIO_CAP)
            for k, c in counts.items()
        }
        for k in list(self.hot):
            if scores.get(k, 0.0) >= thr:
                self.miss[k] = 0
            else:
                self.miss[k] = self.miss.get(k, 0) + 1
                if self.miss[k] >= self.cfg.demote_windows:
                    self.hot.discard(k)
                    self.miss.pop(k, None)
        new_streak = {}
        for k, sc in scores.items():
            if k in self.hot or sc < thr:
                continue
            run = self.streak.get(k, 0) + 1
            if (
                run >= self.cfg.promote_windows
                and len(self.hot) < self.cfg.max_hot
            ):
                self.hot.add(k)
                self.miss[k] = 0
            else:
                new_streak[k] = run
        self.streak = new_streak


def _drive_windows(cfg, ratio_of, stream):
    """Run tracker and oracle over `stream` (a list of per-window
    {fp: count} dicts) on a manual clock; assert the hot-sets agree
    after EVERY window."""
    clock = [0.0]
    tr = HotKeyTracker(cfg, time_fn=lambda: clock[0])
    tr.pressure_fn = ratio_of
    oracle = _HysteresisOracle(cfg, ratio_of)
    for counts in stream:
        if counts:
            fps = np.fromiter(counts, dtype=np.int64, count=len(counts))
            hits = np.fromiter(
                counts.values(), dtype=np.int64, count=len(counts)
            )
            tr.observe(fps, hits)
        clock[0] += cfg.window_s
        # The tracker evaluates a finished window at the NEXT roll —
        # force it so idle windows count too (daemon: poll()).
        tr.poll()
        oracle.window(counts)
        assert set(tr.hot_set) == oracle.hot, (
            f"hot-set diverged from oracle: "
            f"{sorted(tr.hot_set)} vs {sorted(oracle.hot)}"
        )
    return tr, oracle


def test_hysteresis_matches_pymodel_oracle_at_threshold():
    """Seeded frequency streams hovering AT the threshold: the tracker's
    promote/demote decisions must match the oracle window for window —
    in particular the set cannot flap faster than the hysteresis
    windows allow."""
    cfg = HotKeyConfig(
        threshold=100.0, window_s=1.0, promote_windows=2,
        demote_windows=3, max_hot=1024,
    )
    rng = np.random.default_rng(1337)
    keys = [fp64(int(h)) for h in rng.integers(1, 2**62, size=40)]
    stream = []
    for _w in range(60):
        counts = {}
        for k in keys:
            # Hover around threshold*window: ~half the windows over.
            counts[k] = int(rng.integers(70, 131))
        stream.append(counts)
    tr, oracle = _drive_windows(cfg, lambda fp: 1.0, stream)
    # The streams hover, so SOMETHING must have promoted and demoted —
    # otherwise the test proved nothing.
    assert tr.promotions > 0 and tr.demotions > 0


def test_hysteresis_alternating_stream_never_promotes():
    """A key over the threshold only in alternating windows can never
    accumulate promote_windows=2 consecutive hits — no flapping."""
    cfg = HotKeyConfig(
        threshold=100.0, window_s=1.0, promote_windows=2,
        demote_windows=2, max_hot=8,
    )
    k = fp64(0xDEADBEEF)
    stream = [
        {k: 200 if w % 2 == 0 else 10} for w in range(20)
    ]
    tr, _ = _drive_windows(cfg, lambda fp: 1.0, stream)
    assert tr.promotions == 0
    assert not tr.hot_set


def test_hysteresis_sustained_promotes_then_demotes_on_schedule():
    cfg = HotKeyConfig(
        threshold=100.0, window_s=1.0, promote_windows=3,
        demote_windows=2, max_hot=8,
    )
    k = fp64(42)
    stream = [{k: 500}] * 5 + [{k: 1}] * 3
    clock = [0.0]
    tr = HotKeyTracker(cfg, time_fn=lambda: clock[0])
    tr.pressure_fn = lambda fp: 1.0
    hot_after = []
    for counts in stream:
        tr.observe(
            np.array([k], dtype=np.int64),
            np.array(list(counts.values()), dtype=np.int64),
        )
        clock[0] += 1.0
        tr.poll()
        hot_after.append(bool(tr.hot_set))
    # Promoted exactly after the 3rd over-threshold window, demoted
    # exactly after the 2nd under-threshold one.
    assert hot_after == [False, False, True, True, True, True, False,
                         False]


def test_promotion_requires_measured_pressure():
    """The 1909.08969 gate: with owner pressure 0 the score is 0 at ANY
    rate — mirroring's precondition is provably inactive on a healthy
    cluster."""
    cfg = HotKeyConfig(
        threshold=10.0, window_s=1.0, promote_windows=1,
        demote_windows=1, max_hot=8,
    )
    k = fp64(777)
    stream = [{k: 10_000_000}] * 5
    tr, _ = _drive_windows(cfg, lambda fp: 0.0, stream)
    assert tr.promotions == 0 and not tr.hot_set


def test_idle_windows_demote():
    """Traffic stops entirely: poll() must still collapse the set."""
    cfg = HotKeyConfig(
        threshold=10.0, window_s=1.0, promote_windows=1,
        demote_windows=2, max_hot=8,
    )
    k = fp64(5)
    clock = [0.0]
    tr = HotKeyTracker(cfg, time_fn=lambda: clock[0])
    tr.pressure_fn = lambda fp: 1.0
    tr.observe(np.array([k], dtype=np.int64),
               np.array([100], dtype=np.int64))
    clock[0] += 1.0
    tr.poll()
    assert tr.hot_set
    clock[0] += 5.0  # several empty windows pass un-observed
    tr.poll()
    assert not tr.hot_set


# ---------------------------------------------------------------------
# unit tier: next-N-arcs mirror set
# ---------------------------------------------------------------------

class _FakePeer:
    def __init__(self, addr):
        self._addr = addr

    def info(self):
        return self

    @property
    def grpc_address(self):
        return self._addr


def test_get_n_next_arcs_distinct_deterministic():
    addrs = [f"10.0.0.{i}:81" for i in range(6)]
    p1 = ReplicatedConsistentHash()
    p2 = ReplicatedConsistentHash()
    for a in addrs:
        p1.add(_FakePeer(a))
    for a in reversed(addrs):  # insertion order must not matter
        p2.add(_FakePeer(a))
    for i in range(50):
        key = f"k{i}"
        g1 = [p.info().grpc_address for p in p1.get_n(key, 3)]
        g2 = [p.info().grpc_address for p in p2.get_n(key, 3)]
        assert g1 == g2
        assert len(set(g1)) == 3
        assert g1[0] == p1.get(key).info().grpc_address
    # Pool smaller than n: everyone, owner first.
    assert len(p1.get_n("x", 99)) == len(addrs)


def test_hotkey_env_parse(monkeypatch):
    monkeypatch.setenv("GUBER_HOTKEY_THRESHOLD", "123.5")
    monkeypatch.setenv("GUBER_HOTKEY_MIRRORS", "2")
    monkeypatch.setenv("GUBER_HOTKEY_FRACTION", "0.1")
    monkeypatch.setenv("GUBER_HOTKEY_WINDOW", "500ms")
    monkeypatch.setenv("GUBER_HOTKEY_SHED_PRIORITIES", "bulk.*, mid.*")
    cfg = hotkey_config_from_env()
    assert cfg.threshold == 123.5
    assert cfg.mirrors == 2
    assert cfg.fraction == 0.1
    assert cfg.window_s == 0.5
    assert cfg.shed_priorities == ["bulk.*", "mid.*"]
    monkeypatch.setenv("GUBER_HOTKEY_FRACTION", "1.5")
    with pytest.raises(ValueError, match="hot-key"):
        hotkey_config_from_env()


# ---------------------------------------------------------------------
# cluster tier: the full lifecycle on 3 real daemons
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def hot_cluster():
    conf = DaemonConfig(
        flightrec=True,
        hotkey=HotKeyConfig(
            threshold=50.0, mirrors=1, fraction=0.25, window_s=0.3,
            promote_windows=2, demote_windows=2, pressure_ttl_s=1.5,
            shed_cooldown_s=0.4, shed_priorities=["bulk.*", "mid.*"],
        ),
    )
    c = Cluster.start_with(["", "", ""], conf_template=conf)
    for d in c.daemons:
        # No ORGANIC pressure on a CPU run (its latencies would breach
        # the 2ms production target constantly); tests lower the target
        # on purpose and restore it.
        d.flightrec.slo_p99_ms = 1e9
        d.flightrec.window_s = 2.0
    yield c
    c.stop()


def _find_mirrored_key(cluster):
    """A key owned by another daemon whose FIRST next-arc mirror is
    daemon 0 (every peer derives the same list from the shared ring)."""
    d0 = cluster.daemons[0]
    for i in range(2000):
        k = f"h{i}"
        cand = d0.service.local_picker.get_n(f"hot_{k}", 2)
        if not cand[0].info().is_owner and cand[1].info().is_owner:
            return k
    raise AssertionError("no suitable hot key found")


def test_hotkey_lifecycle_mirror_bound_and_collapse(hot_cluster):
    c = hot_cluster
    d0 = c.daemons[0]
    key = _find_mirrored_key(c)
    hash_key = f"hot_{key}"
    owner = c.owner_daemon_of(hash_key)
    owner_peer = d0.service.get_peer(hash_key)

    cl = V1Client(d0.grpc_address)
    try:
        def burst(n=50, name="hot", uk=key):
            return cl.get_rate_limits([
                RateLimitReq(name=name, unique_key=uk, hits=1,
                             limit=LIMIT, duration=DURATION)
                for _ in range(n)
            ], timeout=30)

        # Every phase's admissions of the hot key land in ONE duration
        # window, so they all count against the over-admission bound.
        admitted = 0
        mirror_meta = 0

        # -- phase 0: hot traffic, NO pressure -> provably no widening.
        for _ in range(4):
            admitted += sum(
                1 for r in burst(40)
                if not r.error and r.status == Status.UNDER_LIMIT
            )
            time.sleep(0.1)
        assert d0.service.mirror_served == 0
        assert len(d0.service.active_mirror_fps()) == 0

        # -- phase 1: owner breaches its SLO -> trailing-metadata
        # advertisement -> promotion -> mirror serving.
        owner.flightrec.slo_p99_ms = 1e-4  # every real RPC breaches

        def storm_round():
            nonlocal admitted, mirror_meta
            for r in burst(50):
                if not r.error and r.status == Status.UNDER_LIMIT:
                    admitted += 1
                if (r.metadata or {}).get("hotkey") == "mirror":
                    mirror_meta += 1

        def activated():
            storm_round()
            assert mirror_meta > 0, "mirroring never activated"

        until_pass(activated, timeout=20.0, interval=0.05)
        # The owner's pressure reached d0 as trailing metadata.
        assert owner_peer.pressure_ratio() >= 1.0
        # The overloaded-but-alive owner surfaces as pressure, not as
        # fully healthy (satellite: breaker/degraded interplay).
        assert owner_peer.circuit_snapshot().get("pressure", 0) >= 1.0
        h = c.run(d0.service.health_check())
        assert "Pressure on peer" in h.message
        # ... while the breaker plane stays closed: alive, not dead.
        assert owner_peer.circuit_state_name() in ("closed", "disabled")

        # -- the over-admission bound: saturate both allowances.
        for _ in range(10):
            storm_round()
        bound = LIMIT * (1 + 1 * 0.25)
        assert admitted <= bound, (admitted, bound)
        assert admitted >= LIMIT * 0.75  # the key actually saturated

        # -- SLO shedding on the pressured owner: priority-ordered.
        until_pass(lambda: _assert_owner_sheds(owner), timeout=10.0)

        # -- phase 2: pressure clears -> widening collapses -> the
        # mirror slot is dropped (RESET_REMAINING on demotion).
        owner.flightrec.slo_p99_ms = 1e9

        def collapsed():
            burst(5, name="probe", uk="p1")  # keep windows rolling
            assert not d0.service.hotkeys.hot_set
            assert len(d0.service.active_mirror_fps()) == 0

        until_pass(collapsed, timeout=25.0, interval=0.2)
        assert d0.service.hotkeys.demotions >= 1

        def slot_dropped():
            assert d0.service.backend.get_cache_item(
                hash_key + MIRROR_SUFFIX
            ) is None

        until_pass(slot_dropped, timeout=10.0)
    finally:
        owner.flightrec.slo_p99_ms = 1e9
        cl.close()


def _assert_owner_sheds(owner):
    cl = V1Client(owner.grpc_address)
    try:
        # The owner judges its SLO from the requests of its 2 s window and
        # from no fewer than `min_samples` (20) of them: one probe a tenth
        # of a second is under that, so once the storm's samples have left
        # the window — at once, on a slow host — the breach run would end
        # before `shed_cooldown_s`.  Reads that keep the window fed,
        # whatever the machine's pace.
        for _ in range(25):
            cl.get_rate_limits([
                RateLimitReq(name="keep", unique_key="kp", hits=0,
                             limit=1000, duration=DURATION),
            ], timeout=30)
        rs = cl.get_rate_limits([
            RateLimitReq(name="bulk.jobs", unique_key="b", hits=1,
                         limit=1000, duration=DURATION),
            RateLimitReq(name="keep", unique_key="kp", hits=1,
                         limit=1000, duration=DURATION),
        ], timeout=30)
    finally:
        cl.close()
    assert (rs[0].metadata or {}).get("shed") == "pressure", rs[0]
    assert rs[0].status == Status.OVER_LIMIT
    assert int(rs[0].metadata["retry_after_ms"]) > 0
    # The unmatched name is NEVER shed, whatever the level.
    assert (rs[1].metadata or {}).get("shed") is None, rs[1]


def test_shed_levels_escalate_priority_ordered(hot_cluster):
    """Level math directly: sustained breach below cooldown sheds
    nothing; one cooldown sheds class 0; two shed classes 0 and 1; the
    unmatched class never sheds."""
    c = hot_cluster
    d = c.daemons[2]
    svc = d.service
    fr = d.flightrec
    try:
        fr._pressure_since = None
        assert svc.shed_level() == 0
        fr._pressure_since = time.monotonic() - 0.5  # cooldown 0.4s
        assert svc.shed_level() == 1
        assert svc.shed_priority("bulk.x") == 0
        assert svc.shed_priority("mid.x") == 1
        assert svc.shed_priority("keep") == 2
        fr._pressure_since = time.monotonic() - 0.9
        assert svc.shed_level() == 2
        fr._pressure_since = time.monotonic() - 100.0
        assert svc.shed_level() == 2  # capped at the class count
    finally:
        fr._pressure_since = None


def test_mirror_serve_deny_all_and_reconcile(hot_cluster):
    """Direct _mirror_serve contract: limit<=0 stays deny-all with no
    mirror slot; a positive limit admits at most fraction x limit from
    the local slot and queues the ORIGINAL hits toward the owner
    through the GLOBAL async-hit machinery."""
    c = hot_cluster
    d0 = c.daemons[0]
    svc = d0.service
    peer = next(
        p for p in svc.peer_list() if not p.info().is_owner
    )
    deny = RateLimitReq(name="mz", unique_key="deny", hits=1, limit=0,
                        duration=DURATION)
    resp = c.run(svc._mirror_serve(deny, peer))
    assert resp.status == Status.OVER_LIMIT and resp.remaining == 0
    assert resp.metadata["hotkey"] == "mirror"
    assert svc.backend.get_cache_item(
        deny.hash_key() + MIRROR_SUFFIX
    ) is None

    # A key some OTHER daemon owns, so the reconcile flush is a real
    # cross-peer RPC.
    uk = next(
        f"pos{i}" for i in range(200)
        if not svc.get_peer(f"mz_pos{i}").info().is_owner
    )
    req = RateLimitReq(name="mz", unique_key=uk, hits=1, limit=100,
                       duration=DURATION)
    owner_peer = svc.get_peer(req.hash_key())
    allowed = 0
    for _ in range(60):
        r = c.run(svc._mirror_serve(req, owner_peer))
        assert r.error == ""
        if r.status == Status.UNDER_LIMIT:
            allowed += 1
    assert allowed == 25  # fraction 0.25 x limit 100
    # The ORIGINAL hits reconcile to the owner through the GLOBAL
    # async-hit flush: its authoritative row converges on all 60.
    owner_d = c.owner_daemon_of(req.hash_key())

    def reconciled():
        it = owner_d.service.backend.get_cache_item(req.hash_key())
        assert it is not None
        assert 100 - int(it.remaining) == 60, it
    until_pass(reconciled, timeout=10.0)


def test_tracker_debug_vars_and_gauge(hot_cluster):
    d0 = hot_cluster.daemons[0]
    dv = d0.service.hotkeys.debug_vars()
    assert dv["enabled"] is True
    assert {"hot", "promotions", "demotions"} <= set(dv)
