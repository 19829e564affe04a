"""Gubload: the open-loop scenario harness (docs/loadgen.md).

The load-bearing claims pinned here:

  1. HdrRecorder (runtime/metrics.py): log-linear HDR buckets with a
     PINNED ~1% relative error bound against exact numpy percentiles,
     merge-order independence, and a lossless wire round-trip — the
     properties that make per-worker recorders mergeable into one
     honest tail.
  2. Coordinated omission, demonstrated: the SAME schedule + the SAME
     stalling server yield a p99 that tells the truth open-loop and a
     p99 that hides the stall closed-loop.  This is why the harness
     exists.
  3. Schedule determinism: one seed reproduces byte-identical arrival
     times AND key draws (golden digests), across runs and across
     worker shardings (the union of shards IS the schedule).
  4. The scenario library: every scenario declares phases and a
     ledger-derived verdict; spec validation rejects dangling fault
     hooks.
  5. The gubload env surface parses with named-variable errors.
  6. End to end (tier-1): the steady scenario against a real 2-daemon
     cluster — exact ledger verdict, phase markers in the flight
     recorder, schema-valid artifact rows, phase attribution cleaned
     up after the run.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from gubernator_tpu.core.config import (
    DaemonConfig,
    LoadConfig,
    load_config_from_env,
)
from gubernator_tpu.loadgen import (
    SCENARIOS,
    PhaseSpec,
    PhaseTracker,
    Schedule,
    ScenarioSpec,
    build_schedules,
    closed_loop,
    open_loop,
    resolve_scenario,
    run_scenario,
    validate_row,
)
from gubernator_tpu.loadgen import schedule as schedule_mod
from gubernator_tpu.runtime.metrics import HdrRecorder, Metrics


# -- 1. the HDR recorder ------------------------------------------------


def test_hdr_bucket_reconstruction_error_bound():
    """The structural bound: 256 sub-buckets per power of two means a
    recorded value is reconstructed within 1/256 (~0.4%) relative
    error, for ANY magnitude from 1us to hours."""
    rng = np.random.default_rng(3)
    units = np.concatenate([
        np.arange(1, 2048),                        # every small bucket
        rng.integers(1, 10**10, size=4000),        # up to ~2.8 hours
    ])
    for u in units:
        u = int(u)
        back = HdrRecorder._value_s(HdrRecorder._index(u)) / (
            HdrRecorder.UNIT_S
        )
        if u < 256:
            # The first 256 buckets are exactly 1us wide: the midpoint
            # is within 0.5us ABSOLUTE (a 1us value reads 1.5us — the
            # relative bound only starts once sub-buckets saturate).
            assert abs(back - u) <= 0.5 + 1e-9, (u, back)
        else:
            assert abs(back - u) / u <= 1.0 / 256 + 1e-9, (u, back)


def test_hdr_percentiles_within_pinned_error_vs_numpy():
    """The advertised bound, pinned: heavy-tailed latencies (lognormal
    spanning ~100us..1s) estimate p50/p90/p99/p999 within 1.1% of the
    exact numpy percentile."""
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=-6.0, sigma=1.2, size=20_000)
    h = HdrRecorder()
    for v in vals:
        h.record(float(v))
    assert h.count == 20_000
    for q in (0.50, 0.90, 0.99, 0.999):
        est = h.percentile(q)
        ref = float(np.percentile(vals, q * 100))
        assert abs(est - ref) / ref <= 0.011, (q, est, ref)


def test_hdr_merge_is_commutative_and_lossless():
    """Per-worker recorders merge in ANY order to the same histogram —
    the property that lets a sharded run report one tail."""
    rng = np.random.default_rng(11)
    parts = []
    for i in range(3):
        h = HdrRecorder()
        for v in rng.lognormal(-6.0 + i, 0.8, size=2_000):
            h.record(float(v))
        parts.append(h)

    def merged(order):
        out = HdrRecorder()
        for i in order:
            out.merge(parts[i])
        return out

    a = merged([0, 1, 2])
    b = merged([2, 0, 1])
    assert a.count == b.count == 6_000
    for q in (0.5, 0.99, 0.999):
        assert a.percentile(q) == b.percentile(q)
    # Wire round-trip (workers ship dicts, the parent merges): lossless.
    c = HdrRecorder.from_dict(a.to_dict())
    assert c.count == a.count
    assert c.percentiles((0.5, 0.99)) == a.percentiles((0.5, 0.99))


def test_hdr_from_dict_rejects_mismatched_layout():
    d = HdrRecorder().to_dict()
    d["sub_bits"] = 4
    with pytest.raises(ValueError, match="sub_bits"):
        HdrRecorder.from_dict(d)


# -- 2. coordinated omission, demonstrated ------------------------------


def _uniform_schedule(n: int, duration_s: float) -> Schedule:
    return Schedule(
        times_s=np.linspace(0.0, duration_s * (1 - 1 / n), n),
        key_idx=np.zeros(n, dtype=np.int64),
    )


def test_open_loop_sees_the_stall_closed_loop_hides_it():
    """The defining regression test: a server that stalls 200ms mid-run
    (every request arriving inside the window completes at window end).
    The open-loop recorder charges every arrival scheduled inside the
    stall its full queueing delay — p99 reports the stall.  The
    closed-loop driver just... doesn't send during the stall: ONE
    sample sees it, p99 barely moves.  Same schedule, same server."""
    sched = _uniform_schedule(400, 1.0)
    STALL_AT, STALL_END = 0.30, 0.50

    def run(driver, *recorders):
        async def go():
            loop = asyncio.get_running_loop()
            t0 = loop.time()

            async def send(_key: int) -> bool:
                now = loop.time() - t0
                if STALL_AT <= now < STALL_END:
                    await asyncio.sleep(STALL_END - now + 0.001)
                else:
                    await asyncio.sleep(0.001)
                return True

            return await driver(send, sched, *recorders)

        return asyncio.run(go())

    open_lat, skew = HdrRecorder(), HdrRecorder()
    counts = run(open_loop, open_lat, skew)
    assert counts.admitted == 400 and counts.errors == 0

    closed_lat = HdrRecorder()
    run(closed_loop, closed_lat)

    open_p99 = open_lat.percentile(0.99)
    closed_p99 = closed_lat.percentile(0.99)
    # Open loop: ~80 arrivals land inside the stall; the latest-queued
    # ones waited ~200ms, so p99 must carry (most of) the stall.
    assert open_p99 > 0.10, f"open-loop p99 {open_p99:.3f}s missed it"
    # Closed loop: the single in-flight sample saw the stall; with 400
    # samples p99 is the 4th-highest — the stall vanished.
    assert closed_p99 < 0.05, (
        f"closed-loop p99 {closed_p99:.3f}s should have hidden the "
        "stall (did closed_loop stop coordinating?)"
    )
    assert open_p99 > 3 * closed_p99


# -- 3. schedule determinism --------------------------------------------

# Golden digests for flashcrowd @ seed 20260806, duration 2.0s,
# 100 rps (warm/crowd/cool).  sha256 over the nanosecond-quantized
# arrival times + key draws: if these move, a seed no longer reproduces
# the run and every recorded artifact loses its provenance.
_GOLDEN = (
    "af2e92f9ea885d1b77c6878c72329afe1d19032444badd64b4d92a02b32ff61a",
    "e410fa8d1eacf1e40bd073d354f85850668d3cf6ac6a08478718544a13d3ba20",
    "ee10aec64d0637223aee881cc72634e02ee1428f4cbd36f864c45094843bbb82",
)


def test_schedule_golden_digests():
    cfg = LoadConfig(seed=20260806, duration_s=2.0, target_rps=100.0)
    scheds = build_schedules(SCENARIOS["flashcrowd"], cfg)
    assert tuple(s.digest() for s in scheds) == _GOLDEN
    # And again: byte-identical, not merely statistically similar.
    again = build_schedules(SCENARIOS["flashcrowd"], cfg)
    assert [s.digest() for s in again] == [s.digest() for s in scheds]


def test_different_seeds_different_schedules():
    a = build_schedules(
        SCENARIOS["steady"], LoadConfig(seed=1, duration_s=1.0)
    )
    b = build_schedules(
        SCENARIOS["steady"], LoadConfig(seed=2, duration_s=1.0)
    )
    assert [s.digest() for s in a] != [s.digest() for s in b]


@pytest.mark.parametrize("workers", [1, 2, 3, 5, 8])
def test_worker_shards_partition_the_schedule(workers):
    """Sharding is a stride partition of ONE precomputed plan: the
    union of every worker's shard is exactly the schedule, for any
    worker count — so scaling the generator out never changes WHAT is
    sent, only who sends it."""
    cfg = LoadConfig(seed=99, duration_s=2.0, target_rps=150.0)
    sched = build_schedules(SCENARIOS["steady"], cfg)[1]
    shards = sched.shard(workers)
    assert len(shards) == workers
    assert sum(len(s) for s in shards) == len(sched)
    union = sorted(
        (t, k)
        for s in shards
        for t, k in zip(s.times_s.tolist(), s.key_idx.tolist())
    )
    full = sorted(zip(sched.times_s.tolist(), sched.key_idx.tolist()))
    assert union == full


def test_poisson_times_sorted_and_bounded():
    t = schedule_mod.poisson_times(seed=5, rps=200.0, duration_s=1.5)
    assert (np.diff(t) >= 0).all()
    assert t.min() >= 0 and t.max() < 1.5
    # Poisson arrivals at 200 rps x 1.5s: ~300 +- a few sigma.
    assert 200 < len(t) < 420


def test_zipf_keys_skew():
    k = schedule_mod.zipf_keys(seed=3, s=1.4, n=5_000, universe=64)
    assert k.min() >= 0 and k.max() < 64
    counts = np.bincount(k, minlength=64)
    # Rank-0 dominates and the head carries most of the mass.
    assert counts[0] == counts.max()
    assert counts[:8].sum() > 0.5 * len(k)


# -- 4. the scenario library --------------------------------------------


def test_scenario_library_complete():
    """The acceptance floor: >= 5 scenarios, each with phases, a
    verdict, and a positive key universe; fault phases only ever name
    declared hooks (validated at spec construction)."""
    assert len(SCENARIOS) >= 5
    for name, spec in SCENARIOS.items():
        assert spec.name == name
        assert spec.phases and callable(spec.verdict)
        assert spec.limit > 0 and spec.key_universe > 0
        for p in spec.phases:
            if p.fault is not None:
                assert p.fault in spec.hooks
    # The fault scenarios that make this a harness, present by name.
    assert {
        "reshard_churn", "partition_leased", "region_failover",
    } <= set(SCENARIOS)
    assert SCENARIOS["reshard_churn"].needs_cluster
    assert SCENARIOS["partition_leased"].needs_cluster
    assert SCENARIOS["region_failover"].needs_cluster
    # A multi-region scenario pins its two-region topology.
    assert len(set(SCENARIOS["region_failover"].datacenters)) == 2


def test_scenario_spec_rejects_dangling_fault_hook():
    with pytest.raises(ValueError, match="unknown fault hook"):
        ScenarioSpec(
            name="bad", description="", limit=1, window_ms=1000,
            key_universe=1, tenant="t", verdict=lambda ctx: {},
            phases=(PhaseSpec("p", 1.0, fault="nope"),),
        )


def test_resolve_scenario_names_the_env_surface():
    with pytest.raises(ValueError, match="GUBER_LOAD_SCENARIO"):
        resolve_scenario("no_such_scenario")


# -- 5. the env surface -------------------------------------------------


def test_load_config_from_env(monkeypatch):
    for k in ("GUBER_LOAD_SEED", "GUBER_LOAD_SCENARIO",
              "GUBER_LOAD_DURATION", "GUBER_LOAD_CLIENTS",
              "GUBER_LOAD_TARGET_RPS"):
        monkeypatch.delenv(k, raising=False)
    cfg = load_config_from_env()
    assert (cfg.seed, cfg.scenario) == (1337, "steady")

    monkeypatch.setenv("GUBER_LOAD_SEED", "7")
    monkeypatch.setenv("GUBER_LOAD_SCENARIO", "flashcrowd")
    monkeypatch.setenv("GUBER_LOAD_DURATION", "90s")
    monkeypatch.setenv("GUBER_LOAD_CLIENTS", "32")
    monkeypatch.setenv("GUBER_LOAD_TARGET_RPS", "2500")
    cfg = load_config_from_env()
    assert cfg.seed == 7
    assert cfg.scenario == "flashcrowd"
    assert cfg.duration_s == 90.0
    assert cfg.clients == 32
    assert cfg.target_rps == 2500.0


def test_load_config_bad_value_names_variables(monkeypatch):
    monkeypatch.setenv("GUBER_LOAD_TARGET_RPS", "fast")
    with pytest.raises(ValueError, match="GUBER_LOAD_TARGET_RPS"):
        load_config_from_env()


def test_load_config_validates():
    with pytest.raises(ValueError):
        LoadConfig(duration_s=0.0)
    with pytest.raises(ValueError):
        LoadConfig(clients=0)
    with pytest.raises(ValueError):
        LoadConfig(target_rps=-1.0)


# -- phase-linked attribution (unit) ------------------------------------


class _RecSpy:
    def __init__(self):
        self.records = []

    def record(self, kind, **fields):
        self.records.append({"kind": kind, **fields})


class _FakeDaemon:
    def __init__(self):
        self.flightrec = _RecSpy()
        self.metrics = Metrics()
        self.load_status = None


def _gauge_samples(g):
    return [
        s for m in g.collect() for s in m.samples
    ]


def test_phase_tracker_propagates_and_cleans_up():
    d = _FakeDaemon()
    tr = PhaseTracker("steady", daemons=[d])

    tr.enter("warm")
    assert d.load_status["scenario"] == "steady"
    assert d.load_status["phase"] == "warm"
    assert d.load_status["seq"] == 1
    samples = _gauge_samples(d.metrics.load_active)
    assert [(s.labels["phase"], s.value) for s in samples] == [
        ("warm", 1.0)
    ]

    tr.enter("cruise")  # implicit exit of warm
    assert d.load_status["phase"] == "cruise"
    assert d.load_status["seq"] == 2
    samples = _gauge_samples(d.metrics.load_active)
    assert [s.labels["phase"] for s in samples] == ["cruise"]

    tr.exit()
    tr.exit()  # idempotent
    assert d.load_status is None
    assert _gauge_samples(d.metrics.load_active) == []
    kinds = [
        (r["phase"], r["action"]) for r in d.flightrec.records
        if r["kind"] == "load_phase"
    ]
    assert kinds == [
        ("warm", "enter"), ("warm", "exit"),
        ("cruise", "enter"), ("cruise", "exit"),
    ]


def test_gubtop_renders_load_line():
    from gubernator_tpu.cli.gubtop import _node_lines

    lines = _node_lines("127.0.0.1:9999", {
        "backend": {}, "table": {},
        "load": {"scenario": "steady", "phase": "cruise", "seq": 2,
                 "since": time.time() - 1.0},
    })
    load_lines = [ln for ln in lines if "load:" in ln]
    assert len(load_lines) == 1
    assert "scenario=steady" in load_lines[0]
    assert "phase=cruise" in load_lines[0]


# -- 6. end to end against a real cluster -------------------------------


def test_steady_scenario_end_to_end():
    """The tier-1 acceptance run: a short seeded steady scenario on a
    2-daemon cluster — exact ledger verdict, load_phase markers in the
    flight recorder ring, schema-valid artifact rows, and every
    attribution plane cleaned up after the run."""
    from gubernator_tpu.testing import Cluster

    cfg = LoadConfig(
        seed=20260806, scenario="steady",
        duration_s=1.5, clients=4, target_rps=150.0,
    )
    cluster = Cluster.start_with(
        ["", ""],
        conf_template=DaemonConfig(flightrec=True, flightrec_ring=8192),
    )
    try:
        result = run_scenario("steady", cfg, cluster=cluster)

        v = result["verdict"]
        assert v["client_errors"] == 0
        assert v["ledger_denied"] == 0
        assert v["ledger_allowed"] == v["client_admitted"] > 0

        # Phase markers in every ring (enter AND exit, both phases).
        for d in cluster.daemons:
            ring = d.flightrec.snapshot()["ring"]
            marks = {
                (r["phase"], r["action"]) for r in ring
                if r.get("kind") == "load_phase"
                and r.get("scenario") == "steady"
            }
            assert {
                ("warm", "enter"), ("warm", "exit"),
                ("cruise", "enter"), ("cruise", "exit"),
            } <= marks
            # Attribution cleaned up: no phase is "active" post-run.
            assert d.load_status is None
            assert _gauge_samples(d.metrics.load_active) == []

        # Artifact rows: schema-valid, per-phase + overall.
        artifact = result["artifact"]
        rows = artifact["results"]
        assert {r["phase"] for r in rows} == {
            "warm", "cruise", "overall"
        }
        for row in rows:
            validate_row(row)
    finally:
        cluster.stop()
