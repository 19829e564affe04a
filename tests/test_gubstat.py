"""Gubstat: the table census kernel, the sampler's dispatch discipline,
the per-tenant admission ledger, and the daemon's introspection surface
(runtime/gubstat.py, ops/state.table_stats; docs/observability.md).

The load-bearing pins:
  * the census kernel is verified against a pure-numpy reference on a
    seeded table (every histogram leaf, shadow probe included);
  * the mesh census row-per-shard view agrees with the backend's own
    shard accounting, and totals are additive;
  * sampling never touches the fast lane's
    blocking_fetches ledger — introspection stays off the request path;
  * /debug/vars keeps its top-level schema (an operator dashboard
    contract — drift fails here first);
  * /debug/key is non-mutating (bit-identical re-read) and gated by
    GUBER_STATS_PEEK.
"""
from __future__ import annotations

import asyncio
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.core.config import Config, DaemonConfig, DeviceConfig
from gubernator_tpu.core.types import RateLimitReq
from gubernator_tpu.ops.state import (
    AGE_BIN_EDGES_MS,
    SHADOW_PLANES,
    init_table,
    table_from_host,
    table_stats,
    table_to_host,
)
from gubernator_tpu.runtime.gubstat import (
    PLANE_LABELS,
    TableStatsSampler,
    TenantAccounting,
    classify_plane,
)

DEV = DeviceConfig(num_slots=2048, ways=8, batch_size=64)


# ---------------------------------------------------------------------------
# The census kernel vs a pure-numpy reference.
# ---------------------------------------------------------------------------

def _numpy_census(table, shadow_fps, now, ways):
    """Independent reference for every TableStats leaf."""
    key = np.asarray(table.key)
    expire = np.asarray(table.expire_at)
    t0 = np.asarray(table.t0)
    algo = np.asarray(table.algo)
    limit = np.asarray(table.limit)
    remaining = np.asarray(table.remaining)
    remaining_f = table_to_host(table)["remaining_f"]  # float64 again
    S = key.shape[0]
    nb = S // ways

    resident = key != 0
    alive = resident & (expire > now)
    occupancy = int(resident.sum())
    live = int(alive.sum())

    per_bucket = resident.reshape(nb, ways).sum(axis=1)
    bucket_fill = np.array(
        [(per_bucket == f).sum() for f in range(ways + 1)]
    )

    edges = np.asarray(AGE_BIN_EDGES_MS)

    def hist(values):
        idx = (values[:, None] > edges[None, :]).sum(axis=1)
        return np.array([
            ((idx == b) & alive).sum() for b in range(len(edges) + 1)
        ])

    slot_age = hist(now - t0)
    ttl_remaining = hist(expire - now)

    lim_f = np.maximum(limit.astype(np.float64), 1.0)
    rem_f = np.where(algo == 1, remaining_f, remaining.astype(np.float64))
    frac = np.clip(rem_f / lim_f, 0.0, 1.0)
    fbin = np.minimum((frac * 8).astype(np.int64), 7)
    remaining_fraction = np.stack([
        np.array([
            ((fbin == b) & alive & (algo == a)).sum() for b in range(8)
        ])
        for a in (0, 1)
    ])

    fps = np.asarray(shadow_fps)
    shadow = np.zeros(fps.shape[0], dtype=np.int64)
    for p in range(fps.shape[0]):
        for fp in fps[p]:
            if fp == 0:
                continue
            b = int(np.uint64(fp) & np.uint64(nb - 1))
            row = slice(b * ways, (b + 1) * ways)
            if ((key[row] == fp) & (expire[row] > now)).any():
                shadow[p] += 1
    return (occupancy, live, occupancy - live, bucket_fill, slot_age,
            ttl_remaining, remaining_fraction, shadow)


def test_table_stats_matches_numpy_reference():
    """Seeded random table: every census leaf equals the reference —
    including shadow fingerprints planted in their home buckets, one
    expired, and one enumerated-but-absent."""
    rng = np.random.default_rng(7)
    S, ways = 512, 8
    nb = S // ways
    now = 1_000_000_000

    table = init_table(S)
    leaves = {f: a.copy() for f, a in table_to_host(table).items()}
    n_fill = 300
    slots = rng.choice(S, size=n_fill, replace=False)
    leaves["key"][slots] = rng.integers(1, 2**62, size=n_fill)
    leaves["algo"][slots] = rng.integers(0, 2, size=n_fill)
    leaves["limit"][slots] = rng.integers(1, 1000, size=n_fill)
    leaves["remaining"][slots] = rng.integers(0, 1000, size=n_fill)
    leaves["remaining_f"][slots] = rng.uniform(0, 1000, size=n_fill)
    # Ages and TTLs spanning every histogram bin, ~1/4 expired.
    leaves["t0"][slots] = now - rng.integers(0, 7_200_000, size=n_fill)
    leaves["expire_at"][slots] = now + rng.integers(
        -600_000, 3_600_000, size=n_fill
    )

    # Shadow fingerprints MUST sit in their home bucket to be found
    # (the kernel probes bucket fp & (nb-1), like the inserts did).
    def plant(fp, expire_at):
        b = int(np.uint64(fp) & np.uint64(nb - 1))
        lane = b * ways + int(rng.integers(ways))
        leaves["key"][lane] = fp
        leaves["expire_at"][lane] = expire_at
        leaves["t0"][lane] = now - 5_000
        leaves["limit"][lane] = 100
        return fp

    M = 8
    grid = np.zeros((len(SHADOW_PLANES), M), dtype=np.int64)
    grid[0, 0] = plant(10**9 + 7, now + 60_000)      # live mirror
    grid[0, 1] = plant(10**9 + 9, now - 1)           # expired mirror
    grid[1, 0] = plant(10**9 + 21, now + 60_000)     # live lease carve
    grid[3, 0] = 10**9 + 33                          # enumerated, absent
    grid[4, 0] = plant(10**9 + 41, now + 60_000)     # live region carve

    table = table_from_host(leaves)
    st = table_stats(table, grid, np.int64(now), ways=ways)

    (occ, live, exp_res, fill, age, ttl, frac, shadow) = _numpy_census(
        table, grid, now, ways
    )
    assert int(st.occupancy) == occ
    assert int(st.live) == live
    assert int(st.expired_resident) == exp_res
    np.testing.assert_array_equal(np.asarray(st.bucket_fill), fill)
    np.testing.assert_array_equal(np.asarray(st.slot_age), age)
    np.testing.assert_array_equal(np.asarray(st.ttl_remaining), ttl)
    np.testing.assert_array_equal(
        np.asarray(st.remaining_fraction), frac
    )
    np.testing.assert_array_equal(np.asarray(st.shadow_slots), shadow)
    # The planted plan itself: 1 live mirror (expired one not counted),
    # 1 lease carve, absent handoff fp not counted, 1 region carve.
    assert list(np.asarray(st.shadow_slots)) == [1, 1, 0, 0, 1]
    # Histogram masses account for exactly the live population.
    assert int(np.asarray(st.slot_age).sum()) == live
    assert int(np.asarray(st.ttl_remaining).sum()) == live
    assert int(np.asarray(st.remaining_fraction).sum()) == live


# ---------------------------------------------------------------------------
# Backend dispatch: single-device and mesh geometries.
# ---------------------------------------------------------------------------

def test_device_backend_census_matches_backend_accounting(frozen_clock):
    from gubernator_tpu.runtime.backend import DeviceBackend

    be = DeviceBackend(DEV, clock=frozen_clock)
    be.check([
        RateLimitReq(name="t", unique_key=f"k{i}", hits=1, limit=100,
                     duration=60_000)
        for i in range(20)
    ])
    st = be.table_stats_dispatch(np.zeros((4, 8), dtype=np.int64))()
    # Every leaf carries a leading shard axis (length 1 here).
    assert np.asarray(st.occupancy).shape == (1,)
    assert np.asarray(st.bucket_fill).shape == (1, DEV.ways + 1)
    assert int(np.asarray(st.occupancy).sum()) == be.occupancy() == 20
    assert int(np.asarray(st.live).sum()) == 20


def test_mesh_census_rows_match_shard_occupancy(frozen_clock):
    """The shard_map lift: one census row per shard, agreeing with the
    backend's own per-shard accounting; the replicated shadow grid
    never double-counts across shards."""
    from gubernator_tpu.parallel.sharded import MeshBackend

    cfg = DeviceConfig(
        num_slots=8 * 2048, ways=8, batch_size=64, num_shards=8
    )
    be = MeshBackend(cfg, clock=frozen_clock)
    be.check([
        RateLimitReq(name="m", unique_key=f"k{i}", hits=1, limit=100,
                     duration=60_000)
        for i in range(64)
    ])
    st = be.table_stats_dispatch(np.zeros((4, 8), dtype=np.int64))()
    per_shard = np.asarray(st.occupancy)
    assert per_shard.shape == (8,)
    assert list(per_shard) == be.shard_occupancy()
    assert int(per_shard.sum()) == 64
    assert np.asarray(st.shadow_slots).shape == (8, 4)
    assert int(np.asarray(st.shadow_slots).sum()) == 0


# ---------------------------------------------------------------------------
# Sampler dispatch discipline: off the request path, always.
# ---------------------------------------------------------------------------

def test_sampler_never_blocks_request_path(frozen_clock):
    """Sampling leaves the fast lane's blocking_fetches ledger
    untouched — the acceptance criterion that introspection dispatches
    and fetches on executor threads, never as a request-path
    device->host readback."""
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    async def scenario():
        svc = Service(Config(device=DEV), clock=frozen_clock)
        await svc.start()
        fp = FastPath(svc)
        try:
            await svc._check_local([
                RateLimitReq(name="r", unique_key=f"k{i}", hits=1,
                             limit=100, duration=60_000)
                for i in range(10)
            ])
            before = dict(fp.blocking_fetches)
            sampler = TableStatsSampler(svc)
            for _ in range(3):
                block = await sampler.sample()
            assert block["occupancy"] >= 10
            assert sampler.samples == 3 and sampler.errors == 0
            assert fp.blocking_fetches == before, (
                "census sampling performed a request-path blocking fetch"
            )
        finally:
            await fp.close()
            await svc.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# TenantAccounting: attribution, planes, cardinality bound.
# ---------------------------------------------------------------------------

def test_classify_plane_suffix_classes():
    assert classify_plane("user42") == ""
    assert classify_plane("user42.hot-mirror") == "hot-mirror"
    assert classify_plane("user42.lease-grant") == "lease-grant"
    assert classify_plane("user42.degraded-shadow") == "degraded-shadow"
    assert classify_plane("user42.handoff-shadow") == "handoff-shadow"
    assert set(PLANE_LABELS) == {
        p.lstrip(".") for p in SHADOW_PLANES
    }


class _Resp:
    def __init__(self, status):
        self.status = status


def test_tenant_accounting_attribution():
    ta = TenantAccounting(top_k=4)
    reqs = [
        RateLimitReq(name="a", unique_key="k", hits=3, limit=10,
                     duration=1000),
        RateLimitReq(name="a", unique_key="k.hot-mirror", hits=2,
                     limit=10, duration=1000),
        RateLimitReq(name="a", unique_key="k2", hits=4, limit=10,
                     duration=1000),
        RateLimitReq(name="b", unique_key="x", hits=0, limit=10,
                     duration=1000),  # zero-hit peek: never counted
    ]
    ta.record_checks(reqs, [_Resp(0), _Resp(0), _Resp(1), _Resp(0)])
    ta.record_shed("a", 5)
    (t,) = ta.top(1)
    assert t["name"] == "a"
    assert t["allowed"] == 5 and t["denied"] == 4 and t["shed"] == 5
    assert t["over_admitted"] == {"hot-mirror": 2}
    assert all(x["name"] != "b" for x in ta.top())
    assert ta.recorded_hits == 14


def test_tenant_accounting_fast_lane_vectorized():
    names = ["fast_a", "fast_a", "fast_b", "fast_c"]
    nh = TenantAccounting.name_fingerprints(names)
    decoded = []

    def decode(i):
        decoded.append(i)
        return names[i]

    ta = TenantAccounting(top_k=4)
    ta.record_fast(
        np.asarray(nh),
        np.array([2, 3, 1, 4], dtype=np.int64),
        np.array([0, 1, 0, 0], dtype=np.int64),
        np.array([True, True, True, False]),  # fast_c lane never ran
        decode,
    )
    by_name = {t["name"]: t for t in ta.top()}
    assert by_name["fast_a"]["allowed"] == 2
    assert by_name["fast_a"]["denied"] == 3
    assert by_name["fast_b"]["allowed"] == 1
    assert "fast_c" not in by_name
    # Lazy decode: at most once per admitted tenant, never per lane.
    assert sorted(decoded) == [0, 2]


def test_tenant_accounting_cardinality_bounded():
    """A name-sweep cannot grow the ledger past 4 x top_k; a true heavy
    hitter still displaces a cold resident via the sketch estimate."""
    ta = TenantAccounting(top_k=16)
    cap = ta._cap
    for i in range(cap * 3):
        ta.record(f"sweep{i}", 1, "allowed")
    assert len(ta._tenants) <= cap
    assert ta.dropped > 0
    # Heat one name well past every resident's total: the space-saving
    # rule must admit it even with the table full.
    for _ in range(50):
        ta.record("heavy", 7, "allowed")
    assert any(t["name"] == "heavy" for t in ta.top())
    assert ta.top()[0]["name"] == "heavy"


def test_tenant_accounting_publish_removes_stale_labels():
    from gubernator_tpu.runtime.metrics import Metrics

    m = Metrics()
    ta = TenantAccounting(top_k=1)
    ta.record("one", 5, "allowed")
    ta.publish(m)
    assert m.registry.get_sample_value(
        "gubernator_tenant_hits", {"name": "one", "outcome": "allowed"}
    ) == 5.0
    # "two" takes over the top-1; "one"'s series must disappear.
    ta.record("two", 50, "allowed", plane="hot-mirror")
    ta.publish(m)
    assert m.registry.get_sample_value(
        "gubernator_tenant_hits", {"name": "one", "outcome": "allowed"}
    ) is None
    assert m.registry.get_sample_value(
        "gubernator_tenant_over_admitted",
        {"name": "two", "plane": "hot-mirror"},
    ) == 50.0


# ---------------------------------------------------------------------------
# The daemon surface: /debug/vars schema, /debug/key, env plumbing.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stats_cluster():
    from gubernator_tpu.core.config import StatsConfig
    from gubernator_tpu.testing.cluster import Cluster

    c = Cluster.start(1, conf_template=DaemonConfig(
        stats=StatsConfig(interval_s=0.2),
        flightrec=True,
    ))
    from gubernator_tpu.client import V1Client

    cl = V1Client(c.daemons[0].grpc_address)
    try:
        cl.get_rate_limits([
            RateLimitReq(name="schema", unique_key=f"k{i}", hits=1,
                         limit=100, duration=60_000)
            for i in range(8)
        ])
    finally:
        cl.close()
    try:
        yield c
    finally:
        c.stop()


def _vars(d) -> dict:
    with urllib.request.urlopen(
        f"http://{d.http_address}/debug/vars", timeout=10
    ) as r:
        return json.loads(r.read())


def test_debug_vars_schema_golden(stats_cluster):
    """The top-level /debug/vars schema is an operator contract (gubtop
    and dashboards key off these blocks) — additions belong HERE too,
    removals are breaking."""
    import time

    d = stats_cluster.daemons[0]
    deadline = time.monotonic() + 15.0
    while True:
        v = _vars(d)
        if v.get("table", {}).get("samples", 0) >= 1 and \
                v["table"].get("occupancy", 0) >= 8:
            break
        assert time.monotonic() < deadline, f"sampler never caught up: {v}"
        time.sleep(0.1)

    assert set(v) == {
        "grpc_address", "http_address", "device", "backend",
        "inflight_checks",
        "global", "multi_region_sends", "peers", "circuits", "degraded",
        "hotkeys", "leases", "reshard", "tenants", "table", "fastpath",
        "stages", "stalls", "threads", "process", "tracing", "flightrec",
    }
    # The stage ledger's block (docs/observability.md): lane -> stage ->
    # {count, ms_total, ms_max, max_at_ms}, a lane's rows there from the
    # start.
    assert {"wire", "mach", "xla", "host"} <= set(v["stages"])
    assert {"handler", "ingress", "egress", "wake", "empty",
            "occupied"} <= set(v["stages"]["wire"])
    # (the machinery lane's pack row with its two counters, there at zero
    # from the start: docs/tracing.md, "When a drain cascades")
    assert set(v["stages"]["mach"]["pack"]) == {
        "count", "ms_total", "ms_max", "max_at_ms", "dup_plain",
        "dup_lanes",
    }
    assert set(v["stages"]["mach"]["queue_wait"]) == {
        "count", "ms_total", "ms_max", "max_at_ms",
    }
    # Who had the CPU, and the leaf instances far over their row's mean.
    assert set(v["process"]) == {"cpu_ms"}
    assert all(set(t) == {"cpu_ms"} for family in v["threads"].values()
               for t in family.values())
    assert isinstance(v["stalls"], list)
    # Where the daemon runs, as JAX reports it (tests are held to the
    # CPU; chip_smoke.py requires "tpu" here).
    assert set(v["device"]) == {
        "platform", "device_kind", "device_count", "table_device_ids",
        "compiled_lane", "warmup_s",
    }
    assert v["device"]["platform"] == "cpu"
    assert v["device"]["device_count"] == 8  # conftest's virtual mesh
    assert v["device"]["table_device_ids"] == [0]
    assert v["device"]["compiled_lane"] is True
    assert v["device"]["warmup_s"] > 0
    assert set(v["table"]) == {
        "samples", "errors", "interval_s", "occupancy", "live",
        "expired_resident", "per_shard_occupancy", "bucket_fill",
        "slot_age_ms", "ttl_remaining_ms", "remaining_fraction",
        "shadow_slots", "shadow_enumerated", "age_bin_edges_ms",
    }
    assert set(v["table"]["shadow_slots"]) == set(PLANE_LABELS)
    assert set(v["table"]["remaining_fraction"]) == {"token", "leaky"}
    assert v["tenants"]["top"][0]["name"] == "schema"
    assert v["tenants"]["top"][0]["allowed"] == 8


def test_debug_key_non_mutating_and_peek_gate(stats_cluster):
    d = stats_cluster.daemons[0]
    url = (
        f"http://{d.http_address}/debug/key?name=schema&key=k0"
    )
    with urllib.request.urlopen(url, timeout=10) as r:
        first = json.loads(r.read())
    assert first["found"] is True
    assert first["row"]["remaining"] == 99.0
    assert first["row"]["limit"] == 100
    assert set(first["shadows"]) == set(PLANE_LABELS)
    assert all(s is None for s in first["shadows"].values())
    with urllib.request.urlopen(url, timeout=10) as r:
        second = json.loads(r.read())
    assert first == second, "/debug/key mutated the row"

    # Absent keys answer found=false, not an error.
    with urllib.request.urlopen(
        f"http://{d.http_address}/debug/key?name=schema&key=nope",
        timeout=10,
    ) as r:
        absent = json.loads(r.read())
    assert absent["found"] is False and absent["row"] is None

    # GUBER_STATS_PEEK=0 gates the surface with 403.
    d.service.cfg.stats.peek = False
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=10)
        assert ei.value.code == 403
    finally:
        d.service.cfg.stats.peek = True


def test_stats_env_plumbing(monkeypatch):
    """GUBER_STATS_* flows env -> DaemonConfig, and every knob is
    taught in deploy/example.conf."""
    from pathlib import Path

    from gubernator_tpu.core.config import setup_daemon_config

    monkeypatch.setenv("GUBER_STATS_ENABLED", "false")
    monkeypatch.setenv("GUBER_STATS_INTERVAL", "9s")
    monkeypatch.setenv("GUBER_STATS_TOP_K", "7")
    monkeypatch.setenv("GUBER_STATS_PEEK", "false")
    conf = setup_daemon_config()
    assert conf.stats.enabled is False
    assert conf.stats.interval_s == 9.0
    assert conf.stats.top_k == 7
    assert conf.stats.peek is False

    example = Path(__file__).parent.parent / "deploy" / "example.conf"
    text = example.read_text()
    for knob in ("GUBER_STATS_ENABLED", "GUBER_STATS_INTERVAL",
                 "GUBER_STATS_TOP_K", "GUBER_STATS_PEEK"):
        assert knob in text, f"{knob} missing from deploy/example.conf"
