"""PR 28's additions, all files: the configuration mesh4-global8k, the
traffic mixes global250.closed and zipf99.batch.closed, the cell
mesh4-global8k.global250.closed and the eight per-layer metrics of the
GLOBAL plane.  The data validates, the engine lane's fetch list is
computed from the traffic's bound, every new metric evaluates on a pair
of scrapes that has the program's new counters and returns nothing on one
that lacks them (the parent commit), and the cell rehearses on the CPU:
sound, `correct: false` only for not being a TPU run; with the daemon
broken underneath, not correct by the comparison with the reference.
(zipf99.batch.closed has no cell: exact10m.zipf99.batch.closed was
measured and left out, PERF.md section 7; its traffic file stays, as
rpc2.burst's did.)"""
import copy
import json
import os
import subprocess
import sys

import pytest

from lib import readers, schedule, shapes, spec
from lib.global_sync import hbm_bytes, ici_bytes, sync_rows

BM = spec.benchmark()
CELL2 = "mesh4-global8k.global250.closed"
NEW_METRICS = [
    "global_sync_tick_ms.mesh", "global_sync_share.mesh",
    "global_keys_per_tick.mesh", "global_chunks_per_tick.mesh",
    "engine_checks_per_drain.mesh", "engine_rounds_per_drain.mesh",
    "global_sync_device_ms.mesh", "global_sync_roofline_share.mesh",
]


def _cfg(name):
    return spec.load_json(spec.config_path(BM, name))


def test_the_configuration_is_mesh4_10m_but_for_its_global_keys():
    new, old = _cfg("mesh4-global8k"), _cfg("mesh4-10m")
    spec.check_config(new, "mesh4-global8k")
    for k in ("daemon", "background_timers_s", "guarantees", "chips"):
        assert new[k] == old[k], k
    # The one cut, of scale, and said: ISSUE 28's 65,536 tenants.
    assert new["reduced"] == ["global_keys"] and old["reduced"] == []
    assert "65,536" in new["assumed"]["global_keys"]
    diff = {k for k in old["universe"]
            if new["universe"][k] != old["universe"][k]}
    assert diff == {"global_keys"}
    assert new["universe"]["global_keys"] == 8 * old["universe"]["global_keys"]
    assert not [k for k in new["daemon"] if k not in old["daemon"]]


def test_the_traffic_files_are_batch_closed_but_for_what_they_say():
    base = spec.load_json(spec.traffic_path("batch.closed"))
    skip = {"name", "why", "notes"}
    for name, changed in (
        ("global250.closed", {"global_per_rpc": 250}),
        ("zipf99.batch.closed", {"keys": {
            "distribution": "zipfian", "constant": 0.99, "scramble": True}}),
    ):
        t = spec.load_json(spec.traffic_path(name))
        spec.check_traffic(t, name)
        assert {k: v for k, v in t.items() if k not in skip} == {
            **{k: v for k, v in base.items() if k not in skip}, **changed}
        assert not spec.can_peek(t)


def test_the_cells_and_what_they_report():
    spec.check_benchmark(BM)
    cells = {w["name"]: w for w in BM["workloads"]}
    assert cells[CELL2]["chips"] == 4
    assert (cells[CELL2]["config"], cells[CELL2]["traffic"]) == (
        "mesh4-global8k", "global250.closed")
    assert "zipf99.batch.closed" not in {w["traffic"] for w in cells.values()}
    # Two four-chip cells at PR 28; held by membership and by the quota,
    # not by count: later cells are additions.
    four = {w["name"] for w in BM["workloads"] if w["chips"] == 4}
    assert {CELL2, "mesh4-10m.batch.closed"} <= four
    assert len(four) <= max(1, len(BM["workloads"]) // 2)
    assert len(BM["workloads"]) >= 5     # a later cell is an addition

    def names(group, cell):
        return {m["name"] for m in spec.metrics_of(BM, group, cell)}

    assert names("end_to_end", CELL2) == {"decisions_per_s", "setup_s"}
    # The cell reports what the mesh cell reports; the eight new metrics
    # are the two mesh cells' alone.
    assert names("per_layer", CELL2) == names(
        "per_layer", "mesh4-10m.batch.closed")
    assert set(NEW_METRICS) <= names("per_layer", CELL2)
    for m in BM["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == ["mesh4-10m.batch.closed", CELL2]
            assert m["layer"] == "collectives"


def test_the_engine_lanes_fetch_list_is_computed_from_the_bound():
    t = spec.load_json(spec.traffic_path("global250.closed"))
    u = _cfg("mesh4-global8k")["universe"]
    lanes = shapes.round_lane_bounds(t, u, 4096, 128)
    assert lanes["engine"] == [
        min(4096, u["global_keys"], 4000 // (r + 1)) for r in range(16)]
    assert min(lanes["engine"]) > 128        # every round above tier 128
    assert len(shapes.tier_sequences(lanes["engine"], [128, 4096])) == 150
    assert len(shapes.tier_sequences(lanes["mach"], [128, 4096])) == 11
    # batch.closed's own 8 GLOBAL checks an RPC: 15 programs.
    old = shapes.round_lane_bounds(
        spec.load_json(spec.traffic_path("batch.closed")),
        _cfg("mesh4-10m")["universe"], 4096, 128)
    assert len(shapes.tier_sequences(old["engine"], [128, 4096])) == 15
    # The zipf mix has no peek: batch.closed's 17 on one chip.
    z = shapes.round_lane_bounds(
        spec.load_json(spec.traffic_path("zipf99.batch.closed")),
        _cfg("exact10m-1chip")["universe"], 4096, 128)
    assert "engine" not in z
    assert len(shapes.tier_sequences(z["mach"], [128, 4096])) == 17


def test_a_third_of_the_plans_checks_are_global():
    t = spec.load_json(spec.traffic_path("global250.closed"))
    u = dict(_cfg("mesh4-global8k")["universe"], keys=200000)
    plan = schedule.build_plan(t, u, 28000001, 2.0)
    g = plan.key_index < u["global_keys"]
    starts = plan.offsets[:-1]
    assert all(g[s:s + 250].all() for s in starts[:50])
    assert 0.30 < g.mean() < 0.37


# -- the metrics ------------------------------------------------------------

def _vars(ticks, tick_ms, keys, chunks, drains, checks, wall_ms,
          counters=True):
    tick = {"count": ticks, "ms_total": tick_ms, "ms_max": 1.0}
    pack = {"count": drains, "ms_total": 1.0, "ms_max": 1.0}
    if counters:
        tick.update(keys=keys, chunks=chunks)
        pack.update(checks=checks, rounds=3 * drains)
    out = {"stages": {
        "global": {"sync_tick": tick},
        "engine": {"pack": pack, "drain": {"count": drains, "ms_total": 9.0,
                                            "ms_max": 1.0}},
        "wire": {"empty": {"count": 1, "ms_total": 0.25 * wall_ms,
                           "ms_max": 1.0},
                 "occupied": {"count": 1, "ms_total": 0.75 * wall_ms,
                              "ms_max": 1.0}},
    }, "global": {}}
    if counters:
        out["global"]["engine"] = {"sync_program": {
            "collective": "psum", "shards": 4, "delta_slots": 256}}
    return out


TRACE = {
    "chips_traced": 4, "busy_s": 1.2,
    "modules": {"jit__local": [400, 1.0], "jit__global_sync": [80, 0.2]},
    "host_stages": {"gub.global.sync_tick": [10, 0.5]},
}


def _ctx(counters=True, trace=TRACE):
    snaps = tuple(
        {"vars": v, "metrics": [], "flat": {}}
        for v in (_vars(10, 300.0, 9000, 40, 20, 30000, 5000.0, counters),
                  _vars(30, 1100.0, 49000, 200, 60, 150000, 15000.0,
                        counters)))
    return {"snaps": snaps, "flat": {}, "trace": trace,
            "device": {"kind": "TPU v5 lite"}}


def _value(name, ctx):
    return readers.evaluate(spec.load_json(spec.layer_metric_path(name)), ctx)


def test_every_new_metric_reads_a_program_that_has_the_counters():
    want = {
        "global_sync_tick_ms.mesh": 800.0 / 20,
        "global_sync_share.mesh": 100 * 800.0 / 10000.0,
        "global_keys_per_tick.mesh": 40000 / 20,
        "global_chunks_per_tick.mesh": 160 / 20,
        "engine_checks_per_drain.mesh": 120000 / 40,
        "engine_rounds_per_drain.mesh": 3.0,
        # 0.2 s per chip of the sync program over 10 ticks
        "global_sync_device_ms.mesh": 20.0,
        # 40,000 keys in 160 launches: 250 keys a launch, whose HBM need
        # (250 / 4 x 592 + 250 x 212 = 90,000 B, 0.11 us) is under the
        # full delta grid's ICI need (215,040 B at 200 GB/s = 1.075 us),
        # against 0.2 s / 20 launches a chip
        "global_sync_roofline_share.mesh": 100 * (215040 / 200e9) / 0.01,
    }
    assert set(want) == set(NEW_METRICS)
    for name, value in want.items():
        assert _value(name, _ctx()) == pytest.approx(value), name
    assert _value("global_sync_roofline_share.mesh", _ctx()) <= 100


def test_the_parent_program_reports_what_it_has_and_nothing_else():
    """No counters on the tick, no sync program of its own name, no
    `global.engine` block: the tick's milliseconds are there (the stage
    dates from PR 25), everything else is left out, nothing raises."""
    parent_trace = copy.deepcopy(TRACE)
    parent_trace["modules"] = {"jit__local": [480, 1.2]}
    ctx = _ctx(counters=False, trace=parent_trace)
    got = {n: _value(n, ctx) for n in NEW_METRICS}
    assert {n for n, v in got.items() if v is not None} == {
        "global_sync_tick_ms.mesh", "global_sync_share.mesh"}
    # Nothing traced at all (an untraced run never asks, but a reader
    # must not raise).
    for n in NEW_METRICS[-2:]:
        assert _value(n, _ctx(trace={})) is None
    for n in NEW_METRICS:
        m = spec.load_json(spec.layer_metric_path(n))
        spec.check_layer_metric(m, n)


def test_ici_binds_at_this_geometry_as_the_metric_file_says():
    b = ici_bytes(4, 256, 30, 40)
    assert b == 2 * 0.75 * 4 * 256 * 120 + 3 * 256 * 40
    r = spec.load_json(spec.layer_metric_path(
        "global_sync_roofline_share.mesh"))["read"]
    assert r["bound"] == "ici"
    # A full grid of 4 x 256 keys still needs less of HBM than of ICI.
    for keys in (44, 727, 1024):
        assert hbm_bytes(keys, 4, r) / 819e9 < b / (1600e9 / 8)
    assert hbm_bytes(727, 4, r) == 727 / 4 * 2 * 296 + 727 * 212
    assert sync_rows(TRACE, "^jit__global_sync") == (80, 0.2)
    assert sync_rows({}, "^jit__global_sync") == (0, 0)


# -- the rehearsals -----------------------------------------------------------

def _dry_run(tmp_path, cell, *extra, slots="65536", keys="39000"):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"),
         "--workload", cell, "--seed", "2803000001", "--seconds", "3",
         "--trace", "0", "--platform", "cpu", "--slots", slots,
         "--keys", keys, "--out", str(tmp_path / "out"), *extra],
        env=env, cwd=spec.REPO, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    failed = {ln.split()[1].rstrip(":") for ln in lines
              if ln.startswith("compare ") and ln.endswith("FAILED")}
    return json.loads(lines[-1]), failed


# The replicas hold every GLOBAL key on every shard: 2^20 slots keep the
# rehearsal's replica buckets from crowding, as 2^24 do on the chip.
MESH_DRY = {"slots": "1048576", "keys": "400000"}


def test_the_global_cell_rehearses_sound(tmp_path):
    result, failed = _dry_run(tmp_path, CELL2, **MESH_DRY)
    assert failed == {"not_a_tpu_run"}
    assert result["correct"] is False and result["failed"] == 0
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    for name in ("global_not_under", "global_readback_differs",
                 "wrong_answers", "occupancy_beyond_expected"):
        assert result["compared"][name] == [0, 0], name


@pytest.mark.parametrize("control, must_fail", [
    ("alter", "wrong_answers"),
    ("f32", "wrong_reset_time"),
])
def test_the_global_cell_with_a_broken_daemon_is_not_correct(
        tmp_path, control, must_fail):
    result, failed = _dry_run(tmp_path, CELL2, "--control", control,
                              **MESH_DRY)
    assert must_fail in failed
    assert result["correct"] is False
    assert result["compared"][must_fail][0] > 0
