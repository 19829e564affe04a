"""The trace -> metrics reduction on a small recorded v5e trace
(data/small_trace.xplane.pb: five launches each of two tiny programs on one
chip, recorded through the chip tool)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import readers, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def reduced():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "lib", "trace.py"),
         os.path.join(DATA, "small_trace.xplane.pb")],
        env=env, capture_output=True, text=True, timeout=120, cwd=spec.REPO,
    )
    assert p.returncode == 0, p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_recorded_trace_reduces_to_the_known_numbers(reduced):
    assert reduced["chips_traced"] == 1
    assert reduced["modules"]["jit_bench_small_matmul"][0] == 5
    assert reduced["modules"]["jit_bench_small_add"][0] == 5
    assert reduced["busy_s"] == pytest.approx(3.6621e-05, rel=1e-6)
    assert reduced["window_s"] == pytest.approx(0.048158983, rel=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    names = [n for n, _s in reduced["device_ops"]]
    assert names[0] == "broadcast_add_fusion" and "copy-done" in names
    assert all(" " not in n and "%" not in n for n in names)
    assert reduced["idle_gaps"][0][0] == "bench_small_step"
    assert reduced["collective_s"] == 0


def test_union_of_intervals():
    s = np.array([0.0, 5.0, 20.0, 21.0]) * 1e9
    e = np.array([10.0, 8.0, 25.0, 30.0]) * 1e9
    total, ms, me = trace.union_seconds(s, e)
    assert total == 20.0 and list(ms / 1e9) == [0, 20] and list(
        me / 1e9) == [10, 30]
    assert trace.union_seconds(np.zeros(0), np.zeros(0))[0] == 0.0


def test_short_names_are_stable():
    assert trace.short_op("%while.36 = (s64[9,128]{1,0}) while(...)") == \
        "while.36"
    assert trace.short_module("jit_apply_batch_packed_q(123)") == \
        "jit_apply_batch_packed_q"
    assert trace.stable("a b/c") == "a_b_c"
    assert trace.stable("$fastpath.py:1926 _process") == "fastpath.py__process"


def test_code_readers_read_the_reduction(reduced):
    ctx = {"trace": reduced, "flat": {}, "snaps": ({}, {}),
           "device": {"kind": "TPU v5 lite"},
           "step_bytes": {"tier": 128, "bytes_accessed": 4.0e8}}
    m = spec.load_json(spec.layer_metric_path("step_device_ms.closed"))
    m["read"]["program_regex"] = "bench_small"
    assert readers.evaluate(m, ctx) == pytest.approx(
        reduced["busy_s"] / 10 * 1e3)
    o = spec.load_json(spec.layer_metric_path("step_device_ms.open"))
    assert o["read"]["reader"] == "step_device_ms.closed"   # no new code
    o["read"]["program_regex"] = "bench_small"
    assert readers.evaluate(o, ctx) == readers.evaluate(m, ctx)
    # The compiled program's own bytes x launches, over the peak, over
    # the busy time.
    h = spec.load_json(spec.layer_metric_path("step_hbm_share.closed"))
    h["read"]["program_regex"] = "bench_small"
    want = 4.0e8 * 10 / 819e9 / reduced["busy_s"] * 100
    assert readers.evaluate(h, ctx) == pytest.approx(want)
    assert readers.evaluate(h, dict(ctx, step_bytes={})) is None
    ctx["device"]["kind"] = "unknown chip"
    with pytest.raises(KeyError):
        readers.evaluate(h, ctx)
    # Rounds per drain: step launches over the drains' own stage events.
    r = spec.load_json(spec.layer_metric_path("lane_rounds_per_drain.open"))
    r["read"]["program_regex"] = "bench_small"
    staged = dict(reduced, host_stages={"gub.lane.pack": [4, 0.001]})
    assert readers.evaluate(r, dict(ctx, trace=staged)) == 10 / 4
    assert readers.evaluate(r, ctx) is None    # no stage events traced
    # Nothing traced: the reader returns nothing, the metric is left out.
    assert readers.evaluate(m, dict(ctx, trace={})) is None


def test_ratio_readers_take_the_difference_over_the_window():
    page = (
        '# HELP x\n'
        'gubernator_grpc_request_duration_sum{method="/pb.gubernator.V1/'
        'GetRateLimits"} %s\n'
        'gubernator_grpc_request_duration_count{method="/pb.gubernator.V1/'
        'GetRateLimits"} %s\n'
        'gubernator_grpc_request_duration_sum{method="/other"} 99\n'
    )
    snap = lambda s, c, v: {  # noqa: E731
        "metrics": readers.parse_prometheus(page % (s, c)), "vars": v,
        "flat": {"client:rpc_p99_ms": 7.5},
    }
    lanes = lambda b: {"fastpath": {"served": b * 100, "lanes": {  # noqa
        "mach": {"bubble_ms_total": b, "dispatch_ms_total": 2 * b,
                 "fetch_ms_total": b, "drains": b},
        "engine": {"bubble_ms_total": 0, "dispatch_ms_total": 0,
                   "fetch_ms_total": 0, "drains": b}}}}
    ctx = {"snaps": (snap(1.0, 10, lanes(1.0)), snap(3.0, 50, lanes(5.0)))}
    get = lambda n: readers.evaluate(  # noqa: E731
        spec.load_json(spec.layer_metric_path(n)), ctx)
    assert get("wire_rpc_ms.closed") == pytest.approx(2.0 / 40 * 1000)
    assert get("lane_bubble_share.closed") == pytest.approx(25.0)
    assert get("lane_checks_per_drain.closed") == pytest.approx(400 / 8)
    assert get("rpc_tail_p99_ms.open") == 7.5
    assert get("backend_step_ms.closed") is None     # series absent


def test_a_stage_outranks_the_runtime_events_nested_in_it():
    """Idle gaps are named over every host thread; the shortest gub.* stage
    that covers a gap names it, whatever runtime event sits inside."""
    gap_s, gap_e = np.array([100.0, 1000.0]), np.array([200.0, 1100.0])
    host = (
        ["gub.lane.drain", "gub.backend.d2h_wait", "ReadSyncFlag",
         "XlaLinearize", "gub.lane.pack"],
        np.array([0.0, 90.0, 110.0, 1000.0, 1050.0]),
        np.array([500.0, 210.0, 190.0, 1100.0, 1090.0]),
    )
    named = trace.name_gaps(gap_s, gap_e, host)
    # Gap 1: d2h_wait covers it (ReadSyncFlag overlaps more narrowly).
    # Gap 2: no stage covers it whole, so the most-overlapping event.
    assert named == {"gub.backend.d2h_wait": 100 / 1e9,
                     "XlaLinearize": 100 / 1e9}
