"""`peers4-10m.batch.closed` as ENTERED (PR 39): the cell is in
BENCHMARK.json, bench/held_out.json still lists it and is not edited,
BENCHMARK.json wins; its own metric lists (no `.mesh` metric, the five of
layer "peer hop"); the five data files against a recorded pair of
/debug/vars + /metrics scrapes of a routed CPU daemon; the dry run of the
entered cell, as configured.

Three assertions of earlier tests state the status this PR ended and fail
now by themselves, for a `benchmark` PR to turn:
test_cluster.py::test_the_cluster_cell_is_built_and_held_out (its first
line: the cell is not in BENCHMARK.json), test_moving_cells.py::
test_the_held_out_cells_report_what_the_cell_they_name_reports (the
four-chip cells under --held-out are BENCHMARK.json's PLUS this one) and
test_global_cells.py::test_the_cells_and_what_they_report (PR 28's count:
two four-chip cells; there are three of seven).
"""
import copy
import os

import pytest
from test_dryrun import dry_run

from lib import readers, spec

CELL = "peers4-10m.batch.closed"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PAIR = spec.load_json(os.path.join(DATA, "peer_hop_scrapes_pr39.json"))["snaps"]
HOP = ("peer_forward_ms.closed", "peer_forwards_per_rpc.closed",
       "peer_wait_share.closed", "peer_entry_host_ms.closed",
       "peer_attributed_share.closed")
GET_RATE_LIMITS = "/pb.gubernator.V1/GetRateLimits"
BM = spec.benchmark()


def _ctx(snaps):
    flat = {}
    return {
        "snaps": tuple({
            "vars": s["vars"],
            "metrics": readers.parse_prometheus(s["metrics"]), "flat": flat,
        } for s in snaps),
        "flat": flat, "trace": {},
    }


def _stage(lane, stage, key="ms_total"):
    a, b = (s["vars"]["stages"][lane][stage][key] for s in PAIR)
    return b - a


def _series(name):
    a, b = (sum(v for n, lab, v in readers.parse_prometheus(s["metrics"])
                if n == name and lab.get("method") == GET_RATE_LIMITS)
            for s in PAIR)
    return b - a


# -- the entry ---------------------------------------------------------------

def test_the_cell_is_entered_and_benchmark_json_wins():
    spec.check_benchmark(BM)
    cells = {w["name"]: w for w in BM["workloads"]}
    # Held by membership, not by count or last place: the next cell that
    # is appended must not fail this (PR 45).
    assert CELL in cells and cells[CELL]["chips"] == 4
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    held = spec.load_json(os.path.join(spec.BENCH, "held_out.json"))
    (theirs,) = [w for w in held["workloads"] if w["name"] == CELL]
    assert cells[CELL] == {k: theirs[k] for k in (
        "name", "config", "traffic", "chips", "why")}
    (cfg,) = [c for c in BM["configs"] if c["name"] == "peers4-10m"]
    assert cfg == [c for c in held["configs"]
                   if c["name"] == "peers4-10m"][0]
    assert cfg in BM["configs"] and cfg["reduced"] == []
    # With --held-out the cell is still BENCHMARK.json's: named once, its
    # lists its own and not the borrowed `reports_as` ones.
    both = spec.benchmark(held_out=True)
    spec.check_benchmark(both)
    assert [w["name"] for w in both["workloads"]].count(CELL) == 1
    assert [c["name"] for c in both["configs"]].count("peers4-10m") == 1
    assert ([m["name"] for m in spec.metrics_of(both, "per_layer", CELL)]
            == [m["name"] for m in spec.metrics_of(BM, "per_layer", CELL)])


def test_the_cells_own_metric_lists():
    e2e = [m["name"] for m in spec.metrics_of(BM, "end_to_end", CELL)]
    assert e2e == ["decisions_per_s", "setup_s"]
    mine = [m["name"] for m in spec.metrics_of(BM, "per_layer", CELL)]
    assert not [n for n in mine if n.endswith(".mesh")]
    assert all(n.endswith(".closed") for n in mine)
    assert set(HOP) <= set(mine)
    # Every .closed metric the mesh's batch cell is on (fourteen at PR 39,
    # more since: held by membership), the two a one-chip daemon's drain
    # also reads, the hop's five — and beyond those only metrics of the
    # hop's own layer (PR 43 brought two).
    on_mesh = {m["name"] for m in spec.metrics_of(
        BM, "per_layer", "mesh4-10m.batch.closed") if m["name"].endswith(
        ".closed")}
    assert on_mesh | {"lane_cascade_ms.closed",
                      "lane_rounds_per_drain.closed"} | set(HOP) <= set(mine)
    layer = {m["name"]: m["layer"] for m in BM["per_layer"]}
    assert {layer[n] for n in set(mine) - on_mesh - {
        "lane_cascade_ms.closed", "lane_rounds_per_drain.closed"}} == {
        "peer hop"}
    for m in BM["per_layer"]:
        if m["name"] in HOP:
            assert CELL in m["workloads"] and m["layer"] == "peer hop"
            assert m["moves"] == "decisions_per_s"
    # The five in the order they were entered in, wherever later entries
    # were appended.
    assert [m["name"] for m in BM["per_layer"]
            if m["name"] in HOP] == list(HOP)


# -- the five data files -------------------------------------------------------

@pytest.mark.parametrize("name", HOP)
def test_the_data_file_is_a_ratio_over_the_hops_rows(name):
    m = spec.load_json(spec.layer_metric_path(name))
    spec.check_layer_metric(m, "layer_metrics/" + name)
    assert m["read"]["kind"] == "ratio" and m["read"]["delta"] is True
    assert m["layer"] == "peer hop" and m["moves"] == "decisions_per_s"
    text = open(spec.layer_metric_path(name)).read()
    # test_stage_metrics.py holds a closed table for files that spell it.
    assert "vars:stages." not in text and "vars:*." in text


def test_the_metrics_read_the_recorded_pair():
    got = {n: readers.evaluate(
        spec.load_json(spec.layer_metric_path(n)), _ctx(PAIR)) for n in HOP}
    rpcs = _stage("peer", "route", "count")
    handler_s = _series("gubernator_grpc_request_duration_sum")
    # Two of every round's eight entered by this daemon, each routed once.
    assert rpcs == 6 == _series("gubernator_grpc_request_duration_count")
    want = {
        "peer_forward_ms.closed": _stage("peer", "forward")
        / _stage("peer", "forward", "count"),
        "peer_forwards_per_rpc.closed": _stage("peer", "forward", "count")
        / rpcs,
        "peer_wait_share.closed": 100 * _stage("wire", "peer_wait")
        / (1e3 * handler_s),
        "peer_entry_host_ms.closed": (
            _stage("peer", "route") + _stage("peer", "splice")
            + _stage("peer", "assemble")) / rpcs,
        "peer_attributed_share.closed": 100 * (
            _stage("wire", "ingress") + _stage("mach", "queue_wait")
            + _stage("mach", "in_drain") + _stage("wire", "wake")
            + _stage("wire", "egress") + _stage("wire", "peer_wait"))
        / _stage("wire", "handler"),
    }
    for name in HOP:
        assert got[name] == pytest.approx(want[name]), name
    assert got["peer_forwards_per_rpc.closed"] == 3.0
    assert 0 < got["peer_wait_share.closed"] < 100
    assert 95 <= got["peer_attributed_share.closed"] <= 100.5
    # What the hop's name closes: the accepted share falls short by it.
    old = readers.evaluate(spec.load_json(spec.layer_metric_path(
        "rpc_attributed_share.closed")), _ctx(PAIR))
    assert old == pytest.approx(
        got["peer_attributed_share.closed"]
        - 100 * _stage("wire", "peer_wait") / _stage("wire", "handler"))
    assert old < got["peer_attributed_share.closed"]


@pytest.mark.parametrize("name", HOP)
def test_a_program_without_the_hops_rows_reports_nothing(name):
    """The parent commit (PR 37) has no `peer` lane and no wire.peer_wait:
    the metric is left out of the line and nothing raises."""
    bare = copy.deepcopy(PAIR)
    for s in bare:
        del s["vars"]["stages"]["peer"]
        del s["vars"]["stages"]["wire"]["peer_wait"]
    m = spec.load_json(spec.layer_metric_path(name))
    assert readers.evaluate(m, _ctx(bare)) is None


# -- the dry run ---------------------------------------------------------------

def test_the_entered_cell_rehearses_on_the_normal_path(tmp_path):
    """No --held-out, no --daemon: the configuration's own ports and its
    500 ms forward limit.  (Two runs of the cell at once on one host meet
    on the ports, and the later is refused: PERF.md section 7.)"""
    result, failed, compared = dry_run(tmp_path, CELL, seconds="3")
    assert failed == {"not_a_tpu_run"}
    assert {"forwarded_checks_differ", "local_checks_differ",
            "occupancy_beyond_expected", "wrong_answers"} <= compared
    assert "cell_held_out" not in compared
    assert result["compared"]["forwarded_checks_differ"] == [0, 0]
    assert result["compared"]["local_checks_differ"] == [0, 0]
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["daemons"]) == 4


def test_a_cluster_that_forwards_nothing_still_fails_the_entered_cell(
    tmp_path
):
    """`--control noforward` on the ENTERED cell: every daemon a ring of
    itself, so no check is forwarded and three quarters were the plan's to
    forward — the hop's two comparisons say so, whatever the answers."""
    result, failed, _ = dry_run(tmp_path, CELL, "--control", "noforward",
                                seconds="3")
    assert {"forwarded_checks_differ", "local_checks_differ"} <= failed
    assert "cell_held_out" not in failed and result["correct"] is False
    differ, limit = result["compared"]["forwarded_checks_differ"]
    assert limit == 0 and differ > 0.7 * result["attempted"]
    assert sum(x["forward"] for x in result["daemons"]) == 0
