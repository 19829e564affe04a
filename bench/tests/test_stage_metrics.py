"""The per-layer metrics that read the stage ledger (PR 25): every new data
file evaluates against a recorded pair of /debug/vars scrapes, the
idle_named_share readers against a recorded reduction, and both return
nothing for a program that has no ledger (the parent commit)."""
import copy
import glob
import json
import os

import pytest

from lib import readers, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BM = spec.benchmark()
PAIR = spec.load_json(os.path.join(DATA, "stages_vars_pair.json"))["snaps"]
REDUCTION = spec.load_json(os.path.join(DATA, "reduction_closed_pr25.json"))

NEW = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(spec.BENCH, "layer_metrics", "*.json"))
    if "vars:stages." in open(p).read() or "idle_named_share" in p
)


def _ctx(snaps, trace=None):
    flat = {}
    return {
        "snaps": tuple({"vars": s, "metrics": [], "flat": flat}
                       for s in snaps),
        "flat": flat, "trace": trace or {},
    }


def _d(lane, stage, key="ms_total"):
    a, b = (s["stages"][lane][stage][key] for s in PAIR)
    return b - a


def test_the_stage_metrics_are_listed_for_the_cells_of_their_kind():
    """PR 25's fifteen and PR 27's lane_cascade_ms.open / .closed.
    BENCHMARK.json alone says which cells report a metric (PR 27): the
    data files carry no `workloads`."""
    assert len(NEW) == 17, NEW
    listed = {m["name"]: m for m in BM["per_layer"]}
    assert set(NEW) <= set(listed)
    for name in NEW:
        assert "workloads" not in spec.load_json(
            spec.layer_metric_path(name))
        m = listed[name]
        if name.endswith(".open"):
            assert m["workloads"] == ["exact10m.rpc2.open"]
            assert m["moves"] == "rpc_p50_ms"
        else:
            assert m["workloads"] in (
                ["exact10m.batch.closed", "mesh4-10m.batch.closed",
                 "exact10m.zipf99.rpc16.closed"],
                ["exact10m.batch.closed", "exact10m.zipf99.rpc16.closed"])
            assert m["moves"] == "decisions_per_s"


@pytest.mark.parametrize("name", [n for n in NEW if "idle_named" not in n])
def test_ratio_metric_reads_the_recorded_pair(name):
    m = spec.load_json(spec.layer_metric_path(name))
    assert m["read"]["kind"] == "ratio" and m["read"]["delta"] is True
    value = readers.evaluate(m, _ctx(PAIR))
    assert value is not None and value >= 0
    drains = (PAIR[1]["fastpath"]["lanes"]["mach"]["drains"]
              - PAIR[0]["fastpath"]["lanes"]["mach"]["drains"])
    base = name.rsplit(".", 1)[0]
    want = {
        "wire_loop_ms": (_d("wire", "ingress") + _d("wire", "egress"))
        / _d("wire", "handler", "count"),
        "lane_queue_wait_ms": _d("mach", "queue_wait")
        / _d("mach", "queue_wait", "count"),
        "rpc_attributed_share": 100 * (
            _d("wire", "ingress") + _d("mach", "queue_wait")
            + _d("mach", "in_drain") + _d("wire", "wake")
            + _d("wire", "egress")) / _d("wire", "handler"),
        "lane_host_ms": (
            _d("mach", "handoff") + _d("mach", "resume")
            + _d("mach", "pack") + _d("mach", "cascade")
            + _d("mach", "unpack")) / drains,
        "lane_cascade_ms": _d("mach", "cascade") / drains,
        "backend_lock_wait_ms": _d("mach", "lock_wait") / drains,
        "backend_d2h_wait_ms": _d("mach", "d2h_wait") / drains,
        "daemon_empty_share": 100 * _d("wire", "empty") / (
            _d("wire", "empty") + _d("wire", "occupied")),
    }[base]
    assert value == pytest.approx(want)
    if base == "rpc_attributed_share":
        assert 90 <= value <= 100.5      # one entry per RPC in the record


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_ledger_reports_nothing(name):
    """The parent commit has no `stages` block: the metric is left out of
    the line and nothing raises."""
    bare = copy.deepcopy(PAIR)
    for s in bare:
        del s["stages"]
    m = spec.load_json(spec.layer_metric_path(name))
    assert readers.evaluate(m, _ctx(bare, REDUCTION)) is None


@pytest.mark.parametrize("name", [n for n in NEW if "idle_named" in n])
def test_idle_named_share_reads_the_recorded_reduction(name):
    m = spec.load_json(spec.layer_metric_path(name))
    assert m["read"]["kind"] == "code" and m["read"]["prefix"] == "gub."
    # The recorded run: every listed gap went to a runtime event nested
    # inside a stage (XlaLinearize, ReadSyncFlag, ...) or to an idle pool
    # thread, none to a gub.* name.
    assert readers.evaluate(m, _ctx(PAIR, REDUCTION)) == 0.0
    named = copy.deepcopy(REDUCTION)
    total = sum(s for _n, s in named["idle_gaps"])
    named["idle_gaps"].append(["gub.lane.pack", total])
    named["idle_gaps"].append(["gubernator_other", total])
    assert readers.evaluate(m, _ctx(PAIR, named)) == pytest.approx(100 / 3)
    # Nothing traced: nothing to read.
    assert readers.evaluate(m, _ctx(PAIR, {})) is None
    assert readers.evaluate(m, _ctx(PAIR, {"idle_gaps": []})) is None


def test_recorded_reduction_is_the_shape_the_harness_hands_over():
    assert REDUCTION["idle_gaps"] and all(
        isinstance(n, str) and s >= 0 for n, s in REDUCTION["idle_gaps"])
    json.dumps(REDUCTION)
