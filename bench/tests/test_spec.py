"""BENCHMARK.json and every data file against the harness's own rules."""
import glob
import json
import os

import pytest

from lib import spec

BM = spec.benchmark()


def test_benchmark_json_is_valid_and_agrees_with_the_data_files():
    spec.check_benchmark(BM)


def test_paths_and_command_stay_inside_the_benchmark():
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    # A full check of 24 cells has to fit the driver's budget.
    cells, s = 24, BM["run_seconds"]
    assert (2 + 14 * cells) * (s + 60) + cells * 180 + 1200 <= 43200


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(spec.BENCH, "traffic", "*.json")))
)
def test_every_traffic_file(path):
    t = spec.load_json(path)
    spec.check_traffic(t, path)
    assert spec.NAME.match(t["name"])
    assert os.path.basename(path) == t["name"] + ".json"
    assert t["deadline_s"] >= 5.0, "no operating point on a deadline's edge"


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(spec.BENCH, "configs", "*.json")))
)
def test_every_configuration_file(path):
    c = spec.load_json(path)
    spec.check_config(c, path)
    assert os.path.basename(path) == c["name"] + ".json"
    assert len(c["source"]) <= 200


@pytest.mark.parametrize(
    "path",
    sorted(glob.glob(os.path.join(spec.BENCH, "layer_metrics", "*.json"))),
)
def test_every_layer_metric_file(path):
    m = spec.load_json(path)
    spec.check_layer_metric(m, path)
    assert os.path.basename(path) == m["name"] + ".json"
    assert spec.NAME.match(m["name"]) and spec.UNIT.match(m["unit"])
    assert m["source"] in spec.SOURCES
    assert "\n" not in m["layer"] and len(m["layer"]) <= 200


@pytest.mark.parametrize("bad", ["two words", "a,b", "a/b", "", "x" * 65,
                                 "-lead", "grüß"])
def test_name_rule_refuses(bad):
    assert not spec.NAME.match(bad)


@pytest.mark.parametrize("bad", ["tokens per second", "", "x" * 17, "µs"])
def test_unit_rule_refuses(bad):
    assert not spec.UNIT.match(bad)


def test_a_deadline_under_five_seconds_is_refused():
    t = spec.load_json(spec.traffic_path("rpc2.open"))
    t["deadline_s"] = 0.5
    with pytest.raises(spec.SpecError):
        spec.check_traffic(t, "edited")


def test_an_open_loop_without_a_cap_is_refused():
    t = spec.load_json(spec.traffic_path("rpc2.open"))
    del t["outstanding_cap"]
    with pytest.raises(spec.SpecError):
        spec.check_traffic(t, "edited")


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    from lib import roofline

    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9000")


def test_readme_worked_example_is_valid_data():
    """README.md's token1k.batch.closed: its files are written out there
    as JSON blocks; each must pass the same checks as a shipped file."""
    text = open(os.path.join(spec.BENCH, "README.md")).read()
    blocks = [b.split("```", 1)[0] for b in text.split("```json\n")[1:]]
    parsed = [json.loads(b) for b in blocks]
    configs = [b for b in parsed if "universe" in b]
    assert configs, "no configuration block in the README"
    for c in configs:
        spec.check_config(c, "README")
    for m in (b for b in parsed if "read" in b):
        assert m["read"]["kind"] in ("ratio", "code")
