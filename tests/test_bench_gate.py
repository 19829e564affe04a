"""The perf-regression CI gate (scripts/bench_gate.py; ROADMAP item 5's
down payment): p50 regressions past the threshold on matching
(config, mode) keys fail, platform mismatches warn-only, and the
committed-artifact auto-pick finds the two latest rounds."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "bench_gate",
    Path(__file__).resolve().parent.parent / "scripts" / "bench_gate.py",
)
bench_gate = importlib.util.module_from_spec(_SPEC)
sys.modules.setdefault("bench_gate", bench_gate)
_SPEC.loader.exec_module(bench_gate)


def _artifact(platform="cpu", p50=10.0, cps=1000.0, mode="m1"):
    return {
        "round": 1,
        "platform": platform,
        "results": [
            {
                "config": "serve_sweep_latency_small_batch",
                "serve_mode": mode, "concurrency": 4,
                "p50_ms": p50, "p99_ms": p50 * 2,
                "checks_per_sec": cps,
            },
            {"config": "summary", "platform": platform},
        ],
    }


def test_matching_keys_within_threshold_pass(capsys):
    rc = bench_gate.gate(
        _artifact(p50=10.0), _artifact(p50=12.0), 0.25, False
    )
    assert rc == 0
    assert "0 regression(s)" in capsys.readouterr().out


def test_p50_regression_fails(capsys):
    rc = bench_gate.gate(
        _artifact(p50=100.0), _artifact(p50=130.0), 0.25, False
    )
    assert rc == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and "serve_sweep_latency_small_batch" in out


def test_cpu_noise_floor_masks_small_absolute_deltas():
    """cpu-vs-cpu diffs must clear BOTH the relative threshold and the
    5ms absolute floor — a 12ms small-batch p50 bouncing 3ms between
    identical-code runs (the measured r09/r10 depth-sweep noise) is
    not a regression.  TPU diffs gate on the relative threshold alone:
    in the 2ms-SLO regime a 0.5ms regression is real."""
    # +30% but only +3ms on cpu: masked by the floor.
    assert bench_gate.gate(
        _artifact(p50=10.0), _artifact(p50=13.0), 0.25, False
    ) == 0
    # The same +30% at +30ms: a real regression.
    assert bench_gate.gate(
        _artifact(p50=100.0), _artifact(p50=130.0), 0.25, False
    ) == 1
    # tpu-vs-tpu: no floor — sub-ms regressions gate.
    assert bench_gate.gate(
        _artifact(platform="tpu", p50=1.0),
        _artifact(platform="tpu", p50=1.4),
        0.25, False,
    ) == 1
    # Explicit floor override wins.
    assert bench_gate.gate(
        _artifact(p50=10.0), _artifact(p50=13.0), 0.25, False,
        min_delta_ms=0.0,
    ) == 1


def test_platform_mismatch_warns_only(capsys):
    rc = bench_gate.gate(
        _artifact(platform="tpu", p50=1.0),
        _artifact(platform="cpu", p50=30.0),
        0.25, False,
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "platform mismatch" in out and "WARN" in out
    assert "FAIL" not in out


def test_warn_only_flag_downgrades(capsys):
    rc = bench_gate.gate(
        _artifact(p50=10.0), _artifact(p50=30.0), 0.25, True
    )
    assert rc == 0
    assert "WARN" in capsys.readouterr().out


def test_mode_keys_never_cross_compare():
    """A line of one mode must never be judged against another mode's
    baseline — the key includes serve_mode, so disjoint modes simply
    don't match."""
    base = _artifact(p50=10.0, mode="m1")
    new = _artifact(p50=1000.0, mode="m2")
    assert bench_gate.gate(base, new, 0.25, False) == 0


def test_throughput_drop_is_warning_not_failure(capsys):
    rc = bench_gate.gate(
        _artifact(p50=10.0, cps=1000.0),
        _artifact(p50=10.0, cps=100.0),
        0.25, False,
    )
    assert rc == 0
    assert "throughput" in capsys.readouterr().out


def _load_artifact(platform="cpu", p50=20.0, scenario="flashcrowd",
                   phase="crowd"):
    return {
        "platform": platform,
        "results": [
            {
                "config": "load_scenario",
                "scenario": scenario, "phase": phase,
                "platform": platform,
                "p50_ms": p50, "p99_ms": p50 * 3, "p999_ms": p50 * 5,
                "checks_per_sec": 300.0, "arrivals": 1000,
                "send_skew_p99_ms": 1.0, "open_loop": True,
            },
        ],
    }


def test_scenario_keys_gate_per_phase():
    """gubload rows key on (scenario, phase, platform): the same
    scenario+phase gates p50 like any bench config..."""
    assert bench_gate.gate(
        _load_artifact(p50=20.0), _load_artifact(p50=80.0), 0.25, False
    ) == 1
    assert bench_gate.gate(
        _load_artifact(p50=20.0), _load_artifact(p50=21.0), 0.25, False
    ) == 0


def test_scenario_phase_keys_disjoint():
    """...while different phases of the same scenario never
    cross-compare (a storm phase's tail is not a warm phase's
    regression)."""
    assert bench_gate.gate(
        _load_artifact(phase="warm", p50=5.0),
        _load_artifact(phase="crowd", p50=500.0),
        0.25, False,
    ) == 0


def test_new_scenario_warns_not_fails(capsys):
    """A scenario key with no baseline must WARN and exit 0: its first
    artifact BECOMES the baseline — a new scenario must not brick the
    gate for the PR that introduces it."""
    base = _artifact(p50=10.0)  # no scenario rows at all
    new = _artifact(p50=10.0)
    new["results"].extend(_load_artifact(p50=500.0)["results"])
    assert bench_gate.gate(base, new, 0.25, False) == 0
    out = capsys.readouterr().out
    assert "new scenario key" in out and "WARN" in out
    assert "FAIL" not in out


def test_scenario_platform_in_key_prevents_cross_hw_gating():
    """A cpu-recorded scenario row must not gate a tpu recording even
    when the artifacts' top-level platforms were somehow equal — the
    per-row platform is part of the key."""
    base = _load_artifact(platform="cpu", p50=5.0)
    new = _load_artifact(platform="cpu", p50=5.0)
    new["results"][0]["platform"] = "tpu"
    new["results"][0]["p50_ms"] = 500.0
    assert bench_gate.gate(base, new, 0.25, False) == 0


def test_find_latest_pair(tmp_path):
    for n in (3, 9, 10):
        (tmp_path / f"BENCH_E2E_r{n:02d}.json").write_text("{}")
    # Suffixed A/B variants are not rounds and must be ignored.
    (tmp_path / "BENCH_E2E_r11_sparse0.json").write_text("{}")
    base, new = bench_gate.find_latest_pair(tmp_path)
    assert base.name == "BENCH_E2E_r09.json"
    assert new.name == "BENCH_E2E_r10.json"
    assert bench_gate.find_latest_pair(tmp_path / "nowhere") is None


def test_repo_without_artifacts_is_nothing_to_gate(tmp_path, capsys):
    """The BENCH_E2E_r*.json records are gone (PR 21); until a benchmark
    commits artifacts again `--repo` is a clean one-line no-op, not a
    crash."""
    (tmp_path / "BENCH_E2E_r01.json").write_text("{}")  # one is no pair
    assert bench_gate.main(["--repo", str(tmp_path)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and "nothing to gate" in out[0]


def test_cli_end_to_end(tmp_path):
    b = tmp_path / "base.json"
    n = tmp_path / "new.json"
    b.write_text(json.dumps(_artifact(p50=10.0)))
    n.write_text(json.dumps(_artifact(p50=50.0)))
    assert bench_gate.main([str(b), str(n)]) == 1
    assert bench_gate.main([str(b), str(n), "--warn-only"]) == 0
    assert bench_gate.main([str(b), str(n), "--threshold", "5.0"]) == 0
