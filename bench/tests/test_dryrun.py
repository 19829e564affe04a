"""The rest of a run, driven without the chip: `--platform cpu` skips the
harness's look for a TPU and nothing else.  Sound, every comparison but
`not_a_tpu_run` passes and the last line still says `correct: false`;
with the timed path broken underneath (`--control alter`: one answer in 97
changed where the fetched response is unpacked) or computing in float32
(`--control f32`, the lower-precision control at a size a test can hold),
the comparison with the reference fails.  One daemon start each (~25 s)."""
import json
import os
import subprocess
import sys

import pytest

from lib import spec


def dry_run(tmp_path, cell, *extra, seconds="2"):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"),
         "--workload", cell, "--seed", "2246822519", "--seconds", seconds,
         "--trace", "0", "--platform", "cpu", "--slots", "65536",
         "--keys", "39000", "--out", str(tmp_path / "out"), *extra],
        env=env, cwd=spec.REPO, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    failed = {ln.split()[1].rstrip(":") for ln in lines
              if ln.startswith("compare ") and ln.endswith("FAILED")}
    compared = {ln.split()[1].rstrip(":") for ln in lines
                if ln.startswith("compare ")}
    return json.loads(lines[-1]), failed, compared


def test_sound_dry_run_fails_only_for_not_being_a_tpu(tmp_path):
    result, failed, compared = dry_run(tmp_path, "exact10m.batch.closed")
    assert failed == {"not_a_tpu_run"}
    assert {"wrong_answers", "wrong_reset_time", "compiled_in_window",
            "wire_check_mismatches", "occupancy_below_expected",
            "fastpath_fallbacks_grown"} <= compared
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "window", "compared"]
    assert result["compared"]["not_a_tpu_run"] == [1, 0]
    assert result["compared"]["wrong_answers"] == [0, 0]
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("cell", [
    "exact10m.rpc2.open", "exact10m.zipf99.rpc16.closed",
])
@pytest.mark.parametrize("control, must_fail", [
    ("alter", "wrong_answers"),
    ("f32", "wrong_reset_time"),
])
def test_broken_daemon_comes_out_not_correct(tmp_path, cell, control,
                                             must_fail):
    result, failed, _ = dry_run(tmp_path, cell, "--control", control)
    assert must_fail in failed and "wire_check_mismatches" in failed
    assert result["correct"] is False
    assert result["compared"][must_fail][0] > 0


def test_the_zipf_cell_rehearses_sound(tmp_path):
    result, failed, _ = dry_run(tmp_path, "exact10m.zipf99.rpc16.closed",
                                seconds="4")
    assert failed == {"not_a_tpu_run"}
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_no_accelerator_means_no_result(tmp_path):
    """Here JAX has no TPU: the command exits non-zero, prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"),
         "--workload", "exact10m.rpc2.open", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--out", str(tmp_path / "out")],
        env=env, cwd=spec.REPO, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
