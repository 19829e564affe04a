"""chip_smoke.py's CPU dry run, the failure exit, and the compile-cache rule
the smoke's two daemon starts rest on.

The smoke proper runs on the chip through the chip tool (README "Running
on the chip"); here the same phases run at a tiny size on the CPU in a
subprocess — the parent never imports JAX and every phase is a child, so
nothing of this process's JAX state is involved.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _env(**extra: str) -> dict:
    """The dry run's environment: conftest's cache switch and 8-device
    flag stay behind (the smoke states its own platform and must see
    its compile cache work)."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS",
                     "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(extra)
    return env


def _smoke(args, env, timeout):
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_dry_run_summary(tmp_path):
    """Every phase at the tiny size: exit 0, the JSON summary with the
    fields the chip run is judged by, and as the LAST stdout line the
    verdict with exactly the keys the driver reads."""
    proc = _smoke(
        ["--platform", "cpu", "--slots", "65536", "--keys", "20000",
         "--diff-ops", "20000", "--out", str(tmp_path / "out")],
        _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary_line, verdict_line = proc.stdout.strip().splitlines()
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(verdict_line) == {"ok": True, "device": device}
    s = json.loads(summary_line)
    assert s["ok"] is True and s["claim"] is None
    assert s["device"] == device
    assert s["keys_loaded"] == 20000
    assert s["compile_cache_dir"] == str(tmp_path / "cache")
    assert len(s["native"]["source_sha256"]) == 64
    first, again = s["phases"]["server_first"], s["phases"]["server_again"]
    assert first["verified"] > 3000 and first["mismatches"] == 0
    assert first["load"]["bad_lanes"] == 0
    assert first["fastpath"]["fallbacks"] == 0
    assert first["device"]["compiled_lane"] is True
    # The first start filled an empty cache; the second added nothing.
    assert first["cache_entries_before"] == 0
    assert first["cache_entries_after"] > 0
    assert again["cache_entries_after"] == again["cache_entries_before"]
    assert again["warmup_s"] > 0 and again["mismatches"] == 0
    d = s["phases"]["differential"]
    assert d["differential"]["ops"] >= 20000
    assert d["differential"]["token_mismatches"] == 0
    assert d["differential"]["leaky_status_mismatches"] == 0
    assert d["trunc_corners"]["mismatches"] == 0
    # The Pallas kernel refuses a non-interpret compile on the CPU; the
    # refusal is recorded, not failed.
    assert set(d["kernels"]) == {"cms_pallas"}
    assert all(k["ok"] is False and k["reason"]
               for k in d["kernels"].values())
    assert json.loads((tmp_path / "out" / "summary.json").read_text()) == s


def test_child_that_dies_fails_the_smoke(tmp_path):
    """A server child that exits at start-up: non-zero exit well inside
    the timeout, and no summary line."""
    proc = _smoke(
        ["--platform", "cpu", "--slots", "65536", "--keys", "20000",
         "--out", str(tmp_path / "out"),
         "--server-cmd", f"{sys.executable} -c 'import sys; sys.exit(3)'"],
        _env(), timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "exited rc=3 before it was ready" in proc.stderr


def _cache_dir_seen_by_jax(env) -> str:
    out = subprocess.run(
        [sys.executable, "-c",
         "import gubernator_tpu.ops, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_rule(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the program configures no directory
    (JAX reads the variable itself).  Unset: one fixed path inside the
    checkout, the same on every start."""
    from gubernator_tpu.core.config import compile_cache_dir

    placed = str(tmp_path / "placed")
    fixed = str(REPO / ".jax_cache")
    assert _cache_dir_seen_by_jax(
        _env(JAX_COMPILATION_CACHE_DIR=placed, JAX_PLATFORMS="cpu")
    ) == placed
    assert _cache_dir_seen_by_jax(_env(JAX_PLATFORMS="cpu")) == fixed
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == fixed
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compile_cache_dir() == placed
