#!/usr/bin/env python
"""Perf-regression CI gate: diff two BENCH_E2E-shaped artifacts (a slow
PR fails loudly instead of drifting).  The repo commits no such
artifacts at present — `--repo .` then reports "nothing to gate" — so
today it gates gubload scenario artifacts (scripts/load_smoke.py) and
whatever pair it is handed.

Compares the NEW artifact's per-config p50 against the BASELINE's on
MATCHING keys — (config, serve_mode, concurrency) for bench_e2e rows,
plus (scenario, phase, platform) for gubload scenario rows (a scenario
key with no baseline warns instead of failing) — and fails (exit 1)
when any matched config's p50 regressed by more than --threshold
(default 25%).  Throughput (checks_per_sec) regressions past the same
threshold are reported as warnings: p50 is the gate (the tail is what
operators feel), throughput is noise-prone.

Platform honesty: artifacts record the ACTUAL jax platform.  When the
two artifacts' platforms differ (e.g. a cpu CI runner diffing a tpu
recording), every finding downgrades to a warning and the gate exits 0
— a cross-platform diff measures the platform, not the PR.  `--warn-
only` forces the same downgrade for same-platform diffs (e.g. a fresh
CI-runner artifact vs a committed one recorded on different hardware).

Noise honesty: CPU artifacts carry multi-ms scheduler noise on the
small-batch configs, so on cpu-vs-cpu diffs a p50 regression must
clear BOTH the relative threshold and an absolute floor
(--min-delta-ms, default 5).  TPU diffs gate on the relative threshold
alone — that is the 2ms-SLO regime where half a millisecond is a real
regression, and the floor defaults to 0 there.

Usage:
    bench_gate.py BASELINE.json NEW.json [--threshold 0.25] [--warn-only]
    bench_gate.py --repo .       # auto-pick the two latest committed
                                 # BENCH_E2E_r{N}.json artifacts
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

# Configs with a meaningful, comparable p50 (per-line "config" values).
# Sweep stage/budget lines carry no latency; client sweeps measure the
# client's machinery and are gated by the same key rule when present.
_SKIP_CONFIGS = {
    "summary", "budget_us_per_1000", "serve_sweep_stages",
    "pipeline_sweep_stages", "mesh_serve_sweep_stages",
    "client_mode_budget", "colocated_latency_bound",
}


def _key(line: dict):
    # Scenario rows (gubload artifacts, config == "load_scenario")
    # extend the key with (scenario, phase, platform): each phase of
    # each scenario gates independently, and a row only ever matches a
    # baseline recorded on the same hardware.
    return (
        line.get("config"),
        line.get("serve_mode"),
        line.get("pipeline_depth"),
        line.get("client_mode"),
        line.get("concurrency"),
        line.get("scenario"),
        line.get("phase"),
        line.get("platform"),
    )


def _latency_lines(artifact: dict):
    out = {}
    for line in artifact.get("results", []):
        cfg = line.get("config")
        if not cfg or cfg in _SKIP_CONFIGS:
            continue
        if "p50_ms" not in line or "error" in line:
            continue
        # Last line wins for repeated keys (re-runs within a sweep are
        # successive refinements of the same config).
        out[_key(line)] = line
    return out


def _round_no(path: Path) -> int:
    m = re.match(r"BENCH_E2E_r(\d+)\.json$", path.name)
    return int(m.group(1)) if m else -1


def find_latest_pair(repo: Path):
    """The two most recent committed BENCH_E2E_r{N}.json (suffix-free)
    artifacts — the PR-vs-previous-round diff the CI gate runs — or
    None where the repo carries fewer than two (nothing to gate)."""
    arts = sorted(
        (p for p in repo.glob("BENCH_E2E_r*.json") if _round_no(p) >= 0),
        key=_round_no,
    )
    if len(arts) < 2:
        return None
    return arts[-2], arts[-1]


def gate(baseline: dict, new: dict, threshold: float,
         warn_only: bool, min_delta_ms: float = None) -> int:
    base_platform = baseline.get("platform", "?")
    new_platform = new.get("platform", "?")
    cross = base_platform != new_platform
    if cross:
        print(
            f"bench_gate: platform mismatch ({base_platform!r} -> "
            f"{new_platform!r}) — warn-only (a cross-platform diff "
            "measures the platform, not the PR)"
        )
    soft = cross or warn_only
    if min_delta_ms is None:
        # The platform-conditional noise floor (module docstring): cpu
        # p50s carry multi-ms scheduler noise; tpu gates on the
        # relative threshold alone.
        min_delta_ms = 5.0 if (
            base_platform == "cpu" and new_platform == "cpu"
        ) else 0.0

    base_lines = _latency_lines(baseline)
    new_lines = _latency_lines(new)
    matched = sorted(
        set(base_lines) & set(new_lines), key=lambda k: str(k)
    )
    # A scenario key with no baseline is a NEW scenario (or a platform
    # change): its first artifact becomes the baseline for the next
    # round — warn, never fail (there is nothing to regress against).
    for k in sorted(set(new_lines) - set(base_lines), key=str):
        if new_lines[k].get("scenario"):
            label = "/".join(str(p) for p in k if p is not None)
            print(
                f"bench_gate: WARN new scenario key {label}: no "
                "baseline — recorded for the next round, not gated"
            )
    if not matched:
        print("bench_gate: no matching (config, mode) keys — nothing "
              "to gate (artifact schema drift?)")
        return 0

    failures = 0
    for k in matched:
        b, n = base_lines[k], new_lines[k]
        bp50, np50 = float(b["p50_ms"]), float(n["p50_ms"])
        label = "/".join(str(p) for p in k if p is not None)
        if (
            bp50 > 0
            and np50 > bp50 * (1.0 + threshold)
            and np50 - bp50 > min_delta_ms
        ):
            kind = "WARN" if soft else "FAIL"
            print(
                f"bench_gate: {kind} {label}: p50 {bp50:.3f}ms -> "
                f"{np50:.3f}ms (+{(np50 / bp50 - 1) * 100:.0f}% > "
                f"{threshold * 100:.0f}%)"
            )
            if not soft:
                failures += 1
            continue
        bt = float(b.get("checks_per_sec") or 0)
        nt = float(n.get("checks_per_sec") or 0)
        if bt > 0 and nt < bt * (1.0 - threshold):
            print(
                f"bench_gate: WARN {label}: throughput {bt:.0f} -> "
                f"{nt:.0f} checks/s "
                f"(-{(1 - nt / bt) * 100:.0f}%; informational)"
            )
        else:
            print(
                f"bench_gate: ok   {label}: p50 {bp50:.3f} -> "
                f"{np50:.3f}ms"
            )
    print(
        f"bench_gate: {len(matched)} config(s) compared, "
        f"{failures} regression(s) past {threshold * 100:.0f}%"
    )
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("baseline", nargs="?", help="baseline artifact")
    ap.add_argument("new", nargs="?", help="new artifact")
    ap.add_argument(
        "--repo", default=None,
        help="auto-pick the two latest committed BENCH_E2E_r{N}.json "
        "from this directory instead of naming artifacts",
    )
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="p50 regression fraction that fails (0.25)")
    ap.add_argument("--min-delta-ms", type=float, default=None,
                    help="absolute p50 noise floor a regression must "
                    "also clear (default: 5 for cpu-vs-cpu diffs, 0 "
                    "otherwise)")
    ap.add_argument("--warn-only", action="store_true",
                    help="report regressions but always exit 0")
    args = ap.parse_args(argv)

    if args.repo is not None:
        pair = find_latest_pair(Path(args.repo))
        if pair is None:
            print(f"bench_gate: fewer than two BENCH_E2E_r*.json under "
                  f"{args.repo} — nothing to gate")
            return 0
        base_p, new_p = pair
    elif args.baseline and args.new:
        base_p, new_p = Path(args.baseline), Path(args.new)
    else:
        ap.error("name BASELINE and NEW artifacts, or pass --repo")
    print(f"bench_gate: {base_p.name} (baseline) vs {new_p.name} (new)")
    baseline = json.loads(base_p.read_text())
    new = json.loads(new_p.read_text())
    return gate(baseline, new, args.threshold, args.warn_only,
                args.min_delta_ms)


if __name__ == "__main__":
    sys.exit(main())
