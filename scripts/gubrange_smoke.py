"""CI smoke: run the gubrange plane end-to-end the way an operator
does — the CLI over the real registry must pass strict-clean (every
kernel carries an envelope, zero unbounded intermediates, zero unit
errors, every expect_peak exact), and the shipped negative-control
fixture (unclamped hits*cost) must fail with an overflow finding whose
witness is a REAL kernel execution showing the wrapped output.

Run from the repo root:  python scripts/gubrange_smoke.py
Exits non-zero with a labeled assertion on any missing piece.
(Mirrors scripts/gubtrace_smoke.py / scripts/gubproof_smoke.py.)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Runnable from a checkout without an installed package.
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    # 1. The CLI over the real registry passes strict-clean: both
    #    phases (interval ranges + host suffix discipline), warnings
    #    fatal.
    proc = subprocess.run(
        [sys.executable, "-m", "tools.gubrange", "--json", "--strict"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ},
    )
    assert proc.returncode == 0, (
        f"gubrange CLI failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr}"
    )
    assert json.loads(proc.stdout) == [], (
        f"tree not clean: {proc.stdout}"
    )

    # 2. Envelope coverage is total: every registered kernel analyzed
    #    (the CLI already errors on a missing or stale envelope; this
    #    pins the expected kernel count so silent registry shrinkage
    #    can't fake a pass).
    from tools.gubrange.envelope import load_envelopes
    from tools.gubtrace.registry import specs

    names = {s.name for s in specs()}
    envs = set(load_envelopes())
    assert len(names) >= 23, f"registry shrank to {len(names)} kernels"
    assert envs == names, (
        f"envelope/registry drift: only-envelope={sorted(envs - names)} "
        f"only-registry={sorted(names - envs)}"
    )

    # 3. The negative control: the shipped unclamped hits*cost fixture
    #    must produce an overflow finding AND an executed witness whose
    #    output is the exact two's-complement wrap.
    from pathlib import Path

    from tools.gubrange import run
    from tools.gubrange.fixture import fixture_specs

    fs = run(
        select=["ranges"], specs=fixture_specs(),
        envelope_dir=Path(REPO) / "tests/gubrange_fixtures/envelopes",
        root=Path(REPO),
    )
    overflow = [f for f in fs if f.checker == "overflow"]
    assert overflow, (
        "negative-control fixture did not overflow: "
        + "\n".join(f.render() for f in fs)
    )
    witness = [f for f in fs if f.checker == "witness"]
    assert witness, "overflow finding shipped no executed witness"
    wrapped = str((4_000_000_000 * 4_000_000_000) % 2**64 - 2**64)
    assert "WRAPPED" in witness[0].message, witness[0].message
    assert wrapped in witness[0].message, (
        f"witness does not show the concrete wrap {wrapped}: "
        f"{witness[0].message}"
    )
    print(f"gubrange smoke: negative control wrapped to {wrapped}")

    print("gubrange smoke: PASS")


if __name__ == "__main__":
    main()
