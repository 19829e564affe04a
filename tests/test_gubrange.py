"""gubrange self-tests: the interval domain is exact at the corners,
the unit algebra flags real confusions, the negative-control fixture
produces an overflow finding WITH an executed wrapped witness, a
loosened envelope is rejected, and the saturating device helpers stay
bit-identical to the pymodel oracle at the int64/float53 edges.

The fuzz half upgrades to hypothesis when it is installed; without it
the same property runs over a deterministic corner sweep (the container
pins its dependency set, so the fallback is the normal path in CI).
"""
import json
import math
import shutil
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.core.pymodel import (
    _I64_MAX,
    _I64_MIN,
    _sat_add,
    _sat_sub,
    _trunc,
)
from gubernator_tpu.ops.step import _sat_add_i64, _sat_sub_i64, _trunc_i64
from tools.gubrange import run
from tools.gubrange.absint import RangeWalk
from tools.gubrange.envelope import load_envelope
from tools.gubrange.fixture import fixture_specs
from tools.gubrange.interval import (
    AbsVal,
    div_bounds_float,
    div_bounds_int,
    from_rows,
    mul_bounds,
    rem_bounds_int,
    top_of,
    trunc_to_int_bounds,
)
from tools.gubrange import units

REPO = Path(__file__).resolve().parents[1]
FIXTURE_ENVELOPES = Path(__file__).parent / "gubrange_fixtures" / "envelopes"

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# -- interval domain -----------------------------------------------------

def test_div_bounds_int_excludes_zero_from_divisor():
    lo, hi, zero_div = div_bounds_int(AbsVal(10, 100), AbsVal(0, 5))
    assert zero_div
    # With 0 excluded the divisor is [1, 5]: quotient peaks at 100/1.
    assert (lo, hi) == (2, 100)


def test_div_bounds_int_truncates_toward_zero():
    lo, hi, _ = div_bounds_int(AbsVal(-7, -7), AbsVal(2, 2))
    assert (lo, hi) == (-3, -3)  # Go/XLA: -7/2 = -3, not floor's -4


def test_div_bounds_float_zero_crossing_reaches_inf():
    lo, hi, zero_div = div_bounds_float(AbsVal(1.0, 2.0), AbsVal(-1.0, 1.0))
    assert zero_div
    assert lo == -math.inf and hi == math.inf


def test_mul_bounds_sign_corners():
    assert mul_bounds(AbsVal(-3, 2), AbsVal(-5, 4)) == (-12, 15)


def test_rem_bounds_follow_dividend_sign():
    lo, hi, _ = rem_bounds_int(AbsVal(0, 1000), AbsVal(7, 7))
    assert (lo, hi) == (0, 6)
    # A negative interval crossing -mag still reaches remainder 0 (at
    # -7), so hi may NOT be tightened to a.hi = -1.
    lo, hi, _ = rem_bounds_int(AbsVal(-1000, -1), AbsVal(7, 7))
    assert (lo, hi) == (-6, 0)
    # Entirely inside (-mag, mag) the remainder is the dividend itself.
    lo, hi, _ = rem_bounds_int(AbsVal(-3, 5), AbsVal(7, 7))
    assert (lo, hi) == (-3, 5)


def test_trunc_to_int_bounds_saturates():
    lo, hi = trunc_to_int_bounds(AbsVal(-math.inf, math.inf), "int64")
    assert (lo, hi) == (_I64_MIN, _I64_MAX)
    lo, hi = trunc_to_int_bounds(AbsVal(-1.5, 2.9), "int64")
    assert (lo, hi) == (-1, 2)  # toward zero


def test_from_rows_top_level_is_join():
    rows = [AbsVal(0, 10, unit="ms"), AbsVal(-5, 3, unit="ms"),
            top_of("int64")]
    pack = from_rows(rows, axis=0)
    assert pack.lo == _I64_MIN and pack.hi == _I64_MAX
    assert pack.top  # any TOP row taints the join
    # Unit-bearing rows agree on ms; the unitless (polymorphic) hash
    # row doesn't veto the join.
    assert pack.unit == "ms"
    assert len(pack.rows) == 3 and pack.rows_axis == 0


# -- unit algebra --------------------------------------------------------

def test_units_epoch_arithmetic():
    assert units.add("epoch_ms", "ms") == ("epoch_ms", None)
    _, err = units.add("epoch_ms", "epoch_ms")
    assert err and "absolute timestamps" in err
    assert units.sub("epoch_ms", "epoch_ms") == ("ms", None)
    _, err = units.sub("count", "epoch_ms")
    assert err


def test_units_rate_algebra():
    assert units.mul("count", "rate_ms") == ("ms", None)
    assert units.div("ms", "count") == ("rate_ms", None)
    assert units.div("ms", "rate_ms") == ("count", None)
    _, err = units.add("ns", "ms")
    assert err  # granularity mixing never auto-converts


def test_units_gradual_none_is_polymorphic():
    assert units.add(None, "ms") == ("ms", None)
    assert units.join("ms", None) == ("ms", None)
    assert units.compare(None, "epoch_ms") is None


# -- the walker on a synthetic jaxpr -------------------------------------

def _walk(fn, *seeds):
    args = tuple(jnp.zeros((), jnp.int64) for _ in seeds)
    closed = jax.make_jaxpr(fn)(*args)
    w = RangeWalk()
    out = w.walk(closed, list(seeds))
    return w, out


def test_walker_flags_provable_overflow():
    w, _ = _walk(lambda a, b: a * b,
                 AbsVal(0, 2**40), AbsVal(0, 2**40))
    assert any(i.cls == "overflow" for i in w.issues)


def test_walker_accepts_bounded_product():
    w, out = _walk(lambda a, b: a * b,
                   AbsVal(0, 2**30), AbsVal(0, 2**30))
    assert not w.issues
    assert out[0].hi == 2**60


def test_walker_saturating_add_stays_in_range():
    w, out = _walk(_sat_add_i64, top_of("int64"), top_of("int64"))
    assert not any(i.cls == "overflow" for i in w.issues)
    assert out[0].lo >= _I64_MIN and out[0].hi <= _I64_MAX


def test_walker_taints_epoch_plus_negative():
    w, _ = _walk(lambda now, d: now + d,
                 AbsVal(0, 4102444800000, unit="epoch_ms"),
                 AbsVal(-10, 10, unit="ms"))
    assert any(i.cls == "negative-duration" for i in w.issues)


# -- negative control: the unclamped hits*cost fixture -------------------

def test_fixture_overflows_with_executed_witness():
    fs = run(select=["ranges"], specs=fixture_specs(),
             envelope_dir=FIXTURE_ENVELOPES, root=REPO)
    overflow = [f for f in fs if f.checker == "overflow"]
    assert overflow, "\n".join(f.render() for f in fs)
    assert any("int64" in f.message for f in overflow)
    witness = [f for f in fs if f.checker == "witness"]
    assert witness, "overflow must ship an executed witness"
    msg = witness[0].message
    assert "WRAPPED" in msg and "negative output" in msg
    # The witness is a real kernel execution, not an interval bound:
    # 4e9 * 4e9 mod 2^64, reinterpreted signed, is this exact value.
    assert str((4_000_000_000 * 4_000_000_000) % 2**64 - 2**64) in msg


def test_loosened_envelope_is_rejected(tmp_path):
    src = FIXTURE_ENVELOPES / "fixture_mul_unclamped.json"
    raw = json.loads(src.read_text())
    # Clamp the declared inputs so the kernel genuinely cannot wrap,
    # then leave expect_peak at the old (now unreachable) value: the
    # declaration is looser than provable and must be an ERROR.
    for rule in raw["inputs"]:
        rule["max"] = min(int(rule["max"]), 1000)
    (tmp_path / src.name).write_text(json.dumps(raw))
    fs = run(select=["ranges"], specs=fixture_specs(),
             envelope_dir=tmp_path, root=REPO)
    peak = [f for f in fs if f.checker == "peak"]
    assert peak and "looser than provable" in peak[0].message
    assert all(f.checker != "overflow" for f in fs)


def test_real_kernel_is_strict_clean():
    # One representative of the apply family; the full 23-kernel sweep
    # is the CI gubrange job (scripts/gubrange_smoke.py).
    fs = run(select=["ranges"], kernel="apply_batch", root=REPO)
    assert fs == [], "\n".join(f.render() for f in fs)


def test_envelope_budget_requires_reason():
    env = load_envelope(
        Path("tools/gubrange/envelopes/apply_batch.json")
    )
    env.reasons.pop("negative-duration")
    errs = env.validate()
    assert any("no written reason" in e for e in errs)
    env.budgets["overflow"] = 1
    assert any("non-budgetable" in e for e in env.validate())


# -- saturating helpers: device == oracle at the corners -----------------

_CORNERS = [
    0, 1, -1, 2, -2,
    2**31 - 1, 2**31, 2**31 + 1, -(2**31) - 1, -(2**31), -(2**31) + 1,
    2**53 - 1, 2**53, 2**53 + 1, -(2**53) - 1, -(2**53), -(2**53) + 1,
    2**62, -(2**62),
    _I64_MAX - 1, _I64_MAX, _I64_MIN, _I64_MIN + 1,
]


def _device_sat(fn, a, b):
    out = fn(jnp.asarray(a, jnp.int64), jnp.asarray(b, jnp.int64))
    return np.asarray(out).astype(object).tolist()


def test_sat_add_matches_pymodel_at_corners():
    pairs = [(a, b) for a in _CORNERS for b in _CORNERS]
    av = [p[0] for p in pairs]
    bv = [p[1] for p in pairs]
    got = _device_sat(_sat_add_i64, av, bv)
    want = [_sat_add(a, b) for a, b in pairs]
    assert got == want


def test_sat_sub_matches_pymodel_at_corners():
    pairs = [(a, b) for a in _CORNERS for b in _CORNERS]
    av = [p[0] for p in pairs]
    bv = [p[1] for p in pairs]
    got = _device_sat(_sat_sub_i64, av, bv)
    want = [_sat_sub(a, b) for a, b in pairs]
    assert got == want


_TRUNC_EDGES = [
    0.0, -0.0, 1.5, -1.5, 2.5, -2.5,
    float(2**53) - 1.0, float(2**53), float(2**53) + 2.0,
    math.nextafter(float(2**63), 0.0),   # largest double below 2^63
    float(2**63),                        # saturates at I64_MAX
    math.nextafter(float(-(2**63)), 0.0),
    float(-(2**63)),                     # exactly representable: I64_MIN
    math.nextafter(float(-(2**63)), -math.inf),  # below: saturates
    math.inf, -math.inf, math.nan,
]


def test_go_trunc_saturation_extends_to_float_edges():
    got = np.asarray(
        _trunc_i64(jnp.asarray(_TRUNC_EDGES, jnp.float64))
    ).astype(object).tolist()
    want = [_trunc(x) for x in _TRUNC_EDGES]
    assert got == want


# -- edge fuzz: hypothesis when available, corner sweep otherwise --------

def _check_sat_pair(a, b):
    assert _device_sat(_sat_add_i64, [a], [b]) == [_sat_add(a, b)]
    assert _device_sat(_sat_sub_i64, [a], [b]) == [_sat_sub(a, b)]


def _near(c, spread=2):
    return [min(max(c + d, _I64_MIN), _I64_MAX)
            for d in range(-spread, spread + 1)]


if HAVE_HYPOTHESIS:

    @settings(max_examples=200, deadline=None)
    @given(
        st.integers(min_value=_I64_MIN, max_value=_I64_MAX),
        st.integers(min_value=_I64_MIN, max_value=_I64_MAX),
    )
    def test_sat_fuzz(a, b):
        _check_sat_pair(a, b)

else:

    def test_sat_fuzz():
        # Deterministic stand-in: every pair within ±2 of each power-of-
        # two corner, plus a seeded uniform sample over the full range.
        pts = sorted({p for c in (0, 2**31, 2**53, 2**62, _I64_MAX,
                                  _I64_MIN, -(2**31), -(2**53))
                      for p in _near(c)})
        a = np.array([x for x in pts for _ in pts], dtype=np.int64)
        b = np.array(list(pts) * len(pts), dtype=np.int64)
        rng = np.random.default_rng(20260806)
        ra = rng.integers(_I64_MIN, _I64_MAX, size=512, dtype=np.int64)
        rb = rng.integers(_I64_MIN, _I64_MAX, size=512, dtype=np.int64)
        av = np.concatenate([a, ra]).astype(object).tolist()
        bv = np.concatenate([b, rb]).astype(object).tolist()
        assert _device_sat(_sat_add_i64, av, bv) == [
            _sat_add(x, y) for x, y in zip(av, bv)
        ]
        assert _device_sat(_sat_sub_i64, av, bv) == [
            _sat_sub(x, y) for x, y in zip(av, bv)
        ]


# -- CLI surface ---------------------------------------------------------

def test_cli_strict_single_kernel(tmp_path):
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "tools.gubrange", "--select", "ranges",
         "--kernel", "apply_batch", "--strict", "--json",
         "--dump-dir", str(tmp_path / "dumps")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout) == []
    assert not (tmp_path / "dumps").exists()  # dumps only on failure
