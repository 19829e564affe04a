"""Exact interval domain for the gubrange abstract interpreter.

An abstract value is a closed interval [lo, hi] in exact Python
arithmetic (unbounded ints for integer dtypes, IEEE floats with ±inf
for float dtypes), a dimensional unit tag (tools/gubrange/units.py),
and a TOP flag.

TOP means "unconstrained by the operational envelope" — e.g. a raw key
fingerprint, whose value genuinely spans the whole dtype.  TOP values
flow freely through moves, selects, comparisons and bit-masking (a
fingerprint may be hashed, bucketed, compared), but *signed integer
arithmetic* on a TOP operand is a finding: a sum or product over an
unconstrained int64 is exactly the silent-wrap class this plane
exists to rule out (it can only be licensed by an envelope budget with
a written reason).

UNSIGNED integer arithmetic is modular by definition (jnp uint64 is
arithmetic mod 2^64 — the multiply-shift row hashing in ops/sketch.py
relies on it), so uint ops never raise overflow findings; a result
that would leave the dtype range widens to the full range instead.

Floats carry honest interval endpoints (±inf included); float
arithmetic never "overflows" in the wrap sense, so the only float
finding is division by a zero-inclusive interval.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Optional, Tuple, Union

Num = Union[int, float]

INT_RANGES = {
    "int64": (-(2**63), 2**63 - 1),
    "int32": (-(2**31), 2**31 - 1),
    "int16": (-(2**15), 2**15 - 1),
    "int8": (-(2**7), 2**7 - 1),
    "uint64": (0, 2**64 - 1),
    "uint32": (0, 2**32 - 1),
    "uint16": (0, 2**16 - 1),
    "uint8": (0, 2**8 - 1),
    "bool": (0, 1),
}


def dtype_kind(dtype_name: str) -> str:
    """'int' | 'uint' | 'bool' | 'float' for a numpy dtype name."""
    if dtype_name == "bool":
        return "bool"
    if dtype_name.startswith("uint"):
        return "uint"
    if dtype_name.startswith("int"):
        return "int"
    return "float"


def dtype_range(dtype_name: str) -> Tuple[Num, Num]:
    if dtype_name in INT_RANGES:
        return INT_RANGES[dtype_name]
    return (-math.inf, math.inf)


@dataclass(frozen=True)
class AbsVal:
    """One abstract value: interval + unit + unconstrained flag.

    `rows`/`rows_axis` is the packed-stack refinement: the q-form
    kernels ship 12 semantically-distinct int64 rows in one array
    (key_hash beside hits beside flags), and one scalar interval over
    the whole pack would be uselessly wide.  When set, `rows[i]` bounds
    index i along `rows_axis`, and the top-level lo/hi/unit/top are
    ALWAYS their join — so every transfer that ignores rows is
    conservative-correct automatically; only slice/squeeze/scan
    propagate the refinement (see absint.py)."""

    lo: Num
    hi: Num
    unit: Optional[str] = None
    top: bool = False
    rows: Optional[tuple] = None
    rows_axis: int = 0
    # Split-column refinement (the slot table's physical layout,
    # ops/state.py): `half = (part, logical)` marks a value that is one
    # word of an int64 whose LOGICAL bound is the AbsVal `logical` —
    # part "lo"/"hi" for the uint32 words (as stored, gathered and
    # scattered), "hi32" for the high word shifted into place, "u64"
    # for the whole value in its uint64 guise.  lo/hi/unit of the
    # carrier stay honest bounds of the word itself, so a transfer that
    # ignores `half` is conservative; the combine and the split
    # (absint.py) use it to hand the logical bound across exactly.
    half: Optional[tuple] = None

    def with_unit(self, unit: Optional[str]) -> "AbsVal":
        return replace(self, unit=unit)

    def is_exact(self) -> bool:
        return self.lo == self.hi

    def __str__(self) -> str:
        u = f" {self.unit}" if self.unit else ""
        t = " TOP" if self.top else ""
        r = f" rows@{self.rows_axis}x{len(self.rows)}" if self.rows else ""
        h = f" {self.half[0]}-of {self.half[1]}" if self.half else ""
        return f"[{self.lo}, {self.hi}]{u}{t}{r}{h}"


def from_rows(rows, axis: int) -> AbsVal:
    """The pack value: top-level bounds/unit/top = join of the rows."""
    rows = tuple(rows)
    units = {r.unit for r in rows if r.unit is not None}
    return AbsVal(
        lo=min(r.lo for r in rows),
        hi=max(r.hi for r in rows),
        unit=units.pop() if len(units) == 1 else None,
        top=any(r.top for r in rows),
        rows=rows,
        rows_axis=axis,
    )


def top_of(dtype_name: str, unit: Optional[str] = None) -> AbsVal:
    lo, hi = dtype_range(dtype_name)
    return AbsVal(lo, hi, unit=unit, top=True)


WORD = 0xFFFFFFFF


def half_of(logical: AbsVal, part: str) -> AbsVal:
    """The uint32 word `part` ("lo" | "hi") of an int64 bounded by
    `logical`, tagged with that bound."""
    a, b = int(logical.lo), int(logical.hi)
    if a < 0:  # two's complement: a negative value's words span all
        lo, hi = 0, WORD
    elif part == "hi":
        lo, hi = a >> 32, b >> 32
    elif (a >> 32) == (b >> 32):
        lo, hi = a & WORD, b & WORD
    else:
        lo, hi = 0, WORD
    return AbsVal(lo, hi, half=(part, logical))


def exact(v: Num, unit: Optional[str] = None) -> AbsVal:
    return AbsVal(v, v, unit=unit)


def join_bounds(a: AbsVal, b: AbsVal) -> Tuple[Num, Num, bool]:
    return (min(a.lo, b.lo), max(a.hi, b.hi), a.top or b.top)


# -- endpoint arithmetic (exact; no dtype clipping here) -----------------

def add_bounds(a: AbsVal, b: AbsVal) -> Tuple[Num, Num]:
    return (a.lo + b.lo, a.hi + b.hi)


def sub_bounds(a: AbsVal, b: AbsVal) -> Tuple[Num, Num]:
    return (a.lo - b.hi, a.hi - b.lo)


def _prod(x: Num, y: Num) -> Num:
    # 0 * inf is NaN in IEEE; the exact product's contribution is 0.
    if x == 0 or y == 0:
        return 0
    return x * y


def mul_bounds(a: AbsVal, b: AbsVal) -> Tuple[Num, Num]:
    cands = [
        _prod(a.lo, b.lo), _prod(a.lo, b.hi),
        _prod(a.hi, b.lo), _prod(a.hi, b.hi),
    ]
    return (min(cands), max(cands))


def _idiv(x: int, y: int) -> int:
    """C/Go/XLA integer division: truncation toward zero."""
    q = abs(x) // abs(y)
    return -q if (x < 0) != (y < 0) else q


def div_bounds_int(a: AbsVal, b: AbsVal) -> Tuple[int, int, bool]:
    """Truncating integer division; returns (lo, hi, zero_divisor).

    When the divisor interval includes 0, the quotient bounds are taken
    over the divisor with 0 excluded (the caller reports the finding;
    excluding 0 keeps the analysis usefully precise past it).
    """
    zero_div = b.lo <= 0 <= b.hi
    pieces = []
    if b.hi >= 1:
        pieces.append((max(b.lo, 1), b.hi))
    if b.lo <= -1:
        pieces.append((b.lo, min(b.hi, -1)))
    if not pieces:  # divisor is exactly [0, 0]
        return (0, 0, True)
    cands = []
    for plo, phi in pieces:
        for x in (a.lo, a.hi):
            for y in (plo, phi):
                cands.append(_idiv(int(x), int(y)))
        # The quotient magnitude peaks at the smallest |divisor|, which
        # is an interval endpoint here; numerator extremes included
        # above; 0 crossing of the numerator adds candidate 0.
        if a.lo < 0 < a.hi:
            cands.append(0)
    return (min(cands), max(cands), zero_div)


def div_bounds_float(a: AbsVal, b: AbsVal) -> Tuple[float, float, bool]:
    """IEEE float division bounds; returns (lo, hi, zero_divisor)."""
    zero_div = b.lo <= 0 <= b.hi
    pieces = []
    if b.hi > 0:
        pieces.append((b.lo if b.lo > 0 else math.nextafter(0, 1), b.hi))
    if b.lo < 0:
        pieces.append((b.lo, b.hi if b.hi < 0 else math.nextafter(0, -1)))
    if not pieces:
        # divisor identically 0: x/0 is ±inf (sign of numerator), 0/0 NaN
        return (-math.inf, math.inf, True)
    cands = []
    for plo, phi in pieces:
        for x in (float(a.lo), float(a.hi)):
            for y in (plo, phi):
                if x == 0.0:
                    cands.append(0.0)
                else:
                    try:
                        cands.append(x / y)
                    except (ZeroDivisionError, OverflowError):
                        cands.append(math.inf if (x > 0) == (y > 0)
                                     else -math.inf)
        if a.lo < 0 < a.hi:
            cands.append(0.0)
    if zero_div:
        # a non-zero numerator over a zero-crossing divisor reaches ±inf
        if a.hi > 0:
            cands.append(math.inf)
        if a.lo < 0:
            cands.append(-math.inf)
    return (min(cands), max(cands), zero_div)


def rem_bounds_int(a: AbsVal, b: AbsVal) -> Tuple[int, int, bool]:
    """lax.rem: sign follows the dividend, |r| < |b|."""
    zero_div = b.lo <= 0 <= b.hi
    mag = max(abs(int(b.lo)), abs(int(b.hi)))
    if mag == 0:
        return (0, 0, True)
    lo = -(mag - 1) if a.lo < 0 else 0
    hi = (mag - 1) if a.hi > 0 else 0
    # Tighter when the WHOLE dividend interval sits inside (-mag, mag):
    # there rem(x) == x.  (One-sided tightening is unsound — a dividend
    # interval [-1000, -1] over modulus 7 still reaches remainder 0 at
    # -7, so a.hi alone may not cap the bound.)
    if a.lo > -mag and a.hi < mag:
        lo = max(lo, int(a.lo))
        hi = min(hi, int(a.hi))
    return (lo, hi, zero_div)


def trunc_to_int_bounds(a: AbsVal, dtype_name: str) -> Tuple[int, int]:
    """float -> int convert under the _trunc_i64 saturation contract:
    truncation toward zero, out-of-range/±inf saturating at the dtype
    bounds, NaN -> 0 (pinned by tests/test_differential.py)."""
    rlo, rhi = dtype_range(dtype_name)
    lo = rlo if math.isinf(a.lo) or a.lo <= rlo else int(math.trunc(a.lo))
    hi = rhi if math.isinf(a.hi) or a.hi >= rhi else int(math.trunc(a.hi))
    return (max(lo, rlo), min(hi, rhi))
