"""IEEE-754 binary64 arithmetic on the 64 bits, in integer words.

The leaky bucket is Go's float64, operation for operation
(core/pymodel.py, algorithms.go:327-492).  A TPU has no float64: XLA
gives it a pair of float32s (about 48 bits) whose `+` and `/` are not
IEEE's, so on the v5e `0.1 + 2.9` truncated to 2 and a carried bucket
drifted off the reference (PERF.md section 7, PR 33).  Integer
arithmetic IS the same on every backend, so here a binary64 is its bit
pattern in an int64 and every operation is written out on uint64 words:
round to nearest, ties to even, bit for bit what numpy gives — on the
CPU and on the chip alike, which is what lets the CPU tests speak for
the chip (tests/test_f64bits.py).

A value is an `int64` array of bit patterns ("bits"); the host twins
`to_bits` / `from_bits` are views, so the host formats keep float64.

The envelope.  The step's operands come from the wire's int64 `limit`,
`duration`, `burst`, `hits`, the clock, and the stored column:

  from_i64   any int64: exact below 2^53, nearest-even above.  Never
             inf, NaN or subnormal; -0 never (0 gives +0).
  add, sub   FULL IEEE: zeros (signed), subnormals, infinities and NaN
             in, the IEEE result out (any NaN result is the one quiet
             NaN, 0x7FF8 << 48).  The stored column reaches these, and a host
             may have put any double there.  From the wire alone the
             column only ever holds 0 or a multiple of 2^-52 no smaller
             than that (a sum of whole numbers and of leaks >= 1), so
             subnormal, inf and NaN are unreachable without such a host.
  mul, div   operands are conversions of int64s and quotients of such:
             zero or normal, magnitude in [2^-63, 2^127].  On that
             domain the result is IEEE's, never subnormal or inf.
             Outside it: a subnormal operand counts as zero and a
             result below the normal range is zero (flush to zero);
             x/0 is inf, 0/0 and inf/inf NaN, overflow inf, as IEEE.
             (The step divides only under its `limit == 0` and
             `rate == 0` guards, so neither is reached.)
  trunc_i64  Go's int64(float64) under ops/step.py's documented
             contract: toward zero, saturating at the int64 bounds for
             out-of-range values and infinities, NaN -> 0, -0 -> 0.

Everything is uint64/int64 (tools/gubtrace: no narrowing cast;
tools/gubrange: unsigned words are modular by definition, and the few
signed values are exponents bounded by their masks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_U64 = jnp.uint64


def _c(x: int) -> np.uint64:
    return np.uint64(x)


_SIGN = _c(1 << 63)
_ABS = _c((1 << 63) - 1)
_FRAC = _c((1 << 52) - 1)
_HIDDEN = _c(1 << 52)
_EXP = _c(0x7FF)
_INF = _c(0x7FF << 52)
_NAN = _c(0x7FF8 << 48)
_M32 = _c(0xFFFFFFFF)
_0 = _c(0)
_1 = _c(1)
_I64_MAX = _c((1 << 63) - 1)
# Quotient bits a trip of div's loop: unrolled whole, the 56 steps cost the
# v5e's compiler 21 s a step program; as a loop they cost what the parent's
# program did (scripts/step_hlo.py).
_DIV_UNROLL = 8

# Bit patterns of the constants a caller selects with.
ZERO = np.int64(0)
ONE = np.int64(0x3FF0000000000000)


def to_bits(a) -> np.ndarray:
    """Host: float64 values -> their bits (a view where it can be)."""
    return np.ascontiguousarray(a, dtype=np.float64).view(np.int64)


def from_bits(b) -> np.ndarray:
    """Host: bits -> float64 values (a view where it can be)."""
    return np.ascontiguousarray(b, dtype=np.int64).view(np.float64)


def const(x: float) -> np.int64:
    return np.float64(x).view(np.int64)


def _u(bits) -> jax.Array:
    return jnp.asarray(bits).astype(jnp.int64).astype(_U64)


def _i(u) -> jax.Array:
    return u.astype(jnp.int64)


def _bit(cond) -> jax.Array:
    return cond.astype(_U64)


def _shr_sticky(m, d) -> jax.Array:
    """m >> d for any d >= 0, the bits shifted out OR-ed into bit 0
    (m < 2^63, so a shift clamped to 63 loses all of it to the sticky)."""
    dc = jnp.minimum(d, _c(63))
    lost = (m & ((_1 << dc) - _1)) != _0
    return (m >> dc) | _bit(lost)


def _halve_on_carry(s):
    """(s halved with its sticky kept where it reached bit 56, where): a
    sum or product that outgrew the 56-bit significand; the caller raises
    the exponent there."""
    carry = (s >> _c(56)) != _0
    return jnp.where(carry, (s >> _1) | (s & _1), s), carry


def _round_pack(sign, e, r) -> jax.Array:
    """sign | round-to-nearest-even of r * 2^(e - 1023 - 55).

    `r` < 2^57 carries the significand with its hidden bit at 55 and
    three extra bits (guard, round, sticky); `e` (uint64, 1..2047) is
    the biased exponent that position means — 1 with the hidden bit
    clear for a subnormal.  A carry out of the rounding walks into the
    exponent field by itself; what reaches the field's top is inf."""
    mant = r >> _c(3)
    rem = r & _c(7)
    up = (rem > _c(4)) | ((rem == _c(4)) & ((mant & _1) != _0))
    out = ((e - _1) << _c(52)) + mant + _bit(up)
    return sign | jnp.where(out >= _INF, _INF, out)


def from_i64(x) -> jax.Array:
    """float64(int64), as bits.  A float array (ops/step.py's `_f64` seam
    under the benchmark's lower-precision control) is taken by value."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.int64)
    u = _u(x)
    neg = x < 0
    mag = jnp.where(neg, _0 - u, u)
    lz = lax.clz(mag)
    m = mag << jnp.minimum(lz, _c(63))          # top bit at 63
    r = (m >> _c(8)) | _bit((m & _c(0xFF)) != _0)
    e = _c(1023 + 63) - lz
    out = _round_pack(jnp.where(neg, _SIGN, _0), e, r)
    return _i(jnp.where(mag == _0, _0, out))


def trunc_i64(bits) -> jax.Array:
    """int64(float64) toward zero; saturating; NaN -> 0."""
    u = _u(bits)
    neg = (u >> _c(63)) != _0
    e = (u >> _c(52)) & _EXP
    frac = u & _FRAC
    mant = frac | _HIDDEN
    point = _c(1023 + 52)
    rs = jnp.minimum(jnp.where(e < point, point - e, _0), _c(63))
    ls = jnp.minimum(jnp.where(e > point, e - point, _0), _c(63))
    mag = (mant >> rs) << ls
    mag = jnp.where(e < _c(1023), _0, mag)
    big = e >= _c(1023 + 63)
    pos = jnp.where(big, _I64_MAX, mag)
    ngt = jnp.where(big, _SIGN, _0 - mag)
    out = jnp.where(neg, ngt, pos)
    nan = (e == _EXP) & (frac != _0)
    return _i(jnp.where(nan, _0, out))


def neg(bits) -> jax.Array:
    return _i(_u(bits) ^ _SIGN)


def is_zero(bits) -> jax.Array:
    """x == 0.0 (either zero)."""
    return (_u(bits) & _ABS) == _0


def max0(bits) -> jax.Array:
    """max(x, 0.0): +0 for a negative x or -0, x otherwise (NaN kept)."""
    u = _u(bits)
    negative = ((u >> _c(63)) != _0) & ((u & _ABS) <= _INF)
    return _i(jnp.where(negative, _0, u))


def ge_one(bits) -> jax.Array:
    """x >= 1.0 (False for NaN)."""
    u = _u(bits)
    return (u >= _u(ONE)) & (u <= _INF)


def add(a, b) -> jax.Array:
    """a + b, IEEE (see the module docstring's envelope)."""
    ua, ub = _u(a), _u(b)
    swap = (ua & _ABS) < (ub & _ABS)
    x = jnp.where(swap, ub, ua)                 # |x| >= |y|
    y = jnp.where(swap, ua, ub)
    ex = (x >> _c(52)) & _EXP
    ey = (y >> _c(52)) & _EXP
    fx, fy = x & _FRAC, y & _FRAC
    mx = (fx | jnp.where(ex != _0, _HIDDEN, _0)) << _c(3)
    my = (fy | jnp.where(ey != _0, _HIDDEN, _0)) << _c(3)
    ex1 = jnp.maximum(ex, _1)
    ey1 = jnp.maximum(ey, _1)
    my = _shr_sticky(my, ex1 - ey1)
    same = ((x ^ y) >> _c(63)) == _0
    s = jnp.where(same, mx + my, mx - my)
    s, carry = _halve_on_carry(s)               # same signs only
    e = ex1 + _bit(carry)
    # Cancellation: bring the leading bit back to 55, as far as the
    # exponent allows (what is left below it is a subnormal).
    nl = jnp.where(s == _0, _0, lax.clz(s) - _c(8))
    sh = jnp.minimum(nl, e - _1)
    # An exact zero: +0 from a cancellation, the operands' own from 0 + 0.
    sign = jnp.where(~same & (s == _0), _0, x & _SIGN)
    out = _round_pack(sign, jnp.where(s == _0, _1, e - sh), s << sh)
    # inf and NaN: x is the larger magnitude, so a NaN is in x if anywhere.
    special = ex == _EXP
    y_inf = (ey == _EXP) & (fy == _0)
    bad = (fx != _0) | (y_inf & ~same)
    return _i(jnp.where(special, jnp.where(bad, _NAN, x), out))


def sub(a, b) -> jax.Array:
    """a - b, IEEE."""
    return add(a, neg(b))


def _unpack(u):
    e = (u >> _c(52)) & _EXP
    f = u & _FRAC
    zero = e == _0                              # subnormals count as zero
    inf = (e == _EXP) & (f == _0)
    nan = (e == _EXP) & (f != _0)
    return e.astype(jnp.int64), f | _HIDDEN, zero, inf, nan


def _finish(sign, e, r, zero, inf, nan) -> jax.Array:
    """Pack a mul/div result: `e` is a signed biased exponent, below 1
    flushes to zero, past 2046 is inf."""
    under = e < 1
    ec = jnp.clip(e, 1, 2047).astype(_U64)
    out = _round_pack(sign, ec, r)
    out = jnp.where(under | zero, sign, out)
    out = jnp.where(inf, sign | _INF, out)
    return _i(jnp.where(nan, _NAN, out))


def mul(a, b) -> jax.Array:
    """a * b for zero or normal operands (module docstring)."""
    ua, ub = _u(a), _u(b)
    sign = (ua ^ ub) & _SIGN
    ea, ma, az, ai, an = _unpack(ua)
    eb, mb, bz, bi, bn = _unpack(ub)
    # 53 x 53 -> 106 bits, on 32-bit limbs held in 64-bit words.
    al, ah = ma & _M32, ma >> _c(32)
    bl, bh = mb & _M32, mb >> _c(32)
    ll = al * bl
    mid = al * bh + ah * bl                     # < 2^54
    lo = ll + (mid << _c(32))
    hi = ah * bh + (mid >> _c(32)) + _bit(lo < ll)
    # p = hi:lo in [2^104, 2^106): keep 57 bits and a sticky.
    t = (hi << _c(15)) | (lo >> _c(49))
    t = t | _bit((lo & _c((1 << 49) - 1)) != _0)
    t, carry = _halve_on_carry(t)
    e = ea + eb - 1023 + carry.astype(jnp.int64)
    nan = an | bn | (ai & bz) | (bi & az)
    return _finish(sign, e, t, az | bz, ai | bi, nan)


def div(a, b) -> jax.Array:
    """a / b for zero or normal operands (module docstring)."""
    ua, ub = _u(a), _u(b)
    sign = (ua ^ ub) & _SIGN
    ea, ma, az, ai, an = _unpack(ua)
    eb, mb, bz, bi, bn = _unpack(ub)
    lt = ma < mb
    r = jnp.where(lt, ma << _1, ma)             # mb <= r < 2 mb
    e = ea - eb + 1023 - lt.astype(jnp.int64)
    # Restoring division, one quotient bit a step: 56 bits, the first a 1,
    # so the hidden bit lands at 55; the remainder is the sticky.
    def bit(_, qr):
        q, r = qr
        ge = r >= mb
        return (q << _1) | _bit(ge), jnp.where(ge, r - mb, r) << _1

    q, r = lax.fori_loop(0, 56, bit, (jnp.zeros_like(r), r),
                         unroll=_DIV_UNROLL)
    q = q | _bit(r != _0)
    nan = an | bn | (ai & bi) | (az & bz)
    return _finish(sign, e, q, az | bi, ai | bz, nan)
