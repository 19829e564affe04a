"""ops/f64bits.py: IEEE binary64 on the bits, in integer words.

Every operation against numpy's float64 by `view(int64)` equality, under
`jit`, on seeded operands and on the grids where the v5e's own float64 (a
pair of float32s) departed from IEEE (PERF.md section 7, PR 33); the step
programs hold no value of a float dtype, so what passes here on the CPU
passes on the chip; and a bucket carried in the table (spend, wait, leak)
equals core/pymodel.py at every step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops import f64bits as F
from gubernator_tpu.ops import step as sp
from gubernator_tpu.ops.state import init_table

N = 1 << 20          # operand pairs an operation and an operand class
I64 = np.iinfo(np.int64)
TINY = np.finfo(np.float64).tiny

OPS = {
    "add": (jax.jit(F.add), np.add),
    "sub": (jax.jit(F.sub), np.subtract),
    "mul": (jax.jit(F.mul), np.multiply),
    "div": (jax.jit(F.div), np.divide),
}
from_i64 = jax.jit(F.from_i64)
trunc_i64 = jax.jit(F.trunc_i64)


def _operands(kind: str, rng) -> np.ndarray:
    if kind == "bits":          # any pattern: subnormals, inf, NaN among them
        return rng.integers(I64.min, I64.max, N, dtype=np.int64,
                            endpoint=True).view(np.float64)
    if kind == "ints":          # float64(int64) at every magnitude
        v = rng.integers(I64.min, I64.max, N, dtype=np.int64, endpoint=True)
        return (v >> rng.integers(0, 64, N)).astype(np.float64)
    if kind == "quotients":     # what a rate or a leak is
        return (rng.integers(0, 1 << 44, N).astype(np.float64)
                / rng.integers(1, 1 << 31, N).astype(np.float64))
    raise KeyError(kind)


def _same(got_bits, want: np.ndarray) -> np.ndarray:
    """Bit for bit; any NaN for a NaN (f64bits has the one quiet NaN)."""
    got_bits = np.asarray(got_bits)
    return (got_bits == F.to_bits(want)) | (
        np.isnan(want) & np.isnan(F.from_bits(got_bits)))


def _in_mul_div_domain(a, b, want) -> np.ndarray:
    """mul and div are IEEE for zero or normal operands whose result is
    zero, normal, inf or NaN (the module's envelope): the step's operands
    are conversions of int64s and quotients of such."""
    def ok(x):
        return ~np.isfinite(x) | (x == 0) | (np.abs(x) >= TINY)

    return ok(a) & ok(b) & (
        ~np.isfinite(want) | (np.abs(want) >= TINY)
        | ((want == 0) & ((a == 0) | (b == 0) | np.isinf(a) | np.isinf(b))))


@pytest.mark.parametrize("kb", ["bits", "ints", "quotients"])
@pytest.mark.parametrize("ka", ["bits", "ints", "quotients"])
@pytest.mark.parametrize("op", sorted(OPS))
def test_operation_is_ieee_bit_for_bit(op, ka, kb):
    rng = np.random.default_rng([34, sorted(OPS).index(op), len(ka), len(kb)])
    a, b = _operands(ka, rng), _operands(kb, rng)
    if ka == kb == "bits":
        # Half the pairs a few ulps to a few binades apart: cancellation.
        near = (F.to_bits(a) ^ rng.integers(0, 1 << 54, N)).view(np.float64)
        b = np.where(rng.random(N) < 0.5, near, b)
    fn, ref = OPS[op]
    with np.errstate(all="ignore"):
        want = ref(a, b)
    ok = _same(fn(F.to_bits(a), F.to_bits(b)), want)
    if op in ("mul", "div"):
        dom = _in_mul_div_domain(a, b, want)
        assert dom.sum() > 0.8 * N
        ok |= ~dom
    bad = np.flatnonzero(~ok)
    assert len(bad) == 0, [(a[i].hex(), b[i].hex(), want[i].hex())
                           for i in bad[:5]]


EDGE_INTS = np.array(
    [0, 1, -1, 2, 3, 1 << 52, (1 << 53) - 1, 1 << 53, (1 << 53) + 1,
     (1 << 53) + 2, (1 << 53) + 3, (1 << 54) + 2, (1 << 54) + 6,
     -(1 << 53), -(1 << 53) - 1, -(1 << 53) - 3, 1 << 62, (1 << 62) + 1,
     -(1 << 62), (1 << 62) + (1 << 9), (1 << 62) + (1 << 9) + 1,
     (1 << 62) + 3 * (1 << 9), I64.max, I64.max - 1, I64.max - 511,
     I64.max - 512, I64.min, I64.min + 1, I64.min + 1024, I64.min + 1025],
    dtype=np.int64)


def test_from_i64_rounds_to_nearest_even():
    rng = np.random.default_rng(341)
    v = rng.integers(I64.min, I64.max, N, dtype=np.int64, endpoint=True)
    v = np.concatenate([v >> rng.integers(0, 64, N), EDGE_INTS])
    got = np.asarray(from_i64(v))
    np.testing.assert_array_equal(got, F.to_bits(v.astype(np.float64)))


def _go_trunc(v: np.ndarray) -> np.ndarray:
    """core/pymodel.py `_trunc`, on an array."""
    from gubernator_tpu.core.pymodel import _trunc

    return np.array([_trunc(float(x)) for x in v], dtype=np.int64)


TRUNC_EDGES = np.array(
    [0.0, -0.0, 0.5, -0.5, 0.999999, 1.0, -1.5, 1.5, 18.999999999,
     2.9999999999999996, 124.99999999999999, 2.0**52 + 0.5, 2.0**53,
     -(2.0**53) - 2, 2.0**62, -(2.0**62), 2.0**63 - 1024, 2.0**63,
     -(2.0**63), -(2.0**63) - 2048, 2.0**64, 1e300, -1e300, np.inf,
     -np.inf, np.nan, 5e-324, TINY], dtype=np.float64)


def test_trunc_i64_keeps_the_documented_contract():
    rng = np.random.default_rng(342)
    v = np.concatenate([
        rng.integers(I64.min, I64.max, 1 << 17, dtype=np.int64,
                     endpoint=True).view(np.float64),
        _operands("ints", rng)[: 1 << 17] + 0.5,
        _operands("quotients", rng)[: 1 << 17],
        TRUNC_EDGES,
    ])
    np.testing.assert_array_equal(
        np.asarray(trunc_i64(F.to_bits(v))), _go_trunc(v))


def test_comparisons_and_constants():
    v = np.concatenate([TRUNC_EDGES, -TRUNC_EDGES])
    bits = F.to_bits(v)
    with np.errstate(invalid="ignore"):
        np.testing.assert_array_equal(np.asarray(F.is_zero(bits)), v == 0)
        np.testing.assert_array_equal(np.asarray(F.ge_one(bits)), v >= 1)
        want = np.where(v < 0, 0.0, np.where(v == 0, 0.0, v))
    assert _same(F.max0(bits), want).all()
    assert F.from_bits(np.array([F.ZERO, F.ONE])).tolist() == [0.0, 1.0]
    assert F.const(8.0) == F.to_bits(np.array([8.0]))[0]


# -- the grids of the record (PERF.md section 7, PR 33) ----------------------

def test_tenths_plus_a_leak_truncates_as_ieee():
    """lb1 in tenths of a token from the host plus a leak of a whole token
    or more at 10 ms a token: on the v5e's float64, one short in 15,003
    of 391,391 cases, every one a sum IEEE makes whole (0.1 + 2.9)."""
    tenths = np.arange(0, 1001, dtype=np.int64)
    elapsed = np.arange(10, 401, dtype=np.int64)
    a = np.repeat(tenths, len(elapsed)).astype(np.float64) / 10.0
    e = np.tile(elapsed, len(tenths))
    assert len(a) == 391 * 1001

    @jax.jit
    def device(a_bits, e):
        rate = F.div(F.from_i64(jnp.int64(1000)), F.from_i64(jnp.int64(100)))
        q = F.div(F.from_i64(e), rate)
        s = F.add(a_bits, q)
        d = F.sub(a_bits, F.ONE)
        return q, s, F.trunc_i64(s), d, F.trunc_i64(d)

    q, s, s_i, d, d_i = (np.asarray(x) for x in device(F.to_bits(a), e))
    want_q = e.astype(np.float64) / (1000.0 / 100.0)
    np.testing.assert_array_equal(q, F.to_bits(want_q))
    np.testing.assert_array_equal(s, F.to_bits(a + want_q))
    np.testing.assert_array_equal(s_i, np.trunc(a + want_q).astype(np.int64))
    np.testing.assert_array_equal(d, F.to_bits(a - 1.0))
    np.testing.assert_array_equal(d_i, np.trunc(a - 1.0).astype(np.int64))
    whole = (a + want_q) == np.trunc(a + want_q)
    assert whole.sum() > 30_000        # the sums that went one short


def test_whole_multiples_of_2592_us_a_token_leak_whole_tokens():
    """10^9 in 30 days leaks a token every 2.592 ms: the v5e's quotient
    went short at 61 of the 62 whole multiples up to 20 s (324 ms: 125.0
    became 124.99999999999997, then 124)."""
    dur, lim = 30 * 24 * 3600 * 1000, 10**9
    elapsed = np.arange(0, 20_001, dtype=np.int64)

    @jax.jit
    def device(e):
        rate = F.div(F.from_i64(jnp.int64(dur)), F.from_i64(jnp.int64(lim)))
        leak = F.div(F.from_i64(e), rate)
        return rate, leak, F.trunc_i64(leak)

    rate, leak, leak_i = (np.asarray(x) for x in device(elapsed))
    want = elapsed.astype(np.float64) / (dur / lim)
    assert F.from_bits(rate) == dur / lim == 2.592
    np.testing.assert_array_equal(leak, F.to_bits(want))
    np.testing.assert_array_equal(leak_i, np.trunc(want).astype(np.int64))
    multiples = elapsed[(elapsed * 1000) % 2592 == 0][1:]
    assert len(multiples) == 61 and 324 in multiples
    assert (want[multiples] == np.trunc(want[multiples])).all()


def test_reset_product_rounds_above_2_53_and_saturates():
    """now + (limit - remaining) x rate, the step's reset expression."""
    v = EDGE_INTS
    a, b = np.meshgrid(v, v)
    a, b = a.ravel(), b.ravel()
    now = np.int64(1_790_000_000_000)

    @jax.jit
    def device(lim, rem, rate):
        f = F.from_i64
        return F.trunc_i64(F.add(f(now), F.mul(F.sub(f(lim), f(rem)),
                                               f(rate))))

    got = np.asarray(device(a, np.roll(a, 7), b))
    with np.errstate(all="ignore"):
        want = float(now) + (a.astype(np.float64)
                             - np.roll(a, 7).astype(np.float64)
                             ) * b.astype(np.float64)
    np.testing.assert_array_equal(got, _go_trunc(want))
    assert (got == I64.max).any() and (got == I64.min).any()


# -- no floating-point value in the step --------------------------------------

def _dtypes_of(jaxpr, seen=None) -> set:
    """Every variable's dtype in a jaxpr and in the jaxprs its equations
    carry (pjit, scan, shard_map, custom calls)."""
    seen = set() if seen is None else seen
    out = set()
    for v in list(jaxpr.invars) + list(jaxpr.outvars) + list(jaxpr.constvars):
        if hasattr(v, "aval") and hasattr(v.aval, "dtype"):
            out.add(np.dtype(v.aval.dtype))
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "dtype"):
                out.add(np.dtype(v.aval.dtype))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns") and id(inner) not in seen:
                    seen.add(id(inner))
                    out |= _dtypes_of(inner, seen)
    return out


def _assert_no_float(jaxpr) -> None:
    dtypes = _dtypes_of(jaxpr)
    assert len(dtypes) >= 4, dtypes            # the walk saw the program
    floats = sorted(str(d) for d in dtypes if d.kind in "fc")
    assert floats == [], floats


@pytest.mark.parametrize("lanes", [128, 4096])
def test_one_chip_step_program_holds_no_float(lanes):
    jaxpr = jax.make_jaxpr(
        lambda t, q, now: sp.apply_batch_packed_q(t, q, now, ways=8)
    )(init_table(1 << 12), np.zeros((12, lanes), np.int64), np.int64(0))
    _assert_no_float(jaxpr.jaxpr)


def test_mesh_step_program_holds_no_float():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.parallel.mesh import SHARD_AXIS, make_mesh
    from gubernator_tpu.parallel.sharded import (
        init_sharded_table,
        make_sharded_step_packed,
    )

    n = 4
    mesh = make_mesh(n)
    table = init_sharded_table(n << 10, NamedSharding(mesh, P(SHARD_AXIS)))
    fn = make_sharded_step_packed(mesh, 8)
    jaxpr = jax.make_jaxpr(fn)(
        table, np.zeros((12, n, 64), np.int64), np.int64(0))
    text = str(jaxpr)
    assert "shard_map" in text
    _assert_no_float(jaxpr.jaxpr)


def test_the_lower_precision_control_still_bites():
    """bench/serve.py `--control f32` replaces `ops.step._f64`: the seam
    takes the float64 array it hands on, rounds `now` to 24 bits, and the
    leaky reset_time leaves its RPC by minutes instead of crashing."""
    def run(batch, now):
        return sp.apply_batch_impl(init_table(1 << 10), batch, now, ways=8)[1]

    z = np.zeros(8, np.int64)
    act = np.ones(8, bool)
    batch = sp.DeviceBatchJ(
        key_hash=np.arange(1, 9, dtype=np.int64), hits=z + 1, limit=z + 100,
        duration=z + 1000, algo=np.ones(8, np.int32), burst=z + 100,
        reset_remaining=~act, is_greg=~act, greg_expire=z, greg_duration=z,
        active=act, use_cached=~act)
    now = np.int64(1_790_000_000_123)
    sound = np.asarray(jax.jit(run)(batch, now).reset_time)
    assert (sound == now + 10).all()
    seam = sp._f64
    sp._f64 = lambda x: x.astype(jnp.float32).astype(jnp.float64)
    try:
        # (a wrapper of its own: jit caches by function)
        rounded = np.asarray(
            jax.jit(lambda b, t: run(b, t))(batch, now).reset_time)
    finally:
        sp._f64 = seam
    assert (np.abs(rounded - sound) > 10_000).all(), rounded - sound


# -- a bucket carried in the table ----------------------------------------------

def test_carried_leaky_buckets_equal_the_reference_at_every_step():
    """4,096 leaky buckets, 400 steps: spend 0-2, wait 1-39 ms, leak — the
    remainder carried in the table's bits column from step to step.  On the
    v5e's float64 2,346 of 4,096 such lanes departed from IEEE, the first
    at step 2."""
    from gubernator_tpu.core import clock as clock_mod
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.core.types import Algorithm, RateLimitReq

    lanes, steps, limit, duration = 4096, 400, 100, 1000
    rng = np.random.default_rng(33)
    clk = clock_mod.Clock()
    model = PyRateLimiter(clock=clk)
    keys = np.arange(1, lanes + 1, dtype=np.int64) * 2654435761 + (1 << 40)
    names = [f"k{i}" for i in range(lanes)]
    table = init_table(1 << 16)
    z = np.zeros(lanes, np.int64)
    no = np.zeros(lanes, bool)
    now = 1_790_000_000_000
    whole_leaks = 0
    for step in range(steps):
        now += int(rng.integers(1, 40))
        hits = rng.integers(0, 3, lanes).astype(np.int64)
        act = rng.random(lanes) < 0.6
        batch = sp.DeviceBatchJ(
            key_hash=np.where(act, keys, 0), hits=hits, limit=z + limit,
            duration=z + duration, algo=np.ones(lanes, np.int32),
            burst=z + limit, reset_remaining=no, is_greg=no, greg_expire=z,
            greg_duration=z, active=act, use_cached=no)
        table, resp = sp.apply_batch(table, batch, np.int64(now), ways=8)
        status, remaining, reset = (np.asarray(x) for x in (
            resp.status, resp.remaining, resp.reset_time))
        assert np.asarray(resp.persisted)[act].all()
        clk.freeze(now * 1_000_000)
        for i in np.flatnonzero(act):
            w = model.get_rate_limit(RateLimitReq(
                name="carried", unique_key=names[i], hits=int(hits[i]),
                limit=limit, duration=duration,
                algorithm=Algorithm.LEAKY_BUCKET))
            got = (int(status[i]), int(remaining[i]), int(reset[i]))
            assert got == (int(w.status), w.remaining, w.reset_time), (
                step, i, got, w)
        whole_leaks += int((remaining[act] > 0).sum())
    assert whole_leaks > 100_000
