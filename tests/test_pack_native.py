"""The native pack and unpack of a drain (native/gubtpu.cpp gub_pack_rounds,
gub_gather_rounds) held, bit for bit, to the numpy form the lane served
until PR 42 and keeps as its plain reference (`fastpath._reference_pack`:
`_plan_cascade` + `_cascade_or_rounds` + `native.assign_rounds` +
`_build_rounds` + `pack_batch_q` / `pack_grid_batch`; `_reference_unpack`:
the per-round fancy-index gather + `tally_from_rounds` + the `last_of`
dict).

Every word of every round's tier is compared, zeros included; the
(round, lane) of every check; the cascade's groups with their occurrence
lists (the native map numbers them in ascending order of the signed hash,
as np.unique does: `_run_cascade` sends the write-back's lanes in the
order of the groups, and a lane's place in its round is part of "bit for
bit"); `cap_ok`; the nine gathered columns and the four sums.
"""
import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.core.types import Behavior
from gubernator_tpu.runtime import fastpath
from gubernator_tpu.runtime.backend import RESP_FIELDS, _packed_resp_dict

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)

RESET = int(Behavior.RESET_REMAINING)
SHIFT = 32                      # parallel/mesh.py _SHARD_SHIFT


class Drain:
    """One drain's eleven columns, made from a list of key numbers."""

    def __init__(self, keys, rng, n_shards=1):
        keys = np.asarray(keys, dtype=np.int64)
        n = self.n = len(keys)
        # A key's hash: its number spread over the shard bits, 0 kept 0.
        self.h = np.where(
            keys == 0, 0, keys * np.int64(0x9E3779B97F4A7C15 - (1 << 64))
        ).astype(np.int64)
        self.hits = rng.integers(0, 4, n).astype(np.int64)
        self.lim = np.full(n, 100, dtype=np.int64)
        self.dur = np.full(n, 60_000, dtype=np.int64)
        self.algo = (keys % 2).astype(np.int32)
        self.burst = np.zeros(n, dtype=np.int64)
        self.behavior = np.zeros(n, dtype=np.int64)
        self.is_greg = np.zeros(n, dtype=bool)
        self.ge = np.zeros(n, dtype=np.int64)
        self.gd = np.zeros(n, dtype=np.int64)
        self.use_cached = np.zeros(n, dtype=bool)

    def cols(self):
        return (self.h, self.hits, self.lim, self.dur, self.algo,
                self.burst, self.behavior, self.is_greg, self.ge, self.gd,
                self.use_cached)


def _uniques(rng, n, lo=1000):
    return rng.permutation(np.arange(lo, lo + n))


def _mixed_in(rng, dup_keys, n):
    """`dup_keys` (with repeats) shuffled among fresh keys, n in all."""
    keys = np.concatenate([np.asarray(dup_keys), _uniques(
        rng, n - len(dup_keys))])
    return rng.permutation(keys)


# name -> (builder(rng, B) -> Drain); B so that "more than a round" scales.
def _empty(rng, B):
    return Drain([], rng)


def _one(rng, B):
    return Drain([7], rng)


def _all_one_key(rng, B):
    return Drain([7] * 64, rng)


def _one_key_5000(rng, B):
    return Drain([7] * 5000, rng)


def _one_key_5000_negative(rng, B):
    # No group is eligible: one round a check, where B allows the memory.
    d = Drain([7] * (5000 if B == 128 else 300), rng)
    d.hits[3] = -1
    return d


def _errored(rng, B):
    keys = _mixed_in(rng, [5] * 4 + [6] * 2, 300)
    keys[rng.integers(0, 300, 40)] = 0
    return Drain(keys, rng)


def _all_errored(rng, B):
    return Drain([0] * 9, rng)


def _triple_small(rng, B):
    return Drain(_mixed_in(rng, [5] * 3, 64), rng)


def _pair_small(rng, B):
    return Drain(_mixed_in(rng, [5] * 2, 64), rng)


def _pair_over_a_round(rng, B):
    return Drain(_mixed_in(rng, [5] * 2, B + 900), rng)


def _triple_under_a_round(rng, B):
    return Drain(_mixed_in(rng, [5] * 3, B - 96), rng)


def _triple_over_a_round(rng, B):
    return Drain(_mixed_in(rng, [5] * 3, B + 900), rng)


def _overflow(rng, B):
    # More distinct keys than lanes: capacity sends checks to later rounds.
    return Drain(_mixed_in(rng, [5] * 3 + [6] * 2, 3 * B + 17), rng)


def _flags(rng, B):
    """Seven groups of three, each spoiled another way, and two clean."""
    d = Drain(_mixed_in(rng, [k for k in range(1, 10) for _ in range(3)],
                        200), rng)
    d.hits[:] = 1

    def at(k, j=1):
        return np.flatnonzero(d.h == Drain([k], rng).h[0])[j]

    d.hits[at(1)] = -1
    d.behavior[at(2)] = RESET
    d.is_greg[at(3)] = True
    d.ge[at(3)], d.gd[at(3)] = 1_790_000_000_000, 86_400_000
    d.use_cached[at(4)] = True
    d.lim[at(5)] = 101
    d.dur[at(6, 2)] = 1
    d.algo[at(7)] ^= 1
    d.burst[at(8)] = 100        # reads as the limit: the group stays clean
    d.behavior[at(9)] = 2       # another bit than RESET_REMAINING
    d.burst[at(9, 0)] = 7
    d.burst[at(9, 1)] = 7
    d.burst[at(9, 2)] = 7
    return d


def _burst_mixed(rng, B):
    d = Drain([5, 5, 5, 6, 6], rng)
    d.burst[:] = [0, 100, 99, 0, 100]
    return d


def _cached_groups(rng, B):
    # Groups that are all use_cached send no write-back: a pair cascades.
    d = Drain(_mixed_in(rng, [5] * 2 + [6] * 2, 64), rng)
    d.use_cached[:] = True
    return d


def _cached_and_not(rng, B):
    d = Drain(_mixed_in(rng, [5] * 2 + [6] * 2, 64), rng)
    d.use_cached[d.h == Drain([5], rng).h[0]] = True
    return d


def _zipf(rng, B):
    keys = np.minimum(rng.zipf(1.2, 2000), 500)
    d = Drain(keys, rng)
    d.hits[rng.integers(0, 2000, 3)] = -1
    d.behavior[rng.integers(0, 2000, 5)] = RESET
    d.use_cached[rng.integers(0, 2000, 30)] = True
    d.lim[rng.integers(0, 2000, 10)] = 50
    return d


def _zipf_clean(rng, B):
    # The zipf cells' drain: every repeated key is a group.
    return Drain(np.minimum(rng.zipf(1.2, 120), 500), rng)


def _token1k(rng, B):
    return Drain(rng.permutation(np.repeat(np.arange(1, 1001), 6)), rng)


def _batch_drain(rng, B):
    # The batch cell's: 4,100 checks, one key twice.
    return Drain(_mixed_in(rng, [5] * 2, 4100), rng)


CASES = {f.__name__[1:]: f for f in (
    _empty, _one, _all_one_key, _one_key_5000, _one_key_5000_negative,
    _errored, _all_errored, _triple_small, _pair_small, _pair_over_a_round,
    _triple_under_a_round, _triple_over_a_round, _overflow, _flags,
    _burst_mixed, _cached_groups, _cached_and_not, _zipf, _zipf_clean,
    _token1k,
    _batch_drain,
)}


def _tiers(B):
    return (128, B) if B > 128 else (128,)


def reference_pack(d, n_shards, B, tiers, mode, shift=SHIFT):
    """The numpy form `_process_packed` served until PR 42."""
    return fastpath._reference_pack(d.cols(), n_shards, B, tiers, mode, shift)


def native_pack(d, n_shards, B, tiers, mode, shift=SHIFT):
    return native.pack_rounds(
        *d.cols(), reset_bit=RESET, n_shards=n_shards, shard_shift=shift,
        batch_size=B, tiers=tiers, mode=mode, cap_ok=True,
    )


def _build(case, n_shards, B):
    rng = np.random.default_rng(
        [sorted(CASES).index(case), n_shards, B])
    return CASES[case](rng, B)


GRID = [
    (case, n_shards, B, mode)
    for case in CASES
    for n_shards in (1, 4)
    for B in (128, 4096)
    for mode in ((1,) if case not in ("zipf", "flags", "token1k")
                 else (0, 1, 2))
]


@pytest.mark.parametrize("case,n_shards,B,mode", GRID)
def test_the_native_pack_is_the_reference_bit_for_bit(
    case, n_shards, B, mode
):
    d = _build(case, n_shards, B)
    tiers = _tiers(B)
    want = reference_pack(d, n_shards, B, tiers, mode)
    got = native_pack(d, n_shards, B, tiers, mode)

    assert (got.rnd == want["rnd"]).all() and (got.lane == want["lane"]).all()
    assert len(got.rounds) == len(want["words"])
    for r, (a, b) in enumerate(zip(got.rounds, want["words"])):
        assert a.dtype == np.int64 and a.flags.c_contiguous
        assert a.shape == b.shape == (
            (12, n_shards, got.tiers[r]) if n_shards > 1
            else (12, got.tiers[r]))
        assert (a == b).all(), f"round {r}"
        assert got.lanes[r] == int(np.asarray(want["rounds"][r].active).sum())
    _, _, cap_ok = fastpath._reference_unpack(want, d.h, [], n_shards)
    assert (got.cap_ok == cap_ok).all()
    assert got.valid == int((d.h != 0).sum())
    assert got.cascades is want["cascades"]

    plan = want["groups"]
    if plan is None:
        assert got.groups == 0 and got.firsts is None and got.occ is None
        return
    assert got.groups == len(plan.firsts)
    assert got.occ_total == int(plan.occ.sum())
    assert got.peeks == int((d.hits[plan.occ] == 0).sum())
    assert (got.occ == plan.occ).all()
    # The same groups in the same order, each with the same occurrences.
    assert (got.firsts == plan.firsts).all()
    assert (got.order == plan.order).all()
    assert (got.bounds == plan.bounds).all()
    assert {
        frozenset(got.order[lo:hi].tolist())
        for lo, hi in zip(got.bounds[:-1], got.bounds[1:])
    } == {
        frozenset(np.flatnonzero(plan.inv == g).tolist())
        for g in plan.groups
    }


def _responses(rng, got, n_shards):
    """One random response buffer a round, in the fetched layout."""
    out = []
    for t in got.tiers:
        a = rng.integers(-5, 6, (n_shards, 9, t) if n_shards > 1 else (9, t))
        a[..., 0, :] = rng.integers(0, 2, a[..., 0, :].shape)   # status
        a[..., 4, :] = rng.integers(0, 2, a[..., 4, :].shape)   # persisted
        a[..., 5, :] = rng.integers(0, 2, a[..., 5, :].shape)   # found
        out.append(a.astype(np.int64))
    return out


@pytest.mark.parametrize("case,n_shards,B,mode", GRID)
def test_the_native_gather_is_the_reference_bit_for_bit(
    case, n_shards, B, mode
):
    d = _build(case, n_shards, B)
    tiers = _tiers(B)
    want = reference_pack(d, n_shards, B, tiers, mode)
    packed = native_pack(d, n_shards, B, tiers, mode)
    rng = np.random.default_rng(len(case) + n_shards + B + mode)
    resps = _responses(rng, packed, n_shards)
    host = [_packed_resp_dict(a) for a in resps]

    cols, sums, _ = fastpath._reference_unpack(want, d.h, host, n_shards)
    got = native.gather_rounds(
        packed, d.h, [fastpath._resp_words(hr) for hr in host])
    assert got.cols.shape == (9, d.n)
    for row, f in enumerate(RESP_FIELDS):
        assert (got.cols[row] == cols[f]).all(), f
    assert got.over_limit == sums["over_limit"]
    assert got.not_persisted == sums["not_persisted"]
    assert got.cache_hits == sums["cache_hits"]
    assert got.lanes == sums["lanes"]
    assert got.lanes - got.cache_hits == sums["new_windows"]
    # The engine lane's four columns are the first four.
    four = native.gather_rounds(packed, d.h, resps, n_cols=4)
    assert (four.cols == got.cols[:4]).all()
    assert (four.over_limit, four.lanes) == (got.over_limit, got.lanes)


@pytest.mark.parametrize("n_shards", [1, 4])
def test_a_plain_dict_of_columns_is_gathered_as_its_words_would_be(n_shards):
    """`bench/serve.py --control alter` hands the lane a plain dict with
    one column copied and changed, in place of `_packed_resp_dict`'s: the
    gather reads what the dict holds, not the buffer it came from."""
    d = _build("errored", n_shards, 128)
    packed = native_pack(d, n_shards, 128, (128,), 1)
    rng = np.random.default_rng(3)
    resps = _responses(rng, packed, n_shards)
    host = []
    for a in resps:
        hr = dict(_packed_resp_dict(a))
        rem = hr["remaining"].copy()
        rem[..., ::7] += 1
        hr["remaining"] = rem
        host.append(hr)
    plain = native.gather_rounds(packed, d.h, resps)
    got = native.gather_rounds(
        packed, d.h, [fastpath._resp_words(hr) for hr in host])
    on_7th = (packed.lane % 7 == 0) & (packed.rnd >= 0)
    assert on_7th.any()
    assert (got.cols[2] == plain.cols[2] + on_7th).all()
    for row in (0, 1, 3, 4, 5, 6, 7, 8):
        assert (got.cols[row] == plain.cols[row]).all()


@pytest.mark.parametrize("shift", [32, 44])
def test_the_shard_of_a_hash_is_the_lanes_own_arithmetic(shift):
    """44: the engine lane's arrival shard (global_sync._ARRIVAL_SHIFT)."""
    rng = np.random.default_rng(shift)
    d = Drain(_uniques(rng, 500), rng)
    d.h = rng.integers(-(1 << 63), (1 << 63) - 1, 500, dtype=np.int64)
    d.h[d.h == 0] = 1
    want = reference_pack(d, 4, 128, (128,), 0, shift)
    got = native_pack(d, 4, 128, (128,), 0, shift)
    assert (got.rnd == want["rnd"]).all() and (got.lane == want["lane"]).all()
    for a, b in zip(got.rounds, want["words"]):
        assert (a == b).all()


@pytest.mark.parametrize("tiers", [(128, 1024, 4096), (4096,), (64, 4096)])
def test_a_round_is_cut_to_the_smallest_tier_that_holds_it(tiers):
    rng = np.random.default_rng(5)
    d = Drain(_mixed_in(rng, [5] * 2, 700), rng)
    want = reference_pack(d, 1, 4096, tiers, 1)
    got = native_pack(d, 1, 4096, tiers, 1)
    assert got.tiers == [a.shape[-1] for a in want["words"]]
    for a, b in zip(got.rounds, want["words"]):
        assert (a == b).all()


def test_columns_that_are_left_out_read_as_zero():
    """The write-back's rounds: six columns, no flag set."""
    rng = np.random.default_rng(8)
    d = Drain([5, 6, 5, 7], rng)
    d.burst[:] = 9
    want = reference_pack(d, 1, 128, (128,), 0)
    got = native.pack_rounds(
        d.h, d.hits, d.lim, d.dur, d.algo, d.burst, None, None, None, None,
        None, reset_bit=0, n_shards=1, shard_shift=SHIFT, batch_size=128,
        tiers=(128,), mode=0,
    )
    assert got.cap_ok is None and got.occ is None
    for a, b in zip(got.rounds, want["words"]):
        assert (a == b).all()


def test_a_column_of_another_dtype_or_stride_is_taken_by_value():
    rng = np.random.default_rng(9)
    d = Drain(_mixed_in(rng, [5] * 3, 40), rng)
    want = native_pack(d, 1, 128, (128,), 1)
    wide = np.zeros((d.n, 2), dtype=np.int64)
    wide[:, 0] = d.hits
    d.hits = wide[:, 0]                     # strided
    d.algo = d.algo.astype(np.int64)        # another width
    d.use_cached = d.use_cached.astype(np.int8)
    got = native_pack(d, 1, 128, (128,), 1)
    for a, b in zip(got.rounds, want.rounds):
        assert (a == b).all()
    with pytest.raises(ValueError):
        native.pack_rounds(
            d.h, d.hits[:-1], *d.cols()[2:], reset_bit=RESET, n_shards=1,
            shard_shift=SHIFT, batch_size=128, tiers=(128,), mode=1)


def test_the_replay_of_the_native_plan_is_the_reference_plans():
    """`_run_cascade` over the native plan and over `_plan_cascade`'s: the
    same answers written, the same write-back lanes in the same order."""
    rng = np.random.default_rng(11)
    d = _zipf(rng, 4096)
    d.hits = np.abs(d.hits)
    d.behavior[:] = 0
    want = reference_pack(d, 1, 4096, (128, 4096), 2)
    got = native_pack(d, 1, 4096, (128, 4096), 2)
    assert got.cascades and got.groups > 3
    burst = np.where(d.burst == 0, d.lim, d.burst)
    outs = []
    for plan in (want["groups"], fastpath._CascadePlan(
            occ=got.occ, firsts=got.firsts, order=got.order,
            bounds=got.bounds)):
        st = np.random.default_rng(12)
        cols = [st.integers(0, 50, d.n).astype(np.int64) for _ in range(8)]
        cols[0] %= 2
        cols[5] %= 2                 # cachedv
        cols[6] %= 2                 # foundv
        wb = fastpath._run_cascade(
            plan, d.h, d.hits, d.lim, d.dur, d.algo, burst, *cols)
        outs.append((cols, wb))
    (a_cols, a_wb), (b_cols, b_wb) = outs
    for a, b in zip(a_cols, b_cols):
        assert (a == b).all()
    assert len(a_wb[0]) > 3
    for a, b in zip(a_wb, b_wb):
        assert a.dtype == b.dtype and (a == b).all()


# -- the served lane takes the native pass, and nothing else ---------------

MESH = dict(num_slots=4 * 8 * 64, ways=8, batch_size=64, num_shards=4)


@pytest.fixture(scope="module", params=["one_chip", "mesh"])
def served(request):
    """A daemon on each backend, its client, its compiled lane and a
    frozen clock the reference shares."""
    from gubernator_tpu.client import V1Client
    from gubernator_tpu.core import clock as clock_mod
    from gubernator_tpu.core.config import DeviceConfig
    from gubernator_tpu.testing import Cluster

    c = Cluster.start(
        1, device=DeviceConfig(**MESH) if request.param == "mesh" else None)
    clk = clock_mod.Clock()
    clk.freeze(1_790_000_000_000 * 1_000_000)
    c.daemons[0].service.backend.clock = clk
    cl = V1Client(c.addresses()[0])
    yield cl, clk, c.daemons[0].fastpath
    cl.close()
    c.stop()


def _reqs(tag, keys_hits, algo=0):
    from gubernator_tpu.core.types import RateLimitReq

    return [
        RateLimitReq(name="pack_native", unique_key=f"{tag}.{k}", hits=h,
                     limit=10, duration=60_000, algorithm=algo)
        for k, h in keys_hits
    ]


_RPCS = {
    # One key once; a pair (rides the rounds); three and a peek (cascades,
    # with a write-back); a drained bucket's flip (two write-back lanes of
    # one key, a round apart); an empty key among them (an errored lane).
    "singles": [(k, 1) for k in range(40)],
    "a-pair": [(0, 1), (1, 1), (0, 2), (2, 1)],
    "a-triple-and-a-peek": [(0, 1), (1, 1), (0, 0), (0, 3), (2, 1), (0, 1)],
    "a-flip": [(0, 9), (0, 1), (0, 1), (0, 1), (1, 1)],
    "leaky-triple": [(0, 1), (0, 2), (1, 1), (0, 400)],
}


@pytest.mark.parametrize("case", sorted(_RPCS))
def test_the_served_lane_packs_natively_and_answers_as_the_reference(
    served, case, monkeypatch
):
    """No served drain calls the numpy reference (`_plan_cascade`,
    `_read_lanes`, `_cascade_or_rounds`, `_build_rounds`) nor
    `native.assign_rounds`; its answers are core/pymodel.py's."""
    from gubernator_tpu.core.pymodel import PyRateLimiter

    cl, clk, fp = served

    def refuse(name):
        def f(*a, **kw):
            raise AssertionError(f"the served lane called {name}")
        return f

    for name in ("_plan_cascade", "_read_lanes", "_cascade_or_rounds",
                 "_build_rounds"):
        monkeypatch.setattr(fastpath, name, refuse(name))
    monkeypatch.setattr(native, "assign_rounds", refuse("assign_rounds"))
    calls = []
    pack = native.pack_rounds
    monkeypatch.setattr(
        native, "pack_rounds",
        lambda *a, **kw: calls.append(kw["mode"]) or pack(*a, **kw))

    algo = 1 if case.startswith("leaky") else 0
    oracle = PyRateLimiter(clock=clk)
    before, fallbacks = fp.served, fp.fallbacks
    for _ in range(2):          # the second finds the rows the first left
        reqs = _reqs(case, _RPCS[case], algo)
        got = cl.get_rate_limits(reqs)
        want = [oracle.get_rate_limit(r) for r in reqs]
        assert [(r.error, int(r.status), r.limit, r.remaining, r.reset_time)
                for r in got] == [
            ("", int(w.status), w.limit, w.remaining, w.reset_time)
            for w in want]
    assert fp.served == before + 2 * len(_RPCS[case])
    assert fp.fallbacks == fallbacks
    # One pack a drain (mode 1), and one more (mode 0) where a cascade
    # wrote back.
    assert calls.count(1) == 2 and set(calls) <= {0, 1}
    if case in ("a-triple-and-a-peek", "a-flip", "leaky-triple"):
        assert calls.count(0) >= 1
    else:
        assert calls == [1, 1]


def test_an_answer_altered_where_the_response_is_unpacked_is_served_altered(
    served, monkeypatch
):
    """`bench/serve.py --control alter` patches `backend._packed_resp_dict`
    to show that the benchmark refuses a wrong answer: the compiled lane
    has to serve what that function returns, not the buffer behind it."""
    import gubernator_tpu.runtime.backend as backend

    cl, clk, fp = served
    reqs = _reqs("altered", [(k, 1) for k in range(8)])
    plain = cl.get_rate_limits(reqs)
    assert [r.remaining for r in plain] == [9] * 8
    unpack = backend._packed_resp_dict

    def altered(a):
        out = dict(unpack(a))
        out["remaining"] = out["remaining"] + 100
        return out

    monkeypatch.setattr(backend, "_packed_resp_dict", altered)
    got = cl.get_rate_limits(reqs)
    assert [r.remaining for r in got] == [108] * 8
