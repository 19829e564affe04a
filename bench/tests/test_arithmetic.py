"""Percentile, schedule, shape and placement arithmetic."""
import numpy as np
import pytest

from lib import schedule, shapes
from lib import universe as U
from lib.percentile import beyond, percentile

UNI = {"keys": 100000, "ways": 8, "shards": 1, "global_keys": 0,
       "limit": 1000, "duration_ms": 2592000000,
       "preload_remaining_below": 32}
OPEN = {"loop": "open", "checks_per_rpc": {"min": 2, "max": 2},
        "keys": {"distribution": "uniform"}, "outstanding_cap": 256,
        "arrivals": {"process": "poisson", "rate_rpc_per_s": 350}}
CLOSED = {"loop": "closed", "checks_per_rpc": {"min": 500, "max": 1000},
          "keys": {"distribution": "uniform"}, "in_flight": 16,
          "pool_rpc_per_s": 50, "global_per_rpc": 8}


@pytest.mark.parametrize("q", [0.5, 0.9, 0.95, 0.99, 0.999, 1.0])
@pytest.mark.parametrize("n", [1, 2, 10, 101, 5000])
def test_percentile_is_the_nearest_rank_order_statistic(q, n):
    x = np.random.default_rng(n).exponential(size=n)
    want = np.sort(x)[max(1, int(np.ceil(q * n - 1e-9))) - 1]
    assert percentile(x, q) == want
    # numpy's own nearest rank, which for q * n a hair above a whole number
    # (0.999 * 5000 in floating point) takes the next sample up.
    ref = np.percentile(x, q * 100, method="inverted_cdf")
    assert percentile(x, q) in (ref, np.sort(x)[np.searchsorted(
        np.sort(x), ref) - 1])
    assert beyond(x, q) == int((x > want).sum())


def test_percentile_of_nothing_is_nan():
    assert np.isnan(percentile(np.zeros(0), 0.5))


def test_plan_digest_is_pinned_and_seed_sensitive():
    a = schedule.build_plan(OPEN, UNI, 12345, 28.0)
    b = schedule.build_plan(OPEN, UNI, 12345, 28.0)
    c = schedule.build_plan(OPEN, UNI, 12346, 28.0)
    assert a.digest() == b.digest() != c.digest()
    assert a.digest() == PINNED_OPEN_DIGEST


PINNED_OPEN_DIGEST = "393de8e3e6522c630db43c123791d93cbc6abcb4db5f89d540a23d907e5c07f5"


def test_every_seed_gets_the_same_arrivals_in_another_order():
    t1 = schedule.poisson_times(1, 350.0, 40.0)
    t2 = schedule.poisson_times(2**31 + 7, 350.0, 40.0)
    assert len(t1) == len(t2) == 14000        # rate x duration, exactly
    g1, g2 = np.diff(np.r_[0, t1]), np.diff(np.r_[0, t2])
    blk = schedule.GAP_BLOCK
    np.testing.assert_allclose(np.sort(g1[:blk]), np.sort(g2[:blk]))
    assert not np.allclose(g1[:blk], g2[:blk])
    # Poisson in shape: mean gap 1/rate, coefficient of variation ~1.
    assert abs(g1.mean() * 350.0 - 1) < 1e-9
    assert 0.95 < g1.std() / g1.mean() < 1.02


def test_every_seed_gets_the_same_rpc_sizes_in_another_order():
    s1 = schedule.rpc_sizes(1, 500, 1000, 1002)
    s2 = schedule.rpc_sizes(99, 500, 1000, 1002)
    assert sorted(s1[:501]) == sorted(s2[:501]) == list(range(500, 1001))
    assert list(s1) != list(s2)


def test_global_checks_are_a_fixed_count_per_rpc():
    uni = dict(UNI, global_keys=1024, global_limit=10**9)
    p = schedule.build_plan(CLOSED, uni, 5, 4.0)
    for j in range(len(p)):
        k = p.key_index[p.offsets[j]:p.offsets[j + 1]]
        assert (k < 1024).sum() == 8 and (k[:8] < 1024).all()
    # Without GLOBAL keys in the configuration the knob does nothing.
    q = schedule.build_plan(CLOSED, UNI, 5, 4.0)
    assert (q.key_index >= 0).all() and len(q) == len(p)


def test_tier_sequences_cover_what_the_bound_allows_and_no_more():
    tiers = [128, 4096]
    assert shapes.tier_sequences([512, 128], tiers) == [
        (128, 128), (4096, 128)]
    seqs = shapes.tier_sequences([4096, 4096, 4096, 3584, 128], tiers)
    assert len(seqs) == 17 and len(set(seqs)) == 17
    assert all(list(s) == sorted(s, reverse=True) for s in seqs)
    assert (4096,) * 5 not in seqs          # the spare round holds <= 128
    assert (4096, 4096, 4096, 4096, 128) in seqs
    # Engine lane at 16 RPCs x 8 GLOBAL checks: never above the small tier.
    lanes = shapes.round_lane_bounds(
        CLOSED, {"global_keys": 1024}, 4096, 128)
    assert lanes["mach"] == [4096, 4096, 4096, 3584, 128]
    assert lanes["engine"][0] == 128 and len(lanes["engine"]) == 16
    eng = shapes.tier_sequences(lanes["engine"], tiers)
    assert eng == [(128,) * k for k in range(2, 17)]
    assert shapes.round_lane_bounds(OPEN, {}, 4096, 128) == {
        "mach": [512, 128]}


def test_placement_is_the_set_associative_arithmetic():
    from gubernator_tpu import native

    u = U.build_universe(native, UNI, 77, 1 << 17)
    assert len(set(u.ids.tolist())) == len(u.ids)
    assert u.fp[0] == native.hash_keys([U.key_string(int(u.ids[0]))])[0]
    nb = (1 << 17) // 8
    counts = np.bincount(u.gbucket, minlength=nb)
    assert u.n_resident == int(np.minimum(counts, 8).sum())
    assert (u.crowded == (counts[u.gbucket] > 8)).all()
    h = U.handoff(u, 77, 4096)
    assert (np.diff(h["slot"]) > 0).all()       # slot order, no collisions
    assert (h["probe_found"] == np.isin(h["probe_fp"], h["fp"])).all()
    arr = U.table_arrays(h, 1_700_000_000_000)
    assert int((arr["key"] != 0).sum()) == u.n_resident
    k = int(np.flatnonzero(u.resident)[0])
    slot = int(u.gbucket[k] * 8 + u.way[k])
    assert arr["key"][slot] == u.fp[k]
    assert arr["expire_at"][slot] == 1_700_000_000_000 + UNI["duration_ms"]
    # Touching a non-resident key of a full bucket changes nothing;
    # an outside key landing in a non-full bucket adds one row.
    out = np.flatnonzero(~u.resident)[:1]
    assert U.expected_occupancy(u, out, np.zeros(0, np.int64)) == u.n_resident
