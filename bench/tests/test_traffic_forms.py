"""What the generator learnt in PR 27 — zipfian keys, a hits mix, square-wave
arrivals — as data: the plans of the mixes it had are unchanged, each new
form gives every seed the same multiset in another order, malformed files
are refused with the known forms named, the fetch-program bound holds
against the program's own round assignment, the reference comparison
follows a peek, and a later cell is a files-only addition."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from lib import oracle, schedule, shapes, spec
from lib import universe as U

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PARENT = spec.load_json(os.path.join(DATA, "parent_plan_digests.json"))
BM = spec.benchmark()
UNI = {"keys": 100000, "ways": 8, "shards": 1, "global_keys": 0,
       "limit": 1000, "duration_ms": 2592000000,
       "preload_remaining_below": 32}
ZIPF_CLOSED = spec.load_json(spec.traffic_path("zipf99.rpc16.closed"))
# The same keys and hits on rpc2.open's two-check RPC.
ZIPF = {"name": "zipf99.rpc2.open", "loop": "open", "connections": 4,
        "outstanding_cap": 64, "checks_per_rpc": {"min": 2, "max": 2},
        "hits": ZIPF_CLOSED["hits"], "keys": ZIPF_CLOSED["keys"],
        "arrivals": {"process": "poisson", "rate_rpc_per_s": 200},
        "deadline_s": 5.0, "warm_in_s": 8.0}
BURST = spec.load_json(spec.traffic_path("rpc2.burst"))


@pytest.mark.parametrize("key", sorted(PARENT["digests"]))
def test_plans_of_the_mixes_pr24_brought_are_byte_identical(key):
    config, traffic, seed = key.split("/")
    cfg = spec.load_json(spec.config_path(BM, config))
    t = spec.load_json(spec.traffic_path(traffic))
    plan = schedule.build_plan(
        t, cfg["universe"], int(seed), t["warm_in_s"] + 10.0)
    assert plan.digest() == PARENT["digests"][key]
    assert (plan.hits == 1).all()


def test_zipfian_frequencies_follow_the_distribution_at_10m_keys():
    n, theta, total = 10_000_000, 0.99, 4_000_000
    ranks = schedule.zipfian_ranks(total, n, theta)
    zetan = schedule.zipfian_weights(n, theta)[-1]
    assert 1 / zetan == pytest.approx(0.05535, rel=1e-3)   # the hottest key
    freq = np.bincount(ranks[ranks < 100], minlength=100) / total
    want = 1 / (np.arange(1, 101) ** theta * zetan)
    np.testing.assert_allclose(freq, want, rtol=0.01)
    assert (np.diff(ranks) >= 0).all() and ranks[-1] < n


def test_zipfian_keys_are_one_multiset_for_every_seed():
    uni = dict(UNI, keys=10_000_000)
    t = dict(ZIPF, arrivals={"process": "poisson", "rate_rpc_per_s": 300})
    a = schedule.build_plan(t, uni, 1, 12.0)
    b = schedule.build_plan(t, uni, 2**31 + 11, 12.0)
    # A span that cuts a block of 1,000 gaps holds an arrival more or
    # fewer by seed (PR 24's arrivals): the same count, the same keys.
    assert abs(len(a) - len(b)) <= 5
    np.testing.assert_array_equal(
        np.sort(schedule.key_positions(ZIPF, uni, 1, 8000)),
        np.sort(schedule.key_positions(ZIPF, uni, 2**31 + 11, 8000)))
    b = schedule.Plan(a.offsets, schedule.key_positions(
        ZIPF, uni, 2**31 + 11, len(a.key_index)), a.times_s, a.hits)
    assert a.digest() != b.digest()
    np.testing.assert_array_equal(np.sort(a.key_index), np.sort(b.key_index))
    assert (a.key_index != b.key_index).any()
    keys, counts = np.unique(a.key_index, return_counts=True)
    assert counts.max() / len(a.key_index) == pytest.approx(0.0554, abs=2e-3)
    assert (counts / len(a.key_index)) @ (counts / len(a.key_index)) \
        == pytest.approx(0.005, abs=1e-3)
    # Scrambled: the hottest ranks are FNV-1a 64 of the rank, modulo the
    # key count — spread over the universe, not its first positions.
    hot = schedule.hottest_keys(a, 3)
    want = schedule.fnv1a64(np.arange(3)) % np.uint64(10_000_000)
    assert sorted(hot.tolist()) == sorted(want.astype(np.int64).tolist())
    assert hot.min() > 1000
    # YCSB's fnvhash64 of 0 and 1, computed octet by octet.
    assert schedule.fnv1a64(np.array([0, 1])).tolist() == [
        abs(_fnv_signed(0)), abs(_fnv_signed(1))]


def _fnv_signed(v: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (v & 0xFF)) * 1099511628211) & (2**64 - 1)
        v >>= 8
    return h - 2**64 if h >= 2**63 else h


def test_an_unscrambled_zipfian_keeps_rank_order():
    t = dict(ZIPF, keys={"distribution": "zipfian", "constant": 0.5})
    p = schedule.build_plan(t, UNI, 3, 10.0)
    assert schedule.hottest_keys(p, 1)[0] == 0


def test_hits_mix_is_exact_and_one_multiset_for_every_seed():
    a = schedule.hits_column(ZIPF, 1, 10_000)
    b = schedule.hits_column(ZIPF, 99, 10_000)
    assert sorted(a) == sorted(b) and (a != b).any()
    assert int((a == 0).sum()) == int((a == 1).sum()) == 5_000
    for blk in range(10):                      # whole permuted blocks
        assert a[blk * 1000:(blk + 1) * 1000].sum() == 500
    t = {"hits": {"values": [0, 1, 5], "weights": [1, 2, 1]}}
    c = schedule.hits_column(t, 7, 2_000)
    assert np.bincount(c, minlength=6).tolist() == [500, 1000, 0, 0, 0, 500]
    assert (schedule.hits_column({"hits": 3}, 7, 10) == 3).all()
    assert (schedule.hits_column({}, 7, 10) == 1).all()
    # An RPC of the zipf cell is not one peek and one spend by construction.
    p = schedule.build_plan(ZIPF, UNI, 5, 10.0)
    per_rpc = p.hits.reshape(-1, 2).sum(axis=1)
    assert set(per_rpc.tolist()) == {0, 1, 2}


def test_square_wave_arrivals_are_exact_in_every_phase():
    arr = BURST["arrivals"]
    base, peak = arr["base_rate_rpc_per_s"], arr["burst_rate_rpc_per_s"]
    a = schedule.square_times(1, arr, 20.0)
    b = schedule.square_times(2**31 + 5, arr, 20.0)
    assert len(a) == len(b) == 4 * (4 * base + peak)
    for t in (a, b):
        assert (np.diff(t) > 0).all() and t[0] >= 0 and t[-1] < 20.0
        for period in range(4):
            p0 = 5.0 * period
            count = lambda lo, hi: int(((t >= p0 + lo) & (t < p0 + hi)).sum())  # noqa: E731,E501
            assert count(0, 1) == base and count(1, 2) == peak
            assert count(2, 5) == 3 * base
    # The same gaps inside a phase for every seed, in another order.
    # (The phase's last gap runs to its end: 1 s less the others.)
    def in_burst(t):
        g = np.diff(t[(t >= 1.0) & (t < 2.0)])
        return np.r_[g, 1.0 - g.sum()]

    np.testing.assert_allclose(np.sort(in_burst(a)), np.sort(in_burst(b)))
    np.testing.assert_allclose(
        np.sort(in_burst(a)), schedule._exp_gaps(peak, 1.0 / peak))
    assert not np.allclose(in_burst(a), in_burst(b))
    g = in_burst(a)
    assert 0.9 < g.std() / g.mean() < 1.05     # exponential in shape
    plan = schedule.build_plan(BURST, UNI, 3, 20.0)
    assert len(plan) == len(a) and (plan.hits == 1).all()


@pytest.mark.parametrize("edit, named", [
    ({"keys": {"distribution": "hotspot"}}, "zipfian"),
    ({"keys": {"distribution": "zipfian", "constant": 1.0}}, "0 < c < 1"),
    ({"keys": {"distribution": "zipfian", "constant": 0.9,
               "scramble": "yes"}}, "scramble"),
    ({"keys": {"distribution": "uniform", "constant": 0.9}}, "uniform"),
    ({"hits": -1}, "whole number"),
    ({"hits": 1.5}, "whole number"),
    ({"hits": {"values": [0, 1]}}, "weights"),
    ({"hits": {"values": [0, 1], "weights": [1, 0]}}, "weights"),
    ({"hits": {"values": [1, 1], "weights": [1, 1]}}, "values"),
    ({"arrivals": {"process": "pareto", "rate_rpc_per_s": 5}}, "square"),
    ({"arrivals": {"process": "poisson", "rate_rpc_per_s": 0}}, "poisson"),
    ({"arrivals": {"process": "square", "period_s": 5, "burst_s": 1,
                   "base_rate_rpc_per_s": 10}}, "burst_rate_rpc_per_s"),
    ({"arrivals": {"process": "square", "period_s": 5, "burst_s": 2,
                   "burst_start_s": 4, "base_rate_rpc_per_s": 10,
                   "burst_rate_rpc_per_s": 20}}, "inside the period"),
    ({"arrivals": dict(BURST["arrivals"]), "warm_in_s": 8.0},
     "whole number of periods"),
])
def test_malformed_forms_are_refused_with_the_known_forms_named(edit, named):
    t = dict(BURST, **edit)
    with pytest.raises(spec.SpecError) as e:
        spec.check_traffic(t, "edited")
    assert named in str(e.value)


def test_the_three_new_forms_are_accepted():
    spec.check_traffic(ZIPF, "zipf")
    spec.check_traffic(ZIPF_CLOSED, "zipf closed")
    spec.check_traffic(BURST, "burst")
    assert spec.can_peek(ZIPF) and not spec.can_peek(BURST)
    assert spec.can_peek(ZIPF_CLOSED)
    assert spec.can_peek({"hits": 0}) and not spec.can_peek({})


def _drain_tiers(h, hits, batch, tiers):
    """Round tiers of one drain as the program packs it: cascade groups
    keep one read lane (fastpath._plan_cascade), every other occurrence a
    lane of its own (native.assign_rounds)."""
    from gubernator_tpu import native
    from gubernator_tpu.runtime import fastpath

    n = len(h)
    z = np.zeros(n, dtype=bool)
    lim = np.full(n, 1000, dtype=np.int64)
    plan = fastpath._plan_cascade(
        h, hits, z, z, lim, lim, np.zeros(n, dtype=np.int32), lim, z)
    h_mach = h.copy()
    if plan is not None:
        h_mach[plan.occ] = 0
        h_mach[plan.firsts] = h[plan.firsts]
    rnd, _lane, n_rounds = native.assign_rounds(h_mach, None, 1, batch)
    lanes = np.bincount(rnd[rnd >= 0], minlength=n_rounds)
    return lanes, tuple(next(t for t in tiers if c <= t) for c in lanes)


def test_the_zipf_cells_drain_is_128_checks_and_127_fetch_programs():
    bound = shapes.round_lane_bounds(ZIPF_CLOSED, UNI, 4096, 128)["mach"]
    assert bound == [128 // (r + 1) for r in range(128)]
    seqs = shapes.tier_sequences(bound, [128, 4096])
    assert sorted(seqs) == [(128,) * n for n in range(2, 129)]


@pytest.mark.parametrize("cap", [4, 32, 128])
def test_fetch_program_bound_holds_against_the_programs_round_assignment(cap):
    tiers, batch = [128, 4096], 4096
    t = dict(ZIPF, outstanding_cap=cap)
    bound = shapes.round_lane_bounds(t, UNI, batch, 128)["mach"]
    total = 2 * cap
    assert bound == [min(batch, total // (r + 1)) for r in range(total)]
    warmed = set(shapes.tier_sequences(bound, tiers))
    rng = np.random.default_rng(cap)
    drains = [
        # One key cap x 2 times, half of them peeks: a round an occurrence.
        (np.full(total, 77, dtype=np.int64), np.arange(total) % 2),
        # Two keys half each, one of them all spends (cascaded).
        (np.repeat([5, 9], cap).astype(np.int64),
         np.r_[np.ones(cap), np.arange(cap) % 2].astype(np.int64)),
        # All distinct.
        (np.arange(1, total + 1, dtype=np.int64), np.zeros(total, np.int64)),
    ] + [
        (rng.integers(1, 1 + rng.integers(1, total + 1), size=n),
         rng.integers(0, 2, size=n))
        for n in rng.integers(2, total + 1, size=200)
    ]
    most_rounds = 0
    for h, hits in drains:
        lanes, seq = _drain_tiers(
            h.astype(np.int64), hits.astype(np.int64), batch, tiers)
        assert (lanes <= np.array(bound[:len(lanes)])).all()
        assert len(seq) < 2 or seq in warmed, seq
        most_rounds = max(most_rounds, len(seq))
    assert most_rounds == total              # the adversarial drain
    # Where no check can be a peek the cascade serves every duplicate
    # group from one lane: PR 24's bound, unchanged.
    assert shapes.round_lane_bounds(
        dict(BURST, outstanding_cap=256), UNI, batch, 128
    ) == {"mach": [512, 128]}
    lanes, seq = _drain_tiers(
        np.full(total, 77, dtype=np.int64), np.ones(total, np.int64),
        batch, tiers)
    assert seq == (128,)


class _Sim:
    """A server that IS the reference: a universe's keys preloaded as the
    harness preloads them, answers from core/pymodel.py on a frozen clock."""

    T0 = 1_700_000_000_000

    def __init__(self, uni):
        from gubernator_tpu.core import clock as clock_mod
        from gubernator_tpu.core.pymodel import PyRateLimiter
        from gubernator_tpu.core.types import (
            Algorithm, CacheItem, RateLimitReq, Status,
        )

        self.uni, self.Req, self.Algorithm = uni, RateLimitReq, Algorithm
        clk = clock_mod.Clock()
        clk.freeze(self.T0 * 1_000_000)
        self.model = PyRateLimiter(clock=clk)
        for k in np.flatnonzero(uni.resident):
            leaky = int(uni.algo[k]) == U.ALGO_LEAKY
            key = U.key_string(int(uni.ids[k]))
            self.model.cache[key] = CacheItem(
                key=key, algorithm=self._algo(leaky),
                expire_at=self.T0 + uni.duration_ms, limit=uni.limit,
                duration=uni.duration_ms,
                remaining=(float(uni.remaining0[k]) if leaky
                           else int(uni.remaining0[k])),
                created_at=self.T0, status=Status.UNDER_LIMIT,
                burst=uni.limit,
            )

    def _algo(self, leaky):
        return (self.Algorithm.LEAKY_BUCKET if leaky
                else self.Algorithm.TOKEN_BUCKET)

    def check(self, k: int, hits: int):
        key = U.key_string(int(self.uni.ids[k]))
        r = self.model.get_rate_limit(self.Req(
            name=key[:9], unique_key=key[10:], hits=hits,
            limit=self.uni.limit, duration=self.uni.duration_ms,
            algorithm=self._algo(int(self.uni.algo[k]) == U.ALGO_LEAKY),
        ))
        return int(r.status), r.limit, r.remaining, r.reset_time


def _served(seed: int, in_flight: int):
    """A zipfian, half-peek plan on a 300-key universe whose buckets hold
    0..3 tokens, answered by the reference with `in_flight` RPCs sent
    together and applied in a shuffled order."""
    from gubernator_tpu import native

    cfg = dict(UNI, keys=300, preload_remaining_below=4)
    uni = U.build_universe(native, cfg, seed, 1 << 16)
    assert not uni.crowded.any()
    plan = schedule.build_plan(
        dict(ZIPF, arrivals={"process": "poisson", "rate_rpc_per_s": 200}),
        cfg, seed, 10.0)
    sim = _Sim(uni)
    n = len(plan)
    rng = np.random.default_rng(seed)
    t_send, t_done = np.zeros(n), np.zeros(n)
    cols = {f: np.zeros(2 * n, dtype=np.int64)
            for f in ("status", "limit", "remaining", "reset_time")}
    for g0 in range(0, n, in_flight):
        group = np.arange(g0, min(n, g0 + in_flight))
        t_send[group] = g0 + np.arange(len(group)) * 1e-3
        t_done[group] = g0 + 0.5
        for j in rng.permutation(group):      # any order inside a group
            for c in range(2 * j, 2 * j + 2):
                ans = sim.check(int(plan.key_index[c]), int(plan.hits[c]))
                for f, x in zip(cols, ans):
                    cols[f][c] = x
    wall = np.full(n, _Sim.T0, dtype=np.int64)
    rec = dict(
        cols, code=np.zeros(n, dtype=np.int64), plan_idx=np.arange(n),
        ans_off=np.arange(n + 1) * 2, t_send=t_send, t_done=t_done,
        wall_send=wall, wall_recv=wall,
        err_len=np.zeros(2 * n, dtype=np.int64),
    )
    return plan, rec, uni


def _judge(plan, rec, uni, seed):
    a = oracle.flatten(plan, rec)
    v = oracle.Verdict()
    oracle.screen(a, uni, v)
    oracle.replay_sample(a, rec, uni, _Sim.T0, seed,
                         np.zeros(0, np.int64), np.zeros(0, np.int64), v,
                         always=schedule.hottest_keys(plan, 10))
    return a, v


@pytest.mark.parametrize("in_flight", [1, 7])
def test_reference_comparison_follows_peeks_under_and_over_the_limit(
        in_flight):
    plan, rec, uni = _served(11, in_flight)
    a, v = _judge(plan, rec, uni, 11)
    assert v.notes["sampled_answers"] == len(a.key) == 2 * len(plan)
    assert all(n == 0 for n in v.counts.values()), (v.counts, v.first)
    # The traffic held what it is there for: peeks and spends, under and
    # over the limit, token and leaky, duplicates of a key in one group.
    peek, over = a.hits == 0, a.status == 1
    leaky = uni.algo[a.key] == U.ALGO_LEAKY
    for algo in (leaky, ~leaky):
        assert (peek & over & algo).any() == (not algo is leaky)
        assert (peek & ~over & algo).any() and (~peek & over & algo).any()
        assert (~peek & ~over & algo).any()
    # A peek changes nothing: it shows what the last answer left.
    k = int(schedule.hottest_keys(plan, 1)[0])
    rows = np.flatnonzero(a.key == k)
    if in_flight == 1:
        for prev, cur in zip(rows[:-1], rows[1:]):
            if a.hits[cur] == 0:
                assert a.remaining[cur] == a.remaining[prev]
    # One altered peek, one altered spend: each is a wrong answer.
    for want_hits in (0, 1):
        bad = {f: x.copy() if isinstance(x, np.ndarray) else x
               for f, x in rec.items()}
        c = int(np.flatnonzero((plan.key_index == k)
                               & (plan.hits == want_hits))[3])
        bad["remaining"][c] += 1
        _, vb = _judge(plan, bad, uni, 11)
        assert vb.counts["wrong_answers"] >= 1


def test_a_peek_answered_out_of_its_rpcs_order_is_caught():
    plan, rec, uni = _served(5, 1)
    # Find an RPC whose two checks hit one key, spend then peek, under the
    # limit; swapping the answers is an order no reference produces.
    a = oracle.flatten(plan, rec)
    pair = a.key.reshape(-1, 2)
    hp = a.hits.reshape(-1, 2)
    rem = a.remaining.reshape(-1, 2)
    st = a.status.reshape(-1, 2)
    js = np.flatnonzero((pair[:, 0] == pair[:, 1]) & (hp[:, 0] == 0)
                        & (hp[:, 1] == 1) & (st[:, 1] == 0)
                        & (rem[:, 0] != rem[:, 1]))
    assert len(js), "no such RPC in this plan: pick another seed"
    j = int(js[0])
    for f in ("status", "remaining", "reset_time"):
        rec[f][2 * j], rec[f][2 * j + 1] = rec[f][2 * j + 1], rec[f][2 * j]
    _, v = _judge(plan, rec, uni, 5)
    assert v.counts["wrong_answers"] >= 1


def test_a_later_cell_is_new_files_and_appended_names_only(tmp_path):
    """A made-up configuration, traffic mix, per-layer metric and cell in
    a copy of the tree: no file under bench/ that was there is touched;
    BENCHMARK.json gets new entries and the cell's name appended to the
    `workloads` of each entry it reports, nothing else."""
    root = tmp_path / "tree"
    shutil.copytree(spec.BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache"))
    shutil.copy(os.path.join(spec.REPO, "BENCHMARK.json"), root)

    def digest_all():
        out = {}
        for d, _dirs, files in os.walk(root / "bench"):
            for f in files:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = hash(fh.read())
        return out

    before = digest_all()
    cfg = spec.load_json(spec.config_path(BM, "exact10m-1chip"))
    cfg["name"] = "madeup1m-1chip"
    cfg["source"] = "a made-up deployment for bench/tests"
    cfg["universe"]["keys"] = 1_000_000
    (root / "bench/configs/madeup1m-1chip.json").write_text(json.dumps(cfg))
    t = dict(ZIPF, name="zipf80.rpc2.open", keys={
        "distribution": "zipfian", "constant": 0.8, "scramble": True})
    (root / "bench/traffic/zipf80.rpc2.open.json").write_text(json.dumps(t))
    m = spec.load_json(spec.layer_metric_path("lane_cascade_ms.open"))
    m["name"] = "lane_pack_ms.open"
    m["read"]["num"] = ["vars:stages.*.pack.ms_total"]
    (root / "bench/layer_metrics/lane_pack_ms.open.json").write_text(
        json.dumps(m))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cell = "madeup1m.zipf80.rpc2.open"
    bm["configs"].append({"name": cfg["name"], "source": cfg["source"],
                          "file": "bench/configs/madeup1m-1chip.json",
                          "reduced": [], "why": "made up"})
    bm["workloads"].append({"name": cell, "config": cfg["name"],
                            "traffic": t["name"], "chips": 1,
                            "why": "made up"})
    for e in bm["end_to_end"] + bm["per_layer"]:
        if e["name"] in ("rpc_p50_ms", "rpc_p95_ms") or (
                e["name"].endswith(".open")):
            e["workloads"].append(cell)
    bm["per_layer"].append({
        "name": m["name"], "unit": m["unit"], "better": m["better"],
        "source": m["source"], "layer": m["layer"], "moves": m["moves"],
        "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    check = (
        "import sys; sys.path[:0] = ['bench', '.']\n"
        "from lib import spec\n"
        "bm = spec.benchmark(); spec.check_benchmark(bm)\n"
        f"cell = {cell!r}\n"
        "print(sorted(m['name'] for g in ('end_to_end', 'per_layer') "
        "for m in spec.metrics_of(bm, g, cell)))\n"
    )
    p = subprocess.run([sys.executable, "-c", check], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    reported = p.stdout.strip().splitlines()[-1]
    for name in ("rpc_p50_ms", "setup_s", "lane_pack_ms.open",
                 "lane_rounds_per_drain.open", "wire_rpc_ms.open"):
        assert repr(name) in reported
    after = digest_all()
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 3
