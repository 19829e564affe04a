"""A table that does not hold its universe (`"residency": "tiered"`, PR 45):
the form and each refusal of `spec.check_config`; the preload of both tiers
(every key in exactly one, the table at or under its low-water mark, a row
still at ``bucket * ways + way``); `oracle.replay_tiered` on made-up answers
of a made-up daemon that IS the reference plus docs/tiering.md's moves —
continued, fresh, merged and promoted accepted, minted budget, a merge later
than the deadline and a dropped cold row refused; the counts `run.py` takes
from the daemon's ledger; and the rule of test_traffic_forms.py kept for the
universe: every accepted configuration's arrays are what the parent built,
to the byte."""
import copy
import hashlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from lib import oracle, spec
from lib import universe as U

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HELD = spec.benchmark(held_out=True)
CFG = spec.load_json(spec.config_path(HELD, "tier20m-1chip"))
T0 = 1_700_000_000_000
LIMIT, DUR = 1000, 2_592_000_000
RATE = DUR // LIMIT
SMALL = dict(CFG["universe"], keys=3000)
SLOTS = 2048                     # 256 buckets of 8: 11.7 arrivals a bucket


# -- the form -----------------------------------------------------------------

def test_the_built_configuration_is_of_the_form():
    spec.check_config(CFG, "tier20m-1chip")
    u, d = CFG["universe"], CFG["daemon"]
    assert spec.tiered(u) and u["keys"] == 20_000_000
    assert spec.tier_marks(CFG) == (0.85, 0.84)
    rows = spec.table_rows_at_start(CFG)
    assert rows == int(0.84 * 2 ** 24) == 14_092_861
    assert int(d["GUBER_TIER_COLD_CAPACITY"]) >= u["keys"] - rows
    # exact10m-1chip's geometry, but for the keys and the tier.
    base = spec.load_json(spec.config_path(HELD, "exact10m-1chip"))
    assert {k: v for k, v in d.items() if "TIER" not in k} == base["daemon"]
    assert {k: v for k, v in u.items() if k not in (
        "keys", "residency", "promote_deadline_ms")} == {
        k: v for k, v in base["universe"].items() if k != "keys"}
    assert CFG["reduced"] == ["keys_over_slots"]
    assert set(CFG["reduced"]) <= set(CFG["assumed"])


def test_no_accepted_configuration_says_the_form():
    for c in spec.benchmark()["configs"]:
        cfg = spec.load_json(os.path.join(spec.REPO, c["file"]))
        assert "residency" not in cfg["universe"]
        assert not spec.tiered(cfg["universe"])
        assert not any("TIER" in k for k in cfg["daemon"])


def _with(path, value):
    cfg = copy.deepcopy(CFG)
    group, key = path
    if value is None:
        del cfg[group][key]
    else:
        cfg[group][key] = value
    return cfg


@pytest.mark.parametrize("path, value", [
    (("universe", "residency"), "cold"),
    (("universe", "residency"), True),
    (("daemon", "GUBER_TIER_ENABLED"), None),
    (("daemon", "GUBER_TIER_ENABLED"), "false"),
    (("daemon", "GUBER_TIER_HIGH_WATER"), None),
    (("daemon", "GUBER_TIER_LOW_WATER"), None),
    (("daemon", "GUBER_TIER_LOW_WATER"), "0.85"),       # not under high
    (("daemon", "GUBER_TIER_HIGH_WATER"), "1.5"),
    (("daemon", "GUBER_TIER_COLD_CAPACITY"), None),
    (("daemon", "GUBER_TIER_COLD_CAPACITY"), "5907138"),    # one short
    (("daemon", "GUBER_TIER_INTERVAL"), None),
    (("daemon", "GUBER_TIER_INTERVAL"), "2"),   # background_timers_s says 1
    (("daemon", "GUBER_TIER_INTERVAL"), "1s"),  # plain seconds
    (("background_timers_s", "tier_tick"), None),
    (("universe", "promote_deadline_ms"), None),
    (("universe", "promote_deadline_ms"), 0),
    (("universe", "global_keys"), 8),
    (("universe", "clock"), "moving"),
])
def test_a_tiered_configuration_that_leaves_something_out_is_refused(
    path, value
):
    with pytest.raises(spec.SpecError) as e:
        spec.check_config(_with(path, value), "made-up")
    assert "residency is none of the known forms" in str(e.value)
    assert '"tiered"' in str(e.value) and '"table"' in str(e.value)


def test_a_tiered_mesh_or_cluster_is_refused_for_now():
    mesh = copy.deepcopy(CFG)
    mesh["chips"] = 4
    mesh["universe"]["shards"] = 4
    with pytest.raises(spec.SpecError, match="residency"):
        spec.check_config(mesh, "made-up")
    peers = spec.load_json(spec.config_path(HELD, "peers4-10m"))
    peers["universe"].update(residency="tiered", promote_deadline_ms=2000)
    peers["daemon"].update(
        {k: v for k, v in CFG["daemon"].items() if "TIER" in k})
    peers["background_timers_s"]["tier_tick"] = 1.0
    with pytest.raises(spec.SpecError, match="residency"):
        spec.check_config(peers, "made-up")


def test_the_exact_cold_capacity_is_accepted():
    spec.check_config(_with(("daemon", "GUBER_TIER_COLD_CAPACITY"),
                            "5907139"), "made-up")


# -- the preload of both tiers ------------------------------------------------

@pytest.fixture(scope="module")
def uni():
    from gubernator_tpu import native

    return U.build_universe(native, SMALL, 7, SLOTS,
                            table_rows=int(0.84 * SLOTS))


def test_every_key_starts_in_exactly_one_tier(uni):
    assert uni.cold is not None
    assert (uni.resident ^ uni.cold).all()
    assert uni.n_resident == int(0.84 * SLOTS) == 1720       # AT the mark
    assert int(uni.cold.sum()) == 3000 - 1720
    # A table row's slot is still bucket * ways + way, each used once.
    t = np.flatnonzero(uni.resident)
    slot = uni.gbucket[t].astype(np.int64) * 8 + uni.way[t]
    assert len(np.unique(slot)) == len(t) and uni.way[t].max() < 8
    # Way by way: a bucket's lower ranks before any bucket's higher one,
    # and of the last rank the buckets of the lowest numbers.
    last = int(uni.way[t].max())
    assert (uni.resident[uni.way < last]).all()
    at = np.flatnonzero(uni.way == last)
    inside = uni.resident[at]
    assert uni.gbucket[at][inside].max() < uni.gbucket[at][~inside].min()
    assert not uni.resident[uni.way > last].any()


def test_a_universe_under_the_mark_is_all_table_but_the_crowded_keys():
    from gubernator_tpu import native

    u = U.build_universe(native, dict(SMALL, keys=400), 7, SLOTS,
                         table_rows=int(0.84 * SLOTS))
    assert (u.resident == (u.way < 8)).all()
    assert (u.cold == ~u.resident).all()


def test_the_handoff_carries_both_tiers_and_says_which_the_probe_finds(uni):
    h = U.handoff(uni, 7, 500)
    assert len(h["fp"]) == 1720 and len(h["cold_fp"]) == 1280
    assert not set(h["fp"].tolist()) & set(h["cold_fp"].tolist())
    assert set(h["fp"].tolist()) | set(h["cold_fp"].tolist()) == set(
        uni.fp.tolist())
    assert (h["probe_found"] ^ h["probe_cold"]).all()
    cold = U.cold_arrays(h, T0)
    assert tuple(cold) == U.COLD_FIELDS
    from gubernator_tpu.runtime import coldtier

    assert U.COLD_FIELDS == coldtier.COLD_FIELDS       # copied, held here
    leaky = cold["algo"] == 1
    assert (cold["remaining"][~leaky] == h["cold_remaining0"][~leaky]).all()
    assert (cold["remaining_f"][leaky] == h["cold_remaining0"][leaky]).all()
    assert (cold["remaining"][leaky] == 0).all()
    assert (cold["expire_at"] == T0 + DUR).all() and (cold["t0"] == T0).all()
    assert (cold["limit"] == LIMIT).all() and (cold["burst"] == LIMIT).all()
    # The seam itself takes them: a cold store restored from the columns
    # holds every row, and says so of each key.
    store = coldtier.ColdTier(2000)
    assert store.restore(cold) == 1280 == store.residents()
    assert store.member_hits(h["probe_fp"]).tolist() == h[
        "probe_cold"].tolist()


def test_the_row_bounds_of_both_tiers(uni):
    none = np.zeros(0, np.int64)
    lo, hi = U.tiered_row_bounds(uni, none, 0)
    counts = np.bincount(uni.gbucket, minlength=SLOTS // 8)
    assert lo == int(np.minimum(counts, 8).sum()) < hi == 3000
    assert U.tiered_row_bounds(uni, none, 17)[1] == 3017
    extra = np.array([12345, 12345, 99], dtype=np.int64)
    lo2, hi2 = U.tiered_row_bounds(uni, extra, 0)
    assert hi2 == 3002 and lo <= lo2 <= lo + 2


def test_a_table_universe_is_what_the_parent_built_to_the_byte():
    """test_traffic_forms.py's rule for the universe: the digests were taken
    from the parent commit's bench/lib/universe.py."""
    from gubernator_tpu import native

    want = spec.load_json(os.path.join(DATA, "parent_universe_digests.json"))
    bm = spec.benchmark()
    assert {k.split("/")[0] for k in want["digests"]} == {
        c["name"] for c in bm["configs"]}
    for key, digest in want["digests"].items():
        name, seed = key.split("/")
        cfg = spec.load_json(spec.config_path(bm, name))
        peers = spec.peers_of(cfg)
        u = U.build_universe(native, dict(cfg["universe"], keys=39000),
                             int(seed), 65536 // peers, spec.ring_of(cfg))
        assert u.cold is None
        h = hashlib.sha256()
        for d in range(peers):
            hand = U.handoff(u, int(seed), 4096, d)
            assert not [k for k in hand if "cold" in k]
            for group in (hand, U.table_arrays(hand, T0)):
                for k in sorted(group):
                    h.update(k.encode())
                    h.update(group[k].tobytes())
        for f in ("ids", "fp", "algo", "is_global", "remaining0", "gbucket",
                  "way", "resident", "crowded", "slot_order"):
            h.update(getattr(u, f).tobytes())
        assert h.hexdigest() == digest, key


# -- the comparison -------------------------------------------------------------

class _Daemon:
    """core/pymodel.py as the table, a dict as the cold store, and the moves
    docs/tiering.md gives the tier: demote, promote (the merge), and — for
    the faults — a promote that drops and one that mints.  Rows are created
    at T0 as the harness preloads them; the clock is the test's."""

    def __init__(self, uni):
        from gubernator_tpu.core import clock as clock_mod
        from gubernator_tpu.core.pymodel import PyRateLimiter
        from gubernator_tpu.core.types import Algorithm, RateLimitReq

        self.uni, self.Req, self.Algorithm = uni, RateLimitReq, Algorithm
        self.clk = clock_mod.Clock()
        self.table = PyRateLimiter(clock=self.clk)
        self.cold = {}
        ref = oracle._Reference(uni, T0, np.zeros(0, np.int64))
        for k in range(len(uni.fp)):
            leaky = int(uni.algo[k]) == U.ALGO_LEAKY
            key = U.key_string(int(uni.ids[k]))
            item = ref.preloaded_item(k, key, leaky)
            (self.cold if uni.cold[k] else self.table.cache)[key] = item

    def check(self, k, now_ms, hits=1):
        self.clk.freeze(now_ms * 1_000_000)
        key = U.key_string(int(self.uni.ids[k]))
        leaky = int(self.uni.algo[k]) == U.ALGO_LEAKY
        r = self.table.get_rate_limit(self.Req(
            name=key[:9], unique_key=key[10:], hits=hits, limit=LIMIT,
            duration=DUR, algorithm=(self.Algorithm.LEAKY_BUCKET if leaky
                                     else self.Algorithm.TOKEN_BUCKET)))
        return int(r.status), r.limit, r.remaining, r.reset_time

    def demote(self, k):
        key = U.key_string(int(self.uni.ids[k]))
        self.cold[key] = self.table.cache.pop(key)

    def evict(self, k):
        self.table.cache.pop(U.key_string(int(self.uni.ids[k])))

    def promote(self, k, how="merge"):
        key = U.key_string(int(self.uni.ids[k]))
        row = self.cold.pop(key)
        if how == "drop":
            return
        hot = self.table.cache.get(key)
        if hot is None:
            self.table.cache[key] = row
        elif how == "mint":             # keeps the larger of the two
            hot.remaining = max(hot.remaining, row.remaining)
        else:                           # ops/state.py migrate_inject
            hot.remaining = max(
                hot.remaining - max(row.limit - row.remaining, 0), 0)


def _pick(uni, cold, leaky, weak=False, n=1):
    ok = ((uni.cold == cold) & ((uni.algo == U.ALGO_LEAKY) == leaky)
          & (uni.crowded == weak) & (uni.remaining0 >= 8))
    got = np.flatnonzero(ok)[:n]
    assert len(got) == n
    return [int(k) for k in got]


def _judge(uni, script, deadline_ms=2000.0):
    """`script`: (key, seconds since T0, move) in order — a move is "check",
    "demote", "evict", "promote", "drop" or "mint"; every check is one RPC of
    one check, sent 2 ms before and received 3 ms after it is applied."""
    d = _Daemon(uni)
    rows = []
    for k, at_s, move in script:
        now = T0 + int(at_s * 1e3)
        if move == "check":
            rows.append((k, now) + d.check(k, now))
        elif move in ("demote", "evict"):
            getattr(d, move)(k)
        else:
            d.promote(k, {"promote": "merge"}.get(move, move))
    n = len(rows)
    col = lambda i: np.array([r[i] for r in rows], dtype=np.int64)  # noqa
    a = oracle.Answers(
        key=col(0), rpc=np.arange(n), hits=np.ones(n, np.int64),
        status=col(2), limit=col(3), remaining=col(4), reset_time=col(5),
        err_len=np.zeros(n, np.int64))
    now = col(1)
    rec = dict(t_send=(now - 2) / 1e3, t_done=(now + 3) / 1e3,
               wall_send=now - 2, wall_recv=now + 3)
    v = oracle.Verdict()
    oracle.screen(a, uni, v)
    uni = copy.copy(uni)
    uni.promote_deadline_ms = deadline_ms
    oracle.replay_tiered(a, rec, uni, T0, 0, np.zeros(0, np.int64),
                         np.zeros(0, np.int64), v)
    return a, v


def _clean(v):
    return {k: n for k, n in v.counts.items() if n}


@pytest.mark.parametrize("leaky", [False, True])
def test_continued_fresh_merged_and_promoted_are_accepted(uni, leaky):
    (t,) = _pick(uni, cold=False, leaky=leaky)
    c, c2 = _pick(uni, cold=True, leaky=leaky, n=2)
    r0 = int(uni.remaining0[c])
    a, v = _judge(uni, [
        (t, 1.0, "check"), (t, 1.5, "check"),            # continued
        (c, 1.0, "check"), (c, 1.2, "check"),            # fresh, unmerged
        (c, 1.5, "promote"), (c, 1.6, "check"),          # merged
        (c2, 2.0, "promote"), (c2, 2.1, "check"),        # promoted first
        (t, 3.0, "demote"), (t, 3.5, "check"),           # fresh again
        (t, 3.6, "promote"), (t, 3.7, "check"),          # and merged
        (t, 9.0, "check"),
    ])
    assert _clean(v) == {}
    assert a.remaining[a.key == c].tolist() == [999, 998, r0 - 3]
    assert v.notes["fresh_answers"] == 2 and v.notes["merged_answers"] == 2
    assert v.notes["promoted_answers"] == 1
    assert v.notes["continued_answers"] == 4
    assert v.notes["keys_started_cold"] == 2


def test_a_bucket_that_runs_out_stays_out_through_the_merge(uni):
    (c,) = _pick(uni, cold=True, leaky=False)
    r0 = int(uni.remaining0[c])
    script = [(c, 1.0, "check"), (c, 1.1, "promote")]
    script += [(c, 1.2 + i / 100, "check") for i in range(r0 + 2)]
    a, v = _judge(uni, script)
    assert _clean(v) == {} and a.status[-4:].tolist() == [0, 1, 1, 1]


@pytest.mark.parametrize("leaky", [False, True])
def test_minted_budget_is_a_wrong_answer(uni, leaky):
    (c,) = _pick(uni, cold=True, leaky=leaky)
    _, v = _judge(uni, [
        (c, 1.0, "check"), (c, 1.1, "check"), (c, 1.2, "demote"),
        # The cold store now holds the FRESH row: the preloaded one is lost,
        # which docs/tiering.md's merge never does.
        (c, 1.3, "check"), (c, 1.4, "promote"), (c, 1.5, "check"),
    ])
    assert v.counts["wrong_answers"] == 1 and v.first["what"] == (
        "wrong_answers")
    (c,) = _pick(uni, cold=True, leaky=leaky)
    _, v = _judge(uni, [(c, 1.0, "check"), (c, 1.1, "mint"),
                        (c, 1.2, "check"), (c, 4.0, "check")])
    # max(fresh, cold) is the fresh row: the merge never showed.
    assert _clean(v) == {"merged_late": 1}


def test_an_answer_no_state_gives_is_a_wrong_answer(uni):
    (t,) = _pick(uni, cold=False, leaky=False)
    d = _Daemon(uni)
    st, lim, rem, reset = d.check(t, T0 + 1000)
    a = oracle.Answers(
        key=np.array([t]), rpc=np.array([0]), hits=np.array([1]),
        status=np.array([st]), limit=np.array([lim]),
        remaining=np.array([rem + 1]), reset_time=np.array([reset]),
        err_len=np.array([0]))
    rec = dict(t_send=np.array([1.0]), t_done=np.array([1.01]),
               wall_send=np.array([T0 + 998]), wall_recv=np.array([T0 + 1003]))
    v = oracle.Verdict()
    oracle.replay_tiered(a, rec, uni, T0, 0, np.zeros(0, np.int64),
                         np.zeros(0, np.int64), v)
    assert v.counts["wrong_answers"] == 1


@pytest.mark.parametrize("leaky", [False, True])
def test_a_merge_later_than_the_deadline_is_refused(uni, leaky):
    (c,) = _pick(uni, cold=True, leaky=leaky)
    sound = [(c, 1.0, "check"), (c, 2.9, "check"),       # inside 2 s
             (c, 2.95, "promote"), (c, 3.1, "check")]
    assert _clean(_judge(uni, sound)[1]) == {}
    late = [(c, 1.0, "check"), (c, 3.1, "check"),        # 2.1 s, unmerged
            (c, 3.2, "promote"), (c, 3.3, "check")]
    _, v = _judge(uni, late)
    assert _clean(v) == {"merged_late": 1}
    assert v.first["after_s"] == pytest.approx(2.095)
    # The deadline is the configuration's: at 2.5 s the same answers pass.
    assert _clean(_judge(uni, late, deadline_ms=2500.0)[1]) == {}


@pytest.mark.parametrize("leaky", [False, True])
def test_a_dropped_cold_row_is_refused(uni, leaky):
    (c,) = _pick(uni, cold=True, leaky=leaky)
    (t,) = _pick(uni, cold=False, leaky=leaky)
    _, v = _judge(uni, [
        (c, 1.0, "check"), (c, 1.1, "drop"), (c, 1.5, "check"),
        (c, 4.0, "check"), (c, 5.0, "check"),
        (t, 1.0, "demote"), (t, 1.1, "check"), (t, 1.2, "drop"),
        (t, 6.0, "check"),
    ])
    assert _clean(v) == {"merged_late": 3}


def test_a_crowded_buckets_row_may_have_been_evicted(uni):
    """A bucket with more arrivals than ways can lose a live row to the
    step's own eviction: its fresh starts are allowed and its unmerged
    answers not counted — but a row that STARTED cold is known to wait,
    crowded bucket or not."""
    (w,) = _pick(uni, cold=False, leaky=False, weak=True)
    _, v = _judge(uni, [(w, 1.0, "evict"), (w, 1.1, "check"),
                        (w, 5.0, "check")])
    assert _clean(v) == {} and v.notes["fresh_answers"] == 1
    (w,) = _pick(uni, cold=True, leaky=False, weak=True)
    _, v = _judge(uni, [(w, 1.0, "check"), (w, 5.0, "check")])
    assert _clean(v) == {"merged_late": 1}
    # Its fresh row evicted, the cold row promoted into the empty way: the
    # cold row itself answers.
    _, v = _judge(uni, [(w, 1.0, "check"), (w, 1.1, "evict"),
                        (w, 1.2, "promote"), (w, 1.3, "check")])
    assert _clean(v) == {} and v.notes["promoted_answers"] == 1
    # A bucket with room cannot evict: the same answers are refused there.
    (s,) = _pick(uni, cold=False, leaky=False, weak=False)
    _, v = _judge(uni, [(s, 1.0, "evict"), (s, 1.1, "check"),
                        (s, 5.0, "check")])
    assert _clean(v) == {"merged_late": 1}


def test_rpcs_in_flight_together_may_straddle_a_fresh_start(uni):
    """Two RPCs in flight at once, a demotion between them: the canonical
    order (remaining falling) puts the fresh answer first, the daemon gave
    it second; the interleavings find the order that is."""
    (t,) = _pick(uni, cold=False, leaky=False)
    d = _Daemon(uni)
    first = d.check(t, T0 + 1000)
    d.demote(t)
    second = d.check(t, T0 + 1001)
    a = oracle.Answers(
        key=np.array([t, t]), rpc=np.array([0, 1]), hits=np.array([1, 1]),
        status=np.array([first[0], second[0]]),
        limit=np.array([LIMIT, LIMIT]),
        remaining=np.array([first[2], second[2]]),
        reset_time=np.array([first[3], second[3]]),
        err_len=np.zeros(2, np.int64))
    rec = dict(t_send=np.array([0.990, 0.991]), t_done=np.array([1.01, 1.02]),
               wall_send=np.array([T0 + 990, T0 + 991]),
               wall_recv=np.array([T0 + 1010, T0 + 1020]))
    v = oracle.Verdict()
    oracle.replay_tiered(a, rec, uni, T0, 0, np.zeros(0, np.int64),
                         np.zeros(0, np.int64), v)
    assert second[2] == 999 > first[2] and _clean(v) == {}
    assert v.notes["fresh_answers"] == 1 == v.notes["continued_answers"]


def test_the_frozen_comparison_refuses_what_the_tiered_one_accepts(uni):
    """The seam ISSUE 45 found: `replay_sample` holds a key outside a
    crowded bucket to a strict replay, and calls the tier's fresh answer
    wrong."""
    (c,) = _pick(uni, cold=True, leaky=False)
    d = _Daemon(uni)
    st, lim, rem, reset = d.check(c, T0 + 1000)
    a = oracle.Answers(
        key=np.array([c]), rpc=np.array([0]), hits=np.array([1]),
        status=np.array([st]), limit=np.array([lim]),
        remaining=np.array([rem]), reset_time=np.array([reset]),
        err_len=np.array([0]))
    rec = dict(t_send=np.array([1.0]), t_done=np.array([1.01]),
               wall_send=np.array([T0 + 998]), wall_recv=np.array([T0 + 1003]))
    plain = copy.copy(uni)
    plain.resident = uni.resident | uni.cold    # as if the table held it
    v = oracle.Verdict()
    oracle.replay_sample(a, rec, plain, T0, 0, np.zeros(0, np.int64),
                         np.zeros(0, np.int64), v)
    assert v.counts["wrong_answers"] == 1
    v = oracle.Verdict()
    oracle.replay_tiered(a, rec, uni, T0, 0, np.zeros(0, np.int64),
                         np.zeros(0, np.int64), v)
    assert _clean(v) == {}


def test_the_merge_never_gives_more_than_either_row():
    for hot in (0, 1, 500, 999, 1000):
        for cold in (0, 7, 31, 999, 1000):
            rem, status = oracle.merge_rows((hot, 0), (cold, 1), LIMIT)
            assert rem == max(cold - (LIMIT - hot), 0) <= min(hot, cold)
            assert status == 0
    assert oracle.merge_rows((998.0, 0), (12.0, 0), LIMIT) == (10.0, 0)


def test_the_most_checks_in_a_span():
    t = np.array([0.1, 0.2, 1.0, 1.9, 2.05, 5.0])
    n = np.array([10, 20, 30, 40, 50, 60])
    assert oracle.most_checks_in_span(t, n, 2.0) == 150    # 0.1 .. 2.05
    assert oracle.most_checks_in_span(t, n, 0.05) == 60
    assert oracle.most_checks_in_span(t, n, 10.0) == 210
    assert oracle.most_checks_in_span(t[:0], n[:0], 2.0) == 0


# -- what run.py takes from the daemon's ledger -------------------------------

def _snap(t, occupancy, **tier):
    base = dict(cold_residents=1280, cold_capacity=2000, demotes=0,
                promotes=0, cold_hits=0, capacity_drops=0, promote_failures=0,
                promote_retries=0, demote_passes=0, ticks=0,
                promote_latency={"buckets": [1.0], "cumulative": [0, 0],
                                 "sum_s": 0.0, "p99_s": 0.0})
    base.update(tier)
    return types.SimpleNamespace(t=t, vars={
        "tier": base, "backend": {"occupancy": occupancy}})


def _counts(uni, end, answered=400):
    import run

    cfg = copy.deepcopy(CFG)
    cfg["daemon"]["GUBER_TPU_NUM_SLOTS"] = str(SLOTS)
    compare = run.Compare()
    n = 8
    plan = types.SimpleNamespace(offsets=np.arange(n + 1) * (answered // n))
    rec = dict(code=np.zeros(n, np.int64), plan_idx=np.arange(n),
               t_done=np.linspace(10.0, 17.0, n))
    keys = np.arange(answered) % 3000
    a = oracle.Answers(
        key=keys, rpc=np.repeat(np.arange(n), answered // n),
        hits=np.ones(answered, np.int64), status=np.zeros(answered, np.int64),
        limit=None, remaining=None, reset_time=None,
        err_len=np.zeros(answered, np.int64))
    uni = copy.copy(uni)
    uni.promote_deadline_ms = 2000.0
    tier, lo, hi, occ = run.tiered_counts(
        compare, cfg, uni, plan, rec, a, _snap(0.0, 1720), end, SLOTS,
        np.zeros(0, np.int64), (_snap(9.0, 1720), _snap(19.0, 1740)))
    return compare, tier, lo, hi, occ


def test_a_sound_ledger_passes_and_a_grown_capacity_drops_is_refused(uni):
    compare, tier, lo, hi, occ = _counts(
        uni, _snap(30.0, 1745, demotes=40, promotes=35, cold_hits=50,
                   cold_residents=1255))
    assert compare.ok and occ == 1745 + 1255 and lo <= occ <= hi == 3000
    assert list(compare.seen) == [
        "tier_capacity_drops_grown", "tier_promote_failures_grown",
        "tier_admitted_beyond_ledger", "table_beyond_high_water"]
    assert tier["grown_since_ready"]["demotes"] == 40
    assert tier["high_water_rows"] == int(0.85 * SLOTS) == 1740
    compare, *_ = _counts(uni, _snap(30.0, 1745, capacity_drops=3))
    assert not compare.ok
    assert compare.seen["tier_capacity_drops_grown"] == [3, 0]
    compare, *_ = _counts(uni, _snap(30.0, 1745, promote_failures=1))
    assert compare.seen["tier_promote_failures_grown"] == [1, 0]


def test_a_table_that_fills_past_its_mark_is_refused(uni):
    # 400 checks in the run: the table may stand 1,740 + the busiest two
    # ticks' checks, and no higher.
    compare, *_ = _counts(uni, _snap(30.0, 1740 + 150))
    assert compare.ok
    compare, *_ = _counts(uni, _snap(30.0, 2040))
    assert compare.seen["table_beyond_high_water"][0] > 0 and not compare.ok


def test_keys_answered_inside_the_deadline_may_still_hold_two_rows(uni):
    # The count is taken at t = 18: RPCs answered from t = 16 on are young.
    *_, hi, _ = _counts(uni, _snap(18.0, 1745))
    assert hi == 3000 + 2 * 50


# -- the rest of a run, on a live daemon with the tier on ----------------------
#
# `--platform cpu --slots 65536 --keys 78000` is the cell's own 1.19 x.  A
# live daemon through bench/serve.py is also the seam's pin inside bench/:
# every `/debug/vars` `tier.*` name `run.tiered_counts` reads, the cold
# store's `restore` and `member_hits`, and the private names the warming of
# the tier's programs reaches for — a rename fails here, not on the chip.

NOT_A_RUN = {"not_a_tpu_run", "cell_held_out"}
TIER_VARS = ("cold_residents", "cold_capacity", "demotes", "promotes",
             "cold_hits", "capacity_drops", "promote_failures",
             "promote_latency")


def _tier_dry_run(tmp_path, *extra, seconds="3"):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--held-out",
         "--workload", "tier20m.batch.closed", "--seed", "2246822519",
         "--seconds", seconds, "--trace", "0", "--platform", "cpu",
         "--slots", "65536", "--keys", "78000",
         "--out", str(tmp_path / "out"), *extra],
        env=env, cwd=spec.REPO, capture_output=True, text=True, timeout=900,
    )
    lines = p.stdout.strip().splitlines()
    if p.returncode:
        return None, p.stderr, lines
    failed = {ln.split()[1].rstrip(":") for ln in lines
              if ln.startswith("compare ") and ln.endswith("FAILED")}
    return json.loads(lines[-1]), failed, lines


def test_the_tiered_cell_rehearses_and_names_what_departs(tmp_path):
    result, failed, lines = _tier_dry_run(tmp_path)
    # On this host's CPU the manager's one worker runs seconds behind its
    # queue, so a sound daemon fails `merged_late` here; and on a table of
    # 65,536 slots the demoter reaches rows touched seconds ago, a fresh row
    # among them whose cold row still waits — `put_rows` overwrites that
    # cold row and its budget is minted: a few `wrong_answers` on a key or
    # two (PERF.md section 7), against the thousands of `--control alter`.
    assert NOT_A_RUN <= failed <= NOT_A_RUN | {"merged_late", "wrong_answers"}
    assert result["correct"] is False and result["failed"] == 0
    compared = result["compared"]
    sampled = sum(v for k, v in result["replay"].items()
                  if k.endswith("_answers"))
    assert compared["wrong_answers"][0] < 0.001 * sampled
    assert set(oracle.TIERED_COUNTS) | {
        "tier_capacity_drops_grown", "tier_promote_failures_grown",
        "tier_admitted_beyond_ledger", "table_beyond_high_water",
        "occupancy_beyond_expected", "occupancy_below_expected",
        "preload_occupancy_differs", "probe_differs_from_placement",
        "compiled_in_window"} <= set(compared)
    for name in ("wrong_reset_time", "admitted_beyond_bound",
                 "preload_occupancy_differs", "probe_differs_from_placement",
                 "compiled_in_window", "tier_capacity_drops_grown"):
        assert compared[name] == [0, 0], name
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    seen = result["replay"]
    assert set(seen) == set(oracle.TIERED_SEEN)
    assert min(seen["fresh_answers"], seen["merged_answers"],
               seen["continued_answers"], seen["keys_started_cold"]) > 0
    tier = result["tier"]
    assert tier["grown_since_ready"]["promotes"] > 0
    assert tier["grown_since_ready"]["cold_hits"] > 0
    assert tier["promotes_per_s"] > 0 and tier["table_rows"] > 0
    (ready,) = [ln for ln in lines if "daemon ready" in ln]
    assert "'cold -> cold': 22950, 'table -> table': 55050" in ready
    assert "'cold_restored': 22950" in ready and "'occupancy': 55050" in ready
    assert "tier programs {'programs': 3, 'skipped': ''" in ready
    # The names the comparison reads were all there (a missing one would
    # have ended the run with no result).
    (said,) = [ln for ln in lines if "] tier: table " in ln]
    for name in ("demotes", "promotes", "cold_hits", "capacity_drops",
                 "promote_failures", "p99_s"):
        assert f"'{name}'" in said


@pytest.mark.parametrize("control, must_fail", [
    ("droppromote", "merged_late"),
    ("alter", "wrong_answers"),
    ("f32", "wrong_reset_time"),
])
def test_a_broken_tiered_daemon_comes_out_not_correct(tmp_path, control,
                                                      must_fail):
    result, failed, _ = _tier_dry_run(tmp_path, "--control", control)
    assert must_fail in failed and result["correct"] is False
    assert result["compared"][must_fail][0] > 0
    if control == "droppromote":
        # No promote's merge ever lands: what a sound run shows by the
        # hundred (759-1,056 here, 384-4,428 on the chip).  The few that
        # remain are the demoter's own: of a pass's extracted rows the
        # hotter ones go straight back through `migrate_inject`, and a key
        # served between the two is merged — not through `_promote`.
        assert result["replay"]["merged_answers"] < 40
        assert result["replay"]["promoted_answers"] < 40
        assert result["compared"]["wrong_answers"] == [0, 0]
    else:
        assert "wire_check_mismatches" in failed


def test_a_tiered_cell_with_its_tier_overridden_off_is_refused(tmp_path):
    result, err, lines = _tier_dry_run(
        tmp_path, "--daemon", "GUBER_TIER_ENABLED=false")
    assert result is None and "no result" in err
    assert "residency is none of the known forms" in err
    assert not any(ln.startswith("{") for ln in lines)
