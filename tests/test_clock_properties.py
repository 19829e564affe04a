"""The two properties of the served program that the moving-clock comparison
(`bench/lib/oracle.py` `replay_moving`) reads from an answer, on the served
path (gRPC, compiled lane) under a clock the test steps:

  a leaky answer's  reset_time - (limit - remaining) x trunc(duration / limit)
                    is the clock the daemon answered under;
  a token answer's  reset_time - duration  is the stamp its bucket was
                    created at,

across a leak, an expiry and a renewal.  And ONE CLOCK A DRAIN (PR 34): a
cascade merge's write-back rounds run under the clock its read rounds took,
so a window that ends, or a leak that completes, between the two is not
taken by the write-back (PERF.md section 7, PR 33 found both; they failed
here until `fastpath._process_packed` took the clock once a hold of
`backend._lock`).  A duplicated key that comes three times makes such a
merge; one that comes twice rides the drain's rounds (PR 40,
`fastpath._cascade_or_rounds`), a plain merge whose rounds all run under the
one reading `backend._dispatch_rounds_locked` takes: both are held here.

The tier-1 copy of bench/tests/test_clock_properties.py, without its
`xfail`s, on the one-chip backend and on the mesh backend."""
import pytest

from gubernator_tpu import native
from gubernator_tpu.client import V1Client
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.types import Algorithm, RateLimitReq
from gubernator_tpu.testing import Cluster

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)
T = 1_790_000_000_000
LIMIT, DUR, RATE = 100, 1000, 10


class Stepped(clock_mod.Clock):
    """Stands at `t` ms; `script` holds the next readings, after which it
    stands at the last of them."""

    def __init__(self, t: int) -> None:
        super().__init__()
        self.t, self.script = t, []

    def millisecond_now(self) -> int:
        if self.script:
            self.t = self.script.pop(0)
        return self.t

    def now_ns(self) -> int:
        return self.t * 1_000_000


MESH = DeviceConfig(num_slots=4 * 8 * 64, ways=8, batch_size=64, num_shards=4)


@pytest.fixture(scope="module", params=["one_chip", "mesh"])
def served(request):
    """A daemon on the one-chip backend, and one on the mesh backend: the
    merge's dispatches go through either's `_dispatch_rounds_locked`."""
    c = Cluster.start(1, device=MESH if request.param == "mesh" else None)
    clk = Stepped(T)
    backend = c.daemons[0].service.backend
    backend.clock = clk
    cl = V1Client(c.addresses()[0])
    yield cl, clk, c.daemons[0].fastpath
    cl.close()
    c.stop()


def ask(cl, key, algo, *hits):
    return cl.get_rate_limits(reqs(key, algo, *hits))


def reqs(key, algo, *hits):
    return [
        RateLimitReq(name="clock_props", unique_key=key, hits=h, limit=LIMIT,
                     duration=DUR, algorithm=algo) for h in hits
    ]


class Reference:
    """core/pymodel.py, every request at the clock it is given."""

    def __init__(self) -> None:
        from gubernator_tpu.core.pymodel import PyRateLimiter

        self.clk = clock_mod.Clock()
        self.model = PyRateLimiter(clock=self.clk)

    def ask(self, at, key, algo, *hits):
        self.clk.freeze(at * 1_000_000)
        return [self.model.get_rate_limit(r) for r in reqs(key, algo, *hits)]


def same(got, want):
    return [(int(r.status), r.remaining, r.reset_time) for r in got] == [
        (int(r.status), r.remaining, r.reset_time) for r in want]


def leaky_clock(r):
    return r.reset_time - (LIMIT - r.remaining) * RATE


def test_a_leaky_answer_carries_the_clock_it_was_given_under(served):
    cl, clk, fp = served
    ref = Reference()
    before = fp.served
    seen = []
    # A new bucket, a spend within a millisecond, a leak of two tokens and
    # a half, a peek, the bucket run down and over the limit, a leak of a
    # token to the millisecond, an expiry.
    for at, hits in ((0, 1), (0, 1), (25, 1), (26, 0), (27, 98), (27, 1),
                     (27, 1), (31, 0), (37, 1), (47, 1), (1500, 0)):
        clk.t = T + at
        got = ask(cl, "leaky", Algorithm.LEAKY_BUCKET, hits)
        assert got[0].error == "" and leaky_clock(got[0]) == T + at
        assert same(got, ref.ask(T + at, "leaky", Algorithm.LEAKY_BUCKET,
                                 hits)), (at, hits, got)
        seen.append((int(got[0].status), got[0].remaining))
    assert seen[:4] == [(0, 99), (0, 98), (0, 99), (0, 99)]
    assert (1, 0) in seen and seen[-1] == (0, 100)
    # Duplicates in one RPC (a cascade merge) carry one clock.
    clk.t = T + 1600
    got = ask(cl, "leaky", Algorithm.LEAKY_BUCKET, 1, 0, 1, 1)
    assert [leaky_clock(r) for r in got] == [T + 1600] * 4
    assert same(got, ref.ask(T + 1600, "leaky", Algorithm.LEAKY_BUCKET,
                             1, 0, 1, 1))
    assert fp.served > before       # the compiled lane answered


def test_a_token_answer_carries_its_buckets_creation_stamp(served):
    cl, clk, _ = served
    ref = Reference()
    stamps = []
    # Created at 0; spends and a peek inside the window; the window's last
    # millisecond; its end (expire_at <= now: a new bucket); a renewal
    # long after.
    for at, hits in ((0, 1), (400, 1), (401, 0), (999, 1), (1000, 1),
                     (1001, 0), (5000, 0), (5999, 2), (6000, 0)):
        clk.t = T + at
        got = ask(cl, "token", Algorithm.TOKEN_BUCKET, hits)
        assert same(got, ref.ask(T + at, "token", Algorithm.TOKEN_BUCKET,
                                 hits)), (at, hits, got)
        stamps.append(got[0].reset_time - DUR - T)
    assert stamps == [0, 0, 0, 0, 1000, 1000, 5000, 5000, 6000]


def cascades_of(fp):
    return fp._stages.debug_vars()["mach"]["cascade"]["count"]


# The spends of one key in one RPC: three make a cascade merge (read round,
# replay, write-back round), two a plain merge of two device rounds.
MERGES = pytest.mark.parametrize("spends,cascade", [
    ((1, 1, 1), 1), ((1, 1), 0),
], ids=["cascade_merge", "pair_rides_rounds"])


@MERGES
def test_a_window_that_ends_inside_a_cascade_merge_starts_full(
    served, spends, cascade,
):
    cl, clk, fp = served
    ref = Reference()
    key = f"merge{len(spends)}"
    clk.t = T + 10_000
    assert same(ask(cl, key, Algorithm.TOKEN_BUCKET, 1),
                ref.ask(T + 10_000, key, Algorithm.TOKEN_BUCKET, 1))
    # The merge's first round at the window's last millisecond, whatever
    # reads the clock next (the write-back round, a second round) at its
    # end.
    clk.script = [T + 10_999, T + 11_000]
    before = cascades_of(fp)
    assert same(ask(cl, key, Algorithm.TOKEN_BUCKET, *spends),
                ref.ask(T + 10_999, key, Algorithm.TOKEN_BUCKET, *spends))
    assert cascades_of(fp) - before == cascade
    clk.script = []
    clk.t = T + 11_001
    # The reference: the old window took every spend and the peek opens a
    # new one, full.  (With a clock of its own the write-back opened it at
    # 11,000 and spent them there, so the peek read 98.)
    assert same(ask(cl, key, Algorithm.TOKEN_BUCKET, 0),
                ref.ask(T + 11_001, key, Algorithm.TOKEN_BUCKET, 0))


@MERGES
def test_a_leak_pending_at_a_cascade_merge_is_not_taken_early(
    served, spends, cascade,
):
    cl, clk, fp = served
    ref = Reference()
    key = f"pending{len(spends)}"
    clk.t = T + 20_000
    assert same(ask(cl, key, Algorithm.LEAKY_BUCKET, 10),
                ref.ask(T + 20_000, key, Algorithm.LEAKY_BUCKET, 10))
    # 8 ms later a merge: no whole token has leaked at its first round; at
    # its next round, 3 ms on, one has.  (With a clock of its own the
    # write-back took 1.1 tokens there and stamped the bucket 20,011.)
    clk.script = [T + 20_008, T + 20_011]
    before = cascades_of(fp)
    assert same(ask(cl, key, Algorithm.LEAKY_BUCKET, *spends),
                ref.ask(T + 20_008, key, Algorithm.LEAKY_BUCKET, *spends))
    assert cascades_of(fp) - before == cascade
    clk.script = []
    # At 20,020 the reference leaks 2.0 tokens, all since 20,000 (the
    # write-back's own clock left 0.9 of a token pending since 20,011: one
    # short).
    differ = []
    for at in (20_019, 20_020, 20_021, 20_029, 20_030):
        clk.t = T + at
        differ.append(not same(
            ask(cl, key, Algorithm.LEAKY_BUCKET, 0),
            ref.ask(T + at, key, Algorithm.LEAKY_BUCKET, 0)))
    assert not any(differ), differ
