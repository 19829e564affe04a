"""The device step: batched lookup/insert + branchless bucket algorithms.

One jitted call applies a whole padded batch of rate-limit checks against the
slot table and returns per-lane responses:

    table', resp = apply_batch(table, batch, now)

This replaces the reference's per-request path
(worker channel -> algorithm fn -> LRU dict, workers.go:249-314 +
algorithms.go) with: bucket gather -> victim/claim resolution -> lane
arithmetic -> scatter.  Every ordered special case in algorithms.go is
re-derived as `jnp.where` lane selects; the differential test
(tests/test_differential.py) drives random op streams through this and the
sequential oracle (core/pymodel.py) and requires identical decisions.

Design notes:
- Lookup is W-way set-associative: bucket = key_hash & (num_buckets-1);
  num_buckets must be a power of two.
- Expired slots do not match (the reference cache returns a miss for expired
  items, lrucache.go:115-127); a request whose own slot expired prefers
  reusing that slot.
- Within-batch insert conflicts (two new keys choosing the same victim slot)
  are resolved in INSERT_ROUNDS claim rounds: every lane still in need takes
  its best candidate slot that is not reserved, and of the lanes taking one
  slot the lowest lane wins.  A slot is `bucket * ways + way`, so only lanes
  of one bucket can contend: the lanes are sorted ONCE by (bucket, lane), on
  32-bit operands, and each round is elementwise work plus running max/min
  inside a bucket's segment (`_claim_ways`) — no O(num_slots) temporaries,
  nothing wider than [B, ways], no loop, and the same cost whether two lanes
  miss or all of them.  The result is the rounds' definition exactly
  (tests/test_locate_slots.py keeps it written out over B x ways int64 slots
  and holds the two bit-identical).  After INSERT_ROUNDS, unresolved lanes
  are answered as "transient" new items (correct response, state not
  persisted) — the same acceptable-loss contract as reference cache eviction
  (architecture.md:5-11).
- Duplicate keys within a batch are the host packer's job (ops/batch.py
  rounds); this kernel assumes each active key appears once.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from gubernator_tpu.ops import f64bits as F
from gubernator_tpu.ops.state import (
    KIND_BUCKET,
    KIND_CACHED_RESP,
    SlotTable,
    write_rows,
)

ALGO_TOKEN = 0
ALGO_LEAKY = 1
UNDER = 0
OVER = 1

INSERT_ROUNDS = 3


class Resp(NamedTuple):
    """Per-lane response arrays (RateLimitResp, gubernator.proto:169-182)."""

    status: jax.Array     # int32[B]
    limit: jax.Array      # int64[B]
    remaining: jax.Array  # int64[B]
    reset_time: jax.Array  # int64[B]
    persisted: jax.Array  # bool[B]; False = transient (state not stored)
    found: jax.Array      # bool[B]; matched a live slot
    # POST-step stored remaining (truncated for leaky) — differs from the
    # response `remaining` in corner branches (e.g. a token duration-renew
    # on a hits=0 read reports the pre-renew value, algorithms.go:167).
    # Seeds the fast lane's host-side duplicate cascade
    # (runtime/fastpath.py).
    stored: jax.Array     # int64[B]
    # Lane answered VERBATIM from a live KIND_CACHED_RESP row (the GLOBAL
    # broadcast read path) — no mutation happened; the fast lane's cached
    # duplicate cascade branches on this.
    cached: jax.Array     # bool[B]
    # POST-step stored Status column (the write-back's n_status): what a
    # hits=0 re-read of this row would report.  Token status is STICKY —
    # it differs from the response status on over-more hits, which report
    # OVER without storing it (algorithms.go:167-195); leaky rows store
    # UNDER always (status is computed per read, algorithms.go:395-426).
    # Lets the GLOBAL broadcast derive its rows from the drain's own
    # response instead of re-running zero-hit reads (global.go:205-250).
    stored_status: jax.Array  # int32[B]


class DeviceBatchJ(NamedTuple):
    """Device-side mirror of ops.batch.DeviceBatch."""

    key_hash: jax.Array
    hits: jax.Array
    limit: jax.Array
    duration: jax.Array
    algo: jax.Array
    burst: jax.Array
    reset_remaining: jax.Array
    is_greg: jax.Array
    greg_expire: jax.Array
    greg_duration: jax.Array
    active: jax.Array
    # GLOBAL read path (gubernator.go:434-447): lanes with use_cached set
    # answer verbatim from a live KIND_CACHED_RESP row (the owner's broadcast
    # status) without mutating it; on miss they fall through to the normal
    # algorithm ("process the rate limit like we own it").
    use_cached: jax.Array


def _f64(x: jax.Array) -> jax.Array:
    """The seam every int64 operand of the leaky lanes passes on its way
    into float64 (`_fb`): the identity here, since the conversion itself
    is `f64bits.from_i64`.  The benchmark's lower-precision control
    replaces it (bench/serve.py: the operand rounded through float32 and
    handed on as a float64 array, which `from_i64` takes by value)."""
    return x


def _fb(x: jax.Array) -> jax.Array:
    """float64(x) of an int64 operand, as bits (ops/f64bits.py)."""
    return F.from_i64(_f64(x))


def _trunc_i64(x: jax.Array) -> jax.Array:
    """Go's int64(float64): truncation toward zero — on an XLA float64
    array.  The step no longer computes in XLA's float64 (its leaky
    lanes are `f64bits`, whose `trunc_i64` keeps this contract on the
    bits); this stays as the contract's float spelling for the callers
    that hold a float array (tests/test_differential.py, chip_smoke.py,
    bench/witness/).

    The edge semantics are spelled out here, not left to the backend's
    convert, and differentially pinned against the oracle
    (core/pymodel.py _trunc; tests/test_differential.py::
    test_go_trunc_differential): -1.5 -> -1 (toward zero, not floor),
    exact through +/-2^62, out-of-range/inf SATURATE at the int64
    bounds, NaN -> 0.  Go's own spec leaves these implementation-
    dependent (amd64 CVTTSD2SI gives INT64_MIN for all three), so the
    saturating behavior is this build's documented contract.

    XLA:CPU's convert already behaves so.  XLA:TPU's does not (measured
    on a v5e, PR 21): float64 is a pair of float32s there and the
    convert truncates each half on its own, so a value just below an
    integer lands ON it (18.999999999 -> 19, 6646153.846 -> 6646154),
    and out-of-range values wrap, inf gives -1, NaN garbage.  Hence
    the explicit range selects, and the step back toward zero wherever
    the converted value overshot — a no-op where the convert is exact.
    """
    two63 = jnp.float64(2.0**63)
    over = x >= two63
    under = x <= -two63
    safe = jnp.where(over | under | jnp.isnan(x), jnp.float64(0.0), x)
    y = safe.astype(jnp.int64)
    yf = y.astype(jnp.float64)
    y = jnp.where((safe >= 0) & (yf > safe), y - 1, y)
    y = jnp.where((safe < 0) & (yf < safe), y + 1, y)
    return jnp.where(
        over, jnp.int64(2**63 - 1), jnp.where(under, jnp.int64(-(2**63)), y)
    )


def _sat_add_i64(a: jax.Array, b: jax.Array) -> jax.Array:
    """int64 a+b with two's-complement wrap replaced by saturation.

    Equivalent to clamping the exact unbounded-int sum, which is what
    the oracle mirror (core/pymodel.py _sat_add) computes — the
    differential suite holds the two bit-identical at the int64 corners
    (tests/test_gubrange.py).  Construction: clamp `b` into the room
    `a` leaves before the bound, then add — NO intermediate ever wraps
    (`max(a,0) ∈ [0,MAX]` so `MAX - max(a,0) ∈ [0,MAX]`, and the final
    sum is confined to [MIN,MAX] by the clip), which keeps the gubrange
    interval walk exact instead of a wrap-then-repair select that joins
    to the full int64 range.  Guards the expire/reset epoch math
    against hostile wire durations (the reference wraps silently here,
    algorithms.go:141 `now + r.Duration`); gubrange proves in-envelope
    inputs never come near saturation.
    """
    hi = jnp.int64(2**63 - 1)
    lo = jnp.int64(-(2**63))
    zero = jnp.int64(0)
    room_hi = hi - jnp.maximum(a, zero)
    room_lo = lo - jnp.minimum(a, zero)
    return a + jnp.clip(b, room_lo, room_hi)


def _sat_sub_i64(a: jax.Array, b: jax.Array) -> jax.Array:
    """int64 a-b saturating at the bounds (see _sat_add_i64).

    The subtrahend is clamped into [a-MAX, a-MIN] before subtracting;
    when a constraint endpoint is unrepresentable the corresponding
    clip bound degenerates to MIN/MAX (vacuous), so nothing wraps:
    `max(a,-1) - MAX ∈ [MIN,0]` and `min(a,-1) - MIN ∈ [0,MAX]`.
    """
    hi = jnp.int64(2**63 - 1)
    lo = jnp.int64(-(2**63))
    neg1 = jnp.int64(-1)
    b_lo = jnp.maximum(a, neg1) - hi
    b_hi = jnp.minimum(a, neg1) - lo
    return a - jnp.clip(b, b_lo, b_hi)


def _rank_ways(vscore: jax.Array, eligible: jax.Array) -> jax.Array:
    """int32[B, W]: each way's place in its lane's victim order — lowest
    `vscore` first, the lower way on a tie (what `argmin` picks) — and W
    for a way the lane may never take.  The int64 scores are compared
    here once, elementwise; the claim rounds see only these small ranks.
    """
    ways = vscore.shape[1]
    w = jnp.arange(ways, dtype=jnp.int32)
    mine, other = vscore[:, :, None], vscore[:, None, :]
    ahead = (other < mine) | ((other == mine) & (w[None, :] < w[:, None]))
    rank = jnp.sum(ahead, axis=2, dtype=jnp.int32)
    return jnp.where(eligible, rank, ways)


def _claim_ways(
    bucket: jax.Array,
    match_way: jax.Array,
    rank: jax.Array,
) -> jax.Array:
    """The claim rounds.  int32[B]: the way each lane won, -1 for none.

    `bucket` int32[B]; `match_way` int32[B]: the way a `found` lane
    matched, -1 for the others; `rank` int32[B, W] from `_rank_ways`.

    A slot is `bucket * ways + way`, so only lanes of ONE bucket can ever
    contend for it.  The lanes are therefore sorted once by (bucket,
    lane): a bucket's lanes become one contiguous segment, in lane order,
    and every question a round asks — is this way reserved in my bucket,
    does an earlier lane of my bucket want the same way — is a running
    max/min of lane positions along the sorted axis compared with the
    segment's bounds.  Nothing is table-sized or wider than [B, W], and
    all of it is 32-bit.
    """
    B, ways = rank.shape
    lane = jnp.arange(B, dtype=jnp.int32)
    bkt, lane_s, match_way, *cols = jax.lax.sort(
        (bucket, lane, match_way) + tuple(rank[:, w] for w in range(ways)),
        num_keys=2,
    )
    rank = jnp.stack(cols, axis=1)

    edge = bkt[1:] != bkt[:-1]
    yes = jnp.ones((1,), dtype=bool)
    seg_lo = jax.lax.cummax(
        jnp.where(jnp.concatenate([yes, edge]), lane, 0))
    seg_hi = jax.lax.cummin(
        jnp.where(jnp.concatenate([edge, yes]), lane, B - 1), reverse=True)
    seg_lo, seg_hi, pos = seg_lo[:, None], seg_hi[:, None], lane[:, None]
    w = jnp.arange(ways, dtype=jnp.int32)[None, :]
    none_yet = jnp.full((1, ways), -1, dtype=jnp.int32)

    def last_at_or_before(hit):
        return jax.lax.cummax(jnp.where(hit, pos, -1), axis=0)

    def in_segment(hit, before):
        """Per way: does any lane of my segment hit it."""
        after = jax.lax.cummin(jnp.where(hit, pos, B), axis=0, reverse=True)
        return (before >= seg_lo) | (after <= seg_hi)

    taken = w == match_way[:, None]
    blocked = in_segment(taken, last_at_or_before(taken))
    won_way = jnp.full((B,), -1, dtype=jnp.int32)
    for r in range(INSERT_ROUNDS):
        # Each lane still in need goes for its best way not yet reserved
        # in its bucket; of the lanes going for one way the first wins.
        open_rank = jnp.where(blocked, ways, rank)
        pick = jnp.argmin(open_rank, axis=1).astype(jnp.int32)
        attempt = (won_way < 0) & (jnp.min(open_rank, axis=1) < ways)
        hit = attempt[:, None] & (w == pick[:, None])
        before = last_at_or_before(hit)
        earlier = jnp.concatenate([none_yet, before[:-1]]) >= seg_lo
        win = attempt & ~jnp.any(hit & earlier, axis=1)
        won_way = jnp.where(win, pick, won_way)
        if r + 1 < INSERT_ROUNDS:
            # A way someone went for is a way someone won.
            blocked = blocked | in_segment(hit, before)

    _, won_way = jax.lax.sort((lane_s, won_way), num_keys=1)
    return won_way


def locate_slots(
    table: SlotTable,
    h: jax.Array,
    active: jax.Array,
    now: jax.Array,
    ways: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Set-associative lookup + insert-victim claim for a batch of keys.

    Returns (found, persist, slot, slot_safe, slot32): `found` lanes matched
    a live slot at `slot`; `persist & ~found` lanes won an insert victim at
    `slot`; `~persist` lanes could not claim a slot (transient).  Each
    active key must appear at most once in the batch (the packer's
    contract), so the `persist` lanes' slots are pairwise different.
    `slot32` is `slot` as int32, put together from the 32-bit bucket and
    way the claim runs on — what `write_rows` scatters at; it holds
    `slot` where the table has fewer than 2^31 slots, which `write_rows`
    checks.

    The claim is DEFINED as INSERT_ROUNDS rounds over the whole batch: in
    each, every lane still in need takes the lowest-scored candidate slot
    that no `found` lane matched and no earlier round gave away (the first
    such way on a tie; none left: no attempt), and of the lanes taking one
    slot the lowest lane wins it.  `_claim_ways` computes exactly that,
    bucket by bucket (tests/test_locate_slots.py holds it bit-identical to
    the rounds written out over B x W int64 slots), at a cost that does
    not depend on how many lanes miss.
    """
    S = table.key.shape[0]
    nb = S // ways
    if nb & (nb - 1):
        raise ValueError(f"num_buckets ({nb}) must be a power of two")
    if nb > 1 << 31:
        raise ValueError(f"num_buckets ({nb}) must fit 32 bits")

    bucket = (h.astype(jnp.uint64) & jnp.uint64(nb - 1)).astype(jnp.int64)
    sidx = bucket[:, None] * ways + jnp.arange(ways, dtype=jnp.int64)[None, :]

    cand_key = table.key[sidx]          # [B, W]
    cand_expire = table.expire_at[sidx]
    cand_touched = table.touched[sidx]

    keymatch = (cand_key == h[:, None]) & active[:, None]
    live = cand_expire > now
    match = keymatch & live
    found = match.any(axis=1)
    match_way = jnp.argmax(match, axis=1)
    match_slot = bucket * ways + match_way

    # ---- victim scoring for inserts ------------------------------------
    # Preference: my own expired slot > empty > other expired > oldest touch.
    empty = cand_key == 0
    mine_stale = keymatch & ~live
    klass = jnp.where(
        mine_stale, 0, jnp.where(empty, 1, jnp.where(~live, 2, 3))
    ).astype(jnp.int64)
    vscore = klass * (jnp.int64(1) << 48) + cand_touched  # touched < 2^48 ms

    need = active & ~found
    # A round never takes a way scored at or past `inf` (the mark of a
    # reserved way in the rounds' definition; no real score reaches it).
    inf = jnp.int64(1) << 62
    bucket32 = bucket.astype(jnp.int32)
    match_way32 = jnp.where(found, match_way, -1).astype(jnp.int32)
    won_way = _claim_ways(
        bucket32,
        match_way32,
        _rank_ways(vscore, need[:, None] & (vscore < inf)),
    )
    won = won_way >= 0

    persist = found | won
    slot = jnp.where(
        found, match_slot, jnp.where(won, bucket * ways + won_way, 0)
    )
    slot_safe = jnp.clip(slot, 0, S - 1)
    slot32 = jnp.where(
        persist, bucket32 * ways + jnp.where(found, match_way32, won_way), 0
    )
    return found, persist, slot, slot_safe, slot32


def apply_batch_impl(
    table: SlotTable,
    batch: DeviceBatchJ,
    now: jax.Array,
    ways: int = 8,
) -> Tuple[SlotTable, Resp]:
    """Apply one padded batch; returns (new_table, responses).

    Un-jitted traceable core — call `apply_batch` directly, or wrap this in
    `shard_map` for the mesh-sharded table (gubernator_tpu.parallel).
    """
    now = jnp.asarray(now, dtype=jnp.int64)

    h = batch.key_hash
    active = batch.active
    found, persist, _, slot_safe, slot32 = locate_slots(
        table, h, active, now, ways
    )

    # ---- gather current rows -------------------------------------------
    g = lambda a: a[slot_safe]
    s_algo = g(table.algo)
    s_kind = g(table.kind)
    s_limit = g(table.limit)
    s_dur = g(table.duration)
    s_rem = g(table.remaining)
    s_rem_f = g(table.remaining_f)
    s_t0 = g(table.t0)
    s_status = g(table.status)
    s_burst = g(table.burst)
    s_expire = g(table.expire_at)

    r_hits, r_lim, r_dur = batch.hits, batch.limit, batch.duration
    r_burst = batch.burst
    is_greg = batch.is_greg
    greg_exp = batch.greg_expire
    greg_dur = batch.greg_duration
    req_token = batch.algo == ALGO_TOKEN
    req_leaky = batch.algo == ALGO_LEAKY
    reset = batch.reset_remaining

    is_bucket_row = found & (s_kind == KIND_BUCKET)
    # GLOBAL non-owner read (gubernator.go:434-447): a live cached broadcast
    # row answers verbatim, no mutation.  Without use_cached, a cached row is
    # treated like an algorithm-switch (overwritten via the new-item path).
    cached_hit = found & (s_kind == KIND_CACHED_RESP) & batch.use_cached
    # Path selection (see module docstring):
    tok_clear = req_token & reset & found  # algorithms.go:78-90 (pre type check)
    tok_exist = req_token & ~reset & is_bucket_row & (s_algo == ALGO_TOKEN)
    lky_exist = req_leaky & is_bucket_row & (s_algo == ALGO_LEAKY)
    is_new = active & ~tok_clear & ~tok_exist & ~lky_exist

    # ==== token bucket, existing item (algorithms.go:112-195) ===========
    limit_changed = s_limit != r_lim
    rem0 = jnp.where(
        limit_changed,
        jnp.maximum(_sat_sub_i64(_sat_add_i64(s_rem, r_lim), s_limit), 0),
        s_rem,
    )
    dur_changed = s_dur != r_dur
    expire1 = jnp.where(is_greg, greg_exp, _sat_add_i64(s_t0, r_dur))
    renew = dur_changed & (expire1 <= now)
    te_expire = jnp.where(
        dur_changed, jnp.where(renew, _sat_add_i64(now, r_dur), expire1),
        s_expire,
    )
    te_t0 = jnp.where(renew, now, s_t0)
    rem1 = jnp.where(renew, r_lim, rem0)

    h0 = r_hits == 0
    # "Already at the limit" tests the RESPONSE remaining (rem0, set before
    # the duration-renew branch mutates item remaining) — algorithms.go:167.
    over_zero = ~h0 & (rem0 == 0) & (r_hits > 0)
    exact = ~h0 & ~over_zero & (rem1 == r_hits)  # algorithms.go:176 (item rem)
    over_more = ~h0 & ~over_zero & ~exact & (r_hits > rem1)
    under = ~h0 & ~over_zero & ~exact & ~over_more

    te_rem = jnp.where(exact, 0, jnp.where(under, rem1 - r_hits, rem1))
    te_status = jnp.where(over_zero, OVER, s_status)
    te_resp_status = jnp.where(over_zero | over_more, OVER, s_status)
    te_resp_rem = jnp.where(exact | under, te_rem, rem0)
    te_resp_reset = te_expire

    # ==== token bucket, new item (algorithms.go:203-258) ================
    tn_over = r_hits > r_lim
    tn_rem = jnp.where(tn_over, r_lim, r_lim - r_hits)
    tn_expire = jnp.where(is_greg, greg_exp, _sat_add_i64(now, r_dur))
    tn_resp_status = jnp.where(tn_over, OVER, UNDER)

    # ==== leaky bucket (algorithms.go:327-492) ==========================
    # Go's float64, operation for operation — on the BITS, in integer
    # words (ops/f64bits.py): IEEE binary64 on every backend, where XLA's
    # float64 is a pair of float32s on a TPU.  `lb*`, `*_rate`, `leak`
    # and `ln_rem_f` are int64 bit patterns; no value here is a float.
    with jax.named_scope("leaky_f64bits"):
        f_burst = _fb(r_burst)
        f_dur = _fb(r_dur)
        f_hits = _fb(r_hits)
        f_now = _fb(now)
        f_lim = _fb(r_lim)
        safe_lim = jnp.where(r_lim == 0, 1, r_lim)
        # Both rates in one division: the existing item's (the Gregorian
        # duration where there is one) and the new item's (quirk
        # preserved: RAW r.duration even under Gregorian — algorithms.go:441
        # computes rate before the adjustment).
        rates = F.div(
            jnp.stack([jnp.where(is_greg, _fb(greg_dur), f_dur), f_dur]),
            _fb(safe_lim)[None, :],
        )
        rates = jnp.where((r_lim == 0)[None, :], F.ZERO, rates)
        l_rate, ln_rate = rates[0], rates[1]

        # ---- existing item (algorithms.go:327-426) ---------------------
        lb0 = jnp.where(reset & req_leaky, f_burst, s_rem_f)
        grow = (s_burst != r_burst) & (r_burst > F.trunc_i64(lb0))
        lb1 = jnp.where(grow, f_burst, lb0)
        l_dur_c = jnp.where(is_greg, greg_exp - now, r_dur)
        # l_dur_c may be negative under Gregorian (greg_exp already
        # passed); saturating add keeps a hostile wire expiry from
        # wrapping the epoch.
        le_expire = jnp.where(
            r_hits != 0, _sat_add_i64(now, l_dur_c), s_expire
        )
        # (x / 0 is IEEE's inf or NaN in f64bits too, and dropped here.)
        leak = jnp.where(
            F.is_zero(l_rate), F.ZERO, F.div(_fb(now - s_t0), l_rate)
        )
        leaked = F.trunc_i64(leak) > 0
        lb2 = jnp.where(leaked, F.add(lb1, leak), lb1)
        le_t0 = jnp.where(leaked, now, s_t0)
        lb3 = jnp.where(F.trunc_i64(lb2) > r_burst, f_burst, lb2)
        lrem_i = F.trunc_i64(lb3)
        lrate_i = F.trunc_i64(l_rate)

        l_over_zero = (lrem_i == 0) & (r_hits > 0)
        l_exact = ~l_over_zero & (lrem_i == r_hits)
        l_over_more = ~l_over_zero & ~l_exact & (r_hits > lrem_i)
        l_take = l_exact | (
            ~l_over_zero & ~l_exact & ~l_over_more & (r_hits != 0)
        )
        lb4 = jnp.where(l_take, F.sub(lb3, f_hits), lb3)
        le_stored = F.trunc_i64(lb4)
        le_resp_rem = jnp.where(
            l_exact, 0, jnp.where(l_take, le_stored, lrem_i)
        )
        le_resp_status = jnp.where(l_over_zero | l_over_more, OVER, UNDER)

        # ---- new item (algorithms.go:433-492) --------------------------
        ln_rate_i = F.trunc_i64(ln_rate)
        ln_dur = jnp.where(is_greg, greg_exp - now, r_dur)
        ln_over = r_hits > r_burst
        ln_rem_f = jnp.where(ln_over, F.ZERO, _fb(r_burst - r_hits))
        ln_resp_rem = jnp.where(ln_over, 0, r_burst - r_hits)
        ln_stored = F.trunc_i64(ln_rem_f)
        ln_resp_status = jnp.where(ln_over, OVER, UNDER)
        ln_expire = _sat_add_i64(now, ln_dur)

        # ResetTime = now + (limit - remaining) * rate computed in float64
        # and truncated through the trunc_i64 saturation contract: exact
        # below 2^53 (every realistic envelope), rounding above it and
        # saturating instead of wrapping for hostile wire limits/durations.
        # The oracle mirrors the same float64 evaluation order bit-for-bit
        # (core/pymodel.py).  Both items' products in one pass.
        resets = F.trunc_i64(F.add(f_now, F.mul(
            F.sub(f_lim[None, :], _fb(jnp.stack([
                jnp.where(l_take, le_resp_rem, lrem_i), ln_resp_rem,
            ]))),
            _fb(jnp.stack([lrate_i, ln_rate_i])),
        )))
        le_resp_reset, ln_resp_reset = resets[0], resets[1]

    # ==== select per-lane outputs =======================================
    tok_new = is_new & req_token
    lky_new = is_new & req_leaky

    def sel(te, tn, le, ln, clear):
        x = jnp.where(tok_exist, te, 0)
        x = jnp.where(tok_new, tn, x)
        x = jnp.where(lky_exist, le, x)
        x = jnp.where(lky_new, ln, x)
        return jnp.where(tok_clear, clear, x)

    resp = Resp(
        status=jnp.where(
            cached_hit,
            s_status,
            sel(
                te_resp_status, tn_resp_status, le_resp_status, ln_resp_status,
                UNDER,
            ),
        ).astype(jnp.int32),
        limit=jnp.where(cached_hit, s_limit, jnp.where(active, r_lim, 0)),
        remaining=jnp.where(
            cached_hit,
            s_rem,
            sel(te_resp_rem, tn_rem, le_resp_rem, ln_resp_rem, r_lim),
        ),
        # Cached rows store ExpireAt = broadcast ResetTime (gubernator.go:466).
        reset_time=jnp.where(
            cached_hit,
            s_expire,
            sel(te_resp_reset, tn_expire, le_resp_reset, ln_resp_reset, 0),
        ),
        persisted=persist & active,
        found=found,
        stored=jnp.where(
            cached_hit,
            s_rem,
            sel(te_rem, tn_rem, le_stored, ln_stored, r_lim),
        ),
        cached=cached_hit,
        # Mirrors the write-back's n_status below (kept in sync).
        stored_status=jnp.where(
            cached_hit, s_status, sel(te_status, UNDER, 0, 0, 0)
        ).astype(jnp.int32),
    )

    # ==== write back ====================================================
    do_write = persist & active & ~cached_hit

    n_key = jnp.where(tok_clear, 0, h)
    n_algo = jnp.where(tok_clear, 0, batch.algo).astype(jnp.int32)
    n_kind = jnp.zeros_like(s_kind)
    n_limit = sel(r_lim, r_lim, r_lim, r_lim, 0)
    # Stored duration: leaky-existing stores RAW r.duration (algorithms.go:340)
    # but leaky-new stores the COMPUTED duration (algorithms.go:457).
    n_dur = sel(r_dur, r_dur, r_dur, ln_dur, 0)
    n_rem = sel(te_rem, tn_rem, 0, 0, 0)
    n_rem_f = sel(F.ZERO, F.ZERO, lb4, ln_rem_f, F.ZERO)
    n_t0 = sel(te_t0, now, le_t0, now, 0)
    n_status = sel(te_status, UNDER, 0, 0, 0).astype(jnp.int32)
    n_burst = sel(s_burst, 0, r_burst, r_burst, 0)
    n_expire = sel(te_expire, tn_expire, le_expire, ln_expire, 0)
    n_touched = jnp.where(tok_clear, 0, now)

    new_table = write_rows(table, do_write, slot32, SlotTable(
        key=n_key,
        algo=n_algo,
        kind=n_kind,
        limit=n_limit,
        duration=n_dur,
        remaining=n_rem,
        remaining_f=n_rem_f,
        t0=n_t0,
        status=n_status,
        burst=n_burst,
        expire_at=n_expire,
        touched=n_touched,
    ))
    return new_table, resp


apply_batch = jax.jit(
    apply_batch_impl, static_argnames=("ways",), donate_argnums=(0,)
)


class BucketRows(NamedTuple):
    """A batch of full bucket rows for bulk upsert — the device side of the
    Loader restore stream (workers.go:340-426) and of Store.Get seeding
    (algorithms.go:45-51).  key_hash 0 = inactive lane."""

    key_hash: jax.Array    # int64[B]
    algo: jax.Array        # int32[B]
    limit: jax.Array       # int64[B]
    duration: jax.Array    # int64[B]
    remaining: jax.Array   # int64[B]
    remaining_f: jax.Array  # int64[B]: binary64 BITS (f64bits.to_bits)
    t0: jax.Array          # int64[B]
    status: jax.Array      # int32[B]
    burst: jax.Array       # int64[B]
    expire_at: jax.Array   # int64[B]


def load_rows_impl(
    table: SlotTable,
    rows: BucketRows,
    now: jax.Array,
    ways: int = 8,
) -> SlotTable:
    """Upsert full bucket rows (KIND_BUCKET).  Keys unique within the batch."""
    if rows.remaining_f.dtype != jnp.int64:
        raise TypeError(
            "BucketRows.remaining_f carries binary64 BITS (int64): convert "
            f"on the host with f64bits.to_bits, got {rows.remaining_f.dtype}"
        )
    now = jnp.asarray(now, dtype=jnp.int64)
    active = rows.key_hash != 0
    _, persist, _, _, slot32 = locate_slots(
        table, rows.key_hash, active, now, ways
    )
    return write_rows(table, persist & active, slot32, SlotTable(
        key=rows.key_hash,
        algo=rows.algo,
        kind=jnp.full_like(rows.algo, KIND_BUCKET),
        limit=rows.limit,
        duration=rows.duration,
        remaining=rows.remaining,
        remaining_f=rows.remaining_f,
        t0=rows.t0,
        status=rows.status,
        burst=rows.burst,
        expire_at=rows.expire_at,
        touched=jnp.full_like(rows.key_hash, now),
    ))


load_rows = jax.jit(
    load_rows_impl, static_argnames=("ways",), donate_argnums=(0,)
)


def probe_batch_impl(
    table: SlotTable,
    h: jax.Array,
    now: jax.Array,
    ways: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """Read-only batched lookup: (found, slot) per lane.

    The batched analog of a cache-miss test (lrucache.go:111-127) — used by
    the Store write-through path to find which keys need `Store.Get` seeding
    before a batch, and to read back written rows for `Store.OnChange`.
    """
    S = table.key.shape[0]
    nb = S // ways
    bucket = (h.astype(jnp.uint64) & jnp.uint64(nb - 1)).astype(jnp.int64)
    sidx = bucket[:, None] * ways + jnp.arange(ways, dtype=jnp.int64)[None, :]
    match = (
        (table.key[sidx] == h[:, None])
        & (h[:, None] != 0)
        & (table.expire_at[sidx] > now)
    )
    found = match.any(axis=1)
    slot = bucket * ways + jnp.argmax(match, axis=1)
    return found, jnp.where(found, slot, 0)


probe_batch = jax.jit(probe_batch_impl, static_argnames=("ways",))


# Row order of gather_rows' packed int output.  remaining_f travels as a
# second int64 array of binary64 BITS, which the host views as float64
# (f64bits.from_bits): the seams above it keep their float64 column.
GATHER_ROW_FIELDS = (
    "found", "kind", "algo", "limit", "duration", "remaining",
    "t0", "status", "burst", "expire_at",
)


def gather_rows_impl(
    table: SlotTable,
    h: jax.Array,
    now: jax.Array,
    ways: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """Columnar row read-back: probe + gather every CacheItem field for a
    hash batch as (int64[10, B] in GATHER_ROW_FIELDS order,
    int64[B] remaining_f bits) — two buffers fetched in one sync where
    per-field reads would cost a transfer each.  The compiled fast lane's
    Store.on_change capture (the batched analog of the read the reference
    does inline at algorithms.go:154-158); h=0 lanes read as not-found."""
    found, slot = probe_batch_impl(table, h, now, ways=ways)

    def g(arr):
        return arr[slot]

    packed = jnp.stack([
        found.astype(jnp.int64),
        g(table.kind).astype(jnp.int64),
        g(table.algo).astype(jnp.int64),
        g(table.limit),
        g(table.duration),
        g(table.remaining),
        g(table.t0),
        g(table.status).astype(jnp.int64),
        g(table.burst),
        g(table.expire_at),
    ])
    return packed, g(table.remaining_f)


gather_rows = jax.jit(gather_rows_impl, static_argnames=("ways",))


class CachedRows(NamedTuple):
    """A batch of owner-broadcast statuses (UpdatePeerGlobal rows,
    peers.proto:52-56): key fingerprint + the authoritative RateLimitResp."""

    key_hash: jax.Array   # int64[B]; 0 = inactive lane
    algo: jax.Array       # int32[B]
    limit: jax.Array      # int64[B]
    remaining: jax.Array  # int64[B]
    status: jax.Array     # int32[B]
    reset_time: jax.Array  # int64[B]


def store_cached_rows_impl(
    table: SlotTable,
    rows: CachedRows,
    now: jax.Array,
    ways: int = 8,
) -> SlotTable:
    """Broadcast-receive: upsert KIND_CACHED_RESP rows into a cache table.

    The device analog of UpdatePeerGlobals -> AddCacheItem
    (gubernator.go:464-479): the stored item IS the response, with
    ExpireAt = status.ResetTime.  Keys must be unique within the batch.
    """
    now = jnp.asarray(now, dtype=jnp.int64)
    active = rows.key_hash != 0
    _, persist, _, _, slot32 = locate_slots(
        table, rows.key_hash, active, now, ways
    )
    z = jnp.zeros_like(rows.key_hash)
    return write_rows(table, persist & active, slot32, SlotTable(
        key=rows.key_hash,
        algo=rows.algo,
        kind=jnp.full_like(rows.algo, KIND_CACHED_RESP),
        limit=rows.limit,
        duration=z,
        remaining=rows.remaining,
        remaining_f=z,
        t0=z,
        status=rows.status,
        burst=z,
        expire_at=rows.reset_time,
        touched=jnp.full_like(rows.key_hash, now),
    ))


store_cached_rows = jax.jit(
    store_cached_rows_impl, static_argnames=("ways",), donate_argnums=(0,)
)


def apply_batch_packed_impl(
    table: SlotTable,
    batch: DeviceBatchJ,
    now: jax.Array,
    ways: int = 8,
) -> Tuple[SlotTable, jax.Array]:
    """apply_batch with the response packed into ONE int64[9, B] array —
    a single device->host transfer per step instead of nine.

    Rows: status, limit, remaining, reset_time, persisted, found, stored,
    cached, stored_status.
    """
    new_table, r = apply_batch_impl(table, batch, now, ways)
    packed = jnp.stack([
        r.status.astype(jnp.int64),
        r.limit.astype(jnp.int64),
        r.remaining.astype(jnp.int64),
        r.reset_time.astype(jnp.int64),
        r.persisted.astype(jnp.int64),
        r.found.astype(jnp.int64),
        r.stored.astype(jnp.int64),
        r.cached.astype(jnp.int64),
        r.stored_status.astype(jnp.int64),
    ])
    return new_table, packed


def unpack_batch_q(q) -> DeviceBatchJ:
    """Device-side unpack of ONE int64[12, B] request array (row order =
    DeviceBatch field order; bools/int32s travel widened as int64)."""
    return DeviceBatchJ(
        key_hash=q[0], hits=q[1], limit=q[2], duration=q[3],
        algo=q[4].astype(jnp.int32), burst=q[5],
        reset_remaining=q[6].astype(bool), is_greg=q[7].astype(bool),
        greg_expire=q[8], greg_duration=q[9],
        active=q[10].astype(bool), use_cached=q[11].astype(bool),
    )


def apply_batch_packed_q_impl(
    table: SlotTable,
    q: jax.Array,
    now: jax.Array,
    ways: int = 8,
) -> Tuple[SlotTable, jax.Array]:
    """Fully packed step: ONE int64[12, B] host->device transfer in, ONE
    int64[9, B] transfer out — the single-device analog of the mesh
    path's pack_grid_batch."""
    return apply_batch_packed_impl(table, unpack_batch_q(q), now, ways)


apply_batch_packed_q = jax.jit(
    apply_batch_packed_q_impl, static_argnames=("ways",), donate_argnums=(0,)
)
