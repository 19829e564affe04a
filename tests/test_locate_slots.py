"""`locate_slots` against the three-round definition it replaced.

Until PR 32 the insert claim of `ops/step.py` was written out as
INSERT_ROUNDS rounds of sort + searchsorted + argsort over B x W int64
candidate slots.  That text is frozen below, verbatim, as the reference:
it IS the definition of which lane wins which victim slot, and which
victim is evicted decides later answers.  The served `locate_slots`
resolves the same claims bucket by bucket on lanes sorted once, and is
held bit-identical to the reference on (found, persist, slot, slot_safe)
for every case here.
"""
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from gubernator_tpu.ops import step as sp
from gubernator_tpu.ops.state import SlotTable, table_from_host

try:
    from jax import shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map

INSERT_ROUNDS = 3
NOW = 1_700_000_000_000
DAY = 86_400_000


# ---- the reference: ops/step.py at PR 31, verbatim --------------------------

def _first_claim(tgt: jax.Array, attempt: jax.Array) -> jax.Array:
    """Of all lanes attempting the same target slot, the lowest lane wins.

    Sort-based, O(B log B), no table-sized temporaries.  Returns bool[B]
    winner mask.
    """
    sent = jnp.int64(1) << 62
    v = jnp.where(attempt, tgt, sent)
    order = jnp.argsort(v, stable=True)  # stable: equal slots -> lane order
    v_sorted = v[order]
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), v_sorted[1:] != v_sorted[:-1]]
    )
    win_sorted = first & (v_sorted != sent)
    return jnp.zeros(tgt.shape, dtype=bool).at[order].set(win_sorted)


def _member_of(sorted_vals: jax.Array, queries: jax.Array) -> jax.Array:
    """Membership of `queries` in `sorted_vals` via searchsorted."""
    pos = jnp.searchsorted(sorted_vals, queries)
    pos = jnp.clip(pos, 0, sorted_vals.shape[0] - 1)
    return sorted_vals[pos] == queries


def locate_slots(
    table: SlotTable,
    h: jax.Array,
    active: jax.Array,
    now: jax.Array,
    ways: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Set-associative lookup + insert-victim claim for a batch of keys.

    Returns (found, persist, slot, slot_safe): `found` lanes matched a live
    slot at `slot`; `persist & ~found` lanes won an insert victim at `slot`;
    `~persist` lanes could not claim a slot (transient).  Each active key
    must appear at most once in the batch (the packer's contract).
    """
    S = table.key.shape[0]
    nb = S // ways
    if nb & (nb - 1):
        raise ValueError(f"num_buckets ({nb}) must be a power of two")
    B = h.shape[0]

    bucket = (h.astype(jnp.uint64) & jnp.uint64(nb - 1)).astype(jnp.int64)
    sidx = bucket[:, None] * ways + jnp.arange(ways, dtype=jnp.int64)[None, :]

    cand_key = table.key[sidx]          # [B, W]
    cand_expire = table.expire_at[sidx]
    cand_touched = table.touched[sidx]

    keymatch = (cand_key == h[:, None]) & active[:, None]
    live = cand_expire > now
    match = keymatch & live
    found = match.any(axis=1)
    match_slot = bucket * ways + jnp.argmax(match, axis=1)

    # ---- victim scoring for inserts ------------------------------------
    # Preference: my own expired slot > empty > other expired > oldest touch.
    empty = cand_key == 0
    mine_stale = keymatch & ~live
    klass = jnp.where(
        mine_stale, 0, jnp.where(empty, 1, jnp.where(~live, 2, 3))
    ).astype(jnp.int64)
    vscore = klass * (jnp.int64(1) << 48) + cand_touched  # touched < 2^48 ms

    need = active & ~found
    inf = jnp.int64(1) << 62
    insert_slot = jnp.full((B,), -1, dtype=jnp.int64)
    won = jnp.zeros((B,), dtype=bool)

    for _ in range(INSERT_ROUNDS):
        # Slots reserved this batch: live matches + already-won inserts.
        reserved = jnp.sort(
            jnp.concatenate(
                [
                    jnp.where(found, match_slot, -1),
                    jnp.where(won, insert_slot, -1),
                ]
            )
        )
        blocked = _member_of(reserved, sidx.ravel()).reshape(sidx.shape)
        vs = jnp.where(blocked, inf, vscore)
        vmin = jnp.min(vs, axis=1)
        vslot = bucket * ways + jnp.argmin(vs, axis=1)
        attempt = need & ~won & (vmin < inf)
        win_now = _first_claim(vslot, attempt)
        insert_slot = jnp.where(win_now, vslot, insert_slot)
        won = won | win_now

    persist = found | won
    slot = jnp.where(found, match_slot, jnp.where(won, insert_slot, 0))
    slot_safe = jnp.clip(slot, 0, S - 1)
    return found, persist, slot, slot_safe


# ---- the comparison ---------------------------------------------------------

ref_locate_slots = jax.jit(locate_slots, static_argnames=("ways",))
new_locate_slots = jax.jit(sp.locate_slots, static_argnames=("ways",))
FIELDS = ("found", "persist", "slot", "slot_safe")


def _agree(table: SlotTable, h, active, ways: int, now: int = NOW):
    """Run both, hold them bit-identical, return the new one's result."""
    h = jnp.asarray(h, dtype=jnp.int64)
    active = jnp.asarray(active, dtype=bool)
    now = jnp.int64(now)
    want = ref_locate_slots(table, h, active, now, ways=ways)
    *got, slot32 = new_locate_slots(table, h, active, now, ways=ways)
    for name, w, g in zip(FIELDS, want, got, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    # The write-back's 32-bit spelling of `slot` (ops/state.py write_rows).
    assert slot32.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(slot32), np.asarray(got[2]))
    return tuple(np.asarray(g) for g in got)


def _empty_arrays(num_slots: int) -> dict:
    arrs = {f: np.zeros(num_slots, dtype=np.int64) for f in SlotTable._fields}
    for f in ("algo", "kind", "status"):
        arrs[f] = np.zeros(num_slots, dtype=np.int32)
    arrs["remaining_f"] = np.zeros(num_slots, dtype=np.float64)
    return arrs


def _key_in(bucket, nb: int, salt):
    """A nonzero fingerprint whose bucket (`h & (nb - 1)`) is `bucket`;
    distinct salts give distinct keys."""
    return ((np.asarray(salt, dtype=np.int64) + 1) * nb
            + np.asarray(bucket, dtype=np.int64))


def _elsewhere(B: int, nb: int, bkt: int):
    """B new keys spread over every bucket but `bkt`."""
    return _key_in((bkt + 1 + np.arange(B) % (nb - 1)) % nb, nb, np.arange(B))


def _put(arrs: dict, slot: int, key: int, expire_at: int, touched: int):
    arrs["key"][slot] = key
    arrs["expire_at"][slot] = expire_at
    arrs["touched"][slot] = touched


def _random_case(seed: int, B: int, ways: int, nb: int, fill: float,
                 expired: float, resident: float, inactive: float,
                 crowd: int):
    """A seeded table and batch.  `fill` of the slots hold a row,
    `expired` of those are past their expiry; `resident` of the lanes ask
    for a key the table holds, the rest for new keys, `crowd` > 0 packs
    the new keys into that many buckets; `inactive` of the lanes are off
    and carry whatever hash (a resident key, an active lane's, 0)."""
    rng = np.random.default_rng(seed)
    S = nb * ways
    arrs = _empty_arrays(S)
    held = rng.random(S) < fill
    slots = np.flatnonzero(held)
    arrs["key"][slots] = _key_in(slots // ways, nb, rng.permutation(S)[:len(slots)])
    stale = rng.random(len(slots)) < expired
    arrs["expire_at"][slots] = np.where(
        stale, NOW - rng.integers(0, DAY, len(slots)),
        NOW + rng.integers(1, DAY, len(slots)))
    # A few equal stamps, so that ties between ways are exercised.
    arrs["touched"][slots] = NOW - rng.integers(0, 16, len(slots)) * 1000

    n_res = min(int(B * resident), len(slots))
    res_keys = arrs["key"][rng.choice(slots, n_res, replace=False)] \
        if n_res else np.zeros(0, dtype=np.int64)
    n_new = B - n_res
    new_bkts = (rng.integers(0, nb, n_new) if crowd <= 0 else
                rng.choice(rng.integers(0, nb, crowd), n_new))
    new_keys = _key_in(new_bkts, nb, S + np.arange(n_new))
    h = rng.permutation(np.concatenate([res_keys, new_keys]))
    active = rng.random(B) >= inactive
    off = np.flatnonzero(~active)
    h[off] = rng.choice(
        np.concatenate([h, arrs["key"][slots][:8], [0]]), len(off))
    return table_from_host(arrs), h, active


@pytest.mark.parametrize("ways", [4, 8])
@pytest.mark.parametrize("B", [64, 128, 4096])
@pytest.mark.parametrize("seed,fill,expired,resident,inactive,crowd", [
    (1, 0.0, 0.0, 0.0, 0.0, 0),      # cold table
    (2, 0.5, 0.2, 0.5, 0.1, 0),
    (3, 0.9, 0.1, 0.98, 0.0, 0),     # the benchmark's shape: 2 % miss
    (4, 0.9, 0.5, 0.3, 0.2, 3),      # the misses crowd three buckets
    (5, 1.0, 0.0, 0.1, 0.05, 0),     # every bucket full of live rows
])
def test_random_tables_match_the_three_round_reference(
        B, ways, seed, fill, expired, resident, inactive, crowd):
    # B / 4 buckets: four lanes a bucket on average, so every round of
    # every case has contenders; 8 B buckets: conflicts are rare, as served.
    for nb in (max(B // 4, 1), 8 * B):
        table, h, active = _random_case(
            seed * 1000 + B + ways, B, ways, nb, fill, expired, resident,
            inactive, crowd)
        found, persist, _, _ = _agree(table, h, active, ways)
        assert not (found & ~active).any()
        assert not (persist & ~active).any()


@pytest.mark.parametrize("ways", [4, 8])
@pytest.mark.parametrize("B", [64, 128])
def test_empty_table_every_lane_a_miss(B, ways):
    nb = 4 * B
    h = _key_in(np.arange(B) % nb, nb, np.arange(B))   # a bucket each
    found, persist, slot, _ = _agree(
        table_from_host(_empty_arrays(nb * ways)), h, np.ones(B, bool), ways)
    assert not found.any() and persist.all()
    np.testing.assert_array_equal(slot, np.arange(B) * ways)  # way 0 of each


@pytest.mark.parametrize("ways", [4, 8])
@pytest.mark.parametrize("B", [64, 128])
def test_every_lane_in_one_bucket_goes_transient_after_three_rounds(B, ways):
    nb, bkt = 16, 5
    h = _key_in(np.full(B, bkt), nb, np.arange(B))
    found, persist, slot, _ = _agree(
        table_from_host(_empty_arrays(nb * ways)), h, np.ones(B, bool), ways)
    assert not found.any()
    # One winner a round: lanes 0, 1, 2 take ways 0, 1, 2; a fourth
    # contender of a bucket is transient.
    assert persist.tolist() == [True] * INSERT_ROUNDS + [False] * (
        B - INSERT_ROUNDS)
    assert slot[:INSERT_ROUNDS].tolist() == [
        bkt * ways + r for r in range(INSERT_ROUNDS)]
    assert not slot[INSERT_ROUNDS:].any()


@pytest.mark.parametrize("ways", [4, 8])
def test_found_lane_blocks_its_slot_for_need_lanes_of_its_bucket(ways):
    B, nb, bkt = 64, 32, 9
    arrs = _empty_arrays(nb * ways)
    # The resident row sits in way 0 and is the OLDEST: an insert that did
    # not see it reserved would take its slot first among live rows; the
    # other ways hold newer live rows but for the last, which is empty.
    res = _key_in(bkt, nb, 1000)
    for way in range(ways - 1):
        _put(arrs, bkt * ways + way, _key_in(bkt, nb, 1000 + way),
             NOW + DAY, NOW - DAY + way)
    h = _elsewhere(B, nb, bkt)
    h[7] = res                                   # found, lane 7
    h[3], h[20], h[40] = (_key_in(bkt, nb, 2000 + i) for i in range(3))
    found, persist, slot, _ = _agree(
        table_from_host(arrs), h, np.ones(B, bool), ways)
    assert found[7] and slot[7] == bkt * ways
    # Lane 3 takes the empty way; lanes 20 and 40 evict the oldest rows
    # that are NOT the found one: ways 1 and 2.
    assert persist[[3, 20, 40]].all() and not found[[3, 20, 40]].any()
    assert slot[[3, 20, 40]].tolist() == [
        bkt * ways + ways - 1, bkt * ways + 1, bkt * ways + 2]


@pytest.mark.parametrize("ways", [4, 8])
def test_two_need_lanes_of_a_bucket_preferring_different_ways(ways):
    B, nb, bkt = 64, 32, 17
    arrs = _empty_arrays(nb * ways)
    mine = _key_in(bkt, nb, 555)
    # Way 2 holds lane 30's own row, expired (class 0: it goes back there);
    # way 1 holds another key's expired row (class 2); the rest are empty
    # (class 1), so the other lane prefers way 0.
    _put(arrs, bkt * ways + 2, mine, NOW - 5, NOW - 1000)
    _put(arrs, bkt * ways + 1, _key_in(bkt, nb, 556), NOW - 5, NOW - 2000)
    h = _elsewhere(B, nb, bkt)
    h[12] = _key_in(bkt, nb, 777)
    h[30] = mine
    found, persist, slot, _ = _agree(
        table_from_host(arrs), h, np.ones(B, bool), ways)
    assert not found[[12, 30]].any() and persist[[12, 30]].all()
    assert slot[30] == bkt * ways + 2 and slot[12] == bkt * ways + 0


@pytest.mark.parametrize("ways", [4, 8])
def test_full_bucket_of_live_rows_evicts_the_oldest_touched(ways):
    B, nb, bkt = 64, 32, 3
    arrs = _empty_arrays(nb * ways)
    oldest = ways - 2
    for way in range(ways):
        _put(arrs, bkt * ways + way, _key_in(bkt, nb, 100 + way), NOW + DAY,
             NOW - (10_000 if way == oldest else 10 * way))
    h = _elsewhere(B, nb, bkt)
    h[50] = _key_in(bkt, nb, 999)
    found, persist, slot, _ = _agree(
        table_from_host(arrs), h, np.ones(B, bool), ways)
    assert not found[50] and persist[50]
    assert slot[50] == bkt * ways + oldest


@pytest.mark.parametrize("ways", [4, 8])
@pytest.mark.parametrize("B", [64, 128])
def test_inactive_lanes_with_arbitrary_hashes_change_nothing(B, ways):
    nb = B
    table, h, active = _random_case(77 + B + ways, B, ways, nb, 0.7, 0.3,
                                    0.5, 0.0, 4)
    rng = np.random.default_rng(B * ways)
    active = rng.random(B) < 0.5
    base = _agree(table, h, active, ways)
    junk = h.copy()
    # Resident keys, other lanes' keys, zero, the int64 corners.
    junk[~active] = rng.choice(
        np.concatenate([h, [0, -1, np.iinfo(np.int64).min,
                            np.iinfo(np.int64).max]]), int((~active).sum()))
    again = _agree(table, junk, active, ways)
    for name, a, b in zip(FIELDS, base, again):
        np.testing.assert_array_equal(a[active], b[active], name)
    assert not again[0][~active].any() and not again[1][~active].any()


@pytest.mark.parametrize("ways", [4, 8])
@pytest.mark.parametrize("B", [64, 128])
def test_now_past_every_expiry(B, ways):
    table, h, active = _random_case(91 + B + ways, B, ways, B // 2, 0.8, 0.2,
                                    0.6, 0.1, 0)
    found, persist, _, _ = _agree(table, h, active, ways, now=NOW + 2 * DAY)
    assert not found.any()          # an expired row does not match
    assert persist.any()            # a resident key goes back to its own slot


def test_under_shard_map_on_four_virtual_devices():
    n, B, ways, nb = 4, 128, 8, 32
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    cases = [_random_case(300 + i, B, ways, nb, 0.6, 0.3, 0.4, 0.1, 5)
             for i in range(n)]
    one = [_agree(t, h, a, ways) for t, h, a in cases]

    mesh = Mesh(np.asarray(jax.devices()[:n]), ("shard",))
    table = jax.tree_util.tree_map(
        lambda *leaves: jnp.concatenate(leaves), *(c[0] for c in cases))
    h = jnp.asarray(np.stack([c[1] for c in cases]))
    active = jnp.asarray(np.stack([c[2] for c in cases]))

    def per_shard(table, h, active):
        out = sp.locate_slots(table, h[0], active[0], jnp.int64(NOW), ways)
        return tuple(o[None] for o in out)

    got = jax.jit(shard_map(
        per_shard, mesh=mesh, in_specs=(P("shard"), P("shard"), P("shard")),
        out_specs=P("shard"),
    ))(table, h, active)
    for name, g, want in zip(FIELDS, got[:4], zip(*one), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.stack(want), name)
