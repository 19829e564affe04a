"""The slot table: device-resident rate-limit state.

Replaces the reference's per-worker LRU dict (lrucache.go:32-223) with a
fixed-size, W-way set-associative table held as a struct-of-arrays on device.
A key's 64-bit fingerprint selects one bucket of `ways` slots; lookups gather
all ways and match on the stored fingerprint; inserts pick a victim way
(empty > expired > least-recently-touched).  Eviction is therefore
bucket-local pseudo-LRU rather than the reference's global list LRU
(lrucache.go:147-158) — the acceptable-loss design (architecture.md:5-11)
makes early eviction safe: it can only briefly over-admit.

All arrays share leading dimension S = num_slots so the table shards cleanly
along axis 0 over a device mesh (see gubernator_tpu.parallel.mesh) — every
PHYSICAL column does: an int64 field is two uint32[S] columns on the device
("Physical layout" below); the schema, the kernels' arithmetic and the host
format stay int64.
"""
from __future__ import annotations

import sys
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops import f64bits

# Slot `kind` values.
KIND_BUCKET = 0
KIND_CACHED_RESP = 1  # non-owner's cached GLOBAL broadcast (gubernator.go:464-479)


# --------------------------------------------------------------------------
# Physical layout.  The LOGICAL schema is twelve fields, eight of them
# int64; the PHYSICAL table holds every int64 field as a low and a high
# uint32[S] column.  A TPU has no 64-bit registers: XLA rewrites each
# s64 array into a pair of 32-bit arrays, which is free inside a program
# but not at its boundary, where a 64-bit parameter is split
# (X64SplitLow/High) and a 64-bit result rebuilt (X64Combine) over ALL S
# rows every time the program runs — 27 table-length custom calls a step
# at nine 64-bit columns, to touch a few hundred rows.  Stored as halves,
# the table crosses the boundary as it is, and 64-bit values exist only
# on the gathered [B] / [B, ways] lanes.
#
# `gather64` / `scatter64` (and the host twins `_halves_to_host` /
# `_host_to_halves`) are the only code that knows the layout; `Col64`
# gives them the array spelling (`col[idx]`, `col.at[tgt].set(v)`) so a
# kernel reads the same for a split column as for an int32 one.
# `remaining_f`, the leaky bucket's float64 remaining, is a Col64 too:
# the 64 BITS of the binary64 (ops/f64bits.py), which the step computes
# on in integer words — a TPU has no float64, and XLA's stand-in (a pair
# of float32s) is neither IEEE nor able to hold a binary64.  No column
# and no value of a float dtype is left on the device; the host formats
# keep float64 and cross by view (`table_to_host` / `table_from_host`).
# --------------------------------------------------------------------------

if sys.byteorder != "little":  # the host twins view int64 as (lo, hi) words
    raise ImportError("ops/state.py splits int64 by view: little-endian only")


def gather64(col: "Col64", idx) -> jax.Array:
    """Read both halves at `idx` and combine them: int64[idx.shape]."""
    lo = col.lo[idx].astype(jnp.uint64)
    hi = col.hi[idx].astype(jnp.uint64)
    return ((hi << jnp.uint64(32)) | lo).astype(jnp.int64)


def scatter64(col: "Col64", tgt, v, **kw) -> "Col64":
    """Split int64 `v` and write both halves at `tgt` (lossless: the low
    word by mask, the high word by logical shift).  `kw` is `.at[].set`'s
    own (`mode`, `indices_are_sorted`, ...) and goes to both halves'
    scatters."""
    u = jnp.asarray(v).astype(jnp.int64).astype(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    return Col64(col.lo.at[tgt].set(lo, **kw), col.hi.at[tgt].set(hi, **kw))


def _halves_to_host(lo, hi) -> np.ndarray:
    out = np.empty(np.shape(lo), dtype=np.int64)
    pairs = out.view(np.uint32).reshape(out.shape + (2,))
    pairs[..., 0] = np.asarray(lo)
    pairs[..., 1] = np.asarray(hi)
    return out


def _host_to_halves(arr) -> Tuple[np.ndarray, np.ndarray]:
    a = np.ascontiguousarray(arr, dtype=np.int64)
    pairs = a.view(np.uint32).reshape(a.shape + (2,))
    return pairs[..., 0], pairs[..., 1]


class _Col64At:
    __slots__ = ("_col", "_idx")

    def __init__(self, col: "Col64", idx=None) -> None:
        self._col, self._idx = col, idx

    def __getitem__(self, idx) -> "_Col64At":
        return _Col64At(self._col, idx)

    def set(self, v, **kw) -> "Col64":
        return scatter64(self._col, self._idx, v, **kw)


@jax.tree_util.register_pytree_with_keys_class
class Col64:
    """One logical int64[S] column held as two uint32[S] leaves."""

    __slots__ = ("lo", "hi")
    dtype = np.dtype(np.int64)  # the LOGICAL dtype (`val.astype(col.dtype)`)

    def __init__(self, lo, hi) -> None:
        self.lo, self.hi = lo, hi

    def tree_flatten_with_keys(self):
        k = jax.tree_util.GetAttrKey
        return ((k("lo"), self.lo), (k("hi"), self.hi)), None

    @classmethod
    def tree_unflatten(cls, _aux, children) -> "Col64":
        return cls(*children)

    @property
    def shape(self):
        return self.lo.shape

    def __getitem__(self, idx) -> jax.Array:
        return gather64(self, idx)

    @property
    def at(self) -> _Col64At:
        return _Col64At(self)

    def occupied(self) -> jax.Array:
        """bool[S]: logical value != 0, without leaving 32 bits."""
        return (self.lo | self.hi) != 0

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        out = _halves_to_host(self.lo, self.hi)
        return out if dtype is None else out.astype(dtype)


class SlotTable(NamedTuple):
    """Struct-of-arrays; one row = one CacheItem (cache.go:30-42) flattened
    together with its TokenBucketItem / LeakyBucketItem payload
    (store.go:29-43).  Comments give the LOGICAL type; the int64 fields
    are Col64 (two uint32[S] leaves each)."""

    key: Col64             # int64[S]; xxhash64 fingerprint; 0 = empty
    algo: jax.Array        # int32[S]; Algorithm enum
    kind: jax.Array        # int32[S]; KIND_*
    limit: Col64           # int64[S]
    duration: Col64        # int64[S]
    remaining: Col64       # int64[S]; token-bucket remaining / cached-resp remaining
    remaining_f: Col64     # float64[S] as its bits; leaky fractional remaining
    t0: Col64              # int64[S]; token CreatedAt / leaky UpdatedAt
    status: jax.Array      # int32[S]; token-bucket sticky status / cached-resp status
    burst: Col64           # int64[S]
    expire_at: Col64       # int64[S]; unix ms (CacheItem.ExpireAt)
    touched: Col64         # int64[S]; last-access stamp for victim choice

    @property
    def num_slots(self) -> int:
        return self.key.shape[0]

    def occupancy(self) -> jax.Array:
        return jnp.sum(self.key.occupied())


# The logical int64 fields, stored as Col64.
INT64_FIELDS = (
    "key", "limit", "duration", "remaining", "t0", "burst", "expire_at",
    "touched",
)
# Stored as Col64 too, holding a float64's bits: float64 on the host.
F64_FIELDS = ("remaining_f",)
COL64_FIELDS = INT64_FIELDS + F64_FIELDS


def init_table(num_slots: int) -> SlotTable:
    """All-empty table.  num_slots must keep num_slots/ways a power of two
    (enforced at step-build time) so bucket selection is a mask, not a mod."""
    def zeros(dtype):
        return jnp.zeros((num_slots,), dtype=dtype)

    def column(f):
        if f in COL64_FIELDS:
            return Col64(zeros(jnp.uint32), zeros(jnp.uint32))
        return zeros(jnp.int32)

    return SlotTable(**{f: column(f) for f in SlotTable._fields})


# The TPU's compiler has two scatters (PERF.md section 5.3, measured on a
# v5e).  Told nothing it walks the UPDATES, one row after another: 91 ns a
# row at 4096 lanes, 110 at 128, whatever the column's length.  Told the
# targets are sorted it streams the COLUMN through fast memory at the
# memory system's pace: 0.215 ms a 2^24-row column, 0.04 ms a 2^22-row
# one, whatever the number of updates.  The second is cheaper while a
# lane has fewer than some 7,000 rows to itself: at 4096 lanes into 2^24
# rows (4,096 each) 1.7 times cheaper, at 128 lanes (131,072 each) 14
# times dearer.
SORTED_ROWS_A_LANE = 8192


def sorts_write_back(num_slots: int, lanes: int) -> bool:
    """Whether `write_rows` sorts its targets, and says so, when `lanes`
    rows go into columns of `num_slots` rows — static shapes of the
    program being traced, whose ratio is what the compiler's own choice
    between its two scatters follows too."""
    return num_slots <= SORTED_ROWS_A_LANE * lanes


def write_order(do_write, slot, num_slots: int, sort: bool, vals=()):
    """(int32[B] scatter targets, `vals`) as `write_rows` hands them to
    the scatter.  Lane i goes to `slot[i]` where `do_write[i]` and past
    the table's end where not (`mode="drop"` drops it).  With `sort` the
    targets come back in order, and the [B] vectors `vals` in the same
    order: they ride along as the sort's operands."""
    if slot.dtype != jnp.int32:
        raise TypeError(f"write targets are 32-bit, got {slot.dtype}")
    if num_slots >= 1 << 31:
        raise ValueError(f"num_slots ({num_slots}) must fit 31 bits")
    tgt = jnp.where(do_write, slot, num_slots)
    if sort:
        tgt, *vals = jax.lax.sort((tgt, *vals), num_keys=1)
    return tgt, list(vals)


def write_rows(table: SlotTable, do_write, slot, rows: SlotTable) -> SlotTable:
    """The kernels' write-back: lane i's row — `rows` holds the twelve
    LOGICAL value vectors, [B] each — replaces the table's row `slot[i]`
    (int32[B], from `locate_slots`) where `do_write[i]`.  The written
    slots are pairwise different by the kernels' contract (a key once a
    batch, a claimed way to one lane); were they not, the last lane
    would win, as it always did — XLA is promised nothing about that.
    The one place that hands rows to XLA's scatter: every physical
    column is scattered at the same targets, sorted and said to be where
    `sorts_write_back` finds it pays at these shapes."""
    S = table.num_slots
    sort = sorts_write_back(S, slot.shape[0])
    tgt, vals = write_order(
        do_write, slot, S, sort,
        [v.astype(a.dtype) for a, v in zip(table, rows)],
    )
    return SlotTable(*(
        a.at[tgt].set(v, mode="drop", indices_are_sorted=sort)
        for a, v in zip(table, vals)
    ))


def _floats_back(cols: dict) -> dict:
    """Host columns as fetched -> the logical format: a float64 field's
    bits viewed as float64 again."""
    for f in F64_FIELDS:
        cols[f] = f64bits.from_bits(cols[f])
    return cols


def table_to_host(table: SlotTable) -> dict:
    """DMA the table down as numpy for snapshot/Loader-save
    (the device analog of WorkerPool.Store streaming cache.Each(),
    workers.go:467-530): the twelve LOGICAL arrays, int64 fields
    reassembled on the host and `remaining_f` a float64 array again —
    the checkpoint format."""
    return _floats_back({
        f: np.asarray(getattr(table, f)) for f in table._fields
    })


def table_from_host(arrs: dict) -> SlotTable:
    """The inverse: twelve logical numpy arrays in, physical table out
    (the halves are host views of the int64 arrays, and of the float64
    array's bits)."""
    cols = {f: jnp.asarray(arrs[f]) for f in SlotTable._fields
            if f not in COL64_FIELDS}
    for f in COL64_FIELDS:
        a = f64bits.to_bits(arrs[f]) if f in F64_FIELDS else arrs[f]
        lo, hi = _host_to_halves(a)
        cols[f] = Col64(jnp.asarray(lo), jnp.asarray(hi))
    return SlotTable(**cols)


def read_rows(table: SlotTable, idx) -> dict:
    """Logical rows at `idx` (an index array or a slice) as numpy, one
    entry per field — the host-side point read."""
    return _floats_back({
        f: np.asarray(getattr(table, f)[idx]) for f in table._fields
    })


# --------------------------------------------------------------------------
# Live slot migration (docs/resharding.md): row extract/inject kernels.
#
# A peer join/leave remaps the consistent hash; the rows whose arcs moved
# must LEAVE the old owner's table (or it would keep serving a key it no
# longer owns — an orphaned slot) and LAND in the new owner's, preserving
# remaining/t0/expire_at exactly so the limit window survives the remap.
# Extract is gather+clear fused in ONE donated kernel so the critical
# section under backend._lock is a single dispatch: between the gather
# and the clear nothing else can touch the table, making the handoff's
# "counters conserved" claim a per-row atomicity fact, not a protocol
# hope.  Inject is upsert-IF-ABSENT: a late or replayed Migrate chunk
# can never clobber state the receiver already created (the receiver's
# row is newer by construction — it was written after cutover or by a
# racing authoritative check).
# --------------------------------------------------------------------------


def migrate_extract_impl(
    table: SlotTable,
    h: jax.Array,       # int64[B] key fingerprints; 0 = inactive lane
    now: jax.Array,
    ways: int = 8,
):
    """Probe `h`, gather each found row's fields, and CLEAR the matched
    slots (key=0, expire_at=0) in the same step.  Returns
    (new_table, packed int64[10, B] in ops.step.GATHER_ROW_FIELDS order,
    int64[B] remaining_f bits)."""
    S = table.key.shape[0]
    nb = S // ways
    now = jnp.asarray(now, dtype=jnp.int64)
    bucket = (
        h.astype(jnp.uint64) & jnp.uint64(nb - 1)
    ).astype(jnp.int64)
    sidx = (
        bucket[:, None] * ways
        + jnp.arange(ways, dtype=jnp.int64)[None, :]
    )
    match = (
        (table.key[sidx] == h[:, None])
        & (h[:, None] != 0)
        & (table.expire_at[sidx] > now)
    )
    found = match.any(axis=1)
    slot = bucket * ways + jnp.argmax(match, axis=1)
    src = jnp.where(found, slot, 0)

    def g(arr):
        return arr[src]

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
    rf = g(table.remaining_f)
    # Clear: drop the fingerprint AND the expiry so the slot reads as
    # empty to every probe/locate and as a first-choice victim.
    tgt = jnp.where(found, slot, S)
    new_table = table._replace(
        key=table.key.at[tgt].set(0, mode="drop"),
        expire_at=table.expire_at.at[tgt].set(0, mode="drop"),
    )
    return new_table, packed, rf


migrate_extract = jax.jit(
    migrate_extract_impl, static_argnames=("ways",), donate_argnums=(0,)
)


def migrate_inject_impl(
    table: SlotTable,
    rows,  # ops.step.BucketRows; key_hash 0 = inactive lane
    now: jax.Array,
    ways: int = 8,
):
    """Upsert migrated rows where the key is absent; where it is
    already resident, MERGE by subtracting the migrated row's consumed
    budget (limit - remaining, clamped at 0) from the resident row —
    discovery gives no ordering guarantees, so a receiver may have
    served a moved key (fresh row) before its migrated row arrives, and
    keeping either row alone would lose the other's admissions.  The
    merge conserves: total consumption is the sum of both rows',
    clamped at the limit — it can only LOWER remaining, never inflate
    admission.  Returns (new_table, bool[B] resident-before mask); the
    caller must guard against chunk replays (a re-delivered chunk would
    re-subtract) — runtime/reshard.py keys delivered fingerprints per
    handoff epoch."""
    # Runtime import: ops.step imports this module at load, so the
    # dependency must stay one-way at module scope.
    from gubernator_tpu.ops.step import load_rows_impl, probe_batch_impl

    now = jnp.asarray(now, dtype=jnp.int64)
    found, slot = probe_batch_impl(table, rows.key_hash, now, ways=ways)
    masked = rows._replace(
        key_hash=jnp.where(found, 0, rows.key_hash)
    )
    new_table = load_rows_impl(table, masked, now, ways=ways)
    # Merge-on-conflict: the probe's slots index rows load_rows did not
    # touch (conflict lanes were masked out of the upsert).
    active = rows.key_hash != 0
    conflict = found & active
    consumed_i = jnp.maximum(rows.limit - rows.remaining, 0)
    consumed_f = f64bits.max0(
        f64bits.sub(f64bits.from_i64(rows.limit), rows.remaining_f)
    )
    is_leaky = rows.algo == 1
    src = jnp.where(conflict, slot, 0)
    merged_rem = jnp.maximum(
        new_table.remaining[src]
        - jnp.where(is_leaky, 0, consumed_i),
        0,
    )
    merged_rf = f64bits.max0(f64bits.sub(
        new_table.remaining_f[src],
        jnp.where(is_leaky, consumed_f, f64bits.ZERO),
    ))
    S = table.key.shape[0]
    tgt = jnp.where(conflict, slot, S)
    new_table = new_table._replace(
        remaining=new_table.remaining.at[tgt].set(
            merged_rem, mode="drop"
        ),
        remaining_f=new_table.remaining_f.at[tgt].set(
            merged_rf, mode="drop"
        ),
    )
    return new_table, found


migrate_inject = jax.jit(
    migrate_inject_impl, static_argnames=("ways",), donate_argnums=(0,)
)


# --------------------------------------------------------------------------
# Tiered table (docs/tiering.md): the demotion kernel.
#
# HBM slot count — not kernel throughput — is the binding constraint at
# 100M+ keys, so the coldest residents spill to a host-RAM cold tier
# (runtime/coldtier.py) and promote back on access via migrate_inject.
# demote_extract is migrate_extract's per-row-atomicity shape pointed the
# other way: instead of probing caller-named fingerprints, the DEVICE
# picks the victims — the `batch` least-recently-touched live KIND_BUCKET
# rows (the per-slot `touched` word every step already maintains for
# bucket-local pseudo-LRU) — gathers their fields, and CLEARS the matched
# slots in the same donated dispatch.  Between the gather and the clear
# nothing else can touch the table, so a demoted row exists in exactly
# one tier at every instant the backend lock is free.  Shadow-plane rows
# (hot-mirror / lease-grant / degraded-shadow / handoff-shadow) carry
# derived-key fingerprints the HOST enumerates; they ride the `protect`
# list and are never demoted — their over-admission algebra assumes HBM
# residency.  KIND_CACHED_RESP rows (GLOBAL broadcast cache) are skipped
# device-side: they are a response cache, not bucket state.
# --------------------------------------------------------------------------

# Packed demote row layout: GATHER_ROW_FIELDS with the `found` word
# replaced by the row's own key fingerprint (the caller did not name the
# keys — the kernel picked them; 0 = inactive lane).  remaining_f rides
# alongside as int64[batch] bits, exactly the migrate_extract wire shape.
DEMOTE_ROW_FIELDS = (
    "key", "kind", "algo", "limit", "duration", "remaining", "t0",
    "status", "burst", "expire_at",
)


# What the cut-off stamp is estimated from, and the unit the selection
# counts in.  DEMOTE_SAMPLE slots — whole buckets, every way of one
# bucket in DEMOTE_SAMPLE // ways groups of buckets — are sorted a
# launch, where `top_k` sorted the table (51 ms a pass at 2^24 rows on
# the v5e, PERF.md section 5.12); a table no larger than the sample is
# its own sample, and the cut-off is then exact.  The rows under the
# cut-off are counted by blocks of DEMOTE_BLOCK slots, so the search
# for the r-th of them walks NBK = S / DEMOTE_BLOCK sums and one block,
# never the table.
DEMOTE_SAMPLE = 1 << 16
DEMOTE_BLOCK = 256


def _demote_cutoff(score, take, phase, ways: int):
    """The stamp under which about `take` eligible rows lie: the k-th
    smallest of a sample of `score` (ineligible slots carry int64 max).
    The sample is one bucket (all its ways) of every group of G
    consecutive buckets, `phase` saying which; k = take / G plus three
    standard deviations of that count, so that a launch finds its
    `take` rows under the cut-off; G = 1 makes it the exact k-th."""
    S = score.shape[0]
    nb = S // ways
    G = 1
    while nb // G > max(DEMOTE_SAMPLE // ways, 1) and nb % (2 * G) == 0:
        G *= 2
    if G > 1:
        sample = jax.lax.dynamic_index_in_dim(
            score.reshape(nb // G, G, ways), phase % G, axis=1,
            keepdims=False,
        ).reshape(-1)
        k0 = (take + G - 1) // G
        k = k0 + 3 * jnp.floor(
            jnp.sqrt(k0.astype(jnp.float32))
        ).astype(jnp.int32) + 2
    else:
        sample, k = score, take
    srt = jnp.sort(sample)
    return srt[jnp.clip(k, 1, sample.shape[0]) - 1]


def _block_of(cum, rank):
    """(the block holding the `rank`-th (1-based) entry of a class,
    entries of the class before that block), given `cum`, the running
    count of the class by block: a binary search over the NBK sums."""
    nbk = cum.shape[0]
    b = jnp.clip(
        jnp.searchsorted(cum, rank, side="left"), 0, nbk - 1
    ).astype(jnp.int32)
    return b, jnp.where(b > 0, cum[jnp.maximum(b - 1, 0)], 0)


def _nth_in_block(blocks, b, cls, nth):
    """Slot of the `nth` (1-based) entry of class `cls` inside block
    `b` of `blocks` (int8[NBK, BLK] class codes): the block's prefix
    counts by one [BLK, BLK] triangular product, the form the MXU takes
    (a count is at most BLK, exact in float32)."""
    blk = blocks.shape[1]
    bits = (blocks[b] == cls[:, None]).astype(jnp.bfloat16)
    tri = (
        jnp.arange(blk)[:, None] <= jnp.arange(blk)[None, :]
    ).astype(jnp.bfloat16)
    prefix = jnp.dot(bits, tri, preferred_element_type=jnp.float32)
    off = (prefix < nth[:, None].astype(jnp.float32)).sum(
        axis=1, dtype=jnp.int32
    )
    return b * blk + jnp.minimum(off, blk - 1)


def demote_extract_impl(
    table: SlotTable,
    protect: jax.Array,  # int64[M] shadow-plane fps; 0 = inactive
    now: jax.Array,
    take=None,           # int32: rows wanted, <= batch (None: batch)
    start=0,             # int32: the block the ties are taken from
    ways: int = 8,
    batch: int = 64,
):
    """Pick up to `take` of the coldest (least-recently-touched) live
    KIND_BUCKET residents not on the `protect` list, gather their rows,
    and CLEAR the matched slots (key=0, expire_at=0) in the same
    donated step.  Returns (new_table, packed int64[10, batch] in
    DEMOTE_ROW_FIELDS order, int64[batch] remaining_f bits); lanes past
    `take`, or past the eligible population, come back with key 0 and
    clear nothing.

    No sort of the table: ONE streaming pass picks the cut-off stamp
    (`_demote_cutoff`), a second marks every eligible row as under it
    or on it and counts both by block, and the `take` rows are found
    by rank (`_block_of`, `_nth_in_block`): every row UNDER the cut-off
    first — from block `start` on if there are more than `take` of
    them, which only an estimated cut-off allows — then rows ON it,
    from block `start` on, round the table.  Stamps tie by the million
    (a preload, a restore, a burst inside one millisecond), and a tie
    taken in slot order would empty the same buckets every tick: the
    caller moves `start` (runtime/coldtier.py)."""
    S = table.key.shape[0]
    blk = min(DEMOTE_BLOCK, S)
    nbk = S // blk
    now = jnp.asarray(now, dtype=jnp.int64)
    take = jnp.clip(
        jnp.asarray(batch if take is None else take, dtype=jnp.int32),
        0, batch,
    )
    start = jnp.asarray(start, dtype=jnp.int32) % nbk
    alive = table.key.occupied() & (table.expire_at[...] > now)
    eligible = alive & (table.kind == KIND_BUCKET)
    protected = (
        (table.key[...][:, None] == protect[None, :])
        & (protect[None, :] != 0)
    ).any(axis=1)
    eligible = eligible & ~protected
    # Victim score: last-touch stamp, ineligible slots pushed past any
    # real timestamp (the bucket-local pseudo-LRU word, applied
    # table-wide).
    big = jnp.iinfo(jnp.int64).max
    score = jnp.where(eligible, table.touched[...], big)
    cutoff = _demote_cutoff(score, take, start, ways)
    under = score < cutoff          # `big` for no eligible row at all
    on = eligible & (score == cutoff)
    blocks = (
        under.astype(jnp.int8) + 2 * on.astype(jnp.int8)
    ).reshape(nbk, blk)
    cum_u = jnp.cumsum((blocks == 1).sum(axis=1, dtype=jnp.int32))
    cum_o = jnp.cumsum((blocks == 2).sum(axis=1, dtype=jnp.int32))
    n_u, n_o = cum_u[-1], cum_o[-1]
    a = jnp.minimum(n_u, take)
    b = jnp.minimum(n_o, take - a)
    lane = jnp.arange(batch, dtype=jnp.int32)
    is_u = lane < a
    sel = lane < a + b
    # Ranks count from block `start`, and wrap round the table once.
    # (A count is at most S: said, for the range analysis.)
    prev = jnp.maximum(start - 1, 0)
    base = jnp.where(
        start > 0,
        jnp.clip(jnp.where(is_u, cum_u[prev], cum_o[prev]), 0, S), 0,
    )
    n = jnp.clip(jnp.where(is_u, n_u, n_o), 1, S)
    rank = base + jnp.where(is_u, lane, lane - a) + 1
    rank = jnp.clip(jnp.where(rank > n, rank - n, rank), 1, n)
    # The block by the class's own sums, then ONE look into the block.
    (b_u, before_u), (b_o, before_o) = (
        _block_of(cum_u, rank), _block_of(cum_o, rank)
    )
    idx = _nth_in_block(
        blocks, jnp.where(is_u, b_u, b_o),
        jnp.where(is_u, 1, 2).astype(jnp.int8),
        rank - jnp.where(is_u, before_u, before_o),
    )
    src = jnp.where(sel, idx, 0)

    def g(arr):
        return jnp.where(sel, arr[src], 0)

    packed = jnp.stack([
        g(table.key),
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
    rf = jnp.where(sel, table.remaining_f[src], f64bits.ZERO)
    # Clear exactly like migrate_extract: drop the fingerprint AND the
    # expiry so the slot reads empty to every probe and first-choice to
    # every victim claim.  The targets are pairwise different (a rank a
    # lane), sorted where `write_rows` would sort them.
    sort = sorts_write_back(S, batch)
    tgt = jnp.where(sel, idx, S)
    if sort:
        tgt = jnp.sort(tgt)
    new_table = table._replace(
        key=table.key.at[tgt].set(
            0, mode="drop", indices_are_sorted=sort
        ),
        expire_at=table.expire_at.at[tgt].set(
            0, mode="drop", indices_are_sorted=sort
        ),
    )
    return new_table, packed, rf


demote_extract = jax.jit(
    demote_extract_impl, static_argnames=("ways", "batch"),
    donate_argnums=(0,),
)


# --------------------------------------------------------------------------
# Gubstat (docs/observability.md): the one-pass state census.
#
# The table is the thing HBM capacity binds at scale, yet until now it
# exported a single occupancy scalar.  table_stats computes the whole
# introspection surface — occupancy, bucket-fill (probe/eviction
# pressure), slot-age and TTL-expiry histograms, the remaining-fraction
# distribution per algorithm, and a census of the reserved shadow-slot
# classes — in ONE non-donated device pass, so a periodic sampler can
# run it from an executor thread without ever touching the
# request path (the table is read, never written, and never donated).
# --------------------------------------------------------------------------

# The reserved derived-slot suffix classes, in census-row order.  The
# table stores only 64-bit fingerprints, so the HOST enumerates the
# derived keys it knows about (runtime/service.derived_slot_fps-style)
# and passes their fingerprints per class; the kernel counts which are
# live residents.  Order is a wire contract with runtime/gubstat.py.
SHADOW_PLANES = (
    ".hot-mirror", ".lease-grant", ".degraded-shadow",
    ".handoff-shadow", ".region-carve",
)

# Slot-age / TTL-remaining histogram edges (ms): <=1s, <=10s, <=1m,
# <=10m, <=1h, >1h.  Fixed at trace time — bins are part of the
# compiled shape, one compile per table geometry.
AGE_BIN_EDGES_MS = (1_000, 10_000, 60_000, 600_000, 3_600_000)
AGE_BINS = len(AGE_BIN_EDGES_MS) + 1

# Remaining-fraction bins over [0, 1] (bin k covers [k/8, (k+1)/8)).
FRAC_BINS = 8


class TableStats(NamedTuple):
    """One sample of the state plane (all int64 counts)."""

    occupancy: jax.Array           # int64[]: slots with a fingerprint
    live: jax.Array                # int64[]: resident AND unexpired
    expired_resident: jax.Array    # int64[]: resident but TTL-passed
    bucket_fill: jax.Array         # int64[ways+1]: buckets with k residents
    slot_age: jax.Array            # int64[AGE_BINS]: now - t0, live only
    ttl_remaining: jax.Array       # int64[AGE_BINS]: expire_at - now, live
    remaining_fraction: jax.Array  # int64[2, FRAC_BINS]: per algo enum
    shadow_slots: jax.Array        # int64[len(SHADOW_PLANES)]: live carves


def table_stats_impl(
    table: SlotTable,
    shadow_fps: jax.Array,  # int64[len(SHADOW_PLANES), M]; 0 = inactive
    now: jax.Array,
    ways: int = 8,
) -> TableStats:
    """The full census in one read-only pass; never mutates, never
    donates — safe to dispatch against the live serving table under the
    backend lock (or as a ring host job) at any time."""
    S = table.key.shape[0]
    nb = S // ways
    now = jnp.asarray(now, dtype=jnp.int64)
    resident = table.key.occupied()
    expire_at = table.expire_at[...]
    alive = resident & (expire_at > now)
    occupancy = jnp.sum(resident, dtype=jnp.int64)
    live = jnp.sum(alive, dtype=jnp.int64)

    # Bucket-fill: residents per bucket -> histogram over 0..ways.  A
    # right-shifted distribution is probe/eviction pressure the scalar
    # occupancy cannot show (hash skew fills some buckets at ways while
    # the aggregate looks healthy).
    per_bucket = jnp.sum(
        resident.reshape(nb, ways), axis=1, dtype=jnp.int64
    )
    fill_levels = jnp.arange(ways + 1, dtype=jnp.int64)
    bucket_fill = jnp.sum(
        per_bucket[:, None] == fill_levels[None, :], axis=0,
        dtype=jnp.int64,
    )

    # Slot-age / TTL-remaining histograms (live slots only).
    edges = jnp.asarray(AGE_BIN_EDGES_MS, dtype=jnp.int64)
    bins = jnp.arange(AGE_BINS, dtype=jnp.int64)

    def hist(values: jax.Array) -> jax.Array:
        idx = jnp.sum(
            values[:, None] > edges[None, :], axis=1, dtype=jnp.int64
        )
        onehot = (idx[:, None] == bins[None, :]) & alive[:, None]
        return jnp.sum(onehot, axis=0, dtype=jnp.int64)

    slot_age = hist(now - table.t0[...])
    ttl_remaining = hist(expire_at - now)

    # Remaining-fraction distribution per algorithm: the bin of
    # clip(remaining / max(limit, 1), 0, 1) * FRAC_BINS, the quotient in
    # binary64 as the host would compute it — on the bits (f64bits), so
    # there is no float here either and the bins are the same on every
    # backend.  A NaN (no stored row holds one) falls in bin 0.
    lim_f = f64bits.from_i64(jnp.maximum(table.limit[...], 1))
    rem_f = jnp.where(
        table.algo == 1,
        table.remaining_f[...],
        f64bits.from_i64(table.remaining[...]),
    )
    frac = f64bits.div(rem_f, lim_f)
    fbin = jnp.where(
        f64bits.ge_one(frac),
        FRAC_BINS - 1,
        f64bits.trunc_i64(f64bits.mul(
            f64bits.max0(frac), f64bits.const(float(FRAC_BINS))
        )),
    )
    fbins = jnp.arange(FRAC_BINS, dtype=jnp.int64)
    onehot = fbin[:, None] == fbins[None, :]
    rows = []
    for algo in (0, 1):
        mask = alive & (table.algo == algo)
        rows.append(
            jnp.sum(onehot & mask[:, None], axis=0, dtype=jnp.int64)
        )
    remaining_fraction = jnp.stack(rows)

    # Shadow-slot census: probe each host-enumerated derived-key
    # fingerprint (the migrate_extract bucket walk, read-only) and
    # count live residents per suffix class.
    fp = shadow_fps.reshape(-1)
    bucket = (
        fp.astype(jnp.uint64) & jnp.uint64(nb - 1)
    ).astype(jnp.int64)
    sidx = (
        bucket[:, None] * ways
        + jnp.arange(ways, dtype=jnp.int64)[None, :]
    )
    match = (
        (table.key[sidx] == fp[:, None])
        & (fp[:, None] != 0)
        & (table.expire_at[sidx] > now)
    )
    shadow_slots = jnp.sum(
        match.any(axis=1).reshape(shadow_fps.shape), axis=1,
        dtype=jnp.int64,
    )

    return TableStats(
        occupancy=occupancy,
        live=live,
        expired_resident=occupancy - live,
        bucket_fill=bucket_fill,
        slot_age=slot_age,
        ttl_remaining=ttl_remaining,
        remaining_fraction=remaining_fraction,
        shadow_slots=shadow_slots,
    )


table_stats = jax.jit(table_stats_impl, static_argnames=("ways",))
