"""The key universe of a deployment, made from the seed: key names, their
fingerprints, the rows a preloaded table holds and where each row sits.

The harness (bench/run.py) builds it from (config, seed) to know what the
reference must answer, and hands the daemon launcher (bench/serve.py) the
resident rows to install.  Nothing here imports JAX.

Placement is the set-associative arithmetic of ops/step.py's lookup
(copied, not imported, so a later change to the program cannot move it):
a fingerprint's bucket is ``h & (buckets_per_shard - 1)``, its shard
``(h >> 32) % shards``, a bucket holds ``ways`` rows.  Of the universe
keys that fall in one bucket the first ``ways`` in universe order are
resident; the rest were "evicted" before the run starts (chip_smoke.py's
expected_resident counts the same thing).

A cluster (a configuration that says `"peers": N`, bench/lib/ring.py) is N
daemons of one shard each: a key's daemon is its owner on the consistent-
hash ring, its bucket there ``h & (buckets_per_daemon - 1)``, and
``gbucket = owner * buckets_per_daemon + bucket``; `slots` counts every
daemon's.  With `peers` absent every array is what it was before the form
existed, bit for bit.

A table that does not hold its universe (a configuration that says
`"residency": "tiered"`, docs/tiering.md) starts with BOTH tiers filled,
each key in exactly one (`tier_split`): the table with `table_rows` rows,
its low-water mark, taken way by way — every bucket's first arrival, then
every bucket's second, ... and of the last way that fits only the buckets
of the lowest numbers — so that a row's slot is still ``bucket * ways +
way``; every other key of the universe is a row of the cold store.  With
`residency` absent nothing here is reached.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from lib import ring as ring_mod
from lib.spec import clock_moves

NAMES = 16                      # rate-limit names (tenants) keys spread over
NAME_LEN = KEY_LEN = 9          # b"bench_tNN", b"kXXXXXXXX"
ALGO_TOKEN, ALGO_LEAKY = 0, 1
BEHAVIOR_GLOBAL = 2             # core/types.Behavior.GLOBAL
_HASH_CHUNK = 1 << 19

_HEX4 = np.frombuffer(
    b"".join(b"%04x" % v for v in range(1 << 16)), dtype=np.uint8
).reshape(1 << 16, 4)
_NAME_BLOB = np.frombuffer(
    b"".join(b"bench_t%02d" % t for t in range(NAMES)), dtype=np.uint8
).reshape(NAMES, NAME_LEN)


def derive_seed(seed: int, path: str) -> int:
    """A stable 64-bit sub-seed for `path` (loadgen/schedule.py's idiom):
    the same in every process, unlike salted hash()."""
    digest = hashlib.sha512(f"{seed}/{path}".encode()).digest()
    return int.from_bytes(digest[:8], "big")


def key_ids(index: np.ndarray, seed: int) -> np.ndarray:
    """Distinct 32-bit key ids for universe positions: an odd multiplier
    is a bijection mod 2^32, the seed moves the whole set."""
    mix = np.uint64(derive_seed(seed, "universe") & 0xFFFFFFFF)
    i = index.astype(np.uint64)
    i *= np.uint64(2654435761)
    i += mix
    i &= np.uint64(0xFFFFFFFF)
    return i


def key_bytes(ids: np.ndarray):
    """(names uint8[n, 9], keys uint8[n, 9]) for key ids."""
    n = len(ids)
    keys = np.empty((n, KEY_LEN), dtype=np.uint8)
    keys[:, 0] = ord("k")
    keys[:, 1:5] = _HEX4[(ids >> np.uint64(16)).astype(np.intp)]
    keys[:, 5:9] = _HEX4[(ids & np.uint64(0xFFFF)).astype(np.intp)]
    names = _NAME_BLOB[(ids % np.uint64(NAMES)).astype(np.intp)]
    return names, keys


def key_string(key_id: int) -> str:
    """The reference's hash_key() of one key id: name + "_" + unique_key."""
    return "bench_t%02d_k%08x" % (key_id % NAMES, key_id)


def encode_rpc(native, ids, hits, limit, duration, algo, behavior) -> bytes:
    """Wire bytes of one GetRateLimits RPC from columns."""
    names, keys = key_bytes(ids)
    off = np.arange(len(ids) + 1, dtype=np.int64) * KEY_LEN
    return native.encode_req_columns(
        names.tobytes(), off, keys.tobytes(), off,
        hits, limit, duration, algo, behavior,
        np.zeros(len(ids), dtype=np.int64),
    )


def global_bucket(fp: np.ndarray, slots: int, ways: int, shards: int,
                  owner: Optional[np.ndarray] = None,
                  peers: int = 1) -> np.ndarray:
    """shard * buckets_per_shard + bucket of int64 fingerprints: the
    placement arithmetic of the module docstring, in one place.  In a
    cluster `owner` is each key's daemon on the ring and takes the shard's
    place (a daemon of a cluster has one shard)."""
    nb_local = slots // (shards * peers) // ways
    u = fp.view(np.uint64)
    part = ((u >> np.uint64(32)) % np.uint64(shards) if owner is None
            else owner.astype(np.uint64))
    gb = part * np.uint64(nb_local)
    gb += u & np.uint64(nb_local - 1)
    return gb.astype(np.int64)


def key_algorithms(ids: np.ndarray, cfg: dict) -> np.ndarray:
    """0 token / 1 leaky of key ids: bit 4 of the id, unless the
    configuration's `universe` group says `"algorithm": "token"` for
    every key."""
    if cfg.get("algorithm", "mixed") == "token":
        return np.full(len(ids), ALGO_TOKEN, dtype=np.uint64)
    return (ids >> np.uint64(4)) & np.uint64(1)


def fingerprints(native, ids: np.ndarray) -> np.ndarray:
    """int64 table fingerprints of key ids, through the program's own wire
    parser so they are the server's to the bit."""
    one = np.ones(len(ids), dtype=np.int64)
    payload = encode_rpc(native, ids, one, one, one, one * 0, one * 0)
    return native.parse_reqs(payload).hash


@dataclass
class Universe:
    """Everything the harness knows about the keys before the first RPC.
    Narrow dtypes on purpose: on the chip's host every fresh page costs,
    and ten million keys are many pages."""

    ids: np.ndarray          # uint32[U] key ids
    fp: np.ndarray           # int64[U] fingerprints
    algo: np.ndarray         # uint8[U] 0 token / 1 leaky
    is_global: np.ndarray    # bool[U]
    remaining0: np.ndarray   # uint8[U] preloaded remaining (uint32 past 256)
    gbucket: np.ndarray      # int32[U] shard * buckets_per_shard + bucket
    way: np.ndarray          # int8[U] rank among the bucket's arrivals
    resident: np.ndarray     # bool[U] preloaded (way < ways, not GLOBAL)
    crowded: np.ndarray      # bool[U] bucket has more arrivals than ways
    slot_order: np.ndarray   # uint32[U] universe indexes in table-slot order
    limit: int
    global_limit: int
    duration_ms: int
    slots: int               # of the whole deployment: every daemon's
    ways: int
    shards: int
    moving: bool = False     # the configuration's windows elapse in a run
    ring: Optional[ring_mod.Ring] = None    # a cluster's; None: one daemon
    owner: Optional[np.ndarray] = None      # uint8[U] daemon, with a ring
    # A tiered universe's (None: the table holds it): bool[U], the key
    # starts as a row of the cold store; `resident | cold` is every key.
    cold: Optional[np.ndarray] = None
    promote_deadline_ms: float = 0.0

    @property
    def n_resident(self) -> int:
        return int(self.resident.sum())

    @property
    def peers(self) -> int:
        return 1 if self.ring is None else self.ring.n

    def resident_by_daemon(self) -> np.ndarray:
        """Preloaded rows a daemon: int64[peers]."""
        if self.ring is None:
            return np.array([self.n_resident], dtype=np.int64)
        return np.bincount(self.owner[self.resident], minlength=self.peers)

    def bucket_of(self, fp: np.ndarray) -> np.ndarray:
        """Global buckets of keys in or outside the universe, from their
        fingerprints (a cluster's ring hashes a key to its fingerprint)."""
        owner = (None if self.ring is None
                 else self.ring.owner(fp.view(np.uint64)))
        return global_bucket(fp, self.slots, self.ways, self.shards, owner,
                             self.peers)


def tier_split(way: np.ndarray, gbucket: np.ndarray, ways: int,
               table_rows: int) -> np.ndarray:
    """bool[U]: the keys a tiered universe's table starts with — at most
    `table_rows` of those whose rank among their bucket's arrivals is under
    `ways`, the lower ranks first and, of the last rank that fits, the
    buckets of the lowest numbers (a bucket has one key of a rank, so the
    cut is exact).  Every other key starts in the cold store."""
    fits = way < ways
    level = np.bincount(way[fits], minlength=ways).cumsum()
    if level[-1] <= table_rows:
        return fits
    last = int(np.searchsorted(level, table_rows, side="right"))
    room = table_rows - (int(level[last - 1]) if last else 0)
    resident = way < last
    if room:
        at = np.flatnonzero(way == last)
        cut = np.partition(gbucket[at], room - 1)[room - 1]
        resident[at[gbucket[at] <= cut]] = True
    return resident


def build_universe(native, cfg: dict, seed: int, slots: int,
                   ring=None, table_rows: Optional[int] = None) -> Universe:
    """`cfg` is the configuration file's "universe" group; `slots` the
    table size in use (the CPU dry run overrides it), ONE daemon's where
    `ring` (bench/lib/ring.py) says there are several; `table_rows` the
    rows a tiered universe's table starts with (None: the table holds the
    universe).  Works in chunks so that temporaries are reused instead of
    freshly mapped."""
    n = int(cfg["keys"])
    ways, shards = int(cfg["ways"]), int(cfg["shards"])
    n_global = int(cfg.get("global_keys", 0))
    below = int(cfg["preload_remaining_below"])
    peers = 1 if ring is None else ring.n
    if ring is not None and ring.hash != "xx":
        raise ValueError("only an xx ring hashes a key to its fingerprint")
    slots *= peers
    nb_local = slots // (shards * peers) // ways
    if nb_local & (nb_local - 1):
        raise ValueError(f"buckets per shard ({nb_local}) not a power of two")
    if n >= 1 << 31 or slots // ways >= 1 << 31 or ways > 100 or below > 1 << 32:
        raise ValueError("universe outside the packed layout's range")
    ids = np.empty(n, dtype=np.uint32)
    fp = np.empty(n, dtype=np.int64)
    algo = np.empty(n, dtype=np.uint8)
    remaining0 = np.empty(n, dtype=np.uint8 if below <= 256 else np.uint32)
    gbucket = np.empty(n, dtype=np.int32)
    owner = None if ring is None else np.empty(n, dtype=np.uint8)
    # (bucket, index) packed into one word: a plain in-place sort ranks
    # every key among its bucket's arrivals, in universe order.
    packed = np.empty(n, dtype=np.uint64)
    for lo in range(0, n, _HASH_CHUNK):
        hi = min(n, lo + _HASH_CHUNK)
        index = np.arange(lo, hi, dtype=np.uint64)
        c_ids = key_ids(index, seed)
        c_fp = fingerprints(native, c_ids)
        c_owner = None
        if ring is not None:
            c_owner = owner[lo:hi] = ring.owner(c_fp.view(np.uint64))
        c_gb = global_bucket(
            c_fp, slots, ways, shards, c_owner, peers
        ).view(np.uint64)
        ids[lo:hi] = c_ids
        fp[lo:hi] = c_fp
        algo[lo:hi] = key_algorithms(c_ids, cfg)
        remaining0[lo:hi] = (c_ids >> np.uint64(8)) % np.uint64(below)
        gbucket[lo:hi] = c_gb
        c_gb <<= np.uint64(32)
        c_gb |= index
        packed[lo:hi] = c_gb
    is_global = np.zeros(n, dtype=bool)
    is_global[:n_global] = True
    algo[:n_global] = ALGO_TOKEN
    packed.sort()
    order = packed.astype(np.uint32)        # the low word: universe index
    packed >>= np.uint64(32)                # now the sorted buckets
    is_start = np.empty(n, dtype=bool)
    is_start[0] = True
    np.not_equal(packed[1:], packed[:-1], out=is_start[1:])
    del packed
    pos = np.arange(n, dtype=np.int32)
    run_start = np.where(is_start, pos, np.int32(0))
    np.maximum.accumulate(run_start, out=run_start)
    np.subtract(pos, run_start, out=run_start)
    way = np.empty(n, dtype=np.int8)
    way[order] = np.minimum(run_start, 127)
    del pos, run_start, is_start
    counts = np.minimum(
        np.bincount(gbucket, minlength=nb_local * shards * peers), 127
    ).astype(np.int8)
    crowded = counts[gbucket] > ways
    # GLOBAL keys are created by traffic (the engine syncs them into the
    # owner's bucket), so their way is reserved but left empty at preload.
    resident = (way < ways) & ~is_global
    cold = None
    if table_rows is not None:
        resident = tier_split(way, gbucket, ways, table_rows)
        cold = ~resident
    return Universe(
        ids=ids, fp=fp, algo=algo, is_global=is_global,
        remaining0=remaining0, gbucket=gbucket, way=way, resident=resident,
        crowded=crowded, slot_order=order, limit=int(cfg["limit"]),
        global_limit=int(cfg.get("global_limit", 0)),
        duration_ms=int(cfg["duration_ms"]), slots=slots, ways=ways,
        shards=shards, moving=clock_moves(cfg), ring=ring, owner=owner,
        cold=cold, promote_deadline_ms=float(cfg.get("promote_deadline_ms", 0)),
    )


def handoff(u: Universe, seed: int, n_probe: int,
            daemon: int = 0) -> Dict[str, np.ndarray]:
    """What the daemon launcher needs to preload, so that it does not
    build the universe a second time: the resident rows in slot order, and
    a seeded sample of keys with whether the table must find each.  In a
    cluster, one file a daemon: the rows it owns, at its own slots, and the
    WHOLE sample — a key another daemon owns must not be found here."""
    sel = u.slot_order[u.resident[u.slot_order]]
    rng = np.random.default_rng(derive_seed(seed, "probe"))
    idx = rng.choice(len(u.fp), size=min(n_probe, len(u.fp)), replace=False)
    slot = u.gbucket[sel].astype(np.int64) * u.ways + u.way[sel]
    found = u.resident[idx]
    slots = u.slots // u.peers
    if u.ring is not None:
        mine = u.owner[sel] == daemon
        sel, slot = sel[mine], slot[mine] - daemon * slots
        found = found & (u.owner[idx] == daemon)
    # Rows whose window is a second are expired before they are probed:
    # the launcher then asks the lookup at the stamp they were made at.
    at_stamp = {"probe_at_preload_stamp": np.ones(1, bool)} if u.moving else {}
    if u.cold is not None:
        # The cold store's rows, and of the sample whether IT must hold each.
        csel = np.flatnonzero(u.cold)
        at_stamp.update(
            cold_fp=u.fp[csel], cold_algo=u.algo[csel],
            cold_remaining0=u.remaining0[csel], probe_cold=u.cold[idx],
        )
    return {
        **at_stamp,
        "slot": slot,
        "fp": u.fp[sel],
        "algo": u.algo[sel],
        "remaining0": u.remaining0[sel],
        "probe_fp": u.fp[idx],
        "probe_found": found,
        "geometry": np.array(
            [slots, u.limit, u.duration_ms], dtype=np.int64
        ),
    }


def table_arrays(h: Dict[str, np.ndarray], t0_ms: int) -> Dict[str, np.ndarray]:
    """Host columns of the preloaded table (ops/state.SlotTable's fields,
    the layout runtime/checkpoint.py restores through _install_table):
    every resident key as a bucket row created at `t0_ms` with
    `remaining0` tokens left.  `h` is handoff()'s dict; its rows are in
    slot order, so every column is written front to back."""
    s, limit, duration_ms = (int(x) for x in h["geometry"])
    slot = h["slot"]
    leaky = h["algo"] == ALGO_LEAKY

    def col(dtype, values):
        a = np.zeros(s, dtype=dtype)
        a[slot] = values
        return a

    rem = h["remaining0"]
    return {
        "key": col(np.int64, h["fp"]),
        "algo": col(np.int32, h["algo"]),
        "kind": np.zeros(s, dtype=np.int32),          # KIND_BUCKET
        "limit": col(np.int64, limit),
        "duration": col(np.int64, duration_ms),
        "remaining": col(np.int64, np.where(leaky, 0, rem)),
        "remaining_f": col(np.float64, np.where(leaky, rem, 0)),
        "t0": col(np.int64, t0_ms),
        "status": np.zeros(s, dtype=np.int32),        # UNDER_LIMIT
        "burst": col(np.int64, limit),
        "expire_at": col(np.int64, t0_ms + duration_ms),
        "touched": col(np.int64, t0_ms),
    }


# The cold store's columns (runtime/coldtier.py COLD_FIELDS, the MigratedRows
# layout; copied, not imported): what `ColdTier.restore` takes.
COLD_FIELDS = ("key_hash", "algo", "limit", "duration", "remaining",
               "remaining_f", "t0", "status", "burst", "expire_at")


def cold_arrays(h: Dict[str, np.ndarray], t0_ms: int) -> Dict[str, np.ndarray]:
    """Columns of the preloaded cold store: every key the table does not
    start with as the row `table_arrays` would have given it — created at
    `t0_ms` with `remaining0` tokens left — in the layout a checkpoint's
    `coldtier` entry has."""
    _, limit, duration_ms = (int(x) for x in h["geometry"])
    n = len(h["cold_fp"])
    leaky = h["cold_algo"] == ALGO_LEAKY
    rem = h["cold_remaining0"]

    def full(dtype, value):
        return np.full(n, value, dtype=dtype)

    return {
        "key_hash": h["cold_fp"].astype(np.int64),
        "algo": h["cold_algo"].astype(np.int32),
        "limit": full(np.int64, limit),
        "duration": full(np.int64, duration_ms),
        "remaining": np.where(leaky, 0, rem).astype(np.int64),
        "remaining_f": np.where(leaky, rem, 0).astype(np.float64),
        "t0": full(np.int64, t0_ms),
        "status": full(np.int32, 0),                  # UNDER_LIMIT
        "burst": full(np.int64, limit),
        "expire_at": full(np.int64, t0_ms + duration_ms),
    }


def tiered_row_bounds(u: Universe, extra_fp: np.ndarray,
                      unmerged: int) -> tuple:
    """(least, most) rows the two tiers hold TOGETHER — the table's
    occupancy plus the cold store's residents — once a tiered universe has
    been served.

    Most.  Every key of the universe starts as one row, in one tier, and a
    key outside it (`extra_fp`, the wire check's) gains one.  A demotion
    moves a row, a merge folds two into one; the one thing that makes a
    second row of a key is a fresh start while its cold row waits for its
    merge, and the configuration's `promote_deadline_ms` ends that: of the
    keys answered less than the deadline before the count was taken,
    `unmerged`, each may still have both.  So most = keys + extra +
    `unmerged`.

    Least.  A cold row goes only into the table (a promote) or with a drop
    that is counted and held to 0; a table row goes only to the cold store
    (a demote) or under the step's own eviction, which takes a row only of
    a bucket whose `ways` ways are all live — and leaves that bucket
    `ways` rows.  So the rows of a bucket's keys, both tiers together, never
    fall under min(arrivals, ways): the same arithmetic `expected_occupancy`
    states for a table alone, here over every key, since every key is
    preloaded."""
    least = _rows_a_bucket_holds(u, u.gbucket, extra_fp)
    return least, len(u.fp) + len(np.unique(extra_fp)) + int(unmerged)


def _rows_a_bucket_holds(u: Universe, gbucket: np.ndarray,
                         extra_fp: np.ndarray) -> int:
    """min(distinct arrivals, ways), summed over the buckets: the arrivals
    at `gbucket` and the keys outside the universe with the fingerprints
    `extra_fp`."""
    nb = u.slots // u.ways
    counts = np.bincount(gbucket, minlength=nb)
    if len(extra_fp):
        counts = counts + np.bincount(
            u.bucket_of(np.unique(extra_fp)), minlength=nb,
        )
    return int(np.minimum(counts, u.ways).sum())


def expected_occupancy(u: Universe, touched_index: np.ndarray,
                       extra_fp: np.ndarray) -> int:
    """Rows the table holds once the keys at `touched_index` (and the
    fingerprints `extra_fp` of keys outside the universe) have been
    served: a bucket keeps min(distinct arrivals, ways).  In a cluster the
    sum over daemons, each bucket on its key's ring owner: a row written on
    a daemon that does not own its key is a row beyond it.  That holds where
    windows elapse inside a run too: the table counts a row whether or not
    its window has elapsed (ops/state.py `occupancy`: key != 0), nothing
    clears one, a returning key takes its own expired row first, and an
    arrival takes an empty way before another key's expired row
    (ops/step.py `locate_slots`: "my own expired slot > empty > other
    expired > oldest touch"), so no row goes while its bucket has room."""
    present = u.resident.copy()
    present[touched_index] = True
    return _rows_a_bucket_holds(u, u.gbucket[present], extra_fp)
