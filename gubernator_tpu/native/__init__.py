"""ctypes bindings for the C++ host runtime (native/gubtpu.cpp).

Loads `libgubtpu.so` from this directory, building it with `make -C native`
when it is missing or was built from a different `native/gubtpu.cpp` than
the one in the checkout (the build stamps the source's SHA-256 into the
library; mtimes do not survive a copy).  All entry points have pure-Python
fallbacks (core/hashing.py, ops/batch.py) for library users without a
toolchain; `available()` reports which path is active, a failed build or
load is logged as an ERROR with the compiler's output, and `require()`
raises it — the daemon's `/debug/vars` `device.compiled_lane` and the chip
smoke treat a lane that did not load as a failure, not a slower daemon.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import re
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("gubernator_tpu.native")

_SO_PATH = os.path.join(os.path.dirname(__file__), "libgubtpu.so")
_NATIVE_DIR = os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, "native"
)
_SRC_PATH = os.path.join(_NATIVE_DIR, "gubtpu.cpp")
_STAMP_RE = re.compile(rb"GUBSRCHASH:([0-9a-f]{64})")
_lib: Optional[ctypes.CDLL] = None
# The same library bound a second time, its calls made with the GIL HELD
# (ctypes.PyDLL): for a pass shorter than taking the GIL back (HotkeyPass).
_held: Optional[ctypes.PyDLL] = None
_tried = False
_load_error = ""
_rebuilt = False
_load_lock = threading.Lock()


def source_hash() -> Optional[str]:
    """SHA-256 of native/gubtpu.cpp; None where the checkout carries no
    source (an installed package ships only the library)."""
    try:
        with open(_SRC_PATH, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except FileNotFoundError:
        return None


def _stamped_hash() -> Optional[str]:
    """The source hash stamped into the library on disk, read from its
    bytes — a stale library is never dlopen'ed (a second dlopen of the
    same path would hand back the stale mapping)."""
    try:
        with open(_SO_PATH, "rb") as f:
            m = _STAMP_RE.search(f.read())
    except FileNotFoundError:
        return None
    return m.group(1).decode() if m else None


def _build() -> None:
    """Compile via make; the Makefile writes to a temp path and renames so
    concurrent builders (other processes) never expose a half-written .so.
    -B: make keys on mtime, which says nothing after a copy."""
    try:
        subprocess.run(
            ["make", "-B", "-C", _NATIVE_DIR],
            check=True,
            capture_output=True,
            text=True,
            timeout=120,
        )
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed (rc={e.returncode}): "
            f"{(e.stderr or e.stdout or '').strip()[-2000:]}"
        ) from e
    except (subprocess.TimeoutExpired, OSError) as e:
        raise RuntimeError(f"native build failed: {e}") from e


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _held, _tried, _load_error, _rebuilt
    if _lib is not None or _tried:
        return _lib
    with _load_lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            want = source_hash()
            if want is not None and _stamped_hash() != want:
                _build()
                _rebuilt = True
            _held = _sign_hotkey(ctypes.PyDLL(_SO_PATH))
            _lib = _bind(ctypes.CDLL(_SO_PATH))
        except (RuntimeError, OSError, AttributeError) as e:
            _load_error = f"{type(e).__name__}: {e}"
            log.error(
                "native library unavailable, python lanes only: %s",
                _load_error,
            )
        return _lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.gub_xxh64_batch.argtypes = [
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    lib.gub_xxh64_batch.restype = None
    lib.gub_fnv_hashkey_batch.argtypes = [
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    lib.gub_fnv_hashkey_batch.restype = None
    lib.gub_assign_rounds.argtypes = [
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_void_p,  # shards (int32*) or None
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.gub_assign_rounds.restype = ctypes.c_int64
    # The drain's pack and unpack take addresses (pack_rounds /
    # gather_rounds check dtype and layout themselves: thirty ndpointer
    # conversions would cost a small drain more than the pass).
    lib.gub_pack_rounds.argtypes = (
        [ctypes.c_int64] + [ctypes.c_void_p] * 11
        + [ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
           ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
           ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
           ctypes.c_int64]
        + [ctypes.c_void_p] * 7
    )
    lib.gub_pack_rounds.restype = ctypes.c_int64
    lib.gub_gather_rounds.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.gub_gather_rounds.restype = None
    lib.gub_cold_probe.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.gub_cold_probe.restype = None
    lib.gub_cold_put.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.gub_cold_put.restype = ctypes.c_int64
    lib.gub_cold_pop.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gub_cold_pop.restype = ctypes.c_int64
    _sign_hotkey(lib)
    lib.gub_count_reqs.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.gub_count_reqs.restype = ctypes.c_int64
    lib.gub_parse_reqs2.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    lib.gub_parse_reqs2.restype = ctypes.c_int64
    lib.gub_parse_resps2.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    lib.gub_parse_resps2.restype = ctypes.c_int64
    lib.gub_serialize_resps2.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_char_p,   # meta_blob (may be None)
        ctypes.c_void_p,   # meta_off (int64* or None)
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ]
    lib.gub_serialize_resps2.restype = ctypes.c_int64
    lib.gub_serialize_reqs.argtypes = [
        ctypes.c_int64,
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ]
    lib.gub_serialize_reqs.restype = ctypes.c_int64
    return lib


def _sign_hotkey(lib):
    lib.gub_hotkey_observe.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_double, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.gub_hotkey_observe.restype = ctypes.c_int64
    return lib


def available() -> bool:
    return _load() is not None


def load_error() -> str:
    """Why the library is unavailable ("" when it loaded)."""
    _load()
    return _load_error


def require() -> None:
    """Raise unless the library loaded — for callers that must serve
    from the compiled lane (chip_smoke.py)."""
    if _load() is None:
        raise RuntimeError(f"native library unavailable: {_load_error}")


def rebuilt() -> bool:
    """True when this process had to build the library (missing, or
    stamped with another source hash) rather than verify it."""
    _load()
    return _rebuilt


def hash_keys(keys) -> np.ndarray:
    """XXH64 fingerprints (int64, 0 remapped to 1) of a list of strings."""
    lib = _load()
    n = len(keys)
    if lib is None:
        from gubernator_tpu.core.hashing import bulk_key_hash64

        return bulk_key_hash64(keys)
    encoded = [k.encode() for k in keys]
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    blob = b"".join(encoded)
    out = np.empty(n, dtype=np.int64)
    lib.gub_xxh64_batch(blob, offsets, n, out)
    return out


def fnv_hashkey_batch(
    payload: bytes, cols, variant: str
) -> Optional[np.ndarray]:
    """FNV-1/FNV-1a ring hashes of each parsed request's hash key
    (name + "_" + unique_key), int64 two's-complement view; 0 on errored
    lanes.  `cols` is a ParsedReqs (its msg_off/msg_len frame table is
    re-walked).  Keeps the columnar router serving under the reference's
    fnv placement rings (replicated_hash.go:33) in mixed clusters.
    Returns None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    out = np.empty(cols.n, dtype=np.int64)
    lib.gub_fnv_hashkey_batch(
        payload, cols.msg_off, cols.msg_len, cols.n,
        0 if variant == "fnv1" else 1, out,
    )
    return out


def assign_rounds(
    hashes: np.ndarray,
    shards: Optional[np.ndarray],
    n_shards: int,
    batch_size: int,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """(round, lane) per request + round count; hashes==0 lanes skipped.

    Native only — callers fall back to the ops/batch.py python loop when
    `available()` is False.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(hashes)
    out_round = np.empty(n, dtype=np.int32)
    out_lane = np.empty(n, dtype=np.int32)
    shard_ptr = (
        shards.ctypes.data_as(ctypes.c_void_p)
        if shards is not None
        else None
    )
    n_rounds = lib.gub_assign_rounds(
        np.ascontiguousarray(hashes, dtype=np.int64),
        shard_ptr,
        n,
        n_shards,
        batch_size,
        out_round,
        out_lane,
    )
    return out_round, out_lane, int(n_rounds)


def _column(a: Optional[np.ndarray], dtype, n: int):
    """A column of n `dtype` words as the native pass takes it: (the
    contiguous array, kept alive by the caller for the call; its
    address).  None: a column of zeros, (None, None)."""
    if a is None:
        return None, None
    a = np.ascontiguousarray(a, dtype=dtype)
    if a.shape != (n,):
        raise ValueError(f"native column: want [{n}], got {list(a.shape)}")
    return a, a.ctypes.data


_META_HEAD = 8  # gubtpu.cpp GUB_META_*


class PackedDrain:
    """What gub_pack_rounds made of one drain's columns.

    `rounds[r]` is round r as the device takes it: a fresh contiguous
    int64[12, t] (n_shards 1) or int64[12, n_shards, t], rows in
    DeviceBatch field order, t = `tiers[r]` the smallest compiled tier
    that holds its fullest shard, `lanes[r]` of its lanes active.  Check
    i went to (`rnd[i]`, shard of its hash, `lane[i]`), nowhere where
    `rnd[i]` < 0 (an errored check, or a cascade group's later
    occurrence).  `cap_ok[i]`: i is the last occurrence of its key.

    `groups` > 0: the drain holds that many duplicate groups the host
    cascade may take, of `occ_total` occurrences, `peeks` of them with
    hits 0; `cascades` says whether the rounds are the read lanes'
    (each group's first occurrence alone, hits 0) or every check's.
    `occ`, `firsts`, `order`, `bounds` are the groups, in ascending
    order of the signed hash: bool[n] on their occurrences, each group's
    first occurrence, and its occurrences in arrival order
    `order[bounds[g]:bounds[g + 1]]`."""

    __slots__ = (
        "rounds", "tiers", "lanes", "rnd", "lane", "cap_ok", "valid",
        "cascades", "groups", "occ_total", "peeks", "occ", "firsts",
        "order", "bounds", "n_shards", "shard_shift",
    )


def pack_rounds(
    hash: np.ndarray, hits: np.ndarray, limit: np.ndarray,
    duration: np.ndarray, algo: np.ndarray, burst: np.ndarray,
    behavior: Optional[np.ndarray], is_greg: Optional[np.ndarray],
    greg_expire: Optional[np.ndarray], greg_duration: Optional[np.ndarray],
    use_cached: Optional[np.ndarray], *, reset_bit: int, n_shards: int,
    shard_shift: int, batch_size: int, tiers: Sequence[int], mode: int,
    cap_ok: bool = False,
) -> PackedDrain:
    """A drain's columns to its packed rounds in ONE native call with the
    GIL released (gub_pack_rounds; the layouts are in its comment).
    `burst` is the wire's (0: the limit); `mode` 0 the plain assignment,
    1 the host cascade where it saves a launch, 2 wherever a group is
    eligible.  Native only."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(hash)
    i64 = np.int64
    tiers_a = np.array(tiers, dtype=np.int32)
    kept, addrs = zip(*(
        _column(a, dt, n) for a, dt in (
            (hash, i64), (hits, i64), (limit, i64), (duration, i64),
            (algo, np.int32), (burst, i64), (behavior, i64),
            (is_greg, np.bool_), (greg_expire, i64), (greg_duration, i64),
            (use_cached, np.bool_),
        )
    ))
    pos = np.empty((2, n), dtype=np.int32)
    outs = [pos[0], pos[1], np.empty(n, dtype=bool) if cap_ok else None]
    if mode:
        # occ, firsts, order, bounds
        outs += [
            np.empty(n, dtype=bool), np.empty(n // 2 + 1, dtype=i64),
            np.empty(n, dtype=i64), np.empty(n // 2 + 2, dtype=i64),
        ]
    else:
        outs += [None] * 4
    out_addrs = [None if a is None else a.ctypes.data for a in outs]
    # Two rounds at the tier the drain's checks would fill and two of the
    # smallest hold every drain but one that sends a key many times over
    # with no group eligible; that one is packed again at its size.
    fit = next((t for t in tiers if min(n, batch_size) <= t), tiers[-1])
    words = 12 * n_shards * 2 * (fit + tiers[0])
    max_rounds = 16
    while True:
        # Fresh every drain: the runtime may still read a round of the
        # drain before.
        arena = np.empty(words, dtype=i64)
        meta = np.empty(_META_HEAD + 3 * max_rounds, dtype=i64)
        again = lib.gub_pack_rounds(
            n, *addrs, reset_bit, n_shards, shard_shift, batch_size,
            tiers_a.ctypes.data, len(tiers_a), mode, arena.ctypes.data,
            words, meta.ctypes.data, max_rounds, *out_addrs,
        )
        m = meta.tolist()
        if not again:
            break
        max_rounds, words = m[0], m[1]
    del kept
    cap, occ, firsts, order, bounds = outs[2:]
    out = PackedDrain()
    n_rounds = m[0]
    out.tiers = m[_META_HEAD:_META_HEAD + 3 * n_rounds:3]
    out.lanes = m[_META_HEAD + 1:_META_HEAD + 3 * n_rounds:3]
    shape = (12, n_shards) if n_shards > 1 else (12,)
    out.rounds = [
        arena[off:off + 12 * n_shards * t].reshape(shape + (t,))
        for t, off in zip(
            out.tiers, m[_META_HEAD + 2:_META_HEAD + 3 * n_rounds:3]
        )
    ]
    out.rnd, out.lane = pos[0], pos[1]
    out.cap_ok = cap
    out.cascades = m[2] != 0
    out.groups, out.occ_total, out.peeks, out.valid = m[3], m[4], m[5], m[7]
    if out.groups:
        out.occ = occ
        out.firsts = firsts[:out.groups]
        out.order = order[:out.occ_total]
        out.bounds = bounds[:out.groups + 1]
    else:
        out.occ = out.firsts = out.order = out.bounds = None
    out.n_shards, out.shard_shift = n_shards, shard_shift
    return out


class GatheredDrain:
    """gub_gather_rounds' answer: `cols` int64[k, n], the first k response
    rows (status, limit, remaining, reset_time, persisted, found, stored,
    cached, stored_status) a check, zero where the check had no lane; and
    over the lanes read: `over_limit` (status 1), `not_persisted`,
    `cache_hits` (found), `lanes`."""

    __slots__ = ("cols", "over_limit", "not_persisted", "cache_hits",
                 "lanes")


def gather_rounds(
    packed: PackedDrain, hash: np.ndarray, resps: Sequence[np.ndarray],
    n_cols: int = 9,
) -> GatheredDrain:
    """The fetched responses of `packed`'s rounds (int64[9, t] a round, or
    [n_shards, 9, t]) back to the order of the drain's checks, in ONE
    native call with the GIL released.  Native only."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(hash)
    S = packed.n_shards
    if len(resps) != len(packed.tiers):
        raise ValueError("native gather: a response a round")
    keep = []
    for a, t in zip(resps, packed.tiers):
        want = (S, 9, t) if S > 1 else (9, t)
        if a.dtype != np.int64 or a.shape != want:
            raise TypeError(
                f"native gather: want int64{list(want)}, got "
                f"{a.dtype}{list(a.shape)}"
            )
        keep.append(np.ascontiguousarray(a))
    ptrs = np.array([a.ctypes.data for a in keep], dtype=np.int64)
    tiers = np.array(packed.tiers, dtype=np.int64)
    out = GatheredDrain()
    out.cols = np.empty((n_cols, n), dtype=np.int64)
    sums = np.empty(4, dtype=np.int64)
    hash, hash_addr = _column(hash, np.int64, n)
    lib.gub_gather_rounds(
        n, hash_addr, packed.rnd.ctypes.data,
        packed.lane.ctypes.data, S, packed.shard_shift,
        ptrs.ctypes.data, tiers.ctypes.data, n_cols,
        out.cols.ctypes.data, sums.ctypes.data,
    )
    (out.over_limit, out.not_persisted, out.cache_hits,
     out.lanes) = sums.tolist()
    return out


def cold_probe(fps: np.ndarray, rows: np.ndarray, state: np.ndarray,
               mask: int) -> np.ndarray:
    """int64[n]: the slot of each fingerprint in the cold store's table
    (`rows` int64[mask + 1, 10], `state` uint8[mask + 1]), -1 where it is
    not resident — ONE native pass with the GIL released, as `cold_put`
    and `cold_pop` are (runtime/coldtier.py keeps the numpy forms as
    their reference).  Native only."""
    lib = _load()
    n = len(fps)
    fps, fps_addr = _column(fps, np.int64, n)
    slot = np.empty(n, dtype=np.int64)
    lib.gub_cold_probe(
        n, fps_addr, rows.ctypes.data, state.ctypes.data, int(mask),
        slot.ctypes.data,
    )
    return slot


def cold_put(new: np.ndarray, rows: np.ndarray, state: np.ndarray,
             mask: int, room: int) -> Tuple[int, int, int, int]:
    """Rows `new` (int64[n, 10]) into the cold store's table, one after
    another — a resident key merges, another takes a free slot of its
    chain while `room` lasts: (rows resident after the call that came
    from the batch, merges, drops, tombstones reused).  Native only."""
    lib = _load()
    new = np.ascontiguousarray(new, dtype=np.int64)
    counts = np.zeros(3, dtype=np.int64)
    put = lib.gub_cold_put(
        len(new), new.ctypes.data, rows.ctypes.data, state.ctypes.data,
        int(mask), int(room), counts.ctypes.data,
    )
    merges, drops, reused = counts.tolist()
    return int(put), merges, drops, reused


def cold_pop(fps: np.ndarray, rows: np.ndarray, state: np.ndarray,
             mask: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """The resident rows of `fps` out of the cold store's table: (the
    rows int64[k, 10] in the order asked, which entries of `fps` they
    answer, the tombstones left behind).  Native only."""
    lib = _load()
    n = len(fps)
    fps, fps_addr = _column(fps, np.int64, n)
    out = np.empty((n, rows.shape[1]), dtype=np.int64)
    which = np.empty(n, dtype=np.int64)
    tombs = ctypes.c_int64(0)
    k = lib.gub_cold_pop(
        n, fps_addr, rows.ctypes.data, state.ctypes.data, int(mask),
        out.ctypes.data, which.ctypes.data, ctypes.byref(tombs),
    )
    return out[:k], which[:k], int(tombs.value)


# HotkeyPass keeps the GIL for a batch of at most this many fingerprints
# and releases it for a longer one.  The pass itself is microseconds at
# either size; what differs is who waits.  An RPC of 2 or 16 checks that
# gives the GIL away takes it back behind whichever pool thread woke
# meanwhile, and pays twice the pass's whole cost for it (0.030 against
# 0.059-0.075 ms a call on the chip's host).  An RPC of 750 checks holds
# the loop's GIL for a millisecond of Python either side of the pass, and
# the release inside it is the lanes' pool threads' turn: held, the batch
# cell answered 5-9 % FEWER checks than with the forty numpy calls,
# released 2-6 % more (PERF.md section 5.14).  128 is the step's
# smallest compiled width: a batch one small launch holds is a small one.
HOTKEY_HOLD_GIL_UP_TO = 128


class HotkeyPass:
    """gub_hotkey_observe bound to one sketch (`runtime/hotkey.py`
    HotKeyTracker.observe; `HostCMS.update` then `estimate` are its
    reference): `table` int64[depth, width], updated in place, `mults`
    uint64[depth] and `shift` the sketch's.  The addresses are taken once
    — on the event loop an RPC of two checks pays for every attribute
    read — so the sketch must keep `table` where it is (`HostCMS.clear`
    zeroes it in place).  One caller at a time (the tracker's lock): the
    candidates' buffer is reused.  The same symbol through two bindings,
    chosen by the batch's length (`HOTKEY_HOLD_GIL_UP_TO`).  Native
    only."""

    __slots__ = ("_held", "_freed", "_head", "_keep", "_out", "_out_addr")

    def __init__(self, table: np.ndarray, mults: np.ndarray,
                 shift: int) -> None:
        if _load() is None:
            raise RuntimeError("native library unavailable")
        depth, width = table.shape
        if (table.dtype != np.int64 or not table.flags.c_contiguous
                or mults.dtype != np.uint64 or mults.shape != (depth,)):
            raise TypeError("native hotkey pass: int64[depth, width] and "
                            "uint64[depth]")
        self._held = _held.gub_hotkey_observe
        self._freed = _lib.gub_hotkey_observe
        self._keep = (table, mults)
        self._head = (table.ctypes.data, depth, width, mults.ctypes.data,
                      int(shift))
        self._grow(1024)

    def _grow(self, n: int) -> None:
        self._out = np.empty(n, dtype=np.int64)
        self._out_addr = self._out.ctypes.data

    def __call__(self, hashes: np.ndarray, hits: np.ndarray, floor: float,
                 want: bool) -> Tuple[bool, Sequence[int]]:
        """One served batch: max(hits, 1) added at every non-zero
        fingerprint in each row, then — where `want` — every fingerprint
        whose estimate AFTER the whole batch's adds reaches `floor`, in
        batch order, repeats included.  Returns (whether any fingerprint
        was not zero, those candidates)."""
        n = len(hashes)
        hashes = np.ascontiguousarray(hashes, dtype=np.int64)
        hits = np.ascontiguousarray(hits, dtype=np.int64)
        if hits.shape != (n,) or hashes.ndim != 1:
            raise ValueError("native hotkey pass: two columns of one length")
        room = 0
        if want:
            if n > len(self._out):
                self._grow(n)
            room = n
        fn = self._held if n <= HOTKEY_HOLD_GIL_UP_TO else self._freed
        k = fn(
            *self._head, n, hashes.ctypes.data, hits.ctypes.data, floor,
            room, self._out_addr,
        )
        return k >= 0, (self._out[:k].tolist() if k > 0 else ())


class ParsedReqs:
    """Columnar view of a GetRateLimitsReq payload (gub_parse_reqs2)."""

    __slots__ = (
        "n", "hash", "err", "hits", "limit", "duration", "algo",
        "behavior", "burst", "msg_off", "msg_len", "name_hash",
    )

    def __init__(self, n: int) -> None:
        self.n = n
        self.hash = np.empty(n, dtype=np.int64)
        self.err = np.empty(n, dtype=np.int32)
        self.hits = np.empty(n, dtype=np.int64)
        self.limit = np.empty(n, dtype=np.int64)
        self.duration = np.empty(n, dtype=np.int64)
        self.algo = np.empty(n, dtype=np.int32)
        self.behavior = np.empty(n, dtype=np.int64)
        self.burst = np.empty(n, dtype=np.int64)
        # Each request's raw wire frame within the payload (tag + length
        # varint + body) — splice these to forward without re-encoding.
        self.msg_off = np.empty(n, dtype=np.int64)
        self.msg_len = np.empty(n, dtype=np.int64)
        # XXH64 of the name field alone (0 when empty) — the route key
        # for name-scoped tiers (sketch).
        self.name_hash = np.empty(n, dtype=np.int64)

    def subset(self, idx: np.ndarray) -> "ParsedReqs":
        """Row-subset view (fancy-indexed copies) for split routing."""
        out = ParsedReqs.__new__(ParsedReqs)
        out.n = len(idx)
        for f in ("hash", "err", "hits", "limit", "duration", "algo",
                  "behavior", "burst", "msg_off", "msg_len", "name_hash"):
            setattr(out, f, getattr(self, f)[idx])
        return out


def parse_reqs(payload: bytes) -> Optional[ParsedReqs]:
    """Parse raw GetRateLimitsReq / GetPeerRateLimitsReq bytes into columns.
    Returns None when the native library is unavailable or the payload is
    malformed (callers fall back to python-protobuf for the real error)."""
    lib = _load()
    if lib is None:
        return None
    n = lib.gub_count_reqs(payload, len(payload))
    if n < 0:
        return None
    cols = ParsedReqs(int(n))
    got = lib.gub_parse_reqs2(
        payload, len(payload), n, cols.hash, cols.err, cols.hits,
        cols.limit, cols.duration, cols.algo, cols.behavior, cols.burst,
        cols.msg_off, cols.msg_len, cols.name_hash,
    )
    if got != n:
        return None
    return cols


class ParsedResps:
    """Columnar view of a GetPeerRateLimitsResp payload (gub_parse_resps2).
    err_off/err_len index into the payload bytes (lazy error slicing);
    meta_off/meta_len cover each item's metadata map entries as raw wire
    frames (meta_len -1 = fragmented, drop)."""

    __slots__ = (
        "n", "status", "limit", "remaining", "reset_time",
        "err_off", "err_len", "meta_off", "meta_len",
    )

    def __init__(self, n: int) -> None:
        self.n = n
        self.status = np.empty(n, dtype=np.int64)
        self.limit = np.empty(n, dtype=np.int64)
        self.remaining = np.empty(n, dtype=np.int64)
        self.reset_time = np.empty(n, dtype=np.int64)
        self.err_off = np.empty(n, dtype=np.int64)
        self.err_len = np.empty(n, dtype=np.int64)
        self.meta_off = np.empty(n, dtype=np.int64)
        self.meta_len = np.empty(n, dtype=np.int64)


def parse_resps(payload: bytes) -> Optional[ParsedResps]:
    """Parse raw GetRateLimitsResp / GetPeerRateLimitsResp bytes into
    columns; None when unavailable/malformed."""
    lib = _load()
    if lib is None:
        return None
    n = lib.gub_count_reqs(payload, len(payload))  # same field-1 framing
    if n < 0:
        return None
    cols = ParsedResps(int(n))
    got = lib.gub_parse_resps2(
        payload, len(payload), n, cols.status, cols.limit, cols.remaining,
        cols.reset_time, cols.err_off, cols.err_len, cols.meta_off,
        cols.meta_len,
    )
    if got != n:
        return None
    return cols


def encode_reqs(reqs) -> Optional[bytes]:
    """Emit GetRateLimitsReq / GetPeerRateLimitsReq wire bytes for a
    sequence of RateLimitReq dataclasses without constructing python
    protobuf objects — the compiled CLIENT codec (client.FastV1Client;
    gub_serialize_reqs).  Returns None when the native library is
    unavailable (callers fall back to python-protobuf)."""
    if _load() is None:
        return None
    n = len(reqs)
    names = [r.name.encode() for r in reqs]
    keys = [r.unique_key.encode() for r in reqs]
    name_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(b) for b in names], out=name_off[1:])
    key_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(b) for b in keys], out=key_off[1:])

    def col(attr):
        return np.fromiter(
            (int(getattr(r, attr)) for r in reqs),
            dtype=np.int64, count=n,
        )

    return encode_req_columns(
        b"".join(names), name_off, b"".join(keys), key_off,
        col("hits"), col("limit"), col("duration"), col("algorithm"),
        col("behavior"), col("burst"),
    )


def encode_req_columns(
    names: bytes, name_off: np.ndarray, keys: bytes, key_off: np.ndarray,
    hits: np.ndarray, limit: np.ndarray, duration: np.ndarray,
    algorithm: np.ndarray, behavior: np.ndarray, burst: np.ndarray,
) -> bytes:
    """encode_reqs from columns: concatenated name/key bytes with
    int64[n+1] offsets plus int64[n] field columns — a bulk loader
    (chip_smoke.py) never builds per-request objects.  Native only."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(hits)
    # Worst case per item: 6 numeric fields at 11 B (negative int64
    # varints are 10 B + tag), two string frames at 6 B of framing, and
    # the item frame header — 96 B covers it with slack.
    cap = int(name_off[-1] + key_off[-1]) + n * 96 + 16
    out = np.empty(cap, dtype=np.uint8)

    def c64(a):
        return np.ascontiguousarray(a, dtype=np.int64)

    written = lib.gub_serialize_reqs(
        n, names, c64(name_off), keys, c64(key_off),
        c64(hits), c64(limit), c64(duration), c64(algorithm),
        c64(behavior), c64(burst), out, cap,
    )
    if written < 0:
        raise RuntimeError("serialize_reqs buffer overflow")
    return out[:written].tobytes()


def _encode_varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def meta_frame(key: bytes, value: bytes) -> bytes:
    """A complete metadata map-entry wire frame (RateLimitResp field 6:
    map<string,string>) for serialize_resps' meta_blob."""
    body = (
        b"\x0a" + _encode_varint(len(key)) + key
        + b"\x12" + _encode_varint(len(value)) + value
    )
    return b"\x32" + _encode_varint(len(body)) + body


def serialize_resps(
    status: np.ndarray,
    limit: np.ndarray,
    remaining: np.ndarray,
    reset_time: np.ndarray,
    err_blob: bytes,
    err_off: np.ndarray,
    meta_blob: Optional[bytes] = None,
    meta_off: Optional[np.ndarray] = None,
) -> bytes:
    """Emit GetRateLimitsResp / GetPeerRateLimitsResp wire bytes from packed
    response columns; meta_blob/meta_off add per-request pre-encoded
    metadata map-entry frames (see meta_frame; forwarded-owner and
    sketch-tier annotations).  Native only (callers gate on available())."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(status)
    # Worst case per item: 4 varint fields (<=11 B each) + submsg framing
    # (<=6 B) + error bytes (+3 B framing); metadata frames are verbatim.
    cap = (
        n * 64 + len(err_blob)
        + (len(meta_blob) if meta_blob else 0) + 16
    )
    out = np.empty(cap, dtype=np.uint8)
    if meta_off is not None:
        meta_off = np.ascontiguousarray(meta_off, dtype=np.int64)
        meta_off_ptr = meta_off.ctypes.data_as(ctypes.c_void_p)
    else:
        meta_off_ptr = None
    written = lib.gub_serialize_resps2(
        n,
        np.ascontiguousarray(status, dtype=np.int64),
        np.ascontiguousarray(limit, dtype=np.int64),
        np.ascontiguousarray(remaining, dtype=np.int64),
        np.ascontiguousarray(reset_time, dtype=np.int64),
        err_blob,
        np.ascontiguousarray(err_off, dtype=np.int64),
        meta_blob,
        meta_off_ptr,
        out,
        cap,
    )
    if written < 0:
        raise RuntimeError("serialize_resps buffer overflow")
    return out[:written].tobytes()
