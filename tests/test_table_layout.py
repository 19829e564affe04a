"""The slot table's physical layout (ops/state.py): every int64 field is a
low and a high uint32 column on the device, the logical schema and the
host/checkpoint format are what they were, and the static planes still
judge the logical values.

Why the layout exists is a property of the TPU's compiler, so the last
tests compile the real step programs for a DESCRIBED v5e (no chip) and
look for the boundary conversions the layout removes
(scripts/step_hlo.py).  They skip where no topology can be described.
"""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.hashing import key_hash64
from gubernator_tpu.core.types import RateLimitReq
from gubernator_tpu.ops import f64bits
from gubernator_tpu.ops import state as st
from gubernator_tpu.ops import step as sp
from gubernator_tpu.ops.state import (
    COL64_FIELDS,
    INT64_FIELDS,
    Col64,
    SlotTable,
    init_table,
    table_from_host,
    table_to_host,
)

REPO = Path(__file__).resolve().parents[1]
S, WAYS, B = 512, 8, 16
NOW = np.int64(1_000_000)
I64 = np.iinfo(np.int64)
# The int64 corners every 64-bit field must carry: both words empty,
# both full, each word alone, the sign bit, a fingerprint with the top
# bit set.
CORNERS = np.array(
    [0, -1, 2**32 - 1, 2**32, -(2**32), I64.min, I64.max,
     np.uint64(0xD6E8FEB86659FD93).astype(np.int64)],
    dtype=np.int64,
)
F64_CORNERS = np.array(
    [0.0, -0.0, 1e-300, 5e-324, 0.1, 2.0**53 + 2, 1.7976931348623157e308,
     np.inf],
    dtype=np.float64,
)
# No exception to "32-bit leaves" is left: the leaky remainder is the two
# words of its binary64's BITS (ops/f64bits.py), float64 on the host only.


def _leaf_dtypes(table) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(table)
    return {
        jax.tree_util.keystr(path): np.dtype(leaf.dtype)
        for path, leaf in flat
    }


def _assert_physical(table) -> None:
    assert isinstance(table, SlotTable)
    dtypes = _leaf_dtypes(table)
    assert len(dtypes) == 2 * len(COL64_FIELDS) + 3
    for key, dt in dtypes.items():
        assert dt.itemsize == 4 and dt.kind in "iu", (
            f"{key} is {dt}: not a 32-bit integer column")
    for f in COL64_FIELDS:
        assert dtypes[f".{f}.lo"] == dtypes[f".{f}.hi"] == np.uint32


def _corner_arrays(seed: int = 0) -> dict:
    """Twelve LOGICAL arrays, the parent's checkpoint format, with the
    int64 corners cycled through every 64-bit field."""
    rng = np.random.default_rng(seed)
    out = {}
    for i, f in enumerate(SlotTable._fields):
        if f in INT64_FIELDS:
            out[f] = np.roll(np.resize(CORNERS, S), i)
        elif f == "remaining_f":
            out[f] = np.resize(F64_CORNERS, S)
        else:
            out[f] = rng.integers(0, 2, S).astype(np.int32)
    return out


# -- (1) 32-bit leaves, from init and out of every table-returning kernel --

def test_init_table_leaves_are_32_bit():
    _assert_physical(init_table(S))


def _q(n=1):
    return np.zeros((n, 12, B), np.int64)


_TABLE_KERNELS = {
    "apply_batch": lambda t: sp.apply_batch(
        t, _device_batch(), NOW, ways=WAYS)[0],
    "apply_batch_packed_q": lambda t: sp.apply_batch_packed_q(
        t, _q()[0], NOW, ways=WAYS)[0],
    "load_rows": lambda t: sp.load_rows(t, _rows(), NOW, ways=WAYS),
    "store_cached_rows": lambda t: sp.store_cached_rows(
        t, _cached(), NOW, ways=WAYS),
    "migrate_extract": lambda t: st.migrate_extract(
        t, np.zeros(B, np.int64), NOW, ways=WAYS)[0],
    "migrate_inject": lambda t: st.migrate_inject(
        t, _rows(), NOW, ways=WAYS)[0],
    "demote_extract": lambda t: st.demote_extract(
        t, np.zeros(4, np.int64), NOW, ways=WAYS, batch=8)[0],
}


@pytest.mark.parametrize("kernel", sorted(_TABLE_KERNELS))
def test_kernel_returns_32_bit_leaves(kernel):
    _assert_physical(_TABLE_KERNELS[kernel](init_table(S)))


# -- (2) the host seam: same dict in, same dict out -------------------------

def test_host_round_trip_keeps_every_bit():
    arrs = _corner_arrays()
    table = table_from_host(arrs)
    _assert_physical(table)
    back = table_to_host(table)
    assert list(back) == list(SlotTable._fields)
    for f, a in arrs.items():
        assert back[f].dtype == a.dtype, f
        # Bit-for-bit (so -0.0, denormals and inf in remaining_f count).
        np.testing.assert_array_equal(
            back[f].view(np.uint8), a.view(np.uint8), err_msg=f
        )
    # Each logical corner is its two words, low first.
    words = table.key
    np.testing.assert_array_equal(
        np.asarray(words.lo), arrs["key"].view(np.uint32)[0::2]
    )
    np.testing.assert_array_equal(
        np.asarray(words.hi), arrs["key"].view(np.uint32)[1::2]
    )
    assert int(table.occupancy()) == int(np.count_nonzero(arrs["key"]))


# -- (3) a parent-format checkpoint installs and reads back ------------------

def _placed_checkpoint(cfg: DeviceConfig, keys, now: int) -> dict:
    """A checkpoint dict as the parent wrote it — twelve logical numpy
    arrays — with one token row per key in its bucket's first free way."""
    n = cfg.num_slots
    arrs = {
        f: np.zeros(n, np.int64 if f in INT64_FIELDS else np.int32)
        for f in SlotTable._fields
    }
    arrs["remaining_f"] = np.zeros(n, np.float64)
    nb = n // cfg.ways
    for i, k in enumerate(keys):
        h = key_hash64(k)
        slot = (h & (nb - 1)) * cfg.ways
        while arrs["key"][slot] != 0:
            slot += 1
        arrs["key"][slot] = np.uint64(h).astype(np.int64)
        arrs["limit"][slot] = 2**33 + 100          # > 32 bits on purpose
        arrs["remaining"][slot] = 2**33 + 7 - i
        arrs["duration"][slot] = 60_000
        arrs["t0"][slot] = now
        arrs["expire_at"][slot] = now + 60_000
        arrs["touched"][slot] = now
    return arrs


def test_parent_checkpoint_installs_on_device_backend(frozen_clock):
    from gubernator_tpu.runtime.backend import DeviceBackend

    cfg = DeviceConfig(num_slots=4096, ways=8, batch_size=64)
    now = frozen_clock.millisecond_now()
    keys = [f"ck_k{i}" for i in range(40)]
    arrs = _placed_checkpoint(cfg, keys, now)
    be = DeviceBackend(cfg, clock=frozen_clock)
    be._install_table({f: a.copy() for f, a in arrs.items()})
    _assert_physical(be.table)
    assert be.occupancy() == len(keys)
    snap = be.snapshot()
    for f, a in arrs.items():
        assert snap[f].dtype == a.dtype
        np.testing.assert_array_equal(snap[f], a, err_msg=f)
    for i, k in enumerate(keys):
        item = be.get_cache_item(k)
        assert item is not None and item.remaining == 2**33 + 7 - i, k
        assert item.limit == 2**33 + 100
    # ...and the restored rows keep counting, above 32 bits.
    resps = be.check([
        RateLimitReq(name="ck", unique_key=k[3:], hits=1,
                     limit=2**33 + 100, duration=60_000) for k in keys
    ])
    assert [r.remaining for r in resps] == [
        2**33 + 6 - i for i in range(len(keys))
    ]


def test_parent_checkpoint_installs_on_mesh_backend(frozen_clock):
    from gubernator_tpu.parallel.sharded import MeshBackend

    cfg = DeviceConfig(num_slots=8 * 8 * 64, ways=8, batch_size=64,
                       num_shards=8)
    reqs = [
        RateLimitReq(name="ck", unique_key=f"m{i}", hits=3,
                     limit=2**33 + 100, duration=60_000)
        for i in range(100)
    ]
    b1 = MeshBackend(cfg, clock=frozen_clock)
    b1.check(reqs)
    arrs = b1.snapshot()
    # The format the parent wrote and reads: twelve logical arrays.
    assert list(arrs) == list(SlotTable._fields)
    for f, a in arrs.items():
        want = (np.int64 if f in INT64_FIELDS
                else np.float64 if f == "remaining_f" else np.int32)
        assert a.dtype == want and a.shape == (cfg.num_slots,), f
    b2 = MeshBackend(cfg, clock=frozen_clock)
    b2._install_table(arrs)
    _assert_physical(b2.table)
    assert b2.occupancy() == b1.occupancy() == len(reqs)
    assert b2.shard_occupancy() == b1.shard_occupancy()
    again = b2.snapshot()
    for f, a in arrs.items():
        np.testing.assert_array_equal(again[f], a, err_msg=f)
    for r in reqs[:10]:
        item = b2.get_cache_item(f"ck_{r.unique_key}")
        assert item is not None and item.remaining == 2**33 + 97
    resps = b2.check(reqs)
    assert all(r.remaining == 2**33 + 94 for r in resps)


# -- (4) gather / scatter against a plain int64 numpy table ------------------

_INDEXES = {
    "lanes": np.array([0, 5, 5, S - 1, 17], np.int64),
    "bucket_ways": (np.array([[3], [40]]) * WAYS
                    + np.arange(WAYS)[None, :]).astype(np.int64),
    "slice": slice(8, 24),
    "whole": Ellipsis,
}


@pytest.mark.parametrize("index", sorted(_INDEXES))
def test_gather64_matches_numpy(index):
    logical = _corner_arrays()["key"]
    col = table_from_host(_corner_arrays()).key
    idx = _INDEXES[index]
    got = jax.jit(lambda c, i=idx: c[i])(col)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(np.asarray(got), logical[idx])
    np.testing.assert_array_equal(np.asarray(col), logical)


def test_scatter64_matches_numpy_and_drops_out_of_range():
    logical = _corner_arrays()["limit"]
    col = table_from_host(_corner_arrays()).limit
    tgt = np.array([1, S, 7, S, 300], np.int64)     # S = the drop lane
    vals = np.array([I64.min, 11, -1, 12, 2**32], np.int64)
    out = jax.jit(lambda c: c.at[tgt].set(vals, mode="drop"))(col)
    assert isinstance(out, Col64)
    want = logical.copy()
    want[[1, 7, 300]] = [I64.min, -1, 2**32]
    np.testing.assert_array_equal(np.asarray(out), want)


def _device_batch():
    from tools.gubtrace.registry import _device_batch as make

    return make(B)


def _rows(keys=None):
    """BucketRows of 64-bit corners; key k is bucket k's first tenant."""
    keys = np.zeros(B, np.int64) if keys is None else keys
    n = len(keys)
    c = np.resize(CORNERS, n)
    return sp.BucketRows(
        key_hash=keys, algo=np.arange(n, dtype=np.int32) % 2,
        limit=np.roll(c, 1), duration=np.roll(c, 2), remaining=np.roll(c, 3),
        remaining_f=f64bits.to_bits(np.resize(F64_CORNERS, n)),
        t0=np.roll(c, 4),
        status=np.zeros(n, np.int32), burst=np.roll(c, 5),
        # alive: every expiry is past NOW, with high words in use
        expire_at=np.int64(2**40) + np.arange(n, dtype=np.int64),
    )


def _cached(keys=None):
    keys = np.zeros(B, np.int64) if keys is None else keys
    n = len(keys)
    c = np.resize(CORNERS, n)
    return sp.CachedRows(
        key_hash=keys, algo=np.zeros(n, np.int32), limit=np.roll(c, 1),
        remaining=np.roll(c, 2), status=np.ones(n, np.int32),
        reset_time=np.int64(2**41) + np.arange(n, dtype=np.int64),
    )


# Keys whose buckets differ (low bits) and whose high words are in use.
_KEYS = (np.arange(1, B + 1, dtype=np.int64)
         | (np.int64(1) << 62) | (np.arange(B, dtype=np.int64) << 33))


def _loaded():
    """(table with _rows(_KEYS) upserted, its host view, slot per key)."""
    rows = _rows(_KEYS)
    table = sp.load_rows(init_table(S), rows, NOW, ways=WAYS)
    found, slot = sp.probe_batch(table, _KEYS, NOW, ways=WAYS)
    assert bool(np.asarray(found).all())
    return rows, table, table_to_host(table), np.asarray(slot)


def _check_load_rows():
    rows, _table, host, slot = _loaded()
    for f in ("limit", "duration", "remaining", "t0", "burst", "expire_at"):
        np.testing.assert_array_equal(host[f][slot], getattr(rows, f), f)
    np.testing.assert_array_equal(host["key"][slot], _KEYS)
    np.testing.assert_array_equal(host["touched"][slot], np.full(B, NOW))
    np.testing.assert_array_equal(
        host["remaining_f"][slot].view(np.uint64),
        rows.remaining_f.view(np.uint64),
    )
    untouched = np.setdiff1d(np.arange(S), slot)
    for f in SlotTable._fields:
        assert not host[f][untouched].any(), f


def _check_gather_rows():
    _rows_, table, host, slot = _loaded()
    packed, rf = sp.gather_rows(table, _KEYS, NOW, ways=WAYS)
    packed = np.asarray(packed)
    assert packed[0].all()
    for i, f in enumerate(sp.GATHER_ROW_FIELDS[1:], start=1):
        np.testing.assert_array_equal(packed[i], host[f][slot], f)
    np.testing.assert_array_equal(
        np.asarray(rf), f64bits.to_bits(host["remaining_f"][slot]))


def _check_probe_batch():
    _rows_, table, host, slot = _loaded()
    np.testing.assert_array_equal(host["key"][slot], _KEYS)
    # A key that differs from a resident ONLY in its high word misses.
    found, _ = sp.probe_batch(
        table, _KEYS ^ (np.int64(1) << 40), NOW, ways=WAYS)
    assert not np.asarray(found).any()


def _check_migrate_extract():
    _rows_, table, host, slot = _loaded()
    half = _KEYS.copy()
    half[B // 2:] = 0
    table2, packed, rf = st.migrate_extract(table, half, NOW, ways=WAYS)
    packed = np.asarray(packed)
    moved = slot[: B // 2]
    assert packed[0, : B // 2].all() and not packed[0, B // 2:].any()
    for i, f in enumerate(sp.GATHER_ROW_FIELDS[1:], start=1):
        np.testing.assert_array_equal(
            packed[i, : B // 2], host[f][moved], f)
    after = table_to_host(table2)
    assert not after["key"][moved].any()
    assert not after["expire_at"][moved].any()
    kept = slot[B // 2:]
    for f in SlotTable._fields:
        np.testing.assert_array_equal(after[f][kept], host[f][kept], f)


def _check_migrate_inject():
    rows, _t, host, _slot = _loaded()
    table, resident = st.migrate_inject(
        init_table(S), _rows(_KEYS), NOW, ways=WAYS)
    assert not np.asarray(resident).any()
    again = table_to_host(table)
    for f in SlotTable._fields:
        np.testing.assert_array_equal(again[f], host[f], f)


def _check_demote_extract():
    """The victim order is a 64-bit comparison: a stamp of 2^32 (high
    word 1, low word 0) is NEWER than 2^32 - 1 (high 0, low all ones)."""
    arrs = table_to_host(init_table(S))
    slots = np.array([8, 16, 24, 32])
    stamps = np.array([2**32, 2**32 - 1, 2**33, 5], np.int64)
    arrs["key"][slots] = np.array([11, 12, 13, 14]) | (np.int64(1) << 63)
    arrs["expire_at"][slots] = 2**40
    arrs["touched"][slots] = stamps
    table, packed, _rf = st.demote_extract(
        table_from_host(arrs), np.zeros(4, np.int64), NOW, ways=WAYS,
        batch=2)
    got = set(np.asarray(packed)[0].tolist())
    assert got == set(arrs["key"][[32, 16]].tolist())   # stamps 5, 2^32-1
    after = table_to_host(table)
    assert not after["key"][[32, 16]].any()
    np.testing.assert_array_equal(
        after["key"][[8, 24]], arrs["key"][[8, 24]])


def _check_table_stats():
    arrs = _corner_arrays()
    arrs["expire_at"] = np.where(np.arange(S) % 2 == 0, 2**40, 0)
    stats = st.table_stats(
        table_from_host(arrs), np.zeros((len(st.SHADOW_PLANES), 4),
                                        np.int64), NOW, ways=WAYS)
    resident = arrs["key"] != 0
    assert int(stats.occupancy) == int(resident.sum())
    assert int(stats.live) == int(
        (resident & (arrs["expire_at"] > NOW)).sum())


def _check_store_cached_rows():
    rows = _cached(_KEYS)
    table = sp.store_cached_rows(init_table(S), rows, NOW, ways=WAYS)
    found, slot = sp.probe_batch(table, _KEYS, NOW, ways=WAYS)
    assert bool(np.asarray(found).all())
    host, slot = table_to_host(table), np.asarray(slot)
    np.testing.assert_array_equal(host["limit"][slot], rows.limit)
    np.testing.assert_array_equal(host["remaining"][slot], rows.remaining)
    np.testing.assert_array_equal(host["expire_at"][slot], rows.reset_time)
    assert (host["kind"][slot] == st.KIND_CACHED_RESP).all()


def _check_apply_batch():
    """One token hit on rows whose counters live above 32 bits."""
    n = 4
    big = np.int64(2**33 + 5)
    rows = sp.BucketRows(
        key_hash=_KEYS[:n], algo=np.zeros(n, np.int32),
        limit=np.full(n, big), duration=np.full(n, 2**35, np.int64),
        remaining=big - np.arange(n, dtype=np.int64),
        remaining_f=np.zeros(n, np.int64), t0=np.full(n, NOW),
        status=np.zeros(n, np.int32), burst=np.zeros(n, np.int64),
        expire_at=np.full(n, NOW + 2**35, np.int64),
    )
    table = sp.load_rows(init_table(S), rows, NOW, ways=WAYS)
    z = np.zeros(B, np.int64)
    act = np.arange(B) < n
    batch = sp.DeviceBatchJ(
        key_hash=np.where(act, _KEYS, 0), hits=act.astype(np.int64),
        limit=np.where(act, big, 0), duration=np.where(act, 2**35, 0),
        algo=np.zeros(B, np.int32), burst=z, reset_remaining=~act & False,
        is_greg=~act & False, greg_expire=z, greg_duration=z, active=act,
        use_cached=~act & False,
    )
    table, resp = sp.apply_batch(table, batch, NOW + 1, ways=WAYS)
    want = big - np.arange(n) - 1
    np.testing.assert_array_equal(np.asarray(resp.remaining)[:n], want)
    np.testing.assert_array_equal(
        np.asarray(resp.reset_time)[:n], np.full(n, NOW + 2**35))
    _found, slot = sp.probe_batch(table, _KEYS[:n], NOW + 1, ways=WAYS)
    host = table_to_host(table)
    np.testing.assert_array_equal(host["remaining"][np.asarray(slot)], want)


_KERNEL_CHECKS = {
    f.__name__[len("_check_"):]: f for f in (
        _check_load_rows, _check_gather_rows, _check_probe_batch,
        _check_migrate_extract, _check_migrate_inject,
        _check_demote_extract, _check_table_stats,
        _check_store_cached_rows, _check_apply_batch,
    )
}


@pytest.mark.parametrize("kernel", sorted(_KERNEL_CHECKS))
def test_kernel_agrees_with_int64_numpy_table(kernel):
    """Each kernel's reads and writes through gather64/scatter64 equal
    what a plain int64 numpy table (the host view) holds, for values
    that need both words."""
    _KERNEL_CHECKS[kernel]()


# -- the static planes' negative controls ------------------------------------

def _split_spec(name: str, impl, budget: dict):
    from tools.gubtrace.core import BuiltKernel, KernelSpec

    def build():
        return BuiltKernel(
            fn=jax.jit(impl), trace_fn=impl,
            signatures={"S64": lambda: (
                init_table(64).remaining, np.zeros(8, np.int64),
                np.zeros(8, np.int64))},
            counters=("[0]", "[2]"), allowed_casts=budget,
            expect_aliased=0,
        )

    return KernelSpec(name=name, where="tests/test_table_layout.py",
                      build=build, invariants=frozenset({"dtype-taint"}))


def _store_low_word_only(col, tgt, v):
    """The seeded bug: a counter written back as its low word alone."""
    import jax.numpy as jnp

    u = (col[tgt] + v).astype(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    return Col64(col.lo.at[tgt].set(lo, mode="drop"), col.hi)


def _store_both_words(col, tgt, v):
    return col.at[tgt].set(col[tgt] + v, mode="drop")


def test_dtype_plane_flags_a_low_word_only_store():
    from tools.gubtrace import run

    fs = run(select=["dtype-taint"], root=REPO, specs=[
        _split_spec("viol_low_word_only", _store_low_word_only,
                    {"split64": 1}),
        _split_spec("ok_both_words", _store_both_words, {"split64": 1}),
        _split_spec("undeclared_split", _store_both_words, {}),
    ])
    errs = {f.kernel: f.message for f in fs if f.severity == "error"}
    # Half a counter is a truncation, whatever split budget is declared...
    assert "to_i32" in errs["viol_low_word_only"], fs
    # ...the whole split is its own lossless class, declared like any cast
    assert "ok_both_words" not in errs, fs
    assert "split64" in errs["undeclared_split"], fs
    assert "to_i32" not in errs["undeclared_split"], fs


def _narrow_beside_a_sorted_counter(which: int):
    """A counter ([2]) sorted beside an untainted vector ([1]), as the
    write-back's values ride beside their targets; then ONE of the two
    sorted operands narrowed to int32."""
    import jax.numpy as jnp

    def impl(col, other, counter):
        out = jax.lax.sort((other, counter), num_keys=1)
        return col, out[which].astype(jnp.int32)

    return impl


def test_dtype_plane_follows_a_sort_operand_by_operand():
    from tools.gubtrace import run

    fs = run(select=["dtype-taint"], root=REPO, specs=[
        _split_spec("ok_narrow_the_other", _narrow_beside_a_sorted_counter(0),
                    {}),
        _split_spec("viol_narrow_the_counter",
                    _narrow_beside_a_sorted_counter(1), {}),
    ])
    errs = {f.kernel: f.message for f in fs if f.severity == "error"}
    # No value crosses from one operand of a sort into another...
    assert "ok_narrow_the_other" not in errs, fs
    # ...and a counter is still a counter when it comes out sorted.
    assert "to_i32" in errs["viol_narrow_the_counter"], fs


def test_range_plane_rejects_an_envelope_wider_than_the_logical_bound(
        tmp_path):
    """The bound declared under a field's name is the bound the
    arithmetic sees past the combine: as declared, table_stats is clean;
    widened past the epoch-ms horizon, `now - t0` can wrap and the plane
    says so."""
    from tools.gubrange import run
    from tools.gubrange.envelope import ENVELOPE_DIR

    fs = run(select=["ranges"], kernel="table_stats", root=REPO)
    assert fs == [], "\n".join(f.render() for f in fs)
    raw = json.loads((ENVELOPE_DIR / "table_stats.json").read_text())
    (rule,) = [r for r in raw["inputs"] if r["pattern"] == ".t0"]
    rule["min"], rule["max"] = int(I64.min), int(I64.max)
    (tmp_path / "table_stats.json").write_text(json.dumps(raw))
    fs = run(select=["ranges"], kernel="table_stats", root=REPO,
             envelope_dir=tmp_path)
    assert any(f.checker == "overflow" and f.severity == "error"
               for f in fs), "\n".join(f.render() for f in fs)


# -- what the v5e's compiler makes of it (no chip; scripts/step_hlo.py) ------

@pytest.fixture(scope="module")
def step_hlo():
    spec = importlib.util.spec_from_file_location(
        "step_hlo", REPO / "scripts" / "step_hlo.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo(step_hlo):
    try:
        return step_hlo.describe("v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _assert_no_boundary_conversion(rep: dict) -> None:
    x64 = [r for r in rep["table_length_ops"]
           if r["target"] in ("X64SplitLow", "X64SplitHigh", "X64Combine")]
    # None: remaining_f's three (a float64 column split on the way in and
    # combined on the way out) went with the column's float dtype.
    assert x64 == [], x64
    # Donation holds: the whole table is updated in place.
    assert rep["memory"]["alias_size_in_bytes"] == rep["table_bytes"], rep[
        "memory"]


@pytest.fixture(scope="module")
def one_chip_report(step_hlo, topo):
    """The compiled one-chip step's account, one compile a tier."""
    reports = {}

    def report(lanes: int) -> dict:
        if lanes not in reports:
            reports[lanes] = step_hlo.analyze_step(topo, 1 << 24, lanes)
        return reports[lanes]

    return report


@pytest.mark.parametrize("lanes", [128, 4096])
def test_one_chip_step_has_no_table_length_x64_conversion(
        one_chip_report, lanes):
    rep = one_chip_report(lanes)
    _assert_no_boundary_conversion(rep)
    # 875 MB with int64 columns, 136 MB while remaining_f was a float64
    # column (its two converted halves), 2-5 MB since it is bits.
    assert rep["memory"]["temp_size_in_bytes"] < 20e6, rep["memory"]


# The write-back (ops/state.py `write_rows`) at each one-chip tier of the
# 2^24-slot table, as `sorts_write_back` picks from lanes and rows:
# whether every table scatter is handed sorted targets, and the step's
# sorts — the claim rounds' two, and the write-back's one where it sorts.
_WRITE_BACK = {
    128: {"indices_are_sorted": False, "sorts": 2},
    4096: {"indices_are_sorted": True, "sorts": 3},
}


@pytest.mark.parametrize("lanes", [128, 4096])
def test_one_chip_step_has_its_sorts_and_no_loop_but_the_division(
        one_chip_report, lanes):
    """The claim rounds of `locate_slots` (ops/step.py).  Until PR 32
    they compiled to three `while` loops (`searchsorted` over B x 8 int64
    slots) and six sorts at 128 lanes, nine at 4096 (three of them the
    TPU's lowering of `_first_claim`'s scatter): 55 % of the 4096-lane
    step's device time (PERF.md PR 31).  Now: the sort of the lanes by
    (bucket, lane) and the sort back to lane order, and nothing of the
    claim that loops; since PR 36 also the write-back's one sort of its
    (target, lane) pairs, at a tier whose scatters are told their targets
    are sorted.  The only loops are the leaky lanes' two binary64
    divisions (ops/f64bits.py `div`: a fixed 7 trips of 8 quotient bits
    over the [B] lanes — unrolled whole they cost the compiler 21 s)."""
    loops = one_chip_report(lanes)["loops"]
    whiles = [r for r in loops if r["opcode"] == "while"]
    assert len(whiles) <= 2, loops
    for r in whiles:
        assert "leaky_f64bits" in (r["op_name"] or ""), r
    sorts = [r["name"] for r in loops if r["opcode"] == "sort"]
    assert len(sorts) == _WRITE_BACK[lanes]["sorts"], loops


def _assert_table_scatters(rep: dict, want: dict) -> None:
    """21 scatters, one a physical column, each on 32-bit targets, told
    they are sorted exactly where they are, and never told unique (it
    bought nothing on the chip, and would be a wrong promise for a batch
    that broke the kernels' contract)."""
    scatters = rep["table_scatters"]
    assert len(scatters) == 2 * len(COL64_FIELDS) + 3, scatters
    for r in scatters:
        assert r["index_dtype"] == "s32", r
        assert r["indices_are_sorted"] == want["indices_are_sorted"], r
        assert not r["unique_indices"], r


@pytest.mark.parametrize("lanes", [128, 4096])
def test_one_chip_table_scatters_carry_their_tier_s_promises(
        one_chip_report, lanes):
    _assert_table_scatters(one_chip_report(lanes), _WRITE_BACK[lanes])
    assert st.sorts_write_back(1 << 24, lanes) == _WRITE_BACK[lanes][
        "indices_are_sorted"]


# The rung between (runtime/backend.py `default_tiers`): the same program
# at another static width.  By table rows: whether the write-back sorts
# (at most 8,192 rows a lane), and the columns the compiler stages through
# fast memory around the gathers and the scatter.  On 2^22 rows it stages
# no more of them than the 4096 rung does (46); on 2^24 rows four whole
# columns for the walked scatter (the 4096 rung: 2), 0.1 ms each at the
# memory system's pace.
_RUNGS = {
    (1 << 24, 1024): {"indices_are_sorted": False, "staged": 4},
    (1 << 22, 1024): {"indices_are_sorted": True, "staged": 17},
}


def _assert_a_rung_of_the_same_program(rep: dict, want: dict) -> None:
    _assert_no_boundary_conversion(rep)
    assert rep["table_copies"] == [], rep["table_copies"]
    assert rep["staged_columns"] <= want["staged"], rep["staged_columns"]
    whiles = [r for r in rep["loops"] if r["opcode"] == "while"]
    assert len(whiles) <= 2, rep["loops"]
    for r in whiles:
        assert "leaky_f64bits" in (r["op_name"] or ""), r
    sorts = [r for r in rep["loops"] if r["opcode"] == "sort"]
    assert not [r for r in sorts
                if (r["op_name"] or "").endswith("/scatter")], sorts
    assert len(sorts) == 2 + want["indices_are_sorted"], sorts
    _assert_table_scatters(rep, want)


@pytest.mark.parametrize("slots,lanes", sorted(_RUNGS))
def test_the_rung_between_is_the_4096_lane_program_at_its_width(
        step_hlo, topo, slots, lanes):
    want = _RUNGS[slots, lanes]
    assert st.sorts_write_back(slots, lanes) == want["indices_are_sorted"]
    _assert_a_rung_of_the_same_program(
        step_hlo.analyze_step(topo, slots, lanes), want)


def test_a_mesh_shard_s_rung_is_its_table_s(step_hlo, topo):
    """A shard of the 2^24-slot mesh holds 2^22 rows: its rung sorts and
    streams as a one-chip table of that size does."""
    want = _RUNGS[1 << 22, 1024]
    assert st.sorts_write_back(1 << 22, 1024)
    _assert_a_rung_of_the_same_program(
        step_hlo.analyze_mesh_step(topo, 1 << 24, 1024), want)


@pytest.fixture(scope="module")
def mesh_report(step_hlo, topo):
    return step_hlo.analyze_mesh_step(topo, 1 << 24, 4096)


def test_mesh_step_has_no_table_length_x64_conversion(mesh_report):
    _assert_no_boundary_conversion(mesh_report)


def test_mesh_step_sorts_its_write_back_once(mesh_report):
    """Under `shard_map` the compiler sorted the targets of EVERY table
    scatter itself — 21 sorts of the same 4,096 targets a launch, each
    with `op_name` `.../scatter` (23 sorts in all, PR 34).  Handed targets
    sorted once and told so, it adds none."""
    sorts = [r for r in mesh_report["loops"] if r["opcode"] == "sort"]
    assert not [r for r in sorts
                if (r["op_name"] or "").endswith("/scatter")], sorts
    assert len(sorts) == 3, sorts
    _assert_table_scatters(mesh_report, {"indices_are_sorted": True})


def test_global_sync_program_writes_back_by_its_own_shapes(step_hlo, topo):
    """`GlobalEngine`'s sync program (two applies of 256 lanes and a store
    of the 1,024 gathered rows, each into a shard's 2^22 rows): the
    helper picks per write-back, from its lanes and rows — the applies
    say nothing (16,384 rows a lane), the store sorts (4,096) — and
    no scatter anywhere makes the compiler sort for it."""
    rep = step_hlo.analyze_global_sync(topo, 1 << 24)
    _assert_no_boundary_conversion(rep)
    sorts = [r for r in rep["loops"] if r["opcode"] == "sort"]
    assert not [r for r in sorts
                if (r["op_name"] or "").endswith("/scatter")], sorts
    assert len(sorts) == 3 * 2 + 1, sorts
    columns = 2 * len(COL64_FIELDS) + 3
    told = [r["indices_are_sorted"] for r in rep["table_scatters"]]
    assert told.count(True) == columns and told.count(False) == 2 * columns
    assert not any(r["unique_indices"] for r in rep["table_scatters"])

