"""The kernels' write-back (`ops/state.py` `write_rows`) against the plain
scatter it replaced.

Until PR 36 `apply_batch_impl`, `load_rows_impl` and
`store_cached_rows_impl` each wrote their rows with
`arr.at[tgt].set(val, mode="drop")` on an int64 `tgt`, and told XLA
nothing about it.  That text is frozen below as the reference.
`write_rows` hands XLA the same rows on 32-bit targets and, where
`sorts_write_back` says it pays, sorted once, the values with them — and
must leave the table bit-identical for every batch that honours the
kernels' contract: a key once a batch.  The last tests pin that contract
where it is made, in the packer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import test_locate_slots as ls
from gubernator_tpu.ops import state as st
from gubernator_tpu.ops import step as sp
from gubernator_tpu.ops.state import SlotTable, table_to_host

try:
    from jax import shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map

WAYS = 8
NOW = ls.NOW
# (seed, fill, expired, resident, inactive, crowd) of `_random_case`, as
# scripts/claim_rounds_chip.py runs them on the chip.
CASES = {
    "served": (3, 0.6, 0.0, 0.98, 0.0, 0),
    "cold": (1, 0.0, 0.0, 0.0, 0.0, 0),
    "crowded": (4, 0.9, 0.5, 0.3, 0.2, 3),
    "full": (5, 1.0, 0.0, 0.1, 0.05, 0),
}
LANES = (128, 4096)


# ---- the reference: ops/step.py's write-back at PR 34, verbatim -------------

def plain_write_rows(table: SlotTable, do_write, slot, rows: SlotTable):
    """`slot` int64[B]; every dropped lane goes to the one index S."""
    S = table.key.shape[0]
    tgt = jnp.where(do_write, slot, S)  # S -> dropped by scatter mode

    def scat(arr, val):
        return arr.at[tgt].set(val.astype(arr.dtype), mode="drop")

    return SlotTable(*(scat(a, v) for a, v in zip(table, rows)))


_PLAIN_TRACES = []


def _plain(table, do_write, slot32, rows):
    """`plain_write_rows` behind `write_rows`' signature; each trace of
    it is noted, so that a test can tell it compared two programs."""
    _PLAIN_TRACES.append(slot32.shape)
    return plain_write_rows(table, do_write, slot32.astype(jnp.int64), rows)


def variant_write_rows(index_dtype, sort: bool, unique: bool,
                       via: str = "gather"):
    """A write-back of `write_rows`' signature with what XLA is told
    spelled out — what scripts/claim_rounds_chip.py times on the chip.  A
    dropped lane gets a target of its own only where uniqueness is
    promised; (int64, unsorted, not unique) is the reference.  `via` is
    how a sorted variant puts the values in the targets' order: a
    `gather` of each vector by the sorted lane numbers, or as further
    operands of the `sort` (what `write_rows` does)."""
    kw = dict(mode="drop", indices_are_sorted=sort, unique_indices=unique)

    def write(table, do_write, slot32, rows):
        S, B = table.key.shape[0], slot32.shape[0]
        lane = jnp.arange(B, dtype=index_dtype)
        tgt = jnp.where(do_write, slot32.astype(index_dtype),
                        S + lane if unique else S)
        vals = [v.astype(a.dtype) for a, v in zip(table, rows)]
        if sort and via == "gather":
            tgt, order = jax.lax.sort((tgt, lane), num_keys=1)
            vals = [v[order] for v in vals]
        elif sort:
            tgt, *vals = jax.lax.sort((tgt, *vals), num_keys=1)
        return SlotTable(*(
            a.at[tgt].set(v, **kw) for a, v in zip(table, vals)))

    return write


VARIANTS = {
    f"{np.dtype(dt).name}.{'sorted' if s else 'unsorted'}."
    f"{'unique' if u else 'any'}": (dt, s, u)
    for dt in (jnp.int64, jnp.int32) for s in (False, True)
    for u in (False, True)
}


# ---- seeded tables, batches and rows ---------------------------------------

def _case(name: str, B: int):
    seed, *shape = CASES[name]
    # The served geometry: 8 B buckets, conflicts rare; the others on
    # B / 4 buckets, where every claim round has contenders and the
    # fourth contender of a bucket goes transient.
    nb = 8 * B if name == "served" else B // 4
    return ls._random_case(seed * 1000 + B, B, WAYS, nb, *shape)


def _wide(rng, n):
    """int64 values with both words in use."""
    return rng.integers(-2**62, 2**62, n, dtype=np.int64)


def _random_rows(rng, B: int) -> SlotTable:
    cols = {f: _wide(rng, B) for f in SlotTable._fields}
    for f in ("algo", "kind", "status"):
        cols[f] = rng.integers(0, 2, B).astype(np.int32)
    return SlotTable(**cols)


def _device_batch(rng, h, active) -> sp.DeviceBatchJ:
    """Token and leaky lanes, spends and peeks, some resets (a reset on a
    found token row is a `tok_clear` row: written as zeros), some lanes
    on the GLOBAL read path."""
    B = len(h)
    limit = rng.integers(1, 1000, B).astype(np.int64)
    z = np.zeros(B, np.int64)
    return sp.DeviceBatchJ(
        key_hash=np.asarray(h, np.int64),
        hits=rng.integers(0, 3, B).astype(np.int64), limit=limit,
        duration=rng.integers(1, 30 * ls.DAY, B).astype(np.int64),
        algo=rng.integers(0, 2, B).astype(np.int32), burst=limit,
        reset_remaining=rng.random(B) < 0.2,
        is_greg=np.zeros(B, bool), greg_expire=z, greg_duration=z,
        active=np.asarray(active, bool), use_cached=rng.random(B) < 0.1,
    )


def _bucket_rows(rng, h, active) -> sp.BucketRows:
    B = len(h)
    return sp.BucketRows(
        key_hash=np.where(active, h, 0).astype(np.int64),
        algo=rng.integers(0, 2, B).astype(np.int32), limit=_wide(rng, B),
        duration=_wide(rng, B), remaining=_wide(rng, B),
        remaining_f=_wide(rng, B), t0=_wide(rng, B),
        status=rng.integers(0, 2, B).astype(np.int32), burst=_wide(rng, B),
        expire_at=NOW + rng.integers(1, ls.DAY, B).astype(np.int64),
    )


def _cached_rows(rng, h, active) -> sp.CachedRows:
    B = len(h)
    return sp.CachedRows(
        key_hash=np.where(active, h, 0).astype(np.int64),
        algo=rng.integers(0, 2, B).astype(np.int32), limit=_wide(rng, B),
        remaining=_wide(rng, B),
        status=rng.integers(0, 2, B).astype(np.int32),
        reset_time=NOW + rng.integers(1, ls.DAY, B).astype(np.int64),
    )


_KERNELS = {
    "apply_batch": (sp.apply_batch_impl, _device_batch),
    "load_rows": (sp.load_rows_impl, _bucket_rows),
    "store_cached_rows": (sp.store_cached_rows_impl, _cached_rows),
}


def _assert_tables_identical(got: SlotTable, want: SlotTable) -> None:
    got, want = table_to_host(got), table_to_host(want)
    for f in SlotTable._fields:
        # Bit for bit: remaining_f is a float64 view of arbitrary words.
        np.testing.assert_array_equal(
            got[f].view(np.uint8), want[f].view(np.uint8), err_msg=f)


# ---- the helper alone -------------------------------------------------------

def _located(table, h, active):
    return ls.new_locate_slots(
        table, jnp.asarray(h), jnp.asarray(active), jnp.int64(NOW), ways=WAYS)


@pytest.mark.parametrize("B", LANES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_write_rows_leaves_the_table_the_plain_scatter_leaves(case, B):
    table, h, active = _case(case, B)
    rng = np.random.default_rng(B + len(case))
    _, persist, slot, _, slot32 = _located(table, h, active)
    # As the step: transient and inactive lanes drop, and some more (a
    # lane answered from a cached row writes nothing).
    do_write = np.asarray(persist) & active & (rng.random(B) < 0.9)
    assert do_write.any() and not do_write.all()
    rows = _random_rows(rng, B)
    want = jax.jit(plain_write_rows)(table, do_write, slot, rows)
    got = jax.jit(st.write_rows)(table, do_write, slot32, rows)
    _assert_tables_identical(got, want)
    # ...and it wrote: the written slots hold the lanes' rows.
    host = table_to_host(got)
    np.testing.assert_array_equal(
        host["limit"][np.asarray(slot)[do_write]], rows.limit[do_write])


@pytest.mark.parametrize("B", LANES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_write_targets_are_sorted_and_dropped_past_the_end(case, B):
    table, h, active = _case(case, B)
    S = table.num_slots
    _, persist, _, _, slot32 = _located(table, h, active)
    do_write = np.asarray(persist) & active
    slot32 = np.asarray(slot32)
    for sort in (False, True):
        # The lane numbers ride along as a value: the order of the rest.
        tgt, (order,) = jax.jit(lambda d, s: st.write_order(
            d, s, S, sort, [jnp.arange(B, dtype=jnp.int64)]))(
                do_write, slot32)
        tgt, order = np.asarray(tgt), np.asarray(order)
        assert tgt.dtype == np.int32
        assert sorted(order.tolist()) == list(range(B))     # a permutation
        written = do_write[order]
        # Written rows: in range, pairwise different, their lanes' slots.
        np.testing.assert_array_equal(tgt < S, written)
        assert len(np.unique(tgt[written])) == written.sum()
        np.testing.assert_array_equal(tgt[written], slot32[order][written])
        # Dropped rows: past the table's end.
        assert (tgt[~written] == S).all()
        if sort:
            assert (np.diff(tgt) >= 0).all()                # non-decreasing
            assert (np.diff(tgt[written]) > 0).all()
        else:
            np.testing.assert_array_equal(order, np.arange(B))


def test_write_rows_refuses_what_32_bits_cannot_address():
    table = jax.eval_shape(lambda: st.init_table(64))
    rows = SlotTable(*(jax.ShapeDtypeStruct((8,), np.int64)
                       for _ in SlotTable._fields))
    flag = jax.ShapeDtypeStruct((8,), bool)
    with pytest.raises(TypeError, match="32-bit"):
        jax.eval_shape(st.write_rows, table, flag,
                       jax.ShapeDtypeStruct((8,), np.int64), rows)
    with pytest.raises(ValueError, match="31 bits"):
        jax.eval_shape(
            lambda d, s: st.write_order(d, s, 1 << 31, True),
            flag, jax.ShapeDtypeStruct((8,), np.int32))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_timed_variant_is_the_same_write_back(variant):
    """What scripts/claim_rounds_chip.py times: the promises change the
    program XLA builds, never the table."""
    B = 128
    table, h, active = _case("crowded", B)
    _, persist, slot, _, slot32 = _located(table, h, active)
    do_write = np.asarray(persist) & active
    rows = _random_rows(np.random.default_rng(7), B)
    want = jax.jit(plain_write_rows)(table, do_write, slot, rows)
    for via in ("gather", "sort"):
        got = jax.jit(variant_write_rows(*VARIANTS[variant], via=via))(
            table, do_write, slot32, rows)
        _assert_tables_identical(got, want)


# ---- the three kernels that write through it --------------------------------

@pytest.mark.parametrize("B", LANES)
@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_kernel_leaves_the_table_the_plain_scatter_leaves(
        monkeypatch, kernel, B):
    impl, make = _KERNELS[kernel]
    args = {}
    for case in sorted(CASES):
        table, h, active = _case(case, B)
        args[case] = (table, make(np.random.default_rng(B + len(case)), h,
                                  active), active)

    def run_all():
        # `write_rows` is looked up when the kernel is traced, and a trace
        # is cached by the function traced: a new one each time.
        fn = jax.jit(lambda *a: impl(*a, ways=WAYS))
        return {case: fn(table, arg, jnp.int64(NOW))
                for case, (table, arg, _) in args.items()}

    served = run_all()
    monkeypatch.setattr(sp, "write_rows", _plain)
    traces = len(_PLAIN_TRACES)
    plain = run_all()
    assert len(_PLAIN_TRACES) > traces
    for case, (_, _, active) in args.items():
        got, want = served[case], plain[case]
        if kernel == "apply_batch":
            (got, resp), (want, resp_want) = got, want
            for f, g, w in zip(sp.Resp._fields, resp, resp_want, strict=True):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), f"{case}: {f}")
            persisted = np.asarray(resp.persisted)
            assert persisted.any(), case
            if case != "served":
                # transient lanes: answered, nothing written
                assert (active & ~persisted).any(), case
        _assert_tables_identical(got, want)
        assert int(got.occupancy()) > 0, case


def test_under_shard_map_on_four_virtual_devices(monkeypatch):
    n, B, nb = 4, 128, 32
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    cases = [ls._random_case(400 + i, B, WAYS, nb, 0.6, 0.3, 0.4, 0.1, 5)
             for i in range(n)]
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("shard",))
    table = jax.tree_util.tree_map(
        lambda *leaves: jnp.concatenate(leaves), *(c[0] for c in cases))
    batches = [_device_batch(np.random.default_rng(i), c[1], c[2])
               for i, c in enumerate(cases)]
    batch = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    def run():
        def per_shard(table, batch):
            b = jax.tree_util.tree_map(lambda x: x[0], batch)
            t2, resp = sp.apply_batch_impl(
                table, b, jnp.int64(NOW), ways=WAYS)
            return t2, jax.tree_util.tree_map(lambda x: x[None], resp)

        return jax.jit(shard_map(
            per_shard, mesh=mesh, in_specs=(P("shard"), P("shard")),
            out_specs=P("shard"),
        ))(table, batch)

    got, resp = run()
    monkeypatch.setattr(sp, "write_rows", _plain)
    traces = len(_PLAIN_TRACES)
    want, resp_want = run()
    assert len(_PLAIN_TRACES) > traces
    _assert_tables_identical(got, want)
    for f, g, w in zip(sp.Resp._fields, resp, resp_want, strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), f)
    # Each shard's rows are what that shard's table gives alone.
    one = jax.jit(sp.apply_batch_impl, static_argnames=("ways",))
    alone = [one(c[0], b, jnp.int64(NOW), ways=WAYS)[0]
             for c, b in zip(cases, batches)]
    _assert_tables_identical(want, jax.tree_util.tree_map(
        lambda *leaves: jnp.concatenate(leaves), *alone))


# ---- the contract "a key once a batch", where it is made ---------------------

def _requests_with_duplicates(n: int = 400):
    from gubernator_tpu.core.types import RateLimitReq

    rng = np.random.default_rng(36)
    keys = rng.integers(0, n // 8, n)          # each key ~8 times
    return [RateLimitReq(name="wb", unique_key=f"k{k}", hits=1, limit=100,
                         duration=60_000) for k in keys]


@pytest.mark.parametrize("packer", ["python", "native", "assign_rounds"])
def test_the_packer_never_puts_one_key_twice_into_a_round(packer):
    """The kernels answer and write every lane from the row as it was
    BEFORE the batch, so the written slots must be pairwise different: a
    key's lanes get one slot, and a key is in a round once — duplicates
    go to LATER rounds (ops/batch.py; `gub_assign_rounds` in
    native/gubtpu.cpp for the compiled lane).  `write_rows` promises XLA
    nothing about it (`unique_indices` bought no time on the chip, PERF.md
    section 5.3): were the contract broken the last lane would win, as
    with the plain scatter."""
    from gubernator_tpu import native
    from gubernator_tpu.ops import batch as pk

    reqs = _requests_with_duplicates()
    n_shards, B = 2, 64
    if packer != "python" and not native.available():
        pytest.skip("native library unavailable")
    if packer == "assign_rounds":
        from gubernator_tpu.core.hashing import key_hash64

        h = np.array([np.uint64(key_hash64(f"wb_{r.unique_key}")).astype(
            np.int64) for r in reqs])
        shards = (h.astype(np.uint64) % np.uint64(n_shards)).astype(np.int32)
        rnd, lane, n_rounds = native.assign_rounds(h, shards, n_shards, B)
        assert n_rounds > 1
        where = {}
        for i in range(len(reqs)):
            at = (int(rnd[i]), int(shards[i]), int(lane[i]))
            assert at not in where, "one lane given twice"
            where[at] = h[i]
        for r in range(n_rounds):
            keys = [k for (rr, _, _), k in where.items() if rr == r]
            assert len(keys) == len(set(keys)), f"round {r}"
        return
    pack = (pk._pack_requests_grid_py if packer == "python"
            else pk._pack_requests_grid_native)
    grid = pack(reqs, B, n_shards, lambda key: hash(key) % n_shards)
    assert not grid.errors and len(grid.rounds) > 1
    for r, rnd in enumerate(grid.rounds):
        keys = np.asarray(rnd.key_hash)[np.asarray(rnd.active)]
        assert len(keys) and len(np.unique(keys)) == len(keys), f"round {r}"
    # ...and occurrence k of a key is in a later round than k - 1.
    last = {}
    for req, (r, _, _) in zip(reqs, grid.positions):
        assert last.get(req.unique_key, -1) < r
        last[req.unique_key] = r
