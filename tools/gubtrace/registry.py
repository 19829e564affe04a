"""The declarative kernel registry: every jitted hot-path entrypoint.

Each spec names one jitted kernel, how to build canonical concrete
arguments for it (a small shape/dtype matrix — CPU-runnable sizes, the
invariants are shape-independent), which int64 inputs are tainted
counters/timestamps, the declared tainted-cast budget, the declared
donation surface, and the declared recompile budget.  The registry IS
the contract: a kernel change that moves any of these numbers must
change this file (or the golden snapshots) in the same PR, where a
reviewer sees it.

Canonical geometry (tiny on purpose — gubtrace runs under
JAX_PLATFORMS=cpu in CI):

  single-device   4096 slots x 8 ways, batches 64 and 128
  mesh            8 shards (the CI virtual-device count), 512
                  slots/shard, batch 64 per shard
  sketch          depth 4 x width 1024, batch 128

Counter patterns match `jax.tree_util.keystr` of the flattened args —
`.remaining` hits SlotTable.remaining (and .remaining_f, whose float
lineage the taint walk ignores by construction), `[2]` hits the bare
`now` argument.

Declared-cast budgets cite the deliberate conversion they license; the
dtype checker fails on the budget+1'th cast with its source line.

The pipelined drain (docs/pipeline.md) deliberately adds NO kernels:
its dispatch/fetch split is host-side orchestration over the
entrypoints already registered here (apply_batch_packed_q,
sharded_step_packed, sketch_multi_step, global_sync_step, the gather/
probe ops), so the golden primitive budgets are unchanged — the
completeness checker (AST scan for module-level jax.jit) stays the
authority that any future chained-dispatch kernel must land in this
file.

The lease plane (docs/leases.md) likewise adds NO kernels: grants,
reconciles, and carve-slot drops are host/client-side orchestration
whose device work is ordinary checks through the already-registered
step entrypoints (the `.lease-grant` slot is a normal table row), so
the verified kernels and their goldens are unchanged.

The reshard plane (docs/resharding.md) adds TWO kernels in
ops/state.py: migrate_extract (gather+clear fused — the atomic
old-owner extraction) and migrate_inject (upsert-if-absent — the
new-owner injection that can never clobber newer state).  The mesh
backend's migration path deliberately adds none: it rides the
registered sharded gather/load kernels through the generic
PersistenceHost helpers.
"""
from __future__ import annotations

import functools
from typing import Callable, List

import numpy as np

from tools.gubtrace.core import BuiltKernel, KernelSpec

SLOTS = 4096
WAYS = 8
N_SHARDS = 8
MESH_B = 64
SKETCH_DEPTH = 4
SKETCH_WIDTH = 1024
SKETCH_B = 128

# Table int64 counter/timestamp columns (SlotTable has 12 logical
# fields; the int32 enums algo/kind/status and the float remaining_f are
# excluded — their contracts bound them).  Each is PHYSICALLY two uint32
# leaves (`[0].limit.lo`, `[0].limit.hi`, ops/state.py), which these
# patterns match as substrings: the dtype plane carries the halves'
# taint to the combine, where the logical int64 lineage starts, and
# charges each lossless write-back split to the `split64` class — never
# to `to_i32`, which stays the truncation budget (tools/gubtrace/dtype.py).
_TABLE_COUNTERS = (
    ".key", ".limit", ".duration", ".remaining", ".t0", ".burst",
    ".expire_at", ".touched",
)
_BATCH_COUNTERS = (
    ".key_hash", ".hits", ".greg_expire", ".greg_duration",
)


def _table():
    from gubernator_tpu.ops.state import init_table

    return init_table(SLOTS)


def _now():
    return np.int64(0)


def _device_batch(B: int):
    from gubernator_tpu.ops.step import DeviceBatchJ

    z64 = lambda: np.zeros(B, np.int64)  # noqa: E731
    zb = lambda: np.zeros(B, bool)  # noqa: E731
    return DeviceBatchJ(
        key_hash=z64(), hits=z64(), limit=z64(), duration=z64(),
        algo=np.zeros(B, np.int32), burst=z64(), reset_remaining=zb(),
        is_greg=zb(), greg_expire=z64(), greg_duration=z64(),
        active=zb(), use_cached=zb(),
    )


def _bucket_rows(B: int):
    from gubernator_tpu.ops.step import BucketRows

    z64 = lambda: np.zeros(B, np.int64)  # noqa: E731
    return BucketRows(
        key_hash=z64(), algo=np.zeros(B, np.int32), limit=z64(),
        duration=z64(), remaining=z64(),
        remaining_f=z64(), t0=z64(),  # binary64 bits
        status=np.zeros(B, np.int32), burst=z64(), expire_at=z64(),
    )


def _cached_rows(B: int):
    from gubernator_tpu.ops.step import CachedRows

    z64 = lambda: np.zeros(B, np.int64)  # noqa: E731
    return CachedRows(
        key_hash=z64(), algo=np.zeros(B, np.int32), limit=z64(),
        remaining=z64(), status=np.zeros(B, np.int32), reset_time=z64(),
    )


def _step_spec(
    name: str,
    fn_name: str,
    impl_name: str,
    make_rest: Callable[[int], tuple],
    counters: tuple,
    allowed_casts: dict,
    donated: int,
    batches=(64, 128),
) -> KernelSpec:
    """Shared shape for the ops/step.py table kernels."""

    def build() -> BuiltKernel:
        import gubernator_tpu.ops.step as step

        fn = getattr(step, fn_name)
        impl = functools.partial(getattr(step, impl_name), ways=WAYS)

        def sig(B):
            return lambda: (_table(), *make_rest(B), _now())

        return BuiltKernel(
            fn=fn,
            trace_fn=impl,
            signatures={f"B{B}": sig(B) for B in batches},
            counters=counters,
            allowed_casts=allowed_casts,
            perturbations={
                # The caller-mistake replay: a python-scalar `now`
                # traces as a WEAK int64 and costs one extra compile.
                # Production callers pass np.int64 (runtime/backend);
                # this pins the cost of getting it wrong to exactly 1.
                "weak-now": lambda: (
                    _table(), *make_rest(batches[0]), 0
                ),
            },
            recompile_budget=len(batches) + 1,
            expect_aliased=donated,
        )

    return KernelSpec(name=name, where="gubernator_tpu/ops/step.py",
                      build=build)


# -- deliberate-cast budgets (ops/step.py) -------------------------------
# apply_batch taints every int64 table/batch counter.  It licenses NO
# float cast: the leaky bucket's Go-float arithmetic (algorithms.go
# computes burst/rate/leak/hits in float64) runs on the binary64's BITS
# in uint64/int64 words (ops/f64bits.py) — casts inside the 64-bit
# integer family are free, and a `to_f64` anywhere in a step kernel is a
# regression (tests/test_f64bits.py holds the jaxpr free of floats).
# split64: the write-back stores nine 64-bit columns (key, limit,
# duration, remaining, remaining_f's bits, t0, burst, expire_at,
# touched), one lossless split each.
# to_i32: `locate_slots` narrows each lane's bucket index
# (`h & (num_buckets - 1)`, under 2^31 by its trace-time check) once, for
# the 32-bit sort its claim rounds run on — charged to every kernel that
# locates a slot, once per call.  The write-back's 32-bit targets
# (`slot32`, ops/state.py `write_rows`) are put together from that SAME
# narrowed bucket and the claim's int32 way: no int64 slot is narrowed,
# and the budget stays one.  At the registry's geometry (4096 slots, 64
# or 128 lanes: few rows a lane) the write-back sorts its targets, so the
# goldens hold one sort more than the claim's two: the targets are its
# key and the twelve logical value vectors ride along as operands
# (PR 36).  The dtype plane follows a sort operand by operand, so a
# constant column stays untainted beside a counter (tools/gubtrace/
# dtype.py) and the `split64` budgets are what they were.
_BUCKET_I32 = 1
_APPLY_CASTS = {"to_i32": _BUCKET_I32, "split64": 9}
_APPLY_COUNTERS = _TABLE_COUNTERS + _BATCH_COUNTERS + (".limit",
                                                       ".duration", "[2]")
# Packed q-form: one widened-int64 row is narrowed back to the int32
# algo enum (values 0/1 by wire contract).
_APPLY_Q_CASTS = {"to_i32": 1 + _BUCKET_I32, "split64": 9}


def _migrate_spec(name: str, fn_name: str, impl_name: str,
                  make_rest, counters, allowed_casts,
                  donated: int) -> KernelSpec:
    """ops/state.py live-migration kernels (docs/resharding.md): the
    extract is gather+clear in one donated dispatch (no licensed casts
    — the only conversions are widenings of the int32 enum columns into
    the packed int64 stack); the inject is probe+load+merge in one,
    with no float cast — the conflict merge's leaky-bucket consumed
    budget (limit - remaining_f) is computed on the bits (ops/f64bits.py)
    — and eleven split64: load_rows' nine column writes plus the merged
    `remaining` and `remaining_f`.  The extracts clear with
    a constant 0, which is no counter lineage and splits nothing."""

    def build() -> BuiltKernel:
        import gubernator_tpu.ops.state as state

        fn = getattr(state, fn_name)
        impl = functools.partial(getattr(state, impl_name), ways=WAYS)

        def sig(B):
            return lambda: (_table(), *make_rest(B), _now())

        return BuiltKernel(
            fn=fn,
            trace_fn=impl,
            signatures={f"B{B}": sig(B) for B in (64, 128)},
            counters=counters,
            allowed_casts=allowed_casts,
            perturbations={
                "weak-now": lambda: (_table(), *make_rest(64), 0),
            },
            recompile_budget=3,
            expect_aliased=donated,
        )

    return KernelSpec(name=name, where="gubernator_tpu/ops/state.py",
                      build=build)


def _table_stats_spec() -> KernelSpec:
    """ops/state.py table_stats: the gubstat one-pass state census
    (docs/observability.md) — occupancy, bucket-fill, slot-age / TTL
    histograms, per-algorithm remaining-fraction distribution, and the
    shadow-slot census over host-enumerated derived-key fingerprints.
    Read-only and NON-donated by contract (it dispatches against the
    live serving table from the sampler's thread); no licensed cast: the
    fraction site divides on the binary64's bits (ops/f64bits.py) and the
    bin index stays int64."""

    def build() -> BuiltKernel:
        import gubernator_tpu.ops.state as state

        def sig(M: int):
            return lambda: (
                _table(), np.zeros((4, M), np.int64), _now()
            )

        return BuiltKernel(
            fn=state.table_stats,
            trace_fn=functools.partial(state.table_stats_impl, ways=WAYS),
            signatures={"M8": sig(8), "M16": sig(16)},
            counters=_TABLE_COUNTERS + ("[1]", "[2]"),
            allowed_casts={},
            perturbations={
                "weak-now": lambda: (
                    _table(), np.zeros((4, 8), np.int64), 0
                ),
            },
            recompile_budget=3,
            expect_aliased=0,
        )

    return KernelSpec(name="table_stats",
                      where="gubernator_tpu/ops/state.py", build=build)


def _sketch_state():
    from gubernator_tpu.ops.sketch import init_sketch

    return init_sketch(SKETCH_DEPTH, SKETCH_WIDTH, window_ms=1000)


_SKETCH_COUNTERS = (".window_start", ".window_ms", "[1]", "[4]")
# row_columns narrows the multiply-shift hash to int32 bucket columns
# (< width <= 2^20) once per row; the window-overlap fraction is
# computed in f32 from the ms timestamps (bounded by window_ms).
_SKETCH_CASTS = {"to_i32": SKETCH_DEPTH, "to_f32": 2}


class _PallasInterpretShim:
    """cms_step_pallas with interpret=True pinned — jit facade for the
    execution-based checkers (donation/recompile) on CPU."""

    def __init__(self, jitted) -> None:
        self._jitted = jitted

    def __call__(self, *args):
        return self._jitted(*args, interpret=True)

    def lower(self, *args):
        return self._jitted.lower(*args, interpret=True)

    def clear_cache(self) -> None:
        self._jitted.clear_cache()

    def _cache_size(self) -> int:
        return self._jitted._cache_size()


def _sketch_spec(name: str, fn_name: str, impl_name: str) -> KernelSpec:
    def build() -> BuiltKernel:
        import gubernator_tpu.ops.sketch as sketch

        if fn_name == "cms_step_pallas":
            import gubernator_tpu.ops.pallas.cms_kernel as ck

            fn = ck.cms_step_pallas
            impl = ck.cms_step_pallas_impl
        else:
            fn = getattr(sketch, fn_name)
            impl = getattr(sketch, impl_name)

        def sig():
            return (
                _sketch_state(),
                np.zeros(SKETCH_B, np.int64),
                np.zeros(SKETCH_B, np.int32),
                np.zeros(SKETCH_B, np.int32),
                _now(),
            )

        def weak():
            return sig()[:4] + (0,)

        expect_aliased = 4
        if fn_name == "cms_step_pallas":
            # Mosaic needs a real TPU; interpret mode runs the same
            # semantics (differentially tested bit-exact) on CPU for
            # the execution-based checkers.
            fn = _PallasInterpretShim(ck.cms_step_pallas)

        return BuiltKernel(
            fn=fn,
            trace_fn=impl,
            signatures={"B128": sig},
            counters=_SKETCH_COUNTERS,
            allowed_casts=dict(_SKETCH_CASTS),
            perturbations={"weak-now": weak},
            recompile_budget=2,
            expect_aliased=expect_aliased,
        )

    where = (
        "gubernator_tpu/ops/pallas/cms_kernel.py"
        if fn_name == "cms_step_pallas" else "gubernator_tpu/ops/sketch.py"
    )
    return KernelSpec(name=name, where=where, build=build)


# -- mesh kernels --------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _mesh():
    from gubernator_tpu.parallel.mesh import make_mesh

    return make_mesh(N_SHARDS)


def _sharded(arr_or_table, spec_dims):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(
        arr_or_table, NamedSharding(_mesh(), P(*spec_dims))
    )


def _mesh_table():
    from gubernator_tpu.ops.state import init_table

    return _sharded(init_table(SLOTS), ("shard",))


def _mesh_spec(
    name: str,
    factory: Callable,
    make_rest: Callable[[], tuple],
    counters: tuple,
    allowed_casts: dict,
    donated: int,
) -> KernelSpec:
    def build() -> BuiltKernel:
        fn = factory()

        def sig():
            return (_mesh_table(), *make_rest(), _now())

        return BuiltKernel(
            fn=fn,
            trace_fn=fn,
            signatures={f"n{N_SHARDS}xB{MESH_B}": sig},
            counters=counters,
            allowed_casts=allowed_casts,
            perturbations={},
            # One canonical signature; mesh callers always normalize
            # `now` (np.int64 at every call site), so no weak variant.
            recompile_budget=1,
            expect_aliased=donated,
        )

    return KernelSpec(name=name,
                      where="gubernator_tpu/parallel/sharded.py",
                      build=build)


def _packed_grid():
    return _sharded(
        np.zeros((12, N_SHARDS, MESH_B), np.int64), (None, "shard")
    )


def _row_grid(make_rows):
    rows = make_rows(N_SHARDS * MESH_B)
    return type(rows)(*[
        _sharded(np.asarray(a).reshape(N_SHARDS, MESH_B), ("shard",))
        for a in rows
    ])


def _hash_grid():
    return _sharded(np.zeros((N_SHARDS, MESH_B), np.int64), ("shard",))


def _delta_grid():
    from gubernator_tpu.parallel.global_sync import zero_delta_grid

    grid = zero_delta_grid(N_SHARDS, MESH_B)
    return type(grid)(*[_sharded(a, ("shard",)) for a in grid])


def _global_sync_spec(psum: bool = False) -> KernelSpec:
    def build() -> BuiltKernel:
        from gubernator_tpu.parallel.global_sync import (
            make_global_sync_step,
            make_global_sync_step_psum,
        )

        factory = make_global_sync_step_psum if psum else (
            make_global_sync_step
        )
        fn = factory(_mesh(), WAYS)

        def sig():
            return (_mesh_table(), _mesh_table(), _delta_grid(), _now())

        return BuiltKernel(
            fn=fn,
            trace_fn=fn,
            signatures={f"n{N_SHARDS}xD{MESH_B}": sig},
            counters=_TABLE_COUNTERS + _BATCH_COUNTERS + (
                ".limit", ".duration", "[3]",
            ),
            # Two apply_batch passes ride inside the sync step (no float
            # cast: ops/f64bits.py); the writes split 9 + 9 auth columns
            # and the cache table's 5 (its zeroed remaining_f is a
            # constant, no counter lineage).  The
            # psum form swaps the aggregation collective (one psum vs
            # all_to_all + sort/segment), not the apply passes — but
            # its aggregated hits arrive through _psum_mod64's four
            # 16-bit limbs per value (XLA:TPU lowers no 64-bit integer
            # all-reduce, PERF.md PR 21): 7 values x 4 masked, lossless
            # u64->u32 narrowings, licensed here, after which the
            # aggregate is no longer tainted lineage — so only the
            # table-side splits (18) are charged.
            # Both forms locate a slot three times (two applies, one
            # store): three bucket narrowings, charged where the keys
            # are still tainted lineage (not behind the psum's limbs).
            allowed_casts=(
                {"to_i32": 28, "split64": 18} if psum
                else {"to_i32": 3 * _BUCKET_I32, "split64": 23}
            ),
            perturbations={},
            recompile_budget=1,
            expect_aliased=42,  # auth + cache tables, 21 leaves each
        )

    return KernelSpec(
        name="global_sync_step_psum" if psum else "global_sync_step",
        where="gubernator_tpu/parallel/global_sync.py",
        build=build,
    )


def _sketch_multi_spec() -> KernelSpec:
    def build() -> BuiltKernel:
        from gubernator_tpu.ops.sketch import cms_step_scatter_impl
        from gubernator_tpu.runtime.sketch_backend import make_multi_step

        fn = make_multi_step(cms_step_scatter_impl)

        def sig(k):
            return lambda: (
                _sketch_state(),
                np.zeros((k, SKETCH_B), np.int64),
                np.zeros((k, SKETCH_B), np.int32),
                np.zeros((k, SKETCH_B), np.int32),
                _now(),
            )

        return BuiltKernel(
            fn=fn,
            trace_fn=fn,
            signatures={"k1": sig(1), "k2": sig(2)},
            counters=_SKETCH_COUNTERS,
            allowed_casts=dict(_SKETCH_CASTS),
            perturbations={"weak-now": lambda: sig(1)()[:4] + (0,)},
            recompile_budget=3,
            expect_aliased=4,
        )

    return KernelSpec(
        name="sketch_multi_step",
        where="gubernator_tpu/runtime/sketch_backend.py",
        build=build,
    )


def specs() -> List[KernelSpec]:
    """Every registered kernel (build lazily; order = report order)."""

    def f_step(name):
        import gubernator_tpu.parallel.sharded as sh

        return {
            "sharded_step_packed":
                lambda: sh.make_sharded_step_packed(_mesh(), WAYS),
            "sharded_probe": lambda: sh.make_sharded_probe(_mesh(), WAYS),
            "sharded_gather":
                lambda: sh.make_sharded_gather(_mesh(), WAYS),
            "sharded_table_stats":
                lambda: sh.make_sharded_table_stats(_mesh(), WAYS),
        }[name]

    def row_factory(impl_name, row_type_name):
        def make():
            import gubernator_tpu.ops.step as step
            import gubernator_tpu.parallel.sharded as sh

            return sh.make_sharded_row_op(
                _mesh(), WAYS, getattr(step, impl_name),
                getattr(step, row_type_name),
            )

        return make

    def demote_factory():
        import gubernator_tpu.parallel.sharded as sh

        return sh.make_sharded_demote_extract(_mesh(), WAYS, MESH_B)

    return [
        # -- ops/step.py: the exact-tier table kernels ------------------
        _step_spec(
            "apply_batch", "apply_batch", "apply_batch_impl",
            lambda B: (_device_batch(B),),
            _APPLY_COUNTERS, dict(_APPLY_CASTS), donated=21,
        ),
        _step_spec(
            "load_rows", "load_rows", "load_rows_impl",
            lambda B: (_bucket_rows(B),),
            _TABLE_COUNTERS + (".key_hash", ".limit", ".duration", "[2]"),
            # all nine 64-bit columns (remaining_f's bits among them)
            {"to_i32": _BUCKET_I32, "split64": 9}, donated=21,
        ),
        _step_spec(
            "probe_batch", "probe_batch", "probe_batch_impl",
            lambda B: (np.zeros(B, np.int64),),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=0,
        ),
        _step_spec(
            "gather_rows", "gather_rows", "gather_rows_impl",
            lambda B: (np.zeros(B, np.int64),),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=0,
        ),
        _step_spec(
            "store_cached_rows", "store_cached_rows",
            "store_cached_rows_impl",
            lambda B: (_cached_rows(B),),
            _TABLE_COUNTERS + (".key_hash", ".reset_time", "[2]"),
            # key, limit, remaining, expire_at, touched; duration, t0
            # and burst are written as constant zeros (no lineage).
            {"to_i32": _BUCKET_I32, "split64": 5}, donated=21,
        ),
        _step_spec(
            "apply_batch_packed_q", "apply_batch_packed_q",
            "apply_batch_packed_q_impl",
            lambda B: (np.zeros((12, B), np.int64),),
            _TABLE_COUNTERS + ("[1]", "[2]"),
            dict(_APPLY_Q_CASTS), donated=21,
        ),
        # -- ops/state.py: live-migration row kernels -------------------
        _migrate_spec(
            "migrate_extract", "migrate_extract", "migrate_extract_impl",
            lambda B: (np.zeros(B, np.int64),),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=21,
        ),
        _migrate_spec(
            "migrate_inject", "migrate_inject", "migrate_inject_impl",
            lambda B: (_bucket_rows(B),),
            _TABLE_COUNTERS + (".key_hash", ".limit", ".duration", "[2]"),
            {"to_i32": _BUCKET_I32, "split64": 11}, donated=21,
        ),
        # -- ops/state.py: the tier demotion kernel (docs/tiering.md) --
        # Same gather+clear atomicity shape as migrate_extract, but the
        # DEVICE names the victims: the B here sizes the replicated
        # protect grid; the packed batch rides the static default.
        _migrate_spec(
            "demote_extract", "demote_extract", "demote_extract_impl",
            lambda B: (np.zeros(B, np.int64),),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=21,
        ),
        # -- ops/state.py: the gubstat state census ---------------------
        _table_stats_spec(),
        # -- ops/sketch.py + the fused Pallas form ----------------------
        _sketch_spec("cms_step_onehot", "cms_step_onehot",
                     "cms_step_impl"),
        _sketch_spec("cms_step", "cms_step", "cms_step_scatter_impl"),
        _sketch_spec("cms_step_pallas", "cms_step_pallas",
                     "cms_step_pallas_impl"),
        # -- parallel/: the mesh engine ---------------------------------
        _mesh_spec(
            "sharded_step_packed", f_step("sharded_step_packed"),
            lambda: (_packed_grid(),),
            _TABLE_COUNTERS + ("[1]", "[2]"),
            dict(_APPLY_Q_CASTS), donated=21,
        ),
        _mesh_spec(
            "sharded_load_rows",
            row_factory("load_rows_impl", "BucketRows"),
            lambda: (_row_grid(_bucket_rows),),
            _TABLE_COUNTERS + (".key_hash", ".limit", ".duration", "[2]"),
            {"to_i32": _BUCKET_I32, "split64": 9}, donated=21,
        ),
        _mesh_spec(
            "sharded_store_cached",
            row_factory("store_cached_rows_impl", "CachedRows"),
            lambda: (_row_grid(_cached_rows),),
            _TABLE_COUNTERS + (".key_hash", ".reset_time", "[2]"),
            {"to_i32": _BUCKET_I32, "split64": 5}, donated=21,
        ),
        _mesh_spec(
            "sharded_probe", f_step("sharded_probe"),
            lambda: (_hash_grid(),),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=0,
        ),
        _mesh_spec(
            "sharded_gather", f_step("sharded_gather"),
            lambda: (_hash_grid(),),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=0,
        ),
        _mesh_spec(
            "sharded_demote_extract", demote_factory,
            lambda: (np.zeros(8, np.int64), np.int32(MESH_B), np.int32(0)),
            _TABLE_COUNTERS + ("[1]", "[2]"), {}, donated=21,
        ),
        _mesh_spec(
            "sharded_table_stats", f_step("sharded_table_stats"),
            lambda: (np.zeros((4, 8), np.int64),),
            _TABLE_COUNTERS + ("[1]", "[2]"),
            {}, donated=0,
        ),
        _global_sync_spec(),
        _global_sync_spec(psum=True),
        # -- runtime/sketch_backend.py: the merge-scan step -------------
        _sketch_multi_spec(),
    ]


def registered_names() -> List[str]:
    return [s.name for s in specs()]
