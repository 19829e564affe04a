"""Fused count-min-sketch step as a Pallas TPU kernel.

Semantic reference: gubernator_tpu.ops.sketch.cms_step_impl — same contract,
differentially tested (tests/test_sketch.py).

Fusion story: the XLA path materializes [D, B, W] one-hot tensors in HBM
(32MB+ at B=1024, W=8192) and runs 2D einsums over them.  This kernel
streams the batch through VMEM in blocks: per block it builds each row's
[BLK, W] one-hot on the fly, runs the read-gather and add-scatter as MXU
matmuls against the VMEM-resident sketch, and accumulates the new sketch in
the output ref across sequential grid steps — one HBM round-trip for the
sketch per batch instead of one per einsum operand.

Decisions read the PRE-batch sketch for every block (cur stays an input;
updates accumulate in out_cur), matching the reference semantics exactly.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.sketch import SketchState, _rotate, row_columns

# 128 keeps the [BLK, W] one-hot at 4MB — safely under the 16MB VMEM
# scoped limit with double buffering — and measured fastest on v5e
# (49.6M decisions/s vs 34.2M at 256; 512 OOMs VMEM).
DEFAULT_BLOCK = 128

_I0 = np.int32(0)  # i32 index-map constant (see in_specs note below)


def _cms_kernel(
    overlap_ref,   # VMEM f32[1, 1]
    cur_ref,       # VMEM i32[D, W]      (whole sketch, every step)
    prev_ref,      # VMEM i32[D, W]
    cols_ref,      # VMEM i32[D, BLK]    (this block's columns)
    hits_ref,      # VMEM f32[1, BLK]
    limit_ref,     # VMEM f32[1, BLK]
    active_ref,    # VMEM f32[1, BLK]    (1.0 / 0.0)
    out_cur_ref,   # VMEM i32[D, W]      (accumulated across steps)
    over_ref,      # VMEM f32[1, BLK]
    est_ref,       # VMEM f32[1, BLK]
):
    b = pl.program_id(0)
    depth, width = cur_ref.shape
    blk = cols_ref.shape[1]

    @pl.when(b == jnp.int32(0))
    def _init():
        out_cur_ref[:, :] = cur_ref[:, :]

    # NOTE: x64 mode is on process-wide; bare Python literals would become
    # f64/i64 and 64-bit vectors crash the TPU vector-layout pass.  Keep
    # every in-kernel constant explicitly 32-bit.
    zero_f = jnp.float32(0.0)
    overlap = overlap_ref[0, 0]
    hits = hits_ref[0, :]                     # f32[BLK]
    active = active_ref[0, :]                 # f32[BLK]
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (blk, width), 1)

    est = jnp.full((blk,), 3.0e38, dtype=jnp.float32)
    for d in range(depth):
        cols_d = cols_ref[d, :]               # i32[BLK]
        onehot = (
            (col_iota == cols_d[:, None]) & (active[:, None] > zero_f)
        ).astype(jnp.float32)                 # [BLK, W]
        eff_d = (
            cur_ref[d, :].astype(jnp.float32)
            + prev_ref[d, :].astype(jnp.float32) * overlap
        )                                     # [W]
        # Read-gather: MXU matvec [BLK,W] @ [W,1].
        reads = jnp.dot(
            onehot, eff_d[:, None], preferred_element_type=jnp.float32
        )[:, 0]
        est = jnp.minimum(est, reads)
        # Add-scatter: MXU matvec [1,BLK] @ [BLK,W].
        upd = jnp.dot(
            hits[None, :], onehot, preferred_element_type=jnp.float32
        )[0]                                  # [W]
        out_cur_ref[d, :] = out_cur_ref[d, :] + upd.astype(jnp.int32)

    est = jnp.where(active > zero_f, est, zero_f)
    over = (
        (active > zero_f)
        & (hits > zero_f)
        & (est + hits > limit_ref[0, :])
    ).astype(jnp.float32)
    over_ref[0, :] = over
    est_ref[0, :] = est


def cms_step_pallas_impl(
    state: SketchState,
    key_hash: jax.Array,
    hits: jax.Array,
    limit: jax.Array,
    now: jax.Array,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> Tuple[SketchState, jax.Array, jax.Array]:
    depth, width = state.cur.shape
    B = key_hash.shape[0]
    if B % block:
        raise ValueError(f"batch ({B}) must be a multiple of block ({block})")
    state, overlap = _rotate(state, now)
    active = key_hash != 0
    cols = row_columns(key_hash, depth, width)           # [D, B]

    grid = (B // block,)
    new_cur, over_f, est_f = pl.pallas_call(
        _cms_kernel,
        grid=grid,
        # Index-map constants must be explicit i32: under x64 a bare Python
        # 0 traces as i64 inside the Mosaic grid loop and fails to legalize
        # ("func.return ... (i32, i64)").
        in_specs=[
            pl.BlockSpec((1, 1), lambda b: (_I0, _I0)),
            pl.BlockSpec((depth, width), lambda b: (_I0, _I0)),
            pl.BlockSpec((depth, width), lambda b: (_I0, _I0)),
            pl.BlockSpec((depth, block), lambda b: (_I0, b)),
            pl.BlockSpec((1, block), lambda b: (_I0, b)),
            pl.BlockSpec((1, block), lambda b: (_I0, b)),
            pl.BlockSpec((1, block), lambda b: (_I0, b)),
        ],
        out_specs=[
            pl.BlockSpec((depth, width), lambda b: (_I0, _I0)),
            pl.BlockSpec((1, block), lambda b: (_I0, b)),
            pl.BlockSpec((1, block), lambda b: (_I0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((depth, width), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.float32),
            jax.ShapeDtypeStruct((1, B), jnp.float32),
        ],
        # The sketch output is revisited by every grid step (accumulation),
        # so the grid must be sequential, not parallel.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        overlap.astype(jnp.float32)[None, None],
        state.cur,
        state.prev,
        cols,
        hits.astype(jnp.float32)[None, :],
        limit.astype(jnp.float32)[None, :],
        active.astype(jnp.float32)[None, :],
    )
    return (
        SketchState(new_cur, state.prev, state.window_start, state.window_ms),
        over_f[0] > 0.0,
        est_f[0].astype(jnp.int32),
    )


cms_step_pallas = jax.jit(
    cms_step_pallas_impl, static_argnames=("block", "interpret"),
    donate_argnums=(0,),
)
