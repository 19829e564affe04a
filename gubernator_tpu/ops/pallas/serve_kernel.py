"""Persistent decision kernel: ONE Pallas launch drains a whole request
queue (docs/ring.md's "kill the last dispatch" direction).

Every ring iteration — even a megaround block — is still one XLA entry:
a host->device dispatch with a fixed cost per entry (not measured on a
directly attached chip yet).  This kernel is the next structural step: a
long-lived `pallas_call` that OWNS the table block for the duration of
the launch and drains a device-resident request queue of `k` stacked
rounds across its sequential grid steps — the table lives in the
kernel's output refs from round to round (one HBM round trip per LAUNCH
instead of one XLA entry per ROUND), responses land in a device-resident
response queue, and the sequence word is written by the kernel itself so
the host response protocol is unchanged.

Decision semantics are INHERITED, not re-implemented: each grid step
reads the table refs and applies `ops/step.apply_batch_packed_q_impl` —
the exact body the ring scan runs — so the bit-exact differential
against `ring_step` (tests/test_serve_kernel.py) holds by construction.
The contract is ring_step's:

    table', resps[k, 9, B], seq' = persistent_serve_step(
        table, qs[k, 12, B], nows[k], seq)

CAPABILITY HONESTY (the GUBER_SERVE_MODE=persistent gate): the decision
body leans on gather/scatter patterns Mosaic cannot lower on every
toolchain, so `persistent_supported()` PROBES an actual compile on the
attached backend and reports the real outcome — a CPU backend reports
interpret-only (the emulation path the differential tests pin), and a
TPU whose Mosaic rejects the body reports the compiler's reason.  The
runtime (runtime/fastpath.py) degrades to megaround automatically in
both cases and surfaces the reason in /debug/vars.  This is a
PROTOTYPE of the decision loop's persistent form, not yet the
host-pinned-DMA ring of docs/ring.md's end state: the request queue is
still delivered per launch, but all `k` rounds inside it are served
without re-entering XLA dispatch.
"""
from __future__ import annotations

import functools
import os
import traceback
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.state import SlotTable, init_table
from gubernator_tpu.ops.step import apply_batch_packed_q_impl

_I0 = np.int32(0)  # i32 index-map constant (cms_kernel's x64 rule)


def _serve_kernel(ways, treedef, *refs):
    """One grid step = one packed round against the kernel-resident
    table.  Refs: (qs, nows, seq, table leaves in) then
    (table leaves out, resps, seq out).  The table accumulates in the
    OUT refs across sequential grid steps (the cms_kernel pattern), so
    round b observes rounds [0, b)'s effects exactly like the ring
    scan's carry."""
    n = treedef.num_leaves  # physical leaves: an int64 field is two
    q_ref, now_ref, seq_ref = refs[0:3]
    tin = refs[3:3 + n]
    tout = refs[3 + n:3 + 2 * n]
    resp_ref = refs[3 + 2 * n]
    seq_out_ref = refs[4 + 2 * n]
    b = pl.program_id(0)
    k = pl.num_programs(0)

    @pl.when(b == jnp.int32(0))
    def _init():
        for i_ref, o_ref in zip(tin, tout):
            o_ref[...] = i_ref[...]
        # The kernel writes the advanced sequence word itself — the
        # host response protocol (fetch resps + seq in one transfer,
        # verify against the mirror) is unchanged from ring_step.
        seq_out_ref[...] = seq_ref[...] + jnp.int64(k)

    table = treedef.unflatten([o_ref[...] for o_ref in tout])
    tbl2, resp = apply_batch_packed_q_impl(
        table, q_ref[0], now_ref[0], ways=ways
    )
    for o_ref, col in zip(tout, jax.tree_util.tree_leaves(tbl2)):
        o_ref[...] = col
    resp_ref[0, :, :] = resp


def persistent_serve_step_impl(
    table: SlotTable,
    qs: jax.Array,    # int64[k, 12, B] — the device-resident queue
    nows: jax.Array,  # int64[k]
    seq: jax.Array,   # int64[] — the ring sequence word
    ways: int = 8,
    interpret: bool = False,
) -> Tuple[SlotTable, jax.Array, jax.Array]:
    """Drain `k` packed rounds in ONE kernel launch; returns
    (new_table, int64[k, 9, B] packed responses, seq + k) — the
    ring_step contract, differentially pinned bit-exact."""
    k, rows, B = qs.shape
    S = table.key.shape[0]
    leaves, treedef = jax.tree_util.tree_flatten(table)
    n = len(leaves)
    seq1 = jnp.asarray(seq, dtype=jnp.int64).reshape(1)

    def col_spec():
        return pl.BlockSpec((S,), lambda b: (_I0,))

    outs = pl.pallas_call(
        functools.partial(_serve_kernel, ways, treedef),
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, rows, B), lambda b: (b, _I0, _I0)),
            pl.BlockSpec((1,), lambda b: (b,)),
            pl.BlockSpec((1,), lambda b: (_I0,)),
        ] + [col_spec() for _ in range(n)],
        out_specs=[col_spec() for _ in range(n)] + [
            pl.BlockSpec((1, 9, B), lambda b: (b, _I0, _I0)),
            pl.BlockSpec((1,), lambda b: (_I0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S,), a.dtype) for a in leaves
        ] + [
            jax.ShapeDtypeStruct((k, 9, B), jnp.int64),
            jax.ShapeDtypeStruct((1,), jnp.int64),
        ],
        # The table outputs are revisited by every grid step
        # (accumulation), so the grid must be sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        jnp.asarray(qs, dtype=jnp.int64),
        jnp.asarray(nows, dtype=jnp.int64),
        seq1,
        *leaves,
    )
    return (
        treedef.unflatten(outs[:n]),
        outs[n],
        outs[n + 1][0],
    )


persistent_serve_step = jax.jit(
    persistent_serve_step_impl,
    static_argnames=("ways", "interpret"),
    donate_argnums=(0,),
)


def probe_compile(
    num_slots: int = 256, ways: int = 8, batch: int = 8
) -> Tuple[bool, str]:
    """Attempt an ACTUAL (non-interpret) lowering + compile of the
    kernel on the default backend, abstractly (no device memory is
    allocated).  Returns (ok, reason) — the honest capability signal
    GUBER_SERVE_MODE=persistent gates on."""
    table = jax.eval_shape(lambda: init_table(num_slots))
    try:
        persistent_serve_step.lower(
            table,
            jax.ShapeDtypeStruct((2, 12, batch), jnp.int64),
            jax.ShapeDtypeStruct((2,), jnp.int64),
            jax.ShapeDtypeStruct((), jnp.int64),
            ways=ways,
        ).compile()
    except Exception as e:  # noqa: BLE001 — the reason IS the signal
        # The innermost frame says whose code refused (the lowering's
        # own errors carry no location in their message).
        at = traceback.extract_tb(e.__traceback__)[-1]
        return False, (
            "persistent serve kernel failed to compile: "
            f"{type(e).__name__}: {e} (raised in {at.name} at "
            f"{os.path.basename(at.filename)}:{at.lineno})"
        )
    return True, ""


def persistent_supported(
    platform: str, num_slots: int, ways: int, batch: int
) -> Tuple[bool, str]:
    """Capability report for a backend on `platform` at ITS table and
    batch geometry (a kernel that compiles at a toy size says nothing
    about 2^24 slots held in VMEM): only a real TPU may even attempt the
    Mosaic compile — CPU/GPU report the interpret gap honestly instead
    of shipping an emulated 'persistent' mode that is slower than the
    scan it replaces."""
    if platform != "tpu":
        return False, (
            "persistent serve kernel needs a TPU backend (running on "
            f"{platform!r}; interpret mode serves the differential "
            "tests only)"
        )
    return probe_compile(num_slots, ways, batch)
