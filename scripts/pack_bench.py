#!/usr/bin/env python3
"""A drain's pack and unpack, served form against reference, with no chip.

    python scripts/pack_bench.py                 # here: CPU numbers
    python scripts/pack_bench.py --reps 200 --shapes 4100x1,4500x4

The compiled lane's `lane.pack` and `lane.unpack` are one native call each
(native/gubtpu.cpp gub_pack_rounds / gub_gather_rounds, the GIL released
for their length); until PR 42 they were some sixty numpy calls and one
Python loop a drain, which `runtime/fastpath.py` keeps as the reference
the tests hold the native pass to (`_reference_pack`: `_plan_cascade`,
`_cascade_or_rounds`, `_build_rounds`, `pack_batch_q` / `pack_grid_batch`;
`_reference_unpack`: the per-round gather, `tally_from_rounds`, the
`last_of` loop).  This times both on synthetic
drains at the cells' shapes — 4, 64, 1,100 and 4,100 checks on one shard,
4,500 on four; one key twice in each, as the batch cell's drains have it —
and prints ms a drain: the median of --reps alone, the mean beside the
spinner (there a call either runs through or waits out a switch interval:
the mean is what a drain pays).

Each form is timed twice: alone, and with a second Python thread spinning
on the GIL.  That thread counts its own loops, `spinner_kept` being the
share of its own pace it kept while the other thread packed: a native
call leaves it the GIL for the call's length, a numpy pass gives it up
some seventy times a drain and each time waits out the switch interval
(5 ms) to have it back, so the spinner keeps its pace under BOTH forms
(0.65-1.0 here) — what tells them apart is `beside_spinner_ms`, what one
pack or unpack then costs the thread that makes it: one wait a native
call, one a release for the numpy form (5 ms against 300-500 ms at 4,100
checks here).  A spinner never yields, so it is the worst neighbour a
lane thread can have; the event loop and the other lanes' threads want
the GIL for a part of the time, and the chip's ledgers say how much that
cost (PERF.md section 5.9).

These are CPU numbers of this machine, for the shape of the change: they
are not the chip host's (gVisor, shared cores: the same stages read 3-6x
slower there) and never stand under a device metric's name.  One JSON line
a shape on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from gubernator_tpu import native  # noqa: E402
from gubernator_tpu.core.types import Behavior  # noqa: E402
from gubernator_tpu.parallel.mesh import _SHARD_SHIFT  # noqa: E402
from gubernator_tpu.runtime import fastpath  # noqa: E402
from gubernator_tpu.runtime.backend import _packed_resp_dict  # noqa: E402

RESET = int(Behavior.RESET_REMAINING)
B, TIERS = 4096, (128, 4096)


def drain(n: int, seed: int):
    """The eleven columns of a drain of n checks, one key twice."""
    rng = np.random.default_rng(seed)
    h = rng.integers(1, (1 << 63) - 1, n, dtype=np.int64)
    if n > 1:
        h[n // 2] = h[0]
    z = np.zeros(n, dtype=np.int64)
    return (
        h, np.ones(n, dtype=np.int64), np.full(n, 1000, dtype=np.int64),
        np.full(n, 60_000, dtype=np.int64), np.zeros(n, dtype=np.int32),
        z.copy(), z.copy(), np.zeros(n, dtype=bool), z.copy(), z.copy(),
        np.zeros(n, dtype=bool),
    )


def served_pack(cols, n_shards):
    return native.pack_rounds(
        *cols, reset_bit=RESET, n_shards=n_shards,
        shard_shift=_SHARD_SHIFT, batch_size=B, tiers=TIERS, mode=1,
        cap_ok=True,
    )


def served_unpack(packed, cols, host):
    got = native.gather_rounds(
        packed, cols[0], [fastpath._resp_words(hr) for hr in host])
    return got.cols, got.over_limit, got.lanes - got.cache_hits


def reference_pack(cols, n_shards):
    """`_process_packed` as it was served until PR 42, to the words handed
    to the device (`pack_batch_q` ran under `backend._lock`)."""
    return fastpath._reference_pack(
        cols, n_shards, B, TIERS, 1, _SHARD_SHIFT)


def reference_unpack(ref, cols, host, n_shards):
    return fastpath._reference_unpack(ref, cols[0], host, n_shards)


class Spinner:
    """A Python thread that does nothing but want the GIL, and counts."""

    def __init__(self) -> None:
        self.loops = 0
        self._stop = False
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop:
            self.loops += 1

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop = True
        self._t.join()


def timed(fn, reps: int):
    """(ms a call alone; ms a call beside a spinner; the share of its own
    pace the spinner kept meanwhile — its pace with the GIL to itself is
    read just before, over 0.1 s).  Beside the spinner the calls go on
    for at least 0.3 s, many switch intervals."""
    fn()
    alone = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        alone.append(time.perf_counter() - t0)
    beside = []
    with Spinner() as sp:
        t0, l0 = time.perf_counter(), sp.loops
        time.sleep(0.1)
        own_pace = (sp.loops - l0) / (time.perf_counter() - t0)
        l0, t_all = sp.loops, time.perf_counter()
        while len(beside) < reps or time.perf_counter() - t_all < 0.3:
            t0 = time.perf_counter()
            fn()
            beside.append(time.perf_counter() - t0)
        kept = (sp.loops - l0) / (time.perf_counter() - t_all) / own_pace
    return (round(statistics.median(alone) * 1e3, 4),
            round(statistics.mean(beside) * 1e3, 4), round(kept, 3))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="4x1,64x1,1100x1,4100x1,4500x4",
                    help="checks x shards, comma separated")
    ap.add_argument("--reps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()
    native.require()
    for shape in args.shapes.split(","):
        n, n_shards = (int(x) for x in shape.split("x"))
        cols = drain(n, args.seed)
        packed = served_pack(cols, n_shards)
        ref = reference_pack(cols, n_shards)
        for a, b in zip(packed.rounds, ref["words"]):
            assert (a == b).all(), "the served pack is not the reference"
        rng = np.random.default_rng(args.seed)
        host = [
            _packed_resp_dict(rng.integers(
                0, 2, (n_shards, 9, t) if n_shards > 1 else (9, t)
            ).astype(np.int64)) for t in packed.tiers
        ]
        line = {"checks": n, "shards": n_shards,
                "rounds": len(packed.rounds), "what": "cpu_ms_a_drain"}
        for name, fn in (
            ("pack.native", lambda: served_pack(cols, n_shards)),
            ("pack.numpy", lambda: reference_pack(cols, n_shards)),
            ("unpack.native", lambda: served_unpack(packed, cols, host)),
            ("unpack.numpy",
             lambda: reference_unpack(ref, cols, host, n_shards)),
        ):
            alone, beside, kept = timed(fn, args.reps)
            line[name] = {"alone_ms": alone, "beside_spinner_ms": beside,
                          "spinner_kept": kept}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
