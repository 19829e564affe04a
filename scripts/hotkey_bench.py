#!/usr/bin/env python3
"""The hot-key detector's per-batch update, native pass against numpy form,
with no chip.

    python scripts/hotkey_bench.py                # here: CPU numbers
    python scripts/hotkey_bench.py --sizes 2,750 --seconds 1.0

`HotKeyTracker.observe` runs once a served batch on the event loop, inside
`wire.ingress` (stage `host.hotkey`).  Until PR 49 its sketch update was
some forty numpy calls whose cost did not depend on the batch (`HostCMS`
`update` + `estimate`, kept as `HotKeyTracker._sketch_numpy`, the
reference); it is one native pass now (native/gubtpu.cpp
gub_hotkey_observe, `native.HotkeyPass`).  This times a whole `observe` —
clock, lock, window roll, the pass, the candidates — in us a call, at the
cells' batch sizes (2 fingerprints an RPC in `rpc2.open` and the peers RPC
cell, 16 in the zipf cells, 750 in the batch cells; 5,000 is a drain's
worth), on fresh uniform fingerprints, as the cells' universes give:

  numpy          the reference form;
  native.held    the pass with the GIL held (ctypes.PyDLL): what `observe`
                 does for a batch of at most `native.HOTKEY_HOLD_GIL_UP_TO`
                 (128) fingerprints;
  native.freed   the same symbol through ctypes.CDLL, which releases the
                 GIL for the call and takes it back after: what it does
                 for a longer one.

Each is timed alone (the median) and beside TWO Python threads that do
nothing but want the GIL (the mean: a call either runs through or waits
out switch intervals, and the mean is what an RPC pays).  A spinner never
yields, so it is the worst neighbour the loop can have: a call that gives
the GIL away waits a switch interval (5 ms) or two to have it back, a
call that keeps it is interrupted only when its own interval ends.  Held
reads lower than freed here at EVERY size, 750 and 5,000 too — and that
is the half this script can see: what one `observe` costs the thread that
makes it.  The other half is what the OTHER threads gain from the release,
which only a serving daemon shows: on the chip the batch cell, 750
fingerprints an RPC, answered 5-9 % fewer checks than the parent with the
pass held and 2-6 % more with it freed, while the cells of 2 and 16
fingerprints an RPC did better held (PERF.md section 5.14, where the
choice by length is written down).

These are CPU numbers of this machine, for the shape of the change: they
are not the chip host's (gVisor, shared cores: the same stages read 3-6x
slower there) and never stand under a device metric's name.  One JSON line
a size on stdout.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from gubernator_tpu import native  # noqa: E402
from gubernator_tpu.core.config import HotKeyConfig  # noqa: E402
from gubernator_tpu.runtime.hotkey import HotKeyTracker  # noqa: E402

POOL = 512  # distinct batches a size, sent in turn


class Spinners:
    """Python threads that do nothing but want the GIL."""

    def __init__(self, k: int) -> None:
        self._stop = False
        self._ts = [
            threading.Thread(target=self._run, daemon=True) for _ in range(k)
        ]

    def _run(self) -> None:
        while not self._stop:
            pass

    def __enter__(self):
        for t in self._ts:
            t.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop = True
        for t in self._ts:
            t.join()


def tracker(form: str) -> HotKeyTracker:
    """A tracker whose observe runs the numpy form, or the pass through
    ONE of its two bindings whatever the batch's length."""
    tr = HotKeyTracker(HotKeyConfig())
    if form == "numpy":
        tr._native_pass = None
    elif form == "native.held":
        tr._native_pass._freed = tr._native_pass._held
    else:
        tr._native_pass._held = tr._native_pass._freed
    return tr


def timed(tr: HotKeyTracker, batches, hits, seconds: float):
    """(us a call alone, the median; us a call beside two spinners, the
    mean over at least `seconds`)."""
    k = len(batches)
    alone = []
    t_end = time.perf_counter() + seconds
    i = 0
    while time.perf_counter() < t_end:
        t0 = time.perf_counter()
        tr.observe(batches[i % k], hits)
        alone.append(time.perf_counter() - t0)
        i += 1
    with Spinners(2):
        time.sleep(0.05)
        t0 = time.perf_counter()
        calls = 0
        while time.perf_counter() - t0 < seconds:
            tr.observe(batches[calls % k], hits)
            calls += 1
        beside = (time.perf_counter() - t0) / calls
    return (round(statistics.median(alone) * 1e6, 2),
            round(beside * 1e6, 2), calls)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default="2,16,750,5000",
                    help="fingerprints a batch, comma separated")
    ap.add_argument("--seconds", type=float, default=0.5,
                    help="the length of each timing")
    ap.add_argument("--seed", type=int, default=49)
    args = ap.parse_args()
    native.require()
    rng = np.random.default_rng(args.seed)
    for n in (int(x) for x in args.sizes.split(",")):
        pool = rng.integers(1, (1 << 63) - 1, (POOL, n), dtype=np.int64)
        batches = [pool[i] for i in range(POOL)]
        hits = np.ones(n, dtype=np.int64)
        line = {"fingerprints": n, "what": "cpu_us_a_call",
                "switch_interval_ms": sys.getswitchinterval() * 1e3}
        for form in ("numpy", "native.held", "native.freed"):
            alone, beside, calls = timed(
                tracker(form), batches, hits, args.seconds)
            line[form] = {"alone_us": alone, "beside_2_spinners_us": beside,
                          "calls_beside": calls}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
