#!/usr/bin/env python3
"""Lay the program's clock on the profiler's trace, and say how well it fits.

The stage ledger (gubernator_tpu/runtime/tracing.py) stamps its rows with
time.perf_counter_ns() and its OTel spans with time.time_ns().  The
profiler's .xplane.pb holds times RELATIVE TO THE SESSION'S START (measured
here, not assumed: a host event 90 us into a trace reads start_ns = 92594,
not an epoch value), so a span can be laid on a trace only through an
anchor.  A stage opened with `anchor=True` carries time.time_ns() at its
start as the argument `t_ns` of its profiler event: origin = t_ns -
event.start_ns is the session's start on the epoch clock, and the spread
of that origin over many anchors is how well the two clocks agree.

    python scripts/clock_offset.py [--n 200]

prints one JSON object as its last line: the origin, the deviation of the
anchors' origins from their median in microseconds, and how long after an
anchored dispatch its program starts on the device (same clock: small and
positive).  Run it where the number matters (on the chip, through the chip
tool): it uses whatever device JAX finds and says which.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from gubernator_tpu.runtime import tracing

    ledger = tracing.StageLedger()
    step = jax.jit(lambda x: x * 2 + 1)
    x = step(jnp.arange(1024)).block_until_ready()
    with tempfile.TemporaryDirectory(prefix="gub-clock-") as d:
        jax.profiler.start_trace(d)
        try:
            for _ in range(args.n):
                with ledger.stage("global.sync_tick", "global", anchor=True):
                    x = step(x)
                x.block_until_ready()
                time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(
            os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")
        )
        anchors, device = [], []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if plane.name == "/host:CPU":
                        if ev.name == "gub.global.sync_tick":
                            t_ns = int(dict(ev.stats)["t_ns"])
                            start = int(ev.start_ns)
                            anchors.append((start, t_ns - start))
                    elif plane.name.startswith("/device:") and (
                        line.name == "XLA Modules"
                    ):
                        device.append(int(ev.start_ns))
    anchors.sort()
    device.sort()
    origins = [o for _s, o in anchors]
    origin = statistics.median(origins)
    dev_us = [(o - origin) / 1e3 for o in origins]
    q = statistics.quantiles(dev_us, n=4)
    # Each anchored stage dispatches one program: the device event that
    # starts next after it, on the same (session-relative) clock.
    lags = []
    k = 0
    for start, _o in anchors:
        while k < len(device) and device[k] < start:
            k += 1
        if k < len(device):
            lags.append((device[k] - start) / 1e3)
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "anchors": len(anchors),
        "first_event_start_ns": anchors[0][0],
        "origin_epoch_ns": origin,
        "origin_deviation_us": {
            "min": min(dev_us), "q1": q[0], "q3": q[2], "max": max(dev_us),
        },
        "drift_us_first_to_last": dev_us[-1] - dev_us[0],
        "device_start_after_dispatch_us": (
            {"median": statistics.median(lags), "min": min(lags),
             "max": max(lags), "n": len(lags)} if lags else None
        ),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
