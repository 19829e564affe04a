#!/usr/bin/env python3
"""A drain's response buffers, device to host: one concatenate or a copy each.

    chiprun --timeout 600 -- python scripts/fetch_rounds_chip.py
    JAX_PLATFORMS=cpu python scripts/fetch_rounds_chip.py --platform cpu \\
        --reps 20                                       # dry run here

A drain of R rounds hands `runtime/backend.py` `fetch_ravel` R response
buffers, int64[9, tier] each.  Two ways to bring them to the host:

  concat  ravel each, concatenate on the device, fetch the one result:
          the form `fetch_ravel` had until PR 39.  XLA compiles one
          program per SEQUENCE of round tiers, on the request path the
          first time a sequence is met.
  each    start every buffer's copy (`copy_to_host_async`), then read
          them: no program at all.

For each sequence of round tiers, with NO persistent compile cache (the
variable is dropped and no directory is set, so that `first_ms` is a
compile and not a load): `first_ms`, the first call; then over --reps fresh
sets of buffers `ready_ms`, the median call on buffers that are already
computed (the fetch alone), and `behind_ms`, the median call made straight
after the programs that fill them were enqueued (the fetch as a drain
meets it), per form.  One JSON object on the last line of stdout (also
--out/summary.json).

It fails where there is no TPU unless `--platform cpu` is given, and a
number from such a run is a rehearsal, not a device time.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# 6, 7, 8 rounds of the small tier: the wire check's forwards (PERF.md
# section 6, PR 39); the others are the batch cells' and the zipf cells'.
SEQUENCES = (
    (128, 128), (4096, 128), (4096, 4096), (4096, 4096, 4096, 4096, 128),
    (128,) * 6, (128,) * 7, (128,) * 8,
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--out", default=str(REPO / "chiprun_out/fetch_rounds"))
    args = ap.parse_args()
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"wanted {args.platform}, got {dev.platform}", file=sys.stderr)
        return 1

    def concat(arrs):
        host = np.asarray(jnp.concatenate([a.ravel() for a in arrs]))
        out, off = [], 0
        for a in arrs:
            out.append(host[off:off + a.size].reshape(a.shape))
            off += a.size
        return out

    def each(arrs):
        for a in arrs:
            a.copy_to_host_async()
        return [np.asarray(a) for a in arrs]

    fill = jax.jit(lambda x, k: x + k)
    bases = {
        t: jax.device_put(
            np.arange(9 * t, dtype=np.int64).reshape(9, t), dev
        ) for t in (128, 4096)
    }

    def timed(form, seq, k, ready: bool) -> float:
        arrs = [fill(bases[t], np.int64(k)) for t in seq]
        if ready:
            jax.block_until_ready(arrs)
        t0 = time.perf_counter()
        got = form(arrs)
        ms = 1e3 * (time.perf_counter() - t0)
        assert all(
            g.shape == (9, t) and g[0, 1] == 1 + k for g, t in zip(got, seq)
        )
        return ms

    for t in bases:                      # `fill` compiled before any timing
        jax.block_until_ready(fill(bases[t], np.int64(0)))
    rows = []
    for seq in SEQUENCES:
        row = {"rounds": list(seq)}
        for name, form in (("concat", concat), ("each", each)):
            first = timed(form, seq, 1, True)
            ready = [timed(form, seq, 2 + k, True) for k in range(args.reps)]
            behind = [timed(form, seq, 2 + k, False)
                      for k in range(args.reps)]
            row[name] = {
                "first_ms": round(first, 3),
                "ready_ms": round(statistics.median(ready), 4),
                "behind_ms": round(statistics.median(behind), 4),
                "behind_max_ms": round(max(behind), 3),
            }
        rows.append(row)
        print(json.dumps(row), file=sys.stderr)
    summary = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "reps": args.reps, "sequences": rows,
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
