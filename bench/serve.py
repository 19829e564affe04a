#!/usr/bin/env python3
"""The daemon under test, started the way `python -m gubernator_tpu.cli.server`
starts it (GUBER_* environment -> setup_daemon_config -> Daemon.start), plus
the four things a benchmark run needs from inside the process that holds
the chip:

  preload   the configuration's resident rows, made from the seed by the
            harness and handed over in a file, installed through the
            checkpoint-restore seam (`backend._install_table`, what
            runtime/checkpoint.py calls) instead of 10M checks over gRPC,
            then a seeded sample of keys probed through the program's own
            lookup against the placement arithmetic; of a table that does
            not hold its universe (bench/README.md) also the cold store's
            rows, through the seam a checkpoint restores them by
            (`ColdTier.restore`), the probe saying of each key which tier
            the arithmetic puts it in and which the daemon has it in, and
            the tier's three device programs compiled before the ready
            report, which the daemon's own warm-up leaves to first use;
  warming   every response-fetch executable a drain of the cell's traffic
            can ask for (`fetch_ravel` concatenates one program per
            sequence of round shapes), so that none compiles on the
            request path;
  chip      the ready report says which chip the process holds (what its
            environment pinned, the devices' ids and coordinates), so that
            the harness can refuse a cluster whose daemons share one;
  commands  one JSON object per line on stdin: {"cmd": "trace_start",
            "dir": ...}, {"cmd": "trace_stop"}, {"cmd": "memory"},
            {"cmd": "quit"}; one JSON answer per line on stdout.  The first
            stdout line is the ready report.

Serving itself is untouched: gRPC handlers, compiled lane, default
`pipelined` serve mode.  `--control` breaks the daemon on purpose, to show
that the correctness check fails when it should (never used by a benchmark
run): `f32` is the lower-precision control, the leaky bucket's float64
operands rounded to float32 before use; `alter` changes one answer in 97
where the fetched response is unpacked; `droppromote` is the tiered form's
own: a promote that takes the cold row out of the store and drops it instead
of merging it, so that a key keeps the fresh budget it was served from;
`noforward` gives a daemon of a
cluster a ring of itself alone, so that it serves every check where it
arrives; `oneclock` is no control but a witness (bench/witness/oneclock.py).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, REPO]

import numpy as np  # noqa: E402

from lib import shapes as shapes_mod  # noqa: E402
from lib import universe as universe_mod  # noqa: E402

log = logging.getLogger("bench.serve")


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def apply_control(kind: str) -> None:
    if kind == "f32":
        import jax.numpy as jnp

        import gubernator_tpu.ops.step as step

        step._f64 = lambda x: x.astype(jnp.float32).astype(jnp.float64)
    elif kind == "alter":
        # The broken timed path of bench/tests: one answer in 97 is
        # altered where the fetched response is unpacked.
        import gubernator_tpu.runtime.backend as backend

        unpack = backend._packed_resp_dict

        def altered(a):
            out = dict(unpack(a))
            rem = out["remaining"].copy()
            rem[..., ::97] += 1
            out["remaining"] = rem
            return out

        backend._packed_resp_dict = altered
    elif kind == "droppromote":
        # The tiered form's control: the cold row is popped and dropped, so
        # the merge never lands and every cold hit mints a fresh budget.
        from gubernator_tpu.runtime import coldtier

        def dropped(self, fps, t0):
            n = len(self.cold.pop_rows(fps)["key_hash"])
            with self._cv:
                self._pending.difference_update(fps)
            self.promotes += n
            return n

        coldtier.TierManager._promote = dropped
    elif kind == "noforward":
        # A cluster that serves every check where it arrives: this daemon's
        # ring holds itself alone, so nothing is forwarded to an owner.
        os.environ["GUBER_PEERS"] = os.environ["GUBER_ADVERTISE_ADDRESS"]
    elif kind == "oneclock":
        # No control but a witness, kept apart from this launcher.
        from witness import oneclock

        oneclock.apply()
    elif kind:
        raise SystemExit(f"unknown --control {kind!r}")


def preload(service, path: str) -> dict:
    """Install the rows the harness handed over (bench/lib/universe.py's
    handoff) and probe its seeded sample of keys through the program's own
    lookup."""
    backend = service.backend
    t_wait = time.monotonic()
    while not os.path.exists(path):     # the harness renames it into place
        if time.monotonic() - t_wait > 600:
            raise SystemExit(f"no preload file at {path}")
        time.sleep(0.05)
    t0 = time.monotonic()
    with np.load(path) as z:
        h = {k: z[k] for k in z.files}
    t0_ms = backend.clock.millisecond_now()
    arrays = universe_mod.table_arrays(h, t0_ms)
    t1 = time.monotonic()
    backend._install_table(arrays)
    occupancy = backend.occupancy()
    t2 = time.monotonic()
    del arrays
    tiers = {}
    if "cold_fp" in h:
        tiers = preload_cold(service, h, t0_ms)
    t_cold = time.monotonic()
    # The mesh's probe loops over keys in Python: a smaller sample there.
    n = len(h["probe_fp"]) if backend.cfg.num_shards == 1 else 16384
    # The lookup holds `expire_at > now`: rows whose window has elapsed by
    # now are asked for at the stamp they were created at.
    at_stamp = "probe_at_preload_stamp" in h
    with backend._lock:
        found = np.asarray(backend._found_mask(
            [None] * n, h["probe_fp"][:n].view(np.uint64),
            t0_ms if at_stamp else backend.clock.millisecond_now(),
        ))
    t3 = time.monotonic()
    if tiers:
        # Which tier the arithmetic puts each sampled key in, which the
        # daemon has it in: "table", "cold", "both" or "none" of either.
        cold = service.tier.cold.member_hits(h["probe_fp"][:n])
        names = np.array(["none", "table", "cold", "both"])
        want = names[h["probe_found"][:n] + 2 * h["probe_cold"][:n]]
        have = names[found + 2 * cold]
        pairs, counts = np.unique(
            np.char.add(np.char.add(want, " -> "), have), return_counts=True)
        tiers["probe_tiers"] = dict(zip(pairs.tolist(), counts.tolist()))
        tiers["probe_cold_differs"] = int((cold != h["probe_cold"][:n]).sum())
    return {
        **tiers,
        "t0_ms": int(t0_ms),
        "occupancy": int(occupancy),
        "rows": int(len(h["fp"])),
        "probed": int(n),
        "probe_differs": int((found != h["probe_found"][:n]).sum()),
        "waited_s": round(t0 - t_wait, 3),
        "rows_s": round(t1 - t0, 3),
        "install_s": round(t2 - t1, 3),
        "probe_s": round(t3 - t_cold, 3),
    }


def preload_cold(service, h: dict, t0_ms: int) -> dict:
    """The cold store's rows through the seam runtime/checkpoint.py restores
    them by, stamped as the table's rows are."""
    tier = getattr(service, "tier", None)
    if tier is None:
        raise SystemExit("the configuration is tiered and the daemon has no "
                         "tier manager (GUBER_TIER_ENABLED)")
    t0 = time.monotonic()
    kept = tier.cold.restore(universe_mod.cold_arrays(h, t0_ms))
    return {
        "cold_rows": int(len(h["cold_fp"])),
        "cold_restored": int(kept),
        "cold_residents": int(tier.cold.residents()),
        "cold_restore_s": round(time.monotonic() - t0, 3),
    }


def warm_tier_programs(service) -> dict:
    """The tier's device programs, compiled before the ready report: the
    daemon's warm-up leaves `migrate_inject` to the first promote and
    `demote_extract` to the first tick over the high-water mark, each under
    `backend._lock` with the served path waiting.  Neither call changes the
    table: the promote injects one inactive lane, the demote looks for
    victims at a clock no row is alive at.  Private names, guarded as the
    fetch shapes' are."""
    t0 = time.monotonic()
    out = {"programs": 0, "skipped": ""}
    tier = getattr(service, "tier", None)
    if tier is None:
        return out
    try:
        from gubernator_tpu.ops.state import demote_extract
        from gubernator_tpu.runtime.backend import fetch_ravel

        backend = service.backend
        backend.occupancy_dispatch()()
        idle = {f: np.zeros(1, dtype=np.int64)
                for f in universe_mod.COLD_FIELDS}
        backend.migrate_inject_dispatch(idle)()
        grid = np.asarray(tier._protect_grid(), dtype=np.int64)
        never = np.int64(np.iinfo(np.int64).max - 1)
        with backend._lock:
            backend.table, packed, rf = demote_extract(
                backend.table, grid, never, ways=backend.cfg.ways,
                batch=int(tier.cfg.demote_batch),
            )
        taken = int((fetch_ravel([packed])[0] != 0).sum())
        fetch_ravel([rf])
        if taken:
            raise SystemExit(f"warming demote_extract took {taken} values "
                             "out of the table")
        out["programs"] = 3
    except (AttributeError, TypeError, ImportError) as e:
        out["skipped"] = f"{type(e).__name__}: {e}"
        log.warning("tier-program warming skipped: %s", out["skipped"])
    out["seconds"] = round(time.monotonic() - t0, 3)
    return out


def _lane_responses(service, lane: str, tiers):
    """One device response per tier, from the lane's own step on an
    all-inactive batch (the table is unchanged), and the program's
    function that fetches a list of them to the host."""
    import jax

    backend = service.backend
    now = np.int64(backend.clock.millisecond_now())
    mesh = backend.cfg.num_shards > 1
    if mesh:
        from gubernator_tpu.parallel.sharded import (
            packed_grid_rounds_to_host as to_host,
        )
    else:
        from gubernator_tpu.runtime.backend import (
            packed_rounds_to_host as to_host,
        )
    resps = {}
    if lane == "engine":
        engine = service.global_engine
        with engine._lock:
            for t in tiers:
                batch = jax.device_put(
                    np.zeros((12, engine.n, t), dtype=np.int64),
                    backend._psharding,
                )
                engine.cache_table, resps[t] = engine._ingest(
                    engine.cache_table, batch, now
                )
    elif mesh:
        with backend._lock:
            for t in tiers:
                batch = jax.device_put(
                    np.zeros((12, backend.cfg.num_shards, t), dtype=np.int64),
                    backend._psharding,
                )
                backend.table, resps[t] = backend._step_packed(
                    backend.table, batch, now
                )
    else:
        with backend._lock:
            for t in tiers:
                backend.table, resps[t] = backend._step_packed_q(
                    backend.table, np.zeros((12, t), dtype=np.int64), now
                )
    return resps, to_host


def warm_fetch_shapes(service, lanes: dict) -> dict:
    """`lanes` maps a lane ("mach", "engine") to the most lanes each round
    of one drain can hold (bench/lib/shapes.py derives it from the traffic
    file's outstanding cap).  Private names of the program are used on
    purpose and guarded: if a later PR removes the request-path compile,
    there is nothing to warm and the in-window compile count still
    judges."""
    t0 = time.monotonic()
    out = {"shapes": 0, "max_rounds": 0, "skipped": ""}
    try:
        tiers = list(service.backend._tiers)
        for lane, round_lanes in lanes.items():
            if lane == "engine" and getattr(
                service, "global_engine", None
            ) is None:
                continue
            seqs = shapes_mod.tier_sequences(round_lanes, tiers)
            if not seqs:
                continue
            resps, to_host = _lane_responses(service, lane, tiers)
            for seq in seqs:
                to_host([resps[t] for t in seq])
            out["shapes"] += len(seqs)
            out["max_rounds"] = max(out["max_rounds"], len(round_lanes))
    except (AttributeError, TypeError, ImportError) as e:
        out["skipped"] = f"{type(e).__name__}: {e}"
        log.warning("fetch-shape warming skipped: %s", out["skipped"])
    out["seconds"] = round(time.monotonic() - t0, 3)
    return out


def chip_report(service) -> dict:
    """Which chip this process holds, for a harness that has to tell the
    daemons of a cluster apart: what its environment pinned
    (TPU_VISIBLE_CHIPS; "" where nothing did), and what JAX and the
    kernel say of the table's devices."""
    import jax

    ids = set(service.backend.device_info()["table_device_ids"])
    devs = [d for d in jax.devices() if d.id in ids]
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            held.add(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:
            pass
    return {
        "pinned": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        "ids": [int(d.id) for d in devs],
        "coords": [list(getattr(d, "coords", ())) for d in devs],
        "dev_files": sorted(h for h in held if h.startswith("/dev/")
                            and ("accel" in h or "vfio" in h)),
    }


def memory_stats(service) -> dict:
    import jax

    ids = set(service.backend.device_info()["table_device_ids"])
    peaks = []
    for d in jax.devices():
        if d.id in ids:
            st = d.memory_stats() or {}
            peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return {"memory_peak_bytes": max(peaks) if peaks else 0}


async def run(args) -> None:
    import jax

    from gubernator_tpu.core.config import setup_daemon_config
    from gubernator_tpu.core.logging import setup_logging
    from gubernator_tpu.daemon import Daemon

    conf = setup_daemon_config(None)
    setup_logging(level=conf.log_level, fmt="text")
    t_start = time.monotonic()
    daemon = Daemon(conf)
    await daemon.start()
    ready_s = time.monotonic() - t_start
    loop = asyncio.get_running_loop()
    report = {
        "ready": True,
        "daemon_start_s": round(ready_s, 3),
        "device": daemon.service.backend.device_info(),
    }
    report["device"]["warmup_s"] = round(daemon._warmup_s, 3)
    report["chip"] = chip_report(daemon.service)
    if args.preload:
        report["preload"] = await loop.run_in_executor(
            None, preload, daemon.service, args.preload
        )
    report["fetch_shapes"] = await loop.run_in_executor(
        None, warm_fetch_shapes, daemon.service, json.loads(args.lanes)
    )
    if getattr(daemon.service, "tier", None) is not None:
        report["tier_programs"] = await loop.run_in_executor(
            None, warm_tier_programs, daemon.service
        )
    say(report)

    stop = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    commands: asyncio.Queue = asyncio.Queue()

    def read_stdin() -> None:
        for line in sys.stdin:
            line = line.strip()
            if line:
                loop.call_soon_threadsafe(commands.put_nowait, line)
        loop.call_soon_threadsafe(commands.put_nowait, '{"cmd": "quit"}')

    threading.Thread(target=read_stdin, name="stdin", daemon=True).start()
    tracing = False
    while not stop.is_set():
        get = asyncio.ensure_future(commands.get())
        halt = asyncio.ensure_future(stop.wait())
        done, pending = await asyncio.wait(
            {get, halt}, return_when=asyncio.FIRST_COMPLETED
        )
        for p in pending:
            p.cancel()
        if get not in done:
            break
        msg = json.loads(get.result())
        cmd = msg.get("cmd")
        if cmd == "quit":
            break
        if cmd == "trace_start":
            await loop.run_in_executor(
                None, jax.profiler.start_trace, msg["dir"]
            )
            tracing = True
            say({"cmd": cmd, "t_mono": time.monotonic()})
        elif cmd == "trace_stop":
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            tracing = False
            say({"cmd": cmd, "t_mono": time.monotonic()})
        elif cmd == "memory":
            say({"cmd": cmd, **memory_stats(daemon.service)})
        else:
            say({"cmd": cmd, "error": "unknown command"})
    if tracing:
        jax.profiler.stop_trace()
    await daemon.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preload", default="",
                    help="the harness's handoff file (.npz) to install")
    ap.add_argument("--lanes", default="{}")
    ap.add_argument("--control", default="")
    args = ap.parse_args()
    for kind in args.control.split(","):    # "alter,oneclock": both
        apply_control(kind)
    asyncio.run(run(args))


if __name__ == "__main__":
    main()
