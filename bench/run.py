#!/usr/bin/env python3
"""The benchmark's one command.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One cell of BENCHMARK.json, one run: start the daemon (bench/serve.py: the
server CLI's own start-up, the configuration's key universe preloaded and
every reachable fetch program warmed), check special cases over the wire,
drive the cell's traffic from a client process that never imports JAX
(bench/client.py) through a warm-in and a window of --seconds, compare what
came back with core/pymodel.py, and print — as the LAST line of stdout —
{"correct", "attempted", "failed", "metrics", "device", "window"[,
"breakdown"], "compared"}: `window` is what the generator saw (for sweeps),
`compared` every number compared beside its limit, also the last lines of
stderr.  `attempted` and `failed` count checks.  --trace 0 reports the cell's
end-to-end metrics, --trace 1 its per-layer metrics from a run that also
traces a short span of the window with the profiler.

A configuration that says `"peers": N` (bench/README.md) is N daemons, a
chip each, on one consistent-hash ring: N bench/serve.py children, each
with its own addresses, preload file and chip (pinned by its environment,
bench/lib/cluster.py), the client's connections spread over them, every
snapshot, `memory` and trace command sent to each — and ONE result line:
counters summed, the fullest chip's memory, the mean chip's busy time, and
a `daemons` block that says what each one held and did.

This process never imports JAX (the daemon child holds the chip).  It
fails, printing no result, when the daemon finds no TPU or fewer chips
than the cell asks for.  `--platform cpu --slots 65536` is a dry run for
rehearsing the harness: it is asked for explicitly, never fallen back to,
and its last line always says "correct": false.

Logs of the run land in chiprun_out/bench/<cell>-<seed>-t<trace>/ (git and
the chip copy ignore chiprun_out/).
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import re
import signal
import socket
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

T_PROC = time.monotonic()

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, REPO]

import numpy as np  # noqa: E402

from lib import cluster, oracle, readers, schedule, shapes, spec  # noqa: E402
from lib import universe as universe_mod  # noqa: E402
from lib.percentile import beyond, percentile  # noqa: E402

READY_TIMEOUT_S = 1100.0
# A traced run profiles a short span near the END of its window: starting
# and stopping the profiler stalls the daemon for up to a second, so the
# per-layer numbers that are not read from the trace are taken over the
# part of the window before it.
TRACE_SPAN_S = 2.0
TRACE_TAIL_S = 3.0        # the span starts this long before the window ends
HOT_KEYS = 1000           # of a skewed traffic: always in the replayed sample
# Checks a daemon served by where they went, label `calltype`: "local" where
# it owns the key, "forward" where it sent the check to the owner.
HOP_SERIES = "gubernator_getratelimit_counter_total"


class Refused(Exception):
    """The run cannot produce a result (no chip, a child died, ...)."""


def log(msg: str) -> None:
    print("[%8.2fs] %s" % (time.monotonic() - T_PROC, msg), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(addr: str, path: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=timeout) as r:
        return r.read()


def cache_dir() -> str:
    """Where the program keeps JAX's persistent cache (ops/__init__.py's
    rule, restated): the variable if set, else .jax_cache/ in the
    checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )


def cache_entries() -> set:
    d = cache_dir()
    if not os.path.isdir(d):
        return set()
    return {f for f in os.listdir(d) if f.endswith("-cache")}


class Child:
    """A child process in its own group, its stdout read line by line."""

    def __init__(self, name: str, cmd, env, log_path: str) -> None:
        self.name = name
        self.lines: queue.Queue = queue.Queue()
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._log,
            start_new_session=True,
        )
        self._reader = threading.Thread(
            target=self._read, name=name + "-stdout", daemon=True
        )
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            self.lines.put(raw.decode("utf-8", "replace").strip())
        self.lines.put(None)

    def next_json(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise Refused(f"{self.name}: no answer in {timeout:.0f}s")
            try:
                line = self.lines.get(timeout=min(left, 1.0))
            except queue.Empty:
                continue
            if line is None:
                raise Refused(
                    f"{self.name}: exited rc={self.proc.wait()} "
                    "(see its log)"
                )
            if line.startswith("{"):
                return json.loads(line)

    def send(self, obj=None) -> None:
        data = (json.dumps(obj) if obj is not None else "") + "\n"
        self.proc.stdin.write(data.encode())
        self.proc.stdin.flush()

    def stop(self, grace_s: float = 60.0) -> None:
        """Ask, wait, then make sure nothing of the group is left."""
        try:
            if self.proc.poll() is None:
                try:
                    self.proc.stdin.close()
                except OSError:
                    pass
                try:
                    self.proc.wait(timeout=grace_s)
                except subprocess.TimeoutExpired:
                    self.proc.send_signal(signal.SIGTERM)
                    try:
                        self.proc.wait(timeout=20)
                    except subprocess.TimeoutExpired:
                        pass
        finally:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            self.proc.wait()
            self._log.close()


class Snapshot:
    """/debug/vars and /metrics, and the compile cache's entries.  Of a
    cluster: `each` daemon's /debug/vars, `vars` their sum
    (bench/lib/cluster.py `sum_vars`), `metrics` every daemon's series (a
    reader sums the series that match) and `metrics_each` the same a
    daemon."""

    def __init__(self, http_addrs) -> None:
        self.t = time.monotonic()
        self.each = [json.loads(http(a, "/debug/vars")) for a in http_addrs]
        self.vars = (self.each[0] if len(self.each) == 1
                     else cluster.sum_vars(self.each))
        self.metrics_each = [
            readers.parse_prometheus(http(a, "/metrics").decode())
            for a in http_addrs
        ]
        self.metrics = [row for m in self.metrics_each for row in m]
        self.cache = cache_entries()

    def sum_each(self, path: str) -> int:
        """A whole number of /debug/vars that every daemon has, summed."""
        return sum(int(readers.lookup_vars(v, path)) for v in self.each)


class Compare:
    """Every number compared, printed beside its limit."""

    def __init__(self) -> None:
        self.ok = True
        self.seen: dict = {}

    def __call__(self, name: str, value, limit=0) -> None:
        good = value <= limit
        self.ok &= bool(good)
        self.seen[name] = [value, limit]
        print(f"compare {name}: {value} (limit {limit}) "
              f"{'ok' if good else 'FAILED'}", flush=True)


def server_env(args, cfg: dict, grpc_addr: str, http_addr: str,
               daemon: int = 0) -> dict:
    """The environment of daemon number `daemon`.  One of a cluster also
    advertises the address it listens on (its name on the ring) and is
    given chip `daemon` of the host and no other."""
    env = os.environ.copy()
    env.update(cfg["daemon"])
    env.update(
        GUBER_GRPC_ADDRESS=grpc_addr, GUBER_HTTP_ADDRESS=http_addr,
        GUBER_TPU_PLATFORM=args.platform,
    )
    peers = spec.peers_of(cfg)
    if peers > 1:
        env["GUBER_ADVERTISE_ADDRESS"] = grpc_addr
        if args.platform == "tpu":
            env.update(cluster.pin_env(daemon, free_port()))
    if args.platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count="
            + str(cfg["chips"] // peers)
        ).strip()
    return env


def daemon_addresses(cfg: dict) -> list:
    """The gRPC address of every daemon: a free port for one daemon; a
    cluster's are its configuration's, since a peer's place on the ring
    follows from its address — and a port of those that is taken is a
    run that cannot be made."""
    if spec.peers_of(cfg) == 1:
        return [f"127.0.0.1:{free_port()}"]
    addrs = spec.peer_addresses(cfg)
    for a in addrs:
        host, _, port = a.rpartition(":")
        with socket.socket() as s:
            # The last run's connections may linger in TIME_WAIT; only a
            # listener takes the port.
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind((host, int(port)))
            except OSError as e:
                raise Refused(f"the configuration's peer address {a} is "
                              f"taken: {e}") from e
    return addrs


def check_chips(args, cell: dict, cfg: dict, readies: list) -> None:
    """Refuses a run whose daemons do not hold what the cell asks for."""
    for ready in readies:
        dev = ready["device"]
        if dev["platform"] != args.platform:
            raise Refused(f"daemon runs on {dev['platform']!r}, "
                          f"not {args.platform!r}")
    if len(readies) == 1:
        dev = readies[0]["device"]
        if dev["device_count"] < cell["chips"] or len(
            set(dev["table_device_ids"])
        ) != int(cfg["universe"]["shards"]):
            raise Refused(f"cell needs {cell['chips']} chips, daemon has "
                          f"{dev}")
        return
    # A cluster: every daemon one table on one device; on the TPU a daemon
    # sees its own chip alone, and no two the same one.
    chips = [r["chip"] for r in readies]
    if any(len(set(r["device"]["table_device_ids"])) != 1 for r in readies):
        raise Refused(f"a daemon of a cluster holds one device: {chips}")
    if args.platform == "tpu":
        # A pinned process sees ONE device, numbered 0 whichever chip it
        # is, so the chips are told apart by the pin and by the kernel's
        # device file each process holds (/dev/vfio/<k> on a v5e;
        # /dev/vfio/vfio is the container every process opens).
        files = [set(c["dev_files"]) - {"/dev/vfio/vfio"} for c in chips]
        shared = [a & b for i, a in enumerate(files) for b in files[:i]]
        if (any(r["device"]["device_count"] != 1 for r in readies)
                or any(c["pinned"] == "" for c in chips)
                or len({c["pinned"] for c in chips}) != len(chips)
                or any(shared)):
            raise Refused("every daemon of a cluster has to hold one chip of "
                          f"its own, pinned by its environment: {chips}")
    if sum(r["device"]["device_count"] for r in readies) < cell["chips"]:
        raise Refused(f"cell needs {cell['chips']} chips, the daemons hold "
                      f"{chips}")


def ask_all(servers: list, msgs: list, timeout: float) -> list:
    """One command to every daemon, then every answer: they work at once."""
    for sv, msg in zip(servers, msgs):
        sv.send(msg)
    return [sv.next_json(timeout) for sv in servers]


def window_stats(traffic: dict, rec: dict, plan, tw0: float,
                 tw1: float) -> dict:
    """The end-to-end numbers and the client's own layer numbers over
    [tw0, tw1) on the machine's monotonic clock."""
    seconds = tw1 - tw0
    sizes = np.diff(plan.offsets)[rec["plan_idx"]]
    ok = rec["code"] == oracle.OK
    out = {}
    if traffic["loop"] == "closed":
        sent = (rec["t_send"] >= tw0) & (rec["t_send"] < tw1)
        done = ok & (rec["t_done"] >= tw0) & (rec["t_done"] < tw1)
        out["decisions_per_s"] = float(sizes[done].sum()) / seconds
        per_s = np.bincount(
            np.clip((rec["t_done"][done] - tw0).astype(int), 0,
                    int(seconds) - 1 if seconds >= 1 else 0),
        )
        out["answered_per_second"] = per_s.tolist()
    else:
        sent = (rec["t_due"] >= tw0) & (rec["t_due"] < tw1)
        lat = np.where(
            ok, rec["t_done"] - rec["t_due"], float(traffic["deadline_s"])
        )[sent] * 1e3
        late = (rec["t_send"] - rec["t_due"])[sent] * 1e3
        out.update(
            rpc_p50_ms=percentile(lat, 0.50), rpc_p95_ms=percentile(lat, 0.95),
            rpc_p99_ms=percentile(lat, 0.99), rpc_max_ms=float(lat.max())
            if len(lat) else float("nan"),
            samples=len(lat), beyond_p95=beyond(lat, 0.95),
            gen_late_p99_ms=percentile(late, 0.99),
            cap_waited=int(rec["waited"][sent].sum()),
            arrivals=int(sent.sum()),
        )
    out["attempted"] = int(sizes[sent].sum())
    out["failed"] = int(sizes[sent & ~ok].sum())
    out["rpcs_sent"] = int(sent.sum())
    out["rpcs_failed_anywhere"] = int((~ok).sum())
    return out


TIER_COUNTERS = ("demotes", "promotes", "cold_hits", "capacity_drops",
                 "promote_failures", "promote_retries", "demote_passes",
                 "ticks")


def tiered_counts(compare, cfg: dict, uni, plan, rec: dict, answers,
                  snap_ready, snap_end, slots: int, aux_fp, window) -> tuple:
    """What a table that does not hold its universe is held to beside its
    answers, from the daemon's `/debug/vars` `tier` block and the client's
    record.  Returns (the tier's numbers for the result line, the least and
    the most rows both tiers may hold together, the rows they hold)."""
    t0, t1 = snap_ready.vars["tier"], snap_end.vars["tier"]
    grown = {k: int(t1[k]) - int(t0[k]) for k in TIER_COUNTERS}
    compare("tier_capacity_drops_grown", grown["capacity_drops"])
    compare("tier_promote_failures_grown", grown["promote_failures"])
    # docs/tiering.md's bound from the daemon's own ledger, the form
    # scripts/chaos_smoke.py --scenario coldstorm checks.
    admitted, touched = oracle.tiered_ledger(answers)
    compare("tier_admitted_beyond_ledger", max(
        0, admitted - uni.limit * (touched + grown["demotes"])))
    high, _ = spec.tier_marks(cfg)
    tick_s = float(cfg["background_timers_s"]["tier_tick"])
    ok = rec["code"] == oracle.OK
    sizes = np.diff(plan.offsets)[rec["plan_idx"]]
    room = oracle.most_checks_in_span(rec["t_done"][ok], sizes[ok],
                                      2 * tick_s)
    table = int(snap_end.vars["backend"]["occupancy"])
    most = int(high * slots) + room
    log(f"tier: table {table} rows of {slots}, high-water mark "
        f"{int(high * slots)} and {room} checks in the busiest two ticks; "
        f"cold store {t1['cold_residents']} of {t1['cold_capacity']}; grown "
        f"since ready {grown}; promote latency {t1['promote_latency']}")
    compare("table_beyond_high_water", max(0, table - most))
    # Keys answered less than the deadline before the count: their cold
    # row may still wait for its merge beside a fresh row.
    since = snap_end.t - uni.promote_deadline_ms / 1e3
    young = rec["t_done"][answers.rpc] >= since
    lo, hi = universe_mod.tiered_row_bounds(
        uni, aux_fp, len(np.unique(answers.key[young])))
    w0, w1 = (s.vars["tier"] for s in window)
    per_s = {k + "_per_s": (int(w1[k]) - int(w0[k])) / (window[1].t - window[0].t)
             for k in ("demotes", "promotes", "cold_hits", "capacity_drops")}
    tier = {
        **per_s, "grown_since_ready": grown,
        "table_rows": table, "table_rows_at_window": [
            int(s.vars["backend"]["occupancy"]) for s in window],
        "high_water_rows": int(high * slots),
        "cold_residents": int(t1["cold_residents"]),
        "promote_latency_p99_s": t1["promote_latency"]["p99_s"],
        "promote_latency_mean_s": (
            t1["promote_latency"]["sum_s"]
            / max(1, t1["promote_latency"]["cumulative"][-1])),
        "promote_latency": {
            "buckets": t1["promote_latency"]["buckets"],
            "cumulative": t1["promote_latency"]["cumulative"]},
    }
    return tier, lo, hi, table + int(t1["cold_residents"])


def reduce_trace(trace_dirs: list) -> dict:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lib", "trace.py"), *trace_dirs],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    if p.returncode != 0:
        raise Refused("trace reduction failed:\n" + p.stderr[-2000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def run(args) -> dict:
    """One run; its large files (the preload handoff, the client's record,
    the profiler's trace) live in a temporary directory and go with it."""
    tmp = tempfile.mkdtemp(prefix="gubbench-")
    try:
        return _run(args, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run(args, tmp: str) -> dict:
    from gubernator_tpu import native

    native.require()
    bm = spec.benchmark(held_out=args.held_out)
    spec.check_benchmark(bm)
    cell = spec.workload(bm, args.workload)
    cfg_path = spec.config_path(bm, cell["config"])
    traffic_path = spec.traffic_path(cell["traffic"])
    cfg = spec.load_json(cfg_path)
    traffic = spec.load_json(traffic_path)
    out_dir = args.out or os.path.join(
        REPO, "chiprun_out", "bench",
        f"{args.workload}-{args.seed}-t{args.trace}"
        + (f"-r{args.rate:g}" if args.rate else ""),
    )
    os.makedirs(out_dir, exist_ok=True)
    if args.slots or args.keys or args.daemon:
        # The dry run's smaller deployment, or a reading at another
        # daemon setting, as a file the children read.
        if args.slots:
            cfg["daemon"]["GUBER_TPU_NUM_SLOTS"] = str(args.slots)
        if args.keys:
            cfg["universe"]["keys"] = args.keys
        cfg["daemon"].update(kv.split("=", 1) for kv in args.daemon)
        spec.check_config(cfg, "the configuration as overridden")
        cfg_path = os.path.join(out_dir, "config.dryrun.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
    if args.rate:
        # A sweep point: the cell's traffic at another arrival rate.
        traffic["arrivals"]["rate_rpc_per_s"] = args.rate
        traffic_path = os.path.join(out_dir, "traffic.sweep.json")
        with open(traffic_path, "w") as f:
            json.dump(traffic, f)
    warm_in = float(traffic["warm_in_s"])
    timers = max(cfg["background_timers_s"].values(), default=0.0)
    if warm_in < timers:
        raise spec.SpecError(
            f"warm-in {warm_in}s is shorter than a background timer "
            f"({timers}s) the configuration leaves on"
        )
    slots = int(cfg["daemon"]["GUBER_TPU_NUM_SLOTS"])
    batch = int(cfg["daemon"]["GUBER_TPU_BATCH_SIZE"])
    lanes = shapes.round_lane_bounds(
        traffic, cfg["universe"], batch, smallest_tier=min(128, batch)
    )

    ring = spec.ring_of(cfg)
    peers = spec.peers_of(cfg)
    # One daemon is "server", with serve.log and preload.npz, as before
    # there could be several; daemon k of a cluster carries its number.
    tags = [""] if peers == 1 else [f".{k}" for k in range(peers)]
    grpc_addrs = daemon_addresses(cfg)
    grpc_addr = grpc_addrs[0]       # the wire check's entry daemon
    http_addrs = [f"127.0.0.1:{free_port()}" for _ in grpc_addrs]
    cache_at_start = len(cache_entries())
    servers: list = []
    client = None
    try:
        for k, tag in enumerate(tags):
            servers.append(Child(
                "server" + tag.replace(".", ""),
                [sys.executable, os.path.join(BENCH, "serve.py"),
                 "--preload", os.path.join(tmp, f"preload{tag}.npz"),
                 "--lanes", json.dumps(lanes)]
                + (["--control", args.control] if args.control else []),
                server_env(args, cfg, grpc_addrs[k], http_addrs[k], k),
                os.path.join(out_dir, f"serve{tag}.log"),
            ))
        rec_path = os.path.join(tmp, "client.npz")
        client_env = os.environ.copy()
        client_env["JAX_PLATFORMS"] = "cpu"   # it never imports JAX anyway
        client = Child(
            "client",
            [sys.executable, os.path.join(BENCH, "client.py"),
             "--addr", ",".join(grpc_addrs), "--config", cfg_path,
             "--traffic", traffic_path, "--seed", str(args.seed),
             "--warm-in", str(warm_in), "--seconds", str(args.seconds),
             "--out", rec_path],
            client_env, os.path.join(out_dir, "client.log"),
        )
        tiered = spec.tiered(cfg["universe"])
        uni = universe_mod.build_universe(
            native, cfg["universe"], args.seed, slots, ring,
            table_rows=spec.table_rows_at_start(cfg) if tiered else None,
        )
        for k, tag in enumerate(tags):
            np.savez(os.path.join(tmp, "handoff.npz"),
                     **universe_mod.handoff(uni, args.seed, 262144, k))
            os.rename(os.path.join(tmp, "handoff.npz"),
                      os.path.join(tmp, f"preload{tag}.npz"))
        plan = schedule.build_plan(
            traffic, cfg["universe"], args.seed, warm_in + args.seconds
        )
        log(f"universe: {len(uni.fp)} keys, {uni.n_resident} resident, "
            f"{int(uni.crowded.sum())} in crowded buckets, "
            f"{int(uni.is_global.sum())} GLOBAL; plan {len(plan)} RPCs, "
            f"{int(plan.offsets[-1])} checks"
            + (f"; resident a daemon {uni.resident_by_daemon().tolist()}"
               if peers > 1 else "")
            + (f"; {int(uni.cold.sum())} start in the cold store"
               if tiered else ""))

        readies = [sv.next_json(READY_TIMEOUT_S) for sv in servers]
        for tag, ready in zip(tags, readies):
            dev = ready["device"]
            log(f"daemon{tag} ready: start {ready['daemon_start_s']}s "
                f"(warm-up {dev['warmup_s']}s), device {dev}, chip "
                f"{ready.get('chip')}; preload {ready.get('preload')}; "
                f"fetch shapes {ready['fetch_shapes']}; "
                + (f"tier programs {ready['tier_programs']}; "
                   if "tier_programs" in ready else "") +
                f"lanes {lanes}; compile cache {cache_at_start} -> "
                f"{len(cache_entries())} entries")
        check_chips(args, cell, cfg, readies)
        compare = Compare()
        # Every daemon against its own share of the placement.
        # Of a tiered universe both tiers, each against its own share.
        compare("preload_occupancy_differs", sum(
            abs(r["preload"]["occupancy"] - int(n))
            for r, n in zip(readies, uni.resident_by_daemon())
        ) + (abs(readies[0]["preload"]["cold_residents"] - int(uni.cold.sum()))
             if tiered else 0))
        compare("probe_differs_from_placement",
                sum(r["preload"]["probe_differs"]
                    + r["preload"].get("probe_cold_differs", 0)
                    for r in readies))

        from lib import wirecheck

        wire = wirecheck.verify_wire(grpc_addr, args.seed)
        aux_fp = wire.resident_hashes().view(np.int64)
        aux_seen = wire.seen_hashes().view(np.int64)
        # Buckets the wire check touched: a row of theirs may be evicted.
        aux_buckets = np.unique(uni.bucket_of(aux_seen))
        log(f"wire check: {wire.checked} answers against the reference"
            + (f"; first mismatch {wire.first}" if wire.first else ""))
        compare("wire_check_mismatches", wire.mismatches)
        snap_ready = Snapshot(http_addrs)
        compare("compiled_lane_missing", sum(
            int(v["device"].get("compiled_lane") is not True)
            for v in snap_ready.each
        ))

        planned = client.next_json(600)
        if planned.get("digest") != plan.digest():
            raise Refused("client and harness built different plans")
        client.send()                      # go
        mark = client.next_json(warm_in + 60)
        assert mark["mark"] == "window_start", mark
        setup_s = time.monotonic() - T_PROC
        snap0 = Snapshot(http_addrs)
        trace_dirs = [os.path.join(tmp, "trace" + tag) for tag in tags]
        tsnaps = None
        layer_end = None
        if args.trace and args.seconds >= 2.0:
            offset = max(1.0, args.seconds - TRACE_TAIL_S)
            span = min(TRACE_SPAN_S, args.seconds - offset - 0.5)
            time.sleep(max(0.0, snap0.t + offset - time.monotonic()))
            snap_pre = Snapshot(http_addrs)
            layer_end = snap_pre.t
            # Every daemon at once: a profiler takes up to a second to
            # start, and the daemons' spans should cover the same time.
            ask_all(servers, [{"cmd": "trace_start", "dir": d}
                              for d in trace_dirs], 120)
            ta = Snapshot(http_addrs)
            time.sleep(max(0.1, span))
            tb = Snapshot(http_addrs)
            ask_all(servers, [{"cmd": "trace_stop"}] * peers, 300)
            tsnaps = (ta, tb)
        mark = client.next_json(args.seconds + 360)
        assert mark["mark"] == "window_end", mark
        snap1 = Snapshot(http_addrs)
        log("window closed")
        saved = client.next_json(traffic["deadline_s"] + 300)
        assert saved["mark"] == "saved", saved
        client.stop(grace_s=30)
        client = None

        with np.load(rec_path) as z:
            rec = {k: z[k] for k in z.files}
        extra = json.loads(str(rec.pop("extra")))
        run_stats = {}
        answers = oracle.flatten(plan, rec)
        if uni.is_global.any():
            g = uni.is_global[answers.key]
            gsel = np.flatnonzero(uni.is_global)
            # Keys of crowded buckets are read but not held to it: the
            # owner's row may be evicted, as any row of such a bucket.
            strict = ~uni.crowded[gsel] & ~np.isin(
                uni.gbucket[gsel], aux_buckets
            )
            bad = (rec["gb_differs"] & strict[None, :]).sum(axis=1)
            agreed = np.flatnonzero(bad == 0)
            gb = {
                "differs": int(bad[-1]) if len(bad) else int(strict.sum()),
                "polls": len(bad), "keys_compared": int(strict.sum()),
            }
            if len(agreed):
                run_stats["global_visible_ms"] = float(
                    rec["gb_t"][agreed[0]]
                ) * 1e3
            log(f"GLOBAL: {int(g.sum())} acknowledged hits on {len(gsel)} "
                f"keys ({gb['keys_compared']} outside crowded buckets); "
                f"after the last answer the replicated read and the owner's "
                f"row agreed with them in "
                f"{run_stats.get('global_visible_ms', float('nan')):.0f} ms "
                f"({gb['polls']} polls, {gb['differs']} keys differ at the "
                f"last)")
        memories = ask_all(servers, [{"cmd": "memory"}] * peers, 60)
        # The fullest chip's.
        memory = max(memories, key=lambda m: m["memory_peak_bytes"])
        snap_end = Snapshot(http_addrs)
        for sv in servers:
            sv.send({"cmd": "quit"})
        while servers:
            servers.pop().stop()
    finally:
        for ch in [client] + servers:
            if ch is not None:
                ch.stop(grace_s=0)

    # -- what the window measured ----------------------------------------
    stats = window_stats(traffic, rec, plan, extra["tw0"], extra["tw1"])
    if "answered_per_second" in stats:
        log(f"answered RPCs in each second of the window: "
            f"{stats.pop('answered_per_second')}")
    if "samples" in stats:
        print(f"rpc latency samples: {stats['samples']} "
              f"({stats['beyond_p95']} beyond the 95th percentile); p50 "
              f"{stats['rpc_p50_ms']:.2f} p95 {stats['rpc_p95_ms']:.2f} p99 "
              f"{stats['rpc_p99_ms']:.2f} max {stats['rpc_max_ms']:.2f} ms; "
              f"generator late p99 {stats['gen_late_p99_ms']:.2f} ms, "
              f"{stats['cap_waited']} arrivals waited for the cap")
    log(f"window: {stats['rpcs_sent']} RPCs sent, {stats['attempted']} "
        f"checks, {stats['failed']} failed; {stats['rpcs_failed_anywhere']}"
        f" RPCs failed in the whole run; client {extra}")

    # -- correct ----------------------------------------------------------
    verdict = oracle.Verdict()
    oracle.screen(answers, uni, verdict)
    aside = oracle.unanswered_keys(plan, rec)
    t_chk = time.monotonic()
    skewed = traffic["keys"]["distribution"] != "uniform"
    replay, counted = (
        (oracle.replay_moving, oracle.MOVING_COUNTS) if uni.moving
        else (oracle.replay_tiered, oracle.TIERED_COUNTS) if tiered
        else (oracle.replay_sample, oracle.FROZEN_COUNTS)
    )
    replay(
        answers, rec, uni, [r["preload"]["t0_ms"] for r in readies],
        args.seed, aside,
        aux_buckets, verdict,
        always=schedule.hottest_keys(plan, HOT_KEYS) if skewed else None,
    )
    log(f"checker: {verdict.notes} in {time.monotonic() - t_chk:.1f}s; "
        f"{len(aside)} keys set aside for unanswered RPCs"
        + (f"; first mismatch {verdict.first}" if verdict.first else ""))
    for name in ("errors", "wrong_limit", "malformed_answers") + counted:
        compare(name, verdict.counts[name])
    compare("sampled_answers_missing",
            int(verdict.notes["sampled_answers"] == 0))
    if uni.moving:
        # A run that did not meet what its configuration's numbers make
        # certain has not judged the moving clock: a key hit again inside
        # its window; a new window, where the preloaded ones have all
        # elapsed by the end of the warm-in; a whole token leaked, where
        # one leaks within the warm-in.
        certain = {
            "live_window_answers": True,
            "new_window_answers": uni.duration_ms <= 1e3 * warm_in,
            "whole_token_leaks": uni.duration_ms <= 1e3 * warm_in * uni.limit,
        }
        for name, must in certain.items():
            if must:
                compare(name + "_missing", int(verdict.notes[name] == 0))
    if uni.is_global.any():
        compare("global_not_under", verdict.counts["global_not_under"])
        compare("global_readback_differs", gb["differs"])
    new = sorted(snap1.cache - snap0.cache)
    log(f"compile-cache entries written inside the window: {len(new)} "
        f"{[n[:40] for n in new[:8]]}; before the window, since the daemon "
        f"was ready: {len(snap0.cache - snap_ready.cache)}")
    compare("compiled_in_window", len(new))
    compare("fastpath_fallbacks_grown",
            snap_end.sum_each("fastpath.fallbacks")
            - snap_ready.sum_each("fastpath.fallbacks"))
    compare("serve_mode_degraded", sum(
        int(v["fastpath"]["effective_serve_mode"] != v["fastpath"]["serve_mode"])
        for v in snap_end.each
    ))
    plain = ~uni.is_global[answers.key]
    lo_keys = np.unique(answers.key[plain])
    hi_keys = np.unique(np.concatenate([answers.key, aside]))
    be0, be1 = snap_ready.vars["backend"], snap_end.vars["backend"]
    not_persisted = be1["not_persisted"] - be0["not_persisted"]
    tier = None
    if tiered:
        # Both tiers together against the placement arithmetic, and the
        # table against its high-water mark (bench/lib/universe.py
        # `tiered_row_bounds`, bench/lib/oracle.py `most_checks_in_span`).
        tier, lo, hi, occ = tiered_counts(
            compare, cfg, uni, plan, rec, answers, snap_ready, snap_end,
            slots, aux_fp, (snap0, snap1))
    else:
        occ = be1["occupancy"]
        hi = universe_mod.expected_occupancy(uni, hi_keys, aux_fp)
        lo = universe_mod.expected_occupancy(uni, lo_keys, aux_fp)
    # A wire-check bucket that came and went may have evicted a row.
    lo -= not_persisted + (len(aux_seen) - len(aux_fp))
    log(f"occupancy {occ}, expected {lo}..{hi} ({not_persisted} lanes not "
        f"persisted)")
    compare("occupancy_beyond_expected", max(0, occ - hi))
    compare("occupancy_below_expected", max(0, lo - occ))
    daemons = None
    if peers > 1:
        # The forward hop, counted twice: by the daemons (their
        # gubernator_getratelimit_counter series, by calltype, since the wire
        # check) and from the plan (of every RPC sent, the checks whose
        # ring owner is not the daemon its connection entered by).  A
        # cluster that served everything where it arrived cannot pass.
        hops = cluster.planned_hops(
            plan, uni.owner, peers, rec["plan_idx"], rec["conn"] % peers
        )
        counted = {
            kind: [
                int(readers.window_delta([{
                    "metrics": HOP_SERIES,
                    "labels": {"calltype": kind},
                }], [{"metrics": a}, {"metrics": b}], absent=0.0))
                for a, b in zip(snap_ready.metrics_each,
                                snap_end.metrics_each)
            ] for kind in ("forward", "local")
        }
        log(f"forward hop: the daemons counted {counted}, the plan says "
            f"{hops}")
        compare("forwarded_checks_differ", sum(
            abs(c - h) for c, h in zip(counted["forward"], hops["forward"])))
        compare("local_checks_differ", sum(
            abs(c - h) for c, h in zip(counted["local"], hops["local"])))
        daemons = [
            {
                "grpc": grpc_addrs[k], "chip": readies[k].get("chip"),
                "device": {x: readies[k]["device"][x] for x in (
                    "platform", "device_kind", "device_count",
                    "table_device_ids")},
                "daemon_start_s": readies[k]["daemon_start_s"],
                "memory_peak_bytes": memories[k]["memory_peak_bytes"],
                "preloaded": readies[k]["preload"]["occupancy"],
                "occupancy": snap_end.each[k]["backend"]["occupancy"],
                "served": snap_end.each[k]["fastpath"]["served"]
                - snap_ready.each[k]["fastpath"]["served"],
                "forward": counted["forward"][k],
                "local": counted["local"][k],
            } for k in range(peers)
        ]
    if args.platform != "tpu":
        compare("not_a_tpu_run", 1)
    if args.daemon:
        # A reading at another setting is not the configuration's.
        compare("daemon_setting_overridden", len(args.daemon))
    if args.rate:
        compare("not_the_cells_rate", 1)
    if args.held_out:
        compare("cell_held_out", 1)

    # -- metrics ----------------------------------------------------------
    device = {
        "platform": dev["platform"], "kind": dev["device_kind"],
        # A daemon of a cluster sees the one chip it holds.
        "count": sum(r["device"]["device_count"] for r in readies),
        "memory_peak_bytes": memory["memory_peak_bytes"],
    }
    result = {
        "correct": compare.ok, "attempted": stats["attempted"],
        "failed": stats["failed"], "metrics": {}, "device": device,
    }
    # What the window's generator saw, for sweeps (the driver ignores it).
    result["window"] = {
        k: stats[k] for k in (
            "rpc_p50_ms", "rpc_p95_ms", "rpc_p99_ms", "gen_late_p99_ms",
            "cap_waited", "arrivals", "decisions_per_s",
        ) if k in stats
    }
    if uni.moving:
        # What the moving-clock replay saw (the driver ignores it).
        result["replay"] = {k: verdict.notes[k] for k in oracle.MOVING_SEEN}
    if tier:
        # What the tiered replay saw and the tier's own counters (the same).
        result["replay"] = {k: verdict.notes[k] for k in oracle.TIERED_SEEN}
        result["tier"] = tier
    if daemons:
        # What each daemon of the cluster held and did (the same).
        result["daemons"] = daemons
    e2e = dict(stats, setup_s=setup_s)
    if not args.trace:
        for m in spec.metrics_of(bm, "end_to_end", args.workload):
            result["metrics"][m["name"]] = {
                "value": e2e[m["name"]], "unit": m["unit"],
            }
        result["compared"] = compare.seen
        return result
    trace = reduce_trace(trace_dirs) if tsnaps else {}
    # Everything not read from the trace: the window up to the profiler's
    # start.
    if layer_end is not None:
        stats = window_stats(traffic, rec, plan, extra["tw0"], layer_end)
        snap1 = snap_pre
        log(f"per-layer numbers over the first "
            f"{layer_end - extra['tw0']:.2f}s of the window, before the "
            f"profiler started")
    flat = {f"client:{k}": v for k, v in stats.items()}
    flat.update({f"run:{k}": v for k, v in run_stats.items()})
    flat.update({f"trace:{k}": v for k, v in trace.items()
                 if isinstance(v, (int, float))})
    if tsnaps:
        for path in ("backend.checks",):
            a = readers.lookup_vars(tsnaps[0].vars, path)
            b = readers.lookup_vars(tsnaps[1].vars, path)
            if a is not None and b is not None:
                flat["tracevars:" + path] = b - a
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
        if daemons:
            for d, busy, window in zip(daemons, trace["busy_s_by_chip"],
                                       trace["window_s_by_chip"]):
                d.update(busy_s=busy, window_s=window)
        log(f"trace: {trace.get('daemons_traced', 1)} daemons, "
            f"{trace['chips_traced']} chips a program, busy "
            f"{trace['busy_s']:.3f}"
            f"s of {trace['window_s']:.3f}s; programs "
            f"{ {k: v[0] for k, v in trace['modules'].items()} }")
    ctx = {
        "snaps": tuple(
            {"vars": s.vars, "metrics": s.metrics, "flat": flat}
            for s in (snap0, snap1)
        ),
        "flat": flat, "trace": trace, "device": device,
    }
    for m in spec.metrics_of(bm, "per_layer", args.workload):
        value = readers.evaluate(
            spec.load_json(spec.layer_metric_path(m["name"])), ctx
        )
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"end-to-end numbers of this traced run (not reported): "
        f"{ {k: v for k, v in e2e.items() if isinstance(v, float)} }")
    result["compared"] = compare.seen
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu: a dry run, asked for explicitly; its result "
                    "says correct false")
    ap.add_argument("--slots", type=int, default=0,
                    help="table slots for the cpu dry run")
    ap.add_argument("--keys", type=int, default=0,
                    help="universe size for the cpu dry run")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="an open cell at another RPC/s: a sweep point, "
                    "whose result says correct false")
    ap.add_argument("--held-out", action="store_true",
                    help="also know the cells of bench/held_out.json; the "
                    "result says correct false")
    ap.add_argument("--daemon", action="append", default=[],
                    metavar="GUBER_X=value",
                    help="a daemon setting other than the configuration's: "
                    "a second reading, whose result says correct false")
    ap.add_argument("--control", default="",
                    help="break the daemon on purpose: f32 (the lower-"
                    "precision control), alter, noforward (a cluster whose "
                    "daemons serve all where it arrives); oneclock is a witness "
                    "(bench/witness/oneclock.py)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.platform == "tpu" and (args.slots or args.keys):
        ap.error("--slots and --keys are for the cpu dry run")
    if not all(re.match(r"^GUBER_[A-Z0-9_]+=", kv) for kv in args.daemon):
        ap.error("--daemon takes GUBER_X=value")
    try:
        result = run(args)
    except (Refused, spec.SpecError) as e:
        print(f"bench/run.py: no result: {e}", file=sys.stderr)
        return 1
    # Every number compared beside its limit: the last lines of stderr,
    # and the last key of the result line.
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
