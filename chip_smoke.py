#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the daemon still starts, serves
and decides correctly on the chip.

    python chip_smoke.py                  # one TPU chip (fails without one)
    python chip_smoke.py --chips 4        # the mesh daemon on a 4-chip host
    JAX_PLATFORMS=cpu python chip_smoke.py --platform cpu \\
        --slots 65536 --keys 20000        # CPU dry run, under two minutes

The parent process NEVER imports JAX: a process that has touched JAX holds
the chip, so every phase runs as a child, one after another, each the sole
holder of the chip while it lives.

  server_first  `python -m gubernator_tpu.cli.server` at the real geometry
                (2^24 slots, batch 4096, default serve mode), driven over
                gRPC: load --keys distinct keys in 1000-check RPCs, then a
                few thousand checks verified against core/pymodel.py
                (status/remaining/limit/error exact, reset_time inside its
                send/receive bounds), then /debug/vars: where it ran,
                occupancy, compiled lane, serve mode.
  server_again  the same daemon started again: warm-up seconds and compile
                cache entries — the second start must add none (the
                first is cold only where the cache directory was empty).
  differential  a DeviceBackend at the same geometry under a frozen,
                stepped clock: ~10^5 mixed token/leaky operations against
                the oracle, all four fields exact; then the Pallas
                kernel is compiled for real and the verdict recorded.
  server_mesh4  (--chips 4, or when the one-chip daemon saw >= 4 devices)
                the same server with GUBER_MESH_WAYS=4: four device ids,
                four balanced shard occupancies, GLOBAL read-back
                convergence after a collective sync.

Stdout is two lines: the JSON summary of every phase (also written to
--out/summary.json), then, last, the verdict the driver reads and nothing
else: {"ok": true, "device": {"platform", "kind", "count"}} as the daemon
reported its device.  Any failed phase exits non-zero and prints neither.
Child logs land in --out (default chiprun_out/chip_smoke).  No rate printed
here is a benchmark.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shlex
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

import numpy as np

from gubernator_tpu import native
from gubernator_tpu.client import FastV1Client
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import MAX_BATCH_SIZE, compile_cache_dir
from gubernator_tpu.core.pymodel import PyRateLimiter
from gubernator_tpu.core.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    Status,
)
from gubernator_tpu.net import grpc_api

REPO = os.path.dirname(os.path.abspath(__file__))
T_START = time.monotonic()

WAYS = 8
# Long enough that nothing expires or leaks a whole token while the smoke
# runs (the server's clock is the wall clock): 30 days, and a leak rate of
# 30d/1000 = 43 minutes per token at the largest limit used.
DURATION_MS = 30 * 24 * 3600 * 1000
LOAD_LIMIT = 1000
LOAD_NAMES = 16
LOAD_CONCURRENCY = 32       # load RPCs in flight
READY_TIMEOUT_S = 600.0     # spawn -> served HealthCheck (cold compiles)
PHASE_TIMEOUT_S = 600.0     # the differential child
# The reference's validation errors (gubernator.go:229,235).
ERR_EMPTY_KEY = "field 'unique_key' cannot be empty"
ERR_EMPTY_NAME = "field 'namespace' cannot be empty"


class SmokeFailure(Exception):
    """A phase failed; the smoke exits non-zero and prints no summary."""


def log(msg: str) -> None:
    sys.stderr.write(
        "[chip_smoke %7.1fs] %s\n" % (time.monotonic() - T_START, msg)
    )
    sys.stderr.flush()


def now_ms() -> int:
    return time.time_ns() // 1_000_000


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(addr: str, path: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(
        f"http://{addr}{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def cache_names() -> set:
    d = compile_cache_dir()
    if not os.path.isdir(d):
        return set()
    return {f for f in os.listdir(d) if f.endswith("-cache")}


def cache_entries() -> int:
    return len(cache_names())


def child_env(args, **extra: str) -> Dict[str, str]:
    """The platform is stated on the command line, never discovered: a tpu
    child inherits the environment as is (and fails without a chip); a cpu
    child is held to the CPU with one virtual device per requested chip."""
    env = os.environ.copy()
    if args.platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# Phase A: the server, over the wire
# ---------------------------------------------------------------------------


class Server:
    """One `python -m gubernator_tpu.cli.server` child in its own process
    group; leaving the block stops it and requires a clean exit."""

    def __init__(self, args, name: str, mesh_ways: int) -> None:
        self.args = args
        self.name = name
        self.grpc = f"127.0.0.1:{free_port()}"
        self.http = f"127.0.0.1:{free_port()}"
        self.log_path = os.path.join(args.out, f"{name}.log")
        env = child_env(
            args,
            GUBER_GRPC_ADDRESS=self.grpc,
            GUBER_HTTP_ADDRESS=self.http,
            GUBER_TPU_NUM_SLOTS=str(args.slots),
            GUBER_TPU_BATCH_SIZE=str(args.batch),
            GUBER_TPU_PLATFORM=args.platform,
        )
        if mesh_ways > 1:
            env["GUBER_MESH_WAYS"] = str(mesh_ways)
        cmd = (
            shlex.split(args.server_cmd) if args.server_cmd
            else [sys.executable, "-m", "gubernator_tpu.cli.server"]
        )
        self.cache_before = cache_entries()
        self.t_spawn = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        log(f"{name}: started pid {self.proc.pid} ({' '.join(cmd)})")

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            early = self.proc.poll() is not None
            if not early:
                self.proc.send_signal(signal.SIGTERM)
                try:
                    self.proc.wait(timeout=90)
                except subprocess.TimeoutExpired:
                    raise SmokeFailure(
                        f"{self.name}: server ignored SIGTERM for 90s"
                    )
            if exc_type is None and (early or self.proc.returncode != 0):
                raise SmokeFailure(
                    f"{self.name}: server exited rc={self.proc.returncode}"
                    f"{' before it was stopped' if early else ''}\n"
                    + tail(self.log_path)
                )
        finally:
            try:  # nothing the child started outlives the phase
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
            self._log.close()

    def wait_ready(self) -> float:
        """Seconds from spawn to a served HealthCheck."""
        deadline = self.t_spawn + READY_TIMEOUT_S
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"{self.name}: server exited rc={rc} before it was "
                    f"ready (platform {self.args.platform!r} — no such "
                    "device?)\n" + tail(self.log_path)
                )
            try:
                http_json(self.http, "/v1/HealthCheck", timeout=2.0)
                return time.monotonic() - self.t_spawn
            except (OSError, ValueError):
                pass
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{self.name}: not ready after "
                    f"{READY_TIMEOUT_S:.0f}s\n" + tail(self.log_path)
                )
            time.sleep(0.25)

    def vars(self) -> dict:
        return http_json(self.http, "/debug/vars")


def key_ids(lo: int, hi: int, seed: int) -> np.ndarray:
    """Distinct 32-bit key ids for load positions [lo, hi): an odd
    multiplier is a bijection mod 2^32."""
    i = np.arange(lo, hi, dtype=np.uint64)
    return (i * np.uint64(2654435761) + np.uint64(seed)) & np.uint64(
        0xFFFFFFFF
    )


_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_NAME_BLOB = np.frombuffer(
    b"".join(b"smoke_t%02d" % t for t in range(LOAD_NAMES)), dtype=np.uint8
).reshape(LOAD_NAMES, 9)


def load_payload(lo: int, hi: int, seed: int) -> Tuple[bytes, np.ndarray]:
    """One load RPC's wire bytes for positions [lo, hi) — hits=1 on a
    fresh key each, token/leaky mixed by id bit 4 — and the algorithm
    column (the response check needs it)."""
    ids = key_ids(lo, hi, seed)
    n = len(ids)
    shifts = np.arange(28, -4, -4, dtype=np.uint64)
    keys = np.empty((n, 9), dtype=np.uint8)
    keys[:, 0] = ord("k")
    keys[:, 1:] = _HEX[
        ((ids[:, None] >> shifts[None, :]) & np.uint64(0xF)).astype(np.intp)
    ]
    names = _NAME_BLOB[(ids % np.uint64(LOAD_NAMES)).astype(np.intp)]
    off9 = np.arange(n + 1, dtype=np.int64) * 9
    algo = ((ids >> np.uint64(4)) & np.uint64(1)).astype(np.int64)
    one = np.ones(n, dtype=np.int64)
    payload = native.encode_req_columns(
        names.tobytes(), off9, keys.tobytes(), off9,
        one, one * LOAD_LIMIT, one * DURATION_MS, algo,
        np.zeros(n, dtype=np.int64), np.zeros(n, dtype=np.int64),
    )
    return payload, algo


async def load_keys(
    addr: str, n_keys: int, seed: int, budget_s: float
) -> dict:
    """Drive the load RPCs, LOAD_CONCURRENCY in flight; every response
    lane is checked columnar-ly against the new-item answer (status
    UNDER, remaining limit-1, reset_time inside [send, recv] + offset).
    Stops issuing RPCs when `budget_s` runs out."""
    import grpc.aio

    channel = grpc.aio.insecure_channel(addr)
    call = channel.unary_unary(f"/{grpc_api.V1_SERVICE}/GetRateLimits")
    n_rpcs = (n_keys + MAX_BATCH_SIZE - 1) // MAX_BATCH_SIZE
    next_rpc = 0
    hashes: List[np.ndarray] = []
    stats = {"loaded": 0, "bad_lanes": 0, "first_bad": None}
    t0 = time.monotonic()
    # A whole token leaks only after rate_i ms; reset = now + 1 * rate_i
    # for a leaky bucket one hit below its burst (algorithms.go:452).
    leaky_off = DURATION_MS // LOAD_LIMIT

    async def worker() -> None:
        nonlocal next_rpc
        while next_rpc < n_rpcs and time.monotonic() - t0 < budget_s:
            k = next_rpc
            next_rpc += 1
            lo = k * MAX_BATCH_SIZE
            hi = min(lo + MAX_BATCH_SIZE, n_keys)
            payload, algo = load_payload(lo, hi, seed)
            hashes.append(native.parse_reqs(payload).hash)
            t_send = now_ms()
            raw = await call(payload, timeout=120)
            t_recv = now_ms()
            cols = native.parse_resps(raw)
            if cols is None or cols.n != hi - lo:
                raise SmokeFailure(
                    f"load rpc {k}: malformed response "
                    f"({None if cols is None else cols.n} lanes)"
                )
            off = np.where(algo == 1, leaky_off, DURATION_MS)
            bad = (
                (cols.status != 0)
                | (cols.err_len != 0)
                | (cols.limit != LOAD_LIMIT)
                | (cols.remaining != LOAD_LIMIT - 1)
                | (cols.reset_time < t_send + off)
                | (cols.reset_time > t_recv + off)
            )
            nbad = int(bad.sum())
            if nbad:
                stats["bad_lanes"] += nbad
                if stats["first_bad"] is None:
                    j = int(np.flatnonzero(bad)[0])
                    stats["first_bad"] = {
                        "position": lo + j,
                        "status": int(cols.status[j]),
                        "remaining": int(cols.remaining[j]),
                        "limit": int(cols.limit[j]),
                        "reset_time": int(cols.reset_time[j]),
                        "send_ms": t_send, "recv_ms": t_recv,
                        "algorithm": int(algo[j]),
                    }
            stats["loaded"] += hi - lo
            if (k + 1) % 1000 == 0:
                log(f"  loaded {stats['loaded']} keys")

    try:
        await asyncio.gather(
            *[worker() for _ in range(LOAD_CONCURRENCY)]
        )
    finally:
        await channel.close()
    stats["seconds"] = round(time.monotonic() - t0, 2)
    stats["hashes"] = (
        np.concatenate(hashes) if hashes else np.zeros(0, dtype=np.int64)
    )
    return stats


def expected_resident(hashes: np.ndarray, slots: int, shards: int) -> int:
    """Rows a `ways`-way set-associative table holds after these distinct
    fingerprints were inserted: a bucket keeps min(arrivals, ways) — the
    rest evicted an older row (ops/step.py victim choice).  Shard and
    bucket math are parallel/mesh.shard_of_hash and ops/step's mask."""
    h = np.unique(hashes[hashes != 0]).view(np.uint64)
    nb_local = slots // shards // WAYS
    shard = (h >> np.uint64(32)) % np.uint64(shards)
    bucket = h & np.uint64(nb_local - 1)
    counts = np.bincount(
        (shard * np.uint64(nb_local) + bucket).astype(np.int64),
        minlength=nb_local * shards,
    )
    return int(np.minimum(counts, WAYS).sum())


class WireOracle:
    """core/pymodel.py fed the same requests under a frozen clock.  The
    server's clock is the wall clock, so durations are chosen such that
    status/remaining/limit/error cannot depend on it; reset_time is the
    oracle's offset from its own `now`, re-based on the send/receive
    window of the RPC that fixed it (the creating RPC for a token bucket,
    this RPC for a leaky one)."""

    def __init__(self) -> None:
        self.t0 = now_ms()
        clk = clock_mod.Clock()
        clk.freeze(self.t0 * 1_000_000)
        self.model = PyRateLimiter(clock=clk)
        self.created: Dict[str, Tuple[int, int]] = {}
        self.checked = 0
        self.mismatches = 0
        self.first: Optional[dict] = None

    def resident_hashes(self) -> np.ndarray:
        """Fingerprints of the buckets the oracle still holds (a token
        bucket whose last word was RESET_REMAINING is gone)."""
        return native.hash_keys(list(self.model.cache))

    def _bad(self, what: str, req, want, got) -> None:
        self.mismatches += 1
        if self.first is None:
            self.first = {
                "field": what, "req": repr(req),
                "want": repr(want), "got": repr(got),
            }

    def rpc(self, client: FastV1Client, reqs: List[RateLimitReq]) -> None:
        lo = now_ms()
        resps = client.get_rate_limits(reqs)
        hi = now_ms()
        if len(resps) != len(reqs):
            raise SmokeFailure(
                f"verify rpc: {len(resps)} responses for {len(reqs)} checks"
            )
        for req, got in zip(reqs, resps):
            self.checked += 1
            if not req.unique_key or not req.name:
                want = RateLimitResp(error=(
                    ERR_EMPTY_KEY if not req.unique_key else ERR_EMPTY_NAME
                ))
                win = (0, 0)
            else:
                key = req.hash_key()
                fresh = key not in self.model.cache
                want = self.model.get_rate_limit(req)
                if req.algorithm == Algorithm.TOKEN_BUCKET:
                    if fresh:
                        self.created[key] = (lo, hi)
                    win = self.created[key]
                else:
                    win = (lo, hi)
            for f in ("status", "remaining", "limit", "error"):
                if getattr(got, f) != getattr(want, f):
                    self._bad(f, req, want, got)
                    break
            else:
                base = want.reset_time - self.t0
                ok = (
                    got.reset_time == 0 if want.reset_time == 0
                    else win[0] + base <= got.reset_time <= win[1] + base
                )
                if not ok:
                    self._bad(f"reset_time (window {win})", req, want, got)


def verify_wire(addr: str, seed: int, full: bool) -> WireOracle:
    """The verified checks: UNDER->OVER on one key, duplicates inside one
    RPC, RESET_REMAINING, validation errors, leaky with burst, and a
    seeded random stream (`full`; the second start runs the first only)."""
    oracle = WireOracle()
    T, L = Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET

    def req(key, hits=1, limit=5, algo=T, behavior=0, burst=0,
            name="smoke_verify"):
        return RateLimitReq(
            name=name, unique_key=key, hits=hits, limit=limit,
            duration=DURATION_MS, algorithm=algo, behavior=behavior,
            burst=burst,
        )

    with FastV1Client(addr) as client:
        if client.codec != "native":
            raise SmokeFailure("client codec is not the compiled one")
        tag = f"{seed}-{'full' if full else 'again'}"
        for _ in range(8):  # UNDER x5 then OVER, one check per RPC
            oracle.rpc(client, [req(f"uo-{tag}")])
        if not full:
            return oracle
        # Duplicates inside one RPC decrement in order, token and leaky.
        oracle.rpc(client, [req(f"dup-{tag}", limit=6)] * 10)
        oracle.rpc(client, [req(f"dupl-{tag}", limit=6, algo=L)] * 10)
        # RESET_REMAINING: drained, reset (reset_time 0), recreated.
        oracle.rpc(client, [req(f"rr-{tag}", hits=3)])
        oracle.rpc(client, [req(
            f"rr-{tag}", behavior=int(Behavior.RESET_REMAINING)
        )])
        oracle.rpc(client, [req(f"rr-{tag}")])
        # Validation errors ride beside valid lanes.
        oracle.rpc(client, [
            req(f"ok-{tag}"), req(""), req(f"x-{tag}", name=""),
            req(f"ok-{tag}"),
        ])
        # Leaky with burst > limit: 20 admitted, then OVER.
        for _ in range(3):
            oracle.rpc(client, [
                req(f"lb-{tag}", limit=10, burst=20, algo=L)
            ] * 9)
        # Seeded random stream: fixed algorithm per key (a switch would
        # re-create the bucket at an unknown server time), limits that
        # change, zero-hit reads, over-asks, resets, duplicates.
        rng = random.Random(seed)
        for _ in range(12):
            batch = []
            for _ in range(250):
                k = rng.randrange(200)
                algo = L if k % 2 else T
                batch.append(req(
                    f"r{k}-{tag}",
                    hits=rng.choice([0, 1, 1, 1, 2, 5, 40]),
                    limit=rng.choice([10, 10, 10, 50]),
                    algo=algo,
                    behavior=(
                        int(Behavior.RESET_REMAINING)
                        if rng.random() < 0.03 else 0
                    ),
                    burst=20 if (algo == L and k % 4 == 1) else 0,
                    name=f"smoke_v{k % 3}",
                ))
            oracle.rpc(client, batch)
    return oracle


def verify_global(addr: str, seed: int) -> dict:
    """Behavior=GLOBAL on the mesh: hits ingest into the arrival shard's
    replicated cache, one collective sync (psum) folds them into the
    owner's authoritative row and re-broadcasts it (the sequence
    __graft_entry__.dryrun_multichip asserts on virtual devices).  A
    key's reads arrive where its hits did, so the GLOBAL read-back alone
    would not show the sync: the same keys are also read WITHOUT the
    flag, which answers from the owner shard of the sharded table."""
    n_keys, rounds, limit = 24, 3, 100

    def reqs(hits, behavior):
        return [
            RateLimitReq(
                name="smoke_global", unique_key=f"g{i}-{seed}", hits=hits,
                limit=limit, duration=DURATION_MS, behavior=behavior,
            )
            for i in range(n_keys)
        ]

    want = [limit - rounds]
    with FastV1Client(addr) as client:
        for _ in range(rounds):
            for r in client.get_rate_limits(reqs(1, int(Behavior.GLOBAL))):
                if r.error or r.status != Status.UNDER_LIMIT:
                    raise SmokeFailure(f"GLOBAL hit refused: {r!r}")
        t0 = time.monotonic()
        polls = 0
        while True:
            polls += 1
            replicated = sorted({r.remaining for r in client.get_rate_limits(
                reqs(0, int(Behavior.GLOBAL)))})
            owner = sorted({r.remaining for r in client.get_rate_limits(
                reqs(0, 0))})
            if replicated == want and owner == want:
                break
            if time.monotonic() - t0 > 30:
                raise SmokeFailure(
                    "GLOBAL did not converge in 30s: replicated read-back "
                    f"{replicated}, owner rows {owner}, want {want}"
                )
            time.sleep(0.05)
    return {
        "keys": n_keys, "hits_per_key": rounds,
        "read_back_remaining": want[0], "owner_remaining": want[0],
        "polls": polls, "converged_s": round(time.monotonic() - t0, 3),
        # The sync wrote these buckets into the owner shards' table.
        "hashes": native.hash_keys([r.hash_key() for r in reqs(0, 0)]),
    }


def server_phase(args, name: str, mesh_ways: int, full: bool) -> dict:
    out: dict = {"mesh_ways": mesh_ways}
    with Server(args, name, mesh_ways) as srv:
        out["setup_s"] = round(srv.wait_ready(), 2)
        v0 = srv.vars()
        dev = v0["device"]
        # Rows the daemon's own warm-up left behind (already expired).
        occ0 = v0["backend"]["occupancy"]
        out["warmup_s"] = dev["warmup_s"]
        out["cache_entries_before"] = srv.cache_before
        ready_names = cache_names()
        out["cache_entries_after"] = len(ready_names)
        log(f"{name}: ready in {out['setup_s']}s (warm-up "
            f"{out['warmup_s']}s), device {dev}")
        t_serve = time.monotonic()
        hashes = [np.zeros(0, dtype=np.int64)]
        if full:
            load = asyncio.run(load_keys(
                srv.grpc, args.keys, args.seed, args.load_budget,
            ))
            hashes.append(load.pop("hashes"))
            out["load"] = load
            out["keys_loaded"] = load["loaded"]
            if load["loaded"] < args.keys:
                out["keys_loaded_reason"] = (
                    f"load budget of {args.load_budget:.0f}s ran out "
                    f"after {load['loaded']} of {args.keys} keys"
                )
            log(f"{name}: loaded {load['loaded']} keys in "
                f"{load['seconds']}s, {load['bad_lanes']} bad lanes")
            if load["bad_lanes"]:
                raise SmokeFailure(
                    f"{name}: {load['bad_lanes']} load responses differ "
                    f"from the new-item answer; first {load['first_bad']}"
                )
            if load["loaded"] < min(args.keys, 1_000_000):
                raise SmokeFailure(
                    f"{name}: only {load['loaded']} keys loaded"
                )
        oracle = verify_wire(srv.grpc, args.seed, full)
        hashes.append(oracle.resident_hashes())
        out["verified"] = oracle.checked
        out["mismatches"] = oracle.mismatches
        log(f"{name}: {oracle.checked} checks verified, "
            f"{oracle.mismatches} mismatches")
        if oracle.mismatches:
            raise SmokeFailure(
                f"{name}: {oracle.mismatches} of {oracle.checked} answers "
                f"differ from core/pymodel.py; first {oracle.first}"
            )
        if mesh_ways > 1:
            out["global"] = verify_global(srv.grpc, args.seed)
            hashes.append(out["global"].pop("hashes"))
            log(f"{name}: GLOBAL converged {out['global']}")
        out["serve_s"] = round(time.monotonic() - t_serve, 2)

        v = srv.vars()
        dev, fp, be = v["device"], v["fastpath"], v["backend"]
        out["device"] = dev
        want = expected_resident(
            np.concatenate(hashes), args.slots, mesh_ways
        )
        out["keys_resident"] = be["occupancy"]
        out["keys_resident_expected"] = want
        out["not_persisted"] = be["not_persisted"]
        out["fastpath"] = {
            k: fp[k] for k in
            ("served", "fallbacks", "serve_mode", "effective_serve_mode")
        }
        checks = [
            (dev["platform"] == args.platform,
             f"platform {dev['platform']!r}, want {args.platform!r}"),
            (dev["compiled_lane"] is True,
             f"compiled lane did not load: "
             f"{dev.get('compiled_lane_error')}"),
            (len(set(dev["table_device_ids"])) == mesh_ways,
             f"table on devices {dev['table_device_ids']}, want "
             f"{mesh_ways} distinct"),
            # Set-associative arithmetic, not a load-factor guess: every
            # bucket holds min(arrivals, ways), less the lanes the daemon
            # itself counted as answered-but-not-stored (a key that lost
            # all INSERT_ROUNDS same-batch claims on its bucket).
            (want - be["not_persisted"] <= be["occupancy"] <= want + occ0,
             f"occupancy {be['occupancy']}, expected {want} less at most "
             f"{be['not_persisted']} not persisted, plus at most {occ0} "
             "warm-up rows"),
            (fp["served"] > 0, "fastpath.served == 0"),
            (fp["fallbacks"] == 0,
             f"fastpath.fallbacks == {fp['fallbacks']}"),
            (fp["effective_serve_mode"] == fp["serve_mode"],
             f"serve mode {fp['serve_mode']!r} degraded to "
             f"{fp['effective_serve_mode']!r}"),
        ]
        if mesh_ways > 1:
            occ = be["shard_occupancy"]
            out["shard_occupancy"] = occ
            checks.append((
                len(occ) == mesh_ways and min(occ) > 0
                and max(occ) <= 1.05 * min(occ) + 64,
                f"shard occupancy {occ} is not {mesh_ways} balanced "
                "non-zero entries",
            ))
        failed = [msg for ok, msg in checks if not ok]
        if failed:
            raise SmokeFailure(f"{name}: " + "; ".join(failed))
        # Executables first compiled while SERVING: what the daemon's
        # warm-up does not cover (a first request paid for each).
        out["compiled_while_serving"] = sorted(
            f.rsplit("-", 2)[0] for f in cache_names() - ready_names
        )
        log(f"{name}: compiled while serving {out['compiled_while_serving']}")
    return out


# ---------------------------------------------------------------------------
# Phase B (child; imports JAX): exact differential + kernel compiles
# ---------------------------------------------------------------------------

_I62, _I63 = 2**62, 2**63 - 1


def _diff_req(rng: random.Random, n_keys: int) -> RateLimitReq:
    """tests/test_differential.py's generator plus what a chip may get
    wrong: non-integral leak rates (60000/7, 1000/3), the 60000/20000 == 3
    division, sub-millisecond rates, and hostile int64 limits, bursts and
    durations that drive the expiry adds and the float64->int64
    truncation into saturation (hits stay small: the counter algebra
    saturates only where ops/step.py says it does)."""
    algo = rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET])
    behavior = 0
    if rng.random() < 0.05:
        behavior |= int(Behavior.RESET_REMAINING)
    if rng.random() < 0.05:
        behavior |= int(Behavior.DURATION_IS_GREGORIAN)
    hostile = rng.random() < 0.01
    if behavior & int(Behavior.DURATION_IS_GREGORIAN):
        duration = rng.choice([0, 1, 2])  # minutes/hours/days
    elif hostile:
        duration = rng.choice([_I62, _I63, 1])
    else:
        duration = rng.choice([5, 1000, 30_000, 60_000, 60_000, 86_400_000])
    return RateLimitReq(
        name=f"diff_{rng.randrange(4)}",
        unique_key=f"k:{rng.randrange(n_keys)}",
        algorithm=algo,
        behavior=behavior,
        hits=(rng.choice([0, 1, 2]) if hostile
              else rng.choice([0, 1, 1, 1, 2, 5, -1, 100])),
        limit=(rng.choice([_I62, _I63, 1]) if hostile
               else rng.choice([0, 1, 2, 3, 7, 10, 13, 100, 2000, 20_000])),
        duration=duration,
        burst=rng.choice([0, 0, 0, 20, _I62 if hostile else 7]),
    )


def phase_differential(args) -> int:
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.core.config import DeviceConfig
    from gubernator_tpu.core.pymodel import _trunc
    from gubernator_tpu.ops.step import _trunc_i64
    from gubernator_tpu.runtime.backend import DeviceBackend

    out: dict = {}
    clk = clock_mod.Clock()
    clk.freeze(1_700_000_000_000 * 1_000_000)
    t0 = time.monotonic()
    be = DeviceBackend(
        DeviceConfig(num_slots=args.slots, ways=WAYS,
                     batch_size=args.batch, platform=args.platform),
        clock=clk,
    )
    out["device"] = be.device_info()
    oracle = PyRateLimiter(clock=clk)
    rng = random.Random(args.seed)
    n_keys = 500  # x4 names; the table holds all of them, nothing evicts
    ops = 0
    token_bad = leaky_status_bad = 0
    # Inexact leaky lanes, apart: buckets a >= 2^62 limit/burst/duration
    # ever touched (a TPU float64 carries ~48 bits, so those cannot be
    # exact) and everything else.
    leaky_inexact = {"in_envelope": 0, "int64_corner": 0}
    corner_keys = set()
    first: Dict[str, dict] = {}

    def note(kind: str, step, req, want, got) -> None:
        first.setdefault(kind, {
            "step": step, "req": repr(req), "want": repr(want),
            "got": repr(got),
        })

    step = 0
    while ops < args.diff_ops:
        batch = [
            _diff_req(rng, n_keys) for _ in range(rng.randrange(1, 3000))
        ]
        got_all = be.check(batch)
        for req, got in zip(batch, got_all):
            want = oracle.get_rate_limit(req)
            if max(req.limit, req.burst, req.duration) >= _I62:
                corner_keys.add(req.hash_key())
            exact = (
                got.status == want.status
                and got.remaining == want.remaining
                and got.limit == want.limit
                and got.reset_time == want.reset_time
                and got.error == want.error
            )
            if exact:
                continue
            if req.algorithm == Algorithm.TOKEN_BUCKET:
                token_bad += 1
                note("token", step, req, want, got)
            elif got.status != want.status or got.error != want.error:
                leaky_status_bad += 1
                note("leaky_status", step, req, want, got)
            else:
                kind = (
                    "int64_corner" if req.hash_key() in corner_keys
                    else "in_envelope"
                )
                leaky_inexact[kind] += 1
                note(f"leaky_inexact_{kind}", step, req, want, got)
        ops += len(batch)
        step += 1
        # Past expiries, and fractions of a token across steps.
        clk.advance(rng.choice([0, 1, 7, 333, 500, 3_000, 61_000]))
    out["differential"] = {
        "ops": ops, "steps": step,
        "seconds": round(time.monotonic() - t0, 2),
        "token_mismatches": token_bad,
        "leaky_status_mismatches": leaky_status_bad,
        "leaky_inexact_lanes": leaky_inexact,
        "first": first,
    }

    # The float64 pieces alone, so a stream divergence has a named cause:
    # the truncation corners tests/test_differential.py pins on XLA:CPU,
    # and the leak-rate division f64(duration)/f64(limit).
    import math

    below = math.nextafter(2.0**63, 0.0)
    vals = [
        0.0, -0.0, 0.5, -0.5, 1.9, -1.5, -2.7, 2.999,
        2.0**62, -(2.0**62), 2.0**62 + 4096.0, -(2.0**62) - 4096.0,
        below, -below, 2.0**63, -(2.0**63), 9.3e18, -9.3e18, 1e308, -1e308,
        float("inf"), float("-inf"), float("nan"),
        math.nextafter(1.0, 0.0), math.nextafter(-1.0, 0.0),
        1.7e12 + 0.5, 2.0**53 - 1.0,
    ]
    got_t = np.asarray(
        jax.jit(_trunc_i64)(jnp.asarray(vals, dtype=jnp.float64))
    )
    bad_t = [
        {"value": repr(v), "device": int(g), "oracle": _trunc(v)}
        for v, g in zip(vals, got_t) if int(g) != _trunc(v)
    ]
    out["trunc_corners"] = {
        "checked": len(vals), "mismatches": len(bad_t), "list": bad_t,
    }
    durs = np.array(
        [5, 1000, 30_000, 60_000, 86_400_000, DURATION_MS, _I62],
        dtype=np.int64,
    )
    lims = np.array(
        [1, 2, 3, 7, 10, 13, 100, 1000, 2000, 20_000, _I62], dtype=np.int64
    )
    dd, ll = [a.ravel() for a in np.meshgrid(durs, lims)]
    got_q = np.asarray(jax.jit(
        lambda d, l: d.astype(jnp.float64) / l.astype(jnp.float64)
    )(dd, ll))
    want_q = dd.astype(np.float64) / ll.astype(np.float64)
    bad_q = np.flatnonzero(got_q != want_q)
    out["f64_division"] = {
        "checked": len(dd), "inexact": len(bad_q),
        "first": None if not len(bad_q) else {
            "duration": int(dd[bad_q[0]]), "limit": int(ll[bad_q[0]]),
            "device": repr(float(got_q[bad_q[0]])),
            "ieee": repr(float(want_q[bad_q[0]])),
        },
    }
    del be

    out["kernels"] = compile_kernels(args)
    ok = not (token_bad or leaky_status_bad)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


def compile_kernels(args) -> dict:
    """Compile the opt-in Pallas kernel WITHOUT interpret at the size
    the daemon would run it.  A refusal is recorded word for word, not
    failed: the kernel is opt-in (GUBER_SKETCH_USE_PALLAS)."""
    import jax

    from gubernator_tpu.core.config import SketchTierConfig
    from gubernator_tpu.ops.pallas.cms_kernel import cms_step_pallas
    from gubernator_tpu.ops.sketch import cms_step, init_sketch

    out: dict = {}
    sk = SketchTierConfig()
    rng = np.random.default_rng(args.seed)
    B = sk.batch_size
    ks = rng.integers(1, 1 << 62, size=B, dtype=np.int64)
    ks[: B // 8] = 0  # inactive lanes
    hits = rng.integers(0, 5, size=B).astype(np.int32)
    limits = np.full(B, 20, np.int32)
    now = np.int64(1_700_000_000_000)
    cms: dict = {"width": sk.width, "depth": sk.depth, "batch": B}
    t0 = time.monotonic()
    try:
        st_p, over_p, est_p = cms_step_pallas(
            init_sketch(sk.depth, sk.width, sk.window_ms), ks, hits,
            limits, now,
        )
        jax.block_until_ready(est_p)
    except Exception as e:  # noqa: BLE001 — the compiler's reason IS the result
        cms.update(ok=False, reason=f"{type(e).__name__}: {e}")
    else:
        st_x, over_x, est_x = cms_step(
            init_sketch(sk.depth, sk.width, sk.window_ms), ks, hits,
            limits, now,
        )
        same = bool(
            np.array_equal(np.asarray(over_x), np.asarray(over_p))
            and np.array_equal(np.asarray(est_x), np.asarray(est_p))
            and np.array_equal(np.asarray(st_x.cur), np.asarray(st_p.cur))
        )
        cms.update(ok=True, reason="", matches_scatter_reference=same)
    cms["seconds"] = round(time.monotonic() - t0, 2)
    out["cms_pallas"] = cms
    return out


def differential_phase(args) -> dict:
    """Run phase B as a child (it imports JAX) once no server holds the
    chip; its last stdout line is its JSON result."""
    out_path = os.path.join(args.out, "differential.out")
    err_path = os.path.join(args.out, "differential.log")
    cmd = [
        sys.executable, os.path.abspath(__file__), "--phase", "differential",
        "--platform", args.platform, "--slots", str(args.slots),
        "--batch", str(args.batch), "--seed", str(args.seed),
        "--diff-ops", str(args.diff_ops),
    ]
    log("differential: started")
    t0 = time.monotonic()
    with open(out_path, "wb") as so, open(err_path, "wb") as se:
        proc = subprocess.Popen(
            cmd, env=child_env(args), cwd=REPO, stdout=so, stderr=se,
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"differential: no result in {PHASE_TIMEOUT_S:.0f}s\n"
                + tail(err_path)
            )
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    lines = [ln for ln in tail(out_path, 5).splitlines() if ln.strip()]
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(
            f"differential: rc={rc}, no JSON result\n" + tail(err_path)
        )
    res["seconds_total"] = round(time.monotonic() - t0, 2)
    d = res["differential"]
    log(f"differential: rc={rc} {d['ops']} ops, token mismatches "
        f"{d['token_mismatches']}, leaky status mismatches "
        f"{d['leaky_status_mismatches']}, leaky inexact lanes "
        f"{d['leaky_inexact_lanes']} (status equal)")
    for k, v in res["kernels"].items():
        log(f"kernel {k}: ok={v['ok']} {v['reason'][:300]}")
    if rc != 0 or not res.get("ok"):
        raise SmokeFailure(
            "differential: the device disagrees with core/pymodel.py "
            f"beyond inexact leaky lanes: {json.dumps(d)}"
        )
    if res["device"]["platform"] != args.platform:
        raise SmokeFailure(
            f"differential ran on {res['device']['platform']!r}"
        )
    return res


# ---------------------------------------------------------------------------


def run(args) -> dict:
    os.makedirs(args.out, exist_ok=True)
    # Serve from a library built from the native/gubtpu.cpp beside it:
    # the loader verifies the stamped source hash and rebuilds on a
    # mismatch; a failed build is fatal here.
    native.require()
    summary: dict = {
        "ok": True,
        "device": None,
        "chips": args.chips,
        "table_slots": args.slots,
        "batch_size": args.batch,
        "keys_requested": args.keys,
        "seed": args.seed,
        "native": {
            "source_sha256": native.source_hash(),
            "built_this_run": native.rebuilt(),
        },
        "compile_cache_dir": compile_cache_dir(),
        "phases": {},
    }
    phases = summary["phases"]
    if args.chips == 1:
        phases["server_first"] = first = server_phase(
            args, "server_first", 1, full=True
        )
        phases["server_again"] = again = server_phase(
            args, "server_again", 1, full=False
        )
        if not first["cache_entries_after"]:
            raise SmokeFailure(
                "the daemon left no compile-cache entries under "
                f"{compile_cache_dir()}"
            )
        added = (
            again["cache_entries_after"] - again["cache_entries_before"]
        )
        if added:
            raise SmokeFailure(
                f"the second start added {added} compile-cache entries"
            )
        phases["differential"] = differential_phase(args)
        dev = first["device"]
        main_phase = first
        if dev["device_count"] >= 4:
            phases["server_mesh4"] = server_phase(
                args, "server_mesh4", 4, full=True
            )
    else:
        phases["server_mesh4"] = main_phase = server_phase(
            args, "server_mesh4", args.chips, full=True
        )
        dev = main_phase["device"]
    summary["device"] = {
        "platform": dev["platform"],
        "kind": dev["device_kind"],
        "count": dev["device_count"],
    }
    for k in ("keys_loaded", "keys_loaded_reason", "keys_resident",
              "keys_resident_expected"):
        if k in main_phase:
            summary[k] = main_phase[k]
    summary["seconds_total"] = round(time.monotonic() - T_START, 1)
    summary["claim"] = None
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="the platform every child must run on")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the GUBER_MESH_WAYS=4 server phase")
    ap.add_argument("--slots", type=int, default=1 << 24)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--keys", type=int, default=10_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--diff-ops", type=int, default=100_000)
    ap.add_argument("--load-budget", type=float, default=360.0,
                    help="seconds the key load may take before it stops "
                    "short (keys_loaded says how far it got)")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"))
    ap.add_argument("--server-cmd", default="",
                    help="replace the server child's command (tests)")
    ap.add_argument("--phase", default="", choices=("", "differential"),
                    help="internal: run one JAX phase in this process")
    args = ap.parse_args()
    if args.phase == "differential":
        return phase_differential(args)
    try:
        summary = run(args)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    if "jax" in sys.modules:
        log("FAILED: the parent imported jax")
        return 1
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
