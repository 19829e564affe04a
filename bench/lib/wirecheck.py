"""The wire check: the special cases of the reference's semantics that the
cell's bulk traffic never sends (duplicates of differing size inside one
RPC, RESET_REMAINING, validation errors beside valid lanes, leaky burst,
changing limits, zero-hit reads), a few thousand checks against
core/pymodel.py before the warm-in.  Copied from chip_smoke.py (WireOracle,
verify_wire) so that a later change there cannot move the yardstick; its
keys live outside the universe, and the harness counts their fingerprints
into the placement arithmetic.
"""
from __future__ import annotations

import random
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from gubernator_tpu import native
from gubernator_tpu.client import FastV1Client
from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.pymodel import PyRateLimiter
from gubernator_tpu.core.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
)

DURATION_MS = 30 * 24 * 3600 * 1000
# The reference's validation errors (gubernator.go:229,235).
ERR_EMPTY_KEY = "field 'unique_key' cannot be empty"
ERR_EMPTY_NAME = "field 'namespace' cannot be empty"


class WireCheckFailure(Exception):
    """The daemon's answer cannot be compared at all."""


def now_ms() -> int:
    return time.time_ns() // 1_000_000


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
        self.seen: set = set()
        self.checked = 0
        self.mismatches = 0
        self.first: Optional[dict] = None

    def resident_hashes(self) -> np.ndarray:
        """Fingerprints of the buckets the oracle still holds (a token
        bucket whose last word was RESET_REMAINING is gone)."""
        return native.hash_keys(list(self.model.cache))

    def seen_hashes(self) -> np.ndarray:
        """Fingerprints of every bucket the check ever sent: one that a
        RESET_REMAINING removed again may still have evicted a row."""
        return native.hash_keys(sorted(self.seen))

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
            raise WireCheckFailure(
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
                self.seen.add(key)
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


def verify_wire(addr: str, seed: int) -> WireOracle:
    """The verified checks: UNDER->OVER on one key, duplicates inside one
    RPC, RESET_REMAINING, validation errors, leaky with burst, and a
    seeded random stream."""
    oracle = WireOracle()
    T, L = Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET

    def req(key, hits=1, limit=5, algo=T, behavior=0, burst=0,
            name="bench_verify"):
        return RateLimitReq(
            name=name, unique_key=key, hits=hits, limit=limit,
            duration=DURATION_MS, algorithm=algo, behavior=behavior,
            burst=burst,
        )

    with FastV1Client(addr) as client:
        if client.codec != "native":
            raise WireCheckFailure("client codec is not the compiled one")
        tag = str(seed)
        for _ in range(8):  # UNDER x5 then OVER, one check per RPC
            oracle.rpc(client, [req(f"uo-{tag}")])
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
                    name=f"bench_v{k % 3}",
                ))
            oracle.rpc(client, batch)
    return oracle
