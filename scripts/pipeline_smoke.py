"""Pipelined-drain CPU smoke: depth-2 vs depth-1 result equivalence.

Drives ~10k mixed checks (token/leaky, bursts, RESET_REMAINING, valid
Gregorian, zero/negative hits, duplicate keys) through the compiled fast
lane twice — once at depth 1 (`FastPath(svc, pipeline_depth=1)`, the
strict pre-pipeline discipline: the reference) and once at depth 2 —
under a frozen clock, with concurrent workers owning disjoint key spaces
so every key's history is deterministic regardless of merge composition.
Responses and the final table rows must match bit-for-bit; the depth-2
run must actually have pipelined (>= 2 merges observed in flight) or the
smoke is vacuous.

Runs in the CI matrix (JAX_PLATFORMS=cpu); exit 0 = pass.
"""
from __future__ import annotations

import asyncio
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_WORKERS = 6
BATCHES_PER_WORKER = 24
KEYS_PER_WORKER = 8


def build_schedules():
    from gubernator_tpu.proto import gubernator_pb2 as pb

    rng = random.Random(1234)
    schedules = []
    total = 0
    for w in range(N_WORKERS):
        payloads = []
        for _ in range(BATCHES_PER_WORKER):
            reqs = []
            for _ in range(rng.randrange(40, 90)):
                behavior = 0
                duration = rng.choice([60_000, 60_000, 1_000])
                if rng.random() < 0.06:
                    behavior |= 8  # RESET_REMAINING
                if rng.random() < 0.04:
                    behavior |= 4  # DURATION_IS_GREGORIAN
                    duration = rng.choice([1, 4])
                reqs.append(pb.RateLimitReq(
                    name=f"smoke{w}",
                    unique_key=f"k{rng.randrange(KEYS_PER_WORKER)}",
                    hits=rng.choice([0, 1, 1, 1, 2, 5, -1]),
                    limit=rng.choice([50, 200, 1000]),
                    duration=duration,
                    algorithm=rng.choice([0, 1]),
                    behavior=behavior,
                    burst=rng.choice([0, 0, 60]),
                ))
            total += len(reqs)
            payloads.append(
                pb.GetRateLimitsReq(requests=reqs).SerializeToString()
            )
        schedules.append(payloads)
    return schedules, total


def run_at_depth(depth: int, schedules, clock):
    from gubernator_tpu.core.config import Config, DeviceConfig
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime.fastpath import FastPath
    from gubernator_tpu.runtime.service import Service

    dev = DeviceConfig(num_slots=1 << 14, ways=8, batch_size=512)

    async def scenario():
        svc = Service(Config(device=dev), clock=clock)
        await svc.start()
        fp = FastPath(svc, pipeline_depth=depth)
        results: dict = {}

        async def worker(w: int):
            await asyncio.sleep(w * 0.002)
            got = []
            for payload in schedules[w]:
                raw = await fp.check_raw(payload, peer_rpc=False)
                assert raw is not None, "fast lane fell back"
                got.append([
                    (r.status, r.limit, r.remaining, r.reset_time, r.error)
                    for r in pb.GetRateLimitsResp.FromString(raw).responses
                ])
            results[w] = got

        await asyncio.gather(*(worker(w) for w in range(N_WORKERS)))
        rows = {}
        for w in range(N_WORKERS):
            for k in range(KEYS_PER_WORKER):
                key = f"smoke{w}_k{k}"
                item = svc.backend.get_cache_item(key)
                rows[key] = (
                    (item.remaining, item.expire_at, int(item.status),
                     item.limit, item.duration, int(item.algorithm))
                    if item is not None else None
                )
        stats = fp._mach.debug_vars()
        await fp.close()
        await svc.close()
        return results, rows, stats

    return asyncio.run(scenario())


def main() -> int:
    from gubernator_tpu import native
    from gubernator_tpu.core import clock as clock_mod

    if not native.available():
        print("pipeline_smoke: SKIP (native library unavailable)")
        return 0

    schedules, total = build_schedules()
    print(f"pipeline_smoke: {total} checks x 2 depths")
    clock_mod.freeze()
    try:
        base_results, base_rows, base_stats = run_at_depth(
            1, schedules, clock_mod.default_clock()
        )
        deep_results, deep_rows, deep_stats = run_at_depth(
            2, schedules, clock_mod.default_clock()
        )
    finally:
        clock_mod.unfreeze()

    ok = True
    if deep_results != base_results:
        for w in base_results:
            for i, (a, b) in enumerate(
                zip(base_results[w], deep_results[w])
            ):
                if a != b:
                    print(
                        f"FAIL: worker {w} batch {i} diverged:\n"
                        f"  depth1: {a[:3]}...\n  depth2: {b[:3]}..."
                    )
                    break
        ok = False
    if deep_rows != base_rows:
        diff = {
            k for k in base_rows if base_rows[k] != deep_rows.get(k)
        }
        print(f"FAIL: {len(diff)} table rows diverged: {sorted(diff)[:5]}")
        ok = False
    if deep_stats["max_inflight_seen"] < 2:
        print(
            "FAIL: depth-2 run never pipelined "
            f"(max_inflight_seen={deep_stats['max_inflight_seen']})"
        )
        ok = False
    print(f"pipeline_smoke: depth1 stats {base_stats}")
    print(f"pipeline_smoke: depth2 stats {deep_stats}")
    if ok:
        print(
            f"pipeline_smoke: OK — {total} checks bit-identical across "
            "depths; depth-2 overlapped "
            f"{deep_stats['max_inflight_seen']} merges"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
