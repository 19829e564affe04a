"""Device hot-path microbench: rate-limit decisions/sec on one chip at 10M keys.

Measures the steady-state device step (ops/step.py apply_batch): a
2^24-slot table (~16.7M slots, 8-way buckets) under a 10M-key workload,
mixed token/leaky bucket, batch of 262144 decisions per step
(BENCH_BATCH overrides).  The batch size is the framework's operating
point, not a workload property — the service's maximal-merge drains
feed steps whatever is queued, and per-step launch overhead amortizes
with batch until HBM bandwidth binds.

Two phases, KERNEL and FED:

- kernel: pre-staged device-resident batches, responses left on device
  (one sync per 16 steps) — the chip's decision capability with feeding
  excluded.
- fed: every step uploads a fresh packed [12, B] request array and
  fetches the packed [9, B] response via apply_batch_packed_q at the
  SERVICE-DRAIN lane count (B = BENCH_FED_BATCH, default 4096 — the
  shape the daemon's coalesced merges actually dispatch), pipelined
  with double buffering — what a served workload can realize through
  the host link (168 bytes/decision).

The north-star target (BASELINE.json) is >=50M decisions/sec on a v5e-4,
i.e. 12.5M decisions/sec/chip; `vs_baseline` is value / 12.5e6.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device"} plus fed_* companion fields.  It measures a chip: where JAX
finds only the CPU it exits non-zero, and any failed phase fails the run.
No PERF_LEDGER cell is defined on it yet; nothing it printed before this
machine existed is kept.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

_T0 = time.perf_counter()


def _phase(msg: str) -> None:
    """Progress to stderr (stdout carries only the single JSON line)."""
    sys.stderr.write("[bench %7.1fs] %s\n" % (time.perf_counter() - _T0, msg))
    sys.stderr.flush()


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from gubernator_tpu.ops.state import init_table
    from gubernator_tpu.ops.step import (
        DeviceBatchJ,
        apply_batch,
        apply_batch_impl,
        apply_batch_packed_q,
    )

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit(
            "bench.py measures a chip; JAX found only the CPU"
        )
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }

    num_slots = 1 << 24
    ways = 8
    batch = int(os.environ.get("BENCH_BATCH", 262_144))
    n_keys = int(os.environ.get("BENCH_KEYS", 10_000_000))
    fed_batch = min(batch, int(os.environ.get("BENCH_FED_BATCH", 4096)))
    if n_keys < batch:
        raise SystemExit(
            "BENCH_KEYS (%d) must be >= BENCH_BATCH (%d) for unique "
            "per-batch sampling" % (n_keys, batch)
        )
    if fed_batch < 1:
        raise SystemExit("BENCH_FED_BATCH must be >= 1 (got %d)" % fed_batch)
    n_staged = 8
    now = np.int64(1_700_000_000_000)

    rng = np.random.default_rng(0)
    key_pool = rng.integers(1, 1 << 63, size=n_keys, dtype=np.int64)
    _phase("key pool generated")

    def batch_from_keys(ks) -> DeviceBatchJ:
        """Expand a [batch] key vector into a full DeviceBatchJ on device —
        only the 8-byte/key key column ever crosses the host link."""
        active = ks != 0
        algo = (
            (ks.astype(jnp.uint64) >> jnp.uint64(7)) & jnp.uint64(1)
        ).astype(jnp.int32)
        limit = jnp.full((batch,), 1000, jnp.int64)
        zi = jnp.zeros((batch,), jnp.int64)
        zb = jnp.zeros((batch,), jnp.bool_)
        return DeviceBatchJ(
            key_hash=ks,
            hits=active.astype(jnp.int64),
            limit=limit,
            duration=jnp.full((batch,), 3_600_000, jnp.int64),
            algo=algo,
            burst=limit,
            reset_remaining=zb,
            is_greg=zb,
            greg_expire=zi,
            greg_duration=zi,
            active=active,
            use_cached=zb,
        )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def populate(tbl, keys2d, now_):
        """Insert every key row of keys2d [n_chunks, batch] in ONE device
        program (lax.scan) — one compile, one dispatch."""

        def body(t, ks):
            t, _ = apply_batch_impl(t, batch_from_keys(ks), now_, ways)
            return t, None

        tbl, _ = lax.scan(body, tbl, keys2d)
        return tbl

    with jax.default_device(dev):
        table = init_table(num_slots)
    _phase("table initialized (%d slots)" % num_slots)

    # Populate: insert all keys so the measured steady state runs against
    # a full-size live working set (~60% table load factor at defaults).
    n_chunks = (n_keys + batch - 1) // batch
    keys_padded = np.zeros(n_chunks * batch, dtype=np.int64)
    keys_padded[:n_keys] = key_pool
    keys2d = jax.device_put(keys_padded.reshape(n_chunks, batch), dev)
    table = populate(table, keys2d, now)
    jax.block_until_ready(table.key)
    del keys2d
    _phase("populate done (%d keys, %d chunks)" % (n_keys, n_chunks))

    # Staged measurement batches: unique keys WITHIN each batch (the
    # steady state measured is the unique-key path, not the intra-batch
    # duplicate cascade), drawn uniformly from the full key pool.
    staged_idx = np.stack([
        rng.choice(n_keys, size=batch, replace=False)
        for _ in range(n_staged)
    ])
    expand = jax.jit(batch_from_keys)
    staged = [
        expand(jax.device_put(key_pool[staged_idx[i]], dev))
        for i in range(n_staged)
    ]
    for i in range(2):  # warm the measurement shape
        table, resp = apply_batch(table, staged[i], now, ways=ways)
    jax.block_until_ready(resp.status)
    _phase("warmup done")

    # Timed: run for ~2 seconds of wall time.
    iters = 0
    t0 = time.perf_counter()
    deadline = t0 + 2.0
    while time.perf_counter() < deadline:
        table, resp = apply_batch(
            table, staged[iters % n_staged], now, ways=ways
        )
        iters += 1
        if iters % 16 == 0:
            jax.block_until_ready(resp.status)
    jax.block_until_ready(resp.status)
    elapsed = time.perf_counter() - t0
    value = batch * iters / elapsed
    _phase("kernel phase done (%d iters, %.2fs)" % (iters, elapsed))

    # FED: fresh packed request upload + packed response fetch per step
    # (apply_batch_packed_q, the service-drain shape), double buffered —
    # dispatch step i+1 before fetching response i.
    def pack_q(ks: np.ndarray) -> np.ndarray:
        q = np.zeros((12, fed_batch), dtype=np.int64)
        q[0] = ks
        q[1] = 1
        q[2] = 1000
        q[3] = 3_600_000
        q[4] = (ks.astype(np.uint64) >> np.uint64(7)) & np.uint64(1)
        q[5] = 1000
        q[10] = 1
        return q

    host_qs = [
        pack_q(key_pool[staged_idx[i][:fed_batch]]) for i in range(n_staged)
    ]
    table, r = apply_batch_packed_q(
        table, jax.device_put(host_qs[0], dev), now, ways=ways
    )
    np.asarray(r)  # warm the shape + the transfer path
    fetched = fed_iters = 0
    pending = None
    t0 = time.perf_counter()
    deadline = t0 + 2.0
    while time.perf_counter() < deadline or pending is not None:
        nxt = None
        if time.perf_counter() < deadline:
            table, nxt = apply_batch_packed_q(
                table, jax.device_put(host_qs[fed_iters % n_staged], dev),
                now, ways=ways,
            )
            fed_iters += 1
        if pending is not None:
            np.asarray(pending)  # previous step's full response
            fetched += 1
        pending = nxt
    fed_value = fed_batch * fetched / (time.perf_counter() - t0)
    _phase("fed phase done (%d iters)" % fetched)

    print(json.dumps({
        "metric": "rate_limit_decisions_per_sec_per_chip_10M_keys",
        "value": round(value, 1),
        "unit": "decisions/s",
        "vs_baseline": round(value / 12.5e6, 4),
        "device": device,
        "fed_decisions_per_sec": round(fed_value, 1),
        "fed_vs_baseline": round(fed_value / 12.5e6, 4),
        "fed_batch": fed_batch,
        "fed_link_bytes_per_decision": (12 + 9) * 8,
    }), flush=True)


if __name__ == "__main__":
    main()
