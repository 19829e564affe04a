"""Service-path benchmark: the FULL daemon pipeline, not the bare kernel.

Where bench.py measures the device hot loop alone, this drives real gRPC
traffic through an in-process daemon — wire parse, validation, packing,
device step, response serialization — and reports throughput plus request
latency percentiles for the BASELINE.json configs:

  1. token_1k      TOKEN_BUCKET, 1k keys, batched client traffic
  2. leaky_1m_zipf LEAKY_BUCKET, 1M keys, Zipfian hits
  3. global_4peer  Behavior=GLOBAL on a 4-daemon cluster (non-owner serving)
  4. latency       small batches, p50/p99 GetRateLimits (north-star: <2ms)
  5. cms_sketch    count-min-sketch approximate tier, 100M-key space

Clients send PRE-SERIALIZED payloads over raw-bytes gRPC stubs so the
measurement is the server pipeline + wire, not python-protobuf client cost
(the reference benchmarks use compiled Go clients, benchmark_test.go:29-148).

Prints one JSON line per config:
  {"config", "checks_per_sec", "p50_ms", "p99_ms", "rpcs", "checks"}
and a final "budget" line breaking the host pipeline into stages.

Runs on whatever JAX platform is active (the summary line names it;
JAX_PLATFORMS=cpu for a laptop run).  ~2-3 min including XLA compiles.
A config that fails prints its error line and the run exits non-zero.
One process holds the device throughout: nothing here starts a child.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time
from typing import List, Tuple

import numpy as np


def _percentiles(lat_s: List[float]) -> Tuple[float, float]:
    a = np.asarray(lat_s) * 1000.0
    return float(np.percentile(a, 50)), float(np.percentile(a, 99))


async def drive(
    addresses: List[str],
    payloads: List[bytes],
    seconds: float,
    concurrency: int,
    method: str = "/pb.gubernator.V1/GetRateLimits",
) -> Tuple[int, List[float]]:
    """Fire pre-serialized payloads at the daemon(s) with `concurrency`
    in-flight RPCs; returns (rpc_count, per-rpc latencies)."""
    import grpc.aio

    channels = [grpc.aio.insecure_channel(a) for a in addresses]
    stubs = [ch.unary_unary(method) for ch in channels]
    lat: List[float] = []
    count = 0

    async def worker(wid: int) -> None:
        nonlocal count
        stub = stubs[wid % len(stubs)]
        i = wid
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            p = payloads[i % len(payloads)]
            t0 = time.perf_counter()
            await stub(p)
            lat.append(time.perf_counter() - t0)
            count += 1
            i += concurrency

    await asyncio.gather(*[worker(w) for w in range(concurrency)])
    for ch in channels:
        await ch.close()
    return count, lat


def _rt_mark(d) -> dict:
    """Snapshot one daemon's device round-trip counters."""
    svc = d.service
    eng = d.fastpath._engine_lane
    return {
        "fastlane_drains": d.fastpath._mach.drains,
        "engine_drains": eng.drains if eng is not None else 0,
        "batcher_steps": svc._local_batcher.steps,
        "reread_batches": svc.global_mgr.reread_batches,
        "reread_keys": svc.global_mgr.reread_keys,
        "hit_flush_rpcs": svc.global_mgr.async_sends,
        "broadcast_rpcs": svc.global_mgr.broadcasts,
    }


def build_payload(names_keys, hits=1, limit=1_000_000_000, duration=3_600_000,
                  algorithm=0, behavior=0, burst=0) -> bytes:
    from gubernator_tpu.proto import gubernator_pb2 as pb

    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(
            name=n, unique_key=k, hits=hits, limit=limit, duration=duration,
            algorithm=algorithm, behavior=behavior, burst=burst,
        )
        for n, k in names_keys
    ]).SerializeToString()


def bench(seconds: float, concurrency: int,
          depth_sweep: Tuple[int, ...] = (1, 2, 4),
          workload: str = "",
          client_modes: Tuple[str, ...] = ("python", "native", "leased"),
          ) -> None:
    """Sync driver: client coroutines run on each cluster's OWN loop —
    grpc.aio multiplexes one poller per process, and a second event loop
    polling it (server on the cluster loop, clients on another) thrashes
    into BlockingIOError storms and 30x latency."""
    from gubernator_tpu.core.config import (
        DaemonConfig, DeviceConfig, SketchTierConfig,
    )
    from gubernator_tpu.testing.cluster import Cluster

    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu":
        # XLA:CPU copies the donated table per step, so step time scales
        # with table size — keep the CPU smoke config small.  On TPU the
        # step is an in-place HBM scatter and the big table is free.
        dev_cfg = DeviceConfig(num_slots=1 << 18, ways=8, batch_size=4096)
    else:
        dev_cfg = DeviceConfig(num_slots=1 << 22, ways=8, batch_size=4096)
    # Honor the daemon's drain-policy env knobs so A/B artifacts (shipped
    # sparse=64 vs sparse=0, pipeline depth 2 vs 1) run the exact same
    # harness (the real daemon reads them in setup_daemon_config; Cluster
    # builds DaemonConfig directly, so mirror the knobs the A/Bs vary
    # through the same parse/validate).  Cluster.start_with's `device=`
    # argument is the single source of the device config — the template
    # leaves it alone.
    from gubernator_tpu.core.config import (
        fastpath_sparse_from_env,
        pipeline_depth_from_env,
    )

    sparse = fastpath_sparse_from_env()
    depth = pipeline_depth_from_env()

    def conf(**kw) -> DaemonConfig:
        kw.setdefault("pipeline_depth", depth)
        return DaemonConfig(fastpath_sparse=sparse, **kw)

    rng = np.random.default_rng(7)
    results = []
    failed = []

    def fail(line: dict) -> None:
        """A config failed: its error line prints like any result (the
        other configs still run) and the run exits non-zero."""
        failed.append(line)
        print(json.dumps(line), flush=True)

    def emit(config, checks, rpcs, lat, wall, extra=None):
        p50, p99 = _percentiles(lat)
        line = {
            "config": config,
            "checks_per_sec": round(checks / wall, 1),
            "p50_ms": round(p50, 3),
            "p99_ms": round(p99, 3),
            "rpcs": rpcs,
            "checks": checks,
            "concurrency": concurrency,
        }
        if extra:
            line.update(extra)
        results.append(line)
        print(json.dumps(line), flush=True)

    # ---- configs 1/2/4: single-node daemon (compiled fast lane) -------
    c = Cluster.start_with([""], device=dev_cfg, conf_template=conf())
    try:
        addr = [c.daemons[0].grpc_address]

        # Config 1: token bucket, 1k keys, batch 1000.
        pays = [
            build_payload([("bench_token", f"k{i}") for i in range(1000)])
            for _ in range(1)
        ]
        c.run(drive(addr, pays, 1.0, concurrency), timeout=120)  # warm
        t0 = time.perf_counter()
        rpcs, lat = c.run(
            drive(addr, pays, seconds, concurrency), timeout=120
        )
        emit("token_1k_batch1000", rpcs * 1000, rpcs, lat,
             time.perf_counter() - t0)

        # Config 2: leaky bucket, 1M keys, Zipfian batches.
        n_keys = 1_000_000
        zipf_pays = []
        for _ in range(32):
            ks = rng.zipf(1.3, size=1000) % n_keys
            zipf_pays.append(build_payload(
                [("bench_leaky", f"z{k}") for k in ks],
                algorithm=1, limit=1_000_000, duration=60_000,
            ))
        c.run(drive(addr, zipf_pays, 1.0, concurrency), timeout=120)
        t0 = time.perf_counter()
        rpcs, lat = c.run(
            drive(addr, zipf_pays, seconds, concurrency), timeout=120
        )
        emit("leaky_1m_zipfian", rpcs * 1000, rpcs, lat,
             time.perf_counter() - t0)

        # Config 4: latency, small batches (10 checks), low concurrency.
        small = [
            build_payload([("bench_lat", f"l{j}") for j in range(10)])
            for _ in range(1)
        ]
        c.run(drive(addr, small, 0.5, 1), timeout=120)
        t0 = time.perf_counter()
        rpcs, lat = c.run(drive(addr, small, seconds, 4), timeout=120)
        emit("latency_small_batch", rpcs * 10, rpcs, lat,
             time.perf_counter() - t0, {"concurrency": 4})

        # Host/device budget on the fast lane (per 1000-request batch).
        fp = c.daemons[0].fastpath
        from gubernator_tpu import native

        budget = {"config": "budget_us_per_1000"}
        if native.available():
            pay = pays[0]
            t0 = time.perf_counter()
            for _ in range(100):
                cols = native.parse_reqs(pay)
            budget["parse"] = round((time.perf_counter() - t0) / 100 * 1e6)
            t0 = time.perf_counter()
            for _ in range(100):
                rnd, lane, nr = native.assign_rounds(
                    cols.hash, None, 1, dev_cfg.batch_size
                )
            budget["assign_rounds"] = round(
                (time.perf_counter() - t0) / 100 * 1e6
            )
            z = np.zeros(cols.n, dtype=np.int64)
            off = np.zeros(cols.n + 1, dtype=np.int64)
            t0 = time.perf_counter()
            for _ in range(100):
                native.serialize_resps(z, z, z, z, b"", off)
            budget["serialize"] = round(
                (time.perf_counter() - t0) / 100 * 1e6
            )
            budget["fastpath_served"] = fp.served
            budget["fastpath_fallbacks"] = fp.fallbacks
        # Pipelined-drain stage split (docs/pipeline.md): cumulative
        # dispatch vs fetch wall time over every machinery merge this
        # daemon ran, normalized per 1000 served requests — the term the
        # depth knob attacks is `fetch`, and `bubble` is the dispatch
        # idle time a deeper pipeline would absorb.
        mach = fp._mach
        if fp.served:
            per_k = fp.served / 1000.0
            budget["pipeline_depth"] = fp.pipeline_depth
            budget["dispatch_us_per_1000"] = round(
                mach.dispatch_s * 1e6 / per_k
            )
            budget["fetch_us_per_1000"] = round(mach.fetch_s * 1e6 / per_k)
            budget["bubble_us_per_1000"] = round(
                mach.bubble_s * 1e6 / per_k
            )
            budget["drains"] = {
                "total": mach.drains,
                "overlap": mach.overlap_drains,
                "waited": mach.waited_drains,
                "max_inflight_seen": mach.max_inflight_seen,
            }
            # Blocking device->host fetches performed ON the request
            # path, per check.
            bf = sum(fp.blocking_fetches.values())
            budget["blocking_fetches"] = dict(fp.blocking_fetches)
            budget["blocking_fetches_per_check"] = round(
                bf / fp.served, 6
            )
        results.append(budget)
        print(json.dumps(budget), flush=True)

        # End-of-run gubstat census (docs/observability.md): table
        # occupancy and the top-K tenant ledger from the single-node
        # daemon that served configs 1/2/4, so capacity trends ride the
        # BENCH_E2E artifact trajectory next to the throughput numbers.
        try:
            d0 = c.daemons[0]
            census = {"config": "table_census"}
            if d0.stats_sampler is not None:
                blk = c.run(d0.stats_sampler.sample(), timeout=120)
                census.update({
                    "occupancy": blk["occupancy"],
                    "live": blk["live"],
                    "expired_resident": blk["expired_resident"],
                    "per_shard_occupancy": blk["per_shard_occupancy"],
                    "bucket_fill": blk["bucket_fill"],
                    "shadow_slots": blk["shadow_slots"],
                })
            if d0.service.tenants is not None:
                census["tenants_top"] = d0.service.tenants.top(8)
            results.append(census)
            print(json.dumps(census), flush=True)
        except Exception as e:  # noqa: BLE001 — isolate config failures
            fail({"config": "table_census", "error": str(e)})
    finally:
        c.stop()

    # ---- client-mode sweep: python vs native vs leased -----------------
    # The CLIENT half of the E2E budget (ISSUE 10): the same steady
    # single-key load driven through each SDK tier, measuring what the
    # caller pays per check INCLUDING its own client machinery (the
    # other configs deliberately pre-serialize payloads to exclude it):
    #   python  V1Client — python-protobuf build/parse per call (the
    #           measured ~1.3ms of grpc.aio/protobuf machinery);
    #   native  FastV1Client — the compiled codec (gub_serialize_reqs /
    #           gub_parse_resps2) over a raw-bytes channel;
    #   leased  LeasedClient — client-side admission: checks burn a
    #           granted local allowance with ZERO RPCs (docs/leases.md).
    # The acceptance column is rpcs_per_admitted_check: leased must be
    # >= 10x below python under steady single-key load.
    if client_modes:
        try:
            from gubernator_tpu.client import (
                FastV1Client,
                LeasedClient,
                V1Client,
            )
            from gubernator_tpu.core.config import LeaseConfig
            from gubernator_tpu.core.types import RateLimitReq, Status

            c = Cluster.start_with(
                [""], device=dev_cfg, conf_template=conf()
            )
            try:
                addr = c.daemons[0].grpc_address
                sweep_seconds = max(2.0, seconds / 2)
                lease_cfg = LeaseConfig(
                    fraction=0.25, ttl_ms=60_000, max_holders=4,
                    reconcile_ms=500, low_water=0.25,
                )
                req = RateLimitReq(
                    name="bench_client", unique_key="steady", hits=1,
                    limit=1_000_000_000, duration=3_600_000,
                )
                mode_budget = {"config": "client_mode_budget"}
                for mode in client_modes:
                    if mode == "python":
                        cl = V1Client(addr)
                    elif mode == "native":
                        cl = FastV1Client(addr)
                    elif mode == "leased":
                        cl = LeasedClient(addr, lease=lease_cfg)
                    else:
                        raise ValueError(
                            f"unknown client mode {mode!r}; expected "
                            "python, native, leased"
                        )
                    try:
                        for _ in range(50):  # warm (+ lease grant)
                            cl.get_rate_limits([req])
                        warm_rpcs = (
                            cl.stats()["rpcs"] if mode == "leased"
                            else 50
                        )
                        lat = []
                        admitted = calls = 0
                        t0 = time.perf_counter()
                        t_end = t0 + sweep_seconds
                        while time.perf_counter() < t_end:
                            s0 = time.perf_counter()
                            r = cl.get_rate_limits([req])[0]
                            lat.append(time.perf_counter() - s0)
                            calls += 1
                            if (
                                r.error == ""
                                and r.status == Status.UNDER_LIMIT
                            ):
                                admitted += 1
                        wall = time.perf_counter() - t0
                        if mode == "leased":
                            st = cl.stats()
                            rpcs = st["rpcs"] - warm_rpcs
                            extra_stats = {"client_stats": st}
                        else:
                            rpcs = calls
                            extra_stats = {}
                        rpac = round(rpcs / max(admitted, 1), 6)
                        mode_budget[
                            f"rpcs_per_admitted_check_{mode}"
                        ] = rpac
                        emit(
                            f"client_sweep_{mode}", calls, rpcs, lat,
                            wall, {
                                "client_mode": mode,
                                "concurrency": 1,
                                "admitted": admitted,
                                "rpcs_per_admitted_check": rpac,
                                **(
                                    {"codec": cl.codec}
                                    if mode == "native" else {}
                                ),
                                **extra_stats,
                            },
                        )
                    finally:
                        cl.close()
                results.append(mode_budget)
                print(json.dumps(mode_budget), flush=True)
            finally:
                c.stop()
        except Exception as e:  # noqa: BLE001 — isolate sweep failures
            fail({
                "config": "client_sweep", "error": str(e),
            })

    # ---- pipeline-depth sweep: the tentpole A/B ------------------------
    # Re-run the two throughput configs (token_1k dense batches,
    # leaky_1m Zipfian) and the small-batch latency config at each
    # requested depth on fresh single-node daemons.  Depth 1 is the
    # strict pre-pipeline discipline; the acceptance bar is depth-2
    # checks_per_sec >= depth-1 where fetch dominates, with small-batch
    # p50 no worse than the sparse-overlap numbers.
    for d in depth_sweep:
        try:
            c = Cluster.start_with(
                [""], device=dev_cfg,
                conf_template=conf(pipeline_depth=d),
            )
            try:
                addr = [c.daemons[0].grpc_address]
                sweep_seconds = max(2.0, seconds / 2)
                pays = [build_payload(
                    [("bench_token", f"k{i}") for i in range(1000)]
                )]
                zipf_pays = []
                for _ in range(32):
                    ks = rng.zipf(1.3, size=1000) % 1_000_000
                    zipf_pays.append(build_payload(
                        [("bench_leaky", f"z{k}") for k in ks],
                        algorithm=1, limit=1_000_000, duration=60_000,
                    ))
                small = [build_payload(
                    [("bench_lat", f"l{j}") for j in range(10)]
                )]
                for name, pl, batch, cc in (
                    ("token_1k_batch1000", pays, 1000, concurrency),
                    ("leaky_1m_zipfian", zipf_pays, 1000, concurrency),
                    ("latency_small_batch", small, 10, 4),
                ):
                    c.run(drive(addr, pl, 0.5, cc), timeout=120)  # warm
                    t0 = time.perf_counter()
                    rpcs, lat = c.run(
                        drive(addr, pl, sweep_seconds, cc), timeout=120
                    )
                    emit(f"pipeline_sweep_{name}", rpcs * batch, rpcs,
                         lat, time.perf_counter() - t0,
                         {"pipeline_depth": d, "concurrency": cc})
                fp = c.daemons[0].fastpath
                mach = fp._mach
                line = {
                    "config": "pipeline_sweep_stages",
                    "pipeline_depth": d,
                    "dispatch_s": round(mach.dispatch_s, 3),
                    "fetch_s": round(mach.fetch_s, 3),
                    "bubble_s": round(mach.bubble_s, 3),
                    "drains": mach.drains,
                    "waited_drains": mach.waited_drains,
                    "max_inflight_seen": mach.max_inflight_seen,
                }
                results.append(line)
                print(json.dumps(line), flush=True)
            finally:
                c.stop()
        except Exception as e:  # noqa: BLE001 — isolate sweep failures
            fail({
                "config": "pipeline_sweep", "pipeline_depth": d,
                "error": str(e),
            })

    # ---- config 2b: token bucket with a Store attached ----------------
    # The persistence SPI rides the fast lane (r4): each drain adds one
    # residency probe + one packed capture gather + per-unique-key
    # on_change delivery.  Must land within ~2x of the storeless token
    # config.
    try:
        from gubernator_tpu.runtime.store import MockStore

        store_conf = conf(store=MockStore())
        c = Cluster.start_with(
            [""], device=dev_cfg, conf_template=store_conf
        )
        try:
            addr = [c.daemons[0].grpc_address]
            pays = [
                build_payload(
                    [("bench_store", f"k{i}") for i in range(1000)]
                )
            ]
            c.run(drive(addr, pays, 1.0, concurrency), timeout=120)
            t0 = time.perf_counter()
            rpcs, lat = c.run(
                drive(addr, pays, seconds, concurrency), timeout=120
            )
            st = store_conf.store
            emit("token_1k_store", rpcs * 1000, rpcs, lat,
                 time.perf_counter() - t0, {
                     "store_gets": st.called["get"],
                     "store_on_changes": st.called["on_change"],
                     "fastpath_served": c.daemons[0].fastpath.served,
                     "fastpath_fallbacks": c.daemons[0].fastpath.fallbacks,
                 })
        finally:
            c.stop()
    except Exception as e:  # noqa: BLE001
        fail({"config": "token_1k_store", "error": str(e)})

    # ---- config 3: GLOBAL on a 4-daemon cluster -----------------------
    try:
        c = Cluster.start_with(
            ["", "", "", ""], device=dev_cfg, conf_template=conf()
        )
        try:
            from gubernator_tpu.core.types import Behavior

            g_pays = [
                build_payload(
                    [("bench_global", f"g{i}") for i in range(1000)],
                    behavior=int(Behavior.GLOBAL),
                )
            ]
            addr = [c.daemons[0].grpc_address]
            c.run(drive(addr, g_pays, 1.0, concurrency), timeout=120)
            marks = [_rt_mark(d) for d in c.daemons]
            t0 = time.perf_counter()
            rpcs, lat = c.run(
                drive(addr, g_pays, seconds, concurrency), timeout=120
            )
            wall = time.perf_counter() - t0
            emit("global_4peer", rpcs * 1000, rpcs, lat, wall)
            # Device round-trip accounting (VERDICT r3 #3): every device
            # dispatch->fetch cycle each daemon ran during the window,
            # by component, and the implied cycles per 1000 checks.
            per_node = [
                {k: after[k] - before[k] for k in after}
                for before, after in zip(
                    marks, [_rt_mark(d) for d in c.daemons]
                )
            ]
            node_cycles = [
                n["fastlane_drains"] + n["engine_drains"]
                + n["batcher_steps"] for n in per_node
            ]
            total_cycles = sum(node_cycles)
            busiest_cycles = max(node_cycles)
            acct = {
                "config": "global_roundtrip_accounting",
                "note": (
                    "per-daemon device dispatch->fetch cycles during the "
                    "global_4peer window.  fastlane_drains serve client "
                    "AND forwarded peer batches (one cycle each).  "
                    "Broadcast rows are CAPTURED from each drain's own "
                    "post-step stored columns (r5), so the zero-hit "
                    "re-read steps of global.go:205-250 run only as a "
                    "fallback (reread_batches — 0 in steady state; a "
                    "capture degrades to the re-read when a later "
                    "occurrence moved the row, on RESET_REMAINING, or "
                    "on a leaky overfill clamp).  Broadcast RECEIVES "
                    "(apply_cached_rows) dispatch without a fetch and "
                    "cost no cycle."
                ),
                "checks": rpcs * 1000,
                "cluster_cycles": total_cycles,
                "cycles_per_1000_checks": round(
                    total_cycles / max(rpcs, 1), 2
                ),
                # Every DeviceBackend takes device 0, so all 4 daemons
                # of this cluster serialize their merges on ONE device
                # queue; a chip-per-daemon deployment pays only each
                # daemon's own cycles.
                "window_s": round(wall, 2),
                "per_node": per_node,
            }
            results.append(acct)
            print(json.dumps(acct), flush=True)
        finally:
            c.stop()
    except Exception as e:  # noqa: BLE001 — isolate config failures
        fail({"config": "global_4peer", "error": str(e)})

    # ---- config 5: CMS sketch tier daemon (sketch-named lanes ride the
    # compiled fast lane via the parser's name_hash column), on the
    # default gather/scatter step (use_pallas=False). -------------------
    try:
        sketch_conf = conf(
            sketch=SketchTierConfig(
                names=["cms"], width=1 << 20, depth=4, window_ms=60_000,
                use_pallas=False,
            ),
        )
        c = Cluster.start_with(
            [""], device=dev_cfg, conf_template=sketch_conf
        )
        try:
            addr = [c.daemons[0].grpc_address]
            cms_pays = []
            for _ in range(32):
                ks = rng.integers(0, 100_000_000, size=1000)
                cms_pays.append(build_payload(
                    [("cms", f"s{k}") for k in ks],
                    limit=1_000_000, duration=60_000,
                ))
            c.run(drive(addr, cms_pays, 1.0, concurrency), timeout=120)
            t0 = time.perf_counter()
            rpcs, lat = c.run(
                drive(addr, cms_pays, seconds, concurrency), timeout=120
            )
            emit("cms_sketch_100m_space", rpcs * 1000, rpcs, lat,
                 time.perf_counter() - t0)
        finally:
            c.stop()
    except Exception as e:  # noqa: BLE001
        fail({
            "config": "cms_sketch_100m_space", "error": str(e)
        })

    # ---- --workload zipf:<s>: owner-skew on a 3-daemon cluster --------
    # Production key popularity is zipfian, which funnels the hottest
    # keys onto single ring owners (ROADMAP item 5 / docs/hotkeys.md).
    # This config measures exactly that skew: seeded zipf draws from
    # one client daemon, reported as the per-owner share of applied
    # checks next to the usual latency percentiles — the baseline the
    # hot-key survival plane's mirroring is judged against.
    if workload:
        try:
            kind, _, arg = workload.partition(":")
            if kind not in ("zipf", "churn"):
                raise ValueError(f"unknown workload {workload!r}; "
                                 "expected zipf:<s> or churn:<keys>")
        except ValueError as e:
            fail({"workload": workload, "error": str(e)})
            kind = ""

    # ---- --workload churn:<keys>: tiered-table churn ------------------
    # A keyspace far larger than the HBM slot budget with zipfian reuse
    # — the Guberberg acceptance workload (docs/tiering.md): watermark
    # demotion runs live while cold-resident keys promote back on
    # access, and the budget columns show what the tier costs (cold-hit
    # rate, promote latency, demotion rate) next to the usual
    # percentiles and the fetch-free pin.
    if workload and kind == "churn":
        try:
            keys = int(arg or "50000")
            from gubernator_tpu.core.config import TierConfig

            churn_dev = DeviceConfig(
                num_slots=4096, ways=8, batch_size=1024
            )
            c = Cluster.start_with(
                [""], device=churn_dev,
                conf_template=conf(tier=TierConfig(
                    enabled=True, cold_capacity=max(keys, 1),
                    high_water=0.60, low_water=0.40,
                    demote_batch=256, interval_s=0.25,
                )),
            )
            try:
                from gubernator_tpu.testing.chaos import zipf_keys

                draws = zipf_keys(11, 1.1, 64 * 1000, keys)
                cpays = [
                    build_payload([
                        ("bench_churn", f"c{k}")
                        for k in draws[j * 1000:(j + 1) * 1000]
                    ], limit=1_000_000, duration=60_000)
                    for j in range(64)
                ]
                addr = [c.daemons[0].grpc_address]
                c.run(drive(addr, cpays, 1.0, concurrency), timeout=120)
                d0 = c.daemons[0]
                tv0 = d0.tier.debug_vars() if d0.tier else {}
                t0 = time.perf_counter()
                rpcs, lat = c.run(
                    drive(addr, cpays, seconds, concurrency),
                    timeout=120,
                )
                wall = time.perf_counter() - t0
                tv = d0.tier.debug_vars() if d0.tier else {}
                checks = rpcs * 1000
                extra = {
                    "keyspace": keys,
                    "hbm_slots": churn_dev.num_slots,
                    "keyspace_over_slots": round(
                        keys / churn_dev.num_slots, 1
                    ),
                }
                if tv:
                    from gubernator_tpu.runtime.metrics import (
                        estimate_quantile,
                    )

                    lat_h = tv["promote_latency"]
                    extra.update({
                        "cold_residents": tv["cold_residents"],
                        "cold_hits": tv["cold_hits"] - tv0.get(
                            "cold_hits", 0
                        ),
                        "cold_hit_rate": round(
                            (tv["cold_hits"] - tv0.get("cold_hits", 0))
                            / max(checks, 1), 6
                        ),
                        "promotes": tv["promotes"] - tv0.get(
                            "promotes", 0
                        ),
                        "demotes": tv["demotes"] - tv0.get(
                            "demotes", 0
                        ),
                        "demotes_per_sec": round(
                            (tv["demotes"] - tv0.get("demotes", 0))
                            / wall, 1
                        ),
                        "capacity_drops": tv["capacity_drops"],
                        "promote_p50_ms": round(estimate_quantile(
                            lat_h["buckets"], lat_h["cumulative"], 0.5
                        ) * 1e3, 3),
                        "promote_p99_ms": round(estimate_quantile(
                            lat_h["buckets"], lat_h["cumulative"], 0.99
                        ) * 1e3, 3),
                    })
                fp = d0.fastpath
                if fp is not None and fp.served:
                    bf = sum(fp.blocking_fetches.values())
                    extra["blocking_fetches_per_check"] = round(
                        bf / fp.served, 6
                    )
                emit(f"churn_tiered_{keys}keys", checks, rpcs, lat,
                     wall, extra)
            finally:
                c.stop()
        except Exception as e:  # noqa: BLE001 — isolate config failures
            fail({
                "config": "churn_tiered", "workload": workload,
                "error": str(e),
            })

    if workload and kind == "zipf":
        try:
            zs = float(arg or "1.2")
            c = Cluster.start_with(
                ["", "", ""], device=dev_cfg, conf_template=conf()
            )
            try:
                from gubernator_tpu.testing.chaos import zipf_keys

                universe = 100_000
                draws = zipf_keys(7, zs, 64 * 1000, universe)
                zpays = [
                    build_payload([
                        ("bench_skew", f"z{k}")
                        for k in draws[j * 1000:(j + 1) * 1000]
                    ], limit=1_000_000_000, duration=60_000)
                    for j in range(64)
                ]
                addr = [c.daemons[0].grpc_address]
                c.run(drive(addr, zpays, 1.0, concurrency), timeout=120)
                before = {
                    d.grpc_address: d.service.backend.checks
                    for d in c.daemons
                }
                t0 = time.perf_counter()
                rpcs, lat = c.run(
                    drive(addr, zpays, seconds, concurrency), timeout=120
                )
                wall = time.perf_counter() - t0
                after = {
                    d.grpc_address: d.service.backend.checks
                    for d in c.daemons
                }
                delta = {a: after[a] - before[a] for a in after}
                total = max(sum(delta.values()), 1)
                share = {
                    a: round(v / total, 4) for a, v in delta.items()
                }
                # zipf rank 1 maps to index 0 (zipf_keys subtracts 1).
                hot_owner = c.owner_daemon_of("bench_skew_z0")
                emit(f"zipf_owner_skew_s{zs:g}", rpcs * 1000, rpcs, lat,
                     wall, {
                         "zipf_s": zs,
                         "universe": universe,
                         "per_owner_applied_share": share,
                         "max_owner_share": max(share.values()),
                         "hottest_key_owner": hot_owner.grpc_address,
                     })
            finally:
                c.stop()
        except Exception as e:  # noqa: BLE001 — isolate config failures
            fail({
                "config": "zipf_owner_skew", "workload": workload,
                "error": str(e),
            })

    summary = {
        "config": "summary",
        "platform": platform,
        "workload": workload,
        "fastpath_sparse": sparse,
        "pipeline_depth": depth,
        "pipeline_depth_sweep": list(depth_sweep),
        "client_mode_sweep": list(client_modes),
        "device": {
            "num_slots": dev_cfg.num_slots,
            "batch_size": dev_cfg.batch_size,
        },
        "configs": {r["config"]: r.get("checks_per_sec") for r in results
                    if "checks_per_sec" in r and r["checks_per_sec"]},
    }
    print(json.dumps(summary), flush=True)
    if failed:
        raise SystemExit(
            "bench_e2e: %d config(s) failed: %s"
            % (len(failed), [f.get("config") or f.get("workload")
                             for f in failed])
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument(
        "--pipeline-depth", default="1,2,4",
        help="comma-separated GUBER_PIPELINE_DEPTH sweep re-running the "
        "throughput + small-batch configs per depth (empty disables)",
    )
    ap.add_argument(
        "--client-mode", default="python,native,leased",
        help="comma-separated client-SDK sweep over a steady single-key "
        "load, measuring each tier's own machinery (V1Client python "
        "protobuf vs FastV1Client compiled codec vs LeasedClient "
        "zero-RPC local burns) with an rpcs_per_admitted_check column "
        "(docs/leases.md; empty disables)",
    )
    ap.add_argument(
        "--workload", default="",
        help="extra skewed-workload config: zipf:<s> drives seeded "
        "zipfian key draws at a 3-daemon cluster and reports the "
        "per-owner share of applied checks alongside p50/p99 "
        "(docs/hotkeys.md); churn:<keys> drives a keyspace far larger "
        "than the HBM slot budget at a tier-enabled daemon and "
        "reports cold-hit rate, promote latency, and demotion rate "
        "(docs/tiering.md); empty disables",
    )
    args = ap.parse_args()
    sweep = tuple(
        int(d) for d in args.pipeline_depth.split(",") if d.strip()
    )
    cmodes = tuple(
        m.strip() for m in args.client_mode.split(",") if m.strip()
    )
    bench(args.seconds, args.concurrency, depth_sweep=sweep,
          workload=args.workload, client_modes=cmodes)


if __name__ == "__main__":
    main()
