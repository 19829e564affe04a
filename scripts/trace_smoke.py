"""Trace smoke: ONE connected trace across a 2-daemon cluster, and a
trace-tagged breach dump — the ISSUE 7 acceptance run.

Three phases against real daemons (in-process cluster, flight recorder
armed):

  0. DISABLED — tracing unconfigured: traffic flows, the span plane
     reports {"enabled": False}, zero spans exist, and flight-recorder
     records carry no trace ids (the hot path's default cost).
  1. ONE TRACE — a client root context rides w3c `traceparent` into
     daemon A, whose zero-copy forward carries it to the owner daemon
     B; the trace must contain: both daemons' `rpc.server` spans, the
     `peer.forward` hop, the owner's `fastpath.merge` and the
     merge's `gub.backend.dispatch` stage span — client -> peer
     forward -> coalescer merge -> device dispatch, one trace id end to
     end.
  2. BREACH DUMP — the owner daemon's SLO target is dropped to an
     unmeetable value; the forced dump's flightrec records carry the
     matching trace id AND the dump embeds the trace's spans
     (`traces` block), so the artifact CONTAINS the slow trace.

On failure every collected span is dumped to trace-smoke-dumps/ for
the CI artifact.  Runs in the CI matrix (JAX_PLATFORMS=cpu); exit 0 =
pass.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

DUMP_DIR = "trace-smoke-dumps"


def fail(msg: str, exporter=None) -> None:
    os.makedirs(DUMP_DIR, exist_ok=True)
    if exporter is not None:
        path = os.path.join(DUMP_DIR, "spans.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(exporter.dicts(), f, indent=1)
        print(f"trace_smoke: spans dumped to {path}")
    print(f"trace_smoke: FAIL — {msg}")
    sys.exit(1)


def main() -> None:
    import grpc.aio

    from gubernator_tpu.core.config import DaemonConfig, DeviceConfig
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.runtime import tracing
    from gubernator_tpu.testing.cluster import Cluster
    from gubernator_tpu.testing.tracing import MemorySpanExporter

    conf = DaemonConfig(
        flightrec=True,
        flightrec_dir=DUMP_DIR,
    )
    cluster = Cluster.start(
        2,
        device=DeviceConfig(num_slots=4096, ways=8, batch_size=128),
        conf_template=conf,
    )
    exporter = MemorySpanExporter()
    try:
        d0, d1 = cluster.daemon_at(0), cluster.daemon_at(1)
        # A key daemon 0 must FORWARD (owned by daemon 1).
        key = next(
            f"fwd{i}" for i in range(256)
            if cluster.owner_daemon_of(f"tsmoke_fwd{i}") is d1
        )
        payload = pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(
                name="tsmoke", unique_key=key, hits=1,
                limit=1000, duration=60_000,
            )
        ]).SerializeToString()

        async def call(metadata=None) -> None:
            ch = grpc.aio.insecure_channel(d0.grpc_address)
            try:
                rpc = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
                raw = await rpc(payload, metadata=metadata)
                resp = pb.GetRateLimitsResp.FromString(raw)
                if resp.responses[0].error:
                    raise RuntimeError(resp.responses[0].error)
            finally:
                await ch.close()

        # -- phase 0: disabled ------------------------------------------
        if tracing.enabled():
            fail("tracing unexpectedly enabled at start")
        for _ in range(5):
            cluster.run(call())
        if tracing.debug_vars() != {"enabled": False}:
            fail(f"disabled debug_vars: {tracing.debug_vars()}")
        for d in (d0, d1):
            tagged = [
                r for r in d.flightrec.snapshot()["ring"]
                if "trace_id" in r
            ]
            if tagged:
                fail(f"disabled run produced trace-tagged records: {tagged}")
        print("trace_smoke: phase 0 OK — 0 spans while disabled")

        # -- phase 1: one connected trace -------------------------------
        status = tracing.init_tracing(exporter=exporter)
        if not status.enabled:
            fail(f"init_tracing refused: {status.reason}")
        client_ctx = tracing.SpanContext(
            tracing._new_trace_id(), tracing._new_span_id(), True
        )
        cluster.run(call(
            metadata=(("traceparent", client_ctx.traceparent()),)
        ))
        tid = client_ctx.trace_id_hex()
        spans = exporter.spans_for_trace(tid)
        names = sorted({s.name for s in spans})
        methods = {
            s.attributes.get("rpc.method")
            for s in spans if s.name == "rpc.server"
        }
        if "/pb.gubernator.V1/GetRateLimits" not in methods:
            fail(f"daemon A server span missing (got {names})", exporter)
        if "/pb.gubernator.PeersV1/GetPeerRateLimits" not in methods:
            fail(f"peer server span missing (got {names})", exporter)
        if not any(s.name == "peer.forward" for s in spans):
            fail(f"peer.forward span missing (got {names})", exporter)
        if not any(s.name == "fastpath.merge" for s in spans):
            fail(f"fastpath.merge span missing (got {names})", exporter)
        if not any(s.name == "gub.backend.dispatch" for s in spans):
            fail(
                f"gub.backend.dispatch span missing (got {names})",
                exporter,
            )
        print(
            "trace_smoke: phase 1 OK — one trace "
            f"({len(spans)} spans: {names})"
        )

        # -- phase 2: trace-tagged breach dump --------------------------
        fr = d1.flightrec
        fr.slo_p99_ms = 1e-6  # unmeetable: the next window breaches
        fr.min_samples = 1
        reason = fr.evaluate()
        if reason != "slo_breach":
            fail(f"expected slo_breach, got {reason!r}", exporter)
        path = cluster.run(fr.dump(reason))
        with open(path, encoding="utf-8") as f:
            dump = json.load(f)
        ring_tids = {
            r.get("trace_id") for r in dump["ring"] if "trace_id" in r
        }
        if tid not in ring_tids:
            fail(
                f"breach dump ring records missing trace {tid} "
                f"(have {ring_tids})", exporter,
            )
        dump_traces = {s["trace_id"] for s in dump.get("traces", [])}
        if tid not in dump_traces:
            fail(
                f"breach dump embeds no spans of trace {tid}", exporter
            )
        dumped_names = {
            s["name"] for s in dump["traces"] if s["trace_id"] == tid
        }
        print(
            "trace_smoke: phase 2 OK — breach dump at "
            f"{os.path.basename(path)} carries trace {tid[:8]}… "
            f"({sorted(dumped_names)})"
        )
    finally:
        from gubernator_tpu.runtime.tracing import shutdown_tracing

        shutdown_tracing()
        cluster.stop()
    print("trace_smoke: PASS")


if __name__ == "__main__":
    main()
