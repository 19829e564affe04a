"""Gubscope — the end-to-end attribution plane (runtime/tracing.py).

What is pinned here, per ISSUE 7:

  * span-TREE shape at pipeline depth 1 and 2 via the in-memory
    exporter (no collector needed): request -> coalescer merge (member
    contexts as span links) -> dispatch/fetch stages;
  * w3c traceparent propagation client -> daemon -> peer through the
    in-process cluster (one trace id across two real daemons);
  * exemplar emission on a forced SLO breach, and breach dumps that
    CONTAIN the trace of the offending merge (flightrec linkage);
  * honest `init_tracing` status when the OTLP exporter packages are
    missing (the old bool return hid silently-dropped spans);
  * the disabled path: zero spans, zero contexts, no-op helpers — the
    hot path's default cost.
"""
from __future__ import annotations

import asyncio
import json
from concurrent.futures import ThreadPoolExecutor

import pytest

from gubernator_tpu.core.config import Config, DeviceConfig
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.runtime import tracing
from gubernator_tpu.runtime.fastpath import FastPath, _Coalescer
from gubernator_tpu.runtime.flightrec import FlightRecorder
from gubernator_tpu.runtime.metrics import Metrics
from gubernator_tpu.runtime.service import Service
from gubernator_tpu.runtime.tracing import parse_traceparent
from gubernator_tpu.testing.tracing import memory_tracing

DEV = DeviceConfig(num_slots=2048, ways=8, batch_size=64)


def _payload(n: int = 5, tag: str = "t") -> bytes:
    reqs = [
        pb.RateLimitReq(
            name="trace", unique_key=f"{tag}{i}", hits=1,
            limit=100, duration=60_000,
        )
        for i in range(n)
    ]
    return pb.GetRateLimitsReq(requests=reqs).SerializeToString()


# -- w3c wire format ------------------------------------------------------

def test_traceparent_roundtrip():
    ctx = tracing.SpanContext(0xABC123, 0xDEF456, True)
    parsed = parse_traceparent(ctx.traceparent())
    assert parsed is not None
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    assert parsed.sampled
    unsampled = tracing.SpanContext(7, 9, False)
    assert not parse_traceparent(unsampled.traceparent()).sampled


@pytest.mark.parametrize("bad", [
    "", "garbage", "00-abc-def-01",                       # wrong shapes
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",            # zero trace id
    "00-" + "1" * 32 + "-" + "0" * 16 + "-01",            # zero span id
    "ff-" + "1" * 32 + "-" + "1" * 16 + "-01",            # version ff
    "zz-" + "1" * 32 + "-" + "1" * 16 + "-01",            # non-hex
])
def test_traceparent_malformed(bad):
    assert parse_traceparent(bad) is None


# -- lifecycle / sampler / exporter status --------------------------------

def test_disabled_by_default():
    assert not tracing.enabled()
    assert tracing.current_context() is None
    assert tracing.grpc_metadata() is None
    assert tracing.debug_vars() == {"enabled": False}
    with tracing.span("nope") as sp:
        assert sp is None
        assert tracing.current_context() is None
    status = tracing.init_tracing()  # no OTEL_* env, no exporter
    assert not status
    assert "disabled" in status.reason
    assert not tracing.enabled()


def test_sampler_off_disables_entirely():
    for name in ("off", "always_off"):
        status = tracing.init_tracing(sampler=name)
        assert not status
        assert not tracing.enabled()


def test_ratio_zero_propagates_unsampled_context():
    """ratio 0: no Span objects, but an (unsampled) context still
    propagates so the decision stays consistent downstream."""
    with memory_tracing(sampler="traceidratio", sampler_arg=0.0) as exp:
        with tracing.span("root") as sp:
            assert sp is None
            ctx = tracing.current_context()
            assert ctx is not None and not ctx.sampled
            # Children inherit the unsampled decision (parent-based).
            with tracing.span("child") as ch:
                assert ch is None
        assert len(exp) == 0
        assert tracing.debug_vars()["spans"]["started"] == 0


def test_init_tracing_reports_missing_otlp_exporter(monkeypatch):
    """The satellite fix: OTEL_EXPORTER_OTLP_ENDPOINT set with the
    exporter packages missing must report the REAL exporter status
    instead of a bare True with silently-vanishing spans."""
    pytest.importorskip("prometheus_client")  # always there; keeps idiom
    try:
        import opentelemetry.sdk  # noqa: F401
        pytest.skip("OTel SDK installed; the missing-exporter path is moot")
    except ImportError:
        pass
    monkeypatch.setenv(
        "OTEL_EXPORTER_OTLP_ENDPOINT", "http://127.0.0.1:4318"
    )
    status = tracing.init_tracing()
    try:
        assert status.enabled  # tracing IS armed (local spans)
        assert status.exporter_error is not None
        assert "unavailable" in status.exporter_error
        dv = tracing.debug_vars()
        assert dv["exporter"]["error"] == status.exporter_error
    finally:
        tracing.shutdown_tracing()
    assert not tracing.enabled()


# -- span-tree shape per pipeline depth ------------------------------------

async def _serve_once(depth: int):
    metrics = Metrics()
    fr = FlightRecorder(metrics=metrics, dump_dir="flightrec-dumps")
    metrics.flightrec = fr
    svc = Service(Config(device=DEV), metrics=metrics)
    await svc.start()
    fp = FastPath(svc, pipeline_depth=depth)
    try:
        with tracing.span("client.request") as root:
            raw = await fp.check_raw(_payload(), peer_rpc=False)
            assert raw is not None, "fast lane fell back"
    finally:
        await fp.close()
        await svc.close()
    return root, fr


@pytest.mark.parametrize("depth", [1, 2])
def test_span_tree_per_pipeline_depth(depth):
    with memory_tracing() as exp:
        root, fr = asyncio.run(_serve_once(depth))
        tid = root.context.trace_id_hex()
        spans = exp.spans_for_trace(tid)
        by_name = {s.name: s for s in spans}
        # The merge is a child of the request with the request context
        # among parent/links; stages are children of the merge.
        merge = by_name["fastpath.merge"]
        assert merge.parent_id == root.context.span_id
        assert merge.attributes["lane"] == "mach"
        assert merge.attributes["entries"] == 1
        # The ledger's stages are the stage spans (runtime/tracing.py):
        # the two pipeline stages under the merge, and what runs on the
        # pool thread under its stage.
        dispatch = by_name["gub.lane.dispatch_stage"]
        fetch = by_name["gub.lane.fetch_stage"]
        assert dispatch.parent_id == merge.context.span_id
        assert fetch.parent_id == merge.context.span_id
        assert dispatch.attributes["lane"] == "mach"
        assert by_name["gub.lane.pack"].parent_id == dispatch.context.span_id
        assert by_name["gub.lane.unpack"].parent_id == fetch.context.span_id
        # The per-entry waits hang off the request itself.
        for wait in ("gub.lane.queue_wait", "gub.lane.in_drain",
                     "gub.wire.wake"):
            assert by_name[wait].parent_id == root.context.span_id
        # The fetch stage's flight-recorder record is trace-tagged
        # (context bound on the pool thread).
        recs = [
            r for r in fr.snapshot()["ring"]
            if r.get("trace_id") == tid
        ]
        assert recs, "no flightrec record carried the trace id"


def test_merge_links_member_contexts():
    """A coalesced merge of two concurrent requests: one member's
    context is the merge's parent, the other attaches as a span link —
    both traces can find the shared device round."""

    class _TE:
        __slots__ = ("fut", "trace_ctx")

        def __init__(self):
            self.fut = None
            self.trace_ctx = None

    async def scenario():
        pool = ThreadPoolExecutor(2)
        co = _Coalescer(pool, lambda entries: [0 for _ in entries],
                        lane="mach")
        roots = []

        async def one(i):
            with tracing.span(f"req{i}") as sp:
                roots.append(sp)
                await co.do(_TE())

        # Both entries enqueue before the drain task first runs (the
        # unbounded queue put never yields), so ONE merge drains both.
        await asyncio.gather(one(0), one(1))
        await co.close()
        pool.shutdown(wait=True)
        return roots

    with memory_tracing() as exp:
        roots = asyncio.run(scenario())
        merges = exp.by_name("fastpath.merge")
        assert len(merges) == 1, [s.to_dict() for s in exp.spans()]
        merge = merges[0]
        assert merge.attributes["entries"] == 2
        got = {merge.parent_id} | {l.span_id for l in merge.links}
        want = {r.context.span_id for r in roots}
        assert want <= got


def test_foreign_entries_without_slot_are_tolerated():
    """Entry types without a trace_ctx slot (older tests, ad-hoc lanes)
    must pass through the armed coalescer untraced, not crash."""

    class _Bare:
        __slots__ = ("fut",)

        def __init__(self):
            self.fut = None

    async def scenario():
        pool = ThreadPoolExecutor(1)
        co = _Coalescer(pool, lambda entries: [1 for _ in entries])
        with tracing.span("req"):
            out = await co.do(_Bare())
        await co.close()
        pool.shutdown(wait=True)
        return out

    with memory_tracing():
        assert asyncio.run(scenario()) == 1


# -- flightrec / exemplar linkage -----------------------------------------

def test_openmetrics_exemplar_rendering():
    m = Metrics()
    tid = "ab" * 16
    m.grpc_request_duration.labels(method="/v1/GetRateLimits").observe(
        0.001, {"trace_id": tid}
    )
    text = m.render_openmetrics().decode()
    assert f'trace_id="{tid}"' in text
    # The classic exposition still parses (exemplars simply omitted).
    assert b"gubernator_grpc_request_duration" in m.render()


def test_breach_dump_carries_offending_trace(tmp_path):
    """A forced SLO breach: the dump's exemplars name the slow trace,
    its ring records carry the trace id, and the dump CONTAINS the
    trace's spans (the flightrec <-> span-plane join)."""
    with memory_tracing():
        fr = FlightRecorder(
            slo_p99_ms=0.001, min_samples=1, dump_dir=str(tmp_path)
        )
        with tracing.span("slow.request") as sp:
            tid = sp.context.trace_id_hex()
            fr.record_batch(8, 123.0, kind="fastlane_drain")
        fr.observe_request(0.5, trace_id=tid)
        reason = fr.evaluate()
        assert reason == "slo_breach"

        path = asyncio.run(fr.dump(reason))
        data = json.loads(open(path, encoding="utf-8").read())
        assert data["slow_exemplars"][0]["trace_id"] == tid
        assert any(r.get("trace_id") == tid for r in data["ring"])
        assert any(s["trace_id"] == tid for s in data["traces"])
        assert data["traces"][0]["name"] == "slow.request"


def test_flightrec_records_untagged_when_disabled(tmp_path):
    fr = FlightRecorder(dump_dir=str(tmp_path))
    fr.record_batch(4, 1.0)
    (rec,) = fr.snapshot()["ring"]
    assert "trace_id" not in rec


# -- the disabled hot path ------------------------------------------------

def test_disabled_serving_creates_zero_spans():
    """The hard guarantee: with tracing disarmed, a full fast-lane serve
    allocates no spans and leaves no trace state behind."""
    assert not tracing.enabled()
    root, fr = asyncio.run(_serve_once(2))
    assert root is None  # span() yielded None
    assert tracing.debug_vars() == {"enabled": False}
    assert all(
        "trace_id" not in r for r in fr.snapshot()["ring"]
    )
    # Arm an exporter AFTER the fact: nothing buffered leaks into it.
    with memory_tracing() as exp:
        assert len(exp) == 0


def test_stage_allocates_no_span_when_disabled():
    """The pin, for the ledger's primitive: disarmed, a stage binds no
    context and creates no Span — it only counts."""
    ledger = tracing.StageLedger()
    with ledger.stage("backend.dispatch", "mach") as st:
        assert tracing.current_context() is None
        assert st.context is None
    wait = ledger.begin("lane.handoff", "mach")
    assert wait.context is None
    wait.end()
    assert ledger.totals("mach", "backend.dispatch")[0] == 1
    assert tracing.debug_vars() == {"enabled": False}


# -- cross-daemon propagation (in-process cluster) ------------------------

def test_traceparent_propagation_across_cluster():
    """client -> daemon A -> (peer forward) -> daemon B: one trace id.
    Both daemons live in one process, so one memory exporter observes
    the whole cluster's spans."""
    import grpc.aio

    from gubernator_tpu.testing.cluster import Cluster

    with memory_tracing() as exp:
        cluster = Cluster.start(2)
        try:
            d0 = cluster.daemon_at(0)
            # A key owned by daemon 1, sent to daemon 0 => forward.
            key = next(
                f"fwd{i}" for i in range(64)
                if cluster.owner_daemon_of(f"trace_fwd{i}")
                is cluster.daemon_at(1)
            )
            payload = pb.GetRateLimitsReq(requests=[
                pb.RateLimitReq(
                    name="trace", unique_key=key, hits=1,
                    limit=100, duration=60_000,
                )
            ]).SerializeToString()
            client_ctx = tracing.SpanContext(
                tracing._new_trace_id(), tracing._new_span_id(), True
            )

            async def call():
                ch = grpc.aio.insecure_channel(d0.grpc_address)
                try:
                    rpc = ch.unary_unary(
                        "/pb.gubernator.V1/GetRateLimits"
                    )
                    raw = await rpc(
                        payload,
                        metadata=(
                            ("traceparent", client_ctx.traceparent()),
                        ),
                    )
                    resp = pb.GetRateLimitsResp.FromString(raw)
                    assert not resp.responses[0].error, resp
                finally:
                    await ch.close()

            cluster.run(call())
        finally:
            cluster.stop()

        tid = client_ctx.trace_id_hex()
        spans = exp.spans_for_trace(tid)
        names = [s.name for s in spans]
        servers = [s for s in spans if s.name == "rpc.server"]
        methods = {s.attributes["rpc.method"] for s in servers}
        # Daemon A's client RPC and daemon B's peer RPC in ONE trace.
        assert "/pb.gubernator.V1/GetRateLimits" in methods, names
        assert "/pb.gubernator.PeersV1/GetPeerRateLimits" in methods, names
        forwards = [s for s in spans if s.name == "peer.forward"]
        assert forwards, names
        assert forwards[0].attributes["peer"] == (
            cluster.daemon_at(1).grpc_address
        )
        # The owner daemon's coalescer merge is attributed too.
        assert "fastpath.merge" in names
        # The client root is the outermost parent of daemon A's span.
        a_server = next(
            s for s in servers
            if s.attributes["rpc.method"].endswith("V1/GetRateLimits")
        )
        assert a_server.parent_id == client_ctx.span_id
