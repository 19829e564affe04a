"""Daemon assembly: gRPC + HTTP servers, discovery, metrics, lifecycle.

The analog of the reference daemon (daemon.go:45-442): builds the metrics
registry, the gRPC server hosting both V1 and PeersV1, the JSON/REST
gateway with under_score marshaling (daemon.go:231-249), the `/metrics`
endpoint, the discovery pool, and readiness gating — all on one asyncio
loop, so many daemons can share a process (the in-process cluster fixture
depends on this, cluster/cluster.go:111-146).
"""
from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import List, Optional, Sequence

import grpc
import grpc.aio
from aiohttp import web
from google.protobuf import json_format

from gubernator_tpu.core.config import Config, DaemonConfig
from gubernator_tpu.core.types import PeerInfo
from gubernator_tpu.net import grpc_api
from gubernator_tpu.net.netutil import resolve_host_ip
from gubernator_tpu.net.forward_once import ForwardOnce
from gubernator_tpu.net.peer_client import (
    FORWARD_ID_KEY,
    FORWARD_ONCE_KEY,
    PRESSURE_METADATA_KEY,
)
from gubernator_tpu.net.tls import TLSBundle, setup_tls
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.proto import peers_pb2
from gubernator_tpu.runtime import tracing
from gubernator_tpu.runtime.metrics import Metrics
from gubernator_tpu.runtime.service import ApiError, Service

log = logging.getLogger("gubernator_tpu.daemon")

_GRPC_CODES = {
    "OUT_OF_RANGE": grpc.StatusCode.OUT_OF_RANGE,
    "INVALID_ARGUMENT": grpc.StatusCode.INVALID_ARGUMENT,
    "INTERNAL": grpc.StatusCode.INTERNAL,
    "FAILED_PRECONDITION": grpc.StatusCode.FAILED_PRECONDITION,
}


class _TracingInterceptor(grpc.aio.ServerInterceptor):
    """Server-side w3c context extract: every unary RPC runs inside an
    `rpc.server` span whose parent is the caller's `traceparent`
    metadata (a forwarding daemon or a traced client), so one trace
    spans a multi-daemon cluster.  Listed FIRST so the stats
    interceptor's SLO observation (and its exemplar) runs with the
    request's trace context still bound.  When tracing is disarmed the
    handler is returned untouched — zero per-RPC overhead."""

    async def intercept_service(self, continuation, handler_call_details):
        handler = await continuation(handler_call_details)
        if (
            handler is None
            or handler.unary_unary is None
            or not tracing.enabled()
        ):
            return handler
        method = handler_call_details.method
        parent = None
        for key, value in handler_call_details.invocation_metadata or ():
            if key == "traceparent":
                parent = tracing.parse_traceparent(value)
                break
        inner = handler.unary_unary

        async def wrapped(request, context):
            with tracing.span(
                "rpc.server", parent=parent, **{"rpc.method": method}
            ):
                return await inner(request, context)

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )


class _StatsInterceptor(grpc.aio.ServerInterceptor):
    """Per-RPC count + duration + failed for EVERY server method — the
    analog of the reference's grpc.StatsHandler, which tags each RPC and
    records both services uniformly (grpc_stats.go:41-145), not just
    V1/GetRateLimits."""

    def __init__(self, metrics: Metrics) -> None:
        self.metrics = metrics
        self._stages = tracing.ledger_of(metrics)

    async def _observed_call(self, inner, method, request, context):
        m = self.metrics
        # wire.rpc: the ledger's row is the measurement; the duration
        # series below is its view.
        rpc = self._stages.begin("wire.rpc", "wire")
        failed = "false"
        try:
            out = await inner(request, context)
            # Pressure advertisement (docs/hotkeys.md): while this
            # daemon's rolling p99 breach run is unbroken, every answered
            # RPC carries the ratio as trailing metadata so callers'
            # PeerClients learn the owner is overloaded-but-alive —
            # the signal that gates hot-key mirroring on their side.
            fr = m.flightrec
            if fr is not None and fr.pressure_active():
                try:
                    # (Added to what the handler set: the forward-once
                    # echo of GetPeerRateLimits.)
                    context.set_trailing_metadata(
                        tuple(context.trailing_metadata() or ()) + (
                            (PRESSURE_METADATA_KEY,
                             "%.3f" % max(fr.pressure_ratio(), 1.0)),
                        )
                    )
                except Exception:  # noqa: BLE001 — advisory only
                    pass
            return out
        except BaseException:
            failed = "true"
            raise
        finally:
            dur = rpc.end() / 1e9
            m.grpc_request_counts.labels(
                method=method, failed=failed
            ).inc()
            # The SLO histogram records the serving request's trace id
            # as an OpenMetrics exemplar when the request is sampled —
            # a scrape's p99 bucket then names a trace to pull
            # (rendered by the openmetrics exposition; docs/tracing.md).
            ctx = tracing.current_context()
            tid = ctx.trace_id_hex() if ctx and ctx.sampled else None
            m.grpc_request_duration.labels(method=method).observe(
                dur, {"trace_id": tid} if tid else None
            )
            fr = m.flightrec
            if fr is not None:
                # Every RPC feeds the rolling SLO window (the p99 the
                # north star is stated against is request latency); the
                # trace id makes a breach dump name its slow traces.
                fr.observe_request(dur, trace_id=tid)

    async def intercept_service(self, continuation, handler_call_details):
        handler = await continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler
        method = handler_call_details.method
        inner = handler.unary_unary

        async def wrapped(request, context):
            return await self._observed_call(inner, method, request, context)

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )


async def _raw_rpc(stages, serve, payload: bytes, context):
    """A raw handler's whole: the ledger's wire.handler, and the
    daemon's empty/occupied state clock around it."""
    stages.rpc_enter()
    handler = stages.stage("wire.handler", "wire")
    try:
        return await serve(payload, context)
    finally:
        handler.end()
        stages.rpc_exit()


class _V1Servicer:
    """Wire <-> Service adapter for the client-facing V1 service.

    GetRateLimits is registered RAW (payload bytes in, bytes out): the
    compiled fast lane (runtime/fastpath.py) serves eligible batches with
    zero per-request Python; everything else deserializes here and takes
    the object path."""

    def __init__(self, daemon: "Daemon") -> None:
        self.d = daemon

    async def GetRateLimits(self, payload: bytes, context):
        return await _raw_rpc(
            self.d.metrics.stages, self._get_rate_limits, payload, context
        )

    async def _get_rate_limits(self, payload: bytes, context):
        try:
            fp = self.d.fastpath
            if fp is not None:
                # The client's own deadline bounds the re-asks of a
                # forward that times out (docs/cluster.md).
                left = context.time_remaining()
                out = await fp.check_raw(
                    payload, peer_rpc=False,
                    deadline=None if left is None
                    else time.monotonic() + left,
                )
                if out is not None:
                    return out
            try:
                request = pb.GetRateLimitsReq.FromString(payload)
            except Exception as e:  # noqa: BLE001 — DecodeError etc.
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"failed to parse GetRateLimitsReq: {e}",
                )
            reqs = grpc_api.reqs_from_pb(request.requests)
            resps = await self.d.service.get_rate_limits(reqs)
        except ApiError as e:
            await context.abort(
                _GRPC_CODES.get(e.code, grpc.StatusCode.INTERNAL), str(e)
            )
        return pb.GetRateLimitsResp(
            responses=grpc_api.resps_to_pb(resps)
        ).SerializeToString()

    async def HealthCheck(self, request, context):
        h = await self.d.service.health_check()
        return grpc_api.health_to_pb(h)


class _PeersServicer:
    """Wire <-> Service adapter for the peer-to-peer PeersV1 service.
    GetPeerRateLimits is raw like the client RPC — the owner side of
    forwarded batches is the cluster hot path."""

    def __init__(self, daemon: "Daemon") -> None:
        self.d = daemon

    async def GetPeerRateLimits(self, payload: bytes, context):
        return await _raw_rpc(
            self.d.metrics.stages, self._get_peer_rate_limits, payload,
            context,
        )

    async def _get_peer_rate_limits(self, payload: bytes, context):
        # A forward that names itself is applied once however often it
        # arrives (net/forward_once.py); one that does not (an upstream
        # peer's, the object path's batches) is applied as it comes.
        fid = None
        for key, value in context.invocation_metadata() or ():
            if key == FORWARD_ID_KEY:
                fid = value
                break
        try:
            if fid is None:
                out = await self._apply_forward(payload)
            else:
                out = await self.d.forwards.apply(
                    fid, lambda: self._apply_forward(payload)
                )
        except ApiError as e:
            await context.abort(
                _GRPC_CODES.get(e.code, grpc.StatusCode.INTERNAL), str(e)
            )
        # What lets the caller ask a timed-out forward again.
        context.set_trailing_metadata(((FORWARD_ONCE_KEY, "1"),))
        return out

    async def _apply_forward(self, payload: bytes) -> bytes:
        fp = self.d.fastpath
        if fp is not None:
            out = await fp.check_raw(payload, peer_rpc=True)
            if out is not None:
                return out
        try:
            request = peers_pb2.GetPeerRateLimitsReq.FromString(payload)
        except Exception as e:  # noqa: BLE001 — DecodeError etc.
            raise ApiError(
                "INVALID_ARGUMENT",
                f"failed to parse GetPeerRateLimitsReq: {e}",
            ) from e
        reqs = grpc_api.reqs_from_pb(request.requests)
        resps = await self.d.service.get_peer_rate_limits(reqs)
        return peers_pb2.GetPeerRateLimitsResp(
            rate_limits=grpc_api.resps_to_pb(resps)
        ).SerializeToString()

    async def UpdatePeerGlobals(self, request, context):
        globals_ = [grpc_api.global_from_pb(g) for g in request.globals]
        await self.d.service.update_peer_globals(globals_)
        return peers_pb2.UpdatePeerGlobalsResp()

    async def Lease(self, request, context):
        """Client-side admission (docs/leases.md): grant bounded local
        allowances for owned keys, proxy the rest to their owners."""
        grants = await self.d.service.lease(
            request.client_id, grpc_api.reqs_from_pb(request.requests)
        )
        return peers_pb2.LeaseResp(
            grants=[grpc_api.lease_grant_to_pb(g) for g in grants]
        )

    async def Reconcile(self, request, context):
        items = [
            grpc_api.reconcile_item_from_pb(it) for it in request.items
        ]
        grants = await self.d.service.reconcile(request.client_id, items)
        return peers_pb2.ReconcileResp(
            grants=[grpc_api.lease_grant_to_pb(g) for g in grants]
        )

    async def Handoff(self, request, context):
        """Live resharding control plane (docs/resharding.md): the old
        owner announces a handoff phase; we ack and adjust how covered
        keys are served."""
        accepted, state = await self.d.service.handoff(
            request.from_address, request.epoch, request.phase,
            request.total_rows,
        )
        return peers_pb2.HandoffResp(accepted=accepted, state=state)

    async def Migrate(self, request, context):
        """One chunk of packed table rows for an active inbound
        handoff; injected only where the key is absent here."""
        try:
            injected, skipped = await self.d.service.migrate(
                request.from_address, request.epoch, request.rows,
                request.final,
            )
        except ApiError as e:
            await context.abort(
                _GRPC_CODES.get(e.code, grpc.StatusCode.INTERNAL), str(e)
            )
        return peers_pb2.MigrateResp(injected=injected, skipped=skipped)


class Daemon:
    """One gubernator-tpu node."""

    def __init__(
        self,
        conf: Optional[DaemonConfig] = None,
        clock=None,
    ) -> None:
        self.conf = conf or DaemonConfig()
        self.clock = clock
        self.metrics = Metrics()
        # Region identity (docs/multiregion.md): an enabled region
        # plane with no explicit name takes the data-center tag — the
        # region name IS what peers advertise on the wire, so the WAN
        # split in set_peers and the rendezvous universe agree.
        # dataclasses.replace re-runs validation with the resolved
        # name (self-region-in-peer-map).
        import dataclasses as _dc

        rc = getattr(self.conf, "region", None) or Config().region
        if rc.enabled and not rc.name and self.conf.data_center:
            rc = _dc.replace(rc, name=self.conf.data_center)
        self.region_cfg = rc
        # Flight recorder (runtime/flightrec.py): armed per config; the
        # Metrics bundle carries it to the layers that feed it.
        from gubernator_tpu.runtime.flightrec import recorder_from_config

        self.flightrec = recorder_from_config(self.conf, self.metrics)
        self.metrics.flightrec = self.flightrec
        if self.flightrec is not None:
            self.flightrec.extras["stalls"] = tracing.stalls
        # The loop's heartbeat (the stage ledger's host.loop_lag): one
        # task a daemon, always on, flight recorder or not.
        self._heartbeat: Optional[asyncio.Task] = None
        # gubload phase attribution (loadgen/engine.py PhaseTracker):
        # {"scenario", "phase", "seq", "since"} while a load-scenario
        # phase is driving this node, None otherwise.
        self.load_status: Optional[dict] = None
        # AutoTLS certs must carry the advertise host in their SANs or
        # cross-host peer dials fail hostname verification.
        adv_host = (
            self.conf.advertise_address.rpartition(":")[0]
            or resolve_host_ip(self.conf.grpc_listen_address).rpartition(
                ":"
            )[0]
        )
        self.tls: Optional[TLSBundle] = setup_tls(
            self.conf.tls, hostnames=("localhost", adv_host)
        )
        if self.conf.metric_flags:
            # Opt-in process/runtime collectors on the private registry
            # (GUBER_METRIC_FLAGS, daemon.go:255-266).
            from prometheus_client import (
                GC_COLLECTOR,
                PLATFORM_COLLECTOR,
                PROCESS_COLLECTOR,
            )

            for c in (PROCESS_COLLECTOR, PLATFORM_COLLECTOR, GC_COLLECTOR):
                try:
                    self.metrics.registry.register(c)
                except ValueError:
                    pass  # another daemon in this process registered them
        # Chaos plane (testing/chaos.py): a pre-built injector from the
        # cluster fixture, or a JSON plan file via GUBER_CHAOS_PLAN.
        self.chaos = self.conf.chaos
        if self.chaos is None and getattr(self.conf, "chaos_plan", ""):
            from gubernator_tpu.testing.chaos import ChaosInjector, load_plan

            self.chaos = ChaosInjector(
                load_plan(
                    self.conf.chaos_plan,
                    seed_override=self.conf.chaos_seed or None,
                )
            )
        self.service: Optional[Service] = None
        self._warmup_s = 0.0
        self.fastpath = None
        # Forwards this daemon owns, by the id their entry daemon gave
        # them: applied once, re-asks joined (net/forward_once.py).
        self.forwards = ForwardOnce(
            self.conf.behaviors.batch_timeout_s, self.metrics.stages
        )
        # Gubstat census sampler (runtime/gubstat.py): armed in start()
        # per GUBER_STATS_ENABLED, closed before the fastpath.
        self.stats_sampler = None
        # Guberberg tier manager (runtime/coldtier.py): armed in
        # start() per GUBER_TIER_ENABLED, closed before the fastpath.
        self.tier = None
        self._grpc_server: Optional[grpc.aio.Server] = None
        self._grpc_tls_proxy = None  # net.tls.TLSTerminatingProxy
        self._grpc_backend_dir: Optional[str] = None
        self._http_runner: Optional[web.AppRunner] = None
        self._pool = None
        self._peers: List[PeerInfo] = []
        # Discovery-update applier state: ONE task applies membership
        # updates in order (latest wins), so rapid watch events can
        # never interleave their set_peers rebuilds; direct callers
        # (the cluster fixture) serialize through the same lock.
        self._set_peers_lock = asyncio.Lock()
        self._pending_peers: Optional[List[PeerInfo]] = None
        self._peers_event: Optional[asyncio.Event] = None
        self._peer_update_task: Optional[asyncio.Task] = None
        # Monotone count of APPLIED membership updates (observability +
        # the watch-storm coalescing test).
        self.peer_updates_applied = 0
        self.grpc_address = self.conf.grpc_listen_address
        self.http_address = self.conf.http_listen_address

    # -- lifecycle -------------------------------------------------------
    async def start(self) -> None:
        cfg = Config(
            behaviors=self.conf.behaviors,
            device=self.conf.device,
            cache_size=self.conf.cache_size,
            data_center=self.conf.data_center,
            local_picker_hash=getattr(
                self.conf, "local_picker_hash", "xx"
            ),
            region_picker_hash=getattr(
                self.conf, "region_picker_hash", "xx"
            ),
            loader=getattr(self.conf, "loader", None),
            store=getattr(self.conf, "store", None),
            sketch=getattr(self.conf, "sketch", None),
            circuit=getattr(self.conf, "circuit", None) or Config().circuit,
            degraded_mode=getattr(self.conf, "degraded_mode", "error"),
            shadow_fraction=getattr(self.conf, "shadow_fraction", 0.5),
            hotkey=getattr(self.conf, "hotkey", None) or Config().hotkey,
            lease=getattr(self.conf, "lease", None) or Config().lease,
            stats=getattr(self.conf, "stats", None) or Config().stats,
            tier=getattr(self.conf, "tier", None) or Config().tier,
            region=self.region_cfg,
        )
        peer_creds = (
            self.tls.client_credentials() if self.tls is not None else None
        )
        if self.flightrec is not None:
            self.flightrec.start()
        if self._heartbeat is None:
            self._heartbeat = asyncio.ensure_future(
                self.metrics.stages.heartbeat()
            )
        t_warm = time.monotonic()
        self.service = Service(
            cfg,
            clock=self.clock,
            peer_credentials=peer_creds,
            metrics=self.metrics,
        )
        await self.service.start()
        from gubernator_tpu.runtime.fastpath import FastPath

        self.fastpath = FastPath(self.service)
        # Table build + every start-up compile (or compile-cache load).
        self._warmup_s = time.monotonic() - t_warm
        if cfg.stats.enabled:
            # Gubstat census sampler: periodic table_stats census off
            # the request path (docs/observability.md).  Registered as
            # a flight-recorder extra so breach/SIGUSR2 dumps carry the
            # last table block.
            from gubernator_tpu.runtime.gubstat import TableStatsSampler

            self.stats_sampler = TableStatsSampler(
                self.service,
                metrics=self.metrics,
                interval_s=cfg.stats.interval_s,
            )
            self.stats_sampler.start()
            if self.flightrec is not None:
                self.flightrec.extras["table"] = (
                    lambda: self.stats_sampler.last
                )
        if cfg.tier.enabled:
            # Guberberg tier manager (runtime/coldtier.py;
            # docs/tiering.md): host-RAM cold tier under the HBM table,
            # promote-on-access and watermark demotion on its own
            # worker thread.
            from gubernator_tpu.runtime.coldtier import TierManager

            self.tier = TierManager(
                self.service,
                cfg.tier,
                metrics=self.metrics,
            )
            self.service.tier = self.tier
            self.tier.start()

        # gRPC server (daemon.go:101-126): both services on one listener.
        # 4MB recv cap: grpc-go's default, which reference peers assume.
        # Count-capped peer batches (batch_limit=1000) with long key strings
        # can pass 1MB, and a rejected batch fails every flush window.
        interceptors = [
            _TracingInterceptor(),
            _StatsInterceptor(self.metrics),
        ]
        if self.chaos is not None:
            from gubernator_tpu.testing.chaos import ChaosServerInterceptor

            # Daemon-boundary fault injection; addr resolves lazily
            # (the ephemeral port isn't bound yet).
            interceptors.append(
                ChaosServerInterceptor(self.chaos, lambda: self.grpc_address)
            )
        server = grpc.aio.server(
            options=[
                ("grpc.max_receive_message_length", 4 * 1024 * 1024),
            ],
            interceptors=interceptors,
        )
        server.add_generic_rpc_handlers((
            grpc_api.v1_generic_handler(_V1Servicer(self), raw=True),
            grpc_api.peers_generic_handler(_PeersServicer(self), raw=True),
        ))
        from gubernator_tpu.net.tls import OPTIONAL_MODES

        proxy_auth = (
            self.tls is not None
            and self.tls.client_auth in OPTIONAL_MODES
        )
        if proxy_auth:
            # Optional client-auth (request / verify-if-given): grpc's
            # credentials can't request-without-require a client cert,
            # so terminate TLS in-process (ssl.CERT_OPTIONAL, ALPN h2)
            # and pipe plaintext HTTP/2 to an insecure gRPC listener on
            # a unix socket in a 0700 tempdir — NOT a loopback TCP port,
            # which would let any local process bypass TLS/client-auth.
            import tempfile

            self._grpc_backend_dir = tempfile.mkdtemp(prefix="gubtpu-grpc-")
            bound = "unix:%s/backend.sock" % self._grpc_backend_dir
            port = server.add_insecure_port(bound)
        elif self.tls is not None:
            bound = self.conf.grpc_listen_address
            port = server.add_secure_port(
                bound, self.tls.server_credentials(),
            )
        else:
            bound = self.conf.grpc_listen_address
            port = server.add_insecure_port(bound)
        if port == 0:
            raise RuntimeError(f"failed to bind {bound}")
        host = self.conf.grpc_listen_address.rpartition(":")[0]
        await server.start()
        self._grpc_server = server
        if proxy_auth:
            from gubernator_tpu.net.tls import TLSTerminatingProxy

            self._grpc_tls_proxy = TLSTerminatingProxy(
                self.tls.grpc_proxy_ssl_context(),
                "%s/backend.sock" % self._grpc_backend_dir,
            )
            try:
                port = await self._grpc_tls_proxy.start(
                    self.conf.grpc_listen_address
                )
            except BaseException:
                # The real listener never came up (port already bound,
                # bad address): the daemon is NOT serving, so don't
                # leave the insecure unix-socket backend and its 0700
                # tempdir behind for a caller that may never close().
                import shutil

                self._grpc_tls_proxy = None
                await server.stop(grace=None)
                self._grpc_server = None
                shutil.rmtree(self._grpc_backend_dir, ignore_errors=True)
                self._grpc_backend_dir = None
                raise
        # Rewrite :0 ephemeral binds to the actual port for advertisement.
        self.grpc_address = f"{host}:{port}"
        if self.chaos is not None:
            # Bind the injector to our (now-known) address; every
            # PeerClient built from here on carries the hook.
            self.service.chaos = self.chaos.bind(self.grpc_address)

        await self._start_http()
        await self._start_discovery()
        log.info(
            "gubernator-tpu daemon up: grpc=%s http=%s",
            self.grpc_address, self.http_address,
        )
        log.info(
            "device: %s",
            " ".join(f"{k}={v}" for k, v in self._device_vars().items()),
        )

    async def drain(self) -> int:
        """Graceful scale-down (docs/resharding.md): migrate every
        owned row to the ring without this node, while all listeners
        stay up — the autoscaler's preStop/SIGTERM hook.  Call before
        close(); returns rows shipped."""
        if self.service is None:
            return 0
        return await self.service.drain_for_shutdown()

    async def close(self) -> None:
        # Order: stop taking traffic (discovery, then listeners with a
        # drain grace) BEFORE tearing down the service — late requests must
        # drain, not crash into a closed device executor.
        if self._peer_update_task is not None:
            self._peer_update_task.cancel()
            await asyncio.gather(
                self._peer_update_task, return_exceptions=True
            )
            self._peer_update_task = None
        if self._pool is not None:
            await self._pool.close()
            self._pool = None
        if getattr(self.conf, "reshard_drain_on_close", False):
            # Migrate owned rows out while the listeners still serve
            # (peers keep forwarding through the handoff window).
            try:
                await self.drain()
            except Exception as e:  # noqa: BLE001 — close must proceed
                log.warning("drain on close failed: %s", e)
        if self._grpc_tls_proxy is not None:
            # Refuse NEW connections on the real socket before the gRPC
            # drain (a mid-shutdown dial must see connection-refused, not
            # a handshake onto a dying backend); live pipes keep flowing
            # through the grace below, then get cut.
            await self._grpc_tls_proxy.stop_accepting()
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=1.0)
            self._grpc_server = None
        if self._grpc_tls_proxy is not None:
            await self._grpc_tls_proxy.close()
            self._grpc_tls_proxy = None
        if self._grpc_backend_dir is not None:
            import shutil

            shutil.rmtree(self._grpc_backend_dir, ignore_errors=True)
            self._grpc_backend_dir = None
        if self._http_runner is not None:
            await self._http_runner.cleanup()
            self._http_runner = None
        if self.stats_sampler is not None:
            # Before the fastpath and the service close under it.
            await self.stats_sampler.close()
            self.stats_sampler = None
        if self.tier is not None:
            # Same ordering rule for the tier worker's promote/demote
            # jobs.
            await asyncio.get_running_loop().run_in_executor(
                None, self.tier.close
            )
            self.tier = None
        if self.fastpath is not None:
            await self.fastpath.close()
            self.fastpath = None
        if self.service is not None:
            await self.service.close()
        if self.flightrec is not None:
            await self.flightrec.close()
        if self._heartbeat is not None:
            self._heartbeat.cancel()
            await asyncio.gather(self._heartbeat, return_exceptions=True)
            self._heartbeat = None
        # The served path's budget over this daemon's life, for whoever
        # reads the log after /debug/vars is gone.
        log.info(
            "stage ledger at close: %s",
            json.dumps(self.metrics.stages.debug_vars(), sort_keys=True),
        )
        log.info(
            "stalls at close: %s",
            json.dumps({
                "stalls": tracing.stalls(),
                "threads": tracing.thread_vars(),
                "process": tracing.process_vars(),
            }, sort_keys=True),
        )

    # -- HTTP gateway (daemon.go:231-270) --------------------------------
    async def _start_http(self) -> None:
        app = web.Application()
        app.router.add_post("/v1/GetRateLimits", self._http_get_rate_limits)
        app.router.add_get("/v1/HealthCheck", self._http_health)
        app.router.add_get("/metrics", self._http_metrics)
        app.router.add_get("/debug/flightrec", self._http_flightrec)
        app.router.add_get("/debug/vars", self._http_vars)
        app.router.add_get("/debug/key", self._http_debug_key)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        host, _, port = self.conf.http_listen_address.rpartition(":")
        ssl_ctx = (
            self.tls.server_ssl_context() if self.tls is not None else None
        )
        site = web.TCPSite(runner, host or "0.0.0.0", int(port),
                           ssl_context=ssl_ctx)
        await site.start()
        actual_port = site._server.sockets[0].getsockname()[1]
        self.http_address = f"{host}:{actual_port}"
        self._http_runner = runner

    async def _http_get_rate_limits(self, request: web.Request):
        """REST gateway contract: JSON with under_score field names
        (daemon.go:241-243 marshaler options)."""
        try:
            body = await request.text()
            msg = json_format.Parse(body, pb.GetRateLimitsReq())
        except json_format.ParseError as e:
            return web.json_response({"error": str(e)}, status=400)
        try:
            out = None
            if self.fastpath is not None:
                # Ride the compiled lane: same serialized device pipeline
                # as gRPC traffic, so REST and gRPC checks of one key
                # never interleave mid-cascade.
                raw = await self.fastpath.check_raw(
                    msg.SerializeToString(), peer_rpc=False
                )
                if raw is not None:
                    out = pb.GetRateLimitsResp.FromString(raw)
            if out is None:
                resps = await self.service.get_rate_limits(
                    grpc_api.reqs_from_pb(msg.requests)
                )
                out = pb.GetRateLimitsResp(
                    responses=grpc_api.resps_to_pb(resps)
                )
        except ApiError as e:
            return web.json_response(
                {"error": str(e), "code": e.code}, status=400
            )
        return web.Response(
            text=json_format.MessageToJson(
                out,
                preserving_proto_field_name=True,
                always_print_fields_with_no_presence=True,
            ),
            content_type="application/json",
        )

    async def _http_health(self, request: web.Request):
        h = await self.service.health_check()
        return web.Response(
            text=json_format.MessageToJson(
                grpc_api.health_to_pb(h),
                preserving_proto_field_name=True,
                always_print_fields_with_no_presence=True,
            ),
            content_type="application/json",
        )

    async def _http_metrics(self, request: web.Request):
        with self.metrics.stages.stage("host.scrape", "host"):
            return self._render_metrics(request)

    def _render_metrics(self, request: web.Request):
        # Refresh device gauges at scrape time.
        if self.service is not None:
            self.metrics.device_occupancy.set(
                self.service.backend.occupancy()
            )
            self.metrics.cache_size.set(self.service.backend.occupancy())
            if self.service.global_engine is not None:
                self.metrics.global_cache_occupancy.set(
                    self.service.global_engine.cache_occupancy()
                )
            # Per-shard mesh gauges (docs/architecture.md): occupancy
            # skew, refreshed at scrape like the aggregate occupancy
            # above.
            shard_occ = getattr(
                self.service.backend, "shard_occupancy", None
            )
            if shard_occ is not None:
                for s, occ in enumerate(shard_occ()):
                    self.metrics.shard_occupancy.labels(
                        shard=str(s)
                    ).set(occ)
            # Gubstat top-K tenant gauges: refreshed at scrape (stale
            # tenant labels removed); the table census gauges refresh
            # on the sampler's own cadence, never here.
            if self.service.tenants is not None:
                self.service.tenants.publish(self.metrics)
            # Per-peer rolling error windows (the HealthCheck signal,
            # peer_client.last_errors) as scrape-time gauges.
            for peer in (
                self.service.peer_list()
                + self.service.region_picker.peers()
            ):
                self.metrics.peer_error_window.labels(
                    peerAddr=peer.info().grpc_address
                ).set(len(peer.last_errors()))
                if peer.breaker is not None:
                    self.metrics.circuit_state.labels(
                        peerAddr=peer.info().grpc_address
                    ).set(int(peer.breaker.state))
        # Tracing span counters (runtime/tracing.py is process-global;
        # refreshed at scrape like the device gauges above).
        tv = tracing.debug_vars()
        for state, val in (tv.get("spans") or {}).items():
            if state != "recent":
                self.metrics.tracing_spans.labels(state=state).set(val)
        accept = request.headers.get("Accept", "")
        if "application/openmetrics-text" in accept:
            # OpenMetrics exposition carries the trace-id exemplars the
            # classic text format cannot represent (docs/tracing.md).
            return web.Response(
                body=self.metrics.render_openmetrics(),
                headers={
                    "Content-Type": (
                        "application/openmetrics-text; version=1.0.0; "
                        "charset=utf-8"
                    )
                },
            )
        return web.Response(
            body=self.metrics.render(),
            content_type="text/plain",
            charset="utf-8",
        )

    # -- debug plane (runtime/flightrec.py) ------------------------------
    async def _http_flightrec(self, request: web.Request):
        """Live flight-recorder snapshot; `?limit=N` caps the ring tail."""
        if self.flightrec is None:
            return web.json_response(
                {"enabled": False,
                 "hint": "set GUBER_FLIGHTREC=1 to arm the recorder"},
                status=404,
            )
        try:
            limit = int(request.query.get("limit", "0")) or None
        except ValueError:
            return web.json_response({"error": "bad limit"}, status=400)
        snap = self.flightrec.snapshot(limit=limit)
        snap["enabled"] = True
        return web.json_response(snap)

    def _device_vars(self) -> dict:
        """Where this daemon runs, as JAX reports it: platform,
        device_kind, device count, the ids of the devices the table
        lives on, whether the compiled lane loaded, and what start-up
        warm-up cost."""
        from gubernator_tpu import native

        out = self.service.backend.device_info()
        out["compiled_lane"] = native.available()
        if not out["compiled_lane"]:
            out["compiled_lane_error"] = native.load_error()
        out["warmup_s"] = round(self._warmup_s, 3)
        return out

    async def _http_vars(self, request: web.Request):
        """expvar-style internal counters (the Go daemon exposes
        /debug/vars via expvar; these are the TPU engine's equivalents)."""
        with self.metrics.stages.stage("host.scrape", "host"):
            return web.json_response(self._vars())

    def _vars(self) -> dict:
        out = {
            "grpc_address": self.grpc_address,
            "http_address": self.http_address,
        }
        s = self.service
        if s is not None:
            out["device"] = self._device_vars()
            be = s.backend
            out["backend"] = {
                "checks": be.checks,
                "over_limit": be.over_limit,
                "not_persisted": be.not_persisted,
                "occupancy": be.occupancy(),
            }
            # Mesh backends: the per-shard skew view.
            shard_occ = getattr(be, "shard_occupancy", None)
            if shard_occ is not None:
                out["backend"]["shard_occupancy"] = shard_occ()
            out["inflight_checks"] = s._inflight_checks
            out["global"] = {
                "async_sends": s.global_mgr.async_sends,
                "broadcasts": s.global_mgr.broadcasts,
                "reread_batches": s.global_mgr.reread_batches,
                "reread_keys": s.global_mgr.reread_keys,
            }
            if s.global_engine is not None:
                out["global"]["engine"] = s.global_engine.debug_vars()
            out["multi_region_sends"] = s.multi_region_mgr.region_sends
            out["peers"] = {
                p.info().grpc_address: len(p.last_errors())
                for p in s.peer_list() + s.region_picker.peers()
            }
            out["circuits"] = {
                p.info().grpc_address: p.circuit_snapshot()
                for p in s.peer_list() + s.region_picker.peers()
            }
            out["degraded"] = {
                "mode": s.cfg.degraded_mode,
                "served": s.degraded_served,
                "shadow_owners": {
                    addr: len(keys) for addr, keys in s._shadow.items()
                },
            }
            if s.hotkeys is not None:
                # Hot-key survival plane (docs/hotkeys.md): the exact
                # hot-set, this node's active mirror widenings, and the
                # pressure-shed state.
                s.hotkeys.poll()  # idle demotion isn't traffic-gated
                out["hotkeys"] = {
                    **s.hotkeys.debug_vars(),
                    "mirror_served": s.mirror_served,
                    "active_mirrors": [
                        "%016x" % (int(fp) & 0xFFFFFFFFFFFFFFFF)
                        for fp in s.active_mirror_fps()
                    ],
                    "shed": {
                        "level": s.shed_level(),
                        "served": s.shed_served,
                        "priorities": list(
                            s.cfg.hotkey.shed_priorities
                        ),
                    },
                }
            if s.leases is not None:
                # Client-side admission leases (docs/leases.md): grant/
                # refusal counters, per-key holder expiries, knobs.
                out["leases"] = s.leases.debug_vars()
            if s.reshard is not None:
                # Live resharding (docs/resharding.md): per-peer
                # handoff phases, row counters, shadow burns.
                out["reshard"] = {
                    **s.reshard.debug_vars(),
                    "peer_updates_applied": self.peer_updates_applied,
                }
            if s.regions is not None:
                # Region carve plane (docs/multiregion.md): home
                # universe, drift backlog, per-link heal states.
                out["region"] = s.regions.debug_vars()
        if s is not None and s.tenants is not None:
            # Gubstat per-tenant admission ledger (docs/observability.md).
            out["tenants"] = s.tenants.debug_vars()
        if self.stats_sampler is not None:
            # Gubstat device-table census: the last sampled table block
            # (occupancy, bucket fill, age/TTL histograms, shadow-plane
            # census) plus sampler health.
            out["table"] = self.stats_sampler.debug_vars()
        if self.tier is not None:
            # Guberberg tier ledger (docs/tiering.md): cold residents,
            # promote/demote/cold-hit totals, promote latency histogram.
            out["tier"] = self.tier.debug_vars()
        fp = self.fastpath
        if fp is not None:
            # Per-lane drain/pipeline counters (drains, overlap_drains,
            # waited_drains, bubble_ms_total, occupancy): whether the
            # drain's depth binds.
            out["fastpath"] = fp.debug_vars()
        # The stage ledger (runtime/tracing.py): count / ms_total /
        # ms_max of every step of the served path, by lane
        # (docs/observability.md).
        out["stages"] = self.metrics.stages.debug_vars()
        # Why a stage took that long (docs/observability.md): the leaf
        # instances far over their row's mean, with their times; every
        # Python thread's CPU clock; the whole process's.
        out["stalls"] = tracing.stalls()
        out["threads"] = tracing.thread_vars()
        out["process"] = tracing.process_vars()
        # Attribution plane (runtime/tracing.py): enabled, sampler,
        # honest exporter status, spans started/exported/dropped.
        out["tracing"] = tracing.debug_vars()
        fr = self.flightrec
        if fr is not None:
            out["flightrec"] = {
                "breaches": fr.breaches,
                "dumps": fr.dumps,
                "last_p50_ms": round(fr.last_p50_ms, 3),
                "last_p99_ms": round(fr.last_p99_ms, 3),
                "loop_lag_ms_max": round(fr.max_lag_ms, 2),
                "last_dump_path": fr.last_dump_path,
            }
        if self.load_status is not None:
            out["load"] = dict(self.load_status)
        return out

    @staticmethod
    def _cache_item_json(item) -> Optional[dict]:
        """Decoded host view of one slot-table row (CacheItem)."""
        if item is None:
            return None
        out = {
            "key": item.key,
            "algorithm": int(item.algorithm),
            "limit": int(item.limit),
            "duration": int(item.duration),
            "remaining": float(item.remaining),
            "created_at": int(item.created_at),
            "status": int(item.status),
            "burst": int(item.burst),
            "expire_at": int(item.expire_at),
        }
        if item.cached_resp is not None:
            cr = item.cached_resp
            out["cached_resp"] = {
                "status": int(cr.status),
                "limit": int(cr.limit),
                "remaining": int(cr.remaining),
                "reset_time": int(cr.reset_time),
            }
        return out

    async def _http_debug_key(self, request: web.Request):
        """Gubstat key inspection (docs/observability.md): the decoded
        live row for `?name=...&key=...` plus its shadow-plane siblings
        (.hot-mirror / .lease-grant / .degraded-shadow /
        .handoff-shadow).  READ-ONLY — rides the backend's point-read
        probe (no hits applied, the row is bit-identical afterwards) —
        and owner-routed: a non-owner proxies to the owner's HTTP
        listener so any node answers for any key cluster-wide.
        Gated by GUBER_STATS_PEEK (row contents are operator data)."""
        from gubernator_tpu.runtime.gubstat import PLANE_LABELS
        from gubernator_tpu.ops.state import SHADOW_PLANES

        s = self.service
        if s is None:
            return web.json_response({"error": "not started"}, status=503)
        if not (s.cfg.stats.enabled and s.cfg.stats.peek):
            return web.json_response(
                {"error": "key peek disabled",
                 "hint": "set GUBER_STATS_PEEK=1"},
                status=403,
            )
        name = request.query.get("name", "")
        key = request.query.get("key", "")
        if not name:
            return web.json_response({"error": "missing name"}, status=400)
        hash_key = name + "_" + key
        owner_addr = ""
        if not s._owns_key(hash_key):
            try:
                info = s.get_peer(hash_key).info()
            except Exception:
                info = None
            if info is not None:
                owner_addr = info.grpc_address
                if (
                    info.http_address
                    and request.query.get("noproxy", "") != "1"
                ):
                    # Route to the owner (one hop: the owner serves
                    # with noproxy so a stale ring can't loop).
                    import aiohttp

                    scheme = "https" if self.tls is not None else "http"
                    url = (
                        f"{scheme}://{info.http_address}/debug/key"
                    )
                    ssl_ctx = (
                        self.tls.client_ssl_context()
                        if self.tls is not None
                        else None
                    )
                    try:
                        async with aiohttp.ClientSession() as sess:
                            async with sess.get(
                                url,
                                params={
                                    "name": name, "key": key,
                                    "noproxy": "1",
                                },
                                ssl=ssl_ctx,
                                timeout=aiohttp.ClientTimeout(total=5),
                            ) as resp:
                                body = await resp.json()
                                body["proxied_via"] = self.http_address
                                return web.json_response(
                                    body, status=resp.status
                                )
                    except Exception as e:  # owner answers unreachable
                        return web.json_response(
                            {"error": f"owner proxy failed: {e}",
                             "owner": owner_addr},
                            status=502,
                        )
        be = s.backend
        row = self._cache_item_json(be.get_cache_item(hash_key))
        shadows = {
            label: self._cache_item_json(
                be.get_cache_item(hash_key + suffix)
            )
            for suffix, label in zip(SHADOW_PLANES, PLANE_LABELS)
        }
        return web.json_response({
            "name": name,
            "key": key,
            "hash_key": hash_key,
            "served_by": self.grpc_address,
            "owner": owner_addr or self.grpc_address,
            "found": row is not None,
            "row": row,
            "shadows": shadows,
        })

    # -- peers / discovery ----------------------------------------------
    def advertise_address(self) -> str:
        return self.conf.advertise_address or resolve_host_ip(
            self.grpc_address
        )

    async def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Mark ourselves in the peer list and hand it to the service
        (daemon.go:375-385 sets IsOwner on the local instance).
        Serialized: concurrent callers (the discovery applier, the
        cluster fixture) apply one at a time, in call order."""
        me = self.advertise_address()
        peers = list(peers)
        if self.region_cfg.enabled and self.region_cfg.peers:
            # WAN seed merge (docs/multiregion.md): the configured
            # remote-region addresses ride along with EVERY discovery
            # kind — in-region discovery (dns/gossip/k8s/etcd) only
            # sees its own mesh, and a region partition must not
            # evict the seed arcs we will need to reconcile over.
            have = {p.grpc_address for p in peers}
            for rname, addrs in sorted(self.region_cfg.peers.items()):
                if rname == self.region_cfg.name:
                    continue
                for a in addrs:
                    if a and a not in have:
                        have.add(a)
                        peers.append(PeerInfo(
                            grpc_address=a, data_center=rname
                        ))
        marked = [
            PeerInfo(
                grpc_address=p.grpc_address,
                http_address=p.http_address,
                data_center=p.data_center,
                is_owner=(p.grpc_address == me),
            )
            for p in peers
        ]
        async with self._set_peers_lock:
            self._peers = marked
            await self.service.set_peers(marked)
            self.peer_updates_applied += 1

    def peers(self) -> List[PeerInfo]:
        return list(self._peers)

    async def _apply_peer_updates(self) -> None:
        """The discovery-update applier: ONE long-lived task drains
        membership events latest-wins, so an etcd/k8s watch storm of N
        events within the GUBER_PEER_DEBOUNCE_MS window triggers ONE
        remap, not N interleaved rebuilds (and out-of-order application
        is structurally impossible — there is exactly one applier)."""
        assert self._peers_event is not None
        debounce_s = max(self.conf.peer_debounce_ms, 0) / 1000.0
        while True:
            await self._peers_event.wait()
            if debounce_s:
                # Coalescing window: later events within it simply
                # overwrite _pending_peers (latest wins).
                await asyncio.sleep(debounce_s)
            self._peers_event.clear()
            peers, self._pending_peers = self._pending_peers, None
            if peers is None:
                continue
            try:
                await self.set_peers(peers)
            except Exception as e:  # noqa: BLE001 — keep the applier
                log.warning("peer update failed: %s", e)

    async def _start_discovery(self) -> None:
        kind = self.conf.peer_discovery_type
        if kind in ("none", ""):
            return
        loop = asyncio.get_running_loop()
        self._peers_event = asyncio.Event()
        # Keep a reference to the applier: a fire-and-forget task can
        # be garbage-collected mid-flight, and close() must be able to
        # cancel it.
        self._peer_update_task = asyncio.ensure_future(
            self._apply_peer_updates()
        )

        def on_update(peers: Sequence[PeerInfo]) -> None:
            # Pools usually run on this loop, but some sources (etcd watch
            # callbacks) fire from background threads — route accordingly.
            def submit() -> None:
                self._pending_peers = list(peers)
                self._peers_event.set()

            try:
                running = asyncio.get_running_loop()
            except RuntimeError:
                running = None
            if running is loop:
                submit()
            else:
                loop.call_soon_threadsafe(submit)

        if kind == "static":
            from gubernator_tpu.discovery.static import StaticPool

            peers = [
                PeerInfo(grpc_address=a) for a in self.conf.static_peers
            ]
            me = self.advertise_address()
            if all(p.grpc_address != me for p in peers):
                peers.append(PeerInfo(grpc_address=me))
            self._pool = StaticPool(peers, on_update)
        elif kind == "dns":
            from gubernator_tpu.discovery.dns import DnsPool

            grpc_port = int(self.grpc_address.rpartition(":")[2])
            http_port = int(self.http_address.rpartition(":")[2])
            self._pool = DnsPool(
                self.conf.dns_fqdn,
                on_update,
                grpc_port=grpc_port,
                http_port=http_port,
                poll_interval_s=self.conf.dns_poll_interval_s,
                data_center=self.conf.data_center,
                own_address=self.advertise_address(),
            )
        elif kind == "gossip":
            from gubernator_tpu.discovery.gossip import GossipPool

            gossip_port = int(self.grpc_address.rpartition(":")[2]) + 1000
            bind = self.conf.gossip_bind_address or f"0.0.0.0:{gossip_port}"
            # Gossip identity rides the daemon's advertise host.
            adv_host = self.advertise_address().rpartition(":")[0]
            bind_port = bind.rpartition(":")[2]
            self._pool = GossipPool(
                bind,
                PeerInfo(
                    grpc_address=self.advertise_address(),
                    http_address=self.http_address,
                    data_center=self.conf.data_center,
                ),
                on_update,
                seeds=self.conf.gossip_seeds,
                advertise_address=f"{adv_host}:{bind_port}",
            )
        elif kind == "k8s":
            from gubernator_tpu.discovery.k8s import K8sPool

            self._pool = K8sPool(
                on_update,
                namespace=self.conf.k8s_namespace,
                selector=self.conf.k8s_endpoints_selector,
                pod_ip=self.conf.k8s_pod_ip,
                pod_port=self.conf.k8s_pod_port,
                mechanism=self.conf.k8s_watch_mechanism,
                http_port=int(self.http_address.rpartition(":")[2]),
            )
        elif kind == "etcd":
            from gubernator_tpu.discovery.etcd import EtcdPool

            self._pool = EtcdPool(
                on_update,
                PeerInfo(
                    grpc_address=self.advertise_address(),
                    http_address=self.http_address,
                    data_center=self.conf.data_center,
                ),
                endpoints=getattr(
                    self.conf, "etcd_endpoints", "localhost:2379"
                ),
            )
        else:
            raise ValueError(f"unknown peer_discovery_type '{kind}'")
        await self._pool.start()


async def spawn_daemon(conf: DaemonConfig, clock=None) -> Daemon:
    """Create + start a daemon (SpawnDaemon, daemon.go:66-79)."""
    d = Daemon(conf, clock=clock)
    await d.start()
    return d


async def wait_for_connect(
    addresses: Sequence[str],
    timeout_s: float = 10.0,
    credentials=None,
) -> None:
    """Block until every address accepts a gRPC connection
    (daemon.go:403-442)."""
    deadline = time.monotonic() + timeout_s
    for addr in addresses:
        while True:
            if credentials is not None:
                ch = grpc.aio.secure_channel(addr, credentials)
            else:
                ch = grpc.aio.insecure_channel(addr)
            try:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"timed out connecting to {addr}")
                await asyncio.wait_for(
                    ch.channel_ready(), timeout=remaining
                )
                break
            except asyncio.TimeoutError:
                raise TimeoutError(f"timed out connecting to {addr}")
            finally:
                await ch.close()
