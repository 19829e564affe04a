"""Gubscope: end-to-end request attribution through the serving pipeline.

The reference wraps nearly every function in holster/OTel scopes
(gubernator.go:118-121, workers.go:250-253, algorithms.go:32-35) and
exports to Jaeger/OTLP via standard env vars (jaegertracing.md).  This
runtime's request path is a deep async pipeline — coalesced merges,
dispatch/fetch stages on pool threads, peer forwards — so a span plane that only knows the RPC boundary cannot
answer "where did the 300ms go".  This module is the attribution core:

  * **Spans** are lightweight in-process records (trace/span ids,
    parent, attributes, links, wall times) — no OpenTelemetry package is
    required to create, propagate, or assert on them.  When the OTel SDK
    and OTLP exporter packages ARE installed (the `[tracing]` extra) and
    `OTEL_EXPORTER_OTLP_ENDPOINT` is set, finished spans are bridged to
    OTLP; otherwise they stay in-process (a bounded recent-span ring
    that the flight recorder attaches to breach dumps).
  * **Context** rides a contextvar on the event loop and is carried
    EXPLICITLY across every thread hand-off (coalescer entries) —
    contextvars do not cross `run_in_executor`, so each async
    seam stores the submitting context and re-binds it on the worker
    (`use_context`).
  * **Cross-peer**: `grpc_metadata()` renders the current context as a
    w3c `traceparent` header for outbound peer RPCs;
    `parse_traceparent()` is the server-side extract (daemon.py's
    tracing interceptor), so one trace spans a multi-daemon cluster.
  * **Sampling** follows the OTel env spec (`OTEL_TRACES_SAMPLER` /
    `OTEL_TRACES_SAMPLER_ARG`): parent-based by construction (a child
    inherits its parent's decision), with the root decision drawn from
    the configured ratio.  `always_off`/`off` disables tracing outright.

Disabled is the default and costs (almost) nothing: every entry point
checks one module global and returns before allocating anything — the
hot path creates zero spans and zero contexts until `init_tracing()`
arms the plane (tests/test_tracing.py pins this).

The **stage ledger** (second half of this module) is the always-on
budget of the served path: every step of a decision — handler, parse,
coalescer waits, pack, lock wait, dispatch, device->host wait, unpack,
serialize — is timed where it happens by ONE primitive (`stage` /
`begin`) that feeds three sinks at once: the ledger's integer cells
(`/debug/vars` `stages`), a `jax.profiler.TraceAnnotation` named
`gub.<stage>` (so the section is an event on the profiler's clock, on
the thread that ended it), and — only when the span plane above is
armed and the parent sampled — a `Span`.  It has no switch: the cells
cost a clock read and three integer adds, the annotation one atomic
check while no profiler session is active (docs/tracing.md).

The same ledger says WHY a stage took that long: what shares the
process with the served path has rows of its own on lane `host` (the
collector, the census, the hot-key sketch, the scrapes, the loop's lag);
`/debug/vars` `threads` and `process` say who had the CPU — every Python
thread's own CPU clock, read at render and never on the hot path: a
pool's CPU beside the wall of the sections it ran is how much of that
wall it was running, the rest it waited for the GIL or a core; and an
instance of a leaf row far over its row's mean is kept with its times
in the `stalls` ring.
"""
from __future__ import annotations

import asyncio
import contextlib
import contextvars
import gc
import logging
import os
import re
import sys
import threading
import time
from collections import deque
from typing import (
    Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple,
)

log = logging.getLogger("gubernator_tpu.tracing")

# Bounded ring of recently finished (sampled) spans: the in-process
# trace tail the flight recorder attaches to breach dumps.  Fixed cap —
# a span record is small and 512 covers several breach windows.
RECENT_SPAN_CAP = 512

_SAMPLER_ALIASES = {
    "on": "always_on",
    "off": "always_off",
    "parentbased_always_on": "always_on",
    "parentbased_always_off": "always_off_root",
    "parentbased_traceidratio": "traceidratio",
}


class SpanContext:
    """Immutable (trace_id, span_id, sampled) triple — what crosses
    every async seam and the wire (w3c traceparent)."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: int, span_id: int, sampled: bool) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def traceparent(self) -> str:
        return "00-%032x-%016x-%s" % (
            self.trace_id, self.span_id, "01" if self.sampled else "00"
        )

    def trace_id_hex(self) -> str:
        return "%032x" % self.trace_id

    def span_id_hex(self) -> str:
        return "%016x" % self.span_id

    def __repr__(self) -> str:  # debugging/test output
        return f"<SpanContext {self.traceparent()}>"


def parse_traceparent(value: str) -> Optional[SpanContext]:
    """Parse a w3c `traceparent` header; None on anything malformed
    (never raises — this runs on untrusted RPC metadata)."""
    try:
        parts = value.strip().split("-")
        if len(parts) != 4:
            return None
        version, tid, sid, flags = parts
        if len(version) != 2 or len(tid) != 32 or len(sid) != 16:
            return None
        if int(version, 16) < 0 or version == "ff":
            return None
        trace_id = int(tid, 16)
        span_id = int(sid, 16)
        if trace_id == 0 or span_id == 0:
            return None
        sampled = bool(int(flags, 16) & 0x01)
        return SpanContext(trace_id, span_id, sampled)
    except (ValueError, AttributeError):
        return None


class Span:
    """One finished-or-in-flight sampled span.  Mutation (attributes,
    links) is single-writer by construction: the thread running the
    spanned section.  `end()` is idempotent and hands the span to the
    exporters."""

    __slots__ = (
        "name", "context", "parent_id", "start_ns", "end_ns",
        "attributes", "links", "error",
    )

    def __init__(
        self,
        name: str,
        context: SpanContext,
        parent_id: Optional[int],
        attributes: Optional[Dict] = None,
        links: Sequence[SpanContext] = (),
    ) -> None:
        self.name = name
        self.context = context
        self.parent_id = parent_id
        self.start_ns = time.time_ns()
        self.end_ns: Optional[int] = None
        self.attributes: Dict = dict(attributes) if attributes else {}
        self.links: List[SpanContext] = [
            l for l in links if l is not None
        ]
        self.error: Optional[str] = None

    def set_attribute(self, key: str, value) -> None:
        self.attributes[key] = value

    def add_link(self, ctx: Optional[SpanContext]) -> None:
        if ctx is not None:
            self.links.append(ctx)

    def duration_ms(self) -> float:
        end = self.end_ns if self.end_ns is not None else time.time_ns()
        return (end - self.start_ns) / 1e6

    def end(self, error: Optional[str] = None) -> None:
        if self.end_ns is not None:
            return
        self.end_ns = time.time_ns()
        if error is not None:
            self.error = error
        st = _state
        if st is not None:
            st.finish(self)

    def to_dict(self) -> Dict:
        """JSON-friendly form (breach dumps, smoke artifacts)."""
        return {
            "name": self.name,
            "trace_id": self.context.trace_id_hex(),
            "span_id": self.context.span_id_hex(),
            "parent_id": (
                "%016x" % self.parent_id
                if self.parent_id is not None else None
            ),
            "start_ns": self.start_ns,
            "duration_ms": round(self.duration_ms(), 3),
            "attributes": dict(self.attributes),
            "links": [
                {"trace_id": l.trace_id_hex(), "span_id": l.span_id_hex()}
                for l in self.links
            ],
            "error": self.error,
        }


class TracingStatus:
    """What `init_tracing` actually armed — the honest exporter status
    the old bool return hid (a set OTLP endpoint with the exporter
    packages missing used to report success while spans went nowhere).
    Truthy iff tracing is active, for old-style callers."""

    __slots__ = (
        "enabled", "service_name", "sampler", "ratio",
        "exporter", "exporter_error", "reason",
    )

    def __init__(self, enabled, service_name="", sampler="", ratio=1.0,
                 exporter="none", exporter_error=None, reason=""):
        self.enabled = enabled
        self.service_name = service_name
        self.sampler = sampler
        self.ratio = ratio
        # "otlp" | "memory" | "none" | an explicit exporter's class name
        self.exporter = exporter
        self.exporter_error = exporter_error
        self.reason = reason

    def __bool__(self) -> bool:
        return self.enabled

    def as_dict(self) -> Dict:
        return {
            "enabled": self.enabled,
            "service": self.service_name,
            "sampler": self.sampler,
            "ratio": self.ratio,
            "exporter": self.exporter,
            "exporter_error": self.exporter_error,
            "reason": self.reason,
        }


class _OTLPBridge:
    """Adapter from this module's spans to the OTel SDK's OTLP/HTTP
    exporter (the `[tracing]` extra).  Construction raises ImportError
    when the packages are absent — init_tracing reports that instead of
    pretending spans export."""

    def __init__(self, service_name: str) -> None:
        from opentelemetry import trace as otel_trace
        from opentelemetry.exporter.otlp.proto.http.trace_exporter import (
            OTLPSpanExporter,
        )
        from opentelemetry.sdk.resources import Resource
        from opentelemetry.sdk.trace import ReadableSpan
        from opentelemetry.sdk.trace.export import BatchSpanProcessor
        from opentelemetry.sdk.util.instrumentation import (
            InstrumentationScope,
        )

        self._otel_trace = otel_trace
        self._ReadableSpan = ReadableSpan
        self._resource = Resource.create({"service.name": service_name})
        self._scope = InstrumentationScope("gubernator_tpu")
        self._processor = BatchSpanProcessor(OTLPSpanExporter())

    def _ctx(self, trace_id: int, span_id: int):
        t = self._otel_trace
        return t.SpanContext(
            trace_id=trace_id, span_id=span_id, is_remote=False,
            trace_flags=t.TraceFlags(t.TraceFlags.SAMPLED),
        )

    def export(self, span: Span) -> None:
        t = self._otel_trace
        readable = self._ReadableSpan(
            name=span.name,
            context=self._ctx(span.context.trace_id, span.context.span_id),
            parent=(
                self._ctx(span.context.trace_id, span.parent_id)
                if span.parent_id is not None else None
            ),
            resource=self._resource,
            attributes=dict(span.attributes),
            events=(),
            links=[
                t.Link(self._ctx(l.trace_id, l.span_id))
                for l in span.links
            ],
            kind=t.SpanKind.INTERNAL,
            instrumentation_scope=self._scope,
            status=t.Status(
                t.StatusCode.ERROR if span.error else t.StatusCode.UNSET,
                span.error,
            ),
            start_time=span.start_ns,
            end_time=span.end_ns,
        )
        self._processor.on_end(readable)

    def shutdown(self) -> None:
        self._processor.shutdown()


class _TraceState:
    """Armed tracing plane: sampler + exporters + counters + the
    recent-span ring.  `_lock` guards only its own counters/deque and is
    never held across another lock (ranked last with flightrec._lock in
    tools/gubguard/lockorder.py)."""

    def __init__(self, service_name, sampler, ratio, exporters,
                 exporter_kind, exporter_error) -> None:
        self.service_name = service_name
        self.sampler = sampler
        self.ratio = ratio
        self.exporters = list(exporters)
        self.exporter_kind = exporter_kind
        self.exporter_error = exporter_error
        self._lock = threading.Lock()
        self.spans_started = 0
        self.spans_exported = 0
        self.spans_dropped = 0
        self.recent: deque = deque(maxlen=RECENT_SPAN_CAP)
        # 64-bit threshold for the traceidratio root decision.
        self._threshold = int(min(max(ratio, 0.0), 1.0) * (1 << 64))

    def sample_root(self, trace_id: int) -> bool:
        return (trace_id & ((1 << 64) - 1)) < self._threshold

    def note_started(self) -> None:
        with self._lock:
            self.spans_started += 1

    def finish(self, span: Span) -> None:
        with self._lock:
            self.recent.append(span)
        for exp in self.exporters:
            try:
                exp.export(span)
                with self._lock:
                    self.spans_exported += 1
            except Exception as e:  # noqa: BLE001 — never fail the caller
                with self._lock:
                    self.spans_dropped += 1
                log.debug("span export failed: %s", e)


_state: Optional[_TraceState] = None
_current: contextvars.ContextVar[Optional[SpanContext]] = (
    contextvars.ContextVar("gubernator_tpu_trace_ctx", default=None)
)
_CURRENT = object()  # sentinel: "resolve the parent from the contextvar"


def enabled() -> bool:
    """One global check — the hot path's whole cost when disabled."""
    return _state is not None


def current_context() -> Optional[SpanContext]:
    if _state is None:
        return None
    return _current.get()


def grpc_metadata():
    """Outbound w3c propagation: (("traceparent", ...),) for the current
    context, or None (no context / tracing disabled) — safe to pass
    straight to grpc's `metadata=` kwarg either way."""
    if _state is None:
        return None
    ctx = _current.get()
    if ctx is None:
        return None
    return (("traceparent", ctx.traceparent()),)


def _new_trace_id() -> int:
    tid = int.from_bytes(os.urandom(16), "big")
    return tid or 1


def _new_span_id() -> int:
    sid = int.from_bytes(os.urandom(8), "big")
    return sid or 1


def _begin(state, name, parent, links, attrs):
    """(span-or-None, child context).  A Span exists only when the
    context is sampled; an unsampled context still propagates so the
    decision stays consistent downstream and across peers."""
    span_id = _new_span_id()
    if parent is not None:
        trace_id = parent.trace_id
        sampled = parent.sampled
        parent_id = parent.span_id
    else:
        trace_id = _new_trace_id()
        sampled = state.sample_root(trace_id)
        parent_id = None
    ctx = SpanContext(trace_id, span_id, sampled)
    if not sampled:
        return None, ctx
    state.note_started()
    return Span(name, ctx, parent_id, attrs, links), ctx


def start_span(
    name: str,
    parent: Optional[SpanContext],
    links: Iterable[Optional[SpanContext]] = (),
    **attrs,
) -> Optional[Span]:
    """Manually managed span (caller must `end()` it) with an EXPLICIT
    parent — the form the cross-thread seams use (coalescer merges),
    where the submitting context was captured earlier.
    Returns None when tracing is disabled or the parent is unsampled."""
    st = _state
    if st is None or parent is None or not parent.sampled:
        return None
    sp, _ctx = _begin(
        st, name, parent, [l for l in links if l is not None], attrs
    )
    return sp


@contextlib.contextmanager
def span(
    name: str,
    parent=_CURRENT,
    links: Iterable[Optional[SpanContext]] = (),
    require_parent: bool = False,
    **attrs,
) -> Iterator[Optional[Span]]:
    """Span context manager; yields the Span (None when unsampled or
    disabled) and binds the child context for the duration so nested
    spans / flight-recorder records / outbound RPCs attribute to it.

    `parent` defaults to the current context; pass an explicit
    SpanContext to re-root (server-side traceparent extract, thread
    hand-offs).  `require_parent=True` makes the span a pure
    pass-through when no parent exists — internal pipeline stages use it
    so an untraced request never starts a spurious root trace."""
    st = _state
    if st is None:
        yield None
        return
    pa = _current.get() if parent is _CURRENT else parent
    if require_parent and pa is None:
        yield None
        return
    sp, ctx = _begin(
        st, name, pa, [l for l in links if l is not None], attrs
    )
    token = _current.set(ctx)
    try:
        yield sp
    except BaseException as e:
        if sp is not None:
            sp.end(error=repr(e))
        raise
    finally:
        _current.reset(token)
        if sp is not None:
            sp.end()


@contextlib.contextmanager
def use_context(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Bind an explicitly carried context on the current thread (pool
    workers) without opening a new span."""
    if _state is None or ctx is None:
        yield
        return
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


# -- lifecycle / introspection -------------------------------------------

def _resolve_sampler(sampler: Optional[str], sampler_arg) -> tuple:
    """(canonical sampler name, root ratio).  Parent-based behavior is
    structural here (children always inherit), so the parentbased_*
    spellings only choose the ROOT policy."""
    raw = (
        sampler
        or os.environ.get("OTEL_TRACES_SAMPLER")
        or "parentbased_always_on"
    ).strip().lower()
    canon = _SAMPLER_ALIASES.get(raw, raw)
    if canon == "always_on":
        return raw, 1.0
    if canon == "always_off_root":
        return raw, 0.0
    if canon == "always_off":
        return raw, 0.0
    if canon == "traceidratio":
        arg = sampler_arg
        if arg is None:
            arg = os.environ.get("OTEL_TRACES_SAMPLER_ARG", "1.0")
        try:
            ratio = float(arg)
        except (TypeError, ValueError):
            log.warning(
                "bad OTEL_TRACES_SAMPLER_ARG %r; sampling everything", arg
            )
            ratio = 1.0
        return raw, ratio
    log.warning("unknown OTEL_TRACES_SAMPLER %r; using always_on", raw)
    return raw, 1.0


def init_tracing(
    service_name: Optional[str] = None,
    exporter=None,
    sampler: Optional[str] = None,
    sampler_arg=None,
) -> TracingStatus:
    """Arm the tracing plane from the standard OTEL_* env spec
    (OTEL_SERVICE_NAME, OTEL_TRACES_SAMPLER[_ARG],
    OTEL_EXPORTER_OTLP_ENDPOINT) and/or an explicit exporter.

    Returns a TracingStatus with the REAL exporter state: a configured
    OTLP endpoint whose exporter packages are missing reports
    `exporter_error` (spans then stay in-process — recent-span ring +
    breach dumps — instead of silently vanishing).  Disabled outcomes
    (no OTEL_* configuration at all, or sampler `always_off`/`off`)
    leave the hot path span-free; the status says which."""
    global _state
    service_name = (
        service_name
        or os.environ.get("OTEL_SERVICE_NAME")
        or "gubernator-tpu"
    )
    sampler_name, ratio = _resolve_sampler(sampler, sampler_arg)
    endpoint = os.environ.get("OTEL_EXPORTER_OTLP_ENDPOINT", "")
    if _SAMPLER_ALIASES.get(sampler_name, sampler_name) == "always_off":
        _state = None
        return TracingStatus(
            False, service_name, sampler_name, 0.0,
            reason="sampler is off; tracing disabled",
        )
    opted_in = (
        exporter is not None
        or bool(endpoint)
        or sampler is not None
        or "OTEL_TRACES_SAMPLER" in os.environ
    )
    if not opted_in:
        _state = None
        return TracingStatus(
            False, service_name, sampler_name, ratio,
            reason=(
                "no OTEL_* configuration and no explicit exporter; "
                "tracing disabled"
            ),
        )
    exporters = []
    exporter_kind = "none"
    exporter_error = None
    if exporter is not None:
        exporters.append(exporter)
        exporter_kind = type(exporter).__name__
    if endpoint:
        try:
            exporters.append(_OTLPBridge(service_name))
            exporter_kind = "otlp"
        except Exception as e:  # noqa: BLE001 — ImportError et al.
            exporter_error = f"OTLP exporter unavailable: {e}"
            log.warning(
                "OTEL_EXPORTER_OTLP_ENDPOINT is set but the OTLP "
                "exporter packages are missing (`pip install "
                "gubernator-tpu[tracing]`); spans will NOT be exported "
                "— they stay in-process (recent-span ring, breach "
                "dumps) only: %s", e,
            )
    _state = _TraceState(
        service_name, sampler_name, ratio, exporters,
        exporter_kind, exporter_error,
    )
    return TracingStatus(
        True, service_name, sampler_name, ratio,
        exporter=exporter_kind, exporter_error=exporter_error,
    )


def shutdown_tracing() -> None:
    """Disarm (tests, daemon teardown): later spans are no-ops again."""
    global _state
    st = _state
    _state = None
    if st is not None:
        for exp in st.exporters:
            close = getattr(exp, "shutdown", None)
            if callable(close):
                try:
                    close()
                except Exception as e:  # noqa: BLE001
                    log.debug("exporter shutdown failed: %s", e)


def debug_vars() -> Dict:
    """The /debug/vars `tracing` block: enabled, sampler, exporter
    status, span counters."""
    st = _state
    if st is None:
        return {"enabled": False}
    with st._lock:
        started = st.spans_started
        exported = st.spans_exported
        dropped = st.spans_dropped
        recent = len(st.recent)
    return {
        "enabled": True,
        "service": st.service_name,
        "sampler": st.sampler,
        "ratio": st.ratio,
        "exporter": {
            "kind": st.exporter_kind,
            "error": st.exporter_error,
        },
        "spans": {
            "started": started,
            "exported": exported,
            "dropped": dropped,
            "recent": recent,
        },
    }


def recent_spans_for(
    trace_ids: Iterable[str], limit: int = 256
) -> List[Dict]:
    """Recently finished spans belonging to the given trace ids (hex
    strings) — the flight recorder attaches these to a breach dump so
    the dump carries the full in-process trace of the offending
    merge."""
    st = _state
    if st is None:
        return []
    want = set(trace_ids)
    if not want:
        return []
    with st._lock:
        spans = list(st.recent)
    out = [
        sp.to_dict() for sp in spans
        if sp.context.trace_id_hex() in want
    ]
    return out[-limit:]


# -- the stage ledger ------------------------------------------------------
#
# The vocabulary: `<layer>.<stage>`, each name placed once, where the
# work happens.  The profiler event is `gub.<layer>.<stage>`; the ledger
# key is (lane, stage) and /debug/vars renders `stages.<lane>.<stage>`
# with the layer dropped (the stage halves are unique).  `lane` is
# "wire" for the per-RPC stages, the coalescer lane's own name
# (mach / sketch / engine) for everything a drain does, "direct" for
# the object path and library callers, and the layer itself for the
# forward hop (peer) and the process-wide rows (global, xla).
STAGES: Dict[str, str] = {
    # per RPC, event loop
    "wire.rpc": "stats interceptor entry -> return, every unary method "
                "(feeds gubernator_grpc_request_duration)",
    "wire.handler": "raw GetRateLimits / GetPeerRateLimits handler "
                    "entry -> return",
    "wire.ingress": "handler entry -> the first coalescer enqueue "
                    "(eligibility, parse_reqs, validation, _prep_greg)",
    "wire.wake": "fut.set_result -> the handler coroutine resumes (twice "
                 "for a forward applied under an id: the lane's future, "
                 "then net/forward_once.py's)",
    "wire.egress": "resume -> return (captures, GLOBAL queueing, error "
                   "strings, serialize_resps)",
    "wire.peer_wait": "a routed RPC on its entry daemon: its own lanes "
                      "resumed (wire.wake's end; wire.ingress's end where "
                      "it owns none of its checks) -> the last forward's "
                      "answer is in place",
    "wire.empty": "state: no raw RPC between handler entry and return",
    "wire.occupied": "state: at least one raw RPC in the daemon",
    # per entry, event loop
    "lane.queue_wait": "do() put -> the entry's merge is handed to the "
                       "pool",
    "lane.in_drain": "merge handed to the pool -> fut.set_result",
    # per drain
    "lane.drain": "first entry dequeued -> results set (the whole the "
                  "per-drain stages divide)",
    "lane.slot_wait": "waiting for a fetch slot (the pipeline bubble)",
    "lane.dispatch_wait": "waiting for the dispatch slot",
    "lane.handoff": "run_in_executor submit -> first line on the pool "
                    "thread, dispatch and fetch stage each",
    "lane.resume": "last line on the pool thread -> the coalescer task "
                   "resumes on the loop, dispatch and fetch stage each",
    "lane.pack": "process() up to the device dispatch: concatenate, then "
                 "one native pass with the GIL released (cascade plan, "
                 "round/lane assignment, the rounds in the step's layout)",
    "lane.cascade": "inside a cascade merge's locked window: gather, "
                    "host replay, write-back rounds; counters groups "
                    "(duplicate groups replayed), occ (their occurrences), "
                    "peeks (of those, hits == 0), wb_lanes (lanes of the "
                    "write-back rounds the merge sent)",
    "lane.unpack": "one native gather with the GIL released + finish "
                   "(tallies, per-entry split) after the answer is on "
                   "the host; counter "
                   "new_windows (machinery lane: device read lanes "
                   "answered with found = 0)",
    "lane.dispatch_stage": "the coalescer's side of the dispatch stage "
                           "(feeds fastpath_stage_duration)",
    "lane.fetch_stage": "the coalescer's side of the fetch stage",
    "backend.lock_wait": "blocked on backend._lock / engine._lock",
    "backend.dispatch": "the enqueue of each round's program, packed "
                        "already (compiled lane) or packed here (feeds "
                        "gubernator_tpu_device_step_duration); counters "
                        "launches (step programs enqueued), lanes (the "
                        "compiled width of each, summed, x shards on the "
                        "mesh) and tier_<width> (launches a rung of the "
                        "compiled widths)",
    "backend.d2h_wait": "fetch_ravel: blocked until the answer is on "
                        "the host",
    # the forward hop, on the entry daemon's loop (lane `peer`)
    "peer.route": "once a routed RPC: the ring lookup, the owner masks, "
                  "the per-owner index sets",
    "peer.splice": "per client RPC and remote owner: its checks' frames "
                   "joined from the request's own bytes",
    "peer.batch_wait": "per client RPC and remote owner: its checks handed "
                       "to the peer batcher -> the GetPeerRateLimits that "
                       "carries them is sent (the GUBER_BATCH_WAIT window, "
                       "or less where GUBER_BATCH_LIMIT sent it first)",
    "peer.forward": "per GetPeerRateLimits, which carries what concurrent "
                    "client RPCs send one owner: send -> raw answer (the "
                    "readiness gate, the RPC, the owner's whole handler, "
                    "every re-ask); counters checks (checks sent), batched "
                    "(client RPCs' forwards that shared their "
                    "GetPeerRateLimits with another's), flush_wait / "
                    "flush_limit (batches the window's end / the limit "
                    "sent), and by event: timeouts "
                    "(an ask that ended DEADLINE_EXCEEDED), reasked (the "
                    "same forward asked again under its id), joined (OWNER "
                    "side: an arrival that found its id applied or in "
                    "progress and took that answer), retried (handed to "
                    "the object path's ownership-retry loop), refused "
                    "(answered with an error, or a wrong response count)",
    "peer.assemble": "per client RPC and remote owner: its slice of the "
                     "answer's columns, and the per-check copy of errors "
                     "and the owner's metadata frame",
    # per tick / process
    "global.sync_tick": "one GLOBAL psum sync: staging, dispatch, "
                        "write-through read-back; counters keys (pending "
                        "keys flushed, a lane each), chunks",
    "global.build_chunks": "inside a tick, before the locks: the pending "
                           "dict packed into delta grids and their "
                           "host->device puts",
    "global.wait_locks": "inside a tick: blocked on backend._lock, then "
                         "engine._lock (the serve path holds them)",
    "global.sync_step": "inside a tick, under both locks: the enqueue of "
                        "one chunk's sync program",
    "xla.compile": "backend compiles seen by jax.monitoring (count, ms)",
    # the two-tier table (lane `tier`; runtime/coldtier.py)
    "tier.note_access": "TierManager.note_access, on the request path "
                        "inside service.note_traffic: the sketch update "
                        "and one probe of the batch in the cold store; "
                        "counters keys, cold_hits",
    "tier.promote": "one pass of the promote worker: everything queued "
                    "popped from the cold store and merged into the "
                    "table; counters rows_popped and, of every inject "
                    "launch (the demoter's hotter tail too), "
                    "inject_launches, inject_lanes, rows_injected (rows "
                    "carried), rows_merged (met a resident row); its "
                    "parts in microseconds pop_us, dispatch_us, fetch_us",
    "tier.demote": "one watermark tick over the high mark: select and "
                   "extract on the device, the fetch, the put into the "
                   "cold store; counters demote_rows, demote_launches, "
                   "demote_lanes, reinjected, renoted, cold_merges, "
                   "ticks_late, select_us, put_us",
    "tier.lock": "backend._lock held by one of the tier's dispatches "
                 "(migrate_inject, demote_extract): what the served "
                 "path waits",
    "tier.restore": "ColdTier.restore: a checkpoint's (or a preload's) "
                    "cold rows re-inserted; counter rows",
    # what shares the process with the served path (lane `host`)
    "host.gc": "one collection of the garbage collector, gc.callbacks "
               "start -> stop, on whichever thread collected; counter "
               "gen2 (full collections: the long pauses)",
    "host.census_dispatch": "the gubstat census, an executor thread: "
                            "table_stats_dispatch entry -> return (the wait "
                            "for backend._lock and the enqueue under it)",
    "host.census_fetch": "the census: the executor's wait for the result "
                         "on the host",
    "host.hotkey": "service.note_traffic -> hotkey.observe, on the loop "
                   "inside wire.ingress; counters keys (fingerprints "
                   "handed to it), native (calls whose sketch update "
                   "was the one native pass)",
    "host.scrape": "the /metrics and /debug/vars handlers, entry -> return, "
                   "on the loop",
    "host.loop_lag": "the daemon's heartbeat: how late a sleep of "
                     "LOOP_LAG_INTERVAL_S wakes (feeds "
                     "gubernator_event_loop_lag_seconds)",
    "host.stall": "the instances of leaf rows kept in the stalls ring "
                  "(count, and their wall)",
}

# A LEAF has both ends on one thread (or is the heartbeat's lag): only
# its instances can be stalls.  Every other stage is a wait across an
# await or a thread hand-off, or holds leaves across them (wire.handler,
# lane.drain), and a stall inside it is its leaf's.  (host.hotkey runs
# inside wire.ingress, host.gc inside whatever allocated: a stall there
# is an entry of both.)
LEAVES = frozenset((
    "wire.ingress", "wire.egress",
    "lane.pack", "lane.cascade", "lane.unpack",
    "backend.lock_wait", "backend.dispatch", "backend.d2h_wait",
    "peer.route", "peer.splice", "peer.assemble",
    "global.sync_tick", "global.build_chunks", "global.wait_locks",
    "global.sync_step",
    "host.gc", "host.census_dispatch", "host.census_fetch", "host.hotkey",
    "host.scrape", "host.loop_lag",
    "tier.note_access", "tier.lock",
))
# An instance of a leaf row that took at least STALL_MIN_NS and
# STALL_FACTOR x its row's mean so far is a stall: kept, with its times,
# in a ring of STALL_RING.
STALL_MIN_NS = 20_000_000
STALL_FACTOR = 8
STALL_RING = 256
LOOP_LAG_INTERVAL_S = 0.25

# What a daemon's raw handlers and a coalescer lane time: the rows they
# create at start-up (StageLedger.register).
WIRE_STAGES = tuple(
    s for s in STAGES if s.startswith("wire.") and s != "wire.wake"
)
LANE_STAGES = tuple(
    s for s in STAGES if s.startswith(("lane.", "backend."))
)
# The forward hop's rows (lane `peer`) and its counters: at zero on every
# daemon from start-up, whether or not it ever routes.
PEER_STAGES = tuple(s for s in STAGES if s.startswith("peer."))
PEER_FORWARD_COUNTERS = (
    "checks", "batched", "flush_wait", "flush_limit",
    "timeouts", "reasked", "joined", "retried", "refused",
)
# Lane `host`'s rows, at zero from a daemon's start-up (Metrics);
# host.gc and host.stall are the process's (_GC, _STALL below).
HOST_STAGES = tuple(
    s for s in STAGES
    if s.startswith("host.") and s not in ("host.gc", "host.stall")
)

_TRACE_ME = None  # jax.profiler.TraceAnnotation, resolved on first use
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Cell:
    """One (lane, stage) row: plain integers, written under the owning
    ledger's leaf lock (two fetch stages of one lane may end the same
    stage on two pool threads)."""

    __slots__ = ("lane", "stage", "trace_name", "count", "ns_total",
                 "ns_max", "max_at_ns", "leaf", "observe", "counters")

    def __init__(self, lane: str, stage: str, observe=None) -> None:
        if stage not in STAGES:
            raise KeyError(f"stage {stage!r} is not in tracing.STAGES")
        self.lane = lane
        self.stage = stage
        self.trace_name = "gub." + stage
        self.count = 0
        self.ns_total = 0
        self.ns_max = 0
        self.max_at_ns = 0  # epoch: the end of the longest instance
        self.leaf = stage in LEAVES
        self.observe = observe
        # Named whole-number counters of what the stage worked on (keys
        # a tick flushed, checks a drain packed): rendered beside count.
        self.counters: Dict[str, int] = {}

    def add(self, ns: int) -> bool:
        """Count one instance; True where it is a stall."""
        n, total = self.count, self.ns_total
        self.count = n + 1
        self.ns_total = total + ns
        if ns > self.ns_max:
            self.ns_max = ns
            self.max_at_ns = time.time_ns()
        return (
            ns >= STALL_MIN_NS and self.leaf
            and 0 < STALL_FACTOR * total <= ns * n
        )

    def row(self) -> Dict:
        """The row as /debug/vars renders it."""
        out = {
            "count": self.count,
            "ms_total": round(self.ns_total / 1e6, 6),
            "ms_max": round(self.ns_max / 1e6, 6),
            "max_at_ms": self.max_at_ns // 1_000_000,
        }
        out.update(self.counters)
        return out


# A row's own keys
_RESERVED = frozenset(("count", "ms_total", "ms_max", "max_at_ms"))
_COMPILES = _Cell("xla", "xla.compile")  # process-wide, lock-free reads


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _COMPILES.add(int(duration_secs * 1e9))


# The collector is the process's, and a collection can start on any
# thread at any moment, also inside a ledger's `_lock`, which is not
# re-entrant: its row is a cell of its own, written without that lock
# (collections do not overlap), and so are the stalls — the row
# host.stall and the ring — which a collection has to reach too.  Every
# ledger renders them, as it does _COMPILES.
_GC = _Cell("host", "host.gc")
_GC.counters["gen2"] = 0
_STALL = _Cell("host", "host.stall")
_STALLS: deque = deque(maxlen=STALL_RING)
_gc_open: Optional[Tuple] = None  # (TraceMe, wall ns)


def _note_stall(cell: _Cell, ns: int) -> None:
    """One entry of the stalls ring, taken as the instance ends."""
    end_ms = time.time_ns() / 1e6
    _STALL.add(ns)
    _STALLS.append({
        "t_start_ms": round(end_ms - ns / 1e6, 3),
        "t_end_ms": round(end_ms, 3),
        "lane": cell.lane,
        "stage": cell.stage.split(".", 1)[1],
        "ms": round(ns / 1e6, 3),
        "thread": threading.current_thread().name,
    })


def stalls() -> List[Dict]:
    """The /debug/vars `stalls` block: the process's last STALL_RING
    instances of leaf rows that took STALL_MIN_NS and STALL_FACTOR x
    their row's mean so far — (t_start_ms, t_end_ms) on the epoch clock,
    lane, stage, ms and the thread — oldest first.  Overlapping them by
    time says what a stall shared the process with."""
    return list(_STALLS)


def _on_gc(phase: str, info: Dict) -> None:
    global _gc_open
    if phase == "start":
        tm = _TRACE_ME(_GC.trace_name)
        tm.__enter__()
        _gc_open = (tm, time.perf_counter_ns())
    elif _gc_open is not None:
        tm, t0 = _gc_open
        _gc_open = None
        ns = time.perf_counter_ns() - t0
        tm.__exit__(None, None, None)
        if info["generation"] == 2:
            _GC.counters["gen2"] += 1
        if _GC.add(ns):
            _note_stall(_GC, ns)


def _trace_me():
    """The profiler's TraceMe class, imported the first time a stage is
    timed (a process that times none — the load generator — never
    imports JAX); the compile listener and the collector's callback are
    installed with it."""
    global _TRACE_ME
    if _TRACE_ME is None:
        import jax.monitoring
        from jax.profiler import TraceAnnotation

        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration
        )
        _TRACE_ME = TraceAnnotation
        gc.callbacks.append(_on_gc)
    return _TRACE_ME


class _Open:
    """One open stage.  `with ledger.stage(...)` for a section on one
    thread; `tok = ledger.begin(...)` ... `tok.end()` for a wait that
    spans an await or a thread hand-off (the TraceMe records
    (name, start, end) on the thread that ends it).  `end` is
    idempotent and returns the duration in ns."""

    __slots__ = ("_ledger", "_cell", "_t0", "_tm", "_span", "_token")

    def __init__(self, ledger, cell, parent, bind, anchor) -> None:
        self._ledger = ledger
        self._cell = cell
        self._span = None
        self._token = None
        st = _state
        if st is not None:
            if bind:
                parent = _current.get()
            if parent is not None:
                self._span, ctx = _begin(
                    st, cell.trace_name, parent, [], {"lane": cell.lane}
                )
                if bind:
                    self._token = _current.set(ctx)
        if anchor:
            # The clock anchor: the program's epoch clock at the
            # event's start, to be laid against the profiler's stamp.
            tm = (_TRACE_ME or _trace_me())(
                cell.trace_name, t_ns=time.time_ns()
            )
        else:
            tm = (_TRACE_ME or _trace_me())(cell.trace_name)
        tm.__enter__()
        self._tm = tm
        self._t0 = time.perf_counter_ns()

    @property
    def context(self) -> Optional[SpanContext]:
        """The stage's span context when it has a span (armed plane,
        sampled parent), for parenting what runs inside it elsewhere."""
        return self._span.context if self._span is not None else None

    def tally(self, **counts: int) -> None:
        """Add to the stage's named counters (what this pass worked on);
        a no-op once the stage has ended."""
        if self._cell is not None:
            self._ledger._tally(self._cell, counts)

    def end(self, error: Optional[str] = None) -> int:
        cell = self._cell
        if cell is None:
            return 0
        dt = time.perf_counter_ns() - self._t0
        self._cell = None
        self._tm.__exit__(None, None, None)
        if self._token is not None:
            _current.reset(self._token)
        if self._span is not None:
            self._span.end(error)
        self._ledger._add(cell, dt)
        return dt

    def __enter__(self) -> "_Open":
        return self

    def __exit__(self, _et, ev, _tb) -> None:
        self.end(repr(ev) if ev is not None else None)


class StageLedger:
    """count / ns_total / ns_max per (lane, stage), always on.  One per
    daemon (`Metrics.stages`); a component built without metrics gets
    its own.  `_lock` is a leaf: held for three integer adds, never
    across another lock (tools/gubguard/lockorder.py)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cells: Dict[Tuple[str, str], _Cell] = {}
        self._observers: Dict[Tuple[Optional[str], str], Callable] = {}
        # wire.empty / wire.occupied: raw RPCs between handler entry and
        # return.  Event-loop thread only; the clock starts with the
        # first RPC.
        self._rpcs = 0
        self._since: Optional[int] = None
        self._empty_tm = None
        if "jax" in sys.modules:
            # A daemon's ledger: count its start-up compiles too.  (A
            # process that has not imported JAX is not made to.)
            _trace_me()

    # -- registration ------------------------------------------------------
    def observe(self, stage: str, fn: Callable[[float], None],
                lane: Optional[str] = None) -> None:
        """Feed `fn(seconds)` from every end of `stage` (on `lane`, or
        on every lane): how a Prometheus series stays a view of the
        ledger instead of a second measurement."""
        if stage not in STAGES:
            raise KeyError(f"stage {stage!r} is not in tracing.STAGES")
        self._observers[(lane, stage)] = fn
        with self._lock:
            for (ln, st), cell in self._cells.items():
                if st == stage and lane in (None, ln):
                    cell.observe = fn

    def register(self, lane: str, stages: Iterable[str]) -> None:
        """Create rows ahead of their first use, so that /debug/vars
        shows a lane's whole vocabulary (at zero) from the start."""
        for stage in stages:
            self.cell(lane, stage)

    def declare(self, lane: str, stage: str, *counters: str) -> None:
        """Create named counters of a row at zero ahead of their first
        count: a reader that divides by or into them (the benchmark's
        ratio metrics) finds a number from the start, not nothing."""
        self._tally(self.cell(lane, stage), dict.fromkeys(counters, 0))

    def tally(self, lane: str, stage: str, **counts: int) -> None:
        """Add to a row's named counters where no stage is open."""
        self._tally(self.cell(lane, stage), counts)

    def cell(self, lane: str, stage: str) -> _Cell:
        c = self._cells.get((lane, stage))
        if c is None:
            fn = self._observers.get((lane, stage)) or self._observers.get(
                (None, stage)
            )
            with self._lock:
                c = self._cells.setdefault(
                    (lane, stage), _Cell(lane, stage, fn)
                )
        return c

    def _add(self, cell: _Cell, ns: int) -> None:
        with self._lock:
            stall = cell.add(ns)
        if stall:
            _note_stall(cell, ns)
        if cell.observe is not None:
            cell.observe(ns / 1e9)

    def _tally(self, cell: _Cell, counts: Dict[str, int]) -> None:
        if not _RESERVED.isdisjoint(counts):
            raise KeyError(f"a counter may not be named {sorted(_RESERVED)}")
        with self._lock:
            for name, n in counts.items():
                cell.counters[name] = cell.counters.get(name, 0) + int(n)

    # -- the primitive -----------------------------------------------------
    def stage(self, stage: str, lane: Optional[str] = None,
              anchor: bool = False) -> _Open:
        """A synchronous section on one thread (`with`).  Armed span
        plane: a child span of the current context, bound for the
        section so nested spans and flight-recorder records attribute
        to it."""
        if lane is None:
            lane = _scope.get()[1]
        return _Open(self, self.cell(lane, stage), None, True, anchor)

    def begin(self, stage: str, lane: Optional[str] = None,
              parent: Optional[SpanContext] = None) -> _Open:
        """The begin of a begin-end pair; `parent` is the explicitly
        carried span context (nothing is bound: the wait crosses tasks
        or threads)."""
        if lane is None:
            lane = _scope.get()[1]
        return _Open(self, self.cell(lane, stage), parent, False, False)

    async def heartbeat(self) -> None:
        """host.loop_lag: how late a sleep of LOOP_LAG_INTERVAL_S wakes.
        Any callback that holds the loop delays the wake-up by as long,
        so a sample is a lower bound on the worst stall of its interval.
        One task a daemon, always on (Daemon.start)."""
        cell = self.cell("host", "host.loop_lag")
        due = int(LOOP_LAG_INTERVAL_S * 1e9)
        while True:
            t0 = time.perf_counter_ns()
            await asyncio.sleep(LOOP_LAG_INTERVAL_S)
            self._add(cell, max(0, time.perf_counter_ns() - t0 - due))

    # -- the wire.empty / wire.occupied state clock --------------------------
    def rpc_enter(self) -> None:
        if self._rpcs == 0:
            now = time.perf_counter_ns()
            if self._since is not None:
                self._add(self.cell("wire", "wire.empty"), now - self._since)
                self._empty_tm.__exit__(None, None, None)
            self._since = now
        self._rpcs += 1

    def rpc_exit(self) -> None:
        self._rpcs -= 1
        if self._rpcs == 0:
            now = time.perf_counter_ns()
            self._add(self.cell("wire", "wire.occupied"), now - self._since)
            self._since = now
            self._empty_tm = (_TRACE_ME or _trace_me())("gub.wire.empty")
            self._empty_tm.__enter__()

    # -- rendering -----------------------------------------------------------
    def totals(self, lane: str, stage: str) -> Tuple[int, int, int]:
        """(count, ns_total, ns_max) of one row; zeros if never timed."""
        c = self._cells.get((lane, stage))
        if c is None:
            return 0, 0, 0
        with self._lock:
            return c.count, c.ns_total, c.ns_max

    def debug_vars(self) -> Dict:
        """The /debug/vars `stages` block:
        stages.<lane>.<stage>.{count, ms_total, ms_max, max_at_ms} and
        the stage's named counters.  The open empty/occupied interval is
        counted up to now."""
        with self._lock:
            rows = {
                (c.lane, c.stage): c.row() for c in self._cells.values()
            }
        for c in (_COMPILES, _GC, _STALL):
            rows[c.lane, c.stage] = c.row()
        since, rpcs = self._since, self._rpcs
        if since is not None:
            state = "wire.occupied" if rpcs else "wire.empty"
            open_ms = max(0, time.perf_counter_ns() - since) / 1e6
            row = rows.setdefault(
                ("wire", state), _Cell("wire", state).row()
            )
            row["ms_total"] = round(row["ms_total"] + open_ms, 6)
            if open_ms > row["ms_max"]:
                row["ms_max"] = round(open_ms, 6)
                row["max_at_ms"] = time.time_ns() // 1_000_000
        out: Dict[str, Dict] = {}
        for (lane, stage), row in rows.items():
            out.setdefault(lane, {})[stage.split(".", 1)[1]] = row
        return out


# Who had the CPU.  A thread's CPU clock is read from outside it by the
# kernel's id for that clock — (~tid << 3) | 6, what pthread_getcpuclockid
# returns for a live thread (tests/test_stages.py) — computed from
# Thread.native_id: a thread that has ended makes clock_gettime fail
# cleanly, where pthread_getcpuclockid would read its freed handle.
_THREAD_CPU_NS: Dict[int, Tuple[str, int]] = {}  # live: tid -> name, ns
_ENDED_CPU_NS: Dict[str, int] = {}  # name -> CPU of its ended threads
_FAMILY = re.compile(r"[A-Za-z0-9]+(?:-[A-Za-z]+)?")


def thread_vars() -> Dict[str, Dict[str, Dict[str, float]]]:
    """The /debug/vars `threads` block:
    threads.<family>.<name>.cpu_ms of every Python thread of the
    process, read at render from the threads' own CPU clocks; nothing on
    the hot path.  A family is the first two words of a name: the
    coalescer's pools are `tpu-fastlane` (`tpu-fastlane_0`,
    `tpu-fastlane-engine_0`), the executor's threads `asyncio`, the loop
    `MainThread`.  Over a window the block's sum ÷ wall near 1.0 says
    the GIL is full; `tpu-fastlane`'s ÷ the wall of the sections its
    threads ran says how much of that wall they were running.  A thread
    that has ended keeps its last reading under its name, so no sum
    falls.  Empty off Linux."""
    if not sys.platform.startswith("linux"):
        return {}
    seen = {}
    for t in threading.enumerate():
        tid = t.native_id
        if tid is None:  # not started yet
            continue
        try:
            seen[tid] = (t.name, time.clock_gettime_ns((~tid << 3) | 6))
        except OSError:  # ended since enumerate()
            continue
    for tid, (name, was) in list(_THREAD_CPU_NS.items()):
        now = seen.get(tid)
        # Ended: gone, or its clock has restarted (the id serves a new
        # thread already).
        if now is None or now[1] < was:
            if _THREAD_CPU_NS.pop(tid, None) is not None:
                _ENDED_CPU_NS[name] = _ENDED_CPU_NS.get(name, 0) + was
    _THREAD_CPU_NS.update(seen)
    total = dict(_ENDED_CPU_NS)
    for name, ns in list(_THREAD_CPU_NS.values()):
        total[name] = total.get(name, 0) + ns
    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for name, ns in total.items():
        m = _FAMILY.match(name)
        family = m.group(0) if m else name
        out.setdefault(family, {})[name] = {"cpu_ms": round(ns / 1e6, 6)}
    return out


def process_vars() -> Dict[str, float]:
    """The /debug/vars `process` block: the whole process's CPU time, the
    XLA runtime's and gRPC's own threads included; less the `threads`
    block's sum it is what the runtimes burn beside Python."""
    return {"cpu_ms": round(time.process_time_ns() / 1e6, 6)}


# The ambient (ledger, lane): what a stage placed in code that serves
# many callers (backend dispatch, fetch_ravel) is charged to.  A
# coalescer binds its own on the pool thread for each stage it runs.
PROCESS_LEDGER = StageLedger()
_scope: contextvars.ContextVar[Tuple[StageLedger, str]] = (
    contextvars.ContextVar(
        "gubernator_tpu_stage_scope", default=(PROCESS_LEDGER, "direct")
    )
)


def ledger_of(metrics) -> StageLedger:
    """A daemon's ledger (`Metrics.stages`); the process's for a
    component built without metrics."""
    return getattr(metrics, "stages", None) or PROCESS_LEDGER


@contextlib.contextmanager
def scope(ledger: StageLedger, lane: str) -> Iterator[None]:
    token = _scope.set((ledger, lane))
    try:
        yield
    finally:
        _scope.reset(token)


def stage(stage_name: str, lane: Optional[str] = None,
          anchor: bool = False) -> _Open:
    """`StageLedger.stage` on the ambient ledger."""
    return _scope.get()[0].stage(stage_name, lane, anchor)


def begin(stage_name: str, lane: Optional[str] = None,
          parent: Optional[SpanContext] = None) -> _Open:
    """`StageLedger.begin` on the ambient ledger."""
    return _scope.get()[0].begin(stage_name, lane, parent)
