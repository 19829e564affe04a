"""The compiled host hot path: raw wire bytes -> device step -> wire bytes.

The object path (pb2 -> RateLimitReq dataclasses -> packer -> device ->
RateLimitResp -> pb2) costs several microseconds of Python per request,
which caps a daemon at ~10k checks/s while the device kernel does hundreds
of millions — the round-2 verdict's top gap.  The reference has no such
tax: its whole host loop is compiled Go (workers.go:249-314,
peer_client.go:450-509, generated pb marshalers).

This module is the equivalent compiled lane.  For eligible requests the
daemon hands the raw gRPC payload straight here:

    C++ parse  (native/gubtpu.cpp gub_parse_reqs2: wire -> columns + XXH64)
    numpy      (the coalesced RPCs' columns concatenated)
    C++ pack   (gub_pack_rounds, ONE call a drain with the GIL released:
                burst defaults, the RESET_REMAINING bit, shard routing,
                the duplicate-key round/lane assignment — a drain takes
                the host cascade instead only where that saves a device
                launch: a key three times in a drain of one round does,
                one pair among 5,000 checks rides the two rounds the drain
                has anyway — and the rounds written once, in the layout
                the step program takes, int64[12, tier] each)
    device     (backend.step_rounds_begin: the same jitted kernels as
                check(); sketch-named lanes take one CMS step instead)
    C++ unpack (gub_gather_rounds, ONE call: the fetched responses back to
                request order, with the sums the tallies take)
    C++ emit   (gub_serialize_resps2: columns -> response wire bytes)

_plan_cascade, _read_lanes, _cascade_or_rounds and _build_rounds below are
the pack as numpy made it until PR 42, put together by _reference_pack
(_reference_unpack: the gather, the sums and the cap_ok loop): the plain
reference that tests/test_pack_native.py holds the native pass to, bit for
bit, and scripts/pack_bench.py times it against.  No served path calls
them.

No per-request Python objects exist anywhere on this path.  Concurrent
RPCs coalesce into shared device steps (the LocalBatcher discipline,
runtime/service.py) by concatenating their columns before packing.

Eligibility — anything else falls back to the object path, which remains
the semantic reference:
  - native library loadable;
  - a Store / Loader attached stays ON the lane: residency comes from
    the step's own `found` column (no pre-step probe fetch — a warm
    drain pays ONE combined response+capture fetch, storeless parity),
    Store.get runs only for cold keys, whose drains repair in place
    (_repair_cold_store_keys), and write-through rows are captured
    with ONE packed device gather (ticketed on_change delivery, like
    the object path's batch-boundary fix).
    The SPI itself takes Python objects, so the lane decodes one
    request per UNIQUE key per drain — the only per-key host cost;
    on_change fires once per unique key per DRAIN (coalesced RPCs
    share one delivery; final store state matches the object path);
  - GLOBAL is served HERE — use_cached lanes for non-owned reads,
    queued hits/updates for the managers, and node-owned lanes on a
    mesh service ingesting into the collective GlobalEngine's
    replicated table (client path; the peer RPC keeps RPC-tier
    semantics like _check_local); MULTI_REGION serves like a plain
    lane with owner-side hits queued to the region manager (one
    decode per unique key);
  - sketch-tier names are served HERE too: the parser's name_hash
    column routes them to SketchBackend.check_cols (one CMS step per
    merge), with GLOBAL stripped exactly like the object path's
    routing (service.py) so they count once at the key's owner;
  - for the client-facing RPC: either single-node, or the columnar
    router (vectorized ring lookup + zero-copy forwards) when the ring
    hash matches the device fingerprint hash.
    Peer-to-peer batches (GetPeerRateLimits) are always local by
    construction, so the fast lane also serves the owner side of
    forwarded traffic in a cluster.
"""
from __future__ import annotations

import asyncio
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from gubernator_tpu import native
from gubernator_tpu.core.config import MAX_BATCH_SIZE
from gubernator_tpu.runtime import tracing
from gubernator_tpu.core.interval import (
    GregorianError,
    gregorian_duration,
    gregorian_expiration,
)
from gubernator_tpu.core.types import Behavior
from gubernator_tpu.ops.batch import DeviceBatch, _empty_batch

_ERR_EMPTY_KEY = b"field 'unique_key' cannot be empty"
_ERR_EMPTY_NAME = b"field 'namespace' cannot be empty"
_ERR_GREG = 3  # parse err code for host-side Gregorian failures

_GLOBAL = int(Behavior.GLOBAL)
_MULTI_REGION = int(Behavior.MULTI_REGION)
_NO_BATCHING = int(Behavior.NO_BATCHING)

# The sketch tier's response annotation (object path: metadata
# {"tier": "sketch"}, runtime/sketch_backend.py).
_TIER_SKETCH_FRAME = native.meta_frame(b"tier", b"sketch")


class _Coalescer:
    """The drain discipline shared by the machinery, sketch, and engine
    lanes: arrivals accumulate in the queue; each drain takes the WHOLE
    queue as one merge (bigger merges amortize the per-merge device
    round-trip).  `process` runs on a pool thread with the drained entry
    list; results deliver through each entry's future.

    Two-stage pipeline (the r5 E2E artifact showed the device->host
    response fetch dominating the merge cycle while the old discipline
    serialized it behind the next merge's dispatch):

      dispatch stage — serialized (`max_inflight`, default 1).  `process`
        packs and dispatches the device step (holding the backend lock)
        and returns a zero-arg FETCH CONTINUATION instead of results.
        The table-update chain already serializes correctly on the XLA
        stream, so merge N+1 may dispatch the moment merge N's dispatch
        returns.
      fetch stage — depth-`pipeline_depth`.  The
        continuation syncs the response to host and unmarshals; out-of-
        order completion is safe because results flow through per-entry
        futures.  A fetch SLOT is taken before dispatching, so at most
        `pipeline_depth` merges are outstanding end-to-end; the time a
        ready drain spends waiting for a slot is the pipeline's bubble
        (tracked in `bubble_s` + the bubble metrics).

    Steady-state throughput moves from B/(dispatch+fetch) toward
    B/max(dispatch, fetch).  Maximal merges are preserved — this
    pipelines ACROSS merges, it never splits one (the r5 A/B pinned
    monotone 1>2>3>4>6 for splitting).  `process` may also return a
    plain result list (single-phase; the fetch stage is then a no-op) —
    tests and simple lanes use that form.

    Adaptive sparse overlap (`sparse_limit` > 0) is the depth-k special
    case of the same mechanism: a drain no bigger than `sparse_limit`
    requests that finds every base fetch slot busy may take one of
    OVERLAP_SLOTS sparse fetch slots instead of waiting — at low load a
    small arrival then costs ~1 device round-trip even when the pipeline
    is full (r5: small-batch p50 156 -> 86ms; the reference's batcher
    fires its window early when sparse, peer_client.go:373-446).  Under
    load drains exceed the limit and the maximal-merge discipline holds.
    """

    OVERLAP_SLOTS = 3

    def __init__(self, pool, process, max_inflight: int = 1,
                 sparse_limit: int = 0, size_of=None,
                 pipeline_depth: int = 1, metrics=None,
                 lane: str = "") -> None:
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self._pool = pool
        self._process = process
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self._dispatch_sem = asyncio.Semaphore(max_inflight)
        self._fetch = asyncio.Semaphore(pipeline_depth)
        self._overlap = asyncio.Semaphore(self.OVERLAP_SLOTS)
        self._sparse_limit = sparse_limit
        self._size_of = size_of or (lambda e: 1)
        self._dispatches: set = set()
        self._closed = False
        self.pipeline_depth = pipeline_depth
        self._metrics = metrics
        self._lane = lane
        # The stage ledger (runtime/tracing.py) times every step of a
        # drain; the daemon's when there are metrics, a private one
        # otherwise.  The stage/bubble series and the flight recorder's
        # bubble records are views it feeds.
        self._stages = st = (
            getattr(metrics, "stages", None) or tracing.StageLedger()
        )
        st.register(lane, tracing.LANE_STAGES)
        st.register("wire", ("wire.wake",))
        if metrics is not None:
            hist = metrics.fastpath_stage_duration
            st.observe(
                "lane.dispatch_stage",
                hist.labels(lane=lane, stage="dispatch").observe, lane,
            )
            st.observe(
                "lane.fetch_stage",
                hist.labels(lane=lane, stage="fetch").observe, lane,
            )
            st.observe("lane.slot_wait", self._on_bubble, lane)
        # Per entry: the open wait (queue_wait, then in_drain, then
        # wake), by id(entry) — entry types are the callers' own.
        self._waits: Dict[int, object] = {}
        # Observability: total drains / drains that rode a sparse fetch
        # slot / drains that had to wait for a fetch slot (each wait is
        # one pipeline bubble: the ledger's lane.slot_wait).
        self.drains = 0
        self.overlap_drains = 0
        self.waited_drains = 0
        # Merges currently in flight (dispatch or fetch stage) and the
        # peak ever observed — the pipeline-occupancy view.
        self.inflight = 0
        self.max_inflight_seen = 0

    def _stage_s(self, stage: str) -> float:
        return self._stages.totals(self._lane, stage)[1] / 1e9

    # Cumulative wall time of the pipeline bubble and of the two stages
    # from the coalescer's side of run_in_executor: the ledger's rows.
    bubble_s = property(lambda self: self._stage_s("lane.slot_wait"))
    dispatch_s = property(
        lambda self: self._stage_s("lane.dispatch_stage")
    )
    fetch_s = property(lambda self: self._stage_s("lane.fetch_stage"))

    def debug_vars(self) -> dict:
        """The /debug/vars view of this lane's drain discipline."""
        return {
            "drains": self.drains,
            "overlap_drains": self.overlap_drains,
            "waited_drains": self.waited_drains,
            "bubble_ms_total": round(self.bubble_s * 1e3, 3),
            "dispatch_ms_total": round(self.dispatch_s * 1e3, 3),
            "fetch_ms_total": round(self.fetch_s * 1e3, 3),
            "max_inflight_seen": self.max_inflight_seen,
            "pipeline_depth": self.pipeline_depth,
        }

    def _count_drain(self, kind: str) -> None:
        m = self._metrics
        if m is not None:
            m.fastpath_drains.labels(lane=self._lane, kind=kind).inc()

    def _on_bubble(self, dt_s: float) -> None:
        """The ledger's lane.slot_wait, as the bubble counter and a
        flight-recorder record."""
        m = self._metrics
        m.fastpath_bubble_seconds.labels(lane=self._lane).inc(dt_s)
        fr = getattr(m, "flightrec", None)
        if fr is not None:
            fr.record_bubble(self._lane, dt_s * 1e3)

    async def do(self, entry, ingress=None):
        """Submit an entry and await its result.  `ingress` is the
        caller's open wire.ingress stage, ended at the enqueue."""
        if self._closed:
            raise RuntimeError("fastpath closed")
        entry.fut = asyncio.get_running_loop().create_future()
        ctx = None
        if tracing.enabled():
            # Carry the request's trace context across the coalescer
            # seam: the merge dispatch runs on a pool thread where the
            # submitting task's contextvars are invisible.
            ctx = tracing.current_context()
            try:
                entry.trace_ctx = ctx
            except AttributeError:
                pass  # foreign entry types (tests) without the slot
        if self._task is None:
            self._task = asyncio.ensure_future(self._run())
        if ingress is not None:
            ingress.end()
        self._waits[id(entry)] = self._stages.begin(
            "lane.queue_wait", self._lane, ctx
        )
        await self._queue.put(entry)
        try:
            return await entry.fut
        finally:
            # wire.wake (begun where the result was set) ends here, on
            # the resumed handler; a cancelled wait ends what is open.
            wake = self._waits.pop(id(entry), None)
            if wake is not None:
                wake.end()

    def _next_wait(self, entries, stage: str, lane: str) -> None:
        """End each entry's open wait and begin its next one."""
        waits = self._waits
        begin = self._stages.begin
        for en in entries:
            w = waits.get(id(en))
            if w is not None:
                w.end()
                waits[id(en)] = begin(
                    stage, lane, getattr(en, "trace_ctx", None)
                )

    def _drain_into(self, entries: list) -> None:
        while True:
            try:
                entries.append(self._queue.get_nowait())
            except asyncio.QueueEmpty:
                return

    async def _acquire_fetch_slot(self, entries: list):
        """Take a fetch slot for one merge BEFORE its dispatch (bounds
        outstanding merges to pipeline_depth + sparse slots).  Returns
        the semaphore to release when the merge's fetch completes."""
        if not self._fetch.locked():
            await self._fetch.acquire()  # immediate
            return self._fetch
        if (
            self._sparse_limit > 0
            and not self._overlap.locked()
            and sum(self._size_of(e) for e in entries)
            <= self._sparse_limit
        ):
            # Sparse drain while the pipeline is full: overlap on a
            # sparse slot instead of waiting out a fetch.
            await self._overlap.acquire()
            self.overlap_drains += 1
            self._count_drain("overlap")
            return self._overlap
        # Loaded: hold for a slot (the pipeline bubble); arrivals keep
        # accumulating and ship as ONE bigger merge.
        self.waited_drains += 1
        self._count_drain("waited")
        bubble = self._stages.begin("lane.slot_wait", self._lane)
        try:
            await self._fetch.acquire()
        finally:
            bubble.end()
        self._drain_into(entries)
        return self._fetch

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            first = await self._queue.get()
            drain = self._stages.begin("lane.drain", self._lane)
            entries = [first]
            self._drain_into(entries)
            self.drains += 1
            self._count_drain("total")
            fetch_sem = None
            try:
                fetch_sem = await self._acquire_fetch_slot(entries)
                # Dispatch serialization: the previous merge's dispatch
                # stage is short (no response sync), so this rarely
                # blocks; any arrivals during a wait still merge in.
                if self._dispatch_sem.locked():
                    held = self._stages.begin(
                        "lane.dispatch_wait", self._lane
                    )
                    try:
                        await self._dispatch_sem.acquire()
                    finally:
                        held.end()
                    self._drain_into(entries)
                else:
                    await self._dispatch_sem.acquire()
            except asyncio.CancelledError:
                # Shutdown while holding dequeued entries: fail them
                # instead of orphaning their awaiting handlers.
                drain.end()
                if fetch_sem is not None:
                    fetch_sem.release()
                for en in entries:
                    if not en.fut.done():
                        en.fut.set_exception(
                            RuntimeError("fastpath closed")
                        )
                raise
            self.inflight += 1
            if self.inflight > self.max_inflight_seen:
                self.max_inflight_seen = self.inflight
            m = self._metrics
            if m is not None:
                m.fastpath_pipeline_occupancy.labels(
                    lane=self._lane
                ).observe(self.inflight)
            task = asyncio.ensure_future(
                self._dispatch(loop, entries, fetch_sem, drain)
            )
            self._dispatches.add(task)
            task.add_done_callback(self._dispatches.discard)

    @staticmethod
    def _once(fn):
        """At-most-once wrapper for a fetch continuation: the normal
        path and the orphan resubmit below may both submit it; only the
        first execution runs the closure."""
        ran = [False]
        gate = threading.Lock()

        def run_once():
            with gate:
                if ran[0]:
                    return None
                ran[0] = True
            return fn()

        return run_once

    def _merge_span(self, entries):
        """(merge span, stage parent ctx) for one drained entry list:
        the span's parent is the first SAMPLED member's context and
        every other member attaches as a span link — the merge is the
        join point of N concurrent request traces, and the links are
        what lets any member's trace find the shared device round.
        (None, None) when tracing is off or no member carried a
        context."""
        if not tracing.enabled():
            return None, None
        ctxs = [
            c for c in (getattr(e, "trace_ctx", None) for e in entries)
            if c is not None
        ]
        if not ctxs:
            return None, None
        parent = next((c for c in ctxs if c.sampled), ctxs[0])
        msp = tracing.start_span(
            "fastpath.merge", parent,
            links=[c for c in ctxs if c is not parent],
            lane=self._lane, entries=len(entries),
        )
        if msp is not None:
            msp.set_attribute(
                "size", int(sum(self._size_of(e) for e in entries))
            )
        return msp, (msp.context if msp is not None else parent)

    def _on_pool(self, fn, stage_ctx):
        """`fn` as one stage's pool-thread pass: ends lane.handoff on its
        first line, runs `fn` with this lane's ledger scope and the
        merge's span context bound (contextvars do not cross
        run_in_executor), and begins lane.resume on its last."""
        stages, lane = self._stages, self._lane
        handoff = stages.begin("lane.handoff", lane, stage_ctx)

        def run():
            handoff.end()
            with tracing.scope(stages, lane), tracing.use_context(
                stage_ctx
            ):
                out = fn()
            return out, stages.begin("lane.resume", lane, stage_ctx)

        return run

    async def _staged(self, loop, stage: str, fn, stage_ctx):
        """One pipeline stage from the coalescer's side."""
        whole = self._stages.begin(stage, self._lane, stage_ctx)
        try:
            out, resume = await loop.run_in_executor(
                self._pool, self._on_pool(fn, whole.context or stage_ctx)
            )
            resume.end()
        finally:
            whole.end()
        return out

    async def _dispatch(self, loop, entries, fetch_sem, drain) -> None:
        """One merge's pipeline: dispatch stage on a pool thread (holds
        the dispatch slot), then — if `process` returned a continuation —
        the fetch stage on another pool pass (holds only the fetch slot,
        so the next merge dispatches concurrently)."""
        fetch_fn = None
        msp, stage_ctx = self._merge_span(entries)
        self._next_wait(entries, "lane.in_drain", self._lane)
        try:
            try:
                res = await self._staged(
                    loop, "lane.dispatch_stage",
                    lambda: self._process(entries), stage_ctx,
                )
            finally:
                # Dispatch stage over (or failed): the next merge may
                # dispatch while this one fetches.
                self._dispatch_sem.release()
            if callable(res):
                fetch_fn = self._once(res)
                outs = await self._staged(
                    loop, "lane.fetch_stage", fetch_fn, stage_ctx
                )
            else:
                outs = res  # single-phase process
        except BaseException as e:  # CancelledError is a BaseException
            if fetch_fn is not None and isinstance(
                e, asyncio.CancelledError
            ):
                # The dispatch stage already mutated device/store state
                # (donated table step, write-through ticket); a fetch
                # continuation that never runs would leak its ticket
                # and wedge every later Store.on_change delivery in
                # cond.wait.  Submit it straight to the pool — detached
                # from this cancelled task; the at-most-once gate makes
                # this a no-op when the awaited run already started.
                # FastPath.close() joins the pool, so the side effects
                # land before teardown.  The entries still fail below.
                self._pool.submit(fetch_fn)
            if msp is not None:
                msp.end(error=repr(e))
            err = (
                RuntimeError("fastpath closed")
                if isinstance(e, asyncio.CancelledError) else e
            )
            self._next_wait(entries, "wire.wake", "wire")
            for en in entries:
                if not en.fut.done():
                    en.fut.set_exception(err)
            if isinstance(e, asyncio.CancelledError):
                raise
        else:
            self._next_wait(entries, "wire.wake", "wire")
            for en, out in zip(entries, outs):
                if not en.fut.done():
                    en.fut.set_result(out)
        finally:
            drain.end()
            self.inflight -= 1
            fetch_sem.release()
            if msp is not None:
                msp.end()

    async def close(self) -> None:
        self._closed = True  # new do() calls fail fast, never respawn _run
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self._task = None
        # Let in-flight dispatches finish (their entries get results).
        if self._dispatches:
            await asyncio.gather(
                *list(self._dispatches), return_exceptions=True
            )
        # Entries still queued (never dequeued by _run) must fail too.
        while not self._queue.empty():
            en = self._queue.get_nowait()
            if not en.fut.done():
                en.fut.set_exception(RuntimeError("fastpath closed"))


class FastPath:
    """Per-service compiled lane with a coalescing columnar batcher.

    The three arguments' defaults are what every daemon runs (daemon.py
    builds `FastPath(service)`; no setting carries them); other values
    are for tests, whose reference is depth 1.  No measurement on the
    chip stands behind any of the three yet (ROADMAP A2).
    `max_inflight` bounds concurrent DISPATCH stages (1: every drain
    takes the WHOLE queue as one maximal merge).  `sparse_limit` (64
    requests): a drain at most this big may dispatch on an overlap slot
    rather than wait out the in-flight merge's response sync; 0 is off.
    `pipeline_depth` (2: one merge fetching while the next dispatches)
    bounds OUTSTANDING merges (dispatched, response not yet fetched):
    the response round-trip overlaps the next dispatch, so maximal
    merges pipeline without ever being split (docs/pipeline.md).

    Dispatch order is serialized by the
    backend lock; cascade merges hold that lock across their whole
    read -> replay -> write-back window, which serializes them against
    every other mutation path (this lane, the object path, the GLOBAL
    managers) exactly like any other single-writer section."""

    def __init__(self, service, max_inflight: int = 1,
                 sparse_limit: int = 64,
                 pipeline_depth: int = 2) -> None:
        if max_inflight < 1:
            raise ValueError(
                f"fastpath max_inflight must be >= 1, got {max_inflight}"
            )
        if pipeline_depth < 1:
            raise ValueError(
                f"fastpath pipeline_depth must be >= 1, "
                f"got {pipeline_depth}"
            )
        self.s = service
        metrics = service.metrics
        self._stages = tracing.ledger_of(metrics)
        self._stages.register("wire", tracing.WIRE_STAGES)
        # The forward hop's rows: a daemon that never routes (a single
        # node, the mesh daemon) shows them at zero.
        self._stages.register("peer", tracing.PEER_STAGES)
        self._stages.declare(
            "peer", "peer.forward", *tracing.PEER_FORWARD_COUNTERS
        )
        # Blocking device->host fetches performed ON the request path
        # (a coalescer dispatch/fetch stage), by lane.  The background
        # planes (census, tiering) must leave it unchanged.
        self.blocking_fetches = {"mach": 0, "sketch": 0, "engine": 0}
        # Worker budget: one thread per concurrent dispatch stage plus
        # one per outstanding fetch (pipeline depth + sparse overlap
        # slots) — a fetch blocked on the device (or on a write-through
        # ticket) must never starve the next merge's dispatch in this
        # very pool.
        self._pool = ThreadPoolExecutor(
            max_workers=max_inflight + pipeline_depth + (
                _Coalescer.OVERLAP_SLOTS if sparse_limit > 0 else 0
            ),
            thread_name_prefix="tpu-fastlane",
        )
        self._mach = _Coalescer(
            self._pool, self._process, max_inflight,
            sparse_limit=sparse_limit,
            size_of=lambda e: e.cols.n,
            pipeline_depth=pipeline_depth,
            metrics=metrics, lane="mach",
        )
        # What PR 34's two counters count may never happen in a run (no
        # merge cascades, every key is resident): they read 0, not nothing.
        self._stages.declare("mach", "lane.cascade", "wb_lanes")
        # The drains whose duplicate groups went plain, and the device
        # lanes their later occurrences took (_cascade_or_rounds).
        self._stages.declare("mach", "lane.pack", "dup_plain", "dup_lanes")
        self._stages.declare("mach", "lane.unpack", "new_windows")
        # The sketch and engine lanes each coalesce cross-RPC into one
        # maximal merge at a time, on DEDICATED workers so machinery
        # syncs can't starve them (and vice versa); each lane pipelines
        # its own dispatch/fetch stages at the same depth.
        self._sketch_pool = ThreadPoolExecutor(
            max_workers=1 + pipeline_depth,
            thread_name_prefix="tpu-fastlane-sketch",
        )
        self._sketch_lane = (
            _Coalescer(self._sketch_pool, self._sketch_process,
                       pipeline_depth=pipeline_depth,
                       metrics=metrics, lane="sketch")
            if service.sketch_backend is not None else None
        )
        self._engine_pool = ThreadPoolExecutor(
            max_workers=1 + pipeline_depth,
            thread_name_prefix="tpu-fastlane-engine",
        )
        self._engine_lane = (
            _Coalescer(self._engine_pool, self._engine_process,
                       pipeline_depth=pipeline_depth,
                       metrics=metrics, lane="engine")
            if service.global_engine is not None else None
        )
        self.pipeline_depth = pipeline_depth
        # Servings since start (observability; also asserted in tests to
        # prove the fast lane actually ran).
        self.served = 0
        self.fallbacks = 0
        self._owner_frames: Dict[bytes, bytes] = {}
        # (membership_version, combined hash array) — see _sketch_hashes.
        self._sk_hashes: Optional[Tuple[int, np.ndarray]] = None

    def debug_vars(self) -> dict:
        """The /debug/vars view: per-lane drain/pipeline counters."""
        lanes = {"mach": self._mach.debug_vars()}
        if self._sketch_lane is not None:
            lanes["sketch"] = self._sketch_lane.debug_vars()
        if self._engine_lane is not None:
            lanes["engine"] = self._engine_lane.debug_vars()
        out = {
            "served": self.served,
            "fallbacks": self.fallbacks,
            "pipeline_depth": self.pipeline_depth,
            # One drain discipline; the benchmark compares these two
            # keys (bench/run.py serve_mode_degraded).
            "serve_mode": "pipelined",
            "effective_serve_mode": "pipelined",
            "blocking_fetches": dict(self.blocking_fetches),
            "lanes": lanes,
        }
        return out

    # -- eligibility -----------------------------------------------------
    def _eligible(self) -> bool:
        # Persistence (Store/Loader/keymap) is served ON the lane:
        # seeding/capture batch columnarly per drain (_process), so a
        # store-attached deployment keeps the compiled path.
        return native.available()

    def _sketch_hashes(self) -> np.ndarray:
        """XXH64 fingerprints of the sketch-tier names (route key for the
        parser's name_hash column; the same 64-bit fingerprint stance the
        slot table takes on full keys).  Runtime-spilled names
        (SketchBackend.spill_name) append to the configured set; the
        combined array is cached per membership version — this runs in
        the per-RPC parse path."""
        sb = self.s.sketch_backend
        ver = sb.membership_version
        if self._sk_hashes is None or self._sk_hashes[0] != ver:
            base = native.hash_keys(sorted(sb.cfg.names))
            dyn = sb.dynamic_hashes()
            combined = (
                base if len(dyn) == 0 else np.concatenate([base, dyn])
            )
            self._sk_hashes = (ver, combined)
        return self._sk_hashes[1]

    def _owner_frame(self, addr: bytes) -> bytes:
        f = self._owner_frames.get(addr)
        if f is None:
            f = native.meta_frame(b"owner", addr)
            self._owner_frames[addr] = f
        return f

    def _single_node(self) -> bool:
        """True when no request can need a peer forward: an empty picker,
        or a one-peer picker where that peer is this node."""
        pick = self.s.local_picker
        sz = pick.size()
        if sz == 0:
            return True
        if sz > 1:
            return False
        return pick.peers()[0].info().is_owner

    # -- entry point -----------------------------------------------------
    async def check_raw(
        self, payload: bytes, peer_rpc: bool,
        deadline: Optional[float] = None,
    ) -> Optional[bytes]:
        """Serve a GetRateLimits(Req) / GetPeerRateLimits(Req) payload on
        the compiled lane; None = caller must take the object path.
        Raises ApiError on an oversized batch (same contract as the
        object path).  `deadline` (time.monotonic()) is the client's own:
        all a routed RPC does with it is bound the re-asks of a forward
        that times out (_serve_routed)."""
        # wire.ingress: from here to the first coalescer enqueue
        # (_Coalescer.do ends it); the `finally` ends it for an RPC that
        # never enqueues (fallback, empty, oversized).
        ingress = self._stages.begin(
            "wire.ingress", "wire", tracing.current_context()
        )
        try:
            return await self._check_raw(
                payload, peer_rpc, ingress, deadline
            )
        finally:
            ingress.end()

    async def _check_raw(
        self, payload: bytes, peer_rpc: bool, ingress, deadline=None
    ) -> Optional[bytes]:
        from gubernator_tpu.runtime.service import ApiError

        if not self._eligible():
            self.fallbacks += 1
            return None
        if self.s.shed_level() > 0:
            # SLO-driven shedding is active (docs/hotkeys.md):
            # priority ordering is per request NAME, so the object path
            # applies it — the lane steps aside while this node sheds
            # (an overload condition; the columnar win is moot).
            self.fallbacks += 1
            return None
        rs = self.s.reshard
        if rs is not None and rs.active():
            # A handoff is in flight on this node (docs/resharding.md):
            # covered keys must forward-back / serve the bounded shadow
            # and rerouted keys must leave this table — per-key routing
            # the object path owns.  The lane steps aside for the
            # window (seconds per remap); every other daemon keeps its
            # compiled lane.
            self.fallbacks += 1
            return None
        if self.s.regions is not None:
            # Planet-scale regions (docs/multiregion.md): a remote-homed
            # key must serve the bounded `.region-carve` slot, and the
            # home pick is a per-key rendezvous over STRING hashes
            # (`key@region`) the columnar router cannot express — served
            # on the compiled lane it would answer from the raw row at
            # the full limit, breaking the region bound.  The object
            # path owns region routing.
            self.fallbacks += 1
            return None
        routed = not peer_rpc and not self._single_node()
        if routed and not self._can_route():
            self.fallbacks += 1
            return None
        if routed and len(self.s.local_picker.ring_arrays()[2]) == 0:
            # Empty ring: fall back BEFORE any metric side effects so the
            # object path (which re-runs validation and increments the
            # same counters) can't double-count.  There is no await
            # between here and _serve_routed's ring read, so the router
            # below never sees an empty ring.
            self.fallbacks += 1
            return None
        cols = native.parse_reqs(payload)
        if cols is None:
            self.fallbacks += 1
            return None
        n = cols.n
        if n > MAX_BATCH_SIZE:
            # Metric parity with the object path (service.py rejects with
            # the same counter on the client RPC, none on the peer RPC).
            if peer_rpc:
                raise ApiError(
                    "OUT_OF_RANGE",
                    "'PeerRequest.rate_limits' list too large; max size "
                    "is '%d'" % MAX_BATCH_SIZE,
                )
            self.s.metrics.note_check_error("Request too large")
            raise ApiError(
                "OUT_OF_RANGE",
                "Requests.RateLimits list too large; max size is '%d'"
                % MAX_BATCH_SIZE,
            )
        if not peer_rpc and n and cols.err.any():
            # Metric parity with the object path's client-side validation
            # rejections (gubernator.go:229, 235).
            n_inv = int(((cols.err == 1) | (cols.err == 2)).sum())
            if n_inv:
                self.s.metrics.note_check_error("Invalid request", n_inv)
        sk: Optional[np.ndarray] = None
        if self.s.sketch_backend is not None and n:
            sk = np.isin(cols.name_hash, self._sketch_hashes()) & (
                cols.err == 0
            )
            if sk.any():
                # Sketch names don't compose with GLOBAL replication —
                # strip the flag so they route plainly to the key's owner
                # and count ONCE there (service.py's routing does the
                # same on the object path).
                cols.behavior[sk] &= ~_GLOBAL
            else:
                sk = None
        is_global = (cols.behavior & _GLOBAL) != 0
        if n == 0:
            return b""
        if not peer_rpc:
            # concurrent_checks parity with service.get_rate_limits.
            self.s._inflight_checks += 1
            self.s.metrics.concurrent_checks.observe(
                self.s._inflight_checks
            )
        # Hot-key detection (docs/hotkeys.md) and promote-on-access
        # (docs/tiering.md), each where it is armed: feed them the
        # parsed fingerprint/hits columns once, at the point of no
        # return — every fallback already happened, so the object path
        # can never observe the same batch again.  Zero fingerprints
        # (errored lanes) are ignored by both.
        noted = None
        if self.s.hotkeys is not None or self.s.tier is not None:
            noted = self.s.note_traffic(cols.hash, cols.hits)
        try:
            if routed:
                return await self._serve_routed(
                    payload, cols, n, is_global, sk, ingress, deadline
                )
            return await self._serve(
                payload, cols, n, is_global, sk, peer_rpc, ingress
            )
        finally:
            if noted is not None:
                self.s.tier.note_done(noted)
            if not peer_rpc:
                self.s._inflight_checks -= 1

    def _prep_greg(self, cols, exclude=None) -> Tuple[
        np.ndarray, np.ndarray, np.ndarray, Dict[int, bytes]
    ]:
        """Host-side Gregorian expiry (rare; only flagged lanes loop).
        Marks failed lanes in cols.err and zeroes their hashes.
        `exclude` masks lanes whose tier ignores duration entirely (the
        sketch tier, which neither computes nor errors on Gregorian —
        matching SketchBackend.check)."""
        n = cols.n
        greg_expire = np.zeros(n, dtype=np.int64)
        greg_duration = np.zeros(n, dtype=np.int64)
        is_greg = (
            cols.behavior & int(Behavior.DURATION_IS_GREGORIAN)
        ) != 0
        # Validation errors take precedence: the object path's packer
        # rejects an empty name/key BEFORE evaluating the Gregorian
        # duration, so an already-errored lane must keep its error.
        is_greg &= cols.err == 0
        if exclude is not None:
            is_greg &= ~exclude
        err_extra: Dict[int, bytes] = {}
        if is_greg.any():
            now_dt = self.s.clock.now()
            for i in np.flatnonzero(is_greg):
                i = int(i)
                try:
                    greg_expire[i] = gregorian_expiration(
                        now_dt, int(cols.duration[i])
                    )
                    greg_duration[i] = gregorian_duration(
                        now_dt, int(cols.duration[i])
                    )
                except GregorianError as e:
                    err_extra[i] = str(e).encode()
                    cols.err[i] = _ERR_GREG
                    cols.hash[i] = 0
        return is_greg, greg_expire, greg_duration, err_extra

    def _error_strings(self, cols, err_extra) -> List[bytes]:
        """Per-request error bytes (b'' on clean lanes)."""
        out = [b""] * cols.n
        if cols.err.any():
            for i in np.flatnonzero(cols.err):
                i = int(i)
                code = int(cols.err[i])
                out[i] = (
                    err_extra.get(i, b"")
                    if code == _ERR_GREG
                    else (_ERR_EMPTY_KEY if code == 1 else _ERR_EMPTY_NAME)
                )
        return out

    async def _serve_cols(
        self, payload, cols, is_greg, ge, gd, use_cached=None,
        ingress=None,
    ) -> Tuple[np.ndarray, ...]:
        """Submit columns to the coalescing batcher; returns the seven
        response arrays (status, limit, remaining, reset_time, stored,
        stored_status, cap_ok — the last three feed the GLOBAL broadcast
        capture).  `payload` is the raw wire bytes the columns were
        spliced from — the persistence SPI decodes per-unique-key
        requests from it."""
        return await self._mach.do(_Entry(
            payload=payload,
            cols=cols,
            is_greg=is_greg,
            greg_expire=ge,
            greg_duration=gd,
            use_cached=(
                use_cached if use_cached is not None
                else np.zeros(cols.n, dtype=bool)
            ),
        ), ingress)

    def _decode_req(self, payload, cols, i: int):
        """Decode ONE request's spliced wire frame into a RateLimitReq."""
        from gubernator_tpu.net.grpc_api import req_from_pb
        from gubernator_tpu.proto import gubernator_pb2 as pb

        frame = payload[
            cols.msg_off[i]:cols.msg_off[i] + cols.msg_len[i]
        ]
        return req_from_pb(pb.GetRateLimitsReq.FromString(frame).requests[0])

    def _decode_unique(self, payload, cols, idx, last=False):
        """Yield (req, group_indices) for each UNIQUE key hash among the
        request indices `idx` — one protobuf decode per unique key (the
        managers aggregate by key anyway, global.go:87-95).  `last`
        decodes the group's LAST arrival instead of its first: the
        update queue is last-write-wins per key (queue_update), and the
        broadcast's zero-hit re-read uses the queued request's params —
        first-occurrence params would recreate the bucket differently
        on an algorithm/burst change within one batch."""
        if not len(idx):
            return
        order = idx[np.argsort(cols.hash[idx], kind="stable")]
        hs = cols.hash[order]
        bounds = np.flatnonzero(
            np.concatenate([[True], hs[1:] != hs[:-1]])
        )
        for b_i, lo in enumerate(bounds):
            hi = bounds[b_i + 1] if b_i + 1 < len(bounds) else len(order)
            group = order[lo:hi]
            fi = int(group[-1] if last else group[0])
            yield self._decode_req(payload, cols, fi), group

    def _queue_global(self, payload, cols, idx) -> None:
        """Queue GLOBAL hits (non-owner) for the request indices `idx` —
        the deferred QueueHit of gubernator.go:429-432.  Errored lanes
        are pre-filtered by the caller: a queued errored hit is dropped
        by the owner's validation with no state effect anywhere, so the
        bookkeeping difference from the object path (which queues before
        validating) is unobservable."""
        from dataclasses import replace as dc_replace

        if not len(idx):
            return
        mgr = self.s.global_mgr
        for req, group in self._decode_unique(payload, cols, idx):
            total = int(cols.hits[group].sum())
            mgr.queue_hit(dc_replace(req, hits=total))

    def _queue_global_updates(self, payload, cols, is_global,
                              owned=None, peer_rpc=False,
                              capture=None) -> None:
        """Queue owner-side broadcast updates for GLOBAL lanes — GREGORIAN-
        errored lanes included: the reference QueueUpdates before the
        algorithm runs (gubernator.go:617-619), so with last-write-wins
        per key an errored occurrence can cancel a valid one's pending
        broadcast.  The fast lane reproduces that exactly: the LAST
        arrival per key wins, valid or not.  VALIDATION-errored lanes
        (empty name/key) queue only on the peer RPC: the client RPC
        rejects them before routing (gubernator.go:228-237) so they never
        reach the algorithm, while the peer RPC validates owner-side
        AFTER QueueUpdate.

        `owned` (routed path) masks node-owned lanes.  Which branch an
        errored lane takes depends on where its error was detected:
        validation errors have hash 0 from the parser and route through
        the decode branch below, with ownership decided from the decoded
        key string like the object path's routing; Gregorian errors on
        the ROUTED path keep their true hash in `cols` (only
        serve_local's subset copy was zeroed), so they group with the
        valid lanes — same last-write-wins outcome either way.

        `capture` = (stored_status, stored, reset, limit, cap_ok)
        full-size response columns from this drain: each queued update
        carries the post-step stored state of its LAST arrival, which the
        broadcast ships directly instead of re-running a zero-hit read —
        equal by construction to global.go:205-250's re-read of a bucket
        row (token reports the sticky stored status; leaky always
        re-reads UNDER; reset/remaining are the post-step stored values;
        a lane whose request errored re-captures the error, which the
        broadcast skips exactly as it skips a failed re-read).  A capture
        is kept ONLY when `cap_ok` marks the arrival as its key's last
        mutating occurrence across the WHOLE merged drain (computed in
        _process over every coalesced RPC — a later occurrence, even from
        another concurrent call, moves the row past the capture, and the
        flush-time re-read would then apply the queued request's now
        stale params to the newer row, a reference quirk the re-read
        fallback preserves exactly; sketch lanes never reach _process's
        machinery merge, so their cap_ok stays False).  Later DRAINS
        degrade captures via _touch_captures.  The only intended
        divergences from flush-time
        re-reads: sub-window leaky time-regen (zero under a frozen
        clock) and no resurrection of keys evicted between drain and
        flush."""
        idx = np.flatnonzero(is_global)
        if not len(idx):
            return
        hv = cols.hash[idx]
        valid = idx[hv != 0]
        if owned is not None:
            valid = valid[owned[valid]]
        best: Dict[str, Tuple[int, object]] = {}
        for req, group in self._decode_unique(
            payload, cols, valid, last=True
        ):
            best[req.hash_key()] = (int(group[-1]), req)
        err_lanes = idx[hv == 0]
        if len(err_lanes) and not peer_rpc:
            # Client path: only Gregorian failures reached the algorithm;
            # validation errors were rejected before routing.
            err_lanes = err_lanes[cols.err[err_lanes] == _ERR_GREG]
        if len(err_lanes):
            from gubernator_tpu.runtime.service import PoolEmptyError

            sk_be = self.s.sketch_backend
            for i in err_lanes:
                i = int(i)
                req = self._decode_req(payload, cols, i)
                if sk_be is not None and sk_be.handles(req):
                    # The object path strips GLOBAL from sketch names
                    # unconditionally (errored or not) — a sketch key
                    # never queues an exact-table broadcast.
                    continue
                key = req.hash_key()
                if owned is not None:
                    try:
                        if not self.s.get_peer(key).info().is_owner:
                            continue
                    except PoolEmptyError:
                        continue
                cur = best.get(key)
                if cur is None or i > cur[0]:
                    best[key] = (i, req)
        mgr = self.s.global_mgr
        if capture is None:
            for _, req in best.values():
                mgr.queue_update(req)
            return
        from gubernator_tpu.core.types import RateLimitResp, Status

        sst, sto, rst, lm, cap_ok = capture
        for i, req in best.values():
            if cols.err[i] != 0:
                # Errored last arrival: the re-read would fail the same
                # way and broadcast nothing — capture a sentinel error so
                # the broadcast skips this key (last-write-wins cancel,
                # immune to later mutations: the QUEUED params stay
                # errored).
                st: Optional[RateLimitResp] = RateLimitResp(
                    error="capture: errored lane"
                )
            elif not cap_ok[i]:
                st = None  # a later occurrence moved the row — re-read
            elif int(cols.behavior[i]) & int(Behavior.RESET_REMAINING):
                # The flush-time re-read of a RESET_REMAINING request
                # re-runs the reset (algorithms.go:78-90 precedes the
                # hits==0 early-out) — a mutating read the capture
                # cannot represent.
                st = None
            elif int(cols.algo[i]) == 1 and int(sto[i]) > int(
                cols.burst[i] if cols.burst[i] != 0 else cols.limit[i]
            ):
                # Leaky row overfilled past burst (negative hits): the
                # next read — including the flush re-read — clamps and
                # WRITES remaining back to burst (algorithms.go:372-376).
                # Another mutating read; keep it.
                st = None
            else:
                st = RateLimitResp(
                    status=Status(int(sst[i])),
                    limit=int(lm[i]),
                    remaining=int(sto[i]),
                    reset_time=int(rst[i]),
                )
            mgr.queue_update(req, st)

    def _touch_captures(self, cols, sk=None, eng=None) -> None:
        """Degrade stale captured GLOBAL broadcast rows for every key
        this drain mutated on the machinery table (a non-GLOBAL request
        must not let a pending capture ship pre-mutation state — the
        re-read fallback then sees the post-mutation row, exactly like
        the reference's flush-time read).  Near-free while no captures
        are pending; lanes that re-queue an update below simply
        re-capture fresh state (touch runs first)."""
        mgr = self.s.global_mgr
        if not mgr._pending_h:
            return
        mask = cols.err == 0
        if sk is not None:
            mask &= ~sk
        # Engine lanes stay in the set: they mutate the engine's own
        # tables, but engine services never create RPC captures, so
        # touching them is a no-op — not worth a mask.
        if mask.any():
            mgr.touch_hashes(cols.hash[mask])

    def _queue_multiregion(self, payload, cols, idx) -> None:
        """Queue owner-side MULTI_REGION hits for the request indices
        `idx` toward the cross-region manager (the object path's
        queue_hits call in _check_local, gubernator.go:600-631)."""
        from dataclasses import replace as dc_replace

        if not len(idx):
            return
        mgr = self.s.multi_region_mgr
        for req, group in self._decode_unique(payload, cols, idx):
            total = int(cols.hits[group].sum())
            mgr.queue_hits(dc_replace(req, hits=total))

    async def _serve_split(
        self, payload, cols, is_greg, ge, gd, use_cached, sk, eng=None,
        ingress=None,
    ) -> Tuple[np.ndarray, ...]:
        """Serve a column set, splitting sketch-named lanes to the CMS
        step and engine lanes (node-owned GLOBAL on a mesh service) to
        the collective GlobalEngine; the rest rides the exact machinery.
        All branches run concurrently and scatter into full-size
        response arrays."""
        no_sk = sk is None or not sk.any()
        no_eng = eng is None or not eng.any()
        if no_sk and no_eng:
            return await self._serve_cols(
                payload, cols, is_greg, ge, gd, use_cached=use_cached,
                ingress=ingress,
            )
        n = cols.n
        sk_m = sk if sk is not None else np.zeros(n, dtype=bool)
        eng_m = eng if eng is not None else np.zeros(n, dtype=bool)
        sk_idx = np.flatnonzero(sk_m)
        eng_idx = np.flatnonzero(eng_m)
        ex_idx = np.flatnonzero(~sk_m & ~eng_m)
        status = np.zeros(n, dtype=np.int64)
        out_lim = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        # Post-step stored columns (machinery lanes only — sketch/engine
        # lanes never feed the RPC broadcast capture, so their cap_ok
        # stays False).
        stored = np.zeros(n, dtype=np.int64)
        stored_st = np.zeros(n, dtype=np.int64)
        cap_ok = np.zeros(n, dtype=bool)
        loop = asyncio.get_running_loop()

        async def run_sketch() -> None:
            kh = cols.hash[sk_idx]
            hh = cols.hits[sk_idx]
            ll = cols.limit[sk_idx]
            st, rem, rst = await self._sketch_lane.do(
                _SketchEntry(kh, hh, ll), ingress
            )
            status[sk_idx] = st
            out_lim[sk_idx] = ll
            remaining[sk_idx] = rem
            reset[sk_idx] = rst

        async def run_engine() -> None:
            st, lm, rem, rst = await self._engine_lane.do(
                _EngineEntry(payload, cols, eng_idx, is_greg, ge, gd),
                ingress,
            )
            status[eng_idx] = st
            out_lim[eng_idx] = lm
            remaining[eng_idx] = rem
            reset[eng_idx] = rst
            # Open the sync window for the queued hits (the object
            # path's notify at service.py:405; asyncio.Event — must run
            # on the loop thread, hence here and not in _engine_process).
            if self.s._collective_loop is not None:
                self.s._collective_loop.notify()

        async def run_exact() -> None:
            sub = cols.subset(ex_idx)
            st, lm, rem, rst, sto, sst, cok = await self._serve_cols(
                payload, sub, is_greg[ex_idx], ge[ex_idx], gd[ex_idx],
                use_cached=(
                    use_cached[ex_idx] if use_cached is not None else None
                ),
                ingress=ingress,
            )
            status[ex_idx] = st
            out_lim[ex_idx] = lm
            remaining[ex_idx] = rem
            reset[ex_idx] = rst
            stored[ex_idx] = sto
            stored_st[ex_idx] = sst
            cap_ok[ex_idx] = cok

        tasks = []
        if len(sk_idx):
            tasks.append(run_sketch())
        if len(eng_idx):
            tasks.append(run_engine())
        if len(ex_idx):
            tasks.append(run_exact())
        await asyncio.gather(*tasks)
        return status, out_lim, remaining, reset, stored, stored_st, cap_ok

    def _pack_rounds(self, *cols, mode: int, shard_shift: int = None,
                     cap_ok: bool = False):
        """native.pack_rounds at this backend's geometry: `cols` are the
        drain's eleven columns (hash, hits, limit, duration, algo, burst,
        behavior, is_greg, greg_expire, greg_duration, use_cached; None:
        zeros), `shard_shift` the bits a hash's shard is read from (the
        table's owner shard unless given)."""
        from gubernator_tpu.parallel.mesh import _SHARD_SHIFT

        backend = self.s.backend
        return native.pack_rounds(
            *cols, reset_bit=int(Behavior.RESET_REMAINING),
            n_shards=backend.cfg.num_shards,
            shard_shift=_SHARD_SHIFT if shard_shift is None else shard_shift,
            batch_size=backend.cfg.batch_size, tiers=backend._tiers,
            mode=mode, cap_ok=cap_ok,
        )

    def _engine_process(self, entries):
        pack = tracing.stage("lane.pack")  # ended at the hand-over
        try:
            return self._engine_process_packed(entries, pack)
        finally:
            pack.end()

    def _engine_process_packed(self, entries, pack):
        """Merged columnar serving for node-owned GLOBAL lanes on the
        mesh GlobalEngine — one coalescer drain = ONE engine lock hold
        and dispatch chain (runs on the engine lane's worker thread).
        Dispatch stage: aggregate + pack + serve_packed (engine lock);
        the returned closure (host fetch, unmarshal, tally, deferred
        sync) is the fetch stage.

        Per ENTRY, duplicates aggregate to one lane per unique key
        (hits summed, first occurrence's params, shared response) —
        mirroring one GlobalEngine.check call.  ACROSS entries the same
        key keeps separate lanes, which assign_rounds places in later
        rounds — so a drain of N entries is semantically N sequential
        engine calls, amortized into one round-trip."""
        from gubernator_tpu.parallel.global_sync import _ARRIVAL_SHIFT
        from gubernator_tpu.parallel.sharded import (
            packed_grid_rounds_to_host,
        )
        from gubernator_tpu.runtime.backend import Tally

        engine = self.s.global_engine
        n_shards = self.s.backend.cfg.num_shards
        shift = np.uint64(_ARRIVAL_SHIFT)  # vectorized arrival_dev

        per = []
        for e in entries:
            sub_h = e.cols.hash[e.idx]
            uniq, first, inv = np.unique(
                sub_h, return_index=True, return_inverse=True
            )
            rep = e.idx[first]             # first occurrence per key
            m = len(uniq)
            # Exact int64 sums (float64 bincount weights would corrupt
            # hits above 2^53 and diverge from the pending queue).
            hits_sum = np.zeros(m, dtype=np.int64)
            np.add.at(hits_sum, inv, e.cols.hits[e.idx])
            per.append((e, uniq, inv, rep, m, hits_sum))

        def cat(parts):
            # Uncontended drains (one entry) skip the copies.
            return parts[0] if len(parts) == 1 else np.concatenate(parts)

        h_all = cat([p[1] for p in per])
        offs = np.zeros(len(per) + 1, dtype=np.int64)
        np.cumsum([p[4] for p in per], out=offs[1:])
        sh = (
            (h_all.view(np.uint64) >> shift) % np.uint64(n_shards)
        ).astype(np.int32)
        # Round/lane assignment and the rounds in the device's layout, in
        # one native call (the arrival shard is the same arithmetic there).
        packed = self._pack_rounds(
            h_all,
            cat([p[5] for p in per]),
            cat([p[0].cols.limit[p[3]] for p in per]),
            cat([p[0].cols.duration[p[3]] for p in per]),
            cat([p[0].cols.algo[p[3]] for p in per]),
            cat([p[0].cols.burst[p[3]] for p in per]),
            cat([p[0].cols.behavior[p[3]] for p in per]),
            cat([p[0].is_greg[p[3]] for p in per]),
            cat([p[0].ge[p[3]] for p in per]),
            cat([p[0].gd[p[3]] for p in per]),
            np.ones(len(h_all), dtype=bool),
            mode=0, shard_shift=_ARRIVAL_SHIFT,
        )
        # _decode_unique yields groups in ascending-hash order — exactly
        # each entry's uniq order — so the decoded reqs zip with the
        # computed sums and arrival shards (one source of truth).
        pend = []
        for i, (e, _uniq, _inv, _rep, _m, hits_sum) in enumerate(per):
            off = int(offs[i])
            for j, (req, _group) in enumerate(
                self._decode_unique(e.payload, e.cols, e.idx)
            ):
                pend.append(
                    (req, int(hits_sum[j]), int(sh[off + j]))
                )
        # What this drain held: checks as the RPCs sent them, and the
        # device rounds they took.
        pack.tally(
            checks=sum(len(e.idx) for e in entries),
            rounds=len(packed.rounds),
        )
        pack.end()
        resps, want_sync = engine.serve_packed(packed.rounds, pend)

        def unpack(host) -> List[Tuple[np.ndarray, ...]]:
            got = native.gather_rounds(
                packed, h_all, [_resp_words(hr) for hr in host], n_cols=4
            )
            st_u, lm_u, rem_u, rst_u = got.cols
            self.s.backend._add_tally(Tally(
                checks=len(h_all),
                over_limit=got.over_limit,
                not_persisted=got.not_persisted,
                cache_hits=got.cache_hits,
            ))
            if want_sync:
                engine.sync()
            outs: List[Tuple[np.ndarray, ...]] = []
            for i, (_e, _uq, inv, _rep, _m, _hits) in enumerate(per):
                lo, hi = int(offs[i]), int(offs[i + 1])
                outs.append((
                    st_u[lo:hi][inv], lm_u[lo:hi][inv],
                    rem_u[lo:hi][inv], rst_u[lo:hi][inv],
                ))
            return outs

        def fetch() -> List[Tuple[np.ndarray, ...]]:
            self.blocking_fetches["engine"] += 1
            host = packed_grid_rounds_to_host(resps)
            with tracing.stage("lane.unpack"):
                return unpack(host)

        return fetch

    @staticmethod
    def _sketch_meta(n: int, sk) -> Tuple[Optional[bytes],
                                          Optional[np.ndarray]]:
        """(meta_blob, meta_off) tagging sketch lanes tier=sketch."""
        if sk is None or not sk.any():
            return None, None
        metas = [
            _TIER_SKETCH_FRAME if sk[i] else b"" for i in range(n)
        ]
        off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(m) for m in metas], out=off[1:])
        return b"".join(metas), off

    async def _serve(
        self, payload, cols, n: int, is_global, sk, peer_rpc=False,
        ingress=None,
    ) -> bytes:
        """Single-node / peer-RPC path: everything is local (and owned,
        so GLOBAL lanes serve authoritatively and queue broadcast
        updates).  On a mesh service the CLIENT path routes GLOBAL lanes
        to the collective GlobalEngine; the peer RPC keeps RPC-tier
        semantics (machinery serve + queued update) like the object
        path's _check_local — engine keys sync over ICI, cross-node
        forwards ride the managers."""
        is_greg, ge, gd, err_extra = self._prep_greg(cols, exclude=sk)
        use_engine = self.s.global_engine is not None and not peer_rpc
        eng = None
        if use_engine and is_global.any():
            eng = is_global & (cols.err == 0)
            if not eng.any():
                eng = None
        status, limit, remaining, reset, stored, stored_st, cap_ok = (
            await self._serve_split(
                payload, cols, is_greg, ge, gd, None, sk, eng, ingress
            )
        )
        egress = self._stages.begin(
            "wire.egress", "wire", tracing.current_context()
        )
        if eng is not None:
            # Metric parity: the object path's routing counts engine
            # requests under the "global" source label.
            self.s.metrics.getratelimit_counter.labels("global").inc(
                int(eng.sum())
            )
        self._touch_captures(cols, sk, eng)
        if is_global.any() and not use_engine:
            # With a collective engine, GLOBAL lanes (errored included)
            # belong to the engine path on the object flow — the RPC
            # update manager is never consulted.
            self._queue_global_updates(
                payload, cols, is_global, peer_rpc=peer_rpc,
                capture=(stored_st, stored, reset, limit, cap_ok),
            )
        mr = (cols.behavior & _MULTI_REGION) != 0
        if mr.any():
            self._queue_multiregion(
                payload, cols, np.flatnonzero(mr & (cols.err == 0))
            )
        errs = self._error_strings(cols, err_extra)
        err_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(e) for e in errs], out=err_off[1:])
        meta_blob, meta_off = self._sketch_meta(n, sk)
        self.served += n
        out = native.serialize_resps(
            status, limit, remaining, reset, b"".join(errs), err_off,
            meta_blob, meta_off,
        )
        egress.end()
        return out

    def _can_route(self) -> bool:
        """Columnar routing serves every selectable ring hash: xx rings
        drive the owner lookup straight from the C++ parse fingerprint
        (XXH64 of the hash-key string); fnv1/fnv1a rings — placement
        interop with mixed reference/tpu clusters
        (replicated_hash.go:33) — get a vectorized second hash column
        from gub_fnv_hashkey_batch."""
        from gubernator_tpu.core.hashing import fnv1_64, fnv1a_64
        from gubernator_tpu.net.replicated_hash import xx_64

        return self.s.local_picker.hash_fn in (xx_64, fnv1_64, fnv1a_64)

    async def _serve_routed(
        self, payload: bytes, cols, n: int, is_global, sk, ingress=None,
        deadline: Optional[float] = None,
    ) -> bytes:
        """Multi-node client path: vectorized consistent-hash routing with
        zero-copy forwards.

        One np.searchsorted over the vnode ring maps every request to its
        owner; locally-owned (and errored) lanes ride the normal columnar
        lane, while each remote owner receives ONE GetPeerRateLimits RPC
        whose payload is spliced verbatim from this request's wire bytes —
        no re-encoding in either direction (the reference's asyncRequests
        + peer batcher, gubernator.go:327-416, with the per-request python
        replaced by array ops).  Failed forwards fall back to the object
        path's ownership-retry loop per request; one that times out is
        asked again under its id while `deadline`, the client's, allows
        (net/peer_client.py; the owner applies an id once)."""
        stages = self._stages
        route = stages.stage("peer.route", "peer")
        picker = self.s.local_picker
        ring, ring_idx, peers = picker.ring_arrays()
        # check_raw gated on a non-empty ring with no await in between;
        # a fallback here would double-count the validation metrics the
        # caller already incremented, so the invariant must hold.
        assert peers, "check_raw gates on a non-empty ring"
        from gubernator_tpu.net.replicated_hash import xx_64

        if picker.hash_fn is xx_64:
            h_route = cols.hash
        else:
            # fnv1/fnv1a interop ring (_can_route admitted it): hash the
            # spliced hash-key bytes with the ring's own function —
            # placement stays identical to a reference node's.
            from gubernator_tpu.core.hashing import fnv1_64

            h_route = native.fnv_hashkey_batch(
                payload, cols,
                "fnv1" if picker.hash_fn is fnv1_64 else "fnv1a",
            )
        h_u = h_route.view(np.uint64)
        slot = np.searchsorted(ring, h_u, side="left")
        slot[slot == len(ring)] = 0
        owner = ring_idx[slot]  # peer index per request
        is_owner = np.array(
            [p.info().is_owner for p in peers], dtype=bool
        )
        owned = is_owner[owner]
        # GLOBAL never forwards: non-owned GLOBAL serves from the local
        # replica via use_cached lanes (stale-but-fast reads,
        # gubernator.go:420-460) with the hit queued to the owner.
        glob_cached = is_global & ~owned & (cols.err == 0)
        local_mask = (cols.err != 0) | owned | is_global
        # Hot-key widening (docs/hotkeys.md): lanes for keys this node
        # actively mirrors (hot AND owner pressured AND we are a
        # next-arc replica) leave the forward sets and serve from the
        # local mirror allowance via the object path — the hot-set is
        # tiny and the per-request hop replaces a forwarded RPC to an
        # overloaded owner, not a columnar serve.
        mirror_fps = self.s.active_mirror_fps()
        mirror_mask = None
        if len(mirror_fps):
            mirror_mask = (
                np.isin(cols.hash, mirror_fps)
                & ~local_mask
            )
            if sk is not None:
                mirror_mask &= ~sk
            if not mirror_mask.any():
                mirror_mask = None
        local_idx = np.flatnonzero(local_mask)
        forwardable = ~local_mask
        if mirror_mask is not None:
            forwardable &= ~mirror_mask
        remote_idx = np.flatnonzero(forwardable)
        remote_owner = owner[remote_idx]
        forwards = [
            (peers[int(pi)], remote_idx[remote_owner == pi])
            for pi in np.unique(remote_owner)
        ]
        route.end()

        status = np.zeros(n, dtype=np.int64)
        out_lim = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        stored = np.zeros(n, dtype=np.int64)
        stored_st = np.zeros(n, dtype=np.int64)
        cap_ok = np.zeros(n, dtype=bool)
        errs: List[bytes] = [b""] * n
        metas: List[bytes] = [b""] * n

        async def serve_local(idx: np.ndarray) -> None:
            sub = cols.subset(idx)
            sub_sk = sk[idx] if sk is not None else None
            is_greg, ge, gd, err_extra = self._prep_greg(
                sub, exclude=sub_sk
            )
            # _prep_greg marked Gregorian failures on the subset COPY —
            # propagate so the GLOBAL queue/metadata block (filtered on
            # cols.err == 0) never replicates or annotates a failed lane.
            cols.err[idx] = sub.err
            sub_eng = None
            if self.s.global_engine is not None:
                # Node-owned GLOBAL lanes ride the collective engine
                # (service.py routing: owner + engine -> engine_idx).
                sub_eng = (
                    is_global[idx] & owned[idx] & (sub.err == 0)
                )
                if not sub_eng.any():
                    sub_eng = None
            st, lm, rem, rst, sto, sst, cok = await self._serve_split(
                payload, sub, is_greg, ge, gd, glob_cached[idx], sub_sk,
                sub_eng, ingress,
            )
            status[idx] = st
            out_lim[idx] = lm
            remaining[idx] = rem
            reset[idx] = rst
            stored[idx] = sto
            stored_st[idx] = sst
            cap_ok[idx] = cok
            self._touch_captures(sub, sub_sk, sub_eng)
            sub_errs = self._error_strings(sub, err_extra)
            for j, i in enumerate(idx):
                if sub_errs[j]:
                    errs[int(i)] = sub_errs[j]
            if sub_sk is not None:
                for i in idx[sub_sk]:
                    metas[int(i)] = _TIER_SKETCH_FRAME
            # Metric parity with the object path's routing: non-owned
            # GLOBAL reads and engine-served lanes count as "global",
            # everything else owner-side counts as "local".
            n_glob = int(glob_cached[idx].sum()) + (
                int(sub_eng.sum()) if sub_eng is not None else 0
            )
            m = self.s.metrics.getratelimit_counter
            if n_glob:
                m.labels("global").inc(n_glob)
            if len(idx) - n_glob:
                m.labels("local").inc(len(idx) - n_glob)

        def assemble(addr: bytes, idx: np.ndarray, raw: bytes, rc,
                     lo: int) -> None:
            """This forward's answers: `len(idx)` of the batch's, from
            `lo` (the batcher parsed them once for all it coalesced)."""
            hi = lo + len(idx)
            status[idx] = rc.status[lo:hi]
            out_lim[idx] = rc.limit[lo:hi]
            remaining[idx] = rc.remaining[lo:hi]
            reset[idx] = rc.reset_time[lo:hi]
            owner_frame = self._owner_frame(addr)
            for j, i in enumerate(idx, lo):
                i = int(i)
                if rc.err_len[j]:
                    o = int(rc.err_off[j])
                    errs[i] = raw[o:o + int(rc.err_len[j])]
                # Splice the owner's metadata frames verbatim (tier tags
                # etc.), then append this hop's owner annotation.
                m = b""
                if rc.meta_len[j] > 0:
                    o = int(rc.meta_off[j])
                    m = raw[o:o + int(rc.meta_len[j])]
                metas[i] = m + owner_frame

        async def forward(peer, idx: np.ndarray) -> None:
            import grpc as grpc_mod

            from gubernator_tpu.net.peer_client import PeerNotReadyError

            addr = peer.info().grpc_address.encode()
            with stages.stage("peer.splice", "peer"):
                sub_pay = b"".join(
                    payload[cols.msg_off[i]:cols.msg_off[i] + cols.msg_len[i]]
                    for i in idx
                )
            self.s.metrics.getratelimit_counter.labels("forward").inc(
                len(idx)
            )
            try:
                # Through the peer's batcher (net/peer_client.py): what
                # concurrent client RPCs send this owner shares one
                # GetPeerRateLimits.  peer.forward is timed where that
                # RPC is made, readiness gate and re-asks included, and
                # counts its checks there.
                raw, rc, lo = await peer.forward_raw(
                    sub_pay, len(idx), deadline=deadline,
                    batch=not (cols.behavior[idx] & _NO_BATCHING).any(),
                )
            except Exception as e:  # noqa: BLE001
                # Retry ONLY the failures the object path retries
                # (NotReady / UNAVAILABLE / CANCELLED, which _forward
                # re-resolves with backoff — gubernator.go:382-395).
                # Anything else may follow a delivered batch, and a
                # re-send would double-count the hits.
                retriable = isinstance(e, PeerNotReadyError) or (
                    isinstance(e, grpc_mod.aio.AioRpcError)
                    and e.code() in (
                        grpc_mod.StatusCode.UNAVAILABLE,
                        grpc_mod.StatusCode.CANCELLED,
                    )
                )
                if retriable:
                    stages.tally("peer", "peer.forward", retried=1)
                    await forward_fallback(peer, idx)
                else:
                    # (A timeout here is one nobody may ask again:
                    # the client's deadline or the tries are spent, or
                    # the peer never said it applies an id once.  An
                    # answer that ARRIVED with a wrong count is never
                    # sent again either: the peer applied the batch.)
                    stages.tally("peer", "peer.forward", refused=1)
                    msg = (
                        "Error while fetching rate limit from peer "
                        f"'{peer.info().grpc_address}': {e}"
                    ).encode()
                    for i in idx:
                        errs[int(i)] = msg
                return
            with stages.stage("peer.assemble", "peer"):
                assemble(addr, idx, raw, rc, lo)

        async def forward_fallback(peer, idx: np.ndarray) -> None:
            """Re-route failed forwards through the object path's retry
            loop (ownership changes, NotReady backoff — service._forward).
            """
            async def one(i: int) -> None:
                req = self._decode_req(payload, cols, i)
                resp = await self.s._forward(peer, req, req.hash_key())
                status[i] = int(resp.status)
                out_lim[i] = resp.limit
                remaining[i] = resp.remaining
                reset[i] = resp.reset_time
                if resp.error:
                    errs[i] = resp.error.encode()
                if resp.metadata:
                    metas[i] = b"".join(
                        native.meta_frame(k.encode(), v.encode())
                        for k, v in resp.metadata.items()
                    )

            await asyncio.gather(*(one(int(i)) for i in idx))

        async def serve_mirror(idx: np.ndarray) -> None:
            """Hot lanes served from the local mirror allowance
            (service._mirror_serve: bounded carve-out + async
            reconcile to the owner)."""
            async def one(i: int) -> None:
                req = self._decode_req(payload, cols, i)
                resp = await self.s._mirror_serve(
                    req, peers[int(owner[i])]
                )
                status[i] = int(resp.status)
                out_lim[i] = resp.limit
                remaining[i] = resp.remaining
                reset[i] = resp.reset_time
                if resp.error:
                    errs[i] = resp.error.encode()
                if resp.metadata:
                    metas[i] = b"".join(
                        native.meta_frame(k.encode(), v.encode())
                        for k, v in resp.metadata.items()
                    )

            await asyncio.gather(*(one(int(i)) for i in idx))

        # The hop's tasks start at the loop's next turn, so the RPC's own
        # lanes are enqueued first, as when one gather ran them all.
        hops = [
            asyncio.ensure_future(forward(peer, idx))
            for peer, idx in forwards
        ]
        if mirror_mask is not None:
            hops.append(asyncio.ensure_future(
                serve_mirror(np.flatnonzero(mirror_mask))
            ))
        try:
            if len(local_idx):
                await serve_local(local_idx)
            elif ingress is not None:
                # It owns none of its checks: nothing will enqueue, and
                # wire.ingress must not stretch over the forwards.
                ingress.end()
            if hops:
                # wire.peer_wait: what the forwards take beyond the
                # RPC's own lanes (nothing, if they answered first).
                waited = stages.begin(
                    "wire.peer_wait", "wire", tracing.current_context()
                )
                try:
                    await asyncio.gather(*hops)
                finally:
                    waited.end()
        except BaseException:
            for t in hops:
                t.cancel()
            raise
        egress = stages.begin(
            "wire.egress", "wire", tracing.current_context()
        )

        if is_global.any():
            # Deferred GLOBAL replication (gubernator.go:429-432, 617):
            # non-owned keys queue their hits toward the owner; owned keys
            # queue broadcast updates.  Owner metadata on the served reads.
            gc_idx = np.flatnonzero(glob_cached & (cols.err == 0))
            for i in gc_idx:
                metas[int(i)] = self._owner_frame(
                    peers[int(owner[int(i)])].info().grpc_address.encode()
                )
            self._queue_global(payload, cols, gc_idx)
            if self.s.global_engine is None:
                # Owner-side updates broadcast via the RPC manager only
                # when no collective engine owns replication (the engine
                # broadcasts through sync + the _engine_synced bridge).
                self._queue_global_updates(
                    payload, cols, is_global, owned=owned,
                    capture=(stored_st, stored, reset, out_lim, cap_ok),
                )

        mr = (cols.behavior & _MULTI_REGION) != 0
        if mr.any():
            # Owner-side queueing only: non-owned lanes were forwarded
            # (the owner's peer-RPC lane queues them), and non-owned
            # GLOBAL cached reads don't queue (the object path's
            # `if cached: continue`, service._check_local).
            self._queue_multiregion(
                payload, cols,
                np.flatnonzero(mr & owned & (cols.err == 0)),
            )

        err_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(e) for e in errs], out=err_off[1:])
        meta_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(m) for m in metas], out=meta_off[1:])
        self.served += n
        out = native.serialize_resps(
            status, out_lim, remaining, reset,
            b"".join(errs), err_off, b"".join(metas), meta_off,
        )
        egress.end()
        return out

    # -- persistence SPI on the lane -------------------------------------
    def _persist_decode(self, entries) -> Dict[int, list]:
        """Per-unique-key request decodes for the persistence SPI
        (Store.get / Store.on_change / the Loader keymap take Python
        objects — the one per-KEY host cost the lane pays with
        persistence attached; everything else stays columnar).

        Returns fp(int64) -> [hash_key_str, first_req, capture_req],
        in first-arrival entry order.  `capture_req` is None when every
        occurrence is a GLOBAL cached read (use_cached) — such keys are
        excluded from write-through like _capture_write_through."""
        uniq: Dict[int, list] = {}
        for e in entries:
            valid = np.flatnonzero(e.cols.hash != 0)
            for req, group in self._decode_unique(e.payload, e.cols, valid):
                fp = int(e.cols.hash[group[0]])
                uc = e.use_cached[group]
                cap = None
                if not uc.all():
                    cap = req if not uc[0] else self._decode_req(
                        e.payload, e.cols, int(group[~uc][0])
                    )
                cur = uniq.get(fp)
                if cur is None:
                    uniq[fp] = [req.hash_key(), req, cap]
                elif cur[2] is None and cap is not None:
                    cur[2] = cap
        return uniq

    def _note_spill_pressure(self, entries, h_mach, foundv, persv) -> None:
        """Feed the sketch tier's dynamic-spillover policy with this
        drain's per-name exact-tier pressure (SketchTierConfig
        spill_inserts/spill_transients): insert lanes' key fingerprints
        (the backend's per-name HyperLogLog turns them into a DISTINCT-
        key estimate, immune to expiry/re-insert churn) and slot-denied
        transients (full-bucket pressure).  `h_mach` is the machinery
        hash column (cascade-diverted lanes zeroed — they had no device
        round).  One sort groups hot lanes by name — no per-name array
        scans (a name-sweep attack makes U ≈ n) — and name strings
        decode lazily, only for threshold-crossing names."""
        if len(entries) == 1:
            names = entries[0].cols.name_hash
        else:
            names = np.concatenate(
                [e.cols.name_hash for e in entries]
            )
        act = h_mach != 0
        ins = act & (foundv == 0) & (persv != 0)
        tra = act & (persv == 0)
        hot = np.flatnonzero(ins | tra)
        if not len(hot):
            return
        order = hot[np.argsort(names[hot], kind="stable")]
        ns = names[order]
        bounds = np.flatnonzero(
            np.concatenate([[True], ns[1:] != ns[:-1]])
        )
        items = []
        first_idx: Dict[int, int] = {}
        for b_i, lo in enumerate(bounds):
            hi = bounds[b_i + 1] if b_i + 1 < len(bounds) else len(order)
            grp = order[lo:hi]
            nh = int(ns[lo])
            first_idx[nh] = int(grp[0])
            items.append((
                nh,
                h_mach[grp[ins[grp]]],
                int(tra[grp].sum()),
            ))

        def decode_names(nh: int) -> str:
            i0 = first_idx[nh]
            off = 0
            for e in entries:
                if i0 < off + e.cols.n:
                    return self._decode_req(
                        e.payload, e.cols, i0 - off
                    ).name
                off += e.cols.n
            raise AssertionError("index outside drain")

        self.s.sketch_backend.note_exact_pressure_batch(
            items, decode_names
        )

    def _repair_cold_store_keys(
        self, backend, uniq, cols, answers, now_ms,
    ):
        """Post-step Store.get for COLD keys (backend lock held, response
        already fetched): the step's `found` column replaces the pre-step
        residency probe — a warm store drain pays no probe fetch at all.

        A key whose first occurrence missed (`found` False: absent or
        expired, exactly the probe's liveness test) consults the Store
        (algorithms.go:45-51).  Live store state REPAIRS the drain: the
        store row replaces the fresh bucket the step created (load_rows
        overwrites in place on key match — the fresh row's decrements are
        discarded), every occurrence of the key re-runs on the seeded
        row, and the re-run's responses overwrite the originals — the
        final row and responses are bit-identical to the object path's
        seed-then-step.  The optimistic capture pre-dates the repair, so
        the caller refetches it (packed here with the repair responses:
        a COLD drain pays 2 fetches, matching the old probe path; warm
        drains pay 1).  The lone divergence from seed-then-step: under
        full-bucket insert pressure the fresh insert or the repair upsert
        may each go transient — the same acceptable-loss corner every
        insert path shares (architecture.md:5-11).

        `cols` are the drain's eleven columns as native.pack_rounds takes
        them (hash first), `answers` the gather's int64[9, n] block, whose
        rows the re-run's answers overwrite in place.  Returns None when
        nothing needed repair, else (new capture token, its prefetched
        int host chunks)."""
        from gubernator_tpu.runtime.backend import (
            _packed_resp_dict,
            fetch_ravel,
        )

        h, foundv = cols[0], answers[5]
        uq, first = np.unique(h, return_index=True)
        fidx = dict(zip(uq.tolist(), first.tolist()))
        fps = list(uniq.keys())
        seeded = backend._store_seed_misses(
            [int(np.int64(fp).view(np.uint64)) for fp in fps],
            [uniq[fp][1] for fp in fps],
            [bool(foundv[fidx[fp]]) for fp in fps],
            now_ms,
        )
        if not seeded:
            return None
        rep_fps = [fps[i] for i in seeded]
        R = np.flatnonzero(np.isin(h, np.array(rep_fps, dtype=np.int64)))
        # Every occurrence of the seeded keys again, a round apart.
        r_cols = [c[R] for c in cols]
        r_packed = self._pack_rounds(*r_cols, mode=0)
        r_resps = backend._dispatch_rounds_locked(r_packed.rounds, now_ms)
        cap_fps = np.array(
            [fp for fp, v in uniq.items() if v[2] is not None],
            dtype=np.int64,
        )
        cap_token = backend._gather_rows_dispatch(cap_fps, now_ms)
        cap_ints = backend._gather_rows_int_arrays(cap_token)
        hosts = fetch_ravel(list(r_resps) + cap_ints)
        nr = len(r_resps)
        again = native.gather_rounds(
            r_packed, r_cols[0],
            [_resp_words(_packed_resp_dict(a)) for a in hosts[:nr]],
        )
        # The answers a request reads, and the capture's: `persisted` and
        # `found` (rows 4, 5) stay the first run's, what the tallies count.
        for row in (0, 1, 2, 3, 6, 7, 8):
            answers[row][R] = again.cols[row]
        return cap_token, hosts[nr:]

    def _build_captured(self, uniq, cap_fps, a, rf) -> list:
        """CacheItems from the packed gather columns (GATHER_ROW_FIELDS
        order) — misses and KIND_CACHED_RESP rows are skipped exactly like
        _read_items_locked."""
        from gubernator_tpu.core.types import Algorithm, CacheItem, Status
        from gubernator_tpu.ops.state import KIND_CACHED_RESP

        out = []
        for j, fp in enumerate(cap_fps):
            if not a[0, j] or a[1, j] == KIND_CACHED_RESP:
                continue
            key, _req, cap_req = uniq[int(fp)]
            algo = Algorithm(int(a[2, j]))
            remaining = (
                float(rf[j]) if algo == Algorithm.LEAKY_BUCKET
                else int(a[5, j])
            )
            out.append((cap_req, CacheItem(
                key=key,
                algorithm=algo,
                expire_at=int(a[9, j]),
                limit=int(a[3, j]),
                duration=int(a[4, j]),
                remaining=remaining,
                created_at=int(a[6, j]),
                status=Status(int(a[7, j])),
                burst=int(a[8, j]),
            )))
        return out

    # -- merge processing (runs on _pool threads via _Coalescer) ---------
    def _sketch_process(self, entries: Sequence["_SketchEntry"]):
        """One CMS dispatch for a drained sketch-entry list (cross-RPC
        coalescing; duplicate keys landing in one device chunk share its
        pre-chunk estimate — the CMS's documented batch-granularity
        approximation).  Dispatch stage: concat + device dispatch under
        the sketch lock; the returned closure is the fetch stage."""
        with tracing.stage("lane.pack"):
            if len(entries) == 1:
                e = entries[0]
                kh, hh, ll = e.kh, e.hits, e.limits
            else:
                kh = np.concatenate([e.kh for e in entries])
                hh = np.concatenate([e.hits for e in entries])
                ll = np.concatenate([e.limits for e in entries])
        with tracing.stage("backend.dispatch"):
            fetch_cols = self.s.sketch_backend.check_cols_begin(kh, hh, ll)

        def fetch() -> List[Tuple[np.ndarray, ...]]:
            with tracing.stage("backend.d2h_wait"):
                self.blocking_fetches["sketch"] += 1
                st, rem, rst = fetch_cols()
            outs: List[Tuple[np.ndarray, ...]] = []
            off = 0
            for e in entries:
                k = len(e.kh)
                outs.append((st[off:off + k], rem[off:off + k],
                             rst[off:off + k]))
                off += k
            return outs

        return fetch

    def _process(self, entries: Sequence["_Entry"]):
        # lane.pack: from here to where the step is handed to the device
        # (_process_packed ends it there; the `finally` only on a raise).
        pack = tracing.stage("lane.pack")
        try:
            return self._process_packed(entries, pack)
        finally:
            pack.end()

    def _process_packed(self, entries: Sequence["_Entry"], pack):
        """Pack -> step for a coalesced entry list (runs on a fast-lane
        pool thread; everything here is numpy/C++/device).  This is the
        DISPATCH stage of the pipelined drain: it returns a zero-arg
        fetch closure (host sync + gather + persistence delivery) that
        the coalescer runs on its fetch stage, so the next merge's
        dispatch overlaps this merge's device->host readback.

        Duplicate-heavy batches (Zipfian hot keys) would otherwise explode
        into one device round PER OCCURRENCE of the hottest key; eligible
        duplicate groups (_plan_cascade states the rule; the native pack
        applies it) instead take the host-cascade path: one read lane, an
        exact host-side replay of the per-occurrence algorithm branches,
        and one effective write-back lane — two rounds total regardless
        of skew.

        A drain cascades only where that saves a device launch
        (_cascade_or_rounds states that rule): the cascade pays for its
        rounds with the response fetch INSIDE the backend lock and the
        dispatch stage, so
        a drain whose duplicates fit the rounds it takes anyway (one pair
        among 5,000 checks at batch_size 4096: two rounds either way)
        drops the plan and is a plain merge, each occurrence on a device
        lane of a later round than the one before it, the fetch on the
        fetch stage.  A store drain fetches inside the lock either way
        and keeps its plan."""
        backend = self.s.backend
        n_shards = backend.cfg.num_shards

        if len(entries) == 1:
            c = entries[0].cols
            h, hits, lim, dur = c.hash, c.hits, c.limit, c.duration
            algo, burst, behavior = c.algo, c.burst, c.behavior
            is_greg = entries[0].is_greg
            ge, gd = entries[0].greg_expire, entries[0].greg_duration
            use_cached = entries[0].use_cached
        else:
            h = np.concatenate([e.cols.hash for e in entries])
            hits = np.concatenate([e.cols.hits for e in entries])
            lim = np.concatenate([e.cols.limit for e in entries])
            dur = np.concatenate([e.cols.duration for e in entries])
            algo = np.concatenate([e.cols.algo for e in entries])
            burst = np.concatenate([e.cols.burst for e in entries])
            behavior = np.concatenate([e.cols.behavior for e in entries])
            is_greg = np.concatenate([e.is_greg for e in entries])
            ge = np.concatenate([e.greg_expire for e in entries])
            gd = np.concatenate([e.greg_duration for e in entries])
            use_cached = np.concatenate([e.use_cached for e in entries])

        store = backend.store
        uniq = (
            self._persist_decode(entries)
            if (store is not None or backend._keymap is not None)
            else None
        )
        if uniq and backend._keymap is not None:
            with backend._keymap_lock:
                km = backend._keymap
                for fp, (key, _r, _c) in uniq.items():
                    km[int(np.int64(fp).view(np.uint64))] = key
            backend._maybe_prune_keymap()
        do_store = store is not None and bool(uniq)

        # The whole of the pack in one native call, the GIL released: the
        # eligible duplicate groups (_plan_cascade's rule), whether they
        # cascade (_cascade_or_rounds' rule; a store drain's always do),
        # the (round, lane) of every check and the rounds as the device
        # takes them, burst defaults and the RESET_REMAINING bit applied
        # on the way.
        packed = self._pack_rounds(
            h, hits, lim, dur, algo, burst, behavior, is_greg, ge, gd,
            use_cached, mode=2 if do_store else 1, cap_ok=True,
        )
        rounds = packed.rounds
        plan = None
        if packed.cascades:
            plan = _CascadePlan(
                occ=packed.occ, firsts=packed.firsts,
                order=packed.order, bounds=packed.bounds,
            )
            # What the replay reads of a group's first occurrence.
            burst = np.where(burst == 0, lim, burst)
            # What it will serve, for the lane.cascade row.
            casc_counts = dict(
                groups=packed.groups, occ=packed.occ_total,
                peeks=packed.peeks,
            )
        elif packed.groups:
            # The duplicates ride the rounds the drain has anyway.
            pack.tally(
                dup_plain=1, dup_lanes=packed.occ_total - packed.groups
            )

        t_step0 = time.monotonic()
        got: List = []  # [GatheredDrain] once the response is on the host

        def gather(host) -> None:
            got.append(native.gather_rounds(
                packed, h, [_resp_words(hr) for hr in host]
            ))

        def finish(unpack) -> List[Tuple[np.ndarray, ...]]:
            return self._finish_process(
                entries, packed, got[0], h, unpack,
                replayed=plan is not None or do_store, t_step0=t_step0,
            )

        if plan is None and not do_store:
            # Plain merge: dispatch under the backend lock; the response
            # sync rides the coalescer's FETCH stage, so the next
            # maximal merge dispatches while this one's response syncs
            # (depth bounded by `pipeline_depth`).
            pack.end()
            fetch_host = backend.step_rounds_begin(
                rounds, add_tally=False
            )

            def fetch_plain() -> List[Tuple[np.ndarray, ...]]:
                host = fetch_host()
                self.blocking_fetches["mach"] += 1
                with tracing.stage("lane.unpack") as unpack:
                    gather(host)
                    return finish(unpack)

            return fetch_plain

        # Cascade merge: the read -> host replay -> write-back window
        # must not interleave with ANY other step on these keys — from
        # this lane, the object path, or the GLOBAL managers — so the
        # whole window runs under the backend lock (the same
        # single-writer discipline as every other mutation path).  The
        # write-back itself needs no response sync: the replay already
        # produced every response, and dispatch order serializes it.
        #
        # Store drains take this branch too, with NO pre-step
        # residency probe: the step itself answers residency through
        # its `found` column, so a warm drain pays ONE combined
        # response+capture fetch — storeless parity — instead of the
        # probe fetch + combined fetch it used to (algorithms.go:45-51
        # consults the store only on cache miss; misses repair below).
        # The lock is held through the fetch: a cold key was served
        # from a FRESH row that the repair replaces, and no other
        # drain may observe the interim state.  These in-lock fetches
        # belong to the DISPATCH stage by necessity; what moves to the
        # fetch stage is the rf fetch + write-through delivery below.
        if n_shards > 1:
            from gubernator_tpu.parallel.sharded import (
                packed_grid_rounds_to_host as to_host,
            )
        else:
            from gubernator_tpu.runtime.backend import (
                packed_rounds_to_host as to_host,
            )
        cap_token = wt_seq = None
        cap_fps = int_hosts = None
        pack.end()
        self.blocking_fetches["mach"] += 1
        lock_wait = tracing.stage("backend.lock_wait")
        with backend._lock:
            lock_wait.end()
            # One clock a drain: the read rounds, the write-back rounds,
            # a repair's rounds and the store capture all run under this
            # reading.  A write-back under a later one would find a token
            # window ended, or a leak completed, in the gap its own fetch
            # and replay took — and spend the group's hits in a window no
            # RPC was answered in (PERF.md section 7, PR 33).
            now_ms = backend.clock.millisecond_now()
            resps = backend._dispatch_rounds_locked(rounds, now_ms)
            if plan is not None:
                host = to_host(resps)
                cascade = tracing.stage("lane.cascade")
                cascade.tally(**casc_counts)
                gather(host)
                (status, out_lim, remaining, reset, _persv, foundv,
                 stored, cachedv, stored_st) = got[0].cols
                wb = _run_cascade(
                    plan, h, hits, lim, dur, algo, burst,
                    status, out_lim, remaining, reset, stored, cachedv,
                    foundv, stored_st,
                )
                if wb is None:
                    cascade.end()
                else:
                    # The write-back's lanes: no flag set, and a group's
                    # two lanes (what it spent, then its status flip) in
                    # that order, a round apart.
                    wb_packed = self._pack_rounds(
                        *wb, None, None, None, None, None, mode=0
                    )
                    cascade.tally(wb_lanes=len(wb[0]))
                    cascade.end()
                    backend._dispatch_rounds_locked(
                        wb_packed.rounds, now_ms
                    )
            if do_store:
                from gubernator_tpu.runtime.backend import (
                    _packed_resp_dict,
                    fetch_ravel,
                )

                cap_fps = np.array(
                    [fp for fp, v in uniq.items() if v[2] is not None],
                    dtype=np.int64,
                )
                # Optimistic capture: dispatched with the step so the
                # warm path fetches response + capture in ONE
                # round-trip; a repair below re-dispatches it.
                cap_token = backend._gather_rows_dispatch(
                    cap_fps, now_ms
                )
                cap_ints = backend._gather_rows_int_arrays(cap_token)
                if plan is None:
                    hosts = fetch_ravel(list(resps) + cap_ints)
                    nr = len(resps)
                    gather([_packed_resp_dict(hh) for hh in hosts[:nr]])
                    int_hosts = hosts[nr:]
                else:
                    int_hosts = fetch_ravel(cap_ints)
                rep = self._repair_cold_store_keys(
                    backend, uniq,
                    (h, hits, lim, dur, algo, burst, behavior, is_greg,
                     ge, gd, use_cached),
                    got[0].cols, now_ms,
                )
                if rep is not None:
                    # Rows changed under the optimistic capture —
                    # refetch it (packed with the repair responses
                    # inside _repair_cold_store_keys).
                    cap_token, int_hosts = rep
                wt_seq = backend._wt_ticket()

        def fetch_locked_merge() -> List[Tuple[np.ndarray, ...]]:
            # Fetch stage of a cascade/store merge: the response host
            # sync already happened inside the lock (cascade/repair
            # correctness); what remains is the remaining_f fetch, the
            # capture build, and the Store.on_change delivery — user
            # code plus a ticket wait that must never block the next
            # merge's dispatch.
            if do_store:
                from gubernator_tpu.runtime.backend import fetch_ravel

                captured: list = []
                try:
                    rf_hosts = None
                    if bool((algo == 1).any()):
                        # The leaky-capture remaining_f readback
                        # (ordering-free, so it needn't sit in the lock).
                        self.blocking_fetches["mach"] += 1
                        rf_hosts = fetch_ravel(
                            backend._gather_rows_rf_arrays(cap_token)
                        )
                    a_cols, rf_col = backend._gather_rows_build(
                        cap_token, len(cap_fps), int_hosts, rf_hosts
                    )
                    captured = self._build_captured(
                        uniq, cap_fps, a_cols, rf_col
                    )
                finally:
                    # The ticket MUST be redeemed even if any fetch
                    # fails (the step already happened; a skipped
                    # redemption wedges every later delivery in
                    # cond.wait) — hence the rf sync sits INSIDE this
                    # try as well.
                    backend._deliver_write_through(captured, wt_seq)
            with tracing.stage("lane.unpack") as unpack:
                return finish(unpack)

        return fetch_locked_merge

    def _finish_process(
        self, entries, packed, gathered, h, unpack, replayed, t_step0,
    ) -> List[Tuple[np.ndarray, ...]]:
        """Shared tail of a machinery merge's fetch stage: tallies,
        flight-recorder record, spill pressure and the per-entry split,
        from what the native pack (`packed`: the assignment, the GLOBAL
        capture-validity mask) and gather (`gathered`: the nine columns
        a check, the sums over the device lanes) made.  `replayed`: a
        cascade's replay or a store repair wrote answers after the
        gather, so over-limit is counted from the status column."""
        from gubernator_tpu.runtime.backend import Tally

        backend = self.s.backend
        (status, out_lim, remaining, reset, persv, foundv, stored,
         _cachedv, stored_st) = gathered.cols
        # Device read lanes the step answered with `found` = 0: a key's
        # first arrival or, where windows elapse inside a run, a new
        # window opened (its own row expired).  Read from the fetched
        # response; no device output is added for it.
        unpack.tally(new_windows=gathered.lanes - gathered.cache_hits)
        # Metric parity: checks/over-limit from the per-REQUEST outputs
        # (cascade occurrences never had their own device lane); cache
        # hit/miss + eviction tallies from the device rounds.
        valid = h != 0
        n_over = (
            int((status[valid] == 1).sum()) if replayed
            else gathered.over_limit
        )
        backend._add_tally(Tally(
            checks=packed.valid,
            over_limit=n_over,
            not_persisted=gathered.not_persisted,
            cache_hits=gathered.cache_hits,
        ))
        fr = getattr(self.s.metrics, "flightrec", None)
        if fr is not None:
            fr.record_batch(
                packed.valid, (time.monotonic() - t_step0) * 1e3,
                over_limit=n_over, kind="fastlane_drain",
            )

        # Gubstat per-tenant ledger: same validity stance as the tally
        # above (per-request status column, errored lanes masked).
        # Fast-lane traffic is plane-direct — derived shadow keys are
        # only synthesized on the object path — and name strings decode
        # lazily, at most once per newly-admitted tenant.
        ta = getattr(self.s, "tenants", None)
        if ta is not None:
            if len(entries) == 1:
                t_names = entries[0].cols.name_hash
                t_hits = entries[0].cols.hits
            else:
                t_names = np.concatenate(
                    [e.cols.name_hash for e in entries]
                )
                t_hits = np.concatenate([e.cols.hits for e in entries])

            def _decode_tenant(i: int):
                off2 = 0
                for e in entries:
                    if i < off2 + e.cols.n:
                        return self._decode_req(
                            e.payload, e.cols, i - off2
                        ).name
                    off2 += e.cols.n
                return None

            ta.record_fast(t_names, t_hits, status, valid, _decode_tenant)

        sb = self.s.sketch_backend
        if sb is not None and sb.spill_enabled:
            # The hashes as the device saw them, not h: cascade-diverted
            # duplicate occurrences never got a device lane — their persv
            # stays 0 and raw h would count them as fake transients (a
            # healthy hot key would self-degrade under Zipfian traffic).
            self._note_spill_pressure(
                entries, np.where(packed.rnd >= 0, h, 0), foundv, persv
            )

        # Split back per entry (stored/stored_status/cap_ok feed the
        # GLOBAL broadcast capture; see _queue_global_updates).  cap_ok,
        # the capture's validity, is judged over the WHOLE merged drain
        # (entries are concurrent RPCs; a per-entry view would miss
        # another RPC's later occurrence of the same key): a lane may
        # capture only if it is its key's LAST mutating occurrence in the
        # merge.  Judged per drain — not at queue time — because entries
        # queue their updates in COMPLETION order (remote forwards differ
        # in latency), so a stale earlier occurrence could otherwise
        # overwrite a fresh capture; with this mask it degrades to
        # (req, None) instead, and the flush re-reads.  h == 0 lanes
        # (errored) mutate nothing and never capture.  The pack's one
        # hash map knows each key's last occurrence.
        cap_ok = packed.cap_ok
        outs: List[Tuple[np.ndarray, ...]] = []
        off = 0
        for e in entries:
            k = e.cols.n
            outs.append((
                status[off:off + k], out_lim[off:off + k],
                remaining[off:off + k], reset[off:off + k],
                stored[off:off + k], stored_st[off:off + k],
                cap_ok[off:off + k],
            ))
            off += k
        return outs

    async def close(self) -> None:
        # Machinery first (its in-flight dispatches may still fan into
        # the sketch lane), then the sketch lane; both refuse new work
        # the moment their close() starts.
        await self._mach.close()
        if self._sketch_lane is not None:
            await self._sketch_lane.close()
        if self._engine_lane is not None:
            await self._engine_lane.close()
        self._pool.shutdown(wait=True)
        self._sketch_pool.shutdown(wait=True)
        self._engine_pool.shutdown(wait=True)


class _Entry:
    """Machinery-lane coalescer entry (fut assigned by _Coalescer.do)."""

    __slots__ = (
        "payload", "cols", "is_greg", "greg_expire", "greg_duration",
        "use_cached", "fut", "trace_ctx",
    )

    def __init__(self, payload, cols, is_greg, greg_expire, greg_duration,
                 use_cached):
        self.payload = payload
        self.cols = cols
        self.is_greg = is_greg
        self.greg_expire = greg_expire
        self.greg_duration = greg_duration
        self.use_cached = use_cached
        self.fut = None
        self.trace_ctx = None


class _SketchEntry:
    """Sketch-lane coalescer entry (fut assigned by _Coalescer.do)."""

    __slots__ = ("kh", "hits", "limits", "fut", "trace_ctx")

    def __init__(self, kh, hits, limits):
        self.kh = kh
        self.hits = hits
        self.limits = limits
        self.fut = None
        self.trace_ctx = None


class _EngineEntry:
    """Engine-lane coalescer entry (fut assigned by _Coalescer.do)."""

    __slots__ = (
        "payload", "cols", "idx", "is_greg", "ge", "gd", "fut",
        "trace_ctx",
    )

    def __init__(self, payload, cols, idx, is_greg, ge, gd):
        self.payload = payload
        self.cols = cols
        self.idx = idx
        self.is_greg = is_greg
        self.ge = ge
        self.gd = gd
        self.fut = None
        self.trace_ctx = None


def _resp_words(hr) -> np.ndarray:
    """One round's fetched response as the native gather reads it: the
    int64[9, t] / [n_shards, 9, t] words `_packed_resp_dict` made its
    columns of, or, of a plain dict of columns (a test's, a harness's
    altered one), the columns stacked back into that layout."""
    from gubernator_tpu.runtime.backend import RESP_FIELDS

    words = getattr(hr, "words", None)
    if words is None:
        words = np.stack([hr[f] for f in RESP_FIELDS], axis=-2)
    return words


def _build_rounds(values, rnd, lane, sh_all, n_rounds, n_shards, B):
    """Scatter columnar values into fixed-shape DeviceBatch rounds.
    Returns (rounds, order, bounds) — order/bounds group request indices
    by round for the response gather."""
    ok = np.flatnonzero(rnd >= 0)
    order = ok[np.argsort(rnd[ok], kind="stable")]
    bounds = np.searchsorted(rnd[order], np.arange(n_rounds + 1))
    rounds: List[DeviceBatch] = []
    for r_idx in range(n_rounds):
        grid = _empty_batch((n_shards, B))
        sel = order[bounds[r_idx]:bounds[r_idx + 1]]
        s_m, l_m = sh_all[sel], lane[sel]
        for f, v in values.items():
            getattr(grid, f)[s_m, l_m] = v[sel]
        grid.active[s_m, l_m] = True
        rounds.append(
            grid if n_shards > 1 else DeviceBatch(*[a[0] for a in grid])
        )
    return rounds, order, bounds


class _CascadePlan:
    """The duplicate groups of a drain that the host cascade serves.  The
    served lane's comes from the native pack (native.PackedDrain), with
    the groups in ascending order of the signed hash; _plan_cascade
    builds the same from numpy, the reference the tests hold it to, and
    keeps np.unique's `groups` / `inv` / `first_idx` beside it."""

    __slots__ = ("occ", "firsts", "order", "bounds", "groups", "inv",
                 "first_idx")

    def __init__(self, occ, firsts, order, bounds, groups=None, inv=None,
                 first_idx=None):
        self.occ = occ          # bool[n]: occurrence is in a cascade group
        self.firsts = firsts    # int[G]: first-occurrence index per group
        # Group g's occurrences in arrival order:
        # order[bounds[g]:bounds[g + 1]].  _run_cascade sends the groups'
        # write-back lanes in this order of groups.
        self.order = order
        self.bounds = bounds
        self.groups = groups    # int[G]: group ids (into inv's codomain)
        self.inv = inv          # int[n]: np.unique inverse (key group id)
        self.first_idx = first_idx    # int[nb]: first occurrence per key


def _plan_cascade(h, hits, reset_remaining, is_greg, lim, dur, algo, burst,
                  use_cached):
    """Pick duplicate-key groups the host can serve without one device
    round per occurrence.

    Exact-cascade groups: >1 occurrence of a key where no occurrence
    has negative hits, RESET_REMAINING or a Gregorian duration, and all
    share limit/duration/algorithm/burst.  A peek (hits == 0) belongs:
    the read lane is one, and what a later one answers is the running
    state, unchanged (_run_cascade).  use_cached (GLOBAL
    non-owner) groups qualify too when the flag is UNIFORM across the
    group — the replay branches on the read lane's `cached` flag: a
    verbatim broadcast-row serve copies to every occurrence (the device
    mutates nothing on such reads), while a pre-broadcast bucket runs
    the standard lattice replay.  The per-occurrence branch order of
    the kernel (over-at-zero / exact / over-more / under) is a pure
    function of the running remaining, replayable on host from the
    read lane's post-step `stored` value.

    Mixed cached/uncached groups (ownership changed mid-stream) and
    everything else keep the round-per-occurrence machinery."""
    uniq, first_idx, inv, counts = np.unique(
        h, return_index=True, return_inverse=True, return_counts=True
    )
    dup = (counts > 1) & (uniq != 0)
    if not dup.any():
        return None
    nb = len(uniq)
    same = np.ones(nb, dtype=bool)
    for arr in (lim, dur, burst, algo.astype(np.int64)):
        diff = arr != arr[first_idx][inv]
        same &= np.bincount(
            inv, weights=diff.astype(np.float64), minlength=nb
        ) == 0
    cached_mixed = (
        use_cached != use_cached[first_idx][inv]
    )
    same &= np.bincount(
        inv, weights=cached_mixed.astype(np.float64), minlength=nb
    ) == 0

    bad_occ = (hits < 0) | reset_remaining | is_greg
    grp_bad = np.bincount(
        inv, weights=bad_occ.astype(np.float64), minlength=nb
    ) > 0
    casc = dup & ~grp_bad & same

    if not casc.any():
        return None
    occ = casc[inv]
    at = np.flatnonzero(occ)
    return _CascadePlan(
        occ=occ,
        firsts=first_idx[casc],
        # Occurrence lists per group, in arrival order, via one argsort.
        order=at[np.argsort(inv[at], kind="stable")],
        bounds=np.concatenate([[0], np.cumsum(counts[casc])]),
        groups=np.flatnonzero(casc),
        inv=inv,
        first_idx=first_idx,
    )


def _read_lanes(plan, h):
    """The drain's hashes as a cascade merge sends them to the device:
    every occurrence of a cascade group diverted (0) but its first, the
    group's one READ lane."""
    h_mach = h.copy()
    h_mach[plan.occ] = 0
    h_mach[plan.firsts] = h[plan.firsts]
    return h_mach


def _cascade_or_rounds(plan, h, h_mach, use_cached, shards, n_shards, B):
    """Whether a drain that holds eligible duplicate groups (`plan`) takes
    the host cascade: only where that saves a device launch.

    `native.assign_rounds` already places occurrence k of a key in a later
    round than k-1, so the drain's own hashes `h` take `plain_rounds`
    launches with no help; the cascade takes the rounds of `h_mach`
    (_read_lanes) plus one for the write-back, which a drain whose
    groups are all `use_cached` never sends.  The cascade also holds the
    response fetch inside `backend._lock` and the coalescer's serial
    dispatch stage, so a tie goes plain: one pair among 5,000 checks at
    batch_size 4096 is two rounds either way, a pair in a drain of one
    round a second 128-lane launch either way.  Where the hottest key
    comes three times or more (zipfian traffic, a token held by many)
    the cascade's two rounds win.

    Returns (cascades, (rnd, lane, n_rounds)): the assignment of the
    path chosen — of `h_mach` where it cascades, of `h` where not."""
    plain = native.assign_rounds(h, shards, n_shards, B)
    reads = native.assign_rounds(h_mach, shards, n_shards, B)
    write_back = 0 if use_cached[plan.firsts].all() else 1
    if plain[2] > reads[2] + write_back:
        return True, reads
    return False, plain


def _reference_pack(cols, n_shards, B, tiers, mode, shard_shift):
    """A drain's pack as numpy made it until PR 42, whole: what
    native.pack_rounds (gub_pack_rounds) is held to bit for bit
    (tests/test_pack_native.py) and timed against
    (scripts/pack_bench.py).  `cols`, `mode`, `shard_shift` as
    native.pack_rounds takes them.  Serves nothing."""
    from gubernator_tpu.parallel.sharded import pack_grid_batch
    from gubernator_tpu.runtime.backend import pack_batch_q, tier_of

    h, hits, lim, dur, algo, burst, behavior, is_greg, ge, gd, cached = cols
    n = len(h)
    burst = np.where(burst == 0, lim, burst)
    reset_remaining = (behavior & int(Behavior.RESET_REMAINING)) != 0
    if n_shards > 1:
        sh_all = (
            (h.view(np.uint64) >> np.uint64(shard_shift))
            % np.uint64(n_shards)
        ).astype(np.int32)
    else:
        sh_all = np.zeros(n, dtype=np.int32)
    shards = sh_all if n_shards > 1 else None
    groups = _plan_cascade(
        h, hits, reset_remaining, is_greg, lim, dur, algo, burst, cached
    ) if mode else None
    plan, h_mach, hits_mach, assigned = groups, h, hits, None
    if plan is not None:
        h_mach = _read_lanes(plan, h)
        cascades = True
        if mode == 1:
            cascades, assigned = _cascade_or_rounds(
                plan, h, h_mach, cached, shards, n_shards, B
            )
        if not cascades:
            plan, h_mach = None, h
    if plan is not None:
        hits_mach = hits.copy()
        hits_mach[plan.firsts] = 0    # the read lane spends nothing
    rnd, lane, n_rounds = assigned or native.assign_rounds(
        h_mach, shards, n_shards, B
    )
    values = dict(
        key_hash=h_mach, hits=hits_mach, limit=lim, duration=dur,
        algo=algo, burst=burst, reset_remaining=reset_remaining,
        is_greg=is_greg, greg_expire=ge, greg_duration=gd,
        use_cached=cached,
    )
    rounds, order, bounds = _build_rounds(
        values, rnd, lane, sh_all, n_rounds, n_shards, B
    )
    # backend.dispatch's copy, under backend._lock, cut to the tier.
    pack = pack_grid_batch if n_shards > 1 else pack_batch_q
    words = [pack(db)[..., :tier_of(db.active, tiers)] for db in rounds]
    return dict(
        words=words, rounds=rounds, rnd=rnd, lane=lane, order=order,
        bounds=bounds, sh_all=sh_all, h_mach=h_mach, groups=groups,
        cascades=plan is not None,
    )


def _reference_unpack(ref, h, host, n_shards):
    """A drain's unpack as numpy and Python made it until PR 42, over
    `_reference_pack`'s `ref` and the rounds' host response dicts: the
    nine columns a check, the tallies' sums and `cap_ok`; what
    native.gather_rounds (and the pack's `cap_ok`) are held to."""
    from gubernator_tpu.runtime.backend import (
        RESP_FIELDS,
        tally_from_rounds,
    )

    n = len(h)
    cols = {f: np.zeros(n, dtype=np.int64) for f in RESP_FIELDS}
    order, bounds, lane = ref["order"], ref["bounds"], ref["lane"]
    for r_idx, hr in enumerate(host):
        sel = order[bounds[r_idx]:bounds[r_idx + 1]]
        if n_shards > 1:
            idx = (ref["sh_all"][sel], lane[sel])
        else:
            idx = (lane[sel],)
        for f in RESP_FIELDS:
            cols[f][sel] = hr[f][idx]
    t = tally_from_rounds(ref["rounds"], host)
    h_mach = ref["h_mach"]
    cap_ok = np.zeros(n, dtype=bool)
    last_of: Dict[int, int] = {}
    for j in np.flatnonzero(h != 0):
        last_of[int(h[j])] = int(j)
    cap_ok[list(last_of.values())] = True
    return cols, dict(
        over_limit=int((cols["status"][h_mach != 0] == 1).sum()),
        not_persisted=t.not_persisted, cache_hits=t.cache_hits,
        lanes=t.checks,
        new_windows=int(((cols["found"] == 0) & (h_mach != 0)).sum()),
    ), cap_ok


def _run_cascade(plan, h, hits, lim, dur, algo, burst,
                 status, out_lim, remaining, reset, stored, cachedv,
                 foundv, stored_st=None):
    """Replay each cascade group's occurrences on host, writing their
    responses in place, and build the effective write-back columns.

    The replay is bit-exact against the kernel for eligible groups:
    token (algorithms.go:162-195) and leaky (algorithms.go:395-426) share
    the branch lattice over the running remaining, and leaky's float
    fraction is invariant under integer-hit subtraction so the integer
    `stored` seed suffices.  A read lane answered VERBATIM from a live
    broadcast row (`cachedv`, the GLOBAL non-owner steady state) copies
    its response to every occurrence with no write-back — the device
    mutates nothing on such reads, so each occurrence would read the
    identical row.  One branch is NOT on the lattice: a leaky bucket
    the read lane just CREATED (`foundv` 0) whose first occurrence asks
    for more than its burst is stored empty (algorithms.go:470-476),
    where an existing bucket's over-ask mutates nothing.  That branch
    tests the FIRST occurrence only: where the first is a peek, the
    read lane has created the bucket full exactly as that peek does,
    and a later over-ask meets an existing bucket.

    A peek (hits == 0) mutates nothing and is answered from the running
    state (ops/step.py: token h0 -> rem0, s_status, te_expire; leaky
    r_hits == 0 -> l_take subtracts 0.0, le_expire keeps s_expire): the
    sticky status with the replay's flips so far for token, UNDER for
    leaky, the running remaining, the reset time that remaining gives.
    It adds nothing to the write-back, and a leaky group's expiry is
    refreshed only where some occurrence spent; a group of peeks alone
    writes nothing back.

    Deliberate, documented divergences: the table's sticky Status field
    holds the write-back's value rather than the last occurrence's, a
    fully-drained leaky group's expiry refresh rides an over-limit
    touch lane, and a leaky group that re-creates a resident row of
    the other algorithm replays as an existing bucket."""
    wb_h: List[int] = []
    wb_hits: List[int] = []
    wb_lim: List[int] = []
    wb_dur: List[int] = []
    wb_algo: List[int] = []
    wb_burst: List[int] = []

    bounds = plan.bounds.tolist()
    for g in range(len(plan.firsts)):
        occ = plan.order[bounds[g]:bounds[g + 1]]
        fi = occ[0]
        if cachedv[fi]:
            # Verbatim broadcast-row serve: share, mutate nothing.
            rest = occ[1:]
            status[rest] = status[fi]
            out_lim[rest] = out_lim[fi]
            remaining[rest] = remaining[fi]
            reset[rest] = reset[fi]
            continue
        lim0 = int(lim[fi])
        algo0 = int(algo[fi])
        reset0 = int(reset[fi])
        r0 = int(stored[fi])
        leaky = algo0 == 1
        rate_i = int(float(dur[fi]) / float(lim0)) if (leaky and lim0) else 0
        # Token status is STICKY: under/exact occurrences report the
        # STORED status (te_resp_status = s_status in the kernel), which
        # only flips to OVER on an over-at-zero hit.  The read lane's
        # response status IS the stored status.  Leaky reports fresh.
        st0 = int(status[fi])
        flip = False  # an over-at-zero occurred (token stored -> OVER)
        spent = bool(hits[occ].any())  # not a group of peeks alone
        r = r0
        if leaky and not foundv[fi] and int(hits[fi]) > r:
            r = 0  # new bucket, over-asked: stored empty, reports 0
        for i in occ:
            hc = int(hits[i])
            if hc == 0:
                if i == fi:
                    continue  # the read lane WAS this peek: its answer
                st, rr = (0 if leaky else st0), r
            elif r == 0:
                if not leaky and not flip:
                    flip = True  # sticky stored-status transition
                    st0 = 1
                st, rr = 1, r
            elif r == hc:
                r = 0
                st, rr = (0 if leaky else st0), 0
            elif hc > r:
                st, rr = 1, r
            else:
                r -= hc
                st, rr = (0 if leaky else st0), r
            status[i] = st
            out_lim[i] = lim0
            remaining[i] = rr
            reset[i] = reset0 + (r0 - rr) * rate_i if leaky else reset0
        # Post-replay stored columns (the GLOBAL broadcast capture reads
        # the LAST occurrence): running remaining, and the sticky token
        # status st0 with replay flips applied (leaky stores UNDER).
        stored[occ] = r
        if stored_st is not None:
            stored_st[occ] = 0 if leaky else st0

        def wb_lane(h_val: int) -> None:
            wb_h.append(int(h[fi]))
            wb_hits.append(h_val)
            wb_lim.append(lim0)
            wb_dur.append(int(dur[fi]))
            wb_algo.append(algo0)
            wb_burst.append(int(burst[fi]))

        eff = r0 - r
        if eff > 0:
            wb_lane(eff)
        elif leaky and spent:
            # Over-limit "touch": refreshes the sliding expiry the way
            # every nonzero-hit occurrence does, mutating nothing else.
            wb_lane(int(burst[fi]) + 1)
        if flip:
            # Reproduce the stored-status flip on device: after the eff
            # lane drained the bucket to 0, one more hit is over-at-zero
            # — it stores OVER and mutates nothing else (a later batch's
            # under-branch response reports this stored status, so
            # skipping it would diverge from the object path).
            wb_lane(1)
    if not wb_h:
        return None
    return (
        np.array(wb_h, dtype=np.int64),
        np.array(wb_hits, dtype=np.int64),
        np.array(wb_lim, dtype=np.int64),
        np.array(wb_dur, dtype=np.int64),
        np.array(wb_algo, dtype=np.int32),
        np.array(wb_burst, dtype=np.int64),
    )
