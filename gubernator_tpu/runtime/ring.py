"""The ring drain discipline: a persistent device-loop runner that takes
host fetches off the request path (GUBER_SERVE_MODE=ring).

The classic and pipelined disciplines (runtime/fastpath._Coalescer) pay
one blocking device->host fetch per merge ON the request path — PR 5
overlapped those fetches across merges, but every merge still spends a
fetch cycle inside its own latency.  The ring discipline removes the
fetch from the request path entirely:

  request ring   — producers (fast-lane pool threads) pack a merge's
                   rounds into ring slots (`submit_rounds`) and return
                   immediately with a wait handle; a full ring blocks
                   the producer (backpressure, measured as slot-wait).
  device loop    — ONE runner thread drains queued slots into a single
                   bounded jitted scan (`ops/ring.ring_step`: donated
                   table, up to GUBER_RING_SLOTS rounds per iteration, a
                   monotonically increasing sequence word packed with
                   the responses), double-buffered: iteration N+1
                   dispatches before iteration N's responses are
                   fetched, so the device never waits on the host.
  response ring  — the runner fetches (responses, sequence word) in ONE
                   transfer, verifies the sequence advanced exactly by
                   the consumed slot count, and publishes each round's
                   packed response to its waiting slot (a cheap event
                   wake — no device interaction on the waiter's side).

Merges that must fetch inside the backend lock (host-cascade replay,
Store seeding/repair — fastpath._process's locked branch) ride the same
runner as HOST JOBS (`submit_host`): the work runs verbatim on the
runner thread, FIFO with the ring iterations, so store write-through
tickets still dispatch-order against ring steps and the request path
stays fetch-free even for those merges.

Failure containment: a dispatch error marks the ring BROKEN and fails
its jobs; the fast lane checks `available()` per merge and falls back
to the depth-k pipelined discipline (docs/ring.md's fallback rule).
`close()` finishes the in-flight iteration (its device effects already
happened), fails never-started jobs, and joins the runner.

The runner is LAYOUT-AGNOSTIC: a slot is whatever the backend's
`ring_q_shape(tb)` says — int64[12, B] on a single-table backend,
int64[12, n_shards, B] on the mesh (parallel/sharded.make_mesh_ring_step,
whose per-shard sequence words all advance by the consumed tier and are
verified against the host mirror element-wise).  Blocks stack rounds
along the leading slot axis either way.

MEGAROUND (GUBER_RING_ROUNDS > 1; docs/ring.md): the ring capacity
multiplies to slots x rounds and the runner becomes an ADAPTIVE ROUND
ACCUMULATOR — a shallow queue (<= the base slot tier) dispatches
immediately exactly as before, but a backlog past the base tier widens
the block to the mega tiers (ops/ring.mega_ring_step: ONE XLA entry for
up to slots x rounds rounds), lingering at most GUBER_RING_MAX_LINGER_US
for the block to fill.  Every other contract — double buffering, the
sequence word, mixed-tier response slicing, FIFO host jobs, the
broken-ring fallback — is tier-agnostic and unchanged.

PERSISTENT (GUBER_SERVE_MODE=persistent): blocks route through the
backend's persistent Pallas serve kernel (ops/pallas/serve_kernel.py —
one kernel LAUNCH drains the whole block with the table resident across
rounds) instead of the scans; the caller gates on
`persistent_serve_supported()` and falls back to megaround where the
kernel cannot compile (honest capability reporting, docs/ring.md).

On TPU backends with Pallas DMA support the same protocol maps onto a
device-resident loop with host-pinned rings (docs/ring.md); this runner
is the portable host-driven form and the semantic reference for it.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

import numpy as np

from gubernator_tpu.ops.ring import (
    resolve_mega_tiers,
    resolve_ring_tiers,
    ring_tier_of,
)
from gubernator_tpu.runtime import tracing


class _Job:
    """One submitted unit: either `qs` (an int64[k, 12, B] request block
    already in ring slot layout) or `fn` (a host job run verbatim on the
    runner thread).  `trace_ctx` is the submitter's trace context,
    carried explicitly because the runner is a plain thread — ring
    iterations and host jobs re-attach to the request's trace through
    it."""

    __slots__ = (
        "ring", "qs", "fn", "event", "result", "error", "trace_ctx",
    )

    def __init__(self, ring: "RingBackend", qs=None, fn=None) -> None:
        self.ring = ring
        self.qs = qs
        self.fn = fn
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.trace_ctx = tracing.current_context()

    def publish(self, result=None, error=None) -> None:
        self.result = result
        self.error = error
        self.event.set()

    def wait(self):
        """Bounded wait: a wedged runner (e.g. a host job stuck on a
        slow Store call) must not hang waiters forever — that would
        wedge the coalescer fetch stages and with them FastPath.close().
        Two escapes: the ring turned defunct (close() gave up on the
        runner) with this job unresolved, or the per-job timeout
        expired, in which case the ring is marked broken so every later
        merge falls back to the pipelined discipline."""
        ring = self.ring
        deadline = time.monotonic() + ring.job_timeout_s
        while not self.event.wait(timeout=0.5):
            if ring.defunct:
                raise RingClosedError(
                    "ring shut down with this job unresolved"
                )
            if time.monotonic() >= deadline:
                ring._mark_broken()
                raise RingClosedError(
                    f"ring job timed out after {ring.job_timeout_s:.0f}s"
                    " (runner wedged?)"
                )
        if self.error is not None:
            raise self.error
        return self.result


class RingClosedError(RuntimeError):
    pass


class PartialSubmitError(RuntimeError):
    """A multi-chunk submit_q lost the ring after at least one chunk was
    already queued — and possibly dispatched, i.e. its device effects
    may have landed.  Deliberately NOT a RingClosedError subclass:
    callers handle THAT by falling back to another drain path and
    re-dispatching the merge, which here would apply the queued chunks'
    hits twice.  The only safe handling is to fail the merge."""


class RingBackend:
    """Request/response rings + the persistent device-loop runner."""

    # Ceiling on one job's wait for its published result — a liveness
    # backstop against a wedged runner, far above any legitimate
    # iteration or host-job latency (see _Job.wait).
    JOB_TIMEOUT_S = 120.0

    def __init__(
        self, backend, slots: int = 8, metrics=None,
        job_timeout_s: float = JOB_TIMEOUT_S,
        rounds: int = 1, max_linger_us: float = 0.0,
        persistent: bool = False,
    ) -> None:
        if slots < 1:
            raise ValueError(f"ring slots must be >= 1, got {slots}")
        if rounds < 1:
            raise ValueError(f"ring rounds must be >= 1, got {rounds}")
        if max_linger_us < 0:
            raise ValueError(
                f"ring max_linger_us must be >= 0, got {max_linger_us}"
            )
        if not getattr(backend, "ring_supported", lambda: False)():
            raise ValueError(
                f"{type(backend).__name__} does not support the ring "
                "drain discipline"
            )
        if persistent and not hasattr(
            backend, "persistent_serve_dispatch"
        ):
            raise ValueError(
                f"{type(backend).__name__} has no persistent serve "
                "dispatch (caller must gate on "
                "persistent_serve_supported())"
            )
        self._backend = backend
        self.slots = slots
        # Megaround serving (docs/ring.md): `rounds` multiplies the
        # ring capacity to slots x rounds and arms mega dispatch tiers
        # — ONE XLA entry per up-to-capacity block.  The adaptive
        # accumulator (_maybe_linger_locked + _take_block_locked)
        # dispatches base tiers immediately while the queue is shallow
        # and widens to the mega tiers only under backlog, lingering at
        # most max_linger_us for the block to fill.
        self.rounds = rounds
        self.capacity = slots * rounds
        self.max_linger_s = max_linger_us * 1e-6
        # persistent: route every block through the backend's
        # persistent Pallas serve kernel instead of the ring/mega scans
        # (GUBER_SERVE_MODE=persistent; the caller verified capability).
        self.persistent = persistent
        self._tiers = resolve_ring_tiers(slots)
        self._mega_tiers = resolve_mega_tiers(slots, rounds)
        self._all_tiers = self._tiers + self._mega_tiers
        self._metrics = metrics
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._pending_rounds = 0  # queued, not yet taken by the runner
        self._closed = False
        self.broken = False
        # True once close() has drained/failed everything it can reach:
        # any still-unresolved job can never resolve, so its waiters
        # stop spinning (see _Job.wait).
        self.defunct = False
        self.job_timeout_s = job_timeout_s
        # Host mirror of the device sequence word (ops/ring.py): advances
        # by the consumed TIER (padding slots included) per iteration;
        # the fetch verifies the device word agrees.  On a mesh backend
        # the device word is PER SHARD (int64[n]) and every shard must
        # agree with the mirror; the latest fetched words are kept for
        # /debug/vars + the gubernator_shard_ring_seq gauges.
        self.seq = 0
        self.seq_mismatches = 0
        self.seq_shards: list = []
        # Observability (debug_vars + the ring metrics).
        self.iterations = 0
        self.rounds_consumed = 0
        self.padded_rounds = 0
        self.host_jobs = 0
        self.slot_wait_s = 0.0
        self.slot_waits = 0
        self.loop_lag_s = 0.0  # latest gap between consecutive dispatches
        self.max_block = 0
        # Megaround accounting: iterations served at a mega tier, and
        # the adaptive accumulator's linger waits (count + total time —
        # every wait is bounded by max_linger_us).
        self.mega_iterations = 0
        self.lingers = 0
        self.linger_s = 0.0
        self._last_dispatch = None
        self._seq_dev = backend.ring_seq_init()
        self._runner = threading.Thread(
            target=self._run, name="tpu-ring-runner", daemon=True
        )
        self._runner.start()

    # -- producer side ----------------------------------------------------
    def available(self) -> bool:
        """May a merge enter the ring?  False once closed or broken —
        the fast lane then falls back to the pipelined discipline."""
        return not self._closed and not self.broken

    def submit_rounds(self, rounds: Sequence) -> Callable[[], list]:
        """Convenience form of submit_q for DeviceBatch rounds (tests,
        generic callers): pack them into ring slot layout first.  The
        fast lane scatters its columns straight into the layout instead
        (fastpath._build_rounds_q) — no DeviceBatch objects exist on
        that path."""
        from gubernator_tpu.runtime.backend import tier_of

        be = self._backend
        if not rounds:
            return lambda: []
        tb = max(tier_of(db.active, be._tiers) for db in rounds)
        return self.submit_q(
            np.stack([be.ring_pack_round(db, tb) for db in rounds])
        )

    def submit_q(self, qs: np.ndarray) -> Callable[[], list]:
        """Queue one merge's request block — int64[k, 12, B] rounds
        already in ring slot layout (int64[k, 12, n, B] grid slots on a
        mesh backend) — into `k` ring slots; returns a zero-arg wait
        producing the per-round host response dicts
        (packed_rounds_to_host shape).  Blocks while the ring is full —
        the backpressure the slot-wait metrics measure.

        A merge WIDER than the ring (a duplicate-heavy batch whose
        zero/negative-hit occurrences exploded into many sequential
        rounds) splits into capacity-sized chunks submitted in order:
        the FIFO queue + the in-order scan keep the rounds' effects
        sequential across chunk boundaries, and the machinery lane's
        serialized dispatch stage keeps other merges from interleaving
        mid-merge submissions out of order.

        Raises RingClosedError only while NOTHING has been enqueued
        (safe for the caller to fall back and re-dispatch elsewhere);
        losing the ring between chunks raises PartialSubmitError — the
        queued chunks' device effects may already have landed, so the
        caller must fail the merge instead."""
        n = int(qs.shape[0])
        if n == 0:
            return lambda: []
        if n > self.capacity:
            n_chunks = -(-n // self.capacity)
            waits = []
            for lo in range(0, n, self.capacity):
                try:
                    waits.append(
                        self._submit_chunk(qs[lo:lo + self.capacity])
                    )
                except RingClosedError as e:
                    if not waits:
                        raise
                    raise PartialSubmitError(
                        f"ring rejected chunk {len(waits) + 1}/{n_chunks}"
                        f" with {len(waits)} chunks already queued; "
                        "their device effects may have landed — fail "
                        "the merge, do not re-dispatch it"
                    ) from e

            def wait_all() -> list:
                out: list = []
                for w in waits:
                    out.extend(w())
                return out

            return wait_all
        return self._submit_chunk(qs)

    def _submit_chunk(self, qs: np.ndarray) -> Callable[[], list]:
        n = int(qs.shape[0])
        job = _Job(self, qs=qs)
        t0 = time.monotonic()
        waited = False
        with self._cond:
            while (
                self._pending_rounds + n > self.capacity
                and not self._closed
                and not self.broken
            ):
                waited = True
                self._cond.wait(timeout=0.5)
            if self._closed or self.broken:
                raise RingClosedError(
                    "ring closed" if self._closed else "ring broken"
                )
            self._pending_rounds += n
            self._queue.append(job)
            self._cond.notify_all()
        if waited:
            dt = time.monotonic() - t0
            self.slot_wait_s += dt
            self.slot_waits += 1
            m = self._metrics
            if m is not None:
                m.fastpath_ring_slot_wait.observe(dt)
        return job.wait

    def submit_host(self, fn: Callable[[], object]) -> Callable[[], object]:
        """Queue a host job (e.g. a locked cascade/store merge or a
        sketch fetch) to run verbatim on the runner thread, FIFO with
        the ring iterations; returns a zero-arg wait for fn's result.
        Host jobs occupy no ring slots — their device work is their
        own."""
        job = _Job(self, fn=fn)
        with self._cond:
            if self._closed or self.broken:
                raise RingClosedError(
                    "ring closed" if self._closed else "ring broken"
                )
            self._queue.append(job)
            self._cond.notify_all()
        return job.wait

    def rounds_per_dispatch(self) -> float:
        """The dispatch-amortization factor: real (un-padded) rounds
        served per device dispatch — the number megaround exists to
        raise (gubernator_ring_rounds_per_dispatch; docs/ring.md)."""
        return self.rounds_consumed / max(self.iterations, 1)

    def debug_vars(self) -> dict:
        return {
            "slots": self.slots,
            "rounds": self.rounds,
            "capacity": self.capacity,
            "max_linger_us": round(self.max_linger_s * 1e6, 1),
            "persistent": self.persistent,
            "seq": self.seq,
            "seq_shards": list(self.seq_shards),
            "seq_mismatches": self.seq_mismatches,
            "iterations": self.iterations,
            "mega_iterations": self.mega_iterations,
            "rounds_consumed": self.rounds_consumed,
            "rounds_per_dispatch": round(self.rounds_per_dispatch(), 3),
            "padded_rounds": self.padded_rounds,
            "host_jobs": self.host_jobs,
            "slot_waits": self.slot_waits,
            "slot_wait_ms_total": round(self.slot_wait_s * 1e3, 3),
            "lingers": self.lingers,
            "linger_ms_total": round(self.linger_s * 1e3, 3),
            "loop_lag_ms": round(self.loop_lag_s * 1e3, 3),
            "max_block": self.max_block,
            "broken": self.broken,
        }

    def warmup(self) -> None:
        """Compile every (slot tier x batch tier) ring block shape —
        mega tiers included — so no client merge pays a cold XLA
        compile mid-serving (the daemon calls this after arming the
        ring; a cold scan compile inside a request's ring iteration
        would show up as a multi-second p99 spike).  All-zero blocks
        are inactive no-ops — the table is untouched, only the sequence
        word advances."""
        resps = None
        for tb in self._backend._tiers:
            for t in self._all_tiers:
                qs = np.zeros(
                    (t,) + tuple(self._backend.ring_q_shape(tb)),
                    dtype=np.int64,
                )
                nows = np.zeros(t, dtype=np.int64)
                resps, _mega = self._dispatch_raw(qs, nows)
                self.seq += t
        if resps is not None:
            np.asarray(resps)  # sync the last warmup block

    # -- runner side ------------------------------------------------------
    def _maybe_linger_locked(self) -> None:
        """The adaptive round accumulator's bounded wait (megaround
        only): a SHALLOW queue (<= the base slot capacity) dispatches
        immediately — megaround must never add latency to light
        traffic — but a backlog already past the base tier is the
        under-load signal, so the runner lingers up to max_linger_us
        for the mega block to fill toward capacity before dispatching.
        Caller holds `_cond`; producers' notify_all wakes the wait as
        rounds arrive."""
        if self.rounds <= 1 or self.max_linger_s <= 0.0:
            return
        if not self._queue or self._queue[0].fn is not None:
            return
        if self._pending_rounds <= self.slots:
            return  # shallow: dispatch now
        if self._pending_rounds >= self.capacity:
            return  # already full: nothing to wait for
        t0 = time.monotonic()
        deadline = t0 + self.max_linger_s
        while (
            self._pending_rounds < self.capacity
            and not self._closed
            and not self.broken
        ):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            self._cond.wait(timeout=remaining)
        self.lingers += 1
        self.linger_s += time.monotonic() - t0

    def _take_block_locked(self) -> Optional[List[_Job]]:
        """Pop the next FIFO unit: a host job alone, or every queued
        rounds-job up to the adaptive capacity as one block — the base
        slot tier while the queue is shallow, the mega capacity
        (slots x rounds) once the backlog is past the base tier (the
        under-load half of the accumulator).  Caller holds `_cond`."""
        if not self._queue:
            return None
        if self._queue[0].fn is not None:
            return [self._queue.popleft()]
        cap = (
            self.capacity if self._pending_rounds > self.slots
            else self.slots
        )
        block: List[_Job] = []
        taken = 0
        while self._queue and self._queue[0].fn is None:
            n = int(self._queue[0].qs.shape[0])
            if block and taken + n > cap:
                break
            block.append(self._queue.popleft())
            taken += n
        self._pending_rounds -= taken
        self._cond.notify_all()  # wake producers blocked on capacity
        return block

    def _dispatch_raw(self, qs: np.ndarray, nows: np.ndarray):
        """Route one padded [tier, ...] block to the armed decision
        kernel: the persistent Pallas serve kernel when armed, the
        megaround scan for tiers past the base slot capacity, the base
        ring scan otherwise.  Returns (device responses, mega flag —
        True when the responses carry a leading (r, s) round grid the
        fetch must flatten)."""
        be = self._backend
        tier = int(qs.shape[0])
        if self.persistent:
            resps, self._seq_dev = be.persistent_serve_dispatch(
                qs, nows, self._seq_dev
            )
            return resps, False
        if tier > self.slots:
            r = tier // self.slots
            resps, self._seq_dev = be.ring_mega_dispatch(
                qs.reshape((r, self.slots) + qs.shape[1:]),
                nows.reshape(r, self.slots),
                self._seq_dev,
            )
            return resps, True
        resps, self._seq_dev = be.ring_step_dispatch(
            qs, nows, self._seq_dev
        )
        return resps, False

    def _dispatch_block(self, block: List[_Job]):
        """Assemble a jobs-block into one [tier, 12, B] request-ring
        array and dispatch the jitted scan (the backend serializes
        against every other table mutation under its own lock).  Returns
        the fetch token (block, device responses, seq handle, expected
        seq, t0)."""
        be = self._backend
        k = sum(int(job.qs.shape[0]) for job in block)
        tier = ring_tier_of(k, self._all_tiers)
        # Slot layout is backend-defined (ring_q_shape): [12, B] single
        # table, [12, n, B] mesh grid.  The inner dims are constant
        # across jobs; only the trailing batch tier varies.
        tb = max(int(job.qs.shape[-1]) for job in block)
        inner = tuple(block[0].qs.shape[1:-1])
        qs = np.zeros((tier,) + inner + (tb,), dtype=np.int64)
        off_q = 0
        for job in block:
            jk = int(job.qs.shape[0])
            jtb = int(job.qs.shape[-1])
            # Narrower jobs pad with zero lanes (inactive by layout).
            qs[off_q:off_q + jk, ..., :jtb] = job.qs
            off_q += jk
        now = np.int64(be.clock.millisecond_now())
        nows = np.full(tier, now, dtype=np.int64)
        # One iteration span per device round: parented on the first
        # sampled job's context with every other job's context attached
        # as a span link — a request's trace pins the exact ring
        # iteration it rode, and the monotone sequence word (set below,
        # once consumed) names the device round.
        isp = None
        if tracing.enabled():
            ctxs = [j.trace_ctx for j in block if j.trace_ctx is not None]
            if ctxs:
                parent = next((c for c in ctxs if c.sampled), ctxs[0])
                isp = tracing.start_span(
                    "ring.iteration", parent,
                    links=[c for c in ctxs if c is not parent],
                )
        t0 = time.monotonic()
        if self._last_dispatch is not None:
            self.loop_lag_s = t0 - self._last_dispatch
            m = self._metrics
            if m is not None:
                m.fastpath_ring_loop_lag.set(self.loop_lag_s)
        self._last_dispatch = t0
        # The backend's dispatch is a ledger stage (gub.backend.dispatch,
        # lane "ring"), so ring rounds are visible in jax.profiler
        # captures like every other dispatch and the ring loop-lag
        # gauges line up with the device timeline.
        with tracing.use_context(isp.context if isp is not None else None):
            resps, mega = self._dispatch_raw(qs, nows)
        seq_out = self._seq_dev
        self.iterations += 1
        if mega or (self.persistent and tier > self.slots):
            self.mega_iterations += 1
        self.rounds_consumed += k
        self.padded_rounds += tier - k
        self.seq += tier
        if k > self.max_block:
            self.max_block = k
        if isp is not None:
            isp.set_attribute("ring.seq", self.seq)
            isp.set_attribute("ring.rounds", k)
            isp.set_attribute("ring.tier", tier)
            isp.end()
        m = self._metrics
        if m is not None:
            m.fastpath_ring_occupancy.observe(k)
            m.ring_rounds_per_dispatch.set(self.rounds_per_dispatch())
        # seq_out rides the token so the fetch reads THIS iteration's
        # device word even after the next iteration dispatches with it.
        return (
            block, resps, seq_out, self.seq, t0, mega,
            isp.context if isp is not None else None,
        )

    def _fetch_publish(self, token) -> None:
        """The response-ring side: ONE packed transfer for the whole
        iteration (responses + sequence word), then per-job publication.
        Runs only on the runner thread — never on the request path."""
        block, resps, seq_dev, want_seq, t0, mega, it_ctx = token
        fsp = tracing.start_span(
            "ring.fetch_publish", it_ctx, **{"ring.seq": want_seq}
        )
        try:
            with tracing.use_context(
                fsp.context if fsp is not None else it_ctx
            ):
                self._fetch_publish_inner(block, resps, seq_dev,
                                          want_seq, t0, mega)
        finally:
            if fsp is not None:
                fsp.end()

    def _fetch_publish_inner(
        self, block, resps, seq_dev, want_seq, t0, mega=False
    ) -> None:
        from gubernator_tpu.runtime.backend import (
            _packed_resp_dict,
            fetch_ravel,
        )

        try:
            host, seq_host = fetch_ravel([resps, seq_dev])
        except Exception as e:  # noqa: BLE001 — device fault: break ring
            self._mark_broken()
            for job in block:
                job.publish(error=e)
            return
        if mega:
            # Mega blocks dispatch as an [r, s, ...] round grid
            # (mega_ring_step); flatten the two round axes back so
            # per-job slicing below is tier-agnostic.
            host = host.reshape((-1,) + host.shape[2:])
        # Scalar word on a single-table backend; int64[n] per-shard
        # words on the mesh — EVERY shard's word must agree with the
        # host mirror (a lagging shard means its loop dropped or
        # replayed a block).
        seq_words = np.asarray(seq_host).reshape(-1)
        self.seq_shards = [int(w) for w in seq_words]
        if (seq_words != want_seq).any():
            # The device loop and the host mirror disagree — responses
            # may be misattributed.  Record loudly; the differential
            # suite asserts this never fires.
            self.seq_mismatches += 1
        off = 0
        for job in block:
            n = int(job.qs.shape[0])
            # Slice each job's rows back to ITS OWN batch tier: the
            # block dispatched at the max tier across coalesced jobs,
            # but the submitter's active masks and lane indices are
            # built at the job's tier (tally_from_rounds would
            # broadcast-fail on wider rows; the padded lanes are
            # inactive by construction, so nothing real is dropped).
            w = int(job.qs.shape[-1])
            job.publish(result=[
                _packed_resp_dict(host[off + i][..., :w])
                for i in range(n)
            ])
            off += n
        m = self._metrics
        fr = getattr(m, "flightrec", None) if m is not None else None
        if fr is not None:
            fr.record_batch(
                off, (time.monotonic() - t0) * 1e3, kind="ring_iter",
                rounds_per_dispatch=round(self.rounds_per_dispatch(), 3),
            )

    def _mark_broken(self) -> None:
        with self._cond:
            self.broken = True
            self._cond.notify_all()

    def _run(self) -> None:
        # Everything the runner does to the backend (dispatch, lock
        # wait, the device->host fetch) is charged to the lane "ring".
        with tracing.scope(tracing.ledger_of(self._metrics), "ring"):
            self._run_loop()

    def _run_loop(self) -> None:
        inflight = None  # dispatched, responses not yet fetched
        while True:
            with self._cond:
                while (
                    not self._queue
                    and not self._closed
                    and inflight is None
                ):
                    self._cond.wait()
                if self._closed and not self._queue and inflight is None:
                    return
                self._maybe_linger_locked()
                unit = self._take_block_locked()
                dead = self._closed or self.broken
                dead_msg = "ring closed" if self._closed else "ring broken"
            if unit is None:
                # Idle (or draining at close) with an iteration in
                # flight: fetch and publish it now.
                self._fetch_publish(inflight)
                inflight = None
                continue
            if dead:
                # Close/break raced in after these jobs queued: their
                # effects have NOT happened yet (host jobs never ran,
                # rounds never dispatched) — fail them uniformly
                # rather than execute behind a closing daemon or
                # dispatch against a backend that just faulted.  The
                # in-flight iteration's effects DID land, so it is
                # still fetched and published first.
                if inflight is not None:
                    self._fetch_publish(inflight)
                    inflight = None
                for job in unit:
                    job.publish(error=RingClosedError(dead_msg))
                continue
            if unit[0].fn is not None:
                # Host job: drain the pending fetch first (its buffers
                # are a cheap sync away; the job may hold the backend
                # lock for a while), then run the job verbatim.
                if inflight is not None:
                    self._fetch_publish(inflight)
                    inflight = None
                job = unit[0]
                self.host_jobs += 1
                # A FIFO host job re-attaches to its submitter's trace
                # (locked cascade/store merges, sketch readbacks): the
                # span brackets the whole runner-side execution, so a
                # trace shows exactly how long the job held the runner.
                run = tracing.wrap(
                    job.fn, "ring.host_job", job.trace_ctx
                )
                try:
                    job.publish(result=run())
                except BaseException as e:  # noqa: BLE001 — fail the job
                    job.publish(error=e)
                continue
            try:
                token = self._dispatch_block(unit)
            except BaseException as e:  # noqa: BLE001 — break the ring
                self._mark_broken()
                for job in unit:
                    job.publish(error=e)
                continue
            # Double buffer: the PREVIOUS iteration's fetch overlaps this
            # one's device execution.
            if inflight is not None:
                self._fetch_publish(inflight)
            inflight = token

    def close(self) -> None:
        """Stop the runner: the in-flight iteration is fetched and
        published (its device effects already landed); queued-but-never-
        started jobs — host jobs included — fail with RingClosedError."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._runner.join(timeout=30.0)
        # Belt and braces: anything the runner left behind must resolve.
        with self._cond:
            leftovers = list(self._queue)
            self._queue.clear()
            self._pending_rounds = 0
        for job in leftovers:
            if not job.event.is_set():
                job.publish(error=RingClosedError("ring closed"))
        if self._runner.is_alive():
            # Join timed out: the runner is wedged inside a job it
            # already popped.  Mark broken so nothing new is accepted;
            # `defunct` below makes that job's waiters stop spinning
            # (bounded _Job.wait) instead of hanging shutdown.
            self._mark_broken()
        self.defunct = True
