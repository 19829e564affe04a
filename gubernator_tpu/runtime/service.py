"""The service instance: per-request routing over the cluster.

This is the analog of the reference's V1Instance (gubernator.go:46-824) — the
"brain" that decides, for every rate-limit check, whether to answer from the
local device engine, serve a GLOBAL key from replicated cache, or forward to
the owning peer.  One deliberate TPU-first difference: where the reference
dispatches each request to a worker goroutine individually
(gubernator.go:222-300), this service partitions a client batch ONCE and
applies all locally-owned checks in a single device step — the request fan
becomes vector lanes, not goroutines.

Routing per request (gubernator.go:222-300):
  - validation errors answer inline (handled by the packer);
  - owner == us      -> local device batch;
  - GLOBAL, not ours -> local device batch with the use_cached lane flag
                        (stale-but-fast read, gubernator.go:420-460) + hit
                        queued to the global manager; metadata["owner"] set;
  - otherwise        -> forwarded to the owner through the batching peer
                        client with <=5 retries on ownership change
                        (gubernator.go:327-416).

The GlobalManager re-implements global.go:33-254 on asyncio: an async-hits
loop aggregating (key -> summed hits) flushed to owners every
`global_sync_wait`, and a broadcast loop pushing owner-authoritative statuses
to every peer with the GLOBAL flag cleared to avoid loops (global.go:214-215).

The MultiRegionManager implements the cross-region tier the reference leaves
stubbed (multiregion.go:96-98 "Does nothing for now"): hits aggregate per key
and flush to the key's owner in every OTHER region with the MULTI_REGION flag
cleared (same loop-prevention trick as GLOBAL broadcasts), giving each region
an eventually-consistent view of cross-region hit pressure over DCN.
"""
from __future__ import annotations

import asyncio
import fnmatch
import functools
import logging
import random
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core.config import Config, MAX_BATCH_SIZE
from gubernator_tpu.core.interval import GregorianError, gregorian_expiration
from gubernator_tpu.core.types import (
    Behavior,
    HealthCheckResp,
    LeaseGrant,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
    Status,
    UpdatePeerGlobal,
    has_behavior,
)
from gubernator_tpu.net.peer_client import (
    PeerClient,
    PeerNotReadyError,
    provably_unsent,
)
from gubernator_tpu.net.replicated_hash import (
    HASH_FUNCTIONS,
    PoolEmptyError,
    RegionPicker,
    ReplicatedConsistentHash,
)
from gubernator_tpu.runtime import tracing
from gubernator_tpu.runtime.backend import DeviceBackend

log = logging.getLogger("gubernator_tpu.service")

HEALTHY = "healthy"
UNHEALTHY = "unhealthy"

ASYNC_RETRIES = 5  # forwarded-request ownership-change retries (gubernator.go:350)

# The shadow slot's key suffix: a degraded local_shadow check serves
# from `<unique_key>` + this suffix, so shadow admission state never
# collides with the real key's authoritative or cached rows.
SHADOW_SUFFIX = ".degraded-shadow"

_EMPTY_I64 = np.empty(0, dtype=np.int64)


def forward_backoff_s(
    attempt: int, cap_s: float, rng: random.Random
) -> float:
    """Backoff before ownership-retry `attempt` (1-based) of the
    forwarded-request loop: equal-jittered exponential —
    uniform over [base/2, base] with base = 10ms * 2^(attempt-1) —
    capped at `cap_s` (the batch timeout, so the retry loop's total
    added latency stays within one RPC budget).  Jitter decorrelates
    the retry stampede a dying owner otherwise sees from every
    forwarder at once (the coordination failure arXiv:1909.08969
    measures).  Pure function of (attempt, cap, rng) so tests pin the
    schedule with a seeded rng."""
    base = min(0.01 * (2 ** max(attempt - 1, 0)), cap_s)
    lo = base / 2.0
    return min(lo + rng.random() * (base - lo), cap_s)


class ApiError(Exception):
    """Service-level error with a gRPC status-code name."""

    def __init__(self, code: str, message: str) -> None:
        super().__init__(message)
        self.code = code


class Service:
    """The per-node service instance."""

    def __init__(
        self,
        cfg: Optional[Config] = None,
        backend: Optional[DeviceBackend] = None,
        clock: Optional[clock_mod.Clock] = None,
        peer_credentials=None,
        metrics=None,
    ) -> None:
        from gubernator_tpu.runtime.metrics import Metrics

        self.cfg = cfg or Config()
        self.clock = clock or clock_mod.default_clock()
        self.metrics = metrics or Metrics()
        if backend is not None:
            self.backend = backend
        elif self.cfg.device.num_shards > 1:
            # Multi-chip: shard the table over the device mesh (full
            # Store/Loader SPI, same as the single-device backend).
            from gubernator_tpu.parallel.sharded import MeshBackend

            self.backend = MeshBackend(
                self.cfg.device,
                clock=self.clock,
                metrics=self.metrics,
                store=self.cfg.store,
                track_keys=(self.cfg.loader is not None),
            )
        else:
            self.backend = DeviceBackend(
                self.cfg.device,
                clock=self.clock,
                store=self.cfg.store,
                track_keys=(self.cfg.loader is not None),
                metrics=self.metrics,
            )
        self._inflight_checks = 0
        self._peer_credentials = peer_credentials
        # Chaos binding (testing/chaos.py): set by the daemon after its
        # listen address is known, handed to every PeerClient built
        # afterwards.  None in production.
        self.chaos = None
        # Degraded-mode ownership fallback (docs/resilience.md).
        self._rng = random.Random()
        self.degraded_served = 0
        # owner addr -> {shadow hash_key: the RESET_REMAINING req that
        # drops the shadow slot once the owner heals}.
        self._shadow: Dict[str, Dict[str, RateLimitReq]] = {}
        self._shadow_tasks: set = set()
        # Cached label child: the hot path must not pay a labels() dict
        # lookup per call (reference funcTimeMetric, gubernator.go:118).
        self._fd_get_rate_limits = self.metrics.func_duration.labels(
            "V1Instance.GetRateLimits"
        )

        def picker_hash(name: str, which: str):
            # Named error over a bare KeyError (config.go:403-425
            # validates the same knob).
            try:
                return HASH_FUNCTIONS[name]
            except KeyError:
                raise ValueError(
                    f"invalid {which} picker hash {name!r}; choose one "
                    f"of {sorted(HASH_FUNCTIONS)}"
                ) from None

        hash_fn = picker_hash(self.cfg.local_picker_hash, "local")
        self.local_picker: ReplicatedConsistentHash[PeerClient] = (
            ReplicatedConsistentHash(hash_fn)
        )
        self.region_picker: RegionPicker[PeerClient] = RegionPicker(
            ReplicatedConsistentHash(
                picker_hash(self.cfg.region_picker_hash, "region")
            )
        )
        self._peer_lock = asyncio.Lock()
        # Single-thread executor serializes blocking device work off the loop
        # (the whole-table single-writer discipline, workers.go:19-37).
        self._dev_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpu-step"
        )
        self._local_batcher = LocalBatcher(self)
        # Approximate tier for configured limit names (runtime/sketch_backend).
        # A names-less config still instantiates when dynamic spillover
        # is armed — membership then grows at runtime (spill_name).
        self.sketch_backend = None
        if self.cfg.sketch is not None and (
            self.cfg.sketch.names
            or self.cfg.sketch.spill_inserts is not None
            or self.cfg.sketch.spill_transients is not None
        ):
            from gubernator_tpu.runtime.sketch_backend import SketchBackend

            self.sketch_backend = SketchBackend(
                self.cfg.sketch, clock=self.clock
            )
            # Every actual spill — policy-driven or operator-called —
            # hits the Prometheus counter.
            self.sketch_backend.on_spill = self.metrics.sketch_spillover.inc
        # Hot-key survival plane (runtime/hotkey.py; docs/hotkeys.md):
        # detection over the traffic this node routes.  Promotion is
        # gated on MEASURED owner pressure, so without a flight
        # recorder (or with every owner healthy) the tracker is inert.
        self.hotkeys = None
        if self.cfg.hotkey.enabled:
            from gubernator_tpu.runtime.hotkey import HotKeyTracker

            self.hotkeys = HotKeyTracker(
                self.cfg.hotkey, metrics=self.metrics
            )
            self.hotkeys.pressure_fn = self._owner_pressure_of
            self.hotkeys.on_demote = self._on_hot_demote
        # Guberberg tier manager (runtime/coldtier.py; docs/tiering.md):
        # the daemon arms it when GUBER_TIER_ENABLED; note_traffic feeds
        # its promote-on-access path.
        self.tier = None
        # fp -> RESET_REMAINING req that drops the local mirror slot
        # when its key demotes (the shadow-drop discipline).
        self._mirror_resets: Dict[int, RateLimitReq] = {}
        # (built_monotonic, tracker version, int64 fps) cache for the
        # fast lane's active-mirror mask.
        self._mirror_fps_cache = None
        self.mirror_served = 0
        self.shed_served = 0
        # Gubstat per-tenant admission ledger (runtime/gubstat.py;
        # docs/observability.md): fed at the LOCAL serve choke points
        # only (_check_local tail, fast-lane _finish_process, the shed
        # path) so a cluster-wide sum never double-counts a hit.
        self.tenants = None
        if self.cfg.stats.enabled:
            from gubernator_tpu.runtime.gubstat import TenantAccounting

            self.tenants = TenantAccounting(self.cfg.stats.top_k)
        # Client-side admission leases (runtime/lease.py; docs/leases.md):
        # the owner-side grant/reconcile plane for the Lease/Reconcile
        # peer RPCs.  None when disabled — every grant then refuses.
        self.leases = None
        if self.cfg.lease.enabled:
            from gubernator_tpu.runtime.lease import LeaseManager

            self.leases = LeaseManager(
                self, self.cfg.lease, metrics=self.metrics
            )
        self._lease_sweep_task: Optional[asyncio.Task] = None
        # Elastic membership (runtime/reshard.py; docs/resharding.md):
        # a remap streams moved rows old owner -> new owner instead of
        # orphaning them.  None when disabled — a remap then degrades
        # to the legacy counter reset.
        self.reshard = None
        if self.cfg.reshard.enabled:
            from gubernator_tpu.runtime.reshard import ReshardManager

            self.reshard = ReshardManager(
                self, self.cfg.reshard, metrics=self.metrics
            )
        # The ring as it stood before the latest remap — the inbound
        # handoff's covered-key test (reshard.inbound_covering).
        self._prev_picker = None
        self._reshard_watch_task: Optional[asyncio.Task] = None
        # Planet-scale regions (runtime/multiregion.py;
        # docs/multiregion.md): remote-homed keys serve from a bounded
        # `.region-carve` slot and reconcile over the WAN lane.  None
        # when disabled — every key is then home here.
        self.regions = None
        if self.cfg.region.enabled:
            from gubernator_tpu.runtime.multiregion import RegionManager

            self.regions = RegionManager(
                self, self.cfg.region, metrics=self.metrics
            )
        self.global_mgr = GlobalManager(self)
        self.multi_region_mgr = MultiRegionManager(self)
        # On a mesh backend, GLOBAL keys owned by THIS node serve from the
        # collective engine's replicated cache and sync over ICI
        # (all_to_all hits -> owner, all_gather broadcast) instead of the
        # RPC loops — wired at construction like the reference's
        # globalManager (gubernator.go:137, global.go:63-64).  The RPC
        # GlobalManager still handles keys owned by OTHER nodes.
        self.global_engine = None
        self._collective_loop: Optional[CollectiveGlobalLoop] = None
        from gubernator_tpu.parallel.sharded import MeshBackend

        if isinstance(self.backend, MeshBackend):
            from gubernator_tpu.parallel.global_sync import GlobalEngine

            self.global_engine = GlobalEngine(
                self.backend,
                batch_limit=self.cfg.behaviors.global_batch_limit,
            )
            self.global_engine.on_synced = self._engine_synced
            self._collective_loop = CollectiveGlobalLoop(
                self, self.global_engine
            )
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._closed = False
        self._started = False
        if self.cfg.loader is not None:
            n = self.backend.load_items(self.cfg.loader.load())
            log.info("loader restored %d items", n)

    async def start(self) -> None:
        """Start the background replication loops; requires a running event
        loop (the analog of NewV1Instance spawning the manager goroutines,
        gubernator.go:137-138)."""
        if self._started:
            return
        self._started = True
        self._loop = asyncio.get_running_loop()
        self.global_mgr.start()
        self.multi_region_mgr.start()
        if self.regions is not None:
            self.regions.start()
        if self._collective_loop is not None:
            self._collective_loop.start()
        if self.leases is not None:
            self._lease_sweep_task = asyncio.ensure_future(
                self._lease_sweep_loop()
            )
        if self.reshard is not None:
            self._reshard_watch_task = asyncio.ensure_future(
                self._reshard_watch_loop()
            )
        # Warm the jitted device step so the first client request doesn't
        # pay XLA compilation (20-40s cold) inside an RPC deadline.
        loop = asyncio.get_running_loop()
        # The two-tier table's programs compile with the rest.
        tier = self.cfg.tier if self.cfg.tier.enabled else None
        await loop.run_in_executor(
            self._dev_executor, functools.partial(self.backend.warmup, tier)
        )
        if self.global_engine is not None:
            await loop.run_in_executor(
                self._dev_executor, self.global_engine.warmup
            )
        if self.sketch_backend is not None:
            await loop.run_in_executor(
                self._dev_executor, self.sketch_backend.warmup
            )

    # ------------------------------------------------------------------
    # peer management
    # ------------------------------------------------------------------
    async def set_peers(self, peer_info: Sequence[PeerInfo]) -> None:
        """Atomically swap in a new peer set and drain removed peers
        (gubernator.go:634-717).  The lock spans the whole rebuild so
        concurrent discovery updates (fire-and-forget on_update tasks)
        serialize instead of interleaving across awaits; readers run on
        the same loop and see either the old or the new picker."""
        async with self._peer_lock:
            local = self.local_picker.new()
            region = self.region_picker.new()
            for info in peer_info:
                if info.data_center != self.cfg.data_center:
                    peer = self.region_picker.get_by_address(
                        info.grpc_address
                    )
                    if peer is None:
                        peer = self._new_peer(info)
                    region.add(peer, info.data_center)
                else:
                    peer = self.local_picker.get_by_address(
                        info.grpc_address
                    )
                    if peer is None:
                        peer = self._new_peer(info)
                    else:
                        peer.peer_info = info  # refresh is_owner flag
                    local.add(peer)

            old_local, old_region = self.local_picker, self.region_picker
            self.local_picker, self.region_picker = local, region
            self._prev_picker = old_local

        # Live resharding (docs/resharding.md): the remap may have
        # moved arcs this node owned — stream their rows to the new
        # owners instead of orphaning them.  Spawned (the delta needs a
        # device fetch); routing already follows the NEW ring, and the
        # handoff protocol bounds the window's double admission.
        if self.reshard is not None and old_local.size() > 0:
            self.reshard.on_remap(old_local, local)
        # Derived-slot invalidation: a demoted owner must not keep
        # honoring lease renewals against a stale carve slot, and a
        # node that just BECAME a hot key's owner must not keep a
        # mirror allowance for it.
        if self.leases is not None and old_local.size() > 0:
            self.leases.on_remap()
        self._invalidate_unowned_mirrors()
        if self.regions is not None:
            self.regions.on_remap()

        shutdown: List[PeerClient] = []
        for peer in old_local.peers():
            if local.get_by_address(peer.info().grpc_address) is None:
                shutdown.append(peer)
        for picker in old_region.pickers().values():
            for peer in picker.peers():
                if region.get_by_address(peer.info().grpc_address) is None:
                    shutdown.append(peer)
        if shutdown:
            await asyncio.gather(
                *(p.shutdown() for p in shutdown), return_exceptions=True
            )
            log.debug(
                "peers shutdown: %s",
                [p.info().grpc_address for p in shutdown],
            )

    def _new_peer(self, info: PeerInfo) -> PeerClient:
        peer = PeerClient(
            info,
            behavior=self.cfg.behaviors,
            channel_credentials=self._peer_credentials,
            metrics=self.metrics,
            circuit=self.cfg.circuit,
            chaos=self.chaos,
            pressure_ttl_s=self.cfg.hotkey.pressure_ttl_s,
        )
        # Heal detection for the degraded-mode fallback: ANY successful
        # RPC to the peer (object path, compiled raw lane, GLOBAL
        # flush/broadcast) drops its shadow admission state.
        addr = info.grpc_address
        peer.on_rpc_success = lambda: self._drop_shadow(addr)
        return peer

    def get_peer(self, key: str) -> PeerClient:
        """Owning peer for a hash key (gubernator.go:719-731)."""
        return self.local_picker.get(key)

    def peer_list(self) -> List[PeerClient]:
        return self.local_picker.peers()

    def _owns_key(self, key: str) -> bool:
        """Does THIS node own `key` under the current ring?  An empty
        pool owns everything (single-node mode)."""
        if self.local_picker.size() == 0:
            return True
        try:
            return self.get_peer(key).info().is_owner
        except PoolEmptyError:
            return True

    # ------------------------------------------------------------------
    # elastic membership (runtime/reshard.py; docs/resharding.md)
    # ------------------------------------------------------------------
    def _derived_slot_keys(self) -> List[str]:
        """Hash-key strings of every derived slot this node knows about
        (each ends with its reserved suffix class — lease carve,
        hot-mirror, degraded shadow, handoff shadow)."""
        keys: List[str] = []
        if self.leases is not None:
            from gubernator_tpu.runtime.lease import LEASE_SUFFIX

            with self.leases._lock:
                keys.extend(
                    k + LEASE_SUFFIX for k in self.leases._keys
                )
        keys.extend(
            r.hash_key() for r in self._mirror_resets.values()
        )
        for pending in self._shadow.values():
            keys.extend(pending.keys())
        if self.reshard is not None:
            from gubernator_tpu.runtime.reshard import HANDOFF_SUFFIX

            with self.reshard._lock:
                for ib in self.reshard._inbound.values():
                    keys.extend(
                        k + HANDOFF_SUFFIX for k in ib.shadow
                    )
        if self.regions is not None:
            keys.extend(self.regions.carve_slot_keys())
        return keys

    def derived_slot_fps(self) -> np.ndarray:
        """int64 fingerprints of the derived slots this node can
        invalidate locally — lease carve slots, hot-mirror allowances,
        degraded shadows, handoff shadows.  The reshard plane excludes
        them from migration: derived state re-homes by re-creation at
        its new home (leases re-grant through the ring, mirrors
        re-promote, shadows re-carve), never by copy."""
        from gubernator_tpu.core.hashing import key_hash64

        keys = self._derived_slot_keys()
        if not keys:
            return _EMPTY_I64
        return np.array(
            [np.uint64(key_hash64(k)).view(np.int64) for k in keys],
            dtype=np.int64,
        )

    def derived_slot_fps_by_plane(self) -> Dict[str, np.ndarray]:
        """The same enumeration grouped by reserved suffix class (the
        ops/state.SHADOW_PLANES census order) — the gubstat sampler's
        input: each plane's fingerprints probe the live table so the
        carve-slot population is observable per class."""
        from gubernator_tpu.core.hashing import key_hash64
        from gubernator_tpu.ops.state import SHADOW_PLANES

        grouped: Dict[str, List[int]] = {p: [] for p in SHADOW_PLANES}
        for k in self._derived_slot_keys():
            for p in SHADOW_PLANES:
                if k.endswith(p):
                    grouped[p].append(
                        int(np.uint64(key_hash64(k)).view(np.int64))
                    )
                    break
        return {
            p: np.array(v, dtype=np.int64) if v else _EMPTY_I64
            for p, v in grouped.items()
        }

    def _invalidate_unowned_mirrors(self) -> None:
        """A remap can make this node the OWNER of a key it was
        mirroring — drop the stale mirror allowance so no widened
        admission state survives the ownership change."""
        from gubernator_tpu.runtime.hotkey import MIRROR_SUFFIX

        fps = [
            fp for fp, r in self._mirror_resets.items()
            if r.unique_key.endswith(MIRROR_SUFFIX)
            and self._owns_key(
                r.name + "_" + r.unique_key[: -len(MIRROR_SUFFIX)]
            )
        ]
        if fps:
            self._on_hot_demote(fps)

    async def _reshard_watch_loop(self) -> None:
        """Watchdog cadence for the reshard plane: self-cutover inbound
        handoffs whose old owner went silent, expire released outbound
        records past the stale-router linger."""
        interval = max(self.cfg.reshard.timeout_s / 4.0, 0.05)
        while True:
            await asyncio.sleep(interval)
            try:
                await self.reshard.check_timeouts()
            except Exception as e:  # noqa: BLE001 — keep the cadence
                log.warning("reshard watchdog failed: %s", e)

    async def handoff(
        self, from_addr: str, epoch: int, phase: str, total_rows: int
    ) -> Tuple[bool, str]:
        """Peer-facing Handoff receive (docs/resharding.md)."""
        if self.reshard is None:
            return False, "resharding disabled"
        return await self.reshard.on_handoff(
            from_addr, epoch, phase, total_rows
        )

    async def migrate(
        self, from_addr: str, epoch: int, rows, final: bool
    ) -> Tuple[int, int]:
        """Peer-facing Migrate receive: inject one chunk of packed rows
        for an active inbound handoff."""
        if self.reshard is None:
            raise ApiError(
                "FAILED_PRECONDITION", "resharding disabled"
            )
        try:
            return await self.reshard.on_migrate(
                from_addr, epoch, rows, final
            )
        except KeyError as e:
            raise ApiError("FAILED_PRECONDITION", str(e)) from None

    async def drain_for_shutdown(self) -> int:
        """Graceful scale-down: migrate every owned row to the ring
        without this node (the autoscaler's SIGTERM/preStop drain),
        then keep forwarding stale-routed checks until close.  Returns
        rows shipped; 0 when resharding is disabled or single-node."""
        if self.reshard is None:
            return 0
        return await self.reshard.drain_all()

    def _strip_sketch_global(
        self, reqs: Sequence[RateLimitReq]
    ) -> Sequence[RateLimitReq]:
        """Sketch-tier names don't compose with GLOBAL replication (the
        sketch is not broadcast); strip the flag so such requests route
        plainly to the key's owner and are counted ONCE there instead of
        locally-plus-forwarded (double counting).  Applied on both the
        client routing path and the peer RPC (zero-copy forwards splice
        the client's original bytes, so the owner re-strips)."""
        if self.sketch_backend is None:
            return reqs
        from dataclasses import replace as dc_replace

        return [
            dc_replace(
                r,
                behavior=Behavior(int(r.behavior) & ~int(Behavior.GLOBAL)),
            )
            if (
                has_behavior(r.behavior, Behavior.GLOBAL)
                and self.sketch_backend.handles(r)
            )
            else r
            for r in reqs
        ]

    # ------------------------------------------------------------------
    # hot-key survival plane (runtime/hotkey.py; docs/hotkeys.md)
    # ------------------------------------------------------------------
    def note_traffic(
        self, key_hashes: np.ndarray, hits: np.ndarray
    ):
        """Feed the hot-key detector one batch of routed traffic.
        Called once per batch by whichever path actually serves it (the
        compiled lane's check_raw or the object path), so a fast-lane
        fallback never observes the same requests twice.  Returns what
        the tier wants back through `TierManager.note_done` once the
        batch has been served (None without a tier)."""
        hk = self.hotkeys
        if hk is not None and len(key_hashes):
            with self.metrics.stages.stage("host.hotkey", "host") as st:
                st.tally(
                    keys=len(key_hashes),
                    native=hk.observe(key_hashes, hits),
                )
        tier = self.tier
        if tier is not None and len(key_hashes):
            # Promote-on-access (docs/tiering.md): a served key that is
            # cold-resident schedules a FIFO host-job inject; THIS
            # batch was already answered from whatever the device had.
            return tier.note_access(key_hashes, hits)
        return None

    def _peer_by_fp(self, fp: int) -> Optional[PeerClient]:
        """Owning peer for a device fingerprint — xx rings only, where
        the ring hash IS the XXH64 key fingerprint (the fast router's
        own premise, replicated_hash.ring_arrays).  None on fnv interop
        rings or an empty pool."""
        from gubernator_tpu.net.replicated_hash import xx_64

        pick = self.local_picker
        if pick.size() == 0 or pick.hash_fn is not xx_64:
            return None
        ring, ring_idx, peers = pick.ring_arrays()
        if not len(ring):
            return None
        i = int(np.searchsorted(
            ring, np.int64(fp).astype(np.uint64), side="left"
        ))
        if i == len(ring):
            i = 0
        # ring_idx is the picker's host-side numpy cache, never a
        # device array.
        idx = int(ring_idx[i])  # gubguard: ok=host-sync
        return peers[idx]

    def _owner_pressure_of(self, fp: int) -> float:
        """Owner SLO-pressure ratio for a key fingerprint — the
        multiplier in the hot-key promotion score.  Keys we own use our
        own flight recorder's sustained-breach state; keys a peer owns
        use the ratio that peer advertised on RPC trailing metadata
        (0 once its TTL lapsed).  On fnv interop rings (no fp->owner
        mapping) the strongest signal anywhere applies — conservative:
        it can only promote more, and mirror membership is still
        checked per key at serve time."""
        fr = getattr(self.metrics, "flightrec", None)
        own = (
            fr.pressure_ratio()
            if fr is not None and fr.pressure_active() else 0.0
        )
        peer = self._peer_by_fp(fp)
        if peer is not None:
            if peer.info().is_owner:
                return own
            return peer.pressure_ratio()
        peers = self.local_picker.peers()
        if not peers:
            return own
        return max(
            [own]
            + [
                p.pressure_ratio() for p in peers
                if not p.info().is_owner
            ]
        )

    def _is_mirror_hashed(self, h: int) -> bool:
        """True when this node is one of the key's next-arc mirror
        replicas (owner excluded) for ring hash `h`."""
        try:
            cand = self.local_picker.get_n_hashed(
                h, 1 + self.cfg.hotkey.mirrors
            )
        except PoolEmptyError:
            return False
        return any(p.info().is_owner for p in cand[1:])

    def _mirror_eligible(
        self, req: RateLimitReq, key: str, peer: PeerClient
    ) -> bool:
        """Should this forwarded check serve from a local mirror
        allowance instead?  All four gates must hold: widening enabled,
        the owner currently advertising pressure, the key promoted into
        the hot-set, and this node among the key's next-arc replicas.
        Sketch-tier names never mirror (the CMS tier is already
        cardinality-safe and counts once at the owner)."""
        hk = self.hotkeys
        hkc = self.cfg.hotkey
        if hk is None or hkc.mirrors <= 0:
            return False
        if not peer.pressure_active():
            return False
        if (
            self.sketch_backend is not None
            and self.sketch_backend.handles(req)
        ):
            return False
        from gubernator_tpu.core.hashing import key_hash64
        from gubernator_tpu.runtime.hotkey import fp64

        if not hk.is_hot(fp64(key_hash64(key))):
            return False
        return self._is_mirror_hashed(
            self.local_picker.hash_fn(key.encode())
        )

    def active_mirror_fps(self) -> np.ndarray:
        """int64 fingerprints this node is actively mirroring right now
        (hot AND owner pressured AND we are a next-arc replica) — the
        compiled lane's pull-out mask.  Cached per tracker version with
        a short TTL so pressure transitions land within ~a window.
        Empty on fnv interop rings (the object path still mirrors
        there; only the columnar mask needs the fp->owner mapping)."""
        hk = self.hotkeys
        if hk is None or self.cfg.hotkey.mirrors <= 0:
            return _EMPTY_I64
        hot = hk.hot_arr
        if not len(hot):
            return _EMPTY_I64
        now = time.monotonic()
        cached = self._mirror_fps_cache
        if (
            cached is not None
            and cached[1] == hk.version
            and now - cached[0] < 0.25
        ):
            return cached[2]
        active = [
            int(fp) for fp in hot if self._fp_actively_mirrored(int(fp))
        ]
        arr = (
            np.array(active, dtype=np.int64) if active else _EMPTY_I64
        )
        self._mirror_fps_cache = (now, hk.version, arr)
        return arr

    def _fp_actively_mirrored(self, fp: int) -> bool:
        peer = self._peer_by_fp(fp)
        if peer is None or peer.info().is_owner:
            return False
        if not peer.pressure_active():
            return False
        return self._is_mirror_hashed(int(np.int64(fp).astype(np.uint64)))

    async def _mirror_serve(
        self, req: RateLimitReq, peer: PeerClient
    ) -> RateLimitResp:
        """Serve a hot key from this mirror's LOCAL allowance while its
        owner is under measured SLO pressure.

        The admission algebra is local_shadow's with pressure (not
        death) as the gate: the check rewrites onto
        `<unique_key>.hot-mirror` — its own slot in the local table —
        at `fraction x limit`, so each of the `mirrors` next-arc
        replicas admits at most fraction x limit per window and
        cluster-wide admission for the key stays within
        limit x (1 + mirrors x fraction).  The ORIGINAL hits reconcile
        to the owner through the GLOBAL async-hit machinery
        (aggregated, provably-unsent-gated — at most once), so the
        authoritative row converges on the true total."""
        from dataclasses import replace as dc_replace

        from gubernator_tpu.core.hashing import key_hash64
        from gubernator_tpu.runtime.hotkey import MIRROR_SUFFIX, fp64

        owner = peer.info().grpc_address
        hkc = self.cfg.hotkey
        self.mirror_served += 1
        self.metrics.hotkey_mirror_served.inc()
        self.metrics.getratelimit_counter.labels("local").inc()
        if req.limit <= 0:
            # Deny-all keys stay deny-all on mirrors (the local_shadow
            # rule): the max(1, ...) floor keeps small positive limits
            # serviceable, never fails-open an explicit zero.
            return RateLimitResp(
                status=Status.OVER_LIMIT,
                limit=req.limit,
                remaining=0,
                reset_time=self._resolve_reset_ms(req),
                metadata={"hotkey": "mirror", "owner": owner},
            )
        mirror_limit = max(1, int(req.limit * hkc.fraction))
        mirror = dc_replace(
            req,
            unique_key=req.unique_key + MIRROR_SUFFIX,
            limit=mirror_limit,
            burst=min(req.burst, mirror_limit) if req.burst else 0,
            behavior=Behavior(
                int(req.behavior)
                & ~int(Behavior.GLOBAL)
                & ~int(Behavior.MULTI_REGION)
            ),
        )
        resps = await self._check_local([mirror])
        resp = resps[0]
        if not resp.error:
            md = dict(resp.metadata) if resp.metadata else {}
            md["hotkey"] = "mirror"
            md["owner"] = owner
            resp.metadata = md
            fp = fp64(key_hash64(req.hash_key()))
            if self.hotkeys is not None:
                self.hotkeys.note_name(fp, req.hash_key())
            # Reconcile the ORIGINAL hits toward the owner (async,
            # aggregated per key — global.go:87-95's queue).
            if req.hits:
                self.global_mgr.queue_hit(dc_replace(req))
            # Remember how to drop this mirror slot when the key
            # demotes: zero-hit RESET_REMAINING removes a token row
            # outright and re-fills a leaky one (the shadow-drop
            # mechanics, _drop_shadow).
            self._mirror_resets[fp] = dc_replace(
                mirror,
                hits=0,
                behavior=Behavior(
                    int(mirror.behavior) | int(Behavior.RESET_REMAINING)
                ),
            )
        return resp

    def _on_hot_demote(self, fps: List[int]) -> None:
        """Tracker callback (outside its lock, any thread): the keys
        collapsed out of the hot-set — drop their local mirror slots so
        no stale mirror admission state survives the widening."""
        resets = [
            self._mirror_resets.pop(fp)
            for fp in fps
            if fp in self._mirror_resets
        ]
        if not resets:
            return
        loop = self._loop
        if loop is None or loop.is_closed():
            return

        def submit() -> None:
            t = asyncio.ensure_future(self._reset_mirrors(resets))
            self._shadow_tasks.add(t)
            t.add_done_callback(self._shadow_tasks.discard)

        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is loop:
            submit()
        else:
            loop.call_soon_threadsafe(submit)

    async def _reset_mirrors(self, resets: List[RateLimitReq]) -> None:
        try:
            await self._check_local(resets)
            fr = getattr(self.metrics, "flightrec", None)
            if fr is not None:
                fr.record("hotkey_mirror_drop", keys=len(resets))
        except Exception as e:  # noqa: BLE001 — slots expire anyway
            log.warning("mirror reset after demotion failed: %s", e)

    # ------------------------------------------------------------------
    # SLO-driven adaptive shedding (docs/hotkeys.md)
    # ------------------------------------------------------------------
    def shed_level(self) -> int:
        """Current shed escalation level.  0 = no shedding.  Level L
        sheds requests whose priority class index is < L, where classes
        are the `shed_priorities` globs in lowest-priority-first order.
        Arms only once this node's own p99 breach run has persisted
        `shed_cooldown_s` (the flight recorder's sustained-breach
        clock), escalating one class per further cooldown — and never
        sheds names matching no glob."""
        hkc = self.cfg.hotkey
        if not hkc.enabled or not hkc.shed_priorities:
            return 0
        fr = getattr(self.metrics, "flightrec", None)
        if fr is None:
            return 0
        sustained = fr.pressure_sustained_s()
        if sustained < hkc.shed_cooldown_s:
            return 0
        return min(
            1 + int((sustained - hkc.shed_cooldown_s)
                    // hkc.shed_cooldown_s),
            len(hkc.shed_priorities),
        )

    def shed_priority(self, name: str) -> int:
        """Priority class of a limit name: the index of the first
        matching glob (0 sheds first); names matching none rank past
        every class and are never shed."""
        for i, pat in enumerate(self.cfg.hotkey.shed_priorities):
            if fnmatch.fnmatch(name, pat):
                return i
        return len(self.cfg.hotkey.shed_priorities)

    def _shed_response(self, req: RateLimitReq) -> RateLimitResp:
        """DROP with retry-after rather than queueing: an overloaded
        node must not stack deferred work it cannot serve
        (arXiv:2510.04516's requester-side admission argument)."""
        self.shed_served += 1
        self.metrics.peer_shed_total.labels(
            peerAddr="local", reason="pressure"
        ).inc()
        if self.tenants is not None:
            self.tenants.record_shed(req.name, int(req.hits or 0))
        retry_ms = int(self.cfg.hotkey.shed_cooldown_s * 1000)
        now_ms = int(self.clock.now_ns() // 1_000_000)
        return RateLimitResp(
            status=Status.OVER_LIMIT,
            limit=req.limit,
            remaining=0,
            reset_time=now_ms + retry_ms,
            metadata={
                "shed": "pressure",
                "retry_after_ms": str(retry_ms),
            },
        )

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    async def get_rate_limits(
        self, reqs: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        """The hot path (gubernator.go:194-310)."""
        if len(reqs) > MAX_BATCH_SIZE:
            self.metrics.note_check_error("Request too large")
            raise ApiError(
                "OUT_OF_RANGE",
                "Requests.RateLimits list too large; max size is '%d'"
                % MAX_BATCH_SIZE,
            )
        self._inflight_checks += 1
        self.metrics.concurrent_checks.observe(self._inflight_checks)
        start = time.monotonic()
        try:
            with tracing.span(
                "V1Instance.GetRateLimits", num_items=len(reqs)
            ):
                return await self._get_rate_limits(reqs)
        finally:
            self._inflight_checks -= 1
            self._fd_get_rate_limits.observe(time.monotonic() - start)

    async def _get_rate_limits(
        self, reqs: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        n = len(reqs)
        responses: List[Optional[RateLimitResp]] = [None] * n

        local_idx: List[int] = []
        local_cached: List[bool] = []
        local_owner_meta: List[Optional[str]] = []
        forwards: List[Tuple[int, PeerClient, RateLimitReq, str]] = []
        mirrors: List[Tuple[int, PeerClient, RateLimitReq]] = []
        covered: List[Tuple[int, RateLimitReq, str, object]] = []
        region_serves: List[Tuple[int, RateLimitReq, str, str]] = []

        reqs = self._strip_sketch_global(reqs)

        if self.hotkeys is not None or self.tier is not None:
            valid = [r for r in reqs if r.unique_key and r.name]
            if valid:
                from gubernator_tpu.core.hashing import bulk_key_hash64

                self.note_traffic(
                    bulk_key_hash64([r.hash_key() for r in valid]),
                    np.array([r.hits for r in valid], dtype=np.int64),
                )
        shed = self.shed_level()

        engine_idx: List[int] = []

        single_node = self.local_picker.size() == 0
        for i, req in enumerate(reqs):
            # Client-side validation BEFORE routing (gubernator.go:228-237):
            # an invalid request answers inline — it is never forwarded (no
            # owner metadata on its error) and never queues GLOBAL updates
            # or MULTI_REGION hits.  The peer RPC keeps the owner-side
            # packer validation with QueueUpdate-before-algorithm semantics.
            if not req.unique_key:
                self.metrics.note_check_error("Invalid request")
                responses[i] = RateLimitResp(
                    error="field 'unique_key' cannot be empty"
                )
                continue
            if not req.name:
                self.metrics.note_check_error("Invalid request")
                responses[i] = RateLimitResp(
                    error="field 'namespace' cannot be empty"
                )
                continue
            if shed and self.shed_priority(req.name) < shed:
                # SLO-driven shedding (docs/hotkeys.md): the breach run
                # outlasted the cooldown — drop low-priority traffic
                # BEFORE any routing, device work, or replication
                # queueing (a shed request must leave no state behind).
                responses[i] = self._shed_response(req)
                continue
            key = req.hash_key()
            is_global = has_behavior(req.behavior, Behavior.GLOBAL)
            # Region routing (docs/multiregion.md): a key whose HOME
            # region is elsewhere serves from the bounded local
            # `.region-carve` slot at the in-region owner — never a
            # WAN round-trip on the request path.  GLOBAL and legacy
            # MULTI_REGION traffic keep their own replication lanes.
            region_home: Optional[str] = None
            if (
                self.regions is not None
                and not is_global
                and not has_behavior(req.behavior, Behavior.MULTI_REGION)
            ):
                region_home = self.regions.remote_home(key)
            if single_node:
                if region_home is not None:
                    region_serves.append((i, req, key, region_home))
                    continue
                if is_global and self.global_engine is not None:
                    self.metrics.getratelimit_counter.labels("global").inc()
                    engine_idx.append(i)
                    if has_behavior(req.behavior, Behavior.MULTI_REGION):
                        # The engine path bypasses _check_local's owner-side
                        # queueing — keep cross-region replication alive.
                        self.multi_region_mgr.queue_hits(req)
                else:
                    local_idx.append(i)
                    local_cached.append(False)
                    local_owner_meta.append(None)
                continue
            try:
                peer = self.get_peer(key)
            except PoolEmptyError as e:
                responses[i] = RateLimitResp(
                    error=f"Error in GetPeer, looking up peer that owns "
                    f"rate limit '{key}': {e}"
                )
                continue
            if peer.info().is_owner:
                if region_home is not None:
                    # In-region owner of a remote-homed key: the one
                    # node in this region that carves for it (one
                    # carve per region, not one per node — the bound
                    # counts regions).
                    region_serves.append((i, req, key, region_home))
                    continue
                rs = self.reshard
                if rs is not None and rs.active() and not is_global:
                    # Live resharding (docs/resharding.md): a key whose
                    # arc is mid-handoff must not be served from this
                    # node's (absent or not-yet-authoritative) row.
                    ib = rs.inbound_covering(key)
                    if ib is not None:
                        # We are the NEW owner and the handoff is still
                        # in flight: forward back / bounded shadow.
                        covered.append((i, req, key, ib))
                        continue
                    tgt = rs.reroute_target(key)
                    if tgt is not None:
                        # We are a draining OLD owner whose rows are
                        # gone: forwards-or-serves says forward.
                        tp = self.local_picker.get_by_address(tgt)
                        if tp is not None:
                            forwards.append((i, tp, req, key))
                            continue
                if is_global and self.global_engine is not None:
                    # This node's mesh owns the key: replicated serving +
                    # ICI-collective sync instead of the RPC loops.
                    self.metrics.getratelimit_counter.labels("global").inc()
                    engine_idx.append(i)
                    if has_behavior(req.behavior, Behavior.MULTI_REGION):
                        self.multi_region_mgr.queue_hits(req)
                    continue
                self.metrics.getratelimit_counter.labels("local").inc()
                local_idx.append(i)
                local_cached.append(False)
                local_owner_meta.append(None)
            elif has_behavior(req.behavior, Behavior.GLOBAL):
                self.metrics.getratelimit_counter.labels("global").inc()
                # Serve locally from replicated cache; queue the hit for the
                # owner (gubernator.go:272-283, 420-460).
                local_idx.append(i)
                local_cached.append(True)
                local_owner_meta.append(peer.info().grpc_address)
                self.global_mgr.queue_hit(req)
            elif region_home is None and self._mirror_eligible(req, key, peer):
                # Hot-key widening (docs/hotkeys.md): the owner is
                # measurably pressured and this node is one of the
                # key's next-arc mirrors — serve from the local
                # allowance instead of piling onto the owner.
                mirrors.append((i, peer, req))
            else:
                forwards.append((i, peer, req, key))

        tasks = [
            asyncio.ensure_future(self._forward(peer, req, key))
            for (_, peer, req, key) in forwards
        ]
        mirror_tasks = [
            asyncio.ensure_future(self._mirror_serve(req, peer))
            for (_, peer, req) in mirrors
        ]
        covered_tasks = [
            asyncio.ensure_future(
                self.reshard.serve_covered(req, key, ib)
            )
            for (_, req, key, ib) in covered
        ]
        region_tasks = [
            asyncio.ensure_future(self.regions.serve(req, key, home))
            for (_, req, key, home) in region_serves
        ]

        try:
            if local_idx:
                local_resps = await self._check_local(
                    [reqs[i] for i in local_idx], local_cached
                )
                for j, i in enumerate(local_idx):
                    resp = local_resps[j]
                    if local_owner_meta[j] is not None and not resp.error:
                        resp.metadata = {"owner": local_owner_meta[j]}
                    responses[i] = resp
            if engine_idx:
                eng_reqs = [reqs[i] for i in engine_idx]
                loop = asyncio.get_running_loop()
                eng_resps = await loop.run_in_executor(
                    self._dev_executor,
                    lambda: self.global_engine.check(eng_reqs),
                )
                for j, i in enumerate(engine_idx):
                    responses[i] = eng_resps[j]
                if self._collective_loop is not None:
                    self._collective_loop.notify()
        finally:
            # Always await in-flight forwards — a local-check failure must
            # not orphan tasks whose hits were already applied on peers.
            if tasks:
                results = await asyncio.gather(*tasks, return_exceptions=True)
                for (i, _, _, key), resp in zip(forwards, results):
                    if isinstance(resp, BaseException):
                        responses[i] = RateLimitResp(
                            error=f"Error while fetching rate limit "
                            f"'{key}' from peer: {resp}"
                        )
                    else:
                        responses[i] = resp
            if mirror_tasks:
                results = await asyncio.gather(
                    *mirror_tasks, return_exceptions=True
                )
                for (i, _, req), resp in zip(mirrors, results):
                    if isinstance(resp, BaseException):
                        responses[i] = RateLimitResp(
                            error=f"Error serving hot-key mirror for "
                            f"'{req.hash_key()}': {resp}"
                        )
                    else:
                        responses[i] = resp
            if covered_tasks:
                results = await asyncio.gather(
                    *covered_tasks, return_exceptions=True
                )
                for (i, _, key, _ib), resp in zip(covered, results):
                    if isinstance(resp, BaseException):
                        responses[i] = RateLimitResp(
                            error=f"Error serving resharding key "
                            f"'{key}': {resp}"
                        )
                    else:
                        responses[i] = resp
            if region_tasks:
                results = await asyncio.gather(
                    *region_tasks, return_exceptions=True
                )
                for (i, _, key, _home), resp in zip(region_serves, results):
                    if isinstance(resp, BaseException):
                        responses[i] = RateLimitResp(
                            error=f"Error serving region carve for "
                            f"'{key}': {resp}"
                        )
                    else:
                        responses[i] = resp

        return [r if r is not None else RateLimitResp() for r in responses]

    async def _check_local(
        self,
        reqs: Sequence[RateLimitReq],
        use_cached: Optional[Sequence[bool]] = None,
    ) -> List[RateLimitResp]:
        """Apply checks on the local device engine; queue GLOBAL owner
        updates and MULTI_REGION hits (getRateLimit, gubernator.go:600-631).

        Concurrent callers COALESCE: their requests merge into one device
        step through the local batcher instead of serializing one step per
        RPC — the device analog of the reference's many-workers
        concurrency, and the main p99 lever under concurrent small calls.
        """
        for r, cached in zip(
            reqs, use_cached or [False] * len(reqs)
        ):
            if cached:
                continue  # non-owner read path — not authoritative
            if has_behavior(r.behavior, Behavior.GLOBAL):
                self.global_mgr.queue_update(r)
            if has_behavior(r.behavior, Behavior.MULTI_REGION):
                self.multi_region_mgr.queue_hits(r)
        loop = asyncio.get_running_loop()
        if self.sketch_backend is not None:
            # Split off approximate-tier names; merge answers back in order.
            sk_idx = [
                i for i, r in enumerate(reqs)
                if self.sketch_backend.handles(r)
            ]
            if sk_idx:
                sk_set = set(sk_idx)
                ex_idx = [i for i in range(len(reqs)) if i not in sk_set]
                sk_resps = await loop.run_in_executor(
                    self._dev_executor,
                    lambda: self.sketch_backend.check(
                        [reqs[i] for i in sk_idx]
                    ),
                )
                ex_resps = (
                    await self._local_batcher.check(
                        [reqs[i] for i in ex_idx],
                        [
                            use_cached[i] if use_cached else False
                            for i in ex_idx
                        ],
                    )
                    if ex_idx
                    else []
                )
                out: List[Optional[RateLimitResp]] = [None] * len(reqs)
                for j, i in enumerate(sk_idx):
                    out[i] = sk_resps[j]
                for j, i in enumerate(ex_idx):
                    out[i] = ex_resps[j]
                self._touch_global_captures(
                    [reqs[i] for i in ex_idx],
                    [use_cached[i] for i in ex_idx] if use_cached else None,
                )
                if self.tenants is not None:
                    self.tenants.record_checks(reqs, out)
                return out  # type: ignore[return-value]
        resps = await self._local_batcher.check(reqs, use_cached)
        self._touch_global_captures(reqs, use_cached)
        # Gubstat: every LOCAL device serve — direct and every shadow
        # plane (mirror / lease / degraded / handoff reqs all ride
        # through here with their suffixed unique_key) — tallies into
        # the per-tenant ledger exactly once, at this choke point.
        if self.tenants is not None:
            self.tenants.record_checks(reqs, resps)
        return resps

    def _touch_global_captures(
        self,
        reqs: Sequence[RateLimitReq],
        use_cached: Optional[Sequence[bool]] = None,
    ) -> None:
        """Object-path mutations must degrade any stale captured GLOBAL
        broadcast rows for the touched keys (GlobalManager.touch_hashes).
        No-op unless captures are pending."""
        if not self.global_mgr._pending_h or not reqs:
            return
        from gubernator_tpu.core.hashing import bulk_key_hash64

        keys = [
            r.hash_key()
            for r, cached in zip(
                reqs, use_cached or [False] * len(reqs)
            )
            if not cached
        ]
        if keys:
            self.global_mgr.touch_hashes(bulk_key_hash64(keys))

    async def _forward(
        self, peer: PeerClient, req: RateLimitReq, key: str
    ) -> RateLimitResp:
        """Forward to the owning peer; on NotReady re-resolve the owner (it
        may now be us) up to 5 times (asyncRequests, gubernator.go:327-416).
        When the owner's breaker is open, or the retry loop exhausts, the
        configured GUBER_DEGRADED_MODE policy decides the answer
        (docs/resilience.md).
        """
        attempts = 0
        last_err: Optional[Exception] = None
        cap_s = self.cfg.behaviors.batch_timeout_s
        degraded = self.cfg.degraded_mode != "error"
        while True:
            if attempts > ASYNC_RETRIES:
                return await self._degraded_response(req, key, peer, last_err)
            if attempts != 0 and peer.info().is_owner:
                resps = await self._check_local([req])
                return resps[0]
            if degraded and peer.circuit_open():
                # The owner is known-dead (breaker open, backoff running):
                # re-resolving the ring would hand back the same peer, so
                # serve the degraded policy without burning the retry loop.
                return await self._degraded_response(
                    req, key, peer,
                    last_err or PeerNotReadyError(
                        f"circuit open for {peer.info().grpc_address}"
                    ),
                )
            try:
                self.metrics.getratelimit_counter.labels("forward").inc()
                resp = await peer.get_peer_rate_limit(req)
                # The reference replaces metadata wholesale with the owner
                # annotation (gubernator.go:281,406), but its responses
                # never carry other metadata, so merging is observably
                # identical there — and it preserves the sketch tier's
                # "tier" tag (no reference analog) across forwards.
                md = dict(resp.metadata) if resp.metadata else {}
                md["owner"] = peer.info().grpc_address
                resp.metadata = md
                # (Shadow drop on heal rides peer.on_rpc_success — it
                # fires for this success and every other RPC path.)
                return resp
            except PeerNotReadyError as e:
                last_err = e
                attempts += 1
                self.metrics.asyncrequest_retries.labels(req.name).inc()
                if attempts > ASYNC_RETRIES:
                    continue  # exhausted — no pointless final backoff
                # Back off before re-resolving: immediate retries against a
                # dying peer all complete before any discovery update can
                # land (the reference retries after the peer's reconnect
                # backoff).  Equal-jittered exponential (10ms.. doubling,
                # capped at the batch timeout) keeps total added latency
                # within one RPC budget while decorrelating the retry
                # stampede across forwarders.
                await asyncio.sleep(
                    forward_backoff_s(attempts, cap_s, self._rng)
                )
                try:
                    peer = self.get_peer(key)
                except PoolEmptyError as pe:
                    return RateLimitResp(
                        error="Error finding peer that owns rate limit "
                        f"'{key}': {pe}"
                    )
            except Exception as e:  # noqa: BLE001
                return RateLimitResp(
                    error=f"Error while fetching rate limit '{key}' "
                    f"from peer: {e}"
                )

    def _resolve_reset_ms(self, req: RateLimitReq) -> int:
        """reset_time for a synthesized (degraded / mirror-denied)
        answer.  req.duration under DURATION_IS_GREGORIAN is a
        calendar-interval id (0-5), NOT milliseconds — resolve it
        through the same expansion the algorithm layer uses, or omit
        reset_time when the id is invalid (the authoritative path would
        error on it anyway)."""
        now_ms = int(self.clock.now_ns() // 1_000_000)
        if has_behavior(req.behavior, Behavior.DURATION_IS_GREGORIAN):
            try:
                return gregorian_expiration(
                    self.clock.now(), int(req.duration)
                )
            except GregorianError:
                return 0
        return now_ms + max(int(req.duration), 0)

    # ------------------------------------------------------------------
    # degraded-mode ownership fallback (docs/resilience.md)
    # ------------------------------------------------------------------
    async def _degraded_response(
        self,
        req: RateLimitReq,
        key: str,
        peer: PeerClient,
        last_err: Optional[Exception],
    ) -> RateLimitResp:
        """The answer while the owner is gone, per GUBER_DEGRADED_MODE:

        error        the legacy strict contract — an error response, the
                     client decides (reference gubernator.go:358-366);
        fail_closed  deny: OVER_LIMIT, remaining=0 (an outage admits
                     nothing extra, at the price of rejecting legitimate
                     traffic);
        fail_open    admit: UNDER_LIMIT at the full limit (availability
                     over enforcement — unbounded over-admission while
                     degraded);
        local_shadow serve from a LOCAL shadow slot in the device table
                     at `shadow_fraction` of the limit: each non-owner
                     admits at most fraction*limit per window, bounding
                     cluster-wide over-admission to peers * fraction *
                     limit while keeping per-client fairness.  Shadow
                     state is reset when the owner heals.

        All degraded answers tag `metadata["degraded"]` so clients and
        tests can distinguish them from authoritative decisions."""
        mode = self.cfg.degraded_mode
        if mode == "error":
            return RateLimitResp(
                error="GetPeer() keeps returning peers that are not "
                f"connected for '{key}': {last_err}"
            )
        owner = peer.info().grpc_address
        self.degraded_served += 1
        self.metrics.degraded_total.labels(mode=mode).inc()
        fr = getattr(self.metrics, "flightrec", None)
        if fr is not None:
            fr.record("degraded", mode=mode, key=key, owner=owner)
        reset_ms = self._resolve_reset_ms(req)
        if mode == "fail_closed":
            return RateLimitResp(
                status=Status.OVER_LIMIT,
                limit=req.limit,
                remaining=0,
                reset_time=reset_ms,
                metadata={"degraded": mode, "owner": owner},
            )
        if mode == "fail_open":
            return RateLimitResp(
                status=Status.UNDER_LIMIT,
                limit=req.limit,
                remaining=max(req.limit - req.hits, 0),
                reset_time=reset_ms,
                metadata={"degraded": mode, "owner": owner},
            )
        # local_shadow
        if req.limit <= 0:
            # A deny-all key must stay deny-all while degraded: the
            # max(1, ...) floor below exists to keep a small positive
            # limit serviceable, not to fail-open an explicit zero.
            return RateLimitResp(
                status=Status.OVER_LIMIT,
                limit=req.limit,
                remaining=0,
                reset_time=reset_ms,
                metadata={"degraded": mode, "owner": owner},
            )
        from dataclasses import replace as dc_replace

        shadow_limit = max(1, int(req.limit * self.cfg.shadow_fraction))
        shadow = dc_replace(
            req,
            unique_key=req.unique_key + SHADOW_SUFFIX,
            limit=shadow_limit,
            burst=min(req.burst, shadow_limit) if req.burst else 0,
            behavior=Behavior(
                int(req.behavior)
                & ~int(Behavior.GLOBAL)
                & ~int(Behavior.MULTI_REGION)
            ),
        )
        resps = await self._check_local([shadow])
        resp = resps[0]
        if not resp.error:
            md = dict(resp.metadata) if resp.metadata else {}
            md["degraded"] = mode
            md["owner"] = owner
            resp.metadata = md
            # Remember how to drop this shadow slot on heal: a zero-hit
            # RESET_REMAINING removes a token-bucket row outright
            # (algorithms.go:78-90) and re-fills a leaky one — either
            # way no stale shadow admission state survives the owner
            # becoming authoritative again.
            self._shadow.setdefault(owner, {})[shadow.hash_key()] = (
                dc_replace(
                    shadow,
                    hits=0,
                    behavior=Behavior(
                        int(shadow.behavior)
                        | int(Behavior.RESET_REMAINING)
                    ),
                )
            )
        return resp

    def _drop_shadow(self, addr: str) -> None:
        """The owner healed: reset its shadow slots (fire-and-forget —
        the healed forward that triggered this must not wait on it)."""
        pending = self._shadow.pop(addr, None)
        if not pending:
            return
        resets = list(pending.values())

        async def reset() -> None:
            try:
                await self._check_local(resets)
                fr = getattr(self.metrics, "flightrec", None)
                if fr is not None:
                    fr.record("shadow_drop", owner=addr, keys=len(resets))
            except Exception as e:  # noqa: BLE001 — slots expire anyway
                log.warning(
                    "shadow reset after owner %s healed failed: %s",
                    addr, e,
                )

        t = asyncio.ensure_future(reset())
        self._shadow_tasks.add(t)
        t.add_done_callback(self._shadow_tasks.discard)

    # ------------------------------------------------------------------
    # client-side admission leases (runtime/lease.py; docs/leases.md)
    # ------------------------------------------------------------------
    def spawn_task(self, coro) -> None:
        """Fire-and-forget a coroutine on the service loop, tracked so
        shutdown can await it (the shadow-task discipline)."""
        t = asyncio.ensure_future(coro)
        self._shadow_tasks.add(t)
        t.add_done_callback(self._shadow_tasks.discard)

    async def _lease_sweep_loop(self) -> None:
        """Periodic grant-expiry sweep: lapsed holders are revoked and a
        key's carve slot drops once its last holder is gone, so the
        owner re-collects un-burned allowance without waiting for a
        reconcile that may never come (a dead holder)."""
        interval = max(self.cfg.lease.ttl_ms / 2000.0, 0.05)
        while True:
            await asyncio.sleep(interval)
            try:
                await self.leases.sweep_apply()
            except Exception as e:  # noqa: BLE001 — keep the cadence
                log.warning("lease sweep failed: %s", e)

    def _split_by_owner(self, keys: Sequence[str]):
        """(owned indices, {addr: (peer, indices)}) for a key list —
        the lease/reconcile ownership split.  A pool-empty or
        single-node picker owns everything locally."""
        owned: List[int] = []
        by_peer: Dict[str, Tuple[PeerClient, List[int]]] = {}
        single = self.local_picker.size() == 0
        for i, key in enumerate(keys):
            if single:
                owned.append(i)
                continue
            try:
                peer = self.get_peer(key)
            except PoolEmptyError:
                owned.append(i)
                continue
            if peer.info().is_owner:
                owned.append(i)
            else:
                addr = peer.info().grpc_address
                by_peer.setdefault(addr, (peer, []))[1].append(i)
        return owned, by_peer

    async def lease(
        self, client_id: str, reqs: Sequence[RateLimitReq]
    ) -> List[LeaseGrant]:
        """Grant leases for the keys this node owns; forward the rest
        to their owners (the edge-daemon proxy role — a LeasedClient
        talks to ONE daemon and the ring routes its grants).  Grants
        come back in request order; an unreachable owner refuses
        rather than errors, so the client degrades to per-call checks
        transparently."""
        if self.leases is None:
            return [
                LeaseGrant(
                    key=r.hash_key(), limit=r.limit,
                    refusal="leases disabled",
                )
                for r in reqs
            ]
        out: List[Optional[LeaseGrant]] = [None] * len(reqs)
        owned, by_peer = self._split_by_owner(
            [r.hash_key() for r in reqs]
        )
        if owned:
            grants = await self.leases.grant(
                client_id, [reqs[i] for i in owned]
            )
            for i, g in zip(owned, grants):
                out[i] = g

        async def forward(peer: PeerClient, idx: List[int]) -> None:
            try:
                grants = await peer.lease(
                    client_id, [reqs[i] for i in idx]
                )
                for i, g in zip(idx, grants):
                    out[i] = g
            except Exception as e:  # noqa: BLE001 — refuse, degrade
                for i in idx:
                    out[i] = LeaseGrant(
                        key=reqs[i].hash_key(), limit=reqs[i].limit,
                        refusal=f"owner unreachable: {e}",
                    )

        if by_peer:
            await asyncio.gather(
                *(forward(p, idx) for p, idx in by_peer.values())
            )
        return [
            g if g is not None else LeaseGrant(refusal="not routed")
            for g in out
        ]

    async def reconcile(
        self, client_id: str, items: Sequence
    ) -> List[LeaseGrant]:
        """Apply burned-hit reconciliation for the keys this node owns;
        forward the rest to their owners.  One grant per item in item
        order (allowance 0 unless the item asked to renew)."""
        if self.leases is None:
            return [
                LeaseGrant(
                    key=it.request.hash_key(), limit=it.request.limit,
                    refusal="leases disabled",
                )
                for it in items
            ]
        from dataclasses import replace as dc_replace

        out: List[Optional[LeaseGrant]] = [None] * len(items)
        owned, by_peer = self._split_by_owner(
            [it.request.hash_key() for it in items]
        )
        if owned:
            grants = await self.leases.reconcile(
                client_id, [items[i] for i in owned]
            )
            for i, g in zip(owned, grants):
                out[i] = g

        # Non-owned burned hits ride GlobalManager.queue_hit — the
        # at-most-once aggregation whose flush re-queues on provably-
        # unsent failures, so a holder's burn survives an owner
        # partition and converges after heal (a direct forward would
        # have to drop it on any failure).  Only the release/renew
        # bookkeeping forwards to the owner's LeaseManager, with hits
        # zeroed so they cannot double-apply.
        for _peer, idx in by_peer.values():
            for i in idx:
                if items[i].request.hits > 0:
                    self.global_mgr.queue_hit(
                        dc_replace(items[i].request)
                    )

        async def forward(peer: PeerClient, idx: List[int]) -> None:
            if not any(
                items[i].release or items[i].renew for i in idx
            ):
                # Burn-only items already rode queue_hit — nothing
                # for the owner's LeaseManager to learn.
                for i in idx:
                    out[i] = LeaseGrant(
                        key=items[i].request.hash_key(),
                        limit=items[i].request.limit,
                    )
                return
            stripped = [
                dc_replace(
                    items[i],
                    request=dc_replace(items[i].request, hits=0),
                )
                for i in idx
            ]
            try:
                grants = await peer.reconcile(client_id, stripped)
                for i, g in zip(idx, grants):
                    out[i] = g
            except Exception as e:  # noqa: BLE001
                # Renewals refuse (the client degrades); a lost release
                # is re-collected by the owner's TTL sweep.
                for i in idx:
                    out[i] = LeaseGrant(
                        key=items[i].request.hash_key(),
                        limit=items[i].request.limit,
                        refusal=f"owner unreachable: {e}",
                    )

        if by_peer:
            await asyncio.gather(
                *(forward(p, idx) for p, idx in by_peer.values())
            )
        return [
            g if g is not None else LeaseGrant(refusal="not routed")
            for g in out
        ]

    # ------------------------------------------------------------------
    # peer-facing API (server side)
    # ------------------------------------------------------------------
    async def get_peer_rate_limits(
        self, reqs: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        """Owner side of a forwarded batch: apply ALL requests in one device
        step (replacing the reference's goroutine fan-out,
        gubernator.go:482-543) preserving request order."""
        if len(reqs) > MAX_BATCH_SIZE:
            raise ApiError(
                "OUT_OF_RANGE",
                "'PeerRequest.rate_limits' list too large; max size is '%d'"
                % MAX_BATCH_SIZE,
            )
        # Forwarders normally strip GLOBAL from sketch-tier names before
        # sending, but zero-copy forwards (the compiled lane) splice the
        # client's original bytes — re-strip here so a GLOBAL+sketch
        # request never queues an exact-table broadcast for a sketch key.
        reqs = self._strip_sketch_global(reqs)
        if self.hotkeys is not None or self.tier is not None:
            # Owner-side detection: forwarded traffic is exactly the
            # load a pressured owner needs to see per key.
            valid = [r for r in reqs if r.unique_key and r.name]
            if valid:
                from gubernator_tpu.core.hashing import bulk_key_hash64

                self.note_traffic(
                    bulk_key_hash64([r.hash_key() for r in valid]),
                    np.array([r.hits for r in valid], dtype=np.int64),
                )
        special: Dict[int, object] = {}
        if self.regions is not None:
            # Region routing (docs/multiregion.md): a forwarded check
            # for a remote-homed key lands here because this node is
            # the key's in-region owner — serve the bounded
            # `.region-carve` slot, never the raw row at full limit.
            # The WAN reconcile lane arrives at the HOME region's
            # owner, where remote_home() is None, and applies below.
            for i, r in enumerate(reqs):
                if not r.unique_key or not r.name:
                    continue
                if has_behavior(r.behavior, Behavior.GLOBAL):
                    continue
                if has_behavior(r.behavior, Behavior.MULTI_REGION):
                    continue
                key = r.hash_key()
                home = self.regions.remote_home(key)
                if home is not None:
                    special[i] = ("region", key, home)
        rs = self.reshard
        if rs is not None and rs.active():
            # Live resharding (docs/resharding.md): forwarded checks
            # for mid-handoff keys must not apply on this node's table.
            # Covered inbound keys (we are the new owner, handoff in
            # flight) forward back / serve the bounded shadow; rerouted
            # outbound keys (our rows are gone — post-TRANSFER or a
            # draining leaver) forward to the new owner.  Everything
            # else applies locally as usual.  (Remote-homed keys keep
            # their region dispatch: the carve slot is a derived slot
            # and migrates with the arc.)
            for i, r in enumerate(reqs):
                if i in special:
                    continue
                if not r.unique_key or not r.name:
                    continue
                if has_behavior(r.behavior, Behavior.GLOBAL):
                    continue
                key = r.hash_key()
                ib = rs.inbound_covering(key)
                if ib is not None:
                    special[i] = ("covered", key, ib)
                    continue
                tgt = rs.reroute_target(key)
                if tgt is not None:
                    tp = self.local_picker.get_by_address(tgt)
                    if tp is not None:
                        special[i] = ("reroute", key, tp)
        if special:
            async def _serve_special(spec, r):
                kind, key, arg = spec
                if kind == "region":
                    return await self.regions.serve(r, key, arg)
                if kind == "covered":
                    return await rs.serve_covered(r, key, arg)
                return await self._forward(arg, r, key)

            kept = [
                r for i, r in enumerate(reqs) if i not in special
            ]
            inner_task = asyncio.gather(*(
                _serve_special(special[i], reqs[i])
                for i in sorted(special)
            ), return_exceptions=True)
            inner = (
                await self._check_local(kept) if kept else []
            )
            spec_resps = dict(zip(sorted(special), await inner_task))
            it = iter(inner)
            out: List[RateLimitResp] = []
            for i, r in enumerate(reqs):
                if i in special:
                    resp = spec_resps[i]
                    if isinstance(resp, BaseException):
                        resp = RateLimitResp(
                            error="Error serving forwarded key "
                            f"'{r.hash_key()}': {resp}"
                        )
                    out.append(resp)
                else:
                    out.append(next(it))
            return out
        shed = self.shed_level()
        if shed:
            # Owner-side shedding of forwarded traffic — the relief
            # valve that actually unloads a pressured owner.
            shed_idx = {
                i for i, r in enumerate(reqs)
                if r.name and self.shed_priority(r.name) < shed
            }
            if shed_idx:
                kept = [
                    r for i, r in enumerate(reqs) if i not in shed_idx
                ]
                inner = await self._check_local(kept) if kept else []
                it = iter(inner)
                return [
                    self._shed_response(r) if i in shed_idx else next(it)
                    for i, r in enumerate(reqs)
                ]
        return await self._check_local(reqs)

    async def update_peer_globals(
        self, globals_: Sequence[UpdatePeerGlobal]
    ) -> None:
        """Receive owner-authoritative GLOBAL statuses into the local cache
        (gubernator.go:464-479)."""
        rows = [
            (
                g.key,
                int(g.algorithm),
                int(g.status.limit),
                int(g.status.remaining),
                int(g.status.status),
                int(g.status.reset_time),
            )
            for g in globals_
            if g.status is not None
        ]
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            self._dev_executor, lambda: self.backend.apply_cached_rows(rows)
        )

    # ------------------------------------------------------------------
    # health / lifecycle
    # ------------------------------------------------------------------
    async def health_check(self) -> HealthCheckResp:
        """Report peer connectivity from the rolling per-peer error windows
        (gubernator.go:546-598)."""
        errs: List[str] = []
        local_peers = self.local_picker.peers()
        for peer in local_peers:
            for msg in peer.last_errors():
                errs.append(
                    f"Error returned from local peer.GetLastErr: {msg}"
                )
        region_peers = self.region_picker.peers()
        for peer in region_peers:
            for msg in peer.last_errors():
                errs.append(
                    f"Error returned from region peer.GetLastErr: {msg}"
                )
        # Circuit plane: an open/half-open breaker is a live statement
        # that a peer is being shed — surface it even after the error
        # window has pruned the failures that tripped it.
        for peer in local_peers + region_peers:
            state = peer.circuit_state_name()
            if state in ("open", "half_open"):
                snap = peer.circuit_snapshot()
                errs.append(
                    f"Circuit {state} for peer "
                    f"{peer.info().grpc_address} (trips="
                    f"{snap.get('trips', 0)}, reopens in "
                    f"{snap.get('open_remaining_s', 0.0):g}s)"
                )
        h = HealthCheckResp(
            status=HEALTHY, peer_count=len(local_peers) + len(region_peers)
        )
        if errs:
            h.status = UNHEALTHY
            h.message = "|".join(errs)
        # Pressure plane (docs/hotkeys.md): an overloaded-but-ALIVE
        # peer — clean error window, breaker closed, SLO advertised
        # breached — must not read as fully healthy.  Advisory lines
        # only: the peer IS serving, so status stays driven by
        # connectivity (flipping it would invite LB churn on exactly
        # the node that needs its traffic spread, not removed).
        pressure_lines = []
        for peer in local_peers + region_peers:
            ratio = peer.pressure_ratio()
            if ratio >= 1.0:
                pressure_lines.append(
                    f"Pressure on peer {peer.info().grpc_address}: "
                    f"advertised p99 at {ratio:.2f}x its SLO target"
                )
        lvl = self.shed_level()
        if lvl:
            pressure_lines.append(
                f"Pressure shedding active on this node (level {lvl} "
                f"of {len(self.cfg.hotkey.shed_priorities)})"
            )
        # Migration-state lines (docs/resharding.md): in-flight
        # handoffs are advisory — the node IS serving, just with
        # covered keys routed through the handoff protocol.
        if self.reshard is not None and self.reshard.active():
            pressure_lines.extend(self.reshard.health_lines())
        if pressure_lines:
            extra = "|".join(pressure_lines)
            h.message = f"{h.message}|{extra}" if h.message else extra
        # SLO telemetry rides along (runtime/flightrec.py): the rolling
        # p99 vs the configured target, so degraded-mode decisions can
        # key off measured tail latency (status itself stays driven by
        # peer connectivity, like the reference).
        fr = getattr(self.metrics, "flightrec", None)
        if fr is not None and fr.breaches:
            slo = (
                f"SLO: {fr.breaches} p99 breach(es) of "
                f"{fr.slo_p99_ms:g}ms target; rolling "
                f"p99={fr.last_p99_ms:.3f}ms"
            )
            h.message = f"{h.message}|{slo}" if h.message else slo
        return h

    def _engine_synced(self, pending) -> None:
        """Bridge collective syncs to the RPC tier: after the engine applies
        a window's hits on the auth table, broadcast the (now authoritative)
        statuses to cross-NODE peers via the RPC GlobalManager.  Runs on a
        device-executor thread, so hop to the loop for the asyncio queues."""
        if self.local_picker.size() <= 1:
            return  # single node — every peer already saw the all_gather
        loop = self._loop
        if loop is None or loop.is_closed():
            return

        def queue_all() -> None:
            for p in pending.values():
                self.global_mgr.queue_update(p.req)

        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is loop:
            queue_all()
        else:
            loop.call_soon_threadsafe(queue_all)

    async def close(self) -> None:
        """Flush managers, run the Loader save, shut down peers
        (gubernator.go:159-189)."""
        if self._closed:
            return
        self._closed = True
        if self._lease_sweep_task is not None:
            self._lease_sweep_task.cancel()
            await asyncio.gather(
                self._lease_sweep_task, return_exceptions=True
            )
            self._lease_sweep_task = None
        if self._reshard_watch_task is not None:
            self._reshard_watch_task.cancel()
            await asyncio.gather(
                self._reshard_watch_task, return_exceptions=True
            )
            self._reshard_watch_task = None
        if self._collective_loop is not None:
            await self._collective_loop.close()
        await self.global_mgr.close()
        await self.multi_region_mgr.close()
        if self.regions is not None:
            await self.regions.close()
        await self._local_batcher.close()
        if self.cfg.loader is not None:
            loop = asyncio.get_running_loop()
            items = await loop.run_in_executor(
                self._dev_executor, self.backend.live_items
            )
            self.cfg.loader.save(iter(items))
        peers = set(self.local_picker.peers()) | set(
            self.region_picker.peers()
        )
        if peers:
            await asyncio.gather(
                *(p.shutdown() for p in peers), return_exceptions=True
            )
        self._dev_executor.shutdown(wait=True)


class LocalBatcher:
    """Coalesces concurrent local checks into shared device steps.

    No artificial wait window (unlike the network peer batcher, there is no
    RPC to amortize): a drain loop takes EVERYTHING queued the moment the
    device is free and runs it as one step.  Under load the step rate is
    device-bound while arrival concurrency rides along as extra lanes —
    latency stays ~2 steps instead of `concurrency` steps.
    """

    def __init__(self, service: Service, max_coalesce: int = 8192) -> None:
        self.s = service
        self.max_coalesce = max_coalesce
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        # Device steps this batcher ran (round-trip accounting).
        self.steps = 0

    async def check(
        self,
        reqs: Sequence[RateLimitReq],
        use_cached: Optional[Sequence[bool]] = None,
    ) -> List[RateLimitResp]:
        if self._task is None:
            self._task = asyncio.ensure_future(self._run())
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        await self._queue.put((list(reqs), use_cached, fut))
        return await fut

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            entries = [await self._queue.get()]
            total = len(entries[0][0])
            while total < self.max_coalesce:
                try:
                    e = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
                entries.append(e)
                total += len(e[0])

            merged: List[RateLimitReq] = []
            merged_cached: List[bool] = []
            for reqs, cached, _ in entries:
                merged.extend(reqs)
                merged_cached.extend(
                    cached if cached is not None else [False] * len(reqs)
                )
            self.steps += 1
            try:
                resps = await loop.run_in_executor(
                    self.s._dev_executor,
                    lambda: self.s.backend.check(merged, merged_cached),
                )
            except Exception as e:  # noqa: BLE001
                for _, _, fut in entries:
                    if not fut.done():
                        fut.set_exception(e)
                continue
            off = 0
            for reqs, _, fut in entries:
                if not fut.done():
                    fut.set_result(resps[off:off + len(reqs)])
                off += len(reqs)

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self._task = None


async def window_flush_loop(event, sync_wait_s, take, flush) -> None:
    """The shared batching heartbeat (interval.go:29-72's one-shot ticker):
    the first queued item sets `event`, opening a `sync_wait_s` window;
    when it closes, `take()`'s batch (if any) goes to `flush`.  A flush
    failure is logged and the cadence survives (the flushers do their own
    per-chunk error handling; this guard is the backstop)."""
    while True:
        await event.wait()
        await asyncio.sleep(sync_wait_s)
        event.clear()
        batch = take()
        if batch:
            try:
                await flush(batch)
            except Exception as e:  # noqa: BLE001 — keep the cadence
                log.error("window flush failed: %s", e)


class CollectiveGlobalLoop:
    """Drives GlobalEngine.sync on the global_sync_wait cadence — the
    collective analog of the reference's runAsyncHits + runBroadcasts
    timers (global.go:63-64, 96-119): the first queued hit opens a sync
    window; everything queued within it syncs in one all_to_all/all_gather
    step.  (The batch-limit trigger lives in GlobalEngine.check itself.)
    """

    def __init__(self, service: Service, engine) -> None:
        self.s = service
        self.engine = engine
        self.sync_wait_s = service.cfg.behaviors.global_sync_wait_s
        self._event = asyncio.Event()
        self._task: Optional[asyncio.Task] = None

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.ensure_future(
                window_flush_loop(
                    self._event, self.sync_wait_s,
                    lambda: self.engine.pending, self._flush,
                )
            )

    def notify(self) -> None:
        """Hits were queued on the engine — open/extend a sync window."""
        self._event.set()

    async def _flush(self, _pending) -> None:
        loop = asyncio.get_running_loop()
        start = time.monotonic()
        n = await loop.run_in_executor(
            self.s._dev_executor, self.engine.sync
        )
        if n:
            self.s.metrics.async_durations.observe(time.monotonic() - start)

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self._task = None
        # Final flush so queued hits survive a graceful shutdown.
        if self.engine.pending:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(
                self.s._dev_executor, self.engine.sync
            )


class GlobalManager:
    """Async GLOBAL replication loops (global.go:33-254)."""

    def __init__(self, service: Service) -> None:
        self.s = service
        cfg = service.cfg.behaviors
        self.sync_wait_s = cfg.global_sync_wait_s
        self.batch_limit = cfg.global_batch_limit
        self.timeout_s = cfg.global_timeout_s
        self._hits: Dict[str, RateLimitReq] = {}
        # key -> (req, captured status | None).  A captured status is the
        # post-step stored state from the drain that queued it — broadcast
        # directly, no zero-hit re-read needed.  None falls back to the
        # re-read (object path, engine bridge).
        self._updates: Dict[
            str, Tuple[RateLimitReq, Optional[RateLimitResp]]
        ] = {}
        # Device-fingerprint hash -> key, for entries holding a captured
        # status; lets mutation paths degrade a capture that went stale
        # (touch_hashes) without decoding keys.
        self._pending_h: Dict[int, str] = {}
        self._pending_arr: Optional[np.ndarray] = None
        self._hits_event = asyncio.Event()
        self._updates_event = asyncio.Event()
        self._tasks: List[asyncio.Task] = []
        # Observability counters (scraped by tests for eventual-consistency
        # assertions, functional_test.go:843-867).
        self.async_sends = 0
        self.broadcasts = 0
        # Round-trip accounting: zero-hit broadcast re-read batches/keys
        # (each batch is one LocalBatcher device step).
        self.reread_batches = 0
        self.reread_keys = 0

    def start(self) -> None:
        if self._tasks:
            return
        self._tasks = [
            asyncio.ensure_future(self._run_async_hits()),
            asyncio.ensure_future(self._run_broadcasts()),
        ]

    def queue_hit(self, r: RateLimitReq) -> None:
        """Aggregate a non-owner hit (summing same-key hits,
        global.go:87-95)."""
        key = r.hash_key()
        cur = self._hits.get(key)
        if cur is not None:
            cur.hits += r.hits
        else:
            from dataclasses import replace as dc_replace

            self._hits[key] = dc_replace(r)
        self._hits_event.set()

    def queue_update(
        self, r: RateLimitReq, status: Optional[RateLimitResp] = None
    ) -> None:
        """Record an owner-side status change to broadcast
        (global.go:167-191; last write per key wins).

        `status` is the drain's own post-step stored state for the key —
        when supplied, the broadcast uses it directly instead of running
        the zero-hit re-read of global.go:205-250 (equivalent by
        construction: a GLOBAL-cleared hits=0 read of a bucket row
        reports exactly the post-step stored status/remaining/reset; see
        ops.step.Resp.stored_status).  Callers that cannot capture pass
        None and keep the re-read."""
        key = r.hash_key()
        self._updates[key] = (r, status)
        if status is not None:
            from gubernator_tpu.core.hashing import key_hash64

            h = int(np.uint64(key_hash64(key)).view(np.int64))
            if self._pending_h.get(h) != key:
                self._pending_h[h] = key
                self._pending_arr = None
        self._updates_event.set()

    def touch_hashes(self, hashes: np.ndarray) -> None:
        """Degrade captured updates whose key a later drain mutated
        WITHOUT re-queueing (a non-GLOBAL request on the same key): the
        broadcast must not ship the stale capture, so the entry falls
        back to the zero-hit re-read — which sees the post-mutation
        state, exactly like the reference's flush-time read.  Called by
        every machinery mutation path with the drained int64 fingerprint
        column; near-free while no captures are pending.

        Concurrent-drain caveat: with overlapped drains a capture can be
        queued after the touch of a later-completing drain and survive
        one window stale — bounded by GLOBAL's eventual consistency (the
        reference's own broadcast value is stale by its flush+network
        delay)."""
        if not self._pending_h:
            return
        if self._pending_arr is None:
            self._pending_arr = np.fromiter(
                self._pending_h.keys(), dtype=np.int64,
                count=len(self._pending_h),
            )
        hit = np.isin(self._pending_arr, hashes)
        if not hit.any():
            return
        for h in self._pending_arr[hit]:
            key = self._pending_h.pop(int(h), None)
            if key is None:
                continue
            cur = self._updates.get(key)
            if cur is not None and cur[1] is not None:
                self._updates[key] = (cur[0], None)
        self._pending_arr = None

    def _take_hits(self) -> Dict[str, RateLimitReq]:
        hits, self._hits = self._hits, {}
        return hits

    def _take_updates(
        self,
    ) -> Dict[str, Tuple[RateLimitReq, Optional[RateLimitResp]]]:
        updates, self._updates = self._updates, {}
        self._pending_h.clear()
        self._pending_arr = None
        return updates

    async def _run_async_hits(self) -> None:
        # The first queued hit opens a sync_wait window; everything queued
        # within it flushes together (interval semantics, global.go:96-119),
        # split into batch_limit-sized RPCs by _send_hits.
        await window_flush_loop(
            self._hits_event, self.sync_wait_s,
            self._take_hits, self._send_hits,
        )

    async def _send_hits(self, hits: Dict[str, RateLimitReq]) -> None:
        """Group aggregated hits by owning peer and flush
        (global.go:124-164)."""
        by_peer: Dict[str, Tuple[PeerClient, List[RateLimitReq]]] = {}
        for key, r in hits.items():
            try:
                peer = self.s.get_peer(key)
            except PoolEmptyError:
                continue
            addr = peer.info().grpc_address
            by_peer.setdefault(addr, (peer, []))[1].append(r)
        start = time.monotonic()

        async def flush_one(peer: PeerClient, batch: List[RateLimitReq]):
            # One RPC per batch_limit-sized slice (the owner rejects
            # batches over MAX_BATCH_SIZE, gubernator.go:486-490).
            for lo in range(0, len(batch), self.batch_limit):
                chunk = batch[lo:lo + self.batch_limit]
                try:
                    await asyncio.wait_for(
                        peer.get_peer_rate_limits_batch(chunk),
                        timeout=self.timeout_s,
                    )
                    self.async_sends += 1
                except Exception as e:  # noqa: BLE001
                    if provably_unsent(e, peer):
                        # Shutdown / queue-full / connect-refused provably
                        # precede any delivery, so re-queueing cannot double
                        # count; a transiently unreachable owner keeps the
                        # window's hits (aggregation bounds the backlog by
                        # unique keys).
                        log.warning(
                            "re-queueing global hits for '%s': %s",
                            peer.info().grpc_address, e,
                        )
                        for r in chunk:
                            self.queue_hit(r)
                    else:
                        # Timeout or mid-RPC failure: the owner MAY have
                        # applied the batch already — re-sending would
                        # double count.  Drop, like the reference
                        # (global.go:152-162); the next live hit re-syncs.
                        log.error(
                            "dropping global hits for '%s': %s",
                            peer.info().grpc_address, e,
                        )

        # Fan out per peer — one slow peer must not delay the others.
        # The flush is a trace ROOT (sampled per the configured root
        # sampler): it aggregates many requests' queued hits, so there
        # is no single request context to continue — but the peer RPCs
        # under it still carry w3c traceparent, connecting the flush to
        # the owner daemons' server spans.
        with tracing.span(
            "global.flush_hits", parent=None,
            peers=len(by_peer), keys=len(hits),
        ):
            await asyncio.gather(
                *(flush_one(p, b) for p, b in by_peer.values())
            )
        self.s.metrics.async_durations.observe(time.monotonic() - start)

    async def _run_broadcasts(self) -> None:
        await window_flush_loop(
            self._updates_event, self.sync_wait_s,
            self._take_updates, self._broadcast_peers,
        )

    async def _read_statuses(self, reads) -> List[RateLimitResp]:
        """Zero-hit status re-read for the broadcast, on the OBJECT path.

        Deliberately NOT routed through the compiled lane: re-read lanes
        share keys with in-flight client GLOBAL merges, and a key whose
        occurrences mix use_cached (client reads) with uncached (the
        re-read) loses host-cascade eligibility and falls back to one
        device round per occurrence.  The LocalBatcher still coalesces
        concurrent re-read batches."""
        return await self.s._check_local(reads)

    async def _broadcast_peers(
        self,
        updates: Dict[str, Tuple[RateLimitReq, Optional[RateLimitResp]]],
    ) -> None:
        """Push each updated status to every non-owner peer
        (global.go:205-250).  Entries whose drain captured the post-step
        stored state broadcast it directly; the rest re-read it (hits=0,
        GLOBAL cleared to avoid re-queueing) on the object path."""
        from dataclasses import replace as dc_replace

        globals_: List[UpdatePeerGlobal] = []
        to_read: List[RateLimitReq] = []
        for key, (r, captured) in updates.items():
            if captured is None:
                to_read.append(r)
            elif not captured.error:
                # An errored capture (validation / Gregorian) broadcasts
                # nothing — the re-read would fail the same way and be
                # skipped below.
                globals_.append(
                    UpdatePeerGlobal(
                        key=key, status=captured, algorithm=r.algorithm
                    )
                )
        if to_read:
            # Clear GLOBAL (avoid re-queueing a broadcast,
            # global.go:214-215) AND MULTI_REGION (a zero-hit status read
            # must not wake the cross-region sender).
            reads = [
                dc_replace(
                    r,
                    hits=0,
                    behavior=Behavior(
                        int(r.behavior)
                        & ~int(Behavior.GLOBAL)
                        & ~int(Behavior.MULTI_REGION)
                    ),
                )
                for r in to_read
            ]
            self.reread_batches += 1
            self.reread_keys += len(reads)
            try:
                statuses = await self._read_statuses(reads)
            except Exception as e:  # noqa: BLE001
                # The captured entries need no read — still ship them.
                log.error("while broadcasting update to peers: %s", e)
                statuses = []
            for r, status in zip(reads, statuses):
                if status.error:
                    continue
                globals_.append(
                    UpdatePeerGlobal(
                        key=r.hash_key(), status=status,
                        algorithm=r.algorithm,
                    )
                )
        if not globals_:
            return
        start = time.monotonic()

        async def push_one(peer: PeerClient) -> bool:
            try:
                # Chunk to respect the receiver's 1MB message cap.
                for lo in range(0, len(globals_), self.batch_limit):
                    await asyncio.wait_for(
                        peer.update_peer_globals(
                            globals_[lo:lo + self.batch_limit]
                        ),
                        timeout=self.timeout_s,
                    )
                return True
            except PeerNotReadyError:
                return False
            except Exception as e:  # noqa: BLE001
                log.error(
                    "while broadcasting global updates to '%s': %s",
                    peer.info().grpc_address, e,
                )
                return False

        with tracing.span(
            "global.broadcast", parent=None, updates=len(globals_)
        ):
            results = await asyncio.gather(
                *(
                    push_one(p)
                    for p in self.s.peer_list()
                    if not p.info().is_owner
                )
            )
        sent = any(results)
        if sent:
            self.broadcasts += 1
            self.s.metrics.broadcast_durations.observe(
                time.monotonic() - start
            )

    async def close(self) -> None:
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        self._tasks = []
        # Drain-on-close: flush queued hits and broadcast queued updates
        # (best effort) — a graceful multi-node shutdown must not strand the
        # last window's statuses, especially those the collective engine's
        # final sync just queued for cross-node broadcast.
        hits = self._take_hits()
        if hits:
            await self._send_hits(hits)
        updates = self._take_updates()
        if updates:
            await self._broadcast_peers(updates)


class MultiRegionManager:
    """Cross-region (DCN-tier) hit replication.

    The reference ships only the skeleton — queue + interval loop with a
    no-op sender (multiregion.go:23-102).  Here the sender works: aggregated
    hits flush to the key's owner in every OTHER region, with MULTI_REGION
    cleared on the forwarded copy so receiving regions apply the hits locally
    instead of re-forwarding (the GLOBAL broadcast loop-prevention pattern,
    global.go:214-215).  Every region therefore converges on the sum of all
    regions' hits per key.
    """

    def __init__(self, service: Service) -> None:
        self.s = service
        cfg = service.cfg.behaviors
        self.sync_wait_s = cfg.multi_region_sync_wait_s
        self.batch_limit = cfg.multi_region_batch_limit
        self.timeout_s = cfg.multi_region_timeout_s
        self._hits: Dict[str, RateLimitReq] = {}
        self._event = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self.region_sends = 0

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.ensure_future(self._run())

    def queue_hits(self, r: RateLimitReq) -> None:
        key = r.hash_key()
        cur = self._hits.get(key)
        if cur is not None:
            cur.hits += r.hits
        else:
            from dataclasses import replace as dc_replace

            self._hits[key] = dc_replace(r)
        self._event.set()

    def _take_hits(self) -> Dict[str, RateLimitReq]:
        hits, self._hits = self._hits, {}
        return hits

    async def _run(self) -> None:
        await window_flush_loop(
            self._event, self.sync_wait_s, self._take_hits, self._send_hits
        )

    async def _send_hits(self, hits: Dict[str, RateLimitReq]) -> None:
        from dataclasses import replace as dc_replace

        by_peer: Dict[str, Tuple[PeerClient, List[RateLimitReq]]] = {}
        for key, r in hits.items():
            fwd = dc_replace(
                r,
                behavior=Behavior(
                    int(r.behavior) & ~int(Behavior.MULTI_REGION)
                ),
            )
            for peer in self.s.region_picker.get_clients(key):
                addr = peer.info().grpc_address
                by_peer.setdefault(addr, (peer, []))[1].append(fwd)
        async def flush_one(peer: PeerClient, batch: List[RateLimitReq]):
            for lo in range(0, len(batch), self.batch_limit):
                chunk = batch[lo:lo + self.batch_limit]
                attempts = 0
                while True:
                    try:
                        await asyncio.wait_for(
                            peer.get_peer_rate_limits_batch(chunk),
                            timeout=self.timeout_s,
                        )
                        self.region_sends += 1
                        break
                    except Exception as e:  # noqa: BLE001
                        # Retry in place (with the peer that failed): a
                        # GLOBAL-style re-queue would double-count the
                        # regions that already received this window's fan.
                        attempts += 1
                        if attempts > 3:
                            log.error(
                                "dropping multi-region hits for '%s': %s",
                                peer.info().grpc_address, e,
                            )
                            break
                        # Floor the backoff at 200ms*attempt: a restarted
                        # peer's gRPC channel needs ~1s to reconnect, and
                        # sync_wait-paced retries (500µs default) would all
                        # fail inside that window and drop the hits.
                        await asyncio.sleep(
                            max(0.2 * attempts, self.sync_wait_s)
                        )

        await asyncio.gather(
            *(flush_one(p, b) for p, b in by_peer.values())
        )

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
