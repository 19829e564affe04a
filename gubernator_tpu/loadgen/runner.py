"""Scenario runner (docs/loadgen.md): boots (or targets) a cluster,
precomputes every phase's arrival schedule, drives them open-loop,
applies fault hooks at phase boundaries, and ends in the scenario's
merged-ledger verdict plus the run's artifact (report.py).

The runner is the composition point: schedule.py plans, engine.py
dispatches and records, spec.py/scenarios.py decide pass/fail, and
report.py shapes the proof into the artifact.
"""
from __future__ import annotations

import asyncio
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..core.config import LoadConfig
from ..runtime.metrics import HdrRecorder
from . import report, schedule
from .engine import PhaseTracker, open_loop
from .scenarios import CONF_OVERRIDES, SCENARIOS, hot_key_index
from .spec import PhaseSpec, RunContext, ScenarioSpec


def resolve_scenario(name: str) -> ScenarioSpec:
    try:
        return SCENARIOS[name]
    except KeyError:
        raise ValueError(
            f"unknown scenario {name!r} (GUBER_LOAD_SCENARIO / "
            f"--scenario): one of {sorted(SCENARIOS)}"
        ) from None


def scaled_phases(
    spec: ScenarioSpec, cfg: LoadConfig
) -> List[Tuple[PhaseSpec, float, float]]:
    """(phase, actual_duration_s, target_rps): phase durations are
    nominal weights rescaled so the whole scenario spans
    GUBER_LOAD_DURATION; rps defaults to GUBER_LOAD_TARGET_RPS."""
    total = sum(p.duration_s for p in spec.phases)
    scale = cfg.duration_s / total
    return [
        (p, p.duration_s * scale, p.target_rps or cfg.target_rps)
        for p in spec.phases
    ]


def build_schedules(
    spec: ScenarioSpec, cfg: LoadConfig
) -> List[schedule.Schedule]:
    """Every phase's plan, precomputed before the first RPC — seeds
    derived per phase from the one GUBER_LOAD_SEED, so identical seeds
    reproduce identical arrival times AND key draws."""
    return [
        schedule.build(
            p.arrivals, p.keys,
            schedule.derive_seed(cfg.seed, f"{spec.name}/{i}/{p.name}"),
            rps, dur, spec.key_universe, p.params,
        )
        for i, (p, dur, rps) in enumerate(scaled_phases(spec, cfg))
    ]


def _dump_flightrec(cluster, reason: str) -> None:
    for d in cluster.daemons:
        if d.flightrec is not None:
            path = cluster.run(d.flightrec.dump(reason))
            print(f"flightrec dump ({d.grpc_address}): {path}")


def run_scenario(
    name: str,
    cfg: LoadConfig,
    cluster=None,
    addresses: Optional[Sequence[str]] = None,
    profile_dir: Optional[str] = None,
    num_daemons: int = 2,
) -> Dict:
    """Run one scenario end to end and return
    {"verdict", "artifact", "phase_stats", ...}.  Raises
    AssertionError when the scenario's ledger verdict fails.

    `cluster`: an existing testing.Cluster to drive (kept running).
    `addresses`: external daemon addresses — only scenarios whose
    hooks/verdicts don't need in-process daemons can run this way.
    Neither: boots its own in-process `num_daemons` cluster.
    """
    spec = resolve_scenario(name)
    if addresses and spec.needs_cluster:
        raise ValueError(
            f"scenario {name!r} needs an in-process cluster (fault "
            "hooks / breaker introspection) and cannot drive external "
            "addresses"
        )

    scheds = build_schedules(spec, cfg)
    phases = scaled_phases(spec, cfg)

    from ..testing import ChaosInjector, ChaosPlan

    injector = ChaosInjector(ChaosPlan(seed=cfg.seed))
    injector.set_active(False)  # armed only by fault hooks

    own_cluster = False
    conf = None
    if cluster is None and not addresses:
        from ..core.config import DaemonConfig
        from ..testing import Cluster

        overrides = CONF_OVERRIDES.get(name, dict)()
        conf = DaemonConfig(
            chaos=injector,
            flightrec=True,
            flightrec_dir=os.environ.get(
                "GUBER_FLIGHTREC_DIR", "flightrec-dumps"
            ),
            **overrides,
        )
        cluster = Cluster.start_with(
            list(spec.datacenters) or [""] * num_daemons,
            conf_template=conf,
        )
        own_cluster = True
    elif cluster is not None:
        conf = cluster.daemons[0].conf
        inj = getattr(conf, "chaos", None)
        if inj is not None:
            injector = inj

    addrs = list(addresses) if addresses else cluster.addresses()
    ctx = RunContext(spec, cfg, cluster, injector, addrs)
    ctx.state["conf_template"] = conf
    ctx.state["hot_key_idx"] = hot_key_index(spec, scheds)

    latency = {p.name: HdrRecorder() for p, _, _ in phases}
    skew = HdrRecorder()
    tracker = PhaseTracker(
        spec.name,
        daemons=ctx.daemons,
        profile_dir=profile_dir,
    )
    wall: Dict[str, float] = {}

    async def drive() -> None:
        from ..client import AsyncV1Client
        from ..core.types import RateLimitReq, Status

        clients = [
            AsyncV1Client(addrs[i % len(addrs)])
            for i in range(max(1, min(cfg.clients, 64)))
        ]
        n_sent = 0

        async def send(key_idx: int) -> bool:
            nonlocal n_sent
            n_sent += 1
            c = clients[n_sent % len(clients)]
            r = (await c.get_rate_limits([
                RateLimitReq(
                    name=spec.tenant,
                    unique_key=spec.key_name(key_idx),
                    hits=1, limit=spec.limit,
                    duration=spec.window_ms,
                )
            ], timeout=5.0))[0]
            if r.error != "":
                raise RuntimeError(r.error)
            return r.status == Status.UNDER_LIMIT

        try:
            for (p, dur, rps), sched in zip(phases, scheds):
                tracker.enter(p.name, profile=p.profile)
                if p.fault is not None:
                    await spec.hooks[p.fault](ctx)
                t0 = time.monotonic()
                ctx.counts_by_phase[p.name] = await open_loop(
                    send, sched, latency[p.name], skew
                )
                wall[p.name] = time.monotonic() - t0
            tracker.exit()
        finally:
            tracker.exit()
            for c in clients:
                await c.close()

    t_run = time.monotonic()
    try:
        if cluster is not None:
            # Drive on the cluster's own loop: grpc.aio channels and
            # the daemons' servers then share one poller (a second
            # loop's poller races grpc's completion queue into benign
            # but noisy BlockingIOError callbacks).
            cluster.run(drive(), timeout=cfg.duration_s * 10 + 120.0)
        else:
            asyncio.run(drive())
        verdict = spec.verdict(ctx)
    except BaseException:
        if own_cluster:
            _dump_flightrec(cluster, f"load-{name}-failure")
        raise
    finally:
        if own_cluster:
            cluster.stop()
    total_wall = time.monotonic() - t_run

    overall = HdrRecorder()
    for h in latency.values():
        overall.merge(h)

    phase_stats = {
        p.name: {
            "arrivals": len(sched),
            "intended_rps": round(len(sched) / dur, 1) if dur else 0.0,
            "wall_s": round(wall.get(p.name, 0.0), 3),
            "recorder": latency[p.name],
        }
        for (p, dur, rps), sched in zip(phases, scheds)
    }

    artifact = report.build_artifact(
        spec=spec, cfg=cfg, verdict=verdict, overall=overall,
        skew=skew, phase_stats=phase_stats, total_wall_s=total_wall,
    )
    return {
        "scenario": spec.name,
        "seed": cfg.seed,
        "verdict": verdict,
        "artifact": artifact,
        "phase_stats": phase_stats,
        "overall": overall,
        "skew": skew,
    }
