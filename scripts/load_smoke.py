"""CI smoke: one seeded gubload scenario end to end against a
2-daemon in-process cluster (docs/loadgen.md), proving the whole
open-loop harness chain in one required step:

  1. schedule determinism — the same GUBER_LOAD_SEED builds
     byte-identical arrival plans (digest equality across two builds,
     and across worker shardings: the union of shards IS the plan);
  2. the flashcrowd scenario passes its merged-ledger verdict (exact
     accounting: ledger allowed == client-observed admissions, the
     zipfian hot key saturates its limit exactly, global bound holds);
  3. phase markers landed in every daemon's flight-recorder ring
     (kind="load_phase", enter AND exit for each phase) — the
     phase-linked attribution an operator joins dumps against;
  4. every artifact row passes gubload's schema check
     (loadgen/report.py validate_row).

On any failure each daemon's flight recorder dumps its ring to
GUBER_FLIGHTREC_DIR (default flightrec-dumps/) so the CI artifact
step can pick the evidence up.

Run from the repo root:  python scripts/load_smoke.py [--seed N]
The whole run is deterministic given the seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Runnable from a checkout without an installed package.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCENARIO = "flashcrowd"


def _dump_flightrec(cluster) -> None:
    for d in cluster.daemons:
        if d.flightrec is not None:
            path = cluster.run(d.flightrec.dump("load_smoke_failure"))
            print(f"flightrec dump ({d.grpc_address}): {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int,
                    default=int(os.environ.get("GUBER_LOAD_SEED", 424242)))
    ap.add_argument("--duration", type=float, default=4.0)
    ap.add_argument("--target-rps", type=float, default=300.0)
    args = ap.parse_args(argv)

    from gubernator_tpu.core.config import DaemonConfig, LoadConfig
    from gubernator_tpu.loadgen import (
        SCENARIOS, build_schedules, run_scenario, validate_row,
    )
    from gubernator_tpu.testing import Cluster

    cfg = LoadConfig(
        seed=args.seed, scenario=SCENARIO,
        duration_s=args.duration, clients=6,
        target_rps=args.target_rps,
    )
    spec = SCENARIOS[SCENARIO]

    # 1. Determinism before any RPC: two builds from the seed are
    # byte-identical, and sharding is a partition of the plan.
    a, b = build_schedules(spec, cfg), build_schedules(spec, cfg)
    assert [s.digest() for s in a] == [s.digest() for s in b], (
        "schedule build is not deterministic for a fixed seed"
    )
    for sched in a:
        shards = sched.shard(4)
        assert sum(len(s) for s in shards) == len(sched)
        assert sorted(
            t for s in shards for t in s.times_s.tolist()
        ) == sorted(sched.times_s.tolist()), (
            "worker shards do not partition the schedule"
        )
    print(f"load_smoke: schedules deterministic (seed={cfg.seed}, "
          f"{[len(s) for s in a]} arrivals/phase)")

    # Own cluster (NOT run_scenario's) so the flight-recorder rings are
    # still inspectable after the run.
    conf = DaemonConfig(
        flightrec=True,
        flightrec_dir=os.environ.get(
            "GUBER_FLIGHTREC_DIR", "flightrec-dumps"
        ),
        # Sized so the run's per-request records cannot evict the first
        # phase's markers before we inspect the ring.
        flightrec_ring=16384,
    )
    cluster = Cluster.start_with(["", ""], conf_template=conf)
    try:
        # 2. The scenario itself — run_scenario raises AssertionError
        # with the ledger facts when the verdict fails.
        result = run_scenario(SCENARIO, cfg, cluster=cluster)
        verdict = result["verdict"]
        print(f"load_smoke: {SCENARIO} verdict proven: "
              f"{json.dumps(verdict)}")

        # 3. Phase markers in every daemon's ring: enter AND exit per
        # phase, tagged with this scenario.
        want_phases = {p.name for p in spec.phases}
        for d in cluster.daemons:
            ring = d.flightrec.snapshot()["ring"]
            marks = [r for r in ring if r.get("kind") == "load_phase"
                     and r.get("scenario") == SCENARIO]
            for action in ("enter", "exit"):
                got = {r["phase"] for r in marks
                       if r.get("action") == action}
                assert want_phases <= got, (
                    f"{d.grpc_address}: flightrec ring missing "
                    f"load_phase {action} markers: want {want_phases}, "
                    f"got {got}"
                )
        print(f"load_smoke: phase markers present in "
              f"{len(cluster.daemons)} rings ({sorted(want_phases)})")

        # 4. Artifact schema.
        artifact = result["artifact"]
        for row in artifact["results"]:
            validate_row(row)
        print(f"load_smoke: {len(artifact['results'])} artifact rows "
              "valid")
    except BaseException:
        _dump_flightrec(cluster)
        raise
    finally:
        cluster.stop()

    print(f"load_smoke: PASS (seed={cfg.seed})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
