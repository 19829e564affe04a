"""The program's side of the benchmark's seam.

`bench/` (BENCHMARK.json's harness) reads the program through three
doors: `/debug/vars` paths, `/metrics` series, and — in `bench/serve.py`,
which starts the daemon in-process — a handful of private names.  The
driver's chip run is the only thing that notices when one of them is
renamed, as `output_malformed`, at the cost of a PR.  These cases notice
here.  The files under `bench/` are read, never written and never
imported: a path named by a layer metric is found by parsing its JSON;
a name the harness's code reaches for is found by reading, so those are
written out below, each with the line that uses it.

One daemon of each kind per module: one chip for the `mach` and `wire`
rows, a 4-shard mesh with GLOBAL traffic and one sync tick for the
`engine` and `global` rows, and one chip with the two-tier table on
(docs/tiering.md), cold rows restored, promoted and demoted, for the
`tier` rows and the tier's private names.
"""
from __future__ import annotations

import json
import re
import time
import urllib.request
from pathlib import Path

import pytest

from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.proto import gubernator_pb2 as pb

BENCH = Path(__file__).resolve().parent.parent / "bench"
GET_RATE_LIMITS = "/pb.gubernator.V1/GetRateLimits"


def _layer_reads():
    """(vars paths, series) named by bench/layer_metrics/*.json."""
    paths, series = set(), set()

    def walk(node):
        if isinstance(node, str) and node.startswith("vars:"):
            paths.add(node[len("vars:"):])
        elif isinstance(node, dict) and "metrics" in node:
            series.add((node["metrics"],
                        tuple(sorted(node.get("labels", {}).items()))))
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    for f in sorted((BENCH / "layer_metrics").glob("*.json")):
        walk(json.loads(f.read_text())["read"])
    return sorted(paths), sorted(series)


LAYER_VARS, LAYER_SERIES = _layer_reads()

# /debug/vars keys the harness's code reads: (path, what it must hold).
CODE_VARS = [
    ("device.compiled_lane", True),               # bench/run.py:397
    ("fastpath.fallbacks", "number"),             # bench/run.py:519
    ("fastpath.serve_mode", "pipelined"),         # bench/run.py:521
    ("fastpath.effective_serve_mode", "pipelined"),   # bench/run.py:521
    ("backend.not_persisted", "number"),          # bench/run.py:526
    ("backend.occupancy", "number"),              # bench/run.py:527
    ("backend.checks", "number"),                 # bench/run.py:580
    # bench/readers/global_sync_roofline_share.mesh.py:26 (the compiled
    # program's `bytes_accessed` went with PR 41: the reader counts from
    # the work since PR 31 and reads these two alone)
    ("global.engine.sync_program.shards", "number"),
    ("global.engine.sync_program.delta_slots", "number"),
    ("stages", "block"),       # bench/readers/idle_named_share.open.py:15
    # bench/readers/step_hbm_share.closed.py: lanes = checks - occ + groups
    # (there once a drain has cascaded: the seam's RPCs hold keys thrice)
    ("stages.mach.cascade.occ", "number"),
    ("stages.mach.cascade.groups", "number"),
]

# Jitted programs the traced metrics find by name
# (`read.program_regex` of bench/layer_metrics/*.json): regex, daemon,
# where the program hangs.
STEP_REGEX = "^jit_(apply_batch|sharded|_local)"
SYNC_REGEX = "^jit__global_sync"
PROGRAMS = [
    (STEP_REGEX, "one_chip", "backend._step_packed_q"),
    (STEP_REGEX, "mesh", "backend._step_packed"),
    (SYNC_REGEX, "mesh", "global_engine._sync_step"),
]

# Names of the program bench/serve.py reaches for: (module or object,
# attribute, "callable" | "attr", daemon).  Objects are spelled from the
# service, as the harness spells them.
MODULE_NAMES = [
    ("gubernator_tpu.ops.step", "_f64", "callable"),              # :64
    ("gubernator_tpu.runtime.backend", "_packed_resp_dict",
     "callable"),                                                 # :70
    ("gubernator_tpu.runtime.backend", "packed_rounds_to_host",
     "callable"),                                                 # :139
    ("gubernator_tpu.parallel.sharded", "packed_grid_rounds_to_host",
     "callable"),                                                 # :135
    ("gubernator_tpu.core.config", "setup_daemon_config",
     "callable"),                                                 # :254
    ("gubernator_tpu.daemon", "Daemon", "callable"),              # :256
]
OBJECT_NAMES = [
    ("backend", "_install_table", "callable", "one_chip"),        # :100
    ("backend", "_install_table", "callable", "mesh"),
    ("backend", "occupancy", "callable", "one_chip"),             # :101
    ("backend", "_found_mask", "callable", "one_chip"),           # :107
    ("backend", "_found_mask", "callable", "mesh"),
    ("backend", "_lock", "attr", "one_chip"),                     # :106
    ("backend", "clock", "attr", "one_chip"),                     # :97
    ("backend", "cfg", "attr", "one_chip"),                       # :105
    ("backend", "table", "attr", "one_chip"),                     # :161
    ("backend", "_tiers", "attr", "one_chip"),                    # :183
    ("backend", "_step_packed_q", "callable", "one_chip"),        # :167
    ("backend", "_step_packed", "callable", "mesh"),              # :161
    ("backend", "_psharding", "attr", "mesh"),                    # :149
    ("backend", "device_info", "callable", "one_chip"),           # :242
    ("global_engine", "_lock", "attr", "mesh"),                   # :145
    ("global_engine", "_ingest", "callable", "mesh"),             # :151
    ("global_engine", "cache_table", "attr", "mesh"),             # :151
    ("global_engine", "n", "attr", "mesh"),                       # :148
]


def _is_mesh_row(path: str) -> bool:
    return re.search(r"(^|\.)(engine|global)(\.|$)", path) is not None


def _is_tier_row(path: str) -> bool:
    return re.search(r"(^|\.)tier(\.|$)", path) is not None


def _resolve(tree, path: str):
    """Every node at `path`; a `*` is every key of its level and must
    match at least one."""
    nodes = [tree]
    for part in path.split("."):
        nxt = []
        for n in nodes:
            if not isinstance(n, dict):
                continue
            if part == "*":
                nxt.extend(n.values())
            elif part in n:
                nxt.append(n[part])
        nodes = nxt
    return nodes


def _is_number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _series(text: str):
    out = []
    for line in text.splitlines():
        m = re.match(r"^([A-Za-z_:][\w:]*)(\{.*\})?\s+(\S+)$", line)
        if m and not line.startswith("#"):
            out.append((m.group(1),
                        dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                        m.group(2) or ""))))
    return out


class _Seam:
    """A started daemon and what the harness would have scraped."""

    def __init__(self, cluster, global_keys: int) -> None:
        import grpc.aio

        self.cluster = cluster
        self.daemon = d = cluster.daemon_at(0)

        async def drive():
            ch = grpc.aio.insecure_channel(d.grpc_address)
            rpc = ch.unary_unary(GET_RATE_LIMITS)
            try:
                for i in range(4):
                    reqs = [
                        pb.RateLimitReq(
                            name="seam", unique_key=f"k{i}_{j % 5}",
                            hits=1, limit=100, duration=60_000,
                            algorithm=j % 2,
                        )
                        for j in range(12)
                    ] + [
                        pb.RateLimitReq(
                            name="seamg", unique_key=f"g{j}", hits=1,
                            limit=1_000_000, duration=60_000,
                            behavior=pb.GLOBAL,
                        )
                        for j in range(global_keys)
                    ]
                    raw = await rpc(pb.GetRateLimitsReq(
                        requests=reqs
                    ).SerializeToString())
                    resp = pb.GetRateLimitsResp.FromString(raw)
                    assert not any(r.error for r in resp.responses)
                # One key three times, alone in its drain: three rounds
                # plain, a read lane and a write-back cascaded — the drain
                # cascades (fastpath._cascade_or_rounds).
                raw = await rpc(pb.GetRateLimitsReq(requests=[
                    pb.RateLimitReq(name="seam", unique_key="thrice",
                                    hits=1, limit=100, duration=60_000)
                ] * 3).SerializeToString())
                resp = pb.GetRateLimitsResp.FromString(raw)
                assert [r.remaining for r in resp.responses] == [99, 98, 97]
            finally:
                await ch.close()

        cluster.run(drive(), timeout=120)
        eng = d.service.global_engine
        if eng is not None:
            deadline = time.monotonic() + 60
            while eng.debug_vars()["syncs"] == 0:
                assert time.monotonic() < deadline, "no sync tick"
                time.sleep(0.05)
        self.vars = json.loads(self._get("/debug/vars"))
        self.series = _series(self._get("/metrics").decode())

    def _get(self, path: str) -> bytes:
        with urllib.request.urlopen(
            f"http://{self.daemon.http_address}{path}", timeout=30
        ) as r:
            return r.read()

    def obj(self, name: str):
        return getattr(self.daemon.service, name)


@pytest.fixture(scope="module")
def one_chip():
    from gubernator_tpu import native
    from gubernator_tpu.testing.cluster import Cluster

    if not native.available():
        pytest.skip("native library unavailable")
    c = Cluster.start(1, device=DeviceConfig(
        num_slots=1 << 12, ways=8, batch_size=128,
    ))
    try:
        yield _Seam(c, global_keys=0)
    finally:
        c.stop()


@pytest.fixture(scope="module")
def mesh():
    from gubernator_tpu import native
    from gubernator_tpu.testing.cluster import Cluster

    if not native.available():
        pytest.skip("native library unavailable")
    c = Cluster.start(1, device=DeviceConfig(
        num_slots=1 << 14, ways=8, batch_size=128, num_shards=4,
    ))
    try:
        yield _Seam(c, global_keys=6)
    finally:
        c.stop()


TIER_SLOTS = 1 << 12


@pytest.fixture(scope="module")
def tiered():
    """One chip with the tier on: cold rows restored for the keys the
    seam's RPCs send (so they promote), then a keyspace past the
    table's high mark (so a tick demotes)."""
    import numpy as np

    from gubernator_tpu import native
    from gubernator_tpu.core.config import DaemonConfig, TierConfig
    from gubernator_tpu.core.hashing import key_hash64
    from gubernator_tpu.testing.cluster import Cluster

    if not native.available():
        pytest.skip("native library unavailable")
    c = Cluster.start(1, device=DeviceConfig(
        num_slots=TIER_SLOTS, ways=8, batch_size=128,
    ), conf_template=DaemonConfig(tier=TierConfig(
        enabled=True, cold_capacity=8192, high_water=0.5, low_water=0.4,
        demote_batch=64, interval_s=0.2,
    )))
    try:
        d = c.daemon_at(0)
        tier = d.service.tier
        fps = np.array([
            np.uint64(key_hash64(f"seam_k{i}_{j}")).view(np.int64)
            for i in range(4) for j in range(5)
        ], dtype=np.int64)
        n = len(fps)
        now = d.service.backend.clock.millisecond_now()
        kept = tier.cold.restore({
            "key_hash": fps, "algo": np.arange(n) % 2,
            "limit": np.full(n, 100), "duration": np.full(n, 60_000),
            "remaining": np.full(n, 50),
            "remaining_f": np.full(n, 50.0), "t0": np.full(n, now),
            "status": np.zeros(n), "burst": np.full(n, 100),
            "expire_at": np.full(n, now + 60_000),
        })
        assert kept == n == tier.cold.residents()
        seam = _Seam(c, global_keys=0)

        async def fill():
            import grpc.aio

            ch = grpc.aio.insecure_channel(d.grpc_address)
            rpc = ch.unary_unary(GET_RATE_LIMITS)
            try:
                for lo in range(0, 3000, 500):
                    await rpc(pb.GetRateLimitsReq(requests=[
                        pb.RateLimitReq(
                            name="fill", unique_key=f"f{k}", hits=1,
                            limit=100, duration=60_000)
                        for k in range(lo, lo + 500)
                    ]).SerializeToString())
            finally:
                await ch.close()

        c.run(fill(), timeout=120)
        deadline = time.monotonic() + 60
        while True:
            seam.vars = json.loads(seam._get("/debug/vars"))
            # A whole tick over the mark (its row counts at its end).
            if (seam.vars["stages"]["tier"]["demote"]["count"]
                    and seam.vars["tier"]["promotes"] >= n):
                break
            assert time.monotonic() < deadline, tier.debug_vars()
            time.sleep(0.05)
        tier.close()            # the scrape stands still from here
        seam.vars = json.loads(seam._get("/debug/vars"))
        yield seam
    finally:
        c.stop()


def _seam_for(request, path: str) -> _Seam:
    return request.getfixturevalue(
        "mesh" if _is_mesh_row(path)
        else "tiered" if _is_tier_row(path) else "one_chip"
    )


# -- (a) /debug/vars ------------------------------------------------------

@pytest.mark.parametrize("path", LAYER_VARS)
def test_layer_metric_vars_path_resolves(request, path):
    nodes = _resolve(_seam_for(request, path).vars, path)
    assert nodes, f"/debug/vars has nothing at {path}"
    assert all(_is_number(n) for n in nodes), (path, nodes)


@pytest.mark.parametrize("path,want", CODE_VARS,
                         ids=[p for p, _ in CODE_VARS])
def test_harness_vars_key_resolves(request, path, want):
    nodes = _resolve(_seam_for(request, path).vars, path)
    assert len(nodes) == 1, f"/debug/vars has nothing at {path}"
    got = nodes[0]
    if want == "number":
        assert _is_number(got), (path, got)
    elif want == "block":
        assert isinstance(got, dict) and got, (path, got)
    else:
        assert got == want and type(got) is type(want), (path, got)


def test_layer_metrics_were_found():
    # The parse above is the test's own; if the files change form it
    # must fail here and not pass on nothing.
    assert len(LAYER_VARS) >= 29 and len(LAYER_SERIES) >= 2


def test_the_seams_daemon_has_cascaded(one_chip):
    """What `step_hbm_share.closed` subtracts: groups the host replayed
    and the occurrences they held, on a daemon whose drains held a key
    three times (left open by PR 29-31: PERF.md section 7)."""
    row = one_chip.vars["stages"]["mach"]["cascade"]
    assert row["count"] > 0
    assert row["occ"] >= 3 * row["groups"] > 0


# -- (b) private names ----------------------------------------------------

@pytest.mark.parametrize(
    "module,name,kind", MODULE_NAMES,
    ids=[f"{m.rsplit('.', 1)[-1]}.{n}" for m, n, _ in MODULE_NAMES],
)
def test_module_name_bench_serve_uses(module, name, kind):
    import importlib

    got = getattr(importlib.import_module(module), name)
    assert callable(got) == (kind == "callable")


@pytest.mark.parametrize(
    "obj,name,kind,daemon", OBJECT_NAMES,
    ids=[f"{d}.{o}.{n}" for o, n, _, d in OBJECT_NAMES],
)
def test_object_name_bench_serve_uses(request, obj, name, kind, daemon):
    target = request.getfixturevalue(daemon).obj(obj)
    assert target is not None, obj
    got = getattr(target, name)
    if kind == "callable":
        assert callable(got)
    else:
        assert got is not None and not callable(got)


@pytest.mark.parametrize("daemon", ["one_chip", "mesh"])
def test_device_info_and_warmup(request, daemon):
    seam = request.getfixturevalue(daemon)
    info = seam.obj("backend").device_info()    # bench/serve.py:242, :268
    for key in ("platform", "device_kind", "device_count",
                "table_device_ids"):
        assert key in info, key
    assert len(info["table_device_ids"]) == (4 if daemon == "mesh" else 1)
    assert seam.daemon._warmup_s > 0            # bench/serve.py:270


def test_step_packed_q_is_a_partial_of_a_jitted_function(one_chip):
    # bench/serve.py `_lane_responses` CALLS it on an all-inactive batch
    # of every tier (it lowered `step.func` once, and no longer does:
    # the pin of the partial's inside went with that, ROADMAP C12): the
    # table comes back as it was, a response comes with it.
    import numpy as np

    be = one_chip.obj("backend")
    before = be.occupancy()
    now = np.int64(be.clock.millisecond_now())
    with be._lock:
        for t in be._tiers:
            be.table, resp = be._step_packed_q(
                be.table, np.zeros((12, t), dtype=np.int64), now)
            assert resp is not None
    assert be.occupancy() == before


@pytest.mark.parametrize(
    "regex,daemon,where", PROGRAMS,
    ids=[f"{d}.{w}" for _, d, w in PROGRAMS],
)
def test_program_name_matches_the_traced_metrics_regex(
    request, regex, daemon, where
):
    obj, attr = where.split(".")
    fn = getattr(request.getfixturevalue(daemon).obj(obj), attr)
    fn = getattr(fn, "func", fn)          # through a functools.partial
    # XLA names a jitted program "jit_" + the function's name.
    assert re.match(regex, "jit_" + fn.__name__), fn.__name__


# -- (c) /metrics ---------------------------------------------------------

@pytest.mark.parametrize(
    "series,labels", LAYER_SERIES,
    ids=[s for s, _ in LAYER_SERIES],
)
def test_layer_metric_series_is_exported(one_chip, series, labels):
    want = dict(labels)
    assert any(
        name == series and all(lab.get(k) == v for k, v in want.items())
        for name, lab in one_chip.series
    ), f"/metrics has no {series}{want}"


# -- (d) the cluster cell: the forward hop (PR 39) --------------------------
#
# `peers4-10m.batch.closed` reads a routed daemon: bench/run.py holds the
# `calltype` series to the plan's own count of the hop
# (forwarded_checks_differ, local_checks_differ), the five "peer hop"
# metrics read the hop's ledger rows, and the harness starts each daemon
# of the cluster from the environment and refuses the run by the names of
# its ready report.

HOP_SERIES = "gubernator_getratelimit_counter_total"   # bench/run.py:77
PEER_HOP_METRICS = sorted(
    f.stem for f in (BENCH / "layer_metrics").glob("peer_*.json")
)


@pytest.fixture(scope="module")
def routed():
    """Daemon 0 of two on one ring, after RPCs whose keys both own."""
    from gubernator_tpu import native
    from gubernator_tpu.testing.cluster import Cluster

    if not native.available():
        pytest.skip("native library unavailable")
    c = Cluster.start(2, device=DeviceConfig(
        num_slots=1 << 12, ways=8, batch_size=128,
    ))
    try:
        yield _Seam(c, global_keys=0)
    finally:
        c.stop()


@pytest.mark.parametrize("calltype", ["forward", "local"])
def test_hop_series_counts_by_calltype(routed, calltype):
    assert any(
        name == HOP_SERIES and lab.get("calltype") == calltype
        for name, lab in routed.series
    ), f"/metrics has no {HOP_SERIES}{{calltype={calltype}}}"
    counted = routed.daemon.metrics.getratelimit_counter.labels(calltype)
    assert counted._value.get() > 0


def test_the_peer_hop_metrics_were_found():
    # PR 39's five; PR 43's peer_checks_per_forward and peer_batch_wait_ms.
    assert len(PEER_HOP_METRICS) == 7, PEER_HOP_METRICS


@pytest.mark.parametrize("name", PEER_HOP_METRICS)
def test_peer_hop_metric_reads_a_live_routed_daemon(routed, name):
    """Every term of the metric's data file finds ONE number on a daemon
    that has forwarded, and the numerator has grown from zero."""
    read = json.loads(
        (BENCH / "layer_metrics" / f"{name}.json").read_text())["read"]
    assert read["kind"] == "ratio" and read["delta"] is True
    total = {"num": 0.0, "den": 0.0}
    for side in ("num", "den"):
        for term in read[side]:
            if isinstance(term, dict):
                assert any(
                    n == term["metrics"]
                    and all(lab.get(k) == v
                            for k, v in term["labels"].items())
                    for n, lab in routed.series
                ), term
                total[side] += 1.0      # exported; its value is /metrics'
                continue
            nodes = _resolve(routed.vars, term[len("vars:"):])
            assert len(nodes) == 1 and _is_number(nodes[0]), (term, nodes)
            total[side] += nodes[0]
    assert total["num"] > 0 and total["den"] > 0, (name, total)


# -- (d') the two-tier table: what bench/ reads of it ---------------------

# bench/run.py:353-412 `tiered_counts`: the `tier` block's keys.
TIER_BLOCK = [
    "demotes", "promotes", "cold_hits", "capacity_drops",
    "promote_failures", "promote_retries", "demote_passes", "ticks",
    "cold_residents", "cold_capacity",
]
TIER_LATENCY = ["buckets", "cumulative", "sum_s", "p99_s"]


@pytest.mark.parametrize("key", TIER_BLOCK)
def test_tier_block_key_is_a_number(tiered, key):
    assert _is_number(tiered.vars["tier"][key]), key


def test_tier_block_holds_the_promote_latency_histogram(tiered):
    lat = tiered.vars["tier"]["promote_latency"]
    assert set(TIER_LATENCY) <= set(lat)
    assert len(lat["cumulative"]) == len(lat["buckets"]) + 1
    assert lat["cumulative"][-1] == tiered.vars["tier"]["promotes"] > 0
    assert _is_number(lat["sum_s"]) and _is_number(lat["p99_s"])


def test_the_ledger_shows_lane_tier_with_its_stages_and_counters(tiered):
    from gubernator_tpu.runtime.coldtier import TIER_COUNTERS, TIER_STAGES

    lane = tiered.vars["stages"]["tier"]
    assert set(lane) == {s.split(".", 1)[1] for s in TIER_STAGES}
    for stage, counters in TIER_COUNTERS.items():
        row = lane[stage.split(".", 1)[1]]
        assert set(counters) <= set(row), (stage, row)
    # What the seam's daemon did, as the ledger counted it.
    assert lane["restore"]["rows"] == 20 and lane["restore"]["count"] == 1
    assert lane["note_access"]["cold_hits"] >= 20
    assert lane["promote"]["rows_popped"] >= 20
    assert lane["promote"]["rows_injected"] >= 20
    assert 0 < lane["promote"]["inject_launches"]
    assert lane["promote"]["inject_lanes"] \
        >= 128 * lane["promote"]["inject_launches"]
    assert lane["demote"]["demote_rows"] == tiered.vars["tier"]["demotes"]
    assert lane["demote"]["demote_launches"] \
        == tiered.vars["tier"]["demote_passes"] > 0
    assert lane["lock"]["count"] >= lane["promote"]["count"] > 0


TIER_NAMES = [
    # (object spelled from service.tier, attribute, kind); bench/serve.py
    ("cold", "restore", "callable"),              # :183 preload_cold
    ("cold", "residents", "callable"),            # :187
    ("cold", "member_hits", "callable"),          # :157 the probe's tiers
    ("cold", "pop_rows", "callable"),             # :100 droppromote
    ("", "_promote", "callable"),                 # :106
    ("", "_pending", "attr"),                     # :102
    ("", "_cv", "attr"),                          # :101
    ("", "promotes", "attr"),                     # :103
    ("", "_protect_grid", "callable"),            # :219
    ("cfg", "demote_batch", "attr"),              # :225
]


@pytest.mark.parametrize("obj,name,kind", TIER_NAMES,
                         ids=[f"{o or 'tier'}.{n}" for o, n, _ in TIER_NAMES])
def test_tier_name_bench_serve_uses(tiered, obj, name, kind):
    target = tiered.obj("tier")
    if obj:
        target = getattr(target, obj)
    assert hasattr(target, name), f"tier.{obj}.{name} is gone"
    if kind == "callable":
        assert callable(getattr(target, name))


def test_promote_is_what_the_droppromote_control_replaces(tiered):
    """bench/serve.py:99-106 swaps `TierManager._promote(self, fps, t0)`
    for one that pops and drops: the signature, what it may hand
    `pop_rows`, `_pending.difference_update` under `_cv`."""
    import inspect

    import numpy as np

    from gubernator_tpu.runtime.coldtier import TierManager

    assert list(inspect.signature(TierManager._promote).parameters) \
        == ["self", "fps", "t0"]
    tier = tiered.obj("tier")
    fps = np.array([123456789], dtype=np.int64)
    assert len(tier.cold.pop_rows(fps)["key_hash"]) == 0
    with tier._cv:
        tier._pending.difference_update(fps)


def test_the_tier_programs_warm_as_bench_serve_warms_them(tiered):
    """bench/serve.py:197-236 `warm_tier_programs`, call for call: no
    row leaves the table, the demote kernel takes (table, protect, now,
    ways=, batch=) and gives back three."""
    import inspect

    import numpy as np

    from gubernator_tpu.ops.state import demote_extract, demote_extract_impl
    from gubernator_tpu.runtime.backend import fetch_ravel

    params = inspect.signature(demote_extract_impl).parameters
    assert list(params)[:3] == ["table", "protect", "now"]
    assert {"ways", "batch"} <= set(params)
    service = tiered.daemon.service
    backend, tier = service.backend, service.tier
    before = backend.occupancy_dispatch()()
    idle = {f: np.zeros(1, dtype=np.int64) for f in (
        "key_hash", "algo", "limit", "duration", "remaining",
        "remaining_f", "t0", "status", "burst", "expire_at")}
    assert backend.migrate_inject_dispatch(idle)() == (0, 0)
    grid = np.asarray(tier._protect_grid(), dtype=np.int64)
    never = np.int64(np.iinfo(np.int64).max - 1)
    with backend._lock:
        backend.table, packed, rf = demote_extract(
            backend.table, grid, never, ways=backend.cfg.ways,
            batch=int(tier.cfg.demote_batch),
        )
    assert int((fetch_ravel([packed])[0] != 0).sum()) == 0
    assert len(fetch_ravel([rf])[0]) == tier.cfg.demote_batch
    assert backend.occupancy_dispatch()() <= before   # a tick may run


@pytest.mark.parametrize("name,attr", [
    ("tier_inject_device_ms.closed", "migrate_inject"),
    ("tier_inject_roofline_share.closed", "migrate_inject"),
])
def test_tier_program_name_matches_the_traced_metrics_regex(name, attr):
    from gubernator_tpu.ops import state

    read = json.loads(
        (BENCH / "layer_metrics" / f"{name}.json").read_text())["read"]
    fn = getattr(state, attr)
    # XLA names a jitted program "jit_" + the function's name.
    assert re.match(read["program_regex"], "jit_" + fn.__wrapped__.__name__)


@pytest.mark.parametrize("name", [
    "tier_demote_tick_ms.closed", "tier_demote_rows_per_tick.closed",
])
def test_a_demote_metric_reads_the_daemons_life_not_the_window(name):
    """The table crosses its high mark once in about 3 s, and a traced
    run's per-layer window ends 3 s early: a metric of the demoter that
    took the window's difference would be missing from some result lines,
    which the driver refuses.  (So is one that reads the demote program
    from the profiler's 2 s span: there is none.)"""
    read = json.loads(
        (BENCH / "layer_metrics" / f"{name}.json").read_text())["read"]
    assert read["kind"] == "ratio" and not read.get("delta"), read
    traced = [
        f.stem for f in (BENCH / "layer_metrics").glob("tier_*.json")
        if "demote" in json.loads(f.read_text())["read"].get(
            "program_regex", "")
    ]
    assert traced == [], traced


TIER_METRICS = sorted(
    f.stem for f in (BENCH / "layer_metrics").glob("tier_*.json"))


def test_the_tier_metrics_were_found():
    assert len(TIER_METRICS) == 10, TIER_METRICS


@pytest.mark.parametrize("name", TIER_METRICS)
def test_tier_metric_terms_read_a_live_tiered_daemon(tiered, name):
    """Every `vars:` term of a tier_* data file, wherever in its `read`
    block, is a number on a daemon whose tier has promoted and demoted;
    a ratio's denominator is above 0 there."""
    read = json.loads(
        (BENCH / "layer_metrics" / f"{name}.json").read_text())["read"]
    terms = []

    def walk(node):
        if isinstance(node, str) and node.startswith("vars:"):
            terms.append(node)
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    walk(read)
    if read["kind"] == "ratio":
        assert terms
    for term in terms:
        assert "vars:stages." not in term        # spelled `*`
        nodes = _resolve(tiered.vars, term[len("vars:"):])
        assert nodes and all(_is_number(n) for n in nodes), (term, nodes)
    for term in read.get("den", []) + read.get("launches", []):
        assert sum(_resolve(tiered.vars, term[len("vars:"):])) > 0, term


# -- (e) why a stage took that long (PR 41) -----------------------------------
#
# The fifteen metrics over lane `host` and the `threads` / `process`
# blocks: every term of every data file finds numbers
# on a live daemon, and the divisor has grown from zero.

HOST_METRICS = sorted(
    f.stem for f in (BENCH / "layer_metrics").glob("*.json")
    if f.stem.rsplit(".", 1)[0] in (
        "lane_oncpu_share", "host_python_cores",
        "host_process_cores", "host_gc_share", "host_planes_share",
        "host_census_ms", "wire_loop_lag_ms", "host_stall_share",
    )
)


@pytest.mark.parametrize("daemon", ["one_chip", "mesh"])
def test_lanes_per_launch_reads_the_dispatch_rows_alone(request, daemon):
    """backend_lanes_per_launch.closed (PR 44): its two terms, the
    `stages` level spelled `*`, find the ledger's backend.dispatch rows
    and nothing else of /debug/vars; every launch the seam's RPCs made
    rode the 128 rung, x shards on the mesh, and the rungs are counted."""
    seam = request.getfixturevalue(daemon)
    read = json.loads((
        BENCH / "layer_metrics" / "backend_lanes_per_launch.closed.json"
    ).read_text())["read"]
    assert read["kind"] == "ratio" and read["delta"] is True
    rows = [lane["dispatch"] for lane in seam.vars["stages"].values()
            if "dispatch" in lane]
    total = {}
    for side, counter in (("num", "lanes"), ("den", "launches")):
        (term,) = read[side]
        nodes = _resolve(seam.vars, term[len("vars:"):])
        assert sorted(nodes) == sorted(
            r[counter] for r in rows if counter in r), (term, nodes)
        total[side] = sum(nodes)
    shards = seam.daemon.service.backend.cfg.num_shards
    assert total["den"] > 0
    assert total["num"] == 128 * shards * total["den"], total
    mach = seam.vars["stages"]["mach"]["dispatch"]
    assert mach["tier_128"] == mach["launches"] > 0, mach


def test_the_host_metrics_were_found():
    assert len(HOST_METRICS) == 15, HOST_METRICS


@pytest.mark.parametrize("name", HOST_METRICS)
def test_host_metric_reads_a_live_daemon(one_chip, name):
    read = json.loads(
        (BENCH / "layer_metrics" / f"{name}.json").read_text())["read"]
    assert read["kind"] == "ratio" and read["delta"] is True
    total = {}
    for side in ("num", "den"):
        total[side] = 0.0
        for term in read[side]:
            assert "vars:stages." not in term    # spelled `*`: PERF.md s.7
            nodes = _resolve(one_chip.vars, term[len("vars:"):])
            assert nodes and all(_is_number(n) for n in nodes), (term, nodes)
            total[side] += sum(nodes)
    assert total["den"] > 0 and total["num"] >= 0, (name, total)
    base = name.rsplit(".", 1)[0]
    if base == "lane_oncpu_share":
        # the pools' CPU and nobody else's; within the sections' wall but
        # for the clock's grain and what the pools do between sections
        assert read["num"] == ["vars:threads.tpu-fastlane.*.cpu_ms"]
        assert 0 < total["num"] <= 1.5 * total["den"] + 10, total
    if base in ("host_python_cores", "host_process_cores",
                "host_planes_share", "host_census_ms"):
        assert total["num"] > 0, (name, total)


def test_the_routed_rpcs_handler_closes_with_the_hop_named(routed):
    """peer_attributed_share.closed's terms on a live entry daemon."""
    st = routed.vars["stages"]
    handler = st["wire"]["handler"]["ms_total"]
    parts = sum(st["wire"][s]["ms_total"] for s in (
        "ingress", "wake", "egress", "peer_wait"
    )) + st["mach"]["queue_wait"]["ms_total"] + st["mach"]["in_drain"][
        "ms_total"]
    assert st["wire"]["peer_wait"]["count"] > 0
    assert 0.90 * handler <= parts <= 1.001 * handler, (parts, handler)
    hop = st["peer"]["forward"]
    assert hop["count"] > 0 and hop["checks"] >= hop["count"]
    assert [hop[k] for k in ("timeouts", "reasked", "joined", "retried",
                             "refused")] == [0] * 5


@pytest.mark.parametrize("path", [
    "stages.peer.batch_wait.count", "stages.peer.batch_wait.ms_total",
    "stages.peer.forward.batched", "stages.peer.forward.flush_limit",
    "stages.peer.forward.flush_wait",
])
def test_the_peer_batchers_span_and_tallies(routed, path):
    """PR 43: `peer.batch_wait` (a client RPC's forward, enqueue -> its
    GetPeerRateLimits is sent) and why a batch went, where
    peer_batch_wait_ms.closed and peer_checks_per_forward.closed (and a
    reader of the ledger) look for them."""
    nodes = _resolve(routed.vars, path)
    assert len(nodes) == 1 and _is_number(nodes[0]), (path, nodes)
    hop = routed.vars["stages"]["peer"]
    # Every GetPeerRateLimits went for one of the two reasons, and carried
    # at least one client RPC's forward.
    assert hop["forward"]["count"] == (
        hop["forward"]["flush_wait"] + hop["forward"]["flush_limit"]) > 0
    assert hop["batch_wait"]["count"] >= hop["forward"]["count"]
    assert hop["forward"]["batched"] <= hop["batch_wait"]["count"]
    assert hop["batch_wait"]["ms_total"] > 0


def test_the_ready_reports_names_on_a_daemon_of_a_cluster(routed):
    """bench/serve.py's ready report, bench/run.py `check_chips`: the
    device block's four names, the warm-up time, and the devices'
    `id` / `coords` that `chip_report` reads."""
    import jax

    info = routed.obj("backend").device_info()    # bench/serve.py:274
    assert set(info) >= {"platform", "device_kind", "device_count",
                         "table_device_ids"}      # bench/run.py:135-165
    assert len(set(info["table_device_ids"])) == 1    # bench/run.py:149
    assert routed.daemon._warmup_s > 0            # bench/serve.py:276
    devs = [d for d in jax.devices() if d.id in info["table_device_ids"]]
    assert devs and all(isinstance(d.id, int) for d in devs)  # serve.py:222


def _cluster_configs():
    """The configuration files of BENCHMARK.json that state `peers`."""
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    files = [BENCH.parent / c["file"] for c in bm["configs"]]
    return sorted(f.stem for f in files
                  if json.loads(f.read_text()).get("peers", 1) > 1)


def test_the_cluster_configurations_were_found():
    assert _cluster_configs() == ["peers4-10m", "peers4-10m-batched"]


@pytest.mark.parametrize("config", _cluster_configs())
def test_a_daemon_of_a_cluster_starts_from_the_environment(
        monkeypatch, config):
    """bench/run.py `server_env`: a configuration's `daemon` group plus
    the daemon's own addresses; `--control noforward` rewrites GUBER_PEERS
    to the advertise address alone (bench/serve.py:87).  The group is read
    from the configuration's file: every setting it states is one the
    daemon reads, in the spelling it states (`peers4-10m-batched` states
    the peer batcher's window and limit; `peers4-10m` runs the same two
    as the program's defaults)."""
    from gubernator_tpu.core.config import setup_daemon_config

    daemon = json.loads(
        (BENCH / "configs" / f"{config}.json").read_text())["daemon"]
    peers = "127.0.0.1:21051,127.0.0.1:21052,127.0.0.1:21053,127.0.0.1:21054"
    assert daemon["GUBER_PEERS"] == peers
    for k in ("GUBER_BATCH_WAIT", "GUBER_BATCH_LIMIT"):
        monkeypatch.delenv(k, raising=False)
        assert (k in daemon) == (config == "peers4-10m-batched")
    for k, v in {
        **daemon,
        "GUBER_GRPC_ADDRESS": "127.0.0.1:21052",
        "GUBER_HTTP_ADDRESS": "127.0.0.1:21999",
        "GUBER_ADVERTISE_ADDRESS": "127.0.0.1:21052",
    }.items():
        monkeypatch.setenv(k, v)
    conf = setup_daemon_config(None)
    assert conf.static_peers == peers.split(",")
    assert conf.advertise_address == "127.0.0.1:21052"
    assert conf.local_picker_hash == "xx"
    assert conf.behaviors.batch_timeout_s == 0.5
    assert conf.behaviors.batch_wait_s == 500e-6
    assert conf.behaviors.batch_limit == 1000
    assert (conf.device.num_slots, conf.device.ways,
            conf.device.batch_size) == (1 << 22, 8, 4096)
