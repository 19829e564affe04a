"""The LX telemetry plane: Summary->Histogram migration, SLO breach
detection, the flight recorder, and catalog/doc parity.

Covers the ISSUE-2 acceptance criteria:
- /metrics exposes histogram buckets for all six migrated timings, and a
  p99 estimate computed FROM the buckets agrees with the exact
  percentile (numpy) within one bucket width on synthetic latencies;
- an induced SLO breach in the in-process cluster fixture produces a
  flight-recorder JSON dump and increments slo_breach_total (raceguard
  stays armed for the whole session, so the run also proves the recorder
  introduces no lock-order inversion);
- every collector in docs/prometheus.md exists on Metrics and vice
  versa, and the exposition parses;
- sketch_backend.spillovers (the metric mirror) agrees with the
  Prometheus counter after a driven spillover.
"""
from __future__ import annotations

import asyncio
import json
import re
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from gubernator_tpu.runtime.flightrec import FlightRecorder
from gubernator_tpu.runtime.metrics import (
    LATENCY_BUCKETS,
    Metrics,
    estimate_quantile,
)

REPO = Path(__file__).resolve().parents[1]

MIGRATED = (
    "gubernator_grpc_request_duration",
    "gubernator_func_duration",
    "gubernator_tpu_device_step_duration",
    "gubernator_batch_send_duration",
    "gubernator_async_durations",
    "gubernator_broadcast_durations",
)


def _observe_all(m: Metrics, values) -> None:
    for v in values:
        m.grpc_request_duration.labels(method="/t/M").observe(v)
        m.func_duration.labels(name="f").observe(v)
        m.device_step_duration.observe(v)
        m.batch_send_duration.labels(peerAddr="p:1").observe(v)
        m.async_durations.observe(v)
        m.broadcast_durations.observe(v)


def test_migrated_timings_expose_buckets():
    m = Metrics()
    _observe_all(m, [0.0003, 0.0015, 0.012])
    text = m.render().decode()
    for name in MIGRATED:
        assert f"{name}_bucket" in text, name
        # The 2ms SLO target is an exact bucket boundary for every one.
        assert f'{name}_bucket{{' in text
        assert re.search(
            rf'{name}_bucket{{[^}}]*le="0\.002"', text
        ), f"{name} lacks the 2ms bucket"
        # _count/_sum survive the migration (the eventual-consistency
        # assertions poll *_count exactly like the reference tests).
        assert f"{name}_count" in text or f"{name}_count{{" in text


def test_exposition_parses():
    from prometheus_client.parser import text_string_to_metric_families

    m = Metrics()
    _observe_all(m, [0.001])
    m.note_check_error("Invalid request")
    families = list(
        text_string_to_metric_families(m.render().decode())
    )
    assert len(families) > 20


def _bucket_counts(m: Metrics, name: str):
    """Cumulative (le-ordered) bucket counts for an unlabeled-or-single-
    child histogram family, +Inf last."""
    for mf in m.registry.collect():
        if mf.name != name:
            continue
        pairs = []
        for s in mf.samples:
            if s.name == f"{name}_bucket":
                le = s.labels["le"]
                pairs.append((float("inf") if le == "+Inf" else float(le),
                              int(s.value)))
        pairs.sort()
        return [c for _, c in pairs]
    raise AssertionError(f"no histogram family {name}")


def test_bucket_p99_matches_the_exact_percentile():
    """Acceptance: p99 estimated from scrape-side buckets agrees with the
    samples' exact percentile within one bucket width, on synthetic
    latencies spanning the µs->ms serving regime."""
    rng = np.random.default_rng(42)
    # Lognormal around ~1ms with a tail into tens of ms — the shape the
    # latency configs actually produce.
    lat_s = rng.lognormal(mean=np.log(1e-3), sigma=0.9, size=5000)

    m = Metrics()
    h = m.grpc_request_duration.labels(method="/t/M")
    for v in lat_s:
        h.observe(v)

    counts = _bucket_counts(m, "gubernator_grpc_request_duration")
    est_p99_ms = estimate_quantile(LATENCY_BUCKETS, counts, 0.99) * 1e3

    exact_p99_ms = float(np.percentile(lat_s * 1e3, 99))

    # One bucket width at the bucket the exact p99 lands in.
    bounds = [0.0] + [b * 1e3 for b in LATENCY_BUCKETS]
    hi = next(
        (b for b in bounds[1:] if exact_p99_ms <= b), bounds[-1]
    )
    lo = bounds[max(0, bounds.index(hi) - 1)]
    width = hi - lo
    assert abs(est_p99_ms - exact_p99_ms) <= width, (
        f"bucket p99 {est_p99_ms:.3f}ms vs exact {exact_p99_ms:.3f}ms, "
        f"bucket width {width:.3f}ms"
    )


def _workloads():
    return [w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text()
    )["workloads"]]


@pytest.mark.parametrize("cell", _workloads())
def test_readme_says_where_every_cell_is_measured(cell):
    """README.md's table of what is measured where names every cell
    BENCHMARK.json declares (and no figure: the ledger has those)."""
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    table = readme[readme.index("| Metric | Where it is measured"):]
    table = table[:table.index("\n\n")]
    assert f"`{cell}`" in table, f"{cell} has no row in README.md's table"


def test_nothing_names_a_harness_or_a_setting_that_is_gone():
    """One yardstick (PR 50): outside the records of what was done, no
    file names a harness, a gate or a record that left the tree, or a
    setting a daemon refuses.  The names are spelled in pieces so that
    this file does not name them either."""
    import subprocess

    from gubernator_tpu.core.config import RETIRED_ENV

    gone = [
        "bench" + "_e2e", "bench" + "_gate", "micro" + "bench",
        "BENCH" + "_E2E", "MULTICHIP" + "_r0", "ADVICE" + r"\.md",
        "CHANGE" + r"LOG\.md", r"(?<![\w/.-])bench" + r"\.py",
        "fastpath_sparse" + "_from_env", "pipeline_depth" + "_from_env",
    ] + [
        name for name, _ in RETIRED_ENV if not name.endswith("*")
    ]
    pattern = re.compile("|".join(gone))
    # The records of what was done, the benchmark (a `benchmark` issue's
    # to reword), and the check with its test, which must name them.
    records = {
        "CHANGES.md", "ROADMAP.md", "PERF.md", "SURVEY.md", "ISSUE.md",
        "REVIEW.md", "BENCHMARK.json", "PERF_LEDGER.jsonl",
        "gubernator_tpu/core/config.py", "tests/test_core.py",
    }
    try:
        tracked = subprocess.run(
            ["git", "ls-files", "-z"], cwd=REPO, check=True,
            capture_output=True, text=True,
        ).stdout.split("\0")
    except (OSError, subprocess.CalledProcessError):
        # A checkout without its repository: every file under it, but
        # what a run leaves behind (.gitignore's directories).
        left = {
            line.strip().rstrip("/")
            for line in (REPO / ".gitignore").read_text().splitlines()
            if line.strip().endswith("/")
        } | {".git"}
        tracked = [
            p.relative_to(REPO).as_posix() for p in REPO.rglob("*")
            if p.is_file() and not left & set(p.relative_to(REPO).parts)
        ]
    named = []
    for rel in filter(None, tracked):
        if rel in records or rel.startswith("bench/"):
            continue
        path = REPO / rel
        if not path.is_file():
            continue  # deleted in the working tree, not yet committed
        try:
            text = path.read_text(encoding="utf-8")
        except UnicodeDecodeError:
            continue
        named += [
            f"{rel}:{n}: {m.group(0)}"
            for n, line in enumerate(text.splitlines(), 1)
            for m in [pattern.search(line)] if m
        ]
    assert not named, "\n".join(named)


def test_metrics_catalog_parity():
    """docs/prometheus.md is machine-checked against the Metrics bundle:
    every documented collector exists and every collector is documented
    (doc drift fails, both directions)."""
    doc = (REPO / "docs" / "prometheus.md").read_text(encoding="utf-8")
    documented = set(re.findall(r"\|\s*`(gubernator_[a-z0-9_]+)`", doc))
    assert documented, "no catalog rows parsed from docs/prometheus.md"

    m = Metrics()
    families = {mf.name for mf in m.registry.collect()}

    def doc_matches_family(doc_name: str) -> bool:
        # prometheus_client strips a trailing _total from Counter names:
        # Counter("x_total") -> family "x", samples "x_total".
        return (
            doc_name in families
            or doc_name.removesuffix("_total") in families
        )

    missing = {d for d in documented if not doc_matches_family(d)}
    assert not missing, f"documented but not on Metrics: {sorted(missing)}"

    def family_documented(fam: str) -> bool:
        return fam in documented or f"{fam}_total" in documented

    undocumented = {f for f in families if not family_documented(f)}
    assert not undocumented, (
        f"on Metrics but missing from docs/prometheus.md: "
        f"{sorted(undocumented)}"
    )


def test_sketch_spillover_mirror_matches_counter():
    """The `spillovers` host mirror and gubernator_sketch_spillover_count
    move in lockstep through the Service wiring (on_spill), including
    operator-initiated spill_name calls."""
    from gubernator_tpu import native

    if not native.available():
        pytest.skip("native library unavailable (spill_name hashes names)")
    from gubernator_tpu.core.config import (
        Config,
        DeviceConfig,
        SketchTierConfig,
    )
    from gubernator_tpu.runtime.service import Service

    cfg = Config(
        device=DeviceConfig(num_slots=1024, ways=8, batch_size=64),
        sketch=SketchTierConfig(width=1024, spill_inserts=100),
    )
    svc = Service(cfg)
    sb = svc.sketch_backend
    assert sb is not None

    def counter_value() -> float:
        return svc.metrics.registry.get_sample_value(
            "gubernator_sketch_spillover_count_total"
        ) or 0.0

    assert sb.spillovers == 0 == counter_value()
    assert sb.spill_name("abuse_by_ip") is True
    assert sb.spillovers == 1 == counter_value()
    # Idempotent spill: neither side moves.
    assert sb.spill_name("abuse_by_ip") is False
    assert sb.spillovers == 1 == counter_value()
    assert sb.spill_name("abuse_by_asn") is True
    assert sb.spillovers == 2 == counter_value()


# ---------------------------------------------------------------------------
# flight recorder unit behavior
# ---------------------------------------------------------------------------

def test_flightrec_ring_is_bounded_and_snapshots():
    fr = FlightRecorder(ring_size=8)
    for i in range(50):
        fr.record_batch(i, 0.5, over_limit=1)
    snap = fr.snapshot()
    assert len(snap["ring"]) == 8
    assert snap["ring"][-1]["size"] == 49
    assert snap["ring"][0]["size"] == 42
    limited = fr.snapshot(limit=3)
    assert len(limited["ring"]) == 3
    json.dumps(snap)  # the payload must be JSON-serializable


def test_flightrec_breach_detection_and_gauges():
    m = Metrics()
    fr = FlightRecorder(metrics=m, slo_p99_ms=2.0, min_samples=10)
    m.flightrec = fr
    # Under target: no breach.
    for _ in range(30):
        fr.observe_request(0.0005)
    assert fr.evaluate() is None
    assert fr.breaches == 0
    # Push the tail over 2ms.
    for _ in range(30):
        fr.observe_request(0.050)
    reason = fr.evaluate()
    assert reason == "slo_breach"
    assert fr.breaches == 1
    assert m.registry.get_sample_value(
        "gubernator_slo_breach_total"
    ) == 1.0
    assert m.registry.get_sample_value(
        "gubernator_slo_p99_seconds"
    ) == pytest.approx(0.050, rel=0.2)
    # Cooldown: the breach still counts but no second dump fires.
    fr._last_dump_mono = time.monotonic()
    assert fr.evaluate() is None
    assert fr.breaches == 2


def test_flightrec_error_storm_triggers():
    fr = FlightRecorder(error_storm=5, min_samples=10_000)
    fr.note_error(5)
    assert fr.evaluate() == "error_storm"


def test_flightrec_dump_writes_json(tmp_path):
    m = Metrics()
    fr = FlightRecorder(metrics=m, dump_dir=str(tmp_path))
    fr.record_batch(128, 1.25, over_limit=3, errors=1)
    fr.record("peer_error", peer="p:1", error="boom")

    async def go():
        return await fr.dump("signal")

    path = asyncio.run(go())
    data = json.loads(Path(path).read_text())
    assert data["reason"] == "signal"
    assert data["dumps"] == 1
    kinds = [r["kind"] for r in data["ring"]]
    assert "device_step" in kinds and "peer_error" in kinds
    # The dump itself lands in the ring (black-box audit trail).
    assert fr.snapshot()["ring"][-1]["kind"] == "dump"
    assert m.registry.get_sample_value(
        "gubernator_flightrec_dump_total",
        {"reason": "signal"},
    ) == 1.0


def test_flightrec_cli_renders_dump(tmp_path, capsys):
    from gubernator_tpu.cli import flightrec as cli

    fr = FlightRecorder(dump_dir=str(tmp_path))
    fr.record_batch(64, 0.8)

    async def go():
        await fr.dump("signal")
        # A second dump so directory expansion has something to sort.
        fr._last_dump_mono = -1e9
        await fr.dump("signal")

    asyncio.run(go())
    rc = cli.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert out.count("== ") == 2
    assert "reason=signal" in out
    assert "device_step" in out


def test_flightrec_lag_sampler_runs_and_sets_gauge():
    """The recorder's sampler evaluates the SLO and times nothing; the
    lag gauge and the recorder's lag readings are fed by the daemon's
    heartbeat, the stage ledger's host.loop_lag (tests/test_stages.py
    holds the views to the row)."""
    from gubernator_tpu.runtime import tracing

    m = Metrics()
    fr = m.flightrec = FlightRecorder(
        metrics=m, sample_interval_s=0.02, min_samples=10_000
    )

    async def go():
        fr.start()
        await asyncio.sleep(0.1)
        assert fr.max_lag_ms == 0.0          # no heartbeat, no reading
        beat = asyncio.ensure_future(m.stages.heartbeat())
        await asyncio.sleep(tracing.LOOP_LAG_INTERVAL_S * 1.6)
        beat.cancel()
        await asyncio.gather(beat, return_exceptions=True)
        await fr.close()

    asyncio.run(go())
    n, _ns, mx = m.stages.totals("host", "host.loop_lag")
    assert n >= 1
    assert m.registry.get_sample_value(
        "gubernator_event_loop_lag_seconds"
    ) == pytest.approx(fr.last_lag_ms / 1e3)
    assert fr.max_lag_ms == pytest.approx(mx / 1e6)


# ---------------------------------------------------------------------------
# induced SLO breach in the in-process cluster (acceptance)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def slo_cluster(tmp_path_factory):
    from gubernator_tpu.core.config import DaemonConfig
    from gubernator_tpu.testing.cluster import Cluster

    dump_dir = tmp_path_factory.mktemp("flightrec")
    c = Cluster.start(1, conf_template=DaemonConfig(
        flightrec=True,
        flightrec_dir=str(dump_dir),
        flightrec_ring=256,
        # Any real request latency breaches a 1µs target — the induced
        # breach of the acceptance criterion, deterministic on any host.
        slo_p99_ms=0.001,
    ))
    # Shorten the recorder's windows for test cadence.
    fr = c.daemons[0].flightrec
    fr.min_samples = 10
    fr.cooldown_s = 0.0
    try:
        yield c, dump_dir
    finally:
        c.stop()


def _induce_breach(c, d) -> None:
    """Drive enough gRPC traffic through the daemon that the recorder's
    rolling window fills and its 1µs p99 target breaches, then wait out
    a sampler tick."""
    from gubernator_tpu.client import V1Client
    from gubernator_tpu.core.types import RateLimitReq

    cl = V1Client(d.grpc_address)
    try:
        for i in range(30):
            cl.get_rate_limits([RateLimitReq(
                name="slo_breach", unique_key=f"k{i}", hits=1,
                limit=1000, duration=60_000,
            )])
    finally:
        cl.close()
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and d.flightrec.breaches == 0:
        time.sleep(0.1)


def test_slo_breach_dumps_and_counts(slo_cluster):
    c, dump_dir = slo_cluster
    d = c.daemons[0]
    _induce_breach(c, d)

    deadline = time.monotonic() + 15.0
    dumps = []
    while time.monotonic() < deadline:
        dumps = list(dump_dir.glob("flightrec-*.json"))
        if dumps and d.flightrec.breaches > 0:
            break
        time.sleep(0.1)
    assert d.flightrec.breaches > 0, "no SLO breach detected"
    assert dumps, "breach produced no flight-recorder dump"
    assert d.metrics.registry.get_sample_value(
        "gubernator_slo_breach_total"
    ) >= 1.0

    data = json.loads(dumps[0].read_text())
    assert data["reason"] in ("slo_breach", "error_storm")
    assert data["rolling"]["samples"] >= 10
    kinds = {r["kind"] for r in data["ring"]}
    assert kinds & {"device_step", "fastlane_drain"}, kinds
    # The stage ledger's stalls ring rides every dump, on the epoch clock
    # of the ring's own `ts` (docs/flightrec.md).
    assert isinstance(data["stalls"], list)
    for r in data["stalls"]:
        assert r["t_start_ms"] <= r["t_end_ms"] <= data["now"] * 1e3 + 1
        assert r["ms"] >= 20


def test_slo_breach_surfaces_in_healthcheck(slo_cluster):
    c, _ = slo_cluster
    d = c.daemons[0]
    if d.flightrec.breaches == 0:
        _induce_breach(c, d)
    h = c.run(d.service.health_check())
    assert "SLO:" in h.message
    # Peer connectivity still drives the status field.
    assert h.status == "healthy"


def test_debug_endpoints_serve_snapshots(slo_cluster):
    c, _ = slo_cluster
    d = c.daemons[0]
    if d.flightrec.breaches == 0:
        _induce_breach(c, d)

    with urllib.request.urlopen(
        f"http://{d.http_address}/debug/flightrec?limit=5", timeout=10
    ) as resp:
        snap = json.loads(resp.read())
    assert snap["enabled"] is True
    assert len(snap["ring"]) <= 5
    assert snap["breaches"] >= 1

    with urllib.request.urlopen(
        f"http://{d.http_address}/debug/vars", timeout=10
    ) as resp:
        vars_ = json.loads(resp.read())
    assert vars_["backend"]["checks"] >= 30
    assert vars_["flightrec"]["breaches"] >= 1

    with urllib.request.urlopen(
        f"http://{d.http_address}/metrics", timeout=10
    ) as resp:
        text = resp.read().decode()
    assert 'gubernator_grpc_request_duration_bucket{le="0.002"' in text
    assert "gubernator_slo_p99_seconds" in text
    assert "gubernator_event_loop_lag_seconds" in text


def test_debug_flightrec_404_when_disarmed():
    """A daemon without the recorder answers /debug/flightrec with 404 +
    a hint instead of crashing (checked through the HTTP handler
    directly to avoid booting a second cluster)."""
    from gubernator_tpu.daemon import Daemon

    d = Daemon.__new__(Daemon)
    d.flightrec = None

    class _Req:
        query = {}

    async def go():
        return await Daemon._http_flightrec(d, _Req())

    resp = asyncio.run(go())
    assert resp.status == 404


def test_k8s_discovery_env_plumbing(monkeypatch):
    """GUBER_K8S_* flows env -> DaemonConfig (the VERDICT round-5 L6
    plumbing gap); the daemon hands the values to K8sPool."""
    from gubernator_tpu.core.config import setup_daemon_config

    monkeypatch.setenv("GUBER_K8S_NAMESPACE", "limits")
    monkeypatch.setenv("GUBER_K8S_ENDPOINTS_SELECTOR", "app=guber")
    monkeypatch.setenv("GUBER_K8S_POD_IP", "10.0.0.7")
    monkeypatch.setenv("GUBER_K8S_POD_PORT", "1051")
    monkeypatch.setenv("GUBER_K8S_WATCH_MECHANISM", "pods")
    conf = setup_daemon_config()
    assert conf.k8s_namespace == "limits"
    assert conf.k8s_endpoints_selector == "app=guber"
    assert conf.k8s_pod_ip == "10.0.0.7"
    assert conf.k8s_pod_port == 1051
    assert conf.k8s_watch_mechanism == "pods"
    # And the operator can discover them.
    conf_text = (REPO / "deploy" / "example.conf").read_text()
    for var in (
        "GUBER_K8S_NAMESPACE", "GUBER_K8S_ENDPOINTS_SELECTOR",
        "GUBER_K8S_POD_IP", "GUBER_K8S_POD_PORT",
        "GUBER_K8S_WATCH_MECHANISM",
    ):
        assert var in conf_text, var


def test_flightrec_env_plumbing(monkeypatch):
    from gubernator_tpu.core.config import setup_daemon_config

    monkeypatch.setenv("GUBER_FLIGHTREC", "1")
    monkeypatch.setenv("GUBER_FLIGHTREC_DIR", "/tmp/fr")
    monkeypatch.setenv("GUBER_FLIGHTREC_RING", "64")
    monkeypatch.setenv("GUBER_SLO_P99_MS", "5.5")
    monkeypatch.setenv("GUBER_FLIGHTREC_PROFILE", "2s")
    conf = setup_daemon_config()
    assert conf.flightrec is True
    assert conf.flightrec_dir == "/tmp/fr"
    assert conf.flightrec_ring == 64
    assert conf.slo_p99_ms == 5.5
    assert conf.flightrec_profile_s == 2.0
