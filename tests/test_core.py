"""Core-layer tests: types, clock, Gregorian intervals, config, hashing."""
import json
import os
import re
from datetime import datetime, timezone
from fnmatch import fnmatchcase
from pathlib import Path

import pytest

from gubernator_tpu.core import clock as clock_mod
from gubernator_tpu.core import config as config_mod
from gubernator_tpu.core.config import (
    RETIRED_ENV,
    BehaviorConfig,
    DeviceConfig,
    parse_duration_s,
    setup_daemon_config,
)
from gubernator_tpu.core.hashing import bulk_key_hash64, fnv1_64, fnv1a_64, key_hash64
from gubernator_tpu.core.interval import (
    GREGORIAN_DAYS,
    GREGORIAN_HOURS,
    GREGORIAN_MINUTES,
    GREGORIAN_MONTHS,
    GREGORIAN_WEEKS,
    GREGORIAN_YEARS,
    GregorianError,
    gregorian_duration,
    gregorian_expiration,
)
from gubernator_tpu.core.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    has_behavior,
)


def test_hash_key():
    r = RateLimitReq(name="test_over_limit", unique_key="acct:1234")
    assert r.hash_key() == "test_over_limit_acct:1234"


def test_behavior_flags():
    b = Behavior.GLOBAL | Behavior.RESET_REMAINING
    assert has_behavior(b, Behavior.GLOBAL)
    assert has_behavior(b, Behavior.RESET_REMAINING)
    assert not has_behavior(b, Behavior.NO_BATCHING)
    # BATCHING is the zero value: has_behavior always False (gubernator.go:786)
    assert not has_behavior(b, Behavior.BATCHING)


def test_clock_freeze_advance():
    clk = clock_mod.Clock()
    clk.freeze()
    t0 = clk.millisecond_now()
    clk.advance(1500)
    assert clk.millisecond_now() == t0 + 1500
    clk.unfreeze()
    assert not clk.frozen


# Mirrors interval_test.go:66-137 expectations.
@pytest.mark.parametrize(
    "d,now,expect",
    [
        (
            GREGORIAN_MINUTES,
            datetime(2019, 1, 1, 11, 20, 10, tzinfo=timezone.utc),
            datetime(2019, 1, 1, 11, 20, 59, 999000, tzinfo=timezone.utc),
        ),
        (
            GREGORIAN_HOURS,
            datetime(2019, 1, 1, 11, 20, 10, tzinfo=timezone.utc),
            datetime(2019, 1, 1, 11, 59, 59, 999000, tzinfo=timezone.utc),
        ),
        (
            GREGORIAN_DAYS,
            datetime(2019, 1, 1, 11, 20, 10, tzinfo=timezone.utc),
            datetime(2019, 1, 1, 23, 59, 59, 999000, tzinfo=timezone.utc),
        ),
        (
            GREGORIAN_MONTHS,
            datetime(2019, 1, 15, 11, 20, 10, tzinfo=timezone.utc),
            datetime(2019, 1, 31, 23, 59, 59, 999000, tzinfo=timezone.utc),
        ),
        (
            GREGORIAN_YEARS,
            datetime(2019, 6, 15, 11, 20, 10, tzinfo=timezone.utc),
            datetime(2019, 12, 31, 23, 59, 59, 999000, tzinfo=timezone.utc),
        ),
    ],
)
def test_gregorian_expiration(d, now, expect):
    got = gregorian_expiration(now, d)
    assert got == int(expect.timestamp() * 1000)


def test_gregorian_invalid():
    now = datetime(2019, 1, 1, tzinfo=timezone.utc)
    with pytest.raises(GregorianError):
        gregorian_expiration(now, 99)
    with pytest.raises(GregorianError):
        gregorian_expiration(now, GREGORIAN_WEEKS)
    with pytest.raises(GregorianError):
        gregorian_duration(now, GREGORIAN_WEEKS)


def test_gregorian_duration_values():
    now = datetime(2019, 2, 10, tzinfo=timezone.utc)
    assert gregorian_duration(now, GREGORIAN_MINUTES) == 60_000
    assert gregorian_duration(now, GREGORIAN_HOURS) == 3_600_000
    assert gregorian_duration(now, GREGORIAN_DAYS) == 86_400_000
    assert gregorian_duration(now, GREGORIAN_MONTHS) == 28 * 86_400_000
    assert gregorian_duration(now, GREGORIAN_YEARS) == 365 * 86_400_000


def test_parse_duration():
    assert parse_duration_s("500us") == pytest.approx(500e-6)
    assert parse_duration_s("500ms") == pytest.approx(0.5)
    assert parse_duration_s("2s") == pytest.approx(2.0)
    assert parse_duration_s("0.25") == pytest.approx(0.25)


def test_env_config(monkeypatch):
    monkeypatch.setenv("GUBER_GRPC_ADDRESS", "0.0.0.0:9990")
    monkeypatch.setenv("GUBER_BATCH_LIMIT", "250")
    monkeypatch.setenv("GUBER_BATCH_WAIT", "250us")
    monkeypatch.setenv("GUBER_PEERS", "a:1051, b:1051")
    cfg = setup_daemon_config()
    assert cfg.grpc_listen_address == "0.0.0.0:9990"
    assert cfg.behaviors.batch_limit == 250
    assert cfg.behaviors.batch_wait_s == pytest.approx(250e-6)
    assert cfg.static_peers == ["a:1051", "b:1051"]
    assert cfg.peer_discovery_type == "static"


# The settings that selected a drain discipline, and the three that
# sized the one that is left (constants of FastPath since PR 50), are
# gone; a daemon must refuse them by name, not ignore them.  The ring
# family is matched by its prefix.
_RING = "GUBER_RING"


@pytest.mark.parametrize("name,value", [
    ("GUBER_SERVE_MODE", "ring"),
    ("GUBER_SERVE_MODE", "megaround"),
    ("GUBER_SERVE_MODE", "persistent"),
    ("GUBER_SERVE_MODE", "classic"),
    (_RING + "_SLOTS", "8"),
    (_RING + "_ROUNDS", "4"),
    (_RING + "_MAX_LINGER_US", "200"),
    ("GUBER_FASTPATH_INFLIGHT", "1"),
    ("GUBER_FASTPATH_SPARSE", "64"),
    ("GUBER_PIPELINE_DEPTH", "2"),
])
def test_removed_drain_settings_are_refused(monkeypatch, name, value):
    monkeypatch.setenv(name, value)
    with pytest.raises(ValueError, match=name):
        setup_daemon_config()


def test_the_one_drain_discipline_is_accepted_by_name(monkeypatch):
    monkeypatch.setenv("GUBER_SERVE_MODE", "pipelined")
    conf = setup_daemon_config()
    # What sizes that discipline is not the configuration's to say.
    assert not {"fastpath_inflight", "fastpath_sparse",
                "pipeline_depth"} & set(vars(conf))


_BENCH_CONFIGS = sorted(
    (Path(__file__).resolve().parents[1] / "bench" / "configs").glob("*.json")
)


@pytest.mark.parametrize("path", _BENCH_CONFIGS, ids=lambda p: p.stem)
def test_a_benchmark_configuration_sets_only_what_a_daemon_reads(path):
    """A cell cannot set what the daemon ignores (a name core/config.py
    never reads) or refuses (a retired one): every GUBER_* name in a
    configuration's `daemon` block is read by core/config.py."""
    read = set(re.findall(
        r"GUBER_[A-Z0-9_]+", Path(config_mod.__file__).read_text()
    ))
    settings = json.loads(path.read_text())["daemon"]
    assert settings, path
    for name in settings:
        assert not any(fnmatchcase(name, r) for r, _ in RETIRED_ENV), name
        assert name in read, f"{path.name} sets {name}, which nothing reads"


def test_device_config_validation():
    with pytest.raises(ValueError):
        DeviceConfig(num_slots=100, ways=8)


def test_hashing():
    # FNV test vectors (same constants as segmentio/fasthash).
    assert fnv1a_64(b"") == 0xCBF29CE484222325
    assert fnv1a_64(b"a") == 0xAF63DC4C8601EC8C
    assert fnv1_64(b"a") == 0xAF63BD4C8601B7BE
    assert key_hash64("foo_bar") != 0
    hs = bulk_key_hash64(["a_1", "a_2", "a_1"])
    assert hs[0] == hs[2] != hs[1]


def test_sketch_tier_env_config(monkeypatch):
    """GUBER_SKETCH_* env vars build the approximate tier
    (setup_daemon_config) — deployments aren't limited to programmatic
    config."""
    from gubernator_tpu.core.config import setup_daemon_config

    for v in ("NAMES", "DEPTH", "WIDTH", "WINDOW", "BATCH_SIZE",
              "USE_PALLAS"):
        monkeypatch.delenv(f"GUBER_SKETCH_{v}", raising=False)
    monkeypatch.setenv("GUBER_SKETCH_NAMES", "per_ip, abuse")
    monkeypatch.setenv("GUBER_SKETCH_WIDTH", "65536")
    monkeypatch.setenv("GUBER_SKETCH_WINDOW", "30s")
    conf = setup_daemon_config()
    assert conf.sketch is not None
    assert conf.sketch.names == ["per_ip", "abuse"]
    assert conf.sketch.width == 65536
    assert conf.sketch.window_ms == 30_000
    assert conf.sketch.depth == 4

    monkeypatch.delenv("GUBER_SKETCH_NAMES")
    assert setup_daemon_config().sketch is None


def test_sketch_tier_env_rejects_zero_window(monkeypatch):
    import pytest as _pytest

    from gubernator_tpu.core.config import setup_daemon_config

    monkeypatch.setenv("GUBER_SKETCH_NAMES", "per_ip")
    monkeypatch.setenv("GUBER_SKETCH_WINDOW", "500us")
    with _pytest.raises(ValueError, match="GUBER_SKETCH_WINDOW"):
        setup_daemon_config()


def test_tls_client_auth_env_aliases_and_validation(monkeypatch):
    from gubernator_tpu.core.config import (
        normalize_tls_client_auth,
        setup_daemon_config,
    )

    # Reference spellings (config.go:351-354) canonicalize.
    assert normalize_tls_client_auth("request-cert") == "request"
    assert normalize_tls_client_auth("verify-cert") == "verify-if-given"
    assert normalize_tls_client_auth("require-any-cert") == "require-any"
    # Canonical + legacy spellings pass through; case-insensitive.
    assert normalize_tls_client_auth("Require") == "require"
    assert normalize_tls_client_auth("") == ""

    monkeypatch.setenv("GUBER_TLS_CERT", "/tmp/server.pem")
    monkeypatch.setenv("GUBER_TLS_CLIENT_AUTH", "require-any-cert")
    conf = setup_daemon_config()
    assert conf.tls is not None
    assert conf.tls.client_auth == "require-any"

    # A typo'd mode must fail loudly, never silently disable client auth.
    monkeypatch.setenv("GUBER_TLS_CLIENT_AUTH", "requre")
    with pytest.raises(ValueError, match="client-auth"):
        setup_daemon_config()
