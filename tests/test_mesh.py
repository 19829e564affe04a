"""Mesh-sharded engine tests on a virtual 8-device CPU mesh.

The TPU analog of the reference's in-process multi-daemon cluster
(functional_test.go:42-62, cluster/cluster.go): 8 virtual devices stand in
for an 8-chip pod slice; the differential test proves that sharding the
table over the mesh changes nothing about decisions.
"""
import random

import pytest

from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.core.hashing import key_hash64
from gubernator_tpu.core.pymodel import PyRateLimiter
from gubernator_tpu.core.types import Algorithm, RateLimitReq, Status
from gubernator_tpu.parallel.mesh import shard_of_hash
from gubernator_tpu.parallel.sharded import MeshBackend, pack_requests_sharded
from tests.test_differential import _random_req


def _mesh_backend(frozen_clock, **kw):
    cfg = DeviceConfig(
        num_slots=kw.pop("num_slots", 8 * 2048),
        ways=8,
        batch_size=kw.pop("batch_size", 64),
        num_shards=8,
    )
    return MeshBackend(cfg, clock=frozen_clock)


def test_shard_routing_disjoint_bits():
    """Shard index uses hash bits disjoint from the bucket index."""
    seen = set()
    for i in range(4096):
        h = key_hash64(f"route:{i}")
        seen.add(int(shard_of_hash(h, 8)))
    assert seen == set(range(8))  # all shards reachable


def test_pack_sharded_positions_and_rounds(frozen_clock):
    reqs = [
        RateLimitReq(name="t", unique_key=f"k{i % 5}", hits=1, limit=100,
                     duration=10_000)
        for i in range(15)
    ]
    packed = pack_requests_sharded(reqs, 8, 8, frozen_clock)
    # 5 distinct keys x 3 occurrences -> 3 rounds, each key once per round.
    assert len(packed.rounds) == 3
    seen_rounds = {}
    for i, (rnd, shard, lane) in enumerate(packed.positions):
        key = reqs[i].unique_key
        assert rnd == seen_rounds.get(key, -1) + 1  # occurrences in order
        seen_rounds[key] = rnd
        assert shard == int(shard_of_hash(key_hash64(reqs[i].hash_key()), 8))


@pytest.mark.parametrize("seed", [11, 12])
def test_mesh_differential_vs_oracle(seed, frozen_clock):
    rng = random.Random(seed)
    oracle = PyRateLimiter(clock=frozen_clock)
    dev = _mesh_backend(frozen_clock)

    for step in range(25):
        batch = [_random_req(rng, 40) for _ in range(rng.randrange(1, 48))]
        got_all = dev.check(batch)
        for i, req in enumerate(batch):
            want = oracle.get_rate_limit(req)
            got = got_all[i]
            ctx = f"step={step} i={i} req={req}"
            assert got.status == want.status, ctx
            assert got.remaining == want.remaining, ctx
            assert got.limit == want.limit, ctx
            assert got.reset_time == want.reset_time, ctx
        frozen_clock.advance(rng.choice([0, 1, 500, 3_000, 61_000]))


def test_mesh_sequential_consistency(frozen_clock):
    """Same key hammered through the mesh: counts down exactly."""
    dev = _mesh_backend(frozen_clock)
    for expect in (99, 98, 97):
        (resp,) = dev.check(
            [RateLimitReq(name="seq", unique_key="one", hits=1, limit=100,
                          duration=60_000)]
        )
        assert resp.status == Status.UNDER_LIMIT
        assert resp.remaining == expect


def test_mesh_point_read(frozen_clock):
    dev = _mesh_backend(frozen_clock)
    dev.check(
        [RateLimitReq(name="pr", unique_key="x", hits=3, limit=10,
                      duration=60_000, algorithm=Algorithm.TOKEN_BUCKET)]
    )
    item = dev.get_cache_item("pr_x")
    assert item is not None
    assert item.remaining == 7
    assert dev.get_cache_item("pr_missing") is None


def test_mesh_shard_occupancy(frozen_clock):
    """Per-shard occupancy sums to the aggregate and reflects routed
    inserts (the skew view /debug/vars + gubernator_shard_occupancy
    export)."""
    be = _mesh_backend(frozen_clock, num_slots=8 * 8 * 64)
    be.check([
        RateLimitReq(
            name="mocc", unique_key=f"k{i % 13}", hits=1 + (i % 2),
            limit=40, duration=60_000,
            algorithm=(
                Algorithm.LEAKY_BUCKET if i % 3 == 0
                else Algorithm.TOKEN_BUCKET
            ),
        )
        for i in range(40)
    ])
    per = be.shard_occupancy()
    assert len(per) == 8
    assert sum(per) == be.occupancy() > 0


def test_mesh_ways_env_knob(monkeypatch):
    """GUBER_MESH_WAYS drives the mesh axis size (overriding the
    GUBER_TPU_NUM_SHARDS alias) and invalid geometries are rejected AT
    STARTUP with the env surface named — not deep inside MeshBackend
    construction."""
    from gubernator_tpu.core.config import (
        mesh_ways_from_env,
        setup_daemon_config,
    )

    assert mesh_ways_from_env() == 0  # unset defers to the alias
    monkeypatch.setenv("GUBER_TPU_NUM_SLOTS", str(8 * 8 * 64))
    monkeypatch.setenv("GUBER_TPU_NUM_SHARDS", "2")
    monkeypatch.setenv("GUBER_MESH_WAYS", "8")
    conf = setup_daemon_config()
    assert conf.device.num_shards == 8  # MESH_WAYS wins over the alias
    monkeypatch.setenv("GUBER_MESH_WAYS", "0")
    with pytest.raises(ValueError, match="GUBER_MESH_WAYS"):
        setup_daemon_config()
    # Slots not divisible by ways*mesh_ways: startup rejection that
    # names the geometry env surface.
    monkeypatch.setenv("GUBER_MESH_WAYS", "7")
    with pytest.raises(ValueError, match="GUBER_MESH_WAYS"):
        setup_daemon_config()
    monkeypatch.delenv("GUBER_MESH_WAYS")
    monkeypatch.setenv("GUBER_TPU_NUM_SHARDS", "0")
    with pytest.raises(ValueError, match="GUBER_TPU_NUM_SHARDS"):
        setup_daemon_config()
