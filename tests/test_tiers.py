"""Pin the compiled batch-shape tier contract (runtime/backend.py).

A drain's round rides the smallest compiled shape that holds its active
lanes — the device transfer scales with traffic, not with the configured
max batch — and a full round must NEVER be truncated (batch_size is
always a tier).  These are the invariants the small-shape latency path
rests on.
"""
import numpy as np

from gubernator_tpu.core.config import DeviceConfig
from gubernator_tpu.runtime.backend import DeviceBackend, resolve_tiers, tier_of


def test_resolve_tiers_always_includes_batch_size():
    cfg = DeviceConfig(num_slots=1 << 10, batch_size=4096)
    assert resolve_tiers(cfg) == (128, 4096)

    cfg = DeviceConfig(
        num_slots=1 << 10, batch_size=4096, batch_tiers=(256, 1024)
    )
    assert resolve_tiers(cfg) == (256, 1024, 4096)


def test_resolve_tiers_clamps_and_dedupes():
    # A tier above batch_size clamps to it; duplicates collapse; order
    # is ascending regardless of the configured order.
    cfg = DeviceConfig(
        num_slots=1 << 10, batch_size=2048,
        batch_tiers=(8192, 512, 512, 2048),
    )
    assert resolve_tiers(cfg) == (512, 2048)


def test_tier_of_picks_smallest_holding_tier():
    tiers = (128, 1024, 4096)
    act = np.zeros(4096, dtype=bool)
    act[:5] = True
    assert tier_of(act, tiers) == 128
    act[:128] = True
    assert tier_of(act, tiers) == 128  # boundary: occ == tier fits
    act[:129] = True
    assert tier_of(act, tiers) == 1024
    act[:] = True
    assert tier_of(act, tiers) == 4096


def test_tier_of_sharded_uses_max_per_shard():
    # [n_shards, B]: lanes fill contiguously from 0 per shard, so the
    # busiest shard's count picks the tier for the whole round.
    tiers = (128, 4096)
    act = np.zeros((4, 4096), dtype=bool)
    act[0, :3] = True
    act[2, :200] = True
    assert tier_of(act, tiers) == 4096  # busiest shard (200) > 128
    act[2, :] = False
    act[2, :100] = True
    assert tier_of(act, tiers) == 128  # busiest shard now fits


def test_small_round_rides_small_tier_with_exact_responses():
    """End-to-end through DeviceBackend.check: a 3-request batch on a
    4096-lane config must produce exact token-bucket decrements (the
    small tier serves it — and the response unmarshal must address the
    sliced shape correctly)."""
    from gubernator_tpu.core.types import RateLimitReq

    be = DeviceBackend(
        DeviceConfig(num_slots=1 << 12, ways=4, batch_size=4096)
    )
    reqs = [
        RateLimitReq(name="t", unique_key=f"k{i}", hits=1, limit=10,
                     duration=60_000)
        for i in range(3)
    ]
    for expect_remaining in (9, 8, 7):
        for r in be.check(reqs):
            assert r.error == ""
            assert r.remaining == expect_remaining
