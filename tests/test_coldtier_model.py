"""The cold store against a plain dict (docs/tiering.md, "The cold
store"): random interleavings of put / pop / member / prune / rebuild /
restore, tombstones and a full table included — same residents, same
rows, same `capacity_drops`.  The model is this file's: a dict from
fingerprint to row, a put on a resident key merging as
`migrate_inject` merges (the least budget), never overwriting.  The
probe has two forms, one native pass (`native.cold_probe`) and the
numpy one kept as its reference: every case runs under both, and they
are held to each other slot for slot."""
from __future__ import annotations

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.runtime.coldtier import (
    COLD_FIELDS, _DTYPES, ColdTier, merge_cold,
)

LIMIT = 1000


def _rows(rng, fps):
    n = len(fps)
    algo = rng.integers(0, 2, n).astype(np.int32)
    rem = rng.integers(0, LIMIT + 1, n)
    return {
        "key_hash": np.asarray(fps, dtype=np.int64),
        "algo": algo,
        "limit": np.full(n, LIMIT, dtype=np.int64),
        "duration": np.full(n, 60_000, dtype=np.int64),
        "remaining": rem.astype(np.int64),
        "remaining_f": rng.integers(0, LIMIT + 1, n) + rng.random(n),
        "t0": rng.integers(1, 1 << 40, n),
        "status": rng.integers(0, 2, n).astype(np.int32),
        "burst": np.full(n, LIMIT, dtype=np.int64),
        "expire_at": rng.integers(1_000, 2_000, n),
    }


class DictModel:
    """What ColdTier promises, one key at a time."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self.rows: dict = {}
        self.capacity_drops = 0
        self.cold_merges = 0

    def put(self, cols) -> int:
        put = 0
        for j, fp in enumerate(cols["key_hash"].tolist()):
            if fp == 0:
                continue
            row = {f: _DTYPES[f].type(cols[f][j]) for f in COLD_FIELDS}
            old = self.rows.get(fp)
            if old is not None:
                # migrate_inject's algebra, the waiting row the older.
                leaky = old["algo"] == 1
                used_i = max(int(old["limit"]) - int(old["remaining"]), 0)
                used_f = max(float(old["limit"]) - float(old["remaining_f"]),
                             0.0)
                row["remaining"] = np.int64(max(
                    int(row["remaining"]) - (0 if leaky else used_i), 0))
                row["remaining_f"] = np.float64(max(
                    float(row["remaining_f"]) - (used_f if leaky else 0.0),
                    0.0))
                self.cold_merges += 1
            elif len(self.rows) >= self.capacity:
                self.capacity_drops += 1
                continue
            self.rows[fp] = row
            put += 1
        return put

    def pop(self, fps):
        out = []
        for fp in fps.tolist():
            row = self.rows.pop(fp, None)
            if row is not None:
                out.append(row)
        return out

    def prune(self, now_ms: int) -> int:
        dead = [fp for fp, r in self.rows.items()
                if r["expire_at"] <= now_ms]
        for fp in dead:
            del self.rows[fp]
        return len(dead)


def _same_rows(got, want_rows):
    assert len(got["key_hash"]) == len(want_rows)
    for j, want in enumerate(want_rows):
        for f in COLD_FIELDS:
            assert got[f][j] == want[f], (f, j)


def _same_store(ct: ColdTier, model: DictModel):
    assert ct.residents() == len(model.rows)
    assert ct.capacity_drops == model.capacity_drops
    assert ct.cold_merges == model.cold_merges
    snap = ct.snapshot()
    order = np.argsort(snap["key_hash"])
    _same_rows({f: snap[f][order] for f in COLD_FIELDS},
               [model.rows[fp] for fp in sorted(model.rows)])


@pytest.mark.parametrize("native_probe", [True, False],
                         ids=["native", "numpy"])
@pytest.mark.parametrize("seed, capacity, universe", [
    (1, 48, 96),        # a store that fills: drops counted
    (2, 200, 260),      # long chains, tombstones, rebuilds
    (3, 3000, 2500),    # room to spare
    (4, 64, 64),        # every key resident at once
])
def test_random_interleavings_agree_with_the_dict(seed, capacity, universe,
                                                  native_probe):
    if native_probe and not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(seed)
    ct, model = ColdTier(capacity), DictModel(capacity)
    ct.native = native_probe
    # Fingerprints that collide on their home slot (the low bits), some
    # negative (the table fingerprints are signed), and 0 as padding.
    pool = np.concatenate([
        rng.integers(1, 1 << 62, universe // 2) << 1,
        -rng.integers(1, 1 << 62, universe // 4),
        np.arange(1, universe // 4 + 1) * (ct._mask + 1) + 5,
    ]).astype(np.int64)
    rebuilds = 0
    for step in range(300):
        op = rng.integers(0, 10)
        k = int(rng.integers(1, max(2, universe // 3)))
        fps = rng.choice(pool, k)          # duplicates inside a batch too
        if op < 4:
            fps[rng.random(k) < 0.05] = 0
            cols = _rows(rng, fps)
            assert ct.put_rows(cols) == model.put(cols)
        elif op < 7:
            _same_rows(ct.pop_rows(fps), model.pop(fps))
        elif op == 7:
            want = np.array([fp in model.rows for fp in fps.tolist()])
            assert (ct.member_hits(fps) == want).all()
        elif op == 8:
            now = int(rng.integers(900, 1_300))
            assert ct.prune_expired(now) == model.prune(now)
        else:
            with ct._lock:
                ct._rebuild()
            rebuilds += 1
        if step % 20 == 0:
            _same_store(ct, model)
            if native_probe:
                # The two forms of the probe, slot for slot.
                ask = np.concatenate([pool, [0]])
                assert (ct._probe(ask) == ct._probe_numpy(ask)).all()
            assert ct._tombstones == int((ct._state == 2).sum())
            assert ct._tombstones <= ct._cap // 4
    _same_store(ct, model)
    assert rebuilds
    # Every resident is reachable, nothing else is.
    assert ct.member_hits(pool).sum() == len(
        set(pool.tolist()) & set(model.rows))
    # A restore into a store of another size is the same rows again.
    ct2, model2 = ColdTier(capacity * 2 + 7), DictModel(capacity * 2 + 7)
    snap = ct.snapshot()
    assert ct2.restore(snap) == model2.put(snap)
    _same_store(ct2, model2)


def test_a_full_table_of_tombstones_still_finds_and_inserts():
    """Tombstones up to the rebuild's mark on every chain: a probe
    passes through them, an insert reuses the first."""
    ct = ColdTier(64)                      # 128 slots
    rng = np.random.default_rng(9)
    fps = (np.arange(1, 65) * 128 + 3).astype(np.int64)   # one home slot
    assert ct.put_rows(_rows(rng, fps)) == 64
    assert len(ct.pop_rows(fps[:30])["key_hash"]) == 30
    assert ct._tombstones == 30            # under the mark: no rebuild
    assert ct.member_hits(fps).tolist() == [False] * 30 + [True] * 34
    assert ct.put_rows(_rows(rng, fps[:10])) == 10
    assert ct._tombstones == 20            # the chain's first ten reused
    assert ct.member_hits(fps[:10]).all() and ct.residents() == 44


def test_merge_cold_is_migrate_injects_algebra():
    """Token rows merge on `remaining`, leaky rows on `remaining_f`,
    the kept row is the newer one, and nothing is minted."""
    rng = np.random.default_rng(5)
    new, old = _rows(rng, np.arange(1, 9)), _rows(rng, np.arange(1, 9))
    new["algo"] = old["algo"] = np.array([0, 1] * 4, dtype=np.int32)
    got = merge_cold(new, old)
    tok = old["algo"] == 0
    used = LIMIT - old["remaining"]
    assert (got["remaining"][tok] == np.maximum(
        new["remaining"][tok] - used[tok], 0)).all()
    assert (got["remaining"][~tok] == new["remaining"][~tok]).all()
    used_f = LIMIT - old["remaining_f"]
    assert (got["remaining_f"][~tok] == np.maximum(
        new["remaining_f"][~tok] - used_f[~tok], 0.0)).all()
    assert (got["remaining_f"][tok] == new["remaining_f"][tok]).all()
    assert (got["remaining"] <= new["remaining"]).all()
    assert (got["remaining_f"] <= new["remaining_f"]).all()
    for f in ("status", "t0", "expire_at", "key_hash"):
        assert (got[f] == new[f]).all()
