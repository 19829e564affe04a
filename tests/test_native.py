"""Native host runtime tests: the loader's build rule, XXH64 parity,
packer differential.

Nothing here skips when the library is missing: the toolchain is part of
the installation, the daemon's compiled lane is the path under test, and
a library that did not build is a failure the suite must show."""
from __future__ import annotations

import json
import random
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import xxhash

from gubernator_tpu import native
from gubernator_tpu.core.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.ops.batch import (
    _pack_requests_grid_native,
    _pack_requests_grid_py,
)

REPO = Path(__file__).resolve().parent.parent


def test_library_is_built_from_the_source_beside_it():
    native.require()
    assert native.load_error() == ""
    assert native._stamped_hash() == native.source_hash()


_LOAD_COPY = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("natcopy", sys.argv[1])
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
ok = m.available()
print(json.dumps({
    "available": ok, "rebuilt": m.rebuilt(), "error": m.load_error(),
    "stamp_matches": m._stamped_hash() == m.source_hash(),
    "hash": (m.hash_keys(["a"]).tolist() if ok else None),
}))
"""


def test_loader_rebuilds_on_source_mismatch_and_reports_failure(tmp_path):
    """The build stamps the source's SHA-256; the loader rebuilds when the
    stamp and native/gubtpu.cpp disagree (mtimes say nothing after a
    copy), and a build that fails is a named error, not a quiet
    python-lane fallback."""
    pkg = tmp_path / "gubernator_tpu" / "native"
    pkg.mkdir(parents=True)
    shutil.copy(REPO / "gubernator_tpu/native/__init__.py", pkg)
    shutil.copytree(REPO / "native", tmp_path / "native")
    src = tmp_path / "native" / "gubtpu.cpp"

    def load() -> dict:
        out = subprocess.run(
            [sys.executable, "-c", _LOAD_COPY, str(pkg / "__init__.py")],
            capture_output=True, text=True, timeout=180,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    built = load()  # no library yet: built from the source beside it
    assert built["available"] and built["rebuilt"]
    assert built["stamp_matches"]
    again = load()  # stamp matches: verified, not rebuilt
    assert again["available"] and not again["rebuilt"]

    src.write_text(src.read_text() + "\n// edited\n")
    edited = load()  # same mtime-order or not: the hash moved
    assert edited["available"] and edited["rebuilt"]
    assert edited["stamp_matches"] and edited["hash"] == built["hash"]

    src.write_text("this is not C++\n")
    broken = load()
    assert not broken["available"]
    assert "native build failed" in broken["error"]


def test_xxh64_parity():
    rng = random.Random(0)
    keys = [
        "".join(
            rng.choices("abcdefghijklmnop_0123456789:", k=rng.randint(0, 200))
        )
        for _ in range(2000)
    ]
    got = native.hash_keys(keys)
    want = np.array(
        [xxhash.xxh64_intdigest(k) or 1 for k in keys], dtype=np.uint64
    ).view(np.int64)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("variant", ["fnv1", "fnv1a"])
def test_fnv_hashkey_batch_parity(variant):
    """gub_fnv_hashkey_batch must equal the python fnv of each parsed
    request's hash key (name + '_' + unique_key), with 0 on errored
    lanes — the interop-ring route hashes (replicated_hash.go:33)."""
    from gubernator_tpu.core.hashing import fnv1_64, fnv1a_64
    from gubernator_tpu.proto import gubernator_pb2 as pb

    fn = fnv1_64 if variant == "fnv1" else fnv1a_64
    rng = random.Random(3)
    reqs = []
    for i in range(500):
        name = rng.choice(["a", "rate_limit", "x" * 40, ""])
        key = rng.choice([f"k{i}", "idé:ütf8", "", "y" * 120])
        reqs.append(pb.RateLimitReq(
            name=name, unique_key=key, hits=1, limit=10, duration=1000,
        ))
    payload = pb.GetRateLimitsReq(requests=reqs).SerializeToString()
    cols = native.parse_reqs(payload)
    assert cols is not None and cols.n == len(reqs)
    got = native.fnv_hashkey_batch(payload, cols, variant)
    want = np.array(
        [
            fn((r.name + "_" + r.unique_key).encode())
            if r.name and r.unique_key else 0
            for r in reqs
        ],
        dtype=np.uint64,
    ).view(np.int64)
    np.testing.assert_array_equal(got, want)


def _random_reqs(rng, n):
    reqs = []
    for i in range(n):
        bad = rng.random() < 0.05
        behavior = Behavior.BATCHING
        duration = rng.randint(1000, 60_000)
        p = rng.random()
        if p < 0.1:
            behavior = Behavior.RESET_REMAINING
        elif p < 0.2:
            # Gregorian lanes, including invalid interval ids (errors must
            # not claim rounds/lanes in either packer).
            behavior = Behavior.DURATION_IS_GREGORIAN
            duration = rng.choice([0, 1, 2, 4, 99])
        reqs.append(
            RateLimitReq(
                name="" if bad else f"n{rng.randint(0, 5)}",
                unique_key=f"k{rng.randint(0, n // 2)}",
                hits=rng.randint(0, 5),
                limit=rng.randint(1, 100),
                duration=duration,
                algorithm=rng.choice(list(Algorithm)),
                behavior=behavior,
                burst=rng.choice([0, 50]),
            )
        )
    return reqs


@pytest.mark.parametrize("n_shards", [1, 4])
def test_packer_differential(n_shards):
    """Native and python packers must produce identical grids."""
    rng = random.Random(42)
    reqs = _random_reqs(rng, 500)

    def shard_fn(key: str) -> int:
        return hash(key) % n_shards

    a = _pack_requests_grid_native(reqs, 64, n_shards, shard_fn)
    b = _pack_requests_grid_py(reqs, 64, n_shards, shard_fn)
    assert a.errors == b.errors
    assert a.positions == b.positions
    assert len(a.rounds) == len(b.rounds)
    for ra, rb in zip(a.rounds, b.rounds):
        for f in ra._fields:
            np.testing.assert_array_equal(
                getattr(ra, f), getattr(rb, f), err_msg=f
            )


def test_packer_duplicate_rounds():
    """Same key N times -> N sequential rounds, native path."""
    reqs = [
        RateLimitReq(name="d", unique_key="x", hits=1, limit=10,
                     duration=1000)
        for _ in range(5)
    ]
    g = _pack_requests_grid_native(reqs, 16, 1, lambda k: 0)
    assert [p[0] for p in g.positions] == [0, 1, 2, 3, 4]
    assert len(g.rounds) == 5
