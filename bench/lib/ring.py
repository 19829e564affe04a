"""The owner of a key on a cluster's consistent-hash ring.

The vnode derivation of net/replicated_hash.py (upstream
replicated_hash.go:29-119), copied, not imported, so that a later change
to the program cannot move the yardstick: a peer is its advertise address;
its REPLICAS points are hash(str(i) + md5hex(address)) for i in
0..REPLICAS-1; a key belongs to the peer of the first point at or after the
key's hash, wrapping past the last point to the first.  Of two points with
the same hash the one added first stands first (the program merges with a
stable sort); peers are added in the order the configuration lists them.

The key's hash is the ring function over the bytes of its hash key,
"<name>_<unique_key>".  On an "xx" ring that is XXH64, which is the table
fingerprint itself (the program's router reads the owner straight from the
parsed fingerprint column); "fnv1" and "fnv1a" are upstream's, for mixed
clusters.  Nothing here imports JAX or the program.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Sequence

import numpy as np
import xxhash

REPLICAS = 512                  # upstream's, and the program has no setting
HASHES = ("xx", "fnv1", "fnv1a")
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def hash_rows(kind: str, rows: np.ndarray) -> np.ndarray:
    """uint64 ring hashes of byte strings of one length, uint8[n, length]."""
    if kind == "xx":
        return np.fromiter(
            (xxhash.xxh64_intdigest(r.tobytes()) for r in rows),
            dtype=np.uint64, count=len(rows),
        )
    if kind not in HASHES:
        raise ValueError(f"ring hash {kind!r} is none of {HASHES}")
    h = np.full(len(rows), _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for col in rows.T.astype(np.uint64):
            if kind == "fnv1":
                h *= _FNV_PRIME
                h ^= col
            else:
                h ^= col
                h *= _FNV_PRIME
    return h


def hash_strings(kind: str, strings: Sequence[str]) -> np.ndarray:
    """uint64 ring hashes of strings of any lengths."""
    return np.array([
        hash_rows(kind, np.frombuffer(s.encode(), dtype=np.uint8)[None, :])[0]
        for s in strings
    ], dtype=np.uint64)


@dataclass
class Ring:
    addresses: tuple        # the peers' advertise addresses, in file order
    hash: str               # "xx" | "fnv1" | "fnv1a"
    points: np.ndarray      # uint64[n * replicas], sorted
    peer: np.ndarray        # int32[n * replicas]: the peer of each point

    @property
    def n(self) -> int:
        return len(self.addresses)

    def owner(self, ring_hash: np.ndarray) -> np.ndarray:
        """int32 peer index of uint64 ring hashes."""
        at = np.searchsorted(self.points, ring_hash, side="left")
        at[at == len(self.points)] = 0
        return self.peer[at]


def build(addresses: Sequence[str], kind: str) -> Ring:
    points = np.empty(len(addresses) * REPLICAS, dtype=np.uint64)
    peer = np.repeat(np.arange(len(addresses), dtype=np.int32), REPLICAS)
    for p, addr in enumerate(addresses):
        digest = hashlib.md5(addr.encode()).hexdigest()
        points[p * REPLICAS:(p + 1) * REPLICAS] = hash_strings(
            kind, [str(i) + digest for i in range(REPLICAS)]
        )
    order = np.argsort(points, kind="stable")
    return Ring(tuple(addresses), kind, points[order], peer[order])
