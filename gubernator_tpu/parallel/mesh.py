"""Device-mesh construction and key->shard routing.

The reference shards its key space twice: across worker goroutines inside a
node (workers.go:127-186, 63-bit xxhash ranges) and across peers with a
consistent hash ring (replicated_hash.go:29-118).  On TPU the intra-pod
analog of both is ONE mesh axis: the slot table is sharded along its slot
dimension over the `shard` axis, and a request's 64-bit key fingerprint
selects the owning shard.

Routing uses hash bits 32.. (disjoint from the bucket-index bits, which come
from the LOW bits — ops/step.py bucket = h & (nb_local-1)), so the same
fingerprint drives both levels without correlation.  Shard routing happens on
host, so any shard count works (modulo); only the per-shard bucket count must
stay a power of two for the device-side mask.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from gubernator_tpu.ops.devices import platform_devices

SHARD_AXIS = "shard"
_SHARD_SHIFT = 32


def make_mesh(
    num_shards: int,
    devices: Optional[Sequence[jax.Device]] = None,
    platform: Optional[str] = None,
) -> Mesh:
    """1-D mesh over the first `num_shards` devices (of `platform` unless
    `devices` is given), axis name "shard".

    The rate-limit table is pure data-parallel over the key space, so one
    axis is the natural topology (the reference's peer ring is also 1-D).
    """
    devs = (
        list(devices) if devices is not None
        else platform_devices(platform)
    )
    if len(devs) < num_shards:
        raise ValueError(
            f"need {num_shards} devices, have {len(devs)}"
        )
    return Mesh(np.asarray(devs[:num_shards]), (SHARD_AXIS,))


def shard_of_hash(h, num_shards: int):
    """Owning shard for a 64-bit key fingerprint (works on np or jnp arrays).

    Replaces the worker-pool hash-range interpolation (workers.go:182-186) and
    intra-pod consistent-hash lookup (replicated_hash.go:104-118) with a mask
    over high hash bits.
    """
    u = np.uint64(h) if np.isscalar(h) else h.astype(np.uint64)
    return (u >> np.uint64(_SHARD_SHIFT)) % np.uint64(num_shards)
