"""One general traffic generator: a plan of RPCs made from a traffic file, a
configuration's universe group and the seed, before the first RPC leaves.

The arithmetic follows gubernator_tpu/loadgen/schedule.py (seeded plan,
sha512-derived sub-seeds, latency timed from the intended send) with one
change the benchmark's contract asks for: every seed gets the SAME set of
inter-arrival gaps and RPC sizes, in another order, so that the seed moves
which key is hit when and not how much work a run holds.

  arrivals  "poisson": the gaps of one block are the n mid-quantiles of the
            exponential distribution, scaled to mean 1/rate, permuted by
            the seed; every block of n arrivals therefore spans exactly
            n/rate seconds.
  sizes     checks per RPC: every whole number from min to max once per
            block, permuted by the seed.
  keys      "uniform" over the universe's plain keys; on a configuration
            with GLOBAL keys, `global_per_rpc` checks of every RPC go to
            GLOBAL keys instead (a fixed count, so that the bound on what a
            drain can merge holds by construction and not by luck).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

from lib.universe import derive_seed

GAP_BLOCK = 1000


def _block_permuted(values: np.ndarray, n: int, rng) -> np.ndarray:
    """`n` values: whole permutations of `values`, one after another."""
    blocks = -(-n // len(values))
    out = np.concatenate([rng.permutation(values) for _ in range(blocks)])
    return out[:n]


def poisson_times(seed: int, rate: float, duration_s: float) -> np.ndarray:
    """Intended send offsets in [0, duration_s), in seconds."""
    if rate <= 0 or duration_s <= 0:
        raise ValueError(f"rate and duration must be > 0: {rate}, {duration_s}")
    q = (np.arange(GAP_BLOCK) + 0.5) / GAP_BLOCK
    gaps = -np.log1p(-q)
    gaps *= 1.0 / (gaps.mean() * rate)
    n = int(rate * duration_s) + GAP_BLOCK
    rng = np.random.default_rng(derive_seed(seed, "poisson/times"))
    t = np.cumsum(_block_permuted(gaps, n, rng))
    return t[t < duration_s]


def rpc_sizes(seed: int, lo: int, hi: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(derive_seed(seed, "sizes"))
    return _block_permuted(np.arange(lo, hi + 1, dtype=np.int64), n, rng)


@dataclass(frozen=True)
class Plan:
    """RPC j holds checks [offsets[j], offsets[j+1]) of `key_index`
    (positions in the universe).  `times_s` is None for a closed loop."""

    offsets: np.ndarray
    key_index: np.ndarray
    times_s: np.ndarray

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def digest(self) -> str:
        """Content digest (ns-quantized times, sizes, keys): the same
        seed gives the same hex in every process."""
        h = hashlib.sha256()
        if self.times_s is not None:
            h.update(np.round(self.times_s * 1e9).astype(np.int64).tobytes())
        h.update(self.offsets.astype(np.int64).tobytes())
        h.update(self.key_index.astype(np.int64).tobytes())
        return h.hexdigest()


def build_plan(traffic: dict, universe: dict, seed: int,
               span_s: float) -> Plan:
    """The plan for `span_s` seconds of this traffic (warm-in + window)."""
    lo = int(traffic["checks_per_rpc"]["min"])
    hi = int(traffic["checks_per_rpc"]["max"])
    times = None
    if traffic["loop"] == "open":
        arr = traffic["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        times = poisson_times(seed, float(arr["rate_rpc_per_s"]), span_s)
        n = len(times)
    elif traffic["loop"] == "closed":
        # A pool the loop cycles through; sized for twice the rate the
        # cell was measured at, so a run does not wrap.
        n = int(float(traffic["pool_rpc_per_s"]) * span_s) + 1
    else:
        raise ValueError(f"unknown loop kind {traffic['loop']!r}")
    sizes = rpc_sizes(seed, lo, hi, n)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(sizes, out=offsets[1:])
    total = int(offsets[-1])
    if traffic["keys"]["distribution"] != "uniform":
        raise ValueError(
            f"unknown key distribution {traffic['keys']['distribution']!r}"
        )
    n_keys = int(universe["keys"])
    n_global = int(universe.get("global_keys", 0))
    rng = np.random.default_rng(derive_seed(seed, "uniform/keys"))
    key_index = rng.integers(n_global, n_keys, size=total, dtype=np.int64)
    g = int(traffic.get("global_per_rpc", 0)) if n_global else 0
    if g:
        if g > lo:
            raise ValueError("global_per_rpc exceeds the smallest RPC")
        grng = np.random.default_rng(derive_seed(seed, "global/keys"))
        where = (offsets[:-1, None] + np.arange(g)[None, :]).ravel()
        key_index[where] = grng.integers(0, n_global, size=len(where))
    return Plan(offsets=offsets, key_index=key_index, times_s=times)
