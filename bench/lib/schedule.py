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
            "square": a period of `period_s` seconds holds one burst of
            `burst_s` seconds starting `burst_start_s` into it; the burst
            runs at `burst_rate_rpc_per_s`, the rest at
            `base_rate_rpc_per_s`.  Every phase holds exactly rate x length
            arrivals whose gaps are the exponential mid-quantiles, permuted
            by the seed, so every run holds the same bursts at the same
            offsets.
  sizes     checks per RPC: every whole number from min to max once per
            block, permuted by the seed.
  keys      "uniform" over the universe's plain keys; on a configuration
            with GLOBAL keys, `global_per_rpc` checks of every RPC go to
            GLOBAL keys instead (a fixed count, so that the bound on what a
            drain can merge holds by construction and not by luck).
            "zipfian" (YCSB's ScrambledZipfianGenerator): rank r of the
            plain keys is drawn with probability 1 / (r^constant * zetan);
            the plan's ranks are the mid-quantiles of that distribution
            over its total checks (the inverse of the exact CDF, where
            YCSB applies Gray et al.'s closed form to a uniform draw: that
            form is off by several per cent at the lowest ranks), permuted
            by the seed; with `scramble` a rank's key position is FNV-1a 64
            of the rank modulo the key count, so hot keys spread over
            buckets and shards.  The hot set is the same all run long.
  hits      a whole number, or {"values": [...], "weights": [...]}: the
            values in the weights' proportion, exactly, in whole permuted
            blocks of about a thousand checks.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

from lib.universe import derive_seed

GAP_BLOCK = 1000
HITS_BLOCK = 1000
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


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


def _exp_gaps(n: int, mean: float) -> np.ndarray:
    """The n mid-quantiles of the exponential distribution, mean `mean`."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (mean / gaps.mean())


def square_phases(arr: dict):
    """One period as (start_s, length_s, rate) phases, in order (the
    forms are lib/spec.py's to refuse)."""
    period, burst = float(arr["period_s"]), float(arr["burst_s"])
    at = float(arr.get("burst_start_s", 0.0))
    base = float(arr["base_rate_rpc_per_s"])
    peak = float(arr["burst_rate_rpc_per_s"])
    phases = [(0.0, at, base), (at, burst, peak),
              (at + burst, period - at - burst, base)]
    return [p for p in phases if p[1] > 0]


def square_times(seed: int, arr: dict, duration_s: float) -> np.ndarray:
    """Intended send offsets in [0, duration_s) of a square wave.  A
    phase of length L at rate r holds round(r * L) arrivals: the first
    half of the smallest gap after the phase starts, the others one
    permuted gap after another; the gaps sum to L, so a phase never
    spills into the next."""
    if duration_s <= 0:
        raise ValueError(f"duration must be > 0: {duration_s}")
    period = float(arr["period_s"])
    phases = square_phases(arr)
    out = []
    for p in range(int(np.ceil(duration_s / period))):
        for i, (start, length, rate) in enumerate(phases):
            n = int(round(rate * length))
            if n == 0:
                continue
            gaps = _exp_gaps(n, length / n)
            rng = np.random.default_rng(
                derive_seed(seed, f"square/times/{p}/{i}")
            )
            g = rng.permutation(gaps)
            t = np.cumsum(g) - g + gaps[0] / 2
            out.append(p * period + start + t)
    t = np.concatenate(out)
    return t[t < duration_s]


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """YCSB's Utils.fnvhash64 of whole numbers: FNV-1a over the eight
    octets, lowest first, and the absolute value of the signed result."""
    v = values.astype(np.uint64)
    h = np.full(len(v), FNV_OFFSET_BASIS_64, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64)).view(np.uint64)


def zipfian_weights(n_items: int, theta: float) -> np.ndarray:
    """Cumulative weights of ranks 1..n_items: the last is zetan."""
    return np.cumsum(np.arange(1, n_items + 1, dtype=np.float64) ** -theta)


def zipfian_ranks(total: int, n_items: int, theta: float) -> np.ndarray:
    """`total` ranks (0 the hottest), ascending: the mid-quantiles of the
    zipfian distribution over `n_items`, the same for every seed."""
    cdf = zipfian_weights(n_items, theta)
    u = (np.arange(total, dtype=np.float64) + 0.5) / total
    ranks = np.searchsorted(cdf, u * cdf[-1], side="left")
    return np.minimum(ranks, n_items - 1).astype(np.int64)


def key_positions(traffic: dict, universe: dict, seed: int,
                  total: int) -> np.ndarray:
    """Universe positions of `total` checks on plain keys."""
    keys = traffic["keys"]
    n_keys = int(universe["keys"])
    n_global = int(universe.get("global_keys", 0))
    kind = keys["distribution"]
    if kind == "uniform":
        rng = np.random.default_rng(derive_seed(seed, "uniform/keys"))
        return rng.integers(n_global, n_keys, size=total, dtype=np.int64)
    if kind != "zipfian":
        raise ValueError(f"unknown key distribution {kind!r}")
    n_plain = n_keys - n_global
    ranks = zipfian_ranks(total, n_plain, float(keys["constant"]))
    if keys.get("scramble", False):
        ranks = (fnv1a64(ranks) % np.uint64(n_plain)).astype(np.int64)
    rng = np.random.default_rng(derive_seed(seed, "zipfian/keys"))
    return n_global + rng.permutation(ranks)


def hits_column(traffic: dict, seed: int, total: int) -> np.ndarray:
    """Hits of every check: the scalar, or the mix in exact proportion."""
    h = traffic.get("hits", 1)
    if not isinstance(h, dict):
        return np.full(total, int(h), dtype=np.int64)
    values = np.asarray(h["values"], dtype=np.int64)
    weights = np.asarray(h["weights"], dtype=np.int64)
    block = np.repeat(values, weights * -(-HITS_BLOCK // int(weights.sum())))
    rng = np.random.default_rng(derive_seed(seed, "hits"))
    return _block_permuted(block, total, rng)


def rpc_sizes(seed: int, lo: int, hi: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(derive_seed(seed, "sizes"))
    return _block_permuted(np.arange(lo, hi + 1, dtype=np.int64), n, rng)


@dataclass(frozen=True)
class Plan:
    """RPC j holds checks [offsets[j], offsets[j+1]) of `key_index`
    (positions in the universe) and `hits`.  `times_s` is None for a
    closed loop."""

    offsets: np.ndarray
    key_index: np.ndarray
    times_s: np.ndarray
    hits: np.ndarray

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def digest(self) -> str:
        """Content digest (ns-quantized times, sizes, keys, and hits
        where any is not 1, so that the plans of PR 24's mixes keep their
        digests): the same seed gives the same hex in every process."""
        h = hashlib.sha256()
        if self.times_s is not None:
            h.update(np.round(self.times_s * 1e9).astype(np.int64).tobytes())
        h.update(self.offsets.astype(np.int64).tobytes())
        h.update(self.key_index.astype(np.int64).tobytes())
        if (self.hits != 1).any():
            h.update(self.hits.astype(np.int64).tobytes())
        return h.hexdigest()


def build_plan(traffic: dict, universe: dict, seed: int,
               span_s: float) -> Plan:
    """The plan for `span_s` seconds of this traffic (warm-in + window)."""
    lo = int(traffic["checks_per_rpc"]["min"])
    hi = int(traffic["checks_per_rpc"]["max"])
    times = None
    if traffic["loop"] == "open":
        arr = traffic["arrivals"]
        if arr["process"] == "poisson":
            times = poisson_times(seed, float(arr["rate_rpc_per_s"]), span_s)
        elif arr["process"] == "square":
            times = square_times(seed, arr, span_s)
        else:
            raise ValueError(f"unknown arrival process {arr['process']!r}")
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
    n_global = int(universe.get("global_keys", 0))
    key_index = key_positions(traffic, universe, seed, total)
    g = int(traffic.get("global_per_rpc", 0)) if n_global else 0
    if g:
        if g > lo:
            raise ValueError("global_per_rpc exceeds the smallest RPC")
        grng = np.random.default_rng(derive_seed(seed, "global/keys"))
        where = (offsets[:-1, None] + np.arange(g)[None, :]).ravel()
        key_index[where] = grng.integers(0, n_global, size=len(where))
    return Plan(offsets=offsets, key_index=key_index, times_s=times,
                hits=hits_column(traffic, seed, total))


def hottest_keys(plan: Plan, n: int) -> np.ndarray:
    """The `n` universe positions the plan hits most often."""
    keys, counts = np.unique(plan.key_index, return_counts=True)
    return keys[np.argsort(-counts, kind="stable")[:n]]
