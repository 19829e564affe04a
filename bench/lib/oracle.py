"""The comparison that decides `correct`: what the daemon answered, over the
warm-in and the window, against core/pymodel.py (the plain reference, which
is imported; everything around it is the benchmark's own).

Every answer is screened columnar-ly (no error, the right limit, a status
and a remaining that can exist).  The answers on a seeded sample of keys —
ALL answers on each sampled key since the preload, because a key's answers
only mean something as a sequence — are then replayed through the
reference, field by field:

  status, limit, remaining   exact;
  reset_time                 exact for a preloaded token bucket (its expiry
                             was installed), otherwise the reference's
                             offset from its frozen clock, re-based on the
                             send/receive wall-clock bounds of the RPC that
                             fixed it (chip_smoke.py's WireOracle rule).

Order.  One RPC's duplicates must be answered in order.  Two RPCs that
were in flight at the same time may reach the table in either order, so the
answers of such a group are matched to the reference as a multiset: an order
is sought in which the reference gives exactly these answers, each RPC's own
checks kept in their order (`linearize`).  A bucket only runs down inside a
run (nothing expires or leaks a whole token), so a peek (`hits` 0, which
answers and changes nothing) is taken as soon as the reference's state
matches it and a spend when no peek can be: with `hits` of 0 and one other
value that finds an order whenever one exists.

Crowded buckets.  A bucket that more than `ways` keys of the universe map
to evicts, and which row goes depends on the server's millisecond stamps.
For keys of such buckets (known from the placement arithmetic) an answer
may also be the one a fresh bucket gives; everywhere else eviction is
impossible and the replay is strict.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from lib.universe import ALGO_LEAKY, Universe, key_string

OK = 0  # bench/client.py's code for an answered RPC
TARGET_SAMPLE = 150_000


@dataclass
class Answers:
    """One row per answered check, in (RPC, position) order."""

    key: np.ndarray        # universe index
    rpc: np.ndarray        # row of the client's record
    hits: np.ndarray       # what the check asked for
    status: np.ndarray
    limit: np.ndarray
    remaining: np.ndarray
    reset_time: np.ndarray
    err_len: np.ndarray


def flatten(plan, rec: Dict[str, np.ndarray]) -> Answers:
    ok = np.flatnonzero(rec["code"] == OK)
    sizes = np.diff(rec["ans_off"])[ok]
    starts = plan.offsets[rec["plan_idx"][ok]]
    total = int(sizes.sum())
    rpc = np.repeat(ok, sizes)
    first = np.repeat(np.cumsum(sizes) - sizes, sizes)
    pos = np.arange(total, dtype=np.int64) - first
    at = np.repeat(starts, sizes) + pos
    lo = np.repeat(rec["ans_off"][ok], sizes) + pos
    return Answers(
        key=plan.key_index[at], rpc=rpc, hits=plan.hits[at],
        status=rec["status"][lo], limit=rec["limit"][lo],
        remaining=rec["remaining"][lo], reset_time=rec["reset_time"][lo],
        err_len=rec["err_len"][lo],
    )


def unanswered_keys(plan, rec: Dict[str, np.ndarray]) -> np.ndarray:
    """Keys of RPCs that were sent and not answered: the reference cannot
    know whether the server applied them, so they are set aside."""
    bad = np.flatnonzero(rec["code"] != OK)
    if not len(bad):
        return np.zeros(0, dtype=np.int64)
    j = rec["plan_idx"][bad]
    return np.unique(np.concatenate([
        plan.key_index[plan.offsets[a]:plan.offsets[a + 1]] for a in j
    ]))


@dataclass
class Verdict:
    counts: Dict[str, int] = field(default_factory=dict)
    notes: Dict[str, int] = field(default_factory=dict)
    first: Optional[dict] = None

    def bad(self, what: str, **detail) -> None:
        self.counts[what] = self.counts.get(what, 0) + 1
        if self.first is None:
            self.first = {"what": what, **detail}


def screen(a: Answers, uni: Universe, v: Verdict) -> None:
    """Columnar checks on every answer."""
    g = uni.is_global[a.key]
    want_limit = np.where(g, uni.global_limit, uni.limit)
    v.counts["errors"] = int((a.err_len != 0).sum())
    v.counts["wrong_limit"] = int((a.limit != want_limit).sum())
    # An admitted spend leaves at most limit - hits; a peek or a refusal
    # may show the whole limit.
    most = want_limit - np.where(a.status == 0, a.hits, 0)
    v.counts["malformed_answers"] = int((
        (a.status < 0) | (a.status > 1) | (a.remaining < 0)
        | (a.remaining > most)
    ).sum())
    # GLOBAL limits are far above what a run can send: never over.
    v.counts["global_not_under"] = int((g & (a.status != 0)).sum())
    v.notes["answers"] = len(a.key)
    v.notes["over_limit"] = int((a.status == 1).sum())
    v.notes["global_answers"] = int(g.sum())


def _canonical(rows: List[tuple]) -> List[tuple]:
    """The order in which one request repeated on one key is answered:
    remaining falls, then OVER_LIMIT."""
    return sorted(rows, key=lambda r: (r[0], -r[1]))


def linearize(model, reqs: dict, hkey: str, obs: List[tuple]) -> List[tuple]:
    """An order of `obs` — (status, remaining, reset_time, rpc, hits) of one
    key's answers from RPCs in flight together, each RPC's in request
    order — in which the reference answers as observed; what cannot be
    placed comes last, in its own order.  The reference's state of the key
    is put back as it was."""
    queues: Dict[int, List[tuple]] = {}
    for o in obs:
        queues.setdefault(o[3], []).append(o)
    before = copy.copy(model.cache.get(hkey))

    def restore(item) -> None:
        if item is None:
            model.cache.pop(hkey, None)
        else:
            model.cache[hkey] = item

    out: List[tuple] = []
    while queues:
        heads = sorted((q[0] for q in queues.values()),
                       key=lambda o: (o[4], o[3]))
        for o in heads:
            keep = copy.copy(model.cache.get(hkey))
            want = model.get_rate_limit(reqs[o[4]])
            if (int(want.status), want.remaining) == o[:2]:
                break
            restore(keep)
        else:
            break
        out.append(o)
        queues[o[3]].pop(0)
        if not queues[o[3]]:
            del queues[o[3]]
    restore(before)
    return out + [o for q in queues.values() for o in q]


def replay_sample(a: Answers, rec, uni: Universe, t0_ms: int, seed: int,
                  set_aside: np.ndarray, extra_crowded: np.ndarray,
                  v: Verdict, always: Optional[np.ndarray] = None) -> None:
    """Replay every answer on the sampled keys through core/pymodel.py.
    The keys of `always` (a skewed traffic's hottest) are in the sample
    whatever the seed draws."""
    from gubernator_tpu.core import clock as clock_mod
    from gubernator_tpu.core.pymodel import PyRateLimiter
    from gubernator_tpu.core.types import (
        Algorithm, CacheItem, RateLimitReq, Status,
    )

    plain = ~uni.is_global[a.key]
    modulus = max(1, int(plain.sum()) // TARGET_SAMPLE)
    pick = plain & (a.key % modulus == seed % modulus)
    if always is not None and modulus > 1:
        pick |= plain & np.isin(a.key, always)
    if len(set_aside):
        pick &= ~np.isin(a.key, set_aside)
    idx = np.flatnonzero(pick)
    t_send, t_done = rec["t_send"], rec["t_done"]
    order = idx[np.lexsort((idx, t_send[a.rpc[idx]], a.key[idx]))]
    keys = a.key[order]
    cuts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1], True])
    v.notes["sampled_answers"] = len(order)
    v.notes["sampled_keys"] = len(cuts) - 1
    v.notes["sample_modulus"] = modulus

    clk = clock_mod.Clock()
    clk.freeze(t0_ms * 1_000_000)
    model = PyRateLimiter(clock=clk)
    dur = uni.duration_ms
    crowded_set = set(extra_crowded.tolist())
    restarts = ambiguous = 0

    for c in range(len(cuts) - 1):
        rows = order[cuts[c]:cuts[c + 1]]
        k = int(keys[cuts[c]])
        kid = int(uni.ids[k])
        leaky = int(uni.algo[k]) == ALGO_LEAKY
        hkey = key_string(kid)
        weak = bool(uni.crowded[k]) or int(uni.gbucket[k]) in crowded_set
        algorithm = (Algorithm.LEAKY_BUCKET if leaky
                     else Algorithm.TOKEN_BUCKET)
        reqs = {
            h: RateLimitReq(
                name=hkey[:9], unique_key=hkey[10:], hits=h,
                limit=uni.limit, duration=dur, algorithm=algorithm,
            ) for h in np.unique(a.hits[rows]).tolist()
        }
        model.cache.clear()
        created = None  # wall bounds of the RPC that created the bucket
        if uni.resident[k]:
            model.cache[hkey] = CacheItem(
                key=hkey, algorithm=algorithm,
                expire_at=t0_ms + dur, limit=uni.limit, duration=dur,
                remaining=(float(uni.remaining0[k]) if leaky
                           else int(uni.remaining0[k])),
                created_at=t0_ms, status=Status.UNDER_LIMIT,
                burst=uni.limit,
            )
        # Groups of RPCs in flight together, in send order.
        groups: List[List[int]] = []
        end = -1.0
        for r in rows:
            q = int(a.rpc[r])
            if groups and (q == int(a.rpc[groups[-1][-1]])
                           or t_send[q] < end):
                groups[-1].append(int(r))
            else:
                groups.append([int(r)])
            end = max(end, float(t_done[q]))
        for grp in groups:
            rpcs = {int(a.rpc[r]) for r in grp}
            if weak and len(rpcs) > 1:
                ambiguous += len(grp)
                break  # later answers depend on an unknowable eviction
            obs = [(int(a.status[r]), int(a.remaining[r]),
                    int(a.reset_time[r]), int(a.rpc[r]), int(a.hits[r]))
                   for r in grp]
            # In-order duplicates: one request repeated inside one RPC is
            # answered in the canonical order; an RPC that mixes peeks
            # and spends is held to its order by the replay itself.
            per_rpc: Dict[int, List[tuple]] = {}
            for o in obs:
                per_rpc.setdefault(o[3], []).append(o)
            for q, lst in per_rpc.items():
                if len({o[4] for o in lst}) == 1 and lst != _canonical(lst):
                    v.bad("out_of_order_duplicates", key=hkey, rpc=q,
                          got=[o[:2] for o in lst])
            if len(rpcs) > 1:
                obs = (_canonical(obs) if len(reqs) == 1
                       else linearize(model, reqs, hkey, obs))
            lo = min(int(rec["wall_send"][q]) for q in rpcs)
            hi = max(int(rec["wall_recv"][q]) for q in rpcs)
            for o in obs:
                fresh = hkey not in model.cache
                req = reqs[o[4]]
                want = model.get_rate_limit(req)
                if weak and not fresh and (
                    (int(want.status), want.remaining) != o[:2]
                ):
                    # The row may have been evicted since: a fresh bucket.
                    model.cache.pop(hkey, None)
                    fresh = True
                    restarts += 1
                    want = model.get_rate_limit(req)
                if fresh:
                    created = (lo, hi)
                if (int(want.status), want.remaining) != o[:2]:
                    v.bad("wrong_answers", key=hkey, rpc=o[3],
                          want=(int(want.status), want.remaining),
                          got=o[:2], resident=bool(uni.resident[k]),
                          leaky=leaky)
                    continue
                if leaky or created is not None:
                    off = want.reset_time - t0_ms
                    w = (lo, hi) if leaky else created
                    if len(rpcs) == 1 and leaky:
                        w = (int(rec["wall_send"][o[3]]),
                             int(rec["wall_recv"][o[3]]))
                    good = w[0] + off <= o[2] <= w[1] + off
                else:
                    good = o[2] == want.reset_time
                if not good:
                    v.bad("wrong_reset_time", key=hkey, rpc=o[3],
                          want=want.reset_time - t0_ms, got=o[2] - t0_ms,
                          leaky=leaky, created=created)
    for name in ("wrong_answers", "wrong_reset_time",
                 "out_of_order_duplicates"):
        v.counts.setdefault(name, 0)
    v.notes["crowded_restarts"] = restarts
    v.notes["crowded_ambiguous"] = ambiguous
