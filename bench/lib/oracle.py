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

A clock that moves (`replay_moving`).  All of the above is `replay_sample`,
for a configuration whose windows no run outlasts: the reference's clock
stands at the preload stamp.  Where the configuration says
`"clock": "moving"` windows elapse, buckets renew and leaky buckets leak
inside a run, and the reference is replayed under the clock each answer
was given under, which the answer itself carries:

  leaky   now = reset_time - (limit - remaining) x trunc(duration / limit)
          in every branch of pymodel's leaky bucket.  It must lie inside
          the RPC's wall_send..wall_recv (client and daemon read one host
          clock, both as floor(ns / 10^6), and floor is monotone: no slack)
          and may not fall along one RPC's checks; the reference, its clock
          set to it, gives status, remaining and reset_time exactly.
  token   reset_time - duration is the stamp its bucket was created at: a
          generation.  The generation the reference holds: the RPC was sent
          before its `expire_at` and received at or after its stamp.  A new
          one: the stamp is at or after the old `expire_at` (`is_expired`:
          expire_at <= now) and inside the bounds of an RPC of the
          generation's first group in flight; the reference is replayed at
          the stamp.  Status, remaining and reset_time exact.  An RPC whose
          bounds hold an expiry may be answered from either side of it.

What that leaves open, by construction.  A leaky answer is held as a
whole: status, remaining and reset_time must be what the reference gives at
ONE clock inside its RPC's flight, after the key's earlier answers.  An
answer whose remaining is k tokens off and whose reset_time is right is,
field for field, the answer of a clock k x rate away, and the bucket it
leaves is the same bucket (r tokens at t = r - k at t - k x rate).  It is
refused where that clock leaves the RPC's bounds, falls before the key's
last answer, or meets the limit or 0; elsewhere it is the answer of a
server that took the request that much earlier or later inside its flight,
which no client can tell apart.  A new bucket's answer does not depend on
the clock and has no such room.  `live_leaky_answers_with_room_for_a_token`
counts the live buckets' answers whose bounds hold a clock one rate away.

Answers are replayed in the order of their clocks, and as sent within one
clock; RPCs in flight together under one clock are matched as a multiset as
above.  A key of a crowded bucket may still answer as a fresh bucket, and no
more: a leaky one at its own clock, a token one by a new generation before
the old one's end.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from lib.universe import ALGO_LEAKY, Universe, key_string

OK = 0  # bench/client.py's code for an answered RPC
TARGET_SAMPLE = 150_000
# What a replay counts, every count held to 0; and what the moving one saw,
# the first three of which a run has to have seen (bench/run.py).
FROZEN_COUNTS = ("wrong_answers", "wrong_reset_time",
                 "out_of_order_duplicates")
MOVING_COUNTS = FROZEN_COUNTS + (
    "clock_outside_rpc", "clock_runs_backwards", "answered_after_expiry",
    "renewed_before_expiry")
MOVING_SEEN = ("live_window_answers", "new_window_answers",
               "whole_token_leaks", "straddling_answers",
               "live_leaky_answers",
               "live_leaky_answers_with_room_for_a_token")


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


def _sample(a: Answers, rec, uni: Universe, seed: int,
            set_aside: np.ndarray, always: Optional[np.ndarray],
            v: Verdict):
    """The sampled answers as rows of `a` in (key, send time, row) order,
    their keys, and where each key's run starts and ends."""
    plain = ~uni.is_global[a.key]
    modulus = max(1, int(plain.sum()) // TARGET_SAMPLE)
    pick = plain & (a.key % modulus == seed % modulus)
    if always is not None and modulus > 1:
        pick |= plain & np.isin(a.key, always)
    if len(set_aside):
        pick &= ~np.isin(a.key, set_aside)
    idx = np.flatnonzero(pick)
    order = idx[np.lexsort((idx, rec["t_send"][a.rpc[idx]], a.key[idx]))]
    keys = a.key[order]
    cuts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1], True])
    v.notes["sampled_answers"] = len(order)
    v.notes["sampled_keys"] = len(cuts) - 1
    v.notes["sample_modulus"] = modulus
    return order, keys, cuts


class _Reference:
    """core/pymodel.py on a clock the replay sets, holding one key at a
    time as the harness preloaded it.  `t0_ms` is the preload stamp; of a
    cluster, every daemon's (each stamps the rows it installs with its own
    clock), and `self.t0_ms` is then the stamp of the daemon that owns the
    key last started."""

    def __init__(self, uni: Universe, t0_ms,
                 extra_crowded: np.ndarray) -> None:
        from gubernator_tpu.core import clock as clock_mod
        from gubernator_tpu.core import types
        from gubernator_tpu.core.pymodel import PyRateLimiter

        self.uni, self.types = uni, types
        self.t0_by_daemon = [int(t) for t in np.atleast_1d(t0_ms)]
        self.t0_ms = self.t0_by_daemon[0]
        self.clk = clock_mod.Clock()
        self.at(self.t0_ms)
        self.model = PyRateLimiter(clock=self.clk)
        self.crowded = set(extra_crowded.tolist())

    def at(self, now_ms: int) -> None:
        self.clk.freeze(now_ms * 1_000_000)

    def start_key(self, k: int, hits: np.ndarray):
        """Empty the reference but for key `k`'s preloaded row.  Returns
        the key's hash key, whether it is leaky, a request for each value
        of `hits`, and whether its bucket may evict (a weak key)."""
        uni, t = self.uni, self.types
        if uni.owner is not None:
            self.t0_ms = self.t0_by_daemon[int(uni.owner[k])]
            self.at(self.t0_ms)
        leaky = int(uni.algo[k]) == ALGO_LEAKY
        hkey = key_string(int(uni.ids[k]))
        weak = bool(uni.crowded[k]) or int(uni.gbucket[k]) in self.crowded
        algorithm = (t.Algorithm.LEAKY_BUCKET if leaky
                     else t.Algorithm.TOKEN_BUCKET)
        dur = uni.duration_ms
        reqs = {
            h: t.RateLimitReq(
                name=hkey[:9], unique_key=hkey[10:], hits=h,
                limit=uni.limit, duration=dur, algorithm=algorithm,
            ) for h in np.unique(hits).tolist()
        }
        self.model.cache.clear()
        if uni.resident[k]:
            self.model.cache[hkey] = t.CacheItem(
                key=hkey, algorithm=algorithm,
                expire_at=self.t0_ms + dur, limit=uni.limit, duration=dur,
                remaining=(float(uni.remaining0[k]) if leaky
                           else int(uni.remaining0[k])),
                created_at=self.t0_ms, status=t.Status.UNDER_LIMIT,
                burst=uni.limit,
            )
        return hkey, leaky, reqs, weak


def _in_flight_groups(rows, a: Answers, t_send, t_done,
                      clock=None) -> List[List[int]]:
    """`rows` (of `a`, in replay order) cut into groups of RPCs in flight
    together; under a moving clock a group also ends where the clock
    (`clock[i]` of `rows[i]`) changes."""
    groups: List[List[int]] = []
    end = -1.0
    for i, r in enumerate(rows):
        q = int(a.rpc[r])
        same = clock is None or clock[i] == clock[i - 1]
        if groups and same and (q == int(a.rpc[groups[-1][-1]])
                                or t_send[q] < end):
            groups[-1].append(int(r))
        else:
            groups.append([int(r)])
            end = -1.0
        end = max(end, float(t_done[q]))
    return groups


def _observed(grp: List[int], a: Answers, hkey: str, model, reqs: dict,
              v: Verdict):
    """A group's answers as (status, remaining, reset_time, rpc, hits) in
    the order the reference is to give them, and the group's RPCs."""
    rpcs = {int(a.rpc[r]) for r in grp}
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
    return obs, rpcs


def replay_sample(a: Answers, rec, uni: Universe, t0_ms: int, seed: int,
                  set_aside: np.ndarray, extra_crowded: np.ndarray,
                  v: Verdict, always: Optional[np.ndarray] = None) -> None:
    """Replay every answer on the sampled keys through core/pymodel.py,
    its clock frozen at the preload stamp.  The keys of `always` (a skewed
    traffic's hottest) are in the sample whatever the seed draws."""
    order, keys, cuts = _sample(a, rec, uni, seed, set_aside, always, v)
    t_send, t_done = rec["t_send"], rec["t_done"]
    ref = _Reference(uni, t0_ms, extra_crowded)
    model = ref.model
    restarts = ambiguous = 0

    for c in range(len(cuts) - 1):
        rows = order[cuts[c]:cuts[c + 1]]
        k = int(keys[cuts[c]])
        hkey, leaky, reqs, weak = ref.start_key(k, a.hits[rows])
        created = None  # wall bounds of the RPC that created the bucket
        for grp in _in_flight_groups(rows, a, t_send, t_done):
            if weak and len({int(a.rpc[r]) for r in grp}) > 1:
                ambiguous += len(grp)
                break  # later answers depend on an unknowable eviction
            obs, rpcs = _observed(grp, a, hkey, model, reqs, v)
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
                    off = want.reset_time - ref.t0_ms
                    w = (lo, hi) if leaky else created
                    if len(rpcs) == 1 and leaky:
                        w = (int(rec["wall_send"][o[3]]),
                             int(rec["wall_recv"][o[3]]))
                    good = w[0] + off <= o[2] <= w[1] + off
                else:
                    good = o[2] == want.reset_time
                if not good:
                    v.bad("wrong_reset_time", key=hkey, rpc=o[3],
                          want=want.reset_time - ref.t0_ms,
                          got=o[2] - ref.t0_ms, leaky=leaky, created=created)
    for name in FROZEN_COUNTS:
        v.counts.setdefault(name, 0)
    v.notes["crowded_restarts"] = restarts
    v.notes["crowded_ambiguous"] = ambiguous


def replay_moving(a: Answers, rec, uni: Universe, t0_ms: int, seed: int,
                  set_aside: np.ndarray, extra_crowded: np.ndarray,
                  v: Verdict, always: Optional[np.ndarray] = None) -> None:
    """`replay_sample` for a configuration whose windows elapse inside a
    run: every answer on the sampled keys is replayed through
    core/pymodel.py under the clock the answer itself carries (the module
    docstring has the rules).  Counts in `v.counts` are the names of
    MOVING_COUNTS, all held to 0; `v.notes` gains MOVING_SEEN, what the
    replay saw."""
    order, keys, cuts = _sample(a, rec, uni, seed, set_aside, always, v)
    t_send, t_done = rec["t_send"], rec["t_done"]
    wall_send, wall_recv = rec["wall_send"], rec["wall_recv"]
    ref = _Reference(uni, t0_ms, extra_crowded)
    model = ref.model
    dur, limit = uni.duration_ms, uni.limit
    rate = dur / limit              # pymodel: rate = duration / limit
    rate_i = int(rate)              # and trunc(rate) in its reset_time
    restarts = ambiguous = 0
    saw = dict.fromkeys(MOVING_SEEN, 0)

    for c in range(len(cuts) - 1):
        rows = order[cuts[c]:cuts[c + 1]]
        k = int(keys[cuts[c]])
        hkey, leaky, reqs, weak = ref.start_key(k, a.hits[rows])
        t0_ms = ref.t0_ms           # the owner's stamp; below only in notes
        if leaky:
            clock = a.reset_time[rows] - (limit - a.remaining[rows]) * rate_i
        else:
            clock = a.reset_time[rows] - dur
        # One RPC's checks are answered in order: its clock cannot fall.
        q = a.rpc[rows]
        for i in np.flatnonzero((q[1:] == q[:-1])
                                & (clock[1:] < clock[:-1])).tolist():
            v.bad("clock_runs_backwards", key=hkey, rpc=int(q[i]),
                  clocks=(int(clock[i]) - t0_ms, int(clock[i + 1]) - t0_ms),
                  leaky=leaky)
        by = np.argsort(clock, kind="stable")   # as sent within one clock
        rows, clock = rows[by], clock[by]
        clock_of = dict(zip(rows.tolist(), clock.tolist()))
        for grp in _in_flight_groups(rows, a, t_send, t_done, clock):
            now = clock_of[grp[0]]
            bounds = [(int(wall_send[a.rpc[r]]), int(wall_recv[a.rpc[r]]))
                      for r in grp]
            inside = [s <= now <= r for s, r in bounds]
            if leaky:
                outside = [r for r, ok in zip(grp, inside) if not ok]
                # What the clock check leaves open: a live bucket's answer
                # a token off is the answer of a clock one rate away (the
                # docstring's residual); these RPCs' bounds hold it too.
                room = {int(a.rpc[r]) for r, (s, got) in zip(grp, bounds)
                        if s <= now - rate_i or now + rate_i <= got}
            else:
                # A generation: the one the reference holds, or a new one.
                item = model.cache.get(hkey)
                old_end = item.expire_at if item is not None else None
                held = old_end == now + dur
                if not held and old_end is not None and now < old_end:
                    if weak:    # evicted since, and created again
                        restarts += 1
                    else:
                        v.bad("renewed_before_expiry", key=hkey,
                              rpc=int(a.rpc[grp[0]]), stamp=now - t0_ms,
                              expire_at=old_end - t0_ms)
                    model.cache.pop(hkey, None)
                end = now + dur if held else old_end
                if end is not None:
                    saw["straddling_answers"] += sum(
                        s < end <= r for s, r in bounds)
                # Sent before this generation's end, received at or after
                # its stamp; a new one was stamped inside an RPC of its
                # first group in flight.
                for r, (s, _) in zip(grp, bounds):
                    if s >= now + dur:
                        v.bad("answered_after_expiry", key=hkey,
                              rpc=int(a.rpc[r]), stamp=now - t0_ms,
                              sent=s - t0_ms)
                outside = [r for r, (_, got) in zip(grp, bounds)
                           if got < now]
                if not held and not any(inside) and not outside:
                    outside = grp[:1]
            for r in outside:
                v.bad("clock_outside_rpc", key=hkey, rpc=int(a.rpc[r]),
                      clock=now - t0_ms, leaky=leaky,
                      sent=int(wall_send[a.rpc[r]]) - t0_ms,
                      received=int(wall_recv[a.rpc[r]]) - t0_ms)
            if weak and len({int(a.rpc[r]) for r in grp}) > 1:
                ambiguous += len(grp)
                break  # later answers depend on an unknowable eviction
            ref.at(now)
            obs, _ = _observed(grp, a, hkey, model, reqs, v)
            for o in obs:
                item = model.cache.get(hkey)
                live = item is not None and not item.is_expired(now)
                leaked = (leaky and live
                          and int((now - item.created_at) / rate) > 0)
                want = model.get_rate_limit(reqs[o[4]])
                if weak and leaky and live and (
                    (int(want.status), want.remaining) != o[:2]
                ):
                    # The row may have been evicted since: a fresh bucket.
                    model.cache.pop(hkey, None)
                    live = leaked = False
                    restarts += 1
                    want = model.get_rate_limit(reqs[o[4]])
                saw["live_window_answers" if live
                    else "new_window_answers"] += 1
                saw["whole_token_leaks"] += leaked
                if leaky and live:
                    saw["live_leaky_answers"] += 1
                    saw["live_leaky_answers_with_room_for_a_token"] += (
                        o[3] in room)
                if (int(want.status), want.remaining) != o[:2]:
                    v.bad("wrong_answers", key=hkey, rpc=o[3],
                          want=(int(want.status), want.remaining),
                          got=o[:2], resident=bool(uni.resident[k]),
                          leaky=leaky, clock=now - t0_ms, live=live)
                elif o[2] != want.reset_time:
                    v.bad("wrong_reset_time", key=hkey, rpc=o[3],
                          want=want.reset_time - t0_ms, got=o[2] - t0_ms,
                          leaky=leaky, clock=now - t0_ms)
    for name in MOVING_COUNTS:
        v.counts.setdefault(name, 0)
    v.notes.update(saw)
    v.notes["crowded_restarts"] = restarts
    v.notes["crowded_ambiguous"] = ambiguous
