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

A table that does not hold its universe (`replay_tiered`, for a
configuration that says `"residency": "tiered"`).  docs/tiering.md serves a
check on a key whose row is in the cold store at once, from a fresh row, and
merges the cold row back later; `replay_sample` would call the fresh answer
wrong.  The tiered replay holds the daemon to what that document promises and
proves, and to no less.  Of a sampled key it keeps what the two tiers may
hold — a table row, a cold row, each as its (remaining, status) — and every
answer has to be what core/pymodel.py gives, after the key's earlier
answers, from one of the states the tier's algebra allows:

  continued   the table row as the earlier answers left it;
  merged      that row once the cold row has come back: its remaining less
              what the cold row had consumed, `max(r - consumed, 0)` of the
              cold row's `r` and the fresh row's `consumed` — the cold row
              is gone;
  promoted    no table row: the cold row itself (the promote landed before
              the check did, `note_access` runs ahead of the step);
  fresh       a fresh bucket, the table row — if there was one — now the
              cold row (a demotion; merged with a cold row still waiting,
              which assumes the least budget) — one more fresh start of the
              key;

and nothing else: an answer that none of these gives, such as one that mints
budget, is `wrong_answers`.  Beside the answers, per sampled key:

  admitted_beyond_bound   hits admitted in the run, one limit window, are at
                          most `limit x (1 + fresh starts seen)`;
  merged_late             the widening ends: a key answered from a fresh row
                          whose cold row is CERTAIN to wait — it was
                          preloaded there, or the row went from a bucket
                          that cannot evict — is answered from the merged
                          one by its first RPC sent more than the
                          configuration's `promote_deadline_ms` after that
                          fresh answer was received.  A key of a bucket
                          with more arrivals than ways may have lost its
                          row to the step's eviction instead of a demotion:
                          its fresh starts are allowed, and its unmerged
                          answers past the deadline not counted, since no
                          cold row may wait.

reset_time is `replay_sample`'s: a leaky answer's inside its RPC's bounds, a
token's its row's expiry — the preloaded one exactly, in either tier, or
inside the bounds of the RPC that started the fresh row, which a merge keeps.
RPCs in flight together may reach the table in any order, and a fresh start
may fall between them: every interleaving of a small group is tried (each
RPC's own checks in their order), the canonical order alone for a large one.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional

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
TIERED_COUNTS = FROZEN_COUNTS + ("admitted_beyond_bound", "merged_late")
TIERED_SEEN = ("continued_answers", "fresh_answers", "merged_answers",
               "promoted_answers", "keys_started_cold")
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
            self.model.cache[hkey] = self.preloaded_item(k, hkey, leaky)
        return hkey, leaky, reqs, weak

    def preloaded_item(self, k: int, hkey: str, leaky: bool):
        """Key `k`'s row as the harness preloaded it, at the stamp
        `start_key` set."""
        uni, t = self.uni, self.types
        dur = uni.duration_ms
        return t.CacheItem(
            key=hkey,
            algorithm=(t.Algorithm.LEAKY_BUCKET if leaky
                       else t.Algorithm.TOKEN_BUCKET),
            expire_at=self.t0_ms + dur, limit=uni.limit, duration=dur,
            remaining=(float(uni.remaining0[k]) if leaky
                       else int(uni.remaining0[k])),
            created_at=self.t0_ms, status=t.Status.UNDER_LIMIT,
            burst=uni.limit,
        )


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
              v: Verdict, linear: bool = True):
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
        obs = (_canonical(obs) if len(reqs) == 1 or not linear
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


# -- a table that does not hold its universe ---------------------------------

# A group of RPCs in flight together with more answers than this is
# replayed in its canonical order alone.
INTERLEAVE_MOST = 8


class _Tiers(NamedTuple):
    """What the two tiers may hold of one key, as the replay knows it.  A
    row is (remaining, status): under a clock that stands nothing else of
    the reference's item moves an answer."""

    hot: Optional[tuple] = None       # the table row
    cold: Optional[tuple] = None      # the cold store's row
    hot_born: Optional[tuple] = None  # wall bounds of the RPC that started
    cold_born: Optional[tuple] = None   # the row; None: the preload stamp
    certain: bool = True              # the cold row is known to wait
    since: Optional[float] = None     # when its fresh answer was received
    fresh: int = 0                    # fresh starts seen
    admitted: int = 0                 # hits admitted


def merge_rows(hot: tuple, cold: tuple, limit: int) -> tuple:
    """docs/tiering.md's merge on (remaining, status) rows: the table row
    keeps its status, and its remaining falls by what the cold row had
    consumed and stops at 0 — `max(r - consumed, 0)` of the cold row's `r`
    and the table row's `consumed`.  Never more than either."""
    return max(hot[0] - max(limit - cold[0], 0), 0), hot[1]


class _TieredKeys:
    """The answers of a sampled key against the states the tier's algebra
    allows.  What cannot be told apart is kept apart: a replay holds every
    state the answers so far allow (a row of a crowded bucket that went may
    have been demoted or evicted) and an answer is wrong where none gives
    it.  core/pymodel.py answers every question, once: under a clock that
    stands its answer follows from the row's (remaining, status), the
    algorithm and the hits alone."""

    def __init__(self, ref: _Reference, deadline_s: float, v: Verdict,
                 saw: dict) -> None:
        self.ref, self.deadline_s, self.v, self.saw = ref, deadline_s, v, saw
        self.limit = ref.uni.limit
        self.asked: Dict[tuple, tuple] = {}

    def start(self, hkey: str, reqs: dict, weak: bool, leaky: bool) -> None:
        self.hkey, self.reqs, self.weak, self.leaky = hkey, reqs, weak, leaky

    def _ask(self, row: Optional[tuple], hits: int) -> tuple:
        """(status, remaining, reset_time - the preload stamp) the reference
        answers `hits` with from `row`, and the row it leaves."""
        at = (self.leaky, row, hits)
        got = self.asked.get(at)
        if got is None:
            ref, cache = self.ref, self.ref.model.cache
            cache.clear()
            if row is not None:
                item = ref.preloaded_item(0, self.hkey, self.leaky)
                item.remaining, item.status = row[0], ref.types.Status(row[1])
                cache[self.hkey] = item
            want = ref.model.get_rate_limit(self.reqs[hits])
            left = cache[self.hkey]
            got = self.asked[at] = (
                (int(want.status), want.remaining,
                 want.reset_time - ref.t0_ms),
                (left.remaining, int(left.status)),
            )
            cache.clear()
        return got

    def step(self, st: _Tiers, o: tuple, bounds: tuple, t_send: float,
             t_done: float) -> List[tuple]:
        """Every (`st` after the answer `o`, the state that gave it, whether
        its reset_time is that state's, whether the key's cold row had
        waited past the deadline) that the algebra allows; none where no
        allowed state gives `o` = (status, remaining, reset_time, rpc, hits).
        """
        waited = (st.hot is not None and st.cold is not None
                  and st.since is not None
                  and t_send > st.since + self.deadline_s)
        tries = []
        if st.hot is not None:
            tries.append(("continued", st.hot))
            if st.cold is not None:
                tries.append(("merged",
                              merge_rows(st.hot, st.cold, self.limit)))
        if st.cold is not None and (st.hot is None or self.weak):
            # The promote found no table row: it landed before the check
            # did, or the step had evicted the row of a crowded bucket.
            tries.append(("promoted", st.cold))
        tries.append(("fresh", None))
        out = []
        for kind, row in tries:
            want, left = self._ask(row, o[4])
            if want[:2] != o[:2]:
                continue
            nxt = st._replace(hot=left, admitted=st.admitted
                              + (o[4] if o[0] == 0 else 0))
            if kind == "merged":
                nxt = nxt._replace(cold=None, cold_born=None, since=None)
            elif kind == "promoted":
                nxt = nxt._replace(hot_born=st.cold_born, cold=None,
                                   cold_born=None, since=None)
            elif kind == "fresh":
                nxt = nxt._replace(hot_born=bounds, fresh=st.fresh + 1)
            born = bounds if self.leaky else nxt.hot_born
            good = (o[2] == self.ref.t0_ms + want[2] if born is None
                    else born[0] + want[2] <= o[2] <= born[1] + want[2])
            late = waited and kind == "continued"
            if kind != "fresh":
                out.append((nxt, kind, good, late))
                continue
            waiting = st.cold is not None
            if st.hot is None:
                out.append((nxt._replace(since=t_done if waiting else None),
                            kind, good, late))
                continue
            # A fresh start over a table row: the row went to the cold store
            # (merged with a cold row still waiting there, which assumes the
            # least budget) — or, in a bucket that can evict, was lost.
            out.append((nxt._replace(
                cold=(merge_rows(st.hot, st.cold, self.limit) if waiting
                      else st.hot),
                cold_born=st.hot_born, since=t_done,
                certain=(st.certain and waiting) or not self.weak,
            ), kind, good, late))
            if self.weak:
                out.append((nxt._replace(since=t_done if waiting else None),
                            kind, good, late))
        return out

    def run(self, states: List[_Tiers], obs: List[tuple], rec,
            judge: bool) -> Optional[List[_Tiers]]:
        """`obs` in this order from every state of `states`; with `judge` a
        fault is counted and the replay goes on, without it the first one
        ends the try (None)."""
        v = self.v
        for o in obs:
            q = o[3]
            bounds = (int(rec["wall_send"][q]), int(rec["wall_recv"][q]))
            ts, td = float(rec["t_send"][q]), float(rec["t_done"][q])
            got = [g for st in states for g in self.step(st, o, bounds, ts, td)]
            if not got:
                if not judge:
                    return None
                st = states[0]
                v.bad("wrong_answers", key=self.hkey, rpc=q,
                      want=self._ask(st.hot, o[4])[0][:2], got=o[:2],
                      leaky=self.leaky, fresh_starts=st.fresh,
                      table_row=st.hot, cold_row=st.cold)
                states = [st._replace(hot=self._ask(st.hot, o[4])[1])
                          for st in states]
                continue
            # The least fault that explains the answer: none, then a
            # reset_time, then a merge that a row known to wait had not had.
            merged = [g for g in got if not (g[3] and g[0].certain)]
            keep = [g for g in merged if g[2]] or merged or got
            unmerged = not merged
            if not judge and (unmerged or not keep[0][2]):
                return None
            if judge:
                if not any(g[2] for g in keep):
                    v.bad("wrong_reset_time", key=self.hkey, rpc=q,
                          got=o[2] - self.ref.t0_ms, leaky=self.leaky,
                          born=keep[0][0].hot_born, state=keep[0][1])
                if unmerged:
                    v.bad("merged_late", key=self.hkey, rpc=q,
                          after_s=ts - keep[0][0].since, leaky=self.leaky)
                self.saw[keep[0][1] + "_answers"] += 1
            states = list(dict.fromkeys(g[0] for g in keep))
        return states


def tiered_ledger(a: Answers) -> tuple:
    """(hits the daemon admitted, keys it touched) over every answer of a
    run: the two sides of docs/tiering.md's bound in the form an operator
    checks it, admitted <= limit x (keys touched + demotes)."""
    under = (a.status == 0) & (a.err_len == 0)
    return int(a.hits[under].sum()), len(np.unique(a.key))


def most_checks_in_span(t_done: np.ndarray, sizes: np.ndarray,
                        span_s: float) -> int:
    """The most checks answered in any `span_s` seconds of a run: what the
    served path can have inserted into the table between the end of one
    tick of the tier's manager and the end of the next (a tick that starts
    on its interval and takes no longer than it), since a check inserts at
    most one row.  A tick leaves the table at or under its high-water mark,
    so high_water x slots plus this bounds the table at any time."""
    if not len(t_done):
        return 0
    by = np.argsort(t_done, kind="stable")
    t, upto = t_done[by], np.cumsum(sizes[by])
    first = np.searchsorted(t, t - span_s, side="left")
    before = np.where(first > 0, upto[first - 1], 0)
    return int((upto - before).max())


def _interleavings(obs: List[tuple]):
    """Every order of `obs` that keeps each RPC's own answers in theirs."""
    queues: Dict[int, List[tuple]] = {}
    for o in obs:
        queues.setdefault(o[3], []).append(o)
    qs = list(queues.values())

    def rec(at: List[int], out: List[tuple]):
        if len(out) == len(obs):
            yield list(out)
            return
        for i, q in enumerate(qs):
            if at[i] < len(q):
                at[i] += 1
                out.append(q[at[i] - 1])
                yield from rec(at, out)
                out.pop()
                at[i] -= 1

    yield from rec([0] * len(qs), [])


def replay_tiered(a: Answers, rec, uni: Universe, t0_ms: int, seed: int,
                  set_aside: np.ndarray, extra_crowded: np.ndarray,
                  v: Verdict, always: Optional[np.ndarray] = None) -> None:
    """`replay_sample` for a configuration whose table does not hold its
    universe: every answer on the sampled keys has to be what core/pymodel.py
    gives from one of the states docs/tiering.md's algebra allows (the
    module docstring has them).  Counts in `v.counts` are the names of
    TIERED_COUNTS, all held to 0; `v.notes` gains TIERED_SEEN."""
    order, keys, cuts = _sample(a, rec, uni, seed, set_aside, always, v)
    t_send, t_done = rec["t_send"], rec["t_done"]
    ref = _Reference(uni, t0_ms, extra_crowded)
    saw = dict.fromkeys(TIERED_SEEN, 0)
    deadline_s = uni.promote_deadline_ms / 1e3

    key = _TieredKeys(ref, deadline_s, v, saw)
    for c in range(len(cuts) - 1):
        rows = order[cuts[c]:cuts[c + 1]]
        k = int(keys[cuts[c]])
        hkey, leaky, reqs, weak = ref.start_key(k, a.hits[rows])
        # A key starts with the row the harness preloaded, in the table or
        # in the cold store.
        row = (float(uni.remaining0[k]) if leaky else int(uni.remaining0[k]),
               0)
        saw["keys_started_cold"] += bool(uni.cold[k])
        states = [_Tiers(cold=row) if uni.cold[k] else _Tiers(hot=row)]
        key.start(hkey, reqs, weak, leaky)
        for grp in _in_flight_groups(rows, a, t_send, t_done):
            obs, rpcs = _observed(grp, a, hkey, ref.model, reqs, v,
                                  linear=False)
            if 1 < len(rpcs) and len(obs) <= INTERLEAVE_MOST:
                for cand in _interleavings(obs):
                    if key.run(states, cand, rec, judge=False) is not None:
                        obs = cand
                        break
            states = key.run(states, obs, rec, judge=True)
        # The bound of docs/tiering.md, by the state that saw the most
        # fresh starts: no state the answers allow may break it.
        st = max(states, key=lambda s: s.fresh)
        if st.admitted > uni.limit * (1 + st.fresh):
            v.bad("admitted_beyond_bound", key=hkey, admitted=st.admitted,
                  fresh_starts=st.fresh)
    for name in TIERED_COUNTS:
        v.counts.setdefault(name, 0)
    v.notes.update(saw)
