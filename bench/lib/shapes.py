"""Which response-fetch programs one drain of a cell's traffic can ask for.

The program's coalescer merges every RPC queued at a drain; the merged
checks are packed into rounds of at most `batch` lanes per shard
(native assign_rounds: lanes fill round 0 first, a key's next occurrence
goes to a later round), each round runs at the smallest compiled tier that
holds its lanes, and the responses of all rounds come back through one
`fetch_ravel`, which compiles one concatenate per SEQUENCE of round
shapes.  What bounds a drain is the number of RPCs that can be outstanding
at once: the closed loop's in-flight count, the open loop's cap.  From that
bound this module lists every sequence that can occur, so set-up can warm
all of them — not the ones a steady state happens to use.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from lib.spec import can_peek


def outstanding_bound(traffic: dict) -> int:
    """Most RPCs the generator ever has outstanding."""
    if traffic["loop"] == "closed":
        return int(traffic["in_flight"])
    return int(traffic["outstanding_cap"])


def round_lane_bounds(traffic: dict, universe: dict, batch: int,
                      smallest_tier: int) -> Dict[str, List[int]]:
    """Per lane of the program, the most lanes round r of one drain can
    hold on one shard (all keys on one shard is the worst case).

    mach    plain keys.  Where every check has hits > 0, every duplicate
            group of this traffic (same limit, duration and algorithm) is
            served by the host cascade from ONE read lane, so rounds
            beyond the first exist only when a shard's distinct keys
            exceed `batch`; one spare small round is warmed beyond that.
            Where the traffic file can send hits 0, a duplicate group that
            holds a peek is NOT cascaded (fastpath._plan_cascade): each
            occurrence takes a lane, occurrence k in a later round than
            k-1, so a drain can have as many rounds as checks and round r
            holds at most total/(r+1) lanes (rounds never grow, so r+1
            rounds of c lanes need (r+1) x c checks).
    engine  GLOBAL keys (mesh).  An RPC's duplicates share a lane; the same
            key in r+1 different RPCs of a drain reaches round r, so round
            r holds at most total/(r+1) lanes.
    """
    rpcs = outstanding_bound(traffic)
    per_rpc = int(traffic["checks_per_rpc"]["max"])
    n_global = int(universe.get("global_keys", 0))
    g_per_rpc = int(traffic.get("global_per_rpc", 0)) if n_global else 0
    total = rpcs * (per_rpc - g_per_rpc)
    if can_peek(traffic):
        mach = [min(batch, total // (r + 1)) for r in range(total)]
    else:
        mach = []
        while total > 0:
            mach.append(min(batch, total))
            total -= batch
        mach.append(smallest_tier)
    lanes = {"mach": mach}
    if g_per_rpc:
        g_total = rpcs * g_per_rpc
        lanes["engine"] = [
            min(batch, n_global, g_total // (r + 1)) for r in range(rpcs)
        ]
    return lanes


def tier_sequences(round_lanes: Sequence[int],
                   tiers: Sequence[int]) -> List[Tuple[int, ...]]:
    """Every sequence of round tiers, two rounds or longer (one round is
    fetched without a concatenate), that rounds holding at most
    `round_lanes[r]` lanes can produce.  A round's tier is the smallest
    that holds its lanes, so tier t needs more lanes than the tier below
    it; occupancy never grows from one round to the next."""
    tiers = sorted(tiers)
    below = {t: (tiers[i - 1] if i else 0) for i, t in enumerate(tiers)}
    out: List[Tuple[int, ...]] = []
    # Depth first without recursion: a peeking traffic's bound is hundreds
    # of rounds long.
    stack: List[Tuple[int, ...]] = [()]
    while stack:
        prefix = stack.pop()
        r = len(prefix)
        if r >= 2:
            out.append(prefix)
        if r >= len(round_lanes):
            continue
        for t in reversed(tiers):
            if round_lanes[r] > below[t] and (not prefix or t <= prefix[-1]):
                stack.append(prefix + (t,))
    return out
