"""A deployment of several daemons judged as one: how each daemon's chip is
pinned, how the daemons' snapshots become one, and what the plan says of
the forward hop.  Pure functions, so that bench/tests can hold them
without a daemon.  One daemon (`peers` absent) never comes here: its
snapshot is the daemon's own, to the byte.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def pin_env(k: int, port: int) -> Dict[str, str]:
    """The environment that gives a process chip k of its host and no
    other, as a deployment of one daemon a chip would set it (found on
    the v5e by bench/witness/pin_chips.py): libtpu reads these before it
    opens a device, so they are set before the process starts."""
    return {
        "TPU_VISIBLE_CHIPS": str(k),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(port),
    }


def _sum_leaf(key: str, values: list):
    first = values[0]
    if isinstance(first, dict):
        return sum_vars([v for v in values if isinstance(v, dict)])
    if isinstance(first, bool) or not isinstance(first, (int, float)):
        return first            # a name, a flag, a list: the first daemon's
    numbers = [v for v in values
               if isinstance(v, (int, float)) and not isinstance(v, bool)]
    return max(numbers) if key.endswith("_max") else sum(numbers)


def sum_vars(trees: Sequence[dict]) -> dict:
    """One /debug/vars tree of several daemons': every number summed —
    a counter, a stage's `count` and `ms_total`, an occupancy — except a
    `*_max`, which is the largest, so that every ratio of two sums is a
    mean over the cluster.  What is no number is the first daemon's; what
    must hold on EVERY daemon is read from each tree, not from this."""
    out: dict = {}
    for t in trees:
        for k in t:
            if k not in out:
                out[k] = _sum_leaf(k, [x[k] for x in trees if k in x])
    return out


def checks_by_owner(plan, owner_of_key: np.ndarray, peers: int) -> np.ndarray:
    """int64[len(plan), peers]: how many checks of each planned RPC each
    daemon owns."""
    sizes = np.diff(plan.offsets)
    rpc = np.repeat(np.arange(len(plan), dtype=np.int64), sizes)
    flat = rpc * peers + owner_of_key[plan.key_index].astype(np.int64)
    return np.bincount(flat, minlength=len(plan) * peers).reshape(
        len(plan), peers)


def planned_hops(plan, owner_of_key: np.ndarray, peers: int,
                 sent_plan_idx: np.ndarray,
                 sent_entry: np.ndarray) -> Dict[str, List[int]]:
    """Of the RPCs the generator sent (which plan entry, which daemon it
    entered by), per ENTRY daemon: the checks it owns itself (`local`) and
    the checks another daemon owns (`forward`) — what the daemons' own
    `gubernator_getratelimit_counter{calltype=...}` must have grown by."""
    by_owner = checks_by_owner(plan, owner_of_key, peers)[sent_plan_idx]
    own = by_owner[np.arange(len(sent_plan_idx)), sent_entry]
    total = by_owner.sum(axis=1)
    return {
        "local": np.bincount(sent_entry, weights=own,
                             minlength=peers).astype(np.int64).tolist(),
        "forward": np.bincount(sent_entry, weights=total - own,
                               minlength=peers).astype(np.int64).tolist(),
    }


def combine_traces(reduced: Sequence[dict]) -> dict:
    """One reduction (bench/lib/trace.py) of several daemons' traces, a
    trace a daemon, a chip a trace: the MEAN daemon.  `busy_s` is the mean
    over the chips that ran anything and `window_s` the mean of their
    windows (the daemons start and stop their profilers at their own
    moments), so the idle share is 1 - sum(busy) / sum(window); program
    launches, op seconds and host stages are a daemon's mean too, and
    `chips_traced` is 1 — a daemon's programs run on its chip alone, where
    a mesh program's launch is counted once a chip.  Every chip's own
    reading is listed; the idle gaps are the first daemon's chip's."""
    used = [r for r in reduced if r["chips_traced"]]
    n = max(1, len(used))

    def mean_rows(key: str) -> dict:
        out: dict = {}
        for r in used:
            for name, (count, seconds) in r[key].items():
                row = out.setdefault(name, [0.0, 0.0])
                row[0] += count / n
                row[1] += seconds / n
        return out

    ops: dict = {}
    for r in used:
        for name, seconds in r["device_ops"]:
            ops[name] = ops.get(name, 0.0) + seconds / n
    return {
        "chips_traced": 1 if used else 0,
        "daemons_traced": len(used),
        "busy_s": sum(r["busy_s"] for r in used) / n,
        "window_s": sum(r["window_s"] for r in used) / n,
        "busy_s_by_chip": [r["busy_s"] for r in reduced],
        "window_s_by_chip": [r["window_s"] for r in reduced],
        "collective_s": sum(r["collective_s"] for r in used) / n,
        "modules": mean_rows("modules"),
        "host_stages": mean_rows("host_stages"),
        "device_ops": sorted(
            ([k, v] for k, v in ops.items()), key=lambda kv: -kv[1]
        )[:10],
        "idle_gaps": reduced[0]["idle_gaps"] if reduced else [],
    }
