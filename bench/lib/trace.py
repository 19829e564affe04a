"""The reduction from a profiler trace (.xplane.pb) to numbers.

Run as a program — `python bench/lib/trace.py <trace_dir>` prints one JSON
object — because reading the file needs `jax.profiler.ProfileData` and the
harness's own process never imports JAX.  What a v5e trace holds (looked at
by hand first): one plane per chip, "/device:TPU:<n>", with a line
"XLA Modules" (one event per program launch, "jit_<name>(<hash>)") and a
line "XLA Ops" (one event per HLO op, named by its whole HLO text); the
host's threads are lines of the plane "/host:CPU".  Given several
directories, a cluster's trace a daemon, it prints bench/lib/cluster.py
`combine_traces` of their reductions.

  busy_s       per chip, the union of its "XLA Ops" intervals; averaged
               over the chips that ran anything.
  window_s     first device event start to last device event end, all chips:
               starting and stopping the profiler stalls the host for up to
               a second at either edge of the trace, which is the
               profiler's idle time, not the program's.
  modules      program launches: {short name: [count, seconds]}.
  ops          op time: {short name: [count, seconds]}, names cut to the
               HLO result name ("fusion.7", "while.36", "all-reduce.1").
  idle_gaps    the longest gaps of chip 0, over every host thread: each
               named by the shortest of the program's own `gub.*` stages
               (runtime/tracing.py) that covers the whole gap, else by the
               host event that overlaps it most (the most specific on a
               tie) — a stage outranks the runtime events nested in it.
  host_stages  every `gub.*` event traced: {name: [count, seconds]}.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)
MAX_GAPS = 2000
STAGE_PREFIX = "gub."


def short_op(name: str) -> str:
    """'%fusion.7 = f32[...] fusion(...)' -> 'fusion.7'."""
    return stable(name.split(" = ", 1)[0].lstrip("%"))


def short_module(name: str) -> str:
    """'jit_apply_batch_packed_q(1234)' -> 'jit_apply_batch_packed_q'."""
    return stable(name.split("(", 1)[0])


def stable(name: str) -> str:
    """A short name that survives a refactor: no line numbers, no
    characters a metric name may not have."""
    name = re.sub(r"\.py:\d+", ".py", name)
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", name).strip("_")[:60] or "unnamed"


def union_seconds(starts: np.ndarray, ends: np.ndarray) -> Tuple[
        float, np.ndarray, np.ndarray]:
    """Length of the union of [start, end) intervals (ns in, seconds out)
    and the merged intervals."""
    if not len(starts):
        return 0.0, starts, ends
    order = np.argsort(starts)
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > reach[:-1]]
    ms = s[new]
    me = reach[np.r_[new[1:], True]]
    return float((me - ms).sum()) / 1e9, ms, me


def _events(line) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, st, du = [], [], []
    for ev in line.events:
        names.append(ev.name)
        st.append(ev.start_ns)
        du.append(ev.duration_ns)
    s = np.array(st, dtype=np.float64)
    return names, s, s + np.array(du, dtype=np.float64)


def _totals(names, starts, ends, shorten) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for n, s, e in zip(names, starts, ends):
        row = out.setdefault(shorten(n), [0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
    return out


def name_gaps(gap_s, gap_e, host) -> Dict[str, float]:
    """Seconds of idle gap by what the host was doing: the shortest stage
    of the program's own that covers the gap, else the host event
    overlapping it most."""
    h_names, h_s, h_e = host
    out: Dict[str, float] = {}
    keep = np.argsort(gap_e - gap_s)[::-1][:MAX_GAPS]
    h_len = h_e - h_s
    is_stage = np.array([n.startswith(STAGE_PREFIX) for n in h_names],
                        dtype=bool)
    for g in keep:
        a, b = gap_s[g], gap_e[g]
        name = "no_host_span"
        if len(h_s):
            ov = np.minimum(h_e, b) - np.maximum(h_s, a)
            best = ov.max()
            covering = np.flatnonzero(is_stage & (ov >= (b - a) * 0.999))
            if len(covering):
                name = stable(h_names[covering[np.argmin(h_len[covering])]])
            elif best > 0:
                cand = np.flatnonzero(ov >= best * 0.999)
                name = stable(h_names[cand[np.argmin(h_len[cand])]])
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    t_min, t_max = np.inf, -np.inf
    devices = []
    host = ([], np.zeros(0), np.zeros(0))
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None and plane.name != "/host:CPU":
            continue
        if m is None:
            # Every thread is a line, and every Python thread's line has
            # the same name: take them by position, not by name.
            evs = [_events(ln) for ln in plane.lines]
            if evs:
                host = (
                    [n for v in evs for n in v[0]],
                    np.concatenate([v[1] for v in evs]),
                    np.concatenate([v[2] for v in evs]),
                )
            continue
        lines = {ln.name: _events(ln) for ln in plane.lines}
        ops = lines.get("XLA Ops") or ([], np.zeros(0), np.zeros(0))
        mods = lines.get("XLA Modules") or ([], np.zeros(0), np.zeros(0))
        busy, ms, me = union_seconds(ops[1], ops[2])
        if len(ms):
            t_min, t_max = min(t_min, ms[0]), max(t_max, me[-1])
        devices.append({
            "id": int(m.group(1)), "busy_s": busy, "merged": (ms, me),
            "ops": _totals(*ops, short_op),
            "modules": _totals(*mods, short_module),
        })
    devices.sort(key=lambda d: d["id"])
    used = [d for d in devices if d["busy_s"] > 0]
    window_s = float(t_max - t_min) / 1e9 if used else 0.0
    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    for d in used:
        for src, dst in ((d["ops"], ops), (d["modules"], modules)):
            for k, (c, s) in src.items():
                row = dst.setdefault(k, [0, 0.0])
                row[0] += c
                row[1] += s
    n = max(1, len(used))
    for table in (ops, modules):
        for row in table.values():
            row[1] /= n      # seconds per chip, like busy_s
    gaps: Dict[str, float] = {}
    if used:
        ms, me = used[0]["merged"]
        # A gap runs from one merged interval's end to the next one's start.
        gaps = name_gaps(me[:-1], ms[1:], host) if len(ms) > 1 else {}
    coll = sum(
        s for k, (_c, s) in ops.items() if k.startswith(COLLECTIVES)
    )
    staged = [i for i, n in enumerate(host[0]) if n.startswith(STAGE_PREFIX)]
    top = lambda t: sorted(  # noqa: E731
        ([k, v[1]] for k, v in t.items()), key=lambda kv: -kv[1]
    )[:10]
    return {
        "chips_traced": len(used),
        "busy_s": sum(d["busy_s"] for d in used) / n if used else 0.0,
        "window_s": window_s,
        "collective_s": coll,
        "modules": modules,
        "host_stages": _totals(
            [host[0][i] for i in staged], host[1][staged], host[2][staged],
            stable,
        ),
        "device_ops": top(ops),
        "idle_gaps": sorted(
            ([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1]
        )[:10],
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


if __name__ == "__main__":
    reduced = [
        reduce_xplane(find_xplane(t) if os.path.isdir(t) else t)
        for t in sys.argv[1:]
    ]
    if len(reduced) > 1:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from lib.cluster import combine_traces

        reduced = [combine_traces(reduced)]
    print(json.dumps(reduced[0]))
