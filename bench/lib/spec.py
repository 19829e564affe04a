"""BENCHMARK.json and the benchmark's data files: loading, and the checks
the harness makes on them before a run (bench/tests runs the same checks
over every file).  Everything that belongs to one configuration, one
traffic mix or one per-layer metric is found by its name:

  bench/configs/<config>.json          bench/traffic/<traffic>.json
  bench/layer_metrics/<metric>.json    bench/readers/<metric>.py (code)
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List

from lib import ring

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
METRIC_KEYS = {"name", "unit", "better", "source", "layer", "moves",
               "workloads"}


class SpecError(ValueError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(held_out: bool = False) -> dict:
    """BENCHMARK.json; with `held_out`, also the cells of
    bench/held_out.json that it does not name: built and judged, and kept
    out of the benchmark with the reason given there.  Such a cell reports
    what the cell it names under `reports_as` reports."""
    bm = load_json(os.path.join(REPO, "BENCHMARK.json"))
    if not held_out:
        return bm
    more = load_json(os.path.join(BENCH, "held_out.json"))
    have = {c["name"] for c in bm["configs"]}
    bm["configs"] += [c for c in more["configs"] if c["name"] not in have]
    have = {w["name"] for w in bm["workloads"]}
    for w in more["workloads"]:
        like = w["reports_as"]
        if w["name"] in have:
            continue
        bm["workloads"].append({k: w[k] for k in (
            "name", "config", "traffic", "chips", "why")})
        for m in bm["end_to_end"] + bm["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [w["name"]]
    return bm


def config_path(bm: dict, config: str) -> str:
    for c in bm["configs"]:
        if c["name"] == config:
            return os.path.join(REPO, c["file"])
    raise SpecError(f"no configuration {config!r} in BENCHMARK.json")


def traffic_path(traffic: str) -> str:
    return os.path.join(BENCH, "traffic", traffic + ".json")


def layer_metric_path(name: str) -> str:
    return os.path.join(BENCH, "layer_metrics", name + ".json")


def reader_path(m: dict) -> str:
    """The code of a metric whose reader is code: the file its data file
    names under `read.reader`, else the one of its own name."""
    return os.path.join(
        BENCH, "readers", m["read"].get("reader", m["name"]) + ".py"
    )


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bm: dict, group: str, cell: str) -> List[dict]:
    """The metrics of `group` ("end_to_end" / "per_layer") a cell reports."""
    return [
        m for m in bm[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise SpecError(msg)


KEY_FORMS = ('{"distribution": "uniform"}', '{"distribution": "zipfian", '
             '"constant": 0 < c < 1, "scramble": true | false}')
HITS_FORMS = ("a whole number >= 0", '{"values": [whole numbers >= 0], '
              '"weights": [whole numbers >= 1]} of equal length')
ARRIVAL_FORMS = ('{"process": "poisson", "rate_rpc_per_s": r > 0}',
                 '{"process": "square", "period_s", "burst_s", '
                 '"burst_start_s" (0 if absent), "base_rate_rpc_per_s", '
                 '"burst_rate_rpc_per_s"} with the burst inside the period '
                 "and warm_in_s a whole number of periods")


CLOCK_FORMS = ('absent or "frozen": the clock of the reference stands at the '
               "preload stamp, for windows that no run outlasts",
               '"moving": windows elapse inside a run, and the reference is '
               "replayed under the clock each answer carries")


ALGORITHM_FORMS = ('absent or "mixed": bit 4 of a key id picks token or '
                   'leaky, half each', '"token": every key a token bucket')
PEERS_FORMS = ('absent or 1: one daemon on all of the configuration\'s chips',
               '"peers": N, N = "chips" > 1: N daemons, a chip each, on one '
               "consistent-hash ring of 512 vnodes a peer; then "
               "universe.shards is 1, universe.global_keys 0 or absent "
               "(GLOBAL over gRPC is not judged yet), daemon.GUBER_PEERS "
               "lists N distinct advertise addresses 127.0.0.1:<port>, and "
               'daemon.GUBER_PEER_PICKER_HASH is "xx" (the ring hash is the '
               "table fingerprint; no configuration needs another yet)")
RESIDENCY_FORMS = ('absent or "table": the device table holds the universe, '
                   "min(arrivals, ways) rows a bucket, and the rest was "
                   "evicted before the run",
                   '"tiered": the table does not hold its universe '
                   "(docs/tiering.md): it starts at its low-water mark and "
                   "every other key as a row of the cold store; then "
                   "daemon.GUBER_TIER_ENABLED is \"true\", "
                   "GUBER_TIER_HIGH_WATER and GUBER_TIER_LOW_WATER are stated "
                   "with 0 < low < high <= 1, GUBER_TIER_COLD_CAPACITY is at "
                   "least universe.keys less the rows the table starts with "
                   "(floor(low x slots)), GUBER_TIER_INTERVAL is plain seconds "
                   "and background_timers_s.tier_tick says the same, "
                   "universe.promote_deadline_ms is a number above 0 and, "
                   "for now, universe.shards is 1, peers 1, "
                   "universe.global_keys 0 or absent and universe.clock frozen")
_LOOPBACK = re.compile(r"^127\.0\.0\.1:([1-9][0-9]{3,4})$")
# No run may outlast this (the contract: 360 s, 1200 s where it compiles).
LONGEST_RUN_MS = 1_200_000


def _whole(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool)


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def check_keys(k, where: str) -> None:
    known = f"{where}: keys is none of the known forms: " + "; ".join(
        KEY_FORMS)
    _need(isinstance(k, dict), known)
    kind = k.get("distribution")
    if kind == "uniform":
        _need(set(k) == {"distribution"}, known)
    elif kind == "zipfian":
        _need(set(k) <= {"distribution", "constant", "scramble"}
              and _number(k.get("constant")) and 0 < k["constant"] < 1
              and isinstance(k.get("scramble", False), bool), known)
    else:
        raise SpecError(known)


def check_hits(h, where: str) -> None:
    known = f"{where}: hits is none of the known forms: " + "; ".join(
        HITS_FORMS)
    if isinstance(h, dict):
        v, w = h.get("values"), h.get("weights")
        _need(set(h) == {"values", "weights"} and isinstance(v, list)
              and isinstance(w, list) and len(v) == len(w) >= 1
              and all(_whole(x) and x >= 0 for x in v)
              and all(_whole(x) and x >= 1 for x in w)
              and len(set(v)) == len(v), known)
    else:
        _need(_whole(h) and h >= 0, known)


def can_peek(t: dict) -> bool:
    """Whether the traffic can send `hits` 0: a duplicate group holding a
    peek is not served by the host cascade (bench/lib/shapes.py)."""
    h = t.get("hits", 1)
    return 0 in (h["values"] if isinstance(h, dict) else [h])


def check_arrivals(t: dict, where: str) -> None:
    known = f"{where}: arrivals is none of the known forms: " + "; ".join(
        ARRIVAL_FORMS)
    a = t.get("arrivals")
    _need(isinstance(a, dict), known)
    if a.get("process") == "poisson":
        _need(set(a) == {"process", "rate_rpc_per_s"}
              and _number(a["rate_rpc_per_s"]) and a["rate_rpc_per_s"] > 0,
              known)
    elif a.get("process") == "square":
        need = {"process", "period_s", "burst_s", "base_rate_rpc_per_s",
                "burst_rate_rpc_per_s"}
        _need(need <= set(a) <= need | {"burst_start_s"}
              and all(_number(a[k]) and a[k] > 0 for k in need - {"process"})
              and _number(a.get("burst_start_s", 0))
              and a.get("burst_start_s", 0) >= 0
              and a.get("burst_start_s", 0) + a["burst_s"] <= a["period_s"],
              known)
        periods = float(t.get("warm_in_s", 0)) / a["period_s"]
        _need(periods >= 1 and periods == int(periods), known)
    else:
        raise SpecError(known)


def check_traffic(t: dict, where: str) -> None:
    _need(t.get("loop") in ("open", "closed"), f"{where}: loop kind")
    _need(float(t.get("deadline_s", 0)) >= 5.0,
          f"{where}: a client deadline under 5 s makes a slow answer a "
          "failed one")
    c = t.get("checks_per_rpc", {})
    _need(1 <= int(c.get("min", 0)) <= int(c.get("max", 0)),
          f"{where}: checks_per_rpc")
    _need(int(t.get("connections", 0)) >= 1, f"{where}: connections")
    _need(float(t.get("warm_in_s", 0)) > 0, f"{where}: warm_in_s")
    check_keys(t.get("keys"), where)
    check_hits(t.get("hits", 1), where)
    if t["loop"] == "open":
        _need(int(t.get("outstanding_cap", 0)) >= 1,
              f"{where}: an open loop needs an outstanding cap")
        check_arrivals(t, where)
    else:
        _need(int(t.get("in_flight", 0)) >= 1, f"{where}: in_flight")
        _need(float(t.get("pool_rpc_per_s", 0)) > 0,
              f"{where}: pool_rpc_per_s")


def _check_datum(u: dict, key: str, values, forms, where: str) -> None:
    """A datum of a `universe` group that may be absent (= `values[0]`)."""
    _need(u.get(key, values[0]) in values,
          f"{where}: universe.{key} is none of the known forms: "
          + "; ".join(forms))


def clock_moves(u: dict) -> bool:
    """Whether a configuration's `universe` group says that its windows
    elapse inside a run (bench/lib/oracle.py `replay_moving`)."""
    return u.get("clock", "frozen") == "moving"


def tiered(u: dict) -> bool:
    """Whether a configuration's `universe` group says that the table does
    not hold it (bench/lib/oracle.py `replay_tiered`)."""
    return u.get("residency", "table") == "tiered"


def tier_marks(c: dict) -> tuple:
    """(high, low) water marks of a tiered configuration, as shares of the
    table's slots."""
    d = c["daemon"]
    return float(d["GUBER_TIER_HIGH_WATER"]), float(d["GUBER_TIER_LOW_WATER"])


def table_rows_at_start(c: dict) -> int:
    """Rows a tiered configuration's table starts with: its low-water mark,
    or every key where the universe is smaller."""
    low = tier_marks(c)[1]
    return min(int(c["universe"]["keys"]),
               int(low * int(c["daemon"]["GUBER_TPU_NUM_SLOTS"])))


def check_residency(c: dict, where: str) -> None:
    known = f"{where}: universe.residency is none of the known forms: " \
        + "; ".join(RESIDENCY_FORMS)
    u, d = c["universe"], c["daemon"]
    kind = u.get("residency", "table")
    _need(kind in ("table", "tiered"), known)
    if kind == "table":
        return
    _need(d.get("GUBER_TIER_ENABLED") == "true", known)
    try:
        high, low = tier_marks(c)
        cold = int(d["GUBER_TIER_COLD_CAPACITY"])
        slots = int(d["GUBER_TPU_NUM_SLOTS"])
        tick = float(d["GUBER_TIER_INTERVAL"])
    except (KeyError, ValueError) as e:
        raise SpecError(known) from e
    _need(0 < low < high <= 1 and slots >= 1, known)
    _need(0 < tick == c["background_timers_s"].get("tier_tick"), known)
    _need(cold >= int(u["keys"]) - table_rows_at_start(c), known)
    _need(_number(u.get("promote_deadline_ms"))
          and u["promote_deadline_ms"] > 0, known)
    _need(int(u["shards"]) == 1 and peers_of(c) == 1
          and int(u.get("global_keys", 0)) == 0 and not clock_moves(u), known)


def peers_of(c: dict) -> int:
    """How many daemons a configuration runs (absent: one)."""
    return c.get("peers", 1)


def peer_addresses(c: dict) -> List[str]:
    """The advertise addresses of a cluster's daemons, in ring order."""
    return [a.strip() for a in c["daemon"]["GUBER_PEERS"].split(",")]


def ring_of(c: dict):
    """The ring (bench/lib/ring.py) a configuration states, or None where
    it runs one daemon."""
    if peers_of(c) == 1:
        return None
    return ring.build(peer_addresses(c), c["daemon"]["GUBER_PEER_PICKER_HASH"])


def check_peers(c: dict, where: str) -> None:
    known = f"{where}: peers is none of the known forms: " + "; ".join(
        PEERS_FORMS)
    n = peers_of(c)
    _need(_whole(n) and n in (1, c["chips"]), known)
    if n == 1:
        _need("GUBER_PEERS" not in c["daemon"], known)
        return
    u, d = c["universe"], c["daemon"]
    _need(int(u["shards"]) == 1 and int(u.get("global_keys", 0)) == 0, known)
    _need(isinstance(d.get("GUBER_PEERS"), str)
          and d.get("GUBER_PEER_PICKER_HASH") == "xx", known)
    addrs = peer_addresses(c)
    _need(len(addrs) == n == len(set(addrs))
          and all(_LOOPBACK.match(a) and int(a.rpartition(":")[2]) < 65536
                  for a in addrs), known)


def check_config(c: dict, where: str) -> None:
    for k in ("source", "chips", "daemon", "universe", "guarantees",
              "reduced", "assumed", "background_timers_s"):
        _need(k in c, f"{where}: missing {k!r}")
    u = c["universe"]
    for k in ("keys", "ways", "shards", "limit", "duration_ms",
              "preload_remaining_below"):
        _need(int(u.get(k, 0)) >= 1, f"{where}: universe.{k}")
    _check_datum(u, "clock", ("frozen", "moving"), CLOCK_FORMS, where)
    _check_datum(u, "algorithm", ("mixed", "token"), ALGORITHM_FORMS, where)
    # The frozen comparison cannot see a window end or a token leak back:
    # where either can happen inside a run the configuration says "moving".
    leaks = u.get("algorithm", "mixed") == "mixed"
    shortest = int(u["duration_ms"]) / (int(u["limit"]) if leaks else 1)
    _need(clock_moves(u) or shortest >= LONGEST_RUN_MS,
          f"{where}: universe.clock has to say \"moving\": a "
          + ("token leaks back" if leaks else "window ends")
          + f" every {shortest:g} ms, inside a run of up to "
          f"{LONGEST_RUN_MS} ms, and the frozen comparison cannot see it")
    _need(c["chips"] in (1, 4) and int(u["shards"]) in (1, c["chips"]),
          f"{where}: chips/shards")
    _need(all(k.startswith("GUBER_") for k in c["daemon"]),
          f"{where}: daemon settings are GUBER_* variables")
    check_peers(c, where)
    check_residency(c, where)


def check_layer_metric(m: dict, where: str) -> None:
    for k in ("name", "layer", "unit", "better", "source", "moves",
              "read", "what"):
        _need(k in m, f"{where}: missing {k!r}")
    _need("workloads" not in m, f"{where}: BENCHMARK.json alone says "
          "which cells report a metric")
    kind = m["read"].get("kind")
    _need(kind in ("ratio", "code"), f"{where}: read.kind")
    if kind == "ratio":
        _need(bool(m["read"].get("num")), f"{where}: read.num")
    else:
        _need(os.path.isfile(reader_path(m)),
              f"{where}: no {os.path.relpath(reader_path(m), REPO)}")


def check_benchmark(bm: dict) -> None:
    """The contract's static rules that the harness depends on, and the
    agreement between BENCHMARK.json and the data files."""
    _need(set(bm) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json: keys")
    names: Dict[str, set] = {"config": set(), "cell": set(), "metric": set()}
    for c in bm["configs"]:
        _need(NAME.match(c["name"]) and c["name"] not in names["config"],
              f"configuration name {c['name']!r}")
        names["config"].add(c["name"])
        _need(c["file"].startswith(tuple(p + "/" for p in bm["paths"])),
              f"{c['name']}: file outside paths")
        cfg = load_json(os.path.join(REPO, c["file"]))
        check_config(cfg, c["file"])
        _need(cfg["source"] == c["source"], f"{c['name']}: source differs")
        _need(cfg["reduced"] == c["reduced"], f"{c['name']}: reduced differs")
    pairs = set()
    four = 0
    for w in bm["workloads"]:
        _need(NAME.match(w["name"]) and w["name"] not in names["cell"],
              f"workload name {w['name']!r}")
        names["cell"].add(w["name"])
        _need(w["config"] in names["config"], f"{w['name']}: config")
        _need(NAME.match(w["traffic"]), f"{w['name']}: traffic name")
        _need((w["config"], w["traffic"]) not in pairs,
              f"{w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        _need(len(w["why"]) <= 200 and "\n" not in w["why"],
              f"{w['name']}: why")
        check_traffic(load_json(traffic_path(w["traffic"])),
                      "traffic/" + w["traffic"])
        cfg = load_json(config_path(bm, w["config"]))
        _need(cfg["chips"] == w["chips"], f"{w['name']}: chips")
        four += w["chips"] == 4
    _need(four <= max(1, len(bm["workloads"]) // 2), "too many 4-chip cells")
    e2e = set()
    for m in bm["end_to_end"] + bm["per_layer"]:
        _need(NAME.match(m["name"]) and m["name"] not in names["metric"],
              f"metric name {m['name']!r}")
        names["metric"].add(m["name"])
        _need(UNIT.match(m["unit"]), f"{m['name']}: unit {m['unit']!r}")
        _need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        _need(m["source"] in SOURCES, f"{m['name']}: source")
        for cell in m.get("workloads", []):
            _need(cell in names["cell"], f"{m['name']}: cell {cell!r}")
    for m in bm["end_to_end"]:
        _need(set(m) <= {"name", "unit", "better", "bound", "source",
                         "workloads"}, f"{m['name']}: keys")
        _need(0 < m["bound"] <= 0.25, f"{m['name']}: bound")
        _need(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: end-to-end source")
        e2e.add(m["name"])
    _need("setup_s" in e2e, "no setup_s")
    for m in bm["per_layer"]:
        _need(set(m) <= METRIC_KEYS, f"{m['name']}: keys")
        _need(m["moves"] in e2e, f"{m['name']}: moves {m['moves']!r}")
        spec = load_json(layer_metric_path(m["name"]))
        check_layer_metric(spec, "layer_metrics/" + m["name"])
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            _need(spec[k] == m.get(k), f"{m['name']}: {k} differs from "
                  "its data file")
        for cell in m.get("workloads", []):
            _need(any(e["name"] == m["moves"] for e in
                      metrics_of(bm, "end_to_end", cell)),
                  f"{m['name']}: {cell} does not report {m['moves']}")
    for w in bm["workloads"]:
        mine = metrics_of(bm, "end_to_end", w["name"])
        _need(len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine),
              f"{w['name']}: needs setup_s and one more end-to-end metric")
        _need(metrics_of(bm, "per_layer", w["name"]),
              f"{w['name']}: no per-layer metric")
