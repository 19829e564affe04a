"""Per-layer metrics from their data files.

A metric's file says where its number comes from; the harness flattens
everything a run observed into one namespace and evaluates the file:

  vars:<dotted path>      /debug/vars (a `*` sums over the keys there)
  client:<stat>           the load generator's record of the window
  trace:<stat>            bench/lib/trace.py's reduction (traced runs)
  run:<stat>              what the harness itself timed
  {"metrics": <series>, "labels": {...}}   /metrics, summed over the series
                          whose labels include these

`read.kind` "ratio": scale * sum(num) / sum(den), over the window's
difference when "delta" is true.  "code": read(ctx, spec) of
bench/readers/<name>.py, or of the reader the file names under
`read.reader`, so that a metric of another cell's kind brings no code.  A reader that finds nothing to read returns nothing and
the metric is left out of the line.
"""
from __future__ import annotations

import importlib.util
import os
import re
from typing import Dict, Optional

from lib import spec as spec_mod

_SERIES = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str):
    """[(name, {label: value}, float)] of a /metrics page."""
    out = []
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SERIES.match(line)
        if m:
            try:
                out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                            float(m.group(3))))
            except ValueError:
                pass
    return out


def lookup_vars(tree, path: str) -> Optional[float]:
    """Sum of the numbers at `path` in a /debug/vars tree; `*` is every
    key of that level.  None if nothing is there."""
    nodes = [tree]
    for part in path.split("."):
        nxt = []
        for n in nodes:
            if not isinstance(n, dict):
                continue
            if part == "*":
                nxt.extend(n.values())
            elif part in n:
                nxt.append(n[part])
        nodes = nxt
    vals = [n for n in nodes if isinstance(n, (int, float))
            and not isinstance(n, bool)]
    return float(sum(vals)) if vals else None


def _term(term, snap: dict) -> Optional[float]:
    if isinstance(term, dict):
        want = term.get("labels", {})
        vals = [v for n, lab, v in snap["metrics"] if n == term["metrics"]
                and all(lab.get(k) == x for k, x in want.items())]
        return float(sum(vals)) if vals else None
    kind, _, path = term.partition(":")
    if kind == "vars":
        return lookup_vars(snap["vars"], path)
    return snap["flat"].get(term)


def _side(terms, snaps, delta: bool) -> Optional[float]:
    total = 0.0
    for t in terms:
        b = _term(t, snaps[1])
        a = _term(t, snaps[0]) if delta else 0.0
        if a is None or b is None:
            return None
        total += b - a
    return total


def evaluate(spec: dict, ctx: dict) -> Optional[float]:
    """The metric's value, or None when there is nothing to read."""
    read = spec["read"]
    if read["kind"] == "code":
        path = spec_mod.reader_path(spec)
        mod_spec = importlib.util.spec_from_file_location(
            "bench_reader_" + re.sub(r"\W", "_", spec["name"]), path
        )
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx, spec)
    snaps = ctx["snaps"]
    delta = bool(read.get("delta"))
    num = _side(read["num"], snaps, delta)
    den = _side(read["den"], snaps, delta) if read.get("den") else 1.0
    if num is None or den is None or den == 0 or num != num:
        return None         # nothing there, or a reading that is no number
    return float(read.get("scale", 1.0)) * num / den
