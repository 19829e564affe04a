"""What the readers of the GLOBAL sync program's metrics share: its rows in
a trace reduction, and the bytes one launch moves over ICI.  (What it
moves through HBM is the compiler's own count, read by the program at
warm-up and shown in /debug/vars `global.engine.sync_program`.)"""
from __future__ import annotations

import re
from typing import Tuple


def sync_rows(trace: dict, regex: str) -> Tuple[int, float]:
    """(launches on all chips, seconds per chip) of the programs `regex`
    names in bench/lib/trace.py's `modules`."""
    pat = re.compile(regex)
    rows = [cs for name, cs in (trace.get("modules") or {}).items()
            if pat.search(name)]
    return sum(c for c, _s in rows), sum(s for _c, s in rows)


def ici_bytes(shards: int, delta_slots: int, psum_words_per_lane: int,
              gather_bytes_per_lane: int) -> float:
    """Bytes one chip sends over ICI for one launch: a ring all-reduce of
    the [shards, delta_slots] delta grid moves 2 (n - 1) / n of its bytes
    (`psum_words_per_lane` uint32 words a lane: each int64 field travels
    as four 16-bit limbs in uint32 lanes, _psum_mod64), the all_gather of
    the broadcast rows passes on n - 1 shares of delta_slots rows."""
    n = shards
    psum = 2.0 * (n - 1) / n * shards * delta_slots * psum_words_per_lane * 4
    gather = (n - 1) * delta_slots * gather_bytes_per_lane
    return psum + gather
