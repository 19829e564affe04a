"""What one decision must move through HBM, from shapes alone, and the
table of peaks.  Kept here so that a PR that claims a gain cannot change
what its gain is measured against.

ops/step.py serves a check by (1) probing its bucket: the `ways`
candidates' key, expire_at and touched columns are gathered to find the
row or the victim; (2) gathering the row's ten state columns; (3)
scattering all twelve columns back; plus the request lane in (12 int64)
and the response lane out (9 int64).  Every column is read or written
once per decision; nothing here counts the emulated-int64 arithmetic,
which is compute.
"""
from __future__ import annotations

import json
import os

# ops/state.SlotTable column widths in bytes.
COLUMNS = {
    "key": 8, "algo": 4, "kind": 4, "limit": 8, "duration": 8,
    "remaining": 8, "remaining_f": 8, "t0": 8, "status": 4, "burst": 8,
    "expire_at": 8, "touched": 8,
}
PROBE_COLUMNS = ("key", "expire_at", "touched")
GATHER_COLUMNS = (
    "algo", "kind", "limit", "duration", "remaining", "remaining_f", "t0",
    "status", "burst", "expire_at",
)
REQUEST_LANE_BYTES = 12 * 8
RESPONSE_LANE_BYTES = 9 * 8


def bytes_per_decision(ways: int) -> int:
    probe = ways * sum(COLUMNS[c] for c in PROBE_COLUMNS)
    gather = sum(COLUMNS[c] for c in GATHER_COLUMNS)
    scatter = sum(COLUMNS.values())
    return probe + gather + scatter + REQUEST_LANE_BYTES + RESPONSE_LANE_BYTES


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown device is an error."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "peaks.json",
    )
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json"
        )
    return table[device_kind]
