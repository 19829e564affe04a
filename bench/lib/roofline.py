"""The table of peaks.  Kept here so that a PR that claims a gain cannot
change what its gain is measured against.  What a step program moves
through HBM is the compiler's own count (`bytes accessed` of the compiled
program, read by bench/serve.py): PR 24's count from shapes, 512 B a
decision, never described a program that touches table-length arrays on
every launch, and went with PR 27.
"""
from __future__ import annotations

import json
import os


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
