"""Operational-envelope registry: declared input bounds per kernel.

Each registered kernel (tools/gubtrace/registry.py) carries one JSON
envelope in tools/gubrange/envelopes/<kernel>.json declaring, per input
leaf pattern, the operational bound the deployment promises (max limit,
max hits, max cost, max duration, horizon epoch, table geometry) and
the dimensional unit of the leaf.  The analysis seeds its intervals
from these declarations, so the theorem it proves is exactly "within
the declared envelope, no signed intermediate can wrap".

Exactness cuts both ways, like gubproof's expect_max: `expect_peak`
must EQUAL the analysis' observed peak (largest |bound| any signed-int
arithmetic intermediate reaches), and every finding budget must be
spent exactly — a declared envelope looser than what is provable is an
error, not slack.

Format:

  {
    "kernel": "apply_batch",
    "notes": "why these bounds are the deployment contract",
    "inputs": [
      {"pattern": ".hits", "unit": "count", "min": 0, "max": 1000000000}
    ],
    "budgets": {"float-div-zero": 3},
    "reasons": {"float-div-zero": "where(lim!=0, x/lim, 0) guards"},
    "expect_peak": "9223372036854775807"
  }

`pattern` matches as a substring of the jax.tree_util.keystr keypath of
the flattened args, first match wins — the same matching the gubtrace
counter taint uses.  `expect_peak` is a STRING because JSON numbers
lose integer precision past 2^53.  Every budget entry requires a
written reason.

Split columns.  The slot table stores each int64 field as a low and a
high uint32 leaf (`[0].limit.lo`, `[0].limit.hi`; ops/state.py).  A
rule still declares the LOGICAL int64 bound under the field's name
(`.limit` matches both leaves): `seed` gives each leaf the bound of its
word and tags it with the logical bound, and the interpreter attaches
that bound where the kernel combines the words — so the bound a
reviewer reads here is the bound the arithmetic is proved under, no
wider than before the split.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from tools.gubrange.interval import (
    WORD,
    AbsVal,
    dtype_range,
    from_rows,
    half_of,
    top_of,
)

ENVELOPE_DIR = Path(__file__).resolve().parent / "envelopes"

# Finding classes an envelope may budget (with a reason).  "overflow"
# is deliberately absent: a provable wrap inside the envelope is never
# acceptable — fix the kernel, not the declaration.
BUDGETABLE = (
    "unbounded-arith",
    "int-div-zero",
    "float-div-zero",
    "negative-duration",
    "unit-mismatch",
)


@dataclass(frozen=True)
class InputRule:
    pattern: str
    min: int
    max: int
    unit: Optional[str] = None
    # Packed-stack refinement: per-index bounds along `rows_axis` for
    # the q-form kernels' 12-row int64 packs.  Each entry is
    # {"name": ..., "unit": ..., "min": ..., "max": ...} or
    # {"name": ..., "top": true} for a full-range lane (key_hash).
    rows: Optional[tuple] = None
    rows_axis: int = 0


@dataclass
class Envelope:
    kernel: str
    inputs: List[InputRule]
    budgets: Dict[str, int] = field(default_factory=dict)
    reasons: Dict[str, str] = field(default_factory=dict)
    expect_peak: Optional[int] = None
    notes: str = ""
    path: Optional[Path] = None

    def validate(self) -> List[str]:
        errs = []
        for cls in self.budgets:
            if cls not in BUDGETABLE:
                errs.append(
                    f"budget for non-budgetable class '{cls}' "
                    f"(budgetable: {', '.join(BUDGETABLE)})"
                )
            elif not self.reasons.get(cls, "").strip():
                errs.append(
                    f"budget '{cls}' has no written reason — every "
                    "licensed finding class must say why"
                )
        for cls in self.reasons:
            if cls not in self.budgets:
                errs.append(f"reason for unbudgeted class '{cls}'")
        for r in self.inputs:
            if r.min > r.max:
                errs.append(f"input '{r.pattern}': min {r.min} > max "
                            f"{r.max}")
        return errs


def load_envelope(path: Path) -> Envelope:
    raw = json.loads(path.read_text(encoding="utf-8"))
    peak = raw.get("expect_peak")
    return Envelope(
        kernel=raw["kernel"],
        inputs=[
            InputRule(
                pattern=i["pattern"], min=int(i["min"]), max=int(i["max"]),
                unit=i.get("unit"),
                rows=(tuple(i["rows"]) if i.get("rows") else None),
                rows_axis=int(i.get("rows_axis", 0)),
            )
            for i in raw.get("inputs", ())
        ],
        budgets={k: int(v) for k, v in raw.get("budgets", {}).items()},
        reasons=dict(raw.get("reasons", {})),
        expect_peak=int(peak) if peak is not None else None,
        notes=raw.get("notes", ""),
        path=path,
    )


def load_envelopes(env_dir: Path = ENVELOPE_DIR) -> Dict[str, Envelope]:
    out: Dict[str, Envelope] = {}
    for path in sorted(env_dir.glob("*.json")):
        env = load_envelope(path)
        out[env.kernel] = env
    return out


def save_peak(env: Envelope, peak: int) -> None:
    """--update: rewrite ONLY expect_peak, preserving the rest."""
    assert env.path is not None
    raw = json.loads(env.path.read_text(encoding="utf-8"))
    raw["expect_peak"] = str(peak)
    env.path.write_text(
        json.dumps(raw, indent=2) + "\n", encoding="utf-8"
    )


def seed(
    env: Envelope, args: tuple
) -> Tuple[List[AbsVal], List[str], List[str]]:
    """Interval+unit seeds for the flattened `args` leaves.

    Returns (seeds, unmatched_leaf_keys, unused_patterns):
    unmatched leaves become TOP of their dtype (arithmetic on them is a
    budgetable finding); declared patterns matching no leaf are stale.
    """
    import jax
    import numpy as np

    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    seeds: List[AbsVal] = []
    unmatched: List[str] = []
    used = set()
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        dtype = np.asarray(leaf).dtype.name
        rule = next((r for r in env.inputs if r.pattern in key), None)
        if rule is None:
            if dtype == "bool":
                seeds.append(AbsVal(0, 1))
            else:
                seeds.append(top_of(dtype))
                unmatched.append(f"{key}:{dtype}")
            continue
        used.add(rule.pattern)
        rlo, rhi = dtype_range(dtype)
        if rule.rows is not None:
            row_vals = []
            for r in rule.rows:
                if r.get("top"):
                    row_vals.append(top_of(dtype, unit=r.get("unit")))
                else:
                    row_vals.append(AbsVal(
                        max(int(r["min"]), rlo), min(int(r["max"]), rhi),
                        unit=r.get("unit"),
                    ))
            seeds.append(from_rows(row_vals, rule.rows_axis))
            continue
        part = _word_of(key, dtype)
        if part is not None:
            ilo, ihi = dtype_range("int64")
            seeds.append(half_of(
                AbsVal(max(rule.min, ilo), min(rule.max, ihi),
                       unit=rule.unit),
                part,
            ))
            continue
        lo, hi = max(rule.min, rlo), min(rule.max, rhi)
        seeds.append(AbsVal(lo, hi, unit=rule.unit))
    unused = [r.pattern for r in env.inputs if r.pattern not in used]
    return seeds, unmatched, unused


def _word_of(key: str, dtype: str) -> Optional[str]:
    """"lo" | "hi" for a leaf that is one word of a split int64 column."""
    if dtype == "uint32" and key.endswith((".lo", ".hi")):
        return key[-2:]
    return None


def corner_args(env: Envelope, args: tuple, corner: str = "max") -> tuple:
    """Concrete args with every envelope-matched leaf at its bound
    corner — the witness input (see tools/gubrange/witness.py)."""
    import jax
    import numpy as np

    flat, treedef = jax.tree_util.tree_flatten_with_path(args)
    leaves = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        arr = np.asarray(leaf)
        rule = next((r for r in env.inputs if r.pattern in key), None)
        if rule is not None and arr.dtype.kind in "iu":
            rlo, rhi = dtype_range(arr.dtype.name)
            if rule.rows is not None:
                arr = arr.copy()
                for i, r in enumerate(rule.rows):
                    v = 0 if r.get("top") else (
                        r["max"] if corner == "max" else r["min"]
                    )
                    idx = [slice(None)] * arr.ndim
                    idx[rule.rows_axis] = i
                    arr[tuple(idx)] = min(max(int(v), rlo), rhi)
            else:
                v = rule.max if corner == "max" else rule.min
                part = _word_of(key, arr.dtype.name)
                if part is not None:  # the word of the LOGICAL corner
                    ilo, ihi = dtype_range("int64")
                    v = min(max(v, ilo), ihi) % 2**64
                    v = (v >> 32) if part == "hi" else (v & WORD)
                arr = np.full_like(arr, min(max(v, rlo), rhi))
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
