"""The interval + unit abstract interpreter over closed jaxprs.

One walk carries BOTH abstractions — an exact interval (unbounded
Python ints / IEEE floats) and a dimensional unit tag — through every
equation of a kernel's jaxpr, recursing into pjit/scan/while/cond the
same way the gubtrace dtype-taint walk does (tools/gubtrace/dtype.py).

Finding classes (see docs/gubrange.md):

  overflow           signed-int arithmetic whose exact result interval
                     leaves the output dtype range — NEVER budgetable;
                     this is the theorem the plane proves
  unbounded-arith    signed-int arithmetic on a TOP (envelope-unseeded)
                     operand — budgetable with a written reason
  int-div-zero       integer div/rem by a zero-inclusive interval
  float-div-zero     float division by a zero-inclusive interval (the
                     idiomatic `where(x != 0, a / x, 0)` guard is
                     invisible to a non-relational domain — budgeted)
  negative-duration  a possibly-negative interval added to an absolute
                     timestamp (e.g. a Gregorian expiry already in the
                     past) — budgeted where the reference behaves so
  unit-mismatch      dimensional-algebra violation (ns+ms, epoch+epoch,
                     hits×duration, …)
  unknown-primitive  a primitive with no transfer function — the walk
                     goes conservative (TOP), and says so

The walk also tracks `peak`: the largest absolute bound any signed-int
arithmetic intermediate can reach.  The envelope's `expect_peak` must
EQUAL it (exactness cuts both ways, like gubproof's expect_max): an
envelope declaring a looser peak than the analysis proves reachable is
an error, so envelopes cannot rot into theater.

Scan bodies are unrolled exactly (`length` is small for every
registered kernel); while bodies run to a joined fixpoint and widen to
TOP if they fail to stabilize.  Unsigned arithmetic is modular by
definition (sketch row hashing) and never raises findings.  pallas_call
is opaque: outputs are TOP of their dtype (the kernel bodies are
differentially pinned elsewhere).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence

from tools.gubrange import units as U
from tools.gubrange.interval import (
    WORD,
    AbsVal,
    add_bounds,
    div_bounds_float,
    div_bounds_int,
    dtype_kind,
    dtype_range,
    from_rows,
    join_bounds,
    mul_bounds,
    rem_bounds_int,
    sub_bounds,
    half_of,
    top_of,
    trunc_to_int_bounds,
)
from tools.gubtrace.core import eqn_source

# Value-preserving moves: interval and unit pass through untouched
# (the packed-row refinement is dropped — only slice/squeeze/scan,
# handled explicitly, can track which row survives an axis change).
_SHAPE_ONLY = frozenset({
    "broadcast_in_dim", "reshape", "expand_dims", "transpose",
    "rev", "copy", "pbroadcast", "stop_gradient",
    "reduce_precision", "all_gather", "all_to_all", "ppermute", "pvary",
    "device_put", "sharding_constraint", "split",
})

_CMP = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge",
    # total-order variants (XLA lowers unsigned/NaN-aware compares)
    "eq_to", "ne_to", "lt_to", "le_to", "gt_to", "ge_to",
})

_SCAN_UNROLL_CAP = 128
_WHILE_FIXPOINT_CAP = 64


@dataclass(frozen=True)
class Issue:
    cls: str
    message: str
    where: str = ""


def _aval_dtype(v) -> str:
    return str(v.aval.dtype)


def _strip_rows(a: AbsVal) -> AbsVal:
    """Collapse the packed-row refinement to its (already-joined)
    top-level bounds."""
    if a.rows is None:
        return a
    return replace(a, rows=None, rows_axis=0)


def _half(a: AbsVal, part: str) -> Optional[AbsVal]:
    """The logical bound `a` carries as split-column word `part`."""
    if a.half is not None and a.half[0] == part:
        return a.half[1]
    return None


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


class RangeWalk:
    """One interval+unit walk over a closed jaxpr.

    `collective_n` scales psum-style cross-device reductions (the
    registry's canonical mesh is 8 virtual devices).
    """

    def __init__(self, collective_n: int = 8) -> None:
        self.issues: List[Issue] = []
        self.peak: int = 0
        self.collective_n = collective_n
        self._unknown_seen: set = set()
        self._sites_seen: set = set()

    # -- bookkeeping ------------------------------------------------------

    def _report(self, cls: str, eqn, msg: str) -> None:
        where = eqn_source(eqn) or ""
        if where:
            # Budgets license SITES, not dynamic occurrences: an
            # unrolled scan (or a kernel applying the same impl twice)
            # re-walks the same equation and must not multiply the
            # declared count by the trip geometry.
            key = (cls, where)
            if key in self._sites_seen:
                return
            self._sites_seen.add(key)
        self.issues.append(Issue(cls, msg, where))

    def _lit(self, v) -> AbsVal:
        val = v.val
        try:
            import numpy as np

            arr = np.asarray(val)
            if arr.dtype.kind in "iub":
                return AbsVal(int(arr.min()), int(arr.max()))
            return AbsVal(float(arr.min()), float(arr.max()))
        except Exception:
            return top_of(_aval_dtype(v))

    # -- arithmetic result constructors -----------------------------------

    def _mk_arith(self, eqn, out_i: int, lo, hi,
                  unit: Optional[str], ins: Sequence[AbsVal],
                  op: str) -> AbsVal:
        """Bound-check one arithmetic result against its output dtype."""
        dtype = _aval_dtype(eqn.outvars[out_i])
        kind = dtype_kind(dtype)
        rlo, rhi = dtype_range(dtype)
        if kind == "float":
            return AbsVal(float(lo), float(hi), unit=unit)
        if kind == "uint":
            # Modular by definition (hash mixing); wrap widens, no finding.
            if lo < rlo or hi > rhi:
                lo, hi = rlo, rhi
            return AbsVal(lo, hi, unit=unit,
                          top=any(a.top for a in ins))
        # signed int (bool never reaches arith outputs)
        if any(a.top for a in ins):
            self._report(
                "unbounded-arith", eqn,
                f"{op} on an envelope-unseeded {dtype} operand — bound "
                "the input in the kernel envelope or budget this with a "
                "reason",
            )
            return top_of(dtype, unit=unit)
        self.peak = max(self.peak, abs(int(lo)), abs(int(hi)))
        if lo < rlo or hi > rhi:
            self._report(
                "overflow", eqn,
                f"{op}: exact result [{lo}, {hi}] exceeds {dtype} "
                f"[{rlo}, {rhi}] — this CAN wrap at the declared "
                "envelope",
            )
            lo, hi = max(lo, rlo), min(hi, rhi)
        return AbsVal(int(lo), int(hi), unit=unit)

    def _unit2(self, eqn, rule, a: AbsVal, b: AbsVal) -> Optional[str]:
        unit, err = rule(a.unit, b.unit)
        if err:
            self._report("unit-mismatch", eqn, err)
        return unit

    # -- the walk ---------------------------------------------------------

    def walk(self, jaxpr, in_vals: Sequence[AbsVal]) -> List[AbsVal]:
        j = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
        env: Dict[int, AbsVal] = {}

        def read(v) -> AbsVal:
            if hasattr(v, "val"):
                return self._lit(v)
            got = env.get(id(v))
            if got is None:
                return top_of(_aval_dtype(v))
            return got

        consts = getattr(jaxpr, "consts", None)
        if hasattr(j, "constvars"):
            for cv in j.constvars:
                env[id(cv)] = top_of(_aval_dtype(cv))
            if consts is not None:
                import numpy as np

                for cv, cval in zip(j.constvars, consts):
                    try:
                        arr = np.asarray(cval)
                        if arr.dtype.kind in "iub":
                            env[id(cv)] = AbsVal(int(arr.min()),
                                                 int(arr.max()))
                        else:
                            env[id(cv)] = AbsVal(float(arr.min()),
                                                 float(arr.max()))
                    except Exception:
                        pass

        for var, val in zip(j.invars, in_vals):
            env[id(var)] = val

        for eqn in j.eqns:
            ins = [read(v) for v in eqn.invars]
            outs = self._transfer(eqn, ins)
            for v, val in zip(eqn.outvars, outs):
                env[id(v)] = val

        return [read(v) for v in j.outvars]

    # -- per-primitive transfer -------------------------------------------

    def _transfer(self, eqn, ins: List[AbsVal]) -> List[AbsVal]:
        name = eqn.primitive.name
        p = eqn.params

        if name in _SHAPE_ONLY:
            first = _strip_rows(ins[0])
            if name == "split":
                return [first for _ in eqn.outvars]
            return [first]

        if name == "slice":
            a = ins[0]
            if a.rows is not None:
                s = int(p["start_indices"][a.rows_axis])
                l = int(p["limit_indices"][a.rows_axis])
                strides = p.get("strides")
                step = (int(strides[a.rows_axis])
                        if strides is not None else 1)
                picked = a.rows[s:l:step]
                if picked:
                    return [from_rows(picked, a.rows_axis)]
            return [_strip_rows(a)]

        if name == "squeeze":
            a = ins[0]
            if a.rows is not None:
                dims = tuple(int(d) for d in p["dimensions"])
                if a.rows_axis in dims:
                    if len(a.rows) == 1:
                        return [a.rows[0]]
                    return [_strip_rows(a)]
                new_axis = a.rows_axis - sum(
                    1 for d in dims if d < a.rows_axis
                )
                return [replace(a, rows_axis=new_axis)]
            return [a]

        if name in _CMP:
            err = U.compare(ins[0].unit, ins[1].unit)
            if err:
                self._report("unit-mismatch", eqn, err)
            return [AbsVal(0, 1)]

        if name == "add":
            a, b = ins
            self._check_negative_duration(eqn, a, b)
            unit = self._unit2(eqn, U.add, a, b)
            lo, hi = add_bounds(a, b)
            return [self._mk_arith(eqn, 0, lo, hi, unit, ins, "add")]

        if name == "sub":
            a, b = ins
            unit = self._unit2(eqn, U.sub, a, b)
            lo, hi = sub_bounds(a, b)
            return [self._mk_arith(eqn, 0, lo, hi, unit, ins, "sub")]

        if name == "mul":
            a, b = ins
            unit = self._unit2(eqn, U.mul, a, b)
            lo, hi = mul_bounds(a, b)
            return [self._mk_arith(eqn, 0, lo, hi, unit, ins, "mul")]

        if name == "div":
            a, b = ins
            unit = self._unit2(eqn, U.div, a, b)
            if dtype_kind(_aval_dtype(eqn.outvars[0])) == "float":
                lo, hi, zdiv = div_bounds_float(a, b)
                if zdiv:
                    self._report(
                        "float-div-zero", eqn,
                        f"float division by zero-inclusive interval "
                        f"[{b.lo}, {b.hi}]",
                    )
                return [AbsVal(lo, hi, unit=unit)]
            lo, hi, zdiv = div_bounds_int(a, b)
            if zdiv:
                self._report(
                    "int-div-zero", eqn,
                    f"integer division by zero-inclusive interval "
                    f"[{b.lo}, {b.hi}]",
                )
            return [self._mk_arith(eqn, 0, lo, hi, unit, ins, "div")]

        if name == "rem":
            a, b = ins
            lo, hi, zdiv = rem_bounds_int(a, b)
            if zdiv:
                self._report(
                    "int-div-zero", eqn,
                    f"integer remainder by zero-inclusive interval "
                    f"[{b.lo}, {b.hi}]",
                )
            return [self._mk_arith(eqn, 0, lo, hi, ins[0].unit, ins,
                                   "rem")]

        if name == "neg":
            a = ins[0]
            return [self._mk_arith(eqn, 0, -a.hi, -a.lo, a.unit, ins,
                                   "neg")]

        if name == "abs":
            a = ins[0]
            lo = 0 if a.lo < 0 < a.hi or a.lo == 0 or a.hi == 0 else \
                min(abs(a.lo), abs(a.hi))
            hi = max(abs(a.lo), abs(a.hi))
            return [self._mk_arith(eqn, 0, lo, hi, a.unit, ins, "abs")]

        if name == "sign":
            return [AbsVal(-1, 1)]

        if name == "integer_pow":
            a = ins[0]
            y = int(p["y"])
            cands = [a.lo ** y, a.hi ** y]
            if a.lo < 0 < a.hi:
                cands.append(0)
            lo, hi = min(cands), max(cands)
            if y % 2 == 0:
                lo = max(lo, 0)
            return [self._mk_arith(eqn, 0, lo, hi, None, ins,
                                   "integer_pow")]

        if name in ("max", "min"):
            a, b = ins
            unit = self._unit2(eqn, U.join, a, b)
            f = max if name == "max" else min
            return [AbsVal(f(a.lo, b.lo), f(a.hi, b.hi), unit=unit,
                           top=a.top and b.top)]

        if name == "clamp":
            mn, x, mx = ins
            unit = self._unit2(eqn, U.join, x, mn)
            unit, err = U.join(unit, mx.unit)
            if err:
                self._report("unit-mismatch", eqn, err)
            lo = min(max(x.lo, mn.lo), mx.lo)
            hi = min(max(x.hi, mn.hi), mx.hi)
            return [AbsVal(lo, hi, unit=unit, top=x.top and mn.top
                           and mx.top)]

        if name == "select_n":
            cases = ins[1:]
            out = cases[0]
            for c in cases[1:]:
                unit = self._unit2(eqn, U.join, out, c)
                lo, hi, top = join_bounds(out, c)
                out = AbsVal(lo, hi, unit=unit, top=top)
            return [out]

        if name in ("concatenate", "pad"):
            vals = ins if name == "concatenate" else ins[:2]
            lo = min(v.lo for v in vals)
            hi = max(v.hi for v in vals)
            us = {v.unit for v in vals if v.unit is not None}
            unit = us.pop() if len(us) == 1 else None
            return [AbsVal(lo, hi, unit=unit,
                           top=any(v.top for v in vals))]

        if name in ("and", "or", "xor", "not"):
            dtype = _aval_dtype(eqn.outvars[0])
            if dtype == "bool":
                return [AbsVal(0, 1)]
            if name == "and":
                # The split's low word: `u & 0xFFFFFFFF` of a whole
                # value (tagged at its int64 -> uint64 convert).
                for x, m in (ins, ins[::-1]):
                    whole = _half(x, "u64")
                    if whole is not None and m.is_exact() \
                            and m.lo == WORD:
                        return [half_of(whole, "lo")]
                nonneg = [v for v in ins if v.lo >= 0]
                if nonneg:
                    return [AbsVal(0, min(v.hi for v in nonneg))]
            if name == "or":
                # The combine: `(hi << 32) | lo` of one column's words
                # is a value inside that column's logical bound.
                for x, y in (ins, ins[::-1]):
                    hi32, lo = _half(x, "hi32"), _half(y, "lo")
                    if hi32 is not None and lo is not None:
                        return [self._whole(eqn, hi32, lo, dtype)]
            if name in ("or", "xor") and all(v.lo >= 0 for v in ins):
                m = max(v.hi for v in ins)
                return [AbsVal(0, (1 << max(int(m), 1).bit_length()) - 1)]
            return [top_of(dtype).with_unit(None)]

        if name in ("shift_left", "shift_right_logical",
                    "shift_right_arithmetic"):
            a, s = ins
            dtype = _aval_dtype(eqn.outvars[0])
            if s.is_exact() and s.lo == 32 and dtype == "uint64":
                hi = _half(a, "hi")
                if name == "shift_left" and hi is not None:
                    return [AbsVal(int(a.lo) << 32, int(a.hi) << 32,
                                   half=("hi32", hi))]
                whole = _half(a, "u64")
                if name == "shift_right_logical" and whole is not None:
                    return [half_of(whole, "hi")]
            if a.is_exact() and s.is_exact():
                x, sh = int(a.lo), int(s.lo)
                if name == "shift_left":
                    v = x << sh
                    rlo, rhi = dtype_range(dtype)
                    if v < rlo or v > rhi:
                        if dtype_kind(dtype) == "int":
                            self._report(
                                "overflow", eqn,
                                f"shift_left: {x} << {sh} exceeds "
                                f"{dtype}",
                            )
                        v = ((v - rlo) % (rhi - rlo + 1)) + rlo
                else:
                    v = x >> sh
                return [AbsVal(v, v, unit=a.unit)]
            if a.lo >= 0 and s.lo >= 0 and name != "shift_left":
                return [AbsVal(int(a.lo) >> int(s.hi),
                               int(a.hi) >> int(s.lo), unit=a.unit,
                               top=a.top)]
            return [top_of(dtype)]

        if name == "convert_element_type":
            return [self._convert(eqn, ins[0])]

        if name == "bitcast_convert_type":
            return [top_of(_aval_dtype(eqn.outvars[0]))]

        if name == "iota":
            d = int(p["dimension"])
            return [AbsVal(0, max(int(p["shape"][d]) - 1, 0))]

        if name in ("argmax", "argmin"):
            axes = p.get("axes", ())
            n = 1
            for ax in axes:
                n *= int(eqn.invars[0].aval.shape[int(ax)])
            return [AbsVal(0, max(n - 1, 0))]

        if name in ("reduce_max", "reduce_min"):
            a = ins[0]
            return [AbsVal(a.lo, a.hi, unit=a.unit, top=a.top)]

        if name in ("reduce_and", "reduce_or"):
            return [AbsVal(0, 1)]

        if name == "reduce_sum":
            a = ins[0]
            n = max(_size(eqn.invars[0].aval.shape)
                    // max(_size(eqn.outvars[0].aval.shape), 1), 1)
            return [self._mk_arith(eqn, 0, n * a.lo, n * a.hi, a.unit,
                                   ins, f"reduce_sum(n={n})")]

        if name == "cumsum":
            a = ins[0]
            n = int(eqn.invars[0].aval.shape[int(p.get("axis", 0))])
            lo = min(a.lo, n * a.lo)
            hi = max(a.hi, n * a.hi)
            return [self._mk_arith(eqn, 0, lo, hi, a.unit, ins,
                                   f"cumsum(n={n})")]

        if name in ("cummax", "cummin"):
            a = ins[0]
            return [a]

        if name == "sort":
            return list(ins)

        if name == "gather":
            return [ins[0].with_unit(ins[0].unit)]

        if name == "dynamic_slice":
            return [ins[0]]

        if name in ("scatter", "dynamic_update_slice"):
            op = ins[0]
            upd = ins[-1] if name == "dynamic_update_slice" else ins[2]
            unit = self._unit2(eqn, U.join, op, upd)
            lo, hi, top = join_bounds(op, upd)
            half = None
            if op.half is not None and upd.half is not None \
                    and op.half[0] == upd.half[0]:
                # A word of a split column overwritten by the same word
                # of a split value: the column's logical bound joins.
                half = (op.half[0],
                        self._join_logical(eqn, op.half[1], upd.half[1]))
            return [AbsVal(lo, hi, unit=unit, top=top, half=half)]

        if name in ("scatter-add", "scatter_add"):
            op, upd = ins[0], ins[2]
            n = max(_size(eqn.invars[2].aval.shape), 1)
            unit = self._unit2(eqn, U.add, op, upd)
            lo = op.lo + min(0, n * upd.lo)
            hi = op.hi + max(0, n * upd.hi)
            return [self._mk_arith(eqn, 0, lo, hi, unit, (op, upd),
                                   f"scatter-add(n={n})")]

        if name in ("scatter-min", "scatter-max"):
            op, upd = ins[0], ins[2]
            unit = self._unit2(eqn, U.join, op, upd)
            lo, hi, top = join_bounds(op, upd)
            return [AbsVal(lo, hi, unit=unit, top=top)]

        if name == "dot_general":
            a, b = ins[0], ins[1]
            ((lc, _rc), _batch) = p["dimension_numbers"]
            k = 1
            for ax in lc:
                k *= int(eqn.invars[0].aval.shape[int(ax)])
            mlo, mhi = mul_bounds(a, b)
            unit = self._unit2(eqn, U.mul, a, b)
            return [self._mk_arith(eqn, 0, k * mlo, k * mhi, unit, ins,
                                   f"dot_general(k={k})")]

        if name in ("psum", "psum2", "psum_invariant"):
            a = ins[0]
            n = self.collective_n
            return [self._mk_arith(eqn, i, n * v.lo, n * v.hi, v.unit,
                                   ins, f"psum(n={n})")
                    for i, v in enumerate(ins)]

        if name in ("pmax", "pmin"):
            return list(ins)

        if name == "axis_index":
            return [AbsVal(0, self.collective_n - 1)]

        if name == "top_k":
            a = ins[0]
            n = int(eqn.invars[0].aval.shape[-1])
            return [_strip_rows(a), AbsVal(0, max(n - 1, 0))]

        if name in ("population_count", "clz"):
            return [AbsVal(0, 64)]

        if name == "is_finite":
            return [AbsVal(0, 1)]

        if name in ("floor", "ceil", "round_nearest_even", "round"):
            a = ins[0]
            f = math.floor if name == "floor" else math.ceil
            lo = a.lo if math.isinf(a.lo) else float(f(a.lo))
            hi = a.hi if math.isinf(a.hi) else float(f(a.hi))
            return [AbsVal(lo, hi, unit=a.unit)]

        if name in ("sqrt", "rsqrt", "exp", "log", "log1p", "expm1",
                    "logistic", "tanh", "erf", "sin", "cos", "pow",
                    "atan2", "nextafter", "square", "cbrt"):
            # Float-only transcendental surface: honest don't-know.
            return [AbsVal(-math.inf, math.inf)
                    for _ in eqn.outvars]

        # -- structured control flow --------------------------------------
        if name in ("pjit", "jit") or (
            "jaxpr" in p and name in ("closed_call", "shard_map",
                                      "remat", "checkpoint")
        ):
            return self.walk(p["jaxpr"], ins)

        if name in ("custom_jvp_call", "custom_vjp_call") and \
                p.get("call_jaxpr") is not None:
            return self.walk(p["call_jaxpr"], ins)

        if name == "scan":
            return self._scan(eqn, ins)

        if name == "while":
            return self._while(eqn, ins)

        if name == "cond":
            outs: Optional[List[AbsVal]] = None
            for br in p["branches"]:
                o = self.walk(br, ins[1:])
                if outs is None:
                    outs = o
                else:
                    merged = []
                    for x, y in zip(outs, o):
                        lo, hi, top = join_bounds(x, y)
                        unit, _ = U.join(x.unit, y.unit)
                        merged.append(AbsVal(lo, hi, unit=unit, top=top))
                    outs = merged
            return outs or [top_of(_aval_dtype(v)) for v in eqn.outvars]

        if name == "pallas_call":
            # Opaque by contract: bodies are differentially pinned
            # elsewhere; outputs are unconstrained-of-dtype.
            return [top_of(_aval_dtype(v)) for v in eqn.outvars]

        if name not in self._unknown_seen:
            self._unknown_seen.add(name)
            self._report(
                "unknown-primitive", eqn,
                f"no interval transfer for primitive '{name}' — result "
                "treated as unconstrained (add a transfer function in "
                "tools/gubrange/absint.py)",
            )
        return [top_of(_aval_dtype(v)) for v in eqn.outvars]

    # -- helpers ----------------------------------------------------------

    def _join_logical(self, eqn, a: AbsVal, b: AbsVal) -> AbsVal:
        unit = self._unit2(eqn, U.join, a, b)
        lo, hi, top = join_bounds(a, b)
        return AbsVal(lo, hi, unit=unit, top=top)

    def _whole(self, eqn, hi: AbsVal, lo: AbsVal, dtype: str) -> AbsVal:
        """`(hi << 32) | lo` in uint64, carrying the joined logical
        bound of the two words' column to the int64 convert.  (The
        domain is non-relational: that the two words come from the same
        ROW is ops/state.py's gather64 contract, pinned by
        tests/test_table_layout.py, not something an interval can
        see.)"""
        logical = self._join_logical(eqn, hi, lo)
        rlo, rhi = dtype_range(dtype)
        if logical.lo >= 0:
            return AbsVal(int(logical.lo), int(logical.hi),
                          half=("u64", logical))
        return AbsVal(rlo, rhi, half=("u64", logical))

    def _check_negative_duration(self, eqn, a: AbsVal, b: AbsVal) -> None:
        for x, y in ((a, b), (b, a)):
            if U.is_epoch(x.unit) and not U.is_epoch(y.unit) and \
                    not y.top and y.lo < 0:
                self._report(
                    "negative-duration", eqn,
                    f"possibly-negative interval [{y.lo}, {y.hi}] "
                    f"({y.unit or 'unitless'}) added to an absolute "
                    f"timestamp ({x.unit})",
                )

    def _convert(self, eqn, a: AbsVal) -> AbsVal:
        src = _aval_dtype(eqn.invars[0])
        dst = _aval_dtype(eqn.outvars[0])
        sk, dk = dtype_kind(src), dtype_kind(dst)
        if dk == "bool":
            return AbsVal(0, 1)
        if dk == "float":
            # Int lineage entering float is saturation-safe end-to-end:
            # re-entry to int goes through the _trunc_i64 contract.
            return AbsVal(float(a.lo), float(a.hi), unit=a.unit)
        if sk == "float":
            lo, hi = trunc_to_int_bounds(a, dst)
            return AbsVal(lo, hi, unit=a.unit)
        rlo, rhi = dtype_range(dst)
        # The slot table's split columns (interval.AbsVal.half): the
        # whole value leaves its uint64 guise with its logical bound,
        # enters it tagged with that bound, and a word keeps its tag
        # through the uint32 <-> uint64 converts of combine and split.
        whole = _half(a, "u64")
        if whole is not None and dst == "int64":
            return whole
        half = a.half if sk == "uint" and dk == "uint" else None
        if src == "int64" and dst == "uint64":
            half = ("u64", replace(_strip_rows(a), half=None))
        if a.lo >= rlo and a.hi <= rhi:
            return AbsVal(int(a.lo), int(a.hi), unit=a.unit, top=a.top,
                          half=half)
        # Out-of-range int->int reinterpretation: the dtype-taint plane
        # (gubtrace) governs narrowing legality; range-wise it's the
        # full destination range.
        return AbsVal(rlo, rhi, unit=a.unit, top=a.top, half=half)

    def _scan(self, eqn, ins: List[AbsVal]) -> List[AbsVal]:
        p = eqn.params
        nc, ncarry = int(p["num_consts"]), int(p["num_carry"])
        length = int(p["length"])
        body = p["jaxpr"]
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncarry])
        # Body sees per-iteration elements: axis 0 of each xs is
        # consumed, so a packed-row refinement there shifts down one
        # axis (and collapses if the scan axis WAS the row axis).
        xs = []
        for x in ins[nc + ncarry:]:
            if x.rows is not None:
                x = (_strip_rows(x) if x.rows_axis == 0
                     else replace(x, rows_axis=x.rows_axis - 1))
            xs.append(x)
        n_ys = len(eqn.outvars) - ncarry
        ys: List[Optional[AbsVal]] = [None] * n_ys

        def step(carry_in: List[AbsVal]) -> List[AbsVal]:
            outs = self.walk(body, consts + carry_in + xs)
            for i, y in enumerate(outs[ncarry:]):
                prev = ys[i]
                if prev is None:
                    ys[i] = y
                else:
                    lo, hi, top = join_bounds(prev, y)
                    unit, _ = U.join(prev.unit, y.unit)
                    ys[i] = AbsVal(lo, hi, unit=unit, top=top)
            return outs[:ncarry]

        if length <= _SCAN_UNROLL_CAP:
            for _ in range(length):
                carry = step(carry)
        else:
            stable = False
            for _ in range(_WHILE_FIXPOINT_CAP):
                nxt_raw = step(carry)
                nxt = []
                changed = False
                for cur, new in zip(carry, nxt_raw):
                    lo, hi, top = join_bounds(cur, new)
                    unit, _ = U.join(cur.unit, new.unit)
                    j = AbsVal(lo, hi, unit=unit, top=top)
                    changed = changed or j != cur
                    nxt.append(j)
                carry = nxt
                if not changed:
                    stable = True
                    break
            if not stable:
                carry = [
                    top_of(_aval_dtype(v))
                    for v in eqn.outvars[:ncarry]
                ]
                carry = step(carry)
        return carry + [
            y if y is not None else top_of(_aval_dtype(v))
            for y, v in zip(ys, eqn.outvars[ncarry:])
        ]

    def _while(self, eqn, ins: List[AbsVal]) -> List[AbsVal]:
        p = eqn.params
        nc, nb = int(p["cond_nconsts"]), int(p["body_nconsts"])
        body_consts = ins[nc:nc + nb]
        carry = list(ins[nc + nb:])
        for _ in range(_WHILE_FIXPOINT_CAP):
            out = self.walk(p["body_jaxpr"], body_consts + carry)
            nxt = []
            changed = False
            for cur, new in zip(carry, out):
                lo, hi, top = join_bounds(cur, new)
                unit, _ = U.join(cur.unit, new.unit)
                j = AbsVal(lo, hi, unit=unit, top=top)
                changed = changed or j != cur
                nxt.append(j)
            carry = nxt
            if not changed:
                return carry
        carry = [top_of(_aval_dtype(v)) for v in eqn.outvars]
        return self.walk(p["body_jaxpr"], body_consts + carry)
