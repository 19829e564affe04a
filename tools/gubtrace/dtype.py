"""dtype-taint: no silent counter/timestamp dtype escapes.

The failure class ("When Two is Worse Than One", PAPERS.md — silent
accounting divergence): a refactor introduces an int64→float or
int64→int32 `convert_element_type` on counter dataflow, XLA compiles it
without complaint, and remaining/expiry arithmetic silently loses
precision (f32 is exact only to 2^24; i32 wraps at 2^31 — both far
below real token budgets and unix-ms timestamps).

Mechanics: each kernel declares its int64 counter/timestamp inputs
(`BuiltKernel.counters`, pytree-path patterns).  Taint starts on those
invars and propagates through every equation along the int64/uint64
lineage — once a value is *deliberately* converted (the leaky bucket's
Go-float arithmetic), the cast is charged against the kernel's declared
`allowed_casts` budget and the float lineage is not re-flagged.  Any
tainted cast beyond the declared multiset is an error naming the
offending source line.

Casts are bucketed by destination:
  to_f64  — deliberate Go-semantics float math (budgeted per kernel)
  to_f32 / to_f16 — precision loss for counters (budget only when the
            kernel's contract bounds the value, e.g. CMS cells)
  to_i32 / narrower — wrap/truncation (budget only for fields whose
            contract bounds them, e.g. packed algo enums)
  split64 — the slot table's physical layout (ops/state.py): an int64
            column is stored as a low and a high uint32 column, and a
            write splits the value LOSSLESSLY — `(u & 0xFFFFFFFF)` and
            `(u >> 32)` of the SAME value, both narrowed to uint32 and
            both stored.  One split64 is charged per value split so
            (declared per kernel like every other class); a lone half,
            or a half that is never stored, is what it looks like — a
            truncation — and is charged to to_i32.
The reverse direction needs no budget: a table half is a tainted uint32
input whose lineage (gather, select, scatter) stays tainted, and the
combine `(hi.astype(u64) << 32) | lo.astype(u64)` re-enters the 64-bit
lineage at its widening converts — the taint on logical values starts
at the combine's output.  A half cast anywhere else (to a float, to
int32) is charged like a counter cast.
Casts to bool (lane predicates) and within the 64-bit integer family
are free — they cannot corrupt a counter.  Also free: *index* casts,
i64→i32 whose every (transitive, through shape-only ops) consumer is
the index operand of a gather/scatter/dynamic-slice — jnp indexing
narrows indices to i32 as a matter of course and slot/bucket spaces
are bounded by table geometry (num_slots << 2^31), not by counter
values.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, List, Set, Tuple

from tools.gubtrace.core import (
    BuiltKernel,
    Checker,
    Finding,
    KernelSpec,
    RunContext,
    eqn_source,
    taint_mask,
)

_WIDE_INT = ("int64", "uint64")
_HALF = "uint32"  # a table half: one of an int64 column's two words


def _bucket(dtype_name: str) -> str:
    if dtype_name.startswith("float64"):
        return "to_f64"
    if dtype_name.startswith(("float32",)):
        return "to_f32"
    if dtype_name.startswith(("float16", "bfloat16")):
        return "to_f16"
    if dtype_name.startswith(("int32", "uint32")):
        return "to_i32"
    if dtype_name.startswith(("int16", "uint16", "int8", "uint8")):
        return "to_i8"
    return ""


# Ops that only reshape/relocate an index lineage without using values.
# pbroadcast qualifies: shard_map inserts it to replicate a P()-specced
# value across the mesh axis (e.g. a replicated fingerprint grid whose
# derived bucket indices feed a gather over the sharded table) — it
# moves the lineage between devices without consuming it.
_SHAPE_ONLY = frozenset({
    "broadcast_in_dim", "reshape", "concatenate", "slice", "squeeze",
    "expand_dims", "transpose", "rev", "copy", "pbroadcast", "pvary",
})


def _index_positions(eqn) -> List[int]:
    """invars positions that are *index* operands of this primitive."""
    name = eqn.primitive.name
    n = len(eqn.invars)
    if name == "gather":
        return [1]
    if name in ("scatter", "scatter-add", "scatter-mul", "scatter-min",
                "scatter-max"):
        return [1]
    if name == "dynamic_slice":
        return list(range(1, n))
    if name == "dynamic_update_slice":
        return list(range(2, n))
    return []


def _scalar_literal(v, producer):
    """The Python int of a scalar integer Literal operand — seen through
    the shape-only ops shard_map wraps a constant in (pvary) — else
    None."""
    for _ in range(4):
        eqn = producer.get(id(v))
        if hasattr(v, "val") or eqn is None or \
                eqn.primitive.name not in _SHAPE_ONLY:
            break
        v = eqn.invars[0]
    val = getattr(v, "val", None)
    if val is None or getattr(val, "shape", ()) != ():
        return None
    try:
        return int(val)
    except (TypeError, ValueError):
        return None


def _split_part(eqn, producer):
    """(`lo`|`hi`, source var) when `eqn` computes one word of the
    lossless 64-bit split — `x & 0xFFFFFFFF` or `x >> 32` — else None."""
    if eqn is None or len(eqn.invars) != 2:
        return None
    name = eqn.primitive.name
    a, b = eqn.invars
    if name == "and":
        for x, c in ((a, b), (b, a)):
            if _scalar_literal(c, producer) == 0xFFFFFFFF \
                    and not hasattr(x, "val"):
                return ("lo", x)
    if name == "shift_right_logical" and not hasattr(a, "val") \
            and _scalar_literal(b, producer) == 32:
        return ("hi", a)
    return None


def _is_stored(var, consumers, outvar_ids) -> bool:
    """True when `var` reaches (through shape-only ops) the updates
    operand of a scatter, or leaves the jaxpr — a split word that is
    written somewhere, not computed and dropped."""
    seen = set()
    stack = [var]
    while stack:
        v = stack.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        if id(v) in outvar_ids:
            return True
        for eqn in consumers.get(id(v), ()):
            if eqn.primitive.name == "scatter" and eqn.invars[2] is v:
                return True
            if eqn.primitive.name in _SHAPE_ONLY:
                stack.extend(eqn.outvars)
    return False


def _is_index_only(var, eqn_of_var, consumers, outvar_ids) -> bool:
    """True when every transitive consumer (through shape-only ops) of
    `var` uses it as a gather/scatter/dynamic-slice index."""
    seen = set()
    stack = [var]
    while stack:
        v = stack.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        if id(v) in outvar_ids:
            return False  # escapes this jaxpr — can't prove index-only
        uses = consumers.get(id(v))
        if not uses:
            return False  # dead or untracked — be conservative
        for eqn in uses:
            idx_pos = set(_index_positions(eqn))
            positions = [
                i for i, iv in enumerate(eqn.invars) if iv is v
            ]
            if all(p in idx_pos for p in positions):
                continue
            if eqn.primitive.name in _SHAPE_ONLY:
                stack.extend(eqn.outvars)
                continue
            return False
    return True


class _Walk:
    """One taint-propagation walk over a closed jaxpr."""

    def __init__(self) -> None:
        self.casts: Counter = Counter()
        self.sites: Dict[str, List[str]] = {}

    def _tainted_outs(self, eqn, tin: List[bool]) -> List[bool]:
        """Default propagation: any tainted input taints every wide-int
        output (float/bool/narrow outputs are only reached via an
        explicit convert, which is handled separately), and a tainted
        table half (uint32) taints every uint32 output — the half
        lineage through gather/select/scatter."""
        if not any(tin):
            return [False] * len(eqn.outvars)
        half_in = any(
            t and str(v.aval.dtype) == _HALF
            for v, t in zip(eqn.invars, tin)
        )
        return [
            str(v.aval.dtype) in _WIDE_INT
            or (half_in and str(v.aval.dtype) == _HALF)
            for v in eqn.outvars
        ]

    def walk(self, jaxpr, taint_in: List[bool]) -> List[bool]:
        """Returns the taint mask of jaxpr.outvars."""
        j = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
        tainted: Set[int] = set()
        consumers: Dict[int, list] = {}
        producer: Dict[int, object] = {}
        for eqn in j.eqns:
            for v in eqn.invars:
                if not hasattr(v, "val"):
                    consumers.setdefault(id(v), []).append(eqn)
            for v in eqn.outvars:
                producer[id(v)] = eqn
        outvar_ids = {id(v) for v in j.outvars}
        # id(source var) -> {"lo"/"hi": (stored, site)}: the words of
        # each value this jaxpr splits (settled after the loop).
        splits: Dict[int, Dict[str, Tuple[bool, str]]] = {}

        def is_t(v) -> bool:
            return not hasattr(v, "val") and id(v) in tainted

        for var, t in zip(j.invars, taint_in):
            if t:
                tainted.add(id(var))

        for eqn in j.eqns:
            tin = [is_t(v) for v in eqn.invars]
            name = eqn.primitive.name
            if name == "convert_element_type" and tin[0]:
                src = str(eqn.invars[0].aval.dtype)
                dst = str(eqn.outvars[0].aval.dtype)
                site = f"{src}->{dst} at {eqn_source(eqn) or '?'}"
                if src in _WIDE_INT:
                    b = _bucket(dst)
                    if b in ("to_i32", "to_i8") and _is_index_only(
                        eqn.outvars[0], eqn, consumers, outvar_ids
                    ):
                        continue  # index lineage — bounded by geometry
                    part = _split_part(
                        producer.get(id(eqn.invars[0])), producer
                    ) \
                        if dst == _HALF else None
                    if part is not None:
                        stored = _is_stored(
                            eqn.outvars[0], consumers, outvar_ids
                        )
                        splits.setdefault(id(part[1]), {})[part[0]] = (
                            stored, site
                        )
                        continue
                    if b:
                        self._charge(b, site)
                        continue  # converted lineage is not re-tainted
                elif src == _HALF and dst not in _WIDE_INT:
                    # A table half cast to anything but the combine's
                    # widening (or a bool predicate) is a counter cast.
                    b = _bucket(dst)
                    if b:
                        self._charge(b, site)
                    continue
                # wide-int <-> wide-int keeps taint; half -> wide-int is
                # the combine, where the logical value's taint starts
                if dst in _WIDE_INT:
                    tainted.add(id(eqn.outvars[0]))
                continue
            tout = self._descend(eqn, tin)
            for v, t in zip(eqn.outvars, tout):
                if t:
                    tainted.add(id(v))
        for words in splits.values():
            whole = (
                set(words) == {"lo", "hi"}
                and all(stored for stored, _ in words.values())
            )
            if whole:
                self._charge("split64", words["lo"][1])
            else:  # a lone or dropped half IS a truncation
                for _stored, site in words.values():
                    self._charge("to_i32", site)
        return [is_t(v) for v in j.outvars]

    def _charge(self, bucket: str, site: str) -> None:
        self.casts[bucket] += 1
        self.sites.setdefault(bucket, []).append(site)

    def _descend(self, eqn, tin: List[bool]) -> List[bool]:
        name = eqn.primitive.name
        p = eqn.params
        if name in ("pjit", "jit") or (
            "jaxpr" in p and name in ("closed_call", "shard_map")
        ):
            return self.walk(p["jaxpr"], tin)
        if name in ("custom_jvp_call", "custom_vjp_call") and \
                p.get("call_jaxpr") is not None:
            return self.walk(p["call_jaxpr"], tin)
        if name == "scan":
            return self._fixpoint(
                p["jaxpr"], tin, n_carry=p["num_carry"],
                carry_lo=p["num_consts"],
            )
        if name == "while":
            nc, nb = p["cond_nconsts"], p["body_nconsts"]
            carry_in = tin[nc + nb:]
            body_tin = tin[nc:nc + nb] + carry_in
            out = self._fixpoint(
                p["body_jaxpr"], body_tin, n_carry=len(carry_in),
                carry_lo=nb,
            )
            return out
        if name == "cond":
            outs = None
            for br in p["branches"]:
                o = self.walk(br, tin[1:])
                outs = o if outs is None else [
                    a or b for a, b in zip(outs, o)
                ]
            return outs or [False] * len(eqn.outvars)
        if name == "sort":
            # A sort permutes each operand on its own: the keys decide
            # the order, and no value crosses from one operand into
            # another (the write-back's values ride through one sort
            # beside their targets, ops/state.py `write_order`).
            return list(tin)
        if name == "pallas_call":
            # Opaque: the Pallas kernel body is differentially tested
            # bit-exact against its XLA reference; taint stops here.
            return [False] * len(eqn.outvars)
        return self._tainted_outs(eqn, tin)

    def _fixpoint(self, jaxpr, tin: List[bool], n_carry: int,
                  carry_lo: int) -> List[bool]:
        """Loop bodies: iterate until the carried taint stabilizes."""
        tin = list(tin)
        for _ in range(8):
            out = self.walk(jaxpr, tin)
            carry_out = out[:n_carry]
            cur = tin[carry_lo:carry_lo + n_carry]
            nxt = [a or b for a, b in zip(cur, carry_out)]
            if nxt == cur:
                return out
            tin[carry_lo:carry_lo + n_carry] = nxt
        return out


class DtypeTaintChecker(Checker):
    name = "dtype-taint"

    def check(self, spec: KernelSpec, built: BuiltKernel,
              ctx: RunContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for sig_name, make_args in built.signatures.items():
            mask = taint_mask(make_args(), built.counters)
            walk = _Walk()
            walk.walk(ctx.jaxprs[spec.name][sig_name], mask)
            allowed = Counter(built.allowed_casts)
            for bucket, n in sorted(walk.casts.items()):
                lim = allowed.get(bucket, 0)
                if n > lim:
                    extra = walk.sites[bucket][lim:]
                    out.append(Finding(
                        checker=self.name, kernel=spec.name,
                        message=(
                            f"[{sig_name}] {n} tainted {bucket} cast(s) "
                            f"on int64 counter dataflow, budget {lim}; "
                            "undeclared: " + "; ".join(extra[:4])
                        ),
                    ))
            for bucket, lim in sorted(allowed.items()):
                if walk.casts.get(bucket, 0) < lim:
                    out.append(Finding(
                        checker=self.name, kernel=spec.name,
                        severity="warning",
                        message=(
                            f"[{sig_name}] declared {bucket} budget "
                            f"{lim} but observed "
                            f"{walk.casts.get(bucket, 0)} — shrink the "
                            "declaration (stale budget hides the next "
                            "regression)"
                        ),
                    ))
            break  # taint structure is signature-invariant; one is enough
        return out
