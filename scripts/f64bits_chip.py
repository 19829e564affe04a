#!/usr/bin/env python3
"""The leaky lanes' binary64 on the device: the same bits, and what they cost.

    chiprun --timeout 1500 -- python scripts/f64bits_chip.py
    chiprun --timeout 1500 -- python scripts/f64bits_chip.py --repo .checkout/parent
    JAX_PLATFORMS=cpu python scripts/f64bits_chip.py --platform cpu \\
        --slots 65536 --reps 3                        # dry run here

One JSON object on the last line of stdout (also --out/summary.json):

  exact    `ops/f64bits.py` ON THIS DEVICE against numpy's float64, bit for
           bit: seeded operand pairs an operation, and the grids of
           bench/witness/leaky_steps.py written through the integer
           arithmetic (the witness spells them in XLA's float64, `a + q`,
           which the step no longer computes in; its `program` line runs
           the step itself and is run here as it stands): `quotient`,
           `add`, `take`, `chain` (4,096 lanes x 400 steps, the witness's
           seed), and the whole multiples of 2.592 ms.  Any departure: exit
           code 1.  Left out for a tree that has no f64bits (--repo of the
           parent).
  program  bench/witness/leaky_steps.py `program()`: DeviceBackend.check
           against core/pymodel.py, 92,488 leaky answers.
  ms       host clock around `block_until_ready`: `apply_batch_packed_q` at
           128 and 4096 lanes on a 2^24-slot table whose keys are half leaky
           (table donated and fed back, as the backend runs it), with
           f64bits.div's loop at other unrolls beside the served one, and
           `table_stats` (the census).
  ops      a profiler trace of --reps launches a tier: the device time a
           launch of the ops that are the leaky arithmetic — under the
           `leaky_f64bits` scope here; in a tree without it, the ops whose
           result holds a float (the parent's step computes in floats
           nowhere else) and the table-length X64 conversions of its
           float64 column.

`--repo DIR` measures another checkout's `gubernator_tpu` (the parent's)
with this script.  It fails where there is no TPU unless `--platform cpu`
is given, and a number from such a run is a rehearsal, not a device time.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
WAYS = 8
NOW = 1_790_000_000_000


def _load(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ms_per_call(fn, reps: int) -> float:
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def exact(F, jax, jnp, np) -> dict:
    """f64bits on the default device against numpy, bit for bit."""
    rng = np.random.default_rng(34)
    n = 1 << 20
    out = {}

    def count(name, got, want):
        got = np.asarray(got)
        nan = np.isnan(want) & np.isnan(F.from_bits(got))
        out[name] = {"tried": int(want.size),
                     "differ": int(((got != F.to_bits(want)) & ~nan).sum())}

    bits = rng.integers(-2**63, 2**63 - 1, n, dtype=np.int64,
                        endpoint=True)
    near = bits ^ rng.integers(0, 1 << 54, n)
    ints = (rng.integers(-2**63, 2**63 - 1, n, dtype=np.int64, endpoint=True)
            >> rng.integers(0, 64, n))
    quot = (rng.integers(0, 1 << 44, n).astype(np.float64)
            / rng.integers(1, 1 << 31, n).astype(np.float64))
    a, b = bits.view(np.float64), near.view(np.float64)
    fi, fq = ints.astype(np.float64), quot
    with np.errstate(all="ignore"):
        count("add.any_bits", jax.jit(F.add)(bits, near), a + b)
        count("sub.any_bits", jax.jit(F.sub)(bits, near), a - b)
        count("add.ints_quotients",
              jax.jit(F.add)(F.to_bits(fi), F.to_bits(fq)), fi + fq)
        count("mul.ints", jax.jit(F.mul)(F.to_bits(fi), F.to_bits(fi[::-1])),
              fi * fi[::-1])
        count("mul.ints_quotients",
              jax.jit(F.mul)(F.to_bits(fi), F.to_bits(fq)), fi * fq)
        nz = np.where(fi == 0, 1.0, fi)
        count("div.ints", jax.jit(F.div)(F.to_bits(fi[::-1]), F.to_bits(nz)),
              fi[::-1] / nz)
        qz = np.where(fq == 0, 1.0, fq)
        count("div.ints_quotients",
              jax.jit(F.div)(F.to_bits(fi), F.to_bits(qz)), fi / qz)
    count("from_i64", jax.jit(F.from_i64)(ints), ints.astype(np.float64))
    tr = np.asarray(jax.jit(F.trunc_i64)(F.to_bits(fq * 1000.0)))
    out["trunc_i64"] = {"tried": n, "differ": int(
        (tr != np.trunc(fq * 1000.0).astype(np.int64)).sum())}

    # bench/witness/leaky_steps.py `grids`, at 100 a second.
    limit, duration = 100, 1000
    rate = duration / limit
    tenths = np.arange(0, 10 * limit + 1, dtype=np.int64)
    elapsed = np.arange(int(rate), 40 * int(rate) + 1, dtype=np.int64)
    lb = np.repeat(tenths, len(elapsed)).astype(np.float64) / 10.0
    e = np.tile(elapsed, len(tenths))

    @jax.jit
    def grid(lb_bits, e, dur, lim):
        q = F.div(F.from_i64(e), F.div(F.from_i64(dur), F.from_i64(lim)))
        s = F.add(lb_bits, q)
        d = F.sub(lb_bits, F.ONE)
        return q, s, F.trunc_i64(s), d, F.trunc_i64(d)

    q, s, s_i, d, d_i = (np.asarray(x) for x in grid(
        F.to_bits(lb), e, np.full_like(e, duration), np.full_like(e, limit)))
    want_q = e.astype(np.float64) / rate
    one = len(elapsed)
    out["quotient"] = {"tried": one, "value_differs": int(
        (q[:one] != F.to_bits(want_q[:one])).sum())}
    for name, got_f, got_i, want in (("add", s, s_i, lb + want_q),
                                     ("take", d, d_i, lb - 1.0)):
        out[name] = {
            "tried": int(want.size),
            "truncation_differs": int(
                (got_i != np.trunc(want).astype(np.int64)).sum()),
            "value_differs": int((got_f != F.to_bits(want)).sum()),
            "ieee_whole": int((want == np.trunc(want)).sum()),
        }

    # The whole multiples of 2.592 ms (10^9 in 30 days) up to 20 s.
    dur30, lim9 = 30 * 24 * 3600 * 1000, 10**9
    el = np.arange(0, 20_001, dtype=np.int64)

    @jax.jit
    def leak(el):
        r = F.div(F.from_i64(jnp.int64(dur30)), F.from_i64(jnp.int64(lim9)))
        x = F.div(F.from_i64(el), r)
        return x, F.trunc_i64(x)

    x, x_i = (np.asarray(v) for v in leak(el))
    want = el.astype(np.float64) / (dur30 / lim9)
    mult = (el * 1000) % 2592 == 0
    out["multiples_of_2592us"] = {
        "tried": int(el.size), "whole_multiples": int(mult.sum()),
        "value_differs": int((x != F.to_bits(want)).sum()),
        "truncation_differs": int(
            (x_i != np.trunc(want).astype(np.int64)).sum()),
    }

    # `chain`: spend one, wait, leak — the remainder carried on the device.
    lanes, steps = 4096, 400
    waits = np.random.default_rng(33).integers(
        1, 4 * int(rate), size=(steps, lanes)).astype(np.int64)
    f_rate = F.const(rate)
    f_lim = F.const(float(limit))

    def one_step(carry, wait):
        lb, pending = carry
        lb = jnp.where(F.trunc_i64(lb) > 0, F.sub(lb, F.ONE), lb)
        el = pending + wait
        lk = F.div(F.from_i64(el), f_rate)
        leaked = F.trunc_i64(lk) > 0
        lb = jnp.where(leaked, F.add(lb, lk), lb)
        lb = jnp.where(F.trunc_i64(lb) > limit, f_lim, lb)
        return (lb, jnp.where(leaked, 0, el)), (F.trunc_i64(lb), lb)

    @jax.jit
    def device(waits):
        start = (jnp.full(lanes, f_lim, jnp.int64),
                 jnp.zeros(lanes, jnp.int64))
        return jax.lax.scan(one_step, start, waits)[1]

    got_i, got_f = (np.asarray(v) for v in device(waits))
    lbh = np.full(lanes, float(limit))
    pending = np.zeros(lanes, np.int64)
    departed = np.zeros(lanes, bool)
    trunc_differ = value_differ = 0
    for t in range(steps):
        lbh = np.where(np.trunc(lbh) > 0, lbh - 1.0, lbh)
        el = pending + waits[t]
        lk = el.astype(np.float64) / rate
        leaked = np.trunc(lk) > 0
        lbh = np.where(leaked, lbh + lk, lbh)
        lbh = np.where(np.trunc(lbh) > limit, float(limit), lbh)
        pending = np.where(leaked, 0, el)
        off = got_i[t] != np.trunc(lbh)
        departed |= off
        trunc_differ += int(off.sum())
        value_differ += int((got_f[t] != F.to_bits(lbh)).sum())
    out["chain"] = {"lanes": lanes, "steps": steps,
                    "lanes_that_depart": int(departed.sum()),
                    "truncations_that_differ": trunc_differ,
                    "values_that_differ": value_differ}
    return out


def _departures(block: dict) -> int:
    return sum(v for row in block.values() for k, v in row.items()
               if "differ" in k or k == "lanes_that_depart")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--repo", default=str(HERE),
                    help="the checkout whose gubernator_tpu is measured")
    ap.add_argument("--slots", type=int, default=1 << 24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--unrolls", default="1,8,28",
                    help="f64bits.div loop unrolls to time the step at")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    repo = Path(args.repo).resolve()
    sys.path.insert(0, str(repo))
    out_dir = Path(args.out or HERE / "chiprun_out" / (
        "f64bits" if repo == HERE else "f64bits_" + repo.name))
    out_dir.mkdir(parents=True, exist_ok=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import gubernator_tpu.ops  # noqa: F401 — x64 on, compile cache
    from gubernator_tpu.ops import state as st
    from gubernator_tpu.ops import step as sp

    assert Path(sp.__file__).resolve().is_relative_to(repo), sp.__file__
    try:
        from gubernator_tpu.ops import f64bits as F
    except ImportError:
        F = None

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"wanted {args.platform}, found {dev.platform}", file=sys.stderr)
        return 2
    summary = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "repo": str(repo), "slots": args.slots, "f64bits": F is not None,
        "ms": {}, "leaky_ops_ms_per_launch": {},
    }
    ok = True
    if F is not None:
        summary["exact"] = exact(F, jax, jnp, np)
        ok = _departures(summary["exact"]) == 0

    witness = _load("leaky_steps", repo / "bench/witness/leaky_steps.py")
    summary["program"] = witness.program(np, 100, 1000)
    ok = ok and (F is None or summary["program"]["differ"] == 0)

    # A table whose keys are half leaky, every row live, and batches of
    # resident keys: the leaky lanes leak, add and take on every launch.
    S = args.slots
    nb = S // WAYS
    rng = np.random.default_rng(34)
    slot = np.arange(0, S, 2, dtype=np.int64)
    key = ((slot // WAYS) | (rng.integers(1, 1 << 30, len(slot)) << 32)
           ).astype(np.int64)
    arrs = {f: np.zeros(S, np.int64 if f in st.INT64_FIELDS else np.int32)
            for f in st.SlotTable._fields}
    arrs["remaining_f"] = np.zeros(S, np.float64)
    leaky = (slot // WAYS) % 2 == 1
    arrs["key"][slot] = key
    arrs["algo"][slot] = leaky
    arrs["limit"][slot] = 100
    arrs["duration"][slot] = 1000
    arrs["burst"][slot] = np.where(leaky, 100, 0)
    arrs["remaining"][slot] = np.where(leaky, 0, 50)
    arrs["remaining_f"][slot] = np.where(leaky, 42.5, 0.0)
    arrs["t0"][slot] = NOW - 25
    arrs["expire_at"][slot] = NOW + 975
    arrs["touched"][slot] = NOW - 25
    assert (key & (nb - 1) == slot // WAYS).all()
    table = st.table_from_host(arrs)
    now = jnp.int64(NOW)

    def batch(B):
        pick = rng.choice(len(slot), B, replace=False)
        q = np.zeros((12, B), np.int64)
        q[0], q[1], q[2], q[3] = key[pick], 1, 100, 1000
        q[4], q[5], q[10] = leaky[pick], 100, 1
        return jnp.asarray(q)

    def stepper(fn, B):
        state = {"table": jax.tree_util.tree_map(jnp.copy, table)}
        q = batch(B)

        def step():
            state["table"], resp = fn(state["table"], q, now, ways=WAYS)
            return resp

        return step

    steps = {}
    for B in (128, 4096):
        steps[B] = stepper(sp.apply_batch_packed_q, B)
        summary["ms"][f"apply_batch_packed_q.B{B}"] = _ms_per_call(
            steps[B], args.reps)
    if F is not None and hasattr(F, "_DIV_UNROLL"):
        served = F._DIV_UNROLL
        for u in (int(x) for x in args.unrolls.split(",") if x):
            if u == served:
                continue
            F._DIV_UNROLL = u
            fn = jax.jit(   # a wrapper of its own: jit caches by function
                lambda t, q, now, ways, _u=u: sp.apply_batch_packed_q_impl(
                    t, q, now, ways),
                static_argnames=("ways",), donate_argnums=(0,))
            for B in (128, 4096):
                t0 = time.perf_counter()
                step = stepper(fn, B)
                jax.block_until_ready(step())
                summary["ms"][f"first_call_s.div_unroll_{u}.B{B}"] = (
                    time.perf_counter() - t0)
                summary["ms"][
                    f"apply_batch_packed_q.div_unroll_{u}.B{B}"
                ] = _ms_per_call(step, args.reps)
        F._DIV_UNROLL = served
        summary["div_unroll_served"] = served

    fps = jnp.zeros((len(st.SHADOW_PLANES), 8), jnp.int64)
    summary["ms"]["table_stats"] = _ms_per_call(
        lambda: st.table_stats(table, fps, now, ways=WAYS),
        max(args.reps // 4, 2))

    if dev.platform == "tpu":
        from jax.profiler import ProfileData

        step_hlo = _load("step_hlo", HERE / "scripts" / "step_hlo.py")
        trace_lib = _load("bench_trace", HERE / "bench" / "lib" / "trace.py")
        for B in (128, 4096):
            hlo = sp.apply_batch_packed_q.lower(
                table, jax.ShapeDtypeStruct((12, B), jnp.int64), now,
                ways=WAYS).compile().as_text()
            # HLO result name -> (shape, op_name, custom-call target, is it
            # an op of the ENTRY computation).  A trace's rows nest: a
            # `while` row holds its body's rows, so only entry ops are
            # summed.
            meta, in_entry = {}, False
            for line in hlo.splitlines():
                if line.startswith("ENTRY "):
                    in_entry = True
                elif line.startswith("}"):
                    in_entry = False
                m = step_hlo._OP_RE.match(line)
                if m:
                    n = step_hlo._OPNAME_RE.search(line)
                    t = step_hlo._TARGET_RE.search(line)
                    meta.setdefault(m.group("name"), (
                        m.group("shape"), n.group(1) if n else "",
                        t.group(1) if t else "", in_entry))
            trace_dir = out_dir / f"trace{B}"
            jax.profiler.start_trace(str(trace_dir))
            for _ in range(args.reps):
                resp = steps[B]()
            jax.block_until_ready(resp)
            jax.profiler.stop_trace()
            totals: dict = {}
            pd = ProfileData.from_file(trace_lib.find_xplane(str(trace_dir)))
            for plane in pd.planes:
                if not trace_lib.DEVICE_PLANE.match(plane.name):
                    continue
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        k = trace_lib.short_op(ev.name)
                        totals[k] = totals.get(k, 0.0) + ev.duration_ns
            shutil.rmtree(trace_dir, ignore_errors=True)
            rows = []
            for k, v in totals.items():
                shape, op_name, target, entry = meta.get(
                    k, ("", "", "", True))
                if not entry:
                    continue
                is_leaky = (
                    "leaky_f64bits" in op_name if F is not None else
                    bool(re.search(r"\bf(32|64)\[", shape))
                    or target in step_hlo.X64_TARGETS)
                rows.append([k, v / args.reps / 1e6, op_name, is_leaky])
            rows.sort(key=lambda r: -r[1])
            (out_dir / f"ops{B}.json").write_text(
                json.dumps(rows, indent=0) + "\n")
            summary["leaky_ops_ms_per_launch"][f"B{B}"] = {
                "leaky_ms": sum(r[1] for r in rows if r[3]),
                "leaky_ops": sum(1 for r in rows if r[3]),
                "all_ms": sum(r[1] for r in rows), "all_ops": len(rows),
                "largest_leaky": [r[:3] for r in rows if r[3]][:6],
            }

    summary["ok"] = bool(ok)
    line = json.dumps(summary)
    (out_dir / "summary.json").write_text(line + "\n")
    print(line)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
