#!/usr/bin/env python3
"""`locate_slots` on the device: the same answers, and what they cost.

    chiprun --timeout 900 -- python scripts/claim_rounds_chip.py
    JAX_PLATFORMS=cpu python scripts/claim_rounds_chip.py --platform cpu \\
        --slots 65536 --reps 3                        # dry run here

Five things, one JSON object on the last line of stdout (also written to
--out/summary.json):

  identical  seeded tables and batches (tests/test_locate_slots.py's
             generator: the served geometry with 2 % of the lanes missing,
             a cold table, the misses crowded into three buckets, every
             bucket full) through the served `locate_slots` and through
             the three-round reference frozen in that test file, on THIS
             device, at 4096 and 128 lanes: (found, persist, slot,
             slot_safe) must be bit-identical, or the exit code is 1.
  ms         host clock around `block_until_ready`, per call: the
             reference and the served `locate_slots` alone, and the whole
             step program `apply_batch_packed_q`, at both tiers.
  ops        a profiler trace of --reps launches of the 4096-lane step:
             every HLO op's device time per launch with the `op_name` the
             compiled program gives it (--out/ops.json holds all of them,
             the summary the first 25) — what the step is made of.

  write_back the step at both tiers under each way of handing the
             write-back's rows to XLA's scatter: {int64, int32} targets x
             {sorted once or not} x {told unique or not}
             (tests/test_write_back.py `variant_write_rows`;
             `int64.unsorted.any` is the plain scatter the step had until
             PR 36; a sorted one puts the values in the targets' order by
             a gather a vector), the sorted one with the values riding
             through the sort as its operands, and `served`, which is
             `ops/state.py` `write_rows` as it stands.  Each must leave the
             table and the response bit-identical with the plain one's —
             `served` on all four seeded cases — or the exit code is 1.
             Per variant: ms a launch by the host clock, and from a trace
             of --reps launches the device's ms a launch, of which the
             table scatters', the sorts' and the gathers'
             (--out/write_back.json holds every op).

  rungs      the served step at every rung of the compiled widths
             (`runtime/backend.py` `default_tiers(4096)`, or --rungs) on
             the 2^24- and the 2^22-slot table (--rung-slots), full and with
             three lanes in ten active (an overflow's few hundred, an
             owner's thousand: lanes fill from 0, as the packer fills
             them): ms a launch by the host clock, and from a trace of
             --reps launches the device's ms a launch, of which the table
             scatters', the sorts' and the gathers', and the launch as a
             share of the full 4096-lane one's and of the 4096-lane
             program's carrying the same lanes (what the round paid before
             it had a rung; --out/rungs.json holds every op).  `--only
             rungs` runs nothing else.

It fails where there is no TPU unless `--platform cpu` is given, and a
number from such a run is a rehearsal, not a device time.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))

WAYS = 8
# (seed, fill, expired, resident, inactive, crowd) of _random_case.
CASES = {
    "served": (3, 0.6, 0.0, 0.98, 0.0, 0),
    "cold": (1, 0.0, 0.0, 0.0, 0.0, 0),
    "crowded": (4, 0.9, 0.5, 0.3, 0.2, 3),
    "full": (5, 1.0, 0.0, 0.1, 0.05, 0),
}


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


def _op_names(hlo: str) -> dict:
    """HLO result name -> the op_name metadata of its line (the patterns
    are scripts/step_hlo.py's)."""
    step_hlo = _load("step_hlo", REPO / "scripts" / "step_hlo.py")
    out = {}
    for line in hlo.splitlines():
        m = step_hlo._OP_RE.match(line)
        n = step_hlo._OPNAME_RE.search(line)
        if m and n:
            out.setdefault(m.group("name"), n.group(1))
    return out


def _trace_ops(fn, reps: int, trace_dir: Path, names: dict, traced: bool):
    """Trace `reps` calls of `fn`: (the device's ms a launch by the
    programs' own events, [[op, ms a launch, op_name]] longest first).
    Where there is no TPU the calls are made and nothing is read."""
    import jax

    jax.profiler.start_trace(str(trace_dir))
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    launch_ms, rows = None, []
    if traced:
        from jax.profiler import ProfileData

        # The benchmark's reduction, by path: its name is the standard
        # library's.
        trace_lib = _load("bench_trace", REPO / "bench" / "lib" / "trace.py")
        totals: dict = {}
        module_ns = 0.0
        pd = ProfileData.from_file(trace_lib.find_xplane(str(trace_dir)))
        for plane in pd.planes:
            if not trace_lib.DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    module_ns += sum(ev.duration_ns for ev in line.events)
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    k = trace_lib.short_op(ev.name)
                    totals[k] = totals.get(k, 0.0) + ev.duration_ns
        launch_ms = module_ns / reps / 1e6
        rows = sorted(
            ([k, v / reps / 1e6, names.get(k, "")]
             for k, v in totals.items()), key=lambda r: -r[1])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return launch_ms, rows


def _device_parts(device_ms: float, rows) -> dict:
    """A traced launch's device ms, of which the table scatters', the
    sorts' and the gathers' (`rows`: _trace_ops's, (op, ms, op_name))."""
    def of(suffix):
        return sum(row[1] for row in rows if row[2].endswith(suffix))

    return {"device_ms": device_ms, "scatter_ms": of("/scatter"),
            "sort_ms": of("/sort"), "gather_ms": of("/gather")}


def _write_back(served: dict, args, now, trace_dir: Path, traced: bool):
    """The step under each variant of the write-back (module docstring):
    (all identical, {tier: {variant: readings}}, {tier.variant: ops})."""
    import jax
    import jax.numpy as jnp

    import test_locate_slots as ref
    import test_write_back as wb
    from gubernator_tpu.ops import state as st
    from gubernator_tpu.ops import step as sp

    variants = {"served": st.write_rows}
    variants.update(
        (name, wb.variant_write_rows(*v)) for name, v in wb.VARIANTS.items())
    variants["int32.sorted.any.through_the_sort"] = wb.variant_write_rows(
        jnp.int32, True, False, via="sort")
    plain = "int64.unsorted.any"

    def compiled_step(write, table, q):
        # `write_rows` is looked up when the step is traced, and a trace
        # is cached by the function traced: a new one for every variant.
        def step(table, q, now):
            return sp.apply_batch_packed_q_impl(table, q, now, ways=WAYS)

        sp.write_rows = write
        try:
            return jax.jit(step, donate_argnums=(0,)).lower(
                table, q, now).compile()
        finally:
            sp.write_rows = st.write_rows

    def same(a, b) -> bool:
        return all(bool(jnp.array_equal(x, y)) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b),
            strict=True))

    def copy(table):
        return jax.tree_util.tree_map(jnp.copy, table)

    ok, report, ops = True, {}, {}
    for B, (table, h, active) in served.items():
        q = jnp.asarray(_batch_q(h, active))
        report[f"B{B}"] = tier = {}
        want = compiled_step(variants[plain], table, q)(copy(table), q, now)
        for name, write in variants.items():
            step = compiled_step(write, table, q)
            identical = same(step(copy(table), q, now), want)
            ok = ok and identical
            state = {"table": copy(table)}

            def launch(step=step, state=state, q=q):
                state["table"], resp = step(state["table"], q, now)
                return resp

            ms = _ms_per_call(launch, args.reps)
            device_ms, rows = _trace_ops(
                launch, args.reps, trace_dir, _op_names(step.as_text()),
                traced)

            tier[name] = {"identical": identical, "ms": ms}
            if traced:
                tier[name].update(_device_parts(device_ms, rows))
                ops[f"B{B}.{name}"] = rows
            del state, step
        # `served` against the plain scatter on the conflict-heavy cases
        # (B / 4 buckets: transient lanes, inactive lanes, full buckets).
        for case, (seed, *shape) in CASES.items():
            if case == "served":
                continue
            t, ch, ca = ref._random_case(
                args.seed + seed * 1000 + B, B, WAYS, max(B // 4, 1), *shape)
            cq = jnp.asarray(_batch_q(ch, ca))
            identical = same(
                compiled_step(st.write_rows, t, cq)(copy(t), cq, now),
                compiled_step(variants[plain], t, cq)(copy(t), cq, now))
            ok = ok and identical
            tier[f"served.identical.{case}"] = identical
    return ok, report, ops


def _rungs(args, now, trace_dir: Path, traced: bool):
    """The served step at every rung (module docstring):
    ({S<slots>: {B<rung>.<active lanes>: readings}}, {the same: ops})."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import test_locate_slots as ref
    from gubernator_tpu.ops import step as sp
    from gubernator_tpu.runtime.backend import default_tiers

    report, ops = {}, {}
    for slots in args.rung_slots:
        # The served geometry: 2 % of the lanes miss.
        table, h, _ = ref._random_case(
            args.seed + 7000, 4096, WAYS, slots // WAYS, *CASES["served"][1:])
        state = {"table": table}
        report[f"S{slots}"] = rows_of = {}
        tiers = args.rungs or default_tiers(4096)
        occupied = {B: (B, B * 3 // 10) for B in tiers}
        for B in tiers:
            q_shape = jax.ShapeDtypeStruct((12, B), jnp.int64)
            names = _op_names(sp.apply_batch_packed_q.lower(
                state["table"], q_shape, now, ways=WAYS).compile().as_text())
            # The full width carries every narrower rung's rounds too:
            # what each would have paid without its rung.
            for lanes in (occupied[B] if B < tiers[-1] else sorted(
                    {n for v in occupied.values() for n in v}, reverse=True)):
                q = jnp.asarray(_batch_q(h[:B], np.arange(B) < lanes))

                def launch(q=q):
                    state["table"], resp = sp.apply_batch_packed_q(
                        state["table"], q, now, ways=WAYS)
                    return resp

                r = {"ms": _ms_per_call(launch, args.reps)}
                device_ms, rows = _trace_ops(
                    launch, args.reps, trace_dir, names, traced)

                if traced:
                    r.update(_device_parts(device_ms, rows))
                    ops[f"S{slots}.B{B}.{lanes}"] = rows
                rows_of[f"B{B}.{lanes}"] = r
        if traced:
            top = f"B{tiers[-1]}."
            full = rows_of[f"{top}{tiers[-1]}"]["device_ms"]
            for name, r in rows_of.items():
                r["of_the_4096_lane_launch"] = r["device_ms"] / full
                r["of_the_same_lanes_at_4096"] = r["device_ms"] / rows_of[
                    top + name.split(".")[1]]["device_ms"]
        del state, table
    return report, ops


def _batch_q(h, active):
    """int64[12, B] request rows for the keys `h`: one hit against 1000 in
    30 days, token and leaky lanes alternating."""
    import numpy as np

    import test_locate_slots as ref

    q = np.zeros((12, len(h)), dtype=np.int64)
    q[0], q[1], q[2], q[3] = np.asarray(h), 1, 1000, 30 * ref.DAY
    q[4], q[5], q[10] = np.arange(len(h)) % 2, 1000, np.asarray(active)
    return q


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--slots", type=int, default=1 << 24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "claim_rounds"))
    ap.add_argument("--only", default="", choices=("", "rungs"))
    def ints(s):
        return [int(x) for x in s.split(",") if x]

    ap.add_argument("--rung-slots", default=f"{1 << 24},{1 << 22}",
                    type=ints)
    ap.add_argument("--rungs", default="", type=ints,
                    help="default: default_tiers(4096); PR 44 measured "
                         "128,1024,2048,4096")
    args = ap.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    import gubernator_tpu.ops  # noqa: F401 — x64 on, compile cache
    import test_locate_slots as ref
    from gubernator_tpu.ops import step as sp

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"wanted {args.platform}, found {dev.platform}", file=sys.stderr)
        return 2
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    nb = args.slots // WAYS
    now = jnp.int64(ref.NOW)
    summary = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "slots": args.slots, "identical": {}, "ms": {},
    }

    traced = dev.platform == "tpu"
    summary["rungs"], rung_ops = _rungs(args, now, out_dir / "trace", traced)
    (out_dir / "rungs.json").write_text(json.dumps(rung_ops, indent=0) + "\n")
    if args.only == "rungs":
        return _finish(summary, True, out_dir)

    ok = True
    served = {}
    for B in (4096, 128):
        for name, (seed, *shape) in CASES.items():
            # The served geometry once a tier; the conflict-heavy cases on
            # B / 4 buckets, where every round has contenders.
            case_nb = nb if name == "served" else max(B // 4, 1)
            table, h, active = ref._random_case(
                args.seed + seed * 1000 + B, B, WAYS, case_nb, *shape)
            try:
                found, persist, _, _ = ref._agree(table, h, active, WAYS)
                summary["identical"][f"{name}.B{B}"] = {
                    "identical": True, "found": int(found.sum()),
                    "won": int((persist & ~found).sum()),
                    "transient": int((active & ~persist).sum()),
                }
            except AssertionError as e:
                ok = False
                summary["identical"][f"{name}.B{B}"] = {
                    "identical": False, "differs": str(e)[:400]}
            if name == "served":
                served[B] = (table, jnp.asarray(h), jnp.asarray(active))

    for B, (table, h, active) in served.items():
        summary["ms"][f"locate_slots.reference.B{B}"] = _ms_per_call(
            lambda: ref.ref_locate_slots(table, h, active, now, ways=WAYS),
            args.reps)
        summary["ms"][f"locate_slots.served.B{B}"] = _ms_per_call(
            lambda: ref.new_locate_slots(table, h, active, now, ways=WAYS),
            args.reps)

    # The whole step, table donated and fed back, as the backend runs it.
    steps = {}
    for B, (table, h, active) in served.items():
        q = jnp.asarray(_batch_q(h, active))
        state = {"table": jax.tree_util.tree_map(jnp.copy, table)}

        def step(state=state, q=q):
            state["table"], resp = sp.apply_batch_packed_q(
                state["table"], q, now, ways=WAYS)
            return resp

        summary["ms"][f"apply_batch_packed_q.B{B}"] = _ms_per_call(
            step, args.reps)
        steps[B] = step

    # What the 4096-lane step is made of, op by op.
    table, h, _ = served[4096]
    q = jax.ShapeDtypeStruct((12, 4096), jnp.int64)
    names = _op_names(sp.apply_batch_packed_q.lower(
        table, q, now, ways=WAYS).compile().as_text())
    _, rows = _trace_ops(steps[4096], args.reps, out_dir / "trace", names,
                         traced)
    if traced:
        (out_dir / "ops.json").write_text(json.dumps(rows, indent=0) + "\n")
        summary["ops_ms_per_launch"] = rows[:25]
        summary["ops_counted"] = len(rows)

    wb_ok, summary["write_back"], wb_ops = _write_back(
        served, args, now, out_dir / "trace", traced)
    ok = ok and wb_ok
    (out_dir / "write_back.json").write_text(
        json.dumps(wb_ops, indent=0) + "\n")

    return _finish(summary, ok, out_dir)


def _finish(summary: dict, ok: bool, out_dir: Path) -> int:
    summary["ok"] = bool(ok)
    line = json.dumps(summary)
    (out_dir / "summary.json").write_text(line + "\n")
    print(line)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
