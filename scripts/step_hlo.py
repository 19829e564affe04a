#!/usr/bin/env python3
"""What the TPU's compiler makes of the step program — without a chip.

The compiler for the v5e is installed beside JAX and compiles for a chip
that is described, not attached (`jax.experimental.topologies`), in a few
seconds a program.  This script compiles the served path's step programs
at the deployment geometry and prints what a device trace only names:

  * `memory_analysis()`: argument, alias (donated, updated in place) and
    temp bytes;
  * every op of the entry computation whose result is of TABLE LENGTH,
    with its custom-call target — the boundary conversions
    (`X64SplitLow` / `X64SplitHigh` / `X64Combine`) a 64-bit table column
    costs on a machine with 32-bit registers show up here, as do the
    compiler's own table-length copies;
  * every `while` and `sort`, with the `op_name` that says which line of
    the kernel it is;
  * every scatter into a table-length column (the write-back,
    `ops/state.py` `write_rows`): its index type, what it promises about
    its indices (`indices_are_sorted`, `unique_indices`) and the scoped
    memory its fusion uses, which tells the compiler's two scatter
    emitters apart (some 132 KB: it walks the updates; 16 MB: it streams
    the column).

Programs: the one-chip `apply_batch_packed_q` at each rung of the compiled
widths (`runtime/backend.py` `default_tiers`), and over the four described
devices the mesh step (`make_sharded_step_packed`) at each and
the GLOBAL sync program (`make_global_sync_step_psum`: two applies and a
store of broadcast rows a launch).

    JAX_PLATFORMS=cpu python scripts/step_hlo.py                # 2^24 slots
    JAX_PLATFORMS=cpu python scripts/step_hlo.py --json out.json

Nothing runs, so nothing here is a time.  tests/test_table_layout.py
calls `describe()` / `analyze_*()` and holds the step to "no table-length
X64 conversion but remaining_f's".
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

X64_TARGETS = ("X64SplitLow", "X64SplitHigh", "X64Combine")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\S.*?)\s+"
    r"(?P<opcode>[\w\-]+)\("
)
_DIMS_RE = re.compile(r"\[([\d,]*)\]")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_SCOPED_RE = re.compile(
    r'"used_scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"')
_SCATTER_ARGS_RE = re.compile(r"\bscatter\(([^)]*)\)")


def describe(topology_name: str = "v5e:2x2"):
    """The described topology (raises where it cannot be described)."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology_name
    )


def _entry_lines(hlo: str) -> List[str]:
    out: List[str] = []
    inside = False
    for line in hlo.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if inside:
            if line.startswith("}"):
                break
            out.append(line)
    return out


def _dims(shape: str) -> List[int]:
    return [
        int(d) for m in _DIMS_RE.finditer(shape)
        for d in m.group(1).split(",") if d
    ]


def table_scatters(hlo: str, table_len: int) -> List[dict]:
    """Every `scatter` of the module whose result is of table length —
    inside the fusion the compiler wrapped it in — with its index type
    and promises, and the fusion's name and scoped memory."""
    shapes: Dict[str, str] = {}
    computation = ""
    fusions: Dict[str, dict] = {}
    found = []
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            head = line.split("(", 1)[0].split()
            computation = head[-1].lstrip("%") if head else ""
            continue
        m = _OP_RE.match(line)
        if m is None:
            continue
        shapes[m.group("name")] = m.group("shape")
        if m.group("opcode") == "fusion":
            calls, scoped = _CALLS_RE.search(line), _SCOPED_RE.search(line)
            if calls:
                fusions[calls.group(1)] = {
                    "fusion": m.group("name"),
                    "scoped_bytes": int(scoped.group(1)) if scoped else 0,
                }
        if m.group("opcode") == "scatter" \
                and table_len in _dims(m.group("shape")):
            found.append((computation, m.group("name"), line))
    out = []
    for computation, name, line in found:
        args = _SCATTER_ARGS_RE.search(line).group(1).split(",")
        index = shapes.get(args[1].strip().lstrip("%"), "?")
        out.append({
            "name": name,
            "index_dtype": index.split("[", 1)[0],
            "indices_are_sorted": "indices_are_sorted=true" in line,
            "unique_indices": "unique_indices=true" in line,
            **fusions.get(computation, {"fusion": None, "scoped_bytes": 0}),
        })
    return out


def summarize(compiled, table_len: int) -> dict:
    """The account of one compiled program (see the module docstring)."""
    hlo = compiled.as_text()
    table_ops, loops = [], []
    for line in _entry_lines(hlo):
        m = _OP_RE.match(line)
        if m is None:
            continue
        opcode = m.group("opcode")
        target = _TARGET_RE.search(line)
        op_name = _OPNAME_RE.search(line)
        rec = {
            "name": m.group("name"),
            "opcode": opcode,
            "shape": m.group("shape"),
            "target": target.group(1) if target else None,
            "op_name": op_name.group(1) if op_name else None,
        }
        if opcode in ("while", "sort"):
            loops.append(rec)
        if opcode in ("parameter", "get-tuple-element", "tuple",
                      "bitcast"):
            continue
        if table_len in _dims(m.group("shape")):
            table_ops.append(rec)
    mem = compiled.memory_analysis()
    x64 = [r for r in table_ops if r["target"] in X64_TARGETS]
    # A column the compiler stages through fast memory around its
    # gathers and scatter: whole (a copy-start/copy-done pair) or in
    # slices joined again (slice-starts and one ConcatBitcast).
    staged = [r for r in table_ops if r["opcode"] == "copy-start"
              or r["target"] == "ConcatBitcast"]
    return {
        "table_len": table_len,
        "memory": {
            k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes",
            )
        },
        "table_length_ops": table_ops,
        "table_length_x64": len(x64),
        "x64_by_target": {
            t: sum(1 for r in x64 if r["target"] == t)
            for t in X64_TARGETS
        },
        "loops": loops,
        "staged_columns": len(staged),
        "table_copies": [r for r in table_ops if r["opcode"] == "copy"],
        "table_scatters": table_scatters(hlo, table_len),
    }


def table_bytes(table) -> int:
    import jax
    import numpy as np

    return sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(table)
    )


def _abstract_table(num_slots: int, sharding):
    import jax

    from gubernator_tpu.ops.state import init_table

    shapes = jax.eval_shape(lambda: init_table(num_slots))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes,
    )


def analyze_step(topo, num_slots: int, lanes: int, ways: int = 8) -> dict:
    """The one-chip step program at `lanes` lanes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from gubernator_tpu.ops.step import apply_batch_packed_q

    one = SingleDeviceSharding(topo.devices[0])
    table = _abstract_table(num_slots, one)
    q = jax.ShapeDtypeStruct((12, lanes), jnp.int64, sharding=one)
    now = jax.ShapeDtypeStruct((), jnp.int64, sharding=one)
    compiled = apply_batch_packed_q.lower(
        table, q, now, ways=ways
    ).compile()
    out = summarize(compiled, num_slots)
    out.update(program=f"apply_batch_packed_q[B={lanes}]",
               table_bytes=table_bytes(table))
    return out


def analyze_mesh_step(topo, num_slots: int, lanes: int,
                      ways: int = 8) -> dict:
    """The mesh step over every described device; a shard holds
    num_slots / n rows, and the account is per device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.parallel.mesh import SHARD_AXIS, make_mesh
    from gubernator_tpu.parallel.sharded import make_sharded_step_packed

    n = len(topo.devices)
    mesh = make_mesh(n, devices=topo.devices)
    table = _abstract_table(num_slots, NamedSharding(mesh, P(SHARD_AXIS)))
    q = jax.ShapeDtypeStruct(
        (12, n, lanes), jnp.int64,
        sharding=NamedSharding(mesh, P(None, SHARD_AXIS)),
    )
    now = jax.ShapeDtypeStruct(
        (), jnp.int64, sharding=NamedSharding(mesh, P())
    )
    compiled = make_sharded_step_packed(mesh, ways).lower(
        table, q, now
    ).compile()
    out = summarize(compiled, num_slots // n)
    out.update(program=f"sharded_step_packed[n={n}, B={lanes}]",
               table_bytes=table_bytes(table) // n)
    return out


def analyze_global_sync(topo, num_slots: int, delta_slots: int = 256,
                        ways: int = 8) -> dict:
    """`GlobalEngine`'s sync program over every described device, at the
    engine's defaults: `delta_slots` lanes an owner, a replicated cache
    table of `num_slots` rows beside the authoritative one."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.parallel.global_sync import (
        make_global_sync_step_psum,
        zero_delta_grid,
    )
    from gubernator_tpu.parallel.mesh import SHARD_AXIS, make_mesh

    n = len(topo.devices)
    mesh = make_mesh(n, devices=topo.devices)
    rows = NamedSharding(mesh, P(SHARD_AXIS))
    table = _abstract_table(num_slots, rows)
    delta = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows),
        zero_delta_grid(n, delta_slots),
    )
    now = jax.ShapeDtypeStruct(
        (), "int64", sharding=NamedSharding(mesh, P())
    )
    compiled = make_global_sync_step_psum(mesh, ways).lower(
        table, table, delta, now
    ).compile()
    out = summarize(compiled, num_slots // n)
    out.update(program=f"global_sync_step_psum[n={n}, D={delta_slots}]",
               table_bytes=2 * table_bytes(table) // n)
    return out


def _print(rep: dict) -> None:
    mem = rep["memory"]
    print(f"== {rep['program']}  (table length {rep['table_len']}, "
          f"{rep['table_bytes'] / 1e6:.1f} MB of table)")
    for k, v in mem.items():
        print(f"   {k:26s} {v / 1e6:10.1f} MB")
    print(f"   table-length X64 conversions: {rep['table_length_x64']} "
          f"{rep['x64_by_target']}")
    print(f"   columns staged through fast memory: "
          f"{rep['staged_columns']}; synchronous table-length copies: "
          f"{len(rep['table_copies'])}")
    print(f"   table-length ops in the entry computation "
          f"({len(rep['table_length_ops'])}):")
    for r in rep["table_length_ops"]:
        print(f"     {r['name']:28s} {r['opcode']:14s} "
              f"{r['target'] or '':14s} {r['shape'][:40]:40s} "
              f"{(r['op_name'] or '')[-60:]}")
    print(f"   while / sort ({len(rep['loops'])}):")
    for r in rep["loops"]:
        print(f"     {r['name']:28s} {r['opcode']:6s} "
              f"{(r['op_name'] or '')[-80:]}")
    print(f"   table-length scatters ({len(rep['table_scatters'])}):")
    for r in rep["table_scatters"]:
        print(f"     {r['fusion'] or r['name']:28s} index {r['index_dtype']:4s}"
              f" sorted={r['indices_are_sorted']!s:5s} "
              f"unique={r['unique_indices']!s:5s} "
              f"scoped {r['scoped_bytes']:>10,d} B")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slots", type=int, default=1 << 24)
    ap.add_argument("--tiers", default="",
                    help="comma-separated step tiers (lanes); default: "
                         "the ladder of --batch-size")
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--mesh-lanes", default="",
                    help="the mesh step's tiers; default: --tiers "
                         "above 128 lanes")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--json", default="", help="also write the reports here")
    args = ap.parse_args(argv)

    import gubernator_tpu.ops  # noqa: F401 — switches x64 on

    from gubernator_tpu.runtime.backend import default_tiers

    def lanes_of(text: str, default) -> List[int]:
        return [int(t) for t in text.split(",") if t] or list(default)

    topo = describe(args.topology)
    tiers = lanes_of(args.tiers, default_tiers(args.batch_size))
    mesh_tiers = lanes_of(args.mesh_lanes, [t for t in tiers if t > 128])
    reports: Dict[str, dict] = {}
    programs = (
        [(analyze_step, lanes) for lanes in tiers]
        + [(analyze_mesh_step, lanes) for lanes in mesh_tiers]
        + [(analyze_global_sync,)]
    )
    for analyze, *lanes in programs:
        rep = analyze(topo, args.slots, *lanes)
        reports[rep["program"]] = rep
        _print(rep)
    if args.json:
        Path(args.json).write_text(json.dumps(reports, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
