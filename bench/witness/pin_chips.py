#!/usr/bin/env python3
"""A witness: can four processes of one host each hold ONE chip, pinned by
their environment alone?  (ISSUE 37: it decides how bench/run.py starts a
cluster's daemons, and what a user of a four-chip host has to set.)

    chiprun --chips 4 --timeout 900 -- python3 bench/witness/pin_chips.py

The parent never imports JAX.  For each recipe in turn it starts four
children at once, child k with the recipe's variables for chip k; a child
brings JAX up, says what it sees (devices, ids, coordinates, the /dev
files it holds), waits until ALL FOUR have said so (they hold their chips
together), then traces a short span with the profiler, reads its own
trace's planes back, and says the peak memory of its chip.  The first
recipe under which all four answer wins; under it two more children are
then both given chip 0, to see whether the second is kept off.  Every
child is killed at a time limit: nothing is left holding a chip.  The
last stdout line is one JSON object; the children's stderr goes to
chiprun_out/pin_chips/.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
OUT = os.path.join(REPO, "chiprun_out", "pin_chips")
UP_S, DONE_S = 120.0, 120.0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def pin_env(k: int, port: int, addresses: bool, allow: bool) -> dict:
    """The harness's own pin (bench/lib/cluster.py), and what a recipe
    adds to it."""
    sys.path.insert(0, BENCH)
    from lib import cluster

    env = cluster.pin_env(k, port)
    if addresses:
        env["TPU_PROCESS_ADDRESSES"] = f"localhost:{port}"
        env["CLOUD_TPU_TASK_ID"] = "0"
    if allow:
        env["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    return env


def child(tag: str) -> None:
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    held = sorted({
        os.readlink(f"/proc/self/fd/{f}") for f in os.listdir("/proc/self/fd")
        if os.path.exists(f"/proc/self/fd/{f}")
    } & {
        os.path.join(d, n) for d in ("/dev", "/dev/vfio")
        if os.path.isdir(d) for n in os.listdir(d)
    })
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    (x @ x).block_until_ready()
    print(json.dumps({
        "up": True, "tag": tag, "seconds": round(time.monotonic() - t0, 2),
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "ids": [d.id for d in devs],
        "coords": [list(getattr(d, "coords", ())) for d in devs],
        "process_index": jax.process_index(),
        "dev_files": [h for h in held if "accel" in h or "vfio" in h],
        "pins": {k: v for k, v in os.environ.items()
                 if k.startswith("TPU_") or k == "ALLOW_MULTIPLE_LIBTPU_LOAD"},
    }), flush=True)
    go = os.path.join(OUT, f"{tag.rsplit('.', 1)[0]}.go")
    while not os.path.exists(go):
        time.sleep(0.05)
    trace_dir = os.path.join(OUT, "trace." + tag)
    jax.profiler.start_trace(trace_dir)
    for _ in range(50):
        (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    sys.path.insert(0, BENCH)
    from lib import trace as trace_mod
    from jax.profiler import ProfileData

    path = trace_mod.find_xplane(trace_dir)
    planes = [p.name for p in ProfileData.from_file(path).planes]
    red = trace_mod.reduce_xplane(path)
    st = devs[0].memory_stats() or {}
    print(json.dumps({
        "done": True, "tag": tag, "planes": planes,
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "chips_traced": red["chips_traced"],
        "peak_bytes_in_use": st.get("peak_bytes_in_use"),
    }), flush=True)


def wave(name: str, envs: list) -> dict:
    """Start len(envs) children at once; each one's two lines, or why not."""
    procs = []
    for k, extra in enumerate(envs):
        env = os.environ.copy()
        env.update(extra)
        tag = f"{name}.{k}"
        err = open(os.path.join(OUT, tag + ".err"), "wb")
        procs.append((tag, err, subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", tag],
            env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=err,
            start_new_session=True,
        )))
    got = {tag: {} for tag, _e, _p in procs}

    def lines(limit_s: float, key: str) -> None:
        deadline = time.monotonic() + limit_s
        for tag, _e, p in procs:
            if got[tag].get("failed"):
                continue
            os.set_blocking(p.stdout.fileno(), False)
            buf = b""
            while time.monotonic() < deadline:
                chunk = p.stdout.read()
                if chunk:
                    buf += chunk
                if b"\n" in buf:
                    line, _, buf = buf.partition(b"\n")
                    if line.startswith(b"{"):
                        got[tag][key] = json.loads(line)
                        break
                elif p.poll() is not None:
                    break
                time.sleep(0.05)
            if key not in got[tag]:
                got[tag]["failed"] = (
                    f"no {key!r} line; rc={p.poll()}"
                )

    try:
        lines(UP_S, "up")
        open(os.path.join(OUT, name + ".go"), "w").close()
        lines(DONE_S, "done")
    finally:
        for tag, err, p in procs:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            p.wait()
            err.close()
            if got[tag].get("failed"):
                with open(os.path.join(OUT, tag + ".err"), "rb") as f:
                    got[tag]["stderr_tail"] = f.read()[-1500:].decode(
                        "utf-8", "replace")
    return got


def main() -> int:
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        child(sys.argv[2])
        return 0
    os.makedirs(OUT, exist_ok=True)
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    result = {"recipes": {}, "works": None}
    recipes = [
        ("pins", False, False), ("pins_allow", False, True),
        ("pins_addr", True, False), ("pins_addr_allow", True, True),
    ]
    for name, addresses, allow in recipes:
        got = wave(name, [
            pin_env(k, free_port(), addresses, allow) for k in range(n)
        ])
        result["recipes"][name] = got
        print(json.dumps({name: got}), flush=True)
        if all("done" in g for g in got.values()):
            result["works"] = name
            # Two processes, one chip: is the second kept off?
            same = wave("same_chip", [
                pin_env(0, free_port(), addresses, allow) for _ in range(2)
            ])
            result["recipes"]["same_chip"] = same
            break
    with open(os.path.join(OUT, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["works"] else 1


if __name__ == "__main__":
    sys.exit(main())
