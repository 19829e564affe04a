#!/usr/bin/env python3
"""The load generator: one process, one event loop, no JAX.

It builds the cell's plan from the seed (bench/lib/schedule.py), encodes
every RPC before the first one leaves, then drives `GetRateLimits` over
gRPC through the warm-in and the window without a pause between them:

  closed  `in_flight` callers over `connections` connections, each
          sending its next RPC when the last was answered;
  open    every arrival is sent at its intended time unless
          `outstanding_cap` RPCs are already outstanding (a gateway's
          connection pool); an arrival over the cap waits in the client.
          Nothing is dropped or re-timed: latency always counts from the
          intended send, and the wait is reported.

`--addr` may list several daemons of one cluster, comma-separated: the
mix's `connections` are spread over them round-robin (connection c goes to
daemon c mod N — a load balancer in front of the cluster, upstream README's
"any peer"), and the record says which connection carried each RPC.  With
one address nothing differs.

A slow answer is a latency sample.  An RPC fails by an RPC error, by the
traffic file's deadline, or by being unanswered when the drain ends.
Everything it saw goes into one .npz for the harness; stdout carries the
two window marks the harness snapshots counters on.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, REPO]

import numpy as np  # noqa: E402

from lib import schedule, universe  # noqa: E402

OK, RPC_ERROR, DEADLINE, MALFORMED, UNANSWERED = 0, 1, 2, 3, 4
METHOD = "/pb.gubernator.V1/GetRateLimits"


def mark(name: str, **kw) -> None:
    sys.stdout.write(json.dumps({"mark": name, **kw}) + "\n")
    sys.stdout.flush()


def encode_plan(native, plan, uni_cfg: dict, seed: int) -> list:
    n_global = int(uni_cfg.get("global_keys", 0))
    ids = universe.key_ids(plan.key_index, seed)
    is_global = plan.key_index < n_global
    algo = np.where(
        is_global, 0, universe.key_algorithms(ids, uni_cfg)
    ).astype(np.int64)
    limit = np.where(
        is_global, int(uni_cfg.get("global_limit", 0)), int(uni_cfg["limit"])
    ).astype(np.int64)
    behavior = np.where(is_global, universe.BEHAVIOR_GLOBAL, 0).astype(
        np.int64
    )
    hits = plan.hits.astype(np.int64)
    dur = np.full(len(ids), int(uni_cfg["duration_ms"]), dtype=np.int64)
    out = []
    for j in range(len(plan)):
        s = slice(int(plan.offsets[j]), int(plan.offsets[j + 1]))
        out.append(universe.encode_rpc(
            native, ids[s], hits[s], limit[s], dur[s], algo[s], behavior[s]
        ))
    return out


class Recorder:
    """Per sent RPC: which plan entry, on which connection, when it was
    due, sent and answered (monotonic seconds and wall-clock ms), how it
    ended, its raw answer."""

    def __init__(self) -> None:
        self.plan_idx: list = []
        self.conn: list = []
        self.t_due: list = []
        self.t_send: list = []
        self.t_done: list = []
        self.wall_send: list = []
        self.wall_recv: list = []
        self.code: list = []
        self.raw: list = []
        self.waited: list = []

    def open(self, plan_idx: int, t_due: float, waited: bool = False,
             conn: int = 0) -> int:
        k = len(self.plan_idx)
        self.plan_idx.append(plan_idx)
        self.conn.append(conn)
        self.t_due.append(t_due)
        self.t_send.append(time.monotonic())
        self.wall_send.append(time.time_ns() // 1_000_000)
        self.t_done.append(float("nan"))
        self.wall_recv.append(0)
        self.code.append(UNANSWERED)
        self.raw.append(b"")
        self.waited.append(waited)
        return k

    def close(self, k: int, code: int, raw: bytes) -> None:
        self.t_done[k] = time.monotonic()
        self.wall_recv[k] = time.time_ns() // 1_000_000
        self.code[k] = code
        self.raw[k] = raw


async def one_rpc(call, payload: bytes, deadline_s: float, rec: Recorder,
                  k: int) -> None:
    import grpc

    try:
        raw = await call(payload, timeout=deadline_s)
    except grpc.aio.AioRpcError as e:
        code = (
            DEADLINE if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED
            else RPC_ERROR
        )
        rec.close(k, code, b"")
    else:
        rec.close(k, OK, raw)


async def closed_loop(calls, payloads, traffic, t_end, rec) -> dict:
    nxt = 0

    async def caller(conn: int) -> None:
        nonlocal nxt
        while time.monotonic() < t_end:
            j = nxt % len(payloads)
            nxt += 1
            k = rec.open(j, time.monotonic(), conn=conn)
            await one_rpc(calls[conn], payloads[j], traffic["deadline_s"],
                          rec, k)

    n = int(traffic["in_flight"])
    await asyncio.gather(*[caller(i % len(calls)) for i in range(n)])
    return {"pool": len(payloads), "pool_used": nxt}


async def open_loop(calls, payloads, times, traffic, t0, rec) -> dict:
    cap = asyncio.Semaphore(int(traffic["outstanding_cap"]))
    tasks = set()
    waited = 0

    async def send(j: int, conn: int, over_cap: bool) -> None:
        try:
            k = rec.open(j, t0 + float(times[j]), over_cap, conn)
            await one_rpc(calls[conn], payloads[j], traffic["deadline_s"],
                          rec, k)
        finally:
            cap.release()

    held_until = 0.0   # when the scheduler last came back from a cap wait
    for j in range(len(payloads)):
        due = t0 + float(times[j])
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        # Over the cap itself, or queued behind an arrival that was.
        full = cap.locked()
        over_cap = full or due < held_until
        waited += over_cap
        await cap.acquire()
        if full:
            held_until = time.monotonic()
        t = asyncio.ensure_future(send(j, j % len(calls), over_cap))
        tasks.add(t)
        t.add_done_callback(tasks.discard)
    if tasks:
        await asyncio.gather(*tasks)
    return {"cap_waited": waited}


async def drive(args, traffic, payloads, plan) -> dict:
    import grpc.aio

    rec = Recorder()
    addrs = args.addr.split(",")
    channels = [
        grpc.aio.insecure_channel(
            addrs[c % len(addrs)],
            options=[
                ("grpc.use_local_subchannel_pool", 1),
                ("grpc.max_receive_message_length", 64 << 20),
                ("grpc.max_send_message_length", 64 << 20),
            ],
        )
        for c in range(int(traffic["connections"]))
    ]
    calls = [c.unary_unary(METHOD) for c in channels]
    try:
        await asyncio.gather(*[c.channel_ready() for c in channels])
        t0 = time.monotonic() + 0.05
        tw0, tw1 = t0 + args.warm_in, t0 + args.warm_in + args.seconds
        loop = asyncio.get_running_loop()
        loop.call_at(tw0, mark, "window_start")
        loop.call_at(tw1, mark, "window_end")
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        if traffic["loop"] == "closed":
            extra = await closed_loop(calls, payloads, traffic, tw1, rec)
        else:
            extra = await open_loop(
                calls, payloads, plan.times_s, traffic, t0, rec
            )
        await asyncio.sleep(max(0.0, tw1 - time.monotonic()) + 0.01)
    finally:
        await asyncio.gather(*[c.close() for c in channels])
    extra.update(t0=t0, tw0=tw0, tw1=tw1, t_drained=time.monotonic())
    return {"rec": rec, "extra": extra}


def global_readback(native, addr, uni_cfg, seed, plan, rec, g: int) -> dict:
    """Right after the last answer: poll until the replicated GLOBAL read
    and the owner's row (the same keys read without the flag) both show
    limit minus every acknowledged hit (chip_smoke.py's verify_global
    rule).  Returns the poll times since the last answer and, per poll,
    which keys still differ; the harness decides which keys count."""
    import grpc

    n_global = int(uni_cfg["global_keys"])
    limit = int(uni_cfg["global_limit"])
    ok = np.flatnonzero(np.array(rec.code) == OK)
    starts = plan.offsets[np.array(rec.plan_idx, dtype=np.int64)[ok]]
    hit = plan.key_index[(starts[:, None] + np.arange(g)[None, :]).ravel()]
    want = limit - np.bincount(hit, minlength=n_global)
    ids = universe.key_ids(np.arange(n_global, dtype=np.int64), seed)
    one = np.ones(n_global, dtype=np.int64)
    t_last = np.nanmax(np.array(rec.t_done))
    times, differs = [], []
    with grpc.insecure_channel(addr) as ch:
        call = ch.unary_unary(METHOD)
        while True:
            bad = np.zeros(n_global, dtype=bool)
            for behavior in (universe.BEHAVIOR_GLOBAL, 0):
                for lo in range(0, n_global, 500):   # an RPC holds <= 1000
                    s = slice(lo, lo + 500)
                    raw = call(universe.encode_rpc(
                        native, ids[s], one[s] * 0, one[s] * limit,
                        one[s] * int(uni_cfg["duration_ms"]), one[s] * 0,
                        one[s] * behavior,
                    ), timeout=30)
                    bad[s] |= native.parse_resps(raw).remaining != want[s]
            times.append(time.monotonic() - t_last)
            differs.append(bad)
            if not bad.any() or times[-1] > 3.0:
                break
            time.sleep(0.01)
    return {"gb_t": np.array(times), "gb_differs": np.array(differs)}


def save(native, path: str, plan, rec: Recorder, extra: dict,
         more: dict) -> None:
    n = len(rec.plan_idx)
    code = np.array(rec.code, dtype=np.int64)
    sizes = np.diff(plan.offsets)[np.array(rec.plan_idx, dtype=np.int64)] \
        if n else np.zeros(0, dtype=np.int64)
    ans_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.where(code == OK, sizes, 0), out=ans_off[1:])
    cols = {
        f: np.zeros(int(ans_off[-1]), dtype=np.int64)
        for f in ("status", "limit", "remaining", "reset_time", "err_len")
    }
    first_error = ""
    for k in range(n):
        if code[k] != OK:
            continue
        parsed = native.parse_resps(rec.raw[k])
        lo, hi = int(ans_off[k]), int(ans_off[k + 1])
        if parsed is None or parsed.n != hi - lo:
            # Keep the slots (zeros) so offsets stay valid; the code
            # says the answer cannot be used.
            code[k] = MALFORMED
            cols["err_len"][lo:hi] = -1
            continue
        for f in cols:
            cols[f][lo:hi] = getattr(parsed, f)
        if not first_error and parsed.err_len.any():
            # What the first answer with an error said, for the log.
            j = int(np.flatnonzero(parsed.err_len)[0])
            o = int(parsed.err_off[j])
            first_error = rec.raw[k][o:o + int(parsed.err_len[j])].decode(
                "utf-8", "replace")
    np.savez(
        path, plan_idx=np.array(rec.plan_idx, dtype=np.int64),
        conn=np.array(rec.conn, dtype=np.int64),
        t_due=np.array(rec.t_due), t_send=np.array(rec.t_send),
        t_done=np.array(rec.t_done),
        wall_send=np.array(rec.wall_send, dtype=np.int64),
        wall_recv=np.array(rec.wall_recv, dtype=np.int64),
        code=code, ans_off=ans_off, waited=np.array(rec.waited, dtype=bool),
        extra=json.dumps(dict(extra, first_error=first_error)), **cols,
        **more,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--addr", required=True,
                    help="the daemon's gRPC address; a cluster's, comma-"
                    "separated")
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--warm-in", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from gubernator_tpu import native

    native.require()
    with open(args.config) as f:
        uni_cfg = json.load(f)["universe"]
    with open(args.traffic) as f:
        traffic = json.load(f)
    t = time.monotonic()
    plan = schedule.build_plan(
        traffic, uni_cfg, args.seed, args.warm_in + args.seconds
    )
    payloads = encode_plan(native, plan, uni_cfg, args.seed)
    mark("plan_ready", rpcs=len(plan), checks=int(plan.offsets[-1]),
         digest=plan.digest(), seconds=round(time.monotonic() - t, 3))
    # The harness answers with one line when the daemon is ready.
    sys.stdin.readline()
    got = asyncio.run(drive(args, traffic, payloads, plan))
    more = {}
    g = int(traffic.get("global_per_rpc", 0))
    if int(uni_cfg.get("global_keys", 0)) and g:
        more = global_readback(
            native, args.addr.split(",")[0], uni_cfg, args.seed, plan,
            got["rec"], g
        )
    save(native, args.out, plan, got["rec"], got["extra"], more)
    mark("saved", rpcs=len(got["rec"].plan_idx))


if __name__ == "__main__":
    main()
