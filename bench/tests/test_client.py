"""The open loop's outstanding cap never drops or re-times an arrival, and
the closed loop keeps its in-flight count — against a fake server, no
daemon."""
import asyncio
import time

import numpy as np
import pytest

import client


class FakeCall:
    """Answers after `delay_s`; counts what is outstanding."""

    def __init__(self, delay_s: float) -> None:
        self.delay_s = delay_s
        self.outstanding = 0
        self.peak = 0
        self.calls = 0

    async def __call__(self, payload: bytes, timeout: float) -> bytes:
        self.calls += 1
        self.outstanding += 1
        self.peak = max(self.peak, self.outstanding)
        try:
            await asyncio.sleep(self.delay_s)
        finally:
            self.outstanding -= 1
        return payload


def test_open_loop_cap_delays_but_never_drops_or_retimes():
    # 200 arrivals in 0.2 s against a server that takes 50 ms and a cap of
    # 8: far over capacity, so most arrivals wait for the cap.
    times = np.linspace(0.0, 0.2, 200, endpoint=False)
    payloads = [b"%d" % j for j in range(len(times))]
    call = FakeCall(0.05)
    rec = client.Recorder()
    traffic = {"outstanding_cap": 8, "deadline_s": 5.0}

    async def go():
        t0 = time.monotonic() + 0.01
        extra = await client.open_loop(
            [call], payloads, times, traffic, t0, rec)
        return t0, extra

    t0, extra = asyncio.run(go())
    assert call.peak == 8                       # the cap held
    assert call.calls == len(times)             # nothing dropped
    assert sorted(rec.plan_idx) == list(range(len(times)))
    # Every arrival keeps its intended time, whatever it waited.
    np.testing.assert_allclose(
        np.array(rec.t_due)[np.argsort(rec.plan_idx)], t0 + times)
    assert all(c == client.OK for c in rec.code)
    late = np.array(rec.t_send) - np.array(rec.t_due)
    assert extra["cap_waited"] == sum(rec.waited) > 100
    assert late.max() > 0.5                     # the wait is in the record
    lat = np.array(rec.t_done) - np.array(rec.t_due)
    assert (lat >= 0.05 - 1e-3).all() and lat.max() > 0.5


def test_open_loop_below_the_cap_sends_on_time():
    times = np.linspace(0.0, 0.2, 20, endpoint=False)
    call = FakeCall(0.005)
    rec = client.Recorder()

    async def go():
        t0 = time.monotonic() + 0.01
        return await client.open_loop(
            [call], [b"x"] * 20, times, {"outstanding_cap": 256,
                                         "deadline_s": 5.0}, t0, rec)

    extra = asyncio.run(go())
    assert extra["cap_waited"] == 0 and not any(rec.waited)
    assert (np.array(rec.t_send) - np.array(rec.t_due)).max() < 0.05


def test_closed_loop_keeps_its_callers_in_flight():
    call = FakeCall(0.01)
    rec = client.Recorder()

    async def go():
        t0 = time.monotonic()
        return await client.closed_loop(
            [call, call], [b"a", b"b", b"c"],
            {"in_flight": 6, "deadline_s": 5.0}, t0 + 0.2, rec)

    extra = asyncio.run(go())
    assert call.peak == 6
    assert extra["pool_used"] == call.calls >= 6 * 10
    assert set(rec.plan_idx) == {0, 1, 2}       # the pool cycles


def test_a_failed_rpc_is_recorded_not_raised():
    import grpc

    class Err(grpc.aio.AioRpcError):
        def __init__(self, code):
            self._c = code

        def code(self):
            return self._c

    class Failing:
        def __init__(self, code):
            self.code = code

        async def __call__(self, payload, timeout):
            raise Err(self.code)

    rec = client.Recorder()

    async def go():
        for c in (grpc.StatusCode.DEADLINE_EXCEEDED,
                  grpc.StatusCode.UNAVAILABLE):
            k = rec.open(0, time.monotonic())
            await client.one_rpc(Failing(c), b"", 5.0, rec, k)

    asyncio.run(go())
    assert rec.code == [client.DEADLINE, client.RPC_ERROR]
