"""The owner's half of an exactly-once forward (docs/cluster.md).

An entry daemon gives every raw GetPeerRateLimits an id (metadata
`x-guber-forward-id`, net/peer_client.py) and, where an ask outlasts the
batch timeout, asks again under the same id.  The owner keeps, per id,
the work in progress and then its answer: a second arrival of an id
awaits the first's result and never applies the batch again, and the
first's work runs on whether or not the handler that started it is
cancelled (the gRPC server cancels a handler whose caller gave up).
The cost to a forward that arrives once: a dict entry, and one turn of
the loop between its answer and its handler (timed as `wire.wake`).

An id is forgotten `keep_s` after its work ended — a small multiple of
the batch timeout, long enough for the re-ask of an ask that timed out
while the answer was on its way.  All state is touched from the daemon's
event loop alone.
"""
from __future__ import annotations

import asyncio
import collections
import gc
import logging
import time
from typing import Awaitable, Callable, Deque, Dict, List, Tuple

log = logging.getLogger(__name__)

# Ids are kept this many batch timeouts after their work ended.
KEEP_TIMEOUTS = 8
# The rows a re-ask's log line quotes: what an owner's forward waits in.
_WAITS = ("queue_wait", "drain", "d2h_wait")


class _Forward:
    """One id: when it first came, its work, then the work's answer."""

    __slots__ = ("first", "task", "done", "answer", "error", "waiters")

    def __init__(self, first: float) -> None:
        self.first = first
        self.task = None
        self.done = False
        self.answer = None
        self.error = None
        self.waiters: List[asyncio.Future] = []


class ForwardOnce:
    """id -> the forward's answer, or the work that will give it."""

    def __init__(self, batch_timeout_s: float, stages) -> None:
        self._keep_s = KEEP_TIMEOUTS * batch_timeout_s
        self._stages = stages
        self._work: Dict[str, _Forward] = {}
        self._ended: Deque[Tuple[float, str]] = collections.deque()

    def __len__(self) -> int:
        return len(self._work)

    async def apply(
        self, fid: str, work: Callable[[], Awaitable[bytes]]
    ) -> bytes:
        """The answer of forward `fid`: `work()`'s, run once however
        often and however late (inside `keep_s`) the id arrives.  An
        error the work raised is its answer too."""
        now = time.monotonic()
        fw = self._work.get(fid)        # before forgetting: a late re-ask
        self._forget(now)
        if fw is None:
            fw = self._work[fid] = _Forward(now)
            # A task of its own, so that a caller who gives up — the gRPC
            # server cancels such a handler — cancels its wait and not
            # the work, which a later arrival of the id will ask for.
            # Started at once: the work's stages begin here, not a turn
            # of the loop later.
            fw.task = asyncio.Task(
                self._run(fid, fw, work),
                loop=asyncio.get_running_loop(), eager_start=True,
            )
        else:
            self._stages.tally("peer", "peer.forward", joined=1)
            self._say_why(fid, fw, now - fw.first)
        if not fw.done:
            waiter = asyncio.get_running_loop().create_future()
            fw.waiters.append(waiter)
            (await waiter).end()        # its wire.wake, opened by _run
        if fw.error is not None:
            raise fw.error
        return fw.answer

    async def _run(self, fid: str, fw: _Forward, work) -> None:
        try:
            fw.answer = await work()
        except BaseException as e:  # noqa: BLE001 — the id's answer too
            fw.error = e
            if not isinstance(e, Exception):
                raise                   # this task's own cancellation
        finally:
            fw.done = True
            fw.task = None
            self._ended.append((time.monotonic() + self._keep_s, fid))
            for waiter in fw.waiters:
                if not waiter.done():
                    # wire.wake: the answer is there -> its handler
                    # resumes.
                    waiter.set_result(
                        self._stages.begin("wire.wake", "wire")
                    )
            fw.waiters = []

    def _say_why(self, fid: str, fw: _Forward, age_s: float) -> None:
        """A re-ask means an ask outlasted the batch timeout: rare, and
        the one moment that can say what this owner's forwards waited
        for (PERF.md section 7)."""
        rows = self._stages.debug_vars()
        mach = rows.get("mach", {})
        log.warning(
            "forward %s asked again %.0f ms after it first came; its work "
            "is %s; this daemon's slowest so far, ms: %s; compiles %d, "
            "gc gen-2 collections %d",
            fid, age_s * 1e3, "done" if fw.done else "in progress",
            ", ".join("%s %.0f" % (st, mach.get(st, {}).get("ms_max", 0.0))
                      for st in _WAITS),
            rows.get("xla", {}).get("compile", {}).get("count", 0),
            gc.get_stats()[2]["collections"],
        )

    def _forget(self, now: float) -> None:
        while self._ended and self._ended[0][0] <= now:
            self._work.pop(self._ended.popleft()[1], None)
