"""MicroCoalescer: the shared micro-batching drainer.

One implementation of the submit/flush coalescing loop that both the bus
producer wrapper (messaging/coalesce.py) and the admission plane
(controller/admission.py) ride — the loop's liveness argument is subtle
enough that copies drift (database/batcher.py keeps its own variant
because its flushes run CONCURRENTLY under a semaphore; this one
serializes flushes to preserve submission order).

Waiting for work: ONE drainer task serves the coalescer for its whole
life. With nothing pending it parks on a future (`_wake`) that the next
`submit_nowait` resolves: an idle coalescer costs the loop no turn and
no timer, a wave costs one task step to wake it and mints no task.
`close()` ends the parked task; one that nobody closes is dropped
silently with its coalescer (a parked drainer holds no work).

Liveness: `_wake` is set exactly while the drainer is suspended in its
park, and the empty check that leads there is SYNCHRONOUS with setting
it (no await in between), so a submission either finds `_wake` and
resolves it, or finds the drainer still running and is seen by its next
check — it can never strand between the two. A drainer that is gone
(never started, cancelled, closed) is re-armed by the next submission.

Window semantics: `window_s == 0` flushes at the end of the current
event-loop sweep, so everything scheduled in the same sweep (e.g. one
readback fan-out wave) joins the batch at ZERO idle latency; `window_s >
0` is an age-based Nagle bound — the OLDEST pending item waits at most
window_s, a full batch short-circuits.
"""
from __future__ import annotations

import asyncio
from typing import Awaitable, Callable, List, Optional, Tuple

#: flush receives [(item, future), ...] and may resolve futures itself
#: (e.g. set per-item exceptions); any future still pending when flush
#: returns is resolved with None, a raising flush fails them all instead
FlushFn = Callable[[List[Tuple[object, asyncio.Future]]], Awaitable[None]]


class MicroCoalescer:
    """Coalesce concurrent submissions into bounded, ordered micro-batches
    (see module doc). `submit(item)` returns when the item's batch has
    flushed — or raises what flush assigned to its future."""

    def __init__(self, flush: FlushFn, max_batch: int, window_s: float,
                 name: str = "microbatch"):
        self._flush = flush
        self.max_batch = max(1, int(max_batch))
        self.window_s = max(0.0, float(window_s))
        self.name = name
        self._pending: List[tuple] = []  # (item, fut, t_enqueue)
        self._drainer: Optional[asyncio.Task] = None
        #: the parked drainer's future; None while it runs (or is gone)
        self._wake: Optional[asyncio.Future] = None
        #: drain_all()'s waiters, resolved when the drainer parks or ends
        self._idle: Optional[asyncio.Future] = None
        self._closed = False
        #: set by submit() when the batch fills — interrupts a window sleep
        #: so max_batch really bounds latency DURING the window, not just
        #: between windows
        self._full = asyncio.Event()
        #: True while the flush in flight is the first since the drainer
        #: parked (or started): its wave found the drainer waiting, not
        #: running. The flush function may read it (`ow_produce`'s
        #: `parked`)
        self.parked_flush = False

    @property
    def pending_count(self) -> int:
        return len(self._pending)

    async def submit(self, item) -> None:
        await self.submit_nowait(item)

    def submit_nowait(self, item) -> asyncio.Future:
        """Enqueue without awaiting; returns the item's flush future.
        Callers submitting a whole wave await the futures together
        (`asyncio.gather(*futs)` over FUTURES costs no task per item —
        gather only wraps coroutines in tasks)."""
        loop = asyncio.get_event_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.append((item, fut, loop.time()))
        if len(self._pending) >= self.max_batch:
            self._full.set()  # wake a drainer sleeping out its window
        self._arm(loop)
        return fut

    def _arm(self, loop) -> None:
        wake = self._wake
        if wake is not None:
            if not wake.done():  # the wave's first submission wakes it
                wake.set_result(None)
        elif self._drainer is None or self._drainer.done():
            self._drainer = loop.create_task(self._drain(), name=self.name)

    def _busy(self) -> bool:
        """A drainer is between its wake-up and its next park."""
        return (self._wake is None and self._drainer is not None
                and not self._drainer.done())

    def _signal_idle(self) -> None:
        idle, self._idle = self._idle, None
        if idle is not None and not idle.done():
            idle.set_result(None)

    async def _park(self, loop, me) -> None:
        """Wait for the next submission at no cost to the loop."""
        self._wake = wake = loop.create_future()
        self._signal_idle()
        # a parked drainer holds no work: if its coalescer is dropped
        # unclosed, or the loop closes under it, there is nothing to
        # report ("Task was destroyed but it is pending")
        me._log_destroy_pending = False
        try:
            await wake
        finally:
            self._wake = None
        me._log_destroy_pending = True

    async def _drain(self) -> None:
        loop = asyncio.get_event_loop()
        me = asyncio.current_task()
        batch: List[tuple] = []
        woke = True  # a fresh drainer's first flush found none running
        try:
            while True:
                if not self._pending:
                    if self._closed:
                        return
                    await self._park(loop, me)
                    woke = True
                    continue
                if len(self._pending) < self.max_batch:
                    if self.window_s > 0:
                        lag = self.window_s - (loop.time()
                                               - self._pending[0][2])
                        if lag > 0:
                            # interruptible window: a batch filling
                            # while we sleep flushes NOW (submit
                            # sets _full)
                            self._full.clear()
                            if len(self._pending) < self.max_batch:
                                try:
                                    await asyncio.wait_for(
                                        self._full.wait(), lag)
                                except asyncio.TimeoutError:
                                    pass
                    else:
                        await asyncio.sleep(0)  # end-of-sweep coalesce
                batch = [(item, fut) for (item, fut, _t)
                         in self._pending[:self.max_batch]]
                del self._pending[:len(batch)]
                self.parked_flush, woke = woke, False
                try:
                    await self._flush(batch)
                except Exception as e:  # noqa: BLE001 — fan out to
                    # waiters
                    for _item, fut in batch:
                        if not fut.done():
                            fut.set_exception(e)
                else:
                    for _item, fut in batch:
                        if not fut.done():
                            fut.set_result(None)
        except asyncio.CancelledError:
            # the loop is going down mid-drain (park, sleep or flush
            # cancelled): nobody will ever flush the remainder — cancel
            # every waiter (the popped in-flight batch included) instead
            # of leaving them pending forever
            for _item, fut in batch:
                if not fut.done():
                    fut.cancel()
            for (_item, fut, _t) in self._pending:
                if not fut.done():
                    fut.cancel()
            self._pending.clear()
            raise
        finally:
            self._signal_idle()

    async def drain_all(self) -> None:
        """Wait until everything submitted so far has flushed (or failed):
        nothing pending and no flush in flight."""
        loop = asyncio.get_event_loop()
        while self._pending or self._busy():
            if self._pending:
                self._arm(loop)
            if self._idle is None:
                self._idle = loop.create_future()
            # wait(), not await: a cancelled caller must not cancel the
            # future its fellow waiters share
            await asyncio.wait([self._idle])

    def close(self) -> None:
        """End the drainer once it has nothing left to flush (call on its
        loop). A submission after close() still flushes: it arms a
        drainer that ends when it runs dry."""
        self._closed = True
        wake = self._wake
        if wake is not None and not wake.done():
            wake.set_result(None)
