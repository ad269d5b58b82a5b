"""Coalesced bus I/O (ISSUE 8): the pubN frame op, broker-side partial
dedupe, the CoalescingProducer wrapper, the cheap per-producer mid scheme,
the peek reconnect backoff, and the ensure_topic no-loop fallback."""
import asyncio
import threading
import time

import pytest

from openwhisk_tpu.messaging import (BusCoalesceConfig, CoalescingProducer,
                                     MemoryMessagingProvider, maybe_coalesce)
from openwhisk_tpu.messaging.tcp import (TcpBusServer, TcpConsumer,
                                         TcpMessagingProvider, TcpProducer,
                                         _TcpConnection, _encode_pubn)


async def _server():
    server = TcpBusServer("127.0.0.1", 0)
    await server.start()
    return server, server._server.sockets[0].getsockname()[1]


class TestPubN:
    def test_round_trip_multi_topic(self):
        """One pubN frame fans N payloads across topics; every consumer
        sees its messages in producer order."""
        async def go():
            server, port = await _server()
            provider = TcpMessagingProvider("127.0.0.1", port)
            producer = provider.get_producer()
            items = [("t1", f"a{i}".encode(), None) for i in range(5)] + \
                    [("t2", f"b{i}".encode(), None) for i in range(3)]
            await producer.send_many(items)
            c1 = provider.get_consumer("t1", "g")
            c2 = provider.get_consumer("t2", "g")
            b1 = await c1.peek(100, timeout=0.5)
            b2 = await c2.peek(100, timeout=0.5)
            await c1.close()
            await c2.close()
            await producer.close()
            await server.stop()
            return ([p for *_x, p in b1], [p for *_x, p in b2],
                    producer.sent_count)

        t1, t2, sent = asyncio.run(go())
        assert t1 == [f"a{i}".encode() for i in range(5)]
        assert t2 == [f"b{i}".encode() for i in range(3)]
        assert sent == 8

    def test_full_frame_retry_dedupes_every_submessage(self):
        """A retried pubN frame (lost ack) must not double-deliver: the
        broker answers dup per sub-message and replays nothing."""
        async def go():
            server, port = await _server()
            conn = _TcpConnection("127.0.0.1", port)
            frame = _encode_pubn([("t", "m1", b"x"), ("t", "m2", b"y")])
            r1 = await conn.request_frame(frame)
            r2 = await conn.request_frame(frame)  # the retry
            provider = TcpMessagingProvider("127.0.0.1", port)
            c = provider.get_consumer("t", "g")
            batch = await c.peek(100, timeout=0.5)
            await c.close()
            await conn.close()
            await server.stop()
            return r1, r2, [p for *_x, p in batch]

        r1, r2, msgs = asyncio.run(go())
        assert [s.get("dup") for s in r1["results"]] == [None, None]
        assert [s.get("dup") for s in r2["results"]] == [True, True]
        assert msgs == [b"x", b"y"]

    def test_partial_dedupe(self):
        """A pubN carrying one already-seen mid and one fresh mid delivers
        ONLY the fresh payload (the partial-replay case: some of a prior
        frame's sub-messages landed, the retry must fill in the rest)."""
        async def go():
            server, port = await _server()
            conn = _TcpConnection("127.0.0.1", port)
            await conn.request({"op": "pub", "topic": "t", "mid": "seen-1",
                                "payload": "eA=="})  # b"x"
            resp = await conn.request_frame(_encode_pubn(
                [("t", "seen-1", b"x"), ("t", "fresh-1", b"z")]))
            provider = TcpMessagingProvider("127.0.0.1", port)
            c = provider.get_consumer("t", "g")
            batch = await c.peek(100, timeout=0.5)
            await c.close()
            await conn.close()
            await server.stop()
            return resp, [p for *_x, p in batch]

        resp, msgs = asyncio.run(go())
        assert [s.get("dup") for s in resp["results"]] == [True, None]
        assert msgs == [b"x", b"z"]


class TestPubNByteBound:
    def test_oversized_batch_splits_into_multiple_frames(self, monkeypatch):
        """A coalesced batch whose raw payloads exceed the per-frame byte
        cap must split into several pubN frames (each under the broker's
        frame limit) instead of shipping one rejected mega-frame that
        fails every message forever."""
        from openwhisk_tpu.messaging import tcp as tcp_mod

        async def go():
            server, port = await _server()
            producer = TcpProducer("127.0.0.1", port)
            monkeypatch.setattr(tcp_mod, "MAX_PUBN_PAYLOAD_BYTES", 1024)
            frames = []
            orig = producer._conn.request_frame

            async def counting(frame):
                frames.append(len(frame))
                return await orig(frame)

            producer._conn.request_frame = counting
            items = [("t", bytes([65 + i]) * 300, None) for i in range(10)]
            await producer.send_many(items)
            provider = TcpMessagingProvider("127.0.0.1", port)
            c = provider.get_consumer("t", "g")
            batch = await c.peek(100, timeout=0.5)
            await c.close()
            await producer.close()
            await server.stop()
            return frames, [p for *_x, p in batch], producer.sent_count

        frames, msgs, sent = asyncio.run(go())
        # 10 x 300B over a 1 KiB cap -> 4 frames of <= 3 payloads
        assert len(frames) == 4
        assert msgs == [bytes([65 + i]) * 300 for i in range(10)]
        assert sent == 10


class TestProducerMids:
    def test_prefix_counter_mids_unique_and_cheap(self):
        p1 = TcpProducer("127.0.0.1", 1)
        p2 = TcpProducer("127.0.0.1", 1)
        mids = [p1._next_mid() for _ in range(100)]
        assert len(set(mids)) == 100
        assert all(m.startswith(p1._mid_prefix + "-") for m in mids)
        # distinct producers never collide: the prefix is random per producer
        assert p1._mid_prefix != p2._mid_prefix

    def test_retry_dup_path_regression(self):
        """The counter mid must keep the broker's effectively-once pub:
        resending the SAME frame (a connection retry of a lost ack)
        delivers once; the NEXT logical send gets a fresh mid and
        delivers."""
        async def go():
            server, port = await _server()
            producer = TcpProducer("127.0.0.1", port)
            from openwhisk_tpu.messaging.tcp import _encode_pub
            frame = _encode_pub("t", producer._next_mid(), b"once")
            await producer._conn.request_frame(frame)
            await producer._conn.request_frame(frame)  # retry, same mid
            await producer.send("t", b"next")          # fresh mid
            provider = TcpMessagingProvider("127.0.0.1", port)
            c = provider.get_consumer("t", "g")
            batch = await c.peek(100, timeout=0.5)
            await c.close()
            await producer.close()
            await server.stop()
            return [p for *_x, p in batch]

        assert asyncio.run(go()) == [b"once", b"next"]


class TestCoalescingProducer:
    def test_concurrent_sends_coalesce_once_each(self):
        async def go():
            provider = MemoryMessagingProvider()
            producer = CoalescingProducer(provider.get_producer(),
                                          max_batch=16, window_ms=0.0)
            await asyncio.gather(*[producer.send("t", f"m{i}".encode())
                                   for i in range(40)])
            c = provider.get_consumer("t", "g")
            batch = await c.peek(1000, timeout=0.2)
            await producer.close()
            return [p for *_x, p in batch], producer.sent_count

        msgs, sent = asyncio.run(go())
        assert msgs == [f"m{i}".encode() for i in range(40)]
        assert sent == 40

    def test_window_bounds_the_wait(self):
        """With a positive window, a lone send still ships within ~window
        (age-based Nagle, not an idle stall)."""
        async def go():
            provider = MemoryMessagingProvider()
            producer = CoalescingProducer(provider.get_producer(),
                                          max_batch=64, window_ms=5.0)
            t0 = time.monotonic()
            await producer.send("t", b"solo")
            took = time.monotonic() - t0
            await producer.close()
            return took

        assert asyncio.run(go()) < 0.5

    def test_error_propagates_to_every_waiter(self):
        class _Boom:
            sent_count = 0

            async def send_many(self, items):
                raise ConnectionError("bus down")

            async def close(self):
                pass

        async def go():
            producer = CoalescingProducer(_Boom(), max_batch=8, window_ms=0.0)
            return await asyncio.gather(
                *[producer.send("t", b"m") for _ in range(3)],
                return_exceptions=True)

        results = asyncio.run(go())
        assert all(isinstance(r, ConnectionError) for r in results)

    def test_close_flushes_pending(self):
        async def go():
            provider = MemoryMessagingProvider()
            producer = CoalescingProducer(provider.get_producer(),
                                          max_batch=64, window_ms=50.0)
            sends = [asyncio.ensure_future(producer.send("t", b"late"))]
            await asyncio.sleep(0)   # enqueue, window still open
            await producer.close()   # must flush, not drop
            await asyncio.gather(*sends)
            c = provider.get_consumer("t", "g")
            batch = await c.peek(10, timeout=0.2)
            return [p for *_x, p in batch]

        assert asyncio.run(go()) == [b"late"]

    def test_maybe_coalesce_respects_off_switch(self, monkeypatch):
        provider = MemoryMessagingProvider()
        raw = provider.get_producer()
        assert isinstance(maybe_coalesce(raw), CoalescingProducer)
        monkeypatch.setenv("CONFIG_whisk_bus_coalesce_enabled", "false")
        assert maybe_coalesce(raw) is raw
        # explicit config wins over env
        assert isinstance(
            maybe_coalesce(raw, BusCoalesceConfig(enabled=True)),
            CoalescingProducer)
        # never double-wraps
        wrapped = maybe_coalesce(raw, BusCoalesceConfig(enabled=True))
        assert maybe_coalesce(wrapped, BusCoalesceConfig(enabled=True)) \
            is wrapped

    def test_balancer_and_invoker_ride_the_wrapper(self, monkeypatch):
        """The shipped wiring: CommonLoadBalancer's producer coalesces by
        default and drops back to the raw producer when disabled."""
        from openwhisk_tpu.controller.loadbalancer.base import \
            CommonLoadBalancer
        from openwhisk_tpu.core.entity import ControllerInstanceId

        async def build():
            bal = CommonLoadBalancer(MemoryMessagingProvider(),
                                     ControllerInstanceId("0"))
            kind = type(bal.producer)
            await bal.close()
            return kind

        assert asyncio.run(build()) is CoalescingProducer
        monkeypatch.setenv("CONFIG_whisk_bus_coalesce_enabled", "false")
        assert asyncio.run(build()) is not CoalescingProducer

    def test_pubn_over_tcp_via_wrapper(self):
        """End to end: CoalescingProducer over the TCP bus ships one pubN
        frame for a concurrent wave (broker sees ONE producer request)."""
        async def go():
            server, port = await _server()
            provider = TcpMessagingProvider("127.0.0.1", port)
            producer = CoalescingProducer(provider.get_producer(),
                                          max_batch=64, window_ms=1.0)
            await asyncio.gather(*[producer.send("t", f"m{i}".encode())
                                   for i in range(10)])
            c = provider.get_consumer("t", "g")
            batch = await c.peek(100, timeout=0.5)
            await c.close()
            await producer.close()
            await server.stop()
            return [p for *_x, p in batch]

        assert asyncio.run(go()) == [f"m{i}".encode() for i in range(10)]


class TestMicroCoalescer:
    def test_full_batch_interrupts_the_window_sleep(self):
        """A batch filling WHILE the drainer sleeps out its window must
        flush immediately — max_batch bounds latency during the window,
        not just between windows."""
        from openwhisk_tpu.utils.microbatch import MicroCoalescer

        async def go():
            flushed = []

            async def flush(batch):
                flushed.append(len(batch))

            co = MicroCoalescer(flush, max_batch=4, window_s=5.0)
            t0 = asyncio.get_event_loop().time()
            first = asyncio.ensure_future(co.submit(0))
            await asyncio.sleep(0.05)  # drainer now sleeping out 5 s
            rest = [asyncio.ensure_future(co.submit(i)) for i in (1, 2, 3)]
            await asyncio.wait_for(asyncio.gather(first, *rest), timeout=2.0)
            return flushed, asyncio.get_event_loop().time() - t0

        flushed, took = asyncio.run(go())
        assert flushed == [4]
        assert took < 2.0  # nowhere near the 5 s window


    def test_cancelled_drainer_cancels_waiters(self):
        """A drainer cancelled mid-flush (loop shutdown) must cancel its
        waiters — both the popped in-flight batch and the still-pending
        queue — instead of leaving them awaiting forever."""
        from openwhisk_tpu.utils.microbatch import MicroCoalescer

        async def go():
            started = asyncio.Event()

            async def slow_flush(batch):
                started.set()
                await asyncio.sleep(30)

            co = MicroCoalescer(slow_flush, max_batch=1, window_s=0.0)
            waiters = [asyncio.ensure_future(co.submit(i)) for i in range(3)]
            await started.wait()          # first batch is inside flush
            co._drainer.cancel()
            done, _ = await asyncio.wait(waiters, timeout=2.0)
            return [w.cancelled() for w in waiters], len(done)

        cancelled, n_done = asyncio.run(go())
        assert n_done == 3
        assert all(cancelled)


async def _sweeps(n):
    for _ in range(n):
        await asyncio.sleep(0)


async def _coalescer(state, flush, max_batch=64, window_s=0.0):
    """A MicroCoalescer whose drainer is in `state`: "fresh" = never
    armed (the first submission mints the task), "parked" = one wave has
    flushed and the drainer waits on its future for the next."""
    from openwhisk_tpu.utils.microbatch import MicroCoalescer
    co = MicroCoalescer(flush, max_batch=max_batch, window_s=window_s,
                        name="drainer-under-test")
    if state == "parked":
        seen = []
        co._flush = lambda batch: _record(seen, batch)
        await co.submit("warm")
        await co.drain_all()
        co._flush = flush
        assert seen == [["warm"]]
        assert co._wake is not None and not co._drainer.done()
    return co


async def _record(seen, batch):
    seen.append([item for item, _f in batch])


class _HandleCount:
    """Every callback and timer the loop is handed while installed, by
    the object it is bound to (a task's step and wake-up are bound to
    the task)."""

    def __init__(self, loop):
        self.loop, self.owners, self.timers = loop, [], 0
        self._soon, self._at = loop.call_soon, loop.call_at

    def __enter__(self):
        def call_soon(cb, *a, **kw):
            self.owners.append(getattr(cb, "__self__", None))
            return self._soon(cb, *a, **kw)

        def call_at(when, cb, *a, **kw):
            self.timers += 1
            return self._at(when, cb, *a, **kw)

        self.loop.call_soon, self.loop.call_at = call_soon, call_at
        return self

    def __exit__(self, *exc):
        del self.loop.call_soon, self.loop.call_at
        return False

    def steps_of(self, task) -> int:
        return sum(1 for o in self.owners if o is task)


_STATES = pytest.mark.parametrize("state", ["fresh", "parked"])


class TestParkedDrainer:
    """ISSUE 38: the drainer parks on a future between waves. It neither
    spins sweeps waiting for the next wave nor dies and is minted anew."""

    def test_a_parked_drainer_takes_no_step_and_arms_no_timer(self):
        async def go():
            seen = []
            co = await _coalescer("parked", lambda b: _record(seen, b))
            loop = asyncio.get_event_loop()
            with _HandleCount(loop) as idle:
                await _sweeps(50)
            with _HandleCount(loop) as woken:   # the counter does count
                await co.submit("next")
            return (idle.steps_of(co._drainer), idle.timers,
                    len(idle.owners), woken.steps_of(co._drainer), seen)

        idle_steps, idle_timers, idle_all, woken_steps, seen = \
            asyncio.run(go())
        assert idle_steps == 0 and idle_timers == 0
        assert idle_all == 50          # the test's own 50 sleeps, no more
        assert woken_steps == 2        # the wake-up, the end-of-sweep yield
        assert seen == [["next"]]

    def test_a_thousand_waves_mint_one_task(self):
        async def go():
            seen = []
            loop = asyncio.get_event_loop()
            minted = []
            create_task = loop.create_task

            def counting(coro, **kw):
                minted.append(kw.get("name"))
                return create_task(coro, **kw)

            loop.create_task = counting
            try:
                co = await _coalescer("fresh", lambda b: _record(seen, b))
                for i in range(1000):
                    await co.submit(i)
                    if i % 3 == 0:      # some waves meet a parked drainer,
                        await _sweeps(2)  # some one that has just flushed
            finally:
                del loop.create_task
            return minted.count(co.name), seen

        minted, seen = asyncio.run(go())
        assert minted == 1
        assert [i for b in seen for i in b] == list(range(1000))

    @pytest.mark.parametrize("wave", [1, 5])
    def test_a_parked_drainer_flushes_on_a_fresh_drainers_sweep(self, wave):
        """What `ack_batch_fill` rests on: a wave that meets a parked
        drainer coalesces as, and flushes on the very sweep on which, a
        wave that arms a fresh one does — submissions of the wave's own
        sweep and of the next ride the same flush."""
        async def flushed_after(state):
            seen = []
            co = await _coalescer(state, lambda b: _record(seen, b))
            for i in range(wave):
                co.submit_nowait(i)
            await asyncio.sleep(0)
            co.submit_nowait("next-sweep")
            sweeps = 1
            while not seen:
                await asyncio.sleep(0)
                sweeps += 1
            await co.drain_all()
            return sweeps, seen

        async def go():
            return (await flushed_after("fresh"),
                    await flushed_after("parked"))

        fresh, parked = asyncio.run(go())
        assert parked == fresh
        assert fresh == (2, [list(range(wave)) + ["next-sweep"]])

    @_STATES
    def test_order_and_max_batch(self, state):
        async def go():
            seen = []
            co = await _coalescer(state, lambda b: _record(seen, b),
                                  max_batch=4)
            await asyncio.gather(*[co.submit(i) for i in range(10)])
            return seen

        assert asyncio.run(go()) == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]

    @_STATES
    def test_one_flush_at_a_time(self, state):
        async def go():
            inside, most, seen = [0], [0], []

            async def flush(batch):
                inside[0] += 1
                most[0] = max(most[0], inside[0])
                await asyncio.sleep(0.001)
                seen.append([item for item, _f in batch])
                inside[0] -= 1

            co = await _coalescer(state, flush, max_batch=2)
            await asyncio.gather(*[co.submit(i) for i in range(6)])
            return most[0], seen

        assert asyncio.run(go()) == (1, [[0, 1], [2, 3], [4, 5]])

    @_STATES
    def test_window_holds_a_lone_item_and_a_full_batch_cuts_it(self, state):
        async def go():
            seen = []
            co = await _coalescer(state, lambda b: _record(seen, b),
                                  max_batch=3, window_s=0.05)
            loop = asyncio.get_event_loop()
            t0 = loop.time()
            await co.submit("lone")
            lone = loop.time() - t0
            t0 = loop.time()
            await asyncio.gather(*[co.submit(i) for i in range(3)])
            full = loop.time() - t0
            return lone, full, seen

        lone, full, seen = asyncio.run(go())
        assert 0.04 <= lone < 0.5 and full < 0.04
        assert seen == [["lone"], [0, 1, 2]]

    @_STATES
    def test_per_item_failure_reaches_its_waiter_alone(self, state):
        async def go():
            async def flush(batch):
                for item, fut in batch:
                    if item == "bad":
                        fut.set_exception(ValueError(item))

            co = await _coalescer(state, flush)
            return await asyncio.gather(
                co.submit("ok"), co.submit("bad"), co.submit("ok2"),
                return_exceptions=True)

        ok, bad, ok2 = asyncio.run(go())
        assert ok is None and ok2 is None and isinstance(bad, ValueError)

    @_STATES
    def test_a_raising_flush_fails_its_batch_and_the_next_still_flushes(
            self, state):
        async def go():
            seen = []

            async def flush(batch):
                if batch[0][0] == "boom":
                    raise ConnectionError("bus down")
                await _record(seen, batch)

            co = await _coalescer(state, flush)
            failed = await asyncio.gather(co.submit("boom"), co.submit("b2"),
                                          return_exceptions=True)
            await co.submit("after")
            return failed, seen

        failed, seen = asyncio.run(go())
        assert all(isinstance(f, ConnectionError) for f in failed)
        assert seen == [["after"]]

    @_STATES
    def test_drain_all_waits_for_pending_and_for_the_flush_in_flight(
            self, state):
        async def go():
            seen, gate = [], asyncio.Event()

            async def flush(batch):
                await gate.wait()
                await _record(seen, batch)

            co = await _coalescer(state, flush, max_batch=2)
            futs = [co.submit_nowait(i) for i in range(3)]
            drained = asyncio.ensure_future(co.drain_all())
            await _sweeps(5)           # first batch is inside flush
            held = (drained.done(), co.pending_count, list(seen))
            gate.set()
            await asyncio.wait_for(drained, 2.0)
            after = ([f.done() for f in futs], co.pending_count, seen)
            await asyncio.wait_for(co.drain_all(), 2.0)   # idle: at once
            return held, after

        held, after = asyncio.run(go())
        assert held == (False, 1, [])
        assert after == ([True] * 3, 0, [[0, 1], [2]])

    @_STATES
    def test_close_ends_the_drainer_after_what_is_pending(self, state):
        async def go():
            seen = []
            co = await _coalescer(state, lambda b: _record(seen, b))
            fut = co.submit_nowait("last")
            co.close()
            await fut
            await _sweeps(2)
            ended = co._drainer.done() and not co._drainer.cancelled()
            await co.submit("after-close")    # still flushes
            await _sweeps(2)
            return ended, co._drainer.done(), seen

        ended, ended_again, seen = asyncio.run(go())
        assert ended and ended_again
        assert seen == [["last"], ["after-close"]]

    def test_close_of_an_idle_parked_drainer_ends_its_task(self):
        async def go():
            co = await _coalescer("parked", lambda b: _record([], b))
            co.close()
            await _sweeps(2)
            return co._drainer.done(), co._drainer.cancelled()

        assert asyncio.run(go()) == (True, False)

    def test_cancelling_a_parked_drainer_cancels_the_wave_that_met_it(self):
        async def go():
            co = await _coalescer("parked", lambda b: _record([], b))
            co._drainer.cancel()
            # a submission of the very sweep of the cancel is cancelled
            # with it, not left waiting; the next one arms a new drainer
            orphan = co.submit_nowait("orphan")
            await asyncio.wait([orphan], timeout=2.0)
            await asyncio.wait_for(co.submit("next"), 2.0)
            return orphan.cancelled(), co._drainer.done()

        assert asyncio.run(go()) == (True, False)

    def test_unclosed_coalescers_leave_no_pending_task_warning(self, caplog):
        """Hundreds of coalescers that nobody closes, dropped with their
        loop: a parked drainer holds no work, so nothing is reported."""
        import gc
        import logging
        from openwhisk_tpu.utils.microbatch import MicroCoalescer

        async def go():
            seen = []
            cos = [MicroCoalescer(lambda b: _record(seen, b), 64, 0.0)
                   for _ in range(300)]
            await asyncio.gather(*[co.submit(i)
                                   for i, co in enumerate(cos)])
            for co in cos:
                await co.drain_all()
            return len(seen), sum(co._wake is not None for co in cos)

        loop = asyncio.new_event_loop()
        with caplog.at_level(logging.ERROR, logger="asyncio"):
            try:
                # not asyncio.run: that would cancel the parked tasks
                flushed, parked = loop.run_until_complete(go())
            finally:
                loop.close()
            gc.collect()
        assert (flushed, parked) == (300, 300)
        assert "Task was destroyed" not in caplog.text

    def test_produce_counts_the_flushes_that_woke_a_parked_drainer(self):
        """`parked_flush` as `_ship` reads it: 1 on a lone flush after a
        park (and on a fresh drainer's first), 0 on the next flush of a
        drainer kept busy."""
        from openwhisk_tpu.messaging import coalesce

        async def go():
            provider = MemoryMessagingProvider()
            producer = CoalescingProducer(provider.get_producer(),
                                          max_batch=2, window_ms=0.0)
            flags = []
            ship = producer._co._flush

            async def spy(batch):
                flags.append((len(batch), producer._co.parked_flush))
                await ship(batch)

            producer._co._flush = spy
            n0 = coalesce._STATS["parked_flushes"]
            await producer.send("t", b"lone")              # fresh drainer
            await _sweeps(3)
            await producer.send("t", b"lone-after-a-park")
            await asyncio.gather(*[producer.send("t", b"m")  # 3 flushes,
                                   for _ in range(5)])     # one wake
            await producer.close()
            return flags, coalesce._STATS["parked_flushes"] - n0

        flags, counted = asyncio.run(go())
        assert flags == [(1, True), (1, True),
                         (2, True), (2, False), (1, False)]
        assert counted == 3


class TestPeekBackoff:
    def test_dead_broker_returns_after_timeout_with_retries(self):
        async def go():
            consumer = TcpConsumer("127.0.0.1", 1, "t", "g")
            t0 = time.monotonic()
            batch = await consumer.peek(10, timeout=0.5)
            return batch, time.monotonic() - t0, consumer.reconnects

        batch, took, reconnects = asyncio.run(go())
        assert batch == []
        assert took < 2.0
        # capped exponential backoff: several short retries fit the window
        # (the old behavior slept the WHOLE timeout after one failure)
        assert reconnects >= 3

    def test_broker_returning_mid_window_is_caught(self):
        """The regression the backoff exists for: a broker that comes back
        mid-window serves the peek well before the full timeout."""
        async def go():
            probe = TcpBusServer("127.0.0.1", 0)
            await probe.start()
            port = probe._server.sockets[0].getsockname()[1]
            await probe.stop()  # port known, broker down

            consumer = TcpConsumer("127.0.0.1", port, "t", "g")

            async def revive():
                await asyncio.sleep(0.3)
                server = TcpBusServer("127.0.0.1", port)
                await server.start()
                prod = TcpProducer("127.0.0.1", port)
                await prod.send("t", b"back")
                await prod.close()
                return server

            reviver = asyncio.ensure_future(revive())
            t0 = time.monotonic()
            batch = await consumer.peek(10, timeout=6.0)
            took = time.monotonic() - t0
            server = await reviver
            await consumer.close()
            await server.stop()
            return [p for *_x, p in batch], took, consumer.reconnects

        msgs, took, reconnects = asyncio.run(go())
        assert msgs == [b"back"]
        assert took < 4.0  # well inside the 6 s window, not a full nap
        assert reconnects >= 1


class TestEnsureTopicFallback:
    def test_no_loop_blocking_fallback_configures_retention(self):
        """ensure_topic from a sync context (no running loop) must reach
        the broker via the blocking one-shot instead of silently skipping
        the retention override."""
        async def go():
            server, port = await _server()
            provider = TcpMessagingProvider("127.0.0.1", port)
            # a worker thread has no running event loop — the old code
            # silently dropped the request there
            await asyncio.get_event_loop().run_in_executor(
                None, provider.ensure_topic, "caps", 1, 128 * 100)
            await asyncio.sleep(0.05)
            cap = server.bus.topic("caps").max_messages
            await server.stop()
            return cap

        assert asyncio.run(go()) == 100

    def test_no_loop_no_broker_logs_and_survives(self, caplog):
        provider = TcpMessagingProvider("127.0.0.1", 1)

        def sync_call():
            with caplog.at_level("WARNING",
                                 logger="openwhisk_tpu.messaging.tcp"):
                provider.ensure_topic("t", retention_bytes=1024)

        t = threading.Thread(target=sync_call)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        assert any("ensure_topic" in r.message for r in caplog.records)
