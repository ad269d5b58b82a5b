"""Messaging tests (mirrors reference MessageFeedTests + TestConnector use)."""
import asyncio
import json

import pytest

from openwhisk_tpu.core.entity import (ActivationId, ControllerInstanceId,
                                       EntityName, EntityPath,
                                       FullyQualifiedEntityName, Identity,
                                       InvokerInstanceId, Subject,
                                       ActivationResponse, WhiskActivation)
from openwhisk_tpu.messaging import (ActivationMessage,
                                     CombinedCompletionAndResultMessage,
                                     CompletionMessage, MemoryMessagingProvider,
                                     MessageFeed, PingMessage, ResultMessage,
                                     parse_ack)
from openwhisk_tpu.utils.transaction import TransactionId


def _identity():
    return Identity.generate("guest")


def _activation_message(blocking=True):
    return ActivationMessage(
        TransactionId(), FullyQualifiedEntityName.parse("guest/hello"),
        "1-abc", _identity(), ActivationId.generate(),
        ControllerInstanceId("0"), blocking, {"payload": "x"})


class TestMessageSerde:
    def test_activation_message_roundtrip(self):
        m = _activation_message()
        r = ActivationMessage.parse(m.serialize())
        assert r.activation_id == m.activation_id
        assert str(r.action) == "guest/hello"
        assert r.blocking
        assert r.content == {"payload": "x"}

    def test_ack_roundtrips(self):
        act = WhiskActivation(EntityPath("guest"), EntityName("hello"),
                              Subject("guest-user"), ActivationId.generate(),
                              1.0, 2.0, ActivationResponse.success({"a": 1}))
        inv = InvokerInstanceId(3)
        for msg in (CompletionMessage(TransactionId(), act.activation_id, False, inv),
                    ResultMessage(TransactionId(), act),
                    CombinedCompletionAndResultMessage(TransactionId(), act, inv)):
            r = parse_ack(msg.serialize())
            assert type(r) is type(msg)
            assert r.activation_id == act.activation_id
        c = parse_ack(CombinedCompletionAndResultMessage(TransactionId(), act, inv).serialize())
        assert c.is_slot_free and c.invoker.instance == 3
        assert c.activation.response.result == {"a": 1}
        res = parse_ack(ResultMessage(TransactionId(), act).serialize())
        assert not res.is_slot_free

    def test_ping(self):
        p = PingMessage.parse(PingMessage(InvokerInstanceId(7)).serialize())
        assert p.instance.instance == 7


class TestMemoryBus:
    def test_produce_consume(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            cons = prov.get_consumer("t", "g")
            await prod.send("t", b"m1")
            await prod.send("t", b"m2")
            batch = await cons.peek(10)
            cons.commit()
            return [p for (_, _, _, p) in batch]

        assert asyncio.run(run()) == [b"m1", b"m2"]

    def test_messages_before_subscribe_are_retained(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            await prod.send("t", b"early")
            cons = prov.get_consumer("t", "g")
            batch = await cons.peek(10)
            return [p for (_, _, _, p) in batch]

        assert asyncio.run(run()) == [b"early"]

    def test_competing_consumers_split_messages(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            c1 = prov.get_consumer("t", "g")
            c2 = prov.get_consumer("t", "g")
            for i in range(4):
                await prod.send("t", f"m{i}".encode())
            b1 = await c1.peek(2)
            b2 = await c2.peek(2)
            return len(b1) + len(b2)

        assert asyncio.run(run()) == 4


def _live_timers(loop):
    return [h for h in loop._scheduled if not h.cancelled()]


class TestMemoryBusWaiting:
    """ISSUE 38: a consumer with nothing to read parks on one future of
    its topic, with one timer for the time-out and no lock; a produce
    resolves the topic's parked consumers once per call. The contract of
    the bus, one case each, with the consumer PARKED when the message
    comes."""

    def _parked(self, prov, topic, group, n=10, timeout=2.0, **kw):
        """A consumer of `group` parked in peek; returns (consumer, task)."""
        cons = prov.get_consumer(topic, group, **kw)
        return cons, asyncio.ensure_future(cons.peek(n, timeout=timeout))

    def test_each_group_gets_every_message_once(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            _c1, p1 = self._parked(prov, "t", "g1")
            _c2, p2 = self._parked(prov, "t", "g2")
            await asyncio.sleep(0)
            assert len(prov.bus.topic("t").waiters) == 2
            await prod.send_many([("t", b"a", None), ("t", b"b", None)])
            return [[p for *_x, p in await t] for t in (p1, p2)]

        assert asyncio.run(run()) == [[b"a", b"b"], [b"a", b"b"]]

    @pytest.mark.parametrize("burst", [4, 7])
    def test_two_competing_consumers_get_each_of_1000_messages_once(
            self, burst):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            got = {"c1": [], "c2": []}

            async def consume(name):
                cons = prov.get_consumer("t", "g", max_peek=3)
                while True:
                    batch = await cons.peek(3, timeout=0.2)
                    cons.commit()
                    got[name].extend(p for *_x, p in batch)
                    await asyncio.sleep(0)   # a handler's turn

            tasks = [asyncio.ensure_future(consume(n)) for n in got]
            await asyncio.sleep(0)
            msgs = [f"m{i}".encode() for i in range(1000)]
            for i in range(0, 1000, burst):
                await prod.send_many([("t", m, None)
                                      for m in msgs[i:i + burst]])
                if i % 5 == 0:
                    await asyncio.sleep(0)   # both drain and park again
            for _ in range(2000):
                if len(got["c1"]) + len(got["c2"]) >= 1000:
                    break
                await asyncio.sleep(0)
            for t in tasks:
                t.cancel()
            await asyncio.wait(tasks)
            return got, msgs, prov.bus.topic("t").waiters

        got, msgs, waiters = asyncio.run(run())
        assert sorted(got["c1"] + got["c2"]) == sorted(msgs)
        assert got["c1"] and got["c2"]
        # what one consumer got, it got in arrival order
        for mine in got.values():
            assert mine == sorted(mine, key=lambda m: int(m[1:]))
        assert waiters == []     # a cancelled peek takes its future along

    def test_woken_to_a_drained_queue_parks_again_inside_its_timeout(self):
        async def run():
            from openwhisk_tpu.messaging.memory import BUS_STATS
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            loop = asyncio.get_event_loop()
            _c1, first = self._parked(prov, "t", "g", timeout=5.0)
            await asyncio.sleep(0)
            _c2, second = self._parked(prov, "t", "g", timeout=0.15)
            await asyncio.sleep(0)
            t0 = loop.time()
            stats0 = dict(BUS_STATS)
            await prod.send("t", b"only")     # wakes both; the first takes it
            got_first = await first
            timers = len(_live_timers(loop))  # the second's ONE timer lives
            got_second = await second         # ... and ends it, in its time
            took = loop.time() - t0
            return (got_first, got_second, took, timers,
                    {k: BUS_STATS[k] - stats0[k] for k in stats0})

        got_first, got_second, took, timers, stats = asyncio.run(run())
        assert [p for *_x, p in got_first] == [b"only"]
        assert got_second == [] and 0.1 <= took < 1.0
        assert timers == 1
        assert stats == {"parks": 1, "poll_timeouts": 1}

    def test_woken_to_a_drained_queue_still_gets_the_next_message(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            _c1, first = self._parked(prov, "t", "g", n=1)
            await asyncio.sleep(0)
            _c2, second = self._parked(prov, "t", "g", n=1)
            await asyncio.sleep(0)
            await prod.send("t", b"m1")
            await first
            await asyncio.sleep(0)
            assert not second.done()
            await prod.send("t", b"m2")
            return [p for *_x, p in await asyncio.wait_for(second, 1.0)]

        assert asyncio.run(run()) == [b"m2"]

    def test_timeout_returns_empty_and_leaves_no_timer_and_no_waiter(self):
        async def run():
            from openwhisk_tpu.messaging.memory import BUS_STATS
            prov = MemoryMessagingProvider()
            cons = prov.get_consumer("t", "g")
            loop = asyncio.get_event_loop()
            n0 = BUS_STATS["poll_timeouts"]
            t0 = loop.time()
            batch = await cons.peek(10, timeout=0.1)
            took = loop.time() - t0
            at_once = await cons.peek(10, timeout=0)
            return (batch, at_once, took, _live_timers(loop),
                    prov.bus.topic("t").waiters,
                    BUS_STATS["poll_timeouts"] - n0)

        batch, at_once, took, timers, waiters, timeouts = asyncio.run(run())
        assert batch == [] and at_once == []
        assert 0.09 <= took < 1.0
        assert timers == [] and waiters == []
        assert timeouts == 1

    def test_a_message_cancels_the_parked_peeks_timer(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            loop = asyncio.get_event_loop()
            _c, parked = self._parked(prov, "t", "g", timeout=30.0)
            await asyncio.sleep(0)
            armed = len(_live_timers(loop))
            await prod.send("t", b"m")
            batch = await parked
            return armed, _live_timers(loop), [p for *_x, p in batch]

        assert asyncio.run(run()) == (1, [], [b"m"])

    def test_set_max_messages_swaps_the_deque_under_a_parked_consumer(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            _c, parked = self._parked(prov, "t", "g", n=100)
            await asyncio.sleep(0)
            t = prov.bus.topic("t")
            before = t.groups["g"]
            prov.ensure_topic("t", retention_bytes=128 * 64)   # cap 64
            assert t.groups["g"] is not before
            await prod.send_many([("t", f"m{i}".encode(), None)
                                  for i in range(70)])
            return [p for *_x, p in await asyncio.wait_for(parked, 1.0)]

        # the parked consumer reads the NEW deque, which dropped the oldest
        assert asyncio.run(run()) == [f"m{i}".encode() for i in range(6, 70)]

    def test_from_latest_group_parks_past_the_backlog(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            await prod.send("health", b"stale")
            _c, parked = self._parked(prov, "health", "h1", from_latest=True)
            await asyncio.sleep(0)
            assert not parked.done()
            await prod.send("health", b"live")
            live = [p for *_x, p in await parked]
            # the backlog stays for a later queue-semantics group
            adopted = await prov.get_consumer("health", "q").peek(10, 0.1)
            return live, [p for *_x, p in adopted]

        assert asyncio.run(run()) == ([b"live"], [b"stale", b"live"])

    def test_default_backlog_is_adopted_then_the_group_parks(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            await prod.send("t", b"early")
            cons = prov.get_consumer("t", "g")
            assert "__default__" not in prov.bus.topic("t").groups
            first = await cons.peek(10, timeout=0.5)
            parked = asyncio.ensure_future(cons.peek(10, timeout=2.0))
            await asyncio.sleep(0)
            await prod.send("t", b"late")
            return [p for *_x, p in first], [p for *_x, p in await parked]

        assert asyncio.run(run()) == ([b"early"], [b"late"])

    def test_send_many_keeps_arrival_order_per_topic_and_stamps_each(
            self, monkeypatch):
        from openwhisk_tpu.messaging import memory
        stamped = []
        monkeypatch.setattr(memory, "stamp_produce", stamped.append)

        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            parked = {t: self._parked(prov, t, "g")[1] for t in ("a", "b")}
            await asyncio.sleep(0)
            await prod.send_many([("a", b"a0", "A0"), ("b", b"b0", None),
                                  ("a", b"a1", "A1"), ("b", b"b1", "B1"),
                                  ("a", b"a2", None)])
            await prod.send("a", b"raw")
            return ({t: [p for *_x, p in await f]
                     for t, f in parked.items()}, prod.sent_count)

        got, sent = asyncio.run(run())
        # the lone send landed before the woken consumer took its step
        assert got == {"a": [b"a0", b"a1", b"a2", b"raw"],
                       "b": [b"b0", b"b1"]}
        assert sent == 6
        assert stamped == ["A0", "A1", "B1", b"raw"]

    def test_one_produce_call_wakes_a_parked_consumer_once(self):
        """`send_many` resolves a topic's waiters once however many
        messages it appends: the consumer takes ONE step and reads all."""
        async def run():
            from openwhisk_tpu.messaging.memory import BUS_STATS
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            _c, parked = self._parked(prov, "t", "g", n=100)
            await asyncio.sleep(0)
            n0 = BUS_STATS["parks"]
            await prod.send_many([("t", b"m", None)] * 40)
            return len(await parked), BUS_STATS["parks"] - n0

        assert asyncio.run(run()) == (40, 1)

    def test_stopping_a_feed_takes_its_parked_peek_off_the_topic(self):
        async def run():
            prov = MemoryMessagingProvider()
            loop = asyncio.get_event_loop()

            async def handler(payload):
                pass

            feed = MessageFeed("idle", prov.get_consumer("t", "g"), 4,
                               handler, long_poll_timeout=30.0).start()
            await asyncio.sleep(0.01)
            parked = (len(prov.bus.topic("t").waiters),
                      len(_live_timers(loop)))
            await feed.stop()
            return parked, prov.bus.topic("t").waiters, _live_timers(loop)

        assert asyncio.run(run()) == ((1, 1), [], [])


class TestMessageFeed:
    def test_backpressure_and_delivery(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            cons = prov.get_consumer("activations", "invoker0")
            received = []
            feeds = {}

            async def handler(payload: bytes):
                received.append(payload)
                # simulate async completion later
                async def done():
                    await asyncio.sleep(0.01)
                    feeds["f"].processed()
                asyncio.get_event_loop().create_task(done())

            feed = MessageFeed("test", cons, maximum_handler_capacity=2,
                               handler=handler, long_poll_timeout=0.05)
            feeds["f"] = feed
            feed.start()
            for i in range(6):
                await prod.send("activations", f"m{i}".encode())
            await asyncio.sleep(0.3)
            await feed.stop()
            return received

        received = asyncio.run(run())
        assert received == [f"m{i}".encode() for i in range(6)]

    def test_capacity_limits_inflight(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            cons = prov.get_consumer("t", "g")
            inflight = {"now": 0, "max": 0}
            feeds = {}

            async def handler(payload: bytes):
                inflight["now"] += 1
                inflight["max"] = max(inflight["max"], inflight["now"])

                async def done():
                    await asyncio.sleep(0.02)
                    inflight["now"] -= 1
                    feeds["f"].processed()
                asyncio.get_event_loop().create_task(done())

            feed = MessageFeed("test", cons, maximum_handler_capacity=3,
                               handler=handler, long_poll_timeout=0.05)
            feeds["f"] = feed
            feed.start()
            for i in range(12):
                await prod.send("t", f"m{i}".encode())
            await asyncio.sleep(0.4)
            await feed.stop()
            return inflight["max"]

        assert asyncio.run(run()) <= 3

    def test_handler_error_does_not_kill_feed(self):
        async def run():
            prov = MemoryMessagingProvider()
            prod = prov.get_producer()
            cons = prov.get_consumer("t", "g")
            good = []
            feeds = {}

            async def handler(payload: bytes):
                if payload == b"bad":
                    raise RuntimeError("boom")
                good.append(payload)
                feeds["f"].processed()

            feed = MessageFeed("test", cons, maximum_handler_capacity=2,
                               handler=handler, long_poll_timeout=0.05)
            feeds["f"] = feed
            feed.start()
            await prod.send("t", b"bad")
            await prod.send("t", b"ok")
            await asyncio.sleep(0.2)
            await feed.stop()
            return good

        assert asyncio.run(run()) == [b"ok"]


class TestRetentionAndFromLatest:
    def test_orphan_group_queue_is_bounded(self):
        """A group nobody drains (retired controller) must not grow without
        bound: retention drops oldest, like Kafka."""
        async def go():
            from openwhisk_tpu.messaging.memory import MemoryMessagingProvider
            provider = MemoryMessagingProvider()
            provider.ensure_topic("health", retention_bytes=128 * 100)  # cap 100
            orphan = provider.get_consumer("health", "health-controller9")
            producer = provider.get_producer()
            for i in range(500):
                await producer.send("health", f"ping{i}".encode())
            q = provider.bus.topic("health").groups["health-controller9"]
            assert len(q) == 100
            # oldest dropped, newest retained
            batch = await orphan.peek(1000, timeout=0.1)
            return [p.decode() for (_t, _p, _o, p) in batch]

        msgs = asyncio.run(go())
        assert msgs[0] == "ping400" and msgs[-1] == "ping499"

    def test_from_latest_group_skips_backlog(self):
        """A new from_latest group (per-controller health view) starts at the
        stream head: no replay of retained pings."""
        async def go():
            from openwhisk_tpu.messaging.memory import MemoryMessagingProvider
            provider = MemoryMessagingProvider()
            producer = provider.get_producer()
            for i in range(50):
                await producer.send("health", f"stale{i}".encode())
            fresh = provider.get_consumer("health", "health-controller1",
                                          from_latest=True)
            await producer.send("health", b"live")
            batch = await fresh.peek(100, timeout=0.2)
            return [p for (_t, _p, _o, p) in batch]

        assert asyncio.run(go()) == [b"live"]

    def test_from_latest_over_tcp_bus(self):
        async def go():
            from openwhisk_tpu.messaging.tcp import (TcpBusServer,
                                                     TcpMessagingProvider)
            server = TcpBusServer("127.0.0.1", 0)
            await server.start()
            port = server._server.sockets[0].getsockname()[1]
            provider = TcpMessagingProvider("127.0.0.1", port)
            producer = provider.get_producer()
            for i in range(20):
                await producer.send("health", f"stale{i}".encode())
            fresh = provider.get_consumer("health", "health-c1",
                                          from_latest=True)
            # first peek creates the latest-positioned group server-side
            first = await fresh.peek(100, timeout=0.2)
            await producer.send("health", b"live")
            second = await fresh.peek(100, timeout=1.0)
            await fresh.close()
            await producer.close()
            await server.stop()
            return first, [p for (_t, _pp, _o, p) in second]

        first, second = asyncio.run(go())
        assert first == []
        assert second == [b"live"]

    def test_from_latest_reattach_resumes_backlog(self):
        """from_latest applies only to a NEW group (Kafka offset-reset
        semantics): re-attaching — e.g. after a TCP blip recreates the
        server-side consumer — must resume the buffered backlog, not drop
        it."""
        async def go():
            from openwhisk_tpu.messaging.memory import MemoryMessagingProvider
            provider = MemoryMessagingProvider()
            producer = provider.get_producer()
            c1 = provider.get_consumer("health", "health-c0", from_latest=True)
            await producer.send("health", b"p1")
            await producer.send("health", b"p2")
            # reconnect: same group, new consumer object
            c2 = provider.get_consumer("health", "health-c0", from_latest=True)
            batch = await c2.peek(10, timeout=0.2)
            return [p for (_t, _pp, _o, p) in batch]

        assert asyncio.run(go()) == [b"p1", b"p2"]


class TestProviderForBus:
    def test_default_is_tcp(self):
        from openwhisk_tpu.messaging import provider_for_bus
        from openwhisk_tpu.messaging.tcp import TcpMessagingProvider
        p = provider_for_bus("127.0.0.1:4555")
        assert isinstance(p, TcpMessagingProvider)

    def test_spi_binding_overrides(self, monkeypatch):
        """CONFIG_whisk_spi_MessagingProvider selects the backend for the
        service mains (the Kafka runbook's mechanism); the implementation
        receives the --bus address as its bootstrap argument."""
        from openwhisk_tpu.messaging import provider_for_bus

        monkeypatch.setenv(
            "CONFIG_whisk_spi_MessagingProvider",
            "openwhisk_tpu.messaging.memory:MemoryMessagingProvider")
        from openwhisk_tpu import spi
        spi.reset()
        try:
            from openwhisk_tpu.messaging import MemoryMessagingProvider
            p = provider_for_bus("broker:9092")
            # Memory takes no bootstrap: signature inspection skips the addr
            assert isinstance(p, MemoryMessagingProvider)
        finally:
            spi.reset()
