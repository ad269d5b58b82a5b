"""ISSUE 14: batch-shaped publish SPI + lazy ack result column.

Covers the acceptance contracts:
  * publish_many vs serial publish parity: identical placement decisions
    and books over fuzzed mixed-action batches, identical waterfall
    stamps, the serial path's exact exception texts (standby /
    no-invoker / device-throttle 429), and per-row capacity return on
    cancellation/abandonment;
  * off-switch: batch_wire=False ships every ack as its serial JSON,
    byte-exact;
  * the one-shared-clock arrival fix: _note_arrivals(now, 1) is
    bit-exact with _note_arrival(now);
  * lazy ack result column (ISSUE 35: the ack frame's opaque body, for
    a lone ack too): framed-wire roundtrip for every ack kind, the
    consumer-never-reads case asserted via the host observatory's
    `openwhisk_host_serde_*` counters (zero `ack_result` deserializes
    until a consumer touches the result), the coalescing producer
    shipping the frame end-to-end, and a 1-row ack frame leaving the
    balancer exactly as the serial pass leaves it.
"""
from __future__ import annotations

import asyncio
import json
import random
import time

import numpy as np
import pytest

from openwhisk_tpu.controller.loadbalancer import (LoadBalancerException,
                                                   TpuBalancer)
from openwhisk_tpu.controller.loadbalancer.base import (
    LoadBalancerThrottleException, PublishCoalescer, maybe_batch_publish)
from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                       ActionLimits, CodeExec,
                                       ControllerInstanceId, EntityName,
                                       EntityPath, ExecutableWhiskAction,
                                       Identity, InvokerInstanceId, MB,
                                       MemoryLimit, TimeLimit,
                                       WhiskActivation)
from openwhisk_tpu.core.entity.ids import DocRevision
from openwhisk_tpu.messaging import (ActivationMessage,
                                     MemoryMessagingProvider, PingMessage)
from openwhisk_tpu.messaging.coalesce import CoalescingProducer
from openwhisk_tpu.messaging.columnar import (KIND_ACK, LazyWhiskActivation,
                                              is_batch_payload, make_batch,
                                              parse_batch)
from openwhisk_tpu.messaging.message import (
    CombinedCompletionAndResultMessage, CompletionMessage, ResultMessage,
    parse_ack)
from openwhisk_tpu.utils.hostprof import GLOBAL_HOST_OBSERVATORY
from openwhisk_tpu.utils.ring_buffer import ColumnRing
from openwhisk_tpu.utils.transaction import TransactionId
from openwhisk_tpu.utils.waterfall import (ActivationWaterfall,
                                           STAGE_PUBLISH_ENQUEUE,
                                           WaterfallConfig, _CTX_BASE)


def make_action(name="act", memory=256):
    a = ExecutableWhiskAction(EntityPath("guest"), EntityName(name),
                              CodeExec(kind="python:3", code="x"),
                              limits=ActionLimits(TimeLimit(5000),
                                                  MemoryLimit(MB(memory))))
    a.rev = DocRevision("1-b")
    return a


def make_msg(action, ident, blocking=False):
    return ActivationMessage(
        TransactionId(), action.fully_qualified_name, action.rev.rev, ident,
        ActivationId.generate(), ControllerInstanceId("0"), blocking, {})


async def _healthy_balancer(provider, n_invokers=4, mem=4096, **kw):
    """A TpuBalancer with `n_invokers` registered-and-healthy rows (pings
    only — no consumers ack, so placements hold until released)."""
    bal = TpuBalancer(provider, ControllerInstanceId("0"),
                      managed_fraction=1.0, blackbox_fraction=0.0,
                      prewarm=False, **kw)
    await bal.start()
    producer = provider.get_producer()
    provider.ensure_topic("health")
    instances = [InvokerInstanceId(i, user_memory=MB(mem))
                 for i in range(n_invokers)]
    for _ in range(120):
        for inst in instances:
            await producer.send("health", PingMessage(inst))
        await asyncio.sleep(0.05)
        health = await bal.invoker_health()
        if sum(h.status == "up" for h in health) >= n_invokers:
            break
    else:
        raise RuntimeError("fleet never became healthy")
    # and usable on the device: the flush the registration armed has
    # folded its flips
    for _ in range(100):
        if not bal._health_updates:
            break
        await asyncio.sleep(0.01)
    return bal


async def _drain(bal, timeout=5.0):
    """Wait until no device step is in flight and no work is queued."""
    t0 = time.monotonic()
    while (bal._inflight_steps or bal._pending or bal._releases):
        if time.monotonic() - t0 > timeout:
            raise RuntimeError("balancer did not drain")
        await asyncio.sleep(0.02)
    # one idle fold may still be pending on the flush task
    await asyncio.sleep(0.05)


def _placements(bal, aids):
    return [bal.activation_slots[a].invoker.instance for a in aids]


class TestPublishManyParity:
    def test_parity_fuzz_decisions_books_stamps(self):
        """Serial publish and publish_many over the same fuzzed mixed
        batch produce identical per-row placements, identical device
        books, and both stamp PUBLISH_ENQUEUE."""
        async def go():
            rng = random.Random(11)
            ident = Identity.generate("guest")
            actions = [make_action(f"p{i}", memory=rng.choice([128, 256]))
                       for i in range(5)]
            k = 24
            seq = [actions[rng.randrange(len(actions))] for _ in range(k)]

            async def run_serial():
                provider = MemoryMessagingProvider()
                bal = await _healthy_balancer(provider)
                bal.waterfall = ActivationWaterfall(WaterfallConfig())
                aids = []
                for a in seq:
                    msg = make_msg(a, ident)
                    aid = msg.activation_id.asString
                    ctx = bal.waterfall.begin(aid)
                    aids.append((aid, ctx))
                    await bal.publish(a, msg)
                await _drain(bal)
                out = (_placements(bal, [a for a, _ in aids]),
                       np.asarray(bal.state.free_mb).copy(),
                       [ctx[_CTX_BASE + STAGE_PUBLISH_ENQUEUE] != 0
                        for _, ctx in aids])
                await bal.close()
                return out

            async def run_batched():
                provider = MemoryMessagingProvider()
                bal = await _healthy_balancer(provider)
                assert bal.batch_publish
                bal.waterfall = ActivationWaterfall(WaterfallConfig())
                pairs, aids = [], []
                for a in seq:
                    msg = make_msg(a, ident)
                    aid = msg.activation_id.asString
                    aids.append((aid, bal.waterfall.begin(aid)))
                    pairs.append((a, msg))
                outs = bal.publish_many(pairs)
                await asyncio.gather(*outs)
                await _drain(bal)
                out = (_placements(bal, [a for a, _ in aids]),
                       np.asarray(bal.state.free_mb).copy(),
                       [ctx[_CTX_BASE + STAGE_PUBLISH_ENQUEUE] != 0
                        for _, ctx in aids])
                await bal.close()
                return out

            ser_dec, ser_books, ser_stamps = await run_serial()
            bat_dec, bat_books, bat_stamps = await run_batched()
            assert ser_dec == bat_dec
            assert np.array_equal(ser_books, bat_books)
            assert all(ser_stamps) and all(bat_stamps)

        asyncio.run(go())

    def test_exception_texts_match_serial(self):
        """standby / no-invoker refusals through publish_many carry the
        serial path's exact texts, per row."""
        async def go():
            ident = Identity.generate("guest")
            action = make_action("t")
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider)
            try:
                bal.ha_standby = True
                with pytest.raises(LoadBalancerException) as e_serial:
                    await bal.publish(action, make_msg(action, ident))
                outs = bal.publish_many([(action, make_msg(action, ident))])
                with pytest.raises(LoadBalancerException) as e_batch:
                    await outs[0]
                assert str(e_serial.value) == str(e_batch.value)
                bal.ha_standby = False
            finally:
                await bal.close()

            # empty fleet: same no-invoker text both ways
            provider2 = MemoryMessagingProvider()
            bal2 = TpuBalancer(provider2, ControllerInstanceId("0"),
                               prewarm=False)
            try:
                with pytest.raises(LoadBalancerException) as s2:
                    await bal2.publish(action, make_msg(action, ident))
                outs = bal2.publish_many([(action, make_msg(action, ident))])
                with pytest.raises(LoadBalancerException) as b2:
                    await outs[0]
                assert str(s2.value) == str(b2.value)
            finally:
                await bal2.close()

        asyncio.run(go())

    def test_device_throttle_429_text(self):
        """Device rate admission rejections through publish_many raise
        LoadBalancerThrottleException with the serial path's text."""
        async def go():
            ident = Identity.generate("guest")
            action = make_action("thr", memory=128)
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider,
                                          rate_limit_per_minute=2)
            try:
                pairs = [(action, make_msg(action, ident))
                         for _ in range(16)]
                outs = bal.publish_many(pairs)
                results = await asyncio.gather(*outs,
                                               return_exceptions=True)
                throttled = [r for r in results
                             if isinstance(r, LoadBalancerThrottleException)]
                assert throttled, "expected some device-throttled rows"
                assert str(throttled[0]) == ("Too many requests in the "
                                             "last minute (device rate "
                                             "admission).")
            finally:
                await bal.close()

        asyncio.run(go())

    def test_cancellation_returns_capacity_per_row(self):
        """Rows whose caller future is cancelled before placement give
        their reserved capacity back; surviving rows keep theirs."""
        async def go():
            ident = Identity.generate("guest")
            action = make_action("c", memory=256)
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider, n_invokers=2)
            try:
                free0 = int(np.asarray(bal.state.free_mb).sum())
                pairs = [(action, make_msg(action, ident))
                         for _ in range(8)]
                outs = bal.publish_many(pairs)
                for out in outs[:4]:
                    out.cancel()
                results = await asyncio.gather(*outs,
                                               return_exceptions=True)
                assert sum(isinstance(r, asyncio.CancelledError)
                           for r in results) == 4
                await _drain(bal)
                free1 = int(np.asarray(bal.state.free_mb).sum())
                # only the 4 surviving placements hold memory
                assert free0 - free1 == 4 * 256
                # host slot refcounts balanced back to the survivors
                assert bal._slots.refcount.get(
                    f"{action.fully_qualified_name}:256") == 4
            finally:
                await bal.close()

        asyncio.run(go())

    def test_cancelled_send_flush_cancels_caller(self):
        """A dispatch handed to the bus coalescer whose flush future is
        CANCELLED (drainer torn down with the send still queued) must
        cancel the caller — serial parity is the awaited send raising
        CancelledError, never success for an unsent dispatch."""
        async def go():
            ident = Identity.generate("guest")
            action = make_action("sc")
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider, n_invokers=2)
            real = bal.producer
            try:
                sendfs = []

                class StubProducer:
                    def send_nowait(self, topic, msg):
                        f = asyncio.get_event_loop().create_future()
                        sendfs.append(f)
                        return f

                    def __getattr__(self, name):
                        return getattr(real, name)

                bal.producer = StubProducer()
                outs = bal.publish_many([(action, make_msg(action, ident))])
                for _ in range(200):
                    if sendfs:
                        break
                    await asyncio.sleep(0.02)
                assert sendfs, "dispatch never reached send_nowait"
                assert not outs[0].done()
                sendfs[0].cancel()
                await asyncio.sleep(0)
                with pytest.raises(asyncio.CancelledError):
                    await outs[0]
            finally:
                bal.producer = real
                await bal.close()

        asyncio.run(go())

    def test_failing_rows_skip_arrival_note(self):
        """Rows whose _build_row raises never reach the serial path's
        _note_arrival, so the batched shared clock read must count only
        BUILT rows — else a burst of failing rows decays the arrival
        EWMA (and the coalesce-window policy it feeds) where serial
        stays eager."""
        async def go():
            ident = Identity.generate("guest")
            action = make_action("f")
            bad = make_action("bad")
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider, n_invokers=2)
            try:
                noted = []
                orig_note = bal._note_arrivals
                bal._note_arrivals = (
                    lambda t, n: (noted.append(n), orig_note(t, n))[1])
                real_build = bal._build_row

                def build(a, m):
                    if a is bad:
                        raise RuntimeError("boom")
                    return real_build(a, m)

                bal._build_row = build
                outs = bal.publish_many([(action, make_msg(action, ident)),
                                         (bad, make_msg(bad, ident)),
                                         (action, make_msg(action, ident))])
                with pytest.raises(RuntimeError):
                    await outs[1]
                await asyncio.gather(outs[0], outs[2])
                assert noted == [2]
                # an all-failing batch notes no arrivals at all
                outs2 = bal.publish_many([(bad, make_msg(bad, ident))])
                with pytest.raises(RuntimeError):
                    await outs2[0]
                assert noted == [2]
            finally:
                await bal.close()

        asyncio.run(go())

    def test_note_arrivals_n1_bit_exact(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider, n_invokers=1)
            try:
                bal._gap_ewma_ms = 123.456
                bal._last_pub_t = 10.0
                bal._last_gap_ms = 9.0
                a = (bal._gap_ewma_ms, bal._last_pub_t, bal._last_gap_ms)
                bal._note_arrivals(10.5, 1)
                n1 = (bal._gap_ewma_ms, bal._last_pub_t, bal._last_gap_ms)
                bal._gap_ewma_ms, bal._last_pub_t, bal._last_gap_ms = a
                bal._note_arrival(10.5)
                serial = (bal._gap_ewma_ms, bal._last_pub_t,
                          bal._last_gap_ms)
                assert n1 == serial
                # n>1: pure decay of the n=1 blend, zero last gap
                bal._gap_ewma_ms, bal._last_pub_t, bal._last_gap_ms = a
                bal._note_arrivals(10.5, 4)
                assert bal._last_gap_ms == 0.0
                assert bal._gap_ewma_ms == pytest.approx(
                    serial[0] * 0.9 ** 3)
            finally:
                await bal.close()

        asyncio.run(go())


class TestPublishCoalescer:
    def test_bridges_result_exception_and_cancel(self):
        """The front-door coalescer resolves waiters to publish_many's
        row outcomes without minting tasks, and cancellation flows back
        to the row future."""
        async def go():
            calls = []

            class FakeBal:
                batch_publish = True
                max_batch = 256

                def publish_many(self, pairs):
                    loop = asyncio.get_event_loop()
                    rows = [loop.create_future() for _ in pairs]
                    calls.append((pairs, rows))
                    return rows

            co = PublishCoalescer(FakeBal())
            w1 = co.submit("a", "m1")
            w2 = co.submit("a", "m2")
            w3 = co.submit("a", "m3")
            await asyncio.sleep(0)  # end-of-sweep flush
            assert len(calls) == 1 and len(calls[0][0]) == 3
            rows = calls[0][1]
            rows[0].set_result("promise")
            rows[1].set_exception(LoadBalancerException("nope"))
            w3.cancel()
            await asyncio.sleep(0)
            assert await w1 == "promise"
            with pytest.raises(LoadBalancerException):
                await w2
            assert rows[2].cancelled()

        asyncio.run(go())

    def test_full_batch_flushes_inline(self):
        async def go():
            flushed = []

            class FakeBal:
                batch_publish = True
                max_batch = 2

                def publish_many(self, pairs):
                    flushed.append(len(pairs))
                    loop = asyncio.get_event_loop()
                    rows = [loop.create_future() for _ in pairs]
                    for r in rows:
                        r.set_result(None)
                    return rows

            co = PublishCoalescer(FakeBal(), max_batch=2)
            co.submit("a", "m1")
            co.submit("a", "m2")  # fills the batch: flush NOW, no sweep
            assert flushed == [2]

        asyncio.run(go())


class TestLazyAckResults:
    def _acks(self, n=3):
        ident = Identity.generate("guest")
        inv = InvokerInstanceId(1, user_memory=MB(1024))
        now = time.time()
        acks = []
        for i in range(n):
            act = WhiskActivation(
                EntityPath("guest"), EntityName(f"a{i}"), ident.subject,
                ActivationId.generate(), now, now,
                ActivationResponse.success({"i": i}), duration=1)
            acks.append(CombinedCompletionAndResultMessage(
                TransactionId(), act, inv))
        acks.append(CompletionMessage(TransactionId(),
                                      ActivationId.generate(), False, inv))
        acks.append(ResultMessage(TransactionId(), WhiskActivation(
            EntityPath("guest"), EntityName("r"), ident.subject,
            ActivationId.generate(), now, now,
            ActivationResponse.success({"r": 1}), duration=2)))
        return acks

    def test_lazy_frame_roundtrip_all_kinds(self):
        """The frame's acks against the serial parser's eager ones."""
        acks = self._acks()
        lazy = make_batch(KIND_ACK, acks).serialize()
        assert is_batch_payload(lazy)
        out1 = [parse_ack(a.serialize()) for a in acks]
        k2, out2 = parse_batch(lazy)
        assert k2 == KIND_ACK
        for a, b in zip(out1, out2):
            assert a.kind == b.kind
            assert a.activation_id.asString == b.activation_id.asString
            assert a.is_system_error == b.is_system_error
            assert (a.invoker is None) == (b.invoker is None)
            if a.activation is None:
                assert b.activation is None
                continue
            assert isinstance(b.activation, LazyWhiskActivation)
            assert not b.activation.materialized
            # materializing yields the same activation (modulo the
            # `updated` stamp minted fresh at every to_json call)
            ja = dict(a.activation.to_json())
            jb = dict(b.activation.to_json())
            ja.pop("updated", None)
            jb.pop("updated", None)
            assert ja == jb
            assert b.activation.materialized

    def test_lazy_relay_passes_raw_bytes_through(self):
        """Re-encoding an unread lazy ack reuses the raw payload — no
        parse, no re-serialize."""
        acks = self._acks(2)
        lazy = make_batch(KIND_ACK, acks).serialize()
        _k, out = parse_batch(lazy)
        relay = make_batch(KIND_ACK, out).serialize()
        _k2, out2 = parse_batch(relay)
        for a, b in zip(out, out2):
            assert not (a.activation is not None
                        and a.activation.materialized)
            if a.activation is not None:
                assert b.activation.raw == a.activation.raw

    def test_off_switch_byte_exact(self):
        """batch_wire=False ships every ack as the serial wire's own
        JSON, one payload a message, byte for byte."""
        acks = self._acks(2)

        async def go():
            provider = MemoryMessagingProvider()
            provider.ensure_topic("completed0")
            consumer = provider.get_consumer("completed0", "g0")
            prod = CoalescingProducer(provider.get_producer(),
                                      batch_wire=False)
            await prod.send_batch("completed0", acks)
            await prod.flush()
            got = await consumer.peek(8, timeout=1.0)
            await prod.close()
            return [bytes(g[3]) for g in got]

        payloads = asyncio.run(go())
        assert len(payloads) == len(acks)
        for payload, ack in zip(payloads, acks):
            assert not is_batch_payload(payload)
            assert parse_ack(payload).kind == ack.kind
            if ack.activation is None:
                assert payload == ack.serialize()
                continue
            # a record's `updated` is stamped anew by every to_json()
            cut = payload.index(b'"updated"')
            assert payload[:cut] == ack.serialize()[:cut]
            tail = payload.index(b",", cut)
            assert payload[tail:] == ack.serialize()[tail:]

    def test_corrupt_lazy_body_rejected(self):
        acks = self._acks(2)
        lazy = make_batch(KIND_ACK, acks).serialize()
        with pytest.raises(ValueError):
            parse_batch(lazy[:-3])  # truncated body != the lengths' sum

    def test_corrupt_body_behind_consistent_frame(self):
        """A garbled response payload behind a CONSISTENT frame (header
        and per-row lengths intact) decodes fine and only fails on the
        consumer's first read — which must be the well-defined
        'corrupt lazy ack result' ValueError, not a JSONDecodeError
        escaping deep inside response rendering."""
        acks = self._acks(2)
        lazy = make_batch(KIND_ACK, acks).serialize()
        body_at = lazy.index(b'{"namespace"')   # the first response record
        garbled = lazy[:body_at] + b"\x00" * (len(lazy) - body_at)
        _k, out = parse_batch(garbled)  # frame-level decode succeeds
        bad = next(a.activation for a in out if a.activation is not None)
        assert isinstance(bad, LazyWhiskActivation)
        assert not bad.materialized
        with pytest.raises(ValueError, match="corrupt lazy ack result"):
            _ = bad.response

    @pytest.mark.parametrize("n", [1, 4])
    def test_consumer_never_reads_skips_parse(self, n):
        """The acceptance counter check: an ack frame, of one ack or of
        several, processed by the balancer's completion path books ZERO
        `ack_result` deserializes until a consumer touches the result —
        then exactly the touched rows parse."""
        async def go():
            ident = Identity.generate("guest")
            action = make_action("z", memory=128)
            provider = MemoryMessagingProvider()
            bal = await _healthy_balancer(provider, n_invokers=2)
            was_enabled = GLOBAL_HOST_OBSERVATORY.enabled
            GLOBAL_HOST_OBSERVATORY.enabled = True
            try:
                GLOBAL_HOST_OBSERVATORY.reset()
                inv = InvokerInstanceId(0, user_memory=MB(4096))
                msgs, promises = [], []
                for i in range(n):
                    msg = make_msg(action, ident, blocking=True)
                    msgs.append(msg)
                    promises.append(bal.setup_activation(msg, action, inv))
                payload = make_batch(
                    KIND_ACK, _combined_acks(msgs, ident, inv)).serialize()
                bal.process_acknowledgement_frame(payload)

                def ack_result_count():
                    snap = GLOBAL_HOST_OBSERVATORY.snapshot()
                    return sum(row["count"] for row in snap["serde"]
                               if row["hop"] == "ack_result"
                               and row["direction"] == "deserialize")

                # every promise resolved, nothing parsed
                results = [p.result() for p in promises]
                assert all(isinstance(r, LazyWhiskActivation)
                           for r in results)
                assert ack_result_count() == 0
                # one consumer reads its result -> exactly one parse
                assert results[0].response.status_code == 0
                assert ack_result_count() == 1
            finally:
                GLOBAL_HOST_OBSERVATORY.enabled = was_enabled
                GLOBAL_HOST_OBSERVATORY.reset()
                await bal.close()

        asyncio.run(go())

    @pytest.mark.parametrize("n", [1, 2])
    def test_coalescing_producer_ships_lazy_frames(self, n):
        """End to end through the CoalescingProducer: the acks of one
        topic and flush, a lone one too, are ONE frame."""
        async def go():
            provider = MemoryMessagingProvider()
            provider.ensure_topic("completed0")
            consumer = provider.get_consumer("completed0", "g0")
            prod = CoalescingProducer(provider.get_producer(),
                                      batch_wire=True)
            await prod.send_batch("completed0", self._acks(2)[:n])
            await prod.flush()
            got = await consumer.peek(8, timeout=1.0)
            assert len(got) == 1
            payload = got[0][3]
            assert is_batch_payload(payload)
            kind, out = parse_batch(payload)
            assert kind == KIND_ACK
            assert len(out) == n
            assert all(isinstance(a.activation, LazyWhiskActivation)
                       and not a.activation.materialized for a in out)
            await prod.close()

        asyncio.run(go())

    def test_one_row_frame_leaves_what_the_serial_pass_leaves(self):
        """A lone ack used to take `process_acknowledgement` (serial
        JSON, the per-ack pass); it now arrives as a 1-row frame and
        takes the batched pass. Counters, gauges, waterfall rows, the
        queued releases and the promises' results must not tell the two
        apart, for a tracked ack, a late one and a health probe's."""
        async def run(wire):
            ident = Identity.generate("guest")
            action = make_action("z", memory=128)
            bal = await _healthy_balancer(MemoryMessagingProvider(),
                                          n_invokers=2)
            bal.waterfall = ActivationWaterfall(WaterfallConfig())
            try:
                inv = InvokerInstanceId(1, user_memory=MB(4096))
                msgs, promises = [], []
                for i in range(3):
                    msg = make_msg(action, ident, blocking=True)
                    msg.activation_id = ActivationId(f"{i + 1:032x}")
                    msg.transid = TransactionId(f"tid_{i}",
                                                start_wallclock=1.0 + i)
                    bal.waterfall.begin(msg.activation_id.asString,
                                        t0_ns=1)
                    bal.waterfall.stamp(msg.activation_id.asString,
                                        STAGE_PUBLISH_ENQUEUE, 1000)
                    msgs.append(msg)
                    promises.append(bal.setup_activation(msg, action, inv))
                late = make_msg(action, ident)
                late.activation_id = ActivationId(f"{99:032x}")
                probe = make_msg(action, ident)
                probe.activation_id = ActivationId(f"{98:032x}")
                bal._health_probe_ids.add(probe.activation_id.asString)
                acks = _combined_acks(msgs + [late, probe], ident, inv)
                acks[1].is_system_error = True
                before = bal.metrics.snapshot()
                for ack in acks:
                    if wire == "frame":
                        bal.process_acknowledgement_frame(
                            make_batch(KIND_ACK, [ack]).serialize())
                    else:
                        bal.process_acknowledgement(ack.serialize())
                after = bal.metrics.snapshot()
                # which rows folded, in which order, with which stages
                # stamped; the durations are the clock's own readings
                rows = [(r["activation_id"], r["trace_id"], r["clamped"],
                         sorted(r["stages_ms"]))
                        for r in bal.waterfall.recent(8)]
                return {
                    "counters": {k: v - before["counters"].get(k, 0)
                                 for k, v in after["counters"].items()},
                    "gauges": {k: v for k, v in after["gauges"].items()
                               if not k[0].startswith(("host_", "bus_"))},
                    "releases": list(bal._releases),
                    "active": (bal.total_active_activations,
                               sorted(bal.activation_slots)),
                    "waterfall": (bal.waterfall._finished,
                                  bal.waterfall.active, rows),
                    "results": [p.result().to_json()["response"]
                                for p in promises],
                    "probes": sorted(bal._health_probe_ids),
                }
            finally:
                await bal.close()

        serial = asyncio.run(run("serial"))
        frame = asyncio.run(run("frame"))
        assert frame == serial
        assert len(serial["releases"]) == 3
        assert serial["waterfall"][0] == 3 and serial["active"][0] == 0


def _combined_acks(msgs, ident, inv):
    now = time.time()
    return [CombinedCompletionAndResultMessage(
        m.transid,
        WhiskActivation(EntityPath("guest"), EntityName("z"), ident.subject,
                        m.activation_id, now, now,
                        ActivationResponse.success({"ok": 1}), duration=1),
        inv) for m in msgs]


class TestColumnRingPushBlock:
    def test_push_block_equals_pushes(self):
        rng = np.random.RandomState(3)
        for trial in range(20):
            a = ColumnRing(4, 8)
            b = ColumnRing(4, 8)
            # interleave singles, blocks, and pops to exercise wrap+grow
            for step in range(rng.randint(1, 8)):
                k = rng.randint(1, 13)
                block = rng.randint(0, 1000, size=(4, k)).astype(np.int32)
                for j in range(k):
                    a.push(block[:, j])
                b.push_block(block)
                assert len(a) == len(b)
                if rng.rand() < 0.5 and len(a):
                    n = rng.randint(1, len(a) + 1)
                    oa = np.zeros((4, n), np.int32)
                    ob = np.zeros((4, n), np.int32)
                    a.pop_into(oa, n)
                    b.pop_into(ob, n)
                    assert np.array_equal(oa, ob)
            n = len(a)
            if n:
                oa = np.zeros((4, n), np.int32)
                ob = np.zeros((4, n), np.int32)
                a.pop_into(oa, n)
                b.pop_into(ob, n)
                assert np.array_equal(oa, ob)
