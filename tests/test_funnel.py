"""ISSUE 20: front-end -> balancer admission funnel, tier-1 half.

Covers the acceptance contracts:
  * wire roundtrips for the `fun1` admission frame (activation columns +
    origin/seq/epoch header) and the `funA` per-row outcome frame;
  * partial-dedupe replay over the REAL TCP bus: a retried frame places
    only rows whose first delivery was lost — zero double executions;
  * fence-stamped rows refused whole by a stale-epoch balancer (both
    failure directions: zombie sender behind, demoted balancer behind),
    with the refusal text naming both epochs;
  * backpressure 429 text parity: the funnel-depth bound answers with
    the serial front door's EXACT CONCURRENT_LIMIT_MESSAGE, and the
    device-rate throttle's exact serial text + exception type survive
    the wire hop;
  * blocking completion roundtrip: the front end's promise resolves to
    the WhiskActivation placed at the balancer;
  * the sender's application-level retry re-ships lost frames and the
    receiver's outcome cache answers replayed rows from memory.

The multi-process shared-deployment sweep rides the `multiproc` marker
(conftest probe: cpu count + spawn capability).
"""
from __future__ import annotations

import asyncio
import time

import pytest

from openwhisk_tpu.controller.entitlement import CONCURRENT_LIMIT_MESSAGE
from openwhisk_tpu.controller.loadbalancer.base import (
    ActiveAckTimeout, LoadBalancerException, LoadBalancerThrottleException)
from openwhisk_tpu.controller.loadbalancer.funnel import (
    FrameSender, FunnelBalancer, FunnelConfig, FunnelReceiver,
    funnel_ack_topic, funnel_topic, stale_epoch_text)
from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                       ControllerInstanceId, EntityName,
                                       EntityPath, Identity, Subject,
                                       WhiskActivation)
from openwhisk_tpu.messaging import MemoryMessagingProvider
from openwhisk_tpu.messaging.columnar import (FunnelAckMessage,
                                              FunnelBatchMessage,
                                              FunnelOutcome, KIND_FUNNEL,
                                              KIND_FUNNEL_ACK,
                                              is_batch_payload, parse_batch)

from tests.test_balancers import make_action, make_msg
from tests.test_partitions import until

DEVICE_THROTTLE_TEXT = ("Too many requests in the last minute "
                        "(device rate admission).")


def _activation(aid: ActivationId) -> WhiskActivation:
    now = int(time.time() * 1000)
    return WhiskActivation(EntityPath("guest"), EntityName("fx"),
                           Subject("guest-user"), aid, now, now,
                           ActivationResponse.success({"ok": 1}),
                           duration=1)


class StubBalancer:
    """A balancer double implementing the publish_many contract: each
    row future resolves to a completion promise (mode='place'), raises
    the serial device throttle ('throttle') or the standby refusal
    ('standby'). Placements are recorded so double executions show."""

    fence_epoch = None
    waterfall = None

    def __init__(self, mode="place"):
        self.mode = mode
        self.placed = []
        self.promises = {}

    def publish_many(self, pairs):
        loop = asyncio.get_event_loop()
        outs = []
        for _action, msg in pairs:
            out = loop.create_future()
            aid = msg.activation_id.asString
            if self.mode == "throttle":
                out.set_exception(
                    LoadBalancerThrottleException(DEVICE_THROTTLE_TEXT))
            elif self.mode == "standby":
                out.set_exception(LoadBalancerException(
                    "standby controller: placement is fenced to the "
                    "active leader"))
            else:
                self.placed.append(aid)
                promise = loop.create_future()
                self.promises[aid] = promise
                out.set_result(promise)
            outs.append(out)
        return outs


async def _resolver(name, rev):
    return make_action("fx", memory=128)


def _receiver(provider, balancer, instance="0", **kw):
    return FunnelReceiver(provider, ControllerInstanceId(instance),
                          balancer, resolver=_resolver, **kw)


def _frontend(provider, origin="7", target=0, **cfg):
    config = FunnelConfig(**cfg) if cfg else FunnelConfig()
    return FunnelBalancer(provider, ControllerInstanceId(origin),
                          target=target, config=config)


def _msgs(n, blocking=False):
    action = make_action("fx", memory=128)
    ident = Identity.generate("guest")
    return action, [make_msg(action, ident, blocking) for _ in range(n)]


class TestFunnelWire:
    def test_funnel_frame_roundtrip(self):
        action, msgs = _msgs(3, blocking=True)
        frame = FunnelBatchMessage(msgs, origin=7, seq=42, epoch=5)
        raw = frame.serialize()
        assert is_batch_payload(raw)
        kind, decoded = parse_batch(raw)
        assert kind == KIND_FUNNEL
        assert (decoded.origin, decoded.seq, decoded.epoch) == (7, 42, 5)
        assert [m.activation_id.asString for m in decoded.msgs] == \
            [m.activation_id.asString for m in msgs]
        for orig, back in zip(msgs, decoded.msgs):
            assert str(back.action) == str(orig.action)
            assert back.blocking == orig.blocking
            assert back.user.subject == orig.user.subject

    def test_funnel_ack_roundtrip_all_codes(self):
        aid = ActivationId.generate()
        act = _activation(aid)
        rows = [
            FunnelOutcome("p", "a1"),
            FunnelOutcome("r", "a2", exc=("T", DEVICE_THROTTLE_TEXT)),
            FunnelOutcome("r", "a3", exc=("L", "no invokers")),
            FunnelOutcome("c", aid.asString, resp=act.to_json()),
            FunnelOutcome("c", "a5"),  # slim non-blocking completion
            FunnelOutcome("f", "a6", err=True),
        ]
        raw = FunnelAckMessage(7, 3, rows).serialize()
        assert is_batch_payload(raw)
        kind, frame = parse_batch(raw)
        assert kind == KIND_FUNNEL_ACK
        assert (frame.origin, frame.epoch) == (7, 3)
        assert [r.code for r in frame.rows] == list("prrccf")
        assert frame.rows[1].exc == ("T", DEVICE_THROTTLE_TEXT)
        assert frame.rows[2].exc == ("L", "no invokers")
        back = WhiskActivation.from_json(frame.rows[3].resp)
        assert back.activation_id.asString == aid.asString
        assert frame.rows[4].resp is None
        assert frame.rows[5].err is True


class TestFunnelReceiver:
    def test_partial_dedupe_replay(self):
        """The pubN discipline one layer up: a replayed frame (same
        seq) places ONLY rows never seen — zero double executions."""

        async def go():
            provider = MemoryMessagingProvider()
            bal = StubBalancer()
            recv = _receiver(provider, bal)
            recv.start()
            producer = provider.get_producer()
            action, msgs = _msgs(3)
            a, b, c = msgs
            await producer.send(funnel_topic(0),
                                FunnelBatchMessage([a, b], 7, 1, 0))
            await until(lambda: len(bal.placed) == 2)
            # replay seq 1 with one extra row: only C is fresh
            await producer.send(funnel_topic(0),
                                FunnelBatchMessage([a, b, c], 7, 1, 0))
            await until(lambda: len(bal.placed) == 3)
            await asyncio.sleep(0.05)
            placed, dups = list(bal.placed), recv.dup_rows
            await recv.stop()
            return placed, dups, [m.activation_id.asString for m in msgs]

        placed, dups, aids = asyncio.run(go())
        assert placed == aids, "every row places exactly once, in order"
        assert dups == 2

    def test_stale_epoch_refuses_whole_frame(self):
        """Nonzero frame epochs must equal the balancer's live epoch:
        a frame behind (zombie sender) and a frame ahead (demoted,
        stale-epoch balancer) are both refused whole, naming both
        epochs. Epoch 0 = unfenced bootstrap, admitted."""

        async def go():
            provider = MemoryMessagingProvider()
            bal = StubBalancer()
            bal.fence_epoch = 3
            recv = _receiver(provider, bal)
            recv.start()
            acks = []
            consumer = provider.get_consumer(funnel_ack_topic(7), "t")
            producer = provider.get_producer()

            async def drain():
                while True:
                    for _t, _p, _o, payload in await consumer.peek(
                            16, timeout=0.05):
                        _kind, frame = parse_batch(payload)
                        acks.extend(frame.rows)
                    consumer.commit()
                    await asyncio.sleep(0.01)

            drainer = asyncio.get_event_loop().create_task(drain())
            action, msgs = _msgs(4)
            # frame behind the balancer: zombie sender
            await producer.send(funnel_topic(0),
                                FunnelBatchMessage(msgs[:2], 7, 1, 2))
            # frame ahead of the balancer: this balancer is stale
            await producer.send(funnel_topic(0),
                                FunnelBatchMessage(msgs[2:3], 7, 2, 4))
            await until(lambda: len(acks) >= 3)
            # at the live epoch: admitted
            await producer.send(funnel_topic(0),
                                FunnelBatchMessage(msgs[3:], 7, 3, 3))
            await until(lambda: len(bal.placed) == 1)
            await asyncio.sleep(0.05)
            drainer.cancel()
            out = (list(bal.placed), list(acks), recv.stale_frames)
            await recv.stop()
            return out

        placed, acks, stale = asyncio.run(go())
        assert len(placed) == 1, "only the live-epoch frame placed"
        assert stale == 2
        refusals = [r for r in acks if r.code == "r"]
        assert len(refusals) == 3
        texts = {r.exc[1] for r in refusals}
        assert stale_epoch_text(2, 3) in texts
        assert stale_epoch_text(4, 3) in texts
        assert all(r.exc[0] == "L" for r in refusals)


class TestFunnelFrontEnd:
    def test_backpressure_429_exact_serial_text(self):
        """The funnel-depth bound IS the front door's 429: the exact
        serial CONCURRENT_LIMIT_MESSAGE, raised immediately — never
        unbounded queueing."""

        async def go():
            provider = MemoryMessagingProvider()
            fe = _frontend(provider, depth=2)
            await fe.start()
            action, msgs = _msgs(3)
            outs = fe.publish_many([(action, m) for m in msgs])
            # depth 2: the third row refuses locally, at once
            assert outs[2].done()
            with pytest.raises(LoadBalancerThrottleException) as ei:
                outs[2].result()
            text = str(ei.value)
            await fe.close()
            return text

        text = asyncio.run(go())
        assert text == CONCURRENT_LIMIT_MESSAGE

    def _run_hop(self, mode, blocking=True, n=1):
        """One front end + one receiver over a shared provider; returns
        (row outcomes or exceptions, stub balancer, front end)."""

        async def go():
            provider = MemoryMessagingProvider()
            bal = StubBalancer(mode)
            recv = _receiver(provider, bal)
            recv.start()
            fe = _frontend(provider)
            await fe.start()
            action, msgs = _msgs(n, blocking=blocking)
            outs = fe.publish_many([(action, m) for m in msgs])
            results = []
            for out, m in zip(outs, msgs):
                try:
                    promise = await asyncio.wait_for(out, 8)
                except Exception as e:  # noqa: BLE001 — the assertion
                    results.append(e)
                    continue
                if mode == "place":
                    aid = m.activation_id.asString
                    await until(lambda a=aid: a in bal.promises)
                    bal.promises[aid].set_result(_activation(
                        m.activation_id))
                try:
                    results.append(await asyncio.wait_for(promise, 8))
                except Exception as e:  # noqa: BLE001
                    results.append(e)
            stats = (fe.rows_sent, fe.total_active_activations,
                     recv.rows_received)
            await fe.close()
            await recv.stop()
            return results, stats

        return asyncio.run(go())

    def test_device_throttle_text_survives_hop(self):
        results, _ = self._run_hop("throttle")
        (exc,) = results
        assert isinstance(exc, LoadBalancerThrottleException)
        assert str(exc) == DEVICE_THROTTLE_TEXT

    def test_standby_refusal_text_survives_hop(self):
        results, _ = self._run_hop("standby")
        (exc,) = results
        assert isinstance(exc, LoadBalancerException)
        assert not isinstance(exc, LoadBalancerThrottleException)
        assert str(exc) == ("standby controller: placement is fenced to "
                            "the active leader")

    def test_blocking_completion_roundtrip(self):
        results, stats = self._run_hop("place", blocking=True, n=3)
        assert len(results) == 3
        for act in results:
            assert isinstance(act, WhiskActivation)
            assert act.response.result == {"ok": 1}
        rows_sent, in_flight, rows_received = stats
        assert rows_sent == 3 and rows_received == 3
        assert in_flight == 0, "completed rows left the depth books"

    def test_retry_reships_lost_frame_no_double_execution(self):
        """Drop the first delivery: the sender re-ships the same seq
        after retry_seconds; rows place exactly once."""

        async def go():
            provider = MemoryMessagingProvider()
            bal = StubBalancer()
            recv = _receiver(provider, bal)
            dropped = []
            orig_consume = recv._consume

            async def lossy(payload):
                if not dropped:
                    dropped.append(payload)
                    return  # lose the first frame entirely
                await orig_consume(payload)

            recv._consume = lossy
            recv.start()
            fe = _frontend(provider, depth=64, retry_seconds=0.15,
                           max_retries=3)
            await fe.start()
            action, msgs = _msgs(2, blocking=True)
            outs = fe.publish_many([(action, m) for m in msgs])
            promises = await asyncio.wait_for(
                asyncio.gather(*outs), 8)
            for m in msgs:
                bal.promises[m.activation_id.asString].set_result(
                    _activation(m.activation_id))
            acts = await asyncio.wait_for(asyncio.gather(*promises), 8)
            out = (list(bal.placed), fe.frame_retries, len(dropped),
                   [a.activation_id.asString for a in acts])
            await fe.close()
            await recv.stop()
            return out

        placed, retries, dropped, aids = asyncio.run(go())
        assert dropped == 1 and retries >= 1
        assert sorted(placed) == sorted(aids)
        assert len(placed) == len(set(placed)) == 2, \
            "zero double executions across the retry"

    def test_retry_exhaustion_fails_rows_503(self):
        async def go():
            provider = MemoryMessagingProvider()
            # no receiver at all: every send vanishes
            fe = _frontend(provider, depth=8, retry_seconds=0.05,
                           max_retries=1)
            await fe.start()
            action, msgs = _msgs(1)
            (out,) = fe.publish_many([(action, msgs[0])])
            with pytest.raises(LoadBalancerException) as ei:
                await asyncio.wait_for(out, 8)
            text = str(ei.value)
            stats = (fe.rows_timed_out, fe.total_active_activations)
            await fe.close()
            return text, stats

        text, (timed_out, in_flight) = asyncio.run(go())
        assert "no outcome from balancer" in text
        assert timed_out == 1 and in_flight == 0

    def test_forced_timeout_surfaces_as_active_ack_timeout(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = StubBalancer()
            recv = _receiver(provider, bal)
            recv.start()
            fe = _frontend(provider)
            await fe.start()
            action, msgs = _msgs(1, blocking=True)
            (out,) = fe.publish_many([(action, msgs[0])])
            promise = await asyncio.wait_for(out, 8)
            aid = msgs[0].activation_id.asString
            await until(lambda: aid in bal.promises)
            # the balancer's forced completion path sets ActiveAckTimeout
            bal.promises[aid].set_exception(
                ActiveAckTimeout(msgs[0].activation_id))
            with pytest.raises(ActiveAckTimeout):
                await asyncio.wait_for(promise, 8)
            await fe.close()
            await recv.stop()
            return True

        assert asyncio.run(go())


class TestFunnelOverTcpBus:
    def test_partial_dedupe_replay_over_tcp(self):
        """Satellite: the dedupe/retry discipline over the REAL TCP
        bus — a lossy receiver forces an application-level re-ship and
        every row still executes exactly once, with the serial throttle
        text intact for a refused row."""

        async def go():
            from openwhisk_tpu.messaging.tcp import (TcpBusServer,
                                                     TcpMessagingProvider)
            import socket
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            server = TcpBusServer("127.0.0.1", port)
            await server.start()
            try:
                recv_provider = TcpMessagingProvider("127.0.0.1", port)
                send_provider = TcpMessagingProvider("127.0.0.1", port)
                bal = StubBalancer()
                recv = _receiver(recv_provider, bal)
                dropped = []
                orig_consume = recv._consume

                async def lossy(payload):
                    if not dropped:
                        dropped.append(payload)
                        return
                    await orig_consume(payload)

                recv._consume = lossy
                recv.start()
                fe = _frontend(send_provider, depth=64,
                               retry_seconds=0.2, max_retries=4)
                await fe.start()
                action, msgs = _msgs(3, blocking=True)
                outs = fe.publish_many([(action, m) for m in msgs])
                promises = await asyncio.wait_for(
                    asyncio.gather(*outs), 15)
                for m in msgs:
                    aid = m.activation_id.asString
                    await until(lambda a=aid: a in bal.promises)
                    bal.promises[aid].set_result(
                        _activation(m.activation_id))
                acts = await asyncio.wait_for(
                    asyncio.gather(*promises), 15)
                placed = list(bal.placed)
                retries = fe.frame_retries
                await fe.close()
                await recv.stop()
                return placed, retries, len(acts)
            finally:
                await server.stop()

        placed, retries, n_acts = asyncio.run(go())
        assert retries >= 1, "the lost frame was re-shipped"
        assert len(placed) == len(set(placed)) == 3, \
            "zero double executions over the TCP hop"
        assert n_acts == 3


@pytest.mark.multiproc
class TestFunnelSharedDeployment:
    def test_loadgen_shared_topology_end_to_end(self):
        """Two loadgen worker PROCESSES funnel one shared balancer
        process over the TCP bus; the merged verdict is tagged
        topology='shared' and every worker completes work."""
        import sys
        sys.path.insert(0, "tools")
        try:
            import loadgen
            out = loadgen.multiproc_fixed_rate(
                rate=48, procs=2, duration=1.0, n_invokers=2,
                p99_bound_ms=60000.0)
        finally:
            sys.path.remove("tools")
        assert out["topology"] == "shared"
        assert out["mode"] == "open_loop_multiproc"
        assert out["completed"] > 0, "merged sample union is non-empty"
        assert out["fleet_merged_sustained_per_sec"] > 0
        assert len(out["per_worker"]) == 2
        for w in out["per_worker"]:
            assert "error" not in w, w
            assert (w.get("throughput_per_sec") or 0) > 0
