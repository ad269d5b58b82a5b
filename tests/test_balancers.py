"""Balancer integration tests: TpuBalancer + ShardingBalancer against
simulated invokers on the in-memory bus (the reference pattern of
ShardingContainerPoolBalancerTests + InvokerSupervisionTests: fake bus,
synthetic pings, direct cluster-size updates)."""
import asyncio
import time

import pytest

from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                       CodeExec, ControllerInstanceId,
                                       EntityName, EntityPath,
                                       ExecutableWhiskAction, Identity,
                                       InvokerInstanceId, MB, ActionLimits,
                                       MemoryLimit, TimeLimit, WhiskActivation)
from openwhisk_tpu.core.entity.ids import DocRevision, Subject
from openwhisk_tpu.controller.loadbalancer import (ActiveAckTimeout, HEALTHY,
                                                   LoadBalancerException,
                                                   OFFLINE, ShardingBalancer,
                                                   TpuBalancer, UNHEALTHY)
from openwhisk_tpu.controller.loadbalancer.supervision import InvokerPool
from openwhisk_tpu.messaging import (ActivationMessage,
                                     CombinedCompletionAndResultMessage,
                                     MemoryMessagingProvider, MessageFeed,
                                     PingMessage)
from openwhisk_tpu.utils.transaction import TransactionId


def make_action(name="act", memory=256, kind="python:3"):
    a = ExecutableWhiskAction(EntityPath("guest"), EntityName(name),
                              CodeExec(kind=kind, code="x"),
                              limits=ActionLimits(TimeLimit(5000),
                                                  MemoryLimit(MB(memory))))
    a.rev = DocRevision("1-b")
    return a


def make_msg(action, ident, blocking=False):
    return ActivationMessage(
        TransactionId(), action.fully_qualified_name, action.rev.rev, ident,
        ActivationId.generate(), ControllerInstanceId("0"), blocking, {})


class SimInvoker:
    """A fake invoker: consumes its topic, acks immediately."""

    def __init__(self, provider, instance: InvokerInstanceId, delay=0.0):
        self.provider = provider
        self.instance = instance
        self.delay = delay
        self.handled = []
        self._feed = None

    async def start(self):
        topic = self.instance.as_string
        self.provider.ensure_topic(topic)
        consumer = self.provider.get_consumer(topic, topic)
        producer = self.provider.get_producer()
        box = {}

        async def handle(payload: bytes):
            # the batch wire ships one frame per topic and flush, from
            # one message up (messaging/columnar.py); the serial wire
            # (batchWire off, an uncoalesced producer) one JSON a message
            from openwhisk_tpu.messaging.columnar import (is_batch_payload,
                                                          parse_batch)
            if is_batch_payload(payload):
                _kind, msgs = parse_batch(payload)
            else:
                msgs = [ActivationMessage.parse(payload)]
            self.handled.extend(msgs)

            async def finish(msg):
                if self.delay:
                    await asyncio.sleep(self.delay)
                now = time.time()
                act = WhiskActivation(
                    EntityPath(str(msg.user.namespace.name)), msg.action.name,
                    msg.user.subject, msg.activation_id, now, now,
                    ActivationResponse.success({"ok": True}), duration=1)
                await producer.send(
                    f"completed{msg.root_controller_index.as_string}",
                    CombinedCompletionAndResultMessage(msg.transid, act,
                                                       self.instance))
                box["feed"].processed()
            for msg in msgs:
                asyncio.get_event_loop().create_task(finish(msg))

        self._feed = MessageFeed(topic, consumer, 64, handle)
        box["feed"] = self._feed
        self._feed.start()

    async def ping(self, producer):
        await producer.send("health", PingMessage(self.instance))

    async def stop(self):
        if self._feed:
            await self._feed.stop()


async def _fleet(provider, n, memory_mb=2048, delay=0.0):
    invokers = []
    producer = provider.get_producer()
    for i in range(n):
        inv = SimInvoker(provider, InvokerInstanceId(i, user_memory=MB(memory_mb)),
                         delay=delay)
        await inv.start()
        invokers.append(inv)
    return invokers, producer


async def _ping_all(invokers, producer, times=1):
    for _ in range(times):
        for inv in invokers:
            await inv.ping(producer)
    await asyncio.sleep(0.1)


@pytest.fixture(params=["tpu", "cpu"])
def balancer_cls(request):
    return TpuBalancer if request.param == "tpu" else ShardingBalancer


class TestBalancers:
    def test_publish_roundtrip_and_release(self, balancer_cls):
        async def go():
            provider = MemoryMessagingProvider()
            bal = balancer_cls(provider, ControllerInstanceId("0"),
                               managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 4)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action()
            promises = []
            for _ in range(8):
                msg = make_msg(action, ident, blocking=True)
                promises.append(await bal.publish(action, msg))
            results = await asyncio.gather(*[asyncio.wait_for(p, 5)
                                             for p in promises])
            # wait for slot releases to drain
            await asyncio.sleep(0.2)
            total = bal.total_active_activations
            slots = len(bal.activation_slots)
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return results, total, slots, [len(i.handled) for i in invokers]

        results, total, slots, handled = asyncio.run(go())
        assert len(results) == 8
        assert all(r.response.is_success for r in results)
        assert total == 0 and slots == 0
        assert sum(handled) == 8

    def test_affinity_same_action_same_invoker(self, balancer_cls):
        async def go():
            provider = MemoryMessagingProvider()
            bal = balancer_cls(provider, ControllerInstanceId("0"),
                               managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 8)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("affine", memory=128)
            for _ in range(4):
                p = await bal.publish(action, make_msg(action, ident, True))
                await asyncio.wait_for(p, 5)
                await asyncio.sleep(0.05)  # release between invokes
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return [len(i.handled) for i in invokers]

        handled = asyncio.run(go())
        # all 4 sequential invokes land on the home invoker (warm affinity)
        assert sorted(handled) == [0, 0, 0, 0, 0, 0, 0, 4]

    def test_no_invokers_raises(self, balancer_cls):
        async def go():
            provider = MemoryMessagingProvider()
            bal = balancer_cls(provider, ControllerInstanceId("0"))
            await bal.start()
            ident = Identity.generate("guest")
            action = make_action()
            try:
                with pytest.raises(LoadBalancerException):
                    await bal.publish(action, make_msg(action, ident))
            finally:
                await bal.close()

        asyncio.run(go())

    def test_unhealthy_invoker_not_scheduled(self, balancer_cls):
        async def go():
            provider = MemoryMessagingProvider()
            bal = balancer_cls(provider, ControllerInstanceId("0"),
                               managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 4)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("affine2", memory=128)
            p = await bal.publish(action, make_msg(action, ident, True))
            await asyncio.wait_for(p, 5)
            home = max(range(4), key=lambda i: len(invokers[i].handled))
            # flap the home invoker to unhealthy via system-error outcomes
            for _ in range(5):
                bal.supervision.on_invocation_finished(
                    invokers[home].instance, is_system_error=True, forced=False)
            await asyncio.sleep(0.05)
            p = await bal.publish(action, make_msg(action, ident, True))
            await asyncio.wait_for(p, 5)
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return home, [len(i.handled) for i in invokers]

        home, handled = asyncio.run(go())
        assert handled[home] == 1  # second invoke avoided the unhealthy home
        assert sum(handled) == 2

    def test_offline_after_ping_silence(self):
        async def go():
            provider = MemoryMessagingProvider()
            statuses = {}
            pool = InvokerPool(provider,
                               on_status_changes=lambda w: statuses.update(
                                   {i.instance: s for i, s in w}),
                               ping_timeout=0.3)
            pool.start()
            producer = provider.get_producer()
            inv = InvokerInstanceId(0, user_memory=MB(2048))
            await producer.send("health", PingMessage(inv))
            await asyncio.sleep(0.15)
            up = statuses.get(0)
            await asyncio.sleep(1.3)
            down = statuses.get(0)
            await pool.stop()
            return up, down

        up, down = asyncio.run(go())
        assert up == HEALTHY
        assert down == OFFLINE

    def test_forced_timeout_self_heals_slots(self, balancer_cls):
        async def go():
            provider = MemoryMessagingProvider()
            bal = balancer_cls(provider, ControllerInstanceId("0"),
                               managed_fraction=1.0, blackbox_fraction=0.0)
            bal.TIMEOUT_FACTOR = 0
            bal.TIMEOUT_ADDON = 0.2  # completion-ack timeout ~0.2s
            bal.STD_TIMEOUT = 0.0
            await bal.start()
            # an invoker that never acks
            dead_id = InvokerInstanceId(0, user_memory=MB(2048))
            provider.ensure_topic("invoker0")
            producer = provider.get_producer()
            await producer.send("health", PingMessage(dead_id))
            await asyncio.sleep(0.1)
            ident = Identity.generate("guest")
            action = make_action()
            msg = make_msg(action, ident, blocking=True)
            promise = await bal.publish(action, msg)
            assert bal.total_active_activations == 1
            with pytest.raises(ActiveAckTimeout):
                await asyncio.wait_for(promise, 5)
            healed = bal.total_active_activations
            await bal.close()
            return healed

        assert asyncio.run(go()) == 0


class TestTpuBalancerSpecifics:
    def test_batched_concurrent_publishes(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=0.005, max_batch=64)
            await bal.start()
            invokers, producer = await _fleet(provider, 8, memory_mb=4096)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            actions = [make_action(f"a{i}", memory=128) for i in range(16)]
            # 64 concurrent publishes -> batched into few device steps
            promises = await asyncio.gather(*[
                bal.publish(actions[i % 16], make_msg(actions[i % 16], ident, True))
                for i in range(64)])
            results = await asyncio.gather(*[asyncio.wait_for(p, 10)
                                             for p in promises])
            batches = bal.metrics.histogram_stats("loadbalancer_tpu_schedule_batch_ms")
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return results, batches

        results, batches = asyncio.run(go())
        assert len(results) == 64
        assert all(r.response.is_success for r in results)
        assert batches["count"] < 64  # actually micro-batched

    def test_cluster_resharding(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=2048)
            await _ping_all(invokers, producer)
            import numpy as np
            full = np.asarray(bal.state.free_mb)[:2].tolist()
            bal.update_cluster(2)
            half = np.asarray(bal.state.free_mb)[:2].tolist()
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return full, half

        full, half = asyncio.run(go())
        assert full == [2048, 2048]
        assert half == [1024, 1024]


class TestReviewRegressions:
    def test_burst_beyond_max_batch_all_complete(self):
        """Leftover pending requests past max_batch must flush without
        further traffic (review: _flush_later tail re-arm was a no-op)."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=0.005, max_batch=16)
            await bal.start()
            invokers, producer = await _fleet(provider, 4, memory_mb=8192)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            actions = [make_action(f"b{i}", memory=128) for i in range(8)]
            promises = await asyncio.gather(*[
                bal.publish(actions[i % 8], make_msg(actions[i % 8], ident, True))
                for i in range(40)])  # 40 > max_batch=16
            results = await asyncio.gather(*[asyncio.wait_for(p, 10)
                                             for p in promises])
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return results

        results = asyncio.run(go())
        assert len(results) == 40
        assert all(r.response.is_success for r in results)

    def test_fleet_growth_preserves_inflight_books(self):
        """A new invoker registering mid-flight must not reset existing
        capacity holds (review: _init_device_state wiped the books)."""
        async def go():
            import numpy as np
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              initial_pad=2)
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=1024,
                                              delay=0.5)  # slow acks
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("grow", memory=256)
            # take capacity and keep it in flight
            p = await bal.publish(action, make_msg(action, ident, True))
            held = np.asarray(bal.state.free_mb)[:2].sum()
            # invoker 2 registers (also forces a re-pad beyond initial_pad=2)
            inv3 = SimInvoker(provider, InvokerInstanceId(2, user_memory=MB(1024)))
            await inv3.start()
            await inv3.ping(producer)
            await asyncio.sleep(0.15)
            after_grow = np.asarray(bal.state.free_mb)[:2].sum()
            new_row = int(np.asarray(bal.state.free_mb)[2])
            await asyncio.wait_for(p, 5)
            await asyncio.sleep(0.3)  # release folds in
            healed = np.asarray(bal.state.free_mb)[:3].sum()
            await bal.close()
            for inv in invokers + [inv3]:
                await inv.stop()
            return held, after_grow, new_row, healed

        held, after_grow, new_row, healed = asyncio.run(go())
        assert held == 2 * 1024 - 256        # hold visible
        assert after_grow == held            # growth preserved the hold
        assert new_row == 1024               # new invoker at full capacity
        assert healed == 3 * 1024            # release healed the books

    def test_close_fails_pending_publishers(self):
        """close() during a buffered publish must fail the future, not hang."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=5.0, pipeline_depth=1)
            await bal.start()
            invokers, producer = await _fleet(provider, 1)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action()
            # saturate the pipeline so the publish stays buffered (an idle
            # balancer flushes immediately; a busy one batches)
            bal._inflight_steps = bal.pipeline_depth
            task = asyncio.get_event_loop().create_task(
                bal.publish(action, make_msg(action, ident, True)))
            await asyncio.sleep(0.05)
            await bal.close()
            try:
                with pytest.raises(LoadBalancerException):
                    await asyncio.wait_for(task, 2)
            finally:
                for inv in invokers:
                    await inv.stop()

        asyncio.run(go())

    def test_out_of_order_first_ping_cpu_balancer(self):
        """Invoker 3 pinging first must not mark 0..2 usable (review:
        registry backfill misdispatch)."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = ShardingBalancer(provider, ControllerInstanceId("0"),
                                   managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            producer = provider.get_producer()
            inv3 = SimInvoker(provider, InvokerInstanceId(3, user_memory=MB(2048)))
            await inv3.start()
            await inv3.ping(producer)
            await asyncio.sleep(0.1)
            ident = Identity.generate("guest")
            # many publishes: every one must land on invoker 3
            for i in range(6):
                action = make_action(f"ooo{i}", memory=128)
                p = await bal.publish(action, make_msg(action, ident, True))
                await asyncio.wait_for(p, 5)
            handled = len(inv3.handled)
            await bal.close()
            await inv3.stop()
            return handled

        assert asyncio.run(go()) == 6


class TestPallasKernelOption:
    def test_pallas_kernel_end_to_end(self):
        """TpuBalancer(kernel='pallas') serves real publishes with the
        pallas schedule kernel (interpret mode on the CPU backend)."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=0.005, max_batch=32,
                              action_slots=256, kernel="pallas")
            assert bal.kernel == "pallas"
            await bal.start()
            invokers, producer = await _fleet(provider, 4, memory_mb=2048)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            actions = [make_action(f"pl{i}", memory=256) for i in range(8)]
            promises = await asyncio.gather(*[
                bal.publish(actions[i % 8], make_msg(actions[i % 8], ident, True))
                for i in range(24)])
            results = await asyncio.gather(*[asyncio.wait_for(p, 10)
                                             for p in promises])
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return results

        results = asyncio.run(go())
        assert len(results) == 24
        assert all(r.response.is_success for r in results)

    def test_pallas_falls_back_when_state_too_large(self):
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          action_slots=4096, initial_pad=1024,
                          kernel="pallas")
        assert bal.kernel == "xla"  # 1024x4096 state exceeds the VMEM budget


class TestHealthTestActions:
    def test_unhealthy_invoker_gets_test_activation(self):
        """ref InvokerSupervision: >3 system errors flip an invoker
        Unhealthy; the controller then probes it with the system test
        action (invokerHealthTestAction<controller>) instead of real
        traffic, and its acks feed recovery."""
        async def go():
            from openwhisk_tpu.database import EntityStore, MemoryArtifactStore
            from openwhisk_tpu.messaging.message import ActivationMessage

            provider = MemoryMessagingProvider()
            store = EntityStore(MemoryArtifactStore())
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            await bal.prepare_health_test_action(store)
            # the system action exists in the store
            doc = await store.get_action("whisk.system/invokerHealthTestAction0")
            assert doc is not None

            inv = InvokerInstanceId(0, user_memory=MB(2048))
            producer = provider.get_producer()
            provider.ensure_topic("invoker0")
            probe = provider.get_consumer("invoker0", "probe")
            await producer.send("health", PingMessage(inv))
            await asyncio.sleep(0.15)
            # 4 system errors -> Unhealthy
            for _ in range(4):
                bal.supervision.on_invocation_finished(inv, True, False)
            assert bal.supervision.health()[0].status == "unhealthy"
            # next ping triggers the test-action probe (cooldown starts at 0)
            await producer.send("health", PingMessage(inv))
            await asyncio.sleep(0.2)
            msgs = await probe.peek(10, timeout=1.0)
            await bal.close()
            assert msgs, "no test activation published to the invoker topic"
            # a lone activation is a 1-row frame (messaging/columnar.py)
            from openwhisk_tpu.messaging.columnar import parse_batch
            _kind, (parsed,) = parse_batch(msgs[0][3])
            return str(parsed.action), parsed.blocking

        action, blocking = asyncio.run(go())
        assert action == "whisk.system/invokerHealthTestAction0"
        assert blocking is False

    def test_healthcheck_ack_counts_as_healthcheck(self):
        """Probe acks must hit the healthcheck counter, not pollute the
        late-ack (regularAfterForced) metric operators watch."""
        async def go():
            from openwhisk_tpu.core.entity import ActivationId
            from openwhisk_tpu.database import EntityStore, MemoryArtifactStore

            provider = MemoryMessagingProvider()
            store = EntityStore(MemoryArtifactStore())
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            await bal.prepare_health_test_action(store)
            inv = InvokerInstanceId(0, user_memory=MB(2048))
            await bal._send_health_test_action(inv)
            aid = next(iter(bal._health_probe_ids))
            bal.process_completion(ActivationId(aid), forced=False,
                                   is_system_error=False, invoker=inv)
            hc = bal.metrics.counter_value("loadbalancer_completion_ack_healthcheck")
            late = bal.metrics.counter_value("loadbalancer_completion_ack_regularAfterForced")
            await bal.close()
            return hc, late, aid in bal._health_probe_ids

        hc, late, still_tracked = asyncio.run(go())
        assert hc == 1 and late == 0
        assert not still_tracked

    def test_restore_past_vmem_budget_falls_back_to_xla(self):
        """A snapshot whose n_pad exceeds the pallas VMEM budget must swap
        in the XLA kernel on restore, exactly as _grow_padding does — and
        the swap must honor the placement-kernel knob (auto resolves the
        repair pair on the XLA path, scan keeps the legacy pair)."""
        from openwhisk_tpu.ops.placement import (release_batch,
                                                 release_batch_vector,
                                                 schedule_batch,
                                                 schedule_batch_repair)

        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          action_slots=4096, initial_pad=1024)
        snap = bal.snapshot()

        small = TpuBalancer(MemoryMessagingProvider(), ControllerInstanceId("0"),
                            action_slots=4096, initial_pad=1, kernel="pallas")
        assert small.kernel == "pallas"
        small.restore(snap)
        assert small.kernel_resolved == "xla"
        assert small.placement_kernel_resolved == "repair"
        # auto = the per-bucket hybrid (scan below REPAIR_MIN_BATCH)
        assert getattr(small._sched_fn, "_placement_hybrid", False)
        assert getattr(small._release_fn, "_placement_hybrid", False)

        pinned = TpuBalancer(MemoryMessagingProvider(),
                             ControllerInstanceId("0"),
                             action_slots=4096, initial_pad=1,
                             kernel="pallas", placement_kernel="repair")
        pinned.restore(snap)
        assert pinned._sched_fn is schedule_batch_repair
        assert pinned._release_fn is release_batch_vector

        legacy = TpuBalancer(MemoryMessagingProvider(),
                             ControllerInstanceId("0"),
                             action_slots=4096, initial_pad=1,
                             kernel="pallas", placement_kernel="scan")
        legacy.restore(snap)
        assert legacy.placement_kernel_resolved == "scan"
        assert legacy._sched_fn is schedule_batch
        assert legacy._release_fn is release_batch


class TestPipelinedSteps:
    """Device-step pipelining (dispatch N+1 while N's readback is in
    flight): correctness across many overlapping micro-batches, and clean
    shutdown with work queued or in flight."""

    def test_many_overlapping_batches_all_place(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=0.0005, max_batch=8,
                              pipeline_depth=3)
            await bal.start()
            invokers, producer = await _fleet(provider, 4)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action()
            promises = [await bal.publish(action,
                                          make_msg(action, ident, blocking=True))
                        for _ in range(48)]
            results = await asyncio.gather(*[asyncio.wait_for(p, 10)
                                             for p in promises])
            await asyncio.sleep(0.3)
            leaked = bal.total_active_activations
            slots = len(bal.activation_slots)
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return results, leaked, slots

        results, leaked, slots = asyncio.run(go())
        assert len(results) == 48
        assert all(r.response.is_success for r in results)
        assert leaked == 0 and slots == 0

    def test_close_fails_queued_publishers_without_hanging(self):
        async def go():
            provider = MemoryMessagingProvider()
            # a saturated pipeline + far-away window keeps publishes queued
            # (an idle balancer flushes immediately; a busy one batches)
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0,
                              batch_window=30.0, pipeline_depth=1)
            await bal.start()
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action()
            bal._inflight_steps = bal.pipeline_depth
            tasks = [asyncio.create_task(
                bal.publish(action, make_msg(action, ident, blocking=True)))
                for _ in range(4)]
            await asyncio.sleep(0.05)  # queued; window has not fired
            await asyncio.wait_for(bal.close(), 5)  # must not hang
            outcomes = await asyncio.gather(*tasks, return_exceptions=True)
            for inv in invokers:
                await inv.stop()
            return outcomes

        outcomes = asyncio.run(go())
        assert len(outcomes) == 4
        assert all(isinstance(o, LoadBalancerException) for o in outcomes)
