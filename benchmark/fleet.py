"""The benchmark's simulated invoker fleet.

One feed per invoker on the in-memory bus, as `bench._echo_invoker` has it,
but an activation's completion ack is sent after the ACTION's service time
from a timer (`loop.call_later`), so a slow activation never blocks its
invoker's feed, however many activations one container of the action holds
at once (an I/O-bound action, the case upstream's docs/concurrency.md is
for): the invoker has no container model. Every delivery and every ack is
recorded for the comparison that decides `correct`.
"""
from __future__ import annotations

import asyncio
import contextlib
import time
from typing import Dict, List, Tuple


class SimFleet:
    def __init__(self, provider, n_invokers: int, memory_mb: int,
                 service_of: Dict[str, float], memory_of: Dict[str, int],
                 span=contextlib.nullcontext):
        self.provider = provider
        self.n = n_invokers
        self.memory_mb = memory_mb
        #: action name -> simulated service time in seconds
        self.service_of = service_of
        #: action name -> memory MB (what its release gives back)
        self.memory_of = memory_of
        #: activation id -> invoker indices it was delivered to
        self.deliveries: Dict[str, List[int]] = {}
        #: (invoker, action's fully qualified name, memory MB) of every ack
        self.completions: List[Tuple[int, str, int]] = []
        self.ack_errors = 0
        #: set-up's shape ladder: while `hold` is set, deliveries are parked
        #: (whatever the action's service time) and `release_held` acks them
        #: all in one sweep, so that one release fold sees them together
        self.hold = False
        self._held: list = []
        #: host-span factory (TraceAnnotation while a trace runs)
        self.span = span
        self._feeds: list = []
        self._timers: set = set()
        self._ping_task = None
        self._stop = asyncio.Event()

    async def start(self) -> None:
        from openwhisk_tpu.core.entity import MB, InvokerInstanceId
        from openwhisk_tpu.messaging import PingMessage

        pinger = self.provider.get_producer()
        self.provider.ensure_topic("health")
        instances = []
        for i in range(self.n):
            inst = InvokerInstanceId(i, user_memory=MB(self.memory_mb))
            instances.append(inst)
            self._feeds.append(self._start_invoker(inst))
            await pinger.send("health", PingMessage(inst))

        async def ping() -> None:
            # supervision marks an invoker Offline after 10 s of silence
            while not self._stop.is_set():
                for inst in instances:
                    await pinger.send("health", PingMessage(inst))
                try:
                    await asyncio.wait_for(self._stop.wait(), 1.0)
                except asyncio.TimeoutError:
                    pass

        self._ping_task = asyncio.get_event_loop().create_task(ping())

    def _start_invoker(self, instance):
        from openwhisk_tpu.core.entity import (ActivationResponse, EntityPath,
                                               WhiskActivation)
        from openwhisk_tpu.messaging import (
            ActivationMessage, CombinedCompletionAndResultMessage,
            MessageFeed, maybe_coalesce)
        from openwhisk_tpu.messaging.columnar import is_batch_payload
        from openwhisk_tpu.messaging.connector import (decode_batch,
                                                       decode_message)

        topic = instance.as_string
        idx = instance.instance
        self.provider.ensure_topic(topic)
        consumer = self.provider.get_consumer(topic, topic)
        producer = maybe_coalesce(self.provider.get_producer())
        loop = asyncio.get_event_loop()
        box = {}

        def sent(fut: asyncio.Future) -> None:
            if not fut.cancelled() and fut.exception() is not None:
                self.ack_errors += 1

        def ack(msg, mem: int) -> None:
            with self.span("bench_ack"):
                _ack(msg, mem)

        def _ack(msg, mem: int) -> None:
            now = time.time()
            act = WhiskActivation(
                EntityPath(str(msg.user.namespace.name)), msg.action.name,
                msg.user.subject, msg.activation_id, now, now,
                ActivationResponse.success({"ok": True}), duration=1)
            self.completions.append((idx, str(msg.action), mem))
            producer.send_nowait(
                f"completed{msg.root_controller_index.as_string}",
                CombinedCompletionAndResultMessage(msg.transid, act, instance)
            ).add_done_callback(sent)

        def ack_later(handle_box: list, msg, mem: int) -> None:
            self._timers.discard(handle_box[0])
            ack(msg, mem)

        async def handle(payload: bytes) -> None:
            with self.span("bench_invoker"):
                _handle(payload)
            box["feed"].processed()

        def _handle(payload: bytes) -> None:
            if is_batch_payload(payload):
                _kind, msgs = decode_batch(payload)
            else:
                msgs = [decode_message(ActivationMessage.parse, payload,
                                       "activation")]
            for msg in msgs:
                aid = msg.activation_id.asString
                self.deliveries.setdefault(aid, []).append(idx)
                name = str(msg.action.name)
                mem = self.memory_of[name]
                service = self.service_of[name]
                if self.hold:
                    self._held.append((ack, msg, mem))
                elif service <= 0.0:
                    ack(msg, mem)
                else:
                    hb: list = [None]
                    hb[0] = loop.call_later(service, ack_later, hb, msg, mem)
                    self._timers.add(hb[0])

        feed = MessageFeed(topic, consumer, 256, handle)
        box["feed"] = feed
        feed.start()
        return feed

    @property
    def held(self) -> int:
        return len(self._held)

    def release_held(self) -> None:
        held, self._held = self._held, []
        for ack, msg, mem in held:
            ack(msg, mem)

    async def stop(self) -> None:
        self._stop.set()
        if self._ping_task is not None:
            await self._ping_task
        for t in list(self._timers):
            t.cancel()
        self._timers.clear()
        for f in self._feeds:
            await f.stop()
