"""The benchmark's simulated invoker fleet.

One feed per invoker on the in-memory bus, as `bench._echo_invoker` has it,
but an activation's completion ack is sent after the ACTION's service time
from a timer (`loop.call_later`), so a slow activation never blocks its
invoker's feed, however many activations one container of the action holds
at once (an I/O-bound action, the case upstream's docs/concurrency.md is
for): the invoker has no container model. Every delivery and every ack is
recorded for the comparison that decides `correct`.

The fleet pings as a fleet does: every invoker once a second, at a phase of
its own, from its very first ping. `ping_schedule(n)` deals the invokers,
in their order, to ceil(n / 128) ticks spread evenly over the second. One
task keeps that schedule on its own clock, as real invokers keep theirs: at
each wake it sends, in one block (a `bench_ping` span, one `send_many`),
every invoker whose ping has fallen due since its last wake, so a loop that
turns late delays each ping by as long as it kept the pinger waiting (a few
turns at most) and thins none out. On a loop that turns on time a wake is
one tick, at most `PING_TICK` pings. After a hold longer than a second a
wake sends each invoker's newest due ping once, not one for every second it
missed, and the schedule goes on from the current second: a block is never
more than N pings. At 10,240 invokers that is half of the health topic's
retention (two seconds of the fleet's pings, `HEALTH_RETENTION_BYTES`), and
the health feed drains a backlog in one wake, so no ping is lost to
retention. Such a block begins at the tick then due and wraps round to tick
0, so the invokers of later ticks can ping before those of earlier ones, as
a real fleet's may: the rows below them are registered as upstream pads
them (`reference.ReferenceFleet.register`). `start()` only opens the
invokers' feeds and starts that task: registration is second 0 of the same
schedule, so an invoker that has pinged goes on pinging a second later
however long the program takes over the rows behind it. A ping's bytes are
the same every second (the invoker's instance; no admin address), so each
invoker's is serialized once, in `start()`.

The fleet keeps an account of what it delivered over the measured window
(`window`): the pings sent, and the longest silence of one invoker, on the
harness's clock.
"""
from __future__ import annotations

import asyncio
import bisect
import contextlib
import time
from typing import Dict, List, Tuple

import numpy as np

#: most invokers dealt to one tick: the health feed's `max_peek`
#: (supervision.py), so on a loop that turns on time one wake's block is
#: one batch of the program's feed
PING_TICK = 128


def ping_schedule(n: int) -> List[Tuple[float, int, int]]:
    """One second of a fleet of `n`: (phase, lo, hi) per tick, invokers
    [lo, hi) pinging `phase` seconds into every second. Each invoker is in
    exactly one tick, no tick holds more than `PING_TICK`."""
    ticks = -(-n // PING_TICK)
    return [(k / ticks, k * n // ticks, (k + 1) * n // ticks)
            for k in range(ticks)]


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
        #: pings sent since `start()`
        self.pings = 0
        #: when each invoker's last ping was sent, on the loop's clock
        self._last_ping = np.zeros(n_invokers)
        #: the measured window's account (`window`): pings sent in it, and
        #: the longest silence of one invoker that ended in it
        self.window_pings = None
        self.window_gap_max_s = None
        self._window_from = None
        self._gap_max = 0.0

    async def start(self) -> None:
        from openwhisk_tpu.core.entity import MB, InvokerInstanceId
        from openwhisk_tpu.messaging import PingMessage

        pinger = self.provider.get_producer()
        self.provider.ensure_topic("health")
        loop = asyncio.get_event_loop()
        n = self.n
        schedule = ping_schedule(n)
        ticks = len(schedule)
        phases = [p for p, _lo, _hi in schedule]
        instances = [InvokerInstanceId(i, user_memory=MB(self.memory_mb))
                     for i in range(n)]
        items = [("health", PingMessage(inst).serialize(), None)
                 for inst in instances]
        self._feeds = [self._start_invoker(inst) for inst in instances]
        t0 = loop.time()
        last = self._last_ping
        last[:] = t0

        def due_at(g: int) -> float:
            # tick g of the fleet's life: tick g % ticks of second g // ticks
            return t0 + g // ticks + phases[g % ticks]

        async def ping() -> None:
            # supervision marks an invoker Offline after 10 s of silence.
            # Tick by tick on the schedule's own clock (an invoker's first
            # ping registers it): a late wake does not move the next tick
            g = 0
            while True:
                await asyncio.sleep(max(0.0, due_at(g) - loop.time()))
                now = loop.time()
                sec = int(now - t0)
                due = sec * ticks + bisect.bisect_right(phases,
                                                        now - t0 - sec)
                if due <= g:
                    continue
                # every tick due since the last wake; past a second behind,
                # each invoker's newest due ping alone: at most one round
                first, g = max(g, due - ticks), due
                # `send_many` does not suspend on the in-memory bus: the
                # span holds no other task
                with self.span("bench_ping"):
                    block = []
                    for k in range(first, due):
                        _phase, lo, hi = schedule[k % ticks]
                        if self._window_from is not None:
                            self._gap_max = max(self._gap_max,
                                                now - last[lo:hi].min())
                        last[lo:hi] = now
                        block += items[lo:hi]
                    self.pings += len(block)
                    await pinger.send_many(block)

        self._ping_task = loop.create_task(ping())

    def window(self, opened: bool) -> None:
        """Open or close the account of the measured window: the pings
        sent in it, and the longest silence of one invoker that ended in
        it (a silence still open at the close counted up to the close)."""
        now = asyncio.get_event_loop().time()
        if opened:
            self._window_from = self.pings
            self._gap_max = 0.0
        elif self._window_from is not None:
            self.window_pings = self.pings - self._window_from
            self.window_gap_max_s = max(self._gap_max,
                                        now - float(self._last_ping.min()))
            self._window_from = None

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
        if self._ping_task is not None:
            self._ping_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._ping_task
        for t in list(self._timers):
            t.cancel()
        self._timers.clear()
        for f in self._feeds:
            await f.stop()
