"""Coalescing producer: micro-batched bus produce behind the provider SPI.

The publish->dispatch->invoke->complete path used to pay one bus round trip
per activation: the balancer's readback fan-out wakes N publishers in one
event-loop sweep and each `await producer.send(...)` serialized on the
transport (one lock-guarded TCP frame + ack per message on the TCP bus; one
condition acquire + notify per message on the memory bus). Under open-loop
load those per-request costs compound into the tail (PAPERS.md: Dean &
Barroso — the cure is doing less serial work per request, amortized over
batches).

`CoalescingProducer` wraps any `MessageProducer` and turns concurrent sends
into micro-batches: a send enqueues (payload pre-serialized on the caller's
turn) and resolves when its batch's single `send_many` acknowledges. The
flush fires when the batch fills (`max_batch`) or when the oldest pending
message has waited `window_ms` (a Nagle-style bounded delay; `window_ms=0`
flushes at the end of the current event-loop sweep, which still coalesces a
whole readback wave). Flushes are serialized on one drainer task, so
per-producer ordering is exactly the serial producer's.

With the batch wire on (`CONFIG_whisk_bus_coalesce_batchWire`, the
default) an activation or an ack is not encoded on its sender's turn: it
rides to the flush as an object, and the flush packs the messages of one
(topic, family), from ONE message up, into one struct-packed frame
(messaging/columnar.py) whose repeated sub-objects are interned on both
sides. A lone message is a 1-row frame: these two families have no
per-message JSON form here. `_STATS` counts the frames and their rows by
family, `ow_produce` carries `interned`, the blobs reused without an
encode. Pings, events and pre-encoded bytes pass through as they are.

Backends with a native batch op ship one frame per micro-batch
(`TcpProducer.send_many` -> the broker's `pubN` op: one length-prefixed
frame, N payloads, one ack, broker-side dedupe per sub-message); backends
without one fall back to the base `send_many` (sequential sends — serial
semantics, no wire-protocol change).

Off switches: `CONFIG_whisk_bus_coalesce_enabled=false` makes
`maybe_coalesce()` return the raw producer — the serial path, bit-exact
with today's behavior; `CONFIG_whisk_bus_coalesce_batchWire=false` keeps
the coalescing and ships one plain JSON payload per message, byte-exact
with the serial wire.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..utils.config import load_config
from ..utils.microbatch import MicroCoalescer
from ..utils.waterfall import span
from .columnar import (KIND_ACK, KIND_ACTIVATION, WIRE_STATS, batch_hop_of,
                       batchable_family, intern_hits)
from .connector import MessageProducer, encode_batch, encode_message
from .memory import BUS_STATS

#: process-wide coalescing health counters, exported as gauges by the
#: balancers' supervision tick (export_coalesce_gauges) — one aggregate
#: across producers, like the tracing gauges. `wire_frames` / `wire_rows`
#: count, by family, the frames this process encoded and the messages
#: inside them (rows / frames = how many messages share a frame's tables);
#: `parked_flushes` the flushes whose wave found its drainer parked, not
#: running (over `batches`: how sparse this process's producers are)
_STATS = {"batches": 0, "messages": 0, "max_batch": 0, "parked_flushes": 0,
          "wire_frames": {KIND_ACTIVATION: 0, KIND_ACK: 0},
          "wire_rows": {KIND_ACTIVATION: 0, KIND_ACK: 0}}


@dataclass(frozen=True)
class BusCoalesceConfig:
    """`CONFIG_whisk_bus_coalesce_*` env overrides."""
    enabled: bool = True
    #: flush as soon as this many messages are pending
    max_batch: int = 64
    #: bounded accumulation delay: the oldest pending message waits at most
    #: this long before its frame ships. Default 0 = flush at the end of
    #: the current event-loop sweep, which already coalesces a whole
    #: readback/ack wave at ZERO added idle latency (measured: the produce
    #: stage p99 stays ~1 ms at the sustained rate). Set ~1 ms on expensive
    #: transports (remote TCP, Kafka) to also batch across waves.
    window_ms: float = 0.0
    #: the batch wire (messaging/columnar.py): the activation/ack
    #: messages of one topic and flush, from ONE message up, ship as one
    #: struct-packed frame with the repeated identity / action /
    #: controller / invoker sub-objects interned on both sides (and the
    #: encode moves to flush time, so a message is encoded exactly once).
    #: An ack's response record rides as opaque bytes, so the
    #: controller's completion loop never parses a result nobody reads.
    #: False restores the serial wire format byte-exactly.
    batch_wire: bool = True

    @classmethod
    def from_env(cls) -> "BusCoalesceConfig":
        return load_config(cls, env_path="bus.coalesce")


class CoalescingProducer(MessageProducer):
    """Micro-batching wrapper over any MessageProducer (see module doc).
    The coalescing loop itself is the shared MicroCoalescer
    (utils/microbatch.py) — the admission plane rides the same one."""

    def __init__(self, inner: MessageProducer, max_batch: int = 64,
                 window_ms: float = 0.0, batch_wire: bool = False):
        self.inner = inner
        self.batch_wire = batch_wire
        self._co = MicroCoalescer(self._ship, max_batch,
                                  max(0.0, float(window_ms)) / 1e3,
                                  name="bus-coalesce-drain")

    @property
    def sent_count(self) -> int:
        return self.inner.sent_count

    @property
    def pending_count(self) -> int:
        return self._co.pending_count

    async def send(self, topic: str, msg) -> None:
        # Batch wire fast path: a batchable message (activation / ack) is
        # NOT encoded here — it rides to the flush as an object and is
        # encoded exactly once, as a row of its group's frame.
        # Everything else serializes on the caller's turn as before (the
        # flush loop then ships bytes without touching message objects,
        # and a slow .serialize() is charged to the sender, not to every
        # batch-mate). encode_message / encode_batch both feed the host
        # observatory's per-hop serde accounting.
        if self.batch_wire and not isinstance(msg, (bytes, bytearray)):
            family = batchable_family(msg)
            if family is not None:
                await self._co.submit((topic, family, msg))
                return
        payload = encode_message(msg)
        await self._co.submit((topic, payload, msg))

    def send_nowait(self, topic: str, msg) -> "asyncio.Future":
        """Public task-free submit (the batched publish SPI resolves its
        callers from this future's done-callback): enqueue now, flush
        with the coalescer's next drain."""
        return self._submit_nowait(topic, msg)

    def _submit_nowait(self, topic: str, msg) -> "asyncio.Future":
        """send() without the await: enqueue, return the flush future."""
        if self.batch_wire and not isinstance(msg, (bytes, bytearray)):
            family = batchable_family(msg)
            if family is not None:
                return self._co.submit_nowait((topic, family, msg))
        return self._co.submit_nowait((topic, encode_message(msg), msg))

    async def send_batch(self, topic: str, msgs: list) -> None:
        """Submit a whole wave in one sweep and await the flush ONCE: the
        per-item futures resolve together (same per-item error
        propagation as N send() calls) with no task per message —
        `asyncio.gather` over coroutines would mint one Task each, which
        at thousands of acks/s was measurable loop churn. Failures
        gather with return_exceptions so sibling futures are all
        retrieved (no unretrieved-exception log spam), then the first
        real failure raises."""
        import asyncio
        futs = [self._submit_nowait(topic, m) for m in msgs]
        results = await asyncio.gather(*futs, return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r

    async def _ship(self, batch) -> None:
        """One coalesced flush: the whole batch rides the provider's
        send_many (one pubN frame on the TCP bus). With the batch wire
        on, the batchable messages of one (topic, family) become ONE
        frame — encoded here, exactly once per message — so the pubN
        frame carries one payload per topic instead of one per message.
        The coalescer resolves the waiter futures on return / failure."""
        _STATS["batches"] += 1
        _STATS["messages"] += len(batch)
        _STATS["max_batch"] = max(_STATS["max_batch"], len(batch))
        parked = int(self._co.parked_flush)
        _STATS["parked_flushes"] += parked
        if not self.batch_wire:
            await self.inner.send_many([item for (item, _fut) in batch])
            return
        # the span covers the encode, not the awaited send
        with span("ow_produce", n=len(batch), parked=parked) as sp:
            reused = WIRE_STATS["blob_hits"]
            out = self._encode_flush(batch)
            sp.set_metadata(bytes=sum(len(p) for _t, p, _m in out),
                            interned=WIRE_STATS["blob_hits"] - reused)
        await self.inner.send_many(out)

    def _encode_flush(self, batch) -> list:
        """The synchronous half of `_ship` with the batch wire on: one
        `(topic, payload, msg)` per (topic, family) group or pre-encoded
        item."""
        # group deferred-encode messages per (topic, family), preserving
        # per-topic arrival order WITHIN a family (the serial ordering
        # contract is per-topic; cross-topic order was never guaranteed —
        # send_many already interleaves topics). Pre-encoded items pass
        # through at their arrival position. Caveat, by design: a topic
        # carrying BOTH batchable and unbatchable payloads in one flush
        # may reorder across the kinds (the group anchors at its first
        # message) — no shipped topic mixes kinds (invoker topics carry
        # activations, completed* topics carry acks, health/events stay
        # per-frame), and consumers of each kind are order-independent
        # across the other.
        items: list = []
        groups: dict = {}
        for (topic, payload_or_family, msg), fut in batch:
            if isinstance(payload_or_family, str):
                key = (topic, payload_or_family)
                grp = groups.get(key)
                if grp is None:
                    grp = groups[key] = []
                    # placeholder keeps this group's position in the
                    # flush order (first appearance of the topic)
                    items.append(key)
                grp.append((msg, fut))
            else:
                items.append((topic, payload_or_family, msg))
        out: list = []
        for it in items:
            if isinstance(it, tuple) and len(it) == 2:
                topic, family = it
                group = groups[it]
                try:
                    out.append(self._frame(topic, family,
                                           [m for (m, _f) in group]))
                    continue
                except Exception as e:  # noqa: BLE001 — deferring the encode
                    # to flush time must NOT widen one bad message's
                    # blast radius to the whole flush (the serial path
                    # charged a serialize failure to its sender): retry
                    # each message in a frame of its own, so only the
                    # unserializable ones fail and the rest still ship
                    if len(group) == 1:
                        self._fail(group[0][1], e)
                        continue
                for m, fut in group:
                    try:
                        out.append(self._frame(topic, family, [m]))
                    except Exception as e:  # noqa: BLE001
                        self._fail(fut, e)
            else:
                out.append(it)
        return out

    @staticmethod
    def _fail(fut, exc) -> None:
        if not fut.done():
            fut.set_exception(exc)

    @staticmethod
    def _frame(topic: str, family: str, msgs: list) -> tuple:
        payload, frame = encode_batch(family, msgs)
        _STATS["wire_frames"][family] += 1
        _STATS["wire_rows"][family] += len(msgs)
        return topic, payload, frame

    async def flush(self) -> None:
        """Wait until everything enqueued so far has shipped (or failed)."""
        await self._co.drain_all()

    async def close(self) -> None:
        await self.flush()
        self._co.close()
        await self.inner.close()


def maybe_coalesce(producer: MessageProducer,
                   config: Optional[BusCoalesceConfig] = None
                   ) -> MessageProducer:
    """The wiring hook for producer owners (balancer, invoker, bench echo
    fleet): wrap in a CoalescingProducer when coalescing is on; hand back
    the raw producer — the bit-exact serial path — when it is off."""
    cfg = config if config is not None else BusCoalesceConfig.from_env()
    if not cfg.enabled or isinstance(producer, CoalescingProducer):
        return producer
    return CoalescingProducer(producer, cfg.max_batch, cfg.window_ms,
                              batch_wire=cfg.batch_wire)


def export_coalesce_gauges(metrics) -> None:
    """Coalescing health gauges (ridden by the balancers' supervision tick,
    like export_tracing_gauges): flushed batch/message counts and the
    largest batch seen — messages/batches is the live amortization factor
    — and how often the batch wire's mechanism engaged: frames and rows
    encoded by family, the decoder's intern-table lookups and hits."""
    metrics.gauge("bus_coalesce_batches", _STATS["batches"])
    metrics.gauge("bus_coalesce_messages", _STATS["messages"])
    metrics.gauge("bus_coalesce_batch_max", _STATS["max_batch"])
    metrics.gauge("bus_coalesce_parked_flushes", _STATS["parked_flushes"])
    metrics.gauge("bus_consumer_parks", BUS_STATS["parks"])
    metrics.gauge("bus_consumer_poll_timeouts", BUS_STATS["poll_timeouts"])
    for family, frames in _STATS["wire_frames"].items():
        tags = {"family": batch_hop_of(family)}
        metrics.gauge("bus_wire_frames", frames, tags)
        metrics.gauge("bus_wire_rows", _STATS["wire_rows"][family], tags)
    metrics.gauge("bus_wire_intern_lookups", WIRE_STATS["intern_lookups"])
    metrics.gauge("bus_wire_intern_hits", intern_hits())
