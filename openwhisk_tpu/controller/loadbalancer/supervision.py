"""Invoker supervision: the health protocol.

Rebuild of core/controller/.../loadBalancer/InvokerSupervision.scala:
  - invokers ping the `health` topic at 1 Hz (InvokerReactive.scala:337-342);
  - one FSM per invoker with states Healthy('up') / Unhealthy / Unresponsive
    / Offline('down') (:47-66);
  - a ring buffer of the last 10 invocation outcomes; > 3 system errors ->
    Unhealthy, > 3 timeouts -> Unresponsive (:435-443);
  - Offline after 10 s of ping silence (:294);
  - new invokers register lazily on their first ping (:191-207) and the
    balancer state grows in place — shrinking is by marking Offline only;
  - unhealthy invokers recover via periodic test traffic; here the FSM
    re-opens the error window after a cooldown (the reference posts a system
    test action once per minute — hook `send_test_action` to enable that).
Status changes are pushed to the balancer through `on_status_changes`, in
waves: the changes that one feed wake's pings (or one watchdog tick) made,
in order, so that a fleet registering at once costs the balancer one wave
of new rows per wake and not one per invoker. A wake's pings are parsed as
one block under one `ow_ping` span (`n`: pings in the block).
"""
from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from ...core.entity import InvokerInstanceId
from ...messaging.connector import MessageFeed, HEALTH_RETENTION_BYTES, HEALTH_TOPIC
from ...utils.ring_buffer import RingBuffer
from ...utils.scheduler import Scheduler
from ...utils.transaction import TransactionId
from ...utils.waterfall import span
from .base import HEALTHY, OFFLINE, UNHEALTHY, UNRESPONSIVE, InvokerHealth

SUCCESS = "success"
SYSTEM_ERROR = "system_error"
TIMEOUT = "timeout"

BUFFER_SIZE = 10
ERROR_TOLERANCE = 3
PING_TIMEOUT_S = 10.0
RECOVERY_COOLDOWN_S = 60.0
#: the watchdog's period: the offline rule is checked once a second
WATCHDOG_INTERVAL_S = 1.0
#: a watchdog tick later than its period by more than this found the
#: controller's loop held (a compile, a collection): for that long no ping
#: could be read, so that span is nobody's silence and is not counted in
#: it. One ping period (1 s): a shorter hold makes no invoker miss a ping
HELD_LOOP_S = 1.0


@dataclass
class InvokerActorState:
    id: InvokerInstanceId
    status: str = OFFLINE
    #: when the invoker was last heard, moved later by the span of every
    #: hold of the controller's loop since (`HELD_LOOP_S`): the offline
    #: rule counts silence from here
    last_ping: float = 0.0
    buffer: RingBuffer = field(default_factory=lambda: RingBuffer(BUFFER_SIZE))
    # seed one cooldown in the past: the FIRST probe of an unhealthy invoker
    # must fire immediately (time.monotonic() is host uptime — a bare 0.0
    # default would suppress probes on freshly-booted hosts)
    last_recovery_attempt: float = field(
        default_factory=lambda: time.monotonic() - RECOVERY_COOLDOWN_S)

    def classify(self) -> str:
        """Derive the health status from the outcome window (:435-443)."""
        if self.buffer.count(lambda r: r == SYSTEM_ERROR) > ERROR_TOLERANCE:
            return UNHEALTHY
        if self.buffer.count(lambda r: r == TIMEOUT) > ERROR_TOLERANCE:
            return UNRESPONSIVE
        return HEALTHY


#: (the invoker as it pinged, the admin address it announced) of a payload
Ping = Tuple[InvokerInstanceId, Optional[str]]


def _ping_of(j) -> Ping:
    admin = j.get("admin")
    return (InvokerInstanceId.from_json(j["name"]),
            admin if isinstance(admin, str) and admin else None)


def parse_pings(payloads: List[bytes]) -> List[Optional[Ping]]:
    """One JSON parse for a block of `PingMessage` payloads; where the
    block does not parse whole, each payload alone. None stands for a
    payload that is no ping."""
    try:
        docs = json.loads(b"[" + b",".join(payloads) + b"]")
    except ValueError:
        docs = None
    if docs is None or len(docs) != len(payloads):
        docs = []
        for raw in payloads:
            try:
                docs.append(json.loads(raw))
            except ValueError:
                docs.append(None)
    out: List[Optional[Ping]] = []
    for j in docs:
        try:
            out.append(_ping_of(j))
        except (ValueError, KeyError, TypeError, AttributeError):
            out.append(None)
    return out


class InvokerPool:
    def __init__(self, messaging_provider,
                 on_status_changes: Optional[Callable] = None,
                 send_test_action: Optional[Callable] = None,
                 logger=None, ping_timeout: float = PING_TIMEOUT_S,
                 group: str = "health", on_tick: Optional[Callable] = None):
        self.provider = messaging_provider
        #: takes a wave [(invoker, status)], in order
        self.on_status_changes = on_status_changes or (lambda wave: None)
        self.send_test_action = send_test_action
        #: optional 1 Hz callback riding the watchdog — the balancer hangs
        #: its telemetry burn-rate gauge refresh here so dashboards stay
        #: fresh without a scheduler of their own
        self.on_tick = on_tick
        self.logger = logger
        self.ping_timeout = ping_timeout
        self.group = group
        self.invokers: Dict[int, InvokerActorState] = {}
        #: advisory hints from the anomaly plane (invoker index -> firing
        #: alert name). Observability only: the FSM's status derivation
        #: never reads them — a flagged invoker still takes traffic until
        #: real outcome evidence (the ring buffer) demotes it.
        self.unhealthy_hints: Dict[int, str] = {}
        #: fleet observatory peer directory (ISSUE 16): invoker admin
        #: addresses announced on their health pings. Empty unless
        #: invokers run with the observatory enabled and an address set.
        self.invoker_admin: Dict[int, str] = {}
        self._feed: Optional[MessageFeed] = None
        self._watchdog: Optional[Scheduler] = None
        #: the changes of the wave being gathered; None between waves
        self._wave: Optional[List[tuple]] = None
        #: when the watchdog's last tick ended
        self._tick_done: Optional[float] = None

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        # pings are ephemeral: tight retention, and never replay a backlog
        # into a new per-controller group
        self.provider.ensure_topic(HEALTH_TOPIC,
                                   retention_bytes=HEALTH_RETENTION_BYTES)
        consumer = self.provider.get_consumer(HEALTH_TOPIC, self.group,
                                              max_peek=128, from_latest=True)
        self._feed = MessageFeed("health", consumer, 128, logger=self.logger,
                                 block_handler=self.on_ping_block)
        self._feed.start()
        self._watchdog = Scheduler(WATCHDOG_INTERVAL_S, self._check_offline,
                                   name="invoker-watchdog",
                                   logger=self.logger).start()

    async def stop(self) -> None:
        if self._watchdog:
            await self._watchdog.stop()
        if self._feed:
            await self._feed.stop()

    # -- events ------------------------------------------------------------
    def on_ping_block(self, payloads: List[bytes]) -> None:
        """The pings one wake of the health feed brought: parsed as one
        block (`ow_ping`, `n` = pings in it), each then what `on_ping`
        makes of it, and the status changes they made handed on as one
        wave. A payload that is no ping is skipped, as it always was."""
        self._wave = []
        try:
            with span("ow_ping", n=len(payloads)):
                now = time.monotonic()
                for ping in parse_pings(payloads):
                    if ping is None:
                        continue
                    instance, admin = ping
                    if admin:
                        self.invoker_admin[instance.instance] = admin
                    self.on_ping(instance, now)
        finally:
            self._end_wave()

    def on_ping(self, instance: InvokerInstanceId,
                now: Optional[float] = None) -> None:
        st = self.invokers.get(instance.instance)
        if st is None:
            # lazy registration on first ping (:191-207)
            st = InvokerActorState(instance, status=OFFLINE)
            self.invokers[instance.instance] = st
        st.id = instance  # refresh user_memory etc.
        st.last_ping = time.monotonic() if now is None else now
        if st.status == OFFLINE:
            self._transition(st, HEALTHY if st.classify() == HEALTHY else st.classify())
        elif st.status in (UNHEALTHY, UNRESPONSIVE):
            self._maybe_recover(st)

    def on_invocation_finished(self, instance: Optional[InvokerInstanceId],
                               is_system_error: bool, forced: bool) -> None:
        """Fold an invocation outcome into the window (LB feeds this from
        completion acks; forced timeouts count as timeouts)."""
        if instance is None:
            return
        st = self.invokers.get(instance.instance)
        if st is None:
            return
        outcome = SYSTEM_ERROR if is_system_error else (TIMEOUT if forced else SUCCESS)
        st.buffer.add(outcome)
        if st.status != OFFLINE:
            self._transition(st, st.classify())

    async def _check_offline(self) -> None:
        """Offline after `ping_timeout` of silence (:294), counted over the
        time the controller could hear: a tick that finds the loop was
        held (`HELD_LOOP_S`) takes the held span, from when the tick was
        due, out of every invoker's silence, since pings that came
        meanwhile sit unread; a fleet held silent by its controller's own
        compile would otherwise all go offline. An invoker heard inside
        that span (before the loop stopped) has its silence start at the
        tick. Only the held span is forgiven, so a dead invoker still goes
        offline under holds that come again and again, later by their
        sum."""
        with span("ow_supervision_tick", n=len(self.invokers)):
            now = time.monotonic()
            held = 0.0 if self._tick_done is None else \
                now - self._tick_done - WATCHDOG_INTERVAL_S
            self._wave = []
            try:
                for st in self.invokers.values():
                    if held > HELD_LOOP_S:
                        st.last_ping = min(st.last_ping + held, now)
                    if st.status != OFFLINE \
                            and now - st.last_ping > self.ping_timeout:
                        self._transition(st, OFFLINE)
            finally:
                self._end_wave()
            if self.on_tick is not None:
                try:
                    self.on_tick()
                except Exception:  # noqa: BLE001 — a gauge refresh must
                    pass           # never kill the health watchdog
        self._tick_done = time.monotonic()

    def _maybe_recover(self, st: InvokerActorState) -> None:
        now = time.monotonic()
        if now - st.last_recovery_attempt < RECOVERY_COOLDOWN_S:
            return
        st.last_recovery_attempt = now
        if self.send_test_action is not None:
            asyncio.get_event_loop().create_task(self.send_test_action(st.id))
        else:
            # no test-action channel: re-open the window for organic traffic
            st.buffer = RingBuffer(BUFFER_SIZE)
            self._transition(st, HEALTHY)

    def _transition(self, st: InvokerActorState, new_status: str) -> None:
        if new_status != st.status:
            old = st.status
            st.status = new_status
            if self.logger:
                self.logger.info(TransactionId.INVOKER_HEALTH,
                                 f"invoker{st.id.instance} {old} -> {new_status}",
                                 "InvokerPool")
            if self._wave is not None:
                self._wave.append((st.id, new_status))
            else:
                self.on_status_changes([(st.id, new_status)])

    def _end_wave(self) -> None:
        wave, self._wave = self._wave, None
        if wave:
            self.on_status_changes(wave)

    def set_unhealthy_hints(self, hints: Dict[int, str]) -> None:
        """Replace the advisory hint set (the anomaly plane pushes the full
        current dict every tick when CONFIG_whisk_anomaly_hintUnhealthy is
        on, so recovered invokers shed their hint automatically)."""
        self.unhealthy_hints = dict(hints)

    # -- views -------------------------------------------------------------
    def health(self) -> List[InvokerHealth]:
        return [InvokerHealth(st.id, st.status,
                              hint=self.unhealthy_hints.get(idx))
                for idx, st in sorted(self.invokers.items())]
