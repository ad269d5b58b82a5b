"""ISSUE 25: the program's host spans on the profiler's clock.

`utils/waterfall.span` is `jax.profiler.TraceAnnotation`: off (no profiler
session) it keeps nothing and changes no decision; on, one toy run on the
CPU twin leaves every `ow_*` span of the table in the trace, one `ow_step`
per journaled batch record, one `ow_fold` per fold record, every
activation counted once in an `ow_assemble`'s `b`, and no two spans of the
event loop's thread overlapping except by nesting (the no-`await` rule).
No assertion here is on a time.
"""
from __future__ import annotations

import asyncio
import base64
import gc
import glob
import itertools
import json
import os
import sys
import tracemalloc
import types

import pytest

from openwhisk_tpu.controller.loadbalancer import TpuBalancer
from openwhisk_tpu.controller.loadbalancer.base import maybe_batch_publish
from openwhisk_tpu.controller.loadbalancer.journal import PlacementJournal
from openwhisk_tpu.controller.loadbalancer.quality import (QualityConfig,
                                                           QualityPlane)
from openwhisk_tpu.core.entity import (ActionLimits, ActivationId,
                                       ActivationResponse, CodeExec,
                                       ControllerInstanceId, EntityName,
                                       EntityPath, ExecutableWhiskAction,
                                       Identity, InvokerInstanceId, MB,
                                       MemoryLimit, TimeLimit,
                                       WhiskActivation)
from openwhisk_tpu.core.entity.ids import DocRevision
from openwhisk_tpu.messaging import (ActivationMessage,
                                     CombinedCompletionAndResultMessage,
                                     MemoryMessagingProvider, MessageFeed,
                                     PingMessage, maybe_coalesce)
from openwhisk_tpu.messaging.columnar import is_batch_payload
from openwhisk_tpu.messaging.connector import decode_batch, decode_message
from openwhisk_tpu.utils import waterfall
from openwhisk_tpu.utils.hostprof import (GC_SERVING_THRESHOLDS,
                                          GLOBAL_HOST_OBSERVATORY)
from openwhisk_tpu.utils.transaction import TransactionId

#: every span name of ISSUE 25's table, plus the row continuation
SPANS = {
    "ow_admit", "ow_assemble", "ow_step", "ow_fold", "ow_shadow",
    "ow_quality", "ow_telemetry_fold", "ow_record",
    "ow_journal", "ow_readback_wait", "ow_readback_resume", "ow_fanout",
    "ow_placed", "ow_produce", "ow_ack_decode", "ow_ack_process", "ow_ping",
    "ow_supervision_tick", "ow_telemetry_tick", "ow_anomaly_tick",
    "ow_timeout_fire", "ow_gc"}
N_INVOKERS = 4
BATCHES = (5, 9, 3)
#: how long the echo invokers hold the action named `slow` before its ack:
#: past `invoke.POLL_INTERVAL_MIN`, so a blocking wait polls the store
SLOW_S = 0.25


def _action(name: str) -> ExecutableWhiskAction:
    a = ExecutableWhiskAction(EntityPath("guest"), EntityName(name),
                              CodeExec(kind="python:3", code="x"),
                              limits=ActionLimits(TimeLimit(5000),
                                                  MemoryLimit(MB(256))))
    a.rev = DocRevision("1-b")
    return a


def _msg(action, ident) -> ActivationMessage:
    return ActivationMessage(
        TransactionId(), action.fully_qualified_name, action.rev.rev, ident,
        ActivationId.generate(), ControllerInstanceId("0"), True, {})


def _echo_invoker(provider, instance, service_s: float = 0.0) -> MessageFeed:
    """Acks every activation, at once or `service_s` later, except the
    action named `lost`, and the action named `slow` `SLOW_S` later."""
    topic = instance.as_string
    provider.ensure_topic(topic)
    producer = maybe_coalesce(provider.get_producer())
    box = {}

    def ack(msg) -> None:
        act = WhiskActivation(
            EntityPath(str(msg.user.namespace.name)), msg.action.name,
            msg.user.subject, msg.activation_id, 0, 0,
            ActivationResponse.success({"ok": True}), duration=1)
        producer.send_nowait(
            f"completed{msg.root_controller_index.as_string}",
            CombinedCompletionAndResultMessage(msg.transid, act, instance))

    async def handle(payload: bytes) -> None:
        if is_batch_payload(payload):
            _kind, msgs = decode_batch(payload)
        else:
            msgs = [decode_message(ActivationMessage.parse, payload,
                                   "activation")]
        for msg in msgs:
            name = str(msg.action.name)
            if name == "lost":
                continue
            delay = SLOW_S if name == "slow" else service_s
            if delay > 0:
                asyncio.get_running_loop().call_later(delay, ack, msg)
            else:
                ack(msg)
        box["feed"].processed()

    box["feed"] = MessageFeed(topic, provider.get_consumer(topic, topic),
                              64, handle)
    return box["feed"].start()


async def _idle(bal) -> None:
    for _ in range(400):
        if not (bal._inflight_steps or bal._pending or bal._releases
                or bal._health_updates or bal._readbacks):
            break
        await asyncio.sleep(0.01)
    await asyncio.sleep(0.05)


async def _healthy_fleet(provider, bal, service_s: float = 0.0) -> tuple:
    """N_INVOKERS echo invokers, pinged until the balancer holds them all
    up. Returns their feeds and the coroutine function that pings them."""
    # the first batches compile on the event loop; on a loaded box that
    # can outlast the 10 s ping timeout, and the fleet then reads offline
    bal.supervision.ping_timeout = 600.0
    instances = [InvokerInstanceId(i, user_memory=MB(2048))
                 for i in range(N_INVOKERS)]
    feeds = [_echo_invoker(provider, inst, service_s) for inst in instances]
    pinger = provider.get_producer()
    provider.ensure_topic("health")

    async def ping() -> None:
        for inst in instances:
            await pinger.send("health", PingMessage(inst))

    for _ in range(200):
        await ping()
        await asyncio.sleep(0.05)
        if sum(h.status == "up" for h in await bal.invoker_health()) \
                >= N_INVOKERS:
            break
    else:
        raise RuntimeError("fleet never became healthy")
    await _idle(bal)
    return feeds, ping


async def _toy_run(journal_dir: str, trace_dir=None) -> dict:
    """One fixed toy run; traced from the first publish to the last fold
    when `trace_dir` is given. Returns the journal's records of that
    stretch and the number of activations published in it."""
    import jax

    provider = MemoryMessagingProvider()
    bal = TpuBalancer(
        provider, ControllerInstanceId("0"), managed_fraction=1.0,
        blackbox_fraction=0.0, prewarm=False,
        quality=QualityPlane(QualityConfig(enabled=True, shadow_every_n=1)))
    bal.TIMEOUT_FACTOR, bal.STD_TIMEOUT, bal.TIMEOUT_ADDON = 0, 0.0, 0.4
    journal = PlacementJournal(journal_dir)
    bal.attach_journal(journal)
    await bal.start()
    feeds, ping = await _healthy_fleet(provider, bal)

    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        seq0 = bal._journal_seq
        ident = Identity.generate("guest")
        publisher = maybe_batch_publish(bal)
        published = 0
        for k, n in enumerate(BATCHES):
            msgs = [(a, _msg(a, ident)) for a in
                    (_action(f"act{(k + i) % 4}") for i in range(n))]
            promises = await asyncio.gather(
                *[publisher.publish(a, m) for a, m in msgs])
            await asyncio.gather(*promises)
            published += n
            await _idle(bal)
        # the serial SPI, and an activation nobody acks
        one = _action("act1")
        await (await bal.publish(one, _msg(one, ident)))
        lost = _action("lost")
        with pytest.raises(Exception):
            await (await bal.publish(lost, _msg(lost, ident)))
        published += 2
        gc.collect()
        await ping()
        await asyncio.sleep(1.3)   # one 1 Hz supervision tick at least
        await _idle(bal)
        seq1 = bal._journal_seq
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    for f in feeds:
        await f.stop()
    await bal.close()
    journal.close()
    records = [r for r in PlacementJournal(journal_dir).records()
               if seq0 < r["seq"] <= seq1]
    return {"records": records, "published": published}


def _decisions(records: list) -> list:
    batches = {r["seq"]: r["b"] for r in records if r["t"] == "batch"}
    return [(batches[r["for"]], r["out"]) for r in records
            if r["t"] == "ack"]


def _host_lines(trace_dir: str, prefix: str = "ow_") -> list:
    """[(events of one thread)] with events as (name, start, end, stats)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith(prefix)]
            if evs:
                lines.append(evs)
    return lines


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    plain = asyncio.run(_toy_run(str(tmp / "journal-plain")))
    traced = asyncio.run(_toy_run(str(tmp / "journal-traced"),
                                  str(tmp / "trace")))
    traced["lines"] = _host_lines(str(tmp / "trace"))
    traced["jit_lines"] = _host_lines(str(tmp / "trace"), "PjitFunction(")
    return plain, traced


def test_tracing_changes_no_decision(runs):
    plain, traced = runs
    assert _decisions(plain["records"]) == _decisions(traced["records"])
    assert len(_decisions(plain["records"])) == len(BATCHES) + 2


def test_the_trace_holds_every_span_of_the_table(runs):
    _plain, traced = runs
    seen = {name for line in traced["lines"] for name, *_ in line}
    assert seen >= SPANS, SPANS - seen


def test_steps_and_folds_are_the_journal_s_records(runs):
    _plain, traced = runs
    recs = traced["records"]
    names = [name for line in traced["lines"] for name, *_ in line]
    assert names.count("ow_step") \
        == sum(r["t"] == "batch" for r in recs) == len(BATCHES) + 2
    assert names.count("ow_fold") == sum(r["t"] == "fold" for r in recs) > 0
    assert names.count("ow_journal") == len(recs)
    assemble = [st for line in traced["lines"] for name, _s, _e, st in line
                if name == "ow_assemble"]
    assert sum(st["b"] for st in assemble) == traced["published"]
    # the spans of one micro-batch share its batch record's seq
    assert sorted(st["seq"] for st in assemble) \
        == sorted(r["seq"] for r in recs if r["t"] == "batch")
    steps = [st["seq"] for line in traced["lines"] for name, _s, _e, st
             in line if name == "ow_step"]
    assert sorted(steps) == sorted(st["seq"] for st in assemble)
    frames = [st for line in traced["lines"] for name, _s, _e, st in line
              if name == "ow_ack_decode"]
    assert sum(st["acks"] for st in frames) == traced["published"] - 1
    assert all(st["bytes"] > 0 for st in frames)


def test_every_produce_says_whether_it_woke_a_parked_drainer(runs):
    """ISSUE 38: `ow_produce` carries `parked`; its mean over a run's
    events says how sparse the producers are (the reader a later
    benchmark issue needs is `span_mean` of this stat). The toy run's
    waves are far apart, so nearly every flush meets a parked drainer."""
    _plain, traced = runs
    produces = [st for line in traced["lines"] for name, _s, _e, st in line
                if name == "ow_produce"]
    # the serial SPI's two `ow_produce` (`send_activation_to_invoker`:
    # the dispatch's preparation, `n` alone) are no flush
    assert sum("bytes" not in st for st in produces) == 2
    produces = [st for st in produces if "bytes" in st]
    assert produces and all(st["parked"] in (0, 1) for st in produces)
    mean = sum(st["parked"] for st in produces) / len(produces)
    assert 0.5 < mean <= 1.0


def test_a_step_is_one_jitted_call(runs):
    """ISSUE 31: the post-step books ride the step's own output, so an
    `ow_step` holds ONE jitted call, the `packed` program the device
    metrics pair with the journal, an `ow_fold` likewise, and nothing is
    launched for the books. Outermost calls only: a first call traces the
    jitted functions it is made of."""
    _plain, traced = runs
    (loop,) = [line for line in traced["lines"]
               if any(name == "ow_assemble" for name, *_ in line)]
    (on_loop,) = [line for line in traced["jit_lines"]
                  if any(name == "PjitFunction(packed)"
                         for name, *_ in line)]
    calls = []
    for ev in sorted({ev[:3] for ev in on_loop},
                     key=lambda ev: (ev[1], -ev[2])):
        if not calls or ev[1] >= calls[-1][2]:
            calls.append(ev)
    assert not [name for name, *_ in calls if "books" in name]

    def inside(span):
        return [name for name, s, e in calls
                if span[1] <= s and e <= span[2]]

    steps = [ev for ev in loop if ev[0] == "ow_step"]
    assert len(steps) == len(BATCHES) + 2
    assert all(inside(ev) == ["PjitFunction(packed)"] for ev in steps)
    folds = [ev for ev in loop if ev[0] == "ow_fold"]
    assert folds
    assert all(inside(ev) == ["PjitFunction(packed)"] for ev in folds)
    # every jitted call of the loop's thread is inside a span of the table
    tops = [ev for ev in loop if ev[0] in SPANS]
    assert all(any(t[1] <= s and e <= t[2] for t in tops)
               for _name, s, e in calls)


def test_loop_spans_overlap_only_by_nesting(runs):
    _plain, traced = runs
    (loop,) = [line for line in traced["lines"]
               if any(name == "ow_assemble" for name, *_ in line)]
    # the readback waits on a worker thread, never on the loop's
    assert not any(name == "ow_readback_wait" for name, *_ in loop)
    assert any(name == "ow_readback_wait" for line in traced["lines"]
               if line is not loop for name, *_ in line)
    stack = []
    for name, start, end, _st in sorted(loop, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            assert end <= stack[-1][1], (name, stack[-1][0])
        stack.append((name, end))


def test_a_span_off_keeps_nothing(monkeypatch):
    gc.collect()
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(20000):
            with waterfall.span("ow_x", seq=i, b=7) as sp:
                sp.set_metadata(acks=3)
        gc.collect()
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    here = [d for d in after.compare_to(before, "filename")
            if d.traceback[0].filename in (__file__, waterfall.__file__)]
    assert sum(d.size_diff for d in here) < 4096
    # a process that never imported JAX (an invoker) gets the null span
    monkeypatch.setitem(sys.modules, "jax", None)
    sp = waterfall.span("ow_x", seq=1)
    assert sp is waterfall._NO_SPAN
    with sp as entered:
        entered.set_metadata(n=1)


def test_the_gc_span_needs_no_install_and_is_counted():
    obs = GLOBAL_HOST_OBSERVATORY
    assert not obs.installed
    watchers = obs._gc_watchers
    obs.watch_gc()
    obs.watch_gc()
    assert gc.callbacks.count(obs._gc_cb) == 1
    n = sum(obs._gc_count)
    gc.collect()
    assert sum(obs._gc_count) == n + 1 and obs._gc_span is None
    obs.unwatch_gc()
    assert gc.callbacks.count(obs._gc_cb) == 1
    obs.unwatch_gc()
    assert obs._gc_watchers == watchers
    assert gc.callbacks.count(obs._gc_cb) == (1 if watchers else 0)


# -- ISSUE 26: the served path owns the collector --------------------------

CTL_PORT = 13481


def _plain_balancer(provider) -> TpuBalancer:
    return TpuBalancer(provider, ControllerInstanceId("0"),
                       managed_fraction=1.0, blackbox_fraction=0.0,
                       prewarm=False)


def _collector() -> tuple:
    return gc.get_threshold(), gc.get_freeze_count()


def _serving(found: tuple) -> bool:
    """Is the collector under the serving policy, or as `found`?"""
    thresholds, frozen = _collector()
    if thresholds == GC_SERVING_THRESHOLDS:
        assert frozen > found[1]
        return True
    # all of it unfrozen: a fresh CPython 3.12 holds a few hundred objects
    # of its own in the permanent generation, until the first unfreeze()
    assert thresholds == found[0] and frozen in (0, found[1])
    return False


async def _two_balancers(close_order) -> list:
    a, b = (_plain_balancer(MemoryMessagingProvider()) for _ in range(2))
    found = _collector()
    seen = []
    await a.start()
    await a.start()   # a second start() takes no second share
    seen.append(_serving(found))
    await b.start()
    assert a.gc_tuned["thresholds"] == list(GC_SERVING_THRESHOLDS)
    assert 0 < b.gc_tuned["frozen"] <= a.gc_tuned["frozen"]
    for bal in (a, b)[::close_order]:
        seen.append(_serving(found))
        await bal.close()
        assert bal.gc_tuned is None
    seen.append(_serving(found))
    return seen


async def _never_started() -> list:
    found = _collector()
    await _plain_balancer(MemoryMessagingProvider()).close()
    return [_serving(found)]


async def _controller(tpu: bool) -> list:
    from openwhisk_tpu.controller.core import Controller
    from openwhisk_tpu.controller.loadbalancer import LeanBalancer

    class StubInvoker:
        async def stop(self) -> None:
            pass

    async def no_invoker(invoker_id, provider):
        return StubInvoker()

    provider = MemoryMessagingProvider()
    lb = _plain_balancer(provider) if tpu else LeanBalancer(
        provider, ControllerInstanceId("0"), no_invoker,
        user_memory=MB(512))
    controller = Controller(ControllerInstanceId("0"), provider,
                            load_balancer=lb)
    found = _collector()
    tuners = GLOBAL_HOST_OBSERVATORY._gc_tuners
    await controller.start(port=CTL_PORT)
    try:
        # one share, whoever took it: the balancer, or the controller
        # beside a balancer that takes none
        assert GLOBAL_HOST_OBSERVATORY._gc_tuners == tuners + 1
        assert (getattr(lb, "gc_tuned", None) is not None) == tpu
        seen = [_serving(found)]
    finally:
        await controller.stop()
    return seen + [_serving(found)]


@pytest.mark.parametrize("scenario,seen", [
    (lambda: _two_balancers(1), [True, True, True, False]),
    (lambda: _two_balancers(-1), [True, True, True, False]),
    (_never_started, [False]),
    (lambda: _controller(tpu=True), [True, False]),
    (lambda: _controller(tpu=False), [True, False]),
], ids=["closed_first_in_first_out", "closed_last_in_first_out",
        "close_without_start", "controller_with_a_tpu_balancer",
        "controller_with_another_balancer"])
def test_the_balancer_lifecycle_owns_the_collector(scenario, seen):
    """start() freezes the boot heap and sets GC_SERVING_THRESHOLDS, once
    however many balancers and controllers the process starts; the last
    close() restores thresholds and freeze count exactly."""
    assert GLOBAL_HOST_OBSERVATORY._gc_tuners == 0, \
        "an earlier test left a balancer (and the collector's policy) open"
    assert asyncio.run(scenario()) == seen
    assert GLOBAL_HOST_OBSERVATORY._gc_tuners == 0


ACTIVATIONS, CALLERS = 2048, 32


def test_the_served_path_makes_no_reference_cycles():
    """The finding GC_SERVING_THRESHOLDS rests on: with a service time over
    0 (so `setup_activation`'s entry <-> TimerHandle cycle exists until
    `timeout_task.cancel()` breaks it) a few thousand activations through
    publish -> ack -> promise leave next to nothing for the collector:
    reference counts free the rest. A cycle per row would read >= 1."""
    async def go() -> int:
        provider = MemoryMessagingProvider()
        bal = _plain_balancer(provider)
        await bal.start()
        feeds, _ping = await _healthy_fleet(provider, bal, service_s=0.002)
        ident = Identity.generate("guest")
        actions = [_action(f"act{i}") for i in range(4)]
        publisher = maybe_batch_publish(bal)

        async def caller(k: int, n: int) -> None:
            for i in range(n):
                a = actions[(k + i) % 4]
                await (await publisher.publish(a, _msg(a, ident)))

        async def drive(total: int) -> None:
            await asyncio.gather(*[caller(k, total // CALLERS)
                                   for k in range(CALLERS)])
            await _idle(bal)

        try:
            await drive(ACTIVATIONS // 8)   # compiles happen here
            gc.collect()
            gc.disable()
            try:
                await drive(ACTIVATIONS)
                return gc.collect()
            finally:
                gc.enable()
        finally:
            for f in feeds:
                await f.stop()
            await bal.close()

    unreachable = asyncio.run(go())
    assert unreachable < 0.1 * ACTIVATIONS, unreachable


# -- ISSUE 39: the front door's spans and the feeds' wakes -------------------

#: the spans every REST invoke makes, each with the request's `req`
DOOR_SPANS = ("ow_http_auth", "ow_http_entitle", "ow_http_body",
              "ow_http_resolve", "ow_invoke", "ow_invoke_done",
              "ow_http_respond")
#: a fixed credential and namespace: the answers are the same every run
DOOR_KEY = "0c0ffee0-0000-4000-8000-000000000039:" + "39" * 32
DOOR_NS = "guest-door"
NOOP_CALLS, SLOW_CALLS = 8, 2
#: DOOR_SCRIPT's steps answered with an ack frame's record as it came:
#: the blocking invokes of `noop` without `?result=true`
FRAMED_STEPS = (0, 1, 3)
#: the parent commit's answers to DOOR_SCRIPT (PR 38's tree, tracing off)
ANSWERS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "frontdoor_answers_pr38.json")


def _invoke_path(name: str, **query) -> str:
    q = {"blocking": "true", **query}
    return (f"/api/v1/namespaces/_/actions/{name}?"
            + "&".join(f"{k}={v}" for k, v in q.items()))


def _http(method: str, path: str, body=None, key=DOOR_KEY) -> bytes:
    """One HTTP/1.1 keep-alive request, byte for byte."""
    head = [f"{method} {path} HTTP/1.1", "Host: 127.0.0.1"]
    if key is not None:
        head.append("Authorization: Basic "
                    + base64.b64encode(key.encode()).decode())
    data = b""
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
        head += ["Content-Type: application/json",
                 f"Content-Length: {len(data)}"]
    return ("\r\n".join(head) + "\r\n\r\n").encode() + data


async def _exchange(conn, raw: bytes) -> tuple:
    """Send one request on a connection; read its whole answer."""
    reader, writer = conn
    writer.write(raw)
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    headers = {}
    while (line := await reader.readline()) not in (b"\r\n", b""):
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    body = await reader.readexactly(int(headers.get("content-length", 0)))
    return status, headers, body


#: requests whose answers must not change by a byte: invokes blocking, for
#: the result, without a body, non-blocking; a malformed body and one that
#: is no UTF-8; an action that does not exist; a wrong key, a key no store
#: holds (read from the store, outside any span), no key; a public path
DOOR_SCRIPT = [
    ("POST", _invoke_path("noop"), {"a": 1}, DOOR_KEY),
    ("POST", _invoke_path("noop"), {"a": 2, "b": [1, "x"]}, DOOR_KEY),
    ("POST", _invoke_path("noop", result="true"), {"x": "y"}, DOOR_KEY),
    ("POST", _invoke_path("noop"), None, DOOR_KEY),
    ("POST", _invoke_path("noop", blocking="false"), {}, DOOR_KEY),
    ("POST", _invoke_path("noop"), b"{not json", DOOR_KEY),
    ("POST", _invoke_path("noop"), b"\xff\xfe{}", DOOR_KEY),
    ("POST", _invoke_path("nosuch"), {}, DOOR_KEY),
    ("POST", _invoke_path("noop"), {}, DOOR_KEY.split(":")[0] + ":wrong"),
    ("POST", _invoke_path("noop"), {},
     "0c0ffee0-0000-4000-8000-00000000dead:nokey"),
    ("POST", _invoke_path("noop"), {}, None),
    ("GET", "/ping", None, None),
]


def _answer(status: int, headers: dict, body: bytes) -> list:
    """What is compared of an answer: all but `Date` and `Server`."""
    return [status, {k: v for k, v in sorted(headers.items())
                     if k not in ("date", "server")}, body.decode()]


async def _door(trace_dir=None) -> dict:
    """A toy of the REST front door on the CPU twin: the program's
    `Controller` over in-memory stores around a TpuBalancer and
    N_INVOKERS echo invokers, served as `benchmark/frontdoor.py` serves
    it; the actions `noop` and `slow` created over the API. Answers
    DOOR_SCRIPT with tracing off (ids made deterministic). With
    `trace_dir`, then traces one round of NOOP_CALLS + SLOW_CALLS
    concurrent blocking invokes, each on a keep-alive connection of its
    own, after one round like it untraced (every bucket compiled)."""
    import jax

    from openwhisk_tpu.controller.core import Controller
    from openwhisk_tpu.core.entity import (BasicAuthenticationAuthKey,
                                           ExecManifest, Namespace, Subject,
                                           WhiskAuthRecord,
                                           limits_from_config)
    from openwhisk_tpu.core.entity import entity as entity_module
    from openwhisk_tpu.messaging import columnar
    from openwhisk_tpu.messaging.memory import MemoryConsumer
    from openwhisk_tpu.utils import transaction
    from openwhisk_tpu.utils.logging import Logging

    ExecManifest.initialize(None)
    limits_from_config()
    # every wake of a MessageFeed is a non-empty peek of its consumer:
    # counted from the first peek on, while `wakes[1]` holds
    wakes = [0, False]
    real_peek = MemoryConsumer.peek

    async def peek(self, *args, **kwargs):
        batch = await real_peek(self, *args, **kwargs)
        wakes[0] += bool(batch and wakes[1])
        return batch
    counting = pytest.MonkeyPatch()
    counting.setattr(MemoryConsumer, "peek", peek)
    provider = MemoryMessagingProvider()
    bal = _plain_balancer(provider)
    await bal.start()
    feeds, _ping = await _healthy_fleet(provider, bal)
    controller = Controller(
        ControllerInstanceId("0"), provider, logger=Logging(level="warn"),
        load_balancer=bal, invocations_per_minute=10 ** 6,
        concurrent_invocations=10 ** 4, fires_per_minute=10 ** 6)
    key = BasicAuthenticationAuthKey.parse(DOOR_KEY)
    await controller.auth_store.put(WhiskAuthRecord(
        Subject(DOOR_NS), [Namespace(EntityName(DOOR_NS), key.uuid)], [key]))

    async def serving() -> None:
        pass
    # the balancer serves already: `Controller.start` must not start it
    bal.start = serving
    try:
        await controller.start("127.0.0.1", 0)
    finally:
        del bal.start
    port = controller._runner.addresses[0][1]
    out: dict = {}
    patch = pytest.MonkeyPatch()
    conns = []
    try:
        conns.append(await asyncio.open_connection("127.0.0.1", port))
        for name in ("noop", "slow"):
            status, _h, body = await _exchange(conns[0], _http(
                "PUT", f"/api/v1/namespaces/_/actions/{name}",
                {"exec": {"kind": "python:3", "code": "def main(a): return a"},
                 "limits": {"memory": 256}}))
            assert status == 200, body
        patch.setattr(transaction, "_counter", itertools.count(1))
        ids = itertools.count(1)
        patch.setattr(ActivationId, "generate", classmethod(
            lambda cls: cls.of_hex(f"{next(ids):032x}")))
        # an entity's `updated` is the wall clock where the record is made
        patch.setattr(entity_module, "time",
                      types.SimpleNamespace(time=lambda: 39.0))
        # every record the controller's ack frames brought, as decoded
        real_decode = columnar.AckFrame.decode
        out["framed"] = framed = []

        def decode(raw, header):
            acks = real_decode(raw, header)
            framed.extend(a.activation for a in acks if a.activation)
            return acks
        patch.setattr(columnar.AckFrame, "decode", staticmethod(decode))
        out["answers"] = [_answer(*await _exchange(conns[0], _http(*step)))
                          for step in DOOR_SCRIPT]
        patch.undo()
        if trace_dir is None:
            return out
        bodies = ([("noop", {"i": i}) for i in range(NOOP_CALLS)]
                  + [("slow", {"slow": i}) for i in range(SLOW_CALLS)])
        reqs = [_http("POST", _invoke_path(name), body)
                for name, body in bodies]
        bodies = [body for _name, body in bodies]
        conns += [await asyncio.open_connection("127.0.0.1", port)
                  for _ in reqs[1:]]
        await asyncio.gather(*(_exchange(c, r) for c, r in zip(conns, reqs)))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        wakes[1] = True
        try:
            got = await asyncio.gather(*(_exchange(c, r)
                                         for c, r in zip(conns, reqs)))
        finally:
            wakes[1] = False
            jax.profiler.stop_trace()
        out.update(statuses=[g[0] for g in got], wakes=wakes[0],
                   bodies=sum(len(json.dumps(b).encode()) for b in bodies),
                   slow_body=len(json.dumps({"slow": 0})))
        return out
    finally:
        patch.undo()
        for _reader, writer in conns:
            writer.close()
        await controller.stop()      # closes the balancer
        for f in feeds:
            await f.stop()
        counting.undo()


@pytest.fixture(scope="module")
def door(tmp_path_factory):
    from openwhisk_tpu.core.entity import ConcurrencyLimit, MemoryLimit
    was = (MemoryLimit.MIN, MemoryLimit.STD, MemoryLimit.MAX,
           ConcurrencyLimit.MIN, ConcurrencyLimit.STD, ConcurrencyLimit.MAX)
    trace = str(tmp_path_factory.mktemp("door") / "trace")
    try:
        out = asyncio.run(asyncio.wait_for(_door(trace), 300.0))
    finally:
        (MemoryLimit.MIN, MemoryLimit.STD, MemoryLimit.MAX,
         ConcurrencyLimit.MIN, ConcurrencyLimit.STD,
         ConcurrencyLimit.MAX) = was
    lines = _host_lines(trace)
    (loop,) = [line for line in lines
               if any(name == "ow_http_auth" for name, *_ in line)]
    out.update(lines=lines, loop=loop)
    return out


def _by_req(loop) -> dict:
    got: dict = {}
    for name, _s, _e, st in loop:
        if "req" in st:
            got.setdefault(st["req"], []).append((name, st))
    return got


def test_every_request_has_the_seven_spans_under_one_req(door):
    assert door["statuses"] == [200] * (NOOP_CALLS + SLOW_CALLS)
    by_req = _by_req(door["loop"])
    # one `req` a request, a different one for each
    assert len(by_req) == NOOP_CALLS + SLOW_CALLS
    for req, spans in by_req.items():
        names = [name for name, _st in spans]
        assert set(names) >= set(DOOR_SPANS), (req, names)
        for once in ("ow_http_body", "ow_invoke", "ow_invoke_done"):
            assert names.count(once) == 1, (req, names)
        # the answer's own span knows its size and that its body is the
        # framed record; the CORS headers' span does not
        (answer,) = [st for name, st in spans
                     if name == "ow_http_respond" and "bytes" in st]
        assert answer["bytes"] > 0 and answer["raw"] == 1
    # the admission plane's flushes carry the checks they resolve
    flushes = [st["n"] for line in door["lines"] for name, _s, _e, st in line
               if name == "ow_http_entitle" and "n" in st]
    assert flushes and sum(flushes) == NOOP_CALLS + SLOW_CALLS


def test_the_body_span_counts_every_body_byte_the_clients_sent(door):
    """`ow_http_body`'s `bytes` are the request bodies, byte for byte; no
    front-door span runs off the event loop's thread. aiohttp's own parse
    of the bytes has no span (no public seam: PERF.md section 7)."""
    sizes = [st["bytes"] for name, _s, _e, st in door["loop"]
             if name == "ow_http_body"]
    assert len(sizes) == NOOP_CALLS + SLOW_CALLS
    assert sum(sizes) == door["bodies"]
    assert all(not name.startswith(("ow_http", "ow_invoke"))
               for line in door["lines"] if line is not door["loop"]
               for name, *_ in line)


def test_a_held_promise_is_polled(door):
    """`ow_invoke_done` carries the store polls of the wait: the two `slow`
    invokes (acked SLOW_S = 0.25 s late, past POLL_INTERVAL_MIN) poll at
    least once; `test_the_wait_counts_its_polls` pins the fast case."""
    spans = [dict(s) for s in _by_req(door["loop"]).values()]
    assert all("polls" in st["ow_invoke_done"] for st in spans)
    slow = [st for st in spans
            if st["ow_http_body"]["bytes"] == door["slow_body"]]
    assert len(slow) == SLOW_CALLS
    assert all(st["ow_invoke_done"]["polls"] >= 1 for st in slow)


def test_every_feed_wake_is_one_feed_span(door):
    feeds = [st for line in door["lines"] for name, _s, _e, st in line
             if name == "ow_feed"]
    assert door["wakes"] > 0 and len(feeds) == door["wakes"]
    assert all(st["n"] >= 1 for st in feeds)
    assert all(name != "ow_feed" for line in door["lines"]
               if line is not door["loop"] for name, *_ in line)


def test_the_front_door_s_spans_overlap_only_by_nesting(door):
    stack = []
    for name, start, end, _st in sorted(door["loop"],
                                        key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            assert end <= stack[-1][1], (name, stack[-1][0])
        stack.append((name, end))


def test_tracing_off_the_answers_are_the_parent_s_byte_for_byte(door):
    """Every answer of DOOR_SCRIPT is the parent's byte for byte but the
    blocking 200s of FRAMED_STEPS. Their bodies are now the record's own
    bytes as the invoker framed them, compact JSON (`,` and `:`, as
    upstream's CompactPrinter answers), where the parent parsed the record
    and dumped the entity again with `json.dumps`'s `, ` and `: `. They are
    JSON-equal to the parent's (`updated` is the toy's fixed clock on both
    sides, here the invoker's), with their new Content-Length."""
    with open(ANSWERS) as f:
        parent = json.load(f)
    assert len(door["answers"]) == len(parent)
    for step, (got, was) in enumerate(zip(door["answers"], parent)):
        if step not in FRAMED_STEPS:
            assert got == was, step
            continue
        status, headers, body = got
        assert status == was[0] == 200
        assert headers == {**was[1],
                           "content-length": str(len(body.encode()))}
        assert json.loads(body) == json.loads(was[2])
        assert body != was[2] and ", " not in body


def test_a_blocking_answer_is_the_framed_record_s_bytes(door):
    """The 200 of a blocking invoke is the ack frame's record byte for
    byte, and the record is still unparsed after the answer went out; the
    one record the script parses is the `?result=true` invoke's."""
    framed = {json.loads(lazy.raw)["activationId"]: lazy
              for lazy in door["framed"]}
    for step in FRAMED_STEPS:
        body = door["answers"][step][2].encode()
        lazy = framed[json.loads(body)["activationId"]]
        assert body == lazy.raw and not lazy.materialized
    (parsed,) = [aid for aid, lazy in framed.items() if lazy.materialized]
    # activation ids count up from 1 with the script's invokes: the third
    # is `?result=true`'s
    assert "result=true" in DOOR_SCRIPT[2][1] and parsed == f"{3:032x}"


@pytest.mark.parametrize("held", [False, True], ids=["fast", "held"])
def test_the_wait_counts_its_polls(tmp_path, held):
    """`ActionInvoker.invoke` over a balancer whose promise is resolved at
    once, or SLOW_S later, and a store that never holds the record: the
    outcome's `polls` and the `ow_invoke_done` span's are the store's
    count, 0 for the fast promise; `ow_invoke` and it carry `req`."""
    import jax

    from openwhisk_tpu.controller.invoke import POLL_INTERVAL_MIN, ActionInvoker
    from openwhisk_tpu.core.entity import Parameters
    from openwhisk_tpu.database import NoDocumentException

    class Store:
        polls = 0

        async def get(self, namespace, activation_id):
            Store.polls += 1
            raise NoDocumentException(str(activation_id))

    class Balancer:
        async def publish(self, action, msg):
            fut = asyncio.get_running_loop().create_future()
            if held:
                asyncio.get_running_loop().call_later(
                    SLOW_S, fut.set_result, "done")
            else:
                fut.set_result("done")
            return fut

    assert SLOW_S > POLL_INTERVAL_MIN

    async def go():
        inv = ActionInvoker(None, Store(), Balancer(),
                            ControllerInstanceId("0"))
        return await inv.invoke(Identity.generate("guest"), _action("a"),
                                Parameters(), {"k": 1}, blocking=True,
                                req=39)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        outcome = asyncio.run(go())
    finally:
        jax.profiler.stop_trace()
    assert outcome.activation == "done" and not outcome.accepted
    assert outcome.polls == Store.polls
    assert (outcome.polls >= 1) if held else (outcome.polls == 0)
    spans = {name: st for line in _host_lines(str(tmp_path))
             for name, _s, _e, st in line}
    assert spans["ow_invoke"] == {"req": 39}
    assert spans["ow_invoke_done"] == {"req": 39, "polls": outcome.polls}
