"""Regression tests for review findings (sqlite :memory: threading, LIKE
wildcard escaping, ack shrink aliasing, feed capacity double-credit)."""
import asyncio

import pytest

from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                       EntityName, EntityPath, Subject,
                                       WhiskActivation)
from openwhisk_tpu.database import SqliteArtifactStore
from openwhisk_tpu.database.cache import EntityCache, RemoteCacheInvalidation
from openwhisk_tpu.messaging import MemoryMessagingProvider, ResultMessage, parse_ack
from openwhisk_tpu.utils.transaction import TransactionId


def run(coro):
    return asyncio.run(coro)


def test_sqlite_memory_store_works_across_executor_threads():
    async def go():
        st = SqliteArtifactStore()  # :memory:
        await st.put("ns/a", {"entityType": "actions", "namespace": "ns",
                              "name": "a", "updated": 1})
        return await st.get("ns/a")
    assert run(go())["name"] == "a"


def test_sqlite_namespace_underscore_not_wildcard():
    async def go():
        st = SqliteArtifactStore()
        await st.put("my_ns/a", {"entityType": "actions", "namespace": "my_ns",
                                 "name": "a", "updated": 1})
        await st.put("myxns/pkg/b", {"entityType": "actions", "namespace": "myxns/pkg",
                                     "name": "b", "updated": 2})
        docs = await st.query("actions", "my_ns")
        count = await st.count("actions", "my_ns")
        return [d["name"] for d in docs], count
    names, count = run(go())
    assert names == ["a"]
    assert count == 1


def test_ack_shrink_does_not_mutate_stored_activation():
    act = WhiskActivation(EntityPath("guest"), EntityName("big"),
                          Subject("guest-user"), ActivationId.generate(),
                          1.0, 2.0, ActivationResponse.success({"blob": "x" * 100}))
    msg = ResultMessage(TransactionId(), act)
    shrunk = msg.shrink(10)
    assert act.response.result == {"blob": "x" * 100}  # original intact
    parsed = parse_ack(shrunk.serialize())
    assert parsed.activation.response.result is None
    assert parsed.activation.response.size is not None


def test_invalidation_feed_capacity_not_inflated_by_bad_payloads():
    async def go():
        provider = MemoryMessagingProvider()
        c = EntityCache()
        r = RemoteCacheInvalidation(provider, "c0", {"whisks": c})
        r.start()
        prod = provider.get_producer()
        for _ in range(5):
            await prod.send("cacheInvalidation", b"not json")
        await asyncio.sleep(0.1)
        free = r._feed.free_capacity
        await r.stop()
        return free
    assert run(go()) <= 128


def test_tracer_concurrent_spans_same_transid_finish_their_own():
    """finish_span(span=...) must close the given span even when a later
    concurrent span sits above it on the per-transid stack."""
    from openwhisk_tpu.utils.tracing import Tracer
    from tests.span_buffer import BufferReporter

    rep = BufferReporter()
    tr = Tracer(reporter=rep)
    tid = TransactionId()
    a = tr.start_span("invoke_a", tid)
    b = tr.start_span("invoke_b", tid)  # interleaved concurrent invoke
    tr.finish_span(tid, {"action": "a"}, span=a)  # a finishes FIRST
    tr.finish_span(tid, {"action": "b"}, span=b)
    by_name = {s.name: s for s in rep.spans}
    assert by_name["invoke_a"].tags["action"] == "a"
    assert by_name["invoke_b"].tags["action"] == "b"
    assert not tr._stacks  # fully drained


def test_attachment_conflict_loser_cannot_corrupt_winner_code():
    """Concurrent action updates: the losing writer's attachment bytes must
    never be paired with the winning writer's document (per-put names)."""
    from openwhisk_tpu.core.entity import WhiskAction
    from openwhisk_tpu.core.entity.exec import CodeExec
    from openwhisk_tpu.core.entity.names import EntityName as EN
    from openwhisk_tpu.database import MemoryArtifactStore
    from openwhisk_tpu.database.entities import EntityStore
    from openwhisk_tpu.database.store import DocumentConflict

    big_a = "def main(x): return {'who': 'A'}\n" + "#" * 70_000
    big_b = "def main(x): return {'who': 'B'}\n" + "#" * 70_000

    async def go():
        es = EntityStore(MemoryArtifactStore(), cache=None)
        mk = lambda code: WhiskAction(EntityPath("ns"), EN("act"),
                                      CodeExec(kind="python:3", code=code))
        first = mk(big_a)
        await es.put(first)                       # rev 1, code A
        winner = mk(big_a.replace("'A'", "'A2'"))
        winner.rev = first.rev
        loser = mk(big_b)
        loser.rev = first.rev
        await es.put(winner)                      # rev 2, code A2
        with pytest.raises(DocumentConflict):
            await es.put(loser)                   # stale rev: must lose
        got = await es.get(WhiskAction, "ns/act", use_cache=False)
        return got.exec.code

    code = run(go())
    assert "'A2'" in code and "'B'" not in code  # winner doc ↔ winner code


def test_from_latest_subscriber_keeps_topic_backlog_for_queue_groups():
    """A from_latest consumer (health stream) must not destroy the
    pre-subscription backlog retained for a later queue-semantics group."""
    async def go():
        bus = MemoryMessagingProvider()
        prod = bus.get_producer()
        await prod.send("t", b"retained-1")
        await prod.send("t", b"retained-2")
        bus.get_consumer("t", "stream", from_latest=True)  # must not eat backlog
        queue_consumer = bus.get_consumer("t", "workers")
        got = await queue_consumer.peek(10, timeout=0.05)
        return [payload for _, _, _, payload in got]
    assert run(go()) == [b"retained-1", b"retained-2"]


def test_peek_survives_retention_resize_while_waiting():
    """set_max_messages swaps the group deque; a consumer parked in peek()
    must still see messages appended to the replacement deque."""
    async def go():
        bus = MemoryMessagingProvider()
        consumer = bus.get_consumer("t", "g")
        prod = bus.get_producer()

        async def resize_then_send():
            await asyncio.sleep(0.02)
            bus.bus.topic("t").set_max_messages(16)
            await prod.send("t", b"after-resize")

        task = asyncio.ensure_future(resize_then_send())
        got = await consumer.peek(1, timeout=2.0)
        await task
        return got
    got = run(go())
    assert [p for _, _, _, p in got] == [b"after-resize"]


def test_deploy_limits_keys_normalized_and_validated():
    from openwhisk_tpu.tools.deploy import _config_env
    env = _config_env({"limits": {"invocations_per_minute": 120}})
    assert env == {"CONFIG_whisk_limits_invocationsPerMinute": "120"}
    with pytest.raises(ValueError):
        _config_env({"limits": {"invocationsPerHour": 9}})


def test_actionproxy_reinit_drops_previous_zip_from_sys_path():
    import base64
    import io
    import sys
    import zipfile

    from openwhisk_tpu.containerpool import actionproxy

    def zip_b64(helper_body: str, main_body: str) -> str:
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w") as z:
            z.writestr("helper.py", helper_body)
            z.writestr("__main__.py", main_body)
        return base64.b64encode(buf.getvalue()).decode()

    main_src = "import helper\ndef main(args):\n    return {'v': helper.VALUE}\n"
    saved = actionproxy._state.get("workdir")
    try:
        fn1 = actionproxy._compile_binary_action(zip_b64("VALUE = 1", main_src), "main")
        assert fn1({}) == {"v": 1}
        first_dir = actionproxy._state["workdir"]
        fn2 = actionproxy._compile_binary_action(zip_b64("VALUE = 2", main_src), "main")
        assert fn2({}) == {"v": 2}  # stale helper module must not shadow
        assert first_dir not in sys.path
    finally:
        wd = actionproxy._state.get("workdir")
        if wd and wd in sys.path:
            sys.path.remove(wd)
        actionproxy._state["workdir"] = saved
        sys.modules.pop("helper", None)


def test_actionproxy_failed_reinit_leaves_previous_action_working():
    """A re-init whose zip does not compile must not break the installed
    action: its modules, path entry, and workdir survive the failure."""
    import base64
    import io
    import os
    import sys
    import zipfile

    from openwhisk_tpu.containerpool import actionproxy

    def zip_b64(files: dict) -> str:
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w") as z:
            for name, body in files.items():
                z.writestr(name, body)
        return base64.b64encode(buf.getvalue()).decode()

    good = zip_b64({"helper.py": "VALUE = 7",
                    "__main__.py": "import helper\n"
                                   "def main(args):\n"
                                   "    import helper as h\n"
                                   "    return {'v': h.VALUE}\n"})
    bad = zip_b64({"__main__.py": "not_main = 1\n"})  # no callable main
    saved = actionproxy._state.get("workdir")
    try:
        fn = actionproxy._compile_binary_action(good, "main")
        assert fn({}) == {"v": 7}
        good_dir = actionproxy._state["workdir"]
        with pytest.raises(ValueError):
            actionproxy._compile_binary_action(bad, "main")
        assert actionproxy._state["workdir"] == good_dir
        assert good_dir in sys.path and os.path.isdir(good_dir)
        assert fn({}) == {"v": 7}  # helper import still resolves
    finally:
        wd = actionproxy._state.get("workdir")
        if wd and wd in sys.path:
            sys.path.remove(wd)
        actionproxy._state["workdir"] = saved
        sys.modules.pop("helper", None)


def test_invoker_executes_routed_revision_not_stale_cache():
    """An invoker whose EntityStore cache holds rev-1 of an action must reload
    when the ActivationMessage routes rev-2 (ref InvokerReactive.scala:244-258:
    the fetch is revision-keyed; a warm fleet must never keep executing deleted
    code). Before the fix, each standalone invoker had a private cache with no
    invalidation wiring, so updated actions never took effect."""
    from openwhisk_tpu.core.entity import CodeExec, WhiskAction
    from openwhisk_tpu.database.entities import EntityStore

    async def go():
        st = SqliteArtifactStore()
        es_controller = EntityStore(st)
        es_invoker = EntityStore(st)  # separate cache, as in make_standalone
        a = WhiskAction(EntityPath("ns"), EntityName("a"),
                        CodeExec(kind="python:3", code="v1"))
        rev1 = await es_controller.put(a)
        # warm the invoker-side cache at rev 1
        got1 = await es_invoker.get_action("ns/a", rev=rev1.rev)
        assert got1.exec.code == "v1"
        # controller updates the action -> rev 2
        a2 = await es_controller.get_action("ns/a")
        a2.exec = CodeExec(kind="python:3", code="v2")
        a2.version = a2.version.up_patch()
        rev2 = await es_controller.put(a2)
        # a message routing rev2 must not serve the stale cached rev1
        got2 = await es_invoker.get_action("ns/a", rev=rev2.rev)
        assert got2.exec.code == "v2"
        assert got2.rev.rev == rev2.rev
        # and a rev-less fetch still serves the (now fresh) cache
        got3 = await es_invoker.get_action("ns/a")
        assert got3.exec.code == "v2"
    run(go())


def test_rev_guard_does_not_thrash_on_older_routed_rev():
    """A backlog of old-rev activations draining after an update must be
    served from the (newer) cache, not invalidate it per message; only a
    cached generation OLDER than the routed one reloads."""
    from openwhisk_tpu.database.entities import _rev_older_than

    assert _rev_older_than("1-abc", "2-def") is True
    assert _rev_older_than("2-def", "1-abc") is False   # newer cache: serve
    assert _rev_older_than("2-def", "2-def") is False
    assert _rev_older_than(None, "1-abc") is True
    assert _rev_older_than("garbage", "also-garbage") is True  # conservative reload

    from openwhisk_tpu.core.entity import CodeExec, WhiskAction
    from openwhisk_tpu.database.entities import EntityStore

    async def go():
        st = SqliteArtifactStore()
        es = EntityStore(st)
        a = WhiskAction(EntityPath("ns"), EntityName("b"),
                        CodeExec(kind="python:3", code="v1"))
        rev1 = await es.put(a)
        a2 = await es.get_action("ns/b")
        a2.exec = CodeExec(kind="python:3", code="v2")
        rev2 = await es.put(a2)
        # cache holds rev2; an old-rev message must NOT evict it
        loads = 0
        orig_get = st.get

        async def counting_get(doc_id):
            nonlocal loads
            loads += 1
            return await orig_get(doc_id)

        st.get = counting_get
        got = await es.get_action("ns/b", rev=rev1.rev)
        assert got.exec.code == "v2" and loads == 0
        got = await es.get_action("ns/b", rev=rev2.rev)
        assert got.exec.code == "v2" and loads == 0
    run(go())


def test_device_failure_paths_release_conc_slots():
    """Advisor r4: a device dispatch (or readback) failure must release the
    host-side concurrency slots acquired in publish() — otherwise every
    failed batch permanently leaks refcounts and the zero-refcount invariant
    the soak simulation asserts is violated."""
    from openwhisk_tpu.controller.loadbalancer import (LoadBalancerException,
                                                       TpuBalancer)
    from openwhisk_tpu.core.entity import ControllerInstanceId, Identity
    from tests.test_balancers import _fleet, _ping_all, make_action, make_msg

    async def go():
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          batch_window=0.002, max_batch=8)
        await bal.start()
        invokers, producer = await _fleet(provider, 2)
        await _ping_all(invokers, producer)
        ident = Identity.generate("guest")
        action = make_action("boom", memory=128)

        def explode(*a, **k):
            raise RuntimeError("injected device fault")

        bal._packed_fn = explode
        with pytest.raises(LoadBalancerException):
            await bal.publish(action, make_msg(action, ident, True))
        leaked = sum(bal._slots.refcount.values())
        await bal.close()
        for inv in invokers:
            await inv.stop()
        return leaked

    assert run(go()) == 0


def test_prometheus_label_values_escaped():
    """Advisor r4: label values from user-event bodies (metricName) must not
    corrupt the exposition page — escape backslash, quote, newline."""
    from openwhisk_tpu.utils.logging import MetricEmitter

    m = MetricEmitter()
    m.counter("userevents_total", tags={"metric": 'bad"value\nwith\\stuff'})
    page = m.prometheus_text()
    line = [l for l in page.splitlines() if l.startswith("openwhisk_userevents_total{")][0]
    assert '\n' not in line  # splitlines guarantees it, but the raw value had one
    assert 'bad\\"value\\nwith\\\\stuff' in line


def test_readback_failure_reverses_device_placements():
    """r5 review: when the dispatch succeeds but the host readback fails,
    the batch's placements live on device with no publisher left to release
    them. The balancer must reverse them on device (release fold inverts the
    schedule fold) before freeing the host slots — otherwise a later action
    reusing the slot index inherits phantom concurrency."""
    import numpy as np

    from openwhisk_tpu.controller.loadbalancer import (LoadBalancerException,
                                                       TpuBalancer)
    from openwhisk_tpu.core.entity import ControllerInstanceId, Identity
    from tests.test_balancers import _fleet, _ping_all, make_action, make_msg

    async def go():
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          batch_window=0.002, max_batch=8)
        await bal.start()
        invokers, producer = await _fleet(provider, 2)
        await _ping_all(invokers, producer)
        free0 = np.asarray(bal.state.free_mb).copy()
        conc0 = np.asarray(bal.state.conc_free).copy()

        def poisoned(out):
            raise RuntimeError("device died mid-readback")

        bal._read_back = poisoned
        ident = Identity.generate("guest")
        action = make_action("phantom", memory=256)
        with pytest.raises(LoadBalancerException):
            await bal.publish(action, make_msg(action, ident, True))
        leaked = sum(bal._slots.refcount.values())
        free1 = np.asarray(bal.state.free_mb).copy()
        conc1 = np.asarray(bal.state.conc_free).copy()
        await bal.close()
        for inv in invokers:
            await inv.stop()
        return leaked, (free0 == free1).all(), (conc0 == conc1).all()

    leaked, free_ok, conc_ok = run(go())
    assert leaked == 0
    assert free_ok and conc_ok


def test_cancelled_publisher_releases_capacity():
    """r5 review: a publish() cancelled while awaiting placement (client
    disconnect) must not leak its host conc slot nor the device capacity the
    schedule fold reserved for it."""
    import numpy as np

    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.core.entity import ControllerInstanceId, Identity
    from tests.test_balancers import _fleet, _ping_all, make_action, make_msg

    async def go():
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          batch_window=0.002, max_batch=8)
        await bal.start()
        invokers, producer = await _fleet(provider, 2)
        await _ping_all(invokers, producer)
        free0 = np.asarray(bal.state.free_mb).copy()
        conc0 = np.asarray(bal.state.conc_free).copy()
        ident = Identity.generate("guest")
        action = make_action("gone", memory=256)
        task = asyncio.get_event_loop().create_task(
            bal.publish(action, make_msg(action, ident, True)))
        await asyncio.sleep(0)  # let publish enqueue into _pending
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        # the batch still dispatches; the abandoned release then drains
        for _ in range(100):
            await asyncio.sleep(0.01)
            if (sum(bal._slots.refcount.values()) == 0
                    and (np.asarray(bal.state.free_mb) == free0).all()):
                break
        leaked = sum(bal._slots.refcount.values())
        free1 = np.asarray(bal.state.free_mb).copy()
        conc1 = np.asarray(bal.state.conc_free).copy()
        await bal.close()
        for inv in invokers:
            await inv.stop()
        return leaked, (free0 == free1).all(), (conc0 == conc1).all()

    leaked, free_ok, conc_ok = run(go())
    assert leaked == 0
    assert free_ok and conc_ok


def test_auto_kernel_outgrow_swaps_to_xla():
    """r5 review: with kernel="auto" (the new default) a balancer whose
    state outgrows the pallas VMEM budget must still swap to the XLA
    kernels — the guard keys on kernel_resolved, not the literal "pallas"
    constructor argument."""
    from openwhisk_tpu.controller.loadbalancer import TpuBalancer
    from openwhisk_tpu.core.entity import ControllerInstanceId

    bal = TpuBalancer(MemoryMessagingProvider(), ControllerInstanceId("0"),
                      action_slots=4096, initial_pad=64)
    assert bal.kernel == "auto"
    # simulate the auto policy having resolved pallas (as on real TPU —
    # on the CPU test backend auto resolves xla, so force the state the
    # guard must handle)
    bal.kernel_resolved = "pallas"
    bal._grow_padding(1024)  # (4096+2)*1024*4 bytes >> the 8 MiB budget
    assert bal.kernel_resolved == "xla"
    # the swap honors the placement-kernel knob: auto resolves the
    # per-bucket scan/repair hybrid on the XLA path (PR 5)
    assert bal.placement_kernel_resolved == "repair"
    assert getattr(bal._sched_fn, "_placement_hybrid", False)
    assert getattr(bal._release_fn, "_placement_hybrid", False)
