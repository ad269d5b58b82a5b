"""Aux subsystem tests: tracing, user events, blacklist, attachments,
file activation storage, admin CLI, balancer snapshot/restore."""
import asyncio
import json
import os

import pytest

from openwhisk_tpu.core.entity import (ActivationId, ActivationResponse,
                                       CodeExec, ControllerInstanceId,
                                       EntityName, EntityPath, Identity,
                                       InvokerInstanceId, MB, Parameters,
                                       Subject, UserLimits, WhiskAction,
                                       WhiskActivation, WhiskAuthRecord)
from openwhisk_tpu.core.entity.parameters import ParameterValue
from openwhisk_tpu.database import (AuthStore, EntityStore, MemoryArtifactStore,
                                    SqliteArtifactStore)
from openwhisk_tpu.database.file_activation_store import (
    ArtifactWithFileStorageActivationStore)
from openwhisk_tpu.invoker.blacklist import NamespaceBlacklist
from openwhisk_tpu.messaging import EventMessage, MemoryMessagingProvider
from openwhisk_tpu.controller.monitoring import UserEventsRecorder
from openwhisk_tpu.utils.tracing import CountingReporter, Tracer
from openwhisk_tpu.utils.transaction import TransactionId

from tests.span_buffer import BufferReporter


def run(coro):
    return asyncio.run(coro)


class TestTracing:
    def test_span_hierarchy_and_report(self):
        tracer = Tracer(BufferReporter())
        tid = TransactionId()
        parent = tracer.start_span("controller_activation", tid)
        child = tracer.start_span("loadbalancer_schedule", tid)
        assert child.trace_id == parent.trace_id
        assert child.parent_id == parent.span_id
        tracer.finish_span(tid)
        tracer.finish_span(tid, tags={"action": "ns/a"})
        spans = tracer.reporter.spans
        assert [s.name for s in spans] == ["loadbalancer_schedule",
                                           "controller_activation"]
        assert spans[1].tags["action"] == "ns/a"

    def test_the_default_sink_counts_spans_and_keeps_none(self):
        tracer = Tracer()
        assert isinstance(tracer.reporter, CountingReporter)
        for i in range(3):
            tid = TransactionId()
            tracer.start_span("controller_activation", tid)
            tracer.finish_span(tid, tags={"n": str(i)})
        assert vars(tracer.reporter) == {"sent_spans": 3}   # nothing kept

    def test_context_survives_the_bus(self):
        t_controller, t_invoker = Tracer(), Tracer()
        tid = TransactionId()
        span = t_controller.start_span("controller_activation", tid)
        ctx = t_controller.get_trace_context(tid)
        assert ctx and "traceparent" in ctx
        # invoker side: restore and open a child
        remote_tid = TransactionId(tid.id)
        t_invoker.set_trace_context(remote_tid, ctx)
        child = t_invoker.start_span("invoker_run", remote_tid)
        assert child.trace_id == span.trace_id  # one distributed trace


class TestUserEvents:
    def test_activation_and_metric_events_recorded(self):
        async def go():
            provider = MemoryMessagingProvider()
            rec = UserEventsRecorder(provider)
            rec.start()
            prod = provider.get_producer()
            act = WhiskActivation(EntityPath("guest"), EntityName("hello"),
                                  Subject("guest-user"), ActivationId.generate(),
                                  1.0, 2.0, ActivationResponse.success({}),
                                  duration=42)
            await prod.send("events", EventMessage.for_activation(
                "invoker0", act, "uuid-1", kind="python:3", init_time=7))
            await prod.send("events", EventMessage.for_metric(
                "controller", "ConcurrentRateLimit", 1, "guest-user", "guest",
                "uuid-1"))
            await asyncio.sleep(0.15)
            text = rec.prometheus_text()
            await rec.stop()
            return text

        text = run(go())
        assert ('openwhisk_userevents_activations_total'
                '{action="guest/hello"} 1') in text
        assert ('openwhisk_userevents_cold_starts_total'
                '{action="guest/hello"} 1') in text
        assert ('openwhisk_userevents_rate_limit_total'
                '{metric="ConcurrentRateLimit",namespace="guest"} 1') in text


class TestBlacklist:
    def test_blocked_and_zero_limit_namespaces(self):
        async def go():
            store = AuthStore(MemoryArtifactStore())
            ok = Identity.generate("goodns")
            await store.put(WhiskAuthRecord(ok.subject, [ok.namespace],
                                            [ok.authkey]))
            blocked = Identity.generate("badns")
            await store.put(WhiskAuthRecord(blocked.subject, [blocked.namespace],
                                            [blocked.authkey], blocked=True))
            zero = Identity.generate("zerons")
            rec = WhiskAuthRecord(zero.subject, [zero.namespace], [zero.authkey],
                                  limits={"zerons": UserLimits(
                                      concurrent_invocations=0)})
            await store.put(rec)
            bl = NamespaceBlacklist(store)
            await bl.refresh()
            zero_with_limits = rec.identities()[0]
            return (bl.is_blacklisted(ok), bl.is_blacklisted(blocked),
                    bl.is_blacklisted(zero_with_limits), len(bl))

        ok, blocked, zero, n = run(go())
        assert not ok and blocked and zero
        assert n == 2


class TestCodeAttachments:
    def test_large_code_roundtrips_via_attachment(self):
        async def go():
            raw = MemoryArtifactStore()
            es = EntityStore(raw)
            big_code = "def main(a):\n    return {'x': 1}\n" + "#" * (80 * 1024)
            action = WhiskAction(EntityPath("guest"), EntityName("big"),
                                 CodeExec(kind="python:3", code=big_code))
            await es.put(action)
            # raw doc must NOT inline the code
            doc = await raw.get("guest/big")
            assert isinstance(doc["exec"]["code"], dict)
            ct, data = await raw.read_attachment(
                "guest/big", doc["exec"]["code"]["attachmentName"])
            assert len(data) == len(big_code.encode())
            # fresh store (cold cache) inflates transparently
            es2 = EntityStore(raw)
            got = await es2.get_action("guest/big")
            return got.exec.code == big_code

        assert run(go())


class TestFileActivationStore:
    def test_records_appended_as_ndjson(self, tmp_path):
        async def go():
            path = str(tmp_path / "activations.log")
            st = ArtifactWithFileStorageActivationStore(
                MemoryArtifactStore(), path, write_logs_to_artifact=False)
            act = WhiskActivation(EntityPath("guest"), EntityName("hello"),
                                  Subject("guest-user"), ActivationId.generate(),
                                  1.0, 2.0, ActivationResponse.success({"r": 1}),
                                  logs=["stdout: x"], duration=5)
            await st.store(act)
            stored = await st.get("guest", act.activation_id)
            lines = [json.loads(l) for l in open(path)]
            return stored, lines

        stored, lines = run(go())
        assert stored.logs == []          # logs stripped from the artifact
        assert len(lines) == 1
        assert lines[0]["logs"] == ["stdout: x"]  # ...but shipped to the file


class TestAdminCli:
    def test_user_lifecycle_and_limits(self, tmp_path, capsys):
        from openwhisk_tpu.tools import wskadmin
        db = str(tmp_path / "admin.db")
        assert wskadmin.main(["--db", db, "user", "create", "alice"]) == 0
        auth_line = capsys.readouterr().out.strip()
        assert ":" in auth_line
        assert wskadmin.main(["--db", db, "user", "list"]) == 0
        assert "alice" in capsys.readouterr().out
        assert wskadmin.main(["--db", db, "limits", "set", "alice",
                              "--invocations-per-minute", "5"]) == 0
        capsys.readouterr()
        assert wskadmin.main(["--db", db, "limits", "get", "alice"]) == 0
        assert json.loads(capsys.readouterr().out)["invocationsPerMinute"] == 5
        assert wskadmin.main(["--db", db, "user", "block", "alice"]) == 0
        capsys.readouterr()
        assert wskadmin.main(["--db", db, "user", "list"]) == 0
        assert "(blocked)" in capsys.readouterr().out

    def test_limits_flow_into_identity(self, tmp_path):
        from openwhisk_tpu.tools import wskadmin
        db = str(tmp_path / "admin2.db")
        wskadmin.main(["--db", db, "user", "create", "bobby"])
        wskadmin.main(["--db", db, "limits", "set", "bobby",
                       "--concurrent-invocations", "3"])

        async def go():
            store = AuthStore(SqliteArtifactStore(db))
            ident = await store.identity_by_namespace("bobby")
            return ident.limits.concurrent_invocations

        assert run(go()) == 3


class TestBalancerSnapshot:
    def test_snapshot_restore_roundtrip(self):
        async def go():
            from openwhisk_tpu.controller.loadbalancer import TpuBalancer
            from tests.test_balancers import (SimInvoker, _fleet, _ping_all,
                                              make_action, make_msg)
            import numpy as np
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 4, delay=5.0)  # holds stay
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("snapme", memory=256)
            await bal.publish(action, make_msg(action, ident, True))
            snap = bal.snapshot()
            # restore into a brand-new balancer
            bal2 = TpuBalancer(provider, ControllerInstanceId("0"),
                               managed_fraction=1.0, blackbox_fraction=0.0)
            bal2.restore(snap)
            same_free = (np.asarray(bal2.state.free_mb).tolist() ==
                         np.asarray(bal.state.free_mb).tolist())
            same_reg = [i.instance for i in bal2._registry] == \
                [i.instance for i in bal._registry]
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return same_free, same_reg, json.dumps(snap) is not None

        same_free, same_reg, serializable = run(go())
        assert same_free and same_reg and serializable
