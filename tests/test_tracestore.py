"""ISSUE 18: tail-sampled distributed trace observatory.

Covers the acceptance contracts:
  * the completion-time verdict: every keep reason fires on its trigger,
    the counter label follows the REASONS priority order, the uniform
    floor is deterministic 1-in-N, pre-verdict marks are consumed;
  * bounds: the pending table ages out past `pending_limit` (counted),
    the kept ring's `get()` index never returns an evicted entry, and a
    runaway span producer saturates at SPAN_CAP;
  * cross-process assembly: the spilled half pins publish_enqueue to the
    origin's spill_forward, the invoker half pins invoker_pickup to the
    origin's publish_enqueue, anchorless halves fall back to wall-clock
    deltas, spans dedup by id and halves by identity, and every half's
    stage deltas telescope to its own measured total;
  * disabled is a TRUE no-op: attach() never tees the reporter, the
    verdict path allocates NOTHING (tracemalloc-asserted), and the
    /admin/trace* routes answer 404;
  * satellites: Tracer's time-based expiry sweep (the <1000-stacks leak),
    and the ack frames' sparse trace-context column (eager + lazy wire,
    byte-exact absent when no ack is traced).
"""
from __future__ import annotations

import asyncio
import base64
import time
import tracemalloc
from types import SimpleNamespace

import pytest

from openwhisk_tpu.utils.tracestore import (GLOBAL_TRACE_STORE, REASONS,
                                            TraceStore, TraceTailConfig,
                                            _TeeReporter, assemble_trace,
                                            synthetic_span, tail_config)
from openwhisk_tpu.utils.tracing import Tracer, trace_id_of

from tests.span_buffer import BufferReporter
from openwhisk_tpu.utils.waterfall import (N_STAGES, STAGE_API_ACCEPT,
                                           STAGE_COMPLETION_ACK,
                                           STAGE_INVOKER_PICKUP,
                                           STAGE_PUBLISH_ENQUEUE,
                                           STAGE_RUN, STAGE_SPILL_FORWARD)

CTL_PORT = 13461


def _store(**kw) -> TraceStore:
    cfg = {"enabled": True, "keep_ring": 16, "pending_limit": 64,
           "keep_floor": 0.0}
    cfg.update(kw)
    return TraceStore(TraceTailConfig(**cfg))


def _row(aid="a0", tid="t0", times=None, ts=1000.0):
    """A waterfall row from ABSOLUTE stage offsets (µs since t0): the
    deltas telescope by construction, exactly like _compute_row's."""
    deltas = [-1] * N_STAGES
    prev = total = 0
    for i in sorted(times or {}):
        deltas[i] = times[i] - prev
        prev = total = times[i]
    return {"activation_id": aid, "trace_id": tid, "ts": ts,
            "total_us": total, "deltas_us": deltas, "clamped": 0}


# -- the completion-time verdict --------------------------------------------
class TestVerdict:
    def test_error_outranks_everything(self):
        s = _store()
        s.mark("t0", "divergent")
        e = s.complete("a0", "t0", 5000.0, error=True, timeout=True,
                       fenced=True)
        assert e["reason"] == "error"
        # every other trigger still recorded, in priority order
        assert e["reasons"] == ["error", "timeout", "fenced", "divergent",
                                "slow"]
        assert s.kept_total == {"error": 1}

    @pytest.mark.parametrize("kw,reason", [
        ({"timeout": True}, "timeout"),
        ({"forced": True}, "forced"),
        ({"fenced": True}, "fenced"),
        ({"error": True}, "error"),
    ])
    def test_flag_reasons(self, kw, reason):
        s = _store()
        e = s.complete("a0", "t0", 5.0, **kw)
        assert e["reason"] == reason and s.kept_total == {reason: 1}

    def test_spilled_read_off_the_row(self):
        s = _store()
        row = _row(times={STAGE_API_ACCEPT: 50, STAGE_SPILL_FORWARD: 300})
        e = s.complete("a0", "t0", row=row)
        assert e["reason"] == "spilled"
        assert e["waterfall"]["total_us"] == 300

    def test_trace_id_falls_back_to_the_row(self):
        s = _store()
        e = s.complete("a0", None, row=_row(tid="from-row",
                                            times={STAGE_SPILL_FORWARD: 9}))
        assert e["trace_id"] == "from-row"

    def test_marks_are_consumed_by_the_verdict(self):
        s = _store()
        s.mark("t0", "exemplar")
        assert s.complete("a0", "t0", 5.0)["reason"] == "exemplar"
        # same trace id again: the mark is gone, nothing keeps it
        assert s.complete("a1", "t0", 5.0) is None

    def test_slow_against_live_threshold_source(self):
        s = _store()
        s.threshold_source = lambda: 10.0
        assert s.complete("a0", "t0", 11.0)["reason"] == "slow"
        assert s.complete("a1", "t1", 9.0) is None

    def test_broken_threshold_source_falls_back(self):
        s = _store()
        s.threshold_source = lambda: 1 / 0
        assert s.tail_threshold_ms() == s.default_threshold_ms
        assert s.complete("a0", "t0", s.default_threshold_ms + 1.0) \
            is not None

    def test_e2e_falls_back_to_the_row_total(self):
        s = _store()
        s.threshold_source = lambda: 10.0
        e = s.complete("a0", "t0",
                       row=_row(times={STAGE_COMPLETION_ACK: 50_000}))
        assert e["reason"] == "slow" and e["e2e_ms"] == 50.0

    def test_floor_is_deterministic_one_in_n(self):
        s = _store(keep_floor=0.25)
        assert s._floor_every == 4
        kept = [s.complete(f"a{i}", f"t{i}", 1.0) for i in range(100)]
        floor = [e for e in kept if e is not None]
        assert len(floor) == 25
        assert all(e["reason"] == "floor" for e in floor)
        # exactly every 4th completion, not a random 25%
        assert [i for i, e in enumerate(kept) if e] == list(range(3, 100, 4))
        assert s.dropped_total == 75
        assert s.kept_total == {"floor": 25}

    def test_clean_drop_pops_pending_and_counts(self):
        s = _store()
        s._ingest(synthetic_span("t0", "x", 1.0, 2.0))
        assert s.complete("a0", "t0", 1.0) is None
        assert s._pending == {} and s.dropped_total == 1

    def test_reasons_priority_tuple_is_the_contract(self):
        assert REASONS == ("error", "timeout", "fenced", "spilled",
                           "forced", "divergent", "exemplar", "slow",
                           "floor")


# -- bounds ------------------------------------------------------------------
class TestBounds:
    def test_pending_limit_ages_out_oldest(self):
        s = _store(pending_limit=4)
        for i in range(6):
            s._ingest(synthetic_span(f"t{i}", "x", 1.0, 2.0))
        assert len(s._pending) == 4
        assert s.pending_evicted == 2
        assert "t0" not in s._pending and "t5" in s._pending

    def test_span_cap_per_trace(self):
        s = _store()
        for _ in range(TraceStore.SPAN_CAP + 10):
            s._ingest(synthetic_span("t0", "x", 1.0, 2.0))
        assert len(s._pending["t0"]) == TraceStore.SPAN_CAP

    def test_kept_ring_eviction_keeps_get_consistent(self):
        s = _store(keep_ring=8)
        for i in range(12):
            s.complete(f"a{i}", f"t{i}", 5.0, forced=True)
        assert s.get("t0") is None and s.get("t3") is None
        assert s.get("t11")["activation_id"] == "a11"
        # the by-id index never outgrows the ring
        assert len(s._by_id) <= 8

    def test_get_returns_the_latest_keep_for_a_trace_id(self):
        s = _store()
        s.complete("a0", "t0", 5.0, forced=True)
        s.complete("a1", "t0", 5.0, fenced=True)
        assert s.get("t0")["activation_id"] == "a1"

    def test_entries_oldest_first_and_list_filters(self):
        s = _store()
        s.complete("a0", "t0", 5.0, forced=True)
        s.complete("a1", "t1", 5.0, fenced=True)
        assert [e["trace_id"] for e in s.entries()] == ["t0", "t1"]
        out = s.list(reason="fenced")
        assert [e["trace_id"] for e in out] == ["t1"]
        assert s.list()[0]["trace_id"] == "t1"  # newest first


# -- tee lifecycle -----------------------------------------------------------
class TestTeeLifecycle:
    def test_attach_tees_and_detach_restores(self):
        t = Tracer()
        inner = t.reporter
        s = _store()
        s.attach(t)
        assert isinstance(t.reporter, _TeeReporter)
        assert t.reporter.inner is inner
        assert s.active
        s.attach(t)  # idempotent: never double-wraps
        assert t.reporter.inner is inner
        s.emit(synthetic_span("t0", "x", 1.0, 2.0))
        assert len(s._pending["t0"]) == 1
        assert inner.sent_spans == 1  # the sink still sees every span
        s.detach()
        assert t.reporter is inner and not s.active

    def test_finished_tracer_spans_reach_the_pending_table(self):
        t = Tracer()
        s = _store()
        s.attach(t)
        transid = SimpleNamespace(id="tx1")
        span = t.start_span("op", transid)
        t.finish_span(transid, span=span)
        assert [sp.span_id for sp in s._pending[span.trace_id]] \
            == [span.span_id]
        s.detach()


# -- disabled = TRUE no-op ---------------------------------------------------
class TestDisabledNoop:
    def test_attach_never_wraps_when_disabled(self):
        t = Tracer()
        inner = t.reporter
        s = _store(enabled=False)
        s.attach(t)
        assert t.reporter is inner and not s.active

    def test_verdict_path_allocates_nothing(self):
        s = _store(enabled=False)
        row = _row(times={STAGE_COMPLETION_ACK: 500})
        s.complete("a0", "t0", 5.0, row=row)  # warm the code path
        s.mark("t0", "forced")
        import openwhisk_tpu.utils.tracestore as ts_mod
        filt = (tracemalloc.Filter(True, ts_mod.__file__),)
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            for _ in range(300):
                s.complete("a0", "t0", 5.0, row=row)
                s.mark("t0", "forced")
                s.force("t0")
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        stats = after.filter_traces(filt).compare_to(
            before.filter_traces(filt), "lineno")
        assert sum(st.size_diff for st in stats) <= 0, stats
        assert s._seen == 0 and s._pending == {} and s._marks == {}

    def test_prometheus_text_empty_when_disabled(self):
        assert _store(enabled=False).prometheus_text() == ""

    def test_env_off_switch(self, monkeypatch):
        monkeypatch.setenv("CONFIG_whisk_tracing_tail_enabled", "false")
        assert tail_config().enabled is False


# -- exposition --------------------------------------------------------------
class TestExposition:
    def test_counters_render_with_reason_labels(self):
        s = _store()
        s.complete("a0", "t0", 5.0, forced=True)
        s.complete("a1", "t1", 5.0, error=True)
        s.complete("a2", "t2", 5.0)
        text = s.prometheus_text()
        assert '# TYPE openwhisk_trace_kept_total counter' in text
        assert 'openwhisk_trace_kept_total{reason="forced"} 1' in text
        assert 'openwhisk_trace_kept_total{reason="error"} 1' in text
        assert 'openwhisk_trace_dropped_total 1' in text
        om = s.prometheus_text(openmetrics=True)
        # OM types the base name; samples keep the _total suffix
        assert '# TYPE openwhisk_trace_kept counter' in om
        assert '# TYPE openwhisk_trace_dropped counter' in om
        assert 'openwhisk_trace_dropped_total 1' in om


# -- cross-process assembly --------------------------------------------------
def _half(tid="t0", aid="a0", instance=0, role="controller", times=None,
          ts=1000.0, spans=(), reasons=("floor",), placement=None):
    return {"trace_id": tid, "activation_id": aid, "ts": ts,
            "reason": reasons[0], "reasons": list(reasons),
            "e2e_ms": None,
            "identity": {"instance": instance, "pid": 1, "role": role},
            "spans": list(spans),
            "waterfall": _row(aid=aid, tid=tid, times=times, ts=ts),
            "placement": placement, "quality": None}


class TestAssembly:
    def test_empty_is_found_false(self):
        out = assemble_trace("t0", [], members_missing=[2, 1])
        assert out["found"] is False and out["members_missing"] == [1, 2]

    def test_spilled_half_pins_to_the_spill_forward_stamp(self):
        origin = _half(times={STAGE_API_ACCEPT: 50,
                              STAGE_SPILL_FORWARD: 300},
                       reasons=("spilled",))
        peer = _half(aid="a0", instance=1, ts=1000.7,
                     times={STAGE_PUBLISH_ENQUEUE: 10,
                            STAGE_COMPLETION_ACK: 500},
                     reasons=("fenced",))
        out = assemble_trace("t0", [origin, peer])
        assert out["found"] and out["processes"] == ["controller0",
                                                     "controller1"]
        assert sorted(out["reasons"]) == ["fenced", "spilled"]
        groups = {g["name"]: g for g in out["root"]["children"]}
        # peer t0 sits at origin's spill stamp minus its own enqueue
        assert groups["proc:controller1"]["start_us"] == 300 - 10
        # the tree telescopes past the origin's own total
        assert out["e2e_us"] == (300 - 10) + 500

    def test_invoker_half_pins_to_publish_enqueue(self):
        origin = _half(times={STAGE_API_ACCEPT: 50,
                              STAGE_PUBLISH_ENQUEUE: 200,
                              STAGE_COMPLETION_ACK: 900})
        inv = _half(instance=5, role="invoker", ts=1000.4,
                    times={STAGE_INVOKER_PICKUP: 20, STAGE_RUN: 400})
        out = assemble_trace("t0", [origin, inv])
        groups = {g["name"]: g for g in out["root"]["children"]}
        assert groups["proc:invoker5"]["start_us"] == 200 - 20

    def test_anchorless_half_falls_back_to_wall_clock(self):
        origin = _half(times={STAGE_API_ACCEPT: 100_000}, ts=1000.0)
        other = _half(instance=1, ts=1000.5,
                      times={STAGE_RUN: 20_000})
        out = assemble_trace("t0", [origin, other])
        groups = {g["name"]: g for g in out["root"]["children"]}
        # (ts delta) + origin total - half total
        assert groups["proc:controller1"]["start_us"] == \
            500_000 + 100_000 - 20_000

    def test_each_halfs_stage_deltas_telescope(self):
        times = {STAGE_API_ACCEPT: 50, STAGE_PUBLISH_ENQUEUE: 200,
                 STAGE_COMPLETION_ACK: 900}
        out = assemble_trace("t0", [_half(times=times)])
        (group,) = out["root"]["children"]
        stages = [n for n in group["children"]
                  if n["name"].startswith("stage:")]
        assert sum(n["duration_us"] for n in stages) == 900
        assert group["duration_us"] == 900

    def test_spans_dedup_by_id_and_halves_by_identity(self):
        sp = synthetic_span("t0", "spill_forward", 1000.0, 1000.0,
                            tags={"proc": "controller0"}).to_json()
        h = _half(times={STAGE_API_ACCEPT: 50}, spans=[sp])
        out = assemble_trace("t0", [h, dict(h)])
        assert len(out["root"]["children"]) == 1  # one proc group
        (group,) = out["root"]["children"]
        names = [n["name"] for n in group["children"]]
        assert names.count("spill_forward") == 1

    def test_span_proc_tags_extend_the_process_set(self):
        sp = synthetic_span("t0", "invoker_run", 1000.0, 1000.1,
                            tags={"proc": "invoker3"}).to_json()
        out = assemble_trace(
            "t0", [_half(times={STAGE_API_ACCEPT: 50}, spans=[sp])])
        assert out["processes"] == ["controller0", "invoker3"]

    def test_device_dispatch_stage_carries_the_batch_join(self):
        out = assemble_trace("t0", [_half(
            times={STAGE_API_ACCEPT: 10, STAGE_COMPLETION_ACK: 500},
            placement={"seq": 7, "kernel": "xla", "trace_id": "tb"})])
        # placement join rides the device_dispatch stage only; this row
        # has none, so no stage carries batch tags
        (group,) = out["root"]["children"]
        assert all(not n["tags"] for n in group["children"])
        out2 = assemble_trace("t1", [_half(
            times={STAGE_API_ACCEPT: 10, 6: 300, STAGE_COMPLETION_ACK: 500},
            placement={"seq": 7, "kernel": "xla", "trace_id": "tb"})])
        (group2,) = out2["root"]["children"]
        tags = {n["name"]: n["tags"] for n in group2["children"]}
        assert tags["stage:device_dispatch"]["batch_seq"] == 7
        assert tags["stage:device_dispatch"]["kernel"] == "xla"


# -- admin read side ---------------------------------------------------------
class TestAdminEndpoints:
    def _hdrs(self, ident):
        return {"Authorization": "Basic " + base64.b64encode(
            ident.authkey.compact.encode()).decode()}

    def _controller(self):
        from openwhisk_tpu.controller.core import Controller
        from openwhisk_tpu.controller.loadbalancer.lean import LeanBalancer
        from openwhisk_tpu.core.entity import (ControllerInstanceId,
                                               Identity, MB)
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        from openwhisk_tpu.utils.logging import NullLogging

        async def noop_factory(invoker_id, provider):
            class _Stub:
                async def stop(self):
                    pass
            return _Stub()

        logger = NullLogging()
        provider = MemoryMessagingProvider()
        lb = LeanBalancer(provider, ControllerInstanceId("0"), noop_factory,
                          logger=logger, metrics=logger.metrics,
                          user_memory=MB(512))
        c = Controller(ControllerInstanceId("0"), provider, logger=logger,
                       load_balancer=lb)
        return c, Identity.generate("guest")

    def test_disabled_plane_404s_and_enabled_answers(self):
        import aiohttp
        from openwhisk_tpu.core.entity import WhiskAuthRecord

        store = GLOBAL_TRACE_STORE
        was_enabled, was_cfg = store.enabled, store.config

        async def go():
            c, ident = self._controller()
            await c.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            await c.start(port=CTL_PORT)
            out = {}
            try:
                base = f"http://127.0.0.1:{CTL_PORT}"
                async with aiohttp.ClientSession() as s:
                    # auth gate first: unauthenticated is 401, not 404
                    async with s.get(f"{base}/admin/traces") as r:
                        out["unauth"] = r.status
                    store.enabled = False
                    for key, path in (("list", "/admin/traces"),
                                      ("local", "/admin/trace/local/ff"),
                                      ("asm", "/admin/trace/ff")):
                        async with s.get(base + path,
                                         headers=self._hdrs(ident)) as r:
                            out[f"off_{key}"] = r.status
                    store.enabled = True
                    store.reset()
                    store.complete("a0", "aa11", 5.0, forced=True)
                    async with s.get(f"{base}/admin/trace/local/aa11",
                                     headers=self._hdrs(ident)) as r:
                        out["local"] = (r.status, await r.json())
                    async with s.get(f"{base}/admin/trace/local/none",
                                     headers=self._hdrs(ident)) as r:
                        out["local_miss"] = (r.status, await r.json())
                    async with s.get(
                            f"{base}/admin/traces?reason=forced",
                            headers=self._hdrs(ident)) as r:
                        out["list"] = (r.status, await r.json())
                    async with s.get(f"{base}/admin/trace/aa11",
                                     headers=self._hdrs(ident)) as r:
                        out["asm"] = (r.status, await r.json())
            finally:
                await c.stop()
            return out

        try:
            out = asyncio.run(go())
        finally:
            GLOBAL_TRACE_STORE.enabled = was_enabled
            GLOBAL_TRACE_STORE.config = was_cfg
            GLOBAL_TRACE_STORE.reset()
        assert out["unauth"] == 401
        assert out["off_list"] == out["off_local"] == out["off_asm"] == 404
        status, body = out["local"]
        assert status == 200 and body["found"] is True
        assert body["entry"]["activation_id"] == "a0"
        status, body = out["local_miss"]
        # a live peer that never kept the trace is NOT a missing member
        assert status == 200 and body["found"] is False
        status, body = out["list"]
        assert status == 200
        assert [t["trace_id"] for t in body["traces"]] == ["aa11"]
        assert body["stats"]["kept_total"] == {"forced": 1}
        status, body = out["asm"]
        assert status == 200 and body["found"] is True
        assert body["trace_id"] == "aa11"


# -- satellite: tracer expiry ------------------------------------------------
class TestTracerExpiry:
    def test_small_abandoned_populations_age_out(self):
        # the regression: fewer than 1000 abandoned stacks used to linger
        # forever (only the size trigger swept)
        t = Tracer(expiry_seconds=0.05)
        for i in range(5):
            t.start_span("s", SimpleNamespace(id=f"tx{i}"))
        assert len(t._stacks) == 5
        time.sleep(0.12)
        t.start_span("s", SimpleNamespace(id="fresh"))
        assert set(t._stacks) == {"fresh"}
        assert set(t._touched) == {"fresh"}

    def test_live_stacks_survive_the_sweep(self):
        t = Tracer(expiry_seconds=10.0)
        t._sweep_interval = 0.01
        t.start_span("s", SimpleNamespace(id="tx0"))
        time.sleep(0.02)
        t.start_span("s", SimpleNamespace(id="tx1"))
        assert set(t._stacks) == {"tx0", "tx1"}


# -- satellite: ack frames carry trace context -------------------------------
class TestAckTraceContext:
    def _fixtures(self):
        from openwhisk_tpu.core.entity import (ActivationId,
                                               ActivationResponse,
                                               ControllerInstanceId,
                                               EntityPath, Identity,
                                               InvokerInstanceId, MB,
                                               WhiskActivation)
        from openwhisk_tpu.core.entity.names import FullyQualifiedEntityName
        from openwhisk_tpu.messaging.message import (
            CombinedCompletionAndResultMessage, CompletionMessage)
        from openwhisk_tpu.utils.transaction import TransactionId
        ident = Identity.generate("guest")
        inv = InvokerInstanceId(0, user_memory=MB(512))
        name = FullyQualifiedEntityName.parse("guest/act0").name
        now = time.time()

        def combined(tc=None):
            aid = ActivationId.generate()
            act = WhiskActivation(EntityPath("guest"), name,
                                  ident.subject, aid, now, now,
                                  ActivationResponse.success({"ok": True}),
                                  duration=1)
            ack = CombinedCompletionAndResultMessage(TransactionId(), act,
                                                     inv)
            ack.trace_context = tc
            return ack

        def completion(tc=None):
            ack = CompletionMessage(TransactionId(),
                                    ActivationId.generate(), False, inv)
            ack.trace_context = tc
            return ack

        return combined, completion

    def test_serial_ack_roundtrip_and_absent_when_none(self):
        import json
        from openwhisk_tpu.messaging.message import parse_ack
        combined, completion = self._fixtures()
        tc = {"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"}
        traced = combined(tc)
        out = parse_ack(traced.serialize())
        assert out.trace_context == tc
        assert trace_id_of(out.trace_context) == "ab" * 16
        bare = completion(None)
        assert "traceContext" not in json.loads(bare.serialize())
        assert parse_ack(bare.serialize()).trace_context is None

    def test_eager_batch_sparse_column_roundtrip(self):
        from openwhisk_tpu.messaging.columnar import (KIND_ACK, make_batch,
                                                      parse_batch)
        combined, completion = self._fixtures()
        tc = {"traceparent": "00-" + "11" * 16 + "-" + "22" * 8 + "-01"}
        acks = [completion(None), combined(tc), completion(None)]
        raw = make_batch(KIND_ACK, acks).serialize()
        _kind, out = parse_batch(raw)
        assert [m.trace_context for m in out] == [None, tc, None]
        # untraced frames never grow the column: no sparse section at all
        untraced = make_batch(
            KIND_ACK, [completion(None), combined(None)]).serialize()
        assert b"trace" not in untraced
        assert [m.trace_context for m in parse_batch(untraced)[1]] == \
            [None, None]

    def test_lazy_batch_header_carries_the_column(self):
        """A lone traced ack is a 1-row frame: its context rides the
        sparse section, its response stays unparsed."""
        import json
        from openwhisk_tpu.messaging.columnar import (KIND_ACK, make_batch,
                                                      parse_batch)
        combined, completion = self._fixtures()
        tc = {"traceparent": "00-" + "33" * 16 + "-" + "44" * 8 + "-01"}
        raw = make_batch(KIND_ACK, [combined(tc)]).serialize()
        _kind, out = parse_batch(raw)
        assert [m.trace_context for m in out] == [tc]
        assert not out[0].activation.materialized
        # the traced ack's response survives the lazy wire untouched
        assert out[0].activation.response.result == {"ok": True}
        sparse = json.dumps({"trace": {"0": tc}},
                            separators=(",", ":")).encode()
        assert sparse in raw
        untraced = make_batch(KIND_ACK, [completion(None)]).serialize()
        assert b"trace" not in untraced


# -- satellite: ring-shaped span buffer (regression companion) ---------------
class TestBufferReporterRing:
    def test_newest_spans_survive_saturation(self):
        rep = BufferReporter(max_spans=4)
        for i in range(10):
            rep.report(synthetic_span("t", f"s{i}", 1.0, 2.0))
        assert [s.name for s in rep.spans] == ["s6", "s7", "s8", "s9"]
        assert rep.sent_spans == 10 and rep.dropped_spans == 6
