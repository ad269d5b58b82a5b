"""On-device fleet telemetry + SLO burn-rate plane (ISSUE 2).

Covers: exact log2 bucket assignment and device/NumPy accumulator parity;
invoker-axis growth preserving counts; the namespace shared-overflow tail;
the TelemetryPlane's burn-rate windows, budget math and SLO report (incl.
per-namespace overrides); all three balancers feeding one telemetry surface
through the base-class hook; the `/admin/slo` endpoint (auth, JSON shape);
config off-switch; and the satellite fixes (readback RTT gauge, summary
quantile exposition, honest sliding-window percentiles, BufferReporter
drop counting).
"""
import asyncio
import base64
import time

import aiohttp
import numpy as np
import pytest

from openwhisk_tpu.controller.loadbalancer import (LeanBalancer,
                                                   ShardingBalancer,
                                                   SloConfig,
                                                   TelemetryConfig,
                                                   TelemetryPlane,
                                                   TpuBalancer)
from openwhisk_tpu.core.entity import (ControllerInstanceId, Identity,
                                       WhiskAuthRecord)
from openwhisk_tpu.messaging import MemoryMessagingProvider
from openwhisk_tpu.ops.telemetry import (DeviceLatencyAccumulator,
                                         NumpyLatencyAccumulator,
                                         OUTCOME_ERROR, OUTCOME_SUCCESS,
                                         OUTCOME_TIMEOUT, bucket_bounds_ms,
                                         bucket_of_us)
from tests.test_balancers import _fleet, _ping_all, make_action, make_msg


class TestBucketMath:
    def test_exact_log2_assignment(self):
        # bounds (ms): 1, 2, 4, 8, ... — a 4.000 ms sample must land in
        # le=4 exactly, never a neighbour via float rounding
        assert list(bucket_of_us([1, 1000, 1001, 2000, 4000, 4001], 8)) == \
            [0, 0, 1, 1, 2, 3]
        assert bucket_bounds_ms(6) == [1.0, 2.0, 4.0, 8.0, 16.0]

    def test_overflow_bucket(self):
        # past the last finite bound everything lands in the +Inf bucket
        b = bucket_of_us([10 ** 9], 8)
        assert b[0] == 7

    def test_device_matches_numpy(self):
        rows = [(1, 3, 4000, OUTCOME_SUCCESS), (1, 3, 5000, OUTCOME_ERROR),
                (5, 2, 100, OUTCOME_TIMEOUT), (0, 0, 2 ** 31 - 1,
                                               OUTCOME_SUCCESS)]
        ev = np.zeros((5, 8), np.int32)
        ev[:4, : len(rows)] = np.asarray(rows, np.int32).T
        ev[4, : len(rows)] = 1
        d = DeviceLatencyAccumulator(2, 16, 24)
        n = NumpyLatencyAccumulator(2, 16, 24)
        d.fold(ev)
        n.fold(ev)
        dc, nc = d.counts(), n.counts()
        for f in dc:
            assert np.allclose(dc[f], nc[f]), f

    def test_growth_preserves_counts(self):
        for acc in (NumpyLatencyAccumulator(2, 8, 8),
                    DeviceLatencyAccumulator(2, 8, 8)):
            ev = np.zeros((5, 8), np.int32)
            ev[:4, 0] = [1, 0, 3000, OUTCOME_SUCCESS]
            ev[4, 0] = 1
            acc.fold(ev)
            acc.ensure_invokers(9)   # -> 16 rows
            c = acc.counts()
            assert c["inv_buckets"].shape[0] == 16
            assert c["inv_buckets"][1, 2] == 1
            assert c["inv_outcomes"][1, OUTCOME_SUCCESS] == 1


class TestTelemetryPlane:
    def _plane(self, **slo):
        return TelemetryPlane(
            TelemetryConfig(buckets=10, namespaces=8,
                            shared_namespace_buckets=2),
            SloConfig(**slo))

    def test_ns_overflow_shared_tail(self):
        tp = self._plane()
        dedicated = tp.n_namespaces - tp.shared_tail
        slots = {f"ns{i}": tp._ns_slot(f"ns{i}") for i in range(12)}
        assert sorted(set(slots[f"ns{i}"] for i in range(dedicated))) == \
            list(range(dedicated))
        # overflow namespaces hash into the tail, never a dedicated row
        for i in range(dedicated, 12):
            assert slots[f"ns{i}"] >= dedicated
            assert tp._ns_label(slots[f"ns{i}"]).startswith("~shared")

    def test_slo_report_compliance_and_overrides(self):
        tp = self._plane(e2e_p99_ms=8.0, error_ratio=0.1,
                         overrides={"tenantB": {"e2e_p99_ms": 1.0}})
        for _ in range(99):
            tp.observe(0, "tenantA", 3.0, OUTCOME_SUCCESS)
        tp.observe(0, "tenantA", 900.0, OUTCOME_ERROR)
        for _ in range(10):
            tp.observe(1, "tenantB", 3.0, OUTCOME_SUCCESS)
        rep = tp.slo_report(["invoker0", "invoker1"])
        g = rep["global"]
        assert g["count"] == 110
        assert g["p99_le_ms"] == 4.0 and g["latency_compliant"] is True
        assert g["error_ratio_compliant"] is True and g["compliant"] is True
        by_ns = {n["namespace"]: n for n in rep["namespaces"]}
        # tenantB's override (1 ms) makes its 3 ms p99 non-compliant while
        # the global 8 ms target passes
        assert by_ns["tenantB"]["latency_target_ms"] == 1.0
        assert by_ns["tenantB"]["latency_compliant"] is False
        assert by_ns["tenantA"]["compliant"] is True
        by_inv = {i["invoker"]: i for i in rep["invokers"]}
        assert by_inv["invoker0"]["count"] == 100
        assert by_inv["invoker1"]["count"] == 10

    def test_target_judged_at_bucket_granularity(self):
        # a 1000 ms target with log2 bounds (…512, 1024…) is judged at
        # le=1024: a fleet whose p99 lands in that bucket (e.g. true p99
        # 600 ms) must NOT be flagged as violating
        tp = TelemetryPlane(TelemetryConfig(buckets=14, namespaces=8,
                                            shared_namespace_buckets=2),
                            SloConfig(e2e_p99_ms=1000.0))
        for _ in range(10):
            tp.observe(0, "ns", 600.0, OUTCOME_SUCCESS)
        g = tp.slo_report()["global"]
        assert g["p99_le_ms"] == 1024.0
        assert g["latency_target_le_ms"] == 1024.0
        assert g["latency_compliant"] is True

    def test_latency_in_overflow_bucket_is_noncompliant(self):
        tp = self._plane(e2e_p99_ms=10_000.0)
        # 10 buckets -> last finite bound 256 ms; p99 beyond it reports None
        for _ in range(10):
            tp.observe(0, "ns", 10_000.0, OUTCOME_SUCCESS)
        g = tp.slo_report()["global"]
        assert g["p99_le_ms"] is None
        assert g["latency_compliant"] is False

    def test_burn_rates_and_budget(self):
        tp = self._plane(error_ratio=0.1)
        t0 = time.monotonic()
        for _ in range(90):
            tp.observe(0, "ns", 1.0, OUTCOME_SUCCESS)
        for _ in range(10):
            tp.observe(0, "ns", 1.0, OUTCOME_ERROR)
        vals = tp.tick(now=t0 + 2.0)
        # 10% errors against a 10% target: burning exactly the budget
        assert vals["slo_burn_rate_1m"] == pytest.approx(1.0)
        assert vals["slo_error_budget_remaining"] == pytest.approx(0.0)
        # a clean follow-up minute decays the fast window to zero
        for _ in range(100):
            tp.observe(0, "ns", 1.0, OUTCOME_SUCCESS)
        vals = tp.tick(now=t0 + 100.0)
        assert vals["slo_burn_rate_1m"] == 0.0
        assert vals["slo_burn_rate_10m"] > 0.0  # slow window still sees them

    def test_disabled_plane_is_inert(self):
        tp = TelemetryPlane(TelemetryConfig(enabled=False))
        tp.observe(0, "ns", 1.0, OUTCOME_SUCCESS)
        assert tp.prometheus_text() == ""
        assert tp.slo_report() == {"enabled": False}
        assert tp.tick() == {}

    def test_from_env_config(self, monkeypatch):
        monkeypatch.setenv("CONFIG_whisk_telemetry_enabled", "false")
        monkeypatch.setenv("CONFIG_whisk_telemetry_buckets", "12")
        monkeypatch.setenv("CONFIG_whisk_slo_e2eP99Ms", "123")
        monkeypatch.setenv("CONFIG_whisk_slo_errorRatio", "0.005")
        monkeypatch.setenv("CONFIG_whisk_slo_overrides",
                           '{"guest": {"e2e_p99_ms": 9}}')
        tp = TelemetryPlane.from_config()
        assert tp.enabled is False
        assert tp.config.buckets == 12
        assert tp.slo.e2e_p99_ms == 123.0
        assert tp.slo.error_ratio == 0.005
        assert tp.slo.overrides["guest"]["e2e_p99_ms"] == 9


class TestBalancersFeedOneSurface:
    def test_tpu_balancer_device_accumulator(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("telem", memory=128)
            msgs = [make_msg(action, ident, True) for _ in range(6)]
            await asyncio.gather(*[await bal.publish(action, m)
                                   for m in msgs])
            await asyncio.sleep(0.3)
            bal.telemetry.device_fold()
            rep = bal.telemetry.slo_report(bal._telemetry_invoker_names())
            text = bal.metrics.prometheus_text()
            rtt = bal.metrics.gauge_value("loadbalancer_readback_rtt_ms")
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return rep, text, rtt

        rep, text, rtt = asyncio.run(go())
        assert rep["kernel"] == "device"
        assert rep["global"]["count"] == 6
        assert rep["global"]["outcomes"]["success"] == 6
        assert "openwhisk_invoker_activation_latency_seconds_bucket" in text
        assert 'le="+Inf"' in text
        assert 'outcome="success"' in text
        # satellite: the eager/batched dispatch regime is operator-visible
        assert rtt is not None and rtt > 0

    def test_sharding_balancer_numpy_twin(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = ShardingBalancer(provider, ControllerInstanceId("0"),
                                   managed_fraction=1.0,
                                   blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("telemcpu", memory=128)
            msgs = [make_msg(action, ident, True) for _ in range(4)]
            await asyncio.gather(*[await bal.publish(action, m)
                                   for m in msgs])
            await asyncio.sleep(0.2)
            rep = bal.telemetry.slo_report(bal._telemetry_invoker_names())
            text = bal.metrics.prometheus_text()
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return rep, text

        rep, text = asyncio.run(go())
        assert rep["kernel"] == "cpu"
        assert rep["global"]["count"] == 4
        assert "openwhisk_namespace_activation_latency_seconds_count" in text

    def test_lean_balancer_and_timeout_outcome(self):
        async def go():
            provider = MemoryMessagingProvider()

            class _DummyInvoker:
                async def stop(self):
                    pass

            async def factory(invoker_id, messaging_provider):
                return _DummyInvoker()

            bal = LeanBalancer(provider, ControllerInstanceId("0"), factory)
            await bal.start()
            ident = Identity.generate("guest")
            action = make_action("leantelem", memory=128)
            m1 = make_msg(action, ident, False)
            m2 = make_msg(action, ident, False)
            await bal.publish(action, m1)
            await bal.publish(action, m2)
            # complete one regularly, force-timeout the other
            bal.process_completion(m1.activation_id, forced=False,
                                   is_system_error=False,
                                   invoker=bal.invoker_id)
            bal.process_completion(m2.activation_id, forced=True,
                                   is_system_error=False,
                                   invoker=bal.invoker_id)
            rep = bal.telemetry.slo_report(bal._telemetry_invoker_names())
            await bal.close()
            return rep

        rep = asyncio.run(go())
        g = rep["global"]
        assert g["count"] == 2
        assert g["outcomes"] == {"success": 1, "error": 0, "timeout": 1}
        # forced timeouts burn the error budget
        assert g["error_ratio"] == pytest.approx(0.5)
        assert rep["invokers"][0]["invoker"] == "invoker0"

    def test_disabled_telemetry_records_nothing(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            bal.telemetry.enabled = False
            await bal.start()
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("dark", memory=128)
            msg = make_msg(action, ident, True)
            await (await bal.publish(action, msg))
            await asyncio.sleep(0.2)
            rep = bal.telemetry.slo_report()
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return rep

        assert asyncio.run(go()) == {"enabled": False}


PORT = 13378


class TestSloEndpoint:
    def _run(self, scenario):
        from openwhisk_tpu.controller.core import Controller

        async def go():
            provider = MemoryMessagingProvider()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              managed_fraction=1.0, blackbox_fraction=0.0)
            controller = Controller(ControllerInstanceId("0"), provider,
                                    load_balancer=bal)
            ident = Identity.generate("guest")
            await controller.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            await controller.start(port=PORT)
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            hdrs = {"Authorization": "Basic " + base64.b64encode(
                ident.authkey.compact.encode()).decode()}
            try:
                async with aiohttp.ClientSession() as s:
                    return await scenario(bal, ident, s, hdrs)
            finally:
                await controller.stop()
                for inv in invokers:
                    await inv.stop()

        return asyncio.run(go())

    def test_auth_required(self):
        async def scenario(bal, ident, s, hdrs):
            async with s.get(f"http://127.0.0.1:{PORT}/admin/slo") as r:
                return r.status

        assert self._run(scenario) == 401

    def test_report_shape_under_live_balancer(self):
        async def scenario(bal, ident, s, hdrs):
            action = make_action("sloseen", memory=128)
            msgs = [make_msg(action, ident, True) for _ in range(5)]
            await asyncio.gather(*[await bal.publish(action, m)
                                   for m in msgs])
            await asyncio.sleep(0.3)
            bal.telemetry.device_fold()
            async with s.get(f"http://127.0.0.1:{PORT}/admin/slo",
                             headers=hdrs) as r:
                return r.status, await r.json()

        status, rep = self._run(scenario)
        assert status == 200
        assert rep["enabled"] is True and rep["kernel"] == "device"
        assert {"targets", "windows_s", "buckets_le_ms", "global",
                "namespaces", "invokers"} <= set(rep)
        assert rep["global"]["count"] == 5
        assert rep["targets"]["e2e_p99_ms"] == 1000.0
        assert all(i["invoker"].startswith("invoker")
                   for i in rep["invokers"])


class TestSatellites:
    def test_summary_exposition_has_quantiles(self):
        from openwhisk_tpu.utils.logging import MetricEmitter
        m = MetricEmitter()
        for v in range(1, 101):
            m.histogram("loadbalancer_tpu_readback_ms", float(v))
            m.histogram("userevents_duration_ms", float(v),
                        tags={"action": "guest/a"})
        text = m.prometheus_text()
        assert ('openwhisk_loadbalancer_tpu_readback_ms'
                '{quantile="0.5"} ') in text
        assert ('openwhisk_loadbalancer_tpu_readback_ms'
                '{quantile="0.99"} ') in text
        # labelled series merge the quantile label into the label set
        assert ('openwhisk_userevents_duration_ms'
                '{action="guest/a",quantile="0.5"} ') in text
        assert "openwhisk_userevents_duration_ms_count{" in text

    def test_histogram_window_is_honest_sliding_window(self):
        from openwhisk_tpu.utils.logging import MetricEmitter
        m = MetricEmitter()
        n = MetricEmitter.WINDOW + 10
        for v in range(n):
            m.histogram("h", float(v))
        st = m.histogram_stats("h")
        assert st["count"] == n          # lifetime count
        # the window holds exactly the LAST `WINDOW` samples: the 10 oldest
        # were overwritten in arrival order by the write cursor
        window = m._hist[("h", ())][4]
        assert sorted(window)[0] == 10.0
        assert max(window) == float(n - 1)
        assert len(window) == MetricEmitter.WINDOW

    def test_closed_balancer_stops_rendering(self):
        async def go():
            provider = MemoryMessagingProvider()
            bal = ShardingBalancer(provider, ControllerInstanceId("0"),
                                   managed_fraction=1.0,
                                   blackbox_fraction=0.0)
            await bal.start()
            invokers, producer = await _fleet(provider, 1)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("gone", memory=128)
            msg = make_msg(action, ident, True)
            await (await bal.publish(action, msg))
            await asyncio.sleep(0.2)
            before = bal.metrics.prometheus_text()
            await bal.close()
            after = bal.metrics.prometheus_text()
            for inv in invokers:
                await inv.stop()
            return before, after

        before, after = asyncio.run(go())
        fam = "openwhisk_invoker_activation_latency_seconds"
        assert fam in before
        # a closed balancer must not keep contributing families to a
        # shared emitter (duplicate TYPE lines are an invalid exposition)
        assert fam not in after

    def test_buffer_reporter_counts_drops(self):
        # ring retention (ISSUE 18): a full buffer evicts the OLDEST span
        # — the newest spans are the ones a debugging session wants, and
        # the old behavior (drop new, keep stale) made the buffer useless
        # after the first `max_spans` reports. sent counts every report
        # that reached the buffer; dropped counts the evictions.
        from openwhisk_tpu.utils.tracing import Span
        from tests.span_buffer import BufferReporter
        rep = BufferReporter(max_spans=2)
        for i in range(5):
            rep.report(Span("t", f"s{i}", None, "op", 0.0, end=1.0))
        assert len(rep.spans) == 2
        assert [s.span_id for s in rep.spans] == ["s3", "s4"]
        assert rep.sent_spans == 5
        assert rep.dropped_spans == 3
