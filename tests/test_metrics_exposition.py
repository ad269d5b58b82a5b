"""Prometheus text-exposition validator over a live /metrics page.

Fetches the controller's /metrics with a TpuBalancer placing real
activations (so the page carries counters, gauges, summaries with quantile
lines AND the telemetry plane's device-accumulated histogram families) and
checks every line against the exposition-format grammar: TYPE lines, metric
name / label name charsets, label-value escaping, and — for histogram
families — strictly increasing `le` bounds, monotone non-decreasing
cumulative bucket counts, and a `+Inf` bucket equal to `_count`.
"""
import asyncio
import base64
import re

import aiohttp

from openwhisk_tpu.controller.loadbalancer import TpuBalancer
from openwhisk_tpu.core.entity import (ControllerInstanceId, Identity,
                                       WhiskAuthRecord)
from openwhisk_tpu.messaging import MemoryMessagingProvider
from tests.test_balancers import _fleet, _ping_all, make_action, make_msg

PORT = 13379

_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+"
    r"(-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|[+-]Inf|NaN)$")


def parse_labels(body: str) -> dict:
    """Parse a label block body ('a="x",b="y"') honoring \\\\, \\" and \\n
    escapes — a hand parser, because naive comma-splitting breaks on
    escaped quotes inside values."""
    labels = {}
    i = 0
    while i < len(body):
        eq = body.index("=", i)
        name = body[i:eq]
        assert body[eq + 1] == '"', f"unquoted label value near {body[i:]}"
        j = eq + 2
        val = []
        while body[j] != '"':
            if body[j] == "\\":
                assert body[j + 1] in ('\\', '"', 'n'), \
                    f"bad escape \\{body[j + 1]}"
                val.append({"\\": "\\", '"': '"', "n": "\n"}[body[j + 1]])
                j += 2
            else:
                assert body[j] != "\n"
                val.append(body[j])
                j += 1
        labels[name] = "".join(val)
        i = j + 1
        if i < len(body):
            assert body[i] == ",", f"expected ',' near {body[i:]}"
            i += 1
    return labels


def validate_exposition(text: str) -> dict:
    """Full-grammar pass over one exposition page. Returns
    {family: type} plus the parsed histogram groups for extra checks."""
    types = {}
    samples = []  # (name, labels, value)
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = re.match(r"^# (TYPE|HELP) ([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\s+(.*))?$",
                         line)
            assert m, f"malformed comment line: {line!r}"
            if m.group(1) == "TYPE":
                fam, kind = m.group(2), (m.group(3) or "").strip()
                assert kind in ("counter", "gauge", "histogram", "summary",
                                "untyped"), line
                assert fam not in types, f"duplicate TYPE for {fam}"
                types[fam] = kind
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, label_body, value = m.groups()
        assert _NAME.match(name), name
        labels = parse_labels(label_body) if label_body else {}
        for ln in labels:
            assert _LABEL_NAME.match(ln), ln
        samples.append((name, labels, float(value)))

    # every sample belongs to a declared family (TYPE precedes samples in
    # this exposition: emitters declare per family before rendering)
    def family_of(name):
        if name in types:
            return name
        for suffix in ("_bucket", "_sum", "_count", "_total"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                return name[: -len(suffix)]
        return None

    for name, labels, _ in samples:
        fam = family_of(name)
        assert fam is not None, f"sample {name} has no TYPE line"
        if "quantile" in labels:
            assert types[fam] == "summary", (name, types[fam])
        if "le" in labels:
            assert types[fam] == "histogram", (name, types[fam])

    # histogram semantics: per-series monotone cumulative le buckets,
    # +Inf present and equal to _count
    hist = {}
    counts = {}
    for name, labels, value in samples:
        fam = family_of(name)
        if types.get(fam) != "histogram":
            continue
        key_labels = tuple(sorted((k, v) for k, v in labels.items()
                                  if k != "le"))
        if name.endswith("_bucket"):
            le = labels["le"]
            hist.setdefault((fam, key_labels), []).append(
                (float("inf") if le == "+Inf" else float(le), value))
        elif name.endswith("_count"):
            counts[(fam, key_labels)] = value
    assert hist, "no histogram families on the page"
    for key, buckets in hist.items():
        les = [b[0] for b in buckets]
        assert les == sorted(les) and len(set(les)) == len(les), \
            f"le bounds not strictly increasing for {key}"
        assert les[-1] == float("inf"), f"missing +Inf bucket for {key}"
        cums = [b[1] for b in buckets]
        assert all(a <= b for a, b in zip(cums, cums[1:])), \
            f"cumulative counts not monotone for {key}: {cums}"
        assert key in counts and counts[key] == cums[-1], \
            f"+Inf bucket != _count for {key}"
    return {"types": types, "histograms": hist}


class TestExpositionFormat:
    def test_unit_validator_rejects_garbage(self):
        import pytest
        with pytest.raises(AssertionError):
            validate_exposition("bad-metric-name 1\n")
        with pytest.raises(AssertionError):
            validate_exposition(
                "# TYPE f histogram\n"
                'f_bucket{le="1"} 5\nf_bucket{le="+Inf"} 3\nf_count 3\n')

    def test_live_metrics_page_is_valid(self, tmp_path):
        from openwhisk_tpu.controller.core import Controller

        async def go():
            from openwhisk_tpu.controller.loadbalancer.journal import \
                PlacementJournal
            from openwhisk_tpu.utils.hostprof import GLOBAL_HOST_OBSERVATORY
            from openwhisk_tpu.utils.logging import NullLogging
            # the host observatory's families (ISSUE 11) must render on
            # the same page: Controller.start() installs it on this loop
            GLOBAL_HOST_OBSERVATORY.enabled = True
            GLOBAL_HOST_OBSERVATORY.reset()
            provider = MemoryMessagingProvider()
            # share one emitter between balancer and controller, the way
            # the production assemblies wire it (metrics=logger.metrics) —
            # that is what puts the telemetry renderer on the /metrics page
            logger = NullLogging()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              logger=logger, metrics=logger.metrics,
                              managed_fraction=1.0, blackbox_fraction=0.0)
            # the HA plane's families (ISSUE 9): a live journal + an
            # adopted leadership epoch must render on the same page
            bal.attach_journal(PlacementJournal(str(tmp_path / "wal")))
            bal.set_leadership(2, True)
            controller = Controller(ControllerInstanceId("0"), provider,
                                    logger=logger, load_balancer=bal)
            ident = Identity.generate("guest")
            await controller.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            await controller.start(port=PORT)
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            try:
                action = make_action("exposed", memory=128)
                msgs = [make_msg(action, ident, True) for _ in range(8)]
                # waterfall contexts so the stage-duration family renders
                # (production opens them in the REST handler; this test
                # publishes straight into the balancer)
                from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL
                GLOBAL_WATERFALL.enabled = True
                GLOBAL_WATERFALL.reset()
                for m in msgs:
                    GLOBAL_WATERFALL.begin(m.activation_id.asString)
                await asyncio.gather(*[await bal.publish(action, m)
                                       for m in msgs])
                await asyncio.sleep(0.3)
                bal.telemetry.device_fold()
                bal.telemetry.tick(bal.metrics)  # slo_* gauges on the page
                # journal gauges normally ride the supervision tick;
                # refresh them deterministically for the scrape
                bal.journal.flush()
                bal.journal.export_gauges(bal.metrics)
                # anomaly plane: two ticks (the device path harvests its
                # scores one tick late), then inject a synthetic firing
                # alert so all three new families render. Alert evaluation
                # is frozen afterwards so a racing supervision tick cannot
                # resolve the injected instance before the scrape.
                bal.anomaly.tick(bal.metrics)
                bal.anomaly.tick(bal.metrics)
                from openwhisk_tpu.controller.loadbalancer import \
                    AlertsConfig
                lbl = ((("invoker", "invoker0"),), 99.0)
                now = __import__("time").monotonic()
                bal.anomaly.engine.evaluate(now, {"straggler": [lbl]})
                bal.anomaly.engine.evaluate(now + 31, {"straggler": [lbl]})
                bal.anomaly.alerts_config = AlertsConfig(enabled=False)
                # tracing health gauges normally ride the supervision
                # tick; refresh them deterministically for the scrape
                from openwhisk_tpu.utils.tracing import \
                    export_tracing_gauges
                export_tracing_gauges(bal.metrics)
                # the trace observatory's counters (ISSUE 18) ride the
                # same page via the balancer's registered renderer: one
                # deterministic keep + one drop so both families render
                from openwhisk_tpu.utils.tracestore import \
                    GLOBAL_TRACE_STORE
                GLOBAL_TRACE_STORE.reset()
                GLOBAL_TRACE_STORE.complete("probe0", "feedbeef", 5.0,
                                            forced=True)
                GLOBAL_TRACE_STORE.complete("probe1", "feedbee1", 0.0)
                # HBM gauges: the CPU backend has no memory_stats, so feed
                # the guarded reader a canned answer — this validates the
                # loadbalancer_hbm_* family names against the grammar
                bal.profiler.memory_stats = lambda: {
                    "bytes_in_use": 1 << 20, "bytes_limit": 1 << 30}
                bal.profiler.refresh_memory(bal.metrics)
                # a value that needs label escaping must not corrupt a line
                bal.metrics.counter("exposition_escape_probe",
                                    tags={"metric": 'a"b\\c\nd'})
                # host observatory: force a GC pause so the per-generation
                # family has a row (lag ticks + serde counters accumulated
                # during the publishes above)
                import gc as _gc
                _gc.collect()
                await asyncio.sleep(0.1)  # a few probe ticks post-collect
                async with aiohttp.ClientSession() as s:
                    async with s.get(
                            f"http://127.0.0.1:{PORT}/metrics") as r:
                        return r.status, await r.text()
            finally:
                from openwhisk_tpu.utils.tracestore import \
                    GLOBAL_TRACE_STORE
                GLOBAL_TRACE_STORE.reset()
                GLOBAL_TRACE_STORE.detach()
                await controller.stop()
                for inv in invokers:
                    await inv.stop()

        status, text = asyncio.run(go())
        assert status == 200
        out = validate_exposition(text)
        types = out["types"]
        # the whole catalog rides one page: counters, gauges, summaries,
        # and the telemetry plane's REAL histogram families
        assert types["openwhisk_loadbalancer_activations_published"] == "counter"
        assert types["openwhisk_slo_burn_rate_1m"] == "gauge"
        assert types["openwhisk_loadbalancer_tpu_readback_ms"] == "summary"
        assert types[
            "openwhisk_invoker_activation_latency_seconds"] == "histogram"
        assert types[
            "openwhisk_namespace_activation_latency_seconds"] == "histogram"
        assert types[
            "openwhisk_invoker_activation_outcomes_total"] == "counter"
        # quantile lines present for summaries (satellite)
        assert 'quantile="0.99"' in text
        # at least one histogram series accumulated the 8 activations
        fam_groups = [k for k in out["histograms"]
                      if k[0] == "openwhisk_namespace_activation_latency_seconds"]
        assert fam_groups, "no namespace latency series rendered"
        # the kernel profiling plane's families (ISSUE 3): per-phase
        # device timing as a REAL histogram family, the tagged recompile
        # counter, and the HBM watermark gauges
        assert types[
            "openwhisk_loadbalancer_phase_duration_seconds"] == "histogram"
        phase_groups = {dict(k[1]).get("phase") for k in out["histograms"]
                        if k[0] ==
                        "openwhisk_loadbalancer_phase_duration_seconds"}
        assert {"assembly", "dispatch", "readback"} <= phase_groups
        assert types[
            "openwhisk_loadbalancer_kernel_recompiles_total"] == "counter"
        assert 'openwhisk_loadbalancer_kernel_recompiles_total' \
            '{expected="true"}' in text
        assert types["openwhisk_loadbalancer_hbm_bytes_in_use"] == "gauge"
        assert types["openwhisk_loadbalancer_hbm_utilization_ratio"] == "gauge"
        # the kernel-backend info gauge (ISSUE 10): one live series naming
        # the running backend + placement algorithm + how they were chosen
        assert types["openwhisk_loadbalancer_kernel_backend"] == "gauge"
        backend_series = [ln for ln in text.splitlines() if ln.startswith(
            "openwhisk_loadbalancer_kernel_backend{")]
        assert backend_series
        assert all('backend="' in ln and 'placement="' in ln
                   and 'chosen_by="' in ln for ln in backend_series)
        assert any(ln.endswith(" 1") for ln in backend_series)
        # the anomaly & alerting plane's families (ISSUE 4)
        assert types[
            "openwhisk_loadbalancer_invoker_anomaly_score"] == "gauge"
        score_series = [ln for ln in text.splitlines() if ln.startswith(
            "openwhisk_loadbalancer_invoker_anomaly_score{")]
        assert score_series and all('signal="' in ln for ln in score_series)
        assert types["openwhisk_alerts_firing"] == "gauge"
        assert ('openwhisk_alerts_firing{alertname="straggler",'
                'severity="warning"} 1') in text
        assert types["openwhisk_alert_transitions_total"] == "counter"
        assert ('openwhisk_alert_transitions_total{alertname="straggler",'
                'transition="firing"} 1') in text
        # tracing health gauges (satellite: orphan finishes are visible)
        assert types["openwhisk_tracing_orphan_finishes"] == "gauge"
        # the trace observatory's tail-sampling verdict counters
        # (ISSUE 18) ride the page via the balancer's registered renderer
        assert types["openwhisk_trace_kept_total"] == "counter"
        assert 'openwhisk_trace_kept_total{reason="forced"} 1' in text
        assert types["openwhisk_trace_dropped_total"] == "counter"
        assert "openwhisk_trace_dropped_total 1" in text
        # the HA plane's families (ISSUE 9): journal durability lag /
        # size / fsync tail + the adopted leadership epoch
        assert types["openwhisk_loadbalancer_journal_lag_batches"] == "gauge"
        assert types["openwhisk_loadbalancer_journal_bytes"] == "gauge"
        assert types[
            "openwhisk_loadbalancer_journal_fsync_p99_ms"] == "gauge"
        assert types["openwhisk_controller_leadership_epoch"] == "gauge"
        assert "openwhisk_controller_leadership_epoch 2" in text
        # the latency-waterfall plane's families (ISSUE 7): per-stage e2e
        # timing as a REAL histogram family — the grammar pass above
        # already proved names, label escaping and monotone cumulative
        # `le` for every histogram on the page, this pins the family in
        assert types[
            "openwhisk_activation_stage_duration_seconds"] == "histogram"
        wf_stages = {dict(k[1]).get("stage") for k in out["histograms"]
                     if k[0] == "openwhisk_activation_stage_duration_seconds"}
        assert {"publish_enqueue", "device_dispatch", "produce",
                "completion_ack"} <= wf_stages
        assert types[
            "openwhisk_activation_dominant_stage_total"] == "counter"
        assert 'openwhisk_activation_dominant_stage_total{scope="all"' \
            in text
        # the host hot-loop observatory's families (ISSUE 11): loop lag
        # as a REAL histogram, per-generation GC pauses, task churn, and
        # the per-hop serde cost counters
        assert types[
            "openwhisk_host_event_loop_lag_seconds"] == "histogram"
        assert 'openwhisk_host_event_loop_lag_seconds_bucket' \
            '{le="1e-06",thread="event_loop"}' in text \
            or 'openwhisk_host_event_loop_lag_seconds_bucket' \
            '{thread="event_loop"' in text
        assert types["openwhisk_host_gc_pause_seconds"] == "histogram"
        gc_series = {dict(k[1]).get("generation")
                     for k in out["histograms"]
                     if k[0] == "openwhisk_host_gc_pause_seconds"}
        assert gc_series, "no gc pause series rendered"
        assert types["openwhisk_host_tasks_created_total"] == "counter"
        assert types["openwhisk_host_tasks_finished_total"] == "counter"
        assert types["openwhisk_host_tasks_active"] == "gauge"
        assert types["openwhisk_host_loop_stalls_total"] == "counter"
        assert types[
            "openwhisk_host_gc_pauses_in_dispatch_total"] == "counter"
        # the boot heap the started balancer froze out of the collector
        # (ISSUE 26): scraped while it serves, so far more than the few
        # hundred objects CPython itself keeps there
        assert types["openwhisk_host_gc_frozen_objects"] == "gauge"
        (frozen,) = [ln for ln in text.splitlines() if ln.startswith(
            "openwhisk_host_gc_frozen_objects ")]
        assert int(frozen.split()[1]) > 10_000
        assert types["openwhisk_host_serde_seconds_total"] == "counter"
        assert types["openwhisk_host_serde_bytes_total"] == "counter"
        serde_lines = [ln for ln in text.splitlines() if ln.startswith(
            "openwhisk_host_serde_seconds_total{")]
        assert serde_lines and all(
            'hop="' in ln and 'direction="' in ln for ln in serde_lines)
        # the publish path serializes ActivationMessages (the coalescing
        # producer's caller-turn encode) — that hop must be on the page
        assert any('hop="activation"' in ln and 'direction="serialize"'
                   in ln for ln in serde_lines)


class TestOpenMetricsExemplars:
    """Satellite: flight-recorder rows that carry a trace context leave a
    `# {trace_id="..."}` exemplar on the matching phase-histogram bucket
    line — but ONLY when the scrape negotiates OpenMetrics (the classic
    text format has no exemplar syntax and its parsers reject one)."""

    PORT = 13381

    def test_exemplar_only_on_openmetrics_scrape(self):
        from openwhisk_tpu.controller.core import Controller

        trace_id = "ab" * 16

        async def go():
            from openwhisk_tpu.utils.logging import NullLogging
            provider = MemoryMessagingProvider()
            logger = NullLogging()
            bal = TpuBalancer(provider, ControllerInstanceId("0"),
                              logger=logger, metrics=logger.metrics,
                              managed_fraction=1.0, blackbox_fraction=0.0)
            controller = Controller(ControllerInstanceId("0"), provider,
                                    logger=logger, load_balancer=bal)
            ident = Identity.generate("guest")
            await controller.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            await controller.start(port=self.PORT)
            invokers, producer = await _fleet(provider, 2)
            await _ping_all(invokers, producer)
            try:
                action = make_action("traced", memory=128)
                msgs = [make_msg(action, ident, True) for _ in range(4)]
                for m in msgs:
                    m.trace_context = {
                        "traceparent": f"00-{trace_id}-{'cd' * 8}-01"}
                await asyncio.gather(*[await bal.publish(action, m)
                                       for m in msgs])
                await asyncio.sleep(0.2)
                out = {}
                async with aiohttp.ClientSession() as s:
                    om_hdrs = {"Accept": "application/openmetrics-text; "
                                         "version=1.0.0"}
                    async with s.get(
                            f"http://127.0.0.1:{self.PORT}/metrics",
                            headers=om_hdrs) as r:
                        out["om"] = (r.content_type, await r.text())
                    async with s.get(
                            f"http://127.0.0.1:{self.PORT}/metrics") as r:
                        out["text"] = (r.content_type, await r.text())
                return out
            finally:
                await controller.stop()
                for inv in invokers:
                    await inv.stop()

        out = asyncio.run(go())
        om_type, om_text = out["om"]
        assert om_type == "application/openmetrics-text"
        assert om_text.endswith("# EOF\n")
        ex_lines = [ln for ln in om_text.splitlines()
                    if f'# {{trace_id="{trace_id}"}}' in ln]
        assert ex_lines, "no exemplar on the OpenMetrics page"
        assert all(
            ln.startswith(
                "openwhisk_loadbalancer_phase_duration_seconds_bucket{")
            for ln in ex_lines)
        # OpenMetrics counter naming: the family is suffix-free, every
        # sample carries `_total` — Prometheus's OM parser rejects the
        # whole page otherwise, so exemplar scraping would lose all
        # metrics instead of adding trace links.
        om_counters = set()
        for ln in om_text.splitlines():
            m = re.match(r"^# TYPE (\S+) counter$", ln)
            if m:
                assert not m.group(1).endswith("_total"), \
                    f"OM counter family keeps _total suffix: {m.group(1)}"
                om_counters.add(m.group(1))
        assert om_counters, "no counter families on the OM page"
        sample_names = {m.group(1) for m in (
            _SAMPLE.match(ln.split(" # {")[0])
            for ln in om_text.splitlines()
            if ln and not ln.startswith("#")) if m}
        for fam in om_counters:
            assert fam + "_total" in sample_names, \
                f"OM counter {fam} has no _total sample"
        # the classic page still types counters by their full sample name
        txt_text = out["text"][1]
        classic_counters = {
            m.group(1) for m in (
                re.match(r"^# TYPE (\S+) counter$", ln)
                for ln in txt_text.splitlines()) if m}
        assert any(c.endswith("_total") for c in classic_counters)
        # exemplar format: `value # {labels} exemplar_value timestamp`
        for ln in ex_lines:
            suffix = ln.split("# {", 1)[1].split("} ", 1)[1]
            ex_val, ex_ts = suffix.split(" ")
            assert float(ex_val) > 0 and float(ex_ts) > 0
        txt_type, txt_text = out["text"]
        assert txt_type == "text/plain"
        assert "# {" not in txt_text and "# EOF" not in txt_text
        # the classic page still passes the full exposition grammar
        validate_exposition(txt_text)


class TestPlacementQualityFamilies:
    """ISSUE 17: the placement-quality plane's three families pass the
    same exposition grammar as the live page — the regret histogram on
    the telemetry bucket grid (strictly increasing `le`, monotone
    cumulative counts, `+Inf` == `_count`), the per-invoker divergence
    counter (with OM `_total` negotiation), and the imbalance gauge."""

    def _plane(self):
        import numpy as np

        from openwhisk_tpu.controller.loadbalancer.quality import (
            QualityConfig, QualityPlane)
        from openwhisk_tpu.ops.decision_quality import (N_SUMMARY,
                                                        S_IMBALANCE_COV,
                                                        S_ROWS,
                                                        init_quality_state)
        qp = QualityPlane(QualityConfig(enabled=True))
        qs = init_quality_state(4, qp.n_buckets, numpy=True)
        qs.regret_hist[0] = 3
        qs.regret_hist[5] = 2
        qs.inv_regret_ms[1] = 12.5
        qs.inv_divergence[1] = 3
        qs.counters[0] = 5
        qp._qstate = qs
        s = np.zeros(N_SUMMARY, np.float32)
        s[S_ROWS] = 5
        s[S_IMBALANCE_COV] = 0.25
        qp.note_summary(s)
        return qp

    def test_families_pass_exposition_grammar(self):
        qp = self._plane()
        # a label value that needs escaping must not corrupt a line
        text = qp.prometheus_text(["inv0", 'inv"one\\two'])
        out = validate_exposition(text)
        types = out["types"]
        assert types[
            "openwhisk_loadbalancer_placement_regret"] == "histogram"
        assert types[
            "openwhisk_loadbalancer_decision_divergence_total"] == "counter"
        assert types["openwhisk_loadbalancer_fleet_imbalance"] == "gauge"
        # the regret histogram accumulated both synthetic rows
        hist = [v for k, v in out["histograms"].items()
                if k[0] == "openwhisk_loadbalancer_placement_regret"]
        assert hist and hist[0][-1] == (float("inf"), 5.0)
        # only the divergent invoker renders a counter row, with its
        # escaped label value intact
        div_lines = [ln for ln in text.splitlines() if ln.startswith(
            "openwhisk_loadbalancer_decision_divergence_total{")]
        assert len(div_lines) == 1
        assert parse_labels(
            div_lines[0].split("{", 1)[1].rsplit("}", 1)[0]
        ) == {"invoker": 'inv"one\\two'}
        assert ('openwhisk_loadbalancer_fleet_imbalance{scope="fleet"} '
                "0.25") in text

    def test_openmetrics_counter_negotiation(self):
        qp = self._plane()
        om = qp.prometheus_text(["inv0", "inv1"], openmetrics=True)
        assert ("# TYPE openwhisk_loadbalancer_decision_divergence "
                "counter") in om
        assert "openwhisk_loadbalancer_decision_divergence_total{" in om

    def test_disabled_plane_renders_nothing(self):
        from openwhisk_tpu.controller.loadbalancer.quality import (
            QualityConfig, QualityPlane)
        qp = QualityPlane(QualityConfig(enabled=False))
        assert qp.prometheus_text(["inv0"]) == ""


class TestOpenMetricsCounterNaming:
    """Unit twin of the live OM-page counter check: both render paths
    (the family helpers and MetricEmitter's own counters) switch to
    suffix-free family names + `_total` samples only when asked for
    OpenMetrics, leaving the classic text format untouched."""

    def test_counter_family_text_negotiates_total_suffix(self):
        from openwhisk_tpu.controller.monitoring import counter_family_text
        rows = [({"a": "b"}, 3)]
        classic = counter_family_text("x_total", rows)
        assert classic[0] == "# TYPE x_total counter"
        assert classic[1] == 'x_total{a="b"} 3'
        om = counter_family_text("x_total", rows, openmetrics=True)
        assert om[0] == "# TYPE x counter"
        assert om[1] == 'x_total{a="b"} 3'
        # a family named without the suffix gains it on the OM page only
        om = counter_family_text("y", rows, openmetrics=True)
        assert om[0] == "# TYPE y counter"
        assert om[1] == 'y_total{a="b"} 3'

    def test_metric_emitter_counters_openmetrics(self):
        from openwhisk_tpu.utils.logging import MetricEmitter
        m = MetricEmitter()
        m.counter("completions_total", 2)
        m.counter("bare", 1, tags={"k": "v"})
        om = m.prometheus_text(openmetrics=True)
        assert "# TYPE openwhisk_completions counter" in om
        assert "openwhisk_completions_total 2" in om
        assert "# TYPE openwhisk_bare counter" in om
        assert 'openwhisk_bare_total{k="v"} 1' in om
        classic = m.prometheus_text()
        assert "# TYPE openwhisk_completions_total counter" in classic
        assert "openwhisk_completions_total 2" in classic
        assert 'openwhisk_bare{k="v"} 1' in classic
        assert "openwhisk_bare_total" not in classic


class TestTraceCounterFamilies:
    """ISSUE 18: the trace observatory's tail-sampling verdict counters
    pass the exposition grammar in both renderings. The store's text is
    pure counters — `validate_exposition` demands at least one histogram
    family per PAGE, which the live-page test above covers by composing
    this renderer with the balancer's — so this class checks the line
    grammar, label values and OM `_total` negotiation directly."""

    def _store(self):
        from openwhisk_tpu.utils.tracestore import (TraceStore,
                                                    TraceTailConfig)
        s = TraceStore(TraceTailConfig(enabled=True, keep_ring=8,
                                       pending_limit=16, keep_floor=0.0))
        s.complete("a0", "t0" * 8, 5.0, forced=True)
        s.complete("a1", "t1" * 8, 5.0, error=True)
        s.complete("a2", "t2" * 8, 5.0, error=True)
        s.complete("a3", "t3" * 8, 0.0)  # clean: dropped
        return s

    def test_classic_grammar(self):
        text = self._store().prometheus_text()
        lines = text.splitlines()
        assert "# TYPE openwhisk_trace_kept_total counter" in lines
        assert "# TYPE openwhisk_trace_dropped_total counter" in lines
        # every sample line matches the exposition sample grammar
        samples = {}
        for ln in lines:
            if ln.startswith("#"):
                continue
            m = _SAMPLE.match(ln)
            assert m, f"malformed sample line: {ln!r}"
            samples[(m.group(1), m.group(2) or "")] = float(m.group(3))
        # reason labels come from the verdict priority list, counts add up
        from openwhisk_tpu.utils.tracestore import REASONS
        kept = {parse_labels(lbl)["reason"]: v
                for (name, lbl), v in samples.items()
                if name == "openwhisk_trace_kept_total"}
        assert set(kept) <= set(REASONS)
        assert kept == {"error": 2.0, "forced": 1.0}
        assert samples[("openwhisk_trace_dropped_total", "")] == 1.0

    def test_openmetrics_counter_negotiation(self):
        om = self._store().prometheus_text(openmetrics=True)
        # OM types the suffix-free base name; samples keep `_total`
        assert "# TYPE openwhisk_trace_kept counter" in om
        assert "# TYPE openwhisk_trace_dropped counter" in om
        assert "openwhisk_trace_kept_total{" in om
        assert "openwhisk_trace_dropped_total 1" in om
        assert "# TYPE openwhisk_trace_kept_total" not in om
        assert "# TYPE openwhisk_trace_dropped_total" not in om

    def test_disabled_store_renders_nothing(self):
        from openwhisk_tpu.utils.tracestore import (TraceStore,
                                                    TraceTailConfig)
        s = TraceStore(TraceTailConfig(enabled=False))
        assert s.prometheus_text() == ""
        assert s.prometheus_text(openmetrics=True) == ""
