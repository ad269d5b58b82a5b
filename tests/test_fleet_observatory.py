"""Fleet observatory (ISSUE 16): federation merge math, the causal event
log, peer-directory announcements, and the federation endpoints.

The merge invariant the property tests pin: per-process log2 bucket
counts summed bucket-wise equal the histogram a single process would
have built from the pooled samples — bucketing is per-sample and
bucket-wise integer addition is exact. The off-switch contract: disabled
is a TRUE no-op — heartbeats and pings byte-exact with pre-16 payloads,
fleet endpoints 404.
"""
import asyncio
import base64
import json
import random
import re
import time

import pytest

from openwhisk_tpu.controller.monitoring import (PHASE_MARKS,
                                                 join_spill_rows,
                                                 merge_serialized_counters,
                                                 merged_host_report,
                                                 merged_metrics,
                                                 merged_slo_report,
                                                 merged_timeline,
                                                 merged_waterfall_report,
                                                 metrics_raw,
                                                 reconstruct_phases)
from openwhisk_tpu.utils.eventlog import (EventLog, GLOBAL_EVENT_LOG,
                                          fleet_config, identity,
                                          reset_identity, set_identity)
from openwhisk_tpu.utils.waterfall import (ActivationWaterfall, N_STAGES,
                                           STAGE_API_ACCEPT,
                                           STAGE_COMPLETION_ACK,
                                           STAGE_INVOKER_PICKUP,
                                           STAGE_PUBLISH_ENQUEUE,
                                           STAGE_RECORD_WRITE, STAGE_RUN,
                                           STAGE_SPILL_FORWARD,
                                           WaterfallConfig)

_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


# -- identity & event log --------------------------------------------------
class TestIdentityAndEventLog:
    def teardown_method(self):
        reset_identity()

    def test_identity_block_shape_and_live_pid(self):
        import os
        set_identity(instance=3, role="controller",
                     partitions_fn=lambda: [5, 1])
        ident = identity()
        assert ident == {"instance": 3, "pid": os.getpid(),
                         "role": "controller", "partitions": [1, 5]}

    def test_identity_never_raises(self):
        set_identity(instance=0, role="controller",
                     partitions_fn=lambda: 1 / 0)
        assert identity()["partitions"] == []

    def test_record_stamps_both_clocks_and_seq(self):
        log = EventLog(size=8)
        set_identity(instance=7, role="controller")
        a = log.record("lead_claim", epoch=2)
        b = log.record("member_silent", instance=9, peer=7)
        assert a["kind"] == "lead_claim" and a["epoch"] == 2
        assert a["instance"] == 7          # from identity()
        assert b["instance"] == 9          # explicit wins
        assert b["seq"] == a["seq"] + 1
        assert a["mono"] <= b["mono"] and a["ts"] <= b["ts"]

    def test_disabled_records_nothing(self):
        log = EventLog(size=8, enabled=False)
        assert log.record("lead_claim") is None
        assert log.recent() == []

    def test_ring_eviction_counted(self):
        log = EventLog(size=4)
        for i in range(10):
            log.record("k", i=i)
        recent = log.recent()
        assert len(recent) == 4 and recent[-1]["i"] == 9
        assert log.evicted == 6

    def test_publisher_sees_records_and_never_breaks_recording(self):
        log = EventLog(size=8)
        seen = []
        log.attach_publisher(seen.append)
        log.record("a")
        log.attach_publisher(lambda rec: 1 / 0)
        assert log.record("b") is not None   # raising publisher swallowed
        log.attach_publisher(None)
        log.record("c")
        assert [r["kind"] for r in seen] == ["a"]
        assert [r["kind"] for r in log.recent()] == ["a", "b", "c"]


class TestReconstructPhases:
    @staticmethod
    def _ev(kind, mono, **f):
        return {"kind": kind, "mono": mono, "ts": 1000.0 + mono,
                "seq": int(mono * 1000), **f}

    def test_phases_telescope_to_downtime(self):
        ev = [self._ev("chaos_kill", 10.0),
              self._ev("member_silent", 10.4, peer=0),
              self._ev("part_claim", 10.45),
              self._ev("absorb_end", 10.6),
              self._ev("first_placement", 10.7)]
        out = reconstruct_phases(ev)
        assert out["complete"]
        assert out["phases"] == {"detect_s": 0.4, "claim_s": 0.05,
                                 "absorb_s": 0.15,
                                 "first_placement_s": 0.1}
        assert round(sum(out["phases"].values()), 6) == out["downtime_s"]

    def test_first_mark_at_or_after_previous_wins(self):
        # marks BEFORE the kill and post-recovery duplicates must not
        # pollute the phases
        ev = [self._ev("member_silent", 5.0, peer=9),   # pre-kill noise
              self._ev("chaos_kill", 10.0),
              self._ev("member_silent", 10.4),
              self._ev("part_claim", 10.45),
              self._ev("absorb_end", 10.6),
              self._ev("first_placement", 10.7),
              self._ev("member_silent", 20.0),          # recovered regime
              self._ev("first_placement", 21.0)]
        out = reconstruct_phases(ev)
        assert out["phases"]["detect_s"] == 0.4
        assert out["downtime_s"] == 0.7

    def test_missing_mark_is_incomplete_not_an_error(self):
        ev = [self._ev("chaos_kill", 10.0),
              self._ev("member_silent", 10.4)]
        out = reconstruct_phases(ev)
        assert not out["complete"]
        assert out["downtime_s"] is None
        assert "claim_s" not in out["phases"]

    def test_phase_marks_catalog_is_causal_order(self):
        kinds = [k for k, _ in PHASE_MARKS]
        assert kinds == ["chaos_kill", "member_silent", "part_claim",
                         "absorb_end", "first_placement"]


# -- off-switch byte-exactness ---------------------------------------------
class TestWireByteExactness:
    def test_heartbeat_without_admin_url_is_byte_exact(self):
        from openwhisk_tpu.controller.loadbalancer.membership import \
            ControllerMembership
        from openwhisk_tpu.core.entity import ControllerInstanceId
        from openwhisk_tpu.messaging import MemoryMessagingProvider

        def mk(**kw):
            return ControllerMembership(MemoryMessagingProvider(),
                                        ControllerInstanceId("0"),
                                        object(), **kw)

        plain = mk()._heartbeat_msg()
        assert plain == json.dumps({"kind": "heartbeat",
                                    "instance": 0}).encode()
        assert b"admin" not in mk(admin_url=None)._heartbeat_msg()
        assert b"admin" not in mk(admin_url="")._heartbeat_msg()
        announced = mk(admin_url="http://127.0.0.1:3233")._heartbeat_msg()
        assert json.loads(announced)["admin"] == "http://127.0.0.1:3233"

    def test_ping_without_admin_is_byte_exact_and_parse_tolerates(self):
        from openwhisk_tpu.core.entity import InvokerInstanceId, MB
        from openwhisk_tpu.messaging.message import PingMessage

        inst = InvokerInstanceId(0, user_memory=MB(256))
        plain = PingMessage(inst)
        assert plain.to_json() == {"name": inst.to_json()}
        assert b"admin" not in plain.serialize()
        # legacy payload (no admin key) parses to admin=None
        assert PingMessage.parse(plain.serialize()).admin is None
        ann = PingMessage(inst, admin="http://127.0.0.1:9001")
        back = PingMessage.parse(ann.serialize())
        assert back.admin == "http://127.0.0.1:9001"
        assert back.instance.instance == 0

    def test_peer_directory_tracks_announcing_live_peers(self):
        from openwhisk_tpu.controller.loadbalancer.membership import \
            ControllerMembership
        from openwhisk_tpu.core.entity import ControllerInstanceId
        from openwhisk_tpu.messaging import MemoryMessagingProvider

        class _Balancer:
            def update_cluster(self, n):
                pass

        m = ControllerMembership(MemoryMessagingProvider(),
                                 ControllerInstanceId("0"), _Balancer(),
                                 member_timeout_s=60.0)
        m._on_message(json.dumps(
            {"kind": "heartbeat", "instance": 1,
             "admin": "http://127.0.0.1:41"}).encode())
        m._on_message(json.dumps(
            {"kind": "heartbeat", "instance": 2}).encode())
        assert m.peer_directory() == {1: "http://127.0.0.1:41"}
        m._on_message(json.dumps(
            {"kind": "leave", "instance": 1}).encode())
        assert m.peer_directory() == {}


# -- exact-merge property tests --------------------------------------------
def _feed(wf: ActivationWaterfall, samples, t0=1_000_000_000):
    """samples: list of per-stage microsecond deltas dicts."""
    for i, deltas in enumerate(samples):
        aid = f"a{t0}-{i}"
        now = t0
        wf.begin(aid, t0_ns=now)
        for stage in sorted(deltas):
            now += deltas[stage] * 1000
            wf.stamp(aid, stage, now_ns=now)
        wf.finish(aid)


def _rand_samples(rng, n):
    out = []
    for _ in range(n):
        out.append({STAGE_API_ACCEPT: rng.randint(1, 50),
                    STAGE_PUBLISH_ENQUEUE: rng.randint(1, 2000),
                    STAGE_INVOKER_PICKUP: rng.randint(1, 500),
                    STAGE_RUN: rng.randint(10, 100_000),
                    STAGE_COMPLETION_ACK: rng.randint(1, 300),
                    STAGE_RECORD_WRITE: rng.randint(1, 300)})
    return out


class TestBitExactMerge:
    def teardown_method(self):
        reset_identity()

    @pytest.mark.parametrize("seed", [1, 7, 23])
    def test_bucketwise_merge_equals_pooled_samples(self, seed):
        rng = random.Random(seed)
        cfg = dict(enabled=True, buckets=30)
        a = ActivationWaterfall(WaterfallConfig(**cfg))
        b = ActivationWaterfall(WaterfallConfig(**cfg))
        pooled = ActivationWaterfall(WaterfallConfig(**cfg))
        sa, sb = _rand_samples(rng, 120), _rand_samples(rng, 80)
        _feed(a, sa)
        _feed(b, sb, t0=2_000_000_000)
        _feed(pooled, sa)
        _feed(pooled, sb, t0=2_000_000_000)

        ra, rb = a.raw_counts(), b.raw_counts()
        merged = merged_waterfall_report([ra, rb])
        ref = pooled.report()
        # the rendered per-stage/budget views and the tail threshold +
        # dominant-stage attribution derive purely from the bucket counts
        # and sums — equality here IS bucket-wise exactness
        assert merged["per_stage"] == ref["per_stage"]
        # the merge recomputes the p99 threshold over the MERGED total
        # hist (the pooled instance's own copy refreshes lazily every 64
        # finishes, so it can be stale — the recomputed one cannot)
        from openwhisk_tpu.utils.waterfall import bucket_bounds_ms
        true_tb = pooled._pctl_bucket(pooled._total_hist, 0.99)
        assert merged["tail"]["tail_threshold_ms"] == \
            bucket_bounds_ms(pooled.n_buckets)[true_tb]
        assert merged["tail"]["dominant"] == ref["tail"]["dominant"]
        # dominant_tail is an ONLINE tally against each process's moving
        # p99 threshold — not derivable from buckets; the fleet semantics
        # are "sum of per-member judgments", pinned exactly:
        summed = [x + y for x, y in zip(ra["dominant_tail"],
                                        rb["dominant_tail"])]
        from openwhisk_tpu.utils.waterfall import STAGES
        assert merged["tail"]["dominant_tail"] == {
            STAGES[i]: summed[i] for i in range(N_STAGES) if summed[i]}
        assert merged["finished"] == ref["finished"] == 200
        assert merged["buckets_le_ms"] == ref["buckets_le_ms"]
        assert merged["identity"]["role"] == "fleet"
        assert len(merged["members"]) == 2

    def test_mismatched_bucket_grids_are_skipped_not_pooled(self):
        set_identity(instance=0, role="controller")
        a = ActivationWaterfall(WaterfallConfig(enabled=True, buckets=30))
        b = ActivationWaterfall(WaterfallConfig(enabled=True, buckets=16))
        _feed(a, _rand_samples(random.Random(1), 5))
        _feed(b, _rand_samples(random.Random(2), 5))
        ra, rb = a.raw_counts(), b.raw_counts()
        rb["identity"] = {"instance": 9, "role": "controller"}
        merged = merged_waterfall_report([ra, rb])
        assert merged["finished"] == 5
        assert [m.get("instance") for m in merged["members_skipped"]] == [9]

    def test_merged_slo_is_judged_over_merged_counts(self):
        # two processes whose namespace histograms only violate the p99
        # target when POOLED: a mean of per-process verdicts cannot see it
        from openwhisk_tpu.ops.telemetry import N_OUTCOMES, bucket_bounds_ms
        nb = 24
        bounds = bucket_bounds_ms(nb)

        def raw(inst, hits_slow):
            buckets = [0] * nb
            buckets[4] = 90
            buckets[20] = hits_slow  # ~100ms+ bucket
            return {"identity": {"instance": inst, "role": "controller"},
                    "enabled": True, "kernel": "xla", "buckets": nb,
                    "targets": {"e2e_p99_ms": bounds[10],
                                "error_ratio": 0.5},
                    "overrides": {}, "dropped_events": 0,
                    "namespaces": {"guest": {
                        "buckets": buckets,
                        "outcomes": [sum(buckets)] + [0] * (N_OUTCOMES - 1),
                        "lat_ms": {}}},
                    "invokers": {}}

        merged = merged_slo_report([raw(0, 0), raw(1, 4)])
        ns = merged["namespaces"]["guest"]
        assert ns["count"] == 184
        assert merged["members"] == [
            {"instance": 0, "role": "controller"},
            {"instance": 1, "role": "controller"}]
        # 4/184 > 1% of samples in the slow bucket -> merged p99 blows the
        # target even though member 0 alone was clean
        assert ns["p99_le_ms"] > bounds[10]
        assert ns["latency_compliant"] is False
        # the clean member judged alone is compliant — proving the fleet
        # verdict is a re-judgment of pooled counts, not a vote
        solo = merged_slo_report([raw(0, 0)])
        assert solo["namespaces"]["guest"]["latency_compliant"] is True

    def test_merged_metrics_counters_sum_gauges_stay_per_member(self):
        def raw(inst, n):
            return {"identity": {"instance": inst},
                    "counters": [["requests_total", [["code", "200"]], n]],
                    "gauges": [["load", [], inst * 1.5]],
                    "histograms": [["lat_ms", [], {"count": n,
                                                   "sum": 10.0 * n}]]}

        out = merged_metrics([raw(0, 3), raw(1, 4)])
        assert out["counters"] == [["requests_total", [["code", "200"]], 7]]
        assert out["histograms"] == [["lat_ms", [],
                                      {"count": 7, "sum": 70.0}]]
        assert [g["identity"]["instance"] for g in out["gauges_by_member"]] \
            == [0, 1]
        # a fleet sum of a utilization gauge is a lie: no merged gauges key
        assert "gauges" not in out

    def test_merged_host_report_bucketwise(self):
        def raw(inst, lag_bucket, n):
            nb = 30
            lag = [0] * nb
            lag[lag_bucket] = n
            return {"identity": {"instance": inst, "role": "controller"},
                    "enabled": True, "buckets": nb, "uptime_s": 1.0,
                    "lag": {"hist": lag, "sum_us": 100 * n, "max_us": 900,
                            "ticks": n},
                    "stalls": {"count": 1, "sum_us": 50},
                    "gc": {"hist": [[0] * nb] * 3, "sum_us": [0, 0, 0],
                           "count": [0, 0, 0], "collected": 2,
                           "uncollectable": 0, "overlapping_dispatch": 1},
                    "tasks": {"created": 10 * n, "finished": 9 * n},
                    "serde": [["health", "encode", n, 64 * n, 1000 * n]]}

        out = merged_host_report([raw(0, 5, 10), raw(1, 9, 10)])
        assert out["loop_lag"]["ticks"] == 20
        assert out["tasks"] == {"created": 200, "finished": 180,
                                "active": 20}
        assert out["serde"] == [{"hop": "health", "direction": "encode",
                                 "count": 20, "bytes": 1280, "ms": 0.02}]
        assert [m["instance"] for m in out["members"]] == [0, 1]

    def test_metrics_raw_wire_shape_roundtrips_through_merge(self):
        from openwhisk_tpu.utils.logging import MetricEmitter
        a, b = MetricEmitter(), MetricEmitter()
        for m in (a, b):
            m.counter("loadbalancer_activations_total",
                      tags={"invoker": "invoker0"})
        a.counter("loadbalancer_activations_total",
                  tags={"invoker": "invoker0"})
        ra = metrics_raw(a.snapshot(), {"instance": 0})
        rb = metrics_raw(b.snapshot(), {"instance": 1})
        merged = merge_serialized_counters([ra, rb])
        assert merged == [["loadbalancer_activations_total",
                           [["invoker", "invoker0"]], 3]]


# -- spillover continuity --------------------------------------------------
class TestSpilloverContinuity:
    def test_trace_context_survives_the_ctrlspill_columnar_frame(self):
        from openwhisk_tpu.core.entity import (ActivationId,
                                               ControllerInstanceId,
                                               FullyQualifiedEntityName,
                                               Identity)
        from openwhisk_tpu.messaging.columnar import (KIND_ACTIVATION,
                                                      make_batch,
                                                      parse_batch)
        from openwhisk_tpu.messaging.message import ActivationMessage
        from openwhisk_tpu.utils.transaction import TransactionId

        tc = {"traceparent":
              "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"}
        msg = ActivationMessage(
            TransactionId(), FullyQualifiedEntityName.parse("guest/spilled"),
            "1-a", Identity.generate("guest"), ActivationId.generate(),
            ControllerInstanceId("0"), True, {}, trace_context=tc)
        plain = ActivationMessage(
            TransactionId(), FullyQualifiedEntityName.parse("guest/other"),
            "1-a", Identity.generate("guest"), ActivationId.generate(),
            ControllerInstanceId("0"), True, {})
        _, out = parse_batch(
            make_batch(KIND_ACTIVATION, [msg, plain]).serialize())
        assert out[0].trace_context == tc
        assert out[1].trace_context is None

    def test_join_spill_rows_telescopes_origin_and_peer_halves(self):
        def half(aid, stamped, inst, trace=None, ts=1.0):
            deltas = [-1] * N_STAGES
            for stage, us in stamped.items():
                deltas[stage] = us
            return {"activation_id": aid, "trace_id": trace, "ts": ts,
                    "total_us": sum(stamped.values()),
                    "deltas_us": deltas, "clamped": 0, "instance": inst}

        origin = half("aid1", {STAGE_API_ACCEPT: 100,
                               STAGE_SPILL_FORWARD: 400}, 0,
                      trace="t-origin", ts=1.0)
        peer = half("aid1", {STAGE_PUBLISH_ENQUEUE: 50, STAGE_RUN: 250},
                    1, ts=1.1)
        lone = half("aid2", {STAGE_API_ACCEPT: 10, STAGE_RUN: 20}, 1,
                    ts=0.5)
        rows = join_spill_rows([peer, lone, origin])
        assert [r["activation_id"] for r in rows] == ["aid2", "aid1"]
        joined = rows[1]
        assert joined["joined"] is True
        assert joined["origin_instance"] == 0
        assert joined["peer_instance"] == 1
        assert joined["trace_id"] == "t-origin"
        # telescoping: total == sum of present deltas across BOTH halves
        assert joined["total_us"] == 100 + 400 + 50 + 250
        assert joined["deltas_us"][STAGE_SPILL_FORWARD] == 400
        assert joined["deltas_us"][STAGE_RUN] == 250

    def test_merged_waterfall_renders_joined_row_with_provenance(self):
        a = ActivationWaterfall(WaterfallConfig(enabled=True, buckets=30))
        b = ActivationWaterfall(WaterfallConfig(enabled=True, buckets=30))
        t0 = 1_000_000_000
        # origin half: accepted, then handed off to the spill frame
        a.begin("sp1", t0_ns=t0)
        a.stamp("sp1", STAGE_API_ACCEPT, now_ns=t0 + 100_000)
        a.stamp("sp1", STAGE_SPILL_FORWARD, now_ns=t0 + 500_000)
        a.finish("sp1")
        # peer half: resumed at publish, ran, acked
        b.begin("sp1", t0_ns=t0 + 500_000)
        b.stamp("sp1", STAGE_PUBLISH_ENQUEUE, now_ns=t0 + 600_000)
        b.stamp("sp1", STAGE_RUN, now_ns=t0 + 900_000)
        b.finish("sp1")
        ra = a.raw_counts(rows=8)
        rb = b.raw_counts(rows=8)
        ra["identity"] = {"instance": 0, "role": "controller"}
        rb["identity"] = {"instance": 1, "role": "controller"}
        merged = merged_waterfall_report([ra, rb], recent=8)
        assert merged["joined_rows"] == 1
        row = [r for r in merged["recent"]
               if r["activation_id"] == "sp1"][0]
        assert row["joined"] is True
        assert row["origin_instance"] == 0 and row["peer_instance"] == 1
        assert row["total_ms"] == 0.9  # 0.5ms origin + 0.4ms peer


# -- merged timeline -------------------------------------------------------
class TestMergedTimeline:
    def test_orders_by_wall_then_mono_then_seq(self):
        ev = {
            0: [{"kind": "b", "ts": 2.0, "mono": 5.0, "seq": 1},
                {"kind": "d", "ts": 3.0, "mono": 6.0, "seq": 2}],
            1: [{"kind": "a", "ts": 1.0, "mono": 9.0, "seq": 0},
                {"kind": "c", "ts": 2.0, "mono": 5.5, "seq": 0}],
        }
        out = merged_timeline(ev)
        assert out["members"] == [0, 1]
        assert out["count"] == 4
        assert [e["kind"] for e in out["events"]] == ["a", "b", "c", "d"]

    def test_limit_keeps_the_tail_and_member_key_backfills_instance(self):
        ev = {3: [{"kind": f"k{i}", "ts": float(i)} for i in range(5)]}
        out = merged_timeline(ev, limit=2)
        assert [e["kind"] for e in out["events"]] == ["k3", "k4"]
        assert all(e["instance"] == 3 for e in out["events"])


# -- exposition grammar for the new families -------------------------------
class TestNewFamilyGrammar:
    EDGE_FAMILIES = ("edge_retry_total", "edge_upstream_attempts_total",
                     "edge_upstream_http_503_total")

    def test_edge_stats_counter_rows_obey_the_grammar(self):
        from openwhisk_tpu.edge import EdgeProxy, Upstream
        edge = EdgeProxy(upstreams=[Upstream("http://127.0.0.1:3233")],
                         admin_token="tok")
        edge.retry_total["http_503"] = 2
        edge.upstreams[0].attempts = 5
        edge.upstreams[0].http_503 = 2
        payload = json.loads(self._stats_body(edge))
        names = [row[0] for row in payload["counters"]]
        for fam in self.EDGE_FAMILIES:
            assert fam in names
        for name, tags, value in payload["counters"]:
            assert _NAME.match(name), name
            for k, _v in tags:
                assert _LABEL_NAME.match(k), k
            assert isinstance(value, int) and value >= 0
        assert payload["identity"]["role"] == "edge"

    @staticmethod
    def _stats_body(edge) -> bytes:
        from aiohttp.test_utils import make_mocked_request
        req = make_mocked_request(
            "GET", "/admin/edge/stats",
            headers={"Authorization": "Bearer tok"})
        return edge._edge_stats(req).body

    def test_edge_stats_denied_without_or_with_wrong_token(self):
        from aiohttp import web
        from aiohttp.test_utils import make_mocked_request
        from openwhisk_tpu.edge import EdgeProxy, Upstream
        sealed = EdgeProxy(upstreams=[Upstream("http://127.0.0.1:3233")])
        gated = EdgeProxy(upstreams=[Upstream("http://127.0.0.1:3233")],
                          admin_token="tok")
        for edge, hdrs in ((sealed, {}),
                           (sealed, {"Authorization": "Bearer anything"}),
                           (gated, {}),
                           (gated, {"Authorization": "Bearer wrong"}),
                           (gated, {"Authorization": "Basic dG9r"})):
            req = make_mocked_request("GET", "/admin/edge/stats",
                                      headers=hdrs)
            with pytest.raises(web.HTTPForbidden):
                edge._edge_stats(req)

    def test_metrics_page_posture_unchanged(self):
        from openwhisk_tpu.edge import EdgeProxy, Upstream
        edge = EdgeProxy(upstreams=[Upstream("http://127.0.0.1:3233")])
        assert "/metrics" in edge.extra_denied_paths


# -- federation endpoints over HTTP ----------------------------------------
AUTH_PORT = 13441
PEER_PORT = 13442


def _controller(port, logger=None):
    from openwhisk_tpu.controller.core import Controller
    from openwhisk_tpu.controller.loadbalancer.lean import LeanBalancer
    from openwhisk_tpu.core.entity import (ControllerInstanceId, Identity,
                                           MB, WhiskAuthRecord)
    from openwhisk_tpu.messaging import MemoryMessagingProvider
    from openwhisk_tpu.utils.logging import NullLogging

    async def noop_factory(invoker_id, provider):
        class _Stub:
            async def stop(self):
                pass

        return _Stub()

    logger = logger or NullLogging()
    provider = MemoryMessagingProvider()
    lb = LeanBalancer(provider, ControllerInstanceId("0"), noop_factory,
                      logger=logger, metrics=logger.metrics,
                      user_memory=MB(512))
    c = Controller(ControllerInstanceId("0"), provider, logger=logger,
                   load_balancer=lb)
    ident = Identity.generate("guest")
    return c, ident


class TestFederationEndpoints:
    def teardown_method(self):
        reset_identity()

    def _hdrs(self, ident):
        return {"Authorization": "Basic " + base64.b64encode(
            ident.authkey.compact.encode()).decode()}

    def test_partial_failure_is_labeled_not_an_error(self):
        import aiohttp
        from aiohttp import web
        from openwhisk_tpu.core.entity import WhiskAuthRecord
        from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL

        wf_was = GLOBAL_WATERFALL.enabled

        async def go():
            GLOBAL_WATERFALL.enabled = True
            GLOBAL_WATERFALL.reset()
            c, ident = _controller(AUTH_PORT)
            await c.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            assert c.fleet_config.enabled  # default ON

            # a live stub peer: answers the ?raw=1 scrapes with a second
            # waterfall's raw export — a ≥2-process merge over real HTTP
            peer_wf = ActivationWaterfall(WaterfallConfig(enabled=True,
                                                          buckets=30))
            _feed(peer_wf, _rand_samples(random.Random(3), 10))
            praw = peer_wf.raw_counts(rows=4)
            praw["identity"] = {"instance": 1, "role": "controller"}

            async def peer_waterfall(request):
                assert request.query.get("raw") == "1"
                return web.json_response(praw)

            papp = web.Application()
            papp.router.add_get("/admin/latency/waterfall", peer_waterfall)
            prunner = web.AppRunner(papp)
            await prunner.setup()
            await web.TCPSite(prunner, "127.0.0.1", PEER_PORT).start()

            class _Stub:
                def peer_directory(self):
                    return {1: f"http://127.0.0.1:{PEER_PORT}",
                            2: "http://127.0.0.1:9"}  # dead peer

                async def stop(self):
                    pass

            await c.start(port=AUTH_PORT)
            c.membership = _Stub()
            out = {}
            try:
                base = f"http://127.0.0.1:{AUTH_PORT}"
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{base}/admin/fleet/waterfall") as r:
                        out["wf_status"] = r.status
                        out["wf"] = await r.json()
                    async with s.get(f"{base}/admin/fleet/metrics") as r:
                        out["m_status"] = r.status
                        out["m"] = await r.json()
                    async with s.get(f"{base}/admin/fleet/timeline") as r:
                        out["t_status"] = r.status
                        out["t"] = await r.json()
                    async with s.get(f"{base}/admin/fleet/waterfall",
                                     headers=self._hdrs(ident)) as r:
                        out["wf_auth_status"] = r.status
                        out["wf_auth"] = await r.json()
                    async with s.get(f"{base}/admin/metrics/raw",
                                     headers=self._hdrs(ident)) as r:
                        out["raw_status"] = r.status
            finally:
                await prunner.cleanup()
                await c.stop()
            return out

        out = asyncio.run(go())
        GLOBAL_WATERFALL.enabled = wf_was
        # federation endpoints sit behind the same admin auth gate
        assert out["wf_status"] == 401
        assert out["m_status"] == 401
        assert out["t_status"] == 401
        assert out["raw_status"] == 200
        body = out["wf_auth"]
        assert out["wf_auth_status"] == 200      # partial, never a 500
        assert body["members_missing"] == [2]    # the dead peer, labeled
        roles = [m.get("role") for m in body["members"]]
        assert "controller" in roles
        assert body["finished"] >= 10            # peer counts merged in

    def test_disabled_is_a_404_no_op(self, monkeypatch):
        import aiohttp
        from openwhisk_tpu.core.entity import WhiskAuthRecord

        monkeypatch.setenv("CONFIG_whisk_fleetObservatory_enabled", "false")
        assert fleet_config().enabled is False

        async def go():
            c, ident = _controller(AUTH_PORT + 2)
            await c.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            assert c.fleet_config.enabled is False
            await c.start(port=AUTH_PORT + 2)
            out = {}
            try:
                assert c.fleet_events is None    # no ctrlevents plumbing
                base = f"http://127.0.0.1:{AUTH_PORT + 2}"
                async with aiohttp.ClientSession() as s:
                    for path in ("/admin/fleet/metrics",
                                 "/admin/fleet/waterfall",
                                 "/admin/fleet/slo", "/admin/fleet/host",
                                 "/admin/fleet/timeline",
                                 "/admin/metrics/raw"):
                        async with s.get(base + path,
                                         headers=self._hdrs(ident)) as r:
                            out[path] = r.status
            finally:
                await c.stop()
            return out

        out = asyncio.run(go())
        assert all(status == 404 for status in out.values()), out


# -- ctrlevents bus bridging -----------------------------------------------
class TestFleetEvents:
    def test_frames_fold_into_peer_rings_and_own_frames_skip(self):
        from openwhisk_tpu.controller.fleet import FleetEvents
        from openwhisk_tpu.messaging import MemoryMessagingProvider

        async def go():
            provider = MemoryMessagingProvider()
            log0, log1 = EventLog(size=16), EventLog(size=16)
            fe0 = FleetEvents(provider, 0, event_log=log0)
            fe1 = FleetEvents(provider, 1, event_log=log1)
            fe0.start()
            fe1.start()
            try:
                log0.record("lead_claim", instance=0, epoch=1)
                log1.record("part_claim", instance=1,
                            parts={"3": 2}, prev={})
                for _ in range(100):
                    if fe0.peer_events.get(1) and fe1.peer_events.get(0):
                        break
                    await asyncio.sleep(0.05)
            finally:
                await fe0.stop()
                await fe1.stop()
            return fe0, fe1

        fe0, fe1 = asyncio.run(go())
        assert [r["kind"] for r in fe0.peer_events[1]] == ["part_claim"]
        assert [r["kind"] for r in fe1.peer_events[0]] == ["lead_claim"]
        assert 0 not in fe0.peer_events  # own frames echo back, skipped
        ev0 = fe0.events_by_member()
        assert set(ev0) == {0, 1}
        merged = merged_timeline(ev0)
        assert [e["kind"] for e in merged["events"]] == ["lead_claim",
                                                         "part_claim"]


# -- identity blocks on existing snapshots ---------------------------------
class TestIdentityOnSnapshots:
    def teardown_method(self):
        reset_identity()

    def test_waterfall_and_hostprof_and_slo_raw_carry_identity(self):
        from openwhisk_tpu.utils.hostprof import HostObservatory
        set_identity(instance=4, role="controller")
        wf = ActivationWaterfall(WaterfallConfig(enabled=True, buckets=8))
        for snap in (wf.report(), wf.raw_counts(),
                     HostObservatory().raw_counts()):
            ident = snap["identity"]
            assert ident["instance"] == 4
            assert ident["role"] == "controller"
            assert isinstance(ident["pid"], int)
            assert "partitions" in ident
