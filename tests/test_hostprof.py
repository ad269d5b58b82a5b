"""Host hot-loop observatory (utils/hostprof.py, ISSUE 11).

Covers all four planes — the lag probe measuring an injected 50 ms stall
AND naming the offending coroutine, gc callback accounting under a forced
collect, serde counters matching a known message count/bytes, the sampler
census under synthetic load — plus disabled-is-a-true-no-op (no task
factory swap, no gc callbacks, tracemalloc-clean hot paths), the
generator self-check satellite in tools/loadgen, the bench_compare CLI,
and both admin endpoints auth-gated.

Sampler/timing assertions skip with a logged reason when the box can't
hold a schedule (the pallas-probe pattern from PR 9's conftest): a loaded
CI runner must not turn a timing assertion into a flake.
"""
import asyncio
import gc
import json
import sys
import time
import tracemalloc

import pytest

from openwhisk_tpu.utils.hostprof import (GLOBAL_HOST_OBSERVATORY,
                                          HostObservatory,
                                          HostProfilingConfig)

# ---------------------------------------------------------------------------
# timing probe (conftest pallas-probe pattern): sampler + stall assertions
# need sys._current_frames AND a box that can hold a rough schedule
# ---------------------------------------------------------------------------
_timing_probe_result = None


def _timing_probe():
    global _timing_probe_result
    if _timing_probe_result is not None:
        return _timing_probe_result
    if not hasattr(sys, "_current_frames"):
        _timing_probe_result = (False, "sys._current_frames unavailable")
        return _timing_probe_result
    t0 = time.perf_counter()
    time.sleep(0.05)
    dt = time.perf_counter() - t0
    if dt > 0.5:
        _timing_probe_result = (
            False, f"box too loaded to assert timing "
                   f"(a 50ms sleep took {dt * 1e3:.0f}ms)")
    else:
        _timing_probe_result = (True, "")
    return _timing_probe_result


def _skip_unless_timing():
    ok, reason = _timing_probe()
    if not ok:
        print(f"# skipping sampler/timing assertion: {reason}",
              file=sys.stderr)
        pytest.skip(f"sampler/timing unavailable: {reason}")


def make_obs(**kw) -> HostObservatory:
    return HostObservatory(HostProfilingConfig(**kw))


class TestLagProbeAndStalls:
    def test_lag_probe_measures_injected_stall_and_names_callback(self):
        _skip_unless_timing()
        obs = make_obs(lag_probe_ms=10.0, stall_threshold_ms=30.0,
                       sample_hz=0.0)

        async def blocker():
            time.sleep(0.05)  # a synchronous 50 ms loop stall

        async def go():
            assert obs.install() is True
            try:
                await asyncio.get_event_loop().create_task(blocker())
                # let the probe fire a few clean post-stall ticks
                await asyncio.sleep(0.06)
            finally:
                obs.uninstall()

        asyncio.run(go())
        snap = obs.snapshot()
        # the stall is visible in the lag histogram, measured from the
        # probe tick's SCHEDULED deadline
        assert snap["loop_lag"]["ticks"] >= 5
        assert snap["loop_lag"]["max_ms"] >= 35.0
        # ... and the interposer NAMED the coroutine that caused it
        worst = snap["stalls"]["worst"]
        assert worst, "no stall recorded"
        assert any("blocker" in (s["coro"] or "") for s in worst)
        assert worst[0]["ms"] >= 30.0
        assert snap["stalls"]["count"] >= 1

    def test_lag_backfills_missed_ticks_from_schedule(self):
        """Coordinated omission: one probe firing after a stall must
        record one sample PER missed tick (each from its own deadline),
        not collapse the stall into a single late sample."""
        _skip_unless_timing()
        obs = make_obs(lag_probe_ms=10.0, stall_threshold_ms=5000.0,
                       sample_hz=0.0)

        async def go():
            obs.install()
            try:
                await asyncio.sleep(0.03)  # a few clean ticks
                time.sleep(0.12)           # stall ~12 probe intervals
                await asyncio.sleep(0.03)
            finally:
                obs.uninstall()

        asyncio.run(go())
        snap = obs.snapshot()
        # ~180ms of run at 10ms ticks: backfill must keep tick count near
        # schedule (a non-backfilling probe would record ~6)
        assert snap["loop_lag"]["ticks"] >= 12
        assert snap["loop_lag"]["max_ms"] >= 90.0

    def test_uninstall_restores_task_factory(self):
        obs = make_obs(sample_hz=0.0)

        async def go():
            loop = asyncio.get_event_loop()
            before = loop.get_task_factory()
            assert obs.install() is True
            assert loop.get_task_factory() is not before
            obs.uninstall()
            assert loop.get_task_factory() is before

        asyncio.run(go())

    def test_wrapped_tasks_preserve_results_exceptions_cancellation(self):
        obs = make_obs(sample_hz=0.0)

        async def ok():
            await asyncio.sleep(0)
            return 42

        async def boom():
            raise ValueError("boom")

        async def sleeper():
            await asyncio.sleep(30)

        async def go():
            obs.install()
            try:
                loop = asyncio.get_event_loop()
                assert await loop.create_task(ok(), name="named") == 42
                with pytest.raises(ValueError):
                    await loop.create_task(boom())
                t = loop.create_task(sleeper())
                await asyncio.sleep(0)
                t.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await t
            finally:
                obs.uninstall()

        asyncio.run(go())
        snap = obs.snapshot()
        assert snap["tasks"]["created"] >= 3
        assert snap["tasks"]["finished"] >= 3


class TestResetMidFlight:
    def test_reset_carries_inflight_tasks_so_active_stays_nonnegative(
            self):
        """Review regression: a reset while wrapped tasks are in flight
        (sweep_balancer's headline-window reset) must not let the later
        done-callbacks drive active below zero."""
        obs = make_obs(sample_hz=0.0)

        async def sleeper():
            await asyncio.sleep(0.05)

        async def go():
            obs.install()
            try:
                t = asyncio.get_event_loop().create_task(sleeper())
                await asyncio.sleep(0)
                obs.reset()
                assert obs.snapshot()["tasks"]["active"] >= 1
                await t
            finally:
                obs.uninstall()

        asyncio.run(go())
        tasks = obs.snapshot()["tasks"]
        assert tasks["active"] >= 0, tasks


class TestGcAccounting:
    def test_forced_collect_is_counted_per_generation(self):
        obs = make_obs(sample_hz=0.0)

        async def go():
            obs.install()
            try:
                # build garbage cycles so the collect has real work
                junk = []
                for _ in range(1000):
                    a, b = [], []
                    a.append(b)
                    b.append(a)
                    junk.append(a)
                del junk
                gc.collect()  # full collection -> generation 2
            finally:
                obs.uninstall()

        asyncio.run(go())
        snap = obs.snapshot()
        assert snap["gc"]["pauses"]["2"] >= 1
        assert snap["gc"]["collected"] >= 1000
        assert snap["gc"]["pause_ms"]["2"] >= 0.0
        assert snap["gc"]["pause_share_pct"] >= 0.0

    def test_gc_callback_is_lock_free_under_held_lock(self):
        """Review regression: an automatic collection can fire on an
        allocation made while THIS thread holds the observatory lock
        (snapshot copies, serde first-insert). The gc callback must never
        take that non-reentrant lock — the old version self-deadlocked
        the event loop."""
        obs = make_obs(sample_hz=0.0)
        gc.callbacks.append(obs._gc_cb)
        old = gc.get_threshold()
        try:
            gc.set_threshold(10, 1, 1)  # force frequent collections
            with obs._lock:
                junk = []
                for i in range(2000):
                    junk.append(([i], {"k": i}))
        finally:
            gc.set_threshold(*old)
            gc.callbacks.remove(obs._gc_cb)
        # reaching here at all is the assertion; pauses were still folded
        assert sum(obs.snapshot()["gc"]["pauses"].values()) >= 1

    def test_share_epoch_sane_without_install(self):
        """Review regression: serde accounting runs enabled-only (no
        install), so the share epoch must be the construction time, not
        an install stamp — the old version divided by a 1 us wall."""
        obs = make_obs(sample_hz=0.0)
        time.sleep(0.05)
        obs.serde_observe("activation", "serialize", 100, 1_000_000)
        snap = obs.snapshot()
        assert snap["uptime_s"] >= 0.05
        assert 0.0 < snap["serde"][0]["share_pct"] < 10.0

    def test_gc_pause_inside_dispatch_bracket_is_attributed(self):
        obs = make_obs(sample_hz=0.0)

        async def go():
            obs.install()
            try:
                gc.collect()
                before = obs.snapshot()["gc"]["overlapping_dispatch"]
                obs.begin_dispatch()
                gc.collect()
                obs.end_dispatch()
                gc.collect()
                return before
            finally:
                obs.uninstall()

        before = asyncio.run(go())
        after = obs.snapshot()["gc"]["overlapping_dispatch"]
        # exactly the bracketed collect counted (the two outside did not)
        assert after == before + 1


class TestSerdeAccounting:
    def test_counters_match_known_message_count_and_bytes(self):
        from openwhisk_tpu.messaging.connector import (decode_message,
                                                       encode_message)
        from tests.test_balancers import make_action, make_msg
        from openwhisk_tpu.core.entity import Identity
        from openwhisk_tpu.messaging.message import ActivationMessage

        obs = GLOBAL_HOST_OBSERVATORY
        was_enabled = obs.enabled
        obs.enabled = True
        obs.reset()
        try:
            action = make_action("serde", memory=128)
            msg = make_msg(action, Identity.generate("guest"), True)
            payload = msg.serialize()
            n = 7
            for _ in range(n):
                out = encode_message(msg)
                assert out == payload
                back = decode_message(ActivationMessage.parse, payload,
                                      "activation")
                assert back.activation_id.asString == \
                    msg.activation_id.asString
            snap = obs.snapshot()
            rows = {(r["hop"], r["direction"]): r for r in snap["serde"]}
            enc = rows[("activation", "serialize")]
            dec = rows[("activation", "deserialize")]
            assert enc["count"] == n and dec["count"] == n
            assert enc["bytes"] == n * len(payload)
            assert dec["bytes"] == n * len(payload)
            assert enc["ms"] > 0.0 and dec["ms"] > 0.0
        finally:
            obs.reset()
            obs.enabled = was_enabled

    def test_bytes_pass_through_untouched(self):
        from openwhisk_tpu.messaging.connector import encode_message
        raw = b'{"already": "encoded"}'
        assert encode_message(raw) is raw

    def test_hop_labels_by_message_class(self):
        from openwhisk_tpu.messaging.connector import hop_of
        from openwhisk_tpu.core.entity import (InvokerInstanceId, MB)
        from openwhisk_tpu.messaging.message import (CompletionMessage,
                                                     PingMessage)
        from openwhisk_tpu.utils.transaction import TransactionId
        from openwhisk_tpu.core.entity import ActivationId
        inst = InvokerInstanceId(0, user_memory=MB(256))
        assert hop_of(PingMessage(inst)) == "health_ping"
        assert hop_of(CompletionMessage(
            TransactionId(), ActivationId.generate(), False,
            inst)) == "completion_ack"
        assert hop_of(object()) == "other"


class TestSampler:
    def test_census_non_empty_under_synthetic_load(self):
        _skip_unless_timing()
        obs = make_obs(sample_hz=97.0, lag_probe_ms=50.0,
                       stall_threshold_ms=5000.0)

        def spin(deadline):
            while time.monotonic() < deadline:
                sum(i * i for i in range(500))

        async def go():
            obs.install()
            try:
                end = time.monotonic() + 0.5
                while time.monotonic() < end:
                    spin(min(end, time.monotonic() + 0.02))
                    await asyncio.sleep(0)
            finally:
                obs.uninstall()

        asyncio.run(go())
        snap = obs.snapshot()
        assert snap["sampler"]["samples"] > 0
        assert snap["sampler"]["top"], "self-time census is empty"
        assert all(t["samples"] >= 1 for t in snap["sampler"]["top"])

    def test_capture_window_returns_collapsed_stacks(self):
        _skip_unless_timing()
        obs = make_obs(sample_hz=29.0, lag_probe_ms=50.0,
                       stall_threshold_ms=5000.0, capture_limit_s=1.0)

        async def go():
            obs.install()
            try:
                # capture(5.0) must clamp to the 1 s configured limit
                t0 = time.monotonic()
                out = await obs.capture(5.0)
                assert time.monotonic() - t0 < 3.0
                return out
            finally:
                obs.uninstall()

        out = asyncio.run(go())
        assert out["seconds"] == 1.0
        assert out["samples"] > 0
        assert out["collapsed"], "no collapsed stacks"
        # flamegraph collapsed format: "frame;frame;... N" per line
        line = out["collapsed"].splitlines()[0]
        stack, count = line.rsplit(" ", 1)
        assert ";" in stack or ":" in stack
        assert int(count) >= 1

    def test_concurrent_capture_is_refused(self):
        _skip_unless_timing()
        obs = make_obs(sample_hz=29.0, capture_limit_s=2.0)

        async def go():
            obs.install()
            try:
                first = asyncio.ensure_future(obs.capture(0.5))
                await asyncio.sleep(0.05)
                with pytest.raises(RuntimeError):
                    await obs.capture(0.2)
                await first
            finally:
                obs.uninstall()

        asyncio.run(go())


class TestDisabledNoOp:
    def test_install_refuses_and_touches_nothing(self):
        obs = make_obs(enabled=False)

        async def go():
            loop = asyncio.get_event_loop()
            factory_before = loop.get_task_factory()
            gc_before = list(gc.callbacks)
            assert obs.install() is False
            assert loop.get_task_factory() is factory_before
            assert gc.callbacks == gc_before
            assert obs.sampler_running is False
            assert obs.snapshot() == {"enabled": False}
            assert obs.prometheus_text() == ""

        asyncio.run(go())

    def test_env_off_switch(self, monkeypatch):
        monkeypatch.setenv("CONFIG_whisk_hostProfiling_enabled", "false")
        assert HostObservatory.from_config().enabled is False
        monkeypatch.setenv("CONFIG_whisk_hostProfiling_enabled", "true")
        monkeypatch.setenv("CONFIG_whisk_hostProfiling_stallThresholdMs",
                           "75")
        obs = HostObservatory.from_config()
        assert obs.enabled is True
        assert obs.config.stall_threshold_ms == 75.0

    def test_disabled_hot_paths_allocate_nothing(self):
        from openwhisk_tpu.messaging import connector
        obs = GLOBAL_HOST_OBSERVATORY
        was_enabled = obs.enabled
        obs.enabled = False
        raw = b'{"k": 1}'

        def parse(b):
            return b

        try:
            # warm the paths once, then assert zero residual allocations
            connector.encode_message(raw)
            connector.decode_message(parse, raw, "activation")
            obs.begin_dispatch()
            obs.end_dispatch()
            tracemalloc.start()
            try:
                s1 = tracemalloc.take_snapshot()
                for _ in range(256):
                    connector.encode_message(raw)
                    connector.decode_message(parse, raw, "activation")
                    obs.begin_dispatch()
                    obs.end_dispatch()
                s2 = tracemalloc.take_snapshot()
            finally:
                tracemalloc.stop()
            flt = [tracemalloc.Filter(True, "*utils/hostprof.py"),
                   tracemalloc.Filter(True, "*messaging/connector.py")]
            grown = [d for d in s2.filter_traces(flt).compare_to(
                s1.filter_traces(flt), "lineno") if d.size_diff > 0]
            # proportionality, not zero-tolerance: a REAL per-call leak
            # over 256 iterations is kilobytes; a stray background thread
            # (the full suite leaves a few) touching an observatory
            # property mid-window costs a frame's worth of bytes
            total = sum(d.size_diff for d in grown)
            assert total < 2048, \
                f"disabled observatory allocated {total}B: {grown}"
        finally:
            obs.enabled = was_enabled


class TestLoadgenGeneratorSelfCheck:
    def test_open_loop_reports_generator_gc_and_lag_cause(self):
        from tools.loadgen import make_schedule, open_loop

        async def one(i, sched_ns):
            if i == 3:
                gc.collect()    # a generator-side pause inside the window
            await asyncio.sleep(0.001)
            return True

        row = asyncio.run(open_loop(one, make_schedule(
            200.0, 40, dist="constant")))
        gen = row["generator"]
        assert gen["gc_pauses"] >= 1
        assert gen["gc_pause_total_ms"] >= 0.0
        assert gen["max_fire_lag_ms"] >= 0.0
        assert gen["max_fire_lag_cause"] in ("gc_pause",
                                             "event_loop_stall", None)

    def test_verdict_attributes_generator_vs_system(self):
        from tools.loadgen import verdict
        ok = {"completed": 100, "errors": 0, "unfinished": 0,
              "p99_ms": 20.0, "fire_lag_max_ms": 1.0,
              "generator": {"gc_pauses": 0, "gc_pause_total_ms": 0.0,
                            "gc_pause_max_ms": 0.0,
                            "max_fire_lag_ms": 1.0,
                            "max_fire_lag_cause": None}}
        v = verdict(ok)
        assert v["sustainable"] and v["blames"] == "none"
        # generator-only failure: fire lag with a gc cause
        gen_fail = dict(ok, fire_lag_max_ms=120.0,
                        generator=dict(ok["generator"],
                                       max_fire_lag_ms=120.0,
                                       gc_pauses=2, gc_pause_max_ms=110.0,
                                       max_fire_lag_cause="gc_pause"))
        v = verdict(gen_fail)
        assert not v["sustainable"]
        assert v["blames"] == "generator"
        assert any("gc_pause" in f for f in v["failed"])
        # system failure: p99 blown
        sys_fail = dict(ok, p99_ms=5000.0)
        v = verdict(sys_fail)
        assert not v["sustainable"] and v["blames"] == "system"
        # mixed failure blames the system (the generator reason alone
        # would not have sunk the rung)
        both = dict(gen_fail, errors=3)
        assert verdict(both)["blames"] == "system"

    def test_sustainable_bool_contract_unchanged(self):
        from tools.loadgen import sustainable
        ok = {"completed": 100, "errors": 0, "unfinished": 0,
              "p99_ms": 20.0, "fire_lag_max_ms": 1.0}
        assert sustainable(ok)
        assert not sustainable({**ok, "fire_lag_max_ms": 500.0})


class TestBenchCompare:
    def _rounds(self, tmp_path, old, new):
        a, b = tmp_path / "old.json", tmp_path / "new.json"
        a.write_text(json.dumps(old))
        b.write_text(json.dumps(new))
        return str(a), str(b)

    def test_regression_exits_nonzero(self, tmp_path, capsys):
        import tools.bench_compare as bc
        old = {"value": 100.0, "e2e_open_loop":
               {"sustained_activations_per_sec": 1000.0, "p99_ms": 50.0}}
        new = {"value": 70.0, "e2e_open_loop":
               {"sustained_activations_per_sec": 990.0, "p99_ms": 55.0}}
        a, b = self._rounds(tmp_path, old, new)
        sys.argv = ["bench_compare", a, b]
        assert bc.main() == 1
        out = capsys.readouterr()
        assert "placements_per_sec" in out.out
        assert "REGRESSED" in out.out
        assert "REGRESSION" in out.err

    def test_within_threshold_exits_zero(self, tmp_path):
        import tools.bench_compare as bc
        old = {"value": 100.0}
        new = {"value": 85.0}  # -15% < 20% threshold
        a, b = self._rounds(tmp_path, old, new)
        sys.argv = ["bench_compare", a, b]
        assert bc.main() == 0
        # latency direction: higher is the regression
        a, b = self._rounds(tmp_path,
                            {"failover_downtime": {"downtime_ms": 100.0}},
                            {"failover_downtime": {"downtime_ms": 150.0}})
        sys.argv = ["bench_compare", a, b]
        assert bc.main() == 1

    def test_missing_metrics_skip_and_envelope_unwraps(self, tmp_path,
                                                       capsys):
        import tools.bench_compare as bc
        # the driver's BENCH_r*.json envelope: JSON line inside `tail`
        old = {"n": 1, "rc": 0,
               "tail": "noise\n" + json.dumps({"value": 100.0})}
        new = {"n": 2, "rc": 1, "tail": "died before the JSON line"}
        a, b = self._rounds(tmp_path, old, new)
        sys.argv = ["bench_compare", a, b]
        assert bc.main() == 0  # dead round: skipped, not regressed
        assert "skipped (missing)" in capsys.readouterr().out

    def test_backend_mismatch_is_advisory(self, tmp_path, capsys):
        import tools.bench_compare as bc
        old = {"value": 100.0, "balancer": {"backend": "tpu"}}
        new = {"value": 10.0, "balancer": {"backend": "cpu"},
               "backend": "cpu"}
        a, b = self._rounds(tmp_path, old, new)
        sys.argv = ["bench_compare", a, b]
        assert bc.main() == 0
        out = capsys.readouterr().out
        assert "BACKEND MISMATCH" in out


class TestAdminEndpoints:
    PORT = 13393

    def test_host_profile_and_capture_auth_gated(self):
        import base64

        import aiohttp

        from openwhisk_tpu.controller.core import Controller
        from openwhisk_tpu.controller.loadbalancer.lean import LeanBalancer
        from openwhisk_tpu.core.entity import (ControllerInstanceId,
                                               Identity, MB,
                                               WhiskAuthRecord)
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        from openwhisk_tpu.utils.logging import NullLogging

        obs = GLOBAL_HOST_OBSERVATORY
        was_enabled = obs.enabled
        obs.enabled = True

        async def noop_factory(invoker_id, provider):
            class _Stub:
                async def stop(self):
                    pass

            return _Stub()

        async def go():
            provider = MemoryMessagingProvider()
            logger = NullLogging()
            lb = LeanBalancer(provider, ControllerInstanceId("0"),
                              noop_factory, logger=logger,
                              metrics=logger.metrics,
                              user_memory=MB(512))
            controller = Controller(ControllerInstanceId("0"), provider,
                                    logger=logger, load_balancer=lb)
            ident = Identity.generate("guest")
            await controller.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            await controller.start(port=self.PORT)
            try:
                # the controller's start() installed the observatory
                assert obs.installed
                await asyncio.sleep(0.1)
                hdrs = {"Authorization": "Basic " + base64.b64encode(
                    ident.authkey.compact.encode()).decode()}
                base = f"http://127.0.0.1:{self.PORT}"
                out = {}
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{base}/admin/profile/host") as r:
                        out["anon_get"] = r.status
                    async with s.post(
                            f"{base}/admin/profile/host/capture",
                            json={"seconds": 0.2}) as r:
                        out["anon_post"] = r.status
                    async with s.get(f"{base}/admin/profile/host",
                                     headers=hdrs) as r:
                        out["get"] = (r.status, await r.json())
                    async with s.get(
                            f"{base}/admin/profile/host?collapsed=1",
                            headers=hdrs) as r:
                        out["collapsed"] = (r.status, await r.json())
                    async with s.post(
                            f"{base}/admin/profile/host/capture",
                            headers=hdrs, json={"seconds": 0.2}) as r:
                        out["post"] = (r.status, await r.json())
                    async with s.post(
                            f"{base}/admin/profile/host/capture",
                            headers=hdrs, json={"seconds": "xx"}) as r:
                        out["bad"] = r.status
                return out
            finally:
                await controller.stop()

        try:
            out = asyncio.run(go())
        finally:
            obs.enabled = was_enabled
        # auth-gated like every admin plane
        assert out["anon_get"] == 401
        assert out["anon_post"] == 401
        status, body = out["get"]
        assert status == 200
        assert body["enabled"] and body["installed"]
        assert "loop_lag" in body and "gc" in body and "tasks" in body
        assert body["tasks"]["created"] >= 0
        status, coll = out["collapsed"]
        assert status == 200 and "collapsed" in coll
        assert out["bad"] == 400
        status, cap = out["post"]
        if _timing_probe()[0]:
            assert status == 200
            assert cap["samples"] >= 0 and "collapsed" in cap
        else:
            assert status in (200, 409)
        # the observatory uninstalled with its controller
        assert not obs.installed

    def test_capture_refused_when_disabled(self):
        import base64

        import aiohttp

        from openwhisk_tpu.controller.core import Controller
        from openwhisk_tpu.controller.loadbalancer.lean import LeanBalancer
        from openwhisk_tpu.core.entity import (ControllerInstanceId,
                                               Identity, MB,
                                               WhiskAuthRecord)
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        from openwhisk_tpu.utils.logging import NullLogging

        obs = GLOBAL_HOST_OBSERVATORY
        was_enabled = obs.enabled
        obs.enabled = False

        async def noop_factory(invoker_id, provider):
            class _Stub:
                async def stop(self):
                    pass

            return _Stub()

        async def go():
            provider = MemoryMessagingProvider()
            logger = NullLogging()
            lb = LeanBalancer(provider, ControllerInstanceId("0"),
                              noop_factory, logger=logger,
                              metrics=logger.metrics, user_memory=MB(512))
            controller = Controller(ControllerInstanceId("0"), provider,
                                    logger=logger, load_balancer=lb)
            ident = Identity.generate("guest")
            await controller.auth_store.put(WhiskAuthRecord(
                ident.subject, [ident.namespace], [ident.authkey]))
            await controller.start(port=self.PORT + 1)
            try:
                hdrs = {"Authorization": "Basic " + base64.b64encode(
                    ident.authkey.compact.encode()).decode()}
                base = f"http://127.0.0.1:{self.PORT + 1}"
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{base}/admin/profile/host",
                                     headers=hdrs) as r:
                        get = (r.status, await r.json())
                    async with s.post(
                            f"{base}/admin/profile/host/capture",
                            headers=hdrs, json={"seconds": 0.2}) as r:
                        post = r.status
                return get, post
            finally:
                await controller.stop()

        try:
            (status, body), post = asyncio.run(go())
        finally:
            obs.enabled = was_enabled
        assert status == 200 and body == {"enabled": False}
        assert post == 409
