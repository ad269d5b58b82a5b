"""Smoke coverage for the performance harness (tiny sample counts).

Mirrors the reference's practice of keeping its perf harness compiling and
runnable in CI even though real measurements need dedicated hardware: each
tool runs end-to-end with minimal work so regressions surface in the unit
suite, not on the benchmark box.
"""
import json
import os
import subprocess
import sys

import pytest

PERF_DIR = os.path.join(os.path.dirname(__file__), "performance")
sys.path.insert(0, PERF_DIR)

import simulations  # noqa: E402


class TestSimulations:
    def test_latency_and_apiv1_report_stats(self, capsys):
        ok = simulations.run(["latency", "apiv1"], requests=3, concurrency=2,
                             port=13441)
        lines = [json.loads(l) for l in
                 capsys.readouterr().out.strip().splitlines()]
        assert ok
        assert [l["simulation"] for l in lines] == ["latency", "apiv1"]
        for l in lines:
            assert l["errors"] == 0
            assert l["requests"] == 3
            assert l["rps"] > 0 and l["mean_ms"] > 0
            assert l["p50_ms"] <= l["p99_ms"]

    def test_threshold_violation_fails(self, capsys, monkeypatch):
        monkeypatch.setenv("MIN_REQUESTS_PER_SEC", "1e12")
        assert not simulations.run(["apiv1"], requests=2, concurrency=2,
                                   port=13442)

    def test_cold_and_throughput(self, capsys):
        ok = simulations.run(["throughput", "cold"], requests=3, concurrency=2,
                             port=13443)
        lines = [json.loads(l) for l in
                 capsys.readouterr().out.strip().splitlines()]
        assert ok and [l["errors"] for l in lines] == [0, 0]

    def test_soak_smoke_asserts_clean_books(self, capsys):
        """3s soak over the TPU balancer: mixed load, then zero leaked
        activation slots / concurrency refcounts (the assertions live
        inside soak_simulation)."""
        ok = simulations.run_soak(duration=3.0, concurrency=4, port=13444)
        lines = [json.loads(l) for l in
                 capsys.readouterr().out.strip().splitlines()]
        assert ok
        books = next(l["soak_books"] for l in lines if "soak_books" in l)
        assert books["active_activations"] == 0
        assert books["conc_refcounts"] == 0
        stats = next(l for l in lines if l.get("simulation") == "soak")
        assert stats["errors"] == 0 and stats["requests"] > 0


class TestPlacementSweep:
    def test_single_and_sharded_rows(self):
        import placement_sweep
        row = placement_sweep.bench_single(16, batch=8, iters=2)
        assert row["placements_per_sec"] > 0
        row = placement_sweep.bench_sharded(64, batch=8, iters=2, n_shards=8)
        assert row["config"] == "8-shard" and row["placements_per_sec"] > 0


@pytest.mark.slow
class TestOwperf:
    def test_owperf_csv(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(PERF_DIR, "owperf.py"),
             "--samples", "2", "--ratio", "1", "--port", "13444"],
            capture_output=True, text=True, timeout=180, env=env)
        assert out.returncode == 0, out.stderr
        lines = out.stdout.strip().splitlines()
        assert lines[0].startswith("phase,samples,mean_ms")
        phases = [l.split(",")[0] for l in lines[1:]]
        assert phases == ["action_e2e", "rule_e2e_x1", "waitTime", "initTime",
                          "duration"]


class TestWarmHitParity:
    def test_kernel_matches_oracle_warm_rates(self):
        import warmhit
        out = warmhit.simulate(n_invokers=24, rounds=6, batch=48,
                               n_actions=16)
        assert out["decision_parity"] == 1.0
        assert out["kernel_warm_rate"] == out["oracle_warm_rate"]
        assert out["kernel_warm_rate"] > 0.5  # the workload produces warm hits


class TestBenchFailsLoudly:
    """bench.py has no CPU re-run of a device stage: a device stage without
    a device fails, a rider that produced nothing is named, and either way
    the process exits non-zero (the JSON line still prints)."""

    def _main(self, monkeypatch, capsys, run):
        import json as _json
        import bench
        monkeypatch.setattr(bench, "_reap_leaked_processes", lambda: [])
        monkeypatch.setattr(bench, "_run", run)
        monkeypatch.setattr(sys, "argv", ["bench.py", "--quick"])
        code = None
        try:
            bench.main()
        except SystemExit as e:
            code = e.code
        lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.strip()]
        assert len(lines) == 1
        return code, _json.loads(lines[0])

    def test_no_fallback_machinery_is_left(self):
        import bench
        for gone in ("_ensure_backend", "_probe_backend", "_probe_mesh",
                     "_run_rider", "_rider_subprocess_cpu",
                     "_backend_unavailable", "_pipeline_speedup",
                     "_auto_pick_row"):
            assert not hasattr(bench, gone), gone

    def test_missing_device_exits_nonzero_with_the_error_line(
            self, monkeypatch, capsys):
        from openwhisk_tpu.utils.config import DeviceError

        def no_device(args):
            raise DeviceError("the device balancer needs a TPU")

        code, out = self._main(monkeypatch, capsys, no_device)
        assert code == 1
        assert out["value"] is None and "needs a TPU" in out["error"]

    def test_healthy_run_prints_the_line_and_exits_zero(self, monkeypatch,
                                                         capsys):
        code, out = self._main(
            monkeypatch, capsys,
            lambda args: {"value": 1.0, "backend": "cpu"})
        assert code is None and out == {"value": 1.0, "backend": "cpu"}

    def test_failed_rider_exits_nonzero(self, monkeypatch, capsys):
        code, out = self._main(
            monkeypatch, capsys,
            lambda args: {"value": 1.0, "failed_riders": ["e2e_open_loop"]})
        assert code == 1 and out["failed_riders"] == ["e2e_open_loop"]


class TestBenchRidersMatchLoadgen:
    def test_multiproc_riders_call_loadgen_with_its_signature(
            self, monkeypatch):
        """The multi-process riders drive `loadgen.multiproc_fixed_rate`
        (always the shared topology): their keyword arguments must bind
        to its real signature."""
        import inspect

        import bench
        from tools import loadgen
        sig = inspect.signature(loadgen.multiproc_fixed_rate)
        calls = []

        def fake(*args, **kwargs):
            sig.bind(*args, **kwargs)
            calls.append(kwargs)
            return {"topology": "shared", "procs": kwargs.get("procs"),
                    "sustained": True, "per_worker": [],
                    "fleet_merged_sustained_per_sec": 1.0}

        monkeypatch.setattr(loadgen, "multiproc_fixed_rate", fake)
        assert bench._e2e_multiproc_measure()["procs"] == 2
        assert bench._funnel_10k_measure()["topology"] == "shared"
        assert len(calls) >= 2


class TestE2eOpenLoopRiderFailsLoudly:
    """The `e2e_open_loop` rider measures in ONE device-owning child; when
    that child fails there is no CPU-pinned re-run and no `cpu_fallback`
    block — the rider has no result."""

    def test_dead_device_child_is_not_rerun_on_cpu(self, monkeypatch):
        import bench
        calls = []

        def child(expr, marker, label, pin_cpu=False, **kw):
            calls.append((label, pin_cpu))
            return None

        monkeypatch.setattr(bench, "_subprocess_json", child)
        assert bench._e2e_open_loop() is None
        assert calls == [("e2e_open_loop", False)]

    def test_loadgen_cli_emits_one_json_line_and_exits_nonzero_on_error(
            self, monkeypatch):
        """A broken sweep still produces exactly one parseable JSON line on
        stdout, and the process exits non-zero."""
        import io
        import json as _json
        import sys as _sys
        from tools import loadgen
        monkeypatch.setattr(loadgen, "sweep_balancer",
                            lambda **kw: (_ for _ in ()).throw(
                                RuntimeError("no backend")))
        monkeypatch.setattr(_sys, "argv", ["loadgen"])
        buf = io.StringIO()
        monkeypatch.setattr(_sys, "stdout", buf)
        with pytest.raises(SystemExit) as exc:
            loadgen.main()
        assert exc.value.code == 1
        lines = [l for l in buf.getvalue().splitlines() if l.strip()]
        assert len(lines) == 1
        out = _json.loads(lines[0])
        assert out["sustained_activations_per_sec"] is None
        assert "no backend" in out["error"]


@pytest.mark.slow
class TestOpenLoopSoak:
    """ISSUE 7 satellite: an open-loop soak over the standalone server
    (TPU balancer + real in-process invoker + HTTP surface) asserting the
    waterfall's stage timestamps are monotone per activation and that the
    per-activation stage deltas telescope to the measured total."""

    def test_stage_timestamps_monotone_per_activation(self):
        import harness
        from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL

        async def go(client):
            GLOBAL_WATERFALL.enabled = True
            GLOBAL_WATERFALL.reset()
            assert await client.put_action("ol-soak") == 200
            await client.invoke("ol-soak")  # warm the sandbox + kernels
            await client.invoke("ol-soak")
            GLOBAL_WATERFALL.reset()

            async def one(i):
                status, _ = await client.invoke("ol-soak")
                return status == 200

            stats = await harness.open_loop(60, 25.0, one)
            assert stats.errors == 0
            rows = GLOBAL_WATERFALL.recent(60)
            assert len(rows) >= 55, "most soak activations must finish"
            # the HTTP path stamps the full pipeline: REST accept through
            # completion (record_write races the ack by design)
            want = {"api_accept", "entitle", "throttle", "publish_enqueue",
                    "produce", "invoker_pickup", "container_acquire",
                    "run", "completion_ack"}
            for row in rows:
                assert want <= set(row["stages_ms"]), row
                # monotone: zero causally-ordered stamps arrived out of
                # order (finish() counts every clamp outside the
                # documented record_write race)
                assert row["clamped"] == 0, row
                # no unaccounted gap: deltas telescope to the total
                assert row["total_ms"] == pytest.approx(
                    sum(row["stages_ms"].values()), abs=0.05)
            budget = GLOBAL_WATERFALL.budget()
            assert budget["coverage_ratio"] == pytest.approx(1.0, abs=0.15)

        harness.run_with_standalone(go, port=13449, balancer="tpu")
