"""Tests of the benchmark's own yardstick (benchmark/): the traffic
generator, the roofline bytes, the trace reduction, the data-driven lookup,
the comparison that decides `correct` with its control and its planted
faults, and one toy run end to end on the CPU twin. No assertion here is on
a time or a rate."""
import asyncio
import copy
import json
import os
import re
import shutil

import numpy as np
import pytest

from benchmark import control, reference, roofline, run, trace_reduce, traffic

BENCH = os.path.join(run.ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _toy_root(tmp_path) -> str:
    """A temp copy of the benchmark's data files plus two toy
    configurations (`toyc`: the same fleet with concurrent containers),
    their toy cells and a new metric with a reader of its own: files
    added, none edited."""
    root = str(tmp_path)
    for sub in ("configs", "traffic", "metrics", "readers"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    manifest = run.load_manifest()
    with open(os.path.join(BENCH, "configs", "fleet1k.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy", invokers=16)
    cfg["actions"]["count"] = 48
    cfg["actions"]["service_ms"].update(median=20, min=5, max=120)
    _dump(root, "benchmark/configs/toy.json", cfg)
    cfg = copy.deepcopy(cfg)
    cfg.update(name="toyc", action_concurrency_max=8)
    cfg["actions"]["concurrency"] = {"values": [1, 2, 5],
                                     "weights": [1, 1, 1]}
    _dump(root, "benchmark/configs/toyc.json", cfg)
    base = {"popularity": {"dist": "zipf", "exponent": 1.0},
            "warm_seconds": 0.5, "drain_seconds": 10}
    _dump(root, "benchmark/traffic/toy-closed.json",
          {**base, "loop": "closed", "clients": 96})
    _dump(root, "benchmark/traffic/toy-open.json",
          {**base, "loop": "open", "arrivals": "poisson", "rate_per_s": 200})
    _dump(root, "benchmark/metrics/steps_seen.closed.json",
          {"reader": "steps_seen", "args": {}})
    with open(os.path.join(root, "benchmark/readers/steps_seen.py"), "w") as f:
        f.write("def read(art):\n    return float(len(art['steps'])) or None\n")
    for name in ("toy", "toyc"):
        manifest["configs"].append({"name": name, "source": "test",
                                    "why": "t", "reduced": [],
                                    "file": f"benchmark/configs/{name}.json"})
    for name, loop, e2e in (("toy", "closed", "completed_per_s"),
                            ("toy", "open", "overhead_p50_ms"),
                            ("toyc", "closed", "completed_per_s")):
        manifest["workloads"].append(
            {"name": f"{name}-{loop}", "config": name,
             "traffic": f"toy-{loop}", "chips": 1, "why": "t"})
        for m in manifest["end_to_end"]:
            if m["name"] == e2e:
                m["workloads"].append(f"{name}-{loop}")
    manifest["per_layer"].append(
        {"name": "steps_seen.closed", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "admission and batch assembly",
         "moves": "completed_per_s"})
    _dump(root, "BENCHMARK.json", manifest)
    return root


def _dump(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)


def _run(root, cell, trace=False, faults=None, seed=11, seconds=1.0):
    res = run.resolve_cell(run.load_manifest(root), cell, root)
    device = run.device_or_exit(1)
    out = asyncio.run(run.run_cell(res, seed, seconds, trace, device,
                                   faults=faults))
    return res, device, out


@pytest.fixture(scope="module", autouse=True)
def short_shape_ladder():
    """Two of set-up's six bursts are enough for a toy fleet."""
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "WARM_BURSTS", (8, 32))
    yield
    patch.undo()


@pytest.fixture(scope="module", autouse=True)
def limits_as_they_were():
    """`Sut.start` raises the program's class-constant ceilings to the
    deployment's; hand them back to the tests of other files."""
    from openwhisk_tpu.core.entity import ConcurrencyLimit, MemoryLimit
    was = MemoryLimit.MAX, ConcurrencyLimit.MAX
    yield
    MemoryLimit.MAX, ConcurrencyLimit.MAX = was


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = _toy_root(tmp_path_factory.mktemp("toy"))
    return (root,) + _run(root, "toy-closed")


@pytest.fixture(scope="module")
def toyc(tmp_path_factory):
    root = _toy_root(tmp_path_factory.mktemp("toyc"))
    return (root,) + _run(root, "toyc-closed")


# -- the data files and the lookup ------------------------------------------

def test_manifest_and_data_files_agree():
    m = run.load_manifest()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for entry in (m["configs"] + m["workloads"] + m["end_to_end"]
                  + m["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for cell in m["workloads"]:
        res = run.resolve_cell(m, cell["name"])
        reported = {e["name"] for e in res["end_to_end"]}
        assert len(reported) >= 2 and res["per_layer"]
        for pl in res["per_layer"]:
            assert pl["moves"] in reported
            assert os.path.exists(os.path.join(
                BENCH, "readers", pl["reader"] + ".py"))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))}
    assert on_disk == {p["name"] for p in m["per_layer"]}


def test_new_config_cell_and_metric_are_found_as_files(toy):
    root, res, _device, out = toy
    assert res["config"]["name"] == "toy"
    assert [e["name"] for e in res["end_to_end"]] == ["completed_per_s",
                                                      "setup_s"]
    names = {p["name"] for p in res["per_layer"]}
    assert "steps_seen.closed" in names and "batch_fill.closed" in names
    assert not any(n.endswith(".open") for n in names)
    spec = next(p for p in res["per_layer"] if p["name"] == "steps_seen.closed")
    assert run.read_metric(res, spec, out["art"]) == len(out["art"]["steps"])
    with pytest.raises(run.BenchError):
        run.resolve_cell(run.load_manifest(root), "no-such-cell", root)


# -- traffic -----------------------------------------------------------------

def _traffic(seed):
    with open(os.path.join(BENCH, "configs", "fleet1k.json")) as f:
        cfg = json.load(f)
    mix = {"popularity": {"dist": "zipf", "exponent": 1.0},
           "rate_per_s": 500, "arrivals": "poisson"}
    cat = traffic.make_catalog(cfg, seed)
    ranks = traffic.RankSequence(mix, len(cat.names), seed).take(70000)
    return cat, ranks, traffic.arrival_offsets(mix, 8.0, seed)


def test_traffic_is_a_pure_function_of_the_seed():
    a, b, c = _traffic(2**31 + 7), _traffic(2**31 + 7), _traffic(5)
    assert a[0] == b[0]
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
    assert a[0].names != c[0].names
    assert not np.array_equal(a[1], c[1])
    assert not np.array_equal(a[2], c[2])


def test_every_seed_gets_the_same_work_in_another_order():
    a, c = _traffic(1), _traffic(2)
    assert a[0].memory_mb == c[0].memory_mb
    assert a[0].service_s == c[0].service_s
    blk = traffic.SEQ_BLOCK
    assert np.array_equal(np.bincount(a[1][:blk]), np.bincount(c[1][:blk]))
    assert len(a[2]) == len(c[2]) == 4000
    assert np.allclose(np.sort(np.diff(a[2], prepend=0.0)),
                       np.sort(np.diff(c[2], prepend=0.0)))
    assert a[2][-1] == pytest.approx(8.0) and c[2][-1] == pytest.approx(8.0)
    svc = np.asarray(a[0].service_s)
    assert svc.min() >= 0.010 and svc.max() <= 2.0
    assert abs(np.median(svc) - 0.2) < 0.005
    share = np.bincount(a[1][:blk])[0] / blk      # the hottest action
    assert 0.11 < share < 0.13


# -- roofline ------------------------------------------------------------------

def test_step_bytes_for_two_shapes_worked_by_hand():
    # N=64, B=8, 1 action: books 2*4*64=512, health 64, one column
    # 2*4*64=512, packed in 4*14*8=448, out 4*9=36
    assert roofline.step_bytes(64, 4096, 8, 1) == 512 + 64 + 512 + 448 + 36
    # N=1024, B=256, 200 actions: 8192 + 1024 + 200*8192 + 14336 + 1028
    assert roofline.step_bytes(1024, 4096, 256, 200) == 1662980
    assert roofline.least_step_seconds("TPU v5 lite", 1024, 4096, 256, 200) \
        == pytest.approx(1662980 / 819e9)
    with pytest.raises(KeyError):
        roofline.peak_bytes_per_s("TPU v9 imaginary")
    with pytest.raises(ValueError):
        roofline.step_bytes(64, 4096, 8, 9)


# -- the plain reference --------------------------------------------------------

def test_reference_policy_on_a_case_worked_by_hand():
    fleet = reference.ReferenceFleet(1.0)
    for i in range(3):
        fleet.register(i, 512, True)
    h = reference.generate_hash("ns", "ns/a")
    home, step = h % 3, reference.pairwise_coprimes(3)[h % 2]
    got = [fleet.schedule("ns", "ns/a", 256, rand=1) for _ in range(7)]
    want = [(home, False)] * 2 + [((home + step) % 3, False)] * 2 \
        + [((home + 2 * step) % 3, False)] * 2 + [(1, True)]
    assert got == want
    assert sorted(fleet.free_mb()) == [-256, 0, 0]
    fleet.release(1, 256)
    fleet.set_health(home, False)
    assert fleet.free_mb()[1] == 0
    assert fleet.unusable(3) == 1 and fleet.unusable(4) == 2
    assert reference.pairwise_coprimes(10) == [1, 3, 7]


# -- the trace reduction ----------------------------------------------------------

def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(BENCH, "fixtures", "small.xplane.pb")
    with open(os.path.join(BENCH, "fixtures", "small.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_trace(path)
    assert got["device_planes"] == 1
    # one execution for each of the window's dispatches, in their order
    assert got["step_device_s"] == pytest.approx(want["step_device_s"],
                                                 rel=1e-9)
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert sum(got["step_device_s"]) <= got["busy_s"] * 1.0001
    assert [n for n, _t in got["device_ops"]] == want["device_ops"]
    assert len(got["idle_gaps"]) <= 10
    assert sum(t for _n, t in got["idle_gaps"]) \
        <= got["window_s"] - got["busy_s"] + 1e-9


def test_each_dispatch_is_paired_with_its_own_execution():
    # host starts and device (start, end), one clock, slack 1: the run at
    # 0 was dispatched before the window; the second dispatch (at 20)
    # waits for the first's run to end; the device clock may run ahead of
    # the host's by the slack (run at 49.5 for the dispatch at 50); the
    # last dispatch's run falls after the trace
    runs = [(0, 4), (10.2, 25), (25, 30), (49.5, 52)]
    assert trace_reduce.pair_runs([10, 20, 50, 60], runs, slack=1) \
        == pytest.approx([14.8, 5, 2.5, None])
    assert trace_reduce.pair_runs([], runs) == []
    # PR 32's trace: the device's clock 1.4 ms ahead of the host's, more
    # than a launch takes; a fused step (0.48) and a release-only fold
    # (0.04) in turns, tens of ms apart. Paired one off, every fused step
    # would read as a fold
    host = [18.8, 89.0, 114.9, 190.5, 215.8]
    device = [(17.4, 17.88), (87.8, 87.84), (113.5, 113.98),
              (189.2, 189.24), (214.5, 214.98)]
    want = [0.48, 0.04, 0.48, 0.04, 0.48]
    assert trace_reduce.pair_runs(host, device, slack=5) \
        == pytest.approx(want)
    # an execution inside the slack that was dispatched before the window
    assert trace_reduce.pair_runs(host, [(16.0, 16.04)] + device, slack=5) \
        == pytest.approx(want)
    assert trace_reduce.pair_runs(host, device[:3], slack=5) \
        == pytest.approx(want[:3] + [None, None])


def test_interval_union_and_a_trace_without_a_device():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 6.5)]) \
        == [(0, 3), (5, 7)]
    # a publish (0-10) that dispatches (2-6, JAX's two spans to a call)
    nested = [("pub", (0, 10)), ("jit", (2, 6)), ("jit", (2.5, 5.5)),
              ("ack", (12, 13))]
    assert sorted(trace_reduce._own_time(nested)) == [
        ("ack", (12, 13)), ("jit", (2, 2.5)), ("jit", (2.5, 5.5)),
        ("jit", (5.5, 6)), ("pub", (0, 2)), ("pub", (6, 10))]
    assert trace_reduce.find_trace("/nonexistent") is None


# -- one toy run, its control and its planted faults --------------------------------

def test_toy_run_prints_the_contract_s_last_line(toy):
    _root, res, device, out = toy
    line = run.build_result(res, out, False, device)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checked"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"completed_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(v["value"] <= v["limit"] for v in line["checked"].values())
    assert out["verdict"]["compared"] >= line["attempted"]
    json.dumps(line)
    traced = run.build_result(res, out, True, device)
    assert "batch_fill.closed" in traced["metrics"]
    assert "step_roofline.closed" not in traced["metrics"]  # no trace: silent
    # the step readers count the traced sub-window's fused steps alone
    art = {**out["art"], "device": {"device_kind": "TPU v5 lite"},
           "geometry": {"N": 64, "A": 4096}, "traced_steps": [
               {"fused": True, "B": 8, "distinct": 1, "device_s": 2e-6},
               {"fused": False, "B": 0, "distinct": 0, "device_s": 9e-6},
               {"fused": True, "B": 8, "distinct": 1, "device_s": None}]}
    specs = {p["name"]: p for p in res["per_layer"]}
    assert run.read_metric(res, specs["step_device_ms.closed"], art) \
        == pytest.approx(2e-3)
    assert run.read_metric(res, specs["step_roofline.closed"], art) \
        == pytest.approx(100 * 1572 / 819e9 / 2e-6)


def test_the_control_comes_out_as_not_correct(toy):
    _root, res, _device, out = toy
    ctl = control.control_verdict(out, res["config"])
    assert ctl["correct"] is False
    assert ctl["numbers"]["decision_mismatch"] > 0
    assert out["verdict"]["numbers"]["decision_mismatch"] == 0


def test_mixed_concurrency_toy_run_and_its_control(toyc):
    _root, res, device, out = toyc
    assert sorted(set(out["observed"]["sent"][a][3]
                      for a in out["observed"]["sent"])) == [1, 2, 5]
    line = run.build_result(res, out, False, device)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line["checked"]) == list(reference.LIMITS)
    assert [v["value"] for v in line["checked"].values()] == [0] * 9
    # the second level of books did decide: containers were shared
    reqs = sum(s["b"] for s in out["replayed"]["steps"])
    assert reqs == out["verdict"]["compared"] > line["attempted"] > 0
    assert 0 < out["replayed"]["shared"] < reqs
    # after the drain no container is held: both tables are noughts
    assert not np.count_nonzero(out["observed"]["program_conc_free"])
    assert not out["replayed"]["permits"]
    ctl = control.control_verdict(out, res["config"])
    assert ctl["correct"] is False
    assert ctl["numbers"]["decision_mismatch"] > 0


def _state_unchanged(sut):
    real = sut.bal._packed_fn

    def stuck(state, *args):
        _new, out = real(state, *args)
        return state, out
    sut.bal._packed_fn = stuck


def _answer_altered(sut):
    real = sut.bal._read_back

    def altered(out):
        chosen, forced, throttled, rounds = real(out)
        chosen = np.array(chosen)
        chosen[0] = (chosen[0] + 1) % 16 if chosen[0] >= 0 else chosen[0]
        return chosen, forced, throttled, rounds
    sut.bal._read_back = altered


def _half_the_releases_left_out(sut):
    real, n = sut.bal._queue_release, [0]

    def lossy(*args, **kw):
        n[0] += 1
        if n[0] % 2:
            real(*args, **kw)
    sut.bal._queue_release = lossy


def _an_invoker_held_unusable(sut):
    # the fleet's invoker 3 is up and pinging; the books say otherwise,
    # and the program journals the flip like any other
    sut.bal._health_updates[3] = False


def _spare_permits_ignored(sut):
    # every step starts from a permit table of noughts: a container never
    # takes a second activation once its step is over
    real = sut.bal._packed_fn

    def blind(state, *args):
        return real(state._replace(conc_free=state.conc_free * 0), *args)
    sut.bal._packed_fn = blind


def _release_rows_say_concurrency_one(sut):
    real = sut.bal._queue_release

    def flat(inv, slot, mem, _maxc, key):
        real(inv, slot, mem, 1, key)
    sut.bal._queue_release = flat


def _two_keys_handed_one_slot(sut):
    # the allocator keeps its own count; every key is told slot 7
    real = sut.bal._slots.acquire

    def same(key):
        real(key)
        return 7
    sut.bal._slots.acquire = same


def _one_permit_never_returned(sut):
    real, done = sut.bal._packed_fn, []

    def leaky(state, *args):
        new, out = real(state, *args)
        if not done:
            done.append(1)
            new = new._replace(conc_free=new.conc_free.at[2, 9].add(-1))
        return new, out
    sut.bal._packed_fn = leaky


@pytest.mark.parametrize("fault,number,cell", [
    (_state_unchanged, "decision_mismatch", "toy-closed"),
    (_answer_altered, "decision_mismatch", "toy-closed"),
    (_half_the_releases_left_out, "release_mismatch", "toy-closed"),
    (_an_invoker_held_unusable, "unusable", "toy-closed"),
    (_state_unchanged, "decision_mismatch", "toyc-closed"),
    (_spare_permits_ignored, "decision_mismatch", "toyc-closed"),
    (_release_rows_say_concurrency_one, "unjournaled", "toyc-closed"),
    (_two_keys_handed_one_slot, "slot_conflict", "toyc-closed"),
    (_one_permit_never_returned, "books_mismatch", "toyc-closed"),
])
def test_a_broken_timed_path_comes_out_as_not_correct(tmp_path, fault, number,
                                                      cell):
    root = _toy_root(tmp_path)
    res, device, out = _run(root, cell, faults=fault)
    line = run.build_result(res, out, False, device)
    assert line["correct"] is False
    assert line["checked"][number]["value"] > 0


def test_open_loop_toy_run(tmp_path):
    root = _toy_root(tmp_path)
    res, device, out = _run(root, "toy-open", trace=True)
    line = run.build_result(res, out, False, device)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"overhead_p50_ms", "setup_s"}
    assert line["attempted"] == 200      # rate x window, whatever the seed
    traced = run.build_result(res, out, True, device)
    assert {"fire_lag_p99_ms.open", "overhead_p95_ms.open",
            "overhead_p99_ms.open"} <= set(traced["metrics"])
    assert line["metrics"]["overhead_p50_ms"]["value"] \
        <= traced["metrics"]["overhead_p95_ms.open"]["value"] \
        <= traced["metrics"]["overhead_p99_ms.open"]["value"]
    # a CPU trace has no device plane: the device readers stay silent
    assert not any(n.startswith(("step_", "device_idle"))
                   for n in traced["metrics"])
    assert "busy_s" not in traced["device"] and "breakdown" not in traced


def test_no_accelerator_and_no_cpu_request_is_an_error(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(Exception, match="needs a TPU"):
        run.device_or_exit(1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(run.BenchError):
        run.device_or_exit(4096)
