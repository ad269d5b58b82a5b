"""The configuration `fleet10k-conc` and its cell `fleet10k-conc-zipf-closed`
as files: found by name, a copy of its twin but for what tells them apart,
the cell's two metrics of the fleet's pings read on a traced toy run. The toy is
fleet10k-conc cut in invokers alone (a few hundred, so that registration
still grows the pad several times over), driven end to end on the CPU twin
and held to the plain reference. No assertion here is on a time."""
import asyncio
import copy
import json
import os

import pytest

from benchmark import fleet, run, span_reduce

from tests.perfbench.test_perfbench import _dump, _toy_root

BENCH = os.path.join(run.ROOT, "benchmark")
CELL = "fleet10k-conc-zipf-closed"
PINGS = ("host_ping_us.closed", "pings_per_block.closed")


def _load(rel: str) -> dict:
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


def test_the_cell_its_metrics_and_readers_are_found_as_files():
    m = run.load_manifest()
    res = run.resolve_cell(m, CELL)
    assert res["config"]["name"] == "fleet10k-conc"
    assert res["config"]["invokers"] == 10_240
    assert res["mix"]["name"] == "zipf-closed"
    assert [e["name"] for e in res["end_to_end"]] == ["completed_per_s",
                                                      "setup_s"]
    specs = {p["name"]: p for p in res["per_layer"]}
    for name in PINGS:
        assert specs[name]["layer"] == "fleet and timers"
        assert specs[name]["moves"] == "completed_per_s"
        assert specs[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            BENCH, "readers", specs[name]["reader"] + ".py"))
    assert specs["host_ping_us.closed"]["args"] == {"spans": ["ow_ping"]}
    assert specs["pings_per_block.closed"]["args"] == {"span": "ow_ping",
                                                      "stat": "n"}
    # the 1k twin's metrics, the two of the pings besides
    twin = run.resolve_cell(m, "fleet1k-conc-zipf-closed")
    assert {p["name"] for p in res["per_layer"]} \
        == {p["name"] for p in twin["per_layer"]} | set(PINGS)
    cells = {c["name"]: c for c in m["workloads"]}
    assert cells[CELL]["chips"] == 1


def test_fleet10k_conc_is_fleet1k_conc_key_for_key():
    big, twin = (_load("configs/fleet10k-conc.json"),
                 _load("configs/fleet1k-conc.json"))
    told_apart = {"name", "source", "invokers", "assumed", "reduced",
                  "reduced_why"}
    assert {k: v for k, v in big.items() if k not in told_apart} \
        == {k: v for k, v in twin.items() if k not in told_apart}
    assert list(big) == list(twin)
    assert big["invokers"] == 10_240 and twin["invokers"] == 1_024
    assert big["reduced"] == ["fanout_bursts"]
    assert set(big["reduced_why"]) == {"fanout_bursts"}
    assert big["assumed"][:len(twin["assumed"])] == twin["assumed"]
    assert len(big["assumed"]) == len(twin["assumed"]) + 2
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == "fleet10k-conc")
    assert entry["reduced"] == big["reduced"]
    assert entry["file"] == "benchmark/configs/fleet10k-conc.json"


def test_the_cell_runs_its_twin_s_traffic_file():
    """The two cells differ by fleet width alone: the same traffic file
    (the same callers, actions and service times)."""
    cells = {c["name"]: c for c in run.load_manifest()["workloads"]}
    twin = cells["fleet1k-conc-zipf-closed"]
    assert cells[CELL]["traffic"] == twin["traffic"] == "zipf-closed"
    assert os.path.exists(os.path.join(BENCH, "traffic", "zipf-closed.json"))


# -- a toy of the shape, cut in invokers alone ---------------------------------

@pytest.fixture(scope="module", autouse=True)
def short_shape_ladder():
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "WARM_BURSTS", (8, 32))
    yield
    patch.undo()


@pytest.fixture(scope="module", autouse=True)
def limits_as_they_were():
    from openwhisk_tpu.core.entity import ConcurrencyLimit, MemoryLimit
    was = MemoryLimit.MAX, ConcurrencyLimit.MAX
    yield
    MemoryLimit.MAX, ConcurrencyLimit.MAX = was


#: invokers of the toy: past the 64-row pad three times over
TOY_INVOKERS = 300


def _toy10k_run(tmp_path_factory, tag: str, seed: int):
    root = _toy_root(tmp_path_factory.mktemp(tag))
    # a run directory of its own: the trace read is this run's, whatever
    # the other files' toy runs leave under .bench_run meanwhile
    run_dir = str(tmp_path_factory.mktemp(tag + "-run"))
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "RUN_DIR", run_dir)
    cfg = copy.deepcopy(_load("configs/fleet10k-conc.json"))
    cfg.update(name="toy10k", invokers=TOY_INVOKERS)
    _dump(root, "benchmark/configs/toy10k.json", cfg)
    manifest = run.load_manifest(root)
    manifest["configs"].append({"name": "toy10k", "source": "test",
                                "why": "t", "reduced": [],
                                "file": "benchmark/configs/toy10k.json"})
    manifest["workloads"].append({"name": "toy10k-closed", "config": "toy10k",
                                  "traffic": "toy-closed", "chips": 1,
                                  "why": "t"})
    for m in manifest["end_to_end"]:
        if m["name"] == "completed_per_s":
            m["workloads"].append("toy10k-closed")
    for m in manifest["per_layer"]:
        if m["name"] in PINGS:
            m["workloads"].append("toy10k-closed")
    _dump(root, "BENCHMARK.json", manifest)
    res = run.resolve_cell(manifest, "toy10k-closed", root)
    device = run.device_or_exit(1)
    try:
        out = asyncio.run(run.run_cell(res, seed, 3.5, True, device))
    finally:
        patch.undo()
    return res, device, out, span_reduce.reduce_spans(
        span_reduce.newest_trace(run_dir))


@pytest.fixture(scope="module")
def toy10k(tmp_path_factory):
    return _toy10k_run(tmp_path_factory, "toy10k", 2**31 + 41)


@pytest.fixture(scope="module")
def toy10k_pinging_from_the_top(tmp_path_factory):
    """The toy with its ticks dealt from the top of the fleet down: the
    invokers of the highest indices ping first in every second, as a fleet
    whose pinger runs late wraps round to them, and the program registers
    the rows below them as stand-ins until their own pings come."""
    real = fleet.ping_schedule

    def from_the_top(n):
        return [(p, n - hi, n - lo) for p, lo, hi in real(n)]

    patch = pytest.MonkeyPatch()
    patch.setattr(fleet, "ping_schedule", from_the_top)
    try:
        return _toy10k_run(tmp_path_factory, "toy10k-top", 2**31 + 43)
    finally:
        patch.undo()


def test_the_toy_of_the_shape_is_correct_and_reads_the_pings(toy10k,
                                                             monkeypatch):
    """(A CPU trace has no device plane, so `art` holds no window and
    `for_run` stays silent there; the reduction of the run's own trace is
    handed to the readers directly.)"""
    res, device, out, red = toy10k
    line = run.build_result(res, out, False, device)
    assert line["correct"] is True and line["failed"] == 0
    assert [v["value"] for v in line["checked"].values()] == [0] * 9
    assert out["log"]["geometry"]["N"] == 512
    # every invoker registered in waves of a wake's pings, one `reg`
    # record a wave, and usable on the device from the window's first step
    regs = [r for r in out["records"] if r.get("t") == "reg"]
    assert sum(len(r["reg"]) for r in regs) == TOY_INVOKERS
    assert len(regs) < TOY_INVOKERS // 10
    assert out["verdict"]["numbers"]["unusable"] == 0
    stats = red["by_name"]["ow_ping"]["stats"]
    assert stats["_events"] > 0 and stats["n"] >= stats["_events"]
    monkeypatch.setattr(span_reduce, "for_run", lambda art: red)
    specs = {p["name"]: p for p in res["per_layer"]}
    per_act = run.read_metric(res, specs["host_ping_us.closed"], out["art"])
    assert per_act == pytest.approx(
        red["by_name"]["ow_ping"]["own_s"] * 1e6 / red["activations"])
    assert per_act > 0
    # a tick of the simulated fleet is 100 pings here; a wake takes up to
    # the health feed's 128
    per_block = run.read_metric(res, specs["pings_per_block.closed"],
                                out["art"])
    assert per_block == pytest.approx(stats["n"] / stats["_events"])
    assert 1 <= per_block <= 128


def test_the_toy_s_log_line_says_what_the_fleet_delivered(toy10k):
    """`pings_per_s` and `ping_gap_max_s`: the pings the simulated fleet
    sent in the window over its length, and the longest silence of one
    invoker in it. 300 invokers ping once a second each; a wake sends every
    ping that fell due, so no invoker is silent for less than about its
    second, nor for as long as the program's offline timeout (which
    `unusable` 0 says too). The two fields bound each other, whatever the
    loop's lateness: an invoker silent at most G seconds at a time pings at
    least W / G - 1 times in a window of W, and one whose pings keep their
    schedule at most once a second, a tick at either end besides."""
    from openwhisk_tpu.controller.loadbalancer.supervision import \
        PING_TIMEOUT_S
    _res, _device, out, _red = toy10k
    log = out["log"]
    window_s, gap_s = log["window_s"], log["ping_gap_max_s"]
    assert 0.9 < gap_s < PING_TIMEOUT_S
    sent = log["pings_per_s"] * window_s
    assert TOY_INVOKERS * (window_s / gap_s - 1) <= sent \
        <= TOY_INVOKERS * (window_s + 2)
    assert out["verdict"]["numbers"]["unusable"] == 0


def test_invokers_that_ping_out_of_order_are_registered_as_upstream_pads(
        toy10k_pinging_from_the_top):
    """A row registered ahead of its invoker's ping stands in with the
    memory of the invoker whose ping padded the fleet (upstream's
    InvokerPool.registerInvoker) and keeps those books when its own
    invoker pings; the plain reference pads the same way, so the run is
    correct. The first `reg` record shows the order: its first entries all
    name the invoker that padded them."""
    res, device, out, _red = toy10k_pinging_from_the_top
    regs = [r for r in out["records"] if r.get("t") == "reg"]
    first = [int(j["instance"]) for j in regs[0]["reg"]]
    assert first[0] > 0 and first.count(first[0]) > 1
    assert sum(len(r["reg"]) for r in regs) == TOY_INVOKERS
    line = run.build_result(res, out, False, device)
    assert [v["value"] for v in line["checked"].values()] == [0] * 9
    assert line["correct"] is True
