"""The entry `http` (benchmark/frontdoor.py, benchmark/httpgen.py): a toy
front-door configuration run end to end on the CPU twin, closed and open,
with the generator in a process of its own; the front door's answer broken
three ways; the lookup of the new cell. Every run has a time limit of its
own; no assertion here is on a time or a rate."""
import asyncio
import inspect
import json
import os
import shutil

import pytest

from benchmark import control, reference, run

BENCH = os.path.join(run.ROOT, "benchmark")
CELL = "frontdoor16-noop-closed"
FRONT = ("frontdoor_ms.closed", "http_edge_ms.closed")
RUN_LIMIT_S = 150.0


def _dump(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)


def _toy_root(tmp_path) -> str:
    """A temp copy of the benchmark's data files plus a toy `entry: http`
    configuration (frontdoor16 with three actions on invokers of two slots
    each, so that the callers nearly fill the fleet as the cell's do) and
    its two cells: files added, none edited."""
    root = str(tmp_path)
    for sub in ("configs", "traffic", "metrics", "readers"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    manifest = run.load_manifest()
    with open(os.path.join(BENCH, "configs", "frontdoor16.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toyhttp", invoker_memory_mb=512)
    cfg["actions"]["count"] = 3
    _dump(root, "benchmark/configs/toyhttp.json", cfg)
    base = {"popularity": {"dist": "uniform"}, "warm_seconds": 0.5,
            "drain_seconds": 3}
    _dump(root, "benchmark/traffic/toyh-closed.json",
          {**base, "loop": "closed", "clients": 24})
    _dump(root, "benchmark/traffic/toyh-open.json",
          {**base, "loop": "open", "arrivals": "poisson", "rate_per_s": 100,
           "connections": 64})
    manifest["configs"].append({"name": "toyhttp", "source": "test",
                                "why": "t", "reduced": [],
                                "file": "benchmark/configs/toyhttp.json"})
    for loop, e2e in (("closed", "completed_per_s"),
                      ("open", "overhead_p50_ms")):
        manifest["workloads"].append(
            {"name": f"toyhttp-{loop}", "config": "toyhttp",
             "traffic": f"toyh-{loop}", "chips": 1, "why": "t"})
        for m in manifest["end_to_end"]:
            if m["name"] == e2e:
                m["workloads"].append(f"toyhttp-{loop}")
    for m in manifest["per_layer"]:
        if m["name"] in FRONT:
            m["workloads"].append("toyhttp-closed")
    _dump(root, "BENCHMARK.json", manifest)
    return root


def _run(root, cell, faults=None, seed=2**31 + 11, seconds=1.5):
    res = run.resolve_cell(run.load_manifest(root), cell, root)
    device = run.device_or_exit(1)
    out = asyncio.run(asyncio.wait_for(
        run.run_cell(res, seed, seconds, False, device, faults=faults),
        RUN_LIMIT_S))
    return res, device, out


@pytest.fixture(scope="module", autouse=True)
def short_shape_ladder():
    """The three buckets 24 callers can fill; a bucket first met inside a
    one-second window would compile through all of it."""
    patch = pytest.MonkeyPatch()
    patch.setattr(run, "WARM_BURSTS", (8, 16, 32))
    yield
    patch.undo()


@pytest.fixture(scope="module", autouse=True)
def limits_as_they_were():
    from openwhisk_tpu.core.entity import ConcurrencyLimit, MemoryLimit
    was = (MemoryLimit.MIN, MemoryLimit.STD, MemoryLimit.MAX,
           ConcurrencyLimit.MIN, ConcurrencyLimit.STD, ConcurrencyLimit.MAX)
    yield
    (MemoryLimit.MIN, MemoryLimit.STD, MemoryLimit.MAX, ConcurrencyLimit.MIN,
     ConcurrencyLimit.STD, ConcurrencyLimit.MAX) = was


@pytest.fixture(scope="module")
def closed(tmp_path_factory):
    root = _toy_root(tmp_path_factory.mktemp("toyhttp"))
    seen = {}

    def watch(sut):
        seen["sut"] = sut
        seen["posts"] = _door(sut)
    # on a host that other work holds up, a round of the 24 callers can
    # outlast a toy's window and leave it empty: then a longer one
    for seconds in (1.5, 6.0, 24.0):
        got = _run(root, "toyhttp-closed", faults=watch, seconds=seconds)
        if got[2]["attempted"] >= 48:
            break
    return got + (seen,)


def _door(sut, alter=None) -> list:
    """Count the invokes the front door's handler sees, and hand every
    fifth answer to `alter(web, request, response)`, which may be async."""
    from aiohttp import web
    real, n = sut.controller.api._invoke_action, [0]

    async def watched(request, ns, fqn):
        n[0] += 1
        resp = await real(request, ns, fqn)
        if alter is None or n[0] % 5:
            return resp
        resp = alter(web, request, resp)
        return await resp if inspect.isawaitable(resp) else resp
    sut.controller.api._invoke_action = watched
    return n


# -- the lookup ----------------------------------------------------------------

def test_the_new_cell_its_metrics_and_their_readers_are_found_as_files():
    res = run.resolve_cell(run.load_manifest(), CELL)
    assert res["config"]["entry"] == "http"
    assert res["config"]["name"] == "frontdoor16"
    assert res["mix"]["name"] == "noop-closed"
    assert [e["name"] for e in res["end_to_end"]] == ["completed_per_s",
                                                      "setup_s"]
    specs = {p["name"]: p for p in res["per_layer"]}
    for name in FRONT:
        assert specs[name]["layer"] == "front door"
        assert specs[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            BENCH, "readers", specs[name]["reader"] + ".py"))
    # every keyless `.closed` metric is the new cell's too, and no other
    # cell reports the front door's two
    twin = run.resolve_cell(run.load_manifest(), "standalone16-noop-closed")
    assert {p["name"] for p in res["per_layer"]} \
        == {p["name"] for p in twin["per_layer"]} | set(FRONT)
    assert run.entry_of(res["config"]).__name__ == "benchmark.frontdoor"
    assert run.entry_of(twin["config"]) is run
    with pytest.raises(run.BenchError):
        run.entry_of({"entry": "carrier-pigeon"})


def test_frontdoor16_is_standalone16_key_for_key():
    with open(os.path.join(BENCH, "configs", "frontdoor16.json")) as f:
        front = json.load(f)
    with open(os.path.join(BENCH, "configs", "standalone16.json")) as f:
        twin = json.load(f)
    told_apart = {"name", "source", "entry", "limits", "guarantees",
                  "assumed"}
    assert {k: v for k, v in front.items() if k not in told_apart} \
        == {k: v for k, v in twin.items() if k not in told_apart}
    assert front["guarantees"][:3] == twin["guarantees"]
    assert len(front["guarantees"]) == 4 and front["reduced"] == []
    assert set(front["limits"]) == {"invocations_per_minute",
                                    "concurrent_invocations",
                                    "fires_per_minute"}


def test_the_edge_reader_on_a_case_worked_by_hand():
    res = run.resolve_cell(run.load_manifest(), CELL)
    specs = {p["name"]: p for p in res["per_layer"]}
    stages = ["api_accept", "publish_enqueue", "completion_ack",
              "record_write"]
    # 4 activations finished: 1 + 5 + 2 ms inside the stamps (the record's
    # write is past the ack); the clients waited 10 ms on average
    art = {"response_ms": [8.0, 9.0, 11.0, 12.0], "service_ms_mean": 0.0,
           "waterfall": {"stages": stages, "sum_us": [4000, 20000, 8000, 900],
                         "count": [4, 4, 4, 1]}}
    assert run.read_metric(res, specs["http_edge_ms.closed"], art) \
        == pytest.approx(10.0 - 8.0)
    art["waterfall"]["stages"] = ["api_accept", "entitle", "throttle",
                                  "record_write"]
    assert run.read_metric(res, specs["frontdoor_ms.closed"], art) \
        == pytest.approx(8.0)
    for silent in ({}, {"response_ms": [], "waterfall": art["waterfall"]},
                   {"response_ms": [1.0], "waterfall": {
                       "stages": stages, "sum_us": [0] * 4,
                       "count": [0] * 4}}):
        assert run.read_metric(res, specs["http_edge_ms.closed"],
                               silent) is None


# -- the toy runs ----------------------------------------------------------------

def test_closed_toy_run_over_http_prints_the_contract_s_last_line(closed):
    res, device, out, _seen = closed
    line = run.build_result(res, out, False, device)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checked"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"completed_per_s", "setup_s"}
    assert list(line["checked"]) == list(reference.LIMITS)
    assert [v["value"] for v in line["checked"].values()] == [0] * 9
    json.dumps(line)
    traced = run.build_result(res, out, True, device)["metrics"]
    assert set(FRONT) <= set(traced) and "batch_fill.closed" in traced


def test_the_control_over_the_front_door_comes_out_as_not_correct(closed):
    res, _device, out, _seen = closed
    ctl = control.control_verdict(out, res["config"])
    assert ctl["correct"] is False
    assert ctl["numbers"]["decision_mismatch"] > 0
    assert out["verdict"]["numbers"]["decision_mismatch"] == 0


def test_every_request_crossed_http_from_another_process(closed):
    _res, _device, out, seen = closed
    log, sut = out["log"], seen["sut"]
    assert log["entry"] == "http"
    assert log["generator_pid"] != log["server_pid"] == os.getpid()
    assert log["http_status"] == {"200": len(sut.rank)}
    # one row a request the handler saw, one journaled activation a row
    assert seen["posts"][0] == len(sut.rank) == len(set(sut.aid))
    journaled = {a for r in out["records"] if r.get("t") == "batch"
                 for a in r["aids"][:r["b"]]}
    assert journaled == set(sut.aid) == set(out["observed"]["sent"])
    assert out["verdict"]["compared"] == len(sut.rank) > out["attempted"]
    assert log["generator_lag_p99_ms"] is not None
    assert log["generator_lag_probes"] > 0
    # the child has gone with the run
    with pytest.raises(ProcessLookupError):
        os.kill(log["generator_pid"], 0)


def test_open_toy_run_over_http(tmp_path):
    root = _toy_root(tmp_path)
    res, device, out = _run(root, "toyhttp-open", seed=7)
    line = run.build_result(res, out, False, device)
    assert line["correct"] is True and line["failed"] == 0
    assert [v["value"] for v in line["checked"].values()] == [0] * 9
    assert set(line["metrics"]) == {"overhead_p50_ms", "setup_s"}
    assert line["attempted"] == 150      # rate x window, whatever the seed
    assert len(out["art"]["fire_lag_ms"]) == 150
    traced = run.build_result(res, out, True, device)["metrics"]
    assert {"fire_lag_p99_ms.open", "overhead_p95_ms.open"} <= set(traced)
    assert not set(FRONT) & set(traced)


# -- the front door's answer, broken ------------------------------------------------

def _a_body_with_another_id(sut):
    def alter(web, _request, resp):
        doc = json.loads(resp.body)
        doc["activationId"] = doc["activationId"][::-1]
        return web.json_response(doc, status=resp.status)
    sut.posts = _door(sut, alter)


def _a_502(sut):
    def alter(web, _request, resp):
        return web.json_response(json.loads(resp.body), status=502)
    sut.posts = _door(sut, alter)


def _a_response_killed(sut):
    def alter(_web, request, resp):
        request.transport.abort()
        return resp
    sut.posts = _door(sut, alter)


def _an_answer_that_comes_too_late(sut):
    async def alter(_web, _request, resp):
        await asyncio.sleep(8.0)        # past the toy's drain and late wait
        return resp
    sut.posts = _door(sut, alter)


@pytest.mark.parametrize("fault,numbers,correct", [
    (_a_body_with_another_id, ("not_once", "unjournaled"), False),
    (_a_502, ("lost",), False),
    (_a_response_killed, ("lost", "not_once", "unjournaled"), False),
    (_an_answer_that_comes_too_late, ("lost", "not_once", "unjournaled"),
     False),
])
def test_a_broken_front_door_comes_out_under_its_number(tmp_path, fault,
                                                        numbers, correct,
                                                        monkeypatch):
    root = _toy_root(tmp_path)
    # the wait past the drain: a second here, a minute in a run
    monkeypatch.setattr(run, "LATE_WAIT_S", 1.0)
    seen = {}

    def plant(sut):
        seen["sut"] = sut
        fault(sut)
    res, device, out = _run(root, "toyhttp-closed", faults=plant)
    line = run.build_result(res, out, False, device)
    sut = seen["sut"]
    assert line["correct"] is correct
    for number in numbers:
        assert line["checked"][number]["value"] > 0, number
    # every request the child sent has exactly one row, answered or not
    assert len(sut.rank) == sut.posts[0]
    status = out["log"]["http_status"]
    assert sum(status.values()) == len(sut.rank)
    # `failed` counts the WINDOW's requests alone, and how many of the
    # altered answers fall into a toy's window is the host's to say (every
    # caller may be stuck before it opens): over the whole run some did
    broken = [i for i, ok in enumerate(sut.ok) if not ok]
    assert line["failed"] == sum(1 for i in broken if sut.in_window[i])
    if fault is _a_body_with_another_id:
        assert not broken
    else:
        assert broken
    if fault is _a_502:
        assert status["502"] > 0
        # every id was the journal's: the placement below the door is sound
        # (an id the catalogue cannot name is one the reference cannot
        # replay, so the two other faults show in its decisions as well)
        for number in ("unjournaled", "decision_mismatch", "books_mismatch"):
            assert line["checked"][number]["value"] == 0, number
    elif fault in (_a_response_killed, _an_answer_that_comes_too_late):
        unanswered = [a for a in sut.aid if a.startswith("unanswered-")]
        assert len(unanswered) == status["None"] > 0
        assert line["checked"]["lost"]["value"] == len(unanswered)
    else:
        assert status == {"200": len(sut.rank)}
