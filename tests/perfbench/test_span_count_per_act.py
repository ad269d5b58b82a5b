"""Tests of the reader `span_count_per_act` and of the two metrics that
read it (ISSUE 39): `feed_wakes_per_act.*`, the count of `ow_feed` events
on the event loop's thread over the window's activations; with `stat`, the
sum of a stat over them (`ow_invoke_done`'s `polls`). The ratio on a
reduction worked by hand and on the recorded fixture (for spans it holds),
silence (None, never 0) where the span never occurred, as on the fixture,
whose program is from before `ow_feed`, and as the parent commit is under
the driver. ISSUE 39's three front-door metrics are not in the manifest
(`tests/perfbench/test_frontdoor.py` pins that cell's metrics, and is not
this PR's to edit): their specs are read here as a `benchmark` PR would
add them. No assertion here is on a time."""
import json
import os

import pytest

from benchmark import run, span_reduce

from tests.perfbench.test_span_metrics import FIXTURE, WANT, run_dir  # noqa: F401

BENCH = os.path.join(run.ROOT, "benchmark")
WAKES = {"closed": ("feed_wakes_per_act.closed", "standalone16-noop-closed",
                    "completed_per_s"),
         "open": ("feed_wakes_per_act.open", "standalone16-noop-open",
                  "overhead_p50_ms")}
#: the front door's three as PERF.md section 7 gives them for a `benchmark` PR
FRONT = {"http_edge_host_us.closed": ("span_own_us", {"spans": [
             "ow_http_auth", "ow_invoke_done", "ow_http_respond"]}),
         "frontdoor_host_us.closed": ("span_own_us", {"spans": [
             "ow_http_entitle", "ow_http_body", "ow_http_resolve",
             "ow_invoke"]}),
         "blocking_polls_per_act.closed": ("span_count_per_act", {
             "span": "ow_invoke_done", "stat": "polls"})}


def _spec(name: str, cell: str) -> tuple:
    res = run.resolve_cell(run.load_manifest(), cell)
    return res, next(p for p in res["per_layer"] if p["name"] == name)


@pytest.mark.parametrize("loop", sorted(WAKES))
def test_every_cell_of_its_loop_reports_the_wakes(loop):
    name, _cell, e2e = WAKES[loop]
    m = run.load_manifest()
    entry = next(p for p in m["per_layer"] if p["name"] == name)
    # no `workloads` key: every cell that reports the end-to-end metric,
    # those of later PRs too, since every cell's bus runs MessageFeeds
    assert "workloads" not in entry
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("wake/act", "lower", "program_counter",
                                "bus produce to completion ack", e2e)
    cells = next(e for e in m["end_to_end"] if e["name"] == e2e)["workloads"]
    for cell in cells:
        res = run.resolve_cell(m, cell)
        spec = next(p for p in res["per_layer"] if p["name"] == name)
        assert spec["reader"] == "span_count_per_act"
        assert spec["args"] == {"span": "ow_feed"}


def _front(name: str) -> tuple:
    res = run.resolve_cell(run.load_manifest(), "frontdoor16-noop-closed")
    reader, args = FRONT[name]
    return res, {"name": name, "reader": reader, "args": args}


@pytest.mark.parametrize("name", sorted(FRONT))
def test_the_front_door_s_three_read_the_new_spans(monkeypatch, name):
    """Not in the manifest (the cell's metrics are pinned by a test of the
    benchmark's); through the readers that are there, each reads its spans
    over the window's activations."""
    assert name not in {p["name"] for p in run.load_manifest()["per_layer"]}
    assert not os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
    spans = ("ow_http_auth", "ow_http_entitle", "ow_http_body",
             "ow_http_resolve", "ow_invoke", "ow_invoke_done",
             "ow_http_respond")
    by_name = {s: {"count": 320, "own_s": 0.001 * (i + 1), "idle_s": 0.0,
                   "stats": {"_events": 320, "req": 320, "polls": 32}}
               for i, s in enumerate(spans)}
    red = {"window_s": 3.0, "activations": 320, "by_name": by_name}
    monkeypatch.setattr(span_reduce, "for_run", lambda art: red)
    res, spec = _front(name)
    reader, args = FRONT[name]
    want = (32 / 320 if reader == "span_count_per_act" else
            sum(by_name[s]["own_s"] for s in args["spans"]) * 1e6 / 320)
    assert run.read_metric(res, spec, {}) == pytest.approx(want)


@pytest.mark.parametrize("count,activations,want", [
    (480, 320, 1.5), (64, 256, 0.25),
    # the span never occurred, or nothing was activated: no reading
    (0, 320, None), (None, 320, None), (12, 0, None),
], ids=["several-wakes-an-activation", "batched-wakes", "zero-events",
        "no-such-span", "no-activations"])
def test_the_ratio_on_a_reduction(monkeypatch, count, activations, want):
    by_name = {"ow_assemble": {"count": 3, "own_s": 0.01, "idle_s": 0.0,
                               "stats": {"b": activations}}}
    if count is not None:
        by_name["ow_feed"] = {"count": count, "own_s": 0.001, "idle_s": 0.0,
                              "stats": {"_events": count, "n": 2 * count}}
    red = {"window_s": 3.0, "activations": activations, "by_name": by_name}
    monkeypatch.setattr(span_reduce, "for_run", lambda art: red)
    for loop in WAKES:
        name, cell, _e2e = WAKES[loop]
        res, spec = _spec(name, cell)
        got = run.read_metric(res, spec, {})
        assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("events,polls,want", [
    (320, 64, 0.2), (320, 0, 0.0),
    # a program without `ow_invoke_done` (the parent's): no reading, not 0
    (None, None, None),
], ids=["some-waits-polled", "no-wait-polled", "no-such-span"])
def test_the_polls_on_a_reduction(monkeypatch, events, polls, want):
    """`blocking_polls_per_act.closed`: the sum of `ow_invoke_done`'s
    `polls` over the window's activations; silent where the span is."""
    by_name = {"ow_assemble": {"count": 3, "own_s": 0.01, "idle_s": 0.0,
                               "stats": {"b": 320}}}
    if events is not None:
        by_name["ow_invoke_done"] = {
            "count": events, "own_s": 0.001, "idle_s": 0.0,
            "stats": {"_events": events, "req": 7 * events, "polls": polls}}
    red = {"window_s": 3.0, "activations": 320, "by_name": by_name}
    monkeypatch.setattr(span_reduce, "for_run", lambda art: red)
    res, spec = _front("blocking_polls_per_act.closed")
    got = run.read_metric(res, spec, {})
    assert got == (None if want is None else pytest.approx(want))


def test_no_trace_means_no_reading():
    for loop in WAKES:
        name, cell, _e2e = WAKES[loop]
        res, spec = _spec(name, cell)
        assert run.read_metric(res, spec, {"trace": None}) is None


def test_the_recorded_trace_reads_none_and_counts_what_it_holds(run_dir):
    """The fixture (PR 25's program) has no `ow_feed`: the two metrics are
    silent there. The reader's count, given a span the fixture holds, is
    the hand-checked count of `spans.expected.json` over its activations."""
    run_dir(FIXTURE)
    art = {"trace": {"window_s": WANT["window_s"]}}
    for loop in WAKES:
        name, cell, _e2e = WAKES[loop]
        res, spec = _spec(name, cell)
        assert run.read_metric(res, spec, art) is None
    for span in ("ow_produce", "ow_placed", "ow_ack_decode"):
        spec = {**spec, "args": {"span": span}}
        assert run.read_metric(res, spec, art) == pytest.approx(
            WANT["spans"][span][0] / WANT["activations"], rel=1e-12)
