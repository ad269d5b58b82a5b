"""Tests of the reader `span_ratio` and of the two metrics that read it
(ISSUE 28): the ratio on a reduction whose `ow_fanout` carries the stats,
silence (None, never 0) on the recorded fixture, whose program is from
before `ow_fanout` carried `warm` and `forced`, as the parent commit is
under the driver, and both metrics non-null on a traced toy run of this
tree with concurrent containers. No assertion here is on a time."""
import asyncio
import json
import os

import pytest

from benchmark import run, span_reduce

from tests.perfbench.test_perfbench import _toy_root
from tests.perfbench.test_span_metrics import (FIXTURE, OLD_FIXTURE, WANT,
                                               run_dir)  # noqa: F401

CELL = "fleet1k-conc-zipf-closed"
METRICS = ("warm_share.closed", "forced_share.closed")


def _specs(cell: str = CELL) -> tuple:
    res = run.resolve_cell(run.load_manifest(), cell)
    return res, {p["name"]: p for p in res["per_layer"]}


def test_the_cells_that_report_the_two_shares():
    m = run.load_manifest()
    reporting = {name: [c["name"] for c in m["workloads"]
                        if name in {p["name"] for p in run.resolve_cell(
                            m, c["name"])["per_layer"]}]
                 for name in METRICS}
    # the warm share where containers are shared; the forced share in
    # every closed cell, the accepted one included (no `workloads` key)
    assert reporting["warm_share.closed"] == [CELL]
    assert reporting["forced_share.closed"] == [
        "fleet1k-zipf-closed", CELL, "standalone16-noop-closed",
        "frontdoor16-noop-closed"]
    _res, specs = _specs()
    for name, num in zip(METRICS, ("warm", "forced")):
        assert specs[name]["reader"] == "span_ratio"
        assert specs[name]["args"] == {"span": "ow_fanout", "num": num,
                                       "den": "b"}
        assert (specs[name]["unit"], specs[name]["layer"],
                specs[name]["moves"], specs[name]["source"]) == (
            "%", "device step", "completed_per_s", "program_counter")


@pytest.mark.parametrize("stats,want", [
    ({"_events": 3, "seq": 6, "b": 40, "warm": 10, "forced": 0},
     (25.0, 0.0)),
    ({"_events": 1, "seq": 1, "b": 8, "warm": 0, "forced": 8}, (0.0, 100.0)),
    # a program from before the stats: silent, not 0
    ({"_events": 3, "seq": 6, "b": 40}, (None, None)),
    # no row of any step in the sub-window: no share
    ({"_events": 2, "seq": 3, "b": 0, "warm": 0, "forced": 0}, (None, None)),
], ids=["both", "all-forced", "no-such-stat", "no-rows"])
def test_the_ratio_on_a_reduction(monkeypatch, stats, want):
    red = {"window_s": 3.0, "by_name": {"ow_fanout": {
        "count": stats["_events"], "own_s": 0.01, "idle_s": 0.0,
        "stats": stats}}}
    monkeypatch.setattr(span_reduce, "for_run", lambda art: red)
    res, specs = _specs()
    got = tuple(run.read_metric(res, specs[n], {}) for n in METRICS)
    assert got == want


def test_no_fanout_span_and_no_trace_mean_no_reading(monkeypatch):
    res, specs = _specs()
    monkeypatch.setattr(span_reduce, "for_run",
                        lambda art: {"window_s": 3.0, "by_name": {}})
    assert [run.read_metric(res, specs[n], {}) for n in METRICS] \
        == [None, None]
    monkeypatch.undo()
    assert [run.read_metric(res, specs[n], {"trace": None})
            for n in METRICS] == [None, None]


@pytest.mark.parametrize("fixture", [FIXTURE, OLD_FIXTURE],
                         ids=["spans-without-the-stats", "no-spans"])
def test_a_recorded_trace_of_an_older_program_reads_none(run_dir, fixture):
    """PR 25's fixture has `ow_fanout` with `seq` and `b` alone; PR 24's
    has no program span at all. Neither reads 0."""
    run_dir(fixture)
    window = WANT["window_s"]
    if fixture == OLD_FIXTURE:
        with open(os.path.join(run.ROOT, "benchmark", "fixtures",
                               "small.expected.json")) as f:
            window = json.load(f)["window_s"]
    art = {"trace": {"window_s": window}}
    red = span_reduce.for_run(art)
    if fixture == FIXTURE:
        assert red["by_name"]["ow_fanout"]["stats"]["b"] > 0
        assert "warm" not in red["by_name"]["ow_fanout"]["stats"]
    res, specs = _specs()
    for name in METRICS:
        assert run.read_metric(res, specs[name], art) is None


def test_a_traced_toy_run_with_shared_containers_reads_both(tmp_path,
                                                            monkeypatch):
    """This tree's program on the CPU twin, concurrency {1, 2, 5}, traced:
    the run is correct, its `ow_fanout` spans carry both stats, and the
    two metrics read them. (A CPU trace has no device plane, so `art`
    holds no window and `for_run` stays silent there; the reduction of the
    run's own trace is handed to the readers directly.)"""
    from openwhisk_tpu.core.entity import ConcurrencyLimit, MemoryLimit
    monkeypatch.setattr(MemoryLimit, "MAX", MemoryLimit.MAX)
    monkeypatch.setattr(ConcurrencyLimit, "MAX", ConcurrencyLimit.MAX)
    monkeypatch.setattr(run, "WARM_BURSTS", (8, 32))
    # a run directory of its own: the trace read is this run's, whatever
    # the other files' toy runs leave under .bench_run meanwhile
    monkeypatch.setattr(run, "RUN_DIR", str(tmp_path / "run"))
    root = _toy_root(tmp_path)
    res = run.resolve_cell(run.load_manifest(root), "toyc-closed", root)
    device = run.device_or_exit(1)
    out = asyncio.run(run.run_cell(res, 28, 4.0, True, device))
    line = run.build_result(res, out, False, device)
    assert line["correct"] is True and line["failed"] == 0
    assert out["log"]["placed_on_a_spare_permit"] > 0
    red = span_reduce.reduce_spans(
        span_reduce.newest_trace(str(tmp_path / "run")))
    stats = red["by_name"]["ow_fanout"]["stats"]
    assert stats["b"] > 0 and 0 < stats["warm"] < stats["b"]
    assert stats["warm"] + stats["forced"] <= stats["b"]
    monkeypatch.setattr(span_reduce, "for_run", lambda art: red)
    _res, specs = _specs()
    warm, forced = (run.read_metric(res, specs[n], out["art"])
                    for n in METRICS)
    assert warm == pytest.approx(100.0 * stats["warm"] / stats["b"])
    assert forced == pytest.approx(100.0 * stats["forced"] / stats["b"])
