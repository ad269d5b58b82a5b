"""Tests of the span reduction (benchmark/span_reduce.py) and of the
per-layer metrics that read it (ISSUE 25): own time and the
charge-to-ancestor rule on a case worked by hand, the recorded fixture
against a plain interval-by-interval reference, the window-length guard,
and silence on a trace recorded before the program had spans. No assertion
here is on a time measured by this test."""
import json
import os
import shutil

import pytest

from benchmark import run, span_reduce, trace_reduce

BENCH = os.path.join(run.ROOT, "benchmark")
FIXTURE = os.path.join(BENCH, "fixtures", "spans.xplane.pb")
OLD_FIXTURE = os.path.join(BENCH, "fixtures", "small.xplane.pb")
with open(os.path.join(BENCH, "fixtures", "spans.expected.json")) as _f:
    WANT = json.load(_f)
NEW = ("host_admit_us", "host_dispatch_us", "host_planes_us",
       "host_journal_us", "host_readback_us", "host_produce_us",
       "host_ack_us", "host_fleet_us", "host_gc_us",
       "readback_thread_wait_ms", "ack_batch_fill", "loop_spanned_share",
       "loop_block_max_ms")


def _specs(cell: str) -> dict:
    res = run.resolve_cell(run.load_manifest(), cell)
    return res, {p["name"]: p for p in res["per_layer"]}


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """A `.bench_run` of its own, holding one trace, and a fresh cache."""
    def place(fixture: str) -> None:
        dest = tmp_path / "trace-cell" / "plugins" / "profile" / "t0"
        dest.mkdir(parents=True, exist_ok=True)
        shutil.copy(fixture, dest / "host.xplane.pb")
    monkeypatch.setattr(span_reduce, "RUN_DIR", str(tmp_path))
    monkeypatch.setattr(span_reduce, "_CACHE", {})
    return place


# -- the rules, on a case worked by hand ---------------------------------------

#: a publish of the harness (0-100) that admits (10-90), assembles (20-30)
#: and steps (30-60, JAX's two spans to a call nested in it), then the
#: books copy's dispatch (62-70) under `ow_books_ref` (61-72); a later
#: dispatch (110-120) under no program span; an ack frame (130-150)
HAND = [("bench_publish", (0, 100)), ("ow_admit", (10, 90)),
        ("ow_assemble", (20, 30)), ("ow_step", (30, 60)),
        ("PjitFunction(packed)", (35, 55)),
        ("PjitFunction(packed)", (36, 54)),
        ("ow_books_ref", (61, 72)), ("PjitFunction(copy)", (62, 70)),
        ("PjitFunction(convert)", (110, 120)),
        ("ow_ack_decode", (130, 150))]


def test_a_dispatch_is_charged_to_its_nearest_program_ancestor():
    charged = span_reduce.charge_dispatches(HAND)
    assert [n for n, _iv in charged] == [
        "bench_publish", "ow_admit", "ow_assemble", "ow_step", "ow_step",
        "ow_step", "ow_books_ref", "ow_books_ref", "PjitFunction(convert)",
        "ow_ack_decode"]
    assert sorted(span_reduce.own_blocks(charged)) == [
        ("PjitFunction(convert)", (110, 120)),
        ("bench_publish", (0, 10)), ("bench_publish", (90, 100)),
        ("ow_ack_decode", (130, 150)),
        ("ow_admit", (10, 20)), ("ow_admit", (60, 61)),
        ("ow_admit", (72, 90)),
        ("ow_assemble", (20, 30)),
        ("ow_books_ref", (61, 72)),
        # the step with its dispatch is ONE block of 30, not five pieces
        ("ow_step", (30, 60))]


def _plain_own_time(spans, window):
    """The reference: for every stretch between two span edges, the
    innermost span covering it owns it; a dispatch span hands its stretch
    to the nearest `ow_*` span around it."""
    edges = sorted({window[0], window[1]}
                   | {t for _n, iv in spans for t in iv
                      if window[0] < t < window[1]})
    own = {}
    for a, b in zip(edges, edges[1:]):
        cover = sorted((s for s in spans if s[1][0] <= a and s[1][1] >= b),
                       key=lambda s: (s[1][0], -s[1][1]))
        if not cover:
            continue
        name = cover[-1][0]
        if name.startswith("PjitFunction"):
            name = next((n for n, _iv in reversed(cover)
                         if n.startswith("ow_")), name)
        own[name] = own.get(name, 0.0) + (b - a)
    return own


def _loop_spans(path):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(ev.name, (ev.start_ns, ev.start_ns + ev.duration_ns))
                   for ev in line.events]
            marks = [iv for n, iv in evs if n == trace_reduce.WINDOW_MARK]
            if marks:
                return marks[0], [(n, iv) for n, iv in evs
                                  if n != trace_reduce.WINDOW_MARK
                                  and n.startswith(("ow_", "bench_",
                                                    "PjitFunction"))]
    raise AssertionError("no window in the fixture")


# -- the recorded trace ----------------------------------------------------------

def test_span_reduction_on_the_recorded_trace():
    red = span_reduce.reduce_spans(FIXTURE)
    assert red["window_s"] == pytest.approx(WANT["window_s"], rel=1e-9)
    assert red["activations"] == WANT["activations"]
    for name, (count, own_s) in WANT["spans"].items():
        assert red["by_name"][name]["count"] == count, name
        assert red["by_name"][name]["own_s"] == pytest.approx(own_s,
                                                              rel=1e-9)
    # every name's own time, against the plain reference
    window, spans = _loop_spans(FIXTURE)
    plain = _plain_own_time(spans, window)
    for name, row in red["by_name"].items():
        if name not in span_reduce.ANY_THREAD:
            assert row["own_s"] * 1e9 == pytest.approx(plain.get(name, 0.0),
                                                       abs=1.0), name
    # no instant under two names: own times sum to the union of the spans
    loop_own = sum(r["own_s"] for n, r in red["by_name"].items()
                   if n not in span_reduce.ANY_THREAD)
    on_loop_gc = plain.get("ow_gc", 0.0) / 1e9
    assert loop_own + on_loop_gc == pytest.approx(red["spanned_s"], rel=1e-6)
    assert 0 < red["spanned_s"] < red["window_s"]
    # idle seconds inside a span never exceed its own time; the device ran
    assert all(0 <= r["idle_s"] <= r["own_s"] + 1e-12
               for r in red["by_name"].values())
    assert any(r["idle_s"] < r["own_s"] for r in red["by_name"].values())
    assert [b[0] for b in red["blocks"]] == WANT["longest_blocks"]
    lengths = [b[2] for b in red["blocks"]]
    assert lengths == sorted(lengths, reverse=True) and len(lengths) == 5
    line = span_reduce.summary_line(red)["span_reduce"]
    assert line["us_per_activation"] == pytest.approx(
        line["spanned_us_per_activation"]
        + line["unexplained_us_per_activation"], abs=0.01)
    json.dumps(line)


def test_new_metrics_read_the_recorded_trace(run_dir):
    run_dir(FIXTURE)
    art = {"trace": {"window_s": WANT["window_s"]}}
    for cell, suffix in (("fleet1k-zipf-closed", ".closed"),
                         ("standalone16-noop-open", ".open")):
        res, specs = _specs(cell)
        got = {n: run.read_metric(res, specs[n + suffix], art) for n in NEW}
        assert all(v is not None and v >= 0 for v in got.values()), got
        for name, value in WANT["metrics"].items():
            assert got[name] == pytest.approx(value, rel=1e-9), name
        assert 0 < got["loop_spanned_share"] < 100
    # own time over activations, in microseconds, worked from the JSON
    spans = WANT["spans"]
    assert got["host_admit_us"] == pytest.approx(
        (spans["ow_admit"][1] + spans["ow_assemble"][1]) * 1e6
        / WANT["activations"], rel=1e-9)


@pytest.mark.parametrize("window_s", [None, 0.5, 3.0])
def test_a_trace_of_another_run_is_not_read(run_dir, window_s):
    run_dir(FIXTURE)
    art = {"trace": {"window_s": window_s} if window_s else None}
    assert span_reduce.for_run(art) is None
    assert span_reduce.for_run({"trace": {"window_s": WANT["window_s"]}}) \
        is not None


@pytest.mark.parametrize("name", NEW)
def test_no_program_span_means_no_reading(run_dir, name):
    """A trace recorded before the program had spans (PR 24's fixture, and
    the parent commit under the driver): every new reader stays silent,
    none raises, none reads 0."""
    run_dir(OLD_FIXTURE)
    with open(os.path.join(BENCH, "fixtures", "small.expected.json")) as f:
        art = {"trace": {"window_s": json.load(f)["window_s"]}}
    assert span_reduce.reduce_spans(OLD_FIXTURE) is None
    for cell, suffix in (("fleet1k-zipf-closed", ".closed"),
                         ("standalone16-noop-open", ".open")):
        res, specs = _specs(cell)
        assert run.read_metric(res, specs[name + suffix], art) is None
    assert run.read_metric(res, specs[name + suffix], {"trace": None}) is None


def test_the_trace_is_reduced_once_and_the_newest_is_taken(run_dir, tmp_path,
                                                           capsys):
    run_dir(FIXTURE)
    art = {"trace": {"window_s": WANT["window_s"]}}
    first = span_reduce.for_run(art)
    assert span_reduce.for_run(art) is first
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and "span_reduce" in json.loads(err[0])
    older = tmp_path / "trace-old" / "plugins" / "profile" / "t0"
    older.mkdir(parents=True)
    shutil.copy(OLD_FIXTURE, older / "host.xplane.pb")
    os.utime(older / "host.xplane.pb", (1, 1))
    assert span_reduce.newest_trace().endswith("trace-cell/plugins/profile/"
                                               "t0/host.xplane.pb")
    assert span_reduce.newest_trace(str(tmp_path / "nowhere")) is None
