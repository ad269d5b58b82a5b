"""From the traced run's `.xplane.pb` to host time by the program's own
spans (ISSUE 25). The program wraps its synchronous host blocks in
`jax.profiler.TraceAnnotation`s named `ow_*` (utils/waterfall.span), so they
lie in the profiler's file beside the harness's `bench_*` spans, JAX's
`PjitFunction(...)` dispatch spans and the device's ops, on one clock.

What is read: the host line that holds `bench_window` is the event loop's
thread. Every `ow_*`, `bench_*` and `PjitFunction*` span of that line,
cut to the window, gets its OWN time (`trace_reduce._own_time`: the time
outside the spans nested in it), and a `PjitFunction*` span's own time is
charged to its nearest `ow_*` ancestor (to itself when it has none): the
dispatch of a plane's program is that plane's host cost. Activations of
the sub-window are the sum of `b` over its `ow_assemble` spans. `ow_gc`
and `ow_readback_wait` are read on every thread (a collection stops them
all; the readback waits on a worker).

These are host timestamps, though they come out of the profiler's file:
the metrics built on them give `host_clock` as their source.

The run's artefacts carry no path, so `for_run` takes the newest trace
under `.bench_run/trace-*` and holds it to the run by the length of its
`bench_window`. A trace with no `ow_*` span at all (a program from before
ISSUE 25) gives None: there is nothing to read, which is not a reading
of 0.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

RUN_DIR = os.path.join(ROOT, ".bench_run")
PROGRAM = "ow_"
HARNESS = "bench_"
DISPATCH = "PjitFunction"
#: spans read on every thread, not on the event loop's alone
ANY_THREAD = ("ow_gc", "ow_readback_wait")
BLOCKS = 5

Interval = Tuple[float, float]
Span = Tuple[str, Interval]

#: one reduction per trace file and process: every metric re-executes its
#: reader, and all of them read the same trace
_CACHE: Dict[tuple, Optional[dict]] = {}


def newest_trace(run_dir: Optional[str] = None) -> Optional[str]:
    run_dir = RUN_DIR if run_dir is None else run_dir
    found = [p for d in glob.glob(os.path.join(run_dir, "trace-*"))
             for p in [trace_reduce.find_trace(d)] if p is not None]
    return max(found, key=os.path.getmtime) if found else None


def charge_dispatches(spans: List[Span]) -> List[Span]:
    """One thread's spans with every `PjitFunction*` span renamed to its
    nearest `ow_*` ancestor, where it has one."""
    out: List[Span] = []
    stack: List[Span] = []
    for name, (s, e) in sorted(spans, key=lambda x: (x[1][0], -x[1][1])):
        while stack and stack[-1][1][1] <= s:
            stack.pop()
        label = name
        if name.startswith(DISPATCH):
            label = next((n for n, _iv in reversed(stack)
                          if n.startswith(PROGRAM)), name)
        stack.append((name, (s, e)))
        out.append((label, (s, e)))
    return out


def own_blocks(spans: List[Span]) -> List[Span]:
    """`_own_time`'s pieces, with pieces of one name that touch joined: a
    step's dispatch charged to it leaves the step one block."""
    out: List[Span] = []
    for name, (s, e) in sorted(trace_reduce._own_time(spans),
                               key=lambda x: x[1]):
        if out and out[-1][0] == name and out[-1][1][1] == s:
            out[-1] = (name, (out[-1][1][0], e))
        else:
            out.append((name, (s, e)))
    return out


def _wanted(name: str) -> bool:
    return (name.startswith((PROGRAM, HARNESS, DISPATCH))
            and name != trace_reduce.WINDOW_MARK)


def reduce_spans(path: str) -> Optional[dict]:
    """Seconds by span name inside the traced sub-window, or None where
    the trace has no window or no span of the program's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    window: Optional[Interval] = None
    threads = []    # per host line: [(name, (start, end), stats)]
    loop = None
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                name = ev.name
                if name == trace_reduce.WINDOW_MARK and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    loop = len(threads)
                elif _wanted(name):
                    evs.append((name,
                                (ev.start_ns, ev.start_ns + ev.duration_ns),
                                tuple(ev.stats) if name.startswith(PROGRAM)
                                else ()))
            threads.append(evs)
    if window is None:
        return None
    if not any(name.startswith(PROGRAM) for evs in threads
               for name, _iv, _st in evs):
        return None

    def clipped(evs) -> List[Span]:
        return [(n, iv) for n, raw, _st in evs
                for iv in [trace_reduce._clip(raw[0], raw[1], window)]
                if iv is not None]

    # device-busy intervals of the first chip, for the idle seconds inside
    # each span (as trace_reduce attributes idle time to `bench_*`)
    busy: List[Interval] = []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            busy = trace_reduce._union(
                [iv for _n, iv in
                 list(trace_reduce._events(plane, trace_reduce.OPS_LINE,
                                           window))
                 + list(trace_reduce._events(plane,
                                             trace_reduce.MODULES_LINE,
                                             window))])
            break

    starts = np.array([b[0] for b in busy] or [window[1]])
    ends = np.array([b[1] for b in busy] or [window[1]])
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def busy_before(t: float) -> float:
        i = int(np.searchsorted(starts, t, side="right"))
        if i == 0:
            return 0.0
        return float(cum[i - 1] + min(t, ends[i - 1]) - starts[i - 1])

    def idle_in(iv: Interval) -> float:
        return (iv[1] - iv[0]) - (busy_before(iv[1]) - busy_before(iv[0]))

    loop_spans = clipped(threads[loop])
    blocks = own_blocks(charge_dispatches(loop_spans))
    by_name: Dict[str, dict] = {}

    def row(name: str) -> dict:
        return by_name.setdefault(
            name, {"count": 0, "own_s": 0.0, "idle_s": 0.0, "stats": {}})

    for name, iv in blocks:
        r = row(name)
        r["own_s"] += (iv[1] - iv[0]) / 1e9
        r["idle_s"] += idle_in(iv) / 1e9
    for name, _iv in loop_spans:
        row(name)["count"] += 1
    # the spans read on every thread: whole lengths (they nest nothing of
    # the program's), replacing the loop thread's share of the same name
    for name in ANY_THREAD:
        found = [iv for evs in threads for n, iv in clipped(evs)
                 if n == name]
        if found:
            by_name[name] = {
                "count": len(found),
                "own_s": sum(e - s for s, e in found) / 1e9,
                "idle_s": sum(idle_in(iv) for iv in found) / 1e9,
                "stats": {}}
    for evs in threads:
        for name, raw, stats in evs:
            if stats and name in by_name and raw[0] >= window[0] \
                    and raw[0] < window[1]:
                sums = by_name[name]["stats"]
                sums["_events"] = sums.get("_events", 0) + 1
                for key, value in stats:
                    if isinstance(value, (int, float)):
                        sums[key] = sums.get(key, 0) + value
    gc_blocks = [("ow_gc", iv) for i, evs in enumerate(threads) if i != loop
                 for n, iv in clipped(evs) if n == "ow_gc"]
    longest = sorted(blocks + gc_blocks,
                     key=lambda b: b[1][0] - b[1][1])[:BLOCKS]
    window_s = (window[1] - window[0]) / 1e9
    activations = int(by_name.get("ow_assemble", {}).get("stats", {})
                      .get("b", 0))
    spanned_s = sum(e - s for s, e in trace_reduce._union(
        [iv for _n, iv in loop_spans])) / 1e9
    return {"window_s": window_s, "activations": activations,
            "by_name": by_name, "spanned_s": spanned_s,
            "blocks": [[n, (s - window[0]) / 1e9, (e - s) / 1e9]
                       for n, (s, e) in longest]}


def summary_line(red: dict) -> dict:
    """What a traced run prints to stderr: per span name its count, own
    seconds and device-idle seconds, the longest blocks, and the window's
    microseconds per activation beside the part the spans explain."""
    acts = red["activations"]
    own = sum(r["own_s"] for n, r in red["by_name"].items()
              if n not in ANY_THREAD or n == "ow_gc")
    per = (lambda s: round(s * 1e6 / acts, 3)) if acts else (lambda s: None)
    return {"span_reduce": {
        "window_s": red["window_s"], "activations": acts,
        "spans": {n: [r["count"], round(r["own_s"], 6),
                      round(r["idle_s"], 6)]
                  for n, r in sorted(red["by_name"].items(),
                                     key=lambda kv: -kv[1]["own_s"])
                  if r["own_s"] > 0 or n.startswith(PROGRAM)},
        "longest_blocks": [[n, round(s, 6), round(d, 6)]
                           for n, s, d in red["blocks"]],
        "us_per_activation": per(red["window_s"]),
        "spanned_us_per_activation": per(own),
        "unexplained_us_per_activation": per(red["window_s"] - own)}}


def for_run(art: dict) -> Optional[dict]:
    """The reduction of the run's own trace, or None: no traced run, no
    trace on disk, a trace of another run (its window is not as long as
    the one `trace_reduce` read for this run), or no program spans."""
    tr = art.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    path = newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        red = reduce_spans(path)
        _CACHE.clear()
        _CACHE[key] = red
        if red is not None:
            print(json.dumps(summary_line(red)), file=sys.stderr)
    red = _CACHE[key]
    if red is None or abs(red["window_s"] - tr["window_s"]) > 1e-9:
        return None
    return red
