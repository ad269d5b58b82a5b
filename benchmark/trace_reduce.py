"""From a profiler trace (`.xplane.pb`) to the numbers the device metrics
need. Kept with the benchmark so that every PR reduces a trace the same way.

What a TPU trace holds (one plane per chip, `/device:TPU:<i>`): a line
`XLA Modules` with one event per program execution (`jit_<fn>(<id>)`), and
a line `XLA Ops` with one event per HLO op executed. The host plane
(`/host:CPU`) has one line per thread with `TraceAnnotation` spans; the
benchmark brackets the traced sub-window with one named `bench_window`, so
that host and device sit on one clock and the window is what both are cut
to.

The placement program: the fused step and the release-only fold are both
jitted functions named `packed`, so the device line cannot tell them
apart. The host line can be set against the journal: every dispatch of
either is one outermost `PjitFunction(packed)` span on the event loop's
thread, made in the same synchronous block as its journal record, so the
k-th span of the window is the k-th record of the window. `pair_runs`
gives each dispatch its execution on the device.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_MARK = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans device-idle time is attributed to: the benchmark's own
#: annotations around the calls it makes, and JAX's dispatch spans. They
#: nest (a publish that dispatches, an invoker that acks, JAX's two spans
#: to a call), so each gets its own time only: `_own_time`.
HOST_SPAN = re.compile(r"^(bench_|PjitFunction)")
HOST_OTHER = "host_other(balancer,bus,event_loop)"
#: the jitted function that is the placement program (fused step and
#: release-only fold): `jit_<name>(<id>)` on the device's module line,
#: `PjitFunction(<name>)` where the host dispatches it
STEP_PROGRAM = "packed"
#: the most the device's clock may run ahead of the host's in a trace: the
#: offset is constant within one, 0.1-0.3 ms in most and 1.4-1.8 ms in two
#: of PR 32's four
CLOCK_SLACK_NS = 5e6

Interval = Tuple[float, float]


def find_trace(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def _events(plane, line_name: str, window: Interval):
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, window)
            if iv is not None:
                yield ev.name, iv


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


def _short(op: str) -> str:
    """`%fusion.246 = s32[...]{...} fusion(...)` -> `%fusion.246 fusion`: a
    TPU trace names an op by its whole HLO line."""
    head, sep, rest = op.partition(" = ")
    if not sep:
        return op[:80]
    m = _OPCODE.search(" " + rest)
    return f"{head} {m.group(1)}" if m else head[:80]


def _own_time(spans: List[Tuple[str, Interval]]
              ) -> List[Tuple[str, Interval]]:
    """One thread's spans cut to the time each spends outside the spans
    nested in it, so that no instant is counted under two names."""
    out: List[Tuple[str, Interval]] = []
    stack: List[list] = []   # [name, own time runs from, end]

    def close(until: float) -> None:
        while stack and stack[-1][2] <= until:
            name, cur, end = stack.pop()
            if end > cur:
                out.append((name, (cur, end)))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for name, (s, e) in sorted(spans, key=lambda x: (x[1][0], -x[1][1])):
        close(s)
        if stack and s > stack[-1][1]:
            out.append((stack[-1][0], (stack[-1][1], s)))
        stack.append([name, s, e])
    close(float("inf"))
    return out


def pair_runs(dispatches: List[float], runs: List[Interval],
              slack: float = CLOCK_SLACK_NS) -> List[Optional[float]]:
    """For each host dispatch (start times, in order) the duration of its
    execution on the device. The device runs one program at a time in
    dispatch order, one execution a dispatch, so the window's dispatches
    and its executions are two slices of ONE sequence, and only their first
    members have to be matched: the dispatches' first belongs to the first
    execution that starts no earlier than it, less the clocks' slack, or,
    where that one was dispatched before the window, to the next. Which of
    the two is decided by what a right pairing has and a wrong one has not:
    executions that start a short and steady while after their dispatches
    (`_misfit`). None where the trace ended first."""
    if not dispatches:
        return []
    first = next((k for k, run in enumerate(runs)
                  if run[0] >= dispatches[0] - slack), len(runs))
    first = min((first, first + 1),
                key=lambda k: _misfit(dispatches, runs[k:]))
    mine = runs[first:first + len(dispatches)]
    return [e - s for s, e in mine] + [None] * (len(dispatches) - len(mine))


def _misfit(dispatches: List[float], runs: List[Interval]) -> float:
    """How badly `runs`, taken in order, fit `dispatches`: the band the
    delays from dispatch to execution lie in (their 10th to 90th
    percentile), plus the size of their median. Rightly paired the delay is
    a launch less the clocks' offset, both constant within a trace; paired
    one off it is the gap between two steps."""
    delays = sorted(run[0] - h for h, run in zip(dispatches, runs))
    if not delays:
        return float("inf")
    last = len(delays) - 1
    return (delays[int(0.9 * last)] - delays[int(0.1 * last)]
            + abs(delays[last // 2]))


def reduce_trace(path: str) -> dict:
    """Everything the device readers and `breakdown` need, in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    host_spans: List[Tuple[str, Interval]] = []
    window: Optional[Interval] = None
    dispatches: List[float] = []
    dispatch_name = f"PjitFunction({STEP_PROGRAM})"
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            spans: List[Tuple[str, Interval]] = []
            marked = False
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_MARK and window is None:
                    window, marked = iv, True
                elif HOST_SPAN.match(ev.name):
                    spans.append((ev.name, iv))
            host_spans += _own_time(spans)
            mine = [iv for name, iv in spans if name == dispatch_name]
            if marked:
                # the event loop's thread; JAX nests two spans per call
                edge = window[0]
                for s0, e0 in sorted(mine):
                    if s0 >= edge and e0 <= window[1]:
                        dispatches.append(s0)
                        edge = e0
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    out = {"device_planes": len(devices),
           "window_s": None, "busy_s": None, "step_device_s": [],
           "device_ops": [], "idle_gaps": [], "modules": []}
    if window is None or not devices:
        return out
    step_re = re.compile(rf"^jit_{STEP_PROGRAM}\b")
    # whole executions of the placement program on the first chip
    step_runs = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for line in devices[0].lines if line.name == MODULES_LINE
        for ev in line.events if step_re.search(ev.name)
        and ev.start_ns >= window[0]
        and ev.start_ns + ev.duration_ns <= window[1])
    out["step_device_s"] = [None if d is None else d / 1e9
                            for d in pair_runs(dispatches, step_runs)]
    busy_total = 0.0
    op_time: Dict[str, float] = {}
    mod_time: Dict[str, List[float]] = {}
    first_busy: List[Interval] = []
    for plane in devices:
        ops = list(_events(plane, OPS_LINE, window))
        mods = list(_events(plane, MODULES_LINE, window))
        busy = _union([iv for _n, iv in ops + mods])
        first_busy = first_busy or busy
        busy_total += sum(e - s for s, e in busy)
        for name, (s, e) in ops:
            name = _short(name)
            op_time[name] = op_time.get(name, 0.0) + (e - s)
        for name, (s, e) in mods:
            base = re.sub(r"\(\d+\)$", "", name)
            agg = mod_time.setdefault(base, [0, 0.0])
            agg[0] += 1
            agg[1] += e - s
    out["window_s"] = (window[1] - window[0]) / 1e9
    out["busy_s"] = busy_total / len(devices) / 1e9
    out["device_ops"] = [[n, t / 1e9] for n, t in sorted(
        op_time.items(), key=lambda kv: -kv[1])[:10]]
    out["modules"] = [[n, c, t / 1e9] for n, (c, t) in sorted(
        mod_time.items(), key=lambda kv: -kv[1][1])[:10]]
    # idle time by what the host was doing: each host span gets the part
    # of it during which the (first) device ran nothing; the rest of the
    # idle time is the program's own host code and the event loop
    busy = first_busy
    starts = np.array([b[0] for b in busy] or [window[1]])
    ends = np.array([b[1] for b in busy] or [window[1]])
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def busy_before(t: float) -> float:
        i = int(np.searchsorted(starts, t, side="right"))
        if i == 0:
            return 0.0
        return cum[i - 1] + min(t, ends[i - 1]) - starts[i - 1]

    idle_by: Dict[str, float] = {}
    for name, (hs, he) in host_spans:
        iv = _clip(hs, he, window)
        if iv is None:
            continue
        idle = (iv[1] - iv[0]) - (busy_before(iv[1]) - busy_before(iv[0]))
        if idle > 0:
            idle_by[name] = idle_by.get(name, 0.0) + idle
    total_idle = (window[1] - window[0]) - float(cum[-1])
    idle_by[HOST_OTHER] = max(0.0, total_idle - sum(idle_by.values()))
    out["idle_gaps"] = [[n, float(t) / 1e9] for n, t in sorted(
        idle_by.items(), key=lambda kv: -kv[1])[:10]]
    return out
