"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip: it builds a `TpuBalancer` over the in-memory bus
with the benchmark's simulated invoker fleet, warms the cell's own bucket
shapes with the cell's own traffic (set-up), drives the window through the
configuration's entry, drains, reads the peak memory, closes the balancer,
and only then replays the plain reference over what the window produced.
The entry is the SPI unless the configuration's file states another:
`maybe_batch_publish(bal).publish(action, msg)` -> `await promise` from this
process's own event loop (below), or `"entry": "http"`, the program's REST
API called from a generator in a process of its own (frontdoor.py). The
last line of standard output is the result. Nothing here names a cell, a
configuration or a metric: those are the files under configs/, traffic/,
metrics/ and readers/, found by the names in BENCHMARK.json.

Set-up ends by the clock, twice over. `Sut.start` gives the fleet
`FLEET_UP_WAIT_S` seconds of `time.monotonic()` to register and to be held
healthy, all of it, and then raises `BenchError`: a fleet the program
cannot bring up fails, it does not hang. And `main` starts a watchdog
thread that ends the process with exit code 2 and no result line when the
window has not opened `SETUP_LIMIT_S` seconds later: the one limit that
still holds when the event loop never yields. Neither is an option.
"""
from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, traffic  # noqa: E402

if __name__ == "__main__":
    # an entry's module (frontdoor.py) builds on this one: one module
    # object, whatever name it was started under
    sys.modules.setdefault("benchmark.run", sys.modules[__name__])

#: run-time files (journal, trace) live here, inside the checkout
RUN_DIR = os.path.join(ROOT, ".bench_run")
#: buckets the fused step is compiled for; set-up drives one burst of each
WARM_BURSTS = (8, 16, 32, 64, 128, 256)
LATE_WAIT_S = 60.0
#: a bucket's first fused step may compile for a minute at 1,024 rows
COLD_COMPILE_WAIT_S = 600.0
TRACE_SECONDS = 3.0
#: the fleet has this long, by the wall clock, to register and to be held
#: healthy (on the log line as `fleet_up_s`). The grows, scatters and
#: health folds of registration compile inside it on an empty cache, and
#: cost little: 1,024 rows are up in 8.2 s cold and 5.8-6.3 s warm, 16
#: rows in 0.25 s (chip runs, PR 33), so the margin is over 110 s. A fleet
#: that is not up after two minutes (twelve of supervision's 10 s ping
#: timeouts) is one the program cannot bring up. The driver tries every
#: new cell on the PARENT commit too, and a parent that hangs there
#: refuses the PR that brought the cell
FLEET_UP_WAIT_S = 120.0
#: the whole of set-up, process start to the window's first activation,
#: has this long before the watchdog ends the process. No lower than the
#: 1,200 s the contract gives a cell's first run in a checkout: the cold
#: set-up on record is 124-140 s, a tree whose kernels moved misses the
#: cache once, and `COLD_COMPILE_WAIT_S` allows one bucket alone 600 s
SETUP_LIMIT_S = 1200.0


class BenchError(RuntimeError):
    pass


class Spans:
    """Host spans around the calls the benchmark makes, for the traced
    run's attribution of device-idle time; nothing while no trace runs."""

    def __init__(self):
        self.make = contextlib.nullcontext

    def __call__(self, name: str):
        return self.make(name)


span = Spans()


# -- the manifest and the data files ---------------------------------------

def _load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(root, "BENCHMARK.json")


def resolve_cell(manifest: dict, name: str, root: str = ROOT) -> dict:
    """The cell, its configuration, its traffic mix and the metrics it
    reports, all found by name."""
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    bench_dir = manifest["paths"][0]
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in manifest["per_layer"]:
        # without a `workloads` key a per-layer metric is reported by every
        # cell that reports the end-to-end metric it moves
        if name in m["workloads"] if "workloads" in m \
                else m["moves"] in reported:
            spec = _load_json(root, f"{bench_dir}/metrics/{m['name']}.json")
            per_layer.append({**spec, **m})
    return {"cell": cell, "root": root, "bench_dir": bench_dir,
            "config": _load_json(root, cfg_entry["file"]),
            "mix": _load_json(root,
                              f"{bench_dir}/traffic/{cell['traffic']}.json"),
            "end_to_end": e2e, "per_layer": per_layer}


def read_metric(res: dict, spec: dict, art: dict):
    """Run the metric's own reader (a file under readers/) on the run's
    artefacts; a reader that finds nothing to read returns None."""
    path = os.path.join(res["root"], res["bench_dir"], "readers",
                        f"{spec['reader']}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"_bench_reader_{spec['reader']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(art, **spec.get("args", {}))


# -- the system under test -------------------------------------------------

class Sut:
    """TpuBalancer + in-memory bus + journal + simulated fleet, and the
    per-activation record the comparison and the metrics read."""

    def __init__(self, config: dict, catalog: traffic.Catalog, tag: str):
        self.config = config
        self.catalog = catalog
        self.journal_dir = os.path.join(RUN_DIR, f"journal-{tag}")
        self.bal = None
        self.fleet = None
        self.journal = None
        self._publisher = None
        self._actions = []
        self._ident = None
        self._message = None
        self._waterfall = None
        #: seconds the fleet took to register and to be held healthy
        self.fleet_up_s = None
        # one row per activation published
        self.aid: list = []
        self.rank: list = []
        self.sched_ns: list = []
        self.done_ns: list = []
        self.ok: list = []
        self.in_window: list = []
        self.sent: dict = {}

    async def start(self) -> None:
        from openwhisk_tpu.controller.loadbalancer import TpuBalancer
        from openwhisk_tpu.controller.loadbalancer.base import (
            HEALTHY, maybe_batch_publish)
        from openwhisk_tpu.controller.loadbalancer.journal import \
            journal_from_config
        from openwhisk_tpu.core.entity import (
            ActionLimits, ActivationId, CodeExec, ConcurrencyLimit,
            ControllerInstanceId, EntityName, EntityPath,
            ExecutableWhiskAction, Identity, MB, MemoryLimit, TimeLimit)
        from openwhisk_tpu.core.entity.ids import DocRevision
        from openwhisk_tpu.messaging import (ActivationMessage,
                                             MemoryMessagingProvider)
        from openwhisk_tpu.utils.transaction import TransactionId
        from openwhisk_tpu.utils.logging import Logging
        from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL

        from benchmark.fleet import SimFleet

        cfg, cat = self.config, self.catalog
        # the deployment's action memory ceiling (upstream
        # CONFIG_whisk_memory_max; this program has it as a class constant)
        top = int(cfg["action_memory_max_mb"])
        if MB(top) > MemoryLimit.MAX:
            MemoryLimit.MAX = MB(top)
        # and its per-action concurrency ceiling (upstream
        # CONFIG_whisk_concurrencyLimit_max; absent = 1, the feature off)
        ConcurrencyLimit.MAX = max(
            ConcurrencyLimit.MAX, int(cfg.get("action_concurrency_max", 1)))
        GLOBAL_WATERFALL.reset()
        provider = MemoryMessagingProvider()
        self.bal = TpuBalancer(
            provider, ControllerInstanceId("0"), logger=Logging(level="warn"),
            managed_fraction=float(cfg["managed_fraction"]),
            blackbox_fraction=float(cfg["blackbox_fraction"]),
            action_slots=int(cfg["action_slots"]),
            initial_pad=int(cfg["initial_pad"]),
            kernel=cfg["kernel"], prewarm=bool(cfg["prewarm"]))
        # the write-ahead placement journal, built and attached as the
        # entry points do for `--balancer-journal <dir>` (the program's own
        # switch CONFIG_whisk_ha_journal_enabled=false leaves it off, and
        # the comparison then has nothing to read: `correct` is false)
        shutil.rmtree(self.journal_dir, ignore_errors=True)
        os.makedirs(self.journal_dir, exist_ok=True)
        self.journal = journal_from_config(self.journal_dir)
        if self.journal is not None:
            self.bal.attach_journal(self.journal)
        self._publisher = maybe_batch_publish(self.bal)
        await self.bal.start()
        self.fleet = SimFleet(
            provider, int(cfg["invokers"]), int(cfg["invoker_memory_mb"]),
            dict(zip(cat.names, cat.service_s)),
            dict(zip(cat.names, cat.memory_mb)), span)
        n = int(cfg["invokers"])

        async def fleet_up() -> None:
            await self.fleet.start()
            while sum(h.status == HEALTHY
                      for h in await self.bal.invoker_health()) < n:
                await asyncio.sleep(0.25)

        t_up = time.monotonic()
        try:
            await asyncio.wait_for(fleet_up(), FLEET_UP_WAIT_S)
        except asyncio.TimeoutError:
            raise BenchError("the fleet never became healthy") from None
        self.fleet_up_s = time.monotonic() - t_up
        # let the last health flips fold into the device state
        await asyncio.sleep(1.5)
        # the telemetry plane folds completions in power-of-two buckets of
        # up to 4,096 events, compiles each bucket at first sight, and
        # compiles them all again when a higher invoker index grows its
        # accumulator; which ones a run meets depends on its stalls, so
        # set-up compiles them all at the fleet's full width with empty
        # (all-masked) folds, which change no count
        tel = self.bal.telemetry
        if tel.enabled and hasattr(tel.accumulator, "fold"):
            from openwhisk_tpu.ops.telemetry import E_INV
            b = 8
            while b <= 4096:
                ev = np.zeros((5, b), np.int32)
                ev[E_INV] = n - 1
                tel.accumulator.fold(ev)
                b *= 2
        for name, mem, conc in zip(cat.names, cat.memory_mb, cat.concurrency):
            a = ExecutableWhiskAction(
                EntityPath(cat.namespace), EntityName(name),
                CodeExec(kind="python:3", code="x"),
                limits=ActionLimits(TimeLimit(60_000), MemoryLimit(MB(mem)),
                                    concurrency=ConcurrencyLimit(conc)))
            a.rev = DocRevision("1-b")
            self._actions.append(a)
        self._ident = Identity.generate(cat.namespace)
        self._waterfall = GLOBAL_WATERFALL
        controller = ControllerInstanceId("0")

        def message(action, ident):
            return ActivationMessage(
                TransactionId(), action.fully_qualified_name, action.rev.rev,
                ident, ActivationId.generate(), controller, True, {})

        self._message = message

    def new_row(self, rank: int, sched_ns: int, in_window: bool = False) -> int:
        self.rank.append(rank)
        self.in_window.append(in_window)
        self.sched_ns.append(sched_ns)
        self.done_ns.append(0)
        self.ok.append(False)
        self.aid.append(None)
        return len(self.rank) - 1

    async def one(self, i: int) -> bool:
        """Publish activation row `i` and wait for its completion."""
        cat, rank = self.catalog, self.rank[i]
        action = self._actions[rank]
        with span("bench_publish"):
            waiter = self._submit(i, cat, rank, action)
        try:
            await (await waiter)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — a failed activation is counted
            self._waterfall.discard(self.aid[i])
            return False
        self.done_ns[i] = time.monotonic_ns()
        self.ok[i] = True
        return True

    def _submit(self, i: int, cat, rank: int, action):
        msg = self._message(action, self._ident)
        aid = msg.activation_id.asString
        self.aid[i] = aid
        self.sent[aid] = (cat.namespace, str(action.fully_qualified_name),
                          cat.memory_mb[rank], cat.concurrency[rank])
        # as the API handler does: the waterfall starts at the (scheduled)
        # arrival, so its stage deltas telescope to the client's latency
        self._waterfall.begin(aid, t0_ns=self.sched_ns[i])
        return self._publisher.publish(action, msg)

    async def stop(self) -> None:
        if self.fleet is not None:
            await self.fleet.stop()
        if self.bal is not None:
            await self.bal.close()
        if self.journal is not None:
            self.journal.close()


# -- the entry ---------------------------------------------------------------

def entry_of(config: dict):
    """The module that holds a configuration's entry: `make_sut`, the three
    loops (`burst`, `closed_loop`, `open_loop`) and `hand_over`. A
    configuration that states none enters at the SPI, in this module."""
    entry = config.get("entry", "spi")
    if entry == "http":
        from benchmark import frontdoor
        return frontdoor
    if entry == "spi":
        return sys.modules[__name__]
    raise BenchError(f"unknown entry {entry!r}")


def make_sut(res: dict, catalog: traffic.Catalog, _seed: int) -> Sut:
    return Sut(res["config"], catalog, res["cell"]["name"])


async def hand_over(_sut: Sut, _win: dict) -> dict:
    """The window's rows into the `Sut`'s record, and what the generator
    has for the log line. At the SPI the loops below wrote the rows on this
    event loop as they happened."""
    return {}


# -- the loops ---------------------------------------------------------------

def burst(sut: Sut, seq: traffic.RankSequence, n: int) -> list:
    now = time.monotonic_ns()
    return [asyncio.ensure_future(sut.one(sut.new_row(int(r), now)))
            for r in seq.take(n)]


async def closed_loop(sut: Sut, seq: traffic.RankSequence, clients: int,
                      warm_s: float, seconds: float, on_window) -> dict:
    """`clients` blocking callers; each sends its next activation when its
    last one's promise resolves. Returns the window's bounds."""
    stop = opened = False

    async def client() -> None:
        while not stop:
            await sut.one(sut.new_row(seq.next(), time.monotonic_ns(), opened))

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await asyncio.sleep(warm_s)
    t0 = time.monotonic_ns()
    on_window(t0)
    opened = True
    await asyncio.sleep(seconds)
    t1 = time.monotonic_ns()
    on_window(None)
    stop, opened = True, False
    return {"t0_ns": t0, "t1_ns": t1, "tasks": tasks, "fire_lag_ms": []}


async def open_loop(sut: Sut, seq: traffic.RankSequence, warm_offsets,
                    window_offsets, seconds: float, on_window) -> dict:
    """After tools/loadgen.open_loop: every request fires at its scheduled
    offset whatever the earlier ones do, and is timed FROM the schedule;
    how late each fire was is the generator's own health. The warm
    arrivals come first (set-up), the window's follow without a break."""
    loop = asyncio.get_event_loop()
    tasks, lag_ms = [], []
    warm_s = float(warm_offsets[-1]) if len(warm_offsets) else 0.0
    offsets = list(warm_offsets) + [warm_s + o for o in window_offsets]
    n_warm, n = len(warm_offsets), len(offsets)
    ranks = seq.take(n)
    t0_mono = time.monotonic()
    base_ns = time.monotonic_ns()
    win0 = base_ns + int(warm_s * 1e9)
    win1 = win0 + int(seconds * 1e9)
    i = 0
    while i < n:
        now = time.monotonic() - t0_mono
        while i < n and offsets[i] <= now:
            if i == n_warm:
                on_window(win0)
            sched_ns = base_ns + int(offsets[i] * 1e9)
            if i >= n_warm:
                lag_ms.append((time.monotonic_ns() - sched_ns) / 1e6)
            tasks.append(loop.create_task(
                sut.one(sut.new_row(int(ranks[i]), sched_ns, i >= n_warm))))
            i += 1
        if i < n:
            await asyncio.sleep(max(0.0, offsets[i]
                                    - (time.monotonic() - t0_mono)))
    on_window(None)
    return {"t0_ns": win0, "t1_ns": win1, "tasks": tasks,
            "fire_lag_ms": lag_ms}


# -- one run -------------------------------------------------------------------

_COMPILES: dict = {}


def _on_compile(event: str, _secs: float, **_kw) -> None:
    if event.endswith("backend_compile_duration"):
        _COMPILES["n"] += 1
        _COMPILES["in_window"] += _COMPILES["open"]
    elif event.endswith("jaxpr_to_mlir_module_duration"):
        _COMPILES["lowered_in_window"] += _COMPILES["open"]


def _watch_compiles() -> dict:
    """Count JAX's own compile events for this run. A program shape first
    seen by the process is lowered, then compiled or loaded from the
    persistent cache: either stalls the loop. One listener per process,
    however many runs it makes (control.py, sweep.py, the tests)."""
    import jax

    if not _COMPILES:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    _COMPILES.update(n=0, in_window=0, lowered_in_window=0, open=False)
    return _COMPILES


def percentile(sorted_vals: list, q: float):
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


@contextlib.contextmanager
def setup_limit():
    """The hard limit on set-up, running. Yields the call that clears it,
    which `run_cell` makes as the window opens. A thread, not a signal: a
    handler would have to wait for the very loop that is stuck, and an
    exception raised from it would land in whichever task ran."""
    dog = threading.Timer(SETUP_LIMIT_S, _setup_overran)
    dog.daemon = True
    dog.start()
    try:
        yield dog.cancel
    finally:
        dog.cancel()


def _setup_overran() -> None:
    print(f"benchmark: set-up passed its limit of {SETUP_LIMIT_S:.0f} s; "
          "what every thread was doing:", file=sys.stderr)
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    sys.stderr.flush()
    # no result line; a generator's child (frontdoor.py) ends when its
    # standard input closes with this process
    os._exit(2)


async def run_cell(res: dict, seed: int, seconds: float, trace: bool,
                   device: dict, faults=None, window_opens=None) -> dict:
    import jax
    from openwhisk_tpu.utils.waterfall import GLOBAL_WATERFALL, STAGES

    config, mix = res["config"], res["mix"]
    catalog = traffic.make_catalog(config, seed)
    seq = traffic.RankSequence(mix, len(catalog.names), seed)
    entry = entry_of(config)
    sut = entry.make_sut(res, catalog, seed)
    compiles = _watch_compiles()
    await sut.start()
    if faults:
        faults(sut)

    # set-up: the shape ladder. One burst per bucket compiles that
    # bucket's fused step; its acks, parked and then sent in one sweep,
    # compile the release-only fold and the telemetry fold of that size
    for n in WARM_BURSTS:
        sut.fleet.hold = True
        tasks = entry.burst(sut, seq, n)
        deadline = time.monotonic() + COLD_COMPILE_WAIT_S
        while (sut.fleet.held < n and time.monotonic() < deadline
               and not all(t.done() for t in tasks)):
            await asyncio.sleep(0.005)
        sut.fleet.hold = False
        sut.fleet.release_held()
        await asyncio.wait(tasks, timeout=LATE_WAIT_S)
        await asyncio.sleep(0.05)

    wf0 = {}
    marks = {}

    def on_window(t0_ns) -> None:
        snap = GLOBAL_WATERFALL.raw_counts()
        sut.fleet.window(t0_ns is not None)
        if t0_ns is not None:
            if window_opens is not None:
                window_opens()
            compiles["open"] = True
            wf0.update(snap)
            marks["setup_s"] = t0_ns / 1e9 - _T_START
        else:
            compiles["open"] = False
            marks["waterfall"] = {
                "stages": list(STAGES),
                "sum_us": [a - b for a, b in zip(snap["sum_us"],
                                                 wf0["sum_us"])],
                "count": [a - b for a, b in zip(snap["stage_count"],
                                                wf0["stage_count"])]}

    warm_s = float(mix["warm_seconds"])
    tracer = None
    if trace:
        tracer = asyncio.ensure_future(
            _trace_subwindow(sut, res["cell"]["name"], warm_s + 1.0,
                             min(TRACE_SECONDS, max(0.5, seconds - 2.0))))
    if mix["loop"] == "closed":
        win = await entry.closed_loop(sut, seq, int(mix["clients"]), warm_s,
                                      seconds, on_window)
    elif mix["loop"] == "open":
        win = await entry.open_loop(
            sut, seq, traffic.arrival_offsets(mix, warm_s, seed, 3),
            traffic.arrival_offsets(mix, seconds, seed, 4), seconds,
            on_window)
    else:
        raise BenchError(f"unknown loop {mix['loop']!r}")

    # drain: an answer that comes late is late, not wrong
    drain_t0 = time.monotonic()
    _done, pending = await asyncio.wait(
        win["tasks"], timeout=float(mix["drain_seconds"]))
    drained_s = time.monotonic() - drain_t0
    if pending:
        _done, pending = await asyncio.wait(pending, timeout=LATE_WAIT_S)
    for p in pending:
        p.cancel()
    if pending:
        await asyncio.gather(*pending, return_exceptions=True)
    generator_log = await entry.hand_over(sut, win)
    traced = await tracer if tracer is not None else None
    # let the last releases fold, then read the books the run leaves
    for _ in range(40):
        await asyncio.sleep(0.25)
        if not sut.bal._releases and not sut.bal._pending \
                and not sut.bal._readbacks:
            break
    await asyncio.sleep(0.5)
    program_free = [int(v) for v in np.asarray(sut.bal.state.free_mb)]
    program_conc = np.asarray(sut.bal.state.conc_free)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:res["cell"]["chips"]])
    geometry = {"N": int(sut.bal._n_pad), "A": int(sut.bal.action_slots),
                "kernel": str(sut.bal.kernel_resolved)}
    program_compiles = (sut.bal.profiler.compiles_expected
                        + sut.bal.profiler.compiles_unexpected)
    await sut.stop()

    # the comparison, once the program's state is freed
    t_ref0 = time.monotonic()
    records = list(sut.journal.records()) if sut.journal is not None else []
    replayed = reference.replay(
        records, sut.sent,
        reference.ReferenceFleet(float(config["managed_fraction"])),
        int(config["invokers"]))
    resolved = {a: ok for a, ok in zip(sut.aid, sut.ok) if a is not None}
    in_win = [i for i, w in enumerate(sut.in_window) if w]
    win_aids = {sut.aid[i] for i in in_win}
    observed = {"sent": sut.sent, "resolved": resolved,
                "deliveries": sut.fleet.deliveries,
                "completions": sut.fleet.completions,
                "program_free_mb": program_free,
                "program_conc_free": program_conc, "window_aids": win_aids}
    verdict = reference.compare(replayed, **observed)
    ref_s = time.monotonic() - t_ref0

    # the window's numbers
    t0, t1 = win["t0_ns"], win["t1_ns"]
    window_s = (t1 - t0) / 1e9
    svc = catalog.service_s
    attempted = len(in_win)
    failed = sum(1 for i in in_win if not sut.ok[i])
    completed_in = sum(1 for d in sut.done_ns if t0 <= d < t1)
    end_ns = time.monotonic_ns()
    overhead = sorted(
        ((sut.done_ns[i] if sut.ok[i] else end_ns) - sut.sched_ns[i]) / 1e6
        - svc[sut.rank[i]] * 1e3 for i in in_win)
    response = sorted((sut.done_ns[i] - sut.sched_ns[i]) / 1e6
                      for i in in_win if sut.ok[i])
    # a queue that grows through the window shows as a later half slower
    # than the earlier one (the sweep's backlog test)
    halves = [sorted(((sut.done_ns[i] if sut.ok[i] else end_ns)
                      - sut.sched_ns[i]) / 1e6 for i in part)
              for part in (in_win[:len(in_win) // 2],
                           in_win[len(in_win) // 2:])]
    done_in = [i for i, d in enumerate(sut.done_ns) if t0 <= d < t1]
    steps = [s for s in replayed["steps"]
             if s["fused"] and not win_aids.isdisjoint(s["aids"])]
    art = {
        "window_s": window_s, "attempted": attempted,
        "completed_in_window": completed_in,
        "overhead_ms": overhead, "response_ms": response,
        "fire_lag_ms": sorted(win["fire_lag_ms"]),
        "service_ms_mean": (sum(svc[sut.rank[i]] for i in done_in) * 1e3
                            / len(done_in)) if done_in else 0.0,
        "waterfall": marks.get("waterfall"),
        "steps": [{k: s[k] for k in ("b", "B", "R", "distinct")}
                  for s in steps],
        "geometry": geometry, "device": device, "trace": None,
        "traced_steps": None, "setup_s": marks.get("setup_s"),
    }
    pairing = None
    if traced is not None:
        from benchmark import trace_reduce
        trace_dir, seq0, seq1 = traced
        path = trace_reduce.find_trace(trace_dir)
        if path is not None:
            art["trace"] = trace_reduce.reduce_trace(path)
            # the placement program's dispatches inside the traced
            # sub-window, by the journal and by the trace: one to one
            mine = [s for s in replayed["steps"] if seq0 < s["seq"] <= seq1]
            device_s = art["trace"]["step_device_s"]
            pairing = [len(mine), len(device_s)]
            if len(mine) == len(device_s):
                art["traced_steps"] = [
                    {"fused": s["fused"], "B": s["B"],
                     "distinct": s["distinct"], "device_s": d}
                    for s, d in zip(mine, device_s)]
    log = {
        "seed": seed, "workload": res["cell"]["name"], "geometry": geometry,
        "window_s": window_s, "drained_s": round(drained_s, 3),
        "never_answered": len(pending), "reference_s": round(ref_s, 3),
        "fleet_up_s": round(sut.fleet_up_s, 3),
        "compared": verdict["compared"],
        "placed_on_a_spare_permit": replayed["shared"],
        "journal_records": len(records),
        "compiles_total": compiles["n"],
        "compiles_in_window": compiles["in_window"],
        "lowerings_in_window": compiles["lowered_in_window"],
        "program_compiles": program_compiles,
        "steps_in_window": len(steps), "ack_errors": sut.fleet.ack_errors,
        # what the simulated fleet delivered: an `unusable` beside pings
        # that kept their schedule is the program's, not the harness's
        "pings_per_s": (sut.fleet.window_pings / window_s
                        if sut.fleet.window_pings is not None else None),
        "ping_gap_max_s": sut.fleet.window_gap_max_s,
        "traced_records_and_dispatches": pairing,
        "device_programs": (art["trace"] or {}).get("modules"),
        "memory_peak_bytes": peak,
        "latency_p50_by_half_ms": [percentile(h, 0.5) for h in halves],
        "overhead_percentiles_ms": {
            str(q): percentile(overhead, q)
            for q in (0.5, 0.75, 0.9, 0.95, 0.98, 0.99, 0.999)},
        "gc_collections": [g["collections"] for g in gc.get_stats()],
        **generator_log,
    }
    return {"art": art, "verdict": verdict, "attempted": attempted,
            "failed": failed, "peak": peak, "log": log,
            "records": records, "replayed": replayed, "observed": observed}


async def _trace_subwindow(sut: Sut, tag: str, after_s: float,
                           span_s: float) -> tuple:
    """Trace a short sub-window of the run; start and stop run on a worker
    thread so the event loop keeps serving. Returns the trace's directory
    and the journal's sequence number at either end of the sub-window."""
    import jax

    trace_dir = os.path.join(RUN_DIR, f"trace-{tag}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    span.make = jax.profiler.TraceAnnotation
    await asyncio.sleep(after_s)
    # no Python tracer (it stalls the loop it is meant to watch); host
    # TraceMe spans stay on for the gap attribution
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    await asyncio.to_thread(jax.profiler.start_trace, trace_dir,
                            profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            seq0 = sut.bal._journal_seq
            await asyncio.sleep(span_s)
            seq1 = sut.bal._journal_seq
    finally:
        await asyncio.to_thread(jax.profiler.stop_trace)
        span.make = contextlib.nullcontext
    return trace_dir, seq0, seq1


def device_or_exit(chips: int) -> dict:
    """The device as JAX reports it. No accelerator (unless JAX_PLATFORMS
    names cpu first, the tests' twin) or too few chips: exit, no result."""
    from openwhisk_tpu.utils.config import boot_jax, device_info

    boot_jax()
    info = device_info()  # raises DeviceError on a silent CPU fallback
    if info["device_count"] < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX sees "
                         f"{info['device_count']}")
    return info


def build_result(res: dict, out: dict, trace: bool, device: dict) -> dict:
    art = out["art"]
    metrics = {}
    if not trace:
        for m in res["end_to_end"]:
            value = _end_to_end(m["name"], art)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in res["per_layer"]:
            value = read_metric(res, m, art)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["device_kind"],
           "count": res["cell"]["chips"], "memory_peak_bytes": out["peak"]}
    line = {"correct": bool(out["verdict"]["correct"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": dev}
    tr = art.get("trace")
    if trace and tr and tr.get("busy_s") is not None:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checked"] = reference.checked_line(out["verdict"]["numbers"])
    return line


def _end_to_end(name: str, art: dict):
    """The end-to-end metrics are the benchmark's own, taken by its clock
    over all the work and all the time of the window."""
    if name == "setup_s":
        return art["setup_s"]
    if name == "completed_per_s":
        return art["completed_in_window"] / art["window_s"]
    m = re.fullmatch(r"overhead_p(\d+)_ms", name)
    if m:
        return percentile(art["overhead_ms"], int(m.group(1)) / 100.0)
    raise BenchError(f"no end-to-end metric {name!r} in this harness")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        with setup_limit() as window_opens:
            res = resolve_cell(load_manifest(), args.workload)
            device = device_or_exit(int(res["cell"]["chips"]))
            out = asyncio.run(run_cell(res, args.seed, args.seconds,
                                       bool(args.trace), device,
                                       window_opens=window_opens))
    except Exception as e:  # noqa: BLE001 — no result line on any failure
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    line = build_result(res, out, bool(args.trace), device)
    print(json.dumps(out["log"]), file=sys.stderr)
    for k, v in line["checked"].items():
        print(f"checked {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
