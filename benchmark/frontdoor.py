"""The entry `http`: the cell's load crosses the program's own front door.

A configuration that states `"entry": "http"` is served as `run.Sut` serves
it (same balancer, journal, simulated fleet, warm-up), with the program's
`controller.core.Controller` around that balancer: aiohttp, basic
authentication, entitlement with both throttles (the configuration's
`limits`), `resolve_action`, `ActionInvoker.invoke` and its blocking wait.
Not `standalone.make_standalone`, which builds a real in-process invoker
of its own where the configuration says simulated ones.

The load comes from `benchmark/httpgen.py` in a CHILD process, so that the
generator is off the measured event loop: the functions below have the
signatures of `run.py`'s `burst`, `closed_loop` and `open_loop` and only
tell the child what to do and when. The parent names the window's `t0` and
`t1` on `time.monotonic_ns()` and calls `on_window` at each itself; after
the drain `hand_over` turns the child's rows (one per request SENT) into
the `Sut`'s per-activation record, keyed by the `activationId` the answer
carried, and everything after it runs as for any other entry.
"""
from __future__ import annotations

import asyncio
import atexit
import json
import os
import signal
import sys
import time

from benchmark import run

HTTPGEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "httpgen.py")
#: an answer the child takes this long to give after `abort` never comes
ABORT_WAIT_S = 10.0


class Child:
    """The generator's process and the JSON lines to and from it."""

    def __init__(self):
        self.proc = None
        self._reader = None
        self._waiting: dict = {}
        self._next_id = 0

    async def start(self) -> None:
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, HTTPGEN, stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, limit=1 << 27)
        # a run that raises leaves through `main`, not through `stop`
        atexit.register(self._reap)
        self._reader = asyncio.ensure_future(self._read())

    async def _read(self) -> None:
        while line := await self.proc.stdout.readline():
            msg = json.loads(line)
            fut = self._waiting.pop(msg["id"], None)
            if fut is None or fut.done():
                continue
            if msg["ok"]:
                fut.set_result(msg["result"])
            else:
                fut.set_exception(run.BenchError(
                    f"the generator failed: {msg['error']}"))
        for fut in self._waiting.values():
            if not fut.done():
                fut.set_exception(run.BenchError("the generator has gone"))
        self._waiting.clear()

    def tell(self, cmd: str, **what) -> int:
        self._next_id += 1
        self.proc.stdin.write(json.dumps(
            {"cmd": cmd, "id": self._next_id, **what},
            separators=(",", ":")).encode() + b"\n")
        return self._next_id

    def ask(self, cmd: str, **what) -> asyncio.Future:
        """Send a command; the future is its reply."""
        fut = asyncio.get_event_loop().create_future()
        self._waiting[self.tell(cmd, **what)] = fut
        return fut

    async def stop(self) -> None:
        if self.proc is None:
            return
        if self.proc.returncode is None:
            self.proc.stdin.close()           # end of input ends it
            try:
                await asyncio.wait_for(self.proc.wait(), 10.0)
            except asyncio.TimeoutError:
                self.proc.kill()
                await self.proc.wait()
        await asyncio.gather(self._reader, return_exceptions=True)
        atexit.unregister(self._reap)

    def _reap(self) -> None:
        try:
            os.kill(self.proc.pid, signal.SIGKILL)
            os.waitpid(self.proc.pid, 0)
        except (ProcessLookupError, ChildProcessError):
            pass


class HttpSut(run.Sut):
    """`run.Sut` behind the program's own REST API, and the child that
    calls it."""

    def __init__(self, config: dict, catalog, tag: str, mix: dict, seed: int):
        super().__init__(config, catalog, tag)
        self.mix = mix
        self.seed = seed
        self.controller = None
        self.child = Child()
        #: the loop's reply: every row the child has
        self.report = None
        self.loop_cpu_s = None
        self.meta: list = []

    async def start(self) -> None:
        from openwhisk_tpu.controller.core import Controller
        from openwhisk_tpu.core.entity import (ControllerInstanceId,
                                               ExecManifest, WhiskAuthRecord,
                                               limits_from_config)
        from openwhisk_tpu.utils.logging import Logging

        # as the entry points do at boot, before any action is written;
        # `Sut.start` then raises the ceilings to the deployment's
        ExecManifest.initialize(None)
        limits_from_config()
        await super().start()
        limits = self.config["limits"]
        self.controller = Controller(
            ControllerInstanceId("0"), self.bal.provider,
            logger=Logging(level="warn"), load_balancer=self.bal,
            invocations_per_minute=int(limits["invocations_per_minute"]),
            concurrent_invocations=int(limits["concurrent_invocations"]),
            fires_per_minute=int(limits["fires_per_minute"]))
        ident = self._ident
        await self.controller.auth_store.put(WhiskAuthRecord(
            ident.subject, [ident.namespace], [ident.authkey]))
        # `Controller.start` starts its balancer, and this one is serving
        # already: a second ack feed and a second supervision would follow
        self.bal.start = _started
        try:
            await self.controller.start("127.0.0.1", 0)
        finally:
            del self.bal.start
        port = self.controller._runner.addresses[0][1]
        cat = self.catalog
        # what `sent` holds of an activation, by the rank of its action
        self.meta = [
            (cat.namespace, str(a.fully_qualified_name), mem, conc)
            for a, mem, conc in zip(self._actions, cat.memory_mb,
                                    cat.concurrency)]
        await self.child.start()
        await self.child.ask(
            "hello", url=f"http://127.0.0.1:{port}",
            auth=ident.authkey.compact, mix=self.mix, seed=self.seed,
            actions=list(zip(cat.names, cat.memory_mb, cat.concurrency)))

    async def stop(self) -> None:
        await self.child.stop()
        if self.controller is not None:
            # the balancer is `Sut.stop`'s to close, once
            self.controller.load_balancer = None
            await self.controller.stop()
        await super().stop()


async def _started() -> None:
    pass


def make_sut(res: dict, catalog, seed: int) -> HttpSut:
    return HttpSut(res["config"], catalog, res["cell"]["name"], res["mix"],
                   seed)


async def _sleep_until(t_ns: int) -> None:
    await asyncio.sleep(max(0.0, (t_ns - time.monotonic_ns()) / 1e9))


def burst(sut: HttpSut, _seq, n: int) -> list:
    return [sut.child.ask("burst", n=n)]


async def _window(sut: HttpSut, report, t0: int, t1: int,
                  on_window) -> dict:
    sut.report = report
    await _sleep_until(t0)
    on_window(t0)
    cpu0 = time.thread_time()
    await _sleep_until(t1)
    # the CPU seconds of this thread, the event loop's, over the window:
    # how much of it the serving loop was busy, spanned or not
    sut.loop_cpu_s = time.thread_time() - cpu0
    on_window(None)
    # the drain may cancel what it waits for; the reply itself stays due
    return {"t0_ns": t0, "t1_ns": t1, "tasks": [asyncio.shield(report)],
            "fire_lag_ms": []}


async def closed_loop(sut: HttpSut, _seq, clients: int, warm_s: float,
                      seconds: float, on_window) -> dict:
    t0 = time.monotonic_ns() + int(warm_s * 1e9)
    t1 = t0 + int(seconds * 1e9)
    report = sut.child.ask("closed", clients=clients, t0_ns=t0, t1_ns=t1)
    return await _window(sut, report, t0, t1, on_window)


async def open_loop(sut: HttpSut, _seq, warm_offsets, window_offsets,
                    seconds: float, on_window) -> dict:
    warm_s = float(warm_offsets[-1]) if len(warm_offsets) else 0.0
    offsets = [float(o) for o in warm_offsets] \
        + [warm_s + float(o) for o in window_offsets]
    # the schedule starts once the child has read it
    base = time.monotonic_ns() + int(0.25e9)
    t0 = base + int(warm_s * 1e9)
    t1 = t0 + int(seconds * 1e9)
    report = sut.child.ask("open", offsets=offsets, n_warm=len(warm_offsets),
                           base_ns=base, t0_ns=t0, t1_ns=t1)
    return await _window(sut, report, t0, t1, on_window)


async def hand_over(sut: HttpSut, win: dict) -> dict:
    """The child's rows into the `Sut`'s record. A request that got no
    answer by now (a minute past the drain) is ended and keeps a row, under
    an id no journal holds; one that was refused without an id (429, 401)
    has a row and no id: it is counted in `failed` alone."""
    report = sut.report
    if not report.done():
        # the wait ran out: end what is on its way, take the rows as they are
        sut.child.tell("abort")
        try:
            await asyncio.wait_for(asyncio.shield(report), ABORT_WAIT_S)
        except asyncio.TimeoutError:
            raise run.BenchError("the generator gave no rows") from None
    got = report.result()
    status: dict = {}
    for rank, sched_ns, done_ns, code, aid, ok, in_window in got["rows"]:
        i = sut.new_row(rank, sched_ns, in_window)
        sut.ok[i] = ok
        sut.done_ns[i] = done_ns if ok else 0
        status[str(code)] = status.get(str(code), 0) + 1
        if aid is None and code is None:
            aid = f"unanswered-{i}"
        if aid is not None:
            sut.aid[i] = aid
            sut.sent[aid] = sut.meta[rank]
    win["fire_lag_ms"].extend(got["fire_lag_ms"])
    return {"entry": "http", "generator_pid": got["pid"],
            "server_pid": os.getpid(), "http_status": status,
            "generator_lag_p99_ms": got["lag_p99_ms"],
            "generator_lag_probes": got["lag_probes"],
            "generator_cpu_s": got["cpu_s"],
            "server_loop_cpu_s": sut.loop_cpu_s}
