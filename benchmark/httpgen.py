"""The load generator of an `entry: http` cell, in a process of its own.

    python3 benchmark/httpgen.py        (started by benchmark/frontdoor.py)

It imports nothing of the program: `aiohttp`'s client and the benchmark's
one traffic generator. The parent, which serves, talks to it in JSON lines
over the two pipes: a command carries an `id`, and its one reply carries it
back. `hello` tells it the base URL, the key, the catalogue (name, memory,
concurrency by rank), the mix and the seed, and it creates the actions as a
user does (`PUT .../actions/<name>`); `burst` fires set-up's bursts;
`closed` and `open` run the mix's own loop against absolute
`time.monotonic_ns()` marks (one clock for all processes of a Linux host)
and reply, once every request has its answer, with one row per request
SENT since the process began:

    [rank, scheduled ns, done ns, HTTP status, activationId, ok, in window]

`done ns` is 0 and the status null where no answer came; `ok` says the
answer was a 200 whose body is an activation record with `response.result
== {"ok": true}`. `abort` ends the wait: what is still on its way is
cancelled and reported as it stands. End of input ends the process.
"""
from __future__ import annotations

import asyncio
import base64
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import aiohttp  # noqa: E402

from benchmark import traffic  # noqa: E402

#: the loop-lag probe's timer; its p99 over the window is the generator's
#: own health (a starved generator is not a fast server)
LAG_PROBE_S = 0.005
PUT_CONCURRENCY = 32
NOOP = "def main(args):\n    return {}\n"
OK_RESULT = {"ok": True}


class Generator:
    def __init__(self, spec: dict):
        key = base64.b64encode(spec["auth"].encode()).decode()
        self.headers = {"Authorization": f"Basic {key}",
                        "Content-Type": "application/json"}
        self.actions = spec["actions"]          # [name, MB, concurrency]
        base = f"{spec['url']}/api/v1/namespaces/_/actions/"
        self.action_url = [base + a[0] for a in self.actions]
        self.invoke_url = [u + "?blocking=true" for u in self.action_url]
        self.mix = spec["mix"]
        self.seq = traffic.RankSequence(self.mix, len(self.actions),
                                        int(spec["seed"]))
        # closed: one keep-alive connection a caller; open: the pool the
        # mix states (absent: as many as are in flight)
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(
                limit=int(self.mix.get("connections", 0)),
                keepalive_timeout=300.0),
            timeout=aiohttp.ClientTimeout(total=None), headers=self.headers)
        self.rows: list = []
        self.lag: list = []     # (monotonic ns, lag ms, process CPU s)
        self.window = (0, 0)    # the loop's t0 and t1, once it is told
        self._flying: set = set()
        self._probe = asyncio.ensure_future(self._probe_lag())

    async def _probe_lag(self) -> None:
        while True:
            t = time.monotonic()
            await asyncio.sleep(LAG_PROBE_S)
            self.lag.append((time.monotonic_ns(),
                             (time.monotonic() - t - LAG_PROBE_S) * 1e3,
                             time.process_time()))

    async def create_actions(self) -> int:
        gate = asyncio.Semaphore(PUT_CONCURRENCY)

        async def put(url: str, mem: int, conc: int) -> None:
            body = {"exec": {"kind": "python:3", "code": NOOP},
                    "limits": {"timeout": 60_000, "memory": mem,
                               "concurrency": conc}}
            async with gate, self.session.put(url, json=body) as r:
                if r.status != 200:
                    raise RuntimeError(f"PUT {url}: {r.status} "
                                       f"{await r.text()}")

        await asyncio.gather(*(put(u, a[1], a[2]) for u, a in
                               zip(self.action_url, self.actions)))
        return len(self.actions)

    def send(self, rank: int, sched_ns: int, in_window: bool) -> asyncio.Task:
        row = [rank, sched_ns, 0, None, None, False, in_window]
        self.rows.append(row)
        task = asyncio.ensure_future(self._one(row))
        self._flying.add(task)
        task.add_done_callback(self._flying.discard)
        return task

    async def _one(self, row: list) -> None:
        try:
            async with self.session.post(self.invoke_url[row[0]],
                                         data=b"{}") as r:
                row[3] = r.status
                body = await r.read()
                row[2] = time.monotonic_ns()
            doc = json.loads(body)
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            return              # no answer, or none that can be read
        if isinstance(doc, dict):
            aid = doc.get("activationId")
            row[4] = aid if isinstance(aid, str) else None
            response = doc.get("response")
            row[5] = (row[3] == 200 and isinstance(response, dict)
                      and response.get("result") == OK_RESULT)

    async def burst(self, n: int) -> int:
        now = time.monotonic_ns()
        tasks = [self.send(int(r), now, False) for r in self.seq.take(n)]
        await asyncio.wait(tasks)
        return len(tasks)

    async def closed(self, clients: int, t0_ns: int, t1_ns: int) -> dict:
        """`clients` blocking callers, each sending its next request on its
        last one's answer, until `t1_ns`."""
        async def client() -> None:
            while (now := time.monotonic_ns()) < t1_ns:
                await self.send(self.seq.next(), now, now >= t0_ns)

        self.window = (t0_ns, t1_ns)
        await asyncio.gather(*(client() for _ in range(clients)))
        return self.report([])

    async def open(self, offsets: list, n_warm: int, base_ns: int,
                   t0_ns: int, t1_ns: int) -> dict:
        """After benchmark/run.py's `open_loop`: every request fires at its
        scheduled offset from `base_ns` whatever the earlier ones do, and is
        timed FROM the schedule; the first `n_warm` are set-up's."""
        n, i, fire_lag_ms = len(offsets), 0, []
        ranks = self.seq.take(n)
        self.window = (t0_ns, t1_ns)
        while i < n:
            now = (time.monotonic_ns() - base_ns) / 1e9
            while i < n and offsets[i] <= now:
                sched_ns = base_ns + int(offsets[i] * 1e9)
                if i >= n_warm:
                    fire_lag_ms.append((time.monotonic_ns() - sched_ns) / 1e6)
                self.send(int(ranks[i]), sched_ns, i >= n_warm)
                i += 1
            if i < n:
                await asyncio.sleep(max(
                    0.0, offsets[i] - (time.monotonic_ns() - base_ns) / 1e9))
        while self._flying:
            await asyncio.wait(list(self._flying))
        return self.report(fire_lag_ms)

    def report(self, fire_lag_ms: list) -> dict:
        t0_ns, t1_ns = self.window
        probes = [p for p in self.lag if t0_ns <= p[0] < t1_ns]
        lag = sorted(p[1] for p in probes)
        return {"rows": self.rows, "fire_lag_ms": fire_lag_ms,
                "pid": os.getpid(), "lag_probes": len(lag),
                "lag_p99_ms": lag[min(len(lag) - 1, int(0.99 * len(lag)))]
                if lag else None,
                # this process's CPU seconds between the window's first
                # and last probe: what the generator itself costs
                "cpu_s": probes[-1][2] - probes[0][2] if probes else None}

    async def close(self) -> None:
        self._probe.cancel()
        for t in list(self._flying):
            t.cancel()
        await self.session.close()


async def serve() -> None:
    loop = asyncio.get_event_loop()
    reader = asyncio.StreamReader(limit=1 << 27)
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    gen, jobs = None, set()

    def reply(ident: int, **what) -> None:
        sys.stdout.write(json.dumps({"id": ident, **what},
                                    separators=(",", ":")) + "\n")
        sys.stdout.flush()

    async def job(ident: int, coro) -> None:
        try:
            reply(ident, ok=True, result=await coro)
        except asyncio.CancelledError:
            # `abort`: the rows as they stand
            reply(ident, ok=True, result=gen.report([]))
        except Exception as e:  # noqa: BLE001 — the parent decides what now
            reply(ident, ok=False, error=f"{type(e).__name__}: {e}")

    try:
        while line := await reader.readline():
            msg = json.loads(line)
            cmd, ident = msg.pop("cmd"), msg.pop("id")
            if cmd == "hello":
                gen = Generator(msg)
                coro = gen.create_actions()
            elif cmd == "burst":
                coro = gen.burst(int(msg["n"]))
            elif cmd in ("closed", "open"):
                coro = getattr(gen, cmd)(**msg)
            elif cmd == "abort":
                for j in jobs:
                    j.cancel()
                continue
            else:
                reply(ident, ok=False, error=f"unknown command {cmd!r}")
                continue
            task = asyncio.ensure_future(job(ident, coro))
            jobs.add(task)
            task.add_done_callback(jobs.discard)
    finally:
        for j in jobs:
            j.cancel()
        if gen is not None:
            await gen.close()


if __name__ == "__main__":
    asyncio.run(serve())
