"""The harness at a fleet past 1k: the plain reference registers in linear
time and decides what the eager one decided, the simulated fleet pings at a
phase per invoker on the schedule's own clock however late the loop turns,
and set-up ends by the clock. Times are asserted of the pings' gaps, held
against the schedule's second and the loop's turns measured in the same
run, and of this process's own CPU seconds."""
import asyncio
import json
import os
import random
import subprocess
import sys
import time

import pytest

from benchmark import fleet, reference, run

BENCH = os.path.join(run.ROOT, "benchmark")


# -- the reference: one step list per fleet size, when first needed -----------

class EagerFleet(reference.ReferenceFleet):
    """`register` and `schedule` as the parent commit (c4e23c1) had them:
    the step list worked out anew on every registered row."""

    def __init__(self, managed_fraction=1.0):
        super().__init__(managed_fraction)
        self._steps = [1]

    def register(self, idx, user_memory_mb, usable):
        while idx >= len(self.invokers):
            self.invokers.append(reference._Invoker(0, False))
        self.invokers[idx] = reference._Invoker(
            max(user_memory_mb, reference.MIN_SLOT_MB), usable)
        self._steps = reference.pairwise_coprimes(self.managed_count)

    def schedule(self, namespace, fqn, mem, rand, maxc=1):
        size = self.managed_count
        if size == 0:
            return None, False
        key = reference.action_key(fqn, mem)
        pooled = maxc > 1
        h = reference.generate_hash(namespace, fqn)
        step = self._steps[h % len(self._steps)]
        idx = h % size
        for _ in range(size):
            inv = self.invokers[idx]
            if inv.usable and (inv.free_mb >= mem or (
                    pooled and inv.spare.get(key, 0) > 0)):
                self.shared += inv.acquire(key, mem, maxc)
                return idx, False
            idx = (idx + step) % size
        best = None
        for i in range(size):
            if self.invokers[i].usable:
                r = (i - rand) % size
                if best is None or r < best[0]:
                    best = (r, i)
        if best is None:
            return None, False
        self.invokers[best[1]].acquire(key, mem, maxc)
        return best[1], True


@pytest.mark.parametrize("fraction", [1.0, 0.9])
def test_the_lazy_reference_decides_what_the_eager_one_did(fraction):
    """A fleet registered row by row up to 300, placements, releases and
    flips after every row: decisions and both levels of books are equal."""
    rng = random.Random(int(fraction * 10))
    lazy, eager = reference.ReferenceFleet(fraction), EagerFleet(fraction)
    held = []
    for size in range(1, 301):
        for f in (lazy, eager):
            f.register(size - 1, 512, rng.random() < 0.5 or size == 1)
            f.set_health(size - 1, True)
        for _ in range(4):
            fqn = f"ns/a{rng.randrange(40)}"
            mem, maxc = rng.choice((128, 256, 512)), rng.choice((1, 1, 3))
            rand = rng.randrange(1 << 16)
            got = lazy.schedule("ns", fqn, mem, rand, maxc)
            assert got == eager.schedule("ns", fqn, mem, rand, maxc)
            if got[0] is not None:
                held.append((got[0], mem, fqn, maxc))
        if size % 7 == 0:
            flip = rng.randrange(size)
            for f in (lazy, eager):
                f.set_health(flip, size % 2 == 0)
        while len(held) > size:
            row = held.pop(rng.randrange(len(held)))
            for f in (lazy, eager):
                f.release(*row)
        assert lazy.free_mb() == eager.free_mb()
        assert lazy.spare() == eager.spare()
        assert lazy.shared == eager.shared
    assert lazy.shared > 0


def _count_coprime_calls(monkeypatch) -> list:
    calls, real = [], reference.pairwise_coprimes

    def counted(x):
        calls.append(x)
        return real(x)
    monkeypatch.setattr(reference, "pairwise_coprimes", counted)
    return calls


def test_one_step_list_per_fleet_size_at_which_a_placement_happens(
        monkeypatch):
    calls = _count_coprime_calls(monkeypatch)
    f = reference.ReferenceFleet(1.0)
    for i in range(50):
        f.register(i, 512, True)
    assert calls == []                   # registering costs no list
    for k in range(20):
        f.schedule("ns", f"ns/a{k}", 128, rand=k)
    assert calls == [50]
    for i in range(50, 60):
        f.register(i, 512, True)
    f.register(3, 1024, True)            # a row again: the size stays
    for k in range(20):
        f.schedule("ns", f"ns/b{k}", 128, rand=k)
    assert calls == [50, 60]
    # nine tenths managed: 10 and 11 rows are both a fleet of 9
    g = reference.ReferenceFleet(0.9)
    for i in range(12):
        g.register(i, 512, True)
        if i >= 9:
            g.schedule("ns", "ns/c", 128, rand=0)
    assert calls == [50, 60, 9, 10]


def test_an_invoker_that_pings_ahead_of_lower_ones_pads_the_fleet():
    """Upstream's padding: the rows below a new invoker's index stand in
    with its memory, offline, and a row already in the books keeps them,
    and its health, when its own invoker pings, whatever memory that one
    announces."""
    f = reference.ReferenceFleet(1.0)
    for i in range(4):
        f.register(i, 512, False)
    f.register(7, 1024, False)
    assert f.free_mb() == [512] * 4 + [1024] * 4
    assert f.unusable(8) == 8
    for i in range(8):
        f.set_health(i, True)
    placed, forced = f.schedule("ns", "ns/a", 256, rand=0)
    assert placed is not None and not forced
    before = f.free_mb()
    for i in range(8):
        f.register(i, 2048, False)
    assert f.free_mb() == before
    assert f.unusable(8) == 0


def test_a_10k_fleet_registers_and_places_in_linear_time(monkeypatch):
    calls = _count_coprime_calls(monkeypatch)
    f = reference.ReferenceFleet(1.0)
    t0 = time.process_time()
    for i in range(10_240):
        f.register(i, 2048, True)
    placed = f.schedule("guest", "guest/a", 256, rand=0)
    cpu_s = time.process_time() - t0
    assert calls == [10_240]
    assert placed == (reference.generate_hash("guest", "guest/a") % 10_240,
                      False)
    assert cpu_s < 2.0, cpu_s            # the eager one took 451 s


# -- the fleet's pings ---------------------------------------------------------

@pytest.mark.parametrize("n", [16, 1_024, 10_240, 65_536])
def test_the_ping_schedule_is_a_pure_function_of_the_fleet_s_size(n):
    sched = fleet.ping_schedule(n)
    assert sched == fleet.ping_schedule(n)
    assert len(sched) == -(-n // fleet.PING_TICK)
    # every invoker in exactly one tick of every second, in order
    assert [lo for _p, lo, _hi in sched] == [0] + [hi for _p, _lo, hi
                                                   in sched[:-1]]
    assert sched[-1][2] == n
    assert all(0 < hi - lo <= fleet.PING_TICK for _p, lo, hi in sched)
    phases = [p for p, _lo, _hi in sched]
    assert phases[0] == 0.0 and phases == sorted(set(phases))
    assert all(0.0 <= p < 1.0 for p in phases)
    # the ticks are spread evenly over the second, each near its
    # invokers' own phase i / n
    assert all(abs(p - lo / n) < 1.0 / len(sched) for p, lo, _hi in sched)


class _Pings:
    """Every ping a fleet sends, as (loop turn, time, invoker): the
    producer's `send` and `send_many` both counted, a ping read off its
    message or its bytes. A task that runs at every turn of the loop counts
    the turns and times them; `hold_s` holds each of its turns that long,
    as a busy served loop does."""

    def __init__(self, provider, hold_s: float = 0.0):
        from openwhisk_tpu.messaging import PingMessage

        self.sent: list = []
        self.turn = 0
        self.turn_max_s = 0.0
        #: (when a turn ended, how long it took)
        self.turns_s: list = []
        self.hold_s = hold_s
        real = provider.get_producer

        def count(msg) -> None:
            if isinstance(msg, (bytes, bytearray)):
                msg = PingMessage.parse(msg)
            if isinstance(msg, PingMessage):
                self.sent.append((self.turn, time.monotonic(),
                                  msg.instance.instance))

        def producer():
            prod = real()
            send, send_many = prod.send, prod.send_many

            async def counted(topic, msg):
                count(msg)
                await send(topic, msg)

            async def counted_many(items):
                items = list(items)
                for _topic, payload, msg in items:
                    count(payload if msg is None else msg)
                await send_many(items)
            prod.send, prod.send_many = counted, counted_many
            return prod

        provider.get_producer = producer

    async def turns(self) -> None:
        last = time.monotonic()
        while True:
            self.turn += 1
            if self.hold_s:
                time.sleep(self.hold_s)
            await asyncio.sleep(0)
            now = time.monotonic()
            self.turn_max_s = max(self.turn_max_s, now - last)
            self.turns_s.append((now, now - last))
            last = now

    def by_invoker(self) -> dict:
        out: dict = {}
        for _turn, t, i in self.sent:
            out.setdefault(i, []).append(t)
        return out

    def blocks(self) -> dict:
        """turn -> the invokers pinged in it, in order"""
        out: dict = {}
        for turn, _t, i in self.sent:
            out.setdefault(turn, []).append(i)
        return out


def _drive_fleet(n: int, seconds: float, hold_s: float = 0.0,
                 stall=None):
    """A fleet of `n` on the in-memory bus for `seconds`, from `start()`
    on (which itself sends nothing and returns at once); `stall` is a
    coroutine function run beside it. Returns the count and its end."""
    from openwhisk_tpu.messaging import MemoryMessagingProvider

    async def drive():
        provider = MemoryMessagingProvider()
        pings = _Pings(provider, hold_s)
        sim = fleet.SimFleet(provider, n, 2048, {}, {})
        tasks = [asyncio.ensure_future(pings.turns())]
        if stall is not None:
            tasks.append(asyncio.ensure_future(stall()))
        try:
            await sim.start()
            await asyncio.sleep(seconds)
            end = time.monotonic()
        finally:
            for t in tasks:
                t.cancel()
            await sim.stop()
        return pings, end

    return asyncio.run(drive())


def test_the_fleet_never_sends_more_than_a_tick_between_two_yields():
    """On an idle loop, one tick a wake: 300 invokers are three ticks of
    100 a second, and no turn of the loop carries more than one."""
    pings, _end = _drive_fleet(300, 3.0)
    worst = max(len(b) for b in pings.blocks().values())
    assert worst == 100 <= fleet.PING_TICK
    by_invoker = pings.by_invoker()
    assert sorted(by_invoker) == list(range(300))
    # three rounds in the 3 s, and a fourth begun by the first tick's
    # invokers at most: no invoker twice in a round, none left out
    assert {len(v) for v in by_invoker.values()} <= {3, 4}
    assert {len(v) for i, v in by_invoker.items() if i >= 100} == {3}
    # registration is second 0 of the one schedule: the first pings are
    # spread over a second like every later round, and an invoker's clock
    # runs from its own first ping, whatever the rows behind it take
    for k in (0, 1, 2):
        at = sorted(v[k] for v in by_invoker.values())
        assert at[-1] - at[0] > 0.5       # spread over the second, no burst
    gaps = [b - a for v in by_invoker.values() for a, b in zip(v, v[1:])]
    assert min(gaps) > 0.5


def test_a_held_loop_delays_the_pings_and_thins_none_out():
    """2,560 invokers (20 ticks a second) on a loop whose every turn is
    held 200 ms: a wake sends every tick that fell due meanwhile, so each
    invoker is heard once a second, give or take how late the pinger
    wakes, the silence still open at the end counted too. That lateness
    is one to three turns (a timer that falls due in a turn fires in the
    next, and the task it wakes runs in the one after), so two pings of
    one invoker lie at most a second and two turns apart. A pinger that
    sends one tick a turn hears each invoker every 4 s here."""
    pings, end = _drive_fleet(2_560, 3.5, hold_s=0.2)
    assert pings.turn_max_s >= 0.2
    by_invoker = pings.by_invoker()
    assert sorted(by_invoker) == list(range(2_560))
    gaps = [b - a for v in by_invoker.values()
            for a, b in zip(v, v[1:] + [end])]
    assert max(gaps) <= 1.0 + 2 * pings.turn_max_s + 0.1, \
        (max(gaps), pings.turn_max_s)
    # and no invoker twice in one wake
    assert all(len(set(b)) == len(b) for b in pings.blocks().values())


def test_after_a_long_stall_one_ping_an_invoker_and_the_schedule_goes_on():
    """A 3 s hold of the loop: the next wake sends every invoker's newest
    due ping once (a block of exactly N, not three rounds), and the
    schedule goes on from the current second, one tick a wake."""
    n = 1_024
    held = {}

    async def stall():
        await asyncio.sleep(1.3)
        time.sleep(3.0)
        held["end"] = time.monotonic()

    pings, end = _drive_fleet(n, 6.0, stall=stall)
    after = [(turn, t, i) for turn, t, i in pings.sent if t >= held["end"]]
    first = after[0][0]
    block = [i for turn, _t, i in after if turn == first]
    assert sorted(block) == list(range(n))
    # no missed second is sent later either: one ping a second from here
    by_invoker = {}
    for _turn, t, i in after:
        by_invoker.setdefault(i, []).append(t)
    assert max(len(v) for v in by_invoker.values()) \
        <= 2 + int(end - held["end"])
    assert all(len(set(b)) == len(b) for b in pings.blocks().values())
    # and every invoker is heard again within a second of that block, give
    # or take how late the loop woke the pinger
    turn_max_s = max(d for t, d in pings.turns_s if t > held["end"] + 0.1)
    assert all(len(v) >= 2 and v[1] - v[0] <= 1.0 + 2 * turn_max_s + 0.1
               for v in by_invoker.values())


def test_the_fleet_accounts_for_what_it_delivered_in_the_window():
    """`window` opens and closes the account the log line prints: the
    pings sent in between, and the longest silence of one invoker that
    ended in it (on an idle loop, a second, give or take how late the
    pinger woke: two turns at the most)."""
    from openwhisk_tpu.messaging import MemoryMessagingProvider

    async def drive():
        provider = MemoryMessagingProvider()
        pings = _Pings(provider)
        sim = fleet.SimFleet(provider, 512, 2048, {}, {})
        turns = asyncio.ensure_future(pings.turns())
        try:
            await sim.start()
            await asyncio.sleep(1.2)
            assert sim.window_pings is None
            t0 = time.monotonic()
            sim.window(True)
            await asyncio.sleep(2.0)
            sim.window(False)
            return sim, time.monotonic() - t0, pings.turn_max_s
        finally:
            turns.cancel()
            await sim.stop()

    sim, window_s, turn_max_s = asyncio.run(drive())
    assert 512 * 2 - 128 <= sim.window_pings <= 512 * 2 + 128
    assert sim.window_pings / window_s == pytest.approx(512, rel=0.15)
    assert 0.9 < sim.window_gap_max_s <= 1.0 + 2 * turn_max_s + 0.1
    assert sim.pings >= sim.window_pings + 512


# -- set-up ends by the clock ----------------------------------------------------

def _toy_config() -> dict:
    with open(os.path.join(BENCH, "configs", "fleet1k.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy", invokers=16)
    cfg["actions"]["count"] = 8
    return cfg


class _NeverPings(fleet.SimFleet):
    async def start(self):
        pass


class _NeverStarts(fleet.SimFleet):
    async def start(self):
        await asyncio.Event().wait()


@pytest.mark.parametrize("broken", [_NeverPings, _NeverStarts])
def test_a_fleet_that_does_not_come_up_ends_set_up_with_an_error(
        monkeypatch, broken):
    from benchmark import traffic
    from openwhisk_tpu.core.entity import ConcurrencyLimit, MemoryLimit

    monkeypatch.setattr(MemoryLimit, "MAX", MemoryLimit.MAX)
    monkeypatch.setattr(ConcurrencyLimit, "MAX", ConcurrencyLimit.MAX)
    monkeypatch.setattr(run, "FLEET_UP_WAIT_S", 1.5)
    monkeypatch.setattr(fleet, "SimFleet", broken)
    cfg = _toy_config()

    async def drive():
        sut = run.Sut(cfg, traffic.make_catalog(cfg, 5), "never-up")
        t0 = time.monotonic()
        try:
            with pytest.raises(run.BenchError, match="never became healthy"):
                await sut.start()
            return time.monotonic() - t0
        finally:
            await sut.stop()

    run.device_or_exit(1)
    took = asyncio.run(drive())
    assert took >= 1.5


_STUCK = """
import sys, time
sys.path.insert(0, {root!r})
from benchmark import run
run.SETUP_LIMIT_S = 0.5
with run.setup_limit() as window_opens:
    if {opens}:
        window_opens()
    time.sleep({sleep})     # a loop that never yields
print("a result line")
"""


@pytest.mark.parametrize("opens,sleep,rc,out", [
    (False, 60, 2, ""),
    (True, 1.5, 0, "a result line\n"),
])
def test_the_watchdog_ends_a_set_up_that_never_yields(opens, sleep, rc, out):
    done = subprocess.run(
        [sys.executable, "-c",
         _STUCK.format(root=run.ROOT, opens=opens, sleep=sleep)],
        capture_output=True, text=True, timeout=50)
    assert done.returncode == rc
    assert done.stdout == out
    # it says so, and where every thread stood
    assert ("set-up passed its limit" in done.stderr) == (rc == 2)
    assert ("<module>" in done.stderr) == (rc == 2)
