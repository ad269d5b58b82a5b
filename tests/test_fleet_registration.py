"""A fleet of 10,240 invokers registering at once: the work of
registration, health folding and the ping path grows with the fleet, not
with its square. Everything here counts on the CPU twin; nothing is timed.

* a wave of new rows (what one wake of the health feed's pings
  registered) is one capacity scatter, one install of host books built
  from values the host knows, one `reg` record; no device->host read-back
  but the pad's growths;
* the coprime probe steps are worked out once per partition size, when a
  placement first needs them, never on a flip;
* every flip buffered before a device step is folded by that step,
  traffic or none, in one scatter padded to a power of two;
* a wake's pings are one block under one `ow_ping` span with its `n`,
  each ping meaning what it meant (admin address, registration, the
  watchdog's offline rule);
* the health topic keeps two seconds of 10,240 pings.
"""
from __future__ import annotations

import asyncio
import contextlib
import json

import jax
import numpy as np
import pytest

from openwhisk_tpu.controller.loadbalancer import (HEALTHY, OFFLINE,
                                                   TpuBalancer, supervision,
                                                   tpu_balancer)
from openwhisk_tpu.controller.loadbalancer.journal import PlacementJournal
from openwhisk_tpu.controller.loadbalancer.supervision import (InvokerPool,
                                                               parse_pings)
from openwhisk_tpu.core.entity import (ControllerInstanceId, Identity,
                                       InvokerInstanceId, MB)
from openwhisk_tpu.messaging import MemoryMessagingProvider, PingMessage
from openwhisk_tpu.messaging.connector import (HEALTH_RETENTION_BYTES,
                                               HEALTH_TOPIC)
from tests.test_balancers import make_action, make_msg

FLEET = 10_240
#: the health feed's peek: the most pings one wake brings
WAVE = 128


def _balancer(**kw) -> TpuBalancer:
    # few action slots: the [N, A] matrix at 16,384 rows stays small here
    return TpuBalancer(MemoryMessagingProvider(), ControllerInstanceId("0"),
                       managed_fraction=1.0, blackbox_fraction=0.0,
                       action_slots=8, prewarm=False, **kw)


def _waves(n: int = FLEET, size: int = WAVE):
    for lo in range(0, n, size):
        yield [(InvokerInstanceId(i, user_memory=MB(2048)), HEALTHY)
               for i in range(lo, min(n, lo + size))]


class _CountingNumpy:
    """The balancer module's `np`, with every `asarray` of a device array
    counted (a device->host read-back)."""

    def __init__(self):
        self.readbacks = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self.readbacks += 1
        return np.asarray(a, *args, **kw)


def _count_calls(monkeypatch, obj, name: str) -> list:
    calls, real = [], getattr(obj, name)

    def counted(*args, **kw):
        calls.append(args)
        return real(*args, **kw)
    monkeypatch.setattr(obj, name, counted)
    return calls


# -- registration ------------------------------------------------------------

def test_a_wave_of_new_rows_costs_one_books_patch_and_no_read_back(
        monkeypatch, tmp_path):
    bal = _balancer()
    journal = PlacementJournal(str(tmp_path / "wal"))
    bal.attach_journal(journal)
    counting = _CountingNumpy()
    monkeypatch.setattr(tpu_balancer, "np", counting)
    installs = _count_calls(monkeypatch, bal, "_set_books_now")
    patches = _count_calls(monkeypatch, bal, "_with_rows_since")
    growths = _count_calls(monkeypatch, bal, "_grow_padding")
    waves = list(_waves())
    for wave in waves:
        bal._status_changes(wave)
    assert bal._n_pad == 16_384
    # the pad doubled from 64 to 16,384: eight growths, each installing
    # the re-padded state (which reads its books back once) and each
    # reading the old state back to re-pad it (three arrays); every
    # wave patches the host's books once
    assert len(growths) == 8
    assert len(installs) == len(growths)
    assert len(patches) == len(waves)
    assert counting.readbacks == 4 * len(growths)
    assert len(bal._registry) == len(bal._caps_mb) == FLEET
    assert bal._caps_mb.tolist() == [2048] * FLEET
    # the host's books are the device's, with no read-back to make them
    np.testing.assert_array_equal(bal._books_cache,
                                  np.asarray(bal.state.free_mb))
    assert bal._books_cache[:FLEET].tolist() == [2048] * FLEET
    assert not bal._books_cache[FLEET:].any()
    # one `reg` record a wave, the rows in order
    journal.flush()
    regs = [r for r in journal.records() if r["t"] == "reg"]
    assert len(regs) == len(waves)
    assert [j["instance"] for r in regs for j in r["reg"]] \
        == list(range(FLEET))
    assert all(r["healthy"] == [True] * len(r["reg"]) for r in regs)
    journal.close()


def test_a_step_in_flight_over_a_registration_keeps_its_holds():
    """A step dispatched before a wave registered reads the new rows back
    empty: its books land with their capacity patched in, and its holds
    on the old rows kept; a step dispatched after lands as it is."""
    bal = _balancer()
    bal._status_changes(next(_waves(16)))
    before = bal._next_books_seq()              # a step in flight
    bal._status_changes([(InvokerInstanceId(i, user_memory=MB(2048)),
                          HEALTHY) for i in range(16, 40)])
    held = np.zeros(bal._n_pad, np.int32)
    held[:16] = 2048 - 256
    bal._install_books(held, before)
    assert bal._books_cache[:16].tolist() == [2048 - 256] * 16
    assert bal._books_cache[16:40].tolist() == [2048] * 24
    assert not bal._books_cache[40:].any()
    after = bal._next_books_seq()
    later = bal._books_cache.copy()
    later[20] -= 512
    bal._install_books(later, after)
    np.testing.assert_array_equal(bal._books_cache, later)
    assert bal._reg_marks == []
    # an older step that lands late changes nothing
    bal._install_books(held, before)
    np.testing.assert_array_equal(bal._books_cache, later)


def test_a_row_announced_again_refreshes_its_capacity_alone():
    bal = _balancer()
    bal._status_changes(next(_waves(16)))
    caps = bal._caps_mb
    bal._status_change(InvokerInstanceId(3, user_memory=MB(1024)), HEALTHY)
    assert bal._caps_mb is caps                  # updated, not rebuilt
    assert bal._caps_mb.tolist() == [2048] * 3 + [1024] + [2048] * 12


def test_a_replayed_journal_registers_the_same_fleet(tmp_path):
    bal = _balancer()
    journal = PlacementJournal(str(tmp_path / "wal"))
    bal.attach_journal(journal)
    for wave in _waves(600, 100):
        bal._status_changes(wave)
    bal._status_change(InvokerInstanceId(7), OFFLINE)
    bal._fold_now()
    journal.flush()
    again = _balancer()
    again.replay_journal(list(journal.records()))
    assert again._n_pad == bal._n_pad == 1024
    assert [i.instance for i in again._registry] == list(range(600))
    np.testing.assert_array_equal(np.asarray(again.state.free_mb),
                                  np.asarray(bal.state.free_mb))
    np.testing.assert_array_equal(np.asarray(again.state.health),
                                  np.asarray(bal.state.health))
    assert again._caps_mb.tolist() == bal._caps_mb.tolist()
    journal.close()


# -- partitions ------------------------------------------------------------------

def test_probe_steps_once_per_fleet_size_when_a_placement_needs_them(
        monkeypatch):
    calls = _count_calls(monkeypatch, tpu_balancer, "pairwise_coprimes")
    bal = _balancer()
    for wave in _waves():
        bal._status_changes(wave)
    assert calls == []                           # registering costs none
    for i in range(0, FLEET, 97):                # flips cost none
        bal._status_change(InvokerInstanceId(i), OFFLINE)
        bal._status_change(InvokerInstanceId(i), HEALTHY)
    assert calls == []
    assert (bal.managed_count, bal.blackbox_count) == (FLEET, 1)
    ident = Identity.generate("guest")
    for k in range(20):
        action = make_action(f"a{k}")
        bal._build_row(action, make_msg(action, ident))
    assert calls == [(FLEET,)]
    bal._status_changes(next(_waves(FLEET + 1, FLEET + 1))[FLEET:])
    action = make_action("b")
    bal._build_row(action, make_msg(action, ident))
    assert calls == [(FLEET,), (FLEET + 1,)]


def test_partition_sizes_are_worked_out_only_when_the_fleet_grows(
        monkeypatch):
    bal = _balancer()
    recomputed = _count_calls(monkeypatch, bal, "_recompute_partitions")
    waves = list(_waves(1024))
    for wave in waves:
        bal._status_changes(wave)
    assert len(recomputed) == len(waves)
    for i in range(0, 1024, 5):
        bal._status_change(InvokerInstanceId(i), OFFLINE)
    assert len(recomputed) == len(waves)


# -- health folding ------------------------------------------------------------

@pytest.mark.parametrize("traffic", [False, True],
                         ids=["idle", "under-traffic"])
def test_a_fresh_fleet_is_usable_after_one_device_step(monkeypatch,
                                                       traffic):
    """Every flip of 10,240 registrations is on the device after the one
    step that follows them: the fold a flip arms on an idle balancer, or,
    with requests pending, the fused step, which folds what passes its
    fixed health section first."""

    async def go():
        bal = _balancer()
        bal.send_activation_to_invoker = _sent
        folds = _count_calls(monkeypatch, bal, "_fold_now")
        steps = _count_calls(monkeypatch, bal, "_dispatch_batch")
        for wave in _waves():
            bal._status_changes(wave)
        assert len(bal._health_updates) == FLEET
        assert not np.asarray(bal.state.health).any()
        if traffic:
            action = make_action("t")
            await bal.publish(action, make_msg(action,
                                               Identity.generate("guest")))
        for _ in range(500):
            if not bal._health_updates and not bal._pending:
                break
            await asyncio.sleep(0.01)
        health = np.asarray(bal.state.health)
        assert health[:FLEET].all() and not health[FLEET:].any()
        assert len(folds) == 1
        assert len(steps) == int(traffic)
        await bal.close()

    async def _sent(msg, invoker):
        return None

    asyncio.run(go())


def test_a_flip_scatter_compiles_once_per_power_of_two():
    bal = _balancer()
    bal._status_changes(next(_waves(1024, 1024)))
    sizes = set()
    for n in range(1, 300):
        idx, vals = bal._padded_rows(np.arange(n), np.ones(n, bool))
        assert len(idx) == len(vals) >= n
        assert np.asarray(idx)[n:].tolist() == [n - 1] * (len(idx) - n)
        sizes.add(len(idx))
    assert sizes == {8, 16, 32, 64, 128, 256, 512}


# -- the ping path -----------------------------------------------------------------

def _ping(i: int, admin=None) -> bytes:
    return PingMessage(InvokerInstanceId(i, user_memory=MB(2048)),
                       admin=admin).serialize()


class _Spans:
    """Stands in for supervision's `span`: records (name, stats)."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **stats):
        self.seen.append((name, stats))
        return contextlib.nullcontext()


def test_a_wake_of_pings_is_one_block_and_one_wave(monkeypatch):
    spans = _Spans()
    monkeypatch.setattr(supervision, "span", spans)
    waves = []
    pool = InvokerPool(MemoryMessagingProvider(),
                       on_status_changes=waves.append)
    block = [_ping(i) for i in range(WAVE - 2)] + [
        b"not json", _ping(500, admin="http://10.0.0.5:8085")]
    pool.on_ping_block(block)
    assert spans.seen == [("ow_ping", {"n": WAVE})]
    assert len(waves) == 1
    assert [(i.instance, s) for i, s in waves[0]] \
        == [(i, HEALTHY) for i in range(WAVE - 2)] + [(500, HEALTHY)]
    assert pool.invoker_admin == {500: "http://10.0.0.5:8085"}
    # the same block a second later: one parse of the block, no change
    parses = _count_calls(monkeypatch, supervision, "parse_pings")
    pool.on_ping_block(block)
    assert waves == [waves[0]] and parses == [(block,)]
    assert spans.seen[-1] == ("ow_ping", {"n": WAVE})


def test_offline_after_the_ping_timeout_as_before(monkeypatch):
    waves = []
    pool = InvokerPool(MemoryMessagingProvider(),
                       on_status_changes=waves.append, ping_timeout=10.0)
    pool.on_ping_block([_ping(i) for i in range(6)])
    now = supervision.time.monotonic()
    for i in (1, 4):
        pool.invokers[i].last_ping = now - 11.0
    asyncio.run(pool._check_offline())
    assert [(i.instance, s) for i, s in waves[-1]] \
        == [(1, OFFLINE), (4, OFFLINE)]
    assert [st.status for st in pool.health()] \
        == [HEALTHY, OFFLINE, HEALTHY, HEALTHY, OFFLINE, HEALTHY]
    # a ping brings an offline invoker back, in a wave of its own
    pool.on_ping_block([_ping(4)])
    assert [(i.instance, s) for i, s in waves[-1]] == [(4, HEALTHY)]


class _Clock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _pool_heard_at(clock: _Clock, n: int = 4) -> tuple:
    waves = []
    pool = InvokerPool(MemoryMessagingProvider(),
                       on_status_changes=waves.append, ping_timeout=10.0)
    pool.on_ping_block([_ping(i) for i in range(n)])
    waves.clear()
    return pool, waves


def _tick(pool: InvokerPool, clock: _Clock, late_s: float = 0.0) -> None:
    """One watchdog tick, its period (and `late_s` of a held loop) after
    the last one."""
    clock.t += supervision.WATCHDOG_INTERVAL_S + late_s
    asyncio.run(pool._check_offline())


def test_a_held_loop_is_nobody_s_silence(monkeypatch):
    """The watchdog ticks once a second. When a tick finds the loop was
    held past a ping period (a compile of the controller's own), the pings
    that came meanwhile sit unread: the held span is not counted in any
    invoker's silence. A tick late by less is on time: silence is
    silence."""
    clock = _Clock()
    monkeypatch.setattr(supervision.time, "monotonic", clock)

    def pool_after(late_s: float) -> tuple:
        pool, waves = _pool_heard_at(clock)
        asyncio.run(pool._check_offline())
        for _ in range(9):                       # nine seconds, on time
            _tick(pool, clock)
        _tick(pool, clock, late_s)
        return pool, waves

    # a tick late by half a hold: 10.5 s of silence
    _pool, waves = pool_after(0.5 * supervision.HELD_LOOP_S)
    assert [[(i.instance, s) for i, s in w] for w in waves] \
        == [[(i, OFFLINE) for i in range(4)]]
    # 9 s, then 11 s held: 10 s of silence, not 20
    pool, waves = pool_after(11.0)
    assert waves == []
    pool.on_ping_block([_ping(0)])
    _tick(pool, clock)                           # 11 s for 1, 2 and 3
    assert [[(i.instance, s) for i, s in w] for w in waves] \
        == [[(i, OFFLINE) for i in (1, 2, 3)]]
    for _ in range(9):
        _tick(pool, clock)                       # 10 s for 0
    assert [st.status for st in pool.health()][0] == HEALTHY
    _tick(pool, clock)
    assert [st.status for st in pool.health()] == [OFFLINE] * 4


def test_a_ping_read_between_the_tick_s_due_time_and_the_hold(monkeypatch):
    """The watchdog's timer is due, but the busy loop runs a ping block
    first, and then stops for a compile: that invoker's silence starts at
    the tick that finds the hold, the others' are moved on by the hold."""
    clock = _Clock()
    monkeypatch.setattr(supervision.time, "monotonic", clock)
    pool, waves = _pool_heard_at(clock)
    asyncio.run(pool._check_offline())
    clock.t += supervision.WATCHDOG_INTERVAL_S + 0.3
    pool.on_ping_block([_ping(3)])
    _tick(pool, clock, 11.0 - supervision.WATCHDOG_INTERVAL_S)
    assert waves == []
    for _ in range(9):
        _tick(pool, clock)
    assert waves == []
    _tick(pool, clock)                           # 11 s for 0, 1 and 2
    assert [[(i.instance, s) for i, s in w] for w in waves] \
        == [[(i, OFFLINE) for i in (0, 1, 2)]]
    _tick(pool, clock)
    assert [(i.instance, s) for i, s in waves[-1]] == [(3, OFFLINE)]


@pytest.mark.parametrize("hold_s", [1.5, 3.0, 12.0])
def test_a_dead_invoker_goes_offline_under_holds_again_and_again(
        monkeypatch, hold_s):
    """Every tick finds the loop held: only the held spans are forgiven,
    so an invoker that never pings again is offline once ten seconds of
    its silence fell outside them, while one that pings every tick
    stays."""
    clock = _Clock()
    monkeypatch.setattr(supervision.time, "monotonic", clock)
    pool, waves = _pool_heard_at(clock, n=2)
    asyncio.run(pool._check_offline())
    t0 = clock.t
    ticks = 0
    while pool.invokers[1].status != OFFLINE:
        pool.on_ping_block([_ping(0)])
        _tick(pool, clock, hold_s)
        ticks += 1
        assert ticks <= 11
    # 11 periods of one second outside the holds: past the 10 s timeout
    assert ticks == 11
    assert clock.t - t0 == pytest.approx(11 * (1.0 + hold_s))
    assert [[(i.instance, s) for i, s in w] for w in waves] == [[(1, OFFLINE)]]
    assert pool.invokers[0].status == HEALTHY


def test_a_block_that_does_not_parse_whole_is_parsed_ping_by_ping():
    got = parse_pings([_ping(1), b"{", b"[1]", b'{"name": {}}', _ping(2)])
    assert [p and p[0].instance for p in got] == [1, None, None, None, 2]


def test_the_sharding_balancer_takes_a_wave_in_one_update(monkeypatch):
    from openwhisk_tpu.controller.loadbalancer import ShardingBalancer
    bal = ShardingBalancer(MemoryMessagingProvider(), ControllerInstanceId("0"))
    updates = _count_calls(monkeypatch, bal.policy, "update_invokers")
    bal.supervision.on_ping_block([_ping(i) for i in (0, 1, 3)])
    assert len(updates) == 1
    assert [i.instance for i in bal._registry] == [0, 1, 2, 3]
    assert bal._usable == [True, True, False, True]


def test_the_feed_hands_a_wake_to_the_pool_as_one_block(monkeypatch):
    spans = _Spans()
    monkeypatch.setattr(supervision, "span", spans)

    async def go():
        provider = MemoryMessagingProvider()
        waves = []
        pool = InvokerPool(provider, on_status_changes=waves.append)
        pool.start()
        producer = provider.get_producer()
        for i in range(300):
            await producer.send(HEALTH_TOPIC,
                                PingMessage(InvokerInstanceId(i)))
        for _ in range(200):
            if len(pool.invokers) == 300:
                break
            await asyncio.sleep(0.01)
        await pool.stop()
        return pool, waves

    pool, waves = asyncio.run(go())
    assert sorted(pool.invokers) == list(range(300))
    blocks = [st["n"] for name, st in spans.seen if name == "ow_ping"]
    assert sum(blocks) == 300 and max(blocks) <= WAVE
    assert len(waves) == len(blocks)


# -- the health topic --------------------------------------------------------------

def test_the_health_topic_keeps_two_seconds_of_10k_pings():
    ping = _ping(FLEET - 1)
    assert HEALTH_RETENTION_BYTES >= 2 * FLEET * len(ping)

    async def go():
        provider = MemoryMessagingProvider()
        provider.ensure_topic(HEALTH_TOPIC,
                              retention_bytes=HEALTH_RETENTION_BYTES)
        consumer = provider.get_consumer(HEALTH_TOPIC, "health-0",
                                         max_peek=1 << 20, from_latest=True)
        producer = provider.get_producer()
        for second in range(3):
            for i in range(FLEET):
                await producer.send(HEALTH_TOPIC, _ping(i))
        return await consumer.peek(1 << 20, 0.01)

    kept = asyncio.run(go())
    # three seconds sent while nobody read: the last two are all there
    assert len(kept) >= 2 * FLEET
    last = [json.loads(p)["name"]["instance"] for *_, p in kept[-2 * FLEET:]]
    assert last == list(range(FLEET)) * 2



# -- the spans on a traced run -------------------------------------------------------

def test_the_three_spans_and_their_stats_are_in_a_trace(tmp_path):
    """Under a profiler session: a wake of pings that registers 300 rows
    (`ow_ping` with `n`, then one `ow_register` a wave with `rows`), and
    the first placement on the new fleet (`ow_partitions` with `managed`
    and `blackbox`)."""
    from tests.test_spans import _host_lines

    bal = _balancer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for lo in (0, 128, 256):
            bal.supervision.on_ping_block(
                [_ping(i) for i in range(lo, min(300, lo + 128))])
        action = make_action("p")
        bal._build_row(action, make_msg(action, Identity.generate("guest")))
    finally:
        jax.profiler.stop_trace()
    seen = [(name, st) for line in _host_lines(str(tmp_path))
            for name, _s, _e, st in line]
    assert [st["n"] for name, st in seen if name == "ow_ping"] \
        == [128, 128, 44]
    assert [st["rows"] for name, st in seen if name == "ow_register"] \
        == [128, 128, 44]
    assert [(st["managed"], st["blackbox"]) for name, st in seen
            if name == "ow_partitions"] == [(300, 1)]
