"""ISSUE 29: the dispatch hold is what it amortises.

A partly filled batch is held open for `DISPATCH_HOLD_K` times the own
loop time one fused step is measured to cost (`_note_step_cost`), and only
while the arrival EWMA says the hold will gather more rows. CPU twin:
counts and policy, never a rate."""
from __future__ import annotations

import asyncio
import contextlib
import time

import pytest

from openwhisk_tpu.controller.loadbalancer import TpuBalancer, tpu_balancer
from openwhisk_tpu.core.entity import (ControllerInstanceId, Identity,
                                       InvokerInstanceId, MB)
from openwhisk_tpu.messaging import MemoryMessagingProvider, PingMessage

from tests.test_publish_batch import (_drain, _healthy_balancer, make_action,
                                      make_msg)

K = TpuBalancer.DISPATCH_HOLD_K
N = TpuBalancer.HOLD_SAMPLES


def _feed(bal, cost_s: float, n: int = N) -> None:
    for _ in range(n):
        bal._note_step_cost(cost_s)


def _pressure(bal, gap_ms: float = 0.01, last_gap_ms: float = 0.0) -> None:
    """Arrivals `gap_ms` apart, the last one `last_gap_ms` after its
    predecessor, and that one just now."""
    bal._gap_ewma_ms, bal._last_gap_ms = gap_ms, last_gap_ms
    bal._last_pub_t = time.monotonic()


def _pin(bal, hold_s: float) -> None:
    """Hold `hold_s` under pressure whatever the steps of this test cost."""
    bal._note_step_cost = lambda cost_s: None
    bal._hold_s = hold_s
    _pressure(bal)


def _rows(n: int, ident, memory: int = 128) -> list:
    action = make_action(memory=memory)
    return [(action, make_msg(action, ident)) for _ in range(n)]


def _policy(**kw) -> TpuBalancer:
    """A balancer that never starts: the hold's arithmetic needs no fleet."""
    return TpuBalancer(MemoryMessagingProvider(), ControllerInstanceId("0"),
                       prewarm=False, **kw)


def _with_balancer(body, **kw):
    async def go():
        bal = await _healthy_balancer(MemoryMessagingProvider(), **kw)
        try:
            return await body(bal)
        finally:
            await bal.close()

    return asyncio.run(go())


@pytest.fixture
def assembles(monkeypatch) -> list:
    """The stats of every `ow_assemble` span, as the program rides them."""
    seen = []

    def span(name, **stats):
        if name == "ow_assemble":
            seen.append(stats)
        return contextlib.nullcontext()

    monkeypatch.setattr(tpu_balancer, "span", span)
    return seen


@pytest.mark.parametrize("cost_s", [0.0004, 0.0015, 0.0076, 0.25])
def test_the_hold_is_k_times_the_estimate(cost_s):
    bal = _policy()
    _feed(bal, cost_s)
    _pressure(bal)
    assert bal._coalesce_window_s() == K * cost_s
    assert bal.metrics.gauge_value("loadbalancer_dispatch_hold_ms") \
        == K * cost_s * 1e3


@pytest.mark.parametrize("gap_ms, last_gap_ms, fed, held", [
    (0.01, 0.0, N, True),       # a burst: the hold is on
    (2.9, 2.9, N, True),        # one more row expected inside it
    (3.1, 0.0, N, False),       # a trickle: none expected
    (50.0, 50.0, N, False),
    (0.01, 3.1, N, False),      # a lone request after a burst
    (0.01, 500.0, N, False),
    (0.01, 0.0, 0, False),      # no step measured yet
    (0.01, 0.0, 1, False),      # one sample is no estimate
])
def test_the_hold_is_on_only_when_it_will_gather_rows(
        gap_ms, last_gap_ms, fed, held):
    bal = _policy()
    hold_s = 0.003
    _feed(bal, hold_s / K, fed)
    _pressure(bal, gap_ms, last_gap_ms)
    assert bal._coalesce_window_s() == (K * (hold_s / K) if held else 0.0)


@pytest.mark.parametrize("stalls", [1, 6])
def test_a_stall_inside_a_step_hardly_moves_the_hold(stalls):
    """One 300 ms sample (a collection, a first-sight compile) moves the
    next hold by under 5%; so do six in a row, set-up's shape ladder."""
    bal = _policy()
    for i in range(2 * N):
        bal._note_step_cost(0.0015 * (1 + 0.01 * (i % 5)))
    before = bal._hold_s
    for _ in range(stalls):
        bal._note_step_cost(0.3)
        assert abs(bal._hold_s - before) / before < 0.05


def test_the_estimate_follows_the_rows_a_step_carries():
    """Steps of 8 and of 128 rows order the holds as they order the costs,
    up and down again within HOLD_SAMPLES steps: the hold stays long where
    batches are large. Each row is given 200 us of loop time, ten times
    what the chip's fit gives it (ISSUE 29), so the twin's own noise under
    six test workers cannot reorder them."""
    per_row_s, invokers = 200e-6, 4

    async def body(bal):
        ident = Identity.generate("guest")
        producer = bal.provider.get_producer()
        assemble = bal._assemble_batch

        def per_row(batch, b, bp, t0):
            time.sleep(b * per_row_s)
            return assemble(batch, b, bp, t0)

        bal._assemble_batch = per_row
        holds = []
        for rows in (8, 128, 8):
            for _ in range(N):
                for i in range(invokers):  # a slow twin must not lose them
                    await producer.send("health", PingMessage(
                        InvokerInstanceId(i, user_memory=MB(65536))))
                outs = bal.publish_many(_rows(rows, ident))
                await asyncio.gather(*outs)
                await _drain(bal)
            holds.append(bal._hold_s)
        assert 0 < holds[0] < holds[1] > holds[2] > 0
        assert holds[1] - max(holds[0], holds[2]) > K * 60 * per_row_s

    _with_balancer(body, n_invokers=invokers, mem=65536, pipeline_depth=1)


def test_a_step_that_waited_for_the_loop_cost_that_wait_too():
    """The sample runs from the moment the hold was due: a loop that makes
    the flush task wait lengthens the estimate."""
    async def body(bal):
        ident = Identity.generate("guest")
        samples = []
        bal._note_step_cost = samples.append
        for blocked_s in (0.0, 0.0, 0.2):
            bal._hold_s = 0.05
            _pressure(bal)
            outs = bal.publish_many(_rows(3, ident))
            assert bal._pending            # held, not dispatched inline
            if blocked_s:
                # the loop is busy from before the hold runs out until
                # 150 ms after it
                await asyncio.sleep(0.04)
                time.sleep(blocked_s)
            await asyncio.gather(*outs)
            await _drain(bal)
        assert len(samples) == 3
        assert samples[2] - samples[1] > 0.1

    _with_balancer(body, n_invokers=4, mem=65536)


def test_the_loop_sleeps_through_the_hold_but_not_through_its_last_tick():
    """ISSUE 38: the selector sleeps in whole milliseconds, so a hold that
    ended in a sleep ended up to one late, and the estimate read that as
    the step's cost. The flush task sleeps to within `SELECTOR_TICK_S` of
    the hold's end and yields through the rest: no sleep of the loop
    reaches past the end of the hold, and the hold is still mostly slept."""
    async def body(bal):
        ident = Identity.generate("guest")
        loop = asyncio.get_event_loop()
        select = loop._selector.select
        asked = []          # (when the loop asked, for how long)

        def recording(timeout=None):
            asked.append((time.monotonic(), timeout))
            return select(timeout)

        dues = []
        step = bal._device_step

        async def device_step(delay, due):
            dues.append((due, time.monotonic()))
            return await step(delay, due)

        bal._device_step = device_step
        hold_s = 0.05
        _pin(bal, hold_s)
        loop._selector.select = recording
        try:
            outs = bal.publish_many(_rows(3, ident))
            assert bal._pending            # held, not dispatched inline
            await asyncio.gather(*outs)
        finally:
            del loop._selector.select
        await _drain(bal)
        (due, began), *_ = dues
        assert began >= due                # never early
        held = [(t, timeout) for t, timeout in asked if t < due]
        slept = [timeout for t, timeout in held if timeout]
        # it slept most of the hold away in a few sleeps ...
        assert slept and sum(slept) > hold_s / 2 and len(slept) < 20
        # ... none of which could end after the hold did
        assert all(t + timeout <= due for t, timeout in held if timeout)
        # and through the last tick it turned, sweep by sweep
        assert sum(1 for t, timeout in held
                   if not timeout and t > due - bal.SELECTOR_TICK_S) >= 1

    _with_balancer(body, n_invokers=4, mem=65536)


def test_a_full_batch_dispatches_at_once_whatever_the_hold():
    """Closed on size: inline while the pipeline has room, and without a
    sleep from the flush task when it has not."""
    async def body(bal):
        ident = Identity.generate("guest")
        _pin(bal, 60.0)
        first = bal.publish_many(_rows(8, ident))
        assert not bal._pending and bal._inflight_steps == 1
        await asyncio.gather(*first)
        await _drain(bal)
        # three full batches against a pipeline of one: each goes as soon
        # as the one before it is read back, none waits the minute
        outs = bal.publish_many(_rows(24, ident))
        await asyncio.wait_for(asyncio.gather(*outs), 20.0)
        # one row short of full is held
        bal.publish_many(_rows(7, ident))
        await asyncio.sleep(0.3)
        assert len(bal._pending) == 7 and not bal._flush_task.done()

    _with_balancer(body, n_invokers=4, mem=65536, max_batch=8,
                   pipeline_depth=1)


@pytest.mark.parametrize("pressed", [False, True])
def test_a_release_alone_still_arms_the_flush(pressed):
    async def body(bal):
        ident = Identity.generate("guest")
        (out,) = bal.publish_many(_rows(1, ident))
        await out
        await _drain(bal)
        if pressed:
            _pin(bal, 60.0)
        (aid,) = list(bal.activation_slots)
        entry = bal.activation_slots[aid]
        bal.release_invoker(entry.invoker, entry)
        assert bal._releases and not bal._flush_task.done()
        if not pressed:
            await _drain(bal)
            assert not bal._releases

    _with_balancer(body, n_invokers=1)


@pytest.mark.parametrize("name", ["ADAPTIVE_WINDOW_MS", "ADAPTIVE_MIN_BATCH"])
def test_no_constant_in_milliseconds_decides_the_hold(name):
    assert not hasattr(TpuBalancer, name)
    assert isinstance(K, int) and K >= 1


def test_ow_assemble_carries_the_hold_that_closed_the_batch(assembles):
    async def body(bal):
        ident = Identity.generate("guest")
        # eager on an idle pipeline: no hold closed this batch
        _pressure(bal, 1000.0, 1e9)
        await asyncio.gather(*bal.publish_many(_rows(3, ident)))
        await _drain(bal)
        # held: the flush task slept the hold
        _pin(bal, 0.05)
        await asyncio.gather(*bal.publish_many(_rows(3, ident)))
        await _drain(bal)
        # held, and full by the time the flush task gets the step lock:
        # closed on size
        async with bal._step_lock:
            outs = bal.publish_many(_rows(7, ident))
            outs += bal.publish_many(_rows(1, ident))
            assert len(bal._pending) == 8
            await asyncio.sleep(0.1)
        await asyncio.gather(*outs)
        await _drain(bal)
        return [(s["b"], s["hold_us"]) for s in assembles]

    assert _with_balancer(body, n_invokers=4, mem=65536, max_batch=8) \
        == [(3, 0), (3, 50_000), (8, 0)]


def test_the_gauge_is_the_hold_the_steps_measured():
    async def body(bal):
        ident = Identity.generate("guest")
        for _ in range(N):
            await asyncio.gather(*bal.publish_many(_rows(2, ident)))
            await _drain(bal)
        assert bal._hold_s == K * sorted(bal._step_costs)[1] > 0
        assert bal.metrics.gauge_value("loadbalancer_dispatch_hold_ms") \
            == bal._hold_s * 1e3
        return bal.metrics.prometheus_text()

    assert "openwhisk_loadbalancer_dispatch_hold_ms" in _with_balancer(
        body, n_invokers=2)
