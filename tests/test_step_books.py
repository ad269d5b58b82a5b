"""ISSUE 31: one dispatch and one transfer each way per step.

The post-step books ride the fused step's own output vector (and the
release-only fold returns them next to the state), so a step is one jitted
call on the loop and one device->host conversion on the readback worker;
`occupancy()`'s cache and the flight recorder read a slice of that one host
copy. Everything here runs on the CPU twin and counts; nothing is timed.
"""
from __future__ import annotations

import asyncio
import json
import os
import threading

import jax
import numpy as np
import pytest

from openwhisk_tpu.controller.loadbalancer import (LoadBalancerException,
                                                   TpuBalancer, tpu_balancer)
from openwhisk_tpu.controller.loadbalancer.base import maybe_batch_publish
from openwhisk_tpu.controller.loadbalancer.flight_recorder import \
    free_slot_histogram
from openwhisk_tpu.core.entity import ControllerInstanceId, Identity
from openwhisk_tpu.messaging import MemoryMessagingProvider
from openwhisk_tpu.models.sharding_policy import MIN_SLOT_MB
from tests.test_balancers import _fleet, _ping_all, make_action, make_msg

N_INVOKERS = 4
#: the rate-admission variant's limit: high enough that nothing throttles
RATE = 600_000


class _CountingNumpy:
    """Stands in for the balancer module's `np`: numpy itself, but every
    `asarray` of a device array is noted with the thread that made it."""

    def __init__(self):
        self.conversions = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self.conversions.append(threading.current_thread())
        return np.asarray(a, *args, **kw)


class _Counted:
    """One balancer with its device->host conversions, `_read_back` calls
    and the profiler's per-entry call counts under watch."""

    def __init__(self, bal, monkeypatch):
        self.bal = bal
        self.np = _CountingNumpy()
        monkeypatch.setattr(tpu_balancer, "np", self.np)
        self.read_backs = 0
        real = bal._read_back

        def counted(step):
            self.read_backs += 1
            return real(step)

        bal._read_back = counted
        self._calls0 = self.calls()

    def calls(self) -> dict:
        return {name: e["calls"]
                for name, e in self.bal.profiler.cache_census().items()}

    def new_calls(self) -> dict:
        now = self.calls()
        return {k: v - self._calls0.get(k, 0) for k, v in now.items()
                if v != self._calls0.get(k, 0)}


async def _quiet(bal) -> None:
    for _ in range(600):
        if not (bal._inflight_steps or bal._pending or bal._releases
                or bal._readbacks):
            break
        await asyncio.sleep(0.01)
    await asyncio.sleep(0.02)


async def _balancer(**kw):
    """A started balancer over N_INVOKERS invokers that never ack (what a
    step placed stays placed until the test completes it by hand)."""
    provider = MemoryMessagingProvider()
    bal = TpuBalancer(provider, ControllerInstanceId("0"),
                      managed_fraction=1.0, blackbox_fraction=0.0,
                      prewarm=False, **kw)
    await bal.start()
    invokers, producer = await _fleet(provider, N_INVOKERS, delay=3600.0)
    await _ping_all(invokers, producer)
    await _quiet(bal)
    return bal, invokers


async def _close(bal, invokers) -> None:
    await bal.close()
    for inv in invokers:
        await inv.stop()
    # the invokers' sleeping acks: nobody waits for them
    for t in asyncio.all_tasks():
        if t is not asyncio.current_task():
            t.cancel()


async def _one_step(bal, n: int, name: str = "held") -> None:
    """Publish `n` activations at once: one fused step places them."""
    ident = Identity.generate("guest")
    action = make_action(name, memory=128)
    publisher = maybe_batch_publish(bal)
    await asyncio.gather(*[publisher.publish(action,
                                             make_msg(action, ident, True))
                           for _ in range(n)])
    await _quiet(bal)


def _digest_of(bal, free: np.ndarray) -> dict:
    """What the parent's readback worker computed from its separate copy of
    the post-step books."""
    caps = bal._caps_mb
    n_reg = min(len(caps), len(free))
    cap_total = int(caps[:n_reg].sum())
    used = cap_total - int(free[:n_reg].sum())
    return {"free_slot_hist": free_slot_histogram(free[:n_reg], MIN_SLOT_MB),
            "occupancy": round(used / cap_total, 4) if cap_total else 0.0}


@pytest.mark.parametrize("n", [5, 20], ids=["bucket8", "bucket32"])
@pytest.mark.parametrize("donate", [True, False],
                         ids=["donated", "undonated"])
@pytest.mark.parametrize("kind", ["plain", "rate", "fold"])
def test_a_step_is_one_program_and_one_transfer(kind, donate, n,
                                                monkeypatch):
    async def go():
        bal, invokers = await _balancer(
            donate_state=donate,
            rate_limit_per_minute=RATE if kind == "rate" else None)
        assert bal._donate is donate
        step_entry = "fused_admit_step" if kind == "rate" else "fused_step"
        loop_thread = threading.current_thread()
        await _one_step(bal, n, "warm")     # this bucket's compile
        watch = _Counted(bal, monkeypatch)
        seq0 = bal._books_cache_seq
        try:
            if kind == "fold":
                # complete what the warm step placed: one idle fold
                for entry in list(bal.activation_slots.values()):
                    bal.process_completion(entry.id, forced=False,
                                           is_system_error=False,
                                           invoker=entry.invoker)
                await _quiet(bal)
                assert watch.new_calls() == {"release_packed": 1}
                assert watch.read_backs == 0
                free = np.asarray(bal.state.free_mb)
                assert free[:N_INVOKERS].tolist() == [2048] * N_INVOKERS
            else:
                await _one_step(bal, n)
                assert watch.new_calls() == {step_entry: 1}
                assert watch.read_backs == 1
                free = np.asarray(bal.state.free_mb)
                assert int((2048 - free[:N_INVOKERS]).sum()) == 2 * n * 128
                digest = bal.flight_recorder.recent(1)[0]["digest"]
                want = _digest_of(bal, free)
                assert {k: digest[k] for k in want} == want
            # ONE device->host conversion, off the loop's thread
            assert len(watch.np.conversions) == 1
            assert watch.np.conversions[0] is not loop_thread
            # occupancy() serves this very step's books, and no device
            assert bal._books_cache_seq > seq0
            np.testing.assert_array_equal(bal._books_cache, free)
            rows = bal.occupancy()["invokers"]
            assert [r["free_mb"] for r in rows] \
                == free[:N_INVOKERS].tolist()
            assert len(watch.np.conversions) == 1
        finally:
            await _close(bal, invokers)

    asyncio.run(go())


@pytest.mark.parametrize("donate", [True, False],
                         ids=["donated", "undonated"])
def test_out_of_order_readbacks_install_the_newest_books(donate):
    """Two steps in the pipeline, the first one's readback held back until
    the second has landed: the cache keeps the second's books."""
    async def go():
        bal, invokers = await _balancer(donate_state=donate, max_batch=8,
                                        pipeline_depth=2)
        await _one_step(bal, 16, "warm")
        real = bal._read_back
        first_may_go = threading.Event()
        order = []

        def held(step):
            k = len(order)
            order.append(k)
            if k == 0:
                assert first_may_go.wait(30)
            return real(step)

        bal._read_back = held
        installs = []
        real_install = bal._install_books

        def install(books, seq):
            installs.append(seq)
            real_install(books, seq)
            if len(installs) == 1:
                first_may_go.set()

        bal._install_books = install
        try:
            await _one_step(bal, 16)
            # the second step's books landed first, the first's were
            # dropped by the guard
            assert len(installs) == 2 and installs[0] > installs[1]
            assert bal._books_cache_seq == installs[0]
            np.testing.assert_array_equal(bal._books_cache,
                                          np.asarray(bal.state.free_mb))
        finally:
            first_may_go.set()
            await _close(bal, invokers)

    asyncio.run(go())


@pytest.mark.parametrize("rate", [None, RATE], ids=["plain", "rate"])
@pytest.mark.parametrize("donate", [True, False],
                         ids=["donated", "undonated"])
def test_a_failed_readback_is_reversed_on_the_device(donate, rate):
    """The compensation path decodes `chosen` off the DEVICE vector through
    the one decoder and releases exactly what the step placed."""
    async def go():
        bal, invokers = await _balancer(donate_state=donate,
                                        rate_limit_per_minute=rate)
        await _one_step(bal, 5, "warm")
        free0 = np.asarray(bal.state.free_mb).copy()
        conc0 = np.asarray(bal.state.conc_free).copy()
        cache0 = bal._books_cache.copy()

        def poisoned(step):
            raise RuntimeError("device died mid-readback")

        bal._read_back = poisoned
        ident = Identity.generate("guest")
        action = make_action("phantom", memory=256)
        try:
            got = await asyncio.gather(
                *[bal.publish(action, make_msg(action, ident, True))
                  for _ in range(5)], return_exceptions=True)
            assert all(isinstance(e, LoadBalancerException) for e in got)
            await _quiet(bal)
            np.testing.assert_array_equal(np.asarray(bal.state.free_mb),
                                          free0)
            np.testing.assert_array_equal(np.asarray(bal.state.conc_free),
                                          conc0)
            # the failed step's books were never installed
            np.testing.assert_array_equal(bal._books_cache, cache0)
            assert sum(bal._slots.refcount.values()) \
                == len(bal.activation_slots) > 0
        finally:
            await _close(bal, invokers)

    asyncio.run(go())


@pytest.mark.parametrize("kind", ["plain", "rate"])
def test_a_journal_the_parent_wrote_replays_with_parity(kind):
    """`tests/fixtures/journal_pr30.json`: the records of two toy runs
    journaled by the commit before this change (batch, ack, fold and reg
    records; forced placements; under `rate` throttled rows), with the
    books each run left. The record formats are unchanged: the tree
    re-derives every journaled decision and the same books."""
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "journal_pr30.json")
    with open(path) as f:
        run = json.load(f)[kind]
    recs = run["records"]
    assert {r["t"] for r in recs} == {"reg", "batch", "ack", "fold"}
    bal = TpuBalancer(MemoryMessagingProvider(), ControllerInstanceId("1"),
                      managed_fraction=1.0, blackbox_fraction=0.0,
                      prewarm=False)
    stats = bal.replay_journal(recs)
    assert stats["batches"] == sum(r["t"] == "batch" for r in recs) == 3
    assert stats["parity_mismatches"] == 0
    free = np.asarray(bal.state.free_mb)
    assert free.tolist() == run["free_mb"]
    assert (free[:N_INVOKERS] < 0).any(), "the run forced placements"
    conc = np.asarray(bal.state.conc_free)
    assert [[int(i), int(j), int(conc[i, j])]
            for i, j in zip(*np.nonzero(conc))] == run["conc_nonzero"]
    # the cache was installed from the replayed state
    np.testing.assert_array_equal(bal._books_cache, free)
