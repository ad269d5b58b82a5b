"""loadbalancer/kernel_choice.py: the one owner of which program a fused
step runs (ISSUE 30).

  * `choose` as a pure function of what it observes: one table over
    platform x geometry x the two pins x mesh, no balancer built;
  * the per-bucket hybrid, written once, over each backend's two pairs;
  * a balancer adopting plans as its geometry moves: one swap, said once,
    and XLA for the rest of the process.
"""
import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from openwhisk_tpu.controller.loadbalancer import TpuBalancer  # noqa: E402
from openwhisk_tpu.controller.loadbalancer.kernel_choice import (  # noqa: E402
    REPAIR_MIN_BATCH, choose, pallas_pair, per_bucket, xla_pair)
from openwhisk_tpu.core.entity import (ControllerInstanceId,  # noqa: E402
                                       Identity)
from openwhisk_tpu.messaging import MemoryMessagingProvider  # noqa: E402
from openwhisk_tpu.parallel.fleet_mesh import (fleet_pair,  # noqa: E402
                                               make_fleet_mesh, shard_state)
from openwhisk_tpu.utils.eventlog import GLOBAL_EVENT_LOG  # noqa: E402
from tests.test_balancers import (_fleet, _ping_all, make_action,  # noqa: E402
                                  make_msg)
from tests.test_placement_repair import (_random_batch,  # noqa: E402
                                         _random_state)

#: geometries against the 8 MiB budget the CPU twin mirrors from the v5e
#: (ops/placement_pallas._VMEM_BUDGET_BYTES), as (n_pad, slots, max_batch)
FITS = (64, 4096, 256)          # standalone16: state 1 MiB + scratch 1 MiB
NO_SCRATCH = (256, 4096, 1024)  # state 4 MiB fits, + 16 MiB of scratch not
TOO_BIG = (1024, 4096, 256)     # fleet1k: 16 MiB of state


@pytest.fixture(scope="module")
def mesh():
    return make_fleet_mesh(2)


@pytest.mark.parametrize(
    "platform, geometry, kernel, pk, on_mesh, backend, algorithm, by, why", [
        # the two geometries the benchmark runs, as the chip sees them
        ("tpu", FITS, "auto", "auto", False,
         "pallas", "repair", "static", None),
        ("tpu", TOO_BIG, "auto", "auto", False,
         "xla", "repair", "static", "vmem_fallback"),
        # the state fits, the repair kernel's scratch does not
        ("tpu", NO_SCRATCH, "auto", "auto", False,
         "pallas", "scan", "static", "scratch_evicted"),
        ("tpu", NO_SCRATCH, "auto", "scan", False,
         "pallas", "scan", "static", None),
        # a pinned repair never becomes the Pallas scan
        ("tpu", NO_SCRATCH, "auto", "repair", False,
         "xla", "repair", "static", "vmem_fallback"),
        ("tpu", FITS, "auto", "scan", False,
         "pallas", "scan", "static", None),
        ("tpu", FITS, "xla", "auto", False,
         "xla", "repair", "explicit", None),
        # off the TPU Pallas is not on offer unless asked for by name
        ("cpu", FITS, "auto", "auto", False,
         "xla", "repair", "static", None),
        ("cpu", TOO_BIG, "auto", "scan", False,
         "xla", "scan", "static", None),
        ("cpu", FITS, "pallas", "repair", False,
         "pallas", "repair", "explicit", None),
        ("cpu", TOO_BIG, "pallas", "auto", False,
         "xla", "repair", "fallback", "vmem_fallback"),
        ("cpu", NO_SCRATCH, "pallas", "auto", False,
         "pallas", "scan", "explicit", "scratch_evicted"),
        # a mesh takes the sharded pair whatever the backend knob says
        ("tpu", TOO_BIG, "auto", "auto", True,
         "sharded", "repair", "static", None),
        ("cpu", FITS, "pallas", "scan", True,
         "sharded", "scan", "explicit", None),
    ])
def test_choose_is_a_function_of_what_it_observes(
        monkeypatch, mesh, platform, geometry, kernel, pk, on_mesh,
        backend, algorithm, by, why):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    plan = choose(*geometry, kernel=kernel, placement_kernel=pk,
                  mesh=mesh if on_mesh else None)
    assert (plan.backend, plan.algorithm, plan.chosen_by, plan.why) \
        == (backend, algorithm, by, why)
    # "auto" is the hybrid wherever both algorithms are there to pick from
    hybrid = pk == "auto" and why != "scratch_evicted"
    assert getattr(plan.schedule, "_placement_hybrid", False) == hybrid
    assert getattr(plan.release, "_placement_hybrid", False) == hybrid


def _pairs(backend, mesh):
    if backend == "xla":
        return xla_pair("scan")[:2], xla_pair("repair")[:2]
    if backend == "pallas":
        return pallas_pair("scan")[:2], pallas_pair("repair")[:2]
    return fleet_pair(mesh, "scan")[:2], fleet_pair(mesh, "repair")[:2]


@pytest.mark.pallas
@pytest.mark.parametrize("backend", ["xla", "pallas", "sharded"])
def test_the_hybrid_picks_by_static_width(backend, mesh):
    """Scan at B = 16, repair at B = 32, for the schedule and the release
    alike, and both sides of the branch give the same answers."""
    assert REPAIR_MIN_BATCH == 32
    scan, repair = _pairs(backend, mesh)
    calls = []

    def spied(tag, fn):
        def spy(*args):
            calls.append(tag)
            return fn(*args)
        return spy

    sched, release = per_bucket(
        (spied("scan", scan[0]), spied("rel_scan", scan[1])),
        (spied("repair", repair[0]), spied("rel_repair", repair[1])))
    n = 32
    for b, want in ((16, ["scan", "rel_scan"]), (32, ["repair",
                                                      "rel_repair"])):
        rng = np.random.RandomState(b)
        state = _random_state(n, rng)
        if backend == "sharded":
            state = shard_state(state, mesh)
        batch = _random_batch(n, b, rng)
        del calls[:]
        out = sched(state, batch)
        inv = np.clip(np.asarray(out[1]), 0, None).astype(np.int32)
        ok = np.asarray(out[1]) >= 0
        freed = release(out[0], inv, batch.conc_slot, batch.need_mb,
                        batch.max_conc, ok)
        assert calls == want
        for other_sched, other_rel in (scan, repair):
            o = other_sched(state, batch)
            for got, ref in zip(out[1:4], o[1:4]):  # chosen, forced, warm
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref))
            o_freed = other_rel(o[0], inv, batch.conc_slot, batch.need_mb,
                                batch.max_conc, ok)
            for got, ref in zip(freed, o_freed):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref))


@pytest.mark.pallas
def test_growth_swaps_once_and_the_balancer_stays_xla(monkeypatch):
    """A balancer started as Pallas grows 64 -> 1,024 rows past a (small)
    VMEM budget: one `kernel_swap` event, why="vmem_fallback", no compile
    the watchdog did not expect, and XLA still after a restore back to 64
    rows, where Pallas would fit again."""
    from openwhisk_tpu.ops import placement_pallas as pp
    # 128 KiB of budget: 64 x 64 with its scratch takes 61 KiB, 1,024 x 64
    # takes 264 KiB of state alone
    monkeypatch.setenv("OPENWHISK_TPU_VMEM_BYTES", str(256 * 1024))
    pp._reset_vmem_budget_cache()

    def swaps():
        return [e for e in GLOBAL_EVENT_LOG.recent()
                if e.get("kind") == "kernel_swap"]

    async def go():
        provider = MemoryMessagingProvider()
        bal = TpuBalancer(provider, ControllerInstanceId("0"),
                          managed_fraction=1.0, blackbox_fraction=0.0,
                          initial_pad=64, action_slots=64, max_batch=32,
                          kernel="pallas", prewarm=False,
                          batch_window=0.001)
        await bal.start()
        invokers, producer = await _fleet(provider, 2, memory_mb=2048)
        try:
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")

            async def drive(tag):
                for i in range(4):
                    a = make_action(f"{tag}{i % 2}", memory=128)
                    await (await bal.publish(a, make_msg(a, ident, True)))

            assert (bal.kernel_resolved, bal._kernel_chosen_by) \
                == ("pallas", "explicit")
            await drive("small")
            snap = bal.snapshot()
            before = len(swaps())
            bal._grow_padding(1024)
            assert bal.kernel_resolved == "xla" and bal.kernel == "xla"
            assert bal._kernel_chosen_by == "fallback"
            await drive("big")
            bal.restore(snap)
            assert bal._n_pad == 64
            assert bal.kernel_resolved == "xla"
            await drive("back")
            new = swaps()[before:]
            assert [(e["to"], e["why"]) for e in new] \
                == [("xla", "vmem_fallback")]
            assert bal.kernel_profile()["compiles"]["unexpected"] == 0
            assert bal.metrics.gauge_value(
                "loadbalancer_kernel_backend",
                tags={"backend": "xla", "placement": "repair",
                      "chosen_by": "fallback"}) == 1
        finally:
            await bal.close()
            for inv in invokers:
                await inv.stop()

    try:
        asyncio.run(go())
    finally:
        monkeypatch.delenv("OPENWHISK_TPU_VMEM_BYTES")
        pp._reset_vmem_budget_cache()
