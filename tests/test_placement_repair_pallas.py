"""Fused Pallas speculate-and-repair kernel: parity, selector, kernel swaps
(ISSUE 10).

The Pallas repair kernel (`ops.placement_pallas.schedule_batch_repair_pallas`)
claims BIT-EXACT parity with `ops.placement.schedule_batch_repair` — and
therefore with the scan oracle — by construction: the conflict rules are ONE
shared function (`repair_commit_masks`) and only the index primitives differ
(`flat_prims` scatter/sort vs `pairwise_prims` [B,B] masks). The suites here
are the proof the three-backend selector leans on, all in interpret mode on
the CPU twin (the bench parity stage asserts the same on live hardware):

  * parity fuzz reusing test_placement_repair's generators (mixed
    partitions, forced overload, container-open permit flips, cascade
    overflow, unhealthy/invalid rows, OOB slots, the 64k slow row) with
    ROUND-COUNT equality against the XLA repair kernel — same rules, same
    commit sets, same trip count;
  * prims equivalence fuzz (the only place the implementations could
    drift);
  * compile census through the packed entry point (1 compile/signature,
    zero unexpected — speculation in VMEM must not reintroduce churn);
  * the 3x3 placementKernel x kernel selector matrix (repair no longer
    pins XLA), the VMEM-budget fallback regression, and a backend swap's
    compiles read as the swap by the recompile watchdog.
"""
import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from openwhisk_tpu.ops.placement import (  # noqa: E402
    RequestBatch, flat_prims, init_state, make_fused_step_packed,
    pairwise_prims, release_batch_vector, schedule_batch,
    schedule_batch_repair, unpack_step_output)
from tests.test_placement_repair import (  # noqa: E402
    _random_batch, _random_state)

pallas_mark = pytest.mark.pallas


# ---------------------------------------------------------------------------
# prims equivalence: the only backend-specific code in the repair algorithm
# ---------------------------------------------------------------------------

class TestPrimsEquivalence:
    @pytest.mark.parametrize("seed", range(8))
    def test_pairwise_matches_flat(self, seed):
        """Every RepairPrims helper must agree bit-for-bit between the
        scatter/sort (XLA) and pairwise (Mosaic) implementations — a drift
        here is a drift between the production kernels."""
        rng = np.random.RandomState(seed)
        b = int(rng.choice([4, 8, 16, 64]))
        size = int(rng.choice([4, 16, 128]))
        flag = jnp.asarray(rng.rand(b) < 0.4)
        key = jnp.asarray(rng.randint(0, size, b).astype(np.int32))
        vals = jnp.asarray(rng.randint(0, 512, b).astype(np.int32))
        fp = flat_prims(b)
        pp = pairwise_prims(b)

        def col(x):
            return jnp.asarray(np.asarray(x).reshape(b, 1))

        np.testing.assert_array_equal(
            np.asarray(fp.first_index_where(flag, key, size)),
            np.asarray(pp.first_index_where(col(flag), col(key),
                                            size)).reshape(b))
        np.testing.assert_array_equal(
            np.asarray(fp.any_same_key(flag, key, size)),
            np.asarray(pp.any_same_key(col(flag), col(key),
                                       size)).reshape(b))
        np.testing.assert_array_equal(
            np.asarray(fp.segment_exclusive_sum(vals, key)),
            np.asarray(pp.segment_exclusive_sum(col(vals),
                                                col(key))).reshape(b))
        np.testing.assert_array_equal(
            np.asarray(fp.exclusive_cumsum(vals)),
            np.asarray(pp.exclusive_cumsum(col(vals))).reshape(b))
        np.testing.assert_array_equal(
            np.asarray(fp.exclusive_cummax(vals)),
            np.asarray(pp.exclusive_cummax(col(vals))).reshape(b))
        np.testing.assert_array_equal(
            np.asarray(fp.min_index_where(flag)).reshape(()),
            np.asarray(pp.min_index_where(col(flag))).reshape(()))


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------

def _pallas_repair(state, batch):
    from openwhisk_tpu.ops.placement_pallas import (
        schedule_batch_repair_pallas, to_transposed)
    ts, *out = schedule_batch_repair_pallas(
        to_transposed(state), batch, interpret=True)
    from openwhisk_tpu.ops.placement import PlacementState
    return (PlacementState(ts.free_mb, ts.conc_free.T, ts.health), *out)


def _assert_repair_parity(state, batch, check_rounds=True):
    s_state, s_chosen, s_forced, s_warm = schedule_batch(state, batch)
    x_rounds = schedule_batch_repair(state, batch)[4]
    p_state, p_chosen, p_forced, p_warm, p_rounds = _pallas_repair(state,
                                                                   batch)
    np.testing.assert_array_equal(np.asarray(s_chosen), np.asarray(p_chosen))
    np.testing.assert_array_equal(np.asarray(s_forced), np.asarray(p_forced))
    np.testing.assert_array_equal(np.asarray(s_warm), np.asarray(p_warm))
    np.testing.assert_array_equal(np.asarray(s_state.free_mb),
                                  np.asarray(p_state.free_mb))
    np.testing.assert_array_equal(np.asarray(s_state.conc_free),
                                  np.asarray(p_state.conc_free))
    if check_rounds:
        # shared rules + shared commit sets => the residue loops take the
        # SAME number of rounds (the drift canary the rounds family needs)
        assert int(p_rounds) == int(x_rounds)
    return p_state, int(p_rounds)


@pallas_mark
class TestPallasRepairParity:
    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_parity_with_scan_oracle(self, seed):
        """Randomized fleets/batches: placements, forced flags, books AND
        round counts identical, across chained steps (the second step runs
        on books the first step dirtied)."""
        rng = np.random.RandomState(seed)
        n = int(rng.choice([4, 8, 16, 64]))
        b = int(rng.choice([8, 32, 64]))
        mem = int(rng.choice([512, 1024, 4096]))
        state = _random_state(n, rng, mem=mem)
        for _ in range(2):
            batch = _random_batch(n, b, rng)
            state, rounds = _assert_repair_parity(state, batch)
            assert rounds >= 1

    def test_overload_forced_parity(self):
        """Memory pressure forces random-rotation placement (over-commit):
        the in-kernel residue loop must serialize the forced cascade
        identically."""
        rng = np.random.RandomState(42)
        n, b = 4, 64
        state = init_state(n, [256] * n, action_slots=8)
        for _ in range(2):
            batch = _random_batch(n, b, rng, mem_choices=(256, 512))
            state, _ = _assert_repair_parity(state, batch)
        assert (np.asarray(state.free_mb) < 256).all()  # pressure was real

    def test_container_open_flips_later_choice(self):
        """A max_conc>1 placement OPENS permits on its conc column — the
        hard-conflict class the shared rules must serialize in-kernel."""
        n, b = 4, 16
        state = init_state(n, [256] * n, action_slots=4)
        mk = lambda x: jnp.full((b,), x, jnp.int32)  # noqa: E731
        batch = RequestBatch(mk(0), mk(n), jnp.arange(b, dtype=jnp.int32) % n,
                             mk(1), mk(256), mk(2), mk(4),
                             mk(0), jnp.ones((b,), bool))
        _assert_repair_parity(state, batch)

    def test_same_action_burst_memory_cascade_overflow(self):
        """A one-action burst on a tiny partition: the memory cascade
        commits the run without serializing, and must still match the scan
        exactly when the invoker overflows mid-burst."""
        n, b = 2, 32
        state = init_state(n, [1024] * n, action_slots=4)
        mk = lambda x: jnp.full((b,), x, jnp.int32)  # noqa: E731
        batch = RequestBatch(mk(0), mk(n), mk(0), mk(1), mk(128), mk(1),
                             mk(1), jnp.arange(b, dtype=jnp.int32) % n,
                             jnp.ones((b,), bool))
        _assert_repair_parity(state, batch)

    def test_no_usable_invokers_settle_in_one_round(self):
        rng = np.random.RandomState(7)
        n, b = 8, 16
        state = init_state(n, [1024] * n, action_slots=8)
        state = state._replace(health=jnp.zeros((n,), bool))
        batch = _random_batch(n, b, rng)
        p_state, p_chosen, p_forced, _warm, p_rounds = _pallas_repair(
            state, batch)
        assert (np.asarray(p_chosen) == -1).all()
        assert not np.asarray(p_forced).any()
        assert int(p_rounds) == 1

    def test_out_of_range_slots_match_xla_scatter_semantics(self):
        """OOB slot ids: reads clamp, writes AND slot-keyed conflict marks
        drop — the slot_ok plumbing through the shared rules."""
        n, a = 32, 4
        state = init_state(n, [512] * n, action_slots=a)

        def mk(slots, max_concs):
            b = len(slots)
            z = jnp.zeros((b,), jnp.int32)
            return RequestBatch(
                offset=z, size=jnp.full((b,), n, jnp.int32), home=z,
                step_inv=jnp.ones((b,), jnp.int32),
                need_mb=jnp.full((b,), 128, jnp.int32),
                conc_slot=jnp.asarray(slots, jnp.int32),
                max_conc=jnp.asarray(max_concs, jnp.int32),
                rand=z, valid=jnp.ones((b,), bool))

        # rounds intentionally unchecked: the XLA scatters DROP an OOB
        # writer's conflict marks while the pallas path folds slot_ok into
        # the same drop — outcome parity is the contract here
        _assert_repair_parity(state, mk([9, 3, 3, 9], [4, 4, 4, 1]),
                              check_rounds=False)

    @pytest.mark.slow
    def test_parity_at_64k_fleet_memory_dominant(self):
        """The fleet >> batch production shape at the 64k north-star size,
        memory-dominant traffic (the bulk): interpret mode is slow, so the
        batch stays modest — the [B, N] vector math is what's exercised."""
        rng = np.random.RandomState(3)
        n, b = 65536, 128
        state = _random_state(n, rng, mem=2048, unhealthy_p=0.05)
        batch = _random_batch(n, b, rng, maxc_choices=(1,))
        _, rounds = _assert_repair_parity(state, batch)
        assert rounds <= 4


# ---------------------------------------------------------------------------
# packed entry point: trailing rounds + compile census
# ---------------------------------------------------------------------------

def _packed_buf(rng, n, r, h, b, slots=16):
    batch = _random_batch(n, b, rng, slots=slots)
    rel = np.zeros((5, r), np.int32)
    rel[3] = 1
    health = np.zeros((3, h), np.int32)
    req = np.stack([np.asarray(x, np.int32) for x in
                    (batch.offset, batch.size, batch.home, batch.step_inv,
                     batch.need_mb, batch.conc_slot, batch.max_conc,
                     batch.rand, batch.valid)])
    return np.concatenate([rel.ravel(), health.ravel(), req.ravel()])


def _pallas_repair_sched():
    from openwhisk_tpu.controller.loadbalancer.kernel_choice import \
        pallas_pair
    return pallas_pair("repair")


@pallas_mark
class TestPallasPackedPath:
    def test_packed_step_trailing_rounds_element(self):
        """The packed output keeps the step's one layout (B words, the
        repair-round count, the post-step books), so the flight recorder
        and loadbalancer_repair_rounds family work unchanged on the pallas
        backend."""
        rng = np.random.RandomState(0)
        n, b = 32, 16
        state = _random_state(n, rng)
        buf = _packed_buf(rng, n, 8, 4, b)
        sched, release, resolved = _pallas_repair_sched()
        assert resolved == "repair"
        fn = make_fused_step_packed(release, sched)
        new, out = fn(state, jnp.asarray(buf), 8, 4, b)
        assert out.shape == (b + 1 + n,)
        step = unpack_step_output(np.asarray(out), b)
        assert step.chosen.shape == (b,)
        assert step.rounds >= 1
        np.testing.assert_array_equal(step.books, np.asarray(new.free_mb))
        # and the XLA repair pair derives the SAME decisions and rounds
        fn_x = make_fused_step_packed(release_batch_vector,
                                      schedule_batch_repair)
        state_x = _random_state(n, np.random.RandomState(0))
        _, out_x = fn_x(state_x, jnp.asarray(buf), 8, 4, b)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_x))

    def test_pallas_repair_compiles_once_per_bucket_signature(self):
        """PR-3 watchdog contract on the pallas backend: one compile per
        (R, H, B) bucket signature, zero unexpected — the in-kernel
        residue loop must not reintroduce shape churn."""
        from openwhisk_tpu.ops.profiler import (KernelProfiler,
                                                ProfilingConfig, pow2_statics)
        prof = KernelProfiler(ProfilingConfig(enabled=True))
        sched, release, _ = _pallas_repair_sched()
        fn = prof.wrap("fused_step", make_fused_step_packed(release, sched),
                       expected=pow2_statics)
        rng = np.random.RandomState(3)
        n = 32
        state = _random_state(n, rng)
        sigs = [(8, 4, 8), (8, 4, 16)]
        for repeat in range(3):
            for (r, h, b) in sigs:
                buf = jnp.asarray(_packed_buf(
                    np.random.RandomState(10 + repeat), n, r, h, b))
                state, _ = fn(state, buf, r, h, b)
        census = prof.cache_census()["fused_step"]
        assert census["compiles"] == len(sigs)
        assert census["signatures"] == len(sigs)
        assert census["calls"] == 3 * len(sigs)
        assert prof.compiles_unexpected == 0


# ---------------------------------------------------------------------------
# balancer selector, VMEM fallback, kernel swaps
# ---------------------------------------------------------------------------

from openwhisk_tpu.controller.loadbalancer import TpuBalancer  # noqa: E402
from openwhisk_tpu.core.entity import (ControllerInstanceId,  # noqa: E402
                                       Identity)
from openwhisk_tpu.messaging import MemoryMessagingProvider  # noqa: E402
from tests.test_balancers import (_fleet, _ping_all, make_action,  # noqa: E402
                                  make_msg)


def _mk_balancer(provider, **kw):
    kw.setdefault("managed_fraction", 1.0)
    kw.setdefault("blackbox_fraction", 0.0)
    kw.setdefault("initial_pad", 16)
    kw.setdefault("action_slots", 64)
    kw.setdefault("max_batch", 64)
    return TpuBalancer(provider, ControllerInstanceId("0"), **kw)


@pallas_mark
class TestSelectorMatrix:
    @pytest.mark.parametrize("kernel,pk,want_backend,want_resolved", [
        ("xla", "scan", "xla", "scan"),
        ("xla", "repair", "xla", "repair"),
        ("xla", "auto", "xla", "repair"),
        ("pallas", "scan", "pallas", "scan"),
        ("pallas", "repair", "pallas", "repair"),
        ("pallas", "auto", "pallas", "repair"),
        # the CPU twin's static auto resolver: xla (pallas = interpret)
        ("auto", "scan", "xla", "scan"),
        ("auto", "repair", "xla", "repair"),
        ("auto", "auto", "xla", "repair"),
    ])
    def test_env_knob_matrix(self, monkeypatch, kernel, pk, want_backend,
                             want_resolved):
        """The full 3x3 placementKernel x kernel matrix through the ENV
        knobs — in particular placementKernel=repair no longer pins the
        XLA path (the fused pallas repair kernel exists now)."""
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_placementKernel", pk)
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_kernel", kernel)
        bal = _mk_balancer(MemoryMessagingProvider())
        assert bal.kernel == kernel  # the backend knob reads the env too
        assert bal.kernel_resolved == want_backend
        assert bal.placement_kernel_resolved == want_resolved
        if want_backend == "pallas":
            kind = getattr(bal._sched_fn, "_pallas_kind", None)
            assert kind == ("repair" if pk == "repair" else
                            "auto" if pk == "auto" else "scan")

    def test_pallas_repair_places_end_to_end(self):
        """publish() -> device step -> readback through the fused pallas
        repair kernel on the CPU twin (interpret), books and slots
        balanced, zero unexpected recompiles."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = _mk_balancer(provider, kernel="pallas",
                               placement_kernel="repair",
                               batch_window=0.001)
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=2048)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            for i in range(6):
                a = make_action(f"pr{i % 2}", memory=128)
                await (await bal.publish(a, make_msg(a, ident, True)))
            prof = bal.kernel_profile()
            assert prof["kernel"] == "pallas"
            assert prof["placement_kernel"] == "repair"
            assert prof["compiles"]["unexpected"] == 0
            await bal.close()
            for inv in invokers:
                await inv.stop()

        asyncio.run(go())

    def test_explicit_pallas_vmem_fallback_logs_and_runs_xla(self,
                                                             monkeypatch):
        """Satellite regression: explicit kernel=pallas that fails the
        (device-read) VMEM fit keeps the fall-back-and-log behavior — the
        balancer runs XLA and says why."""
        from openwhisk_tpu.ops import placement_pallas as pp
        monkeypatch.setenv("OPENWHISK_TPU_VMEM_BYTES", str(4 * 1024))
        pp._reset_vmem_budget_cache()
        try:
            logs = []

            class Log:
                def warn(self, *a, **k):
                    logs.append(" ".join(str(x) for x in a))

                def info(self, *a, **k):
                    pass

                def error(self, *a, **k):
                    pass

            bal = _mk_balancer(MemoryMessagingProvider(), kernel="pallas",
                               logger=Log())
            assert bal.kernel_resolved == "xla"
            assert bal.kernel == "xla"  # pinned off for later rebuilds
            assert any("does not fit" in line or "unavailable" in line
                       for line in logs)
        finally:
            monkeypatch.delenv("OPENWHISK_TPU_VMEM_BYTES")
            pp._reset_vmem_budget_cache()

    def test_vmem_budget_env_override_and_repair_scratch(self, monkeypatch):
        from openwhisk_tpu.ops import placement_pallas as pp
        monkeypatch.setenv("OPENWHISK_TPU_VMEM_BYTES",
                           str(64 * 1024 * 1024))
        pp._reset_vmem_budget_cache()
        try:
            assert pp.vmem_budget_bytes() == 32 * 1024 * 1024
            assert pp.fits_vmem(1024, 256)
            # the repair kernel budgets [B, N] residue scratch on top of
            # the resident state: same geometry, bigger footprint
            assert pp.fits_vmem_repair(1024, 256, 256)
            assert not pp.fits_vmem_repair(16384, 256, 1024)
        finally:
            monkeypatch.delenv("OPENWHISK_TPU_VMEM_BYTES")
            pp._reset_vmem_budget_cache()


@pallas_mark
class TestKernelSwap:
    def test_profiler_classifies_swap_compiles_as_expected(self):
        """Satellite: re-wrapping an entry point (a backend swap) opens a
        rebuild window — compiles of the fresh cache classify as
        kernel_swap, never shape_churn, even past first_call."""
        from openwhisk_tpu.ops.profiler import KernelProfiler, \
            ProfilingConfig

        prof = KernelProfiler(ProfilingConfig(enabled=True))
        calls = {"a": 0, "b": 0}

        def fn_a(x):
            calls["a"] += 1
            return x

        def fn_b(x):
            calls["b"] += 1
            return x

        wrapped = prof.wrap("fused_step", fn_a)
        wrapped(np.zeros((4,)))
        assert prof.compiles_unexpected == 0
        # the swap: same name, new callable — two distinct signatures
        # compile afterwards, NEITHER may read as churn
        wrapped = prof.wrap("fused_step", fn_b)
        wrapped(np.zeros((4,)))
        wrapped(np.zeros((7,)))  # not a pow2 bucket, no predicate set
        assert prof.compiles_unexpected == 0
        reasons = [e["reason"] for e in prof.compile_log(10)
                   if e["entry"] == "fused_step"]
        assert "kernel_swap" in reasons
