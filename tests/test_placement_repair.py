"""Speculate-and-repair placement kernel: bit-exactness and host-path
regressions (ISSUE 5).

The repair kernel (`ops.placement.schedule_batch_repair`) and the
vectorized release fold (`release_batch_vector`) claim BIT-EXACT parity
with the reference lax.scan pair — the fuzz suites here are the proof the
balancer's `placement_kernel="auto"` default leans on: randomized fleets
(mixed partitions, overload-forced placement, unhealthy rows, shared
concurrency slots, invalid rows), placements AND books compared exactly,
including the throttled/admit fused variant. Host-path regressions cover
the buffer-donation materialize boundaries (snapshot mid-flight under the
pipelined dispatch), occupancy served from cached books, the scan+depth-1
legacy no-op path, and the compile census (one compile per bucketed
(R, H, B) signature — speculation must not reintroduce shape churn).
"""
import asyncio
import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from openwhisk_tpu.ops.placement import (  # noqa: E402
    PlacementState, RequestBatch, init_state, make_fused_admit_step_packed,
    make_fused_step_packed, release_batch, release_batch_vector,
    schedule_batch, schedule_batch_repair, unpack_step_output)
from openwhisk_tpu.ops.throttle import init_buckets  # noqa: E402


def _random_batch(n, b, rng, mem_choices=(128, 256, 512), slots=16,
                  maxc_choices=(1, 1, 4), valid_p=0.95):
    """A randomized RequestBatch over mixed sub-partitions of an n-invoker
    fleet: random offset/size windows (the managed/blackbox split and
    cluster slicing), coprime probe steps, shared conc slots, container
    actions, and some invalid (padding) rows."""
    off = rng.randint(0, max(1, n // 2), b).astype(np.int32)
    size = np.maximum(1, rng.randint(1, n + 1, b) - off).astype(np.int32)
    size = np.minimum(size, n - off).astype(np.int32)
    home = (rng.randint(0, 1 << 16, b) % size).astype(np.int32)
    step_inv = np.zeros(b, np.int32)
    for i in range(b):
        s = int(size[i])
        st = rng.randint(1, s + 1)
        while math.gcd(int(st), s) != 1:
            st = rng.randint(1, s + 1)
        step_inv[i] = pow(int(st), -1, s) if s > 1 else 0
    need = rng.choice(mem_choices, b).astype(np.int32)
    slot = rng.randint(0, slots, b).astype(np.int32)
    maxc = rng.choice(maxc_choices, b).astype(np.int32)
    rand = (rng.randint(0, 1 << 20, b).astype(np.int32)
            % np.maximum(size, 1))
    valid = rng.rand(b) < valid_p
    return RequestBatch(*[jnp.asarray(x) for x in
                          (off, size, home, step_inv, need, slot, maxc,
                           rand, valid)])


def _random_state(n, rng, mem=1024, slots=16, unhealthy_p=0.2,
                  conc_p=0.3):
    st = init_state(n, [mem] * n, action_slots=slots)
    health = ~(rng.rand(n) < unhealthy_p)
    if not health.any():
        health[rng.randint(0, n)] = True
    conc = np.where(rng.rand(n, slots) < conc_p,
                    rng.randint(1, 4, (n, slots)), 0).astype(np.int32)
    return st._replace(health=jnp.asarray(health),
                       conc_free=jnp.asarray(conc))


def _assert_same_outcome(scan_out, repair_out):
    s_state, s_chosen, s_forced, s_warm = scan_out
    r_state, r_chosen, r_forced, r_warm = repair_out[:4]
    np.testing.assert_array_equal(np.asarray(s_chosen), np.asarray(r_chosen))
    np.testing.assert_array_equal(np.asarray(s_forced), np.asarray(r_forced))
    np.testing.assert_array_equal(np.asarray(s_warm), np.asarray(r_warm))
    np.testing.assert_array_equal(np.asarray(s_state.free_mb),
                                  np.asarray(r_state.free_mb))
    np.testing.assert_array_equal(np.asarray(s_state.conc_free),
                                  np.asarray(r_state.conc_free))


class TestRepairKernelParity:
    @pytest.mark.parametrize("seed", range(8))
    def test_fuzz_parity_with_scan_oracle(self, seed):
        """Randomized fleets/batches: placements, forced flags and books
        bit-identical to the scan oracle, across chained steps (the second
        step runs on books the first step dirtied)."""
        rng = np.random.RandomState(seed)
        n = int(rng.choice([4, 8, 16, 64, 256]))
        b = int(rng.choice([8, 32, 64]))
        mem = int(rng.choice([512, 1024, 4096]))
        s_state = r_state = _random_state(n, rng, mem=mem)
        for step in range(3):
            batch = _random_batch(n, b, rng)
            s_out = schedule_batch(s_state, batch)
            r_out = schedule_batch_repair(r_state, batch)
            _assert_same_outcome(s_out, r_out)
            s_state, r_state = s_out[0], r_out[0]
            assert int(r_out[4]) >= 1  # at least one commit round ran

    def test_overload_forced_parity(self):
        """Memory pressure forces random-rotation placement (over-commit):
        the repair loop must serialize the forced cascade identically."""
        rng = np.random.RandomState(42)
        n, b = 4, 64
        state = init_state(n, [256] * n, action_slots=8)
        for _ in range(3):
            batch = _random_batch(n, b, rng, mem_choices=(256, 512))
            s_out = schedule_batch(state, batch)
            r_out = schedule_batch_repair(state, batch)
            _assert_same_outcome(s_out, r_out)
            state = s_out[0]
        assert np.asarray(s_out[2]).any()  # the scenario actually forced

    def test_no_usable_invokers_all_unplaced(self):
        n, b = 8, 16
        rng = np.random.RandomState(7)
        state = init_state(n, [1024] * n, action_slots=8)
        state = state._replace(health=jnp.zeros((n,), bool))
        batch = _random_batch(n, b, rng)
        r_state, chosen, forced, _warm, rounds = schedule_batch_repair(
            state, batch)
        assert (np.asarray(chosen) == -1).all()
        assert not np.asarray(forced).any()
        np.testing.assert_array_equal(np.asarray(r_state.free_mb),
                                      np.asarray(state.free_mb))
        # unplaceable rows are outcome-invariant: one round settles them
        assert int(rounds) == 1

    def test_same_action_burst_memory_cascade(self):
        """A burst of one simple action on a tiny partition is the memory-
        cascade fast path: prefix sums commit the whole run without
        serializing — and must still match the scan exactly when the
        invoker overflows mid-burst."""
        n, b = 2, 32
        state = init_state(n, [1024] * n, action_slots=4)
        mk = lambda x: jnp.full((b,), x, jnp.int32)  # noqa: E731
        batch = RequestBatch(mk(0), mk(n), mk(0), mk(1), mk(128), mk(1),
                             mk(1), jnp.arange(b, dtype=jnp.int32) % n,
                             jnp.ones((b,), bool))
        s_out = schedule_batch(state, batch)
        r_out = schedule_batch_repair(state, batch)
        _assert_same_outcome(s_out, r_out)

    def test_container_open_flips_later_choice(self):
        """A max_conc>1 placement OPENS permits on its conc column, which
        can hand a better-ranked invoker to a later request in the same
        batch — the hard-conflict rule the repair loop must serialize."""
        n, b = 4, 16
        state = init_state(n, [256] * n, action_slots=4)
        mk = lambda x: jnp.full((b,), x, jnp.int32)  # noqa: E731
        batch = RequestBatch(mk(0), mk(n), jnp.arange(b, dtype=jnp.int32) % n,
                             mk(1), mk(256), mk(2), mk(4),
                             mk(0), jnp.ones((b,), bool))
        s_out = schedule_batch(state, batch)
        r_out = schedule_batch_repair(state, batch)
        _assert_same_outcome(s_out, r_out)

    @pytest.mark.slow
    def test_parity_at_64k_fleet(self):
        rng = np.random.RandomState(3)
        n, b = 65536, 256
        state = _random_state(n, rng, mem=2048, unhealthy_p=0.05)
        batch = _random_batch(n, b, rng)
        s_out = schedule_batch(state, batch)
        r_out = schedule_batch_repair(state, batch)
        _assert_same_outcome(s_out, r_out)
        # the mixed batch crams a third of its rows (max_conc>1) into 16
        # shared conc slots: conc-column writers are hard conflicts BY
        # DESIGN (they never commute with order-inverted column reads), so
        # this shape serializes partially — measured 23 rounds — and only
        # the "well below B" contract applies
        assert int(r_out[4]) < b // 4

        # the fleet >> batch claim proper: memory-dominant traffic (the
        # production bulk; max_conc <= 1) sees almost no conflicts
        rng2 = np.random.RandomState(3)
        state2 = _random_state(n, rng2, mem=2048, unhealthy_p=0.05)
        batch2 = _random_batch(n, b, rng2, maxc_choices=(1,))
        s2 = schedule_batch(state2, batch2)
        r2 = schedule_batch_repair(state2, batch2)
        _assert_same_outcome(s2, r2)
        assert int(r2[4]) <= 4


class TestReleaseVectorParity:
    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_parity_with_scan_release(self, seed):
        rng = np.random.RandomState(seed)
        n = int(rng.choice([4, 16, 64]))
        r = int(rng.choice([8, 32, 64]))
        st = _random_state(n, rng, conc_p=0.5)
        inv = jnp.asarray(rng.randint(0, n, r).astype(np.int32))
        slot = jnp.asarray(rng.randint(0, 16, r).astype(np.int32))
        need = jnp.asarray(rng.choice([128, 256], r).astype(np.int32))
        maxc = jnp.asarray(rng.choice([1, 4, 4, 6], r).astype(np.int32))
        valid = jnp.asarray(rng.rand(r) < 0.9)
        a = release_batch(st, inv, slot, need, maxc, valid)
        b = release_batch_vector(st, inv, slot, need, maxc, valid)
        np.testing.assert_array_equal(np.asarray(a.free_mb),
                                      np.asarray(b.free_mb))
        np.testing.assert_array_equal(np.asarray(a.conc_free),
                                      np.asarray(b.conc_free))

    def test_heterogeneous_group_replays_every_row(self):
        """Slot-conflation regression: when two actions share a hashed slot
        on one invoker, the WHOLE group replays sequentially — the
        leader-matching rows must not be dropped with the bulk apply."""
        n = 2
        st = init_state(n, [4096] * n, action_slots=4)
        st = st._replace(conc_free=st.conc_free.at[0, 1].set(2))
        # rows 0 and 2 match the leader (need=256, maxc=3); row 1 conflates
        inv = jnp.asarray([0, 0, 0], jnp.int32)
        slot = jnp.asarray([1, 1, 1], jnp.int32)
        need = jnp.asarray([256, 512, 256], jnp.int32)
        maxc = jnp.asarray([3, 4, 3], jnp.int32)
        valid = jnp.ones((3,), bool)
        a = release_batch(st, inv, slot, need, maxc, valid)
        b = release_batch_vector(st, inv, slot, need, maxc, valid)
        np.testing.assert_array_equal(np.asarray(a.free_mb),
                                      np.asarray(b.free_mb))
        np.testing.assert_array_equal(np.asarray(a.conc_free),
                                      np.asarray(b.conc_free))


def _packed_buf(rng, n, r, h, b, rows=9, slots=16):
    batch = _random_batch(n, b, rng, slots=slots)
    rel = np.zeros((5, r), np.int32)
    rel[3] = 1
    health = np.zeros((3, h), np.int32)
    req = np.stack([np.asarray(x, np.int32) for x in
                    (batch.offset, batch.size, batch.home, batch.step_inv,
                     batch.need_mb, batch.conc_slot, batch.max_conc,
                     batch.rand, batch.valid)])
    if rows == 10:
        req = np.concatenate(
            [req, rng.randint(0, 4, (1, b)).astype(np.int32)])
    return np.concatenate([rel.ravel(), health.ravel(), req.ravel()])


class TestFusedPackedParity:
    def test_packed_step_trailing_rounds_element(self):
        rng = np.random.RandomState(0)
        n, b = 32, 16
        state = _random_state(n, rng)
        buf = _packed_buf(rng, n, 8, 4, b)
        fn = make_fused_step_packed(release_batch_vector,
                                    schedule_batch_repair)
        new, out = fn(state, jnp.asarray(buf), 8, 4, b)
        assert out.shape == (b + 1 + n,)
        step = unpack_step_output(np.asarray(out), b)
        assert step.chosen.shape == step.warm.shape == (b,)
        assert step.rounds >= 1
        np.testing.assert_array_equal(step.books, np.asarray(new.free_mb))
        # the scan pair reports rounds == 0 through the same contract
        new_s, out_s = make_fused_step_packed()(state, jnp.asarray(buf),
                                                8, 4, b)
        s = unpack_step_output(np.asarray(out_s), b)
        assert s.rounds == 0
        np.testing.assert_array_equal(step.chosen, s.chosen)
        np.testing.assert_array_equal(s.books, np.asarray(new_s.free_mb))

    def test_admit_variant_parity_scan_vs_repair(self):
        """The throttled/admit fused step: same packed buffer + bucket
        carry through both kernel pairs -> identical decisions, throttle
        flags, books AND bucket state."""
        rng = np.random.RandomState(1)
        n, r, h, b = 32, 8, 4, 16
        buf = jnp.asarray(_packed_buf(rng, n, r, h, b, rows=10))
        outs = {}
        for name, (rel_fn, sched_fn) in {
                "scan": (release_batch, schedule_batch),
                "repair": (release_batch_vector, schedule_batch_repair)}.items():
            state = _random_state(n, np.random.RandomState(99))
            buckets = init_buckets(64, 6)
            fn = make_fused_admit_step_packed(rel_fn, sched_fn)
            (state, buckets), out = fn((state, buckets), buf,
                                       np.float32(1.0), r, h, b)
            step = unpack_step_output(np.asarray(out), b)
            np.testing.assert_array_equal(step.books,
                                          np.asarray(state.free_mb))
            outs[name] = (step.chosen, step.forced, step.throttled,
                          step.warm, np.asarray(state.free_mb),
                          np.asarray(state.conc_free),
                          np.asarray(buckets.tokens))
        for a, bb in zip(outs["scan"], outs["repair"]):
            np.testing.assert_array_equal(a, bb)

    def test_donated_packed_step_invalidates_input_state(self):
        """donate=True consumes the input state's buffers: correctness
        first (same outputs as undonated), and the caller contract — the
        pre-call reference must not be reused (the balancer's materialize
        boundaries exist because of this)."""
        rng = np.random.RandomState(2)
        n, b = 16, 8
        state = _random_state(n, rng)
        free0 = np.asarray(state.free_mb).copy()
        buf = jnp.asarray(_packed_buf(rng, n, 8, 4, b))
        fn = make_fused_step_packed(release_batch_vector,
                                    schedule_batch_repair, donate=True)
        ref = make_fused_step_packed(release_batch_vector,
                                     schedule_batch_repair)
        state2 = PlacementState(jnp.asarray(free0),
                                jnp.asarray(np.asarray(state.conc_free)),
                                jnp.asarray(np.asarray(state.health)))
        _, out_ref = ref(state2, buf, 8, 4, b)
        new_state, out = fn(state, buf, 8, 4, b)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
        # the output is always safe to read; the donated input may be
        # gone (backends without donation support keep it alive — both
        # are within contract, so only assert the output)
        assert np.asarray(new_state.free_mb).shape == (n,)


class TestCompileCensus:
    def test_repair_kernel_compiles_once_per_bucket_signature(self):
        """PR-3 watchdog contract: the repair kernel compiles exactly once
        per (R, H, B) bucket signature and NEVER as unexpected shape churn
        — speculation must not reintroduce per-batch recompiles."""
        from openwhisk_tpu.ops.profiler import (KernelProfiler,
                                                ProfilingConfig, pow2_statics)
        prof = KernelProfiler(ProfilingConfig(enabled=True))
        fn = prof.wrap("fused_step",
                       make_fused_step_packed(release_batch_vector,
                                              schedule_batch_repair),
                       expected=pow2_statics)
        rng = np.random.RandomState(3)
        n = 32
        state = _random_state(n, rng)
        sigs = [(8, 4, 8), (8, 4, 16), (16, 4, 16)]
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
# balancer host path: donation boundaries, occupancy cache, legacy no-op
# ---------------------------------------------------------------------------

from openwhisk_tpu.controller.loadbalancer import TpuBalancer  # noqa: E402
from openwhisk_tpu.core.entity import ControllerInstanceId, Identity  # noqa: E402
from openwhisk_tpu.messaging import MemoryMessagingProvider  # noqa: E402
from tests.test_balancers import (_fleet, _ping_all, make_action,  # noqa: E402
                                  make_msg)


def _mk_balancer(provider, **kw):
    kw.setdefault("managed_fraction", 1.0)
    kw.setdefault("blackbox_fraction", 0.0)
    return TpuBalancer(provider, ControllerInstanceId("0"), **kw)


class TestBalancerHostPath:
    def test_placement_kernel_env_knob(self, monkeypatch):
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_placementKernel",
                           "scan")
        bal = _mk_balancer(MemoryMessagingProvider())
        assert bal.placement_kernel == "scan"
        assert bal.placement_kernel_resolved == "scan"
        assert bal._sched_fn is schedule_batch
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_placementKernel",
                           "repair")
        bal2 = _mk_balancer(MemoryMessagingProvider())
        assert bal2.placement_kernel_resolved == "repair"
        assert bal2._sched_fn is schedule_batch_repair
        # constructor overrides env
        bal3 = _mk_balancer(MemoryMessagingProvider(),
                            placement_kernel="scan")
        assert bal3.placement_kernel_resolved == "scan"
        with pytest.raises(ValueError):
            _mk_balancer(MemoryMessagingProvider(), placement_kernel="bogus")

    @pytest.mark.skipif(jax.default_backend() != "cpu",
                        reason="exercises the CPU-backend donation gate")
    def test_donation_auto_gates_off_on_cpu_backend(self):
        """XLA:CPU cannot alias donated buffers and runs donated programs
        synchronously at dispatch — the balancer must gate donation off
        there (it is on for real devices), while an explicit constructor
        True still pins it for boundary tests."""
        bal = _mk_balancer(MemoryMessagingProvider())
        assert bal._donate is False
        # materialize is then a pass-through of the live reference
        assert bal._materialize_state() is bal.state
        pinned = _mk_balancer(MemoryMessagingProvider(), donate_state=True)
        assert pinned._donate is True

    def test_prewarm_knob_off_disables_compile_ahead(self, monkeypatch):
        monkeypatch.setenv("CONFIG_whisk_loadBalancer_prewarm", "false")
        bal = _mk_balancer(MemoryMessagingProvider())
        assert bal.prewarm is False
        bal._prewarm_buckets(8, 8, 8)
        assert bal._warm_sigs == set() and bal._warm_queue == []
        # default (env cleared): compile-ahead is on
        monkeypatch.delenv("CONFIG_whisk_loadBalancer_prewarm")
        warm = _mk_balancer(MemoryMessagingProvider())
        assert warm.prewarm is True

    def test_snapshot_mid_flight_under_pipeline_and_donation(self):
        """The satellite regression: snapshot_parts() -> worker-thread
        snapshot() while donated pipelined steps are consuming state
        buffers. Without the materialize boundary the worker reads an
        invalidated buffer and the snapshot dies."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = _mk_balancer(provider, batch_window=0.001, max_batch=8,
                               pipeline_depth=2, donate_state=True)
            assert bal._donate
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=4096,
                                              delay=0.05)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("snapmid", memory=128)

            async def one():
                p = await bal.publish(action, make_msg(action, ident, True))
                await p

            snaps = []

            async def snapshotter():
                # the BalancerSnapshotter pattern: parts on the loop, the
                # heavy transfer on a worker thread, racing live dispatches
                for _ in range(6):
                    parts = bal.snapshot_parts()
                    snaps.append(await asyncio.to_thread(bal.snapshot,
                                                         parts))
                    await asyncio.sleep(0.002)

            await asyncio.gather(snapshotter(),
                                 *[one() for _ in range(48)])
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return snaps

        snaps = asyncio.run(go())
        assert len(snaps) == 6
        for snap in snaps:
            assert len(snap["free_mb"]) == snap["n_pad"]
            # restore round-trips onto a fresh balancer
            fresh = _mk_balancer(MemoryMessagingProvider())
            fresh.restore(snap)
            assert np.asarray(fresh.state.free_mb).tolist() == snap["free_mb"]

    def test_failed_donated_admit_dispatch_reinits_bucket_carry(self):
        """Review regression: the admit step donates (state, buckets) as
        ONE carry, so a dispatch that fails after consuming the donation
        deletes the token-bucket arrays too. Recovery must re-init the
        carry (the _build_packed_fns guard keeps any non-None bucket
        state, deleted or not) or every later dispatch dies on 'Array has
        been deleted' — a permanent placement outage."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = _mk_balancer(provider, donate_state=True,
                               rate_limit_per_minute=600,
                               batch_window=0.001)
            assert bal._donate
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=2048)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("bucketheal", memory=128)
            real = bal._packed_fn
            armed = {"on": True}

            def consume_then_raise(carry, buf, now, r, h, b):
                out = real(carry, buf, now, r, h, b)
                if armed["on"]:
                    armed["on"] = False
                    raise RuntimeError("injected post-consumption failure")
                return out

            bal._packed_fn = consume_then_raise
            # publish awaits the placement future internally, so the
            # injected dispatch failure surfaces right here
            with pytest.raises(Exception, match="dispatch failed"):
                await bal.publish(action, make_msg(action, ident, True))
            # the consumed carry was re-initialized, not kept deleted
            assert bal._bucket_state is None or \
                not bal._bucket_state.tokens.is_deleted()
            # and the next dispatch places normally
            p2 = await bal.publish(action, make_msg(action, ident, True))
            await p2
            assert not bal._bucket_state.tokens.is_deleted()
            await bal.close()
            for inv in invokers:
                await inv.stop()

        asyncio.run(go())

    def test_failed_donated_idle_fold_rebuilds_state(self):
        """Review regression: the IDLE release fold (no pending requests)
        donates the state too — a failure past consumption must rebuild
        the books, or a drain-only balancer wedges forever on 'Array has
        been deleted' (the request-dispatch guard never runs without
        traffic)."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = _mk_balancer(provider, donate_state=True,
                               batch_window=0.001)
            assert bal._donate
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=2048)
            await _ping_all(invokers, producer)
            real = bal._release_packed_fn

            def consume_then_raise(state, rel):
                real(state, rel)
                raise RuntimeError("injected idle-fold failure")

            bal._release_packed_fn = consume_then_raise
            slot = bal._slots.acquire("heal:128")
            bal._queue_release(0, slot, 128, 1, "heal:128")
            await bal._device_step()  # idle: no pending -> release fold
            # the consumed state was rebuilt (and the fold fns with it)
            assert not bal.state.free_mb.is_deleted()
            assert bal._release_packed_fn is not consume_then_raise
            # the balancer still places after the outage
            ident = Identity.generate("guest")
            action = make_action("idleheal", memory=128)
            await (await bal.publish(action, make_msg(action, ident, True)))
            await bal.close()
            for inv in invokers:
                await inv.stop()

        asyncio.run(go())

    def test_occupancy_serves_cached_books_without_device(self):
        """occupancy() must never touch the device: after a placement it
        reflects the held capacity purely from the readback cache (the
        state reference is removed to prove no device read happens)."""
        async def go():
            provider = MemoryMessagingProvider()
            bal = _mk_balancer(provider)
            await bal.start()
            invokers, producer = await _fleet(provider, 2, memory_mb=2048,
                                              delay=0.5)
            await _ping_all(invokers, producer)
            ident = Identity.generate("guest")
            action = make_action("occache", memory=256)
            promise = await bal.publish(action, make_msg(action, ident, True))
            state_ref, bal.state = bal.state, None  # any device read crashes
            try:
                mid = bal.occupancy()
            finally:
                bal.state = state_ref
            await promise
            await bal.close()
            for inv in invokers:
                await inv.stop()
            return mid

        mid = asyncio.run(go())
        assert mid["fleet"]["used_mb"] == 256
        assert bool(TpuBalancer.OCCUPANCY_SYNCS_DEVICE) is False

    def test_scan_depth1_legacy_path_is_bit_exact(self):
        """placement_kernel=scan + pipeline_depth=1 must place a
        deterministic request sequence on EXACTLY the invokers the default
        (repair+pipelined) path picks."""
        def run(**cfg):
            async def go():
                provider = MemoryMessagingProvider()
                bal = _mk_balancer(provider, **cfg)
                await bal.start()
                invokers, producer = await _fleet(provider, 3,
                                                  memory_mb=2048)
                await _ping_all(invokers, producer)
                ident = Identity.generate("guest")
                placed = []
                for i in range(24):
                    action = make_action(f"legacy{i % 3}", memory=256)
                    p = await bal.publish(action,
                                          make_msg(action, ident, True))
                    entry = bal.activation_slots[
                        list(bal.activation_slots)[-1]]
                    placed.append(entry.invoker.instance)
                    await p
                await bal.close()
                for inv in invokers:
                    await inv.stop()
                return placed

            return asyncio.run(go())

        modern = run()
        legacy = run(placement_kernel="scan", pipeline_depth=1)
        assert modern == legacy
