"""Placement quality observatory (ISSUE 17): kernel-level proofs.

Four contracts back the plane's headline claim ("measure placement
quality without changing placement"):

  * the jitted on-device scorer and its NumPy twin are the SAME
    arithmetic — integer outputs (histogram, counters, divergence)
    bit-identical, float32 accumulations within reduction-order
    tolerance, across both conc layouts and both shadow cadences;
  * the shadow counterfactual step with a ZERO penalty reproduces the
    production packed decision vector bit-for-bit (scan and repair
    kernel families, plain and admit variants) and never touches the
    live books;
  * a nonzero penalty means the same thing to every kernel family
    (scan == repair == pallas == pallas-repair under one penalty
    vector), one probe-ring lap of demotion per penalty level, and
    `penalty=None` stays the identity;
  * a disabled plane is a TRUE no-op (tracemalloc-asserted, the PR 3/10
    pattern) and the fleet merger (`merged_quality_report`) sums
    member histograms/counters bit-exactly — two members' merged counts
    equal one member that scored the pooled batches.
"""
import tracemalloc

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from openwhisk_tpu.controller.loadbalancer.quality import (  # noqa: E402
    QualityConfig, QualityPlane)
from openwhisk_tpu.controller.monitoring import (  # noqa: E402
    _pctl_from_hist, merged_quality_report)
from openwhisk_tpu.ops.decision_quality import (  # noqa: E402
    COUNTERS, C_PLACED, C_ROWS, C_SHADOW_DIVERGENT, C_SHADOW_ROWS,
    init_quality_state, make_quality_step, quality_step_np)
from openwhisk_tpu.ops.placement import (  # noqa: E402
    RequestBatch, init_state, make_fused_admit_step_packed,
    make_fused_step_packed, make_shadow_admit_step_packed,
    make_shadow_step_packed, release_batch, release_batch_vector,
    schedule_batch, schedule_batch_repair, unpack_step_output)
from openwhisk_tpu.ops.placement_pallas import (  # noqa: E402
    schedule_batch_pallas, schedule_batch_repair_pallas, to_transposed)
from openwhisk_tpu.ops.throttle import init_buckets  # noqa: E402


# -- randomized fixtures (the test_placement_repair fuzz idiom) ------------

def _random_batch(n, b, rng, slots=16, valid_p=0.9):
    import math
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
    need = rng.choice([128, 256, 512], b).astype(np.int32)
    slot = rng.randint(0, slots, b).astype(np.int32)
    maxc = rng.choice([1, 1, 4], b).astype(np.int32)
    rand = (rng.randint(0, 1 << 20, b).astype(np.int32)
            % np.maximum(size, 1))
    valid = rng.rand(b) < valid_p
    return RequestBatch(*[jnp.asarray(x) for x in
                          (off, size, home, step_inv, need, slot, maxc,
                           rand, valid)])


def _random_state(n, rng, mem=1024, slots=16, unhealthy_p=0.2):
    st = init_state(n, [mem] * n, action_slots=slots)
    health = ~(rng.rand(n) < unhealthy_p)
    if not health.any():
        health[rng.randint(0, n)] = True
    conc = np.where(rng.rand(n, slots) < 0.3,
                    rng.randint(1, 4, (n, slots)), 0).astype(np.int32)
    return st._replace(health=jnp.asarray(health),
                       conc_free=jnp.asarray(conc))


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


def _words(chosen, forced=0, throttled=0, warm=0):
    """Decision words in the packed step's layout (ops.placement
    .pack_decisions), from host arrays."""
    return (((np.asarray(chosen, np.int32) + 1) << 3) | (warm << 2)
            | (throttled << 1) | forced).astype(np.int32)


def _step_words(p_out, b):
    """A production step's B decision words, re-packed from what the one
    decoder of its output vector returns."""
    s = unpack_step_output(np.asarray(p_out), b)
    return _words(s.chosen, s.forced.astype(np.int32),
                  s.throttled.astype(np.int32), s.warm.astype(np.int32))


def _fuzz_scorer_inputs(rng, n, b, slots=8, shadow=True):
    """Random post-commit books + a random (but well-formed) packed
    decision vector — the scorer consumes decisions, it need not have
    produced them, so the fuzz space is wider than any one kernel's."""
    req = np.zeros((9, b), np.int32)
    off = rng.randint(0, max(1, n // 2), b).astype(np.int32)
    size = np.minimum(np.maximum(1, rng.randint(1, n + 1, b) - off),
                      n - off).astype(np.int32)
    req[0], req[1] = off, size
    req[2] = rng.randint(0, 1 << 16, b) % size
    req[4] = rng.choice([128, 256, 512], b)
    req[5] = rng.randint(0, slots, b)
    req[8] = (rng.rand(b) < 0.9).astype(np.int32)
    free = rng.randint(0, 2048, n).astype(np.int32)
    conc = np.where(rng.rand(n, slots) < 0.4,
                    rng.randint(1, 4, (n, slots)), 0).astype(np.int32)
    health = rng.rand(n) < 0.85
    if not health.any():
        health[0] = True
    # a mix of measured and unmeasured (cost-0 optimistic) invokers
    ewma = np.where(rng.rand(n) < 0.7, rng.rand(n) * 500.0,
                    0.0).astype(np.float32)
    cap = np.full(n, 2048, np.int32)
    cap[rng.rand(n) < 0.1] = 0

    def vec():
        chosen = rng.randint(-1, n, b).astype(np.int32)
        throttled = ((rng.rand(b) < 0.1) & (chosen < 0)).astype(np.int32)
        forced = ((rng.rand(b) < 0.2) & (chosen >= 0)).astype(np.int32)
        warm = ((rng.rand(b) < 0.4) & (chosen >= 0)
                & (forced == 0)).astype(np.int32)
        return _words(chosen, forced, throttled, warm)

    return (free, conc, health, ewma, cap, req, vec(),
            vec() if shadow else None)


# -- scorer parity: jitted step vs NumPy twin ------------------------------

class TestScorerParity:
    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_parity_jit_vs_numpy(self, seed):
        """Chained steps over random books/decisions: ints exact, floats
        to reduction-order tolerance. Layout and shadow cadence vary with
        the seed so both traced programs get coverage."""
        rng = np.random.RandomState(seed)
        n = int(rng.choice([4, 8, 32]))
        b = int(rng.choice([8, 16, 64]))
        nb = int(rng.choice([8, 24]))
        transposed = bool(seed % 2)
        shadow = seed != 2  # one seed exercises the no-shadow program
        step = make_quality_step(nb, transposed=transposed)
        qs_j = init_quality_state(n, nb)
        qs_n = init_quality_state(n, nb, numpy=True)
        for _ in range(3):
            free, conc, health, ewma, cap, req, out, sh = \
                _fuzz_scorer_inputs(rng, n, b, shadow=shadow)
            conc_in = conc.T.copy() if transposed else conc
            qs_j, sum_j = step(
                qs_j, jnp.asarray(free), jnp.asarray(conc_in),
                jnp.asarray(health), jnp.asarray(ewma), jnp.asarray(cap),
                jnp.asarray(req), jnp.asarray(out),
                jnp.asarray(sh) if sh is not None else None)
            qs_n, sum_n = quality_step_np(
                qs_n, free, conc_in, health, ewma, cap, req, out, sh,
                transposed=transposed)
        np.testing.assert_array_equal(np.asarray(qs_j.regret_hist),
                                      qs_n.regret_hist)
        np.testing.assert_array_equal(np.asarray(qs_j.counters),
                                      qs_n.counters)
        np.testing.assert_array_equal(np.asarray(qs_j.inv_divergence),
                                      qs_n.inv_divergence)
        np.testing.assert_allclose(np.asarray(qs_j.inv_regret_ms),
                                   qs_n.inv_regret_ms, rtol=1e-5, atol=1e-2)
        np.testing.assert_allclose(np.asarray(sum_j), sum_n,
                                   rtol=1e-5, atol=1e-2)
        # conservation: every placed row lands in exactly one bucket
        assert int(qs_n.regret_hist.sum()) == int(qs_n.counters[C_PLACED])

    def test_layouts_agree_on_same_books(self):
        """[N, A] and the Pallas [A, N] layout are the same books — the
        scorer must not care which one it was built for."""
        rng = np.random.RandomState(17)
        n, b, nb = 8, 16, 8
        free, conc, health, ewma, cap, req, out, sh = \
            _fuzz_scorer_inputs(rng, n, b)
        a = quality_step_np(init_quality_state(n, nb, numpy=True), free,
                            conc, health, ewma, cap, req, out, sh)
        t = quality_step_np(init_quality_state(n, nb, numpy=True), free,
                            conc.T.copy(), health, ewma, cap, req, out, sh,
                            transposed=True)
        for x, y in zip(a[0], t[0]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(a[1], t[1])

    def test_counter_semantics(self):
        """Hand-built single batch: every attribution counter lands where
        the layout says it does."""
        n, nb = 4, 8
        free = np.asarray([512, 512, 512, 512], np.int32)
        conc = np.zeros((n, 2), np.int32)
        conc[1, 0] = 1  # invoker1 slot0 has a spare permit left
        health = np.asarray([True, True, True, False])
        ewma = np.asarray([100.0, 5.0, 0.0, 0.0], np.float32)
        cap = np.full(n, 1024, np.int32)
        # rows: placed@home(0) on new memory, overflow(chosen=1,home=0) on
        #       a spare permit (warm), throttled, unplaced, invalid
        req = np.zeros((9, 5), np.int32)
        req[1] = n          # size: whole fleet
        req[4] = 128        # need_mb
        req[8] = [1, 1, 1, 1, 0]
        chosen = np.asarray([0, 1, -1, -1, 0], np.int32)
        throttled = np.asarray([0, 0, 1, 0, 0], np.int32)
        warm = np.asarray([0, 1, 0, 0, 0], np.int32)
        out = _words(chosen, throttled=throttled, warm=warm)
        qs, summary = quality_step_np(
            init_quality_state(n, nb, numpy=True), free, conc, health,
            ewma, cap, req, out)
        got = {name: int(qs.counters[i]) for i, name in enumerate(COUNTERS)}
        assert got == {"rows": 4, "placed": 2, "forced": 0, "overflow": 1,
                       "throttled": 1, "unplaced": 1, "cold_start": 1,
                       "shadow_rows": 0, "shadow_divergent": 0}
        # row 0 chose the 100ms invoker while 5ms and 0ms (unmeasured,
        # optimistic) alternatives were feasible: regret = 100 - 0
        assert qs.inv_regret_ms[0] == pytest.approx(100.0)
        # row 1 chose the cheapest measured invoker but invoker2 is
        # unmeasured AND feasible via free memory -> regret 5 - 0
        assert qs.inv_regret_ms[1] == pytest.approx(5.0)

    def test_shadow_divergence_attribution(self):
        n, nb = 4, 8
        free = np.full(n, 512, np.int32)
        conc = np.zeros((n, 2), np.int32)
        health = np.ones(n, bool)
        ewma = np.asarray([50.0, 10.0, 0.0, 0.0], np.float32)
        cap = np.full(n, 1024, np.int32)
        req = np.zeros((9, 3), np.int32)
        req[1] = n
        req[4] = 128
        req[8] = 1
        out = _words([0, 1, 2])
        shadow = _words([1, 1, 2])
        qs, summary = quality_step_np(
            init_quality_state(n, nb, numpy=True), free, conc, health,
            ewma, cap, req, out, shadow)
        assert int(qs.counters[C_SHADOW_ROWS]) == 3
        assert int(qs.counters[C_SHADOW_DIVERGENT]) == 1
        # divergence is attributed at the PRODUCTION choice
        np.testing.assert_array_equal(qs.inv_divergence, [1, 0, 0, 0])
        # delta = cost[prod=0] - cost[shadow=1] = 50 - 10 (predicted
        # saving had the shadow's choice been taken)
        from openwhisk_tpu.ops.decision_quality import S_SHADOW_DELTA_MS
        assert summary[S_SHADOW_DELTA_MS] == pytest.approx(40.0)


# -- shadow counterfactual: bit-exactness against production ---------------

class TestShadowCounterfactual:
    @pytest.mark.parametrize("rel_fn,sched_fn", [
        (release_batch, schedule_batch),
        (release_batch_vector, schedule_batch_repair),
    ], ids=["scan", "repair"])
    def test_zero_penalty_shadow_matches_production(self, rel_fn, sched_fn):
        """The acceptance contract: with the penalty zeroed, the shadow's
        packed decisions equal the production step's bit-for-bit, and the
        live books the production step is about to consume are untouched."""
        rng = np.random.RandomState(5)
        n, r, h, b = 32, 8, 4, 16
        state = _random_state(n, rng)
        free0 = np.asarray(state.free_mb).copy()
        conc0 = np.asarray(state.conc_free).copy()
        buf = jnp.asarray(_packed_buf(rng, n, r, h, b))
        s_out = make_shadow_step_packed(rel_fn, sched_fn)(
            state, buf, jnp.zeros((n,), jnp.int32), r, h, b)
        assert s_out.shape == (b,)  # words only: no rounds, no books
        _, p_out = make_fused_step_packed(rel_fn, sched_fn)(
            state, buf, r, h, b)
        np.testing.assert_array_equal(np.asarray(s_out), _step_words(p_out, b))
        np.testing.assert_array_equal(np.asarray(state.free_mb), free0)
        np.testing.assert_array_equal(np.asarray(state.conc_free), conc0)

    def test_zero_penalty_admit_shadow_matches_production(self):
        """Admit variant: same bucket state + now -> identical throttle
        bits and decisions, and the shadow returns neither books nor
        buckets to mutate."""
        rng = np.random.RandomState(6)
        n, r, h, b = 32, 8, 4, 16
        state = _random_state(n, rng)
        buckets = init_buckets(64, 6)
        tokens0 = np.asarray(buckets.tokens).copy()
        buf = jnp.asarray(_packed_buf(rng, n, r, h, b, rows=10))
        s_out = make_shadow_admit_step_packed(release_batch, schedule_batch)(
            (state, buckets), buf, jnp.zeros((n,), jnp.int32),
            np.float32(1.0), r, h, b)
        _, p_out = make_fused_admit_step_packed(release_batch,
                                                schedule_batch)(
            (state, buckets), buf, np.float32(1.0), r, h, b)
        np.testing.assert_array_equal(np.asarray(s_out), _step_words(p_out, b))
        # the tight bucket actually throttled something, so bit 1 is live
        assert unpack_step_output(np.asarray(p_out), b).throttled.any()
        np.testing.assert_array_equal(np.asarray(buckets.tokens), tokens0)

    @pytest.mark.pallas
    def test_penalized_parity_across_kernel_families(self):
        """One penalty vector means one thing: scan, repair, pallas and
        pallas-repair (interpret mode) agree on every placement, forced
        flag AND the post-commit books under the same nonzero penalty."""
        rng = np.random.RandomState(11)
        n, b = 32, 24
        state = _random_state(n, rng, slots=8)
        batch = _random_batch(n, b, rng, slots=8)
        pen = jnp.asarray(np.where(rng.rand(n) < 0.3,
                                   rng.randint(1, 4, n), 0), jnp.int32)
        ref = schedule_batch(state, batch, pen)
        outs = [
            schedule_batch_repair(state, batch, pen),
            schedule_batch_pallas(to_transposed(state), batch,
                                  interpret=True, penalty=pen),
            schedule_batch_repair_pallas(to_transposed(state), batch,
                                         interpret=True, penalty=pen),
        ]
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(ref[1]),
                                          np.asarray(out[1]), err_msg=str(i))
            np.testing.assert_array_equal(np.asarray(ref[2]),
                                          np.asarray(out[2]), err_msg=str(i))
            np.testing.assert_array_equal(np.asarray(ref[0].free_mb),
                                          np.asarray(out[0].free_mb))

    @pytest.mark.pallas
    def test_zero_penalty_is_identity_everywhere(self):
        """penalty=0 and penalty=None are the same schedule — the shadow
        with no active penalties measures exactly zero divergence."""
        rng = np.random.RandomState(13)
        n, b = 16, 16
        state = _random_state(n, rng, slots=8)
        batch = _random_batch(n, b, rng, slots=8)
        zero = jnp.zeros((n,), jnp.int32)
        for none_out, zero_out in [
                (schedule_batch(state, batch),
                 schedule_batch(state, batch, zero)),
                (schedule_batch_repair(state, batch),
                 schedule_batch_repair(state, batch, zero)),
                (schedule_batch_pallas(to_transposed(state), batch,
                                       interpret=True),
                 schedule_batch_pallas(to_transposed(state), batch,
                                       interpret=True, penalty=zero))]:
            np.testing.assert_array_equal(np.asarray(none_out[1]),
                                          np.asarray(zero_out[1]))
            np.testing.assert_array_equal(np.asarray(none_out[2]),
                                          np.asarray(zero_out[2]))

    def test_penalty_demotes_straggler_by_probe_laps(self):
        """The augmented geometry: each penalty level pushes the invoker
        one full probe-ring lap down the preference order, so a penalized
        home loses to the next probe stop — without ever making an
        infeasible invoker placeable."""
        n = 4
        state = init_state(n, [1024] * n, action_slots=4)
        z = jnp.zeros((1,), jnp.int32)
        batch = RequestBatch(
            offset=z, size=jnp.full((1,), n, jnp.int32), home=z,
            step_inv=jnp.ones((1,), jnp.int32),
            need_mb=jnp.full((1,), 128, jnp.int32), conc_slot=z,
            max_conc=jnp.ones((1,), jnp.int32), rand=z,
            valid=jnp.ones((1,), bool))
        _, chosen0, forced0, _ = schedule_batch(state, batch)
        assert int(chosen0[0]) == 0 and not bool(forced0[0])
        pen = jnp.asarray([2, 0, 0, 0], jnp.int32)
        _, chosen_p, forced_p, _ = schedule_batch(state, batch, pen)
        assert int(chosen_p[0]) == 1  # next probe stop, not the home
        assert not bool(forced_p[0])
        # penalizing everything reorders, never unplaces: still placed
        _, chosen_all, _, _ = schedule_batch(
            state, batch, jnp.full((n,), 3, jnp.int32))
        assert int(chosen_all[0]) >= 0


# -- disabled plane: a true no-op ------------------------------------------

class TestDisabledPlane:
    def test_disabled_plane_is_a_true_noop(self):
        """PR 3/10 contract, tracemalloc-asserted: every hook a disabled
        plane sits on (record_placement attribution, the dispatch-side
        device step, readback fold, supervision tick) allocates nothing."""
        qp = QualityPlane(QualityConfig(enabled=False))
        qp.attach(anomaly=None, invoker_names=lambda: ["invoker0"])

        def drive():
            qp.observe_decision(True, False, False)
            assert qp.device_step(None, None, None, None, None, None,
                                  None) is None
            qp.note_summary(None)
            qp.use_device(8)
            qp.maybe_tick(None)

        drive()  # warm every path once
        tracemalloc.start()
        try:
            s1 = tracemalloc.take_snapshot()
            for _ in range(256):
                drive()
            s2 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        flt = [tracemalloc.Filter(True, "*loadbalancer/quality.py")]
        grown = [d for d in s2.filter_traces(flt).compare_to(
            s1.filter_traces(flt), "lineno") if d.size_diff > 0]
        total = sum(d.size_diff for d in grown)
        assert total < 2048, f"disabled quality plane allocated {total}B"
        # and it never allocated device or host state
        assert qp._qstate is None
        assert qp.tick() == {}
        assert qp.prometheus_text(["invoker0"]) == ""
        assert qp.quality_report(["invoker0"]) == {"enabled": False}
        assert qp.raw_counts(["invoker0"]) == {"enabled": False}


# -- fleet federation: bit-exact bucket-wise merge -------------------------

def _raw_member(qs, names, ident, imbalance=0.1):
    """A `/admin/placement/quality?raw=1` body built from a scored
    numpy QualityState (the shape QualityPlane.raw_counts exports)."""
    return {
        "identity": {"instance": ident}, "enabled": True, "kernel": "numpy",
        "buckets": int(qs.regret_hist.shape[0]),
        "regret_hist": [int(v) for v in qs.regret_hist],
        "counters": [int(v) for v in qs.counters],
        "counter_names": list(COUNTERS),
        "invokers": {nm: {"regret_ms": float(qs.inv_regret_ms[i]),
                          "divergence": int(qs.inv_divergence[i])}
                     for i, nm in enumerate(names)
                     if qs.inv_regret_ms[i] > 0 or qs.inv_divergence[i] > 0},
        "batches": 2, "shadow_batches": 1,
        "divergent_rows": int(qs.counters[C_SHADOW_DIVERGENT]),
        "shadow_rows": int(qs.counters[C_SHADOW_ROWS]),
        "regret_sum_ms": float(qs.inv_regret_ms.sum()),
        "fleet_imbalance_cov": imbalance,
    }


class TestFleetQualityMerge:
    def test_merge_is_bit_exact_with_pooled_scoring(self):
        """The federation property: score four batches split across two
        members, merge their raw exports — the merged histogram, counters
        and per-invoker divergence equal ONE member that scored all four
        batches. The fleet p99 then re-derives from merged counts."""
        n, b, nb = 8, 32, 8
        names = [f"invoker{i}" for i in range(n)]
        rng = np.random.RandomState(23)
        batches = [_fuzz_scorer_inputs(np.random.RandomState(100 + i), n, b)
                   for i in range(4)]
        member_states, pooled = [], init_quality_state(n, nb, numpy=True)
        for half in (batches[:2], batches[2:]):
            qs = init_quality_state(n, nb, numpy=True)
            for args in half:
                qs, _ = quality_step_np(qs, *args)
            member_states.append(qs)
        for args in batches:
            pooled, _ = quality_step_np(pooled, *args)

        raws = [_raw_member(qs, names, f"m{i}")
                for i, qs in enumerate(member_states)]
        merged = merged_quality_report(raws)
        assert merged["enabled"]
        assert merged["regret_hist"] == [int(v) for v in pooled.regret_hist]
        assert merged["counters"] == {
            name: int(pooled.counters[i])
            for i, name in enumerate(COUNTERS)}
        by_name = {row["invoker"]: row for row in merged["invokers"]}
        for i, nm in enumerate(names):
            div = int(pooled.inv_divergence[i])
            reg = float(pooled.inv_regret_ms[i])
            if reg <= 0 and div <= 0:
                assert nm not in by_name
                continue
            assert by_name[nm]["divergent_rows"] == div
            assert by_name[nm]["regret_ms"] == pytest.approx(reg, abs=1e-2)
        # fleet percentile from MERGED counts, not an average of p99s
        bounds = merged["buckets_le_ms"]
        bi = _pctl_from_hist([int(v) for v in pooled.regret_hist], 0.99)
        expect = bounds[bi] if bi < len(bounds) else None
        assert merged["regret_p99_le_ms"] == expect
        assert merged["shadow_rows"] == int(pooled.counters[C_SHADOW_ROWS])
        assert merged["divergent_rows"] == \
            int(pooled.counters[C_SHADOW_DIVERGENT])
        assert merged["divergence_ratio"] == pytest.approx(
            merged["divergent_rows"] / max(1, merged["shadow_rows"]),
            abs=1e-6)
        assert [m["instance"] for m in merged["members"]] == ["m0", "m1"]

    def test_plane_raw_export_feeds_the_merger(self):
        """End-to-end shape contract: QualityPlane.raw_counts (what the
        endpoint scrapes with ?raw=1) merges against a hand-built member
        without translation."""
        n, b, nb = 4, 16, 8
        qp = QualityPlane(QualityConfig(enabled=True, buckets=nb))
        qs = init_quality_state(n, nb, numpy=True)
        free, conc, health, ewma, cap, req, out, sh = \
            _fuzz_scorer_inputs(np.random.RandomState(31), n, b)
        qs, summary = quality_step_np(qs, free, conc, health, ewma, cap,
                                      req, out, sh)
        qp._qstate = qs
        qp.note_summary(summary)
        raw = qp.raw_counts([f"invoker{i}" for i in range(n)])
        other = _raw_member(qs, [f"invoker{i}" for i in range(n)], "m1")
        merged = merged_quality_report([raw, other])
        assert merged["enabled"]
        assert merged["regret_hist"] == \
            [2 * int(v) for v in qs.regret_hist]
        assert merged["counters"]["rows"] == 2 * int(qs.counters[C_ROWS])

    def test_bucket_mismatch_skipped_with_provenance(self):
        n, nb = 4, 8
        names = [f"invoker{i}" for i in range(n)]
        qs = init_quality_state(n, nb, numpy=True)
        free, conc, health, ewma, cap, req, out, sh = \
            _fuzz_scorer_inputs(np.random.RandomState(41), n, 16)
        qs, _ = quality_step_np(qs, free, conc, health, ewma, cap, req,
                                out, sh)
        good = _raw_member(qs, names, "good")
        odd = _raw_member(init_quality_state(n, nb + 4, numpy=True),
                          names, "odd")
        merged = merged_quality_report([good, odd])
        assert [m["instance"] for m in merged["members"]] == ["good"]
        assert [m["instance"] for m in merged["members_skipped"]] == ["odd"]
        # the mismatched member contributed nothing to the sums
        assert merged["regret_hist"] == [int(v) for v in qs.regret_hist]

    def test_disabled_and_empty_members(self):
        assert merged_quality_report([]) == {"enabled": False,
                                             "members": []}
        assert merged_quality_report(
            [{"enabled": False}]) == {"enabled": False, "members": []}
