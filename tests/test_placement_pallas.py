"""Pallas placement kernel: exact parity with the XLA kernel.

Runs in interpret mode on the CPU backend (the kernel itself is TPU-shaped;
interpret mode executes the same program semantics). On-device parity and
the timing comparison are exercised by tests/performance/placement_sweep.py
--pallas on real hardware.
"""
import numpy as np
import pytest

import jax.numpy as jnp

pytestmark = pytest.mark.pallas

from __graft_entry__ import _example_batch
from openwhisk_tpu.ops.placement import init_state, schedule_batch, set_health
from openwhisk_tpu.ops.placement_pallas import (fits_vmem,
                                                schedule_batch_pallas,
                                                to_transposed)


@pytest.mark.parametrize("n,batch,seed", [(64, 32, 1), (256, 96, 2),
                                          (128, 64, 3)])
def test_pallas_matches_xla(n, batch, seed):
    state = init_state(n, [1024] * n, action_slots=64)
    req = _example_batch(n, batch, seed=seed)
    s1, c1, f1, w1 = schedule_batch(state, req)
    s2, c2, f2, w2 = schedule_batch_pallas(to_transposed(state), req,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(s1.free_mb),
                                  np.asarray(s2.free_mb))
    np.testing.assert_array_equal(np.asarray(s1.conc_free),
                                  np.asarray(s2.conc_free).T)


def test_pallas_respects_health_mask_and_overload():
    n = 16
    state = init_state(n, [256] * n, action_slots=8)
    state = set_health(state, list(range(8)), [False] * 8)
    req = _example_batch(n, 48, seed=9)  # demand far exceeds capacity
    s1, c1, f1, w1 = schedule_batch(state, req)
    s2, c2, f2, w2 = schedule_batch_pallas(to_transposed(state), req,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    # unhealthy invokers never chosen, even forced
    assert not set(np.asarray(c2)[np.asarray(c2) >= 0]) & set(range(8))
    assert np.asarray(f2).any()  # overload forced placements happened


def test_pallas_out_of_range_slots_match_xla_scatter_semantics():
    """OOB slot ids: reads clamp to the last column, writes are dropped —
    exactly XLA's dynamic_index_in_dim + scatter behavior. The adversarial
    case is max_conc>1 with an OOB slot (a clamping write would mint phantom
    concurrency permits in column A-1 that a later request could consume)."""
    from openwhisk_tpu.ops.placement import RequestBatch
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

    # OOB slot 9 with max_conc=4, then a legit request on slot 3 (the
    # clamped column) with max_conc=4
    req = mk([9, 3, 3], [4, 4, 4])
    s1, c1, f1, w1 = schedule_batch(state, req)
    s2, c2, f2, w2 = schedule_batch_pallas(to_transposed(state), req,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(s1.free_mb),
                                  np.asarray(s2.free_mb))
    np.testing.assert_array_equal(np.asarray(s1.conc_free),
                                  np.asarray(s2.conc_free).T)


def test_fits_vmem_budget():
    assert fits_vmem(1024, 256)
    assert fits_vmem(4096, 256)
    assert not fits_vmem(65536, 256)
