"""Placement decision-quality scoring on device.

Nothing in the balancer measures whether the placement kernel's decisions
are actually GOOD — the telemetry plane sees realized completion latency,
but by then a bad placement is sunk cost and unattributable. This module
scores every committed micro-batch against the predictive signals the
balancer already holds on device (the anomaly plane's per-invoker latency
EWMAs and the post-commit capacity books), emitting three quantities:

  regret       per placed row, `max(0, cost[chosen] - min cost over the
               feasible alternatives)` where cost is the per-invoker
               predicted latency (EWMA, ms) and feasibility re-applies the
               production constraints (partition, health, spare warm permit
               OR free memory) against the POST-commit books. Regret is
               therefore a slight over-statement for rows whose chosen
               invoker's commit starved an alternative — the honest
               direction for an alerting signal. Invokers with no latency
               signal score cost 0 (optimistic): choosing a known-slow
               invoker while an unmeasured one was feasible counts as full
               regret, which is exactly the straggler-avoidance miss the
               shadow plane exists to measure.
  imbalance    the post-commit fleet occupancy CoV (stddev/mean of
               `1 - free/cap` over healthy, non-padding invokers): 0 is a
               perfectly level fleet, >1 means placement is piling load.
  attribution  forced / overflow (placed off the home invoker) / throttled
               / unplaced counts, and cold starts: placed rows that took
               memory for a new container, i.e. placed and not `warm` (the
               kernels' per-row use_conc bit in the decision word), exact.

A shadow decision vector (the counterfactual kernel's output for the same
batch) folds in the same program: divergent-row counts, the predicted-cost
delta over divergent rows (positive = the shadow's choices predicted
faster), and per-invoker divergence attribution at the production choice.

Everything accumulates into a tiny on-device `QualityState` (one histogram
over the telemetry bucket grid so fleet federation can merge bucket-wise
bit-exactly, a counter vector, and two per-invoker vectors); the jitted
step returns a float32 summary row for the flight recorder. The NumPy twin
(`quality_step_np`) runs the identical arithmetic for the CPU balancers
and the parity fuzz: integer outputs match the jitted path exactly,
float32 accumulations match to reduction-order tolerance.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .placement import unpack_chosen, unpack_warm
from .telemetry import DEFAULT_BUCKETS, _bounds_us

#: counter-vector layout (int32[N_COUNTERS]); the plane exposes these by
#: name, the fleet merger sums them positionally
COUNTERS = ("rows", "placed", "forced", "overflow", "throttled", "unplaced",
            "cold_start", "shadow_rows", "shadow_divergent")
(C_ROWS, C_PLACED, C_FORCED, C_OVERFLOW, C_THROTTLED, C_UNPLACED,
 C_COLDSTART, C_SHADOW_ROWS, C_SHADOW_DIVERGENT) = range(len(COUNTERS))
N_COUNTERS = len(COUNTERS)

#: per-batch summary row (float32[N_SUMMARY]) returned by the step
(S_REGRET_SUM_MS, S_REGRET_MAX_MS, S_REGRET_ROWS, S_ROWS, S_IMBALANCE_COV,
 S_DIVERGENT, S_SHADOW_DELTA_MS, S_SHADOW_ROWS) = range(8)
N_SUMMARY = 8

#: regret clip before the us conversion: keeps `regret_ms * 1000` inside
#: int32 on both paths (2e6 ms ~ 33 min, far past any sane EWMA)
_REGRET_CLIP_MS = 2.0e6


class QualityState(NamedTuple):
    regret_hist: object     # int32[n_buckets]  (telemetry bucket grid)
    counters: object        # int32[N_COUNTERS]
    inv_regret_ms: object   # float32[N] cumulative regret at the chosen
    inv_divergence: object  # int32[N] shadow-divergent rows by prod choice


def init_quality_state(n_pad: int, n_buckets: int = DEFAULT_BUCKETS,
                       numpy: bool = False) -> QualityState:
    xp = np if numpy else jnp
    return QualityState(xp.zeros((n_buckets,), xp.int32),
                        xp.zeros((N_COUNTERS,), xp.int32),
                        xp.zeros((n_pad,), xp.float32),
                        xp.zeros((n_pad,), xp.int32))


def _score_math(xp, free_post, conc_bn, health, ewma_ms, cap_mb,
                req, out_vec, shadow_vec, bounds_us):
    """The one copy of the scoring arithmetic, written against the numpy/
    jax.numpy common surface (`xp`); `conc_bn` arrives pre-gathered as
    [B, N] so the caller owns the [N, A]-vs-transposed layout difference.
    Scatter-adds differ in spelling (jnp `.at[].add`, np.add.at), so the
    accumulation happens in the two wrappers off the masks built here."""
    b = req.shape[1]
    n = free_post.shape[0]
    offset, size, home = req[0], req[1], req[2]
    need, slot = req[4], req[5]
    valid = req[8] > 0
    chosen, forced, throttled = unpack_chosen(out_vec[:b])
    placed = valid & (chosen >= 0)
    chosen_c = xp.clip(chosen, 0, n - 1)

    idx = xp.arange(n, dtype=xp.int32)
    local = idx[None, :] - offset[:, None]
    in_part = (local >= 0) & (local < size[:, None])
    feasible = (in_part & health[None, :]
                & ((conc_bn > 0) | (free_post[None, :] >= need[:, None])))
    inf = xp.float32(3.0e38)
    cost = ewma_ms.astype(xp.float32)
    alt = xp.where(feasible, cost[None, :], inf)
    best = xp.min(alt, axis=1)
    any_feasible = best < inf
    regret_ms = xp.where(
        placed & any_feasible,
        xp.maximum(cost[chosen_c] - best, xp.float32(0.0)),
        xp.float32(0.0)).astype(xp.float32)
    regret_ms = xp.minimum(regret_ms, xp.float32(_REGRET_CLIP_MS))
    regret_us = (regret_ms * xp.float32(1000.0)).astype(xp.int32)
    bucket = xp.sum((regret_us[:, None] > bounds_us[None, :])
                    .astype(xp.int32), axis=1)

    home_g = offset + home
    overflow = placed & ~forced & (chosen != home_g)
    unplaced = valid & ~placed & ~throttled
    cold = placed & ~unpack_warm(out_vec[:b])

    m = health & (cap_mb > 0)
    k = xp.maximum(xp.sum(m.astype(xp.int32)), 1).astype(xp.float32)
    occ = xp.where(m, xp.float32(1.0)
                   - free_post.astype(xp.float32)
                   / xp.maximum(cap_mb, 1).astype(xp.float32),
                   xp.float32(0.0)).astype(xp.float32)
    mean = xp.sum(occ) / k
    var = xp.sum(xp.where(m, (occ - mean) * (occ - mean),
                          xp.float32(0.0))) / k
    cov = xp.sqrt(var) / xp.maximum(mean, xp.float32(1e-6))

    counters = [
        xp.sum(valid.astype(xp.int32)), xp.sum(placed.astype(xp.int32)),
        xp.sum((forced & valid).astype(xp.int32)),
        xp.sum(overflow.astype(xp.int32)),
        xp.sum(throttled.astype(xp.int32)),
        xp.sum(unplaced.astype(xp.int32)), xp.sum(cold.astype(xp.int32))]

    if shadow_vec is not None:
        s_chosen, _, _ = unpack_chosen(shadow_vec[:b])
        divergent = valid & (s_chosen != chosen)
        both = divergent & placed & (s_chosen >= 0)
        s_c = xp.clip(s_chosen, 0, n - 1)
        delta_ms = xp.sum(xp.where(both, cost[chosen_c] - cost[s_c],
                                   xp.float32(0.0)))
        counters += [xp.sum(valid.astype(xp.int32)),
                     xp.sum(divergent.astype(xp.int32))]
    else:
        divergent = xp.zeros((b,), bool)
        delta_ms = xp.float32(0.0)
        counters += [xp.int32(0), xp.int32(0)]

    summary = [xp.sum(regret_ms), xp.max(regret_ms),
               xp.sum((placed & any_feasible).astype(xp.int32))
               .astype(xp.float32),
               xp.sum(valid.astype(xp.int32)).astype(xp.float32), cov,
               xp.sum(divergent.astype(xp.int32)).astype(xp.float32),
               delta_ms,
               (xp.sum(valid.astype(xp.int32)).astype(xp.float32)
                if shadow_vec is not None else xp.float32(0.0))]
    return (chosen_c, placed, bucket, regret_ms, divergent, counters,
            summary)


def make_quality_step(n_buckets: int = DEFAULT_BUCKETS,
                      transposed: bool = False):
    """Build the jitted per-micro-batch scorer.

    step(qstate, free_post, conc_post, health, ewma_ms, cap_mb, req,
         out_vec, shadow_vec) -> (new_qstate, summary float32[N_SUMMARY])

    All array inputs may be live device buffers — the step reads, never
    writes, and is dispatched asynchronously right after the production
    step (post-commit books). `shadow_vec=None` traces the no-shadow
    variant (pytree-static, so the two cadences are two cached programs).
    `transposed=True` consumes the Pallas kernels' [A, N] conc layout.
    """
    bounds = jnp.asarray(np.minimum(_bounds_us(n_buckets), 2 ** 31 - 1),
                         jnp.int32)

    @jax.jit
    def step(qstate: QualityState, free_post, conc_post, health, ewma_ms,
             cap_mb, req, out_vec, shadow_vec=None
             ) -> Tuple[QualityState, jax.Array]:
        slot = req[5]
        if transposed:
            conc_bn = conc_post[slot, :]
        else:
            conc_bn = conc_post[:, slot].T
        chosen_c, placed, bucket, regret_ms, divergent, counters, summary = \
            _score_math(jnp, free_post, conc_bn, health, ewma_ms,
                        cap_mb, req, out_vec, shadow_vec, bounds)
        hist = qstate.regret_hist.at[bucket].add(
            placed.astype(jnp.int32))
        ctr = qstate.counters + jnp.stack(counters)
        inv_r = qstate.inv_regret_ms.at[chosen_c].add(
            jnp.where(placed, regret_ms, 0.0))
        inv_d = qstate.inv_divergence.at[chosen_c].add(
            (divergent & placed).astype(jnp.int32))
        return (QualityState(hist, ctr, inv_r, inv_d),
                jnp.stack([jnp.asarray(s, jnp.float32) for s in summary]))

    return step


def quality_step_np(qstate: QualityState, free_post, conc_post, health,
                    ewma_ms, cap_mb, req, out_vec,
                    shadow_vec: Optional[np.ndarray] = None,
                    transposed: bool = False
                    ) -> Tuple[QualityState, np.ndarray]:
    """NumPy twin of `make_quality_step` for the CPU balancers and the
    parity fuzz: identical arithmetic over the same float32/int32 types.
    Mutates nothing; returns a fresh QualityState of numpy arrays."""
    bounds = np.minimum(_bounds_us(qstate.regret_hist.shape[0]),
                        2 ** 31 - 1).astype(np.int32)
    req = np.asarray(req, np.int32)
    out_vec = np.asarray(out_vec, np.int32)
    free_post = np.asarray(free_post, np.int32)
    health = np.asarray(health, bool)
    ewma_ms = np.asarray(ewma_ms, np.float32)
    cap_mb = np.asarray(cap_mb, np.int32)
    conc_post = np.asarray(conc_post, np.int32)
    if shadow_vec is not None:
        shadow_vec = np.asarray(shadow_vec, np.int32)
    slot = req[5]
    conc_bn = conc_post[slot, :] if transposed else conc_post[:, slot].T
    chosen_c, placed, bucket, regret_ms, divergent, counters, summary = \
        _score_math(np, free_post, conc_bn, health, ewma_ms,
                    cap_mb, req, out_vec, shadow_vec, bounds)
    hist = np.array(qstate.regret_hist, np.int32, copy=True)
    np.add.at(hist, bucket, placed.astype(np.int32))
    ctr = qstate.counters + np.stack(counters).astype(np.int32)
    inv_r = np.array(qstate.inv_regret_ms, np.float32, copy=True)
    np.add.at(inv_r, chosen_c, np.where(placed, regret_ms,
                                        np.float32(0.0)))
    inv_d = np.array(qstate.inv_divergence, np.int32, copy=True)
    np.add.at(inv_d, chosen_c, (divergent & placed).astype(np.int32))
    return (QualityState(hist, ctr, inv_r, inv_d),
            np.asarray(summary, np.float32))
