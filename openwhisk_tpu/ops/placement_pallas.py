"""Pallas TPU kernel for batched placement.

The XLA path (ops/placement.py) lowers the per-request reduction through
`lax.scan`; this kernel instead runs the whole micro-batch inside ONE
pallas_call with the fleet state resident in VMEM across all B iterations —
no per-iteration HBM round-trips for the capacity books, and the request
columns live in SMEM as scalars.

Layout notes (TPU tiling wants the fleet on the 128-lane axis):
  free    int32[1, N]   free memory permits
  health  int32[1, N]   usable mask (0/1)
  conc_t  int32[A, N]   spare concurrency permits, TRANSPOSED vs the XLA
                        kernel's [N, A] so a request's action-slot row is a
                        contiguous [1, N] vector.
  reqs    int32[B, 10]  (offset, size, home, step_inv, need, slot, max_conc,
                        rand, valid, slot_in_range) per request, in SMEM.

Semantics are identical to ops/placement.py::schedule_batch (asserted by
tests in interpret mode AND by chip_smoke.py's parity leg on the chip):
same probe-rank argmin, same forced placement, same NestedSemaphore capacity
updates, same sequential intra-batch resolution. Both kernels return their
per-row bits in ONE int32 row, `flags` = forced | warm<<1 (warm = the row's
`use_conc`), so the exact warm bit costs no output buffer of its own. `fits_vmem` /
`fits_vmem_repair` report whether a configuration qualifies (larger fleets
use the XLA/sharded path).
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .placement import (PlacementState, RequestBatch, _mulmod,
                        pairwise_prims, repair_commit_masks)

#: the byte budget `fits_vmem` / `fits_vmem_repair` admit state and scratch
#: against, keyed by `device_kind`. No runtime probe reports a usable number
#: (on the v5e `memory_stats()` carries HBM counters only and the device has
#: no vmem attribute), and no `pallas_call` here passes compiler params, so
#: Mosaic's own scoped-VMEM limit (16 MiB on the v5e) is what really
#: decides. A kind's entry is therefore ESTABLISHED, not assumed: every
#: kernel compiles and matches XLA on that device at the largest pow2
#: geometries the budget admits (chip_smoke.py leg C re-checks it). The
#: interpret-mode CPU twin has no VMEM; it mirrors the v5e entry so the
#: twin takes the kernel decisions the chip takes.
_VMEM_BUDGET_BYTES = {
    "TPU v5 lite": 8 * 1024 * 1024,
    "cpu": 8 * 1024 * 1024,
}
_vmem_budget_cache: Optional[int] = None


def vmem_budget_bytes() -> int:
    """The VMEM byte budget of the running device (cached): half of
    `OPENWHISK_TPU_VMEM_BYTES` when set (the test seam), else the
    `_VMEM_BUDGET_BYTES` entry for its `device_kind`. A kind nobody has
    established a budget on raises instead of inheriting another chip's."""
    global _vmem_budget_cache
    if _vmem_budget_cache is not None:
        return _vmem_budget_cache
    env = os.environ.get("OPENWHISK_TPU_VMEM_BYTES")
    if env:
        _vmem_budget_cache = int(env) // 2
        return _vmem_budget_cache
    kind = jax.devices()[0].device_kind
    if kind not in _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"no Pallas VMEM budget established for device kind {kind!r}: "
            f"compile the kernels on it at the largest geometry the budget "
            f"admits, then add it to _VMEM_BUDGET_BYTES")
    _vmem_budget_cache = _VMEM_BUDGET_BYTES[kind]
    return _vmem_budget_cache


def _reset_vmem_budget_cache() -> None:
    """Test seam: re-probe the budget (env overrides are read once)."""
    global _vmem_budget_cache
    _vmem_budget_cache = None


def fits_vmem(n_pad: int, action_slots: int) -> bool:
    """Does the VMEM-resident scan kernel's state fit? (conc [A, N] + free/
    health rows)."""
    return (action_slots + 2) * n_pad * 4 <= vmem_budget_bytes()


#: [B, N] buffers the repair kernel keeps live across the residue loop
#: (probe-rank geometry + the gathered conc rows) plus the per-round
#: materialized temporaries (Mosaic fuses the elementwise chains, so the
#: eligibility/key/selection masks share, not stack), and the [B, B]
#: pairwise conflict matrices
_REPAIR_BN_BUFFERS = 4
_REPAIR_BB_BUFFERS = 3


def fits_vmem_repair(n_pad: int, action_slots: int, batch: int) -> bool:
    """`fits_vmem` for the speculate-and-repair kernel: on top of the
    resident state it budgets the residue loop's [B, N] scratch/temporaries
    and the [B, B] pairwise conflict matrices (see repair kernel layout)."""
    elems = ((action_slots + 2) * n_pad
             + _REPAIR_BN_BUFFERS * batch * n_pad
             + _REPAIR_BB_BUFFERS * batch * batch)
    return elems * 4 <= vmem_budget_bytes()


def to_transposed(state: PlacementState) -> PlacementState:
    """Standard [N, A] state <-> kernel layout ([A, N] conc). Involution."""
    return PlacementState(state.free_mb, state.conc_free.T,
                          state.health)


def _unpack_flags(flags):
    """A kernel's `flags` row -> (forced bool[B], warm bool[B])."""
    return (flags & 1) > 0, (flags & 2) > 0


def _kernel_body(reqs_ref, health_ref, free_ref, conc_ref, chosen_ref,
                 flags_ref, free_out, conc_out, pen_ref=None):
    n = free_out.shape[1]
    b = chosen_ref.shape[1]
    # the penalized rank can exceed n + 2 (one probe-ring lap per penalty
    # level), so the penalized variant needs the larger sentinel — same
    # rule as ops.placement._schedule_one
    big = jnp.int32(n + 2) if pen_ref is None else jnp.int32(1 << 30)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    bidx = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)

    # state starts in the aliased output buffers
    free_out[:] = free_ref[:]
    conc_out[:] = conc_ref[:]
    chosen_ref[:] = jnp.full((1, b), -1, jnp.int32)
    flags_ref[:] = jnp.zeros((1, b), jnp.int32)

    def body(i, _):
        offset = reqs_ref[i, 0]
        size = reqs_ref[i, 1]
        home = reqs_ref[i, 2]
        step_inv = reqs_ref[i, 3]
        need = reqs_ref[i, 4]
        slot = reqs_ref[i, 5]
        max_conc = reqs_ref[i, 6]
        rand = reqs_ref[i, 7]
        valid = reqs_ref[i, 8] > 0
        slot_ok = reqs_ref[i, 9] > 0

        local = idx - offset
        in_part = (local >= 0) & (local < size)
        m = jnp.maximum(size, 1)
        rank = _mulmod(local - home, step_inv, m)
        if pen_ref is not None:
            rank = rank + pen_ref[:] * m

        healthy = health_ref[:] > 0
        conc_row = conc_out[pl.ds(slot, 1), :]
        eligible = in_part & healthy & ((conc_row > 0) | (free_out[:] >= need))
        key = jnp.where(eligible, rank, big)
        kmin = jnp.min(key)
        sel = jnp.min(jnp.where(key == kmin, idx, big))
        found = kmin < big

        usable = in_part & healthy
        fkey = jnp.where(usable, jnp.mod(local - rand, m), big)
        fmin = jnp.min(fkey)
        fsel = jnp.min(jnp.where(fkey == fmin, idx, big))
        have_usable = fmin < big

        chosen = jnp.where(found, sel, fsel)
        placed = valid & (found | have_usable)
        forced = valid & jnp.logical_not(found) & have_usable

        is_sel = idx == chosen
        conc_at = jnp.sum(jnp.where(is_sel, conc_row, 0))
        use_conc = placed & (conc_at > 0)
        take_mem = placed & jnp.logical_not(use_conc)

        free_out[:] = free_out[:] - jnp.where(
            is_sel & take_mem, need, 0).astype(jnp.int32)
        conc_delta = jnp.where(
            use_conc, -1,
            jnp.where(take_mem & (max_conc > 1), max_conc - 1, 0))
        # an out-of-range slot reads the clamped column (like XLA's
        # dynamic_index_in_dim) but its write is DROPPED (like XLA scatter)
        conc_out[pl.ds(slot, 1), :] = conc_row + jnp.where(
            is_sel & slot_ok, conc_delta, 0).astype(jnp.int32)

        at_i = bidx == i
        chosen_ref[:] = jnp.where(at_i & placed, chosen, chosen_ref[:])
        flags = jnp.where(forced, 1, 0) | jnp.where(use_conc, 2, 0)
        flags_ref[:] = jnp.where(at_i, flags, flags_ref[:])
        return 0

    jax.lax.fori_loop(0, b, body, 0)


def _kernel(reqs_ref, health_ref, free_ref, conc_ref, chosen_ref, flags_ref,
            free_out, conc_out):
    _kernel_body(reqs_ref, health_ref, free_ref, conc_ref, chosen_ref,
                 flags_ref, free_out, conc_out)


def _kernel_penalized(reqs_ref, health_ref, free_ref, conc_ref, pen_ref,
                      chosen_ref, flags_ref, free_out, conc_out):
    _kernel_body(reqs_ref, health_ref, free_ref, conc_ref, chosen_ref,
                 flags_ref, free_out, conc_out, pen_ref=pen_ref)


@partial(jax.jit, static_argnames=("interpret",))
def schedule_batch_pallas(state: PlacementState, batch: RequestBatch,
                          interpret: bool = False, penalty=None
                          ) -> Tuple[PlacementState, jax.Array, jax.Array,
                                     jax.Array]:
    """Drop-in for schedule_batch, state in transposed ([A, N]) layout.
    `penalty=None` traces the original kernel unchanged; a penalty vector
    appends one [1, N] VMEM input AFTER the aliased state buffers, so the
    input_output_aliases indices are identical in both variants."""
    n = state.free_mb.shape[0]
    a = state.conc_free.shape[0]
    b = batch.offset.shape[0]
    # pl.ds needs an in-range start: clamp the read column (XLA's
    # dynamic_index_in_dim does the same) and flag OOB slots so their
    # writes are dropped (XLA scatter semantics)
    slot_ok = (batch.conc_slot >= 0) & (batch.conc_slot < a)
    slot = jnp.clip(batch.conc_slot, 0, a - 1)
    reqs = jnp.stack(
        [batch.offset, batch.size, batch.home, batch.step_inv, batch.need_mb,
         slot, batch.max_conc, batch.rand,
         batch.valid.astype(jnp.int32), slot_ok.astype(jnp.int32)], axis=1)
    free2 = state.free_mb.reshape(1, n)
    health2 = state.health.astype(jnp.int32).reshape(1, n)

    out_shape = (jax.ShapeDtypeStruct((1, b), jnp.int32),
                 jax.ShapeDtypeStruct((1, b), jnp.int32),
                 jax.ShapeDtypeStruct((1, n), jnp.int32),
                 jax.ShapeDtypeStruct((a, n), jnp.int32))
    out_specs = (pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pltpu.VMEM))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM)]
    if penalty is None:
        chosen, flags, free_o, conc_o = pl.pallas_call(
            _kernel, out_shape=out_shape, in_specs=in_specs,
            out_specs=out_specs, input_output_aliases={2: 2, 3: 3},
            interpret=interpret,
        )(reqs, health2, free2, state.conc_free)
    else:
        chosen, flags, free_o, conc_o = pl.pallas_call(
            _kernel_penalized, out_shape=out_shape,
            in_specs=in_specs + [pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=out_specs, input_output_aliases={2: 2, 3: 3},
            interpret=interpret,
        )(reqs, health2, free2, state.conc_free,
          penalty.astype(jnp.int32).reshape(1, n))

    new_state = PlacementState(free_o.reshape(n), conc_o, state.health)
    return (new_state, chosen.reshape(b)) + _unpack_flags(flags.reshape(b))


def _repair_kernel_body(reqs_ref, reqs_v_ref, health_ref, free_ref, conc_ref,
                        chosen_ref, flags_ref, rounds_ref, free_out,
                        conc_out, conc_bn_ref, pen_ref=None):
    """Speculate-and-repair in ONE kernel: full-batch probe, the shared
    conflict rules (ops.placement.repair_commit_masks with the pairwise
    prims), scatter-commit, and the residue loop — all with the fleet
    state resident in VMEM, so repair rounds cost vector passes instead of
    the multi-dispatch round trips the XLA while_loop pays per round.

    Orientation: per-request vectors are COLUMNS ([B, 1], request on the
    sublane axis) so [B, N] probe math and [B, B] pairwise conflict math
    broadcast without transposes; the same request matrix arrives twice —
    `reqs_ref` in SMEM (scalar reads for the dynamic-slice loops) and
    `reqs_v_ref` in VMEM (column vectors for the batch math)."""
    n = free_out.shape[1]
    b = chosen_ref.shape[1]
    # penalized ranks can exceed n + 2: larger sentinel, same rule as the
    # XLA _probe_geometry
    big = jnp.int32(n + 2) if pen_ref is None else jnp.int32(1 << 30)
    idx_bn = jax.lax.broadcasted_iota(jnp.int32, (b, n), 1)
    bidx_col = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    eye_bb = (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
              == jax.lax.broadcasted_iota(jnp.int32, (b, b), 1))
    prims = pairwise_prims(b)

    # per-request columns [B, 1]
    offset = reqs_v_ref[:, 0:1]
    size = reqs_v_ref[:, 1:2]
    home = reqs_v_ref[:, 2:3]
    step_inv = reqs_v_ref[:, 3:4]
    need = reqs_v_ref[:, 4:5]
    slot_col = reqs_v_ref[:, 5:6]
    maxc = reqs_v_ref[:, 6:7]
    rand = reqs_v_ref[:, 7:8]
    valid = reqs_v_ref[:, 8:9] > 0
    slot_ok = reqs_v_ref[:, 9:10] > 0
    simple = maxc <= 1

    # state starts in the aliased output buffers
    free_out[:] = free_ref[:]
    conc_out[:] = conc_ref[:]

    # loop-invariant geometry (health never changes inside a batch): probe
    # ranks masked to the usable partition, and the whole forced path —
    # forced placement ignores capacity, so fchoice/have_usable are fixed
    local = idx_bn - offset
    in_part = (local >= 0) & (local < size)
    m = jnp.maximum(size, 1)
    healthy = health_ref[:] > 0                      # [1, N]
    usable = in_part & healthy
    geom_rank = _mulmod(local - home, step_inv, m)
    if pen_ref is not None:
        geom_rank = geom_rank + pen_ref[:] * m
    geom_key = jnp.where(usable, geom_rank, big)
    fkey = jnp.where(usable, jnp.mod(local - rand, m), big)
    fmin = jnp.min(fkey, axis=1, keepdims=True)
    fchoice = jnp.min(jnp.where(fkey == fmin, idx_bn, big), axis=1,
                      keepdims=True)
    have_usable = fmin < big
    col_conc_geom = usable  # permit visibility is masked to the partition

    # Mosaic cannot carry i1 vectors through scf.while ("failed to
    # legalize scf.yield", v5e / libtpu 0.0.34): the masks ride the loop
    # as int32 columns (pending 0/1; flags = forced | warm<<1, written
    # once, in the round that settles the row) and are compared back to
    # bool inside
    def cond(carry):
        pending_i, _, _, rounds = carry
        return (jnp.max(pending_i) > 0) & (rounds <= b)

    def body(carry):
        pending_i, chosen, flags_i, rounds = carry
        pending = pending_i > 0
        # per-round speculation: gather each request's conc column row
        # (the only dynamically-indexed read; slots pre-clamped host-side)
        def gather(i, _):
            conc_bn_ref[pl.ds(i, 1), :] = conc_out[pl.ds(reqs_ref[i, 5], 1), :]
            return 0

        jax.lax.fori_loop(0, b, gather, 0)
        conc_bn = conc_bn_ref[:]
        has_conc = conc_bn > 0
        free_row = free_out[:]                       # [1, N]
        eligible = has_conc | (free_row >= need)
        key = jnp.where(eligible, geom_key, big)
        kmin = jnp.min(key, axis=1, keepdims=True)
        choice = jnp.min(jnp.where(key == kmin, idx_bn, big), axis=1,
                         keepdims=True)
        found = kmin < big
        sel = jnp.where(found, choice, fchoice)      # [B, 1]
        placed = valid & (found | have_usable)
        forced = valid & jnp.logical_not(found) & have_usable
        is_sel = idx_bn == sel                       # [B, N]
        conc_at_sel = jnp.sum(jnp.where(is_sel, conc_bn, 0), axis=1,
                              keepdims=True)
        use_conc = placed & (conc_at_sel > 0)
        take_mem = placed & jnp.logical_not(use_conc)
        col_conc = jnp.any(col_conc_geom & has_conc, axis=1, keepdims=True)
        free_at_sel = jnp.sum(jnp.where(is_sel, free_row, 0), axis=1,
                              keepdims=True)

        safe, commit = repair_commit_masks(
            prims, pending=pending, placed=placed, forced=forced, sel=sel,
            take_mem=take_mem, use_conc=use_conc, simple=simple,
            need_mb=need, conc_slot=slot_col, free_at_sel=free_at_sel,
            col_conc=col_conc, n=n, a_slots=conc_out.shape[0],
            slot_ok=slot_ok)

        # commit: memory deltas collapse to one [B, N] -> [1, N] reduction
        # (cascade writers on one invoker sum exactly); conc deltas are the
        # rare class — scatter them row by row, predicated off for the
        # (typical) zero-delta rows
        dmem = jnp.sum(jnp.where(is_sel & commit & take_mem, need, 0),
                       axis=0, keepdims=True)
        free_out[:] = free_row - dmem.astype(jnp.int32)
        conc_delta = jnp.where(
            commit & use_conc, -1,
            jnp.where(commit & take_mem & jnp.logical_not(simple),
                      maxc - 1, 0))
        # an out-of-range slot reads the clamped column but its write is
        # DROPPED (XLA scatter semantics, like the scan kernel)
        conc_delta = jnp.where(slot_ok, conc_delta, 0)

        def put(i, _):
            d = jnp.sum(jnp.where(bidx_col == i, conc_delta, 0))

            @pl.when(d != 0)
            def _():
                sel_i = jnp.sum(jnp.where(bidx_col == i, sel, 0))
                s = reqs_ref[i, 5]
                row = conc_out[pl.ds(s, 1), :]
                lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
                conc_out[pl.ds(s, 1), :] = row + jnp.where(
                    lane == sel_i, d, 0).astype(jnp.int32)

            return 0

        jax.lax.fori_loop(0, b, put, 0)
        chosen = jnp.where(safe, jnp.where(placed, sel, jnp.int32(-1)),
                           chosen)
        flags_i = jnp.where(
            safe, jnp.where(forced, 1, 0) | jnp.where(use_conc, 2, 0),
            flags_i)
        return (jnp.where(safe, 0, pending_i), chosen, flags_i, rounds + 1)

    _, chosen, flags_i, rounds = jax.lax.while_loop(
        cond, body, (valid.astype(jnp.int32),
                     jnp.full((b, 1), -1, jnp.int32),
                     jnp.zeros((b, 1), jnp.int32), jnp.int32(0)))

    # [B, 1] -> [1, B] result rows via the diagonal-mask transpose
    chosen_ref[:] = jnp.sum(jnp.where(eye_bb, chosen, 0), axis=0,
                            keepdims=True)
    flags_ref[:] = jnp.sum(jnp.where(eye_bb, flags_i, 0), axis=0,
                           keepdims=True)
    rounds_ref[0, 0] = rounds


def _repair_kernel(reqs_ref, reqs_v_ref, health_ref, free_ref, conc_ref,
                   chosen_ref, flags_ref, rounds_ref, free_out, conc_out,
                   conc_bn_ref):
    _repair_kernel_body(reqs_ref, reqs_v_ref, health_ref, free_ref, conc_ref,
                        chosen_ref, flags_ref, rounds_ref, free_out,
                        conc_out, conc_bn_ref)


def _repair_kernel_penalized(reqs_ref, reqs_v_ref, health_ref, free_ref,
                             conc_ref, pen_ref, chosen_ref, flags_ref,
                             rounds_ref, free_out, conc_out, conc_bn_ref):
    _repair_kernel_body(reqs_ref, reqs_v_ref, health_ref, free_ref, conc_ref,
                        chosen_ref, flags_ref, rounds_ref, free_out,
                        conc_out, conc_bn_ref, pen_ref=pen_ref)


@partial(jax.jit, static_argnames=("interpret",))
def schedule_batch_repair_pallas(state: PlacementState, batch: RequestBatch,
                                 interpret: bool = False, penalty=None
                                 ) -> Tuple[PlacementState, jax.Array,
                                            jax.Array, jax.Array, jax.Array]:
    """Drop-in for ops.placement.schedule_batch_repair (state in the
    kernel's transposed [A, N] layout): same (state, chosen, forced, warm,
    rounds) contract, bit-exact with the XLA repair kernel — the conflict
    rules are literally the same function (`repair_commit_masks`), only
    the index primitives differ (pairwise vs scatter/sort; their
    equivalence is fuzz-asserted). One pallas_call runs probe + conflict
    detection + commit + the residue loop with the fleet books resident in
    VMEM — no per-round dispatch round trips."""
    n = state.free_mb.shape[0]
    a = state.conc_free.shape[0]
    b = batch.offset.shape[0]
    # pl.ds needs an in-range start: clamp the gathered column (XLA's
    # fancy-index gather does the same) and flag OOB slots so their writes
    # — and their slot-keyed conflict marks — drop like XLA scatters
    slot_ok = (batch.conc_slot >= 0) & (batch.conc_slot < a)
    slot = jnp.clip(batch.conc_slot, 0, a - 1)
    reqs = jnp.stack(
        [batch.offset, batch.size, batch.home, batch.step_inv, batch.need_mb,
         slot, batch.max_conc, batch.rand,
         batch.valid.astype(jnp.int32), slot_ok.astype(jnp.int32)], axis=1)
    free2 = state.free_mb.reshape(1, n)
    health2 = state.health.astype(jnp.int32).reshape(1, n)

    out_shape = (jax.ShapeDtypeStruct((1, b), jnp.int32),
                 jax.ShapeDtypeStruct((1, b), jnp.int32),
                 jax.ShapeDtypeStruct((1, 1), jnp.int32),
                 jax.ShapeDtypeStruct((1, n), jnp.int32),
                 jax.ShapeDtypeStruct((a, n), jnp.int32))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM)]
    out_specs = (pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pltpu.SMEM),
                 pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pltpu.VMEM))
    if penalty is None:
        chosen, flags, rounds, free_o, conc_o = pl.pallas_call(
            _repair_kernel, out_shape=out_shape, in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((b, n), jnp.int32)],
            input_output_aliases={3: 3, 4: 4},
            interpret=interpret,
        )(reqs, reqs, health2, free2, state.conc_free)
    else:
        chosen, flags, rounds, free_o, conc_o = pl.pallas_call(
            _repair_kernel_penalized, out_shape=out_shape,
            in_specs=in_specs + [pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((b, n), jnp.int32)],
            input_output_aliases={3: 3, 4: 4},
            interpret=interpret,
        )(reqs, reqs, health2, free2, state.conc_free,
          penalty.astype(jnp.int32).reshape(1, n))

    new_state = PlacementState(free_o.reshape(n), conc_o, state.health)
    return ((new_state, chosen.reshape(b)) + _unpack_flags(flags.reshape(b))
            + (rounds.reshape(()),))
