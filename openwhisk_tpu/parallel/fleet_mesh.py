"""Production fleet mesh: speculate-and-repair over a `('fleet',)` axis.

`sharded_state.py` proved the plumbing (the scan schedule with a per-step
all_gather election); this module promotes the invoker axis to a
PRODUCTION device mesh the balancer can run at 100k-1M invokers:

  * `make_fleet_mesh`        — the `('fleet',)` mesh (power-of-two shard
                               count so pow2 invoker pads always divide).
  * `make_fleet_repair_schedule`
                             — the speculate-and-repair kernel shard_map'd
                               over the mesh. Each round, every shard
                               speculates its LOCAL [B, n_local] probe
                               slice, one tiny all_gather per round elects
                               the global winners, and a psum-masked
                               exchange reads the winning cells' occupancy
                               (free_mb / conc permits) from their owner
                               shards — the "global-occupancy exchange".
                               The conflict rules are THE shared
                               `repair_commit_masks` (one copy with the
                               XLA and Pallas kernels, so the three
                               production kernels cannot drift); they run
                               replicated in B-space on every shard, so
                               pending/round control flow stays identical
                               across shards and to the single-device
                               kernel — bit-exact decisions, books, AND
                               round counts (the parity fuzz asserts it).
  * `make_fleet_release_vector`
                             — the vectorized release fold, owner-masked:
                               every shard runs the replicated group-by
                               math and applies only the rows whose
                               invoker it owns. Same-invoker rows always
                               land on one shard, so the sequential
                               semantics argument of `release_batch_vector`
                               carries over unchanged. No collectives.
  * `fleet_pair`             — the mesh's (schedule, release) pair for
                               one algorithm, scan | repair; which one
                               runs is loadbalancer/kernel_choice.py's
                               choice, so the placementKernel knob means
                               the same thing on a mesh as on one device.

Why the collectives are cheap: per repair round the wire traffic is ONE
[B, 2] all_gather (winner election) plus three [B] psums (occupancy
exchange) — a few KB riding ICI — while the [B, n_local] probe math stays
shard-local. Fleet capacity therefore scales with chips; the single
device's HBM bounds only n_local = n_pad / n_shards.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.placement import (PlacementState, RequestBatch, _mulmod,
                             flat_prims, release_batch_vector,
                             repair_commit_masks)
from .sharded_state import (make_mesh, make_sharded_release,
                            make_sharded_schedule, shard_state)

#: the production mesh axis name (sharded_state's prototype used "inv")
FLEET_AXIS = "fleet"


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def make_fleet_mesh(n_shards: Optional[int] = None,
                    axis: str = FLEET_AXIS) -> Mesh:
    """Mesh over the `('fleet',)` axis. `n_shards=None` takes every
    visible device, rounded DOWN to a power of two: the balancer pads the
    invoker axis to powers of two, and `shard_state` needs the pad to
    divide evenly over the shards — a 6-device mesh would make every pow2
    pad indivisible. More shards than the default backend has devices
    raises (`make_mesh`)."""
    avail = len(jax.devices())
    if not n_shards:  # None OR 0 both mean "all devices, pow2-floored"
        want = _pow2_floor(max(1, avail))
    elif _pow2_floor(n_shards) != n_shards:
        raise ValueError(f"fleet shard count must be a power of two "
                         f"(pow2 invoker pads must divide evenly), "
                         f"got {n_shards}")
    else:
        want = n_shards
    return make_mesh(want, axis=axis)


def mesh_axis(mesh: Mesh) -> str:
    return mesh.axis_names[0]


def mesh_shards(mesh: Mesh) -> int:
    return int(mesh.shape[mesh_axis(mesh)])


def mesh_topology(mesh: Optional[Mesh]) -> dict:
    """The topology record stamped into the journal / snapshot / admin
    planes (a replayer on a different topology must cold-start, not
    silently mis-shard)."""
    if mesh is None:
        return {"n_shards": 1, "axis": None}
    return {"n_shards": mesh_shards(mesh), "axis": mesh_axis(mesh),
            "platform": mesh.devices.flat[0].platform}


def make_fleet_repair_schedule(mesh: Mesh, axis: Optional[str] = None,
                               penalized: bool = False):
    """The speculate-and-repair schedule over the fleet mesh — bit-exact
    `schedule_batch_repair` semantics (state, chosen, forced, warm, rounds;
    `use_conc` is already replicated, it reads the psum'd permit) with
    the [B, N] probe sharded to [B, n_local] per device.

    Exactness argument, per round:
      * speculation — each shard computes its local slice of exactly the
        arrays the single-device kernel computes ([B, n_local] eligibility
        and ranks over the same loop-invariant geometry); the all_gather
        election picks the lexicographic (key, global index) minimum,
        which IS what a single-device argmin (first index achieving the
        min) returns over the concatenated axis. The forced-placement
        candidate is elected once, outside the loop, the same way.
      * occupancy exchange — `free_mb[sel]` and the conc permit at
        (sel, slot) live on exactly one owner shard; a psum of the
        owner-masked value (zeros elsewhere) reproduces the single-device
        gather bit-for-bit (integer psum, one non-zero term). `col_conc`
        (any consumable permit on my column) is a psum-of-any over the
        local slices.
      * conflict rules — `repair_commit_masks` consumes only replicated
        [B]-space vectors, so every shard derives identical safe/commit
        masks; `pending` evolves identically on all shards and identically
        to the single-device kernel, which is why round counts match and
        the while_loop stays coherent across the mesh.
      * commit — owner-masked scatter-adds (zero deltas elsewhere; a
        zero add at a clipped index is a no-op).

    `penalized=True` builds the counterfactual variant: the returned fn
    takes a third argument, a global int32[N] penalty vector (sharded like
    the books), folded into the loop-invariant geometry as one probe-ring
    lap per level — the same seam the XLA/Pallas kernels thread, so all
    three families penalize identically. The sentinel grows to 2^30
    because augmented ranks can exceed n_total + 2.
    """
    axis = axis or mesh_axis(mesh)
    n_shards = mesh_shards(mesh)

    def _sharded(state: PlacementState, batch: RequestBatch, penalty=None):
        b = batch.valid.shape[0]
        prims = flat_prims(b)
        n_local = state.free_mb.shape[0]
        n_total = n_local * n_shards
        a_slots = state.conc_free.shape[1]
        off = jax.lax.axis_index(axis).astype(jnp.int32) * n_local
        big = jnp.int32(n_total + 2) if penalty is None else jnp.int32(1 << 30)

        # loop-invariant LOCAL geometry: this shard's slice of the
        # [B, N] rank/partition math (ops.placement._probe_geometry)
        gidx = off + jnp.arange(n_local, dtype=jnp.int32)
        local = gidx[None, :] - batch.offset[:, None]        # [B, n_local]
        size_col = batch.size[:, None]
        in_part = (local >= 0) & (local < size_col)
        size_safe = jnp.maximum(size_col, 1)
        rank = _mulmod(local - batch.home[:, None], batch.step_inv[:, None],
                       size_safe)
        if penalty is not None:
            rank = rank + penalty[None, :] * size_safe
        usable = in_part & state.health[None, :]

        def _elect(key_loc):
            """Local [B, n_local] keys -> globally elected (min key,
            owning global index) per request: local argmin, then ONE
            [B, 2] all_gather and a lexicographic (key, index) min —
            the single-device first-index-of-min semantics."""
            a = jnp.argmin(key_loc, axis=1)
            my_key = jnp.take_along_axis(key_loc, a[:, None], 1)[:, 0]
            my_idx = off + a.astype(jnp.int32)
            allv = jax.lax.all_gather(
                jnp.stack([my_key, my_idx], axis=-1), axis)  # [S, B, 2]
            kmin = jnp.min(allv[:, :, 0], axis=0)
            idx = jnp.min(jnp.where(allv[:, :, 0] == kmin[None, :],
                                    allv[:, :, 1], big), axis=0)
            return kmin, idx

        # the forced path is loop-invariant (capacity-blind, health fixed
        # inside a batch): elect the global forced candidate once
        fkey = jnp.where(usable, jnp.mod(local - batch.rand[:, None],
                                         size_safe), big)
        fmin, fbest = _elect(fkey)
        have_usable = fmin < big
        simple = batch.max_conc <= 1

        def cond(carry):
            _, _, pending, _, _, _, rounds = carry
            return jnp.any(pending) & (rounds <= b)

        def body(carry):
            free, conc, pending, chosen, forced_acc, warm_acc, rounds = carry
            conc_bn = conc[:, batch.conc_slot].T             # [B, n_local]
            has_conc = conc_bn > 0
            eligible = usable & (has_conc
                                 | (free[None, :] >= batch.need_mb[:, None]))
            kmin, choice = _elect(jnp.where(eligible, rank, big))
            found = kmin < big
            sel = jnp.where(found, choice, fbest)
            placed = batch.valid & (found | have_usable)
            forced = batch.valid & ~found & have_usable

            # global-occupancy exchange: the winning cell's books live on
            # one owner shard — psum the owner-masked reads
            lsel = jnp.clip(sel - off, 0, n_local - 1)
            mine = (sel >= off) & (sel < off + n_local)
            conc_at_sel = jax.lax.psum(
                jnp.where(mine,
                          jnp.take_along_axis(conc_bn, lsel[:, None],
                                              1)[:, 0], 0), axis)
            free_at_sel = jax.lax.psum(jnp.where(mine, free[lsel], 0), axis)
            use_conc = placed & (conc_at_sel > 0)
            take_mem = placed & ~use_conc
            col_conc = jax.lax.psum(
                jnp.any(usable & has_conc, axis=1).astype(jnp.int32),
                axis) > 0

            # THE shared conflict rules (ops.placement.repair_commit_masks)
            # over replicated [B] vectors: identical on every shard
            safe, commit = repair_commit_masks(
                prims, pending=pending, placed=placed, forced=forced,
                sel=sel, take_mem=take_mem, use_conc=use_conc,
                simple=simple, need_mb=batch.need_mb,
                conc_slot=batch.conc_slot, free_at_sel=free_at_sel,
                col_conc=col_conc, n=n_total, a_slots=a_slots)

            # owner-masked commit (zero adds elsewhere are no-ops)
            dmem = jnp.where(commit & take_mem & mine, batch.need_mb, 0)
            free = free.at[lsel].add(-dmem.astype(jnp.int32))
            conc_delta = jnp.where(
                commit & use_conc & mine, -1,
                jnp.where(commit & take_mem & ~simple & mine,
                          batch.max_conc - 1, 0))
            conc = conc.at[lsel, batch.conc_slot].add(
                conc_delta.astype(jnp.int32))
            chosen = jnp.where(safe, jnp.where(placed, sel, jnp.int32(-1)),
                               chosen)
            forced_acc = forced_acc | (safe & forced)
            warm_acc = warm_acc | (safe & use_conc)
            return (free, conc, pending & ~safe, chosen, forced_acc,
                    warm_acc, rounds + 1)

        free, conc, _, chosen, forced, warm, rounds = jax.lax.while_loop(
            cond, body,
            (state.free_mb, state.conc_free, batch.valid,
             jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), bool),
             jnp.zeros((b,), bool), jnp.int32(0)))
        return PlacementState(free, conc, state.health), chosen, forced, \
            warm, rounds

    state_spec = PlacementState(P(axis), P(axis, None), P(axis))
    batch_spec = RequestBatch(*([P()] * 9))
    if penalized:
        fn = shard_map(_sharded, mesh=mesh,
                       in_specs=(state_spec, batch_spec, P(axis)),
                       out_specs=(state_spec, P(), P(), P(), P()),
                       check_vma=False)
    else:
        fn = shard_map(lambda s, b: _sharded(s, b), mesh=mesh,
                       in_specs=(state_spec, batch_spec),
                       out_specs=(state_spec, P(), P(), P(), P()),
                       check_vma=False)
    return jax.jit(fn)


def make_fleet_release_vector(mesh: Mesh, axis: Optional[str] = None):
    """Owner-masked `release_batch_vector` over the mesh. Each shard runs
    the full (replicated) group-by-(invoker, slot) math with rows it does
    not own masked invalid; a group's rows all share one invoker, hence
    one shard, so within-group batch order — the only order that matters
    (see release_batch_vector's exactness argument) — is preserved
    locally. The heterogeneous-conflation residue loop runs per shard
    over its own rows only (no collectives in the body, so divergent
    trip counts across shards are fine)."""
    axis = axis or mesh_axis(mesh)

    def _sharded(state: PlacementState, inv, slot, need_mb, max_conc, valid):
        n_local = state.free_mb.shape[0]
        off = jax.lax.axis_index(axis).astype(jnp.int32) * n_local
        mine = valid & (inv >= off) & (inv < off + n_local)
        linv = jnp.clip(inv - off, 0, n_local - 1)
        return release_batch_vector(state, linv, slot, need_mb, max_conc,
                                    mine)

    state_spec = PlacementState(P(axis), P(axis, None), P(axis))
    fn = shard_map(_sharded, mesh=mesh,
                   in_specs=(state_spec, P(), P(), P(), P(), P()),
                   out_specs=state_spec, check_vma=False)
    return jax.jit(fn)


def fleet_pair(mesh: Mesh, algorithm: str, axis: Optional[str] = None):
    """(schedule_fn, release_fn, algorithm) of the fleet mesh: "repair" is
    the sharded speculate-and-repair pair, "scan" the prototype scan pair
    (sharded_state). Both are bit-exact with each other and with the
    single-device kernels. Which of them a balancer runs, and the
    per-bucket hybrid over the two, is
    controller/loadbalancer/kernel_choice.py's to decide."""
    axis = axis or mesh_axis(mesh)
    if algorithm == "scan":
        return (make_sharded_schedule(mesh, axis=axis),
                make_sharded_release(mesh, axis=axis), "scan")
    if algorithm == "repair":
        return (make_fleet_repair_schedule(mesh, axis=axis),
                make_fleet_release_vector(mesh, axis=axis), "repair")
    raise ValueError(f"algorithm must be scan|repair, got {algorithm!r}")


__all__ = ["FLEET_AXIS", "make_fleet_mesh", "mesh_axis", "mesh_shards",
           "mesh_topology", "make_fleet_repair_schedule",
           "make_fleet_release_vector", "fleet_pair", "shard_state",
           "make_mesh"]
