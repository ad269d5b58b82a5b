"""Sharded placement: invoker axis split over a device mesh.

Layout: PlacementState.free_mb/health are sharded on the "inv" mesh axis,
conc_free on ("inv", None); the request batch is replicated. Each scan step:
  1. every device reduces its local shard to (best probe-rank, its global
     index) plus the forced-placement fallback candidate,
  2. one all_gather of those 4 scalars per device elects the global winner
     (the collective is tiny and rides ICI),
  3. only the owning device applies the capacity update (masked scatter),
     and only it knows the row's warm bit (`use_conc`); one psum over the
     finished [B] column after the scan replicates it.
This preserves the exact sequential semantics of the single-device kernel —
and therefore of the reference's one-at-a-time scheduler — at any shard
count, which the parity tests assert on an 8-way virtual mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.placement import PlacementState, RequestBatch, _mulmod


def make_mesh(n_devices: Optional[int] = None, axis: str = "inv") -> Mesh:
    """Mesh over the default backend's devices. Asking for more devices
    than it has raises: a mesh quietly built from another backend's
    devices (the virtual CPU ones) would serve from the host while
    reporting a device mesh."""
    devices = jax.devices()
    want = n_devices or len(devices)
    if len(devices) < want:
        raise ValueError(
            f"mesh needs {want} devices, the {jax.default_backend()} "
            f"backend has {len(devices)}")
    return Mesh(devices[:want], (axis,))


def shard_state(state: PlacementState, mesh: Mesh, axis: Optional[str] = None
                ) -> PlacementState:
    """Place the state arrays with the invoker axis sharded over the mesh.
    `axis=None` infers the mesh's (single) axis name, so the same call
    works for the prototype "inv" meshes and the production "fleet" ones."""
    axis = axis or mesh.axis_names[0]
    n = state.free_mb.shape[0]
    assert n % mesh.shape[axis] == 0, \
        f"invoker padding {n} must divide evenly over {mesh.shape[axis]} shards"
    sh1 = NamedSharding(mesh, P(axis))
    sh2 = NamedSharding(mesh, P(axis, None))
    return PlacementState(jax.device_put(state.free_mb, sh1),
                          jax.device_put(state.conc_free, sh2),
                          jax.device_put(state.health, sh1))


def make_sharded_schedule(mesh: Mesh, axis: str = "inv"):
    """Build the jitted sharded schedule_batch for this mesh."""
    n_shards = mesh.shape[axis]

    def _local_body(state: PlacementState, req, shard_offset, n_total):
        offset, size, home, step_inv, need, slot, max_conc, rand, valid = req
        n_local = state.free_mb.shape[0]
        big = jnp.int32(n_total + 2)
        bigidx = jnp.int32(n_total + 2)

        gidx = shard_offset + jnp.arange(n_local, dtype=jnp.int32)
        local = gidx - offset
        in_part = (local >= 0) & (local < size)
        size_safe = jnp.maximum(size, 1)
        rank = _mulmod(local - home, step_inv, size_safe)

        conc_col = jax.lax.dynamic_index_in_dim(state.conc_free, slot, axis=1,
                                                keepdims=False)
        eligible = in_part & state.health & ((conc_col > 0) | (state.free_mb >= need))
        key = jnp.where(eligible, rank, big)
        a = jnp.argmin(key)
        my_best = (key[a], gidx[a])

        usable = in_part & state.health
        fkey = jnp.where(usable, jnp.mod(local - rand, size_safe), big)
        fa = jnp.argmin(fkey)
        my_forced = (fkey[fa], gidx[fa])

        # one tiny all_gather elects the global winner
        packed = jnp.stack([my_best[0], my_best[1], my_forced[0], my_forced[1]])
        allv = jax.lax.all_gather(packed, axis)  # [n_shards, 4]
        bkeys, bidx, fkeys, fidx = allv[:, 0], allv[:, 1], allv[:, 2], allv[:, 3]
        # winner = lexicographic min over (key, global index)
        best_key = jnp.min(bkeys)
        best_idx = jnp.min(jnp.where(bkeys == best_key, bidx, bigidx))
        found = best_key < big
        fbest_key = jnp.min(fkeys)
        fbest_idx = jnp.min(jnp.where(fkeys == fbest_key, fidx, bigidx))
        have_usable = fbest_key < big

        sel = jnp.where(found, best_idx, fbest_idx)
        placed = valid & (found | have_usable)
        forced = valid & ~found & have_usable

        # owner-masked update
        lsel = jnp.clip(sel - shard_offset, 0, n_local - 1)
        mine = (sel >= shard_offset) & (sel < shard_offset + n_local)
        sel_conc = conc_col[lsel] > 0
        use_conc = placed & mine & sel_conc
        take_mem = placed & mine & ~sel_conc
        free_mb = state.free_mb.at[lsel].add(
            jnp.where(take_mem, -need, 0).astype(jnp.int32))
        conc_delta = jnp.where(use_conc, -1,
                               jnp.where(take_mem & (max_conc > 1),
                                         max_conc - 1, 0))
        conc_free = state.conc_free.at[lsel, slot].add(conc_delta.astype(jnp.int32))
        new_state = PlacementState(free_mb, conc_free, state.health)
        return new_state, (jnp.where(placed, sel, -1), forced, use_conc)

    def _sharded(state: PlacementState, batch: RequestBatch):
        n_local = state.free_mb.shape[0]  # inside shard_map: local shape
        shard_offset = jax.lax.axis_index(axis).astype(jnp.int32) * n_local
        n_total = n_local * n_shards
        reqs = (batch.offset, batch.size, batch.home, batch.step_inv,
                batch.need_mb, batch.conc_slot, batch.max_conc, batch.rand,
                batch.valid)
        new_state, (chosen, forced, warm_mine) = jax.lax.scan(
            lambda s, r: _local_body(s, r, shard_offset, n_total), state, reqs)
        warm = jax.lax.psum(warm_mine.astype(jnp.int32), axis) > 0
        return new_state, chosen, forced, warm

    state_spec = PlacementState(P(axis), P(axis, None), P(axis))
    batch_spec = RequestBatch(*([P()] * 9))
    fn = shard_map(_sharded, mesh=mesh,
                   in_specs=(state_spec, batch_spec),
                   out_specs=(state_spec, P(), P(), P()),
                   check_vma=False)
    return jax.jit(fn)


def make_sharded_release(mesh: Mesh, axis: str = "inv"):
    """Jitted sharded release: owner-shard-masked updates, no collectives."""

    def _local(state: PlacementState, rel, shard_offset):
        inv, slot, need, max_conc, valid = rel
        n_local = state.free_mb.shape[0]
        mine = valid & (inv >= shard_offset) & (inv < shard_offset + n_local)
        linv = jnp.clip(inv - shard_offset, 0, n_local - 1)
        simple = mine & (max_conc <= 1)
        conc_val = state.conc_free[linv, slot] + 1
        reduced = mine & (max_conc > 1) & (conc_val >= max_conc)
        conc_delta = jnp.where(mine & (max_conc > 1),
                               jnp.where(reduced, 1 - max_conc, 1), 0)
        free_delta = jnp.where(simple | reduced, need, 0)
        return PlacementState(
            state.free_mb.at[linv].add(free_delta.astype(jnp.int32)),
            state.conc_free.at[linv, slot].add(conc_delta.astype(jnp.int32)),
            state.health), ()

    def _sharded(state: PlacementState, inv, slot, need, max_conc, valid):
        n_local = state.free_mb.shape[0]
        shard_offset = jax.lax.axis_index(axis).astype(jnp.int32) * n_local
        new_state, _ = jax.lax.scan(
            lambda s, r: _local(s, r, shard_offset), state,
            (inv, slot, need, max_conc, valid))
        return new_state

    state_spec = PlacementState(P(axis), P(axis, None), P(axis))
    fn = shard_map(_sharded, mesh=mesh,
                   in_specs=(state_spec, P(), P(), P(), P(), P()),
                   out_specs=state_spec, check_vma=False)
    return jax.jit(fn)
