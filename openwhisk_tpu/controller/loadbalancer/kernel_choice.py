"""Which program a fused step runs, decided in one place.

A placement step has a BACKEND (the XLA kernels of ops/placement.py, the
VMEM-resident Pallas kernels of ops/placement_pallas.py, or the shard_map'd
kernels of parallel/fleet_mesh.py) and an ALGORITHM (the reference
lax.scan, or speculate-and-repair). Every combination is bit-exact with
every other (the fuzz suites assert it), so the choice moves compile and
run cost, never placements. `choose` makes it from what the code can
observe: the platform, the geometry, the VMEM budget, whether there is a
mesh. The two knobs it takes are pins: tests hold the scan as the
reference, the benchmark's configurations pass `kernel`.

  mesh                               -> sharded
  kernel="xla"                       -> xla
  kernel="pallas"                    -> pallas while it fits VMEM, else xla
                                        (chosen_by="fallback")
  kernel="auto", on a TPU            -> as kernel="pallas", chosen_by="static"
  kernel="auto", elsewhere           -> xla (Pallas has only interpret mode
                                        there: a debugging path)

  placement_kernel="scan" | "repair" -> that algorithm at every bucket
  placement_kernel="auto"            -> per bucket: scan below
                                        REPAIR_MIN_BATCH, repair from it on;
                                        on Pallas the VMEM scan alone where
                                        the repair kernel's scratch does not
                                        fit beside the state

Which of Pallas and XLA is faster where both fit is not measured.

This module imports ops/ and parallel/, both below it, and nothing of the
balancer; nothing in ops/ or parallel/ imports it.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax

from ...ops.placement import (PlacementState, release_batch,
                              release_batch_vector, schedule_batch,
                              schedule_batch_repair)

#: batch-bucket width from which placement_kernel="auto" swaps the scan
#: program for the speculate-and-repair kernel (every backend). Below it
#: the scan both EXECUTES fine (a handful of sequential probe steps) and
#: COMPILES ~3x faster (~0.45 s vs ~1.2 s per bucket signature on a dev
#: box) — and compile latency is what light traffic actually feels, since
#: a new bucket shape jit-compiles inside a live dispatch. At and above it
#: the scan's B-length dependency chain dominates and repair wins outright.
REPAIR_MIN_BATCH = 32


class KernelPlan(NamedTuple):
    """What `choose` decided, and the functions that run it."""
    backend: str                  # xla | pallas | sharded
    algorithm: str                # scan | repair (repair for the hybrid)
    schedule: Callable            # (state, batch) -> (state, chosen, ...)
    release: Callable             # (state, inv, slot, need_mb, maxc, valid)
    #: the penalised twin of the same kernel family (state, batch, penalty):
    #: the quality plane's shadow step runs it, so a divergence measures the
    #: penalty, not a swap of kernels
    shadow_schedule: Callable
    chosen_by: str                # explicit | static | fallback
    #: why Pallas, on offer, was not taken whole: "vmem_fallback" (nothing
    #: fits: XLA), "scratch_evicted" (the state fits, the repair kernel's
    #: scratch does not: the VMEM scan). None otherwise. A balancer whose
    #: geometry grows through either records it with the swap.
    why: Optional[str] = None


def per_bucket(scan_pair, repair_pair, threshold: int = REPAIR_MIN_BATCH):
    """The hybrid (schedule, release) over a backend's scan pair and its
    repair pair: batch and release widths are static per jit signature, so
    the branch resolves at trace time and each compiled program contains
    exactly one kernel — scan below `threshold`, repair at and above it."""
    sched_scan, rel_scan = scan_pair
    sched_repair, rel_repair = repair_pair

    def auto_schedule(state, batch):
        if batch.valid.shape[0] >= threshold:
            return sched_repair(state, batch)
        return sched_scan(state, batch)

    def auto_release(state, inv, slot, need_mb, max_conc, valid):
        if inv.shape[0] >= threshold:
            return rel_repair(state, inv, slot, need_mb, max_conc, valid)
        return rel_scan(state, inv, slot, need_mb, max_conc, valid)

    auto_schedule._placement_hybrid = True
    auto_release._placement_hybrid = True
    return auto_schedule, auto_release


def _pick(placement_kernel: str, scan_pair, repair_pair):
    """(schedule_fn, release_fn, algorithm) of one backend for the
    placement-kernel pin; "auto" reports "repair", what its loaded buckets
    run."""
    if placement_kernel == "scan":
        return (*scan_pair, "scan")
    if placement_kernel == "repair":
        return (*repair_pair, "repair")
    return (*per_bucket(scan_pair, repair_pair), "repair")


def xla_pair(placement_kernel: str):
    """The XLA backend: the reference lax.scan pair, or the speculate-and-
    repair schedule with the vectorized release fold."""
    return _pick(placement_kernel, (schedule_batch, release_batch),
                 (schedule_batch_repair, release_batch_vector))


def pallas_pair(placement_kernel: str):
    """The Pallas backend. "scan" is the VMEM-resident sequential kernel;
    "repair" is the fused speculate-and-repair kernel
    (`schedule_batch_repair_pallas`) — probe + conflict detect + commit +
    the residue loop in ONE pallas_call with the books resident in VMEM,
    sharing the conflict rules with the XLA kernel so the two cannot
    drift. The kernel layout is conc-transposed; state everywhere else
    stays [N, A] — converting inside jit keeps both transposes on-device in
    the same program as the kernel call. The release fold is the XLA
    pair's (it fuses into the same program around the pallas call)."""
    from ...ops.placement_pallas import (schedule_batch_pallas,
                                         schedule_batch_repair_pallas,
                                         to_transposed)
    interpret = jax.default_backend() == "cpu"

    @jax.jit
    def sched_scan(st, batch):
        ts, *out = schedule_batch_pallas(
            to_transposed(st), batch, interpret=interpret)
        return (PlacementState(ts.free_mb, ts.conc_free.T, ts.health),
                *out)

    @jax.jit
    def sched_repair(st, batch):
        ts, *out = schedule_batch_repair_pallas(
            to_transposed(st), batch, interpret=interpret)
        return (PlacementState(ts.free_mb, ts.conc_free.T, ts.health),
                *out)

    sched_scan._pallas_kind = "scan"
    sched_repair._pallas_kind = "repair"
    sched, release, algorithm = _pick(
        placement_kernel, (sched_scan, release_batch),
        (sched_repair, release_batch_vector))
    if placement_kernel == "auto":
        sched._pallas_kind = "auto"
    return sched, release, algorithm


def _pallas_shadow(algorithm: str):
    from ...ops.placement_pallas import (schedule_batch_pallas,
                                         schedule_batch_repair_pallas,
                                         to_transposed)
    interpret = jax.default_backend() == "cpu"
    fn = (schedule_batch_repair_pallas if algorithm == "repair"
          else schedule_batch_pallas)

    def sched(st, batch, penalty):
        # the transposed result state is dead in the shadow program
        # (decisions only) — XLA drops the transposes
        return fn(to_transposed(st), batch, interpret=interpret,
                  penalty=penalty)

    return sched


def pallas_fit(n_pad: int, action_slots: int, max_batch: int,
               placement_kernel: str) -> Optional[str]:
    """What the Pallas backend can run at a geometry: "repair" (state + the
    repair kernel's residue scratch fit VMEM), "scan" (only the resident
    state fits — placement_kernel="auto" takes the VMEM scan, which needs
    no [B, N] scratch), or None (nothing fits). A pinned
    placement_kernel="repair" never becomes the Pallas scan."""
    from ...ops.placement_pallas import fits_vmem, fits_vmem_repair
    if placement_kernel != "scan" and fits_vmem_repair(n_pad, action_slots,
                                                       max_batch):
        return "repair"
    if placement_kernel != "repair" and fits_vmem(n_pad, action_slots):
        return "scan"
    return None


def choose(n_pad: int, action_slots: int, max_batch: int, *,
           kernel: str = "auto", placement_kernel: str = "auto",
           mesh=None, axis: Optional[str] = None) -> KernelPlan:
    """The plan for a geometry (the module doc has the rule as a table).
    Pure but for what it reads of the platform: `jax.default_backend()`
    and the VMEM budget of the running device."""
    chosen_by = "explicit" if kernel != "auto" else "static"
    if mesh is not None:
        # parallel/ may not import upward, so the hybrid over the mesh's
        # two pairs is built here like the other backends'
        from ...parallel.fleet_mesh import (fleet_pair,
                                            make_fleet_repair_schedule)
        sched, release, algorithm = _pick(
            placement_kernel, fleet_pair(mesh, "scan", axis=axis)[:2],
            fleet_pair(mesh, "repair", axis=axis)[:2])
        # every pair is bit-exact with every other, so the mesh shadow is
        # always the penalised sharded repair kernel
        return KernelPlan("sharded", algorithm, sched, release,
                          make_fleet_repair_schedule(mesh, axis=axis,
                                                     penalized=True),
                          chosen_by)
    why = None
    if kernel == "pallas" or (kernel == "auto"
                              and jax.default_backend() == "tpu"):
        fit = pallas_fit(n_pad, action_slots, max_batch, placement_kernel)
        if fit is not None:
            if fit == "scan" and placement_kernel == "auto":
                placement_kernel, why = "scan", "scratch_evicted"
            sched, release, algorithm = pallas_pair(placement_kernel)
            return KernelPlan("pallas", algorithm, sched, release,
                              _pallas_shadow(algorithm), chosen_by, why)
        why = "vmem_fallback"
        if kernel == "pallas":
            chosen_by = "fallback"
    sched, release, algorithm = xla_pair(placement_kernel)
    return KernelPlan("xla", algorithm, sched, release,
                      schedule_batch_repair if algorithm == "repair"
                      else schedule_batch, chosen_by, why)
