"""TpuBalancer: placement decisions computed on TPU.

The north-star component (BASELINE.json): a LoadBalancerProvider whose
scheduling inner loop — the reference's per-activation CPU probe walk
(ShardingContainerPoolBalancer.schedule) — runs as a vectorized device
kernel over the live fleet state:

  publish() ──> micro-batch buffer ──┐ (flush at max_batch, or after the
                                     │  dispatch hold / batch_window)
  completion acks ──> release buffer ┤
  health transitions ─> health buffer┤
                                     ▼
            one device step: release_batch ∘ set_health ∘ schedule_batch
                                     │
             assignments ──> ActivationMessage dispatch over the bus

Design notes (SURVEY §7 "hard parts"):
  - batching vs latency: a partly filled batch is held for
    DISPATCH_HOLD_K x the loop time a fused step is measured to cost
    (under arrival pressure), else `batch_window` (default 2 ms), or until
    `max_batch` queue; dispatch is loop-serialized, which keeps ordering
    and lets the next batch fill while one computes.
  - host<->device coherence: acks and health flips never touch device state
    directly — they buffer host-side and fold in at the next step boundary
    (double-buffered deltas), so the kernel never races its own state.
  - dynamic fleets: arrays are padded to powers of two; fleet growth re-pads
    (a rare recompile) while health flips are O(1) device updates.
  - intra-batch contention: lax.scan preserves the reference's sequential
    read-modify-write semantics exactly (see ops/placement.py).

Fleet partitioning, hashing, coprime steps and cluster-share division all
reuse the CPU policy's formulas (models.sharding_policy) so the kernel stays
bit-for-bit parity-testable against the oracle.
"""
from __future__ import annotations

import asyncio
import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.entity import ExecutableWhiskAction, InvokerInstanceId
from ...messaging.message import ActivationMessage
from ...models.sharding_policy import (MIN_SLOT_MB, generate_hash,
                                       pairwise_coprimes)
from ...ops.anomaly import S_EWMA_MS, S_STRAGGLER_FLAG
from ...ops.decision_quality import (S_DIVERGENT, S_IMBALANCE_COV,
                                     S_REGRET_SUM_MS, init_quality_state)
from ...ops.placement import (PlacementState, RequestBatch, init_state,
                              make_fused_admit_step_packed,
                              make_fused_step_packed, make_release_packed,
                              make_shadow_admit_step_packed,
                              make_shadow_step_packed,
                              journal_words, set_health,
                              unpack_step_output)
from .journal import decode_array, encode_array
from .kernel_choice import KernelPlan, choose
from ...ops.throttle import init_buckets
from ...utils.config import device_info, load_config
from ...utils.eventlog import GLOBAL_EVENT_LOG
from ...utils.ring_buffer import ColumnRing
from ...messaging.coalesce import export_coalesce_gauges
from ...messaging.tcp import export_bus_gauges
from ...utils.hostprof import GLOBAL_HOST_OBSERVATORY
from ...utils.tracing import export_tracing_gauges, trace_id_of
from ...utils.waterfall import (STAGE_BATCH_ASSEMBLE, STAGE_DEVICE_DISPATCH,
                                STAGE_DEVICE_READBACK, STAGE_PUBLISH_ENQUEUE,
                                STAGE_SPILL_FORWARD, span)
from .base import (HEALTHY, CommonLoadBalancer, InvokerHealth,
                   LoadBalancerException, LoadBalancerThrottleException)
from .flight_recorder import (BatchRecord, free_slot_histogram,
                              occupancy_json)
from .supervision import InvokerPool


@dataclass(frozen=True)
class PlacementPathConfig:
    """`CONFIG_whisk_loadBalancer_*` hot-path knobs (constructor arguments
    override the env). `placement_kernel` and `kernel` are PINS on what
    kernel_choice.choose would pick from what it observes (its module doc
    has the rule): the tests hold the scan as the reference, the
    benchmark's configurations pass `kernel`.

    placement_kernel: the BATCH ALGORITHM — "scan" (the reference lax.scan:
      sequential depth B), "repair" (speculate-and-repair: sequential depth
      ~ the intra-batch conflict count; bit-exact with the scan, see
      ops/placement.schedule_batch_repair), or "auto" (per bucket: scan
      below REPAIR_MIN_BATCH, repair from it on).
    kernel: the device BACKEND — "xla", "pallas" or "auto" (Pallas on a TPU
      while the state fits VMEM, else XLA). Orthogonal to placement_kernel;
      ignored on a fleet mesh.
    prewarm: compile successor bucket signatures ahead of traffic on a
      background drainer thread (see _prewarm_buckets). Off = every new
      bucket shape compiles synchronously inside a live dispatch — the
      right setting for latency-measurement harnesses that can't tolerate
      background-compile GIL hiccups.
    fleet_mesh: shard the invoker axis of the placement state over a
      ('fleet',) device mesh (parallel/fleet_mesh.py) — the horizontal-
      scale mode where fleet capacity grows with chips instead of one
      device's HBM. Per-shard speculate-and-repair with a per-round
      global-occupancy exchange; bit-exact with the single-device kernels
      at any shard count. Default OFF = the single-device path.
    fleet_shards: shard count for fleet_mesh (power of two; 0 = every
      visible device, rounded down to a power of two). Asking for more
      shards than the default backend has devices is an error.
    """
    placement_kernel: str = "auto"   # scan | repair | auto
    kernel: str = "auto"             # xla | pallas | auto
    prewarm: bool = True
    fleet_mesh: bool = False
    fleet_shards: int = 0


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _mod_inverse(step: int, m: int) -> int:
    return pow(step, -1, m) if m > 1 else 0


class _SlotAllocator:
    """Host-side collision-free action->concurrency-slot mapping (the inner
    NestedSemaphore level is dense on device; slots recycle when no
    in-flight activation references them).

    Saturation: the balancer grows the slot axis before this allocator ever
    runs dry (see TpuBalancer._ensure_slot_capacity); only past the hard cap
    does a key land in `overflow` — a stable CRC32-hashed slot (restart-safe,
    unlike builtin hash() under PYTHONHASHSEED) shared with whatever
    dedicated key owns it, refcounted so release stays balanced, and counted
    by the saturation metric so conflated pools are never silent."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self.slots: Dict[str, int] = {}
        self.refcount: Dict[str, int] = {}
        self.free: List[int] = list(range(n_slots - 1, -1, -1))
        #: key -> [slot, refcount]; the slot is pinned at first acquire so
        #: every in-flight activation of the key releases the slot it took,
        #: even if n_slots grows (which would move the CRC32 residue)
        self.overflow: Dict[str, List[int]] = {}

    def _stable_slot(self, key: str) -> int:
        return zlib.crc32(key.encode()) % self.n_slots

    @property
    def saturated(self) -> bool:
        return not self.free

    def needs_slot(self, key: str) -> bool:
        """Would acquiring `key` want a slot it doesn't own? (Overflowed keys
        count: their next acquire migrates to a dedicated slot if one is
        free.)"""
        return key not in self.slots

    def acquire(self, key: str) -> int:
        of = self.overflow.get(key)
        if of is not None and not self.free and key not in self.slots:
            of[1] += 1  # still capped: pile on the pinned shared slot
            return of[0]
        if key not in self.slots:
            if not self.free:
                slot = self._stable_slot(key)
                self.overflow[key] = [slot, 1]
                return slot
            # fresh key — or an overflowed key migrating now that capacity
            # freed (its old in-flight releases still land on the pinned
            # slot: every release carries the slot its acquire returned)
            self.slots[key] = self.free.pop()
        self.refcount[key] = self.refcount.get(key, 0) + 1
        return self.slots[key]

    def lookup(self, key: str) -> int:
        """Best-effort slot for `key` (fallback when a release arrives
        without its acquire-time slot, e.g. after a pre-upgrade snapshot)."""
        slot = self.slots.get(key)
        if slot is not None:
            return slot
        of = self.overflow.get(key)
        return of[0] if of is not None else self._stable_slot(key)

    def release(self, key: str, slot: Optional[int] = None) -> None:
        """Balance the acquire that returned `slot` (None = best guess)."""
        ded = self.slots.get(key)
        of = self.overflow.get(key)
        use_dedicated = (ded is not None and self.refcount.get(key, 0) > 0
                         and (slot is None or slot == ded or of is None))
        if not use_dedicated and of is not None:
            of[1] -= 1
            if of[1] <= 0:
                self.overflow.pop(key)
            return
        n = self.refcount.get(key, 0) - 1
        if n <= 0:
            self.refcount.pop(key, None)
            s = self.slots.pop(key, None)
            if s is not None:
                self.free.append(s)
        else:
            self.refcount[key] = n

    def grow(self, new_n: int) -> None:
        """Extend the slot axis (the balancer grew the device array to
        match). Existing assignments — including pinned overflow slots —
        stay put; only fresh capacity is added."""
        assert new_n > self.n_slots
        self.free = list(range(new_n - 1, self.n_slots - 1, -1)) + self.free
        self.n_slots = new_n


class TpuBalancer(CommonLoadBalancer):
    def __init__(self, messaging_provider, controller_instance, logger=None,
                 metrics=None, cluster_size: int = 1,
                 managed_fraction: float = 0.9, blackbox_fraction: float = 0.1,
                 batch_window: float = 0.002, max_batch: int = 256,
                 action_slots: int = 4096, max_action_slots: int = 65536,
                 initial_pad: int = 64, mesh=None,
                 kernel: Optional[str] = None,
                 pipeline_depth: int = 4,
                 rate_limit_per_minute: Optional[int] = None,
                 placement_kernel: Optional[str] = None,
                 donate_state: Optional[bool] = None,
                 prewarm: Optional[bool] = None,
                 fleet_mesh: Optional[bool] = None,
                 fleet_shards: Optional[int] = None,
                 profiler=None, anomaly=None, waterfall=None, quality=None):
        super().__init__(messaging_provider, controller_instance, logger,
                         metrics, profiler=profiler, anomaly=anomaly,
                         waterfall=waterfall, quality=quality)
        self._cluster_size = cluster_size
        #: {platform, device_kind, device_count} of the backend every device
        #: program below runs on — a non-TPU backend raises here unless
        #: JAX_PLATFORMS names cpu first (utils.config.check_device_platform);
        #: the boot banner and /admin/profile/kernel report it
        self.device = device_info()
        path_cfg = load_config(PlacementPathConfig, env_path="load_balancer")
        #: "auto" | "xla" | "pallas" (single-device backend knob)
        self.kernel = kernel if kernel is not None else path_cfg.kernel
        if self.kernel not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"kernel must be auto|xla|pallas, got {self.kernel!r}")
        #: scan | repair | auto — the batch algorithm
        self.placement_kernel = (placement_kernel if placement_kernel
                                 is not None else path_cfg.placement_kernel)
        if self.placement_kernel not in ("scan", "repair", "auto"):
            raise ValueError(
                f"placement_kernel must be scan|repair|auto, "
                f"got {self.placement_kernel!r}")
        #: donation is not a knob: `_build_packed_fns` decides it from what
        #: it observes (a device backend, no mesh). `donate_state=True` is
        #: the pin of the materialize-boundary tests: the donated path is
        #: production's on the chip, and the CPU twin cannot reach it
        #: otherwise.
        self._donate_pinned = donate_state is True
        self.prewarm = (prewarm if prewarm is not None
                        else path_cfg.prewarm)
        #: what runs (`_adopt_plan`): xla | pallas | sharded, scan | repair
        self.kernel_resolved: Optional[str] = None
        self.placement_kernel_resolved: Optional[str] = None
        #: how it was picked: "explicit" (the kernel knob), "static"
        #: (kernel_choice's rule) or "fallback" (Pallas outgrew its VMEM
        #: budget)
        self._kernel_chosen_by: Optional[str] = None
        #: pure-function memos on the publish hot path: (ns, fqn) -> crc32
        #: home hash and (step, size) -> modular inverse. Both are
        #: deterministic (never invalidated); bounded by a clear at 64k.
        self._hash_cache: Dict[tuple, int] = {}
        self._modinv_cache: Dict[tuple, int] = {}
        #: batched-publish send tasks — ONLY the raw-producer fallback
        #: mints these (the coalescing producer's send_nowait path is
        #: task-free; see _row_placed). close() drains them AFTER
        #: failing queued publishers, so every caller-facing future
        #: resolves before the producer goes away.
        self._publish_finishers: set = set()
        #: publish inter-arrival EWMA (ms) — the dispatch hold's pressure
        #: signal. Initialized sparse so a fresh balancer is eager.
        self._gap_ewma_ms = 1000.0
        self._last_gap_ms = 1e9
        self._last_pub_t = time.monotonic()
        #: what the last HOLD_SAMPLES fused steps cost the loop (s) and the
        #: hold derived from them (`_note_step_cost`). Zeros until that
        #: many steps have run: an unmeasured balancer holds nothing.
        self._step_costs = deque([0.0] * self.HOLD_SAMPLES,
                                 maxlen=self.HOLD_SAMPLES)
        self._hold_s = 0.0
        self.managed_fraction = managed_fraction
        self.blackbox_fraction = blackbox_fraction
        self.batch_window = batch_window
        self.max_batch = max_batch
        self.action_slots = action_slots
        self.max_action_slots = max(max_action_slots, action_slots)
        #: fleet-mesh mode (CONFIG_whisk_loadBalancer_fleetMesh): build the
        #: ('fleet',) mesh here unless the caller handed one in (the legacy
        #: mesh= constructor path keeps working; its axis name is adopted
        #: whatever it is). Default OFF = the single-device path, bit-exact.
        self.fleet_mesh = (fleet_mesh if fleet_mesh is not None
                           else path_cfg.fleet_mesh)
        if mesh is None and self.fleet_mesh:
            from ...parallel.fleet_mesh import make_fleet_mesh
            shards_cfg = (fleet_shards if fleet_shards is not None
                          else path_cfg.fleet_shards)
            mesh = make_fleet_mesh(shards_cfg or None)
        self.mesh = mesh
        #: mesh axis name and shard count (1 without a mesh) — the admin/
        #: occupancy planes and journal topology records read these
        self.fleet_axis = mesh.axis_names[0] if mesh is not None else None
        self.n_shards = (int(np.prod(list(mesh.shape.values())))
                         if mesh is not None else 1)
        #: opt-in bulk ACTIVATE admission ON DEVICE (ops.throttle token
        #: buckets fused into the placement step): per-namespace platform
        #: rate as a bus-boundary backstop. The HTTP front door's
        #: entitlement RateThrottler (with per-user overrides) remains the
        #: primary enforcement; this catches traffic that bypasses it
        #: (direct bus publishers, misconfigured edges).
        self.rate_limit_per_minute = rate_limit_per_minute
        self._ns_slots: Dict[str, int] = {}
        self._bucket_state = None
        self._t0_mono = time.monotonic()
        self._n_pad = max(initial_pad, self.n_shards)
        if mesh is not None:
            # power-of-two pad so the invoker axis always divides evenly
            # over the (power-of-two) shard count; single-device pads keep
            # the caller's exact value (bit-exact legacy behavior)
            self._n_pad = _next_pow2(self._n_pad)

        self._registry: List[InvokerInstanceId] = []
        self._healthy: List[bool] = []
        self._slots = _SlotAllocator(action_slots)
        self._rand_counter = 0

        self.state: Optional[PlacementState] = None
        self._sched_fn = None
        self._release_fn = None
        #: write-ahead placement journal (loadbalancer/journal.py): None =
        #: journaling off, the bit-exact legacy path. Every committed
        #: device-state mutation appends one record; a restored controller
        #: replays the tail on top of the snapshot (replay_journal).
        self.journal = None
        self._journal_seq = 0
        #: True while replay_journal re-applies records, so the re-applied
        #: mutations don't journal themselves again
        self._journal_mute = False
        #: a fleet-mesh writer stamps ONE `mesh` topology record ahead of
        #: its first append (per process / per promotion), so a replayer
        #: on a different device count cold-starts with a logged reason
        #: instead of silently mis-sharding
        self._journal_mesh_stamped = False
        #: cross-partition spillover (active/active only; spillover.py):
        #: with a sink attached, publish_many diverts its non-blocking
        #: overflow past `spillover_depth` pending rows to the
        #: least-loaded peer instead of deepening the local queue
        self.spillover_sink = None
        self.spillover_depth = 256
        self.spilled_rows = 0
        #: host numpy copy of free_mb from the last readback/state install —
        #: occupancy() serves from this, never the live device buffer.
        #: Installs are sequence-guarded: readback worker threads finish
        #: out of order under the pipeline, and last-writer-wins would let
        #: an older step's books stick until the next dispatch.
        self._books_cache: Optional[np.ndarray] = None
        self._books_seq = 0
        self._books_cache_seq = 0
        #: (books seq, first new row) of each registration that a step
        #: dispatched before it may still read back: that step read the
        #: rows unregistered, so its books get their capacity patched in
        self._reg_marks: List[Tuple[int, int]] = []
        #: placement-quality plane inputs, host-refreshed on the 1 Hz
        #: supervision tick from the anomaly plane's harvested scores:
        #: padded per-invoker cost (latency EWMA) and capacity vectors for
        #: the scorer, and the straggler-flag penalty for the shadow
        #: kernel (uploaded to device lazily, only when the flags change)
        self._quality_ewma_np = np.zeros(self._n_pad, np.float32)
        self._quality_caps_np = np.zeros(self._n_pad, np.int32)
        self._quality_ewma = None
        self._quality_caps = None
        self._shadow_penalty_np = np.zeros(self._n_pad, np.int32)
        self._shadow_penalty = None
        self._shadow_fn = None
        self._quality_batches = 0
        self._init_device_state()

        # pending request queue + delta buffers; the int fields mirror into
        # preallocated column rings at enqueue time so the per-flush packed
        # matrices assemble with two slice copies
        self._pending: List[tuple] = []      # (req_tuple, future, slot_key)
        self._releases: List[tuple] = []     # (inv_idx, slot, mem, maxc, key)
        self._req_ring = ColumnRing(10, max_batch * 4)
        self._rel_ring = ColumnRing(4, max_batch * 4)
        self._health_updates: Dict[int, bool] = {}
        self._flush_task: Optional[asyncio.Task] = None
        #: what start() froze and set ({frozen, thresholds}); None while
        #: this balancer holds no share of the collector's policy
        self.gc_tuned: Optional[dict] = None
        self._step_lock = asyncio.Lock()
        # device-step pipelining: dispatch is async (JAX returns future
        # arrays immediately), so batch N+1 can be dispatched while batch
        # N's readback is still crossing the wire — the counter bounds
        # in-flight readbacks (the event wakes waiters when one lands),
        # the task set tracks them for close()
        self.pipeline_depth = max(1, pipeline_depth)
        self._inflight_steps = 0
        self._capacity_free = asyncio.Event()
        self._readbacks: set = set()
        #: EWMA of the device readback round trip — picks the eager-vs-
        #: batching dispatch policy (slow round trips serialize; fast ones
        #: don't). Starts ABOVE the fast threshold: unknown counts as slow,
        #: because misclassifying a slow device as fast costs a serialized
        #: round trip while the reverse costs one event-loop tick.
        self._rtt_ewma_ms = 2 * self.RTT_FAST_MS

        # group is per-controller: every controller needs its OWN full view
        # of the ping stream (a shared group would split pings between
        # controllers; ref: each controller runs its own InvokerPool)
        self.supervision = InvokerPool(
            messaging_provider, on_status_changes=self._status_changes,
            logger=logger, group=f"health-{controller_instance.as_string}",
            on_tick=self._telemetry_tick)
        # advisory unhealthy hints from the anomaly plane land on the
        # supervision pool (pushed only when hintUnhealthy is configured)
        self.anomaly.hint_sink = self.supervision.set_unhealthy_hints
        # completion telemetry accumulates ON DEVICE for this balancer: the
        # buffered event rows fold into the accumulator as one scatter-add
        # per dispatch cycle (_dispatch_batch / idle _device_step)
        if self.telemetry.enabled:
            self.telemetry.use_device(self._n_pad)
        #: partition size -> its coprime probe steps (`_probe_steps`)
        self._steps_of: Dict[int, List[int]] = {}
        self._recompute_partitions()
        self._rebuild_caps()

    def _telemetry_tick(self) -> None:
        # the supervision watchdog also drains completion events that
        # arrived while no placement traffic was flowing (idle fleets must
        # still converge their device counts)
        self._telemetry_fold()
        self.telemetry.tick(self.metrics)
        # anomaly detection rides the same tick: the device program
        # dispatches now and its scores harvest NEXT tick (no device sync
        # on the event loop, same rule as the burn-rate math)
        self.anomaly.tick(self.metrics)
        # the quality plane rides the same cadence: refresh its cost/
        # penalty vectors from the scores the anomaly tick just harvested,
        # then its gauges (host aggregates only — no device sync)
        if self.quality.enabled:
            self._refresh_quality_signals()
            self.quality.tick(self.metrics)
        # HBM watermark gauges ride the same 1 Hz tick (guarded no-op on
        # backends without memory_stats, e.g. CPU)
        self.profiler.refresh_memory(self.metrics)
        export_tracing_gauges(self.metrics)
        # bus-client health rides the same cadence: coalescing batch sizes
        # and consumer reconnects (messaging/{coalesce,tcp}.py)
        export_coalesce_gauges(self.metrics)
        export_bus_gauges(self.metrics)
        # journal durability lag / size / fsync tail (HA plane) ride the
        # same 1 Hz cadence
        if self.journal is not None:
            self.journal.export_gauges(self.metrics)
        # fleet-mesh visibility: shard count + per-shard occupancy from
        # the cached books (host-side only)
        if self.mesh is not None:
            self._export_shard_gauges()

    # -- device state ------------------------------------------------------
    def _choose_plan(self) -> KernelPlan:
        return choose(self._n_pad, self.action_slots, self.max_batch,
                      kernel=self.kernel,
                      placement_kernel=self.placement_kernel,
                      mesh=self.mesh, axis=self.fleet_axis)

    def _adopt_plan(self, plan: KernelPlan, rebuild: bool = False) -> None:
        """Run what kernel_choice picked for the geometry the state now
        has. Called wherever the state is built or changes shape; a plan
        that names what runs already is a no-op unless `rebuild` (fresh
        state: fresh programs). A live balancer whose backend or algorithm
        changes (Pallas outgrew its VMEM budget through growth or a
        snapshot restore) swaps under an expect window, so the recompile
        watchdog reads the fresh programs' compiles as the swap they are,
        and says so in the event log. One that fell back to XLA for want
        of VMEM stays there for the life of the process: `self.kernel` is
        pinned, so no later plan offers Pallas again."""
        first = self.kernel_resolved is None
        changed = ((self.kernel_resolved, self.placement_kernel_resolved)
                   != (plan.backend, plan.algorithm))
        if first:
            self._kernel_chosen_by = plan.chosen_by
        elif changed:
            self.profiler.expect("kernel_swap")
            GLOBAL_EVENT_LOG.record(
                "kernel_swap", instance=self.controller.instance,
                to=(plan.backend if plan.backend != self.kernel_resolved
                    else f"{plan.backend}_{plan.algorithm}"),
                why=plan.why)
        if (plan.why == "vmem_fallback" and changed
                and (plan.chosen_by == "fallback" or not first)):
            # Pallas was running, or asked for by name, and does not fit
            if self.logger:
                self.logger.warn(
                    None, f"pallas kernel needs VMEM-resident state; "
                    f"{self._n_pad}x{self.action_slots} "
                    f"(placement_kernel={self.placement_kernel}, "
                    f"max_batch={self.max_batch}) does not fit — using the "
                    f"XLA kernel")
            self.kernel = plan.backend
            self._kernel_chosen_by = "fallback"
        if not (changed or rebuild):
            return
        self.kernel_resolved = plan.backend
        self.placement_kernel_resolved = plan.algorithm
        self._sched_fn, self._release_fn = plan.schedule, plan.release
        # release + health-fold + schedule as ONE compiled program (vs
        # three dispatches per micro-batch), fed through the transfer-packed
        # wrappers (3 host->device transfers per step instead of 16)
        self._build_packed_fns(plan.shadow_schedule)
        self._export_kernel_gauge()

    def _init_device_state(self) -> None:
        n = len(self._registry)
        slot_mb = [self._slot_mb(i.user_memory.to_mb) for i in self._registry]
        state = init_state(n or 1, slot_mb or [0], n_pad=self._n_pad,
                           action_slots=self.action_slots)
        health = jnp.zeros_like(state.health)
        if self._healthy:
            health = health.at[jnp.arange(len(self._healthy))].set(
                jnp.asarray(self._healthy, bool))
        state = state._replace(health=health)
        if self.mesh is not None:
            from ...parallel.sharded_state import shard_state
            state = shard_state(state, self.mesh, axis=self.fleet_axis)
        self.state = state
        self._adopt_plan(self._choose_plan(), rebuild=True)
        self._set_books_now(np.asarray(self.state.free_mb))
        # placement-quality plane: device accumulator + jitted scorer keyed
        # to the current invoker pad (a geometry rebuild restarts the
        # accumulated quality counts — different arrays, like the anomaly
        # plane's kernel swaps). Live state keeps conc in [N, A] on every
        # backend (the pallas pair transposes inside its own program), so
        # the scorer never needs the transposed layout here.
        if self.quality.enabled:
            self.quality.use_device(self._n_pad)
            self._refresh_quality_signals()

    #: the batch-shaped publish SPI (ISSUE 14), advertised to the front
    #: end: `maybe_batch_publish` builds a PublishCoalescer off it
    batch_publish = True

    def _build_packed_fns(self, shadow_sched_fn) -> None:
        """(Re)build the packed step, the release-only program and the
        shadow twin for the adopted schedule pair. The profiler interposes
        on every jitted entry point: compile events classify by first-call
        / expect-window / rebuild window / pow2-bucketed statics (the only
        shapes _bucket may produce) — anything else is shape churn and
        trips the recompile watchdog."""
        from ...ops.profiler import pow2_statics
        # buffer donation: XLA reuses the state's buffers for the output, so
        # the [N, A] concurrency matrix stops round-tripping HBM every step
        # (holders of the pre-call state must copy first, see
        # _materialize_state). Off on a mesh (sharded buffers stay owned by
        # their own path) and on the CPU backend: XLA:CPU cannot alias
        # donated buffers and runs the donated program SYNCHRONOUSLY at
        # dispatch — the event loop blocks for the whole step, the RTT EWMA
        # reads ~0 and flips the dispatch regime to eager micro-batches
        # (measured 5x rate loss on the CPU twin) — all cost, no HBM to
        # save.
        self._donate = self.mesh is None and (
            jax.default_backend() != "cpu" or self._donate_pinned)
        sched_fn, release_fn = self._sched_fn, self._release_fn
        if self.rate_limit_per_minute is not None:
            self._packed_fn = self.profiler.wrap(
                "fused_admit_step",
                make_fused_admit_step_packed(release_fn, sched_fn,
                                             donate=self._donate),
                expected=pow2_statics)
            # bucket state is SOFT (a rolling rate window, never
            # checkpointed) but it CARRIES across kernel swaps and growth
            # rebuilds — re-initializing here would grant every namespace a
            # fresh full burst whenever the fleet grows mid-minute
            if self._bucket_state is None:
                self._bucket_state = init_buckets(self.RATE_NS_BUCKETS,
                                                  self.rate_limit_per_minute)
        else:
            self._packed_fn = self.profiler.wrap(
                "fused_step",
                make_fused_step_packed(release_fn, sched_fn,
                                       donate=self._donate),
                expected=pow2_statics)
        self._release_packed_fn = self.profiler.wrap(
            "release_packed",
            make_release_packed(release_fn, donate=self._donate),
            expected=lambda st, rel: _next_pow2(rel.shape[1]) == rel.shape[1])
        # fn rebuild = fresh jit caches: everything needs re-warming (the
        # queue entries pin the fn they were enqueued for, so stale warms
        # drain harmlessly against the abandoned cache)
        self._warm_sigs = set()
        self._warm_queue = []
        self._warm_task = getattr(self, "_warm_task", None)
        # the decision-only shadow twin (quality plane) runs the penalised
        # variant of the PRODUCTION kernel family over the same packed
        # buffer and release/health folds, so divergence measures the
        # penalty, not a kernel swap; it never donates and writes nothing
        # back — production stays bit-exact with the plane on
        self._shadow_fn = None
        if self.quality.enabled and self.quality.shadow_every_n > 0:
            make_shadow = (make_shadow_admit_step_packed
                           if self.rate_limit_per_minute is not None
                           else make_shadow_step_packed)
            self._shadow_fn = make_shadow(release_fn, shadow_sched_fn)

    def _refresh_quality_signals(self) -> None:
        """Host-side refresh of the quality-plane input vectors (1 Hz
        supervision tick + geometry rebuilds): the anomaly plane's
        latency EWMAs become the scorer's cost vector, its straggler
        flags the shadow penalty. All three vectors re-upload to device
        only when they actually change — the scorer runs every batch, so
        a per-batch host->device transfer of 1 Hz signals would tax the
        dispatch path for nothing; steady fleets pay nothing."""
        n = self._n_pad
        caps = np.zeros(n, np.int32)
        reg_caps = getattr(self, "_caps_mb", None)
        if reg_caps is not None:
            m = min(n, len(reg_caps))
            caps[:m] = np.minimum(reg_caps[:m], 2 ** 31 - 1)
        if (self._quality_caps is None
                or not np.array_equal(caps, self._quality_caps_np)):
            self._quality_caps_np = caps
            self._quality_caps = jnp.asarray(caps)
        ewma = np.zeros(n, np.float32)
        pen = np.zeros(n, np.int32)
        sc = getattr(self.anomaly, "_scores", None)
        if sc is not None:
            k = min(n, sc.shape[1])
            ewma[:k] = sc[S_EWMA_MS, :k]
            pen[:k] = sc[S_STRAGGLER_FLAG, :k].astype(np.int32)
        if (self._quality_ewma is None
                or not np.array_equal(ewma, self._quality_ewma_np)):
            self._quality_ewma_np = ewma
            self._quality_ewma = jnp.asarray(ewma)
        if (self._shadow_penalty is None
                or not np.array_equal(pen, self._shadow_penalty_np)):
            self._shadow_penalty_np = pen
            self._shadow_penalty = jnp.asarray(pen)

    def _prewarm_buckets(self, r: int, h: int, b: int) -> None:
        """Compile-ahead for the packed step's SUCCESSOR bucket shapes. A
        new (R, H, B) signature otherwise compiles synchronously inside a
        live dispatch — ~0.5 s for the scan program and ~1.2 s for the
        repair kernel on a dev box — stalling the event loop and inflating
        the e2e latency of every in-flight activation. XLA compiles
        release the GIL, so warming on a worker thread costs the loop only
        millisecond hiccups while the jit cache fills for the real call.
        Buckets grow by doubling, so (2R, H, B) and (R, H, 2B) keep the
        compiled set one step ahead of traffic growth; already-warmed
        signatures de-dup in _warm_sigs (reset when the fns rebuild).
        On a fleet mesh the warm dummies are sharded like the live state
        (same NamedSharding → same jit cache key), so the mesh pays the
        same zero in-dispatch compile stalls as the single-device path.
        `prewarm=False` disables the whole plane (legacy compile-on-demand
        behavior)."""
        if not self.prewarm:
            return
        self._warm_sigs.add((r, h, b))  # the live call just compiled it
        cand = []
        if r < self.max_batch * 4:
            cand.append((min(r * 2, self.max_batch * 4), h, b))
        if b < self.max_batch:
            cand.append((r, h, min(b * 2, self.max_batch)))
        self._spawn_warm([s for s in cand if s not in self._warm_sigs])

    def _spawn_warm(self, todo: list) -> None:
        """Queue signatures for the single warm drainer. ONE compile runs
        at a time: concurrent warm compiles multiply the GIL hiccups the
        event loop feels, without finishing the ladder any sooner."""
        if not todo or getattr(self, "_closing", False):
            return
        self._warm_sigs.update(todo)
        self._warm_queue.extend((sig, self._packed_fn) for sig in todo)
        if self._warm_task is not None and not self._warm_task.done():
            return

        async def _drain():
            while self._warm_queue and not getattr(self, "_closing", False):
                sig, fn = self._warm_queue.pop(0)
                await asyncio.to_thread(self._warm_one, sig, fn)

        self._warm_task = asyncio.get_event_loop().create_task(_drain())
        self._readbacks.add(self._warm_task)
        self._warm_task.add_done_callback(self._readbacks.discard)

    def _warm_one(self, sig: tuple, fn) -> None:
        """Compile one (R, H, B) signature of a packed step + its
        release-only program (drainer thread; XLA compiles drop the GIL).
        Warming is best-effort, the live path compiles on demand anyway;
        but a SILENT fail would make a systematically broken prewarm (dummy
        inputs drifting from the real signature) look identical to a
        working one, so say why."""
        try:
            self._warm_fns(sig, fn)
        except Exception as e:  # noqa: BLE001
            if self.logger:
                self.logger.warn(None, f"bucket prewarm {sig} failed: {e!r}",
                                 "TpuBalancer")

    def _warm_fns(self, sig: tuple, fn) -> None:
        wr, wh, wb = sig
        rate_on = self.rate_limit_per_minute is not None
        rows = 10 if rate_on else 9
        buf = jnp.asarray(np.zeros(5 * wr + 3 * wh + rows * wb, np.int32))

        # all-zero dummies: valid masks are 0, so nothing places or
        # releases — only the compile (keyed on shapes + statics) matters.
        # Donation consumes the dummies, nothing else; each warmed entry
        # point gets its own. On a mesh the dummy is sharded exactly like
        # the live state so the warm compile keys the live cache entry.
        def dummy_state():
            st = PlacementState(
                jnp.zeros((self._n_pad,), jnp.int32),
                jnp.zeros((self._n_pad, self.action_slots), jnp.int32),
                jnp.zeros((self._n_pad,), bool))
            if self.mesh is not None:
                from ...parallel.sharded_state import shard_state
                st = shard_state(st, self.mesh, axis=self.fleet_axis)
            return st

        buckets = None
        if rate_on:
            buckets = init_buckets(self.RATE_NS_BUCKETS,
                                   self.rate_limit_per_minute)
            (st_w, _bk), out_w = fn(
                (dummy_state(), buckets), buf,
                np.float32(time.monotonic() - self._t0_mono), wr, wh, wb)
        else:
            st_w, out_w = fn(dummy_state(), buf, wr, wh, wb)
        # the idle release fold compiles its own release-only program
        # per R bucket — warm it too, or a drain-only lull still eats
        # the in-dispatch compile stall this plane exists to avoid
        self._release_packed_fn(dummy_state(), np.zeros((5, wr), np.int32))
        # shadow + quality-scorer programs ride the same warm ladder: a
        # first-sight compile inside a live dispatch would stall the loop
        # exactly like an unwarmed packed step. The warm step's own
        # post-state/decision outputs key the scorer's cache entry (same
        # shapes and shardings as the live call).
        sv = None
        if self._shadow_fn is not None:
            pen = jnp.zeros((self._n_pad,), jnp.int32)
            if rate_on:
                sv = self._shadow_fn((dummy_state(), buckets), buf, pen,
                                     np.float32(0.0), wr, wh, wb)
            else:
                sv = self._shadow_fn(dummy_state(), buf, pen, wr, wh, wb)
        step = getattr(self.quality, "_step", None)
        if step is not None:
            qs = init_quality_state(self._n_pad, self.quality.n_buckets)
            req9 = np.zeros((9, wb), np.int32)
            ewma = np.zeros(self._n_pad, np.float32)
            caps = np.zeros(self._n_pad, np.int32)
            step(qs, st_w.free_mb, st_w.conc_free, st_w.health, ewma,
                 caps, req9, out_w, None)
            if sv is not None:
                step(qs, st_w.free_mb, st_w.conc_free, st_w.health, ewma,
                     caps, req9, out_w, sv)

    def _export_kernel_gauge(self) -> None:
        """Info-style backend gauge: exactly one live
        `loadbalancer_kernel_backend{backend,placement,chosen_by} 1`
        series; the superseded combination is zeroed on swaps so a scrape
        sees the flip, not two live backends."""
        tags = {"backend": self.kernel_resolved,
                "placement": self.placement_kernel_resolved,
                "chosen_by": self._kernel_chosen_by}
        prev = getattr(self, "_kernel_gauge_tags", None)
        if prev is not None and prev != tags:
            self.metrics.gauge("loadbalancer_kernel_backend", 0, tags=prev)
        self._kernel_gauge_tags = tags
        self.metrics.gauge("loadbalancer_kernel_backend", 1, tags=tags)

    def _ns_slot(self, ns_id: str) -> int:
        slot = self._ns_slots.get(ns_id)
        if slot is None:
            dedicated = self.RATE_NS_BUCKETS - self.RATE_NS_SHARED_BUCKETS
            if len(self._ns_slots) < dedicated:
                # dedicated slot — memoized (bounds the dict at the axis)
                slot = len(self._ns_slots)
                self._ns_slots[ns_id] = slot
            else:  # dedicated range full: hash into the reserved SHARED
                # tail sub-range, NOT the full axis — overflow namespaces
                # conflate only with each other, never draining a dedicated
                # tenant's tokens. NOT memoized: crc32 is cheaper than
                # unbounded dict growth.
                slot = dedicated + (zlib.crc32(ns_id.encode())
                                    % self.RATE_NS_SHARED_BUCKETS)
        return slot

    def _slot_mb(self, user_memory_mb: int) -> int:
        return max(user_memory_mb // self._cluster_size, MIN_SLOT_MB)

    # -- fleet bookkeeping -------------------------------------------------
    def _status_change(self, instance: InvokerInstanceId, status: str) -> None:
        """One status change: a wave of one."""
        self._status_changes([(instance, status)])

    def _status_changes(self, wave) -> None:
        """A wave of supervision status changes [(invoker, status)], in
        order: what one wake of the health feed's pings changed. The rows
        it adds to the registry are registered together (`_register_rows`);
        every change, of a new row or an old one, is a flip for the next
        device step, and a flush is armed for now, so that the step comes
        without traffic to bring it and a publish that follows finds the
        flips folded. The fleet's partition sizes are worked out again only
        when new rows move them."""
        n0 = len(self._registry)
        for instance, status in wave:
            idx = instance.instance
            while idx >= len(self._registry):
                self._registry.append(instance)
                self._healthy.append(False)
            self._registry[idx] = instance
            self._healthy[idx] = status == HEALTHY
            self._health_updates[idx] = self._healthy[idx]
            if idx < n0:
                # a re-registered row may announce another memory size
                self._caps_mb[idx] = self._slot_mb(instance.user_memory.to_mb)
        if len(self._registry) > n0:
            self._register_rows(n0)
            self._recompute_partitions()
        if self._health_updates:
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                return  # no loop serves this balancer yet: the next step
            self._arm_flush(urgent=True)

    def _register_rows(self, n0: int) -> None:
        """Rows [n0, len(registry)) are new: grow the pad if they pass it,
        then ONE scatter of their capacity into the device books, the same
        values patched into the host's cached books and into the books of
        every step already in flight when they read back (known here: no
        device->host read-back), the capacity vector grown by them, and ONE
        `reg` journal record. Existing rows keep their in-flight holds. A
        new row is unusable on the device until its health flip is folded,
        as the journal's readers hold it."""
        n = len(self._registry)
        with span("ow_register", rows=n - n0):
            if n > self._n_pad:
                self._grow_padding(_next_pow2(n))
            caps = np.fromiter(
                (self._slot_mb(self._registry[i].user_memory.to_mb)
                 for i in range(n0, n)), np.int32, n - n0)
            idx, vals = self._padded_rows(np.arange(n0, n, dtype=np.int32),
                                          caps)
            self.state = self.state._replace(
                free_mb=self.state.free_mb.at[idx].set(vals))
            self._caps_mb = np.concatenate([self._caps_mb[:n0],
                                            caps.astype(np.int64)])
            self._reg_marks.append((self._books_seq, n0))
            self._books_cache = self._with_rows_since(self._books_cache, n0)
            if self._journal_live():
                self._journal_append({
                    "t": "reg",
                    "reg": [self._registry[i].to_json() for i in range(n0, n)],
                    "healthy": [bool(h) for h in self._healthy[n0:n]]})

    def _padded_rows(self, idx: np.ndarray, vals: np.ndarray) -> tuple:
        """A scatter's rows and values padded to a power-of-two bucket by
        repeating the last pair (the same write again), so that the eager
        scatter compiles once per bucket, not once per wave size."""
        n = len(idx)
        b = self._bucket(n, max(self._n_pad, n))
        if b > n:
            idx = np.concatenate([idx, np.full(b - n, idx[-1], idx.dtype)])
            vals = np.concatenate([vals, np.full(b - n, vals[-1],
                                                 vals.dtype)])
        return jnp.asarray(idx), jnp.asarray(vals)

    def _next_books_seq(self) -> int:
        """Claim the next books-cache sequence number (event-loop only:
        dispatches and state installs are loop-serialized)."""
        self._books_seq += 1
        return self._books_seq

    def _install_books(self, books_np, seq: int) -> None:
        """Install host books into occupancy()'s cache unless a NEWER
        step's books already landed; a step dispatched before a
        registration gets the new rows' capacity patched in. Called on the
        event loop."""
        if seq >= self._books_cache_seq:
            since = [n0 for s, n0 in self._reg_marks if s >= seq]
            if since:
                books_np = self._with_rows_since(books_np, min(since))
            self._reg_marks = [m for m in self._reg_marks if m[0] >= seq]
            self._books_cache_seq = seq
            self._books_cache = books_np

    def _with_rows_since(self, books, n0: int) -> np.ndarray:
        """`books` at the current pad with rows [n0, registry) at their
        full capacity, as registration scattered them on the device."""
        out = np.zeros((self._n_pad,), np.int32)
        m = min(len(books), self._n_pad)
        out[:m] = books[:m]
        n = len(self._registry)
        out[n0:n] = self._caps_mb[n0:n]
        return out

    def _set_books_now(self, books_np) -> None:
        """Synchronous cache install for authoritative state changes
        (init/growth/restore) — supersedes any in-flight readback's
        books."""
        self._install_books(books_np, self._next_books_seq())

    def _recover_consumed_state(self) -> bool:
        """After a failed donated device call: if the failure happened
        past the point where XLA consumed the donated buffers, the books
        (and possibly the token-bucket carry, donated in the same tuple by
        the admit variant) are unrecoverable deleted arrays — every later
        call on them would die on 'Array has been deleted'. Rebuild
        fresh-capacity state; leaked in-flight holds self-heal via forced
        timeouts, exactly as after a restart. Returns True when a rebuild
        happened (the failure consumed the donation), False when the
        buffers are intact (failure before consumption, or donation off).
        Every donated call site — request dispatch, the idle release
        fold, the readback-compensation release — routes its failure
        handler through here."""
        if not self._donate:
            return False
        bucket_gone = (self._bucket_state is not None
                       and self._bucket_state.tokens.is_deleted())
        # check conc_free AND free_mb: a host view can pin one leaf from
        # donation (on the CPU twin np.asarray is zero-copy, and
        # _set_books_now's callers hand the cache such a view of free_mb
        # until a step's own output replaces it) while the unreferenced
        # leaves are consumed — one leaf alone would miss the outage
        if not (self.state.free_mb.is_deleted()
                or self.state.conc_free.is_deleted() or bucket_gone):
            return False
        if self.logger:
            self.logger.error(
                None, "device call failure consumed the donated state;"
                " rebuilding device books", "TpuBalancer")
        if bucket_gone:
            self._bucket_state = None
        self._init_device_state()
        if self._journal_live():
            # books were rebuilt at full capacity: replay must do the same
            self._journal_append({"t": "reinit"})
        return True

    def _releases_queued(self) -> int:
        return len(self._releases)

    def _set_inflight(self, delta: int) -> None:
        """Single writer for the in-flight step counter and its gauge —
        the two must never drift, so every pipeline transition (dispatch,
        readback, both failure paths) goes through here."""
        self._inflight_steps += delta
        self.metrics.gauge("loadbalancer_pipeline_inflight",
                           self._inflight_steps)

    def _materialize_state(self) -> PlacementState:
        """Copy-out boundary for holders of the device state. With buffer
        donation ON, the NEXT dispatched step CONSUMES self.state's buffers
        (XLA aliases them into its output), so any reader that keeps the
        state across an await/thread boundary — the snapshot worker, a
        growth re-pad racing the pipeline, occupancy's cold fallback — must
        hold its own copy. Without donation the arrays are immutable and
        the live reference is safe to hold forever."""
        st = self.state
        if not getattr(self, "_donate", False):
            return st
        return PlacementState(jnp.copy(st.free_mb), jnp.copy(st.conc_free),
                              jnp.copy(st.health))

    def _grow_padding(self, new_pad: int) -> None:
        """Re-pad the device arrays, PRESERVING the live books (in-flight
        memory holds and concurrency permits survive fleet growth; only
        update_cluster resets them, which is reference behavior)."""
        st = self._materialize_state()
        old_free = np.asarray(st.free_mb)
        old_conc = np.asarray(st.conc_free)
        old_health = np.asarray(st.health)
        self.profiler.expect("reshard" if self.mesh is not None
                             else "fleet_growth")
        n_old = old_free.shape[0]
        free = np.zeros((new_pad,), np.int32)
        free[:n_old] = old_free
        conc = np.zeros((new_pad, self.action_slots), np.int32)
        conc[:n_old] = old_conc
        health = np.zeros((new_pad,), bool)
        health[:n_old] = old_health
        self._n_pad = new_pad
        self._install_state(PlacementState(jnp.asarray(free),
                                           jnp.asarray(conc),
                                           jnp.asarray(health)))
        if self._journal_live():
            self._journal_append({"t": "grow", "n_pad": new_pad})

    def _ensure_slot_capacity(self, slot_key: str) -> None:
        """Grow the concurrency-slot axis before the allocator runs dry, the
        same way _grow_padding grows the invoker axis. Past the hard cap the
        allocator's stable-hash overflow takes over — counted and warned, so
        conflated concurrency pools are never silent."""
        if not (self._slots.saturated and self._slots.needs_slot(slot_key)):
            return
        if self.action_slots < self.max_action_slots:
            self._grow_slots(min(self.action_slots * 2, self.max_action_slots))
        else:
            # counted on EVERY overflowed acquire, so sustained conflation
            # shows up as a climbing rate, not a one-off blip
            self.metrics.counter("loadbalancer_action_slot_overflow")
            if self.logger and slot_key not in self._slots.overflow:
                self.logger.warn(
                    None, f"action concurrency slots saturated at the hard "
                    f"cap ({self.action_slots}); '{slot_key}' shares a "
                    "hashed slot (conflated concurrency pool)")

    def _install_state(self, state: PlacementState) -> None:
        """Adopt new-shape device arrays: shard onto the mesh (if any) and
        run what kernel_choice picks for the new shapes (Pallas goes when
        they outgrow its VMEM budget). On a mesh this
        IS a reshard event — the new-shape shard_map programs compile
        under an expect window (the caller's growth/restore window, plus
        this explicit reshard stamp) so the recompile watchdog stays
        quiet through cluster grow/resize."""
        if self.mesh is not None:
            from ...parallel.sharded_state import shard_state
            self.profiler.expect("reshard")
            state = shard_state(state, self.mesh, axis=self.fleet_axis)
        self.state = state
        self._set_books_now(np.asarray(state.free_mb))
        self._adopt_plan(self._choose_plan())

    def _grow_slots(self, new_slots: int) -> None:
        """Widen conc_free's action axis, preserving every live permit."""
        self.profiler.expect("slot_growth")
        st = self._materialize_state()
        old_conc = np.asarray(st.conc_free)
        conc = np.zeros((old_conc.shape[0], new_slots), np.int32)
        conc[:, : old_conc.shape[1]] = old_conc
        self.action_slots = new_slots
        self._slots.grow(new_slots)
        self._install_state(PlacementState(st.free_mb,
                                           jnp.asarray(conc),
                                           st.health))
        self.metrics.counter("loadbalancer_action_slot_growth")
        if self._journal_live():
            self._journal_append({"t": "slots", "action_slots": new_slots})
        if self.logger:
            self.logger.info(
                None, f"grew action concurrency slots to {new_slots}")

    def _recompute_partitions(self) -> None:
        n = len(self._registry)
        self.managed_count = max(int(self.managed_fraction * n), 1) if n else 0
        self.blackbox_count = max(int(self.blackbox_fraction * n), 1) if n else 0

    def _rebuild_caps(self) -> None:
        """The host-side per-invoker capacity vector (this controller's
        memory share) from the whole registry, for when every row's share
        changes (cluster size, restore); registration grows it instead.
        Kept in step with the registry so the flight recorder's occupancy
        digest never needs a per-step rebuild."""
        self._caps_mb = np.asarray(
            [self._slot_mb(i.user_memory.to_mb) for i in self._registry],
            np.int64)

    def _probe_steps(self, size: int) -> List[int]:
        """The coprime probe steps of a partition of `size` invokers,
        worked out when a placement first needs them (one list costs
        O(size^2 / log size), a fifth of a second at 10,240 on one CPU
        core) and kept per size: a
        registering fleet passes through every size on its way up and
        places at few of them."""
        steps = self._steps_of.get(size)
        if steps is None:
            if len(self._steps_of) >= 64:
                self._steps_of.clear()
            with span("ow_partitions", managed=self.managed_count,
                      blackbox=self.blackbox_count):
                steps = self._steps_of[size] = pairwise_coprimes(
                    max(1, size))
        return steps

    def update_cluster(self, cluster_size: int) -> None:
        """Controller joined/left: re-shard every invoker's memory
        (ref updateCluster :561-584)."""
        if cluster_size != self._cluster_size:
            self._cluster_size = cluster_size
            self.profiler.expect("reshard" if self.mesh is not None
                                 else "cluster_resize")
            self._init_device_state()
            self._rebuild_caps()  # capacity shares changed
            if self._journal_live():
                self._journal_append({"t": "cluster", "size": cluster_size})

    @property
    def cluster_size(self) -> int:
        return self._cluster_size

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> None:
        self.start_ack_feed()
        self.supervision.start()
        # warm the first-traffic bucket signature while the fleet is still
        # registering, so the opening micro-batches skip the cold compile
        if self.prewarm and \
                (8, self.HEALTH_BATCH, 8) not in self._warm_sigs:
            self._spawn_warm([(8, self.HEALTH_BATCH, 8)])
        # a collection stops the loop like any other code, so the served
        # path owns the collector while it serves: the `ow_gc` span with
        # the pause accounting, and the policy (utils/hostprof.py tune_gc:
        # the boot heap frozen, the generations sized for serving). Both
        # are counted, and close() hands both back.
        if self.gc_tuned is None:
            GLOBAL_HOST_OBSERVATORY.watch_gc()
            self.gc_tuned = GLOBAL_HOST_OBSERVATORY.tune_gc()
            if self.logger:
                self.logger.info(
                    None,
                    f"gc tuned: froze {self.gc_tuned['frozen']} objects, "
                    f"thresholds {self.gc_tuned['thresholds']}")

    async def close(self) -> None:
        self._closing = True  # no new flush tasks from here on
        await self.supervision.stop()
        if self._flush_task:
            self._flush_task.cancel()
        # let in-flight readbacks resolve their publishers first
        if self._readbacks:
            await asyncio.gather(*list(self._readbacks),
                                 return_exceptions=True)
        # fail queued publishers instead of leaving them awaiting forever
        pending, self._pending = self._pending, []
        self._req_ring.clear()
        for req, fut, slot_key, _t, aid, *_ in pending:
            self._slots.release(slot_key, req[self.R_CONC_SLOT])
            self.waterfall.discard(aid)
            if not fut.done():
                fut.set_exception(LoadBalancerException("load balancer shut down"))
        # batched-publish finishers drain AFTER the queued rows fail (every
        # placement future they await is resolved by now — dispatched rows
        # by the readback gather above, queued rows by the loop above) and
        # BEFORE the producer closes, so every caller-facing future maps
        # its outcome while sends still work
        if self._publish_finishers:
            await asyncio.gather(*list(self._publish_finishers),
                                 return_exceptions=True)
        # releases queued during the readback drain (abandoned publishers)
        # will never reach a device step now — free their host slots
        for r in self._releases:
            self._slots.release(r[4], r[1])
        self._releases.clear()
        self._rel_ring.clear()
        await super().close()
        if self.gc_tuned is not None:
            self.gc_tuned = None
            GLOBAL_HOST_OBSERVATORY.untune_gc()
            GLOBAL_HOST_OBSERVATORY.unwatch_gc()

    # -- publish -----------------------------------------------------------
    def _standby_error(self) -> Optional[LoadBalancerException]:
        """The pre-placement refusals shared by publish/publish_many."""
        if self.ha_standby:
            # HA failover mode: placement is fenced to the active leader —
            # refusing BEFORE any state change makes the 503 safe for the
            # edge to retry on the active upstream
            return LoadBalancerException(
                "standby controller: placement is fenced to the active "
                "leader")
        if len(self._registry) == 0 or not any(self._healthy):
            return LoadBalancerException(
                "No invokers available to schedule the activation.")
        return None

    def _build_row(self, action: ExecutableWhiskAction,
                   msg: ActivationMessage) -> tuple:
        """One request row in packed-matrix order — the per-activation
        half of publish, shared verbatim by the serial and batched paths
        (parity by construction). The home hash and the modular inverse
        are pure functions of their inputs, so both ride bounded memo
        dicts; everything stateful (_rand_counter, the slot allocator,
        slot-axis growth) mutates in exactly the serial order."""
        n = len(self._registry)
        blackbox = action.exec_metadata().is_blackbox
        size = self.blackbox_count if blackbox else self.managed_count
        offset = (n - self.blackbox_count) if blackbox else 0
        fqn_str = str(action.fully_qualified_name)
        hkey = (str(msg.user.namespace.name), fqn_str)
        h = self._hash_cache.get(hkey)
        if h is None:
            if len(self._hash_cache) >= 65536:
                self._hash_cache.clear()
            h = self._hash_cache[hkey] = generate_hash(*hkey)
        steps = self._probe_steps(size)
        step = steps[h % len(steps)]
        ikey = (step, size)
        step_inv = self._modinv_cache.get(ikey)
        if step_inv is None:
            if len(self._modinv_cache) >= 65536:
                self._modinv_cache.clear()
            step_inv = self._modinv_cache[ikey] = _mod_inverse(step, size)
        self._rand_counter += 1
        mem = action.limits.memory.megabytes
        maxc = action.limits.concurrency.max_concurrent
        slot_key = f"{fqn_str}:{mem}"
        self._ensure_slot_capacity(slot_key)
        # request row in packed-matrix order (see _dispatch_batch): a plain
        # tuple converts to the int32 batch matrix in one C-speed np.array
        # call instead of a per-field Python fill loop
        ns_slot = (self._ns_slot(msg.user.namespace.uuid.asString)
                   if self.rate_limit_per_minute is not None else 0)
        req = (offset, size, h % size, step_inv, mem,
               self._slots.acquire(slot_key), maxc,
               (h ^ (self._rand_counter * 2654435761)) % max(size, 1), 1,
               ns_slot)
        return req, slot_key, fqn_str

    async def publish(self, action: ExecutableWhiskAction, msg: ActivationMessage
                      ) -> asyncio.Future:
        err = self._standby_error()
        if err is not None:
            raise err
        pid = None
        if self.partition_ring is not None:
            pid = self.partition_of_msg(msg)
            err = self._partition_refusal(msg, pid)
            if err is not None:
                raise err
        # the span ends before the await: it covers this thread's time
        with span("ow_admit", n=1):
            req, slot_key, fqn_str = self._build_row(action, msg)
            fut: asyncio.Future = asyncio.get_event_loop().create_future()
            # trailing fields feed the flight recorder: enqueue time
            # (queue-age digest), the activation/action ids for the decision
            # row, and the trace id (exemplar plumbing on OpenMetrics scrapes)
            aid_str = msg.activation_id.asString
            t_now = time.monotonic()
            self._note_arrival(t_now)
            entry = (req, fut, slot_key, t_now,
                     aid_str, fqn_str,
                     trace_id_of(msg.trace_context))
            if pid is not None:
                # active/active: the row's (partition, epoch) rides the entry
                # so the dispatch-time journal record carries per-partition
                # ids + the epoch each row was admitted under (a spilled row
                # keeps its origin's stamp when that is ahead of ours)
                entry = entry + ((pid, self._row_epoch(msg, pid)),)
            # waterfall: the activation is now IN the balancer's queue — the
            # delta from here to batch_assemble is pure queueing/window wait
            self.waterfall.stamp(aid_str, STAGE_PUBLISH_ENQUEUE)
            # the packed-matrix column lands in the preallocated ring NOW
            # (one C-speed write) — flush-time assembly is two slice
            # copies. The entry is built FIRST: an exception between a
            # ring push and its queue append would desync the two FIFOs
            # and shift every later request's geometry.
            self._req_ring.push(req)
            self._pending.append(entry)
            # inline fast path: with free pipeline capacity, dispatch NOW
            # (synchronously — the assembly+enqueue body has no awaits) when
            # the batch is full, or on an idle FAST device (sub-window round
            # trips: overlap is real, so eager dispatch just cuts latency).
            # On a device with a slow round trip they serialize, so splitting
            # an arrival wave into eager sub-batches multiplies wire time —
            # measured RTT (EWMA of the readback histogram) picks the policy.
            # Under arrival PRESSURE (_coalesce_window_s > 0) eager dispatch
            # is the tax, not the cure: per-arrival steps ship batches of 1-3
            # and the fixed dispatch cost dominates the loop — hold the
            # window and let the batch fill instead.
            if not ((len(self._pending) >= self.max_batch
                     or (self._inflight_steps == 0
                         and self._rtt_ewma_ms < self.RTT_FAST_MS
                         and self._coalesce_window_s() == 0.0))
                    and self._try_flush_now()):
                self._arm_flush(urgent=len(self._pending) >= self.max_batch)
        try:
            inv_idx, forced = await fut
        except asyncio.CancelledError:
            # Cancelled between set_result and resumption: the placement is
            # lost to this caller but its capacity is not — give it back the
            # same way the readback loop does for futures cancelled earlier.
            if fut.done() and not fut.cancelled() and fut.exception() is None:
                self._abandon_placement(int(fut.result()[0]), req, slot_key)
            # abandoned = never acked = never finished: drop the stage
            # vector too, like every other abandonment path (a cancelled
            # future's vector would otherwise sit in the active map until
            # the eviction cap pushed out a LIVE activation's instead)
            self.waterfall.discard(aid_str)
            raise
        invoker, promise = self._map_placement(inv_idx, forced, req,
                                               slot_key, aid_str, msg, action)
        await self.send_activation_to_invoker(msg, invoker)
        return promise

    def _map_placement(self, inv_idx: int, forced, req: tuple,
                       slot_key: str, aid: str, msg, action):
        """The post-placement outcome mapping, shared by the serial
        `publish` and the batched `_row_placed` continuation so the two
        paths cannot drift: failure codes release the held capacity,
        discard the stage vector and raise the serial exception texts;
        success books the forced counter, sets up the activation entry
        and returns (invoker, completion promise)."""
        if inv_idx == -2:
            # device token bucket rejected it: no capacity was consumed
            self._slots.release(slot_key, req[self.R_CONC_SLOT])
            self.waterfall.discard(aid)
            self.metrics.counter("loadbalancer_device_throttled")
            raise LoadBalancerThrottleException(
                "Too many requests in the last minute (device rate "
                "admission).")
        if inv_idx < 0:
            self._slots.release(slot_key, req[self.R_CONC_SLOT])
            self.waterfall.discard(aid)
            raise LoadBalancerException(
                "No invokers available to schedule the activation.")
        if forced:
            self.metrics.counter("loadbalancer_forced_placements")
        invoker = self._registry[inv_idx]
        promise = self.setup_activation(msg, action, invoker)
        entry = self.activation_slots.get(aid)
        if entry is not None:
            entry.conc_slot = req[self.R_CONC_SLOT]
        return invoker, promise

    def publish_many(self, pairs) -> List[asyncio.Future]:
        """The batch-shaped publish SPI (ISSUE 14): one call schedules a
        whole admission batch. Against N serial publishes this pays ONE
        clock read + arrival-EWMA pass (`_note_arrivals`), ONE
        `stamp_many(PUBLISH_ENQUEUE)`, ONE NumPy column pass into the
        request ring (`push_block`), ONE shared flush decision — the
        whole batch lands in one device micro-batch instead of an eager
        head-of-batch dispatch of 1 — with per-row continuations as
        done-callbacks (`_row_placed`: zero tasks, sends handed to the
        bus coalescer task-free). Each returned future
        resolves to the completion promise (what `publish` returns) or
        raises `publish`'s exact exceptions; per-row decisions, waterfall
        stamps, 429 texts and abandonment capacity-returns are the serial
        path's, row for row (parity-fuzzed)."""
        with span("ow_admit", n=len(pairs)):
            return self._admit_many(pairs)

    def _admit_many(self, pairs) -> List[asyncio.Future]:
        """`publish_many`'s synchronous body: row build, slot allocation,
        ring push, the shared flush decision."""
        loop = asyncio.get_event_loop()
        outs: List[asyncio.Future] = [loop.create_future() for _ in pairs]
        err = self._standby_error()
        if err is not None:
            # fresh exception instance per row (serial parity: each
            # publish call raises its own) — N waiters re-raising one
            # shared object interleave their __traceback__ frames
            for out in outs:
                out.set_exception(type(err)(*err.args))
            return outs
        built: List[tuple] = []
        ring = self.partition_ring
        # cross-partition spillover (active/active): with a peered sink
        # and the pending queue past the depth gate, this batch's
        # NON-BLOCKING tail forwards to the least-loaded peer instead of
        # deepening the local queue (the forwarded rows are fenced with
        # this owner's partition epoch, so the peer's journal replays
        # them exactly; blocking rows stay local — their client waits on
        # THIS controller's completion promise)
        overflow = 0
        if (ring is not None and self.spillover_sink is not None
                and self.spillover_sink.has_peer()):
            overflow = max(0, len(self._pending) + len(pairs)
                           - self.spillover_depth)
        spill_rows: List[tuple] = []
        for (action, msg), out in zip(pairs, outs):
            pid = None
            if ring is not None:
                pid = self.partition_of_msg(msg)
                err = self._partition_refusal(msg, pid)
                if err is not None:
                    out.set_exception(err)
                    continue
                if overflow > 0 and not msg.blocking \
                        and pid in self.owned_partitions:
                    spill_rows.append((action, msg, out, pid))
                    overflow -= 1
                    continue
            try:
                req, slot_key, fqn_str = self._build_row(action, msg)
            except Exception as e:  # noqa: BLE001 — per-row isolation,
                # like N independent publish calls: one bad row must not
                # strand its batch-mates
                out.set_exception(e)
                continue
            built.append((req, loop.create_future(), slot_key,
                          msg.activation_id.asString, msg, action, out,
                          fqn_str, pid))
        if spill_rows:
            self._spill_forward(spill_rows)
        if not built:
            return outs
        # the serial path notes an arrival only AFTER a successful row
        # build (a raising _build_row never reaches _note_arrival), so
        # the shared clock read counts built rows, not offered pairs —
        # else a burst of failing rows would decay the arrival EWMA and
        # flip _coalesce_window_s where serial stays eager
        t_now = time.monotonic()
        self._note_arrivals(t_now, len(built))
        # the NumPy column pass: every built row's packed column lands in
        # the preallocated ring in one [rows, k] block write (two slice
        # copies), replacing k per-row ring assignments. The pending
        # entries append in the SAME synchronous block, so the two FIFOs
        # cannot desync.
        self._req_ring.push_block(
            np.asarray([b[0] for b in built], np.int32).T)
        for req, fut, slot_key, aid, msg, _action, _out, fqn_str, pid \
                in built:
            entry = (req, fut, slot_key, t_now, aid, fqn_str,
                     trace_id_of(msg.trace_context))
            self._pending.append(
                entry if pid is None
                else entry + ((pid, self._row_epoch(msg, pid)),))
        self.waterfall.stamp_many([b[3] for b in built],
                                  STAGE_PUBLISH_ENQUEUE)
        self.metrics.histogram("loadbalancer_publish_batch_size",
                               len(built))
        # ONE shared flush decision for the whole admission batch (the
        # serial path decides per row, which at idle eagerly dispatches a
        # 1-deep device step for the batch's FIRST row): drain full
        # buckets inline, then apply the serial eager/window rule once.
        while (len(self._pending) >= self.max_batch
               and self._try_flush_now()):
            pass
        if self._pending and not (
                self._inflight_steps == 0
                and self._rtt_ewma_ms < self.RTT_FAST_MS
                and self._coalesce_window_s() == 0.0
                and self._try_flush_now()):
            self._arm_flush(urgent=len(self._pending) >= self.max_batch)
        # per-row continuations are DONE-CALLBACKS, not a task: at sweep
        # depth (a few rows per event-loop sweep at moderate rates) a
        # per-batch finisher task costs more than the per-row work it
        # amortizes — measured as a ~0.7 tasks/activation regression.
        # The callback chain mints zero loop objects beyond the two
        # futures the SPI contract needs, and the caller-cancellation
        # bridge makes the readback fan-out read a gone caller as an
        # abandoned publisher (capacity returned per row).
        for b in built:
            req, fut, slot_key, aid, msg, action, out, _fqn, _pid = b
            out.add_done_callback(
                lambda o, f=fut: (f.cancel() if (o.cancelled()
                                                 and not f.done())
                                  else None))
            fut.add_done_callback(
                lambda f, r=req, sk=slot_key, a=aid, m=msg, ac=action,
                o=out: self._row_placed(f, r, sk, a, m, ac, o))
        return outs

    def _row_placed(self, fut: asyncio.Future, req: tuple, slot_key: str,
                    aid: str, msg, action, out: asyncio.Future) -> None:
        """One batched-publish row's continuation (a done-callback on its
        placement future): the serial publish's post-placement body —
        error mapping, activation setup, fencing — then the dispatch send
        handed to the bus coalescer WITHOUT awaiting (its flush future
        resolves `out`, so send failures still surface exactly like the
        serial path's raised send errors). All rows of a readback wave run
        their callbacks in one sweep, so their sends coalesce into the
        same bus frames the serial path's fan-out produced."""
        with span("ow_placed"):
            self._place_row(fut, req, slot_key, aid, msg, action, out)

    def _place_row(self, fut: asyncio.Future, req: tuple, slot_key: str,
                   aid: str, msg, action, out: asyncio.Future) -> None:
        wf = self.waterfall
        try:
            if fut.cancelled():
                # abandoned row: the readback fan-out (or the bridge
                # racing an unplaced row) already returned the capacity
                # and dropped the stage vector
                return
            exc = fut.exception()
            if exc is not None:
                # dispatch failure: the failing device step already
                # released this row's slot and discarded its vector
                if not out.done():
                    out.set_exception(exc)
                return
            inv_idx, forced = fut.result()
            if out.cancelled():
                # caller went away between the fan-out resolving the row
                # and this callback — the serial CancelledError branch
                self._abandon_placement(int(inv_idx), req, slot_key)
                wf.discard(aid)
                return
            # outcome mapping shared verbatim with the serial publish
            # (_map_placement): failure codes release capacity, discard
            # the vector and raise the serial texts — the enclosing
            # except hands them to `out` exactly like a serial raise
            invoker, promise = self._map_placement(inv_idx, forced, req,
                                                   slot_key, aid, msg,
                                                   action)
            send_nowait = getattr(self.producer, "send_nowait", None)
            if send_nowait is not None:
                # fence stamping + published counter shared with the
                # serial send (prepare_dispatch), so the two paths
                # cannot drift. Note this task-free submit is the one
                # dispatch that does NOT flow through the
                # send_activation_to_invoker hook — minting a coroutine
                # per row to honor it would be the exact per-activation
                # floor this path removes.
                topic = self.prepare_dispatch(msg, invoker)
                sendf = send_nowait(topic, msg)

                def _sent(sf: asyncio.Future) -> None:
                    # retrieve the flush outcome UNCONDITIONALLY (before
                    # any early-return): a caller gone by cancellation
                    # must not leave an unretrieved flush exception
                    # spamming the loop's GC-time logger
                    send_exc = (None if sf.cancelled()
                                else sf.exception())
                    if out.done():
                        return
                    if sf.cancelled():
                        # the coalescer's drainer was cancelled with the
                        # dispatch still queued (loop teardown): serial
                        # parity is the awaited send RAISING
                        # CancelledError to the caller — never success
                        # for an unsent dispatch
                        out.cancel()
                        return
                    if send_exc is not None:
                        # serial parity: the entry stays; the forced
                        # timeout self-heals the held capacity
                        out.set_exception(send_exc)
                    else:
                        out.set_result(promise)

                sendf.add_done_callback(_sent)
            else:
                # raw (non-coalescing) producer: no task-free submit —
                # one send task per row, the serial cost (this is the
                # coalescing-off configuration, not the hot path). The
                # task awaits send_activation_to_invoker (which runs
                # prepare_dispatch itself), so the documented dispatch
                # hook keeps covering this path for subclasses/tests.
                task = asyncio.get_event_loop().create_task(
                    self._send_then_resolve(invoker, msg, out, promise))
                self._publish_finishers.add(task)
                task.add_done_callback(self._publish_finishers.discard)
        except Exception as e:  # noqa: BLE001 — a raising done-callback
            # would land in the loop's exception handler and strand the
            # caller: fail the row instead
            if not out.done():
                out.set_exception(e)

    def _row_epoch(self, msg, pid: int) -> int:
        """The fence epoch a row is admitted under: our view of its
        partition's epoch, or the origin's stamp when that is ahead (a
        spilled row whose claim announcement we have not folded yet)."""
        ep = self.partition_epochs.get(pid, 0)
        if msg.fence_part == pid and msg.fence_epoch is not None:
            ep = max(ep, int(msg.fence_epoch))
        return ep

    def _spill_forward(self, rows: List[tuple]) -> None:
        """Forward an overflow sub-batch to the spillover sink
        (spillover.py). Each row is fence-stamped with ITS partition's
        current epoch BEFORE it leaves — the stamp is both the invoker
        fence and the peer-side admission credential — and the waterfall
        stamps the extra hop, then folds the origin-side partial vector
        (the peer's books own the rest of the row's life). The caller's
        future resolves to a completed placeholder promise: spillover
        only takes non-blocking rows, whose promise is never awaited."""
        wf = self.waterfall
        loop = asyncio.get_event_loop()
        pairs = []
        for action, msg, _out, pid in rows:
            msg.fence_part = pid
            msg.fence_epoch = self.partition_epochs.get(pid, 0)
            pairs.append((action, msg))
        try:
            sent = self.spillover_sink.forward(pairs)
        except Exception as e:  # noqa: BLE001 — a failing forward fails
            # its rows like a refused publish, never the whole batch —
            # and is never counted as a forward (no stamp, no counter)
            for _action, _msg, out, _pid in rows:
                if not out.done():
                    out.set_exception(LoadBalancerException(
                        f"spillover forward failed: {e}"))
            return
        # handed to the sink: NOW count the forwards and fold the
        # origin-side waterfall (an async send that later fails shows up
        # in loadbalancer_spillover_send_failed, like a lost produce)
        for _action, msg, _out, _pid in rows:
            aid = msg.activation_id.asString
            wf.stamp(aid, STAGE_SPILL_FORWARD)
            row = wf.finish(aid)
            # ISSUE 18: the origin half's tail verdict runs HERE — the
            # spill hop is this process's terminal stage (no completion
            # ack ever comes back to these books), so waiting for one
            # would leak the pending spans forever. The kept half (the
            # driver + hop spans joined to the partial stage vector) is
            # what /admin/trace/{id} merges with the peer's half.
            if self.trace_store.enabled:
                from ...utils.tracing import trace_id_of
                tid = (row or {}).get("trace_id") or trace_id_of(
                    getattr(msg, "trace_context", None))
                self.trace_store.complete(aid, tid, row=row)
        self.spilled_rows += len(rows)
        self.metrics.counter("loadbalancer_spillover_forwarded", len(rows))
        for (_action, _msg, out, _pid), row_sent in zip(rows, sent):
            placeholder: asyncio.Future = loop.create_future()
            placeholder.set_result(None)

            def _resolve(sf: asyncio.Future, o=out, p=placeholder) -> None:
                exc = None if sf.cancelled() else sf.exception()
                if exc is not None:
                    self.metrics.counter(
                        "loadbalancer_spillover_send_failed")
                if o.done():
                    return
                if sf.cancelled():
                    o.cancel()
                elif exc is not None:
                    o.set_exception(exc)
                else:
                    o.set_result(p)

            row_sent.add_done_callback(_resolve)

    async def _send_then_resolve(self, invoker, msg, out: asyncio.Future,
                                 promise) -> None:
        try:
            await self.send_activation_to_invoker(msg, invoker)
        except Exception as e:  # noqa: BLE001
            if not out.done():
                out.set_exception(e)
            return
        if not out.done():
            out.set_result(promise)

    def _abandon_placement(self, inv_idx: int, req: tuple, slot_key: str) -> None:
        """A publisher went away (client disconnect) after its request was
        (or will never be) placed. Route the reserved capacity through the
        normal release queue — which also frees the host conc slot at drain
        time, keeping the slot index pinned to this action until the
        device-side decrement lands."""
        if inv_idx >= 0:
            self._queue_release(inv_idx, req[self.R_CONC_SLOT],
                                req[self.R_NEED_MB], req[self.R_MAX_CONC],
                                slot_key)
            self._arm_flush()
        else:
            self._slots.release(slot_key, req[self.R_CONC_SLOT])

    def _queue_release(self, inv: int, slot: int, mem: int, maxc: int,
                       key: str) -> None:
        """Buffer one capacity release for the next device step (the slot
        KEY rides host-side for drain-time slot bookkeeping; the int column
        mirrors into the release ring for flush assembly)."""
        self._rel_ring.push((inv, slot, mem, maxc))
        self._releases.append((inv, slot, mem, maxc, key))

    # -- completion hooks --------------------------------------------------
    def release_invoker(self, invoker: InvokerInstanceId, entry) -> None:
        action_name = entry.action_key.rsplit("@", 1)[0]
        key = f"{action_name}:{entry.memory_mb}"
        slot = (entry.conc_slot if entry.conc_slot is not None
                else self._slots.lookup(key))
        self._queue_release(invoker.instance, slot, entry.memory_mb,
                            entry.max_concurrent, key)
        self._arm_flush()

    def on_invocation_finished(self, invoker, is_system_error, forced) -> None:
        self.supervision.on_invocation_finished(invoker, is_system_error, forced)

    async def invoker_health(self) -> List[InvokerHealth]:
        return self.supervision.health()

    #: occupancy() now serves from the last readback's CACHED books — no
    #: device sync, so the admin endpoint runs inline on the event loop and
    #: can never stall (or race a donated buffer under) the dispatch loop
    OCCUPANCY_SYNCS_DEVICE = False

    def occupancy(self) -> dict:
        """Per-invoker slots-in-use/capacity from the cached books: the
        post-step `free_mb` that every fused step appends to its own output
        vector (a slice of the host copy the readback worker makes anyway;
        a release-only fold returns them beside the state), installed on
        the loop under a sequence guard, and re-set on every state
        install, so the cache exists from construction onward. Under a
        full pipeline it lags the dispatched state by up to
        `pipeline_depth` unread steps — and never costs a device->host
        transfer on the API path, which under buffer donation would
        additionally race the dispatch loop consuming the live buffer.
        Host books are snapshotted up front (list() is atomic under the
        GIL) and every index is length-guarded against concurrent fleet
        growth."""
        free = self._books_cache
        if free is None:  # pre-init construction window: empty fleet
            free = np.zeros((0,), np.int32)
        registry = list(self._registry)
        healthy = list(self._healthy)
        caps = self._caps_mb

        def rows():
            for i, inv in enumerate(registry):
                cap = (int(caps[i]) if i < len(caps)
                       else self._slot_mb(inv.user_memory.to_mb))
                f = int(free[i]) if i < len(free) else cap
                yield (inv.as_string,
                       healthy[i] if i < len(healthy) else False,
                       cap, f, cap - f)

        out = occupancy_json(self.kernel_resolved, rows())
        if self.mesh is not None:
            # per-shard books aggregated from the SAME cached vector —
            # still zero device syncs on the API path
            out["mesh"] = {"n_shards": self.n_shards,
                           "axis": self.fleet_axis}
            out["shards"] = self._shard_occupancy(free, caps)
        return out

    def _shard_occupancy(self, free, caps) -> List[dict]:
        """Per-shard occupancy rows from host-cached books. Shard s owns
        invoker rows [s*k, (s+1)*k) with k = n_pad / n_shards (the
        NamedSharding block layout); padding rows carry zero capacity and
        zero free, so they drop out of the sums."""
        rows_per = max(1, self._n_pad // max(1, self.n_shards))
        n_reg = len(caps)
        out = []
        for s in range(self.n_shards):
            lo, hi = s * rows_per, (s + 1) * rows_per
            reg_hi = min(hi, n_reg)
            cap = int(caps[lo:reg_hi].sum()) if lo < n_reg else 0
            f = int(free[lo:min(hi, len(free))].sum()) \
                if lo < len(free) else cap
            used = cap - f
            out.append({"shard": s,
                        "invokers": max(0, reg_hi - lo),
                        "capacity_mb": cap, "used_mb": used,
                        "occupancy": (round(used / cap, 4) if cap
                                      else 0.0)})
        return out

    def _export_shard_gauges(self) -> None:
        """`loadbalancer_fleet_shards` + per-shard occupancy ratios from
        the cached books — host numpy only, never a device sync (rides
        the 1 Hz supervision tick)."""
        self.metrics.gauge("loadbalancer_fleet_shards", self.n_shards)
        free = self._books_cache
        if free is None:
            return
        for row in self._shard_occupancy(free, self._caps_mb):
            self.metrics.gauge("loadbalancer_shard_occupancy_ratio",
                               row["occupancy"],
                               tags={"shard": str(row["shard"])})

    def kernel_profile(self) -> dict:
        """The profiling-plane payload, labeled with the kernel actually
        running (xla / pallas / sharded) — host-side reads only, no device
        sync (memory_stats is a runtime counter read, not an array pull)."""
        out = self.profiler.profile_json(kernel=self.kernel_resolved)
        out["placement_kernel"] = self.placement_kernel_resolved
        out["kernel_chosen_by"] = self._kernel_chosen_by
        out["device"] = self.device
        if self.mesh is not None:
            out["mesh"] = {"n_shards": self.n_shards,
                           "axis": self.fleet_axis}
        return out

    # -- placement journal (HA plane; loadbalancer/journal.py) -------------
    def attach_journal(self, journal) -> None:
        """Adopt a PlacementJournal. Appends start from the max of the
        balancer's own seq and what the log already holds, so a restarted
        active never reuses a sequence number. Also registers the
        journal's durability lag as an alert signal: the built-in
        `journal_stall` rule (anomaly.py) fires when the lag stays above
        its threshold for its window — an fsync device stall — and
        /admin/ready surfaces the firing state."""
        self.journal = journal
        if journal is not None:
            self._journal_seq = max(self._journal_seq, journal.last_seq())
            self.anomaly.extra_signals["journal_lag_batches"] = (
                lambda: float(self.journal.lag_batches)
                if self.journal is not None else None)

    def _journal_live(self) -> bool:
        return (self.journal is not None and not self._journal_mute
                and not self.ha_standby)

    def _journal_mesh_header(self) -> None:
        """Topology header: ONE `mesh` record ahead of this writer's
        first append (rides alongside `reg`/`cluster`), so replay can
        refuse a different device count with a logged reason."""
        if self.mesh is not None and not self._journal_mesh_stamped:
            self._journal_mesh_stamped = True
            from ...parallel.fleet_mesh import mesh_topology
            self._journal_append({"t": "mesh", **mesh_topology(self.mesh)})

    def _journal_next_seq(self) -> int:
        """The seq the next `_journal_append` will stamp (0: journal off),
        for spans that open before their record is written."""
        if not self._journal_live():
            return 0
        self._journal_mesh_header()
        return self._journal_seq + 1

    def _journal_append(self, rec: dict) -> int:
        """Stamp the next seq (and fencing epoch) onto `rec` and append.
        Returns the seq (0 when journaling is off). Called on the event
        loop in the SAME synchronous block as the state mutation it
        records, so journal order == device-state mutation order and a
        snapshot's `journal_seq` is exactly consistent with its books."""
        if not self._journal_live():
            return 0
        if rec.get("t") != "mesh":
            self._journal_mesh_header()
        self._journal_seq += 1
        rec["seq"] = self._journal_seq
        if self.fence_epoch is not None:
            rec["epoch"] = self.fence_epoch
        try:
            with span("ow_journal") as sp:
                sp.set_metadata(bytes=self.journal.append(rec) or 0)
        except Exception as e:  # noqa: BLE001 — journaling degrades, the
            # placement path never dies for the flight data recorder
            if self.logger:
                self.logger.warn(None, f"journal append failed: {e!r}; "
                                       "detaching journal", "TpuBalancer")
            self.journal = None
        return rec.get("seq", 0)

    def replay_journal(self, records, logger=None,
                       from_seq: Optional[int] = None,
                       parts_filter=None, foreign: bool = False) -> dict:
        """Deterministically re-execute a journal tail on top of the
        current (snapshot-restored) state. Batch records re-run the SAME
        schedule/release kernels the active used (non-donated replay
        programs) over the recorded packed input buffers — placement is
        bit-deterministic (ops/placement parity suite), so the re-derived
        books equal the dead active's and the re-derived decisions equal
        the journaled readback (`parity_mismatches` counts divergence,
        e.g. a kernel-knob change across the restart). Structural records
        (registration/growth/cluster) re-apply their host-side mutation.

        Batches journaled at dispatch but crashed before readback replay
        with their full request set (conservative over-hold: those
        placements were computed on the dead device; self-heal via forced
        timeouts reclaims them, exactly the checkpoint posture).

        Mesh topology: a fleet-mesh writer stamps `mesh` records and a
        shard count (`S`) on every batch record. Replay proceeds only on
        a MATCHING topology (a promoted standby with the same device
        count reshards at restore and replays the tail bit-exactly);
        any mismatch — journal written at a different shard count, or a
        single-device journal replayed on a mesh (and vice versa) —
        COLD-STARTS with a logged reason instead of silently
        mis-sharding (`skipped: "mesh_topology"`).

        Active/active (ISSUE 15): `parts_filter` restricts the replay to
        records whose `parts` intersect the given partition set — the
        HANDOFF path, where the new owner of a partition set absorbs the
        previous owner's tail and nothing else (structural records —
        registration/growth/cluster — are the previous owner's OWN
        topology and are skipped under a filter). `foreign=True` marks
        the tail as another controller's journal: its seqs live in that
        journal's numbering, so this balancer's own `_journal_seq` never
        moves, and a topology mismatch SKIPS the absorb (logged) instead
        of cold-starting the survivor's live books. Records carrying a
        `pe` (per-partition epoch) map are additionally dropped PER
        PARTITION: a record whose every overlapping partition was
        superseded at-or-before its seq is a zombie owner's late flush."""
        stats: dict = {}
        for _ in self.replay_stepper(records, logger=logger,
                                     from_seq=from_seq,
                                     parts_filter=parts_filter,
                                     foreign=foreign, stats=stats):
            pass
        return stats

    def replay_stepper(self, records, logger=None,
                       from_seq: Optional[int] = None,
                       parts_filter=None, foreign: bool = False,
                       stats: Optional[dict] = None):
        """The replay engine behind `replay_journal`, exposed as a
        generator for the time-travel debugger (timetravel.py): yields one
        step dict `{seq, t, rec, detail}` per APPLIED record (acks and
        stale/filtered records are handled internally, exactly as before),
        so a consumer can stop at seq K, break on an activation id, or
        inspect the re-derived books between any two steps. `stats` is a
        caller-supplied dict mutated in place (replayed/batches/
        parity_mismatches/last_seq...) — shared state with the driver, and
        still correct when the consumer abandons the generator early:
        finalization (journal un-mute, host-books refresh, last_seq) runs
        in the generator's `finally`, i.e. also on `close()`."""
        log = logger or self.logger
        if stats is None:
            stats = {}
        if from_seq is not None and not foreign:
            self._journal_seq = int(from_seq)
        stats.update({"replayed": 0, "batches": 0, "parity_mismatches": 0,
                      "from_seq": (int(from_seq) if from_seq is not None
                                   else self._journal_seq)})
        self.profiler.expect("snapshot_restore")
        recs = [r for r in records]
        # stale-epoch filter: a demoted active's already-popped write batch
        # can still land in its own old segment AFTER the new epoch began —
        # any record whose epoch is superseded at-or-before its seq was
        # never part of the promoted active's state and must not replay
        first_seq: Dict[int, int] = {}
        #: per-partition variant of the same bound: (pid, epoch) -> first
        #: seq observed carrying it (records with a `pe` map)
        pfirst_seq: Dict[tuple, int] = {}
        for r in recs:
            e, s = int(r.get("epoch", 0)), int(r.get("seq", 0))
            first_seq[e] = min(first_seq.get(e, s), s)
            for pid_s, pe in (r.get("pe") or {}).items():
                key = (int(pid_s), int(pe))
                pfirst_seq[key] = min(pfirst_seq.get(key, s), s)
        bounds = sorted(first_seq.items())
        pbounds: Dict[int, list] = {}
        for (pid, e), s in sorted(pfirst_seq.items()):
            pbounds.setdefault(pid, []).append((e, s))

        def _fresh_for(pid: int, e: int, s: int) -> bool:
            return not any(e2 > e and s2 <= s
                           for e2, s2 in pbounds.get(pid, ()))

        def _fresh(r: dict) -> bool:
            e, s = int(r.get("epoch", 0)), int(r.get("seq", 0))
            if any(e2 > e and s2 <= s for e2, s2 in bounds):
                return False
            pe = r.get("pe")
            if not pe:
                return True
            # fresh while ANY overlapping partition is fresh — a batch
            # mixing a stale and a live partition still owes the live
            # partition its holds (the stale rows are epsilon over-hold,
            # self-healed by forced timeouts like every replay over-hold)
            pids = ((int(p) for p in pe)
                    if parts_filter is None
                    else (int(p) for p in pe if int(p) in parts_filter))
            return any(_fresh_for(p, int(pe[str(p)]), s) for p in pids)

        if parts_filter is not None:
            parts_filter = set(int(p) for p in parts_filter)
            kept = []
            kept_seqs = set()
            for r in recs:
                t = r.get("t")
                if t == "batch":
                    if parts_filter & set(int(p) for p in
                                          r.get("parts") or ()):
                        kept.append(r)
                        kept_seqs.add(int(r.get("seq", 0)))
                elif t == "ack":
                    # an ack applies through its dispatch-time batch
                    # record: absorbed iff that batch was
                    if int(r.get("for", 0)) in kept_seqs:
                        kept.append(r)
                # everything else (reg/grow/slots/cluster/reinit/fold/
                # mesh): the previous owner's own topology and idle
                # bookkeeping — never part of a partition handoff
            stats["filtered_out"] = len(recs) - len(kept)
            recs = kept
        n_all = len(recs)
        recs = [r for r in recs if _fresh(r)]
        stats["stale_epoch_dropped"] = n_all - len(recs)
        # acks key their dispatch-time batch record by `for` (the ack's own
        # seq only orders it in the log)
        acks = {int(r["for"]): r for r in recs
                if r.get("t") == "ack" and "for" in r}
        replay_step = make_fused_step_packed(self._release_fn, self._sched_fn)
        replay_release = make_release_packed(self._release_fn)
        # foreign tails run on a LOCAL cursor in the dead owner's seq
        # space; our own journal numbering is untouched
        cursor = (int(from_seq or 0) if foreign else self._journal_seq)
        self._journal_mute = True
        cold = False
        try:
            for rec in recs:
                t = rec.get("t")
                seq = int(rec.get("seq", 0))
                if t == "ack":
                    # already applied through its batch record; still claim
                    # the seq so the promoted active never reuses it
                    cursor = max(cursor, seq)
                    if not foreign:
                        self._journal_seq = cursor
                    continue
                if seq <= cursor:
                    continue
                if t in ("batch", "mesh"):
                    got = int(rec.get("S" if t == "batch" else "n_shards",
                                      1))
                    if got != self.n_shards:
                        if foreign:
                            # NEVER cold-start a live survivor's books
                            # over an absorbed tail: skip the absorb, say
                            # so — the epoch bump (which already
                            # happened) is the correctness guarantee;
                            # the un-replayed holds self-heal
                            if log:
                                log.warn(None, "absorbed journal tail was "
                                               f"written at {got} fleet "
                                               f"shard(s), this balancer "
                                               f"runs {self.n_shards}; "
                                               "skipping the absorb "
                                               "replay", "TpuBalancer")
                            stats["skipped"] = "mesh_topology"
                            break
                        cold = True
                        self._topology_coldstart(stats, recs, got, log)
                        return
                detail = None
                if t == "mesh":
                    pass  # topology verified above; nothing to re-apply
                elif t == "batch":
                    detail = self._replay_batch(rec, acks.get(seq),
                                                replay_step, stats)
                elif t == "fold":
                    self._replay_fold(rec, replay_release)
                elif t == "reg":
                    self._replay_reg(rec)
                elif t == "grow":
                    if int(rec["n_pad"]) > self._n_pad:
                        self._grow_padding(int(rec["n_pad"]))
                elif t == "slots":
                    if int(rec["action_slots"]) > self.action_slots:
                        self._grow_slots(int(rec["action_slots"]))
                elif t == "cluster":
                    self.update_cluster(int(rec["size"]))
                elif t == "reinit":
                    self._init_device_state()
                elif log:
                    log.warn(None, f"journal record type {t!r} unknown "
                                   "(newer writer?); skipped", "TpuBalancer")
                stats["replayed"] += 1
                cursor = max(cursor, seq)
                if not foreign:
                    self._journal_seq = cursor
                yield {"seq": seq, "t": t, "rec": rec, "detail": detail}
        finally:
            self._journal_mute = False
            if not cold:
                self._set_books_now(np.asarray(self.state.free_mb))
                stats["last_seq"] = cursor
                if stats["parity_mismatches"] and log:
                    log.warn(None, f"journal replay re-derived "
                                   f"{stats['parity_mismatches']} decisions "
                                   "differently than the recorded readback "
                                   "(kernel knobs changed across the "
                                   "restart?)", "TpuBalancer")

    def absorb_partitions(self, pids, journal, snap_doc=None,
                          logger=None) -> dict:
        """Partition handoff, absorb side (ISSUE 15): replay the PREVIOUS
        owner's journal tail — filtered to exactly the partitions this
        controller just claimed — through the same kernels, on top of the
        live books. This is PR 8's promote-and-replay scoped per
        partition: the dead (or rebalanced-away) owner's post-snapshot
        in-flight holds for these partitions land on the new owner's
        books conservatively (un-acked rows self-heal via forced
        timeouts), per-partition stale epochs drop, and the previous
        owner's structural records never touch our topology. The
        absorbed tail's seqs stay in the previous owner's numbering
        (`foreign`), so our own journal order is untouched. The epoch
        bump that fences the previous owner happened at claim time
        (set_partition_leadership) — this replay is books-accuracy, the
        fence is the zero-double-execution guarantee.

        Every failure path degrades to skipped-absorb with the fence
        still in place; never an abort."""
        log = logger or self.logger
        pids = set(int(p) for p in pids)
        for pid in pids:
            self.partition_replay[pid] = "replaying"
        from_seq = int((snap_doc or {}).get("journal_seq", 0))
        GLOBAL_EVENT_LOG.record("absorb_start",
                                instance=self.controller.instance,
                                parts=sorted(pids), from_seq=from_seq)
        stats = {"absorbed_partitions": sorted(pids), "replayed": 0}
        try:
            stats = self.replay_journal(journal.records(from_seq),
                                        logger=log, from_seq=from_seq,
                                        parts_filter=pids, foreign=True)
            stats["absorbed_partitions"] = sorted(pids)
        except Exception as e:  # noqa: BLE001 — degrade, never abort: the
            # claim's epoch bump already fences the previous owner
            stats["skipped"] = f"absorb_error: {e!r}"
            if log:
                log.warn(None, f"partition absorb replay failed ({e!r}); "
                               "continuing with the fence only",
                         "TpuBalancer")
        finally:
            for pid in pids:
                self.partition_replay[pid] = "ready"
        GLOBAL_EVENT_LOG.record("absorb_end",
                                instance=self.controller.instance,
                                parts=sorted(pids),
                                replayed=int(stats.get("replayed", 0)),
                                skipped=stats.get("skipped"))
        self.metrics.counter("loadbalancer_partitions_absorbed", len(pids))
        return stats

    def _topology_coldstart(self, stats: dict, recs: list, got: int,
                            log) -> dict:
        """A journal tail written at a different mesh topology cannot be
        replayed here (the packed records are deterministic only through
        the SAME sharded kernels): cold-start — fresh full-capacity books
        over the restored registry; leaked in-flight holds self-heal via
        forced timeouts, exactly the pruned-tail posture — with a logged
        reason. Every seq in the tail is still claimed so a promoted
        active never reuses one."""
        if log:
            log.warn(None, f"placement journal tail was written at {got} "
                           f"fleet shard(s) but this balancer runs "
                           f"{self.n_shards}; cold-starting instead of "
                           f"mis-sharding the replay", "TpuBalancer")
        stats["skipped"] = "mesh_topology"
        stats["journal_shards"] = got
        stats["balancer_shards"] = self.n_shards
        self._journal_seq = max(
            [self._journal_seq] + [int(r.get("seq", 0)) for r in recs])
        self._init_device_state()
        stats["last_seq"] = self._journal_seq
        return stats

    def _replay_batch(self, rec: dict, ack: Optional[dict], replay_step,
                      stats: dict) -> dict:
        R, H, B = int(rec["R"]), int(rec["H"]), int(rec["B"])
        rows, b = int(rec["rows"]), int(rec["b"])
        buf = decode_array(rec["buf"])
        rel = buf[:5 * R]
        health = buf[5 * R:5 * R + 3 * H]
        req = buf[5 * R + 3 * H:].reshape(rows, B)[:9].copy()
        if ack is not None:
            out_rec = np.asarray(ack["out"], np.int64)
            throttled = ((out_rec >> 1) & 1).astype(bool)
            # device rate admission already rejected these at commit time:
            # replay with their valid bit cleared so the re-derived books
            # hold exactly what the committed step held
            req[8, :len(throttled)] &= ~throttled
        buf9 = np.concatenate([rel, health, req.ravel()]).astype(np.int32)
        self.state, out = replay_step(self.state, buf9, R, H, B)
        stats["batches"] += 1
        #: per-batch evidence for the time-travel debugger (timetravel.py):
        #: the driver (replay_journal) ignores it
        detail: dict = {"b": b, "aids": rec.get("aids") or [],
                        "acked": ack is not None, "mismatches": 0}
        if ack is not None:
            derived = journal_words(np.asarray(out)[:b].astype(np.int64))
            recorded = np.asarray(ack["out"], np.int64)[:b]
            thr = ((recorded >> 1) & 1).astype(bool)
            mism = int(np.count_nonzero(derived[~thr] != recorded[~thr]))
            stats["parity_mismatches"] += mism
            detail.update({"derived": derived, "recorded": recorded,
                           "throttled": thr, "mismatches": mism})
        return detail

    def _replay_fold(self, rec: dict, replay_release) -> None:
        if "rel" in rec:
            rel = decode_array(rec["rel"]).reshape(5, -1)
            self.state, _ = replay_release(self.state, rel)
        health = rec.get("health")
        if health:
            self.state = set_health(self.state,
                                    [int(i) for i, _ in health],
                                    [bool(v) for _, v in health])

    def _replay_reg(self, rec: dict) -> None:
        # the rows' health comes from the flips journaled after them
        n0 = len(self._registry)
        for j, healthy in zip(rec["reg"], rec["healthy"]):
            inv = InvokerInstanceId.from_json(j)
            idx = inv.instance
            while idx >= len(self._registry):
                self._registry.append(inv)
                self._healthy.append(False)
            self._registry[idx] = inv
            self._healthy[idx] = bool(healthy)
        if len(self._registry) > n0:
            self._register_rows(n0)
        self._recompute_partitions()
        self._rebuild_caps()

    # -- checkpoint / resume (SURVEY §5.4) ---------------------------------
    def snapshot_parts(self) -> dict:
        """Event-loop-side capture for a snapshot: ONE consistent reference
        to the (immutable) device state plus copies of the host books. The
        heavy device->host transfer can then run on a worker thread
        (checkpoint.BalancerSnapshotter) without racing loop mutations or
        mixing books from different device steps. With buffer donation ON
        the captured state is an explicit device-side COPY: the live
        reference would be consumed (invalidated) by the next pipelined
        dispatch before the worker thread gets to read it."""
        return {
            "state": self._materialize_state(),
            "journal_seq": self._journal_seq,
            "n_pad": self._n_pad,
            "fleet_shards": self.n_shards,
            "cluster_size": self._cluster_size,
            "action_slots": self.action_slots,
            "registry": [inv.to_json() for inv in self._registry],
            "healthy": list(self._healthy),
            "slots": dict(self._slots.slots),
            "slot_refcount": dict(self._slots.refcount),
            "slot_overflow": {k: list(v)
                              for k, v in self._slots.overflow.items()},
        }

    def snapshot(self, parts: Optional[dict] = None) -> dict:
        """Host-side snapshot of the device capacity matrix + registry. The
        balancer state is soft (reconstructible from pings/acks), so this is
        the whole checkpoint story: dump it periodically, restore on boot to
        skip the warm-up window. Thread-safe given `parts` from
        snapshot_parts()."""
        parts = dict(parts) if parts is not None else self.snapshot_parts()
        state = parts.pop("state")
        conc = np.asarray(state.conc_free)
        nz = np.nonzero(conc)
        parts["free_mb"] = np.asarray(state.free_mb).tolist()
        parts["conc_nonzero"] = [[int(i), int(j), int(conc[i, j])]
                                 for i, j in zip(*nz)]
        return parts

    def restore(self, snap: dict) -> None:
        self.profiler.expect("snapshot_restore")
        # the snapshot's books are GLOBAL (topology-independent): restoring
        # them onto a different shard count is a deterministic reshard —
        # _install_state re-places every row on this balancer's own mesh.
        # Said out loud because the JOURNAL tail is not topology-portable
        # (replay_journal cold-starts on a mismatch).
        snap_shards = int(snap.get("fleet_shards", 1))
        if snap_shards != self.n_shards and self.logger:
            self.logger.info(
                None, f"snapshot was taken at {snap_shards} fleet "
                f"shard(s); resharding deterministically onto "
                f"{self.n_shards}", "TpuBalancer")
        # the snapshot's books already hold every journaled mutation up to
        # this seq: replay_journal resumes from here (older snapshots carry
        # no seq — a full-history journal replays from 0)
        self._journal_seq = int(snap.get("journal_seq", 0))
        self._n_pad = int(snap["n_pad"])
        if self.mesh is not None and self._n_pad % self.n_shards:
            # a single-device snapshot may carry a pad the mesh cannot
            # divide: round up (extra rows are unhealthy zero-capacity
            # padding, exactly like growth padding)
            self._n_pad = _next_pow2(max(self._n_pad, self.n_shards))
        self._cluster_size = int(snap["cluster_size"])
        # older snapshots predate the growable slot axis
        self.action_slots = int(snap.get("action_slots", self.action_slots))
        self._registry = [InvokerInstanceId.from_json(j)
                          for j in snap["registry"]]
        self._healthy = [bool(h) for h in snap["healthy"]]
        free = np.asarray(snap["free_mb"], np.int32)
        if len(free) < self._n_pad:  # pad rounded up above
            free = np.concatenate(
                [free, np.zeros((self._n_pad - len(free),), np.int32)])
        conc = np.zeros((self._n_pad, self.action_slots), np.int32)
        for i, j, v in snap.get("conc_nonzero", []):
            conc[i, j] = v
        health = np.zeros((self._n_pad,), bool)
        health[: len(self._healthy)] = self._healthy
        self._install_state(PlacementState(jnp.asarray(free),
                                           jnp.asarray(conc),
                                           jnp.asarray(health)))
        self._slots.n_slots = self.action_slots
        self._slots.slots = dict(snap.get("slots", {}))
        self._slots.refcount = dict(snap.get("slot_refcount", {}))
        self._slots.overflow = {k: [int(v[0]), int(v[1])]
                                for k, v in snap.get("slot_overflow", {}).items()}
        used = set(self._slots.slots.values())
        self._slots.free = [s for s in range(self.action_slots - 1, -1, -1)
                            if s not in used]
        self._recompute_partitions()
        self._rebuild_caps()

    # -- the device step ---------------------------------------------------

    #: the dispatch hold: under arrival pressure a partly filled batch is
    #: held open for DISPATCH_HOLD_K x what one fused step costs the loop,
    #: measured (`_note_step_cost`), instead of dispatching per arrival; an
    #: idle or slow-trickle balancer keeps the eager fast path. What a hold
    #: amortises is that cost, so it is the only thing that sizes it: a
    #: hold of K step costs keeps the steps' share of the loop at or under
    #: 1 / (K + 1) whatever the fleet, the planes or the rows per step, and
    #: a loop that makes its steps wait lengthens the hold by itself.
    #: K settled ON THE CHIP (TPU v5e, one seed a cell, PERF.md section 6,
    #: PR 29), at K = 1 / 2 / 3 / 4 (in brackets the 8 ms constant it
    #: replaced, same seed):
    #:   standalone16-noop-open   12.31 / 9.31 / 9.79 / 11.58 ms (13.72)
    #:   fleet1k-zipf-open        23.87 / 19.19 / 17.89 / 16.57 ms (17.81)
    #:   standalone16-noop-closed 4,351 / 4,249 / 4,051 / 3,334 act/s (3,948)
    #: of overhead_p50_ms and completed_per_s. 1 falls under the arrival
    #: gap at 800/s (a step per arrival: the reverted eager policy), 2
    #: drives fleet1k's half-busy loop into its own queue, 4 holds the
    #: convoy of 128 for 12.6 ms: 3 is the one that costs no cell anything.
    #: (Read at the step's cost of that day: PR 31 took a launch and a
    #: transfer off every step and did not read K again, PERF.md section 7.)
    DISPATCH_HOLD_K = 3
    #: the loop's selector sleeps in whole milliseconds (epoll_wait; CPython
    #: rounds a timeout UP to the next one), and a loop woken from its sleep
    #: starts cold: on an idle loop the hold's timer fired up to a
    #: millisecond late and the estimate below read that lateness as the
    #: step's cost, K + 1 times over (PERF.md section 6, PR 38: until then
    #: the bus's lingering drainers kept the loop turning by accident). So
    #: the flush task sleeps to within one such tick of the hold's end and
    #: yields sweep by sweep through the rest: the hold ends on time
    #: whether the loop is idle or not, at under a tick of turning a step.
    SELECTOR_TICK_S = 1e-3
    #: step costs kept; the estimate is their second smallest, so it
    #: stands while six of eight samples are stalls (set-up's ladder
    #: compiles six bucket shapes in a row; a collection pauses one step
    #: for 50-300 ms) and one freak low moves nothing
    HOLD_SAMPLES = 8

    def _note_arrival(self, now: float) -> None:
        """Track the publish inter-arrival EWMA — the pressure signal the
        dispatch hold switches on. One subtract + one blend per publish."""
        gap_ms = (now - self._last_pub_t) * 1e3
        self._last_pub_t = now
        self._last_gap_ms = gap_ms
        self._gap_ewma_ms = min(0.9 * self._gap_ewma_ms + 0.1 * gap_ms,
                                1000.0)

    def _note_arrivals(self, now: float, n: int) -> None:
        """Arrival accounting for a whole admission batch at ONE shared
        clock read (the ISSUE 14 small fix: the serial path paid a
        time.monotonic() + blend per activation). Equivalent to n serial
        `_note_arrival(now)` calls: the first blends the real gap, the
        remaining n-1 blend zero gaps — a pure 0.9^(n-1) decay, applied
        in closed form (the 1000 ms clamp only ever binds on the first
        blend, since decay shrinks). At n=1 this IS `_note_arrival`,
        bit-exact."""
        self._note_arrival(now)
        if n > 1:
            self._gap_ewma_ms *= 0.9 ** (n - 1)
            self._last_gap_ms = 0.0

    def _note_step_cost(self, cost_s: float) -> None:
        """What one fused step cost the loop, into the estimate the hold is
        derived from: from the moment its hold ran out (the loop's queue in
        front of the flush task, the step lock, the pipeline's room) to
        `_dispatch_batch`'s last line; an inline step has no such moment
        and counts `_dispatch_batch` alone. One sort of HOLD_SAMPLES floats
        a step; nothing per activation."""
        self._step_costs.append(cost_s)
        self._hold_s = self.DISPATCH_HOLD_K * sorted(self._step_costs)[1]
        self.metrics.gauge("loadbalancer_dispatch_hold_ms",
                           self._hold_s * 1e3)

    def _coalesce_window_s(self) -> float:
        """The hold (s) when arrival pressure says it will gather more
        rows, else 0: the inter-arrival EWMA predicts another arrival
        inside one hold, and the instantaneous gap confirms traffic is
        still flowing (a lone request after a burst must not inherit the
        burst's hold)."""
        hold_ms = self._hold_s * 1e3
        if self._gap_ewma_ms <= hold_ms and self._last_gap_ms <= hold_ms:
            return self._hold_s
        return 0.0

    def _arm_flush(self, urgent: bool = False) -> None:
        if getattr(self, "_closing", False):
            return  # close() drains queued releases host-side itself
        window = self._coalesce_window_s()
        # idle fast path: with no step in flight there is nothing to batch
        # WITH — waiting out the window would only add latency (the window
        # exists to amortize a round trip that is already being paid).
        # Under arrival pressure the dispatch hold overrides: the batch
        # forming over the next few ms IS the thing to batch with.
        if self._inflight_steps == 0 and self._pending and window == 0.0:
            urgent = True
        if self._flush_task is None or self._flush_task.done():
            self._flush_task = asyncio.get_event_loop().create_task(
                self._flush_later(0 if urgent
                                  else (window or self.batch_window)))

    async def _flush_later(self, delay: float) -> None:
        # loop INSIDE the task until drained: a tail call to _arm_flush would
        # be a no-op (this task is not done() yet) and strand leftover work
        while True:
            due = time.monotonic() + delay
            if delay > self.SELECTOR_TICK_S:
                await asyncio.sleep(delay - self.SELECTOR_TICK_S)
            while time.monotonic() < due:
                await asyncio.sleep(0)
            async with self._step_lock:
                await self._device_step(delay, due)
            if not (self._pending or self._releases or self._health_updates):
                return
            # a batch that is full already is held for nothing
            delay = (0.0 if len(self._pending) >= self.max_batch
                     else self._coalesce_window_s() or self.batch_window)

    #: request-tuple field indices (row order of the packed matrix)
    R_NEED_MB, R_CONC_SLOT, R_MAX_CONC = 4, 5, 6

    #: namespace-bucket axis for device rate admission
    RATE_NS_BUCKETS = 1024

    #: tail sub-range of the bucket axis reserved for overflow namespaces
    #: (beyond RATE_NS_BUCKETS - RATE_NS_SHARED_BUCKETS dedicated tenants):
    #: they CRC32-hash into these shared buckets, so conflation stays among
    #: overflow namespaces instead of draining dedicated tenants' tokens
    RATE_NS_SHARED_BUCKETS = 64

    #: health updates a fused step carries — a FIXED batch shape, so the
    #: fused program's compile-cache keys vary only in (release, batch)
    #: buckets. A step that finds more buffered (a fleet registering) folds
    #: them all first in one scatter of their own (`_fold_now`, padded to a
    #: power of two); so does an idle fold, which a flip arms. Either way a
    #: flip buffered before a device step is on the device after it
    HEALTH_BATCH = 64

    #: below this measured round trip the device counts as "fast": eager
    #: idle dispatch wins; above it, wave batching wins (slow device round
    #: trips serialize rather than pipeline)
    RTT_FAST_MS = 5.0

    #: don't pay a telemetry-fold dispatch on the hot path for fewer than
    #: this many buffered completion events; the supervision tick and the
    #: scrape-time drain pick up the tail within a second
    TELEMETRY_FOLD_MIN = 64

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        """Pad batch sizes to power-of-two buckets so the jitted kernels see
        at most log2(max_batch) distinct shapes (no per-size recompiles)."""
        b = 8
        while b < n and b < cap:
            b *= 2
        return min(b, cap) if n <= cap else cap

    def _release_packed(self, pad_to: Optional[int] = None) -> np.ndarray:
        """Drain buffered releases into ONE packed int32[5,R] host array
        (+ host-side slot bookkeeping).
        The int columns were written into the release ring at enqueue
        time, so assembly is two contiguous slice copies.

        The per-step drain cap equals max_batch (not a multiple): the
        batch-shaped ack path (ISSUE 12) lands a whole completion
        frame's releases in one sweep, and larger caps reached R buckets
        the steady state never compiles. The backlog still drains at >=
        the ack arrival rate (releases match placements one-to-one), so
        the leftover queue is bounded by one burst.

        `pad_to`: the fused-step caller passes its shared (R, B) bucket
        — see _dispatch_batch's shared-bucket rule — so the release axis
        pads to the SAME power of two as the request axis instead of
        minting an independent static dim."""
        cap = self.max_batch
        rel, self._releases = self._releases[:cap], self._releases[cap:]
        b = self._bucket(len(rel), cap) if rel else 8
        if pad_to is not None:
            b = max(b, pad_to)
        out = np.zeros((5, b), np.int32)
        out[3, len(rel):] = 1  # padded rows: maxc=1
        if rel:
            self._rel_ring.pop_into(out[:4], len(rel))
            out[4, :len(rel)] = 1
        for r in rel:
            self._slots.release(r[4], r[1])
        return out

    def _health_packed(self) -> np.ndarray:
        """Drain up to HEALTH_BATCH flips into ONE packed int32[3,H] array,
        padded by repeating the last flip."""
        b = self.HEALTH_BATCH
        take = list(self._health_updates.items())[:b]
        for k, _ in take:
            del self._health_updates[k]
        out = np.zeros((3, b), np.int32)
        if take:
            pad = b - len(take)
            idxs = [k for k, _ in take] + [take[-1][0]] * pad
            vals = [int(v) for _, v in take] + [int(take[-1][1])] * pad
            out[0] = idxs
            out[1] = vals
            out[2] = 1
        return out

    def _try_flush_now(self) -> bool:
        """Synchronous dispatch fast path: runs the batch dispatch inline
        when the pipeline has capacity and no flush task is mid-step. The
        dispatch body has no awaits, so it is atomic on the event loop."""
        if (self._pending and not self._step_lock.locked()
                and self._inflight_steps < self.pipeline_depth
                and not getattr(self, "_closing", False)):
            self._set_inflight(1)
            self._dispatch_batch()
            return True
        return False

    async def _device_step(self, held_s: float = 0.0,
                           due: Optional[float] = None) -> None:
        if not self._pending:
            # nothing to schedule: fold releases (padded+masked like the
            # fused path) and health (exact-size; dict keys are unique)
            books = None
            try:
                if self._releases or self._health_updates:
                    with span("ow_fold", rows=min(len(self._releases),
                                                  self.max_batch)):
                        books = self._fold_now()
            except Exception as e:  # noqa: BLE001 — a failed donated fold
                # may have CONSUMED self.state: without a rebuild every
                # later idle fold dies on the deleted buffer and a
                # drain-only balancer stays wedged indefinitely. (The
                # popped releases are moot either way: rebuilt books start
                # at full capacity.)
                if not self._recover_consumed_state():
                    raise
                if self.logger:
                    self.logger.error(None, f"idle fold failed: {e!r}",
                                      "TpuBalancer")
            if books is not None:
                # no schedule means no readback to piggyback the occupancy
                # cache on — refresh it off-loop so idle fleets converge
                self._refresh_books_async(books)
            try:
                self._telemetry_fold()
            except Exception as e:  # noqa: BLE001 — a telemetry failure
                # must not kill the flush task (stranding queued releases)
                if self.logger:
                    self.logger.warn(None, f"telemetry fold failed: {e!r}",
                                     "TpuBalancer")
            return

        # bound dispatched-but-unread steps (capacity freed by the readback
        # task) BEFORE popping the batch: a cancellation while waiting here
        # (close() cancels the flush task) must leave the queue intact so
        # close() can fail those publishers instead of stranding them
        while self._inflight_steps >= self.pipeline_depth:
            self._capacity_free.clear()
            await self._capacity_free.wait()
        self._set_inflight(1)
        self._dispatch_batch(held_s, due)

    def _fold_now(self, releases: bool = True):
        """The release-only / health fold and its journal record (one
        `ow_fold` span to one `fold` record): every buffered flip, in one
        scatter padded to a power-of-two bucket, and with `releases` the
        buffered releases. Returns the release fold's books output (a
        device vector of its own), None where only health was folded."""
        rel_np = ups = books = None
        if releases and self._releases:
            rel_np = self._release_packed()
            self.state, books = self._release_packed_fn(self.state, rel_np)
        if self._health_updates:
            ups, self._health_updates = self._health_updates, {}
            idx, vals = self._padded_rows(
                np.fromiter(ups.keys(), np.int32, len(ups)),
                np.fromiter(ups.values(), bool, len(ups)))
            self.state = self.state._replace(
                health=self.state.health.at[idx].set(vals))
        if self._journal_live():
            fold = {"t": "fold"}
            if rel_np is not None:
                fold["rel"] = encode_array(rel_np)
            if ups:
                fold["health"] = [[int(k), bool(v)]
                                  for k, v in ups.items()]
            self._journal_append(fold)
        return books

    def _telemetry_fold(self, seq: int = 0) -> None:
        with span("ow_telemetry_fold", seq=seq):
            self.telemetry.device_fold()

    def _assemble_batch(self, batch, b: int, bp: int, t0: float):
        """Host packing of one micro-batch (the `ow_assemble` span): the
        request matrix, the flight-recorder digest, the drained releases
        and health flips, and the ONE buffer the step takes."""
        # ONE packed request matrix: row layout must match
        # make_fused_step_packed (offset..rand, valid); request tuples are
        # already in row order, so one C-speed np.array call fills it.
        # Padded request columns keep size=1/max_conc=1 like the old
        # pad_req dict
        rate_on = self.rate_limit_per_minute is not None
        rows = 10 if rate_on else 9
        req_np = np.zeros((rows, bp), np.int32)
        req_np[1, b:] = 1  # size
        req_np[6, b:] = 1  # max_conc
        # columns were written at publish() time: drain the b oldest
        # (rate off drops the ring's ns_slot row — pop_into copies only
        # the rows req_np carries)
        self._req_ring.pop_into(req_np, b)
        # flight-recorder input digest, captured host-side before the step
        # (batch is FIFO: batch[0] carries the oldest enqueue time)
        rec = None
        if self.flight_recorder.enabled:
            rec = BatchRecord(digest={
                "kernel": self.kernel_resolved,
                "healthy_invokers": sum(self._healthy),
                "queue_depth": b + len(self._pending),
                "oldest_age_ms": round((t0 - batch[0][3]) * 1e3, 3),
            })
            if self.mesh is not None:
                rec.digest["shards"] = self.n_shards
            tid = next((e[6] for e in batch if e[6]), None)
            if tid is not None:
                # the record carries a trace: the phase histogram's bucket
                # line gets an exemplar pointing at it (OpenMetrics only)
                rec.digest["trace_id"] = tid
        # waterfall: assemble/dispatch/readback are BATCH events — one
        # shared timestamp per edge for every activation in the batch (the
        # aid list is built once, only when the plane is live)
        wf_aids = [e[4] for e in batch] if self.waterfall.enabled else None
        rel_np = self._release_packed(pad_to=bp)
        health_np = self._health_packed()
        # releases + health flips + schedule: ONE device program over ONE
        # host->device transfer and ONE packed result vector back (the old
        # column-wise path did 16 in + 2 out — with a slow device round
        # trip the transfers dominate the step, not the kernel). No
        # await between the pop above and the task creation below, so no
        # cancellation window can orphan the popped batch.
        buf = np.concatenate([rel_np.ravel(), health_np.ravel(),
                              req_np.ravel()])
        return req_np, rec, wf_aids, rel_np, health_np, buf

    def _dispatch_batch(self, held_s: float = 0.0,
                        due: Optional[float] = None) -> None:
        """One fused step. `held_s`: what the flush task slept before it,
        and `due`: when that sleep should have ended; 0 and None from the
        inline paths (a full batch, eager on an idle pipeline)."""
        if len(self._health_updates) > self.HEALTH_BATCH:
            # more flips than a step's fixed health section holds (a fleet
            # registering under traffic): all of them fold first, in one
            # padded scatter of their own, so that no flip waits on the
            # steps after this one
            with span("ow_fold", rows=0):
                self._fold_now(releases=False)
        batch, self._pending = self._pending[: self.max_batch], \
            self._pending[self.max_batch:]
        t0 = time.monotonic()
        b = len(batch)
        # ONE shared power-of-two bucket for the release AND request axes:
        # R and B are independent static dims of the fused program, so
        # their cross product is the jit cache-key space — log2 x log2
        # combos, most compiled mid-run the first time an arrival pattern
        # surfaces them (the batch-shaped ack path made this chronic:
        # measured as repeated ~400 ms first-sight compile stalls).
        # Padding both axes to max(R_bucket, B_bucket) collapses the key
        # space to log2(max_batch) shapes, which one warmup pass covers;
        # the cost is a few masked zero rows in a kernel that is already
        # shape-padded.
        n_rel = min(len(self._releases), self.max_batch)
        bp = max(self._bucket(b, self.max_batch),
                 self._bucket(n_rel, self.max_batch) if n_rel else 8)
        # the id the spans of this micro-batch share: its batch record's
        # journal seq or, journal off, its books seq
        books_seq = self._next_books_seq()
        seq = self._journal_next_seq() or books_seq
        # hold_us: the hold that closed this batch; a full one closed on size
        with span("ow_assemble", seq=seq, b=b, bp=bp, n_rel=n_rel,
                  pending=len(self._pending), inflight=self._inflight_steps,
                  hold_us=int(held_s * 1e6) if b < self.max_batch else 0):
            req_np, rec, wf_aids, rel_np, health_np, buf = \
                self._assemble_batch(batch, b, bp, t0)
            t_assembled = time.monotonic()
        rate_on = self.rate_limit_per_minute is not None
        wf = self.waterfall
        # shadow counterfactual (quality plane, every K batches): a
        # decision-only pass over the SAME packed buffer, enqueued BEFORE
        # the (possibly donating) production step so it reads the
        # pre-step buffers off the device stream. It writes nothing back;
        # `now` is hoisted and shared so the rate-admission fold (a pure
        # function of buckets/now) reproduces the production admitted set
        # exactly.
        now32 = (np.float32(time.monotonic() - self._t0_mono)
                 if rate_on else None)
        shadow_out = None
        if self._shadow_fn is not None:
            self._quality_batches += 1
            k = self.quality.shadow_every_n
            if k > 0 and self._quality_batches % k == 0:
                try:
                    with span("ow_shadow", seq=seq):
                        if rate_on:
                            shadow_out = self._shadow_fn(
                                (self.state, self._bucket_state), buf,
                                self._shadow_penalty, now32,
                                rel_np.shape[1], health_np.shape[1], bp)
                        else:
                            shadow_out = self._shadow_fn(
                                self.state, buf, self._shadow_penalty,
                                rel_np.shape[1], health_np.shape[1], bp)
                except Exception as e:  # noqa: BLE001 — the shadow is
                    # observability: it must never take placement down
                    shadow_out = None
                    if self.logger:
                        self.logger.warn(None, f"shadow step failed: {e!r}",
                                         "TpuBalancer")
        # host-observatory bracket: a GC pause landing inside this window
        # stalls the device dispatch — counting it here turns a mysterious
        # dispatch-stage outlier in the waterfall into an attributed cause
        GLOBAL_HOST_OBSERVATORY.begin_dispatch()
        try:
            # JAX's own `PjitFunction(packed)` span nests in this one
            with span("ow_step", seq=seq):
                if rate_on:
                    (self.state, self._bucket_state), out = self._packed_fn(
                        (self.state, self._bucket_state), buf, now32,
                        rel_np.shape[1], health_np.shape[1], bp)
                else:
                    self.state, out = self._packed_fn(
                        self.state, buf, rel_np.shape[1],
                        health_np.shape[1], bp)
        except Exception as e:  # noqa: BLE001 — a failed dispatch must not
            # leak the permit, the host-side conc slots, or strand the
            # publishers (device capacity from the drained releases is
            # recovered by forced-timeout self-heal)
            self._set_inflight(-1)
            self._capacity_free.set()
            self._recover_consumed_state()
            for req, fut, slot_key, _t, aid, *_ in batch:
                self._slots.release(slot_key, req[self.R_CONC_SLOT])
                wf.discard(aid)
                if not fut.done():
                    fut.set_exception(
                        LoadBalancerException(f"device dispatch failed: {e}"))
            if self.logger:
                self.logger.error(None, f"device dispatch failed: {e!r}",
                                  "TpuBalancer")
            return
        finally:
            GLOBAL_HOST_OBSERVATORY.end_dispatch()

        # quality scoring (every batch when the plane is armed): one tiny
        # read-only program over the POST-commit books, the decision
        # vector and the anomaly EWMAs — enqueued async on the same
        # stream; the summary row resolves on the readback worker
        q_summary = None
        if self.quality.enabled:
            try:
                with span("ow_quality", seq=seq):
                    q_summary = self.quality.device_step(
                        self.state.free_mb, self.state.conc_free,
                        self.state.health, self._quality_ewma,
                        self._quality_caps, req_np[:9], out, shadow_out)
            except Exception as e:  # noqa: BLE001 — scoring must never
                # take the placement path down with it
                if self.logger:
                    self.logger.warn(None, f"quality step failed: {e!r}",
                                     "TpuBalancer")

        # write-ahead journal: the state mutation above is committed on
        # the loop, so the record lands at exactly this point in mutation
        # order (readback appends a matching `ack` with the decisions)
        jseq = 0
        if self._journal_live():
            jrec = {
                "t": "batch", "R": int(rel_np.shape[1]),
                "H": int(health_np.shape[1]), "B": bp,
                "rows": int(req_np.shape[0]), "b": b,
                "buf": encode_array(buf),
                "aids": [e[4] for e in batch]}
            if self.partition_ring is not None:
                # active/active: the record carries its rows' ring
                # partitions plus the epoch each was admitted under, so
                # a handoff replays EXACTLY the partitions the new owner
                # absorbed and drops per-partition stale epochs
                # (replay_journal parts_filter). Off-mode records carry
                # neither key — the wire format is unchanged.
                pe: Dict[str, int] = {}
                for e in batch:
                    if len(e) > 7:
                        p, ep = e[7]
                        pe[str(p)] = max(pe.get(str(p), 0), int(ep))
                jrec["parts"] = sorted(int(p) for p in pe)
                jrec["pe"] = pe
            if self.mesh is not None:
                # shard count travels on EVERY batch record (the one-shot
                # `mesh` header can be pruned away with its snapshot):
                # replay refuses a topology mismatch per batch
                jrec["S"] = self.n_shards
            jseq = self._journal_append(jrec)
        # compile-ahead: warm the successor bucket shapes off-loop before
        # queue growth needs them in a live dispatch
        self._prewarm_buckets(rel_np.shape[1], health_np.shape[1], bp)
        # completion telemetry rides the SAME dispatch cycle: at most one
        # extra scatter-add program per batch over event rows already packed
        # host-side — asynchronous like the step itself, no readback (counts
        # stay on device until a scrape). Small tails are left for the 1 Hz
        # supervision tick / scrape-time drain instead of paying a dispatch
        # for a near-empty fold on every micro-batch.
        try:
            if self.telemetry.pending >= self.TELEMETRY_FOLD_MIN:
                self._telemetry_fold(seq)
        except Exception as e:  # noqa: BLE001 — telemetry must never take
            # the placement path down with it
            if self.logger:
                self.logger.warn(None, f"telemetry fold failed: {e!r}",
                                 "TpuBalancer")
        # phase breakdown (bench + ops visibility): assembly is host numpy
        # packing, dispatch is the jit enqueue (transfers + program launch)
        t_dispatched = time.monotonic()
        if wf_aids is not None:
            wf.stamp_many(wf_aids, STAGE_BATCH_ASSEMBLE,
                          int(t_assembled * 1e9))
            wf.stamp_many(wf_aids, STAGE_DEVICE_DISPATCH,
                          int(t_dispatched * 1e9))
        self.metrics.histogram("loadbalancer_tpu_batch_size", b)
        self.profiler.observe_phase("assembly", (t_assembled - t0) * 1e3)
        self.profiler.observe_phase("dispatch",
                                    (t_dispatched - t_assembled) * 1e3)
        if rec is not None:
            rec.timings["assembly_ms"] = round((t_assembled - t0) * 1e3, 3)
            rec.timings["dispatch_ms"] = round(
                (t_dispatched - t_assembled) * 1e3, 3)
        # pipelined readback: dispatch returns future arrays immediately, so
        # the NEXT batch can dispatch (chained on device) while this batch's
        # results cross the wire on a worker thread — when the device
        # round trip dwarfs the compute, serializing them caps
        # throughput at batch/RTT. Dispatch stays event-loop-serialized
        # under the step lock; only readbacks overlap.
        # under donation the NEXT dispatched step consumes self.state's
        # buffers while this step's readback is still crossing the wire:
        # the worker reads the post-step books off `out`, never the state
        task = asyncio.get_event_loop().create_task(
            self._readback_step(batch, b, out, t0, req_np, rec,
                                books_seq, jseq, q_summary, seq))
        self._readbacks.add(task)
        task.add_done_callback(self._readbacks.discard)
        self._note_step_cost(time.monotonic() - (due or t0))

    def _refresh_books_async(self, books) -> None:
        """Refresh occupancy()'s cached books off a device step that has no
        readback of its own (the idle release fold): `books` is that
        fold's own output, which no later dispatch consumes; convert it on
        a worker thread. Tracked in _readbacks so close() drains it."""
        seq = self._next_books_seq()

        async def _pull():
            self._install_books(await asyncio.to_thread(np.asarray, books),
                                seq)

        task = asyncio.get_event_loop().create_task(_pull())
        self._readbacks.add(task)
        task.add_done_callback(self._readbacks.discard)

    def _read_back(self, step):
        """Readback seam (runs on the worker thread): what the loop is told
        a step decided, (chosen, forced, throttled, repair rounds) out of
        the decoded host copy of its output. A separate method so tests
        can inject readback failures and altered answers."""
        return step[:4]

    async def _readback_step(self, batch, b, out, t0, req_np, rec=None,
                             books_seq=0, journal_seq=0, q_summary=None,
                             seq=0) -> None:
        # the step-duration stamp is taken ON the worker thread so the
        # metric measures device step + readback, not loop re-scheduling
        def _read():
            with span("ow_readback_wait", seq=seq):
                return _read_spanned()

        def _read_spanned():
            t_r0 = time.monotonic()
            # the step's ONE device->host transfer: decision words, repair
            # rounds and the post-step books are slices of this host copy
            step = unpack_step_output(np.asarray(out), req_np.shape[1])
            arrs = self._read_back(step)
            # the kernels' exact warm bit (the seam's 4-tuple stays what
            # tests inject), and the step's counts of rows placed on a
            # spare permit of a container already there and of rows
            # forced: counted here, off the loop; both count every
            # journaled step's rows, abandoned ones included
            counts = (int(np.count_nonzero(step.warm[:b])),
                      int(np.count_nonzero(arrs[1][:b])))
            t_r1 = time.monotonic()
            rb_ms = (t_r1 - t_r0) * 1e3
            self.metrics.histogram("loadbalancer_tpu_readback_ms", rb_ms)
            self.profiler.observe_phase("readback", rb_ms)
            # benign cross-thread write: a float EWMA steering a heuristic
            self._rtt_ewma_ms = 0.8 * self._rtt_ewma_ms + 0.2 * rb_ms
            # the EWMA silently flips the eager-vs-batched dispatch policy
            # at RTT_FAST_MS — exported so operators can SEE which regime
            # the balancer is in (not just infer it from latency shifts)
            self.metrics.gauge("loadbalancer_readback_rtt_ms",
                               self._rtt_ewma_ms)
            # POST-step books, as the step itself returned them: they
            # also refresh occupancy()'s cache so the admin endpoint never
            # needs its own device sync — the install itself happens back
            # on the loop, sequence-guarded (worker threads finish out of
            # order under the pipeline)
            free_np = step.books
            if rec is not None:
                caps = self._caps_mb
                n_reg = min(len(caps), len(free_np))
                cap_total = int(caps[:n_reg].sum())
                used = cap_total - int(free_np[:n_reg].sum())
                rec.digest["free_slot_hist"] = free_slot_histogram(
                    free_np[:n_reg], MIN_SLOT_MB)
                rec.digest["occupancy"] = (
                    round(used / cap_total, 4) if cap_total else 0.0)
                rec.timings["readback_ms"] = round(rb_ms, 3)
            # quality summary: resolved here on the worker alongside the
            # books it was computed from (the scorer program has had the
            # whole readback round trip to complete)
            if q_summary is not None:
                try:
                    s = np.asarray(q_summary)
                    self.quality.note_summary(s)
                    if rec is not None:
                        rec.digest["quality"] = {
                            "regret_ms": round(float(s[S_REGRET_SUM_MS]), 3),
                            "imbalance_cov": round(
                                float(s[S_IMBALANCE_COV]), 4),
                            "divergent": int(s[S_DIVERGENT]),
                        }
                except Exception as e:  # noqa: BLE001 — a failed score
                    # readout must not fail the batch readback
                    if self.logger:
                        self.logger.warn(
                            None, f"quality summary failed: {e!r}",
                            "TpuBalancer")
            return arrs, step.warm, counts, t_r1, free_np

        try:
            (chosen_np, forced_np, throttled_np, rounds), warm_np, \
                (n_warm, n_forced), t_done, books_np = \
                await asyncio.to_thread(_read)
            with span("ow_readback_resume", seq=seq):
                self._install_books(books_np, books_seq)
                if journal_seq and self._journal_live():
                    # the committed decision vector, keyed to the
                    # dispatch-time batch record: replay asserts parity
                    # against it, and the throttled bits tell replay which
                    # requests the device rate admission rejected (they
                    # consumed no capacity); the layout is the journal's
                    # own (placement.journal_words: no warm bit)
                    enc = (((chosen_np[:b].astype(np.int64) + 1) << 2)
                           | (throttled_np[:b].astype(np.int64) << 1)
                           | forced_np[:b].astype(np.int64))
                    self._journal_append({"t": "ack", "for": journal_seq,
                                          "out": [int(v) for v in enc]})
        except Exception as e:  # noqa: BLE001 — publishers must not hang,
            # and their host-side conc slots must not leak. The DISPATCH
            # succeeded (only the host conversion failed), so the device
            # state holds this batch's placements with no publisher left to
            # ever release them. Reverse them ON DEVICE — `out` is still
            # a device array, so no readback is needed to undo exactly what
            # the schedule fold acquired (release_batch is its inverse).
            compensated = True
            try:
                chosen = unpack_step_output(out, req_np.shape[1]).chosen
                rel = jnp.stack([
                    jnp.maximum(chosen, 0).astype(jnp.int32),
                    jnp.asarray(req_np[5]), jnp.asarray(req_np[4]),
                    jnp.asarray(req_np[6]),
                    jnp.asarray(req_np[8]) * (chosen >= 0).astype(jnp.int32)])
                with span("ow_fold", rows=b):
                    self.state, _ = self._release_packed_fn(self.state, rel)
                    if journal_seq and self._journal_live():
                        # the dispatch-time batch record stands; journal
                        # its on-device reversal so replay undoes it
                        # identically (np.asarray syncs, but this is
                        # already an error path)
                        self._journal_append({"t": "fold",
                                              "rel": encode_array(
                                                  np.asarray(rel))})
            except Exception:  # noqa: BLE001 — device genuinely dead: keep
                # the host refcounts PINNED so the slot indices cannot be
                # reassigned to a different action and inherit the phantom
                # concurrency; restart/self-heal owns recovery from here.
                # If the failed release consumed the donated state, rebuild
                # it so the dispatch loop itself survives the outage.
                compensated = False
                self._recover_consumed_state()
            for req, fut, slot_key, _t, aid, *_ in batch:
                if compensated:
                    self._slots.release(slot_key, req[self.R_CONC_SLOT])
                self.waterfall.discard(aid)
                if not fut.done():
                    fut.set_exception(
                        LoadBalancerException(f"device step failed: {e}"))
            self._set_inflight(-1)
            self._capacity_free.set()
            # already surfaced through the futures — re-raising would only
            # produce unretrieved-task noise on the loop
            if self.logger:
                self.logger.error(None, f"device readback failed: {e!r} "
                                  f"(compensated={compensated})",
                                  "TpuBalancer")
            return
        with span("ow_fanout", seq=seq, b=b, warm=n_warm, forced=n_forced):
            self._set_inflight(-1)
            self._capacity_free.set()
            wf = self.waterfall
            if wf.enabled:
                wf.stamp_many([e[4] for e in batch], STAGE_DEVICE_READBACK,
                              int(t_done * 1e9))
            dt_ms = (t_done - t0) * 1e3
            self.metrics.histogram("loadbalancer_tpu_schedule_batch_ms",
                                   dt_ms)
            self.metrics.counter("loadbalancer_tpu_scheduled", b)
            self.metrics.counter("loadbalancer_tpu_warm_placements", n_warm)
            if self.placement_kernel_resolved == "repair" and rounds > 0:
                # how many speculate-commit rounds the batch actually cost
                # — the knob's health signal (repair pays off iff this
                # stays near 1; a fleet-sized spike means pathological
                # intra-batch contention and the scan kernel would serve
                # better). Batches the "auto" hybrid routed to the scan
                # program report 0 and stay out of the histogram.
                self.metrics.histogram("loadbalancer_repair_rounds", rounds)
                if rec is not None:
                    rec.digest["repair_rounds"] = rounds
            t_f0 = time.monotonic()
            for (req, fut, slot_key, _t, aid, *_), inv_idx, f, thr in zip(
                    batch, chosen_np, forced_np, throttled_np):
                if fut.cancelled():
                    # abandoned publisher (client disconnected while
                    # awaiting placement): nobody will ever ack this
                    # activation, so give back what the schedule fold
                    # reserved for it (throttled requests carry chosen ==
                    # -1: nothing was reserved) — and drop its waterfall
                    # vector, which will never finish
                    self._abandon_placement(int(inv_idx), req, slot_key)
                    wf.discard(aid)
                elif not fut.done():
                    fut.set_result((-2 if thr else int(inv_idx), bool(f)))
            fanout_ms = (time.monotonic() - t_f0) * 1e3
        with span("ow_record", seq=seq):
            self._record_step(rec, batch, chosen_np, forced_np,
                              throttled_np, warm_np, fanout_ms, dt_ms, b)

    def _record_step(self, rec, batch, chosen_np, forced_np, throttled_np,
                     warm_np, fanout_ms: float, dt_ms: float, b: int) -> None:
        """What the profiler, the flight recorder and the trace store take
        from one read-back micro-batch (the `ow_record` span)."""
        prof = self.profiler
        prof.observe_phase("fanout", fanout_ms)
        prof.observe_phase("total", dt_ms,
                           trace_id=(rec.digest.get("trace_id")
                                     if rec is not None else None))
        if rec is not None:
            # tail sampling: with a threshold armed, full per-decision rows
            # are filed only for slow batches (a live capture window takes
            # everything); skipped batches still refresh the gauges
            self._record_batch(rec, batch, chosen_np, forced_np, throttled_np,
                               warm_np, fanout_ms,
                               file=prof.admit_batch(dt_ms))
            # after the record files: the device span's batch_seq tag is
            # the assigned ring seq (the join key /admin/trace ships)
            self._trace_batch_hooks(rec, batch, forced_np, dt_ms, b)
            if prof.capture_armed:
                row = rec.to_json()
                row["total_ms"] = round(dt_ms, 3)
                prof.capture_step(row)
        elif prof.capture_armed:
            # flight recorder off: the capture window still gets timings
            prof.capture_step({"ts": time.time(), "batch_size": b,
                               "total_ms": round(dt_ms, 3)})

    def _trace_batch_hooks(self, rec, batch, forced_np, dt_ms: float,
                           b: int) -> None:
        """ISSUE 18 trace-observatory riders for one placed micro-batch,
        all from stamps already taken (rec.ts, dt_ms — no new clock
        reads): the per-batch `device_dispatch` span under the digest's
        trace id (the flight-recorder link the assembled tree joins on),
        the `divergent` mark when the shadow counterfactual disagreed,
        the `exemplar` force-keep (the phase histogram just pinned this
        trace id onto a bucket line — every rendered exemplar must
        resolve), and the `forced` mark per force-placed row. A mark is
        a reason to KEEP a trace, so a warm placement gets none: a kept
        trace reads `warm` off its flight-recorder row (`placement`)."""
        from ...utils.tracestore import GLOBAL_TRACE_STORE, synthetic_span
        store = GLOBAL_TRACE_STORE
        if not store.active:
            return
        tid = rec.digest.get("trace_id")
        if tid:
            store.emit(synthetic_span(
                tid, "device_dispatch", rec.ts, rec.ts + dt_ms / 1e3,
                tags={"proc": f"controller{self.controller.name}",
                      "batch_seq": str(rec.seq),
                      "kernel": str(rec.digest.get("kernel")),
                      "batch_size": str(b)}))
            if self.profiler.enabled:
                store.force(tid, "exemplar")
            q = rec.digest.get("quality")
            if q and q.get("divergent"):
                store.mark(tid, "divergent")
        for e, f in zip(batch, forced_np):
            if f and e[6]:
                store.mark(e[6], "forced")

    def _record_batch(self, rec, batch, chosen_np, forced_np, throttled_np,
                      warm_np, fanout_ms: float, file: bool = True) -> None:
        """Finish and file the flight-recorder record for one micro-batch,
        and refresh the introspection gauges. `file=False` (tail-sampled
        fast batch) refreshes the gauges without ringing the record."""
        rec.timings["fanout_ms"] = round(fanout_ms, 3)
        fr = self.flight_recorder
        if file:
            n_reg = len(self._registry)
            decisions = rec.decisions
            for (req, fut, slot_key, t_enq, aid, act, _tid, *_), ci, f, thr, \
                    w in zip(batch, chosen_np, forced_np, throttled_np,
                             warm_np):
                ci = int(ci)
                name = (self._registry[ci].as_string
                        if 0 <= ci < n_reg else None)
                decisions.append((aid, act, ci, name, bool(f), bool(thr),
                                  req[self.R_NEED_MB], bool(w)))
            fr.record(rec)
        m = self.metrics
        d = rec.digest
        m.gauge("loadbalancer_placement_queue_depth", d["queue_depth"])
        m.gauge("loadbalancer_placement_batch_age_ms", d["oldest_age_ms"])
        m.gauge("loadbalancer_healthy_invokers", d["healthy_invokers"])
        m.gauge("loadbalancer_fleet_occupancy_ratio", d.get("occupancy", 0.0))
        m.gauge("loadbalancer_flight_recorder_dropped", fr.dropped)


class TpuBalancerProvider:
    @staticmethod
    def instance(**kwargs) -> TpuBalancer:
        return TpuBalancer(**kwargs)
