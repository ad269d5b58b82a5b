"""Kernel profiling observatory: the compiled data path, observed.

The flight recorder (PR 1) explains *where* a placement went and the
telemetry plane (PR 2) says *whether* the fleet meets its SLOs — but the
fused TPU program itself was still a black box: an unexpected recompile
(shape churn, a growth event) silently costs hundreds of milliseconds of
placement latency, and nothing in the stack could report why the device
path degraded. This module closes that gap with four host-side surfaces,
all wired through the balancer base-class hook so the CPU-twin balancers
(sharding, lean) report through the same plane with a `kernel: "cpu"`
profile:

  1. **Compile tracking** — `wrap(name, fn)` interposes on a jitted entry
     point and detects compile events by jit-cache-key signature (shapes +
     dtypes of array args, values of static scalars: exactly what keys the
     XLA cache). Each event records wall time and a classification:
     *expected* (first call, a growth/swap event the balancer flagged via
     `expect(reason)`, or a signature the entry's `expected` predicate
     blesses — the power-of-two batch buckets) or *unexpected* shape
     churn. Churn trips the recompile watchdog: a structured warning and a
     `loadbalancer_kernel_recompiles_total{expected="false"}` bump.
  2. **Per-phase device timing** — `observe_phase` folds the dispatch
     cycle's assembly/dispatch/readback/fanout millis into log2 bucket
     counts rendered as a real Prometheus histogram family
     (`loadbalancer_phase_duration_seconds{phase=...}`) via the
     `MetricEmitter.register_renderer` hook, plus a per-phase sliding
     window for p50/p99 rollups on the admin surface.
  3. **HBM watermarks** — `refresh_memory` reads `device.memory_stats()`
     (guarded: a no-op on backends without it, e.g. CPU) into
     `loadbalancer_hbm_*` gauges on the supervision tick, keeping a
     high-watermark across ticks even when the backend reports no peak.
  4. **The capture plane** — `arm_capture(n)` records the next n dispatch
     steps at full detail (optionally wrapping `jax.profiler.trace` into a
     server-side directory when the real profiler is importable), and
     `admit_batch` implements tail sampling: with a threshold configured,
     full per-decision flight-recorder rows are kept only for batches
     slower than it — deep detail gets cheaper, not pricier, at scale.

Hot-path budget: with profiling disabled, `wrap` returns the function
unchanged and every other entry point returns before allocating — a true
no-op (asserted by tier-1). Enabled, the steady-state cost per dispatch is
one signature tuple + dict hit per wrapped call and one bucket increment
per phase; everything else (classification, logging, capture) runs only on
the rare compile/capture events. Off-switch: `CONFIG_whisk_profiling_*`.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..utils.config import load_config
from ..utils.ring_buffer import SeqRingBuffer

#: phase-duration bucket upper bounds, ms: 1/16 ms .. ~8.2 s, log2-spaced
#: (assembly runs tens of microseconds; a slow device readback runs ~100 ms)
PHASE_BOUNDS_MS: List[float] = [2.0 ** e for e in range(-4, 14)]
_PHASE_BOUNDS = np.asarray(PHASE_BOUNDS_MS, np.float64)


@dataclass(frozen=True)
class ProfilingConfig:
    """`CONFIG_whisk_profiling_*` env overrides."""
    enabled: bool = True
    #: compile events kept in the log ring
    compile_log: int = 256
    #: per-phase samples kept for the p50/p99 rollups
    phase_window: int = 512
    #: hard cap on the steps one capture window may arm
    capture_limit: int = 256
    #: how long a flagged `expect(reason)` stays live, seconds
    expect_window_s: float = 30.0
    #: >0: the flight recorder keeps full per-decision rows only for
    #: batches slower than this (tail sampling); 0 keeps everything
    tail_threshold_ms: float = 0.0


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def pow2_statics(*args: Any) -> bool:
    """The TPU balancer's expected-shape predicate: every static int
    argument (batch bucket widths) is a power of two — the shapes its
    `_bucket` padding is allowed to produce. Anything else is churn."""
    return all(_is_pow2(a) for a in args
               if isinstance(a, int) and not isinstance(a, bool))


def _sig_of(x: Any) -> Any:
    """One leaf of a jit cache-key signature: array-likes key by
    (shape, dtype) — exactly what XLA's cache keys on — and python
    scalars key by value (they are static arguments to the jit)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    if isinstance(x, (tuple, list)):  # NamedTuple pytrees included
        return tuple(_sig_of(e) for e in x)
    if isinstance(x, (bool, int, float, str)) or x is None:
        return ("static", x)
    return ("obj", type(x).__name__)


class _PhaseAgg:
    """Per-phase accumulation: log2 bucket counts (exposition) + a
    pre-sized sliding sample window (p50/p99 rollups). One bucket
    increment and one window write per observation — no growth. Traced
    observations additionally pin the latest exemplar on their bucket
    (bucket_idx -> (labels, value_ms, unix_ts)), linking the histogram's
    OpenMetrics rendering back to a trace."""

    __slots__ = ("counts", "sum_ms", "count", "window", "cursor",
                 "exemplars")

    def __init__(self, window: int):
        self.counts = np.zeros(len(PHASE_BOUNDS_MS) + 1, np.int64)
        self.sum_ms = 0.0
        self.count = 0
        self.window = np.zeros(max(8, window), np.float64)
        self.cursor = 0
        self.exemplars: Dict[int, tuple] = {}

    def add(self, ms: float, trace_id: Optional[str] = None) -> None:
        b = int(np.searchsorted(_PHASE_BOUNDS, ms, "left"))
        self.counts[b] += 1
        self.sum_ms += ms
        self.window[self.cursor] = ms
        self.cursor = (self.cursor + 1) % self.window.shape[0]
        self.count += 1
        if trace_id is not None:
            self.exemplars[b] = ({"trace_id": trace_id}, ms, time.time())

    def rollup(self) -> dict:
        n = min(self.count, self.window.shape[0])
        win = np.sort(self.window[:n]) if n else self.window[:0]
        return {
            "count": self.count,
            "mean_ms": round(self.sum_ms / self.count, 4) if self.count else None,
            "p50_ms": round(float(win[n // 2]), 4) if n else None,
            "p99_ms": round(float(win[min(n - 1, int(n * 0.99))]), 4) if n else None,
        }


class KernelProfiler:
    """One per balancer (base-class hook, like the flight recorder)."""

    def __init__(self, config: Optional[ProfilingConfig] = None,
                 logger=None, metrics=None):
        self.config = config or ProfilingConfig()
        self.enabled = self.config.enabled
        self.logger = logger
        self.metrics = metrics
        self.tail_threshold_ms = float(self.config.tail_threshold_ms)
        #: entry name -> {"fn", "seen": {sig: calls}, "compiles", "expected"}
        self._entries: Dict[str, dict] = {}
        self._compile_log: SeqRingBuffer[dict] = SeqRingBuffer(
            max(1, int(self.config.compile_log)))
        self.compiles_expected = 0
        self.compiles_unexpected = 0
        #: batches the tail sampler dropped full rows for
        self.tail_skipped = 0
        self._expect_reason: Optional[str] = None
        self._expect_until = 0.0
        #: observe_phase is called from the readback worker thread AND the
        #: event loop; rollup/render from scrape threads
        self._phase_lock = threading.Lock()
        self._phases: Dict[str, _PhaseAgg] = {}
        # capture plane
        self._capture_remaining = 0
        self._capture_rows: List[dict] = []
        self._capture_started: Optional[float] = None
        self._trace_dir: Optional[str] = None
        self._trace_active = False
        # HBM watermark across ticks (backends without peak_bytes_in_use)
        self._hbm_high_water = 0
        self._mem_refreshed = 0.0

    @classmethod
    def from_config(cls, logger=None, metrics=None) -> "KernelProfiler":
        return cls(config=load_config(ProfilingConfig, env_path="profiling"),
                   logger=logger, metrics=metrics)

    # -- compile tracking --------------------------------------------------
    def expect(self, reason: str) -> None:
        """Flag that upcoming compiles are expected (growth event, kernel
        swap, restore): classification windows for `expect_window_s`."""
        if not self.enabled:
            return
        self._expect_reason = reason
        self._expect_until = time.monotonic() + self.config.expect_window_s

    def wrap(self, name: str, fn: Callable,
             expected: Optional[Callable[..., bool]] = None) -> Callable:
        """Interpose on a jitted entry point. Disabled -> `fn` unchanged.
        Re-wrapping a name with a NEW callable (the balancer rebuilt its
        fused program) resets the signature cache: the fresh jit cache
        will compile every signature again, and those compiles classify
        through the expect window the balancer flags around rebuilds."""
        if not self.enabled:
            return fn
        entry = self._entries.get(name)
        if entry is None or entry["fn"] is not fn:
            # re-registering a NAME with a new callable is a kernel/backend
            # swap (pallas->xla VMEM fallback, growth rebuild): stamp it
            # so the fresh cache's compiles classify as
            # the swap they are — for expect_window_s after the rebuild —
            # instead of leaning on first_call (one compile only) or the
            # shape predicate, and never as shape_churn
            rebuilt = None if entry is None else time.monotonic()
            entry = {"fn": fn, "seen": {}, "compiles": 0,
                     "expected": expected, "rebuilt_at": rebuilt}
            self._entries[name] = entry
        seen = entry["seen"]

        def profiled(*args):
            if not self.enabled:
                return fn(*args)
            sig = tuple(_sig_of(a) for a in args)
            hit = seen.get(sig)
            if hit is not None:
                seen[sig] = hit + 1
                return fn(*args)
            # cache miss: this call traces + compiles (jax compiles
            # synchronously, so the call's wall time covers the compile)
            t0 = time.monotonic()
            out = fn(*args)
            wall_ms = (time.monotonic() - t0) * 1e3
            seen[sig] = 1
            self._on_compile(name, entry, sig, args, wall_ms)
            return out

        profiled.__wrapped__ = fn
        profiled._kernel_profiled = True
        return profiled

    def _on_compile(self, name: str, entry: dict, sig: tuple, args: tuple,
                    wall_ms: float) -> None:
        rebuilt_at = entry.get("rebuilt_at")
        if self._expect_reason is not None \
                and time.monotonic() < self._expect_until:
            exp, reason = True, self._expect_reason
        elif rebuilt_at is not None and (time.monotonic() - rebuilt_at
                                         < self.config.expect_window_s):
            # a freshly swapped-in entry point recompiling its working set
            # (see wrap): expected, whatever the signature looks like
            exp, reason = True, "kernel_swap"
        elif entry["compiles"] == 0:
            exp, reason = True, "first_call"
        elif entry["expected"] is not None and entry["expected"](*args):
            exp, reason = True, "bucketed_shape"
        else:
            exp, reason = False, "shape_churn"
        entry["compiles"] += 1
        if exp:
            self.compiles_expected += 1
        else:
            self.compiles_unexpected += 1
        event = {
            "ts": round(time.time(), 3),
            "entry": name,
            "signature": repr(sig),
            "wall_ms": round(wall_ms, 3),
            "expected": exp,
            "reason": reason,
        }
        self._compile_log.append(event)
        if self.metrics is not None:
            self.metrics.counter(
                "loadbalancer_kernel_recompiles_total",
                tags={"expected": "true" if exp else "false"})
        if not exp and self.logger is not None:
            # the recompile watchdog: churn costs hundreds of ms of
            # placement latency per event — say so, with the shape key
            self.logger.warn(
                None, f"unexpected kernel recompile (shape churn): "
                f"entry={name} wall_ms={wall_ms:.1f} sig={sig}",
                "KernelProfiler")

    def compile_log(self, n: int = 50) -> List[dict]:
        return self._compile_log.last(n)

    def cache_census(self) -> dict:
        """Per entry point: live cache keys, compiles paid, total calls."""
        return {name: {
            "signatures": len(e["seen"]),
            "compiles": e["compiles"],
            "calls": int(sum(e["seen"].values())),
        } for name, e in self._entries.items()}

    # -- per-phase device timing -------------------------------------------
    def observe_phase(self, phase: str, ms: float,
                      trace_id: Optional[str] = None) -> None:
        """Fold one phase duration in. `trace_id` (from a flight-recorder
        row that carried a trace context) pins an exemplar on the bucket
        this observation lands in — rendered only on OpenMetrics scrapes."""
        if not self.enabled:
            return
        with self._phase_lock:
            agg = self._phases.get(phase)
            if agg is None:
                agg = _PhaseAgg(self.config.phase_window)
                self._phases[phase] = agg
            agg.add(ms, trace_id)

    def phase_rollups(self) -> dict:
        with self._phase_lock:
            return {phase: agg.rollup()
                    for phase, agg in self._phases.items()}

    def prometheus_text(self, openmetrics: bool = False) -> str:
        """The phase-duration histogram family, rendered through the same
        exposition helpers as the telemetry plane (register_renderer
        hook). Empty while no phases observed (or disabled). When the
        scrape negotiated OpenMetrics, bucket lines carry the pinned
        trace exemplars (the classic text format has no exemplar syntax,
        so they are omitted there)."""
        if not self.enabled:
            return ""
        from ..controller.monitoring import histogram_family_text
        with self._phase_lock:
            rows = [(phase, agg.counts.copy(), agg.sum_ms)
                    for phase, agg in sorted(self._phases.items())]
            exemplars = ({phase: dict(agg.exemplars)
                          for phase, agg in self._phases.items()
                          if agg.exemplars} if openmetrics else None)
        if not rows:
            return ""
        return "\n".join(histogram_family_text(
            "openwhisk_loadbalancer_phase_duration_seconds", "phase",
            rows, PHASE_BOUNDS_MS, exemplars=exemplars))

    # -- HBM / memory watermarks -------------------------------------------
    def memory_stats(self) -> dict:
        """`device.memory_stats()` of the first local device, guarded: CPU
        backends (and PJRT plugins without the API) answer {}."""
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 — introspection must never raise
            return {}
        if not stats:
            return {}
        return {k: int(v) for k, v in stats.items()
                if isinstance(v, (int, np.integer))}

    def refresh_memory(self, metrics=None) -> dict:
        """Refresh the `loadbalancer_hbm_*` gauges (supervision tick)."""
        if not self.enabled:
            return {}
        stats = self.memory_stats()
        if not stats:
            return {}
        in_use = stats.get("bytes_in_use", 0)
        self._hbm_high_water = max(self._hbm_high_water,
                                   stats.get("peak_bytes_in_use", in_use))
        out = {
            "loadbalancer_hbm_bytes_in_use": in_use,
            "loadbalancer_hbm_peak_bytes_in_use": self._hbm_high_water,
        }
        limit = stats.get("bytes_limit")
        if limit:
            out["loadbalancer_hbm_bytes_limit"] = limit
            out["loadbalancer_hbm_utilization_ratio"] = round(
                in_use / limit, 6)
        m = metrics if metrics is not None else self.metrics
        if m is not None:
            for k, v in out.items():
                m.gauge(k, v)
        return out

    def maybe_refresh_memory(self, metrics=None,
                             min_interval_s: float = 1.0) -> None:
        """`refresh_memory` with a 1 Hz cap, for balancers without a
        supervision scheduler (lean) that refresh off the dispatch/
        completion stream — the analogue of TelemetryPlane.maybe_tick."""
        if not self.enabled:
            return
        now = time.monotonic()
        if now - self._mem_refreshed < min_interval_s:
            return
        self._mem_refreshed = now
        self.refresh_memory(metrics)

    # -- capture plane + tail sampling -------------------------------------
    @property
    def capture_armed(self) -> bool:
        return self._capture_remaining > 0

    def arm_capture(self, steps: int, trace_dir: Optional[str] = None,
                    tail_threshold_ms: Optional[float] = None) -> dict:
        """Arm a bounded capture window: the next `steps` dispatch steps
        are recorded at full detail (capped at `capture_limit`). With
        `trace_dir`, also starts a `jax.profiler` trace into it when the
        real profiler is importable (stopped when the window drains).
        `tail_threshold_ms` re-targets the tail sampler (0 disables)."""
        steps = max(1, min(int(steps), int(self.config.capture_limit)))
        if self._trace_active:
            self._stop_trace()  # re-arm replaces any live trace
        self._capture_rows = []
        self._capture_remaining = steps
        self._capture_started = time.time()
        if tail_threshold_ms is not None:
            self.tail_threshold_ms = max(0.0, float(tail_threshold_ms))
        trace = {"requested": trace_dir is not None, "active": False}
        if trace_dir is not None:
            try:
                import jax.profiler
                jax.profiler.start_trace(trace_dir)
                self._trace_dir = trace_dir
                self._trace_active = True
                trace["active"] = True
            except Exception as e:  # noqa: BLE001 — the capture window
                # still works without the device trace
                trace["error"] = repr(e)
        return {"armed": True, "steps": steps, "trace": trace,
                "tail_threshold_ms": self.tail_threshold_ms}

    def _stop_trace(self) -> None:
        self._trace_active = False
        try:
            import jax.profiler
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — a dead trace must not take
            pass           # the dispatch path down with it

    def capture_step(self, row: dict) -> bool:
        """Record one dispatch step into the armed window; returns False
        (and stays a no-op) when no window is armed."""
        if not self.enabled or self._capture_remaining <= 0:
            return False
        self._capture_rows.append(row)
        self._capture_remaining -= 1
        if self._capture_remaining == 0 and self._trace_active:
            self._stop_trace()
        return True

    def admit_batch(self, total_ms: float) -> bool:
        """Tail-sampling admission for full flight-recorder rows: with a
        threshold set, only batches slower than it keep per-decision
        detail — unless a capture window wants everything. Counts what it
        drops (silent truncation would read as 'recorded everything')."""
        if not self.enabled:
            return True
        if self._capture_remaining > 0:
            return True
        if self.tail_threshold_ms <= 0.0 or total_ms >= self.tail_threshold_ms:
            return True
        self.tail_skipped += 1
        return False

    # -- the admin payload -------------------------------------------------
    def profile_json(self, kernel: str = "cpu") -> dict:
        """The `GET /admin/profile/kernel` payload: compile log + census,
        per-phase p50/p99 rollups, memory stats, capture status."""
        return {
            "enabled": self.enabled,
            "kernel": kernel,
            "compiles": {
                "expected": self.compiles_expected,
                "unexpected": self.compiles_unexpected,
                "log": self.compile_log(),
            },
            "cache_census": self.cache_census(),
            "phases": self.phase_rollups(),
            "phase_bounds_ms": PHASE_BOUNDS_MS,
            "memory": self.memory_stats(),
            "hbm_high_water_bytes": self._hbm_high_water,
            "tail_threshold_ms": self.tail_threshold_ms,
            "tail_skipped": self.tail_skipped,
            "capture": {
                "armed": self.capture_armed,
                "remaining": self._capture_remaining,
                "captured": len(self._capture_rows),
                "started": self._capture_started,
                "trace_dir": self._trace_dir,
                "trace_active": self._trace_active,
                "steps": self._capture_rows,
            },
        }
