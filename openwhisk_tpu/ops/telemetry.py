"""On-device fleet telemetry: latency histograms + outcome counters.

The host-side MetricEmitter keeps one Python dict update per activation —
fine for a laptop, unusable for per-invoker x per-namespace resolution at
the 64k-invoker north star. This module keeps the telemetry the same way
the placement decision is kept: as dense device arrays updated by one
vectorized scatter-add per micro-batch, folded into the TPU balancer's
dispatch/readback cycle (the event rows ride the same flush cadence as the
release fold, so no extra host<->device transfer shows up per activation).

State (static shapes; fleets grow into padding like PlacementState):

  inv_buckets  int32[N, B]  latency bucket counts per invoker
  ns_buckets   int32[M, B]  latency bucket counts per namespace slot
  inv_lat_ms   float32[N]   latency sum per invoker (Prometheus `_sum`)
  ns_lat_ms    float32[M]
  inv_outcomes int32[N, K]  completions per invoker by outcome
  ns_outcomes  int32[M, K]

Buckets are log2-spaced: bucket i counts latencies in (2^(i-1), 2^i] ms,
bucket 0 is <= 1 ms and the last bucket is the +Inf overflow — cumulative
`le` rendering happens host-side at scrape time (controller/monitoring.py).
Bucket assignment is integer-exact (comparisons against precomputed
microsecond bounds, no float log), so a 4.000 ms sample always lands in
`le=4`, never in a neighbouring bucket via rounding.

`NumpyLatencyAccumulator` is the bit-identical host twin the CPU balancers
(sharding, lean) feed through the same base-class hook, so every balancer
reports into one telemetry surface.
"""
from __future__ import annotations

from typing import List, NamedTuple

import numpy as np

#: completion outcome axis
OUTCOME_SUCCESS, OUTCOME_ERROR, OUTCOME_TIMEOUT = range(3)
N_OUTCOMES = 3
OUTCOME_NAMES = ("success", "error", "timeout")

DEFAULT_BUCKETS = 24

#: packed event-row layout (one int32[5, E] matrix per fold)
E_INV, E_NS, E_LAT_US, E_OUTCOME, E_VALID = range(5)


def bucket_bounds_ms(n_buckets: int = DEFAULT_BUCKETS) -> List[float]:
    """Finite upper bounds in ms: 1, 2, 4, ... 2^(n-2); the implicit last
    bucket is +Inf."""
    return [float(2 ** i) for i in range(max(1, n_buckets - 1))]


def _bounds_us(n_buckets: int) -> np.ndarray:
    """Bucket bounds in int32-safe microseconds. Samples are clipped to
    int32 max (~35.8 min) on the way in, so bounds past that saturate too:
    everything above lands in the first saturated bucket, identically on
    the device and NumPy paths."""
    return np.asarray(
        [min(1000 * 2 ** i, 2 ** 31 - 1)
         for i in range(max(1, n_buckets - 1))], np.int64)


def bucket_of_us(lat_us, n_buckets: int):
    """Exact bucket index for integer microsecond latencies (numpy in,
    numpy out): the first bucket whose bound covers the sample."""
    bounds = _bounds_us(n_buckets)
    return np.searchsorted(bounds, np.asarray(lat_us, np.int64),
                           side="left").astype(np.int64)


class TelemetryState(NamedTuple):
    inv_buckets: object   # int32[N, B]
    ns_buckets: object    # int32[M, B]
    inv_lat_ms: object    # float32[N]
    ns_lat_ms: object     # float32[M]
    inv_outcomes: object  # int32[N, K]
    ns_outcomes: object   # int32[M, K]


def init_telemetry(n_invokers: int, n_namespaces: int,
                   n_buckets: int = DEFAULT_BUCKETS) -> TelemetryState:
    import jax.numpy as jnp
    return TelemetryState(
        jnp.zeros((n_invokers, n_buckets), jnp.int32),
        jnp.zeros((n_namespaces, n_buckets), jnp.int32),
        jnp.zeros((n_invokers,), jnp.float32),
        jnp.zeros((n_namespaces,), jnp.float32),
        jnp.zeros((n_invokers, N_OUTCOMES), jnp.int32),
        jnp.zeros((n_namespaces, N_OUTCOMES), jnp.int32),
    )


def make_record_packed():
    """One jitted scatter-add over a packed int32[5, E] event matrix
    (inv_idx, ns_slot, latency_us, outcome, valid): SIX dense updates in one
    device program, one host->device transfer per fold. E is part of the jit
    shape key — the balancer pads folds to power-of-two buckets so the cache
    stays small. Invalid (padding) rows scatter zeros, so no masking gymnastics
    are needed beyond the valid column itself."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def telemetry_fold(state: TelemetryState, ev) -> TelemetryState:
        inv, ns, lat_us, outcome, valid = ev
        n_buckets = state.inv_buckets.shape[1]
        # integer-exact log2 bucket: count the bounds each sample exceeds
        # (bounds saturate at int32 max, matching the host clip on lat_us)
        bounds = jnp.asarray(
            [min(1000 * 2 ** i, 2 ** 31 - 1) for i in range(n_buckets - 1)],
            jnp.int32)
        b = jnp.sum(lat_us[:, None] > bounds[None, :], axis=1)
        v = valid.astype(jnp.int32)
        inv = jnp.clip(inv, 0, state.inv_buckets.shape[0] - 1)
        ns = jnp.clip(ns, 0, state.ns_buckets.shape[0] - 1)
        k = jnp.clip(outcome, 0, N_OUTCOMES - 1)
        lat_ms = valid * lat_us.astype(jnp.float32) * 1e-3
        return TelemetryState(
            state.inv_buckets.at[inv, b].add(v),
            state.ns_buckets.at[ns, b].add(v),
            state.inv_lat_ms.at[inv].add(lat_ms),
            state.ns_lat_ms.at[ns].add(lat_ms),
            state.inv_outcomes.at[inv, k].add(v),
            state.ns_outcomes.at[ns, k].add(v),
        )

    return telemetry_fold


class DeviceLatencyAccumulator:
    """Device-resident accumulator for the TPU balancer: fold() dispatches
    the jitted scatter-add asynchronously (no readback — counts stay on
    device until a scrape), counts() is the cold-path device->host sync."""

    kernel = "device"

    def __init__(self, n_invokers: int, n_namespaces: int,
                 n_buckets: int = DEFAULT_BUCKETS):
        self.n_buckets = n_buckets
        self.n_namespaces = n_namespaces
        self.n_invokers = max(1, n_invokers)
        self.state = init_telemetry(self.n_invokers, n_namespaces, n_buckets)
        self._record = make_record_packed()

    def ensure_invokers(self, n: int) -> None:
        """Grow the invoker axis to the next power of two >= n, preserving
        accumulated counts (mirrors TpuBalancer._grow_padding)."""
        if n <= self.n_invokers:
            return
        import jax.numpy as jnp
        new_n = 1
        while new_n < n:
            new_n *= 2
        old = self.counts()
        st = init_telemetry(new_n, self.n_namespaces, self.n_buckets)
        self.state = TelemetryState(
            st.inv_buckets.at[: self.n_invokers].set(
                jnp.asarray(old["inv_buckets"])),
            jnp.asarray(old["ns_buckets"]),
            st.inv_lat_ms.at[: self.n_invokers].set(
                jnp.asarray(old["inv_lat_ms"])),
            jnp.asarray(old["ns_lat_ms"]),
            st.inv_outcomes.at[: self.n_invokers].set(
                jnp.asarray(old["inv_outcomes"])),
            jnp.asarray(old["ns_outcomes"]),
        )
        self.n_invokers = new_n

    def fold(self, events: np.ndarray) -> None:
        """events: int32[5, E] packed rows (already padded by the caller)."""
        self.ensure_invokers(int(events[E_INV].max(initial=0)) + 1)
        self.state = self._record(self.state, events)

    def counts(self) -> dict:
        """Device->host sync of every accumulator array (cold path: one
        scrape or SLO evaluation, run off the event loop by callers)."""
        return {f: np.asarray(getattr(self.state, f))
                for f in TelemetryState._fields}


class NumpyLatencyAccumulator:
    """Host twin with identical bucket math for the CPU balancers. add() is
    the O(1) per-completion fast path; fold() accepts the same packed
    matrix as the device accumulator (used by tests for parity)."""

    kernel = "cpu"

    def __init__(self, n_invokers: int, n_namespaces: int,
                 n_buckets: int = DEFAULT_BUCKETS):
        self.n_buckets = n_buckets
        self.n_namespaces = n_namespaces
        self.n_invokers = max(1, n_invokers)
        self._bounds_us = _bounds_us(n_buckets)
        z = np.zeros
        self.inv_buckets = z((self.n_invokers, n_buckets), np.int64)
        self.ns_buckets = z((n_namespaces, n_buckets), np.int64)
        self.inv_lat_ms = z((self.n_invokers,), np.float64)
        self.ns_lat_ms = z((n_namespaces,), np.float64)
        self.inv_outcomes = z((self.n_invokers, N_OUTCOMES), np.int64)
        self.ns_outcomes = z((n_namespaces, N_OUTCOMES), np.int64)

    def ensure_invokers(self, n: int) -> None:
        if n <= self.n_invokers:
            return
        new_n = 1
        while new_n < n:
            new_n *= 2
        for name in ("inv_buckets", "inv_outcomes"):
            old = getattr(self, name)
            grown = np.zeros((new_n, old.shape[1]), old.dtype)
            grown[: old.shape[0]] = old
            setattr(self, name, grown)
        lat = np.zeros((new_n,), np.float64)
        lat[: self.inv_lat_ms.shape[0]] = self.inv_lat_ms
        self.inv_lat_ms = lat
        self.n_invokers = new_n

    def add(self, inv: int, ns_slot: int, lat_us: int, outcome: int) -> None:
        self.ensure_invokers(inv + 1)
        ns_slot = min(max(ns_slot, 0), self.n_namespaces - 1)
        outcome = min(max(outcome, 0), N_OUTCOMES - 1)
        b = int(np.searchsorted(self._bounds_us, lat_us, side="left"))
        self.inv_buckets[inv, b] += 1
        self.ns_buckets[ns_slot, b] += 1
        self.inv_lat_ms[inv] += lat_us * 1e-3
        self.ns_lat_ms[ns_slot] += lat_us * 1e-3
        self.inv_outcomes[inv, outcome] += 1
        self.ns_outcomes[ns_slot, outcome] += 1

    def fold(self, events: np.ndarray) -> None:
        for col in events.T:
            if col[E_VALID]:
                self.add(int(col[E_INV]), int(col[E_NS]),
                         int(col[E_LAT_US]), int(col[E_OUTCOME]))

    def counts(self) -> dict:
        return {f: getattr(self, f).copy()
                for f in TelemetryState._fields}
