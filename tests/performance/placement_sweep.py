"""Placement-kernel scale sweep: 16 -> 64k invokers, single-device + sharded.

The BASELINE.json build-target matrix: placement decisions/sec and p50
schedule() step latency across fleet sizes from 16 simulated invokers up to
64k invokers sharded 8 ways (the north-star configuration; SURVEY §6). The
device step measured is the full per-batch work the balancer does:
schedule_batch + the matching release fold, books held constant.

    python tests/performance/placement_sweep.py                 # on device
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tests/performance/placement_sweep.py --sharded   # virtual mesh

Prints one JSON line per configuration.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _measure(config: str, n_invokers: int, batch: int, iters: int,
             state, step) -> dict:
    """Shared warmup + timing loop: full device step, books held constant."""
    import jax

    for _ in range(3):
        state, chosen = step(state)
    jax.block_until_ready(state)

    lat = []
    t0 = time.perf_counter()
    for _ in range(iters):
        t1 = time.perf_counter()
        state, chosen = step(state)
        jax.block_until_ready(chosen)
        lat.append(time.perf_counter() - t1)
    dt = time.perf_counter() - t0
    return {"config": config, "n_invokers": n_invokers, "batch": batch,
            "placements_per_sec": round(batch * iters / dt, 1),
            "p50_step_ms": round(sorted(lat)[len(lat) // 2] * 1e3, 3)}


def bench_single(n_invokers: int, batch: int, iters: int, slot_mb: int = 2048,
                 seed: int = 7) -> dict:
    import jax.numpy as jnp

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import (init_state, release_batch,
                                             schedule_batch)

    state = init_state(n_invokers, [slot_mb] * n_invokers, action_slots=256)
    req = _example_batch(n_invokers, batch, seed=seed)

    def step(state):
        state, chosen, forced, _warm = schedule_batch(state, req)
        ok = chosen >= 0
        return release_batch(state, jnp.clip(chosen, 0), req.conc_slot,
                             req.need_mb, req.max_conc, ok), chosen

    return _measure("single-device", n_invokers, batch, iters, state, step)


def bench_sharded(n_invokers: int, batch: int, iters: int, n_shards: int = 8,
                  slot_mb: int = 2048, seed: int = 7) -> dict:
    import jax.numpy as jnp

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import init_state
    from openwhisk_tpu.parallel.sharded_state import (make_mesh,
                                                      make_sharded_release,
                                                      make_sharded_schedule,
                                                      shard_state)

    mesh = make_mesh(n_shards)
    state = shard_state(
        init_state(n_invokers, [slot_mb] * n_invokers, action_slots=256), mesh)
    req = _example_batch(n_invokers, batch, seed=seed)
    schedule = make_sharded_schedule(mesh)
    release = make_sharded_release(mesh)

    def step(state):
        state, chosen, forced, _warm = schedule(state, req)
        ok = chosen >= 0
        return release(state, jnp.clip(chosen, 0), req.conc_slot,
                       req.need_mb, req.max_conc, ok), chosen

    return _measure(f"{n_shards}-shard", n_invokers, batch, iters, state, step)


def bench_pallas(n_invokers: int, batch: int, iters: int, slot_mb: int = 2048,
                 action_slots: int = 256, seed: int = 7) -> dict:
    """schedule-only comparison of the pallas kernel vs the XLA scan."""
    import jax

    from __graft_entry__ import _example_batch
    from openwhisk_tpu.ops.placement import init_state, schedule_batch
    from openwhisk_tpu.ops.placement_pallas import (schedule_batch_pallas,
                                                    to_transposed)

    state = init_state(n_invokers, [slot_mb] * n_invokers,
                       action_slots=action_slots)
    req = _example_batch(n_invokers, batch, seed=seed)
    row = _measure("xla-schedule", n_invokers, batch, iters, state,
                   lambda s: schedule_batch(s, req)[:2])
    prow = _measure("pallas-schedule", n_invokers, batch, iters,
                    to_transposed(state),
                    lambda s: schedule_batch_pallas(s, req)[:2])
    row["pallas_placements_per_sec"] = prow["placements_per_sec"]
    row["pallas_p50_step_ms"] = prow["p50_step_ms"]
    row["config"] = "pallas-vs-xla"
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sharded", action="store_true",
                    help="also run the 8-shard configurations (needs >=8 "
                         "devices, e.g. the virtual CPU mesh)")
    ap.add_argument("--pallas", action="store_true",
                    help="also compare the pallas schedule kernel vs XLA")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--sizes", type=int, nargs="*",
                    default=[16, 256, 4096, 65536])
    args = ap.parse_args()

    for n in args.sizes:
        print(json.dumps(bench_single(n, args.batch, args.iters)), flush=True)
    if args.sharded:
        for n in args.sizes:
            if n % 8:
                continue
            print(json.dumps(bench_sharded(n, args.batch, args.iters)),
                  flush=True)
    if args.pallas:
        from openwhisk_tpu.ops.placement_pallas import fits_vmem
        for n in args.sizes:
            if fits_vmem(n, 256):
                print(json.dumps(bench_pallas(n, args.batch, args.iters)),
                      flush=True)


if __name__ == "__main__":
    main()
