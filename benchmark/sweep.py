"""Find an open-loop cell's knee, once, on the chip. Not part of a run.

    python3 benchmark/sweep.py --workload <cell> --seed 1 --seconds 8 --rate0 500

Doubles the offered rate from `--rate0` until a step is not sustainable,
then bisects twice between the last sustainable rate and the first that
was not. A step is sustainable iff (after tools/loadgen.verdict): at least
98% of the window's activations completed inside the drain, the overhead
p99 stays under 1 s, the generator's fire lag p99 (and, where the entry's
generator is a process of its own, its event loop's lag p99) stays under
50 ms, and the later half of the window is not slower than the earlier half
by more than 2x + 5 ms (no growing backlog). The cell's fixed rate is 0.8 x the
highest sustainable rate, written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

MIN_COMPLETION_RATIO = 0.98
P99_BOUND_MS = 1000.0
MAX_FIRE_LAG_P99_MS = 50.0


def sustainable(out: dict) -> list:
    """The reasons a step failed; empty when it is sustainable."""
    art, failed = out["art"], []
    done = out["attempted"] - out["failed"]
    if done < MIN_COMPLETION_RATIO * max(1, out["attempted"]):
        failed.append(f"completed {done}/{out['attempted']}")
    p99 = run.percentile(art["overhead_ms"], 0.99)
    if p99 is None or p99 > P99_BOUND_MS:
        failed.append(f"overhead p99 {p99} ms")
    # a generator in a process of its own (entry `http`) also reports the
    # lag of its own event loop: a starved generator is not a fast server
    for what, lag in (
            ("fire", run.percentile(art["fire_lag_ms"], 0.99)),
            ("generator", out["log"].get("generator_lag_p99_ms"))):
        if lag is not None and lag > MAX_FIRE_LAG_P99_MS:
            failed.append(f"{what} lag p99 {lag} ms")
    first, second = out["log"]["latency_p50_by_half_ms"]
    if first is not None and second > 2 * first + 5.0:
        failed.append(f"backlog grows: p50 {first} -> {second} ms")
    if not out["verdict"]["correct"]:
        failed.append("not correct")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rate0", type=float, default=500.0)
    ap.add_argument("--max-steps", type=int, default=8)
    args = ap.parse_args(argv)
    res = run.resolve_cell(run.load_manifest(), args.workload)
    device = run.device_or_exit(int(res["cell"]["chips"]))

    def step(rate: float) -> bool:
        res["mix"] = {**res["mix"], "rate_per_s": rate}
        out = asyncio.run(run.run_cell(res, args.seed, args.seconds, False,
                                       device))
        why = sustainable(out)
        print(json.dumps({
            "rate_per_s": rate, "sustainable": not why, "failed": why,
            "platform": device["platform"], "attempted": out["attempted"],
            "overhead_p50_ms": run.percentile(out["art"]["overhead_ms"], .5),
            "overhead_p99_ms": run.percentile(out["art"]["overhead_ms"], .99),
            "fire_lag_p99_ms": run.percentile(out["art"]["fire_lag_ms"], .99),
            "generator_lag_p99_ms": out["log"].get("generator_lag_p99_ms"),
            "p50_by_half_ms": out["log"]["latency_p50_by_half_ms"],
            "steps_in_window": out["log"]["steps_in_window"]}), flush=True)
        return not why

    good, bad, rate = None, None, args.rate0
    for _ in range(args.max_steps):
        if step(rate):
            good, rate = rate, rate * 2
        else:
            bad = rate
            break
    if good is not None and bad is not None:
        for _ in range(2):
            mid = (good + bad) / 2
            if step(mid):
                good = mid
            else:
                bad = mid
    print(json.dumps({"knee_per_s": good, "first_unsustainable_per_s": bad,
                      "rate_at_four_fifths": None if good is None
                      else round(0.8 * good)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
