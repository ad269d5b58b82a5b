"""Diff two BENCH_*.json rounds mechanically.

ROADMAP house-keeping: the outstanding PR 9 claim (>5M placements/s for
`pallas_repair`) needs a clean device round —
when one lands, it should be judged by a tool, not by eyeballing two
JSON blobs. This CLI prints a per-rider delta table between two rounds and
exits nonzero when any HEADLINE metric regressed by more than the
threshold (default 20%).

Usage (documented in docs/tpu-balancer.md):

    python tools/bench_compare.py BENCH_r04.json BENCH_r06.json
    python tools/bench_compare.py old.json new.json --threshold 10

Judgment rules:
  * Only the curated HEADLINES list gates the exit code; the delta table
    is informational and covers every shared numeric at the top two
    levels.
  * A metric missing (or null) on either side is SKIPPED and said so —
    a rider that failed to run is a different problem than a regression.
  * When the two rounds ran on different backends (the `backend`
    tag), the comparison is ADVISORY: deltas
    print, the exit code stays 0, and the mismatch is named — a CPU
    number must never fail a device round or vice versa.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Tuple

#: (label, path into the round dict, direction). "higher" metrics regress
#: when the new value drops below old*(1-thr); "lower" metrics (latencies,
#: downtime) regress when the new value climbs above old*(1+thr); "zero"
#: metrics are correctness invariants — ANY nonzero new value regresses,
#: no threshold (a 0->1 jump has no percentage).
HEADLINES = (
    ("placements_per_sec", ("value",), "higher"),
    ("balancer_activations_per_sec",
     ("balancer", "activations_per_sec"), "higher"),
    ("e2e_sustained_per_sec",
     ("e2e_open_loop", "sustained_activations_per_sec"), "higher"),
    ("e2e_p99_ms", ("e2e_open_loop", "p99_ms"), "lower"),
    ("host_observatory_sustained_per_sec",
     ("host_observatory", "sustained_activations_per_sec"), "higher"),
    ("host_observatory_loop_lag_p99_ms",
     ("host_observatory", "loop_lag_p99_ms"), "lower"),
    # ISSUE 14: the two host-floor numbers the batched publish SPI and
    # the lazy ack result column are judged by
    ("host_observatory_serde_worst_hop_pct",
     ("host_observatory", "stage_shares", "serde_worst_hop_pct"), "lower"),
    ("host_observatory_tasks_per_activation",
     ("host_observatory", "stage_shares", "tasks_per_activation"), "lower"),
    ("e2e_fleet_mesh_sustained_per_sec",
     ("e2e_open_loop", "fleet_mesh_point", "sustained_activations_per_sec"),
     "higher"),
    ("bus_coalesced_msgs_per_sec",
     ("bus_coalesce_speedup", "coalesced_msgs_per_sec"), "higher"),
    ("failover_downtime_ms", ("failover_downtime", "downtime_ms"), "lower"),
    # ISSUE 15: active/active partitioned control under a mid-burst kill.
    # double_executions is the zero-double-execution CONTRACT, not a
    # perf number — any nonzero value fails the round outright.
    ("partition_chaos_downtime_s",
     ("partition_chaos", "downtime_s"), "lower"),
    ("partition_chaos_double_executions",
     ("partition_chaos", "double_executions"), "zero"),
    ("partition_chaos_absorbed_rate",
     ("partition_chaos", "absorbed_rate"), "higher"),
    # ISSUE 16: the reconstructed causal timeline decomposes the chaos
    # outage into named phases (their sum IS the timeline's downtime, so
    # a regression here names WHICH phase got slower); plus the --procs
    # fleet-merged generator headline
    ("partition_chaos_phase_detect_s",
     ("partition_chaos", "timeline", "phases", "detect_s"), "lower"),
    ("partition_chaos_phase_claim_s",
     ("partition_chaos", "timeline", "phases", "claim_s"), "lower"),
    ("partition_chaos_phase_absorb_s",
     ("partition_chaos", "timeline", "phases", "absorb_s"), "lower"),
    ("partition_chaos_phase_first_placement_s",
     ("partition_chaos", "timeline", "phases", "first_placement_s"),
     "lower"),
    ("fleet_merged_sustained_per_sec",
     ("e2e_open_loop", "multiproc_point", "fleet_merged_sustained_per_sec"),
     "higher"),
    # ISSUE 20: the SHARED multi-process deployment — front-end worker
    # processes funneling ONE balancer process over the TCP bus. The
    # merged-schedule sustained rate is a system number (topology
    # "shared"), unlike the twins-mode generator headline above; the
    # proc count rides along so a rate regression that came from a
    # smaller front-end ladder names itself.
    ("funnel_sustained_per_sec",
     ("funnel_10k", "funnel_sustained_per_sec"), "higher"),
    ("funnel_frontend_procs",
     ("funnel_10k", "funnel_frontend_procs"), "higher"),
    # ISSUE 17: placement quality under the straggler A/B — predicted
    # regret left on the table and how often the penalized shadow would
    # have placed differently (both lower-is-better), plus the plane's
    # <= 5% paired-overhead gate
    ("placement_regret_p99_ms",
     ("placement_quality", "straggler", "regret_p99_le_ms"), "lower"),
    ("shadow_divergence_ratio",
     ("placement_quality", "shadow_divergence_ratio"), "lower"),
    ("placement_quality_overhead_pct",
     ("placement_quality_overhead", "overhead_pct"), "lower"),
    # ISSUE 19: incident forensics — every acceptance plane must keep
    # landing in the bundle, the time-travel replay is a determinism
    # CONTRACT (any mismatch fails the round outright), and the armed
    # recorder rides the house paired-overhead gate
    ("incident_capture_planes",
     ("incident_capture", "planes_captured"), "higher"),
    ("incident_replay_mismatches",
     ("incident_capture", "replay_parity_mismatches"), "zero"),
    ("incident_overhead_pct",
     ("incident_overhead", "overhead_pct"), "lower"),
)


def unwrap_round(doc: dict) -> dict:
    """Accept either a bare bench.py JSON line or the driver's
    BENCH_r*.json envelope ({n, cmd, rc, tail}), whose `tail` holds the
    process output with the one JSON line somewhere in it (usually last).
    A dead round (rc!=0, no JSON line) unwraps to {} — every metric then
    reads as missing, which is the honest verdict."""
    if "value" in doc or "metric" in doc:
        return doc
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    inner = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(inner, dict):
                    return inner
        return {}
    return doc


def _get(doc: dict, path: Tuple[str, ...]):
    node = doc
    for p in path:
        if not isinstance(node, dict):
            return None
        node = node.get(p)
    return node if isinstance(node, (int, float)) and not isinstance(
        node, bool) else None


def _pct(old: float, new: float) -> Optional[float]:
    if not old:
        return None
    return 100.0 * (new - old) / old


def compare(old: dict, new: dict, threshold_pct: float = 20.0) -> dict:
    """Headline verdicts + the informational delta table. Pure function:
    the CLI below owns printing and the exit code."""
    backend_old = old.get("backend") or (old.get("balancer") or {}).get(
        "backend")
    backend_new = new.get("backend") or (new.get("balancer") or {}).get(
        "backend")
    # Advisory when the backends differ — OR when exactly one side is
    # tagged: rounds before r06 only tagged the backend on CPU fallback,
    # so an untagged old round is almost certainly a DEVICE round, and a
    # device-vs-CPU diff must never gate (a CPU number reading as a 99%
    # placements regression against TPU hardware is a category error,
    # not a regression). Rounds from r06 on are always tagged, so
    # same-backend comparisons keep their teeth.
    backend_mismatch = (backend_old != backend_new
                        and (backend_old is not None
                             or backend_new is not None))
    rows = []
    regressions = []
    for label, path, direction in HEADLINES:
        o, n = _get(old, path), _get(new, path)
        if o is None or n is None:
            rows.append({"metric": label, "old": o, "new": n,
                         "delta_pct": None, "verdict": "skipped (missing)"})
            continue
        delta = _pct(o, n)
        regressed = False
        if direction == "zero":
            regressed = n > 0
        elif delta is not None:
            if direction == "higher":
                regressed = n < o * (1.0 - threshold_pct / 100.0)
            else:
                regressed = n > o * (1.0 + threshold_pct / 100.0)
        verdict = "REGRESSED" if regressed else "ok"
        if regressed and backend_mismatch:
            verdict = "regressed (advisory: backend mismatch)"
        elif regressed:
            regressions.append(label)
        rows.append({"metric": label, "old": o, "new": n,
                     "delta_pct": round(delta, 1) if delta is not None
                     else None, "verdict": verdict})

    # informational table: every shared numeric at the top two levels
    deltas = []

    def walk(prefix, a, b, depth):
        for k in sorted(set(a) & set(b)):
            va, vb = a[k], b[k]
            name = f"{prefix}{k}"
            if isinstance(va, (int, float)) and not isinstance(va, bool) \
                    and isinstance(vb, (int, float)) \
                    and not isinstance(vb, bool):
                deltas.append((name, va, vb, _pct(va, vb)))
            elif isinstance(va, dict) and isinstance(vb, dict) and depth < 2:
                walk(name + ".", va, vb, depth + 1)

    walk("", old, new, 0)
    return {
        "headlines": rows,
        "regressions": regressions,
        "deltas": deltas,
        "backend_old": backend_old,
        "backend_new": backend_new,
        "backend_mismatch": backend_mismatch,
        "threshold_pct": threshold_pct,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("old", help="baseline BENCH_*.json")
    ap.add_argument("new", help="candidate BENCH_*.json")
    ap.add_argument("--threshold", type=float, default=20.0,
                    help="regression threshold in percent (default 20)")
    ap.add_argument("--full", action="store_true",
                    help="print the full two-level delta table, not just "
                         "the headline metrics")
    args = ap.parse_args()
    try:
        with open(args.old) as f:
            old = json.load(f)
        with open(args.new) as f:
            new = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_compare: cannot read rounds: {e}", file=sys.stderr)
        return 2
    if not isinstance(old, dict) or not isinstance(new, dict):
        print("bench_compare: rounds must be JSON objects", file=sys.stderr)
        return 2
    old, new = unwrap_round(old), unwrap_round(new)
    out = compare(old, new, args.threshold)
    # code provenance (ISSUE 19 satellite): bench.py stamps git_commit +
    # round label into `host`, so the diff names what code produced each
    # side even after branches moved on
    def _prov(doc):
        host = doc.get("host") or {}
        commit = host.get("git_commit") or "?"
        rnd = host.get("round")
        return f"{commit} (round {rnd})" if rnd else commit

    print(f"# old: {_prov(old)}  ->  new: {_prov(new)}")
    if out["backend_mismatch"]:
        print(f"# BACKEND MISMATCH: old={out['backend_old']} "
              f"new={out['backend_new']} — comparison is advisory, "
              "exit code stays 0")
    w = max(len(r["metric"]) for r in out["headlines"])
    print(f"{'metric':<{w}}  {'old':>12}  {'new':>12}  {'delta':>8}  verdict")
    for r in out["headlines"]:
        old_s = "-" if r["old"] is None else f"{r['old']:g}"
        new_s = "-" if r["new"] is None else f"{r['new']:g}"
        d = "-" if r["delta_pct"] is None else f"{r['delta_pct']:+.1f}%"
        print(f"{r['metric']:<{w}}  {old_s:>12}  {new_s:>12}  {d:>8}  "
              f"{r['verdict']}")
    if args.full and out["deltas"]:
        print("\n# full delta table (top two levels)")
        for name, o, n, d in out["deltas"]:
            ds = "-" if d is None else f"{d:+.1f}%"
            print(f"{name}  {o:g} -> {n:g}  ({ds})")
    if out["regressions"]:
        print(f"\nREGRESSION: {', '.join(out['regressions'])} moved more "
              f"than {args.threshold:g}% the wrong way", file=sys.stderr)
        return 1
    print(f"\nok: no headline metric regressed more than "
          f"{args.threshold:g}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
