"""The control of the comparison that decides `correct`, and the readings
its limits were set from. Not part of a benchmark run.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell as `run.py` does (the program, on the chip,
at the cell's own size), prints the program's numbers (the lower reading),
then puts the CONTROL in the program's place: the plain reference with one
stated guarantee broken, driven over the same requests and releases in the
same order, and prints the numbers the same comparison gives it (the upper
reading). The control folds a step's releases after its requests instead of
before (stale books, memory and spare permits alike: the later, rarer
flush a faster step would be tempted by) and has to come out as not correct
on every seed of every cell.
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

from benchmark import reference, run  # noqa: E402


def control_verdict(out: dict, config: dict) -> dict:
    """The comparison's verdict on the control's decisions for one run."""
    control = reference.replay(
        out["records"], out["observed"]["sent"],
        reference.ReferenceFleet(float(config["managed_fraction"]),
                                 late_release=True),
        int(config["invokers"]))
    decisions = {aid: (dec[0] if dec[0] is not None else -1, dec[1])
                 for aid, dec in control["reference"].items()}
    return reference.compare(out["replayed"], decisions=decisions,
                             **out["observed"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    res = run.resolve_cell(run.load_manifest(), args.workload)
    device = run.device_or_exit(int(res["cell"]["chips"]))
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = asyncio.run(run.run_cell(res, seed, args.seconds, False,
                                       device))
        ctl = control_verdict(out, res["config"])
        row = {"seed": seed, "workload": args.workload,
               "platform": device["platform"],
               "compared": out["verdict"]["compared"],
               "program": out["verdict"]["numbers"],
               "program_correct": out["verdict"]["correct"],
               "control": ctl["numbers"],
               "control_correct": ctl["correct"]}
        print(json.dumps(row), flush=True)
        bad += (not out["verdict"]["correct"]) or ctl["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
