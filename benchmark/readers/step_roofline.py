"""The fused placement step's share of its bytes roofline: the least time
the chip needs for the algorithmic bytes (benchmark/roofline.py) of the
traced sub-window's fused steps, over the device time of those same
steps' executions."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import roofline  # noqa: E402


def read(art):
    steps = [s for s in art.get("traced_steps") or []
             if s["fused"] and s["device_s"] is not None]
    measured = sum(s["device_s"] for s in steps)
    if measured <= 0:
        return None
    geo, kind = art["geometry"], art["device"]["device_kind"]
    least = sum(roofline.least_step_seconds(kind, geo["N"], geo["A"],
                                            s["B"], s["distinct"])
                for s in steps)
    return 100.0 * least / measured
