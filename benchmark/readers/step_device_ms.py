"""Device time of one fused placement step, from the profiler trace: the
executions that the traced sub-window's fused steps (by the journal)
dispatched, release-only folds left out; mean per step."""


def read(art):
    runs = [s["device_s"] for s in art.get("traced_steps") or []
            if s["fused"] and s["device_s"] is not None]
    if not runs:
        return None
    return sum(runs) / len(runs) * 1e3
