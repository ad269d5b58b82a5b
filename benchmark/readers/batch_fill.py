"""Activations per device step: the window's activations over the fused
steps that scheduled any of them (counted from the journal's batch
records)."""


def read(art):
    steps = art.get("steps") or []
    if not steps:
        return None
    return sum(s["b"] for s in steps) / len(steps)
