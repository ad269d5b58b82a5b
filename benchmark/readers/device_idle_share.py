"""1 - (union of device-op intervals) / (traced sub-window), from the
profiler trace, averaged over the chips used."""


def read(art):
    tr = art.get("trace")
    if not tr or tr.get("busy_s") is None or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
