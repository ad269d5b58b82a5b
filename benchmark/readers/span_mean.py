"""A mean over the events of one program span inside the traced
sub-window, on whichever thread they ran: of the stat `stat` the program
rode on the span (`acks` of `ow_ack_decode`), or of the span's length in
milliseconds where no stat is named (`ow_readback_wait`). 0 where the
trace is usable and the span did not occur."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import span_reduce  # noqa: E402


def read(art, span, stat=None):
    red = span_reduce.for_run(art)
    if red is None:
        return None
    row = red["by_name"].get(span)
    if row is None:
        return 0.0
    if stat is None:
        return row["own_s"] * 1e3 / row["count"] if row["count"] else 0.0
    n = row["stats"].get("_events", 0)
    return row["stats"].get(stat, 0) / n if n else 0.0
