"""What the spans show of the event loop's thread in the traced
sub-window. `spanned_share`: the union of every `ow_*`, `bench_*` and
`PjitFunction*` span on it over the window, in percent (the rest is code
no span names: asyncio's own scheduling, the bus's queues). `block_max_ms`:
the longest stretch of one span's own time on it, or of `ow_gc` on any
thread: what a stalled loop was doing, if a span covers it."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import span_reduce  # noqa: E402


def read(art, what):
    red = span_reduce.for_run(art)
    if red is None:
        return None
    if what == "spanned_share":
        return 100.0 * red["spanned_s"] / red["window_s"]
    if what == "block_max_ms":
        return max((d for _n, _s, d in red["blocks"]), default=0.0) * 1e3
    raise ValueError(f"loop_cover reads spanned_share or block_max_ms, "
                     f"not {what!r}")
