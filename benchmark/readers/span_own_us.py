"""Host microseconds per activation by the program's own spans: the own
time of `spans` on the event loop's thread inside the traced sub-window
(a `PjitFunction*` dispatch charged to the `ow_*` span it nests in;
`ow_gc` on every thread), over the activations the window's `ow_assemble`
spans count. 0 where the trace is usable and the spans did not occur."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import span_reduce  # noqa: E402


def read(art, spans):
    red = span_reduce.for_run(art)
    if red is None or not red["activations"]:
        return None
    own_s = sum(red["by_name"].get(name, {}).get("own_s", 0.0)
                for name in spans)
    return own_s * 1e6 / red["activations"]
