"""Events of one program span per activation: the count of `span`'s
events on the event loop's thread inside the traced sub-window, over the
activations the window's `ow_assemble` spans count (`ow_feed`: the bus
feeds' wakes that brought work). With `stat`, the sum of that stat over
the span's events in the sub-window instead of their count
(`ow_invoke_done`'s `polls`: the blocking waits' store polls). None where
the trace is unusable, the window counted no activation, or the span never
occurred (a program from before it, as the parent commit is under the
driver): never 0 for "absent"."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import span_reduce  # noqa: E402


def read(art, span, stat=None):
    red = span_reduce.for_run(art)
    if red is None or not red["activations"]:
        return None
    row = red["by_name"].get(span)
    if row is None or not row["count"]:
        return None
    total = row["count"] if stat is None else row["stats"].get(stat, 0)
    return total / red["activations"]
