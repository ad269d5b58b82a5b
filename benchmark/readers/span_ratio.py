"""A share, in percent: the sum of one stat over the sum of another, over
the events of one program span inside the traced sub-window, on whichever
thread they ran (`warm` over `b` of `ow_fanout`: the rows of each step
placed on a spare permit of a container an invoker already held, over the
rows of the step). None where the trace is unusable, the span did not
occur, its events carry no stat `num` (a program from before the stat, as
the parent commit is under the driver) or `den` sums to nothing: never 0
for "absent"."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import span_reduce  # noqa: E402


def read(art, span, num, den):
    red = span_reduce.for_run(art)
    if red is None:
        return None
    stats = red["by_name"].get(span, {}).get("stats", {})
    if num not in stats or not stats.get(den):
        return None
    return 100.0 * stats[num] / stats[den]
