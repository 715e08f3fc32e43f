"""95th percentile of the gaps between the trainer's per-tree progress stamps
(the first tree of a call is timed from the call's start), all trees of the
window."""

from benchmarks.lib import compare


def read(ctx):
    starts = [s for s, _ in ctx["calls"]]
    ms, prev = [], float("-inf")
    for end in ctx["driver"].unit_ends:
        began = max([prev] + [s for s in starts if s <= end])
        ms.append(1e3 * (end - began))
        prev = end
    return compare.p95(ms)
