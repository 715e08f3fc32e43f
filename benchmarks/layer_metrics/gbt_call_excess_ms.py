"""How much longer the window's longest `train_trees` call was than its
fellows: the longest of the window's `train.trees.call` spans less their
median, in milliseconds. Every call grows the same forest, so in a quiet run
this is a few milliseconds; a call that stalled (one in seventeen to
twenty-four one-chip runs, 1 to 4 s: PERF.md section 7) shows whole.
`gbt_call_excess_wait_ms` says how much of it lay where the loop waits for
the device. Read from the program's own spans, traced or not; nothing with
fewer than three calls in the window or a program without the spans."""

import statistics

from benchmarks.lib import hostspans


def longest_call(ctx):
    """(the window's `train.trees.call` spans, the longest of them), or None
    with fewer than three."""
    calls = [e for e in hostspans.ring(ctx, "train.trees.call")
             if e["name"] == "train.trees.call"]
    if len(calls) < 3:
        return None
    return calls, max(calls, key=lambda e: e["dur"])


def read(ctx):
    got = longest_call(ctx)
    if got is None:
        return None
    calls, longest = got
    return 1e-3 * (longest["dur"]
                   - statistics.median(e["dur"] for e in calls))
