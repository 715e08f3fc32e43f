"""How much longer the window's longest `train_trees` call waited for the
device than its fellows: the summed `train.tree.wait` spans of the longest
`train.trees.call` (those that carry its `call` attribute) less the median
of the same sum over the window's calls, in milliseconds. Beside
`gbt_call_excess_ms`: where the two agree the extra time lay under
`block_until_ready` (the device, or the runtime under it), where this one
reads a few milliseconds it lay in the host's own code (the prologue, a bag's
draw, the assembly). Read from the program's own spans; nothing with fewer
than three calls in the window."""

import statistics

from benchmarks.lib import hostspans, spec

longest_call = spec.load_module("layer_metrics",
                                "gbt_call_excess_ms").longest_call


def read(ctx):
    got = longest_call(ctx)
    if got is None:
        return None
    calls, longest = got
    waits = {}
    for e in hostspans.ring(ctx, "train.tree.wait"):
        c = e["args"].get("call")
        waits[c] = waits.get(c, 0.0) + e["dur"]
    per_call = [waits.get(e["args"].get("call"), 0.0) for e in calls]
    return 1e-3 * (waits.get(longest["args"].get("call"), 0.0)
                   - statistics.median(per_call))
