"""Host milliseconds a forest's `train_trees` call spends drawing its bags:
the window's `train.trees.bag` spans (one a tree: a Poisson draw and a
column subset on the host, and the bag's crossing to the device) summed,
over the calls they belong to. The first tree's is in the prologue, with the
device idle; tree k + 1's is drawn once tree k is dispatched, so it is host
work done and moves the rate only through what the device's tree does not
hide (the first draw of a call, or all of them should a tree get faster
than a draw): what is not hidden shows in the idle share. A program without
the span (a parent commit, or a call that is not a forest's) gives
nothing."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.ring(ctx, "train.trees.bag")
    calls = {e["args"].get("call") for e in evs}
    if not evs:
        return None
    return 1e3 * hostspans.seconds(evs, "train.trees.bag") / len(calls)
