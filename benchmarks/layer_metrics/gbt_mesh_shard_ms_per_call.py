"""Host milliseconds a meshed `train_trees` call spends placing its rows
over the mesh: the window's `train.trees.shard` spans (inside
`train.trees.prologue`: cast, pad, put, the validity draw's placement), over
their count. A program without the span (a parent commit) gives nothing."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.ring(ctx, "train.trees.shard")
    if not evs:
        return None
    return 1e3 * hostspans.seconds(evs, "train.trees.shard") / len(evs)
