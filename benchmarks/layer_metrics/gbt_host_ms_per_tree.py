"""What the host spends on a tree when it is not waiting for the device: the
window's `train.trees.call` spans less their `train.tree.wait` (every place
the loop blocks on the device) and `train.tree.progress_cb` (the caller's
time), over the window's `train.tree` spans. The prologue of each call is in
it, shared out over the call's trees. Read from the program's own spans."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.ring(ctx, "train.tree")
    trees = sum(e["name"] == "train.tree" for e in evs)
    call_s = hostspans.seconds(evs, "train.trees.call")
    if not trees or call_s <= 0:
        return None
    host_s = (call_s - hostspans.seconds(evs, "train.tree.wait")
              - hostspans.seconds(evs, "train.tree.progress_cb"))
    return 1e3 * host_s / trees
