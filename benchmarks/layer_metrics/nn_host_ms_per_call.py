"""What the host spends on a `train_nn` call outside the synced dispatch of
its program: the window's `train.nn.call` spans less their `train.nn.program`
spans (prologue, scalar pull, result), over the calls. Read from the
program's own spans."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.ring(ctx, "train.nn.")
    calls = sum(e["name"] == "train.nn.call" for e in evs)
    if not calls:
        return None
    return 1e3 * (hostspans.seconds(evs, "train.nn.call")
                  - hostspans.seconds(evs, "train.nn.program")) / calls
