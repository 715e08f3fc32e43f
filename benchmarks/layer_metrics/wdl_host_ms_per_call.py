"""What the host spends on a `train_wdl` call outside the synced dispatch of
its program: the window's `train.wdl.call` spans less their
`train.wdl.program` spans (prologue, pull of the chosen weights, result),
over the calls. Read from the program's own spans; a program without them
returns nothing."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.ring(ctx, "train.wdl.")
    calls = sum(e["name"] == "train.wdl.call" for e in evs)
    if not calls:
        return None
    return 1e3 * (hostspans.seconds(evs, "train.wdl.call")
                  - hostspans.seconds(evs, "train.wdl.program")) / calls
