"""Seconds of set-up spent in `pallas_call` while the trainer's programs were
traced, which is where every kernel body is traced into a jaxpr of its own:
the program's `tree.kernel.trace` spans (one a Mosaic call of the whole-tree
program: 105 at 28 x 256 slots and depth 8) that end before the window
starts, summed. It is a part of `setup_trace_lower_s`, not beside it. A
program without the span (a parent commit) gives nothing."""

from benchmarks.lib import hostspans

SPAN = "tree.kernel.trace"


def read(ctx):
    evs = hostspans.ring(ctx, SPAN, before_window=True)
    if not evs:
        return None
    return hostspans.seconds(evs, SPAN)
