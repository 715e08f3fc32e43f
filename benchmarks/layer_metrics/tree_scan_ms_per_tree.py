"""Device milliseconds a tree spends in XLA's split scan and the subtraction
before it: the traced window's own time under `tree.L{L}/scan` (the XLA scan
of a derived sibling, or of a whole level past the fused kernel's 32 nodes)
and `tree.L{L}/derive` (parent less built child, and the interleave), all
levels together, a tree. The scan inside the fused kernel is the kernel's.
Joined by `benchmarks/lib/scopes.py`; a program without `scope_table` gives
nothing."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.tree_ms(ctx, r"tree\.L\d+/(?:scan|derive)")
