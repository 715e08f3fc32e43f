"""Device milliseconds a tree spends on the code operand on its way to the
kernel: the traced window's own time in operations whose `op_name` has a
`tree.codes` part, a tree. That is the operand's pad to whole blocks, its cut
into a chunk's columns and its cast, which the whole-tree program writes
inside a level's `hist` (`tree.L1/hist/tree.codes/pad`; the level is folded
out: every level writes the pad and the compiler keeps one a tree), the
`tree.codes8` program (int32 codes clipped and cast to int8, once a forest)
and the `tree.hist` programs of the growers that drive levels from the host.
The row-major copy that layout assignment makes of the operand carries the
parameter's `op_name` and is in `tree_unscoped_ms_per_tree`. 0.0 where the
scope holds nothing; nothing at all for a program without `scope_table`.
Joined by `benchmarks/lib/scopes.py`."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.tree_ms(ctx, r"tree\.codes")
