"""Device milliseconds a tree spends in its leaf pass: the traced window's
own time under `tree.leaf` (the last level's node totals, the leaf values,
each row's prediction looked up from them), a tree. `tree.leaf/psum`, a
meshed tree's all-reduce of the totals, is not in it:
`tree_psum_ms_per_tree` has the all-reduces. Joined by
`benchmarks/lib/scopes.py`; a program without `scope_table` gives nothing."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.tree_ms(ctx, r"tree\.leaf")
