"""Device milliseconds a tree spends moving its rows to their children: the
traced window's own time in the operations written under a level's
`tree.L{L}/route` scope (`route_rows`: the split feature's table entry, the
mask word, the build bit of the level below), all levels together, over the
trees whose ends fall inside the window. Scopes are joined to events by
`benchmarks/lib/scopes.py`; a program without `scope_table` gives nothing."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.tree_ms(ctx, r"tree\.L\d+/route")
