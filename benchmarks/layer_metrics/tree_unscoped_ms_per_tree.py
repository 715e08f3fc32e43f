"""Device milliseconds a tree that the program's scopes do not cover: the
traced window's own time in operations whose instruction carries no scope of
the program's (what the compiler made itself: layout copies, the pieces of
an operation it split, a multi-output fusion; and the few lines of the tree
programs outside every scope) plus those no kept executable has an
instruction for (programs dispatched outside every seam: the elementwise
updates of labels and predictions between trees), the tree kernel's events
left out, a tree. With the five scoped readers beside it, it adds up to
`tree_xla_ms_per_tree`. Joined by `benchmarks/lib/scopes.py`; a program
without `scope_table` gives nothing."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.tree_ms(ctx, "%s|%s" % (scopes.UNSCOPED, scopes.UNMATCHED))
