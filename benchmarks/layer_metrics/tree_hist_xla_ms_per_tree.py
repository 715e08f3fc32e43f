"""Device milliseconds a tree spends in XLA's share of the histogram phase:
the traced window's own time under `tree.L{L}/hist`, all levels together,
less the tree kernel's own events (`tree_kernel_ms_per_tree` has those,
found by the kernel's name) and less what is written under `tree.codes`
inside it (the code operand's pad, cut and cast:
`tree_codes_ms_per_tree`): the two row operands made a level, and what
gathers a level's histogram planes. Joined by `benchmarks/lib/scopes.py`; a
program without `scope_table` gives nothing."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.tree_ms(ctx, r"tree\.L\d+/hist")
