"""Device milliseconds a tree spends outside the tree kernel: the summed own
time of every device operation of the traced window, less the events that
`tree_kernel_ms_per_tree` finds by the kernel's name, over the trees whose
ends fall inside the window. It is XLA's part of the whole-tree program (row
routing, the kernel's pads and casts, derive, scan, leaves) plus what runs
between trees (labels, predictions, errors). Untraced, or with no tree in
the window, nothing is returned."""

from benchmarks.lib import spec

KERNEL = spec.load_module("layer_metrics", "tree_kernel_ms_per_tree").KERNEL


def read(ctx):
    tr = ctx["trace"]
    trees = len(ctx["driver"].unit_ends)
    if not tr or not trees:
        return None
    other_s = sum(v for k, v in tr["op_seconds"].items()
                  if not KERNEL.search(k))
    return 1e3 * other_s / trees
