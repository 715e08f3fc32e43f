"""Device milliseconds a tree spends in the tree kernel: the summed own time
of the events whose HLO text carries the kernel's name in its
`kernel_metadata` (what `pallas_call(metadata=...)` becomes: the kernel is
found by its name, whatever else shares `tpu_custom_call`), over the trees
whose ends fall inside the traced window. Where no event carries the name (a
program that gives its kernel none), nothing is returned."""

import re

KERNEL = re.compile(r'"kernel"\s*:\s*"(tree_fused_level|tree_hist)"')


def read(ctx):
    tr = ctx["trace"]
    trees = len(ctx["driver"].unit_ends)
    if not tr or not trees:
        return None
    kernel_s = sum(v for k, v in tr["op_seconds"].items()
                   if KERNEL.search(k))
    if kernel_s <= 0:
        return None
    return 1e3 * kernel_s / trees
