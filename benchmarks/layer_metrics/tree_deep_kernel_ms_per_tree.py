"""Device milliseconds a tree spends in the hist-mode tree kernel: the summed
own time of the events that carry the kernel's name `tree_hist` in their
`kernel_metadata`, over the trees whose ends fall inside the traced window.
On one chip those are exactly the levels whose built half passes the fused
scan's 32 nodes (the configuration's `hist_mode_levels`). Where no event
carries the name, nothing is returned."""

import re


def kernel_seconds(ctx, kernel="tree_hist"):
    """(seconds in the events named `kernel`, trees of the window), or
    None."""
    tr = ctx["trace"]
    trees = len(ctx["driver"].unit_ends)
    if not tr or not trees:
        return None
    named = re.compile(r'"kernel"\s*:\s*"%s"' % kernel)
    kernel_s = sum(v for k, v in tr["op_seconds"].items() if named.search(k))
    return (kernel_s, trees) if kernel_s > 0 else None


def read(ctx):
    got = kernel_seconds(ctx)
    return None if got is None else 1e3 * got[0] / got[1]
