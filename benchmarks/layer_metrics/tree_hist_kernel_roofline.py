"""The hist-mode tree kernel's share of its roofline on one chip of a mesh:
the least seconds the split levels of the window's trees need at the HBM
peak for the first chip's rows (`rows / chips`; the bytes counted from
shapes by benchmarks/lib/work.py, the leaf pass left out: it is not the
kernel's), over the summed device time of the first chip's events that
carry the kernel's name `tree_hist` in their `kernel_metadata`. Where no
event carries the name, nothing is returned."""

import re

from benchmarks.lib import work

KERNEL = re.compile(r'"kernel"\s*:\s*"tree_hist"')


def read(ctx):
    tr = ctx["trace"]
    trees = len(ctx["driver"].unit_ends)
    if not tr or not trees:
        return None
    kernel_s = sum(v for k, v in tr["op_seconds"].items()
                   if KERNEL.search(k))
    if kernel_s <= 0:
        return None
    c, chips = ctx["cell"].config, ctx["cell"].chips
    per_tree = work.tree_min_bytes(c["rows"] / chips, c["features"],
                                   c["max_depth"], leaf_pass=False)
    least_s = trees * per_tree / work.peaks(ctx["device_kind"])[
        "hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
