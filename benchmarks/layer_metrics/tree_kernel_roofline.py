"""The fused tree kernel's share of its roofline: the least seconds the
levels of the window's trees need at the HBM peak (the bytes counted from
shapes by benchmarks/lib/work.py, the leaf pass left out: it is not the
kernel's), over the summed device time of the kernel's events.

The program gives its `pallas_call` no name, so the events are told apart by
what the trace prints of them: a `custom-call` whose target is
`tpu_custom_call`. Where there is none, nothing is returned."""

from benchmarks.lib import work

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    kernel_s = sum(v for k, v in tr["op_seconds"].items() if KERNEL_MARK in k)
    if kernel_s <= 0:
        return None
    c = ctx["cell"].config
    per_tree = work.tree_min_bytes(c["rows"], c["features"], c["max_depth"],
                                   leaf_pass=False)
    # the trees whose ends fall inside the traced window
    trees = len(ctx["driver"].unit_ends)
    least_s = trees * per_tree / work.peaks(ctx["device_kind"])[
        "hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
