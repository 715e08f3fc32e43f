"""Whole-step share of the host's chips for a tree grown over a mesh: the
least time one tree can take when every chip reads its share of the rows at
the HBM peak (the bytes counted from shapes by benchmarks/lib/work.py, over
chips x the peak), over the seconds a tree took in the window (all trees
over all of its wall time). Whatever implements the level, and however the
rows are shared out, it cannot pass 100 %."""

from benchmarks.lib import work


def read(ctx):
    c, chips = ctx["cell"].config, ctx["cell"].chips
    least_s = work.tree_min_bytes(c["rows"], c["features"], c["max_depth"]) \
        / (chips * work.peaks(ctx["device_kind"])["hbm_bytes_per_s"])
    trees_per_s = ctx["rate"] / c["rows"]
    return 100.0 * least_s * trees_per_s
