"""Whole-step share of the chip's peak for a tree: the least time one tree can
take on this chip over the seconds a tree took in the window (all trees over
all of its wall time). Histogram building does about one add a byte read, so
the floor is HBM traffic: benchmarks/lib/work.py counts it from shapes."""

from benchmarks.lib import work


def read(ctx):
    c = ctx["cell"].config
    least_s = work.tree_min_bytes(c["rows"], c["features"], c["max_depth"]) \
        / work.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    trees_per_s = ctx["rate"] / c["rows"]
    return 100.0 * least_s * trees_per_s
