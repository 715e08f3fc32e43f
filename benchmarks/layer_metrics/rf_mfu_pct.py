"""Whole-step share of the chip's peak for a tree of a forest: the least time
one tree can take on this chip over the seconds a tree took in the window
(all trees over all of its wall time, the host's bag draws included).
Histogram building does about one add a byte read, so the floor is HBM
traffic, with the forest's float32 planes: benchmarks/lib/rf_work.py counts
it from shapes. It is the algorithm's floor, whatever implements a level."""

from benchmarks.lib import rf_work, work


def read(ctx):
    c = ctx["cell"].config
    least_s = rf_work.forest_tree_min_bytes(
        c["rows"], c["features"], c["max_depth"]) \
        / work.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    trees_per_s = ctx["rate"] / c["rows"]
    return 100.0 * least_s * trees_per_s
