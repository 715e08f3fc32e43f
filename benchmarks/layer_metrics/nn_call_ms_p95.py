"""95th percentile of the benchmark's own clock around each train_nn call,
over all calls of the window."""

from benchmarks.lib import compare


def read(ctx):
    return compare.p95(1e3 * (e - s) for s, e in ctx["calls"])
