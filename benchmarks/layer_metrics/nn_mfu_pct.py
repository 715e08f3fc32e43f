"""Whole-step share of the chip's bf16 peak: the matmul FLOPs the algorithm
needs for a row-epoch, times the row-epochs a second the window completed
(all calls over all of its wall time), over the peak."""

from benchmarks.lib import work


def read(ctx):
    c = ctx["cell"].config
    flops = work.mlp_flops_per_row_epoch(c["features"], c["hidden_nodes"],
                                         c["outputs"])
    peak = work.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * ctx["rate"] / peak
