"""Whole-step share of the chip's bf16 peak: the matmul FLOPs a wide-and-deep
row-epoch needs (the tower and the wide dense dot, forward, weight gradients
and the input gradients somebody needs; the lookups do no arithmetic), times
the row-epochs a second the window completed (all calls over all of its wall
time), over the peak. At this shape the MXU's floor is above HBM's, so it
cannot pass 100 %."""

from benchmarks.lib import wdl_work, work


def read(ctx):
    c = ctx["cell"].config
    flops = wdl_work.wdl_flops_per_row_epoch(
        c["dense_columns"], c["categorical_columns"], c["embed_outputs"],
        c["hidden_nodes"])
    peak = work.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * ctx["rate"] / peak
