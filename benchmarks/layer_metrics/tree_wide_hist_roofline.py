"""The hist-mode tree kernel's share of its roofline at thousands of one-hot
columns: the least seconds the configuration's `hist_mode_levels` of the
window's trees need (the level built at 64 nodes in `higgs_gbt_255`: half
the rows, the larger of their bytes at the HBM peak and their one-hot
matmuls at the MXU's bf16 peak counted without the nodes: benchmarks/lib/
gbt_wide_work.py), over the summed device time of the events named
`tree_hist`. Where no event carries the name, nothing is returned."""

from benchmarks.lib import spec

_fused = spec.load_module("layer_metrics", "tree_wide_fused_roofline")

KERNEL = "tree_hist"


def read(ctx):
    return _fused.read_kernel(ctx, KERNEL,
                              ctx["cell"].config["hist_mode_levels"])
