"""The fused tree kernel's share of its roofline on a forest's float32
planes: the least seconds the levels of up to 32 built nodes of the window's
trees need (every split level that is not among the configuration's
`hist_mode_levels`; the larger of their bytes at the HBM peak, 44 B a row,
and their one-hot matmuls at the MXU's peak, counted by
benchmarks/lib/rf_work.py), over the summed device time of the events named
`tree_fused_level`. The accepted `tree_kernel_roofline` counts 2-byte planes
and every kernel event, so it is not this cell's. Where no event carries the
name, nothing is returned."""

from benchmarks.lib import spec

_deep = spec.load_module("layer_metrics", "tree_deep_kernel_roofline")

KERNEL = "tree_fused_level"


def levels(config):
    return [lv for lv in range(config["max_depth"])
            if lv not in config["hist_mode_levels"]]


def read(ctx):
    return _deep.read_kernel(ctx, KERNEL, levels)
