"""The fused tree kernel's share of its roofline at thousands of one-hot
columns: the least seconds the levels built at 1 to 32 nodes of the window's
trees need (every split level that is not among the configuration's
`hist_mode_levels`; level by level the larger of the bytes at the HBM peak,
38 B a row, and the one-hot matmuls at the MXU's bf16 peak counted without
the nodes, which at 7,168 columns is the larger: benchmarks/lib/
gbt_wide_work.py), over the summed device time of the events named
`tree_fused_level`. The accepted `tree_kernel_roofline` counts bytes alone
and every kernel event, so it is not this cell's. Where no event carries the
name, nothing is returned."""

from benchmarks.lib import gbt_wide_work, spec, work

KERNEL = "tree_fused_level"


def read_kernel(ctx, kernel, levels):
    """The roofline's seconds for the split `levels` of the window's trees,
    as a share of the device time of the events named `kernel`."""
    got = spec.load_module(
        "layer_metrics", "tree_deep_kernel_ms_per_tree").kernel_seconds(
            ctx, kernel)
    if got is None:
        return None
    kernel_s, trees = got
    c = ctx["cell"].config
    least_s = trees * gbt_wide_work.levels_floor_seconds(
        c["rows"], c["features"], c["features"] * c["slots_per_feature"],
        levels, work.peaks(ctx["device_kind"]))
    return 100.0 * least_s / kernel_s


def read(ctx):
    return read_kernel(ctx, KERNEL,
                       gbt_wide_work.fused_levels(ctx["cell"].config))
