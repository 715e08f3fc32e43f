"""The hist-mode tree kernel's share of its roofline in a forest's deep
levels: the least seconds those levels of the window's trees need (the
larger of their bytes at the HBM peak, half the rows a level at 44 B a row,
and their one-hot matmuls at the MXU's peak, which at 64 to 256 built nodes
is the larger by far; both counted by benchmarks/lib/rf_work.py; which
levels they are is the configuration's `hist_mode_levels`), over the summed
device time of the events named `tree_hist`. Where no event carries the
name, nothing is returned."""

from benchmarks.lib import rf_work, spec, work

KERNEL = "tree_hist"


def levels(config):
    return config["hist_mode_levels"]


def read_kernel(ctx, kernel, levels_of):
    """The roofline's seconds for the levels `levels_of(config)` of the
    window's trees, as a share of the device time of the events named
    `kernel`."""
    got = spec.load_module(
        "layer_metrics", "tree_deep_kernel_ms_per_tree").kernel_seconds(
            ctx, kernel)
    if got is None:
        return None
    kernel_s, trees = got
    c = ctx["cell"].config
    least_s = trees * rf_work.levels_floor_seconds(
        c["rows"], c["features"], c["features"] * c["slots_per_feature"],
        levels_of(c), work.peaks(ctx["device_kind"]))
    return 100.0 * least_s / kernel_s


def read(ctx):
    return read_kernel(ctx, KERNEL, levels)
