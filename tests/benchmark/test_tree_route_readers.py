"""The two per-layer readers PR 27 adds, each on a stated `ctx` and registry:
`tree_xla_ms_per_tree` (device time outside the named kernel, a tree) and
`tree_route_dense_per_tree` (the program's `tree.route.dense` over
`train.trees`); each returns nothing, never 0, where there is nothing to
read."""

import pytest

from benchmarks.lib import spec
from shifu_tpu import obs

KERNEL_L1 = ('%tree_fused_level.42 = (f32[1,512]{1,0}) custom-call(%a, %b), '
             'custom_call_target="tpu_custom_call", frontend_attributes='
             '{kernel_metadata={\n"L":"1",\n"kernel":"tree_fused_level"\n}}')
KERNEL_L64 = ('%tree_hist.3 = f32[64,512]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call", frontend_attributes='
              '{kernel_metadata={"L":"64","kernel":"tree_hist"}}')
# a Mosaic kernel that is not the tree kernel counts with XLA's operations
OTHER_KERNEL = ('%route.1 = s32[8]{0} custom-call(%a), custom_call_target='
                '"tpu_custom_call", frontend_attributes={kernel_metadata='
                '{"kernel":"tree_route"}}')
ROUTE = "%fusion.7 = s32[5500000]{0} fusion(s32[5500000,28] %codes.1)"
COPY = "%copy.3 = bf16[5500416,3]{1,0} copy(%p)"


class _Driver:
    def __init__(self, ends):
        self.unit_ends = ends


def _reader(name):
    return spec.load_module("layer_metrics", name)


def _ctx(trace, ends):
    return {"trace": trace, "driver": _Driver(ends)}


@pytest.fixture
def registry():
    obs.reset()
    yield obs.registry()
    obs.reset()


def test_tree_xla_ms_per_tree_leaves_the_kernel_out_by_its_name():
    read = _reader("tree_xla_ms_per_tree").read
    ops = {KERNEL_L1: 1.2, KERNEL_L64: 0.8, OTHER_KERNEL: 0.5, ROUTE: 3.0,
           COPY: 0.25}
    ctx = _ctx({"op_seconds": ops}, [1.0] * 4)
    assert read(ctx) == pytest.approx(1e3 * 3.75 / 4)
    # with the kernel's reader it accounts for all of the device's own time
    kernel = _reader("tree_kernel_ms_per_tree").read(ctx)
    assert read(ctx) + kernel == pytest.approx(1e3 * sum(ops.values()) / 4)


def test_tree_xla_ms_per_tree_without_a_kernel_is_all_of_the_time():
    read = _reader("tree_xla_ms_per_tree").read
    ctx = _ctx({"op_seconds": {ROUTE: 3.0, COPY: 1.0}}, [1.0, 2.0])
    assert read(ctx) == pytest.approx(2000.0)


@pytest.mark.parametrize("trace,ends", [
    (None, [1.0]),  # untraced
    ({}, [1.0]),
    ({"op_seconds": {ROUTE: 2.0}}, []),  # no tree ended in the window
], ids=["untraced", "empty_trace", "no_trees"])
def test_tree_xla_ms_per_tree_reads_nothing(trace, ends):
    assert _reader("tree_xla_ms_per_tree").read(_ctx(trace, ends)) is None


def test_tree_route_dense_per_tree(registry):
    read = _reader("tree_route_dense_per_tree").read
    ctx = _ctx(None, [])  # a counter's ratio needs no trace
    assert read(ctx) is None
    registry.counter("train.trees").inc(2)
    assert read(ctx) is None  # a program that does not count its routing
    registry.counter("tree.route.dense").inc(2 * 6)
    assert read(ctx) == 6.0
    registry.counter("tree.route.dense").inc(10 * 6)
    registry.counter("train.trees").inc(10)
    assert read(ctx) == 6.0
    registry.counter("tree.route.gather").inc(5)  # counted apart
    assert read(ctx) == 6.0


def test_tree_route_dense_per_tree_with_no_trees(registry):
    registry.counter("tree.route.dense").inc(6)
    assert _reader("tree_route_dense_per_tree").read(_ctx(None, [])) is None


def test_both_readers_are_listed_for_the_gbt_cell_alone():
    cell = spec.Cell("higgs_gbt.train_levelwise")
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"tree_xla_ms_per_tree", "tree_route_dense_per_tree"} <= names
    nn = spec.Cell("higgs_nn.train_fullbatch")
    assert not {"tree_xla_ms_per_tree", "tree_route_dense_per_tree"} & {
        m["name"] for m in nn.metrics("per_layer")}
