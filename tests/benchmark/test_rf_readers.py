"""The forest cell's five per-layer readers, each on a stated `ctx`: what
they read, the byte and FLOP counts they rest on, and that each returns nothing,
never 0, where the trace or the program has nothing of the kind (an untraced
run, a parent commit's traced run)."""

import pytest

from benchmarks.lib import rf_work, spec, work
from shifu_tpu import obs

T0 = 1000.0
CALLS = [(T0, T0 + 10.0), (T0 + 10.0, T0 + 20.0)]
CELL = "higgs_rf.train_depth10"
HBM, MXU = 819e9, 197e12
N, SLOTS = 5_500_000, 28 * 33
DEEP, SHALLOW = [7, 8, 9], list(range(7))

FUSED = ('%tree_fused_level.42 = (f32[1,512]{1,0}) custom-call(%a, %b), '
         'custom_call_target="tpu_custom_call", frontend_attributes='
         '{kernel_metadata={\n"L":"32",\n"kernel":"tree_fused_level"\n}}')
HIST_L64 = ('%tree_hist.3 = f32[64,1024]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={"L":"64","kernel":"tree_hist"}}')
HIST_L256 = ('%tree_hist.5 = f32[256,1024]{1,0} custom-call(%a), '
             'custom_call_target="tpu_custom_call", frontend_attributes='
             '{kernel_metadata={\n"L":"256",\n"kernel":"tree_hist"\n}}')
SCAN = "%fusion.7 = f32[3,512,924]{2,1,0} fusion(%hist), kind=kLoop"


def _reader(name):
    return spec.load_module("layer_metrics", name)


class _Driver:
    def __init__(self, ends):
        self.unit_ends = ends


def _ctx(**kw):
    ctx = {"window_start": T0, "calls": CALLS, "trace": None,
           "driver": _Driver([1.0] * 20), "cell": spec.Cell(CELL),
           "rate": 5_500_000.0 * 2, "device_kind": "TPU v5 lite"}
    ctx.update(kw)
    return ctx


def _trace(ops):
    return {"busy_s": 12.0, "window_s": 20.0, "op_seconds": ops}


@pytest.fixture
def ring():
    obs.reset()

    def put(name, start, seconds, parent="", **args):
        obs.tracer().record(name, T0 + start, T0 + start + seconds, parent,
                            args)
    yield put
    obs.reset()


# ---- the bytes ----

def test_forest_bytes_are_the_issues_counts():
    """44 B a row (28 int8 codes, three f32 planes, an int32 node id): a
    depth-10 tree reads every row at the root and for the leaf pass and
    half of them at each of nine levels, 6.5 x n x 44 B = 1.573 GB; the
    three levels past 32 built nodes 3 x 0.5 x n x 44 B = 363 MB."""
    n = 5_500_000
    assert rf_work.forest_tree_min_bytes(n, 28, 10) == 6.5 * n * 44 \
        == 1_573_000_000
    assert rf_work.levels_min_bytes(n, 28, DEEP) == 1.5 * n * 44 \
        == 363_000_000
    # the root reads every row, the six levels below it half
    assert rf_work.levels_min_bytes(n, 28, SHALLOW) == 4 * n * 44
    assert rf_work.levels_min_bytes(n, 28, range(10)) \
        == work.tree_min_bytes(n, 28, 10, code_bytes=1, plane_bytes=4,
                               leaf_pass=False)
    assert rf_work.forest_tree_min_bytes(n, 28, 10) == work.tree_min_bytes(
        n, 28, 10, code_bytes=1, plane_bytes=4)
    # a GBT tree's bf16 planes make 38 B a row: the accepted readers' count
    assert work.tree_min_bytes(n, 28, 6) == 4.5 * n * 38
    assert rf_work.levels_min_bytes(n, 28, []) == 0


def test_forest_flops_are_the_one_hot_matmuls_of_the_built_nodes():
    """A level below the root builds half its nodes from half the rows
    (64, 128, 256 at levels 7, 8, 9; 1, 2, ..., 32 at levels 1..6), the root
    one from all: 2 FLOPs x 3 planes x 924 slots a row and built node. Past
    32 nodes the MXU's floor is 78 times the HBM's, so it is the
    roofline; at the fused levels it still is, by 4."""
    assert rf_work.levels_dot_flops(N, SLOTS, DEEP) \
        == 2 * 3 * SLOTS * (N / 2) * (64 + 128 + 256)
    assert rf_work.levels_dot_flops(N, SLOTS, SHALLOW) \
        == 2 * 3 * SLOTS * (N + (N / 2) * 63)
    assert rf_work.levels_dot_flops(N, SLOTS, []) == 0
    peaks = work.peaks("TPU v5 lite")
    deep = rf_work.levels_floor_seconds(N, 28, SLOTS, DEEP, peaks)
    assert deep == rf_work.levels_dot_flops(N, SLOTS, DEEP) / MXU \
        == pytest.approx(34.67e-3, rel=1e-3)
    assert deep / (363e6 / HBM) == pytest.approx(78.2, rel=1e-2)
    shallow = rf_work.levels_floor_seconds(N, 28, SLOTS, SHALLOW, peaks)
    assert shallow == pytest.approx(5.031e-3, rel=1e-3)
    assert shallow / (4 * N * 44 / HBM) == pytest.approx(4.26, rel=1e-2)
    # a chip whose HBM were 100 times slower would be held by its bytes
    slow = dict(peaks, hbm_bytes_per_s=HBM / 100)
    assert rf_work.levels_floor_seconds(N, 28, SLOTS, DEEP, slow) \
        == 363e6 / (HBM / 100)


# ---- rf_mfu_pct ----

def test_rf_mfu_is_the_trees_floor_x_trees_a_second():
    read = _reader("rf_mfu_pct").read
    # two trees a second, each 1.573 GB / 819 GB/s = 1.921 ms at the least
    got = read(_ctx())
    assert got == pytest.approx(100 * 2 * 1.573e9 / HBM)
    assert got == pytest.approx(0.3841, rel=1e-3) and 0 < got < 100
    with pytest.raises(KeyError):
        read(_ctx(device_kind="cpu"))


# ---- tree_deep_kernel_ms_per_tree, tree_deep_kernel_roofline ----

def test_deep_kernel_readers_take_the_hist_mode_events_alone():
    ops = {FUSED: 3.0, HIST_L64: 1.0, HIST_L256: 4.0, SCAN: 0.5}
    ctx = _ctx(trace=_trace(ops))
    assert _reader("tree_deep_kernel_ms_per_tree").read(ctx) \
        == pytest.approx(1e3 * 5.0 / 20)
    # 20 trees x 34.67 ms of matmuls at the bf16 peak, of 5 s
    got = _reader("tree_deep_kernel_roofline").read(ctx)
    assert got == pytest.approx(
        100 * 20 * rf_work.levels_dot_flops(N, SLOTS, DEEP) / MXU / 5.0)
    assert got == pytest.approx(13.87, rel=1e-3)
    # the fused calls on the forest's f32 planes: 20 x 5.031 ms of 3 s
    got = _reader("tree_fused_level_roofline").read(ctx)
    assert got == pytest.approx(
        100 * 20 * rf_work.levels_dot_flops(N, SLOTS, SHALLOW) / MXU / 3.0)
    assert got == pytest.approx(3.354, rel=1e-3)
    # the accepted reader of every kernel sees the fused calls too
    assert _reader("tree_kernel_ms_per_tree").read(ctx) \
        == pytest.approx(1e3 * 8.0 / 20)


@pytest.mark.parametrize("name", ["tree_deep_kernel_ms_per_tree",
                                  "tree_deep_kernel_roofline",
                                  "tree_fused_level_roofline"])
@pytest.mark.parametrize("trace,ends", [
    (None, [1.0]),
    (_trace({}), [1.0]),
    (_trace({SCAN: 0.5}), [1.0]),  # no kernel of either name
    (_trace({FUSED: 3.0, HIST_L64: 2.0}), []),  # no tree ended in the window
], ids=["untraced", "empty", "no_kernel", "no_tree"])
def test_kernel_readers_read_nothing(name, trace, ends):
    assert _reader(name).read(_ctx(trace=trace, driver=_Driver(ends))) is None


def test_each_kernel_reader_takes_its_own_kernels_events_alone():
    only_fused = _ctx(trace=_trace({FUSED: 3.0, SCAN: 0.5}))
    assert _reader("tree_deep_kernel_ms_per_tree").read(only_fused) is None
    assert _reader("tree_deep_kernel_roofline").read(only_fused) is None
    assert _reader("tree_fused_level_roofline").read(only_fused) > 0
    only_deep = _ctx(trace=_trace({HIST_L64: 2.0}))
    assert _reader("tree_fused_level_roofline").read(only_deep) is None
    assert _reader("tree_deep_kernel_roofline").read(only_deep) > 0


# ---- rf_bag_ms_per_call ----

def test_bag_ms_is_the_windows_bag_spans_over_their_calls(ring):
    read = _reader("rf_bag_ms_per_call").read
    assert read(_ctx()) is None  # a program without the span
    ring("train.trees.bag", -9.0, 3.0, call=1, k=0)  # warm-up
    for call, start in ((2, 0.0), (3, 10.0)):
        # the first tree's in the prologue, the next inside the trees before
        ring("train.trees.bag", start + 0.1, 0.3,
             "train.trees.call/train.trees.prologue", call=call, k=0,
             rows=5_500_000, bytes=11_000_000)
        ring("train.trees.prologue", start, 0.5, "train.trees.call")
        for k in range(1, 10):
            ring("train.trees.bag", start + k, 0.3 + 0.01 * (call - 2),
                 "train.trees.call/train.tree", call=call, k=k,
                 rows=5_500_000, bytes=11_000_000)
    assert read(_ctx()) == pytest.approx((3000.0 + 3090.0) / 2)
    assert read(_ctx(calls=[])) is None


# ---- what the cell lists ----

def test_the_cell_lists_its_readers_and_not_the_two_byte_ones():
    names = {m["name"] for m in spec.Cell(CELL).metrics("per_layer")}
    assert names == {
        "rf_mfu_pct", "tree_deep_kernel_ms_per_tree",
        "tree_deep_kernel_roofline", "rf_bag_ms_per_call",
        "tree_fused_level_roofline",
        "gbt_tree_ms_p95", "gbt_host_ms_per_tree", "tree_kernel_ms_per_tree",
        "tree_xla_ms_per_tree", "tree_hist_built_per_tree",
        "tree_kernel_calls_per_tree", "tree_route_dense_per_tree",
        "device_idle_pct.gbt", "setup_trace_lower_s",
        "setup_compile_or_fetch_s"}
    # those count 2-byte planes and every level: not this cell's
    assert not names & {"gbt_mfu_pct", "tree_kernel_roofline",
                        "tree_hist_kernel_roofline"}
    e2e = {m["name"] for m in spec.Cell(CELL).metrics("end_to_end")}
    assert e2e == {"gbt_row_trees_per_s", "setup_s"}
