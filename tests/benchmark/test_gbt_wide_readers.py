"""The four per-layer readers of `higgs_gbt_255.train_depth8`, each on a
stated `ctx`, and the floors they rest on (`benchmarks/lib/
gbt_wide_work.py`), to the digit; each returns nothing, never 0, where the
trace or the program has nothing of the kind (an untraced run, a parent
commit's traced run)."""

import json
import os

import pytest

from benchmarks.lib import gbt_wide_work, spec, work
from shifu_tpu import obs

T0 = 1000.0
CALLS = [(T0, T0 + 11.0), (T0 + 11.0, T0 + 22.0)]
CELL = "higgs_gbt_255.train_depth8"
HBM, MXU = 819e9, 197e12
N, SLOTS = 11_000_000, 28 * 256
FUSED_LEVELS, HIST_LEVELS = list(range(7)), [7]

FUSED = ('%tree_fused_level.42 = (f32[32,512]{1,0}) custom-call(%a, %b), '
         'custom_call_target="tpu_custom_call", frontend_attributes='
         '{kernel_metadata={\n"L":"32",\n"kernel":"tree_fused_level"\n}}')
FUSED_L1 = ('%tree_fused_level.7 = (f32[1,512]{1,0}) custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={"L":"1","kernel":"tree_fused_level"}}')
HIST_L64 = ('%tree_hist.3 = f32[64,1024]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={"L":"64","kernel":"tree_hist"}}')
SCAN = "%fusion.7 = f32[3,128,7168]{2,1,0} fusion(%hist), kind=kLoop"


def _reader(name):
    return spec.load_module("layer_metrics", name)


class _Driver:
    def __init__(self, ends):
        self.unit_ends = ends


def _ctx(**kw):
    ctx = {"window_start": T0, "calls": CALLS, "trace": None,
           "driver": _Driver([1.0] * 20), "cell": spec.Cell(CELL),
           "rate": 1.0e7, "device_kind": "TPU v5 lite"}
    ctx.update(kw)
    return ctx


def _trace(ops):
    return {"busy_s": 21.0, "window_s": 22.0, "op_seconds": ops}


@pytest.fixture
def ring():
    obs.reset()

    def put(name, start, seconds, parent="", **args):
        obs.tracer().record(name, T0 + start, T0 + start + seconds, parent,
                            args)
    yield put
    obs.reset()


# ---- the floors ----

def test_the_floors_arithmetic_to_the_digit():
    """43,008 FLOP a row (2 x 3 planes x 7,168 slots, no nodes) against
    38 B: the fused levels read every row at the root and half at each of
    six levels, the level built at 64 nodes half."""
    assert gbt_wide_work.levels_dot_flops(N, SLOTS, FUSED_LEVELS) \
        == 43_008 * (11_000_000 + 6 * 5_500_000) == 1_892_352_000_000
    assert gbt_wide_work.levels_dot_flops(N, SLOTS, HIST_LEVELS) \
        == 43_008 * 5_500_000 == 236_544_000_000
    assert gbt_wide_work.levels_dot_flops(N, SLOTS, []) == 0
    assert gbt_wide_work.levels_min_bytes(N, 28, FUSED_LEVELS) \
        == 38 * (11_000_000 + 6 * 5_500_000) == 1_672_000_000
    assert gbt_wide_work.levels_min_bytes(N, 28, HIST_LEVELS) \
        == 38 * 5_500_000 == 209_000_000
    # the split levels' bytes are `work.tree_min_bytes`' without the leaf
    # pass, and `gbt_mfu_pct`'s tree is 5.5 x n x 38 B = 2.30 GB
    assert gbt_wide_work.levels_min_bytes(N, 28, range(8)) \
        == work.tree_min_bytes(N, 28, 8, leaf_pass=False)
    assert work.tree_min_bytes(N, 28, 8) == 5.5 * N * 38 == 2_299_000_000
    peaks = work.peaks("TPU v5 lite")
    fused = gbt_wide_work.levels_floor_seconds(N, 28, SLOTS, FUSED_LEVELS,
                                               peaks)
    hist = gbt_wide_work.levels_floor_seconds(N, 28, SLOTS, HIST_LEVELS,
                                              peaks)
    # the MXU holds every level: its floor is 4.7 times HBM's
    assert fused == pytest.approx(1_892_352_000_000 / MXU)
    assert fused == pytest.approx(9.606e-3, rel=1e-3)
    assert hist == pytest.approx(236_544_000_000 / MXU)
    assert fused / (1_672_000_000 / HBM) == pytest.approx(4.705, rel=1e-3)
    # a chip whose HBM were 100 times slower would be held by its bytes
    slow = dict(peaks, hbm_bytes_per_s=HBM / 100)
    assert gbt_wide_work.levels_floor_seconds(
        N, 28, SLOTS, HIST_LEVELS, slow) == 209_000_000 / (HBM / 100)


def test_the_levels_are_the_configurations():
    c = spec.Cell(CELL).config
    assert gbt_wide_work.fused_levels(c) == FUSED_LEVELS
    assert c["hist_mode_levels"] == HIST_LEVELS
    assert (c["rows"], c["features"] * c["slots_per_feature"]) == (N, SLOTS)


# ---- the two rooflines ----

def test_fused_roofline_is_the_fused_levels_floor_over_their_events():
    read = _reader("tree_wide_fused_roofline").read
    assert read(_ctx()) is None  # untraced
    assert read(_ctx(trace=_trace({SCAN: 3.0, HIST_L64: 1.0}))) is None
    ops = {FUSED: 10.0, FUSED_L1: 6.0, HIST_L64: 1.5, SCAN: 3.0}
    got = read(_ctx(trace=_trace(ops)))
    # 20 trees, 9.606 ms each at the least, over 16 s in the fused kernel
    assert got == pytest.approx(100 * 20 * 1_892_352_000_000 / MXU / 16.0)
    assert got == pytest.approx(1.2007, rel=1e-3) and 0 < got < 100
    with pytest.raises(KeyError):
        read(_ctx(trace=_trace(ops), device_kind="cpu"))


def test_hist_roofline_is_the_hist_levels_floor_over_their_events():
    read = _reader("tree_wide_hist_roofline").read
    assert read(_ctx()) is None
    assert read(_ctx(trace=_trace({SCAN: 3.0, FUSED: 1.0}))) is None
    got = read(_ctx(trace=_trace({FUSED: 10.0, HIST_L64: 1.5, SCAN: 3.0})))
    assert got == pytest.approx(100 * 20 * 236_544_000_000 / MXU / 1.5)
    assert got == pytest.approx(1.601, rel=1e-3) and 0 < got < 100
    # no tree ended in the window: nothing, not a division by zero
    assert read(_ctx(trace=_trace({HIST_L64: 1.5}),
                     driver=_Driver([]))) is None


# ---- tree_kernel_chunks_per_level ----

def test_chunks_per_level_gives_the_fused_count_where_a_level_is_fused():
    read = _reader("tree_kernel_chunks_per_level").read
    obs.reset()
    try:
        reg = obs.registry()
        assert read({}) is None
        reg.counter("tree.kernel.chunks", mode="hist").inc(2 * 7)
        assert read({}) is None  # a program that does not count its trees
        reg.counter("train.trees").inc(2)
        assert read({}) == pytest.approx(7.0)  # all levels in hist mode
        reg.counter("tree.kernel.chunks", mode="fused").inc(2 * 14)
        assert read({}) == pytest.approx(14.0)
        reg.counter("tree.kernel.chunks", mode="fused").inc(10 * 14)
        reg.counter("tree.kernel.chunks", mode="hist").inc(10 * 7)
        reg.counter("train.trees").inc(10)
        assert read({}) == pytest.approx(14.0)
    finally:
        obs.reset()


def test_chunks_per_level_reads_nothing_on_the_parent():
    obs.reset()
    try:
        obs.registry().counter("train.trees").inc(10)
        obs.registry().counter("tree.kernel.calls").inc(10 * 105)
        assert _reader("tree_kernel_chunks_per_level").read({}) is None
    finally:
        obs.reset()


# ---- setup_kernel_trace_s ----

def test_setup_kernel_trace_sums_the_spans_that_end_before_the_window(ring):
    read = _reader("setup_kernel_trace_s").read
    assert read(_ctx()) is None  # a program without the span
    parent = "train.trees.call/train.tree"
    for i in range(14):
        ring("tree.kernel.trace", -60.0 + i, 0.25, parent,
             kernel="tree_fused_level", L=1, chunk=i, W=512)
    ring("tree.kernel.trace", -40.0, 0.5, parent, kernel="tree_hist", L=64,
         chunk=0, W=1024)
    ring("jax.trace", -70.0, 30.0, parent, fun="tree_body")
    # one inside the window (a program traced late) is not set-up's
    ring("tree.kernel.trace", 3.0, 9.0, parent, kernel="tree_hist", L=64,
         chunk=1, W=1024)
    assert read(_ctx()) == pytest.approx(14 * 0.25 + 0.5)
    assert read(_ctx(calls=[])) is None


# ---- BENCHMARK.json ----

def test_the_four_are_listed_beside_their_neighbours():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    kernel = per_layer["tree_kernel_calls_per_tree"]
    for name, unit, source in [
            ("tree_wide_fused_roofline", "%", "device_trace"),
            ("tree_wide_hist_roofline", "%", "device_trace"),
            ("tree_kernel_chunks_per_level", "count", "program_counter")]:
        m = per_layer[name]
        assert (m["layer"], m["moves"]) == (kernel["layer"], kernel["moves"])
        assert (m["unit"], m["source"], m["workloads"]) == (unit, source,
                                                            [CELL])
    m = per_layer["setup_kernel_trace_s"]
    split = per_layer["setup_trace_lower_s"]
    assert (m["layer"], m["moves"]) == (split["layer"], split["moves"])
    assert (m["unit"], m["better"], m["source"], m["workloads"]) == (
        "s", "lower", "program_span", [CELL])
    assert list(per_layer)[-4:] == [
        "tree_wide_fused_roofline", "tree_wide_hist_roofline",
        "tree_kernel_chunks_per_level", "setup_kernel_trace_s"]
    for name in ("gbt_mfu_pct", "tree_kernel_calls_per_tree",
                 "setup_trace_lower_s", "tree_unscoped_ms_per_tree"):
        assert per_layer[name]["workloads"][-1] == CELL
    assert CELL not in per_layer["tree_kernel_roofline"]["workloads"]
