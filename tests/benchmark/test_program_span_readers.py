"""The per-layer readers that read the program's own spans, counts and named
kernel, each on a stated `ctx`; and each returning nothing, never 0, where
the program has nothing of the kind (a parent commit's traced run)."""

import pytest

from benchmarks.lib import hostspans, spec, xplane
from shifu_tpu import obs

T0 = 1000.0  # where the stated window starts, on the host's clock
CALLS = [(T0, T0 + 10.0), (T0 + 10.0, T0 + 20.0)]


def _reader(name):
    return spec.load_module("layer_metrics", name)


def _ctx(**kw):
    ctx = {"window_start": T0, "calls": CALLS, "trace": None}
    ctx.update(kw)
    return ctx


@pytest.fixture
def ring():
    """A fresh tracer and registry; `put(name, start, seconds, parent)`
    records a span at seconds after the window's start."""
    obs.reset()

    def put(name, start, seconds, parent="", **args):
        obs.tracer().record(name, T0 + start, T0 + start + seconds, parent,
                            args)
    yield put
    obs.reset()


class _Driver:
    def __init__(self, ends):
        self.unit_ends = ends


# ---- the ring, clipped to the window ----

def test_ring_keeps_the_windows_spans_and_drops_the_warm_ups(ring):
    ring("train.tree", -5.0, 1.0)  # warm-up
    ring("train.tree", 1.0, 2.0)
    ring("train.tree", 19.0, 2.0)  # ends after the last call did
    ring("other", 2.0, 1.0)
    got = hostspans.ring(_ctx(), "train.")
    assert [(e["name"], round(e["dur"])) for e in got] == [
        ("train.tree", 2_000_000)]
    before = hostspans.ring(_ctx(), "train.", before_window=True)
    assert [round(e["dur"]) for e in before] == [1_000_000]
    assert hostspans.ring(_ctx(calls=[]), "train.") == []


def test_ring_is_empty_for_a_tracer_without_between(monkeypatch):
    class Old:
        events = []
    monkeypatch.setattr(obs, "tracer", lambda: Old())
    assert hostspans.ring(_ctx(), "train.") == []


# ---- gbt_host_ms_per_tree ----

def test_gbt_host_ms_per_tree(ring):
    read = _reader("gbt_host_ms_per_tree").read
    assert read(_ctx()) is None  # no spans: nothing, not 0
    call = "train.trees.call"
    ring("train.tree.wait", -3.0, 2.0, call)  # warm-up: left out
    for c in (0.0, 10.0):
        ring("train.trees.prologue", c, 0.1, call)
        for k in range(2):
            s = c + 0.1 + 4.9 * k
            tree = call + "/train.tree"
            ring("train.tree.wait", s + 0.05, 4.0, tree + "/train.tree.assemble")
            ring("train.tree.assemble", s, 4.2, tree)
            ring("train.tree.wait", s + 4.2, 0.5, tree)
            ring("train.tree.progress_cb", s + 4.7, 0.1, tree)
            ring("train.tree", s, 4.9, call, k=k)
        ring(call, c, 9.95)
    # (2 x 9.95 - 4 x 4.5 wait - 4 x 0.1 callback) / 4 trees = 0.375 s
    assert read(_ctx()) == pytest.approx(375.0)


def test_gbt_host_ms_per_tree_needs_both_a_call_and_a_tree(ring):
    read = _reader("gbt_host_ms_per_tree").read
    ring("train.tree", 1.0, 2.0)
    assert read(_ctx()) is None
    obs.reset()
    obs.tracer().record("train.trees.call", T0 + 1.0, T0 + 9.0)
    assert read(_ctx()) is None


# ---- nn_host_ms_per_call ----

def test_nn_host_ms_per_call(ring):
    read = _reader("nn_host_ms_per_call").read
    assert read(_ctx()) is None
    ring("train.nn.call", -2.0, 1.0)  # warm-up
    ring("train.nn.program", -1.9, 0.8, "train.nn.call")
    for c in (0.0, 10.0):
        ring("train.nn.prologue", c, 0.010, "train.nn.call")
        ring("train.nn.program", c + 0.010, 9.9, "train.nn.call")
        ring("train.nn.pull", c + 9.91, 0.002, "train.nn.call")
        ring("train.nn.call", c, 9.914)
    assert read(_ctx()) == pytest.approx(14.0)


# ---- tree_kernel_ms_per_tree ----

KERNEL_L1 = ('%tree_fused_level.42 = (f32[1,512]{1,0}) custom-call(%a, %b), '
             'custom_call_target="tpu_custom_call", frontend_attributes='
             '{kernel_metadata={\n"L":"1",\n"kernel":"tree_fused_level"\n}}')
KERNEL_L64 = ('%tree_hist.3 = f32[64,512]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call", frontend_attributes='
              '{kernel_metadata={"L":"64","kernel":"tree_hist"}}')
NAMELESS = ('%fused_entry.42 = (f32[1,512]{1,0}) custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={}}')
OTHER_KERNEL = ('%route.1 = s32[8]{0} custom-call(%a), custom_call_target='
                '"tpu_custom_call", frontend_attributes={kernel_metadata='
                '{"kernel":"tree_route"}}')


def test_tree_kernel_ms_per_tree_finds_the_kernel_by_its_name():
    read = _reader("tree_kernel_ms_per_tree").read
    ops = {KERNEL_L1: 1.2, KERNEL_L64: 0.8, OTHER_KERNEL: 5.0,
           "%fusion.7 = s32[5500000]{0} fusion(%codes)": 3.0}
    ctx = _ctx(trace={"op_seconds": ops}, driver=_Driver([1.0] * 4))
    assert read(ctx) == pytest.approx(500.0)
    # while there is one kernel, the two ways of finding it agree
    roofline = _reader("tree_kernel_roofline")
    ours = {k: v for k, v in ops.items() if k != OTHER_KERNEL}
    by_target = sum(v for k, v in ours.items() if roofline.KERNEL_MARK in k)
    ctx = _ctx(trace={"op_seconds": ours}, driver=_Driver([1.0] * 4))
    assert read(ctx) == pytest.approx(1e3 * by_target / 4)


@pytest.mark.parametrize("trace,ends", [
    (None, [1.0]),
    ({"op_seconds": {NAMELESS: 2.0}}, [1.0]),  # a kernel with no name
    ({"op_seconds": {}}, [1.0]),
    ({"op_seconds": {KERNEL_L1: 2.0}}, []),  # no tree ended in the window
])
def test_tree_kernel_ms_per_tree_reads_nothing(trace, ends):
    read = _reader("tree_kernel_ms_per_tree").read
    assert read(_ctx(trace=trace, driver=_Driver(ends))) is None


# ---- tree_hist_built_per_tree ----

def test_tree_hist_built_per_tree(ring):
    read = _reader("tree_hist_built_per_tree").read
    assert read(_ctx()) is None
    obs.registry().counter("tree.hist.built").inc(2 * 32)
    assert read(_ctx()) is None  # a program that does not count its trees
    obs.registry().counter("train.trees").inc(2)
    assert read(_ctx()) == pytest.approx(32.0)
    obs.registry().counter("tree.hist.built").inc(10 * 32)
    obs.registry().counter("train.trees").inc(10)
    assert read(_ctx()) == pytest.approx(32.0)


# ---- setup_trace_lower_s, setup_compile_or_fetch_s ----

def test_setup_split_reads_the_trainers_compile_events(ring):
    lower = _reader("setup_trace_lower_s").read
    fetch = _reader("setup_compile_or_fetch_s").read
    assert lower(_ctx()) is None and fetch(_ctx()) is None
    under = "train.trees.call/train.tree"
    ring("jax.trace", -30.0, 1.0, "", fun="make")  # the benchmark's data
    ring("jax.compile", -29.0, 2.0, "", fun="jit(make)")
    ring("jax.trace", -20.0, 0.5, under, fun="take_along_axis")  # nested
    ring("jax.trace", -21.0, 8.0, under, fun="fused_entry")
    ring("jax.lower", -13.0, 4.0, under, fun="jit(fused_entry)")
    ring("jax.compile", -9.0, 3.25, under, fun="jit(fused_entry)")
    ring("jax.compile", -5.0, 0.25, "train.trees.call/train.trees.prologue",
         fun="jit(codes8)")
    ring("jax.compile", 25.0, 6.0, "", fun="jit(reference)")  # the check's
    ring("jax.lower", 3.0, 1.0, under, fun="jit(late)")  # inside the window
    assert lower(_ctx()) == pytest.approx(12.5)
    assert fetch(_ctx()) == pytest.approx(3.5)


def test_setup_split_reads_nothing_when_no_event_is_the_trainers(ring):
    ring("jax.trace", -30.0, 1.0, "", fun="make")
    ring("jax.compile", -29.0, 2.0, "step.other", fun="jit(make)")
    assert _reader("setup_trace_lower_s").read(_ctx()) is None
    assert _reader("setup_compile_or_fetch_s").read(_ctx()) is None


# ---- idle gaps by the program's spans ----

def test_idle_gaps_are_named_by_the_programs_innermost_span():
    device = {"/device:TPU:0": [("k", 10, 40), ("k", 50, 90)]}
    spans = [("bench.call", 0, 100), ("shifu.train.trees.call", 2, 98),
             ("shifu.train.tree", 5, 95), ("shifu.train.tree.wait", 42, 49)]
    r = xplane.summarize(device, spans)
    names = sorted(g[0].split(" ")[0] for g in r["idle_gaps"])
    assert names == ["shifu.train.tree", "shifu.train.tree.wait"]
    assert hostspans.PREFIXES == ("bench.", "shifu.")


def test_hostspans_reads_the_recorded_fixture_like_xplane(tmp_path):
    import os

    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "nn_two_calls.xplane.pb")
    # a trace of a program without spans: bench.* alone, same reduction
    assert [s[0] for s in hostspans.read_spans(fixture)] == [
        "bench.call", "bench.call"]
    assert hostspans.reduce(fixture) == xplane.reduce(fixture)
