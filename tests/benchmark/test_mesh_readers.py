"""The five per-layer readers of the meshed GBT cell, each against a
synthetic two-chip trace reduced by `xplane.summarize` (as
`test_benchmark_lib.py::test_summarize_synthetic_two_chips` builds one), a
stated cell and a stated ring; each returns nothing, never 0, where there is
nothing to read."""

import types

import pytest

from benchmarks.lib import spec, work, xplane
from shifu_tpu import obs

S = 1_000_000_000  # a second, in the trace's nanoseconds
HIST = ('%tree_hist.3 = f32[64,512]{1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", frontend_attributes='
        '{kernel_metadata={"L":"16","kernel":"tree_hist"}}')
FUSED = ('%tree_fused_level.4 = (f32[1,512]{1,0}) custom-call(%a, %b), '
         'custom_call_target="tpu_custom_call", frontend_attributes='
         '{kernel_metadata={"L":"1","kernel":"tree_fused_level"}}')
# as the v5e's trace prints them: the instruction is named after the jax op
PSUM = ("%psum.54 = f32[3,16,924]{2,1,0:T(8,128)S(1)} all-reduce(f32[3,16,924]"
        "{2,1,0:T(8,128)S(1)} %pad_maximum_fusion.11), channel_id=1, "
        "replica_groups={{0,1,2,3}}, use_global_device_ids=true")
PSUM_DONE = "%all-reduce-done.1 = f32[2,64]{1,0} all-reduce-done(%s.1)"
SCAN = "%fusion.9 = f32[16,924]{1,0} fusion(f32[3,16,924] %all-reduce.5)"
ERRORS = ("%all-reduce.5 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(f32[] "
          "%get-tuple-element.1, f32[] %get-tuple-element), channel_id=1")
HBM = work.peaks("TPU v5 lite")["hbm_bytes_per_s"]


def _reader(name):
    return spec.load_module("layer_metrics", name)


def _cell(rows=8_000_000, chips=2):
    return types.SimpleNamespace(
        chips=chips, config={"rows": rows, "features": 28, "max_depth": 6})


def _ctx(ops0, ops1=(), trees=4, **kw):
    """Two chips over a 10 s window of one `bench.call`: the first chip runs
    `ops0` [(name, start s, seconds)], the second `ops1`."""
    ev = lambda ops: [(k, int(s * S), int((s + d) * S)) for k, s, d in ops]  # noqa: E731
    device = {"/device:TPU:0": ev(ops0), "/device:TPU:1": ev(ops1)}
    trace = xplane.summarize(device, [("bench.call", 0, 10 * S)], chips=2)
    ctx = {"trace": trace, "cell": _cell(), "device_kind": "TPU v5 lite",
           "driver": types.SimpleNamespace(unit_ends=[1.0] * trees),
           "window_start": 1000.0, "calls": [(1000.0, 1010.0)]}
    ctx.update(kw)
    return ctx


OPS0 = [(HIST, 0.0, 2.0), (PSUM, 2.0, 0.5), (SCAN, 2.5, 1.0),
        (HIST, 4.0, 2.0), (PSUM_DONE, 6.0, 0.25), (FUSED, 7.0, 1.0)]
OPS1 = [(HIST, 0.0, 9.0)]  # a slower second chip: the readers see the first


def test_tree_hist_kernel_roofline_reckons_the_first_chips_rows():
    read = _reader("tree_hist_kernel_roofline").read
    ctx = _ctx(OPS0, OPS1)
    per_tree = work.tree_min_bytes(4_000_000, 28, 6, leaf_pass=False)
    assert per_tree == 4_000_000 * 3.5 * 38
    # 4 s in `tree_hist` on the first chip; the fused kernel is not it
    assert read(ctx) == pytest.approx(100.0 * 4 * per_tree / HBM / 4.0)
    assert read(_ctx([(FUSED, 0.0, 1.0), (SCAN, 2.0, 1.0)])) is None
    assert read(_ctx(OPS0, trees=0)) is None
    assert read(dict(ctx, trace=None)) is None


def test_tree_psum_ms_per_tree_sums_the_first_chips_all_reduces():
    read = _reader("tree_psum_ms_per_tree").read
    assert read(_ctx(OPS0, OPS1)) == pytest.approx(1e3 * 0.75 / 4)
    # the errors program's all-reduce between trees counts too
    assert read(_ctx(OPS0 + [(ERRORS, 8.5, 0.25)])) == pytest.approx(250.0)
    # an operation that only reads an all-reduce's result is not one
    assert read(_ctx([(HIST, 0.0, 2.0), (SCAN, 2.5, 1.0)])) is None
    assert read(dict(_ctx(OPS0), trace=None)) is None


def test_gbt_mesh_mfu_pct_is_the_share_of_all_the_chips():
    read = _reader("gbt_mesh_mfu_pct").read
    rate = 16_000_000.0  # row-trees a second: 2 trees a second
    least_s = work.tree_min_bytes(8_000_000, 28, 6) / (2 * HBM)
    got = read(_ctx(OPS0, rate=rate))
    assert got == pytest.approx(100.0 * least_s * 2.0)
    # half of what the one-chip reader makes of the same numbers
    one = _reader("gbt_mfu_pct").read(_ctx(OPS0, rate=rate))
    assert got == pytest.approx(one / 2)
    # at the floor itself the share is 100 %
    assert read(_ctx(OPS0, rate=8_000_000 / least_s)) == pytest.approx(100.0)


def test_device_idle_pct_gbt_mesh_averages_the_chips():
    read = _reader("device_idle_pct.gbt_mesh").read
    # busy 6.75 s and 9 s of 10: 7.875 s on average
    assert read(_ctx(OPS0, OPS1)) == pytest.approx(21.25)
    assert read(dict(_ctx(OPS0), trace=None)) is None
    assert read(_ctx([], [])) is None


def test_gbt_mesh_shard_ms_per_call_reads_the_windows_spans():
    read = _reader("gbt_mesh_shard_ms_per_call").read
    obs.reset()
    try:
        ctx = _ctx(OPS0)
        assert read(ctx) is None  # a program without the span
        put = obs.tracer().record
        put("train.trees.shard", 990.0, 995.0, "", {"source": "host"})
        put("train.trees.shard", 1000.5, 1000.75, "", {"source": "device"})
        put("train.trees.shard", 1005.0, 1005.25, "", {"source": "device"})
        put("train.trees.prologue", 1005.0, 1006.0, "", {})
        assert read(ctx) == pytest.approx(250.0)  # the warm-up's left out
    finally:
        obs.reset()
