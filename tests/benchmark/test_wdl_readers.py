"""The WDL cell's per-layer readers, each on a stated `ctx`: what they read,
and that each returns nothing, never 0, where the program or the trace has
nothing of the kind (a parent commit's traced run); and the FLOP count they
rest on against XLA's own for the same matmuls."""

import pytest

from benchmarks.lib import spec, wdl_work
from shifu_tpu import obs

T0 = 1000.0
CALLS = [(T0, T0 + 10.0), (T0 + 10.0, T0 + 20.0)]
CELL = "criteo_wdl.train_fullbatch"


def _reader(name):
    return spec.load_module("layer_metrics", name)


class _Driver:
    """`holders` None: a program that hands out no executable (a parent
    commit)."""

    def __init__(self, n=1000, epochs=2, holders=(), count=None):
        self.n, self.epochs, self._count = n, epochs, count
        self._holders = None if holders is None else set(holders)

    def program_lookups(self):
        return self._holders

    def program_lookup_count(self):
        return self._count


def _ctx(**kw):
    ctx = {"window_start": T0, "calls": CALLS, "trace": None,
           "driver": _Driver(), "cell": spec.Cell(CELL), "rate": 573_008.0,
           "device_kind": "TPU v5 lite"}
    ctx.update(kw)
    return ctx


@pytest.fixture
def ring():
    obs.reset()

    def put(name, start, seconds, parent="", **args):
        obs.tracer().record(name, T0 + start, T0 + start + seconds, parent,
                            args)
    yield put
    obs.reset()


# ---- the count ----

def test_wdl_macs_are_the_issues_count():
    macs = wdl_work.wdl_macs_per_row(13, 26, 8, [100, 50])
    assert macs == {"forward": 27_163, "weight_grad": 27_163,
                    "input_grad": 25_850}
    assert wdl_work.wdl_flops_per_row_epoch(13, 26, 8, [100, 50]) == 160_352


def test_wdl_flops_match_xlas_count_for_the_same_matmuls():
    """The tower and the wide dense dot as plain matmuls, forward and the
    gradients somebody needs (weights, and inputs but for the dense columns
    of the first layer): XLA's cost analysis counts the same FLOPs a row."""
    import jax
    import jax.numpy as jnp

    n, n_dense, n_cat, embed, hidden = 512, 13, 26, 8, [100, 50]
    sizes = [n_dense + n_cat * embed] + hidden + [1]

    def loss(params, emb, dense):
        ws, wide_dense = params
        h = jnp.concatenate([dense, emb], axis=1)
        for w in ws:
            h = h @ w
        return jnp.sum(h[:, 0] + dense @ wide_dense)

    ws = [jnp.ones((a, b)) for a, b in zip(sizes[:-1], sizes[1:])]
    args = ((ws, jnp.ones(n_dense)), jnp.ones((n, n_cat * embed)),
            jnp.ones((n, n_dense)))
    cost = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        *args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    want = wdl_work.wdl_flops_per_row_epoch(n_dense, n_cat, embed, hidden)
    assert cost["flops"] / n == pytest.approx(want, rel=0.02)


# ---- wdl_mfu_pct ----

def test_wdl_mfu_is_flops_x_rate_over_the_peak():
    got = _reader("wdl_mfu_pct").read(_ctx())
    assert got == pytest.approx(100 * 160_352 * 573_008.0 / 197e12)
    assert 0 < got < 100
    with pytest.raises(KeyError):
        _reader("wdl_mfu_pct").read(_ctx(device_kind="cpu"))


# ---- wdl_lookup_ms_per_epoch ----

GATHER = ("%gather.3 = f32[2865039]{0} gather(f32[10001]{0} %p, "
          "s32[2865039,1]{1,0} %i), offset_dims={}")
FUSED = ("%fusion.668 = bf16[2865039,8]{1,0} fusion(f32[10001,8]{1,0} %a, "
         "s32[2865039]{0} %b), kind=kCustom, calls=%fused_computation.7")
SCATTER_FUSED = ("%fusion.12 = f32[10001]{0} fusion(f32[2865039]{0} %g), "
                 "kind=kCustom, calls=%fused_computation.9")
TOWER = ("%convolution_add_fusion = f32[2865039,100]{1,0} fusion(%x, %w), "
         "kind=kOutput, calls=%fused_computation.1")
AFTER_A_GATHER = ("%add.5 = f32[2865039]{0} add(f32[2865039]{0} %gather.3, "
                  "f32[2865039]{0} %y)")


def _trace(ops):
    return {"busy_s": 19.0, "window_s": 20.0, "op_seconds": ops}


def test_lookup_ms_sums_the_events_the_driver_names():
    read = _reader("wdl_lookup_ms_per_epoch").read
    ops = {GATHER: 1.0, FUSED: 2.0, SCATTER_FUSED: 4.0, TOWER: 0.5,
           AFTER_A_GATHER: 0.25}
    drv = _Driver(epochs=2, holders={"gather.3", "fusion.668", "fusion.12"})
    # two calls of two epochs: 7 s of lookups over 4 epochs
    assert read(_ctx(trace=_trace(ops), driver=drv)) == pytest.approx(1750.0)


@pytest.mark.parametrize("holders", [None, ()], ids=["no_accessor", "none"])
def test_lookup_ms_is_nothing_where_the_driver_names_no_holder(holders):
    """Never the bare `gather` events alone: on a chip they are a part of
    the lookups (the rest sit in fusions), and a part read as the whole
    would show a later PR a gain it did not make."""
    read = _reader("wdl_lookup_ms_per_epoch").read
    ops = {GATHER: 1.0, FUSED: 2.0, SCATTER_FUSED: 4.0}
    drv = _Driver(epochs=2, holders=holders)
    assert read(_ctx(trace=_trace(ops), driver=drv)) is None


def test_lookup_ms_is_nothing_without_a_trace_an_event_or_the_driver():
    read = _reader("wdl_lookup_ms_per_epoch").read
    named = _Driver(holders={"gather.3"})
    assert read(_ctx(driver=named)) is None
    assert read(_ctx(trace=_trace({TOWER: 0.5, AFTER_A_GATHER: 1.0}),
                     driver=named)) is None
    assert read(_ctx(trace=_trace({GATHER: 1.0}), calls=[],
                     driver=named)) is None

    class NoLookups:
        unit_ends = []

    assert read(_ctx(trace=_trace({GATHER: 1.0}), driver=NoLookups())) is None


def test_lookup_holders_reads_a_compiled_modules_text():
    mod = spec.load_module("drivers", "wdl_fullbatch")
    text = """HloModule jit_program

%fused_computation.7 (p0: f32[9,8], p1: s32[64,1]) -> f32[64,8] {
  %p0 = f32[9,8]{1,0} parameter(0)
  %p1 = s32[64,1]{1,0} parameter(1)
  ROOT %gather.1 = f32[64,8]{1,0} gather(%p0, %p1), offset_dims={1}
}

%fused_computation.9 (p0: f32[9], p1: s32[64,1], p2: f32[64]) -> f32[9] {
  %p0.1 = f32[9]{0} parameter(0)
  ROOT %scatter-add.2 = f32[9]{0} scatter(%p0.1, %p1.1, %p2.1), to_apply=%add
}

%fused_computation.1 (a: f32[64,8]) -> f32[64,8] {
  ROOT %neg = f32[64,8]{1,0} negate(%a)
}

%fused_computation.11 (p0: s32[64,1], p1: f32[64]) -> f32[9] {
  %fusion.3 = f32[9]{0} fusion(%c), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.4 = f32[9]{0} fusion(%fusion.3, %p0.2, %p1.2), kind=kCustom, calls=%fused_computation.9
}

%body (c: (f32[9,8])) -> (f32[9,8]) {
  %fusion.668 = f32[64,8]{1,0} fusion(%t, %i), kind=kCustom, calls=%fused_computation.7
  %fusion.12 = f32[9]{0} fusion(%w, %i, %g), kind=kCustom, calls=%fused_computation.9
  %negate_fusion = f32[64,8]{1,0} fusion(%fusion.668), kind=kLoop, calls=%fused_computation.1
  %fusion.759 = f32[9]{0} fusion(%i, %g), kind=kCustom, calls=%fused_computation.11
  %gather.5 = f32[64]{0} gather(%w, %i), offset_dims={}
  ROOT %tuple = (f32[9,8]) tuple(%t)
}

ENTRY %main (x: f32[9,8]) -> f32[9,8] {
  ROOT %while = (f32[9,8]) while(%x), condition=%cond, body=%body
}
"""
    # fusion.759 wraps fusion.4, which holds the scatter: found at any depth;
    # fusion.3 and negate_fusion hold neither
    assert mod.lookup_holders(text) == {
        "gather.1", "scatter-add.2", "fusion.668", "fusion.12", "gather.5",
        "fusion.4", "fusion.759"}


def test_program_lookups_reads_the_executable_the_entry_ran(monkeypatch):
    """The driver's own program at a small size, run here through the entry:
    every name is an instruction of the executable the dispatch seam kept
    (`obs.profile.compiled_texts`), a gather and a scatter a table at least
    (on any backend). A program without the accessor names nothing; one
    with it that kept nothing is an error, never a part of the lookups."""
    from shifu_tpu.obs import profile

    cell = spec.Cell(CELL)
    cell.config = dict(cell.config, category_cap=20)
    drv = spec.load_module("drivers", "wdl_fullbatch").setup(cell, 3, 500)
    try:
        drv.call()
        names = drv.program_lookups()
        count = drv.program_lookup_count()
    finally:
        drv.trainer._PROGRAMS.clear()
    assert len(names) >= 2 * 26
    assert all(isinstance(n, str) and " " not in n for n in names)
    assert 2 * 26 <= count <= len(names)
    monkeypatch.setattr(profile, "compiled_texts", lambda name: [])
    with pytest.raises(RuntimeError, match="kept no executable"):
        drv.program_lookups()
    monkeypatch.delattr(profile, "compiled_texts")
    assert drv.program_lookups() is None
    assert drv.program_lookup_count() is None


def test_compiled_texts_are_the_seams_own_executables():
    import jax
    import jax.numpy as jnp

    from shifu_tpu.obs import profile

    fn = jax.jit(lambda x: jnp.tanh(x) * 3.0)
    assert profile.compiled_texts("test.texts_seam") == []
    try:
        profile.dispatch("test.texts_seam", fn, jnp.ones((8,)))
        profile.dispatch("test.texts_seam", fn, jnp.ones((16,)))
        texts = profile.compiled_texts("test.texts_seam")
        assert len(texts) == 2 and all("tanh" in t for t in texts)
        assert profile.compiled_texts("test.other_seam") == []
    finally:
        profile.release_fn(fn)
    assert profile.compiled_texts("test.texts_seam") == []


# ---- wdl_lookups_per_row_epoch ----

def test_lookups_per_row_epoch_is_the_compiled_programs_count():
    read = _reader("wdl_lookups_per_row_epoch").read
    assert read(_ctx()) is None  # a program that hands out no executable
    assert read(_ctx(driver=_Driver(count=97))) == 97

    class NoLookups:
        unit_ends = []

    assert read(_ctx(driver=NoLookups())) is None


def test_lookup_count_counts_both_opcodes():
    mod = spec.load_module("drivers", "wdl_fullbatch")
    assert mod.lookup_count("\n".join([GATHER, FUSED, SCATTER_FUSED, TOWER,
                                       AFTER_A_GATHER])) == 1
    assert mod.lookup_count(
        "%s = f32[9]{0} scatter(%a, %b, %c), to_apply=%add\n" + GATHER) == 2


# ---- wdl_host_ms_per_call ----

def test_host_ms_is_call_less_program_over_the_windows_calls(ring):
    read = _reader("wdl_host_ms_per_call").read
    assert read(_ctx()) is None  # a program without the spans
    ring("train.wdl.call", -8.0, 4.0)  # warm-up: not the window's
    ring("train.wdl.program", -7.9, 3.8)
    for start in (0.0, 10.0):
        ring("train.wdl.prologue", start, 0.03, "train.wdl.call")
        ring("train.wdl.program", start + 0.03, 9.9, "train.wdl.call")
        ring("train.wdl.pull", start + 9.93, 0.01, "train.wdl.call")
        ring("train.wdl.call", start, 9.95)
    assert read(_ctx()) == pytest.approx(50.0)


# ---- the accepted readers the cell is appended to ----

def test_the_cell_reports_the_nn_familys_idle_share_and_call_tail():
    names = {m["name"] for m in spec.Cell(CELL).metrics("per_layer")}
    assert {"device_idle_pct.nn", "nn_call_ms_p95"} <= names
    assert "device_idle_pct.wdl" not in names
    read = _reader("device_idle_pct.nn").read
    assert read(_ctx()) is None
    assert read(_ctx(trace=_trace({}))) == pytest.approx(5.0)
    # the benchmark's own clock around each call: a stalled call shows
    calls = CALLS + [(T0 + 20.0, T0 + 34.0)]
    assert _reader("nn_call_ms_p95").read(_ctx(calls=calls)) \
        == pytest.approx(14000.0)
