"""Device time by the program's own scopes: `obs.profile.scope_table` on
executables a seam kept, `benchmarks/lib/scopes.py`'s join of a traced
window's events to it, the ten per-layer readers over that join, and the two
that read the slowest call off the program's spans. Each reader on a stated
`ctx`; each returning nothing where the program or the trace has nothing of
the kind (a parent commit's traced run)."""

import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import scopes, spec
from shifu_tpu import obs
from shifu_tpu.obs import profile
from shifu_tpu.train import tree_trainer as tt
from shifu_tpu.utils import environment

T0 = 1000.0
CALLS = [(T0, T0 + 10.0), (T0 + 10.0, T0 + 20.0)]


def _reader(name):
    return spec.load_module("layer_metrics", name)


class _Driver:
    def __init__(self, trees=0, epochs=0, holders=None):
        self.unit_ends = [T0 + 1.0 + k for k in range(trees)]
        self.epochs = epochs
        if holders is not None:
            self.program_lookups = lambda: set(holders)


def _ctx(ops=None, **kw):
    ctx = {"window_start": T0, "calls": CALLS, "driver": _Driver(),
           "trace": None if ops is None else {"op_seconds": ops}}
    ctx.update(kw)
    return ctx


def _event(name, shape="f32[600]{0}", rest="fusion(f32[600]{0} %p.1)"):
    return "%%%s = %s %s" % (name, shape, rest)


def _table(monkeypatch, *executables):
    """`scope_table` handing out the stated [(seam, {name: (shape,
    op_name)})]."""
    monkeypatch.setattr(profile, "scope_table", lambda: list(executables))


def _kept(seam):
    """What `scope_table` holds for one seam."""
    return [(s, ops) for s, ops in profile.scope_table() if s == seam]


# ---- the program's side: obs.profile.scope_table ----

def _two_scopes(x, w):
    with jax.named_scope("tree.L4/route"):
        y = x @ w
    with jax.named_scope("nn.fwd"):
        z = jnp.tanh(y) * 2.0 + 1.0
    return z, y


def test_scope_table_maps_a_plain_instruction_and_a_fusion_to_their_scopes():
    fn = jax.jit(_two_scopes)
    seam = "test.scope_seam"
    assert _kept(seam) == []
    try:
        profile.dispatch(seam, fn, jnp.ones((64, 32)), jnp.ones((32, 16)))
        (_seam, ops), = _kept(seam)
        # the very map again, not a second parse of the text
        assert _kept(seam)[0][1] is ops
        text, = profile.compiled_texts(seam)
        plain = [n for n, (_sh, op) in ops.items()
                 if op.endswith("tree.L4/route/dot_general")]
        fused = [n for n, (_sh, op) in ops.items()
                 if "/nn.fwd/" in op and re.search(
                     r"%%%s = \S+ fusion\(" % re.escape(n), text)]
        assert plain and fused, sorted(ops.items())
        assert ops[plain[0]][0] == "f32[64,16]"  # the layout is cut
        assert scopes.scope_of(ops[plain[0]][1]) == "tree.L4/route"
        assert scopes.scope_of(ops[fused[0]][1]) == "nn.fwd"
        assert _kept("test.another_seam") == []
    finally:
        profile.release_fn(fn)
    assert _kept(seam) == []


def test_scope_table_is_empty_with_the_profiler_off():
    fn = jax.jit(_two_scopes)
    environment.set_property("shifu.profile.mode", "off")
    try:
        profile.dispatch("test.scope_off", fn, jnp.ones((8, 4)),
                         jnp.ones((4, 2)))
        assert _kept("test.scope_off") == []
    finally:
        environment.set_property("shifu.profile.mode", "")
        profile.release_fn(fn)


def test_instruction_scopes_reads_tuples_and_instructions_over_lines():
    text = "\n".join([
        "ENTRY %main.1 (p: f32[8]) -> f32[8] {",
        "  %p.1 = f32[8]{0:T(1024)} parameter(0), metadata={op_name=\"p\"}",
        "  %tree_hist.2 = (f32[1,512]{1,0:T(1,128)}, /*index=1*/f32[1,3]"
        "{1,0}) custom-call(%p.1), frontend_attributes={kernel_metadata={",
        "\"L\":\"2\",",
        "\"kernel\":\"tree_hist\"",
        "}}, metadata={op_name=\"jit(f)/tree.L4/hist/tree_hist/pallas_call\""
        " stack_frame_id=4}, backend_config={\"x\": \"metadata={op_name=\"}",
        "  ROOT %copy.3 = f32[8]{0} copy(%p.1)",
        "}"])
    assert profile._instruction_scopes(text) == {
        "p.1": ("f32[8]", "p"),
        "tree_hist.2": ("(f32[1,512], f32[1,3])",
                        "jit(f)/tree.L4/hist/tree_hist/pallas_call"),
        "copy.3": ("f32[8]", "")}


def test_whole_tree_program_carries_a_scope_a_level_and_phase(monkeypatch):
    """The whole-tree program at toy size with the kernels interpreted, run
    through its own seam: the table of the executable that seam kept has
    instructions under a level's `hist` and `route` and under `tree.leaf`,
    and the code operand's under `tree.codes`, which that program writes
    inside a level's `hist`; the `tree.codes8` program's are under a bare
    `tree.codes`."""
    n, F, slots, D = 300, 3, 5, 2
    lay = tt.make_layout([slots] * F, [False] * F)
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (True, True, True))
    key_before = set(tt._PROGRAMS)
    # blocks of 128 rows, so that 300 rows need the code operand padded
    environment.set_property("shifu.pallas.blk", "128")
    try:
        prog = tt._get_tree_program(D, lay, "variance", 1, 0.0,
                                    sub_levels=(False, True))
        codes8_prog = tt._get_codes8_program(lay)
    finally:
        environment.set_property("shifu.pallas.blk", "")
        for k in set(tt._PROGRAMS) - key_before:
            del tt._PROGRAMS[k]  # built under a steered state: never reuse
    codes = jnp.arange(n * F, dtype=jnp.int32).reshape(n, F) % slots
    try:
        codes8 = codes8_prog(codes)
        prog(codes, codes8, (jnp.arange(n) % 2).astype(jnp.float32),
             jnp.ones(n), jnp.ones(lay.T, bool))
        (_seam, ops), = _kept("tree.pallas_fused")
        found = {scopes.scope_of(op) for _sh, op in ops.values()}
        assert {"tree.L1/hist", "tree.L1/route", "tree.L2/hist",
                "tree.leaf", "tree.codes"} <= found, found
        nested = [op for _sh, op in ops.values()
                  if scopes.scope_of(op) == "tree.codes"]
        assert all(re.search(r"/tree\.L\d+/hist/(?:.*/)?tree\.codes/", op)
                   for op in nested), nested
        (_seam8, ops8), = _kept("tree.codes8")
        assert "tree.codes" in {scopes.scope_of(op)
                                for _sh, op in ops8.values()}
    finally:
        profile.release_fn(prog.fn)
        profile.release_fn(codes8_prog.fn)


# ---- the benchmark's side: the cut and the join ----

@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/tree.L4/route/dot_general", "tree.L4/route"),
    ("jit(f)/tree.L1/hist/tree.codes/pad", "tree.codes"),
    ("jit(f)/tree.L128/hist/tree_hist/tree.codes/convert_element_type",
     "tree.codes"),
    ("jit(f)/tree.L1/hist/pad", "tree.L1/hist"),
    ("jit(body)/shard_map/tree.L8/psum/psum", "tree.L8/psum"),
    ("jit(f)/tree.leaf/while/body/add", "tree.leaf"),
    ("jit(f)/tree.leaf/psum/psum", "tree.leaf/psum"),
    ("jit(build)/tree.codes/clamp", "tree.codes"),
    ("jit(t)/while/body/nn.bwd/jvp(nn.fwd)/dot_general",
     "nn.bwd/jvp(nn.fwd)"),
    ("jit(t)/while/body/nn.bwd/transpose(jvp(nn.fwd))/mul",
     "nn.bwd/transpose(jvp(nn.fwd))"),
    ("jit(t)/nn.bwd/neg", "nn.bwd"),
    ("jit(t)/nn.update/jit(_where)/select_n", "nn.update"),
    ("jit(t)/while/body/jvp(wdl.embed)/gather", "jvp(wdl.embed)"),
    ("jit(t)/transpose(jvp(wdl.embed))/scatter-add",
     "transpose(jvp(wdl.embed))"),
    ("jit(t)/wdl.loss/reduce_sum", "wdl.loss"),
    ("gather", None), ("codes8", None), ("", None)])
def test_an_op_name_is_cut_to_the_programs_own_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_trace_by_scope_cuts_a_kept_files_tf_op_as_the_join_cuts_an_op_name(
        tmp_path):
    """The check of the join reads the same grouping off a kept file: a
    synthetic xplane with one event a `tf_op`, through
    `scripts/trace_by_scope.py` and through `scope_of`."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    import importlib.util
    import os

    from benchmarks.lib import xplane

    tf_ops = ["jit(f)/tree.L4/route/select_n",
              "jit(f)/tree.L1/hist/tree.codes/pad",
              "jit(f)/tree.L1/hist/mul", "jit(build)/tree.codes/clamp",
              "jit(f)/tree.leaf/psum/psum", "codes8"]
    space = xplane_pb2.XSpace()
    dev = space.planes.add(name=xplane.DEVICE_PREFIX + "0")
    dev.stat_metadata[1].name = "tf_op"
    line = dev.lines.add(name=xplane.OPS_LINE, timestamp_ns=0)
    for k, tf_op in enumerate(tf_ops, 1):
        dev.event_metadata[k].name = "%%fusion.%d = f32[8]{0} fusion()" % k
        dev.event_metadata[k].stats.add(metadata_id=1, str_value=tf_op)
        # event k takes 2**k us of its own, one after the other
        line.events.add(metadata_id=k, offset_ps=(2 ** k) * 10 ** 6,
                        duration_ps=(2 ** k) * 10 ** 6)
    host = space.planes.add(name=xplane.HOST_PLANE)
    host.event_metadata[1].name = xplane.CALL_SPAN
    host.lines.add(timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=0, duration_ps=10 ** 9)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    mod_spec = importlib.util.spec_from_file_location(
        "trace_by_scope", os.path.join(spec.ROOT, "scripts",
                                       "trace_by_scope.py"))
    script = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(script)
    got, = script.by_scope(str(path))
    want = {}
    for k, tf_op in enumerate(tf_ops, 1):
        key = scopes.scope_of(tf_op) or "-"
        want[key] = want.get(key, 0.0) + (2 ** k) * 1e-6
    assert got["by_scope"] == pytest.approx(want)
    assert got["by_scope"]["tree.codes"] == pytest.approx((4 + 16) * 1e-6)
    assert got["by_phase"]["tree.codes"] == pytest.approx((4 + 16) * 1e-6)


def test_bare_strips_jaxs_wrappers():
    assert scopes.bare("transpose(jvp(wdl.embed))") == "wdl.embed"
    assert scopes.bare("jvp(wdl.deep)") == "wdl.deep"
    assert scopes.bare("nn.bwd/jvp(nn.fwd)") == "nn.bwd"
    assert scopes.bare(scopes.UNSCOPED) == scopes.UNSCOPED


def test_by_scope_matches_leaves_unscoped_and_leaves_unmatched(monkeypatch):
    big = {"fusion.1": ("f32[600]", "jit(f)/tree.L4/route/select_n"),
           "copy.2": ("s8[600,28]", "codes8"),
           "fusion.7": ("f32[600]", "jit(f)/tree.L2/hist/mul"),
           "fusion.8": ("s32[600]", "jit(f)/tree.L2/scan/add"),
           "fusion.9": ("f32[600]", "jit(f)/jvp(wdl.embed)/gather"),
           "fusion.10": ("(f32[600], s32[])",
                         "jit(f)/transpose(jvp(wdl.embed))/scatter-add")}
    small = {"fusion.7": ("f32[2]", "jit(e)/reduce_sum"),
             "fusion.8": ("s32[600]", "jit(e)/tree.leaf/add"),
             "fusion.1": ("f32[600]", "jit(e)/tree.L4/route/select_n")}
    _table(monkeypatch, ("tree.pallas_fused", big), ("tree.errors", small))
    ops = {
        _event("fusion.1"): 1.0,  # both executables, one scope: matched
        _event("copy.2", "s8[600,28]{1,0:T(8,128)(4,1)}",
               "copy(s8[600,28]{0,1} %codes8.1)"): 2.0,  # unscoped
        _event("multiply.3"): 4.0,  # no such instruction: unmatched
        _event("fusion.7", "f32[600]{0:T(1024)}"): 8.0,  # settled by shape
        _event("fusion.7", "f32[2]{0}"): 16.0,  # the other one: unscoped
        _event("fusion.8", "s32[600]{0}"): 32.0,  # shapes agree: unmatched
        _event("fusion.1", "f32[9]{0}"): 64.0,  # a shape nobody has
        _event("fusion.9"): 128.0,
        _event("fusion.10", "(f32[600]{0}, /*index=1*/s32[]{:T(128)})",
               "fusion(f32[600]{0} %p.1)"): 256.0,
        "no instruction at its head": 512.0,
    }
    assert scopes.by_scope(_ctx(ops)) == {
        "tree.L4/route": 1.0, "tree.L2/hist": 8.0,
        "jvp(wdl.embed)": 128.0, "transpose(jvp(wdl.embed))": 256.0,
        scopes.UNSCOPED: 2.0 + 16.0,
        scopes.UNMATCHED: 4.0 + 32.0 + 64.0 + 512.0}


def test_by_scope_is_nothing_without_a_trace_a_table_or_the_accessor(
        monkeypatch):
    ops = {_event("fusion.1"): 1.0}
    _table(monkeypatch, ("s", {"fusion.1": ("f32[600]", "tree.leaf/add")}))
    assert scopes.by_scope(_ctx()) is None  # untraced
    assert scopes.by_scope(_ctx(ops)) == {"tree.leaf": 1.0}
    _table(monkeypatch)  # the program kept nothing
    assert scopes.by_scope(_ctx(ops)) is None
    monkeypatch.delattr(profile, "scope_table")  # a parent commit
    assert scopes.by_scope(_ctx(ops)) is None


# ---- the ten readers ----

KERNEL_EVENT = (
    '%tree_hist.5 = (f32[1,512]{1,0}) custom-call(s32[600,28]{1,0} %p), '
    'custom_call_target="tpu_custom_call", frontend_attributes='
    '{kernel_metadata={"L":"4","kernel":"tree_hist"}}')
TREE_TABLE = ("tree.pallas_fused", {
    "tree_hist.5": ("(f32[1,512])", "jit(f)/tree.L4/hist/tree_hist/pallas_call"),
    "fusion.1": ("f32[600]", "jit(f)/tree.L4/route/select_n"),
    "fusion.2": ("f32[600]", "jit(f)/tree.L128/route/select_n"),
    "fusion.3": ("f32[600]", "jit(f)/tree.L4/scan/cumsum"),
    "fusion.4": ("f32[600]", "jit(f)/tree.L4/derive/sub"),
    "fusion.5": ("f32[600]", "jit(f)/tree.leaf/while/body/add"),
    "all-reduce.6": ("f32[600]", "jit(f)/tree.leaf/psum/psum"),
    "fusion.7": ("f32[600]", "jit(f)/tree.L1/hist/tree.codes/pad"),
    "fusion.8": ("f32[600]", "jit(f)/tree.L2/hist/mul"),
    "fusion.9": ("f32[600]", "jit(build)/tree.codes/clamp"),
    "copy.10": ("f32[600]", "codes8"),
    "all-reduce.11": ("f32[600]", "jit(f)/tree.L4/psum/psum")})
TREE_OPS = {
    KERNEL_EVENT: 0.5, _event("fusion.1"): 0.001, _event("fusion.2"): 0.002,
    _event("fusion.3"): 0.004, _event("fusion.4"): 0.008,
    _event("fusion.5"): 0.016, _event("all-reduce.6"): 0.032,
    _event("fusion.7"): 0.064, _event("fusion.8"): 0.128,
    _event("fusion.9"): 0.256, _event("copy.10"): 0.512,
    _event("subtract.12"): 1.024, _event("all-reduce.11"): 2.048}
TREE_READERS = {  # seconds of the window in each, over four trees
    "tree_route_ms_per_tree": 0.001 + 0.002,
    "tree_scan_ms_per_tree": 0.004 + 0.008,
    "tree_leaf_ms_per_tree": 0.016,
    "tree_hist_xla_ms_per_tree": 0.128,
    "tree_codes_ms_per_tree": 0.064 + 0.256,  # inside a `hist`, and bare
    "tree_unscoped_ms_per_tree": 0.512 + 1.024}


@pytest.mark.parametrize("name", sorted(TREE_READERS))
def test_a_tree_reader_is_its_scopes_sum_over_the_windows_trees(
        monkeypatch, name):
    read = _reader(name).read
    _table(monkeypatch, TREE_TABLE)
    drv = _Driver(trees=4)
    assert read(_ctx(TREE_OPS, driver=drv)) == pytest.approx(
        1e3 * TREE_READERS[name] / 4)
    assert read(_ctx(driver=drv)) is None  # untraced
    assert read(_ctx(TREE_OPS)) is None  # no tree ended in the window
    _table(monkeypatch)
    assert read(_ctx(TREE_OPS, driver=drv)) is None  # nothing kept
    monkeypatch.delattr(profile, "scope_table")
    assert read(_ctx(TREE_OPS, driver=drv)) is None  # a parent commit


def test_the_tree_readers_and_the_psums_add_up_to_tree_xla_ms_per_tree(
        monkeypatch):
    _table(monkeypatch, TREE_TABLE)
    ctx = _ctx(TREE_OPS, driver=_Driver(trees=4))
    parts = sum(_reader(name).read(ctx) for name in TREE_READERS)
    psums = 1e3 * (0.032 + 2.048) / 4
    assert psums == pytest.approx(1e3 * sum(
        s for k, s in scopes.by_scope(ctx).items()
        if k.endswith("/psum")) / 4)
    assert parts + psums == pytest.approx(
        _reader("tree_xla_ms_per_tree").read(ctx))
    assert _reader("tree_kernel_ms_per_tree").read(ctx) == pytest.approx(
        125.0)
    # a scope that holds nothing reads 0.0, not nothing
    _table(monkeypatch, ("s", {"fusion.1": TREE_TABLE[1]["fusion.1"]}))
    assert _reader("tree_codes_ms_per_tree").read(
        _ctx({_event("fusion.1"): 1.0}, driver=_Driver(trees=4))) == 0.0


WDL_TABLE = ("wdl.train_program", {
    "fusion.1": ("f32[600]", "jit(t)/while/body/jvp(wdl.embed)/gather"),
    "copy.2": ("f32[600]", "jit(t)/while/body/jvp(wdl.embed)/gather"),
    "fusion.3": ("f32[600]", "jit(t)/while/body/jvp(wdl.embed)/concatenate"),
    "fusion.4": ("f32[600]",
                 "jit(t)/while/body/transpose(jvp(wdl.embed))/scatter-add"),
    "copy.5": ("f32[600]", "jit(t)/while/body/transpose(jvp(wdl.embed))/split"),
    "fusion.6": ("f32[600]", "jit(t)/while/body/jvp(wdl.deep)/dot_general"),
    "fusion.7": ("f32[600]",
                 "jit(t)/while/body/transpose(jvp(wdl.deep))/dot_general"),
    "fusion.8": ("f32[600]", "jit(t)/while/body/wdl.loss/wdl.deep/max"),
    "fusion.9": ("f32[600]", "jit(t)/while/body/wdl.update/mul")})
WDL_OPS = {_event("fusion.%d" % k if k not in (2, 5) else "copy.%d" % k):
           2.0 ** -k for k in range(1, 10)}


def test_wdl_relayout_is_the_embed_scopes_less_the_lookups(monkeypatch):
    read = _reader("wdl_relayout_ms_per_epoch").read
    _table(monkeypatch, WDL_TABLE)
    drv = _Driver(epochs=2, holders={"fusion.1", "fusion.4"})
    want = 1e3 * (2.0 ** -2 + 2.0 ** -3 + 2.0 ** -5) / 4
    assert read(_ctx(WDL_OPS, driver=drv)) == pytest.approx(want)
    # with the lookups' own reader: all that the two embed scopes hold
    both = read(_ctx(WDL_OPS, driver=drv)) + _reader(
        "wdl_lookup_ms_per_epoch").read(_ctx(WDL_OPS, driver=drv))
    embed = sum(s for k, s in scopes.by_scope(_ctx(WDL_OPS)).items()
                if scopes.bare(k) == "wdl.embed")
    assert both == pytest.approx(1e3 * embed / 4)
    assert read(_ctx(driver=drv)) is None
    assert read(_ctx(WDL_OPS, driver=_Driver(epochs=2))) is None  # no names
    assert read(_ctx(WDL_OPS, driver=_Driver(epochs=2, holders=()))) is None
    assert read(_ctx(WDL_OPS, driver=_Driver(holders={"fusion.1"}))) is None
    _table(monkeypatch)
    assert read(_ctx(WDL_OPS, driver=drv)) is None
    monkeypatch.delattr(profile, "scope_table")
    assert read(_ctx(WDL_OPS, driver=drv)) is None


def test_wdl_deep_is_the_tower_forward_and_transposed(monkeypatch):
    read = _reader("wdl_deep_ms_per_epoch").read
    _table(monkeypatch, WDL_TABLE)
    drv = _Driver(epochs=2)
    assert read(_ctx(WDL_OPS, driver=drv)) == pytest.approx(
        1e3 * (2.0 ** -6 + 2.0 ** -7) / 4)
    assert read(_ctx(driver=drv)) is None
    assert read(_ctx(WDL_OPS)) is None  # a driver without epochs
    _table(monkeypatch)
    assert read(_ctx(WDL_OPS, driver=drv)) is None
    monkeypatch.delattr(profile, "scope_table")
    assert read(_ctx(WDL_OPS, driver=drv)) is None


NN_TABLE = ("nn.train_program", {
    "fusion.1": ("f32[600]", "jit(t)/while/body/nn.bwd/jvp(nn.fwd)/tanh"),
    "fusion.2": ("f32[600]", "jit(t)/while/body/nn.fwd/tanh"),
    "fusion.3": ("f32[600]",
                 "jit(t)/while/body/nn.bwd/transpose(jvp(nn.fwd))/mul"),
    "fusion.4": ("f32[600]", "jit(t)/while/body/nn.bwd/jvp()/slice"),
    "fusion.5": ("f32[600]", "jit(t)/while/body/nn.bwd/neg"),
    "fusion.6": ("f32[600]", "jit(t)/while/body/nn.valid/reduce_sum"),
    "fusion.7": ("f32[600]", "jit(t)/while/body/nn.update/sign")})
NN_OPS = {_event("fusion.%d" % k): 2.0 ** -k for k in range(1, 8)}


@pytest.mark.parametrize("name,seconds", [
    ("nn_fwd_ms_per_epoch", 2.0 ** -1 + 2.0 ** -2),
    ("nn_bwd_ms_per_epoch", 2.0 ** -3 + 2.0 ** -4 + 2.0 ** -5)])
def test_an_nn_reader_is_its_side_of_nn_bwd_over_the_epochs(
        monkeypatch, name, seconds):
    read = _reader(name).read
    _table(monkeypatch, NN_TABLE)
    drv = _Driver(epochs=20)
    assert read(_ctx(NN_OPS, driver=drv)) == pytest.approx(
        1e3 * seconds / 40)
    assert read(_ctx(driver=drv)) is None
    assert read(_ctx(NN_OPS)) is None
    _table(monkeypatch)
    assert read(_ctx(NN_OPS, driver=drv)) is None
    monkeypatch.delattr(profile, "scope_table")
    assert read(_ctx(NN_OPS, driver=drv)) is None


# ---- the slowest call, by span ----

@pytest.fixture
def ring():
    obs.reset()

    def put(name, start, seconds, parent="", **args):
        obs.tracer().record(name, T0 + start, T0 + start + seconds, parent,
                            args)
    yield put
    obs.reset()


def _calls(ring, lengths, waits, prologue=0.1):
    """Back-to-back `train.trees.call` spans of two trees each: `lengths`
    seconds long, of which `waits` are each tree's wait."""
    at = 0.0
    for c, (length, wait) in enumerate(zip(lengths, waits), 1):
        name = "train.trees.call"
        ring("train.trees.prologue", at, prologue, name, call=c)
        for k in range(2):
            ring("train.tree.wait", at + prologue + k * wait, wait,
                 name + "/train.tree", call=c, k=k)
        ring(name, at, length, call=c)
        at += length
    return [(T0, T0 + at)]


def test_call_excess_reads_a_few_ms_in_a_quiet_window(ring):
    calls = _calls(ring, [4.000, 4.002, 4.006, 4.001],
                   [1.900, 1.901, 1.902, 1.9005])
    ctx = _ctx(calls=calls)
    assert _reader("gbt_call_excess_ms").read(ctx) == pytest.approx(
        1e3 * (4.006 - 4.0015))
    assert _reader("gbt_call_excess_wait_ms").read(ctx) == pytest.approx(
        1e3 * 2 * (1.902 - 1.90075))


def test_call_excess_finds_a_stall_inside_the_wait(ring):
    calls = _calls(ring, [4.0, 6.5, 4.0], [1.9, 3.15, 1.9])
    ctx = _ctx(calls=calls)
    assert _reader("gbt_call_excess_ms").read(ctx) == pytest.approx(2500.0)
    assert _reader("gbt_call_excess_wait_ms").read(ctx) == pytest.approx(
        2500.0)


def test_call_excess_finds_a_stall_in_the_prologue(ring):
    ring("train.trees.call", -5.0, 4.0, call=0)  # the warm-up: left out
    ring("train.tree.wait", -4.0, 3.5, "train.trees.call", call=0, k=0)
    calls = _calls(ring, [4.0, 4.0, 6.5], [1.9, 1.9, 1.9])
    ctx = _ctx(calls=calls)
    assert _reader("gbt_call_excess_ms").read(ctx) == pytest.approx(2500.0)
    assert _reader("gbt_call_excess_wait_ms").read(ctx) == pytest.approx(0.0)


@pytest.mark.parametrize("name", ["gbt_call_excess_ms",
                                  "gbt_call_excess_wait_ms"])
def test_call_excess_needs_three_calls_in_the_window(ring, name):
    read = _reader(name).read
    assert read(_ctx()) is None  # a program without the spans
    calls = _calls(ring, [4.0, 6.5], [1.9, 1.9])
    assert read(_ctx(calls=calls)) is None


# ---- BENCHMARK.json ----

def test_every_new_metric_is_listed_with_a_reader_and_its_cells():
    import json
    import os

    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    # not the forest cell: `test_rf_readers.py` holds its list to the
    # fifteen readers of PR 34, and that file is the benchmark's
    tree = ["higgs_gbt.train_levelwise", "higgs_gbt_full.train_mesh4"]
    cells = dict.fromkeys(TREE_READERS, tree)
    cells.update(gbt_call_excess_ms=tree, gbt_call_excess_wait_ms=tree,
                 wdl_relayout_ms_per_epoch=["criteo_wdl.train_fullbatch"],
                 wdl_deep_ms_per_epoch=["criteo_wdl.train_fullbatch"],
                 nn_fwd_ms_per_epoch=["higgs_nn.train_fullbatch"],
                 nn_bwd_ms_per_epoch=["higgs_nn.train_fullbatch"])
    for name, want in cells.items():
        m = per_layer[name]
        # a cell more (the forest's) is a line of data, not of this test
        assert set(want) <= set(m["workloads"]), name
        assert (m["unit"], m["better"]) == ("ms", "lower"), name
        assert m["source"] == ("program_span" if name.startswith("gbt_call")
                               else "device_trace"), name
        assert callable(_reader(name).read)
