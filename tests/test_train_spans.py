"""The trainers' own measurement: the spans and counts `train_trees`,
`train_nn` and `train_wdl` leave in the tracer's ring, and the names their compiled programs
carry (a named kernel, one scope a level and a phase).

The span tables are the ones in docs/OBSERVABILITY.md ("Span tracing"); the
benchmark's per-layer readers (benchmarks/layer_metrics/) read exactly these
names.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shifu_tpu import obs  # noqa: E402
from shifu_tpu.train import nn_trainer as nt  # noqa: E402
from shifu_tpu.train import tree_trainer as tt  # noqa: E402
from shifu_tpu.train import wdl_trainer as wt  # noqa: E402
from shifu_tpu.utils import environment  # noqa: E402

N, F, SLOTS = 2000, 6, 9


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(5)
    codes = rng.integers(0, SLOTS - 1, (N, F)).astype(np.int32)
    y = ((codes[:, 0] > 3) ^ (codes[:, 1] > 5)).astype(np.float32)
    x = rng.normal(size=(N, F)).astype(np.float32)
    return codes, x, y, np.ones(N, np.float32)


def _grow(rows, progress_cb=None, trees=3, **cfg):
    codes, _x, y, w = rows
    conf = tt.TreeTrainConfig(algorithm="GBT", tree_num=trees, max_depth=3,
                              valid_set_rate=0.2, seed=3, **cfg)
    return tt.train_trees(codes, y, w, [SLOTS] * F, [False] * F,
                          ["f%d" % i for i in range(F)], conf,
                          progress_cb=progress_cb)


def _train_events():
    """The ring's `train.` events as (name, parent, k), in the order they
    ended, and the events themselves by name."""
    evs = [e for e in obs.tracer().events if e["name"].startswith("train.")]
    return ([(e["name"], e["args"].get("parent", ""), e["args"].get("k"))
             for e in evs], evs)


CALL, TREE = "train.trees.call", "train.trees.call/train.tree"


def _tree_spans(k):
    """What one synced tree leaves, in the order the spans end."""
    return [("train.tree.wait", TREE + "/train.tree.assemble", k),
            ("train.tree.assemble", TREE, k),
            ("train.tree.wait", TREE, k),
            ("train.tree.progress_cb", TREE, k),
            ("train.tree", CALL, k)]


def test_train_trees_with_progress_cb_leaves_the_tables_spans(rows):
    _grow(rows, progress_cb=lambda *a: None)  # compile outside the count
    obs.reset()
    seen = []
    res = _grow(rows, progress_cb=lambda k, t, v: seen.append(k))
    assert seen == [1, 2, 3] and len(res.spec.trees) == 3
    got, evs = _train_events()
    want = [("train.trees.prologue", CALL, None)]
    for k in range(3):
        want += _tree_spans(k)
    want.append((CALL, "", None))
    assert got == want
    assert {e["args"]["call"] for e in evs} == {1}
    call = evs[-1]
    assert call["args"] == {"call": 1, "rows": N, "trees": 3, "depth": 3}
    reg = obs.registry()
    assert reg.counter("train.trees").value == 3
    assert reg.counter("train.calls", engine="tree").value == 1
    # the divisor tree.hist.* lacked: depth 3 with subtraction builds
    # 1 + 1 + 2 histograms a tree
    assert reg.counter("tree.hist.built").value == 3 * 4
    # every span lies inside its call
    for e in evs:
        assert call["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= call["ts"] + call["dur"] + 1.0


def test_train_trees_without_a_consumer_syncs_once_at_the_end(rows):
    _grow(rows)
    obs.reset()
    _grow(rows)
    _grow(rows)
    got, evs = _train_events()
    one_call = ([("train.trees.prologue", CALL, None)]
                + [("train.tree", CALL, k) for k in range(3)]
                # trees and errors ride one round-trip after the loop
                + [("train.tree.wait", CALL + "/train.tree.assemble", 2),
                   ("train.tree.assemble", CALL, 2),
                   (CALL, "", None)])
    assert got == one_call + one_call
    assert [e["args"]["call"] for e in evs] == [1] * 7 + [2] * 7
    assert obs.registry().counter("train.trees").value == 6
    assert obs.registry().counter("train.calls", engine="tree").value == 2


def test_every_blocking_pull_lands_in_wait_not_in_host_time(rows,
                                                            monkeypatch):
    """Plant a slow `device_get`: the time must show in `train.tree.wait`,
    and the host's share (call less wait less progress_cb, what
    `gbt_host_ms_per_tree` reads) must not grow by it."""
    _grow(rows, progress_cb=lambda *a: None)
    real = jax.device_get
    slow = 0.05

    def slow_get(x):
        time.sleep(slow)
        return real(x)

    def host_and_wait():
        _got, evs = _train_events()
        dur = lambda name: sum(e["dur"] for e in evs  # noqa: E731
                               if e["name"] == name) * 1e-6
        wait = dur("train.tree.wait")
        return (dur(CALL) - wait - dur("train.tree.progress_cb")), wait

    obs.reset()
    _grow(rows, progress_cb=lambda *a: None)
    host0, wait0 = host_and_wait()
    monkeypatch.setattr(jax, "device_get", slow_get)
    obs.reset()
    _grow(rows, progress_cb=lambda *a: None)
    host1, wait1 = host_and_wait()
    assert wait1 - wait0 >= 3 * slow * 0.9  # one pull a tree
    assert host1 - host0 < slow  # none of the 0.15 s reads as host time
    # the deferred path's one pull, after the loop, too
    obs.reset()
    _grow(rows)
    _host, wait2 = host_and_wait()
    assert wait2 >= slow * 0.9


def test_progress_cb_time_is_the_callers(rows):
    _grow(rows, progress_cb=lambda *a: None)
    obs.reset()
    _grow(rows, progress_cb=lambda *a: time.sleep(0.02))
    _got, evs = _train_events()
    cb = [e["dur"] * 1e-6 for e in evs
          if e["name"] == "train.tree.progress_cb"]
    assert len(cb) == 3 and min(cb) >= 0.018


def _forest(rows, progress_cb=None, algorithm="RF", trees=3, **cfg):
    codes, _x, y, w = rows
    conf = tt.TreeTrainConfig(algorithm=algorithm, tree_num=trees,
                              max_depth=3, valid_set_rate=0.2, seed=3, **cfg)
    return tt.train_trees(codes, y, w, [SLOTS] * F, [False] * F,
                          ["f%d" % i for i in range(F)], conf,
                          progress_cb=progress_cb)


@pytest.mark.parametrize("algorithm,trees", [("RF", 3), ("RF", 1),
                                             ("GBT", 3)])
def test_a_forests_bag_draws_have_a_span_of_their_own(rows, algorithm,
                                                      trees):
    """`train.trees.bag`: one tree's bag and column draws and the bag's put,
    with what crossed to the device (a uint16 a row). The first tree's is in
    the prologue; tree k + 1's is inside tree k's `train.tree`, after the
    tree is dispatched, so the host draws while the device grows. Only a
    forest enters it: a GBT call's spans are what they were."""
    obs.reset()
    _forest(rows, algorithm=algorithm, trees=trees,
            feature_subset_strategy="TWOTHIRDS")
    got, evs = _train_events()
    bags = [e for e in evs if e["name"] == "train.trees.bag"]
    if algorithm == "GBT":
        assert not bags and got[0] == ("train.trees.prologue", CALL, None)
        return
    assert [e["args"] for e in bags] == [
        {"call": 1, "k": k, "rows": N, "bytes": 2 * N,
         "parent": (CALL + "/train.trees.prologue" if k == 0
                    else TREE)} for k in range(trees)]
    # tree k + 1's bag ends inside tree k's span, before the tree's end
    names = [g[0] for g in got]
    assert names[:2] == ["train.trees.bag", "train.trees.prologue"]
    for k in range(1, trees):
        assert got.index(("train.trees.bag", TREE, k)) \
            < got.index(("train.tree", CALL, k - 1))
    # the other spans are a GBT call's, in a GBT call's order
    rest = [g for g in got if g[0] != "train.trees.bag"]
    assert rest[0] == ("train.trees.prologue", CALL, None)
    assert [g for g in rest if g[0] == "train.tree"] == [
        ("train.tree", CALL, k) for k in range(trees)]


def test_the_next_bag_is_drawn_before_the_loop_waits_for_the_tree(rows):
    """With a consumer the loop blocks a tree (`train.tree.wait`): the next
    tree's bag must be drawn before the first of them, or the device idles
    through the draw."""
    _forest(rows, progress_cb=lambda *a: None)
    obs.reset()
    _forest(rows, progress_cb=lambda *a: None)
    _got, evs = _train_events()
    for k in range(2):
        (bag,) = [e for e in evs if e["name"] == "train.trees.bag"
                  and e["args"]["k"] == k + 1]
        waits = [e for e in evs if e["name"] == "train.tree.wait"
                 and e["args"]["k"] == k]
        assert waits and all(bag["ts"] + bag["dur"] <= w["ts"] + 1.0
                             for w in waits)


def _bag_ks():
    return [e["args"]["k"] for e in obs.tracer().events
            if e["name"] == "train.trees.bag"]


def test_a_forest_that_stops_early_draws_one_bag_past_its_last_tree(rows):
    """Bags are drawn one tree ahead, so a stop after tree k has drawn tree
    k + 1's and no other; the forest is the first trees of the
    uninterrupted one."""
    obs.reset()
    res = _forest(rows, trees=40, early_stop_rounds=1,
                  bagging_sample_rate=0.05)
    grown = len(res.spec.trees)
    assert 2 <= grown < 40
    assert _bag_ks() == list(range(grown + 1))
    whole = _forest(rows, trees=grown, bagging_sample_rate=0.05)
    for a, b in zip(res.spec.trees, whole.spec.trees):
        assert np.array_equal(a.feature, b.feature)
        assert np.array_equal(a.leaf_value, b.leaf_value)


def test_a_callback_that_raises_leaves_no_bag_being_drawn(rows):
    """`progress_cb` raising at the second tree unwinds `train_trees` with
    the bags of trees 0, 1 and 2 drawn (one ahead) and nothing left behind
    that draws or puts another."""
    def cb(k, _t, _v):
        if k == 2:
            raise RuntimeError("stop here")

    obs.reset()
    with pytest.raises(RuntimeError, match="stop here"):
        _forest(rows, progress_cb=cb, trees=6)
    assert _bag_ks() == [0, 1, 2]
    time.sleep(0.2)
    assert _bag_ks() == [0, 1, 2]


def test_train_nn_leaves_the_tables_spans(rows):
    _codes, x, y, w = rows
    cfg = nt.NNTrainConfig(hidden_nodes=[8], activations=["tanh"],
                           num_epochs=2, seed=1)
    nt.train_nn(x, y, w, cfg)
    obs.reset()
    res = nt.train_nn(x, y, w, cfg)
    nt.train_nn(x, y, w, cfg, fetch_params=False)
    assert res.iterations == 2
    got, evs = _train_events()
    NNCALL = "train.nn.call"
    one = [("train.nn.prologue", NNCALL, None),
           ("train.nn.program", NNCALL, None),
           ("train.nn.pull", NNCALL, None),
           (NNCALL, "", None)]
    assert got == one + one
    assert [e["args"]["call"] for e in evs] == [1] * 4 + [2] * 4
    assert evs[3]["args"] == {"call": 1, "rows": N, "epochs": 2}
    assert evs[1]["args"]["limit"] == 2
    n_flat = F * 8 + 8 + 8 + 1
    # two f32, one i32 and one f32 scalar; the parameters where asked
    assert evs[2]["args"]["bytes"] == 16 + 4 * n_flat
    assert evs[6]["args"]["bytes"] == 16
    reg = obs.registry()
    assert reg.counter("train.calls", engine="nn").value == 2
    assert reg.counter("train.iterations").value == 4


def test_checkpointed_train_nn_has_one_program_span_a_segment(rows,
                                                              tmp_path):
    _codes, x, y, w = rows
    cfg = nt.NNTrainConfig(hidden_nodes=[8], activations=["tanh"],
                           num_epochs=3, seed=1, checkpoint_every=2,
                           checkpoint_path=str(tmp_path / "ck.npy"))
    obs.reset()
    nt.train_nn(x, y, w, cfg)
    _got, evs = _train_events()
    assert [e["args"]["limit"] for e in evs
            if e["name"] == "train.nn.program"] == [2, 3]


WDL_VOCAB = [5, 9, 3]


def _wdl(rows, **cfg):
    codes, x, y, w = rows
    conf = wt.WDLTrainConfig(hidden=[8], activations=["relu"], embed_dim=2,
                             seed=1, **cfg)
    cat = np.minimum(codes[:, :3], np.array(WDL_VOCAB) - 1)
    return wt.train_wdl(x, cat, y, w, WDL_VOCAB, conf)


def test_train_wdl_leaves_the_tables_spans(rows):
    _wdl(rows, num_epochs=2)
    obs.reset()
    res = _wdl(rows, num_epochs=2)
    _wdl(rows, num_epochs=3)
    assert res.iterations == 2
    got, evs = _train_events()
    WCALL = "train.wdl.call"
    one = [("train.wdl.prologue", WCALL, None),
           ("train.wdl.program", WCALL, None),
           ("train.wdl.pull", WCALL, None),
           (WCALL, "", None)]
    assert got == one + one
    assert [e["args"]["call"] for e in evs] == [1] * 4 + [2] * 4
    assert evs[3]["args"] == {"call": 1, "rows": N, "fields": 3, "epochs": 2}
    assert evs[1]["args"]["limit"] == 2 and evs[5]["args"]["limit"] == 3
    n_flat = sum(WDL_VOCAB) * 3 + F + (F + 3 * 2) * 8 + 8 + 8 + 1 + 1
    # two f32, one i32 and one f32 scalar, and the chosen weights
    assert evs[2]["args"]["bytes"] == 16 + 4 * n_flat
    reg = obs.registry()
    assert reg.counter("train.calls", engine="wdl").value == 2
    assert reg.counter("train.calls", engine="nn").value == 0
    assert reg.counter("train.iterations").value == 5


@pytest.mark.parametrize("epochs", [1, 4])
def test_train_wdl_counts_its_epochs_and_sets_the_errors(rows, epochs):
    """As `train_nn` does: `train.iterations` counts the epochs run, the two
    gauges hold what the result says; no counter is kept from a formula
    (the lookups an epoch makes are read off the compiled program)."""
    _wdl(rows, num_epochs=epochs)
    obs.reset()
    res = _wdl(rows, num_epochs=epochs)
    reg = obs.registry()
    assert reg.counter("train.iterations").value == epochs
    assert reg.gauge("train.train_error").value == res.train_error
    assert reg.gauge("train.valid_error").value == res.valid_error
    _wdl(rows, num_epochs=epochs)
    assert reg.counter("train.iterations").value == 2 * epochs
    assert not [k for k in reg.snapshot()["counters"] if k.startswith("wdl.")]


def test_checkpointed_train_wdl_has_one_program_span_a_segment(rows,
                                                               tmp_path):
    obs.reset()
    _wdl(rows, num_epochs=3, checkpoint_every=2,
         checkpoint_path=str(tmp_path / "ck.npy"))
    _got, evs = _train_events()
    assert [e["args"]["limit"] for e in evs
            if e["name"] == "train.wdl.program"] == [2, 3]
    assert obs.registry().counter("train.iterations").value == 3


# ---- names inside the compiled programs ----

def _eqns(jaxpr, out):
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _eqns(inner, out)
    return out


@pytest.fixture
def pallas_on():
    environment.set_property("shifu.pallas.mode", "on")
    try:
        yield
    finally:
        environment.set_property("shifu.pallas.mode", "")


def _tree_program_eqns(D, sub_levels):
    from shifu_tpu.ops import hist_pallas as hp

    lay = tt.make_layout([SLOTS] * 5, [False] * 5)  # no test shares it
    before = set(tt._PROGRAMS)
    try:
        prog = tt._get_tree_program(D, lay, "variance", 1, 0.0,
                                    sub_levels=sub_levels, lowp=True)
    finally:
        for k in set(tt._PROGRAMS) - before:
            del tt._PROGRAMS[k]  # built under a mode this test set
    n = 256
    codes = jnp.zeros((n, 5), jnp.int32)
    args = (codes, jnp.zeros(n), jnp.ones(n), jnp.ones(lay.T, bool))
    if tt._pallas_state()[2]:
        args = (codes, hp.make_codes8_fn(lay)(codes)) + args[1:]
    return _eqns(jax.make_jaxpr(prog.fn)(*args).jaxpr, [])


def test_tree_program_names_its_kernel_and_scopes(pallas_on):
    eqns = _tree_program_eqns(3, (False, True, True, True))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert kernels
    for e in kernels:
        assert e.params["name"] == "tree_fused_level"
        assert e.params["metadata"]["kernel"] == "tree_fused_level"
        assert int(e.params["metadata"]["L"]) in (1, 2)
    # a level's kernel is built at the width of the smaller children
    by_scope = {(str(e.source_info.name_stack).split("/")[0],
                 e.params["metadata"]["L"]) for e in kernels}
    assert by_scope == {("tree.L1", "1"), ("tree.L2", "1"),
                        ("tree.L4", "2")}
    stacks = {str(e.source_info.name_stack) for e in eqns}
    want = {"tree.L%d/%s" % (L, ph) for L in (1, 2, 4)
            for ph in ("hist", "route")}
    want |= {"tree.L%d/%s" % (L, ph) for L in (2, 4)
             for ph in ("derive", "scan")}
    want.add("tree.leaf")
    assert want <= stacks
    # every level's routing sits in its route scope: selects summed over
    # a static axis, and no gather from a 2-D table (tests/test_route_rows.py
    # holds it to that at the GBT cell's shape)
    for L in (1, 2, 4):
        routed = [e for e in eqns if str(e.source_info.name_stack).startswith(
            "tree.L%d/route" % L)]
        assert [e for e in routed if e.primitive.name == "reduce_sum"]
        assert not [e for e in routed if e.primitive.name == "gather"
                    and e.invars[0].aval.ndim > 1]


def test_tree_program_scopes_without_the_kernel():
    """The XLA path (no Pallas: the CPU's default) carries the same scopes;
    without subtraction a level has no derive phase."""
    eqns = _tree_program_eqns(2, ())
    assert not [e for e in eqns if e.primitive.name == "pallas_call"]
    stacks = {str(e.source_info.name_stack) for e in eqns}
    for scope in ("tree.L1/hist", "tree.L1/scan", "tree.L1/route",
                  "tree.L2/hist", "tree.L2/scan", "tree.L2/route",
                  "tree.leaf"):
        assert any(s.startswith(scope) for s in stacks), scope
    assert not any("/derive" in s for s in stacks)


def test_hist_mode_kernel_is_named_tree_hist(pallas_on):
    from shifu_tpu.ops import hist_pallas as hp

    lay = tt.make_layout([SLOTS] * 5, [False] * 5)
    fn = hp.make_pallas_hist_fn(4, lay)
    n = 128
    jp = jax.make_jaxpr(fn)(jnp.zeros((n, 5), jnp.int32), jnp.zeros(n),
                            jnp.ones(n), jnp.zeros(n, jnp.int32),
                            jnp.ones(n, bool))
    kernels = [e for e in _eqns(jp.jaxpr, [])
               if e.primitive.name == "pallas_call"]
    assert kernels and all(
        e.params["name"] == "tree_hist"
        and dict(e.params["metadata"]) == {"kernel": "tree_hist", "L": "4"}
        for e in kernels)
    ann = obs.profiler().snapshot()["annotations"]["ops.hist_pallas"]
    assert ann["kernel"] == "tree_hist"  # manifest and trace agree


HIGGS = ([33] * 28, [False] * 28)


def test_tree_kernel_calls_counts_a_trees_mosaic_calls(pallas_on):
    """`tree.kernel.calls` over `train.trees`: chunks x built levels. A
    depth-6 forest at HIGGS's layout (28 x 33 slots: two chunks of 15 and
    13 features under the fused scan's cap) makes 2 x 6 = 12 calls a tree,
    and the traced program holds as many kernels."""
    slots, is_cat = HIGGS
    rng = np.random.default_rng(11)
    n = 600
    codes = rng.integers(0, 32, (n, len(slots))).astype(np.int32)
    y = (codes[:, 0] > 15).astype(np.float32)
    conf = tt.TreeTrainConfig(algorithm="GBT", tree_num=2, max_depth=6,
                              valid_set_rate=0.2, seed=3)
    obs.reset()
    res = tt.train_trees(codes, y, np.ones(n, np.float32), slots, is_cat,
                         ["f%d" % i for i in range(len(slots))], conf)
    assert len(res.spec.trees) == 2
    reg = obs.registry()
    assert reg.counter("train.trees").value == 2
    assert reg.counter("tree.hist.built").value == 2 * 32
    assert reg.counter("tree.kernel.calls").value == 2 * 12
    ann = obs.profiler().snapshot()["annotations"]["ops.hist_pallas"]
    assert (ann["chunks"], ann["paddedT"], ann["T"]) == (2, 1024, 924)


@pytest.mark.parametrize("slots,D,sub,meshed,want", [
    (HIGGS[0], 6, True, False, 2 * 6),   # the fused cell: 42 before PR 29
    (HIGGS[0], 6, True, True, 1 * 6),    # hist mode under a mesh: 24 before
    (HIGGS[0], 6, False, False, 2 * 6),
    # subtraction off at depth 8: levels 64 and 128 leave the fused scan
    (HIGGS[0], 8, False, False, 2 * 6 + 1 * 2),
    # subtraction on: level 64 is built by the fused kernel of level 32
    (HIGGS[0], 8, True, False, 2 * 7 + 1 * 1),
    ([33] * 20 + [65] * 10, 3, True, False, 3 * 3),
    # 255 bins (higgs_gbt_255): two 256-slot features a fused chunk, four
    # in hist mode: 7 fused levels x 14 + the level built at 64 nodes x 7
    ([256] * 28, 8, True, False, 14 * 7 + 7 * 1),
    ([256] * 28, 8, True, True, 7 * 8),
])
def test_tree_kernel_calls_from_static_shapes(pallas_on, slots, D, sub,
                                              meshed, want):
    from shifu_tpu.ops import hist_pallas as hp

    lay = tt.make_layout(slots, [False] * len(slots))
    sub_levels = tuple(sub and d >= 1 for d in range(D + 1))
    mesh = object() if meshed else None  # only asked whether it is there
    assert tt._tree_kernel_calls(D, lay, sub_levels, mesh) == want
    assert hp.kernel_calls(lay, fused=True) == len(
        hp._chunks(lay, hp._SCAN_W_CAP))
    assert hp.kernel_calls(lay, fused=False) == len(hp._chunks(lay))


def test_tree_kernel_calls_is_silent_with_the_kernel_off(rows):
    obs.reset()
    _grow(rows)  # the CPU's default: the XLA lowering
    assert "tree.kernel.calls" not in obs.registry().snapshot()["counters"]
    lay = tt.make_layout(*HIGGS)
    assert tt._tree_kernel_calls(6, lay, (False,) + (True,) * 6) == 0


@pytest.mark.parametrize("algorithm", ["GBT", "RF"])
def test_off_the_chip_a_forest_grows_through_the_rebuild_path(
        rows, monkeypatch, algorithm):
    """One CPU device, kernel off: the whole-tree program builds every
    level's histogram from the codes it is handed. No program holds a
    forest-wide one-hot and the tree program takes no fifth argument."""
    handed = []
    real = tt._get_tree_program

    def recording(*a, **kw):
        prog = real(*a, **kw)

        def call(*args):
            handed.append(len(args))
            return prog(*args)
        return call

    monkeypatch.setattr(tt, "_get_tree_program", recording)
    codes, _x, y, w = rows
    cfg = tt.TreeTrainConfig(algorithm=algorithm, tree_num=4, max_depth=4,
                             valid_set_rate=0.2, seed=3)
    obs.reset()
    res = tt.train_trees(codes, y, w, [SLOTS] * F, [False] * F,
                         ["f%d" % i for i in range(F)], cfg)
    assert len(res.spec.trees) == 4
    assert handed == [4] * 4  # codes, labels, weights, fot
    # the grower and the error pass, and no program that builds anything
    # once a forest
    assert set(obs.profiler().snapshot()["programs"]) == {
        "tree.whole_tree", "tree.errors"}
    assert not [k for k in tt._PROGRAMS if k[0] == "mbuild"]


def test_hist_program_counts_its_kernel_calls_at_each_dispatch(pallas_on):
    """The host-driven growers dispatch one hist program a level or a
    batch: each dispatch is the layout's hist-mode chunks."""
    lay = tt.make_layout([9] * 5 + [1500], [False] * 6)  # no test shares it
    before = set(tt._PROGRAMS)
    try:
        prog = tt._get_hist_program(2, lay)
    finally:
        for k in set(tt._PROGRAMS) - before:
            del tt._PROGRAMS[k]  # built under a mode this test set
    n = 64
    la = tt._device_layout(lay, np.ones(6, bool))
    args = (jnp.zeros((n, 6), jnp.int32), jnp.zeros(n), jnp.ones(n),
            jnp.zeros(n, jnp.int32), jnp.ones(n, bool), la.off, la.clip,
            la.seg_t, la.pos_t)
    obs.reset()
    h = prog(*args)
    prog(*args)
    assert h.shape == (3, 2, lay.T)
    # 45 + 1,500 columns at wmax 1,024: two chunks
    assert obs.registry().counter("tree.kernel.calls").value == 2 * 2
    assert obs.registry().counter("tree.kernel.chunks",
                                  mode="hist").value == 2 * 2
    # the program is traced once: a `tree.kernel.trace` a chunk, under no
    # trainer's span here
    assert obs.registry().counter("tree.kernel.traces").value == 2
    spans = [e for e in obs.tracer().events
             if e["name"] == "tree.kernel.trace"]
    assert [(e["args"]["kernel"], e["args"]["L"], e["args"]["chunk"])
            for e in spans] == [("tree_hist", 2, 0), ("tree_hist", 2, 1)]
    assert all("parent" not in e["args"] for e in spans)


def test_kernel_chunks_at_255_bins_are_14_fused_and_7_in_hist_mode(
        pallas_on):
    """`tree.kernel.chunks{mode=}` is the layout's chunks under each cap:
    256 slots fill two lanes' worth of columns each, so the fused scan's
    512-column cap puts 2 features in a chunk and wmax 1,024 puts 4."""
    from shifu_tpu.ops import hist_pallas as hp

    lay = tt.make_layout([256] * 28, [False] * 28)
    sub_levels = (False,) + (True,) * 8
    assert tt._tree_kernel_plan(8, lay, sub_levels) == (
        ("fused", 7, 14), ("hist", 1, 7))
    assert tt._tree_kernel_calls(8, lay, sub_levels) == 105
    assert tt._tree_kernel_plan(8, lay, sub_levels, object()) == (
        ("hist", 8, 7),)
    fused = hp._chunks(lay, hp._SCAN_W_CAP)
    assert [(c.f_lo, c.f_hi, c.w) for c in fused] == [
        (2 * i, 2 * i + 2, 512) for i in range(14)]
    assert [(c.f_lo, c.f_hi, c.w) for c in hp._chunks(lay)] == [
        (4 * i, 4 * i + 4, 1024) for i in range(7)]
    assert hp.code_dtype(lay) == np.int32
    # an int32 code block is one sublane tile of 8 features
    assert [hp._code_window(c, lay) for c in fused[:5]] == [
        (8, 0)] * 4 + [(8, 1)]
    obs.reset()
    tt._record_kernel_calls(tt._tree_kernel_plan(8, lay, sub_levels))
    tt._record_kernel_calls(tt._tree_kernel_plan(8, lay, sub_levels))
    counters = obs.registry().snapshot()["counters"]
    assert counters["tree.kernel.calls"] == 2 * 105
    assert counters['tree.kernel.chunks{mode="fused"}'] == 2 * 14
    assert counters['tree.kernel.chunks{mode="hist"}'] == 2 * 7
    tt._record_kernel_calls(())  # the kernel off: nothing is counted
    assert obs.registry().snapshot()["counters"] == counters


def test_every_kernel_call_of_a_traced_tree_leaves_a_trace_span(rows,
                                                                pallas_on):
    """`tree.kernel.trace`: one a `pallas_call` made while the whole-tree
    program is traced, with the kernel's name, level and chunk, under the
    trainer's span, and `tree.kernel.traces` counts them; a second
    `train_trees` call traces nothing and adds none."""
    obs.reset()
    lay = tt.make_layout([SLOTS] * F, [False] * F)
    before = set(tt._PROGRAMS)
    try:
        _grow(rows, trees=2)
        _grow(rows, trees=2)
    finally:
        for k in set(tt._PROGRAMS) - before:
            del tt._PROGRAMS[k]  # built under a mode this test set
    spans = [e for e in obs.tracer().events
             if e["name"] == "tree.kernel.trace"]
    # depth 3, one chunk: the root and the fused kernels of levels 1 and 2
    assert [(e["args"]["kernel"], e["args"]["L"], e["args"]["chunk"],
             e["args"]["W"]) for e in spans] == [
        ("tree_fused_level", 1, 0, 128), ("tree_fused_level", 1, 0, 128),
        ("tree_fused_level", 2, 0, 128)]
    assert {e["args"]["parent"] for e in spans} == {TREE}
    assert all(e["dur"] > 0 for e in spans)
    reg = obs.registry()
    assert reg.counter("tree.kernel.traces").value == 3
    assert reg.counter("tree.kernel.calls").value == 4 * 3
    assert reg.counter("tree.kernel.chunks", mode="fused").value == 4
    assert tt._tree_kernel_calls(3, lay, (False, True, True, True)) == 3


def test_nn_program_carries_its_scopes():
    from shifu_tpu.models.nn import flatten_params, init_params

    cfg = nt.NNTrainConfig(hidden_nodes=[7], activations=["tanh"],
                           propagation="R")
    flat0, shapes = flatten_params(
        init_params([F, 7, 1], seed=0, init=cfg.weight_init))
    program, init_state = nt._get_program(cfg, shapes, 64)
    flat = jnp.asarray(flat0)
    carry = (flat, init_state(flat0.size), jnp.int32(0), jnp.float32(0.1),
             jnp.float32(np.inf), flat, jnp.int32(0),
             jnp.zeros((), dtype=bool), jnp.float32(0.0), jnp.float32(0.0))
    row = jnp.ones(64)
    jp = jax.make_jaxpr(program)(carry, jnp.int32(2), jnp.ones((64, F)),
                                 row, row, row, jax.random.PRNGKey(0),
                                 jnp.float32(1.0))
    eqns = _eqns(jp.jaxpr, [])
    stacks = {str(e.source_info.name_stack) for e in eqns}
    for scope in ("nn.bwd/jvp(nn.fwd)", "nn.bwd/transpose(jvp(nn.fwd))",
                  "nn.valid", "nn.update"):
        assert any(scope in s for s in stacks), (scope, sorted(stacks))
    # both matmuls of the forward pass and their transposes are named
    dots = [str(e.source_info.name_stack) for e in eqns
            if e.primitive.name == "dot_general"]
    assert sum("nn.bwd/jvp(nn.fwd)" in s for s in dots) == 2
    assert sum("transpose(jvp(nn.fwd))" in s for s in dots) >= 2


def test_wdl_program_carries_its_scopes():
    from shifu_tpu.models.wdl import flatten_wdl, init_wdl_params

    cfg = wt.WDLTrainConfig(hidden=[7], activations=["relu"], embed_dim=2)
    tpl = init_wdl_params(F, WDL_VOCAB, 2, [7])
    key_before = set(wt._PROGRAMS)
    program, init_state = wt._get_program(cfg, tpl)
    for k in set(wt._PROGRAMS) - key_before:
        del wt._PROGRAMS[k]
    flat = jnp.asarray(flatten_wdl(tpl))
    carry = (flat, init_state(flat.size), jnp.int32(0), jnp.float32(np.inf),
             flat, jnp.int32(0), jnp.zeros((), dtype=bool), jnp.float32(0.0),
             jnp.float32(0.0))
    row = jnp.ones(64)
    jp = jax.make_jaxpr(program)(carry, jnp.int32(2), jnp.ones((64, F)),
                                 jnp.zeros((64, 3), jnp.int32), row, row,
                                 row, jnp.float32(1.0), jnp.float32(0.005))
    eqns = _eqns(jp.jaxpr, [])
    stacks = {str(e.source_info.name_stack) for e in eqns}
    for scope in ("jvp(wdl.embed)", "transpose(jvp(wdl.embed))",
                  "jvp(wdl.wide)", "transpose(jvp(wdl.wide))",
                  "jvp(wdl.deep)", "transpose(jvp(wdl.deep))",
                  "wdl.loss", "wdl.update"):
        assert any(scope in s for s in stacks), (scope, sorted(stacks))
    # one lookup a field, for its embedding row and its wide weight together
    # (PR 33), and one scatter-add that is its transpose, both under
    # `wdl.embed`; `wdl.wide` keeps the dense dot and the selector dot
    by = lambda prim, scope: sum(  # noqa: E731
        e.primitive.name == prim and scope in str(e.source_info.name_stack)
        for e in eqns)
    assert by("gather", "jvp(wdl.embed)") == 3
    assert by("scatter-add", "transpose(jvp(wdl.embed))") == 3
    assert sum(e.primitive.name in ("gather", "scatter-add")
               for e in eqns) == 6
    dots = [str(e.source_info.name_stack) for e in eqns
            if e.primitive.name == "dot_general"]
    assert sum("jvp(wdl.wide)" in s and "transpose" not in s
               for s in dots) == 2
    # the tower's two matmuls forward, and a weight and an input gradient
    # of each behind them
    assert sum("jvp(wdl.deep)" in s and "transpose" not in s
               for s in dots) == 2
    assert sum("transpose(jvp(wdl.deep))" in s for s in dots) == 4
