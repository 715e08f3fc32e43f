"""Histogram subtraction (build the smaller child, derive the sibling).

Contracts, per the train.params.treeHistSubtraction knob (default on):
  * RF histograms are integer sums in f32 (integer Poisson bag weights x
    0/1 labels), so derived = parent - built is EXACT and RF forests are
    BIT-EQUAL subtraction-on vs -off — binary and NATIVE multi-class,
    in-memory and streamed.
  * GBT moment planes carry float residuals: subtraction re-associates
    f32 summation, so GBT forests are TOLERANCE-equal (scores; a
    knife-edge zero-gain deep node may legitimately flip split/no-split,
    which the f64 accumulator chain removes when jax x64 is enabled).
  * A level-wise tree of depth D derives 2^(D-1) - 1 = leaves/2 - 1
    node-histograms (`tree.hist.derived`), builds 2^(D-1)
    (`tree.hist.built`) — vs 2^D - 1 built with subtraction off.
  * When the retained parent + child batch exceed the MaxStatsMemoryMB
    node-plane budget, the level falls back to a full rebuild and counts
    `tree.hist.fallback_rebuilds`; results must not change.
"""

import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.train.tree_trainer import (
    TreeTrainConfig,
    _node_batch_size,
    _sub_level_fits,
    make_layout,
    train_trees,
)


def _make_data(n=2500, f=5, bins=16, seed=0):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, bins, size=(n, f)).astype(np.int32)
    y = ((codes[:, 0] + codes[:, 1] + rng.integers(0, 8, n))
         > bins + 2).astype(np.float32)
    w = np.ones(n, np.float32)
    return codes, y, w, [bins] * f


def _cfg_off(cfg):
    return TreeTrainConfig(**{**cfg.__dict__, "hist_subtraction": False})


def _assert_forests_bit_equal(a, b):
    assert len(a.spec.trees) == len(b.spec.trees)
    for ta, tb in zip(a.spec.trees, b.spec.trees):
        np.testing.assert_array_equal(ta.feature, tb.feature)
        np.testing.assert_array_equal(ta.left_mask, tb.left_mask)
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value, atol=0)


def _hist_counters():
    snap = obs.registry().snapshot().get("counters", {})
    return {k.split(".")[-1]: v for k, v in snap.items()
            if k.startswith("tree.hist.")}


def test_rf_binary_bit_parity():
    """Integer count/moment planes subtract exactly: identical forests."""
    codes, y, w, slots = _make_data()
    cols = [f"c{i}" for i in range(len(slots))]
    cfg = TreeTrainConfig(algorithm="RF", tree_num=4, max_depth=4, seed=3,
                          feature_subset_strategy="TWOTHIRDS")
    on = train_trees(codes, y, w, slots, [False] * len(slots), cols, cfg)
    off = train_trees(codes, y, w, slots, [False] * len(slots), cols,
                      _cfg_off(cfg))
    _assert_forests_bit_equal(on, off)


def test_rf_multiclass_bit_parity():
    """NATIVE multi-class count planes are pure counts: exact too."""
    codes, y, w, slots = _make_data()
    y3 = (codes[:, 0] // 6).astype(np.float32)
    cols = [f"c{i}" for i in range(len(slots))]
    cfg = TreeTrainConfig(algorithm="RF", tree_num=3, max_depth=3, seed=2,
                          impurity="gini", n_classes=3)
    on = train_trees(codes, y3, w, slots, [False] * len(slots), cols, cfg)
    off = train_trees(codes, y3, w, slots, [False] * len(slots), cols,
                      _cfg_off(cfg))
    _assert_forests_bit_equal(on, off)


def test_gbt_tolerance_parity():
    """GBT derived moments re-associate f32: scores equal to tolerance."""
    codes, y, w, slots = _make_data()
    cols = [f"c{i}" for i in range(len(slots))]
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=5, max_depth=4,
                          learning_rate=0.2, seed=3)
    on = train_trees(codes, y, w, slots, [False] * len(slots), cols, cfg)
    off = train_trees(codes, y, w, slots, [False] * len(slots), cols,
                      _cfg_off(cfg))
    s_on = on.spec.independent().compute(codes)
    s_off = off.spec.independent().compute(codes)
    np.testing.assert_allclose(s_on, s_off, atol=1e-3)
    assert on.valid_error == pytest.approx(off.valid_error, abs=1e-4)


def test_gbt_leafwise_tolerance_parity():
    """Leaf-wise growth derives the second frontier child from the
    retained parent histogram; scores must match the rebuild-both run."""
    codes, y, w, slots = _make_data()
    cols = [f"c{i}" for i in range(len(slots))]
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=3, max_depth=6,
                          max_leaves=7, learning_rate=0.3, seed=5)
    on = train_trees(codes, y, w, slots, [False] * len(slots), cols, cfg)
    off = train_trees(codes, y, w, slots, [False] * len(slots), cols,
                      _cfg_off(cfg))
    s_on = on.spec.independent().compute(codes)
    s_off = off.spec.independent().compute(codes)
    np.testing.assert_allclose(s_on, s_off, atol=1e-3)


def test_counters_levelwise():
    """Per level-wise tree of depth D: derived = 2^(D-1) - 1 = leaves/2 - 1
    histograms, built = 2^(D-1); subtraction-off builds all 2^D - 1."""
    codes, y, w, slots = _make_data(n=1200)
    cols = [f"c{i}" for i in range(len(slots))]
    trees, depth = 3, 4
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=trees, max_depth=depth,
                          seed=1)
    obs.reset()
    train_trees(codes, y, w, slots, [False] * len(slots), cols, cfg)
    c_on = _hist_counters()
    leaves = 2 ** depth
    assert c_on["derived"] == trees * (leaves // 2 - 1)
    assert c_on["built"] == trees * (leaves // 2)
    assert "fallback_rebuilds" not in c_on

    obs.reset()
    train_trees(codes, y, w, slots, [False] * len(slots), cols,
                _cfg_off(cfg))
    c_off = _hist_counters()
    assert c_off["built"] == trees * (leaves - 1)
    assert "derived" not in c_off
    # the acceptance ratio: subtraction builds ~half the node-histograms
    assert c_on["built"] / c_off["built"] <= 0.55


def test_counters_leafwise():
    """Each leaf-wise split sweeps ONE child histogram and derives the
    sibling: built = 1 root + n_splits, derived = n_splits."""
    codes, y, w, slots = _make_data(n=1200)
    cols = [f"c{i}" for i in range(len(slots))]
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=1, max_depth=6,
                          max_leaves=6, seed=2)
    obs.reset()
    res = train_trees(codes, y, w, slots, [False] * len(slots), cols, cfg)
    c = _hist_counters()
    n_splits = int((res.spec.trees[0].feature >= 0).sum())
    assert n_splits >= 1
    assert c["derived"] == n_splits
    assert c["built"] == 1 + n_splits


def test_budget_pressure_fallback():
    """A wide layout under a tiny MaxStatsMemoryMB forces the batched path
    and the full-rebuild fallback; results must be unchanged and the
    fallback counted."""
    rng = np.random.default_rng(0)
    n = 1500
    slots = [4000, 16, 16]
    codes = np.stack([rng.integers(0, 4000, n), rng.integers(0, 16, n),
                      rng.integers(0, 16, n)], 1).astype(np.int32)
    y = ((codes[:, 1] + codes[:, 2] + rng.integers(0, 8, n))
         > 18).astype(np.float32)
    w = np.ones(n, np.float32)
    cols = [f"c{i}" for i in range(3)]
    depth = 5
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=2, max_depth=depth,
                          seed=1, max_stats_memory_mb=1,
                          min_instances_per_node=2)
    lay = make_layout(slots, [False] * 3)
    cap = _node_batch_size(lay.T, cfg.max_stats_memory_mb)
    assert 2 ** depth > cap  # pins the host-driven batched path
    # the plan must be mixed: shallow levels subtract, deep levels fall back
    fits = [_sub_level_fits(2 ** d, cap, False) for d in range(1, depth + 1)]
    assert any(fits) and not all(fits)

    obs.reset()
    on = train_trees(codes, y, w, slots, [False] * 3, cols, cfg)
    c = _hist_counters()
    assert c["fallback_rebuilds"] >= 1
    assert c["derived"] >= 1
    off = train_trees(codes, y, w, slots, [False] * 3, cols, _cfg_off(cfg))
    s_on = on.spec.independent().compute(codes)
    s_off = off.spec.independent().compute(codes)
    np.testing.assert_allclose(s_on, s_off, atol=1e-3)


def test_streamed_levelwise_counters_and_rf_bit_parity(tmp_path):
    """The streamed level-wise grower derives every level >= 1 including
    the final leaf level; RF stays bit-equal across the knob."""
    from shifu_tpu.norm.dataset import write_codes
    from shifu_tpu.train.streaming_tree import train_trees_streamed

    rng = np.random.default_rng(0)
    n, f, bins = 2000, 5, 8
    codes = rng.integers(0, bins, size=(n, f)).astype(np.int32)
    y = ((codes[:, 0] + codes[:, 1] + rng.integers(0, 4, n))
         > 9).astype(np.float32)
    w = np.ones(n, np.float32)
    cols = [f"c{i}" for i in range(f)]
    out = str(tmp_path / "codes")
    write_codes(out, codes, y, w, cols, [bins] * f, n_shards=3)

    trees, depth = 2, 3
    cfg = TreeTrainConfig(algorithm="RF", tree_num=trees, max_depth=depth,
                          seed=3)
    obs.reset()
    on = train_trees_streamed(out, [bins] * f, [False] * f, cols, cfg)
    c = _hist_counters()
    # levels 1..D derive (incl. the final leaf level): 2^D - 1 per tree
    assert c["derived"] == trees * (2 ** depth - 1)
    assert c["built"] == trees * (2 ** depth)
    off = train_trees_streamed(out, [bins] * f, [False] * f, cols,
                               _cfg_off(cfg))
    _assert_forests_bit_equal(on, off)


@pytest.mark.parametrize("depth", [5, 9])
def test_three_growers_agree_with_and_without_subtraction(monkeypatch,
                                                          depth):
    """RF planes (a Poisson bag's whole numbers x 0/1 labels) are exact, so
    the whole-tree program, the node-batched `build_tree` (stats budget
    steered down: it subtracts while 2 L nodes fit and rebuilds below) and
    the streamed grower (three shards; it derives the leaf level too) give
    one DenseTree and one `resting`, with the build mask `route_rows` hands
    on (PR 35) as with every node rebuilt. Depth 9 has parents of 128 and
    256 nodes, the forest cell's widest."""
    import jax.numpy as jnp

    from shifu_tpu.train import streaming_tree as st
    from shifu_tpu.train import tree_trainer as tt

    rng = np.random.default_rng(depth)
    n, F, bins = 6000, 6, 16
    codes = rng.integers(0, bins, size=(n, F)).astype(np.int32)
    y = ((codes[:, 0] + codes[:, 1] + codes[:, 2] % 5
          + rng.integers(0, 10, n)) > bins + 4).astype(np.float32)
    w = rng.poisson(1.0, size=n).astype(np.float32)
    slots, is_cat = [bins] * F, [False] * F
    feat_ok = np.array([True, True, True, False, True, True])
    cfg = TreeTrainConfig(algorithm="RF", tree_num=1, max_depth=depth,
                          min_instances_per_node=1, seed=1)
    dev = (jnp.asarray(codes), jnp.asarray(y), jnp.asarray(w))

    def grow(c):
        tree, resting = tt.build_tree(*dev, slots, is_cat, c, feat_ok)
        return tree, np.asarray(resting)

    lay = make_layout(slots, is_cat)
    assert 2 ** depth <= _node_batch_size(lay.T, cfg.max_stats_memory_mb)
    grown = {"whole_tree": grow(cfg), "rebuilt": grow(_cfg_off(cfg))}

    # the streamed grower, three shards of unequal length
    cuts = [0, 1500, 4100, n]
    shards = [slice(a, b) for a, b in zip(cuts, cuts[1:])]

    class Feed:
        def codes(self, s):
            return codes[shards[s]]

    work = [{"labels": jnp.asarray(y[sl]), "w": jnp.asarray(w[sl]),
             "node": jnp.zeros(sl.stop - sl.start, jnp.int32),
             "active": jnp.ones(sl.stop - sl.start, bool),
             "resting": jnp.zeros(sl.stop - sl.start, jnp.int32)}
            for sl in shards]
    obs.reset()
    tree = st._grow_levelwise_streamed(
        Feed(), work, tt._device_layout(lay, feat_ok), lay, cfg, depth,
        jnp.asarray, lambda a: a, None)
    assert _hist_counters()["derived"] == 2 ** depth - 1
    grown["streamed"] = (tree, np.concatenate(
        [np.asarray(wk["resting"]) for wk in work]))

    # the node-batched grower: one node short of the whole-tree program
    cap = 2 ** depth - 1
    monkeypatch.setattr(tt, "_node_batch_size", lambda *a, **k: cap)
    plan, _acc64 = tt._sub_plan(cfg, cap)
    assert any(plan) and not all(plan[1:])
    obs.reset()
    grown["node_batched"] = grow(cfg)
    c = _hist_counters()
    assert c["derived"] >= 1 and c["fallback_rebuilds"] >= 1

    want_tree, want_resting = grown["rebuilt"]
    # parents of 2^(D-1) nodes split; at depth 9 some nodes above them do
    # not, so rows rest before the leaf level too
    leaves = 2 ** depth - 1
    assert (want_tree.feature[leaves // 2:leaves] >= 0).any()
    assert want_resting.max() >= leaves
    assert depth < 9 or want_resting.min() < leaves
    for name, (got_tree, got_resting) in grown.items():
        np.testing.assert_array_equal(got_tree.feature, want_tree.feature,
                                      name)
        np.testing.assert_array_equal(got_tree.left_mask,
                                      want_tree.left_mask, name)
        np.testing.assert_array_equal(got_tree.leaf_value,
                                      want_tree.leaf_value, name)
        np.testing.assert_array_equal(got_resting, want_resting, name)


# The growers over the Pallas kernel (interpret mode here). Since PR 38 the
# kernel reads its codes as `[F, n_pad]`, the rows along the lanes: the
# whole-tree program is handed the operand made once a call
# (`tree.codes8`), every other caller hands `codes [n, F]` and the entry
# turns it where it pads the planes. "narrow": int8 codes; "wide": a
# 300-slot feature turns the operand int32 and lies in several chunks.
_KERNEL_LAYOUTS = {"narrow": [16] * 5 + [9], "wide": [16] * 4 + [300, 9]}


def _kernel_table(slots, n=1300, seed=3):
    rng = np.random.default_rng(seed)
    codes = np.stack([rng.integers(0, s, size=n) for s in slots],
                     1).astype(np.int32)
    y = ((codes[:, 0] + codes[:, 1] + rng.integers(0, 8, n))
         > 18).astype(np.float32)
    w = rng.poisson(1.0, size=n).astype(np.float32)
    return codes, y, w


@pytest.fixture
def kernel_mode():
    """Sets `shifu.pallas.mode`; afterwards the knob and the program cache
    are as they were (a program built under a knob is never reused)."""
    from shifu_tpu.train import tree_trainer as tt
    from shifu_tpu.utils import environment

    before = set(tt._PROGRAMS)

    def set_mode(mode):
        environment.set_property("shifu.pallas.mode", mode)

    try:
        yield set_mode
    finally:
        environment.set_property("shifu.pallas.mode", "")
        for k in set(tt._PROGRAMS) - before:
            del tt._PROGRAMS[k]


@pytest.mark.parametrize("grower", ["whole_tree", "node_batched",
                                    "leaf_wise", "streamed"])
@pytest.mark.parametrize("layout", list(_KERNEL_LAYOUTS))
def test_growers_over_the_kernel_grow_the_xla_forest(monkeypatch, tmp_path,
                                                     kernel_mode, layout,
                                                     grower):
    """RF planes are whole numbers, so every grower grows over the kernel,
    bit for bit, the forest it grows over the XLA lowering: the whole-tree
    program (hoisted operand), the node-batched `build_tree` and the
    leaf-wise grower (the hist-mode entry turns `codes` every call), the
    streamed grower (a `tree.hist` program a shard)."""
    from shifu_tpu.train import tree_trainer as tt

    slots = _KERNEL_LAYOUTS[layout]
    codes, y, w = _kernel_table(slots)
    cols = [f"c{i}" for i in range(len(slots))]
    is_cat = [False] * (len(slots) - 1) + [True]
    kw = {"max_leaves": 9} if grower == "leaf_wise" else {}
    cfg = TreeTrainConfig(algorithm="RF", tree_num=2, max_depth=4,
                          min_instances_per_node=1, seed=2, **kw)
    if grower == "node_batched":  # one node short of the whole-tree program
        monkeypatch.setattr(tt, "_node_batch_size", lambda *a, **k: 15)

    def grow():
        if grower == "streamed":
            from shifu_tpu.norm.dataset import write_codes
            from shifu_tpu.train.streaming_tree import train_trees_streamed

            out = str(tmp_path / "codes")
            write_codes(out, codes, y, w, cols, slots, n_shards=3)
            return train_trees_streamed(out, slots, is_cat, cols, cfg)
        return train_trees(codes, y, w, slots, is_cat, cols, cfg)

    forests = {}
    for mode in ("off", "on"):
        kernel_mode(mode)
        obs.reset()
        forests[mode] = grow()
        calls = obs.registry().snapshot()["counters"].get(
            "tree.kernel.calls", 0)
        assert (calls > 0) == (mode == "on")
    _assert_forests_bit_equal(forests["on"], forests["off"])


@pytest.mark.parametrize("layout", list(_KERNEL_LAYOUTS))
@pytest.mark.parametrize("L", [1, 8])
def test_meshed_hist_program_over_the_kernel(kernel_mode, layout, L):
    """`_get_hist_program(mesh=)`, the streamed trainer's worker merge:
    every chip's hist-mode entry turns its own rows' codes, the partials
    are all-reduced, and the sum is the one-chip kernel's and the XLA
    builder's histogram bit for bit (whole-number planes)."""
    import jax.numpy as jnp

    from shifu_tpu.parallel.mesh import data_mesh, shard_rows
    from shifu_tpu.train import tree_trainer as tt

    slots = _KERNEL_LAYOUTS[layout]
    lay = make_layout(slots, [False] * len(slots))
    codes, y, w = _kernel_table(slots, n=1200)
    rng = np.random.default_rng(L)
    node = rng.integers(0, L, size=len(y)).astype(np.int32)
    active = rng.random(len(y)) < 0.8
    la = tt._device_layout(lay, np.ones(len(slots), bool))
    rest = (la.off, la.clip, la.seg_t, la.pos_t)
    rows = tuple(jnp.asarray(a) for a in (codes, y, w, node, active))
    mesh = data_mesh(4)
    kernel_mode("off")
    want = np.asarray(tt._get_hist_program(L, lay)(*rows, *rest))
    kernel_mode("on")
    one = np.asarray(tt._get_hist_program(L, lay)(*rows, *rest))
    four = np.asarray(tt._get_hist_program(L, lay, mesh=mesh)(
        *(shard_rows(a, mesh) for a in rows), *rest))
    np.testing.assert_array_equal(one, want)
    np.testing.assert_array_equal(four, want)
