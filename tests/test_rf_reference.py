"""`train_trees` with `algorithm="RF"` against the benchmark's plain forest
reference (`benchmarks/references/rf_levelwise.py`, nothing of shifu_tpu) at
a few thousand rows: the trees the trainer grows miss none of the gain the
reference's own histograms offer, their node values are the reference's
means, the errors told to `progress_cb` are the running mean's, and every
tree was grown on the reference's bag and the reference's columns. With the
kernel off (the XLA path) and on in interpret mode; depth 7 has a level of
64 nodes, depth 8 in interpret mode a built half of 64, past the fused
scan's 32, so the hist-mode kernel and the XLA scan run under subtraction as
they do in `higgs_rf.train_depth10`.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks.lib import spec  # noqa: E402
from shifu_tpu import obs  # noqa: E402
from shifu_tpu.train import tree_trainer as tt  # noqa: E402
from shifu_tpu.utils import environment  # noqa: E402

N, F, S, SEED, TREES = 3000, 6, 9, 21, 2
STRATEGY = "TWOTHIRDS"


@pytest.fixture(scope="module")
def ref():
    return spec.load_module("references", "rf_levelwise")


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(5)
    codes = rng.integers(0, S - 1, (N, F)).astype(np.int32)
    y = (((codes[:, 0] > 3) ^ (codes[:, 1] > 5))
         | (rng.random(N) < 0.2)).astype(np.float32)
    return codes, y, np.ones(N, np.float32)


def _grow(rows, depth, monkeypatch):
    """(forest, errors told to progress_cb, [(weights, feat_ok_t)] a tree as
    the whole-tree program was handed them)."""
    codes, y, w = rows
    handed = []
    real = tt._get_tree_program

    def get(*a, **kw):
        prog = real(*a, **kw)

        def run(*args):
            handed.append((np.asarray(args[-2]), np.asarray(args[-1])))
            return prog(*args)
        return run

    monkeypatch.setattr(tt, "_get_tree_program", get)
    cfg = tt.TreeTrainConfig(
        algorithm="RF", tree_num=TREES, max_depth=depth,
        feature_subset_strategy=STRATEGY, valid_set_rate=0.2,
        min_instances_per_node=5, seed=SEED)
    errs = []
    res = tt.train_trees(codes, y, w, [S] * F, [False] * F,
                         ["f%d" % i for i in range(F)], cfg,
                         progress_cb=lambda k, t, v: errs.append((t, v)))
    forest = [(np.asarray(t.feature), np.asarray(t.left_mask),
               np.asarray(t.leaf_value)) for t in res.spec.trees]
    assert [t.weight for t in res.spec.trees] == [1.0] * TREES
    return forest, errs, handed


@pytest.mark.parametrize("depth,kernel,calls_a_tree", [
    (4, "off", 0), (7, "off", 0), (4, "interpret", 4),
    (8, "interpret", 8)])
def test_trainer_forest_against_the_plain_reference(ref, rows, monkeypatch,
                                                    depth, kernel,
                                                    calls_a_tree):
    if kernel == "interpret":
        environment.set_property("shifu.pallas.mode", "on")
    obs.reset()
    try:
        forest, errs, handed = _grow(rows, depth, monkeypatch)
    finally:
        environment.set_property("shifu.pallas.mode", "")
    # one chunk a level: depth 8 builds seven fused levels (halves of 1 to
    # 32 nodes) and one in hist mode (a half of 64)
    assert obs.registry().counter("tree.kernel.calls").value \
        == TREES * calls_a_tree
    codes, y, w = (jnp.asarray(a) for a in rows)
    k_sub = tt.subset_count(STRATEGY, F)
    assert k_sub == 4
    valid = ref.split_valid(N, SEED, 0.2)
    R = ref.Reference(N, F, S, depth)
    ev = R.evaluate(codes, y, w, jnp.asarray(valid), forest, SEED, 1.0,
                    k_sub, 5.0)
    # regret 0: where the trainer's split is not the reference's own it is a
    # tie (equal gains by the reference's float64 arithmetic)
    assert ev["regret"] == [0.0] * TREES
    assert max(ev["value_gap"]) < 1e-6
    assert np.allclose(errs, ev["errors"], rtol=1e-6, atol=0)
    # every tree splits down to its last level
    assert all((f[R.level(depth - 1)] >= 0).any() for f, _m, _v in forest)
    # the bag and the columns of each tree are the reference's
    lay = tt.make_layout([S] * F, [False] * F)
    assert len(handed) == TREES
    for k, (w_k, fot) in enumerate(handed):
        bag, allowed = ref.draw_tree(N, F, SEED, k, 1.0, k_sub)
        assert np.array_equal(w_k, np.where(valid, 0.0, 1.0) * bag)
        assert np.array_equal(fot, allowed[lay.seg_of_t])
        assert allowed.sum() == k_sub and 0.9 < bag.mean() < 1.1
        # no node splits on a column outside the tree's own
        f = forest[k][0]
        assert allowed[f[f >= 0]].all()


def test_reference_grows_the_trainers_forest_and_follows_it(ref, rows):
    """`grow` and `evaluate` agree with each other, and at depth 4 the
    reference grows the trainer's forest node for node."""
    codes, y, w = (jnp.asarray(a) for a in rows)
    valid = jnp.asarray(ref.split_valid(N, SEED, 0.2))
    R = ref.Reference(N, F, S, 4)
    forest, errs = R.grow(codes, y, w, valid, TREES, SEED, 1.0, 4, 5.0)
    ev = R.evaluate(codes, y, w, valid, forest, SEED, 1.0, 4, 5.0,
                    follow=[1])
    assert ev["followed"] == [1] and ev["regret"] == [0.0]
    assert len(ev["value_gap"]) == 1 and ev["value_gap"][0] < 1e-6
    assert np.allclose(ev["errors"], errs, rtol=1e-6) and len(errs) == TREES
    cfg = tt.TreeTrainConfig(
        algorithm="RF", tree_num=TREES, max_depth=4,
        feature_subset_strategy=STRATEGY, valid_set_rate=0.2,
        min_instances_per_node=5, seed=SEED)
    res = tt.train_trees(*rows, [S] * F, [False] * F,
                         ["f%d" % i for i in range(F)], cfg)
    for t, (f, m, v) in zip(res.spec.trees, forest):
        assert np.array_equal(t.feature, f)
        assert np.array_equal(np.asarray(t.left_mask)[:, :S], m)
        assert np.allclose(t.leaf_value, v, rtol=1e-6)


@pytest.mark.parametrize("bits,worst", [(8, 2.0 ** -8), (3, 2.0 ** -3)])
def test_control_rounding_keeps_the_stated_bits(ref, bits, worst):
    a = np.random.default_rng(0).uniform(1.0, 5e6, 4000)
    r = ref.round_bits(a, bits)
    rel = np.abs(r - a) / a
    assert worst / 8 < rel.max() <= worst / 2 * 1.0000001
    assert np.array_equal(ref.round_bits(r, bits), r)  # already rounded
    assert ref.round_bits(a, None) is a
    whole = np.arange(0.0, 2 ** (bits + 1) + 1)
    assert np.array_equal(ref.round_bits(whole, bits), whole)


def test_the_configurations_subset_is_the_trainers(ref):
    cell = spec.Cell("higgs_rf.train_depth10")
    c = cell.config
    assert c["features_per_tree"] == tt.subset_count(
        c["feature_subset_strategy"], c["features"]) == 18
    # the levels built in hist mode: a built half past the fused scan's cap
    assert c["hist_mode_levels"] == [
        d for d in range(1, c["max_depth"])
        if 2 ** (d - 1) > tt._FUSED_SCAN_L_CAP] == [7, 8, 9]
    assert list(cell.entry) and c["reduced"].keys() == {"rows"}
