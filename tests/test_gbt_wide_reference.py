"""`train_trees` at 256 slots a column and depth 8 (the shape of
`higgs_gbt_255.train_depth8`) against the benchmark's plain reference for
wide layouts (`benchmarks/references/gbt_levelwise_wide.py`, nothing of
shifu_tpu) at a few thousand rows, and that reference against
`gbt_levelwise`, whose docstring is its specification.

With the kernel off (the XLA path) at all 28 columns, 7,168 one-hot columns;
with it on in interpret mode at 6 columns x 256 slots: 3 chunks a level
under the fused scan's 512-column cap and 2 in hist mode, int32 codes (more
than 128 slots a column), bf16 planes, and the level of 128 nodes built from
a half of 64 by the hist-mode kernel with the XLA scan after it. Not all 28
columns in interpret mode: that program takes 90 s to compile on a CPU.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks.lib import spec  # noqa: E402
from shifu_tpu import obs  # noqa: E402
from shifu_tpu.ops import hist_pallas as hp  # noqa: E402
from shifu_tpu.train import tree_trainer as tt  # noqa: E402
from shifu_tpu.utils import environment  # noqa: E402

S, D, SEED, TREES, LR, MIN_INST = 256, 8, 29, 2, 0.1, 5


@pytest.fixture(scope="module")
def wide():
    return spec.load_module("references", "gbt_levelwise_wide")


def _rows(n, F, seed=7):
    """Codes uniform over the 255 value bins and a label on four columns,
    one through an interaction, so that trees split to their last level."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, S - 1, (n, F)).astype(np.int32)
    u = (codes[:, :4] + 0.5) / (S - 1) - 0.5
    z = (2.0 * u[:, 0] - 1.5 * u[:, 1]
         + 2.0 * np.where(u[:, 2] > 0, u[:, 3], -u[:, 3])
         + 0.3 * rng.standard_normal(n))
    return codes, (z > 0).astype(np.float32), np.ones(n, np.float32)


def _cfg(depth=D):
    return tt.TreeTrainConfig(
        algorithm="GBT", tree_num=TREES, max_depth=depth, learning_rate=LR,
        min_instances_per_node=MIN_INST, valid_set_rate=0.2, seed=SEED)


def _call(rows, F, depth=D):
    """(forest, weights, errors told to progress_cb, digest) of one
    `train_trees` call."""
    errs = []
    res = tt.train_trees(*rows, [S] * F, [False] * F,
                         ["f%d" % i for i in range(F)], _cfg(depth),
                         progress_cb=lambda k, t, v: errs.append((t, v)))
    forest = [(np.asarray(t.feature), np.asarray(t.left_mask),
               np.asarray(t.leaf_value)) for t in res.spec.trees]
    h = hashlib.sha256()
    for f, m, v in forest:
        h.update(f.tobytes() + m.tobytes() + v.tobytes())
    return (forest, [float(t.weight) for t in res.spec.trees], errs,
            h.hexdigest())


@pytest.mark.parametrize("kernel,F,n,regret,value_gap,calls,chunks", [
    ("off", 28, 6000, 1e-6, 1e-5, 0, {}),
    # 7 fused levels x 3 chunks + the level built at 64 nodes x 2 chunks;
    # the limits are the one-chip GBT cell's: bf16 planes
    ("interpret", 6, 4000, 6e-3, 4e-2, 23, {"fused": 3, "hist": 2}),
])
def test_trainer_at_256_slots_and_depth_8_against_the_plain_reference(
        wide, kernel, F, n, regret, value_gap, calls, chunks):
    rows = _rows(n, F)
    if kernel == "interpret":
        environment.set_property("shifu.pallas.mode", "on")
    obs.reset()
    try:
        first = _call(rows, F)
        forest, weights, errs, digest = _call(rows, F)
        lay = tt.make_layout([S] * F, [False] * F)
        assert hp.code_dtype(lay) == np.int32
    finally:
        environment.set_property("shifu.pallas.mode", "")
    assert first[3] == digest  # forests_differ 0 over two calls
    counters = obs.registry().snapshot()["counters"]
    assert counters.get("tree.kernel.calls", 0) == 2 * TREES * calls
    assert {m: counters.get('tree.kernel.chunks{mode="%s"}' % m)
            for m in chunks} == {m: 2 * TREES * c for m, c in chunks.items()}
    assert weights == [1.0, LR]
    codes, y, w = (jnp.asarray(a) for a in rows)
    valid = jnp.asarray(wide.split_valid(n, SEED, 0.2))
    R = wide.Reference(n, F, S, D)
    ev = R.evaluate(codes, y, w, valid, forest, weights, float(MIN_INST),
                    follow=[0, 1])
    assert ev["followed"] == [0, 1]
    assert max(ev["regret"]) < regret
    assert max(ev["value_gap"]) < value_gap
    assert np.allclose(errs, ev["errors"], rtol=1e-5, atol=0)
    # every tree reaches its last split level, the one of 128 nodes
    assert all((f[R.level(D - 1)] >= 0).any() for f, _m, _v in forest)


def test_wide_reference_against_gbt_levelwise_on_one_forest(wide):
    """Same forest in, same `regret`, `value_gap` and errors out, sound and
    with a split altered; and the two grow the same forest."""
    narrow = spec.load_module("references", "gbt_levelwise")
    n, F, depth = 3000, 5, 4
    rows = _rows(n, F, seed=3)
    forest, weights, _errs, _ = _call(rows, F, depth)
    codes, y, w = (jnp.asarray(a) for a in rows)
    assert np.array_equal(wide.split_valid(n, SEED, 0.2),
                          narrow.split_valid(n, SEED, 0.2))
    valid = jnp.asarray(wide.split_valid(n, SEED, 0.2))
    W = wide.Reference(n, F, S, depth)
    N = narrow.Reference(n, F, S, depth)
    f0 = forest[0][0].copy()
    f0[1] = (f0[1] + 2) % F
    altered = [(f0,) + forest[0][1:]] + forest[1:]
    for trees in (forest, altered):
        a = W.evaluate(codes, y, w, valid, trees, weights, float(MIN_INST),
                       follow=[0, 1])
        b = N.evaluate(codes, y, w, valid, trees, weights, float(MIN_INST))
        assert np.allclose(a["regret"], b["regret"], rtol=1e-5, atol=1e-9)
        assert np.allclose(a["value_gap"], b["value_gap"], rtol=1e-4,
                           atol=1e-9)
        assert np.allclose(a["errors"], b["errors"], rtol=1e-6, atol=0)
    assert a["regret"][0] > 0.1  # the altered split is seen by both
    # default: the first, the middle and the last tree are followed
    assert wide.default_follow(10) == [0, 5, 9]
    assert wide.default_follow(2) == [0, 1] and wide.default_follow(1) == [0]
    got = [W.grow(codes, y, w, valid, TREES, LR, float(MIN_INST), **kw)
           for kw in ({}, {"lowp": True}, {"fault": "half"},
                      {"fault": "stuck"})]
    want = [N.grow(codes, y, w, valid, TREES, LR, float(MIN_INST), **kw)
            for kw in ({}, {"lowp": True}, {"fault": "half"},
                       {"fault": "stuck"})]
    for (gf, gw, ge), (wf, ww, we) in zip(got, want):
        assert gw == ww and np.allclose(ge, we, rtol=1e-6)
        for (f1, m1, v1), (f2, m2, v2) in zip(gf, wf):
            assert np.array_equal(f1, f2) and np.array_equal(m1, m2)
            assert np.allclose(v1, v2, rtol=1e-5, atol=1e-7)
    # the control is not the sound forest
    assert any(not np.array_equal(a[0], b[0])
               or not np.allclose(a[2], b[2], rtol=1e-4)
               for a, b in zip(got[0][0], got[1][0]))


def test_the_configuration_is_the_public_comparisons(wide):
    cell = spec.Cell("higgs_gbt_255.train_depth8")
    c = cell.config
    assert (c["features"], c["slots_per_feature"], c["max_depth"]) == (
        28, 256, 8)
    assert (c["learning_rate"], c["min_instances_per_node"]) == (0.1, 100)
    assert c["rows"] == c["published_rows"] == 11_000_000
    assert abs(c["valid_set_rate"] - 500_000 / 11_000_000) < 1e-7
    assert c["reduced"].keys() == {"trees_per_call"}
    assert cell.traffic["trees_per_call"] == 10 and cell.chips == 1
    assert c["reference"] == "gbt_levelwise_wide"
    # the levels built in hist mode: a built half past the fused scan's cap
    assert c["hist_mode_levels"] == [
        d for d in range(1, c["max_depth"])
        if 2 ** (d - 1) > tt._FUSED_SCAN_L_CAP] == [7]
    lay = tt.make_layout([c["slots_per_feature"]] * c["features"],
                         [False] * c["features"])
    assert lay.T == c["one_hot_columns"] == 7168
