"""The GBT cell's harness on the CPU at 2,000 rows, depth 3, 3 trees a call: a
sound run is correct, the lower-precision control is not, and a timed path
broken underneath is not."""

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import spec

CELL = "higgs_gbt.train_levelwise"
ROWS = 2000


@pytest.fixture(autouse=True)
def small_trees(monkeypatch):
    real = spec.Cell.__init__

    def init(self, name):
        real(self, name)
        if name == CELL:
            self.config["max_depth"] = 3
            self.traffic["trees_per_call"] = 3

    monkeypatch.setattr(spec.Cell, "__init__", init)


def _run(seed=21, **kw):
    return run.run_cell(CELL, seed, 0.2, False, require_chip=False,
                        rows=ROWS, **kw)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"gbt_row_trees_per_s", "setup_s"}
    limits = spec.Cell(CELL).traffic["limits"]
    held = {k for k, v in limits.items() if v is not None}
    assert set(out["compared"]) == held >= {"regret", "value_gap",
                                            "forests_differ"}
    assert out["compared"]["forests_differ"]["value"] == 0.0
    assert out["compared"]["regret"]["value"] == 0.0  # f32 planes on the CPU


@pytest.mark.parametrize("seed", [4, 2**31 + 29])
def test_control_is_not_correct(seed):
    out = _run(seed=seed, control=True)
    assert out["correct"] is False


def _wrap_tree_program(monkeypatch, change):
    from shifu_tpu.train import tree_trainer as tt

    real = tt._get_tree_program

    def get(*a, **kw):
        prog = real(*a, **kw)
        return lambda *args: change(prog, args)

    monkeypatch.setattr(tt, "_get_tree_program", get)


def test_fault_state_left_unchanged(monkeypatch):
    """The running prediction never moves: every tree fits the first
    residual again."""
    def change(prog, args):
        f, m, lv, rest, pred = prog(*args)
        return f, m, lv, rest, pred * 0.0

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False
    assert out["compared"]["value_gap"]["value"] > 0.5


def test_fault_half_the_batch_left_out(monkeypatch):
    def change(prog, args):
        args = list(args)
        w = args[2]  # (codes, labels, weights, feat_ok[, M]) off the TPU
        args[2] = w * (np.arange(w.shape[0]) % 2 == 0)
        return prog(*args)

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False


@pytest.mark.parametrize("what", ["value", "split"])
def test_fault_answer_altered_where_it_is_produced(monkeypatch, what):
    from shifu_tpu.train import tree_trainer as tt

    real = tt._assemble_dense_tree

    def broken(feat, mask, leaf, D):
        tree = real(feat, mask, leaf, D)
        if what == "value":
            tree.leaf_value = tree.leaf_value.copy()
            tree.leaf_value[-1] *= 1.2
        else:
            tree.feature = tree.feature.copy()
            tree.feature[1] = (tree.feature[1] + 7) % 28
        return tree

    monkeypatch.setattr(tt, "_assemble_dense_tree", broken)
    out = _run()
    assert out["correct"] is False
    key = "value_gap" if what == "value" else "regret"
    c = out["compared"][key]
    assert c["value"] > c["limit"]


# ---- the plain reference on its own ----

@pytest.fixture(scope="module")
def ref():
    return spec.load_module("references", "gbt_levelwise")


def test_reference_draw_is_the_trainers_draw(ref):
    n, seed = 3000, 77
    mine = ref.split_valid(n, seed, 0.2)
    theirs = np.random.default_rng([seed, 999_983]).random(n) < 0.2
    assert np.array_equal(mine, theirs) and 0.15 < mine.mean() < 0.25


def test_reference_gains_against_a_loop(ref):
    rng = np.random.default_rng(0)
    R = ref.Reference(64, 2, 5, 1)
    H = np.zeros((R.N, 3, 2, 5))
    codes = rng.integers(0, 5, size=(200, 2))
    r = rng.normal(size=200)
    for c, v in zip(codes, r):
        for f in range(2):
            H[0, :, f, c[f]] += (1.0, v, v * v)
    gain, count, mean = R.gains(H, 5.0)
    assert count[0] == 200 and mean[0] == pytest.approx(r.mean())
    for f in range(2):
        for cut in range(4):
            left = codes[:, f] <= cut
            a, b = r[left], r[~left]
            if len(a) < 5 or len(b) < 5:
                assert gain[0, f, cut] == -np.inf
                continue
            want = ((r - r.mean()) ** 2).sum() - ((a - a.mean()) ** 2).sum() \
                - ((b - b.mean()) ** 2).sum()
            assert gain[0, f, cut] == pytest.approx(want, rel=1e-9)
    assert np.all(gain[0, :, 4] == -np.inf)  # the last bin is no cut


def test_reference_grows_what_it_then_follows_without_regret(ref):
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    n, F, S, D = 1500, 4, 9, 2
    codes = jnp.asarray(rng.integers(0, 8, size=(n, F)).astype(np.int32))
    y = jnp.asarray((np.asarray(codes[:, 0]) + rng.integers(0, 8, n) > 7)
                    .astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    valid = jnp.asarray(ref.split_valid(n, 5, 0.2))
    R = ref.Reference(n, F, S, D)
    forest, weights, errs = R.grow(codes, y, w, valid, 2, 0.05, 5.0)
    ev = R.evaluate(codes, y, w, valid, forest, weights, 5.0)
    assert ev["regret"] == [0.0, 0.0]
    assert max(ev["value_gap"]) < 1e-5
    assert np.allclose(ev["errors"], errs, rtol=1e-6)
    assert forest[0][0][0] == 0  # the root splits on the column that matters
    assert weights == [1.0, 0.05]
