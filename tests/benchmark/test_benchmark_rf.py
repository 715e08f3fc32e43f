"""The forest cell's harness on the CPU at 2,000 rows, depth 4, 3 trees a
call: a sound run is correct; the control, every fault planted in the
reference's place and every fault planted in the timed path underneath is
not, each by the number named beside it."""

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import spec

CELL = "higgs_rf.train_depth10"
ROWS = 2000
# off the chip the program's arithmetic is the reference's own, so any limit
# above rounding does; these stand in for the chip's (the cell's file)
LIMITS = {"regret": 1e-4, "value_gap": 1e-4, "error_gap": 1e-5,
          "forests_differ": 0.0}


@pytest.fixture(autouse=True)
def small_forest(monkeypatch):
    real = spec.Cell.__init__

    def init(self, name):
        real(self, name)
        if name == CELL:
            self.config["max_depth"] = 4
            self.traffic["trees_per_call"] = 3
            self.traffic["limits"] = dict(LIMITS)

    monkeypatch.setattr(spec.Cell, "__init__", init)


def _run(seed=21, **kw):
    return run.run_cell(CELL, seed, 0.2, False, require_chip=False,
                        rows=ROWS, **kw)


def _over(out) -> set:
    return {k for k, c in out["compared"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"] is True
    assert set(out["metrics"]) == {"gbt_row_trees_per_s", "setup_s"}
    assert set(out["compared"]) == set(LIMITS)
    assert out["compared"]["forests_differ"]["value"] == 0.0
    assert out["compared"]["regret"]["value"] == 0.0
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_the_cells_own_limits_are_all_held():
    """The file's limits, not this test's: every number is compared."""
    limits = spec.load_json("workloads", CELL + ".json")["limits"]
    assert set(limits) == set(LIMITS)
    assert all(v is not None for v in limits.values())
    assert limits["forests_differ"] == 0.0


@pytest.mark.parametrize("seed", [4, 2**31 + 29])
def test_control_is_not_correct(seed):
    out = _run(seed=seed, control=True)
    assert out["correct"] is False
    assert "value_gap" in _over(out)


@pytest.mark.parametrize("fault,number", [
    ("lowp8", "value_gap"), ("bag", "value_gap"), ("subset", "regret"),
    ("half", "value_gap"), ("sum", "error_gap"), ("value", "value_gap"),
    ("split", "regret")])
def test_planted_fault_is_not_correct_by_its_number(fault, number):
    """As `benchmarks/calibrate.py --faults` plants them."""
    cell = spec.Cell(CELL)
    drv = spec.load_module("drivers", "tree_forest").setup(cell, 21, ROWS)
    drv.warm_and_read()
    sound = drv.compared()
    assert all(c["value"] <= c["limit"] for c in sound.values())
    got = drv.compared(fault=fault)
    assert got[number]["value"] > got[number]["limit"]


def _wrap_tree_program(monkeypatch, change):
    from shifu_tpu.train import tree_trainer as tt

    real = tt._get_tree_program

    def get(*a, **kw):
        prog = real(*a, **kw)
        return lambda *args: change(prog, args)

    monkeypatch.setattr(tt, "_get_tree_program", get)


def test_fault_bag_ignored_in_the_timed_path(monkeypatch):
    """Every tree on every row: the weights handed to the tree program lose
    their bag counts."""
    def change(prog, args):
        args = list(args)
        args[-2] = (args[-2] > 0).astype(args[-2].dtype)
        return prog(*args)

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False and "value_gap" in _over(out)


def test_fault_subset_ignored_in_the_timed_path(monkeypatch):
    def change(prog, args):
        args = list(args)
        args[-1] = args[-1] | True
        return prog(*args)

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False and "regret" in _over(out)


def test_fault_half_the_rows_left_out(monkeypatch):
    def change(prog, args):
        args = list(args)
        w = args[-2]
        args[-2] = w * (np.arange(w.shape[0]) % 2 == 0)
        return prog(*args)

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False and "value_gap" in _over(out)


def test_fault_running_mean_replaced_by_a_sum(monkeypatch):
    """The trees are the forest's own; only the errors told to the caller
    are a sum's."""
    def change(prog, args):
        f, m, lv, rest, pred = prog(*args)
        change.k += 1
        # (pred * k + x) / (k + 1) with x = sum's share gives the sum
        return f, m, lv, rest, pred * change.k

    change.k = 0
    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False and _over(out) == {"error_gap"}


@pytest.mark.parametrize("what", ["value", "split"])
def test_fault_answer_altered_where_it_is_produced(monkeypatch, what):
    from shifu_tpu.train import tree_trainer as tt

    real = tt._assemble_dense_tree

    def broken(feat, mask, leaf, D):
        tree = real(feat, mask, leaf, D)
        if what == "value":
            tree.leaf_value = tree.leaf_value.copy()
            tree.leaf_value[tree.leaf_value.nonzero()[0][-1]] *= 1.2
        else:
            tree.feature = tree.feature.copy()
            tree.feature[1] = (tree.feature[1] + 7) % 28
        return tree

    monkeypatch.setattr(tt, "_assemble_dense_tree", broken)
    out = _run()
    assert out["correct"] is False
    assert ("value_gap" if what == "value" else "regret") in _over(out)
