"""The 255-bin cell's harness on the CPU at 4,000 rows, the cell's own 28 x
256 slots and depth 8, 3 trees a call: its files load and say what
BENCHMARK.json says, a sound run is correct, the lower-precision control is
not, and neither is a fault planted in the reference's place or in the timed
path underneath, each by the number named beside it."""

import json
import os

import pytest

from benchmarks import run
from benchmarks.lib import spec

CELL = "higgs_gbt_255.train_depth8"
ROWS = 4000
# off the chip the program's arithmetic is the reference's own, so any limit
# above rounding does; these stand in for the chip's (the cell's file)
LIMITS = {"regret": 1e-4, "value_gap": 1e-4, "error_gap": 1e-5,
          "forests_differ": 0.0}


@pytest.fixture(autouse=True)
def small_calls(monkeypatch):
    real = spec.Cell.__init__

    def init(self, name):
        real(self, name)
        if name == CELL:
            self.config["min_instances_per_node"] = 5
            self.traffic["trees_per_call"] = 3
            self.traffic["limits"] = dict(LIMITS)

    monkeypatch.setattr(spec.Cell, "__init__", init)


def _run(seed=21, **kw):
    return run.run_cell(CELL, seed, 0.2, False, require_chip=False,
                        rows=ROWS, **kw)


def _over(out) -> set:
    return {k for k, c in out["compared"].items() if c["value"] > c["limit"]}


def test_the_cells_files_say_what_the_benchmark_says():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == "higgs_gbt_255"][0]
    assert entry == bench["configs"][-1]  # appended, nothing put before it
    assert entry["reduced"] == ["trees_per_call"]
    assert entry["file"] == "benchmarks/configs/higgs_gbt_255.json"
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    cell = spec.Cell(CELL)
    assert cell.traffic["driver"] == cell.config["driver"] == "tree_levelwise"
    assert cell.traffic["warm_up_trees"] == 2
    limits = spec.load_json("workloads", CELL + ".json")["limits"]
    assert set(limits) == set(LIMITS)
    assert all(v is not None for v in limits.values())
    assert limits["forests_differ"] == 0.0
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"gbt_row_trees_per_s", "setup_s"}
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert {"tree_wide_fused_roofline", "tree_wide_hist_roofline",
            "tree_kernel_chunks_per_level", "setup_kernel_trace_s",
            "gbt_mfu_pct", "tree_kernel_calls_per_tree"} <= mine
    # bytes alone, and the forest's f32 planes at 924 slots: not this cell's
    assert not {"tree_kernel_roofline", "tree_deep_kernel_roofline",
                "tree_fused_level_roofline", "rf_mfu_pct"} & mine


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"] is True
    assert set(out["metrics"]) == {"gbt_row_trees_per_s", "setup_s"}
    assert set(out["compared"]) == set(LIMITS)
    assert out["compared"]["forests_differ"]["value"] == 0.0
    assert out["compared"]["regret"]["value"] == 0.0  # f32 planes on the CPU
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_control_is_not_correct():
    out = _run(seed=2**31 + 29, control=True)
    assert out["correct"] is False
    assert "value_gap" in _over(out)


def test_planted_faults_are_not_correct_by_their_numbers():
    """As `benchmarks/calibrate.py --faults` plants them."""
    cell = spec.Cell(CELL)
    drv = spec.load_module("drivers", "tree_levelwise").setup(cell, 21, ROWS)
    drv.warm_and_read()
    sound = drv.compared()
    assert all(c["value"] <= c["limit"] for c in sound.values())
    for fault, number in [("half", "value_gap"), ("stuck", "value_gap"),
                          ("split", "regret")]:
        got = drv.compared(fault=fault)
        assert got[number]["value"] > got[number]["limit"], fault


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
