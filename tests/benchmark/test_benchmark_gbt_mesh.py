"""The meshed GBT cell's harness on the CPU: `data_mesh(4)` over the suite's
forced host devices, 2,000 rows, depth 3, 3 trees a call. A sound run is
correct, the lower-precision control is not, and a timed path broken
underneath is not. And what ties the share to the whole: the row shards'
histograms add up to the plain reference's histogram of all rows, by the
program's hist-mode kernel and by the reference that walks the shards."""

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import spec

CELL = "higgs_gbt_full.train_mesh4"
ROWS = 2000


@pytest.fixture
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


def test_cell_is_declared_on_four_chips_with_its_readers():
    cell = spec.Cell(CELL)
    assert cell.chips == 4 and cell.config["rows"] == 11_000_000
    assert cell.config["rows"] == cell.config["published_rows"]
    assert cell.config["chips_share_rows"] == 4
    assert list(cell.config["reduced"]) == ["trees_per_call"]
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"gbt_mesh_mfu_pct", "tree_hist_kernel_roofline",
                     "tree_psum_ms_per_tree", "gbt_mesh_shard_ms_per_call",
                     "device_idle_pct.gbt_mesh", "tree_kernel_ms_per_tree"}
    # the readers that reckon one chip are not this cell's
    assert not names & {"gbt_mfu_pct", "tree_kernel_roofline",
                        "device_idle_pct.gbt"}
    for name in names:
        assert callable(spec.load_module("layer_metrics", name).read)
    # every training setting is the one-chip configuration's
    one = spec.Cell("higgs_gbt.train_levelwise").config
    for key in ("features", "slots_per_feature", "max_depth", "impurity",
                "loss", "learning_rate", "min_instances_per_node",
                "min_info_gain", "feature_subset_strategy",
                "max_stats_memory_mb", "hist_subtraction", "valid_set_rate",
                "trees_per_call", "precision"):
        assert cell.config[key] == one[key], key


def test_sound_run_is_correct_and_fed_from_the_devices(small_trees):
    from shifu_tpu import obs

    obs.reset()
    out = _run()
    assert out["correct"] is True
    assert set(out["metrics"]) == {"gbt_row_trees_per_s", "setup_s"}
    limits = spec.Cell(CELL).traffic["limits"]
    assert set(out["compared"]) == {k for k, v in limits.items()
                                    if v is not None}
    assert out["compared"]["forests_differ"]["value"] == 0.0
    assert out["compared"]["regret"]["value"] == 0.0  # f32 planes on the CPU
    shards = [e for e in obs.tracer().events
              if e["name"] == "train.trees.shard"]
    assert shards and all(e["args"]["source"] == "device" for e in shards)
    # the validity draw alone crosses, a byte a row
    assert {e["args"]["bytes"] for e in shards} == {ROWS}
    counters = obs.registry().snapshot()["counters"]
    assert counters.get("mesh.d2h_bytes", 0.0) == 0.0
    assert counters["tree.psum"] == 4 * counters["train.trees"]


@pytest.mark.parametrize("seed", [5, 2**31 + 29])
def test_control_is_not_correct(small_trees, seed):
    """(At 2,000 rows and 8 leaves the limits, read at 11,000,000 rows,
    catch the control on 8 seeds of 10: 2 and 4 slip through here.)"""
    out = _run(seed=seed, control=True)
    assert out["correct"] is False


def _wrap_tree_program(monkeypatch, change):
    from shifu_tpu.train import tree_trainer as tt

    real = tt._get_tree_program

    def get(*a, **kw):
        assert kw.get("mesh") is not None  # the meshed program, no other
        prog = real(*a, **kw)
        return lambda *args: change(prog, args)

    monkeypatch.setattr(tt, "_get_tree_program", get)


def test_fault_state_left_unchanged(small_trees, monkeypatch):
    def change(prog, args):
        f, m, lv, rest, pred = prog(*args)
        return f, m, lv, rest, pred * 0.0

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False
    assert out["compared"]["value_gap"]["value"] > 0.5


def test_fault_one_chips_rows_left_out(small_trees, monkeypatch):
    """A shard that never reaches the all-reduce: the first chip's rows
    weigh nothing."""
    def change(prog, args):
        args = list(args)
        w = args[2]  # (codes, labels, weights, feat_ok)
        args[2] = w * (np.arange(w.shape[0]) >= w.shape[0] // 4)
        return prog(*args)

    _wrap_tree_program(monkeypatch, change)
    out = _run()
    assert out["correct"] is False


@pytest.mark.parametrize("what", ["value", "split"])
def test_fault_answer_altered_where_it_is_produced(small_trees, monkeypatch,
                                                   what):
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


# ---- the share and the whole ----

F, S, DEPTH = 28, 33, 5  # the cell's columns; levels of 1, 2, ..., 32 nodes


@pytest.fixture(scope="module")
def table():
    """4 x 300 rows at the cell's columns, a node and a plane triple a
    row, on the host."""
    rng = np.random.default_rng(5)
    n = 1200
    codes = rng.integers(0, S, size=(n, F)).astype(np.int32)
    r = rng.normal(size=n).astype(np.float32)
    w = (rng.random(n) < 0.8).astype(np.float32)
    return codes, r, w


@pytest.fixture(scope="module")
def plain():
    return spec.load_module("references", "gbt_levelwise")


def _level_path(slot, active, d):
    """`path [n, DEPTH + 1]` with the rows in level d's nodes alone."""
    path = np.full((slot.shape[0], DEPTH + 1), -1, np.int32)
    path[:, d] = np.where(active, 2**d - 1 + slot, -1)
    return path


@pytest.mark.parametrize("d", range(DEPTH + 1))
def test_shards_hist_mode_histograms_add_up_to_the_plain_reference(
        table, plain, d):
    """The program's hist-mode kernel (interpret mode) on each of four row
    shards under `shard_map`, the four partial histograms added, against
    the plain reference's float32 histogram of all rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from shifu_tpu.ops.hist_pallas import make_pallas_hist_fn
    from shifu_tpu.parallel.mesh import (data_mesh, shard_map_compat,
                                         shard_rows)
    from shifu_tpu.train.tree_trainer import make_layout

    codes, r, w = table
    n, L = codes.shape[0], 2**d
    rng = np.random.default_rng(d)
    slot = rng.integers(0, L, size=n).astype(np.int32)
    active = rng.random(n) < 0.9
    lay = make_layout([S] * F, [False] * F)
    kernel = make_pallas_hist_fn(L, lay, interpret=True)
    mesh = data_mesh(4)
    per_chip = jax.jit(shard_map_compat(
        lambda *a: kernel(*a)[None], mesh=mesh,
        in_specs=(P("data"),) * 5, out_specs=P("data")))
    parts = np.asarray(per_chip(*[shard_rows(a, mesh) for a in
                                  (codes, r, w, slot, active)]))
    assert parts.shape == (4, 3, L, F * S)
    assert (np.abs(parts).sum(axis=(1, 2, 3)) > 0).all()  # each chip's own

    R = plain.Reference(n, F, S, DEPTH)
    planes = jnp.stack([w, w * r, w * r * r], axis=1)
    H = np.asarray(R.path_hist(jnp.asarray(codes),
                               jnp.asarray(_level_path(slot, active, d)),
                               planes))  # [N, 3, F, S]
    want = H[L - 1:2 * L - 1].transpose(1, 0, 2, 3).reshape(3, L, F * S)
    np.testing.assert_allclose(parts.sum(axis=0), want, rtol=1e-5,
                               atol=1e-4)


def test_sharded_reference_is_the_plain_reference(table, plain):
    """The file that walks the shards against the file it walks them with:
    the same histogram of all rows, and the same numbers for a forest."""
    import jax.numpy as jnp

    from shifu_tpu.parallel.mesh import data_mesh, shard_rows

    codes, r, w = table
    n = codes.shape[0]
    y = (r > 0).astype(np.float32)
    sharded = spec.load_module("references", "gbt_levelwise_sharded")
    mesh = data_mesh(4)
    Rs = sharded.Reference(n, F, S, 3)
    Rp = plain.Reference(n, F, S, 3)
    valid = plain.split_valid(n, 9, 0.2)
    on_mesh = [shard_rows(a, mesh) for a in (codes, y, w)]
    on_one = [jnp.asarray(a) for a in (codes, y, w)]
    grown = Rp.grow(*on_one, jnp.asarray(valid), 2, 0.05, 5.0)
    again = Rs.grow(*on_mesh, jnp.asarray(valid), 2, 0.05, 5.0)
    for (f1, m1, v1), (f2, m2, v2) in zip(grown[0], again[0]):
        assert np.array_equal(f1, f2) and np.array_equal(m1, m2)
        np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-6)
    ev_p = Rp.evaluate(*on_one, jnp.asarray(valid), grown[0], grown[1], 5.0)
    ev_s = Rs.evaluate(*on_mesh, jnp.asarray(valid), grown[0], grown[1], 5.0)
    for key in ("regret", "value_gap"):
        np.testing.assert_allclose(ev_s[key], ev_p[key], atol=1e-5)
    np.testing.assert_allclose(ev_s["errors"], ev_p["errors"], rtol=1e-5)
    assert len(Rs._by_shard) == 1  # one program, compiled once
