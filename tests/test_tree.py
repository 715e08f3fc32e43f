"""GBT/RF tree engine tests: split correctness on hand-built data, GBT
residual fitting, RF voting, serialization roundtrip, categorical subset
splits, and the end-to-end tree train processor."""

import os

import numpy as np
import pytest

from shifu_tpu.models.tree import DenseTree, TreeModelSpec
from shifu_tpu.train.tree_trainer import (
    TreeTrainConfig,
    build_tree,
    subset_count,
    train_trees,
)


def _codes_1feat(values, slots=4):
    return np.asarray(values, dtype=np.int32).reshape(-1, 1), [slots]


class TestBuildTree:
    def test_perfect_numeric_split(self):
        """y = 1 iff code >= 2: one split should separate exactly."""
        import jax.numpy as jnp

        codes, slots = _codes_1feat([0, 0, 1, 1, 2, 2, 3, 3] * 10)
        y = (codes[:, 0] >= 2).astype(np.float32)
        w = np.ones(len(y), dtype=np.float32)
        cfg = TreeTrainConfig(max_depth=2, min_instances_per_node=1)
        tree, resting = build_tree(
            jnp.asarray(codes), jnp.asarray(y), jnp.asarray(w),
            np.asarray(slots), np.asarray([False]), cfg, np.asarray([True]),
        )
        assert tree.feature[0] == 0
        # bins 0,1 left; 2,3 right
        assert tree.left_mask[0, :2].all() and not tree.left_mask[0, 2:4].any()
        pred = tree.leaf_value[resting]
        np.testing.assert_allclose(pred, y, atol=1e-5)

    def test_categorical_subset_split(self):
        """Categorical where bins {0, 2} are positive: mean-sorted subset
        split must put them on one side despite non-contiguous codes."""
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        codes = rng.integers(0, 4, size=(400, 1)).astype(np.int32)
        y = np.isin(codes[:, 0], [0, 2]).astype(np.float32)
        w = np.ones(len(y), dtype=np.float32)
        cfg = TreeTrainConfig(max_depth=1, min_instances_per_node=1)
        tree, resting = build_tree(
            jnp.asarray(codes), jnp.asarray(y), jnp.asarray(w),
            np.asarray([4]), np.asarray([True]), cfg, np.asarray([True]),
        )
        pred = tree.leaf_value[resting]
        np.testing.assert_allclose(pred, y, atol=1e-5)
        left_set = set(np.nonzero(tree.left_mask[0])[0].tolist())
        assert left_set in ({0, 2}, {1, 3})

    def test_min_instances_blocks_split(self):
        import jax.numpy as jnp

        codes, slots = _codes_1feat([0, 1, 2, 3])
        y = np.asarray([0, 0, 1, 1], np.float32)
        w = np.ones(4, np.float32)
        cfg = TreeTrainConfig(max_depth=2, min_instances_per_node=10)
        tree, resting = build_tree(
            jnp.asarray(codes), jnp.asarray(y), jnp.asarray(w),
            np.asarray(slots), np.asarray([False]), cfg, np.asarray([True]),
        )
        assert tree.feature[0] == -1  # no split possible
        assert (resting == 0).all()
        assert tree.leaf_value[0] == pytest.approx(0.5)


def _make_data(n=2000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    slots = [8] * f
    codes = rng.integers(0, 8, size=(n, f)).astype(np.int32)
    logits = (codes[:, 0] >= 4) * 2.0 + (codes[:, 1] <= 2) * 1.0 - 1.5
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    w = np.ones(n, dtype=np.float32)
    return codes, y, w, slots


class TestTrainTrees:
    def test_gbt_learns(self):
        codes, y, w, slots = _make_data()
        cfg = TreeTrainConfig(algorithm="GBT", tree_num=20, max_depth=3,
                              learning_rate=0.3, valid_set_rate=0.2, seed=1)
        res = train_trees(codes, y, w, slots, [False] * 8,
                          [f"c{i}" for i in range(8)], cfg)
        assert len(res.spec.trees) == 20
        assert res.valid_error < 0.12

        scores = res.spec.independent().compute(codes)
        auc_num = ((scores[y == 1][:, None] > scores[y == 0][None, :]).mean())
        assert auc_num > 0.85

    def test_rf_learns(self):
        codes, y, w, slots = _make_data()
        cfg = TreeTrainConfig(algorithm="RF", tree_num=10, max_depth=5,
                              feature_subset_strategy="TWOTHIRDS",
                              valid_set_rate=0.2, seed=2)
        res = train_trees(codes, y, w, slots, [False] * 8,
                          [f"c{i}" for i in range(8)], cfg)
        scores = res.spec.independent().compute(codes)
        assert res.valid_error < 0.15
        assert scores.min() >= 0 and scores.max() <= 1

    def test_gbt_log_loss(self):
        codes, y, w, slots = _make_data()
        cfg = TreeTrainConfig(algorithm="GBT", tree_num=15, max_depth=3,
                              loss="log", learning_rate=0.3, seed=3)
        res = train_trees(codes, y, w, slots, [False] * 8,
                          [f"c{i}" for i in range(8)], cfg)
        scores = res.spec.independent().compute(codes)
        assert ((scores > 0.5) == (y > 0.5)).mean() > 0.85

    def test_early_stop(self):
        codes, y, w, slots = _make_data(n=400)
        cfg = TreeTrainConfig(algorithm="GBT", tree_num=100, max_depth=3,
                              learning_rate=0.5, early_stop_rounds=3,
                              valid_set_rate=0.3, seed=4)
        res = train_trees(codes, y, w, slots, [False] * 8,
                          [f"c{i}" for i in range(8)], cfg)
        assert len(res.spec.trees) < 100

    def test_impurities_all_run(self):
        codes, y, w, slots = _make_data(n=500)
        for imp in ("variance", "friedmanmse", "entropy", "gini"):
            cfg = TreeTrainConfig(algorithm="RF", tree_num=2, max_depth=3,
                                  impurity=imp, seed=5)
            res = train_trees(codes, y, w, slots, [False] * 8,
                              [f"c{i}" for i in range(8)], cfg)
            assert np.isfinite(res.valid_error), imp

    def test_subset_count(self):
        assert subset_count("ALL", 100) == 100
        assert subset_count("HALF", 100) == 50
        assert subset_count("SQRT", 100) == 10
        assert subset_count("LOG2", 64) == 6
        assert subset_count("TWOTHIRDS", 9) == 6


class TestTreeSpec:
    def test_roundtrip(self, tmp_path):
        codes, y, w, slots = _make_data(n=500)
        cfg = TreeTrainConfig(algorithm="GBT", tree_num=5, max_depth=3, seed=6)
        res = train_trees(codes, y, w, slots, [False] * 8,
                          [f"c{i}" for i in range(8)], cfg)
        path = str(tmp_path / "model0.gbt")
        res.spec.save(path)
        loaded = TreeModelSpec.load(path)
        assert len(loaded.trees) == 5
        assert loaded.algorithm == "GBT"
        s1 = res.spec.independent().compute(codes[:50])
        s2 = loaded.independent().compute(codes[:50])
        np.testing.assert_allclose(s1, s2, atol=1e-6)

    def test_raw_record_scoring(self, tmp_path):
        """codes_from_raw bins raw values with embedded boundaries."""
        from shifu_tpu.data.reader import ColumnarData

        tree = DenseTree(
            feature=np.asarray([0, -1, -1], np.int32),
            left_mask=np.asarray([[1, 1, 0, 0]] * 3, bool),
            leaf_value=np.asarray([0.5, 0.1, 0.9], np.float32),
        )
        spec = TreeModelSpec(
            algorithm="RF", trees=[tree], input_columns=["x"], slots=[4],
            boundaries=[[-np.inf, 1.0, 2.0]], categories=[None],
        )
        data = ColumnarData(
            names=["x"],
            raw={"x": np.asarray(["0.5", "1.5", "5.0", "?"], object)},
            n_rows=4,
        )
        codes = spec.independent().codes_from_raw(data)
        np.testing.assert_array_equal(codes[:, 0], [0, 1, 2, 3])
        scores = spec.independent().compute(codes)
        np.testing.assert_allclose(scores, [0.1, 0.1, 0.9, 0.9], atol=1e-6)


class TestTreeProcessor:
    def test_end_to_end_gbt(self, tmp_path):
        from tests.helpers import make_model_set

        root = str(tmp_path / "ms")
        make_model_set(root, n_rows=400, algorithm="GBT")
        from shifu_tpu.config.model_config import ModelConfig
        from shifu_tpu.processor.init import InitProcessor
        from shifu_tpu.processor.norm import NormProcessor
        from shifu_tpu.processor.stats import StatsProcessor
        from shifu_tpu.processor.train import TrainProcessor

        mc = ModelConfig.load(os.path.join(root, "ModelConfig.json"))
        mc.train.params["TreeNum"] = 10
        mc.train.params["MaxDepth"] = 4
        mc.save(os.path.join(root, "ModelConfig.json"))
        assert InitProcessor(root).run() == 0
        assert StatsProcessor(root).run() == 0
        assert NormProcessor(root).run() == 0
        assert TrainProcessor(root).run() == 0
        model_path = os.path.join(root, "models", "model0.gbt")
        assert os.path.isfile(model_path)

        spec = TreeModelSpec.load(model_path)
        assert spec.valid_error is not None

        # eval with the tree model via the standard eval path
        from shifu_tpu.processor.evaluate import EvalProcessor

        mc = ModelConfig.load(os.path.join(root, "ModelConfig.json"))
        mc.evals[0].data_set.data_path = mc.data_set.data_path
        mc.evals[0].data_set.header_path = mc.data_set.header_path
        mc.save(os.path.join(root, "ModelConfig.json"))
        assert EvalProcessor(root, run_name="").run() == 0
        import json

        with open(os.path.join(root, "evals", "Eval1",
                               "EvalPerformance.json")) as fh:
            perf = json.load(fh)
        assert perf["areaUnderRoc"] > 0.85


class TestMeshParallelTrees:
    """The multi-chip contract (DTMaster.java:297-310 histogram merge →
    psum): an 8-device row-sharded build must produce the SAME forest as
    the single-device build."""

    def test_8_device_tree_equals_1_device_tree(self):
        from shifu_tpu.parallel.mesh import data_mesh

        rng = np.random.default_rng(7)
        n, F, S = 1003, 10, 12  # row count NOT divisible by 8 (pad path)
        codes = rng.integers(0, S, size=(n, F)).astype(np.int32)
        y = (codes[:, 0] + codes[:, 1]
             + rng.normal(scale=2, size=n) > S).astype(np.float32)
        w = np.ones(n, np.float32)
        slots = [S] * F
        is_cat = [False] * (F - 2) + [True, True]
        cols = [f"c{i}" for i in range(F)]

        for alg in ("GBT", "RF"):
            cfg = TreeTrainConfig(algorithm=alg, tree_num=4, max_depth=4,
                                  seed=3)
            r1 = train_trees(codes, y, w, slots, is_cat, cols, cfg)
            r8 = train_trees(codes, y, w, slots, is_cat, cols, cfg,
                             mesh=data_mesh(8))
            assert len(r1.spec.trees) == len(r8.spec.trees)
            for t1, t8 in zip(r1.spec.trees, r8.spec.trees):
                np.testing.assert_array_equal(t1.feature, t8.feature)
                np.testing.assert_array_equal(t1.left_mask, t8.left_mask)
                np.testing.assert_allclose(t1.leaf_value, t8.leaf_value,
                                           atol=1e-4)
            assert abs(r1.valid_error - r8.valid_error) < 1e-4, alg
