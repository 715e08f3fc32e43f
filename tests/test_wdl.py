"""WDL tests: forward math, training convergence, TP-sharded embeddings on
the virtual mesh, spec roundtrip, and end-to-end processor + eval."""

import os

import numpy as np
import pytest

from shifu_tpu.models.wdl import (
    WDLModelSpec,
    flatten_wdl,
    init_wdl_params,
    unflatten_wdl,
    unflatten_wdl_from_shapes,
    wdl_arrays,
    wdl_forward,
    wdl_shapes,
)
from shifu_tpu.train.wdl_trainer import WDLTrainConfig, train_wdl


def _make_data(n=1500, dn=4, seed=0):
    """Signal in dense col 0 and categorical field 0 (vocab 5)."""
    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, dn)).astype(np.float32)
    codes = np.stack([
        rng.integers(0, 5, n), rng.integers(0, 3, n)
    ], axis=1).astype(np.int32)
    logits = dense[:, 0] * 1.5 + (codes[:, 0] >= 3) * 2.0 - 1.5
    t = (logits + rng.normal(scale=0.4, size=n) > 0).astype(np.float32)
    w = np.ones(n, np.float32)
    return dense, codes, t, w, [5, 3]


class TestForward:
    def test_flatten_roundtrip(self):
        p = init_wdl_params(3, [5, 4], 2, [8], seed=1)
        flat = flatten_wdl(p)
        p2 = unflatten_wdl(flat, p)
        np.testing.assert_allclose(p.embed[0], p2.embed[0])
        np.testing.assert_allclose(p.dense_layers[0]["W"], p2.dense_layers[0]["W"])
        np.testing.assert_allclose(p.bias, p2.bias)

    def test_forward_shape_and_range(self):
        import jax.numpy as jnp

        p = init_wdl_params(3, [5, 4], 2, [8], seed=1)
        dense = jnp.zeros((7, 3))
        codes = jnp.zeros((7, 2), jnp.int32)
        out = wdl_forward(p, dense, codes, ["relu"])
        assert out.shape == (7,)
        assert ((out >= 0) & (out <= 1)).all()

    def test_wide_tower_contributes(self):
        import jax.numpy as jnp

        p = init_wdl_params(1, [3], 2, [4], seed=1)
        p.wide[0] = np.asarray([0.0, 5.0, -5.0], np.float32)
        dense = jnp.zeros((3, 1))
        codes = jnp.asarray([[0], [1], [2]], jnp.int32)
        out = np.asarray(wdl_forward(p, dense, codes, ["relu"]))
        assert out[1] > out[0] > out[2]


def _two_lookup_forward(p, dense, codes, activations, logits_only=False):
    """The forward written out with two lookups a field, `embed[idx]` for
    the tower and `wide[idx]` for the wide sum, in plain `jax.numpy`: what
    `wdl_forward`'s one lookup a field (PR 33) has to equal, and the only
    copy of that loop the repo keeps."""
    import jax.numpy as jnp

    from shifu_tpu.models.nn import activation_fn

    pieces, wide_logit = [dense], dense @ jnp.asarray(p.wide_dense)
    for f in range(len(p.embed)):
        embed, wide = jnp.asarray(p.embed[f]), jnp.asarray(p.wide[f])
        idx = jnp.clip(codes[:, f], 0, embed.shape[0] - 1)
        pieces.append(embed[idx])
        wide_logit = wide_logit + wide[idx]
    h = jnp.concatenate(pieces, axis=1)
    for i, layer in enumerate(p.dense_layers[:-1]):
        act = activation_fn(activations[i % len(activations)])
        h = act(h @ layer["W"] + layer["b"])
    last = p.dense_layers[-1]
    logit = (h @ last["W"] + last["b"])[:, 0] + wide_logit + jnp.asarray(p.bias)[0]
    return logit if logits_only else 1.0 / (1.0 + jnp.exp(-logit))


def _trainer_loss(forward, shapes, n_cat, activations):
    """`wdl_trainer._get_program`'s `loss_fn` over a given forward."""
    import jax.numpy as jnp

    def loss(flat, dense, codes, t, sig):
        p = unflatten_wdl_from_shapes(flat, shapes, n_cat)
        pc = jnp.clip(forward(p, dense, codes, activations), 1e-7, 1 - 1e-7)
        return jnp.sum(sig * -(t * jnp.log(pc) + (1 - t) * jnp.log(1 - pc)))

    return loss


def _parity_case(embed_dim, hidden, seed=11, n=257, dn=3):
    """Tables of one row, of three and of seven; codes below 0 and past the
    last row in every field, so the clip is on the path."""
    vocab = [1, 3, 7]
    rng = np.random.default_rng(seed)
    p = init_wdl_params(dn, vocab, embed_dim, hidden, seed=seed)
    flat = flatten_wdl(p)
    flat = (flat + rng.normal(0, 0.3, flat.size)).astype(np.float32)
    dense = rng.normal(size=(n, dn)).astype(np.float32)
    codes = np.stack([rng.integers(-2, v + 3, n) for v in vocab],
                     axis=1).astype(np.int32)
    t = (rng.random(n) < 0.4).astype(np.float32)
    sig = rng.integers(0, 3, n).astype(np.float32)
    return unflatten_wdl(flat, p), flat, wdl_shapes(p), dense, codes, t, sig


class TestOneLookupAField:
    """`wdl_forward` reads a field's embedding row and its wide weight with
    one gather (PR 33): value and every gradient leaf against the two-lookup
    forward above."""

    @pytest.mark.parametrize("embed_dim,hidden,acts", [
        (1, [5], ["relu"]), (2, [6, 4], ["tanh", "relu"]), (8, [], ["relu"]),
        (8, [16], ["relu"])])
    @pytest.mark.parametrize("tables", ["host", "device"])
    def test_forward_and_every_gradient_leaf(self, embed_dim, hidden, acts,
                                             tables):
        import jax
        import jax.numpy as jnp

        p, flat, shapes, dense, codes, t, sig = _parity_case(embed_dim, hidden)
        assert codes.min() < 0 and (codes.max(axis=0) > [0, 2, 6]).all()
        if tables == "device":
            p = unflatten_wdl_from_shapes(jnp.asarray(flat), shapes, 3)
        for logits_only in (True, False):
            got = jax.jit(lambda d, c: wdl_forward(p, d, c, acts, logits_only))(
                dense, codes)
            want = _two_lookup_forward(p, dense, codes, acts, logits_only)
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        grads = [jax.jit(jax.grad(_trainer_loss(fwd, shapes, 3, acts)))(
            jnp.asarray(flat), dense, codes, t, sig)
            for fwd in (wdl_forward, _two_lookup_forward)]
        leaves = [unflatten_wdl_from_shapes(np.asarray(g), shapes, 3)
                  for g in grads]
        for i, (a, b) in enumerate(zip(*map(wdl_arrays, leaves))):
            assert a.shape == b.shape
            assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b), i
            assert np.linalg.norm(b) > 0, i

    @pytest.mark.parametrize("embed_dim", [1, 2, 8])
    def test_under_vmap_as_the_bagged_trainer_runs_it(self, embed_dim):
        import jax
        import jax.numpy as jnp

        p, flat, shapes, dense, codes, t, sig = _parity_case(embed_dim, [6])
        rng = np.random.default_rng(5)
        flats = jnp.asarray(np.stack(
            [flat, flat + rng.normal(0, 0.1, flat.size).astype(np.float32)]))
        sigs = jnp.asarray(np.stack([sig, sig[::-1]]))
        # train_wdl_bagged: members along the parameters and the draw, the
        # rows shared
        ga, gb = [jax.jit(jax.vmap(
            jax.grad(_trainer_loss(fwd, shapes, 3, ["relu"])),
            in_axes=(0, None, None, None, 0)))(flats, dense, codes, t, sigs)
            for fwd in (wdl_forward, _two_lookup_forward)]
        ends = np.cumsum([int(np.prod(shp)) for shp in shapes])[:-1]
        for shp, a, b in zip(shapes, np.split(np.asarray(ga), ends, axis=1),
                             np.split(np.asarray(gb), ends, axis=1)):
            assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b), shp


class TestTrain:
    def test_learns_both_towers(self):
        dense, codes, t, w, vocab = _make_data()
        cfg = WDLTrainConfig(hidden=[16], activations=["relu"], embed_dim=4,
                             learning_rate=0.02, num_epochs=150,
                             valid_set_rate=0.2, seed=1)
        res = train_wdl(dense, codes, t, w, vocab, cfg)
        assert res.valid_error < 0.12

    @pytest.mark.parametrize("model_axis", [1, 2])
    def test_mesh_matches_single(self, model_axis):
        """Rows over `data`; with a `model` axis the embedding tables are
        held to `P(None, "model")` before the forward puts a field's table
        together from them."""
        from shifu_tpu.parallel.mesh import data_mesh

        dense, codes, t, w, vocab = _make_data(n=260)
        cfg = WDLTrainConfig(hidden=[8], embed_dim=2, optimizer="ADAM",
                             learning_rate=0.05, num_epochs=15,
                             valid_set_rate=0.25, seed=3)
        r1 = train_wdl(dense, codes, t, w, vocab, cfg)
        r2 = train_wdl(dense, codes, t, w, vocab, cfg,
                       mesh=data_mesh(model_axis=model_axis))
        np.testing.assert_allclose(
            flatten_wdl(r1.params), flatten_wdl(r2.params), rtol=3e-3, atol=3e-4
        )

    def test_early_stop(self):
        dense, codes, t, w, vocab = _make_data(n=300)
        cfg = WDLTrainConfig(hidden=[8], embed_dim=2, learning_rate=0.1,
                             num_epochs=400, valid_set_rate=0.3,
                             early_stop_window=8, seed=5)
        res = train_wdl(dense, codes, t, w, vocab, cfg)
        assert res.iterations < 400


class TestSpec:
    def test_roundtrip_and_score(self, tmp_path):
        dense, codes, t, w, vocab = _make_data(n=400)
        cfg = WDLTrainConfig(hidden=[8], embed_dim=2, num_epochs=30, seed=7)
        res = train_wdl(dense, codes, t, w, vocab, cfg)
        spec = WDLModelSpec(
            hidden=[8], activations=["relu", "relu"], embed_dim=2,
            dense_columns=[f"n{i}" for i in range(4)],
            cat_columns=["c0", "c1"], vocab_sizes=vocab,
            categories=[["a", "b", "c", "d"], ["x", "y"]],
            params=res.params,
        )
        path = str(tmp_path / "model0.wdl")
        spec.save(path)
        loaded = WDLModelSpec.load(path)
        s1 = spec.independent().compute_parts(dense[:20], codes[:20])
        s2 = loaded.independent().compute_parts(dense[:20], codes[:20])
        np.testing.assert_allclose(s1, s2, atol=1e-6)

    def test_the_file_is_what_it_was_before_the_one_lookup(self, tmp_path):
        """PR 33 changed how the forward reads the tables, not what is
        stored: a `.wdl` saved from fixed parameters has the bytes PR 32's
        `save` wrote for them (the hash is from that commit's code), laid
        out as header then embed, wide, wide_dense, W and b a layer, bias;
        loaded again it scores to the two-lookup forward's values."""
        import hashlib
        import json
        import struct

        tpl = init_wdl_params(2, [1, 3], 2, [3])
        n = flatten_wdl(tpl).size
        flat = (((np.arange(n) * 37) % 101 - 50) / 64).astype(np.float32)
        spec = WDLModelSpec(
            hidden=[3], activations=["tanh"], embed_dim=2,
            dense_columns=["n0", "n1"], cat_columns=["c0", "c1"],
            vocab_sizes=[1, 3], categories=[[], ["a", "b"]],
            params=unflatten_wdl(flat, tpl), train_error=0.25,
            valid_error=0.5)
        path = str(tmp_path / "m.wdl")
        spec.save(path)
        with open(path, "rb") as fh:
            data = fh.read()
        assert hashlib.sha256(data).hexdigest() == (
            "0746b757dec62a7f0b1e80060714ca54aaf31c1965db0381a16a6f9c8ba5a572")
        (hlen,) = struct.unpack("<I", data[4:8])
        assert data[:4] == b"STWD" and data[8 + hlen:] == flat.tobytes()
        assert json.loads(data[8:8 + hlen])["shapes"] == [
            [1, 2], [3, 2], [1], [3], [2], [6, 3], [3], [3, 1], [1], [1]]
        p = spec.params
        assert np.array_equal(p.embed[1], flat[2:8].reshape(3, 2))
        assert np.array_equal(p.wide[1], flat[9:12])
        rng = np.random.default_rng(2)
        dense = rng.normal(size=(40, 2)).astype(np.float32)
        codes = rng.integers(-1, 5, (40, 2)).astype(np.int32)
        got = WDLModelSpec.load(path).independent().compute_parts(dense, codes)
        want = _two_lookup_forward(p, dense, codes, ["tanh"])
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


class TestProcessor:
    def test_end_to_end_wdl(self, tmp_path):
        from tests.helpers import make_model_set

        root = str(tmp_path / "ms")
        make_model_set(root, n_rows=500, algorithm="WDL")
        from shifu_tpu.config.model_config import ModelConfig
        from shifu_tpu.processor.evaluate import EvalProcessor
        from shifu_tpu.processor.init import InitProcessor
        from shifu_tpu.processor.norm import NormProcessor
        from shifu_tpu.processor.stats import StatsProcessor
        from shifu_tpu.processor.train import TrainProcessor

        mc = ModelConfig.load(os.path.join(root, "ModelConfig.json"))
        mc.train.num_train_epochs = 60
        mc.train.params["NumHiddenNodes"] = [16]
        mc.train.params["ActivationFunc"] = ["relu"]
        mc.train.params["LearningRate"] = 0.02
        mc.evals[0].data_set.data_path = mc.data_set.data_path
        mc.evals[0].data_set.header_path = mc.data_set.header_path
        mc.save(os.path.join(root, "ModelConfig.json"))
        assert InitProcessor(root).run() == 0
        assert StatsProcessor(root).run() == 0
        assert NormProcessor(root).run() == 0
        assert TrainProcessor(root).run() == 0
        model_path = os.path.join(root, "models", "model0.wdl")
        assert os.path.isfile(model_path)
        spec = WDLModelSpec.load(model_path)
        assert spec.cat_columns == ["cat_0", "cat_1"]
        assert len(spec.dense_columns) == 10

        assert EvalProcessor(root, run_name="").run() == 0
        import json

        with open(os.path.join(root, "evals", "Eval1",
                               "EvalPerformance.json")) as fh:
            perf = json.load(fh)
        assert perf["areaUnderRoc"] > 0.9


class TestWDLFirstClass:
    """WDL promoted to NN-equal treatment: vmapped bagging, grid search,
    k-fold, continuous training (TrainModelProcessor.java:768-945)."""

    def _pipeline_root(self, tmp_path, **train_kw):
        from tests.helpers import make_model_set

        root = str(tmp_path / "ms")
        make_model_set(root, n_rows=400, algorithm="WDL")
        from shifu_tpu.config.model_config import ModelConfig
        from shifu_tpu.processor.init import InitProcessor
        from shifu_tpu.processor.norm import NormProcessor
        from shifu_tpu.processor.stats import StatsProcessor

        assert InitProcessor(root).run() == 0
        assert StatsProcessor(root).run() == 0
        assert NormProcessor(root).run() == 0
        mc = ModelConfig.load(os.path.join(root, "ModelConfig.json"))
        mc.train.num_train_epochs = 25
        mc.train.params.update({"NumHiddenNodes": [16], "ActivationFunc": ["relu"],
                                "LearningRate": 0.01})
        for k, v in train_kw.items():
            if k == "params":
                mc.train.params.update(v)
            else:
                setattr(mc.train, k, v)
        mc.save(os.path.join(root, "ModelConfig.json"))
        return root

    def test_bagged_wdl(self, tmp_path):
        from shifu_tpu.processor.train import TrainProcessor

        root = self._pipeline_root(tmp_path, bagging_num=3)
        assert TrainProcessor(root).run() == 0
        from shifu_tpu.models.wdl import WDLModelSpec

        for i in range(3):
            path = os.path.join(root, "models", f"model{i}.wdl")
            assert os.path.isfile(path)
            spec = WDLModelSpec.load(path)
            assert spec.valid_error is not None
            assert os.path.isfile(
                os.path.join(root, "tmp", "train", f"progress_{i}.log"))
        # members differ (independent seeds/samples)
        a = WDLModelSpec.load(os.path.join(root, "models", "model0.wdl"))
        b = WDLModelSpec.load(os.path.join(root, "models", "model1.wdl"))
        assert not np.allclose(a.params.bias, b.params.bias) or not np.allclose(
            a.params.wide_dense, b.params.wide_dense)

    def test_wdl_grid_search(self, tmp_path):
        from shifu_tpu.processor.train import TrainProcessor

        root = self._pipeline_root(
            tmp_path, params={"LearningRate": [0.002, 0.01, 0.05]})
        assert TrainProcessor(root).run() == 0
        assert os.path.isfile(os.path.join(root, "models", "model0.wdl"))

    def test_wdl_k_fold(self, tmp_path):
        from shifu_tpu.processor.train import TrainProcessor

        root = self._pipeline_root(tmp_path, num_k_fold=3)
        assert TrainProcessor(root).run() == 0
        for i in range(3):
            assert os.path.isfile(
                os.path.join(root, "models", f"model{i}.wdl"))

    def test_wdl_continuous(self, tmp_path):
        from shifu_tpu.processor.train import TrainProcessor

        root = self._pipeline_root(tmp_path)
        assert TrainProcessor(root).run() == 0
        from shifu_tpu.models.wdl import WDLModelSpec

        first = WDLModelSpec.load(os.path.join(root, "models", "model0.wdl"))
        from shifu_tpu.config.model_config import ModelConfig

        mc = ModelConfig.load(os.path.join(root, "ModelConfig.json"))
        mc.train.is_continuous = True
        mc.train.num_train_epochs = 1  # barely moves off the loaded weights
        mc.save(os.path.join(root, "ModelConfig.json"))
        assert TrainProcessor(root).run() == 0
        second = WDLModelSpec.load(os.path.join(root, "models", "model0.wdl"))
        # resumed from the first model's weights, not re-initialized: one
        # epoch at lr=0.01 stays near the trained weights, while a fresh
        # Xavier init would differ wholesale
        from shifu_tpu.models.wdl import flatten_wdl, init_wdl_params

        f1 = flatten_wdl(first.params)
        f2 = flatten_wdl(second.params)
        fresh = flatten_wdl(init_wdl_params(
            len(first.dense_columns), first.vocab_sizes, first.embed_dim,
            first.hidden, seed=23))
        drift = float(np.linalg.norm(f2 - f1))
        scratch_dist = float(np.linalg.norm(fresh - f1))
        assert drift < 0.25 * scratch_dist, (drift, scratch_dist)


def test_wdl_streamed_training(tmp_path):
    """train.trainOnDisk=true streams WDL from the shard pairs and still
    learns (train/streaming_wdl.py)."""
    from tests.helpers import make_model_set

    root = str(tmp_path / "ms")
    make_model_set(root, n_rows=400, algorithm="WDL")
    from shifu_tpu.config.model_config import ModelConfig
    from shifu_tpu.processor.init import InitProcessor
    from shifu_tpu.processor.norm import NormProcessor
    from shifu_tpu.processor.stats import StatsProcessor
    from shifu_tpu.processor.train import TrainProcessor

    assert InitProcessor(root).run() == 0
    assert StatsProcessor(root).run() == 0
    assert NormProcessor(root).run() == 0
    mc = ModelConfig.load(os.path.join(root, "ModelConfig.json"))
    mc.train.num_train_epochs = 30
    mc.train.train_on_disk = True
    mc.train.params.update({"NumHiddenNodes": [16],
                            "ActivationFunc": ["relu"]})
    mc.save(os.path.join(root, "ModelConfig.json"))
    assert TrainProcessor(root).run() == 0

    from shifu_tpu.models.wdl import WDLModelSpec

    spec = WDLModelSpec.load(os.path.join(root, "models", "model0.wdl"))
    assert spec.valid_error is not None and spec.valid_error < 0.25
    assert os.path.isfile(os.path.join(root, "tmp", "train",
                                       "progress_0.log"))


def test_wdl_streamed_mesh_matches_single_device(tmp_path):
    """Streamed WDL composes with the mesh: row-sharded shard pairs, shard
    gradients psum'd — same trajectory as the single-device stream."""
    import numpy as np

    from shifu_tpu.norm.dataset import write_codes, write_normalized
    from shifu_tpu.parallel.mesh import data_mesh
    from shifu_tpu.train.streaming_wdl import train_wdl_streamed
    from shifu_tpu.train.wdl_trainer import WDLTrainConfig

    rng = np.random.default_rng(5)
    n, nd, nc, vocab = 1200, 4, 2, 6
    dense = rng.normal(size=(n, nd)).astype(np.float32)
    codes = rng.integers(0, vocab, size=(n, nc)).astype(np.int16)
    t = ((dense[:, 0] + (codes[:, 0] >= 3)) > 0.5).astype(np.int8)
    w = np.ones(n, np.float32)
    norm_dir = str(tmp_path / "NormalizedData")
    codes_dir = str(tmp_path / "CleanedData")
    cols = [f"d{i}" for i in range(nd)] + [f"c{i}" for i in range(nc)]
    write_normalized(norm_dir, np.concatenate(
        [dense, codes.astype(np.float32)], 1), t, w, cols, n_shards=3)
    write_codes(codes_dir, np.concatenate(
        [np.zeros((n, nd), np.int16), codes], 1), t, w, cols,
        [1] * nd + [vocab] * nc, n_shards=3)
    cfg = WDLTrainConfig(hidden=[8], activations=["relu"], embed_dim=4,
                         num_epochs=10, valid_set_rate=0.2, seed=3)
    num_idx = list(range(nd))
    cat_idx = [nd, nd + 1]
    single = train_wdl_streamed(norm_dir, codes_dir, num_idx, cat_idx,
                                [vocab] * nc, cfg)
    meshed = train_wdl_streamed(norm_dir, codes_dir, num_idx, cat_idx,
                                [vocab] * nc, cfg, mesh=data_mesh())
    assert meshed.iterations == single.iterations
    assert meshed.valid_error == pytest.approx(single.valid_error,
                                               abs=1e-4)
    np.testing.assert_allclose(meshed.params.embed[0],
                               single.params.embed[0], atol=1e-4)
