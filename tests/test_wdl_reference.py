"""`train_wdl` against the benchmark's plain reference
(benchmarks/references/wdl_adam.py, nothing of shifu_tpu) on seeded random
weights, small, on the CPU: logits, the first gradient leaf by leaf, three
ADAM steps; and the reference's own rules against the trainer's."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmarks.lib import spec  # noqa: E402
from shifu_tpu.models import wdl  # noqa: E402
from shifu_tpu.obs import profile  # noqa: E402
from shifu_tpu.train import wdl_trainer as wt  # noqa: E402

N, N_DENSE, VOCAB, EMBED, HIDDEN = 1500, 5, [7, 40, 3, 12], 4, [16, 8]


@pytest.fixture(scope="module")
def ref():
    return spec.load_module("references", "wdl_adam")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(21)
    dense = rng.normal(size=(N, N_DENSE)).astype(np.float32)
    codes = np.stack([rng.integers(0, v, N) for v in VOCAB], 1).astype(
        np.int32)
    t = (dense[:, 0] + 0.5 * (codes[:, 1] % 3) + rng.normal(size=N)
         > 0.5).astype(np.float32)
    w = rng.uniform(0.5, 1.5, N).astype(np.float32)
    return dense, codes, t, w


def _random_flat(ref, seed):
    """Every leaf random, the wide weights and biases too (the trainer's own
    start has them 0, which would hide a wide part left out)."""
    rng = np.random.default_rng(seed)
    n = ref.start_flat(N_DENSE, VOCAB, EMBED, HIDDEN, 0).size
    return rng.normal(0, 0.3, n).astype(np.float32)


def test_start_and_layout_are_the_trainers(ref):
    tpl = wdl.init_wdl_params(N_DENSE, VOCAB, EMBED, HIDDEN, seed=13)
    flat = ref.start_flat(N_DENSE, VOCAB, EMBED, HIDDEN, 13)
    assert np.array_equal(flat, wdl.flatten_wdl(tpl))
    shapes = ref.leaf_shapes(N_DENSE, VOCAB, EMBED, HIDDEN)
    assert shapes == [tuple(s) for s in wdl.wdl_shapes(tpl)]
    for a, b in zip(ref.leaves_of(flat, shapes), wdl.wdl_arrays(tpl)):
        assert np.array_equal(a, b)


def test_draw_is_the_trainers(ref):
    from shifu_tpu.train.nn_trainer import split_and_sample

    sig, valid = split_and_sample(5000, wt.WDLTrainConfig(
        seed=9, valid_set_rate=0.2))
    rs, rv = ref.split_rows(5000, 9, 0.2)
    assert np.array_equal(sig, rs) and np.array_equal(valid, rv > 0)


def test_logits_are_the_models(ref, data):
    dense, codes, t, w = data
    flat = _random_flat(ref, 3)
    shapes = ref.leaf_shapes(N_DENSE, VOCAB, EMBED, HIDDEN)
    tpl = wdl.init_wdl_params(N_DENSE, VOCAB, EMBED, HIDDEN)
    p = wdl.unflatten_wdl(jnp.asarray(flat), tpl)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(wdl.wdl_forward(p, dense, codes, ["relu", "relu"]))
    # the reference hands out no logits: one block's squared errors against
    # the model's probabilities say the same
    block = ref._make_block_fn(len(VOCAB), EMBED, False, None)
    one = jnp.ones(N)
    _g, tr, _va = block(ref.leaves_of(jnp.asarray(flat), shapes), dense,
                        codes, t, one, one)
    assert float(tr) == pytest.approx(float(np.sum((t - want) ** 2)),
                                      rel=1e-5)


@pytest.fixture(scope="module")
def both(ref, data):
    """Three epochs of `train_wdl` with the state tapped at its seam after
    each, and the reference's three steps from the same start."""
    dense, codes, t, w = data
    flat0 = _random_flat(ref, 4)
    seen, real = [], profile.dispatch

    def tap(name, fn, *a, **kw):
        out = real(name, fn, *a, **kw)
        if name == "wdl.train_program":
            seen.append(out)
        return out

    profile.dispatch = tap
    try:
        with jax.default_matmul_precision("highest"):
            for k in (1, 2, 3):
                cfg = wt.WDLTrainConfig(
                    hidden=HIDDEN, embed_dim=EMBED, num_epochs=k, seed=6,
                    valid_set_rate=0.2, l2_reg=0.01)
                wt.train_wdl(dense, codes, t, w, VOCAB, cfg, init_flat=flat0)
    finally:
        profile.dispatch = real
        wt._PROGRAMS.clear()
    shapes = ref.leaf_shapes(N_DENSE, VOCAB, EMBED, HIDDEN)
    want = ref.first_steps(dense, codes, t, jnp.asarray(w), flat0, shapes, 6,
                           0.2, 0.005, l2_reg=0.01, steps=3, block_rows=400)
    return seen, want, shapes


def test_first_gradient_leaf_by_leaf(ref, both):
    seen, want, shapes = both
    # ADAM's first m is (1 - beta1) x (descent direction - L2's part)
    m = np.asarray(seen[0][1]["m"]) / np.float32(0.1)
    flat0 = want["flats"][0]
    n_train = ref.split_rows(N, 6, 0.2)[0].sum()
    g = m + 0.01 * flat0 / n_train
    for i, (a, b) in enumerate(zip(ref.leaves_of(g, shapes), want["grad1"])):
        scale = max(np.abs(b).max(), 1e-6)
        assert np.abs(a - b).max() <= 2e-4 * scale, i


def test_three_adam_steps(both):
    seen, want, _shapes = both
    for k in range(3):
        flat, _opt, it, _bv, _bf, _bad, _halt, tr, va = seen[k]
        assert int(it) == k + 1
        assert np.allclose(np.asarray(flat), want["flats"][k + 1],
                           rtol=0, atol=2e-5), k
        assert float(tr) == pytest.approx(want["losses"][k][0], rel=1e-5)
        assert float(va) == pytest.approx(want["losses"][k][1], rel=1e-5)
    # every weight with a gradient moved by about lr at the first step
    step1 = np.abs(want["flats"][1] - want["flats"][0])
    assert np.median(step1[step1 > 0]) == pytest.approx(0.005, rel=0.05)


def test_blocks_do_not_change_the_answer(ref, data):
    dense, codes, t, w = data
    flat0 = _random_flat(ref, 5)
    shapes = ref.leaf_shapes(N_DENSE, VOCAB, EMBED, HIDDEN)
    a = ref.first_steps(dense, codes, t, jnp.asarray(w), flat0, shapes, 1,
                        0.2, 0.005, steps=2)
    b = ref.first_steps(dense, codes, t, jnp.asarray(w), flat0, shapes, 1,
                        0.2, 0.005, steps=2, block_rows=333)
    assert np.allclose(a["losses"], b["losses"], rtol=1e-5)
    for p, q in zip(a["grad1"], b["grad1"]):
        assert np.allclose(p, q, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fault", ["half", "embed_grad_dropped",
                                   "codes_shifted", "wide_skipped"])
def test_each_planted_fault_changes_the_first_gradient(ref, data, fault):
    dense, codes, t, w = data
    flat0 = _random_flat(ref, 5)
    shapes = ref.leaf_shapes(N_DENSE, VOCAB, EMBED, HIDDEN)
    kw = dict(steps=1)
    a = ref.first_steps(dense, codes, t, jnp.asarray(w), flat0, shapes, 1,
                        0.2, 0.005, **kw)
    b = ref.first_steps(dense, codes, t, jnp.asarray(w), flat0, shapes, 1,
                        0.2, 0.005, fault=fault, **kw)
    assert fault in ref.FAULTS
    worst = max(np.abs(p - q).max() / max(np.abs(p).max(), 1e-9)
                for p, q in zip(a["grad1"], b["grad1"]))
    assert worst > 0.2
