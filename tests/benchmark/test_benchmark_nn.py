"""The NN cell's harness on the CPU at 2,000 rows: a sound run is correct, the
lower-precision control is not, and a timed path broken underneath is not."""

import json

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import spec

CELL = "higgs_nn.train_fullbatch"
ROWS = 2000


def _run(seed=11, **kw):
    return run.run_cell(CELL, seed, 0.2, False, require_chip=False,
                        rows=ROWS, **kw)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_result_line_shape(sound):
    assert list(sound)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(sound)
    assert set(sound["metrics"]) == {"nn_row_epochs_per_s", "setup_s"}
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert sound["device"]["platform"] == "cpu"
    json.dumps(sound)  # one JSON object


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    for name, item in sound["compared"].items():
        assert np.isfinite(item["value"]), name


def test_every_limit_the_cell_holds_is_reported(sound):
    limits = spec.Cell(CELL).traffic["limits"]
    held = {k for k, v in limits.items() if v is not None}
    assert set(sound["compared"]) == held == {"grad_gap", "flip_share",
                                              "entry_gap"}
    assert set(sound["not_compared"]) == set(limits) - held


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_control_is_not_correct(seed):
    out = _run(seed=seed, control=True)
    assert out["correct"] is False
    c = out["compared"]
    failed = [k for k, v in c.items()
              if v["limit"] is not None and v["value"] > v["limit"]]
    assert failed, c


def _fresh_trainer():
    from shifu_tpu.train import nn_trainer

    nn_trainer._PROGRAMS.clear()
    nn_trainer._SAMPLE_CACHE.clear()
    return nn_trainer


def test_fault_state_left_unchanged(monkeypatch):
    tr = _fresh_trainer()
    real = tr.make_updater

    def broken(*a, **kw):
        init, _apply = real(*a, **kw)
        return init, lambda state, w, g, lr, it, nts: (w, state)

    monkeypatch.setattr(tr, "make_updater", broken)
    try:
        out = _run()
    finally:
        _fresh_trainer()
    assert out["correct"] is False
    assert out["compared"]["flip_share"]["value"] == pytest.approx(1.0)
    assert out["not_compared"]["change1_gap"] == pytest.approx(1.0)


def test_fault_half_the_batch_left_out(monkeypatch):
    tr = _fresh_trainer()
    real = tr._device_split_and_sample

    def broken(n, cfg):
        sig, valid, nts = real(n, cfg)
        keep = (np.arange(n) % 2 == 0).astype(np.float32)
        return sig * keep, valid, max(nts / 2.0, 1.0)

    monkeypatch.setattr(tr, "_device_split_and_sample", broken)
    try:
        out = _run()
    finally:
        _fresh_trainer()
    assert out["correct"] is False
    assert out["compared"]["grad_gap"]["value"] > 0.3


# ---- the plain reference on its own ----

@pytest.fixture(scope="module")
def ref():
    return spec.load_module("references", "mlp_rprop")


def test_reference_draw_is_the_trainers_draw(ref):
    from shifu_tpu.train.nn_trainer import NNTrainConfig, split_and_sample

    sig, valid = split_and_sample(5000, NNTrainConfig(seed=9,
                                                      valid_set_rate=0.2))
    rs, rv = ref.split_rows(5000, 9, 0.2)
    assert np.array_equal(sig, rs) and np.array_equal(valid, rv > 0)


def test_reference_flat_layout_is_the_trainers(ref):
    from shifu_tpu.models.nn import flatten_params, unflatten_params

    sizes = [4, 3, 1]
    flat = ref.xavier_flat(sizes, 5)
    leaves = ref.leaves_of(flat, sizes)
    params = unflatten_params(flat, [(4, 3), (3, 1)])
    assert np.array_equal(leaves[0], params[0]["W"])
    assert np.array_equal(leaves[3], params[1]["b"])
    assert np.array_equal(flatten_params(params)[0], flat)
    assert not leaves[1].any() and np.abs(leaves[0]).max() <= np.sqrt(6 / 7)


def test_reference_rprop_by_hand(ref):
    import jax.numpy as jnp

    st = {"step": jnp.full(3, 0.1), "last_g": jnp.zeros(3),
          "last_delta": jnp.zeros(3)}
    w = jnp.zeros(3)
    w, st = ref._rprop(st, w, jnp.array([2.0, -3.0, 0.0]))
    assert np.allclose(w, [0.1, -0.1, 0.0])
    # same sign: the step grows by 1.2; a turn: the last change is taken back
    w, st = ref._rprop(st, w, jnp.array([1.0, 3.0, 0.0]))
    assert np.allclose(w, [0.1 + 0.12, 0.0, 0.0])
    assert np.allclose(st["step"], [0.12, 0.05, 0.1])
    assert np.allclose(st["last_g"], [1.0, 0.0, 0.0])


def test_reference_blocks_do_not_change_the_answer(ref):
    import jax

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (600, 5))
    t = (x[:, 0] > 0).astype("float32")
    w = np.ones(600, np.float32)
    sizes = [5, 8, 1]
    flat0 = ref.xavier_flat(sizes, 1)
    a = ref.first_steps(x, t, w, flat0, sizes, 1, 0.2, steps=2)
    b = ref.first_steps(x, t, w, flat0, sizes, 1, 0.2, steps=2,
                        block_rows=100)
    assert np.allclose(a["losses"], b["losses"], rtol=1e-5)
    for p, q in zip(a["grad1"], b["grad1"]):
        assert np.allclose(p, q, rtol=1e-4, atol=1e-6)
