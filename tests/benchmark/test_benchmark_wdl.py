"""The WDL cell's harness on the CPU at 2,000 rows, with the tables cut by the
cell's own rule (min(c, cap) + 1) at a cap of 50: a sound run is correct, the
lower-precision control is not, and the timed path broken underneath (rows
left out, one column's embedding gradient dropped, one column's codes
shifted, the wide part skipped) is not, each by the number meant to catch
it."""

import json

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import spec

CELL = "criteo_wdl.train_fullbatch"
ROWS, CAP = 2000, 50


def _cut_tables(mp):
    real = spec.Cell.__init__

    def init(self, name):
        real(self, name)
        if name == CELL:
            self.config = dict(self.config, category_cap=CAP)

    mp.setattr(spec.Cell, "__init__", init)


@pytest.fixture(autouse=True)
def small_tables(monkeypatch):
    _cut_tables(monkeypatch)


def _run(seed=11, **kw):
    return run.run_cell(CELL, seed, 0.2, False, require_chip=False,
                        rows=ROWS, **kw)


def _fresh_trainer():
    from shifu_tpu.train import nn_trainer, wdl_trainer

    wdl_trainer._PROGRAMS.clear()
    nn_trainer._SAMPLE_CACHE.clear()
    return wdl_trainer


@pytest.fixture(scope="module")
def sound():
    with pytest.MonkeyPatch.context() as mp:  # module scope: its own patch
        _cut_tables(mp)
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
    assert sound["compared"]["entry_gap"]["value"] == 0.0


def test_every_limit_the_cell_holds_is_reported(sound):
    limits = spec.Cell(CELL).traffic["limits"]
    held = {k for k, v in limits.items() if v is not None}
    assert {"grad_gap", "flip_share", "entry_gap", "change3_gap"} <= held
    assert set(sound["compared"]) == held
    assert set(sound["not_compared"]) == set(limits) - held


def _over(out):
    return {k for k, v in out["compared"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_control_is_not_correct(seed):
    out = _run(seed=seed, control=True)
    assert out["correct"] is False
    assert {"grad_gap", "flip_share"} & _over(out), out["compared"]


def _forward_with(change):
    """`wdl_forward` with its parameters or codes changed first."""
    from shifu_tpu.models import wdl

    def forward(p, dense, codes, *a, **kw):
        p, codes = change(p, codes)
        return wdl.wdl_forward(p, dense, codes, *a, **kw)

    return forward


def _embed_grad_dropped(p, codes):
    import jax

    p.embed = [jax.lax.stop_gradient(p.embed[0])] + list(p.embed[1:])
    return p, codes


def _codes_shifted(p, codes):
    return p, codes.at[:, 0].add(1)


def _wide_skipped(p, codes):
    import jax.numpy as jnp

    p.wide = [jnp.zeros_like(t) for t in p.wide]
    return p, codes


@pytest.mark.parametrize("change,caught_by", [
    (_embed_grad_dropped, "grad_gap"),
    (_codes_shifted, "leaf_flip_share"),
    (_wide_skipped, "grad_gap"),
], ids=["embed_grad_dropped", "codes_shifted", "wide_skipped"])
def test_fault_in_the_forward_pass(monkeypatch, change, caught_by):
    tr = _fresh_trainer()
    monkeypatch.setattr(tr, "wdl_forward", _forward_with(change))
    try:
        out = _run()
    finally:
        _fresh_trainer()
    assert out["correct"] is False
    assert caught_by in _over(out), out["compared"]


def test_fault_half_the_rows_left_out(monkeypatch):
    from shifu_tpu.train import nn_trainer

    _fresh_trainer()
    real = nn_trainer._device_split_and_sample

    def broken(n, cfg):
        sig, valid, nts = real(n, cfg)
        keep = (np.arange(n) % 2 == 0).astype(np.float32)
        return sig * keep, valid, max(nts / 2.0, 1.0)

    monkeypatch.setattr(nn_trainer, "_device_split_and_sample", broken)
    try:
        out = _run()
    finally:
        _fresh_trainer()
    assert out["correct"] is False
    assert out["compared"]["grad_gap"]["value"] > 0.3


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


def _moments_dropped(apply):
    """ADAM's moments not carried: every step starts from noughts."""
    import jax
    import jax.numpy as jnp

    def broken(state, w, g, lr, it, nts):
        fresh = jax.tree.map(
            lambda a: jnp.where(it > 1, jnp.zeros_like(a), a), state)
        return apply(fresh, w, g, lr, it, nts)

    return broken


def _held_after_1(apply):
    """The weights held after the first epoch while `it` still counts."""
    import jax.numpy as jnp

    def broken(state, w, g, lr, it, nts):
        new_w, new_state = apply(state, w, g, lr, it, nts)
        return jnp.where(it > 1, w, new_w), new_state

    return broken


@pytest.mark.parametrize("breaker", [_moments_dropped, _held_after_1],
                         ids=["moments_dropped", "held_after_1"])
def test_fault_in_the_update_from_the_second_step_on(monkeypatch, breaker):
    """The first step is sound, so no first-step number moves: the change
    over the three steps is what catches a later step that is wrong."""
    tr = _fresh_trainer()
    real = tr.make_updater

    def broken(*a, **kw):
        init, apply = real(*a, **kw)
        return init, breaker(apply)

    monkeypatch.setattr(tr, "make_updater", broken)
    try:
        out = _run()
    finally:
        _fresh_trainer()
    assert out["correct"] is False
    over = _over(out)
    assert "change3_gap" in over, out["compared"]
    assert not over & {"grad_gap", "flip_share", "leaf_flip_share",
                       "loss1_gap"}, out["compared"]


@pytest.mark.parametrize("fault,caught_by", [
    ("half", "grad_gap"), ("embed_grad_dropped", "grad_gap"),
    ("codes_shifted", "leaf_flip_share"), ("wide_skipped", "grad_gap"),
    ("moments_dropped", "change3_gap"), ("held_after_1", "change3_gap")])
def test_reference_with_a_fault_planted_fails_by_its_number(fault, caught_by):
    """What `calibrate.py --faults` reads on the chip: the reference with the
    fault, in the program's place."""
    cell = spec.Cell(CELL)
    drv = spec.load_module("drivers", "wdl_fullbatch").setup(cell, 7, ROWS)
    drv.program = {}
    got = drv.compared(fault=fault)
    assert got[caught_by]["value"] > got[caught_by]["limit"], got


# ---- the configuration and the data ----

def test_config_tables_follow_the_cap_rule():
    cfg = spec.load_json("configs", "criteo_wdl.json")
    drv = spec.load_module("drivers", "wdl_fullbatch")
    cards = cfg["published_cardinalities"]
    assert len(cards) == cfg["categorical_columns"] == 26
    assert sum(cards) == 33_762_577
    assert drv.vocab_sizes(cfg) == cfg["vocab_sizes"]
    assert sum(cfg["vocab_sizes"]) == cfg["table_rows"] == 119_915
    assert sum(c > cfg["category_cap"] for c in cards) == 10
    ref = spec.load_module("references", "wdl_adam")
    shapes = ref.leaf_shapes(13, cfg["vocab_sizes"], 8, [100, 50])
    assert sum(int(np.prod(s)) for s in shapes) == cfg["parameters"] \
        == 1_106_550


def test_data_is_the_seeds_and_folds_the_tail():
    cfg = dict(spec.load_json("configs", "criteo_wdl.json"), category_cap=CAP)
    drv = spec.load_module("drivers", "wdl_fullbatch")
    dense, codes, t, w = (np.asarray(a) for a in drv._make_data(4000, cfg, 9))
    again = drv._make_data(4000, cfg, 9)
    assert np.array_equal(codes, np.asarray(again[1]))
    assert not np.array_equal(
        codes, np.asarray(drv._make_data(4000, cfg, 10)[1]))
    assert np.abs(dense).max() <= 4.0 and dense.shape == (4000, 13)
    vocab = drv.vocab_sizes(cfg)
    assert codes.min() == 0 and (codes.max(axis=0) < vocab).all()
    # a capped column's tail lands on the slot after the last kept category,
    # a hot key; an uncapped column never reaches its missing slot
    assert np.mean(codes[:, 2] == CAP) > 0.3
    assert codes[:, 8].max() == 2 and vocab[8] == 4
    assert 0.15 < t.mean() < 0.4 and (w == 1).all()
