"""The small tables' embedding gradient as a one-hot contraction
(ops/onehot_grad_pallas.py, in Pallas interpret mode here): the table
gradients against the gather's own transpose, the precision the three
bfloat16 terms keep, which fields and which trainers take the path, and
`train_wdl` with it against `train_wdl` without."""

import numpy as np
import pytest

from shifu_tpu.models.wdl import (
    flatten_wdl,
    init_wdl_params,
    unflatten_wdl,
    wdl_forward,
)
from shifu_tpu.obs import registry
from shifu_tpu.ops import onehot_grad_pallas as og
from shifu_tpu.train import wdl_trainer as wt
from shifu_tpu.utils import environment

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

COUNTER = "wdl.lookup.onehot_fields"


@pytest.fixture
def kernel_on():
    """`-Dshifu.pallas.mode=on`: the kernel in interpret mode off a TPU."""
    environment.set_property("shifu.pallas.mode", "on")
    try:
        yield
    finally:
        environment.set_property("shifu.pallas.mode", "")


def _fired():
    return registry().counter(COUNTER).value


def _case(vocab, n, seed=0, width=9, valid=None):
    rng = np.random.default_rng(seed)
    tables = [jnp.asarray(rng.normal(size=(v, width)).astype(np.float32))
              for v in vocab]
    # codes below 0 and past the last row in every field, and each field's
    # first and last row named outright
    codes = np.stack([rng.integers(-3, v + 3, n) for v in vocab], axis=1)
    codes[:2] = [[0] * len(vocab), [v - 1 for v in vocab]]
    codes = jnp.asarray(codes.astype(np.int32))
    cots = [rng.normal(size=(n, width)).astype(np.float32) for _ in vocab]
    if valid is not None:  # validation rows: sig 0, so a zero cotangent
        cots = [c * valid[:, None] for c in cots]
    return tables, codes, [jnp.asarray(c) for c in cots]


def _grads(tables, codes, cots, onehot: bool):
    vocab = [int(t.shape[0]) for t in tables]
    fields = tuple(range(len(vocab)))
    idxs = [jnp.clip(codes[:, f], 0, v - 1) for f, v in enumerate(vocab)]

    def loss(tbs):
        if onehot:
            outs = og.lookups(tbs, idxs, og.codes_operand(codes, fields,
                                                          vocab), True)
        else:
            outs = [tb[ix] for tb, ix in zip(tbs, idxs)]
        return sum(jnp.sum(o * c) for o, c in zip(outs, cots))

    return jax.jit(jax.grad(loss))(tables), idxs


def _bound(idxs, cots, vocab):
    """Per table entry, f32 rounding of a sum of its contributions: 1e-6
    of the sum of their magnitudes."""
    return [1e-6 * np.asarray(jnp.zeros((v, c.shape[1])).at[ix].add(
        jnp.abs(c))) + 1e-30 for ix, c, v in zip(idxs, cots, vocab)]


@pytest.mark.parametrize("n", [300, 1300], ids=["one_block", "partial_block"])
@pytest.mark.parametrize("vocab", [[1, 4, 127], [128, 129], [4096],
                                   [1, 4, 127, 128, 129, 4096]],
                         ids=["1_4_127", "128_129", "4096", "all"])
def test_table_grads_equal_the_gathers_transpose(vocab, n):
    tables, codes, cots = _case(vocab, n, seed=n + len(vocab))
    got, idxs = _grads(tables, codes, cots, onehot=True)
    want, _ = _grads(tables, codes, cots, onehot=False)
    for f, (a, b, tol) in enumerate(zip(got, want,
                                        _bound(idxs, cots, vocab))):
        assert a.shape == b.shape == (vocab[f], 9)
        assert np.all(np.abs(np.asarray(a) - np.asarray(b)) <= tol), f


@pytest.mark.parametrize("width", [8, 17, 33],
                         ids=["terms_fill_the_rows", "embed16", "embed32"])
def test_table_grads_at_other_widths(width):
    """A tile's accumulator rows follow the width (`term_rows`): 24 at
    width 8, with no padding, 56 at an embedding of 16 and 104 at 32."""
    vocab, n = [1, 130, 4096], 1300
    tables, codes, cots = _case(vocab, n, seed=width, width=width)
    got, idxs = _grads(tables, codes, cots, onehot=True)
    want, _ = _grads(tables, codes, cots, onehot=False)
    assert og.term_rows(width) == {8: 24, 17: 56, 33: 104}[width]
    for f, (a, b, tol) in enumerate(zip(got, want,
                                        _bound(idxs, cots, vocab))):
        assert a.shape == b.shape == (vocab[f], width)
        assert np.all(np.abs(np.asarray(a) - np.asarray(b)) <= tol), f


def test_zero_cotangents_from_validation_rows():
    n, vocab = 1300, [5, 300]
    valid = (np.arange(n) % 5 != 0).astype(np.float32)
    tables, codes, cots = _case(vocab, n, seed=3, valid=valid)
    got, idxs = _grads(tables, codes, cots, onehot=True)
    want, _ = _grads(tables, codes, cots, onehot=False)
    for a, b, tol in zip(got, want, _bound(idxs, cots, vocab)):
        assert np.all(np.abs(np.asarray(a) - np.asarray(b)) <= tol)
    # a code only validation rows hold has a gradient of exactly 0
    only_valid = np.asarray(codes)[valid == 0, 1].clip(0, 299)
    left = np.setdiff1d(only_valid, np.asarray(codes)[valid == 1, 1])
    assert np.all(np.asarray(got[1])[left] == 0)


@pytest.mark.parametrize("low", [2.0 ** -10, 2.0 ** -10 + 2.0 ** -20],
                         ids=["mid_bits", "lo_bits"])
def test_the_three_terms_keep_the_bits_one_pass_loses(low):
    """1 + 2^-10 is 1.0 in bfloat16, and 2^-20 is past a hi + mid pair:
    a sum of such cotangents into one table row is wrong by one bfloat16
    pass (and by two terms, for the second), and f32-exact by three."""
    n = 1300
    cot = np.full((n, 9), 1.0 + low, np.float32)
    cot[:, 1::2] *= -1.5
    codes = jnp.zeros((n, 1), jnp.int32)
    tables = [jnp.zeros((3, 9), jnp.float32)]
    got, _ = _grads(tables, codes, [jnp.asarray(cot)], onehot=True)
    exact = cot.astype(np.float64).sum(axis=0)
    assert np.allclose(np.asarray(got[0])[0], exact, rtol=2e-7, atol=0)
    assert np.all(np.asarray(got[0])[1:] == 0)

    def terms(x, k):
        out, rest = np.zeros_like(x, np.float64), x.astype(np.float32)
        for _ in range(k):
            t = np.asarray(jnp.asarray(rest).astype(jnp.bfloat16)
                           .astype(jnp.float32))
            out, rest = out + t, rest - t
        return out

    # the terms left out weigh 2^-10 or 2^-20 of each cotangent: more
    # than twice the 2e-7 the kernel is held to
    lossy = terms(cot, 1 if low == 2.0 ** -10 else 2).sum(axis=0)
    assert np.all(np.abs(lossy - exact) > 5e-7 * np.abs(exact))


# ---- which fields, which trainers ----

VOCAB = [5, 4096, 4097, 1, 5000, 130]  # tables on both sides of the cap
SMALL = (0, 1, 3, 5)


def test_the_plan_reads_shapes_the_mesh_and_vmap(kernel_on):
    """The tables' rows, the width and the mesh decide; whether the
    program is vmapped does not (`train_wdl_bagged` takes the kernel)."""
    from shifu_tpu.parallel.mesh import data_mesh

    assert og.small_fields(VOCAB, 9) == SMALL
    assert wt._onehot_plan(VOCAB, 9, None) == (SMALL, True)
    assert wt._onehot_plan(VOCAB, 9, data_mesh()) == ((), False)


@pytest.mark.parametrize("vocab, width, taken", [
    ([4000] * 60, 9, 50),
    ([4000] * 60, 17, 26),
    ([4096, 5, 3000, 128] * 10, 33, 31),  # nine of 4,096 rows left
    ([100] * 160, 9, og.FIELDS_CAP),
    ([4096], 129, 0),
], ids=["60_near_the_cap", "60_near_the_cap_width17", "mixed_width33",
        "160_one_tile", "one_too_wide"])
def test_the_plan_takes_the_smallest_tables_that_fit_vmem(vocab, width,
                                                         taken):
    """However many small tables there are, one call holds what fits
    `VMEM_BUDGET` and `FIELDS_CAP`, smallest table first; the rest keep
    the scatter-add."""
    fields = og.small_fields(vocab, width)
    assert len(fields) == taken and list(fields) == sorted(fields)
    if fields:
        assert og.call_vmem([vocab[f] for f in fields],
                            width) <= og.VMEM_BUDGET
    left = [vocab[f] for f in range(len(vocab)) if f not in fields
            and vocab[f] <= og.ROWS_CAP]
    if left:
        assert min(left) >= max([vocab[f] for f in fields], default=0)
        assert len(fields) == og.FIELDS_CAP or og.call_vmem(
            [vocab[f] for f in fields] + [min(left)], width) > og.VMEM_BUDGET


def test_the_plan_is_off_where_the_kernel_is():
    # the CPU harness: auto is off the chip
    assert wt._onehot_plan(VOCAB, 9, None) == ((), False)
    environment.set_property("shifu.pallas.mode", "off")
    try:
        assert wt._onehot_plan(VOCAB, 9, None) == ((), False)
    finally:
        environment.set_property("shifu.pallas.mode", "")


def _data(n=600, seed=0, vocab=VOCAB):
    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    codes = np.stack([rng.integers(-1, v + 2, n) for v in vocab],
                     axis=1).astype(np.int32)
    t = ((dense[:, 0] + (codes[:, 0] >= 3)) > 0.5).astype(np.float32)
    return dense, codes, t, np.ones(n, np.float32)


def _cfg(epochs=3, embed_dim=4, **kw):
    return wt.WDLTrainConfig(hidden=[8], activations=["relu"],
                             embed_dim=embed_dim, learning_rate=0.01,
                             num_epochs=epochs, valid_set_rate=0.2, seed=3,
                             **kw)


def test_train_wdl_counts_the_small_fields_once_a_trace(kernel_on):
    dense, codes, t, w = _data()
    wt._PROGRAMS.clear()  # a program traced before would count nothing
    before = _fired()
    wt.train_wdl(dense, codes, t, w, VOCAB, _cfg())
    assert _fired() - before == len(SMALL)
    # the program is cached: a second call traces nothing
    wt.train_wdl(dense, codes, t, w, VOCAB, _cfg())
    assert _fired() - before == len(SMALL)


@pytest.mark.parametrize("model_axis", [1, 2])
def test_a_mesh_keeps_the_scatter(kernel_on, model_axis):
    from shifu_tpu.parallel.mesh import data_mesh

    dense, codes, t, w = _data(n=520)
    before = _fired()
    wt.train_wdl(dense, codes, t, w, VOCAB, _cfg(epochs=2),
                 mesh=data_mesh(model_axis=model_axis))
    assert _fired() == before


def test_the_bagged_trainer_keeps_the_scatter(kernel_on):
    """Over a mesh: its members' tables are sharded as `train_wdl`'s."""
    from shifu_tpu.parallel.mesh import data_mesh

    dense, codes, t, w = _data(n=520)
    before = _fired()
    res = wt.train_wdl_bagged(dense, codes, t, w, VOCAB, _cfg(epochs=2), 2,
                              mesh=data_mesh())
    assert len(res) == 2 and _fired() == before


def test_the_bagged_trainer_takes_the_kernel_under_vmap():
    """One device: the vmapped program's transpose is the kernel with the
    members on a grid axis of their own, and each member's weights after
    three epochs are the scatter-add's to f32 rounding."""
    dense, codes, t, w = _data(n=1300, seed=2)
    flats = {}
    for mode in ("off", "on"):
        environment.set_property("shifu.pallas.mode", mode)
        try:
            before = _fired()
            res = wt.train_wdl_bagged(dense, codes, t, w, VOCAB, _cfg(), 3)
            assert _fired() - before == (len(SMALL) if mode == "on" else 0)
        finally:
            environment.set_property("shifu.pallas.mode", "")
        assert [r.iterations for r in res] == [3] * 3
        flats[mode] = [flatten_wdl(r.params) for r in res]
    assert np.any(flats["on"][0] != flats["on"][1])
    for a, b in zip(flats["on"], flats["off"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_streamed_loop_keeps_the_scatter(kernel_on, tmp_path):
    from shifu_tpu.norm.dataset import write_codes, write_normalized
    from shifu_tpu.train.streaming_wdl import train_wdl_streamed

    rng = np.random.default_rng(5)
    n, nd, vocab = 600, 3, [6, 40]
    dense = rng.normal(size=(n, nd)).astype(np.float32)
    codes = np.stack([rng.integers(0, v, n) for v in vocab],
                     axis=1).astype(np.int16)
    t = ((dense[:, 0] + (codes[:, 0] >= 3)) > 0.5).astype(np.int8)
    w = np.ones(n, np.float32)
    cols = [f"d{i}" for i in range(nd)] + ["c0", "c1"]
    norm_dir, codes_dir = str(tmp_path / "norm"), str(tmp_path / "codes")
    write_normalized(norm_dir, np.concatenate(
        [dense, codes.astype(np.float32)], 1), t, w, cols, n_shards=2)
    write_codes(codes_dir, np.concatenate(
        [np.zeros((n, nd), np.int16), codes], 1), t, w, cols,
        [1] * nd + vocab, n_shards=2)
    before = _fired()
    res = train_wdl_streamed(norm_dir, codes_dir, list(range(nd)),
                             [nd, nd + 1], vocab, _cfg(epochs=2))
    assert res.iterations == 2 and _fired() == before


def test_train_wdl_with_the_kernel_is_train_wdl_without():
    """Three epochs from the same start: the weights to f32 rounding (only
    the order of the small tables' gradient sums differs), and the
    forward's probability bit for bit."""
    dense, codes, t, w = _data(n=1300, seed=2)
    flats = {}
    for mode in ("off", "on"):
        environment.set_property("shifu.pallas.mode", mode)
        try:
            res = wt.train_wdl(dense, codes, t, w, VOCAB, _cfg())
        finally:
            environment.set_property("shifu.pallas.mode", "")
        assert res.iterations == 3
        flats[mode] = flatten_wdl(res.params)
    np.testing.assert_allclose(flats["on"], flats["off"], rtol=1e-5,
                               atol=1e-6)
    assert np.any(flats["on"] != flatten_wdl(init_wdl_params(
        3, VOCAB, 4, [8], seed=3)))

    p = unflatten_wdl(flats["on"], init_wdl_params(3, VOCAB, 4, [8]))
    codes_t = og.codes_operand(jnp.asarray(codes), SMALL, VOCAB)
    plain = jax.jit(lambda d, c: wdl_forward(p, d, c, ["relu"]))
    onehot = jax.jit(lambda d, c, ct: wdl_forward(
        p, d, c, ["relu"], onehot=(SMALL, ct, True)))
    assert np.array_equal(np.asarray(plain(dense, codes)),
                          np.asarray(onehot(dense, codes, codes_t)))


def test_train_wdl_at_embed16_with_the_kernel_is_without():
    """`EmbedOutputs` 16: every small table still takes the kernel, and
    the weights after two epochs are the scatter-add's to f32 rounding."""
    dense, codes, t, w = _data(n=700, seed=4)
    flats = {}
    for mode in ("off", "on"):
        environment.set_property("shifu.pallas.mode", mode)
        try:
            wt._PROGRAMS.clear()
            before = _fired()
            res = wt.train_wdl(dense, codes, t, w, VOCAB,
                               _cfg(epochs=2, embed_dim=16))
            assert _fired() - before == (len(SMALL) if mode == "on" else 0)
        finally:
            environment.set_property("shifu.pallas.mode", "")
        flats[mode] = flatten_wdl(res.params)
    np.testing.assert_allclose(flats["on"], flats["off"], rtol=1e-5,
                               atol=1e-6)


def test_the_kernel_body_holds_no_loop_and_no_nested_jit():
    """What a body costs to trace it costs in every process's set-up, and
    on the chip's host a nested `jit` or a loop costs seconds of it
    (PERF.md, section 7): the body is straight-line `lax`."""
    vocab, n = (5, 300, 4096), 1300
    jp = jax.make_jaxpr(lambda c, p: og.table_grads(c, p, vocab, True))(
        jnp.zeros((3, n), jnp.int32), [jnp.zeros((9, n))] * 3)
    (call,) = [e for e in jp.jaxpr.eqns if e.primitive.name == "pallas_call"]
    names = {e.primitive.name for e in call.params["jaxpr"].eqns}
    assert not names & {"jit", "pjit", "while", "scan", "closed_call"}, names
    assert "dot_general" in names
