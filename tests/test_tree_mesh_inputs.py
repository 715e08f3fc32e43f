"""`train_trees(..., mesh=)` fed from the devices: row-sharded `jax.Array`
inputs stay on the mesh and grow, bit for bit, the forest the same host
arrays grow; what the placement records (`train.trees.shard`,
`mesh.h2d_bytes`, `mesh.d2h_bytes`), what a meshed tree all-reduces
(`tree.psum`, `tree.psum.bytes`, the `psum` scopes), and `pad_rows` /
`shard_rows` on either kind of array. `data_mesh(4)` is over the suite's
forced host devices."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shifu_tpu import obs  # noqa: E402
from shifu_tpu.parallel.mesh import (data_mesh, pad_rows, pull_rows,  # noqa: E402
                                     shard_rows)
from shifu_tpu.train import tree_trainer as tt  # noqa: E402
from tests.test_train_spans import _eqns  # noqa: E402  (the jaxpr walker)

F, S = 10, 12
COLS = ["c%d" % i for i in range(F)]


def _table(n):
    rng = np.random.default_rng(7)
    codes = rng.integers(0, S, size=(n, F)).astype(np.int32)
    y = (codes[:, 0] + codes[:, 1]
         + rng.normal(scale=2, size=n) > S).astype(np.float32)
    return codes, y, np.ones(n, np.float32)


def _grow(rows, alg, mesh, trees=3, depth=4, **kw):
    cfg = tt.TreeTrainConfig(algorithm=alg, tree_num=trees, max_depth=depth,
                             seed=3)
    return tt.train_trees(*rows, [S] * F, [False] * (F - 2) + [True, True],
                          COLS, cfg, mesh=mesh, **kw)


def _counters():
    return obs.registry().snapshot()["counters"]


def _same_forest(a, b):
    assert len(a.spec.trees) == len(b.spec.trees)
    for ta, tb in zip(a.spec.trees, b.spec.trees):
        assert np.array_equal(ta.feature, tb.feature)
        assert np.array_equal(ta.left_mask, tb.left_mask)
        assert np.array_equal(ta.leaf_value, tb.leaf_value)  # bit for bit
    assert a.valid_error == b.valid_error
    assert a.train_error == b.train_error


@pytest.mark.parametrize("alg", ["GBT", "RF"])
@pytest.mark.parametrize("n", [1000, 1003])
def test_device_inputs_grow_the_host_arrays_forest(alg, n):
    """1,000 rows divide over four chips and arrive row-sharded; 1,003 do
    not (no row-sharded array has uneven shards), arrive on one device and
    are padded and spread on the devices. Neither leaves them."""
    mesh = data_mesh(4)
    host = _table(n)
    from_host = _grow(host, alg, mesh)
    if n % 4 == 0:
        dev = [shard_rows(a, mesh) for a in host]
    else:
        dev = [jax.device_put(a, jax.devices()[1]) for a in host]
    obs.reset()
    from_dev = _grow(dev, alg, mesh)
    _same_forest(from_host, from_dev)
    c = _counters()
    assert c.get("mesh.d2h_bytes", 0.0) == 0.0
    # the validity draw crosses (a byte a padded row), and RF's bag counts
    draw = -(-n // 4) * 4
    bags = 3 * draw * 4 if alg == "RF" else 0
    assert c["mesh.h2d_bytes"] == draw + bags + F * S  # + the feature mask
    (shard,) = [e for e in obs.tracer().events
                if e["name"] == "train.trees.shard"]
    assert shard["args"]["source"] == "device"
    assert shard["args"]["bytes"] == draw
    assert shard["args"]["parent"] == "train.trees.call/train.trees.prologue"
    obs.reset()


@pytest.mark.parametrize("alg", ["GBT", "RF"])
def test_the_meshed_kernel_grows_the_one_chip_forest(alg):
    """With the kernel on (interpret mode here) every chip's hist-mode
    entry reads its own rows' codes as `[F, n / chips]`, and the meshed
    forest is the one-chip kernel's (RF: bit for bit; GBT's bf16 planes
    round each chip's partial sums, so its splits are held and its leaves
    to a rounding), in 4 kernel calls a tree and with nothing brought back
    to the host."""
    from shifu_tpu.utils import environment

    rows, mesh = _table(1000), data_mesh(4)
    environment.set_property("shifu.pallas.mode", "on")
    try:
        one = _grow(rows, alg, None)
        obs.reset()
        four = _grow(tuple(shard_rows(a, mesh) for a in rows), alg, mesh)
        counters = _counters()
    finally:
        environment.set_property("shifu.pallas.mode", "")
    assert counters["tree.kernel.calls"] == 3 * 4  # 1 chunk x 4 levels
    assert not counters.get("mesh.d2h_bytes")
    for ta, tb in zip(one.spec.trees, four.spec.trees):
        assert np.array_equal(ta.feature, tb.feature)
        assert np.array_equal(ta.left_mask, tb.left_mask)
        if alg == "RF":
            assert np.array_equal(ta.leaf_value, tb.leaf_value)
        else:
            np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                       atol=1e-5)


def test_host_inputs_are_put_once_and_say_so():
    mesh = data_mesh(4)
    host = _table(1003)
    obs.reset()
    _grow(host, "GBT", mesh)
    (shard,) = [e for e in obs.tracer().events
                if e["name"] == "train.trees.shard"]
    assert shard["args"]["source"] == "host"
    # codes, labels, weights, the draw and the real-row mask, padded
    assert shard["args"]["bytes"] == 1004 * (F * 4 + 4 + 4 + 1 + 1)
    assert _counters().get("mesh.d2h_bytes", 0.0) == 0.0
    obs.reset()


def test_codes_placed_ahead_of_the_call_keep_every_draw():
    """What `processor/train_tree.py` does once for all its bags: the code
    matrix padded to the mesh and placed, the tags left on the host. The
    draws follow the tags' row count, so the forest is the same."""
    mesh = data_mesh(4)
    codes, y, w = _table(1003)
    placed = shard_rows(pad_rows([codes], 4)[0][0], mesh)
    assert placed.shape[0] == 1004
    for alg in ("GBT", "RF"):
        _same_forest(_grow((codes, y, w), alg, mesh),
                     _grow((placed, y, w), alg, mesh))


def test_a_resumed_forests_scores_count_as_pulled():
    mesh = data_mesh(4)
    rows = [shard_rows(a, mesh) for a in _table(1000)]
    first = _grow(rows, "GBT", mesh, trees=2)
    obs.reset()
    whole = _grow(rows, "GBT", mesh, trees=3,
                  init_trees=list(first.spec.trees))
    assert _counters()["mesh.d2h_bytes"] == 1000 * 4  # F(x) of 1,000 rows
    _same_forest(whole, _grow(rows, "GBT", mesh, trees=3))
    obs.reset()


@pytest.mark.parametrize("depth,sub", [(6, True), (3, True), (3, False)])
def test_a_meshed_tree_counts_its_all_reduces(depth, sub):
    mesh = data_mesh(4)
    rows = _table(1000)
    cfg = tt.TreeTrainConfig(algorithm="GBT", tree_num=2, max_depth=depth,
                             seed=3, hist_subtraction=sub)
    obs.reset()
    tt.train_trees(*rows, [S] * F, [False] * F, COLS, cfg, mesh=mesh)
    c = _counters()
    assert c["train.trees"] == 2
    assert c["tree.psum"] == 2 * (depth + 1)  # 7 a tree at depth 6
    T = F * S
    widths = [2**d // 2 if (sub and d) else 2**d for d in range(depth)]
    assert c["tree.psum.bytes"] == 2 * 4 * (3 * sum(widths) * T
                                            + 2 * 2**depth)
    assert c["tree.hist.built"] == 2 * sum(widths)
    obs.reset()
    tt.train_trees(*rows, [S] * F, [False] * F, COLS, cfg)  # one device
    assert "tree.psum" not in _counters()
    obs.reset()


def test_psum_counts_at_the_cells_shape():
    """28 x 33 slots, depth 6, subtraction from level 1: 355,328 bytes a
    tree in 7 all-reduces, the widest 3 x 16 x 924 float32."""
    sub = (False,) + (True,) * 6
    assert tt._psum_counts(6, 924, sub) == (7, 4 * (3 * 32 * 924 + 2 * 64))
    assert tt._psum_counts(6, 924, (False,) * 7) == (
        7, 4 * (3 * 63 * 924 + 2 * 64))
    assert tt._psum_counts(2, 10, (False,) * 3, n_classes=4) == (
        3, 4 * (4 * 3 * 10 + 4 * 4))


def test_the_meshed_programs_all_reduces_sit_in_psum_scopes():
    mesh = data_mesh(4)
    lay = tt.make_layout([S] * 5, [False] * 5)
    prog = tt._get_tree_program(3, lay, "variance", 1, 0.0, mesh=mesh,
                                sub_levels=(False, True, True, True))
    n = 256
    jp = jax.make_jaxpr(prog.fn)(jnp.zeros((n, 5), jnp.int32), jnp.zeros(n),
                                 jnp.ones(n), jnp.ones(lay.T, bool))
    psums = [e for e in _eqns(jp.jaxpr, [])
             if e.primitive.name.startswith("psum")]
    assert sorted(str(e.source_info.name_stack) for e in psums) == [
        "tree.L1/psum", "tree.L2/psum", "tree.L4/psum", "tree.leaf/psum"]
    # the level's histogram is built under `hist`, not under `psum`
    stacks = {str(e.source_info.name_stack) for e in _eqns(jp.jaxpr, [])}
    assert any(s.startswith("tree.L4/hist") for s in stacks)


# ---- pad_rows / shard_rows on either kind of array ----

def test_pad_rows_pads_each_array_where_it_lies():
    host = np.arange(10, dtype=np.int32).reshape(5, 2)
    dev = jnp.arange(5, dtype=jnp.float32)
    (h, d), n = pad_rows([host, dev], 4)
    assert n == 5 and h.shape == (8, 2) and d.shape == (8,)
    assert isinstance(h, np.ndarray) and isinstance(d, jax.Array)
    assert np.array_equal(h[:5], host) and not h[5:].any()
    assert np.array_equal(np.asarray(d), [0, 1, 2, 3, 4, 0, 0, 0])
    same, _ = pad_rows([np.zeros(8), jnp.zeros(8)], 4)
    assert same[0].shape == same[1].shape == (8,)
    # an array that already carries the padding rows is kept
    (long, short), n = pad_rows([np.ones((8, 2)), np.ones(5)], 4)
    assert n == 8 and long.shape == (8, 2)
    assert np.array_equal(short, [1, 1, 1, 1, 1, 0, 0, 0])


def test_shard_rows_counts_only_what_crosses():
    mesh = data_mesh(4)
    obs.reset()
    host = np.ones((8, 3), np.float32)
    placed = shard_rows(host, mesh)
    assert _counters()["mesh.h2d_bytes"] == host.nbytes
    assert len({s.device for s in placed.addressable_shards}) == 4
    assert shard_rows(placed, mesh) is placed  # where it lies already
    on_one = jax.device_put(host, jax.devices()[2])
    moved = shard_rows(on_one, mesh)  # between devices: nothing crosses
    assert moved.sharding.is_equivalent_to(placed.sharding, 2)
    assert _counters()["mesh.h2d_bytes"] == host.nbytes
    assert np.array_equal(pull_rows(moved), host)
    assert _counters()["mesh.d2h_bytes"] == host.nbytes
    assert pull_rows(host) is host or np.array_equal(pull_rows(host), host)
    assert _counters()["mesh.d2h_bytes"] == host.nbytes  # a host array: none
    obs.reset()
