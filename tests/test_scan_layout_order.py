"""The XLA split scan reads the feature layout at trace time. Where no
column is categorical the slots are scanned in the layout's own order: no
sort, no reorder gathers and no rank scatter over [L, T]; every layout's
segment base and total come by static slices of the cumulative planes.

Held here, on the CPU: the scan's nine outputs equal, bit for bit, those
of the form that sorted every node's slots at run time and gathered the
segment bounds per column (`_sorted_scan`, kept below as it was), for
numeric and mixed layouts, regression and multi-class; the numeric scan's
HLO holds none of that index work; and `tree.scan.levels{order=}` counts
the XLA-scanned levels the plan says."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shifu_tpu import obs  # noqa: E402
from shifu_tpu.train import tree_trainer as tt  # noqa: E402

NAMES = ("feature", "cut_rank", "rank_flat", "leaf_value", "is_split",
         "best_gain", "left_mask", "node_cnt", "left_cnt")

LAYOUTS = {
    "28x33": [33] * 28,
    "28x256": [256] * 28,
    "uneven": [2, 33, 256, 7],
    "one_feature": [17],
}


def _sorted_scan(L, T, s_max, impurity, min_inst, min_gain, K=0):
    """The split scan as it was before the layout was read at trace time:
    the layout as run-time arrays, every node's slots ordered by a lexsort,
    the planes reordered, the segment bounds gathered per column and the
    ranks scattered back. Regression (K = 0) or K-class counts."""

    def scan(hist, feat_ok_t, is_cat_t, seg_t, pos_t, start_t, size_t,
             off_f, clip_f, seg0_size):
        if K >= 3:
            cnt = hist.sum(0)
            key = (hist * jnp.arange(K, dtype=jnp.float32)[:, None, None]
                   ).sum(0)
        else:
            cnt, key = hist[0], hist[1]
        mean = jnp.where(cnt > 0, key / jnp.maximum(cnt, 1e-12), jnp.inf)
        sec = jnp.where(is_cat_t[None, :], mean,
                        jnp.broadcast_to(pos_t.astype(jnp.float32), cnt.shape))
        order = jax.vmap(lambda row: jnp.lexsort((row, seg_t)))(sec)

        def reorder(a):
            return jnp.take_along_axis(a, order, axis=-1)

        start_prev = jnp.maximum(start_t - 1, 0)
        end_idx = start_t + size_t - 1
        if K >= 3:
            ccum = jnp.cumsum(jax.vmap(reorder)(hist), axis=-1)
            base = jnp.where(start_t[None, None, :] > 0,
                             ccum[:, :, start_prev], 0.0)
            left = ccum - base
            tot = ccum[:, :, end_idx] - base
            right = tot - left
            lcnt, rcnt, tcnt = left.sum(0), right.sum(0), tot.sum(0)

            def mass(counts, total):
                p = counts / jnp.maximum(total[None], 1e-12)
                if impurity == "entropy":
                    h = -(p * jnp.log2(jnp.maximum(p, 1e-12))).sum(0)
                else:
                    h = 1.0 - (p * p).sum(0)
                return total * h

            gain = mass(tot, tcnt) - mass(left, lcnt) - mass(right, rcnt)
        else:
            c0, c1, c2 = (jnp.cumsum(reorder(a), axis=-1) for a in hist)

            def seg_sums(c):
                base = jnp.where(start_t > 0, c[:, start_prev], 0.0)
                return c - base, c[:, end_idx] - base

            (lcnt, tcnt), (ls1, ts1), (ls2, ts2) = (seg_sums(c0),
                                                    seg_sums(c1),
                                                    seg_sums(c2))
            rcnt, rs1, rs2 = tcnt - lcnt, ts1 - ls1, ts2 - ls2

            def sse(c, s, q):
                return q - s * s / jnp.maximum(c, 1e-12)

            def gini_mass(c, p):
                ng = c - p
                return c - (p * p + ng * ng) / jnp.maximum(c, 1e-12)

            def entropy_mass(c, p):
                pr = p / jnp.maximum(c, 1e-12)
                q = 1.0 - pr
                h = -(pr * jnp.log2(jnp.maximum(pr, 1e-12))
                      + q * jnp.log2(jnp.maximum(q, 1e-12)))
                return c * h

            if impurity == "entropy":
                gain = (entropy_mass(tcnt, ts1) - entropy_mass(lcnt, ls1)
                        - entropy_mass(rcnt, rs1))
            elif impurity == "gini":
                gain = (gini_mass(tcnt, ts1) - gini_mass(lcnt, ls1)
                        - gini_mass(rcnt, rs1))
            elif impurity == "friedmanmse":
                ml = ls1 / jnp.maximum(lcnt, 1e-12)
                mr = rs1 / jnp.maximum(rcnt, 1e-12)
                gain = lcnt * rcnt / jnp.maximum(tcnt, 1e-12) * (ml - mr) ** 2
            else:
                gain = (sse(tcnt, ts1, ts2) - sse(lcnt, ls1, ls2)
                        - sse(rcnt, rs1, rs2))
        valid = ((lcnt >= min_inst) & (rcnt >= min_inst) & (gain > min_gain)
                 & feat_ok_t[None, :] & (pos_t < size_t - 1)[None, :])
        gain = jnp.where(valid, gain, -jnp.inf)
        best = jnp.argmax(gain, axis=-1)
        best_gain = jnp.take_along_axis(gain, best[:, None], axis=-1)[:, 0]
        left_cnt = jnp.take_along_axis(lcnt, best[:, None], axis=-1)[:, 0]
        feature = seg_t[best].astype(jnp.int32)
        cut_rank = pos_t[best].astype(jnp.int32)
        is_split = jnp.isfinite(best_gain)
        rank_flat = (jnp.zeros((L, T), jnp.int32)
                     .at[jnp.arange(L)[:, None], order]
                     .set(jnp.broadcast_to(pos_t, (L, T))))
        if K >= 3:
            node_class_cnt = ccum[:, :, seg0_size - 1]
            node_cnt = node_class_cnt.sum(0)
            leaf_value = jnp.argmax(node_class_cnt, axis=0).astype(
                jnp.float32)
        else:
            node_cnt = c0[:, seg0_size - 1]
            leaf_value = c1[:, seg0_size - 1] / jnp.maximum(node_cnt, 1e-12)
        s_range = jnp.arange(s_max, dtype=jnp.int32)
        f_clip = clip_f[feature]
        s_idx = jnp.minimum(s_range[None, :], f_clip[:, None])
        flat_idx = off_f[feature][:, None] + s_idx
        ranks = jnp.take_along_axis(rank_flat, flat_idx, axis=-1)
        left_mask = ((ranks <= cut_rank[:, None])
                     & (s_range[None, :] <= f_clip[:, None])
                     & is_split[:, None])
        return (feature, cut_rank, rank_flat, leaf_value, is_split,
                best_gain, left_mask, node_cnt, left_cnt)

    return scan


def _hist(lay, L, K, seed):
    """A seeded level histogram with the cases a scan can trip on planted:
    empty slots, empty nodes, two nodes alike, and equal gains (a
    feature's slots repeated in the next feature of its width, or a run of
    one value inside a feature)."""
    rng = np.random.default_rng(seed)
    T = lay.T
    cnt = rng.integers(0, 30, size=(L, T)).astype(np.float32)
    cnt[rng.random((L, T)) < 0.15] = 0.0  # empty slots
    if K >= 3:
        hist = (rng.random((K, L, T)) * cnt).round().astype(np.float32)
    else:
        mean = rng.normal(size=(L, T)).astype(np.float32)
        hist = np.stack([cnt, cnt * mean,
                         cnt * (mean * mean + 0.25)]).astype(np.float32)
    slots = lay.slots.tolist()
    for f in range(len(slots) - 1):  # a feature's twin: ties across columns
        if slots[f + 1] == slots[f] and f % 3 == 0:
            a, b = int(lay.off[f]), int(lay.off[f + 1])
            hist[:, :, b:b + slots[f]] = hist[:, :, a:a + slots[f]]
    f = int(np.argmax(slots))  # a run of one value: ties inside a column
    o = int(lay.off[f])
    hist[:, :, o + 1:o + min(slots[f], 6)] = hist[:, :, o:o + 1]
    if L > 2:
        hist[:, 1, :] = 0.0  # an empty node
        hist[:, 2, :] = hist[:, 0, :]  # two nodes alike
    return hist


def _feat_ok(n_features, seed):
    ok = np.random.default_rng(seed + 1).random(n_features) < 0.75
    ok[0] = True
    if n_features > 1:
        ok[-1] = False
    return ok


def _both(slots, is_cat, L, impurity, K, seed, min_inst=3):
    lay = tt.make_layout(slots, is_cat)
    hist = jnp.asarray(_hist(lay, L, K, seed))
    fot = jnp.asarray(_feat_ok(len(slots), seed)[lay.seg_of_t])
    new = jax.jit(tt._make_scan_fn(lay, impurity, min_inst, 0.0, K))(
        hist, fot)
    old = jax.jit(_sorted_scan(L, lay.T, lay.s_max, impurity, min_inst,
                               0.0, K))(
        hist, fot, jnp.asarray(lay.is_cat_t), jnp.asarray(lay.seg_of_t),
        jnp.asarray(lay.pos_in_seg), jnp.asarray(lay.off[lay.seg_of_t]),
        jnp.asarray(lay.seg_size_t), jnp.asarray(lay.off),
        jnp.asarray(lay.clip_max), int(lay.slots[0]))
    return new, old


def _assert_bit_equal(new, old):
    for name, a, b in zip(NAMES, new, old):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        # the same bits: floats by their integer view, so a -0.0 or a NaN
        # in another place would differ too
        if a.dtype.kind == "f":
            a, b = a.view(np.int32), b.view(np.int32)
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("L", [1, 8, 128])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_numeric_scan_equals_the_sorted_form_bit_for_bit(layout, L):
    slots = LAYOUTS[layout]
    new, old = _both(slots, [False] * len(slots), L, "variance", 0,
                     seed=11 * L + len(slots))
    _assert_bit_equal(new, old)
    assert np.asarray(new[4]).any()  # some node splits


@pytest.mark.parametrize("L", [1, 8, 128])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_multiclass_scan_equals_the_sorted_form_bit_for_bit(layout, L):
    slots = LAYOUTS[layout]
    impurity = "entropy" if L == 8 else "gini"
    new, old = _both(slots, [False] * len(slots), L, impurity, 4,
                     seed=13 * L + len(slots))
    _assert_bit_equal(new, old)
    assert np.asarray(new[4]).any()


@pytest.mark.parametrize("impurity", ["entropy", "gini", "friedmanmse"])
def test_every_impurity_equals_the_sorted_form(impurity):
    slots = LAYOUTS["uneven"]
    new, old = _both(slots, [False] * len(slots), 8, impurity, 0, seed=5)
    _assert_bit_equal(new, old)


@pytest.mark.parametrize("K", [0, 3], ids=["regression", "3-class"])
@pytest.mark.parametrize("L", [1, 8])
def test_a_categorical_layout_keeps_the_sorted_path(L, K):
    """A mixed layout still orders each categorical column by its mean
    label: the same outputs as the sorted form, and its ranks are not
    the layout's positions."""
    slots, is_cat = [5, 33, 12, 256, 7], [False, False, True, False, True]
    new, old = _both(slots, is_cat, L, "gini" if K else "variance", K,
                     seed=17 + L)
    _assert_bit_equal(new, old)
    lay = tt.make_layout(slots, is_cat)
    assert lay.has_cat
    assert (np.asarray(new[2]) != lay.pos_in_seg[None, :]).any()


def _hlo(slots, is_cat, L, K=0):
    lay = tt.make_layout(slots, is_cat)
    C = K if K >= 3 else 3
    fn = jax.jit(tt._make_scan_fn(lay, "variance", 3, 0.0, K))
    return lay, fn.lower(jax.ShapeDtypeStruct((C, L, lay.T), jnp.float32),
                         jax.ShapeDtypeStruct((lay.T,), jnp.bool_)
                         ).as_text(dialect="hlo")


def _gather_sizes(hlo):
    sizes = []
    for dims in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", hlo):
        sizes.append(int(np.prod([int(d) for d in dims.split(",") if d])))
    return sizes


@pytest.mark.parametrize("K", [0, 3], ids=["regression", "3-class"])
@pytest.mark.parametrize("layout", ["28x33", "uneven"])
def test_the_numeric_scan_holds_no_sort_scatter_or_wide_gather(layout, K):
    L = 8
    slots = LAYOUTS[layout]
    lay, hlo = _hlo(slots, [False] * len(slots), L, K)
    assert " sort(" not in hlo
    assert " scatter(" not in hlo
    assert all(n < L * lay.T for n in _gather_sizes(hlo)), _gather_sizes(hlo)
    # a categorical column brings the sort, its reorders and the scatter
    lay_c, hlo_c = _hlo(slots, [False] * (len(slots) - 1) + [True], L, K)
    assert " sort(" in hlo_c and " scatter(" in hlo_c
    assert any(n >= L * lay_c.T for n in _gather_sizes(hlo_c))


def test_the_plan_counts_the_cells_xla_scanned_levels(monkeypatch):
    """Levels whose scan is XLA's, a tree: all but the first where the
    fused kernel scans (subtraction derives every later level, and the
    derived half is XLA's), every level under a mesh or with the kernel
    off."""
    every = (False,) + (True,) * 10
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (True, False, mesh is None))
    assert tt._tree_scan_levels(8, every) == 7  # higgs_gbt_255
    assert tt._tree_scan_levels(6, every) == 5  # higgs_gbt
    assert tt._tree_scan_levels(10, every) == 9  # higgs_rf
    assert tt._tree_scan_levels(6, every, mesh=object()) == 6  # the mesh
    # no subtraction: the levels the fused kernel builds whole scan there
    assert tt._tree_scan_levels(8, (False,) * 9) == 2
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (False, False, False))
    assert tt._tree_scan_levels(6, every) == 6


@pytest.mark.parametrize("batch_cap", [None, 2],
                         ids=["whole_tree_program", "node_batched_grower"])
@pytest.mark.parametrize("is_cat", [False, True], ids=["numeric", "mixed"])
def test_a_tree_counts_its_scanned_levels_by_order(monkeypatch, batch_cap,
                                                   is_cat):
    if batch_cap:  # two nodes a batch: depth 3 no longer fits one program
        monkeypatch.setattr(tt, "_node_batch_size", lambda *a, **k: batch_cap)
    obs.reset()
    try:
        rng = np.random.default_rng(3)
        n, F, D = 600, 4, 3
        codes = rng.integers(0, 8, size=(n, F)).astype(np.int32)
        y = (codes[:, 0] + codes[:, 1] > 7).astype(np.float32)
        cfg = tt.TreeTrainConfig(algorithm="GBT", tree_num=2, max_depth=D,
                                 valid_set_rate=0.1, seed=1)
        tt.train_trees(codes, y, np.ones(n, np.float32), [9] * F,
                       [False] * (F - 1) + [is_cat],
                       ["f%d" % i for i in range(F)], cfg)
        counters = obs.registry().snapshot()["counters"]
        programs = obs.profiler().snapshot()["programs"]
        mine, other = ("sorted", "layout") if is_cat else ("layout", "sorted")
        got = counters['tree.scan.levels{order="%s"}' % mine]
        if batch_cap:  # one a dispatch of the scan program
            assert got == programs["tree.split_scan"]["dispatches"] > 2 * D
        else:  # the kernel is off here: every level is XLA's, a tree
            assert got == 2 * D
        assert 'tree.scan.levels{order="%s"}' % other not in counters
    finally:
        obs.reset()
