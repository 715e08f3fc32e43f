"""Row routing (`tree_trainer.route_rows`): the dense form against the
per-row gather formulation, which is kept here as the plain reference; the
identity between a scan's `left_mask` and its `rank_flat` that the dense
form rests on; the build-row mask it hands the next level (histogram
subtraction) against the two-line gather it replaced in PR 35; and the
whole-tree program's scopes, which must hold no gather from `codes` or from
a level's [L, T] table under route, and none a row under hist or route."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.train import tree_trainer as tt


def route_gather(codes, node, active, resting, feature, cut_rank, rank_flat,
                 is_split, base, off_f, clip_f):
    """The reference: what `tree_body` and `row_update` ran up to PR 26,
    two per-row gathers a level."""
    L = is_split.shape[0]
    nl = jnp.clip(node, 0, L - 1)
    settled = active & ~is_split[nl]
    resting2 = jnp.where(settled, base + nl, resting)
    f = jnp.where(is_split, feature, 0)[nl]
    code = jnp.take_along_axis(codes, f[:, None], axis=1)[:, 0]
    cf = off_f[f] + jnp.clip(code, 0, clip_f[f])
    goes_left = rank_flat[nl, cf] <= cut_rank[nl]
    still = is_split[nl] & active
    return (resting2,
            jnp.where(still, jnp.where(goes_left, 2 * nl, 2 * nl + 1), 0),
            still)


# feature columns of the cases: (slots, is_cat)
NUMERIC = ([33] * 5, [False] * 5)
MIXED = ([33, 12, 33, 7, 20, 33], [False, True, False, True, True, False])
WIDE = ([256, 40, 256], [False, True, True])


def _level(cols, L, seed, n_classes=0, dead_nodes=True):
    """A level's real scan outputs: a seeded histogram through the
    trainer's own scan. Nodes 1, 4, 7, ... get an empty histogram, so they
    do not split."""
    slots, is_cat = cols
    lay = tt.make_layout(slots, is_cat)
    rng = np.random.default_rng(seed)
    planes = n_classes if n_classes >= 3 else 3
    cnt = rng.integers(0, 40, size=(L, lay.T)).astype(np.float32)
    if n_classes >= 3:
        hist = rng.integers(0, 15, size=(planes, L, lay.T)).astype(np.float32)
    else:
        mean = rng.normal(size=(L, lay.T)).astype(np.float32)
        hist = np.stack([cnt, cnt * mean, cnt * (mean * mean + 0.5)])
    if dead_nodes:
        hist[:, 1::3, :] = 0.0
    la = tt._device_layout(lay, np.ones(len(slots), bool))
    scan = tt._get_scan_program(L, lay.T, lay.s_max, "variance", 1, 0.0,
                                n_classes)
    out = scan(jnp.asarray(hist), la.feat_ok_t, la.is_cat_t, la.seg_t,
               la.pos_t, la.start_t, la.size_t, la.off, la.clip,
               la.seg0_size)
    return lay, la, out


def _rows(lay, L, n, seed, inactive=0.2):
    """Rows with codes from two below 0 to two past the last slot (the
    last slot is the missing-value one), node ids one past both ends."""
    rng = np.random.default_rng(seed + 1)
    codes = np.stack([rng.integers(-2, s + 2, size=n) for s in lay.slots],
                     axis=1).astype(np.int32)
    node = rng.integers(-1, L + 1, size=n).astype(np.int32)
    active = rng.random(n) >= inactive
    resting = rng.integers(0, 1000, size=n).astype(np.int32)
    return (jnp.asarray(codes), jnp.asarray(node), jnp.asarray(active),
            jnp.asarray(resting))


CASES = [
    # id, columns, L, share of inactive rows
    ("numeric_L1", NUMERIC, 1, 0.2),
    ("numeric_L2", NUMERIC, 2, 0.2),
    ("numeric_L32", NUMERIC, 32, 0.2),
    ("categorical_L2", MIXED, 2, 0.2),
    ("categorical_L32", MIXED, 32, 0.2),
    ("categorical_L64", MIXED, 64, 0.2),
    ("categorical_L512", MIXED, 512, 0.2),
    ("all_rows_active_L32", MIXED, 32, 0.0),
    ("no_row_active_L32", MIXED, 32, 1.0),
    ("slots256_L1", WIDE, 1, 0.2),
    ("slots256_L32", WIDE, 32, 0.2),
    ("slots256_L512", WIDE, 512, 0.2),
]


@pytest.mark.parametrize("cols,L,inactive",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_route_rows_is_the_gather_form_bit_for_bit(cols, L, inactive):
    lay, la, (bf, br, rank_flat, _lv, is_split, _g, lm, _nc, _lc) = _level(
        cols, L, seed=L)
    split = np.asarray(is_split)
    assert split.any() and (L == 1 or not split.all())
    codes, node, active, resting = _rows(lay, L, 4000, seed=L,
                                         inactive=inactive)
    base = jnp.int32(L - 1)
    want = jax.jit(route_gather)(codes, node, active, resting, bf, br,
                                 rank_flat, is_split, base, la.off, la.clip)
    got = jax.jit(tt.route_rows)(codes, node, active, resting, bf, is_split,
                                 lm, base, la.clip)
    for name, w, g in zip(("resting", "node", "active"), want, got):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    # the rows did move: both children are taken where any row goes on
    if inactive < 1.0:
        moved = np.asarray(got[1])[np.asarray(got[2])]
        assert (moved % 2 == 0).any() and (moved % 2 == 1).any()


@pytest.mark.parametrize("cols,L", [(MIXED, 32), (WIDE, 8)],
                         ids=["categorical_L32", "slots256_L8"])
def test_route_rows_past_the_select_cap_is_the_gather_form(monkeypatch, cols,
                                                           L):
    """Tables longer than `_ROUTE_SELECT_CAP` are read by a 1-D gather; the
    cap is lowered here so that the mask words (and, at 16, the per-node
    scalars too) take that branch at a test's size."""
    lay, la, (bf, br, rank_flat, _lv, is_split, _g, lm, _nc, _lc) = _level(
        cols, L, seed=L + 1)
    codes, node, active, resting = _rows(lay, L, 3000, seed=L + 1)
    base = jnp.int32(L - 1)
    want = route_gather(codes, node, active, resting, bf, br, rank_flat,
                        is_split, base, la.off, la.clip)
    for cap in (L, 16 if L > 16 else 4):
        monkeypatch.setattr(tt, "_ROUTE_SELECT_CAP", cap)
        assert not tt.route_is_dense(L, lay.s_max)
        got = jax.jit(tt.route_rows)(codes, node, active, resting, bf,
                                     is_split, lm, base, la.clip)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def built_gather(node, active, left_small):
    """The reference: `_sub_row_masks` as the growers ran it up to PR 34,
    on the rows as `route_rows` left them: a row is built iff its node's
    low bit is its parent's built side, the side a per-row gather names."""
    built_lsb = jnp.where(left_small, 0, 1)
    return node >> 1, active & ((node & 1) == built_lsb[node >> 1])


BUILT_CASES = [
    # id, columns, L, share of inactive rows, _ROUTE_SELECT_CAP (None: as is)
    ("L1", NUMERIC, 1, 0.2, None),
    ("L4", MIXED, 4, 0.2, None),
    ("L64", MIXED, 64, 0.2, None),
    ("L256", NUMERIC, 256, 0.2, None),
    ("L512", MIXED, 512, 0.2, None),
    ("L64_all_rows_active", NUMERIC, 64, 0.0, None),
    ("L64_no_row_active", NUMERIC, 64, 1.0, None),
    ("L64_words_past_the_cap", MIXED, 64, 0.2, 64),
    ("L64_nodes_past_the_cap", MIXED, 64, 0.2, 16),
    ("L256_nodes_past_the_cap", NUMERIC, 256, 0.2, 128),
]


@pytest.mark.parametrize("cols,L,inactive,cap",
                         [c[1:] for c in BUILT_CASES],
                         ids=[c[0] for c in BUILT_CASES])
def test_route_rows_hands_on_the_build_mask_bit_for_bit(monkeypatch, cols, L,
                                                        inactive, cap):
    """`route_rows(..., left_small)`'s fourth value is the old form's mask
    on the rows it moved, and the first three are what it returns without
    `left_small`. Nodes 1, 4, 7, ... do not split; nodes 0, 5, 10, ... are
    ties (lc == nc - lc, so the left child is the built one), nodes 2, 7,
    12, ... have the smaller child on the right by one row."""
    lay, la, (bf, _br, _rank, _lv, is_split, _g, lm, nc, lc) = _level(
        cols, L, seed=L + 2)
    codes, node, active, resting = _rows(lay, L, 4000, seed=L + 2,
                                         inactive=inactive)
    i = jnp.arange(L)
    nc = jnp.where(i % 5 == 0, 2 * lc, jnp.where(i % 5 == 2, 2 * lc - 1, nc))
    left_small = lc <= nc - lc
    ls = np.asarray(left_small)
    assert (np.asarray(lc) == np.asarray(nc - lc)).any() and ls.any()
    assert L < 4 or not ls.all()
    if cap is not None:
        monkeypatch.setattr(tt, "_ROUTE_SELECT_CAP", cap)
        assert not tt.route_is_dense(L, lay.s_max)
    base = jnp.int32(L - 1)
    plain = jax.jit(tt.route_rows)(codes, node, active, resting, bf,
                                   is_split, lm, base, la.clip)
    got = jax.jit(tt.route_rows)(codes, node, active, resting, bf, is_split,
                                 lm, base, la.clip, left_small)
    assert plain[3] is None
    for name, w, g in zip(("resting", "node", "active"), plain, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    nhalf, want = built_gather(got[1], got[2], left_small)
    assert got[3].dtype == want.dtype and got[3].shape == want.shape
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[1] >> 1), np.asarray(nhalf))
    if inactive < 1.0 and L >= 4:
        # rows are built on both sides, and some go to the derived child
        b, nd, act = (np.asarray(a) for a in (got[3], got[1], got[2]))
        assert (nd[b] % 2 == 0).any() and (nd[b] % 2 == 1).any()
        assert (act & ~b).any()
    else:
        assert np.asarray(got[3]).any() == (inactive < 1.0)


def test_the_dense_rule_is_on_static_shapes_alone():
    # 33 slots are 2 mask words a node, 256 slots 8: 4,096 words is the cap
    assert tt._ROUTE_SELECT_CAP == 4096
    assert all(tt.route_is_dense(L, 33) for L in (1, 32, 64, 512, 2048))
    assert not tt.route_is_dense(4096, 33)
    assert tt.route_is_dense(512, 256) and not tt.route_is_dense(1024, 256)
    assert tt._route_counts(6, 33) == (6, 0)  # the GBT cell
    assert tt._route_counts(10, 33) == (10, 0)  # RF at depth 10
    assert tt._route_counts(13, 33) == (12, 1)
    assert tt._route_counts(12, 2001) == (7, 5)  # one 2,001-slot column


@pytest.mark.parametrize("batch_cap", [None, 2],
                         ids=["whole_tree_program", "node_batched_grower"])
def test_a_tree_counts_its_levels_routed_each_way(monkeypatch, batch_cap):
    from shifu_tpu import obs

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
                       [False] * F, ["f%d" % i for i in range(F)], cfg)
        counters = obs.registry().snapshot()["counters"]
        # only the node-batched grower rebuilds a level in batches
        assert ("tree.hist.fallback_rebuilds" in counters) == bool(batch_cap)
        assert counters["train.trees"] == 2
        assert counters["tree.route.dense"] == 2 * D
        assert "tree.route.gather" not in counters
    finally:
        obs.reset()


def test_route_rows_where_no_node_splits():
    lay, la, (bf, br, rank_flat, _lv, is_split, _g, lm, _nc, _lc) = _level(
        MIXED, 4, seed=9)
    is_split = jnp.zeros_like(is_split)
    lm = jnp.zeros_like(lm)
    codes, node, active, resting = _rows(lay, 4, 500, seed=9)
    want = route_gather(codes, node, active, resting, bf, br, rank_flat,
                        is_split, jnp.int32(3), la.off, la.clip)
    got = tt.route_rows(codes, node, active, resting, bf, is_split, lm,
                        jnp.int32(3), la.clip)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.asarray(got[2]).any()


@pytest.mark.parametrize("cols,L,n_classes", [
    (MIXED, 8, 0), (WIDE, 4, 0), (MIXED, 8, 4)],
    ids=["regression", "slots256", "multiclass"])
def test_left_mask_is_rank_flat_against_cut_rank(cols, L, n_classes):
    """The identity the dense form rests on: on a scan's real output,
    lm[l, c] == (rank_flat[l, off[f_l] + c] <= cut_rank[l]) for every code c
    up to clip[f_l] of a node that splits, and lm is all False elsewhere."""
    lay, _la, out = _level(cols, L, seed=5, n_classes=n_classes)
    bf, br, rank_flat, _lv, is_split, _g, lm, _nc, _lc = (
        np.asarray(a) for a in out)
    assert is_split.any() and not is_split.all()
    for l in range(L):
        if not is_split[l]:
            assert not lm[l].any()
            continue
        f = bf[l]
        clip = lay.clip_max[f]
        c = np.arange(clip + 1)
        np.testing.assert_array_equal(
            lm[l, :clip + 1], rank_flat[l, lay.off[f] + c] <= br[l])
        assert not lm[l, clip + 1:].any()


# ---- the traced whole-tree program ----

def _scoped_eqns(jaxpr, outer=""):
    """(name stack, equation) of a jaxpr and of every jaxpr inside it. An
    inner jit (take_along_axis is one) starts its own name stack, so the
    caller's is carried down."""
    for e in jaxpr.eqns:
        stack = "/".join(p for p in (outer, str(e.source_info.name_stack))
                         if p)
        yield stack, e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scoped_eqns(inner, stack)


def _gathers(jaxpr, phases="route"):
    """(scope, operand shape, result shape) of every gather under a
    tree.L*/<phase> scope."""
    found = []
    for stack, e in _scoped_eqns(jaxpr):
        scope = re.match(r"tree\.L\d+/(%s)" % phases, stack)
        if e.primitive.name == "gather" and scope:
            found.append((scope.group(0), tuple(e.invars[0].aval.shape),
                          tuple(e.outvars[0].aval.shape)))
    return found


def test_whole_tree_program_routes_without_a_2d_gather():
    """At the GBT cell's columns, slots and depth (a small n): no gather
    whose operand is [n, F] (codes) or [L, T] (a level's rank table) under
    any tree.L*/route scope, so the per-row gathers cannot come back
    unnoticed."""
    n, F, slots, D = 512, 28, 33, 6
    lay = tt.make_layout([slots] * F, [False] * F)
    prog = tt._get_tree_program(D, lay, "variance", 5, 0.0,
                                sub_levels=(False,) + (True,) * (D - 1))
    jp = jax.make_jaxpr(prog.fn)(
        jnp.zeros((n, F), jnp.int32), jnp.zeros(n), jnp.ones(n),
        jnp.ones(lay.T, bool))
    stacks = {stack for stack, _e in _scoped_eqns(jp.jaxpr)}
    for d in range(D):
        assert any(s.startswith("tree.L%d/route" % 2**d) for s in stacks)
    two_d = {(n, F)} | {(2**d, lay.T) for d in range(D)}
    assert [g for g in _gathers(jp.jaxpr) if g[1] in two_d] == []

    # the reference form, traced the same way, is caught by this reader
    la = tt._device_layout(lay, np.ones(F, bool))

    def old(codes, node, active, resting, bf, br, rank_flat, is_split):
        with jax.named_scope("tree.L4/route"):
            return route_gather(codes, node, active, resting, bf, br,
                                rank_flat, is_split, 3, la.off, la.clip)

    zi = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    jp_old = jax.make_jaxpr(old)(zi(n, F), zi(n), jnp.ones(n, bool), zi(n),
                                 zi(4), zi(4), zi(4, lay.T),
                                 jnp.ones(4, bool))
    assert sorted(g[1] for g in _gathers(jp_old.jaxpr)
                  if g[1] in two_d) == [(4, lay.T), (n, F)]


def _row_gathers(jaxpr, n):
    """(scope, result shape) of every gather that hands out a value a row
    (a result whose leading axis is the rows') under a tree.L*/hist or
    tree.L*/route scope."""
    return [(scope, out) for scope, _operand, out
            in _gathers(jaxpr, "hist|route") if out[:1] == (n,)]


def test_forest_tree_program_has_no_gather_a_row(monkeypatch):
    """At the forest cell's columns, slots, depth and subtraction plan
    (benchmarks/configs/higgs_rf.json; a small n that is no level's width),
    with the kernels the chip runs (interpreted here: fused with subtraction
    up to 64 nodes, hist mode from 128): nothing under a level's hist or
    route scope is a gather with a value a row, so the build mask's
    `built_lsb[node >> 1]` (PR 34: 45 and 47 ms a tree at 128 and 256
    parents) cannot come back unnoticed."""
    n, F, slots, D = 600, 28, 33, 10
    lay = tt.make_layout([slots] * F, [False] * F)
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (True, True, True))
    key_before = set(tt._PROGRAMS)
    try:
        prog = tt._get_tree_program(D, lay, "variance", 5, 0.0,
                                    sub_levels=(False,) + (True,) * (D - 1))
    finally:
        for k in set(tt._PROGRAMS) - key_before:
            del tt._PROGRAMS[k]  # built under a steered state: never reuse
    jp = jax.make_jaxpr(prog.fn)(
        jnp.zeros((n, F), jnp.int32), jnp.zeros((n, F), jnp.int8),
        jnp.zeros(n), jnp.ones(n), jnp.ones(lay.T, bool))
    stacks = {stack for stack, _e in _scoped_eqns(jp.jaxpr)}
    for d in range(D):
        for what in ("hist", "route"):
            assert any(s.startswith("tree.L%d/%s" % (2**d, what))
                       for s in stacks), (d, what)
    assert _row_gathers(jp.jaxpr, n) == []

    # the form it replaced, traced under such a scope, is caught
    def old(node, active, left_small):
        with jax.named_scope("tree.L256/hist"):
            return built_gather(node, active, left_small)

    jp_old = jax.make_jaxpr(old)(jnp.zeros(n, jnp.int32), jnp.ones(n, bool),
                                 jnp.ones(128, bool))
    assert _row_gathers(jp_old.jaxpr, n) == [("tree.L256/hist", (n,))]
