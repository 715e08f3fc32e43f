"""Fused Pallas histogram→split-scan kernel vs the XLA references
(interpret mode on CPU; on TPU the same kernels compile via Mosaic — see
ops/hist_pallas.py for the densely packed layout and precision policy).

Covers the PR-11 acceptance matrix: hist parity vs the scatter
reference, in-kernel split scan == the reference split_scan on
ragged/wide layouts (33/65-wide segments, multi-chunk wide features),
RF forest BIT-parity kernel on vs off (binary + NATIVE multiclass),
GBT tolerance parity level- and leaf-wise, int8-code/bf16-plane bounds,
histogram-subtraction composition (built ratio still <= 0.55), and the
-Dshifu.pallas.* knob surface.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shifu_tpu.ops.hist_pallas import (  # noqa: E402
    _block_rows,
    _chunks,
    code_dtype,
    make_codes8_fn,
    make_fused_level_fn,
    make_pallas_hist_fn,
    pallas_active,
    wide_features,
)
from shifu_tpu.train.tree_trainer import (  # noqa: E402
    TreeTrainConfig,
    _device_layout,
    _make_hist_fn,
    _make_scan_fn,
    make_layout,
    train_trees,
)
from shifu_tpu.utils import environment


@pytest.fixture
def pallas_on():
    environment.set_property("shifu.pallas.mode", "on")
    try:
        yield
    finally:
        environment.set_property("shifu.pallas.mode", "")


def _set_mode(mode):
    environment.set_property("shifu.pallas.mode", mode)


def _ref_hist(L, lay, codes, y, w, node, active, n_classes=0):
    la = _device_layout(lay, np.ones(len(lay.slots), bool))
    fn = jax.jit(_make_hist_fn(L, lay, allow_matmul=False,
                               n_classes=n_classes))
    return np.asarray(fn(jnp.asarray(codes), jnp.asarray(y),
                         jnp.asarray(w), jnp.asarray(node),
                         jnp.asarray(active), la.off, la.clip, la.seg_t,
                         la.pos_t))


def _pallas_hist(L, lay, codes, y, w, node, active, n_classes=0,
                 low_precision=False, hoisted=False):
    """`hoisted`: the code operand made ahead by `make_codes8_fn`, as the
    whole-tree program is handed it; else the [n, F] entry turns it."""
    fn = jax.jit(make_pallas_hist_fn(L, lay, n_classes=n_classes,
                                     interpret=True,
                                     low_precision=low_precision))
    codes_t = (jax.jit(make_codes8_fn(lay))(jnp.asarray(codes)),) \
        if hoisted else ()
    return np.asarray(fn(jnp.asarray(codes), jnp.asarray(y),
                         jnp.asarray(w), jnp.asarray(node),
                         jnp.asarray(active), *codes_t))


def _mixed_case(n=1500, seed=0, full_range=False):
    rng = np.random.default_rng(seed)
    # narrow numerics + 33/65-wide categoricals (the Mosaic unaligned-
    # store shapes of the round-5 measured loss) + one wide categorical
    # that must split across chunks
    slots = [9] * 6 + [33, 65] + [1500]
    is_cat = [False] * 6 + [True] * 3
    hi = [s if full_range else s - 1 for s in slots]
    codes = np.stack(
        [rng.integers(0, h, size=n) for h in hi], 1).astype(np.int32)
    y = rng.random(n).astype(np.float32)
    w = rng.integers(1, 4, size=n).astype(np.float32)
    return slots, is_cat, codes, y, w, rng


# ---------------------------------------------------------------------------
# histogram parity
# ---------------------------------------------------------------------------


def test_pallas_matches_scatter_regression():
    slots, is_cat, codes, y, w, rng = _mixed_case()
    lay = make_layout(slots, is_cat)
    L = 8
    node = rng.integers(0, L, size=len(y)).astype(np.int32)
    active = rng.random(len(y)) < 0.9
    h_ref = _ref_hist(L, lay, codes, y, w, node, active)
    h_pl = _pallas_hist(L, lay, codes, y, w, node, active)
    # counts: integer weights sum exactly in f32 either way
    np.testing.assert_array_equal(h_ref[0], h_pl[0])
    # sums/sqsums: equal up to float summation order
    np.testing.assert_allclose(h_ref, h_pl, rtol=1e-5, atol=1e-3)


def test_pallas_matches_scatter_multiclass():
    slots, is_cat, codes, _y, w, rng = _mixed_case(seed=3)
    lay = make_layout(slots, is_cat)
    K, L = 4, 4
    cls = rng.integers(0, K, size=len(w)).astype(np.float32)
    node = rng.integers(0, L, size=len(w)).astype(np.int32)
    active = np.ones(len(w), bool)
    h_ref = _ref_hist(L, lay, codes, cls, w, node, active, n_classes=K)
    h_pl = _pallas_hist(L, lay, codes, cls, w, node, active, n_classes=K)
    np.testing.assert_array_equal(h_ref, h_pl)  # pure counts: exact


def test_bf16_plane_parity_bounds():
    """bf16 component planes: integer-weight COUNT plane stays exact
    (0/1-valued bf16 operands, f32 MXU accumulation); float moment
    planes land within bf16 rounding of the f32 reference."""
    slots, is_cat, codes, y, w, rng = _mixed_case(n=900, seed=5)
    lay = make_layout(slots, is_cat)
    L = 4
    node = rng.integers(0, L, size=len(y)).astype(np.int32)
    active = np.ones(len(y), bool)
    w1 = np.ones(len(y), np.float32)
    h_ref = _ref_hist(L, lay, codes, y, w1, node, active)
    h_pl = _pallas_hist(L, lay, codes, y, w1, node, active,
                        low_precision=True)
    np.testing.assert_array_equal(h_ref[0], h_pl[0])  # counts exact
    # moments: one bf16 rounding per plane value (~2^-8 relative)
    np.testing.assert_allclose(h_ref[1:], h_pl[1:], rtol=1e-2, atol=0.15)


# ---------------------------------------------------------------------------
# rows along the lanes: [C, n] planes and [1, n] node ids, at every level
# width a grower builds
# ---------------------------------------------------------------------------

_LANES_SLOTS = [9] * 6 + [33, 65]
_LANES_CAT = [False] * 6 + [True] * 2
# The code operand's two forms (PR 37), `[F, n]` with the rows along the
# lanes. "narrow": every feature within 128 slots, so int8 codes; the
# 9-slot features end inside an 8-column tile of MT, which then holds two
# features' columns. "wide": a feature of 1,500 slots turns the operand
# int32 and lies in several chunks, all pieces but the first with
# `lo` > 0; its codes leave their range on both sides, for the [n, F]
# entry to clip as the reference does.
_LANES_LAYOUTS = {
    "narrow": (_LANES_SLOTS, _LANES_CAT, np.int8),
    "wide": ([9, 1500, 33], [False, True, True], np.int32),
}


def _lanes_cases(levels, more):
    """(L, lowp, n_classes, layout, hoisted): every level, precision and
    plane kind on the narrow layout through the [n, F] entry, and `more`
    for each other pair of layout and entry (`hoisted`: the operand made
    ahead by `make_codes8_fn`, as the whole-tree program is handed it)."""
    cases = [(L, lowp, nc, "narrow", False) for nc in (0, 3)
             for lowp in (False, True) for L in levels]
    for layout, hoisted in [("narrow", True), ("wide", False),
                            ("wide", True)]:
        cases += [c + (layout, hoisted) for c in more]
    return [pytest.param(*c, id="-".join(
        [str(c[0]), "bf16" if c[1] else "f32",
         "classes3" if c[2] else "moments", c[3]] + ["hoisted"] * c[4]))
        for c in cases]


def _lanes_case(L, n_classes, n=1100, seed=17, layout="narrow"):
    """Integer weights and integer labels: every plane value is a small
    integer, exact in bf16 as in f32, so kernel and reference must agree
    BIT for bit at either precision. n = 1,100 is 2 blocks of 512 and a
    tail of 76 rows in a third: the 436 padded rows must add nothing.
    Inactive rows carry node ids the level does not have."""
    rng = np.random.default_rng(seed + L)
    slots = _LANES_LAYOUTS[layout][0]
    over = 0 if layout == "narrow" else 3  # codes past both ends
    codes = np.stack([rng.integers(-over, s + over, size=n) for s in slots],
                     1).astype(np.int32)
    w = rng.integers(1, 4, size=n).astype(np.float32)
    y = (rng.integers(0, n_classes, size=n) if n_classes
         else codes[:, 0] >= 4).astype(np.float32)
    active = rng.random(n) < 0.9
    node = np.where(active, rng.integers(0, L, size=n),
                    rng.integers(-3, L + 3, size=n)).astype(np.int32)
    return codes, y, w, node, active


@pytest.mark.parametrize(
    "L,lowp,n_classes,layout,hoisted",
    _lanes_cases([1, 4, 32, 64, 512],
                 [(1, False, 0), (4, True, 3), (32, True, 0),
                  (64, False, 3), (512, False, 0)]))
def test_hist_kernel_parity_rows_along_lanes(L, lowp, n_classes, layout,
                                             hoisted):
    """Against `hist_scatter`, bit for bit: the f32 cases are RF's
    planes."""
    slots, is_cat, _dt = _LANES_LAYOUTS[layout]
    lay = make_layout(slots, is_cat)
    codes, y, w, node, active = _lanes_case(L, n_classes, layout=layout)
    assert len(y) % 512 and _block_rows(len(y), 512) == 512
    h_ref = _ref_hist(L, lay, codes, y, w, node, active,
                      n_classes=n_classes)
    h_pl = _pallas_hist(L, lay, codes, y, w, node, active,
                        n_classes=n_classes, low_precision=lowp,
                        hoisted=hoisted)
    assert h_pl.shape == (3, L, lay.T)
    np.testing.assert_array_equal(h_ref, h_pl)
    # every active row lands once in feature 0's columns, no padded row does
    weight = h_pl.sum(0) if n_classes else h_pl[0]
    assert weight[:, :slots[0]].sum() == w[active].sum()


@pytest.mark.parametrize(
    "L,lowp,n_classes,layout,hoisted",
    _lanes_cases([1, 4, 32], [(1, False, 0), (4, True, 3), (8, False, 0)]))
def test_fused_level_parity_rows_along_lanes(L, lowp, n_classes, layout,
                                             hoisted):
    slots, is_cat, _dt = _LANES_LAYOUTS[layout]
    codes, y, w, _node, _active = _lanes_case(L, n_classes, layout=layout)
    h_ref, hist, ref, out = _run_scan_pair(
        slots, is_cat, codes, y, w, L=L,
        impurity="gini" if n_classes else "variance", n_classes=n_classes,
        low_precision=lowp, hoisted=hoisted)
    np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(hist))
    # the class impurities divide and square in another order in the
    # kernel: their gains agree to rounding, every choice exactly
    _assert_scan_equal(ref, out, exact_floats=not n_classes)


@pytest.mark.parametrize("n,blk,want", [
    (90, 512, 90),      # all the rows in one step: the block is the array
    (512, 512, 512),
    (700, 128, 128),
    (700, 100, 128),    # the knob in whole lanes once there is a second step
    (700, 8, 128),
    (5_500_000, 512, 512),
])
def test_block_rows_end_on_a_lane_boundary(n, blk, want):
    assert _block_rows(n, blk) == want


# ---------------------------------------------------------------------------
# densely packed chunk layout
# ---------------------------------------------------------------------------

_MIXED_SLOTS = [9] * 6 + [33, 65] + [1500]  # _mixed_case's


# (slots, target, features a chunk, wide features)
@pytest.mark.parametrize("slots,target,want_nf,want_wide", [
    (_MIXED_SLOTS, 1024, None, [8]),
    (_MIXED_SLOTS, 512, None, [8]),
    ([33] * 28, 512, [15, 13], []),   # HIGGS under the fused scan's cap
    ([33] * 28, 1024, [28], []),      # HIGGS in hist mode: one chunk
    ([850, 300], 1024, [1, 1], []),   # 300 does not fit beside 850
    ([1500], 1024, [1, 1], [0]),
    ([128] * 5, 512, [4, 1], []),     # exact lanes pack as they did
], ids=["mixed-1024", "mixed-512", "higgs-512", "higgs-1024", "850+300",
        "1500", "128x5"])
def test_chunks_cover_layout_densely_packed(slots, target, want_nf,
                                            want_wide):
    lay = make_layout(slots, [False] * len(slots))
    chunks = _chunks(lay, target)
    kept, cover, home = 0, [], {}
    for ci, ch in enumerate(chunks):
        assert ch.w % 128 == 0 and ch.w <= target
        end = 0
        for (f, lo, hi, col0) in ch.pieces:
            assert col0 == end  # pieces side by side, no gap between them
            end = col0 + hi - lo
            np.testing.assert_array_equal(ch.pos[col0:end],
                                          np.arange(lo, hi))
            assert (ch.seg[col0:end] == f).all()
            cover += [(f, c) for c in range(lo, hi)]
            home.setdefault(f, set()).add(ci)
        # only the tail past the last piece is dead, and under a lane
        assert ch.w - end < 128
        assert (ch.pos[end:] == -1).all() and (ch.seg[end:] == -1).all()
        assert not ch.scan_ok[end:].any()
        np.testing.assert_array_equal(ch.keep, np.arange(end))
        kept += len(ch.keep)
    assert kept == lay.T  # the tail drops at compaction, contract unchanged
    assert cover == [(f, c) for f, s in enumerate(slots) for c in range(s)]
    # a feature that fits lies whole in one chunk (its in-kernel scan sees
    # only that chunk); wider ones are the epilogue's XLA fallback
    assert wide_features(lay, target) == want_wide
    for f, s in enumerate(slots):
        assert (len(home[f]) == 1) == (s <= target)
    if want_nf is not None:
        assert [ch.f_hi - ch.f_lo for ch in chunks] == want_nf
    # int8 codes where every feature of the layout fits 128 slots: one
    # code operand a layout, whatever its chunks hold
    assert code_dtype(lay) == (np.int8 if max(slots) <= 128 else np.int32)


@pytest.mark.parametrize("n", [300, 512, 1100],
                         ids=["one-block", "whole-blocks", "ragged"])
@pytest.mark.parametrize("layout", list(_LANES_LAYOUTS))
def test_codes8_planes(layout, n):
    """The hoisted code operand: `[F, n]`, the rows along the lanes, every
    code clipped into its feature's slots, int8 where the layout's slot
    counts allow and int32 where one feature passes 128. It is handed over
    as long as the table (the entry pads it to whole blocks beside the
    planes): 1,100 rows stay 1,100."""
    slots, is_cat, want_dt = _LANES_LAYOUTS[layout]
    lay = make_layout(slots, is_cat)
    codes, *_ = _lanes_case(4, 0, n=n, layout=layout)
    codes8 = np.asarray(jax.jit(make_codes8_fn(lay))(jnp.asarray(codes)))
    assert codes8.dtype == want_dt and codes8.shape == (len(slots), n)
    np.testing.assert_array_equal(
        codes8, np.clip(codes, 0, np.asarray(slots) - 1).T)


# Tiles of MT's 8 columns at their worst: features of 3 and 5 slots lie
# three to a tile (two selects), one of 8 fills a tile alone and starts
# mid-tile, one of 1 slot, and a 40-slot feature runs over five tiles and
# ends mid-tile. 77 live columns of 128: the dead tail is 6 tiles.
_TILE_SLOTS = [3, 3, 5, 8, 1, 40, 3, 3, 3, 8]
_TILE_CAT = [False, True] * 5


@pytest.mark.parametrize("hoisted", [False, True], ids=["turned", "hoisted"])
@pytest.mark.parametrize("n_classes", [0, 3, 4, 7],
                         ids=["moments", "C3", "C4", "C7"])
@pytest.mark.parametrize("L", [1, 16, 256])
def test_hist_kernel_parity_several_features_a_tile(L, n_classes, hoisted):
    """Bit for bit against `hist_scatter` on f32 planes (integer weights
    and labels): pieces that end anywhere in a sublane tile, C = 3 and
    C > 3 (native multi-class counts, past the stacked LHS from C x L >
    128), L = 256 (the forest's deepest built level), a ragged last block,
    out-of-range codes on both sides against the clip."""
    lay = make_layout(_TILE_SLOTS, _TILE_CAT)
    rng = np.random.default_rng(5 + L + n_classes)
    n = 1100
    codes = np.stack([rng.integers(-2, s + 2, size=n) for s in _TILE_SLOTS],
                     1).astype(np.int32)
    w = rng.integers(1, 4, size=n).astype(np.float32)
    y = (rng.integers(0, n_classes, size=n) if n_classes
         else rng.integers(0, 3, size=n)).astype(np.float32)
    node = rng.integers(0, L, size=n).astype(np.int32)
    active = rng.random(n) < 0.8
    node[~active] = L + 5
    h_ref = _ref_hist(L, lay, codes, y, w, node, active, n_classes=n_classes)
    h_pl = _pallas_hist(L, lay, codes, y, w, node, active,
                        n_classes=n_classes, hoisted=hoisted)
    assert h_pl.shape == (max(n_classes, 3), L, lay.T)
    np.testing.assert_array_equal(h_ref, h_pl)
    # a code past either end lands in its feature's first or last slot
    f, first = 5, int(lay.off[5])
    weight = h_pl.sum(0) if n_classes else h_pl[0]
    want_first = w[active & (codes[:, f] <= 0)].sum()
    want_last = w[active & (codes[:, f] >= _TILE_SLOTS[f] - 1)].sum()
    assert weight[:, first].sum() == want_first
    assert weight[:, first + _TILE_SLOTS[f] - 1].sum() == want_last


@pytest.mark.parametrize("slots,L,lowp,do_scan", [
    ([33] * 28, 16, True, False), ([33] * 28, 16, True, True),
    ([33] * 28, 256, False, False), ([33] * 28, 32, False, True),
    (_TILE_SLOTS, 4, False, False), (_TILE_SLOTS, 4, False, True),
    ([9, 1500, 33], 4, False, False), ([9, 1500, 33], 4, False, True)],
    ids=["higgs-L16-hist", "higgs-L16-fused", "higgs-L256-hist",
         "higgs-L32-fused", "tiles-hist", "tiles-fused", "wide-hist",
         "wide-fused"])
def test_the_kernels_body_holds_the_accumulate_dots_alone(slots, L, lowp,
                                                          do_scan):
    """No selection matmul is left in a body: of the equations Pallas is
    handed, the only `dot_general` outside the last step's scan are the
    accumulate's, one a group of components, each `[group x rows, blk]`
    against MT `[W, blk]` over the row axis, and none has an operand with a
    feature a row (`[nf, W]` or `[blk, nf]`, the old `codes_f @ sel`). And
    the body stays small, since a body is traced and lowered in every
    process's set-up: 2 equations a feature (its row's slice and its
    broadcast), 1 a piece (a straddled tile's select) and a fixed 50.
    HIGGS at L = 16 reads 123 in hist mode (28 features, W = 1,024) and 88
    fused (15, W = 512); the parent's bodies 44 and 46; PR 37's form, a
    compare a tile of 8 columns and a `want` a slab, 343 and 190."""
    from shifu_tpu.ops import hist_pallas as hp

    lay = make_layout(slots, [False] * len(slots))
    target = hp._target(fused=do_scan)
    scan_key = ("variance", 1, 0.0, 0) if do_scan else None
    n, blk, C = 1024, 512, 3
    comp_dt = jnp.bfloat16 if lowp else jnp.float32
    for ci, ch in enumerate(hp._chunks(lay, target)[:3]):
        call = hp._build_call(lay.key, target, ci, L, C, blk, lowp, scan_key,
                              False)
        args = [jnp.zeros((len(slots), n), hp.code_dtype(lay)),
                jnp.zeros((C, n), comp_dt), jnp.zeros((1, n), jnp.int32)]
        if do_scan:
            args.append(jnp.ones((1, ch.w), jnp.float32))
        jaxpr = jax.make_jaxpr(call)(*args)
        (pc,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name ==
                 "pallas_call"]
        body = pc.params["jaxpr"]
        top = [e for e in body.eqns if e.primitive.name != "cond"]
        dots = [e for e in top if e.primitive.name == "dot_general"]
        rows_f, _ = hp._code_window(ch, lay)
        sub = 16 if lowp else 8
        tiled = -(-L // sub) * sub
        group, rows = (C, tiled) if C * tiled <= 128 else (1, L)
        assert len(dots) == C // group
        for e in dots:
            lhs, rhs = (v.aval.shape for v in e.invars)
            assert lhs == (group * rows, blk) and rhs == (ch.w, blk)
            assert e.params["dimension_numbers"] == (((1,), (1,)), ((), ()))
        nf = ch.f_hi - ch.f_lo
        assert len(top) <= 50 + 2 * nf + len(ch.pieces), len(top)


# ---------------------------------------------------------------------------
# in-kernel split scan == reference split_scan
# ---------------------------------------------------------------------------


def _run_scan_pair(slots, is_cat, codes, y, w, L, impurity, n_classes=0,
                   min_inst=2, seed=7, wmax=None, low_precision=False,
                   hoisted=False):
    rng = np.random.default_rng(seed)
    n = len(y)
    lay = make_layout(slots, is_cat)
    node = rng.integers(0, L, size=n).astype(np.int32)
    active = rng.random(n) < 0.95
    feat_ok = np.ones(len(slots), bool)
    fot = jnp.asarray(feat_ok[lay.seg_of_t])
    la = _device_layout(lay, feat_ok)
    if wmax is not None:
        environment.set_property("shifu.pallas.wmax", str(wmax))
    try:
        h_ref = jax.jit(_make_hist_fn(L, lay, allow_matmul=False,
                                      n_classes=n_classes))(
            jnp.asarray(codes), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(node), jnp.asarray(active), la.off, la.clip,
            la.seg_t, la.pos_t)
        scan = jax.jit(_make_scan_fn(L, lay.T, lay.s_max, impurity,
                                     min_inst, 0.0, n_classes))
        ref = scan(h_ref, fot, la.is_cat_t, la.seg_t, la.pos_t,
                   la.start_t, la.size_t, la.off, la.clip,
                   int(lay.slots[0]))
        fused = jax.jit(make_fused_level_fn(
            L, lay, impurity, min_inst, 0.0, n_classes=n_classes,
            interpret=True, low_precision=low_precision))
        codes_t = (jax.jit(make_codes8_fn(lay))(jnp.asarray(codes))
                   if hoisted else None)
        hist, out = fused(jnp.asarray(codes), codes_t, jnp.asarray(y),
                          jnp.asarray(w), jnp.asarray(node),
                          jnp.asarray(active), fot)
    finally:
        if wmax is not None:
            environment.set_property("shifu.pallas.wmax", "")
    return h_ref, hist, ref, out


def _assert_scan_equal(ref, out, exact_floats):
    names = ("feature", "cut_rank", "rank_flat", "leaf_value", "is_split",
             "best_gain", "left_mask", "node_cnt", "left_cnt")
    for nm, a, b in zip(names, ref, out):
        a, b = np.asarray(a), np.asarray(b)
        if nm in ("best_gain", "leaf_value", "node_cnt", "left_cnt"):
            if exact_floats:
                np.testing.assert_array_equal(a, b, err_msg=nm)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3,
                                           err_msg=nm)
        else:
            np.testing.assert_array_equal(a, b, err_msg=nm)


@pytest.mark.parametrize("impurity", ["variance", "friedmanmse",
                                      "entropy", "gini"])
def test_fused_scan_matches_reference_ragged(impurity):
    """All four impurities over the ragged 33/65-wide + multi-chunk-wide
    layout. Integer 0/1 labels x integer weights make every plane an
    exact integer sum, so even gains/leaves must be BIT-equal between
    the pairwise-rank kernel formulation and the lexsort reference."""
    slots, is_cat, codes, _y, w, rng = _mixed_case(n=1300, seed=11)
    y = (codes[:, 0] >= 4).astype(np.float32)  # 0/1: exact planes
    h_ref, hist, ref, out = _run_scan_pair(slots, is_cat, codes, y, w,
                                           L=4, impurity=impurity)
    np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(hist))
    _assert_scan_equal(ref, out, exact_floats=True)


def test_fused_scan_matches_reference_float_labels():
    """GBT-shaped float labels: discrete outputs (feature, cut, ranks,
    masks, split flags) still match exactly; float stats within
    summation-order tolerance."""
    slots, is_cat, codes, y, w, _rng = _mixed_case(n=1300, seed=12)
    _h, _hist, ref, out = _run_scan_pair(slots, is_cat, codes, y, w,
                                         L=4, impurity="variance")
    _assert_scan_equal(ref, out, exact_floats=False)


def test_fused_scan_matches_reference_multiclass():
    slots, is_cat, codes, _y, w, rng = _mixed_case(n=1100, seed=13)
    K = 4
    cls = rng.integers(0, K, size=len(w)).astype(np.float32)
    _h, _hist, ref, out = _run_scan_pair(slots, is_cat, codes, cls, w,
                                         L=2, impurity="entropy",
                                         n_classes=K)
    _assert_scan_equal(ref, out, exact_floats=True)


def test_fused_scan_chunk_tail_never_splits_fitting_feature():
    """Regression (PR-11 review): a feature that FITS one chunk must
    never straddle a chunk tail — its in-kernel scan only sees its own
    chunk's columns, so a tail split would scan partial histograms
    while staying off the wide-feature XLA fallback. slots=[850, 300]
    at wmax 1024 is exactly that shape: f0 pads to 896, leaving 128
    columns of tail that must NOT receive a piece of f1."""
    rng = np.random.default_rng(21)
    slots = [850, 300]
    is_cat = [True, True]
    lay = make_layout(slots, is_cat)
    chunks = _chunks(lay, 1024)
    assert wide_features(lay, 1024) == []
    for ch in chunks:  # every piece covers its whole feature
        for (f, lo, hi, _c0) in ch.pieces:
            assert (lo, hi) == (0, slots[f])
    n = 1200
    codes = np.stack([rng.integers(0, s, size=n) for s in slots],
                     1).astype(np.int32)
    y = (codes[:, 1] >= 150).astype(np.float32)
    w = np.ones(n, np.float32)
    _h, _hist, ref, out = _run_scan_pair(slots, is_cat, codes, y, w,
                                         L=2, impurity="variance")
    _assert_scan_equal(ref, out, exact_floats=True)


def test_fused_scan_narrow_wmax_multichunk():
    """A small -Dshifu.pallas.wmax forces EVERY feature wider than one
    chunk onto the XLA fallback and splits the narrow ones across many
    chunks — the composed result must still equal the reference."""
    slots, is_cat, codes, _y, w, rng = _mixed_case(n=900, seed=14)
    y = (codes[:, 1] >= 5).astype(np.float32)
    lay = make_layout(slots, is_cat)
    assert wide_features(lay, 256) == [8]
    assert len(_chunks(lay, 256)) > len(_chunks(lay, 1024))
    _h, _hist, ref, out = _run_scan_pair(slots, is_cat, codes, y, w,
                                         L=2, impurity="variance",
                                         wmax=256)
    _assert_scan_equal(ref, out, exact_floats=True)


# ---------------------------------------------------------------------------
# end-to-end forest parity, kernel on vs off
# ---------------------------------------------------------------------------


def _forest_data(n=2500, seed=0):
    rng = np.random.default_rng(seed)
    slots = [17] * 5 + [33, 65]
    is_cat = [False] * 5 + [True] * 2
    codes = np.stack([rng.integers(0, s - 1, size=n) for s in slots],
                     1).astype(np.int32)
    y = ((codes[:, 0] >= 8).astype(np.int8)
         | (codes[:, 5] >= 20).astype(np.int8)).astype(np.float32)
    noise = rng.random(n) < 0.15
    y = np.where(noise, 1.0 - y, y).astype(np.float32)
    w = np.ones(n, np.float32)
    cols = [f"f{i}" for i in range(len(slots))]
    return codes, y, w, slots, is_cat, cols


def _run_mode(mode, codes, y, w, slots, is_cat, cols, cfg):
    _set_mode(mode)
    try:
        return train_trees(codes, y, w, slots, is_cat, cols, cfg)
    finally:
        _set_mode("")


def _assert_forests_bit_equal(a, b):
    assert len(a.spec.trees) == len(b.spec.trees)
    for t0, t1 in zip(a.spec.trees, b.spec.trees):
        np.testing.assert_array_equal(t0.feature, t1.feature)
        np.testing.assert_array_equal(t0.left_mask, t1.left_mask)
        np.testing.assert_array_equal(t0.leaf_value, t1.leaf_value)


def test_rf_bit_parity_fused_kernel_binary():
    """PR-3 gate under the fused kernel: RF integer-weight planes stay
    f32 and exact, so the forest is BIT-equal kernel on vs off —
    subtraction composition included (depth 4 engages the derive
    chain)."""
    codes, y, w, slots, is_cat, cols = _forest_data()
    cfg = TreeTrainConfig(algorithm="RF", tree_num=3, max_depth=4,
                          feature_subset_strategy="TWOTHIRDS", seed=3,
                          valid_set_rate=0.1)
    off = _run_mode("off", codes, y, w, slots, is_cat, cols, cfg)
    on = _run_mode("on", codes, y, w, slots, is_cat, cols, cfg)
    _assert_forests_bit_equal(off, on)
    assert off.valid_error == on.valid_error


def test_rf_bit_parity_fused_kernel_multiclass():
    codes, _y, w, slots, is_cat, cols = _forest_data(seed=4)
    rng = np.random.default_rng(9)
    y3 = np.clip(codes[:, 0] // 6 + rng.integers(0, 2, len(w)),
                 0, 2).astype(np.float32)
    cfg = TreeTrainConfig(algorithm="RF", tree_num=2, max_depth=3,
                          impurity="gini", n_classes=3, seed=5)
    off = _run_mode("off", codes, y3, w, slots, is_cat, cols, cfg)
    on = _run_mode("on", codes, y3, w, slots, is_cat, cols, cfg)
    _assert_forests_bit_equal(off, on)


def test_gbt_tolerance_parity_levelwise():
    """GBT under the kernel: bf16 planes + matvec summation order means
    tolerance parity, not bit parity — scores must stay close."""
    codes, y, w, slots, is_cat, cols = _forest_data(seed=6)
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=4, max_depth=4,
                          learning_rate=0.3, seed=7, valid_set_rate=0.1)
    off = _run_mode("off", codes, y, w, slots, is_cat, cols, cfg)
    on = _run_mode("on", codes, y, w, slots, is_cat, cols, cfg)
    s_off = off.spec.independent().compute(codes)
    s_on = on.spec.independent().compute(codes)
    np.testing.assert_allclose(s_on, s_off, atol=0.03)


def test_gbt_tolerance_parity_leafwise():
    codes, y, w, slots, is_cat, cols = _forest_data(seed=8, n=1500)
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=2, max_depth=6,
                          max_leaves=7, learning_rate=0.3, seed=9)
    off = _run_mode("off", codes, y, w, slots, is_cat, cols, cfg)
    on = _run_mode("on", codes, y, w, slots, is_cat, cols, cfg)
    s_off = off.spec.independent().compute(codes)
    s_on = on.spec.independent().compute(codes)
    np.testing.assert_allclose(s_on, s_off, atol=0.03)


def test_subtraction_composition_built_ratio(pallas_on):
    """Histogram subtraction composes with the fused kernel: the kernel
    grows only the smaller child, the sibling derives as parent − built,
    and the built-histogram counters keep the <= 0.55 acceptance ratio
    of the subtraction-off run."""
    from shifu_tpu import obs

    codes, y, w, slots, is_cat, cols = _forest_data(n=1200, seed=10)
    trees, depth = 2, 4
    cfg = TreeTrainConfig(algorithm="GBT", tree_num=trees,
                          max_depth=depth, seed=1)
    cfg_off = TreeTrainConfig(**{**cfg.__dict__, "hist_subtraction": False})

    def counters():
        snap = obs.registry().snapshot().get("counters", {})
        return {k.split(".")[-1]: v for k, v in snap.items()
                if k.startswith("tree.hist.")}

    obs.reset()
    train_trees(codes, y, w, slots, is_cat, cols, cfg)
    c_on = counters()
    obs.reset()
    train_trees(codes, y, w, slots, is_cat, cols, cfg_off)
    c_off = counters()
    leaves = 2 ** depth
    assert c_on["built"] == trees * (leaves // 2)
    assert c_on["derived"] == trees * (leaves // 2 - 1)
    assert c_on["built"] / c_off["built"] <= 0.55


# ---------------------------------------------------------------------------
# knob surface
# ---------------------------------------------------------------------------


def test_mode_knob_resolution():
    """auto = off on the CPU harness; on = forced with interpret mode;
    off = XLA. (On a TPU backend auto resolves to the compiled
    kernel.)"""
    try:
        _set_mode("auto")
        assert pallas_active() == (False, False)  # CPU harness
        _set_mode("off")
        assert pallas_active() == (False, False)
        _set_mode("on")
        assert pallas_active() == (True, True)  # interpret off-TPU
        _set_mode("bogus")
        assert pallas_active() == (False, False)  # falls back to auto
    finally:
        _set_mode("")


@pytest.mark.parametrize("narrow", [False, True], ids=["int32", "int8"])
def test_shaping_knobs_and_profiler_annotation(narrow):
    """-Dshifu.pallas.blk/.wmax override the VMEM shaping (the kernel-
    tuning sweep seam), the overridden kernel still matches the scatter
    reference exactly, and the chosen shaping lands in the profiler
    snapshot so every manifest records what produced its numbers: how the
    three row operands lie, and how many chunks read int8 codes (all, or
    none where a feature passes 128 slots)."""
    from shifu_tpu import obs
    from shifu_tpu.ops.hist_pallas import blk_setting, wmax_setting

    slots, is_cat, codes, y, w, rng = _mixed_case(n=700)
    if narrow:  # the 1,500-slot column folded into 120 slots
        slots, codes = slots[:-1] + [120], codes % 120
    lay = make_layout(slots, is_cat)
    L = 4
    node = rng.integers(0, L, size=len(y)).astype(np.int32)
    active = rng.random(len(y)) < 0.9
    h_ref = _ref_hist(L, lay, codes, y, w, node, active)

    environment.set_property("shifu.pallas.blk", "128")
    environment.set_property("shifu.pallas.wmax", "256")
    obs.reset()
    try:
        assert blk_setting() == 128 and wmax_setting() == 256
        # the narrower wmax splits the packed layout into more
        # chunks
        assert len(_chunks(lay)) > len(_chunks(lay, target=1024))
        h_pl = _pallas_hist(L, lay, codes, y, w, node, active)
        np.testing.assert_array_equal(h_ref[0], h_pl[0])
        np.testing.assert_allclose(h_ref, h_pl, rtol=2e-5, atol=1e-4)
        ann = obs.profiler().snapshot()["annotations"]["ops.hist_pallas"]
        assert ann["blk"] == 128 and ann["wMax"] == 256
        assert ann["rowLayout"] == "planes[C,n] node[1,n] codes[F,n]"
        assert ann["chunks"] == len(_chunks(lay))
        assert ann["int8Chunks"] == (len(_chunks(lay)) if narrow else 0)
        assert ann["mode"] in ("auto", "on", "off")
    finally:
        environment.set_property("shifu.pallas.blk", "")
        environment.set_property("shifu.pallas.wmax", "")
    assert blk_setting() == 512 and wmax_setting() == 1024
