"""Fused Pallas TPU kernel: bin-code gather → per-node histogram
accumulate → split gain scan, with low-precision planes.

The tree builder's hot op (dt/DTWorker.java:851 featureUpdate, fused by
SURVEY §7.5 into "the histogram kernel") is

    hist[c, l, t] = Σ_i comps[c, i] · (node[i] == l) · (code_t[i] == t)

followed immediately by the split gain scan over the [C, L, T] result.
The XLA lowering in tree_trainer materializes the [blk, T] (or, hoisted,
the full [n, T]) code one-hot M in HBM between the compare and the
matmul, and round-trips the histogram to HBM between the build dispatch
and the scan. This kernel keeps BOTH in VMEM:

    grid (row blocks)  — per-chunk VMEM-resident [L, W] accumulator per
                         component, revisited across the grid (init at
                         block 0, += afterwards)
    per block          — the chunk's code one-hot MT [W, blk] is built
                         with the ROWS ALONG THE LANES and no matmul: a
                         feature's code row is broadcast down the
                         sublanes of that feature's columns and ONE
                         compare with what each column stands for gives
                         the one-hot, over a DENSELY PACKED column
                         layout (below); the component planes times the
                         node one-hot, [C x L, blk], contract the row
                         axis with MT on the MXU, both along their lanes
    last block         — the split scan runs in-kernel on the resident
                         planes (pairwise-rank formulation, below) and
                         emits per-column gain/rank/left-count planes,
                         so the histogram never has to be re-read from
                         HBM by a second scan dispatch

Operands, all zero-padded to whole blocks of rows and all three with the
ROWS ALONG THE LANES: component planes `[C, n_pad]` (bf16 or f32) and
node ids `[1, n_pad]` (int32), which the wrapper makes every level, and
the codes `[F, n_pad]` (`make_codes8_fn`: clipped, int8 where every
feature of the layout has at most 128 slots, int32 otherwise), which a
grower makes ONCE A CALL, on one chip and on each chip's shard under a
mesh, so that no cut, cast or layout copy of the code matrix is left
inside a tree, only the operand's pad to whole blocks; an entry handed
`codes [n, F]` alone turns it where it pads the planes. Blocks `(C, blk)`, `(1, blk)` and `(rows of F,
blk)`, where a chunk's kernel reads the smallest aligned run of sublane
tiles that holds its features (`_code_window`; all 28 rows for HIGGS,
16 KB a step) and takes them by static sublane slices. As `[n, C]`,
`[n, 1]` and `[n, nf]` they would put 3, 1 or 13 to 28 values on the
128-lane axis: XLA writes, the DMA moves and the step's vregs hold 128
lanes a row for them (2.8 GB written a level for 22 MB of node ids at
5.5 M rows; 128 B a row of an int8 chunk and 512 B of the int32 matrix
for its codes), the `[blk, L]` LHS has to be turned for the MXU, and a
row's code of feature f has to be moved sideways into the 33 columns of
that feature by a matmul of its own (`codes_f @ sel`, 256 of the step's
304 `vmatmul` until PR 38). This way the node one-hot `[L, blk]` and
the code one-hot `[W, blk]` are each sublane broadcasts of rows and one
compare, and `A [.., blk] x MT [W, blk]^T` is the MXU's own transposed
weight push. While C x L (L in whole sublane tiles) fits the MXU's 128
rows, the C components share ONE stacked LHS, so MT is pushed as
weights once a step and not once a component; wider levels stream
enough rows a component to pay for their own push and keep a dot each
(static shapes alone decide). PERF.md, sections 5 and 6, has what each
costs on the chip.

Three changes over the round-5 kernel (which was slower than the XLA
lowering and shipped dark behind an env var):

1. DENSELY PACKED COLUMN LAYOUT, NO PER-FEATURE STORES. The old kernel
   wrote each feature's one-hot segment at its raw flat-T offset with
   per-run slice stores; 33/65-wide segments land mid-lane and Mosaic
   emits masked unaligned lane stores. The rebuilt kernel builds the
   one-hot with no store at all: the code row of the feature that holds
   a tile of 8 columns (of the two features, where a tile straddles a
   boundary: one select more) is broadcast down the tile's sublanes,
   the tiles lie one under the other as `[W, blk]`, and one compare
   with the static slot-position column, handed over the same in all
   128 lanes, gives the one-hot; Mosaic packs the compare's masks and
   pushes them to the MXU as they are (PR 38; until then a static
   selection matmul, codes_f32 @ E, moved each column's code into place
   first, and was half of a grid step's MXU time). Every row's
   broadcast and every straddle's mask is made once a body, in plain
   `lax`: 2 operations a feature and 1 a straddle, so that a body costs
   what the old one did to trace and lower (a first form, a compare a
   tile of 8 columns and a `want` a slab of 128, was as fast on the chip
   and three times as dear to trace: PERF.md, section 6, PR 38).
   A chunk's feature pieces sit SIDE BY SIDE in the columns and only
   the chunk's total width is rounded up to 128 (the tail is dead
   columns, which stand for -1 and match no code, are masked out of the
   gain scan and dropped at the [C, L, T] compaction — the output
   contract is unchanged).
   Until PR 29 every piece started at a 128-lane boundary, a leftover
   of the per-feature stores: nothing in this kernel finds a column by
   its position (the scan goes by the seg / size / iscat metadata
   rows), so the alignment bought nothing and cost HIGGS's 28 x 33
   slots 3,584 columns for 924 and 7 calls a level for 2 (PERF.md,
   section 6).

2. LOW-PRECISION PLANES. Bin codes travel int8 in HBM where every
   feature of the layout fits 128 slots (a layout with a wider feature
   keeps int32 codes for all its chunks: one operand, one dtype).
   GBT gradient/hessian component planes travel bf16 with f32 MXU
   accumulation (`preferred_element_type`); RF planes stay f32 so
   integer-weight counts stay exact and PR-3's bit-parity gate holds
   bit-for-bit.

3. IN-KERNEL SPLIT SCAN. After the last grid step the kernel computes,
   per (node, candidate column), the cumulative left/right stats IN THE
   REFERENCE'S MEAN-SORTED ORDER without sorting: left(a) = Σ_b
   IND[b, a] · h[b] where IND[b, a] = [b's (sec, index) lex-≤ a's,
   same segment] — a [W, W] indicator built from one exact
   eye-transpose of the sec row plus static column metadata, applied as
   C matvecs on the MXU per node. rank(a) = Σ_b IND[b, a] − 1
   reproduces the lexsort rank exactly (stable ties included), so the
   emitted (gain, rank, left-count) planes are combinable with the XLA
   reference scan epilogue: argmax with the reference's ordered-position
   tie-break, rank_flat for row routing, the model-facing left mask.
   Features too wide for one scan chunk (> min(wmax, _SCAN_W_CAP)
   padded columns) fall back to the XLA reference scan on just their
   columns of the compacted histogram — the kernel masks them out of
   its own scan.

Numerics: counts and integer-weight moments are exact under any
summation order (< 2^24): RF histograms are BIT-equal kernel on vs off,
forests too off the chip (on it the scan's MXU matvecs round: PERF.md,
section 7); GBT float planes differ by association and one bf16 rounding.

Mode selection is the cataloged knob `-Dshifu.pallas.mode`:
  auto  (default) kernel on TPU backends, XLA elsewhere
  on    kernel everywhere — interpret mode off-TPU (CPU tests)
  off   XLA lowering everywhere
(The round-5 `SHIFU_PALLAS` env var is retired; docs/KNOBS.md has the
catalog row.)
"""

from __future__ import annotations

import functools
import time
from typing import List, Optional

import numpy as np

_LANE = 128  # TPU lane width: a chunk's total width is a multiple of it

# VMEM budget shaping: rows per grid step x max padded chunk columns.
# The row operands' blocks, the [W, W] scan indicator + C [L, W] planes
# must sit well under ~16 MB (MT itself never leaves the vregs).
# Overridable per PROCESS (-Dshifu.pallas.blk / -Dshifu.pallas.wmax) so
# kernel-tuning rounds can sweep shapings
# without code edits — per process because the built kernels are cached
# (_build_call lru, tree_trainer's program cache): set the knobs at
# launch, one process per shaping.
# The chosen values land in the profiler snapshot (obs.profile
# annotations, process-global so a later obs scope still reports them)
# so every manifest records which shaping produced its numbers.
_BLK = 512
_W_MAX = 1024
# the in-kernel scan holds a [W, W] f32 indicator scratch plus several
# [W, W] compare temporaries per node of its L-times unrolled loop, and
# Mosaic's scoped-VMEM stack (16 MiB on a v5e) grows with W^2 and with L.
# Compiled for a described v5e: at W = 1024 only L <= 2 fits (L = 8 asks
# 27.5 MiB); at W = 512 every level the grower fuses (L <= 32, f32 and
# bf16 planes) compiles on tests/test_chip_compile.py's layouts. So
# fused-scan chunking clamps to 512 columns even when
# -Dshifu.pallas.wmax asks for wider (hist-only chunks honor the raw
# knob); tests/test_chip_compile.py holds the rule to the compiler.
_SCAN_W_CAP = 512
# rows of the MXU's systolic array (128 x 128 on a v5e): the most the
# accumulate step's stacked LHS may hold (`group` in _build_call)
_MXU_ROWS = 128


def blk_setting() -> int:
    """shifu.pallas.blk — rows per grid step (default 512; `_block_rows`
    rounds it up to whole lanes once the rows need a second step)."""
    from shifu_tpu.utils import environment

    return max(8, environment.get_int("shifu.pallas.blk", _BLK))


def wmax_setting() -> int:
    """shifu.pallas.wmax — max one-hot columns per VMEM chunk (1024)."""
    from shifu_tpu.utils import environment

    return max(_LANE, environment.get_int("shifu.pallas.wmax", _W_MAX))


def pallas_mode() -> str:
    """shifu.pallas.mode — auto | on | off (default auto)."""
    from shifu_tpu.utils import environment

    m = (environment.get_property("shifu.pallas.mode", "auto")
         or "auto").strip().lower()
    return m if m in ("auto", "on", "off") else "auto"


def _on_tpu() -> bool:
    # errors rise: a backend that fails to start must not read as "not a
    # TPU" and turn the kernel off in silence
    import jax

    return jax.default_backend() == "tpu"


def pallas_active() -> tuple:
    """(enabled, interpret) for the current process.

    auto = the default: kernel on TPU, XLA elsewhere. on =
    forced everywhere, interpret mode off-TPU (the CPU test harness).
    off = XLA everywhere."""
    mode = pallas_mode()
    if mode == "off":
        return False, False
    if mode == "on":
        return True, not _on_tpu()
    return _on_tpu(), False


def _pad_lane(w: int) -> int:
    return -(-w // _LANE) * _LANE


class _Chunk:
    """One kernel chunk: a contiguous run of feature pieces (f, lo, hi,
    col0) packed side by side from column 0, the total rounded up to the
    128-lane boundary, plus the static per-column metadata the kernel and
    the epilogue need. Only the tail past the last piece is dead
    (`pos` = `seg` = -1, `scan_ok` = 0)."""

    __slots__ = ("pieces", "w", "f_lo", "f_hi", "pos", "seg", "size",
                 "iscat", "scan_ok", "seg0", "t_idx", "keep", "start")

    def __init__(self, pieces, lay, whole):
        self.pieces = pieces
        self.f_lo = pieces[0][0]
        self.f_hi = pieces[-1][0] + 1
        w = _pad_lane(pieces[-1][3] + pieces[-1][2] - pieces[-1][1])
        self.w = w
        pos = np.full(w, -1, np.int32)
        seg = np.full(w, -1, np.int32)
        size = np.ones(w, np.int32)
        iscat = np.zeros(w, np.int32)
        scan_ok = np.zeros(w, np.int32)
        seg0 = np.zeros(w, np.float32)
        t_idx = np.full(w, -1, np.int64)
        start = np.zeros(w, np.int32)
        for (f, lo, hi, col0) in pieces:
            cw = hi - lo
            sl = slice(col0, col0 + cw)
            pos[sl] = np.arange(lo, hi, dtype=np.int32)
            seg[sl] = f
            size[sl] = int(lay.slots[f])
            iscat[sl] = int(bool(lay.is_cat_t[lay.off[f]]))
            scan_ok[sl] = int(whole[f])
            seg0[sl] = 1.0 if f == 0 else 0.0
            t_idx[sl] = np.arange(int(lay.off[f]) + lo,
                                  int(lay.off[f]) + hi, dtype=np.int64)
            start[sl] = int(lay.off[f])
        self.pos, self.seg, self.size, self.iscat = pos, seg, size, iscat
        self.scan_ok, self.seg0, self.t_idx = scan_ok, seg0, t_idx
        self.start = start
        self.keep = np.nonzero(pos >= 0)[0].astype(np.int64)


def _target(fused: bool = False, target: Optional[int] = None) -> int:
    """Most columns a chunk may hold, in whole lanes: `target`, else the
    wmax knob (under the fused scan clamped to `_SCAN_W_CAP`)."""
    if target is None:
        target = wmax_setting()
        if fused:
            target = min(target, _SCAN_W_CAP)
    return max(_LANE, (target // _LANE) * _LANE)


def _chunks(lay, target: Optional[int] = None) -> List[_Chunk]:
    """Split the flat T axis into chunks of <= target columns, feature
    pieces packed side by side (a piece starts where the last one ended;
    the chunk's width alone is rounded up to 128 lanes). A feature that
    fits the target lies whole in one chunk; a feature wider than the
    target spans several pieces/chunks (and is then excluded from the
    in-kernel scan — the epilogue's XLA fallback owns it). Chunks cover
    whole features of [0, T) in order, so the caller can hand the kernel
    a contiguous column slice of the code matrix."""
    target = _target(target=target)
    slots = [int(s) for s in lay.slots]
    whole = [s <= target for s in slots]
    chunks: List[_Chunk] = []
    cur: List[tuple] = []
    cur_w = 0
    for f, s in enumerate(slots):
        lo = 0
        while lo < s:
            avail = target - cur_w
            # a chunk-fitting feature must NEVER straddle a chunk tail:
            # its in-kernel scan sees only its own chunk's columns, so a
            # split would scan partial histograms — start a fresh chunk
            # instead (only over-wide features split, and those are the
            # epilogue's XLA-fallback set; a piece of one joins a chunk
            # only for a lane or more)
            fresh = s > avail if whole[f] else avail < _LANE
            if fresh:
                chunks.append(_Chunk(cur, lay, whole))
                cur, cur_w = [], 0
                continue
            take = min(s - lo, avail)
            cur.append((f, lo, lo + take, cur_w))
            cur_w += take
            lo += take
    if cur:
        chunks.append(_Chunk(cur, lay, whole))
    return chunks


def wide_features(lay, target: Optional[int] = None) -> List[int]:
    """Features too wide for one chunk at this shaping — scanned by the
    XLA reference fallback instead of the in-kernel scan."""
    target = _target(target=target)
    return [f for f, s in enumerate(int(x) for x in lay.slots)
            if s > target]


def kernel_calls(lay, fused: bool) -> int:
    """Mosaic calls one histogram build makes: the layout's chunks under
    the fused scan's column cap, or in hist mode."""
    return len(_chunks(lay, _target(fused)))


def kernel_name(do_scan: bool) -> str:
    """The name the kernel carries in the compiled program (`pallas_call`'s
    `name` and `metadata["kernel"]`) and in the run manifest."""
    return "tree_fused_level" if do_scan else "tree_hist"


def code_dtype(lay):
    """The code operand's dtype, from the layout's slot counts alone: int8
    where every feature has at most 128 slots, int32 otherwise."""
    return np.int8 if int(max(lay.slots, default=0)) <= _LANE else np.int32


def _code_window(ch: _Chunk, lay) -> tuple:
    """(rows, block index) of the block of the `[F, n]` code operand a
    chunk's kernel reads: the smallest power-of-two run of the dtype's
    sublane tiles that holds the chunk's features in one aligned block, or
    all F rows (the block is then the array's whole first axis)."""
    rows = 32 if code_dtype(lay) == np.int8 else 8
    while ch.f_lo // rows != (ch.f_hi - 1) // rows:
        rows *= 2
    n_feat = len(lay.slots)
    return (n_feat, 0) if rows >= n_feat else (rows, ch.f_lo // rows)


@functools.lru_cache(maxsize=None)
def _build_call(lay_key: tuple, target: int, ci: int, L: int, C: int,
                blk: int, lowp: bool, scan_key, interpret: bool):
    """One chunk's pallas_call builder, cached per static configuration.

    Returns call(codes_t [F, n], comps [C, n], node [1, n],
    featok [1, W] or None in hist mode) -> (C hist planes [L, W], + when
    scan_key: gain [L, W], rank [L, W], lcnt [L, W], tot0 [L, C]).

    scan_key = None (hist-only) or (impurity, min_inst, min_gain,
    n_classes)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from shifu_tpu.train.tree_trainer import make_layout

    lay = make_layout(list(lay_key[0]), list(lay_key[1]))
    ch = _chunks(lay, target)[ci]
    W = ch.w
    f_rows, f_blk = _code_window(ch, lay)
    do_scan = scan_key is not None
    name = kernel_name(do_scan)
    comp_dt = jnp.bfloat16 if lowp else jnp.float32
    # the accumulate step's LHS: all C components stacked, each on L
    # rounded up to whole sublane tiles of the planes' dtype, while that
    # fits the MXU's rows; past it a component streams enough rows of its
    # own to pay for its push of MT, and each keeps its dot
    sub = 16 if lowp else 8
    tiled = -(-L // sub) * sub
    group, rows = (C, tiled) if C * tiled <= _MXU_ROWS else (1, L)
    if do_scan:
        impurity, min_inst, min_gain, n_classes = scan_key
        use_entropy = impurity == "entropy"

    # MT's recipe, from static shapes alone: a tile of 8 columns
    # [r0, r0 + 8) names the pieces that hold them, each as (the feature's
    # row in the code block, the tile's first sublane the piece holds)
    f_base = f_blk * f_rows
    tile_rows = [
        [(f - f_base, max(col0 - r0, 0)) for (f, lo, hi, col0) in ch.pieces
         if col0 < r0 + 8 and col0 + hi - lo > r0]
        for r0 in range(0, W, 8)]

    # static column metadata rides in as [1, W] / [W, 1] inputs (vector
    # constants are inputs, not closure captures, in Mosaic): what each
    # column stands for as a column, for MT (the wrapper hands it over the
    # same in all 128 lanes: a lane broadcast in the step would be 288 XLU
    # operations and 11 % more bundles), and the scan's rows
    posc_np = ch.pos[:, None]
    pos_np = ch.pos[None, :]
    seg_row_np = ch.seg[None, :]
    seg_col_np = ch.seg[:, None]
    iscat_np = ch.iscat[None, :]
    size_np = ch.size[None, :].astype(np.float32)
    seg0_np = ch.seg0[:, None]

    def kernel(*refs):
        codes_ref, comps_ref, node_ref, posc_ref = refs[:4]
        k = 4
        if do_scan:
            (featok_ref, pos_ref, segr_ref, segc_ref, iscat_ref, size_ref,
             seg0_ref) = refs[k:k + 7]
            k += 7
        hist_refs = refs[k:k + C]
        k += C
        if do_scan:
            gain_ref, rank_ref, lcnt_ref, tot0_ref = refs[k:k + 4]
            wsq_ref, sec_ref, secT_ref = refs[k + 4:k + 7]

        i = pl.program_id(0)
        grid_n = pl.num_programs(0)

        @pl.when(i == 0)
        def _init():
            for out_ref in hist_refs:
                out_ref[...] = jnp.zeros_like(out_ref)

        # ---- MT [W, blk], the code one-hot with the rows along the
        # lanes, built with no dot and no store: a feature's code row is
        # broadcast down the sublanes of the tiles of 8 columns it holds
        # (a tile that straddles two features selects between two rows),
        # the tiles lie one under the other, and ONE compare with what
        # each column stands for gives the one-hot; the dead tail stands
        # for -1, which no clipped code is. Plain `lax` on numpy scalars,
        # every row and every mask made once: a `jnp` operation is a
        # traced call of its own, and what a body costs to trace and
        # lower it costs in every process's set-up (PERF.md, section 6,
        # PR 38) ----
        lax = jax.lax
        codes = codes_ref[...].astype(jnp.int32)  # [f_rows, blk]
        sub8 = lax.broadcasted_iota(jnp.int32, (8, blk), 0)
        code_rows, from_sub = {}, {}

        def code_row(r):
            if r not in code_rows:
                code_rows[r] = lax.broadcast_in_dim(
                    lax.slice_in_dim(codes, r, r + 1, axis=0), (8, blk),
                    (0, 1))
            return code_rows[r]

        def tile_codes(pieces):
            code = sub8  # a dead tile: any code
            for n, (r, s0) in enumerate(pieces):
                if n and s0 not in from_sub:
                    from_sub[s0] = lax.ge(sub8, np.int32(s0))
                code = (lax.select(from_sub[s0], code_row(r), code) if n
                        else code_row(r))
            return code

        code = lax.concatenate([tile_codes(p) for p in tile_rows], 0)
        # what each column stands for, the same in every lane: the
        # [W, 128] operand side by side as often as the block is wide
        want = lax.concatenate([posc_ref[...]] * -(-blk // _LANE), 1)
        if want.shape[1] != blk:
            want = lax.slice_in_dim(want, 0, blk, axis=1)
        mt = lax.eq(code, want).astype(comp_dt)  # [W, blk]

        # rows along the lanes: the node one-hot is a sublane broadcast
        # of the [1, blk] ids. The components of one group share a
        # stacked LHS, so MT is pushed to the MXU as weights once a group
        # and not once a component; a component's rows start on a tile
        # boundary, and those past L match no node id
        comps = comps_ref[...]  # [C, blk]
        oh_node = (node_ref[...] == lax.broadcasted_iota(
            jnp.int32, (rows, blk), 0)).astype(comp_dt)
        for c0 in range(0, C, group):
            A = jnp.concatenate([comps[c:c + 1, :] * oh_node
                                 for c in range(c0, c0 + group)], axis=0)
            contrib = lax.dot_general(
                A, mt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [group * rows, W]
            for k in range(group):
                hist_refs[c0 + k][...] += contrib[k * rows:k * rows + L, :]

        if not do_scan:
            return

        # ---- fused split scan on the VMEM-resident planes (last step):
        # the reference's mean-sorted cumulative stats via the pairwise
        # lex-≤ indicator — no sort, all matmul/elementwise ----
        @pl.when(i == grid_n - 1)
        def _scan():
            eps = 1e-12
            # the reference keys empty category slots with +inf so they
            # sort last; the eye-transpose matmul would turn 0*inf into
            # NaN, so use a huge FINITE sentinel — same ordering, same
            # stable index tie-break among empties
            big = 3.0e38
            h = [hist_refs[c][...] for c in range(C)]  # [L, W] f32
            if n_classes >= 3:
                cnt = h[0]
                ex = jnp.zeros_like(cnt)
                for c in range(1, C):
                    cnt = cnt + h[c]
                for c in range(C):
                    ex = ex + float(c) * h[c]
                mean = jnp.where(cnt > 0, ex / jnp.maximum(cnt, eps), big)
            else:
                cnt, s1 = h[0], h[1]
                mean = jnp.where(cnt > 0, s1 / jnp.maximum(cnt, eps), big)
            posf = pos_ref[...].astype(jnp.float32)  # [1, W]
            sec_ref[...] = jnp.where(
                iscat_ref[...] != 0, mean,
                jnp.broadcast_to(posf, (L, W)))
            # exact data transpose via an in-kernel identity matmul:
            # secT[b, l] = sec[l, b] (1.0 * x sums with zeros — exact)
            wsq_ref[...] = (jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
                            == jax.lax.broadcasted_iota(
                                jnp.int32, (W, W), 1)).astype(jnp.float32)
            secT_ref[...] = jax.lax.dot_general(
                wsq_ref[...], sec_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [W, L]

            seg_eq = segc_ref[...] == segr_ref[...]  # [W, W] static
            tie = (jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
                   <= jax.lax.broadcasted_iota(jnp.int32, (W, W), 1))
            fok = featok_ref[...]  # [1, W] f32, tail/wide already 0
            sizef = size_ref[...]  # [1, W] f32
            gain_rows, rank_rows, lcnt_rows = [], [], []
            for l in range(L):
                sec_r = sec_ref[l:l + 1, :]    # [1, W]
                sec_c = secT_ref[:, l:l + 1]   # [W, 1]
                lt = sec_c < sec_r
                eq = sec_c == sec_r
                inc = lt | (eq & tie)          # lex-≤ on (sec, index)
                wsq_ref[...] = jnp.where(seg_eq & inc, 1.0, 0.0)
                ind = wsq_ref[...]
                left = [jax.lax.dot_general(
                    h[c][l:l + 1, :], ind, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for c in range(C)]  # [1, W] each
                rank = jnp.sum(ind, axis=0, keepdims=True) - 1.0
                wsq_ref[...] = jnp.where(seg_eq & ~inc, 1.0, 0.0)
                indr = wsq_ref[...]
                right = [jax.lax.dot_general(
                    h[c][l:l + 1, :], indr, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for c in range(C)]

                if n_classes >= 3:
                    lc = left[0]
                    rc = right[0]
                    for c in range(1, C):
                        lc = lc + left[c]
                        rc = rc + right[c]
                    tc = lc + rc

                    def mass(parts, total):
                        acc = None
                        for c in range(C):
                            p = parts[c] / jnp.maximum(total, eps)
                            if use_entropy:
                                t = -p * (jnp.log2(jnp.maximum(p, eps)))
                            else:
                                t = p * p
                            acc = t if acc is None else acc + t
                        if use_entropy:
                            return total * acc
                        return total * (1.0 - acc)

                    tot = [left[c] + right[c] for c in range(C)]
                    g = (mass(tot, tc) - mass(left, lc) - mass(right, rc))
                else:
                    lc, ls1, ls2 = left
                    rc, rs1, rs2 = right
                    tc, ts1, ts2 = lc + rc, ls1 + rs1, ls2 + rs2
                    if impurity == "entropy":
                        def emass(c_, p_):
                            pr = p_ / jnp.maximum(c_, eps)
                            q = 1.0 - pr
                            hh = -(pr * jnp.log2(jnp.maximum(pr, eps))
                                   + q * jnp.log2(jnp.maximum(q, eps)))
                            return c_ * hh

                        g = emass(tc, ts1) - emass(lc, ls1) - emass(rc,
                                                                    rs1)
                    elif impurity == "gini":
                        def gmass(c_, p_):
                            ng = c_ - p_
                            return c_ - (p_ * p_ + ng * ng) / jnp.maximum(
                                c_, eps)

                        g = gmass(tc, ts1) - gmass(lc, ls1) - gmass(rc,
                                                                    rs1)
                    elif impurity == "friedmanmse":
                        ml = ls1 / jnp.maximum(lc, eps)
                        mr = rs1 / jnp.maximum(rc, eps)
                        g = (lc * rc / jnp.maximum(tc, eps)
                             * (ml - mr) ** 2)
                    else:  # variance

                        def sse(c_, s_, q_):
                            return q_ - s_ * s_ / jnp.maximum(c_, eps)

                        g = (sse(tc, ts1, ts2) - sse(lc, ls1, ls2)
                             - sse(rc, rs1, rs2))

                valid = ((lc >= min_inst) & (rc >= min_inst)
                         & (g > min_gain) & (fok > 0)
                         & (rank < sizef - 1.0))
                gain_rows.append(jnp.where(valid, g, -jnp.inf))
                rank_rows.append(rank)
                lcnt_rows.append(lc)
            gain_ref[...] = jnp.concatenate(gain_rows, axis=0)
            rank_ref[...] = jnp.concatenate(rank_rows, axis=0)
            lcnt_ref[...] = jnp.concatenate(lcnt_rows, axis=0)
            # node totals = segment-0 column sums (the reference's
            # seg0-cumsum endpoint), summed across chunks outside
            tot_cols = [jax.lax.dot_general(
                hist_refs[c][...], seg0_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) for c in range(C)]
            tot0_ref[...] = jnp.concatenate(tot_cols, axis=1)  # [L, C]

    def call(codes_t, comps, node_row, featok=None):
        import jax.numpy as jnp

        grid = codes_t.shape[1] // blk
        in_specs = [
            pl.BlockSpec((f_rows, blk), lambda i: (f_blk, i)),
            pl.BlockSpec((C, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((W, _LANE), lambda i: (0, 0)),
        ]
        args = [codes_t, comps, node_row,
                jnp.broadcast_to(jnp.asarray(posc_np), (W, _LANE))]
        if do_scan:
            in_specs += [
                pl.BlockSpec((1, W), lambda i: (0, 0)),
                pl.BlockSpec((1, W), lambda i: (0, 0)),
                pl.BlockSpec((1, W), lambda i: (0, 0)),
                pl.BlockSpec((W, 1), lambda i: (0, 0)),
                pl.BlockSpec((1, W), lambda i: (0, 0)),
                pl.BlockSpec((1, W), lambda i: (0, 0)),
                pl.BlockSpec((W, 1), lambda i: (0, 0)),
            ]
            args += [featok.astype(jnp.float32), jnp.asarray(pos_np),
                     jnp.asarray(seg_row_np), jnp.asarray(seg_col_np),
                     jnp.asarray(iscat_np), jnp.asarray(size_np),
                     jnp.asarray(seg0_np)]
        out_specs = [pl.BlockSpec((L, W), lambda i: (0, 0))
                     for _ in range(C)]
        out_shape = [jax.ShapeDtypeStruct((L, W), jnp.float32)
                     for _ in range(C)]
        if do_scan:
            out_specs += [pl.BlockSpec((L, W), lambda i: (0, 0))] * 3 \
                + [pl.BlockSpec((L, C), lambda i: (0, 0))]
            out_shape += [jax.ShapeDtypeStruct((L, W), jnp.float32)] * 3 \
                + [jax.ShapeDtypeStruct((L, C), jnp.float32)]
        scratch = []
        if do_scan:
            scratch = [pltpu.VMEM((W, W), jnp.float32),
                       pltpu.VMEM((L, W), jnp.float32),
                       pltpu.VMEM((W, L), jnp.float32)]
        t_trace = time.perf_counter()
        outs = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name=name,
            # `name` is the op's kernel_name attribute, which the HLO text
            # of a profiler trace does not print; `metadata` is printed,
            # as frontend_attributes={kernel_metadata={...}}, and is what
            # tells this kernel and its level from any other in a trace
            metadata={"kernel": name, "L": str(L)},
        )(*args)
        if isinstance(codes_t, jax.core.Tracer):
            _record_kernel_trace(t_trace, name, L, ci, W)
        return outs

    return call


def _record_kernel_trace(start: float, kernel: str, L: int, chunk: int,
                         W: int) -> None:
    """Span `tree.kernel.trace` and counter `tree.kernel.traces`: one
    `pallas_call` made while a program is traced, from `start` to now,
    which is where the kernel's body is traced into a jaxpr of its own:
    summed, what a process pays for kernel bodies before its first tree
    (PERF.md, sections 6 and 7, PR 39). A ring event like `jax.trace`
    (obs/jaxprobe.py), not a `with` span: it carries the span path open on
    the tracing thread as its `parent` and opens no frame under the call
    (a frame more under a traced body is dearer on a chip's host than the
    body: PERF.md, section 6, PR 39)."""
    from shifu_tpu.obs import registry, tracer

    registry().counter("tree.kernel.traces").inc()
    tr = tracer()
    tr.record("tree.kernel.trace", start, time.perf_counter(),
              tr.current_path(),
              {"kernel": kernel, "L": L, "chunk": chunk, "W": W})


def _block_rows(n: int, blk: int) -> int:
    """Rows a grid step takes of n: all of them where the `blk` knob
    holds them (the block is then the whole array, whatever n is), else
    the knob in whole lanes: a (1, blk) block of the row operands ends on
    a 128-lane boundary."""
    return n if n <= blk else _pad_lane(blk)


def _pad_rows(arr, blk: int, axis: int):
    """`arr` with zeros after its rows (along `axis`) up to whole blocks:
    a padded row carries zero planes and adds nothing to node 0."""
    import jax.numpy as jnp

    pad = -arr.shape[axis] % blk
    if not pad:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths)


def _row_operands(labels, weights, active, node_slot, L: int,
                  n_classes: int, dtype, blk: int):
    """The kernel's two per-row operands, rows along the lanes and padded
    to whole blocks: component planes [C, n_pad] (tree_trainer's, with
    inactive rows zeroed out via the weight) and node ids [1, n_pad]. On
    the sublanes ([n, C], [n, 1]) each row would take a 128-lane tile row
    of its own, in HBM and in every block's DMA (PERF.md, section 6,
    PR 31)."""
    import jax.numpy as jnp

    from shifu_tpu.train.tree_trainer import _make_comps_of

    planes = _make_comps_of(n_classes)(
        jnp.where(active, weights, 0.0), labels)
    comps = jnp.stack(planes, 0).astype(dtype)
    nl = jnp.where(active, jnp.clip(node_slot, 0, L - 1), 0)
    return _pad_rows(comps, blk, 1), _pad_rows(nl[None, :], blk, 1)


def _annotate(lay, chunks, L, do_scan, lowp, interpret):
    from shifu_tpu.obs import profile as _profile

    i8_chunks = len(chunks) if code_dtype(lay) == np.int8 else 0
    _profile.annotate(
        "ops.hist_pallas", kernel=kernel_name(do_scan), blk=blk_setting(),
        # how the three per-row operands lie: rows along the lanes
        rowLayout="planes[C,n] node[1,n] codes[F,n]",
        wMax=wmax_setting(), chunks=len(chunks), L=int(L), T=int(lay.T),
        paddedT=int(sum(c.w for c in chunks)), fusedScan=bool(do_scan),
        bf16Planes=bool(lowp), int8Chunks=int(i8_chunks),
        mode=pallas_mode(), interpret=bool(interpret))


def make_codes8_fn(lay):
    """jit-able (codes [n, F] i32) -> the kernel's code operand `[F, n]`,
    rows along the lanes: clipped to [0, clip_max], int8 where every
    feature of the layout has at most 128 slots and int32 otherwise
    (`code_dtype`). Codes are node-, label- and tree-independent, so a
    grower makes it once a call, on one chip and under `shard_map` on each
    chip's own rows, and hands it to every tree and level."""
    import jax
    import jax.numpy as jnp

    def build(codes):
        with jax.named_scope("tree.codes"):
            return jnp.clip(codes, 0, jnp.asarray(lay.clip_max)[None, :]
                            ).astype(code_dtype(lay)).T

    return build


def _code_operand(lay, codes, codes_t, blk: int):
    """The code operand in whole blocks of rows: the hoisted `[F, n]` one
    where the caller made it, else `codes [n, F]` turned here. The pad is
    all a tree keeps of the operand's making (one a tree: every level
    writes it and the compiler keeps the first), under `tree.codes` inside
    the level's `hist`, where `tests/benchmark/test_scope_readers.py` and
    `tree_codes_ms_per_tree` look for it."""
    import jax

    if codes_t is None:
        codes_t = make_codes8_fn(lay)(codes)
    with jax.named_scope("tree.codes"):
        return _pad_rows(codes_t, blk, 1)


def make_pallas_hist_fn(L: int, lay, n_classes: int = 0,
                        interpret: bool = False,
                        low_precision: bool = False):
    """Histogram-only kernel entry: traced fn (codes, labels, weights,
    node_slot, active, codes_t=None) -> [C, L, T] matching tree_trainer's
    histogram contract (the hist-subtraction built-child, budget-batched,
    leaf-wise and streamed/shard_map call sites). `codes_t` is
    `make_codes8_fn`'s operand where the caller hoisted it; without it
    `codes [n, F]` is turned here. `interpret=True` runs the kernels in
    pallas interpret mode (CPU tests)."""
    import jax.numpy as jnp

    C = n_classes if n_classes >= 3 else 3
    blk_max = blk_setting()
    target = _target()
    chunks = _chunks(lay, target)
    comp_dt = jnp.bfloat16 if low_precision else jnp.float32
    _annotate(lay, chunks, L, False, low_precision, interpret)

    def hist_fn(codes, labels, weights, node_slot, active, codes_t=None):
        blk = _block_rows(codes.shape[0], blk_max)
        comps_p, node_p = _row_operands(labels, weights, active, node_slot,
                                        L, n_classes, comp_dt, blk)
        codes_p = _code_operand(lay, codes, codes_t, blk)
        parts = []
        for ci, ch in enumerate(chunks):
            call = _build_call(lay.key, target, ci, L, C, blk,
                               low_precision, None, interpret)
            outs = call(codes_p, comps_p, node_p)
            planes = jnp.stack(outs[:C])  # [C, L, W]
            parts.append(planes[:, :, jnp.asarray(ch.keep)])
        return (parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=2))  # [C, L, T]

    return hist_fn


def make_fused_level_fn(L: int, lay, impurity: str, min_inst: int,
                        min_gain: float, n_classes: int = 0,
                        interpret: bool = False,
                        low_precision: bool = False):
    """Fused histogram + split-scan entry for one tree level.

    Traced fn (codes, codes8, labels, weights, node_slot, active,
    feat_ok_t) -> (hist [C, L, T], scan) where `scan` is the reference
    split_scan 9-tuple (feature, cut_rank, rank_flat, leaf_value,
    is_split, best_gain, left_mask, node_cnt, left_cnt) — drop-in for
    tree_trainer's per-level hist+scan pair. `codes8` is
    `make_codes8_fn`'s `[F, n]` operand where the caller hoisted it, or
    None: `codes [n, F]` is then turned here."""
    import jax.numpy as jnp

    C = n_classes if n_classes >= 3 else 3
    blk_max = blk_setting()
    target = _target(fused=True)
    chunks = _chunks(lay, target)
    wide = wide_features(lay, target)
    comp_dt = jnp.bfloat16 if low_precision else jnp.float32
    scan_key = (impurity, int(min_inst), float(min_gain), int(n_classes))
    T, s_max = lay.T, lay.s_max
    _annotate(lay, chunks, L, True, low_precision, interpret)

    # static epilogue maps over the padded column space
    start_all = np.concatenate([ch.start for ch in chunks])
    seg_all = np.concatenate([ch.seg for ch in chunks])
    keep_all = np.concatenate(
        [ch.keep + off for ch, off in zip(
            chunks, np.cumsum([0] + [c.w for c in chunks[:-1]]))])
    # XLA-fallback sub-layout for chunk-spanning wide features
    if wide:
        wide_cols = np.concatenate(
            [np.arange(int(lay.off[f]), int(lay.off[f]) + int(lay.slots[f]),
                       dtype=np.int64) for f in wide])
        w_slots = np.asarray([int(lay.slots[f]) for f in wide], np.int32)
        w_off = np.zeros(len(wide), np.int32)
        w_off[1:] = np.cumsum(w_slots[:-1])
        w_seg = np.repeat(np.arange(len(wide), dtype=np.int32), w_slots)
        w_pos = np.arange(int(w_slots.sum()), dtype=np.int32) - w_off[w_seg]
        w_start = w_off[w_seg]
        w_size = w_slots[w_seg]
        w_iscat = np.asarray(
            [bool(lay.is_cat_t[lay.off[f]]) for f in wide])[w_seg]
        w_clip = np.maximum(w_slots - 1, 0)
        w_smax = int(w_slots.max())
        wide_arr = np.asarray(wide, np.int32)
        from shifu_tpu.train.tree_trainer import _make_scan_fn

        wide_scan = _make_scan_fn(L, int(w_slots.sum()), w_smax, impurity,
                                  min_inst, min_gain, n_classes)
    off_c = np.asarray(lay.off)
    clip_c = np.asarray(lay.clip_max)

    def fused_fn(codes, codes8, labels, weights, node_slot, active,
                 feat_ok_t):
        blk = _block_rows(codes.shape[0], blk_max)
        comps_p, node_p = _row_operands(labels, weights, active, node_slot,
                                        L, n_classes, comp_dt, blk)
        codes_p = _code_operand(lay, codes, codes8, blk)
        fok_f = feat_ok_t.astype(jnp.float32)

        hist_parts, gain_parts, rank_parts, lcnt_parts = [], [], [], []
        tot0 = None
        for ci, ch in enumerate(chunks):
            call = _build_call(lay.key, target, ci, L, C, blk,
                               low_precision, scan_key, interpret)
            # dynamic per-tree feature mask folded with the static
            # scannable/tail mask into one [1, W] plane
            t_clamp = np.where(ch.t_idx >= 0, ch.t_idx, 0)
            fok = (fok_f[jnp.asarray(t_clamp)]
                   * jnp.asarray((ch.scan_ok > 0)
                                 & (ch.pos >= 0), np.float32))[None, :]
            outs = call(codes_p, comps_p, node_p, fok)
            planes = jnp.stack(outs[:C])
            hist_parts.append(planes[:, :, jnp.asarray(ch.keep)])
            gain_parts.append(outs[C])
            rank_parts.append(outs[C + 1])
            lcnt_parts.append(outs[C + 2])
            tot0 = outs[C + 3] if tot0 is None else tot0 + outs[C + 3]

        hist = (hist_parts[0] if len(hist_parts) == 1
                else jnp.concatenate(hist_parts, axis=2))  # [C, L, T]
        gain_all = jnp.concatenate(gain_parts, axis=1)  # [L, ΣW]
        rank_all = jnp.concatenate(rank_parts, axis=1)
        lcnt_all = jnp.concatenate(lcnt_parts, axis=1)

        # kernel-side best with the reference's ordered-position
        # tie-break: o = segment start + within-segment rank
        o_all = jnp.asarray(start_all, jnp.float32)[None, :] + rank_all
        gmax = jnp.max(gain_all, axis=-1)
        cand = gain_all == gmax[:, None]
        obest = jnp.min(jnp.where(cand, o_all, jnp.inf), axis=-1)
        best = jnp.argmax(cand & (o_all == obest[:, None]), axis=-1)
        pick = lambda a: jnp.take_along_axis(  # noqa: E731
            a, best[:, None], axis=-1)[:, 0]
        feature = jnp.asarray(seg_all)[best].astype(jnp.int32)
        cut_rank = pick(rank_all).astype(jnp.int32)
        left_cnt = pick(lcnt_all)
        best_gain = gmax

        # rank_flat over the ORIGINAL flat columns (row routing + mask)
        rank_flat = rank_all[:, jnp.asarray(keep_all)].astype(jnp.int32)

        if wide:
            sub = wide_scan(
                hist[:, :, jnp.asarray(wide_cols)],
                fok_f[jnp.asarray(wide_cols)] > 0,
                jnp.asarray(w_iscat), jnp.asarray(w_seg),
                jnp.asarray(w_pos), jnp.asarray(w_start),
                jnp.asarray(w_size), jnp.asarray(w_off),
                jnp.asarray(w_clip), int(w_slots[0]))
            (f_w, cut_w, rank_w, _lv, _sp, g_w, _lm, _nc, lc_w) = sub
            f_wg = jnp.asarray(wide_arr)[f_w]
            o_w = jnp.asarray(off_c)[f_wg].astype(jnp.float32) \
                + cut_w.astype(jnp.float32)
            take_w = (g_w > best_gain) | ((g_w == best_gain)
                                          & (o_w < obest))
            feature = jnp.where(take_w, f_wg, feature)
            cut_rank = jnp.where(take_w, cut_w, cut_rank)
            left_cnt = jnp.where(take_w, lc_w, left_cnt)
            best_gain = jnp.where(take_w, g_w, best_gain)
            rank_flat = rank_flat.at[:, jnp.asarray(wide_cols)].set(rank_w)

        is_split = jnp.isfinite(best_gain)

        # node stats from the segment-0 totals (summed across chunks)
        if n_classes >= 3:
            node_cnt = tot0.sum(axis=1)
            leaf_value = jnp.argmax(tot0, axis=1).astype(jnp.float32)
        else:
            node_cnt = tot0[:, 0]
            leaf_value = tot0[:, 1] / jnp.maximum(node_cnt, 1e-12)

        # model-facing mask over ORIGINAL codes [L, s_max] (reference
        # formula, from the merged rank_flat)
        s_range = jnp.arange(s_max, dtype=jnp.int32)
        f_clip = jnp.asarray(clip_c)[feature]
        s_idx = jnp.minimum(s_range[None, :], f_clip[:, None])
        flat_idx = jnp.asarray(off_c)[feature][:, None] + s_idx
        ranks = jnp.take_along_axis(rank_flat, flat_idx, axis=-1)
        left_mask = (
            (ranks <= cut_rank[:, None])
            & (s_range[None, :] <= f_clip[:, None])
            & is_split[:, None]
        )
        return hist, (feature, cut_rank, rank_flat, leaf_value, is_split,
                      best_gain, left_mask, node_cnt, left_cnt)

    return fused_fn
