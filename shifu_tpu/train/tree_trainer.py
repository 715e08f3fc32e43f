"""GBT/RF histogram tree builder — fused scatter-add histograms, level-wise
or leaf-wise growth, per-tree checkpoint/resume.

What DTMaster/DTWorker do across a Hadoop cluster (SURVEY §3.2: workers
accumulate per-node per-feature bin histograms via Impurity.featureUpdate
dt/DTWorker.java:851, master merges + picks best split per node
dt/DTMaster.java:274-360) happens here as jit programs over a FLAT
per-feature slot layout:

    histogram  [3, L, T]  T = sum(slots_f): each feature owns exactly its
               own slot segment, so one 10k-category column no longer
               inflates every feature's histogram (the reference budgets
               node batches by stats memory, DTMaster.java:450-467 — here
               the node-batch size L is sized from MaxStatsMemoryMB over
               the true T). Built by ONE scatter-add over the [n, F] code
               matrix; row-sharded inputs all-reduce (psum) the histogram
               when run on a mesh.
    split scan ordered prefix sums per (node, feature segment): numeric
               segments keep code order, categorical segments sort by label
               mean (lexsort within static segment boundaries); gain by
               impurity (variance/friedmanmse: dt/Impurity.java:106,255;
               entropy/gini via binary counts :368,553).
    growth     level-wise (default) or LEAF-WISE under maxLeaves
               (DTMaster.java:137, toSplitQueue :260-271): best-gain leaf
               splits first, explicit child pointers.
    reuse      histogram SUBTRACTION (train.params.treeHistSubtraction,
               default on): each split's children partition the parent's
               rows, so every level >= 1 builds only the SMALLER child of
               each split as a half-width histogram and derives the
               sibling as parent − built (LightGBM/XGBoost recurrence);
               leaf-wise growth derives the second frontier child from the
               retained parent for free. RF planes under unit/integer
               sample weights are integer-valued in f32 and subtract
               BIT-EXACTLY; float planes (GBT residuals, fractional RF
               significance) retain the parent chain in f64 when jax x64
               is on. Memory-gated by
               MaxStatsMemoryMB (fallback = full rebuild, counted);
               `tree.hist.built/derived/fallback_rebuilds` counters land
               in run ledgers.

GBT parity (dt/DTWorker.java:1470-1486): tree 0 weight 1.0, later trees
weight=learningRate; per-tree labels are -loss gradient. RF: per-tree
Poisson bagging + feature subset (FeatureSubsetStrategy.java). Per-tree
RNG streams are keyed by (seed, tree_index) so a checkpointed run resumes
BIT-EQUAL under the SAME framework version — resuming a checkpoint
written by a build with a different histogram lowering may legitimately
diverge in float-summation order
(DTMaster.doCheckPoint:637, recovery :284-291); isContinuous
keeps adding GBT trees up to TreeNum (TrainModelProcessor.java:1166-1184).
Early stop: simple worsen-count OR the reference's windowed decider
(dt/DTEarlyStopDecider.java:49) under EnableEarlyStop.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from shifu_tpu.obs import profile, registry, span

from shifu_tpu.models.tree import DenseTree, TreeModelSpec
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)


@dataclass
class TreeTrainConfig:
    algorithm: str = "GBT"  # GBT | RF
    tree_num: int = 100
    max_depth: int = 6
    max_leaves: int = -1  # > 0 switches to leaf-wise growth
    impurity: str = "variance"  # variance | friedmanmse | entropy | gini
    loss: str = "squared"  # squared | log (GBT label relabeling)
    learning_rate: float = 0.05
    min_instances_per_node: int = 5
    min_info_gain: float = 0.0
    feature_subset_strategy: str = "ALL"  # ALL/HALF/ONETHIRD/TWOTHIRDS/SQRT/LOG2/AUTO
    bagging_sample_rate: float = 1.0
    bagging_with_replacement: bool = True
    valid_set_rate: float = 0.1
    dropout_rate: float = 0.0  # GBT DART-style per-row drop (DROPOUT_RATE)
    early_stop_rounds: int = 0  # GBT: stop when valid error worsens N rounds
    enable_early_stop: bool = False  # DTEarlyStopDecider windowed decider
    max_stats_memory_mb: int = 256  # histogram node-batch budget
    hist_subtraction: bool = True  # build smaller child, derive the sibling
    n_classes: int = 0  # >= 3: NATIVE RF multi-class (majority-vote leaves)
    seed: int = 0

    @classmethod
    def from_model_config(cls, mc, trainer_id: int = 0) -> "TreeTrainConfig":
        t = mc.train
        alg = t.algorithm.value if hasattr(t.algorithm, "value") else str(t.algorithm)

        def g(key, default):
            v = t.get_param(key, default)
            return default if v is None else v

        alg = "RF" if alg in ("RF", "DT") else "GBT"
        return cls(
            algorithm=alg,
            tree_num=int(g("TreeNum", 100 if alg == "GBT" else 10)),
            max_depth=int(g("MaxDepth", 6 if alg == "GBT" else 10)),
            max_leaves=int(g("MaxLeaves", -1)),
            impurity=str(g("Impurity", "variance")).lower(),
            loss=str(g("Loss", "squared")).lower(),
            learning_rate=float(g("LearningRate", 0.05)),
            dropout_rate=float(g("DropoutRate", 0.0)),
            min_instances_per_node=int(g("MinInstancesPerNode", 5)),
            min_info_gain=float(g("MinInfoGain", 0.0)),
            feature_subset_strategy=str(
                g("FeatureSubsetStrategy", "ALL")
            ).upper(),
            bagging_sample_rate=float(t.bagging_sample_rate or 1.0),
            bagging_with_replacement=bool(t.bagging_with_replacement),
            valid_set_rate=float(t.valid_set_rate or 0.1),
            early_stop_rounds=int(g("EarlyStopRounds", 0)),
            enable_early_stop=bool(g("EnableEarlyStop", False)),
            max_stats_memory_mb=int(g("MaxStatsMemoryMB", 256)),
            hist_subtraction=bool(g("TreeHistSubtraction", True)),
            n_classes=(len(mc.tags())
                       if (mc.is_multi_classification()
                           and not t.is_one_vs_all()) else 0),
            seed=trainer_id * 977 + 13,
        )


def subset_count(strategy: str, n_features: int) -> int:
    s = strategy.upper()
    if s in ("ALL", ""):
        return n_features
    if s == "HALF":
        return max(1, n_features // 2)
    if s == "ONETHIRD":
        return max(1, n_features // 3)
    if s == "TWOTHIRDS":
        return max(1, (2 * n_features) // 3)
    if s == "QUARTER":
        return max(1, n_features // 4)
    if s in ("SQRT", "AUTO"):
        return max(1, int(math.sqrt(n_features)))
    if s == "LOG2":
        return max(1, int(math.log2(max(n_features, 2))))
    return n_features


# ---------------------------------------------------------------------------
# static per-feature slot layout
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FeatureLayout:
    """Flat per-feature slot addressing: feature f owns slots
    [off[f], off[f]+slots[f]) of a T-wide axis. All arrays are static per
    (slots, is_cat) signature and shared by every compiled program."""

    slots: np.ndarray  # [F] int32
    off: np.ndarray  # [F] int32 segment starts
    T: int
    seg_of_t: np.ndarray  # [T] feature id per flat slot
    pos_in_seg: np.ndarray  # [T] slot rank within its segment
    seg_start_t: np.ndarray  # [T]
    seg_size_t: np.ndarray  # [T]
    is_cat_t: np.ndarray  # [T] bool
    clip_max: np.ndarray  # [F] slots-1
    s_max: int
    key: tuple = ()  # static cache key (the make_layout interning key)


_LAYOUTS: Dict[tuple, FeatureLayout] = {}


def make_layout(slots: List[int], is_cat: List[bool]) -> FeatureLayout:
    key = (tuple(int(s) for s in slots), tuple(bool(c) for c in is_cat))
    lay = _LAYOUTS.get(key)
    if lay is not None:
        return lay
    slots_np = np.asarray(slots, np.int32)
    off = np.zeros(len(slots), np.int32)
    off[1:] = np.cumsum(slots_np[:-1])
    T = int(slots_np.sum())
    seg = np.repeat(np.arange(len(slots), dtype=np.int32), slots_np)
    pos = np.arange(T, dtype=np.int32) - off[seg]
    lay = FeatureLayout(
        slots=slots_np,
        off=off,
        T=T,
        seg_of_t=seg,
        pos_in_seg=pos,
        seg_start_t=off[seg],
        seg_size_t=slots_np[seg],
        is_cat_t=np.asarray(is_cat, bool)[seg],
        clip_max=np.maximum(slots_np - 1, 0),
        s_max=int(slots_np.max()) if len(slots) else 1,
        key=key,
    )
    _LAYOUTS[key] = lay
    return lay


# ---------------------------------------------------------------------------
# compiled programs (cached per shape/hyperparam signature)
# ---------------------------------------------------------------------------

_PROGRAMS: Dict[tuple, object] = {}

# the one-hot contraction's lhs is [blk, C*L]; past this width the matmul's
# L-fold redundancy stops paying for itself and the scatter path wins
MATMUL_CL_CAP = 4096

# the Pallas fused scan unrolls an L-iteration node loop in-kernel; past
# this node count the generated program outgrows the fusion win and the
# level drops to hist-mode kernel + XLA scan
_FUSED_SCAN_L_CAP = 32


def _make_comps_of(n_classes: int):
    """Shared histogram component builder: [w, wy, wy^2] for
    regression/binary, one weighted count plane per class for NATIVE
    multi-class (dt/Impurity.java:368,553)."""
    import jax.numpy as jnp

    def comps_of(w, labels):
        if n_classes >= 3:
            cls = jnp.clip(labels.astype(jnp.int32), 0, n_classes - 1)
            return [w * (cls == c).astype(jnp.float32)
                    for c in range(n_classes)]
        return [w, w * labels, w * labels * labels]

    return comps_of


def _onehot_cols(code_b, pieces, slots_np, clip_np, blk: int):
    """One chunk's code one-hots as a list of [blk, *] bool columns in
    flat-slot order."""
    import jax.numpy as jnp

    cols = []
    for run in _piece_runs(pieces, slots_np):
        if len(run) == 1:
            (f, lo, hi) = run[0]
            cw = hi - lo
            cf = jnp.clip(code_b[:, f], 0, int(clip_np[f]))
            # for a partial piece of a wide feature the equality against
            # the shifted range doubles as the bound check
            cols.append((cf - lo)[:, None] == jnp.arange(cw)[None, :])
        else:  # consecutive full features of EQUAL width: one vectorized
            # [blk, m, w] one-hot keeps the trace O(runs), not O(features)
            fs = [f for (f, _lo, _hi) in run]
            cw = run[0][2]
            cf = jnp.clip(code_b[:, fs[0]:fs[-1] + 1], 0, cw - 1)
            cols.append((cf[:, :, None]
                         == jnp.arange(cw)[None, None, :]).reshape(
                blk, len(fs) * cw))
    return cols

# target lane width of one flat-T chunk (feature one-hots are concatenated
# at their STATIC column offsets, so a 10k-category feature just spans
# several chunks instead of inflating every feature to its width)
_T_CHUNK = 2048


def _t_chunks(lay: FeatureLayout, target: int = _T_CHUNK):
    """Split the flat T axis into chunks of ~`target` columns. Each chunk is
    a list of (feature, slot_lo, slot_hi) pieces laid out back-to-back; the
    concatenation of all chunks covers [0, T) in flat-slot order."""
    chunks: List[list] = []
    cur: list = []
    cur_w = 0
    for f, s in enumerate(int(x) for x in lay.slots):
        lo = 0
        while lo < s:
            take = min(s - lo, target - cur_w)
            if take == 0:
                chunks.append(cur)
                cur, cur_w = [], 0
                continue
            cur.append((f, lo, lo + take))
            cur_w += take
            lo += take
            if cur_w >= target:
                chunks.append(cur)
                cur, cur_w = [], 0
    if cur:
        chunks.append(cur)
    return chunks


def _piece_runs(pieces: list, slots_np: np.ndarray) -> List[list]:
    """Group a chunk's pieces into runs of CONSECUTIVE full features with
    equal slot width (vectorizable as one [blk, m, w] one-hot); partial
    pieces of wide features stay singleton runs."""
    runs: List[list] = []
    for piece in pieces:
        (f, lo, hi) = piece
        full = lo == 0 and hi == int(slots_np[f])
        if (runs and full and len(runs[-1])
                and runs[-1][-1][0] == f - 1
                and runs[-1][-1][1] == 0
                and runs[-1][-1][2] == int(slots_np[f - 1])
                and hi - lo == runs[-1][-1][2] - runs[-1][-1][1]):
            runs[-1].append(piece)
        else:
            runs.append([piece])
    return runs


def _make_hist_fn(L: int, lay: FeatureLayout, allow_matmul: bool = True,
                  n_classes: int = 0):
    """Traced histogram builder: [C, L, T] over the flat per-feature slot
    axis — the Impurity.featureUpdate hot loop (dt/DTWorker.java:851) fused
    into one device op. Regression/binary uses C=3 components (cnt, sum,
    sqsum); NATIVE multi-class (n_classes >= 3, RF classification) uses one
    weighted COUNT PLANE PER CLASS (the reference's Entropy/Gini
    featureUpdate keeps per-class counts, dt/Impurity.java:368,553). Under
    a `data`-sharded mesh each device reduces its row shard and the caller
    psums the histogram (replacing DTMaster's NodeStats merge,
    DTMaster.java:297-310).

    Two lowerings, chosen statically:
      * matmul (SURVEY §7.5's histogram-kernel obligation, MXU-shaped):
        (component ⊙ one-hot(node))ᵀ @ one-hot(flat code) per T-chunk.
        Feature one-hots sit at STATIC column offsets inside each chunk,
        so the contraction width is always ~_T_CHUNK regardless of how
        wide any single categorical column is. f32 operands so
        counts/sums accumulate exactly.
      * scatter-add fallback when C*L outgrows MATMUL_CL_CAP (the lhs
        would be wider than the redundancy is worth).

    The returned fn keeps the historical traced-layout signature
    (off_f/clip_f/seg_t/pos_t) so scatter and matmul are drop-in
    interchangeable; the matmul path bakes the static layout in."""
    import jax.numpy as jnp

    C = n_classes if n_classes >= 3 else 3
    T = lay.T
    use_matmul = allow_matmul and C * L <= MATMUL_CL_CAP
    comps_of = _make_comps_of(n_classes)

    def hist_scatter(codes, labels, weights, node_slot, active, off_f,
                     clip_f, seg_t, pos_t):
        n, F = codes.shape
        w = jnp.where(active, weights, 0.0)
        nl = jnp.where(active, jnp.clip(node_slot, 0, L - 1), 0)
        code_f = jnp.clip(codes, 0, clip_f[None, :])
        flat = nl[:, None] * T + off_f[None, :] + code_f
        planes = [
            jnp.zeros((L * T,), jnp.float32)
            .at[flat]
            .add(jnp.broadcast_to(c[:, None], (n, F)))
            .reshape(L, T)
            for c in comps_of(w, labels)
        ]
        return jnp.stack(planes)

    if not use_matmul:
        return hist_scatter

    chunks = _t_chunks(lay)
    slots_np = lay.slots
    clip_np = lay.clip_max
    chunk_max = max(sum(hi - lo for _f, lo, hi in ch) for ch in chunks)
    # bound the per-block working set (A [blk, C*L] + M [blk, chunk]) to
    # ~32 MB so XLA keeps blocks cache-resident; round to a tile multiple
    blk_target = (32 << 20) // (4 * max(chunk_max + C * L, 1))
    BLK = max(256, min(131072, (blk_target // 256) * 256))

    def hist_matmul(codes, labels, weights, node_slot, active, off_f,
                    clip_f, seg_t, pos_t):
        import jax

        n, F = codes.shape
        w = jnp.where(active, weights, 0.0)
        nl = jnp.where(active, jnp.clip(node_slot, 0, L - 1), 0)
        comps = jnp.stack(comps_of(w, labels), 1)  # [n, C]

        blk = min(BLK, n)
        n_pad = -(-n // blk) * blk
        pad = n_pad - n
        with jax.named_scope("tree.codes"):
            codes_p = jnp.pad(codes, ((0, pad), (0, 0)))
        nl_p = jnp.pad(nl, (0, pad))
        comps_p = jnp.pad(comps, ((0, pad), (0, 0)))

        def block(hist, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)
            comps_b = sl(comps_p)
            if L == 1:
                A = comps_b  # [blk, C]
            else:
                oh_node = (sl(nl_p)[:, None]
                           == jnp.arange(L)[None, :]).astype(jnp.float32)
                A = (comps_b[:, :, None] * oh_node[:, None, :]).reshape(
                    blk, C * L)
            code_b = sl(codes_p)
            parts = []
            for pieces in chunks:
                cols = _onehot_cols(code_b, pieces, slots_np, clip_np, blk)
                M = (cols[0] if len(cols) == 1
                     else jnp.concatenate(cols, axis=1)).astype(jnp.float32)
                parts.append(jnp.einsum("nk,nt->kt", A, M))
            contrib = (parts[0] if len(parts) == 1
                       else jnp.concatenate(parts, axis=1))  # [C*L, T]
            return hist + contrib, None

        hist0 = jnp.zeros((C * L, T), jnp.float32)
        hist, _ = jax.lax.scan(block, hist0, jnp.arange(n_pad // blk))
        return hist.reshape(C, L, T)

    return hist_matmul


def _make_leaf_fn(L: int, n_classes: int = 0):
    """Final-level aggregation: per-node (cnt, sum) — or per-class counts —
    WITHOUT building the full [C, L, T] histogram (leaf values only need
    node totals, so the deepest level skips the per-slot work entirely).
    Returns the RAW accumulator [C, L] so a meshed caller can psum it
    before the nonlinear ratio/argmax finalize step."""
    import jax.numpy as jnp

    def leaf_acc(labels, weights, node_slot, active):
        import jax

        n = labels.shape[0]
        w = jnp.where(active, weights, 0.0)
        nl = jnp.where(active, jnp.clip(node_slot, 0, L - 1), 0)
        if n_classes >= 3:
            cls = jnp.clip(labels.astype(jnp.int32), 0, n_classes - 1)
            comps = jnp.stack(
                [w * (cls == c).astype(jnp.float32)
                 for c in range(n_classes)], 1)
        else:
            comps = jnp.stack([w, w * labels], 1)
        C = comps.shape[1]

        blk = min(131072, n)
        n_pad = -(-n // blk) * blk
        pad = n_pad - n
        nl_p = jnp.pad(nl, (0, pad))
        comps_p = jnp.pad(comps, ((0, pad), (0, 0)))

        def block(acc, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk, 0)
            oh = (sl(nl_p)[:, None]
                  == jnp.arange(L)[None, :]).astype(jnp.float32)
            return acc + jnp.einsum("nc,nl->cl", sl(comps_p), oh), None

        acc0 = jnp.zeros((C, L), jnp.float32)
        acc, _ = jax.lax.scan(block, acc0, jnp.arange(n_pad // blk))
        return acc

    def leaf_finalize(acc):
        if n_classes >= 3:
            return jnp.argmax(acc, axis=0).astype(jnp.float32)  # majority
        cnt, s1 = acc[0], acc[1]
        return s1 / jnp.maximum(cnt, 1e-12)

    return leaf_acc, leaf_finalize


def _get_hist_program(L: int, lay: FeatureLayout,
                      allow_matmul: bool = True, n_classes: int = 0,
                      mesh=None, low_precision: bool = False):
    """Standalone jitted histogram program. With a `mesh`, the builder runs
    under shard_map on per-device row shards and psums the [C, L, T]
    result — the per-level worker-merge for callers (streamed trainer)
    that drive levels from the host. When the Pallas kernel is enabled
    (-Dshifu.pallas.mode) the builder is the hist-mode kernel — inside
    the shard_map on a mesh, so each device contracts its own rows in
    VMEM and only the [C, L, T] partial rides the psum."""
    p_on, p_interp, _ = _pallas_state(mesh)
    lowp = bool(low_precision and p_on)
    key = ("hist", L, lay.key, allow_matmul, n_classes, _mesh_key(mesh),
           p_on, p_interp, lowp)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax

    if p_on:
        from shifu_tpu.ops.hist_pallas import make_pallas_hist_fn

        pfn = make_pallas_hist_fn(L, lay, n_classes=n_classes,
                                  interpret=p_interp, low_precision=lowp)

        def fn(codes, labels, weights, node, active, *_layout, _pfn=pfn):
            return _pfn(codes, labels, weights, node, active)
    else:
        fn = _make_hist_fn(L, lay, allow_matmul, n_classes)
    if mesh is None:
        prog = jax.jit(fn)
    else:
        from jax.sharding import PartitionSpec as P

        from shifu_tpu.parallel.mesh import row_axes

        r_axes = row_axes(mesh)
        rspec = P(r_axes if len(r_axes) > 1 else r_axes[0])

        def meshed(codes, labels, weights, node, active, off, clip, seg,
                   pos):
            h = fn(codes, labels, weights, node, active, off, clip, seg,
                   pos)
            return jax.lax.psum(h, r_axes)

        from shifu_tpu.parallel.mesh import shard_map_compat

        prog = jax.jit(shard_map_compat(
            meshed, mesh=mesh, in_specs=(rspec,) * 5 + (P(),) * 4,
            out_specs=P()))
    prog = profile.wrap("tree.hist", prog)
    if p_on:
        from shifu_tpu.ops.hist_pallas import kernel_calls

        def counted(*args, _prog=prog,
                    _plan=(("hist", 1, kernel_calls(lay, fused=False)),)):
            _record_kernel_calls(_plan)
            return _prog(*args)

        prog = counted
    _PROGRAMS[key] = prog
    return prog


def _make_scan_fn(L: int, T: int, s_max: int, impurity: str,
                  min_inst: int, min_gain: float, n_classes: int = 0):
    """Raw (unjitted) reference split scan — shared by the jitted scan
    program and the Pallas fused path, which reuses it for the derived
    sibling halves of histogram subtraction and as the fallback for
    features too wide for one in-kernel chunk."""
    if n_classes >= 3:
        return _make_cls_scan(L, T, s_max, impurity, min_inst, min_gain,
                              n_classes)
    return _make_split_scan(L, T, s_max, impurity, min_inst, min_gain)


def _get_scan_program(L: int, T: int, s_max: int, impurity: str,
                      min_inst: int, min_gain: float, n_classes: int = 0):
    key = ("scan", L, T, s_max, impurity, min_inst, float(min_gain),
           n_classes)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax

    prog = profile.wrap(
        "tree.split_scan",
        jax.jit(_make_scan_fn(L, T, s_max, impurity, min_inst, min_gain,
                              n_classes)))
    _PROGRAMS[key] = prog
    return prog


def _make_split_scan(L: int, T: int, s_max: int, impurity: str,
                     min_inst: int, min_gain: float):
    import jax
    import jax.numpy as jnp

    def split_scan(hist, feat_ok_t, is_cat_t, seg_t, pos_t, start_t, size_t,
                   off_f, clip_f, seg0_size):
        """Best split per node from the flat histogram.

        Ordered prefix sums inside static segment boundaries: lexsort on
        (segment, key) where key = mean label for categorical segments
        (the reference's mean-sort category split) and slot position for
        numeric ones. Segment boundaries are static, so the ordered layout
        keeps feature f at [off[f], off[f]+slots[f]).

        Returns (feature [L], cut_rank [L], rank_flat [L, T], leaf_value
        [L], is_split [L], best_gain [L], left_mask_model [L, s_max],
        node_cnt [L], left_cnt [L]) — left_cnt is the best split's left
        weighted count, the histogram-subtraction paths' smaller-child
        selector (garbage where is_split is False)."""
        cnt, s1, s2 = hist[0], hist[1], hist[2]
        mean = jnp.where(cnt > 0, s1 / jnp.maximum(cnt, 1e-12), jnp.inf)
        sec = jnp.where(is_cat_t[None, :], mean,
                        jnp.broadcast_to(pos_t.astype(jnp.float32), cnt.shape))

        def order_row(sec_row):
            return jnp.lexsort((sec_row, seg_t))

        order = jax.vmap(order_row)(sec)  # [L, T] original index per pos

        def reorder(a):
            return jnp.take_along_axis(a, order, axis=-1)

        c0 = jnp.cumsum(reorder(cnt), axis=-1)
        c1 = jnp.cumsum(reorder(s1), axis=-1)
        c2 = jnp.cumsum(reorder(s2), axis=-1)

        start_prev = jnp.maximum(start_t - 1, 0)
        end_idx = start_t + size_t - 1

        def seg_sums(c):
            base = jnp.where(start_t > 0, c[:, start_prev], 0.0)
            left = c - base
            tot = c[:, end_idx] - base
            return left, tot

        lcnt, tcnt = seg_sums(c0)
        ls1, ts1 = seg_sums(c1)
        ls2, ts2 = seg_sums(c2)
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
        else:  # variance
            gain = sse(tcnt, ts1, ts2) - sse(lcnt, ls1, ls2) - sse(rcnt, rs1, rs2)

        valid = (
            (lcnt >= min_inst)
            & (rcnt >= min_inst)
            & (gain > min_gain)
            & feat_ok_t[None, :]
            & (pos_t < size_t - 1)[None, :]  # cut at segment end = no split
        )
        gain = jnp.where(valid, gain, -jnp.inf)

        best = jnp.argmax(gain, axis=-1)  # ordered position
        best_gain = jnp.take_along_axis(gain, best[:, None], axis=-1)[:, 0]
        left_cnt = jnp.take_along_axis(lcnt, best[:, None], axis=-1)[:, 0]
        feature = seg_t[best].astype(jnp.int32)
        cut_rank = pos_t[best].astype(jnp.int32)
        is_split = jnp.isfinite(best_gain)

        # rank of each ORIGINAL flat slot within its segment's ordering
        rank_flat = (
            jnp.zeros((L, T), jnp.int32)
            .at[jnp.arange(L)[:, None], order]
            .set(jnp.broadcast_to(pos_t, (L, T)))
        )

        node_cnt = c0[:, seg0_size - 1]
        node_sum = c1[:, seg0_size - 1]
        leaf_value = node_sum / jnp.maximum(node_cnt, 1e-12)

        # model-facing mask over ORIGINAL codes [L, s_max]
        s_range = jnp.arange(s_max, dtype=jnp.int32)
        f_clip = clip_f[feature]  # [L]
        s_idx = jnp.minimum(s_range[None, :], f_clip[:, None])
        flat_idx = off_f[feature][:, None] + s_idx
        ranks = jnp.take_along_axis(rank_flat, flat_idx, axis=-1)
        left_mask = (
            (ranks <= cut_rank[:, None])
            & (s_range[None, :] <= f_clip[:, None])
            & is_split[:, None]
        )
        return (feature, cut_rank, rank_flat, leaf_value, is_split,
                best_gain, left_mask, node_cnt, left_cnt)

    return split_scan


def _make_cls_scan(L: int, T: int, s_max: int, impurity: str, min_inst: int,
                   min_gain: float, K: int):
    """Multi-class split scan over per-class count planes [K, L, T] —
    NATIVE RF classification (reference Entropy/Gini multi-class counts,
    dt/Impurity.java:368,553). Leaf value = MAJORITY CLASS index; the gain
    is the K-class entropy/gini mass drop (variance/friedmanmse fall back
    to gini — the reference only supports entropy/gini for classification).

    Returns the same tuple shape as the regression scan so the tree
    builders are oblivious to the mode."""
    import jax
    import jax.numpy as jnp

    use_entropy = impurity == "entropy"

    def cls_scan(hist, feat_ok_t, is_cat_t, seg_t, pos_t, start_t, size_t,
                 off_f, clip_f, seg0_size):
        cnt = hist.sum(0)  # [L, T] total weighted count per slot
        # categorical ordering key: expected class index (the multi-class
        # generalization of the reference's mean-response category sort)
        exp = (hist * jnp.arange(K, dtype=jnp.float32)[:, None, None]).sum(0)
        mean = jnp.where(cnt > 0, exp / jnp.maximum(cnt, 1e-12), jnp.inf)
        sec = jnp.where(is_cat_t[None, :], mean,
                        jnp.broadcast_to(pos_t.astype(jnp.float32), cnt.shape))

        def order_row(sec_row):
            return jnp.lexsort((sec_row, seg_t))

        order = jax.vmap(order_row)(sec)  # [L, T]

        def reorder(a):
            return jnp.take_along_axis(a, order, axis=-1)

        ccum = jnp.cumsum(jax.vmap(reorder)(hist), axis=-1)  # [K, L, T]

        start_prev = jnp.maximum(start_t - 1, 0)
        end_idx = start_t + size_t - 1
        base = jnp.where(start_t[None, None, :] > 0,
                         ccum[:, :, start_prev], 0.0)
        left = ccum - base  # per-class left counts
        tot = ccum[:, :, end_idx] - base
        right = tot - left
        lcnt = left.sum(0)
        rcnt = right.sum(0)
        tcnt = tot.sum(0)

        def mass(counts, total):
            p = counts / jnp.maximum(total[None], 1e-12)
            if use_entropy:
                h = -(p * jnp.log2(jnp.maximum(p, 1e-12))).sum(0)
            else:  # gini
                h = 1.0 - (p * p).sum(0)
            return total * h

        gain = (mass(tot, tcnt) - mass(left, lcnt) - mass(right, rcnt))

        valid = (
            (lcnt >= min_inst)
            & (rcnt >= min_inst)
            & (gain > min_gain)
            & feat_ok_t[None, :]
            & (pos_t < size_t - 1)[None, :]
        )
        gain = jnp.where(valid, gain, -jnp.inf)

        best = jnp.argmax(gain, axis=-1)
        best_gain = jnp.take_along_axis(gain, best[:, None], axis=-1)[:, 0]
        left_cnt = jnp.take_along_axis(lcnt, best[:, None], axis=-1)[:, 0]
        feature = seg_t[best].astype(jnp.int32)
        cut_rank = pos_t[best].astype(jnp.int32)
        is_split = jnp.isfinite(best_gain)

        rank_flat = (
            jnp.zeros((L, T), jnp.int32)
            .at[jnp.arange(L)[:, None], order]
            .set(jnp.broadcast_to(pos_t, (L, T)))
        )

        node_class_cnt = ccum[:, :, seg0_size - 1]  # [K, L]
        node_cnt = node_class_cnt.sum(0)
        leaf_value = jnp.argmax(node_class_cnt, axis=0).astype(jnp.float32)

        s_range = jnp.arange(s_max, dtype=jnp.int32)
        f_clip = clip_f[feature]
        s_idx = jnp.minimum(s_range[None, :], f_clip[:, None])
        flat_idx = off_f[feature][:, None] + s_idx
        ranks = jnp.take_along_axis(rank_flat, flat_idx, axis=-1)
        left_mask = (
            (ranks <= cut_rank[:, None])
            & (s_range[None, :] <= f_clip[:, None])
            & is_split[:, None]
        )
        return (feature, cut_rank, rank_flat, leaf_value, is_split,
                best_gain, left_mask, node_cnt, left_cnt)

    return cls_scan


# Row routing looks its per-row values up in tables that are small and
# static in length: a level's per-node scalars [L] and its packed mask words
# [L * ceil(s_max / 32)]. Up to this many entries the lookup is a
# compare-select over the table's axis, which the TPU's vector unit streams
# (one v5e, 5.5 M rows: 1.0 ms at 64 entries, 10 ms at 1,024, 40 ms at
# 4,096); past it a 1-D gather, which costs the same 27-43 ms whatever the
# table. PERF.md section 6 (PR 27) has the readings. What a row needs of
# its node beyond those tables rides an entry it reads anyway: the next
# level's build-row mask (histogram subtraction) is one bit of the feature's
# entry and comes out of `route_rows`, where a gather of its own costs 45 ms
# at 128 entries (section 6, PR 35).
_ROUTE_SELECT_CAP = 4096


def _mask_words(s_max: int) -> int:
    return -(-s_max // 32)


def route_is_dense(L: int, s_max: int) -> bool:
    """Whether a level of L nodes routes its rows with no per-row gather at
    all (`tree.route.dense`), or takes its mask word by a 1-D gather
    (`tree.route.gather`): a rule on static shapes alone."""
    return L * _mask_words(s_max) <= _ROUTE_SELECT_CAP


def _lookup(table, idx):
    """table[idx] for every row; `table` is 1-D."""
    import jax.numpy as jnp

    N = table.shape[0]
    if N > _ROUTE_SELECT_CAP:
        return table[idx]
    hit = idx[:, None] == jnp.arange(N, dtype=jnp.int32)
    return jnp.where(hit, table, 0).sum(axis=1, dtype=table.dtype)


def route_rows(codes, node, active, resting, feature, is_split, left_mask,
               base, clip_f, left_small=None):
    """Move a level's rows one level down: rows of a node that does not
    split settle at base + node, the others go to child 2i or 2i + 1
    (level-wise numbering within the next level) as the level's
    `left_mask` [L, s_max] says of their code at the node's feature: the
    mask the served model follows, so training and serving route alike.

    No per-row gather from a 2-D table (a TPU v5e runs those at 16-20 ns a
    row whatever the table): the row's code is a select over the static
    feature axis that streams `codes` once, its node's feature, clip and
    mask word a `_lookup` each, the mask packed 32 slots a word.

    Returns (resting, node, active, built) for the next level. `built` is
    None unless the next level derives siblings by subtraction and the
    caller hands in this level's `left_small` [L] (the left child is the
    smaller one, so the one that is built): then it is the next level's
    build-row mask, the rows that went to the side their parent builds.
    The bit rides the feature's table entry, so it costs no lookup of its
    own; the half-width histogram's node ids are the caller's `node >> 1`.
    Traced inside the whole-tree program (every level) and alone as
    `tree.row_update` (the node-batched and streamed growers)."""
    import jax.numpy as jnp

    L, s_max = left_mask.shape
    F = codes.shape[1]
    W = _mask_words(s_max)
    bits = jnp.pad(left_mask, ((0, 0), (0, W * 32 - s_max)))
    words = (bits.reshape(L * W, 32).astype(jnp.uint32)
             << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1,
                                                      dtype=jnp.uint32)

    nl = jnp.clip(node, 0, L - 1)
    entry = feature if left_small is None else 2 * feature + left_small
    e = _lookup(jnp.where(is_split, entry, -1), nl)  # -1: no split
    split_row = e >= 0
    f = e if left_small is None else e >> 1
    resting = jnp.where(active & ~split_row, base + nl, resting)
    code = jnp.where(f[:, None] == jnp.arange(F, dtype=jnp.int32),
                     codes, 0).sum(axis=1)
    c = jnp.clip(code, 0, _lookup(clip_f[feature], nl))
    word = _lookup(words, nl * W + (c >> 5))
    goes_left = ((word >> (c & 31).astype(jnp.uint32)) & 1) > 0
    still = split_row & active
    built = (None if left_small is None
             else still & (goes_left == ((e & 1) > 0)))
    return (resting,
            jnp.where(still, jnp.where(goes_left, 2 * nl, 2 * nl + 1), 0),
            still, built)


def _get_update_program():
    prog = _PROGRAMS.get("update")
    if prog is None:
        import jax

        prog = profile.wrap("tree.row_update", jax.jit(route_rows))
        _PROGRAMS["update"] = prog
    return prog


def _node_batch_size(T: int, max_stats_memory_mb: int,
                     n_classes: int = 0) -> int:
    """Nodes per histogram batch under the stats-memory budget
    (DTMaster.getStatsMem node batching, DTMaster.java:450-467): the
    [C, L, T] f32 histogram must fit maxStatsMemoryMB, where C = 3 for
    regression/binary and C = n_classes for NATIVE multi-class."""
    planes = n_classes if n_classes >= 3 else 3
    budget = max(1, max_stats_memory_mb) * (1 << 20)
    return max(1, budget // (planes * 4 * max(T, 1)))


# ---------------------------------------------------------------------------
# histogram subtraction (build the smaller child, derive the sibling)
# ---------------------------------------------------------------------------
#
# A split's two children partition their parent's rows exactly, so
# H[sibling] = H[parent] - H[built child] (the LightGBM/XGBoost
# histogram-subtraction recurrence; the same reduction-reuse DrJAX frames
# for MapReduce-style aggregations). Every level >= 1 therefore builds
# only the SMALLER child of each split — half the node-histograms per
# level, and for the matmul lowering a half-width [C, L/2, T]
# contraction — and reconstructs the full level by one fused elementwise
# derive. RF histograms under unit/integer sample weights are integer
# sums in f32 (exact under any order, counts < 2^24), so subtraction is
# BIT-EXACT there; GBT moment planes — and RF under a FRACTIONAL
# significance column — carry float values, so the retained parent chain
# accumulates in f64 when jax x64 is enabled (exactly-rounded single f32
# subtraction otherwise) and is only downcast to f32 at the split scan.


def _sub_acc64() -> bool:
    """f64 accumulator chain for the retained-parent recurrence — only
    meaningful (and only requested, to avoid the x64 truncation warning)
    when jax x64 is on. Applies to BOTH algorithms: GBT moment planes
    always carry float residuals, and RF planes are only integer-valued
    (exact in f32) when the sample-weight column is unit/integer — a
    fractional significance column makes RF inexact too. For exact
    integer planes the f64 chain is a bit-identical no-op."""
    import jax

    return bool(jax.config.jax_enable_x64)


def _sub_level_fits(L: int, batch_cap: int, acc64: bool) -> bool:
    """Memory gate for subtraction at a level of L nodes, in units of
    [C, 1, T] f32 node planes against the MaxStatsMemoryMB budget
    (`batch_cap`, DTMaster.java:450-467): the retained parent [C, L/2, T]
    (doubled when the accumulator chain is f64), the built smaller-child
    histogram [C, L/2, T] f32, and the reconstructed level [C, L, T] in
    accumulator dtype (plus its f32 scan view when that is f64) must fit
    together; otherwise the level falls back to a full rebuild."""
    f = 2 if acc64 else 1
    half = max(L // 2, 1)
    planes = half * (f + 1) + L * f + (L if acc64 else 0)
    return planes <= batch_cap


def _sub_plan(cfg: "TreeTrainConfig", batch_cap: int) -> Tuple[tuple, bool]:
    """Static per-level subtraction decisions for a level-wise tree:
    (sub_levels[d] for d in range(max_depth + 1), acc64). Depends only on
    cfg + the layout-derived batch_cap, so a checkpoint-resumed run picks
    the SAME plan as the uninterrupted one (bit-equal resume contract).
    Index D (the final leaf level) matters only to the host-driven batched
    path; the fused program's final level aggregates node totals without a
    per-slot histogram."""
    acc64 = _sub_acc64()
    levels = tuple(
        d >= 1 and cfg.hist_subtraction
        and _sub_level_fits(2 ** d, batch_cap, acc64)
        for d in range(cfg.max_depth + 1)
    )
    return levels, acc64


def _get_derive_program():
    """Fused sibling derivation: (parent [C, Lh, T] acc-dtype, built
    [C, Lh, T] f32, parent is_split [Lh], left_small [Lh]) ->
    (hist [C, 2*Lh, T] f32 for the split scan, hist_acc for the next
    level's retained parent). Children of NON-split parents are zeroed so
    the reconstructed level is elementwise identical in structure to a
    full rebuild (a derived child of a non-split parent would otherwise
    inherit the parent's histogram)."""
    key = ("derive",)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    @jax.jit
    def derive(parent, built, psplit, left_small):
        C, Lh, T = parent.shape
        b = built.astype(parent.dtype)
        pm = jnp.where(psplit[None, :, None], parent - b,
                       jnp.zeros_like(parent))
        lh = jnp.where(left_small[None, :, None], b, pm)
        rh = jnp.where(left_small[None, :, None], pm, b)
        # children interleave 2p / 2p+1 in level order
        acc = jnp.stack([lh, rh], axis=2).reshape(C, 2 * Lh, T)
        return acc.astype(jnp.float32), acc

    prog = profile.wrap("tree.hist_derive", derive)
    _PROGRAMS[key] = prog
    return prog


def _plan_counts(sub_levels: tuple, enabled: bool) -> Tuple[int, int, int]:
    """(built, derived, fallback) node-histogram counts for one fused
    level-wise tree under a static subtraction plan — one histogram batch
    per level, and the final leaf level aggregates node totals without a
    per-slot histogram, so it is not counted."""
    built = derived = fallback = 0
    for d, sub in enumerate(sub_levels):
        L = 2 ** d
        if sub:
            built += L // 2
            derived += L // 2
        else:
            built += L
            if enabled and d >= 1:
                fallback += 1
    return built, derived, fallback


def _record_hist_counters(built: int, derived: int, fallback: int) -> None:
    """Run-ledger counters for the subtraction win (`tree.hist.built` /
    `tree.hist.derived` / `tree.hist.fallback_rebuilds`, units =
    node-histograms resp. fallback batch rebuilds)."""
    from shifu_tpu.obs import registry

    reg = registry()
    if built:
        reg.counter("tree.hist.built").inc(built)
    if derived:
        reg.counter("tree.hist.derived").inc(derived)
    if fallback:
        reg.counter("tree.hist.fallback_rebuilds").inc(fallback)


def _tree_kernel_plan(D: int, lay: FeatureLayout, sub_levels: tuple,
                      mesh=None) -> tuple:
    """((mode, levels, chunks), ...) of one whole-tree program
    (`_get_tree_program`), from static shapes alone: for each kernel mode
    the program uses, "fused" or "hist", the levels built with it and the
    layout's chunks under that mode's column cap. A level built by
    subtraction runs the kernel of the level above it (half the nodes); a
    kernel is the fused one where `_pallas_state` and `_FUSED_SCAN_L_CAP`
    say so, else the hist-mode one; empty with the kernel off."""
    p_on, _interp, p_fused = _pallas_state(mesh)
    if not p_on:
        return ()
    from shifu_tpu.ops.hist_pallas import kernel_calls

    built_at = [d - 1 if d and sub_levels[d] else d for d in range(D)]
    n_fused = sum(p_fused and 2**i <= _FUSED_SCAN_L_CAP for i in built_at)
    plan = []
    if n_fused:
        plan.append(("fused", n_fused, kernel_calls(lay, fused=True)))
    if D > n_fused:
        plan.append(("hist", D - n_fused, kernel_calls(lay, fused=False)))
    return tuple(plan)


def _tree_kernel_calls(D: int, lay: FeatureLayout, sub_levels: tuple,
                       mesh=None) -> int:
    """Mosaic kernel calls of one whole-tree program: chunks x built
    levels over `_tree_kernel_plan`'s modes; 0 with the kernel off."""
    return sum(levels * chunks for _mode, levels, chunks
               in _tree_kernel_plan(D, lay, sub_levels, mesh))


def _record_kernel_calls(plan: tuple) -> None:
    """`tree.kernel.calls`: Mosaic kernel calls handed to the device, counted
    beside `tree.hist.built` (the whole-tree program's once a tree, a
    hist program's at each dispatch), and `tree.kernel.chunks{mode=}`: the
    layout's chunks under each mode's cap, once for every mode the
    dispatched program builds a level with. `plan` is
    `_tree_kernel_plan`'s."""
    from shifu_tpu.obs import registry

    reg = registry()
    for mode, levels, chunks in plan:
        reg.counter("tree.kernel.calls").inc(levels * chunks)
        reg.counter("tree.kernel.chunks", mode=mode).inc(chunks)


def _psum_counts(D: int, T: int, sub_levels: tuple,
                 n_classes: int = 0) -> Tuple[int, int]:
    """(all-reduces, bytes each chip hands in) of one meshed whole-tree
    program, from static shapes alone: a float32 [C, L, T] histogram a
    level (half as wide where the level is built by subtraction) and the
    [C, 2^D] leaf totals."""
    c_hist = n_classes if n_classes >= 3 else 3
    c_leaf = n_classes if n_classes >= 3 else 2
    nodes = sum(2**d // 2 if d and sub_levels[d] else 2**d
                for d in range(D))
    return D + 1, 4 * (c_hist * nodes * T + c_leaf * 2**D)


def _record_psum_counters(D: int, T: int, sub_levels: tuple,
                          n_classes: int = 0) -> None:
    """`tree.psum` / `tree.psum.bytes`: what one meshed tree all-reduces,
    counted beside `tree.hist.built`."""
    from shifu_tpu.obs import registry

    count, nbytes = _psum_counts(D, T, sub_levels, n_classes)
    reg = registry()
    reg.counter("tree.psum").inc(count)
    reg.counter("tree.psum.bytes").inc(nbytes)


def _route_counts(D: int, s_max: int) -> Tuple[int, int]:
    """(dense, gather) levels of one level-wise tree of depth D: how
    `route_rows` moves the rows of levels 1, 2, ..., 2^(D-1)."""
    dense = sum(route_is_dense(2**d, s_max) for d in range(D))
    return dense, D - dense


def _record_route_counters(D: int, s_max: int) -> None:
    """`tree.route.dense` / `tree.route.gather`: a tree's levels routed
    each way, counted beside `tree.hist.built`."""
    from shifu_tpu.obs import registry

    dense, gather = _route_counts(D, s_max)
    reg = registry()
    if dense:
        reg.counter("tree.route.dense").inc(dense)
    if gather:
        reg.counter("tree.route.gather").inc(gather)


@dataclass
class _LayoutArrays:
    """Device copies of the static layout arrays."""

    off: object
    clip: object
    feat_ok_t: object
    is_cat_t: object
    seg_t: object
    pos_t: object
    start_t: object
    size_t: object
    seg0_size: int


def _device_layout(lay: FeatureLayout, feat_ok: np.ndarray, replicate_fn=None):
    import jax.numpy as jnp

    arrs = _LayoutArrays(
        off=jnp.asarray(lay.off),
        clip=jnp.asarray(lay.clip_max),
        feat_ok_t=jnp.asarray(np.asarray(feat_ok, bool)[lay.seg_of_t]),
        is_cat_t=jnp.asarray(lay.is_cat_t),
        seg_t=jnp.asarray(lay.seg_of_t),
        pos_t=jnp.asarray(lay.pos_in_seg),
        start_t=jnp.asarray(lay.seg_start_t),
        size_t=jnp.asarray(lay.seg_size_t),
        seg0_size=int(lay.slots[0]) if len(lay.slots) else 1,
    )
    if replicate_fn is not None:
        for name in ("off", "clip", "feat_ok_t", "is_cat_t", "seg_t",
                     "pos_t", "start_t", "size_t"):
            setattr(arrs, name, replicate_fn(getattr(arrs, name)))
    return arrs


def _scan_batched(hists, la, lay, cfg, L_level):
    """Run split_scan over node batches and concatenate to full-level
    arrays. `hists` yields ([3, Lb, T], Lb, batch_start)."""
    feats, cuts, ranks, leaves, splits, gains, masks, cnts, lcnts = (
        [], [], [], [], [], [], [], [], []
    )
    for hist, Lb, _b0 in hists:
        scan = _get_scan_program(Lb, lay.T, lay.s_max, cfg.impurity,
                                 cfg.min_instances_per_node,
                                 cfg.min_info_gain, cfg.n_classes)
        (f, c, r, lv, sp, g, m, nc, lc) = scan(
            hist, la.feat_ok_t, la.is_cat_t, la.seg_t, la.pos_t, la.start_t,
            la.size_t, la.off, la.clip, la.seg0_size,
        )
        feats.append(f); cuts.append(c); ranks.append(r); leaves.append(lv)
        splits.append(sp); gains.append(g); masks.append(m); cnts.append(nc)
        lcnts.append(lc)
    import jax.numpy as jnp

    cat = lambda xs: jnp.concatenate(xs, axis=0)  # noqa: E731
    return (cat(feats), cat(cuts), cat(ranks), cat(leaves), cat(splits),
            cat(gains), cat(masks), cat(cnts), cat(lcnts))


def _mesh_key(mesh) -> Optional[tuple]:
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def _pallas_state(mesh=None) -> Tuple[bool, bool, bool]:
    """(enabled, interpret, fused_scan) for the rebuilt Pallas kernel
    (ops/hist_pallas.py, knob -Dshifu.pallas.mode, default auto = on for
    TPU backends). fused_scan — the in-kernel split scan — holds only
    single-device: under a mesh each device's histogram is a PARTIAL
    that must psum before any gain math, so meshed growers use the
    kernel in hist-only mode inside shard_map and keep the XLA scan
    after the collective."""
    from shifu_tpu.ops.hist_pallas import pallas_active

    enabled, interpret = pallas_active()
    return enabled, interpret, enabled and mesh is None


def _low_precision(cfg: "TreeTrainConfig") -> bool:
    """bf16 component-plane eligibility for the Pallas kernel: GBT
    binary/regression only — RF planes must stay f32 so integer-weight
    counts are exact (the PR-3 bit-parity gate), and NATIVE multiclass
    planes ARE the counts."""
    return cfg.algorithm == "GBT" and cfg.n_classes < 3


def _get_codes8_program(lay: FeatureLayout, mesh=None):
    """Cached jit: [n, F] i32 codes -> the tree kernel's code operand
    `[F, n]`, rows along the lanes, clipped, int8 where the layout's slot
    counts allow (hoisted once per forest: codes are
    node/label/tree-independent). Under a `mesh` every chip turns its own
    rows: row-sharded `[n, F]` in, `[F, n]` sharded along its second axis
    out, no collective. One program makes the operand for every call and
    tree, so its shape, dtype and sharding never change under the
    whole-tree program's cache key."""
    key = ("codes8", lay.key, _mesh_key(mesh))
    prog = _PROGRAMS.get(key)
    if prog is None:
        import jax

        from shifu_tpu.ops.hist_pallas import make_codes8_fn

        fn = make_codes8_fn(lay)
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            from shifu_tpu.parallel.mesh import row_axes, shard_map_compat

            r_axes = row_axes(mesh)
            rows = r_axes if len(r_axes) > 1 else r_axes[0]
            fn = shard_map_compat(fn, mesh=mesh, in_specs=(P(rows),),
                                  out_specs=P(None, rows))
        prog = profile.wrap("tree.codes8", jax.jit(fn))
        _PROGRAMS[key] = prog
    return prog


def _interleave_children(left_small, built, derived):
    """Interleave per-parent (built, derived) child values into level
    order [2*Lh, ...]: the built (smaller) child sits at 2p when the
    parent's left side was smaller, 2p+1 otherwise."""
    import jax.numpy as jnp

    Lh = built.shape[0]
    ls = left_small.reshape((Lh,) + (1,) * (built.ndim - 1))
    lh = jnp.where(ls, built, derived)
    rh = jnp.where(ls, derived, built)
    return jnp.stack([lh, rh], axis=1).reshape((2 * Lh,)
                                               + built.shape[1:])


def _get_tree_program(D: int, lay: FeatureLayout, impurity: str,
                      min_inst: int, min_gain: float, n_classes: int = 0,
                      mesh=None, sub_levels: tuple = (),
                      acc64: bool = False,
                      lowp: bool = False):
    """ONE jit program for a whole level-wise tree, levels UNROLLED at
    their exact widths: level d builds a [C, 2^d, T] histogram (≈3.5x less
    padded-node work than running every level at 2^D) and the final level
    skips the per-slot histogram entirely (leaf values only need node
    totals). Collapses the per-level dispatch chain into a single device
    call: one host dispatch per tree instead of ~3 per level, and XLA
    schedules the levels back to back with no host in between.

    With a `mesh` the whole program runs under shard_map: rows stay local
    per device, each level's histogram is psum'd over the `data` axis (the
    DTMaster NodeStats merge, DTMaster.java:297-310), and the split scan
    runs replicated — the BSP master/worker exchange as one SPMD program.

    Signature: prog(codes, labels, weights, feat_ok_t), or with the Pallas
    kernel on prog(codes, codes8, labels, weights, feat_ok_t) with
    `_get_codes8_program`'s operand second, on one chip and under a mesh ->
    (feat_flat, mask_flat, leaf_flat, resting, row_pred) — the flat arrays
    ARE the DenseTree layout (level-order concatenation, final level
    -1/zeros), so host assembly is three contiguous transfers instead of
    ~3(D+1) per-level ones (every device->host pull is a sync point).
    Static layout arrays are baked in as constants; only the per-tree
    feature subset stays an argument.

    `sub_levels` (static, from `_sub_plan`) turns on histogram subtraction
    per level: a True at index d builds only the SMALLER child of each
    level-(d-1) split as a half-width [C, 2^(d-1), T] histogram and derives
    every sibling from the retained parent level in one fused elementwise
    step — the same recurrence inside the single-dispatch scan, so the
    one-jit-per-tree path halves its per-level histogram work too."""
    # normalize to exactly D entries so the default () means "subtraction
    # off" rather than an IndexError in the level loop
    sub_levels = tuple(bool(s) for s in sub_levels[:D])
    sub_levels += (False,) * (D - len(sub_levels))
    p_on, p_interp, p_fused = _pallas_state(mesh)
    lowp = bool(lowp and p_on)
    key = ("tree", D, lay.key, impurity, min_inst, float(min_gain),
           n_classes, _mesh_key(mesh), sub_levels, acc64,
           p_on, p_interp, p_fused, lowp)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    T, s_max = lay.T, lay.s_max
    min_inst_eff = max(min_inst, 1)
    # the in-kernel scan unrolls an L-iteration node loop over [W, W]
    # indicators; past this width the program size outweighs the fusion
    # win, so deeper levels run the hist-mode kernel + the XLA scan
    fuse_at = [p_fused and 2**d <= _FUSED_SCAN_L_CAP for d in range(D)]
    fused_fns = [None] * D
    if p_on:
        from shifu_tpu.ops.hist_pallas import (make_fused_level_fn,
                                               make_pallas_hist_fn)

        fused_fns = [make_fused_level_fn(
            2**d, lay, impurity, min_inst_eff, min_gain,
            n_classes=n_classes, interpret=p_interp, low_precision=lowp)
            if fuse_at[d] else None for d in range(D)]
        # hist-mode kernel for the un-fused levels, and for meshed
        # growers (per device inside shard_map; the scan stays XLA,
        # after the psum merges the partials)
        hist_fns = [make_pallas_hist_fn(2**d, lay, n_classes=n_classes,
                                        interpret=p_interp,
                                        low_precision=lowp)
                    if not fuse_at[d] else None for d in range(D)]
    else:
        hist_fns = [_make_hist_fn(2**d, lay, n_classes=n_classes)
                    for d in range(D)]
    scan_fns = [_get_scan_program(2**d, T, s_max, impurity, min_inst_eff,
                                  min_gain, n_classes) for d in range(D)]
    raw_scan_fns = ([_make_scan_fn(2**d, T, s_max, impurity, min_inst_eff,
                                   min_gain, n_classes) for d in range(D)]
                    if p_fused else None)
    leaf_acc, leaf_finalize = _make_leaf_fn(2**D, n_classes)

    # static layout constants (closed over; jit hoists them once)
    off_c = jnp.asarray(lay.off)
    clip_c = jnp.asarray(lay.clip_max)
    is_cat_c = jnp.asarray(lay.is_cat_t)
    seg_c = jnp.asarray(lay.seg_of_t)
    pos_c = jnp.asarray(lay.pos_in_seg)
    start_c = jnp.asarray(lay.seg_start_t)
    size_c = jnp.asarray(lay.seg_size_t)
    seg0 = int(lay.slots[0]) if len(lay.slots) else 1
    on_mesh = mesh is not None
    if on_mesh:
        from shifu_tpu.parallel.mesh import row_axes

        r_axes = row_axes(mesh)

    acc_dt = jnp.float64 if acc64 else jnp.float32
    derive = _get_derive_program()

    def tree_body(codes, *rest):
        # with the kernel on, the program's second argument is the hoisted
        # code operand (`_get_codes8_program`), on one chip and under a
        # mesh alike: it enters here and not through a wrapper of its own
        # (a frame more under every traced operation: PERF.md, section 7)
        codes8 = rest[0] if p_on else None
        labels, weights, feat_ok_t = rest[-3:]
        n = codes.shape[0]
        node = jnp.zeros(n, jnp.int32)
        active = jnp.ones(n, bool)
        resting = jnp.zeros(n, jnp.int32)
        feats_l, masks_l, leaves_l = [], [], []
        # retained parent level: (hist_acc, is_split, left_small, the build
        # mask of its children's rows)
        prev = None

        def call_hist(L, idx, node_arg, act_arg):
            # the kernel's entry reads the hoisted code operand, the XLA
            # lowering the layout's constants
            rest = (codes8,) if p_on else (off_c, clip_c, seg_c, pos_c)
            with phase(L, "hist"):
                h = hist_fns[idx](codes, labels, weights, node_arg,
                                  act_arg, *rest)
            if on_mesh:
                # the level's all-reduce under a scope of its own: it waits
                # for the slowest chip, which the histogram does not
                with phase(L, "psum"):
                    h = jax.lax.psum(h, r_axes)
            return h

        def xla_scan(idx, hist, raw=False):
            fn = raw_scan_fns[idx] if raw else scan_fns[idx]
            return fn(hist, feat_ok_t, is_cat_c, seg_c, pos_c, start_c,
                      size_c, off_c, clip_c, seg0)

        # One named scope a level and one a phase inside it: op metadata
        # only (the `tf_op` a profiler trace shows for each device
        # operation), so device time can be told by level and phase
        # whatever the fusions are numbered. `hist` is the kernel or
        # `call_hist` with its pads and casts, `derive` the subtraction
        # and interleave, `scan` the XLA scan where it runs, `route` the
        # rows' move to their children; under a mesh `psum` is the
        # all-reduce of the level's histogram (`tree.leaf/psum`: of the
        # leaf totals). What is done to the code operand carries
        # `tree.codes` (ops/hist_pallas.py): its pad to whole blocks
        # inside a level's `hist` here, its clip, cast and turn a scope
        # of their own in the programs that have no level (`tree.hist`,
        # `tree.codes8`).
        def phase(L, what):
            return jax.named_scope("tree.L%d/%s" % (L, what))

        for d in range(D):
            L = 2**d
            if prev is not None and fuse_at[d - 1]:
                # subtraction composed with the fused kernel: grow only
                # the SMALLER child in-kernel (hist + its scan in one
                # pass), derive the sibling as parent − built and scan it
                # with the XLA reference, then interleave per parent
                p_hist, p_split, left_small, build_row = prev
                with phase(L, "hist"):
                    built, scan_b = fused_fns[d - 1](
                        codes, codes8, labels, weights, node >> 1,
                        build_row, feat_ok_t)
                with phase(L, "derive"):
                    b_acc = built.astype(p_hist.dtype)
                    derived = jnp.where(p_split[None, :, None],
                                        p_hist - b_acc,
                                        jnp.zeros_like(p_hist))
                with phase(L, "scan"):
                    scan_d = xla_scan(d - 1, derived.astype(jnp.float32),
                                      raw=True)
                with phase(L, "derive"):
                    (bf, _br, _rank, lv, is_split, _g, lm, nc,
                     lc) = tuple(
                        _interleave_children(left_small, xb, xd)
                        for xb, xd in zip(scan_b, scan_d))
                    hist_acc = jnp.concatenate(
                        [_interleave_children(left_small, b_acc[c],
                                              derived[c])
                         [None] for c in range(b_acc.shape[0])], axis=0)
            elif prev is None and fuse_at[d]:
                with phase(L, "hist"):
                    hist, scan_t = fused_fns[d](codes, codes8, labels,
                                                weights, node, active,
                                                feat_ok_t)
                    (bf, _br, _rank, lv, is_split, _g, lm, nc,
                     lc) = scan_t
                    hist_acc = hist.astype(acc_dt) if acc64 else hist
            elif prev is not None:  # sub_levels[d]: derive from the parent
                p_hist, p_split, left_small, build_row = prev
                with phase(L, "hist"):
                    nhalf = node >> 1
                built = call_hist(L, d - 1, nhalf, build_row)
                with phase(L, "derive"):
                    hist, hist_acc = derive(p_hist, built, p_split,
                                            left_small)
                with phase(L, "scan"):
                    (bf, _br, _rank, lv, is_split, _g, lm, nc,
                     lc) = xla_scan(d, hist)
            else:
                hist = call_hist(L, d, node, active)
                hist_acc = hist.astype(acc_dt) if acc64 else hist
                with phase(L, "scan"):
                    (bf, _br, _rank, lv, is_split, _g, lm, nc,
                     lc) = xla_scan(d, hist)
            retain = d + 1 < D and sub_levels[d + 1]
            with phase(L, "route"):
                left_small = lc <= nc - lc if retain else None
                resting, node, active, build_row = route_rows(
                    codes, node, active, resting, bf, is_split, lm,
                    L - 1, clip_c, left_small)
            prev = ((hist_acc, is_split, left_small, build_row)
                    if retain else None)
            feats_l.append(jnp.where(is_split, bf, -1))
            masks_l.append(lm)
            leaves_l.append(lv)

        # final level: node totals only (no per-slot histogram)
        L2 = 2**D
        with jax.named_scope("tree.leaf"):
            acc = leaf_acc(labels, weights, node, active)
            if on_mesh:
                with jax.named_scope("psum"):  # tree.leaf/psum
                    acc = jax.lax.psum(acc, r_axes)
            leaves_l.append(leaf_finalize(acc))
            resting = jnp.where(active, (L2 - 1) + node, resting)
        feat_flat = jnp.concatenate(
            feats_l + [jnp.full(L2, -1, jnp.int32)])
        mask_flat = jnp.concatenate(
            masks_l + [jnp.zeros((L2, s_max), bool)], axis=0)
        leaf_flat = jnp.concatenate(leaves_l)
        with jax.named_scope("tree.leaf"):
            row_pred = _lookup(leaf_flat, resting)
        return feat_flat, mask_flat, leaf_flat, resting, row_pred

    body = tree_body
    if on_mesh:
        from jax.sharding import PartitionSpec as P

        from shifu_tpu.parallel.mesh import shard_map_compat

        rows = r_axes if len(r_axes) > 1 else r_axes[0]
        rspec = P(rows)
        turned = (P(None, rows),) if p_on else ()
        body = shard_map_compat(
            body, mesh=mesh, in_specs=(rspec, *turned, rspec, rspec, P()),
            out_specs=(P(), P(), P(), rspec, rspec))
    prog = jax.jit(body)
    # the fused-kernel grower is its own profiler seam so `shifu profile
    # --diff` can compare it against the XLA path's tree.whole_tree
    prog = profile.wrap("tree.pallas_fused" if p_fused
                        else "tree.whole_tree", prog)
    _PROGRAMS[key] = prog
    return prog


def _assemble_dense_tree(feat_flat, mask_flat, leaf_flat,
                         D: int) -> DenseTree:
    """Host assembly: the program's flat arrays already ARE the DenseTree
    level-order layout."""
    return DenseTree(
        feature=np.asarray(feat_flat, np.int32),
        left_mask=np.asarray(mask_flat, bool),
        leaf_value=np.asarray(leaf_flat, np.float32),
        weight=1.0,
    )


def build_tree(
    codes,
    labels,
    weights,
    slots: np.ndarray,
    is_cat: np.ndarray,
    cfg: TreeTrainConfig,
    feat_ok: np.ndarray,
    mesh=None,
) -> Tuple[DenseTree, np.ndarray]:
    """One LEVEL-WISE tree. codes [n, F] int32 on device; labels/weights [n]
    f32 on device (weights already carry bagging significance). With a
    `mesh`, the row arrays must already be sharded over its `data` axis.

    Returns (tree, resting [n] int32) — resting is the node index each row
    ends at, so callers get per-row predictions without re-traversal."""
    import jax.numpy as jnp

    n, F = codes.shape
    lay = make_layout(list(np.asarray(slots)), list(np.asarray(is_cat, bool)))
    D = cfg.max_depth
    batch_cap = _node_batch_size(lay.T, cfg.max_stats_memory_mb,
                                 cfg.n_classes)

    replicate_fn = None
    if mesh is not None:
        from shifu_tpu.parallel.mesh import replicate, shard_rows

        replicate_fn = lambda a: replicate(a, mesh)  # noqa: E731

    sub_levels, acc64 = _sub_plan(cfg, batch_cap)

    # fused single-dispatch path: whole tree in ONE jit call when the
    # full-width [3, 2^D, T] histogram fits the stats-memory budget —
    # collapses ~3 dispatches/level into 1/tree (no host between
    # levels). The program bakes the layout in; only the feature-subset
    # mask transfers.
    if 2**D <= batch_cap:
        lowp = _low_precision(cfg)
        prog = _get_tree_program(D, lay, cfg.impurity,
                                 cfg.min_instances_per_node,
                                 cfg.min_info_gain,
                                 n_classes=cfg.n_classes, mesh=mesh,
                                 sub_levels=sub_levels, acc64=acc64,
                                 lowp=lowp)
        fot = jnp.asarray(np.asarray(feat_ok, bool)[lay.seg_of_t])
        if replicate_fn is not None:
            fot = replicate_fn(fot)
        hoisted = ((_get_codes8_program(lay, mesh)(codes),)
                   if _pallas_state(mesh)[0] else ())
        feats_d, masks_d, leaves_d, resting, _row_pred = prog(
            codes, *hoisted, labels, weights, fot)
        import jax

        _record_hist_counters(
            *_plan_counts(sub_levels[:D], cfg.hist_subtraction))
        _record_kernel_calls(_tree_kernel_plan(D, lay, sub_levels, mesh))
        _record_route_counters(D, lay.s_max)
        if mesh is not None:
            _record_psum_counters(D, lay.T, sub_levels, cfg.n_classes)
        feats_h, masks_h, leaves_h = jax.device_get(
            (feats_d, masks_d, leaves_d))
        return _assemble_dense_tree(feats_h, masks_h, leaves_h, D), resting

    la = _device_layout(lay, feat_ok, replicate_fn)

    if mesh is not None:
        from shifu_tpu.parallel.mesh import shard_rows

        node_local = shard_rows(jnp.zeros(n, dtype=jnp.int32), mesh)
        active = shard_rows(jnp.ones(n, dtype=bool), mesh)
        resting = shard_rows(jnp.zeros(n, dtype=jnp.int32), mesh)
    else:
        node_local = jnp.zeros(n, dtype=jnp.int32)
        active = jnp.ones(n, dtype=bool)
        resting = jnp.zeros(n, dtype=jnp.int32)

    derive = _get_derive_program()
    acc_dt = jnp.float64 if acc64 else jnp.float32
    sub_on = cfg.hist_subtraction
    lowp = _low_precision(cfg)
    n_built = n_derived = n_fallback = 0
    feat_levels, mask_levels, leaf_levels = [], [], []
    # retained parent level: (hist_acc, is_split, left_small, the build mask
    # of its children's rows)
    prev = None
    for depth in range(D + 1):
        L = 2**depth
        base = L - 1
        final = depth == D
        # retention for the NEXT level's derivation implies that level
        # passed the gate, so THIS level is at most cap/4 nodes: one batch
        retain_next = (not final) and sub_on and sub_levels[depth + 1]
        if prev is not None:  # sub_levels[depth]: half-width build + derive
            Lh = L // 2
            p_hist, p_split, left_small, build_row = prev
            hist_p = _get_hist_program(Lh, lay, allow_matmul=mesh is None,
                                       n_classes=cfg.n_classes,
                                       low_precision=lowp)
            built = hist_p(codes, labels, weights, node_local >> 1, build_row,
                           la.off, la.clip, la.seg_t, la.pos_t)
            hist_f32, hist_acc = derive(p_hist, built, p_split, left_small)
            parts = [(hist_f32, L, 0)]
            n_built += Lh
            n_derived += Lh
        elif retain_next:  # full rebuild, kept whole for the next level
            hist_p = _get_hist_program(L, lay, allow_matmul=mesh is None,
                                       n_classes=cfg.n_classes,
                                       low_precision=lowp)
            full = hist_p(codes, labels, weights, node_local, active,
                          la.off, la.clip, la.seg_t, la.pos_t)
            hist_acc = full.astype(acc_dt) if acc64 else full
            parts = [(full, L, 0)]
            n_built += L
            if sub_on and depth >= 1:
                n_fallback += 1
        else:  # budget-batched full rebuild (lazy: scan drops each batch)
            hist_acc = None

            def hist_batches(L=L, node_local=node_local, active=active):
                for b0 in range(0, L, batch_cap):
                    Lb = min(batch_cap, L - b0)
                    hist_p = _get_hist_program(Lb, lay,
                                               allow_matmul=mesh is None,
                                               n_classes=cfg.n_classes,
                                               low_precision=lowp)
                    in_batch = (active & (node_local >= b0)
                                & (node_local < b0 + Lb))
                    yield hist_p(codes, labels, weights, node_local - b0,
                                 in_batch, la.off, la.clip, la.seg_t,
                                 la.pos_t), Lb, b0

            parts = hist_batches()
            n_built += L
            if sub_on and depth >= 1:
                n_fallback += -(-L // batch_cap)

        (bf, _br, _rank, lv, is_split, _gain, lm, nc, lc) = _scan_batched(
            parts, la, lay, cfg, L
        )
        if final:  # leaf values for the deepest children + settle leftovers
            leaf_levels.append(lv)
            feat_levels.append(jnp.full(L, -1, jnp.int32))
            mask_levels.append(jnp.zeros((L, lay.s_max), bool))
            resting = jnp.where(active, base + node_local, resting)
            break
        left_small = lc <= nc - lc if retain_next else None
        resting, node_local, active, build_row = _get_update_program()(
            codes, node_local, active, resting, bf, is_split, lm,
            jnp.int32(base), la.clip, left_small)
        prev = ((hist_acc, is_split, left_small, build_row)
                if retain_next else None)
        feat_levels.append(jnp.where(is_split, bf, -1))
        mask_levels.append(lm)
        leaf_levels.append(lv)
    _record_hist_counters(n_built, n_derived, n_fallback)
    _record_route_counters(D, lay.s_max)

    # ONE host sync for the whole tree
    import jax

    feature, left_mask, leaf_value = jax.device_get(
        (jnp.concatenate(feat_levels), jnp.concatenate(mask_levels, axis=0),
         jnp.concatenate(leaf_levels))
    )
    tree = DenseTree(
        feature=np.asarray(feature, np.int32),
        left_mask=np.asarray(left_mask, bool),
        leaf_value=np.asarray(leaf_value, np.float32),
        weight=1.0,
    )
    return tree, resting


def build_tree_leafwise(
    codes,
    labels,
    weights,
    slots: np.ndarray,
    is_cat: np.ndarray,
    cfg: TreeTrainConfig,
    feat_ok: np.ndarray,
) -> Tuple[DenseTree, np.ndarray]:
    """LEAF-WISE growth under maxLeaves (DTMaster.java:137: the toSplitQueue
    splits the best-gain leaf first). Each iteration evaluates only the new
    frontier nodes (a 2-slot histogram batch), picks the global best-gain
    leaf, and splits it; nodes append parent-before-child, so children get
    EXPLICIT pointers and the tree may be lopsided.

    Returns (tree, resting node ids [n])."""
    import jax.numpy as jnp

    n, F = codes.shape
    lay = make_layout(list(np.asarray(slots)), list(np.asarray(is_cat, bool)))
    la = _device_layout(lay, feat_ok)
    max_leaves = cfg.max_leaves
    max_nodes = 2 * max_leaves - 1

    node_id = jnp.zeros(n, dtype=jnp.int32)  # explicit node ids per row

    # host-side growing tree arrays (parent-before-child ordering)
    feature = [-1]
    left_c = [-1]
    right_c = [-1]
    leaf_val = [0.0]
    masks = [np.zeros(lay.s_max, bool)]
    depth_of = {0: 0}
    # candidate splits per leaf: id -> (gain, feat, cut_rank, rank_row, mask)
    candidates: Dict[int, tuple] = {}

    hist1 = _get_hist_program(1, lay, n_classes=cfg.n_classes,
                              low_precision=_low_precision(cfg))
    scan1 = _get_scan_program(1, lay.T, lay.s_max, cfg.impurity,
                              cfg.min_instances_per_node, cfg.min_info_gain,
                              cfg.n_classes)
    # parent-reuse: each candidate's histogram is retained (budget-gated by
    # the MaxStatsMemoryMB node-plane cap, f64 planes counting double) so a
    # split builds ONE child and derives the sibling as parent − built —
    # one frontier histogram per split instead of two
    sub_on = cfg.hist_subtraction
    acc64 = _sub_acc64()
    acc_dt = jnp.float64 if acc64 else jnp.float32
    batch_cap = _node_batch_size(lay.T, cfg.max_stats_memory_mb,
                                 cfg.n_classes)
    plane_cost = 2 if acc64 else 1
    stored: Dict[int, object] = {}  # leaf id -> [C, 1, T] hist, acc dtype
    n_built = n_derived = n_fallback = 0

    def build_hist(lid: int):
        act = node_id == lid
        return hist1(codes, labels, weights, jnp.zeros(n, jnp.int32), act,
                     la.off, la.clip, la.seg_t, la.pos_t)

    def evaluate(lid: int, hist):
        """Candidate split for one leaf from its (built or derived)
        histogram; `hist` may arrive in the f64 accumulator dtype and is
        downcast only for the scan."""
        (f, c, r, lv, sp, g, m, nc, lc) = scan1(
            hist.astype(jnp.float32) if hist.dtype != jnp.float32 else hist,
            la.feat_ok_t, la.is_cat_t, la.seg_t, la.pos_t,
            la.start_t, la.size_t, la.off, la.clip, la.seg0_size,
        )
        leaf_val[lid] = float(lv[0])
        if bool(sp[0]) and depth_of[lid] < cfg.max_depth:
            candidates[lid] = (float(g[0]), int(f[0]), int(c[0]),
                               r[0], np.asarray(m[0]), float(lc[0]),
                               float(nc[0]))
            if sub_on and (len(stored) + 1) * plane_cost <= batch_cap:
                stored[lid] = (hist.astype(acc_dt)
                               if hist.dtype != acc_dt else hist)

    evaluate(0, build_hist(0))
    n_built += 1
    n_leaves = 1
    while n_leaves < max_leaves and candidates:
        best_id = max(candidates, key=lambda k: candidates[k][0])
        (_gain, bf, cut, rank_row, mask_row, lcnt,
         ncnt) = candidates.pop(best_id)
        parent_hist = stored.pop(best_id, None)
        li, ri = len(feature), len(feature) + 1
        if ri > max_nodes:
            break
        feature[best_id] = bf
        left_c[best_id] = li
        right_c[best_id] = ri
        masks[best_id] = mask_row
        for _ in range(2):
            feature.append(-1)
            left_c.append(-1)
            right_c.append(-1)
            leaf_val.append(0.0)
            masks.append(np.zeros(lay.s_max, bool))
        depth_of[li] = depth_of[ri] = depth_of[best_id] + 1
        # reroute rows of the split node
        sel = node_id == best_id
        code = codes[:, bf]
        cf = int(lay.off[bf]) + jnp.clip(code, 0, int(lay.clip_max[bf]))
        goes_left = rank_row[cf] <= cut
        node_id = jnp.where(sel, jnp.where(goes_left, li, ri), node_id)
        n_leaves += 1
        if parent_hist is not None:
            # build the smaller child, derive the sibling from the parent
            smaller, larger = ((li, ri) if lcnt <= ncnt - lcnt
                               else (ri, li))
            built = build_hist(smaller)
            derived = parent_hist - built.astype(parent_hist.dtype)
            evaluate(smaller, built)
            evaluate(larger, derived)
            n_built += 1
            n_derived += 1
        else:
            evaluate(li, build_hist(li))
            evaluate(ri, build_hist(ri))
            n_built += 2
            if sub_on:
                n_fallback += 1
    _record_hist_counters(n_built, n_derived, n_fallback)

    tree = DenseTree(
        feature=np.asarray(feature, np.int32),
        left_mask=np.stack(masks).astype(bool),
        leaf_value=np.asarray(leaf_val, np.float32),
        weight=1.0,
        left=np.asarray(left_c, np.int32),
        right=np.asarray(right_c, np.int32),
    )
    return tree, node_id


# ---------------------------------------------------------------------------
# early stop (dt/DTEarlyStopDecider.java:49)
# ---------------------------------------------------------------------------


class _MinQueue:
    def __init__(self, capacity: int):
        self.capacity = capacity
        self.restart()

    def restart(self):
        self.min = float("inf")
        self.size = -1

    def add(self, v: float) -> bool:
        self.min = min(self.min, v)
        self.size += 1
        return self.size >= self.capacity

    def pop_min(self) -> float:
        m = self.min
        self.restart()
        return m


class _AverageQueue:
    def __init__(self, capacity: int):
        self.capacity = capacity
        self.arr = [0.0] * capacity
        self.restart()

    def restart(self):
        self.total = 0
        self.sum = 0.0

    def add(self, v: float) -> bool:
        idx = self.total % self.capacity
        self.total += 1
        if self.total <= self.capacity:
            self.sum += v
            self.arr[idx] = self.sum / self.total
            return False
        self.sum += v - self.arr[idx]
        self.arr[idx] = self.sum / self.capacity
        return True

    def gain(self) -> float:
        cur = (self.total - 1) % self.capacity
        last = (self.total - 2) % self.capacity
        return self.arr[last] - self.arr[cur]

    def average(self) -> float:
        k = min(self.total, self.capacity)
        return self.arr[(self.total - 1) % self.capacity] if k else 0.0


class DTEarlyStopDecider:
    """Windowed early-stop: min over a window feeds a moving average; when
    the average's gain stays ~zero for 3 windows the decider "restarts", and
    3 restarts mean stop (dt/DTEarlyStopDecider.java:49, MAGIC_NUMBER=3,
    NEARLY_ZERO=1e-6)."""

    MAGIC = 3
    NEARLY_ZERO = 1e-6

    def __init__(self, tree_depth: int):
        if tree_depth <= 0:
            raise ValueError("tree depth must be positive")
        self.min_queue = _MinQueue(tree_depth * self.MAGIC)
        self.avg_queue = _AverageQueue(tree_depth)
        self.gain_zero_count = 0
        self.restart_count = 0

    def add(self, validation_error: float) -> bool:
        if self.min_queue.add(validation_error):
            m = self.min_queue.pop_min()
            if self.avg_queue.add(m):
                if self.avg_queue.gain() < self.NEARLY_ZERO:
                    self.gain_zero_count += 1
                    if self.gain_zero_count >= self.MAGIC:
                        self.avg_queue.restart()
                        self.restart_count += 1
                        self.gain_zero_count = 0
                else:
                    self.gain_zero_count = 0
        return self.can_stop()

    def can_stop(self) -> bool:
        return self.restart_count >= self.MAGIC


# ---------------------------------------------------------------------------
# full training run
# ---------------------------------------------------------------------------


@dataclass
class TreeTrainResult:
    spec: TreeModelSpec
    train_error: float
    valid_error: float


def _get_errors_program():
    """Cached (score, y, valid_mask, real) -> (train_err, valid_err) —
    defined at module level so repeated train_trees calls reuse ONE
    compiled program instead of re-jitting a fresh closure per run."""
    key = ("errors",)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    @jax.jit
    def errors_of(score, y, vm, real):
        sq = (y - score) ** 2
        vsel = vm & real
        tsel = (~vm) & real
        v = jnp.sum(jnp.where(vsel, sq, 0.0)) / jnp.maximum(
            jnp.sum(vsel), 1.0)
        t = jnp.sum(jnp.where(tsel, sq, 0.0)) / jnp.maximum(
            jnp.sum(tsel), 1.0)
        return t, v

    prog = profile.wrap("tree.errors", errors_of)
    _PROGRAMS[key] = prog
    return prog


def _get_cls_errors_program():
    key = ("cls_errors",)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    @jax.jit
    def cls_errors_of(votes, y, vm, real):
        pred_class = jnp.argmax(votes, axis=1).astype(jnp.float32)
        err = (pred_class != y).astype(jnp.float32)
        vsel = vm & real
        tsel = (~vm) & real
        v = (jnp.sum(jnp.where(vsel, err, 0.0))
             / jnp.maximum(jnp.sum(vsel), 1.0))
        t = (jnp.sum(jnp.where(tsel, err, 0.0))
             / jnp.maximum(jnp.sum(tsel), 1.0))
        return t, v

    prog = profile.wrap("tree.errors", cls_errors_of)
    _PROGRAMS[key] = prog
    return prog


def _score_existing(trees: List[DenseTree], codes) -> "object":
    """Raw GBT prediction F(x) of an existing forest (continuous-training
    recovery: DTWorker.recoverGBTData:1452 re-derives predict state)."""
    import jax.numpy as jnp

    from shifu_tpu.models.tree import traverse_trees

    if not trees:
        return jnp.zeros(codes.shape[0], dtype=jnp.float32)
    per_tree = traverse_trees(trees, codes)
    # sequential left-to-right fold, NOT jnp.sum: the uninterrupted run
    # accumulates `pred += weight_k * tree_pred` one tree at a time, and
    # jnp.sum's pairwise reduction associates f32 differently — a resumed
    # GBT run would see ~1e-7-shifted residual labels and drift off the
    # bit-equal contract (tests/test_tree_parity.py::test_resume_is_bit_equal)
    score = jnp.zeros(codes.shape[0], dtype=jnp.float32)
    for t in range(per_tree.shape[1]):
        score = score + per_tree[:, t]
    return score


def _assemble_deferred(trees: List, deferred: List[tuple],
                       cfg: TreeTrainConfig, extra=None, *, call: int,
                       k: int):
    """Materialize fused-path trees from their device results. The backlog
    is stacked on device first so the host pull is ONE device_get of
    three contiguous arrays (plus the caller's `extra` pytree, fetched in
    the same round-trip), not three per tree — each pull is a host sync
    that stalls the async dispatch chain. Returns the fetched `extra`.
    `call` and `k` (the newest tree of the backlog) label the spans: the
    pull is a `train.tree.wait` inside the `train.tree.assemble`."""
    import jax
    import jax.numpy as jnp

    with span("train.tree.assemble", call=call, k=k):
        f_all = jnp.stack([f for _k, _w, f, _m, _lv in deferred])
        m_all = jnp.stack([m for _k, _w, _f, m, _lv in deferred])
        l_all = jnp.stack([lv for _k, _w, _f, _m, lv in deferred])
        with span("train.tree.wait", call=call, k=k):
            fh_all, mh_all, lh_all, extra_h = jax.device_get(
                (f_all, m_all, l_all, extra))
        for i, (ki, weight_k, _f, _m, _lv) in enumerate(deferred):
            tree = _assemble_dense_tree(fh_all[i], mh_all[i], lh_all[i],
                                        cfg.max_depth)
            tree.weight = weight_k
            trees[ki] = tree  # trees list is indexed by global tree id
        deferred.clear()
    return extra_h


def train_trees(
    codes: np.ndarray,
    tags: np.ndarray,
    weights: np.ndarray,
    slots: List[int],
    is_cat: List[bool],
    columns: List[str],
    cfg: TreeTrainConfig,
    boundaries: Optional[List] = None,
    categories: Optional[List] = None,
    progress_cb=None,
    mesh=None,
    init_trees: Optional[List[DenseTree]] = None,
    init_valid_errors: Optional[List[float]] = None,
    checkpoint_cb: Optional[
        Callable[[int, List[DenseTree], List[float]], None]
    ] = None,
) -> TreeTrainResult:
    """Full GBT/RF training run. `mesh` shards rows over its `data` axis
    (the TPU equivalent of DTWorker row shards); None = single device.

    `init_trees` resumes/continues from an existing forest: per-tree RNG
    streams are keyed by (seed, tree index), so training trees k..N after
    loading trees 0..k-1 reproduces the uninterrupted run BIT-EQUAL
    (DTMaster checkpoint recovery :284-291; GBT isContinuous
    TrainModelProcessor.java:1166-1184). Pass the checkpointed
    `init_valid_errors` history too so the early-stop state (worsen count,
    windowed decider) replays exactly; `checkpoint_cb(k, trees,
    valid_errors)` fires after each tree for the caller to persist both."""
    import jax
    import jax.numpy as jnp

    # No inner function for the body: what the first tree traces is traced
    # from this frame, and one more Python frame under it cost the whole-tree
    # program 3 s of its 15 s of tracing on a v5e host (PERF.md, PR 26).
    call = int(registry().counter("train.calls", engine="tree").inc())
    with span("train.trees.call", call=call, rows=int(codes.shape[0]),
              trees=int(cfg.tree_num), depth=int(cfg.max_depth)):
        with span("train.trees.prologue", call=call):
            n, F = codes.shape
            # rng draws always use the UNpadded count so the stream (and
            # therefore every tree) is identical with and without a mesh; a
            # code matrix placed over the mesh ahead of the call may carry
            # the mesh's padding rows, the tags never do
            n_orig = int(np.shape(tags)[0])
            valid_mask = np.random.default_rng([cfg.seed, 999_983]).random(
                n_orig) < cfg.valid_set_rate
            from shifu_tpu.parallel.mesh import (pad_rows, pull_rows,
                                                 shard_rows)

            if mesh is not None:
                row_put = lambda a: shard_rows(a, mesh)  # noqa: E731
                # row-sharded jax.Arrays stay on the mesh as they lie (cast
                # and, where the row count does not divide, padded on the
                # devices); host arrays are padded on the host and put once.
                # Either way the validity draw is the one array made here.
                on_device = isinstance(codes, jax.Array)
                h2d = registry().counter("mesh.h2d_bytes")
                with span("train.trees.shard", call=call,
                          source="device" if on_device else "host") as sh:
                    crossed = h2d.value
                    rows_in = [
                        a.astype(dt) if isinstance(a, jax.Array)
                        else np.asarray(a, dt)
                        for a, dt in ((codes, np.int32), (tags, np.float32),
                                      (weights, np.float32))]
                    rows_in += [valid_mask,
                                jnp.ones(n_orig, bool) if on_device
                                else np.ones(n_orig, bool)]
                    padded, _ = pad_rows(rows_in, mesh.devices.size)
                    n = padded[0].shape[0]
                    codes_j, y_j, w_j, vm_j, real_j = [
                        shard_rows(a, mesh) for a in padded]
                    base_w_j = jnp.where(vm_j, 0.0, w_j)
                    sh["bytes"] = int(h2d.value - crossed)
            else:
                # device-resident inputs stay on device (the code matrix is the
                # big one and may already live in HBM from a previous run)
                row_put = jnp.asarray
                codes_j = (codes.astype(jnp.int32) if isinstance(codes, jax.Array)
                           else jnp.asarray(np.asarray(codes, np.int32)))
                y_j = (tags.astype(jnp.float32) if isinstance(tags, jax.Array)
                       else jnp.asarray(np.asarray(tags, np.float32)))
                w_j = (weights.astype(jnp.float32)
                       if isinstance(weights, jax.Array)
                       else jnp.asarray(np.asarray(weights, np.float32)))
                vm_j = jnp.asarray(valid_mask)
                base_w_j = jnp.where(vm_j, 0.0, w_j)
                real_j = jnp.ones(n, dtype=bool)
            slots_np = np.asarray(slots, dtype=np.int32)
            is_cat_np = np.asarray(is_cat, dtype=bool)

            k_sub = subset_count(cfg.feature_subset_strategy, F)
            leaf_wise = cfg.max_leaves and cfg.max_leaves > 0
            if leaf_wise and mesh is not None:
                log.warning("leaf-wise growth runs single-device; ignoring mesh")
                mesh = None
            trees: List[DenseTree] = list(init_trees or [])
            start_k = len(trees)
            lr = cfg.learning_rate
            is_gbt = cfg.algorithm == "GBT"
            log_loss = cfg.loss == "log"

            reg_err = _get_errors_program()
            errors_of = lambda score: reg_err(score, y_j, vm_j, real_j)  # noqa: E731

            is_cls = cfg.n_classes >= 3
            if is_cls and is_gbt:
                raise ValueError(
                    "NATIVE multi-class tree training is RF-only (the reference "
                    "supports GBT multi-class via ONEVSALL, "
                    "TrainModelProcessor.java:341-349)"
                )
            if is_cls:
                c_err = _get_cls_errors_program()
                cls_errors_of = lambda votes: c_err(  # noqa: E731
                    votes, y_j, vm_j, real_j)

            # prediction state re-derived from loaded trees on resume (the workers'
            # recoverGBTData analog): GBT keeps the raw sum F(x), RF the running
            # mean over trees built so far — classification keeps per-class VOTES
            votes = None
            if is_cls:
                if start_k:
                    from shifu_tpu.models.tree import traverse_trees

                    per_tree = pull_rows(
                        traverse_trees(trees, codes_j))  # [n, k] class
                    votes_np = np.zeros((n, cfg.n_classes), np.float32)
                    for col in range(per_tree.shape[1]):
                        cls_idx = np.clip(per_tree[:, col].astype(np.int64), 0,
                                          cfg.n_classes - 1)
                        votes_np[np.arange(n), cls_idx] += 1.0
                    votes = row_put(votes_np)
                else:
                    votes = row_put(np.zeros((n, cfg.n_classes), np.float32))
                pred = row_put(jnp.zeros(n, dtype=jnp.float32))
            elif start_k:
                if is_gbt and cfg.dropout_rate > 0.0:
                    # DART resume: regenerate each tree's keyed per-row keep mask
                    # so the running prediction matches the uninterrupted run
                    from shifu_tpu.models.tree import traverse_trees

                    per_tree = pull_rows(
                        traverse_trees(trees, codes_j))  # [n, k]
                    s = np.zeros(n, np.float32)
                    for col in range(per_tree.shape[1]):
                        contrib = per_tree[:, col]  # weight folded by traverse
                        if col > 0:
                            keep = (np.random.default_rng([cfg.seed, col, 777])
                                    .random(n_orig) >= cfg.dropout_rate)
                            keep = np.pad(keep.astype(np.float32),
                                          (0, n - n_orig), constant_values=1.0)
                            contrib = contrib * keep
                        s += contrib
                else:
                    s = pull_rows(_score_existing(trees, codes_j))
                pred = row_put((s if is_gbt else s / start_k).astype(np.float32))
            else:
                pred = row_put(jnp.zeros(n, dtype=jnp.float32))
            # replay the checkpointed error history through the early-stop state so
            # a resumed run stops at the same tree the uninterrupted run would
            valid_errors: List[float] = list(init_valid_errors or [])[:start_k]
            bad_rounds = 0
            decider = (DTEarlyStopDecider(cfg.max_depth)
                       if cfg.enable_early_stop else None)
            for idx, v in enumerate(valid_errors):
                if decider is not None:
                    decider.add(v)
                if cfg.early_stop_rounds and idx >= 1:
                    if v > min(valid_errors[:idx + 1]):
                        bad_rounds += 1
                    else:
                        bad_rounds = 0
            terr = verr = 0.0

            # per-tree host sync only when someone consumes per-tree results;
            # otherwise the whole forest builds as ONE async dispatch chain
            # (progress/checkpoint/early-stop all off => no host round-trips
            # between trees)
            need_sync = bool(progress_cb or checkpoint_cb or cfg.early_stop_rounds
                             or decider is not None)
            lay = make_layout([int(s) for s in slots_np], [bool(c) for c in is_cat_np])
            batch_cap = _node_batch_size(lay.T, cfg.max_stats_memory_mb,
                                         cfg.n_classes)
            fused = (not leaf_wise) and 2**cfg.max_depth <= batch_cap
            codes8_forest = ()
            if fused:
                replicate_fn = None
                if mesh is not None:
                    from shifu_tpu.parallel.mesh import replicate

                    replicate_fn = lambda a: replicate(a, mesh)  # noqa: E731
                sub_levels, acc64 = _sub_plan(cfg, batch_cap)
                sub_counts = _plan_counts(sub_levels[:cfg.max_depth],
                                          cfg.hist_subtraction)
                kernel_plan = _tree_kernel_plan(cfg.max_depth, lay,
                                                sub_levels, mesh)
                tree_prog = _get_tree_program(
                    cfg.max_depth, lay, cfg.impurity,
                    cfg.min_instances_per_node, cfg.min_info_gain,
                    n_classes=cfg.n_classes, mesh=mesh,
                    sub_levels=sub_levels, acc64=acc64,
                    lowp=_low_precision(cfg),
                )
                if _pallas_state(mesh)[0]:
                    # the kernel's code operand, hoisted once per forest
                    # (codes are tree/level-independent)
                    codes8_forest = (_get_codes8_program(lay, mesh)(codes_j),)
            deferred: List[tuple] = []  # (k, weight, feats_d, masks_d, leaves_d)
            err_pairs: List[tuple] = []  # device (train, valid) when deferred

            # the ALL-features mask never changes: transfer it once instead of per
            # tree (a host->device put per tree buys nothing)
            fot_all_features = None
            if fused and k_sub >= F:
                fot_all_features = jnp.asarray(np.ones(lay.T, dtype=bool))
                if replicate_fn is not None:
                    fot_all_features = replicate_fn(fot_all_features)

            # ---- per-tree RNG draws: each tree's stream is keyed by (seed, tree
            # index) — resume at tree k replays identically. feat_ok stays
            # host-side (tiny, drives layout masks). A forest's bag (a Poisson
            # or Bernoulli count for every row, ~0.2 s of numpy a tree at
            # 5.5 M rows) is drawn a tree AHEAD: the first here, tree k + 1's
            # in the loop once tree k is dispatched, so the host draws while
            # the device grows (PERF.md section 6, PR 34: all drawn here they
            # idled the chip 2 s a 10-tree call). A bag crosses as uint16
            # (exact: counts nowhere near 65535; half the bytes of f32). ----
            is_rf = cfg.algorithm == "RF"

            def draw_feat_ok(rng_k):
                feat_ok = np.zeros(F, dtype=bool)
                if k_sub >= F:
                    feat_ok[:] = True
                else:
                    feat_ok[rng_k.choice(F, size=k_sub, replace=False)] = True
                return feat_ok

            def draw_bag(k):
                """Tree k's bag, on the device, and its column subset."""
                with span("train.trees.bag", call=call, k=k,
                          rows=n_orig) as bag_sp:
                    rng_k = np.random.default_rng([cfg.seed, k])
                    if cfg.bagging_with_replacement:
                        bag = rng_k.poisson(cfg.bagging_sample_rate,
                                            size=n_orig)
                    else:
                        bag = rng_k.random(n_orig) < cfg.bagging_sample_rate
                    bag = np.pad(bag.astype(np.uint16 if mesh is None
                                            else np.float32), (0, n - n_orig))
                    feat_ok = draw_feat_ok(rng_k)
                    bag_sp["bytes"] = int(bag.nbytes)
                    return row_put(bag), feat_ok

            feat_oks: Dict[int, np.ndarray] = {} if is_rf else {
                k: draw_feat_ok(np.random.default_rng([cfg.seed, k]))
                for k in range(start_k, cfg.tree_num)}
            ahead = (draw_bag(start_k)
                     if is_rf and start_k < cfg.tree_num else None)

        for k in range(start_k, cfg.tree_num):
            with span("train.tree", call=call, k=k):
                if is_rf:
                    bag_j, feat_ok = ahead
                    w_k = base_w_j * bag_j.astype(jnp.float32)
                    labels_k = y_j
                else:  # GBT: fit the negative loss gradient
                    feat_ok = feat_oks[k]
                    w_k = base_w_j
                    if log_loss:
                        labels_k = y_j - 1.0 / (1.0 + jnp.exp(-pred))
                    else:
                        labels_k = y_j - pred

                tree = None
                if leaf_wise:
                    tree, resting = build_tree_leafwise(
                        codes_j, labels_k, w_k, slots_np, is_cat_np, cfg, feat_ok
                    )
                    tree_pred = jnp.asarray(tree.leaf_value)[resting]
                elif fused:
                    if fot_all_features is not None:
                        fot = fot_all_features
                    else:
                        fot = jnp.asarray(np.asarray(feat_ok, bool)[lay.seg_of_t])
                        if replicate_fn is not None:
                            fot = replicate_fn(fot)
                    feats_d, masks_d, leaves_d, _resting, tree_pred = tree_prog(
                        codes_j, *codes8_forest, labels_k, w_k, fot)
                    _record_hist_counters(*sub_counts)
                    _record_kernel_calls(kernel_plan)
                    _record_route_counters(cfg.max_depth, lay.s_max)
                    if mesh is not None:
                        _record_psum_counters(cfg.max_depth, lay.T,
                                              sub_levels, cfg.n_classes)
                    deferred.append(
                        (k, 1.0 if (is_gbt and k == 0) else (lr if is_gbt else 1.0),
                         feats_d, masks_d, leaves_d))
                else:
                    tree, resting = build_tree(
                        codes_j, labels_k, w_k, slots_np, is_cat_np, cfg, feat_ok,
                        mesh=mesh,
                    )
                    tree_pred = jnp.asarray(tree.leaf_value)[resting]
                registry().counter("train.trees").inc()
                weight_k = 1.0 if (is_gbt and k == 0) else (lr if is_gbt else 1.0)
                if tree is not None:
                    tree.weight = weight_k
                    trees.append(tree)
                else:
                    trees.append(None)  # placeholder; assembled after the loop

                if is_cls:
                    import jax.nn as jnn

                    votes = votes + jnn.one_hot(
                        jnp.clip(tree_pred.astype(jnp.int32), 0, cfg.n_classes - 1),
                        cfg.n_classes, dtype=jnp.float32)
                    t_e, v_e = cls_errors_of(votes)
                elif is_gbt:
                    if cfg.dropout_rate > 0.0 and k > 0:
                        # DART-ish per-row dropout (dt/DTWorker.java:634-640): each
                        # row independently skips this tree's contribution to its
                        # RUNNING prediction (the gradient target), never the model;
                        # keyed per tree so checkpoint resume replays identically
                        keep = (np.random.default_rng([cfg.seed, k, 777])
                                .random(n_orig) >= cfg.dropout_rate)
                        keep = np.pad(keep.astype(np.float32), (0, n - n_orig),
                                      constant_values=1.0)
                        pred = pred + weight_k * tree_pred * row_put(keep)
                    else:
                        pred = pred + weight_k * tree_pred
                    score = (
                        1.0 / (1.0 + jnp.exp(-pred)) if log_loss
                        else jnp.clip(pred, 0.0, 1.0)
                    )
                    t_e, v_e = errors_of(score)
                else:
                    n_prev = k  # RF running mean over trees built so far
                    pred = tree_pred if k == 0 else (pred * n_prev + tree_pred) / (k + 1)
                    score = jnp.clip(pred, 0.0, 1.0)
                    t_e, v_e = errors_of(score)
                if is_rf and k + 1 < cfg.tree_num:
                    # this tree is dispatched: draw the next one's bag before
                    # anything below waits for the device
                    ahead = draw_bag(k + 1)
                if not need_sync:
                    err_pairs.append((t_e, v_e))
                    valid_errors.append(None)  # filled after the final sync
                    continue
                if deferred:  # sync consumers need real trees: drain the backlog
                    _assemble_deferred(trees, deferred, cfg, call=call, k=k)
                with span("train.tree.wait", call=call, k=k):
                    terr, verr = float(t_e), float(v_e)  # one sync per tree
                valid_errors.append(verr)
                if progress_cb:
                    # the caller's time, not the trainer's
                    with span("train.tree.progress_cb", call=call, k=k):
                        progress_cb(k + 1, terr, verr)
                if checkpoint_cb:
                    checkpoint_cb(k + 1, trees, valid_errors)
                if decider is not None and decider.add(verr):
                    log.info("windowed early stop after %d trees "
                             "(DTEarlyStopDecider)", k + 1)
                    break
                if cfg.early_stop_rounds and len(valid_errors) > 1:
                    if verr > min(valid_errors):
                        bad_rounds += 1
                        if bad_rounds >= cfg.early_stop_rounds:
                            log.info("early stop after %d trees", k + 1)
                            break
                    else:
                        bad_rounds = 0

        errs_d = (jnp.stack([jnp.stack(p) for p in err_pairs])
                  if err_pairs else None)
        k_last = len(trees) - 1
        if deferred:  # trees + errors ride ONE host round-trip
            errs_d = _assemble_deferred(trees, deferred, cfg, extra=errs_d,
                                        call=call, k=k_last)
        elif errs_d is not None:
            with span("train.tree.wait", call=call, k=k_last):
                errs_d = jax.device_get(errs_d)
        if err_pairs:  # deferred error sync
            host = np.asarray(errs_d)
            errs = [(float(t), float(v)) for t, v in host]
            terr, verr = errs[-1]
            j = 0
            for i in range(len(valid_errors)):
                if valid_errors[i] is None:
                    valid_errors[i] = errs[j][1]
                    j += 1

        spec = TreeModelSpec(
            algorithm=cfg.algorithm,
            trees=trees,
            input_columns=list(columns),
            slots=[int(s) for s in slots],
            boundaries=boundaries or [None] * F,
            categories=categories or [None] * F,
            loss=cfg.loss,
            learning_rate=lr,
            init_pred=0.0,
            convert_to_prob="SIGMOID" if cfg.loss == "log" else "RAW",
            train_error=terr,
            valid_error=valid_errors[-1] if valid_errors else None,
            n_classes=cfg.n_classes,
        )
        return TreeTrainResult(spec=spec, train_error=terr,
                               valid_error=valid_errors[-1] if valid_errors else 0.0)
