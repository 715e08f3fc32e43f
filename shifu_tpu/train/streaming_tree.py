"""Larger-than-memory GBT/RF: stream the bin-code shards per tree level.

The per-row STATE of tree building is tiny (node position, activity,
resting node, GBT prediction — ~13 bytes/row), so it stays on device for
every shard; only the [n, F] CODE matrix is too big, and it streams from
the mmap'd CleanedData shards once per level:

    per level:  for each shard s:
                    device_put(codes_s)                (async transfer)
                    row_update_s for the PREVIOUS level's decisions
                    hist += hist_program(codes_s, state_s)
                split scan on the merged histogram     (tiny)

The merged-histogram-then-split structure is exactly DTWorker partial
stats -> DTMaster merge (dt/DTMaster.java:297-310) with disk shards
standing in for workers. The same RNG streams as the in-memory trainer
drive sampling.

EQUALITY CONTRACT vs the in-memory trainer (tests/test_streaming_train.py
pins each clause):
  * histogram COUNT planes are sums of integers in f32 — EXACT under any
    summation order while total weighted counts stay < 2^24. Hence:
      - multi-class RF (count-only histograms, integer bag weights):
        forests are BIT-EQUAL;
      - split structure (feature + categorical mask per node): equal in
        practice, because count-based validity is exact and gain values
        rarely tie; a regression-label gain tie across shard orders may
        legitimately pick a different equal-gain split.
  * label sum/sqsum planes and leaf values: equal up to float-summation
    order (per-shard partials associate differently than one whole-array
    pass) — compared with tolerance, never bit-asserted.
"""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

from shifu_tpu.models.tree import DenseTree, TreeModelSpec
from shifu_tpu.norm.dataset import read_meta
from shifu_tpu.train.tree_trainer import (
    DTEarlyStopDecider,
    _low_precision,
    TreeTrainConfig,
    TreeTrainResult,
    _device_layout,
    _get_derive_program,
    _get_hist_program,
    _get_update_program,
    _node_batch_size,
    _record_hist_counters,
    _record_route_counters,
    _scan_batched,
    _sub_acc64,
    _sub_plan,
    make_layout,
    subset_count,
)
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)


class CodesFeed:
    """Shard loader over CleanedData codes-*.npy (mmap'd; one shard of
    codes resident at a time)."""

    def __init__(self, data_dir: str):
        self.data_dir = data_dir
        self.meta = read_meta(data_dir)
        self.n_shards = len(self.meta.shard_rows)
        self.n_rows = self.meta.n_rows

    def codes(self, s: int) -> np.ndarray:
        return np.load(
            os.path.join(self.data_dir, f"codes-{s:05d}.npy"), mmap_mode="r"
        )

    def tags(self, s: int) -> np.ndarray:
        return np.load(
            os.path.join(self.data_dir, f"tags-{s:05d}.npy"), mmap_mode="r"
        )

    def weights(self, s: int) -> np.ndarray:
        return np.load(
            os.path.join(self.data_dir, f"weights-{s:05d}.npy"),
            mmap_mode="r",
        )


def _iter_codes(feed: CodesFeed, work):
    """work-aligned shard code matrices with the disk read on the prefetch
    thread (data/pipeline.py): shard s+1 loads while shard s's histograms
    dispatch. Host RAM holds at most prefetchChunks+2 code matrices; the
    device still holds exactly one."""
    from shifu_tpu.data.pipeline import prefetch_iter

    return zip(work, prefetch_iter(
        range(len(work)),
        transform=lambda s: np.asarray(feed.codes(s), np.int32)))


def _grow_levelwise_streamed(feed, work, la, lay, cfg, D, row_put,
                             pad_to_mesh, mesh):
    """One LEVEL-WISE tree with streamed histograms. pending = the previous
    level's split decisions; each shard applies them the next time its
    codes are resident, so exactly ONE shard's code matrix lives on device
    at any moment and every level costs one transfer per shard. Node
    batches honor the stats-memory budget exactly like the in-memory
    per-level path (DTMaster.java:450-467). Mutates work[s]["resting"]."""
    import jax
    import jax.numpy as jnp

    feat_levels, mask_levels, leaf_levels = [], [], []
    batch_cap = _node_batch_size(lay.T, cfg.max_stats_memory_mb,
                                 cfg.n_classes)
    sub_levels, acc64 = _sub_plan(cfg, batch_cap)
    acc_dt = jnp.float64 if acc64 else jnp.float32
    derive = _get_derive_program()
    sub_on = cfg.hist_subtraction
    n_built = n_derived = n_fallback = 0
    pending = None
    prev = None  # retained parent level (hist_acc, is_split, left_small)
    for depth in range(D + 1):
        L = 2**depth
        base = L - 1
        use_sub = prev is not None  # sub_levels[depth] held at depth-1
        retain_next = depth < D and sub_on and sub_levels[depth + 1]
        if use_sub:
            # shards accumulate only the SMALLER child of each parent as
            # a half-width histogram; siblings derive after the merge
            Lh = L // 2
            p_hist, p_split, left_small = prev
            ranges = [(0, Lh)]
        else:
            ranges = [(b0, min(batch_cap, L - b0))
                      for b0 in range(0, L, batch_cap)]
        hist_parts = [None] * len(ranges)
        for wk, codes_host in _iter_codes(feed, work):
            codes_s = row_put(pad_to_mesh(codes_host))
            if pending is not None:
                # with use_sub the pass also says which of the shard's
                # rows went to the child this level builds
                pbf, psplit, pmask, pbase, p_small = pending
                wk["resting"], wk["node"], wk["active"], build_row = (
                    _get_update_program()(
                        codes_s, wk["node"], wk["active"], wk["resting"],
                        pbf, psplit, pmask, jnp.int32(pbase), la.clip,
                        p_small))
            for bi, (b0, Lb) in enumerate(ranges):
                # -Dshifu.pallas.mode routes this through the hist-mode
                # Pallas kernel (inside shard_map on a mesh): per-shard
                # code reads feed VMEM-resident planes, no [rows, T]
                # one-hot materializes between transfer and psum
                hist_p = _get_hist_program(Lb, lay,
                                           n_classes=cfg.n_classes,
                                           mesh=mesh,
                                           low_precision=_low_precision(
                                               cfg))
                if use_sub:
                    nd, in_batch = wk["node"] >> 1, build_row
                else:
                    nd = wk["node"] - b0
                    in_batch = (wk["active"] & (wk["node"] >= b0)
                                & (wk["node"] < b0 + Lb))
                h = hist_p(codes_s, wk["labels"], wk["w"],
                           nd, in_batch, la.off, la.clip,
                           la.seg_t, la.pos_t)
                hist_parts[bi] = (h if hist_parts[bi] is None
                                  else hist_parts[bi] + h)
            del codes_s  # drop before the next shard loads
        pending = None
        hist_acc = None
        if use_sub:
            hist_f32, hist_acc = derive(p_hist, hist_parts[0], p_split,
                                        left_small)
            scan_parts = [(hist_f32, L, 0)]
            n_built += Lh
            n_derived += Lh
        else:
            scan_parts = [(hist_parts[bi], Lb, b0)
                          for bi, (b0, Lb) in enumerate(ranges)]
            n_built += L
            if sub_on and depth >= 1:
                n_fallback += len(ranges)
        (bf, _br, _rank, lv, is_split, _g, lm, nc, lc) = _scan_batched(
            scan_parts, la, lay, cfg, L,
        )
        if depth == D:  # final level: leaves only + settle leftovers
            leaf_levels.append(lv)
            feat_levels.append(jnp.full(L, -1, jnp.int32))
            mask_levels.append(jnp.zeros((L, lay.s_max), bool))
            for wk in work:
                wk["resting"] = jnp.where(
                    wk["active"], base + wk["node"], wk["resting"])
            break
        prev = next_small = None
        if retain_next:
            if hist_acc is None:  # full-rebuild level kept whole (the
                # next level's gate bounds this one to a single batch)
                full = (hist_parts[0] if len(hist_parts) == 1
                        else jnp.concatenate(hist_parts, axis=1))
                hist_acc = full.astype(acc_dt) if acc64 else full
            next_small = lc <= nc - lc
            prev = (hist_acc, is_split, next_small)
        pending = (bf, is_split, lm, base, next_small)
        feat_levels.append(jnp.where(is_split, bf, -1))
        mask_levels.append(lm)
        leaf_levels.append(lv)
    _record_hist_counters(n_built, n_derived, n_fallback)
    _record_route_counters(D, lay.s_max)

    feature, left_mask, leaf_value = jax.device_get(
        (jnp.concatenate(feat_levels),
         jnp.concatenate(mask_levels, axis=0),
         jnp.concatenate(leaf_levels))
    )
    return DenseTree(
        feature=np.asarray(feature, np.int32),
        left_mask=np.asarray(left_mask, bool),
        leaf_value=np.asarray(leaf_value, np.float32),
        weight=1.0,
    )


def _grow_leafwise_streamed(feed, work, la, lay, cfg, row_put, pad_to_mesh,
                            mesh):
    """LEAF-WISE growth with streamed histograms (DTMaster.java:137
    toSplitQueue, :260-271): the split queue and the growing tree are tiny
    host state; each iteration re-streams the code shards once to (a)
    apply the previous split's row reroute and (b) accumulate the two new
    frontier leaves' histograms. Cost per split = one pass over the
    shards, at any data scale.

    Mutates each work[s]["node"] to the final explicit node id (the
    caller's resting state) and returns the DenseTree."""
    import jax.numpy as jnp

    from shifu_tpu.train.tree_trainer import _get_scan_program

    hist1 = _get_hist_program(1, lay, n_classes=cfg.n_classes, mesh=mesh,
                              low_precision=_low_precision(cfg))
    scan1 = _get_scan_program(1, lay.T, lay.s_max, cfg.impurity,
                              cfg.min_instances_per_node, cfg.min_info_gain,
                              cfg.n_classes)
    max_leaves = cfg.max_leaves
    max_nodes = 2 * max_leaves - 1
    feature = [-1]
    left_c = [-1]
    right_c = [-1]
    leaf_val = [0.0]
    masks = [np.zeros(lay.s_max, bool)]
    depth_of = {0: 0}
    candidates = {}
    pending = None  # (split node id, feat, cut, rank_row_dev, li, ri)
    # parent-reuse: candidate histograms are retained (budget-gated) so a
    # split's sweep accumulates ONE frontier histogram per shard (the
    # smaller child) instead of two and derives the sibling as
    # parent − built — the shard I/O pass count per split is unchanged
    sub_on = cfg.hist_subtraction
    acc64 = _sub_acc64()
    acc_dt = jnp.float64 if acc64 else jnp.float32
    batch_cap = _node_batch_size(lay.T, cfg.max_stats_memory_mb,
                                 cfg.n_classes)
    plane_cost = 2 if acc64 else 1
    stored = {}  # leaf id -> [C, 1, T] hist in acc dtype
    n_built = n_derived = n_fallback = 0

    def sweep(leaf_ids):
        """One pass over the shards: apply the pending reroute, then
        accumulate each listed leaf's histogram across shards."""
        nonlocal pending
        hists = {lid: None for lid in leaf_ids}
        for wk, codes_host in _iter_codes(feed, work):
            codes_s = row_put(pad_to_mesh(codes_host))
            if pending is not None:
                best_id, bf, cut, rank_row, li, ri = pending
                sel = wk["node"] == best_id
                code = codes_s[:, bf]
                cf = jnp.clip(code, 0, int(lay.clip_max[bf]))
                goes_left = rank_row[int(lay.off[bf]) + cf] <= cut
                wk["node"] = jnp.where(
                    sel, jnp.where(goes_left, li, ri), wk["node"])
            for lid in leaf_ids:
                act = (wk["node"] == lid) & wk["active"]
                h = hist1(codes_s, wk["labels"], wk["w"],
                          jnp.zeros_like(wk["node"]), act, la.off, la.clip,
                          la.seg_t, la.pos_t)
                hists[lid] = h if hists[lid] is None else hists[lid] + h
            del codes_s
        pending = None
        return hists

    def evaluate(hists):
        for lid, hist in hists.items():
            (f, c, r, lv, sp, g, m, nc, lc) = scan1(
                (hist.astype(jnp.float32)
                 if hist.dtype != jnp.float32 else hist),
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

    evaluate(sweep([0]))
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
        pending = (best_id, bf, cut, rank_row, li, ri)
        n_leaves += 1
        if parent_hist is not None:
            # the sweep (which also applies the reroute above) builds only
            # the smaller child; the sibling derives from the parent free
            smaller, larger = ((li, ri) if lcnt <= ncnt - lcnt
                               else (ri, li))
            built = sweep([smaller])[smaller]
            derived = parent_hist - built.astype(parent_hist.dtype)
            evaluate({smaller: built, larger: derived})
            n_built += 1
            n_derived += 1
        else:
            evaluate(sweep([li, ri]))  # also applies the reroute above
            n_built += 2
            if sub_on:
                n_fallback += 1
    _record_hist_counters(n_built, n_derived, n_fallback)

    return DenseTree(
        feature=np.asarray(feature, np.int32),
        left_mask=np.stack(masks).astype(bool),
        leaf_value=np.asarray(leaf_val, np.float32),
        weight=1.0,
        left=np.asarray(left_c, np.int32),
        right=np.asarray(right_c, np.int32),
    )


def train_trees_streamed(
    codes_dir: str,
    slots: List[int],
    is_cat: List[bool],
    columns: List[str],
    cfg: TreeTrainConfig,
    tags_override: Optional[np.ndarray] = None,
    boundaries: Optional[List] = None,
    categories: Optional[List] = None,
    progress_cb=None,
    mesh=None,
) -> TreeTrainResult:
    """Level-wise GBT/RF streamed from shards. `tags_override` supplies
    per-class binary targets for ONEVSALL members.

    With a `mesh`, each shard's rows are sharded over the `data` axis and
    the per-level histogram is psum'd across devices (shard_map inside
    `_get_hist_program`) — disk streaming composes with the device mesh
    exactly like the reference's per-worker spill
    (AbstractNNWorker.java:485-494)."""
    import jax
    import jax.numpy as jnp

    is_cls = cfg.n_classes >= 3
    if is_cls and cfg.algorithm == "GBT":
        raise ValueError("NATIVE multi-class tree training is RF-only")
    feed = CodesFeed(codes_dir)
    F = len(slots)
    lay = make_layout([int(s) for s in slots], [bool(c) for c in is_cat])
    la = _device_layout(lay, np.ones(F, bool))
    D = cfg.max_depth
    is_gbt = cfg.algorithm == "GBT"
    log_loss = cfg.loss == "log"
    lr = cfg.learning_rate

    if mesh is not None:
        from shifu_tpu.parallel.mesh import round_up_rows, shard_rows

        def row_put(a):
            return shard_rows(a, mesh)

        def pad_to_mesh(a):
            rows = a.shape[0]
            target = round_up_rows(rows, mesh)
            if target == rows:
                return a
            return np.pad(a, [(0, target - rows)] + [(0, 0)] * (a.ndim - 1))
    else:
        row_put = jnp.asarray

        def pad_to_mesh(a):
            return a

    # per-shard device state (small): labels/weights/valid stay resident
    rng_valid = np.random.default_rng([cfg.seed, 999_983])
    shard_state = []
    offset = 0
    for s in range(feed.n_shards):
        rows = feed.meta.shard_rows[s]
        # one GLOBAL valid draw keeps the split identical to the in-memory
        # trainer (same seed stream over the concatenated row order)
        valid = rng_valid.random(rows) < cfg.valid_set_rate
        y = np.asarray(feed.tags(s), np.float32)
        if tags_override is not None:
            y = tags_override[offset:offset + rows].astype(np.float32)
        w = np.where(valid, 0.0, np.asarray(feed.weights(s), np.float32))
        real = np.ones(rows, bool)
        prows = pad_to_mesh(real).shape[0]
        shard_state.append({
            "rows": rows,
            "y": row_put(pad_to_mesh(y)),
            "base_w": row_put(pad_to_mesh(w.astype(np.float32))),
            "valid": row_put(pad_to_mesh(valid)),
            "real": row_put(pad_to_mesh(real)),
            "pred": row_put(np.zeros(prows, np.float32)),
            "votes": (row_put(np.zeros((prows, cfg.n_classes), np.float32))
                      if is_cls else None),
        })
        offset += rows

    from shifu_tpu.obs import profile

    @jax.jit
    def _shard_errors(score, y, valid, real):
        sq = (y - score) ** 2
        v = jnp.sum(jnp.where(valid & real, sq, 0.0))
        t = jnp.sum(jnp.where((~valid) & real, sq, 0.0))
        return t, v, jnp.sum((valid & real).astype(jnp.float32))

    @jax.jit
    def _shard_cls_errors(votes, y, valid, real):
        pred_class = jnp.argmax(votes, axis=1).astype(jnp.float32)
        err = (pred_class != y).astype(jnp.float32)
        v = jnp.sum(jnp.where(valid & real, err, 0.0))
        t = jnp.sum(jnp.where((~valid) & real, err, 0.0))
        return t, v, jnp.sum((valid & real).astype(jnp.float32))

    shard_errors = profile.wrap("tree.shard_errors", _shard_errors)
    shard_cls_errors = profile.wrap("tree.shard_cls_errors",
                                    _shard_cls_errors)

    trees: List[DenseTree] = []
    valid_errors: List[float] = []
    bad_rounds = 0
    decider = (DTEarlyStopDecider(cfg.max_depth)
               if cfg.enable_early_stop else None)
    terr = verr = 0.0
    n_total = feed.n_rows

    for k in range(cfg.tree_num):
        rng_k = np.random.default_rng([cfg.seed, k])
        if cfg.algorithm == "RF":
            if cfg.bagging_with_replacement:
                bag_all = rng_k.poisson(cfg.bagging_sample_rate,
                                        size=n_total)
            else:
                bag_all = (rng_k.random(n_total)
                           < cfg.bagging_sample_rate)
        k_sub = subset_count(cfg.feature_subset_strategy, F)
        feat_ok = np.zeros(F, dtype=bool)
        if k_sub >= F:
            feat_ok[:] = True
        else:
            feat_ok[rng_k.choice(F, size=k_sub, replace=False)] = True
        fot = np.asarray(feat_ok, bool)[lay.seg_of_t]
        la.feat_ok_t = jnp.asarray(fot)

        # per-shard per-tree working arrays
        work = []
        offset = 0
        for s, st in enumerate(shard_state):
            rows = st["rows"]
            prows = int(st["y"].shape[0])
            if cfg.algorithm == "RF":
                w_k = st["base_w"] * row_put(pad_to_mesh(
                    bag_all[offset:offset + rows].astype(np.float32)))
                labels = st["y"]
            else:
                w_k = st["base_w"]
                if log_loss:
                    labels = st["y"] - 1.0 / (1.0 + jnp.exp(-st["pred"]))
                else:
                    labels = st["y"] - st["pred"]
            work.append({
                "labels": labels, "w": w_k,
                "node": row_put(np.zeros(prows, np.int32)),
                "active": st["real"],
                "resting": row_put(np.zeros(prows, np.int32)),
            })
            offset += rows

        weight_k = 1.0 if (is_gbt and k == 0) else (lr if is_gbt else 1.0)
        if cfg.max_leaves and cfg.max_leaves > 0:
            tree = _grow_leafwise_streamed(feed, work, la, lay, cfg,
                                           row_put, pad_to_mesh, mesh)
            tree.weight = weight_k
            for wk in work:
                wk["resting"] = wk["node"]  # explicit leaf node ids
        else:
            tree = _grow_levelwise_streamed(
                feed, work, la, lay, cfg, D, row_put, pad_to_mesh, mesh)
            tree.weight = weight_k
        trees.append(tree)

        # per-shard prediction/error updates (incl. DART per-row dropout,
        # same keyed stream as the in-memory trainer)
        drop_all = None
        if is_gbt and cfg.dropout_rate > 0.0 and k > 0:
            drop_all = (np.random.default_rng([cfg.seed, k, 777])
                        .random(n_total) >= cfg.dropout_rate)
        t_sum = v_sum = v_cnt = 0.0
        t_cnt = 0.0
        leaf_j = jnp.asarray(tree.leaf_value)
        drop_off = 0
        for wk, st in zip(work, shard_state):
            tree_pred = leaf_j[wk["resting"]]
            if is_cls:
                import jax.nn as jnn

                st["votes"] = st["votes"] + jnn.one_hot(
                    jnp.clip(tree_pred.astype(jnp.int32), 0,
                             cfg.n_classes - 1),
                    cfg.n_classes, dtype=jnp.float32)
                ts, vs, vc = shard_cls_errors(st["votes"], st["y"],
                                              st["valid"], st["real"])
                t_sum += float(ts)
                v_sum += float(vs)
                v_cnt += float(vc)
                t_cnt += st["rows"] - float(vc)
                continue
            if is_gbt:
                if drop_all is not None:
                    keep = row_put(pad_to_mesh(
                        drop_all[drop_off:drop_off + st["rows"]]
                        .astype(np.float32)))
                    tree_pred = tree_pred * keep
                drop_off += st["rows"]
                st["pred"] = st["pred"] + tree.weight * tree_pred
                score = (1.0 / (1.0 + jnp.exp(-st["pred"])) if log_loss
                         else jnp.clip(st["pred"], 0.0, 1.0))
            else:
                st["pred"] = (tree_pred if k == 0
                              else (st["pred"] * k + tree_pred) / (k + 1))
                score = jnp.clip(st["pred"], 0.0, 1.0)
            ts, vs, vc = shard_errors(score, st["y"], st["valid"],
                                      st["real"])
            t_sum += float(ts)
            v_sum += float(vs)
            v_cnt += float(vc)
            t_cnt += st["rows"] - float(vc)
        terr = t_sum / max(t_cnt, 1.0)
        verr = v_sum / max(v_cnt, 1.0)
        valid_errors.append(verr)
        if progress_cb:
            progress_cb(k + 1, terr, verr)
        if decider is not None and decider.add(verr):
            log.info("streamed windowed early stop after %d trees", k + 1)
            break
        if cfg.early_stop_rounds and len(valid_errors) > 1:
            if verr > min(valid_errors):
                bad_rounds += 1
                if bad_rounds >= cfg.early_stop_rounds:
                    log.info("streamed early stop after %d trees", k + 1)
                    break
            else:
                bad_rounds = 0

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
