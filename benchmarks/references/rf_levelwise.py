"""Plain reference: a level-wise random forest of regression trees on binned
codes, as Shifu defines it. Straightforward jax.numpy and numpy, float32 sums
with matmuls at `highest`, gains in float64; no kernels, no subtraction,
nothing of shifu_tpu.

Semantics, as the trainer's users see them:
- rows are split once: `valid` where `default_rng([seed, 999983]).random(n) <
  rate`; a valid row has weight 0 in every histogram and counts only in the
  validation error;
- tree k has a stream of its own, `default_rng([seed, k])`: first its bag,
  `poisson(rate, n)` (sampling with replacement: a row counts as often as it
  was drawn), then its columns, `choice(F, k_sub, replace=False)` (no draw
  where k_sub >= F). It sees the rows with weight w x bag and may split on
  its own columns alone;
- every tree fits the labels themselves; a tree is a complete binary layout,
  node i with children 2i+1 and 2i+2, grown level by level to `depth`; every
  node's value is the weighted mean of the labels of its training rows;
- a node splits on the (feature, cut) of the largest variance gain
  sse(node) - sse(left) - sse(right), sse = sum(w y^2) - sum(w y)^2 / sum(w),
  left = bins <= cut; a split needs `min_instances` weighted rows on each
  side and a gain above 0; the last bin of a feature is no cut;
- the forest's prediction is the running mean of its trees' predictions; the
  error after a tree is the mean of (y - clip(mean, 0, 1))^2 over the
  training rows and over the validation rows, each row once.

A row is in one node a level, so a level's histograms are one matmul of the
rows' node one-hot (times the three planes) with their code one-hot, rows in
blocks: 2 x 3 x 2^d x n x F x S operations at level d, 1,023 node rows a
depth-10 tree and not 2,047 x 11.

Two uses. `evaluate` follows a forest that something else grew. Trees of a
forest are independent given their bags, so the histograms (the costly part:
`regret`, the share of each level's best gain over the tree's own columns
that the chosen splits miss, and `value_gap`, each node's value against the
reference's mean) are built for the trees named in `follow`, and every tree
is traversed for the errors after it. `grow` grows a forest itself; with
`keep_bits` it is the control of `correct`: the program's planes are float32
and, with unit weights, whole numbers, so rounding them catches nothing; the
control rounds every node histogram and every cumulative sum to `keep_bits`
bits of mantissa before the gains and the means (`lax.reduce_precision`'s
rule, float32's exponent range kept), one step below what the chip's
in-kernel scan keeps (benchmarks/configs/higgs_rf.json, `precision`).
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 32768


def split_valid(n: int, seed: int, rate: float) -> np.ndarray:
    return np.random.default_rng([seed, 999_983]).random(n) < rate


def draw_tree(n: int, F: int, seed: int, k: int, rate: float, k_sub: int):
    """(bag [n] float32, allowed [F] bool) of tree k, in the trainer's
    order: the bag first, then the columns, from one stream."""
    rng = np.random.default_rng([seed, k])
    bag = rng.poisson(rate, size=n).astype(np.float32)
    allowed = np.zeros(F, bool)
    if k_sub >= F:
        allowed[:] = True
    else:
        allowed[rng.choice(F, size=k_sub, replace=False)] = True
    return bag, allowed


def round_bits(a: np.ndarray, keep_bits: int | None) -> np.ndarray:
    """float64 values rounded to `keep_bits` stored bits of mantissa (to
    nearest, ties to even, no range lost)."""
    if keep_bits is None:
        return a
    m, e = np.frexp(np.asarray(a, np.float64))
    scale = 2.0 ** (keep_bits + 1)
    return np.ldexp(np.round(m * scale) / scale, e)


def _block(n: int) -> int:
    nb = -(-n // BLOCK_ROWS)
    for cand in range(nb, 8 * nb + 1):
        if n % cand == 0:
            return n // cand
    return n


class Reference:
    """The compiled pieces for one shape: n rows, F features of S slots each,
    trees of `depth` levels below the root."""

    def __init__(self, n: int, F: int, S: int, depth: int):
        import jax
        import jax.numpy as jnp

        self.n, self.F, self.S, self.D = n, F, S, depth
        self.N = 2 ** (depth + 1) - 1
        D = depth
        blk = _block(n)
        nb = n // blk

        def step(codes, node, alive, feature, left_flat):
            """One level down: (node, alive) of every row after the split of
            the node it is in; a row whose node does not split rests."""
            f = feature[node]
            alive = alive & (f >= 0)
            code = jnp.take_along_axis(
                codes, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
            left = left_flat[node * S + jnp.clip(code, 0, S - 1)]
            child = jnp.where(left, 2 * node + 1, 2 * node + 2)
            return jnp.where(alive, child, node), alive

        def traverse(codes, feature, left_mask):
            """path [n, D+1]: the node a row is in at each depth, -1 below the
            node it rests in."""
            left_flat = left_mask.reshape(-1)
            node = jnp.zeros(n, jnp.int32)
            alive = jnp.ones(n, bool)
            cols = [node]
            for _ in range(D):
                node, alive = step(codes, node, alive, feature, left_flat)
                cols.append(jnp.where(alive, node, -1))
            return jnp.stack(cols, axis=1)

        def level_hist(codes, local, planes, L):
            """H [L, 3, F, S]: sum of each plane over the rows of each of a
            level's L nodes (`local` in [0, L), -1 for a row not in the
            level), by feature and bin."""

            def body(b, acc):
                c = jax.lax.dynamic_slice_in_dim(codes, b * blk, blk, 0)
                p = jax.lax.dynamic_slice_in_dim(local, b * blk, blk, 0)
                v = jax.lax.dynamic_slice_in_dim(planes, b * blk, blk, 0)
                a = jax.nn.one_hot(p, L, dtype=jnp.float32)
                a3 = (a[:, :, None] * v[:, None, :]).reshape(blk, L * 3)
                oh = jax.nn.one_hot(c, S, dtype=jnp.float32).reshape(
                    blk, F * S)
                # both operands written out before the matmul: fused into
                # it, the two one-hots cost the v5e's compiler 13 s a level
                # where this costs 2 (described-chip compile, PR 34)
                a3, oh = jax.lax.optimization_barrier((a3, oh))
                return acc + jnp.matmul(a3.T, oh, precision="highest")

            acc = jax.lax.fori_loop(0, nb, body,
                                    jnp.zeros((L * 3, F * S), jnp.float32))
            return acc.reshape(L, 3, F, S)

        def level_totals(local, planes, L):
            """[L, 3]: the planes' sums over each node's rows, no bins (all
            the deepest level needs)."""

            def body(b, acc):
                p = jax.lax.dynamic_slice_in_dim(local, b * blk, blk, 0)
                v = jax.lax.dynamic_slice_in_dim(planes, b * blk, blk, 0)
                a = jax.nn.one_hot(p, L, dtype=jnp.float32)
                return acc + jnp.matmul(a.T, v, precision="highest")

            return jax.lax.fori_loop(0, nb, body,
                                     jnp.zeros((L, 3), jnp.float32))

        def planes_of(y, w):
            return jnp.stack([w, w * y, w * y * y], axis=1)

        def errors(y, pred, valid):
            sq = (y - jnp.clip(pred, 0.0, 1.0)) ** 2
            t = jnp.sum(jnp.where(valid, 0.0, sq)) / jnp.maximum(
                jnp.sum(~valid), 1)
            v = jnp.sum(jnp.where(valid, sq, 0.0)) / jnp.maximum(
                jnp.sum(valid), 1)
            return t, v

        self.step = jax.jit(step)
        self.traverse = jax.jit(traverse)
        self.level_hist = jax.jit(level_hist, static_argnums=3)
        self.level_totals = jax.jit(level_totals, static_argnums=2)
        self.planes_of = jax.jit(planes_of)
        self.errors = jax.jit(errors)
        self.rest_node = jax.jit(lambda path: jnp.max(path, axis=1))

    def level(self, d: int) -> slice:
        """Level d's nodes in the flat layout."""
        return slice(2 ** d - 1, 2 ** (d + 1) - 1)

    # ---- host arithmetic on a level's small histogram, float64 ----

    def gains(self, H: np.ndarray, min_instances: float,
              allowed: np.ndarray, keep_bits: int | None = None):
        """(gain [L, F, S] with -inf where no split may be made, count [L],
        mean [L]) from H [L, 3, F, S]; columns outside `allowed` split
        nothing. `keep_bits` is the control's rounding."""
        H = round_bits(np.asarray(H, np.float64), keep_bits)
        cum = round_bits(np.cumsum(H, axis=-1), keep_bits)
        tot = cum[..., -1:]
        lc, ls, lq = cum[:, 0], cum[:, 1], cum[:, 2]
        tc, ts, tq = tot[:, 0], tot[:, 1], tot[:, 2]
        rc, rs, rq = tc - lc, ts - ls, tq - lq

        def sse(c, s, q):
            return q - s * s / np.maximum(c, 1e-12)

        gain = sse(tc, ts, tq) - sse(lc, ls, lq) - sse(rc, rs, rq)
        ok = (lc >= min_instances) & (rc >= min_instances) & (gain > 0.0)
        ok[..., -1] = False
        ok &= np.asarray(allowed, bool)[None, :, None]
        gain = np.where(ok, gain, -np.inf)
        count, total = tot[:, 0, 0, 0], tot[:, 1, 0, 0]
        return gain, count, total / np.maximum(count, 1e-12)

    def _tree_planes(self, y, wt, seed, k, rate, k_sub, fault=None):
        import jax.numpy as jnp

        bag, allowed = draw_tree(self.n, self.F, seed, k, rate, k_sub)
        if fault == "bag":  # every tree on every row
            bag[:] = 1.0
        if fault == "subset":  # every tree on every column
            allowed[:] = True
        return self.planes_of(y, wt * jnp.asarray(bag)), allowed

    # ---- following a forest that something else grew ----

    def evaluate(self, codes, y, w, valid, forest: list, seed: int,
                 rate: float, k_sub: int, min_instances: float,
                 follow: list | None = None) -> dict:
        """forest: [(feature [N] int, left_mask [N, S] bool, value [N] f32)]
        in the order grown. Returns `errors`, the (train, valid) error after
        each tree, and for each tree of `follow` (all by default) `regret`
        (the worst level's share of the best gain over the tree's own columns
        that the chosen splits miss; a split on a column outside them is
        granted nothing) and `value_gap` (the worst node's value against the
        reference's mean, measured against that mean or the median node's,
        whichever is larger)."""
        import jax.numpy as jnp

        wt = jnp.where(valid, 0.0, w)
        follow = list(range(len(forest))) if follow is None else follow
        pred = jnp.zeros(self.n, jnp.float32)
        out = {"regret": [], "value_gap": [], "errors": [],
               "followed": list(follow)}
        for k, (feature, left_mask, value) in enumerate(forest):
            feature = np.asarray(feature, np.int32)
            left_mask = np.asarray(left_mask, bool)
            value = np.asarray(value, np.float32)
            path = self.traverse(codes, jnp.asarray(feature),
                                 jnp.asarray(left_mask))
            if k in follow:
                planes, allowed = self._tree_planes(y, wt, seed, k, rate,
                                                    k_sub)
                regret, gap = self._follow_tree(
                    codes, path, planes, allowed, feature, left_mask, value,
                    min_instances)
                out["regret"].append(regret)
                out["value_gap"].append(gap)
            tree_pred = jnp.asarray(value)[self.rest_node(path)]
            pred = tree_pred if k == 0 else (pred * k + tree_pred) / (k + 1)
            t, v = self.errors(y, pred, valid)
            out["errors"].append((float(t), float(v)))
        return out

    def _follow_tree(self, codes, path, planes, allowed, feature, left_mask,
                     value, min_instances):
        D = self.D
        count = np.zeros(self.N)
        mean = np.zeros(self.N)
        worst = 0.0
        for d in range(D + 1):
            lvl, L = self.level(d), 2 ** d
            local = path[:, d] - (L - 1)  # -1 - (L - 1) < 0: in no node
            if d == D:
                tot = np.asarray(self.level_totals(local, planes, L),
                                 np.float64)
                count[lvl] = tot[:, 0]
                mean[lvl] = tot[:, 1] / np.maximum(tot[:, 0], 1e-12)
                break
            H = np.asarray(self.level_hist(codes, local, planes, L))
            gain, count[lvl], mean[lvl] = self.gains(H, min_instances,
                                                     allowed)
            best = np.maximum(gain.reshape(L, -1).max(axis=1), 0.0)
            chosen = np.zeros(L)
            for j in np.nonzero(feature[lvl] >= 0)[0]:
                i = lvl.start + j
                m = left_mask[i, :self.S]
                cut = int(m.sum()) - 1
                if cut < 0 or not m[:cut + 1].all():
                    continue  # not a cut of ordered bins: no gain granted
                g = gain[j, feature[i], cut]
                chosen[j] = g if np.isfinite(g) else 0.0
            sel = count[lvl] > 0
            b, c = best[sel].sum(), chosen[sel].sum()
            if b > 0:
                worst = max(worst, (b - c) / b)
        reached = count > 0
        scale = np.maximum(np.abs(mean), np.median(np.abs(mean[reached])))
        gap = np.max((np.abs(value - mean) / np.maximum(scale, 1e-30))[
            reached])
        return float(worst), float(gap)

    # ---- growing a forest ----

    def grow(self, codes, y, w, valid, trees: int, seed: int, rate: float,
             k_sub: int, min_instances: float, keep_bits: int | None = None,
             fault: str | None = None):
        """(forest, errors) in `evaluate`'s form. `keep_bits` makes it the
        control. `fault` plants one, for reading what it does to the numbers
        compared: "bag" grows every tree on every row, "subset" on every
        column, "half" leaves every second row out of every sum, "sum" adds
        the trees' predictions up where their mean is wanted."""
        import jax.numpy as jnp

        D, S = self.D, self.S
        wt = jnp.where(valid, 0.0, w)
        if fault == "half":
            wt = wt * (jnp.arange(self.n) % 2 == 0)
        pred = jnp.zeros(self.n, jnp.float32)
        cuts = np.arange(S)
        forest, errs = [], []
        for k in range(trees):
            feature = np.full(self.N, -1, np.int32)
            left_mask = np.zeros((self.N, S), bool)
            value = np.zeros(self.N, np.float32)
            planes, allowed = self._tree_planes(y, wt, seed, k, rate, k_sub,
                                                fault)
            node = jnp.zeros(self.n, jnp.int32)
            alive = jnp.ones(self.n, bool)
            for d in range(D + 1):
                lvl, L = self.level(d), 2 ** d
                local = jnp.where(alive, node - (L - 1), -1)
                if d == D:
                    tot = round_bits(np.asarray(self.level_totals(
                        local, planes, L), np.float64), keep_bits)
                    reached = tot[:, 0] > 0
                    value[lvl] = np.where(
                        reached, tot[:, 1] / np.maximum(tot[:, 0], 1e-12), 0)
                    break
                H = np.asarray(self.level_hist(codes, local, planes, L))
                gain, count, mean = self.gains(H, min_instances, allowed,
                                               keep_bits)
                for j in np.nonzero(count > 0)[0]:
                    i = lvl.start + j
                    value[i] = mean[j]
                    f, cut = divmod(int(np.argmax(gain[j])), S)
                    if np.isfinite(gain[j, f, cut]):
                        feature[i] = f
                        left_mask[i] = cuts <= cut
                node, alive = self.step(
                    codes, node, alive, jnp.asarray(feature),
                    jnp.asarray(left_mask.reshape(-1)))
            tree_pred = jnp.asarray(value)[node]
            if fault == "sum":
                pred = pred + tree_pred
            else:
                pred = tree_pred if k == 0 else (pred * k + tree_pred) / (
                    k + 1)
            t, v = self.errors(y, pred, valid)
            forest.append((feature, left_mask, value))
            errs.append((float(t), float(v)))
        return forest, errs
