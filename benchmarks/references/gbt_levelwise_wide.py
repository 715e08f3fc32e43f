"""Plain reference: level-wise gradient-boosted regression trees on binned
codes, as Shifu defines them, for layouts of thousands of one-hot columns.
Straightforward jax.numpy and numpy, float32 sums with matmuls at `highest`,
gains in float64; no kernels, no subtraction, nothing of shifu_tpu.

The semantics are `gbt_levelwise`'s, whose docstring is the specification:
the validity draw, the residuals, the complete binary layout, the variance
gain, `min_instances`, the last bin no cut, the errors. What differs is how
a histogram is built. `gbt_levelwise.path_hist` multiplies every node row of
the whole tree, 3 x (2^(D+1) - 1), with every column for every tree: at 28 x
256 slots and depth 8 that is `[1533, rows] x [rows, 7168]`, 2.4e14 operations
a tree at 11,000,000 rows. A row is in one node a level, so here a level's
histograms are one matmul of the rows' node one-hot (times the three planes)
with their code one-hot, rows in blocks, as `rf_levelwise.level_hist` builds
them for the forest: at most 128 node rows x 3 planes against the columns,
255 node rows a depth-8 tree, and the deepest level needs its totals alone.

Two uses. `evaluate` follows a forest that something else grew, tree by tree
with that forest's own predictions: every tree is traversed for the errors
after it (a boosted tree's residuals need every tree before it, and a
traversal is cheap), and the histograms (the costly part: `regret`, the
share of each level's best gain that the chosen splits miss, and
`value_gap`, each node's value against the reference's mean) are built for
the trees named in `follow`: the first, the middle and the last by default.
`grow` grows a forest itself; with `lowp=True` it is the control of
`correct`: the program's component planes are bfloat16 (8 bits of mantissa),
so the control rounds w, w r and w r^2 to e4m3's 3 stored bits
(`lax.reduce_precision`, float32's exponent range kept) before summing.
"""

from __future__ import annotations

import numpy as np

# a block's code one-hot is [rows, F x S] float32: 8,000 rows x 7,168 columns
# are 229 MB beside the cell's 3 GB of live data
BLOCK_ROWS = 8192


def split_valid(n: int, seed: int, rate: float) -> np.ndarray:
    return np.random.default_rng([seed, 999_983]).random(n) < rate


def _block(n: int) -> int:
    nb = -(-n // BLOCK_ROWS)
    for cand in range(nb, 8 * nb + 1):
        if n % cand == 0:
            return n // cand
    return n


def default_follow(trees: int) -> list:
    """The trees whose histograms `evaluate` builds where nobody says: the
    first, the middle (the sixth of ten) and the last."""
    return sorted({0, trees // 2, trees - 1})


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
            level's L nodes (`local` in [0, L), negative for a row not in the
            level), by feature and bin."""

            def body(b, acc):
                c = jax.lax.dynamic_slice_in_dim(codes, b * blk, blk, 0)
                p = jax.lax.dynamic_slice_in_dim(local, b * blk, blk, 0)
                v = jax.lax.dynamic_slice_in_dim(planes, b * blk, blk, 0)
                a = jax.nn.one_hot(p, L, dtype=jnp.float32)
                a3 = (a[:, :, None] * v[:, None, :]).reshape(blk, L * 3)
                oh = jax.nn.one_hot(c, S, dtype=jnp.float32).reshape(
                    blk, F * S)
                # both operands written out before the matmul, as
                # rf_levelwise does: fused into it, the two one-hots cost the
                # v5e's compiler many times as long a level
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

        def planes_of(y, pred, w, lowp):
            r = y - pred
            v = jnp.stack([w, w * r, w * r * r], axis=1)
            return jax.lax.reduce_precision(v, 8, 3) if lowp else v

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
        self.planes_of = jax.jit(planes_of, static_argnums=3)
        self.errors = jax.jit(errors)
        self.rest_node = jax.jit(lambda path: jnp.max(path, axis=1))

    def level(self, d: int) -> slice:
        """Level d's nodes in the flat layout."""
        return slice(2 ** d - 1, 2 ** (d + 1) - 1)

    # ---- host arithmetic on a level's small histogram, float64 ----

    def gains(self, H: np.ndarray, min_instances: float):
        """(gain [L, F, S] with -inf where no split may be made, count [L],
        mean [L]) from H [L, 3, F, S]."""
        H = np.asarray(H, np.float64)
        cum = np.cumsum(H, axis=-1)
        tot = cum[..., -1:]
        lc, ls, lq = cum[:, 0], cum[:, 1], cum[:, 2]
        tc, ts, tq = tot[:, 0], tot[:, 1], tot[:, 2]
        rc, rs, rq = tc - lc, ts - ls, tq - lq

        def sse(c, s, q):
            return q - s * s / np.maximum(c, 1e-12)

        gain = sse(tc, ts, tq) - sse(lc, ls, lq) - sse(rc, rs, rq)
        ok = (lc >= min_instances) & (rc >= min_instances) & (gain > 0.0)
        ok[..., -1] = False
        gain = np.where(ok, gain, -np.inf)
        count = H[:, 0, 0, :].sum(axis=-1)
        mean = H[:, 1, 0, :].sum(axis=-1) / np.maximum(count, 1e-12)
        return gain, count, mean

    # ---- following a forest that something else grew ----

    def evaluate(self, codes, y, w, valid, forest: list, weights: list,
                 min_instances: float, follow: list | None = None) -> dict:
        """forest: [(feature [N] int, left_mask [N, S] bool, value [N] f32)],
        weights: each tree's weight in F. Returns `errors`, the (train,
        valid) error after each tree, and for each tree of `follow`
        (`default_follow` where none is given) `regret` (the worst level's
        share of the best gain that the chosen splits miss) and `value_gap`
        (the worst node's value against the reference's mean, measured
        against that mean or the median node's, whichever is larger)."""
        import jax.numpy as jnp

        wt = jnp.where(valid, 0.0, w)
        follow = default_follow(len(forest)) if follow is None else follow
        pred = jnp.zeros(self.n, jnp.float32)
        out = {"regret": [], "value_gap": [], "errors": [],
               "followed": list(follow)}
        for k, ((feature, left_mask, value), weight) in enumerate(
                zip(forest, weights)):
            feature = np.asarray(feature, np.int32)
            left_mask = np.asarray(left_mask, bool)
            value = np.asarray(value, np.float32)
            path = self.traverse(codes, jnp.asarray(feature),
                                 jnp.asarray(left_mask))
            if k in follow:
                regret, gap = self._follow_tree(
                    codes, path, self.planes_of(y, pred, wt, False), feature,
                    left_mask, value, min_instances)
                out["regret"].append(regret)
                out["value_gap"].append(gap)
            pred = pred + weight * jnp.asarray(value)[self.rest_node(path)]
            t, v = self.errors(y, pred, valid)
            out["errors"].append((float(t), float(v)))
        return out

    def _follow_tree(self, codes, path, planes, feature, left_mask, value,
                     min_instances):
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
            gain, count[lvl], mean[lvl] = self.gains(H, min_instances)
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

    def grow(self, codes, y, w, valid, trees: int, learning_rate: float,
             min_instances: float, lowp: bool = False,
             fault: str | None = None):
        """(forest, weights, errors) in `evaluate`'s form. `fault` plants one,
        for reading what it does to the numbers compared: "half" leaves every
        second row out of every sum, "stuck" never moves the running
        prediction."""
        import jax.numpy as jnp

        D, S = self.D, self.S
        wt = jnp.where(valid, 0.0, w)
        if fault == "half":
            wt = wt * (jnp.arange(self.n) % 2 == 0)
        pred = jnp.zeros(self.n, jnp.float32)
        cuts = np.arange(S)
        forest, weights, errs = [], [], []
        for k in range(trees):
            feature = np.full(self.N, -1, np.int32)
            left_mask = np.zeros((self.N, S), bool)
            value = np.zeros(self.N, np.float32)
            planes = self.planes_of(y, pred, wt, lowp)
            node = jnp.zeros(self.n, jnp.int32)
            alive = jnp.ones(self.n, bool)
            for d in range(D + 1):
                lvl, L = self.level(d), 2 ** d
                local = jnp.where(alive, node - (L - 1), -1)
                if d == D:
                    tot = np.asarray(self.level_totals(local, planes, L),
                                     np.float64)
                    value[lvl] = np.where(
                        tot[:, 0] > 0,
                        tot[:, 1] / np.maximum(tot[:, 0], 1e-12), 0)
                    break
                H = np.asarray(self.level_hist(codes, local, planes, L))
                gain, count, mean = self.gains(H, min_instances)
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
            weight = 1.0 if k == 0 else learning_rate
            if fault != "stuck":
                pred = pred + weight * jnp.asarray(value)[node]
            t, v = self.errors(y, pred, valid)
            forest.append((feature, left_mask, value))
            weights.append(weight)
            errs.append((float(t), float(v)))
        return forest, weights, errs
