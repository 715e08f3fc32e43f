"""Plain reference: level-wise gradient-boosted regression trees on binned
codes, as Shifu defines them. Straightforward jax.numpy and numpy, float32
sums with matmuls at `highest`, gains in float64; no kernels, no subtraction,
nothing of shifu_tpu.

Semantics, as the trainer's users see them:
- rows are split once: `valid` where `default_rng([seed, 999983]).random(n) <
  rate`; a valid row has weight 0 in every histogram and counts only in the
  validation error;
- tree k fits the residual y - F(x) of the running prediction F (squared
  loss); tree 0 enters F with weight 1, every later tree with the learning
  rate;
- a tree is a complete binary layout, node i with children 2i+1 and 2i+2,
  grown level by level to `depth`; every node's value is the weighted mean of
  the residuals of its training rows;
- a node splits on the (feature, cut) of the largest variance gain
  sse(node) - sse(left) - sse(right), sse = sum(w r^2) - sum(w r)^2 / sum(w),
  left = bins <= cut; a split needs `min_instances` weighted rows on each
  side and a gain above 0; the last bin of a feature is no cut;
- the error after a tree is the mean of (y - clip(F, 0, 1))^2 over the
  training rows and over the validation rows.

Two uses. `evaluate` follows a forest that something else grew, tree by tree
with that forest's own predictions (as a served model's tokens are followed),
and says how far each level's chosen splits fall short of the best gain by
the reference's own histograms, and how far each node's value lies from the
reference's mean. `grow` grows a forest itself; with `lowp=True` it is the
control of `correct`: the program's component planes are bfloat16 (8 bits of
mantissa), so the control rounds w, w r and w r^2 to e4m3's 3 stored bits
(`lax.reduce_precision`, float32's exponent range kept) before summing.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 65536


def split_valid(n: int, seed: int, rate: float) -> np.ndarray:
    return np.random.default_rng([seed, 999_983]).random(n) < rate


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
        N, D = self.N, depth
        blk = _block(n)
        nb = n // blk

        def traverse(codes, feature, left_mask):
            """path [n, D+1]: the node a row is in at each depth, -1 below the
            node it rests in."""
            node = jnp.zeros(n, jnp.int32)
            alive = jnp.ones(n, bool)
            cols = [node]
            for _ in range(D):
                f = feature[node]
                alive = alive & (f >= 0)
                code = jnp.take_along_axis(
                    codes, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
                left = left_mask[node, jnp.clip(code, 0, S - 1)]
                node = jnp.where(left, 2 * node + 1, 2 * node + 2)
                node = jnp.where(alive, node, 0)
                cols.append(jnp.where(alive, node, -1))
            return jnp.stack(cols, axis=1)

        def path_hist(codes, path, planes):
            """H [N, 3, F, S]: sum of each plane over the rows of each node,
            by feature and bin."""

            def body(b, acc):
                c = jax.lax.dynamic_slice_in_dim(codes, b * blk, blk, 0)
                p = jax.lax.dynamic_slice_in_dim(path, b * blk, blk, 0)
                v = jax.lax.dynamic_slice_in_dim(planes, b * blk, blk, 0)
                a = jax.nn.one_hot(p, N, dtype=jnp.float32).sum(axis=1)
                a3 = (a[:, :, None] * v[:, None, :]).reshape(blk, N * 3)
                oh = jax.nn.one_hot(c, S, dtype=jnp.float32).reshape(
                    blk, F * S)
                return acc + jnp.matmul(a3.T, oh, precision="highest")

            acc = jax.lax.fori_loop(0, nb, body,
                                    jnp.zeros((N * 3, F * S), jnp.float32))
            return acc.reshape(N, 3, F, S)

        def planes_of(y, pred, w, lowp):
            r = y - pred
            v = jnp.stack([w, w * r, w * r * r], axis=1)
            return jax.lax.reduce_precision(v, 8, 3) if lowp else v

        def rest_node(path):
            """The node each row rests in: the deepest of its path."""
            return jnp.max(path, axis=1)

        def errors(y, pred, valid):
            sq = (y - jnp.clip(pred, 0.0, 1.0)) ** 2
            t = jnp.sum(jnp.where(valid, 0.0, sq)) / jnp.maximum(
                jnp.sum(~valid), 1)
            v = jnp.sum(jnp.where(valid, sq, 0.0)) / jnp.maximum(
                jnp.sum(valid), 1)
            return t, v

        self.traverse = jax.jit(traverse)
        self.path_hist = jax.jit(path_hist)
        self.planes_of = jax.jit(planes_of, static_argnums=3)
        self.rest_node = jax.jit(rest_node)
        self.errors = jax.jit(errors)

    # ---- host arithmetic on the small histogram, float64 ----

    def gains(self, H: np.ndarray, min_instances: float):
        """(gain [N, F, S] with -inf where no split may be made, count [N],
        mean [N]) from H [N, 3, F, S]."""
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
                 min_instances: float) -> dict:
        """forest: [(feature [N] int, left_mask [N, S] bool, value [N] f32)],
        weights: each tree's weight in F. Returns, a tree: regret (the worst
        level's share of the best gain that the chosen splits miss), value_gap
        (the worst node's value against the reference's mean, measured
        against that mean or the median node's, whichever is larger), and the
        (train, valid) error after it."""
        import jax.numpy as jnp

        wt = jnp.where(valid, 0.0, w)
        pred = jnp.zeros(self.n, jnp.float32)
        out = {"regret": [], "value_gap": [], "errors": []}
        for (feature, left_mask, value), weight in zip(forest, weights):
            feature = np.asarray(feature, np.int32)
            left_mask = np.asarray(left_mask, bool)
            value = np.asarray(value, np.float32)
            path = self.traverse(codes, jnp.asarray(feature),
                                 jnp.asarray(left_mask))
            H = np.asarray(self.path_hist(
                codes, path, self.planes_of(y, pred, wt, False)))
            gain, count, mean = self.gains(H, min_instances)
            best = np.maximum(gain.reshape(self.N, -1).max(axis=1), 0.0)
            chosen = np.zeros(self.N)
            for i in np.nonzero(feature >= 0)[0]:
                m = left_mask[i, :self.S]
                cut = int(m.sum()) - 1
                if cut < 0 or not m[:cut + 1].all():
                    continue  # not a cut of ordered bins: no gain granted
                g = gain[i, feature[i], cut]
                chosen[i] = g if np.isfinite(g) else 0.0
            reached = count > 0
            inner = np.arange(self.N) < 2 ** self.D - 1
            worst = 0.0
            for d in range(self.D):
                lvl = slice(2 ** d - 1, 2 ** (d + 1) - 1)
                sel = reached[lvl] & inner[lvl]
                b, c = best[lvl][sel].sum(), chosen[lvl][sel].sum()
                if b > 0:
                    worst = max(worst, (b - c) / b)
            out["regret"].append(float(worst))
            scale = np.maximum(np.abs(mean), np.median(np.abs(mean[reached])))
            out["value_gap"].append(float(np.max(
                (np.abs(value - mean) / np.maximum(scale, 1e-30))[reached])))
            pred = pred + weight * jnp.asarray(value)[self.rest_node(path)]
            t, v = self.errors(y, pred, valid)
            out["errors"].append((float(t), float(v)))
        return out

    # ---- growing a forest ----

    def grow(self, codes, y, w, valid, trees: int, learning_rate: float,
             min_instances: float, lowp: bool = False,
             fault: str | None = None):
        """(forest, weights, errors) in `evaluate`'s form. `fault` plants one,
        for reading what it does to the numbers compared: "half" leaves every
        second row out of every sum, "stuck" never moves the running
        prediction."""
        import jax.numpy as jnp

        wt = jnp.where(valid, 0.0, w)
        if fault == "half":
            wt = wt * (jnp.arange(self.n) % 2 == 0)
        pred = jnp.zeros(self.n, jnp.float32)
        cuts = np.arange(self.S)
        forest, weights, errs = [], [], []
        for k in range(trees):
            feature = np.full(self.N, -1, np.int32)
            left_mask = np.zeros((self.N, self.S), bool)
            value = np.zeros(self.N, np.float32)
            planes = self.planes_of(y, pred, wt, lowp)
            for d in range(self.D + 1):
                path = self.traverse(codes, jnp.asarray(feature),
                                     jnp.asarray(left_mask))
                only = jnp.where(jnp.arange(self.D + 1)[None, :] == d,
                                 path, -1)
                H = np.asarray(self.path_hist(codes, only, planes))
                gain, count, mean = self.gains(H, min_instances)
                for i in range(2 ** d - 1, 2 ** (d + 1) - 1):
                    if count[i] <= 0:
                        continue
                    value[i] = mean[i]
                    flat = int(np.argmax(gain[i]))
                    f, cut = divmod(flat, self.S)
                    if d < self.D and np.isfinite(gain[i, f, cut]):
                        feature[i] = f
                        left_mask[i] = cuts <= cut
            weight = 1.0 if k == 0 else learning_rate
            path = self.traverse(codes, jnp.asarray(feature),
                                 jnp.asarray(left_mask))
            if fault != "stuck":
                pred = pred + weight * jnp.asarray(value)[
                    self.rest_node(path)]
            t, v = self.errors(y, pred, valid)
            forest.append((feature, left_mask, value))
            weights.append(weight)
            errs.append((float(t), float(v)))
        return forest, weights, errs
