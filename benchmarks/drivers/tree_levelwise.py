"""Driver `tree_levelwise`: `shifu_tpu.train.tree_trainer.train_trees` called
back to back on device-resident codes, which is what `shifu train` (GBT) ends
in. `progress_cb` is used only to stamp each tree's end and keep the errors
the trainer reports.

Every call grows the same forest from the same start (the trainer's draws are
keyed by its seed), so `correct` follows the forest of the window's last call
tree by tree against the plain reference, and holds every other call's forest
to be that forest, bit for bit.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

from benchmarks.lib import compare, spec


def _make_data(n: int, F: int, bins: int, seed: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kc, kn = jax.random.split(key)
        codes = jax.random.randint(kc, (n, F), 0, bins, jnp.int32)
        u = (codes[:, :6].astype(jnp.float32) + 0.5) / bins - 0.5
        z = (2.0 * u[:, 0] - 1.5 * u[:, 1] + u[:, 2]
             + 2.0 * jnp.where(u[:, 3] > 0, u[:, 4], -u[:, 4])
             + jnp.sin(6.0 * u[:, 5]) * 0.5
             + 0.35 * jax.random.normal(kn, (n,)))
        return codes, (z > 0).astype(jnp.float32), jnp.ones((n,), jnp.float32)

    return make(jax.random.PRNGKey(seed % (2**31 - 1)))


class Driver:
    def __init__(self, cell, seed: int, rows: int | None = None):
        import jax

        from shifu_tpu.train import tree_trainer

        c, traffic = cell.config, cell.traffic
        self.cell = cell
        self.trainer = tree_trainer
        self.n = int(rows or c["rows"])
        self.seed = seed % (2**31 - 1)
        self.F, self.S = int(c["features"]), int(c["slots_per_feature"])
        self.D = int(c["max_depth"])
        self.trees = int(traffic["trees_per_call"])
        self.work_per_call = self.n * self.trees
        self.unit_ends = []
        self.ref = spec.load_module("references", c["reference"])
        self.codes, self.y, self.w = jax.block_until_ready(
            _make_data(self.n, self.F, self.S - 1, self.seed))
        self.cols = ["f%d" % i for i in range(self.F)]
        self.train_cfg = self._cfg(self.trees)
        self.last = None  # (forest, weights, errors) of the newest call
        self.digests = []

    def _cfg(self, trees: int):
        c = self.cell.config
        return self.trainer.TreeTrainConfig(
            algorithm="GBT", tree_num=trees, max_depth=self.D,
            impurity=c["impurity"], loss=c["loss"],
            learning_rate=float(c["learning_rate"]),
            min_instances_per_node=int(c["min_instances_per_node"]),
            min_info_gain=float(c["min_info_gain"]),
            feature_subset_strategy=c["feature_subset_strategy"],
            valid_set_rate=float(c["valid_set_rate"]),
            max_stats_memory_mb=int(c["max_stats_memory_mb"]),
            hist_subtraction=bool(c["hist_subtraction"]), seed=self.seed)

    def _stamp(self, k: int, terr: float, verr: float) -> None:
        self.unit_ends.append(time.perf_counter())
        self._errors.append((float(terr), float(verr)))

    def call(self, cfg=None) -> None:
        cfg = cfg or self.train_cfg
        self._errors = []
        res = self.trainer.train_trees(
            self.codes, self.y, self.w, [self.S] * self.F,
            [False] * self.F, self.cols, cfg, progress_cb=self._stamp)
        trees = res.spec.trees
        if len(trees) != cfg.tree_num:
            raise RuntimeError("train_trees grew %d of %d trees"
                               % (len(trees), cfg.tree_num))
        forest = [(np.asarray(t.feature), np.asarray(t.left_mask),
                   np.asarray(t.leaf_value)) for t in trees]
        h = hashlib.sha256()
        for f, m, v in forest:
            h.update(f.tobytes() + m.tobytes() + v.tobytes())
        self.digests.append(h.hexdigest())
        self.last = (forest, [float(t.weight) for t in trees],
                     list(self._errors))

    def warm_and_read(self) -> None:
        """A call of two trees: the first tree and a later one between them
        run every program a longer call runs, so it compiles or fetches all
        the window uses at a fifth of a whole call's time."""
        self.call(self._cfg(min(2, self.trees)))
        self.unit_ends.clear()
        self.digests.clear()

    def free(self) -> None:
        pass  # the trainer keeps nothing on the device between calls

    def compared(self, control: bool = False, fault: str | None = None):
        """Each number beside its limit. With `control` the reference growing
        in the lower precision stands in the program's place; with `fault`
        one is planted: "half" and "stuck" in the reference growing three
        trees in the program's place, "value" and "split" in the forest the
        program grew."""
        import jax.numpy as jnp

        c = self.cell.config
        R = self.ref.Reference(self.n, self.F, self.S, self.D)
        valid = jnp.asarray(self.ref.split_valid(
            self.n, self.seed, float(c["valid_set_rate"])))
        mi = float(c["min_instances_per_node"])
        differ = 0
        if control or fault in ("half", "stuck"):
            got = R.grow(self.codes, self.y, self.w, valid,
                         self.trees if control else 3,
                         float(c["learning_rate"]), mi, lowp=control,
                         fault=fault)
        else:
            got = self.last
            differ = sum(d != self.digests[-1] for d in self.digests)
        forest, weights, errors = got
        if fault == "value":
            f, m, v = forest[-1]
            v = v.copy()
            v[-1] *= 1.2
            forest = forest[:-1] + [(f, m, v)]
        elif fault == "split":
            f, m, v = forest[0]
            f = f.copy()
            f[1] = (f[1] + 7) % self.F
            forest = [(f, m, v)] + forest[1:]
        ev = R.evaluate(self.codes, self.y, self.w, valid, forest, weights,
                        mi)
        out = {
            "regret": max(ev["regret"]),
            "value_gap": max(ev["value_gap"]),
            "error_gap": max(compare.rel_gap(a, b)
                             for pa, pb in zip(errors, ev["errors"])
                             for a, b in zip(pa, pb)),
            "forests_differ": float(differ),
        }
        lim = self.cell.traffic["limits"]
        return {k: {"value": float(v), "limit": lim.get(k)}
                for k, v in out.items()}


def setup(cell, seed: int, rows: int | None = None) -> Driver:
    return Driver(cell, seed, rows)
