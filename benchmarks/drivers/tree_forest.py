"""Driver `tree_forest`: `shifu_tpu.train.tree_trainer.train_trees` with
`algorithm="RF"` called back to back on device-resident codes, which is what
`shifu train` (RF) ends in. The data, the calls, the stamps and the digests
are `tree_levelwise`'s; what differs is the configuration handed over (a
forest: a Poisson bag and a column subset a tree, the labels themselves
fitted, the running mean) and the comparison that decides `correct`.

Every call grows the same forest from the same start (the trainer's draws
are keyed by its seed and the tree's index), so `correct` follows the forest
of the window's last call against the plain reference and holds every other
call's forest to be that forest, bit for bit. A forest's trees are
independent given their bags: `regret` and `value_gap`, which need every
node's histogram, follow the first, a middle and the last tree; `error_gap`
follows all of them.
"""

from __future__ import annotations

import functools

from benchmarks.lib import compare, spec

_levelwise = spec.load_module("drivers", "tree_levelwise")

# planted in the reference growing three trees in the program's place
GROWN_FAULTS = ("bag", "subset", "half", "sum")


class Driver(_levelwise.Driver):
    def _cfg(self, trees: int):
        c = self.cell.config
        return self.trainer.TreeTrainConfig(
            algorithm=c["algorithm"], tree_num=trees, max_depth=self.D,
            impurity=c["impurity"],
            min_instances_per_node=int(c["min_instances_per_node"]),
            min_info_gain=float(c["min_info_gain"]),
            feature_subset_strategy=c["feature_subset_strategy"],
            bagging_with_replacement=bool(c["bagging_with_replacement"]),
            bagging_sample_rate=float(c["bagging_sample_rate"]),
            valid_set_rate=float(c["valid_set_rate"]),
            max_stats_memory_mb=int(c["max_stats_memory_mb"]),
            hist_subtraction=bool(c["hist_subtraction"]), seed=self.seed)

    @functools.cached_property
    def reference(self):
        """One set of compiled pieces a driver: `calibrate.py` compares a
        seed's forest many times over."""
        return self.ref.Reference(self.n, self.F, self.S, self.D)

    def compared(self, control: bool = False, fault: str | None = None):
        """Each number beside its limit. With `control` the reference growing
        with its histograms and cumulative sums rounded to the
        configuration's `control_mantissa_bits` stands in the program's
        place ("lowp8" as a `fault`: to bfloat16's 8 bits, which the chip's
        own scan keeps of its operands and which therefore separates
        nothing); with another `fault` one is planted: those of GROWN_FAULTS
        in the reference growing three trees in the program's place, "value"
        and "split" in the forest the program grew."""
        import jax.numpy as jnp

        c = self.cell.config
        R = self.reference
        valid = jnp.asarray(self.ref.split_valid(
            self.n, self.seed, float(c["valid_set_rate"])))
        draws = (self.seed, float(c["bagging_sample_rate"]),
                 int(c["features_per_tree"]),
                 float(c["min_instances_per_node"]))
        keep_bits = (int(c["control_mantissa_bits"]) if control
                     else 8 if fault == "lowp8" else None)
        differ = 0
        if keep_bits is not None:
            forest, errors = R.grow(self.codes, self.y, self.w, valid,
                                    self.trees, *draws, keep_bits=keep_bits)
        elif fault in GROWN_FAULTS:
            forest, errors = R.grow(self.codes, self.y, self.w, valid, 3,
                                    *draws, fault=fault)
        else:
            forest, _weights, errors = self.last
            differ = sum(d != self.digests[-1] for d in self.digests)
        if fault == "value":
            f, m, v = forest[-1]
            v = v.copy()
            v[v.nonzero()[0][-1]] *= 1.2  # the last node a row reached
            forest = forest[:-1] + [(f, m, v)]
        elif fault == "split":
            f, m, v = forest[0]
            f = f.copy()
            f[1] = (f[1] + 7) % self.F
            forest = [(f, m, v)] + forest[1:]
        last = len(forest) - 1
        ev = R.evaluate(self.codes, self.y, self.w, valid, forest, *draws,
                        follow=sorted({0, last // 2, last}))
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
