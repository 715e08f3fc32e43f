"""Driver `wdl_fullbatch`: `shifu_tpu.train.wdl_trainer.train_wdl` called back
to back on device-resident data, which is what `shifu train` (WDL) ends in.

Set-up makes the data on the device from the seed in one jitted call (dense
columns, one code a categorical column drawn from a truncated power law and
folded as `shifu stats` folds a capped column, a label), then drives the
entry through its first steps (1 .. check_steps epochs from the same start):
those calls compile or fetch the one program the window uses, warm it, and
leave the readings `correct` compares. The optimizer's state is read where
the trainer hands its program to `obs.profile.dispatch` (its own seam, under
the name `wdl.train_program`), since `train_wdl` returns only the chosen
weights and two errors.
"""

from __future__ import annotations

import json
import re

import numpy as np

from benchmarks.lib import compare, spec

SEAM = "wdl.train_program"
# a leaf's own flip share is read only where it has this many moved weights
LEAF_FLIP_MIN = 256


def worst_leaf_gap(prog_leaves, ref_leaves) -> float:
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that same leaf: an embedding table's
    gradient is a tenth of a wide table's, and measured against the median
    leaf (`compare.worst_leaf_norm_gap`) a table left out would read 0.1.
    A leaf that is nought in the reference is held to a thousandth of the
    median leaf."""
    pn = np.array([np.linalg.norm(np.asarray(p, np.float64).ravel())
                   for p in prog_leaves])
    rn = np.array([np.linalg.norm(np.asarray(r, np.float64).ravel())
                   for r in ref_leaves])
    floor = max(1e-3 * float(np.median(rn)), 1e-30)
    return float(np.max(np.abs(pn - rn) / np.maximum(rn, floor)))


def vocab_sizes(cfg: dict) -> list:
    """One table a categorical column: the kept categories (at most the cap,
    by frequency) and the trailing slot for everything else."""
    cap = int(cfg["category_cap"])
    return [min(int(c), cap) + 1 for c in cfg["published_cardinalities"]]


_MAKERS = {}  # one compiled generator a shape, whatever the seed


def _make_data(n: int, cfg: dict, seed: int):
    import jax

    key = (n, json.dumps(cfg, sort_keys=True))
    if key not in _MAKERS:
        _MAKERS[key] = _maker(n, cfg)
    return _MAKERS[key](jax.random.PRNGKey(seed % (2**31 - 1)))


def _maker(n: int, cfg: dict):
    import jax
    import jax.numpy as jnp

    d = cfg["data"]
    n_dense = int(cfg["dense_columns"])
    cards = np.asarray(cfg["published_cardinalities"], np.float32)
    cap = int(cfg["category_cap"])
    e = 1.0 - float(d["rank_exponent"])
    on_dense, on_codes = d["label_dense_columns"], d["label_code_columns"]

    @jax.jit
    def make(key):
        kd, kc, kn = jax.random.split(key, 3)
        cut = float(d["dense_cutoff"])
        dense = jnp.clip(jax.random.normal(kd, (n, n_dense), jnp.float32),
                         -cut, cut)
        # inverse CDF of the continuous power law rank^-s on [1, card + 1)
        u = jax.random.uniform(kc, (n, cards.size), jnp.float32)
        rank = jnp.floor((1.0 + u * ((cards + 1.0) ** e - 1.0)) ** (1.0 / e))
        code = jnp.clip(rank, 1.0, cards).astype(jnp.int32) - 1
        # a value outside the kept categories goes where the code matrix
        # sends it (stats/binning.py categorical_bin_index): the slot after
        # the last kept category
        codes = jnp.where(code >= cap, cap, code)
        z = (0.9 * dense[:, on_dense[0]] - 0.7 * dense[:, on_dense[1]]
             + 0.5 * dense[:, on_dense[2]] * dense[:, on_dense[3]])
        for f in on_codes:  # a fixed pseudo-random effect a category
            z = z + 0.8 * jnp.sin(2.39996 * codes[:, f].astype(jnp.float32)
                                  + f)
        z = z + jax.random.logistic(kn, (n,), jnp.float32)
        label = (z + float(d["label_bias"]) > 0).astype(jnp.float32)
        return dense, codes, label, jnp.ones((n,), jnp.float32)

    return make


_OPCODE = re.compile(r" (?:gather|scatter)\(")


def lookup_holders(hlo_text: str) -> set:
    """Names of the instructions of a compiled module that are a `gather` or
    a `scatter`, or a fusion whose computation holds one, at any depth (the
    v5e's compiler wraps a scatter's fusion in a second one that also fills
    the table with zeros)."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    out, inside = set(), set()
    while True:
        found = set()
        for comp, lines in comps.items():
            for ln in lines:
                m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", ln)
                if not m:
                    continue
                called = re.search(r" fusion\(.*calls=%([\w.\-]+)", ln)
                if _OPCODE.search(ln) or (called
                                         and called.group(1) in inside):
                    out.add(m.group(1))
                    found.add(comp)
        if found <= inside:
            return out
        inside |= found


def lookup_count(hlo_text: str) -> int:
    """The `gather` and `scatter` instructions of a compiled module."""
    return len(_OPCODE.findall(hlo_text))


class Driver:
    def __init__(self, cell, seed: int, rows: int | None = None):
        import jax

        from shifu_tpu.train import wdl_trainer

        cfg, traffic = cell.config, cell.traffic
        self.cell = cell
        self.trainer = wdl_trainer
        self.n = int(rows or cfg["rows"])
        self.seed = seed % (2**31 - 1)
        self.vocab = vocab_sizes(cfg)
        self.n_dense = int(cfg["dense_columns"])
        self.epochs = int(traffic["epochs_per_call"])
        self.steps = int(traffic["check_steps"])
        self.work_per_call = self.n * self.epochs
        self.unit_ends = []  # WDL calls have no inner stamps
        self.ref = spec.load_module("references", cfg["reference"])
        model = (self.n_dense, self.vocab, int(cfg["embed_outputs"]),
                 cfg["hidden_nodes"])
        self.shapes = self.ref.leaf_shapes(*model)
        self.dense, self.codes, self.t, self.w = jax.block_until_ready(
            _make_data(self.n, cfg, self.seed))
        self.flat0 = self.ref.start_flat(*model, self.seed)
        self.train_cfg = self._cfg(self.epochs)
        self.program = None
        self._sound = None  # the reference's sound steps, once

    def _cfg(self, epochs: int):
        c = self.cell.config
        return self.trainer.WDLTrainConfig(
            hidden=list(c["hidden_nodes"]),
            activations=[c["activation"]] * len(c["hidden_nodes"]),
            embed_dim=int(c["embed_outputs"]),
            learning_rate=float(c["learning_rate"]),
            optimizer=c["optimizer"], l2_reg=float(c["l2_reg"]),
            num_epochs=epochs, valid_set_rate=float(c["valid_set_rate"]),
            seed=self.seed)

    def _train(self, cfg):
        return self.trainer.train_wdl(self.dense, self.codes, self.t, self.w,
                                      self.vocab, cfg, init_flat=self.flat0)

    def warm_and_read(self) -> None:
        """The entry's first steps, through the window's own call and data:
        k epochs from the start, for k = 1..check_steps."""
        from shifu_tpu.models.wdl import flatten_wdl
        from shifu_tpu.obs import profile

        seen = []
        real = profile.dispatch

        def tap(name, fn, *args, **kw):
            out = real(name, fn, *args, **kw)
            if name == SEAM:
                seen.append(out)
            return out

        losses, flats, entry = [], [], []
        profile.dispatch = tap
        try:
            for k in range(1, self.steps + 1):
                res = self._train(self._cfg(k))
                (flat, opt, it, best_val, best_flat, _bad, _halt, tr,
                 va) = seen[-1]
                losses.append((float(tr), float(va)))
                flats.append(np.asarray(flat))
                # what the entry handed back against the state it came from:
                # the weights it chose (those the best validation error was
                # read on), the error, the count
                chosen = np.asarray(
                    best_flat if np.isfinite(float(best_val)) else flat)
                entry.append(max(
                    float(np.max(np.abs(flatten_wdl(res.params) - chosen))),
                    compare.rel_gap(res.train_error, float(tr)),
                    abs(res.iterations - k), abs(int(it) - k)))
                if k == 1:
                    # ADAM's first m is (1 - beta1) x the descent direction
                    grad1 = np.asarray(opt["m"]) / np.float32(
                        1.0 - self.ref.BETA1)
        finally:
            profile.dispatch = real
        self.program = {
            "losses": losses,
            "grad1": self.ref.leaves_of(grad1, self.shapes),
            "change1": self.ref.leaves_of(flats[0] - self.flat0, self.shapes),
            "change": self.ref.leaves_of(flats[-1] - self.flat0, self.shapes),
            "entry": entry,
        }

    def call(self) -> None:
        res = self._train(self.train_cfg)
        if res.iterations != self.epochs:
            raise RuntimeError("train_wdl stopped after %d of %d epochs"
                               % (res.iterations, self.epochs))

    def free(self) -> None:
        """Drop what the program keeps on the device, the data excepted (the
        reference reads the same rows)."""
        from shifu_tpu.train import nn_trainer

        nn_trainer._SAMPLE_CACHE.clear()

    def program_texts(self):
        """The compiled text of the executable(s) the window ran, which the
        trainer's dispatch seam keeps (`obs.profile.compiled_texts`); None
        under a program that has no such accessor (a parent commit). With
        the accessor there and nothing kept, the lookups' readers would
        read a part for the whole: that is an error, not a reading."""
        from shifu_tpu.obs import profile

        if not hasattr(profile, "compiled_texts"):
            return None
        texts = profile.compiled_texts(SEAM)
        if not texts:
            raise RuntimeError("the seam %s kept no executable" % SEAM)
        return texts

    def program_lookups(self):
        """`lookup_holders` of the executable the window ran: a reader of
        the device trace needs them, since a fusion's event does not say
        what it holds, and the same program lowered again need not number
        its fusions alike (PR 32 read a third of the lookups that way)."""
        texts = self.program_texts()
        if texts is None:
            return None
        return set().union(*(lookup_holders(t) for t in texts))

    def program_lookup_count(self):
        """Passes over the rows that are a table lookup or its transpose,
        an epoch: the `gather` and `scatter` instructions of the executable
        the window ran (the epoch loop's body holds every one)."""
        texts = self.program_texts()
        return None if texts is None else max(map(lookup_count, texts))

    def reference(self, lowp: bool = False, **kw) -> dict:
        c = self.cell.config
        return self.ref.first_steps(
            self.dense, self.codes, self.t, self.w, self.flat0, self.shapes,
            self.seed, float(c["valid_set_rate"]), float(c["learning_rate"]),
            float(c["l2_reg"]), steps=self.steps, lowp=lowp, **kw)

    def compared(self, control: bool = False, fault: str | None = None):
        """Each number beside its limit; a number with the limit None is read
        and not compared (PERF.md says why). With `control` the reference in
        the lower precision stands in the program's place, with `fault` the
        reference with that fault planted."""
        if self._sound is None:
            self._sound = self.reference()
        ref = self._sound
        if fault:
            got = self.reference(fault=fault)
        else:
            got = self.reference(lowp=True) if control else self.program
        lim = self.cell.traffic["limits"]
        lr = float(self.cell.config["learning_rate"])

        def loss_gap(k):
            return max(compare.rel_gap(a, b) for a, b in
                       zip(got["losses"][k], ref["losses"][k]))

        # ADAM's first move is lr x sign(g) wherever g is not 0: a weight's
        # move is not the reference's where it is turned, left out or made
        # where the reference makes none
        flips = [np.abs(np.ravel(a) - np.ravel(b)) > 0.5 * lr
                 for a, b in zip(got["change1"], ref["change1"])]
        moved = [(np.ravel(a) != 0) | (np.ravel(b) != 0)
                 for a, b in zip(got["change1"], ref["change1"])]
        n_moved = max(sum(int(m.sum()) for m in moved), 1)
        out = {
            "grad_gap": worst_leaf_gap(got["grad1"], ref["grad1"]),
            "flip_share": sum(int(f.sum()) for f in flips) / n_moved,
            # the same share leaf by leaf, the worst leaf's: one column's
            # lookups shifted turns half of that column's moves and little
            # of the whole
            "leaf_flip_share": max(
                [f.sum() / m.sum() for f, m in zip(flips, moved)
                 if m.sum() >= LEAF_FLIP_MIN] or [0.0]),
        }
        for k in range(self.steps):
            out["loss%d_gap" % (k + 1)] = loss_gap(k)
        # the later steps: ADAM's moments carried and the weights moved. A
        # leaf is held to the median leaf's norm where its own is smaller:
        # one first move that bf16 turns is 0.35 % of the norm of a
        # 16-row table's 128 weights, and such tables read up to 8.7e-3
        # sound against their own norm
        out["change%d_gap" % self.steps] = compare.worst_leaf_norm_gap(
            got["change"], ref["change"])
        if "entry" in got:
            out["entry_gap"] = max(got["entry"])
        return {k: {"value": float(v), "limit": lim.get(k)}
                for k, v in out.items()}


def setup(cell, seed: int, rows: int | None = None) -> Driver:
    return Driver(cell, seed, rows)
