"""Driver `nn_fullbatch`: `shifu_tpu.train.nn_trainer.train_nn` called back to
back on device-resident data, which is what `shifu train` (NN) ends in.

Set-up makes the data on the device from the seed in one jitted call, then
drives the entry through its first steps (1, 2 and 3 epochs from the same
start): those calls compile or fetch the one program the window uses, warm it,
and leave the readings `correct` compares. The optimizer's state is read where
the trainer hands its program to `obs.profile.dispatch` (its own seam, under
the name `nn.train_program`), since `train_nn` returns only errors.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import compare, spec


def _make_data(n: int, d: int, seed: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kx, kn = jax.random.split(key)
        x = jax.random.normal(kx, (n, d), jnp.float32)
        z = (1.5 * x[:, 0] - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
             + 0.8 * jnp.tanh(x[:, 4]) + 0.5 * jax.random.normal(kn, (n,)))
        return x, (z > 0).astype(jnp.float32), jnp.ones((n,), jnp.float32)

    return make(jax.random.PRNGKey(seed % (2**31 - 1)))


class Driver:
    def __init__(self, cell, seed: int, rows: int | None = None):
        import jax

        from shifu_tpu.train import nn_trainer

        cfg, traffic = cell.config, cell.traffic
        self.cell = cell
        self.trainer = nn_trainer
        self.n = int(rows or cfg["rows"])
        self.seed = seed % (2**31 - 1)
        self.sizes = [cfg["features"]] + list(cfg["hidden_nodes"]) + \
            [cfg["outputs"]]
        self.epochs = int(traffic["epochs_per_call"])
        self.steps = int(traffic["check_steps"])
        self.work_per_call = self.n * self.epochs
        self.unit_ends = []  # NN calls have no inner stamps
        self.ref = spec.load_module("references", cfg["reference"])
        self.x, self.t, self.w = jax.block_until_ready(
            _make_data(self.n, self.sizes[0], self.seed))
        self.flat0 = self.ref.xavier_flat(self.sizes, self.seed)
        self.train_cfg = self._cfg(self.epochs)
        self.program = None

    def _cfg(self, epochs: int):
        c = self.cell.config
        return self.trainer.NNTrainConfig(
            hidden_nodes=list(c["hidden_nodes"]),
            activations=[c["activation"]] * len(c["hidden_nodes"]),
            learning_rate=float(c["learning_rate"]),
            propagation=c["propagation"], loss=c["loss"],
            num_epochs=epochs, valid_set_rate=float(c["valid_set_rate"]),
            seed=self.seed, mixed_precision=bool(c["mixed_precision"]))

    def _train(self, cfg):
        return self.trainer.train_nn(self.x, self.t, self.w, cfg,
                                     init_flat=self.flat0, fetch_params=False)

    def warm_and_read(self) -> None:
        """The entry's first steps, through the window's own call and data:
        k epochs from the start, for k = 1..check_steps."""
        from shifu_tpu.obs import profile

        seen = []
        real = profile.dispatch

        def tap(name, fn, *args, **kw):
            out = real(name, fn, *args, **kw)
            if name == "nn.train_program":
                seen.append(out)
            return out

        losses, entry = [], []
        profile.dispatch = tap
        try:
            for k in range(1, self.steps + 1):
                res = self._train(self._cfg(k))
                carry = seen[-1]
                losses.append((float(carry[8]), float(carry[9])))
                entry.append((res.train_error, res.iterations))
                if k == 1:
                    grad1 = np.asarray(carry[1]["last_gradient"])
                    flat1 = np.asarray(carry[0])
            flat_end = np.asarray(seen[-1][0])
        finally:
            profile.dispatch = real
        self.program = {
            "losses": losses,
            "grad1": self.ref.leaves_of(grad1, self.sizes),
            "change1": self.ref.leaves_of(flat1 - self.flat0, self.sizes),
            "change": self.ref.leaves_of(flat_end - self.flat0, self.sizes),
            "entry": entry,
        }

    def call(self) -> None:
        res = self._train(self.train_cfg)
        if res.iterations != self.epochs:
            raise RuntimeError("train_nn stopped after %d of %d epochs"
                               % (res.iterations, self.epochs))

    def free(self) -> None:
        """Drop what the program keeps on the device, the data excepted (the
        reference reads the same rows)."""
        self.trainer._SAMPLE_CACHE.clear()

    def reference(self, lowp: bool = False, **kw) -> dict:
        c = self.cell.config
        return self.ref.first_steps(
            self.x, self.t, self.w, self.flat0, self.sizes, self.seed,
            float(c["valid_set_rate"]), steps=self.steps, lowp=lowp, **kw)

    def reference_twice(self) -> dict:
        """The look behind the limits: the same float32 reference, its rows
        summed in blocks of half the size, against itself. What differs here
        is rounding in the order of a sum and nothing else."""
        a, b = self.reference(), self.reference(block_rows=62500)
        return {"loss%d_gap" % (k + 1): max(
            compare.rel_gap(p, q) for p, q in zip(a["losses"][k],
                                                  b["losses"][k]))
            for k in range(self.steps)}

    def compared(self, control: bool = False, fault: str | None = None):
        """Each number beside its limit; a number with the limit None is read
        and not compared (PERF.md says why). With `control` the reference in
        the lower precision stands in the program's place, with `fault` the
        reference with that fault planted."""
        ref = self.reference()
        if fault:
            got = self.reference(fault=fault)
        else:
            got = self.reference(lowp=True) if control else self.program
        lim = self.cell.traffic["limits"]

        def loss_gap(k):
            return max(compare.rel_gap(a, b) for a, b in
                       zip(got["losses"][k], ref["losses"][k]))

        gnorm = np.array([np.linalg.norm(g) for g in ref["grad1"]])
        dead = gnorm < 1e-3 * np.median(gnorm)
        g1 = np.concatenate([np.ravel(a) for a in got["change1"]])
        r1 = np.concatenate([np.ravel(a) for a in ref["change1"]])
        out = {
            "loss1_gap": loss_gap(0),
            "grad_gap": compare.worst_leaf_norm_gap(got["grad1"],
                                                    ref["grad1"]),
            "change1_gap": compare.worst_leaf_norm_gap(
                got["change1"], ref["change1"], skip=dead),
            # every weight's first RPROP move is +-0.1: the share whose move
            # is not the reference's (turned, left out, or made twice)
            "flip_share": float(np.mean(np.abs(g1 - r1) > 0.05)),
        }
        for k in range(1, self.steps):
            out["loss%d_gap" % (k + 1)] = loss_gap(k)
        out["change%d_gap" % self.steps] = compare.worst_leaf_norm_gap(
            got["change"], ref["change"], skip=dead)
        if "entry" in got:
            out["entry_gap"] = max(
                [compare.rel_gap(e[0], l[0]) for e, l in
                 zip(got["entry"], got["losses"])]
                + [abs(e[1] - k) for k, e in enumerate(got["entry"], 1)])
        return {k: {"value": float(v), "limit": lim.get(k)}
                for k, v in out.items()}


def setup(cell, seed: int, rows: int | None = None) -> Driver:
    return Driver(cell, seed, rows)
