"""Driver `tree_mesh`: `train_trees(..., mesh=data_mesh(cell.chips))` called
back to back on device-resident codes, labels and weights that already lie
row-sharded over the mesh: what `shifu train` (GBT) ends in on a host of
several chips (`processor/train_tree.py` takes `data_mesh()` whenever jax
finds more than one device). Everything but the mesh and the placement of
the data is `tree_levelwise`'s: the same calls, stamps, digests and the same
comparison, with a reference that walks the row shards.

Notes for a multi-chip driver:
- `setup` builds `data_mesh(cell.chips)` over the first `cell.chips` devices
  jax finds (on the CPU tests those are the suite's forced host devices); the
  rows must divide by the chips, since a row-sharded `jax.Array` has even
  shards (`train_trees` itself pads any row count).
- The data is made on the devices, shard by shard, each shard by
  `tree_levelwise._make_data`'s rule under its own key (the seed's key
  folded with the shard's index), in one program under `shard_map`: no host
  copy of the table ever exists, and setting up 11,000,000 rows costs what
  one chip's 2,750,000 cost. (Calling `_make_data` once a device compiled
  the rule four times: 18 s of set-up on the chips.)
- The per-layer readers see the first chip's operations
  (`lib/xplane.summarize`), so a meshed cell's kernel readers reckon with
  `rows / chips`, and its whole-step share with `chips` x the peak.
"""

from __future__ import annotations

import functools
import types

from benchmarks.lib import spec

_levelwise = spec.load_module("drivers", "tree_levelwise")


def make_data(mesh, n: int, F: int, bins: int, seed: int):
    """(codes [n, F] int32, labels [n], weights [n]) row-sharded over
    `mesh`, every shard made on its own device by `tree_levelwise`'s rule
    (its `_make_data` jits one device's table; this is the same body under
    `shard_map`, one program for all the chips)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from shifu_tpu.parallel.mesh import (row_axes, row_shard_count,
                                         shard_map_compat)

    shards = row_shard_count(mesh)
    if n % shards:
        raise ValueError("%d rows do not divide over %d devices"
                         % (n, shards))
    m = n // shards
    axes = row_axes(mesh)

    def make(key):
        kc, kn = jax.random.split(
            jax.random.fold_in(key, jax.lax.axis_index(axes)))
        codes = jax.random.randint(kc, (m, F), 0, bins, jnp.int32)
        u = (codes[:, :6].astype(jnp.float32) + 0.5) / bins - 0.5
        z = (2.0 * u[:, 0] - 1.5 * u[:, 1] + u[:, 2]
             + 2.0 * jnp.where(u[:, 3] > 0, u[:, 4], -u[:, 4])
             + jnp.sin(6.0 * u[:, 5]) * 0.5
             + 0.35 * jax.random.normal(kn, (m,)))
        return codes, (z > 0).astype(jnp.float32), jnp.ones((m,), jnp.float32)

    rows = axes if len(axes) > 1 else axes[0]
    return jax.jit(shard_map_compat(
        make, mesh=mesh, in_specs=P(),
        out_specs=(P(rows, None), P(rows), P(rows))))(
            jax.random.PRNGKey(seed % (2**31 - 1)))


class Driver(_levelwise.Driver):
    def __init__(self, cell, seed: int, rows: int | None = None):
        import jax

        from shifu_tpu.parallel.mesh import data_mesh
        from shifu_tpu.train import tree_trainer

        c, traffic = cell.config, cell.traffic
        self.cell = cell
        self.mesh = data_mesh(cell.chips)
        # the base driver reaches the trainer through these two names: here
        # `train_trees` has the mesh bound
        self.trainer = types.SimpleNamespace(
            TreeTrainConfig=tree_trainer.TreeTrainConfig,
            train_trees=functools.partial(tree_trainer.train_trees,
                                          mesh=self.mesh))
        self.n = int(rows or c["rows"])
        self.seed = seed % (2**31 - 1)
        self.F, self.S = int(c["features"]), int(c["slots_per_feature"])
        self.D = int(c["max_depth"])
        self.trees = int(traffic["trees_per_call"])
        self.work_per_call = self.n * self.trees
        self.unit_ends = []
        self.ref = spec.load_module("references", c["reference"])
        self.codes, self.y, self.w = jax.block_until_ready(
            make_data(self.mesh, self.n, self.F, self.S - 1, self.seed))
        self.cols = ["f%d" % i for i in range(self.F)]
        self.train_cfg = self._cfg(self.trees)
        self.last = None
        self.digests = []


def setup(cell, seed: int, rows: int | None = None) -> Driver:
    return Driver(cell, seed, rows)
