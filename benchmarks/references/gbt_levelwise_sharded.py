"""Plain reference for a table whose rows lie sharded over several devices:
`gbt_levelwise`'s semantics and arithmetic, every line of them, with one
step done shard by shard. The histogram of all rows is the sum of the
histograms of the row shards, so `path_hist` walks the shards, has each
built in float32 on the device that holds it by `gbt_levelwise`'s own
`path_hist` for a table of the shard's size (`shard_map`, no collective),
and adds them in float64 on the host. Everything else (`traverse`, `planes_of`, `rest_node`, `errors`)
is per-row arithmetic or a sum over rows, which jax.numpy does on a
row-sharded array as it does on any other. Nothing of shifu_tpu.

Why not `gbt_levelwise` itself: it would hold whole HIGGS on one v5e (the
chip's compiler gives `path_hist` 1.94 GB of arguments and 0.22 GB of
temporaries at 11,000,000 rows), but the table lies on four devices. To use
it the 1.3 GB table is first copied to one chip, which then works alone for
four times as long while the host's other three are held idle: in every
check of the cell, and 80 times a seed under the control. Walking the
shards where they lie keeps the check a quarter as long and copies nothing.
`tests/benchmark/test_benchmark_gbt_mesh.py` holds the two files to the
same histogram and the same numbers.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import spec

_plain = spec.load_module("references", "gbt_levelwise")
split_valid = _plain.split_valid


class Reference(_plain.Reference):
    def __init__(self, n: int, F: int, S: int, depth: int):
        super().__init__(n, F, S, depth)
        self._on_one_device = self.path_hist  # gbt_levelwise's, for n rows
        self._by_shard = {}  # {sharding of the codes: compiled walk}
        self.path_hist = self._path_hist_by_shard

    def _path_hist_by_shard(self, codes, path, planes) -> np.ndarray:
        """H [N, 3, F, S] float64: the shards' float32 histograms, added."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = codes.sharding
        if not isinstance(sharding, NamedSharding) or sharding.spec[0] is None:
            # one device holds every row
            return np.asarray(self._on_one_device(codes, path, planes),
                              np.float64)
        walk = self._by_shard.get(sharding)
        if walk is None:
            # one program for all the devices (a jit a device would compile
            # `path_hist` once a device: 32 s each on a v5e): every device
            # runs gbt_levelwise's own `path_hist` for a table of its
            # shard's size, and hands back its histogram unreduced
            rows = sharding.spec[0]
            per = _plain.Reference(sharding.shard_shape(codes.shape)[0],
                                   self.F, self.S, self.D)
            walk = jax.jit(jax.shard_map(
                lambda c, p, v: per.path_hist(c, p, v)[None],
                mesh=sharding.mesh, in_specs=(P(rows, None),) * 3,
                out_specs=P(rows), check_vma=False))
            self._by_shard[sharding] = walk
        parts = np.asarray(walk(codes, jax.device_put(path, sharding),
                                jax.device_put(planes, sharding)))
        return parts.astype(np.float64).sum(axis=0)
