"""The meshed tree grower alone, on a four-chip host: `train_trees(...,
mesh=data_mesh(chips))` at whole HIGGS's shape, fed from the devices and
from host arrays, a few trees a call.

Usage (four v5e):  chiprun --chips 4 -- python scripts/mesh_tree_probe.py
                   [--rows 11000000] [--trees 2] [--chips 4] [--host 1]

The data is made on the devices, shard by shard, as the benchmark's driver
`benchmarks/drivers/tree_mesh.py` makes it. One JSON line a call: seconds a
tree (gaps between `progress_cb` stamps), the prologue's and the placement's
spans, the mesh's byte counters, each chip's peak memory. Lines also go to
chiprun_out/mesh_tree_probe.json. PERF.md section 6 (PR 28) has the
readings."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=11_000_000)
    ap.add_argument("--trees", type=int, default=2)
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--host", type=int, default=1,
                    help="also one call from host copies of the arrays")
    ap.add_argument("--seed", type=int, default=2890417733)
    a = ap.parse_args(argv)

    import jax

    from benchmarks.lib import spec
    from shifu_tpu import obs
    from shifu_tpu.parallel.mesh import data_mesh
    from shifu_tpu.train import tree_trainer
    from shifu_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    F, S, D = 28, 33, 6
    mesh = data_mesh(a.chips)
    seed = a.seed % (2**31 - 1)
    drv = spec.load_module("drivers", "tree_mesh")
    t0 = time.perf_counter()
    codes, y, w = jax.block_until_ready(
        drv.make_data(mesh, a.rows, F, S - 1, seed))
    data_s = time.perf_counter() - t0
    cfg = tree_trainer.TreeTrainConfig(
        algorithm="GBT", tree_num=a.trees, max_depth=D, impurity="variance",
        loss="squared", learning_rate=0.05, min_instances_per_node=5,
        min_info_gain=0.0, feature_subset_strategy="ALL",
        valid_set_rate=0.2, max_stats_memory_mb=256, hist_subtraction=True,
        seed=seed)
    lines = [{"data_s": data_s, "rows": a.rows, "chips": a.chips,
              "device": jax.devices()[0].device_kind}]
    print(json.dumps(lines[0]), flush=True)
    inputs = [("device", (codes, y, w))] * a.calls
    if a.host:
        inputs.append(("host", tuple(np.asarray(x) for x in (codes, y, w))))
    for source, (c, yy, ww) in inputs:
        stamps = []
        before = dict(obs.registry().snapshot()["counters"])
        t0 = time.perf_counter()
        res = tree_trainer.train_trees(
            c, yy, ww, [S] * F, [False] * F, ["f%d" % i for i in range(F)],
            cfg, progress_cb=lambda k, t, v: stamps.append(
                (time.perf_counter(), t, v)), mesh=mesh)
        t1 = time.perf_counter()
        after = obs.registry().snapshot()["counters"]
        evs = obs.tracer().between(t0, t1, "train.trees.")
        line = {
            "source": source, "call_s": t1 - t0,
            "tree_s": list(np.diff([t0] + [s[0] for s in stamps])),
            "errors": [s[1:] for s in stamps],
            "spans_s": {e["name"]: e["dur"] * 1e-6 for e in evs},
            "shard_args": [e["args"] for e in evs
                           if e["name"] == "train.trees.shard"],
            "counters": {k: after[k] - before.get(k, 0.0) for k in after
                         if k.startswith(("mesh.", "tree."))
                         and after[k] != before.get(k, 0.0)},
            "memory_peak_bytes": [
                max(st.get("peak_bytes_in_use", 0),
                    st.get("peak_bytes_reserved", 0))
                for st in (d.memory_stats() or {}
                           for d in jax.devices()[:a.chips])],
            "root_feature": int(res.spec.trees[0].feature[0]),
        }
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mesh_tree_probe.json", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
