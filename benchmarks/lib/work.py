"""What the algorithm needs, counted from shapes: the yardstick's own
arithmetic, whatever the program does to get there.

`mlp_flops_per_row_epoch` is a copy of bench.py's `_mlp_flops_per_row_epoch`
(pinned there against XLA's cost analysis); `tree_min_bytes` is this
benchmark's own.
"""

from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A kind that is not in the table is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in benchmarks/lib/"
                       "peaks.json" % device_kind)
    return table[device_kind]


def mlp_flops_per_row_epoch(d: int, hidden: list, out: int = 1) -> float:
    """Matmul FLOPs one row costs in one full-batch training step: forward
    (2 a MAC), weight gradient and input gradient (4 a MAC), less the first
    layer's input gradient, which nobody needs."""
    sizes = [d] + list(hidden) + [out]
    macs = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return 6.0 * macs - 2.0 * sizes[0] * sizes[1]


def tree_min_bytes(n: int, features: int, depth: int,
                   code_bytes: int = 1, plane_bytes: int = 2,
                   planes: int = 3, node_bytes: int = 4,
                   leaf_pass: bool = True) -> float:
    """The least HBM traffic one level-wise tree of `depth` levels needs:
    every row's codes, component planes and node id are read once at the
    root and once for the leaf pass, and half of them at every other level,
    which is what sibling subtraction leaves. Histogram building does about
    one add a byte read, so this, over the HBM peak, is the tree's floor.
    `leaf_pass=False` counts the split levels alone."""
    row = features * code_bytes + planes * plane_bytes + node_bytes
    row_reads = n * (1.0 + 0.5 * (depth - 1) + (1.0 if leaf_pass else 0.0))
    return row_reads * row
