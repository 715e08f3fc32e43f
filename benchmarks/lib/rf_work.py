"""What a random forest's tree needs, counted from shapes: the yardstick's
own arithmetic for the forest cells, beside `work.py`, whose
`tree_min_bytes` does the counting of bytes. A forest's component planes are
float32 (whole-number bag counts must stay exact), its codes could travel as
int8: 44 B a row at 28 features where a GBT tree's bf16 planes make 38.

A split level is named by its index, the root 0. Under sibling subtraction a
level below the root reads half the rows and builds half of its `2**level`
nodes; the root reads every row and builds one.
"""

from __future__ import annotations

from benchmarks.lib import work

CODE_BYTES, PLANE_BYTES, PLANES = 1, 4, 3


def forest_tree_min_bytes(n: int, features: int, depth: int) -> float:
    """The least HBM traffic of one tree of the forest, leaf pass and all."""
    return work.tree_min_bytes(n, features, depth, code_bytes=CODE_BYTES,
                               plane_bytes=PLANE_BYTES)


def levels_min_bytes(n: int, features: int, levels) -> float:
    """The least HBM traffic of the split `levels`."""
    def upto(depth):
        return work.tree_min_bytes(
            n, features, depth, code_bytes=CODE_BYTES,
            plane_bytes=PLANE_BYTES, leaf_pass=False) if depth else 0.0
    return sum(upto(lv + 1) - upto(lv) for lv in levels)


def levels_dot_flops(n: int, slots: int, levels) -> float:
    """The FLOPs of the histogram matmuls of the split `levels`: a row's
    one-hot over the `slots` of all columns against its three planes spread
    over the nodes the level builds, a multiply and an add each. The rows
    are those the bytes count."""
    return sum(2.0 * PLANES * slots * (n if lv == 0 else n / 2)
               * max(1, 2 ** lv // 2) for lv in levels)


def levels_floor_seconds(n: int, features: int, slots: int, levels,
                         peaks: dict) -> float:
    """The roofline of the tree kernels at the split `levels`: the larger of
    their bytes at the HBM peak and their matmuls at the chip's bf16 peak.
    That peak and no lower one, although the planes are float32: it is the
    fastest the MXU multiplies, so no kernel can pass it (Mosaic's float32
    dot takes one bf16 pass: PERF.md section 5, PR 34), and one that spends
    the passes of a true float32 product reads a third or a sixth."""
    return max(levels_min_bytes(n, features, levels)
               / peaks["hbm_bytes_per_s"],
               levels_dot_flops(n, slots, levels)
               / peaks["bf16_flops_per_s"])
