"""What a boosted tree over thousands of one-hot columns needs, counted from
shapes: the yardstick's own arithmetic for `higgs_gbt_255`, beside `work.py`,
whose `tree_min_bytes` does the counting of bytes (38 B a row at 28 features:
a code is a byte at 256 slots whatever the program ships, three bf16 planes,
an int32 node id).

A split level is named by its index, the root 0. Under sibling subtraction a
level below the root reads half the rows; the root reads every row.

The one-hot matmuls are counted WITHOUT the nodes: a row's one-hot over the
`slots` of all columns against its three planes, a multiply and an add each.
A row is in one node a level, so a kernel that brings a block's rows to one
node needs no more, and the share cannot pass 100 % when a later kernel does
that (`rf_work.levels_dot_flops` counts the built nodes too, which such a
kernel would not pay for). At 7,168 columns the matmuls, not HBM, are a
level's floor: 43,008 FLOP a row against 38 B.
"""

from __future__ import annotations

from benchmarks.lib import work

PLANES = 3


def level_rows(n: int, level: int) -> float:
    """The rows split level `level` reads: all at the root, half below."""
    return float(n) if level == 0 else n / 2.0


def levels_min_bytes(n: int, features: int, levels) -> float:
    """The least HBM traffic of the split `levels`, as `work.tree_min_bytes`
    counts a row."""
    row = work.tree_min_bytes(1, features, 1, leaf_pass=False)
    return sum(level_rows(n, lv) * row for lv in levels)


def levels_dot_flops(n: int, slots: int, levels) -> float:
    """The FLOPs of the histogram matmuls of the split `levels`, the nodes
    left out: 2 x 3 planes x `slots` a row the level reads."""
    return sum(2.0 * PLANES * slots * level_rows(n, lv) for lv in levels)


def levels_floor_seconds(n: int, features: int, slots: int, levels,
                         peaks: dict) -> float:
    """The roofline of the tree kernels at the split `levels`: level by
    level the larger of its bytes at the HBM peak and its matmuls at the
    chip's bf16 peak."""
    return sum(max(levels_min_bytes(n, features, [lv])
                   / peaks["hbm_bytes_per_s"],
                   levels_dot_flops(n, slots, [lv])
                   / peaks["bf16_flops_per_s"]) for lv in levels)


def fused_levels(config: dict) -> list:
    """The split levels the fused kernel builds: every level that is not
    among the configuration's `hist_mode_levels`."""
    return [lv for lv in range(config["max_depth"])
            if lv not in config["hist_mode_levels"]]
