"""What one row of a wide-and-deep training step needs, counted from shapes:
the yardstick's own arithmetic for the WDL cells, beside `work.py`.

The lookups move bytes and do no arithmetic, so the FLOPs are the tower's and
the wide dense dot's alone; `tests/benchmark/test_wdl_readers.py` holds the
count against XLA's own for the same matmuls.
"""

from __future__ import annotations


def wdl_macs_per_row(n_dense: int, n_cat: int, embed: int,
                     hidden: list) -> dict:
    """Multiply-adds one row costs, by pass: `forward` (the tower on
    [dense, embedding rows] and the wide dense dot), `weight_grad` (the
    same again), `input_grad` (every layer's but the first, and of the first
    only the embedding columns: nobody needs a gradient of the data)."""
    sizes = [n_dense + n_cat * embed] + list(hidden) + [1]
    tower = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    forward = tower + n_dense
    input_grad = tower - n_dense * sizes[1]
    return {"forward": forward, "weight_grad": forward,
            "input_grad": input_grad}


def wdl_flops_per_row_epoch(n_dense: int, n_cat: int, embed: int,
                            hidden: list) -> float:
    """Matmul FLOPs (2 a MAC) one row costs in one full-batch step."""
    return 2.0 * sum(wdl_macs_per_row(n_dense, n_cat, embed,
                                      hidden).values())

