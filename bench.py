"""The independent copy of the NN cell's FLOP count that
tests/benchmark/test_benchmark_lib.py::test_mlp_flops_match_bench_py holds
benchmarks/lib/work.py equal to. Nothing else may be added to this file; it
goes with that test's read of it at the next `benchmark` issue."""


def _mlp_flops_per_row_epoch(d: int, hidden: list) -> float:
    """Exact training-step matmul FLOPs per row: forward (2/MAC) plus
    backward weight-grad and input-grad (4/MAC), MINUS the first layer's
    input gradient — dL/dx is never computed (inputs need no grad), so
    the textbook 6x-forward count overstates a 1024 -> 2048 x 2 -> 1 net
    by ~11%."""
    sizes = [d] + list(hidden) + [1]
    macs = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return 6.0 * macs - 2.0 * sizes[0] * sizes[1]
