"""The per-layer reader PR 29 adds, on a stated registry:
`tree_kernel_calls_per_tree` (the program's `tree.kernel.calls` over
`train.trees`); it returns nothing, never 0, where there is nothing to
read."""

import pytest

from benchmarks.lib import spec
from shifu_tpu import obs


@pytest.fixture
def registry():
    obs.reset()
    yield obs.registry()
    obs.reset()


def _read():
    return spec.load_module("layer_metrics", "tree_kernel_calls_per_tree").read


def test_tree_kernel_calls_per_tree(registry):
    read = _read()
    assert read({}) is None
    registry.counter("tree.kernel.calls").inc(2 * 12)
    assert read({}) is None  # a program that does not count its trees
    registry.counter("train.trees").inc(2)
    assert read({}) == pytest.approx(12.0)
    registry.counter("tree.kernel.calls").inc(10 * 12)
    registry.counter("train.trees").inc(10)
    assert read({}) == pytest.approx(12.0)


def test_tree_kernel_calls_per_tree_reads_nothing_on_the_parent(registry):
    """A program from before the counter grows trees and counts its
    histograms, but no kernel calls: the metric is left out, not 0."""
    registry.counter("train.trees").inc(10)
    registry.counter("tree.hist.built").inc(10 * 32)
    assert _read()({}) is None


def test_tree_kernel_calls_per_tree_is_declared_beside_its_neighbour():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    mine = per_layer["tree_kernel_calls_per_tree"]
    built = per_layer["tree_hist_built_per_tree"]
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert mine[key] == built[key], key
