"""The yardstick's arithmetic: trace reduction, work counts, comparison rules,
and BENCHMARK.json against the files it names."""

import importlib.util
import json
import os
import re

import numpy as np
import pytest

from benchmarks.lib import compare, spec, work, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "nn_two_calls.xplane.pb")


# ---- intervals ----

@pytest.mark.parametrize("ivs,merged", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),
])
def test_union(ivs, merged):
    assert xplane.union(ivs) == merged


@pytest.mark.parametrize("merged,lo,hi,want", [
    ([], 0, 10, [(0, 10)]),
    ([(0, 10)], 0, 10, []),
    ([(2, 3), (5, 6)], 0, 10, [(0, 2), (3, 5), (6, 10)]),
    ([(0, 4)], 0, 10, [(4, 10)]),
])
def test_gaps(merged, lo, hi, want):
    assert xplane.gaps(merged, lo, hi) == want


def test_self_times_take_nested_events_out_of_their_parent():
    evs = [("while", 0, 100), ("a", 10, 30), ("b", 30, 60), ("a", 70, 80),
           ("alone", 120, 130)]
    own = xplane.self_times(evs, 0, 200)
    assert own == {"while": 40, "a": 30, "b": 30, "alone": 10}
    assert sum(own.values()) == 110  # the union's length


def test_self_times_clip_to_the_window():
    own = xplane.self_times([("a", 0, 100), ("b", 110, 150)], 40, 120)
    assert own == {"a": 60, "b": 10}


@pytest.mark.parametrize("gap,want", [
    ((12, 14), "bench.inner"), ((3, 5), "bench.call"), ((30, 31),
                                                        "between calls")])
def test_gap_is_named_by_the_innermost_span(gap, want):
    spans = [("bench.call", 0, 20), ("bench.inner", 10, 15)]
    assert xplane.label_gap(gap, spans) == want


def test_summarize_synthetic_two_chips():
    device = {"/device:TPU:0": [("k", 0, 50), ("k", 60, 100)],
              "/device:TPU:1": [("k", 0, 100)]}
    spans = [("bench.call", 0, 100)]
    r = xplane.summarize(device, spans, chips=2)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(95e-9)  # mean of 90 and 100
    assert r["idle_gaps"][0][1] == pytest.approx(10e-9)
    assert r["idle_gaps"][0][0].startswith("bench.call")


def test_no_device_plane_reads_nothing():
    r = xplane.summarize({}, [], chips=1)
    assert r["busy_s"] == 0.0 and r["window_s"] == 0.0


# ---- the recorded trace (one v5e, higgs_nn.train_fullbatch, two calls) ----

@pytest.fixture(scope="module")
def recorded():
    return xplane.read_planes(FIXTURE)


def test_fixture_planes(recorded):
    device, spans = recorded
    assert list(device) == ["/device:TPU:0"]
    assert len(device["/device:TPU:0"]) == 5260
    assert [s[0] for s in spans] == ["bench.call", "bench.call"]


def test_fixture_busy_union_against_a_raster(recorded):
    device, spans = recorded
    r = xplane.summarize(device, spans)
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # a second way to the union: paint microseconds
    cells = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for _, s, e in device["/device:TPU:0"]:
        a, b = int((max(s, lo) - lo) // 1000), int((min(e, hi) - lo) // 1000)
        cells[a:b + 1] = True
    assert r["busy_s"] == pytest.approx(cells.sum() * 1e-6, rel=2e-3)
    assert r["busy_s"] == pytest.approx(2.409710458, rel=1e-9)
    assert r["window_s"] == pytest.approx(2.437667626, rel=1e-9)


def test_fixture_idle_share_and_gaps(recorded):
    r = xplane.summarize(*recorded)
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert idle == pytest.approx(1.14688, rel=1e-4)
    name, longest = r["idle_gaps"][0]
    assert name.startswith("bench.call (longest of 113")
    assert longest == pytest.approx(0.004612377, rel=1e-6)


def test_fixture_own_times_add_up_to_busy(recorded):
    r = xplane.summarize(*recorded)
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"],
                                                          rel=1e-9)
    top_name, top_s = r["device_ops"][0]
    assert top_name.startswith("%fusion.127") and "{" not in top_name
    assert top_s == pytest.approx(0.233033470, rel=1e-6)
    # the while loop that wraps every epoch owns next to nothing itself
    wh = [v for k, v in r["op_seconds"].items() if k.startswith("%while")]
    assert wh and max(wh) < 0.01 * r["busy_s"]


def test_idle_reader_returns_nothing_without_a_trace():
    reader = spec.load_module("layer_metrics", "device_idle_pct.nn")
    assert reader.read({"trace": None}) is None
    assert reader.read({"trace": {"busy_s": 0.0, "window_s": 0.0}}) is None
    assert reader.read({"trace": {"busy_s": 9.0, "window_s": 10.0}}) == \
        pytest.approx(10.0)


# ---- work counts and peaks ----

def test_mlp_flops_match_bench_py():
    assert work.mlp_flops_per_row_epoch(28, [300] * 5, 1) == 2_195_400
    path = os.path.join(spec.ROOT, "bench.py")
    s = importlib.util.spec_from_file_location("_bench_for_test", path)
    bench = importlib.util.module_from_spec(s)
    s.loader.exec_module(bench)
    for d, hidden in ((28, [300] * 5), (30, [50]), (7, [])):
        assert work.mlp_flops_per_row_epoch(d, hidden) == \
            bench._mlp_flops_per_row_epoch(d, hidden)


def test_tree_min_bytes_higgs():
    # 4.5 passes over the rows at 28 int8 codes + 3 bf16 planes + one int32
    assert work.tree_min_bytes(5_500_000, 28, 6) == 4.5 * 5_500_000 * 38
    assert work.tree_min_bytes(5_500_000, 28, 6) == 940_500_000
    assert work.tree_min_bytes(1000, 1, 1) == 2.0 * 1000 * 11


def test_peaks_table():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_mfu_readers_on_stated_rates():
    cell = spec.Cell("higgs_nn.train_fullbatch")
    nn = spec.load_module("layer_metrics", "nn_mfu_pct")
    v = nn.read({"cell": cell, "rate": 22.5e6, "device_kind": "TPU v5 lite"})
    assert v == pytest.approx(100 * 2_195_400 * 22.5e6 / 197e12)
    cell = spec.Cell("higgs_gbt.train_levelwise")
    gbt = spec.load_module("layer_metrics", "gbt_mfu_pct")
    v = gbt.read({"cell": cell, "rate": 5.5e6 * 4, "device_kind":
                  "TPU v5 lite"})  # four trees a second
    assert v == pytest.approx(100 * (940.5e6 / 819e9) * 4)


def test_tree_ms_reader_times_first_tree_from_its_call():
    class D:
        unit_ends = [1.3, 1.5, 2.4, 2.6]
    reader = spec.load_module("layer_metrics", "gbt_tree_ms_p95")
    v = reader.read({"driver": D, "calls": [(1.0, 1.6), (2.0, 2.7)]})
    assert v == pytest.approx(400.0)  # gaps 300, 200, 400, 200


# ---- comparison rules ----

def test_worst_leaf_norm_gap_uses_the_median_floor():
    ref = [np.ones(100), np.ones(100), np.full(4, 1e-6)]
    got = [np.ones(100) * 1.01, np.ones(100), np.full(4, 3e-6)]
    # leaf 0: 1 %; the tiny leaf is held against the median leaf's norm
    assert compare.worst_leaf_norm_gap(got, ref) == pytest.approx(0.01)
    assert compare.worst_leaf_norm_gap(got, ref, skip=[True, False, False]) \
        < 1e-6


def test_norm_gap_is_gap_of_norms_not_norm_of_gap():
    ref, got = [np.array([1.0, 0.0])], [np.array([0.0, 1.0])]
    assert compare.worst_leaf_norm_gap(got, ref) == 0.0


@pytest.mark.parametrize("value,limit,ok", [
    (0.5, 1.0, True), (1.0, 1.0, True), (1.5, 1.0, False),
    (float("nan"), 1.0, False), (float("inf"), 1.0, False),
    (5.0, None, True), (0.0, 0.0, True), (1e-9, 0.0, False)])
def test_verdict(value, limit, ok):
    assert compare.verdict({"x": {"value": value, "limit": limit}}) is ok


# ---- the entry ----

def test_cli_refuses_to_run_without_a_chip(capsys):
    from benchmarks import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "higgs_nn.train_fullbatch", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no result line


def test_cli_refuses_an_unknown_cell():
    from benchmarks import run

    with pytest.raises(SystemExit):
        run.run_cell("no.such_cell", 1, 1.0, False, require_chip=False)


# ---- BENCHMARK.json against its files ----

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)


def test_every_name_has_its_file(bench):
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(bench["paths"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        spec.load_module("references", cfg["reference"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        assert cell.traffic["traffic"] == w["traffic"]
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "drivers", cell.traffic["driver"] + ".py"))
        assert len(cell.metrics("end_to_end")) >= 2
        assert len(cell.metrics("per_layer")) >= 1
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert hasattr(spec.load_module("layer_metrics", m["name"]), "read")
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)
