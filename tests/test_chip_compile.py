"""Ask the chip's compiler, without the chip: the main path's kernels at
bench widths compile for a DESCRIBED TPU v5e (on-chip-measurement guide,
section 2). Interpret mode (tests/test_hist_pallas.py) cannot see what
Mosaic refuses — scoped-VMEM overflow, unaligned slices — and PR 22 found
the fused kernel refused at every level past L = 2 that way.

The topology is described INSIDE a module-scoped fixture, never at
import / collection: only one process may load libtpu, and every xdist
worker imports every test file. Keep these tests in this one file (a
second file can land on another worker, whose fixture then skips).
Nothing here runs on a device; a compile that passes is not a chip run.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shifu_tpu.ops import hist_pallas as hp  # noqa: E402
from shifu_tpu.train import tree_trainer as tt  # noqa: E402

# 30 numeric columns; 20 numeric + 10 categorical of 65 slots; 180 numeric
# + 19 categorical of 65 slots + one of 2,001; and the benchmark's GBT
# cells' layout (benchmarks/configs/higgs_gbt*.json)
LAYOUTS = {
    "gbt": ([33] * 30, [False] * 30),
    "rf": ([33] * 20 + [65] * 10, [False] * 20 + [True] * 10),
    "gbt_wide": ([33] * 180 + [65] * 19 + [2001],
                 [False] * 180 + [True] * 20),
    "higgs": ([33] * 28, [False] * 28),
}
N_ROWS = 65_536
CELL_ROWS = 5_500_000  # higgs_gbt.train_levelwise's, one chip
L_MAX = tt._FUSED_SCAN_L_CAP  # the largest level the static rule fuses


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no such topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable can be written to the persistent cache
    # but not read back without a chip: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _row_args(one_chip, F, rows=N_ROWS):
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    return (s((rows, F), jnp.int32), s((rows,), jnp.float32),
            s((rows,), jnp.float32), s((rows,), jnp.int32),
            s((rows,), jnp.bool_))


def _compiled_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _code_operand(one_chip, lay, rows=N_ROWS):
    """The kernels' hoisted code operand, `[F, n]` (hp.make_codes8_fn)."""
    return _shape(one_chip, (len(lay.slots), rows), hp.code_dtype(lay))


def _lane_padded_code_ops(text):
    """Instructions that write a code operand with the rows on the
    sublanes, `s8[n, 13 | 15]` chunk cuts or the `s32[n, 28]` matrix: the
    `pad`, `copy` and `pad_slice_fusion` the kernels' `[n, nf]` operand cost
    once a tree until PR 38 (128 and 512 B a row in HBM for 13 to 28
    codes). Nothing makes one now: the operand is `[F, n]`, made once a
    call outside the tree program."""
    return re.findall(
        r"= s(?:8\[\d+,(?:13|15)\]|32\[\d+,28\])\S* "
        r"(?:pad|copy|fusion)\([^\n]*", text)


def _code_operand_writes(text):
    """The instructions of a tree program that write an `[F, n]` code
    operand in HBM, by opcode: one `pad` to whole blocks where the rows
    are not whole blocks (the first level's; every level reads it), and
    nothing else: no clip, cast, transpose or `copy`, which
    `tt._get_codes8_program` did once a call. A write into memory space
    `S(1)` is left out: at N_ROWS the operand is 1.8 MB and XLA prefetches
    it into VMEM whole (`copy-done` of `s8[28,65536]{...S(1)}`)."""
    return [op for shape, op in re.findall(
        r"= (s8\[28,\d+\]\S*) (\w[\w-]*)\(", text)
        if op != "parameter" and "S(1)" not in shape]


# higgs at wmax 1,024 is ONE chunk of 28 features side by side (924 of
# 1,024 columns), L_MAX the widest level the mesh4 cell builds
@pytest.mark.parametrize("layout,L,lowp", [
    ("gbt", 1, False), ("gbt", 1, True), ("gbt", 64, False),
    ("gbt", 64, True), ("higgs", 1, True), ("higgs", L_MAX, True)])
def test_hist_kernel_compiles(one_chip, layout, L, lowp):
    slots, is_cat = LAYOUTS[layout]
    lay = tt.make_layout(slots, is_cat)
    fn = hp.make_pallas_hist_fn(L, lay, low_precision=lowp)
    compiled = jax.jit(fn).lower(*_row_args(one_chip, len(slots))).compile()
    assert _compiled_kernels(compiled) == hp.kernel_calls(lay, False, L)
    if layout == "higgs":
        assert [(ch.w, ch.f_hi - ch.f_lo) for ch in hp._chunks(lay)] == [
            (1024, 28)]


def test_hist_kernel_compiles_at_rf_deepest_level(one_chip):
    """RF's deepest level (depth 10: 512 nodes), NATIVE three-class
    counts on f32 planes: the [512, blk] node one-hot at its largest,
    past the stacked LHS (three dots) and past the fused scan; its two
    chunks (1,310 columns) in one call, 20 MB of accumulators."""
    slots, is_cat = LAYOUTS["rf"]
    lay = tt.make_layout(slots, is_cat)
    fn = hp.make_pallas_hist_fn(512, lay, n_classes=3)
    compiled = jax.jit(fn).lower(*_row_args(one_chip, len(slots))).compile()
    assert len(hp._chunks(lay)) == 2
    assert _compiled_kernels(compiled) == hp.kernel_calls(lay, False, 512,
                                                          3) == 1


# f32 planes are RF's, bf16 planes are GBT's (tree_trainer._low_precision)
@pytest.mark.parametrize("layout,lowp", [
    ("gbt", True), ("rf", False), ("gbt_wide", True), ("higgs", True)])
@pytest.mark.parametrize("L", [1, L_MAX], ids=["Lmin", "Lmax"])
def test_fused_kernels_compile_where_the_rule_admits(one_chip, layout,
                                                     lowp, L):
    """Every call the fused entry makes at L, each over a group of chunks
    (`hp._groups`: one call a level for each of these layouts, gbt_wide's
    19 chunks included), with the scan a loop over the group's chunks at
    the widest one's width."""
    slots, is_cat = LAYOUTS[layout]
    lay = tt.make_layout(slots, is_cat)
    chunks = hp._chunks(lay, hp._SCAN_W_CAP)
    assert max(ch.w for ch in chunks) <= hp._SCAN_W_CAP
    blk, C = hp.blk_setting(), 3
    comp_dt = jnp.bfloat16 if lowp else jnp.float32
    scan_key = ("variance" if lowp else "entropy", 1, 0.0, 0)
    groups = hp._groups(chunks, L, C, True)
    calls, args = [], []
    for first, end in groups:
        calls.append(hp._build_call(lay.key, hp._SCAN_W_CAP, (first, end), L,
                                    C, blk, lowp, scan_key, False))
        args.append((
            _code_operand(one_chip, lay),
            _shape(one_chip, (C, N_ROWS), comp_dt),
            _shape(one_chip, (1, N_ROWS), jnp.int32),
            _shape(one_chip, (end - first, 1,
                              max(ch.w for ch in chunks[first:end])),
                   jnp.float32)))

    def every_call(*group_args):
        return [call(*a) for call, a in zip(calls, group_args)]

    compiled = jax.jit(every_call).lower(*args).compile()
    assert _compiled_kernels(compiled) == len(groups)
    assert len(groups) == hp.kernel_calls(lay, True, L)


@pytest.mark.parametrize("layout", ["gbt", "gbt_wide"])
def test_fused_level_entry_compiles(one_chip, layout):
    """The entry the grower calls, whole: every chunk's kernel plus the
    XLA epilogue, and on gbt_wide the XLA fallback scan of the
    2,001-slot column (too wide for one in-kernel chunk)."""
    slots, is_cat = LAYOUTS[layout]
    lay = tt.make_layout(slots, is_cat)
    fn = hp.make_fused_level_fn(1, lay, "variance", 1, 0.0,
                                low_precision=True)
    codes, labels, weights, node, active = _row_args(one_chip, len(slots))
    compiled = jax.jit(fn).lower(
        codes, _code_operand(one_chip, lay), labels, weights,
        node, active, _shape(one_chip, (lay.T,), jnp.bool_)).compile()
    assert _compiled_kernels(compiled) == hp.kernel_calls(lay, True, 1)
    assert hp.wide_features(lay, hp._SCAN_W_CAP) == (
        [199] if layout == "gbt_wide" else [])


# The kernel's three per-row operands ride with the rows along the lanes,
# [C, n] planes, [1, n] node ids and, since PR 38, [F, n] codes: a level's
# temporaries are the row operands in whole blocks and little else, 44 B a
# row behind the hoisted code operand (the fused entry, as the whole-tree
# program calls it) and 64 B where the entry turns `codes [n, F]` itself
# (the hist-mode entry, as the `tree.hist` programs call it). With the
# codes a row to a sublane, `[n, nf]`, they read 268 and 524 B (128 B a
# row for each int8 chunk, 512 B for the int32 matrix; PR 31), and 1,024
# and 1,280 B with all three operands so (described-chip compile). At
# N_ROWS the operands sit in VMEM and the reading is 0, so this one asks
# at the cell's rows (shapes only: nothing is allocated).
@pytest.mark.parametrize("entry,L,cap", [
    ("fused", 1, 60), ("hist", L_MAX, 80)])
def test_level_temporaries_a_row_at_the_cells_rows(one_chip, entry, L, cap):
    slots, is_cat = LAYOUTS["higgs"]
    lay = tt.make_layout(slots, is_cat)
    rows = CELL_ROWS
    codes, labels, weights, node, active = _row_args(
        one_chip, len(slots), rows)
    if entry == "fused":
        fn = hp.make_fused_level_fn(L, lay, "variance", 5, 0.0,
                                    low_precision=True)
        args = (codes, _code_operand(one_chip, lay, rows), labels,
                weights, node, active, _shape(one_chip, (lay.T,), jnp.bool_))
    else:
        fn = hp.make_pallas_hist_fn(L, lay, low_precision=True)
        args = (codes, labels, weights, node, active)
    compiled = jax.jit(fn).lower(*args).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < cap * rows, temp / rows
    assert _lane_padded_code_ops(compiled.as_text()) == []


@pytest.mark.parametrize("meshed,want", [(False, 6), (True, 6)],
                         ids=["fused", "hist-mode"])
def test_whole_tree_program_kernel_count_at_higgs(one_chip, monkeypatch,
                                                  meshed, want):
    """The whole-tree program of the benchmark's GBT cells (28 x 33 slots,
    depth 6, subtraction on, bf16 planes): a call a built level, its 2
    chunks in one under the fused scan (2 x 6 calls until PR 40), and its 1
    where every level runs the hist-mode kernel, as
    the meshed grower's do (here on one described chip, without the mesh:
    `_pallas_state` is steered in the test, as it is off the chip anyway).
    The counter's arithmetic and the compiler's count agree. Both take
    the hoisted `[F, n]` code operand as their second argument, as the
    one-chip and the meshed program do, and in neither is a lane-padded
    `[n, nf]` form of it left in the executable, nor any write of the
    operand (65,536 rows are whole blocks: not even its pad)."""
    slots, is_cat = LAYOUTS["higgs"]
    lay = tt.make_layout(slots, is_cat)
    D, sub_levels = 6, (False,) + (True,) * 6
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (True, False, not meshed))
    key_before = set(tt._PROGRAMS)
    try:
        prog = tt._get_tree_program(D, lay, "variance", 5, 0.0,
                                    sub_levels=sub_levels, lowp=True)
    finally:
        for k in set(tt._PROGRAMS) - key_before:
            del tt._PROGRAMS[k]  # built under a steered state: never reuse
    codes, labels, weights, _node, _active = _row_args(one_chip, len(slots))
    args = (codes, labels, weights, _shape(one_chip, (lay.T,), jnp.bool_))
    args = (codes, _code_operand(one_chip, lay)) + args[1:]
    compiled = prog.fn.lower(*args).compile()
    assert _compiled_kernels(compiled) == want
    assert tt._tree_kernel_calls(D, lay, sub_levels) == want
    assert _lane_padded_code_ops(compiled.as_text()) == []
    assert _code_operand_writes(compiled.as_text()) == []


def test_forest_tree_program_at_the_rf_cells_size(one_chip, monkeypatch):
    """The whole-tree program of `higgs_rf.train_depth10`
    (benchmarks/configs/higgs_rf.json: 5,500,000 rows x 28 x 33 slots, depth
    10, f32 planes, subtraction at every level) for the described v5e: 10
    Mosaic calls, 7 `tree_fused_level` (levels 0 to 6, built halves of 1 to
    32 nodes, the 2 chunks of a level in one call: 14 calls until PR 40)
    and 3 `tree_hist` (levels 7 to 9, built halves of 64 to 256 nodes, one
    chunk); no `gather` that hands out a value a row
    (PR 34's program held two, `built_lsb[node >> 1]` at 128 and 256 parents:
    the build mask comes out of `route_rows` since PR 35); no `pad`, `copy`
    or `pad_slice_fusion` of a lane-padded code operand; temporaries under
    110 B a row (88 since PR 38: 0.48 GB; 1,050 before, 5.78 GB, of which
    the int32 code matrix's row-major `copy` and its `pad` for the
    hist-mode entry were 2.8 GB each) and, with the arguments, under 10 %
    of the 15.75 GiB a v5e hands out (8.3 %). About 40 s on the CPU."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "higgs_rf.json")) as f:
        c = json.load(f)
    rows, D = c["rows"], c["max_depth"]
    assert (rows, D, c["features"], c["slots_per_feature"]) == (
        5_500_000, 10, 28, 33)
    lay = tt.make_layout([c["slots_per_feature"]] * c["features"],
                         [False] * c["features"])
    cfg = tt.TreeTrainConfig(
        algorithm="RF", tree_num=c["trees_per_call"], max_depth=D,
        max_stats_memory_mb=c["max_stats_memory_mb"],
        hist_subtraction=c["hist_subtraction"])
    batch_cap = tt._node_batch_size(lay.T, cfg.max_stats_memory_mb)
    assert 2 ** D <= batch_cap  # the whole-tree program, not build_tree
    sub_levels, acc64 = tt._sub_plan(cfg, batch_cap)
    assert all(sub_levels[1:D]) and not tt._low_precision(cfg)
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (True, False, True))
    key_before = set(tt._PROGRAMS)
    try:
        prog = tt._get_tree_program(
            D, lay, c["impurity"], c["min_instances_per_node"],
            c["min_info_gain"], sub_levels=sub_levels, acc64=acc64,
            lowp=False)
    finally:
        for k in set(tt._PROGRAMS) - key_before:
            del tt._PROGRAMS[k]  # built under a steered state: never reuse
    codes, labels, weights, _node, _active = _row_args(
        one_chip, c["features"], rows)
    compiled = prog.fn.lower(
        codes, _code_operand(one_chip, lay, rows), labels, weights,
        _shape(one_chip, (lay.T,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert _lane_padded_code_ops(text) == []
    # 5,500,000 rows are 416 short of whole blocks: the one pad, no more
    assert _code_operand_writes(text) == ["pad"]
    # each call is an instruction named after its kernel
    names = re.findall(r"%(tree_fused_level|tree_hist)[\w.]* = ", text)
    assert (names.count("tree_fused_level"), names.count("tree_hist")) == (
        7, 3), names
    assert _compiled_kernels(compiled) == 10
    assert tt._tree_kernel_calls(D, lay, sub_levels, None, cfg.n_classes) \
        == 10
    assert re.findall(r"= \w+\[%d\][^\n=]* gather\(" % rows, text) == []
    # the pattern's opcode: 93 gathers, none per row (189 while the XLA
    # scan reordered its planes and gathered its segment bounds by column)
    assert len(re.findall(r" gather\(", text)) > 80
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 110 * rows, \
        mem.temp_size_in_bytes / rows
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < 0.10 * 15.75 * 2**30)


def test_nn_train_step_compiles_at_small_width(one_chip):
    from shifu_tpu.models.nn import flatten_params, init_params
    from shifu_tpu.train import nn_trainer as nt

    rows, d = 1_000_000, 30  # 1,000,000 x 30 numeric, 30 -> [50] -> 1
    cfg = nt.NNTrainConfig(hidden_nodes=[50], activations=["tanh"],
                           mixed_precision=True)
    flat0, shapes = flatten_params(
        init_params([d, 50, 1], seed=0, init=cfg.weight_init))
    program, init_state = nt._get_program(cfg, shapes, rows)
    flat = jnp.asarray(flat0)
    carry = (flat, init_state(flat0.size), jnp.int32(0), jnp.float32(0.1),
             jnp.float32(np.inf), flat, jnp.int32(0),
             jnp.zeros((), dtype=bool), jnp.float32(0.0), jnp.float32(0.0))
    row = jax.ShapeDtypeStruct((rows,), jnp.float32)
    args = (carry, jnp.int32(5),
            jax.ShapeDtypeStruct((rows, d), jnp.float32), row, row, row,
            jax.random.PRNGKey(0), jnp.float32(1.0))
    args = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype), args)
    compiled = program.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_wdl_program_compiles_at_the_criteo_cells_size(one_chip):
    """The wide-and-deep program of `criteo_wdl.train_fullbatch`
    (benchmarks/configs/criteo_wdl.json: 2,865,039 rows x 13 dense + 26
    codes, tables of min(c, 10000) + 1 rows): it fits one v5e with the
    check's reference beside it (temporaries and arguments under 70 % of
    the 15.75 GiB a v5e hands out), its temporaries stay under 3.0 KB a row
    (2,774 B since PR 33: 7.95 GB, the 26 gathered `f32[n, 9]` planes and
    their concatenation; 1,844 B in PR 32), and XLA lowers the 52 lookups
    a row to 26 `gather`, one a field for its embedding row and its wide
    weight together, and their transposes to 26 `scatter` (PR 32's two
    lookups a field were 45 `gather`, the 7 wide tables of 3 to 27 rows
    selects, and 52 `scatter`). A PR that changes how the lookups are
    lowered changes these counts, and says so here."""
    import json
    import os
    import re

    from shifu_tpu.models.wdl import flatten_wdl, init_wdl_params
    from shifu_tpu.train import wdl_trainer as wt

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "criteo_wdl.json")) as f:
        c = json.load(f)
    rows, vocab = c["rows"], c["vocab_sizes"]
    assert (rows, sum(vocab)) == (2_865_039, 119_915)
    tpl = init_wdl_params(c["dense_columns"], vocab, c["embed_outputs"],
                          c["hidden_nodes"])
    n_flat = flatten_wdl(tpl).size
    assert n_flat == c["parameters"]
    key_before = set(wt._PROGRAMS)
    program, _init = wt._get_program(wt.WDLTrainConfig(), tpl)
    for k in set(wt._PROGRAMS) - key_before:
        del wt._PROGRAMS[k]
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    flat, row = s((n_flat,), jnp.float32), s((rows,), jnp.float32)
    f32, i32 = s((), jnp.float32), s((), jnp.int32)
    carry = (flat, {"m": flat, "v": flat}, i32, f32, flat, i32,
             s((), jnp.bool_), f32, f32)
    compiled = program.lower(
        carry, i32, s((rows, c["dense_columns"]), jnp.float32),
        s((rows, len(vocab)), jnp.int32), row, row, row, f32, f32).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 3000 * rows, \
        mem.temp_size_in_bytes / rows
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            < 0.70 * 15.75 * 2**30)
    text = compiled.as_text()
    assert len(re.findall(r" gather\(", text)) == 26
    assert len(re.findall(r" scatter\(", text)) == 26


def test_wdl_program_with_the_onehot_kernel_at_the_criteo_cells_size(
        one_chip, monkeypatch):
    """The same program as the chip builds it (`_onehot_plan` finds the
    kernel on): the 14 tables of at most 4,096 rows take ONE
    `wdl_onehot_grad` call (ops/onehot_grad_pallas.py) in place of their
    scatter-adds, so the program holds 26 `gather` and 12 `scatter`; the
    kernel's codes operand is made before the epoch loop, and the
    temporaries stay under 3.0 KB a row (2,783 B: the `[14, n]` codes)."""
    import json
    import os

    from shifu_tpu.models.wdl import flatten_wdl, init_wdl_params
    from shifu_tpu.train import wdl_trainer as wt

    monkeypatch.setattr(hp, "pallas_active", lambda: (True, False))
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "criteo_wdl.json")) as f:
        c = json.load(f)
    rows, vocab = c["rows"], c["vocab_sizes"]
    tpl = init_wdl_params(c["dense_columns"], vocab, c["embed_outputs"],
                          c["hidden_nodes"])
    n_flat = flatten_wdl(tpl).size
    key_before = set(wt._PROGRAMS)
    program, _init = wt._get_program(wt.WDLTrainConfig(), tpl)
    for k in set(wt._PROGRAMS) - key_before:
        del wt._PROGRAMS[k]
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    flat, row = s((n_flat,), jnp.float32), s((rows,), jnp.float32)
    f32, i32 = s((), jnp.float32), s((), jnp.int32)
    carry = (flat, {"m": flat, "v": flat}, i32, f32, flat, i32,
             s((), jnp.bool_), f32, f32)
    compiled = program.lower(
        carry, i32, s((rows, c["dense_columns"]), jnp.float32),
        s((rows, len(vocab)), jnp.int32), row, row, row, f32, f32).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 3000 * rows, \
        mem.temp_size_in_bytes / rows
    text = compiled.as_text()
    assert len(re.findall(r" gather\(", text)) == 26
    assert len(re.findall(r" scatter\(", text)) == 12
    assert text.count('"kernel":"wdl_onehot_grad"') == 1
    # the codes operand `s32[14, n]` is made outside the loop's body
    entry = text[text.index("\nENTRY "):]
    assert re.search(r"s32\[14,%d\]" % rows, entry)


@pytest.mark.parametrize("vocab, width, taken", [
    ([4000] * 60, 9, 50),
    ([100] * 160, 9, 128),
    ([4, 5, 11, 16, 19, 25, 28, 106, 306, 584, 634, 1461, 2174, 3195], 17,
     14),
], ids=["60_near_the_cap", "160_one_tile", "criteo_small_embed16"])
def test_onehot_kernel_compiles_where_the_plan_takes_it(one_chip, vocab,
                                                         width, taken):
    """Whatever the plan takes (`onehot_grad_pallas.small_fields`: the
    smallest tables while `call_vmem` fits `VMEM_BUDGET`, `FIELDS_CAP` at
    most) compiles: many tables near the cap (a 26 MB accumulator window),
    many one-tile tables (the unrolled body at its most fields), and the
    Criteo cell's 14 at `EmbedOutputs` 16. The compiler refuses a call
    whose window passes the v5e's 128 MiB of VMEM."""
    from shifu_tpu.ops import onehot_grad_pallas as og

    fields = og.small_fields(vocab, width)
    assert len(fields) == taken
    v = tuple(vocab[f] for f in fields)
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    compiled = jax.jit(lambda c, *p: og.table_grads(c, list(p), v)).lower(
        s((len(v), N_ROWS), jnp.int32),
        *[s((width, N_ROWS), jnp.float32)] * len(v)).compile()
    assert compiled.as_text().count('"kernel":"wdl_onehot_grad"') == 1


def test_onehot_kernel_compiles_under_vmap(one_chip):
    """`train_wdl_bagged`'s form: the members' cotangents on a leading axis,
    the codes shared; `pallas_call`'s batching rule makes it one call."""
    from shifu_tpu.ops import onehot_grad_pallas as og

    v, M = (5, 130, 4096), 3
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    fn = jax.vmap(lambda c, *p: og.table_grads(c, list(p), v),
                  in_axes=(None,) + (0,) * len(v))
    compiled = jax.jit(fn).lower(
        s((len(v), N_ROWS), jnp.int32),
        *[s((M, 9, N_ROWS), jnp.float32)] * len(v)).compile()
    assert compiled.as_text().count('"kernel":"wdl_onehot_grad"') == 1


def test_levels_beyond_the_rule_go_to_the_hist_kernel(monkeypatch):
    """The static rule, as the grower applies it: levels with
    2**d <= _FUSED_SCAN_L_CAP build the fused kernel (chunked to
    _SCAN_W_CAP), deeper ones the hist-mode kernel + XLA scan. Builders
    only — nothing is traced or compiled here."""
    fused_L, hist_L = [], []
    monkeypatch.setattr(hp, "pallas_active", lambda: (True, False))
    monkeypatch.setattr(
        hp, "make_fused_level_fn",
        lambda L, *a, **k: fused_L.append(L) or (lambda *x: None))
    monkeypatch.setattr(
        hp, "make_pallas_hist_fn",
        lambda L, *a, **k: hist_L.append(L) or (lambda *x: None))
    lay = tt.make_layout([33] * 7, [False] * 7)  # a layout no test shares
    key_before = set(tt._PROGRAMS)
    try:
        tt._get_tree_program(8, lay, "variance", 1, 0.0, lowp=True)
    finally:
        for k in set(tt._PROGRAMS) - key_before:
            del tt._PROGRAMS[k]  # built from stubs: never reuse
    assert fused_L == [1, 2, 4, 8, 16, 32] and L_MAX == 32
    assert hist_L == [64, 128]
    assert hp._SCAN_W_CAP == 512


# 255 bins (higgs_gbt_255): more than 128 slots a column makes the code
# operand int32, a block of it one sublane tile of 8 features, and 256 slots
# fill two lanes' worth of columns each: 2 features a chunk under the fused
# scan's cap, 4 in hist mode, with whole 256-column segments in the [W, W]
# scan. One chunk of each kind at the cell's 11,000,000 rows and its widest
# level of that mode (a built half of 32 nodes fused, of 64 in hist mode),
# on bf16 planes. Not the whole-tree program (105 such calls): it takes
# 160 s to compile here, PERF.md section 4 has its reading. The temporaries
# are the row operands in whole blocks: 10 B a row of planes and node ids,
# and the code operand's pad (8 B and 16 B a row for 2 and 4 int32 codes;
# 11,000,000 rows are not whole blocks of 512).
@pytest.mark.parametrize("entry,features,L,cap", [
    ("fused", 2, L_MAX, 40), ("hist", 4, 2 * L_MAX, 60)])
def test_one_chunk_of_256_slot_columns_at_whole_higgs(one_chip, entry,
                                                      features, L, cap):
    rows = 11_000_000
    lay = tt.make_layout([256] * features, [False] * features)
    assert hp.code_dtype(lay) == np.int32
    codes, labels, weights, node, active = _row_args(one_chip, features,
                                                     rows)
    operand = _code_operand(one_chip, lay, rows)
    assert operand.dtype == jnp.int32 and operand.shape == (features, rows)
    if entry == "fused":
        chunks = hp._chunks(lay, hp._SCAN_W_CAP)
        fn = hp.make_fused_level_fn(L, lay, "variance", 100, 0.0,
                                    low_precision=True)
        args = (codes, operand, labels, weights, node, active,
                _shape(one_chip, (lay.T,), jnp.bool_))
    else:
        chunks = hp._chunks(lay)
        fn = hp.make_pallas_hist_fn(L, lay, low_precision=True)
        args = (codes, labels, weights, node, active, operand)
    assert [(ch.w, ch.f_hi - ch.f_lo) for ch in chunks] == [
        (256 * features, features)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _compiled_kernels(compiled) == 1
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < cap * rows, temp / rows


# The whole 255-bin layout (higgs_gbt_255: 28 x 256 slots, all 11,000,000
# rows, bf16 planes) at the widest level of each mode, one call a level: the
# 14 fused chunks at a built half of 32 nodes (37 MB of VMEM by `_call_vmem`,
# the most any cell asks of the group rule) and the 7 hist-mode chunks at 64.
# 17 s to compile here where the 14 calls of PR 39 took 55; a scan unrolled
# over the 14 chunks took 1,003 s (PERF.md, section 6, PR 40). The
# temporaries are the operands in whole blocks: the `[28, n]` int32 code
# operand's pad (11,000,000 rows are not whole blocks of 512), 112 B a row,
# and the planes and node ids.
@pytest.mark.parametrize("fused,L", [(True, L_MAX), (False, 2 * L_MAX)],
                         ids=["fused", "hist"])
def test_a_level_at_256_slots_is_one_call_at_whole_higgs(one_chip, fused, L):
    rows = 11_000_000
    lay = tt.make_layout([256] * 28, [False] * 28)
    chunks = hp._chunks(lay, hp._target(fused))
    assert len(chunks) == (14 if fused else 7)
    assert hp._groups(chunks, L, 3, fused) == [(0, len(chunks))]
    codes, labels, weights, node, active = _row_args(one_chip, 28, rows)
    operand = _code_operand(one_chip, lay, rows)
    if fused:
        fn = hp.make_fused_level_fn(L, lay, "variance", 100, 0.0,
                                    low_precision=True)
        args = (codes, operand, labels, weights, node, active,
                _shape(one_chip, (lay.T,), jnp.bool_))
    else:
        fn = hp.make_pallas_hist_fn(L, lay, low_precision=True)
        args = (codes, labels, weights, node, active, operand)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _compiled_kernels(compiled) == hp.kernel_calls(lay, fused, L) == 1
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 150 * rows, temp / rows


@pytest.mark.parametrize("fused,ci,L", [(True, 13, 1), (False, 6, 2 * L_MAX)])
def test_the_last_chunk_of_28_columns_reads_a_ragged_code_block(one_chip,
                                                                fused, ci, L):
    """At all 28 columns of 256 slots a chunk's code block is one tile of 8
    int32 rows of the `[28, n]` operand, and 28 rows are three tiles and a
    half: the last fused chunk (features 26 and 27) and the last hist-mode
    chunk (24 to 27) read block 3, rows 24 to 31, of which four are past the
    operand's end. Mosaic takes the ragged block (and the chip reads the
    reference's forest through it: PERF.md section 2)."""
    lay = tt.make_layout([256] * 28, [False] * 28)
    target = hp._target(fused=fused)
    ch = hp._chunks(lay, target)[ci]
    assert (ch.f_lo, ch.f_hi) == ((26, 28) if fused else (24, 28))
    assert hp._code_window([ch], lay) == (8, 3)
    blk, C = hp.blk_setting(), 3
    rows = -(-11_000_000 // blk) * blk
    call = hp._build_call(lay.key, target, (ci, ci + 1), L, C, blk, True,
                          ("variance", 100, 0.0, 0) if fused else None, False)
    args = [_code_operand(one_chip, lay, rows),
            _shape(one_chip, (C, rows), jnp.bfloat16),
            _shape(one_chip, (1, rows), jnp.int32)]
    if fused:
        args.append(_shape(one_chip, (1, 1, ch.w), jnp.float32))
    compiled = jax.jit(call).lower(*args).compile()
    assert _compiled_kernels(compiled) == 1
