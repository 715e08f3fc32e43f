"""A guard on the tree cells' set-up, on the CPU. PR 37 took the selection
matmul out of the tree kernel, read x 1.72 to x 1.88 in the GBT cells and was
refused for 4.07 s more `setup_s` on `higgs_gbt_full.train_mesh4`: bodies
three to eight times as long to trace and lower, paid in every process.
Nothing on the CPU had said so. Two things are held here, for the one-chip
and the meshed whole-tree program alike:

- the program is traced ONCE over a warm-up call and two more calls (the
  ring's `jax.trace` events and the dispatch seam's own count of programs
  built), and so is the program that makes its hoisted code operand: one whose
  shape, dtype, weak type, sharding or committed-ness differed between two
  calls or two trees would miss `jax.jit`'s cache and pay the program again
  (5 s on the mesh), unseen by any parity test;
- the traced program's equations, kernels' bodies and nested calls counted,
  stay within 25 % of the parent's (PR 36's, read from its checkout by the
  same walk): what a program costs to trace and lower goes with their number.

`data_mesh(4)` is over the suite's forced host devices; the kernel runs in
interpret mode for the calls and is only traced, as the chip's, for the
count."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shifu_tpu import obs  # noqa: E402
from shifu_tpu.parallel.mesh import data_mesh, shard_rows  # noqa: E402
from shifu_tpu.train import tree_trainer as tt  # noqa: E402
from shifu_tpu.utils import environment  # noqa: E402
from tests.test_train_spans import _eqns  # noqa: E402  (the jaxpr walker)

F, S = 11, 13  # a layout no other test file grows trees on: the programs are this file's own
COLS = ["c%d" % i for i in range(F)]


def _table(n, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, S, size=(n, F)).astype(np.int32)
    y = (codes[:, 0] + codes[:, 1]
         + rng.normal(scale=2, size=n) > S).astype(np.float32)
    return codes, y, np.ones(n, np.float32)


@pytest.fixture
def kernel_on():
    """The kernel on (interpret mode off the chip), and the program cache
    as it was afterwards: programs built under the knob are never reused."""
    before = set(tt._PROGRAMS)
    environment.set_property("shifu.pallas.mode", "on")
    try:
        yield
    finally:
        environment.set_property("shifu.pallas.mode", "")
        for k in set(tt._PROGRAMS) - before:
            del tt._PROGRAMS[k]


def _traces(fun: str) -> list:
    """The ring's `jax.trace` events of the function `fun` (obs/jaxprobe.py
    keeps every trace of a millisecond or more, with the name jax sends)."""
    return [e for e in obs.tracer().events
            if e["name"] == "jax.trace" and e["args"].get("fun") == fun]


@pytest.mark.parametrize("alg", ["GBT", "RF"])
@pytest.mark.parametrize("meshed", [False, True], ids=["one-chip", "mesh4"])
def test_the_tree_program_is_traced_once_over_three_calls(kernel_on, meshed,
                                                          alg):
    """A warm-up call and two more, each with a table of its own (as the
    benchmark's calls have their labels) and three trees: ONE `jax.trace`
    event of `tree_body` and one program built at the tree's dispatch seam,
    and the same of `tree.codes8` (`build`), whose operand is the program's
    second argument on one chip and under the mesh. The seams dispatch
    executables they compiled ahead of time, so `_cache_size()` of the jit
    objects stays 0 and says nothing; the seam's own count does."""
    mesh = data_mesh(4) if meshed else None
    cfg = tt.TreeTrainConfig(algorithm=alg, tree_num=3, max_depth=4, seed=3,
                             min_instances_per_node=3)
    obs.reset()
    for call in range(3):
        rows = _table(1000, seed=call)
        if meshed:
            rows = tuple(shard_rows(a, mesh) for a in rows)
        tt.train_trees(*rows, [S] * F, [False] * (F - 2) + [True, True],
                       COLS, cfg, mesh=mesh)
    programs = obs.profiler().snapshot()["programs"]
    seam = "tree.whole_tree" if meshed else "tree.pallas_fused"
    assert programs[seam]["programsCompiled"] == 1
    assert programs[seam]["dispatches"] == 9
    assert programs["tree.codes8"]["programsCompiled"] == 1
    assert programs["tree.codes8"]["dispatches"] == 3
    assert len(_traces("tree_body")) == 1
    assert len(_traces("build")) == 1


@pytest.mark.parametrize("meshed", [False, True], ids=["one-chip", "mesh4"])
def test_the_code_operand_has_one_form(kernel_on, meshed):
    """What `tree.codes8` hands the tree program: `[F, n]` int8, committed,
    sharded along its rows under the mesh (275 of 1,100 a chip), and the
    same aval and sharding from one call to the next."""
    mesh = data_mesh(4) if meshed else None
    lay = tt.make_layout([S] * F, [False] * F)
    prog = tt._get_codes8_program(lay, mesh)
    seen = []
    for call in range(2):
        codes = jnp.asarray(_table(1100, seed=call)[0])
        if meshed:
            codes = shard_rows(codes, mesh)
        out = prog(codes)
        seen.append((out.shape, out.dtype, out.weak_type, out.sharding,
                     out.committed))
    assert seen[0] == seen[1]
    shape, dtype, weak, sharding, committed = seen[0]
    assert shape == (F, 1100)
    assert (dtype, weak) == (jnp.int8, False)
    if meshed:
        assert committed
        assert sharding.spec == jax.sharding.PartitionSpec(None, "data")
        assert [s.data.shape for s in out.addressable_shards] == [(F, 275)] * 4
    want = np.clip(np.asarray(_table(1100, seed=1)[0]), 0, S - 1).T
    np.testing.assert_array_equal(np.asarray(out), want)


# The parent's counts (PR 36, commit 9455a64; `git archive` of it and this
# walk, PR 38) for the three cells' programs over HIGGS's 28 x 33 slots, and
# what each program of this PR read when it was written: the bodies hold 2
# equations a feature and 1 a straddled tile more than the parent's
# selection matmul did (123 against 44 in hist mode, 88 against 46 fused).
# PR 37's one-chip program read 10,600.
PARENT = {"one-chip": 9004, "mesh4": 2691, "forest": 15561}
READ = {"one-chip": 9436, "mesh4": 3147, "forest": 16293}
MARGIN = 0.25


@pytest.mark.parametrize("cell", list(PARENT))
def test_the_tree_programs_equations_stay_near_the_parents(monkeypatch, cell):
    """The whole-tree program of each tree cell, traced as for the chip
    (the kernel on and not interpreted; nothing is lowered or run) at 4,096
    rows: depth 6 on bf16 planes on one chip and over four, depth 10 on f32
    planes for the forest, subtraction at every level."""
    meshed = cell == "mesh4"
    mesh = data_mesh(4) if meshed else None
    monkeypatch.setattr(tt, "_pallas_state",
                        lambda mesh=None: (True, False, mesh is None))
    lay = tt.make_layout([33] * 28, [False] * 28)
    D, lowp = (10, False) if cell == "forest" else (6, True)
    before = set(tt._PROGRAMS)
    try:
        prog = tt._get_tree_program(D, lay, "variance", 5, 0.0, mesh=mesh,
                                    sub_levels=(False,) + (True,) * D,
                                    lowp=lowp)
    finally:
        for k in set(tt._PROGRAMS) - before:
            del tt._PROGRAMS[k]  # built under a steered state: never reuse
    rows = 4096
    s = jax.ShapeDtypeStruct
    traced = prog.fn.trace(
        s((rows, 28), jnp.int32), s((28, rows), jnp.int8),
        s((rows,), jnp.float32), s((rows,), jnp.float32),
        s((lay.T,), jnp.bool_))
    # equations of the program and of every jaxpr its equations hold (nested
    # calls, `shard_map`, `cond` branches, a `pallas_call`'s body)
    n = len(_eqns(traced.jaxpr.jaxpr, []))
    assert n <= PARENT[cell] * (1 + MARGIN), (n, PARENT[cell])
    # and no silent drift of the reading this file states: 2 % either way
    assert abs(n - READ[cell]) <= 0.02 * READ[cell], (n, READ[cell])
