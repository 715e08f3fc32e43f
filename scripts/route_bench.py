"""Row routing alone, on the chip: the per-row gather form (what the tree
program ran up to PR 26; `tests/test_route_rows.py` keeps it as the plain
reference) against `tree_trainer.route_rows`, at a list of shapes.

Usage (one v5e):  chiprun -- python scripts/route_bench.py [--explore]

A shape is (n, F, s_max, L): rows, feature columns, slots a feature, nodes
in the level. Each form is jitted alone, run 5 times after a warm-up call
and timed with `block_until_ready`; the least of the five is printed, in
ms, one JSON line a shape, and all lines go to chiprun_out/route_bench.json.
`--explore` also times the parts (the code select, the mask lookup) in the
forms that were tried; PERF.md section 6 (PR 27) has the readings.
`--built` times, instead, the forms of the next level's build-row mask
(histogram subtraction) at the cell's rows and L = 16 to 512: none (the
routing pass alone), `ride` (the bit in the feature's table entry: what
`route_rows` does since PR 35), `select` (a `_lookup` of its own after the
pass) and `gather` (`built_lsb[node >> 1]`, the form up to PR 34), each one
jitted program; PERF.md section 6 (PR 35) has the readings."""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the cell, the next cell in line (higgs_rf.train_depth10), one wide shape,
# one with 256 slots
SHAPES = [
    (5_500_000, 28, 33, 1),
    (5_500_000, 28, 33, 32),
    (5_500_000, 28, 33, 64),
    (5_500_000, 28, 33, 256),
    (5_500_000, 28, 33, 512),
    (1_000_000, 512, 33, 32),
    (2_000_000, 28, 256, 32),
    (2_000_000, 28, 256, 512),
    # past tree_trainer._ROUTE_SELECT_CAP mask words: the 1-D gather
    (5_500_000, 28, 33, 4096),
    (2_000_000, 28, 256, 2048),
]


def make_inputs(n, F, s_max, L, seed=0):
    """A level's scan outputs and the rows' state, from `seed`: a real
    `left_mask` row for each node (its feature's ranks against a cut), so
    the gather form and the mask form must agree."""
    rng = np.random.default_rng(seed)
    T = F * s_max
    off = (np.arange(F) * s_max).astype(np.int32)
    clip = np.full(F, s_max - 1, np.int32)
    feature = rng.integers(0, F, size=L).astype(np.int32)
    is_split = rng.random(L) < 0.9
    rank_flat = rng.permuted(
        np.tile(np.arange(s_max, dtype=np.int32), (L * F, 1)),
        axis=1).reshape(L, T)
    cut = rng.integers(0, s_max - 1, size=L).astype(np.int32)
    cols = off[feature][:, None] + np.arange(s_max)[None, :]
    lm = (np.take_along_axis(rank_flat, cols, axis=1) <= cut[:, None]) \
        & is_split[:, None]
    assert rank_flat.shape == (L, T)
    return dict(
        node=rng.integers(0, L, size=n).astype(np.int32),
        active=rng.random(n) < 0.95,
        resting=np.zeros(n, np.int32),
        feature=feature, cut_rank=cut, rank_flat=rank_flat,
        is_split=is_split, left_mask=lm, off=off, clip=clip)


def best_ms(fn, args, reps=5):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e3, 3), out


def explore(codes, d, F, s_max, L):
    """The parts, in the forms tried."""
    import jax
    import jax.numpy as jnp

    node, lm = d["node"], d["left_mask"]
    f_row = jnp.where(d["is_split"], d["feature"], 0)[node]
    out = {}

    def code_gather(codes, f):
        return jnp.take_along_axis(codes, f[:, None], axis=1)[:, 0]

    def code_select(codes, f):
        return jnp.where(f[:, None] == jnp.arange(F, dtype=jnp.int32),
                         codes, 0).sum(1)

    ref = None
    for name, fn in [("code.gather", code_gather),
                     ("code.select_sum", code_select)]:
        out[name], o = best_ms(jax.jit(fn), (codes, f_row))
        ref = o if ref is None else ref
        assert bool(jnp.array_equal(o, ref)), name
    c = jnp.clip(ref, 0, s_max - 1)

    W = -(-s_max // 32)
    pad = jnp.zeros((L, W * 32), jnp.uint32).at[:, :s_max].set(
        lm.astype(jnp.uint32))
    packed = (pad.reshape(L, W, 32)
              << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)

    def mask_gather2d(nl, c):
        cf = d["off"][f_row] + c
        return d["rank_flat"][nl, cf] <= d["cut_rank"][nl]

    def mask_flat(nl, c):
        return lm.reshape(-1)[nl * s_max + c]

    def bit_of(word, c):
        return ((word >> (c & 31).astype(jnp.uint32)) & 1) > 0

    def mask_packed_gather(nl, c):
        return bit_of(packed.reshape(-1)[nl * W + (c >> 5)], c)

    def mask_packed_select(nl, c):
        hit = nl[:, None] == jnp.arange(L, dtype=jnp.int32)
        word = jnp.zeros_like(nl).astype(jnp.uint32)
        for w in range(W):
            ww = jnp.where(hit, packed[:, w][None, :], 0).sum(
                1, dtype=jnp.uint32)
            word = jnp.where((c >> 5) == w, ww, word)
        return bit_of(word, c)

    def mask_packed_chain(nl, c):
        words = [jnp.zeros_like(nl).astype(jnp.uint32) for _ in range(W)]
        for l in range(L):
            hit = nl == l
            words = [jnp.where(hit, packed[l, w], words[w])
                     for w in range(W)]
        word = words[0]
        for w in range(1, W):
            word = jnp.where((c >> 5) == w, words[w], word)
        return bit_of(word, c)

    forms = [("mask.gather2d", mask_gather2d), ("mask.flat_bool", mask_flat),
             ("mask.packed_gather", mask_packed_gather),
             ("mask.packed_select_sum", mask_packed_select)]
    if L * W <= 512:
        forms.append(("mask.packed_select_chain", mask_packed_chain))
    ref = None
    for name, fn in forms:
        out[name], o = best_ms(jax.jit(fn), (node, c))
        ref = o if ref is None else ref
        # off-clip slots aside, every form must say what the gather says
        assert bool(jnp.array_equal(o | ~d["is_split"][node],
                                    ref | ~d["is_split"][node])), name
    return out


def built_forms(n=5_500_000, F=28, s_max=33, levels=(16, 64, 128, 256, 512)):
    """The build-row mask of the next level, in the forms tried."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.train import tree_trainer as tt

    def after(lookup):
        def form(*a):
            resting, node, active, _ = tt.route_rows(*a[:-1])
            lsb = lookup(jnp.where(a[-1], 0, 1), node >> 1)
            return resting, node, active, active & ((node & 1) == lsb)
        return form

    forms = {
        "none": lambda *a: tt.route_rows(*a[:-1])[:3],
        "ride": tt.route_rows,
        "select": after(tt._lookup),
        "gather": after(lambda table, idx: table[idx]),
    }
    lines = []
    for L in levels:
        d = {k: jnp.asarray(v) for k, v in
             make_inputs(n, F, s_max, L).items()}
        codes = jax.random.randint(jax.random.PRNGKey(1), (n, F), -1,
                                   s_max + 2, jnp.int32)
        left_small = jax.random.bernoulli(jax.random.PRNGKey(2), 0.5, (L,))
        args = (codes, d["node"], d["active"], d["resting"], d["feature"],
                d["is_split"], d["left_mask"], jnp.int32(L - 1), d["clip"],
                left_small)
        line, ref = {"n": n, "F": F, "s_max": s_max, "L": L}, None
        for name, fn in forms.items():
            line[name + "_ms"], o = best_ms(jax.jit(fn), args)
            if name != "none":
                ref = o if ref is None else ref
                assert all(bool(jnp.array_equal(x, y))
                           for x, y in zip(o, ref)), name
        print(json.dumps(line), flush=True)
        lines.append(line)
        del codes, d, args, ref, o
    return lines


def main():
    import jax
    import jax.numpy as jnp

    from shifu_tpu.train.tree_trainer import route_rows
    from tests.test_route_rows import route_gather

    dev = jax.devices()[0]
    if "--built" in sys.argv:
        lines = built_forms()
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/route_bench_built.json", "w") as fh:
            json.dump({"device": dev.device_kind, "lines": lines}, fh,
                      indent=1)
        return
    lines = []
    for n, F, s_max, L in SHAPES:
        d = {k: jnp.asarray(v) for k, v in
             make_inputs(n, F, s_max, L).items()}
        codes = jax.random.randint(jax.random.PRNGKey(1), (n, F), -1,
                                   s_max + 2, jnp.int32)
        base = jnp.int32(L - 1)
        g_ms, g = best_ms(jax.jit(route_gather), (
            codes, d["node"], d["active"], d["resting"], d["feature"],
            d["cut_rank"], d["rank_flat"], d["is_split"], base, d["off"],
            d["clip"]))
        r_ms, r = best_ms(jax.jit(route_rows), (
            codes, d["node"], d["active"], d["resting"], d["feature"],
            d["is_split"], d["left_mask"], base, d["clip"]))
        same = all(bool(jnp.array_equal(a, b)) for a, b in zip(g, r))
        line = {"n": n, "F": F, "s_max": s_max, "L": L,
                "gather_ms": g_ms, "route_rows_ms": r_ms, "same": same,
                "device": dev.device_kind}
        if "--explore" in sys.argv:
            line["parts"] = explore(codes, d, F, s_max, L)
        print(json.dumps(line), flush=True)
        lines.append(line)
        del codes, d, g, r
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/route_bench.json", "w") as fh:
        json.dump(lines, fh, indent=1)
    if not all(ln["same"] for ln in lines):
        sys.exit(1)


if __name__ == "__main__":
    main()
