"""Pallas TPU kernel: the gradient of small embedding tables as a one-hot
contraction on the MXU, every small field of a wide-and-deep model in one
call.

Under `jax.grad` the transpose of a lookup `tb[idx]` is a scatter-add of the
`[n, w]` cotangent into the `[v, w]` table. XLA's scatter-add on a TPU costs a
pass over the rows whatever the table's size (6.75 ns a row on a v5e, at 4
table rows as at 10,001: PERF.md, section 5). For a small table the same sum

    g[v, :] = sum_i [code_i == v] * cot[i, :]

is a one-hot histogram of the cotangent, which this kernel computes as the
tree kernel (`ops/hist_pallas.py`) computes its histograms, with nothing of
its logic shared (operands, outputs and precision all differ):

    grid (row blocks)  — one `[T x R, 128]` f32 accumulator, VMEM-resident
                         across the grid (zeroed at block 0): table f has
                         its own T_f tiles of 128 of its rows, a tile a
                         block of R = 3 x width accumulator rows rounded up
                         to whole sublane tiles (`term_rows`: 32 at width
                         9, an embedding of 8 and the wide weight)
    per block, field   — a code is (tile, slot) = (code >> 7, code & 127);
                         the slot one-hot MT `[128, blk]` has the ROWS
                         ALONG THE LANES, a sublane broadcast of the slot
                         row and ONE compare with the slot iota, never
                         written to HBM; the field's cotangent rows, split
                         in three bfloat16 terms (hi, mid and lo: 24 bits
                         together) and padded to R rows, are stacked once
                         a tile, each copy kept where the row's tile is
                         that tile and 0 elsewhere, as the LHS
                         `[T_f x R, blk]`; ONE dot on the MXU contracts
                         the row axis with MT, f32 accumulation, into the
                         field's T_f blocks of the accumulator
    after the call     — a tile's three terms are summed and the tiles
                         laid out as the tables' `[v_f, w]` gradients (XLA,
                         on 1.2 MB)

So MT is pushed to the MXU once a field and not once a tile, and a tile
costs a select of the cotangent rows where a one-hot of its own would cost
a compare for each of its 128 x blk entries: on the described v5e's
compiler a 512-row step of the Criteo cell's 14 tables (75 tiles) is 2,861
bundles, where a compare a tile made it 4,747 to 4,883, and the body is 353
equations, where a block of its own a tile made it 1,085 (PERF.md,
section 6).

Operands, rows along the lanes: the clipped codes `[F, n]` int32
(`codes_operand`, made once a program, before its epoch loop) and one
cotangent plane `[w, n]` f32 a field, which XLA writes so where it splits
the concatenated plane's cotangent (one `[F x w, n]` operand cost a
concatenation and a pad more in the described chip's program). The last
block runs past the n rows, and the kernel takes its planes there as 0.

Precision: a 0/1 one-hot is exact in bfloat16 and so is every product with
a bfloat16 term; the three terms of an f32 hold all of its 24 bits, and the
MXU accumulates in f32. Only the order of the additions differs from the
scatter-add's. A single bfloat16 pass would be a lower precision than the
model's f32 tables, not a faster result.

What a call holds in VMEM (`call_vmem`: the accumulator, the row blocks
and the largest field's stacked LHS) grows with the tables' tiles and the
width, and `small_fields` takes fields only while it fits `VMEM_BUDGET`
(and `FIELDS_CAP`).

`lookups` is the entry: the small fields' gathers, forward as `tb[idx]`,
whose `custom_vjp` transpose is this kernel, one call for all of them. It
runs under `vmap` too (`pallas_call`'s batching rule puts the batch on a
grid axis of its own, each member with its own accumulator).
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

KERNEL = "wdl_onehot_grad"
# a table takes this path where it has at most this many rows (32 tiles).
# The cap is not the break-even: the kernel's cost grows with the table's
# tiles and the scatter-add's does not, and on a v5e one table of 4,096 rows
# took 5.46 ms of the kernel against 22.73 of its scatter-add, one of 10,001
# rows 11.24 against 22.84, so it breaks even near 23,000 rows (PERF.md,
# section 7). It holds a field's stacked LHS, `[T_f x R, blk]` f32 and its
# bfloat16 copy, to 3 MB of VMEM at width 9; a wider cap is a later
# measurement's to set
ROWS_CAP = 4096
# what a call may hold in VMEM by `call_vmem`: a quarter of a v5e's 128 MiB.
# The described v5e's compiler refuses a call whose accumulator window alone
# passes 128 MiB (`RESOURCE_EXHAUSTED`) and compiles 140 tables of 32 tiles
# (a 73 MB window) and one table of 32 tiles at width 129 (PERF.md,
# section 7); the Criteo cell's 14 tables take 5.2 MB
VMEM_BUDGET = 32 << 20
# fields one call takes at most: its body is unrolled a field (25
# equations each), and 128 one-tile tables take the described v5e's
# compiler about 5 s (tests/test_chip_compile.py)
FIELDS_CAP = 128
_LANE = 128
_SUBLANE = 8
_BLK = 512  # rows a grid step


def term_rows(width: int) -> int:
    """Accumulator rows of a tile: its three terms of the cotangent, each
    `width` rows, on whole sublane tiles."""
    return -(-3 * width // _SUBLANE) * _SUBLANE


def _tiles(v: int) -> int:
    return -(-int(v) // _LANE)


def call_vmem(vocab, width: int) -> int:
    """Bytes of VMEM one call over the tables `vocab` holds at `_BLK` rows a
    step: the accumulator's window (one, its block never moves), the row
    blocks double-buffered, and the largest field's stacked LHS (f32 and
    bfloat16), slot one-hot and dot."""
    R = term_rows(width)
    sub = -(-width // _SUBLANE) * _SUBLANE
    acc = sum(_tiles(v) for v in vocab) * R * _LANE * 4
    rows = 2 * _BLK * 4 * (-(-len(vocab) // _SUBLANE) * _SUBLANE
                           + len(vocab) * sub)
    t = max(_tiles(v) for v in vocab)
    field = t * R * (_BLK * 6 + _LANE * 4) + _LANE * _BLK * 2
    return acc + rows + field


def small_fields(vocab_sizes, width: int) -> Tuple[int, ...]:
    """The fields, in their order, whose table has at most `ROWS_CAP` rows,
    taken smallest table first while one call over them fits
    `VMEM_BUDGET` (`call_vmem`), `FIELDS_CAP` at most; the others keep
    the scatter-add."""
    taken = []
    for v, f in sorted((int(v), f) for f, v in enumerate(vocab_sizes)
                       if int(v) <= ROWS_CAP):
        if len(taken) == FIELDS_CAP or call_vmem(
                [vocab_sizes[g] for g in taken + [f]], width) > VMEM_BUDGET:
            break
        taken.append(f)
    return tuple(sorted(taken))


def block_rows(n: int) -> int:
    """Rows a grid step takes of n: all of them where one block holds
    them (the block is then the whole array), else `_BLK`."""
    return n if n <= _BLK else _BLK


def codes_operand(codes, fields, vocab_sizes):
    """`[len(fields), n]` int32: each field's codes clipped to its table as
    `wdl_plane` clips them, with the rows along the lanes."""
    import jax.numpy as jnp

    return jnp.stack([jnp.clip(codes[:, f], 0, int(vocab_sizes[f]) - 1)
                      for f in fields]).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _build_call(vocab: tuple, width: int, n: int, interpret: bool):
    """call(codes_t [F, n] int32, F planes [width, n] f32) ->
    `[T x R, 128]` f32 (R = `term_rows(width)`): tile t's block of R rows
    holds the three terms' sums of its 128 table rows, each `[width, 128]`,
    one under the other."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    F = len(vocab)
    blk = block_rows(n)
    tiles = [_tiles(v) for v in vocab]
    R = term_rows(width)
    first = np.concatenate([[0], np.cumsum(tiles)]).astype(int) * R
    lax = jax.lax

    def kernel(codes_ref, *refs):
        plane_refs, out_ref = refs[:F], refs[F]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # the last block runs past the n rows: what it reads there is not
        # the operands', and its planes are taken as 0
        live = lax.lt(lax.broadcasted_iota(jnp.int32, (width, blk), 1),
                      n - i * blk)
        nought = lax.full((width, blk), 0, jnp.float32)
        # the terms' rows padded to R (none where 3 x width is R already)
        pad = [lax.full((R - 3 * width, blk), 0, jnp.float32)] * (
            R > 3 * width)
        slot = lax.broadcasted_iota(jnp.int32, (_LANE, blk), 0)
        for f, t in enumerate(tiles):
            p = lax.select(live, plane_refs[f][...], nought)
            hi = p.astype(jnp.bfloat16).astype(jnp.float32)
            rest = p - hi
            mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
            lo = (rest - mid).astype(jnp.bfloat16).astype(jnp.float32)
            lhs = lax.concatenate([hi, mid, lo] + pad, 0)  # [R, blk]
            code = codes_ref[f:f + 1, :]  # [1, blk]
            mt = lax.eq(lax.broadcast_in_dim(
                lax.bitwise_and(code, np.int32(_LANE - 1)), (_LANE, blk),
                (0, 1)), slot).astype(jnp.bfloat16)  # [128, blk]
            if t > 1:  # a copy a tile, kept where the row's code is in it
                shape = (t, R, blk)
                lhs = lax.reshape(lax.select(
                    lax.eq(lax.broadcast_in_dim(
                        lax.shift_right_logical(code, np.int32(7)), shape,
                        (1, 2)), lax.broadcasted_iota(jnp.int32, shape, 0)),
                    lax.broadcast_in_dim(lhs, shape, (1, 2)),
                    lax.full(shape, 0, jnp.float32)), (t * R, blk))
            c = lax.dot_general(
                lhs.astype(jnp.bfloat16), mt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [t x R, 128]
            out_ref[first[f]:first[f + 1], :] += c

    def call(codes_t, planes):
        plane = pl.BlockSpec((width, blk), lambda i: (0, i))
        rows = int(first[-1])
        return pl.pallas_call(
            kernel,
            grid=(-(-n // blk),),
            in_specs=[pl.BlockSpec((F, blk), lambda i: (0, i))]
            + [plane] * F,
            out_specs=pl.BlockSpec((rows, _LANE), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
            interpret=interpret,
            name=KERNEL,
            # printed in the HLO text as kernel_metadata: how a profiler
            # trace's event is told from any other custom call
            metadata={"kernel": KERNEL, "fields": str(F)},
        )(codes_t, *planes)

    return call


def table_grads(codes_t, planes, vocab: tuple, interpret: bool = False):
    """Each table's `[v_f, width]` gradient: the sum over the rows i of
    `planes[f][:, i]` into the table row `codes_t[f, i]`."""
    import jax

    if isinstance(codes_t, jax.core.Tracer):
        from shifu_tpu.obs import registry

        registry().counter("wdl.lookup.onehot_fields").inc(len(vocab))
    n = codes_t.shape[1]
    width = planes[0].shape[0]
    out = _build_call(vocab, width, n, interpret)(codes_t, planes)
    out = out.reshape(-1, term_rows(width), _LANE)  # [T, R, 128]
    # the three terms' sums, added here: [T, width, 128]
    g = out[:, :width] + out[:, width:2 * width] + out[:, 2 * width:3 * width]
    grads, t0 = [], 0
    for v in vocab:
        t = _tiles(v)
        grads.append(g[t0:t0 + t].transpose(0, 2, 1).reshape(
            t * _LANE, width)[:v])
        t0 += t
    return tuple(grads)


@functools.lru_cache(maxsize=None)
def _lookup_fn(vocab: tuple, interpret: bool):
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def lookup(tables, idxs, codes_t):
        return tuple(tb[ix] for tb, ix in zip(tables, idxs))

    def fwd(tables, idxs, codes_t):
        return lookup(tables, idxs, codes_t), codes_t

    def bwd(codes_t, cots):
        # each cotangent `[n, width]` turned to `[width, n]`: XLA writes it
        # so from the concatenated plane's cotangent, where it split it
        grads = table_grads(codes_t, [c.T for c in cots], vocab, interpret)
        return grads, (None,) * len(cots), None

    lookup.defvjp(fwd, bwd)
    return lookup


def lookups(tables, idxs, codes_t, interpret: bool = False):
    """`tuple(tb[ix] for tb, ix in zip(tables, idxs))`, whose transpose is
    ONE call of the kernel for all the tables: `codes_t` is
    `codes_operand` of the same fields (the `idxs` along the lanes).
    Neither the indices nor the codes get a cotangent."""
    vocab = tuple(int(tb.shape[0]) for tb in tables)
    return _lookup_fn(vocab, interpret)(tuple(tables), tuple(idxs), codes_t)
