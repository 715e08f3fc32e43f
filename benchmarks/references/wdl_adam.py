"""Plain reference: a wide-and-deep network trained full-batch by ADAM, as
Shifu's WDL trainer defines it. Straightforward jax.numpy, float32 with
matmuls at `highest`; no kernels, no cache, nothing of shifu_tpu.

Semantics, as the trainer's users see them:
- deep part: the dense columns [n, Dn] beside one embedding row [E] a
  categorical field, looked up by the row's code, through a relu tower to
  one logit; wide part: `dense @ wide_dense` plus one weight a field,
  looked up by the same code; probability = sigmoid(deep + wide + bias);
- rows are split once: `valid` where `default_rng(seed).random(n) < rate`,
  and a second draw of the same generator for bagging (rate 1.0: all kept);
  a valid row has train significance 0;
- the loss minimised is sum(sig * logloss(p)), p clipped to [1e-7, 1 - 1e-7],
  summed over rows, not averaged; the gradient handed on is the descent
  direction, its negative;
- the error reported for a step is sum(sig * (t - p)^2) / max(sum(sig), 1),
  read on the weights the step starts from;
- ADAM: m = 0.9 m + 0.1 g, v = 0.999 v + 0.001 g^2, the step
  lr * m_hat / (sqrt(v_hat) + 1e-8) added to the weights (g is the descent
  direction); with L2, g - reg * w / n_train stands for g.

Departures from `shifu_tpu/models/wdl.py` and `train/wdl_trainer.py`:
- the lookups are `jnp.take` on the forward side and the table gradients are
  `jax.ops.segment_sum` of the per-row gradients, written by hand, where the
  program differentiates `table[idx]` and lets XLA transpose the gather;
- the wide part's gradient is by hand (d loss / d logit, summed by code);
  only the tower goes through `jax.grad`;
- rows are taken a block at a time and the blocks' gradients added, so the
  activations of one block, not of the set, live on the device;
- the parameters are a list of leaves (26 embedding tables, 26 wide tables,
  the wide dense weights, W and b a layer, the bias), not one flat vector.

`lowp=True` is the control of `correct`. On the chip the program's float32
matmuls multiply in one bfloat16 pass (8 bits of mantissa), so the precision
below is an fp8 multiply: both operands of every matmul, forward and
backward, are rounded to e4m3's 3 stored bits of mantissa
(`lax.reduce_precision`, float32's exponent range kept), the rest float32.
"""

from __future__ import annotations

import numpy as np

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
PROB_EPS = 1e-7
BLOCK_ROWS = 262144
FAULTS = ("half", "embed_grad_dropped", "codes_shifted", "wide_skipped",
          "moments_dropped", "held_after_1")


def split_rows(n: int, seed: int, valid_rate: float):
    """(train significance [n] f32, valid mask [n] f32)."""
    rng = np.random.default_rng(seed)
    valid = rng.random(n) < valid_rate
    sig = (rng.random(n) < 1.0).astype(np.float32)
    sig[valid] = 0.0
    return sig, valid.astype(np.float32)


def _tower(n_dense: int, vocab: list, embed: int, hidden: list) -> list:
    """(inputs, outputs) of each of the tower's layers."""
    sizes = [n_dense + len(vocab) * embed] + list(hidden) + [1]
    return list(zip(sizes[:-1], sizes[1:]))


def leaf_shapes(n_dense: int, vocab: list, embed: int, hidden: list) -> list:
    """The leaves in the order of the trainer's flat vector."""
    shapes = [(v, embed) for v in vocab] + [(v,) for v in vocab]
    shapes.append((n_dense,))
    for fi, fo in _tower(n_dense, vocab, embed, hidden):
        shapes += [(fi, fo), (fo,)]
    return shapes + [(1,)]


def start_flat(n_dense: int, vocab: list, embed: int, hidden: list,
               seed: int) -> np.ndarray:
    """The benchmark's starting weights from the seed, by the trainer's own
    rule (`init_wdl_params`): N(0, 0.05) embedding rows, Xavier-uniform tower
    weights, every wide weight and every bias 0; one generator, in leaf
    order."""
    rng = np.random.default_rng(seed)
    chunks = [rng.normal(0, 0.05, size=(v, embed)).ravel() for v in vocab]
    chunks += [np.zeros(v) for v in vocab] + [np.zeros(n_dense)]
    for fi, fo in _tower(n_dense, vocab, embed, hidden):
        lim = np.sqrt(6.0 / (fi + fo))
        chunks += [rng.uniform(-lim, lim, size=(fi, fo)).ravel(),
                   np.zeros(fo)]
    chunks.append(np.zeros(1))
    return np.concatenate(chunks).astype(np.float32)


def leaves_of(flat, shapes: list) -> list:
    out, off = [], 0
    for shp in shapes:
        size = int(np.prod(shp))
        out.append(flat[off:off + size].reshape(shp))
        off += size
    return out


def _make_block_fn(n_cat: int, embed: int, lowp: bool, fault: str | None):
    import jax
    import jax.numpy as jnp

    def q(a):
        # the tangent of reduce_precision is reduce_precision of the tangent,
        # so the backward matmuls see rounded operands too
        return jax.lax.reduce_precision(a, 8, 3) if lowp else a

    def deep(rows, layers, dense):
        h = jnp.concatenate([dense] + rows, axis=1)
        for i in range(0, len(layers) - 2, 2):
            h = jax.nn.relu(q(h) @ q(layers[i]) + layers[i + 1])
        return (q(h) @ q(layers[-2]) + layers[-1])[:, 0]

    def block(leaves, dense, codes, t, sig_t, sig_v):
        tables, wides = leaves[:n_cat], leaves[n_cat:2 * n_cat]
        wide_dense, layers, bias = (leaves[2 * n_cat],
                                    leaves[2 * n_cat + 1:-1], leaves[-1])
        idx = [jnp.clip(codes[:, f], 0, tables[f].shape[0] - 1)
               for f in range(n_cat)]
        if fault == "codes_shifted":  # the first field reads its neighbour
            idx[0] = jnp.clip(idx[0] + 1, 0, tables[0].shape[0] - 1)
        rows = [jnp.take(tables[f], idx[f], axis=0) for f in range(n_cat)]

        def loss_of(rows, layers, wide_logit):
            logit = deep(rows, layers, dense) + wide_logit + bias[0]
            p = jax.nn.sigmoid(logit)
            pc = jnp.clip(p, PROB_EPS, 1 - PROB_EPS)
            ll = -(t * jnp.log(pc) + (1 - t) * jnp.log(1 - pc))
            return jnp.sum(sig_t * ll), p

        wide_logit = q(dense) @ q(wide_dense)
        if fault != "wide_skipped":
            for f in range(n_cat):
                wide_logit = wide_logit + jnp.take(wides[f], idx[f])
        (_, p), (g_rows, g_layers, dl) = jax.value_and_grad(
            loss_of, argnums=(0, 1, 2), has_aux=True)(rows, layers,
                                                      wide_logit)
        # dl is d loss / d logit a row: everything the wide part needs
        g_tables = [jax.ops.segment_sum(g_rows[f], idx[f],
                                        num_segments=tables[f].shape[0])
                    for f in range(n_cat)]
        if fault == "embed_grad_dropped":
            g_tables[0] = jnp.zeros_like(g_tables[0])
        g_wides = [jax.ops.segment_sum(dl, idx[f],
                                       num_segments=wides[f].shape[0])
                   for f in range(n_cat)]
        if fault == "wide_skipped":
            g_wides = [jnp.zeros_like(g) for g in g_wides]
        grads = (g_tables + g_wides + [q(dl) @ q(dense)] + list(g_layers)
                 + [jnp.sum(dl)[None]])
        sq = (t - p) ** 2
        return ([-g for g in grads], jnp.sum(sig_t * sq), jnp.sum(sig_v * sq))

    return jax.jit(block)


def _adam(state, w, g, lr, it, l2_reg, n_train):
    import jax.numpy as jnp

    if l2_reg:
        g = g - l2_reg * w / n_train
    m = BETA1 * state["m"] + (1 - BETA1) * g
    v = BETA2 * state["v"] + (1 - BETA2) * g * g
    it_f = jnp.float32(max(it, 1))
    step = lr * (m / (1 - BETA1 ** it_f)) / (
        jnp.sqrt(v / (1 - BETA2 ** it_f)) + ADAM_EPS)
    return w + step, {"m": m, "v": v}


def first_steps(dense, codes, t, w, flat0: np.ndarray, shapes: list,
                seed: int, valid_rate: float, lr: float, l2_reg: float = 0.0,
                steps: int = 3, lowp: bool = False,
                block_rows: int = BLOCK_ROWS, fault: str | None = None) -> dict:
    """Follow the first `steps` full-batch steps from `flat0`. dense [n, Dn],
    codes [n, Dc], t [n], w [n] are device or host arrays.

    Returns losses [(train, valid) a step], grad1 (the first step's descent
    direction, a list of leaves), change1 and change (weights after the first
    and after the last step less `flat0`, by leaf) and flats (the weights
    each step starts from, then the last step's result)."""
    import jax
    import jax.numpy as jnp

    n = dense.shape[0]
    n_cat = codes.shape[1]
    sig, valid = split_rows(n, seed, valid_rate)
    if fault == "half":  # every second row left out, the mean over the rest
        sig = sig * (np.arange(n) % 2 == 0)
    n_train = float(max(sig.sum(), 1.0))
    sig_t = jnp.asarray(sig) * w
    sig_v = jnp.asarray(valid) * w
    den_t = float(jnp.maximum(jnp.sum(sig_t), 1.0))
    den_v = float(jnp.maximum(jnp.sum(sig_v), 1.0))
    block = _make_block_fn(n_cat, shapes[0][1], lowp, fault)
    flat = jnp.asarray(flat0, jnp.float32)
    state = {"m": jnp.zeros_like(flat), "v": jnp.zeros_like(flat)}
    losses, flats = [], []
    with jax.default_matmul_precision("highest"):
        for k in range(steps):
            leaves = leaves_of(flat, shapes)
            g_sum, tr, va = None, 0.0, 0.0
            for lo in range(0, n, block_rows):
                sl = slice(lo, min(lo + block_rows, n))
                g, a, c = block(leaves, dense[sl], codes[sl], t[sl],
                                sig_t[sl], sig_v[sl])
                g_sum = g if g_sum is None else [p + q for p, q in
                                                 zip(g_sum, g)]
                tr, va = tr + a, va + c
            losses.append((float(tr) / den_t, float(va) / den_v))
            flats.append(np.asarray(flat))
            if k == 0:
                grad1 = [np.asarray(a) for a in g_sum]
            g_flat = jnp.concatenate([a.ravel() for a in g_sum])
            stepped, state = _adam(state, flat, g_flat, jnp.float32(lr),
                                   k + 1, l2_reg, n_train)
            # the two faults of the later steps: ADAM's moments not carried
            # from a step to the next; the weights held after the first
            # step while the steps still count
            if fault == "moments_dropped":
                state = {k_: jnp.zeros_like(a) for k_, a in state.items()}
            if not (fault == "held_after_1" and k > 0):
                flat = stepped
    flats.append(np.asarray(flat))
    start = np.asarray(flat0, np.float32)
    return {"losses": losses, "grad1": grad1,
            "change1": leaves_of(flats[1] - start, shapes),
            "change": leaves_of(flats[-1] - start, shapes),
            "flats": flats}
