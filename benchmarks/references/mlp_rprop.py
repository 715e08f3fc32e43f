"""Plain reference: a tanh MLP with one sigmoid output, trained full-batch by
RPROP+ as Encog and Shifu define it. Straightforward jax.numpy, float32 with
matmuls at `highest`; no kernels, no cache, nothing of shifu_tpu.

Semantics, as the trainer's users see them:
- rows are split once: `valid` where `default_rng(seed).random(n) < rate`,
  and a second draw of the same generator for bagging (rate 1.0: all kept);
  a valid row has train significance 0;
- the error of a step is sum(sig * (t - p)^2) / max(sum(sig), 1), read on the
  weights the step starts from;
- the gradient is the descent direction -d/dw sum(sig * 0.5 * (t - p)^2),
  summed over rows, not averaged;
- RPROP+ (Encog's ResilientPropagation): a step of 0.1 to start with, times
  1.2 where the gradient keeps its sign (at most 50), times 0.5 where it turns
  (at least 1e-6) and the last change is taken back, the gradient then
  remembered as 0.

`lowp=True` is the control of `correct`. The configuration's matmuls multiply
in bfloat16 (8 bits of mantissa) and accumulate in float32, so the precision
below is an fp8 multiply: both operands of every matmul, forward and backward,
are rounded to e4m3's 3 stored bits of mantissa (`lax.reduce_precision`, with
float32's exponent range kept, which is fp8 at its best: perfectly scaled),
and everything else stays float32.
"""

from __future__ import annotations

import numpy as np

ETA_PLUS, ETA_MINUS = 1.2, 0.5
STEP_INIT, STEP_MAX, STEP_MIN = 0.1, 50.0, 1e-6
BLOCK_ROWS = 131072


def split_rows(n: int, seed: int, valid_rate: float):
    """(train significance [n] f32, valid mask [n] f32)."""
    rng = np.random.default_rng(seed)
    valid = rng.random(n) < valid_rate
    sig = (rng.random(n) < 1.0).astype(np.float32)
    sig[valid] = 0.0
    return sig, valid.astype(np.float32)


def xavier_flat(sizes: list, seed: int) -> np.ndarray:
    """The benchmark's starting weights from the seed, in the flat layout the
    trainer's `init_flat` takes: per layer W [in, out] row-major, then b."""
    rng = np.random.default_rng([seed, 7])
    chunks = []
    for fi, fo in zip(sizes[:-1], sizes[1:]):
        lim = np.sqrt(6.0 / (fi + fo))
        chunks.append(rng.uniform(-lim, lim, size=fi * fo))
        chunks.append(np.zeros(fo))
    return np.concatenate(chunks).astype(np.float32)


def leaves_of(flat, sizes: list) -> list:
    out, off = [], 0
    for fi, fo in zip(sizes[:-1], sizes[1:]):
        out.append(flat[off:off + fi * fo].reshape(fi, fo))
        off += fi * fo
        out.append(flat[off:off + fo])
        off += fo
    return out


def _blocks(n: int, block_rows: int) -> int:
    nb = -(-n // block_rows)
    for cand in range(nb, 4 * nb + 1):
        if n % cand == 0:
            return cand
    return 1


def _make_block_fn(sizes: list, lowp: bool):
    import jax
    import jax.numpy as jnp

    def q(a):
        # the tangent of reduce_precision is reduce_precision of the tangent,
        # so the backward matmuls see rounded operands too
        return jax.lax.reduce_precision(a, 8, 3) if lowp else a

    def forward(leaves, x):
        h = x
        for i in range(0, len(leaves) - 2, 2):
            h = jnp.tanh(q(h) @ q(leaves[i]) + leaves[i + 1])
        z = q(h) @ q(leaves[-2]) + leaves[-1]
        return jax.nn.sigmoid(z)[:, 0]

    def block_loss(leaves, x, t, sig):
        sq = (t - forward(leaves, x)) ** 2
        return jnp.sum(sig * 0.5 * sq), sq

    def block(leaves, x, t, sig_t, sig_v):
        (_, sq), grads = jax.value_and_grad(block_loss, has_aux=True)(
            leaves, x, t, sig_t)
        return ([-g for g in grads],
                jnp.sum(sig_t * sq), jnp.sum(sig_v * sq))

    return jax.jit(block)


def _rprop(state, w, g):
    import jax.numpy as jnp

    change = jnp.sign(g * state["last_g"])
    grow = jnp.minimum(state["step"] * ETA_PLUS, STEP_MAX)
    shrink = jnp.maximum(state["step"] * ETA_MINUS, STEP_MIN)
    step = jnp.where(change > 0, grow, jnp.where(change < 0, shrink,
                                                 state["step"]))
    delta = jnp.where(change > 0, jnp.sign(g) * grow,
                      jnp.where(change < 0, -state["last_delta"],
                                jnp.sign(g) * state["step"]))
    last_g = jnp.where(change < 0, 0.0, g)
    return w + delta, {"step": step, "last_g": last_g, "last_delta": delta}


def first_steps(x, t, w, flat0: np.ndarray, sizes: list, seed: int,
                valid_rate: float, steps: int = 3, lowp: bool = False,
                block_rows: int = BLOCK_ROWS, fault: str | None = None) -> dict:
    """Follow the first `steps` full-batch steps from `flat0`. x [n, d], t [n],
    w [n] are device or host arrays; rows are taken a block at a time so that
    the activations of one block, not of the set, live on the device.

    Returns losses [(train, valid) a step], grad1 (the first step's descent
    direction, a list of leaves), change1 and change (weights after the first
    and after the last step less `flat0`, by leaf)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    sig, valid = split_rows(n, seed, valid_rate)
    if fault == "half":  # a planted fault, for reading what it does to the
        # numbers compared: every second row left out, the mean over the rest
        sig = sig * (np.arange(n) % 2 == 0)
    sig_t = jnp.asarray(sig) * w
    sig_v = jnp.asarray(valid) * w
    den_t = float(jnp.maximum(jnp.sum(sig_t), 1.0))
    den_v = float(jnp.maximum(jnp.sum(sig_v), 1.0))
    nb = _blocks(n, block_rows)
    rows = n // nb
    block = _make_block_fn(sizes, lowp)
    flat = jnp.asarray(flat0, jnp.float32)
    state = {"step": jnp.full(flat.shape, STEP_INIT, jnp.float32),
             "last_g": jnp.zeros_like(flat), "last_delta": jnp.zeros_like(flat)}
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for k in range(steps):
            leaves = leaves_of(flat, sizes)
            g_sum, tr, va = None, 0.0, 0.0
            for b in range(nb):
                sl = slice(b * rows, (b + 1) * rows)
                g, a, c = block(leaves, x[sl], t[sl], sig_t[sl], sig_v[sl])
                g_sum = g if g_sum is None else [p + q for p, q in
                                                 zip(g_sum, g)]
                tr, va = tr + a, va + c
            losses.append((float(tr) / den_t, float(va) / den_v))
            g_flat = jnp.concatenate([a.ravel() for a in g_sum])
            if k == 0:
                grad1 = [np.asarray(a) for a in g_sum]
            flat, state = _rprop(state, flat, g_flat)
            if k == 0:
                change1 = np.asarray(flat) - np.asarray(flat0, np.float32)
    change = np.asarray(flat) - np.asarray(flat0, np.float32)
    return {"losses": losses, "grad1": grad1,
            "change1": leaves_of(change1, sizes),
            "change": leaves_of(change, sizes)}
