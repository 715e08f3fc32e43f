"""Per-stage compiled programs: MPMD pipeline bodies for NN and WDL.

One separately jitted program per stage — pinned to its granted device
by committed-input placement (device_put the stage's weights and the
incoming activation onto the device; jit follows). The backward is
GPipe-with-rematerialization: each stage's vjp recomputes its forward
inside the same jit, so no stage ever stores another microbatch's
activations — the only cross-stage traffic is the boundary activation
forward and its cotangent backward.

Precision policy (PR 11, pinned in tests): stage-BOUNDARY activations
are always f32; bf16 appears only inside matmuls when
`mixed_precision` (the `_loss_and_errors` matmul rule, reproduced here
operation-for-operation so the `stages=1` degenerate config is
bit-identical to the monolithic program).

Gradient convention matches train/streaming.py: stages return the
DESCENT direction g = -dL/dw summed over records.
"""

from __future__ import annotations

from typing import List, Tuple

from shifu_tpu.coresident.plan import StagePlan
from shifu_tpu.models.nn import activation_fn
from shifu_tpu.models.wdl import wdl_plane
from shifu_tpu.train.nn_trainer import NNTrainConfig

_PROGRAMS: dict = {}


def _nn_unflatten_group(flat_k, shapes, lo: int, hi: int):
    params, off = [], 0
    for (fi, fo) in shapes[lo:hi]:
        w = flat_k[off: off + fi * fo].reshape(fi, fo)
        off += fi * fo
        b = flat_k[off: off + fo]
        off += fo
        params.append({"W": w, "b": b})
    return params


def _nn_matmul(bf16: bool):
    import jax.numpy as jnp

    def matmul(h, w):
        if bf16:
            return (h.astype(jnp.bfloat16)
                    @ w.astype(jnp.bfloat16)).astype(jnp.float32)
        return h @ w

    return matmul


def make_nn_stage_programs(cfg: NNTrainConfig, plan: StagePlan):
    """{"fwd": [K-1 jitted (flat_k, h) -> h'], "bwd": [K-1 jitted
    (flat_k, h, cot) -> (g_k, cot_in)], "head": jitted (flat_K, h, t,
    sig_t, sig_v, tclass) -> (g_K, cot_in, tr_sum, va_sum, tr_w,
    va_w)}. The head reproduces streaming's shard_grad loss + metric
    math exactly (ONEVSALL transform included)."""
    import jax
    import jax.numpy as jnp

    key = ("nn", tuple(plan.shapes),
           tuple(s.layer_lo for s in plan.stages), tuple(cfg.activations),
           cfg.loss, cfg.mixed_precision)
    cached = _PROGRAMS.get(key)
    if cached is not None:
        return cached

    shapes = plan.shapes
    acts = cfg.activations
    n_hidden = len(shapes) - 1
    out_dim = shapes[-1][1]
    hinge = cfg.loss == "hinge"
    matmul = _nn_matmul(cfg.mixed_precision)

    def group_fwd(flat_k, h, lo, hi):
        params = _nn_unflatten_group(flat_k, shapes, lo, hi)
        for j, gi in enumerate(range(lo, hi)):
            z = matmul(h, params[j]["W"]) + params[j]["b"]
            if gi < n_hidden:
                h = activation_fn(
                    acts[gi % len(acts)] if acts else "tanh")(z)
            else:  # the output layer (last stage only)
                h = z if hinge else activation_fn("sigmoid")(z)
        return h

    def make_fwd(lo, hi):
        @jax.jit
        def fwd(flat_k, h):
            # boundary contract: f32 leaves the stage, whatever lived
            # inside the matmuls
            return group_fwd(flat_k, h, lo, hi).astype(jnp.float32)

        return fwd

    def make_bwd(lo, hi):
        @jax.jit
        def bwd(flat_k, h, cot):
            # remat: the vjp recomputes this stage's forward in-jit
            _, vjp_fn = jax.vjp(
                lambda fk, hh: group_fwd(fk, hh, lo, hi).astype(
                    jnp.float32), flat_k, h)
            g_pos, cot_in = vjp_fn(cot)
            return -g_pos, cot_in.astype(jnp.float32)

        return bwd

    def ideal_of(t):
        if out_dim > 1:
            return jax.nn.one_hot(t.astype(jnp.int32), out_dim,
                                  dtype=jnp.float32)
        return t

    def record_loss(p, ideal):
        if hinge:
            pm = 2.0 * ideal - 1.0
            return jnp.maximum(0.0, 1.0 - pm * p)
        if cfg.loss == "log":
            eps = 1e-7
            pc = jnp.clip(p, eps, 1 - eps)
            e = -(ideal * jnp.log(pc) + (1 - ideal) * jnp.log(1 - pc))
        elif cfg.loss == "absolute":
            e = jnp.abs(ideal - p)
        else:
            e = 0.5 * (ideal - p) ** 2
        return e.sum(axis=-1) if out_dim > 1 else e

    last = plan.stages[-1]

    @jax.jit
    def head(flat_k, h, t, sig_t, sig_v, tclass):
        t2 = jnp.where(tclass >= 0,
                       (t == tclass.astype(t.dtype)).astype(jnp.float32),
                       t)

        def loss(fk, hh):
            out = group_fwd(fk, hh, last.layer_lo, last.layer_hi)
            p = out if out_dim > 1 else out[:, 0]
            return jnp.sum(sig_t * record_loss(p, ideal_of(t2))), p

        (_lv, p), (g_pos, cot_in) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(flat_k, h)
        if hinge:
            p = activation_fn("sigmoid")(p)
        sq = (ideal_of(t2) - p) ** 2
        if out_dim > 1:
            sq = sq.mean(axis=-1)
        return (-g_pos, cot_in.astype(jnp.float32),
                jnp.sum(sig_t * sq), jnp.sum(sig_v * sq),
                jnp.sum(sig_t), jnp.sum(sig_v))

    progs = {
        "fwd": [make_fwd(s.layer_lo, s.layer_hi)
                for s in plan.stages[:-1]],
        "bwd": [make_bwd(s.layer_lo, s.layer_hi)
                for s in plan.stages[:-1]],
        "head": head,
    }
    _PROGRAMS[key] = progs
    return progs


def _wdl_unflatten_group(flat_k, sizes_shapes):
    parts, off = [], 0
    for shp, size in sizes_shapes:
        parts.append(flat_k[off: off + size].reshape(shp))
        off += size
    return parts


def make_wdl_stage_programs(cfg, plan: StagePlan):
    """WDL pipeline bodies. Stage 0 owns the one lookup a field
    (`models/wdl.wdl_plane`, as `wdl_forward` does it) + wide
    tower (its logit is data-only, so it is computed once and carried
    beside the deep activation as one extra f32 column); mid stages
    apply their dense layers; the head owns the output layer, bias and
    the log-loss + squared-error metric math from
    train/streaming_wdl.py, reproduced exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = ("wdl", tuple(plan.shapes), plan.n_cat,
           tuple(s.layer_lo for s in plan.stages),
           tuple(cfg.activations))
    cached = _PROGRAMS.get(key)
    if cached is not None:
        return cached

    shapes = plan.shapes
    n_cat = plan.n_cat
    head_arrays = 2 * n_cat + 1
    n_dense = (len(shapes) - head_arrays - 1) // 2
    n_hidden = n_dense - 1
    acts = cfg.activations

    def sizes_of(a_lo, a_hi):
        return [(shapes[i], int(np.prod(shapes[i])))
                for i in range(a_lo, a_hi)]

    def act_of(gi):
        return activation_fn(
            acts[gi % len(acts)] if acts else "relu")

    def deep_group(layers, h, dlo, dhi):
        # `layers` is a flat list of W, b arrays for dense layers
        # [dlo, dhi); hidden layers get their GLOBAL activation index
        for j, gi in enumerate(range(dlo, dhi)):
            w, b = layers[2 * j], layers[2 * j + 1]
            z = h @ w + b
            h = act_of(gi)(z) if gi < n_hidden else z
        return h

    def make_first(stage):
        a_hi = head_arrays + 2 * stage.layer_hi

        def body(flat_k, dense, codes):
            parts = _wdl_unflatten_group(flat_k, sizes_of(0, a_hi))
            embed = parts[:n_cat]
            wide = parts[n_cat: 2 * n_cat]
            wide_dense = parts[2 * n_cat]
            layers = parts[head_arrays:]
            # the forward's own lookup and wide sum, op for op: K stages
            # reproduce one stage bit for bit
            h, first_w, is_wide = wdl_plane(embed, wide, layers[0], dense,
                                            codes)
            wl = dense @ wide_dense + jnp.dot(
                h, is_wide, precision=jax.lax.Precision.HIGHEST)
            h = deep_group([first_w] + layers[1:], h, stage.layer_lo,
                           stage.layer_hi)
            return h.astype(jnp.float32), wl.astype(jnp.float32)

        @jax.jit
        def fwd(flat_k, dense, codes):
            return body(flat_k, dense, codes)

        @jax.jit
        def bwd(flat_k, dense, codes, cot_h, cot_wl):
            _, vjp_fn = jax.vjp(lambda fk: body(fk, dense, codes),
                                flat_k)
            (g_pos,) = vjp_fn((cot_h, cot_wl))
            return -g_pos

        return fwd, bwd

    def make_mid(stage):
        a_lo = head_arrays + 2 * stage.layer_lo
        a_hi = head_arrays + 2 * stage.layer_hi

        def body(flat_k, h, wl):
            layers = _wdl_unflatten_group(flat_k, sizes_of(a_lo, a_hi))
            h = deep_group(layers, h, stage.layer_lo, stage.layer_hi)
            # the wide logit rides through untouched (identity) so its
            # cotangent routes back to stage 0 with the activation's
            return h.astype(jnp.float32), wl

        @jax.jit
        def fwd(flat_k, h, wl):
            return body(flat_k, h, wl)

        @jax.jit
        def bwd(flat_k, h, wl, cot_h, cot_wl):
            _, vjp_fn = jax.vjp(body, flat_k, h, wl)
            g_pos, cot_h_in, cot_wl_in = vjp_fn((cot_h, cot_wl))
            return (-g_pos, cot_h_in.astype(jnp.float32),
                    cot_wl_in.astype(jnp.float32))

        return fwd, bwd

    last = plan.stages[-1]
    a_lo = head_arrays + 2 * last.layer_lo

    @jax.jit
    def head(flat_k, h, wl, t, sig_t, sig_v):
        def loss(fk, hh, wwl):
            parts = _wdl_unflatten_group(
                fk, sizes_of(a_lo, len(shapes) - 1) + [(shapes[-1], 1)])
            layers, bias = parts[:-1], parts[-1]
            hh = deep_group(layers, hh, last.layer_lo, last.layer_hi)
            logit = hh[:, 0] + wwl + bias[0]
            prob = 1.0 / (1.0 + jnp.exp(-logit))
            eps = 1e-7
            pc = jnp.clip(prob, eps, 1 - eps)
            ll = -(t * jnp.log(pc) + (1 - t) * jnp.log(1 - pc))
            return jnp.sum(sig_t * ll), prob

        (_lv, prob), (g_pos, cot_h, cot_wl) = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(flat_k, h, wl)
        sq = (t - prob) ** 2
        return (-g_pos, cot_h.astype(jnp.float32),
                cot_wl.astype(jnp.float32),
                jnp.sum(sig_t * sq), jnp.sum(sig_v * sq),
                jnp.sum(sig_t), jnp.sum(sig_v))

    first_fwd, first_bwd = make_first(plan.stages[0])
    mids = [make_mid(s) for s in plan.stages[1:-1]]
    progs = {
        "first_fwd": first_fwd,
        "first_bwd": first_bwd,
        "mid_fwd": [m[0] for m in mids],
        "mid_bwd": [m[1] for m in mids],
        "head": head,
    }
    _PROGRAMS[key] = progs
    return progs
