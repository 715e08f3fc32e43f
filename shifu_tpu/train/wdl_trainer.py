"""WDL trainer — same jit while_loop harness as the NN trainer, over the
flattened wide&deep parameter vector.

Parity: wdl/WDLMaster.java:65 (master merges gradients + optimizer step) and
wdl/WDLWorker.java (per-record fwd/bwd) collapse into one SPMD program; the
optimizer set (wdl/optimization/*: GradientDescent, AdaGrad + the shared
Propagation/ADAM family) reuses shifu_tpu.train.updaters. Loss is weighted
log loss (the reference's WDL trains sigmoid + cross-entropy).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from shifu_tpu.models.wdl import (
    WDLParams,
    flatten_wdl,
    init_wdl_params,
    unflatten_wdl,
    unflatten_wdl_from_shapes,
    wdl_forward,
    wdl_shapes,
)
from shifu_tpu.obs import profile, registry, span
from shifu_tpu.resilience.checkpoint import atomic_save_npy
from shifu_tpu.train.updaters import make_updater
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)


@dataclass
class WDLTrainConfig:
    hidden: List[int] = field(default_factory=lambda: [100, 50])
    activations: List[str] = field(default_factory=lambda: ["relu", "relu"])
    embed_dim: int = 8
    learning_rate: float = 0.005
    optimizer: str = "ADAM"
    l2_reg: float = 0.0
    num_epochs: int = 100
    valid_set_rate: float = 0.2
    bagging_sample_rate: float = 1.0
    bagging_with_replacement: bool = False
    early_stop_window: int = 0
    seed: int = 0
    checkpoint_every: int = 0
    checkpoint_path: Optional[str] = None
    progress_cb: Optional[object] = None

    @classmethod
    def from_model_config(cls, mc, trainer_id: int = 0) -> "WDLTrainConfig":
        t = mc.train

        def g(key, default):
            v = t.get_param(key, default)
            return default if v is None else v

        return cls(
            hidden=[int(x) for x in g("NumHiddenNodes", [100, 50])],
            activations=[str(a) for a in g("ActivationFunc", ["relu", "relu"])],
            embed_dim=int(g("EmbedOutputs", 8)),
            learning_rate=float(g("LearningRate", 0.005)),
            optimizer=str(g("Optimizer", "ADAM")).upper(),
            l2_reg=float(g("L2Reg", 0.0) or g("RegularizedConstant", 0.0)),
            num_epochs=int(t.num_train_epochs or 100),
            valid_set_rate=float(t.valid_set_rate or 0.0),
            bagging_sample_rate=float(t.bagging_sample_rate or 1.0),
            bagging_with_replacement=bool(t.bagging_with_replacement),
            early_stop_window=int(g("EarlyStopWindowSize", 0)),
            seed=trainer_id * 1000 + 23,
        )


@dataclass
class WDLTrainResult:
    params: WDLParams
    train_error: float
    valid_error: float
    iterations: int


_PROGRAMS: Dict[tuple, object] = {}


def _onehot_plan(vocab_sizes, width: int, mesh) -> tuple:
    """(fields, interpret): the fields whose lookups' transpose is the
    one-hot kernel (`models/wdl.wdl_plane`), which are the tables of at
    most 4,096 rows that one call at `width` (an embedding row and the wide
    weight) holds in VMEM (`onehot_grad_pallas.small_fields`), where the
    kernel runs (`hist_pallas.pallas_active`: a TPU, or interpret mode
    where -Dshifu.pallas.mode=on) and with no mesh (a sharded table keeps
    XLA's partitioned scatter); else no field. The program may be vmapped
    (`train_wdl_bagged`): the kernel is too."""
    if mesh is not None:
        return (), False
    from shifu_tpu.ops.hist_pallas import pallas_active

    enabled, interpret = pallas_active()
    if not enabled:
        return (), False
    from shifu_tpu.ops.onehot_grad_pallas import small_fields

    return small_fields(vocab_sizes, width), interpret


def _get_program(cfg: WDLTrainConfig, template: WDLParams, mesh=None):
    import jax
    import jax.numpy as jnp

    # tensor parallelism: when the mesh has a `model` axis, embedding tables
    # are constrained to shard their embed dim across it — XLA inserts the
    # all-gathers/reduce-scatters (SURVEY §2.8: TP for wide WDL vocab tables)
    embed_sharding = None
    if mesh is not None and "model" in mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P

        embed_sharding = NamedSharding(mesh, P(None, "model"))

    # close over shapes only — retaining `template`'s arrays in the cached
    # closure would pin every initial 10k-vocab embedding table forever
    shapes = wdl_shapes(template)
    n_cat = len(template.embed)
    vocab = [int(e.shape[0]) for e in template.embed]
    fields, interpret = _onehot_plan(
        vocab, int(template.embed[0].shape[1]) + 1 if n_cat else 1, mesh)
    key = (tuple(shapes), n_cat, tuple(cfg.activations), cfg.optimizer,
           cfg.l2_reg, cfg.early_stop_window, embed_sharding, fields,
           interpret)
    if key in _PROGRAMS:
        return _PROGRAMS[key]

    init_state, apply_update = make_updater(
        cfg.optimizer if cfg.optimizer != "GD" else "B",
        momentum=0.0,
        reg=cfg.l2_reg,
        reg_level="L2" if cfg.l2_reg else "NONE",
    )
    window = cfg.early_stop_window

    def loss_fn(flat, dense, codes, codes_t, t, sig):
        p = unflatten_wdl_from_shapes(flat, shapes, n_cat)
        if embed_sharding is not None:
            p.embed = [
                jax.lax.with_sharding_constraint(e, embed_sharding)
                for e in p.embed
            ]
        prob = wdl_forward(p, dense, codes, cfg.activations,
                           onehot=(fields, codes_t, interpret) if fields
                           else None)
        with jax.named_scope("wdl.loss"):
            eps = 1e-7
            pc = jnp.clip(prob, eps, 1 - eps)
            ll = -(t * jnp.log(pc) + (1 - t) * jnp.log(1 - pc))
            return jnp.sum(sig * ll), prob

    grad_fn = jax.grad(loss_fn, has_aux=True)

    # Named scopes (`wdl.embed`, `wdl.wide`, `wdl.deep` in wdl_forward,
    # `wdl.loss`, `wdl.update` here) are op metadata only and not in the
    # compile cache's key.
    def one_iter(carry, dense, codes, codes_t, t, sig_tr, sig_va, nts, lr):
        (flat, opt, it, best_val, best_flat, bad, halt, tr_e, va_e) = carry
        g_neg, prob = grad_fn(flat, dense, codes, codes_t, t, sig_tr)
        g = -g_neg
        with jax.named_scope("wdl.loss"):
            sq = (t - prob) ** 2
            tr = jnp.sum(sig_tr * sq) / jnp.maximum(jnp.sum(sig_tr), 1.0)
            va = jnp.sum(sig_va * sq) / jnp.maximum(jnp.sum(sig_va), 1.0)
        with jax.named_scope("wdl.update"):
            new_flat, new_opt = apply_update(opt, flat, g, lr, it + 1, nts)
            improved = va < best_val
            best_val2 = jnp.where(improved, va, best_val)
            best_flat2 = jnp.where(improved, flat, best_flat)
            bad2 = jnp.where(improved, 0, bad + 1)
        halt2 = (bad2 >= window) if window > 0 else jnp.zeros((), bool)
        return (new_flat, new_opt, it + 1, best_val2, best_flat2, bad2,
                halt2, tr, va)

    @jax.jit
    def program(carry, limit, dense, codes, t, sig_tr, sig_va, nts, lr):
        # the one-hot kernel's codes operand does not change from one epoch
        # to the next: made once, before the loop
        codes_t = None
        if fields:
            from shifu_tpu.ops.onehot_grad_pallas import codes_operand

            codes_t = codes_operand(codes, fields, vocab)

        def cond(c):
            return (c[2] < limit) & (~c[6])

        def body(c):
            return one_iter(c, dense, codes, codes_t, t, sig_tr, sig_va, nts,
                            lr)

        return jax.lax.while_loop(cond, body, carry)

    _PROGRAMS[key] = (program, init_state)
    return _PROGRAMS[key]


def _to_host_params(chosen: np.ndarray, template: WDLParams) -> WDLParams:
    params = unflatten_wdl(chosen, template)
    return WDLParams(
        embed=[np.asarray(a) for a in params.embed],
        wide=[np.asarray(a) for a in params.wide],
        wide_dense=np.asarray(params.wide_dense),
        dense_layers=[{k: np.asarray(v) for k, v in l.items()}
                      for l in params.dense_layers],
        bias=np.asarray(params.bias),
    )


def train_wdl(
    dense: np.ndarray,
    codes: np.ndarray,
    tags: np.ndarray,
    weights: np.ndarray,
    vocab_sizes: List[int],
    cfg: WDLTrainConfig,
    mesh=None,
    init_flat: Optional[np.ndarray] = None,
) -> WDLTrainResult:
    """One WDL model. `init_flat` resumes continuous training from existing
    weights (checkContinuousTraining parity, like the NN path)."""
    import jax
    import jax.numpy as jnp

    # spans, counters and their names mirror train_nn's (one reader idiom
    # serves both); the body stays in this frame, as there
    n = dense.shape[0]
    n_cat = len(vocab_sizes)
    call = int(registry().counter("train.calls", engine="wdl").inc())
    with span("train.wdl.call", call=call, rows=int(n), fields=n_cat,
              epochs=int(cfg.num_epochs)):
        with span("train.wdl.prologue", call=call):
            template = init_wdl_params(
                dense.shape[1], vocab_sizes, cfg.embed_dim, cfg.hidden,
                seed=cfg.seed
            )
            flat0 = flatten_wdl(template)
            if init_flat is not None and init_flat.size == flat0.size:
                flat0 = init_flat.astype(np.float32)

            d = dense.astype(np.float32) if not isinstance(dense, jax.Array) else dense
            c = codes.astype(jnp.int32) if isinstance(codes, jax.Array) else codes.astype(np.int32)
            t = tags.astype(np.float32) if not isinstance(tags, jax.Array) else tags
            if mesh is None:
                # deterministic draw rides the NN trainer's device cache —
                # repeat runs transfer zero sampling bytes (remote TPU links)
                from shifu_tpu.train.nn_trainer import _device_split_and_sample

                sig_d, valid_d, nts = _device_split_and_sample(n, cfg)
                w_d = (weights if isinstance(weights, jax.Array)
                       else jnp.asarray(np.asarray(weights, np.float32)))
                sig_tr = sig_d * w_d
                sig_va = valid_d * w_d
            else:
                from shifu_tpu.parallel.mesh import (
                    pad_rows,
                    row_shard_count,
                    shard_rows,
                )
                from shifu_tpu.train.nn_trainer import split_and_sample

                sig, valid = split_and_sample(n, cfg)
                sig_tr = (sig * np.asarray(weights)).astype(np.float32)
                sig_va = (valid.astype(np.float32)
                          * np.asarray(weights)).astype(np.float32)
                nts = float(max(sig.sum(), 1.0))
                n_data = row_shard_count(mesh)
                (d, c, t, sig_tr, sig_va), _ = pad_rows(
                    [d, c, t, sig_tr, sig_va], n_data)
                d = shard_rows(d, mesh)
                c = shard_rows(c, mesh)
                t = shard_rows(t, mesh)
                sig_tr = shard_rows(sig_tr, mesh)
                sig_va = shard_rows(sig_va, mesh)

            program, init_state = _get_program(cfg, template, mesh=mesh)
            opt0 = init_state(flat0.size)
            flat_j = jnp.asarray(flat0)
            if mesh is not None:
                from shifu_tpu.parallel.mesh import replicate

                flat_j = replicate(flat_j, mesh)
                opt0 = replicate(opt0, mesh)

            carry = (
                flat_j, opt0, jnp.int32(0), jnp.float32(np.inf), flat_j,
                jnp.int32(0), jnp.zeros((), bool), jnp.float32(0.0),
                jnp.float32(0.0),
            )
            nts_j = jnp.float32(nts)
            lr_j = jnp.float32(cfg.learning_rate)

        def run_until(cr, limit):
            with span("train.wdl.program", call=call, limit=int(limit)):
                return profile.dispatch(
                    "wdl.train_program", program, cr, jnp.int32(limit), d, c,
                    t, sig_tr, sig_va, nts_j, lr_j, sync=True)

        if cfg.checkpoint_every and cfg.checkpoint_every > 0:
            it = 0
            while it < cfg.num_epochs:
                limit = min(it + cfg.checkpoint_every, cfg.num_epochs)
                with profile.scaled(limit - it):
                    carry = run_until(carry, limit)
                it = int(carry[2])
                if cfg.progress_cb:
                    cfg.progress_cb(it, float(carry[7]), float(carry[8]))
                if cfg.checkpoint_path:
                    atomic_save_npy(cfg.checkpoint_path, np.asarray(carry[0]))
                if bool(carry[6]) or it >= cfg.num_epochs:
                    break
            result = carry
        else:
            with profile.scaled(cfg.num_epochs):
                result = run_until(carry, cfg.num_epochs)
        (flat_f, _, it_f, best_val, best_flat, _, _, tr_e, va_e) = result

        # one host round-trip for all scalars (serial casts pay an RTT each
        # on remote TPU links), then the chosen weights
        with span("train.wdl.pull", call=call) as pulled:
            scalars = jax.device_get((it_f, best_val, tr_e, va_e))
            it_h, bv, tr_h, va_h = (a.item() for a in scalars)
            use_best = cfg.valid_set_rate > 0 and math.isfinite(bv)
            chosen = np.asarray(best_flat if use_best else flat_f)
            pulled["bytes"] = sum(a.nbytes for a in scalars) + chosen.nbytes
        params = _to_host_params(chosen, template)
        final_valid = float(bv) if use_best else float(va_h)
        reg = registry()
        reg.gauge("train.train_error").set(float(tr_h))
        reg.gauge("train.valid_error").set(final_valid)
        reg.counter("train.iterations").inc(int(it_h))
        log.info("wdl train done: %d iterations, train_err %.6f valid_err %.6f",
                 int(it_h), float(tr_h), final_valid)
        return WDLTrainResult(
            params=params, train_error=float(tr_h), valid_error=final_valid,
            iterations=int(it_h),
        )


def train_wdl_bagged(
    dense: np.ndarray,
    codes: np.ndarray,
    tags: np.ndarray,
    weights: np.ndarray,
    vocab_sizes: List[int],
    base_cfg: WDLTrainConfig,
    n_members: int,
    mesh=None,
    init_flats: Optional[List[Optional[np.ndarray]]] = None,
    member_lrs: Optional[List[float]] = None,
    member_sigs: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    checkpoint_paths: Optional[List[str]] = None,
) -> List[WDLTrainResult]:
    """All bagging members / grid trials / k-folds as ONE vmapped program —
    the WDL twin of train_nn_bagged (the reference fans WDL bagging out as
    Guagua jobs exactly like NN, TrainModelProcessor.java:768-945 +
    prepareWDLParams :1474).

    `member_lrs` batches grid trials that differ only in LearningRate;
    `member_sigs` (sig_train [M, n], sig_valid [M, n]) batches k-fold folds
    with unbiased final-weights holdout semantics."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.train.nn_trainer import split_and_sample

    n = dense.shape[0]
    M = n_members
    template = init_wdl_params(
        dense.shape[1], vocab_sizes, base_cfg.embed_dim, base_cfg.hidden,
        seed=base_cfg.seed,
    )
    flat0s, sig_ts, sig_vs, ntss = [], [], [], []
    for i in range(M):
        seed_i = base_cfg.seed + i * 1000
        tpl_i = init_wdl_params(
            dense.shape[1], vocab_sizes, base_cfg.embed_dim, base_cfg.hidden,
            seed=seed_i,
        )
        flat0 = flatten_wdl(tpl_i)
        init_i = (init_flats or [None] * M)[i]
        if init_i is not None and init_i.size == flat0.size:
            flat0 = init_i.astype(np.float32)
        flat0s.append(flat0)
        if member_sigs is not None:
            sig_ts.append(np.asarray(member_sigs[0][i], np.float32))
            sig_vs.append(np.asarray(member_sigs[1][i], np.float32))
            ntss.append(float(max((member_sigs[0][i] > 0).sum(), 1.0)))
        else:
            cfg_i = WDLTrainConfig(**{**base_cfg.__dict__, "seed": seed_i})
            sig, valid = split_and_sample(n, cfg_i)
            sig_ts.append((sig * weights).astype(np.float32))
            sig_vs.append(
                (valid.astype(np.float32) * weights).astype(np.float32)
            )
            ntss.append(float(max(sig.sum(), 1.0)))

    d = dense.astype(np.float32)
    c = codes.astype(np.int32)
    t = tags.astype(np.float32)
    sig_t = np.stack(sig_ts)
    sig_v = np.stack(sig_vs)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from shifu_tpu.parallel.mesh import pad_rows, shard_rows

        from shifu_tpu.parallel.mesh import row_shard_count

        n_data = row_shard_count(mesh)
        (d, c, t), _ = pad_rows([d, c, t], n_data)
        sig_t = np.pad(sig_t, ((0, 0), (0, d.shape[0] - n)))
        sig_v = np.pad(sig_v, ((0, 0), (0, d.shape[0] - n)))
        d = shard_rows(d, mesh)
        c = shard_rows(c, mesh)
        t = shard_rows(t, mesh)
        from shifu_tpu.parallel.mesh import row_axes as _raxes

        member_rows = NamedSharding(mesh, P(None, _raxes(mesh)))
        sig_t = jax.device_put(sig_t, member_rows)
        sig_v = jax.device_put(sig_v, member_rows)

    program, init_state = _get_program(base_cfg, template, mesh=mesh)
    bag_key = ("wdl-bagged", id(program), M)
    program_b = _PROGRAMS.get(bag_key)
    if program_b is None:
        program_b = jax.jit(
            jax.vmap(program,
                     in_axes=(0, None, None, None, None, 0, 0, 0, 0))
        )
        _PROGRAMS[bag_key] = program_b

    n_flat = flat0s[0].size
    flat_j = jnp.asarray(np.stack(flat0s))
    opt0 = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *[init_state(n_flat) for _ in range(M)]
    )
    if mesh is not None:
        from shifu_tpu.parallel.mesh import replicate

        flat_j = replicate(flat_j, mesh)
        opt0 = replicate(opt0, mesh)
    carry = (
        flat_j, opt0, jnp.zeros(M, jnp.int32),
        jnp.full(M, np.inf, jnp.float32), flat_j, jnp.zeros(M, jnp.int32),
        jnp.zeros(M, bool), jnp.zeros(M, jnp.float32),
        jnp.zeros(M, jnp.float32),
    )
    nts_j = jnp.asarray(ntss, jnp.float32)
    lrs = (jnp.asarray(member_lrs, jnp.float32) if member_lrs is not None
           else jnp.full(M, base_cfg.learning_rate, jnp.float32))

    def run_until(cr, limit):
        # the vmapped program's cost analysis covers all M members per
        # loop body already, so scaled() credits epochs only
        return profile.dispatch(
            "wdl.train_program_bagged", program_b, cr, jnp.int32(limit),
            d, c, t, sig_t, sig_v, nts_j, lrs, sync=True)

    if base_cfg.checkpoint_every and base_cfg.checkpoint_every > 0:
        it = 0
        last_reported = [-1] * M
        while it < base_cfg.num_epochs:
            limit = min(it + base_cfg.checkpoint_every,
                        base_cfg.num_epochs)
            with profile.scaled(limit - it):
                carry = run_until(carry, limit)
            it = int(np.asarray(carry[2]).max())
            its = np.asarray(carry[2])
            trs, vas = np.asarray(carry[7]), np.asarray(carry[8])
            flats = np.asarray(carry[0])
            for i in range(M):
                it_i = int(its[i])
                if it_i == last_reported[i]:
                    continue  # member already halted
                last_reported[i] = it_i
                if base_cfg.progress_cb:
                    base_cfg.progress_cb((i, it_i), float(trs[i]),
                                         float(vas[i]))
                if checkpoint_paths and checkpoint_paths[i]:
                    atomic_save_npy(checkpoint_paths[i], flats[i])
            if bool(np.asarray(carry[6]).all()) or it >= base_cfg.num_epochs:
                break
        out = carry
    else:
        with profile.scaled(base_cfg.num_epochs):
            out = run_until(carry, base_cfg.num_epochs)
    (flat_f, _, it_f, best_val, best_flat, _, _, tr_e, va_e) = out

    results = []
    flat_f_np = np.asarray(flat_f)
    best_flat_np = np.asarray(best_flat)
    for i in range(M):
        bv = float(np.asarray(best_val)[i])
        use_best = (member_sigs is None and base_cfg.valid_set_rate > 0
                    and math.isfinite(bv))
        chosen = best_flat_np[i] if use_best else flat_f_np[i]
        results.append(WDLTrainResult(
            params=_to_host_params(chosen, template),
            train_error=float(np.asarray(tr_e)[i]),
            valid_error=bv if use_best else float(np.asarray(va_e)[i]),
            iterations=int(np.asarray(it_f)[i]),
        ))
    log.info("wdl bagged train done: %d members in one program, avg valid "
             "%.6f", M, float(np.mean([r.valid_error for r in results])))
    return results
