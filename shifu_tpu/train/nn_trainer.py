"""Distributed NN/LR trainer — one jit-compiled SPMD program per training run.

What the reference spreads across NNMaster/NNWorker/Guagua/ZooKeeper
(SURVEY §3.1: per-iteration Bytable exchange, master gradient sum, Weight
update, early-stop halt flag) collapses here into a single
`lax.while_loop` inside jit:

    worker shard gradients  -> row-sharded jnp.dot; XLA all-reduces (psum)
                               when producing the replicated gradient
    master Weight update    -> updaters.make_updater pure function
    ZK halt flag            -> replicated bool in the loop carry
    NNOutput checkpoints    -> host callback every `checkpoint_every` iters

The gradient convention is Encog's: g = -dE/dw SUMMED over records (NNMaster
sums worker gradients, NNMaster.java:240-249), error reported as the
significance-weighted mean. LR decay per iteration (NNMaster.java:267),
window early stop (earlystop/WindowEarlyStop.java:23), convergence threshold
(ConvergeAndValidToleranceEarlyStop.java:22). Mini-batching via rotating
contiguous chunks (MiniBatchs param, AbstractNNWorker). Bagging/validation
sampling parity: AbstractNNWorker.sampleWeights:668 — Poisson counts when
baggingWithReplacement else Bernoulli keep-mask.

LR (algorithm=LR) is the same trainer with zero hidden layers and log loss
(lr/LogisticRegressionWorker.java:302 computes the same sigmoid gradient).
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from shifu_tpu.analysis import sanitize
from shifu_tpu.models.nn import (
    activation_fn,
    flatten_params,
    init_params,
    unflatten_params,
)
from shifu_tpu.obs import profile, registry, span
from shifu_tpu.resilience.checkpoint import atomic_save_npy
from shifu_tpu.train.updaters import make_updater
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)


@dataclass
class NNTrainConfig:
    hidden_nodes: List[int] = field(default_factory=lambda: [50])
    activations: List[str] = field(default_factory=lambda: ["tanh"])
    learning_rate: float = 0.1
    propagation: str = "Q"
    momentum: float = 0.5
    learning_decay: float = 0.0
    regularized_constant: float = 0.0
    reg_level: str = "NONE"  # NONE | L1 | L2 (RegulationLevel.java)
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    num_epochs: int = 100
    mini_batchs: int = 1  # epoch split count; 1 = full batch
    dropout_rate: float = 0.0
    loss: str = "squared"  # squared | log | absolute (nn/*ErrorCalculation)
    valid_set_rate: float = 0.2
    bagging_sample_rate: float = 1.0
    bagging_with_replacement: bool = False
    early_stop_window: int = 0  # 0 = disabled
    convergence_threshold: float = 0.0
    weight_init: str = "xavier"
    n_classes: int = 2  # >2 = NATIVE multi-class: one-hot ideal, K sigmoid outputs
    seed: int = 0
    is_continuous: bool = False
    mixed_precision: bool = False  # bf16 matmuls (MXU), f32 accumulation
    checkpoint_every: int = 0
    checkpoint_path: Optional[str] = None
    progress_cb: Optional[Callable[[int, float, float], None]] = None

    @classmethod
    def from_model_config(cls, mc, trainer_id: int = 0) -> "NNTrainConfig":
        """Wire train.params the way TrainModelProcessor.prepareNNParams
        (TrainModelProcessor.java:1338) feeds NNMaster/Workers."""
        t = mc.train
        p = t.params or {}

        def g(key, default):
            v = t.get_param(key, default)
            return default if v is None else v

        alg = t.algorithm.value if hasattr(t.algorithm, "value") else str(t.algorithm)
        hidden = list(g("NumHiddenNodes", [50]))
        acts = [str(a) for a in g("ActivationFunc", ["tanh"])]
        if alg == "LR":
            hidden, acts = [], []
        if alg == "SVM":
            # liblinear parity (core/alg/SVMTrainer.java:38): linear
            # kernel only, L2-regularized hinge with Const -> C (reg=1/C).
            kernel = str(g("Kernel", "linear")).lower()
            if kernel != "linear":
                raise ValueError(
                    f"SVM Kernel={kernel!r} is not supported — the TPU "
                    "build trains the liblinear path (linear kernel); use "
                    "Kernel=linear or algorithm=NN")
            c_const = float(g("Const", 1.0))
            return cls(
                n_classes=2,
                hidden_nodes=[], activations=[], loss="hinge",
                learning_rate=float(g("LearningRate", 0.1)),
                propagation=str(g("Propagation", "Q")),
                reg_level="L2",
                regularized_constant=1.0 / max(c_const, 1e-12),
                num_epochs=int(t.num_train_epochs or 100),
                valid_set_rate=float(t.valid_set_rate or 0.0),
                bagging_sample_rate=float(t.bagging_sample_rate or 1.0),
                bagging_with_replacement=bool(t.bagging_with_replacement),
                early_stop_window=int(g("EarlyStopWindowSize", 0)),
                convergence_threshold=float(t.convergence_threshold or 0.0),
                seed=trainer_id * 1000 + 7,
            )
        # NATIVE multi-class: K output nodes, one-hot ideal (NNWorker.java:128
        # "ideal[ideaIndex] = 1f"); ONEVSALL stays binary per trainer.
        n_classes = 2
        if mc.is_multi_classification() and not t.is_one_vs_all():
            n_classes = len(mc.tags())
        return cls(
            n_classes=n_classes,
            hidden_nodes=hidden,
            activations=acts,
            learning_rate=float(g("LearningRate", 0.1)),
            propagation=str(g("Propagation", "Q")),
            momentum=float(g("Momentum", 0.5)),
            learning_decay=float(g("LearningDecay", 0.0)),
            regularized_constant=float(g("RegularizedConstant", 0.0)),
            reg_level=str(g("L1orL2", "NONE")).upper(),
            adam_beta1=float(g("AdamBeta1", 0.9)),
            adam_beta2=float(g("AdamBeta2", 0.999)),
            num_epochs=int(t.num_train_epochs or 100),
            mini_batchs=max(1, int(g("MiniBatchs", 1))),
            dropout_rate=float(g("DropoutRate", 0.0)),
            loss=str(g("Loss", "log" if alg == "LR" else "squared")).lower(),
            valid_set_rate=float(t.valid_set_rate or 0.0),
            bagging_sample_rate=float(t.bagging_sample_rate or 1.0),
            bagging_with_replacement=bool(t.bagging_with_replacement),
            early_stop_window=int(g("EarlyStopWindowSize", 0)),
            convergence_threshold=float(t.convergence_threshold or 0.0),
            weight_init=str(g("WeightInitializer", "xavier")).lower(),
            seed=trainer_id * 1000 + 7,
        )


@dataclass
class TrainResult:
    params: List[Dict[str, np.ndarray]]
    train_error: float
    valid_error: float
    iterations: int
    history: List[Tuple[int, float, float]] = field(default_factory=list)


def split_and_sample(
    n: int, cfg: NNTrainConfig
) -> Tuple[np.ndarray, np.ndarray]:
    """(train significance multiplier [n], valid mask [n]) — bagging sampling
    parity with AbstractNNWorker.sampleWeights:668."""
    rng = np.random.default_rng(cfg.seed)
    valid = rng.random(n) < cfg.valid_set_rate
    if cfg.bagging_with_replacement:
        sig = rng.poisson(cfg.bagging_sample_rate, size=n).astype(np.float32)
    else:
        sig = (rng.random(n) < cfg.bagging_sample_rate).astype(np.float32)
    sig[valid] = 0.0
    return sig, valid


# Device-resident sampling draws, keyed by everything that determines them.
# The draw is a pure function of (n, seed, rates), so repeated runs on the
# same dataset (grid members, benches, retrains) skip the host->device
# transfer of two [n] f32 masks — on a remote TPU link that transfer
# costs more than the training itself for small nets.
_SAMPLE_CACHE: Dict[tuple, tuple] = {}


def _device_split_and_sample(n: int, cfg: NNTrainConfig):
    """(sig [n] f32 device, valid_f [n] f32 device, n_train_size)."""
    import jax

    key = (n, cfg.seed, round(float(cfg.valid_set_rate), 9),
           round(float(cfg.bagging_sample_rate), 9),
           bool(cfg.bagging_with_replacement))
    ent = _SAMPLE_CACHE.get(key)
    if ent is None:
        sig, valid = split_and_sample(n, cfg)
        # bound cached BYTES, not entry count (8 masks of a 20M-row set
        # would pin >1 GB of HBM past the training step otherwise)
        cached = sum(e[0].size * 8 for e in _SAMPLE_CACHE.values())
        if cached + n * 8 > (128 << 20):
            _SAMPLE_CACHE.clear()
        ent = (jax.device_put(sig),
               jax.device_put(valid.astype(np.float32)),
               float(max(sig.sum(), 1.0)))
        _SAMPLE_CACHE[key] = ent
    return ent


def _loss_and_errors(cfg: NNTrainConfig, shapes):
    """Build the jit-able (flat_w, x, t, sig_train, sig_valid, key) ->
    (descent_grad, train_err, valid_err) function."""
    import jax
    import jax.numpy as jnp

    acts = cfg.activations
    n_hidden = len(cfg.hidden_nodes)
    dropout = cfg.dropout_rate
    bf16 = cfg.mixed_precision
    # output width comes from the final layer shape; >1 means NATIVE
    # multi-class (t holds class indices, ideal is one-hot)
    out_dim = shapes[-1][1]
    # hinge = linear SVM (core/alg/SVMTrainer.java:38 trains liblinear):
    # the forward value is the RAW decision w.x + b, the loss is
    # max(0, 1 - y*f(x)) with y in {-1,+1}; L2 regularization carries
    # liblinear's C via reg = 1/C (see NNTrainConfig.from_model_config)
    hinge = cfg.loss == "hinge"

    def unflatten(flat):
        params, off = [], 0
        for (fi, fo) in shapes:
            w = flat[off : off + fi * fo].reshape(fi, fo)
            off += fi * fo
            b = flat[off : off + fo]
            off += fo
            params.append({"W": w, "b": b})
        return params

    def matmul(h, w):
        if bf16:  # MXU-friendly: bf16 operands, f32 result (bf16
            # activations measured SLOWER on v5e — the elementwise chain
            # between matmuls does not repay the extra converts)
            return (h.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)).astype(
                jnp.float32
            )
        return h @ w

    def fwd(params, x, key, train: bool):
        h = x
        for i in range(n_hidden):
            h = activation_fn(acts[i % len(acts)] if acts else "tanh")(
                matmul(h, params[i]["W"]) + params[i]["b"]
            )
            if train and dropout > 0.0:
                key, sub = jax.random.split(key)
                keep = jax.random.bernoulli(sub, 1.0 - dropout, h.shape)
                h = jnp.where(keep, h / (1.0 - dropout), 0.0)
        out = matmul(h, params[-1]["W"]) + params[-1]["b"]
        if not hinge:  # SVM keeps the raw decision value
            out = activation_fn("sigmoid")(out)
        return out if out_dim > 1 else out[:, 0]

    def ideal_of(t):
        """Targets: binary t in {0,1} [n]; multi-class t is the class index
        and the ideal vector is one-hot over K sigmoid outputs
        (NNWorker.java:128)."""
        if out_dim > 1:
            return jax.nn.one_hot(t.astype(jnp.int32), out_dim,
                                  dtype=jnp.float32)
        return t

    def record_loss(p, ideal):
        if hinge:
            pm = 2.0 * ideal - 1.0  # {0,1} -> {-1,+1}
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

    def total_loss(flat, x, t, sig, key):
        params = unflatten(flat)
        with jax.named_scope("nn.fwd"):
            p = fwd(params, x, key, train=True)
        return jnp.sum(sig * record_loss(p, ideal_of(t))), p

    grad_fn = jax.grad(total_loss, has_aux=True)

    # Named scopes are op metadata only (the `tf_op` a profiler trace shows
    # for each device operation). Under `nn.bwd` jax names the forward half
    # `jvp(nn.fwd)` and the backward half `transpose(jvp(nn.fwd))`.
    def step_metrics(flat, x, t, sig_train, sig_valid, key):
        with jax.named_scope("nn.bwd"):
            g_neg, p_train = grad_fn(flat, x, t, sig_train, key)
            g = -g_neg  # descent direction, summed over records
        with jax.named_scope("nn.valid"):
            if dropout > 0.0:
                # dropout-free predictions for error reporting
                p = fwd(unflatten(flat), x, key, train=False)
            else:
                p = p_train
            # reported errors are squared-error means like Encog
            # calculateError (multi-class: mean over the K output neurons
            # as well); the SVM decision value maps through sigmoid first
            # so its error lives on the same [0,1] scale (saved models
            # score sigmoid(w.x+b) too)
            if hinge:
                p = activation_fn("sigmoid")(p)
            sq = (ideal_of(t) - p) ** 2
            if out_dim > 1:
                sq = sq.mean(axis=-1)
            train_err = (jnp.sum(sig_train * sq)
                         / jnp.maximum(jnp.sum(sig_train), 1.0))
            valid_err = (jnp.sum(sig_valid * sq)
                         / jnp.maximum(jnp.sum(sig_valid), 1.0))
        return g, train_err, valid_err

    return step_metrics


# Compiled-program cache: one XLA program per (architecture, hyperparams)
# signature; data, seed, epoch limit and sample size are traced arguments so
# bagging members, grid trials with same arch, and bench warmups all reuse it.
_PROGRAMS: dict = {}


def _get_program(cfg: NNTrainConfig, shapes, rows: int):
    import jax
    import jax.numpy as jnp

    n_batches = cfg.mini_batchs
    cache_key = (
        tuple(shapes), tuple(cfg.activations), cfg.loss, cfg.dropout_rate,
        cfg.mixed_precision, n_batches, rows if n_batches > 1 else -1,
        cfg.early_stop_window, cfg.convergence_threshold, cfg.learning_decay,
        (cfg.propagation or "Q").upper(), cfg.momentum,
        cfg.regularized_constant, cfg.reg_level, cfg.adam_beta1, cfg.adam_beta2,
    )
    cached = _PROGRAMS.get(cache_key)
    if cached is not None:
        return cached

    step_metrics = _loss_and_errors(cfg, shapes)
    init_state, apply_update = make_updater(
        cfg.propagation,
        momentum=cfg.momentum,
        reg=cfg.regularized_constant,
        reg_level=cfg.reg_level,
        adam_beta1=cfg.adam_beta1,
        adam_beta2=cfg.adam_beta2,
    )
    window = cfg.early_stop_window
    conv = cfg.convergence_threshold
    decay = cfg.learning_decay
    # ceil so rotating slices cover every row (last slice overlaps the tail
    # instead of dropping rows % n_batches records from all gradients)
    batch = -(-rows // n_batches) if n_batches > 1 else rows

    def one_iter(carry, x, t, sig_train, sig_valid, key0, nts):
        (flat, opt, it, lr, best_val, best_flat, bad, halt, tr_e, va_e) = carry
        key = jax.random.fold_in(key0, it)
        if n_batches > 1:
            start = jnp.minimum((it % n_batches) * batch, rows - batch)
            xs = jax.lax.dynamic_slice_in_dim(x, start, batch, 0)
            ts = jax.lax.dynamic_slice_in_dim(t, start, batch, 0)
            ss = jax.lax.dynamic_slice_in_dim(sig_train, start, batch, 0)
            g, _, _ = step_metrics(flat, xs, ts, ss, ss, key)
            _, tr, va = step_metrics(flat, x, t, sig_train, sig_valid, key)
        else:
            g, tr, va = step_metrics(flat, x, t, sig_train, sig_valid, key)
        with jax.named_scope("nn.update"):
            new_flat, new_opt = apply_update(opt, flat, g, lr, it + 1, nts)
        improved = va < best_val
        best_val2 = jnp.where(improved, va, best_val)
        # va was measured on the PRE-update weights; keep those as "best"
        best_flat2 = jnp.where(improved, flat, best_flat)
        bad2 = jnp.where(improved, 0, bad + 1)
        halt2 = jnp.zeros((), dtype=bool)
        if window > 0:
            halt2 = halt2 | (bad2 >= window)
        if conv > 0.0:
            halt2 = halt2 | ((tr + va) / 2.0 <= conv)
        lr2 = lr * (1.0 - decay)
        return (new_flat, new_opt, it + 1, lr2, best_val2, best_flat2, bad2,
                halt2, tr, va)

    @jax.jit
    def program(carry, limit, x, t, sig_train, sig_valid, key0, nts):
        """Iterate until `limit` or halt. limit/seed/data/sample-size are
        traced operands so the same program serves any epoch count,
        checkpoint cadence, bag member, and dataset of the same shape."""

        def cond(c):
            return (c[2] < limit) & (~c[7])

        def body(c):
            return one_iter(c, x, t, sig_train, sig_valid, key0, nts)

        return jax.lax.while_loop(cond, body, carry)

    _PROGRAMS[cache_key] = (program, init_state)
    return program, init_state


def train_nn(
    features: np.ndarray,
    tags: np.ndarray,
    weights: np.ndarray,
    cfg: NNTrainConfig,
    mesh=None,
    init_flat: Optional[np.ndarray] = None,
    fetch_params: bool = True,
) -> TrainResult:
    """Train one model. features [n, d] float32 (normalized), tags [n] {0,1},
    weights [n] significance. `mesh` shards rows over its `data` axis;
    None = single device. `fetch_params=False` skips the device->host
    weight transfer and returns params=None — steady-state benchmarking on
    remote TPU links, where pulling a 25 MB weight vector costs seconds."""
    import jax
    import jax.numpy as jnp

    # the body stays in this frame (see train_trees: a frame more under a
    # program's first dispatch is paid for in its tracing)
    call = int(registry().counter("train.calls", engine="nn").inc())
    with span("train.nn.call", call=call, rows=int(features.shape[0]),
              epochs=int(cfg.num_epochs)):
        with span("train.nn.prologue", call=call):
            n, d = features.shape
            out_dim = cfg.n_classes if cfg.n_classes > 2 else 1
            layer_sizes = [d] + list(cfg.hidden_nodes) + [out_dim]
            params0 = init_params(layer_sizes, seed=cfg.seed, init=cfg.weight_init)
            flat0, shapes = flatten_params(params0)
            if init_flat is not None and init_flat.size == flat0.size:
                flat0 = init_flat.astype(np.float32)  # continuous training resume
            n_flat = flat0.size

            # ---- shard rows over the mesh; pad to even splits with zero significance
            # features may already live on device (bench / repeated runs): don't pull
            # it back to host, HBM residency is the point
            x = features if isinstance(features, jax.Array) else features.astype(np.float32)
            t = tags if isinstance(tags, jax.Array) else tags.astype(np.float32)
            if mesh is not None:
                from shifu_tpu.parallel.mesh import pad_rows, shard_rows

                sig, valid_mask = split_and_sample(n, cfg)
                sig_train = (sig * np.asarray(weights)).astype(np.float32)
                sig_valid = (valid_mask.astype(np.float32)
                             * np.asarray(weights)).astype(np.float32)
                n_train_size = float(max(sig.sum(), 1.0))
                n_dev = mesh.devices.size
                (x, t, sig_train, sig_valid), _ = pad_rows(
                    [x, t, sig_train, sig_valid], n_dev
                )
                x = shard_rows(x, mesh)
                t = shard_rows(t, mesh)
                sig_train = shard_rows(sig_train, mesh)
                sig_valid = shard_rows(sig_valid, mesh)
            else:
                # single device: the deterministic draw lives in a device cache and
                # the weight product happens on device — repeat runs transfer zero
                # sampling bytes. Host inputs are placed EXPLICITLY here (one
                # device_put, not an implicit per-dispatch transfer) so the
                # program dispatch below is a transfer-free sanitizer seam.
                if not isinstance(x, jax.Array):
                    x = jax.device_put(x)
                if not isinstance(t, jax.Array):
                    t = jax.device_put(t)
                sig_d, valid_d, n_train_size = _device_split_and_sample(n, cfg)
                w_d = (weights if isinstance(weights, jax.Array)
                       else jax.device_put(np.asarray(weights, np.float32)))
                sig_train = sig_d * w_d
                sig_valid = valid_d * w_d

            rows = x.shape[0]
            max_iters = cfg.num_epochs
            program, init_state = _get_program(cfg, shapes, rows)
            opt0 = init_state(n_flat)

            flat_j = jnp.asarray(flat0)
            if mesh is not None:
                from shifu_tpu.parallel.mesh import replicate

                flat_j = replicate(flat_j, mesh)
                opt0 = replicate(opt0, mesh)

            carry0 = (
                flat_j, opt0, jnp.int32(0), jnp.float32(cfg.learning_rate),
                jnp.float32(np.inf), flat_j, jnp.int32(0),
                jnp.zeros((), dtype=bool), jnp.float32(0.0), jnp.float32(0.0),
            )
            key0 = jax.random.PRNGKey(cfg.seed)
            nts = jnp.float32(n_train_size)

        def run_until(carry, limit):
            # sanitizer seam: every operand is device-resident by here (the
            # scalar conversion included), so the program dispatch itself
            # must be transfer-free (-Dshifu.sanitize=transfer). Profiled
            # sync (the caller pulls scalars right after anyway); the
            # enclosing scaled() context credits one loop body per epoch.
            with span("train.nn.program", call=call, limit=int(limit)):
                limit_j = jnp.int32(limit)
                with sanitize.transfer_free("nn.program"):
                    return profile.dispatch(
                        "nn.train_program", program, carry, limit_j, x, t,
                        sig_train, sig_valid, key0, nts, sync=True)

        if cfg.checkpoint_every and cfg.checkpoint_every > 0:
            result = _run_with_checkpoints(run_until, carry0, cfg, max_iters)
        else:
            with profile.scaled(max_iters):
                result = run_until(carry0, max_iters)

        (flat_f, _, it_f, _, best_val, best_flat, _, _, tr_e, va_e) = result
        # ONE host round-trip for all scalars (serial float()/int() casts each
        # pay a full RTT on remote TPU links)
        with span("train.nn.pull", call=call) as pulled:
            scalars = jax.device_get((it_f, best_val, tr_e, va_e))
            it_n, bv, tr_h, va_h = (a.item() for a in scalars)
            pulled["bytes"] = sum(a.nbytes for a in scalars)
            it_n = int(it_n)
            final_valid = float(bv) if math.isfinite(bv) else float(va_h)
            use_best = cfg.valid_set_rate > 0 and math.isfinite(bv)
            if fetch_params:
                chosen = (np.asarray(best_flat) if use_best
                          else np.asarray(flat_f))
                pulled["bytes"] += chosen.nbytes
        params = unflatten_params(chosen, shapes) if fetch_params else None
        reg = registry()
        reg.gauge("train.train_error").set(float(tr_h))
        reg.gauge("train.valid_error").set(final_valid)
        reg.counter("train.iterations").inc(it_n)
        log.info(
            "train done: %d iterations, train_err %.6f valid_err %.6f",
            it_n, tr_h, final_valid,
        )
        return TrainResult(
            params=params,
            train_error=float(tr_h),
            valid_error=final_valid,
            iterations=it_n,
        )


def train_nn_bagged(
    features: np.ndarray,
    tags: np.ndarray,
    weights: np.ndarray,
    base_cfg: NNTrainConfig,
    n_members: int,
    mesh=None,
    init_flats: Optional[List[Optional[np.ndarray]]] = None,
    member_seed: Callable[[int], int] = lambda i: i * 1000 + 7,
    checkpoint_paths: Optional[List[str]] = None,
    member_tags: Optional[np.ndarray] = None,
    member_lrs: Optional[List[float]] = None,
    member_sigs: Optional[Tuple[np.ndarray, np.ndarray]] = None,
) -> List[TrainResult]:
    """Train all bagging members as ONE vmapped SPMD program.

    The reference fans each bag member out as a separate Guagua job, five in
    parallel (TrainModelProcessor.java:768-945, shifuconfig
    shifu.train.bagging.inparallel); here the member axis is vmapped over the
    shared row-sharded dataset, so the MXU sees [M, n, d] batched matmuls and
    all members train in one XLA execution. jax's while_loop batching rule
    masks members that early-stop, so per-member halting semantics match the
    serial path exactly.

    `member_tags` [M, n] overrides the shared tags per member — the ONEVSALL
    case (NNWorker.java:116-120: trainer i's ideal is tag==i) rides the same
    member axis as bagging.

    `member_lrs` [M] gives each member its own learning rate — grid-search
    trials that differ only in traced hyperparams (LearningRate) batch onto
    the member axis too (gs/GridSearch.java:44 flattens the grid; here the
    flat trials become one vmapped program instead of N Guagua jobs).

    `member_sigs` (sig_train [M, n], sig_valid [M, n]) overrides the
    bagging/validation sampling entirely — the k-fold case: fold i's
    sig_valid marks its held-out fold (TrainModelProcessor.java:947-969)."""
    import jax
    import jax.numpy as jnp

    n, d = features.shape
    out_dim = base_cfg.n_classes if base_cfg.n_classes > 2 else 1
    layer_sizes = [d] + list(base_cfg.hidden_nodes) + [out_dim]
    shapes = None
    device_sigs = member_sigs is None and mesh is None
    flat0s, sig_ts, sig_vs, ntss, seeds = [], [], [], [], []
    for i in range(n_members):
        seed_i = member_seed(i)
        seeds.append(seed_i)
        params0 = init_params(layer_sizes, seed=seed_i, init=base_cfg.weight_init)
        flat0, shapes = flatten_params(params0)
        init_i = (init_flats or [None] * n_members)[i]
        if init_i is not None and init_i.size == flat0.size:
            flat0 = init_i.astype(np.float32)
        if member_sigs is not None:
            sig_ts.append(np.asarray(member_sigs[0][i], np.float32))
            sig_vs.append(np.asarray(member_sigs[1][i], np.float32))
            ntss.append(float(max((member_sigs[0][i] > 0).sum(), 1.0)))
        else:
            cfg_i = NNTrainConfig(**{**base_cfg.__dict__, "seed": seed_i})
            if device_sigs:
                # per-member draws ride the device cache: a 5-member bag
                # on 1M rows would otherwise transfer ~40 MB of masks
                # per call over a remote TPU link
                sig_d, valid_d, nts_i = _device_split_and_sample(n, cfg_i)
                sig_ts.append(sig_d)
                sig_vs.append(valid_d)
                ntss.append(nts_i)
            else:
                sig, valid_mask = split_and_sample(n, cfg_i)
                sig_ts.append((sig * weights).astype(np.float32))
                sig_vs.append(
                    (valid_mask.astype(np.float32) * weights)
                    .astype(np.float32))
                ntss.append(float(max(sig.sum(), 1.0)))
        flat0s.append(flat0)

    x = features if isinstance(features, jax.Array) else features.astype(np.float32)
    t_batched = member_tags is not None
    if t_batched:
        t = np.asarray(member_tags, np.float32)  # [M, n]
    else:
        t = tags if isinstance(tags, jax.Array) else tags.astype(np.float32)
    if device_sigs:
        w_d = (weights if isinstance(weights, jax.Array)
               else jnp.asarray(np.asarray(weights, np.float32)))
        sig_t = jnp.stack(sig_ts) * w_d[None, :]  # [M, n] on device
        sig_v = jnp.stack(sig_vs) * w_d[None, :]
    else:
        sig_t = np.stack(sig_ts)  # [M, n]
        sig_v = np.stack(sig_vs)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from shifu_tpu.parallel.mesh import pad_rows, shard_rows

        n_dev = mesh.devices.size
        (x,), _ = pad_rows([x], n_dev)
        from shifu_tpu.parallel.mesh import row_axes as _raxes

        member_rows = NamedSharding(mesh, P(None, _raxes(mesh)))
        if t_batched:
            t = jax.device_put(np.pad(t, ((0, 0), (0, x.shape[0] - n))),
                               member_rows)
        else:
            (t,), _ = pad_rows([t], n_dev)
            t = shard_rows(t, mesh)
        sig_t = np.pad(sig_t, ((0, 0), (0, x.shape[0] - n)))
        sig_v = np.pad(sig_v, ((0, 0), (0, x.shape[0] - n)))
        x = shard_rows(x, mesh)
        sig_t = jax.device_put(sig_t, member_rows)
        sig_v = jax.device_put(sig_v, member_rows)

    rows = x.shape[0]
    program, init_state = _get_program(base_cfg, shapes, rows)
    bag_key = ("bagged", id(program), n_members, t_batched)
    program_b = _PROGRAMS.get(bag_key)
    if program_b is None:
        program_b = jax.jit(
            jax.vmap(program,
                     in_axes=(0, None, None, 0 if t_batched else None,
                              0, 0, 0, 0)),
            static_argnums=(),
        )
        _PROGRAMS[bag_key] = program_b

    n_flat = flat0s[0].size
    flat_j = jnp.asarray(np.stack(flat0s))  # [M, n_flat]
    opt0 = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *[init_state(n_flat) for _ in range(n_members)]
    )
    if mesh is not None:
        from shifu_tpu.parallel.mesh import replicate

        flat_j = replicate(flat_j, mesh)
        opt0 = replicate(opt0, mesh)
    M = n_members
    lrs0 = (
        jnp.asarray(member_lrs, jnp.float32)
        if member_lrs is not None
        else jnp.full(M, base_cfg.learning_rate, jnp.float32)
    )
    carry0 = (
        flat_j, opt0, jnp.zeros(M, jnp.int32),
        lrs0,
        jnp.full(M, np.inf, jnp.float32), flat_j, jnp.zeros(M, jnp.int32),
        jnp.zeros(M, dtype=bool), jnp.zeros(M, jnp.float32),
        jnp.zeros(M, jnp.float32),
    )
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    nts_j = jnp.asarray(ntss, jnp.float32)
    max_iters = base_cfg.num_epochs

    def run_until(carry, limit):
        # the vmapped program's cost analysis already covers all M
        # members per loop body, so scaled() credits epochs only
        return profile.dispatch(
            "nn.train_program_bagged", program_b, carry, jnp.int32(limit),
            x, t, sig_t, sig_v, keys, nts_j, sync=True)

    if base_cfg.checkpoint_every and base_cfg.checkpoint_every > 0:
        # segmented run: per-member checkpoints + progress between segments
        # (NNOutput.postIteration parity, one file per trainer)
        carry = carry0
        it = 0
        last_reported = [-1] * M
        while it < max_iters:
            limit = min(it + base_cfg.checkpoint_every, max_iters)
            with profile.scaled(limit - it):
                carry = run_until(carry, limit)
            it = int(np.asarray(carry[2]).max())
            trs, vas = np.asarray(carry[8]), np.asarray(carry[9])
            its = np.asarray(carry[2])
            flats = np.asarray(carry[0])
            for i in range(M):
                it_i = int(its[i])
                if it_i == last_reported[i]:
                    continue  # member already halted; don't re-report
                last_reported[i] = it_i
                if base_cfg.progress_cb:
                    base_cfg.progress_cb((i, it_i), float(trs[i]),
                                         float(vas[i]))
                if checkpoint_paths and checkpoint_paths[i]:
                    atomic_save_npy(checkpoint_paths[i], flats[i])
            if bool(np.asarray(carry[7]).all()) or it >= max_iters:
                break
        out = carry
    else:
        with profile.scaled(max_iters):
            out = run_until(carry0, max_iters)
    (flat_f, _, it_f, _, best_val, best_flat, _, _, tr_e, va_e) = out

    results = []
    flat_f_np = np.asarray(flat_f)
    best_flat_np = np.asarray(best_flat)
    for i in range(n_members):
        bv = float(np.asarray(best_val)[i])
        # member_sigs (k-fold) stays an UNBIASED holdout: final weights and
        # the final-epoch holdout error, not the min-over-epochs snapshot
        # (TrainModelProcessor.java:947-969 evaluates the finished model)
        use_best = (member_sigs is None and base_cfg.valid_set_rate > 0
                    and math.isfinite(bv))
        chosen = best_flat_np[i] if use_best else flat_f_np[i]
        results.append(TrainResult(
            params=unflatten_params(chosen, shapes),
            train_error=float(np.asarray(tr_e)[i]),
            valid_error=bv if use_best else float(np.asarray(va_e)[i]),
            iterations=int(np.asarray(it_f)[i]),
        ))
    from shifu_tpu.obs import registry

    avg_valid = float(np.mean([r.valid_error for r in results]))
    reg = registry()
    reg.gauge("train.valid_error").set(avg_valid)
    reg.counter("train.members").inc(n_members)
    reg.counter("train.iterations").inc(
        sum(r.iterations for r in results))
    log.info("bagged train done: %d members in one program, avg valid %.6f",
             n_members, avg_valid)
    return results


def _run_with_checkpoints(run_until, carry, cfg, max_iters):
    """Chunked run: jit loop in segments, checkpoint + progress between them
    (NNOutput.postIteration:158 writes tmp models each epoch)."""
    import jax.numpy as jnp

    every = cfg.checkpoint_every
    it = 0
    while it < max_iters:
        limit = min(it + every, max_iters)
        with profile.scaled(limit - it):  # loop bodies this segment runs
            carry = run_until(carry, limit)
        it = int(carry[2])
        tr, va = float(carry[8]), float(carry[9])
        if cfg.progress_cb:
            cfg.progress_cb(it, tr, va)
        if cfg.checkpoint_path:
            atomic_save_npy(cfg.checkpoint_path, np.asarray(carry[0]))
        if bool(carry[7]) or it >= max_iters:
            break
    return carry
