"""`shifu train` — train model(s) on the normalized matrix.

Parity: core/processor/TrainModelProcessor.java:105 — bagging fan-out,
k-fold, grid search, continuous training, per-algorithm param wiring
(prepareNNParams :1338 / prepareLRParams :1325), progress + val-error files.
The Guagua job fan-out (runDistributedTrain:661) becomes: bagging members
vmapped into ONE SPMD program over the full device mesh (train_nn_bagged) —
the member axis rides the MXU batch dimension instead of parallel Hadoop
jobs; grid-search trials reuse the compiled step (same shapes = jit cache
hit).
"""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

from shifu_tpu.config.model_config import Algorithm
from shifu_tpu.norm.dataset import load_normalized
from shifu_tpu.norm.normalizer import build_norm_plan, plan_to_json
from shifu_tpu.processor.basic import BasicProcessor
from shifu_tpu.utils.errors import ErrorCode, ShifuError
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)


class TrainProcessor(BasicProcessor):
    step = "train"

    def __init__(self, root: str = ".", dry: bool = False):
        super().__init__(root)
        self.dry = dry

    # ---- helpers ----
    def _model_suffix(self, alg: Algorithm) -> str:
        return {
            Algorithm.NN: "nn",
            Algorithm.LR: "lr",
            Algorithm.GBT: "gbt",
            Algorithm.RF: "rf",
            Algorithm.DT: "rf",
            Algorithm.WDL: "wdl",
        }.get(alg, "nn")

    def run_step(self) -> None:
        self.setup()
        mc = self.model_config
        assert mc is not None
        alg = mc.train.algorithm

        if self.dry:
            log.info("dry run: config validated, algorithm=%s", alg.value)
            return

        if alg in (Algorithm.NN, Algorithm.LR, Algorithm.SVM):
            self._train_nn_family(alg)
        elif alg in (Algorithm.GBT, Algorithm.RF, Algorithm.DT):
            self._train_tree_family(alg)
        elif alg == Algorithm.WDL:
            self._train_wdl()
        else:
            raise ShifuError(
                ErrorCode.INVALID_MODEL_CONFIG, f"algorithm {alg.value} not supported"
            )

    # ---- NN / LR ----
    def _train_nn_family(self, alg: Algorithm) -> None:
        from shifu_tpu.train.grid_search import flatten_params
        from shifu_tpu.train.nn_trainer import NNTrainConfig, train_nn

        mc = self.model_config
        norm_dir = self.paths.normalized_data_dir()
        if not os.path.isdir(norm_dir):
            raise ShifuError(
                ErrorCode.DATA_NOT_FOUND, f"{norm_dir} — run `shifu norm` first"
            )
        plan = build_norm_plan(mc, self.column_configs)
        norm_json = plan_to_json(plan)
        suffix = self._model_suffix(alg)
        self.paths.ensure(self.paths.models_dir())
        self.paths.ensure(self.paths.train_dir())

        from shifu_tpu.train.streaming import should_stream_training

        # a co-resident run (retrain --coresident) always rides the
        # shard-streamed epoch loop: the stage pipeline feeds from the
        # same ShardFeed whatever the matrix size
        if (getattr(self, "coresident_cfg", None) is not None
                or should_stream_training(
                    norm_dir, force_attr=bool(mc.train.train_on_disk))):
            # spill composes with the mesh: shards stream row-sharded and
            # XLA all-reduces each shard gradient (the reference spills
            # inside every distributed worker, AbstractNNWorker.java:485)
            self._train_nn_streamed(alg, norm_dir, norm_json, suffix,
                                    mesh=self._mesh())
            return

        meta, feats, tags, weights = load_normalized(norm_dir)
        feats = np.asarray(feats, dtype=np.float32)
        tags = np.asarray(tags, dtype=np.float32)
        weights = np.asarray(weights, dtype=np.float32)
        log.info("training on %d rows x %d features (%s)",
                 feats.shape[0], feats.shape[1], alg.value)

        mesh = self._mesh()

        composites = flatten_params(
            mc.train.params or {},
            self.resolve(mc.train.grid_config_file)
            if mc.train.grid_config_file
            else None,
        )
        is_grid = len(composites) > 1
        num_kfold = mc.train.num_k_fold or -1
        bagging = max(1, int(mc.train.bagging_num or 1))

        if mc.is_multi_classification() and mc.train.is_one_vs_all():
            if is_grid:
                # grid under OVA: each trial trains all K per-class members
                # as one vmapped program; trial score = mean per-class
                # holdout error (the reference fans out grid x class Guagua
                # jobs, TrainModelProcessor.java:684-945)
                best = self._grid_search_ova(alg, composites, feats, tags,
                                             weights, mesh)
                log.info("ONEVSALL grid search best params: %s", best)
                mc.train.params = best
            if num_kfold > 0:
                log.warning("num_k_fold is ignored under ONEVSALL "
                            "multi-class (one model per class)")
            self._train_one_vs_all(alg, feats, tags, weights, mesh,
                                   norm_json, suffix)
            return

        if is_grid:
            best = self._grid_search(alg, composites, feats, tags, weights, mesh)
            log.info("grid search best params: %s", best)
            mc.train.params = best
            composites = [best]

        if num_kfold > 0:
            self._k_fold(alg, num_kfold, feats, tags, weights, mesh, norm_json, suffix)
            return

        if bagging > 1:
            # all members in ONE vmapped program (the reference's 5-parallel
            # Guagua jobs, shifuconfig shifu.train.bagging.inparallel)
            from shifu_tpu.train.nn_trainer import train_nn_bagged

            base_cfg = NNTrainConfig.from_model_config(mc, trainer_id=0)
            init_flats = [
                self._continuous_init(i, suffix) if mc.train.is_continuous
                else None
                for i in range(bagging)
            ]
            base_cfg.checkpoint_every = self._checkpoint_every()
            checkpoint_paths = [
                os.path.join(self.paths.ensure(self.paths.checkpoint_dir(i)),
                             "weights.npy")
                for i in range(bagging)
            ]
            from shifu_tpu.processor.train_common import (
                member_progress_writer,
            )

            base_cfg.progress_cb = member_progress_writer(
                [self.paths.progress_path(i) for i in range(bagging)]
            )
            results = train_nn_bagged(feats, tags, weights, base_cfg, bagging,
                                      mesh=mesh, init_flats=init_flats,
                                      checkpoint_paths=checkpoint_paths)
            val_errors: List[float] = []
            for i, result in enumerate(results):
                cfg_i = NNTrainConfig.from_model_config(mc, trainer_id=i)
                spec = self._make_spec(alg, cfg_i, result, meta.columns,
                                       norm_json)
                path = self.paths.model_path(i, suffix)
                spec.save(path)
                with open(self.paths.val_error_path(i), "w") as fh:
                    fh.write(f"{result.valid_error}\n")
                val_errors.append(result.valid_error)
                log.info("model %d -> %s (valid err %.6f)", i, path,
                         result.valid_error)
            log.info("bagging avg valid error: %.6f", float(np.mean(val_errors)))
            return

        cfg = NNTrainConfig.from_model_config(mc, trainer_id=0)
        init_flat = self._continuous_init(0, suffix) if mc.train.is_continuous else None
        cfg.checkpoint_every = self._checkpoint_every()
        cfg.checkpoint_path = os.path.join(
            self.paths.ensure(self.paths.checkpoint_dir(0)), "weights.npy"
        )
        from shifu_tpu.processor.train_common import progress_writer

        cfg.progress_cb = progress_writer(self.paths.progress_path(0))
        result = train_nn(feats, tags, weights, cfg, mesh=mesh,
                          init_flat=init_flat)
        spec = self._make_spec(alg, cfg, result, meta.columns, norm_json)
        path = self.paths.model_path(0, suffix)
        spec.save(path)
        with open(self.paths.val_error_path(0), "w") as fh:
            fh.write(f"{result.valid_error}\n")
        log.info("model 0 -> %s (valid err %.6f)", path, result.valid_error)

    def _train_nn_streamed(self, alg, norm_dir, norm_json, suffix,
                           mesh=None) -> None:
        """Larger-than-memory path: the normalized matrix never concatenates
        into one host array; members stream the mmap'd shards through a
        double-buffered device feed (train/streaming.py; the reference's
        MemoryDiskFloatMLDataSet disk-spill analog). Bagging members /
        one-vs-all classes / grid trials / folds run serially — each full
        run is itself one chip-saturating program (the reference fans them
        out as Guagua jobs over data of any size,
        TrainModelProcessor.java:768-945)."""
        from shifu_tpu.train.grid_search import flatten_params
        from shifu_tpu.train.nn_trainer import NNTrainConfig
        from shifu_tpu.train.streaming import train_nn_streamed

        mc = self.model_config
        cc_base = getattr(self, "coresident_cfg", None)
        composites = flatten_params(
            mc.train.params or {},
            self.resolve(mc.train.grid_config_file)
            if mc.train.grid_config_file else None,
        )
        if cc_base is not None and (len(composites) > 1
                                    or (mc.train.num_k_fold or -1) > 0):
            raise ShifuError(
                ErrorCode.INVALID_MODEL_CONFIG,
                "--coresident trains the final member(s) only — grid "
                "search / k-fold explore on the dedicated trainer first")
        multi = mc.is_multi_classification()
        is_ova = multi and mc.train.is_one_vs_all()
        if len(composites) > 1:
            best = self._grid_search_streamed(
                norm_dir, composites, mesh,
                n_classes=len(mc.tags()) if is_ova else 0)
            log.info("streamed grid search best params: %s", best)
            mc.train.params = best
        num_kfold = mc.train.num_k_fold or -1
        if num_kfold > 0:
            if is_ova:
                log.warning("num_k_fold is ignored under ONEVSALL "
                            "multi-class (one model per class)")
            else:
                self._k_fold_streamed(alg, num_kfold, norm_dir, norm_json,
                                      suffix, mesh)
                return
        ova = is_ova
        class_tags = [str(t) for t in mc.tags()] if multi else None
        n_members = (len(class_tags) if ova
                     else max(1, int(mc.train.bagging_num or 1)))
        meta_cols = self._norm_meta_columns()
        log.info("training STREAMED from %s (%d member(s))", norm_dir,
                 n_members)
        for i in range(n_members):
            cfg = NNTrainConfig.from_model_config(mc, trainer_id=i)
            cfg.checkpoint_every = self._checkpoint_every()
            cfg.checkpoint_path = os.path.join(
                self.paths.ensure(self.paths.checkpoint_dir(i)), "weights.npy"
            )
            from shifu_tpu.processor.train_common import progress_writer

            cfg.progress_cb = progress_writer(self.paths.progress_path(i), i)
            init_flat = (self._continuous_init(i, suffix)
                         if mc.train.is_continuous else None)
            from shifu_tpu.resilience.checkpoint import resume_requested

            if cc_base is not None:
                from dataclasses import replace as dc_replace

                from shifu_tpu.coresident import train_nn_coresident

                # bagging members need distinct checkpoint families +
                # ledger identities (OVA classes already split on the
                # family's -c<class> suffix)
                ccfg_i = dc_replace(
                    cc_base,
                    tenant=(cc_base.tenant if i == 0 or ova
                            else f"{cc_base.tenant}-m{i}"))
                res = train_nn_coresident(
                    norm_dir, cfg, ccfg=ccfg_i, init_flat=init_flat,
                    target_class=i if ova else None,
                    resume=resume_requested(),
                    ident_extra=getattr(self, "train_ident_extra", None))
            else:
                res = train_nn_streamed(
                    norm_dir, cfg, init_flat=init_flat,
                    target_class=i if ova else None,
                    mesh=mesh, resume=resume_requested(),
                    ident_extra=getattr(self, "train_ident_extra", None))
            spec = self._make_spec(alg, cfg, res, meta_cols, norm_json,
                                   class_tags=class_tags)
            path = self.paths.model_path(i, suffix)
            spec.save(path)
            with open(self.paths.val_error_path(i), "w") as fh:
                fh.write(f"{res.valid_error}\n")
            log.info("streamed model %d -> %s (valid err %.6f)", i, path,
                     res.valid_error)

    def _grid_search_ova(self, alg, composites, feats, tags, weights,
                         mesh) -> dict:
        """Grid x ONEVSALL: trials run serially, each trial's K per-class
        binary members ride one vmapped program; the trial's score is the
        mean class holdout error."""
        from shifu_tpu.train.nn_trainer import NNTrainConfig, train_nn_bagged

        mc = self.model_config
        K = len(mc.tags())
        member_tags = np.stack(
            [(tags == k).astype(np.float32) for k in range(K)]
        )
        orig = mc.train.params
        results = []
        for gi, params in enumerate(composites):
            mc.train.params = params
            try:
                cfg = NNTrainConfig.from_model_config(mc, trainer_id=0)
            finally:
                mc.train.params = orig
            trial = train_nn_bagged(feats, tags, weights, cfg, K, mesh=mesh,
                                    member_tags=member_tags,
                                    member_seed=lambda i, _g=gi:
                                    (_g * 100 + i) * 1000 + 7)
            err = float(np.mean([r.valid_error for r in trial]))
            results.append((err, gi, params))
            log.info("OVA grid trial %d/%d mean class err %.6f params=%s",
                     gi + 1, len(composites), err, params)
        results.sort(key=lambda r: r[0])
        return results[0][2]

    def _grid_search_streamed(self, norm_dir, composites, mesh,
                              n_classes: int = 0) -> dict:
        """Serial grid trials over the streamed trainer — each trial is a
        full shard-streamed run (an error here was a parity subtraction:
        the reference fans trials out as Guagua jobs over data of any
        size, TrainModelProcessor.java:768-945). Under ONEVSALL
        (n_classes > 0) each trial streams one run PER CLASS and scores
        the mean class holdout error, mirroring _grid_search_ova."""
        from shifu_tpu.train.nn_trainer import NNTrainConfig
        from shifu_tpu.train.streaming import train_nn_streamed

        mc = self.model_config
        orig = mc.train.params
        results = []
        for gi, params in enumerate(composites):
            mc.train.params = params
            try:
                cfg = NNTrainConfig.from_model_config(mc, trainer_id=gi)
            finally:
                mc.train.params = orig
            if n_classes > 0:
                errs = [
                    train_nn_streamed(norm_dir, cfg, mesh=mesh,
                                      target_class=k).valid_error
                    for k in range(n_classes)
                ]
                err = float(np.mean(errs))
            else:
                err = train_nn_streamed(norm_dir, cfg,
                                        mesh=mesh).valid_error
            results.append((err, gi, params))
            log.info("streamed grid trial %d/%d valid err %.6f params=%s",
                     gi + 1, len(composites), err, params)
        results.sort(key=lambda r: r[0])
        return results[0][2]

    def _k_fold_streamed(self, alg, k, norm_dir, norm_json, suffix,
                         mesh) -> None:
        """Streamed k-fold: fold membership is global-row-index % k (same
        fold geometry as the in-memory path), carried into each shard via
        ShardFeed's sig_override; folds run serially."""
        from shifu_tpu.train.nn_trainer import NNTrainConfig
        from shifu_tpu.train.streaming import train_nn_streamed

        mc = self.model_config
        meta_cols = self._norm_meta_columns()
        errors = []
        for i in range(k):
            cfg = NNTrainConfig.from_model_config(mc, trainer_id=i)
            cfg.valid_set_rate = 0.0  # the fold drives the split
            cfg.early_stop_window = 0

            def sig_override(s, rows, offset, w, _i=i, _cfg=cfg):
                idx = np.arange(offset, offset + rows)
                fold = idx % k
                rng = np.random.default_rng(_i * 1000 + 7 + s)
                if _cfg.bagging_with_replacement:
                    bag = rng.poisson(_cfg.bagging_sample_rate, size=rows)
                else:
                    bag = rng.random(rows) < _cfg.bagging_sample_rate
                sig_t = np.where(fold == _i, 0.0, w * bag)
                sig_v = np.where(fold == _i, w, 0.0)
                return sig_t, sig_v

            res = train_nn_streamed(norm_dir, cfg, mesh=mesh,
                                    sig_override=sig_override)
            spec = self._make_spec(alg, cfg, res, meta_cols, norm_json)
            spec.save(self.paths.model_path(i, suffix))
            errors.append(res.valid_error)
            log.info("streamed fold %d/%d holdout err %.6f", i + 1, k,
                     res.valid_error)
        log.info("streamed k-fold avg validation error: %.6f",
                 float(np.mean(errors)))

    def _train_one_vs_all(self, alg, feats, tags, weights, mesh, norm_json,
                          suffix) -> None:
        """ONEVSALL: one binary model per class, all classes trained as ONE
        vmapped program on the member axis (the reference fans out
        baggingNum=classes Guagua jobs, TrainModelProcessor.java:691-699;
        trainer i's ideal is tag==i, NNWorker.java:116-120)."""
        from shifu_tpu.train.nn_trainer import NNTrainConfig, train_nn_bagged

        mc = self.model_config
        class_tags = [str(t) for t in mc.tags()]
        K = len(class_tags)
        if (mc.train.bagging_num or 1) not in (1, K):
            log.warning("'train:baggingNum' is overridden to %d because of "
                        "ONEVSALL multiple classification.", K)
        base_cfg = NNTrainConfig.from_model_config(mc, trainer_id=0)
        base_cfg.checkpoint_every = self._checkpoint_every()
        member_tags = np.stack(
            [(tags == k).astype(np.float32) for k in range(K)]
        )
        init_flats = [
            self._continuous_init(k, suffix) if mc.train.is_continuous else None
            for k in range(K)
        ]
        checkpoint_paths = [
            os.path.join(self.paths.ensure(self.paths.checkpoint_dir(k)),
                         "weights.npy")
            for k in range(K)
        ]
        results = train_nn_bagged(
            feats, tags, weights, base_cfg, K, mesh=mesh,
            init_flats=init_flats, checkpoint_paths=checkpoint_paths,
            member_tags=member_tags,
        )
        meta_cols = self._norm_meta_columns()
        for k, result in enumerate(results):
            cfg_k = NNTrainConfig.from_model_config(mc, trainer_id=k)
            spec = self._make_spec(alg, cfg_k, result, meta_cols, norm_json,
                                   class_tags=class_tags)
            path = self.paths.model_path(k, suffix)
            spec.save(path)
            with open(self.paths.val_error_path(k), "w") as fh:
                fh.write(f"{result.valid_error}\n")
            log.info("one-vs-all model %d (class %s) -> %s (valid err %.6f)",
                     k, class_tags[k], path, result.valid_error)

    def _norm_meta_columns(self) -> List[str]:
        from shifu_tpu.norm.dataset import read_meta

        try:
            return list(read_meta(self.paths.normalized_data_dir()).columns)
        except Exception:  # no norm meta yet: fall back to ColumnConfig order
            return []

    def _checkpoint_every(self) -> int:
        """Checkpoint cadence = train.epochsPerIteration (the reference
        writes tmp models every epochsPerIteration master iterations)."""
        mc = self.model_config
        per = int(mc.train.epochs_per_iteration or 1)
        return max(per, 10) if per <= 1 else per

    @staticmethod
    def _program_signature(cfg) -> tuple:
        """Everything baked STATICALLY into the compiled training program —
        trials that share it differ only in traced operands (LearningRate,
        seed) and can ride one vmapped member axis."""
        return (
            tuple(cfg.hidden_nodes), tuple(cfg.activations), cfg.loss,
            cfg.dropout_rate, cfg.mixed_precision, cfg.mini_batchs,
            cfg.early_stop_window, cfg.convergence_threshold,
            cfg.learning_decay, (cfg.propagation or "Q").upper(),
            cfg.momentum, cfg.regularized_constant, cfg.reg_level,
            cfg.adam_beta1, cfg.adam_beta2, cfg.num_epochs,
            cfg.valid_set_rate, cfg.bagging_sample_rate,
            cfg.bagging_with_replacement, cfg.weight_init, cfg.n_classes,
        )

    def _grid_search(self, alg, composites, feats, tags, weights, mesh) -> dict:
        """Grid trials batched on the vmapped member axis, grouped by
        compiled-program signature — a 30-trial LearningRate sweep is ONE
        XLA execution, not 30 (the reference runs each trial as a Guagua
        job, gs/GridSearch.java:44 + TrainModelProcessor.java:768-945)."""
        from shifu_tpu.train.nn_trainer import NNTrainConfig, train_nn_bagged

        mc = self.model_config
        orig_params = mc.train.params
        cfgs = []
        for gi, params in enumerate(composites):
            mc.train.params = params
            try:
                cfgs.append(NNTrainConfig.from_model_config(mc, trainer_id=gi))
            finally:
                mc.train.params = orig_params
        groups: dict = {}
        for gi, cfg in enumerate(cfgs):
            groups.setdefault(self._program_signature(cfg), []).append(gi)

        results = []
        for idxs in groups.values():
            trial_results = train_nn_bagged(
                feats, tags, weights, cfgs[idxs[0]], len(idxs), mesh=mesh,
                member_seed=lambda i, _idxs=idxs: _idxs[i] * 1000 + 7,
                member_lrs=[cfgs[i].learning_rate for i in idxs],
            )
            for gi, res in zip(idxs, trial_results):
                results.append((res.valid_error, gi, composites[gi]))
                log.info("grid trial %d/%d valid err %.6f params=%s",
                         gi + 1, len(composites), res.valid_error,
                         composites[gi])
        log.info("grid search: %d trials in %d vmapped group(s)",
                 len(composites), len(groups))
        results.sort(key=lambda r: r[0])
        return results[0][2]

    def _k_fold(self, alg, k, feats, tags, weights, mesh, norm_json, suffix) -> None:
        """All k folds as ONE vmapped program: fold i's member holds out fold
        i via per-member significance masks; the trainer's valid error IS the
        holdout error (TrainModelProcessor.java:947-969)."""
        from shifu_tpu.train.nn_trainer import NNTrainConfig, train_nn_bagged

        mc = self.model_config
        n = feats.shape[0]
        fold = np.arange(n) % k
        base = NNTrainConfig.from_model_config(mc, trainer_id=0)
        base.valid_set_rate = 0.0  # folds drive the split instead
        base.early_stop_window = 0  # holdout must not steer training
        sig_ts, sig_vs = [], []
        for i in range(k):
            # bagging sampling still applies inside each fold's train side,
            # as the serial path's split_and_sample did
            rng = np.random.default_rng(i * 1000 + 7)
            if base.bagging_with_replacement:
                bag = rng.poisson(base.bagging_sample_rate, size=n)
            else:
                bag = rng.random(n) < base.bagging_sample_rate
            sig_ts.append(np.where(fold == i, 0.0, weights * bag))
            sig_vs.append(np.where(fold == i, weights, 0.0))
        sig_t = np.stack(sig_ts).astype(np.float32)
        sig_v = np.stack(sig_vs).astype(np.float32)
        results = train_nn_bagged(feats, tags, weights, base, k, mesh=mesh,
                                  member_sigs=(sig_t, sig_v))
        meta_cols = self._norm_meta_columns()
        errors = []
        for i, res in enumerate(results):
            cfg_i = NNTrainConfig.from_model_config(mc, trainer_id=i)
            spec = self._make_spec(alg, cfg_i, res, meta_cols, norm_json)
            spec.save(self.paths.model_path(i, suffix))
            errors.append(res.valid_error)
            log.info("fold %d/%d holdout err %.6f", i + 1, k, res.valid_error)
        log.info("k-fold avg validation error: %.6f", float(np.mean(errors)))

    def _continuous_init(self, i: int, suffix: str) -> Optional[np.ndarray]:
        """Continuous training resumes from the existing model's weights
        (checkContinuousTraining TrainModelProcessor.java:1149)."""
        from shifu_tpu.models.nn import NNModelSpec, flatten_params

        path = self.paths.model_path(i, suffix)
        if not os.path.isfile(path):
            return None
        try:
            spec = NNModelSpec.load(path)
            flat, _ = flatten_params(spec.params)
            log.info("continuous training: resuming model %d from %s", i, path)
            return flat
        except Exception as e:  # corrupt/mismatched spec: fresh start, logged
            log.warning("cannot resume from %s (%s); fresh start", path, e)
            return None

    def _make_spec(self, alg, cfg, result, columns, norm_json,
                   class_tags=None):
        from shifu_tpu.models.nn import NNModelSpec

        in_dim = result.params[0]["W"].shape[0]
        out_dim = result.params[-1]["W"].shape[1]
        mc = self.model_config
        if class_tags is None and mc is not None and mc.is_multi_classification():
            class_tags = [str(t) for t in mc.tags()]
        return NNModelSpec(
            layer_sizes=[len(columns) if columns else in_dim]
            + list(cfg.hidden_nodes)
            + [out_dim],
            activations=list(cfg.activations),
            input_columns=list(columns),
            norm_type=norm_json.get("normType", "ZSCALE"),
            algorithm=alg.value,
            loss=cfg.loss,
            norm_specs=norm_json.get("columns", []),
            norm_cutoff=float(norm_json.get("cutoff", 4.0)),
            params=result.params,
            train_error=result.train_error,
            valid_error=result.valid_error,
            class_tags=list(class_tags or []),
        )

    def _mesh(self):
        # a broken device set must fail here, not train on one device
        from shifu_tpu.parallel.mesh import data_mesh

        return data_mesh()

    # ---- trees / WDL: wired in by their engines ----
    def _train_tree_family(self, alg: Algorithm) -> None:
        from shifu_tpu.processor.train_tree import train_tree_models

        train_tree_models(self, alg)

    def _train_wdl(self) -> None:
        from shifu_tpu.processor.train_wdl import train_wdl_models

        train_wdl_models(self)
