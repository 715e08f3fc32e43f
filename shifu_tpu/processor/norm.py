"""`shifu norm` — produce the dense normalized training matrix.

Parity: core/processor/NormalizeModelProcessor.java:67 (Normalize.pig +
udf/NormalizeUDF) and the optional MR shuffle (core/shuffle/MapReduceShuffle).
TPU-first shape: one pass builds BOTH artifacts every trainer needs —
  NormalizedData/   float32 feature shards (NN/LR/WDL input)
  CleanedData/      int16 bin-code shards (GBT/RF input; replaces the
                    reference's raw-column CleanedData, the tree engine bins
                    at the source instead of per-iteration)
Shuffle is a host-side permutation before sharding (the MR shuffle's only
purpose is balanced random shards — reference NormalizeModelProcessor.java:87).
"""

from __future__ import annotations

import numpy as np

from shifu_tpu.data.purify import combined_mask
from shifu_tpu.data.reader import (
    make_tags_for,
    make_weights,
    read_columnar,
    read_header,
)
from shifu_tpu.norm.dataset import write_codes, write_normalized
from shifu_tpu.norm.normalizer import (
    _slots,
    apply_norm_plan,
    bin_code_matrix,
    build_norm_plan,
    norm_columns,
)
from shifu_tpu.processor.basic import BasicProcessor
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)


def default_shards() -> int:
    # a broken device set must fail here, not write one shard
    import jax

    return len(jax.devices())


class NormProcessor(BasicProcessor):
    step = "norm"

    def __init__(self, root: str = ".", shuffle: bool = False, seed: int = 0,
                 names_override=None, host_plan=None):
        super().__init__(root)
        self.shuffle = shuffle
        self.seed = seed
        # the retrain seam: the traffic log's `_meta.json` names the file
        # layout (input columns + target/weight + score/sha/ts), which is
        # neither the configured header nor ColumnConfig order
        self.names_override = list(names_override) if names_override else None
        # explicit HostPlan override for in-process multi-host drivers
        # (tests/bench); production processes read the lifecycle knobs
        self.host_plan = host_plan

    def run_step(self) -> None:
        self.setup()
        mc = self.model_config
        assert mc is not None
        ds = mc.data_set

        if self.names_override:
            names = list(self.names_override)
        elif ds.header_path:
            names = read_header(self.resolve(ds.header_path), ds.header_delimiter)
        else:
            names = [c.column_name for c in self.column_configs]

        from shifu_tpu.data.stream import should_stream

        if should_stream(self.resolve(ds.data_path)):
            self._run_streaming(names)
            return

        from shifu_tpu.data.pipeline import HostPlan

        hp = self.host_plan if self.host_plan is not None else HostPlan()
        if hp.active:
            raise ValueError(
                "-Dshifu.lifecycle.hosts > 1 requires the streaming norm "
                "path (dataset under the memory budget loads in one "
                "process) — drop the hosts knob or lower "
                "shifu.stream.memoryBudgetMb")

        data = read_columnar(
            self.resolve(ds.data_path),
            names,
            delimiter=ds.data_delimiter,
            missing_values=tuple(ds.missing_or_invalid_values),
        )

        # purify + invalid-tag drop + norm sampling (NormalizeUDF filters rows
        # through DataPurifier and sampler before emitting)
        mask = combined_mask(ds.filter_expressions, data.raw, data.n_rows)
        tags_all = make_tags_for(mc, data.column(ds.target_column_name))
        mask &= tags_all >= 0
        if mc.normalize.sample_rate < 1.0:
            rng = np.random.default_rng(self.seed)
            keep = rng.random(data.n_rows) < mc.normalize.sample_rate
            if mc.normalize.sample_neg_only:
                keep |= tags_all == 1
            mask &= keep
        data = data.select_rows(mask)
        tags = tags_all[mask]
        weights = make_weights(data, ds.weight_column_name)

        if self.shuffle:
            perm = np.random.default_rng(self.seed).permutation(data.n_rows)
            data = data.select_rows(perm)
            tags = tags[perm]
            weights = weights[perm]

        from shifu_tpu.obs import registry, span

        reg = registry()
        timers = reg.stage_timers("norm.stage")
        plan = build_norm_plan(mc, self.column_configs)
        code_cache: dict = {}
        with span("norm.normalize", rows=data.n_rows), \
                timers.timer("normalize"):
            feats = apply_norm_plan(plan, data, code_cache=code_cache)
        reg.counter("norm.rows").inc(int(feats.shape[0]))
        reg.gauge("norm.columns").set(int(feats.shape[1]))
        n_shards = default_shards()
        out_dir = self.paths.normalized_data_dir()
        # persist the output-name -> source-column mapping so later steps
        # (SE/ST varsel under one-hot expansion) don't have to reconstruct
        # the plan against possibly-changed ColumnConfigs
        extra = {"sourceOf": plan.source_of}
        self._add_class_meta(extra, tags)
        with span("norm.write", shards=n_shards), timers.timer("write"):
            write_normalized(
                out_dir,
                feats,
                tags,
                weights,
                plan.out_names,
                norm_type=mc.normalize.norm_type.value,
                n_shards=n_shards,
                extra=extra,
            )
        log.info(
            "normalized %d rows x %d cols (%s) -> %s [%d shards]",
            feats.shape[0], feats.shape[1], mc.normalize.norm_type.value,
            out_dir, n_shards,
        )

        # tree-model bin codes
        tree_cols = norm_columns(self.column_configs)
        with span("norm.bincode"), timers.timer("bincode"):
            codes = bin_code_matrix(tree_cols, data, cache=code_cache)
            write_codes(
                self.paths.cleaned_data_dir(),
                codes,
                tags,
                weights,
                [c.column_name for c in tree_cols],
                [_slots(c) for c in tree_cols],
                n_shards=n_shards,
            )
        log.info("bin codes -> %s", self.paths.cleaned_data_dir())

    def _stream_config_sha(self, plan, slots, n_shards):
        """(sha, per-section shas) for the streaming norm run: the full
        norm plan (type, cutoff, every per-column table) and code layout
        in the `norm` section, chunk geometry / shard plan / sampling in
        the `data` section — a snapshot written under different config
        must not be resumed, and the rejection names which side moved."""
        from shifu_tpu.data.stream import chunk_rows_setting
        from shifu_tpu.norm.normalizer import plan_to_json
        from shifu_tpu.resilience.checkpoint import sectioned_sha

        return sectioned_sha({
            "norm": {
                "plan": plan_to_json(plan),
                "slots": [int(s) for s in slots],
            },
            "data": {
                "seed": self.seed,
                "sampleRate": self.model_config.normalize.sample_rate,
                # chunk geometry governs both the chunk index AND the
                # shard-per-chunk layout — never resume across a change
                "chunkRows": chunk_rows_setting(),
                "shards": int(n_shards),
            },
        })

    def _add_class_meta(self, extra: dict, tags: np.ndarray) -> None:
        """Multi-class: record the tag list + training class priors in
        meta.json — the eval confusion matrix's binRatio source (the
        reference reads binCountPos/Neg per class from the target
        ColumnConfig, ConfusionMatrix.java:645-653)."""
        mc = self.model_config
        if not mc.is_multi_classification():
            return
        from shifu_tpu.eval.multiclass import class_priors

        class_tags = [str(t) for t in mc.tags()]
        extra["classTags"] = class_tags
        extra["classPriors"] = class_priors(
            np.asarray(tags), len(class_tags)
        ).tolist()

    def _run_streaming(self, names) -> None:
        """Bounded-memory norm: one chunked pass writes BOTH artifacts
        (NormalizedData f32 + CleanedData bin codes). Without shuffle, one
        shard per ingest chunk; with shuffle, a two-pass external shuffle
        (ShuffleShardWriter) produces a true uniform global permutation —
        the MR shuffle's contract (core/shuffle/MapReduceShuffle.java:47) —
        with peak memory of one bucket.

        Multi-host (shifu.lifecycle.hosts > 1): each process streams only
        its HostPlan slice of the chunk list, writing chunk-indexed part
        files (HostPartWriter); after a hostsync barrier the merge host
        renames the sorted union into the sequential shard layout, so
        both artifacts are byte-identical to the 1-process run."""
        from shifu_tpu.data.pipeline import HostPlan, prefetch_iter
        from shifu_tpu.data.stream import chunk_source, memory_budget_bytes
        from shifu_tpu.norm.dataset import (
            HostPartWriter,
            ShardWriter,
            ShuffleShardWriter,
        )
        from shifu_tpu.obs import registry, span
        from shifu_tpu.parallel import hostsync
        from shifu_tpu.stats.engine import _prepare_rows

        mc = self.model_config
        ds = mc.data_set
        plan = build_norm_plan(mc, self.column_configs)
        tree_cols = norm_columns(self.column_configs)
        slots = [_slots(c) for c in tree_cols]
        code_dtype = np.int16 if (not slots or max(slots) < 2**15) else np.int32

        hp = self.host_plan if self.host_plan is not None else HostPlan()
        if self.shuffle and hp.active:
            raise ValueError(
                "-shuffle is not multi-host capable: the external-shuffle "
                "writer owns the global permutation and cannot be split "
                "across processes — run the shuffle norm on one process "
                "or drop -Dshifu.lifecycle.hosts")
        if hp.active:
            feat_writer = HostPartWriter(
                self.paths.normalized_data_dir(), "features", np.float32,
                plan.out_names, mc.normalize.norm_type.value,
                extra={"sourceOf": plan.source_of},
            )
            code_writer = HostPartWriter(
                self.paths.cleaned_data_dir(), "codes", code_dtype,
                [c.column_name for c in tree_cols], "CODES",
                extra={"slots": slots},
            )
        elif self.shuffle:
            # bucket count so one bucket fits ~1/4 of the memory budget;
            # gz-compressed text typically expands ~4x when materialized
            from shifu_tpu.data.reader import _expand_paths
            from shifu_tpu.fs.source import size_of

            raw_bytes = sum(
                size_of(p) * (4 if p.endswith(".gz") else 1)
                for p in _expand_paths(self.resolve(ds.data_path)))
            n_buckets = max(
                default_shards(),
                int(np.ceil(raw_bytes / max(memory_budget_bytes() // 4, 1))),
            )
            feat_writer = ShuffleShardWriter(
                self.paths.normalized_data_dir(), "features", np.float32,
                plan.out_names, mc.normalize.norm_type.value,
                n_buckets=n_buckets, seed=self.seed,
                extra={"sourceOf": plan.source_of},
            )
            code_writer = ShuffleShardWriter(
                self.paths.cleaned_data_dir(), "codes", code_dtype,
                [c.column_name for c in tree_cols], "CODES",
                n_buckets=n_buckets, seed=self.seed,
                extra={"slots": slots},
            )
        else:
            feat_writer = ShardWriter(
                self.paths.normalized_data_dir(), "features", np.float32,
                plan.out_names, mc.normalize.norm_type.value,
                extra={"sourceOf": plan.source_of},
            )
            code_writer = ShardWriter(
                self.paths.cleaned_data_dir(), "codes", code_dtype,
                [c.column_name for c in tree_cols], "CODES",
                extra={"slots": slots},
            )
        if ds.filter_expressions:
            needed = None  # expressions may reference any column
        else:
            keep = {s.cc.column_name for s in plan.specs}
            keep.update(c.column_name for c in tree_cols)
            keep.add(ds.target_column_name)
            if ds.weight_column_name:
                keep.add(ds.weight_column_name)
            # parse only the columns this pass reads — meta/padding fields
            # never leave the CSV tokenizer (bounded-memory envelope)
            needed = [n for n in names if n in keep]
        factory = chunk_source(
            self.resolve(ds.data_path), names,
            delimiter=ds.data_delimiter,
            missing_values=tuple(ds.missing_or_invalid_values),
            columns=needed,
        )
        # registry-backed: streaming-stage timings land in the run manifest
        reg = registry()
        timers = reg.stage_timers("norm.stage")

        def _normed(numbered):
            """Prefetch-thread stage: parse + purify + norm + bin-code one
            chunk; the consumer thread only appends to the shard writers."""
            ci, chunk = numbered
            with timers.timer("prepare"):
                chunk, tags, weights = _prepare_rows(
                    mc, chunk, [self.seed, ci], mc.normalize.sample_rate,
                    mc.normalize.sample_neg_only,
                )
            if not chunk.n_rows:
                return None
            with timers.timer("bincode"):
                code_cache: dict = {}
                feats = apply_norm_plan(plan, chunk, code_cache=code_cache)
                codes = bin_code_matrix(tree_cols, chunk, cache=code_cache)
            return ci, feats, codes, tags, weights

        # ---- shard plan + preemption safety: chunks divide round-robin
        # over the lifecycle row shards (ShardPlan — the same plan the
        # stats folds use), each shard keeping its own chunk cursor in
        # its own snapshot file; the artifact writers are the shared
        # reduce state (they append in global chunk order, which is what
        # keeps the output byte-identical across shard counts). The
        # external-shuffle path appends to bucket files and is NOT
        # resumable — it restarts ----
        from shifu_tpu.data.pipeline import ShardPlan
        from shifu_tpu.resilience import checkpoint as ckpt_mod
        from shifu_tpu.resilience import faults

        shard_plan = ShardPlan(host=hp)
        S = shard_plan.n_shards
        cursors = [-1] * S
        shard_rows_f = [0] * S
        ck = None
        n_rows = 0
        all_tag_counts: dict = {}
        sha, sha_sections = self._stream_config_sha(plan, slots, S)
        if not self.shuffle and ckpt_mod.ckpt_stream_enabled():
            # keyed by self.step so a retrain's norm pass (step
            # "retrain-norm") never collides with a real `shifu norm`
            # resume on the same model set
            ck = ckpt_mod.ShardedStreamCheckpoint(
                ckpt_mod.ckpt_base(self.root, self.step, "stream"),
                sha, S, sections=sha_sections,
                n_hosts=hp.n_hosts, host_index=hp.host_index)
            if ckpt_mod.resume_requested():
                loaded = ck.load()
                if loaded is not None:
                    cursors, per_shard, shared = loaded
                    cursors = list(cursors)
                    shard_rows_f = [int(m.get("rows", 0))
                                    for _a, m, _b in per_shard]
                    meta = shared[1]
                    if hp.active:
                        feat_writer.restore(meta["featParts"])
                        code_writer.restore(meta["codeParts"])
                    else:
                        feat_writer.restore(meta["featShardRows"])
                        code_writer.restore(meta["codeShardRows"])
                    n_rows = int(meta["nRows"])
                    all_tag_counts = {int(k): int(v) for k, v in
                                      meta["tagCounts"].items()}
                    faults.survived("preempt")
                    log.info("resuming streaming norm (shard cursors %s)",
                             cursors)
            else:
                ck.clear()
        elif self.shuffle and ckpt_mod.resume_requested():
            log.warning("--resume with -shuffle: the external-shuffle "
                        "writer appends to bucket files and cannot "
                        "resume mid-stream; restarting from row zero")
        if hp.active and not ckpt_mod.resume_requested():
            # fresh fleet run: drop this host's stale barrier part so a
            # dead earlier run can't satisfy the merge barrier early
            hostsync.clear_part(self.root, self.step, hp)

        def _writer_state() -> dict:
            if hp.active:
                return {"featParts": {str(k): v for k, v in
                                      feat_writer.part_rows.items()},
                        "codeParts": {str(k): v for k, v in
                                      code_writer.part_rows.items()}}
            return {"featShardRows": list(feat_writer.shard_rows),
                    "codeShardRows": list(code_writer.shard_rows)}

        def _ckpt_state():
            per_shard = [
                (cursors[s], None, {"rows": shard_rows_f[s]}, None)
                for s in range(S)]
            shared = (None,
                      {**_writer_state(),
                       "nRows": n_rows,
                       "tagCounts": {str(k): v for k, v in
                                     all_tag_counts.items()}},
                      None)
            return per_shard, shared

        with span("norm.stream", shuffle=self.shuffle, shards=S) as sp:
            for item in prefetch_iter(shard_plan.resume_slice(
                                          enumerate(factory()), cursors),
                                      transform=_normed,
                                      timers=timers, stage="parse"):
                if item is None:
                    continue
                faults.fault_point("chunk")
                ci, feats, codes, tags, weights = item
                with timers.timer("write"):
                    if hp.active:
                        feat_writer.add(ci, feats, tags, weights)
                        code_writer.add(ci, codes, tags, weights)
                    else:
                        feat_writer.add(feats, tags, weights)
                        code_writer.add(codes, tags, weights)
                n_rows += len(tags)
                shard = shard_plan.shard_of(ci)
                cursors[shard] = ci
                shard_rows_f[shard] += len(tags)
                shard_plan.record(shard, len(tags), "norm")
                hp.record(len(tags), "norm")
                for t, c in zip(*np.unique(tags, return_counts=True)):
                    all_tag_counts[int(t)] = (
                        all_tag_counts.get(int(t), 0) + int(c))
                if ck is not None:
                    ck.maybe_save(_ckpt_state)
            sp["rows"] = n_rows
        if ck is not None:
            ck.clear()
        reg.counter("norm.rows").inc(n_rows)  # this host's streamed rows
        reg.gauge("norm.columns").set(len(plan.out_names))
        log.info("streaming norm pipeline: %s", timers.summary())

        feat_union: dict = {}
        code_union: dict = {}
        if hp.active:
            # all-gather the per-host part lists; every host learns the
            # fleet union (and merged tag counts) in sorted-host order
            hostsync.publish_part(
                self.root, self.step, hp, sha,
                meta={**_writer_state(),
                      "nRows": n_rows,
                      "tagCounts": {str(k): int(v) for k, v in
                                    all_tag_counts.items()}})
            parts = hostsync.await_parts(self.root, self.step, hp, sha)
            merged_tags: dict = {}
            n_rows = 0
            for _arrays, pmeta, _blob in parts:
                feat_union.update({int(k): int(v) for k, v in
                                   pmeta["featParts"].items()})
                code_union.update({int(k): int(v) for k, v in
                                   pmeta["codeParts"].items()})
                n_rows += int(pmeta["nRows"])
                for k, v in pmeta["tagCounts"].items():
                    merged_tags[int(k)] = merged_tags.get(int(k), 0) + int(v)
            all_tag_counts = merged_tags

        if mc.is_multi_classification() and feat_writer.extra is not None:
            class_tags = [str(t) for t in mc.tags()]
            total = max(sum(all_tag_counts.values()), 1)
            feat_writer.extra["classTags"] = class_tags
            feat_writer.extra["classPriors"] = [
                all_tag_counts.get(k, 0) / total for k in range(len(class_tags))
            ]
        if hp.active:
            if not hp.is_merge_host:
                log.info("streaming norm host %d/%d: %d parts staged; "
                         "merge host writes the artifacts",
                         hp.host_index, hp.n_hosts, len(_writer_state()
                                                        ["featParts"]))
                return
            feat_meta = feat_writer.merge(feat_union)
            code_writer.merge(code_union)
        else:
            feat_meta = feat_writer.close()
            code_writer.close()
        log.info(
            "streaming norm: %d rows x %d cols (%s) -> %s [%d shards] "
            "+ bin codes -> %s",
            n_rows, len(feat_meta.columns), mc.normalize.norm_type.value,
            self.paths.normalized_data_dir(), len(feat_meta.shard_rows),
            self.paths.cleaned_data_dir(),
        )
