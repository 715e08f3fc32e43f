"""Atomic artifact writes + mid-stream checkpoint/resume.

Two layers:

`atomic_write` / `atomic_write_json` / `atomic_save_npy`
    Every checkpoint-like artifact (trainer weights, stream snapshots,
    manifests) must be torn-file-proof: a kill mid-write must leave
    either the previous complete file or the new complete file, never a
    half-written one. The pattern is the only portable one — write to a
    temp file IN THE SAME DIRECTORY, then `os.replace` (atomic on POSIX
    within a filesystem). `shifu check` rule SH104 flags direct
    `np.save`/`open(.., "w")` writes to checkpoint-like paths that
    bypass these helpers.

`StreamCheckpoint`
    The mid-stream snapshot for chunked folds: every
    `shifu.ckpt.everyChunks` folded chunks (default 16) the owning loop
    persists `(chunk_index, fold arrays, meta)` plus a config sha; a
    resumed run (`shifu <step> --resume`) loads it, skips the already-
    folded chunks, and — because the snapshot captures the exact f32
    device window + host f64 fold rather than forcing an early flush —
    produces BIT-IDENTICAL results to an uninterrupted run. A sha
    mismatch (config changed between runs) rejects the checkpoint and
    starts fresh; corrupt files are rejected the same way, never
    crashed on.

Format: one `.ckpt.npz` file — named numpy arrays plus a `__meta__`
JSON payload (chunk index, config sha, caller meta) and an optional
`__blob__` (pickled host-side state, e.g. pass-1 sketches). Writes go
through `atomic_write` with the `ckpt` fault seam inside, so the chaos
harness can prove a kill during checkpointing is survivable.

Metrics: `ckpt.writes`, `ckpt.bytes`, `ckpt.resumes`, `ckpt.rejected`.
"""

from __future__ import annotations

import io
import json
import os
import tempfile
from typing import Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from shifu_tpu.utils import environment
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)

DEFAULT_EVERY_CHUNKS = 16
CKPT_SUBDIR = os.path.join(".shifu", "runs", "ckpt")
CKPT_SUFFIX = ".ckpt.npz"

META_KEY = "__meta__"
BLOB_KEY = "__blob__"


def every_chunks_setting() -> int:
    """shifu.ckpt.everyChunks — stream-checkpoint cadence (chunks between
    snapshots; <= 0 disables mid-stream checkpointing)."""
    return environment.get_int("shifu.ckpt.everyChunks",
                               DEFAULT_EVERY_CHUNKS)


def ckpt_stream_enabled() -> bool:
    """shifu.ckpt.stream — master switch for mid-stream checkpoints
    (default on)."""
    return environment.get_bool("shifu.ckpt.stream", True) \
        and every_chunks_setting() > 0


def resume_requested() -> bool:
    """shifu.resume — set by the CLI `--resume` flags."""
    return environment.get_bool("shifu.resume", False)


# ---------------------------------------------------------------------------
# atomic writes
# ---------------------------------------------------------------------------


def atomic_write(path: str,
                 data: Union[bytes, Callable[[io.BufferedWriter], None]],
                 ) -> str:
    """Write `data` (bytes, or a writer callable) to `path` atomically:
    temp file in the same directory, fsync, `os.replace`. A kill at any
    point leaves the previous file intact."""
    from shifu_tpu.resilience import faults

    path = os.path.abspath(path)
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix="." + os.path.basename(path),
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            if callable(data):
                data(fh)
            else:
                fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        # the injectable failure window: after the bytes are down but
        # before the rename — exactly where a torn write would happen
        # without the temp+replace discipline
        faults.fault_point("ckpt")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:  # already replaced or never created
            pass
        raise
    return path


def atomic_write_json(path: str, obj, indent: int = 2,
                      sort_keys: bool = True) -> str:
    return atomic_write(
        path, json.dumps(obj, indent=indent, sort_keys=sort_keys,
                         default=str).encode("utf-8"))


def atomic_save_npy(path: str, array: np.ndarray) -> str:
    """Atomic `np.save` — the drop-in for every trainer checkpoint write
    (a torn weights.npy used to be possible on any mid-save kill)."""
    buf = io.BytesIO()
    np.save(buf, np.asarray(array))
    return atomic_write(path, buf.getvalue())


# ---------------------------------------------------------------------------
# stream checkpoints
# ---------------------------------------------------------------------------


def config_sha(ident: dict) -> str:
    """Checkpoint-compatibility identity: sha1 over the canonical JSON of
    the caller's identity dict (hyperparameters, layouts, seeds),
    truncated to 16 hex chars. One definition so every resumable stream
    agrees on what 'same config' means."""
    import hashlib

    return hashlib.sha1(
        json.dumps(ident, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]


def sectioned_sha(sections: Dict[str, dict]) -> Tuple[str, Dict[str, str]]:
    """(overall sha, per-section shas) for a SECTIONED identity — e.g.
    {"data": {...}, "train": {...}, "loop": {...}}. The overall sha keys
    checkpoint compatibility exactly like `config_sha`; the per-section
    shas ride in the snapshot meta so a rejection can say WHICH section
    (data vs train vs loop) diverged instead of just "config changed"."""
    per = {name: config_sha(ident) for name, ident in sections.items()}
    return config_sha(per), per


def resume_slice(numbered, after: int):
    """Skip the already-folded prefix of an enumerate()-style stream:
    yields the (index, item) pairs with index > `after` (the chunk index
    a StreamCheckpoint recorded). Indices ride with the items, so
    index-keyed draws ([seed, chunk_index] sampling) are preserved."""
    for pair in numbered:
        if pair[0] > after:
            yield pair


def ckpt_dir(root: str) -> str:
    return os.path.join(os.path.abspath(root), CKPT_SUBDIR)


def ckpt_path(root: str, step: str, name: str) -> str:
    return os.path.join(ckpt_dir(root), f"{step}-{name}{CKPT_SUFFIX}")


def ckpt_base(root: str, step: str, name: str) -> str:
    """Suffix-less base path for a sharded checkpoint family
    (`<base>-shardNNNNN.ckpt.npz` + `<base>-shared.ckpt.npz`)."""
    return os.path.join(ckpt_dir(root), f"{step}-{name}")


class StreamCheckpoint:
    """One resumable stream's snapshot file.

    `save` persists (chunk_index, arrays, meta [, blob]) atomically;
    `load` returns them only when the stored config sha matches —
    resuming a fold onto changed config/binning would be silently wrong,
    so mismatch means start fresh. `maybe_save` applies the cadence so
    callers write one line, and `state_fn` is only invoked when a write
    is actually due (snapshotting can cost a device sync)."""

    def __init__(self, path: str, config_sha: str,
                 every: Optional[int] = None,
                 sections: Optional[Dict[str, str]] = None) -> None:
        self.path = path
        self.config_sha = config_sha
        # per-section shas (sectioned_sha): stored in the snapshot meta so
        # a config-mismatch rejection names the diverged section(s)
        self.sections = dict(sections) if sections else None
        self.every = every_chunks_setting() if every is None else int(every)
        self._since = 0

    # ---- write side ----
    def save(self, chunk_index: int,
             arrays: Optional[Dict[str, np.ndarray]] = None,
             meta: Optional[dict] = None,
             blob: Optional[bytes] = None) -> str:
        from shifu_tpu.obs import registry
        from shifu_tpu.resilience import retry

        payload: Dict[str, np.ndarray] = {}
        for k, v in (arrays or {}).items():
            assert not k.startswith("__"), k
            payload[k] = np.asarray(v)
        header = {
            "chunkIndex": int(chunk_index),
            "configSha": self.config_sha,
            "meta": meta or {},
        }
        if self.sections:
            header["sections"] = self.sections
        payload[META_KEY] = np.frombuffer(
            json.dumps(header, sort_keys=True).encode("utf-8"),
            dtype=np.uint8)
        if blob is not None:
            payload[BLOB_KEY] = np.frombuffer(blob, dtype=np.uint8)
        buf = io.BytesIO()
        np.savez(buf, **payload)
        data = buf.getvalue()
        # retried: an injected (or real, transient) failure during the
        # checkpoint write must not kill the stream it protects
        retry.retry_call(lambda: atomic_write(self.path, data), seam="ckpt")
        reg = registry()
        reg.counter("ckpt.writes").inc()
        reg.counter("ckpt.bytes").inc(len(data))
        return self.path

    def maybe_save(self, chunk_index: int,
                   state_fn: Callable[[], Tuple[Optional[Dict[str, np.ndarray]],
                                                Optional[dict],
                                                Optional[bytes]]],
                   ) -> bool:
        """Cadence-gated save after folding chunk `chunk_index`; returns
        True when a snapshot was written."""
        if self.every <= 0:
            return False
        self._since += 1
        if self._since < self.every:
            return False
        self._since = 0
        arrays, meta, blob = state_fn()
        self.save(chunk_index, arrays=arrays, meta=meta, blob=blob)
        return True

    # ---- read side ----
    def load(self) -> Optional[Tuple[int, Dict[str, np.ndarray],
                                     dict, Optional[bytes]]]:
        """(chunk_index, arrays, meta, blob) or None (absent / corrupt /
        config mismatch — all mean start fresh, never crash)."""
        from shifu_tpu.obs import registry

        if not os.path.isfile(self.path):
            return None
        try:
            with np.load(self.path) as z:
                header = json.loads(bytes(z[META_KEY].tobytes()).decode())
                arrays = {k: z[k] for k in z.files
                          if k not in (META_KEY, BLOB_KEY)}
                blob = (z[BLOB_KEY].tobytes()
                        if BLOB_KEY in z.files else None)
        except Exception as e:  # corrupt/truncated checkpoint: start fresh
            log.warning("checkpoint %s unreadable (%s); starting fresh",
                        self.path, e)
            registry().counter("ckpt.rejected", reason="corrupt").inc()
            return None
        if header.get("configSha") != self.config_sha:
            # name the diverged section(s) when both sides recorded them:
            # "config changed" is useless at 3am; "the data section
            # changed but train didn't" tells the operator to re-run the
            # upstream step rather than question their hyperparameters
            stored = header.get("sections") or {}
            diverged = "unknown"
            if stored and self.sections:
                names = sorted(
                    k for k in set(stored) | set(self.sections)
                    if stored.get(k) != self.sections.get(k))
                diverged = ",".join(names) or "unknown"
            log.warning("checkpoint %s was built under a different config "
                        "(%s != %s; diverged section(s): %s); starting "
                        "fresh", self.path, header.get("configSha"),
                        self.config_sha, diverged)
            registry().counter("ckpt.rejected", reason="config",
                               section=diverged).inc()
            return None
        registry().counter("ckpt.resumes").inc()
        return int(header["chunkIndex"]), arrays, header.get("meta", {}), blob

    def clear(self) -> None:
        """Remove the snapshot (the stream completed; nothing to resume)."""
        try:
            os.unlink(self.path)
        except OSError:  # never written / already cleared
            pass


class ShardedStreamCheckpoint:
    """Per-shard snapshot family for a sharded streaming fold.

    One snapshot file PER ROW SHARD — shard s's file carries (its own
    chunk cursor, its own local fold state, its own counters) — plus one
    `-shared` file for the state no single shard owns (the post-psum
    host float64 fold, writer bookkeeping). All files share the caller's
    config sha.

    Kill-atomicity is two-phase: shard files ALTERNATE between two slots
    (`-shard00000-a` / `-b`) per save epoch, and the shared file —
    written LAST, itself atomic — is the commit pointer: its meta names
    the epoch and the slot that form the current complete family. A kill
    anywhere during the S shard-file writes touches only the NEW slot;
    the shared pointer still names the previous slot, whose files this
    save never opened — so the previous complete snapshot is never lost,
    exactly the guarantee the single-file `atomic_write` gave the
    unsharded folds. `load` verifies every pointed-at shard file carries
    the committed epoch and shard count and otherwise rejects the WHOLE
    family (`ckpt.rejected{reason=partial|epoch|shards}`) — shards must
    never resume from different cadence points than the shared reduce
    state they fold into.

    Under a multi-process HostPlan (`n_hosts` > 1) the family is
    PER-HOST: host h's files live under `<base>-h00h-...`, carry only
    h's own cursor slice and local fold state, and h resumes from them
    alone — no host ever reads another host's cursors. The committed
    stamp records the host count, and a host-count change between runs
    rejects the family (`ckpt.rejected{reason=hosts}`) exactly like a
    shard-count change does: the chunk -> host assignment moved, so
    every stored cursor names a different slice. `n_hosts=1` keeps the
    legacy un-prefixed file names byte-for-byte.

    The layout is identical on a real pod, so the resume contract
    carries over unchanged. `clear` globs the whole family — including
    stale slot or extra-shard files a previous run with a different
    shard count left — so nothing phantom ever shows in `shifu runs
    --resumable` (a 1-host clear also sweeps leftover per-host families;
    a multi-host clear touches only its OWN host's files — other hosts'
    live families are theirs to clear).
    """

    _SLOTS = ("a", "b")

    def __init__(self, base: str, config_sha: str, n_shards: int,
                 every: Optional[int] = None,
                 sections: Optional[Dict[str, str]] = None,
                 n_hosts: int = 1, host_index: int = 0,
                 part_kind: str = "shards") -> None:
        self.base = base
        self.n_shards = max(1, int(n_shards))
        self.n_hosts = max(1, int(n_hosts))
        self.host_index = int(host_index)
        self.config_sha = config_sha
        # what a part IS: "shards" for the row-sharded folds (legacy
        # byte-identical), "stages" for the co-resident trainer's
        # per-pipeline-stage family. The kind names the stamp key, the
        # per-part file infix, and the rejection reason when the count
        # moved between runs.
        self.part_kind = part_kind
        self._part_infix = ("shard" if part_kind == "shards"
                            else (part_kind[:-1] if part_kind.endswith("s")
                                  else part_kind) or "part")
        self.every = every_chunks_setting() if every is None else int(every)
        self._since = 0
        self._epoch = 0
        family = (base if self.n_hosts == 1
                  else f"{base}-h{self.host_index:03d}")
        self._family = family
        self._shards = [
            {slot: StreamCheckpoint(
                f"{family}-{self._part_infix}{s:05d}-{slot}{CKPT_SUFFIX}",
                config_sha, every=0, sections=sections)
             for slot in self._SLOTS}
            for s in range(self.n_shards)]
        self._shared = StreamCheckpoint(f"{family}-shared{CKPT_SUFFIX}",
                                        config_sha, every=0,
                                        sections=sections)

    def _slot(self, epoch: int) -> str:
        return self._SLOTS[epoch % len(self._SLOTS)]

    # ---- write side ----
    def save(self, per_shard: List[Tuple[int, Optional[Dict[str, np.ndarray]],
                                         Optional[dict], Optional[bytes]]],
             shared: Tuple[Optional[Dict[str, np.ndarray]], Optional[dict],
                           Optional[bytes]]) -> None:
        """Persist every shard's (cursor, arrays, meta, blob) into the
        next slot, then commit by writing the shared pointer last."""
        assert len(per_shard) == self.n_shards, \
            (len(per_shard), self.n_shards)
        epoch = self._epoch + 1
        slot = self._slot(epoch)
        stamp = {"epoch": epoch, self.part_kind: self.n_shards}
        if self.n_hosts > 1:
            stamp["hosts"] = self.n_hosts
            stamp["host"] = self.host_index
        for cks, (ci, arrays, meta, blob) in zip(self._shards, per_shard):
            cks[slot].save(ci, arrays=arrays,
                           meta={**(meta or {}), **stamp}, blob=blob)
        arrays, meta, blob = shared
        self._shared.save(-1, arrays=arrays,
                          meta={**(meta or {}), **stamp, "slot": slot},
                          blob=blob)
        self._epoch = epoch  # committed

    def maybe_save(self, state_fn: Callable[[], tuple]) -> bool:
        """Cadence-gated save (one call per folded chunk); `state_fn`
        returns (per_shard, shared) and is only invoked when a write is
        due."""
        if self.every <= 0:
            return False
        self._since += 1
        if self._since < self.every:
            return False
        self._since = 0
        per_shard, shared = state_fn()
        self.save(per_shard, shared)
        return True

    # ---- read side ----
    def load(self) -> Optional[Tuple[
            List[int], List[Tuple[Dict[str, np.ndarray], dict,
                                  Optional[bytes]]],
            Tuple[Dict[str, np.ndarray], dict, Optional[bytes]]]]:
        """(cursors, per_shard [(arrays, meta, blob)], shared) or None.
        The shared pointer names the committed (epoch, slot); any shard
        file of that slot missing/corrupt/sha-mismatched, a shard-count
        change, or an epoch disagreeing with the pointer rejects the
        WHOLE family — partial resumes would silently double- or
        drop-fold chunks."""
        from shifu_tpu.obs import registry

        shared = self._shared.load()
        if shared is None:
            return None
        epoch = shared[2].get("epoch")
        slot = shared[2].get("slot")
        if epoch is None or slot not in self._SLOTS:
            registry().counter("ckpt.rejected", reason="partial").inc()
            return None
        if shared[2].get(self.part_kind) != self.n_shards:
            # e.g. `ckpt.rejected{reason="stages"}` when a co-resident
            # resume asks for a different pipeline partitioning than the
            # family was written under — every stored part covers a
            # different flat slice, so resuming would be silently wrong
            log.warning("sharded checkpoint %s was written with %s %s "
                        "(now %d); starting fresh", self.base,
                        shared[2].get(self.part_kind), self.part_kind,
                        self.n_shards)
            registry().counter("ckpt.rejected",
                               reason=self.part_kind).inc()
            return None
        if shared[2].get("hosts", 1) != self.n_hosts:
            # the chunk -> host assignment moved: every stored cursor
            # names a slice this run will never be handed, so resuming
            # would double- and drop-fold chunks at once
            log.warning("sharded checkpoint %s was written with %s hosts "
                        "(now %d); starting fresh", self._family,
                        shared[2].get("hosts", 1), self.n_hosts)
            registry().counter("ckpt.rejected", reason="hosts").inc()
            return None
        loads = [cks[slot].load() for cks in self._shards]
        if any(ld is None for ld in loads):
            registry().counter("ckpt.rejected", reason="partial").inc()
            return None
        epochs = {ld[2].get("epoch") for ld in loads}
        if epochs != {epoch}:
            log.warning("sharded checkpoint %s slot %s has epochs %s but "
                        "the pointer committed %s; starting fresh",
                        self.base, slot,
                        sorted(str(e) for e in epochs), epoch)
            registry().counter("ckpt.rejected", reason="epoch").inc()
            return None
        self._epoch = int(epoch)
        cursors = [ld[0] for ld in loads]
        per_shard = [(ld[1], ld[2], ld[3]) for ld in loads]
        return cursors, per_shard, (shared[1], shared[2], shared[3])

    def clear(self) -> None:
        """Remove the WHOLE family — both slots, the pointer, and any
        stale `-shardNNNNN*` files a run with a different shard count
        left behind (they would otherwise show as phantom resumables).
        A 1-host clear also sweeps per-host (`-hNNN-*`) families from an
        earlier multi-host run; a multi-host clear stays inside its own
        host's family — the other hosts' files are live state owned by
        running peers."""
        from shifu_tpu.fs.listing import sorted_glob

        patterns = [self._family + "-" + self._part_infix + "*"
                    + CKPT_SUFFIX]
        if self.n_hosts == 1:
            patterns.append(self.base + "-h*" + CKPT_SUFFIX)
        for pattern in patterns:
            for path in sorted_glob(pattern):
                try:
                    os.unlink(path)
                except OSError:  # already gone
                    pass
        self._shared.clear()


def list_resumable(root: str) -> List[dict]:
    """Stream checkpoints a preempted step left behind — the data for
    `shifu runs --resumable`. Scans <root>/.shifu/runs/ckpt (the chunked
    fold snapshots) AND the trainer checkpoint dirs (streamed NN/WDL
    state lives beside cfg.checkpoint_path — under tmp/train/ for
    `shifu train`, under tmp/retrain/train/ for `shifu retrain`)."""
    from shifu_tpu.fs.listing import sorted_glob

    root = os.path.abspath(root)
    paths: List[str] = []
    d = ckpt_dir(root)
    if os.path.isdir(d):
        paths.extend(os.path.join(d, name) for name in sorted(os.listdir(d))
                     if name.endswith(CKPT_SUFFIX))
    trainer_globs = [
        ("train", os.path.join(root, "tmp", "train")),
        ("retrain", os.path.join(root, "tmp", "retrain", "train")),
    ]
    step_of = {}
    for step, base in trainer_globs:
        for path in sorted_glob(
                os.path.join(base, "**", "*" + CKPT_SUFFIX),
                recursive=True):
            paths.append(path)
            step_of[path] = step
    import re

    # a co-resident family is MANY files (per-stage slots + the shared
    # commit pointer) but ONE resumable run: list the pointer as one
    # aggregated entry and hide the per-stage slot files behind it
    part_re = re.compile(r"^coresident-.+-stage\d{5}-[ab]$")
    out: List[dict] = []
    for path in paths:
        name = os.path.basename(path)[: -len(CKPT_SUFFIX)]
        if os.path.dirname(path) == d and part_re.match(name):
            continue
        if os.path.dirname(path) != d:
            # trainer snapshot: qualify with its checkpoint dir so bagged
            # members (checkpoint_0, checkpoint_1, ...) stay distinct,
            # and with the step so `shifu retrain --resume` state is
            # distinguishable from `shifu train --resume` state
            name = (f"{step_of.get(path, 'train')}-"
                    f"{os.path.basename(os.path.dirname(path))}")
        entry = {
            "name": name,
            "path": path,
            "bytes": os.path.getsize(path),
            "mtime": os.path.getmtime(path),
        }
        try:
            with np.load(path) as z:
                header = json.loads(bytes(z[META_KEY].tobytes()).decode())
            entry["chunkIndex"] = header.get("chunkIndex")
            entry["configSha"] = header.get("configSha")
            entry["meta"] = header.get("meta", {})
            if (os.path.dirname(path) == d
                    and name.startswith("coresident-")
                    and name.endswith("-shared")):
                # the family's commit pointer: surface the run identity
                # (trainer epoch + stage count) for `shifu runs
                # --resumable`
                entry["name"] = name[: -len("-shared")]
                entry["family"] = "coresident"
                entry["epoch"] = entry["meta"].get("it")
                entry["stages"] = entry["meta"].get("stages")
        except Exception:  # unreadable: still listed, marked corrupt
            entry["corrupt"] = True
        out.append(entry)
    return out
