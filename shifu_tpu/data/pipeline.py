"""Overlapped streaming pipeline: background chunk prefetch feeding
shape-bucketed jit consumers.

The serial chunked paths ran parse -> host bin-code -> device aggregate ->
device->host sync strictly in sequence, one chunk at a time, so the device
idled during every parse and the host idled during every device step. This
module supplies the three pieces every chunked consumer shares (streaming
stats, streaming norm, the NN/WDL/tree shard feeds, chunked scoring):

  * ``prefetch_iter`` — a bounded-queue background producer. ONE worker
    thread pulls the source iterator and applies the host-side transform
    (CSV parse, bin-coding, shard load) while the consumer's device work
    runs; up to ``shifu.ingest.prefetchChunks`` (default 2) transformed
    chunks sit ready in the queue. A single thread plus a FIFO queue keeps
    chunk order — and therefore every accumulated result — bit-identical
    to the serial path; ``prefetchChunks=0`` degrades to a plain inline
    loop for debugging.
  * ``bucket_rows`` — power-of-two row buckets, so padded chunk shapes
    take O(log max_chunk_rows) distinct values and jit consumers compile
    a bounded set of programs regardless of the chunk-size sequence (the
    old running-max padding recompiled every time a larger chunk arrived).
  * ``ShardPlan`` — the deterministic chunk -> row-shard assignment the
    whole lifecycle shares (round-robin on the chunk index), so every
    streaming fold divides work O(rows/shards) over the mesh and every
    shard can prefetch exactly its own slice.
  * ``DeviceAccumulator`` — keeps one f32 BinAggregates window PER ROW
    SHARD resident on the lifecycle mesh across chunks (the fold is a
    shard_map program: each shard aggregates its own chunk locally), so
    the only device->host transfer in a streamed aggregation is one
    psum-tree-reduced window flush instead of a full sync per chunk —
    and instead of one pull per shard.
"""

from __future__ import annotations

import queue
import threading
from typing import (
    Any,
    Callable,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from shifu_tpu.utils import environment
from shifu_tpu.utils.timing import StageTimers

DEFAULT_PREFETCH_CHUNKS = 2

# Smallest row bucket: chunks below this all pad to one shape, so tiny
# ragged tails don't each compile their own program.
MIN_ROW_BUCKET = 256


def prefetch_chunks_setting() -> int:
    """shifu.ingest.prefetchChunks — queue depth of the background
    prefetcher (0 = serial inline execution)."""
    return environment.get_int("shifu.ingest.prefetchChunks",
                               DEFAULT_PREFETCH_CHUNKS)


def bucket_rows(n: int, minimum: int = MIN_ROW_BUCKET) -> int:
    """Smallest power of two >= n (floored at `minimum`).

    Padding chunks to bucketed row counts bounds the set of shapes a jit
    consumer ever sees at O(log max_chunk_rows), whatever the chunk-size
    sequence; padding waste is < 2x compute on the padded rows, which carry
    zero weight/invalid tags and change no result."""
    if n <= minimum:
        return minimum
    return 1 << int(n - 1).bit_length()


def prefetch_iter(
    source: Iterable[Any],
    depth: Optional[int] = None,
    transform: Optional[Callable[[Any], Any]] = None,
    timers: Optional[StageTimers] = None,
    stage: str = "parse",
) -> Iterator[Any]:
    """Iterate `source` with the pull + `transform` running on a background
    thread, keeping up to `depth` transformed items ready.

    `depth` defaults to shifu.ingest.prefetchChunks; depth <= 0 runs the
    identical pull/transform inline (serial fallback). `timers`, when
    given, accumulates the source-pull wall-clock under `stage` (the
    transform times its own stages so none is double-counted) — time the
    consumer does NOT wait for once the queue is warm. Up to depth + 2
    items are in flight: the queue, one finished item in a blocked worker,
    one in the consumer.

    Guarantees: items arrive in source order (one worker, FIFO queue);
    worker exceptions re-raise in the consumer at the failing position;
    abandoning the iterator (break / close) stops the worker promptly.
    """
    if depth is None:
        depth = prefetch_chunks_setting()

    def _produce(it: Iterator[Any]):
        from shifu_tpu.resilience import faults

        # guarded like profile.dispatch's device seam: the unfaulted hot
        # path pays one property lookup per chunk, nothing more
        chaos = faults.plan_active()
        if chaos:
            from shifu_tpu.resilience import retry

            # `io` fault seam BEFORE the pull, retried under the io
            # budget. Only the injected fault is retryable here: an
            # exception raised inside next(it) CLOSES a generator
            # source, so "retrying" the pull would read as a clean
            # end-of-stream and silently truncate the chunk stream —
            # real read errors must stay loud.
            retry.retry_call(lambda: faults.fault_point("io"), seam="io")
        if timers is not None:
            with timers.timer(stage):
                item = next(it)
        else:
            item = next(it)
        if transform is not None:
            if chaos:
                from shifu_tpu.resilience import retry

                # the per-chunk transform is pure host work (parse/
                # bin-code/pad), so a crashed prefetch worker "restarts"
                # by re-running it under the retry budget
                def _apply(i=item):
                    faults.fault_point("prefetch")
                    return transform(i)

                item = retry.retry_call(_apply, seam="prefetch")
            else:
                item = transform(item)
        from shifu_tpu.obs import registry

        registry().counter("pipeline.chunks").inc()
        return item

    if depth <= 0:
        def _serial() -> Iterator[Any]:
            it = iter(source)
            while True:
                try:
                    yield _produce(it)
                except StopIteration:
                    return

        return _serial()

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def _put(msg) -> bool:
        while not stop.is_set():
            try:
                q.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _work() -> None:
        try:
            it = iter(source)
        except BaseException as e:  # a failing __iter__ must not hang the consumer
            _put(("error", e))
            return
        while not stop.is_set():
            try:
                item = _produce(it)
            except StopIteration:
                _put(("end", None))
                return
            except BaseException as e:  # re-raised consumer-side
                _put(("error", e))
                return
            if not _put(("item", item)):
                return
            # drop the local reference NOW: otherwise the handed-off chunk
            # stays alive in this frame until the next _produce returns,
            # keeping one extra chunk resident for the whole parse
            item = None

    def _consume() -> Iterator[Any]:
        worker = threading.Thread(target=_work, name="shifu-prefetch",
                                  daemon=True)
        worker.start()
        try:
            while True:
                kind, val = q.get()
                if kind == "end":
                    return
                if kind == "error":
                    raise val
                yield val
                # the consumer is done with the chunk once it re-enters the
                # generator; release it before blocking on the queue or one
                # extra chunk stays resident across the whole next wait
                val = None
        finally:
            stop.set()
            try:  # unblock a worker stuck on a full queue
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            worker.join(timeout=5.0)

    return _consume()


# ---------------------------------------------------------------------------
# shard planning — the lifecycle map/reduce work division
# ---------------------------------------------------------------------------


class HostPlan:
    """Deterministic chunk -> HOST assignment: the per-process layer of
    the pod-scale data plane, sitting ABOVE ShardPlan's per-device
    round-robin.

    Round-robin on the global chunk index: `host_of(ci) = ci % H`, so
    with H hosts over K chunk files every process prefetches and folds
    at most ceil(K/H) of them — the work-division bound
    tests/test_sharded_lifecycle.py::TestHostPlan holds. Like ShardPlan
    the assignment is a pure function of (ci, H): every process derives the identical partition
    with zero coordination, keyed only by its own host index
    (-Dshifu.lifecycle.hostIndex, or jax.process_index() on a real pod;
    the PR-14 lease id names the process, the index orders it).
    `local_index(ci) = ci // H` renumbers a host's own chunks densely so
    the per-device ShardPlan composes underneath and every LOCAL shard
    still folds ~1/S of the host's slice. H=1 is the degenerate
    single-controller plan — same code path, every chunk owned.
    """

    def __init__(self, n_hosts: Optional[int] = None,
                 host_index: Optional[int] = None) -> None:
        from shifu_tpu.parallel.mesh import (
            lifecycle_host_index,
            lifecycle_hosts,
        )

        self.n_hosts = (lifecycle_hosts() if n_hosts is None
                        else max(1, int(n_hosts)))
        self.host_index = (lifecycle_host_index() if host_index is None
                           else int(host_index))
        if not (0 <= self.host_index < self.n_hosts):
            raise ValueError(
                f"host index {self.host_index} outside [0, {self.n_hosts})"
                " — check -Dshifu.lifecycle.hostIndex vs"
                " -Dshifu.lifecycle.hosts")

    @property
    def active(self) -> bool:
        return self.n_hosts > 1

    @property
    def is_merge_host(self) -> bool:
        """Host 0 merges the per-host partials in sorted-host order and
        writes the final artifacts; every other host publishes its part
        and leaves the shared files alone."""
        return self.host_index == 0

    def host_of(self, chunk_index: int) -> int:
        return chunk_index % self.n_hosts

    def owns(self, chunk_index: int) -> bool:
        return chunk_index % self.n_hosts == self.host_index

    def local_index(self, chunk_index: int) -> int:
        """Dense ordinal of an OWNED chunk within this host's slice —
        what the per-device ShardPlan round-robins on, so all S local
        shards stay busy whatever H is."""
        return chunk_index // self.n_hosts

    def record(self, rows: int, stage: str) -> None:
        """Per-host obs: host.chunks / host.rows land in every manifest
        labeled by host and lifecycle stage — the counters the CI
        affinity-division assertion reads (each process only ever
        increments its OWN host label, so two processes' manifests are
        disjoint by construction)."""
        from shifu_tpu.obs import registry

        reg = registry()
        h = str(self.host_index)
        reg.counter("host.chunks", host=h, stage=stage).inc()
        reg.counter("host.rows", host=h, stage=stage).inc(rows)


class ShardPlan:
    """Deterministic chunk -> row-shard assignment for the lifecycle
    folds (streaming stats, norm, eval scoring, init autotype).

    Round-robin on the global chunk index: `shard_of(ci) = ci % S`, so
    with S shards over K chunks every shard folds at most ceil(K/S)
    chunks — the work-division bound
    tests/test_sharded_lifecycle.py::TestShardPlan holds. The
    assignment is a pure function of (ci, S): every pass, every resume,
    and every host in a real multi-host run derives the identical plan
    with zero coordination, and a shard can prefetch exactly its own
    slice of the chunk stream (`shard_slice`). S=1 is the degenerate
    single-device plan — same code path, every chunk on shard 0.

    With a multi-process HostPlan composed on top (`host=`), ownership
    filters FIRST — this process only ever sees chunks with
    `host_of(ci) == host_index` — and the round-robin runs on the host's
    dense local ordinal (`ci // H`), so all S local shards divide the
    host's slice evenly whatever H is. H=1 reduces every formula to the
    original global one.
    """

    def __init__(self, n_shards: Optional[int] = None,
                 host: Optional[HostPlan] = None) -> None:
        from shifu_tpu.parallel.mesh import lifecycle_shards

        self.n_shards = (lifecycle_shards() if n_shards is None
                         else max(1, int(n_shards)))
        self.host = HostPlan() if host is None else host

    def shard_of(self, chunk_index: int) -> int:
        return self.host.local_index(chunk_index) % self.n_shards

    def group_of(self, chunk_index: int) -> int:
        """Super-step index: group g holds this host's local chunks
        [g*S, (g+1)*S) — one chunk per shard, the unit one sharded fold
        dispatch consumes."""
        return self.host.local_index(chunk_index) // self.n_shards

    def shard_slice(self, numbered: Iterable, shard: int) -> Iterator:
        """Only the owned (ci, item) pairs assigned to `shard` — what a
        multi-host shard prefetches as its own slice."""
        for ci, item in numbered:
            if self.host.owns(ci) and self.shard_of(ci) == shard:
                yield ci, item

    def slices(self, items: Sequence) -> List[List[Tuple[int, Any]]]:
        """Enumerate the chunk list ONCE and hand every shard its index
        view: views[s] is the list of owned (ci, item) pairs shard s
        folds. Replaces S separate `shard_slice` passes, each of which
        re-enumerated (and re-filtered) the full K-chunk list — O(K)
        instead of O(K*S) for per-shard fan-out over a materialized
        list."""
        views: List[List[Tuple[int, Any]]] = \
            [[] for _ in range(self.n_shards)]
        for ci, item in enumerate(items):
            if self.host.owns(ci):
                views[self.shard_of(ci)].append((ci, item))
        return views

    def resume_slice(self, numbered: Iterable,
                     cursors: Sequence[int]) -> Iterator:
        """Per-shard resume over this host's slice: yield owned
        (ci, item) pairs each local shard has NOT folded yet (ci > its
        cursor). Chunks below every cursor are skipped before parse,
        exactly like the single-cursor checkpoint.resume_slice."""
        for pair in numbered:
            ci = pair[0]
            if self.host.owns(ci) and ci > cursors[self.shard_of(ci)]:
                yield pair

    def record(self, shard: int, rows: int, stage: str) -> None:
        """Per-shard obs: shard.chunks / shard.rows land in every
        manifest, labeled by shard and lifecycle stage — the counters the
        work-division acceptance asserts."""
        from shifu_tpu.obs import registry

        reg = registry()
        reg.counter("shard.chunks", shard=str(shard), stage=stage).inc()
        reg.counter("shard.rows", shard=str(shard), stage=stage).inc(rows)


# Device windows fold in f32; a slot's count stays exact below 2^24. The
# psum reduce SUMS the S shard windows in f32, so the bound that matters
# is the TOTAL row count across all shard windows: the window flushes to
# the host float64 fold before that total can reach 2^24 (2^23 leaves a
# whole 65536-row chunk of headroom; a reduced slot count is bounded by
# the window's total rows). Per-shard bounds alone would NOT be enough —
# S exact per-shard counts can sum past 2^24.
WINDOW_FLUSH_ROWS = 1 << 23


class DeviceAccumulator:
    """Sharded device-resident fold of per-chunk BinAggregates, flushed
    to a host float64 fold in bounded windows.

    One f32 window per row shard, stacked [S, ...] and sharded over the
    lifecycle mesh (parallel/mesh.py). The fold is a shard_map program
    (ops/binagg.sharded_window_fold): each shard bin-aggregates its own
    chunk locally and folds it into its own window — one dispatch folds
    up to S chunks with no cross-shard traffic. The windowed flush is ONE
    psum-tree reduction over the mesh's row axes (dcn, data) followed by
    ONE device->host sync — where a per-shard host accumulation would
    cost O(S) pulls per window, the reduce rides ICI/DCN and the host
    sees a single replicated result.

    Exactness invariant (unchanged from the single-device fold, which is
    the S=1 degenerate case of this class): within a window every count
    is exact in f32 — each shard's slot counts are bounded by its own
    window rows, the psum sums them exactly because the flush policy
    bounds the TOTAL window rows across shards below 2^23 < 2^24 — and
    the moment sums are float-summation-order-accurate; across windows
    everything accumulates in float64 — arbitrarily long streams cannot
    saturate, and counts are exact at any stream length and shard count.
    """

    def __init__(self, flush_rows: int = WINDOW_FLUSH_ROWS,
                 n_shards: int = 1) -> None:
        self._acc = None  # stacked [S, ...] device windows
        self._host: Optional[List[np.ndarray]] = None  # f64 fold
        self._flush_rows = flush_rows
        self.n_shards = max(1, int(n_shards))
        self._rows = np.zeros(self.n_shards, dtype=np.int64)
        self._mesh = None

    @property
    def mesh(self):
        if self._mesh is None:
            from shifu_tpu.parallel.mesh import lifecycle_mesh

            self._mesh = lifecycle_mesh(self.n_shards)
        return self._mesh

    @property
    def empty(self) -> bool:
        return self._acc is None and self._host is None

    @property
    def window_rows(self) -> int:
        """Total window rows across shards (the f32-exactness bound the
        flush policy enforces — the psum reduce sums all shards)."""
        return int(self._rows.sum())

    def _flush(self) -> None:
        if self._acc is None:
            return
        import jax

        from shifu_tpu.obs import profile, registry
        from shifu_tpu.ops.binagg import window_reduce

        from shifu_tpu.parallel.mesh import hierarchical_reduce

        reg = registry()
        # the reduce: ONE psum tree over the row axes closes all S shard
        # windows; the single device_get below is the window's ENTIRE d2h
        # budget — was one pull per shard
        reg.counter("reduce.psum_windows").inc()
        reg.counter("device.d2h_syncs").inc()
        if hierarchical_reduce(self.mesh):
            # explicit two-stage lowering: the window crossed DCN as ONE
            # per-slice partial after the ICI psum (ops/binagg)
            reg.counter("reduce.dcn_hops").inc()
        reduced = profile.dispatch(
            "pipeline.psum_reduce", window_reduce(self.mesh), self._acc,
            sync=False)
        part = [np.asarray(x[0], dtype=np.float64)
                for x in jax.device_get(reduced)]
        # -Dshifu.sanitize=divergence: digest every window fold so two
        # runs of the same stream can diff WHERE determinism broke
        from shifu_tpu.analysis import sanitize

        sanitize.record_fold("pipeline.window", part)
        self._acc = None
        self._rows[:] = 0
        if self._host is None:
            self._host = part
        else:
            self._host = [
                np.minimum(h, p) if k == 6 else  # vmin
                np.maximum(h, p) if k == 7 else  # vmax
                h + p
                for k, (h, p) in enumerate(zip(self._host, part))
            ]

    def _ensure_window(self, total_slots: int, n_numeric: int) -> None:
        if self._acc is None:
            from shifu_tpu.ops.binagg import window_init

            self._acc = window_init(self.mesh, total_slots, n_numeric)

    def add(self, agg, rows: int, shard: int = 0) -> None:
        """Fold ONE precomputed chunk aggregate into `shard`'s window;
        `rows` is the chunk's REAL row count (padding rows carry invalid
        tags and count nothing). The streamed stats path uses fold_group
        (the in-program map) instead; this is the entry point for callers
        that already hold a BinAggregates."""
        if self._acc is not None \
                and self.window_rows + rows > self._flush_rows:
            self._flush()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from shifu_tpu.analysis import sanitize
        from shifu_tpu.obs import profile
        from shifu_tpu.ops.binagg import masked_window_add

        self._ensure_window(int(agg.pos.shape[0]), int(agg.vsum.shape[0]))
        # replication of the aggregate across the mesh is the one
        # sanctioned move — explicit, before the guard arms
        rep = NamedSharding(self.mesh, P())
        agg = jax.device_put(agg, rep)
        sid = jax.device_put(np.int32(shard), rep)
        # sanitizer seam: window + aggregate are now device-resident and
        # correctly placed, so the fold dispatch must not move bytes; the
        # only sanctioned transfer is _flush's explicit device_get.
        # Profiled async (sync would reintroduce the per-chunk RTT wait
        # this accumulator exists to remove).
        with sanitize.transfer_free("pipeline.device_fold"):
            self._acc = profile.dispatch(
                "pipeline.device_fold", masked_window_add(self.mesh),
                self._acc, agg, sid, sync=False)
        self._rows[shard] += rows

    def fold_group(self, codes: np.ndarray, col_offsets: np.ndarray,
                   total_slots: int, tags: np.ndarray,
                   weights: np.ndarray, values: np.ndarray,
                   rows_per_shard: Sequence[int]) -> None:
        """The sharded map: fold one super-step group — stacked [S, n, C]
        codes / [S, n] tags / [S, n] weights / [S, n, Cn] values, one row
        block per shard (empty shards carry invalid-tag padding) — in ONE
        shard_map dispatch. Each shard aggregates its own block locally
        and folds it into its own f32 window."""
        adds = np.asarray(rows_per_shard, dtype=np.int64)
        if self._acc is not None \
                and self.window_rows + int(adds.sum()) > self._flush_rows:
            self._flush()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from shifu_tpu.analysis import sanitize
        from shifu_tpu.obs import profile
        from shifu_tpu.ops.binagg import sharded_window_fold
        from shifu_tpu.parallel.mesh import row_axes

        self._ensure_window(int(total_slots), int(values.shape[2]))
        axes = row_axes(self.mesh)
        ax = axes if len(axes) > 1 else axes[0]

        def rspec(ndim):
            return NamedSharding(
                self.mesh, P(ax, *([None] * (ndim - 1))))

        # each shard's slice lands on its own devices — the explicit,
        # sanctioned h2d placement ("each host prefetches its own shard")
        codes_d = jax.device_put(codes, rspec(3))
        tags_d = jax.device_put(tags, rspec(2))
        weights_d = jax.device_put(weights, rspec(2))
        values_d = jax.device_put(values, rspec(3))
        offs_d = jax.device_put(col_offsets,
                                NamedSharding(self.mesh, P(None)))
        with sanitize.transfer_free("pipeline.sharded_fold"):
            self._acc = profile.dispatch(
                "pipeline.sharded_fold",
                sharded_window_fold(self.mesh, int(total_slots)),
                self._acc, codes_d, offs_d, tags_d, weights_d, values_d,
                sync=False)
        self._rows += adds

    def fetch(self) -> Optional[List[np.ndarray]]:
        """Final sync: aggregates as float64 numpy arrays in BinAggregates
        field order, or None if no chunk was ever added."""
        self._flush()
        return self._host

    # ---- checkpoint seam (resilience/checkpoint.py) ----
    def snapshot(self) -> dict:
        """Checkpointable state WITHOUT forcing a window flush: the f32
        device windows are pulled as-is (device_get is bit-exact), so a
        resumed fold continues the identical per-shard f32 summation
        order and the result stays bit-identical to an uninterrupted run
        — flushing early here would regroup the f32 sums and break
        parity."""
        out: dict = {"rows": self._rows.copy()}
        if self._host is not None:
            for k, a in enumerate(self._host):
                out[f"host{k}"] = a
        if self._acc is not None:
            import jax

            for k, a in enumerate(jax.device_get(self._acc)):
                out[f"win{k}"] = np.asarray(a)
        return out

    def restore(self, arrays: dict) -> None:
        """Rebuild from `snapshot` arrays (stacked windows re-placed
        sharded over the lifecycle mesh)."""
        host = [arrays[f"host{k}"] for k in range(len(arrays))
                if f"host{k}" in arrays]
        self._host = [np.asarray(a, dtype=np.float64) for a in host] \
            if host else None
        win = [arrays[f"win{k}"] for k in range(len(arrays))
               if f"win{k}" in arrays]
        if win:
            self._acc = self._place_windows(win)
        else:
            self._acc = None
        rows = np.atleast_1d(np.asarray(arrays["rows"], dtype=np.int64))
        assert rows.shape[0] == self.n_shards, (rows.shape, self.n_shards)
        self._rows = rows.copy()

    def _place_windows(self, win: List[np.ndarray]):
        import jax

        from shifu_tpu.ops.binagg import BinAggregates, window_specs
        from jax.sharding import NamedSharding

        sharded, _ = window_specs(self.mesh)
        return BinAggregates(*[
            jax.device_put(np.asarray(a, dtype=np.float32),
                           NamedSharding(self.mesh, s))
            for a, s in zip(win, sharded)])

    # ---- per-shard checkpoint layout (ShardedStreamCheckpoint) ----
    def snapshot_parts(self) -> Tuple[List[dict], dict]:
        """(per_shard, shared): shard s's file gets ITS window slice +
        row count (`local fold state per shard`); the shared reduce file
        gets the post-psum host float64 fold, which no single shard
        owns."""
        snap = self.snapshot()
        per_shard: List[dict] = []
        for s in range(self.n_shards):
            part = {"rows": np.int64(self._rows[s])}
            for k in range(10):
                if f"win{k}" in snap:
                    part[f"win{k}"] = snap[f"win{k}"][s]
            per_shard.append(part)
        shared = {k: v for k, v in snap.items() if k.startswith("host")}
        return per_shard, shared

    def restore_parts(self, per_shard: List[dict], shared: dict) -> None:
        assert len(per_shard) == self.n_shards, \
            (len(per_shard), self.n_shards)
        merged: dict = {
            "rows": np.asarray([int(p["rows"]) for p in per_shard],
                               dtype=np.int64)}
        if any("win0" in p for p in per_shard):
            for k in range(10):
                if f"win{k}" not in per_shard[0]:
                    continue
                merged[f"win{k}"] = np.stack(
                    [p[f"win{k}"] for p in per_shard])
        merged.update(shared)
        self.restore(merged)
