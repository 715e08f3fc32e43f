"""Dynamic micro-batching: coalesce concurrent requests into one dispatch.

Single-record dispatches waste the accelerator (a 1-row matmul costs the
same launch overhead as a 1024-row one); unbounded batching wastes the
client's latency budget. The batcher sits between the admission queue and
the fused registry program and closes each batch by one of two policies
(`shifu.serve.batching`):

  continuous (default) — in-flight admission: requests coalesce in the
      admission queue WHILE the previous dispatch is on device, and the
      bucket closes on capacity (`shifu.serve.maxBatchRows`) or the
      instant the queue runs dry — never on a wall clock. An idle
      replica dispatches a lone request immediately instead of parking
      it `maxWaitMs` hoping for company, so p99 under load stops paying
      the coalesce deadline: the previous dispatch's device time IS the
      coalescing window.
  barrier — the pre-fleet policy, kept for deployments that want a
      minimum coalesce window:

      * row cap       shifu.serve.maxBatchRows (default 1024)
      * wait deadline shifu.serve.maxWaitMs    (default 2.0 ms after the
                      batch's FIRST request arrives)

Coalesced rows concatenate into one raw batch, score in one fused
dispatch (the registry pads to the power-of-two row bucket, so compile
count stays bounded whatever sizes traffic produces — continuous
buckets close ragged and pad to the same power-of-two shapes), and the
result is sliced back per request — padding rows belong to the
registry, request boundaries to the batcher, and neither leaks into the
other.

Fleet context (serve/fleet.py): one batcher serves one replica. `labels`
(typically {"replica": "0"}) ride every serve.* metric the batcher
records, and `expected_wait`/`drain_stats` expose the observed drain
rate the DrainAwareRouter places micro-batches by.

One worker thread keeps ordering FIFO and the device queue depth at one
batch; requests resolve through a per-request event (`ScoreRequest.wait`).

Self-healing (resilience layer): the worker runs under a supervisor —
an unexpected crash disposes of the in-flight batch's requests
INDIVIDUALLY through the fleet failover hook when one is wired (each
rider replays on a healthy replica, or gets an explicit error once the
budget is spent; standalone batchers answer with the error directly —
either way never a hang), preserves the admission queue, and restarts
the worker up to `shifu.serve.maxWorkerRestarts` times (health flips to
`degraded` until clean batches accumulate). Every batch outcome is also
reported to the replica's circuit breaker (`serve/health.py`): repeated
dispatch failures quarantine the replica out of the routing set
entirely — the failure domain worker restarts cannot heal. Every admitted request also carries a
deadline (`shifu.serve.deadlineMs`): a request that outlives it is shed
with an explicit error before dispatch instead of wasting a wedged
backend's time. The observed drain rate feeds the 429 Retry-After hint
(`retry_after_seconds`, exported as the `serve.retry_after_seconds`
gauge).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from shifu_tpu.analysis.racetrack import tracked_lock
from shifu_tpu.data.reader import ColumnarData
from shifu_tpu.eval.scorer import ScoreResult
from shifu_tpu.serve.health import HealthMonitor
from shifu_tpu.serve.queue import AdmissionQueue
from shifu_tpu.utils import environment
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)

DEFAULT_MAX_BATCH_ROWS = 1024
DEFAULT_MAX_WAIT_MS = 2.0
DEFAULT_MAX_WORKER_RESTARTS = 5
DEFAULT_DEADLINE_MS = 30_000.0
BATCHING_CONTINUOUS = "continuous"
BATCHING_BARRIER = "barrier"
# Retry-After clamp: never tell a client "come back immediately" while
# shedding, never park it longer than half a minute on a stale estimate
RETRY_AFTER_MIN_S = 1.0
RETRY_AFTER_MAX_S = 30.0
DRAIN_WINDOW_S = 10.0

# Exponential histogram edges, pinned (tests/test_serve.py). The metrics
# registry's DEFAULT_BUCKETS start at 5 ms — useless for a path whose p99
# is single-digit milliseconds: every observation landed in the first two
# buckets and the exported quantiles collapsed. Doubling edges from 100 µs
# give ~equal relative resolution from sub-ms latencies to multi-second
# stalls.
LATENCY_BUCKETS = tuple(0.0001 * 2 ** k for k in range(16)) + (float("inf"),)
# batch sizes are power-of-two-ish by construction (row buckets), so the
# edges are exact powers of two up to the 8192 cap ambit
BATCH_ROWS_BUCKETS = tuple(float(2 ** k) for k in range(14)) + (float("inf"),)


def max_batch_rows_setting() -> int:
    return environment.get_int("shifu.serve.maxBatchRows",
                               DEFAULT_MAX_BATCH_ROWS)


def max_wait_ms_setting() -> float:
    raw = environment.get_property("shifu.serve.maxWaitMs", "")
    try:
        return float(raw) if raw else DEFAULT_MAX_WAIT_MS
    except ValueError:
        return DEFAULT_MAX_WAIT_MS


def max_worker_restarts_setting() -> int:
    return environment.get_int("shifu.serve.maxWorkerRestarts",
                               DEFAULT_MAX_WORKER_RESTARTS)


def batching_setting() -> str:
    """shifu.serve.batching — continuous (close buckets on capacity or
    queue-dry, never a wall clock) | barrier (the maxWaitMs coalesce
    deadline). Unknown values fall back to continuous."""
    raw = environment.get_property("shifu.serve.batching", "").strip()
    return (BATCHING_BARRIER if raw.lower() == BATCHING_BARRIER
            else BATCHING_CONTINUOUS)


def deadline_ms_setting() -> float:
    """shifu.serve.deadlineMs — per-request budget from admission to
    dispatch (0 disables). A request older than this is shed with an
    explicit error instead of being scored for a client that gave up."""
    raw = environment.get_property("shifu.serve.deadlineMs", "")
    try:
        return float(raw) if raw else DEFAULT_DEADLINE_MS
    except ValueError:
        return DEFAULT_DEADLINE_MS


class DeadlineExceededError(TimeoutError):
    """The request outlived shifu.serve.deadlineMs before dispatch."""


class ScoreRequest:
    """One admitted request: a raw columnar slice plus its completion.

    `trace` (obs/reqtrace.RequestTrace, optional) rides along so the
    batcher can stamp the queue-wait / coalesce-wait stages and fan the
    batch-level featurize/device/d2h durations out per request."""

    __slots__ = ("data", "n_rows", "enqueued_at", "popped_at", "deadline",
                 "_done", "result", "error", "trace", "failovers",
                 "wire_format")

    def __init__(self, data: ColumnarData,
                 deadline_s: Optional[float] = None,
                 trace=None) -> None:
        self.data = data
        self.n_rows = data.n_rows
        # which wire format carried this request (serve/wire.py stamps
        # "binary" on decoded batches; everything else is "json") — the
        # format= label on serve.requests / serve.latency_seconds
        self.wire_format = getattr(data, "wire_format", "json")
        self.enqueued_at = time.perf_counter()
        self.popped_at = self.enqueued_at
        self.deadline = (self.enqueued_at + deadline_s
                         if deadline_s else None)
        self._done = threading.Event()
        self.result: Optional[ScoreResult] = None
        self.error: Optional[BaseException] = None
        self.trace = trace
        # times this request was replayed on another replica after its
        # batch failed (fleet failover; bounded by the failover budget).
        # Scoring is pure, so a replay can never double-answer — resolve
        # and fail go through the same one-shot event either way.
        self.failovers = 0

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now or time.perf_counter()) > self.deadline)

    def resolve(self, result: ScoreResult) -> None:
        self.result = result
        self._done.set()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> ScoreResult:
        if not self._done.wait(timeout):
            raise TimeoutError("score request did not complete in time")
        if self.error is not None:
            raise self.error
        return self.result


def _concat_batches(datas: Sequence[ColumnarData]) -> ColumnarData:
    if len(datas) == 1:
        return datas[0]
    names = datas[0].names
    raw = {}
    for name in names:
        typed = [d.typed_column(name) for d in datas]
        if (typed[0] is not None
                and all(t is not None and t.dtype == typed[0].dtype
                        for t in typed)):
            # every rider delivered this column typed (binary wire or
            # typed JSON) with one dtype: the coalesced batch stays
            # typed and the featurizer never parses a string for it.
            # Mixed dtypes (an i64 rider next to an f64 one) fall to
            # strings below — promoting i64 would print "3" as "3.0"
            # and shift its categorical identity.
            raw[name] = np.concatenate(typed)
        else:
            raw[name] = np.concatenate([
                np.asarray(d.column(name), dtype=object) for d in datas])
    return ColumnarData(names=list(names), raw=raw,
                        n_rows=sum(d.n_rows for d in datas),
                        missing_values=datas[0].missing_values)


def _note_popped(req: ScoreRequest) -> None:
    """Stamp the queue-wait stage the moment a request leaves the
    admission queue (enqueue -> worker pop)."""
    now = time.perf_counter()
    req.popped_at = now
    if req.trace is not None:
        req.trace.add_stage("queue", now - req.enqueued_at,
                            t0=req.enqueued_at)


def _slice_result(res: ScoreResult, start: int, stop: int) -> ScoreResult:
    return ScoreResult(
        model_scores=res.model_scores[start:stop],
        mean=res.mean[start:stop],
        max=res.max[start:stop],
        min=res.min[start:stop],
        median=res.median[start:stop],
        model_names=res.model_names,
        model_widths=res.model_widths,
    )


class MicroBatcher:
    """Admission-queue consumer: coalesce -> score -> fan results out,
    supervised — a crashed scoring worker restarts (bounded) with the
    queue preserved and the in-flight batch failed request-by-request."""

    def __init__(self, score_fn: Callable[[ColumnarData], ScoreResult],
                 admission: AdmissionQueue,
                 max_batch_rows: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 health: Optional[HealthMonitor] = None,
                 max_restarts: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 observer: Optional[Callable[[ColumnarData, ScoreResult],
                                             None]] = None,
                 batching: Optional[str] = None,
                 labels: Optional[dict] = None,
                 breaker=None) -> None:
        self.score_fn = score_fn
        self.admission = admission
        # device-dispatch circuit breaker (serve/health.CircuitBreaker),
        # owned by the replica: every batch outcome is reported so
        # repeated dispatch failures quarantine the replica
        self.breaker = breaker
        # fleet failover hook, assigned by ReplicaFleet after
        # construction: called with (request, error) when a batch fails —
        # replays the request on a healthy replica or fails it under the
        # bounded per-request budget. None = fail directly (standalone
        # batchers outside a fleet).
        self.failover: Optional[Callable[[ScoreRequest, BaseException],
                                         None]] = None
        # metric identity: the fleet passes {"replica": "<i>"} so every
        # serve.* sample this batcher records is attributable to its
        # replica on one shared /metrics page
        self.labels = dict(labels or {})
        try:
            self._replica_index: Optional[int] = int(
                self.labels["replica"])
        except (KeyError, ValueError):
            # no replica identity: per-replica fault targeting
            # (`seam@replica=N`) can't match this batcher's events
            self._replica_index = None
        self.batching = batching_setting() if batching is None else (
            BATCHING_BARRIER if str(batching).lower() == BATCHING_BARRIER
            else BATCHING_CONTINUOUS)
        # post-resolution hook: runs AFTER every request in the batch has
        # its answer, so traffic logging / shadow scoring / drift checks
        # (the continuous-loop seams) never add to client latency. An
        # observer crash is contained — it fails no request.
        self.observer = observer
        self.health = health if health is not None else HealthMonitor()
        self.max_batch_rows = (max_batch_rows_setting()
                               if max_batch_rows is None
                               else int(max_batch_rows))
        self.max_wait_s = (max_wait_ms_setting()
                           if max_wait_ms is None
                           else float(max_wait_ms)) / 1000.0
        self.max_restarts = (max_worker_restarts_setting()
                             if max_restarts is None else int(max_restarts))
        self.deadline_s = ((deadline_ms_setting()
                            if deadline_ms is None else float(deadline_ms))
                           / 1000.0)
        self.restarts = 0
        self._inflight: Optional[List[ScoreRequest]] = None
        self._drained = threading.Event()  # set on clean drain OR give-up
        # (t_done, n_requests) per completed batch; the lock covers the
        # worker's append racing retry_after_seconds() on handler threads
        self._drain_log: deque = deque(maxlen=64)
        self._drain_lock = tracked_lock("serve.batcher.drain_log")
        self._worker = self._spawn()

    def _spawn(self) -> threading.Thread:
        worker = threading.Thread(target=self._run,
                                  name="shifu-serve-batcher",
                                  daemon=True)
        worker.start()
        return worker

    def submit(self, data: ColumnarData, trace=None) -> ScoreRequest:
        """Admit one request (raises queue.RejectedError on shed)."""
        req = ScoreRequest(data, deadline_s=self.deadline_s or None,
                           trace=trace)
        self.admission.put(req)
        return req

    def _dispose(self, req: ScoreRequest, error: BaseException) -> None:
        """A request whose batch failed: hand it to the fleet failover
        (replay on a healthy replica, budget-bounded) or answer it with
        the error — never leave it unanswered."""
        fo = self.failover
        if fo is None:
            req.fail(error)
            return
        try:
            fo(req, error)
        except Exception as fe:  # failover trouble must still answer
            log.warning("failover handler failed: %s", fe)
            req.fail(error)

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for drain: meaningful only after admission.close().
        Event-based, not thread-based — the worker thread may have been
        replaced by the supervisor since this batcher was built."""
        self._drained.wait(timeout)

    @property
    def draining(self) -> bool:
        return self.admission.closed and not self._drained.is_set()

    # ---- supervisor ----
    def _run(self) -> None:
        from shifu_tpu.obs import registry

        try:
            self._loop()
            self._drained.set()  # clean drain (queue closed and empty)
            return
        except BaseException as e:  # supervisor: ANY worker death (incl.
            # injected faults and non-Exception crashes) must be survived
            reg = registry()
            reg.counter("serve.worker.crashes", **self.labels).inc()
            log.warning("serve scoring worker crashed: %s: %s",
                        type(e).__name__, e)
            # the batch being scored when the worker died: every request
            # gets an individual answer — failed over to a healthy
            # replica when a fleet is around it, an error response when
            # not; crashed != hung either way
            inflight, self._inflight = self._inflight, None
            err = RuntimeError(f"scoring worker crashed mid-batch: {e}")
            for r in inflight or []:
                self._dispose(r, err)
            if self.breaker is not None and inflight:
                # a crash WITH a batch in flight is a dispatch failure:
                # the device (or the program around it) ate the batch
                self.breaker.note_failure(
                    f"worker crash: {type(e).__name__}")
            self.health.note_crash(
                f"scoring worker crashed: {type(e).__name__}")
            if self.restarts >= self.max_restarts:
                log.error("serve worker restart budget (%d) exhausted; "
                          "draining", self.max_restarts)
                self.health.set_draining("worker restart budget exhausted")
                self.admission.close()
                # answer everything still queued — zero requests may be
                # left admitted-but-unanswered (in a fleet the backlog
                # fails over to the surviving replicas)
                drain_err = RuntimeError(
                    "scoring worker unavailable (restart budget "
                    "exhausted)")
                while True:
                    req = self.admission.get(timeout=0)
                    if req is None:
                        break
                    self._dispose(req, drain_err)
                self._drained.set()
                return
            self.restarts += 1
            reg.counter("serve.worker.restarts", **self.labels).inc()
            log.info("restarting serve scoring worker (%d/%d)",
                     self.restarts, self.max_restarts)
            self._worker = self._spawn()

    def _gather(self) -> Optional[List[ScoreRequest]]:
        """Block for the next request, then coalesce into the bucket.
        None = queue closed and fully drained.

        Continuous mode: everything already queued joins (up to the row
        cap) and the bucket closes the instant the queue runs dry — the
        coalescing window was the previous dispatch's device time, and
        a lone request on an idle replica dispatches immediately.
        Barrier mode: the bucket additionally holds up to `maxWaitMs`
        after the FIRST request, the pre-fleet policy."""
        first = self.admission.get()
        if first is None:
            return None
        _note_popped(first)
        batch = [first]
        # register with the supervisor IMMEDIATELY (same list object, so
        # later appends are visible): a request popped from the queue is
        # answerable only through _inflight if this worker dies while
        # still coalescing
        self._inflight = batch
        rows = first.n_rows
        if self.batching == BATCHING_CONTINUOUS:
            while rows < self.max_batch_rows:
                nxt = self.admission.get(timeout=0)
                if nxt is None:
                    break  # capacity not hit but nothing is waiting NOW
                _note_popped(nxt)
                batch.append(nxt)
                rows += nxt.n_rows
            return batch
        deadline = time.perf_counter() + self.max_wait_s
        while rows < self.max_batch_rows:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                break
            nxt = self.admission.get(timeout=remaining)
            if nxt is None:
                break
            _note_popped(nxt)
            batch.append(nxt)
            rows += nxt.n_rows
        return batch

    def _loop(self) -> None:
        from shifu_tpu.obs import registry, reqtrace
        from shifu_tpu.resilience import faults

        while True:
            batch = self._gather()
            if batch is None:
                return
            reg = registry()
            # deadline shed BEFORE dispatch: a request that outlived its
            # budget behind a wedged batch gets an explicit error now,
            # not a result its client stopped waiting for
            now = time.perf_counter()
            live: List[ScoreRequest] = []
            for r in batch:
                if r.expired(now):
                    reg.counter("serve.deadline.shed", **self.labels).inc()
                    r.fail(DeadlineExceededError(
                        "request exceeded shifu.serve.deadlineMs before "
                        "dispatch"))
                else:
                    live.append(r)
            batch = live
            if not batch:
                self._inflight = None
                continue
            # _inflight (registered in _gather) stays set until every
            # request in the batch has an answer: if anything below
            # escapes — e.g. the injected `serve` fault on the next line,
            # or any real crash outside the per-batch guard — the
            # supervisor (_run) reads it and fails each request
            # individually; a finally-clear would hide the batch from the
            # crash path. Re-point it at the post-shed batch (the live
            # set is the honest one; double-failing an already-shed
            # request is harmless).
            self._inflight = batch
            faults.fault_point("serve")
            rows = sum(r.n_rows for r in batch)
            # coalesce-wait closes here: pop -> dispatch is the time a
            # request spent waiting for its bucket to fill/close — the
            # convoy term the continuous-batching policy exists to bound
            dispatch_t = time.perf_counter()
            dispatch_unix = time.time()
            traced = [r for r in batch if r.trace is not None]
            replica = self.labels.get("replica", "0")
            for r in traced:
                r.trace.add_stage("coalesce", dispatch_t - r.popped_at,
                                  t0=r.popped_at)
                r.trace.annotate(replica=replica, batchRequests=len(batch),
                                 batchRows=rows)
            reg.counter("serve.batches", **self.labels).inc()
            reg.histogram(
                "serve.batch.rows", buckets=BATCH_ROWS_BUCKETS,
                **self.labels,
            ).observe(rows)
            try:
                # the registry notes featurize/device/d2h into the
                # thread-local capture; they fan out to every request
                # that rode the bucket (a batch-level stage IS each
                # rider's wait)
                with reqtrace.capture_stages(enabled=bool(traced)) as cap:
                    with reg.timer("serve.batch.score",
                                   **self.labels).time():
                        # the device_dead chaos seam: a persistent
                        # per-replica dispatch failure fires HERE, inside
                        # the per-batch guard — a failed batch, not a
                        # crashed worker (that is the `serve` seam above)
                        faults.fault_point("serve.dispatch",
                                           replica=self._replica_index)
                        concat = _concat_batches([r.data for r in batch])
                        result = self.score_fn(concat)
            except Exception as e:  # fan the failure out per request:
                # failover replays each rider on a healthy replica (or
                # answers it with the error), and the breaker counts the
                # dispatch failure toward quarantining this replica
                log.warning("serve batch of %d requests failed: %s",
                            len(batch), e)
                reg.counter("serve.batch.errors", **self.labels).inc()
                if self.breaker is not None:
                    self.breaker.note_failure(f"{type(e).__name__}: {e}")
                for r in batch:
                    self._dispose(r, e)
                self._inflight = None
                continue
            if cap:
                for stage, dur, t0 in cap.stages:
                    for r in traced:
                        r.trace.add_stage(stage, dur, t0)
                if cap.attrs:
                    # batch-level attributes (the scoring version's sha,
                    # from the SwappableRegistry swap point) annotate
                    # every rider — per-request version lineage that
                    # stays correct across a mid-roll promote
                    for r in traced:
                        r.trace.annotate(**cap.attrs)
            off = 0
            now = time.perf_counter()
            # per-request latency and count carry the wire-format label —
            # a coalesced batch can mix JSON and binary riders, so the
            # split happens here, per rider, not per batch
            lat_by_fmt: dict = {}
            n_by_fmt: dict = {}
            for r in batch:
                r.resolve(_slice_result(result, off, off + r.n_rows))
                off += r.n_rows
                fmt = r.wire_format
                lat = lat_by_fmt.get(fmt)
                if lat is None:
                    lat = reg.histogram("serve.latency_seconds",
                                        buckets=LATENCY_BUCKETS,
                                        format=fmt, **self.labels)
                    lat_by_fmt[fmt] = lat
                lat.observe(now - r.enqueued_at)
                n_by_fmt[fmt] = n_by_fmt.get(fmt, 0) + 1
            for fmt, cnt in n_by_fmt.items():
                reg.counter("serve.requests", format=fmt,
                            **self.labels).inc(cnt)
            reg.counter("serve.records", **self.labels).inc(rows)
            self._inflight = None
            with self._drain_lock:
                self._drain_log.append((now, len(batch)))
            self.health.note_ok()
            if self.breaker is not None:
                self.breaker.note_ok()
            if traced:
                # the convoy witness: which traces shared this bucket
                reqtrace.buffer().note_batch(
                    replica, [r.trace.trace_id for r in traced],
                    requests=len(batch), rows=rows,
                    started_unix=dispatch_unix,
                    dur_s=now - dispatch_t)
            if self.observer is not None:
                # every client already has its answer; the loop seams
                # (traffic log, shadow scoring, drift verdicts) run here
                # so they cost queue headroom, never request latency
                if traced:
                    # per-row trace ids ride the batch into the traffic
                    # log (serve -> retrain lineage); rows of un-traced
                    # requests log the empty token
                    concat.trace_ids = np.concatenate([
                        np.full(r.n_rows,
                                r.trace.trace_id if r.trace else "",
                                dtype=object)
                        for r in batch])
                try:
                    self.observer(concat, result)
                except Exception as oe:  # observers must not kill serving
                    log.warning("serve observer failed: %s", oe)
                    reg.counter("serve.observer.errors",
                                **self.labels).inc()

    # ---- load hints ----
    def drain_stats(self, now: Optional[float] = None
                    ) -> Tuple[int, Optional[float]]:
        """(queued requests, observed drain rate in requests/s over the
        last DRAIN_WINDOW_S, or None with no usable history) — the
        per-replica signal the DrainAwareRouter and the fleet Retry-After
        estimator both read. Rates count REQUESTS, not batches: queue
        depth counts requests, so a batches/s rate would overestimate
        the backlog by the coalescing factor."""
        if now is None:
            now = time.perf_counter()
        with self._drain_lock:
            drained = list(self._drain_log)
        recent = [(t, n) for t, n in drained if now - t <= DRAIN_WINDOW_S]
        # backlog = queued + the bucket currently on device: the router
        # must see a replica whose whole queue just moved into one
        # in-flight bucket as busy, not idle (bare read — _inflight is a
        # single reference the worker swaps, and an off-by-a-batch
        # estimate only shades the ranking)
        inflight = self._inflight
        depth = len(self.admission) + (len(inflight) if inflight else 0)
        if len(recent) >= 2:
            span = max(now - recent[0][0], 1e-3)
            return depth, sum(n for _, n in recent) / span
        return depth, None

    def expected_wait(self, now: Optional[float] = None) -> float:
        """Estimated seconds before a newly admitted request dispatches:
        backlog ÷ observed drain rate. With no drain history yet the raw
        backlog ranks the replica (0.0 for an idle one), which is all
        the router's RELATIVE placement needs."""
        depth, rate = self.drain_stats(now)
        if not depth:
            return 0.0
        if rate is None:
            return float(depth)
        return depth / max(rate, 1e-3)

    def retry_after_seconds(self) -> float:
        """429 Retry-After derived from the OBSERVED drain rate: queue
        depth ÷ recently drained requests/s, clamped — a loaded server
        tells clients how long the backlog actually is instead of a
        fixed hint. Exported as the `serve.retry_after_seconds` gauge.
        (The fleet-wide analog lives on ReplicaFleet: total backlog over
        the SUMMED per-replica drain rates.)"""
        from shifu_tpu.obs import registry

        depth, rate = self.drain_stats()
        if rate is not None:
            hint = depth / max(rate, 1e-3)
        else:
            hint = RETRY_AFTER_MIN_S  # no drain history: cheap optimism
        hint = min(max(hint, RETRY_AFTER_MIN_S), RETRY_AFTER_MAX_S)
        registry().gauge("serve.retry_after_seconds",
                         **self.labels).set(hint)
        return hint
