"""Central catalog of every ``-Dshifu.*`` operational knob.

The reference carried its operational surface in one ``shifuconfig``
file; this repo grew ~50 ``-D`` properties across nine PRs, each read
at its use site through ``utils/environment`` getters — and nothing
guaranteed a knob written in a runbook still existed, was spelled
right, or was read with the type its default implies. This registry is
the single source of truth:

  * ``shifu check`` rule **SH105** (rules/hygiene.py) statically
    verifies every ``environment.get_*("shifu....")`` call site against
    it — undeclared keys, getter/type mismatches, and declared knobs
    nothing reads are all findings, so the catalog can never drift from
    the code.
  * ``shifu check --knobs`` renders it as ``docs/KNOBS.md``; the
    committed file is checked for staleness in the tier-1 suite (and
    therefore in CI).

Dynamic keys (per-seam retry overrides, profile-diff gates) are
declared as glob patterns — the literal ``*`` stands for exactly the
dynamic fragment the reading f-string interpolates, and SH105 requires
the read site's literalized pattern to match a declared glob verbatim.

Types are semantic: ``get_property`` may read any knob (string read +
manual parse is the idiom for floats that distinguish "unset" from
"0"), but a typed getter must match the declared type exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str       # literal key, or a glob with `*` for dynamic parts
    type: str       # "int" | "float" | "bool" | "str"
    default: str    # rendered default (docs; "" = unset/off)
    doc: str        # one line


_K = Knob

KNOBS: List[Knob] = [
    # ---- ingest / streaming pipeline (PR 1, PR 8) ----
    _K("shifu.ingest.chunkRows", "int", "65536",
       "rows per streamed chunk (data/stream.py)"),
    _K("shifu.ingest.memoryBudgetMB", "int", "512",
       "datasets above this stream chunked instead of loading in-RAM"),
    _K("shifu.ingest.forceStreaming", "str", "",
       "\"true\"/\"1\" forces the streaming ingest path regardless of size"),
    _K("shifu.ingest.prefetchChunks", "int", "2",
       "background prefetch queue depth (0 = serial inline loop)"),
    _K("shifu.lifecycle.shards", "int", "0 (= all devices)",
       "row shards the lifecycle folds divide chunks over (ShardPlan)"),
    # ---- pod-scale data plane (PR 18) ----
    _K("shifu.lifecycle.hosts", "int", "1",
       "processes the chunk list partitions over (HostPlan): each host "
       "streams only its own slice; artifacts stay byte-identical"),
    _K("shifu.lifecycle.hostIndex", "int", "-1 (= jax.process_index())",
       "this process's slot in the HostPlan partition (0..hosts-1)"),
    _K("shifu.lifecycle.hostWaitMs", "float", "600000",
       "host merge barrier timeout (parallel/hostsync.py): how long a "
       "host waits for peers' parts before failing loudly"),
    _K("shifu.reduce.topology", "str", "auto",
       "window_reduce collective shape: auto (hierarchical when the "
       "mesh has a dcn axis) | hierarchical | flat (joint psum)"),
    _K("shifu.loop.trafficScope", "str", "fleet",
       "traffic-log reader scope: fleet (union every serve writer) or "
       "one writer id (that process's chunks only)"),
    # ---- train ----
    _K("shifu.train.forceStreaming", "str", "",
       "\"true\"/\"1\" forces shard-streamed training"),
    _K("shifu.train.memoryBudgetMB", "int", "1024",
       "normalized matrix budget before training streams from shards"),
    _K("shifu.gridsearch.threshold", "int", "30",
       "max grid points trained in-process before bagging kicks in"),
    _K("shifu.rebin.maxNumBin", "int", "stats.maxNumBin",
       "rebin target bin count (defaults to the ModelConfig value)"),
    # ---- kernels (PR 11: fused Pallas histogram→split-scan) ----
    _K("shifu.pallas.mode", "str", "auto",
       "fused tree histogram kernel: auto (TPU on / CPU off) | on "
       "(forced; interpret mode off-TPU) | off (XLA lowering)"),
    _K("shifu.pallas.blk", "int", "512",
       "pallas histogram kernel rows per grid step (ops/hist_pallas.py); "
       "rounded up to whole 128-row lanes once the rows need a second "
       "step"),
    _K("shifu.pallas.wmax", "int", "1024",
       "pallas histogram kernel max padded one-hot columns per VMEM "
       "chunk (fused-scan chunks clamp to 512)"),
    # ---- observability / profiling (PR 2, PR 6) ----
    _K("shifu.profile", "str", "",
       "\"xla\" = deep-capture into the ledger dir; else explicit trace dir"),
    _K("shifu.profile.mode", "str", "on",
       "program profiler: on | off (off skips the AOT cost accounting)"),
    _K("shifu.profile.peakTflops", "float", "0 (= chip table)",
       "override the roofline peak TFLOP/s (obs/costmodel.py)"),
    _K("shifu.profile.peakGBs", "float", "0 (= chip table)",
       "override the roofline peak HBM GB/s"),
    _K("shifu.profile.diff.*", "float", "flopsPct 10 / bytesPct 25 / "
       "hbmPct 25 / secondsPct 0",
       "`shifu profile --diff` regression gates (pct increase; 0 = off)"),
    # ---- request tracing (PR 13) ----
    _K("shifu.trace.sample", "float", "0.05",
       "request-trace head sampling: fraction of requests whose traces "
       "are retained in the ring (0 = slow-tail capture only)"),
    _K("shifu.trace.slowMs", "float", "100",
       "request-trace tail capture: every request slower than this is "
       "retained regardless of sampling (0 disables)"),
    _K("shifu.trace.maxTraces", "int", "512",
       "retained request-trace ring capacity (overflow drops the "
       "oldest, counted serve.trace.dropped)"),
    _K("shifu.trace.maxEvents", "int", "65536",
       "span-tracer event ring capacity (obs/tracing.py; overflow "
       "drops the oldest span, counted trace.dropped)"),
    # ---- fleet observability plane (PR 17) ----
    _K("shifu.obs.snapshotMs", "float", "0 (= off)",
       "on-disk metrics time-series cadence: every this-many ms the "
       "serve process rewrites a delta-encoded registry snapshot chunk "
       "under .shifu/runs/obs/<leaseId>/ (atomic rotating files) — a "
       "SIGKILLed process still leaves its last windows behind"),
    _K("shifu.obs.chunkWindows", "int", "8",
       "snapshot windows per time-series chunk file; every chunk opens "
       "with a FULL snapshot, so retention can drop whole chunks"),
    _K("shifu.obs.retainChunks", "int", "16",
       "time-series chunk files kept per process (older ones deleted)"),
    _K("shifu.obs.fleet.timeoutMs", "float", "1000",
       "per-peer scrape timeout for the /fleet/metrics collector (live "
       "peers over loopback HTTP, expired peers from their on-disk "
       "time-series)"),
    # ---- sanitizers (PR 4, this PR) ----
    _K("shifu.sanitize", "str", "",
       "comma list of armed sanitizer modes: "
       "transfer,nan,recompile,race,divergence (or `all`)"),
    _K("shifu.sanitize.recompileBudget", "int", "64",
       "compiles per armed stage before a recompile breach is recorded"),
    _K("shifu.sanitize.race.holdMs", "float", "250",
       "race mode: lock-hold ms above which a long-hold event is "
       "recorded (0 disables)"),
    _K("shifu.sanitize.divergence.maxFolds", "int", "512",
       "divergence mode: cap on per-window fold digests kept in the "
       "verdict (folds past the cap still count, digests are dropped)"),
    # ---- resilience (PR 7) ----
    _K("shifu.faults", "str", "",
       "deterministic fault-injection spec (resilience/faults.py grammar)"),
    _K("shifu.resume", "bool", "false",
       "resume a preempted step from its mid-stream checkpoint"),
    _K("shifu.ckpt.stream", "bool", "true",
       "write mid-stream checkpoints during streaming folds"),
    _K("shifu.ckpt.everyChunks", "int", "16",
       "folded chunks between mid-stream checkpoints"),
    _K("shifu.retry.max", "int", "3",
       "retry attempt budget for io/prefetch/device/ckpt seams (1 = none)"),
    _K("shifu.retry.baseMs", "float", "25",
       "first retry backoff (exponential, full jitter)"),
    _K("shifu.retry.capMs", "float", "2000",
       "retry backoff ceiling"),
    _K("shifu.retry.*.max", "int", "shifu.retry.max",
       "per-seam retry budget override (e.g. shifu.retry.io.max)"),
    _K("shifu.retry.*.baseMs", "float", "shifu.retry.baseMs",
       "per-seam backoff base override"),
    _K("shifu.retry.*.capMs", "float", "shifu.retry.capMs",
       "per-seam backoff cap override"),
    # ---- failure domains (PR 14): heartbeat leases ----
    _K("shifu.lease.ttlMs", "float", "5000",
       "serve-process heartbeat lease TTL — a process that misses "
       "renewal this long is expired for its peers (0 disables leases)"),
    _K("shifu.lease.renewMs", "float", "0 (= ttlMs / 3)",
       "lease renewal cadence"),
    _K("shifu.lease.sweepAfterMs", "float", "0 (= 20 x ttlMs)",
       "expired leases older than this are garbage-collected by any "
       "scanner (until then they surface as a degrade reason)"),
    # ---- serve (PR 5, PR 7, PR 12) ----
    _K("shifu.serve.replicas", "int", "0 (= all local devices)",
       "scoring replicas, one per device (replica i -> device i mod "
       "ndev); 1 = the single-replica pre-fleet behavior"),
    _K("shifu.serve.batching", "str", "continuous",
       "micro-batch close policy: continuous (close on capacity or "
       "queue-dry — p99 never pays maxWaitMs) | barrier (wait up to "
       "maxWaitMs after the first request)"),
    _K("shifu.serve.routerPenalty", "float", "4",
       "drain-aware router: expected-wait multiplier for DEGRADED "
       "replicas (de-prioritize, don't eject)"),
    _K("shifu.serve.maxBatchRows", "int", "1024",
       "micro-batcher row cap per coalesced dispatch"),
    _K("shifu.serve.maxWaitMs", "float", "2.0",
       "barrier-mode coalesce deadline after the first request "
       "(continuous mode never waits on a clock)"),
    _K("shifu.serve.queueDepth", "int", "128",
       "admission bound PER REPLICA — requests beyond it spill to "
       "another replica or shed with 429"),
    _K("shifu.serve.maxWorkerRestarts", "int", "5",
       "supervisor restart budget before the replica drains"),
    _K("shifu.serve.deadlineMs", "float", "30000",
       "per-request admission-to-dispatch budget (0 disables)"),
    _K("shifu.serve.wire.maxBodyMB", "float", "64",
       "largest columnar binary request body (serve/wire.py) the "
       "server will decode — a bounds check before any allocation "
       "sized from untrusted header fields; oversize bodies answer "
       "400"),
    # ---- multi-tenant model zoo (PR 15) ----
    _K("shifu.serve.hbmBudgetMB", "float", "0 (= unbounded)",
       "model-zoo HBM budget: total device bytes the ledger admits "
       "tenants against (weights + compiled-program temps per warm "
       "bucket, from memory_analysis); admission past it evicts cold "
       "tenants LRU"),
    _K("shifu.serve.zoo.warmupMs", "float", "5000",
       "cold-tenant Retry-After fallback before any admission has been "
       "observed (after one, the observed warm-up time drives the hint)"),
    _K("shifu.serve.sloMs", "float", "0 (= off)",
       "request-latency SLO threshold in ms: arms serve.slo.good/bad "
       "counters + the burn-rate gauge wired into /healthz reasons"),
    _K("shifu.serve.sloTarget", "float", "0.99",
       "SLO objective (fraction of requests that must meet sloMs); "
       "burn rate = windowed bad fraction / (1 - target)"),
    _K("shifu.serve.slo.*.ms", "float", "shifu.serve.sloMs",
       "per-tenant SLO threshold override (e.g. shifu.serve.slo.fraud"
       ".ms) — each zoo tenant's SloTracker resolves its own budget"),
    _K("shifu.serve.slo.*.target", "float", "shifu.serve.sloTarget",
       "per-tenant SLO objective override (also drives the per-tenant "
       "burn in /fleet/healthz and `shifu top`)"),
    # ---- co-resident trainer (PR 20) ----
    _K("shifu.coresident.stages", "int", "0 (= from the grant)",
       "pipeline stage count K for the co-resident retrainer; 0 sizes "
       "K from the ledger grant's free budget (plan.default_stages)"),
    _K("shifu.coresident.microbatches", "int", "1",
       "GPipe microbatches per shard filling the pipeline (1 = whole "
       "shard at once; accumulation order is pinned sequential)"),
    _K("shifu.coresident.waitMs", "float", "30000",
       "how long an evicted co-resident trainer polls the ledger for "
       "re-admission before giving up with EvictedError"),
    _K("shifu.coresident.throttleMs", "float", "0 (= flat out)",
       "host sleep between epochs — the background tenant yields its "
       "devices to serving traffic for this long each epoch"),
    _K("shifu.coresident.tenant", "str", "retrain",
       "ledger tenant name the trainer registers under (its /admin and "
       "/healthz identity, and the checkpoint family prefix)"),
    _K("shifu.coresident.replicas", "int", "1",
       "data-parallel pipeline replicas; per-stage gradients all-reduce "
       "through parallel/mesh.fleet_reduce when > 1"),
    # ---- failure domains (PR 14): replica circuit breaker ----
    _K("shifu.serve.breaker.failures", "int", "3",
       "consecutive device-dispatch failures that trip a replica's "
       "circuit breaker open (the router then treats it as absent)"),
    _K("shifu.serve.breaker.probeBaseMs", "float", "500",
       "first open->half-open probe backoff window (jittered "
       "exponential, the resilience/retry.py formula)"),
    _K("shifu.serve.breaker.probeCapMs", "float", "30000",
       "probe backoff ceiling"),
    _K("shifu.serve.breaker.probeOks", "int", "2",
       "consecutive successful half-open probes before the breaker "
       "closes"),
    _K("shifu.serve.breaker.failoverMax", "int", "2",
       "times one request may be replayed on another replica after its "
       "batch failed, before it is answered with the error"),
    # ---- continuous loop (PR 9) ----
    _K("shifu.loop.logSample", "float", "0 (= off)",
       "fraction of served rows written to the traffic log"),
    _K("shifu.loop.logChunkRows", "int", "4096",
       "rows per traffic-log chunk file"),
    _K("shifu.loop.psiDegrade", "float", "0.2",
       "per-column PSI that flips /healthz to degraded + recommends "
       "retrain"),
    _K("shifu.loop.driftMinRows", "int", "256",
       "live rows before drift verdicts bind (below: `warming`)"),
    _K("shifu.loop.driftCheckBatches", "int", "32",
       "batches between drift verdict checks (a check flushes the window)"),
    _K("shifu.loop.shadowSample", "float", "0.25",
       "fraction of live batches the staged shadow also scores"),
    _K("shifu.loop.shadowTolerance", "float", "5.0",
       "|mean-score delta| (0..1000) counted as shadow agreement"),
    _K("shifu.loop.promoteAgree", "float", "0.95",
       "min shadow agreement rate to promote"),
    _K("shifu.loop.promoteMinRows", "int", "64",
       "min shadow-scored rows before a promote decision binds"),
    _K("shifu.loop.appendTrees", "int", "10",
       "GBT retrain: trees appended on new chunks"),
    _K("shifu.promote.roundDeadlineMs", "float", "0 (= one lease TTL)",
       "fleet-atomic promotion round ack deadline — raise it when a "
       "candidate's fleet-wide stage+warm outlasts a lease TTL (fence "
       "safety is re-checked at commit regardless)"),
]


def by_name() -> Dict[str, Knob]:
    return {k.name: k for k in KNOBS}


def render_markdown() -> str:
    """docs/KNOBS.md, generated — `shifu check --knobs` emits this and
    the tier-1 suite (and therefore CI) fails when the committed file is
    stale."""
    lines = [
        "# `-Dshifu.*` knob catalog",
        "",
        "Generated by `shifu check --knobs` from "
        "`shifu_tpu/analysis/knobs.py` — do not edit by hand; "
        "regenerate with:",
        "",
        "```",
        "$ python -m shifu_tpu check --knobs > docs/KNOBS.md",
        "```",
        "",
        "Every key is settable three ways (utils/environment.py): "
        "`$SHIFU_TPU_HOME/conf/shifuconfig` / `/etc/shifuconfig`, a "
        "`SHIFU_*` environment variable, or a `-Dkey=value` CLI "
        "override (highest priority). Rule **SH105** keeps this catalog "
        "exact: every `environment.get_*` call site must read a "
        "declared key with the declared type, and every declared key "
        "must have a reader. A literal `*` marks a dynamic key "
        "fragment (per-seam / per-gate overrides).",
        "",
        "| knob | type | default | purpose |",
        "|---|---|---|---|",
    ]
    for k in KNOBS:
        lines.append(
            f"| `{k.name}` | {k.type} | {k.default or '(unset)'} "
            f"| {k.doc} |")
    return "\n".join(lines) + "\n"
