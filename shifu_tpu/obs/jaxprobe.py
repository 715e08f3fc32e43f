"""JAX runtime probes: trace / lower / compile events and the persistent
cache's hits and misses, via jax.monitoring listeners.

XLA recompiles are the silent tax of a shape-unstable pipeline (PR 1's
bucketed padding exists to bound them); these probes make every trace,
lowering and backend compile a registry counter AND a finished span in the
tracer's ring, so run manifests and bench output can say "this step compiled
N programs for M seconds" instead of guessing from wall-clock, and can say
WHICH: each span (of a millisecond or more, `RING_FLOOR_SECONDS`) keeps the
`fun` name jax sends with the event, and its `parent` is the span path open
on the recording thread when the event arrived (jax compiles on the calling
thread), e.g. `train.trees.call/train.tree`. A backend compile that the
persistent cache served is a `jax.compile` span all the same (its seconds
are the fetch) and counts under `jax.cache.hits`.

The listeners resolve the CURRENT global registry and tracer at event time,
so the per-step reset in BasicProcessor.run() scopes compile counts to the
step that caused them. Installed by BasicProcessor.run, the sanitizers, and
the first `obs.profile.dispatch` of a process (so a caller that goes straight
to `train_nn` / `train_trees` is covered). Device-transfer counters have no
monitoring event in jax; the explicit placement seams count themselves
(parallel/mesh.py h2d, data/pipeline.py DeviceAccumulator d2h).
"""

from __future__ import annotations

import time

from shifu_tpu.analysis.racetrack import tracked_lock

_installed = False
_lock = tracked_lock("obs.jaxprobe")

# event name -> (counter to inc, timer to accumulate and span to record,
# duration histogram); jaxpr_trace fires per cache-missing trace,
# jaxpr_to_mlir_module is the lowering of that jaxpr, backend_compile is
# the XLA compile or its fetch from the persistent cache. The histogram
# keeps PER-EVENT durations (not just the aggregate the timer holds), so a
# manifest can show whether a step's compile seconds were one monster
# program or a recompile storm of small ones — and the sanitizer's
# recompile-watchdog breach can quote the wall-clock the recompiles
# actually cost.
_DURATION_EVENTS = {
    "/jax/core/compile/backend_compile_duration":
        ("jax.compiles", "jax.compile", "jax.compile.duration_seconds"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax.lowers", "jax.lower", "jax.lower.duration_seconds"),
    "/jax/core/compile/jaxpr_trace_duration":
        ("jax.traces", "jax.trace", "jax.trace.duration_seconds"),
}
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax.cache.hits",
    "/jax/compilation_cache/cache_misses": "jax.cache.misses",
}

# An event shorter than this is counted (counter, timer, histogram) but not
# kept in the ring. jax fires a trace event for every jitted `jnp` function
# traced inside a program: 11,129 for one whole-tree program, of which 79
# last a millisecond or more and hold 97 % of the seconds (described-chip
# lowering, PR 26). Kept, six such programs would fill the ring's 65,536
# and push the step's own spans out.
RING_FLOOR_SECONDS = 1e-3

# exponential edges, 1 ms .. ~65 s: one XLA compile spans that whole
# range depending on program size, so linear edges resolve nothing
DURATION_BUCKETS = tuple(0.001 * 2 ** k for k in range(17)) + (float("inf"),)


def _on_duration(name: str, duration: float, fun_name: str = "",
                 **_other) -> None:
    from shifu_tpu.obs import registry, tracer

    if name == CACHE_RETRIEVAL_EVENT:
        registry().timer("jax.cache.retrieval").add(duration)
        return
    hit = _DURATION_EVENTS.get(name)
    if hit is None:
        return
    reg = registry()
    reg.counter(hit[0]).inc()
    reg.timer(hit[1]).add(duration)
    reg.histogram(hit[2], buckets=DURATION_BUCKETS).observe(duration)
    if duration < RING_FLOOR_SECONDS:
        return
    tr = tracer()
    end = time.perf_counter()
    tr.record(hit[1], end - duration, end, tr.current_path(),
              {"fun": fun_name})


def _on_event(name: str, **_other) -> None:
    counter = _CACHE_EVENTS.get(name)
    if counter is not None:
        from shifu_tpu.obs import registry

        registry().counter(counter).inc()


def install() -> bool:
    """Idempotently register the monitoring listeners. Returns True once
    the probes are active."""
    global _installed
    if _installed:  # the dispatch seam asks at every call
        return True
    with _lock:
        if _installed:
            return True
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True
        return True
