"""JAX runtime probes: compile counts/seconds via jax.monitoring listeners.

XLA recompiles are the silent tax of a shape-unstable pipeline (PR 1's
bucketed padding exists to bound them); these probes make every backend
compile a registry counter so run manifests and bench output can say "this
step compiled N programs for M seconds" instead of guessing from wall-clock.

The listener resolves the CURRENT global registry at event time, so the
per-step registry reset in BasicProcessor.run() scopes compile counts to the
step that caused them. Device-transfer counters have no monitoring event in
jax; the explicit placement seams count themselves (parallel/mesh.py h2d,
data/pipeline.py DeviceAccumulator d2h).
"""

from __future__ import annotations

from shifu_tpu.analysis.racetrack import tracked_lock

_installed = False
_lock = tracked_lock("obs.jaxprobe")

# event name -> (counter to inc, timer to accumulate, duration histogram);
# backend_compile is the actual XLA compile, jaxpr_trace fires per
# cache-missing trace. The histogram keeps PER-EVENT durations (not just
# the aggregate the timer holds), so a manifest can show whether a step's
# compile seconds were one monster program or a recompile storm of small
# ones — and the sanitizer's recompile-watchdog breach can quote the
# wall-clock the recompiles actually cost.
_DURATION_EVENTS = {
    "/jax/core/compile/backend_compile_duration":
        ("jax.compiles", "jax.compile", "jax.compile.duration_seconds"),
    "/jax/core/compile/jaxpr_trace_duration":
        ("jax.traces", "jax.trace", "jax.trace.duration_seconds"),
}

# exponential edges, 1 ms .. ~65 s: one XLA compile spans that whole
# range depending on program size, so linear edges resolve nothing
DURATION_BUCKETS = tuple(0.001 * 2 ** k for k in range(17)) + (float("inf"),)


def install() -> bool:
    """Idempotently register the monitoring listeners. Returns True once
    the probes are active."""
    global _installed
    with _lock:
        if _installed:
            return True
        from jax import monitoring

        def _on_duration(name: str, duration: float, **_kw) -> None:
            hit = _DURATION_EVENTS.get(name)
            if hit is None:
                return
            from shifu_tpu.obs import registry

            reg = registry()
            reg.counter(hit[0]).inc()
            reg.timer(hit[1]).add(duration)
            reg.histogram(hit[2], buckets=DURATION_BUCKETS).observe(duration)

        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
        return True
