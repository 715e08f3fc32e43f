"""Counts jax's own trace / lower / compile events (jax.monitoring), so that
set-up can be split and a compile inside the window is seen."""

from __future__ import annotations

_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_fetch_s",
}


class CompileLog:
    """Seconds and counts by kind since the last `take()`. jax keeps every
    listener for the life of the process, so there is one log a process."""

    _the_one = None

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._the_one is None:
            cls._the_one = cls()
        cls._the_one.take()
        return cls._the_one

    def __init__(self):
        from jax import monitoring

        self._acc = {}
        self.take()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        key = _EVENTS.get(event)
        if key is not None:
            self._acc[key] += seconds
            if key == "compile_or_fetch_s":
                self._acc["compiles"] += 1

    def take(self) -> dict:
        out, self._acc = self._acc, {
            "trace_s": 0.0, "lower_s": 0.0, "compile_or_fetch_s": 0.0,
            "compiles": 0}
        return out
