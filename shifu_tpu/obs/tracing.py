"""Span tracing: nested wall-clock spans serialized as a Chrome trace.

`with tracer.span("stats.pass2", rows=n):` records start/end/duration and
attributes; the collected events serialize to the Chrome-trace JSON format
(`chrome://tracing` / Perfetto "traceEvents" with ph="X" complete events),
one file per lifecycle step next to the run manifest (obs/ledger.py).

Every span also opens `jax.profiler.TraceAnnotation("shifu." + name)` for
its duration, with the span's scalar attributes as the event's stats. With a
`jax.profiler` session running (`-Dshifu.profile=xla`, a benchmark's traced
run) the span therefore lands on the host plane of the xplane file, on the
device trace's own clock, beside the device's operations; with none an
annotation is an atomic load. A process that never imported jax has no
session to write into, so host-only steps import nothing for this.

The ring's events stay on `time.perf_counter()`: `Tracer.t0` is the anchor
their `ts` counts from, and `Tracer.between(lo, hi)` picks the events that
end between two `perf_counter` stamps (a measured window, without its
warm-up).

Thread-safe: the streaming pipeline's prefetch worker opens spans on its own
thread; events carry the recording thread id so overlap between the parse
thread and the device thread is visible as parallel tracks.

Bounded: the event store is a ring of `-Dshifu.trace.maxEvents` entries
(knob read at construction — obs.reset()/step boundaries re-read it). A
long-running `shifu serve` used to grow `_events` forever; now overflow
drops the OLDEST span and counts `trace.dropped`, so the newest spans —
the ones a shutdown manifest wants — survive at bounded memory.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque

from shifu_tpu.analysis.racetrack import tracked_lock
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, Iterator, List, Optional

from shifu_tpu.utils import environment

DEFAULT_MAX_EVENTS = 65536


PROFILER_PREFIX = "shifu."  # a span's name in the profiler's trace

# os.getpid() is a system call, and a v5e host read 5.7 us for it against
# 0.17 us in the sandbox (my chip run, PR 26): asked once a process, not
# once an event (jax fires 11,000 trace events for one whole-tree program)
_PID = os.getpid()


def _pid_after_fork() -> None:
    global _PID
    _PID = os.getpid()


os.register_at_fork(after_in_child=_pid_after_fork)


def profiler_annotation(name: str, **stats: Any):
    """`jax.profiler.TraceAnnotation(name, **stats)`, or a context that does
    nothing in a process that has not imported jax (no profiler session can
    be running there)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None while jax still imports
    if profiler is None:
        return nullcontext()
    return profiler.TraceAnnotation(name, **stats)


def max_events_setting() -> int:
    """shifu.trace.maxEvents — span-event ring capacity (per Tracer)."""
    return environment.get_int("shifu.trace.maxEvents", DEFAULT_MAX_EVENTS)


class Tracer:
    def __init__(self, max_events: Optional[int] = None) -> None:
        self._lock = tracked_lock("obs.tracing")
        self.max_events = max(1, (max_events_setting()
                                  if max_events is None else int(max_events)))
        self._events: deque = deque(maxlen=self.max_events)
        self._dropped = 0
        self._local = threading.local()
        # the perf_counter stamp every event's `ts` counts from
        self.t0 = time.perf_counter()

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    def current_path(self) -> str:
        """Dotted path of the innermost open span on this thread ("" if none)."""
        return "/".join(self._stack())

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
        """Record a nested span; yields the mutable attrs dict so callers can
        attach results discovered mid-span (row counts, output paths)."""
        stack = self._stack()
        parent = "/".join(stack)
        stack.append(name)
        args = dict(attrs)
        t0 = time.perf_counter()
        try:
            with profiler_annotation(
                    PROFILER_PREFIX + name,
                    **{k: v for k, v in args.items()
                       if isinstance(v, (str, int, float))}):
                yield args
        finally:
            stack.pop()
            self.record(name, t0, time.perf_counter(), parent, args)

    def record(self, name: str, start: float, end: float, parent: str = "",
               args: Optional[Dict[str, Any]] = None) -> None:
        """Append one finished span, `start` and `end` being `perf_counter`
        stamps: what `span` does at its exit, and how an event that arrives
        with its duration already taken (obs/jaxprobe.py) joins the ring."""
        event = {
            "name": name,
            "ph": "X",
            "ts": (start - self.t0) * 1e6,  # Chrome trace wants microseconds
            "dur": (end - start) * 1e6,
            "pid": _PID,
            "tid": threading.get_ident(),
            "args": {k: _jsonable(v) for k, v in (args or {}).items()},
        }
        if parent:
            event["args"]["parent"] = parent
        overflow = False
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1  # deque evicts the oldest span
                overflow = True
            self._events.append(event)
        if overflow:
            from shifu_tpu.obs import registry

            registry().counter("trace.dropped").inc()

    @property
    def events(self) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def between(self, lo: float, hi: float, prefix: str = "") -> List[dict]:
        """The events that END inside [lo, hi], both `perf_counter` stamps
        (the clock a caller times its own window on), whose name starts with
        `prefix`; in the order they ended."""
        # an event that ends on a bound is inside, whatever rounding does to
        # `ts + dur` against a bound taken from the same stamps: a nanosecond
        # of slack, where a double holds these microseconds to 1e-4
        lo_us = (lo - self.t0) * 1e6 - 1e-3
        hi_us = (hi - self.t0) * 1e6 + 1e-3
        with self._lock:
            return [dict(e) for e in self._events
                    if e["name"].startswith(prefix)
                    and lo_us <= e["ts"] + e["dur"] <= hi_us]

    @property
    def dropped(self) -> int:
        """Spans evicted by the -Dshifu.trace.maxEvents ring."""
        with self._lock:
            return self._dropped

    def span_seconds(self, name: str) -> float:
        """Total recorded duration of all spans with this name (seconds)."""
        with self._lock:
            return sum(e["dur"] for e in self._events
                       if e["name"] == name) / 1e6

    def to_chrome_trace(self) -> dict:
        with self._lock:
            events = sorted(self._events, key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> Optional[str]:
        """Write the Chrome-trace JSON; returns the path (None if no spans)."""
        with self._lock:
            if not self._events:
                return None
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.to_chrome_trace(), fh)
        return path


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)
