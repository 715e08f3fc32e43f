"""The program's own spans, in both places they land.

`shifu_tpu.obs.span` keeps every span in the tracer's ring on the host's
`perf_counter`, the clock `ctx["window_start"]` and `ctx["calls"]` are on:
`ring(ctx, prefix)` is the part of the ring a reader of the window wants.
It also writes each span into the profiler's trace as `shifu.<name>`, on the
device's clock, beside the benchmark's own `bench.*` annotations: `reduce`
is `xplane.reduce` with both kinds of span handed to `xplane.summarize`,
which names an idle gap by the innermost span that covers it.

A program without the spans (a parent commit) gives an empty ring and a
trace with `bench.*` alone; neither is an error.
"""

from __future__ import annotations

from benchmarks.lib import xplane

PREFIXES = ("bench.", "shifu.")


def ring(ctx, prefix: str, before_window: bool = False) -> list:
    """The tracer's events whose name starts with `prefix` and which end
    inside the measured window, or before it (set-up) with `before_window`."""
    from shifu_tpu import obs

    between = getattr(obs.tracer(), "between", None)
    if between is None or not ctx["calls"]:
        return []
    if before_window:
        return between(float("-inf"), ctx["window_start"], prefix)
    return between(ctx["window_start"], ctx["calls"][-1][1], prefix)


def trainer_setup(ctx, prefix: str) -> list:
    """The set-up's compile events (`jax.trace`, `jax.lower`, `jax.compile`)
    whose parent span is a trainer's (`train....`): the benchmark's data
    program and the driver's own are left out."""
    return [e for e in ring(ctx, prefix, before_window=True)
            if e["args"].get("parent", "").startswith("train.")]


def seconds(events: list, name: str) -> float:
    return sum(e["dur"] for e in events if e["name"] == name) * 1e-6


def read_spans(path: str) -> list:
    """[(name, start_ns, end_ns)] of the host plane's `bench.*` and `shifu.*`
    events."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def reduce(path: str, chips: int = 1) -> dict:
    device, _bench_only = xplane.read_planes(path)
    return xplane.summarize(device, read_spans(path), chips)
