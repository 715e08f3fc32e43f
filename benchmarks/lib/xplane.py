"""From the profiler's xplane file to numbers: device busy time as the union
of the intervals in which an operation ran, the idle gaps with what the host
was doing in each, and device time by operation name.

Read with nothing but `jax.profiler.ProfileData`. The window is the span of
the benchmark's own `bench.call` annotations, which the profiler writes into
the same file on the same clock as the device's events.
"""

from __future__ import annotations

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
CALL_SPAN = "bench.call"


def union(intervals: list) -> list:
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(merged: list, lo: float, hi: float) -> list:
    """The complement of a merged, clipped interval list inside [lo, hi]."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label_gap(gap: tuple, spans: list) -> str:
    """What the host was doing at the middle of the gap: the innermost of the
    benchmark's spans that covers it, or 'between calls'."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "between calls"


def self_times(events: list, lo: float, hi: float) -> dict:
    """{name: ns} of each operation's own time inside [lo, hi]: an event's
    duration less that of the events nested in it, so that a `while` or a
    `call` that only wraps others counts for what it adds itself."""
    out, stack = {}, []  # stack of [name, end, own]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def short_name(hlo: str, width: int = 160) -> str:
    """An HLO line without its layouts, cut to `width`."""
    import re

    return re.sub(r"\{[^{}]*\}", "", hlo)[:width]


def read_planes(path: str):
    """(device events by plane {plane: [(name, start_ns, end_ns)]}, host spans
    [(name, start_ns, end_ns)])."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = device.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return device, spans


def summarize(device: dict, spans: list, chips: int = 1) -> dict:
    """busy_s and window_s (seconds, busy averaged over the chips used),
    device_ops [[name, seconds]] by own time, idle_gaps [[what the host was
    doing, seconds]] longest first, op_seconds {full name: own seconds} on the
    first chip, inside the window."""
    calls = [(s, e) for name, s, e in spans if name == CALL_SPAN]
    planes = sorted(device)[:chips]
    if calls:
        lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    elif planes:
        lo = min(s for p in planes for _, s, _ in device[p])
        hi = max(e for p in planes for _, _, e in device[p])
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "op_seconds": {}}
    busy, idle, ops = 0.0, [], {}
    for i, p in enumerate(planes):
        merged = union(clip([(s, e) for _, s, e in device[p]], lo, hi))
        busy += sum(e - s for s, e in merged)
        if i == 0:
            idle = gaps(merged, lo, hi)
            ops = self_times(device[p], lo, hi)
    by_label = {}
    for g in idle:
        key = label_gap(g, spans)
        by_label.setdefault(key, []).append(g[1] - g[0])
    longest = sorted(((max(v), k, len(v), sum(v)) for k, v in
                      by_label.items()), reverse=True)
    op_s = {k: v * 1e-9 for k, v in ops.items()}
    return {
        "busy_s": busy * 1e-9 / max(len(planes), 1),
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[short_name(k), v] for k, v in sorted(
            op_s.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [["%s (longest of %d, %.6f s in all)" % (k, n, tot * 1e-9),
                       mx * 1e-9] for mx, k, n, tot in longest],
        "op_seconds": op_s,
    }


def idle_pct(trace) -> float | None:
    """The share of the traced window in which no operation ran on the
    device; nothing where there is no trace or no device event in it."""
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def reduce(path: str, chips: int = 1) -> dict:
    device, spans = read_planes(path)
    return summarize(device, spans, chips)
