"""Device time by named scope, from a kept xplane file
(`BENCH_KEEP_TRACE=<dir>`): each operation's own time inside the window of
the `bench.call` spans, grouped by the scope its `tf_op` carries
(`tree.L4/hist`, `tree.L4/psum`, `nn.bwd`, `transpose(jvp(wdl.embed))`, ...),
one chip at a time.

    python scripts/trace_by_scope.py <file.xplane.pb> [--depth 2] [--top 12]

Prints one JSON line a chip: busy and window seconds, seconds by scope (cut
to `--depth` parts; the level folded out of `tree.L<n>/<phase>` under
`by_phase`; `tree.codes` read wherever it stands in the stack, so
`tree.L1/hist/tree.codes/pad` is `tree.codes`' and not the level's), and the
`--top` largest operations. Needs tensorflow's copy of
the xplane protobuf (`jax.profiler.ProfileData` hands out no `tf_op`), which
the benchmark itself does not.

Since PR 36 the benchmark reads the same split without this script and
without a kept file: `benchmarks/lib/scopes.py` joins the traced window's
events to `obs.profile.scope_table()` by instruction name, and ten per-layer
metrics report it in every traced run (`tree_route_ms_per_tree`, ...,
`nn_bwd_ms_per_epoch`). What this script is still for: a kept file read
after the run (an xprof session of `-Dshifu.profile=xla` too), every chip
of a mesh and not the first alone, the split by level (`by_scope` keeps
`tree.L128/route` apart where the metrics fold the level out), the largest
operations with their `tf_op`, and a check of the join itself: on one
traced run the two agree to the digit wherever `scope_table` has the
window's executables (PERF.md section 6, PR 36)."""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import xplane  # noqa: E402

# a part of an op's name stack that is one of the program's own scopes, bare
# (`tree.L4`, `nn.bwd`, `wdl.update`) or as jax wraps it under a `grad`
# (`jvp(wdl.embed)`, `transpose(jvp(wdl.embed))`)
SCOPE = re.compile(r"(?:^|\()(?:tree|nn|wdl)\.")
CODES = "tree.codes"


def _stat_value(plane, stat):
    if stat.HasField("ref_value"):
        return plane.stat_metadata[stat.ref_value].name
    return stat.str_value


def read(path: str):
    """({plane: [(metadata id, start ns, end ns)]}, {plane: {id: (name,
    tf_op)}}, [(start, end)] of the `bench.call` spans)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    events, meta, calls = {}, {}, []
    for plane in space.planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            tf_op = [k for k, v in plane.stat_metadata.items()
                     if v.name == "tf_op"]
            meta[plane.name] = {
                k: (m.name, next((_stat_value(plane, s) for s in m.stats
                                  if s.metadata_id in tf_op), ""))
                for k, m in plane.event_metadata.items()}
            for line in plane.lines:
                if line.name != xplane.OPS_LINE:
                    continue
                base = line.timestamp_ns
                events[plane.name] = [
                    (ev.metadata_id, base + ev.offset_ps / 1000.0,
                     base + (ev.offset_ps + ev.duration_ps) / 1000.0)
                    for ev in line.events]
        elif plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                base = line.timestamp_ns
                for ev in line.events:
                    if plane.event_metadata[ev.metadata_id].name \
                            == xplane.CALL_SPAN:
                        s = base + ev.offset_ps / 1000.0
                        calls.append((s, s + ev.duration_ps / 1000.0))
    return events, meta, calls


def by_scope(path: str, depth: int = 2, top: int = 12) -> list:
    events, meta, calls = read(path)
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    out = []
    for plane in sorted(events):
        own = xplane.self_times(events[plane], lo, hi)
        busy = sum(e - s for s, e in xplane.union(xplane.clip(
            [(s, e) for _, s, e in events[plane]], lo, hi)))
        scopes, phases = {}, {}
        for k, ns in own.items():
            parts = [p for p in meta[plane][k][1].split("/") if p]
            # jit(...)/ wrappers come first: start at the program's own scope
            at = next((i for i, p in enumerate(parts) if SCOPE.search(p)),
                      None)
            key = "/".join(parts[at:at + depth]) if at is not None else "-"
            if CODES in parts:
                # written inside a level's `hist` in the whole-tree
                # program: the code operand's, not that level's
                key = CODES
            scopes[key] = scopes.get(key, 0.0) + ns * 1e-9
            m = re.match(r"tree\.L\d+/(\w+)", key)
            ph = (m.group(1) if m else "psum" if key == "tree.leaf/psum"
                  else key.split("/")[0])
            phases[ph] = phases.get(ph, 0.0) + ns * 1e-9
        ops = sorted(own.items(), key=lambda kv: -kv[1])[:top]
        out.append({
            "chip": plane, "window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "by_phase": dict(sorted(phases.items(), key=lambda kv: -kv[1])),
            "by_scope": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
            "top_ops": [[xplane.short_name(meta[plane][k][0], 100),
                         meta[plane][k][1][-60:], ns * 1e-9]
                        for k, ns in ops]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--top", type=int, default=12)
    a = ap.parse_args(argv)
    for line in by_scope(a.path, a.depth, a.top):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
