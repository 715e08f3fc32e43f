"""One-off tool, not a test: cuts a recorded xplane file down to a fixture.

    python tests/benchmark/make_xplane_fixture.py <in.xplane.pb> <out.xplane.pb> <calls>

Keeps the first chip's `XLA Ops` line and the host's `bench.*` spans, from the
start of the first `bench.call` to the end of the `<calls>`-th, and only the
names those events use. Needs tensorflow's copy of the xplane protobuf, which
the tests do not.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2


def main(src: str, dst: str, calls: int) -> None:
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    host = [p for p in space.planes if p.name == "/host:CPU"][0]
    dev = [p for p in space.planes if p.name == "/device:TPU:0"][0]
    names = {k: v.name for k, v in host.event_metadata.items()}
    spans = []
    for line in host.lines:
        for ev in line.events:
            if names.get(ev.metadata_id, "") == "bench.call":
                s = line.timestamp_ns * 1000 + ev.offset_ps
                spans.append((s, s + ev.duration_ps))
    spans.sort()
    lo, hi = spans[0][0], spans[calls - 1][1]
    out = xplane_pb2.XSpace()
    for plane, keep_line in ((dev, lambda l: l.name == "XLA Ops"),
                             (host, lambda l: True)):
        p = out.planes.add()
        p.id, p.name = plane.id, plane.name
        used = set()
        for line in plane.lines:
            if not keep_line(line):
                continue
            evs = []
            for ev in line.events:
                s = line.timestamp_ns * 1000 + ev.offset_ps
                name = plane.event_metadata[ev.metadata_id].name
                if plane is host and not name.startswith("bench."):
                    continue
                if s >= lo and s + ev.duration_ps <= hi:
                    evs.append(ev)
            if not evs:
                continue
            nl = p.lines.add()
            nl.id, nl.name = line.id, line.name
            nl.timestamp_ns = line.timestamp_ns
            for ev in evs:
                ne = nl.events.add()
                ne.metadata_id = ev.metadata_id
                ne.offset_ps, ne.duration_ps = ev.offset_ps, ev.duration_ps
                used.add(ev.metadata_id)
        for k in used:
            p.event_metadata[k].id = k
            p.event_metadata[k].name = plane.event_metadata[k].name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]))
