"""What the tree cells' whole-tree programs cost to trace and lower, without a
chip: each is built as for a DESCRIBED v5e (kernels on, not interpreted),
traced and lowered in this process, and jax's own events are listed by
function. Set-up on the chip pays these in every process (`setup_s`,
`setup_trace_lower_s`); PR 37's kernel bodies tripled them unseen.

    JAX_PLATFORMS=cpu python scripts/tree_setup_probe.py [one|mesh|rf ...]

One JSON line a program: seconds of the FIRST trace and lowering of a fresh
process (what a benchmark run pays: the first also fills jax's own caches,
so run a program alone to compare two checkouts), the equations of the
traced program with kernels' bodies and nested calls counted
(tests/test_tree_setup_guard.py holds them to the parent's), and the six
longest events. CPU seconds: they rank two checkouts, they are not the
chip host's.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CELLS = {  # depth, f32 or bf16 planes, rows, chips: benchmarks/configs/
    "one": (6, True, 5_500_000, 1),
    "mesh": (6, True, 11_000_000, 4),
    "rf": (10, False, 5_500_000, 1),
}


def equations(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    n += equations(inner)
    return n


def main(which) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import monitoring
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from shifu_tpu.ops import hist_pallas as hp
    from shifu_tpu.train import tree_trainer as tt

    events = collections.defaultdict(lambda: [0, 0.0])

    def on(event, seconds, **kw):
        key = (event.split("/")[-1].replace("_duration", ""),
               str(kw.get("fun_name")))
        events[key][0] += 1
        events[key][1] += seconds

    monitoring.register_event_duration_secs_listener(on)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    tt._pallas_state = lambda mesh=None: (True, False, mesh is None)
    lay = tt.make_layout([33] * 28, [False] * 28)
    for name in which:
        D, lowp, rows, chips = CELLS[name]
        mesh = (Mesh(np.array(topo.devices[:chips]), ("data",))
                if chips > 1 else None)

        def shape(sh, dt, spec):
            sharding = (NamedSharding(mesh, spec) if mesh is not None
                        else SingleDeviceSharding(topo.devices[0]))
            return jax.ShapeDtypeStruct(sh, dt, sharding=sharding)

        prog = tt._get_tree_program(D, lay, "variance", 5, 0.0, mesh=mesh,
                                    sub_levels=(False,) + (True,) * D,
                                    lowp=lowp)
        row = shape((rows,), jnp.float32, P("data"))
        args = (shape((rows, 28), jnp.int32, P("data")),
                shape((28, rows), hp.code_dtype(lay), P(None, "data")),
                row, row, shape((lay.T,), jnp.bool_, P()))
        events.clear()
        t0 = time.perf_counter()
        traced = prog.fn.trace(*args)
        t1 = time.perf_counter()
        traced.lower()
        t2 = time.perf_counter()
        top = sorted(events.items(), key=lambda kv: -kv[1][1])[:6]
        print(json.dumps({
            "program": name, "trace_s": round(t1 - t0, 3),
            "lower_s": round(t2 - t1, 3),
            "equations": equations(traced.jaxpr.jaxpr),
            "longest": [[k[0], k[1], n, round(s, 3)]
                        for k, (n, s) in top]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(CELLS))
