"""Device-mesh helpers — the TPU-native replacement for the Guagua BSP layer.

The reference runs master+workers as Hadoop mappers synchronized through
ZooKeeper (SURVEY §5: guagua-mapreduce, NNParams Bytable exchange). Here the
whole "cluster" is one SPMD program: rows are sharded over the mesh's row
axes, weights are replicated, and XLA inserts the gradient all-reduce (the
`psum` that replaces NNMaster.accumulateGradients) when the jitted train step
consumes row-sharded inputs and produces replicated outputs.

Axis names:
    dcn    — OUTER axis across slices/hosts connected by data-center
             network (multi-slice pods). Present only when the device set
             spans >1 slice (or when forced via dcn_slices). Row sharding
             spans (dcn, data) so the heavy within-slice reduction rides
             ICI and only the per-slice partial crosses DCN — XLA lowers
             the psum hierarchically from the mesh topology.
    data   — row (batch) parallelism within a slice; every trainer uses it
    model  — reserved for tensor-parallel WDL embedding shards
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def _slice_count(devices) -> int:
    """Distinct slice indices in the device set (1 on single-slice or when
    the platform doesn't expose slice_index, e.g. CPU)."""
    ids = set()
    for d in devices:
        ids.add(getattr(d, "slice_index", 0) or 0)
    return max(1, len(ids))


def data_mesh(n_devices: Optional[int] = None, model_axis: int = 1,
              dcn_slices: Optional[int] = None):
    """Mesh over the available devices.

    Single slice: (data[, model]). Multi-slice (detected from the devices'
    slice_index, or forced with `dcn_slices` for virtual-device tests):
    (dcn, data[, model]) with `dcn` outermost, so collectives are
    hierarchical — within-slice over ICI first, across slices over DCN
    (SURVEY §5's comm-backend obligation)."""
    import jax
    from jax.sharding import Mesh

    from shifu_tpu.obs import registry

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    registry().gauge("mesh.devices").set(n)
    n_dcn = dcn_slices if dcn_slices else _slice_count(devices)
    if n_dcn > 1:
        assert n % n_dcn == 0, (n, n_dcn)
        per_slice = n // n_dcn
        if dcn_slices:
            dev = np.array(devices).reshape(n_dcn, per_slice)
        else:  # group real devices by their slice
            by_slice: dict = {}
            for d in devices:
                by_slice.setdefault(getattr(d, "slice_index", 0) or 0,
                                    []).append(d)
            sizes = {k: len(v) for k, v in by_slice.items()}
            if len(set(sizes.values())) != 1:
                raise ValueError(
                    f"device set spans slices unevenly ({sizes}); a mesh "
                    "needs equal devices per slice — pass n_devices as a "
                    "multiple of the slice size")
            dev = np.array([by_slice[k] for k in sorted(by_slice)])
        if model_axis > 1:
            assert per_slice % model_axis == 0, (per_slice, model_axis)
            dev = dev.reshape(n_dcn, per_slice // model_axis, model_axis)
            return Mesh(dev, ("dcn", "data", "model"))
        return Mesh(dev, ("dcn", "data"))
    if model_axis > 1:
        assert n % model_axis == 0, (n, model_axis)
        dev = np.array(devices).reshape(n // model_axis, model_axis)
        return Mesh(dev, ("data", "model"))
    return Mesh(np.array(devices), ("data",))


_LIFECYCLE_MESHES: dict = {}


def lifecycle_shards() -> int:
    """Row-shard count for the lifecycle map/reduce folds (streaming
    stats/norm/eval/autotype): `shifu.lifecycle.shards` when set (>0),
    else every visible device. 1 is the degenerate single-device case —
    the same code path, a 1-wide mesh."""
    from shifu_tpu.utils import environment

    n = environment.get_int("shifu.lifecycle.shards", 0)
    if n > 0:
        return n
    import jax

    return max(1, len(jax.devices()))


def lifecycle_hosts() -> int:
    """Host (process) count of the pod-scale data plane:
    `shifu.lifecycle.hosts` when set (>0), else 1 — the single-controller
    degenerate case every pre-host run is."""
    from shifu_tpu.utils import environment

    return max(1, environment.get_int("shifu.lifecycle.hosts", 1))


def lifecycle_host_index() -> int:
    """This process's host index in [0, lifecycle_hosts()):
    `shifu.lifecycle.hostIndex` when set, else `jax.process_index()` —
    on a real multi-host pod the jax runtime numbers the processes; on a
    CPU fleet of OS processes the launcher pins the index (the PR-14
    lease id names the process, the index orders it)."""
    from shifu_tpu.utils import environment

    idx = environment.get_int("shifu.lifecycle.hostIndex", -1)
    if idx >= 0:
        return idx
    import jax

    return int(jax.process_index())


def reduce_topology() -> str:
    """shifu.reduce.topology — window-reduce lowering override:
    `auto` (default: hierarchical when the mesh has a dcn axis, flat on a
    single-slice mesh), `hierarchical`, or `flat` (forces the one-stage
    joint psum even on a multi-slice mesh — the bit-parity reference)."""
    from shifu_tpu.utils import environment

    v = environment.get_property("shifu.reduce.topology", "auto")
    v = (v or "auto").strip().lower()
    return v if v in ("auto", "hierarchical", "flat") else "auto"


def hierarchical_reduce(mesh) -> bool:
    """Whether window_reduce on `mesh` lowers as the explicit two-stage
    tree (psum over ICI/`data` first, then ONE partial per slice across
    `dcn`). Flat is the 1-slice degenerate case: with no dcn axis there
    is nothing to stage."""
    return "dcn" in row_axes(mesh) and reduce_topology() != "flat"


def lifecycle_mesh(n_shards: Optional[int] = None):
    """The (cached) mesh the lifecycle folds shard rows over: the first
    `n_shards` devices, (dcn, data) when the set spans slices so the
    windowed psum reduce lowers hierarchically — heavy within-slice over
    ICI, one partial per slice over DCN."""
    n = lifecycle_shards() if n_shards is None else max(1, int(n_shards))
    mesh = _LIFECYCLE_MESHES.get(n)
    if mesh is None:
        mesh = data_mesh(n_devices=n)
        _LIFECYCLE_MESHES[n] = mesh
    return mesh


def row_axes(mesh) -> Tuple[str, ...]:
    """Axis names rows shard over: ('dcn', 'data') on a multi-slice mesh,
    ('data',) otherwise. Also the psum axes for gradient/histogram
    all-reduces."""
    return tuple(a for a in mesh.axis_names if a in ("dcn", "data"))


def row_shard_count(mesh) -> int:
    """Number of row shards = product of the row axes' sizes (what row
    counts must pad to)."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in row_axes(mesh):
        n *= shape.get(a, 1)
    return n


def round_up_rows(n: int, mesh) -> int:
    """Smallest row count >= n that splits evenly over the mesh's row
    shards (padding rows must carry zero significance — see pad_rows)."""
    m = row_shard_count(mesh)
    return -(-n // m) * m


def pad_rows(
    arrays: Sequence, multiple: int
) -> Tuple[list, int]:
    """Pad row dimension to a multiple (sharding needs even splits). Padded
    rows must carry zero significance — callers pad weights with 0. Host
    arrays are padded on the host and `jax.Array`s on their devices; an
    array that already carries padding rows (a code matrix placed over the
    mesh ahead of the call) is kept and the others are padded up to it.
    Returns the arrays and the first one's row count as it came in."""
    import jax

    n = arrays[0].shape[0]
    longest = max(a.shape[0] for a in arrays)
    target = ((longest + multiple - 1) // multiple) * multiple
    out = []
    for a in arrays:
        short = target - a.shape[0]
        if not short:
            out.append(a)
        elif isinstance(a, jax.Array):
            import jax.numpy as jnp

            out.append(jnp.pad(a, [(0, short)] + [(0, 0)] * (a.ndim - 1)))
        else:
            out.append(np.concatenate(
                [a, np.zeros((short,) + a.shape[1:], dtype=a.dtype)], axis=0))
    return out, n


def _row_sharding(mesh, ndim: int):
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = row_axes(mesh)
    return NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0],
                                 *([None] * (ndim - 1))))


def shard_rows(array, mesh):
    """Place an array on the mesh sharded along its leading (row) axis —
    over (dcn, data) on a multi-slice mesh. A `jax.Array` that already lies
    so is returned as it is, and one that lies elsewhere on the devices
    moves between them: `mesh.h2d_bytes` counts only what crosses from the
    host."""
    import jax

    from shifu_tpu.obs import registry

    sharding = _row_sharding(mesh, array.ndim)
    on_device = isinstance(array, jax.Array)
    if on_device and array.sharding.is_equivalent_to(sharding, array.ndim):
        return array
    # collective-op accounting: every sharded placement seeds a program
    # whose row-sharded consumption XLA closes with a psum over `axes`
    reg = registry()
    reg.counter("mesh.shard_rows", axes="x".join(row_axes(mesh))).inc()
    if not on_device:
        reg.counter("mesh.h2d_bytes").inc(float(getattr(array, "nbytes", 0)))
    out = jax.device_put(array, sharding)
    # where the rows actually landed (shape metadata, no transfer): a
    # placement that put everything on one device shows here
    shards = out.addressable_shards
    rows = [s.data.shape[0] for s in shards]
    reg.gauge("mesh.rows_per_device.min").set(min(rows))
    reg.gauge("mesh.rows_per_device.max").set(max(rows))
    reg.gauge("mesh.row_devices").set(len({s.device.id for s in shards}))
    return out


def pull_rows(array) -> np.ndarray:
    """A row array as a host array; what leaves the devices for it is
    counted in `mesh.d2h_bytes` (the in-memory tree trainer pulls none on a
    fresh run: only a resumed forest's per-row scores come back)."""
    import jax

    if isinstance(array, jax.Array):
        from shifu_tpu.obs import registry

        registry().counter("mesh.d2h_bytes").inc(float(array.nbytes))
    return np.asarray(array)


def replicate(tree, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from shifu_tpu.obs import registry

    sharding = NamedSharding(mesh, P())
    leaves = jax.tree_util.tree_leaves(tree)
    reg = registry()
    reg.counter("mesh.replicated_arrays").inc(len(leaves))
    reg.counter("mesh.h2d_bytes").inc(
        float(sum(getattr(a, "nbytes", 0) for a in leaves)))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sharding), tree)


_FLEET_REDUCE_PROGRAMS: dict = {}


def fleet_mesh(n_devices: int):
    """Mesh over the first `n_devices` devices — the serving fleet's
    replica devices are always a prefix of jax.devices() (serve/fleet.py
    assigns replica i -> device i % ndev), so this is the mesh whose row
    shards line up one-to-one with the fleet's distinct devices. Shares
    the lifecycle mesh cache: the fleet's reduce and the lifecycle folds
    deliberately run on ONE mesh family (the TensorFlow/DrJAX argument —
    train and serve share a compiled-graph substrate)."""
    return lifecycle_mesh(n_shards=max(1, int(n_devices)))


def fleet_reduce(mesh, parts: np.ndarray, max_cols: int = 0) -> np.ndarray:
    """One-collective merge of per-device stat vectors — the serving
    fleet's analog of ops/binagg.window_reduce: `parts` is [D, K] with
    one row per mesh device, the leading K-max_cols columns reduce with
    psum and the trailing `max_cols` columns with pmax (extrema don't
    sum), and every device ends up with the same replicated [K] result —
    the host pulls ONE vector, not D.

    Used for cross-replica shadow-agreement evidence (rolling promote):
    each replica's counts stage onto its own device, one psum tree
    closes the fleet verdict."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    parts = np.asarray(parts, dtype=np.float32)
    axes = row_axes(mesh)
    n_shards = row_shard_count(mesh)
    assert parts.shape[0] == n_shards, (parts.shape, n_shards)
    key = (id(mesh), int(parts.shape[1]), int(max_cols))
    prog = _FLEET_REDUCE_PROGRAMS.get(key)
    if prog is None:
        def local(v):  # v: [1, K] — this device's stat row
            summed = jax.lax.psum(v, axes)
            if max_cols:
                maxed = jax.lax.pmax(v[:, -max_cols:], axes)
                summed = jnp.concatenate(
                    [summed[:, : v.shape[1] - max_cols], maxed], axis=1)
            return summed[0]

        prog = jax.jit(shard_map_compat(
            local, mesh=mesh,
            in_specs=(P(axes if len(axes) > 1 else axes[0], None),),
            out_specs=P()))
        _FLEET_REDUCE_PROGRAMS[key] = prog
    spec = P(axes if len(axes) > 1 else axes[0], None)
    staged = jax.device_put(parts, NamedSharding(mesh, spec))
    from shifu_tpu.obs import registry

    registry().counter("serve.fleet.reduces").inc()
    return np.asarray(jax.device_get(prog(staged)), dtype=np.float64)


def shard_map_compat(fn, *, mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with replication checking off by default (the
    growers psum partials themselves). One helper so every call site
    spells the check the same way."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
