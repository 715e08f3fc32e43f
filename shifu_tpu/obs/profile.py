"""ProgramProfiler: per-jit-program XLA cost accounting at dispatch seams.

Every hot path in this repo funnels through a handful of compiled
programs (trainer while-loops, the tree grower's histogram/scan/update
kernels, the streamed shard-grad, the pipeline device fold, the serve
registry's fused raw->score program). This module makes each of those
dispatch seams self-accounting: the first time a program runs with a
given input signature it is lowered once through the AOT API
(`fn.lower(...).compile()`), XLA's `cost_analysis()` (FLOPs, bytes
accessed) and `memory_analysis()` (peak HBM) are recorded, and every
subsequent dispatch goes through that same compiled executable — so the
accounting costs ONE compile per program+shape, exactly what plain jit
dispatch costs, not two.

Per program the current obs scope accumulates: dispatch count, FLOPs and
bytes (scaled by `scaled(k)` for programs whose device loop runs k
iterations per dispatch — XLA counts a while-loop body once), peak HBM,
compile seconds, and device wall-clock (for `sync=True` seams, which
block on the result; async seams record dispatch time and are flagged
`synced: false`). `snapshot()` joins the counts with the chip peak table
(obs/costmodel.py) into achieved FLOP/s, achieved bandwidth, arithmetic
intensity, MFU and a roofline verdict; BasicProcessor.run() embeds it in
every run-ledger manifest.

Fallbacks keep the seams safe: tracer arguments (a wrapped program used
inside another traced program), un-lowerable callables, or any AOT
failure degrade to a plain `fn(*args)` call with dispatch counting only
(`costSource: "unavailable"`). `-Dshifu.profile.mode=off` disables the
profiler entirely (plain calls, zero overhead).
"""

from __future__ import annotations

import threading
import time

from shifu_tpu.analysis.racetrack import tracked_lock
from shifu_tpu.obs import jaxprobe, tracing
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

SCHEMA = "shifu.profile/1"

# process-global cost cache: one lower+compile per (seam, fn, signature).
# Survives obs scope resets (the executable cache it mirrors does too);
# LRU-capped so churned per-instance jits cannot grow it unboundedly.
# An evicted-then-revisited signature pays one fresh AOT compile (the jit
# dispatch cache is separate), so the cap sits well above any one run's
# working set of (program, layout, row-bucket) combinations.
_COST_CACHE_MAX = 512
_cost_lock = tracked_lock("obs.profile.cost_cache")
_cost_cache: "OrderedDict[tuple, _CostEntry]" = OrderedDict()

_tls = threading.local()


def _mode() -> str:
    from shifu_tpu.utils import environment

    return (environment.get_property("shifu.profile.mode", "on")
            or "on").strip().lower()


class _CostEntry:
    """One lowered+compiled program signature and its XLA cost numbers.

    Holds a strong reference to the wrapped `fn`: the cache key uses
    id(fn), so the entry must keep that object alive — a garbage-
    collected fn whose id CPython recycles for a new program (per-model
    jit closures in eval/serve) would otherwise resolve to a stale
    executable with the OLD closure's constants baked in."""

    __slots__ = ("fn", "compiled", "flops", "bytes_accessed", "peak_hbm",
                 "arg_bytes", "compile_seconds", "source", "scope_map")

    def __init__(self, fn: Optional[Callable] = None) -> None:
        self.fn = fn
        self.compiled = None
        self.scope_map: Optional[Dict[str, Tuple[str, str]]] = None
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.peak_hbm: Optional[float] = None
        self.arg_bytes: Optional[float] = None
        self.compile_seconds: float = 0.0
        self.source = "unavailable"


@contextmanager
def scaled(k: float):
    """Multiply cost attribution for dispatches inside: a trainer that
    runs its while-loop body k times per dispatch wraps the dispatch in
    `scaled(k)` so FLOPs/bytes count k bodies (XLA's cost analysis counts
    a while body exactly once, whatever the trip count)."""
    prev = getattr(_tls, "scale", 1.0)
    _tls.scale = max(1.0, float(k))
    try:
        yield
    finally:
        _tls.scale = prev


def _current_scale() -> float:
    return getattr(_tls, "scale", 1.0)


def _split_static(args: tuple, kwargs: dict, static_argnums: tuple,
                  static_argnames: tuple):
    """(dynamic args, dynamic kwargs, hashable static key)."""
    if not static_argnums and not static_argnames:
        return args, kwargs, ()
    dyn_args = tuple(a for i, a in enumerate(args)
                     if i not in static_argnums)
    statics = tuple((i, args[i]) for i in static_argnums if i < len(args))
    dyn_kwargs = {k: v for k, v in kwargs.items()
                  if k not in static_argnames}
    statics += tuple((k, kwargs[k]) for k in static_argnames
                     if k in kwargs)
    return dyn_args, dyn_kwargs, statics


def _signature(dyn_args: tuple, dyn_kwargs: dict, statics: tuple):
    """Hashable (treedef, avals+shardings, statics) key for the dynamic
    arguments — the same distinctions the jit cache draws (shape, dtype,
    weak type, sharding), so one entry maps to one executable."""
    import jax
    from jax.api_util import shaped_abstractify

    leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
    keys = []
    for leaf in leaves:
        if isinstance(leaf, jax.core.Tracer):
            return None  # traced context: no profiling, inline the call
        keys.append((shaped_abstractify(leaf),
                     getattr(leaf, "sharding", None)))
    return (treedef, tuple(keys), statics)


def _first_cost_dict(analysis) -> dict:
    if isinstance(analysis, (list, tuple)):
        return dict(analysis[0]) if analysis else {}
    return dict(analysis or {})


def _build_entry(name: str, fn: Callable, args: tuple,
                 kwargs: dict) -> _CostEntry:
    """Lower+compile once, harvest cost/memory analyses. Transfers are
    re-allowed inside (profiler-internal work, not the caller's hot
    path), so building an entry under an armed transfer guard is legal."""
    entry = _CostEntry(fn)
    import jax

    lower = getattr(fn, "lower", None)
    if lower is None:
        return entry
    t0 = time.perf_counter()
    with jax.transfer_guard("allow"):
        try:
            lowered = lower(*args, **kwargs)
        except Exception:  # un-lowerable seam -> plain-dispatch fallback
            # (exotic pytree, shard_map edge, ...): dispatch counting only
            return entry
        try:
            cost = _first_cost_dict(lowered.cost_analysis())
        except Exception:  # cost analysis is best-effort per backend
            cost = {}
        # NOT guarded: a compile the backend refuses (Mosaic VMEM limit,
        # HBM OOM) rises from the seam, once — re-dispatching through
        # plain jit would only pay the same failing compile a second time
        compiled = lowered.compile()
        entry.compile_seconds = time.perf_counter() - t0
        if not cost:
            try:
                cost = _first_cost_dict(compiled.cost_analysis())
            except Exception:  # cost analysis is best-effort per backend
                cost = {}
        entry.flops = float(cost.get("flops", 0.0)) or None
        entry.bytes_accessed = (
            float(cost.get("bytes accessed", 0.0)) or None)
        try:
            mem = compiled.memory_analysis()
            entry.peak_hbm = float(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0))
            entry.arg_bytes = float(
                getattr(mem, "argument_size_in_bytes", 0)) or None
        except Exception:  # memory stats are best-effort per backend
            entry.peak_hbm = None
        entry.compiled = compiled
        entry.source = "xla"
    return entry


class ProgramProfiler:
    """Per-obs-scope accumulator (reset with the registry/tracer)."""

    def __init__(self) -> None:
        self._lock = tracked_lock("obs.profile.profiler")
        self._programs: Dict[str, Dict[str, Any]] = {}

    # ---- recording ----
    def _stats(self, name: str) -> Dict[str, Any]:
        st = self._programs.get(name)
        if st is None:
            st = {
                "dispatches": 0, "scaledDispatches": 0.0, "flops": 0.0,
                "bytesAccessed": 0.0, "peakHbmBytes": 0.0, "argBytes": 0.0,
                "compileSeconds": 0.0, "programsCompiled": 0,
                "deviceSeconds": 0.0, "dispatchSeconds": 0.0,
                "syncedDispatches": 0, "costSource": "unavailable",
            }
            self._programs[name] = st
        return st

    def record_compile(self, name: str, entry: _CostEntry) -> None:
        with self._lock:
            st = self._stats(name)
            st["compileSeconds"] += entry.compile_seconds
            st["programsCompiled"] += 1

    def annotate(self, name: str, **kv: Any) -> None:
        """Attach kernel/program shaping facts (chosen block sizes, knob
        values) to seam `name`; they ride into every snapshot so a
        tuning sweep can read WHICH shaping produced WHICH roofline
        numbers from the manifest alone. Stored PROCESS-globally (like
        the cost cache): annotations describe compiled kernels, which
        survive obs.reset() too — a build in an earlier scope must still
        be visible in a later scope's manifest."""
        with _ann_lock:
            _annotations_store.setdefault(name, {}).update(kv)

    def record_dispatch(self, name: str, entry: Optional[_CostEntry],
                        scale: float, seconds: float, sync: bool) -> None:
        with self._lock:
            st = self._stats(name)
            st["dispatches"] += 1
            # work units: scaled(k) dispatches count k loop bodies, so
            # cross-run diffs can normalize per body, not per call
            st["scaledDispatches"] += max(1.0, float(scale))
            st["dispatchSeconds"] += seconds
            if sync:
                st["syncedDispatches"] += 1
                st["deviceSeconds"] += seconds
            if entry is not None and entry.source == "xla":
                st["costSource"] = "xla"
                if entry.flops:
                    st["flops"] += entry.flops * scale
                if entry.bytes_accessed:
                    st["bytesAccessed"] += entry.bytes_accessed * scale
                if entry.peak_hbm:
                    st["peakHbmBytes"] = max(st["peakHbmBytes"],
                                             entry.peak_hbm)
                if entry.arg_bytes:
                    # the program's HBM INPUT CONTRACT (largest
                    # signature): what a dispatch must read from HBM
                    # regardless of how the backend accounts internal
                    # traffic — the metric that shows a once-
                    # materialized operand leaving a program's
                    # argument list
                    st["argBytes"] = max(st["argBytes"], entry.arg_bytes)

    # ---- views ----
    def snapshot(self, peaks=None) -> dict:
        """The manifest `profile` section: per-program table + totals,
        joined with the chip peak envelope into roofline terms."""
        from shifu_tpu.obs import costmodel

        if peaks is None:
            peaks = costmodel.detect()
        with self._lock:
            progs = {k: dict(v) for k, v in self._programs.items()}
        with _ann_lock:
            annotations = {k: dict(v)
                           for k, v in _annotations_store.items()}
        out_programs = {}
        for name, st in sorted(progs.items()):
            synced = (st["dispatches"] > 0
                      and st["syncedDispatches"] == st["dispatches"])
            flops = st["flops"] or None
            bytes_ = st["bytesAccessed"] or None
            derived = costmodel.derive(
                flops, bytes_, st["deviceSeconds"] if synced else None,
                peaks)
            out_programs[name] = {
                "dispatches": st["dispatches"],
                "scaledDispatches": round(st["scaledDispatches"], 1),
                "flops": st["flops"],
                "bytesAccessed": st["bytesAccessed"],
                "peakHbmBytes": st["peakHbmBytes"],
                "argBytes": st["argBytes"],
                "compileSeconds": round(st["compileSeconds"], 4),
                "programsCompiled": st["programsCompiled"],
                "deviceSeconds": round(st["deviceSeconds"], 4),
                "dispatchSeconds": round(st["dispatchSeconds"], 4),
                "synced": synced,
                "costSource": st["costSource"],
                **derived,
            }
        tot = {"flops": 0.0, "bytesAccessed": 0.0, "peakHbmBytes": 0.0,
               "dispatches": 0, "deviceSeconds": 0.0, "compileSeconds": 0.0}
        all_synced = bool(out_programs)
        device_s = 0.0  # unrounded, so totals MFU matches the rows'
        for name, p in out_programs.items():
            tot["flops"] += p["flops"]
            tot["bytesAccessed"] += p["bytesAccessed"]
            tot["peakHbmBytes"] = max(tot["peakHbmBytes"],
                                      p["peakHbmBytes"])
            tot["dispatches"] += p["dispatches"]
            tot["deviceSeconds"] += p["deviceSeconds"]
            tot["compileSeconds"] += p["compileSeconds"]
            device_s += progs[name]["deviceSeconds"]
            all_synced = all_synced and p["synced"]
        tot["deviceSeconds"] = round(tot["deviceSeconds"], 4)
        tot["compileSeconds"] = round(tot["compileSeconds"], 4)
        tot.update(costmodel.derive(
            tot["flops"] or None, tot["bytesAccessed"] or None,
            device_s if all_synced and device_s else None, peaks))
        out = {
            "schema": SCHEMA,
            "chip": costmodel.peaks_dict(peaks),
            "programs": out_programs,
            "totals": tot,
        }
        if annotations:
            out["annotations"] = annotations
        return out


_profiler = ProgramProfiler()

# program-shaping annotations: process-global on purpose (see
# ProgramProfiler.annotate) — reset() preserves them, like _cost_cache
_annotations_store: Dict[str, Dict[str, Any]] = {}
_ann_lock = tracked_lock("obs.profile.annotations")


def profiler() -> ProgramProfiler:
    """The process-global profiler (current obs scope)."""
    return _profiler


def annotate(name: str, **kv) -> None:
    """Record program-shaping facts against seam `name` in the current
    obs scope (see ProgramProfiler.annotate)."""
    _profiler.annotate(name, **kv)


def reset() -> None:
    """Fresh per-scope accumulator (called from obs.reset()); the
    process-global cost cache deliberately survives — the executables it
    mirrors do too."""
    global _profiler
    _profiler = ProgramProfiler()


# ---------------------------------------------------------------------------
# dispatch seams
# ---------------------------------------------------------------------------


def _cost_entry(name: str, fn: Callable, sig, args: tuple,
                kwargs: dict) -> Optional[_CostEntry]:
    key = (name, id(fn), sig)
    with _cost_lock:
        entry = _cost_cache.get(key)
        if entry is not None:
            _cost_cache.move_to_end(key)
            return entry
    entry = _build_entry(name, fn, args, kwargs)
    with _cost_lock:
        have = _cost_cache.get(key)
        if have is not None:  # lost a race: keep the first build
            return have
        _cost_cache[key] = entry
        while len(_cost_cache) > _COST_CACHE_MAX:
            _cost_cache.popitem(last=False)
    _profiler.record_compile(name, entry)
    return entry


def release_fn(fn: Callable) -> int:
    """Drop every cached cost entry built for `fn` and return how many
    were dropped. The entries hold STRONG references to `fn` and its
    compiled executables (see _CostEntry) — correct for live programs,
    but a program being evicted (the serve zoo's LRU, a promoted-away
    registry version) must actually free its device buffers, and this
    cache would otherwise pin the closure'd weights until 512 other
    programs churned it out."""
    fid = id(fn)
    with _cost_lock:
        keys = [k for k in _cost_cache if k[1] == fid]
        for k in keys:
            del _cost_cache[k]
    return len(keys)


def fn_memory(name: str, fn: Callable) -> List[Dict[str, float]]:
    """memory_analysis() numbers of every compiled signature cached for
    seam `name` + program `fn`: one dict per signature (= per row bucket
    for the serve registry) with argBytes, peakBytes (args+out+temps
    −aliases) and tempOutBytes (peak − args: what the program adds to
    residency beyond its inputs). The serve zoo's HBM budget ledger
    prices a tenant's compiled-program residency from these."""
    fid = id(fn)
    with _cost_lock:
        entries = [e for k, e in _cost_cache.items()
                   if k[0] == name and k[1] == fid]
    out = []
    for e in entries:
        if e.peak_hbm is None:
            continue
        arg = float(e.arg_bytes or 0.0)
        out.append({
            "argBytes": arg,
            "peakBytes": float(e.peak_hbm),
            "tempOutBytes": max(0.0, float(e.peak_hbm) - arg),
        })
    return out


def compiled_texts(name: str) -> List[str]:
    """The compiled module's text of every executable the cost cache holds
    for seam `name`, one a signature. A device trace names a fusion
    (`%fusion.668`) and not what it holds; these are the very executables
    the seam dispatches, so their text says. Empty with the profiler off,
    after an eviction, or where the ahead-of-time compile fell back to
    plain jit."""
    with _cost_lock:
        entries = [e for k, e in _cost_cache.items() if k[0] == name]
    return [e.compiled.as_text() for e in entries if e.compiled is not None]


def result_shape(text: str, at: int, stop: Optional[int] = None) -> str:
    """The result shape an instruction's text carries at `at`, just after
    its `%name = `, without layouts and index comments: `f32[64,16]`,
    `(f32[1,512], s32[])`. A device trace's event names print it the same
    way, so a reader of both compares the two through this."""
    import re

    stop = len(text) if stop is None else stop
    if text.startswith("(", at):  # a tuple: to its closing parenthesis
        depth = 0
        for end in range(at, stop):
            depth += {"(": 1, ")": -1}.get(text[end], 0)
            if not depth:
                break
        end += 1
    else:
        end = text.find(" ", at, stop)
    return re.sub(r"\{[^{}]*\}|/\*[^*]*\*/", "", text[at:end])


def _instruction_scopes(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (result shape, op_name)} of a compiled module's
    text. The op_name is the instruction's own metadata ('' where it has
    none: a parameter, or what the compiler made itself); a fusion's line
    carries its root's. An instruction may run over several lines (a
    Mosaic call prints its `kernel_metadata` on lines of their own), so
    each runs from its `%name = ` to the next one's."""
    import re

    head = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ", re.M)
    scope = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')
    out: Dict[str, Tuple[str, str]] = {}
    heads = list(head.finditer(hlo_text))
    for m, nxt in zip(heads, heads[1:] + [None]):
        stop = len(hlo_text) if nxt is None else nxt.start()
        op = scope.search(hlo_text, m.end(), stop)
        out[m.group(1)] = (result_shape(hlo_text, m.end(), stop),
                           op.group(1) if op else "")
    return out


def scope_table() -> List[Tuple[str, Dict[str, Tuple[str, str]]]]:
    """Which named scope each instruction of the kept executables belongs
    to: one `(seam, {instruction name: (result shape, op_name)})` for
    every executable the cost cache holds, whatever its seam. A device
    trace names an event by its instruction
    (`%fusion.168 = s32[5500000]{0} fusion(...)`); the `op_name` of that
    instruction in the compiled module carries the program's
    `jax.named_scope`s (`jit(f)/tree.L4/route/...`), so the two joined by
    name give device time by scope with no trace file kept. The shape
    (layouts cut) tells apart a name that two executables both use.

    A map is built from `compiled.as_text()` the first time it is asked
    for and kept on the cache entry (the text itself, megabytes for a
    program that holds Mosaic kernels, is not); a dispatch pays nothing.
    Empty where `compiled_texts` is: profiler off, entry evicted, or the
    ahead-of-time compile fell back to plain jit."""
    with _cost_lock:
        entries = [(k[0], e) for k, e in _cost_cache.items()]
    out = []
    for seam, e in entries:
        if e.compiled is None:
            continue
        if e.scope_map is None:
            e.scope_map = _instruction_scopes(e.compiled.as_text())
        out.append((seam, e.scope_map))
    return out


def dispatch(name: str, fn: Callable, *args, sync: bool = True,
             static_argnums: Tuple[int, ...] = (),
             static_argnames: Tuple[str, ...] = (), **kwargs):
    """Run `fn(*args, **kwargs)` through the profiler under seam `name`.

    sync=True blocks on the result (accurate device wall-clock — use
    where the caller synchronizes right after anyway); sync=False leaves
    the dispatch asynchronous (streamed/overlapped seams) and flags the
    program `synced: false` in snapshots.

    This is also the `device` fault/retry seam: with a fault plan armed
    (-Dshifu.faults=device...) the whole dispatch runs under the
    `shifu.retry.device.*` budget — a jit program is pure, so re-running
    it on a transient runtime error is always safe. The guard keeps the
    unfaulted hot path free of the extra frame.
    """
    from shifu_tpu.resilience import faults as _faults

    if _faults.plan_active():
        from shifu_tpu.resilience import retry as _retry

        def _attempt():
            _faults.fault_point("device")
            return _dispatch_inner(name, fn, args, kwargs, sync,
                                   static_argnums, static_argnames)

        return _retry.retry_call(
            _attempt, seam="device",
            retryable=_retry.DEFAULT_TRANSIENT + (RuntimeError,))
    return _dispatch_inner(name, fn, args, kwargs, sync,
                           static_argnums, static_argnames)


def _dispatch_inner(name, fn, args, kwargs, sync,
                    static_argnums, static_argnames):
    if _mode() == "off":
        return fn(*args, **kwargs)
    try:
        dyn_args, dyn_kwargs, statics = _split_static(
            args, kwargs, tuple(static_argnums), tuple(static_argnames))
        sig = _signature(dyn_args, dyn_kwargs, statics)
    except Exception:  # unhashable/exotic signature -> unprofiled call
        sig = None
    if sig is None:  # tracer context or unhashable signature
        return fn(*args, **kwargs)
    # a process that goes straight to a trainer (no BasicProcessor.run)
    # still records which program traced, lowered and compiled
    jaxprobe.install()
    entry = _cost_entry(name, fn, sig, args, kwargs)
    scale = _current_scale()
    t0 = time.perf_counter()
    # annotation only, no ring event: this seam is per request in serve/
    # and per chunk in stats/
    with tracing.profiler_annotation(
            tracing.PROFILER_PREFIX + "prog." + name):
        if entry.compiled is not None:
            try:
                out = entry.compiled(*dyn_args, **dyn_kwargs)
            except (TypeError, ValueError):
                # AOT call convention mismatch: permanent per-entry fallback
                entry.compiled = None
                out = fn(*args, **kwargs)
        else:
            out = fn(*args, **kwargs)
        if sync:
            import jax

            out = jax.block_until_ready(out)
    _profiler.record_dispatch(name, entry, scale,
                              time.perf_counter() - t0, sync)
    return out


class ProfiledProgram:
    """Callable proxy a dispatch seam can cache in place of the raw jit
    object; attribute access passes through (``_cache_size`` probes in
    tests keep working)."""

    def __init__(self, name: str, fn: Callable, *, sync: bool = False,
                 static_argnums: Tuple[int, ...] = (),
                 static_argnames: Tuple[str, ...] = ()) -> None:
        self.profile_name = name
        self.fn = fn
        self.sync = sync
        self.static_argnums = tuple(static_argnums)
        self.static_argnames = tuple(static_argnames)

    def __call__(self, *args, **kwargs):
        return dispatch(self.profile_name, self.fn, *args,
                        sync=self.sync,
                        static_argnums=self.static_argnums,
                        static_argnames=self.static_argnames, **kwargs)

    def __getattr__(self, item):
        return getattr(self.fn, item)


def wrap(name: str, fn: Callable, *, sync: bool = False,
         static_argnums: Tuple[int, ...] = (),
         static_argnames: Tuple[str, ...] = ()) -> ProfiledProgram:
    return ProfiledProgram(name, fn, sync=sync,
                           static_argnums=static_argnums,
                           static_argnames=static_argnames)


# ---------------------------------------------------------------------------
# rendering + diffing (shared by `shifu profile` and `shifu runs --diff`;
# pure stdlib — the CLI paths must work without jax installed)
# ---------------------------------------------------------------------------


def _fmt_count(v: Optional[float]) -> str:
    if v is None:
        return "-"
    v = float(v)
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    if v != int(v):
        return f"{v:.4f}"
    return f"{v:.0f}"


def format_profile(manifest: dict) -> str:
    """Human per-program table for one manifest's profile section."""
    prof = manifest.get("profile") or {}
    programs = prof.get("programs") or {}
    head = (f"{manifest.get('step', '?')}-{manifest.get('seq', '?')} "
            f"[{manifest.get('status', '?')}]")
    chip = prof.get("chip") or {}
    if chip:
        head += (f"  chip={chip.get('name')} "
                 f"peak={chip.get('peakTflops')}TF/"
                 f"{chip.get('peakHbmGBs')}GBps ({chip.get('source')})")
    lines = [head]
    if not programs:
        lines.append("  (no profiled programs in this manifest)")
        return "\n".join(lines)
    lines.append(
        f"  {'PROGRAM':<24} {'DISP':>6} {'FLOPS':>9} {'BYTES':>9} "
        f"{'PEAK HBM':>9} {'COMPILE':>8} {'DEVICE':>8} {'TFLOP/s':>8} "
        f"{'MFU':>7} {'AI':>7} ROOFLINE")
    def _opt(v, spec):
        return "-" if v is None else format(v, spec)

    for name, p in programs.items():
        dev = (f"{p.get('deviceSeconds', 0.0):.3f}s"
               if p.get("synced") else
               f"~{p.get('dispatchSeconds', 0.0):.3f}s")
        lines.append(
            f"  {name:<24} {p.get('dispatches', 0):>6} "
            f"{_fmt_count(p.get('flops')):>9} "
            f"{_fmt_count(p.get('bytesAccessed')):>9} "
            f"{_fmt_count(p.get('peakHbmBytes')):>9} "
            f"{p.get('compileSeconds', 0.0):>7.3f}s {dev:>8} "
            f"{_opt(p.get('achievedTflops'), '.4f'):>8} "
            f"{_opt(p.get('mfu'), '.4f'):>7} "
            f"{_opt(p.get('arithmeticIntensity'), '.2f'):>7} "
            f"{p.get('roofline') or '-'}")
    tot = prof.get("totals") or {}
    if tot:
        lines.append(
            f"  {'TOTAL':<24} {tot.get('dispatches', 0):>6} "
            f"{_fmt_count(tot.get('flops')):>9} "
            f"{_fmt_count(tot.get('bytesAccessed')):>9} "
            f"{_fmt_count(tot.get('peakHbmBytes')):>9} "
            f"{tot.get('compileSeconds', 0.0):>7.3f}s "
            f"{tot.get('deviceSeconds', 0.0):>7.3f}s "
            f"{_opt(tot.get('achievedTflops'), '.4f'):>8} "
            f"{_opt(tot.get('mfu'), '.4f'):>7} "
            f"{_opt(tot.get('arithmeticIntensity'), '.2f'):>7} "
            f"{tot.get('roofline') or '-'}")
    return "\n".join(lines)


class DiffRow(dict):
    """One diffed key: {key, a, b, delta, pct, flag}."""


def _diff_rows(a: Dict[str, float], b: Dict[str, float]) -> List[DiffRow]:
    rows: List[DiffRow] = []
    for key in sorted(set(a) | set(b)):
        va, vb = a.get(key), b.get(key)
        if va is None:
            rows.append(DiffRow(key=key, a=None, b=vb, delta=None,
                                pct=None, flag="added"))
        elif vb is None:
            rows.append(DiffRow(key=key, a=va, b=None, delta=None,
                                pct=None, flag="removed"))
        elif va != vb:
            pct = ((vb - va) / abs(va) * 100.0) if va else None
            rows.append(DiffRow(key=key, a=va, b=vb, delta=vb - va,
                                pct=pct, flag="changed"))
    return rows


def render_diff(title: str, rows: List[DiffRow],
                breaches: Optional[List[str]] = None) -> str:
    """Shared diff table renderer (`shifu profile --diff`,
    `shifu runs --diff`)."""
    lines = [title]
    if not rows:
        lines.append("  (no differences)")
    else:
        lines.append(f"  {'KEY':<44} {'A':>12} {'B':>12} {'Δ':>12} "
                     f"{'Δ%':>8}  FLAG")
        for r in rows:
            pct = "-" if r["pct"] is None else f"{r['pct']:+.1f}%"
            lines.append(
                f"  {r['key']:<44} {_fmt_count(r['a']):>12} "
                f"{_fmt_count(r['b']):>12} {_fmt_count(r['delta']):>12} "
                f"{pct:>8}  {r['flag']}")
    for b in breaches or []:
        lines.append(f"  REGRESSION: {b}")
    return "\n".join(lines)


DIFF_DEFAULTS = {  # pct-increase gates; deterministic metrics only
    "flopsPct": 10.0,
    "bytesPct": 25.0,
    "hbmPct": 25.0,
    "secondsPct": 0.0,  # 0 = timing not gated (noisy by nature)
}


def diff_thresholds(overrides: Optional[dict] = None) -> dict:
    """DIFF_DEFAULTS <- -Dshifu.profile.diff.* <- explicit overrides."""
    from shifu_tpu.utils import environment

    th = dict(DIFF_DEFAULTS)
    for key in th:
        th[key] = environment.get_float(f"shifu.profile.diff.{key}",
                                        th[key])
    for key, val in (overrides or {}).items():
        if val is not None:
            th[key] = float(val)
    return th


def _per_unit(p: dict, field: str) -> Optional[float]:
    """Cost per unit of work: scaledDispatches when recorded (a
    `scaled(epochs)` trainer dispatch counts epochs units, so runs with
    different epoch counts still compare per loop body), else raw
    dispatch count (older/hand-built manifests)."""
    d = p.get("scaledDispatches") or p.get("dispatches") or 0
    v = p.get(field)
    if not d or v is None:
        return None
    return v / d


def diff_profiles(ma: dict, mb: dict,
                  thresholds: Optional[dict] = None
                  ) -> Tuple[List[DiffRow], List[str]]:
    """Program-by-program regression diff of two manifests' profile
    sections (A = baseline, B = candidate). Cost metrics compare per
    unit of work (scaled dispatches) so a run with more trees/epochs
    doesn't read as a per-program regression; breaches are pct increases
    beyond the thresholds."""
    th = diff_thresholds(thresholds)
    pa = (ma.get("profile") or {}).get("programs") or {}
    pb = (mb.get("profile") or {}).get("programs") or {}
    rows: List[DiffRow] = []
    breaches: List[str] = []
    gates = (("flops", "flopsPct"), ("bytesAccessed", "bytesPct"),
             ("peakHbmBytes", "hbmPct"), ("deviceSeconds", "secondsPct"))
    for name in sorted(set(pa) | set(pb)):
        a, b = pa.get(name), pb.get(name)
        if a is None or b is None:
            rows.append(DiffRow(key=name, a=None, b=None, delta=None,
                                pct=None,
                                flag="added" if a is None else "removed"))
            continue
        for field, gate in gates:
            if field == "peakHbmBytes":  # a high-water mark, not a sum
                va, vb = a.get(field), b.get(field)
            else:
                va, vb = _per_unit(a, field), _per_unit(b, field)
            if va is None and vb is None:
                continue
            if va != vb:
                pct = ((vb - va) / abs(va) * 100.0) if va else None
                rows.append(DiffRow(key=f"{name}.{field}/unit"
                                    if field != "peakHbmBytes"
                                    else f"{name}.{field}",
                                    a=va, b=vb,
                                    delta=None if None in (va, vb)
                                    else vb - va,
                                    pct=pct, flag="changed"))
                limit = th.get(gate, 0.0)
                if limit > 0.0 and pct is not None and pct > limit:
                    breaches.append(
                        f"{name}: {field} +{pct:.1f}% > {limit:.0f}% "
                        f"({_fmt_count(va)} -> {_fmt_count(vb)})")
        da, db = a.get("dispatches", 0), b.get("dispatches", 0)
        if da != db:
            rows.append(DiffRow(key=f"{name}.dispatches", a=da, b=db,
                                delta=db - da,
                                pct=(db - da) / da * 100.0 if da else None,
                                flag="changed"))
    return rows, breaches


def diff_metric_snapshots(ma: dict, mb: dict) -> List[DiffRow]:
    """Counters/gauges diff of two manifests (`shifu runs --diff`)."""
    rows: List[DiffRow] = []
    for kind in ("counters", "gauges"):
        a = (ma.get("metrics") or {}).get(kind) or {}
        b = (mb.get("metrics") or {}).get(kind) or {}
        for r in _diff_rows(a, b):
            r["key"] = f"{kind[:-1]}:{r['key']}"
            rows.append(r)
    return rows


def resolve_manifest(root: str, ident: str) -> dict:
    """Locate one run manifest: a JSON file path, a `<step>-<seq>` id
    under <root>/.shifu/runs, or a bare step name (newest run wins)."""
    import json
    import os

    from shifu_tpu.obs.ledger import list_runs, runs_dir

    if os.path.isfile(ident):
        with open(ident) as fh:
            m = json.load(fh)
        m["path"] = ident
        return m
    direct = os.path.join(runs_dir(root), f"{ident}.json")
    if os.path.isfile(direct):
        with open(direct) as fh:
            m = json.load(fh)
        m["path"] = direct
        return m
    runs = list_runs(root, step=ident, last=1)
    if runs:
        return runs[0]
    raise FileNotFoundError(
        f"no run manifest matches '{ident}' (tried a file path, "
        f"{direct}, and the newest '{ident}' step run)")
