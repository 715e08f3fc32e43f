"""Runtime sanitizer harness: ``-Dshifu.sanitize=transfer,nan,recompile,race``.

The static pass (engine.py) catches what the AST can see; this harness
catches what only the runtime can — the ASan/TSan analog for a jit
pipeline. Four opt-in modes, combined freely:

  transfer   arms ``jax.transfer_guard("disallow")`` around *declared
             traced stages* (the ``transfer_free(...)`` seams in
             nn_trainer / streaming / data.pipeline). Explicit
             ``jax.device_put``/``device_get`` stay legal; any IMPLICIT
             host↔device transfer inside a seam raises, the trip is
             recorded, and the step fails like a sanitizer trap. The
             guard is scoped to seams, not whole steps, because host→
             device staging (chunk feeds, scalar operand creation) is
             legitimate *between* traced stages.
  nan        arms ``jax.debug_nans`` for the step (the checkify-style
             trap): the first NaN/Inf produced under jit raises
             FloatingPointError at the producing primitive.
  recompile  a watchdog on the obs/jaxprobe compile counters: each armed
             stage gets a compile budget (``shifu.sanitize.recompileBudget``,
             default 64); a breach is recorded and logged as a ledger
             warning — recompile storms are a perf bug, not a
             correctness trap, so the step still completes.
  divergence multi-host lockstep witness (parallel/hostsync.py): every
             barrier part published while armed carries a stamp — a
             monotone per-(step, host) sequence id plus a digest of
             (config sha, barrier step, call-site, merge-key order).
             An awaiting peer that observes a mismatched digest or an
             out-of-order sequence raises DivergenceError LOUDLY
             instead of silently merging divergent state; the static
             counterpart is JX301/SH301/SH302 (rules/spmd.py).
             Single-process runs record per-window fold digests
             (data/pipeline.py flush), so a re-run can diff exactly
             which window broke determinism.
  race       lock instrumentation (analysis/racetrack.py): every
             ``tracked_lock(...)`` site constructed while armed records
             per-thread acquisition stacks; lock-order inversions and
             ``@guarded_by`` violations make the verdict unclean,
             long holds past ``shifu.sanitize.race.holdMs`` are
             reported (perf hazard, not gated). Arming is read at lock
             CONSTRUCTION time, so set ``-Dshifu.sanitize=race`` before
             building the serve/loop objects to be watched.

Verdicts: ``Sanitizer.verdict()`` returns a ``shifu.sanitize/1`` dict —
BasicProcessor.run() embeds it in the run-ledger manifest (success AND
failure). Trip/breach counts also land
in the metrics registry (``sanitizer.*``), so `shifu runs` output and
Prometheus exports see them too.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import traceback
from typing import Dict, Iterable, List, Optional, Sequence

from shifu_tpu.analysis.racetrack import tracked_lock
from shifu_tpu.utils import environment
from shifu_tpu.utils.log import get_logger

log = get_logger(__name__)

SCHEMA = "shifu.sanitize/1"
MODES = ("transfer", "nan", "recompile", "race", "divergence")
DEFAULT_RECOMPILE_BUDGET = 64
DEFAULT_MAX_FOLD_DIGESTS = 512


class DivergenceError(RuntimeError):
    """A hostsync barrier observed divergent peer state while
    -Dshifu.sanitize=divergence was armed: a peer's stamp digest did not
    match this host's (different config/call-site/merge-key order) or
    its barrier sequence was out of order. Raised INSTEAD of merging —
    a divergent merge would poison every downstream artifact silently;
    the refusal names the step, both hosts, and both digests."""

_lock = tracked_lock("analysis.sanitize")
_current: Optional["Sanitizer"] = None


def modes_from_environment() -> List[str]:
    """Parse -Dshifu.sanitize=transfer,nan,recompile (also accepts
    'all'); unknown mode names raise so a typo cannot silently disarm
    the run."""
    raw = (environment.get_property("shifu.sanitize", "") or "").strip()
    if not raw:
        return []
    if raw.lower() == "all":
        return list(MODES)
    modes = [m.strip().lower() for m in raw.split(",") if m.strip()]
    unknown = [m for m in modes if m not in MODES]
    if unknown:
        raise ValueError(
            f"shifu.sanitize: unknown mode(s) {', '.join(unknown)} "
            f"(known: {', '.join(MODES)})")
    return modes


def recompile_budget() -> int:
    return environment.get_int("shifu.sanitize.recompileBudget",
                               DEFAULT_RECOMPILE_BUDGET)


def max_fold_digests() -> int:
    """shifu.sanitize.divergence.maxFolds — cap on per-window fold
    digests kept for the verdict (a long stream would otherwise grow
    the manifest unboundedly; the digests past the cap still count)."""
    return environment.get_int("shifu.sanitize.divergence.maxFolds",
                               DEFAULT_MAX_FOLD_DIGESTS)


def _is_transfer_error(e: BaseException) -> bool:
    return "transfer" in str(e).lower() and "isallowed" in str(e)


def _barrier_call_site() -> str:
    """module:function of the nearest stack frame OUTSIDE the sanitizer/
    hostsync plumbing — the publish site whose identity the divergence
    digest pins. Deliberately not the line number: peers must agree on
    WHICH barrier they are at, while a trailing-whitespace edit between
    restarts must not read as divergence."""
    skip = ("sanitize.py", "hostsync.py")
    for frame in reversed(traceback.extract_stack()[:-1]):
        base = frame.filename.rsplit("/", 1)[-1]
        if base not in skip:
            return f"{base}:{frame.name}"
    return "?"


class Sanitizer:
    """One armed sanitizer scope (a lifecycle step or a bench scenario)."""

    def __init__(self, modes: Iterable[str],
                 budget: Optional[int] = None) -> None:
        self.modes = frozenset(modes)
        unknown = self.modes - set(MODES)
        if unknown:
            raise ValueError(f"unknown sanitizer mode(s): {sorted(unknown)}")
        self.budget = recompile_budget() if budget is None else budget
        self.transfer_trips = 0
        self.nan_trips = 0
        self.recompile_breaches = 0
        self.recompile_seconds = 0.0  # wall-clock of breached stages' compiles
        self.stages_armed = 0
        self.events: List[dict] = []
        # divergence-mode state: per-(step, host) barrier sequence
        # counters, published stamps, peer checks, and the single-host
        # fold-digest trail (all under _lock — thread-hosts share one
        # process-global sanitizer)
        self.divergence_trips = 0
        self.divergence_stamps = 0
        self.divergence_checks = 0
        self.fold_digests: List[dict] = []
        self.folds_recorded = 0
        self._barrier_seq: Dict[tuple, int] = {}
        self._max_folds = max_fold_digests()
        # race-mode scope: the verdict reports the tracker's DELTA from
        # this sanitizer's construction (the tracker itself is
        # process-global, like the fault-injection counters)
        from shifu_tpu.analysis import racetrack

        self._race_mark = racetrack.tracker().mark()

    @property
    def active(self) -> bool:
        return bool(self.modes)

    # ---- recording (also mirrored into the metrics registry so ledger
    # tables/Prometheus see sanitizer activity without parsing verdicts)
    def _record(self, kind: str, stage: str, detail: str) -> None:
        self.events.append({"kind": kind, "stage": stage,
                            "detail": detail})
        from shifu_tpu.obs import registry

        registry().counter(f"sanitizer.{kind}").inc()

    def record_transfer_trip(self, stage: str, detail: str) -> None:
        self.transfer_trips += 1
        self._record("transfer.trips", stage, detail)
        log.warning("sanitizer[transfer] trip in %s: %s", stage,
                    detail[:200])

    def record_nan_trip(self, stage: str, detail: str) -> None:
        self.nan_trips += 1
        self._record("nan.trips", stage, detail)
        log.warning("sanitizer[nan] trap in %s: %s", stage, detail[:200])

    def record_recompile_breach(self, stage: str, compiles: float,
                                seconds: float = 0.0) -> None:
        self.recompile_breaches += 1
        self.recompile_seconds += seconds
        self._record("recompile.breaches", stage,
                     f"{compiles:.0f} compiles ({seconds:.2f}s wall-clock)"
                     f" > budget {self.budget}")
        log.warning(
            "sanitizer[recompile] budget breach in %s: %.0f compiles "
            "costing %.2fs wall-clock > budget %d "
            "(shifu.sanitize.recompileBudget)", stage, compiles, seconds,
            self.budget)

    def record_divergence_trip(self, stage: str, detail: str) -> None:
        with _lock:
            self.divergence_trips += 1
        self._record("divergence.trips", stage, detail)
        log.warning("sanitizer[divergence] trip in %s: %s", stage,
                    detail[:300])

    # ---- divergence stamps (the hostsync barrier contract)
    def barrier_stamp(self, step: str, host_index: int, sha: str,
                      merge_keys: Sequence[str]) -> dict:
        """The stamp publish_part embeds while armed: a monotone
        per-(step, host) sequence id plus a digest of (config sha, step,
        publishing call-site, merge-key ORDER). Peers at the same
        barrier must compute the identical digest — anything else means
        the fleet is not running the same merge."""
        with _lock:
            key = (step, int(host_index))
            seq = self._barrier_seq.get(key, 0) + 1
            self._barrier_seq[key] = seq
            self.divergence_stamps += 1
        digest = hashlib.sha256(json.dumps({
            "configSha": sha,
            "step": step,
            "site": _barrier_call_site(),
            "mergeKeys": list(merge_keys),
        }, sort_keys=True).encode("utf-8")).hexdigest()[:16]
        from shifu_tpu.obs import registry

        registry().counter("sanitizer.divergence.stamps",
                           step=step).inc()
        return {"seq": seq, "digest": digest}

    def check_barrier_stamps(self, step: str, own_host: int,
                             own_stamp: Optional[dict],
                             peer_stamps: Dict[int, Optional[dict]]
                             ) -> None:
        """Validate every peer's stamp against this host's at an
        await_parts barrier. Raises DivergenceError on the first
        mismatch — the named refusal that replaces a silent merge of
        divergent state."""
        from shifu_tpu.obs import registry

        registry().counter("sanitizer.divergence.checks",
                           step=step).inc()
        with _lock:
            self.divergence_checks += 1
        if own_stamp is None:
            return  # this host published unarmed (stamp-free stream)
        for host, stamp in sorted(peer_stamps.items()):
            if host == own_host:
                continue
            problem = None
            if stamp is None:
                problem = ("peer published NO divergence stamp — fleet "
                           "is not uniformly armed")
            elif stamp.get("digest") != own_stamp.get("digest"):
                problem = (f"digest mismatch: peer {stamp.get('digest')}"
                           f" != own {own_stamp.get('digest')} (config "
                           f"sha, call-site or merge-key order differs)")
            elif stamp.get("seq") != own_stamp.get("seq"):
                problem = (f"out-of-order barrier sequence: peer "
                           f"{stamp.get('seq')} != own "
                           f"{own_stamp.get('seq')}")
            if problem:
                detail = (f"barrier '{step}': host {host} diverged from "
                          f"host {own_host} — {problem}")
                self.record_divergence_trip(step, detail)
                raise DivergenceError(
                    f"sanitizer[divergence] {detail}; refusing to merge"
                    f" (the verdict rides the run manifest)")

    def record_fold(self, stage: str, arrays) -> None:
        """Single-process determinism trail: digest one window fold so a
        re-run can diff exactly where the fold stream diverged."""
        h = hashlib.sha256()
        for a in arrays:
            import numpy as np

            h.update(np.ascontiguousarray(a).tobytes())
        with _lock:
            self.folds_recorded += 1
            seq = self.folds_recorded
            if len(self.fold_digests) < self._max_folds:
                self.fold_digests.append(
                    {"stage": stage, "seq": seq,
                     "digest": h.hexdigest()[:16]})
        from shifu_tpu.obs import registry

        registry().counter("sanitizer.divergence.folds",
                           stage=stage).inc()

    # ---- arming
    @contextlib.contextmanager
    def armed(self, stage: str):
        """Arm the step-scoped modes around `stage`: debug_nans for the
        whole region, the recompile watchdog over its compile-counter
        delta. Transfer guarding happens at the finer transfer_free()
        seams inside. Exceptions propagate (sanitizer-trap semantics) —
        trips are recorded first, and the caller's ledger write still
        sees the verdict because it runs in its own finally."""
        if not self.active:
            yield
            return
        self.stages_armed += 1
        compiles0 = self._compile_count()
        seconds0 = self._compile_seconds()
        nan_cm = contextlib.nullcontext()
        if "nan" in self.modes:
            import jax

            nan_cm = jax.debug_nans(True)
        try:
            with nan_cm:
                yield
        except FloatingPointError as e:
            if "nan" in self.modes:
                self.record_nan_trip(stage, f"{type(e).__name__}: {e}")
            raise
        finally:
            if "recompile" in self.modes:
                delta = self._compile_count() - compiles0
                if delta > self.budget:
                    # the jaxprobe duration events make the breach
                    # actionable: N compiles AND the wall-clock they cost
                    self.record_recompile_breach(
                        stage, delta,
                        self._compile_seconds() - seconds0)

    @contextlib.contextmanager
    def transfer_free(self, stage: str):
        """Declare a region transfer-free. Under the `transfer` mode any
        implicit host↔device transfer inside raises (explicit
        device_put/device_get remain legal); the trip is recorded and
        the error propagates."""
        if "transfer" not in self.modes:
            yield
            return
        import jax

        try:
            with jax.transfer_guard("disallow"):
                yield
        except Exception as e:
            if _is_transfer_error(e):
                self.record_transfer_trip(stage, str(e))
            raise

    # ---- verdict
    def verdict(self) -> dict:
        from shifu_tpu.analysis import racetrack

        race_armed = "race" in self.modes
        race = {"armed": race_armed}
        race_dirty = 0
        if race_armed:
            race.update(racetrack.tracker().verdict(self._race_mark))
            # inversions + guard violations are correctness findings;
            # long holds are a perf hazard — reported, never gating
            # `clean` (the recompile-watchdog contract)
            race_dirty = race["inversions"] + race["guardViolations"]
        return {
            "schema": SCHEMA,
            "modes": sorted(self.modes),
            "stagesArmed": self.stages_armed,
            "transfer": {
                "armed": "transfer" in self.modes,
                "trips": self.transfer_trips,
            },
            "nan": {
                "armed": "nan" in self.modes,
                "trips": self.nan_trips,
            },
            "recompile": {
                "armed": "recompile" in self.modes,
                "budgetPerStage": self.budget,
                "breaches": self.recompile_breaches,
                "breachedCompileSeconds": round(self.recompile_seconds, 3),
            },
            "race": race,
            "divergence": {
                "armed": "divergence" in self.modes,
                "trips": self.divergence_trips,
                "stampsPublished": self.divergence_stamps,
                "barriersChecked": self.divergence_checks,
                "foldsRecorded": self.folds_recorded,
                "foldDigests": list(self.fold_digests),
            },
            "events": self.events,
            "clean": not (self.transfer_trips or self.nan_trips
                          or self.recompile_breaches or race_dirty
                          or self.divergence_trips),
        }

    @staticmethod
    def _compile_count() -> float:
        from shifu_tpu import obs

        obs.install_jax_probes()
        return obs.registry().counter("jax.compiles").value

    @staticmethod
    def _compile_seconds() -> float:
        from shifu_tpu import obs

        obs.install_jax_probes()
        return obs.registry().timer("jax.compile").seconds


def from_environment() -> Sanitizer:
    return Sanitizer(modes_from_environment())


def current() -> Optional[Sanitizer]:
    return _current


@contextlib.contextmanager
def activate(san: Sanitizer):
    """Make `san` the process-current sanitizer so library seams
    (transfer_free below) find it without plumbing. Nested activation
    restores the previous one on exit."""
    global _current
    with _lock:
        prev, _current = _current, san
    try:
        yield san
    finally:
        with _lock:
            _current = prev


@contextlib.contextmanager
def transfer_free(stage: str):
    """Library-side seam: no-op unless a sanitizer with the `transfer`
    mode is active. Cheap enough for per-dispatch call sites (one global
    read when disarmed)."""
    san = _current
    if san is None or "transfer" not in san.modes:
        yield
        return
    with san.transfer_free(stage):
        yield


def _divergence_active() -> Optional[Sanitizer]:
    san = _current
    if san is not None and "divergence" in san.modes:
        return san
    return None


def barrier_stamp(step: str, host_index: int, sha: str,
                  merge_keys: Sequence[str]) -> Optional[dict]:
    """hostsync.publish_part seam: the stamp to embed in the part
    header, or None when divergence is disarmed (one global read)."""
    san = _divergence_active()
    if san is None:
        return None
    return san.barrier_stamp(step, host_index, sha, merge_keys)


def check_barrier_stamps(step: str, own_host: int,
                         own_stamp: Optional[dict],
                         peer_stamps: Dict[int, Optional[dict]]) -> None:
    """hostsync.await_parts seam: validate peers before the merge;
    raises DivergenceError on mismatch, no-op when disarmed."""
    san = _divergence_active()
    if san is None:
        return
    san.check_barrier_stamps(step, own_host, own_stamp, peer_stamps)


def record_fold(stage: str, arrays) -> None:
    """data-pipeline seam: digest one window fold while armed (no-op
    otherwise) — the single-process determinism trail."""
    san = _divergence_active()
    if san is not None:
        san.record_fold(stage, arrays)
