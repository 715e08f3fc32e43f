"""Continuous-loop subsystem (shifu_tpu/loop/): traffic log, online PSI
drift, zero-downtime hot-swap with shadow scoring, promote gating, and
`shifu retrain` warm-start provenance + chaos parity.

The acceptance pins live here: unshifted replay stays under PSI 0.05
while covariate-shifted replay crosses 0.2 and degrades /healthz with a
ledger recommendation; a hot-swap under concurrent load answers every
request (counted per version, zero lost); a retrain killed mid-stream
resumes bit-identical to an uninterrupted one.
"""

import glob
import json
import os
import shutil
import threading

import numpy as np
import pytest

from shifu_tpu.utils import environment
from tests.helpers import make_binary_dataset, make_model_set


class _Props:
    """Env-property overrides for one test, restored on exit."""

    def __init__(self, **props):
        self.props = {k.replace("_", "."): v for k, v in props.items()}

    def __enter__(self):
        for k, v in self.props.items():
            environment.set_property(k, v)
        return self

    def __exit__(self, *exc):
        for k in self.props:
            environment.set_property(k, "")


def _counter_delta(before, after, prefix):
    """Per-key counter deltas for keys starting with `prefix`."""
    out = {}
    for k, v in after.items():
        if k.startswith(prefix):
            d = v - before.get(k, 0.0)
            if d:
                out[k] = d
    return out


def _snapshot_counters():
    from shifu_tpu import obs

    return dict(obs.registry().snapshot().get("counters", {}))


@pytest.fixture(scope="module")
def model_set(tmp_path_factory):
    """One trained NN model set for the whole module (stats bins + counts
    feed the drift baseline; models feed serve/hot-swap/retrain)."""
    from shifu_tpu.processor.init import InitProcessor
    from shifu_tpu.processor.norm import NormProcessor
    from shifu_tpu.processor.stats import StatsProcessor
    from shifu_tpu.processor.train import TrainProcessor

    root = str(tmp_path_factory.mktemp("loop_ms"))
    make_model_set(root, n_rows=400)
    mcp = os.path.join(root, "ModelConfig.json")
    mc = json.load(open(mcp))
    mc["train"]["numTrainEpochs"] = 12
    json.dump(mc, open(mcp, "w"), indent=2)
    assert InitProcessor(root).run() == 0
    assert StatsProcessor(root).run() == 0
    assert NormProcessor(root).run() == 0
    assert TrainProcessor(root).run() == 0
    return root


@pytest.fixture()
def column_configs(model_set):
    from shifu_tpu.config import load_column_config_list

    return load_column_config_list(
        os.path.join(model_set, "ColumnConfig.json"))


def _raw_batch(names, rows):
    from shifu_tpu.serve.registry import records_to_columnar

    return records_to_columnar([dict(zip(names, r)) for r in rows], names)


def _training_raw(model_set):
    from shifu_tpu.data.reader import read_columnar, read_header

    names = read_header(os.path.join(model_set, "data", "header.txt"))
    return read_columnar(os.path.join(model_set, "data", "data.txt"),
                         names)


# ---------------------------------------------------------------------------
# traffic log
# ---------------------------------------------------------------------------


class _FakeResult:
    def __init__(self, n):
        self.mean = np.linspace(100.0, 900.0, n)


def _fake_data(names, n, fill="1.5"):
    from shifu_tpu.serve.registry import records_to_columnar

    return records_to_columnar([{c: fill for c in names}] * n, names)


class TestTrafficLog:
    NAMES = ["a", "b"]

    def test_rotation_flush_and_meta(self, tmp_path):
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        log = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                         sample=1.0, chunk_rows=10)
        for _ in range(3):
            log.record(_fake_data(self.NAMES, 7), _FakeResult(7), "sha0")
        # the buffer rotates into a whole chunk file when it reaches
        # chunk_rows (14 >= 10 after batch 2); batch 3 stays buffered
        chunks = sorted(glob.glob(
            str(tmp_path / ".shifu/runs/traffic/traffic-*.psv")))
        assert len(chunks) == 1
        log.flush()
        chunks = sorted(glob.glob(
            str(tmp_path / ".shifu/runs/traffic/traffic-*.psv")))
        assert len(chunks) == 2
        rows = sum(1 for p in chunks for _ in open(p))
        assert rows == 21
        meta = json.load(open(
            tmp_path / ".shifu/runs/traffic/_meta.json"))
        assert meta["schema"] == "shifu.traffic/1"
        assert meta["columns"][-4:] == ["shifu_score_mean",
                                        "shifu_model_sha", "shifu_trace",
                                        "shifu_ts"]

    def test_seq_grows_across_restart(self, tmp_path):
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        a = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                       sample=1.0, chunk_rows=4)
        a.record(_fake_data(self.NAMES, 4), _FakeResult(4), "s")
        a.close()
        b = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                       sample=1.0, chunk_rows=4)
        b.record(_fake_data(self.NAMES, 4), _FakeResult(4), "s")
        b.close()
        names = sorted(os.path.basename(p) for p in glob.glob(
            str(tmp_path / ".shifu/runs/traffic/traffic-*.psv")))
        assert names == ["traffic-00001.psv", "traffic-00002.psv"]

    def test_sampling_is_deterministic(self, tmp_path):
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        kept = []
        for sub in ("x", "y"):
            log = TrafficLog(str(tmp_path / sub),
                             traffic_columns(self.NAMES),
                             sample=0.5, chunk_rows=1000, seed=3)
            n = sum(log.record(_fake_data(self.NAMES, 50),
                               _FakeResult(50), "s") for _ in range(4))
            log.flush()
            kept.append(n)
        assert kept[0] == kept[1]
        files = [sorted(glob.glob(str(tmp_path / sub /
                                      ".shifu/runs/traffic/*.psv")))
                 for sub in ("x", "y")]

        def rows_sans_ts(paths):
            # the trailing field is wall-clock: strip it before comparing
            return [line.rsplit("|", 1)[0]
                    for p in paths for line in open(p)]

        assert rows_sans_ts(files[0]) == rows_sans_ts(files[1])

    def test_delimiter_and_newline_sanitized(self, tmp_path):
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        log = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                         sample=1.0, chunk_rows=1)
        log.record(_fake_data(self.NAMES, 1, fill="bad|val\nue"),
                   _FakeResult(1), "s")
        (path,) = glob.glob(str(tmp_path / ".shifu/runs/traffic/*.psv"))
        line = open(path).read().rstrip("\n")
        # 2 feature fields + score + sha + trace + ts = exactly 6 fields
        assert len(line.split("|")) == 6
        assert "bad;val ue" in line

    def test_readback_is_an_ordinary_chunk_stream(self, tmp_path):
        from shifu_tpu.loop.traffic import (
            TrafficLog,
            traffic_columns,
            traffic_source,
        )

        log = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                         sample=1.0, chunk_rows=8)
        for _ in range(3):
            log.record(_fake_data(self.NAMES, 5), _FakeResult(5), "sha9")
        log.close()
        factory, names = traffic_source(str(tmp_path))
        assert names[:2] == self.NAMES
        chunks = list(factory())
        total = sum(c.n_rows for c in chunks)
        assert total == 15
        first = chunks[0]
        assert list(first.column("shifu_model_sha"))[0] == "sha9"
        # scores parse back numerically
        assert np.isfinite(first.numeric("shifu_score_mean")).all()

    def test_snapshot_counts_only_this_runs_chunks(self, tmp_path):
        """The manifest's chunk count is per-replica accounting: a
        restarted server must not claim the chunks a previous run left
        on disk (the seq counter DOES continue across restarts)."""
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        a = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                       sample=1.0, chunk_rows=4)
        a.record(_fake_data(self.NAMES, 4), _FakeResult(4), "s")
        a.close()
        assert a.snapshot()["chunks"] == 1
        b = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                       sample=1.0, chunk_rows=4)
        assert b.snapshot()["chunks"] == 0
        b.record(_fake_data(self.NAMES, 4), _FakeResult(4), "s")
        b.close()
        assert b.snapshot()["chunks"] == 1

    def test_schema_change_retires_old_chunks(self, tmp_path):
        """A restart with a different column schema must not rewrite
        _meta.json over chunks framed with the old one — old rows would
        parse misaligned into the new columns and retrain on garbage.
        The old log retires wholesale to a superseded subdir."""
        from shifu_tpu.loop.traffic import (
            TrafficLog,
            list_chunks,
            traffic_columns,
            traffic_dir,
            traffic_source,
        )

        a = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                       sample=1.0, chunk_rows=4)
        a.record(_fake_data(self.NAMES, 4), _FakeResult(4), "s")
        a.close()
        assert len(list_chunks(str(tmp_path))) == 1
        new_cols = traffic_columns(self.NAMES + ["extra_col"])
        b = TrafficLog(str(tmp_path), new_cols, sample=1.0, chunk_rows=4)
        # active dir holds ONLY the new schema; old files retired intact
        assert list_chunks(str(tmp_path)) == []
        retired = os.path.join(traffic_dir(str(tmp_path)), "superseded-1")
        assert len(glob.glob(os.path.join(retired, "traffic-*.psv"))) == 1
        assert os.path.isfile(os.path.join(retired, "_meta.json"))
        b.record(_fake_data(self.NAMES + ["extra_col"], 4),
                 _FakeResult(4), "s")
        b.close()
        _factory, names = traffic_source(str(tmp_path))
        assert names == new_cols  # readback sees one coherent schema
        # matching-schema restart still keeps everything (no retirement)
        c = TrafficLog(str(tmp_path), new_cols, sample=1.0, chunk_rows=4)
        c.record(_fake_data(self.NAMES + ["extra_col"], 4),
                 _FakeResult(4), "s")
        c.close()
        assert len(list_chunks(str(tmp_path))) == 2

    def test_readback_without_log_raises(self, tmp_path):
        from shifu_tpu.loop.traffic import traffic_source

        with pytest.raises(FileNotFoundError):
            traffic_source(str(tmp_path))


class TestFleetTrafficLog:
    """ISSUE-18 fleet sharing: N serve processes append to ONE log under
    their (sanitized) lease ids; consumers read the union."""

    NAMES = ["a", "b"]

    def _log(self, root, writer, n=4):
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        log = TrafficLog(str(root), traffic_columns(self.NAMES),
                         sample=1.0, chunk_rows=4, writer=writer)
        log.record(_fake_data(self.NAMES, n), _FakeResult(n), "sha0")
        log.close()
        return log

    def test_writer_id_sanitizes_and_never_parses_as_seq(self):
        from shifu_tpu.loop.traffic import _CHUNK_RE, writer_id

        # lease ids are host-pid-token (resilience/lease.py)
        wid = writer_id("box.example-4242-deadbeef")
        assert wid == "box_example_4242_deadbeef"
        for raw in ("12345", "", "007-x"):
            wid = writer_id(raw)
            m = _CHUNK_RE.match(f"traffic-{wid}-00001.psv")
            assert m and m.group(1) == wid, (raw, wid)

    def test_union_in_seq_then_writer_order_and_scope_filter(
            self, tmp_path):
        from shifu_tpu.loop.traffic import (
            chunk_writer,
            list_chunks,
            list_writers,
        )

        self._log(tmp_path, "hostB_1_aa")
        self._log(tmp_path, "hostA_2_bb")
        self._log(tmp_path, "hostA_2_bb", n=4)  # second chunk, seq 2
        union = [os.path.basename(p) for p in list_chunks(str(tmp_path))]
        assert union == ["traffic-hostA_2_bb-00001.psv",
                         "traffic-hostB_1_aa-00001.psv",
                         "traffic-hostA_2_bb-00002.psv"]
        assert list_writers(str(tmp_path)) == ["hostA_2_bb",
                                               "hostB_1_aa"]
        only_a = list_chunks(str(tmp_path), scope="hostA_2_bb")
        assert [chunk_writer(p) for p in only_a] == ["hostA_2_bb"] * 2

    def test_per_writer_sequences_are_independent(self, tmp_path):
        """Two processes appending concurrently never race on a shared
        sequence: each writer numbers its OWN chunks, and a restart
        resumes after its own highest seq, ignoring the peer's."""
        self._log(tmp_path, "w1")
        self._log(tmp_path, "w2")
        self._log(tmp_path, "w1")  # restart of writer 1
        names = sorted(os.path.basename(p) for p in glob.glob(
            str(tmp_path / ".shifu/runs/traffic/traffic-*.psv")))
        assert names == ["traffic-w1-00001.psv", "traffic-w1-00002.psv",
                         "traffic-w2-00001.psv"]

    def test_set_writer_rebases_sequence_post_lease(self, tmp_path):
        """The server names its writer only after the lease grant;
        set_writer on a live log must re-derive the next seq from the
        new writer's own chunks."""
        from shifu_tpu.loop.traffic import TrafficLog, traffic_columns

        self._log(tmp_path, "lease1")  # pre-existing chunk of lease1
        log = TrafficLog(str(tmp_path), traffic_columns(self.NAMES),
                         sample=1.0, chunk_rows=4)
        log.set_writer("lease1")
        log.record(_fake_data(self.NAMES, 4), _FakeResult(4), "s")
        log.close()
        assert log.snapshot()["writer"] == "lease1"
        names = sorted(os.path.basename(p) for p in glob.glob(
            str(tmp_path / ".shifu/runs/traffic/traffic-*.psv")))
        assert names == ["traffic-lease1-00001.psv",
                         "traffic-lease1-00002.psv"]

    def test_readback_unions_all_writers(self, tmp_path):
        from shifu_tpu.loop.traffic import traffic_source

        self._log(tmp_path, "w1")
        self._log(tmp_path, "w2")
        factory, _names = traffic_source(str(tmp_path))
        rows = sum(c.n_rows for c in factory())
        assert rows == 8
        solo, _ = traffic_source(str(tmp_path), scope="w2")
        assert sum(c.n_rows for c in solo()) == 4


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------


class TestDriftMonitor:
    def test_unshifted_replay_stays_quiet(self, model_set, column_configs):
        from shifu_tpu.loop.drift import DriftMonitor

        mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        assert mon.enabled
        mon.fold_host(_training_raw(model_set))
        v = mon.verdict()
        assert v["status"] == "ok"
        # replaying the training distribution itself: everything quiet
        assert v["maxPsi"] < 0.05, v["psi"]

    def test_shifted_replay_crosses_threshold_and_degrades(
            self, model_set, column_configs, tmp_path):
        from shifu_tpu.loop.drift import DriftMonitor
        from shifu_tpu.serve.health import HealthMonitor

        names, rows, _ = make_binary_dataset(n_rows=400, seed=21)
        shifted = []
        for r in rows:
            r = list(r)
            # covariate shift: num_0 (field 1) scaled + offset far out of
            # its training bins
            try:
                r[1] = f"{float(r[1]) * 4.0 + 25.0:.6g}"
            except ValueError:
                pass
            shifted.append(r)
        mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        mon.fold_host(_raw_batch(names, shifted))
        health = HealthMonitor()
        ledger_root = str(tmp_path)
        v = mon.check_degrade(health, ledger_root, model_sha="abc123")
        assert v is not None and v["status"] == "drift"
        assert "num_0" in v["driftedColumns"]
        assert v["psi"]["num_0"] > 0.2
        assert health.snapshot()["status"] == "degraded"
        # exactly ONE machine-readable recommendation manifest
        recs = glob.glob(os.path.join(ledger_root,
                                      ".shifu/runs/recommend-*.json"))
        assert len(recs) == 1
        rec = json.load(open(recs[0]))["recommendation"]
        assert rec["action"] == "retrain"
        assert rec["modelSetSha"] == "abc123"
        assert "num_0" in rec["drift"]["driftedColumns"]
        # a second breach on the same columns stamps no second manifest
        mon.check_degrade(health, ledger_root, model_sha="abc123")
        assert len(glob.glob(os.path.join(
            ledger_root, ".shifu/runs/recommend-*.json"))) == 1

    def test_reset_mid_flush_drops_old_window_counts(
            self, model_set, column_configs, monkeypatch):
        """A promotion reset() landing while a window flush is between
        its swap (under the lock) and its merge-back must DROP the old
        version's counts instead of resurrecting them into the zeroed
        host fold — the new version's PSI must start from a clean
        slate."""
        import jax
        import jax.numpy as jnp

        from shifu_tpu.loop.drift import DriftMonitor

        mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        assert mon.enabled
        mon.note_window(jnp.ones(mon.total_slots, jnp.float32), 8)
        real_get = jax.device_get
        fired = []

        def reset_then_get(x):
            if not fired:
                fired.append(1)
                mon.reset()  # the promotion, exactly mid-flush
            return real_get(x)

        monkeypatch.setattr(jax, "device_get", reset_then_get)
        mon._flush()
        assert fired
        assert float(mon._host.sum()) == 0.0  # old counts dropped
        # and post-reset traffic still folds normally
        monkeypatch.setattr(jax, "device_get", real_get)
        mon.note_window(jnp.ones(mon.total_slots, jnp.float32), 8)
        mon._flush()
        assert float(mon._host.sum()) == float(mon.total_slots)
        # the fold-ADOPTION path is guarded the same way: a window read
        # before the reset must not be adopted after it (the registry
        # passes window()'s generation back through note_window)
        _w, gen = mon.window()
        mon.reset()
        mon.note_window(jnp.full(mon.total_slots, 7.0, jnp.float32), 8,
                        gen=gen)
        assert mon._rows == 0 and not mon._windows  # stale: dropped
        w, gen = mon.window()
        mon.note_window(w + 1.0, 8, gen=gen)  # current gen: adopted
        mon._flush()
        assert float(mon._host.sum()) == float(mon.total_slots)
        # fleet-PR regression: a fold whose BASE window a concurrent
        # flush already merged must be DROPPED (its token's flush epoch
        # is stale) — adopting base+delta would double-count the base
        # into the next flush (the N-replica worker interleave)
        w, tok = mon.window()
        mon.note_window(w + 1.0, 8, gen=tok)
        mon._flush()          # merges w+1.0; bumps the key's epoch
        before = float(mon._host.sum())
        mon.note_window(w + 1.0, 8, gen=tok)  # stale epoch: dropped
        mon._flush()
        assert float(mon._host.sum()) == before
        # and a fresh token folds normally again
        w, tok = mon.window()
        mon.note_window(w + 1.0, 8, gen=tok)
        mon._flush()
        assert float(mon._host.sum()) == before + float(mon.total_slots)

    def test_reset_reopens_the_degrade_loop(self, model_set,
                                            column_configs, tmp_path):
        """After a promote acts on the recommendation, reset() clears the
        monitor so drift on the NEW version's traffic degrades and
        recommends AGAIN — the closed loop closes more than once."""
        from shifu_tpu.loop.drift import DriftMonitor
        from shifu_tpu.serve.health import HealthMonitor

        names, rows, _ = make_binary_dataset(n_rows=400, seed=22)
        shifted = []
        for r in rows:
            r = list(r)
            try:
                r[1] = f"{float(r[1]) * 4.0 + 25.0:.6g}"
            except ValueError:
                pass
            shifted.append(r)
        mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        health = HealthMonitor()
        ledger_root = str(tmp_path)
        mon.fold_host(_raw_batch(names, shifted))
        assert mon.check_degrade(health, ledger_root,
                                 model_sha="v1")["status"] == "drift"
        # promote path: recommendation acted on — health clears, monitor
        # resets (what ScoringServer.promote_candidate does)
        health.clear_degraded()
        mon.reset()
        assert health.snapshot()["status"] == "ok"
        assert mon.verdict()["rows"] == 0
        # the new version drifts too: re-degrades + SECOND recommendation
        mon.fold_host(_raw_batch(names, shifted))
        v = mon.check_degrade(health, ledger_root, model_sha="v2")
        assert v["status"] == "drift"
        assert health.snapshot()["status"] == "degraded"
        recs = sorted(glob.glob(os.path.join(
            ledger_root, ".shifu/runs/recommend-*.json")))
        assert len(recs) == 2
        assert json.load(open(recs[1]))["recommendation"][
            "modelSetSha"] == "v2"

    def test_clear_degraded_spares_crash_degrades(self):
        """A promote clears the STICKY (drift) degrade only: scoring
        crashes degrade through their own hysteresis, and routing full
        traffic back onto a still-crashing replica because an unrelated
        promote landed would be wrong."""
        from shifu_tpu.serve.health import HealthMonitor

        h = HealthMonitor()
        h.note_crash("worker died")
        assert h.snapshot()["status"] == "degraded"
        h.clear_degraded()  # promote acts on drift, not on crashes
        assert h.snapshot()["status"] == "degraded"
        # a PURE drift degrade (no crash underneath) DOES clear
        h2 = HealthMonitor()
        h2.note_degraded("psi over threshold")
        h2.clear_degraded()
        assert h2.snapshot()["status"] == "ok"

    def test_clear_degraded_keeps_layered_crash_degrade(self):
        """Crash degrade + drift degrade can LAYER; promoting away the
        drift must leave the crash degrade (and its clean-batch
        hysteresis) underneath."""
        from shifu_tpu.serve.health import HealthMonitor

        h = HealthMonitor(ok_after=2)
        h.note_crash("worker died")
        h.note_degraded("psi over threshold")
        h.clear_degraded()  # promote acted on the drift only
        snap = h.snapshot()
        assert snap["status"] == "degraded"
        assert snap["reason"] == "worker died"  # crash cause restored
        h.note_ok()
        h.note_ok()  # hysteresis resumes and heals the crash degrade
        assert h.snapshot()["status"] == "ok"

    def test_check_degrade_returns_verdict_when_quiet(
            self, model_set, column_configs):
        """One verdict computation per cadence: the quiet path hands the
        verdict back instead of None, so callers never call verdict()
        a second time."""
        from shifu_tpu.loop.drift import DriftMonitor

        mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        mon.fold_host(_training_raw(model_set))
        v = mon.check_degrade()
        assert v is not None and v["status"] == "ok"

    def test_warming_below_min_rows_never_degrades(self, column_configs):
        from shifu_tpu.loop.drift import DriftMonitor

        mon = DriftMonitor(column_configs, threshold=0.0, min_rows=10_000)
        names, rows, _ = make_binary_dataset(n_rows=50, seed=33)
        mon.fold_host(_raw_batch(names, rows))
        v = mon.verdict()
        assert v["status"] == "warming"
        assert v["driftedColumns"] == []
        assert mon.check_degrade() is None or v["status"] != "drift"

    def test_fused_fold_matches_host_fold(self, model_set, column_configs):
        """The traced in-program fold and the host fallback fold must
        agree bin-for-bin — one drift definition, two execution paths."""
        from shifu_tpu.loop.drift import DriftMonitor
        from shifu_tpu.serve.registry import ModelRegistry

        fused_mon = DriftMonitor(column_configs, threshold=0.2,
                                 min_rows=64)
        reg = ModelRegistry(os.path.join(model_set, "models"),
                            drift=fused_mon)
        assert reg.fused
        raw = _training_raw(model_set)
        reg.score_raw(raw)
        host_mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        host_mon.fold_host(raw)
        a = fused_mon.psi_by_column()
        b = host_mon.psi_by_column()
        assert set(a) == set(b)
        for k in a:
            assert a[k] == pytest.approx(b[k], abs=1e-9), k
        # and the raw counts themselves are identical
        assert np.array_equal(fused_mon._host, host_mon._host)

    def test_warm_does_not_pollute_drift_window(self, model_set,
                                                column_configs):
        """Startup warm-up scores synthetic all-"0" rows; they are not
        traffic and must fold NOTHING into the drift monitor — else they
        burn the min-rows warm-up and skew the PSI baseline."""
        from shifu_tpu.loop.drift import DriftMonitor
        from shifu_tpu.serve.registry import ModelRegistry

        mon = DriftMonitor(column_configs, threshold=0.2, min_rows=64)
        reg = ModelRegistry(os.path.join(model_set, "models"), drift=mon)
        reg.warm([1, 16])
        assert mon.verdict()["rows"] == 0
        assert reg.drift_live  # restored for real traffic
        reg.score_raw(_training_raw(model_set))
        assert mon.verdict()["rows"] > 0

    def test_column_whose_counts_mismatch_is_not_monitored(
            self, column_configs):
        import copy

        from shifu_tpu.loop.drift import DriftMonitor

        ccs = copy.deepcopy(column_configs)
        victim = next(c for c in ccs
                      if c.column_binning.bin_boundary
                      and c.column_binning.bin_count_pos)
        victim.column_binning.bin_count_pos = [1, 2]  # wrong arity
        victim.column_binning.bin_count_neg = [1, 2]
        mon = DriftMonitor(ccs, threshold=0.2, min_rows=1)
        assert victim.column_name not in [c.name for c in mon.cols]


# ---------------------------------------------------------------------------
# hot-swap + shadow scoring
# ---------------------------------------------------------------------------


def _perturbed_candidate(model_set, tmp_path, delta=1e-3):
    """A candidate dir whose single NN model differs slightly (new sha,
    near-identical scores)."""
    from shifu_tpu.models.nn import NNModelSpec

    cand = str(tmp_path / "candidate")
    os.makedirs(cand, exist_ok=True)
    spec = NNModelSpec.load(os.path.join(model_set, "models", "model0.nn"))
    spec.params[-1]["b"] = np.asarray(spec.params[-1]["b"]) + delta
    spec.save(os.path.join(cand, "model0.nn"))
    return cand


class TestHotSwap:
    def test_stage_shadow_agree_promote(self, model_set, tmp_path):
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.registry import ModelRegistry

        with _Props(shifu_loop_shadowSample="1.0"):
            sw = SwappableRegistry(
                ModelRegistry(os.path.join(model_set, "models")))
            old_sha = sw.sha
            cand = _perturbed_candidate(model_set, tmp_path)
            staged = sw.stage(cand)
            assert staged["sha"] != old_sha
            raw = _training_raw(model_set)
            res = sw.score_raw(raw)
            sw.observe(raw, res)
            snap = sw.shadow_snapshot()
            assert snap["rows"] == raw.n_rows
            assert snap["errors"] == 0
            # +1e-3 on the output bias: full agreement at tolerance 5.0
            assert snap["agreement"] == 1.0
            assert snap["maxAbsDelta"] < 5.0
            out = sw.promote()
            assert out["from"] == old_sha and out["to"] == staged["sha"]
            assert sw.sha == staged["sha"]
            assert sw.shadow_snapshot() is None

    def test_promote_without_stage_raises(self, model_set):
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.registry import ModelRegistry

        sw = SwappableRegistry(
            ModelRegistry(os.path.join(model_set, "models")))
        with pytest.raises(ValueError):
            sw.promote()

    def test_stage_rejects_schema_change(self, model_set, tmp_path):
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.models.nn import NNModelSpec
        from shifu_tpu.serve.registry import ModelRegistry

        spec = NNModelSpec.load(
            os.path.join(model_set, "models", "model0.nn"))
        spec.norm_specs = spec.norm_specs[:-1]  # drop an input column
        spec.layer_sizes = list(spec.layer_sizes)
        cand = str(tmp_path / "bad_candidate")
        os.makedirs(cand)
        spec.save(os.path.join(cand, "model0.nn"))
        sw = SwappableRegistry(
            ModelRegistry(os.path.join(model_set, "models")))
        with pytest.raises(ValueError, match="schema"):
            sw.stage(cand)

    def test_swap_under_load_loses_nothing(self, model_set, tmp_path):
        """The acceptance pin: concurrent scoring across a hot-swap —
        every request answered exactly once, per-version counters account
        for every row, both versions served."""
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.batcher import AdmissionQueue
        from shifu_tpu.serve.registry import ModelRegistry
        from shifu_tpu.serve.server import Scorer

        before = _snapshot_counters()
        with _Props(shifu_loop_shadowSample="1.0"):
            sw = SwappableRegistry(
                ModelRegistry(os.path.join(model_set, "models")))
            old_sha = sw.sha
            cand = _perturbed_candidate(model_set, tmp_path)
            scorer = Scorer(sw, AdmissionQueue(256), max_wait_ms=1.0)
            names = list(sw.input_columns)
            rec = {c: "0.5" for c in names}
            n_threads, per_thread, rows_per = 4, 30, 3
            errors = []
            answered = [0] * n_threads
            swapped = threading.Event()

            def client(ti):
                for _ in range(per_thread):
                    try:
                        res = scorer.score_batch([rec] * rows_per,
                                                 timeout=30.0)
                        assert len(res.mean) == rows_per
                        answered[ti] += rows_per
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            # stage + promote mid-flight
            sw.stage(cand)
            swapped.set()
            out = sw.promote()
            for t in threads:
                t.join()
            scorer.close()
            assert not errors, errors[:3]
            total = n_threads * per_thread * rows_per
            assert sum(answered) == total
            after = _snapshot_counters()
            per_version = _counter_delta(before, after,
                                         "serve.version.records")
            assert sum(per_version.values()) == total, per_version
            # the swap happened mid-load: the new version answered the
            # tail (the old may have answered everything before the swap
            # on a fast promote, so only the new sha is REQUIRED)
            assert any(out["to"] in k for k in per_version), per_version

    def test_scored_sha_survives_a_promote(self, model_set, tmp_path):
        """The observer attributes a batch to the version that SCORED it:
        a promote landing between the score and the observe must not
        re-stamp the batch with the new sha."""
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.registry import ModelRegistry

        sw = SwappableRegistry(
            ModelRegistry(os.path.join(model_set, "models")))
        old_sha = sw.sha
        sw.score_raw(_training_raw(model_set))
        sw.stage(_perturbed_candidate(model_set, tmp_path))
        sw.promote()
        assert sw.sha != old_sha          # the NEXT batch is the new set
        assert sw.scored_sha == old_sha   # the last batch stays the old

    def test_shadow_delta_binning_matches_observe(self):
        """The vectorized add_binned path (ShadowStats.note) lands every
        observation in the same bucket a per-value observe() would —
        including exact bucket edges and the +inf overflow."""
        from shifu_tpu.loop.hotswap import SCORE_DELTA_BUCKETS
        from shifu_tpu.obs.metrics import Histogram

        d = np.abs(np.asarray([0.0, 0.4, 0.5, 0.7, 3.0, -2.0, 1e6],
                              dtype=np.float64))
        bulk = Histogram(buckets=SCORE_DELTA_BUCKETS)
        binned = np.bincount(
            np.searchsorted(np.asarray(bulk.buckets), d, side="left"),
            minlength=len(bulk.buckets))
        bulk.add_binned(binned.tolist(), float(d.sum()), int(d.size),
                        float(d.min()), float(d.max()))
        ref = Histogram(buckets=SCORE_DELTA_BUCKETS)
        for v in d:
            ref.observe(float(v))
        got, want = bulk.as_dict(), ref.as_dict()
        assert got["counts"] == want["counts"]
        assert got["count"] == want["count"]
        assert got["sum"] == pytest.approx(want["sum"])
        assert (got["min"], got["max"]) == (want["min"], want["max"])

    def test_nan_shadow_delta_is_disagreement_not_crash(self):
        """A candidate emitting NaN scores must show up as disagreement
        in the gate evidence — not kill the observer pass."""
        from shifu_tpu.loop.hotswap import ShadowStats

        stats = ShadowStats(tolerance=0.5)
        stats.note(np.asarray([0.1, np.nan, 0.2, np.inf]))
        snap = stats.snapshot()
        assert snap["rows"] == 4
        assert snap["agreement"] == pytest.approx(0.5)  # NaN/inf disagree
        assert snap["maxAbsDelta"] == np.inf

    def test_shadow_sample_zero_disables_shadow_scoring(
            self, model_set, tmp_path):
        """shadowSample=0 means OFF (like the traffic log's sample<=0),
        not one-batch-in-a-million."""
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.registry import ModelRegistry

        with _Props(shifu_loop_shadowSample="0"):
            sw = SwappableRegistry(
                ModelRegistry(os.path.join(model_set, "models")))
            sw.stage(_perturbed_candidate(model_set, tmp_path))
            raw = _training_raw(model_set)
            res = sw.score_raw(raw)
            sw.observe(raw, res)
            assert sw.shadow_snapshot()["rows"] == 0

    def test_promote_bound_to_expected_sha(self, model_set, tmp_path):
        """promote(expected_sha) refuses a shadow that is not the
        candidate the gate evidence described."""
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.registry import ModelRegistry

        sw = SwappableRegistry(
            ModelRegistry(os.path.join(model_set, "models")))
        cand = _perturbed_candidate(model_set, tmp_path)
        staged = sw.stage(cand)
        with pytest.raises(ValueError, match="re-staged"):
            sw.promote(expected_sha="0" * 16)
        assert sw.shadow_snapshot() is not None  # still staged
        out = sw.promote(expected_sha=staged["sha"])
        assert out["to"] == staged["sha"]

    def test_shadow_error_contained(self, model_set, tmp_path):
        from shifu_tpu.loop.hotswap import SwappableRegistry
        from shifu_tpu.serve.registry import ModelRegistry

        with _Props(shifu_loop_shadowSample="1.0"):
            sw = SwappableRegistry(
                ModelRegistry(os.path.join(model_set, "models")))
            cand = _perturbed_candidate(model_set, tmp_path)
            sw.stage(cand)
            sw._shadow.score_raw = None  # simulate a candidate crash
            raw = _training_raw(model_set)
            res = sw.score_raw(raw)  # live path unaffected
            sw.observe(raw, res)     # shadow failure contained
            snap = sw.shadow_snapshot()
            assert snap["errors"] == 1
            assert len(res.mean) == raw.n_rows


# ---------------------------------------------------------------------------
# promote gates
# ---------------------------------------------------------------------------


class TestPromoteGates:
    def _shadow(self, **kw):
        base = {"sha": "c" * 16, "rows": 500, "errors": 0,
                "agreement": 0.99, "tolerance": 5.0}
        base.update(kw)
        return base

    def _rec(self):
        return {"recommendation": {
            "action": "retrain", "modelSetSha": "a" * 16,
            "drift": {"driftedColumns": ["num_0"], "maxPsi": 0.31}}}

    def test_all_gates_pass(self):
        from shifu_tpu.loop.promote import evaluate_gates

        d = evaluate_gates(self._shadow(), self._rec(),
                           agree_min=0.95, min_rows=64)
        assert d["promote"] is True
        assert d["gates"]["shadow"]["ok"] and d["gates"]["drift"]["ok"]
        assert d["gates"]["drift"]["recommendation"]["maxPsi"] == 0.31

    @pytest.mark.parametrize("shadow,why", [
        (None, "no shadow stats"),
        ({"rows": 10, "errors": 0, "agreement": 1.0}, "10 shadow rows"),
        ({"rows": 500, "errors": 2, "agreement": 1.0}, "errored"),
        ({"rows": 500, "errors": 0, "agreement": 0.5}, "agreement"),
    ])
    def test_shadow_gate_failures(self, shadow, why):
        from shifu_tpu.loop.promote import evaluate_gates

        d = evaluate_gates(shadow, self._rec(),
                           agree_min=0.95, min_rows=64)
        assert d["promote"] is False
        assert why in d["gates"]["shadow"]["reason"]

    def test_shadow_gate_rejects_foreign_evidence(self):
        """Agreement earned by a previously staged candidate must not
        green-light a different one."""
        from shifu_tpu.loop.promote import evaluate_gates

        d = evaluate_gates(self._shadow(), self._rec(),
                           agree_min=0.95, min_rows=64,
                           candidate_sha="d" * 16)
        assert d["promote"] is False
        assert "not the candidate" in d["gates"]["shadow"]["reason"]
        # matching sha (or unknown candidate sha): evidence accepted
        ok = evaluate_gates(self._shadow(), self._rec(),
                            agree_min=0.95, min_rows=64,
                            candidate_sha="c" * 16)
        assert ok["promote"] is True

    def test_drift_gate_rejects_stale_recommendation(self):
        """A recommendation stamped against an older active sha was
        already acted on — it must not justify rollouts forever."""
        from shifu_tpu.loop.promote import evaluate_gates

        d = evaluate_gates(self._shadow(), self._rec(),
                           agree_min=0.95, min_rows=64,
                           active_sha="b" * 16)  # rec targets "a"*16
        assert d["promote"] is False
        assert "already acted on" in d["gates"]["drift"]["reason"]
        ok = evaluate_gates(self._shadow(), self._rec(),
                            agree_min=0.95, min_rows=64,
                            active_sha="a" * 16)
        assert ok["promote"] is True

    def test_drift_gate_blocks_without_recommendation(self):
        from shifu_tpu.loop.promote import evaluate_gates

        d = evaluate_gates(self._shadow(), None,
                           agree_min=0.95, min_rows=64)
        assert d["promote"] is False
        assert "no retrain recommendation" in d["gates"]["drift"]["reason"]
        d2 = evaluate_gates(self._shadow(), None, agree_min=0.95,
                            min_rows=64, require_drift=False)
        assert d2["promote"] is True

    def test_offline_swap_is_recoverable(self, tmp_path):
        from shifu_tpu.loop.promote import offline_swap

        root = str(tmp_path)
        os.makedirs(os.path.join(root, "models"))
        open(os.path.join(root, "models", "model0.nn"), "w").write("old")
        cand = os.path.join(root, "models.candidate")
        os.makedirs(cand)
        open(os.path.join(cand, "model0.nn"), "w").write("new")
        out = offline_swap(root, cand)
        assert open(os.path.join(root, "models", "model0.nn")).read() \
            == "new"
        assert open(os.path.join(
            root, "models.previous", "model0.nn")).read() == "old"
        assert out["models"].endswith("models")

    def test_run_promote_writes_manifest_and_exit_codes(self, tmp_path):
        from shifu_tpu.loop.promote import run_promote

        root = str(tmp_path)
        # no shadow stats, no recommendation -> held (exit 1) + manifest
        assert run_promote(root, None) == 1
        (p,) = glob.glob(os.path.join(root, ".shifu/runs/promote-*.json"))
        m = json.load(open(p))["promote"]
        assert m["decision"]["promote"] is False
        assert not m["decision"]["gates"]["shadow"]["ok"]


# ---------------------------------------------------------------------------
# PSI merge/fold edge cases (satellite)
# ---------------------------------------------------------------------------


class TestPsiEdgeCases:
    def test_zero_sides_defined(self):
        from shifu_tpu.stats.psi import psi_from_counts

        assert psi_from_counts(np.zeros(4), np.ones(4)) == 0.0
        assert psi_from_counts(np.ones(4), np.zeros(4)) == 0.0
        assert psi_from_counts(np.zeros(0), np.zeros(0)) == 0.0

    def test_zero_expected_frequency_is_smoothed_finite(self):
        from shifu_tpu.stats.psi import psi_from_counts

        # a live category training never saw (expected 0, actual > 0)
        # and a training bin live traffic never hits (actual 0)
        e = np.array([100.0, 50.0, 0.0])
        a = np.array([0.0, 80.0, 70.0])
        p = psi_from_counts(e, a)
        assert np.isfinite(p) and p > 0.0

    def test_identical_distributions_are_zero(self):
        from shifu_tpu.stats.psi import psi_from_counts

        c = np.array([10.0, 20.0, 30.0])
        assert psi_from_counts(c, c * 7) == pytest.approx(0.0, abs=1e-12)

    def _accs(self, column_configs, k):
        import copy

        from shifu_tpu.stats.psi import PsiAccumulator

        return [PsiAccumulator(copy.deepcopy(column_configs), "cat_0")
                for _ in range(k)]

    def test_merge_additivity_matches_single_fold(self, column_configs):
        """PSI is computed from pure counts: S accumulators over chunk
        slices, merged, must equal the single accumulator — including
        units only one shard saw."""
        import copy

        from shifu_tpu.data.reader import read_columnar, read_header
        from shifu_tpu.stats.psi import PsiAccumulator

        names, rows, _ = make_binary_dataset(n_rows=300, seed=5)
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            from tests.helpers import write_dataset

            data_path, _h = write_dataset(d, names, rows)
            data = read_columnar(data_path,
                                 read_header(os.path.join(d, "header.txt")))
        ccs_a = copy.deepcopy(column_configs)
        ccs_b = copy.deepcopy(column_configs)
        single = PsiAccumulator(ccs_a, "cat_0")
        single.update(data)
        shards = [PsiAccumulator(copy.deepcopy(column_configs), "cat_0")
                  for _ in range(3)]
        n = data.n_rows
        for s in range(3):
            mask = np.zeros(n, dtype=bool)
            mask[s::3] = True
            shards[s].update(data.select_rows(mask))
        merged = shards[0]
        merged.merge(shards[1])
        merged.merge(shards[2])
        for j in range(len(single.cols)):
            assert np.array_equal(single.overall[j], merged.overall[j])
        assert sorted(single.unit_counts) == sorted(merged.unit_counts)
        for u in single.unit_counts:
            for j in range(len(single.cols)):
                assert np.array_equal(single.unit_counts[u][j],
                                      merged.unit_counts[u][j])
        single.finalize()
        merged_ccs = [copy.deepcopy(c) for c in column_configs]
        merged2 = PsiAccumulator(merged_ccs, "cat_0")
        merged2.merge(merged)
        merged2.finalize()
        for ca, cb in zip(ccs_a, merged_ccs):
            assert ca.column_stats.psi == cb.column_stats.psi
            assert ca.column_stats.unit_stats == cb.column_stats.unit_stats

    def test_merge_rejects_mismatched_layout(self, column_configs):
        import copy

        from shifu_tpu.stats.psi import PsiAccumulator

        a = PsiAccumulator(copy.deepcopy(column_configs), "cat_0")
        b = PsiAccumulator(copy.deepcopy(column_configs), "cat_1")
        with pytest.raises(ValueError, match="cannot merge"):
            a.merge(b)

    def test_unseen_category_lands_in_missing_slot(self, column_configs):
        from shifu_tpu.serve.registry import records_to_columnar
        from shifu_tpu.stats.psi import PsiAccumulator

        cat = next(c for c in column_configs if c.is_categorical()
                   and c.column_binning.bin_category)
        acc = PsiAccumulator([cat], "unit")
        recs = [{cat.column_name: "NEVER_SEEN_IN_TRAINING", "unit": "u1"}]
        data = records_to_columnar(recs * 5, [cat.column_name, "unit"])
        acc.update(data)
        # all 5 rows in the trailing missing/unseen slot
        assert acc.overall[0][-1] == 5.0
        assert acc.overall[0][:-1].sum() == 0.0


# ---------------------------------------------------------------------------
# sharded correlation/PSI parity (satellite: ROADMAP item-2 residue)
# ---------------------------------------------------------------------------


class TestShardedCorrPsiParity:
    def test_s8_vs_s1_byte_parity(self, tmp_path):
        """The corr/PSI chunk pass divided over the ShardPlan (S=8) must
        reproduce the S=1 artifacts byte-for-byte: PSI state is integer
        counts in f64 (exact), and every correlation shard folds with the
        SAME first-chunk shift so the merged f64 moments are the same
        sums."""
        from shifu_tpu.processor.init import InitProcessor
        from shifu_tpu.processor.stats import StatsProcessor

        base = str(tmp_path / "base")
        make_model_set(base, n_rows=420, seed=9)
        mcp = os.path.join(base, "ModelConfig.json")
        mc = json.load(open(mcp))
        mc["stats"]["psiColumnName"] = "cat_0"
        json.dump(mc, open(mcp, "w"), indent=2)
        assert InitProcessor(base).run() == 0
        roots = {}
        for s in (1, 8):
            root = str(tmp_path / f"s{s}")
            shutil.copytree(base, root)
            with _Props(shifu_ingest_forceStreaming="true",
                        shifu_ingest_chunkRows="48",
                        shifu_lifecycle_shards=str(s)):
                assert StatsProcessor(root, correlation=True,
                                      psi=True).run() == 0
            roots[s] = root
        corr1 = open(os.path.join(
            roots[1], "tmp", "stats", "correlation.csv")).read()
        corr8 = open(os.path.join(
            roots[8], "tmp", "stats", "correlation.csv")).read()
        assert corr1 == corr8
        cc1 = json.load(open(os.path.join(roots[1], "ColumnConfig.json")))
        cc8 = json.load(open(os.path.join(roots[8], "ColumnConfig.json")))
        psi1 = [(c["columnName"], c["columnStats"].get("psi"),
                 c["columnStats"].get("unitStats")) for c in cc1]
        psi8 = [(c["columnName"], c["columnStats"].get("psi"),
                 c["columnStats"].get("unitStats")) for c in cc8]
        assert psi1 == psi8
        assert any(p is not None and p != 0.0 for _n, p, _u in psi1)

    def test_correlation_merge_requires_shared_shift(self):
        """Per-shard shifts would change the f64 summands, not just their
        order — the driver derives ONE shift from the globally first
        chunk; merging accumulators built over different column sets
        rejects."""
        from shifu_tpu.stats.correlation import StreamingCorrelation

        a = StreamingCorrelation()
        b = StreamingCorrelation()
        a.names = ["x", "y"]
        b.names = ["x", "z"]
        a._acc = [np.ones((2, 2))] * 4
        b._acc = [np.ones((2, 2))] * 4
        with pytest.raises(ValueError, match="different column sets"):
            a.merge(b)
        # same columns, different shifts: the f64 moment sums are
        # residuals around the shift — folding them would be silently
        # wrong, so merge rejects instead
        b.names = ["x", "y"]
        a._shift = np.asarray([0.0, 1.0], dtype=np.float32)
        b._shift = np.asarray([5.0, 1.0], dtype=np.float32)
        with pytest.raises(ValueError, match="different shifts"):
            a.merge(b)
        b._shift = a._shift.copy()
        a.merge(b)  # shared shift folds fine


# ---------------------------------------------------------------------------
# retrain: warm start, provenance, chaos parity
# ---------------------------------------------------------------------------


def _prep_trained(root, n_rows=300, epochs=12, algorithm="NN",
                  extra_mc=None):
    from shifu_tpu.processor.init import InitProcessor
    from shifu_tpu.processor.norm import NormProcessor
    from shifu_tpu.processor.stats import StatsProcessor
    from shifu_tpu.processor.train import TrainProcessor

    make_model_set(root, n_rows=n_rows, seed=7, algorithm=algorithm)
    mcp = os.path.join(root, "ModelConfig.json")
    mc = json.load(open(mcp))
    mc["train"]["numTrainEpochs"] = epochs
    mc["train"]["epochsPerIteration"] = 2
    for k, v in (extra_mc or {}).items():
        mc["train"][k] = v
    json.dump(mc, open(mcp, "w"), indent=2)
    assert InitProcessor(root).run() == 0
    assert StatsProcessor(root).run() == 0
    assert NormProcessor(root).run() == 0
    assert TrainProcessor(root).run() == 0
    return root


class TestRetrain:
    def test_requires_parent_models(self, tmp_path):
        from shifu_tpu.processor.init import InitProcessor
        from shifu_tpu.processor.retrain import RetrainProcessor
        from shifu_tpu.utils.errors import ShifuError

        root = str(tmp_path / "ms")
        make_model_set(root, n_rows=120)
        assert InitProcessor(root).run() == 0
        with pytest.raises(ShifuError, match="shifu train"):
            RetrainProcessor(root).run()

    def test_from_traffic_and_data_are_mutually_exclusive(self, tmp_path):
        """Both flags name a source; silently preferring one would train
        on data the operator did not ask for — reject up front."""
        from shifu_tpu.processor.retrain import RetrainProcessor
        from shifu_tpu.utils.errors import ShifuError

        root = str(tmp_path / "ms")
        make_model_set(root, n_rows=120)
        with pytest.raises(ShifuError, match="mutually exclusive"):
            RetrainProcessor(root, from_traffic=True,
                             data_path="new.csv")

    def test_nn_warm_start_provenance_and_candidate(self, model_set):
        from shifu_tpu.processor.retrain import RetrainProcessor
        from shifu_tpu.serve.registry import model_set_sha

        assert RetrainProcessor(model_set).run() == 0
        cand = os.path.join(model_set, "models.candidate")
        assert os.path.isfile(os.path.join(cand, "model0.nn"))
        manifests = sorted(
            p for p in glob.glob(
                os.path.join(model_set, ".shifu/runs/retrain-*.json"))
            if not p.endswith(".trace.json"))
        m = json.load(open(manifests[-1]))
        rt = m["retrain"]
        assert rt["parent"]["modelSetSha"] == model_set_sha(
            [os.path.join(model_set, "models", "model0.nn")])
        assert rt["candidate"]["modelSetSha"] != rt["parent"]["modelSetSha"]
        assert set(rt["configShas"]) == {"data", "train", "loop"}
        assert rt["source"]["kind"] == "data"
        assert rt["source"]["rows"] > 0
        # originals untouched: retrain normalizes into tmp/retrain
        assert os.path.isdir(os.path.join(model_set, "tmp", "retrain",
                                          "norm", "NormalizedData"))
        assert os.path.isfile(os.path.join(model_set, "models",
                                           "model0.nn"))

    def test_gbt_appends_parent_trees_bitwise(self, tmp_path):
        from shifu_tpu.models.tree import TreeModelSpec
        from shifu_tpu.processor.retrain import RetrainProcessor

        root = _prep_trained(str(tmp_path / "gbt"), n_rows=260,
                             algorithm="GBT",
                             extra_mc={"params": {"TreeNum": 8}})
        parent = TreeModelSpec.load(
            os.path.join(root, "models", "model0.gbt"))
        assert RetrainProcessor(root, append_trees=4).run() == 0
        cand = TreeModelSpec.load(
            os.path.join(root, "models.candidate", "model0.gbt"))
        assert len(cand.trees) == len(parent.trees) + 4
        assert json.dumps(cand.trees[:len(parent.trees)], sort_keys=True,
                          default=str) \
            == json.dumps(parent.trees, sort_keys=True, default=str)
        m = json.load(open(sorted(
            p for p in glob.glob(os.path.join(
                root, ".shifu/runs/retrain-*.json"))
            if not p.endswith(".trace.json"))[-1]))
        assert m["retrain"]["warmStart"]["appendedTrees"] == 4
        assert m["retrain"]["parent"]["trees"] == len(parent.trees)

    def test_traffic_log_roundtrip_retrains(self, tmp_path):
        """Serve -> traffic log -> retrain: the log is label-joined (the
        target rides the request conversion as an extra raw column) and
        `shifu retrain --from-traffic` consumes exactly the logged
        chunks."""
        from shifu_tpu.processor.retrain import RetrainProcessor
        from shifu_tpu.serve.server import ScoringServer

        root = _prep_trained(str(tmp_path / "ms"), n_rows=260, epochs=6)
        names, rows, _ = make_binary_dataset(n_rows=120, seed=13)
        writers = set()
        with _Props(shifu_loop_logSample="1.0",
                    shifu_loop_logChunkRows="64"):
            # TWO serve processes in sequence (fresh lease each): the
            # fleet-shared log keeps one chunk family per writer and the
            # retrain below consumes the union
            for start_at in (0, 60):
                server = ScoringServer(root=root, port=0)
                server.start()
                try:
                    writers.add(server.traffic.writer)
                    for start in range(start_at, start_at + 60, 30):
                        recs = [dict(zip(names, r))
                                for r in rows[start:start + 30]]
                        server.scorer.score_batch(recs)
                finally:
                    manifest = server.shutdown()
        assert len(writers) == 2 and all(writers)
        m = json.load(open(manifest))
        assert m["traffic"]["chunks"] >= 1
        assert RetrainProcessor(root, from_traffic=True).run() == 0
        rm = json.load(open(sorted(
            p for p in glob.glob(os.path.join(
                root, ".shifu/runs/retrain-*.json"))
            if not p.endswith(".trace.json"))[-1]))
        src = rm["retrain"]["source"]
        assert src["kind"] == "traffic"
        assert src["trafficChunks"]
        assert src["rows"] > 0
        # the lineage manifest records the whole fleet's writers
        assert sorted(src["trafficWriters"]) == sorted(writers)
        assert os.path.isfile(os.path.join(root, "models.candidate",
                                           "model0.nn"))

    def test_chaos_parity_resume_bit_identical(self, tmp_path):
        """Acceptance: kill `shifu retrain` mid-stream, `--resume`
        produces weights bit-identical to an uninterrupted retrain."""
        from shifu_tpu.models.nn import NNModelSpec, flatten_params
        from shifu_tpu.processor.retrain import RetrainProcessor
        from shifu_tpu.resilience.faults import PreemptionError

        clean = _prep_trained(str(tmp_path / "clean"), n_rows=260,
                              epochs=10)
        chaos = str(tmp_path / "chaos")
        shutil.copytree(clean, chaos)
        with _Props(shifu_train_forceStreaming="true"):
            assert RetrainProcessor(clean).run() == 0
            with _Props(shifu_faults="preempt@epoch=4"):
                with pytest.raises(PreemptionError):
                    RetrainProcessor(chaos).run()
            m = json.load(open(os.path.join(
                chaos, ".shifu/runs/retrain-1.json")))
            assert m["status"] == "failed"
            c = m["metrics"]["counters"]
            assert c.get('fault.injected{seam="preempt"}') == 1.0
            # the retrain trainer checkpoint is listed as resumable
            from shifu_tpu.resilience.checkpoint import list_resumable

            names = [e["name"] for e in list_resumable(chaos)]
            assert any(n.startswith("retrain-") for n in names), names
            with _Props(shifu_resume="true"):
                assert RetrainProcessor(chaos).run() == 0
        a = flatten_params(NNModelSpec.load(os.path.join(
            clean, "models.candidate", "model0.nn")).params)[0]
        b = flatten_params(NNModelSpec.load(os.path.join(
            chaos, "models.candidate", "model0.nn")).params)[0]
        assert np.array_equal(a, b)

    def test_checkpoint_rejection_names_diverged_section(self, tmp_path):
        """A streamed-train snapshot whose `loop` section (warm-start
        parent) diverged is rejected naming exactly that section."""
        from shifu_tpu.resilience.checkpoint import (
            StreamCheckpoint,
            sectioned_sha,
        )

        path = str(tmp_path / "t.ckpt.npz")
        sha_a, sec_a = sectioned_sha({
            "train": {"lr": 0.1}, "data": {"rows": 10},
            "loop": {"parentModelSetSha": "aaaa"}})
        StreamCheckpoint(path, sha_a, every=0, sections=sec_a).save(
            3, arrays={"w": np.zeros(2)}, meta={"epoch": 3})
        sha_b, sec_b = sectioned_sha({
            "train": {"lr": 0.1}, "data": {"rows": 10},
            "loop": {"parentModelSetSha": "bbbb"}})
        before = _snapshot_counters()
        ck = StreamCheckpoint(path, sha_b, every=0, sections=sec_b)
        assert ck.load() is None
        after = _snapshot_counters()
        d = _counter_delta(before, after, "ckpt.rejected")
        assert d.get('ckpt.rejected{reason="config",section="loop"}') \
            == 1.0, d


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------


class TestLoopCli:
    def test_parsers_exist(self):
        from shifu_tpu.cli import build_parser

        p = build_parser()
        args = p.parse_args(["retrain", "--from-traffic",
                             "--append-trees", "7"])
        assert args.command == "retrain"
        assert args.from_traffic and args.append_trees == 7
        args = p.parse_args(["promote", "--no-drift-gate", "--force",
                             "--serve-url", "http://x:1", "--stage"])
        assert args.command == "promote"
        assert args.no_drift_gate and args.force and args.stage
        args = p.parse_args(["serve", "--traffic-log"])
        assert args.traffic_log == "1.0"
        args = p.parse_args(["serve", "--traffic-log", "0.25"])
        assert args.traffic_log == "0.25"

    def test_bad_traffic_log_fraction_fails_startup(self, tmp_path,
                                                    monkeypatch):
        """A malformed --traffic-log value must fail the serve startup,
        not silently disable logging (get_float would swallow it into
        the 0.0 default and the server would log nothing for days)."""
        from shifu_tpu.cli import main

        monkeypatch.chdir(tmp_path)  # no model set needed: fails before
        assert main(["serve", "--traffic-log", "0,5"]) == 1
        assert main(["serve", "--traffic-log", "1.5"]) == 1
        assert main(["serve", "--traffic-log", "0"]) == 1
