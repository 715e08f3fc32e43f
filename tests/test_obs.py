"""Observability subsystem: registry, exporters, tracing, run ledger, CLI.

Covers the PR-2 acceptance contract: a deterministic manifest schema check,
Prometheus/JSON exporters round-tripping the same registry state, the
BasicProcessor.run() wrapper (profiler dir under -Dshifu.profile, manifest on
success AND failure, sequence numbering), and the end-to-end
stats -> norm -> train ledger over the synthetic fixture.
"""

import json
import logging
import os
import threading

import numpy as np
import pytest

from tests.helpers import make_model_set


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def _populated_registry():
    from shifu_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("stats.rows_valid").inc(600)
    reg.counter("eval.records", eval="EvalA").inc(100)
    reg.gauge("eval.auc", eval="EvalA").set(0.97)
    reg.timer("stats.stage", stage="parse").add(1.25, 12)
    reg.timer("stats.stage", stage="device").add(0.5, 12)
    h = reg.histogram("chunk.seconds")
    h.observe(0.3)
    h.observe(2.0)
    s = reg.series("train.train_error", trainer=0)
    s.append(1, 0.25)
    s.append(2, 0.20)
    return reg


class TestMetricsRegistry:
    def test_kinds_and_labels(self):
        reg = _populated_registry()
        assert reg.counter("stats.rows_valid").value == 600
        # same name, different labels = different metric
        assert reg.counter("eval.records", eval="EvalA").value == 100
        assert reg.counter("eval.records", eval="EvalB").value == 0
        assert reg.timer("stats.stage", stage="parse").calls == 12
        assert reg.series("train.train_error", trainer=0).last == 0.20
        snap = reg.snapshot()
        assert snap["counters"]['eval.records{eval="EvalA"}'] == 100
        assert snap["timers"]['stats.stage{stage="parse"}']["seconds"] == 1.25
        assert snap["series"]['train.train_error{trainer="0"}'] == [
            [1.0, 0.25], [2.0, 0.20]]
        assert snap["histograms"]["chunk.seconds"]["count"] == 2

    def test_json_round_trip(self):
        from shifu_tpu.obs import MetricsRegistry

        reg = _populated_registry()
        text = reg.to_json()
        clone = MetricsRegistry.from_json(text)
        assert clone.to_json() == text
        assert clone.snapshot() == reg.snapshot()

    def test_prometheus_round_trip(self):
        """The text exporter's samples parse back to exactly flatten() —
        the same registry state through both exporters."""
        from shifu_tpu.obs import MetricsRegistry, parse_prometheus

        reg = _populated_registry()
        text = reg.to_prometheus()
        assert parse_prometheus(text) == reg.flatten()
        # and the JSON round-tripped clone flattens identically
        clone = MetricsRegistry.from_json(reg.to_json())
        assert clone.flatten() == reg.flatten()
        # spot-check naming conventions
        flat = reg.flatten()
        assert flat["stats_rows_valid_total"] == 600
        assert flat['stats_stage_seconds_total{stage="parse"}'] == 1.25
        assert flat['train_train_error_last{trainer="0"}'] == 0.20

    def test_label_value_escaping_round_trips(self):
        """Label values come from user config (eval-set names) — quotes and
        backslashes must survive both exporters."""
        from shifu_tpu.obs import MetricsRegistry, parse_prometheus

        reg = MetricsRegistry()
        nasty = 'A"B\\C'
        reg.counter("eval.records", eval=nasty).inc(7)
        clone = MetricsRegistry.from_json(reg.to_json())
        assert clone.to_json() == reg.to_json()
        assert clone.counter("eval.records", eval=nasty).value == 7
        prom = reg.to_prometheus()
        assert parse_prometheus(prom) == reg.flatten()
        assert '\\"' in prom and "\\\\" in prom  # escaped on the wire

    def test_thread_safety(self):
        from shifu_tpu.obs import MetricsRegistry

        reg = MetricsRegistry()

        def work():
            for _ in range(1000):
                reg.counter("n").inc()
                reg.timer("t").add(0.001)

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("n").value == 4000
        assert reg.timer("t").calls == 4000

    def test_stage_timers_compat_and_registry_backing(self):
        from shifu_tpu.obs import MetricsRegistry
        from shifu_tpu.utils.timing import StageTimers

        # bare: self-contained, PR-1 API intact
        st = StageTimers()
        with st.timer("parse"):
            pass
        st.add("device", 0.5, 2)
        assert st.calls("parse") == 1
        assert st.seconds("device") == 0.5
        assert "device 0.50s/2" in st.summary()
        assert st.as_dict()["device"]["calls"] == 2
        # registry-backed: stages are registry timers -> manifest-visible
        reg = MetricsRegistry()
        rt = reg.stage_timers("norm.stage")
        rt.add("write", 0.25)
        assert reg.timer("norm.stage", stage="write").seconds == 0.25
        assert 'norm.stage{stage="write"}' in reg.snapshot()["timers"]


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


class TestTracing:
    def test_nested_spans_chrome_trace(self):
        from shifu_tpu.obs.tracing import Tracer

        tr = Tracer()
        with tr.span("step.stats", seq=1) as attrs:
            with tr.span("stats.pass1"):
                pass
            attrs["rows"] = 300
        events = tr.to_chrome_trace()["traceEvents"]
        assert [e["name"] for e in events] == ["step.stats", "stats.pass1"]
        outer = next(e for e in events if e["name"] == "step.stats")
        inner = next(e for e in events if e["name"] == "stats.pass1")
        for e in events:
            assert e["ph"] == "X" and e["pid"] == os.getpid()
        # containment: inner starts after and ends before outer
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert outer["args"] == {"seq": 1, "rows": 300}
        assert inner["args"]["parent"] == "step.stats"

    def test_save_and_span_seconds(self, tmp_path):
        from shifu_tpu.obs.tracing import Tracer

        tr = Tracer()
        assert tr.save(str(tmp_path / "x" / "t.json")) is None  # no spans
        with tr.span("a"):
            pass
        path = tr.save(str(tmp_path / "x" / "t.json"))
        assert path and os.path.isfile(path)
        doc = json.load(open(path))
        assert doc["traceEvents"][0]["name"] == "a"
        assert tr.span_seconds("a") >= 0.0
        assert tr.span_seconds("missing") == 0.0

    def test_span_lands_in_the_profilers_trace(self, tmp_path):
        """A span is also a `shifu.<name>` TraceAnnotation: in a captured
        profile it sits on the host plane, on the profiler's clock, inside
        a `bench.`-style annotation opened around it, with its scalar
        attributes as the event's stats."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from shifu_tpu.obs.tracing import Tracer

        tr = Tracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.control"):
                with tr.span("probe.outer", call=7, what="x", skipped=None):
                    with tr.span("probe.inner"):
                        pass
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", "shifu.")):
                        found[ev.name] = (ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          dict(ev.stats))
        assert set(found) == {"bench.control", "shifu.probe.outer",
                              "shifu.probe.inner"}
        c, o, i = (found[k] for k in ("bench.control", "shifu.probe.outer",
                                      "shifu.probe.inner"))
        assert c[0] <= o[0] <= i[0] and i[1] <= o[1] <= c[1]
        assert o[2] == {"call": 7, "what": "x"}
        # the ring keeps the same two spans on the host's clock
        assert [e["name"] for e in tr.events] == ["probe.inner",
                                                  "probe.outer"]

    def test_span_without_jax_opens_no_annotation(self, monkeypatch):
        import contextlib
        import sys

        from shifu_tpu.obs import tracing

        monkeypatch.setitem(sys.modules, "jax", None)  # import jax raises
        assert isinstance(tracing.profiler_annotation("shifu.x"),
                          contextlib.nullcontext)
        tr = tracing.Tracer()
        with tr.span("host.only"):
            pass
        assert [e["name"] for e in tr.events] == ["host.only"]

    def test_between_clips_by_perf_counter(self):
        from shifu_tpu.obs.tracing import Tracer

        tr = Tracer()
        t0 = tr.t0
        tr.record("warm.a", t0 + 1.0, t0 + 2.0)
        tr.record("win.a", t0 + 2.5, t0 + 3.5, "outer", {"k": 1})
        tr.record("win.b", t0 + 1.5, t0 + 4.0)  # starts early, ends inside
        tr.record("late.a", t0 + 4.5, t0 + 6.0)  # ends after the window
        got = tr.between(t0 + 3.0, t0 + 5.0)
        assert [e["name"] for e in got] == ["win.a", "win.b"]
        assert got[0]["args"] == {"k": 1, "parent": "outer"}
        assert got[0]["dur"] == pytest.approx(1e6)
        assert [e["name"] for e in tr.between(t0 + 3.0, t0 + 5.0, "win.b")
                ] == ["win.b"]
        assert [e["name"] for e in tr.between(float("-inf"), t0 + 2.0)
                ] == ["warm.a"]
        assert tr.between(t0 + 10.0, t0 + 11.0) == []


# ---------------------------------------------------------------------------
# run wrapper + ledger
# ---------------------------------------------------------------------------


def _dummy_processor(root, step="teststep", fail=False):
    from shifu_tpu.processor.basic import BasicProcessor

    class Dummy(BasicProcessor):
        pass

    Dummy.step = step

    class Ok(Dummy):
        def run_step(self):
            from shifu_tpu.obs import registry

            registry().counter(f"{step}.rows").inc(42)

    class Boom(Dummy):
        def run_step(self):
            raise RuntimeError("step exploded")

    return (Boom if fail else Ok)(root)


class TestRunWrapperAndLedger:
    def test_manifest_on_success_and_sequence_numbering(self, tmp_path):
        root = str(tmp_path)
        assert _dummy_processor(root).run() == 0
        assert _dummy_processor(root).run() == 0
        runs = os.path.join(root, ".shifu", "runs")
        names = sorted(os.listdir(runs))
        assert "teststep-1.json" in names and "teststep-2.json" in names
        m = json.load(open(os.path.join(runs, "teststep-2.json")))
        assert m["schema"] == "shifu.run/1"
        assert m["step"] == "teststep" and m["seq"] == 2
        assert m["status"] == "ok" and m["exitStatus"] == 0
        assert m["error"] is None
        assert isinstance(m["argv"], list)
        assert m["elapsedSeconds"] >= 0
        assert m["metrics"]["counters"]["teststep.rows"] == 42
        # registry reset between runs: seq-2 counter is 42, not 84
        m1 = json.load(open(os.path.join(runs, "teststep-1.json")))
        assert m1["metrics"]["counters"]["teststep.rows"] == 42
        # root span recorded into the chrome trace beside the manifest
        assert m["tracePath"]
        trace = json.load(open(os.path.join(root, m["tracePath"])))
        assert any(e["name"] == "step.teststep"
                   for e in trace["traceEvents"])
        # jax info present (cpu under the test harness)
        assert m["jax"].get("backend") == "cpu"

    def test_manifest_on_failure_reraises(self, tmp_path):
        root = str(tmp_path)
        proc = _dummy_processor(root, fail=True)
        with pytest.raises(RuntimeError, match="step exploded"):
            proc.run()
        m = json.load(open(os.path.join(
            root, ".shifu", "runs", "teststep-1.json")))
        assert m["status"] == "failed" and m["exitStatus"] == 1
        assert m["error"] == "RuntimeError: step exploded"

    def test_profiler_dir_created_under_shifu_profile(self, tmp_path):
        from shifu_tpu.utils import environment

        root = str(tmp_path)
        environment.set_property("shifu.profile", "prof")
        try:
            assert _dummy_processor(root, step="profstep").run() == 0
        finally:
            environment.set_property("shifu.profile", "")
        prof_dir = os.path.join(root, "prof", "profstep")
        assert os.path.isdir(prof_dir)
        m = json.load(open(os.path.join(
            root, ".shifu", "runs", "profstep-1.json")))
        assert m["profileDir"] == prof_dir

    def test_list_and_format_runs(self, tmp_path):
        from shifu_tpu.obs.ledger import format_runs, list_runs

        root = str(tmp_path)
        assert format_runs(list_runs(root)) == \
            "(no runs recorded under .shifu/runs)"
        _dummy_processor(root, step="stats").run()
        _dummy_processor(root, step="norm").run()
        _dummy_processor(root, step="stats").run()
        all_runs = list_runs(root)
        assert len(all_runs) == 3
        # newest first
        assert (all_runs[0]["startedAtUnix"]
                >= all_runs[-1]["startedAtUnix"])
        assert len(list_runs(root, last=2)) == 2
        stats_only = list_runs(root, step="stats")
        assert {m["step"] for m in stats_only} == {"stats"}
        assert sorted(m["seq"] for m in stats_only) == [1, 2]
        table = format_runs(all_runs)
        assert "STEP" in table and "stats" in table and "norm" in table

    def test_runs_cli(self, tmp_path, monkeypatch, capsys):
        from shifu_tpu import cli

        root = str(tmp_path)
        _dummy_processor(root, step="stats").run()
        _dummy_processor(root, step="norm").run()
        monkeypatch.chdir(root)
        assert cli.main(["runs", "--last", "1"]) == 0
        out = capsys.readouterr().out
        assert "norm" in out and "stats" not in out.replace("STEP", "")
        assert cli.main(["runs", "--step", "stats", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert len(doc) == 1 and doc[0]["step"] == "stats"


# ---------------------------------------------------------------------------
# end-to-end ledger over the fixture (acceptance criterion)
# ---------------------------------------------------------------------------


class TestLifecycleLedger:
    @pytest.fixture()
    def model_root(self, tmp_path):
        root = make_model_set(str(tmp_path / "ModelSet"), n_rows=300)
        mc_path = os.path.join(root, "ModelConfig.json")
        mc = json.load(open(mc_path))
        mc["train"]["numTrainEpochs"] = 15
        json.dump(mc, open(mc_path, "w"), indent=2)
        return root

    def test_stats_norm_train_manifests(self, model_root):
        from shifu_tpu.processor.init import InitProcessor
        from shifu_tpu.processor.norm import NormProcessor
        from shifu_tpu.processor.stats import StatsProcessor
        from shifu_tpu.processor.train import TrainProcessor

        assert InitProcessor(model_root).run() == 0
        assert StatsProcessor(model_root).run() == 0
        assert NormProcessor(model_root).run() == 0
        assert TrainProcessor(model_root).run() == 0

        runs = os.path.join(model_root, ".shifu", "runs")
        for step in ("init", "stats", "norm", "train"):
            assert os.path.isfile(os.path.join(runs, f"{step}-1.json")), step

        stats = json.load(open(os.path.join(runs, "stats-1.json")))
        counters = stats["metrics"]["counters"]
        assert counters["stats.rows_valid"] == 300
        assert counters["stats.rows_pos"] + counters["stats.rows_neg"] == 300
        # stage timers routed through the registry into the manifest
        timers = stats["metrics"]["timers"]
        assert any(k.startswith("stats.stage{") for k in timers), timers
        assert stats["configHashes"]["ModelConfig.json"]
        # NOTE: no jax.compiles floor here — in a warm process the step can
        # ride the process-global jit cache (zero fresh compiles is the
        # desired steady state); TestJaxProbes pins the counter itself

        norm = json.load(open(os.path.join(runs, "norm-1.json")))
        assert norm["metrics"]["counters"]["norm.rows"] == 300
        assert any(k.startswith("norm.stage{")
                   for k in norm["metrics"]["timers"])

        train = json.load(open(os.path.join(runs, "train-1.json")))
        series = train["metrics"]["series"]
        # per-epoch training series, non-empty
        curve = series.get('train.valid_error{trainer="0"}')
        assert curve and len(curve) >= 1
        assert train["metrics"]["gauges"]["train.valid_error"] < 0.5
        assert train["metrics"]["counters"]["train.iterations"] >= 1

        # `shifu runs --last 3` renders them
        from shifu_tpu.obs.ledger import format_runs, list_runs

        table = format_runs(list_runs(model_root, last=3))
        assert "train" in table and "norm" in table and "stats" in table


# ---------------------------------------------------------------------------
# jax compile probes
# ---------------------------------------------------------------------------


class TestJaxProbes:
    def test_compile_counter_increments_on_fresh_compile(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs

        assert obs.install_jax_probes()
        obs.reset()

        @jax.jit  # fresh function object -> guaranteed cache miss
        def f(x):
            return x * 3 + 1

        f(jnp.ones(17)).block_until_ready()
        reg = obs.registry()
        assert reg.counter("jax.compiles").value >= 1
        assert reg.timer("jax.compile").seconds > 0
        before = reg.counter("jax.compiles").value
        f(jnp.ones(17)).block_until_ready()  # cache hit: no new compile
        assert reg.counter("jax.compiles").value == before

    def test_events_say_which_program_and_under_which_span(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs

        assert obs.install_jax_probes()
        obs.reset()

        @jax.jit
        def probed_program(x):
            return x * 7 - 1

        with obs.span("outer.step"):
            with obs.span("inner.part"):
                probed_program(jnp.ones(13)).block_until_ready()
        probed_program(jnp.ones(21)).block_until_ready()  # no span open
        reg = obs.registry()
        assert reg.counter("jax.lowers").value >= 2
        assert reg.timer("jax.lower").seconds > 0
        hist = reg.snapshot()["histograms"]["jax.lower.duration_seconds"]
        assert hist["count"] >= 2
        mine = [e for e in obs.tracer().events
                if "probed_program" in e["args"].get("fun", "")]
        assert {e["name"] for e in mine} == {"jax.trace", "jax.lower",
                                             "jax.compile"}
        inside = [e for e in mine
                  if e["args"].get("parent") == "outer.step/inner.part"]
        outside = [e for e in mine if "parent" not in e["args"]]
        for kind in ("jax.trace", "jax.lower", "jax.compile"):
            assert sum(e["name"] == kind for e in inside) == 1
            assert sum(e["name"] == kind for e in outside) == 1
        # an event ends when it arrives and starts its duration earlier:
        # inside the span that was open
        part = next(e for e in obs.tracer().events
                    if e["name"] == "inner.part")
        for e in inside:
            assert e["dur"] > 0
            assert part["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= part["ts"] + part["dur"] + 1e3

    def test_persistent_cache_hit_and_miss_are_counted(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache

        from shifu_tpu import obs

        assert obs.install_jax_probes()
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
        was = {k: getattr(jax.config, k) for k in keys}
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        try:
            x = jnp.ones(19)  # its own little program, before the count
            obs.reset()

            def cached_program(x):
                return jnp.tanh(x) * 11 + 3

            jax.jit(cached_program)(x).block_until_ready()
            reg = obs.registry()
            assert reg.counter("jax.cache.misses").value == 1
            assert reg.counter("jax.cache.hits").value == 0
            # a fresh jit of the same function: jax's in-memory caches do
            # not know it, the directory does
            jax.clear_caches()
            jax.jit(cached_program)(x).block_until_ready()
            assert reg.counter("jax.cache.hits").value == 1
            assert reg.counter("jax.cache.misses").value == 1
            assert reg.timer("jax.cache.retrieval").calls == 1
            assert reg.timer("jax.cache.retrieval").seconds > 0
            # the fetch is a backend "compile" event all the same
            assert reg.counter("jax.compiles").value == 2
        finally:
            for k, v in was.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# satellite: idempotent logging configure
# ---------------------------------------------------------------------------


class TestConfigureLogging:
    def test_repeated_configure_is_effective(self):
        from shifu_tpu.utils.log import configure

        root = logging.getLogger()
        old_handlers = list(root.handlers)
        old_level = root.level
        old_jax = logging.getLogger("jax").level
        try:
            configure(verbose=False)
            assert root.level == logging.INFO
            assert logging.getLogger("jax").level == logging.WARNING
            # the bug: basicConfig silently no-ops once handlers exist —
            # a later -v must still take effect
            configure(verbose=True)
            assert root.level == logging.DEBUG
            assert logging.getLogger("jax").level == logging.NOTSET
            configure(verbose=False)
            assert root.level == logging.INFO
            # force=True replaces rather than stacks handlers
            assert len(root.handlers) == 1
        finally:
            root.handlers[:] = old_handlers
            root.setLevel(old_level)
            logging.getLogger("jax").setLevel(old_jax)
