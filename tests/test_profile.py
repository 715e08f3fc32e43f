"""Program profiler + costmodel + `shifu profile` CLI.

Covers the ISSUE-6 acceptance contract: costmodel units against a fake
chip table (override knobs, roofline boundary), profiler-vs-hand-math
FLOPs parity on a dense MLP (the real nn training program at a
reduced row count), the manifest `profile` section schema through
BasicProcessor.run, regression gating (`shifu profile --diff` exits 1 on
an injected 2x-FLOPs regression), `shifu runs --diff`, and a no-jax
smoke over the CLI parse/render path.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# costmodel
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_lookup_table_and_unknown(self):
        from shifu_tpu.obs import costmodel

        v5e = costmodel.lookup("TPU v5 lite")
        assert v5e and v5e.peak_tflops == 197.0 and v5e.source == "table"
        v5p = costmodel.lookup("tpu v5p chip")
        assert v5p and v5p.peak_tflops == 459.0
        assert costmodel.lookup("weird accelerator") is None

    def test_detect_cpu_nominal_and_overrides(self):
        from shifu_tpu.obs import costmodel
        from shifu_tpu.utils import environment

        peaks = costmodel.detect()  # cpu under the test harness
        assert peaks.source == "nominal"
        assert peaks.peak_tflops > 0 and peaks.peak_hbm_gbs > 0
        environment.set_property("shifu.profile.peakTflops", "123.5")
        environment.set_property("shifu.profile.peakGBs", "456.0")
        try:
            over = costmodel.detect()
            assert over.source == "override"
            assert over.peak_tflops == 123.5
            assert over.peak_hbm_gbs == 456.0
        finally:
            environment.set_property("shifu.profile.peakTflops", "")
            environment.set_property("shifu.profile.peakGBs", "")

    def test_roofline_boundary_and_derive(self):
        from shifu_tpu.obs.costmodel import ChipPeaks, derive, \
            roofline_verdict

        # fake chip: 1 TFLOP/s over 100 GB/s -> machine balance 10 f/B
        chip = ChipPeaks("fake", "fake", 1.0, 100.0, "table")
        assert chip.machine_balance == 10.0
        assert roofline_verdict(1000.0, 10.0, chip) == "compute-bound"
        assert roofline_verdict(99.0, 10.0, chip) == "memory-bound"
        assert roofline_verdict(100.0, 10.0, chip) == "compute-bound"
        d = derive(5e11, 1e10, 1.0, chip)  # half the peak, AI=50
        assert d["achievedTflops"] == pytest.approx(0.5)
        assert d["mfu"] == pytest.approx(0.5)
        assert d["achievedGBps"] == pytest.approx(10.0)
        assert d["membw"] == pytest.approx(0.1)
        assert d["arithmeticIntensity"] == pytest.approx(50.0)
        assert d["roofline"] == "compute-bound"
        # no timing -> static fields only
        d2 = derive(100.0, 1000.0, None, chip)
        assert d2["achievedTflops"] is None and d2["mfu"] is None
        assert d2["roofline"] == "memory-bound"


# ---------------------------------------------------------------------------
# profiler dispatch + scaling
# ---------------------------------------------------------------------------


class TestProgramProfiler:
    def test_dispatch_records_costs_and_scale(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs
        from shifu_tpu.obs import profile

        obs.reset()

        @jax.jit
        def f(x):
            return (x @ x.T).sum()

        x = jnp.ones((64, 64))
        out = profile.dispatch("t.prog", f, x, sync=True)
        assert float(out) == pytest.approx(64.0 * 64 * 64)
        with profile.scaled(10):
            profile.dispatch("t.prog", f, x, sync=True)
        snap = obs.profiler().snapshot()
        p = snap["programs"]["t.prog"]
        assert p["dispatches"] == 2
        assert p["costSource"] == "xla"
        # second dispatch carries 10x the first's flops: total = 11 units
        assert p["flops"] == pytest.approx(11 * (p["flops"] / 11))
        one = p["flops"] / 11.0
        assert one > 2 * 64**3 * 0.9  # ~2NMK matmul flops
        assert p["bytesAccessed"] > 0
        assert p["peakHbmBytes"] > 0
        assert p["synced"] is True
        assert p["deviceSeconds"] >= 0.0
        assert snap["totals"]["dispatches"] == 2
        assert snap["schema"] == "shifu.profile/1"

    def test_results_match_plain_jit_and_cache_no_extra_compiles(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs
        from shifu_tpu.obs import profile

        assert obs.install_jax_probes()
        obs.reset()
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))

        @jax.jit
        def g(x):
            return jnp.tanh(x) * 2.0 + x.sum(axis=1, keepdims=True)

        want = np.asarray(g(xs))
        obs.reset()
        compiles0 = obs.registry().counter("jax.compiles").value
        got = np.asarray(profile.dispatch("t.g", g, xs, sync=True))
        np.testing.assert_array_equal(want, got)
        after_first = obs.registry().counter("jax.compiles").value
        # steady state: repeat dispatches hit the AOT executable cache
        for _ in range(3):
            profile.dispatch("t.g", g, xs, sync=True)
        assert obs.registry().counter("jax.compiles").value == after_first

    def test_mode_off_and_tracer_fallback(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs
        from shifu_tpu.obs import profile
        from shifu_tpu.utils import environment

        obs.reset()

        @jax.jit
        def f(x):
            return x + 1

        environment.set_property("shifu.profile.mode", "off")
        try:
            profile.dispatch("t.off", f, jnp.ones(3), sync=True)
        finally:
            environment.set_property("shifu.profile.mode", "")
        assert "t.off" not in obs.profiler().snapshot()["programs"]

        # a wrapped program used under trace inlines without recording
        wrapped = profile.wrap("t.inner", f)

        @jax.jit
        def outer(x):
            return wrapped(x) * 2

        out = np.asarray(outer(jnp.ones(3)))
        np.testing.assert_array_equal(out, np.full(3, 4.0))
        assert "t.inner" not in obs.profiler().snapshot()["programs"]

    def test_static_args_profiled_wrapper(self):
        import jax.numpy as jnp

        from shifu_tpu import obs
        from shifu_tpu.ops.binagg import bin_aggregate_profiled

        obs.reset()
        agg = bin_aggregate_profiled(
            jnp.asarray(np.zeros((16, 2), np.int32)),
            jnp.asarray(np.array([0, 3], np.int32)),
            7,  # positional static total_slots
            jnp.asarray(np.ones(16, np.int32)),
            jnp.asarray(np.ones(16, np.float32)),
            jnp.asarray(np.zeros((16, 1), np.float32)),
        )
        assert float(np.asarray(agg.pos).sum()) == 32.0  # 16 rows x 2 cols
        p = obs.profiler().snapshot()["programs"]["stats.bin_aggregate"]
        assert p["dispatches"] == 1 and p["costSource"] == "xla"


# ---------------------------------------------------------------------------
# profiler vs hand math on a dense MLP
# ---------------------------------------------------------------------------


class TestDenseMfuParity:
    def test_xla_flops_match_corrected_hand_formula(self):
        """The profiler's FLOP count against the benchmark's closed-form
        count (fwd 2/MAC + bwd 4/MAC minus the never-computed first-layer
        input grad) on the REAL nn training program at a dense layer
        shape, 1024 -> 2048 x 2 -> 1, reduced row count."""
        import jax.numpy as jnp

        import jax
        from benchmarks.lib.work import mlp_flops_per_row_epoch
        from shifu_tpu import obs
        from shifu_tpu.obs import profile
        from shifu_tpu.train.nn_trainer import (
            NNTrainConfig,
            _get_program,
            flatten_params,
            init_params,
        )

        obs.reset()
        d, hidden = 1024, [2048, 2048]
        n = 512  # flops scale linearly in rows
        cfg = NNTrainConfig(
            hidden_nodes=list(hidden), activations=["tanh"] * len(hidden),
            propagation="R", num_epochs=2, valid_set_rate=0.1, seed=1,
            mixed_precision=True)
        sizes = [d] + list(hidden) + [1]
        flat0, shapes = flatten_params(init_params(sizes, seed=1))
        program, init_state = _get_program(cfg, shapes, n)
        carry = (
            jnp.asarray(flat0), init_state(flat0.size), jnp.int32(0),
            jnp.float32(0.1), jnp.float32(np.inf), jnp.asarray(flat0),
            jnp.int32(0), jnp.zeros((), bool), jnp.float32(0.0),
            jnp.float32(0.0),
        )
        x = jnp.ones((n, d))
        t = jnp.ones(n)
        s = jnp.ones(n)
        epochs = 2
        with profile.scaled(epochs):
            profile.dispatch("parity.dense", program, carry,
                             jnp.int32(epochs), x, t, s, s,
                             jax.random.PRNGKey(1), jnp.float32(n),
                             sync=True)
        p = obs.profiler().snapshot()["programs"]["parity.dense"]
        assert p["costSource"] == "xla"
        hand = mlp_flops_per_row_epoch(d, hidden) * n * epochs
        assert p["flops"] == pytest.approx(hand, rel=0.05)


# ---------------------------------------------------------------------------
# manifest profile section (BasicProcessor.run)
# ---------------------------------------------------------------------------


def _dispatching_processor(root, step="profstep", fail=False):
    from shifu_tpu.processor.basic import BasicProcessor

    class Proc(BasicProcessor):
        def run_step(self):
            import jax
            import jax.numpy as jnp

            from shifu_tpu.obs import profile

            @jax.jit
            def prog(x):
                return (x * 2 + 1).sum()

            profile.dispatch("test.program", prog, jnp.ones(128),
                             sync=True)
            if fail:
                raise RuntimeError("boom after dispatch")

    Proc.step = step
    return Proc(root)


REQUIRED_PROGRAM_KEYS = {
    "dispatches", "flops", "bytesAccessed", "peakHbmBytes",
    "compileSeconds", "deviceSeconds", "achievedTflops", "mfu",
    "arithmeticIntensity", "roofline", "synced", "costSource",
}


class TestManifestProfileSection:
    def test_schema_on_success(self, tmp_path):
        root = str(tmp_path)
        assert _dispatching_processor(root).run() == 0
        m = json.load(open(os.path.join(
            root, ".shifu", "runs", "profstep-1.json")))
        prof = m["profile"]
        assert prof["schema"] == "shifu.profile/1"
        assert prof["chip"]["peakTflops"] > 0
        p = prof["programs"]["test.program"]
        assert REQUIRED_PROGRAM_KEYS <= set(p)
        assert p["dispatches"] == 1
        assert p["flops"] > 0
        assert prof["totals"]["flops"] == p["flops"]

    def test_profile_present_on_failure(self, tmp_path):
        root = str(tmp_path)
        proc = _dispatching_processor(root, fail=True)
        with pytest.raises(RuntimeError, match="boom after dispatch"):
            proc.run()
        m = json.load(open(os.path.join(
            root, ".shifu", "runs", "profstep-1.json")))
        assert m["status"] == "failed"
        assert m["profile"]["programs"]["test.program"]["dispatches"] == 1


# ---------------------------------------------------------------------------
# diffing + CLI gating
# ---------------------------------------------------------------------------


def _fake_manifest(root, step, seq, flops, seconds=1.0, dispatches=4,
                   counters=None):
    """Hand-built manifest with a profile section (no jax needed)."""
    runs = os.path.join(root, ".shifu", "runs")
    os.makedirs(runs, exist_ok=True)
    m = {
        "schema": "shifu.run/1", "step": step, "seq": seq, "status": "ok",
        "startedAtUnix": 1000.0 + seq,
        "metrics": {"counters": counters or {}, "gauges": {}},
        "profile": {
            "schema": "shifu.profile/1",
            "chip": {"name": "fake", "peakTflops": 1.0,
                     "peakHbmGBs": 100.0, "source": "table"},
            "programs": {
                "tree.hist": {
                    "dispatches": dispatches, "flops": flops,
                    "bytesAccessed": flops / 10.0,
                    "peakHbmBytes": 1 << 20,
                    "compileSeconds": 0.5, "deviceSeconds": seconds,
                    "synced": True, "costSource": "xla",
                },
            },
            "totals": {"flops": flops, "dispatches": dispatches},
        },
    }
    path = os.path.join(runs, f"{step}-{seq}.json")
    json.dump(m, open(path, "w"))
    return path


class TestProfileDiff:
    def test_injected_2x_flops_regression_exits_1(self, tmp_path,
                                                  monkeypatch, capsys):
        from shifu_tpu import cli

        root = str(tmp_path)
        _fake_manifest(root, "train", 1, flops=1e9)
        _fake_manifest(root, "train", 2, flops=2e9)  # 2x per-dispatch
        monkeypatch.chdir(root)
        rc = cli.main(["profile", "--diff", "train-1", "train-2"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out and "tree.hist" in out
        assert "flops" in out

    def test_identical_runs_exit_0_and_threshold_override(
            self, tmp_path, monkeypatch, capsys):
        from shifu_tpu import cli

        root = str(tmp_path)
        _fake_manifest(root, "train", 1, flops=1e9)
        _fake_manifest(root, "train", 2, flops=1e9)
        _fake_manifest(root, "train", 3, flops=2e9)
        monkeypatch.chdir(root)
        assert cli.main(["profile", "--diff", "train-1", "train-2"]) == 0
        # a 2x jump passes when the caller loosens the gates to 150%
        assert cli.main(["profile", "--diff", "train-1", "train-3",
                         "--flops-pct", "150",
                         "--bytes-pct", "150"]) == 0
        # unknown manifest id -> clean error, not a traceback
        assert cli.main(["profile", "--diff", "train-1", "nope-9"]) == 2
        capsys.readouterr()

    def test_profile_list_and_json(self, tmp_path, monkeypatch, capsys):
        from shifu_tpu import cli

        root = str(tmp_path)
        _fake_manifest(root, "train", 1, flops=1e9)
        monkeypatch.chdir(root)
        assert cli.main(["profile", "train"]) == 0
        out = capsys.readouterr().out
        assert "tree.hist" in out and "ROOFLINE" in out
        assert cli.main(["profile", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc[0]["profile"]["programs"]["tree.hist"]["flops"] == 1e9

    def test_runs_diff_metric_snapshots(self, tmp_path, monkeypatch,
                                        capsys):
        from shifu_tpu import cli

        root = str(tmp_path)
        _fake_manifest(root, "stats", 1,
                       flops=1e6, counters={"stats.rows_valid": 100,
                                            "stats.chunks": 4})
        _fake_manifest(root, "stats", 2,
                       flops=1e6, counters={"stats.rows_valid": 250,
                                            "pipeline.chunks": 9})
        monkeypatch.chdir(root)
        assert cli.main(["runs", "--diff", "stats-1", "stats-2"]) == 0
        out = capsys.readouterr().out
        assert "counter:stats.rows_valid" in out
        assert "+150.0%" in out
        assert "removed" in out and "added" in out

    def test_diff_profiles_per_dispatch_normalization(self):
        """More dispatches with the same per-dispatch cost is NOT a
        regression (a 10-tree run vs a 5-tree run)."""
        from shifu_tpu.obs.profile import diff_profiles

        a = {"profile": {"programs": {"p": {
            "dispatches": 5, "flops": 5e9, "bytesAccessed": 5e8,
            "peakHbmBytes": 100.0, "deviceSeconds": 1.0}}}}
        b = {"profile": {"programs": {"p": {
            "dispatches": 10, "flops": 1e10, "bytesAccessed": 1e9,
            "peakHbmBytes": 100.0, "deviceSeconds": 2.0}}}}
        rows, breaches = diff_profiles(a, b)
        assert breaches == []


# ---------------------------------------------------------------------------
# CLI parse path runs without jax
# ---------------------------------------------------------------------------


class TestNoJaxCli:
    def test_profile_cli_smoke_without_jax(self, tmp_path):
        """`shifu profile` (list + --diff over hand-built manifests) must
        not import jax — CI lint-tier jobs and bare checkouts drive it."""
        root = str(tmp_path)
        _fake_manifest(root, "train", 1, flops=1e9)
        _fake_manifest(root, "train", 2, flops=2e9)
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"  # any `import jax` now raises
            "from shifu_tpu import cli\n"
            "assert cli.main(['profile', '--last', '1']) == 0\n"
            "rc = cli.main(['profile', '--diff', 'train-1', 'train-2'])\n"
            "assert rc == 1, rc\n"
            "assert cli.main(['runs', '--diff', 'train-1', 'train-2']) == 0\n"
            "print('NOJAX-OK')\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep
            + env.get("PYTHONPATH", ""))
        res = subprocess.run([sys.executable, "-c", code], cwd=root,
                             capture_output=True, text=True, env=env,
                             timeout=120)
        assert res.returncode == 0, res.stderr
        assert "NOJAX-OK" in res.stdout


# ---------------------------------------------------------------------------
# jaxprobe duration histogram + watchdog seconds (satellites)
# ---------------------------------------------------------------------------


class TestCompileDurations:
    def test_duration_histogram_records_per_event(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs

        assert obs.install_jax_probes()
        obs.reset()

        @jax.jit  # fresh object -> guaranteed cache miss
        def f(x):
            return x * 5 - 2

        f(jnp.ones(9)).block_until_ready()
        snap = obs.registry().snapshot()["histograms"]
        h = snap.get("jax.compile.duration_seconds")
        assert h and h["count"] >= 1
        assert h["sum"] > 0

    def test_recompile_breach_reports_wall_clock(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs
        from shifu_tpu.analysis.sanitize import Sanitizer

        assert obs.install_jax_probes()
        obs.reset()
        san = Sanitizer(["recompile"], budget=0)
        with san.armed("t.stage"):
            @jax.jit
            def f(x):
                return x + 3

            f(jnp.ones(11)).block_until_ready()
        v = san.verdict()
        assert v["recompile"]["breaches"] == 1
        assert v["recompile"]["breachedCompileSeconds"] > 0
        assert "wall-clock" in v["events"][0]["detail"]


class TestDispatchSeamTracing:
    def test_bare_dispatch_installs_the_probes(self, tmp_path):
        """A process that goes straight to a dispatch seam (the trainers
        called without BasicProcessor.run: the benchmark's drivers)
        still records which program compiled: the seam installs
        the jax probes itself."""
        code = (
            "import jax, jax.numpy as jnp\n"
            "from shifu_tpu import obs\n"
            "from shifu_tpu.obs import jaxprobe, profile\n"
            "assert not jaxprobe._installed\n"
            "f = jax.jit(lambda x: x * 2 + 1)\n"
            "x = jnp.ones(8)\n"
            "with obs.span('caller.step'):\n"
            "    profile.dispatch('t.bare', f, x, sync=True)\n"
            "assert jaxprobe._installed\n"
            "assert obs.registry().counter('jax.compiles').value >= 1\n"
            "evs = [e for e in obs.tracer().events\n"
            "       if e['name'] == 'jax.compile']\n"
            "assert evs and all(e['args']['parent'] == 'caller.step'\n"
            "                   for e in evs), evs\n"
            "assert any('lambda' in e['args']['fun'] for e in evs), evs\n"
            "print('BARE-OK')\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep
            + env.get("PYTHONPATH", ""))
        res = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert res.returncode == 0, res.stderr
        assert "BARE-OK" in res.stdout

    def test_dispatch_is_a_shifu_prog_annotation_and_no_ring_event(
            self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from shifu_tpu import obs
        from shifu_tpu.obs import profile

        f = jax.jit(lambda x: jnp.tanh(x) + 1)
        x = jnp.ones((16, 4))
        profile.dispatch("t.annotated", f, x, sync=True)  # compile outside
        obs.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with obs.span("caller.step"):
                profile.dispatch("t.annotated", f, x, sync=True)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        host = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("shifu."):
                            host[ev.name] = (ev.start_ns,
                                             ev.start_ns + ev.duration_ns)
        assert set(host) == {"shifu.caller.step", "shifu.prog.t.annotated"}
        step, prog = host["shifu.caller.step"], host["shifu.prog.t.annotated"]
        assert step[0] <= prog[0] and prog[1] <= step[1]
        # annotation only: the seam is per request in serve/, so the ring
        # gets nothing from it
        assert [e["name"] for e in obs.tracer().events] == ["caller.step"]


class TestXlaDeepCapture:
    def test_profile_xla_traces_into_ledger_dir(self, tmp_path):
        """-Dshifu.profile=xla wraps the step in jax.profiler.trace under
        .shifu/runs/<step>-<seq>-xla and links the newest Perfetto trace
        from the manifest; explicit-dir values keep the old behavior
        (pinned in test_obs.py)."""
        from shifu_tpu.utils import environment

        root = str(tmp_path)
        proc = _dispatching_processor(root, step="xstep")
        environment.set_property("shifu.profile", "xla")
        try:
            assert proc.run() == 0
        finally:
            environment.set_property("shifu.profile", "")
        m = json.load(open(os.path.join(
            root, ".shifu", "runs", "xstep-1.json")))
        assert m["profileDir"].endswith(
            os.path.join(".shifu", "runs", "xstep-1-xla"))
        assert os.path.isdir(m["profileDir"])
        trace = m.get("perfettoTrace")
        if trace:  # written whenever this jax build emits a trace file
            assert os.path.isfile(trace)
            assert ".trace.json" in trace


class TestScaledWorkNormalization:
    def test_more_epochs_is_not_a_regression(self):
        """A trainer dispatch under scaled(epochs) books epochs x the
        body's flops; the diff must normalize by scaledDispatches so a
        20-epoch run vs a 10-epoch run compares per loop body."""
        from shifu_tpu.obs.profile import diff_profiles

        def manifest(epochs):
            return {"profile": {"programs": {"nn.train_program": {
                "dispatches": 1, "scaledDispatches": float(epochs),
                "flops": 1e9 * epochs, "bytesAccessed": 1e8 * epochs,
                "peakHbmBytes": 100.0,
                "deviceSeconds": 0.1 * epochs}}}}

        rows, breaches = diff_profiles(manifest(10), manifest(20))
        assert breaches == []

    def test_snapshot_records_scaled_dispatches(self):
        import jax
        import jax.numpy as jnp

        from shifu_tpu import obs
        from shifu_tpu.obs import profile

        obs.reset()

        @jax.jit
        def f(x):
            return x * 2

        with profile.scaled(7):
            profile.dispatch("t.sc", f, jnp.ones(4), sync=True)
        profile.dispatch("t.sc", f, jnp.ones(4), sync=True)
        p = obs.profiler().snapshot()["programs"]["t.sc"]
        assert p["dispatches"] == 2
        assert p["scaledDispatches"] == 8.0
