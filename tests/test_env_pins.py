"""Pins for what PR 22's bring-up repaired around the installed packages
and the device: the parsers own the arrays they write into (pandas 3
hands `Series.to_numpy()` back read-only), the compile cache is placed by
one rule, and no fallback hides the device (an accelerator missing from
the chip table is an error, the kernel probe lets backend errors rise).
"""

import os

import numpy as np
import pytest

from shifu_tpu.data.reader import ColumnarData, _flat_parse


@pytest.fixture
def readonly_to_numpy(monkeypatch):
    """Series.to_numpy() returns a READ-ONLY array unless the caller asks
    for its own copy — pandas 3's behaviour, forced so the pin holds on
    any pandas."""
    import pandas as pd

    orig = pd.Series.to_numpy

    def to_numpy(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        if not kwargs.get("copy"):
            out = out.view()
            out.flags.writeable = False
        return out

    monkeypatch.setattr(pd.Series, "to_numpy", to_numpy)


def _data(cols, missing=("", "?")):
    raw = {k: np.asarray(v, dtype=object) for k, v in cols.items()}
    n = len(next(iter(raw.values())))
    return ColumnarData(names=list(raw), raw=raw, n_rows=n,
                        missing_values=missing)


@pytest.mark.parametrize("missing", [("", "?"), ("", "999")],
                         ids=["string-token", "numeric-token"])
def test_numeric_owns_its_array(readonly_to_numpy, missing):
    got = _data({"a": ["1.5", "?", "999", "inf"]}, missing).numeric("a")
    assert got[0] == 1.5 and np.isnan(got[1]) and np.isnan(got[3])
    assert np.isnan(got[2]) == ("999" in missing)
    assert got.flags.writeable


@pytest.mark.parametrize("missing", [("", "?"), ("", "999")],
                         ids=["coercing-path", "numeric-token"])
def test_flat_parse_owns_its_array(readonly_to_numpy, missing):
    # "?" pushes the batch off the all-numeric fast path into the
    # coercing parser; a numeric missing token adds the masking pass
    got = _flat_parse(_data({"a": ["1.5", "?"], "b": ["999", "-inf"]},
                            missing), ["a", "b"])
    assert got.shape == (2, 2)
    assert got[0, 0] == 1.5 and np.isnan(got[1, 0]) and np.isnan(got[1, 1])
    assert np.isnan(got[0, 1]) == ("999" in missing)


def test_stream_module_imports_pyarrow_on_the_importing_thread():
    """The chunk readers run on the prefetch worker; pyarrow's first
    import must not happen there (intermittent segfault with the
    installed pyarrow), so data/stream.py imports it at module import."""
    import subprocess
    import sys

    code = ("import sys, shifu_tpu.data.stream as s; "
            "assert 'pandas' not in sys.modules; "
            "assert ('pyarrow' in sys.modules) == s._HAVE_PYARROW; "
            "print(s._string_dtype())")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() in ("string[pyarrow]", "<class 'str'>")


class TestCompileCachePlacement:
    def test_env_var_set_means_nothing_is_set(self, monkeypatch, tmp_path):
        import jax

        from shifu_tpu.utils import platform

        monkeypatch.setenv(platform.CACHE_ENV, str(tmp_path / "outside"))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert platform.place_compile_cache() is None
        assert calls == []

    def test_fixed_checkout_path_same_in_two_calls(self, monkeypatch):
        import jax

        from shifu_tpu.utils import platform

        monkeypatch.delenv(platform.CACHE_ENV, raising=False)
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        first = platform.place_compile_cache()
        second = platform.place_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", first)] * 2

    def test_importing_the_package_places_no_cache(self):
        import jax

        import shifu_tpu  # noqa: F401

        # tier-1 runs with jax's default: no persistent cache
        assert not jax.config.jax_compilation_cache_dir


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class TestNoFallbackHidesTheDevice:
    def test_unlisted_accelerator_is_an_error(self, monkeypatch):
        import jax

        from shifu_tpu.obs import costmodel

        monkeypatch.setattr(
            jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v9 mega")])
        with pytest.raises(ValueError, match="CHIP_TABLE"):
            costmodel.detect()

    def test_unlisted_accelerator_with_both_overrides(self, monkeypatch):
        import jax

        from shifu_tpu.obs import costmodel
        from shifu_tpu.utils import environment

        monkeypatch.setattr(
            jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v9 mega")])
        environment.set_property("shifu.profile.peakTflops", "100")
        environment.set_property("shifu.profile.peakGBs", "1000")
        try:
            peaks = costmodel.detect()
        finally:
            environment.set_property("shifu.profile.peakTflops", "")
            environment.set_property("shifu.profile.peakGBs", "")
        assert (peaks.source, peaks.peak_tflops) == ("override", 100.0)

    def test_listed_chip_and_cpu(self, monkeypatch):
        import jax

        from shifu_tpu.obs import costmodel

        assert costmodel.detect().source == "nominal"  # the cpu harness
        monkeypatch.setattr(
            jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
        peaks = costmodel.detect()
        assert (peaks.source, peaks.peak_tflops) == ("table", 197.0)

    def test_kernel_probe_lets_backend_errors_rise(self, monkeypatch):
        import jax

        from shifu_tpu.ops import hist_pallas

        def broken():
            raise RuntimeError("backend failed to initialize")

        monkeypatch.setattr(jax, "default_backend", broken)
        with pytest.raises(RuntimeError, match="failed to initialize"):
            hist_pallas.pallas_active()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert hist_pallas.pallas_active() == (True, False)

    def test_profiler_seam_raises_a_refused_compile_once(self):
        from shifu_tpu.obs import profile

        class Lowered:
            compiles = 0

            def cost_analysis(self):
                return {}

            def compile(self):
                Lowered.compiles += 1
                raise RuntimeError("Mosaic: scoped vmem limit exceeded")

        class Jitted:
            calls = 0

            def lower(self, *a, **k):
                return Lowered()

            def __call__(self, *a, **k):
                Jitted.calls += 1

        fn = Jitted()
        with pytest.raises(RuntimeError, match="scoped vmem"):
            profile.dispatch("test.refused_compile", fn, np.zeros(3))
        # the refusal surfaced from the seam's own compile; the program
        # was not re-dispatched through plain jit to fail a second time
        assert (Lowered.compiles, Jitted.calls) == (1, 0)
