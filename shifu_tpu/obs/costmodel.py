"""Per-chip peak table + roofline math for the program profiler.

XLA's `cost_analysis()` says what a program *does* (FLOPs, bytes moved);
this module says what the chip *could* do (peak dense-matmul FLOP/s, peak
HBM bandwidth), so the profiler (obs/profile.py) can turn raw counts into
achieved-vs-peak utilization (MFU), arithmetic intensity, and a roofline
verdict: a program whose FLOPs-per-byte sits below the chip's machine
balance is memory-bound — more MXU efficiency cannot speed it up, only
fewer bytes can (the classic Williams/Waterman/Patterson roofline model).

Peaks are public per-chip numbers (bf16 dense matmul TFLOP/s, HBM GB/s),
matched by `device_kind` substring. The CPU entry is a NOMINAL figure so
dev-harness rooflines classify sensibly; treat CPU MFU as relative only.
It applies to platform == "cpu" alone: an accelerator missing from the
table is an error, never graded against another device's peaks.

Override knobs (for unlisted chips or corrected figures):
    -Dshifu.profile.peakTflops=<float>   peak dense TFLOP/s
    -Dshifu.profile.peakGBs=<float>      peak memory bandwidth GB/s
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional


class ChipPeaks(NamedTuple):
    """Peak envelope of one accelerator chip."""

    name: str
    kind: str            # raw jax device_kind (or "" when undetected)
    peak_tflops: float   # dense matmul TFLOP/s (bf16 for TPUs)
    peak_hbm_gbs: float  # memory bandwidth GB/s
    source: str          # "table" | "override" | "nominal"

    @property
    def machine_balance(self) -> float:
        """FLOPs per byte at the roofline ridge point."""
        return (self.peak_tflops * 1e12) / (self.peak_hbm_gbs * 1e9)


# device_kind substring -> (display name, peak bf16 TFLOP/s, HBM GB/s).
# Order matters: first substring match wins ("v5 lite" before "v5").
CHIP_TABLE = (
    ("v5 lite", ("TPU v5e", 197.0, 819.0)),
    ("v5e", ("TPU v5e", 197.0, 819.0)),
    ("v5p", ("TPU v5p", 459.0, 2765.0)),
    ("v6", ("TPU v6e", 918.0, 1640.0)),  # Trillium
    ("v4", ("TPU v4", 275.0, 1228.0)),
    ("v3", ("TPU v3", 123.0, 900.0)),
    ("v2", ("TPU v2", 45.0, 700.0)),
)

# Dev-harness nominal: a few AVX cores' worth of f32 matmul and one DDR
# channel-ish of bandwidth. Roofline classification stays meaningful;
# absolute CPU MFU is not a benchmark number.
CPU_NOMINAL = ("CPU (nominal)", 0.25, 25.0)


def lookup(kind: str) -> Optional[ChipPeaks]:
    """Table entry for a device_kind string, or None if unlisted."""
    low = (kind or "").lower()
    for key, (name, tflops, gbs) in CHIP_TABLE:
        if key in low:
            return ChipPeaks(name, kind, tflops, gbs, "table")
    return None


def _overridden(peaks: ChipPeaks) -> ChipPeaks:
    from shifu_tpu.utils import environment

    tflops = environment.get_float("shifu.profile.peakTflops", 0.0)
    gbs = environment.get_float("shifu.profile.peakGBs", 0.0)
    if tflops <= 0.0 and gbs <= 0.0:
        return peaks
    return ChipPeaks(
        peaks.name,
        peaks.kind,
        tflops if tflops > 0.0 else peaks.peak_tflops,
        gbs if gbs > 0.0 else peaks.peak_hbm_gbs,
        "override",
    )


def detect() -> ChipPeaks:
    """Peaks for the current jax backend (override > table; the nominal
    entry is for platform == "cpu" only). An accelerator that is not in
    CHIP_TABLE is an error unless BOTH -Dshifu.profile.peak* overrides
    are given — grading an unknown chip against CPU peaks would report
    a wrong MFU under a plausible name."""
    import jax

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "")
    if dev.platform == "cpu":
        name, tflops, gbs = CPU_NOMINAL
        return _overridden(ChipPeaks(name, kind, tflops, gbs, "nominal"))
    peaks = _overridden(
        lookup(kind) or ChipPeaks(kind, kind, 0.0, 0.0, "unlisted"))
    if peaks.peak_tflops <= 0.0 or peaks.peak_hbm_gbs <= 0.0:
        raise ValueError(
            f"device_kind {kind!r} ({dev.platform}) is not in "
            "obs/costmodel.py CHIP_TABLE: add it with its published "
            "peaks, or pass -Dshifu.profile.peakTflops and "
            "-Dshifu.profile.peakGBs")
    return peaks


def roofline_verdict(flops: float, bytes_accessed: float,
                     peaks: ChipPeaks) -> Optional[str]:
    """Static classification from arithmetic intensity vs machine balance
    (needs no timing, so it holds for async-dispatched programs too)."""
    if not bytes_accessed or flops is None:
        return None
    ai = flops / bytes_accessed
    return "compute-bound" if ai >= peaks.machine_balance else "memory-bound"


def derive(flops: Optional[float], bytes_accessed: Optional[float],
           device_seconds: Optional[float],
           peaks: ChipPeaks) -> Dict[str, Optional[float]]:
    """Achieved-vs-peak numbers for one program (or a totals row).
    Timing-dependent fields are None when `device_seconds` is falsy."""
    out: Dict[str, Optional[float]] = {
        "arithmeticIntensity": None,
        "achievedTflops": None,
        "achievedGBps": None,
        "mfu": None,
        "membw": None,
        "roofline": None,
    }
    if flops is None:
        return out
    if bytes_accessed:
        out["arithmeticIntensity"] = round(flops / bytes_accessed, 4)
        out["roofline"] = roofline_verdict(flops, bytes_accessed, peaks)
    if device_seconds and device_seconds > 0.0:
        tflops = flops / device_seconds / 1e12
        out["achievedTflops"] = round(tflops, 6)
        out["mfu"] = round(tflops / peaks.peak_tflops, 6)
        if bytes_accessed:
            gbps = bytes_accessed / device_seconds / 1e9
            out["achievedGBps"] = round(gbps, 4)
            out["membw"] = round(gbps / peaks.peak_hbm_gbs, 6)
    return out


def peaks_dict(peaks: ChipPeaks) -> dict:
    """JSON form embedded in profile snapshots/manifests."""
    return {
        "name": peaks.name,
        "deviceKind": peaks.kind,
        "peakTflops": peaks.peak_tflops,
        "peakHbmGBs": peaks.peak_hbm_gbs,
        "machineBalance": round(peaks.machine_balance, 4),
        "source": peaks.source,
    }
