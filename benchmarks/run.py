"""The benchmark's one entry:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child. It finds the cell in BENCHMARK.json, the cell's
traffic in benchmarks/workloads/<cell>.json, its configuration in the file
BENCHMARK.json names, and imports benchmarks/drivers/<driver>.py; traced, it
also imports benchmarks/layer_metrics/<metric>.py for every per-layer metric
that lists the cell. See benchmarks/README.md.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.lib import compare, spec  # noqa: E402

WORK_DIR = os.path.join(spec.BENCH_DIR, ".work")


def _say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _device_block(devices, used: int) -> dict:
    peak = 0
    for d in devices[:used]:
        # the allocator counts live buffers and, apart from them, what the
        # runtime reserves for a running program's temporaries: the peak is
        # at least the larger of the two (and at most their sum)
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, rows: int | None = None,
             control: bool = False, t_start: float | None = None) -> dict:
    """One run of one cell; returns the result line as a dict. `rows` and
    `require_chip=False` are for the CPU tests of the benchmark itself, and
    `control` puts the lower-precision reference in the program's place."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.Cell(workload)

    import jax

    from benchmarks.lib import compiles

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            raise SystemExit("cell %s needs %d TPU chip(s); jax found %d x %s"
                             % (workload, cell.chips, len(devices),
                                devices[0].platform))
        from shifu_tpu.utils.platform import place_compile_cache

        place_compile_cache()  # sets nothing where the environment names one
    clog = compiles.CompileLog.get()
    t_backend = time.perf_counter()

    driver_mod = spec.load_module("drivers", cell.traffic["driver"])
    drv = driver_mod.setup(cell, seed, rows)
    t_data = time.perf_counter()
    clog.take()  # the data's own program counts as data
    drv.warm_and_read()
    t_warm = time.perf_counter()
    comp = clog.take()
    setup_s = t_warm - t_start
    split = {
        "backend_start_s": t_backend - t_start,
        "data_s": t_data - t_backend,
        "trace_lower_s": comp["trace_s"] + comp["lower_s"],
        "compile_or_fetch_s": comp["compile_or_fetch_s"],
        # jax's own events nest (a trace inside a lowering), so the rest can
        # come out a little under 0: it is then 0
        "warm_up_s": max(0.0, (t_warm - t_data) - comp["trace_s"]
                         - comp["lower_s"] - comp["compile_or_fetch_s"]),
        "programs": comp["compiles"],
    }

    trace_dir = os.path.join(WORK_DIR, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's own spans are enough
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    calls = []
    t0 = time.perf_counter()
    while True:
        s = time.perf_counter()
        if s - t0 >= seconds:
            break
        with jax.profiler.TraceAnnotation("bench.call"):
            drv.call()
        calls.append((s, time.perf_counter()))
    wall_s = calls[-1][1] - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = clog.take()
    device = _device_block(devices, cell.chips)
    mem_stats = devices[0].memory_stats() or {}

    rate = drv.work_per_call * len(calls) / wall_s
    ctx = {"cell": cell, "driver": drv, "calls": calls, "wall_s": wall_s,
           "rate": rate, "window_start": t0, "trace": None,
           "device_kind": device["kind"]}
    breakdown = None
    if trace:
        from benchmarks.lib import xplane

        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        red = xplane.reduce(files[0], chips=cell.chips)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(files[0], os.path.join(
                keep, workload + ".xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}

    drv.free()
    t_check = time.perf_counter()
    readings = drv.compared(control=control)
    check_s = time.perf_counter() - t_check
    compared = {k: v for k, v in readings.items() if v["limit"] is not None}
    not_compared = {k: v["value"] for k, v in readings.items()
                    if v["limit"] is None}
    correct = compare.verdict(compared)

    metrics = {}
    if trace:
        for m in cell.metrics("per_layer"):
            reader = spec.load_module("layer_metrics", m["name"])
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        # the harness's own quantities under the names the cell's traffic
        # file gives them, then whatever else the driver measured itself
        mine = {"setup_s": setup_s, "rate": rate, "wall_s": wall_s}
        e2e = {"setup_s": setup_s}
        for name, what in cell.traffic.get("end_to_end", {}).items():
            e2e[name] = mine[what]
        if hasattr(drv, "end_to_end"):
            e2e.update(drv.end_to_end(ctx))
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    print(json.dumps({"setup_split": split,
                      "compiles_in_window": in_window["compiles"],
                      "calls": len(calls), "wall_s": wall_s,
                      "check_s": check_s,
                      "memory_stats": mem_stats}), flush=True)
    if in_window["compiles"]:
        raise SystemExit("%d program(s) compiled inside the measured window"
                         % in_window["compiles"])
    for k, item in compared.items():
        _say("compared %s = %.6g  limit %s" % (k, item["value"],
                                               item["limit"]))
    out = {"correct": bool(correct), "attempted": len(calls), "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["not_compared"] = not_compared
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                   t_start=_T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
