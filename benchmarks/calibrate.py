"""Readings for the limits of `correct`, many seeds in one process:

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 [--control 3] [--rows n]

For each seed it builds the cell's driver, drives the program through the
steps `correct` reads, and prints each number compared; for the first
`--control` seeds it prints the control's numbers too (the reference in the
lower precision, put in the program's place). No measured window. Not run by
the benchmark: this is how the limits in benchmarks/workloads/*.json were set.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.lib import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--faults", default="",
                    help="comma list of faults to plant, on the control's "
                    "seeds")
    ap.add_argument("--out", default=None)
    ap.add_argument("--refcheck", action="store_true",
                    help="also read the reference against itself, summed in "
                    "blocks of another size")
    a = ap.parse_args(argv)
    cell = spec.Cell(a.workload)
    driver_mod = spec.load_module("drivers", cell.traffic["driver"])
    lines = []
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        drv = driver_mod.setup(cell, seed, a.rows)
        drv.warm_and_read()
        drv.free()
        row = {"seed": seed, "program": {
            k: v["value"] for k, v in drv.compared().items()}}
        if i < a.control:
            row["control"] = {k: v["value"] for k, v in
                              drv.compared(control=True).items()}
            for fault in filter(None, a.faults.split(",")):
                row["fault." + fault] = {
                    k: v["value"] for k, v in
                    drv.compared(fault=fault).items()}
        if a.refcheck and hasattr(drv, "reference_twice"):
            row["reference_vs_itself"] = drv.reference_twice()
        print(json.dumps(row), flush=True)
        lines.append(row)
        del drv
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            for row in lines:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
