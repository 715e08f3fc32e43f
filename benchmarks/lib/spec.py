"""Where the benchmark's data files are, and how a cell is looked up.

Everything that belongs to one cell, one configuration, one driver, one
reference or one per-layer metric is a file of its own, found by the name
`BENCHMARK.json` gives it; nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, by path: names may hold dots."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "benchmarks.%s.%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with the files it names, read."""

    def __init__(self, name: str):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        entries = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if not entries:
            raise SystemExit("no workload %r in BENCHMARK.json" % name)
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.traffic = load_json("workloads", name + ".json")
        cfg_entry = [c for c in self.benchmark["configs"]
                     if c["name"] == self.entry["config"]][0]
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)

    def metrics(self, group: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports: those
        that list it, and those that list no cells at all."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]
