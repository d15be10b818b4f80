"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own; nothing here knows any of their names.
"""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(dotted):
    """``package.module:attr`` to the attribute, ``package.module`` to the
    module."""
    mod, _, attr = dotted.partition(":")
    module = importlib.import_module(mod)
    return getattr(module, attr) if attr else module


# the harness's own sections of a configuration's file; every other key is the
# model's configuration as published, dicts and lists included
HARNESS_SECTIONS = ("source", "family", "reference", "reduced", "assumed",
                    "deployment", "serving", "training", "check",
                    "expected_routes")


class Cell:
    """One entry of ``workloads`` with its configuration, its family, its mix
    and its metrics."""

    def __init__(self, benchmark, name, bench_dir=BENCH_DIR, traffic_dir=None):
        cells = {w["name"]: w for w in benchmark["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in benchmark["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(os.path.join(
            os.path.dirname(bench_dir), self.config_entry["file"]))
        # the published keys sit at the top level of the file, as in the
        # source's config.json; "model" is that same dict under a name, and
        # what the family is handed
        self.config["model"] = {k: v for k, v in self.config.items()
                                if k not in HARNESS_SECTIONS}
        self.family = resolve(self.config["family"])
        self.reference = resolve(self.config["reference"])
        self.traffic = load_json(os.path.join(
            traffic_dir or os.path.join(bench_dir, "traffic"),
            self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in benchmark["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = []
        for m in benchmark["per_layer"]:
            if name in m.get("workloads", [name]):
                data = load_json(os.path.join(bench_dir, "metrics",
                                              m["name"] + ".json"))
                self.per_layer.append({**m, **data})

    def driver(self):
        return importlib.import_module(
            "benchmarks.drivers." + self.traffic["kind"])
