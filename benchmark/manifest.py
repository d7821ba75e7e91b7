"""Reads ``BENCHMARK.json`` and finds a cell's files by the names in it:
its configuration's file, ``traffic/<mix>.json`` and, for each per-layer
metric the cell reports, ``layer_metrics/<metric>.py``.  Adding a cell, a
configuration, a mix or a metric is adding files and entries."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.bench_dir = os.path.join(root, self.doc["paths"][0])

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {cell['config']!r}")

    def mix(self, cell: dict) -> dict:
        path = os.path.join(self.bench_dir, "traffic",
                            cell["traffic"] + ".json")
        with open(path) as f:
            return json.load(f)

    def _reported(self, section: str, cell_name: str) -> list:
        return [m for m in self.doc[section]
                if cell_name in m.get("workloads", [cell_name])]

    def end_to_end(self, cell_name: str) -> list:
        return self._reported("end_to_end", cell_name)

    def per_layer(self, cell_name: str) -> list:
        return self._reported("per_layer", cell_name)

    def reader(self, metric_name: str):
        """The ``read(observed) -> number | None`` of a per-layer metric."""
        path = os.path.join(self.bench_dir, "layer_metrics",
                            metric_name + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark.layer_metrics." + metric_name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
