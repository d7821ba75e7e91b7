"""Reads ``BENCHMARK.json`` and finds a cell's files by the names in it:
its configuration's file, ``traffic/<mix>.json`` and, for each per-layer
metric the cell reports, ``layer_metrics/<metric>.py``; and by the names
in the configuration's file ``drivers/<kind>.py``, ``models/<model>.py``
and ``reference/<reference>.py``.  Adding a cell, a configuration, a mix,
a metric or an architecture is adding files and entries.

**How an architecture enters.**  Its configuration's file names three
files, each new with it:

``models/<model>.py``
    what the driver of its kind asks of a model.  For ``serve``:
    ``ZOO_NAME``, ``weights(cfg, seed)``, ``register(name, cfg, tree)``,
    ``pipeline_options(cfg)``, ``flops_per_token(cfg, context)`` and
    ``CONTROL``, the keyword arguments that put the reference at the
    precision below the one the configuration states.  For ``stream``:
    ``ZOO_NAME``, ``weights``, ``register``, ``flops_per_frame(cfg)``,
    ``CONTROL``.  ``register`` is the program-facing part: the one
    function of the module whose body may import ``nnstreamer_tpu`` (the
    two that exist keep theirs in ``adapter.py``).  Weights and counts
    import nothing of the program.
``reference/<reference>.py``
    the plain reference: ``served_gaps`` and ``control_gaps`` for
    ``serve``, ``logits_in_blocks`` for ``stream``.  It imports nothing of
    the program and nothing of the model module.
``drivers/<kind>.py``
    only where no driver that exists fits, as for a model whose step does
    not yield one token a sequence: a class ``Driver`` with ``load()``,
    ``run()``, ``close()`` and ``control_reading()``.

Readers tied to one architecture's counts (``decode_weight_roofline``,
``paged_attn_roofline``) say so; a new architecture brings readers of its
own and is added only to the lists of those that read what its driver
observes (``step_mfu.*``, ``device_idle.*``, the serve-loop readers).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

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

    def names(self, group: str) -> list:
        """The modules ``<group>/*.py`` of this benchmark."""
        return sorted(f[:-3] for f in os.listdir(
            os.path.join(self.bench_dir, group))
            if f.endswith(".py") and f != "__init__.py")

    def _load(self, group: str, name: str):
        """The module ``<group>/<name>.py``, loaded from its file; a name
        with no file raises with the names that exist."""
        path = os.path.join(self.bench_dir, group, name + ".py")
        if not os.path.isfile(path):
            raise KeyError(f"no {group}/{name}.py in {self.bench_dir}; it "
                           f"has {self.names(group)}")
        modname = f"benchmark.{group}.{name.replace('.', '_')}"
        mod = sys.modules.get(modname)
        if getattr(mod, "__file__", None) == path:
            return mod
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        # dataclasses look their module up by name while it is executed
        sys.modules[modname] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
        return mod

    def reader(self, metric_name: str):
        """The ``read(observed) -> number | None`` of a per-layer metric."""
        return self._load("layer_metrics", metric_name).read

    def driver(self, cfg: dict):
        """The ``Driver`` class of a configuration's ``kind``."""
        return self._load("drivers", cfg["kind"]).Driver

    def model(self, cfg: dict):
        """The module of a configuration's ``model``."""
        return self._load("models", cfg["model"])

    def reference(self, cfg: dict):
        """The module of the plain reference a configuration is compared
        with."""
        return self._load("reference", cfg["reference"])
