"""``BENCHMARK.json`` against the files it names, and the harness against
the rule that a later PR adds files and entries and edits none."""

import hashlib
import inspect
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import (ROOT, TOY_TWO_BRANCH, add_toy_cells, copy_benchmark,
                      run_toy)

from benchmark.manifest import Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmark"] and 1 <= doc["run_seconds"] <= 51
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          e["name"]))
    assert len(names) == len(set(names))
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    assert len(json.dumps(doc)) < 64 * 1024


def test_every_cell_finds_its_files_and_reports_enough(doc):
    m = Manifest(ROOT)
    cells = [w["name"] for w in doc["workloads"]]
    used = set()
    for w in doc["workloads"]:
        cfg, mix = m.config(w), m.mix(w)
        used.add(w["config"])
        assert cfg["name"] == w["config"] and cfg["kind"] == mix["kind"]
        assert cfg["reduced"] == next(
            c["reduced"] for c in doc["configs"] if c["name"] == w["config"])
        assert "assumed" in cfg and "source" in cfg and "limits" in cfg
        e2e = [x["name"] for x in m.end_to_end(w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert m.per_layer(w["name"])
    assert used == {c["name"] for c in doc["configs"]}
    e2e_of = {c: {x["name"] for x in m.end_to_end(c)} for c in cells}
    for p in doc["per_layer"]:
        assert set(p) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert p["workloads"], p["name"]     # every reader names its cells
        for c in p["workloads"]:
            assert c in cells and p["moves"] in e2e_of[c], (p["name"], c)
        assert callable(m.reader(p["name"]))
    for e in doc["end_to_end"]:
        for c in e.get("workloads", []):
            assert c in cells
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_readers_return_nothing_when_there_is_nothing_to_read(doc):
    m = Manifest(ROOT)
    empty = {"spans": [], "window_ns": [0, 1], "window_s": 1.0,
             "trace": None, "peaks": None, "chips": 1, "cfg": {}}
    for p in doc["per_layer"]:
        assert m.reader(p["name"])(dict(empty)) is None, p["name"]


def _digest(root):
    out = {}
    for base, _dirs, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_is_added_as_files_and_entries_only(tmp_path):
    """A copy of the benchmark gains three configurations, three mixes and
    four cells, and with one configuration an architecture it did not
    know (its model module, its reference); no file that was there changes
    but BENCHMARK.json, and the new cells are found and run."""
    root = copy_benchmark(str(tmp_path))
    before = _digest(root)
    add_toy_cells(root)
    after = _digest(root)
    changed = {k for k in before if before[k] != after[k]}
    assert changed == {"BENCHMARK.json"}
    assert len(after) == len(before) + 8
    r = run_toy(root, "toy_decoder.toy_closed", seed=3, seconds=1.0)
    assert r["correct"] and r["metrics"]["serve_tok_s"]["value"] > 0
    cell = "toy_two_branch.toy_closed"
    r = run_toy(root, cell, seed=3, seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["metrics"]["serve_tok_s"]["value"] > 0
    assert {m["name"] for m in Manifest(root).per_layer(cell)} \
        >= {"step_mfu.serve", "device_idle.serve", "serve_live_slots"}
    # against the reference of another architecture it is not correct
    with open(os.path.join(root, "benchmark/configs/toy_two_branch.json"),
              "w") as f:
        json.dump(dict(TOY_TWO_BRANCH, reference="dense_decoder"), f)
    r = run_toy(root, cell, seed=3, seconds=1.0)
    assert not r["correct"]
    assert [c["name"] for c in r["checks"] if not c["ok"]] == [
        "logit_gap_max"]


@pytest.mark.parametrize("look_up,key,group", [
    ("model", "model", "models"), ("reference", "reference", "reference"),
    ("driver", "kind", "drivers")])
def test_a_name_with_no_file_raises_with_those_that_exist(look_up, key,
                                                          group):
    m = Manifest(ROOT)
    with pytest.raises(KeyError) as e:
        getattr(m, look_up)({key: "nonesuch"})
    assert "nonesuch" in str(e.value)
    assert m.names(group) and all(n in str(e.value)
                                  for n in m.names(group))


def test_every_configuration_names_files_that_are_there(doc):
    """``kind``, ``model`` and ``reference`` of every configuration's file
    resolve, and each module has what the driver of that kind calls."""
    m = Manifest(ROOT)
    asked = {"serve": (("ZOO_NAME", "CONTROL", "weights", "register",
                        "pipeline_options", "flops_per_token"),
                       ("served_gaps", "control_gaps")),
             "stream": (("ZOO_NAME", "CONTROL", "weights", "register",
                         "flops_per_frame"), ("logits_in_blocks",))}
    for c in doc["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert isinstance(m.driver(cfg), type)
        of_model, of_reference = asked[cfg["kind"]]
        assert all(hasattr(m.model(cfg), n) for n in of_model), c["name"]
        assert all(hasattr(m.reference(cfg), n) for n in of_reference)


def test_run_py_and_the_drivers_name_no_model():
    """What belongs to one architecture reaches a driver through
    ``ctx.model`` and ``ctx.reference`` alone: ``run.py`` and the files
    under ``drivers/`` name no model, no reference, and nothing that
    ``weights.py`` or ``flops.py`` defines or ``adapter.py`` registers."""
    from benchmark import adapter, flops, weights

    m = Manifest(ROOT)
    words = set(m.names("models")) | set(m.names("reference")) | {
        n for mod in (weights, flops) for n in vars(mod)
        if not n.startswith("_") and getattr(vars(mod)[n], "__module__",
                                             None) in (None, mod.__name__)
        and not inspect.ismodule(vars(mod)[n])
    } | {n for n in vars(adapter) if n.startswith("register_")}
    assert {"dense_decoder", "mobilenet_v1", "decoder_tree",
            "register_decoder", "decoder_flops_per_token",
            "MOBILENET_V1_BLOCKS"} <= words
    files = [os.path.join(ROOT, "benchmark", "run.py")] + [
        os.path.join(ROOT, "benchmark", "drivers", n + ".py")
        for n in m.names("drivers")]
    for path in files:
        with open(path) as f:
            text = f.read()
        assert not [w for w in words
                    if re.search(rf"\b{re.escape(w)}\b", text)], path
        assert not re.search(
            r"^\s*(from|import)\s.*\b(weights|flops|reference|models)\b",
            text, re.M), path
    with open(files[0]) as f:
        assert not re.search(r"\bif kind\b|\bkind ==", f.read())


def _run(args, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_py_refuses_a_machine_without_a_chip(doc):
    cell = doc["workloads"][0]["name"]
    p = _run(["benchmark/run.py", "--workload", cell, "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT,
             {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "needs an accelerator" in p.stderr
    assert not p.stdout.strip()


def test_run_py_fails_without_the_program(tmp_path, doc):
    """In a directory that holds only BENCHMARK.json and benchmark/ there
    is no system under test: non-zero, and no result line."""
    root = copy_benchmark(str(tmp_path))
    add_toy_cells(root)
    # past the look for a chip, so the missing program is what stops it
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark import run; "
            "from benchmark.manifest import Manifest; "
            "run.run_cell(Manifest(%r), 'toy_decoder.toy_closed', 1, 1.0, "
            "False, {'platform': 'cpu', 'kind': 'cpu', 'count': 1})"
            % (root, root))
    p = _run(["-c", code], root, {"JAX_PLATFORMS": "cpu",
                                  "PYTHONPATH": ""})
    assert p.returncode != 0 and "nnstreamer_tpu" in p.stderr
    assert not p.stdout.strip()
