"""The benchmark's own tests run on the CPU at toy size:

    python -m pytest benchmark/tests -q

They need no chip and never report a time as a device metric.
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

TOY_DECODER = {
    "name": "toy_decoder", "kind": "serve", "source": "a toy for tests",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "reduced": [], "assumed": {},
    "precision": {"weights": "int8", "compute": "bfloat16"},
    "serve": {"slots": 4, "block_size": 16, "max_seq": 256},
    "limits": {"logit_gap_max": 0.25, "stream_faults": 0,
               "compiles_in_window": 0},
}
TOY_CLASSIFIER = {
    "name": "toy_mobilenet", "kind": "stream", "source": "a toy for tests",
    "width_multiplier": 1.0, "image_size": 32, "num_classes": 1001,
    "reduced": [], "assumed": {},
    "precision": {"weights": "float32", "compute": "bfloat16"},
    "limits": {"score_err_spread": 0.0028, "answers_malformed": 0,
               "compiles_in_window": 0},
}
TOY_MIXES = {
    "toy_closed": {"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 4},
                   "prompt_len": {"dist": "uniform", "min": 5, "max": 12},
                   "max_new": 24, "check_requests": 2},
    "toy_open": {"kind": "serve",
                 "arrival": {"mode": "poisson", "rate_per_s": 6.0,
                             "ramp_seconds": 0.5},
                 "prompt_len": {"dist": "lognormal", "median": 12,
                                "sigma": 0.8, "min": 4, "max": 70},
                 "max_new": 16, "check_requests": 3},
    "toy_hostfed": {"kind": "stream", "feed": "host", "batch": 128,
                    "max_inflight": 4, "sink_buffers": 4,
                    "pool_batches": 2, "check_rows": 128},
}


def add_toy_cells(root: str) -> None:
    """Adds two configurations, three mixes and three cells to the copy
    of the benchmark at ``root`` — as files and entries only."""
    bench = os.path.join(root, "benchmark")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for cfg in (TOY_DECODER, TOY_CLASSIFIER):
        rel = f"benchmark/configs/{cfg['name']}.json"
        with open(os.path.join(root, rel), "x") as f:
            json.dump(cfg, f)
        doc["configs"].append({"name": cfg["name"], "source": cfg["source"],
                               "file": rel, "reduced": [], "why": "toy"})
    for name, mix in TOY_MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "x") as f:
            json.dump(mix, f)
        config = ("toy_mobilenet" if mix["kind"] == "stream"
                  else "toy_decoder")
        cell = f"{config}.{name}"
        doc["workloads"].append({"name": cell, "config": config,
                                 "traffic": name, "chips": 1, "why": "toy"})
        family = "mobilenet_v1" if mix["kind"] == "stream" else "mistral_7b"
        for m in doc["end_to_end"] + doc["per_layer"]:
            if any(w.startswith(family + ".") for w in m.get("workloads",
                                                             [])):
                m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(doc, f)


def copy_benchmark(root: str) -> str:
    """BENCHMARK.json and benchmark/, and nothing else, into ``root``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    """A copy of BENCHMARK.json and benchmark/ with toy cells added."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench_copy")))
    add_toy_cells(root)
    return root


def run_toy(root, cell, seed=1, seconds=1.5, traced=False):
    from benchmark import run
    from benchmark.manifest import Manifest

    return run.run_cell(Manifest(root), cell, seed, seconds, traced,
                        {"platform": "cpu", "kind": "cpu", "count": 1})


