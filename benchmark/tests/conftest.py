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
    "model": "dense_decoder", "reference": "dense_decoder",
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
    "model": "mobilenet_v1", "reference": "mobilenet_v1",
    "width_multiplier": 1.0, "image_size": 32, "num_classes": 1001,
    "reduced": [], "assumed": {},
    "precision": {"weights": "float32", "compute": "bfloat16"},
    "limits": {"score_err_within": 0.0028, "answers_malformed": 0,
               "compiles_in_window": 0},
}
#: an architecture the benchmark does not know: its model module and its
#: reference come with it, from ``tests/toy/``
TOY_TWO_BRANCH = dict(TOY_DECODER, name="toy_two_branch",
                      model="two_branch_decoder",
                      reference="two_branch_decoder")
#: readers that count one architecture's bytes (their docstrings say so)
TIED_TO_DENSE_DECODER = ("decode_weight_roofline", "paged_attn_roofline")
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
    """Adds three configurations, one of them of a new architecture with
    its model module and its reference, three mixes and four cells to the
    copy of the benchmark at ``root`` — as files and entries only."""
    bench = os.path.join(root, "benchmark")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    toy = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
    for group, key in (("models", "model"), ("reference", "reference")):
        name = TOY_TWO_BRANCH[key] + ".py"
        with open(os.path.join(toy, group, name)) as src, \
                open(os.path.join(bench, group, name), "x") as dst:
            dst.write(src.read())
    for cfg in (TOY_DECODER, TOY_CLASSIFIER, TOY_TWO_BRANCH):
        rel = f"benchmark/configs/{cfg['name']}.json"
        with open(os.path.join(root, rel), "x") as f:
            json.dump(cfg, f)
        doc["configs"].append({"name": cfg["name"], "source": cfg["source"],
                               "file": rel, "reduced": [], "why": "toy"})
    for name, mix in TOY_MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "x") as f:
            json.dump(mix, f)
        if mix["kind"] == "stream":
            configs, family = ["toy_mobilenet"], "mobilenet_v1"
        else:
            configs, family = ["toy_decoder"], "mistral_7b"
            if name == "toy_closed":
                configs.append("toy_two_branch")
        for config in configs:
            cell = f"{config}.{name}"
            doc["workloads"].append({"name": cell, "config": config,
                                     "traffic": name, "chips": 1,
                                     "why": "toy"})
            for m in doc["end_to_end"] + doc["per_layer"]:
                if config == "toy_two_branch" and \
                        m["name"] in TIED_TO_DENSE_DECODER:
                    continue
                if any(w.startswith(family + ".")
                       for w in m.get("workloads", [])):
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


