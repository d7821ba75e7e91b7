"""The latent-attention decoder with group-limited experts in the benchmark:
a toy of the same pattern (layer 0 dense, then expert layers of 16 routed
experts in 4 groups of 4, 2 groups kept, 4 a token, 4 held, a shared
expert; YaRN over a shrunk original context) runs through ``run.run_cell``
on the CPU and is ``correct`` against its reference; the float8 control is
not; the configuration states its cut and its byte arithmetic is the
tree's; the three readers read hand-written observations, and nothing
where a program has nothing for them."""

import copy
import json
import os

import pytest

from conftest import ROOT, copy_benchmark, run_toy

from benchmark.manifest import Manifest

CELL = "toy_mla_moe.toy_closed_long"
REAL = "ax_k1"
REAL_CELL = REAL + ".decode_c64_n1024"
READERS = ("grouped_moe_expert_roofline", "grouped_moe_pairs_per_expert",
           "moe_group_hit_share")


def toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           REAL + ".json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(name="toy_mla_moe", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, vocab_size=512,
               moe_intermediate_size=32, n_routed_experts=4,
               num_experts_per_tok=4, q_lora_rank=32, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_hidden_layers=3, n_group=4, topk_group=2)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_first=4)
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
    # float32 compute over the same bfloat16 weights: what is left is the
    # order of float32 sums, 1e-6 of a logit and no token changed; the
    # float8 control moves a logit by 1 (tests/test_mla_moe.py)
    cfg["limits"] = dict(cfg["limits"], logit_gap_max=0.002,
                         gap_stretch_tokens=8)
    return cfg


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench_mla_moe")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    rel = "benchmark/configs/toy_mla_moe.json"
    with open(os.path.join(root, rel), "x") as f:
        json.dump(toy_config(), f)
    doc["configs"].append({"name": "toy_mla_moe", "source": "a toy",
                           "file": rel, "reduced": [], "why": "toy"})
    with open(os.path.join(root, "benchmark", "traffic",
                           "toy_closed_long.json"), "x") as f:
        json.dump({"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 3},
                   "prompt_len": {"dist": "uniform", "min": 5, "max": 12},
                   "max_new": 40, "check_requests": 2}, f)
    doc["workloads"].append({"name": CELL, "config": "toy_mla_moe",
                             "traffic": "toy_closed_long", "chips": 1,
                             "why": "toy"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if any(w.startswith(REAL + ".") for w in m.get("workloads", [])):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def _check(result, name):
    return next(c for c in result["checks"] if c["name"] == name)


def test_toy_cell_is_correct(toy_root):
    r = run_toy(toy_root, CELL, seed=2**31 + 11, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _check(r, "compiles_in_window")["value"] == 0
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("seed", [71, 72])
def test_float8_control_is_not_correct(toy_root, seed):
    from benchmark import control

    row = control.read_seed(Manifest(toy_root), CELL, seed, 2.0)
    limit = toy_config()["limits"]["logit_gap_max"]
    assert row["program"]["logit_gap_max"] <= limit / 3
    assert row["control"]["logit_gap_max"] > limit
    assert row["control"]["logit_gap_max"] > \
        30 * row["program"]["logit_gap_max"]


def test_the_configuration_states_its_cut():
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size"])
    assert cfg["published"] == dict(cfg["published"], num_hidden_layers=61,
                                    n_routed_experts=192, vocab_size=163840)
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (8, 12, 20480)
    # no width differs from the source, the router keeps its groups
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_tok"], cfg["n_group"],
            cfg["topk_group"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["first_k_dense_replace"]) == (
        7168, 18432, 2048, 64, 1536, 512, 128, 64, 128, 8, 8, 4, 1, 2.5,
        10000, 1)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert set(cfg["assumed"]) >= {
        "norm_placement", "topk_method", "router_bias", "group_score",
        "rope", "hidden_act", "tie_word_embeddings", "renorm_eps",
        "seq_aux", "weights"}
    assert cfg["assumed"]["renorm_eps"]["value"] == 1e-20
    d = cfg["deployment"]
    assert (d["chips_sharing_a_layer"], d["rank"], d["held_first"]) \
        == (16, 0, 0)
    # 12 of 192 a rank of 16; a group of 24 is two ranks
    assert cfg["published"]["n_routed_experts"] \
        == d["chips_sharing_a_layer"] * cfg["n_routed_experts"]
    assert cfg["published"]["n_routed_experts"] // cfg["n_group"] \
        == 2 * cfg["n_routed_experts"]
    mix = m.mix(m.cell(REAL_CELL))
    assert mix == {"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 64},
                   "prompt_len": {"dist": "uniform", "min": 16, "max": 32},
                   "max_new": 1024, "check_requests": 2}
    reported = {x["name"] for x in m.per_layer(REAL_CELL)}
    assert reported >= set(READERS) | {"step_mfu.serve", "device_idle.serve"}
    assert {x["name"] for x in m.end_to_end(REAL_CELL)} == {
        "serve_tok_s", "setup_s"}


def test_the_byte_arithmetic_is_the_trees():
    """11,051,728,896 B of weights (every matrix bfloat16, the router
    float32; the gains and the correction bias beside them) and 10,240 B
    of cache a token (8 latent layers x a row of 576 values padded to 640
    lanes x 2 B), as the program allocates them; the cell's pool of
    64 x 66 blocks of 16 is 692,060,160 B."""
    import math

    from benchmark.models import mla_moe_decoder as M
    from nnstreamer_tpu.models import llama, zoo

    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    assert M.weight_bytes(cfg) == 11_051_728_896
    assert M.weight_bytes(cfg) / 16_909_336_064 == pytest.approx(0.6536,
                                                                 abs=1e-4)
    M.register("count_only_axk1", cfg, None)
    lcfg = zoo.build("count_only_axk1", {}).config
    gains = 4 * (8 * (2 * 7168 + 1536 + 512) + 7 * 192 + 7168)
    assert llama.param_bytes_estimate(lcfg, param_dtype="bfloat16") \
        == M.weight_bytes(cfg) + gains
    assert llama.paged_cache_bytes(lcfg, 1, 1) == 10_240
    blocks = cfg["serve"]["slots"] * math.ceil((32 + 1024) / 16)
    assert blocks == 4224
    assert llama.paged_cache_bytes(lcfg, blocks, 16) == 692_060_160


# -- the readers, on observations written by hand ---------------------------

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS


def _obs(cfg):
    """A window of one second from perf_counter 10.0; the profiler ran
    from 10.2 to 10.6 and holds 15 executions of the decode step's
    grouped product; two decode spans closed meanwhile (11 and 12 experts
    hit a step a layer, 8 steps, 7 expert layers) and one before."""
    def span(start_ms, dur_ms, **a):
        return ("serve.decode", LO + int(start_ms * MS), int(dur_ms * MS),
                dict(a, chunk=8, occupancy=64))
    step_layers = 8 * 7
    spans = [span(0, 190, moe_pairs=1, moe_experts_hit=1,
                  moe_group_hits=64 * step_layers),
             span(200, 180, moe_pairs=32 * step_layers,
                  moe_experts_hit=11 * step_layers,
                  moe_group_hits=16 * step_layers),
             span(400, 180, moe_pairs=64 * step_layers,
                  moe_experts_hit=12 * step_layers,
                  moe_group_hits=16 * step_layers)]
    rows = cfg["serve"]["slots"] * cfg["num_experts_per_tok"]
    ops = {f"%ragged-dot-swiglu.1 f32[{rows},7168] custom-call": 0.030,
           "%ragged-dot-swiglu.2 f32[256,7168] custom-call": 5.0,
           "%fusion.1 bf16[64,7168] fusion": 1.0}
    return {"cfg": cfg, "spans": spans, "window_ns": [LO, HI],
            "window": [10.0, 11.0], "window_s": 1.0, "chips": 1,
            "decoded": [(10.3, 410), (10.5, 100), (10.9, 700)],
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "trace": {"host_span": (10.2, 10.6), "ops": ops,
                      "op_calls": {
                          f"%ragged-dot-swiglu.1 f32[{rows},7168] "
                          "custom-call": 15,
                          "%ragged-dot-swiglu.2 f32[256,7168] custom-call":
                          3},
                      "modules": {"jit_decode_chunk(1)": 0.3},
                      "module_calls": {"jit_decode_chunk(1)": 2}}}


def test_readers_on_hand_written_observations():
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    # 15 executions x mean (11, 12) experts hit x 88.1 MB over 0.030 s
    need = 15 * 11.5 * 3 * 7168 * 2048 * 2
    assert m.reader("grouped_moe_expert_roofline")(obs) == pytest.approx(
        100 * need / 819e9 / 0.030)
    # all three spans end inside the window: pairs a step a layer an
    # expert = (1 / (56 x 12) + 32 / 12 + 64 / 12) / 3
    assert m.reader("grouped_moe_pairs_per_expert")(obs) == pytest.approx(
        (1 / (56 * 12) + 32 / 12 + 64 / 12) / 3)
    # rows that kept the held group over all live rows: (1 + 1/4 + 1/4) / 3
    assert m.reader("moe_group_hit_share")(obs) == pytest.approx(50.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_nothing_is_to_read(name):
    """A parent without the span args, the kernel or the operations: no
    number, no exception."""
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    obs["spans"] = [(k, ts, d, {"chunk": 8, "occupancy": 64})
                    for k, ts, d, _a in obs["spans"]]
    obs["trace"]["ops"] = {"%fusion.1 bf16[64,7168] fusion": 1.0}
    assert m.reader(name)(obs) is None
    assert m.reader(name)({"cfg": cfg, "window_ns": [LO, HI],
                           "spans": [], "trace": None,
                           "peaks": None}) is None
