"""The latent-attention, shortcut-expert decoder in the benchmark: a toy of
the same pattern (two attention blocks and two dense FFNs a published layer,
the experts across them, 16 routed of which 4 are held + 8 identity experts,
a softmax router) runs through ``run.run_cell`` on the CPU and is
``correct`` against its reference; the float8 control is not; the four
readers read hand-written observations, and nothing where a program has
nothing for them; the configuration states its cut."""

import copy
import json
import os

import pytest

from conftest import ROOT, copy_benchmark, run_toy

from benchmark.manifest import Manifest

CELL = "toy_scmoe_latent.toy_closed_long"
REAL = "longcat_flash_omni"
REAL_CELL = REAL + ".decode_c64_n1024"
READERS = ("latent_attn_roofline", "scmoe_expert_roofline",
           "scmoe_pairs_per_expert", "moe_zero_share")


def toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           REAL + ".json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(name="toy_scmoe_latent", hidden_size=64, ffn_hidden_size=128,
               num_attention_heads=4, vocab_size=512,
               expert_ffn_hidden_size=32, n_routed_experts=4, moe_topk=4,
               zero_expert_num=8, q_lora_rank=32, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_layers=2)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_first=4)
    cfg["assumed"] = dict(cfg["assumed"],
                          mla_scale_q_lora={"value": 2.0 ** 0.5},
                          mla_scale_kv_lora={"value": 2.0 ** 0.5})
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
    # float32 compute over the same bfloat16 weights: what is left is the
    # order of float32 sums, 1e-5 of a logit and no token changed; the
    # float8 control's worst stretch of 8 tokens reads 0.004 to 0.03
    cfg["limits"] = dict(cfg["limits"], logit_gap_max=0.002,
                         gap_stretch_tokens=8)
    return cfg


@pytest.fixture(scope="module")
def latent_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench_latent")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    rel = "benchmark/configs/toy_scmoe_latent.json"
    with open(os.path.join(root, rel), "x") as f:
        json.dump(toy_config(), f)
    doc["configs"].append({"name": "toy_scmoe_latent", "source": "a toy",
                           "file": rel, "reduced": [], "why": "toy"})
    with open(os.path.join(root, "benchmark", "traffic",
                           "toy_closed_long.json"), "x") as f:
        json.dump({"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 3},
                   "prompt_len": {"dist": "uniform", "min": 5, "max": 12},
                   "max_new": 40, "check_requests": 2}, f)
    doc["workloads"].append({"name": CELL, "config": "toy_scmoe_latent",
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


def test_toy_cell_is_correct(latent_root):
    r = run_toy(latent_root, CELL, seed=2**31 + 7, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _check(r, "compiles_in_window")["value"] == 0
    # the cell's tails follow the host's pace, not the program (PERF.md §6)
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("seed", [41, 42])
def test_float8_control_is_not_correct(latent_root, seed):
    from benchmark import control

    row = control.read_seed(Manifest(latent_root), CELL, seed, 2.0)
    limit = toy_config()["limits"]["logit_gap_max"]
    assert row["program"]["logit_gap_max"] <= limit / 3
    # which requests a 2 s window finishes is the machine's: the control
    # read 0.004 to 0.03 over repeats, the program 1e-5
    assert row["control"]["logit_gap_max"] > limit
    assert row["control"]["logit_gap_max"] > \
        30 * row["program"]["logit_gap_max"]


def test_the_configuration_states_its_cut():
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        ["num_layers", "n_routed_experts", "vocab_size"])
    assert cfg["published"] == dict(cfg["published"], num_layers=28,
                                    n_routed_experts=512, vocab_size=131072)
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 16384)
    # no width differs from the source
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["moe_topk"], cfg["zero_expert_num"],
            cfg["routed_scaling_factor"], cfg["rope_theta"]) == (
        6144, 12288, 2048, 64, 1536, 512, 128, 64, 128, 12, 256, 6, 10**7)
    assert set(cfg["assumed"]) >= {
        "mla_scale_q_lora", "mla_scale_kv_lora", "norm_topk_prob",
        "hidden_act", "tie_word_embeddings", "rope", "weights"}
    assert cfg["assumed"]["mla_scale_q_lora"]["value"] == 2.0
    assert cfg["assumed"]["mla_scale_kv_lora"]["value"] == 12 ** 0.5
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert cfg["deployment"]["rank"] == 0
    mix = m.mix(m.cell(REAL_CELL))
    assert mix == {"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 64},
                   "prompt_len": {"dist": "uniform", "min": 16, "max": 32},
                   "max_new": 1024, "check_requests": 2}
    reported = {x["name"] for x in m.per_layer(REAL_CELL)}
    assert reported >= set(READERS) | {"step_mfu.serve", "device_idle.serve"}
    assert {x["name"] for x in m.end_to_end(REAL_CELL)} == {
        "serve_tok_s", "setup_s"}


# -- the readers, on observations written by hand ---------------------------

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS


def _obs(cfg):
    """A window of one second from perf_counter 10.0; the profiler ran
    from 10.2 to 10.6 and holds 2 decode calls; two decode spans closed
    meanwhile (9 and 11 experts hit a step a layer, 8 steps, 4 layers)."""
    def span(start_ms, dur_ms, **a):
        return ("serve.decode", LO + int(start_ms * MS), int(dur_ms * MS),
                dict(a, chunk=8, occupancy=64))
    all_pairs = 64 * 8 * 12 * 4
    spans = [span(0, 190, moe_pairs=1, moe_experts_hit=1,
                  moe_max_per_expert=1, moe_zero_pairs=all_pairs // 2),
             span(200, 180, moe_pairs=16 * 4 * 8, moe_experts_hit=9 * 8 * 4,
                  moe_max_per_expert=4, moe_zero_pairs=all_pairs // 4),
             span(400, 180, moe_pairs=2 * 16 * 4 * 8,
                  moe_experts_hit=11 * 8 * 4, moe_max_per_expert=5,
                  moe_zero_pairs=all_pairs // 4)]
    slots = cfg["serve"]["slots"]
    rows = slots * cfg["moe_topk"]
    ops = {f"%ragged-dot-none.1 bf16[{rows},2048] custom-call": 0.020,
           f"%ragged-dot-none.2 f32[{rows},6144] custom-call": 0.010,
           "%ragged-dot-none.9 bf16[384,2048] custom-call": 5.0,
           f"%paged_latent_attention.3 bf16[{slots},64,512] custom-call":
           0.008,
           f"%paged_attention.4 bf16[{slots},64,128] custom-call": 3.0,
           "%fusion.1 bf16[64,6144] fusion": 1.0}
    return {"cfg": cfg, "spans": spans, "window_ns": [LO, HI],
            "window": [10.0, 11.0], "window_s": 1.0, "chips": 1,
            "decoded": [(10.3, 410), (10.5, 100), (10.9, 700)],
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "trace": {"host_span": (10.2, 10.6), "ops": ops,
                      "modules": {"jit_decode_chunk(1)": 0.3},
                      "module_calls": {"jit_decode_chunk(1)": 2}}}


def test_readers_on_hand_written_observations():
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    # 2 calls x mean (9, 11) x 32 step-layers x 75.5 MB over 0.030 s
    need = 2 * 10 * 32 * 3 * 6144 * 2048 * 2
    assert m.reader("scmoe_expert_roofline")(obs) == pytest.approx(
        100 * need / 819e9 / 0.030)
    # 2 tokens pulled in the traced stretch (contexts 410 and 100), 2
    # decode spans, 2 calls: 8 blocks x 1,152 B a position
    assert m.reader("latent_attn_roofline")(obs) == pytest.approx(
        100 * (2 * 8 * 1152 * 510 / 2) / 819e9 / 0.008)
    # all three spans end inside the window: pairs a step a layer an
    # expert = (1 / 512 + 1 + 2) / 3
    assert m.reader("scmoe_pairs_per_expert")(obs) == pytest.approx(
        (1 / (8 * 4 * 16) + 1 + 2) / 3)
    # identity pairs over all pairs of the live rows: (1/2 + 1/4 + 1/4) / 3
    assert m.reader("moe_zero_share")(obs) == pytest.approx(100 / 3)


def test_rooflines_read_on_where_the_ring_has_dropped_the_traced_stretch():
    """Past ≈ 1,900 tokens/s the ring no longer holds the spans closed
    while the profiler ran: the two trace readers then take the decode
    calls' rows, steps and experts hit from the spans of the same window
    that are left, and the calls of the stretch from its tokens."""
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    obs["spans"] = obs["spans"][2:]          # only the span closed at 10.58
    obs["trace"]["host_span"] = (10.0, 10.5)   # ... after the profiler
    obs["decoded"] = [(10.1 + i * 1e-4, 300) for i in range(1024)]
    # 1,024 tokens pulled = 2 calls of 64 rows x 8 steps; 2 calls traced
    assert m.reader("latent_attn_roofline")(obs) == pytest.approx(
        100 * (2 * 1024 * 8 * 1152 * 300 / 2) / 819e9 / 0.008)
    need = 2 * 11 * 32 * 3 * 6144 * 2048 * 2
    assert m.reader("scmoe_expert_roofline")(obs) == pytest.approx(
        100 * need / 819e9 / 0.030)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_nothing_is_to_read(name):
    """A parent without the span args, the kernel or the operations: no
    number, no exception."""
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    obs["spans"] = [(k, ts, d, {"chunk": 8, "occupancy": 64})
                    for k, ts, d, _a in obs["spans"]]
    obs["trace"]["ops"] = {"%fusion.1 bf16[64,6144] fusion": 1.0}
    assert m.reader(name)(obs) is None
    assert m.reader(name)({"cfg": cfg, "window_ns": [LO, HI],
                           "spans": [], "trace": None,
                           "peaks": None}) is None
