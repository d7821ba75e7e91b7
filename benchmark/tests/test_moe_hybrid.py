"""The patterned sparse-expert decoder in the benchmark: a toy of the same
pattern (window-window-window-full twice, layer 0 dense, 16 routed experts
of which 4 are held, q/k norm, heads wider than hidden / heads) runs
through ``run.run_cell`` on the CPU and is ``correct`` against its
reference; the float8 control is not; the three readers read hand-written
observations; the counts are the issue's arithmetic."""

import copy
import json
import os

import pytest

from conftest import ROOT, copy_benchmark, run_toy

from benchmark.manifest import Manifest

CELL = "toy_moe_hybrid.toy_closed_long"
REAL = "k_exaone_236b_a23b"


def toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           REAL + ".json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(name="toy_moe_hybrid", hidden_size=64, intermediate_size=192,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               vocab_size=512, moe_intermediate_size=32, num_experts=4,
               num_experts_per_tok=4, sliding_window=8)
    cfg["sliding_windows"] = [8 if w else 0 for w in cfg["sliding_windows"]]
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_first=4)
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
    # float32 compute over the same bfloat16 weights: what is left is the
    # order of float32 sums, 1e-5 of a logit and no token changed; the
    # float8 control's worst stretch of 8 tokens reads 0.02 and more
    # ... over stretches of 8 tokens: answers here are 40 tokens long
    cfg["limits"] = dict(cfg["limits"], logit_gap_max=0.002,
                         gap_stretch_tokens=8)
    return cfg


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench_hybrid")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    rel = "benchmark/configs/toy_moe_hybrid.json"
    with open(os.path.join(root, rel), "x") as f:
        json.dump(toy_config(), f)
    doc["configs"].append({"name": "toy_moe_hybrid", "source": "a toy",
                           "file": rel, "reduced": [], "why": "toy"})
    # contexts to 12 + 40: more than four windows of 8
    with open(os.path.join(root, "benchmark", "traffic",
                           "toy_closed_long.json"), "x") as f:
        json.dump({"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 3},
                   "prompt_len": {"dist": "uniform", "min": 5, "max": 12},
                   "max_new": 40, "check_requests": 2}, f)
    doc["workloads"].append({"name": CELL, "config": "toy_moe_hybrid",
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


def test_toy_cell_is_correct(hybrid_root):
    r = run_toy(hybrid_root, CELL, seed=2**31 + 7, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _check(r, "compiles_in_window")["value"] == 0
    # the end-to-end metrics the real cell reports (PERF.md §6, PR 29,
    # says why the first-token tail is not among them)
    assert set(r["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("seed", [41, 42])
def test_float8_control_is_not_correct(hybrid_root, seed):
    from benchmark import control

    row = control.read_seed(Manifest(hybrid_root), CELL, seed, 2.0)
    limit = toy_config()["limits"]["logit_gap_max"]
    assert row["program"]["logit_gap_max"] <= limit / 3
    assert row["control"]["logit_gap_max"] > 3 * limit


def test_the_counts_are_the_issues_arithmetic():
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL + ".decode_c64_n768"))
    model = m.model(cfg)
    assert model.expert_bytes(cfg) == 3 * 6144 * 2048 * 2
    assert model.n_sparse_layers(cfg) == 7
    # attention 113.25 M a layer, layer 0's FFN 339.74 M, a sparse layer's
    # router 0.79 M + shared 37.75 M + 1.0 expert in expectation, the head
    attn, expert = 6144 * 8192 * 2 + 6144 * 1024 * 2, 3 * 6144 * 2048
    want = (8 * attn + 3 * 6144 * 18432
            + 7 * (6144 * 128 + expert + 8 * 16 / 128 * expert)
            + 6144 * 19200)
    assert model.matmul_params_per_token(cfg) == want
    # at context 410: two full layers read 410 positions, six window
    # layers 128; 4,096 B a position
    assert model.kv_bytes_attended(cfg, 410) == 4096 * (2 * 410 + 6 * 128)
    assert model.kv_bytes_attended(cfg, 100) == 4096 * 8 * 100
    assert model.flops_per_token(cfg, 410) == 2 * want + 4 * 8192 * (
        2 * 410 + 6 * 128)
    kinds = [k[3] for k in model.layer_kinds(cfg)]
    assert kinds == ["window128.rope.dense"] + [
        "window128.rope.experts", "window128.rope.experts",
        "full.nope.experts", "window128.rope.experts",
        "window128.rope.experts", "window128.rope.experts",
        "full.nope.experts"][:7]


def test_the_configuration_states_its_cut():
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL + ".decode_c64_n768"))
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size",
         "num_nextn_predict_layers"])
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["num_experts"] == 128
    assert cfg["published"]["vocab_size"] == 153600
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (8, 16, 19200, 0)
    # no width differs from the source
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (6144, 18432, 128, 2048, 8, 128,
                                            64, 8)
    assert set(cfg["assumed"]) >= {"norm_placement", "qk_norm",
                                   "rope_layers", "router_bias", "weights"}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8


def test_the_compared_number_is_a_stretchs_mean():
    """One flipped token in a request of agreeing ones reads a quarter of
    its gap over stretches of 4, a run of four bad tokens reads in full;
    the prompt before the served positions and the padding after them are
    inside no stretch that a served position starts."""
    import numpy as np

    from benchmark.reference.moe_hybrid_decoder import stretch_mean

    T = 20
    tokens = np.zeros((2, T), np.int32)
    tokens[0, :16] = 7     # prompt 4 + 12 served, 4 of padding
    tokens[1, :20] = 7     # no padding at all
    raw = np.zeros((2, T), np.float32)
    raw[:, :3] = 50.0      # prompt positions: never served
    raw[0, 15:] = 90.0     # what follows the last served token
    raw[0, 8] = 2.0        # one flip
    raw[1, 10:14] = 1.0    # four bad tokens in a row
    got = np.asarray(stretch_mean(raw, tokens, 4))
    served0 = got[0, 3:15]
    assert served0.max() == 0.5 and served0[-1] == 0.0
    assert got[1, 3:19].max() == 1.0
    assert np.asarray(stretch_mean(raw, tokens, 1))[0, 8] == 2.0


# -- the readers, on observations written by hand ---------------------------

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS


def _obs(cfg):
    """A window of one second from perf_counter 10.0; the profiler ran
    from 10.2 to 10.6 and holds 2 decode calls; two decode spans closed
    meanwhile (13 and 15 experts hit a step a layer, 8 steps, 7 layers)."""
    def span(start_ms, dur_ms, **a):
        return ("serve.decode", LO + int(start_ms * MS), int(dur_ms * MS),
                dict(a, chunk=8, occupancy=64))
    hits = [13 * 8 * 7, 15 * 8 * 7]
    spans = [span(0, 190, moe_pairs=1, moe_experts_hit=1,
                  moe_max_per_expert=1),
             span(200, 180, moe_pairs=4 * 16 * 7 * 8,
                  moe_experts_hit=hits[0], moe_max_per_expert=9),
             span(400, 180, moe_pairs=3 * 16 * 7 * 8,
                  moe_experts_hit=hits[1], moe_max_per_expert=11)]
    rows = cfg["serve"]["slots"] * cfg["num_experts_per_tok"]
    slots = cfg["serve"]["slots"]
    ops = {f"%ragged-dot-none.1 bf16[{rows},2048] custom-call": 0.020,
           f"%ragged-dot-none.2 f32[{rows},6144] custom-call": 0.010,
           "%ragged-dot-none.9 bf16[256,2048] custom-call": 5.0,
           f"%paged_attention.3 bf16[{slots},64,128] custom-call": 0.040,
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
    cfg = m.config(m.cell(REAL + ".decode_c64_n768"))
    obs = _obs(cfg)
    # 2 calls x mean (13, 15) x 56 step-layers x 75.5 MB over 0.030 s
    need = 2 * 14 * 56 * 3 * 6144 * 2048 * 2
    assert m.reader("moe_expert_roofline")(obs) == pytest.approx(
        100 * need / 819e9 / 0.030)
    # 2 tokens pulled in the traced stretch, 2 decode spans, 2 calls
    kv = 4096 * ((2 * 410 + 6 * 128) + 8 * 100)
    assert m.reader("hybrid_attn_roofline")(obs) == pytest.approx(
        100 * (2 * kv / 2) / 819e9 / 0.040)
    # all three spans end inside the window: pairs a step a layer an
    # expert = (1 / 896 + 4 + 3) / 3
    assert m.reader("moe_pairs_per_expert")(obs) == pytest.approx(
        (1 / (8 * 7 * 16) + 4 + 3) / 3)


@pytest.mark.parametrize("name", ["moe_expert_roofline",
                                  "hybrid_attn_roofline",
                                  "moe_pairs_per_expert"])
def test_readers_return_nothing_where_nothing_is_to_read(name):
    """A parent without the span args or the operations: no number, no
    exception."""
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL + ".decode_c64_n768"))
    obs = _obs(cfg)
    obs["spans"] = [(k, ts, d, {"chunk": 8, "occupancy": 64})
                    for k, ts, d, _a in obs["spans"]]
    obs["trace"]["ops"] = {"%fusion.1 bf16[64,6144] fusion": 1.0}
    assert m.reader(name)(obs) is None
    assert m.reader(name)({"cfg": cfg, "window_ns": [LO, HI],
                           "spans": [], "trace": None,
                           "peaks": None}) is None
