"""The convolution-and-attention, every-expert-held decoder in the
benchmark: a toy of the same pattern (ten layers ``conv conv | attention
conv conv conv`` twice, two leading dense layers, 16 experts top-4 all held,
heads of width 16) runs through ``run.run_cell`` on the CPU and is
``correct`` against its reference; the float8 control is not; the model
module is the reference at toy size; the four readers read hand-written
observations, nothing where a program has nothing for them, and never over
100 when the trace's edge cuts a chunk; the configuration states its cut and
its entries resolve to files."""

import copy
import json
import os

import numpy as np
import pytest

from conftest import ROOT, copy_benchmark, run_toy

from benchmark.manifest import Manifest

CELL = "toy_conv_moe.toy_closed_long"
REAL = "lfm2_24b_a2b"
REAL_CELL = REAL + ".decode_c64_n1024"
READERS = ("conv_moe_expert_roofline", "hd64_attn_roofline",
           "conv_moe_weight_roofline", "conv_moe_pairs_per_expert")


def toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           REAL + ".json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(name="toy_conv_moe", hidden_size=64, intermediate_size=192,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
               moe_intermediate_size=32, num_experts=16)
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
    # float32 compute over the same bfloat16 weights: what is left is the
    # order of float32 sums, 1e-5 of a logit and no token changed; the
    # float8 control's worst stretch reads 0.01 and more.  The stretch is
    # the real cell's own (``gap_stretch_tokens`` is not overridden): the
    # toy's answers are 72 tokens so that one fits
    cfg["limits"] = dict(cfg["limits"], logit_gap_max=0.002)
    assert cfg["limits"]["gap_stretch_tokens"] == 64
    return cfg


@pytest.fixture(scope="module")
def conv_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench_conv")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    rel = "benchmark/configs/toy_conv_moe.json"
    with open(os.path.join(root, rel), "x") as f:
        json.dump(toy_config(), f)
    doc["configs"].append({"name": "toy_conv_moe", "source": "a toy",
                           "file": rel, "reduced": [], "why": "toy"})
    with open(os.path.join(root, "benchmark", "traffic",
                           "toy_closed_long.json"), "x") as f:
        # prompts shorter than a prefill chunk (32), and longer
        json.dump({"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 3},
                   "prompt_len": {"dist": "uniform", "min": 5, "max": 40},
                   "max_new": 72, "check_requests": 2}, f)
    doc["workloads"].append({"name": CELL, "config": "toy_conv_moe",
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


def test_toy_cell_is_correct(conv_root):
    r = run_toy(conv_root, CELL, seed=2**31 + 11, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _check(r, "compiles_in_window")["value"] == 0
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("seed", [51, 52])
def test_float8_control_is_not_correct(conv_root, seed):
    from benchmark import control

    row = control.read_seed(Manifest(conv_root), CELL, seed, 2.0)
    limit = toy_config()["limits"]["logit_gap_max"]
    assert row["program"]["logit_gap_max"] <= limit / 3
    assert row["control"]["logit_gap_max"] > limit
    assert row["control"]["logit_gap_max"] > \
        30 * row["program"]["logit_gap_max"]


def _padded_column(orig):
    """The state is taken at the chunk's LAST column, padding or not."""
    def mixer(cfg, lp, h, state=None, slots=None, pos_offset=None,
              layer=None, live=None, n_valid=None):
        return orig(cfg, lp, h, state, slots, pos_offset, layer, live, None)
    return mixer


@pytest.mark.parametrize("seed", [53, 54])
def test_a_state_taken_at_a_padded_column_is_not_correct(
        conv_root, monkeypatch, seed):
    """The one-off fault of the mechanism this configuration adds that
    spoils SERVED tokens — the state after a prefill chunk taken at the
    chunk's last column, padding or not, so that a stream's first two
    decoded tokens filter over columns that are not its own — injected
    into the program's convolution mixer and read through the very path
    that decides ``correct`` (``control.read_seed``: the cell's traffic,
    the served tokens replayed by the reference, the worst stretch of the
    real cell's 64 tokens against the limit): over the limit, where the
    sound program (above) reads under a third of it.  The other one-off
    fault, no reset at admission, spoils positions 0 and 1 of a PROMPT,
    whose next tokens are not served ones: the served-token comparison
    reads it under the limit (0.0002 here; the chip's reading is in
    PERF.md section 6), and ``tests/test_conv_moe.py`` holds it on logits
    (a slot's second stream is that stream served alone)."""
    from benchmark import control
    from nnstreamer_tpu.models import llama

    monkeypatch.setattr(llama, "_conv_mixer",
                        _padded_column(llama._conv_mixer))
    row = control.read_seed(Manifest(conv_root), CELL, seed, 2.0)
    assert row["program"]["logit_gap_max"] > \
        toy_config()["limits"]["logit_gap_max"]


def test_the_model_module_is_the_reference_at_toy_size():
    """The tree the model module makes, through the program's cacheless
    forward pass as ``register`` describes the model, against the
    reference: the order of float32 sums apart (1e-5 measured); the float8
    control a thousand times the tolerance away; the head is the
    embedding's copy and the reference does not read it."""
    import jax
    import jax.numpy as jnp

    from benchmark.models import conv_moe_decoder as M
    from benchmark.reference import conv_moe_decoder as R
    from nnstreamer_tpu.models import llama, zoo

    cfg = toy_config()
    tree = M.weights(cfg, 3)
    M.register("toy_conv_moe_module_test", cfg, tree)
    lcfg = zoo.build("toy_conv_moe_module_test", {"dtype": "float32"}).config
    assert [k.name for k in lcfg.kinds] == [k for _c, _f, k in
                                            M.layer_kinds(cfg)]
    toks = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(
        np.int32)
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    got = np.asarray(jax.jit(lambda p, t: llama.forward(
        p, t, lcfg, "float32"))(tree, toks))
    assert np.abs(got - ref).max() < 2e-4
    low = np.asarray(R.logits(tree, jnp.asarray(toks), cfg, **M.CONTROL))
    assert np.abs(low - ref).max() > 0.2
    assert np.array_equal(np.asarray(tree["lm_head"]),
                          np.asarray(tree["embed"]).T)
    untied = dict(tree, lm_head=tree["lm_head"] * 0)
    assert np.array_equal(
        np.asarray(R.logits(untied, jnp.asarray(toks), cfg)), ref)
    # a router that spreads: every expert of the first sparse layer is
    # chosen by some of the 80 tokens
    routes = []
    R.logits(tree, jnp.asarray(toks), cfg, routes=routes)
    assert len(routes) == 8 and len(np.unique(np.asarray(routes[0]))) == 16


def test_the_configuration_states_its_cut_and_resolves_to_files():
    m = Manifest(ROOT)
    cell = m.cell(REAL_CELL)
    cfg = m.config(cell)
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] and cell["chips"] == 1
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["num_hidden_layers"] == 10 and cfg["num_dense_layers"] == 2
    # the catalog row's lists whole; the ten layers run are the first ten
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    # no width differs from the source
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["vocab_size"], cfg["routed_scaling_factor"],
            cfg["rope_parameters"]["rope_theta"], cfg["norm_eps"]) == (
        2048, 11776, 1536, 32, 8, 64, 4, 3, 65536, 1, 10**6, 1e-5)
    assert set(cfg["assumed"]) >= {
        "w_in_order", "norm_topk_eps", "router", "qk_norm", "rope",
        "tie_word_embeddings", "hidden_act", "norm_placement", "weights"}
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["stage"], dep["stages"]) == (
        1, 0, 4)
    # files: the model module, the reference, the driver, the mix, readers
    assert m.model(cfg).ZOO_NAME == "bench_conv_moe_decoder"
    assert callable(m.reference(cfg).served_gaps)
    assert callable(m.reference(cfg).control_gaps)
    assert m.driver(cfg).__name__ == "Driver"
    assert m.mix(cell) == {
        "kind": "serve", "arrival": {"mode": "closed", "clients": 64},
        "prompt_len": {"dist": "uniform", "min": 16, "max": 32},
        "max_new": 1024, "check_requests": 2}
    reported = {x["name"] for x in m.per_layer(REAL_CELL)}
    assert reported == set(READERS) | {
        "step_mfu.serve", "device_idle.serve", "serve_live_slots",
        "serve_host_gap_pct", "serve_emit_pct", "serve_admit_pct",
        "serve_deliver_ahead_pct"}
    for name in reported:
        assert callable(m.reader(name))
    assert {x["name"] for x in m.end_to_end(REAL_CELL)} == {
        "serve_tok_s", "setup_s"}


def test_the_models_counts_are_the_issues():
    from benchmark.models import conv_moe_decoder as M

    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    assert M.expert_bytes(cfg) == 18_874_368
    assert M.mixer_params(cfg, True) == 16_783_360
    assert M.mixer_params(cfg, False) + 128 == 10_485_888
    assert (M.n_expert_layers(cfg), M.n_attention_layers(cfg),
            M.held_experts(cfg)) == (8, 2, 64)
    assert abs(M.tree_bytes(cfg) - 10.8e9) < 0.02e9      # 5.40 G x 2 B
    assert M.kv_bytes_attended(cfg, 1) == 2 * 2048
    # 63 of 64 experts a layer: 9.5 GB of experts in a step of 10.4 GB
    step = M.step_weight_bytes(cfg, 8 * 63)
    assert abs(step - 10.39e9) < 0.02e9
    assert 0.90 < 8 * 63 * M.expert_bytes(cfg) / step < 0.93
    # one more position costs QK^T and PV on the two attention layers
    assert M.flops_per_token(cfg, 101) - M.flops_per_token(cfg, 100) \
        == 2 * 4 * 2048


# -- the readers, on observations written by hand ---------------------------

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS


def _obs(cfg):
    """A window of one second from perf_counter 10.0; the profiler ran
    from 10.2 to 10.6 and holds 2 decode calls but the kernels of 1.5 of
    them (its edge cut a chunk); two decode spans closed meanwhile (61
    and 63 experts hit a step a layer, 8 steps, 8 sparse layers)."""
    def span(start_ms, dur_ms, **a):
        return ("serve.decode", LO + int(start_ms * MS), int(dur_ms * MS),
                dict(a, chunk=8, occupancy=64))
    pairs = 64 * 4 * 8 * 8
    spans = [span(0, 190, moe_pairs=pairs // 2, moe_experts_hit=1,
                  moe_max_per_expert=1, moe_zero_pairs=0),
             span(200, 180, moe_pairs=pairs, moe_experts_hit=61 * 8 * 8,
                  moe_max_per_expert=9, moe_zero_pairs=0),
             span(400, 180, moe_pairs=pairs, moe_experts_hit=63 * 8 * 8,
                  moe_max_per_expert=11, moe_zero_pairs=0)]
    ops = {"%ragged-dot-swiglu.1 f32[256,2048] custom-call": 0.160,
           "%ragged-dot-swiglu.2 f32[128,2048] custom-call": 5.0,
           "%paged_attention.3 bf16[64,32,128] custom-call": 0.0012,
           "%paged_attention.9 bf16[64,64,128] custom-call": 3.0,
           "%fusion.1 bf16[64,2048] fusion": 1.0}
    # 1.5 chunks: 96 executions of the expert product, 24 of the kernel
    calls = {"%ragged-dot-swiglu.1 f32[256,2048] custom-call": 96,
             "%ragged-dot-swiglu.2 f32[128,2048] custom-call": 7,
             "%paged_attention.3 bf16[64,32,128] custom-call": 24,
             "%paged_attention.9 bf16[64,64,128] custom-call": 24,
             "%fusion.1 bf16[64,2048] fusion": 500}
    return {"cfg": cfg, "spans": spans, "window_ns": [LO, HI],
            "window": [10.0, 11.0], "window_s": 1.0, "chips": 1,
            "decoded": [(10.3, 410), (10.5, 100), (10.9, 700)],
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "trace": {"host_span": (10.2, 10.6), "ops": ops,
                      "op_calls": calls,
                      "modules": {"jit_decode_chunk(1)": 0.3},
                      "module_calls": {"jit_decode_chunk(1)": 2}}}


def test_readers_on_hand_written_observations():
    from benchmark.models import conv_moe_decoder as M

    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    # 96 executions x mean (61, 63) experts x 18.9 MB over 0.160 s — by
    # the decode calls (2 x 64 executions) it would read a third more
    need = 96 * 62 * 18_874_368
    got = m.reader("conv_moe_expert_roofline")(obs)
    assert got == pytest.approx(100 * need / 819e9 / 0.160)
    assert got < 100 < got * 128 / 96
    # 24 executions x 64 rows x the mean context of the 2 tokens pulled in
    # the stretch (410, 100) x 2,048 B a position a layer
    assert m.reader("hd64_attn_roofline")(obs) == pytest.approx(
        100 * (24 * 64 * 255 * 2048) / 819e9 / 0.0012)
    # 96 / 8 = 12 steps x (62 x 8 experts + everything else once)
    assert m.reader("conv_moe_weight_roofline")(obs) == pytest.approx(
        100 * 12 * M.step_weight_bytes(cfg, 62 * 8) / 819e9 / 0.3)
    # all three spans end inside the window: (2 + 4 + 4) / 3 pairs
    assert m.reader("conv_moe_pairs_per_expert")(obs) == pytest.approx(
        10 / 3)


def test_rooflines_read_on_where_the_ring_has_dropped_the_traced_stretch():
    """Past ≈ 3,000 tokens/s the ring no longer holds the spans closed
    while the profiler ran: the three trace readers then take a step's
    rows and experts hit from the spans of the same window that are
    left."""
    from benchmark.models import conv_moe_decoder as M

    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    obs["spans"] = obs["spans"][2:]          # only the span closed at 10.58
    obs["trace"]["host_span"] = (10.0, 10.5)   # ... after the profiler
    obs["decoded"] = [(10.1 + i * 1e-4, 300) for i in range(1024)]
    assert m.reader("conv_moe_expert_roofline")(obs) == pytest.approx(
        100 * 96 * 63 * 18_874_368 / 819e9 / 0.160)
    assert m.reader("hd64_attn_roofline")(obs) == pytest.approx(
        100 * (24 * 64 * 300 * 2048) / 819e9 / 0.0012)
    assert m.reader("conv_moe_weight_roofline")(obs) == pytest.approx(
        100 * 12 * M.step_weight_bytes(cfg, 63 * 8) / 819e9 / 0.3)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_nothing_is_to_read(name):
    """A parent without the span args, the kernel or the operations: no
    number, no exception."""
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    obs["spans"] = [(k, ts, d, {"chunk": 8, "occupancy": 64})
                    for k, ts, d, _a in obs["spans"]]
    obs["trace"]["ops"] = {"%fusion.1 bf16[64,2048] fusion": 1.0}
    obs["trace"]["op_calls"] = {"%fusion.1 bf16[64,2048] fusion": 3}
    assert m.reader(name)(obs) is None
    del obs["trace"]["op_calls"]
    assert m.reader(name)(obs) is None
    assert m.reader(name)({"cfg": cfg, "window_ns": [LO, HI],
                           "spans": [], "trace": None,
                           "peaks": None}) is None
