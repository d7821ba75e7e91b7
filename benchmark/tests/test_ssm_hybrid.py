"""The state-space-and-attention decoder in the benchmark: a toy of the
same order (four layers ``mamba mamba attention mamba``, a dense FFN in
each, four query heads over one KV head, 128 channels and a state of 16 a
Mamba layer) runs through ``run.run_cell`` on the CPU and is ``correct``
against its reference; the float8 control is not, nor is a program whose
state after a padded prefill chunk is taken at the chunk's last column, or
whose state is not zeroed at admission;
the model module is the reference at toy size; its counts are the
published model's; the two readers read hand-written observations, and
nothing where a program has nothing for them; the configuration states
that nothing is cut and its entries resolve to files."""

import copy
import json
import os

import numpy as np
import pytest

from conftest import ROOT, copy_benchmark, run_toy

from benchmark.manifest import Manifest

CELL = "toy_ssm_hybrid.toy_closed_long"
REAL = "jamba2_3b"
REAL_CELL = REAL + ".decode_c64_n1024"
READERS = ("ssm_update_roofline", "ssm_step_roofline")


def toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           REAL + ".json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(name="toy_ssm_hybrid", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, vocab_size=512, num_hidden_layers=4,
               attn_layer_period=4, attn_layer_offset=2, mamba_dt_rank=8)
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
def ssm_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench_ssm")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    rel = "benchmark/configs/toy_ssm_hybrid.json"
    with open(os.path.join(root, rel), "x") as f:
        json.dump(toy_config(), f)
    doc["configs"].append({"name": "toy_ssm_hybrid", "source": "a toy",
                           "file": rel, "reduced": [], "why": "toy"})
    with open(os.path.join(root, "benchmark", "traffic",
                           "toy_closed_long.json"), "x") as f:
        # prompts shorter than a prefill chunk (32), and longer
        json.dump({"kind": "serve",
                   "arrival": {"mode": "closed", "clients": 3},
                   "prompt_len": {"dist": "uniform", "min": 5, "max": 40},
                   "max_new": 72, "check_requests": 2}, f)
    doc["workloads"].append({"name": CELL, "config": "toy_ssm_hybrid",
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


def test_toy_cell_is_correct(ssm_root):
    r = run_toy(ssm_root, CELL, seed=2**31 + 13, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _check(r, "compiles_in_window")["value"] == 0
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("seed", [61, 62])
def test_float8_control_is_not_correct(ssm_root, seed):
    from benchmark import control

    row = control.read_seed(Manifest(ssm_root), CELL, seed, 2.0)
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


def _no_reset(orig):
    """A stream's first chunk reads what the slot's last stream left."""
    def mixer(cfg, lp, h, state=None, slots=None, pos_offset=None,
              layer=None, live=None, n_valid=None):
        import jax.numpy as jnp

        if pos_offset is not None:
            pos_offset = jnp.where(pos_offset == 0, -1, pos_offset)
        return orig(cfg, lp, h, state, slots, pos_offset, layer, live,
                    n_valid)
    return mixer


@pytest.mark.parametrize("fault,seed", [
    (_padded_column, 63), (_padded_column, 64), (_no_reset, 65),
    (_no_reset, 66)], ids=["padded_column-63", "padded_column-64",
                           "no_reset-65", "no_reset-66"])
def test_a_fault_of_the_state_is_not_correct(ssm_root, monkeypatch, fault,
                                             seed):
    """The two faults of the mechanism this configuration adds, injected
    into the program's state-space mixer and read through the very path
    that decides ``correct`` (``control.read_seed``): over the limit,
    where the sound program (above) reads under a third of it.  The state
    after a padded prefill chunk taken at the chunk's last column scans
    the pad tokens into it, and every decoded token of the stream reads a
    state that is not its own; a state not zeroed at admission carries
    the slot's last stream into the next one, and unlike a convolution
    layer's two columns a state matrix forgets it only over hundreds of
    steps (0.020–0.038 measured on the toy, ten times the limit)."""
    from benchmark import control
    from nnstreamer_tpu.models import llama

    monkeypatch.setattr(llama, "_ssm_mixer", fault(llama._ssm_mixer))
    row = control.read_seed(Manifest(ssm_root), CELL, seed, 2.0)
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

    from benchmark.models import ssm_hybrid_decoder as M
    from benchmark.reference import ssm_hybrid_decoder as R
    from nnstreamer_tpu.models import llama, zoo

    cfg = toy_config()
    tree = M.weights(cfg, 3)
    M.register("toy_ssm_module_test", cfg, tree)
    lcfg = zoo.build("toy_ssm_module_test", {"dtype": "float32"}).config
    assert [k.name for k in lcfg.kinds] == [k for _a, k in
                                            M.layer_kinds(cfg)] == [
        "ssm.dense", "ssm.dense", "full.nope.dense", "ssm.dense"]
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
    # Mamba's recipe: a = -(1 .. 16) in every channel, steps in [1e-3,
    # 1e-1] before the input moves them, D = 1
    stack = tree["layers"]["ssm.dense"]
    assert np.allclose(-np.exp(np.asarray(stack["A_log"][0]))[:, 0],
                       -np.arange(1, 17))
    dt0 = np.log1p(np.exp(np.asarray(stack["dt_bias"])))
    assert 1e-3 <= dt0.min() and dt0.max() <= 1e-1
    assert np.all(np.asarray(stack["ssm_d"]) == 1.0)


def test_the_configuration_states_its_cut_and_resolves_to_files():
    m = Manifest(ROOT)
    cell = m.cell(REAL_CELL)
    cfg = m.config(cell)
    entry = next(c for c in m.doc["configs"] if c["name"] == REAL)
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"] and cell["chips"] == 1
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    # the catalog row's numbers, every one as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["mamba_expand"],
            cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"],
            cfg["vocab_size"], cfg["attn_layer_period"],
            cfg["attn_layer_offset"], cfg["num_experts"],
            cfg["rms_norm_eps"]) == (
        2560, 8192, 28, 20, 1, 2, 16, 160, 4, 65536, 14, 7, 1, 1e-6)
    assert set(cfg["assumed"]) >= {
        "layer_order", "attention", "mamba_mixer", "ssm_state",
        "conv_filter_layout", "a_log_layout", "tie_word_embeddings",
        "hidden_act", "norm_placement", "weights"}
    assert cfg["precision"]["ssm_state"] == "float32"
    assert cfg["serve"] == {"slots": 64, "block_size": 16, "max_seq": 4096}
    # files: the model module, the reference, the driver, the mix, readers
    assert m.model(cfg).ZOO_NAME == "bench_ssm_hybrid_decoder"
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
        "serve_deliver_ahead_pct", "serve_ring_window_pct",
        "serve_traced_tok_s", "serve_gap_emit_pct", "serve_dispatch_pct",
        "serve_emit_us_per_token"}
    for name in reported:
        assert callable(m.reader(name))
    assert {x["name"] for x in m.end_to_end(REAL_CELL)} >= {
        "serve_tok_s", "setup_s"}


def test_the_models_counts_are_the_published_models():
    """The sizes reckoned for the published model: 3,029,337,472
    parameters (the head tied), 6.40 GB resident with the head's copy and
    the float32 leaves; a slot's state 8,519,680 B and its filter columns
    798,720 B; an update call 41.9 MB of state at 64 rows; 1,024 B of
    cache a token; a step of 7.3 GB at the cell's mean context, 15 % of
    it the float32 state."""
    from benchmark.models import ssm_hybrid_decoder as M

    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    assert M.tree_params(cfg) == 3_029_337_472
    assert M.tree_bytes(cfg) == 6_399_313_408
    assert (M.n_ssm_layers(cfg), M.n_attention_layers(cfg)) == (26, 2)
    assert [l for l in range(28) if M.is_attention(cfg, l)] == [7, 21]
    assert M.state_bytes_per_slot(cfg) == 8_519_680
    assert M.conv_bytes_per_slot(cfg) == 798_720
    state = 64 * 2 * 16 * 5120 * 4
    assert state == 41_943_040
    assert state < M.ssm_update_bytes(cfg, 64) < 1.1 * state
    assert M.kv_bytes_attended(cfg, 1) == 1024
    step = M.step_bytes(cfg, 64, 64 * 544)
    assert 7.2e9 < step < 7.4e9
    assert 0.14 < 64 * 2 * M.state_bytes_per_slot(cfg) / step < 0.16
    # one more position costs QK^T and PV on the two attention layers
    assert M.flops_per_token(cfg, 101) - M.flops_per_token(cfg, 100) \
        == 2 * 4 * 2560


# -- the readers, on observations written by hand ---------------------------

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS


def _obs(cfg):
    """A window of one second from perf_counter 10.0; the profiler ran
    from 10.2 to 10.6 and holds 2 decode calls but the kernels of 1.5 of
    them (its edge cut a chunk); two decode spans closed meanwhile, at 64
    and 62 live rows."""
    def span(start_ms, dur_ms, occupancy, **a):
        return ("serve.decode", LO + int(start_ms * MS), int(dur_ms * MS),
                dict(a, chunk=8, occupancy=occupancy))
    spans = [span(0, 190, 64),
             span(200, 180, 64, ssm_kernel_layers=26),
             span(400, 180, 62, ssm_kernel_layers=26)]
    ops = {"%ssm_update.7 (tuple) custom-call": 0.020,
           "%paged_attention.3 bf16[64,20,128] custom-call": 0.0012,
           "%fusion.1 bf16[64,2560] fusion": 1.0}
    # 1.5 chunks of 8 steps x 26 layers: 312 executions of the kernel
    calls = {"%ssm_update.7 (tuple) custom-call": 312,
             "%paged_attention.3 bf16[64,20,128] custom-call": 24,
             "%fusion.1 bf16[64,2560] fusion": 500}
    return {"cfg": cfg, "spans": spans, "window_ns": [LO, HI],
            "window": [10.0, 11.0], "window_s": 1.0, "chips": 1,
            "decoded": [(10.3, 400), (10.5, 600), (10.9, 700)],
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "trace": {"host_span": (10.2, 10.6), "ops": ops,
                      "op_calls": calls,
                      "modules": {"jit_decode_chunk(1)": 0.15},
                      "module_calls": {"jit_decode_chunk(1)": 2}}}


def test_readers_on_hand_written_observations():
    from benchmark.models import ssm_hybrid_decoder as M

    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    # 312 executions x the bytes of 63 rows (the mean of the two spans
    # that took the kernel) over 0.020 s
    got = m.reader("ssm_update_roofline")(obs)
    assert got == pytest.approx(
        100 * 312 * M.ssm_update_bytes(cfg, 63) / 819e9 / 0.020)
    assert 0 < got < 100
    # 312 / 26 = 12 steps x a step at 63 rows of the mean context (500)
    assert m.reader("ssm_step_roofline")(obs) == pytest.approx(
        100 * 12 * M.step_bytes(cfg, 63, 63 * 500) / 819e9 / 0.15)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_nothing_is_to_read(name):
    """A parent without the span args, the kernel or the operations: no
    number, no exception."""
    m = Manifest(ROOT)
    cfg = m.config(m.cell(REAL_CELL))
    obs = _obs(cfg)
    assert m.reader(name)(obs) is not None
    obs["spans"] = [(k, ts, d, {"chunk": 8, "occupancy": 64})
                    for k, ts, d, _a in obs["spans"]]
    assert m.reader(name)(obs) is None
    obs = _obs(cfg)
    obs["trace"]["ops"] = {"%fusion.1 bf16[64,2560] fusion": 1.0}
    obs["trace"]["op_calls"] = {"%fusion.1 bf16[64,2560] fusion": 3}
    assert m.reader(name)(obs) is None
    del obs["trace"]["op_calls"]
    assert m.reader(name)(obs) is None
    assert m.reader(name)({"cfg": cfg, "window_ns": [LO, HI],
                           "spans": [], "trace": None,
                           "peaks": None}) is None
