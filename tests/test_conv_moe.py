"""Gated short-convolution layers whose state a SLOT owns, beside attention
layers in the paged cache, over sparse experts of which every one is held —
on the serving path, held to the plain reference
(``benchmark/reference/conv_moe_decoder.py``, float32, the convolution as a
sum over shifted copies, imports nothing of the program) ON LOGITS, at a toy
size with the pattern of the benchmark's configuration: ten layers ``conv
conv | attention conv conv conv`` twice, two leading dense layers, 16
experts top-4, heads of width 16.

**Tolerances.**  The toy runs float32 compute (and a float32 cache and
state) over the bfloat16 weights the model module makes, so program and
reference differ by the order of float32 sums alone: 1e-5 of a logit
measured, ``TOL`` = 2e-4 allowed — for all three forms of the convolution
(the cacheless forward, a padded prefill chunk that reads and leaves two
carried columns, a decode step) against the one reference.  The same
weights rounded to float8 (the benchmark's control) move a logit by 0.2
and more: a thousand times ``TOL``.  A state carried wrongly (zeros in
place of the two columns) moves a logit by 100 x ``TOL`` and more:
``test_a_lost_state_is_visible`` holds that THESE tests' comparison, on
logits, sees it (what the benchmark's comparison of served tokens sees of
such a fault is another question: ``benchmark/tests/test_conv_moe.py`` and
PERF.md section 6 answer it with the fault injected).  State
against the reference's ``u`` is compared exactly up to float32 rounding of
one product (1e-6).
"""

import copy
import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import nnstreamer_tpu as nt  # noqa: E402
from benchmark.models import conv_moe_decoder as M  # noqa: E402
from benchmark.reference import conv_moe_decoder as R  # noqa: E402
from nnstreamer_tpu.filters.kv_blocks import BlockManager  # noqa: E402
from nnstreamer_tpu.filters.llm import serving_plan  # noqa: E402
from nnstreamer_tpu.models import llama, zoo  # noqa: E402

TOL = 2e-4
ZOO = "toy_conv_moe_for_tests"
C, BS = 8, 4        # prefill chunk and block size of every loop below


def real_cfg() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b.json")) as f:
        return json.load(f)


def toy_cfg() -> dict:
    """The benchmark's configuration file with toy widths."""
    cfg = copy.deepcopy(real_cfg())
    cfg.update(hidden_size=64, intermediate_size=192, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512,
               moe_intermediate_size=32, num_experts=16)
    cfg["serve"] = {"slots": 3, "block_size": BS, "max_seq": 128}
    # every token's own gap is compared here, not a stretch's mean
    cfg["limits"] = dict(cfg["limits"], gap_stretch_tokens=1)
    return cfg


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    tree = M.weights(cfg, 7)
    M.register(ZOO, cfg, tree)
    return cfg, tree, zoo.build(ZOO, {"dtype": "float32"}).config


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 512, shape).astype(
        np.int32)


# -- the description ----------------------------------------------------------

def test_the_toy_preset_has_the_benchmarks_pattern(toy):
    _, _, lcfg = toy
    preset = llama.PRESETS["conv_moe_tiny"]
    assert [k.name for k in preset.kinds] == [
        "conv.dense", "conv.dense", "full.rope.experts", "conv.experts",
        "conv.experts", "conv.experts", "full.rope.experts", "conv.experts",
        "conv.experts", "conv.experts"]
    assert preset.kinds == lcfg.kinds
    assert [k.cache for k in preset.kinds[:3]] == ["conv", "conv", "full"]
    # prefix 2, then periods of 4: six block copies however deep — the
    # published 40 layers end on (attention, conv): a prefix of 4 instead
    assert llama.walk_plan(preset.kinds) == llama.WalkPlan(2, 4, 2)
    assert (preset.n_conv_layers, preset.n_full_layers,
            preset.n_window_layers, preset.n_latent_layers) == (8, 2, 0, 0)
    assert preset.head_dim == 16 and preset.conv_taps == 3
    bundle = zoo.build("conv_moe_tiny", {})
    assert bundle.param_pspecs is None
    logits = bundle.apply_fn(bundle.params, _tokens((1, 12)))
    assert logits.shape == (1, 12, 512) and np.isfinite(logits).all()
    assert llama.param_bytes_estimate(preset) == sum(
        x.nbytes for x in jax.tree.leaves(bundle.params))
    pool = llama.init_paged_cache(preset, 10, BS, slots=3)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 10, BS, 2, 16), "v": (2, 10, BS, 2, 16),
        "conv": (8, 3, 2, 64)}
    assert llama.block_size_of({"conv": pool["conv"], "k": pool["k"]}) == BS
    assert llama.allocated_leaves(pool) == ["k", "v"]
    assert llama.paged_cache_bytes(preset, 10, BS, slots=3) == sum(
        x.nbytes for x in pool.values())
    assert llama.conv_state_bytes(preset, 3) == pool["conv"].nbytes
    plan = serving_plan(preset, slots=3, block_size=BS)
    assert plan["programs"] == 3 and plan["win_ring"] == 0
    assert plan["conv_state_bytes"] == pool["conv"].nbytes
    assert plan["pool_bytes"] == llama.paged_cache_bytes(
        preset, plan["n_blocks"], BS, slots=3)
    # the cache a token costs is the attention layers' alone
    assert plan["decode_bytes_per_ctx_token"] == 2 * 2 * 2 * 16 * 2
    assert "convolution layers" in llama.pattern_traits(preset)
    assert serving_plan(llama.PRESETS["hybrid_moe_tiny"], slots=3)[
        "conv_state_bytes"] == 0


@pytest.mark.parametrize("kind,reason", [
    (dict(conv=True, window=8), "no attention to give a window"),
    (dict(conv=True, latent=True), "no attention to give a window"),
    (dict(conv=True, rope=False), "no attention to give a window"),
    (dict(conv=True, shortcut="open"), "no attention to give a window"),
])
def test_a_convolution_kind_with_attentions_traits_refuses(kind, reason):
    with pytest.raises(ValueError, match=reason):
        llama.LayerKind(**kind)


@pytest.mark.parametrize("change,reason", [
    (dict(conv_taps=1), "needs two or more"),
    (dict(n_layers=2, pattern=(llama.LayerKind(conv=True),) * 2),
     "convolution layers only has no paged cache"),
])
def test_a_config_the_paged_path_cannot_serve_refuses(change, reason):
    with pytest.raises(ValueError, match=reason):
        dataclasses.replace(llama.PRESETS["conv_moe_tiny"], **change)


def test_the_configurations_tree_is_the_bytes_reckoned():
    """At the published widths, by shapes, nothing allocated: 5.40 G
    parameters = 10.8 GB, 64 % of the chip's ``bytes_limit``; 4,096 B of
    cache a token, 65,536 B of state a slot; the cell's pool 277 MB + 4.2
    MB."""
    cfg = real_cfg()
    M.register("conv_moe_count_only", cfg, None)
    lcfg = zoo.build("conv_moe_count_only", {}).config
    total = llama.param_bytes_estimate(lcfg, param_dtype="bfloat16")
    assert total == M.tree_bytes(cfg) == 10_804_800_512
    assert 0.63 < total / 16_909_336_064 < 0.65
    shapes = jax.eval_shape(lambda: llama.init_params(lcfg, 0, "bfloat16"))
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == total
    assert llama.walk_plan(lcfg.kinds) == llama.WalkPlan(2, 4, 2)
    assert lcfg.experts.n_held == 64 and lcfg.experts.norm_eps == 1e-6
    plan = serving_plan(lcfg, slots=64, block_size=16, kv_blocks=4224)
    assert plan["decode_bytes_per_ctx_token"] == 4096
    assert plan["conv_state_bytes"] == 64 * 65_536 == 4_194_304
    assert plan["pool_bytes"] == 4224 * 16 * 4096 + 4_194_304
    assert plan["n_blocks"] == 4224 and plan["max_blocks"] == 256


# -- the program against the reference, on logits ----------------------------

def test_forward_is_the_reference(toy):
    cfg, tree, lcfg = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    got = np.asarray(jax.jit(lambda p, t: llama.forward(
        p, t, lcfg, "float32"))(tree, toks))
    assert np.abs(got - ref).max() < TOL
    low = np.asarray(R.logits(tree, jnp.asarray(toks), cfg, **M.CONTROL))
    assert np.abs(low - ref).max() > 1000 * TOL


def _paged(lcfg, slots=3, n_blocks=60, max_blocks=16):
    """A pool whose state leaf holds what a former stream left, a table a
    slot, and the two jitted programs as the loop builds them."""
    pool = llama.init_paged_cache(lcfg, n_blocks, BS, "float32", slots=slots)
    pool["conv"] = pool["conv"] + 7.0
    tables = np.full((slots, max_blocks), n_blocks, np.int32)
    for s in range(slots):
        tables[s, :12] = 12 * s + np.arange(12)
    prefill = jax.jit(lambda p, t, pl, tb, pos, off: llama.forward_paged(
        p, t, pl, tb, pos, lcfg, "float32", logit_off=off, n_valid=off + 1))
    decode = jax.jit(lambda p, t, pl, tb, pos: llama.forward_paged(
        p, t, pl, tb, pos, lcfg, "float32"))
    return pool, tables, prefill, decode, max_blocks * BS


def _prefill(tree, prefill, pool, tables, s, row):
    """Slot ``s`` prefills ``row`` in padded chunks of ``C``, as the loop's
    step 2 does; returns the last real token's logits and the pool."""
    T = len(row)
    P = -(-T // C) * C
    padded = np.pad(row, (0, P - T))[None]
    for p0 in range(0, P, C):
        final = p0 + C >= P
        lg, pool = prefill(
            tree, padded[:, p0:p0 + C], pool,
            {"full": tables[s:s + 1], "slot": np.asarray([s], np.int32)},
            np.asarray([p0], np.int32),
            np.int32(T - 1 - p0 if final else C - 1))
    return np.asarray(lg)[0, 0], pool


def _reference_u(tree, cfg, toks, layer):
    """``u`` [T, D] of conv layer ``layer`` (0 or 1: the two leading
    ones, whose input the reference's own pieces give) for one row."""
    eps = cfg["norm_eps"]
    stack = tree["layers"]["conv.dense"]
    with jax.default_matmul_precision("highest"):
        x = tree["embed"][toks[None]].astype(jnp.float32)
        for l in range(layer + 1):
            lp = jax.tree.map(lambda a: a[l], stack)
            got = []
            mix = R.conv_mixer(R._rmsnorm(x, lp["ln_attn"], eps), lp,
                               cfg["conv_L_cache"], u_out=got)
            if l == layer:
                return np.asarray(got[0][0])
            x = x + mix
            h = R._rmsnorm(x, lp["ln_mlp"], eps)
            x = x + (jax.nn.silu(h @ R._f32(lp["w_gate"], None))
                     * (h @ R._f32(lp["w_up"], None))) \
                @ R._f32(lp["w_down"], None)


@pytest.mark.parametrize("T", [1, 2, 5, 8, 13, 16, 20],
                         ids=lambda t: f"prompt{t}")
def test_the_state_after_a_padded_chunk_is_the_last_two_valid_columns(
        toy, T):
    """A prompt of ``T`` tokens prefilled in chunks of 8, the last one
    padded: the slot's state is ``(u_{T-2}, u_{T-1})`` of the REFERENCE —
    taken at the row's valid length, never at the chunk's last column —
    with zero where the stream has no such token yet; what the slot's
    former stream left (the leaf starts at 7.0) is gone; the other slots'
    state is untouched; the logits are the reference's."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, _, _ = _paged(lcfg)
    row = _tokens((T,), seed=T)
    lg, pool = _prefill(tree, prefill, pool, tables, 1, row)
    ref = np.asarray(R.logits(tree, jnp.asarray(row[None]), cfg))
    assert np.abs(lg - ref[0, T - 1]).max() < TOL
    state = np.asarray(pool["conv"])
    for layer in (0, 1):
        u = np.concatenate([np.zeros((2, 64), np.float32),
                            _reference_u(tree, cfg, row, layer)])
        assert np.abs(state[layer, 1] - u[T:T + 2]).max() < 1e-5
    assert np.all(state[:, [0, 2]] == 7.0)


@pytest.mark.parametrize("T", [5, 13, 20],
                         ids=["shorter_than_a_chunk", "not_a_multiple",
                              "three_chunks"])
def test_chunked_prefill_then_paged_decode_is_the_reference(toy, T):
    """Prefill in padded chunks of 8, then decode one token a step, every
    step's logits against the reference's full forward pass — beside a
    second slot that decodes from the start, and a third that is parked
    and whose state nothing touches."""
    cfg, tree, lcfg = toy
    N = 12
    pool, tables, prefill, decode, park = _paged(lcfg)
    toks = _tokens((2, 24 + N), seed=T)
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    T0 = (T, 9)
    for s in (0, 1):
        lg, pool = _prefill(tree, prefill, pool, tables, s, toks[s, :T0[s]])
        assert np.abs(lg - ref[s, T0[s] - 1]).max() < TOL
    pos = np.asarray([T0[0], T0[1], park], np.int32)
    tabs = {"full": tables, "slot": np.arange(3, dtype=np.int32)}
    for _ in range(N):
        tok = np.asarray([toks[0, pos[0]], toks[1, pos[1]], 0], np.int32)
        lg, pool = decode(tree, tok[:, None], pool, tabs, pos)
        for s in (0, 1):
            assert np.abs(np.asarray(lg)[s, 0] - ref[s, pos[s]]).max() < TOL
        pos[:2] += 1
    assert np.all(np.asarray(pool["conv"])[:, 2] == 7.0)


def test_a_parked_row_leaves_its_neighbours_state_and_outputs_alone(toy):
    """The same live row decoded beside two parked rows and beside two
    live ones: bit for bit the same logits and the same state; the parked
    rows' state is what it was (a slot in the middle of its next stream's
    prefill is parked in the decode step and must keep its columns)."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, decode, park = _paged(lcfg)
    toks = _tokens((3, 14), seed=3)
    for s in range(3):
        _, pool = _prefill(tree, prefill, pool, tables, s, toks[s, :9])
    before = np.asarray(pool["conv"]).copy()
    tabs = {"full": tables, "slot": np.arange(3, dtype=np.int32)}
    tok = toks[:, 9:10]
    pool_a = jax.tree.map(jnp.copy, pool)
    lg_all, pool_all = decode(tree, tok, pool, tabs,
                              np.asarray([9, 9, 9], np.int32))
    lg_one, pool_one = decode(tree, tok, pool_a, tabs,
                              np.asarray([park, 9, park + 5], np.int32))
    assert np.array_equal(np.asarray(lg_all)[1], np.asarray(lg_one)[1])
    one, every = np.asarray(pool_one["conv"]), np.asarray(pool_all["conv"])
    assert np.array_equal(one[:, 1], every[:, 1])
    assert np.array_equal(one[:, [0, 2]], before[:, [0, 2]])
    assert not np.array_equal(every[:, 0], before[:, 0])


@pytest.mark.parametrize("fault", ["no_slots", "no_slot_table",
                                   "slot_past_the_leaf"])
def test_a_callers_mistake_with_the_slots_is_refused_not_hidden(toy, fault):
    """The state is kept by slot, so a caller that names no slots builds
    no pool, tables without ``"slot"`` do not trace, and a slot id past
    the leaf (a value, which no trace can refuse) reads NaN into that
    row's logits and writes nothing: it is never folded onto slot 0's
    columns, whose stream stays the reference's."""
    cfg, tree, lcfg = toy
    if fault == "no_slots":
        with pytest.raises(ValueError, match="slots >= 1"):
            llama.init_paged_cache(lcfg, 10, BS)
        assert llama.conv_state_bytes(lcfg, 0) == 0
        assert llama.conv_state_bytes(lcfg, 3) == 8 * 3 * 2 * 64 * 2
        return
    pool, tables, prefill, decode, park = _paged(lcfg)
    if fault == "no_slot_table":
        with pytest.raises(ValueError, match="kept by slot"):
            decode(tree, np.zeros((3, 1), np.int32), pool, tables,
                   np.zeros((3,), np.int32))
        return
    toks = _tokens((3, 11), seed=5)
    for s in range(3):
        _, pool = _prefill(tree, prefill, pool, tables, s, toks[s, :10])
    before = np.asarray(pool["conv"]).copy()
    lg, after = decode(tree, toks[:, 10:11], pool,
                       {"full": tables,
                        "slot": np.asarray([0, 3, -1], np.int32)},
                       np.asarray([10, 10, 10], np.int32))
    lg, after = np.asarray(lg), np.asarray(after["conv"])
    ref = np.asarray(R.logits(tree, jnp.asarray(toks[:1]), cfg))[0, 10]
    assert np.abs(lg[0, 0] - ref).max() < TOL
    assert np.isnan(lg[1]).all() and np.isnan(lg[2]).all()
    assert np.array_equal(after[:, 1:], before[:, 1:])
    assert not np.array_equal(after[:, 0], before[:, 0])


def test_a_lost_state_is_visible(toy):
    """Decoding from a state of zeros in place of the two carried columns
    moves the next token's logits by 100 x ``TOL`` and more: a
    comparison on logits, as these tests make it, sees a state lost at a
    chunk's edge or at admission."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, decode, park = _paged(lcfg)
    toks = _tokens((1, 14), seed=9)
    _, pool = _prefill(tree, prefill, pool, tables, 0, toks[0, :13])
    tabs = {"full": tables, "slot": np.arange(3, dtype=np.int32)}
    pos = np.asarray([13, park, park], np.int32)
    tok = np.asarray([[toks[0, 13]], [0], [0]], np.int32)
    lost = dict(jax.tree.map(jnp.copy, pool),
                conv=jnp.zeros_like(pool["conv"]))
    lg, _ = decode(tree, tok, pool, tabs, pos)
    lg_lost, _ = decode(tree, tok, lost, tabs, pos)
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))[0, 13]
    assert np.abs(np.asarray(lg)[0, 0] - ref).max() < TOL
    assert np.abs(np.asarray(lg_lost)[0, 0] - ref).max() > 100 * TOL


# -- through the continuous loop ----------------------------------------------

def _serve(opts, prompts, max_new=24, stagger=0.05):
    p = nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm model={ZOO} "
        f"custom=max_new:{max_new},max_seq:128,dtype:float32,"
        f"serve:continuous,slots:3,block_size:{BS},prefill_chunk:{C},"
        f"kv_blocks:60,temperature:0.0{opts} invoke-dynamic=true "
        "name=f ! tensor_sink name=out", trace_mode="ring")
    got = {i: [] for i in range(len(prompts))}
    seen = {}
    t0 = time.monotonic_ns()   # the ring is the process's: ours from here

    def pull():
        b = p.pull("out", timeout=120)
        got[b.meta["req"]].append(
            int(np.asarray(b.tensors[0]).reshape(-1)[0]))
        return bool(b.meta.get("stream_last"))

    with p:
        done = 0
        for i, pr in enumerate(prompts):
            b = nt.Buffer([pr])
            b.meta["req"] = i
            p.push("src", b)
            if stagger is None:
                while not got[i]:
                    done += pull()
            else:
                time.sleep(stagger)
        while done < len(prompts):
            done += pull()
        from nnstreamer_tpu.utils import tracing

        loop = p.element("f").fw._serve
        seen["events"] = [e for e in tracing.recorder.events()
                          if e.stage == "llm.serve" and e.ts >= t0]
        seen["stats"] = loop.pool_stats()
        seen["share_prefix"] = loop.kv.share_prefix
        seen["census"] = (loop._decode._cache_size(),
                          loop._prefill._cache_size(),
                          loop._set_tok._cache_size())
    return got, seen


#: lengths: shorter than a chunk of 8, not a multiple of it, three
#: chunks; then three more that take the slots the first ones leave
CHURN = (5, 13, 20, 17, 6, 24)


@pytest.fixture(scope="module")
def churn(toy):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (n,)).astype(np.int32) for n in CHURN]
    got, seen = _serve("", prompts)
    return prompts, got, seen


def _gap(cfg, tree, prompt, served):
    toks = np.concatenate([prompt, np.asarray(served, np.int32)])[None]
    gap, _ = R.served_gaps(tree, toks, cfg)
    return float(np.asarray(gap)[0, len(prompt) - 1:-1].max())


@pytest.mark.parametrize("i", range(len(CHURN)), ids=[
    "shorter_than_a_chunk", "not_a_multiple", "three_chunks",
    "second_stream_a", "second_stream_b", "second_stream_c"])
def test_served_through_the_loop_is_the_reference(toy, churn, i):
    """Six requests over three slots: every served token's logit is the
    reference's best given all before it (greedy over float32 logits that
    differ by 1e-5) — prompts on both sides of a chunk's edge, and the
    streams that take a slot another stream left its state in."""
    cfg, tree, _ = toy
    prompts, got, _ = churn
    assert len(got[i]) == 24
    assert _gap(cfg, tree, prompts[i], got[i]) < TOL


def test_a_slots_second_stream_is_that_stream_served_alone(toy, churn):
    """Some slot served two streams (its state reset at the second one's
    admission, from ``pos == 0``, a value): the second stream's tokens
    are those of the same prompt served alone in a fresh loop."""
    prompts, got, seen = churn
    by_slot = {}
    for e in seen["events"]:
        if e.kind == "serve.admit":
            by_slot.setdefault(e.args["slot"], []).append(e.args["tokens"])
    reused = [v for v in by_slot.values() if len(v) > 1]
    assert reused, by_slot
    second = CHURN.index(reused[0][1])
    alone, _ = _serve("", [prompts[second]])
    assert alone[0] == got[second]


def test_the_census_stays_three_and_the_spans_say_the_state(churn):
    """One signature a program however the slots churned, the routing
    changed and the state's values moved; the pool's accounting reports
    the state's bytes (a constant of the deployment: since PR 38 no span
    repeats it every iteration), ``serve.decode`` the five expert
    counts."""
    _, _, seen = churn
    assert seen["census"] == (1, 1, 1)
    state = 8 * 3 * 2 * 64 * 4      # conv layers x slots x 2 x D, float32
    assert seen["stats"]["conv_state_bytes"] == state
    assert seen["stats"]["blocks_free"] == seen["stats"]["blocks_total"]
    iters = [e.args for e in seen["events"] if e.kind == "serve.iter"]
    assert iters and not any("conv_state_bytes" in a for a in iters)
    dec = [e.args for e in seen["events"] if e.kind == "serve.decode"]
    assert dec and all(
        {"moe_pairs", "moe_experts_hit", "moe_max_per_expert",
         "moe_zero_pairs", "moe_weight_passes"} <= set(a) for a in dec)
    # every expert is held: every choice of every live row is a pair here
    layers, chunk, k = 8, 8, 4
    assert all(0 < a["moe_pairs"] <= layers * chunk * a["occupancy"] * k
               and a["moe_zero_pairs"] == 0 for a in dec)


def test_prefix_cache_on_is_prefix_cache_off_and_every_lookup_a_miss(toy):
    """Two requests share their first 16 tokens.  The state at the end of
    the shared prefix is in no block, so the manager shares nothing on
    this model: with ``prefix_cache`` on (the default) every lookup is a
    miss, nothing is indexed, and the tokens are those of ``prefix_cache``
    off — and the reference's."""
    cfg, tree, _ = toy
    from nnstreamer_tpu.core.log import metrics

    rng = np.random.default_rng(2)
    head = rng.integers(0, 512, (16,)).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(0, 512, (n,)).astype(
        np.int32)]) for n in (3, 5)]
    before = metrics.snapshot().get("llm.serve.prefix_hits", 0)
    on, seen = _serve("", prompts, max_new=12, stagger=None)
    off, _ = _serve(",prefix_cache:0", prompts, max_new=12, stagger=None)
    assert metrics.snapshot().get("llm.serve.prefix_hits", 0) == before
    assert on == off
    assert seen["share_prefix"] is False
    assert seen["stats"]["blocks_cached"] == 0
    for i, pr in enumerate(prompts):
        assert _gap(cfg, tree, pr, on[i]) < TOL


def test_the_block_manager_hands_out_slot_ids_and_shares_nothing():
    kv = BlockManager(slots=4, block_size=4, prefill_chunk=8, n_blocks=20,
                      max_blocks=8, win_ring=0, win_blocks=0,
                      prefix_cache=True, count=lambda *a, **k: None,
                      conv_state_bytes=4096)
    assert kv.share_prefix is False
    tabs = kv.tabs(slice(2, 3))
    assert set(tabs) == {"full", "slot"} and tabs["slot"].tolist() == [2]
    assert kv.tabs()["slot"].tolist() == [0, 1, 2, 3]
    assert kv.stats()["conv_state_bytes"] == 4096
    row = np.arange(16, dtype=np.int32)
    plan = kv.lookup(1, row, 16, 4)
    kv.admit(0, plan)
    kv.register(0, plan.hashes)
    assert not kv.prefix_index and kv.lookup(2, row, 16, 4).shared == 0
    plain = BlockManager(slots=4, block_size=4, prefill_chunk=8,
                         n_blocks=20, max_blocks=8, win_ring=0,
                         win_blocks=0, prefix_cache=True,
                         count=lambda *a, **k: None)
    assert plain.share_prefix and isinstance(plain.tabs(), np.ndarray)
    assert plain.stats()["conv_state_bytes"] == 0


# -- what is not built refuses ------------------------------------------------

def _open(custom, **kw):
    return nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm "
        f"model=conv_moe_tiny custom={custom} invoke-dynamic=true "
        "name=f ! tensor_sink name=out", **kw)


@pytest.mark.parametrize("custom,reason", [
    ("serve:continuous,slots:2,draft:llama_tiny",
     "a rejected tail would already have moved it past the accepted"),
    ("max_new:4", "served by serve:continuous only"),
    ("serve:continuous,slots:2,quant:int8",
     "no quantized layout yet, and this model has a layer pattern: "
     "sparse experts, convolution layers"),
])
def test_unsupported_options_refuse_at_construction(custom, reason):
    with pytest.raises(Exception, match=reason):
        _open(custom)


@pytest.mark.parametrize("call", ["drain_stream", "adopt_stream"])
def test_drain_and_adopt_refuse_slot_owned_state(call):
    with _open("serve:continuous,slots:2,max_new:4") as p:
        fw = p.element("f").fw
        with pytest.raises(Exception, match="owns convolution state is not "
                                            "built.*filter from zeros"):
            if call == "drain_stream":
                fw.drain_stream(1)
            else:
                fw.adopt_stream({"version": 2}, lambda *a: None)


def test_paths_of_the_one_kind_decoder_refuse_with_the_reason():
    cfg = llama.PRESETS["conv_moe_tiny"]
    params = jax.eval_shape(lambda: llama.init_params(cfg))
    toks = jnp.zeros((1, 4), jnp.int32)
    why = "convolution layers .state owned by the slot, in no block."
    with pytest.raises(NotImplementedError, match=why):
        llama.forward_cached(params, toks, None, 0, cfg)
    with pytest.raises(NotImplementedError, match=why):
        llama.forward_seq_parallel(None, params, toks, cfg)
    assert any("no tensor-parallel layout" in p
               for p in llama.tp_divisibility_problems(cfg, 2))


def test_the_renormalisations_epsilon_is_a_number_of_the_config():
    """``norm_eps`` 0 (the default) computes what the router computed
    before it existed; 1e-6 divides by the sum + 1e-6."""
    from nnstreamer_tpu.models import moe

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    lp = {"w_router": jax.random.normal(keys[0], (64, 16)) * 0.125,
          "router_bias": 0.02 * jax.random.normal(keys[1], (16,))}
    h = jax.random.normal(keys[2], (10, 64))
    plain = moe.ExpertsConfig(n_experts=16, top_k=4, hidden=32)
    eps = dataclasses.replace(plain, norm_eps=0.5)
    assert plain.norm_eps == 0.0
    (i0, w0), (i1, w1) = moe.route(h, lp, plain), moe.route(h, lp, eps)
    assert np.array_equal(i0, i1)
    assert np.allclose(np.asarray(w0).sum(-1), 1.0, atol=1e-6)
    s = jax.nn.sigmoid(h @ lp["w_router"])
    chosen = np.take_along_axis(np.asarray(s), np.asarray(i0), -1)
    assert np.allclose(w1, chosen / (chosen.sum(-1, keepdims=True) + 0.5),
                       atol=1e-6)
