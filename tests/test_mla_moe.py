"""Latent attention under YaRN before plain expert layers whose sigmoid
router chooses among the best groups of experts, beside a shared expert —
held to the plain reference (``benchmark/reference/mla_moe_decoder.py``,
float32, per-head attention, imports nothing of the program) ON LOGITS, at
a toy size with the pattern of the benchmark's configuration: layer 0
dense, then expert layers of 16 routed experts in 4 groups of 4, 2 groups
kept and 4 experts a token, 4 held here (group 1), a latent of 32 + a
rotated key of 8.

**Tolerances.**  The toy runs float32 compute over the bfloat16 weights the
model module makes, so program and reference differ by the order of float32
sums alone: 2e-6 of a logit measured, ``TOL`` = 2e-4 allowed — for the
cacheless forward (the latent expanded to K and V per head) and for a
prefill chunk and a decode step over the pool (in the latent space)
against the one reference.  The same weights rounded to float8 (the
benchmark's control) move a logit by 1.0: ``test_float8_control_fails_the_
tolerance`` holds that 1000 x above ``TOL``.
"""

import copy
import dataclasses
import json
import math
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
from benchmark.models import mla_moe_decoder as M  # noqa: E402
from benchmark.reference import mla_moe_decoder as R  # noqa: E402
from nnstreamer_tpu.models import llama, moe, zoo  # noqa: E402

TOL = 2e-4
ZOO = "toy_mla_moe_for_tests"


def file_cfg() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "ax_k1.json")) as f:
        return json.load(f)


def toy_cfg(n_layers=4, held_first=4, held=4) -> dict:
    """The benchmark's configuration file with toy widths; the original
    context shrunk with them, so that the toy's positions reach the
    ramp's both ends."""
    cfg = copy.deepcopy(file_cfg())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               vocab_size=512, moe_intermediate_size=32,
               n_routed_experts=held, num_experts_per_tok=4,
               q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16,
               num_hidden_layers=n_layers, n_group=4, topk_group=2)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_first=held_first)
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
    # every token's own gap is compared here, not a stretch's mean
    cfg["limits"] = dict(cfg["limits"], gap_stretch_tokens=1)
    return cfg


def _build(n_layers=4, name=ZOO):
    cfg = toy_cfg(n_layers)
    tree = M.weights(cfg, 7)
    M.register(name, cfg, tree)
    return cfg, tree, zoo.build(name, {"dtype": "float32"}).config


@pytest.fixture(scope="module")
def toy():
    return _build()


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 512, shape).astype(
        np.int32)


# -- the description ----------------------------------------------------------

def test_the_toy_preset_has_the_benchmarks_pattern(toy):
    _, _, lcfg = toy
    preset = llama.PRESETS["latent_moe_tiny"]
    assert [k.name for k in preset.kinds] == [M.DENSE] + [M.EXPERTS] * 3
    assert preset.kinds == lcfg.kinds
    assert preset.experts.held_groups == range(1, 2)
    # the published 61 layers: one block copy for layer 0, one scanned
    assert llama.walk_plan(preset.kinds[:1] + preset.kinds[1:2] * 60) \
        == llama.WalkPlan(1, 1, 60)
    bundle = zoo.build("latent_moe_tiny", {})
    logits = bundle.apply_fn(bundle.params, _tokens((1, 12)))
    assert logits.shape == (1, 12, 512) and np.isfinite(logits).all()


def test_yarn_frequencies_and_scale_are_the_formulas():
    """At A.X-K1's published rotation (64 dims, base 10,000, factor 32,
    beta 32 / 1, an original context of 4,096, mscale = mscale_all_dim =
    1): low 10, high 23; the frequencies below 10 are the base's, above 23
    the base's / 32, a linear ramp between; cos and sin x 1; the latent
    softmax scale 192^-0.5 x 1.813260 = 0.130861."""
    cfg = file_cfg()
    M.register("count_only_yarn", cfg, None)
    lcfg = zoo.build("count_only_yarn", {}).config
    rs = lcfg.rope_scaling
    assert (rs.factor, rs.original_max_position, rs.beta_fast, rs.beta_slow,
            rs.mscale, rs.mscale_all_dim) == (32, 4096, 32, 1, 1, 1)
    freqs, gain = llama.rope_frequencies(10000.0, 64, rs)
    d, base = 64, 10000.0
    low = max(math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                         / (2 * math.log(base))), 0)
    high = min(math.ceil(d * math.log(4096 / (1 * 2 * math.pi))
                         / (2 * math.log(base))), d - 1)
    assert (low, high) == (10, 23)
    want = []
    for i in range(32):
        extra = base ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / 32 * ramp + extra * (1 - ramp))
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-6)
    np.testing.assert_allclose(R.yarn(cfg)[0], want, rtol=1e-12)
    m = 0.1 * math.log(32) + 1
    assert gain == 1.0 and R.yarn(cfg)[1] == 1.0
    assert abs(m * m - 1.813260) < 1e-6
    assert abs(lcfg.latent_scale - 192 ** -0.5 * m * m) < 1e-12
    assert abs(lcfg.latent_scale - 0.130861) < 1e-6
    assert abs(R.yarn(cfg)[2] - lcfg.latent_scale) < 1e-12
    # unscaled, the frequencies are today's expression and the scale too
    plain, g = llama.rope_frequencies(10000.0, 64)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(
        10000.0 ** (-jnp.arange(0, 32, dtype=jnp.float32) / 32)))
    assert g == 1.0
    assert dataclasses.replace(lcfg, rope_scaling=None).latent_scale \
        == 192 ** -0.5
    # a gain other than 1 multiplies cos and sin
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 64)),
                    jnp.float32)
    muted = dataclasses.replace(rs, mscale=0.0)
    got = llama._rope(x, jnp.arange(5), 10000.0, muted)
    want = llama._rope(x, jnp.arange(5), 10000.0, rs) / m
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_the_models_counts_are_the_trees():
    """The arithmetic the readers, ``step_mfu.serve`` and PERF.md section
    4 divide by, at the published widths, against the issue's numbers."""
    cfg = file_cfg()
    assert M.attention_params(cfg) == 101_122_048
    assert M.expert_bytes(cfg) == 88_080_384
    assert M.weight_bytes(cfg) == 11_051_728_896
    assert M.n_expert_layers(cfg) == 7 and M.held_experts(cfg) == 12
    assert M.experts_per_token(cfg) == 8
    gains = sum(int(np.prod(shape)) * 4 for ffn in M.layer_ffns(cfg)
                for shape in M.leaf_shapes(cfg, ffn).values()
                if len(shape) == 1) + 7168 * 4
    M.register("count_only", cfg, None)
    assert llama.param_bytes_estimate(
        zoo.build("count_only", {}).config, param_dtype="bfloat16") \
        == M.weight_bytes(cfg) + gains
    # per-head attention: 64 x 2 x (192 + 128) a position a layer
    assert M.flops_per_token(cfg, 101) - M.flops_per_token(cfg, 100) \
        == 8 * 40_960


# -- the program against the reference, on logits ----------------------------

def test_forward_is_the_reference(toy):
    cfg, tree, lcfg = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    got = np.asarray(jax.jit(lambda p, t: llama.forward(
        p, t, lcfg, "float32"))(tree, toks))
    assert np.abs(got - ref).max() < TOL


def test_float8_control_fails_the_tolerance(toy):
    cfg, tree, _ = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    low = np.asarray(R.logits(tree, jnp.asarray(toks), cfg, **M.CONTROL))
    assert np.abs(low - ref).max() > 1000 * TOL


@pytest.mark.parametrize("parts", [(0.0, 1.0), (1.0, 0.0)],
                         ids=["no_routed_experts", "no_shared_expert"])
def test_each_part_of_the_expert_layer_is_visible(toy, parts):
    """Without the held routed experts' output, or without the shared
    expert's, the logits move by 100 x the tolerance and more."""
    cfg, tree, _ = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    cut = np.asarray(R.logits(tree, jnp.asarray(toks), cfg, parts=parts))
    assert np.abs(cut - ref).max() > 100 * TOL


def test_without_yarn_the_logits_move(toy):
    """Serving with the unscaled rotation and scale would serve another
    model: the reference without ``rope_scaling``'s effect (factor 1)
    moves the logits by 100 x the tolerance and more."""
    cfg, tree, _ = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    plain = copy.deepcopy(cfg)
    plain["rope_scaling"] = dict(plain["rope_scaling"], factor=1)
    other = np.asarray(R.logits(tree, jnp.asarray(toks), plain))
    assert np.abs(other - ref).max() > 100 * TOL


@pytest.mark.parametrize("n_layers", [2, 4], ids=["unrolled", "scanned"])
def test_chunked_prefill_then_paged_decode_is_the_reference(n_layers):
    """Prefill in chunks of 8 into the latent pool, then decode one token
    a step and compare every step's logits with the reference's full
    forward — a slot joining after the first has decoded six tokens, the
    first retiring (parked) while the second goes on — and the group
    hits: at most the live rows a step an expert layer, and some."""
    cfg, tree, lcfg = _build(n_layers, ZOO + str(n_layers))
    assert llama.walk_plan(lcfg.kinds).n_periods == (3 if n_layers == 4
                                                     else 0)
    bs, C, B, N = 4, 8, 2, 24
    T0 = (13, 10)
    n_blocks, max_blocks = 30, 16
    pool = llama.init_paged_cache(lcfg, n_blocks, bs, "float32")
    tables = np.full((B, max_blocks), n_blocks, np.int32)
    tables[0, :12], tables[1, :12] = np.arange(12), 12 + np.arange(12)
    park = max_blocks * bs
    toks = _tokens((B, max(T0) + N))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    prefill = jax.jit(lambda p, t, pl, tb, pos, off: llama.forward_paged(
        p, t, pl, tb, pos, lcfg, "float32", logit_off=off))
    decode = jax.jit(lambda p, t, pl, tb, pos: llama.forward_paged(
        p, t, pl, tb, pos, lcfg, "float32", with_stats=True))

    def admit(b, pool):
        P = -(-T0[b] // C) * C
        row = np.pad(toks[b:b + 1, :T0[b]], ((0, 0), (0, P - T0[b])))
        for p0 in range(0, P, C):
            final = p0 + C >= P
            lg, pool = prefill(tree, row[:, p0:p0 + C], pool,
                               tables[b:b + 1], np.asarray([p0], np.int32),
                               np.int32(T0[b] - 1 - p0 if final else 0))
        assert np.abs(np.asarray(lg)[0, 0] - ref[b, T0[b] - 1]).max() < TOL
        return pool

    pool = admit(0, pool)
    pos = np.asarray([T0[0], park], np.int32)      # row 1 idle
    group_hits = 0
    for i in range(N):
        if i == 6:
            pool = admit(1, pool)
            pos[1] = T0[1]
        if i == 16:
            pos[0] = park                          # row 0 retires
        live = pos < park
        tok = np.where(live, toks[np.arange(B), np.minimum(pos, toks.shape[1]
                                                           - 1)], 0)
        lg, pool, stats = decode(tree, tok[:, None].astype(np.int32), pool,
                                 tables, pos)
        for b in np.nonzero(live)[0]:
            assert np.abs(np.asarray(lg)[b, 0] - ref[b, pos[b]]).max() < TOL
        assert len(stats) == moe.n_stats(lcfg.experts) == 6
        pairs, hit, most, identity, _, hits = (int(v) for v in stats)
        layers = n_layers - 1
        assert identity == 0 and 0 <= hit <= pairs <= layers * live.sum() * 4
        # a row reaches the held group in a layer only if it kept it, and
        # a pair on a held expert needs the row there
        assert pairs <= 4 * hits and hits <= layers * live.sum()
        group_hits += hits
        pos = np.where(live, pos + 1, pos)
    assert group_hits > 0


# -- the router ------------------------------------------------------------

def _routes_by_hand(biased, scores, n_group, topk_group, k, eps, scale):
    """A plain loop over groups and tokens, in float64: each group's two
    best, the ``topk_group`` best groups (the lower first among equals),
    the ``k`` best experts of the kept groups (the lower first)."""
    N, E = biased.shape
    size = E // n_group
    idx, w, kept = [], [], []
    for t in range(N):
        score = [sorted(biased[t, g * size:(g + 1) * size])[-2:]
                 for g in range(n_group)]
        score = [a + b for a, b in score]
        groups = sorted(range(n_group), key=lambda g: (-score[g], g))
        groups = set(groups[:topk_group])
        allowed = [e for e in range(E) if e // size in groups]
        chosen = sorted(allowed, key=lambda e: (-biased[t, e], e))[:k]
        total = sum(scores[t, e] for e in chosen) + eps
        idx.append(chosen)
        w.append([scores[t, e] / total * scale for e in chosen])
        kept.append([g in groups for g in range(n_group)])
    return np.asarray(idx), np.asarray(w), np.asarray(kept)


@pytest.mark.parametrize("case", ["spread", "ties", "masked"])
def test_grouped_route_is_a_loop_over_groups(case):
    """``moe.route`` against a plain loop over groups: scores that spread;
    ties within a group and between groups (the lower id wins, as
    ``top_k`` orders equals); and a group whose one expert outscores
    every expert of the kept groups but whose second-best is low, so the
    group is dropped and that expert can NOT be chosen."""
    E, G, kg, k, D = 16, 4, 2, 4, 8
    ex = moe.ExpertsConfig(n_experts=E, top_k=k, hidden=8, scale=2.5,
                           norm_eps=1e-20, n_group=G, topk_group=kg)
    rng = np.random.default_rng(4)
    h = rng.standard_normal((6, D)).astype(np.float32)
    w_router = rng.standard_normal((D, E)).astype(np.float32)
    bias = 0.02 * rng.standard_normal(E).astype(np.float32)
    if case == "ties":
        # every router output equal: all scores tie, groups and experts
        w_router = np.zeros((D, E), np.float32)
        bias = np.zeros(E, np.float32)
        bias[[1, 2, 9, 10]] = 0.5        # groups 0 and 2 tie at the top
    elif case == "masked":
        bias = np.full(E, 0.0, np.float32)
        bias[0] = 5.0                    # group 0: one great, three poor
        bias[1:4] = -5.0
        bias[4:12] = 0.3                 # groups 1 and 2: all good
    lp = {"w_router": jnp.asarray(w_router), "router_bias": jnp.asarray(bias)}
    kept = []
    idx, w = moe.route(jnp.asarray(h), lp, ex, kept)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(jnp.asarray(h) @ lp["w_router"]),
                       np.float64)
    want_idx, want_w, want_kept = _routes_by_hand(
        s + bias.astype(np.float64), s, G, kg, k, 1e-20, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(kept[0]), want_kept)
    if case == "masked":
        assert not (np.asarray(idx) == 0).any()
        assert not np.asarray(kept[0])[:, 0].any()
    if case == "ties":
        assert (np.asarray(kept[0]) == [True, False, True, False]).all()
    # the reference routes alike
    flat = dict(n_group=G, topk_group=kg, num_experts_per_tok=k,
                renorm_eps=1e-20, routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        r_idx, r_w = R.route(jnp.asarray(h), lp, flat)
    np.testing.assert_array_equal(np.asarray(r_idx), want_idx)
    np.testing.assert_allclose(np.asarray(r_w), want_w, rtol=1e-5)


def _route_before_groups(h, lp, ex):
    """``moe.route`` as it was before groups existed, word for word."""
    logits = jnp.dot(h.astype(jnp.float32),
                     lp["w_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if ex.scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           ex.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if ex.norm_topk:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total + ex.norm_eps if ex.norm_eps else total)
    return idx, w * ex.scale


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_ungrouped_routing_is_unchanged(scoring):
    """With ``n_group`` 1 the router traces to the program it traced to
    before groups existed (the same equations, op for op) and gives the
    same bits; the stats keep their five entries."""
    ex = moe.ExpertsConfig(n_experts=16, top_k=4, hidden=8, scoring=scoring,
                           scale=2.5)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    lp = {"w_router": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
          "router_bias": jnp.asarray(0.02 * rng.standard_normal(16),
                                     jnp.float32)}
    new = jax.make_jaxpr(lambda h, lp: moe.route(h, lp, ex))(h, lp)
    old = jax.make_jaxpr(lambda h, lp: _route_before_groups(h, lp, ex))(
        h, lp)
    assert str(new) == str(old)
    for a, b in zip(moe.route(h, lp, ex), _route_before_groups(h, lp, ex)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert moe.n_stats(ex) == moe.N_STATS == 5


def _layer_weights(D=32, F=16, E=8, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    return {
        "w_router": jax.random.normal(keys[0], (D, E)) * D ** -0.5,
        "router_bias": 0.02 * jax.random.normal(keys[1], (E,)),
        "we_gate": jax.random.normal(keys[2], (E, D, F)) * D ** -0.5,
        "we_up": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
        "we_down": jax.random.normal(keys[4], (E, F, D)) * F ** -0.5,
        "ws_gate": jax.random.normal(keys[5], (D, F)) * D ** -0.5,
        "ws_up": jax.random.normal(keys[6], (D, F)) * D ** -0.5,
        "ws_down": jax.random.normal(keys[7], (F, D)) * F ** -0.5,
    }, jax.random.normal(keys[8], (3, 5, D))


def _layer(full, h, first, count, E=8):
    ex = moe.ExpertsConfig(n_experts=E, top_k=2, hidden=16, shared=1,
                           scale=2.5, norm_eps=1e-20, n_group=4,
                           topk_group=2, held_first=first, held_count=count)
    lp = dict(full, **{leaf: full[leaf][first:first + (count or E)]
                       for leaf in moe.STACKED_LEAVES})
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_ffn(h, lp, ex, jnp.float32)
    return np.asarray(out), [int(v) for v in stats]


def _shared_by_hand(full, x):
    f = {n: np.asarray(a, np.float64) for n, a in full.items()}
    a = x @ f["ws_gate"]
    return (a / (1 + np.exp(-a)) * (x @ f["ws_up"])) @ f["ws_down"]


def test_the_shares_of_all_ranks_add_up_to_the_whole_layer():
    """8 routed experts in 4 groups of 2 over 4 ranks of 2 (a rank a
    group), 2 groups kept and 2 experts a token, a shared expert that
    every rank computes alike: the four ranks' partial results, the
    shared expert counted ONCE, are the uncut layer — which is the layer
    by hand, every chosen expert of every token.  Each rank's group hits
    are the tokens that kept its group: 2 of 4 a token, so they sum to
    twice the tokens."""
    full, h = _layer_weights()
    x = np.asarray(h, np.float64).reshape(-1, 32)
    f = {n: np.asarray(a, np.float64) for n, a in full.items()}
    s = 1 / (1 + np.exp(-(x @ f["w_router"])))
    idx, w, _ = _routes_by_hand(s + f["router_bias"], s, 4, 2, 2, 1e-20, 2.5)
    routed = np.zeros_like(x)
    for t in range(len(x)):
        for e, g in zip(idx[t], w[t]):
            a = x[t] @ f["we_gate"][e]
            routed[t] += g * ((a / (1 + np.exp(-a)) * (x[t] @ f["we_up"][e]))
                              @ f["we_down"][e])
    shared = _shared_by_hand(full, x)
    whole, stats = _layer(full, h, 0, 0)
    assert np.abs(whole.reshape(x.shape) - (routed + shared)).max() < 1e-4
    parts = [_layer(full, h, r * 2, 2) for r in range(4)]
    summed = sum(p for p, _ in parts) - 3 * shared.reshape(whole.shape)
    assert np.abs(summed - whole).max() < 1e-4
    assert sum(st[0] for _, st in parts) == stats[0] == 15 * 2
    assert sum(st[5] for _, st in parts) == 2 * 15
    # the whole set held: every token keeps a group of it
    assert stats[5] == 15


@pytest.mark.parametrize("kw,reason", [
    (dict(n_group=3), "split into groups of equal size"),
    (dict(n_group=16, topk_group=2), "a group needs at least two"),
    (dict(n_group=4, topk_group=5), "topk_group 5 of 4 groups"),
    (dict(n_group=4, topk_group=1, top_k=6), "top_k 6 from 1 kept groups"),
    (dict(n_group=4, topk_group=2, zero_experts=4),
     "identity experts with groups"),
    (dict(topk_group=2), "without groups"),
])
def test_each_grouping_refusal_gives_its_reason(kw, reason):
    args = dict(n_experts=16, top_k=4, hidden=8)
    args.update(kw)
    with pytest.raises(ValueError, match=reason):
        moe.ExpertsConfig(**args)


@pytest.mark.parametrize("kw,reason", [
    (dict(factor=1.0), "factor above 1"),
    (dict(beta_fast=1.0, beta_slow=32.0), "beta_fast > beta_slow"),
    (dict(original_max_position=0), "the original context"),
])
def test_each_yarn_refusal_gives_its_reason(kw, reason):
    args = dict(factor=32.0, original_max_position=4096)
    args.update(kw)
    with pytest.raises(ValueError, match=reason):
        llama.RopeScaling(**args)


def test_yarn_on_a_layer_that_is_not_latent_refuses():
    with pytest.raises(ValueError, match="latent attention layers only"):
        dataclasses.replace(llama.PRESETS["llama_tiny"],
                            rope_scaling=llama.RopeScaling(32.0, 64))


# -- through the continuous loop ----------------------------------------------

def test_continuous_loop_is_the_reference_and_counts_group_hits(toy):
    """Four requests over three slots through ``tensor_filter
    framework=llm``: every served token's logit is the reference's best,
    and every ``serve.decode`` span carries ``moe_group_hits`` — at most
    the live rows x steps x expert layers."""
    from nnstreamer_tpu.utils import tracing

    cfg, tree, _ = toy
    tracing.recorder.clear()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (n,)).astype(np.int32)
               for n in (5, 13, 9, 17)]
    t0 = time.monotonic_ns()
    p = nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm model={ZOO} "
        "custom=max_new:24,max_seq:128,dtype:float32,serve:continuous,"
        "slots:3,block_size:4,prefill_chunk:8,kv_blocks:60,temperature:0.0 "
        "invoke-dynamic=true name=f ! tensor_sink name=out",
        trace_mode="ring")
    got = {i: [] for i in range(len(prompts))}
    with p:
        for i, pr in enumerate(prompts):
            b = nt.Buffer([pr])
            b.meta["req"] = i
            p.push("src", b)
            time.sleep(0.05)
        done = 0
        while done < len(prompts):
            b = p.pull("out", timeout=120)
            got[b.meta["req"]].append(
                int(np.asarray(b.tensors[0]).reshape(-1)[0]))
            done += bool(b.meta.get("stream_last"))
        events = [e for e in tracing.recorder.events()
                  if e.stage == "llm.serve" and e.ts >= t0]
    for i, pr in enumerate(prompts):
        assert len(got[i]) == 24
        toks = np.concatenate([pr, np.asarray(got[i], np.int32)])[None]
        gap, _ = R.served_gaps(tree, toks, cfg)
        assert float(np.asarray(gap)[0, len(pr) - 1:-1].max()) < TOL
    dec = [e.args for e in events if e.kind == "serve.decode"]
    assert dec and all("moe_group_hits" in a for a in dec)
    assert all(a["moe_group_hits"] <= 3 * a["chunk"] * a["occupancy"]
               for a in dec)
    assert sum(a["moe_group_hits"] for a in dec) > 0
