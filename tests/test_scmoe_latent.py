"""Latent attention, a shortcut-connected expert branch and a softmax router
with identity experts on the serving path — held to the plain reference
(``benchmark/reference/scmoe_latent_decoder.py``, float32, per-head
attention, imports nothing of the program) ON LOGITS, at a toy size with the
pattern of the benchmark's configuration: two attention blocks and two dense
FFNs a published layer with the experts across them, 16 routed + 8 identity
experts, 4 a token, 4 held here, a latent of 32 + a rotated key of 8 in a
pool row padded to 128 lanes.

**Tolerances.**  The toy runs float32 compute over the bfloat16 weights the
model module makes, so program and reference differ by the order of float32
sums alone: 1e-5 of a logit measured, ``TOL`` = 2e-4 allowed — for BOTH
forms of the attention (the cacheless forward expands the latent to K and V
per head, a prefill chunk and a decode step over the pool stay in the latent
space) against the one reference.
The same weights rounded to float8 (the benchmark's control) move a logit by
0.3 and more: ``test_float8_control_fails_the_tolerance`` holds that 1000 x
above ``TOL``.  Zeroing the real experts' output, or the identity pairs',
moves a logit by 100 x ``TOL`` and more: the branch is visible to the
comparison.
"""

import copy
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
from benchmark.models import scmoe_latent_decoder as M  # noqa: E402
from benchmark.reference import scmoe_latent_decoder as R  # noqa: E402
from nnstreamer_tpu.filters.llm import serving_plan  # noqa: E402
from nnstreamer_tpu.models import llama, moe, zoo  # noqa: E402
from nnstreamer_tpu.ops import attention as A  # noqa: E402

TOL = 2e-4
ZOO = "toy_scmoe_latent_for_tests"


def toy_cfg(n_layers=2, held_first=4, held=4) -> dict:
    """The benchmark's configuration file with toy widths."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat_flash_omni.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(hidden_size=64, ffn_hidden_size=128, num_attention_heads=4,
               vocab_size=512, expert_ffn_hidden_size=32,
               n_routed_experts=held, moe_topk=4, zero_expert_num=8,
               q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, num_layers=n_layers)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_first=held_first)
    cfg["assumed"] = dict(
        cfg["assumed"],
        mla_scale_q_lora={"value": 2.0 ** 0.5},
        mla_scale_kv_lora={"value": 2.0 ** 0.5})
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
    # every token's own gap is compared here, not a stretch's mean
    cfg["limits"] = dict(cfg["limits"], gap_stretch_tokens=1)
    return cfg


def _build(n_layers=2, name=ZOO):
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
    preset = llama.PRESETS["latent_shortcut_tiny"]
    assert [k.name for k in preset.kinds] == [M.OPEN, M.CLOSE] * 2
    assert preset.kinds == lcfg.kinds
    # 56 sub-layers of period 2 at the published depth: two block copies
    assert llama.walk_plan(preset.kinds[:2] * 28) == llama.WalkPlan(0, 2, 28)
    assert (preset.n_latent_layers, preset.n_full_layers,
            preset.n_window_layers) == (4, 0, 0)
    assert preset.latent_width == 40 and A.latent_pool_width(40) == 128
    assert A.latent_pool_width(576) == 640
    bundle = zoo.build("latent_shortcut_tiny", {})
    assert bundle.param_pspecs is None
    logits = bundle.apply_fn(bundle.params, _tokens((1, 12)))
    assert logits.shape == (1, 12, 512) and np.isfinite(logits).all()
    assert llama.param_bytes_estimate(preset) == sum(
        x.nbytes for x in jax.tree.leaves(bundle.params))
    pool = llama.init_paged_cache(preset, 10, 4)
    assert {k: v.shape for k, v in pool.items()} == {"c": (4, 10, 4, 128)}
    assert llama.paged_cache_bytes(preset, 10, 4) == pool["c"].nbytes
    plan = serving_plan(preset, slots=3, block_size=4)
    assert plan["programs"] == 3 and plan["win_ring"] == 0
    assert plan["decode_bytes_per_ctx_token"] == 4 * 40 * 2
    assert plan["pool_bytes"] == llama.paged_cache_bytes(
        preset, plan["n_blocks"], 4)


@pytest.mark.parametrize("kinds,reason", [
    (("open", "open"), "open already"),
    (("close", "open"), "not open"),
    (("open", ""), "never closed"),
])
def test_a_shortcut_that_does_not_pair_up_refuses(kinds, reason):
    preset = llama.PRESETS["latent_shortcut_tiny"]
    import dataclasses

    with pytest.raises(ValueError, match=reason):
        dataclasses.replace(preset, n_layers=2, pattern=tuple(
            llama.LayerKind(latent=True, shortcut=s) for s in kinds))


def test_the_models_counts_are_the_trees(toy):
    """The arithmetic the readers and ``step_mfu.serve`` divide by, at the
    published widths, against the issue's own numbers."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat_flash_omni.json")) as f:
        cfg = json.load(f)
    assert M.attention_params(cfg) == 90_570_752
    assert M.expert_bytes(cfg) == 75_497_472
    assert M.latent_bytes_attended(cfg, 1) == 8 * 1152
    shapes = {k: M.leaf_shapes(cfg, k) for k in (M.OPEN, M.CLOSE)}
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] * 2 + 4 * 6144
    for s in shapes.values():
        total += cfg["num_layers"] * sum(
            int(np.prod(shape)) * (4 if leaf in M._F32 else 2)
            for leaf, shape in s.items())
    assert abs(total - 10.38e9) < 0.03e9   # the issue's 10.38 GB
    M.register("count_only", cfg, None)
    assert llama.param_bytes_estimate(
        zoo.build("count_only", {}).config, param_dtype="bfloat16") == total
    # per-head attention: 64 x 2 x (192 + 128) a position a block
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
                         ids=["no_real_experts", "no_identity_pairs"])
def test_each_half_of_the_expert_branch_is_visible(toy, parts):
    """Without the held real experts' output, or without the identity
    pairs, the logits move by 100 x the tolerance and more: neither can be
    left out unseen."""
    cfg, tree, _ = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    cut = np.asarray(R.logits(tree, jnp.asarray(toks), cfg, parts=parts))
    assert np.abs(cut - ref).max() > 100 * TOL


@pytest.mark.parametrize("n_layers", [1, 2], ids=["unrolled", "scanned"])
def test_chunked_prefill_then_paged_decode_is_the_reference(n_layers):
    """Prefill in chunks of 8 into the latent pool, then decode one token
    a step (both in the latent space: the absorbed form) and compare every
    step's logits with the reference's full forward — with a slot that
    joins after the first has decoded six tokens, and the first retiring
    (parked) while the second goes on.  Two published layers take the scan
    over periods (layer indices traced), one the one-by-one walk."""
    cfg, tree, lcfg = _build(n_layers, ZOO + str(n_layers))
    assert llama.walk_plan(lcfg.kinds).n_periods == (2 if n_layers == 2
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
    zero_pairs = 0
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
        pairs, hit, most, identity, _ = (int(v) for v in stats)
        # counts are over the live rows: 4 choices a row a published layer
        assert 0 <= hit <= pairs <= n_layers * live.sum() * 4 - identity
        zero_pairs += identity
        pos = np.where(live, pos + 1, pos)
    assert zero_pairs > 0


# -- the kernel, in interpret mode ------------------------------------------

def test_paged_latent_kernel_is_its_reference_and_the_expanded_form():
    """The Pallas kernel (interpret mode) against the XLA reference and
    against plain per-head attention over K and V EXPANDED from the rows'
    true latents: contexts that end inside a block, on a block's edge,
    past one wave of 32 blocks (a full wave takes the unrolled starts and
    the one wait, the rest of the row the loops), on a wave's edge and on
    two waves' (the next row's first wave is then started by a full one),
    and an idle row."""
    bs, H, r, dr, dn, dv, B = 16, 4, 128, 16, 8, 8, 6
    assert A._latent_wave_blocks(bs) == 32
    lens = np.asarray([21, 32, 32 * bs + 5, 0, 32 * bs, 64 * bs], np.int32)
    S = 64 * bs
    rng = np.random.default_rng(0)
    hist = rng.standard_normal((B, S, r + dr)).astype(np.float32)
    wk = rng.standard_normal((r, H, dn)).astype(np.float32) * r ** -0.5
    wv = rng.standard_normal((r, H, dv)).astype(np.float32) * r ** -0.5
    q_c = rng.standard_normal((B, H, dn)).astype(np.float32)
    q_r = rng.standard_normal((B, H, dr)).astype(np.float32)
    max_blocks = S // bs
    n_blocks = B * max_blocks
    tables = rng.permutation(n_blocks).reshape(B, max_blocks).astype(
        np.int32)
    W = A.latent_pool_width(r + dr)
    assert W == 256
    pool = np.zeros((n_blocks, bs, W), np.float32)
    for b in range(B):
        for pos in range(lens[b]):
            pool[tables[b, pos // bs], pos % bs, :r + dr] = hist[b, pos]
    scale = (dn + dr) ** -0.5
    q_lat = np.einsum("bhn,rhn->bhr", q_c, wk)
    q = jnp.asarray(np.concatenate([q_lat, q_r], -1)[:, None])
    args = (q, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(lens))
    kern = np.asarray(A.paged_latent_attention(
        *args, v_width=r, scale=scale, interpret=True))
    ref = np.asarray(A.paged_latent_attention_reference(
        *args, v_width=r, scale=scale))
    assert kern.shape == (B, 1, H, r)
    live = lens > 0
    assert np.abs(kern - ref)[live].max() < 1e-5
    for b in np.flatnonzero(live):
        L = int(lens[b])
        c, k_r = hist[b, :L, :r], hist[b, :L, r:]
        k_c = np.einsum("sr,rhn->shn", c, wk)
        v = np.einsum("sr,rhv->shv", c, wv)
        s = (np.einsum("hn,shn->hs", q_c[b], k_c)
             + np.einsum("hd,sd->hs", q_r[b], k_r)) * scale
        w = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hs,shv->hv", w / w.sum(-1, keepdims=True), v)
        got = np.einsum("hr,rhv->hv", kern[b, 0], wv)
        assert np.abs(got - want).max() < 1e-4


# -- the expert layer -------------------------------------------------------

def _layer_weights(D=64, F=32, E=16, Z=8, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "w_router": jax.random.normal(keys[0], (D, E + Z)) * 2 * D ** -0.5,
        "router_bias": 0.02 * jax.random.normal(keys[1], (E + Z,)),
        "we_gate": jax.random.normal(keys[2], (E, D, F)) * D ** -0.5,
        "we_up": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
        "we_down": jax.random.normal(keys[4], (E, F, D)) * F ** -0.5,
    }, jax.random.normal(keys[5], (3, 5, D))


def _layer(full, h, first, count, E=16, Z=8, k=4):
    ex = moe.ExpertsConfig(n_experts=E, top_k=k, hidden=32,
                           scoring="softmax", norm_topk=False, scale=6.0,
                           zero_experts=Z, held_first=first,
                           held_count=count)
    lp = dict(full, **{leaf: full[leaf][first:first + (count or E)]
                       for leaf in moe.STACKED_LEAVES})
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_ffn(h, lp, ex, jnp.float32)
    *counts, passes = (int(v) for v in stats)
    assert passes == 0   # the CPU takes ragged_dot: no kernel pass
    return np.asarray(out), counts


def _by_hand(full, h, E=16, k=4):
    """The uncut layer in float64: every chosen expert of every token."""
    x = np.asarray(h, np.float64).reshape(-1, h.shape[-1])
    f = {n: np.asarray(a, np.float64) for n, a in full.items()}
    z = x @ f["w_router"]
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    real, identity = np.zeros_like(x), np.zeros_like(x)
    for t in range(len(x)):
        for e in np.argsort(-(p[t] + f["router_bias"]))[:k]:
            if e >= E:
                identity[t] += 6 * p[t, e] * x[t]
                continue
            a = x[t] @ f["we_gate"][e]
            real[t] += 6 * p[t, e] * (
                (a / (1 + np.exp(-a)) * (x[t] @ f["we_up"][e]))
                @ f["we_down"][e])
    return real, identity


def test_the_shares_of_all_ranks_add_up_to_the_whole_layer():
    """16 routed experts over 4 ranks of 4, 8 identity experts that every
    rank computes alike: the four ranks' partial results, the identity
    pairs counted ONCE, are the uncut layer — which is the layer by hand,
    every chosen expert of every token."""
    full, h = _layer_weights()
    whole, stats = _layer(full, h, 0, 0)
    real, identity = _by_hand(full, h)
    assert np.abs(whole.reshape(real.shape) - (real + identity)).max() < 1e-4
    parts = [_layer(full, h, r * 4, 4) for r in range(4)]
    # every rank's result holds the identity pairs: take them off all but
    # one (what the exchange's sum does with what every rank computes)
    summed = sum(p for p, _ in parts) - 3 * identity.reshape(whole.shape)
    assert np.abs(summed - whole).max() < 1e-4
    # the counts: the ranks' real pairs add up, the identity pairs are
    # every rank's alike, and together they are every choice made
    assert sum(s[0] for _, s in parts) == stats[0]
    assert {s[3] for _, s in parts} == {stats[3]}
    assert stats[0] + stats[3] == 3 * 5 * 4 and stats[3] > 0


def test_a_token_of_identity_choices_and_one_held_elsewhere_are_right():
    """The correction bias steers the CHOICE only.  With it all on the
    identity experts a token computes no expert: the layer is ``sum of g x
    h`` and the grouped product owns no row.  With it all on experts held
    by another rank this rank's partial result is zero."""
    full, h = _layer_weights()
    x = np.asarray(h, np.float64).reshape(-1, 64)
    z = x @ np.asarray(full["w_router"], np.float64)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)

    def biased(lo, hi):
        b = np.zeros(24, np.float32)
        b[lo:hi] = 10.0
        return dict(full, router_bias=jnp.asarray(b))

    out, stats = _layer(biased(16, 24), h, 4, 4)
    top4 = np.sort(p[:, 16:], axis=-1)[:, -4:].sum(-1, keepdims=True)
    assert np.abs(out.reshape(x.shape) - 6 * top4 * x).max() < 1e-4
    assert stats == [0, 0, 0, 3 * 5 * 4]
    out, stats = _layer(biased(8, 16), h, 4, 4)
    assert np.abs(out).max() == 0.0 and stats == [0, 0, 0, 0]
    # and all on the held ones: every pair is computed here
    _, stats = _layer(biased(4, 8), h, 4, 4)
    assert stats[0] == 3 * 5 * 4 and stats[1] == 4 and stats[3] == 0


# -- through the continuous loop ----------------------------------------------

def _serve(cfg_opts, prompts, max_new=40, stagger=0.05):
    p = nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm model={ZOO} "
        f"custom=max_new:{max_new},max_seq:128,dtype:float32,"
        f"serve:continuous,slots:3,block_size:4,prefill_chunk:8,"
        f"kv_blocks:60,temperature:0.0{cfg_opts} invoke-dynamic=true "
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
                # the next joins once this one's prompt blocks are indexed
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
        seen["census"] = (loop._decode._cache_size(),
                          loop._prefill._cache_size(),
                          loop._set_tok._cache_size())
    return got, seen


def _served_is_the_reference(cfg, tree, prompts, got):
    for i, pr in enumerate(prompts):
        toks = np.concatenate([pr, np.asarray(got[i], np.int32)])[None]
        gap, _ = R.served_gaps(tree, toks, cfg)
        assert float(np.asarray(gap)[0, len(pr) - 1:-1].max()) < TOL


def test_continuous_loop_with_slots_joining_and_retiring(toy):
    """Five requests over three slots, so slots retire and are taken
    again: every served token's logit is the reference's best (greedy over
    float32 logits that differ by 1e-5).  One signature a program, however
    the slots churned: the census of a plain loop stays 3."""
    cfg, tree, _ = toy
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (n,)).astype(np.int32)
               for n in (5, 13, 9, 17, 6)]
    got, seen = _serve("", prompts)
    assert all(len(got[i]) == 40 for i in range(5))
    _served_is_the_reference(cfg, tree, prompts, got)
    assert seen["stats"]["blocks_free"] == seen["stats"]["blocks_total"]
    assert seen["census"] == (1, 1, 1)
    dec = [e.args for e in seen["events"] if e.kind == "serve.decode"]
    assert dec and all(
        {"moe_pairs", "moe_experts_hit", "moe_max_per_expert",
         "moe_zero_pairs"} <= set(a) for a in dec)
    layers, chunk, k = 2, 8, 4
    assert all(a["moe_pairs"] + a["moe_zero_pairs"]
               <= layers * chunk * a["occupancy"] * k for a in dec)
    assert sum(a["moe_zero_pairs"] for a in dec) > 0


def test_shared_prefix_on_the_latent_pool_is_a_hit_and_is_right(toy):
    """Two requests share their first 16 tokens.  Every sub-layer's state
    lives in allocator blocks (no window ring), so the second request
    resumes from the first one's blocks — a HIT — forks the block it
    writes into, and its tokens are the reference's."""
    cfg, tree, _ = toy
    from nnstreamer_tpu.core.log import metrics

    rng = np.random.default_rng(2)
    head = rng.integers(0, 512, (16,)).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(0, 512, (n,)).astype(
        np.int32)]) for n in (3, 5)]
    before = metrics.snapshot().get("llm.serve.prefix_hits", 0)
    got, _ = _serve("", prompts, max_new=12, stagger=None)
    assert metrics.snapshot().get("llm.serve.prefix_hits", 0) > before
    _served_is_the_reference(cfg, tree, prompts, got)


def test_drain_and_adopt_carry_the_latent_blocks(toy):
    """A stream drained mid-answer carries its blocks of the latent pool
    (``blocks_c``) and continues, adopted, with the tokens an undrained
    run serves."""
    cfg, tree, _ = toy
    prompt = _tokens((11,), seed=5)
    whole, _ = _serve("", [prompt], max_new=24)

    def pipeline():
        return nt.Pipeline(
            f"appsrc name=src ! tensor_filter framework=llm model={ZOO} "
            "custom=max_new:24,max_seq:128,dtype:float32,serve:continuous,"
            "slots:3,block_size:4,prefill_chunk:8,kv_blocks:60,"
            "stream_chunk:4,temperature:0.0 invoke-dynamic=true name=f ! "
            "tensor_sink name=out")

    toks = []
    with pipeline() as p:
        p.push("src", nt.Buffer([prompt]))
        b = p.pull("out", timeout=120)
        toks.append(int(np.asarray(b.tensors[0]).reshape(-1)[0]))
        sid = b.meta["stream_id"]
        snap = p.element("f").fw.drain_stream(sid)
    assert snap["kind"] == "live" and "blocks_c" in snap \
        and "blocks_k" not in snap
    assert snap["blocks_c"].shape[0] == 4 and snap["blocks_c"].shape[2:] \
        == (4, 128)
    rest = []
    with pipeline() as p:
        fw = p.element("f").fw
        import threading

        done = threading.Event()

        def emit(tensors, meta):
            rest.append((meta["stream_index"],
                         int(np.asarray(tensors[0]).reshape(-1)[0])))
            if meta.get("stream_last"):
                done.set()

        fw.adopt_stream(snap, emit)
        assert done.wait(120)
    served = dict(rest)
    # tokens delivered before the drain may be re-sent after it: by index
    # the stream is the undrained one
    for i, t in enumerate(whole[0]):
        if i in served:
            assert served[i] == t
    assert toks[0] == whole[0][0] and max(served) == 23


# -- what is not built refuses ------------------------------------------------

def _open(custom, **kw):
    return nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm "
        f"model=latent_shortcut_tiny custom={custom} invoke-dynamic=true "
        "name=f ! tensor_sink name=out", **kw)


@pytest.mark.parametrize("custom,reason", [
    ("serve:continuous,slots:2,draft:llama_tiny",
     "latent pool its k\\+1 queries take the gather reference"),
    ("max_new:4", "served by serve:continuous only"),
    ("serve:continuous,slots:2,quant:int8",
     "no quantized layout yet, and this model has a layer pattern: "
     "sparse experts, latent attention"),
    ("serve:continuous,slots:2,quant:int4", "latent projections"),
])
def test_unsupported_options_refuse_at_construction(custom, reason):
    with pytest.raises(Exception, match=reason):
        _open(custom)


def test_tensor_parallel_refuses_a_latent_model():
    cfg = llama.PRESETS["latent_shortcut_tiny"]
    assert llama.tp_divisibility_problems(cfg, 1) == []
    probs = llama.tp_divisibility_problems(cfg, 2)
    assert any("no KV-head axis to shard" in p for p in probs)
    with pytest.raises(Exception, match="no KV-head axis to shard"):
        _open("serve:continuous,slots:2", model_parallel=2)


def test_paths_of_the_one_kind_decoder_refuse_with_the_reason():
    cfg = llama.PRESETS["latent_shortcut_tiny"]
    params = jax.eval_shape(lambda: llama.init_params(cfg))
    toks = jnp.zeros((1, 4), jnp.int32)
    why = "latent attention .one cache row for all heads., a shortcut"
    with pytest.raises(NotImplementedError, match=why):
        llama.forward_cached(params, toks, None, 0, cfg)
    with pytest.raises(NotImplementedError, match=why):
        llama.forward_seq_parallel(None, params, toks, cfg)
    with pytest.raises(NotImplementedError, match=why):
        llama.load_checkpoint("/nonexistent.safetensors", cfg)
    with pytest.raises(ValueError, match="no quantized layout"):
        llama.init_params_int8(cfg)
    with pytest.raises(ValueError, match="softmax"):
        moe.ExpertsConfig(n_experts=8, top_k=2, hidden=8, scoring="tanh")
