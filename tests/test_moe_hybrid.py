"""A patterned model on the serving path: window and full attention in one
paged cache, sparse experts that know which experts they hold, a walk laid
out by periods — held to the plain reference
(``benchmark/reference/moe_hybrid_decoder.py``, float32, imports nothing
of the program) ON LOGITS, at a toy size with the pattern of the benchmark's
configuration: window-window-window-full twice, layer 0 dense and the rest
sparse (16 routed experts, 4 a token, 4 held here), q/k norm, heads of 32
where hidden / heads is 16, window 8.

**Tolerances.**  The toy runs float32 compute over the bfloat16 weights
the model module makes, so program and reference differ by the order of
float32 sums alone: 1e-5 of a logit measured, ``TOL`` = 2e-4 allowed.  The
same weights rounded to float8 (the benchmark's control, one precision
below the configuration's bfloat16) move a logit by 0.5 and more:
``test_float8_control_fails_the_tolerance`` holds that 1000 x above
``TOL``, so a lower precision cannot pass any comparison here.
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
from benchmark.models import moe_hybrid_decoder as M  # noqa: E402
from benchmark.reference import moe_hybrid_decoder as R  # noqa: E402
from nnstreamer_tpu.filters.base import FrameworkError  # noqa: E402
from nnstreamer_tpu.filters.llm import serving_plan  # noqa: E402
from nnstreamer_tpu.models import llama, moe, zoo  # noqa: E402

TOL = 2e-4
ZOO = "toy_hybrid_for_tests"


def toy_cfg(n_layers=8, held_first=4, held=4) -> dict:
    """The benchmark's configuration file with toy widths."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k_exaone_236b_a23b.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(hidden_size=64, intermediate_size=192, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=512,
               moe_intermediate_size=32, num_experts=held,
               num_experts_per_tok=4, sliding_window=8,
               num_hidden_layers=n_layers)
    cfg["sliding_windows"] = [8 if w else 0 for w in cfg["sliding_windows"]]
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_first=held_first)
    cfg["serve"] = {"slots": 3, "block_size": 4, "max_seq": 128}
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


# -- the description and the walk -------------------------------------------

def test_pattern_of_one_kind_is_the_plain_config():
    """A pattern that names the default kind everywhere IS no pattern: the
    config equals the plain one, so it walks the plain path and compiles
    to the program it compiled to before patterns existed."""
    plain = llama.PRESETS["llama_tiny"]
    same = llama.LlamaConfig(**{
        **{f.name: getattr(plain, f.name)
           for f in plain.__dataclass_fields__.values()},
        "pattern": (llama.LayerKind(),) * plain.n_layers})
    assert same == plain and not same.patterned

    def lowered(cfg):
        params = jax.eval_shape(lambda: llama.init_params(cfg))
        pool = jax.eval_shape(lambda: llama.init_paged_cache(cfg, 8, 4))
        return jax.jit(lambda p, t, pl, tb, pos: llama.forward_paged(
            p, t, pl, tb, pos, cfg)).lower(
                params, jax.ShapeDtypeStruct((2, 1), jnp.int32), pool,
                jax.ShapeDtypeStruct((2, 4), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.int32)).as_text()

    assert lowered(same) == lowered(plain)
    assert serving_plan(plain, slots=4)["win_ring"] == 0
    assert set(llama.init_paged_cache(plain, 8, 4)) == {"k", "v"}


@pytest.mark.parametrize("n_layers,plan", [
    (8, (8, 0, 0)),      # two periods: the second is no repeat of the first
    (12, (4, 4, 2)),     # layer 0's period one by one, then a scan of two
    (48, (4, 4, 11)),    # the published depth: 8 copies of the block
])
def test_walk_plan_lays_a_deep_model_out_by_periods(n_layers, plan):
    kinds = tuple(
        llama.LayerKind(window=0 if l % 4 == 3 else 8, rope=l % 4 != 3,
                        ffn="dense" if l == 0 else "experts")
        for l in range(n_layers))
    got = llama.walk_plan(kinds)
    assert (got.prefix, got.period, got.n_periods) == plan
    assert llama.walk_plan((llama.LayerKind(),) * 32) == llama.WalkPlan(
        0, 1, 32)


def test_the_toy_preset_has_the_benchmarks_pattern():
    cfg = llama.PRESETS["hybrid_moe_tiny"]
    assert [k.name for k in cfg.kinds[:4]] == [
        "window8.rope.dense", "window8.rope.experts",
        "window8.rope.experts", "full.nope.experts"]
    assert cfg.head_dim == 32 != cfg.dim // cfg.n_heads
    assert (cfg.n_full_layers, cfg.n_window_layers) == (2, 6)
    # ceil(8 / 4) + ceil(8 / 4) + 1 blocks a slot, whatever the context
    assert llama.window_ring_blocks(cfg, 4, 8) == 5
    assert llama.window_ring_blocks(cfg, 16, 32) == 1 + 2 + 1
    bundle = zoo.build("hybrid_moe_tiny", {})
    assert bundle.param_pspecs is None
    logits = bundle.apply_fn(bundle.params, _tokens((1, 12)))
    assert logits.shape == (1, 12, 512) and np.isfinite(logits).all()
    assert llama.param_bytes_estimate(cfg) == sum(
        x.nbytes for x in jax.tree.leaves(bundle.params))


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
    low = np.asarray(R.logits(tree, jnp.asarray(toks), cfg,
                              **M.CONTROL))
    assert np.abs(low - ref).max() > 1000 * TOL


@pytest.mark.parametrize("n_layers", [8, 12], ids=["unrolled", "scanned"])
def test_chunked_prefill_then_paged_decode_is_the_reference(n_layers):
    """Prefill in chunks of 8 into the two pools, then decode one token a
    step to context 43 — more than five windows of 8, so every ring entry
    has been overwritten several times — and compare every step's logits
    with the reference's full forward.  12 layers take the scan over
    periods (layer indices traced), 8 the one-by-one walk."""
    cfg = toy_cfg(n_layers)
    tree = M.weights(cfg, 7)
    M.register(ZOO + str(n_layers), cfg, tree)
    lcfg = zoo.build(ZOO + str(n_layers), {"dtype": "float32"}).config
    bs, C, B, T0, N = 4, 8, 2, 13, 30
    ring = llama.window_ring_blocks(lcfg, bs, C)
    n_blocks, max_blocks = 40, 16
    pool = llama.init_paged_cache(lcfg, n_blocks, bs, "float32",
                                  win_blocks=B * ring)
    assert pool["k"].shape[:2] == (lcfg.n_full_layers, n_blocks)
    assert pool["k_win"].shape[:2] == (lcfg.n_window_layers, B * ring)
    tables = np.full((B, max_blocks), n_blocks, np.int32)
    tables[0, :12], tables[1, :12] = np.arange(12), 12 + np.arange(12)
    win = (np.arange(B)[:, None] * ring + np.arange(ring)[None, :]).astype(
        np.int32)
    toks = _tokens((B, T0 + N))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    prefill = jax.jit(lambda p, t, pl, tb, pos, off: llama.forward_paged(
        p, t, pl, tb, pos, lcfg, "float32", logit_off=off))
    decode = jax.jit(lambda p, t, pl, tb, pos: llama.forward_paged(
        p, t, pl, tb, pos, lcfg, "float32", with_stats=True))
    P = -(-T0 // C) * C
    for b in range(B):
        row = np.pad(toks[b:b + 1, :T0], ((0, 0), (0, P - T0)))
        for p0 in range(0, P, C):
            final = p0 + C >= P
            lg, pool = prefill(
                tree, row[:, p0:p0 + C], pool,
                {"full": tables[b:b + 1], "win": win[b:b + 1]},
                np.asarray([p0], np.int32),
                np.int32(T0 - 1 - p0 if final else 0))
        assert np.abs(np.asarray(lg)[0, 0] - ref[b, T0 - 1]).max() < TOL
    pos = np.full((B,), T0, np.int32)
    for i in range(N):
        lg, pool, stats = decode(tree, toks[:, T0 + i][:, None], pool,
                                 {"full": tables, "win": win}, pos + i)
        assert np.abs(np.asarray(lg)[:, 0] - ref[:, T0 + i]).max() < TOL
    pairs, hit, most, identity, passes = (int(v) for v in stats)
    assert identity == 0   # this router has no identity experts
    assert passes == 0     # the CPU takes ragged_dot: no kernel pass
    sparse = n_layers - 1
    assert 0 < hit <= sparse * 4 and most <= B * 4
    assert hit <= pairs <= sparse * B * 4


# -- the shares add up --------------------------------------------------------

def test_the_shares_of_all_ranks_add_up_to_the_whole_layer():
    """16 routed experts over 4 ranks of 4: the partial results of the
    four ranks, the shared expert counted once, are the uncut layer —
    the same router, the same weights over ALL chosen experts — in the
    program's layer and in the reference's alike."""
    D, F, E, k = 64, 32, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(3), 9)
    full = {
        "w_router": jax.random.normal(keys[0], (D, E)) * D ** -0.5,
        "router_bias": 0.02 * jax.random.normal(keys[1], (E,)),
        "we_gate": jax.random.normal(keys[2], (E, D, F)) * D ** -0.5,
        "we_up": jax.random.normal(keys[3], (E, D, F)) * D ** -0.5,
        "we_down": jax.random.normal(keys[4], (E, F, D)) * F ** -0.5,
        "ws_gate": jax.random.normal(keys[5], (D, F)) * D ** -0.5,
        "ws_up": jax.random.normal(keys[6], (D, F)) * D ** -0.5,
        "ws_down": jax.random.normal(keys[7], (F, D)) * F ** -0.5,
    }
    h = jax.random.normal(keys[8], (3, 5, D))

    def layer(first, count, shared=True):
        ex = moe.ExpertsConfig(n_experts=E, top_k=k, hidden=F,
                               shared=1 if shared else 0, scale=2.5,
                               held_first=first, held_count=count)
        lp = dict(full, **{leaf: full[leaf][first:first + (count or E)]
                           for leaf in moe.STACKED_LEAVES})
        with jax.default_matmul_precision("highest"):
            return np.asarray(moe.moe_ffn(h, lp, ex, jnp.float32)[0])

    whole = layer(0, 0)
    shared_only = layer(0, 4) - layer(0, 4, shared=False)
    parts = sum(layer(r * 4, 4, shared=False) for r in range(4))
    assert np.abs(parts + shared_only - whole).max() < 1e-5

    # and the reference's uncut layer is the program's: by hand, every
    # chosen expert of every token
    x = np.asarray(h, np.float64).reshape(-1, D)
    f = {n: np.asarray(a, np.float64) for n, a in full.items()}
    s = 1 / (1 + np.exp(-(x @ f["w_router"])))
    want = np.zeros_like(x)
    for t in range(len(x)):
        chosen = np.argsort(-(s[t] + f["router_bias"]))[:k]
        for e in chosen:
            w = 2.5 * s[t, e] / s[t, chosen].sum()
            a = x[t] @ f["we_gate"][e]
            want[t] += w * ((a / (1 + np.exp(-a)) * (x[t] @ f["we_up"][e]))
                            @ f["we_down"][e])
    a = x @ f["ws_gate"]
    want += (a / (1 + np.exp(-a)) * (x @ f["ws_up"])) @ f["ws_down"]
    assert np.abs(whole.reshape(-1, D) - want).max() < 1e-4


def test_expert_layer_takes_its_kinds_whole_stack():
    """Handed the stack of three layers and a layer's index — traced, as
    the scan over periods hands it — the layer computes that layer's
    experts and no other's."""
    D, F, E = 64, 32, 4
    ex = moe.ExpertsConfig(n_experts=16, top_k=4, hidden=F, held_first=4,
                           held_count=E)
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    stack = {"we_gate": jax.random.normal(ks[0], (3, E, D, F)) * 0.1,
             "we_up": jax.random.normal(ks[1], (3, E, D, F)) * 0.1,
             "we_down": jax.random.normal(ks[2], (3, E, F, D)) * 0.1}
    lp = {"w_router": jax.random.normal(ks[3], (D, 16)) * 0.1,
          "router_bias": jnp.zeros((16,))}
    h = jax.random.normal(ks[4], (2, 3, D))
    for i in range(3):
        alone = moe.moe_ffn(h, dict(lp, **{n: a[i] for n, a in
                                           stack.items()}),
                            ex, jnp.float32)[0]
        whole = jax.jit(lambda li: moe.moe_ffn(
            h, dict(lp, **stack, _layer=li), ex, jnp.float32)[0])(
                jnp.int32(i))
        assert np.abs(np.asarray(whole) - np.asarray(alone)).max() < 1e-5


# -- through the continuous loop ----------------------------------------------

def _serve(cfg_opts, prompts, max_new=40, stagger=0.05):
    p = nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm model={ZOO} "
        f"custom=max_new:{max_new},max_seq:128,dtype:float32,"
        f"serve:continuous,slots:3,block_size:4,prefill_chunk:8,"
        f"kv_blocks:48,temperature:0.0{cfg_opts} invoke-dynamic=true "
        "name=f ! tensor_sink name=out", trace_mode="ring")
    got = {i: [] for i in range(len(prompts))}
    seen = {"win": 0, "full": 0, "bound": None, "hits": 0}
    with p:
        loop = None
        for i, pr in enumerate(prompts):
            b = nt.Buffer([pr])
            b.meta["req"] = i
            p.push("src", b)
            time.sleep(stagger)
        done = 0
        while done < len(prompts):
            b = p.pull("out", timeout=120)
            got[b.meta["req"]].append(
                int(np.asarray(b.tensors[0]).reshape(-1)[0]))
            done += bool(b.meta.get("stream_last"))
            loop = loop or p.element("f").fw._serve
            st = loop.pool_stats()
            seen["win"] = max(seen["win"], st["win_blocks_live"])
            seen["bound"] = (st["win_ring"], st["win_blocks_total"])
        from nnstreamer_tpu.core.log import metrics
        from nnstreamer_tpu.utils import tracing

        seen["events"] = [e for e in tracing.recorder.events()
                          if e.stage == "llm.serve"]
        seen["stats"] = loop.pool_stats()
        seen["census"] = (loop._decode._cache_size(),
                          loop._prefill._cache_size(),
                          loop._set_tok._cache_size())
    return got, seen


def test_continuous_loop_with_slots_joining_and_retiring(toy):
    """Five requests over three slots, so slots retire and are taken
    again by a stream that finds the last occupant's rows in its ring:
    every served token's logit is the reference's best (gap 0 — greedy
    over float32 logits that differ by 1e-5), at contexts to 57, seven
    windows.  The window pool never holds more than its ring a slot."""
    cfg, tree, lcfg = toy
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (n,)).astype(np.int32)
               for n in (5, 13, 9, 17, 6)]
    got, seen = _serve("", prompts)
    for i, pr in enumerate(prompts):
        assert len(got[i]) == 40
        toks = np.concatenate([pr, np.asarray(got[i], np.int32)])[None]
        gap, _ = R.served_gaps(tree, toks, cfg)
        assert float(np.asarray(gap)[0, len(pr) - 1:-1].max()) < TOL
    ring, total = seen["bound"]
    assert ring == llama.window_ring_blocks(lcfg, 4, 8) == 5
    assert total == 3 * ring and 0 < seen["win"] <= total
    assert seen["stats"]["blocks_free"] == seen["stats"]["blocks_total"]
    # one signature each, however the slots churned
    assert seen["census"] == (1, 1, 1)
    # the spans: expert counts on serve.decode, both pools on serve.iter
    dec = [e.args for e in seen["events"] if e.kind == "serve.decode"]
    assert dec and all(
        {"moe_pairs", "moe_experts_hit", "moe_max_per_expert"} <= set(a)
        for a in dec)
    sparse, held, chunk = 7, 4, 8
    assert all(0 < a["moe_experts_hit"] <= sparse * held * chunk
               and a["moe_experts_hit"] <= a["moe_pairs"]
               <= sparse * chunk * a["occupancy"] * 4 for a in dec)
    its = [e.args for e in seen["events"] if e.kind == "serve.iter"]
    assert its and all("full_blocks" in a and "win_blocks" in a
                       for a in its)
    assert max(a["win_blocks"] for a in its) <= total
    assert max(a["full_blocks"] for a in its) > 0


def test_shared_prefix_on_a_window_model_is_a_miss_and_is_right(toy):
    """Two requests share their first 16 tokens.  A window layer's rows
    live in the ring of the slot that wrote them, so the second request
    cannot resume from the first one's blocks: the lookup is a miss by
    the stated rule (docs/SERVING.md), it prefills from position 0, and
    its tokens are the reference's."""
    cfg, tree, _ = toy
    from nnstreamer_tpu.core.log import metrics

    rng = np.random.default_rng(2)
    head = rng.integers(0, 512, (16,)).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(0, 512, (n,)).astype(
        np.int32)]) for n in (3, 5)]
    before = metrics.snapshot().get("llm.serve.prefix_hits", 0)
    got, seen = _serve("", prompts, max_new=12, stagger=0.5)
    after = metrics.snapshot().get("llm.serve.prefix_hits", 0)
    assert after == before
    assert seen["stats"]["blocks_cached"] == 0
    for i, pr in enumerate(prompts):
        toks = np.concatenate([pr, np.asarray(got[i], np.int32)])[None]
        gap, _ = R.served_gaps(tree, toks, cfg)
        assert float(np.asarray(gap)[0, len(pr) - 1:-1].max()) < TOL


# -- what is not built refuses ------------------------------------------------

def _open(custom):
    return nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm "
        f"model=hybrid_moe_tiny custom={custom} invoke-dynamic=true name=f "
        "! tensor_sink name=out")


@pytest.mark.parametrize("custom,reason", [
    ("serve:continuous,slots:2,draft:llama_tiny",
     "draft: with a patterned target"),
    ("max_new:4", "served by serve:continuous only"),
    ("serve:continuous,slots:2,quant:int8", "no quantized layout"),
    ("serve:continuous,slots:2,quant:int4", "no quantized layout"),
])
def test_unsupported_options_refuse_at_construction(custom, reason):
    with pytest.raises(Exception, match=reason):
        _open(custom)


def test_tensor_parallel_refuses_a_patterned_model():
    cfg = llama.PRESETS["hybrid_moe_tiny"]
    assert llama.tp_divisibility_problems(cfg, 1) == []
    probs = llama.tp_divisibility_problems(cfg, 2)
    assert probs and "no tensor-parallel layout" in probs[0]
    with pytest.raises(Exception, match="no tensor-parallel layout"):
        nt.Pipeline(
            "appsrc name=src ! tensor_filter framework=llm "
            "model=hybrid_moe_tiny custom=serve:continuous,slots:2 "
            "invoke-dynamic=true name=f ! tensor_sink name=out",
            model_parallel=2)


def test_paths_of_the_one_kind_decoder_refuse_a_pattern():
    cfg = llama.PRESETS["hybrid_moe_tiny"]
    params = jax.eval_shape(lambda: llama.init_params(cfg))
    with pytest.raises(NotImplementedError, match="one-kind decoder"):
        llama.forward_cached(params, jnp.zeros((1, 4), jnp.int32),
                             None, 0, cfg)
    with pytest.raises(NotImplementedError, match="one-kind decoder"):
        llama.forward_seq_parallel(None, params,
                                   jnp.zeros((1, 4), jnp.int32), cfg)
    with pytest.raises(ValueError, match="no quantized layout"):
        llama.init_params_int8(cfg)


def test_drain_and_adopt_of_a_two_pool_slot_refuse():
    p = _open("serve:continuous,slots:2,block_size:4,prefill_chunk:8,"
              "max_new:4")
    with p:
        fw = p.element("f").fw
        with pytest.raises(FrameworkError, match="two-pool slot"):
            fw.drain_stream(1)
        with pytest.raises(FrameworkError, match="two-pool slot"):
            fw.adopt_stream({"version": 2, "kind": "queued"}, None)


# -- the kernel's window, in interpret mode -----------------------------------

@pytest.mark.parametrize("window,ring", [
    (0, False), (8, False), (20, False), (8, True), (20, True)],
    ids=["full", "window8", "window20", "ring8", "ring20"])
def test_paged_kernel_with_a_window_is_its_reference(window, ring):
    """The Pallas kernel (interpret mode) against the XLA reference and
    against dense masked attention over the rows' true histories: rows at
    contexts below, at and far past the window, one of them idle."""
    from nnstreamer_tpu.ops import attention as A

    bs, H, hkv, D, B = 4, 4, 2, 32, 4
    lens = np.asarray([3, 9, 41, 0], np.int32)
    S = 48
    rng = np.random.default_rng(0)
    k_hist = rng.standard_normal((B, S, hkv, D)).astype(np.float32)
    v_hist = rng.standard_normal((B, S, hkv, D)).astype(np.float32)
    q = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    R_ = 7 if ring else S // bs    # ceil(20 / 4) + 1 + 1
    n_blocks = B * R_
    tables = (np.arange(B)[:, None] * R_ + np.arange(R_)[None, :]).astype(
        np.int32)
    kp = np.zeros((n_blocks, bs, hkv, D), np.float32)
    vp = np.zeros_like(kp)
    for b in range(B):
        for pos in range(lens[b]):     # later positions overwrite a ring
            e = tables[b, (pos // bs) % R_ if ring else pos // bs]
            kp[e, pos % bs], vp[e, pos % bs] = k_hist[b, pos], v_hist[b, pos]
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens))
    kern = np.asarray(A.paged_attention(*args, interpret=True,
                                        window=window, ring=ring))
    ref = np.asarray(A.paged_attention_reference(*args, window=window,
                                                 ring=ring))
    assert np.abs(kern - ref)[:3].max() < 1e-5
    for b in range(3):
        L = int(lens[b])
        lo = max(0, L - window) if window else 0
        kk = np.repeat(k_hist[b, lo:L], H // hkv, axis=1)
        vv = np.repeat(v_hist[b, lo:L], H // hkv, axis=1)
        s = np.einsum("hd,khd->hk", q[b, 0], kk) / np.sqrt(D)
        w = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hk,khd->hd", w / w.sum(-1, keepdims=True), vv)
        assert np.abs(kern[b, 0] - want).max() < 1e-5


# -- the benchmark's manifest, where tier 1 reaches it ------------------------

def test_run_py_and_the_drivers_name_no_model():
    """A new architecture enters as files: the harness finds them by the
    names in the configuration's file and spells none of them."""
    from benchmark.manifest import Manifest

    m = Manifest(ROOT)
    names = {c["name"] for c in m.doc["configs"]}
    for c in m.doc["configs"]:
        cfg = m.config({"config": c["name"]})
        names |= {cfg["model"], cfg["reference"]}
    bench = os.path.join(ROOT, "benchmark")
    files = [os.path.join(bench, "run.py")] + [
        os.path.join(bench, "drivers", f)
        for f in os.listdir(os.path.join(bench, "drivers"))
        if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert name not in text, f"{path} names {name}"


def test_every_configuration_names_files_that_are_there():
    from benchmark.manifest import Manifest

    m = Manifest(ROOT)
    assert "k_exaone_236b_a23b" in {c["name"] for c in m.doc["configs"]}
    for c in m.doc["configs"]:
        cfg = m.config({"config": c["name"]})
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert hasattr(m.driver(cfg), "load")
        model, ref = m.model(cfg), m.reference(cfg)
        assert model.ZOO_NAME and model.CONTROL
        if cfg["kind"] == "serve":
            assert callable(model.flops_per_token)
            assert callable(ref.served_gaps) and callable(ref.control_gaps)
    for w in m.doc["workloads"]:
        assert m.mix(w)["kind"] == m.config(w)["kind"]
        for metric in m.per_layer(w["name"]):
            assert callable(m.reader(metric["name"]))
