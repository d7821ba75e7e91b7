"""Selective state-space (Mamba-1) layers whose float32 state a SLOT owns,
beside unrotated attention layers over one KV head in the paged cache — on
the serving path, held to the plain reference
(``benchmark/reference/ssm_hybrid_decoder.py``: float32, the recurrence a
Python loop over positions, imports nothing of the program) ON LOGITS, at a
toy size with the order of the benchmark's configuration: four layers
``mamba mamba | attention | mamba``, 128 channels and a state of 16 a Mamba
layer, a step size of rank 8, filters of 4 taps, four query heads over one
KV head of 16.

**Tolerances.**  The toy runs float32 compute (and a float32 cache and
state) over the bfloat16 weights the model module makes, so program and
reference differ by the order of float32 sums alone: 5e-6 of a logit
measured, ``TOL`` = 2e-4 allowed — for the three forms of the recurrence
(the cacheless forward's step-by-step scan, a padded prefill chunk's
associative scan, a decode step's update) against the one reference.  The
same weights rounded to float8 (the benchmark's control) move a logit by
0.2 and more: a thousand times ``TOL``.  A state lost (zeros in place of
the carried matrix) moves a logit by 100 x ``TOL`` and more:
``test_a_lost_state_is_visible`` holds that THESE tests' comparison, on
logits, sees it.  The carried state against the reference's ``s_t`` is
compared to 1e-5 (float32 rounding of T products and sums), the
associative scan against the sequential recurrence to 1e-5 (the same
numbers summed in another order), the kernel against its twin to 1e-5 on
the state and 1e-5 of ``y``'s largest (float32, its order apart).
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
from benchmark.models import ssm_hybrid_decoder as M  # noqa: E402
from benchmark.reference import ssm_hybrid_decoder as R  # noqa: E402
from nnstreamer_tpu.filters.kv_blocks import BlockManager  # noqa: E402
from nnstreamer_tpu.filters.llm import serving_plan  # noqa: E402
from nnstreamer_tpu.models import llama, zoo  # noqa: E402
from nnstreamer_tpu.ops import ssm as S  # noqa: E402

TOL = 2e-4
ZOO = "toy_ssm_hybrid_for_tests"
C, BS = 8, 4        # prefill chunk and block size of every loop below
E, N = 128, 16      # a Mamba layer's channels and state


def real_cfg() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        return json.load(f)


def toy_cfg() -> dict:
    """The benchmark's configuration file with toy widths and depth: the
    period of 4 puts attention at layer 2."""
    cfg = copy.deepcopy(real_cfg())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               vocab_size=512, num_hidden_layers=4, attn_layer_period=4,
               attn_layer_offset=2, mamba_dt_rank=8)
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
    return np.random.default_rng(seed).integers(1, 512, shape).astype(
        np.int32)


# -- the description ----------------------------------------------------------

def test_the_toy_preset_has_the_benchmarks_order(toy):
    _, _, lcfg = toy
    preset = llama.PRESETS["ssm_hybrid_tiny"]
    assert [k.name for k in preset.kinds] == [
        "ssm.dense", "ssm.dense", "full.nope.dense", "ssm.dense"]
    assert preset.kinds == lcfg.kinds
    assert [k.cache for k in preset.kinds] == ["ssm", "ssm", "full", "ssm"]
    assert (preset.n_ssm_layers, preset.n_full_layers, preset.n_conv_layers,
            preset.ssm_inner, preset.head_dim) == (3, 1, 0, E, 16)
    assert preset.slot_state
    bundle = zoo.build("ssm_hybrid_tiny", {})
    assert bundle.param_pspecs is None
    logits = bundle.apply_fn(bundle.params, _tokens((1, 12)))
    assert logits.shape == (1, 12, 512) and np.isfinite(logits).all()
    assert llama.param_bytes_estimate(preset) == sum(
        x.nbytes for x in jax.tree.leaves(bundle.params))
    pool = llama.init_paged_cache(preset, 10, BS, slots=3)
    assert {k: (v.shape, v.dtype) for k, v in pool.items()} == {
        "k": ((1, 10, BS, 1, 16), jnp.bfloat16),
        "v": ((1, 10, BS, 1, 16), jnp.bfloat16),
        "ssm": ((3, 3, N, E), jnp.float32),
        "ssm_conv": ((3, 3, 3 * E), jnp.bfloat16)}
    assert llama.block_size_of(
        {"ssm": pool["ssm"], "ssm_conv": pool["ssm_conv"],
         "k": pool["k"]}) == BS
    assert llama.allocated_leaves(pool) == ["k", "v"]
    assert llama.paged_cache_bytes(preset, 10, BS, slots=3) == sum(
        x.nbytes for x in pool.values())
    assert llama.ssm_state_bytes(preset, 3) == pool["ssm"].nbytes
    assert llama.conv_state_bytes(preset, 3) == pool["ssm_conv"].nbytes
    plan = serving_plan(preset, slots=3, block_size=BS)
    assert plan["programs"] == 3 and plan["win_ring"] == 0
    assert plan["ssm_state_bytes"] == pool["ssm"].nbytes
    assert plan["conv_state_bytes"] == pool["ssm_conv"].nbytes
    # the cache a token costs is the attention layer's alone
    assert plan["decode_bytes_per_ctx_token"] == 2 * 1 * 16 * 2
    assert "state-space layers" in llama.pattern_traits(preset)
    assert serving_plan(llama.PRESETS["conv_moe_tiny"], slots=3)[
        "ssm_state_bytes"] == 0


@pytest.mark.parametrize("kind", [
    dict(ssm=True, window=8), dict(ssm=True, latent=True),
    dict(ssm=True, rope=False), dict(ssm=True, shortcut="open"),
    dict(ssm=True, conv=True)],
    ids=["window", "latent", "unrotated", "shortcut", "conv"])
def test_a_state_space_kind_with_other_traits_refuses(kind):
    with pytest.raises(ValueError, match="a state-space layer has no "
                                         "attention"):
        llama.LayerKind(**kind)


@pytest.mark.parametrize("change,reason", [
    (dict(ssm_dt_rank=0), "needs ssm_expand, ssm_state and ssm_dt_rank"),
    (dict(ssm_conv_taps=1), "two or more ssm_conv_taps"),
    (dict(pattern=(llama.LayerKind(ssm=True),) * 4),
     "a model of state-space layers only has no paged cache"),
])
def test_a_config_the_paged_path_cannot_serve_refuses(change, reason):
    import dataclasses

    with pytest.raises(ValueError, match=reason):
        dataclasses.replace(llama.PRESETS["ssm_hybrid_tiny"], **change)


def test_the_configurations_tree_is_the_bytes_reckoned():
    """At the published widths, by shapes, nothing allocated: 3.03 G
    parameters, 6.40 GB resident with the head's transposed copy and the
    float32 leaves, 38 % of the chip's ``bytes_limit``; 1,024 B of cache a
    token, 8,519,680 B of float32 state and 798,720 B of filter columns a
    slot; the cell's pool 277 MB of blocks + 545 MB + 51 MB of state."""
    cfg = real_cfg()
    M.register("ssm_hybrid_count_only", cfg, None)
    lcfg = zoo.build("ssm_hybrid_count_only", {}).config
    total = llama.param_bytes_estimate(lcfg, param_dtype="bfloat16")
    assert total == M.tree_bytes(cfg) == 6_399_313_408
    assert 0.37 < total / 16_909_336_064 < 0.39
    shapes = jax.eval_shape(lambda: llama.init_params(lcfg, 0, "bfloat16"))
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == total
    assert llama.walk_plan(lcfg.kinds) == llama.WalkPlan(0, 14, 2)
    assert [l for l, k in enumerate(lcfg.kinds) if not k.ssm] == [7, 21]
    assert all(not k.rope for k in lcfg.kinds if not k.ssm)
    plan = serving_plan(lcfg, slots=64, block_size=16, kv_blocks=4224)
    assert plan["decode_bytes_per_ctx_token"] == 1024
    assert plan["ssm_state_bytes"] == 64 * 8_519_680
    assert plan["conv_state_bytes"] == 64 * 798_720
    assert plan["pool_bytes"] == 4224 * 16 * 1024 + 64 * (8_519_680
                                                          + 798_720)
    assert plan["n_blocks"] == 4224 and plan["max_blocks"] == 256


# -- the program against the reference, on logits ----------------------------

def test_forward_is_the_reference(toy):
    """The cacheless forward: the recurrence run over the sequence step
    by step from zero."""
    cfg, tree, lcfg = toy
    toks = _tokens((2, 40))
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    got = np.asarray(jax.jit(lambda p, t: llama.forward(
        p, t, lcfg, "float32"))(tree, toks))
    assert np.abs(got - ref).max() < TOL
    low = np.asarray(R.logits(tree, jnp.asarray(toks), cfg, **M.CONTROL))
    assert np.abs(low - ref).max() > 1000 * TOL


def _paged(lcfg, slots=3, n_blocks=60, max_blocks=16):
    """A pool whose state leaves hold what a former stream left, a table a
    slot, and the two jitted programs as the loop builds them."""
    pool = llama.init_paged_cache(lcfg, n_blocks, BS, "float32", slots=slots)
    pool["ssm"] = pool["ssm"] + 7.0
    pool["ssm_conv"] = pool["ssm_conv"] + 7.0
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


def _reference_state(tree, cfg, toks):
    """The reference's state ``s_t`` [T, N, E] of the first Mamba layer,
    and its filter's input ``x`` [T, E], for one row."""
    eps = cfg["rms_norm_eps"]
    lp = jax.tree.map(lambda a: a[0], tree["layers"]["ssm.dense"])
    with jax.default_matmul_precision("highest"):
        x = tree["embed"][toks[None]].astype(jnp.float32)
        h = R._rmsnorm(x, lp["ln_attn"], eps)
        xc, _z, dt, b, c, a = R.mamba_inputs(h, lp, cfg)
        states = []
        R.recurrence(xc, dt, b, c, a, states=states)
        pre = (h @ R._f32(lp["w_in"], None))[..., :E]
    return np.stack([np.asarray(s[0]) for s in states]), np.asarray(pre[0])


@pytest.mark.parametrize("T", [1, 3, 5, 8, 13, 16, 20],
                         ids=lambda t: f"prompt{t}")
def test_the_state_after_a_padded_chunk_is_the_state_at_the_valid_length(
        toy, T):
    """A prompt of ``T`` tokens prefilled in chunks of 8, the last one
    padded: the slot's state is the REFERENCE's ``s_{T-1}`` and its filter
    columns the reference's last three inputs — taken at the row's valid
    length, never at the chunk's last column — with zero where the stream
    has no such token yet; what the slot's former stream left (the leaves
    start at 7.0) is gone; the other slots' state is untouched; the logits
    are the reference's."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, _, _ = _paged(lcfg)
    row = _tokens((T,), seed=T)
    lg, pool = _prefill(tree, prefill, pool, tables, 1, row)
    ref = np.asarray(R.logits(tree, jnp.asarray(row[None]), cfg))
    assert np.abs(lg - ref[0, T - 1]).max() < TOL
    want, pre = _reference_state(tree, cfg, row)
    state = np.asarray(pool["ssm"])
    assert np.abs(state[0, 1] - want[T - 1]).max() < 1e-5 * max(
        1.0, np.abs(want[T - 1]).max())
    cols = np.concatenate([np.zeros((3, E), np.float32), pre])[T:T + 3]
    assert np.abs(np.asarray(pool["ssm_conv"])[0, 1].reshape(3, E)
                  - cols).max() < 1e-5
    assert np.all(state[:, [0, 2]] == 7.0)
    assert np.all(np.asarray(pool["ssm_conv"])[:, [0, 2]] == 7.0)


@pytest.mark.parametrize("T", [5, 13, 20],
                         ids=["shorter_than_a_chunk", "not_a_multiple",
                              "three_chunks"])
def test_chunked_prefill_then_paged_decode_is_the_reference(toy, T):
    """Prefill in padded chunks of 8 (``n_valid`` < 8 on the last), then
    decode one token a step through the pool by slot, every step's logits
    against the reference's full forward pass — beside a second slot that
    decodes from its own prompt, and a third that is parked and whose
    state nothing touches."""
    cfg, tree, lcfg = toy
    n = 12
    pool, tables, prefill, decode, park = _paged(lcfg)
    toks = _tokens((2, 24 + n), seed=T)
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))
    T0 = (T, 9)
    for s in (0, 1):
        lg, pool = _prefill(tree, prefill, pool, tables, s, toks[s, :T0[s]])
        assert np.abs(lg - ref[s, T0[s] - 1]).max() < TOL
    pos = np.asarray([T0[0], T0[1], park], np.int32)
    tabs = {"full": tables, "slot": np.arange(3, dtype=np.int32)}
    for _ in range(n):
        tok = np.asarray([toks[0, pos[0]], toks[1, pos[1]], 0], np.int32)
        lg, pool = decode(tree, tok[:, None], pool, tabs, pos)
        for s in (0, 1):
            assert np.abs(np.asarray(lg)[s, 0] - ref[s, pos[s]]).max() < TOL
        pos[:2] += 1
    assert np.all(np.asarray(pool["ssm"])[:, 2] == 7.0)
    assert np.all(np.asarray(pool["ssm_conv"])[:, 2] == 7.0)


def test_a_parked_row_leaves_its_state_untouched(toy):
    """The same live row decoded beside two parked rows and beside two
    live ones: bit for bit the same logits and the same state; the parked
    rows' state is what it was (a slot in the middle of its next stream's
    prefill is parked in the decode step and must keep its state)."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, decode, park = _paged(lcfg)
    toks = _tokens((3, 14), seed=3)
    for s in range(3):
        _, pool = _prefill(tree, prefill, pool, tables, s, toks[s, :9])
    before = {k: np.asarray(pool[k]).copy() for k in ("ssm", "ssm_conv")}
    tabs = {"full": tables, "slot": np.arange(3, dtype=np.int32)}
    tok = toks[:, 9:10]
    pool_a = jax.tree.map(jnp.copy, pool)
    lg_all, pool_all = decode(tree, tok, pool, tabs,
                              np.asarray([9, 9, 9], np.int32))
    lg_one, pool_one = decode(tree, tok, pool_a, tabs,
                              np.asarray([park, 9, park + 5], np.int32))
    assert np.array_equal(np.asarray(lg_all)[1], np.asarray(lg_one)[1])
    for k in ("ssm", "ssm_conv"):
        one, every = np.asarray(pool_one[k]), np.asarray(pool_all[k])
        assert np.array_equal(one[:, 1], every[:, 1])
        assert np.array_equal(one[:, [0, 2]], before[k][:, [0, 2]])
        assert not np.array_equal(every[:, 0], before[k][:, 0])


def test_a_slot_id_past_the_leaf_reads_nan_and_writes_nothing(toy):
    """A slot id outside the leaf (a value, which no trace can refuse)
    reads NaN into that row's logits and writes nothing: it is never
    folded onto another slot's state, whose stream stays the
    reference's."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, decode, park = _paged(lcfg)
    with pytest.raises(ValueError, match="kept by slot"):
        decode(tree, np.zeros((3, 1), np.int32), pool, tables,
               np.zeros((3,), np.int32))
    toks = _tokens((3, 11), seed=5)
    for s in range(3):
        _, pool = _prefill(tree, prefill, pool, tables, s, toks[s, :10])
    before = np.asarray(pool["ssm"]).copy()
    lg, after = decode(tree, toks[:, 10:11], pool,
                       {"full": tables,
                        "slot": np.asarray([0, 3, -1], np.int32)},
                       np.asarray([10, 10, 10], np.int32))
    lg, after = np.asarray(lg), np.asarray(after["ssm"])
    ref = np.asarray(R.logits(tree, jnp.asarray(toks[:1]), cfg))[0, 10]
    assert np.abs(lg[0, 0] - ref).max() < TOL
    assert np.isnan(lg[1]).all() and np.isnan(lg[2]).all()
    assert np.array_equal(after[:, 1:], before[:, 1:])
    assert not np.array_equal(after[:, 0], before[:, 0])


def test_a_lost_state_is_visible(toy):
    """Decoding from a state of zeros in place of the carried matrix
    moves the next token's logits by 100 x ``TOL`` and more: a comparison
    on logits, as these tests make it, sees a state lost at a chunk's edge
    or at admission."""
    cfg, tree, lcfg = toy
    pool, tables, prefill, decode, park = _paged(lcfg)
    toks = _tokens((1, 14), seed=9)
    _, pool = _prefill(tree, prefill, pool, tables, 0, toks[0, :13])
    tabs = {"full": tables, "slot": np.arange(3, dtype=np.int32)}
    pos = np.asarray([13, park, park], np.int32)
    tok = np.asarray([[toks[0, 13]], [0], [0]], np.int32)
    lost = dict(jax.tree.map(jnp.copy, pool),
                ssm=jnp.zeros_like(pool["ssm"]))
    lg, _ = decode(tree, tok, pool, tabs, pos)
    lg_lost, _ = decode(tree, tok, lost, tabs, pos)
    ref = np.asarray(R.logits(tree, jnp.asarray(toks), cfg))[0, 13]
    assert np.abs(np.asarray(lg)[0, 0] - ref).max() < TOL
    assert np.abs(np.asarray(lg_lost)[0, 0] - ref).max() > 100 * TOL


# -- the two scans and the kernel ---------------------------------------------

def _mixer_inputs(seed, B, T):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (B, T, E))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, T, E)) - 3.0)
    b = jax.random.normal(keys[2], (B, T, N))
    c = jax.random.normal(keys[3], (B, T, N))
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, E)) * jnp.exp(0.1 * jax.random.normal(
                              keys[4], (N, E)))
    return x, dt, b, c, a


@pytest.mark.parametrize("n_valid", [32, 17, 1])
def test_the_associative_scan_chunk_is_the_sequential_recurrence(
        toy, n_valid):
    """A prefill chunk of 32 columns through the program's state-space
    mixer (the associative scan: five levels) from a carried state, and
    the same columns through the cacheless form (a scan step by step) and
    through the reference's Python loop: the outputs of the real columns
    and the state at ``n_valid`` agree to float32 rounding; the padding
    past ``n_valid`` moves the state by nothing."""
    cfg, tree, lcfg = toy
    lp = jax.tree.map(lambda a: a[0], tree["layers"]["ssm.dense"])
    T = 32
    h = jax.random.normal(jax.random.PRNGKey(n_valid), (1, T, 64))
    pool = llama.init_paged_cache(lcfg, 8, BS, "float32", slots=2)
    leaves = (pool["ssm"], pool["ssm_conv"])
    chunk = jax.jit(lambda lp, h, st, nv: llama._ssm_mixer(
        lcfg, lp, h, st, jnp.asarray([1], jnp.int32),
        jnp.asarray([0], jnp.int32), 0, jnp.asarray([True]), nv))
    out, (s_leaf, _c) = chunk(lp, h, leaves, jnp.int32(n_valid))
    whole, _ = jax.jit(lambda lp, h: llama._ssm_mixer(lcfg, lp, h))(lp, h)
    out, whole = np.asarray(out), np.asarray(whole)
    scale = np.abs(whole).max()
    assert np.abs(out[0, :n_valid] - whole[0, :n_valid]).max() \
        < 1e-5 * scale
    # the state at n_valid is the reference loop's s_{n_valid - 1}
    with jax.default_matmul_precision("highest"):
        xc, _z, dt, b, c, a = R.mamba_inputs(h, lp, cfg)
        states = []
        R.recurrence(xc, dt, b, c, a, states=states)
    want = np.asarray(states[n_valid - 1][0])
    assert np.abs(np.asarray(s_leaf)[0, 1] - want).max() \
        < 1e-5 * max(1.0, np.abs(want).max())
    assert np.all(np.asarray(s_leaf)[:, 0] == 0)


@pytest.mark.parametrize("case", ["all_live", "parked_and_fresh",
                                  "none_live", "ids_past_the_leaf"])
def test_the_kernel_is_its_twin(case):
    """The update kernel under the interpreter against its ``jax.numpy``
    twin, rows permuted over the slots: live rows, parked rows (returning
    zeros, writing nothing), a stream's first token (reading zeros), no
    live row at all, and ids outside the leaf (NaN, nothing written)."""
    B, L, slots = 5, 3, 6
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(keys[0], (L, slots, N, E))
    x, dt, b, c, a = (v[:, 0] if v.ndim == 3 else v
                      for v in _mixer_inputs(1, B, 1))
    z = jax.random.normal(keys[1], (B, E))
    d = 1.0 + 0.1 * jax.random.normal(keys[2], (E,))
    ids, live, fresh = {
        "all_live": ([4, 2, 0, 1, 5], [1] * 5, [0] * 5),
        "parked_and_fresh": ([4, 2, 0, 1, 5], [0, 1, 0, 1, 1],
                             [0, 1, 1, 0, 0]),
        "none_live": ([0, 1, 2, 3, 4], [0] * 5, [1] * 5),
        "ids_past_the_leaf": ([0, 6, -1, 3, 4], [1, 1, 1, 0, 1],
                              [0, 0, 0, 0, 1])}[case]
    args = (state, jnp.int32(1), jnp.asarray(ids, jnp.int32),
            jnp.asarray(live, bool), jnp.asarray(fresh, bool), x, dt, z, b,
            c, a, d)
    y0, s0 = S.ssm_update_reference(*args)
    y1, s1 = jax.jit(lambda *v: S.ssm_update(*v, interpret=True))(*args)
    y0, y1 = np.asarray(y0), np.asarray(y1)
    assert np.array_equal(np.isnan(y0), np.isnan(y1))
    scale = max(1.0, float(np.nanmax(np.abs(y0))))
    assert np.nanmax(np.abs(y0 - y1)) < 1e-5 * scale
    assert np.abs(np.asarray(s0) - np.asarray(s1)).max() < 1e-5
    # layers other than 1, and slots no live row names, as they were
    named = {i for i, lv in zip(ids, live) if lv and 0 <= i < slots}
    keep = [s for s in range(slots) if s not in named]
    s1 = np.asarray(s1)
    assert np.array_equal(s1[[0, 2]], np.asarray(state)[[0, 2]])
    assert np.array_equal(s1[1, keep], np.asarray(state)[1, keep])
    assert np.all(y1[~np.asarray(live, bool) & ~np.isnan(y1).any(-1)] == 0)


# -- through the continuous loop ----------------------------------------------

def _serve(opts, prompts, max_new=24, stagger=0.05):
    from nnstreamer_tpu.core.log import metrics
    from nnstreamer_tpu.utils import tracing

    tracing.recorder.clear()
    p = nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm model={ZOO} "
        f"custom=max_new:{max_new},max_seq:128,dtype:float32,"
        f"serve:continuous,slots:3,block_size:{BS},prefill_chunk:{C},"
        f"kv_blocks:60,temperature:0.0{opts} invoke-dynamic=true "
        "name=f ! tensor_sink name=out", trace_mode="ring")
    got = {i: [] for i in range(len(prompts))}
    seen = {}
    t0 = time.monotonic_ns()
    resets0 = metrics.snapshot().get("llm.serve.ssm_state_resets", 0)

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
        loop = p.element("f").fw._serve
        seen["events"] = [e for e in tracing.recorder.events()
                          if e.stage == "llm.serve" and e.ts >= t0]
        seen["stats"] = loop.pool_stats()
        seen["share_prefix"] = loop.kv.share_prefix
        seen["census"] = (loop._decode._cache_size(),
                          loop._prefill._cache_size(),
                          loop._set_tok._cache_size())
    seen["resets"] = metrics.snapshot().get(
        "llm.serve.ssm_state_resets", 0) - resets0
    return got, seen


#: lengths: shorter than a chunk of 8, not a multiple of it, three
#: chunks; then three more that take the slots the first ones leave
CHURN = (5, 13, 20, 17, 6, 24)


@pytest.fixture(scope="module")
def churn(toy):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 512, (n,)).astype(np.int32) for n in CHURN]
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
    """Some slot served two streams (its state zeroed at the second one's
    first chunk, from ``pos == 0``, a value): the second stream's tokens
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
    """One signature a program however the slots churned; the pool's
    accounting reports the state's bytes; every stream's first chunk
    counts one reset; ``serve.prefill_chunk`` carries the real columns it
    scanned and ``serve.decode`` the layers whose update took the kernel
    (none off the chip: the twin runs)."""
    _, _, seen = churn
    assert seen["census"] == (1, 1, 1)
    assert seen["stats"]["ssm_state_bytes"] == 3 * 3 * N * E * 4
    assert seen["stats"]["conv_state_bytes"] == 3 * 3 * 3 * E * 4
    assert seen["stats"]["blocks_free"] == seen["stats"]["blocks_total"]
    assert seen["resets"] == len(CHURN)
    chunks = [e.args for e in seen["events"]
              if e.kind == "serve.prefill_chunk"]
    # each prompt's chunks scan its length in real columns
    assert sum(a["ssm_scan_cols"] for a in chunks) == sum(CHURN)
    assert all(1 <= a["ssm_scan_cols"] <= C for a in chunks)
    dec = [e.args for e in seen["events"] if e.kind == "serve.decode"]
    assert dec and all(a["ssm_kernel_layers"] == 0 for a in dec)


def test_prefix_cache_on_is_prefix_cache_off_and_every_lookup_a_miss(toy):
    """Two requests share their first 16 tokens.  The state at the end of
    the shared prefix is in no block, so the manager shares nothing on
    this model: with ``prefix_cache`` on (the default) every lookup is a
    miss, nothing is indexed, and the tokens are those of ``prefix_cache``
    off — and the reference's."""
    cfg, tree, _ = toy
    from nnstreamer_tpu.core.log import metrics

    rng = np.random.default_rng(2)
    head = rng.integers(1, 512, (16,)).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(1, 512, (n,)).astype(
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
                      ssm_state_bytes=8192)
    assert kv.share_prefix is False and kv.slot_state
    tabs = kv.tabs(slice(1, 3))
    assert set(tabs) == {"full", "slot"} and tabs["slot"].tolist() == [1, 2]
    stats = kv.stats()
    assert (stats["ssm_state_bytes"], stats["conv_state_bytes"]) == (8192, 0)


# -- what is not built refuses ------------------------------------------------

def _open(custom, **kw):
    return nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm "
        f"model=ssm_hybrid_tiny custom={custom} invoke-dynamic=true "
        "name=f ! tensor_sink name=out", **kw)


@pytest.mark.parametrize("custom,reason", [
    ("serve:continuous,slots:2,draft:llama_tiny",
     "nor a state-space layer's state matrix; this target has a layer "
     "pattern: unrotated layers, state-space layers"),
    ("max_new:4", "served by serve:continuous only"),
    ("serve:continuous,slots:2,quant:int8",
     "no quantized layout yet, and this model has a layer pattern: "
     "unrotated layers, state-space layers"),
])
def test_unsupported_options_refuse_at_construction(custom, reason):
    with pytest.raises(Exception, match=reason):
        _open(custom)


@pytest.mark.parametrize("call", ["drain_stream", "adopt_stream"])
def test_drain_and_adopt_refuse_slot_owned_state(call):
    with _open("serve:continuous,slots:2,max_new:4") as p:
        fw = p.element("f").fw
        with pytest.raises(Exception, match="owns state-space state is not "
                                            "built.*scan from zeros.*"
                                            "state-space layers"):
            if call == "drain_stream":
                fw.drain_stream(1)
            else:
                fw.adopt_stream({"version": 2}, lambda *a: None)


def test_paths_of_the_one_kind_decoder_refuse_with_the_reason():
    cfg = llama.PRESETS["ssm_hybrid_tiny"]
    params = jax.eval_shape(lambda: llama.init_params(cfg))
    toks = jnp.zeros((1, 4), jnp.int32)
    why = r"state-space layers \(a float32 state matrix owned by the slot"
    with pytest.raises(NotImplementedError, match=why):
        llama.forward_cached(params, toks, None, 0, cfg)
    with pytest.raises(NotImplementedError, match=why):
        llama.forward_seq_parallel(None, params, toks, cfg)
    with pytest.raises(NotImplementedError, match=why):
        llama.load_checkpoint("model.safetensors", cfg)
    with pytest.raises(ValueError, match="slots >= 1"):
        llama.init_paged_cache(cfg, 10, BS)
    assert any("no tensor-parallel layout" in p
               for p in llama.tp_divisibility_problems(cfg, 2))
