"""Continuous LLM serving over the block-paged KV cache (ISSUE 6).

Covers the contracts docs/SERVING.md §4 promises:

* paged decode emits token-for-token what the dense-cache per-request
  path emits, at EVERY occupancy 1..slots;
* the block allocator never leaks across stream churn and a recycled
  slot never sees a previous stream's cache rows;
* chunked prefill equals monolithic prefill;
* the standing loop's program census is CLOSED: stream join/leave/
  complete causes ZERO new XLA compilations once the loop is warm
  (the fixed-decode-signature pin);
* the Pallas paged-attention kernel (interpret mode on CPU) matches the
  reference formulation block for block;
* int4 continuous serving routes through the same paged path and
  matches the int4 per-request stream;
* the deep lint prices the block pool + the continuous decode programs.
"""

import numpy as np
import pytest

from nnstreamer_tpu.core.log import metrics
from nnstreamer_tpu.models import llama


def _fw(custom, model="llama_tiny"):
    from nnstreamer_tpu.filters.llm import LLMFramework

    fw = LLMFramework()
    fw.open({"model": model, "custom": custom})
    return fw


def _plain_tokens(prompt, custom, model="llama_tiny"):
    """Reference: the per-request streaming path (dense KV cache)."""
    fw = _fw(custom, model)
    try:
        return [int(ids[0]) for ids, *_ in fw.invoke_stream([prompt])]
    finally:
        fw.close()


def _serve_tokens(fw, prompts, timeout=300.0):
    """Submit ``prompts`` into a continuous loop together; returns the
    per-stream ordered token lists."""
    import threading

    got = {i: [] for i in range(len(prompts))}
    lock = threading.Lock()

    def emit_for(i):
        def emit(tensors, meta):
            with lock:
                got[i].append(int(tensors[0][0]))
        return emit

    for i, p in enumerate(prompts):
        fw.submit([p], {}, emit_for(i))
    assert fw.drain(timeout=timeout)
    return got


BASE = "max_new:5,stream_chunk:2,temperature:0.0,dtype:float32"


class TestPagedVsDense:
    def test_bit_identical_at_every_occupancy(self):
        """occupancy k = k prompts admitted together into a slots=4 loop;
        every stream must emit exactly its independent dense-path ids."""
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 500, (t,), dtype=np.int32)
                   for t in (3, 7, 5, 9)]
        want = [_plain_tokens(p, BASE) for p in prompts]
        fw = _fw(BASE + ",serve:continuous,slots:4,block_size:8")
        try:
            for k in range(1, 5):
                got = _serve_tokens(fw, prompts[:k])
                for i in range(k):
                    assert got[i] == want[i], f"occupancy {k}, stream {i}"
        finally:
            fw.close()

    def test_chunked_prefill_matches_monolithic(self):
        # 19 tokens with prefill_chunk:4 -> 5 chunks (last chunk: 3 real
        # rows + 1 pad); the dense reference prefills all 19 in one shot.
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 500, (19,), dtype=np.int32)
        want = _plain_tokens(prompt, BASE)
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:4")
        try:
            got = _serve_tokens(fw, [prompt])
        finally:
            fw.close()
        assert got[0] == want

    def test_int4_paged_matches_int4_stream(self):
        # satellite: the paged decode must route through the SAME
        # nibble-packed mats (_INT4_GROUPS fused qkv/gate-up) as the
        # static int4 path — greedy ids prove the routing end to end.
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 500, (6,), dtype=np.int32)
        base = BASE + ",quant:int4"
        want = _plain_tokens(prompt, base)
        fw = _fw(base + ",serve:continuous,slots:2,block_size:8")
        try:
            got = _serve_tokens(fw, [prompt])
        finally:
            fw.close()
        assert got[0] == want


class TestBlockAllocator:
    def test_churn_frees_every_block_and_slot(self):
        # kv_blocks sized so TWO streams fit but three defer: admission
        # must serialize the overflow, every stream must finish, and the
        # pool must drain back to fully free.
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:4,"
                 "kv_blocks:8")
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 500, (t,), dtype=np.int32)
                   for t in (3, 6, 4, 8, 5)]
        try:
            got = _serve_tokens(fw, prompts)
            assert all(len(v) == 5 for v in got.values())
            serve = fw._serve
            assert sorted(serve.kv.free) == list(range(serve.n_blocks))
            assert (serve.kv.tables == serve.sentinel).all()
            assert all(not b for b in serve.kv.slot_blocks)
            assert (serve._pos == serve.park).all()
        finally:
            fw.close()

    def test_recycled_slot_emits_reference_tokens(self):
        # slots:1 forces every stream through the SAME slot; stream i+1
        # decodes over blocks stream i just freed.  Any stale row leaking
        # through a recycled block/table would corrupt the greedy ids.
        rng = np.random.default_rng(4)
        prompts = [rng.integers(1, 500, (t,), dtype=np.int32)
                   for t in (4, 9, 6)]
        want = [_plain_tokens(p, BASE) for p in prompts]
        fw = _fw(BASE + ",serve:continuous,slots:1,block_size:4")
        try:
            got = _serve_tokens(fw, prompts)
        finally:
            fw.close()
        for i in range(3):
            assert got[i] == want[i], f"stream {i} after slot recycle"

    def test_impossible_reservation_rejected_not_wedged(self):
        # pool of 8 tokens total (kv_blocks:2 x block_size:4); a legal
        # (< max_seq) prompt whose T+max_new reservation can NEVER fit
        # must be rejected with stream_aborted — deferring would wedge
        # the FIFO head forever — and the loop stays serviceable.
        fw = _fw(BASE + ",serve:continuous,slots:1,block_size:4,"
                 "kv_blocks:2")
        metas = []
        try:
            fw.submit([np.arange(1, 8, dtype=np.int32)], {},
                      lambda t, m: metas.append(m))  # T=7, n=5 -> 12 > 8
            assert fw.drain(timeout=60)
            assert metas and metas[0].get("stream_aborted") is True
            got = _serve_tokens(fw, [np.array([1, 2, 3], np.int32)])
            assert len(got[0]) == 5  # a fitting prompt still completes
        finally:
            fw.close()

    def test_oversize_prompt_rejected_with_abort(self):
        fw = _fw(BASE + ",serve:continuous,slots:1,max_seq:64")
        metas = []
        try:
            fw.submit([np.ones((64,), np.int32)], {},
                      lambda t, m: metas.append(m))
            assert fw.drain(timeout=60)
        finally:
            fw.close()
        assert metas and metas[0].get("stream_aborted") is True
        assert metas[0].get("stream_last") is True


class TestFixedDecodeSignature:
    def test_zero_recompiles_across_join_leave_complete(self):
        """The compile-counter pin: once the loop is warm, admitting
        streams of NEW lengths, draining them, and re-admitting must not
        compile anything — block tables/positions/occupancy are VALUES,
        not shapes, in every program the loop runs."""
        fw = _fw(BASE + ",serve:continuous,slots:3,block_size:8,"
                 "prefill_chunk:4")
        rng = np.random.default_rng(5)
        try:
            _serve_tokens(fw, [rng.integers(1, 500, (3,), np.int32)])
            serve = fw._serve
            warm = {
                "decode": serve._decode._cache_size(),
                "prefill": serve._prefill._cache_size(),
                "set_tok": serve._set_tok._cache_size(),
            }
            assert warm == {"decode": 1, "prefill": 1, "set_tok": 1}
            # churn: new lengths, concurrent joins, full drain, rejoin
            _serve_tokens(fw, [rng.integers(1, 500, (t,), np.int32)
                               for t in (1, 7, 13)])
            _serve_tokens(fw, [rng.integers(1, 500, (9,), np.int32)])
            after = {
                "decode": serve._decode._cache_size(),
                "prefill": serve._prefill._cache_size(),
                "set_tok": serve._set_tok._cache_size(),
            }
        finally:
            fw.close()
        assert after == warm, f"recompile on churn: {warm} -> {after}"


class TestPagedForward:
    """models/llama.py forward_paged against the dense forward_cached."""

    def test_matches_dense_cache_logits(self):
        import jax.numpy as jnp

        cfg = llama.PRESETS["llama_tiny"]
        params = llama.init_params(cfg, seed=0)
        rng = np.random.default_rng(6)
        T = 5
        prompt = rng.integers(1, cfg.vocab, (1, T), np.int32)

        dense = llama.init_cache(cfg, 1, dtype="float32")
        ref, dense = llama.forward_cached(params, prompt, dense, 0, cfg,
                                          compute_dtype="float32")
        nxt = np.array([[7]], np.int32)
        ref2, _ = llama.forward_cached(params, nxt, dense, T, cfg,
                                       compute_dtype="float32")

        bs, max_blocks = 4, 8
        pool = llama.init_paged_cache(cfg, 16, bs, dtype="float32")
        tables = np.full((1, max_blocks), 16, np.int32)
        tables[0, :3] = [11, 2, 7]  # 3 blocks cover T+1 <= 12 rows
        lg, pool = llama.forward_paged(
            params, jnp.asarray(prompt), pool, jnp.asarray(tables),
            jnp.zeros((1,), jnp.int32), cfg, compute_dtype="float32")
        np.testing.assert_allclose(np.asarray(lg[:, -1]),
                                   np.asarray(ref[:, -1]),
                                   rtol=2e-4, atol=2e-4)
        lg2, _ = llama.forward_paged(
            params, jnp.asarray(nxt), pool, jnp.asarray(tables),
            jnp.full((1,), T, jnp.int32), cfg, compute_dtype="float32")
        np.testing.assert_allclose(np.asarray(lg2[:, 0]),
                                   np.asarray(ref2[:, 0]),
                                   rtol=2e-4, atol=2e-4)

    def test_parked_row_never_writes_pool(self):
        import jax.numpy as jnp

        cfg = llama.PRESETS["llama_tiny"]
        params = llama.init_params(cfg, seed=0)
        bs, max_blocks = 4, 8
        pool = llama.init_paged_cache(cfg, 6, bs, dtype="float32")
        before = np.asarray(pool["k"]).copy()
        tables = np.full((2, max_blocks), 6, np.int32)
        tables[0, 0] = 3  # row 0 live in block 3; row 1 parked
        toks = np.array([[5], [5]], np.int32)
        pos = jnp.asarray(np.array([0, max_blocks * bs], np.int32))
        _, pool = llama.forward_paged(
            params, jnp.asarray(toks), pool, jnp.asarray(tables), pos,
            cfg, compute_dtype="float32")
        after = np.asarray(pool["k"])
        assert not np.array_equal(after[:, 3], before[:, 3])  # live wrote
        mask = np.ones(6, bool)
        mask[3] = False  # every OTHER block untouched
        np.testing.assert_array_equal(after[:, mask], before[:, mask])


def _forward_paged_by_layer(params, tokens, pool, tables, pos, cfg,
                            logit_off=None):
    """The paged forward as it was before the pool became the layer
    loop's carry, kept here as the plain reference: the pool's layers
    are the scan's INPUTS and the written layers its OUTPUTS, each layer
    sees only its own ``[n_blocks, bs, Hkv, hd]`` blocks, and a dropped
    write aims at ``n_blocks``, one past the layer's own end."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from nnstreamer_tpu.ops.attention import paged_attention

    dt = jnp.float32
    B, T = tokens.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = jnp.asarray(params["embed"]).astype(dt)[tokens]
    positions = pos[:, None] + jnp.arange(T)[None, :]
    max_blocks = tables.shape[1]

    def body(x, layer):
        lp, k_pool, v_pool = layer
        n_blocks, bs = k_pool.shape[:2]
        h = llama._rmsnorm(x, lp["ln_attn"], cfg.norm_eps)
        q = llama._mm(h, lp, "wq", dt).reshape(B, T, H, hd)
        k = llama._mm(h, lp, "wk", dt).reshape(B, T, Hkv, hd)
        v = llama._mm(h, lp, "wv", dt).reshape(B, T, Hkv, hd)
        q = llama._rope(q, positions, cfg.rope_theta)
        k = llama._rope(k, positions, cfg.rope_theta)
        idx = pos[:, None] + jnp.arange(T)[None, :]
        valid = (idx >= 0) & (idx < max_blocks * bs)
        slot_blk = jnp.clip(idx // bs, 0, max_blocks - 1)
        blk = jnp.where(
            valid, jnp.take_along_axis(tables, slot_blk, axis=1), n_blocks)
        k_pool = k_pool.at[blk, idx % bs].set(k, mode="drop")
        v_pool = v_pool.at[blk, idx % bs].set(v, mode="drop")
        lens = jnp.where(pos + T <= max_blocks * bs, pos + T,
                         0).astype(jnp.int32)
        attn = paged_attention(q, k_pool, v_pool, tables, lens)
        x = x + llama._mm(attn.reshape(B, T, H * hd), lp, "wo", dt)
        h = llama._rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
        gate = jax.nn.silu(llama._mm(h, lp, "w_gate", dt))
        x = x + llama._mm(gate * llama._mm(h, lp, "w_up", dt), lp,
                          "w_down", dt)
        return x, (k_pool, v_pool)

    x, (k_new, v_new) = lax.scan(
        body, x, (params["layers"], pool["k"], pool["v"]))
    x = llama._rmsnorm(x, params["ln_out"], cfg.norm_eps)
    if logit_off is not None:
        x = lax.dynamic_slice_in_dim(x, logit_off, 1, axis=1)
    return llama._lm_head(params, x, dt), {"k": k_new, "v": v_new}


class TestPoolCarriedThroughLayers:
    """``forward_paged`` carries the whole pool through its layer scan
    and addresses layer l's block j as flat block ``l * n_blocks + j``.
    It must stay the SAME function as the per-layer formulation, bit for
    bit, on the logits and on every block of every layer — above all
    where a write is dropped (a parked row, an unallocated table entry):
    the per-layer sentinel ``n_blocks`` is block 0 of the NEXT layer in
    the flat view."""

    BS, MAX_BLOCKS, N_BLOCKS = 4, 8, 12

    def _cfg(self, n_kv_heads):
        import dataclasses

        # three layers: a middle one has a neighbour on both sides
        return dataclasses.replace(llama.PRESETS["llama_tiny"], n_layers=3,
                                   n_kv_heads=n_kv_heads)

    def _pool(self, cfg, rng):
        import jax.numpy as jnp

        shape = (cfg.n_layers, self.N_BLOCKS, self.BS, cfg.n_kv_heads,
                 cfg.head_dim)
        # a pool already full of other streams' rows: a stray write shows
        return {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
                "v": jnp.asarray(rng.standard_normal(shape), jnp.float32)}

    def _same(self, cfg, tokens, pool, tables, pos, wrote, logit_off=None):
        """Logits and the whole returned pool equal the reference's, and
        of the pool's blocks exactly ``wrote`` changed, in EVERY layer."""
        import jax
        import jax.numpy as jnp

        params = llama.init_params(cfg, seed=3)
        args = (params, jnp.asarray(tokens), pool, jnp.asarray(tables),
                jnp.asarray(pos))
        ref_lg, ref_pool = jax.jit(
            lambda *a: _forward_paged_by_layer(*a, cfg, logit_off))(*args)
        lg, got = jax.jit(lambda *a: llama.forward_paged(
            *a, cfg, compute_dtype="float32", logit_off=logit_off))(*args)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref_lg))
        for name in ("k", "v"):
            assert got[name].shape == pool[name].shape
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(ref_pool[name]))
        after, before = np.asarray(got["k"]), np.asarray(pool["k"])
        hit = np.zeros(self.N_BLOCKS, bool)
        hit[wrote] = True
        assert (after[:, hit] != before[:, hit]).any(axis=(2, 3, 4)).all()
        np.testing.assert_array_equal(after[:, ~hit], before[:, ~hit])

    @pytest.mark.parametrize("n_kv_heads", [4, 2], ids=["mha", "gqa"])
    def test_decode_step_matches_per_layer_reference(self, n_kv_heads):
        cfg = self._cfg(n_kv_heads)
        rng = np.random.default_rng(27)
        pool = self._pool(cfg, rng)
        S = self.N_BLOCKS  # the unallocated-entry sentinel
        tables = np.full((4, self.MAX_BLOCKS), S, np.int32)
        tables[0, :1] = [5]          # row 0 at depth 2: block 5
        tables[1, :3] = [0, 9, 3]    # row 1 at depth 9: its third block
        tables[2, :2] = [7, 1]       # row 2 parked: must write nothing
        tables[3, :1] = [11]         # row 3 at depth 6: a SENTINEL entry
        pos = np.array([2, 9, self.MAX_BLOCKS * self.BS, 6], np.int32)
        tokens = rng.integers(1, cfg.vocab, (4, 1), np.int32)
        # only rows 0 and 1 wrote, each in its own block; the parked row
        # and the sentinel entry changed no block at all — not block 0 of
        # the next layer, where n_blocks points in the flat view
        self._same(cfg, tokens, pool, tables, pos, wrote=[5, 3])

    @pytest.mark.parametrize("n_kv_heads", [4, 2], ids=["mha", "gqa"])
    def test_prefill_chunk_matches_per_layer_reference(self, n_kv_heads):
        cfg = self._cfg(n_kv_heads)
        rng = np.random.default_rng(28)
        pool = self._pool(cfg, rng)
        # an 8-token chunk from position 4 crosses blocks 1 and 2 of the
        # row; its last block is NOT allocated (sentinel): those two rows
        # of the chunk drop
        tables = np.full((1, self.MAX_BLOCKS), self.N_BLOCKS, np.int32)
        tables[0, :2] = [10, 6]
        tokens = rng.integers(1, cfg.vocab, (1, 8), np.int32)
        self._same(cfg, tokens, pool, tables, np.array([4], np.int32),
                   wrote=[6], logit_off=5)


def _barriers(jaxpr, times=1):
    """The operand counts of the ``optimization_barrier`` equations of
    ``jaxpr``, each listed as many times as the scans around it run it."""
    import jax

    found = []
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "optimization_barrier":
            found += [len(eqn.invars)] * times
        inner = times * (eqn.params["length"] if eqn.primitive.name == "scan"
                         else 1)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _barriers(sub, inner)
    return found


class TestProjectionsKeepTheirLayout:
    """Every paged call — a decode step as much as a prefill chunk —
    passes each full or window attention layer's q, k and v products
    through ONE optimization barrier, so that the compiled program reads
    their weights where they lie (tests/test_kernels_tpu_compile.py
    holds the compiled decode programs to it); the latent, convolution
    and state-space mixers take none."""

    @pytest.mark.parametrize("T", [1, 4], ids=["decode_step", "chunk"])
    @pytest.mark.parametrize("preset,quant", [
        ("llama_tiny", None), ("llama_tiny", "int8"), ("llama_small", None),
        ("hybrid_moe_tiny", None), ("conv_moe_tiny", None),
        ("ssm_hybrid_tiny", None), ("latent_shortcut_tiny", None)])
    def test_a_barrier_in_every_attention_layer(self, preset, quant, T):
        import jax
        import jax.numpy as jnp

        cfg = llama.PRESETS[preset]
        B, bs = 2, 4
        init = llama.init_params_int8 if quant else llama.init_params
        params = jax.eval_shape(lambda: init(cfg, 0, "float32"))
        ring = llama.window_ring_blocks(cfg, bs, 8)
        pool = jax.eval_shape(lambda: llama.init_paged_cache(
            cfg, 16, bs, "float32", win_blocks=B * ring, slots=B))
        rows = jax.ShapeDtypeStruct((B,), jnp.int32)
        tables = jax.ShapeDtypeStruct((B, 8), jnp.int32)
        if ring:
            tables = {"full": tables,
                      "win": jax.ShapeDtypeStruct((B, ring), jnp.int32)}
        elif cfg.slot_state:
            tables = {"full": tables, "slot": rows}
        jaxpr = jax.jit(lambda *a: llama.forward_paged(
            *a, cfg, "float32")).trace(
            params, jax.ShapeDtypeStruct((B, T), jnp.int32), pool, tables,
            rows).jaxpr
        attention = sum(not (k.conv or k.ssm or k.latent)
                        for k in cfg.kinds)
        assert _barriers(jaxpr) == [3] * attention


class TestPagedAttentionKernel:
    def _case(self, rng, B=4, H=4, hkv=2, D=16, bs=8, n_blocks=16,
              max_blocks=4, lens=(1, 5, 8, 29)):
        import jax.numpy as jnp

        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k_pool = jnp.asarray(
            rng.standard_normal((n_blocks, bs, hkv, D)), jnp.float32)
        v_pool = jnp.asarray(
            rng.standard_normal((n_blocks, bs, hkv, D)), jnp.float32)
        tables = np.full((B, max_blocks), n_blocks, np.int32)
        blocks = rng.permutation(n_blocks)
        i = 0
        for b, ln in enumerate(lens):
            need = -(-ln // bs)
            tables[b, :need] = blocks[i:i + need]
            i += need
        lens = jnp.asarray(np.asarray(lens, np.int32))
        return q, k_pool, v_pool, jnp.asarray(tables), lens

    def test_interpret_kernel_matches_reference(self):
        from nnstreamer_tpu.ops.attention import (
            paged_attention, paged_attention_reference)

        rng = np.random.default_rng(7)
        q, kp, vp, tbl, lens = self._case(rng)
        got = np.asarray(paged_attention(q, kp, vp, tbl, lens,
                                         interpret=True))
        ref = np.asarray(paged_attention_reference(q, kp, vp, tbl, lens))
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_idle_row_zero_output_no_dma(self):
        # context len 0 = idle slot: the kernel's fori_loop runs zero
        # iterations (no block DMA) and the row emits finite zeros.
        from nnstreamer_tpu.ops.attention import paged_attention

        rng = np.random.default_rng(8)
        q, kp, vp, tbl, _ = self._case(rng)
        import jax.numpy as jnp

        lens = jnp.asarray(np.array([0, 5, 0, 29], np.int32))
        got = np.asarray(paged_attention(q, kp, vp, tbl, lens,
                                         interpret=True))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
        np.testing.assert_array_equal(got[2], np.zeros_like(got[2]))


class TestServingTelemetry:
    def test_prefill_pad_waste_counter(self):
        # 5 real tokens with prefill_chunk:8 -> one 8-row chunk, waste 3
        # (the satellite replacing power-of-two bucketing's up-to-2x).
        before = metrics.snapshot()
        fw = _fw(BASE + ",serve:continuous,slots:1,block_size:4,"
                 "prefill_chunk:8")
        try:
            _serve_tokens(fw, [np.array([3, 1, 4, 1, 5], np.int32)])
        finally:
            fw.close()
        after = metrics.snapshot()

        def delta(name):
            return after.get(name, 0) - before.get(name, 0)

        assert delta("llm.serve.prefill_tokens") == 8
        assert delta("llm.serve.prefill_pad_waste") == 3

    def test_serve_spans_recorded_through_pipeline(self):
        # trace_mode=ring + the element->framework recorder handoff:
        # admit/prefill-chunk/decode spans land in the flight recorder.
        import nnstreamer_tpu as nt
        from nnstreamer_tpu.utils import tracing

        p = nt.Pipeline(
            "appsrc name=src ! tensor_filter framework=llm "
            "model=llama_tiny custom=max_new:4,serve:continuous,slots:2,"
            "temperature:0.0,block_size:8 invoke-dynamic=true ! "
            "tensor_sink name=out", trace_mode="ring")
        with p:
            p.push("src", np.array([1, 5, 9, 2], np.int32))
            bufs = [p.pull("out", timeout=120) for _ in range(4)]
            p.eos("src")
            p.wait(timeout=120)
        assert sum(1 for b in bufs if b.meta.get("stream_last")) == 1
        kinds = {e.kind for e in tracing.recorder.events()
                 if e.stage == "llm.serve"}
        assert {"serve.admit", "serve.prefill_chunk",
                "serve.decode"} <= kinds
        # the taxonomy documents what it records
        for k in ("serve.admit", "serve.prefill_chunk", "serve.decode"):
            assert k in tracing.SPAN_KINDS


class TestDeepLintPricing:
    DESC = ("appsrc name=src ! tensor_filter framework=llm "
            "model=llama_tiny custom=max_new:4,serve:continuous,slots:2,"
            "block_size:8,prefill_chunk:8 invoke-dynamic=true ! "
            "tensor_sink name=out")

    def test_pool_and_programs_priced(self):
        import nnstreamer_tpu as nt
        from nnstreamer_tpu.filters.llm import serving_plan

        report = nt.analyze(self.DESC, deep=True)
        stage = next(s for s in report.resources.stages if s.pool_bytes)
        cfg = llama.PRESETS["llama_tiny"]
        plan = serving_plan(cfg, slots=2, block_size=8, prefill_chunk=8)
        assert stage.pool_bytes == plan["pool_bytes"]
        assert stage.pool_bytes == llama.paged_cache_bytes(
            cfg, plan["n_blocks"], 8)
        assert stage.variants == plan["programs"] == 3
        assert stage.param_bytes == llama.param_bytes_estimate(cfg)
        # the pool is in the high-water total and the census
        assert report.resources.hbm_estimate >= stage.pool_bytes
        assert report.resources.compiled_variants >= 3
        assert "kv pool" in report.resources.render()
        # no recompile-unbounded: the serving signature is CLOSED
        assert not any(d.code == "recompile-unbounded" for d in report)

    def test_budget_warning_names_the_pool(self):
        import nnstreamer_tpu as nt

        report = nt.analyze(self.DESC, deep=True, hbm_budget_bytes=1024)
        diag = next(d for d in report if d.code == "hbm-budget")
        assert "kv pool" in diag.message

    def test_checkpoint_model_is_unpriced_not_unbounded(self):
        import nnstreamer_tpu as nt

        desc = self.DESC.replace("model=llama_tiny",
                                 "model=/nonexistent/llm.gguf")
        report = nt.analyze(desc, deep=True)
        codes = [d.code for d in report]
        assert "serving-unpriced" in codes
        assert "recompile-unbounded" not in codes


class TestServingPlan:
    def test_worst_case_pool_and_table_span(self):
        from nnstreamer_tpu.filters.llm import serving_plan

        cfg = llama.PRESETS["llama_tiny"]  # max_seq 256
        plan = serving_plan(cfg, slots=4, block_size=16, prefill_chunk=32)
        assert plan["n_blocks"] == 4 * 16  # slots * ceil(256/16)
        # table spans the largest chunk-padded prompt: ceil(255/32)*32=256
        assert plan["max_blocks"] == 16
        assert plan["pool_bytes"] == llama.paged_cache_bytes(cfg, 64, 16)

    def test_kv_blocks_clamped_to_worst_case(self):
        from nnstreamer_tpu.filters.llm import serving_plan

        cfg = llama.PRESETS["llama_tiny"]
        plan = serving_plan(cfg, slots=2, block_size=16, kv_blocks=10_000)
        assert plan["n_blocks"] == 2 * 16
        small = serving_plan(cfg, slots=2, block_size=16, kv_blocks=5)
        assert small["n_blocks"] == 5


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))


# ---------------------------------------------------------------------------
# PR 32: a decode chunk is SETTLED when it materializes and its tokens are
# DELIVERED under the next chunk, unless a stream ended in it and nothing
# is queued (docs/SERVING.md "Settling and delivering")
# ---------------------------------------------------------------------------

CH = 4
LONG = 3 * CH + 5       # index 0 at admission, then four chunks exactly
AHEAD = f"stream_chunk:{CH},temperature:0.0,dtype:float32"
LOOP = ",serve:continuous,slots:2,block_size:8,prefill_chunk:8"


class _Stream:
    """emit() target for one stream: what its client receives, in order.
    ``log`` (shared with the wrapped ``_decode``) takes ``(name, index)``
    per token; ``at`` maps a stream index to a callable run on the serve
    thread right after that token has been taken."""

    def __init__(self, name="s", log=None, at=None):
        import threading

        self.name, self.log, self.at = name, log, at or {}
        self.ids, self.idx, self.metas = [], [], []
        self.done = threading.Event()

    def __call__(self, tensors, meta):
        self.ids.append(int(tensors[0][0]))
        self.idx.append(meta["stream_index"])
        self.metas.append(dict(meta))
        if self.log is not None:
            self.log.append((self.name, meta["stream_index"]))
        if meta.get("stream_last"):
            self.done.set()
        hook = self.at.get(meta["stream_index"])
        if hook is not None:
            hook()

    @property
    def sid(self):
        return self.metas[0]["stream_id"]

    def terminators(self):
        return [m for m in self.metas if m.get("stream_last")]

    def whole(self, want):
        """Exactly ``want``, indices dense from 0, one terminator, last."""
        assert self.ids == want, (self.name, self.ids, want)
        assert self.idx == list(range(len(want))), (self.name, self.idx)
        assert len(self.terminators()) == 1, self.name
        assert self.metas[-1].get("stream_last") is True
        assert not self.metas[-1].get("stream_aborted")


def _ahead():
    from nnstreamer_tpu.core.log import metrics as m

    return m.snapshot().get("llm.serve.deliver_ahead", 0.0)


def _warm_loop(custom, log=None, fail_at=None):
    """A loop that has served one request (so it exists and is warm), with
    ``_decode`` wrapped to log ``("decode", n)`` for its n-th call from
    here on and to raise on call ``fail_at``."""
    fw = _fw(custom)
    _serve_tokens(fw, [np.array([9, 9, 9], np.int32)])
    serve = fw._serve
    inner, calls = serve._decode, [0]

    def decode(*a, **kw):
        calls[0] += 1
        if log is not None:
            log.append(("decode", calls[0]))
        if calls[0] == fail_at:
            raise RuntimeError("forced: the decode dispatch failed")
        return inner(*a, **kw)

    serve._decode = decode
    return fw


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, (t,), dtype=np.int32) for t in lengths]


def _refs(prompts, max_new):
    base = f"max_new:{max_new},{AHEAD}"
    return [_plain_tokens(p, base) for p in prompts]


@pytest.fixture(scope="module")
def abc():
    """Three prompts and their dense-path streams of LONG tokens."""
    prompts = _prompts(32, (5, 11, 7))
    return prompts, _refs(prompts, LONG)


class TestSettleThenDeliver:
    @pytest.mark.parametrize("max_new", [1, CH - 1, CH, CH + 1, LONG])
    def test_streams_indices_and_last_at_every_length(self, max_new, abc):
        """Three prompts through two slots (the third is seated in a slot
        whose old stream's tail may still be owed): every stream is the
        dense path's, its indices are dense and its one terminator is its
        last token — whichever side of the next dispatch it left on."""
        prompts, long_refs = abc
        # greedy: a shorter max_new is a prefix of the longer stream
        want = [r[:max_new] for r in long_refs]
        fw = _fw(f"max_new:{max_new},{AHEAD}{LOOP}")
        got = [_Stream(str(i)) for i in range(3)]
        try:
            for p, g in zip(prompts, got):
                fw.submit([p], {}, g)
            assert fw.drain(timeout=300)
            stats = fw._serve.pool_stats()
        finally:
            fw.close()
        for g, w in zip(got, want):
            g.whole(w)
        assert stats["blocks_free"] == stats["blocks_total"]
        assert stats["live_streams"] == 0

    def test_eos_in_mid_chunk_cuts_the_stream_there(self, monkeypatch, abc):
        from nnstreamer_tpu.filters import llm

        prompts, refs = abc
        ref = refs[0]
        # an index in the middle of a chunk whose token is new there
        j = next(j for j in (6, 7, 10, 11, 2, 3) if ref[j] not in ref[:j])
        monkeypatch.setattr(llm.ByteTokenizer, "eos", ref[j])
        fw = _fw(f"max_new:{LONG},{AHEAD}{LOOP},stop_eos:1")
        a, b = _Stream("a"), _Stream("b")
        try:
            fw.submit([prompts[0]], {}, a)
            fw.submit([prompts[1]], {}, b)
            assert fw.drain(timeout=300)
            stats = fw._serve.pool_stats()
        finally:
            fw.close()
        a.whole(ref[:j + 1])
        # the other stream is cut at ITS first eos, or runs to its end
        want_b = refs[1]
        if ref[j] in want_b:
            want_b = want_b[:want_b.index(ref[j]) + 1]
        b.whole(want_b)
        assert stats["blocks_free"] == stats["blocks_total"]

    def test_prefix_shared_pair(self):
        rng = np.random.default_rng(33)
        pre = rng.integers(1, 500, (16,), dtype=np.int32)
        pa = np.concatenate([pre, rng.integers(1, 500, (3,), np.int32)])
        pb = np.concatenate([pre, rng.integers(1, 500, (5,), np.int32)])
        want = _refs([pa, pb], LONG)
        fw = _fw(f"max_new:{LONG},{AHEAD}{LOOP}")
        h0 = metrics.snapshot().get("llm.serve.prefix_hits", 0.0)
        b = _Stream("b")
        # b joins from the serve thread once a's prompt blocks are indexed
        a = _Stream("a", at={1: lambda: fw.submit([pb], {}, b)})
        try:
            fw.submit([pa], {}, a)
            assert a.done.wait(300) and b.done.wait(300)
        finally:
            fw.close()
        a.whole(want[0])
        b.whole(want[1])
        assert metrics.snapshot().get("llm.serve.prefix_hits", 0.0) > h0

    def test_sampled_stream_is_the_same_alone_and_in_company(self):
        """The draws stay a pure function of (seed, admission number,
        positions): a stream decodes the same tokens whether its chunks
        were delivered at once (alone: nothing to run ahead of at its
        end) or under the next chunk beside another stream."""
        custom = (f"max_new:{LONG},stream_chunk:{CH},temperature:0.9,"
                  f"top_k:40,seed:7,dtype:float32{LOOP}")
        pa, pb = _prompts(34, (6, 9))
        runs = []
        for company in (False, True):
            fw = _fw(custom)
            a, b = _Stream("a"), _Stream("b")
            try:
                fw.submit([pa], {}, a)
                if company:
                    fw.submit([pb], {}, b)
                assert fw.drain(timeout=300)
            finally:
                fw.close()
            runs.append(a.ids)
            assert a.idx == list(range(LONG))
        assert runs[0] == runs[1]


class TestDeliveryOrder:
    """``_decode`` and the streams' emit log one sequence (all of it on
    the serve thread): where each chunk's tokens left relative to the next
    dispatch."""

    def test_a_boundary_that_retires_nothing_dispatches_first(self, abc):
        prompts, refs = abc
        log = []
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}", log)
        a = _Stream("a", log)
        n0 = _ahead()
        try:
            fw.submit([prompts[0]], {}, a)
            assert a.done.wait(300) and fw.drain(timeout=60)
        finally:
            fw.close()
        a.whole(refs[0])
        assert [e for e in log if e[0] == "decode"] == \
            [("decode", n) for n in (1, 2, 3, 4)]
        for c in (1, 2, 3):
            # chunk c retired nothing: dispatch c+1 precedes its first token
            assert log.index(("decode", c + 1)) < \
                log.index(("a", 1 + (c - 1) * CH)), (c, log)
        # the last chunk ended the stream with nothing queued: delivered
        # at once, after its own dispatch and with none to follow
        assert log[-CH:] == [("a", i) for i in range(LONG - CH, LONG)]
        assert _ahead() - n0 == 3

    def test_a_retirement_with_nothing_queued_delivers_first(self, abc):
        prompts, refs = abc
        log = []
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}", log)
        b = _Stream("b", log)
        a = _Stream("a", log,
                    at={2: lambda: fw.submit([prompts[1]], {}, b)})
        try:
            fw.submit([prompts[0]], {}, a)
            assert a.done.wait(300) and b.done.wait(300)
        finally:
            fw.close()
        a.whole(refs[0])
        b.whole(refs[1])
        # a's last chunk came with dispatch 4; b decodes on: dispatch 5
        # follows a's last token, so a's client is answered with the loop
        # idle, as before this change
        assert log.index(("decode", 4)) < log.index(("a", 13))
        assert log.index(("a", LONG - 1)) < \
            log.index(("decode", 5)), log
        # while b's boundaries that retire nothing still run ahead
        assert log.index(("decode", 6)) < log.index(("b", 9))

    def test_the_idle_delivery_stops_once_the_freed_caller_is_back(
            self, abc):
        """The stream that ends leaves first and whole, then the other
        streams' first token of the chunk; its caller has answered by then
        (here: from inside the last token's emit), and with that request
        queued the rest of the chunk waits for the dispatch."""
        prompts, refs = abc
        log = []
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}", log)
        b, c = _Stream("b", log), _Stream("c", log)
        a = _Stream("a", log,
                    at={2: lambda: fw.submit([prompts[1]], {}, b),
                        LONG - 1: lambda: fw.submit([prompts[2]], {}, c)})
        n0 = _ahead()
        try:
            fw.submit([prompts[0]], {}, a)
            assert all(s.done.wait(300) for s in (a, b, c))
            assert fw.drain(timeout=60)
        finally:
            fw.close()
        for s, w in zip((a, b, c), refs):
            s.whole(w)
        # chunk 4: a's tail, its caller's next request, dispatch 5 with
        # that request seated and answered, then the rest of b's share
        # (b's long prompt took two iterations to prefill: it went live
        # with dispatch 4, so its share of chunk 4 is tokens 1 to CH)
        assert log.index(("decode", 4)) < log.index(("b", 0)) < \
            log.index(("a", 13))
        # b's first token of the chunk does not wait for c's admission
        assert log.index(("a", LONG - 1)) < log.index(("b", 1)) < \
            log.index(("decode", 5)) < log.index(("c", 0)) < \
            log.index(("b", 2)), log
        # both halves of chunk 4 are deliveries; the second ran ahead
        assert _ahead() - n0 >= 4

    def test_one_answer_is_not_enough_where_two_streams_ended(self):
        """Two streams end in one chunk and one caller answers: the loop
        goes on delivering with the chip idle (the second caller may be
        an instant away), as it did before this change."""
        pa, pb, pz, pc = _prompts(37, (4, 6, 5, 7))
        want = _refs([pa, pb, pz, pc], LONG)
        log = []
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP},slots:3", log)
        a, b, z, c = (_Stream(n, log) for n in "abzc")
        a.at = {2: lambda: fw.submit([pz], {}, z),
                LONG - 1: lambda: fw.submit([pc], {}, c)}
        try:
            # a and b are admitted together (nothing decoding: the prefill
            # budget is waived), so they end in one chunk
            fw._serve.submit(pa[None], {}, a)
            fw.submit([pb], {}, b)
            assert all(s.done.wait(300) for s in (a, b, z, c))
        finally:
            fw.close()
        for s, w in zip((a, b, z, c), want):
            s.whole(w)
        # a and b leave first, whole; then z's share of the chunk, all of
        # it before the next dispatch
        end_a, end_b = (log.index((n, LONG - 1)) for n in "ab")
        nxt = next(i for i, e in enumerate(log)
                   if e[0] == "decode" and i > max(end_a, end_b))
        zs = [i for i, e in enumerate(log) if e[0] == "z"]
        between = [i for i in zs if max(end_a, end_b) < i < nxt]
        assert len(between) == CH, log
        assert not [i for i in zs if min(end_a, end_b) - CH < i
                    < max(end_a, end_b)], log

    def test_a_retirement_with_a_request_queued_dispatches_first(self, abc):
        """...and the queued request is seated in the slot the settling
        freed while the old stream's tail is still owed."""
        prompts, refs = abc
        log = []
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}", log)
        b, c = _Stream("b", log), _Stream("c", log)
        # c arrives during the delivery of a's chunk 3, which lies in the
        # iteration that settles a's last chunk: both slots are taken
        a = _Stream("a", log,
                    at={2: lambda: fw.submit([prompts[1]], {}, b),
                        12: lambda: fw.submit([prompts[2]], {}, c)})
        try:
            fw.submit([prompts[0]], {}, a)
            assert all(s.done.wait(300) for s in (a, b, c))
            assert fw.drain(timeout=60)
            stats = fw._serve.pool_stats()
        finally:
            fw.close()
        for s, w in zip((a, b, c), refs):
            s.whole(w)
        tail = log.index(("a", 13))
        assert log.index(("decode", 5)) < tail, log
        # c holds a's slot and has its first token before a's tail leaves
        assert log.index(("c", 0)) < tail
        assert stats["blocks_free"] == stats["blocks_total"]


class TestPendingDeliveryIsFlushed:
    """Anything that ends, moves or reads a stream meets a delivery that
    was put off: the tokens leave first."""

    def test_drain_snapshot_equals_what_was_received(self, abc):
        import threading
        import time

        prompts, refs = abc
        custom = f"max_new:{LONG},{AHEAD}{LOOP}"
        fw = _warm_loop(custom)
        serve = fw._serve
        a = _Stream("a")

        class Seen(threading.Event):
            def set(self):                  # on the serve thread
                self.received = len(a.ids)
                super().set()

        cmd = {"kind": "drain", "ev": Seen(),
               "deadline": time.monotonic() + 60}

        def ask():
            # from inside chunk 1's delivery: chunk 2 is settled later in
            # this iteration and retires nothing, so its delivery is
            # pending when the command is read
            cmd["sid"] = a.sid
            serve._ctl.append(cmd)

        a.at[2] = ask
        fw_b = _fw(custom)
        cont = _Stream("cont")
        try:
            fw.submit([prompts[0]], {}, a)
            assert cmd["ev"].wait(120) and not cmd.get("error"), cmd
            snap = cmd["result"]
            assert snap["sidx"] == cmd["ev"].received == 1 + 2 * CH
            assert a.idx == list(range(snap["sidx"]))
            assert not a.terminators()      # moved, not ended
            fw_b.adopt_stream(snap, cont)
            assert cont.done.wait(120)
        finally:
            fw.close()
            fw_b.close()
        assert a.ids + cont.ids == refs[0]
        assert cont.idx == list(range(snap["sidx"], LONG))
        assert len(cont.terminators()) == 1

    @pytest.mark.parametrize("ends_in_pending_chunk", [False, True])
    def test_cancel_sends_the_tokens_then_one_terminator(
            self, abc, ends_in_pending_chunk):
        from nnstreamer_tpu.utils import elastic

        prompts, refs = abc
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP},slots:1")
        a, b = _Stream("a"), _Stream("b")

        def cancel():
            elastic.cancel_stream(a.sid, "gone", force=True)
            if ends_in_pending_chunk:
                # a request queued: the chunk that ends a is put off
                fw.submit([prompts[1]], {}, b)

        # chunk 3's delivery lies in the iteration that settles the last
        # chunk; chunk 1's in one that settles a chunk retiring nothing
        a.at[12 if ends_in_pending_chunk else 2] = cancel
        try:
            fw.submit([prompts[0]], {}, a)
            assert a.done.wait(120)
            assert fw.drain(timeout=120)
            stats = fw._serve.pool_stats()
        finally:
            fw.close()
        if ends_in_pending_chunk:
            a.whole(refs[0])                # its own end came first
            b.whole(refs[1])
        else:
            n = 1 + 2 * CH                  # all of the settled chunk
            assert a.ids[:n] == refs[0][:n]
            assert a.idx == list(range(n + 1))
            assert len(a.terminators()) == 1
            assert a.metas[-1]["stream_aborted"] is True
            assert a.metas[-1]["abort_reason"] == "gone"
        assert stats["blocks_free"] == stats["blocks_total"]

    def test_crash_gives_pending_tokens_then_one_terminator_each(self, abc):
        prompts, refs = abc
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}", fail_at=5)
        b, c = _Stream("b"), _Stream("c")
        a = _Stream("a",
                    at={2: lambda: fw.submit([prompts[1]], {}, b),
                        12: lambda: fw.submit([prompts[2]], {}, c)})
        try:
            fw.submit([prompts[0]], {}, a)
            # dispatch 5 raises with chunk 4 settled and undelivered: it
            # ended a (in no slot any more), b decodes on, c has a's slot
            assert all(s.done.wait(120) for s in (a, b, c))
            assert fw.drain(timeout=60)
            with pytest.raises(Exception, match="serve loop died"):
                fw.submit([prompts[0]], {}, _Stream())
        finally:
            fw.close()
        a.whole(refs[0])                    # its tail, and its own end
        # b's prompt took two iterations to prefill: its first token and
        # chunk 4, the one that was pending
        n = 1 + CH
        assert b.ids[:n] == refs[1][:n] and b.idx[:n] == list(range(n))
        assert len(b.ids) == n + 1
        assert c.ids == [0]
        for s in (b, c):
            assert len(s.terminators()) == 1, s.name
            assert s.metas[-1]["stream_aborted"] is True


class TestCensusUnderRunAhead:
    @pytest.mark.parametrize("extra,programs", [
        ("", ("_decode", "_prefill", "_set_tok")),
        (",draft:llama_tiny,spec_k:2",
         ("_propose", "_verify", "_draft_prefill", "_prefill", "_set_tok")),
    ], ids=["plain3", "speculative5"])
    def test_no_program_is_added_and_none_compiles_again(self, extra,
                                                         programs):
        fw = _fw(f"max_new:{CH + 2},{AHEAD}{LOOP}{extra}")
        try:
            _serve_tokens(fw, _prompts(35, (3,)))
            serve = fw._serve
            warm = {p: getattr(serve, p)._cache_size() for p in programs}
            assert warm == {p: 1 for p in programs}
            n0 = _ahead()
            # churn: boundaries that run ahead, that do not, and a slot
            # seated again with a tail owed
            got = _serve_tokens(fw, _prompts(36, (1, 7, 13, 4, 9)))
            after = {p: getattr(serve, p)._cache_size() for p in programs}
            ran_ahead = _ahead() - n0
        finally:
            fw.close()
        assert all(len(v) == CH + 2 for v in got.values())
        assert after == warm, f"compiled in the loop: {warm} -> {after}"
        # the speculative round keeps its order; the plain loop ran ahead
        assert (ran_ahead > 0) == (extra == "")


# ---------------------------------------------------------------------------
# the first token is sampled and committed inside the prefill program (PR 37)
# ---------------------------------------------------------------------------

def _log_prefills(serve, log):
    """Wrap ``_prefill`` to append, per call, the chunk's first position,
    its slot argument, ``tok`` and the slot keys before and after (read
    before the call donates them) and the small vector it returns."""
    inner = serve._prefill

    def prefill(*a):
        entry = {"pos": int(a[4][0]), "slot": int(a[4][3]),
                 "tok0": np.asarray(a[5]).copy(),
                 "keys0": np.asarray(a[6]).copy()}
        out = inner(*a)
        entry.update(first=np.asarray(out[0]), tok1=np.asarray(out[1]),
                     keys1=np.asarray(out[2]))
        log.append(entry)
        return out

    serve._prefill = prefill


class TestFirstTokenCommittedByThePrefill:
    def test_only_the_final_chunk_commits_and_only_its_own_row(self):
        """A prompt of three chunks beside a live stream, then a prompt
        that hits the first's prefix: each admission commits ONCE, on its
        final chunk; a chunk that is not final leaves ``tok`` and the slot
        keys as they were, and the final one changes its own row alone —
        to the token the stream then emits first."""
        rng = np.random.default_rng(70)
        pre = rng.integers(1, 500, (16,), dtype=np.int32)
        short = rng.integers(1, 500, (3,), dtype=np.int32)
        long_ = np.concatenate([pre, rng.integers(1, 500, (5,), np.int32)])
        hit = np.concatenate([pre, rng.integers(1, 500, (2,), np.int32)])
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}")
        calls = []
        _log_prefills(fw._serve, calls)
        B = 2
        h0 = metrics.snapshot().get("llm.serve.prefix_hits", 0.0)
        c = _Stream("c")
        # c joins from the serve thread once b's prompt blocks are indexed
        a = _Stream("a")
        b = _Stream("b", at={1: lambda: fw.submit([hit], {}, c)})
        try:
            fw.submit([short], {}, a)
            fw.submit([long_], {}, b)
            assert a.done.wait(300) and b.done.wait(300) and c.done.wait(300)
        finally:
            fw.close()
        assert metrics.snapshot().get("llm.serve.prefix_hits", 0.0) > h0
        # short: one chunk; long_: 21 tokens = three chunks of 8; hit: its
        # first 16 tokens are shared, so its only chunk starts at 16
        assert [(c_["pos"], c_["slot"] < B) for c_ in calls] == [
            (0, True), (0, False), (8, False), (16, True), (16, True)]
        committed = [c_ for c_ in calls if c_["slot"] < B]
        for c_ in calls:
            slot, first = c_["slot"], c_["first"]
            rows = [r for r in range(B) if r != slot]   # all, if slot == B
            assert (c_["tok1"][rows] == c_["tok0"][rows]).all()
            assert (c_["keys1"][rows] == c_["keys0"][rows]).all()
            if slot < B:
                assert c_["tok1"][slot] == first[0]
                assert (c_["keys1"][slot]
                        == first[1:3].view(np.uint32)).all()
                assert first[3] == 1        # the logits were finite
        assert [int(c_["first"][0]) for c_ in committed] == [
            a.ids[0], b.ids[0], c.ids[0]]
        # three admissions, three slot keys
        assert len({tuple(c_["first"][1:3]) for c_ in committed}) == 3

    @pytest.mark.parametrize("how", ["max_new_1", "first_token_eos"])
    def test_a_stream_that_ends_at_its_first_token_retires_as_before(
            self, how, monkeypatch, abc):
        """``n == 1`` and an EOS on token 0: one token with
        ``stream_last``, slot and blocks free at once — what the program
        wrote into ``tok[slot]`` is never read, and the stream seated in
        the slot next is the dense path's."""
        from nnstreamer_tpu.filters import llm

        prompts, refs = abc
        if how == "max_new_1":
            custom = f"max_new:1,{AHEAD}{LOOP}"
            want = [[r[0]] for r in refs[:2]]
        else:
            monkeypatch.setattr(llm.ByteTokenizer, "eos", refs[0][0])
            custom = f"max_new:{LONG},{AHEAD}{LOOP},stop_eos:1"
            want = [refs[0][:1], refs[1]]
            if refs[0][0] in want[1]:
                want[1] = want[1][:want[1].index(refs[0][0]) + 1]
        fw = _fw(custom.replace("slots:2", "slots:1"))
        a, b = _Stream("a"), _Stream("b")
        try:
            fw.submit([prompts[0]], {}, a)
            assert a.done.wait(300)
            fw.submit([prompts[1]], {}, b)   # seated in the slot a left
            assert fw.drain(timeout=300)
            stats = fw._serve.pool_stats()
        finally:
            fw.close()
        a.whole(want[0])
        b.whole(want[1])
        assert stats["blocks_free"] == stats["blocks_total"]
        assert stats["live_streams"] == 0

    def test_nothing_but_the_prefill_runs_before_the_decode_dispatch(
            self, monkeypatch, abc):
        """From a final prefill chunk's dispatch to the decode chunk's the
        serve thread issues ONE program (that prefill) and fetches nothing
        from the device: no key folded, no sampler, no slot-vector setter,
        no eager primitive, no ``np.asarray`` of a device array."""
        import threading

        import jax

        from nnstreamer_tpu.filters import llm

        prompts, refs = abc
        fw = _warm_loop(f"max_new:{LONG},{AHEAD}{LOOP}")
        serve = fw._serve
        B = 2
        seen, window = [], [False]

        def on_serve_thread_in_window():
            return window[0] and \
                threading.current_thread().name == "llm-serve"

        def watched(name, fn):
            def call(*a, **kw):
                if on_serve_thread_in_window():
                    seen.append(name)
                return fn(*a, **kw)
            return call

        prefill, decode = serve._prefill, serve._decode

        def prefill_w(*a):
            out = prefill(*a)
            if int(a[4][3]) < B:    # the chunk that commits
                window[0] = True
                seen.append("prefill_step")
            return out

        def decode_w(*a, **kw):
            if window[0]:
                seen.append("decode_chunk")
            window[0] = False
            return decode(*a, **kw)

        serve._prefill, serve._decode = prefill_w, decode_w
        serve._set_tok = watched("_set_tok", serve._set_tok)
        monkeypatch.setattr(jax.random, "fold_in",
                            watched("fold_in", jax.random.fold_in))
        monkeypatch.setattr(llm.llama, "sample_token",
                            watched("sample_token", llm.llama.sample_token))
        # every primitive applied eagerly goes through here (a jitted
        # function called again does not: the loop's own are wrapped above)
        from jax._src import core

        monkeypatch.setattr(core.EvalTrace, "process_primitive", watched(
            "eager operation", core.EvalTrace.process_primitive))
        real_np = llm.np

        class NpProxy:
            def __getattr__(self, name):
                val = getattr(real_np, name)
                if name != "asarray":
                    return val

                def asarray(x, *a, **kw):
                    if isinstance(x, jax.Array) and \
                            on_serve_thread_in_window():
                        seen.append("device fetch")
                    return val(x, *a, **kw)
                return asarray

        monkeypatch.setattr(llm, "np", NpProxy())
        got = [_Stream(str(i)) for i in range(3)]
        try:
            for p, g in zip(prompts, got):
                fw.submit([p], {}, g)
            assert fw.drain(timeout=300)
        finally:
            fw.close()
        for g, w in zip(got, refs):
            g.whole(w)
        # two admitted in the first iteration (two final chunks, then the
        # one decode dispatch), the third into a freed slot later
        assert seen == ["prefill_step", "prefill_step", "decode_chunk",
                        "prefill_step", "decode_chunk"], seen

    def test_the_counter_counts_admissions(self, abc):
        prompts, _refs_ = abc
        n0 = metrics.snapshot().get("llm.serve.first_token_in_prefill", 0.0)
        fw = _fw(f"max_new:{CH},{AHEAD}{LOOP}")
        try:
            _serve_tokens(fw, list(prompts))
        finally:
            fw.close()
        assert metrics.snapshot()["llm.serve.first_token_in_prefill"] \
            - n0 == len(prompts)
