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
