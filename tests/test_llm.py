"""LLM path tests (benchmark config #5): KV-cache decode, TP sharding,
ring-attention sequence parallelism, token streaming through a pipeline."""

import numpy as np
import pytest

import nnstreamer_tpu as nt
from nnstreamer_tpu.models import llama


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.PRESETS["llama_tiny"]
    params = llama.init_params(cfg, seed=0)
    return cfg, params


def test_forward_shapes(tiny):
    cfg, params = tiny
    toks = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab
    logits = llama.forward(params, toks, cfg, compute_dtype="float32")
    assert logits.shape == (2, 6, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


def test_cached_decode_matches_full_forward(tiny):
    """Prefill+cached decode must equal the uncached full forward — the
    KV-cache correctness invariant."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    T = 10
    toks = rng.integers(0, cfg.vocab, (1, T), np.int32)

    full = np.asarray(llama.forward(params, toks, cfg, compute_dtype="float32"))

    cache = llama.init_cache(cfg, 1, dtype="float32")
    pre, cache = llama.forward_cached(params, toks[:, :4], cache, 0, cfg,
                                      compute_dtype="float32")
    np.testing.assert_allclose(np.asarray(pre), full[:, :4], rtol=2e-4, atol=2e-4)
    for i in range(4, T):
        step, cache = llama.forward_cached(params, toks[:, i : i + 1], cache,
                                           i, cfg, compute_dtype="float32")
        np.testing.assert_allclose(
            np.asarray(step[:, 0]), full[:, i], rtol=2e-4, atol=2e-4
        )


def test_generate_scan_deterministic(tiny):
    cfg, params = tiny
    prompt = np.array([[1, 5, 9, 13]], np.int32)
    a = np.asarray(llama.generate_scan(params, prompt, cfg, max_new=8,
                                       temperature=0.0, compute_dtype="float32"))
    b = np.asarray(llama.generate_scan(params, prompt, cfg, max_new=8,
                                       temperature=0.0, compute_dtype="float32"))
    assert a.shape == (1, 8)
    np.testing.assert_array_equal(a, b)
    assert (a >= 0).all() and (a < cfg.vocab).all()


def test_seq_parallel_matches_dense(tiny):
    """Ring-attention SP forward == single-device forward (SURVEY §5.7:
    long-context is first-class here, absent in the reference)."""
    import jax

    from nnstreamer_tpu.parallel import make_mesh

    cfg, params = tiny
    mesh = make_mesh(seq=4, data=1, devices=jax.devices()[:4])
    toks = np.arange(16, dtype=np.int32)[None, :] % cfg.vocab
    dense = np.asarray(llama.forward(params, toks, cfg, compute_dtype="float32"))
    sp = np.asarray(llama.forward_seq_parallel(mesh, params, toks, cfg,
                                               compute_dtype="float32"))
    np.testing.assert_allclose(sp, dense, rtol=2e-3, atol=2e-3)


def test_tp_sharded_generation_matches_single():
    """TP over the model axis must not change greedy outputs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nnstreamer_tpu.parallel import make_mesh
    from nnstreamer_tpu.parallel.sharding import shard_params

    cfg = llama.PRESETS["llama_tiny"]
    params = llama.init_params(cfg, seed=0)
    prompt = np.array([[1, 7, 3]], np.int32)
    ref = np.asarray(llama.generate_scan(params, prompt, cfg, max_new=6,
                                         temperature=0.0, compute_dtype="float32"))

    mesh = make_mesh(model=2, data=1, devices=jax.devices()[:2])
    sharded = shard_params(mesh, params, llama.param_pspecs())
    out = np.asarray(llama.generate_scan(sharded, prompt, cfg, max_new=6,
                                         temperature=0.0, compute_dtype="float32"))
    np.testing.assert_array_equal(out, ref)


def test_llm_pipeline_token_streaming():
    """Full pipeline: prompt pushed as text, tokens stream out one buffer
    each (the reference llamacpp contract)."""
    p = nt.Pipeline(
        "appsrc name=src ! "
        "tensor_filter framework=llm model=llama_tiny "
        "custom=max_new:5,dtype:float32 invoke-dynamic=true ! "
        "tensor_sink name=out"
    )
    with p:
        p.push("src", "hi")
        outs = [p.pull("out", timeout=120) for _ in range(5)]
        p.eos("src")
        p.wait(timeout=60)
    for i, buf in enumerate(outs):
        assert buf.meta["stream_index"] == i
        ids = buf.tensors[0]
        assert ids.dtype == np.int32 and ids.shape == (1,)
        assert 0 <= int(ids[0]) < llama.PRESETS["llama_tiny"].vocab


def test_llm_invoke_nonstream():
    from nnstreamer_tpu.filters.llm import LLMFramework

    fw = LLMFramework()
    fw.open({"model": "llama_tiny", "custom": "max_new:4,dtype:float32"})
    prompt = np.frombuffer(b"ab", np.uint8)
    ids, text = fw.invoke([prompt])
    assert ids.shape == (1, 4)
    # determinism across invokes
    ids2, _ = fw.invoke([prompt])
    np.testing.assert_array_equal(ids, ids2)
    fw.close()


def test_llama_7b_shaped_tp_forward_matches_replicated():
    """Config #5 shape check: the REAL 7B per-layer shapes (dim 4096, 32
    heads, head_dim 128, ffn 11008) forwarded under TP=4 GSPMD sharding
    must match the replicated forward.  Layers truncated to 2 and vocab
    shrunk to keep the CPU-mesh test tractable (VERDICT r1 item #4: shapes
    real, depth truncated is acceptable for tests; the bench runs full
    depth on the chip)."""
    import dataclasses

    import jax

    from nnstreamer_tpu.parallel import make_mesh
    from nnstreamer_tpu.parallel.sharding import shard_params

    cfg = dataclasses.replace(
        llama.PRESETS["llama2_7b"], n_layers=2, vocab=1024, max_seq=64)
    assert cfg.head_dim == 128  # the real 7B head geometry
    params = llama.init_params(cfg, seed=0)
    toks = (np.arange(8, dtype=np.int32)[None, :] * 37) % cfg.vocab

    ref = np.asarray(llama.forward(params, toks, cfg, compute_dtype="float32"))

    mesh = make_mesh(model=4, data=1, devices=jax.devices()[:4])
    sharded = shard_params(mesh, params, llama.param_pspecs())
    out = jax.jit(
        lambda p, t: llama.forward(p, t, cfg, compute_dtype="float32")
    )(sharded, toks)
    out = np.asarray(out)
    assert out.shape == (1, 8, cfg.vocab)
    # GSPMD all-reduce ordering differs from the replicated reduction:
    # loose-but-meaningful tolerance on f32 logits.
    np.testing.assert_allclose(ref, out, rtol=2e-3, atol=2e-3)


def test_init_params_bf16_storage():
    """7B HBM-fit path: weights generated directly in bfloat16."""
    import jax.numpy as jnp

    cfg = llama.PRESETS["llama_tiny"]
    params = llama.init_params(cfg, seed=0, dtype="bfloat16")
    assert params["embed"].dtype == jnp.bfloat16
    assert params["layers"]["wq"].dtype == jnp.bfloat16
    toks = np.array([[1, 2, 3]], np.int32)
    logits = llama.forward(params, toks, cfg, compute_dtype="bfloat16")
    assert np.isfinite(np.asarray(logits)).all()


class TestInt8WeightOnly:
    """Weight-only int8 (custom=quant:int8): halves HBM bytes/token on
    the bandwidth-bound decode step; numerics must stay close."""

    def _cfg(self):
        from nnstreamer_tpu.models import llama

        return llama.PRESETS["llama_tiny"]

    def test_logits_close_and_storage_halved(self):
        from nnstreamer_tpu.models import llama

        cfg = self._cfg()
        params = llama.init_params(cfg, seed=0)
        qparams = llama.quantize_int8(params)
        for k in llama._QUANT_MATS:
            assert qparams["layers"][k + "_q"].dtype == np.int8
        assert qparams["lm_head_q"].dtype == np.int8
        toks = np.array([[1, 7, 3, 9, 2]], np.int32)
        a = np.asarray(llama.forward(params, toks, cfg,
                                     compute_dtype="float32"))
        b = np.asarray(llama.forward(qparams, toks, cfg,
                                     compute_dtype="float32"))
        # per-channel int8 keeps relative error small; cosine per position
        cos = (a * b).sum(-1) / (
            np.linalg.norm(a, axis=-1) * np.linalg.norm(b, axis=-1))
        assert cos.min() > 0.999, cos.min()

    def test_init_params_int8_matches_quantize_after_init(self):
        # The memory-bounded per-mat path must be bit-identical to
        # quantize_int8(init_params(...)) — same RNG stream, same math
        # (this is what lets 7B int8 build without the full-precision
        # tree ever being resident).
        import jax

        from nnstreamer_tpu.models import llama

        cfg = self._cfg()
        ref = llama.quantize_int8(
            llama.init_params(cfg, seed=3, dtype="bfloat16"))
        fused = llama.init_params_int8(cfg, seed=3, gen_dtype="bfloat16")
        flat_r, tdef_r = jax.tree.flatten(ref)
        flat_f, tdef_f = jax.tree.flatten(fused)
        assert tdef_r == tdef_f
        for r, f in zip(flat_r, flat_f):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(f))

    def test_generate_scan_runs_quantized(self):
        import jax

        from nnstreamer_tpu.models import llama

        cfg = self._cfg()
        qparams = llama.quantize_int8(llama.init_params(cfg, seed=1))
        toks = llama.generate_scan(qparams, np.array([[1, 5, 9]], np.int32),
                                   cfg, max_new=4, temperature=0.0,
                                   compute_dtype="float32")
        toks = np.asarray(toks)
        assert toks.shape == (1, 4)
        assert ((toks >= 0) & (toks < cfg.vocab)).all()

    def test_tp_pspecs_match_quant_tree(self):
        import jax

        from nnstreamer_tpu.models import llama
        from nnstreamer_tpu.parallel import make_mesh, shard_params

        cfg = self._cfg()
        qparams = llama.quantize_int8(llama.init_params(cfg, seed=2))
        mesh = make_mesh(model=2, data=1, devices=jax.devices()[:2])
        sharded = shard_params(mesh, qparams, llama.param_pspecs(quant=True))
        toks = np.array([[1, 2, 3]], np.int32)
        logits = np.asarray(llama.forward(sharded, toks, cfg,
                                          compute_dtype="float32"))
        ref = np.asarray(llama.forward(qparams, toks, cfg,
                                       compute_dtype="float32"))
        np.testing.assert_allclose(logits, ref, rtol=1e-4, atol=1e-5)

    def test_llm_filter_quant_option(self):
        p = nt.Pipeline(
            "appsrc name=src caps=other/tensors,dimensions=1:1,"
            "types=int32,format=flexible ! "
            "tensor_filter framework=llm model=llama_tiny "
            "custom=max_new:4,quant:int8,dtype:float32 ! "
            "tensor_sink name=out")
        with p:
            p.push("src", np.array([[1, 5]], np.int32))
            toks = [int(np.asarray(p.pull("out", timeout=120)
                                   .tensors[0]).ravel()[0])
                    for _ in range(4)]
            p.eos()
            p.wait(timeout=30)
        assert len(toks) == 4

    def test_llm_filter_quant_with_tp(self):
        # quant + tp must SHARD the quantized tree (bundle pspecs), not
        # silently replicate (review r3 finding)
        p = nt.Pipeline(
            "appsrc name=src caps=other/tensors,dimensions=1:1,"
            "types=int32,format=flexible ! "
            "tensor_filter framework=llm model=llama_tiny "
            "custom=max_new:3,quant:int8,tp:2,dtype:float32 name=f ! "
            "tensor_sink name=out")
        with p:
            fw = p.element("f").fw
            q = fw.bundle.params["layers"]["wq_q"]
            # sharded over the model axis: each device holds out/2
            shard_shapes = {tuple(s.data.shape) for s in q.addressable_shards}
            full = tuple(q.shape)
            assert shard_shapes == {(full[0], full[1], full[2] // 2)}, (
                shard_shapes, full)
            p.push("src", np.array([[1, 5]], np.int32))
            for _ in range(3):
                p.pull("out", timeout=120)
            p.eos()
            p.wait(timeout=30)


class TestPrefillBucketing:
    """SURVEY §7 "dynamic shapes vs XLA static shapes": prompts right-pad
    to power-of-two buckets so mixed-length serving compiles at most
    log2(max_seq) prefill programs — with numerics IDENTICAL to the
    unbucketed program (causal attention hides pad rows; decode
    overwrites cache row `pos` before anything can attend it)."""

    def _ids(self, prompt):
        from nnstreamer_tpu.filters.llm import LLMFramework

        fw = LLMFramework()
        fw.open({"model": "llama_tiny",
                 "custom": "max_new:6,stream_chunk:2,temperature:0.7"})
        return [out[0].copy() for out in fw.invoke_stream([prompt])]

    def test_bucketed_matches_unbucketed(self):
        import dataclasses

        from nnstreamer_tpu.core import config as config_mod

        prompts = [np.arange(1, 6, dtype=np.int32),        # 5 -> bucket 32
                   np.arange(1, 41, dtype=np.int32)]       # 40 -> bucket 64
        for prompt in prompts:
            cfg = config_mod.get_config()
            try:
                config_mod.set_config(
                    dataclasses.replace(cfg, shape_bucketing=False))
                plain = self._ids(prompt)
                config_mod.set_config(
                    dataclasses.replace(cfg, shape_bucketing=True))
                bucketed = self._ids(prompt)
            finally:
                config_mod.set_config(cfg)
            assert len(plain) == len(bucketed)
            for a, b in zip(plain, bucketed):
                np.testing.assert_array_equal(a, b)

    def test_mixed_lengths_share_prefill_program(self):
        from nnstreamer_tpu.filters.llm import LLMFramework

        fw = LLMFramework()
        fw.open({"model": "llama_tiny", "custom": "max_new:1"})
        for t in (3, 9, 17, 30):  # all bucket to 32
            list(fw.invoke_stream([np.arange(1, t + 1, dtype=np.int32)]))
        # jit cache: one prefill entry despite four prompt lengths
        assert fw._fwd._cache_size() == 1


class TestPerRowPositionDecode:
    """Foundation of continuous batching: a [B] position vector lets
    concurrent streams sit at different depths in one decode program.
    Per-row decode must match each stream decoded independently."""

    def test_mixed_depth_decode_matches_independent(self):
        import jax.numpy as jnp

        cfg = llama.PRESETS["llama_tiny"]
        params = llama.init_params(cfg, seed=0)
        rng = np.random.default_rng(1)
        lens = [4, 9]  # two streams at different depths
        prompts = [rng.integers(1, cfg.vocab, (1, t), np.int32)
                   for t in lens]

        # independent reference: prefill+decode each stream alone
        ref_logits = []
        for p in prompts:
            c = llama.init_cache(cfg, 1, dtype="float32")
            _, c = llama.forward_cached(params, p, c, 0, cfg,
                                        compute_dtype="float32")
            nxt = np.array([[7]], np.int32)
            lg, _ = llama.forward_cached(params, nxt, c, p.shape[1], cfg,
                                         compute_dtype="float32")
            ref_logits.append(np.asarray(lg[:, 0]))

        # batched: place both single-row prefilled caches into a 2-slot
        # cache, then ONE per-row-position decode step (host-side row
        # copy: the runtime's serving path is block-paged now, so dense
        # slot admission exists only as this test's reference rig)
        bk = np.zeros((cfg.n_layers, 2, cfg.max_seq, cfg.n_kv_heads,
                       cfg.head_dim), np.float32)
        bv = bk.copy()
        for slot, p in enumerate(prompts):
            c = llama.init_cache(cfg, 1, dtype="float32")
            _, c = llama.forward_cached(params, p, c, 0, cfg,
                                        compute_dtype="float32")
            bk[:, slot] = np.asarray(c["k"])[:, 0]
            bv[:, slot] = np.asarray(c["v"])[:, 0]
        big = {"k": jnp.asarray(bk), "v": jnp.asarray(bv)}
        toks = np.array([[7], [7]], np.int32)
        pos = jnp.asarray(np.array(lens, np.int32))
        lg, big = llama.forward_cached(params, toks, big, pos, cfg,
                                       compute_dtype="float32")
        lg = np.asarray(lg[:, 0])
        for row, ref in enumerate(ref_logits):
            np.testing.assert_allclose(lg[row], ref[0], rtol=2e-4,
                                       atol=2e-4)

    def test_idle_slot_out_of_range_write_is_dropped(self):
        import jax.numpy as jnp

        cfg = llama.PRESETS["llama_tiny"]
        params = llama.init_params(cfg, seed=0)
        big = llama.init_cache(cfg, 2, dtype="float32")
        before = np.asarray(big["k"]).copy()
        toks = np.array([[3], [3]], np.int32)
        # row 0 live at pos 0; row 1 idle, parked at max_seq (out of range)
        pos = jnp.asarray(np.array([0, cfg.max_seq], np.int32))
        _, big = llama.forward_cached(params, toks, big, pos, cfg,
                                      compute_dtype="float32")
        after = np.asarray(big["k"])
        assert not np.array_equal(after[:, 0], before[:, 0])  # live row wrote
        np.testing.assert_array_equal(after[:, 1], before[:, 1])  # idle didn't


class TestContinuousServing:
    """custom=serve:continuous — a standing decode loop with slot
    admission (continuous batching).  Late requests join a RUNNING
    decode at the next chunk boundary instead of waiting for the current
    group to finish."""

    def _fw(self, custom):
        from nnstreamer_tpu.filters.llm import LLMFramework

        fw = LLMFramework()
        fw.open({"model": "llama_tiny", "custom": custom})
        return fw

    def test_greedy_matches_plain_streaming(self):
        # temperature 0: the continuous loop must emit token-for-token
        # what the plain per-request streaming path emits.
        plain = self._fw("max_new:6,stream_chunk:2,temperature:0.0")
        prompt = np.array([2, 8, 5, 1], np.int32)
        want = [int(ids[0]) for ids, _ in plain.invoke_stream([prompt])]
        plain.close()

        fw = self._fw("max_new:6,stream_chunk:2,temperature:0.0,"
                      "serve:continuous,slots:2")
        got = []
        fw.submit([prompt], {}, lambda t, m: got.append(
            (int(t[0][0]), m["stream_index"], m.get("stream_last", False))))
        assert fw.drain(timeout=120)
        fw.close()
        assert [g[0] for g in got] == want
        assert [g[1] for g in got] == list(range(6))
        assert got[-1][2] is True

    def test_late_request_joins_running_decode(self):
        # Stream A is long; B arrives AFTER A started.  In a static group
        # B would wait for A to finish; continuous admission means B's
        # tokens arrive interleaved with A's remaining tokens.
        import threading
        import time

        fw = self._fw("max_new:24,stream_chunk:2,temperature:0.0,"
                      "serve:continuous,slots:2")
        events = []
        lock = threading.Lock()

        def emit_for(rid):
            def emit(t, m):
                with lock:
                    events.append((rid, m["stream_index"]))
            return emit

        fw.submit([np.array([1, 5, 9, 2], np.int32)], {}, emit_for("A"))
        # wait until A has demonstrably started streaming
        deadline = time.monotonic() + 60
        while not events and time.monotonic() < deadline:
            time.sleep(0.01)
        assert events, "stream A never started"
        fw.submit([np.array([3, 3, 7, 8], np.int32)], {}, emit_for("B"))
        assert fw.drain(timeout=120)
        fw.close()
        a_idx = [i for i, e in enumerate(events) if e[0] == "A"]
        b_idx = [i for i, e in enumerate(events) if e[0] == "B"]
        assert len(a_idx) == 24 and len(b_idx) == 24
        # the continuous property: B started before A finished
        assert b_idx[0] < a_idx[-1]
        # per-stream ordering intact
        for idxs in ([e[1] for e in events if e[0] == "A"],
                     [e[1] for e in events if e[0] == "B"]):
            assert idxs == list(range(24))

    def test_more_requests_than_slots_queue(self):
        fw = self._fw("max_new:4,stream_chunk:2,temperature:0.0,"
                      "serve:continuous,slots:1")
        done = []
        for rid in range(3):
            fw.submit([np.array([1 + rid, 5, 9], np.int32)], {"rid": rid},
                      lambda t, m: done.append(m["rid"])
                      if m.get("stream_last") else None)
        assert fw.drain(timeout=180)
        fw.close()
        assert sorted(done) == [0, 1, 2]

    def test_pipeline_eos_waits_for_streams(self):
        import nnstreamer_tpu as nt

        p = nt.Pipeline(
            "appsrc name=src ! tensor_filter framework=llm model=llama_tiny "
            "custom=max_new:5,serve:continuous,slots:2,temperature:0.0 "
            "invoke-dynamic=true ! tensor_sink name=out")
        with p:
            p.push("src", np.array([1, 5, 9, 2], np.int32))
            p.push("src", np.array([3, 3, 7, 8], np.int32))
            p.eos("src")  # EOS while both streams are mid-flight
            bufs = [p.pull("out", timeout=120) for _ in range(10)]
            p.wait(timeout=120)
        assert sum(1 for b in bufs if b.meta.get("stream_last")) == 2

    def test_continuous_with_tensor_parallel(self):
        # serve:continuous composes with custom=tp:N — the sharded params
        # flow through admission prefill and the per-row decode; greedy
        # ids must match the unsharded continuous loop.
        prompt = np.array([4, 9, 1, 7], np.int32)

        def run(custom):
            fw = self._fw(custom)
            got = []
            fw.submit([prompt], {}, lambda t, m: got.append(int(t[0][0])))
            assert fw.drain(timeout=120)
            fw.close()
            return got

        base = "max_new:5,stream_chunk:2,temperature:0.0,serve:continuous"
        ids = run(base + ",slots:2")
        ids_tp = run(base + ",slots:2,tp:2")
        assert ids_tp == ids

    def test_serve_loop_crash_terminates_streams(self, monkeypatch):
        # A dying loop must terminate every live and queued stream with
        # stream_aborted (clients never hang to their timeouts) and make
        # subsequent submits fail loudly.
        from nnstreamer_tpu.filters import llm as llm_mod
        from nnstreamer_tpu.filters.llm import FrameworkError

        fw = self._fw("max_new:8,stream_chunk:2,temperature:0.0,"
                      "serve:continuous,slots:1")
        calls = {"n": 0}
        real = llm_mod.BlockManager.register

        # once an admission, after its final prefill chunk went out (the
        # first token is sampled inside that program: no sampler call is
        # left on the serve thread to fail)
        def dying(*a, **k):
            calls["n"] += 1
            if calls["n"] > 1:
                raise RuntimeError("injected serve-loop failure")
            return real(*a, **k)

        monkeypatch.setattr(llm_mod.BlockManager, "register", dying)
        got = []
        fw.submit([np.array([1, 5, 9], np.int32)], {},
                  lambda t, m: got.append(dict(m)))
        # a second request queued behind the doomed one must also be
        # terminated, not stranded
        fw.submit([np.array([2, 6, 8], np.int32)], {},
                  lambda t, m: got.append(dict(m)))
        # drain() returns only after the crash handler has emitted every
        # stream_aborted terminator (it sets idle last), so the asserts
        # need no further synchronization.
        assert fw.drain(timeout=60)
        assert any(m.get("stream_aborted") and m.get("stream_last")
                   for m in got), got
        with pytest.raises(FrameworkError, match="serve loop died"):
            fw.submit([np.array([3], np.int32)], {}, lambda t, m: None)
        fw.close()
