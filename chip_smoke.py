#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py        # needs a TPU; no flags, no network, no child

Drives the main path once through the entry points a user calls
(``nt.Pipeline`` strings), in ONE process (a chip belongs to one process
at a time), and checks what comes out by the repo's own means:

* stream leg — BASELINE config #1: device-fed and host-fed MobileNet-v1
  at width 1.0, fused transform+filter+decoder stage, class ids against
  ``mobilenet.apply``;
* serving leg — config #5: ``tensor_filter framework=llm model=llama2_7b
  custom=serve:continuous,...`` at full width, three staggered prompts,
  bit-identical replay, the closed 3-program census, serve spans;
* kernel leg — the three Pallas kernels as the model calls them, lowered
  text checked for ``tpu_custom_call``, outputs against their references.

Exits non-zero on any failure, and before any leg when jax finds no TPU.
The last stdout line of a passing run is one JSON object naming the
device.  tests/test_chip_smoke.py runs the same leg functions at toy size
on the CPU; this script itself always demands the chip.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

import numpy as np

LABEL_CHAIN = ["tensor_transform", "tensor_filter", "tensor_decoder"]


class CompileClock:
    """Sums jax's backend-compile durations (a persistent-cache hit counts
    its retrieval time) and counts cache hits while registered."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()  # stage threads compile concurrently

    def _on_duration(self, event, duration, **_kw):
        if event == self.COMPILE:
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            with self._lock:
                self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _fused_label_stage(p):
    """The stage holding transform+filter+decoder as ONE program."""
    for s in p.stages:
        el = getattr(s.element, "fused", s.element)  # folded device source
        if [c.kind for c in getattr(el, "chain", [])] == LABEL_CHAIN:
            return el
    raise AssertionError(
        "transform+filter+decoder did not fuse: "
        f"{[s.element.name for s in p.stages]}")


def _label_desc(source: str, model: str, size: int, batch: int) -> str:
    return (
        f"{source} ! tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        f"tensor_filter framework=jax model={model} "
        f"custom=size:{size},batch:{batch} name=f ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out "
        "max-buffers=4")


def _host_frames(rng, batch: int, size: int, n: int = 2) -> list:
    """uint8 camera-style batches; per-frame brightness so the frames do
    not all land in one class."""
    return [(rng.integers(0, 256, (batch, size, size, 3))
             * rng.random((batch, 1, 1, 1))).astype(np.uint8)
            for _ in range(n)]


def _check_class_ids(params, frames: list, ids: list):
    """Class ids the pipeline chose vs ``mobilenet.apply`` on the same
    frames and weights.  A differently-fused bf16 program may flip a
    near-tie, so a chosen class must sit within bf16 noise (2% of the
    row's logit range) of the reference's best, and >= 90% must be the
    reference argmax outright.  Returns (agreement, worst gap)."""
    import jax

    from nnstreamer_tpu.models import mobilenet

    ref = jax.jit(lambda x: mobilenet.apply(
        params, (x.astype(np.float32) - 127.5) / 127.5))
    agree, worst = 1.0, 0.0
    for i, (x, got) in enumerate(zip(frames, ids)):
        logits = np.asarray(ref(x))
        _check(np.isfinite(logits).all(), "reference logits not finite")
        _check(got.shape == (len(x),), f"batch {i}: ids {got.shape}")
        gap = logits.max(axis=1) - logits[np.arange(len(x)), got]
        tol = 0.02 * (logits.max(axis=1) - logits.min(axis=1))
        _check((gap <= tol).all(),
               f"batch {i}: class ids disagree with mobilenet.apply "
               f"(worst gap {gap.max():.4f}, tol {tol.min():.4f})")
        agree = min(agree, float((got == logits.argmax(axis=1)).mean()))
        worst = max(worst, float(gap.max()))
    _check(agree >= 0.9, f"argmax agreement {agree:.3f}")
    return round(agree, 4), round(worst, 5)


def _pull_pushed(p, frames: list, n_push: int) -> list:
    """Push ``n_push`` batches from a second thread (admission credits
    release at pop, so pushing past max-inflight on the pulling thread
    deadlocks by design) and pull as many outputs."""
    def pusher():
        for i in range(n_push):
            p.push("src", frames[i % len(frames)])

    t = threading.Thread(target=pusher, daemon=True)
    t.start()
    got = [p.pull("out", timeout=600) for _ in range(n_push)]
    t.join(timeout=60)
    _check(not t.is_alive(), "appsrc pusher did not finish")
    return got


def stream_leg(model: str = "mobilenet_v1", size: int = 224,
               batch: int = 256, batches: int = 8) -> dict:
    """Config #1 twice: device-fed (videotestsrc device=true, zero H2D) and
    host-fed through appsrc (the H2D ingress-donation path), class ids of
    the host-fed run checked against ``mobilenet.apply`` on the frames."""
    import jax

    import nnstreamer_tpu as nt

    # -- device-fed --------------------------------------------------------
    p = nt.Pipeline(_label_desc(
        f"videotestsrc device=true batch={batch} "
        f"num-buffers={batch * batches} width={size} height={size} name=src",
        model, size, batch))
    _fused_label_stage(p)
    with p:
        for i in range(batches):
            b = p.pull("out", timeout=600)
            _check(len(b.meta["label"]) == batch,
                   f"batch {i}: {len(b.meta['label'])} labels, want {batch}")
            idx = np.asarray(b.meta["label_index"])
            _check(idx.shape == (batch,) and (idx >= 0).all()
                   and (idx < 1001).all(), f"batch {i}: bad class ids")
        p.wait(timeout=60)

    # -- host-fed ----------------------------------------------------------
    p = nt.Pipeline(_label_desc(
        f"appsrc name=src caps=other/tensors,dimensions=3:{size}:{size}:"
        f"{batch},types=uint8 max-inflight=4", model, size, batch))
    stage = _fused_label_stage(p)
    _check(stage._ingress_put, "planner did not plan ingress donation")
    frames = _host_frames(np.random.default_rng(0), batch, size)
    n_push = 4
    with p:
        params = p.element("f").fw.bundle.params  # the live weights
        got = _pull_pushed(p, frames, n_push)
        p.eos()
        p.wait(timeout=60)
    on_cpu = jax.default_backend() == "cpu"
    _check(stage._donate_active == (not on_cpu),
           f"ingress donation active={stage._donate_active} on "
           f"{jax.default_backend()}")

    ids = [np.asarray(b.meta["label_index"]) for b in got]
    for b in got:
        _check(np.isfinite(np.asarray(b.meta["score"])).all(),
               "non-finite scores")
    agree, worst = _check_class_ids(
        params, [frames[i % 2] for i in range(n_push)], ids)
    return {"model": model, "size": size, "batch": batch,
            "device_batches": batches, "host_batches": n_push,
            "donation_active": bool(stage._donate_active),
            "argmax_agreement": agree,
            "distinct_classes": len(set(np.concatenate(ids).tolist())),
            "worst_logit_gap": worst}


def sharded_stream_leg(model: str = "mobilenet_v1", size: int = 224,
                       batch: int = 64, n_push: int = 16,
                       replicas: int = 4) -> dict:
    """The stream leg's model under ``Pipeline(data_parallel=N,
    batch_max=8)``: host-fed batches back up, the micro-batch shards over
    a real ``(N, 1)`` mesh, and one output's shards sit on N distinct
    devices (the per-replica counters are read off the output's own
    ``addressable_shards``).  No label decoder here: its deferred host
    mapping vetoes sharding (pipeline/plan.py), so class ids are the
    argmax of the logits the sink receives."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics

    before = metrics.snapshot()
    p = nt.Pipeline(
        f"appsrc name=src caps=other/tensors,dimensions=3:{size}:{size}:"
        f"{batch},types=uint8 ! tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        f"tensor_filter framework=jax model={model} "
        f"custom=size:{size},batch:{batch} name=f ! tensor_sink name=out",
        data_parallel=replicas, batch_max=8)
    frames = _host_frames(np.random.default_rng(1), batch, size)
    with p:
        params = p.element("f").fw.bundle.params
        got = _pull_pushed(p, frames, n_push)
        p.eos()
        p.wait(timeout=60)
        _check(p.mesh_shape == (replicas, 1), f"mesh {p.mesh_shape}")
    after = metrics.snapshot()
    placed = sorted(k for k in after if ".shard_rows.d" in k
                    and after[k] > before.get(k, 0))
    _check(len(placed) == replicas,
           f"output shards on {len(placed)} devices, want {replicas}: "
           f"{placed}")
    ids = [np.asarray(b.tensors[0]).reshape(batch, -1).argmax(axis=1)
           for b in got]
    agree, worst = _check_class_ids(
        params, [frames[i % 2] for i in range(n_push)], ids)
    return {"mesh_shape": list(p.mesh_shape), "shard_counters": placed,
            "argmax_agreement": agree, "worst_logit_gap": worst}


def serving_leg(model: str = "llama2_7b", n_layers: int = 0,
                max_new: int = 24, prompt_lens=(19, 70, 7), slots: int = 4,
                quant: str = "int8", param_dtype: str = "bfloat16",
                first_token_timeout: float = 900.0) -> dict:
    """Config #5 continuous serving: ``quant`` weights, ``slots`` slots,
    ``serve:continuous`` over the paged cache.  ``n_layers`` (0 = the
    preset's full depth) is the ONLY cut allowed."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.utils import tracing

    cfg = llama.PRESETS[model]
    depth = n_layers or cfg.n_layers
    block_size = 16
    longest = max(prompt_lens)
    max_seq = max(256, 1 << (longest + max_new).bit_length())
    need = -(-(longest + max_new) // block_size)
    custom = (f"max_new:{max_new},param_dtype:{param_dtype},"
              f"max_seq:{max_seq},stream_chunk:8,quant:{quant},"
              f"serve:continuous,slots:{slots},block_size:{block_size},"
              f"prefill_chunk:32,kv_blocks:{slots * need},temperature:0.0")
    if n_layers:
        custom += f",n_layers:{n_layers}"
    p = nt.Pipeline(
        "appsrc name=src ! "
        f"tensor_filter framework=llm model={model} custom={custom} "
        "invoke-dynamic=true name=f ! tensor_sink name=out",
        xray=True, trace_mode="ring")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 400, (n,), dtype=np.int32)
               for n in prompt_lens]

    def request(i, prompt):
        b = nt.Buffer([prompt])
        b.meta["smoke_stream"] = i
        return b

    streams: dict = {}

    def take(timeout):
        b = p.pull("out", timeout=timeout)
        _check(not b.meta.get("stream_aborted"),
               f"stream_aborted: {b.meta.get('abort_reason')!r} — the serve "
               "loop died or rejected the request (see the log above)")
        toks = streams.setdefault(b.meta["smoke_stream"], [])
        _check(b.meta["stream_index"] == len(toks),
               f"stream {b.meta['smoke_stream']}: index "
               f"{b.meta['stream_index']} after {len(toks)} tokens")
        tok = int(np.asarray(b.tensors[0]).reshape(-1)[0])
        _check(0 <= tok < cfg.vocab, f"token id {tok} out of range")
        toks.append(tok)
        _check(bool(b.meta.get("stream_last")) == (len(toks) == max_new),
               f"stream {b.meta['smoke_stream']}: stream_last="
               f"{b.meta.get('stream_last')} at token {len(toks)}/{max_new}")
        return b

    with p:
        t0 = time.perf_counter()
        p.push("src", request(0, prompts[0]))
        p.push("src", request(1, prompts[1]))
        # the first pull carries weight generation + the loop's compiles
        while 0 not in streams:
            take(first_token_timeout)
        first_token_s = time.perf_counter() - t0
        p.push("src", request(2, prompts[2]))  # the late joiner
        while sum(len(v) for v in streams.values()) < 3 * max_new:
            take(300)
        replay_id = len(prompts)
        p.push("src", request(replay_id, prompts[0]))
        while len(streams.get(replay_id, [])) < max_new:
            take(300)
        _check(streams[replay_id] == streams[0],
               "replaying prompt 1 did not reproduce its tokens:\n"
               f"  first  {streams[0]}\n  replay {streams[replay_id]}")
        rep = p.explain()
        p.eos()
        p.wait(timeout=120)

    progs = {k: v for k, v in rep["census"]["programs"].items()
             if k.startswith("f.serve/")}
    _check(sorted(progs) == ["f.serve/decode", "f.serve/prefill",
                             "f.serve/set_tok"],
           f"serve census {sorted(progs)}")
    for k, e in progs.items():
        # warm-up compiles each program once; any later compile shows here
        _check(e["live_compiles"] == 1 and e["within"],
               f"{k}: {e['live_compiles']} compiles {e['live_signatures']}")
    _check(rep["census"]["drift_total"] == 0,
           f"census drift {rep['census']['drift_total']}")
    kinds = {e.kind for e in tracing.recorder.events()
             if e.stage == "llm.serve"}
    want = {"serve.admit", "serve.prefill_chunk", "serve.decode"}
    _check(want <= kinds, f"serve spans missing: {sorted(want - kinds)}")
    return {"model": model, "n_layers": depth, "full_depth":
            depth == cfg.n_layers, "dim": cfg.dim, "heads":
            [cfg.n_heads, cfg.n_kv_heads, cfg.head_dim], "quant": quant,
            "slots": slots, "max_new": max_new,
            "prompt_lens": list(prompt_lens),
            "first_token_s": round(first_token_s, 2),
            "programs": len(progs), "replay_identical": True,
            "tokens": sum(len(v) for v in streams.values())}


def kernel_leg(n_heads: int = 32, n_kv_heads=(32, 8), head_dim: int = 128,
               dim: int = 4096, ffn: int = 11008, slots: int = 4,
               block_size: int = 16, seq: int = 128,
               paged_heads=((32, 32), (32, 8), (64, 8)),
               paged_lens=(0, 1, 94, 128, 129, 300, 544, 800),
               window: int = 128, grouped_dims=(6144, 2048),
               grouped=((64, 8, 128, 16, 2), (64, 12, 768, 16, 2),
                        (64, 4, 64, 64, 2, (2048, 1536))),
               paged_narrow=((32, 8, 64),), interpret=None) -> dict:
    """flash_attention, paged_attention, matmul_int4 and grouped_swiglu at
    the serving leg's shapes, called as models/llama.py and models/moe.py
    call them (``interpret`` left at None on the chip; the CPU dry run
    passes True).  The lowered text must hold ``tpu_custom_call`` — the
    kernel engaged, the shape gates did not route to the reference — and
    the outputs match the references.

    The paged kernel runs at both serving cells' head counts (32/8, 64/8)
    and at MHA, without a window and with ``window`` over a slot's ring,
    on rows that are idle (length 0), end mid-block, end mid-wave (the
    kernel streams 1024 // (block_size * kv_heads) blocks a wave), fill a
    wave exactly and pass it by one token.  ``paged_narrow`` (heads, KV
    heads, head width) adds the form for heads narrower than the 128
    lanes, two KV heads to a lane row (ops/attention.py): 32/8 heads of
    64, the convolution cell's.

    The grouped expert kernel runs at the sparse cells' shapes
    (``grouped``: slots, experts a token, router outputs, experts held,
    layers in the stack, two of the cells' 7, 4 and 8 so that the leg fits
    beside whatever the legs before it left on the device, and (D, F)
    where they are not ``grouped_dims``: the third cell holds all 64
    experts of 2048 x 1536) — the layer's sizes written into the
    stack-wide vector at the second layer — with
    the sizes the cell's router gives (every row's choices uniform over
    the router's outputs) and with skewed ones: half the rows on one
    expert (several visits), a quarter on another, seven on the last."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops import attention as A
    from nnstreamer_tpu.ops import grouped_ffn as GF
    from nnstreamer_tpu.ops import int4_matmul as I4

    rng = np.random.default_rng(0)
    errs = {}

    def arr(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           ).astype(dtype)

    def run(name, fn, ref_fn, args, tol):
        jit = jax.jit(fn)
        if not interpret:
            _check("tpu_custom_call" in jit.lower(*args).as_text(),
                   f"{name}: lowered without tpu_custom_call — the "
                   "reference stood in for the kernel")
        got = np.asarray(jit(*args).astype(jnp.float32))
        want = np.asarray(jax.jit(ref_fn)(*args).astype(jnp.float32))
        _check(got.shape == want.shape, f"{name}: shape {got.shape}")
        _check(np.isfinite(got).all(), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want)) / max(
            1e-6, float(np.max(np.abs(want)))))
        _check(err < tol, f"{name}: rel err {err:.4g} >= {tol}")
        errs[name] = round(err, 5)

    kw = {} if interpret is None else {"interpret": interpret}
    for hkv in n_kv_heads:
        tag = f"{n_heads}/{hkv}"
        q = arr((1, seq, n_heads, head_dim))
        k, v = (arr((1, seq, hkv, head_dim)) for _ in range(2))
        run(f"flash_attention {tag}",
            lambda q, k, v: A.flash_attention(q, k, v, causal=True, **kw),
            lambda q, k, v: A.attention_reference(q, k, v, causal=True),
            (q, k, v), 0.05)

    # every row at its own depth; a full table names a row's blocks in
    # order (unused entries hold the sentinel), a ring's entry j % width
    # holds logical block j, as models/llama.py fills them
    lens = np.asarray(paged_lens, np.int32)
    rows = len(lens)
    live = jnp.asarray((lens > 0).reshape(rows, 1, 1, 1))
    for heads, hkv, hd in [(*hh, head_dim) for hh in paged_heads] \
            + list(paged_narrow):
        for ring in (False, True):
            width = (window // block_size + 3 if ring
                     else -(-int(lens.max()) // block_size) + 1)
            n_blocks = rows * width
            # narrow heads are stored several to a lane row, as
            # models/llama.py init_paged_cache stores them
            pack = A.kv_lane_pack(hkv, hd)
            qd = arr((rows, 1, heads, hd))
            kp, vp = (arr((n_blocks, block_size, hkv // pack, hd * pack))
                      for _ in range(2))
            tbl = rng.permutation(n_blocks).astype(np.int32).reshape(
                rows, width)
            if not ring:
                used = -(-lens // block_size)
                tbl[np.arange(width)[None, :] >= used[:, None]] = n_blocks
            opts = {"window": window, "ring": True} if ring else {}
            run(f"paged_attention {heads}/{hkv}"
                + (f" x {hd}" if hd != head_dim else "")
                + (f" window {window} ring {width}" if ring else ""),
                # idle rows emit garbage the serve loop never reads
                lambda q, kp, vp, t, n, opts=opts: jnp.where(
                    live, A.paged_attention(q, kp, vp, t, n, **opts, **kw),
                    0),
                lambda q, kp, vp, t, n, opts=opts: jnp.where(
                    live, A.paged_attention_reference(q, kp, vp, t, n,
                                                      **opts), 0),
                (qd, kp, vp, jnp.asarray(tbl), jnp.asarray(lens)), 0.05)

    h_kv = n_kv_heads[0] * head_dim
    for din, fout in ((dim, dim + 2 * h_kv), (dim, dim), (dim, 2 * ffn),
                      (ffn, dim)):
        packed, scale = jax.jit(I4.quantize_int4)(arr((din, fout)))
        h = arr((slots, din))
        run(f"matmul_int4 {din}x{fout}",
            lambda h, p, s: I4.matmul_int4(h, p, s, **kw),
            I4.matmul_int4_reference, (h, packed, scale), 0.03)

    # the stacks are made on the device: 2.4 GB at the cells' widths
    for slots_g, top_k, n_router, held, layers, *dims in grouped:
        D, F = dims[0] if dims else grouped_dims
        M, G = slots_g * top_k, layers * held
        keys = jax.random.split(jax.random.PRNGKey(M), 4)
        xs = jax.random.normal(keys[0], (M, D), jnp.bfloat16)
        ws = tuple(
            jax.random.normal(k, shape, jnp.bfloat16) * shape[1] ** -0.5
            for k, shape in zip(keys[1:], ((G, D, F), (G, D, F), (G, F, D))))
        chosen = rng.integers(0, n_router, M)
        cell = np.bincount(chosen[chosen < held], minlength=held)
        skew = np.zeros(held, np.int64)
        skew[[0, 2, held - 1]] = M // 2, M // 4, 7
        opts = dict(live=held, expect=M / n_router, **kw)
        for tag, sizes in (("cell", cell), ("skewed", skew)):
            groups = np.zeros(G, np.int32)
            groups[(layers - 1) * held:] = sizes
            own = jnp.arange(M)[:, None] < int(sizes.sum())
            run(f"grouped_swiglu {M}x{G}"
                + (f" {D}x{F}" if dims else "") + f" {tag}",
                # rows past the groups are never read
                lambda x, g, u, d, n, own=own: jnp.where(
                    own, GF.grouped_swiglu(x, g, u, d, n, **opts)[0], 0),
                lambda x, g, u, d, n, own=own: jnp.where(
                    own, GF.grouped_swiglu_reference(x, g, u, d, n), 0),
                (xs, *ws, jnp.asarray(groups)), 0.03)
        del xs, ws
    return {"rel_err": errs}


def machine_facts() -> dict:
    """What later measurement rests on: device memory per
    ``memory_stats()``, and whether ``block_until_ready`` blocks — one
    ~100 ms program timed to dispatch return, to ``block_until_ready``,
    and (a second call) to the fetch of a scalar it computes."""
    import jax
    import jax.numpy as jnp

    stats = jax.devices()[0].memory_stats() or {}
    x = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def busy(x):
        a = jax.lax.fori_loop(
            0, 128, lambda _, a: (a @ x) * jnp.bfloat16(1 / 4096), x)
        return a, a[0, 0].astype(jnp.float32)

    np.asarray(busy(x)[1])  # compile + warm
    t0 = time.perf_counter()
    out = busy(x)
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    np.asarray(busy(x)[1])
    t3 = time.perf_counter()
    blocked, fetched = t2 - t0, t3 - t2
    return {"hbm_bytes_limit": stats.get("bytes_limit"),
            "dispatch_ms": round((t1 - t0) * 1e3, 2),
            "block_until_ready_ms": round(blocked * 1e3, 2),
            "dispatch_and_fetch_scalar_ms": round(fetched * 1e3, 2),
            "block_until_ready_blocks": blocked > 0.5 * fetched}


def main() -> int:
    from nnstreamer_tpu.core.platform import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    import jax
    import jaxlib

    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices())}
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found platform "
              f"{d.platform!r} ({d.device_kind})", file=sys.stderr)
        return 2
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    from nnstreamer_tpu import native

    print(f"device {device}  jax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  libtpu {libtpu}")
    print(f"compile cache {cache_dir}  JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS')!r}  native(host C++) "
          f"available: {native.available()}")
    print(f"machine {machine_facts()}")

    legs = [("stream", stream_leg), ("serving", serving_leg),
            ("kernel", kernel_leg)]
    if device["count"] >= 4:
        legs.append(("sharded_stream", sharded_stream_leg))
    else:
        print("leg sharded_stream: did not run (needs >= 4 devices, have "
              f"{device['count']})")
    ok = True
    for name, leg in legs:
        t0 = time.perf_counter()
        with CompileClock() as clock:
            try:
                facts = leg()
                status = "PASS"
            except Exception:  # noqa: BLE001 - report, fail at the end
                traceback.print_exc()
                facts, status, ok = {}, "FAIL", False
        print(f"leg {name}: {status} seconds={time.perf_counter() - t0:.1f} "
              f"compile_seconds={clock.seconds:.1f} "
              f"compiles={clock.compiles} cache_hits={clock.cache_hits} "
              f"{json.dumps(facts)}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
