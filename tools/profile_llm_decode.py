#!/usr/bin/env python
"""On-chip component breakdown of the 7B int8 decode step (VERDICT r4
Weak #1): where do the ms/token go?

Times, at the real llama2_7b shape with weight-only int8:

* ``hbm_floor``   — read every param byte once (sum-reduce): the
                    session's measured weight-streaming floor.
* ``mats_only``   — lax.scan over layers running ONLY the seven _mm
                    weight matmuls + residual adds (no attention, no
                    cache): the achievable weight-bound step.
* ``attn_only``   — lax.scan over layers running ONLY the cache update +
                    masked attention einsum (no weight mats).
* ``step``        — one full decode step (forward_cached T=1).
* ``chunk32``     — the production 32-step decode scan, /32 per token.

Sync discipline: sync by fetching bytes — every timing dispatches N
calls and fetches a few bytes of the last output (tools/_chiptime.py).

Usage:  python tools/profile_llm_decode.py [--max-seq 1024]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.models import llama
from tools._chiptime import chip_time_ms, fetch_rtt_s


def report(name, ms, per=1, **extra):
    rec = {"probe": name, "ms": round(ms, 3),
           "ms_per_token": round(ms / per, 3), **extra}
    print(json.dumps(rec), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=1)
    args = ap.parse_args()

    cfg = llama.PRESETS["llama2_7b"]
    cfg = llama.LlamaConfig(**{**cfg.__dict__, "max_seq": args.max_seq})
    B = args.batch

    print(json.dumps({"probe": "init", "device": str(jax.devices()[0]),
                      "max_seq": args.max_seq, "batch": B,
                      "fetch_rtt_ms": round(fetch_rtt_s() * 1e3, 2)}),
          flush=True)
    t0 = time.perf_counter()
    params = llama.init_params_int8(cfg, seed=0, gen_dtype="bfloat16")
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))

    @jax.jit
    def hbm_floor(p):
        return sum(jnp.sum(x.view(jnp.int8) if x.dtype == jnp.bfloat16
                           else x, dtype=jnp.int32)
                   for x in jax.tree.leaves(p))

    np.asarray(hbm_floor(params))  # also forces params materialization
    print(json.dumps({"probe": "init_params_int8_s",
                      "s": round(time.perf_counter() - t0, 1)}), flush=True)

    ms = chip_time_ms(hbm_floor, params, iters=8)
    report("hbm_floor", ms, gb=round(nbytes / 1e9, 2),
           gbs=round(nbytes / (ms * 1e-3) / 1e9, 1))

    dt = jnp.bfloat16
    x0 = jnp.zeros((B, 1, cfg.dim), dt)
    small = lambda o: o.reshape(-1)[:4]  # noqa: E731

    @jax.jit
    def mats_only(p, x):
        def body(x, lp):
            h = llama._rmsnorm(x, lp["ln_attn"], cfg.norm_eps)
            q = llama._mm(h, lp, "wq", dt)
            k = llama._mm(h, lp, "wk", dt)
            v = llama._mm(h, lp, "wv", dt)
            attn = (q + k + v)  # stand-in for attention output
            x = x + llama._mm(attn, lp, "wo", dt)
            h = llama._rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
            gate = jax.nn.silu(llama._mm(h, lp, "w_gate", dt))
            up = llama._mm(h, lp, "w_up", dt)
            x = x + llama._mm(gate * up, lp, "w_down", dt)
            return x, None

        x, _ = jax.lax.scan(body, x, p["layers"])
        return x

    report("mats_only", chip_time_ms(mats_only, params, x0, fetch=small))

    cache = llama.init_cache(cfg, B, dtype="bfloat16")
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    kv_new = jnp.zeros((B, 1, Hkv, hd), dt)

    @jax.jit
    def attn_only(c, kv_new, pos):
        H = cfg.n_heads

        def body(x, layer):
            kc, vc = layer
            kc = jax.lax.dynamic_update_slice(
                kc, kv_new.astype(kc.dtype), (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, kv_new.astype(vc.dtype), (0, pos, 0, 0))
            q = x.reshape(B, 1, H, hd)
            kr = llama._repeat_kv(kc.astype(dt), H // Hkv)
            vr = llama._repeat_kv(vc.astype(dt), H // Hkv)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                           preferred_element_type=jnp.float32)
            S = kr.shape[1]
            mask = jnp.arange(S)[None, None, None, :] <= pos
            s = jnp.where(mask, s, jnp.float32(-1e30))
            p_ = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", p_.astype(dt), vr)
            return attn.reshape(B, 1, H * hd), (kc, vc)

        x, _ = jax.lax.scan(body, jnp.zeros((B, 1, cfg.dim), dt),
                            (c["k"], c["v"]))
        return x

    report("attn_only", chip_time_ms(attn_only, cache, kv_new, 40,
                                     fetch=small),
           cache_gb=round(sum(v.size * v.dtype.itemsize
                              for v in cache.values()) / 1e9, 2))

    step = jax.jit(functools.partial(llama.forward_cached, cfg=cfg))
    tok = jnp.ones((B, 1), jnp.int32)
    report("step", chip_time_ms(
        lambda p, t, c: step(p, t, c, 40), params, tok, cache,
        fetch=lambda o: o[0].reshape(-1)[:4]))

    @jax.jit
    def chunk32(p, tok, c, pos0):
        def sbody(carry, i):
            tok, c = carry
            logits, c = llama.forward_cached(p, tok[:, None], c,
                                             pos0 + i, cfg)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (nxt, c), nxt

        (tok, c), toks = jax.lax.scan(sbody, (tok, c), jnp.arange(32))
        return toks

    tok1 = jnp.ones((B,), jnp.int32)
    ms = chip_time_ms(chunk32, params, tok1, cache, 40, iters=4)
    report("chunk32", ms, per=32,
           toks_per_s=round(32e3 / ms, 1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
