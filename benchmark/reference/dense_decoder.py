"""A dense causal decoder — RMSNorm, rotary attention with grouped KV
heads, SwiGLU — as Mistral-7B-v0.1's published description gives it, in
float32 ``jax.numpy`` at ``highest`` matmul precision.  No kernels, no
cache, no batching tricks: the full sequence goes through every layer.

``weights`` is the tree ``benchmark/weights.py`` makes: int8 matrices with
float32 per-output-channel scales, which are dequantized here one layer at
a time (the configuration states int8 weights, so W = q * s IS the model).

``weight_bits=4`` is the control: the same forward with every matrix
re-quantized to symmetric int4 per output channel, the next precision
below the one the configuration states.
"""

from __future__ import annotations

import functools


def _dequant(q, s, weight_bits: int):
    import jax.numpy as jnp

    q = q.astype(jnp.float32)
    if weight_bits == 8:
        return q * s
    if weight_bits == 4:
        return jnp.round(q * (7.0 / 127.0)) * (s * (127.0 / 7.0))
    raise ValueError(f"weight_bits {weight_bits}")


def _rmsnorm(x, gain, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * gain


def _rope(x, theta):
    """Rotary embedding, the half-split convention of the published
    Hugging Face implementation.  ``x``: [B, T, H, D]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(weights, tokens, cfg: dict, weight_bits: int = 8):
    """[B, T] token ids -> [B, T, vocab] float32 logits."""
    import jax
    import jax.numpy as jnp

    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, T = tokens.shape
    causal = jnp.tril(jnp.ones((T, T), bool))

    def mat(lp, name):
        return _dequant(lp[name + "_q"], lp[name + "_s"], weight_bits)

    def block(x, lp):
        h = _rmsnorm(x, lp["ln_attn"], eps)
        q = _rope((h @ mat(lp, "wq")).reshape(B, T, H, hd), theta)
        k = _rope((h @ mat(lp, "wk")).reshape(B, T, Hkv, hd), theta)
        v = (h @ mat(lp, "wv")).reshape(B, T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, H * hd)
        x = x + a @ mat(lp, "wo")
        h = _rmsnorm(x, lp["ln_mlp"], eps)
        x = x + (jax.nn.silu(h @ mat(lp, "w_gate"))
                 * (h @ mat(lp, "w_up"))) @ mat(lp, "w_down")
        return x, None

    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        x, _ = jax.lax.scan(block, x, weights["layers"])
        x = _rmsnorm(x, weights["ln_out"], eps)
        return x @ _dequant(weights["lm_head_q"], weights["lm_head_s"],
                            weight_bits)


@functools.cache
def _gaps_fn(cfg_items: tuple, weight_bits: int):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)

    def gaps(weights, tokens):
        """For each position t the reference's view of token t+1: how far
        its logit lies below the reference's best (0 = the reference's own
        choice), and, for the control, which token this precision puts
        first."""
        lg = logits(weights, tokens, cfg, 8)
        best = lg.max(axis=-1)
        nxt = jnp.roll(tokens, -1, axis=1)
        served_gap = best - jnp.take_along_axis(
            lg, nxt[..., None], axis=-1)[..., 0]
        if weight_bits == 8:
            return served_gap, lg.argmax(axis=-1)
        low = logits(weights, tokens, cfg, weight_bits).argmax(axis=-1)
        low_gap = best - jnp.take_along_axis(lg, low[..., None],
                                             axis=-1)[..., 0]
        return low_gap, low

    return jax.jit(gaps)


_CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "rms_norm_eps", "rope_theta")


def served_gaps(weights, tokens, cfg: dict):
    """``gap[b, t]``: how far below the reference's best logit the token
    at ``tokens[b, t + 1]`` lies, given ``tokens[b, :t + 1]``; and the
    reference's own choice there."""
    fn = _gaps_fn(tuple((k, cfg[k]) for k in _CFG_KEYS), 8)
    return fn(weights, tokens)


def control_gaps(weights, tokens, cfg: dict, weight_bits: int = 4):
    """The control's reading: at each position, the gap of the token the
    lower precision puts first."""
    fn = _gaps_fn(tuple((k, cfg[k]) for k in _CFG_KEYS), weight_bits)
    return fn(weights, tokens)
