"""Plain float32 reference of the toy two-branch decoder (see the model
module of the same name): RMSNorm, rotary grouped-query attention, and
two SwiGLU branches added to the residual.  Imports nothing of the
program; the three helpers are the dense reference's."""

from benchmark.reference.dense_decoder import _dequant, _rmsnorm, _rope


def logits(weights, tokens, cfg: dict, weight_bits: int = 8):
    """[B, T] token ids -> [B, T, vocab] float32 logits."""
    import jax
    import jax.numpy as jnp

    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, T = tokens.shape
    causal = jnp.tril(jnp.ones((T, T), bool))

    def mat(lp, name, scale=None):
        return _dequant(lp[name + "_q"], lp[(scale or name) + "_s"],
                        weight_bits)

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
        one = (jax.nn.silu(h @ mat(lp, "w_gate"))
               * (h @ mat(lp, "w_up"))) @ mat(lp, "w_down")
        two = (jax.nn.silu(h @ mat(lp, "w_gate2"))
               * (h @ mat(lp, "w_up2"))) @ mat(lp, "w_down2", "w_down")
        return x + one + two, None

    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        x, _ = jax.lax.scan(block, x, weights["layers"])
        x = _rmsnorm(x, weights["ln_out"], eps)
        return x @ _dequant(weights["lm_head_q"], weights["lm_head_s"],
                            weight_bits)


def _gaps(weights, tokens, cfg, weight_bits):
    """At each position, how far below the reference's best logit lies the
    next served token (8 bits) or the lower precision's first choice."""
    import jax.numpy as jnp

    lg = logits(weights, tokens, cfg, 8)
    pick = (jnp.roll(tokens, -1, axis=1) if weight_bits == 8 else
            logits(weights, tokens, cfg, weight_bits).argmax(axis=-1))
    at = jnp.take_along_axis(lg, pick[..., None], axis=-1)[..., 0]
    return lg.max(axis=-1) - at, pick


def served_gaps(weights, tokens, cfg: dict):
    return _gaps(weights, tokens, cfg, 8)


def control_gaps(weights, tokens, cfg: dict, weight_bits: int = 4):
    return _gaps(weights, tokens, cfg, weight_bits)
