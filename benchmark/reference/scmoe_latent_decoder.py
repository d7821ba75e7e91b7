"""A decoder of latent attention and shortcut-connected sparse experts, as
the configuration's file describes it, in float32 ``jax.numpy`` at
``highest`` matmul precision.  No kernels, no cache, no grouped products,
no batching tricks: the full sequence goes through every published layer,
one layer at a time; K and V are EXPANDED per head from the latent (never
the absorbed form a decode kernel uses); every held expert is applied to
every token, one expert at a time (each upcast to float32 alone, so the
reference fits beside the resident weights).

One published layer, ``N`` an RMSNorm with its own gain each time::

    a0 = x  + MLA_0(N(x))
    h0 = N(a0)
    s  = MoE(h0)                      # the shortcut branch opens here
    b0 = a0 + FFN_0(h0)               # dense SwiGLU
    a1 = b0 + MLA_1(N(b0))
    y  = a1 + FFN_1(N(a1)) + s        # and closes here

``MLA(h)`` at positions p: ``c_q = a_q * N(h W_qa)``; ``q = c_q W_qb`` ->
per head ``(q_C | q_R)`` of (nope | rope) values; ``[c | k_R] = h W_kva``;
``c <- a_kv * N(c)`` (``k_R`` is not scaled); ``q_R, k_R <- RoPE(., p)``
(half-split pairing, no rescaling), ``k_R`` ONE key for all heads;
``[k_C | v] = c W_kvb`` per head; scores ``(q_C . k_C + q_R . k_R) /
sqrt(nope + rope)``, causal softmax, ``out = concat_h(P v) W_o``.

``MoE(h)``: ``p = softmax(h W_r)`` in float32 over ALL the router's
outputs (the published routed experts, then the identity experts); the
chosen are the ``moe_topk`` largest of ``p + b`` (``b`` the correction
bias, for the choice only); ``g_j = routed_scaling_factor * p_j``, NOT
renormalised; ``MoE(h) = sum over chosen AND HELD real j of g_j E_j(h) +
sum over chosen identity j of g_j h``, ``E_j`` a SwiGLU.  Only the experts
``[held_first, held_first + held)`` are in ``weights``: what the others
would add is left out, here as in the program, and the partial result
goes on (the chip's share of an expert-parallel deployment, ``deployment``
in the file).  The identity pairs need no expert and are all here.

Head: ``lm_head(N(x))``, float32.

Where the published ``config.json`` has a boolean or nothing, the file's
``assumed`` group says what was taken; this module reads the VALUES from
there and refuses one it does not implement.

``weights`` is the tree ``benchmark/models/scmoe_latent_decoder.py``
makes: ``embed`` [V, D], ``ln_out`` [D], ``lm_head`` [D, V] and under
``layers`` the two sub-layer stacks ``latent.rope.dense.open`` (first
attention block, first dense FFN, router, held experts) and
``latent.rope.dense.close`` (second attention block and FFN), published
layers on the leading axis, matrices ``[in, out]``, experts ``[held, in,
out]``.

``weight_dtype`` is the control: every matrix rounded to that type first
(``float8_e4m3fn``, the precision below the bfloat16 the file states).
"""

from __future__ import annotations

import functools

# plain arithmetic that is the same for every such decoder: RMSNorm,
# half-split RoPE, the control's rounding, a sum of SwiGLUs one member
# upcast at a time, and the stretch's mean over served positions
from benchmark.reference.moe_hybrid_decoder import (_f32, _ffn_sum, _gap_of,
                                                    _rmsnorm, _rope,
                                                    stretch_mean)

OPEN, CLOSE = "latent.rope.dense.open", "latent.rope.dense.close"

_ASSUMED = {"norm_topk_prob": False, "router": "softmax_bias_choice_only",
            "zero_expert": "identity_of_the_normed_input",
            "shortcut": "open_after_first_attention_close_at_layer_end",
            "hidden_act": "silu", "rope": "half_split_no_rescaling",
            "norm_placement": "pre"}


def check_assumed(cfg: dict) -> None:
    a = cfg["assumed"]
    bad = {k: a[k]["value"] for k, v in _ASSUMED.items()
           if a[k]["value"] != v}
    if bad or cfg["zero_expert_type"] != "identity" \
            or cfg["attention_method"] != "MLA" or cfg["attention_bias"]:
        raise ValueError(f"an assumed or stated value this reference does "
                         f"not implement: {bad or cfg['zero_expert_type']}")


def route(h, lp, cfg: dict):
    """Chosen router outputs [N, k] and their weights [N, k]."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(h @ lp["w_router"].astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(p + lp["router_bias"].astype(jnp.float32),
                           cfg["moe_topk"])
    return idx, jnp.take_along_axis(p, idx, axis=-1) \
        * cfg["routed_scaling_factor"]


@functools.cache
def _layer_fn(cfg_items: tuple, weight_dtype):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    H, eps, theta = (cfg["num_attention_heads"], cfg["rms_norm_eps"],
                     float(cfg["rope_theta"]))
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    first, held, routed = (cfg["held_first"], cfg["n_routed_experts"],
                           cfg["published_routed"])

    def mla(h, lp):
        B, T, _ = h.shape

        def mat(name):
            return _f32(lp[name], weight_dtype)

        cq = cfg["scale_q"] * _rmsnorm(h @ mat("wq_a"), lp["q_a_norm"], eps)
        q = (cq @ mat("wq_b")).reshape(B, T, H, dn + dr)
        kva = h @ mat("wkv_a")
        c = cfg["scale_kv"] * _rmsnorm(kva[..., :r], lp["kv_a_norm"], eps)
        k_r = _rope(kva[..., None, r:], theta)            # [B, T, 1, dr]
        q_c, q_r = q[..., :dn], _rope(q[..., dn:], theta)
        kv = (c @ mat("wkv_b")).reshape(B, T, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk",
                       jnp.concatenate([q_c, q_r], axis=-1), k) \
            / ((dn + dr) ** 0.5)
        keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
        return a.reshape(B, T, H * dv) @ mat("wo")

    def ffn(h, lp):
        """The dense SwiGLU in column blocks, one block upcast at a
        time."""
        B, T, D = h.shape
        F = lp["w_gate"].shape[1]
        n = max(1, F // 2048) if F % 2048 == 0 else 1

        def cols(w):   # [D, F] -> [n, D, F / n]
            return w.reshape(D, n, F // n).transpose(1, 0, 2)

        y = _ffn_sum(h.reshape(B * T, D), cols(lp["w_gate"]),
                     cols(lp["w_up"]), lp["w_down"].reshape(n, F // n, D),
                     jnp.ones((B * T, n), jnp.float32), weight_dtype)
        return y.reshape(B, T, D)

    def moe(h, lp, parts):
        B, T, D = h.shape
        h = h.reshape(B * T, D)
        idx, g = route(h, lp, cfg)
        # weight of each HELD expert for each token: 0 where not chosen
        onehot = (idx - first)[:, :, None] == jnp.arange(held)[None, None, :]
        weight = jnp.sum(jnp.where(onehot, g[:, :, None], 0.0), axis=1)
        real = _ffn_sum(h, lp["we_gate"], lp["we_up"], lp["we_down"],
                        weight, weight_dtype)
        identity = h * jnp.sum(jnp.where(idx >= routed, g, 0.0), axis=-1,
                               keepdims=True)
        y = parts[0] * real + parts[1] * identity
        return y.reshape(B, T, D), idx

    def layer(x, lo, lc, parts):
        a0 = x + mla(_rmsnorm(x, lo["ln_attn"], eps), lo)
        h0 = _rmsnorm(a0, lo["ln_mlp"], eps)
        s, idx = moe(h0, lo, parts)
        b0 = a0 + ffn(h0, lo)
        a1 = b0 + mla(_rmsnorm(b0, lc["ln_attn"], eps), lc)
        return a1 + ffn(_rmsnorm(a1, lc["ln_mlp"], eps), lc) + s, idx

    return jax.jit(layer)


_CFG_KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_routed_experts", "moe_topk",
             "routed_scaling_factor", "held_first", "published_routed",
             "scale_q", "scale_kv")


def _flat_cfg(cfg: dict) -> tuple:
    a = cfg["assumed"]
    flat = dict(cfg, held_first=cfg["deployment"]["held_first"],
                published_routed=cfg["published"]["n_routed_experts"],
                scale_q=a["mla_scale_q_lora"]["value"],
                scale_kv=a["mla_scale_kv_lora"]["value"])
    return tuple((k, flat[k]) for k in _CFG_KEYS)


def logits(weights, tokens, cfg: dict, weight_dtype=None, routes=None,
           parts=(1.0, 1.0)):
    """[B, T] token ids -> [B, T, vocab] float32 logits.  ``routes``, a
    list, receives each published layer's chosen router outputs ``[B * T,
    k]``.  ``parts`` scales the real experts' and the identity pairs'
    contributions (the tests zero one to see that the comparison sees
    it)."""
    import jax
    import jax.numpy as jnp

    check_assumed(cfg)
    fn = _layer_fn(_flat_cfg(cfg), weight_dtype)
    parts = jnp.asarray(parts, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        for l in range(cfg["num_layers"]):
            lo, lc = (jax.tree.map(lambda a: a[l], weights["layers"][k])
                      for k in (OPEN, CLOSE))
            x, idx = fn(x, lo, lc, parts)
            if routes is not None:
                routes.append(idx)
        x = _rmsnorm(x, weights["ln_out"], cfg["rms_norm_eps"])
        return x @ _f32(weights["lm_head"], weight_dtype)


def served_gaps(weights, tokens, cfg: dict):
    """``gap[b, t]``: how far below the reference's best logit the token
    at ``tokens[b, t + 1]`` lies, given ``tokens[b, :t + 1]``, as the
    mean over the stretch of ``limits.gap_stretch_tokens`` positions from
    ``t`` on (``stretch_mean``: one flipped choice among the router's
    outputs moves a single token as far as float8 weights do, a lower
    precision moves EVERY token); and the reference's own choice."""
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens)
    lg = logits(weights, tokens, cfg)
    nxt = jnp.roll(tokens, -1, axis=1)
    raw = _gap_of(lg, lg.max(axis=-1), nxt)
    return (stretch_mean(raw, tokens, cfg["limits"]["gap_stretch_tokens"]),
            lg.argmax(axis=-1))


def control_gaps(weights, tokens, cfg: dict,
                 weight_dtype: str = "float8_e4m3fn"):
    """The control's reading: at each position the gap of the token the
    lower precision puts first, over the same stretches."""
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens)
    lg = logits(weights, tokens, cfg)
    low = logits(weights, tokens, cfg, weight_dtype).argmax(axis=-1)
    raw = _gap_of(lg, lg.max(axis=-1), low)
    return (stretch_mean(raw, tokens, cfg["limits"]["gap_stretch_tokens"]),
            low)
