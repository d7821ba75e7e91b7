"""A decoder of latent attention under YaRN, leading dense layers, then
sparse-expert layers with a group-limited sigmoid router and a shared
expert, as the configuration's file describes it, in float32 ``jax.numpy``
at ``highest`` matmul precision.  No kernels, no cache, no grouped
products: the full sequence goes through every layer, one jitted layer at
a time (a replay is a handful of dispatches, not one a position); K and V
are EXPANDED per head from the latent; every held expert is applied to
every token, one expert at a time (each upcast to float32 alone, so the
reference fits beside the resident weights).

One layer, ``N`` an RMSNorm with its own gain each time::

    a = x + MLA(N(x))
    y = a + FFN(N(a))       # a dense SwiGLU, or the experts below

``MLA(h)`` at positions p: ``c_q = N(h W_qa)``; ``q = c_q W_qb`` -> per
head ``(q_C | q_R)`` of (nope | rope) values; ``[c | k_R] = h W_kva``,
``c <- N(c)``; ``q_R, k_R <- RoPE(., p)`` with YaRN's frequencies (below),
``k_R`` ONE key for all heads; ``[k_C | v] = c W_kvb`` per head; scores
``(q_C . k_C + q_R . k_R) * scale``, causal softmax, ``out = concat_h(P v)
W_o``.

**YaRN** (``rope_scaling``, d = rope dims, i = 0 .. d/2 - 1, half-split
pairs ``(i, i + d/2)``): ``f_i = theta^(-2i/d)``; ``low = max(floor(d
ln(L0 / (beta_fast 2 pi)) / (2 ln theta)), 0)``, ``high = min(ceil(d ln(L0
/ (beta_slow 2 pi)) / (2 ln theta)), d - 1)``; ``ramp_i = clip((i - low) /
(high - low), 0, 1)``; the frequency is ``f_i / factor * ramp_i + f_i (1 -
ramp_i)``; cos and sin are multiplied by ``m(factor, mscale) / m(factor,
mscale_all_dim)`` and the scale is ``(nope + rope)^-0.5 m(factor,
mscale_all_dim)^2``, ``m(s, a) = 0.1 a ln s + 1``.

**The experts** (``s = sigmoid(h W_r)`` in float32 over ALL the routed
experts of the published model, ``s' = s + b`` with ``b`` the correction
bias, for the choice only): the experts split into ``n_group`` groups of
consecutive ids; a group scores the sum of its two largest ``s'``; the
``topk_group`` best groups are kept (of equal scores the lower group);
the chosen are the ``num_experts_per_tok`` largest ``s'`` among the kept
groups' experts; ``g_j = s_j / (sum of the chosen s + 1e-20) x
routed_scaling_factor``; ``MoE(h) = sum over chosen AND HELD j of g_j
E_j(h) + E_shared(h)``.  Only the experts ``[held_first, held_first +
held)`` are in ``weights``: what the others would add is left out, here as
in the program, and the partial result goes on (the chip's share of an
expert-parallel deployment, ``deployment`` in the file).

Head: ``lm_head(N(x))``, float32.

Where the published ``config.json`` is silent the file's ``assumed`` group
says what was taken; this module reads the VALUES from there and refuses
one it does not implement.

``weights`` is the tree ``benchmark/models/mla_moe_decoder.py`` makes:
``embed`` [V, D], ``ln_out`` [D], ``lm_head`` [D, V] and under ``layers``
the stacks ``latent.rope.dense`` and ``latent.rope.experts``, the layers of
each kind in order on the leading axis (matrices ``[in, out]``, experts
``[held, in, out]``).

``weight_dtype`` is the control: every matrix rounded to that type first
(``float8_e4m3fn``, the precision below the bfloat16 the file states).
"""

from __future__ import annotations

import functools
import math

# plain arithmetic that is the same for every such decoder: RMSNorm, the
# control's rounding, a sum of SwiGLUs one member upcast at a time, and
# the stretch's mean over served positions
from benchmark.reference.moe_hybrid_decoder import (_f32, _ffn_sum, _gap_of,
                                                    _rmsnorm, stretch_mean)

DENSE, EXPERTS = "latent.rope.dense", "latent.rope.experts"

_ASSUMED = {"norm_placement": "pre", "topk_method": "group_limited",
            "router_bias": "choice_only", "group_score": "top2_sum",
            "rope": "half_split_yarn", "hidden_act": "silu",
            "tie_word_embeddings": False, "seq_aux": "training_only"}


def check_assumed(cfg: dict) -> None:
    a = cfg["assumed"]
    bad = {k: a[k]["value"] for k, v in _ASSUMED.items()
           if a[k]["value"] != v}
    if bad or cfg["scoring_func"] != "sigmoid" or cfg["attention_bias"] \
            or cfg["rope_scaling"]["type"] != "yarn" \
            or not cfg["norm_topk_prob"]:
        raise ValueError(f"an assumed or stated value this reference does "
                         f"not implement: {bad or cfg['scoring_func']}")


def _mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def yarn(cfg: dict):
    """(frequencies [d / 2] as a list, the cos/sin gain, the softmax
    scale), by the formulas of the module docstring."""
    rs, d = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    L0 = rs["original_max_position_embeddings"]

    def index(turns):
        return d * math.log(L0 / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(index(rs["beta_fast"])), 0)
    high = min(math.ceil(index(rs["beta_slow"])), d - 1)
    if high == low:
        high += 0.001
    freqs = []
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freqs.append(f / rs["factor"] * ramp + f * (1 - ramp))
    m_all = _mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5 * m_all ** 2
    return freqs, _mscale(rs["factor"], rs["mscale"]) / m_all, scale


def _rope(x, freqs, gain):
    """Half-split rotary embedding at positions 0 .. T-1; ``x``: [B, T,
    H, d]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos = gain * jnp.cos(ang)[None, :, None, :]
    sin = gain * jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(h, lp, cfg: dict):
    """Chosen experts [N, k] and their weights [N, k], group by group;
    ``cfg`` is :func:`flat_cfg`'s."""
    import jax
    import jax.numpy as jnp

    G, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ lp["w_router"].astype(jnp.float32))
    biased = s + lp["router_bias"].astype(jnp.float32)
    N, E = biased.shape
    by_group = biased.reshape(N, G, E // G)
    score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)     # [N, G]
    # the kg best groups, the lower group first among equals
    order = jnp.argsort(-score, axis=-1, stable=True)[:, :kg]
    kept = jnp.any(order[:, :, None] == jnp.arange(G)[None, None, :],
                   axis=1)                                     # [N, G]
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), biased, -jnp.inf)
    idx = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg["renorm_eps"])
    return idx, w * cfg["routed_scaling_factor"]


@functools.cache
def _layer_fn(cfg_items: tuple, ffn: str, weight_dtype):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    first, held = cfg["held_first"], cfg["n_routed_experts"]
    freqs, gain, scale = cfg["yarn"]

    def mla(h, lp):
        B, T, _ = h.shape

        def mat(name):
            return _f32(lp[name], weight_dtype)

        cq = _rmsnorm(h @ mat("wq_a"), lp["q_a_norm"], eps)
        q = (cq @ mat("wq_b")).reshape(B, T, H, dn + dr)
        kva = h @ mat("wkv_a")
        c = _rmsnorm(kva[..., :r], lp["kv_a_norm"], eps)
        k_r = _rope(kva[..., None, r:], freqs, gain)          # [B, T, 1, dr]
        q_c, q_r = q[..., :dn], _rope(q[..., dn:], freqs, gain)
        kv = (c @ mat("wkv_b")).reshape(B, T, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk",
                       jnp.concatenate([q_c, q_r], axis=-1), k) * scale
        keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
        return a.reshape(B, T, H * dv) @ mat("wo")

    def dense(h, lp):
        """The dense SwiGLU in column blocks, one block upcast at a
        time."""
        N, D = h.shape
        F = lp["w_gate"].shape[1]
        n = max(1, F // 2048) if F % 2048 == 0 else 1

        def cols(w):   # [D, F] -> [n, D, F / n]
            return w.reshape(D, n, F // n).transpose(1, 0, 2)

        return _ffn_sum(h, cols(lp["w_gate"]), cols(lp["w_up"]),
                        lp["w_down"].reshape(n, F // n, D),
                        jnp.ones((N, n), jnp.float32), weight_dtype)

    def experts(h, lp, parts):
        idx, g = route(h, lp, cfg)
        # weight of each HELD expert for each token: 0 where not chosen
        onehot = (idx - first)[:, :, None] == jnp.arange(held)[None, None, :]
        weight = jnp.sum(jnp.where(onehot, g[:, :, None], 0.0), axis=1)
        routed = _ffn_sum(h, lp["we_gate"], lp["we_up"], lp["we_down"],
                          weight, weight_dtype)
        shared = _ffn_sum(h, lp["ws_gate"][None], lp["ws_up"][None],
                          lp["ws_down"][None],
                          jnp.ones((h.shape[0], 1), jnp.float32),
                          weight_dtype)
        return parts[0] * routed + parts[1] * shared, idx

    def layer(x, lp, parts):
        B, T, D = x.shape
        a = x + mla(_rmsnorm(x, lp["ln_attn"], eps), lp)
        h = _rmsnorm(a, lp["ln_mlp"], eps).reshape(B * T, D)
        if ffn == "dense":
            return a + dense(h, lp).reshape(B, T, D), \
                jnp.zeros((B * T, 0), jnp.int32)
        y, idx = experts(h, lp, parts)
        return a + y.reshape(B, T, D), idx

    return jax.jit(layer)


_CFG_KEYS = ("num_attention_heads", "rms_norm_eps", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "n_routed_experts", "num_experts_per_tok", "n_group",
             "topk_group", "routed_scaling_factor")


def flat_cfg(cfg: dict) -> dict:
    """What a layer reads of the file, in hashable values."""
    freqs, gain, scale = yarn(cfg)
    return dict({k: cfg[k] for k in _CFG_KEYS},
                held_first=cfg["deployment"]["held_first"],
                renorm_eps=cfg["assumed"]["renorm_eps"]["value"],
                yarn=(tuple(freqs), gain, scale))


def layer_ffns(cfg: dict) -> list:
    first = cfg["first_k_dense_replace"]
    return ["dense" if l < first else "experts"
            for l in range(cfg["num_hidden_layers"])]


def logits(weights, tokens, cfg: dict, weight_dtype=None, routes=None,
           parts=(1.0, 1.0)):
    """[B, T] token ids -> [B, T, vocab] float32 logits.  ``routes``, a
    list, receives each expert layer's chosen experts ``[B * T, k]``.
    ``parts`` scales the routed experts' and the shared expert's
    contributions (the tests zero one to see that the comparison sees
    it)."""
    import jax
    import jax.numpy as jnp

    check_assumed(cfg)
    items = tuple(sorted(flat_cfg(cfg).items()))
    parts = jnp.asarray(parts, jnp.float32)
    seen = {DENSE: 0, EXPERTS: 0}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"].astype(jnp.float32)[tokens]
        for ffn in layer_ffns(cfg):
            key = DENSE if ffn == "dense" else EXPERTS
            i = seen[key]
            seen[key] = i + 1
            lp = jax.tree.map(lambda a: a[i], weights["layers"][key])
            x, idx = _layer_fn(items, ffn, weight_dtype)(x, lp, parts)
            if routes is not None and ffn == "experts":
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
