"""A decoder whose layers mix two kinds of token mixer — a gated short
convolution or causal attention — over a dense or a sparse-expert
feed-forward, as the configuration's file describes it, in float32
``jax.numpy`` at ``highest`` matmul precision.  No kernels, no cache, no
state carried from call to call, no grouped products: the full sequence
goes through every layer, one layer at a time, the convolution as a sum
over shifted copies of its input, and through every expert, one at a time
(each upcast to float32 alone, so the reference fits beside the resident
weights).

Pre-norm (``N`` = RMSNorm with a learned gain, eps ``norm_eps``), no bias
anywhere: ``x <- x + Mixer_l(N_op(x))``, ``x <- x + FFN_l(N_ffn(x))``,
``logits = N_out(x_L) E^T`` with ``E`` the embedding (tied).

* conv mixer (``layer_types[l] == "conv"``): ``[b | c | v] = h w_in`` (in
  thirds); ``u = b * v``; ``z_t = sum_j w_conv[j] * u_{t - (K - 1) + j}``
  for the ``K = conv_L_cache`` taps, depthwise, causal, ``u`` before the
  first token zero; ``out = (c * z) w_out``.
* attention mixer (``"full_attention"``): q, k, v by ``wq``, ``wk``,
  ``wv`` (``head_dim = hidden_size / num_attention_heads``); RMSNorm over
  each head of q and k (gains ``q_norm``, ``k_norm``) BEFORE the rotation;
  half-split RoPE on q and k; causal over all positions, scale
  ``1/sqrt(head_dim)``, grouped KV heads; ``out = concat w_o``.
* dense FFN (layers before ``num_dense_layers``): ``w_down(silu(w_gate h)
  * w_up h)``.
* sparse FFN: ``s = sigmoid(h w_router)`` in float32 over all experts; the
  chosen are the ``num_experts_per_tok`` largest of ``s + router_bias``
  (the bias for the choice only); their weights ``s_i / (sum of the chosen
  s + 1e-6) * routed_scaling_factor``; ``out = sum over chosen i of g_i
  E_i(h)``.  Every expert is in ``weights``: nothing is left out.

**Departures from the published description**, each also under
``assumed`` in the file, which this module reads the VALUES of and refuses
one it does not implement: ``w_conv`` is stored ``[taps, hidden]`` (the
published depthwise filter ``[hidden, 1, taps]`` transposed: the same
numbers); the rotation pairs dimension ``i`` with ``i + head_dim / 2``
(with weights from a seed the interleaved pairing is a permutation of
``wq``'s and ``wk``'s columns); the head is the embedding transposed
(``weights["embed"]``: the tree's ``lm_head`` leaf, a transposed copy the
program reads, is NOT read here, so a copy that is not the embedding's
shows as a gap).

``weights`` is the tree ``benchmark/models/conv_moe_decoder.py`` makes:
``embed`` [V, D], ``ln_out`` [D] and under ``layers`` one stack a layer
kind, keyed ``<conv|full.rope>.<dense|experts>`` with the kind's layers in
order on the leading axis (matrices ``[in, out]``, experts ``[n, in,
out]``).

``weight_dtype`` is the control: every matrix rounded to that type first
(``float8_e4m3fn``, the precision below the bfloat16 the file states).
"""

from __future__ import annotations

import functools

# the arithmetic every reference shares: rounding for the control, one
# expert at a time, the gap, RMSNorm, the rotation, a stretch's mean
from benchmark.reference.moe_hybrid_decoder import (_f32, _ffn_sum, _gap_of,
                                                    _rmsnorm, _rope,
                                                    stretch_mean)

#: ``assumed`` values this reference implements
_IMPLEMENTS = {
    "w_in_order": "b_c_v",
    "qk_norm": "per_head_before_rope",
    "rope": "half_split_no_rescaling",
    "router": "sigmoid_bias_choice_only",
    "tie_word_embeddings": True,
    "hidden_act": "silu",
    "norm_placement": "pre",
}


def layer_kinds(cfg: dict) -> list:
    """For each layer run ``(mixer, ffn, stack key)``: the first
    ``num_hidden_layers`` entries of ``layer_types``, dense before
    ``num_dense_layers``."""
    a = cfg["assumed"]
    for name, want in _IMPLEMENTS.items():
        if a[name]["value"] != want:
            raise ValueError(f"assumed.{name} = {a[name]['value']!r}: this "
                             f"reference implements {want!r}")
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("the reference has no convolution bias and a "
                         "router with its correction bias")
    out = []
    for l in range(cfg["num_hidden_layers"]):
        mixer = {"conv": "conv", "full_attention": "full.rope"}[
            cfg["layer_types"][l]]
        ffn = "dense" if l < cfg["num_dense_layers"] else "experts"
        out.append((mixer, ffn, f"{mixer}.{ffn}"))
    return out


def route(h, lp, cfg: dict):
    """Chosen experts [N, k] and their weights [N, k]."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ lp["w_router"].astype(jnp.float32))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg["norm_topk_eps"])
    return idx, w * cfg["routed_scaling_factor"]


def conv_mixer(h, lp, taps: int, weight_dtype=None, u_out=None):
    """The gated short convolution on ``h`` [B, T, D] as a sum over
    ``taps`` shifted copies of ``u``.  ``u_out``, a list, receives ``u``
    (the tests compare the program's carried state with its columns)."""
    import jax.numpy as jnp

    D = h.shape[-1]
    bcv = h @ _f32(lp["w_in"], weight_dtype)
    b, c, v = bcv[..., :D], bcv[..., D:2 * D], bcv[..., 2 * D:]
    u = b * v
    if u_out is not None:
        u_out.append(u)
    T = u.shape[1]
    w = _f32(lp["w_conv"], weight_dtype)                     # [taps, D]
    z = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j     # tap j reads the column `back` before
        z = z + w[j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :T]
    return (c * z) @ _f32(lp["w_out"], weight_dtype)


@functools.cache
def _layer_fn(cfg_items: tuple, mixer: str, ffn: str, weight_dtype):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]

    def layer(x, lp):
        B, T, D = x.shape

        def mat(name):
            return _f32(lp[name], weight_dtype)

        h = _rmsnorm(x, lp["ln_attn"], eps)
        if mixer == "conv":
            x = x + conv_mixer(h, lp, cfg["conv_L_cache"], weight_dtype)
        else:
            q = _rmsnorm((h @ mat("wq")).reshape(B, T, H, hd),
                         lp["q_norm"], eps)
            k = _rmsnorm((h @ mat("wk")).reshape(B, T, Hkv, hd),
                         lp["k_norm"], eps)
            v = (h @ mat("wv")).reshape(B, T, Hkv, hd)
            q, k = _rope(q, theta), _rope(k, theta)
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
            keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, H * hd)
            x = x + a @ mat("wo")

        h = _rmsnorm(x, lp["ln_mlp"], eps).reshape(B * T, D)
        if ffn == "dense":
            y = (jax.nn.silu(h @ mat("w_gate")) * (h @ mat("w_up"))) \
                @ mat("w_down")
            return x + y.reshape(B, T, D), jnp.zeros((B * T, 0), jnp.int32)
        idx, g = route(h, lp, cfg)
        n = lp["we_gate"].shape[0]
        onehot = idx[:, :, None] == jnp.arange(n)[None, None, :]
        weight = jnp.sum(jnp.where(onehot, g[:, :, None], 0.0), axis=1)
        y = _ffn_sum(h, lp["we_gate"], lp["we_up"], lp["we_down"], weight,
                     weight_dtype)
        return x + y.reshape(B, T, D), idx

    return jax.jit(layer)


_CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
             "norm_eps", "rope_theta", "conv_L_cache",
             "num_experts_per_tok", "norm_topk_prob", "norm_topk_eps",
             "routed_scaling_factor")


def _flat_cfg(cfg: dict) -> tuple:
    flat = dict(cfg, rope_theta=cfg["rope_parameters"]["rope_theta"],
                norm_topk_eps=cfg["assumed"]["norm_topk_eps"]["value"])
    return tuple((k, flat[k]) for k in _CFG_KEYS)


def logits(weights, tokens, cfg: dict, weight_dtype=None, routes=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits.  ``routes``, a
    list, receives each sparse layer's chosen experts ``[B * T, k]``."""
    import jax
    import jax.numpy as jnp

    items = _flat_cfg(cfg)
    seen: dict = {}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for mixer, ffn, key in layer_kinds(cfg):
            i = seen.get(key, 0)
            seen[key] = i + 1
            lp = jax.tree.map(lambda a: a[i], weights["layers"][key])
            x, idx = _layer_fn(items, mixer, ffn, weight_dtype)(x, lp)
            if routes is not None and ffn == "experts":
                routes.append(idx)
        x = _rmsnorm(x, weights["ln_out"], cfg["norm_eps"])
        return x @ _f32(weights["embed"], weight_dtype).T


def served_gaps(weights, tokens, cfg: dict):
    """``gap[b, t]``: how far below the reference's best logit the token
    at ``tokens[b, t + 1]`` lies, given ``tokens[b, :t + 1]``, as the
    mean over the stretch of ``limits.gap_stretch_tokens`` positions from
    ``t`` on (``stretch_mean``: one flipped choice among the router's
    outputs moves a single token as far as float8 weights do, a lower
    precision moves EVERY token, and so does a convolution state carried
    wrongly at every step; a state wrong ONCE moves the two or three
    tokens that filter over it, by far more than a flipped choice does:
    the configuration's ``limits_note`` gives what each reads); and the
    reference's own choice."""
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
