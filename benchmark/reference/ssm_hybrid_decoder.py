"""A decoder whose layers mix two kinds of token mixer — a selective
state-space layer (Mamba-1) or causal attention without positional
encoding — over a dense feed-forward in every layer, as the
configuration's file describes it, in float32 ``jax.numpy`` at ``highest``
matmul precision.  No kernels, no cache, no batching of the recurrence: the
full sequence goes through every layer, one layer at a time, and a
state-space layer's recurrence is a Python loop over the positions, one
step a position, as the published ``slow_forward`` writes it (traced
``STEPS_A_CALL`` positions to a compiled call, so that a replay of 2 x
1,056 positions through 26 layers is not 27,456 dispatches).

Pre-norm (``N`` = RMSNorm with a learned gain, eps ``rms_norm_eps``):
``x <- x + Mixer_l(N_op(x))``, ``x <- x + FFN_l(N_ffn(x))``, ``logits =
N_out(x_L) E^T`` with ``E`` the embedding (tied).

* Mamba mixer (every layer but those where ``l % attn_layer_period ==
  attn_layer_offset``): ``[x | z] = h w_in`` (halves of ``E = mamba_expand
  x hidden_size`` channels, no bias); ``x <- silu(sum_j w_conv[j] x_{t -
  (K - 1) + j} + conv_b)`` for the ``K = mamba_d_conv`` taps, depthwise,
  causal, ``x`` before the first token zero; ``[d | B | C] = x w_x``
  (``mamba_dt_rank + 2 mamba_d_state``), each RMS-normed with its gain;
  ``dt = softplus(d w_dt + dt_bias)``; ``A = -exp(A_log)``; from ``s_0 =
  0``, ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t`` and ``y_t = s_t .
  C_t + D x_t``; ``out = (y * silu(z)) w_out`` (no bias).
* attention mixer: q, k, v by ``wq``, ``wk``, ``wv`` (``head_dim =
  hidden_size / num_attention_heads``, ``num_key_value_heads`` KV heads
  shared by the query heads), no bias, NO rotation, causal over all
  positions at scale ``1/sqrt(head_dim)``; ``out = concat w_o``.
* FFN (every layer: ``num_experts`` 1): ``w_down(silu(w_gate h) * w_up
  h)``.

**Departures from the published description**, each also under
``assumed`` in the file, which this module reads the VALUES of and refuses
one it does not implement: ``w_conv`` is stored ``[taps, channels]`` and
``A_log`` ``[state, channels]`` (the published layouts transposed: the
same numbers); the head is the embedding transposed (``weights["embed"]``:
the tree's ``lm_head`` leaf, a transposed copy the program reads, is NOT
read here, so a copy that is not the embedding's shows as a gap).  The
state is contracted with ``C`` in float32 where the published
``slow_forward`` first casts it to the activations' type: here everything
is float32.

``weights`` is the tree ``benchmark/models/ssm_hybrid_decoder.py`` makes:
``embed`` [V, D], ``ln_out`` [D] and under ``layers`` one stack a layer
kind, ``ssm.dense`` and ``full.nope.dense``, with the kind's layers in
order on the leading axis (matrices ``[in, out]``).

``weight_dtype`` is the control: every matrix, and the filter, rounded to
that type first (``float8_e4m3fn``, the precision below the bfloat16 the
file states); the gains and the recurrence's own float32 numbers (``A_log``,
``dt_bias``, ``D``) are not.
"""

from __future__ import annotations

import functools

# the arithmetic every reference shares: rounding for the control, the
# gap, RMSNorm, a stretch's mean
from benchmark.reference.moe_hybrid_decoder import (_f32, _gap_of, _rmsnorm,
                                                    stretch_mean)

#: ``assumed`` values this reference implements
_IMPLEMENTS = {
    "layer_order": "attention_where_layer_mod_period_is_offset",
    "attention": "mqa_unrotated_causal",
    "mamba_mixer": "mamba1_dt_b_c_rmsnorm",
    "conv_filter_layout": "taps_by_channels",
    "a_log_layout": "state_by_channels",
    "tie_word_embeddings": True,
    "hidden_act": "silu",
    "norm_placement": "pre",
}


def layer_kinds(cfg: dict) -> list:
    """For each layer run ``(mixer, stack key)``."""
    a = cfg["assumed"]
    for name, want in _IMPLEMENTS.items():
        if a[name]["value"] != want:
            raise ValueError(f"assumed.{name} = {a[name]['value']!r}: this "
                             f"reference implements {want!r}")
    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"] \
            or cfg["num_experts"] != 1:
        raise ValueError("the reference has a filter bias, no projection "
                         "bias and one dense FFN a layer")
    out = []
    for l in range(cfg["num_hidden_layers"]):
        if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
            out.append(("attention", "full.nope.dense"))
        else:
            out.append(("mamba", "ssm.dense"))
    return out


def mamba_inputs(h, lp, cfg: dict, weight_dtype=None):
    """What the recurrence reads, from the normed input ``h`` [B, T, D]:
    ``(x, z, dt [B, T, E], B, C [B, T, N], A [N, E])``, float32."""
    import jax
    import jax.numpy as jnp

    E, N = cfg["mamba_expand"] * h.shape[-1], cfg["mamba_d_state"]
    R, K, eps = cfg["mamba_dt_rank"], cfg["mamba_d_conv"], \
        cfg["rms_norm_eps"]
    xz = h @ _f32(lp["w_in"], weight_dtype)
    x, z = xz[..., :E], xz[..., E:]
    T = x.shape[1]
    w = _f32(lp["w_conv"], weight_dtype)                   # [K, E]
    acc = jnp.zeros_like(x) + _f32(lp["conv_b"], weight_dtype)
    for j in range(K):
        back = K - 1 - j     # tap j reads the column `back` before
        acc = acc + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :T]
    x = jax.nn.silu(acc)
    dbc = x @ _f32(lp["w_x"], weight_dtype)
    d = _rmsnorm(dbc[..., :R], lp["dt_norm"], eps)
    b = _rmsnorm(dbc[..., R:R + N], lp["b_norm"], eps)
    c = _rmsnorm(dbc[..., R + N:], lp["c_norm"], eps)
    dt = jax.nn.softplus(d @ _f32(lp["w_dt"], weight_dtype) + lp["dt_bias"])
    return x, z, dt, b, c, -jnp.exp(lp["A_log"])


#: positions a compiled call of the recurrence takes, one after another
STEPS_A_CALL = 32


@functools.cache
def _steps_fn(n: int):
    import jax
    import jax.numpy as jnp

    def steps(s, t0, dt, x, b, c, a):
        """Positions ``t0 .. t0 + n - 1`` of the ``[B, T, ...]`` inputs, a
        position at a time: ``s`` [B, N, E] -> (the state after the last,
        the states after each [B, n, N, E], ``y`` [B, n, E])."""
        states, ys = [], []
        for i in range(n):
            dt_t, x_t, b_t, c_t = (
                jax.lax.dynamic_index_in_dim(v, t0 + i, 1, keepdims=False)
                for v in (dt, x, b, c))
            s = jnp.exp(dt_t[:, None, :] * a) * s + (dt_t * x_t)[:, None, :] \
                * b_t[:, :, None]
            states.append(s)
            ys.append(jnp.sum(s * c_t[:, :, None], axis=1))
        return s, jnp.stack(states, axis=1), jnp.stack(ys, axis=1)

    return jax.jit(steps)


def recurrence(x, dt, b, c, a, states=None):
    """``y`` [B, T, E] of the recurrence from ``s_0 = 0``, a position at a
    time (:data:`STEPS_A_CALL` of them a compiled call).  ``states``, a
    list, receives ``s_t`` [B, N, E] after each position (the tests
    compare the program's carried state with them)."""
    import jax.numpy as jnp

    B, T, E = x.shape
    s = jnp.zeros((B, a.shape[0], E), jnp.float32)
    ys = []
    for t0 in range(0, T, STEPS_A_CALL):
        n = min(STEPS_A_CALL, T - t0)
        s, st, y = _steps_fn(n)(s, t0, dt, x, b, c, a)
        ys.append(y)
        if states is not None:
            states.extend(st[:, i] for i in range(n))
    return jnp.concatenate(ys, axis=1)


@functools.cache
def _parts(cfg_items: tuple, mixer: str, weight_dtype):
    """The jitted pieces of one layer kind: before the recurrence and
    after it (a Mamba layer), or the whole layer (attention)."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]

    def ffn(x, lp):
        h = _rmsnorm(x, lp["ln_mlp"], eps)
        return x + (jax.nn.silu(h @ _f32(lp["w_gate"], weight_dtype))
                    * (h @ _f32(lp["w_up"], weight_dtype))) \
            @ _f32(lp["w_down"], weight_dtype)

    def attention(x, lp):
        B, T, D = x.shape
        hd = D // H
        h = _rmsnorm(x, lp["ln_attn"], eps)
        q = (h @ _f32(lp["wq"], weight_dtype)).reshape(B, T, H, hd)
        k = (h @ _f32(lp["wk"], weight_dtype)).reshape(B, T, Hkv, hd)
        v = (h @ _f32(lp["wv"], weight_dtype)).reshape(B, T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (hd ** 0.5)
        keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, D)
        return ffn(x + a @ _f32(lp["wo"], weight_dtype), lp)

    def before(x, lp):
        return mamba_inputs(_rmsnorm(x, lp["ln_attn"], eps), lp, cfg,
                            weight_dtype)

    def after(x, lp, y, xc, z):
        out = ((y + lp["ssm_d"] * xc) * jax.nn.silu(z)) \
            @ _f32(lp["w_out"], weight_dtype)
        return ffn(x + out, lp)

    if mixer == "attention":
        return jax.jit(attention)
    return jax.jit(before), jax.jit(after)


_CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
             "mamba_expand", "mamba_d_state", "mamba_dt_rank",
             "mamba_d_conv")


def logits(weights, tokens, cfg: dict, weight_dtype=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits."""
    import jax
    import jax.numpy as jnp

    items = tuple((k, cfg[k]) for k in _CFG_KEYS)
    seen: dict = {}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for mixer, key in layer_kinds(cfg):
            i = seen.get(key, 0)
            seen[key] = i + 1
            lp = jax.tree.map(lambda a: a[i], weights["layers"][key])
            if mixer == "attention":
                x = _parts(items, mixer, weight_dtype)(x, lp)
                continue
            before, after = _parts(items, mixer, weight_dtype)
            xc, z, dt, b, c, a = before(x, lp)
            x = after(x, lp, recurrence(xc, dt, b, c, a), xc, z)
        x = _rmsnorm(x, weights["ln_out"], cfg["rms_norm_eps"])
        return x @ _f32(weights["embed"], weight_dtype).T


def served_gaps(weights, tokens, cfg: dict):
    """``gap[b, t]``: how far below the reference's best logit the token
    at ``tokens[b, t + 1]`` lies, given ``tokens[b, :t + 1]``, as the
    mean over the stretch of ``limits.gap_stretch_tokens`` positions from
    ``t`` on (``stretch_mean``: a lower precision moves every token, and
    so does a state carried wrongly; the configuration's ``limits_note``
    gives what each reads); and the reference's own choice."""
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
