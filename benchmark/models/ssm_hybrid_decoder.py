"""A decoder whose layers mix two kinds of token mixer — a selective
state-space layer (Mamba-1) whose state is a float32 matrix a stream, or
causal attention without positional encoding over a paged K/V cache — over
a dense feed-forward in every layer, as the ``serve`` driver meets it: the
weights, the registration with the program, what the pipeline string
states, FLOPs per token, the control, and the counts its readers under
``layer_metrics/`` divide by.  Its reference is
``reference/ssm_hybrid_decoder.py``, which imports nothing from here.

Everything reads the configuration's file; no model's name appears.
Weights and counts import nothing of the program: ``register`` alone
does.

**The tree.**  One stack a layer kind, keyed as the program's checkpoint
layout keys them (``nnstreamer_tpu/models/llama.py`` ``LayerKind.name``):
``ssm.dense`` and ``full.nope.dense``, the kind's layers in order on the
leading axis.  Matrices are ``[in, out]``; the filter ``[taps,
channels]`` and ``A_log`` ``[state, channels]`` (the published layouts
transposed: ``assumed.conv_filter_layout``, ``assumed.a_log_layout``).
The head is the embedding's transposed copy
(``assumed.tie_word_embeddings``).
"""

from __future__ import annotations

import math

from benchmark.traffic import jax_seed

ZOO_NAME = "bench_ssm_hybrid_decoder"
#: the reference one precision below the bfloat16 the family states
CONTROL = {"weight_dtype": "float8_e4m3fn"}

#: leaves that stay float32: the gains, and what the recurrence reads
#: beside its float32 state
_F32 = ("ln_attn", "ln_mlp", "dt_norm", "b_norm", "c_norm", "dt_bias",
        "A_log", "ssm_d")
#: the matrices that write into the residual stream
_RESIDUAL_OUT = ("w_out", "wo", "w_down")

#: what the embedding's rows are drawn at (a standard deviation): the
#: stream a token enters with.  1, as for the other configurations: on the
#: chip the 0.5 of ``conv_moe_decoder`` made greedy answers no less
#: repetitive here (a median 33 % of distinct tokens in 200 against 42 %
#: at 1: PERF.md section 6)
EMBED_STD = 1.0


def residual_scale(cfg: dict) -> float:
    """``1 / sqrt(2 N)`` for the depth N: a mixer and a feed-forward
    write into the stream a layer (the GPT-2 convention, as the other
    configurations take it)."""
    return (2.0 * cfg["num_hidden_layers"]) ** -0.5


def inner(cfg: dict) -> int:
    """A state-space layer's channels ``E``."""
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def is_attention(cfg: dict, layer: int) -> bool:
    """``assumed.layer_order``: attention where ``layer %
    attn_layer_period == attn_layer_offset``."""
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def layer_kinds(cfg: dict) -> list:
    """For each layer ``(is attention, stack key)``."""
    return [(a, "full.nope.dense" if a else "ssm.dense")
            for a in (is_attention(cfg, l)
                      for l in range(cfg["num_hidden_layers"]))]


def leaf_shapes(cfg: dict, attention: bool) -> dict:
    """Leaf -> shape of one layer."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"ln_attn": (D,), "ln_mlp": (D,), "w_gate": (D, F),
           "w_up": (D, F), "w_down": (F, D)}
    if attention:
        hq = cfg["num_attention_heads"] * head_dim(cfg)
        hkv = cfg["num_key_value_heads"] * head_dim(cfg)
        out.update(wq=(D, hq), wk=(D, hkv), wv=(D, hkv), wo=(hq, D))
    else:
        E, N, R = inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
        out.update(w_in=(D, 2 * E), w_conv=(cfg["mamba_d_conv"], E),
                   conv_b=(E,), w_x=(E, R + 2 * N), dt_norm=(R,),
                   b_norm=(N,), c_norm=(N,), w_dt=(R, E), dt_bias=(E,),
                   A_log=(N, E), ssm_d=(E,), w_out=(E, D))
    return out


def tree_params(cfg: dict) -> int:
    """Parameters of the model as published (the tied head once)."""
    total = cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for attention, _key in layer_kinds(cfg):
        total += sum(math.prod(s)
                     for s in leaf_shapes(cfg, attention).values())
    return total


def tree_bytes(cfg: dict) -> int:
    """Bytes of the tree :func:`weights` makes, by shapes alone (the head's
    transposed copy counted: it is resident)."""
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] * 2 \
        + 4 * cfg["hidden_size"]
    for attention, _key in layer_kinds(cfg):
        for leaf, shape in leaf_shapes(cfg, attention).items():
            total += math.prod(shape) * (4 if leaf in _F32 else 2)
    return total


def weights(cfg: dict, seed: int):
    """The tree of the configuration, made on the device in the type it is
    served in (``assumed.weights`` says each word)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(jax_seed(seed, "ssm_hybrid_decoder"))
    k_embed, k_norm, k_sign, k_layers = jax.random.split(key, 4)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    N, R, K = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    stacks: dict = {}
    for l, (attention, name) in enumerate(layer_kinds(cfg)):
        stacks.setdefault(name, (attention, []))[1].append(l)
    res = residual_scale(cfg)

    def one_layer(k, shapes):
        out = {}
        for kk, (leaf, shape) in zip(jax.random.split(k, len(shapes)),
                                     sorted(shapes.items())):
            if leaf == "A_log":
                # S4D-real: a = -(1 .. N) in every channel
                out[leaf] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], shape)
            elif leaf == "dt_bias":
                # softplus^-1 of a step size log-uniform in [1e-3, 1e-1]
                dt0 = jnp.exp(jax.random.uniform(
                    kk, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[leaf] = dt0 + jnp.log(-jnp.expm1(-dt0))
            elif leaf == "ssm_d":
                out[leaf] = jnp.ones(shape, jnp.float32)
            elif leaf in _F32:
                out[leaf] = 1.0 + 0.1 * jax.random.normal(kk, shape,
                                                          jnp.float32)
            elif leaf == "w_dt":
                out[leaf] = jax.random.uniform(
                    kk, shape, jnp.float32, -R ** -0.5,
                    R ** -0.5).astype(jnp.bfloat16)
            elif leaf == "conv_b":
                out[leaf] = jax.random.uniform(
                    kk, shape, jnp.float32, -K ** -0.5,
                    K ** -0.5).astype(jnp.bfloat16)
            else:
                # the filter N(0, 1/taps) a tap; He-normal elsewhere
                std = K ** -0.5 if leaf == "w_conv" \
                    else (2.0 / shape[-2]) ** 0.5
                if leaf in _RESIDUAL_OUT:
                    std *= res
                out[leaf] = jax.random.normal(kk, shape, jnp.bfloat16) \
                    * jnp.bfloat16(std)
        return out

    layers = {}
    for name, (attention, ls) in sorted(stacks.items()):
        shapes = leaf_shapes(cfg, attention)
        # one layer at a time: the random bits of a whole stack never
        # exist at once
        keys = jnp.stack([jax.random.fold_in(k_layers, l) for l in ls])
        layers[name] = jax.jit(lambda ks, s=shapes: jax.lax.map(
            lambda k: one_layer(k, s), ks))(keys)
    embed = jax.jit(lambda k: jax.random.normal(
        k, (V, D), jnp.bfloat16) * jnp.bfloat16(EMBED_STD))(k_embed)
    return {
        "embed": embed,
        "layers": layers,
        # the tied head: a gain of magnitude sqrt(2 / D) / EMBED_STD gives
        # the logits the scale an untied He-normal head gives, and of
        # RANDOM SIGN: under gains of one sign the token's own embedding,
        # which dominates the stream, gives its own logit e . e = D against
        # a spread of sqrt(D), and on the chip every one of 64 greedy
        # answers repeated one token (PERF.md section 6)
        "ln_out": (2.0 / D) ** 0.5 / EMBED_STD * jax.random.rademacher(
            k_sign, (D,), jnp.float32) * (1.0 + 0.1 * jax.random.normal(
                k_norm, (D,), jnp.float32)),
        "lm_head": jax.jit(lambda e: e.T)(embed),
    }


def register(name: str, cfg: dict, tree) -> None:
    """Zoo entry ``name``: the program's patterned decoder over ``tree``.
    The one function here that imports the program.  The program's
    description of the model is built HERE, not when the pipeline opens:
    a program that lacks the state-space layer kind refuses the
    configuration at once, before anything is compiled."""
    from nnstreamer_tpu.core.types import TensorFormat, TensorsSpec
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    def config(max_seq: int):
        return llama.LlamaConfig(
            vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            ffn_hidden=cfg["intermediate_size"], max_seq=max_seq,
            norm_eps=cfg["rms_norm_eps"],
            ssm_expand=cfg["mamba_expand"], ssm_state=cfg["mamba_d_state"],
            ssm_dt_rank=cfg["mamba_dt_rank"],
            ssm_conv_taps=cfg["mamba_d_conv"],
            pattern=tuple(llama.LayerKind(rope=False) if attention
                          else llama.LayerKind(ssm=True)
                          for attention, _key in layer_kinds(cfg)))

    config(cfg["serve"]["max_seq"])

    def build(opts):
        lcfg = config(int(opts.get("max_seq", cfg["serve"]["max_seq"])))
        dtype = opts.get("dtype", cfg["precision"]["compute"])
        bundle = ModelBundle(
            apply_fn=lambda p, t: llama.forward(p, t, lcfg,
                                                compute_dtype=dtype),
            params=tree,
            in_spec=TensorsSpec.from_string("1:1", "int32").replace(
                format=TensorFormat.FLEXIBLE),
            out_spec=TensorsSpec.from_string(
                f"{lcfg.vocab}:1:1", "float32").replace(
                format=TensorFormat.FLEXIBLE),
            param_pspecs=None, name=name)
        bundle.config = lcfg
        return bundle

    register_model(name, build)


def pipeline_options(cfg: dict) -> list:
    """Nothing beyond the deployment's sizes: bfloat16 weights are the
    program's default and the state is float32 whatever they are."""
    if cfg["precision"]["weights"] != "bfloat16" \
            or cfg["precision"]["ssm_state"] != "float32":
        raise ValueError("the state-space layers are served with bfloat16 "
                         "weights over a float32 state only")
    return []


# -- counts -------------------------------------------------------------------

def n_ssm_layers(cfg: dict) -> int:
    return sum(1 for a, _k in layer_kinds(cfg) if not a)


def n_attention_layers(cfg: dict) -> int:
    return sum(1 for a, _k in layer_kinds(cfg) if a)


def matmul_params_per_token(cfg: dict) -> int:
    """Weights one token is multiplied through: every layer's mixer
    matrices and FFN, and the head (the filter and the state's own
    numbers are no matrix)."""
    D, E = cfg["hidden_size"], inner(cfg)
    N, R = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    ssm = 2 * D * E + E * (R + 2 * N) + R * E + E * D
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    hkv = cfg["num_key_value_heads"] * head_dim(cfg)
    attn = 2 * D * hq + 2 * D * hkv
    return (D * cfg["vocab_size"]
            + n_ssm_layers(cfg) * ssm + n_attention_layers(cfg) * attn
            + cfg["num_hidden_layers"] * 3 * D * cfg["intermediate_size"])


def flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs to process one token that attends to ``context``
    positions: 2 a weight, QK^T and PV over the whole context on the
    attention layers, and a state-space layer's filter (2 a tap a
    channel) and recurrence (decay, update and the C contraction: 6 an
    element of the state), which no context length changes."""
    E, N = inner(cfg), cfg["mamba_d_state"]
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    return (2 * matmul_params_per_token(cfg)
            + 4 * hq * n_attention_layers(cfg) * context
            + n_ssm_layers(cfg) * (6 * N * E + 2 * cfg["mamba_d_conv"] * E))


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's float32 state over the state-space layers (8,519,680)."""
    return n_ssm_layers(cfg) * cfg["mamba_d_state"] * inner(cfg) * 4


def conv_bytes_per_slot(cfg: dict) -> int:
    """A slot's filter columns over the state-space layers, bfloat16
    (798,720)."""
    return n_ssm_layers(cfg) * (cfg["mamba_d_conv"] - 1) * inner(cfg) * 2


def ssm_update_bytes(cfg: dict, rows: float) -> float:
    """Bytes ONE call of the decode step's update kernel (one state-space
    layer) has to move for ``rows`` live rows: each row's state read and
    written (float32, 327,680 B each way), its ``x``, ``z`` and ``y``
    (bfloat16), ``dt`` (float32) and ``B``, ``C`` (float32); ``A`` and
    ``D`` once (float32).  41.9 MB at 64 rows."""
    E, N = inner(cfg), cfg["mamba_d_state"]
    row = 2 * N * E * 4 + 3 * E * 2 + E * 4 + 2 * N * 4
    return rows * row + N * E * 4 + E * 4


def kv_bytes_attended(cfg: dict, context: float) -> float:
    """K and V bytes one decoded token's attention reads across the
    attention layers: ``context`` positions x K and V x KV heads x head
    width x 2 bytes (bfloat16 cache) a layer — 512 B a position a layer at
    the published widths."""
    row = 2 * cfg["num_key_value_heads"] * head_dim(cfg) * 2
    return n_attention_layers(cfg) * row * context


def step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes a decode step has to move at ``rows`` live rows whose
    contexts sum to ``context``: every matrix of the layers and the head
    once (the embedding is gathered, a row a stream: not counted), the
    state-space layers' state and filter columns of each row read and
    written, and the K/V of the live context.  Activations are left out,
    so this is a floor."""
    weights = tree_bytes(cfg) - cfg["vocab_size"] * cfg["hidden_size"] * 2
    state = 2 * rows * (state_bytes_per_slot(cfg) + conv_bytes_per_slot(cfg))
    return weights + state + kv_bytes_attended(cfg, context)
