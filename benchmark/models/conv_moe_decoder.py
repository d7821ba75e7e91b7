"""A decoder whose layers mix two kinds of token mixer — a gated short
convolution whose state is a fixed few columns a stream, or causal
attention over a paged K/V cache at a head width of hidden / heads — over a
dense feed-forward (the leading layers) or sparse experts of which EVERY
one is held, as the ``serve`` driver meets it: the weights, the
registration with the program, what the pipeline string states, FLOPs per
token, the control, and the counts its readers under ``layer_metrics/``
divide by.  Its reference is ``reference/conv_moe_decoder.py``, which
imports nothing from here.

Everything reads the configuration's file; no model's name appears.
Weights and counts import nothing of the program: ``register`` alone
does.

**The tree.**  One stack a layer kind, keyed as the program's checkpoint
layout keys them (``nnstreamer_tpu/models/llama.py`` ``LayerKind.name``):
``conv.dense``, ``conv.experts``, ``full.rope.experts`` (and
``full.rope.dense`` where a leading layer is attention), the kind's layers
in order on the leading axis.  Matrices are ``[in, out]``, experts ``[n,
in, out]``, the convolution's filter ``[taps, hidden]``.  The head is the
embedding's transposed copy (``assumed.tie_word_embeddings``).
"""

from __future__ import annotations

import math

from benchmark.traffic import jax_seed

ZOO_NAME = "bench_conv_moe_decoder"
#: the reference one precision below the bfloat16 the family states
CONTROL = {"weight_dtype": "float8_e4m3fn"}

#: leaves that stay float32 (gains; the router, so that a choice among
#: near-equal scores does not hang on bf16 rounding)
_F32 = ("ln_attn", "ln_mlp", "q_norm", "k_norm", "w_router", "router_bias")
#: the matrices that write into the residual stream
_RESIDUAL_OUT = ("w_out", "wo", "w_down", "we_down")


#: what the embedding's rows are drawn at (a standard deviation): the
#: stream a token enters with.  Half of the other sparse configurations'
#: 1: the branches of ten layers at the PUBLISHED depth's scale then add
#: twice the embedding's variance, as they would at the depth run with an
#: embedding of 1, and the next token depends on what a stream carries —
#: at 1 it is all but a function of the last token and greedy streams fall
#: into cycles (5 to 164 distinct tokens in eight answers of 256: my chip
#: run, PR 35), in which the comparison sees the same few positions
EMBED_STD = 0.5
#: the experts' down projections, beside the other residual writers.  A
#: sparse layer's branch is four experts of 64, and WHICH four is a
#: discontinuous function of a bfloat16 hidden state: with every writer
#: alike the program's choice is not the reference's on 11 % of
#: token-layers, a swapped expert is a quarter of the branch, later layers
#: flip on what it moved, and those swaps — not arithmetic: 63 parts of 64
#: — are the program's gap, as large as float8's and as large as a fault
#: of the state this configuration adds.  At a quarter (a sixteenth of the
#: variance) the choices differ on 5 %, stop cascading, and the worst
#: stretch of 64 tokens falls from 0.088 to 0.004-0.006 (PERF.md §6, PR 35)
EXPERT_WRITER = 0.25


def residual_scale(cfg: dict) -> float:
    """``1 / sqrt(2 N)`` for the PUBLISHED depth N: a mixer and a
    feed-forward write into the stream a layer (the GPT-2 convention;
    ``models/moe_hybrid_decoder.py residual_scale`` says what happens to
    the router's spread without it), as the other two sparse
    configurations take it."""
    return (2.0 * cfg["published"]["num_hidden_layers"]) ** -0.5


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_kinds(cfg: dict) -> list:
    """For each layer run ``(is conv, ffn, stack key)``: the first
    ``num_hidden_layers`` entries of ``layer_types``, dense before
    ``num_dense_layers``."""
    out = []
    for l in range(cfg["num_hidden_layers"]):
        conv = {"conv": True, "full_attention": False}[cfg["layer_types"][l]]
        ffn = "dense" if l < cfg["num_dense_layers"] else "experts"
        out.append((conv, ffn,
                    f"{'conv' if conv else 'full.rope'}.{ffn}"))
    return out


def leaf_shapes(cfg: dict, conv: bool, ffn: str) -> dict:
    """Leaf -> shape of one layer."""
    D, hd = cfg["hidden_size"], head_dim(cfg)
    out = {"ln_attn": (D,), "ln_mlp": (D,)}
    if conv:
        out.update(w_in=(D, 3 * D), w_conv=(cfg["conv_L_cache"], D),
                   w_out=(D, D))
    else:
        hq = cfg["num_attention_heads"] * hd
        hkv = cfg["num_key_value_heads"] * hd
        out.update(wq=(D, hq), wk=(D, hkv), wv=(D, hkv), wo=(hq, D),
                   q_norm=(hd,), k_norm=(hd,))
    if ffn == "dense":
        F = cfg["intermediate_size"]
        out.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
    else:
        E, Fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        out.update(w_router=(D, E), router_bias=(E,), we_gate=(E, D, Fe),
                   we_up=(E, D, Fe), we_down=(E, Fe, D))
    return out


def tree_bytes(cfg: dict) -> int:
    """Bytes of the tree :func:`weights` makes, by shapes alone (the head's
    transposed copy counted: it is resident)."""
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] * 2 \
        + 4 * cfg["hidden_size"]
    for conv, ffn, _key in layer_kinds(cfg):
        for leaf, shape in leaf_shapes(cfg, conv, ffn).items():
            total += math.prod(shape) * (4 if leaf in _F32 else 2)
    return total


def weights(cfg: dict, seed: int):
    """The tree of the configuration, made on the device in the type it is
    served in (``assumed.weights`` says each word)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(jax_seed(seed, "conv_moe_decoder"))
    k_embed, k_norm, k_sign, k_layers = jax.random.split(key, 4)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    stacks: dict = {}
    for l, (conv, ffn, name) in enumerate(layer_kinds(cfg)):
        stacks.setdefault(name, (conv, ffn, []))[2].append(l)
    res = residual_scale(cfg)

    def one_layer(k, shapes):
        out = {}
        for kk, (leaf, shape) in zip(jax.random.split(k, len(shapes)),
                                     sorted(shapes.items())):
            if leaf == "router_bias":
                out[leaf] = 0.02 * jax.random.normal(kk, shape, jnp.float32)
            elif leaf == "w_router":
                out[leaf] = jax.random.normal(kk, shape, jnp.float32) \
                    * (shape[0] ** -0.5)
            elif leaf in _F32:
                out[leaf] = 1.0 + 0.1 * jax.random.normal(kk, shape,
                                                          jnp.float32)
            else:
                # b, c and v multiply one another: unit variance each; a
                # tap a third of the filter's; He-normal elsewhere
                std = {"w_in": shape[0] ** -0.5,
                       "w_conv": shape[0] ** -0.5}.get(
                    leaf, (2.0 / shape[-2]) ** 0.5)
                if leaf in _RESIDUAL_OUT:
                    std *= res * (EXPERT_WRITER if leaf == "we_down" else 1)
                out[leaf] = jax.random.normal(kk, shape, jnp.bfloat16) \
                    * jnp.bfloat16(std)
        return out

    layers = {}
    for name, (conv, ffn, ls) in sorted(stacks.items()):
        shapes = leaf_shapes(cfg, conv, ffn)
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
        # the tied head: a gain of magnitude sqrt(2 / D) / EMBED_STD, which
        # gives the logits the scale an untied He-normal head gives, and
        # of RANDOM SIGN.  The stream is dominated by the token's own embedding
        # (that keeps the router spread), so under gains of one sign
        # every token's largest logit would be its own, e . e = D against
        # a spread of sqrt(D): the served streams would repeat one token
        # and no precision could move the choice.  Signs of mean zero take
        # the systematic part out, as training does through the layers
        "ln_out": (2.0 / D) ** 0.5 / EMBED_STD * jax.random.rademacher(
            k_sign, (D,), jnp.float32) * (1.0 + 0.1 * jax.random.normal(
                k_norm, (D,), jnp.float32)),
        "lm_head": jax.jit(lambda e: e.T)(embed),
    }


def register(name: str, cfg: dict, tree) -> None:
    """Zoo entry ``name``: the program's patterned decoder over ``tree``.
    The one function here that imports the program.  The program's
    description of the model is built HERE, not when the pipeline opens:
    a program that lacks the convolution layer kind refuses the
    configuration at once, before anything is compiled."""
    from nnstreamer_tpu.core.types import TensorFormat, TensorsSpec
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.models.moe import ExpertsConfig
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    def config(max_seq: int):
        return llama.LlamaConfig(
            vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            ffn_hidden=cfg["intermediate_size"], max_seq=max_seq,
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            norm_eps=cfg["norm_eps"], qk_norm=True,
            conv_taps=cfg["conv_L_cache"],
            pattern=tuple(llama.LayerKind(conv=conv, ffn=ffn)
                          for conv, ffn, _name in layer_kinds(cfg)),
            experts=ExpertsConfig(
                n_experts=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                hidden=cfg["moe_intermediate_size"], shared=0,
                scoring="sigmoid", norm_topk=cfg["norm_topk_prob"],
                norm_eps=cfg["assumed"]["norm_topk_eps"]["value"],
                scale=float(cfg["routed_scaling_factor"])))

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
    program's default."""
    if cfg["precision"]["weights"] != "bfloat16":
        raise ValueError("the convolution layers and the expert matrices "
                         "are served in bfloat16 only")
    return []


# -- counts -------------------------------------------------------------------

def n_expert_layers(cfg: dict) -> int:
    return sum(1 for _c, ffn, _n in layer_kinds(cfg) if ffn == "experts")


def n_attention_layers(cfg: dict) -> int:
    return sum(1 for conv, _f, _n in layer_kinds(cfg) if not conv)


def held_experts(cfg: dict) -> int:
    """Every expert of a layer is held (``deployment``)."""
    return cfg["num_experts"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE expert's three matrices as served (18,874,368)."""
    return expert_params(cfg) * 2


def mixer_params(cfg: dict, conv: bool) -> int:
    """A mixer's matrices: ``w_in``, the filter and ``w_out``
    (16,783,360), or ``wq``, ``wk``, ``wv``, ``wo`` (10,485,760; the q/k
    norm gains are no matrix)."""
    D = cfg["hidden_size"]
    if conv:
        return 4 * D * D + cfg["conv_L_cache"] * D
    hkv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * D * D + 2 * D * hkv


def matmul_params_per_token(cfg: dict) -> float:
    """Weights one token is multiplied through: each layer's mixer; a
    dense layer's three matrices; a sparse layer's router and its
    ``num_experts_per_tok`` experts (all held, so every choice is
    computed here); the head."""
    D = cfg["hidden_size"]
    total = D * cfg["vocab_size"]
    for conv, ffn, _n in layer_kinds(cfg):
        total += mixer_params(cfg, conv)
        total += 3 * D * cfg["intermediate_size"] if ffn == "dense" else (
            D * cfg["num_experts"]
            + cfg["num_experts_per_tok"] * expert_params(cfg))
    return total


def flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs to process one token that attends to ``context``
    positions: 2 a weight, plus QK^T and PV over the whole context on the
    attention layers (a convolution layer's work does not grow with the
    context: its taps are among the weights)."""
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    return (2 * matmul_params_per_token(cfg)
            + 4 * hq * n_attention_layers(cfg) * context)


def step_weight_bytes(cfg: dict, experts_hit: float) -> float:
    """Bytes a decode step has to stream: the ``experts_hit`` experts its
    rows were routed to (summed over the sparse layers), and every other
    matrix of the layers and the head once — mixers, dense FFNs in
    bfloat16, routers in float32.  The embedding is gathered (a row a
    stream), the cache and the state are ``kv_bytes_attended``'s and a few
    MB: not counted, so this is a floor."""
    D = cfg["hidden_size"]
    rest = D * cfg["vocab_size"] * 2
    for conv, ffn, _n in layer_kinds(cfg):
        rest += mixer_params(cfg, conv) * 2
        rest += 3 * D * cfg["intermediate_size"] * 2 if ffn == "dense" \
            else D * cfg["num_experts"] * 4
    return experts_hit * expert_bytes(cfg) + rest


def kv_bytes_attended(cfg: dict, context: float) -> float:
    """K and V bytes one decoded token's attention reads across the
    attention layers: ``context`` positions x K and V x KV heads x head
    width x 2 bytes (bfloat16 cache) a layer — 2,048 B a position a layer
    at the published widths.  The convolution layers read none."""
    row = 2 * cfg["num_key_value_heads"] * head_dim(cfg) * 2
    return n_attention_layers(cfg) * row * context
