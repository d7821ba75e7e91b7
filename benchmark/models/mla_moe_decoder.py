"""A decoder of latent attention under YaRN in every layer, leading dense
layers, then sparse-expert layers whose sigmoid router chooses among the
best groups of experts beside a shared expert — as the ``serve`` driver
meets it: the weights, the registration with the program, what the
pipeline string states, FLOPs per token, the control, and the counts its
readers under ``layer_metrics/`` divide by (under the common names
``expert_bytes``, ``n_expert_layers``, ``held_experts``,
``experts_per_token``).  Its reference is ``reference/mla_moe_decoder.py``,
which imports nothing from here.

Everything reads the configuration's file; no model's name appears.
Weights and counts import nothing of the program: ``register`` alone
does.

**The tree.**  The program's checkpoint layout keeps one stack a layer
kind (``nnstreamer_tpu/models/llama.py`` ``LayerKind.name``): ``DENSE``
holds the leading dense layers, ``EXPERTS`` the sparse ones, each leaf with
the layers of its kind on the leading axis; matrices are ``[in, out]``,
experts ``[held, in, out]``.
"""

from __future__ import annotations

from benchmark.traffic import jax_seed

ZOO_NAME = "bench_mla_moe_decoder"
#: the reference one precision below the bfloat16 the family states
CONTROL = {"weight_dtype": "float8_e4m3fn"}

#: the two layer kinds' stack keys
DENSE, EXPERTS = "latent.rope.dense", "latent.rope.experts"

#: leaves that stay float32 (gains; the router, so that a choice among
#: near-equal scores does not hang on bf16 rounding)
_F32 = ("ln_attn", "ln_mlp", "q_a_norm", "kv_a_norm", "w_router",
        "router_bias")
#: the matrices that write into the residual stream
_RESIDUAL_OUT = ("wo", "w_down", "we_down", "ws_down")
#: standard deviation of the correction bias (``assumed.weights``): the
#: top 8 of the 96 scores a token's kept groups offer lie ≈ 0.01 apart,
#: so K-EXAONE's 0.02 would steer the choice by the seed — 10.68 to 10.98
#: of 12 held experts hit a step where an even router hits 11.2 — and a
#: cell's speed with it
ROUTER_BIAS_STD = 0.002
#: the attention block's five matrices
_ATTENTION = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")


def residual_scale(cfg: dict) -> float:
    """``1 / sqrt(2 N)`` for the N layers of the PUBLISHED depth: two
    branches a layer write into the stream (the GPT-2 convention;
    ``models/moe_hybrid_decoder.py residual_scale`` says what happens
    without it)."""
    return (2.0 * cfg["published"]["num_hidden_layers"]) ** -0.5


def layer_ffns(cfg: dict) -> list:
    """``dense`` or ``experts`` for each layer run here."""
    first = cfg["first_k_dense_replace"]
    return ["dense" if l < first else "experts"
            for l in range(cfg["num_hidden_layers"])]


def leaf_shapes(cfg: dict, ffn: str) -> dict:
    """Leaf -> shape of one layer of the feed-forward kind ``ffn``."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    out = {"ln_attn": (D,), "ln_mlp": (D,),
           "wq_a": (D, rq), "q_a_norm": (rq,), "wq_b": (rq, H * (dn + dr)),
           "wkv_a": (D, rkv + dr), "kv_a_norm": (rkv,),
           "wkv_b": (rkv, H * (dn + dv)), "wo": (H * dv, D)}
    if ffn == "dense":
        F = cfg["intermediate_size"]
        out.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
    else:
        E, Fe = held_experts(cfg), cfg["moe_intermediate_size"]
        R = cfg["published"]["n_routed_experts"]
        Fs = cfg["n_shared_experts"] * Fe
        out.update(w_router=(D, R), router_bias=(R,),
                   we_gate=(E, D, Fe), we_up=(E, D, Fe), we_down=(E, Fe, D),
                   ws_gate=(D, Fs), ws_up=(D, Fs), ws_down=(Fs, D))
    return out


def weights(cfg: dict, seed: int):
    """The tree of the configuration's share, made on the device in the
    type it is served in (``assumed.weights`` says each word)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(jax_seed(seed, "mla_moe_decoder"))
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    res = residual_scale(cfg)

    def one_layer(k, shapes):
        out = {}
        for kk, (leaf, shape) in zip(jax.random.split(k, len(shapes)),
                                     sorted(shapes.items())):
            if leaf == "router_bias":
                out[leaf] = ROUTER_BIAS_STD * jax.random.normal(
                    kk, shape, jnp.float32)
            elif leaf == "w_router":
                out[leaf] = jax.random.normal(kk, shape, jnp.float32) \
                    * (shape[0] ** -0.5)
            elif leaf in _F32:
                out[leaf] = 1.0 + 0.1 * jax.random.normal(kk, shape,
                                                          jnp.float32)
            else:
                std = (2.0 / shape[-2]) ** 0.5 * (
                    res if leaf in _RESIDUAL_OUT else 1.0)
                out[leaf] = jax.random.normal(kk, shape, jnp.bfloat16) \
                    * jnp.bfloat16(std)
        return out

    layers = {}
    ffns = layer_ffns(cfg)
    for name, ffn in ((DENSE, "dense"), (EXPERTS, "experts")):
        ls = [l for l, f in enumerate(ffns) if f == ffn]
        if not ls:
            continue
        shapes = leaf_shapes(cfg, ffn)
        # one layer at a time: the random bits of a whole stack never
        # exist at once
        keys = jnp.stack([jax.random.fold_in(k_layers, l) for l in ls])
        layers[name] = jax.jit(lambda ks, s=shapes: jax.lax.map(
            lambda k: one_layer(k, s), ks))(keys)
    return {
        # unit variance: the stream a token enters with is its own
        "embed": jax.jit(lambda k: jax.random.normal(
            k, (V, D), jnp.bfloat16))(k_embed),
        "layers": layers,
        "ln_out": 1.0 + 0.1 * jax.random.normal(k_norm, (D,), jnp.float32),
        "lm_head": jax.jit(lambda k: jax.random.normal(
            k, (D, V), jnp.bfloat16) * jnp.bfloat16((2.0 / D) ** 0.5)
        )(k_head),
    }


def register(name: str, cfg: dict, tree) -> None:
    """Zoo entry ``name``: the program's patterned decoder over ``tree``.
    The one function here that imports the program.  The program's
    description of the model is built HERE, not when the pipeline opens:
    a program that lacks group-limited routing or YaRN refuses the
    configuration at once, before anything is compiled."""
    from nnstreamer_tpu.core.types import TensorFormat, TensorsSpec
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.models.moe import ExpertsConfig
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    a, rs = cfg["assumed"], cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope scaling {rs['type']!r}: yarn is built")

    def config(max_seq: int):
        return llama.LlamaConfig(
            vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"], n_kv_heads=1,
            ffn_hidden=cfg["intermediate_size"], max_seq=max_seq,
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=cfg["rms_norm_eps"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            rope_scaling=llama.RopeScaling(
                factor=float(rs["factor"]),
                original_max_position=rs["original_max_position_embeddings"],
                beta_fast=float(rs["beta_fast"]),
                beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]),
                mscale_all_dim=float(rs["mscale_all_dim"])),
            pattern=tuple(llama.LayerKind(latent=True, ffn=f)
                          for f in layer_ffns(cfg)),
            experts=ExpertsConfig(
                n_experts=cfg["published"]["n_routed_experts"],
                top_k=cfg["num_experts_per_tok"],
                hidden=cfg["moe_intermediate_size"],
                shared=cfg["n_shared_experts"],
                scoring=cfg["scoring_func"],
                norm_topk=cfg["norm_topk_prob"],
                norm_eps=a["renorm_eps"]["value"],
                scale=float(cfg["routed_scaling_factor"]),
                n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                held_first=cfg["deployment"]["held_first"],
                held_count=held_experts(cfg)))

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
        raise ValueError("the latent projections and expert matrices are "
                         "served in bfloat16 only")
    return []


def held_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def n_expert_layers(cfg: dict) -> int:
    return layer_ffns(cfg).count("experts")


def attention_params(cfg: dict) -> int:
    """One attention block's five matrices (101,122,048 at the published
    widths)."""
    s = leaf_shapes(cfg, "dense")
    return sum(s[m][0] * s[m][1] for m in _ATTENTION)


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE held expert's three matrices as served."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def weight_bytes(cfg: dict) -> int:
    """Bytes of every matrix of the tree as served (bfloat16, the router
    float32), the gains and the correction bias left out: 11,051,728,896
    at the cell's cut."""
    total = 2 * 2 * cfg["vocab_size"] * cfg["hidden_size"]
    for ffn in layer_ffns(cfg):
        for leaf, shape in leaf_shapes(cfg, ffn).items():
            if len(shape) > 1:
                n = 1
                for d in shape:
                    n *= d
                total += n * (4 if leaf in _F32 else 2)
    return total


def matmul_params_per_token(cfg: dict) -> float:
    """Weights one token is multiplied through on THIS chip, in
    expectation over the router's choice: each layer's attention block
    (``wkv_b`` counted once, for the token's own latent: the per-head
    form); a dense layer's three matrices; an expert layer's router,
    shared expert and ``top_k x held / routed`` of a held expert (8 x 12
    / 192 = 0.5 of one where the router spreads evenly); the head."""
    D = cfg["hidden_size"]
    expert = 3 * D * cfg["moe_intermediate_size"]
    routed = cfg["published"]["n_routed_experts"]
    sparse = (D * routed + cfg["n_shared_experts"] * expert
              + experts_per_token(cfg) * held_experts(cfg) / routed * expert)
    total = D * cfg["vocab_size"]
    for ffn in layer_ffns(cfg):
        total += attention_params(cfg) + (
            3 * D * cfg["intermediate_size"] if ffn == "dense" else sparse)
    return total


def flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs to process one token that attends to ``context``
    positions: 2 a weight (``matmul_params_per_token``), plus attention
    in its CHEAPER, per-head form — ``heads x 2 x ((nope + rope) + v)`` a
    position a layer, 40,960 at the published widths — not the absorbed
    form the decode kernel executes, so the share of the peak cannot be
    flattered by the form chosen."""
    per_pos = cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return (2 * matmul_params_per_token(cfg)
            + cfg["num_hidden_layers"] * per_pos * context)

