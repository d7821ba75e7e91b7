"""A decoder of latent attention and shortcut-connected sparse experts —
two attention blocks and two dense feed-forwards a published layer, the
expert branch taken off after the first attention and added back at the
layer's end, a softmax router whose outputs past the routed experts are
identity (zero-compute) experts — as the ``serve`` driver meets it: the
weights, the registration with the program, what the pipeline string
states, FLOPs per token, the control, and the counts its readers under
``layer_metrics/`` divide by.  Its reference is
``reference/scmoe_latent_decoder.py``, which imports nothing from here.

Everything reads the configuration's file; no model's name appears.
Weights and counts import nothing of the program: ``register`` alone
does.

**The tree.**  A published layer is two sub-layers, and the program's
checkpoint layout keeps one stack a sub-layer kind
(``nnstreamer_tpu/models/llama.py`` ``LayerKind.name``): ``OPEN`` holds,
for each published layer, its first attention block, its first dense
FFN, the router and the held experts; ``CLOSE`` its second attention
block and second dense FFN.  Matrices are ``[in, out]``, experts
``[held, in, out]``, each leaf with the published layers on its leading
axis.
"""

from __future__ import annotations

from benchmark.traffic import jax_seed

ZOO_NAME = "bench_scmoe_latent_decoder"
#: the reference one precision below the bfloat16 the family states
CONTROL = {"weight_dtype": "float8_e4m3fn"}

#: the two sub-layer kinds' stack keys
OPEN, CLOSE = "latent.rope.dense.open", "latent.rope.dense.close"

#: leaves that stay float32 (gains; the router, so that a choice among
#: near-equal scores does not hang on bf16 rounding)
_F32 = ("ln_attn", "ln_mlp", "q_a_norm", "kv_a_norm", "w_router",
        "router_bias")
#: the matrices that write into the residual stream
_RESIDUAL_OUT = ("wo", "w_down", "we_down")
#: standard deviation of the router's logits (``assumed.weights``)
ROUTER_LOGIT_STD = 2.0


def residual_scale(cfg: dict) -> float:
    """``1 / sqrt(5 N)`` for the N published layers of the PUBLISHED
    depth: four dense branches (two attention blocks, two FFNs) and the
    expert branch write into the stream a layer (the GPT-2 convention;
    ``models/moe_hybrid_decoder.py residual_scale`` says what happens
    without it)."""
    return (5.0 * cfg["published"]["num_layers"]) ** -0.5


def router_outputs(cfg: dict) -> int:
    """Routed experts of the published model, then identity experts."""
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def latent_width(cfg: dict) -> int:
    """Values one attention block caches a token."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def leaf_shapes(cfg: dict, kind: str) -> dict:
    """Leaf -> shape of one sub-layer of ``kind``."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    F = cfg["ffn_hidden_size"]
    out = {"ln_attn": (D,), "ln_mlp": (D,),
           "wq_a": (D, rq), "q_a_norm": (rq,), "wq_b": (rq, H * (dn + dr)),
           "wkv_a": (D, rkv + dr), "kv_a_norm": (rkv,),
           "wkv_b": (rkv, H * (dn + dv)), "wo": (H * dv, D),
           "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    if kind == OPEN:
        E, Fe = cfg["n_routed_experts"], cfg["expert_ffn_hidden_size"]
        R = router_outputs(cfg)
        out.update(w_router=(D, R), router_bias=(R,),
                   we_gate=(E, D, Fe), we_up=(E, D, Fe), we_down=(E, Fe, D))
    return out


def weights(cfg: dict, seed: int):
    """The tree of the configuration's share, made on the device in the
    type it is served in (``assumed.weights`` says each word)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(jax_seed(seed, "scmoe_latent_decoder"))
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_layers"]
    res = residual_scale(cfg)

    def one_layer(k, shapes):
        out = {}
        for kk, (leaf, shape) in zip(jax.random.split(k, len(shapes)),
                                     sorted(shapes.items())):
            if leaf == "router_bias":
                # it is added to softmax probabilities (1 / outputs on
                # average, the chosen ones 10-90 x that): half the
                # average, or it would make every token's choice
                out[leaf] = (0.5 / shape[0]) * jax.random.normal(
                    kk, shape, jnp.float32)
            elif leaf == "w_router":
                out[leaf] = jax.random.normal(kk, shape, jnp.float32) \
                    * (ROUTER_LOGIT_STD * shape[0] ** -0.5)
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
    for i, kind in enumerate((OPEN, CLOSE)):
        shapes = leaf_shapes(cfg, kind)
        # one sub-layer at a time: the random bits of a whole stack
        # never exist at once
        keys = jnp.stack([jax.random.fold_in(k_layers, 2 * l + i)
                          for l in range(L)])
        layers[kind] = jax.jit(lambda ks, s=shapes: jax.lax.map(
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
    a program that lacks latent attention or the shortcut refuses the
    configuration at once, before anything is compiled."""
    from nnstreamer_tpu.core.types import TensorFormat, TensorsSpec
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.models.moe import ExpertsConfig
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    a = cfg["assumed"]

    def config(max_seq: int):
        return llama.LlamaConfig(
            vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=2 * cfg["num_layers"],
            n_heads=cfg["num_attention_heads"], n_kv_heads=1,
            ffn_hidden=cfg["ffn_hidden_size"], max_seq=max_seq,
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=cfg["rms_norm_eps"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            q_lora_scale=a["mla_scale_q_lora"]["value"],
            kv_lora_scale=a["mla_scale_kv_lora"]["value"],
            pattern=tuple(
                llama.LayerKind(latent=True,
                                shortcut="close" if l % 2 else "open")
                for l in range(2 * cfg["num_layers"])),
            experts=ExpertsConfig(
                n_experts=cfg["published"]["n_routed_experts"],
                top_k=cfg["moe_topk"],
                hidden=cfg["expert_ffn_hidden_size"], shared=0,
                scoring="softmax",
                norm_topk=a["norm_topk_prob"]["value"],
                scale=float(cfg["routed_scaling_factor"]),
                zero_experts=cfg["zero_expert_num"],
                held_first=cfg["deployment"]["held_first"],
                held_count=cfg["n_routed_experts"]))

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


def n_attention_blocks(cfg: dict) -> int:
    return 2 * cfg["num_layers"]


def n_expert_layers(cfg: dict) -> int:
    return cfg["num_layers"]


def attention_params(cfg: dict) -> int:
    """One attention block's five matrices (90,570,752 at the published
    widths): both compressions, both expansions, the output."""
    s = leaf_shapes(cfg, CLOSE)
    return sum(s[m][0] * s[m][1]
               for m in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))


def matmul_params_per_token(cfg: dict) -> float:
    """Weights one token is multiplied through on THIS chip, in
    expectation over the router's choice: per published layer two
    attention blocks (``wkv_b`` counted once, for the token's own latent:
    the per-head form), two dense FFNs, the router, and ``top_k x held /
    router outputs`` of a held expert (12 x 16 / 768 = 0.25 of one: a
    third of the choices are identity experts and multiply nothing); the
    head."""
    D = cfg["hidden_size"]
    expert = 3 * D * cfg["expert_ffn_hidden_size"]
    layer = (2 * attention_params(cfg) + 2 * 3 * D * cfg["ffn_hidden_size"]
             + D * router_outputs(cfg)
             + cfg["moe_topk"] * cfg["n_routed_experts"]
             / router_outputs(cfg) * expert)
    return cfg["num_layers"] * layer + D * cfg["vocab_size"]


def flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs to process one token that attends to ``context``
    positions: 2 a weight (``matmul_params_per_token``), plus attention
    in its CHEAPER, per-head form — ``heads x 2 x ((nope + rope) + v)`` a
    position a block, 40,960 at the published widths — not the absorbed
    form's ``heads x 2 x (576 + 512)`` that the decode kernel executes,
    so the share of the peak cannot be flattered by the form chosen."""
    per_pos = cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return (2 * matmul_params_per_token(cfg)
            + n_attention_blocks(cfg) * per_pos * context)


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE held expert's three matrices as served."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"] * 2


def latent_bytes_attended(cfg: dict, context: float) -> float:
    """Cache bytes one decoded token's attention has to read across all
    attention blocks: ``context`` positions x ``latent_width`` values x
    2 bytes (bfloat16) a block, each row read ONCE for scores and values
    alike.  The pool's rows are padded to the 128 lanes (576 -> 640); the
    padding is not counted: it is the kernel's cost, and lowers its
    share."""
    return n_attention_blocks(cfg) * context * latent_width(cfg) * 2
