"""A decoder with a layer pattern — window and full attention mixed, a
dense first layer and sparse-expert layers after it, q/k norm, heads wider
than hidden / heads — as the ``serve`` driver meets it: the weights, the
registration with the program, what the pipeline string states, FLOPs per
token, and the control.  Its reference is
``reference/moe_hybrid_decoder.py``, which imports nothing from here.

Everything reads the configuration's file; no model's name appears.
Weights and counts import nothing of the program: ``register`` alone
does.
"""

from __future__ import annotations

from benchmark.traffic import jax_seed

ZOO_NAME = "bench_moe_hybrid_decoder"
#: the reference one precision below the bfloat16 the family states
CONTROL = {"weight_dtype": "float8_e4m3fn"}

#: leaves that stay float32 (gains; the router, so that a choice among
#: near-equal scores does not hang on bf16 rounding)
_F32 = ("ln_attn", "ln_mlp", "q_norm", "k_norm", "w_router", "router_bias")
#: the matrices that write into the residual stream
_RESIDUAL_OUT = ("wo", "w_down", "we_down", "ws_down")


def residual_scale(cfg: dict) -> float:
    """What the output projections of the residual branches are scaled by
    at initialisation: ``1 / sqrt(2 N)`` for the N layers of the PUBLISHED
    depth (two branches a layer), the GPT-2 convention.  Without it every
    branch adds as much variance as the stream holds, the token's own
    embedding is swamped after two layers, all rows of a batch carry much
    the same hidden state, and the router sends them to the same few
    experts: 12.7-13.3 of 16 held experts hit a step, up to 57 of 64 rows
    on one expert, and a step's time that moves with the seed by 2.7 %
    (my chip runs, PR 29).  A trained router is load-balanced (the
    correction bias exists for that); with the stream dominated by the
    token, random weights route as evenly as one."""
    return (2.0 * cfg["published"]["num_hidden_layers"]) ** -0.5


def layer_kinds(cfg: dict) -> list:
    """For each layer ``(window, rotated, ffn, stack key)``; the key is
    the program's checkpoint layout for a patterned model
    (``nnstreamer_tpu/models/llama.py`` ``LayerKind.name``)."""
    rotated = set(cfg["assumed"]["rope_layers"]["value"])
    out = []
    for l in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][l]
        window = cfg["sliding_windows"][l]
        rope = kind in rotated
        ffn = {"dense": "dense", "sparse": "experts"}[
            cfg["mlp_layer_types"][l]]
        out.append((window, rope, ffn, ".".join([
            f"window{window}" if window else "full",
            "rope" if rope else "nope", ffn])))
    return out


def leaf_shapes(cfg: dict, ffn: str) -> dict:
    """Leaf -> shape of one layer; matrices ``[in, out]``, experts
    ``[held, in, out]``."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    hq = cfg["num_attention_heads"] * hd
    hkv = cfg["num_key_value_heads"] * hd
    out = {"wq": (D, hq), "wk": (D, hkv), "wv": (D, hkv), "wo": (hq, D),
           "ln_attn": (D,), "ln_mlp": (D,), "q_norm": (hd,),
           "k_norm": (hd,)}
    if ffn == "dense":
        F = cfg["intermediate_size"]
        out.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
    else:
        E, Fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        Fs = cfg["num_shared_experts"] * Fe
        out.update(w_router=(D, cfg["published"]["num_experts"]),
                   router_bias=(cfg["published"]["num_experts"],),
                   we_gate=(E, D, Fe), we_up=(E, D, Fe), we_down=(E, Fe, D),
                   ws_gate=(D, Fs), ws_up=(D, Fs), ws_down=(Fs, D))
    return out


def weights(cfg: dict, seed: int):
    """The tree of the configuration's share, made on the device in the
    type it is served in: bfloat16 matrices (He-normal) and embedding,
    float32 gains near 1, a float32 router whose scores spread over
    (0, 1), a correction bias that is small and not zero."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(jax_seed(seed, "moe_hybrid_decoder"))
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    stacks: dict = {}
    for l, (_w, _r, ffn, name) in enumerate(layer_kinds(cfg)):
        stacks.setdefault(name, (ffn, []))[1].append(l)

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
                std = (2.0 / shape[-2]) ** 0.5 * (
                    res if leaf in _RESIDUAL_OUT else 1.0)
                out[leaf] = jax.random.normal(kk, shape, jnp.bfloat16) \
                    * jnp.bfloat16(std)
        return out

    layers = {}
    for name, (ffn, ls) in sorted(stacks.items()):
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
    The one function here that imports the program."""
    from nnstreamer_tpu.core.types import TensorFormat, TensorsSpec
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.models.moe import ExpertsConfig
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    def build(opts):
        lcfg = llama.LlamaConfig(
            vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            ffn_hidden=cfg["intermediate_size"],
            max_seq=int(opts.get("max_seq", cfg["serve"]["max_seq"])),
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            norm_eps=cfg["rms_norm_eps"], head_size=cfg["head_dim"],
            qk_norm=True,
            pattern=tuple(llama.LayerKind(window=w, rope=r, ffn=f)
                          for w, r, f, _name in layer_kinds(cfg)),
            experts=ExpertsConfig(
                n_experts=cfg["published"]["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                hidden=cfg["moe_intermediate_size"],
                shared=cfg["num_shared_experts"],
                scoring=cfg["scoring_func"],
                norm_topk=cfg["norm_topk_prob"],
                scale=cfg["routed_scaling_factor"],
                held_first=cfg["deployment"]["held_first"],
                held_count=cfg["num_experts"]))
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
        raise ValueError("expert matrices are served in bfloat16 only")
    return []


def matmul_params_per_token(cfg: dict) -> float:
    """Weights one token is multiplied through on THIS chip, in
    expectation over the router's choice: each layer's four attention
    matrices; a dense layer's three; a sparse layer's router, shared
    expert, and ``top_k * held / routed`` of its held experts (a token's
    k choices fall on a held expert with that frequency when the router
    spreads evenly, which random weights do: 8 * 16 / 128 = 1.0 expert);
    the head."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    hq = cfg["num_attention_heads"] * hd
    hkv = cfg["num_key_value_heads"] * hd
    attn = D * hq + 2 * D * hkv + hq * D
    expert = 3 * D * cfg["moe_intermediate_size"]
    routed = cfg["published"]["num_experts"]
    sparse = (D * routed + cfg["num_shared_experts"] * expert
              + cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
              * expert)
    total = D * cfg["vocab_size"]
    for _w, _r, ffn, _n in layer_kinds(cfg):
        total += attn + (3 * D * cfg["intermediate_size"]
                         if ffn == "dense" else sparse)
    return total


def flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs to process one token that attends to ``context``
    positions: 2 a weight (``matmul_params_per_token``: an EXPECTATION
    over the expert choice), plus QK^T and PV over what each layer
    attends — the whole context on a full layer, at most the window on a
    window layer."""
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    attended = sum(min(context, w) if w else context
                   for w, _r, _f, _n in layer_kinds(cfg))
    return 2 * matmul_params_per_token(cfg) + 4 * hq * attended


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE held expert's three matrices as served."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def kv_bytes_attended(cfg: dict, context: float) -> float:
    """K and V bytes one decoded token's attention reads across all
    layers: per layer ``min(context, window)`` positions x K and V x KV
    heads x head width x 2 bytes (bfloat16 cache)."""
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    return row * sum(min(context, w) if w else context
                     for w, _r, _f, _n in layer_kinds(cfg))


def n_sparse_layers(cfg: dict) -> int:
    return sum(1 for _w, _r, ffn, _n in layer_kinds(cfg)
               if ffn == "experts")
