"""Weights from ``--seed``, made on the device in one jitted call each, in
the type they are served in.

The benchmark owns the weights: the program receives the tree the way a
checkpoint's would enter (through the zoo hook, ``benchmark/adapter.py``)
and the plain reference is handed the same arrays — neither takes anything
the other has made.  Leaf names and shapes are the checkpoint layout
``nnstreamer_tpu/models`` documents for both families.
"""

from __future__ import annotations

from . import flops
from .traffic import jax_seed


def decoder_tree(cfg: dict, seed: int):
    """Weight-only int8 decoder: for each matrix an int8 ``[in, out]``
    (stacked ``[L, in, out]`` for the blocks) uniform over [-127, 127] and
    a float32 per-output-channel scale that gives the product He-normal
    variance; bf16 embedding; norm gains near 1."""
    import jax

    key = jax.random.PRNGKey(jax_seed(seed, "decoder"))
    return jax.jit(_decoder_tree, static_argnums=(1, 2, 3, 4, 5, 6))(
        key, cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"] * cfg["head_dim"],
        cfg["num_key_value_heads"] * cfg["head_dim"],
        cfg["intermediate_size"], cfg["vocab_size"])


def _decoder_tree(key, L, D, HQ, HKV, F, V):
    import jax
    import jax.numpy as jnp

    #: std of an integer uniform over [-127, 127]
    q_std = (127 * 128 / 3) ** 0.5

    def qmat(k, fan_in, fan_out):
        kq, ks = jax.random.split(k)
        q = jax.random.randint(kq, (fan_in, fan_out), -127, 128, jnp.int8)
        jitter = jax.random.uniform(ks, (1, fan_out), jnp.float32, 0.75,
                                    1.25)
        return q, jitter * ((2.0 / fan_in) ** 0.5 / q_std)

    shapes = {"wq": (D, HQ), "wk": (D, HKV), "wv": (D, HKV), "wo": (HQ, D),
              "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def one_layer(k):
        # one layer at a time: the random bits of a whole stacked matrix
        # (7.5 GB as uint32 for w_gate) never exist at once
        ks = jax.random.split(k, len(shapes) + 2)
        out = {}
        for kk, (name, (fi, fo)) in zip(ks, shapes.items()):
            out[name + "_q"], out[name + "_s"] = qmat(kk, fi, fo)
        out["ln_attn"] = 1.0 + 0.1 * jax.random.normal(ks[-2], (D,),
                                                       jnp.float32)
        out["ln_mlp"] = 1.0 + 0.1 * jax.random.normal(ks[-1], (D,),
                                                      jnp.float32)
        return out

    layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
    head_q, head_s = qmat(k_head, D, V)
    embed = (jax.random.normal(k_embed, (V, D), jnp.bfloat16)
             * jnp.bfloat16(0.5 * (2.0 / D) ** 0.5))
    return {
        "embed": embed,
        "layers": layers,
        "ln_out": 1.0 + 0.1 * jax.random.normal(k_norm, (D,), jnp.float32),
        "lm_head_q": head_q,
        "lm_head_s": head_s,
    }


def mobilenet_v1_tree(cfg: dict, seed: int):
    """MobileNet-v1 in inference form: HWIO float32 kernels (He-normal),
    and after every convolution a per-channel scale near 1 and a small
    bias, which is what a folded batch norm leaves."""
    import jax

    key = jax.random.PRNGKey(jax_seed(seed, "mobilenet_v1"))
    return jax.jit(_mobilenet_tree, static_argnums=(1,))(
        key, int(cfg["num_classes"]))


def _mobilenet_tree(key, classes):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 128))

    def conv(kh, kw, cin, cout, fan_in):
        w = jax.random.normal(next(keys), (kh, kw, cin, cout), jnp.float32)
        return w * (2.0 / fan_in) ** 0.5

    def scale_bias(c):
        return (1.0 + 0.1 * jax.random.normal(next(keys), (c,), jnp.float32),
                0.1 * jax.random.normal(next(keys), (c,), jnp.float32))

    s, b = scale_bias(32)
    tree = {"stem": {"w": conv(3, 3, 3, 32, 27), "scale": s, "bias": b}}
    cin = 32
    for i, (_stride, cout) in enumerate(flops.MOBILENET_V1_BLOCKS):
        ds, db = scale_bias(cin)
        ps, pb = scale_bias(cout)
        tree[f"block{i}"] = {
            "dw": conv(3, 3, 1, cin, 9), "dw_scale": ds, "dw_bias": db,
            "pw": conv(1, 1, cin, cout, cin), "pw_scale": ps, "pw_bias": pb}
        cin = cout
    tree["head"] = {
        "w": conv(1, 1, cin, classes, cin),
        "bias": 0.1 * jax.random.normal(next(keys), (classes,),
                                        jnp.float32)}
    return tree
