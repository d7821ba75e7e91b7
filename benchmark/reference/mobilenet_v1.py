"""MobileNet-v1 (arXiv:1704.04861, Table 1) in float32 ``jax.numpy`` at
``highest`` precision: a 3x3 stride-2 stem, thirteen depthwise-separable
blocks, global average pooling and a 1x1 classifier; every convolution is
followed by a per-channel scale and bias (a folded batch norm) and ReLU6.
Input: uint8 frames, normalised to [-1, 1] as the pipeline does.

``compute="float8"`` is the control: every kernel and every activation
(each operation's result) stored as float8 (e4m3), accumulating in float32
as a chip would — the next precision below the bfloat16 the configuration
states.
"""

from __future__ import annotations

import functools

from ..flops import MOBILENET_V1_BLOCKS


def logits(tree, frames_u8, compute: str = "float32"):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def q(a):
        """What storing ``a`` at the ``compute`` precision leaves of it."""
        if compute == "float32":
            return a
        if compute == "float8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        raise ValueError(f"compute {compute!r}")

    def conv(x, w, stride, groups=1):
        return q(lax.conv_general_dilated(
            x, q(w), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups))

    def sbr(x, scale, bias):
        return q(jnp.clip(x * q(scale) + q(bias), 0.0, 6.0))

    with jax.default_matmul_precision("highest"):
        x = q((frames_u8.astype(jnp.float32) - 127.5) / 127.5)
        p = tree["stem"]
        x = sbr(conv(x, p["w"], 2), p["scale"], p["bias"])
        for i, (stride, _cout) in enumerate(MOBILENET_V1_BLOCKS):
            p = tree[f"block{i}"]
            x = sbr(conv(x, p["dw"], stride, groups=x.shape[-1]),
                    p["dw_scale"], p["dw_bias"])
            x = sbr(conv(x, p["pw"], 1), p["pw_scale"], p["pw_bias"])
        x = q(jnp.mean(x, axis=(1, 2), keepdims=True))
        x = q(conv(x, tree["head"]["w"], 1) + q(tree["head"]["bias"]))
        return x[:, 0, 0, :]


@functools.cache
def _jit(compute: str):
    import jax

    return jax.jit(functools.partial(logits, compute=compute))


def logits_in_blocks(tree, frames_u8, rows: int = 256,
                     compute: str = "float32"):
    """Host ``[N, classes]`` logits, computed ``rows`` frames at a time
    so the float32 activations fit beside whatever else is resident."""
    import numpy as np

    fn = _jit(compute)
    return np.concatenate([np.asarray(fn(tree, frames_u8[i:i + rows]))
                           for i in range(0, len(frames_u8), rows)])
