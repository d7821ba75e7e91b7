"""Operations and bytes the algorithms need, from their shapes alone.

What an implementation happens to execute (XLA's cost analysis) is not
read anywhere: a roofline share divides what the mathematics requires by
the time it took.  Each function takes the configuration file's own keys.
"""

from __future__ import annotations

#: (stride, output channels) of MobileNet-v1's 13 depthwise-separable
#: blocks, Table 1 of arXiv:1704.04861.
MOBILENET_V1_BLOCKS = ((1, 64), (2, 128), (1, 128), (2, 256), (1, 256),
                       (2, 512), (1, 512), (1, 512), (1, 512), (1, 512),
                       (1, 512), (2, 1024), (1, 1024))


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# -- dense decoder (Mistral-7B's family) ----------------------------------

def decoder_matmul_params(cfg: dict) -> int:
    """Weights every token is multiplied through: the seven matrices of
    each block plus the output head (the embedding is a gather)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def decoder_weight_bytes(cfg: dict, bytes_per_weight: float = 1.0) -> float:
    """Bytes one decode step has to stream for the matrices alone."""
    return decoder_matmul_params(cfg) * bytes_per_weight


def decoder_flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs to process one token that attends to ``context``
    positions: 2 per weight, plus QK^T and PV over the context."""
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    attn = 4 * cfg["num_hidden_layers"] * hq * context
    return 2 * decoder_matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V rows one context position holds across all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * bytes_per_value)


# -- MobileNet-v1 ----------------------------------------------------------

def mobilenet_v1_flops_per_frame(cfg: dict) -> float:
    """2 x multiply-accumulates of one forward pass at width 1.0, SAME
    padding: stem 3x3, 13 x (depthwise 3x3 + pointwise 1x1), the
    classifier.  569 M MACs at 224 x 224, as the paper's Table 4 says."""
    size, cin = cfg["image_size"], 3
    size = _ceil_div(size, 2)
    macs = size * size * 9 * cin * 32
    cin = 32
    for stride, cout in MOBILENET_V1_BLOCKS:
        size = _ceil_div(size, stride)
        macs += size * size * 9 * cin          # depthwise
        macs += size * size * cin * cout       # pointwise
        cin = cout
    macs += cin * cfg["num_classes"]
    return 2.0 * macs
