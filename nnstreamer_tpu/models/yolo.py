"""YOLOv5-style single-shot detector — the second half of benchmark
config #2 ("SSD-MobileNet / YOLOv5 object detection", BASELINE.json).

Reference analog: the reference decodes YOLOv5/YOLOv8 raw output in
``tensordec-boundingbox.c``'s yolo modes (SURVEY §2.5 [UNVERIFIED]); the
model itself comes from a .tflite/.onnx file.  Zero-egress here, so the
zoo provides a compact YOLOv5-shaped network built from the shared
depthwise-separable blocks: a strided backbone with three detection
scales (strides 8/16/32), ``anchors_per_cell`` predictors per cell, and
the YOLOv5 head convention — sigmoid box/objectness/class activations
with per-cell offset decode — emitting ONE ``[B, N, 5+C]`` tensor in the
exact layout ``tensor_decoder mode=bounding_boxes option1=yolov5``
consumes (cx, cy, w, h normalized, objectness, class scores).

TPU-first: the whole predict-and-decode is one jitted program; the grid
offset/anchor math is folded into the fused pipeline program next to the
convs, and the decoder's device-NMS path (option7=device) keeps the
full decode on device.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

from ..core.types import TensorsSpec
from .backbone import fm_size, he_conv, make_ops, rounded, sep_block_params, \
    sep_block_pspecs, stem_params, stem_pspecs
from .zoo import ModelBundle, register_model

#: (stride-2 steps between scales are built from these widths)
_BACKBONE = [64, 128, 256]   # strides 8, 16, 32 scale widths (pre width-mult)
_ANCHORS_PER_CELL = 3
#: YOLOv5-ish anchor sizes per scale, normalized to input size
_ANCHOR_SIZES = {
    8: [(0.04, 0.06), (0.08, 0.12), (0.12, 0.09)],
    16: [(0.14, 0.22), (0.26, 0.17), (0.24, 0.38)],
    32: [(0.45, 0.35), (0.38, 0.64), (0.75, 0.70)],
}


def _keygen(seed: int):
    import jax

    key = jax.random.PRNGKey(seed)
    while True:
        key, sub = jax.random.split(key)
        yield sub


def init_params(classes: int, width: float = 1.0, seed: int = 0,
                anchors_per_cell: int = _ANCHORS_PER_CELL,
                head_values: int = 5) -> Dict:
    """``anchors_per_cell``/``head_values`` let the anchor-free v8 head
    (1 predictor per cell, 4+C values) share the backbone with v5."""
    keys = _keygen(seed)
    params: Dict = {"stem": stem_params(keys, 3, rounded(32, width))}
    cin = rounded(32, width)
    # stem is stride 2; three stride-2 stages land strides 8/16/32 with one
    # refining block per scale
    for i, ch in enumerate(_BACKBONE):
        cout = rounded(ch, width)
        params[f"down{i}"] = sep_block_params(keys, cin, cout)   # stride 2
        params[f"block{i}"] = sep_block_params(keys, cout, cout)  # stride 1
        cin = cout
        nout = anchors_per_cell * (head_values + classes)
        params[f"head{i}"] = {
            "w": he_conv(next(keys), 1, 1, cout, nout),
            # objectness prior: like the SSD low-prior cls bias, random
            # weights should predict "no object" almost everywhere
            "b": np.full((nout,), -4.0, np.float32),
        }
    return params


def param_pspecs() -> Dict:
    from jax.sharding import PartitionSpec as P

    specs: Dict = {"stem": stem_pspecs()}
    for i in range(len(_BACKBONE)):
        specs[f"down{i}"] = sep_block_pspecs()
        specs[f"block{i}"] = sep_block_pspecs()
        specs[f"head{i}"] = {"w": P(), "b": P()}
    return specs


def num_predictions(size: int) -> int:
    return sum(
        fm_size(size, s) ** 2 * _ANCHORS_PER_CELL for s in (8, 16, 32))


def _backbone_feats(params, x, size: int, compute_dtype):
    """Shared stem + three-scale backbone: [B, size, size, 3] ->
    [(stride, feature_map, head_params)] at strides 8/16/32."""
    import jax
    import jax.numpy as jnp

    assert x.shape[1] == x.shape[2] == size, (
        f"yolo input must be {size}x{size}, got {x.shape}")
    conv2d, sbr, sep = make_ops(compute_dtype)
    cdt = jnp.dtype(compute_dtype)

    h = conv2d(x.astype(cdt), params["stem"]["w"], 2)
    h = sbr(h, params["stem"]["scale"], params["stem"]["bias"])
    # extra stride-2 maxpool after the stem puts the three down/block
    # stages at strides 8/16/32 — each head consumes its own stage's
    # feature map (channel counts match init_params' loop exactly)
    h = jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "SAME")
    feats = []
    for i, stride in enumerate((8, 16, 32)):
        h = sep(h, params[f"down{i}"], 2)
        h = sep(h, params[f"block{i}"], 1)
        feats.append((stride, h, params[f"head{i}"]))
    return feats


def _poly_coeffs(g: int, n_out: int, n_anchor: int, box_a):
    """Per-(position, channel) FMA coefficients for a yolo-family decode
    head, out = A*sigmoid(raw)^2 + B*sigmoid(raw) + C over the flattened
    [N_s, n_out] scale block — the whole box decode as ONE lane-friendly
    pass (the textbook slice/meshgrid/stack form builds minor-dim-3/4
    tensors that TPU pads to 128 lanes).  ``box_a``: [n_anchor, 2] quadratic
    coefficients for the w/h channels (4*anchor, already in the head's
    output units).  Channels: 0/1 affine cell-centers, 2/3 quadratic
    w/h, the rest identity (scores)."""
    gy, gx = np.meshgrid(np.arange(g), np.arange(g), indexing="ij")
    pos = np.stack([gx, gy], -1).reshape(-1, 2)
    pos = np.repeat(pos, n_anchor, axis=0)  # [N_s, 2], anchor-minor
    box_a = np.tile(np.asarray(box_a, np.float32), (g * g, 1))
    N_s = g * g * n_anchor
    A = np.zeros((N_s, n_out), np.float32)
    B = np.zeros((N_s, n_out), np.float32)
    C = np.zeros((N_s, n_out), np.float32)
    B[:, 4:] = 1.0
    B[:, 0] = B[:, 1] = 2.0 / g
    C[:, 0] = (pos[:, 0] - 0.5) / g
    C[:, 1] = (pos[:, 1] - 0.5) / g
    A[:, 2] = box_a[:, 0]
    A[:, 3] = box_a[:, 1]
    return A, B, C


def _poly_decode(raws, abc):
    """Concatenate per-scale raw head tensors and run the fused
    polynomial decode (see :func:`_poly_coeffs`)."""
    import jax
    import jax.numpy as jnp

    raw = jnp.concatenate(raws, axis=1).astype(jnp.float32)
    A = jnp.asarray(np.concatenate([a for a, _, _ in abc]))
    B = jnp.asarray(np.concatenate([b for _, b, _ in abc]))
    C = jnp.asarray(np.concatenate([c for _, _, c in abc]))
    s = jax.nn.sigmoid(raw)
    return (A * s + B) * s + C


def apply(params, x, *, classes: int, size: int, compute_dtype="bfloat16"):
    """[B, size, size, 3] float32 in [0,1] -> [B, N, 5+C] float32
    (yolov5 layout).  ``size`` pins the traced input so N matches the
    bundle's negotiated out_spec."""
    import jax.numpy as jnp

    conv2d, _, _ = make_ops(compute_dtype)
    cdt = jnp.dtype(compute_dtype)
    feats = _backbone_feats(params, x, size, compute_dtype)

    B = x.shape[0]
    raws, abc = [], []
    for stride, fm, hp in feats:
        g = fm.shape[1]
        raw = conv2d(fm, hp["w"], 1) + hp["b"].astype(cdt)
        raws.append(raw.reshape(B, g * g * _ANCHORS_PER_CELL,
                                5 + classes))
        anch = np.asarray(_ANCHOR_SIZES[stride], np.float32)  # [A, 2]
        abc.append(_poly_coeffs(g, 5 + classes, _ANCHORS_PER_CELL,
                                4.0 * anch))
    return _poly_decode(raws, abc)


def num_predictions_v8(size: int) -> int:
    return sum(fm_size(size, s) ** 2 for s in (8, 16, 32))


def apply_v8(params, x, *, classes: int, size: int,
             compute_dtype="bfloat16"):
    """[B, size, size, 3] float32 in [0,1] -> [B, 4+C, N] float32 — the
    YOLOv8 (ultralytics) channels-first export layout the reference's
    yolov8 decoder mode consumes: anchor-free (one predictor per cell, no
    objectness column), post-sigmoid class scores, normalized cx,cy,w,h."""
    import jax.numpy as jnp

    conv2d, _, _ = make_ops(compute_dtype)
    cdt = jnp.dtype(compute_dtype)
    B = x.shape[0]
    raws, abc = [], []
    for stride, fm, hp in _backbone_feats(params, x, size, compute_dtype):
        g = fm.shape[1]
        raw = conv2d(fm, hp["w"], 1) + hp["b"].astype(cdt)
        raws.append(raw.reshape(B, g * g, 4 + classes))
        # anchor-free decode: cell-offset centers; w/h from a per-scale
        # prior proportional to the stride (v8's dist2bbox analog)
        prior = 4.0 * (4.0 * stride / size)  # quadratic coeff = 4*prior
        abc.append(_poly_coeffs(g, 4 + classes, 1, [[prior, prior]]))
    return jnp.swapaxes(_poly_decode(raws, abc), 1, 2)


@register_model("yolov8")
def _yolov8(opts: Dict[str, str]) -> ModelBundle:
    classes = int(opts.get("classes", 80))
    width = float(opts.get("width", 1.0))
    seed = int(opts.get("seed", 0))
    size = int(opts.get("size", 224))
    batch = int(opts.get("batch", 1))
    dtype = opts.get("dtype", "bfloat16")
    if size % 32:
        raise ValueError(f"yolov8 size must be a multiple of 32, got {size}")

    params = init_params(classes=classes, width=width, seed=seed,
                         anchors_per_cell=1, head_values=4)
    apply_fn = functools.partial(
        apply_v8, classes=classes, size=size, compute_dtype=dtype)
    n = num_predictions_v8(size)
    return ModelBundle(
        apply_fn=apply_fn,
        params=params,
        in_spec=TensorsSpec.from_string(f"3:{size}:{size}:{batch}", "float32"),
        out_spec=TensorsSpec.from_string(
            f"{n}:{4 + classes}:{batch}", "float32"),
        param_pspecs=param_pspecs(),
        name="yolov8",
    )


@register_model("yolov5")
def _yolo(opts: Dict[str, str]) -> ModelBundle:
    classes = int(opts.get("classes", 80))
    width = float(opts.get("width", 1.0))
    seed = int(opts.get("seed", 0))
    size = int(opts.get("size", 224))
    batch = int(opts.get("batch", 1))
    dtype = opts.get("dtype", "bfloat16")
    if size % 32:
        raise ValueError(f"yolov5 size must be a multiple of 32, got {size}")

    params = init_params(classes=classes, width=width, seed=seed)
    apply_fn = functools.partial(
        apply, classes=classes, size=size, compute_dtype=dtype)
    n = num_predictions(size)
    return ModelBundle(
        apply_fn=apply_fn,
        params=params,
        in_spec=TensorsSpec.from_string(f"3:{size}:{size}:{batch}", "float32"),
        out_spec=TensorsSpec.from_string(
            f"{5 + classes}:{n}:{batch}", "float32"),
        param_pspecs=param_pspecs(),
        name="yolov5",
    )


# -- CSP-YOLOv5s: the real-geometry detector ------------------------------
#
# Faithful YOLOv5-v6 architecture (CSPDarknet backbone + SPPF + PANet
# head + anchor head), ~7M params / ~17 GFLOPs per frame at 640x640 with
# the default width 0.5 / depth 0.33 multipliers — the compute class of
# the reference's canonical yolov5s.tflite/onnx detector (BASELINE
# config #2), not the toy `yolov5` zoo stand-in above (which stays for
# cheap tests).  Weights are seeded (zero-egress); real checkpoints can
# enter via models/onnx.py.  All NHWC, SiLU, BN folded to per-channel
# scale/bias (inference form), one jitted program.

#: YOLOv5 anchor priors, pixels at the nominal 640 input (P3/P4/P5)
_V5S_ANCHORS_PX = {
    8: [(10, 13), (16, 30), (33, 23)],
    16: [(30, 61), (62, 45), (59, 119)],
    32: [(116, 90), (156, 198), (373, 326)],
}


def _conv_p(keys, k: int, cin: int, cout: int) -> Dict:
    return {"w": he_conv(next(keys), k, k, cin, cout),
            "scale": np.ones((cout,), np.float32),
            "bias": np.zeros((cout,), np.float32)}


def _c3_p(keys, cin: int, cout: int, n: int) -> Dict:
    ch = cout // 2
    return {
        "cv1": _conv_p(keys, 1, cin, ch),
        "cv2": _conv_p(keys, 1, cin, ch),
        "cv3": _conv_p(keys, 1, 2 * ch, cout),
        "m": [{"a": _conv_p(keys, 1, ch, ch), "b": _conv_p(keys, 3, ch, ch)}
              for _ in range(n)],
    }


def v5s_channels(width: float = 0.5):
    """Backbone channel plan after the width multiplier (c1..c5)."""
    return [rounded(c, width) for c in (64, 128, 256, 512, 1024)]


def v5s_depths(depth: float = 0.33):
    """C3 repeat counts after the depth multiplier (backbone stages)."""
    return [max(1, round(n * depth)) for n in (3, 6, 9, 3)]


def init_v5s_params(classes: int = 80, width: float = 0.5,
                    depth: float = 0.33, seed: int = 0) -> Dict:
    keys = _keygen(seed)
    c1, c2, c3, c4, c5 = v5s_channels(width)
    n1, n2, n3, n4 = v5s_depths(depth)
    nout = _ANCHORS_PER_CELL * (5 + classes)
    p: Dict = {
        "stem": _conv_p(keys, 6, 3, c1),
        "down1": _conv_p(keys, 3, c1, c2), "c3_1": _c3_p(keys, c2, c2, n1),
        "down2": _conv_p(keys, 3, c2, c3), "c3_2": _c3_p(keys, c3, c3, n2),
        "down3": _conv_p(keys, 3, c3, c4), "c3_3": _c3_p(keys, c4, c4, n3),
        "down4": _conv_p(keys, 3, c4, c5), "c3_4": _c3_p(keys, c5, c5, n4),
        "sppf_cv1": _conv_p(keys, 1, c5, c5 // 2),
        "sppf_cv2": _conv_p(keys, 1, c5 * 2, c5),
        # PANet head (top-down then bottom-up), shortcut-free C3s
        "h_lat5": _conv_p(keys, 1, c5, c4),
        "h_c3_4": _c3_p(keys, 2 * c4, c4, n4),
        "h_lat4": _conv_p(keys, 1, c4, c3),
        "h_c3_3": _c3_p(keys, 2 * c3, c3, n4),
        "h_down3": _conv_p(keys, 3, c3, c3),
        "h_c3_4b": _c3_p(keys, 2 * c3, c4, n4),
        "h_down4": _conv_p(keys, 3, c4, c4),
        "h_c3_5b": _c3_p(keys, 2 * c4, c5, n4),
    }
    for i, cin in enumerate((c3, c4, c5)):
        p[f"det{i}"] = {
            "w": he_conv(next(keys), 1, 1, cin, nout),
            "b": np.full((nout,), -4.0, np.float32),  # no-object prior
        }
    return p


def v5s_param_pspecs(params: Dict):
    """Replicated weights (DP/batch sharding is the detection serving
    axis; 7M bf16 params replicate for free)."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(lambda _: P(), params)


def num_predictions_v5s(size: int) -> int:
    return num_predictions(size)  # 3 anchors/cell at strides 8/16/32


def apply_v5s(params, x, *, classes: int, size: int,
              compute_dtype="bfloat16"):
    """[B, size, size, 3] float32 in [0,1] -> [B, N, 5+C] float32, the
    yolov5 layout ``tensor_decoder mode=bounding_boxes option1=yolov5``
    consumes — same contract as the toy ``apply`` above, real compute."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    assert x.shape[1] == x.shape[2] == size
    cdt = jnp.dtype(compute_dtype)

    def conv(x, p, stride=1):
        y = lax.conv_general_dilated(
            x, jnp.asarray(p["w"]).astype(cdt), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = y * jnp.asarray(p["scale"]).astype(cdt) \
            + jnp.asarray(p["bias"]).astype(cdt)
        return jax.nn.silu(y)

    def c3(x, p, shortcut=True):
        a = conv(x, p["cv1"])
        for bp in p["m"]:
            b = conv(conv(a, bp["a"]), bp["b"])
            a = a + b if shortcut else b
        return conv(jnp.concatenate([a, conv(x, p["cv2"])], -1), p["cv3"])

    def maxpool5(x):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 5, 5, 1), (1, 1, 1, 1), "SAME")

    def up2(x):
        return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)

    h = conv(x.astype(cdt), params["stem"], 2)          # stride 2
    h = conv(h, params["down1"], 2)                     # stride 4
    h = c3(h, params["c3_1"])
    h = conv(h, params["down2"], 2)                     # stride 8
    p3 = h = c3(h, params["c3_2"])
    h = conv(h, params["down3"], 2)                     # stride 16
    p4 = h = c3(h, params["c3_3"])
    h = conv(h, params["down4"], 2)                     # stride 32
    h = c3(h, params["c3_4"])
    a = conv(h, params["sppf_cv1"])                     # SPPF
    m1 = maxpool5(a)
    m2 = maxpool5(m1)
    p5 = conv(jnp.concatenate([a, m1, m2, maxpool5(m2)], -1),
              params["sppf_cv2"])

    # PANet: top-down
    lat5 = conv(p5, params["h_lat5"])
    f4 = c3(jnp.concatenate([up2(lat5), p4], -1), params["h_c3_4"],
            shortcut=False)
    lat4 = conv(f4, params["h_lat4"])
    o3 = c3(jnp.concatenate([up2(lat4), p3], -1), params["h_c3_3"],
            shortcut=False)
    # bottom-up
    o4 = c3(jnp.concatenate([conv(o3, params["h_down3"], 2), lat4], -1),
            params["h_c3_4b"], shortcut=False)
    o5 = c3(jnp.concatenate([conv(o4, params["h_down4"], 2), lat5], -1),
            params["h_c3_5b"], shortcut=False)

    B = x.shape[0]
    # Detect head as the fused polynomial decode (see _poly_coeffs —
    # the textbook slice/meshgrid/stack form pads minor-dim-3/4 tensors
    # to 128 lanes).  Anchors are pixels of the
    # NETWORK INPUT (ultralytics convention): normalize by the actual
    # input size.
    raws, abc = [], []
    for stride, fm in ((8, o3), (16, o4), (32, o5)):
        hp = params[f"det{(stride.bit_length() - 4)}"]
        g = fm.shape[1]
        raw = lax.conv_general_dilated(
            fm, jnp.asarray(hp["w"]).astype(cdt), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        raw = raw + jnp.asarray(hp["b"]).astype(cdt)
        n_out = raw.shape[-1] // _ANCHORS_PER_CELL
        raws.append(raw.reshape(B, g * g * _ANCHORS_PER_CELL, n_out))
        anch = np.asarray(_V5S_ANCHORS_PX[stride], np.float32) / size
        abc.append(_poly_coeffs(g, n_out, _ANCHORS_PER_CELL, 4.0 * anch))
    return _poly_decode(raws, abc)


@register_model("yolov5s")
def _yolov5s(opts: Dict[str, str]) -> ModelBundle:
    classes = int(opts.get("classes", 80))
    width = float(opts.get("width", 0.5))
    depth = float(opts.get("depth", 0.33))
    seed = int(opts.get("seed", 0))
    size = int(opts.get("size", 640))
    batch = int(opts.get("batch", 1))
    dtype = opts.get("dtype", "bfloat16")
    if size % 32:
        raise ValueError(f"yolov5s size must be a multiple of 32, got {size}")
    params = init_v5s_params(classes=classes, width=width, depth=depth,
                             seed=seed)
    apply_fn = functools.partial(
        apply_v5s, classes=classes, size=size, compute_dtype=dtype)
    n = num_predictions_v5s(size)
    return ModelBundle(
        apply_fn=apply_fn,
        params=params,
        in_spec=TensorsSpec.from_string(f"3:{size}:{size}:{batch}", "float32"),
        out_spec=TensorsSpec.from_string(
            f"{5 + classes}:{n}:{batch}", "float32"),
        param_pspecs=v5s_param_pspecs(params),
        name="yolov5s",
    )
