"""bounding_boxes decoder: detections -> overlay video + meta.

Reference analog: ``tensordec-boundingbox.c`` + per-format modules
(mobilenetssd.cc, yolo.cc — SURVEY §2.5, BASELINE config #2): model output
-> threshold -> NMS -> ``video/x-raw`` RGBA overlay with box rectangles;
label file via option properties.

Input contracts (option1 selects, mirroring the reference's format modes):

* ``ssd`` (default): two tensors — boxes (N,4) corner-format, normalized
  [0,1]; scores (N,C) per-class (class 0 may be background when option
  ``bg`` set).  Our models/ssd.py emits exactly this (decoded anchors are a
  model concern, matching how tflite SSD graphs embed their postprocess).
* ``yolov5``: one tensor (N, 5+C): cx,cy,w,h (normalized), objectness,
  class scores.

Options (reference numbering): option1=format, option2=labels,
option3=score threshold (default 0.5), option4=WIDTH:HEIGHT of output
overlay (default 640:480), option5=iou threshold (default 0.5),
option6=max detections, option7=NMS placement (host|device),
option8=model input size for pixel-coordinate boxes,
option9=output form (overlay|tensors).

Output: RGBA overlay frame (H,W,4) uint8 + ``buf.meta["detections"]`` =
list of dicts {box, score, class_index, label}; with option9=tensors,
the detections themselves as tensors (boxes/scores/classes[/valid]) and
no canvas — the indices-not-payloads treatment for headless serving.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..core.buffer import Buffer
from ..core.caps import Caps, MediaType
from ..core.registry import register_decoder
from ..core.types import TensorsSpec
from ..ops.nms import center_to_corner, nms_numpy
from .base import Decoder, load_labels

def _ssd_topk(boxes, scores, k: int):
    """Pure-JAX SSD prefilter shared by the fused device_fn and the unfused
    _device_topk path (they must stay numerically identical — both feed
    ``_decode_one``'s "triple" contract): per-anchor class argmax + top-k.
    boxes [B,N,4], scores [B,N,C] -> ([B,K,4] f32, [B,K] f32, [B,K] i32)."""
    import jax.numpy as jnp
    from jax import lax

    s = scores.reshape(scores.shape[0], scores.shape[1], -1)
    cls = jnp.argmax(s, axis=-1).astype(jnp.int32)
    sc = jnp.max(s, axis=-1)
    top_sc, idx = lax.top_k(sc, k)
    top_b = jnp.take_along_axis(
        boxes.reshape(boxes.shape[0], -1, 4), idx[..., None], axis=1)
    top_c = jnp.take_along_axis(cls, idx, axis=1)
    return (top_b.astype(jnp.float32), top_sc.astype(jnp.float32), top_c)


_PALETTE = np.array(
    [
        [230, 25, 75, 255], [60, 180, 75, 255], [255, 225, 25, 255],
        [0, 130, 200, 255], [245, 130, 48, 255], [145, 30, 180, 255],
        [70, 240, 240, 255], [240, 50, 230, 255], [210, 245, 60, 255],
        [250, 190, 190, 255],
    ],
    np.uint8,
)


@register_decoder("bounding_boxes")
class BoundingBoxes(Decoder):
    mode = "bounding_boxes"

    def __init__(self, props):
        super().__init__(props)
        self.format = (self.option(1) or "ssd").lower()
        labels = self.option(2) or "coco-mini"
        self.labels = load_labels(labels)
        self.threshold = float(self.option(3) or 0.5)
        size = self.option(4) or "640:480"
        w, h = size.split(":")
        self.out_w, self.out_h = int(w), int(h)
        self.iou_threshold = float(self.option(5) or 0.5)
        self.max_detections = int(self.option(6) or 100)
        # option7: where greedy NMS runs when the decoder is fused.
        # "host" (default) = top-k prefilter on device, NMS at the sink
        # edge; "device" = the whole decode (threshold+NMS) inside the
        # fused XLA program via ops.nms.nms_jax — only final detections
        # ever cross to the host.
        nms_opt = (self.option(7) or "host").lower()
        if nms_opt.startswith("nms:"):
            nms_opt = nms_opt[4:]
        if nms_opt not in ("host", "device"):
            raise ValueError(f"option7 (nms placement) must be host|device, "
                             f"got {nms_opt!r}")
        self.nms_mode = nms_opt
        # option8 (yolov8): model-input WIDTH[:HEIGHT] when the tensor
        # carries pixel-coordinate boxes (ultralytics default); unset means
        # normalized [0,1] coords.
        o8 = self.option(8)
        if o8:
            wh = [int(v) for v in str(o8).split(":")]
            mw, mh = (wh[0], wh[0]) if len(wh) == 1 else (wh[0], wh[1])
            self.box_scale = np.asarray([mw, mh, mw, mh], np.float32)
        else:
            self.box_scale = np.float32(1.0)
        # option9: output form.  "overlay" (default) = the reference's
        # video/x-raw RGBA frame with rectangles drawn on the host.
        # "tensors" = ship the detections THEMSELVES (boxes f32 [M,4],
        # scores f32 [M], classes i32 [M]) and skip the canvas — the
        # classification recipe (indices-not-payloads) applied to
        # detection: a batch-256 overlay canvas is ~100 MB of host memset
        # + draw per batch that a headless serving pipeline never looks
        # at.  (The reference has no headless mode; its tensor_region
        # decoder is the precedent for tensor-form decoder output.)
        out_mode = (self.option(9) or "overlay").lower()
        if out_mode not in ("overlay", "tensors"):
            raise ValueError(f"option9 (output form) must be "
                             f"overlay|tensors, got {out_mode!r}")
        self.out_mode = out_mode

    def out_caps(self, in_spec: Optional[TensorsSpec]) -> Caps:
        if self.out_mode == "tensors":
            return Caps.tensors()
        return Caps.new(
            MediaType.VIDEO, format="RGBA", width=self.out_w, height=self.out_h
        )

    # -- decode ------------------------------------------------------------
    def decode(self, tensors: List[np.ndarray], buf: Buffer):
        # Batched buffers ([B, N, ...] per tensor) decode per frame and are
        # emitted as B separate video buffers — NMS must never mix boxes of
        # different frames, and the negotiated caps (one WxH RGBA frame per
        # buffer) stay truthful.  The reference decodes one frame per
        # buffer; TPU pipelines batch upstream and un-batch here.
        ndim = getattr(tensors[0], "ndim", None)
        if ndim is None:
            ndim = np.asarray(tensors[0]).ndim
        if ndim >= 3:
            outs = []
            for b, frame in enumerate(self._split_frames(tensors)):
                dets = self._decode_dets(frame)
                if self.out_mode == "tensors":
                    o = buf.with_tensors(self._det_tensors(dets), spec=None)
                else:
                    o = buf.with_tensors([self._draw(dets)], spec=None)
                o.meta["detections"] = dets
                o.meta["batch_index"] = b
                outs.append(o)
            return outs
        detections = self._decode_dets(tensors)
        if self.out_mode == "tensors":
            out = buf.with_tensors(self._det_tensors(detections), spec=None)
        else:
            out = buf.with_tensors([self._draw(detections)], spec=None)
        out.meta["detections"] = detections
        return out

    @staticmethod
    def _det_tensors(dets) -> List[np.ndarray]:
        """detections list -> (boxes f32 [M,4], scores f32 [M],
        classes i32 [M]) — the option9=tensors output contract."""
        m = len(dets)
        boxes = np.zeros((m, 4), np.float32)
        scores = np.zeros((m,), np.float32)
        classes = np.zeros((m,), np.int32)
        for i, d in enumerate(dets):
            boxes[i] = d["box"]
            scores[i] = d["score"]
            classes[i] = d["class_index"]
        return [boxes, scores, classes]

    def _split_frames(self, tensors):
        """Per-frame inputs for a batched buffer.  SSD-format device arrays
        go through a jitted top-k prefilter FIRST (SURVEY §7 hard-parts:
        "NMS on TPU -> top-k based approximation"): only K=4*max_detections
        candidates per frame cross to the host instead of the full
        [B, N, C] score tensor — the host-side greedy NMS then runs on K
        boxes, not thousands."""
        n = tensors[0].shape[1]
        k = 4 * self.max_detections
        if self.format in ("ssd", "mobilenet-ssd", "mobilenetv2-ssd") and n > k:
            tb, ts, tc = self._device_topk(tensors[0], tensors[1], k)
            return [
                ("triple", (tb[b], ts[b], tc[b])) for b in range(tb.shape[0])
            ]
        host = [np.asarray(t) for t in tensors]  # ONE device fetch per tensor
        return [
            ("raw", [t[b] for t in host]) for b in range(host[0].shape[0])
        ]

    def _device_topk(self, boxes, scores, k: int):
        import jax
        import jax.numpy as jnp

        fn = getattr(self, "_topk_fn", None)
        if fn is None:
            fn = self._topk_fn = jax.jit(
                lambda b, s: _ssd_topk(b, s, k))
        tb, ts, tc = fn(jnp.asarray(boxes), jnp.asarray(scores))
        return np.asarray(tb), np.asarray(ts), np.asarray(tc)

    def _decode_dets(self, frame):
        if isinstance(frame, tuple) and frame[0] == "triple":
            boxes, scores, classes = frame[1]
            m = scores >= self.threshold
            boxes, scores, classes = boxes[m], scores[m], classes[m]
        else:
            tensors = frame[1] if isinstance(frame, tuple) else frame
            if self.format in ("ssd", "mobilenet-ssd", "mobilenetv2-ssd"):
                boxes, scores, classes = self._decode_ssd(tensors)
            elif self.format == "yolov8":
                boxes, scores, classes = self._decode_yolov8(tensors)
            elif self.format in ("yolov5", "yolo"):
                boxes, scores, classes = self._decode_yolo(tensors)
            else:
                raise ValueError(f"unknown bounding-box format {self.format!r}")

        keep = nms_numpy(boxes, scores, self.iou_threshold, self.max_detections)
        detections = []
        for i in keep:
            x1, y1, x2, y2 = boxes[i]
            ci = int(classes[i])
            detections.append(
                {
                    "box": [float(x1), float(y1), float(x2), float(y2)],
                    "score": float(scores[i]),
                    "class_index": ci,
                    "label": self.labels[ci] if ci < len(self.labels) else str(ci),
                }
            )
        return detections

    # -- fusion ------------------------------------------------------------
    # The whole prefilter joins the fused XLA program: per-anchor class
    # argmax + top-k run on device, only [B,K] candidates cross to the host
    # (async D2H started by the fused stage), and threshold/NMS/overlay
    # resolve in ``host_post`` at the sink edge.  The fused path emits ONE
    # buffer per (possibly batched) input with stacked overlays [B,H,W,4]
    # and per-frame ``meta["detections"]`` lists; the unfused host path
    # keeps the reference's one-video-frame-per-buffer un-batching.
    def device_fn(self, in_spec: TensorsSpec):
        import jax.numpy as jnp
        from jax import lax

        from ..core.types import TensorSpec

        fmt = self.format
        if fmt in ("ssd", "mobilenet-ssd", "mobilenetv2-ssd"):
            if len(in_spec) < 2:
                return None
            bshape = in_spec[0].shape  # (B, N, 4)
            if len(bshape) != 3:
                return None
            batch, n = bshape[0], bshape[1]
            k = min(4 * self.max_detections, n)

            def fn(arrays):
                return _ssd_topk(arrays[0], arrays[1], k)

        elif fmt in ("yolov5", "yolov8", "yolo"):
            if len(in_spec) != 1 or len(in_spec[0].shape) != 3:
                return None
            v8 = fmt == "yolov8"
            if v8:
                batch, c4, n = in_spec[0].shape  # channels-first (B,4+C,N)
                if c4 < 5:
                    return None
            else:
                batch, n, width = in_spec[0].shape
                if width < 5:
                    return None
            k = min(4 * self.max_detections, n)
            box_scale = jnp.asarray(self.box_scale, jnp.float32)

            def fn(arrays):
                pred = arrays[0].astype(jnp.float32)
                if v8:
                    pred = jnp.swapaxes(pred, 1, 2)  # -> (B, N, 4+C)
                    xywh = pred[..., :4] / box_scale
                    sc_all = pred[..., 4:]
                else:
                    xywh, obj, cls = (pred[..., :4], pred[..., 4],
                                      pred[..., 5:])
                    sc_all = (obj[..., None] * cls if cls.shape[-1]
                              else obj[..., None])
                classes = jnp.argmax(sc_all, axis=-1).astype(jnp.int32)
                sc = jnp.max(sc_all, axis=-1)
                top_sc, idx = lax.top_k(sc, k)
                cx, cy = xywh[..., 0], xywh[..., 1]
                w2, h2 = xywh[..., 2] / 2, xywh[..., 3] / 2
                boxes = jnp.stack(
                    [cx - w2, cy - h2, cx + w2, cy + h2], axis=-1)
                top_b = jnp.take_along_axis(boxes, idx[..., None], axis=1)
                top_c = jnp.take_along_axis(classes, idx, axis=1)
                return (top_b, top_sc, top_c)

        else:
            return None

        if self.nms_mode == "device":
            import jax

            from ..ops.nms import nms_jax

            m = self.max_detections
            thr, iou_thr = self.threshold, self.iou_threshold
            pack = self.out_mode == "tensors"

            def fn_nms(arrays):
                tb, ts, tc = fn(arrays)
                masked = jnp.where(ts >= thr, ts, -jnp.inf)

                def per_frame(b, s):
                    idx, valid = nms_jax(b, s, iou_thr, m)
                    return (jnp.take(b, idx, axis=0),
                            jnp.where(valid, jnp.take(s, idx), 0.0),
                            idx, valid)

                kb, ks, kidx, kv = jax.vmap(per_frame)(tb, masked)
                kc = jnp.take_along_axis(tc, kidx, axis=1)
                if pack:
                    # ONE [B, M, 7] tensor (x1 y1 x2 y2 score class valid):
                    # the D2H payload crosses the sink edge as a single
                    # transfer — each separate tensor pays its own D2H
                    # fetch roundtrip
                    return (jnp.concatenate(
                        [kb, ks[..., None], kc.astype(jnp.float32)[..., None],
                         kv.astype(jnp.float32)[..., None]], axis=-1),)
                return (kb, ks, kc, kv.astype(jnp.uint8))

            if pack:
                out_spec = TensorsSpec((
                    TensorSpec.from_shape((batch, m, 7), np.float32),))
            else:
                out_spec = TensorsSpec((
                    TensorSpec.from_shape((batch, m, 4), np.float32),
                    TensorSpec.from_shape((batch, m), np.float32),
                    TensorSpec.from_shape((batch, m), np.int32),
                    TensorSpec.from_shape((batch, m), np.uint8),
                ))
            return fn_nms, out_spec

        out_spec = TensorsSpec((
            TensorSpec.from_shape((batch, k, 4), np.float32),
            TensorSpec.from_shape((batch, k), np.float32),
            TensorSpec.from_shape((batch, k), np.int32),
        ))
        return fn, out_spec

    def host_post(self, arrays, buf: Buffer) -> Buffer:
        if self.out_mode == "tensors":
            return self._host_post_tensors(arrays, buf)
        tb = np.asarray(arrays[0], np.float32)
        ts = np.asarray(arrays[1], np.float32)
        tc = np.asarray(arrays[2])
        valid = np.asarray(arrays[3]).astype(bool) if len(arrays) > 3 else None
        b = tb.shape[0]
        canvas = np.zeros((b, self.out_h, self.out_w, 4), np.uint8)
        dets = []
        for i in range(b):
            if valid is not None:
                # device-NMS path: arrays ARE the final detections
                d = [
                    {
                        "box": [float(v) for v in tb[i, j]],
                        "score": float(ts[i, j]),
                        "class_index": int(tc[i, j]),
                        "label": (self.labels[int(tc[i, j])]
                                  if int(tc[i, j]) < len(self.labels)
                                  else str(int(tc[i, j]))),
                    }
                    for j in range(tb.shape[1]) if valid[i, j]
                ]
                self._draw_into(canvas[i], d)
            else:
                d = self._decode_dets(("triple", (tb[i], ts[i], tc[i])))
                self._draw_into(canvas[i], d)
            dets.append(d)
        if b == 1:
            new = buf.with_tensors([canvas[0]], spec=None)
            new.meta["detections"] = dets[0]
            return new
        new = buf.with_tensors([canvas], spec=None)
        new.meta["detections"] = dets
        return new

    def _host_post_tensors(self, arrays, buf: Buffer) -> Buffer:
        """option9=tensors sink edge: NO canvas, NO per-detection Python
        dicts — with device NMS ONE packed [B,M,7] array crossed D2H and
        unpacks here into (boxes [B,M,4], scores, classes, valid); with
        host NMS the greedy pass runs here and pads into the same
        layout.  Host work per batch is O(B*M) numpy, not O(B*H*W)
        pixels."""
        if len(arrays) == 1:  # device NMS emitted packed [B, M, 7]
            p = np.asarray(arrays[0], np.float32)
            return buf.with_tensors(
                [np.ascontiguousarray(p[..., :4]),
                 np.ascontiguousarray(p[..., 4]),
                 p[..., 5].astype(np.int32),
                 p[..., 6].astype(np.uint8)], spec=None)
        tb = np.asarray(arrays[0], np.float32)
        ts = np.asarray(arrays[1], np.float32)
        tc = np.asarray(arrays[2])
        b, m = tb.shape[0], self.max_detections
        boxes = np.zeros((b, m, 4), np.float32)
        scores = np.zeros((b, m), np.float32)
        classes = np.zeros((b, m), np.int32)
        valid = np.zeros((b, m), np.uint8)
        for i in range(b):
            d = self._decode_dets(("triple", (tb[i], ts[i], tc[i])))
            for j, det in enumerate(d[:m]):
                boxes[i, j] = det["box"]
                scores[i, j] = det["score"]
                classes[i, j] = det["class_index"]
                valid[i, j] = 1
        return buf.with_tensors([boxes, scores, classes, valid], spec=None)

    def _decode_ssd(self, tensors):
        boxes = np.asarray(tensors[0], np.float32).reshape(-1, 4)
        scores_all = np.asarray(tensors[1], np.float32)
        scores_all = scores_all.reshape(boxes.shape[0], -1)
        classes = scores_all.argmax(axis=1)
        scores = scores_all.max(axis=1)
        m = scores >= self.threshold
        return boxes[m], scores[m], classes[m]

    def _decode_yolo(self, tensors):
        pred = np.asarray(tensors[0], np.float32)
        pred = pred.reshape(-1, pred.shape[-1])
        xywh, obj, cls = pred[:, :4], pred[:, 4], pred[:, 5:]
        scores_all = obj[:, None] * cls if cls.size else obj[:, None]
        classes = scores_all.argmax(axis=1)
        scores = scores_all.max(axis=1)
        boxes = center_to_corner(xywh)
        m = scores >= self.threshold
        return boxes[m], scores[m], classes[m]

    def _decode_yolov8(self, tensors):
        # ultralytics export layout: (4+C, N) channels-first per frame,
        # anchor-free — class scores ARE the confidence (no objectness).
        pred = np.asarray(tensors[0], np.float32)
        if pred.ndim == 3:
            pred = pred.reshape(pred.shape[-2], pred.shape[-1])
        pred = pred.T  # (N, 4+C)
        xywh, cls = pred[:, :4], pred[:, 4:]
        classes = cls.argmax(axis=1)
        scores = cls.max(axis=1)
        boxes = center_to_corner(xywh / self.box_scale)
        m = scores >= self.threshold
        return boxes[m], scores[m], classes[m]

    def _draw(self, detections) -> np.ndarray:
        overlay = np.zeros((self.out_h, self.out_w, 4), np.uint8)
        self._draw_into(overlay, detections)
        return overlay

    def _draw_into(self, overlay: np.ndarray, detections) -> np.ndarray:
        """Draw in place — the batched host_post path allocates ONE
        [B, H, W, 4] canvas and draws each frame into its row view
        (per-frame zeros + a final np.stack copy were ~70% of the
        measured host_post time at batch 64)."""
        t = 2  # line thickness (reference draws 1px rectangles + label text)
        for d in detections:
            x1, y1, x2, y2 = d["box"]
            color = _PALETTE[d["class_index"] % len(_PALETTE)]
            px1 = int(np.clip(x1 * self.out_w, 0, self.out_w - 1))
            px2 = int(np.clip(x2 * self.out_w, 0, self.out_w - 1))
            py1 = int(np.clip(y1 * self.out_h, 0, self.out_h - 1))
            py2 = int(np.clip(y2 * self.out_h, 0, self.out_h - 1))
            overlay[py1 : py1 + t, px1:px2] = color
            overlay[max(0, py2 - t) : py2, px1:px2] = color
            overlay[py1:py2, px1 : px1 + t] = color
            overlay[py1:py2, max(0, px2 - t) : px2] = color
        return overlay
