"""pose_estimation decoder: heatmaps -> keypoints + skeleton overlay.

Reference analog: ``tensordec-pose.c`` (SURVEY §2.5, BASELINE config #3):
per-keypoint heatmaps -> argmax locations (scaled to output size) -> keypoint
dots + bone lines on an RGBA overlay; keypoints in meta.

Input contract: heatmaps tensor shaped (H', W', K) (numpy order; nnstreamer
dims K:W':H') — PoseNet-style.  Optional second tensor (K, 2) of short-range
offsets is added when present.

Options: option1=labels (keypoint names file), option2=WIDTH:HEIGHT of the
overlay (default 640:480), option3=score threshold, option4=output form
(``overlay`` default | ``tensors``: keypoint coordinates themselves as
(x f32 [K], y f32 [K], score f32 [K]) — batched [B,K] — with no skeleton
canvas; the indices-not-payloads treatment for headless serving).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..core.buffer import Buffer
from ..core.caps import Caps, MediaType
from ..core.registry import register_decoder
from ..core.types import TensorsSpec
from .base import Decoder, load_labels

# COCO-17 skeleton bones (keypoint index pairs)
_BONES = [
    (0, 1), (0, 2), (1, 3), (2, 4), (5, 6), (5, 7), (7, 9), (6, 8), (8, 10),
    (5, 11), (6, 12), (11, 12), (11, 13), (13, 15), (12, 14), (14, 16),
]


@register_decoder("pose_estimation")
class PoseEstimation(Decoder):
    mode = "pose_estimation"

    def __init__(self, props):
        super().__init__(props)
        size = self.option(2) or "640:480"
        w, h = size.split(":")
        self.out_w, self.out_h = int(w), int(h)
        self.threshold = float(self.option(3) or 0.3)
        out_mode = (self.option(4) or "overlay").lower()
        if out_mode not in ("overlay", "tensors"):
            raise ValueError(f"option4 (output form) must be "
                             f"overlay|tensors, got {out_mode!r}")
        self.out_mode = out_mode

    def out_caps(self, in_spec: Optional[TensorsSpec]) -> Caps:
        if self.out_mode == "tensors":
            return Caps.tensors()
        return Caps.new(
            MediaType.VIDEO, format="RGBA", width=self.out_w, height=self.out_h
        )

    def decode(self, tensors: List[np.ndarray], buf: Buffer) -> Buffer:
        hm = np.asarray(tensors[0], np.float32)
        if hm.ndim > 3:
            # Batched heatmaps [..., H', W', K]: decode each frame.
            lead = hm.shape[: hm.ndim - 3]
            n = int(np.prod(lead))
            frames = hm.reshape((n,) + hm.shape[-3:])
            if n > 1:
                rest = [np.asarray(t) for t in tensors[1:]]
                per_frame, kps = [], []
                for i in range(n):
                    sub = [frames[i]] + [
                        t[i] if t.shape[:1] == (n,) else t for t in rest
                    ]
                    o = self._decode_one(sub, buf)
                    per_frame.append(o.tensors)
                    kps.append(o.meta["keypoints"])
                # stack EVERY output tensor across frames: overlay mode has
                # one ([B,H,W,4]); tensors mode has three (px/py/score,
                # each [B,K]) — dropping to tensors[0] alone would lose y
                # and confidence in the batched host path
                stacked = [np.stack([f[t] for f in per_frame])
                           for t in range(len(per_frame[0]))]
                out = buf.with_tensors(stacked, spec=None)
                out.meta["keypoints"] = kps
                return out
            hm = frames[0]
        return self._decode_one([hm] + list(tensors[1:]), buf)

    def _coords(self, idx, off, hh: int, hw: int):
        """Flat heatmap argmax indices [..., K] -> (px, py) overlay pixel
        coords, same leading shape.  The ONLY place the scale/offset math
        lives: the host decode path and the fused ``host_post`` both call
        it, so they cannot diverge."""
        ys, xs = np.unravel_index(idx, (hh, hw))
        px = (xs + 0.5) / hw * self.out_w
        py = (ys + 0.5) / hh * self.out_h
        if off is not None:  # short-range offsets (..., K, 2) in cells
            px = px + off[..., 0] / hw * self.out_w
            py = py + off[..., 1] / hh * self.out_h
        return px, py

    def _keypoints(self, idx, scores, off, hh: int, hw: int):
        """Flat heatmap argmax indices -> keypoint dicts (host path)."""
        px, py = self._coords(idx, off, hh, hw)
        return [
            {"x": float(px[i]), "y": float(py[i]), "score": float(scores[i])}
            for i in range(len(idx))
        ]

    def _decode_one(self, tensors: List[np.ndarray], buf: Buffer) -> Buffer:
        hm = np.asarray(tensors[0], np.float32)
        hh, hw, k = hm.shape
        flat = hm.reshape(-1, k)
        idx = flat.argmax(axis=0)
        scores = flat[idx, np.arange(k)]
        off = (np.asarray(tensors[1], np.float32).reshape(-1, 2)[:k]
               if len(tensors) > 1 else None)
        keypoints = self._keypoints(idx, scores, off, hh, hw)
        if self.out_mode == "tensors":
            px, py = self._coords(idx, off, hh, hw)
            out = buf.with_tensors(
                [px.astype(np.float32), py.astype(np.float32),
                 scores.astype(np.float32)], spec=None)
        else:
            out = buf.with_tensors([self._draw(keypoints)], spec=None)
        out.meta["keypoints"] = keypoints
        return out

    # -- fusion ------------------------------------------------------------
    # Heatmap argmax runs inside the fused XLA program; only [B,K] indices
    # and scores (plus the first-K offset pairs, replicating the host
    # path's math bit-for-bit) cross to the host with async D2H in flight.
    # Keypoint dicts and the skeleton overlay resolve in ``host_post`` at
    # the sink edge.  Batched fused output is ONE buffer with stacked
    # overlays [B,H,W,4] (same shape the host path's batched decode emits).
    def device_fn(self, in_spec: TensorsSpec):
        import jax.numpy as jnp

        from ..core.types import TensorSpec

        shape = in_spec[0].shape
        if len(shape) != 4:
            return None
        batch, hh, hw, k = shape
        self._fused_grid = (hh, hw)
        have_off = len(in_spec) > 1

        pack = self.out_mode == "tensors"

        def fn(arrays):
            hm = arrays[0].astype(jnp.float32)
            b = hm.shape[0]
            flat = hm.reshape(b, -1, k)
            idx = jnp.argmax(flat, axis=1).astype(jnp.int32)  # [B, K]
            score = jnp.take_along_axis(flat, idx[:, None, :], axis=1)[:, 0]
            outs = [idx, score.astype(jnp.float32)]
            if have_off:
                off = arrays[1].astype(jnp.float32).reshape(b, -1, 2)[:, :k]
                outs.append(off)
            if pack:
                # ONE [B, K, 2(+2)] f32 payload (idx, score[, off]): a
                # single D2H transfer instead of 2-3 — each separate
                # tensor pays its own fetch roundtrip.  idx as f32 is
                # exact (heatmap cells << 2^24).
                cols = [outs[0].astype(jnp.float32)[..., None],
                        outs[1][..., None]]
                if have_off:
                    cols.append(outs[2])
                return (jnp.concatenate(cols, axis=-1),)
            return tuple(outs)

        if pack:
            return fn, TensorsSpec((TensorSpec.from_shape(
                (batch, k, 4 if have_off else 2), np.float32),))
        specs = [
            TensorSpec.from_shape((batch, k), np.int32),
            TensorSpec.from_shape((batch, k), np.float32),
        ]
        if have_off:
            specs.append(TensorSpec.from_shape((batch, k, 2), np.float32))
        return fn, TensorsSpec(tuple(specs))

    def host_post(self, arrays, buf: Buffer) -> Buffer:
        hh, hw = self._fused_grid
        if len(arrays) == 1:  # packed tensors-mode payload [B, K, 2(+2)]
            p = np.asarray(arrays[0], np.float32)
            idx = p[..., 0].astype(np.int64)
            scores = p[..., 1]
            off = p[..., 2:4] if p.shape[-1] >= 4 else None
        else:
            idx = np.asarray(arrays[0])
            scores = np.asarray(arrays[1], np.float32)
            off = (np.asarray(arrays[2], np.float32)
                   if len(arrays) > 2 else None)
        b, k = idx.shape
        # Batched coordinates via the shared _coords math; the vectorized
        # batch draw replaced a per-frame python loop that dominated the
        # pull path at ~30 ms per 64-batch.
        px, py = self._coords(idx, off, hh, hw)
        if self.out_mode == "tensors":
            # keypoints themselves, no canvas and no per-dict Python:
            # O(B*K) floats cross the sink edge instead of O(B*H*W) pixels
            return buf.with_tensors(
                [px.astype(np.float32), py.astype(np.float32),
                 scores.astype(np.float32)], spec=None)
        kps_all = [
            [
                {"x": float(px[i, j]), "y": float(py[i, j]),
                 "score": float(scores[i, j])}
                for j in range(k)
            ]
            for i in range(b)
        ]
        overlays = self._draw_batch(px, py, scores)  # [B, H, W, 4]
        if b == 1:
            new = buf.with_tensors([overlays[0]], spec=None)
            new.meta["keypoints"] = kps_all[0]
            return new
        new = buf.with_tensors([overlays], spec=None)
        new.meta["keypoints"] = kps_all
        return new

    def _draw_batch(self, px, py, scores, n: int = 64) -> np.ndarray:
        """All frames' overlays in a few vectorized scatters — pixel-equal
        to per-frame :meth:`_draw` (bones first, then dots; same clipping).
        px/py/scores: [B, K] arrays."""
        b, k = px.shape
        h, w = self.out_h, self.out_w
        overlay = np.zeros((b, h, w, 4), np.uint8)
        green = np.array([60, 220, 60, 255], np.uint8)
        white = np.array([255, 255, 255, 255], np.uint8)
        ok = scores >= self.threshold  # [B, K]
        fi = np.arange(b)[:, None]
        for a, c in _BONES:
            if a >= k or c >= k:
                continue
            # [B, n] interpolated line points per frame — np.linspace with
            # array endpoints: bit-identical to the per-frame _line math
            xs = np.linspace(px[:, a], px[:, c], n, axis=1).astype(int)
            ys = np.linspace(py[:, a], py[:, c], n, axis=1).astype(int)
            m = (ok[:, a] & ok[:, c])[:, None] & (xs >= 0) & (xs < w) & \
                (ys >= 0) & (ys < h)
            fr = np.broadcast_to(fi, xs.shape)
            overlay[fr[m], ys[m], xs[m]] = white
        # dots: 6x6 patch at each confident keypoint (rows y-3..y+2)
        dy, dx = np.meshgrid(np.arange(-3, 3), np.arange(-3, 3),
                             indexing="ij")
        yy = py.astype(int)[:, :, None, None] + dy  # [B, K, 6, 6]
        xx = px.astype(int)[:, :, None, None] + dx
        m = ok[:, :, None, None] & (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        fr = np.broadcast_to(np.arange(b)[:, None, None, None], yy.shape)
        overlay[fr[m], yy[m], xx[m]] = green
        return overlay

    def _draw(self, kps) -> np.ndarray:
        overlay = np.zeros((self.out_h, self.out_w, 4), np.uint8)
        green = np.array([60, 220, 60, 255], np.uint8)
        white = np.array([255, 255, 255, 255], np.uint8)
        for a, b in _BONES:
            if a < len(kps) and b < len(kps):
                ka, kb = kps[a], kps[b]
                if ka["score"] >= self.threshold and kb["score"] >= self.threshold:
                    self._line(overlay, ka, kb, white)
        for kp in kps:
            if kp["score"] >= self.threshold:
                x, y = int(kp["x"]), int(kp["y"])
                # clamp BOTH ends: a negative stop (keypoint far off-screen)
                # would wrap around and paint a near-full-width band
                overlay[
                    max(0, y - 3) : max(0, y + 3),
                    max(0, x - 3) : max(0, x + 3),
                ] = green
        return overlay

    def _line(self, img, ka, kb, color, n: int = 64):
        xs = np.linspace(ka["x"], kb["x"], n).astype(int)
        ys = np.linspace(ka["y"], kb["y"], n).astype(int)
        m = (xs >= 0) & (xs < img.shape[1]) & (ys >= 0) & (ys < img.shape[0])
        img[ys[m], xs[m]] = color
