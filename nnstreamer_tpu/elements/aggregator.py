"""tensor_aggregator: window/stride accumulation along a dim.

Reference analog: ``gsttensor_aggregator.c`` (SURVEY §2.2) — concatenate N
frames along an axis with flush control; the time-series/audio windowing
primitive (and the closest thing the reference has to sequence-dimension
machinery, §5.7).

Props (reference names):
* ``frames-in``    — frames contained in one incoming buffer (along the dim)
* ``frames-out``   — frames per outgoing buffer (window size)
* ``frames-flush`` — frames to drop after each output (stride; 0 => frames-out,
                     i.e. non-overlapping windows)
* ``frames-dim``   — nnstreamer dim index to count frames along
* ``concat``       — true (default): one concatenated tensor per window;
                     false: the window's frames stay separate tensors in one
                     buffer (the reference's multi-GstMemory buffer analog)

TPU-first extension — **device mode** (``device=true``, docs/ARCHITECTURE.md
"Streaming state"): the concat/window carry lives as an HBM-RESIDENT ring
between dispatches instead of a host ``np.concatenate``.  The host path
fetches every incoming buffer to host, concatenates, slices, and re-uploads
downstream — for a windowed audio pipeline that is one full D2H+H2D round
trip per window, during which the device idles.  In device mode the ring update runs IN-PROGRAM:

* the carry is a fixed-shape jax Array of ``need + step`` samples along the
  frames axis (``need`` = window, ``step`` = samples per incoming buffer);
* appends are ``lax.dynamic_update_slice`` at a TRACED write offset —
  offsets are runtime values, not shapes, so advancing the window never
  recompiles;
* window emission slices the ring head and advances by ``frames-flush``
  via a static ``jnp.roll`` in the same program.

Exactly THREE programs run for the stage's lifetime (ring init, append,
window+advance) — the same fixed-signature discipline as the continuous
LLM serving loop's 3-program pin — and emitted windows are device arrays:
an ``aggregator ! tensor_filter`` chain passes state filter-ward with ZERO
d2h between window dispatches (pinned by tests/test_aggregator_device.py's
transfer trap).  Window outputs are bit-identical to the host path (pure
data movement, no arithmetic).  The deep lint prices the ring
(``analysis/tracecheck.py``: "agg ring" bytes + the 3-program census) and
the residency planner counts the downstream edge device-resident.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..core.buffer import Buffer
from ..core.caps import Caps, MediaType
from ..core.registry import register_element
from ..core.types import TensorSpec, TensorsSpec
from .base import Element, ElementError, SRC


@register_element("tensor_aggregator")
class TensorAggregator(Element):
    kind = "tensor_aggregator"
    PAD_TEMPLATES = {"sink": Caps.new(MediaType.TENSORS)}

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.frames_in = int(self.props.get("frames_in", 1))
        self.frames_out = int(self.props.get("frames_out", 1))
        self.frames_flush = int(self.props.get("frames_flush", 0)) or self.frames_out
        self.frames_dim = int(self.props.get("frames_dim", 3))
        self.concat = str(self.props.get("concat", "true")).lower() not in (
            "false", "0", "no",
        )
        self.device = str(self.props.get("device", "false")).lower() in (
            "true", "1", "yes",
        )
        if self.device and not self.concat:
            raise ElementError(
                "tensor_aggregator device=true requires concat=true (the "
                "HBM ring carries ONE windowed tensor; multi-tensor "
                "windows stay on the host path)")
        #: read by the residency planner: downstream edges carry device
        #: arrays (the ring head), so they count device-resident
        self.device_resident = self.device
        self._window: Optional[np.ndarray] = None
        self._axis: Optional[int] = None
        # device mode: HBM ring + valid-sample watermark + the 3 jitted
        # programs (built lazily at first buffer — construction and
        # negotiation stay backend-free)
        self._ring = None
        self._valid = 0
        self._progs = None

    def configure(self, in_caps, out_pads):
        self.in_caps = dict(in_caps)
        src = next(iter(in_caps.values()), Caps.any())
        spec = src.spec
        out_spec = None
        if spec is not None and len(spec) == 1:
            dims = list(spec[0].dims)
            if self.frames_dim >= len(dims):
                raise ElementError(
                    f"frames-dim {self.frames_dim} out of range for rank {len(dims)}"
                )
            frame = dims[self.frames_dim] // self.frames_in
            if self.concat:
                dims[self.frames_dim] = frame * self.frames_out
                out_spec = TensorsSpec(
                    (TensorSpec(tuple(dims), spec[0].dtype),), rate=spec.rate
                )
            else:
                dims[self.frames_dim] = frame
                one = TensorSpec(tuple(dims), spec[0].dtype)
                out_spec = TensorsSpec(
                    tuple(one for _ in range(self.frames_out)), rate=spec.rate
                )
        caps = Caps.tensors(out_spec)
        self.out_caps = {p: caps for p in out_pads}
        return self.out_caps

    # -- device mode: HBM-resident ring ------------------------------------
    def _build_device_programs(self, shape, dtype):
        """Build the stage's THREE lifetime programs from the first
        buffer's signature (fixed shapes; the append offset and window
        advance are runtime VALUES, so nothing here ever recompiles
        across window advances — the zero-recompile pin)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        axis = len(shape) - 1 - self.frames_dim
        step = shape[axis]
        frame_len = step // self.frames_in
        need = self.frames_out * frame_len
        stride = self.frames_flush * frame_len
        ring_shape = list(shape)
        ring_shape[axis] = need + step
        ring_shape = tuple(ring_shape)

        def append(ring, x, valid):
            start = [jnp.int32(0)] * len(shape)
            start[axis] = valid
            return lax.dynamic_update_slice(ring, x, tuple(start))

        def window_advance(ring):
            win = lax.slice_in_dim(ring, 0, need, axis=axis)
            return jnp.roll(ring, -stride, axis=axis), win

        self._dev_axis, self._dev_step = axis, step
        self._dev_need, self._dev_stride = need, stride
        self._progs = {
            "init": jax.jit(lambda: jnp.zeros(ring_shape, dtype)),
            "append": jax.jit(append),
            "window": jax.jit(window_advance),
        }
        xr = getattr(self, "_xray", None)
        if xr is not None:
            # nns-xray: exactly the 3 lifetime programs the deep lint
            # prices (analysis/tracecheck.AGGREGATOR_PROGRAMS) — a 4th
            # compile (a re-specializing upstream) is census drift
            xr.expect(self.name, "agg", budget=3,
                      note="device-aggregator 3-program ring")
            rec = getattr(self, "_trace_rec", None)
            self._progs = {k: xr.track(p, self.name, "agg", rec=rec)
                           for k, p in self._progs.items()}
        return self._progs

    def _process_device(self, buf: Buffer):
        """One ring update per buffer, zero host round-trips: append the
        incoming samples at the valid watermark (in-program), then emit
        every complete window as a DEVICE-array slice of the ring head,
        advancing by the flush stride.  The watermark is a host-side
        Python int — a value the programs take as an argument, never a
        shape — so occupancy changes cost nothing."""
        import jax.numpy as jnp

        if len(buf.tensors) != 1:
            raise ElementError(
                "tensor_aggregator device=true aggregates ONE tensor per "
                f"buffer, got {len(buf.tensors)}")
        x = buf.tensors[0]
        if not hasattr(x, "addressable_shards") \
                and not type(x).__module__.startswith("jax"):
            # host ingest boundary: one H2D here, then the ring never
            # leaves HBM again
            x = jnp.asarray(x)
        progs = self._progs or self._build_device_programs(
            tuple(x.shape), np.dtype(x.dtype))
        if self._ring is None:
            self._ring = progs["init"]()
            self._valid = 0
        self._ring = progs["append"](self._ring, x, self._valid)
        self._valid += self._dev_step
        outs: List = []
        while self._valid >= self._dev_need:
            self._ring, win = progs["window"](self._ring)
            # host semantics: dropping past the end of the window forgets
            # at most what exists (an over-long flush never carries debt)
            self._valid = max(0, self._valid - self._dev_stride)
            outs.append((SRC, buf.with_tensors([win], spec=None)))
        return outs

    def process(self, pad, buf: Buffer):
        if self.device:
            return self._process_device(buf)
        x = np.asarray(buf.tensors[0])
        axis = x.ndim - 1 - self.frames_dim
        if self._window is None:
            self._window = x
            self._axis = axis
        else:
            self._window = np.concatenate([self._window, x], axis=axis)
        outs: List = []
        # one incoming buffer carries frames_in frames; window counts frames
        frame_len = x.shape[axis] // self.frames_in  # samples per frame
        need = self.frames_out * frame_len
        stride = self.frames_flush * frame_len
        while self._window.shape[axis] >= need:
            sl = [slice(None)] * self._window.ndim
            sl[axis] = slice(0, need)
            window = self._window[tuple(sl)]
            if self.concat:
                tensors = [window]
            else:
                tensors = []
                for i in range(self.frames_out):
                    fsl = [slice(None)] * window.ndim
                    fsl[axis] = slice(i * frame_len, (i + 1) * frame_len)
                    tensors.append(window[tuple(fsl)])
            outs.append((SRC, buf.with_tensors(tensors, spec=None)))
            keep = [slice(None)] * self._window.ndim
            keep[axis] = slice(stride, None)
            self._window = self._window[tuple(keep)]
        return outs

    def finalize(self):
        # both paths drop partial windows at EOS (the reference's
        # behavior); device mode also releases the ring's HBM
        self._window = None
        self._ring = None
        self._valid = 0
        return []

    def stop(self) -> None:
        self._ring = None
        self._progs = None
        self._valid = 0
