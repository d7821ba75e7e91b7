"""Source elements: app feed + deterministic test sources.

Reference analogs: ``appsrc``, ``videotestsrc``, ``audiotestsrc``,
``filesrc`` (GStreamer base plugins used throughout the reference's SSAT
suites as deterministic inputs — SURVEY §4), and ``datareposrc`` lives in
elements/datarepo.py.

TPU-first note: sources are host elements by definition (camera/file/app
ingest).  They produce host numpy buffers; the first fused device stage
downstream does one `device_put` per buffer and everything after stays in
HBM.
"""

from __future__ import annotations

import queue as _queue
import threading
import time as _time
from typing import Iterator, Optional, Union

import numpy as np

from ..core.buffer import Buffer, Event
from ..core.caps import Caps, MediaType, parse_caps_string, video_bpp
from ..core.meta_keys import META_TENANT
from ..core.log import STALL_FLOOR_S
from ..core.log import metrics as _metrics
from ..core.registry import register_element
from ..core.types import TensorsSpec, parse_fraction
from .base import ElementError, SourceElement, SRC


class _InflightCredit:
    """End-to-end admission token (``appsrc max-inflight=N``): released
    the FIRST time this buffer — or any buffer derived from it; meta
    copies share the token by reference — reaches a sink, and as a safety
    net when every derived buffer is garbage-collected (drop/eviction
    paths must never leak a credit and deadlock the pusher)."""

    __slots__ = ("_sem", "_done", "_lock")

    def __init__(self, sem: threading.Semaphore):
        self._sem = sem
        self._done = False
        self._lock = threading.Lock()

    def release(self) -> None:
        with self._lock:
            if self._done:
                return
            self._done = True
        self._sem.release()

    def __del__(self):  # drop-path safety net
        try:
            self.release()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass


@register_element("appsrc")
class AppSrc(SourceElement):
    """Application-driven source: ``pipeline.push(name, array)`` feeds it.

    A pushed array is taken by reference and belongs to the pipeline from
    then on: a stage thread reads it later, and the device transfer that
    reads it is asynchronous (the CPU client aliases aligned numpy memory
    outright).  An app that reuses its frame buffer pushes a copy.

    Props: ``caps`` (caps string describing what the app will push),
    ``max-buffers`` (feed queue bound), ``block`` (push blocks when full),
    ``max-inflight`` (END-TO-END admission bound: at most N pushed buffers
    anywhere between this source and a sink; push blocks past that.  The
    per-stage queues bound memory, but on a transport-saturated pipeline
    they still let queue-depth x batch-time of latency build up ahead of
    every frame — the reference gets the same effect from short GStreamer
    queues; here one credit spans the whole pipeline),
    ``tenant`` (tenant identity stamped into every pushed buffer's meta —
    rides the query wire so a remote server's per-tenant accounting and
    admission control see it; an explicit prop is app DATA, stamped
    regardless of trace mode — docs/SERVING.md "Front door").
    """

    kind = "appsrc"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        cap = self.props.get("caps")
        self._caps = parse_caps_string(str(cap)) if cap else Caps.any()
        self.tenant = str(self.props.get("tenant", "") or "") or None
        self.block = bool(self.props.get("block", True))
        # block=false matches GStreamer appsrc semantics: push never blocks
        # and the feed queue grows unbounded (max-buffers is the bound only
        # in blocking mode — still read unconditionally so the pairing
        # block=false max-buffers=N stays a legal property set).
        cap_n = int(self.props.get("max_buffers", 64))
        self._q: _queue.Queue = _queue.Queue(
            maxsize=cap_n if self.block else 0)
        self._eos = threading.Event()
        n_inflight = int(self.props.get("max_inflight", 0))
        self._inflight_sem = (threading.Semaphore(n_inflight)
                              if n_inflight > 0 else None)

    def configure(self, in_caps, out_pads):
        self.out_caps = {p: self._caps for p in out_pads}
        return self.out_caps

    # -- app API -----------------------------------------------------------
    def push(self, data, pts: Optional[int] = None) -> None:
        if self._eos.is_set():
            raise RuntimeError("appsrc already EOS")
        if isinstance(data, Buffer):
            buf = data
        elif isinstance(data, (list, tuple)):
            buf = Buffer(list(data), pts=pts)
        elif isinstance(data, str):
            buf = Buffer([np.frombuffer(data.encode("utf-8"), np.uint8)], pts=pts)
        elif isinstance(data, (bytes, bytearray)):
            buf = Buffer([np.frombuffer(bytes(data), np.uint8)], pts=pts)
        else:
            buf = Buffer([np.asarray(data)], pts=pts)
        if self.tenant is not None and META_TENANT not in buf.meta:
            buf.meta[META_TENANT] = self.tenant
        if self._inflight_sem is not None:
            stop = getattr(self, "_stop_event", None)
            t0 = _time.perf_counter()
            while not self._inflight_sem.acquire(timeout=0.1):
                if self._eos.is_set() or (stop is not None
                                          and stop.is_set()):
                    raise RuntimeError("appsrc stopping; push abandoned")
            # h2d-wait accounting (the ingress half of the stall split;
            # the sink counts the d2h half): time the PUSH blocked on
            # admission is the transport/backlog wait, distinct from the
            # pull-side fetch wait that used to be conflated with it in
            # one rtt_stalls number.
            wait = _time.perf_counter() - t0
            _metrics.count(f"{self.name}.h2d_wait_ms", wait * 1e3)
            if wait > STALL_FLOOR_S:
                _metrics.count(f"{self.name}.h2d_stalls")
            buf.meta["_inflight_credit"] = _InflightCredit(
                self._inflight_sem)
        self._q.put(buf)

    def signal_eos(self) -> None:
        self._eos.set()

    def generate(self) -> Iterator[Union[Buffer, Event]]:
        stop = getattr(self, "_stop_event", None)
        while True:
            try:
                yield self._q.get(timeout=0.05)
            except _queue.Empty:
                if self._eos.is_set() and self._q.empty():
                    return
                # stop() without EOS: exit instead of pinning the runner
                # thread on the join timeout (pipeline teardown, not EOS)
                if stop is not None and stop.is_set():
                    return


@register_element("videotestsrc")
class VideoTestSrc(SourceElement):
    """Deterministic video frames (reference test pipelines' workhorse).

    Props: ``width``, ``height``, ``format`` (RGB/BGR/RGBA/GRAY8),
    ``num-buffers``, ``pattern`` (``smpte`` gradient, ``ball``, ``black``,
    ``white``, ``random`` with fixed seed), ``framerate``.

    TPU-first extension: ``device=true`` generates the pattern **on
    device** as a jitted XLA program and emits batched ``other/tensors``
    buffers (``batch`` frames per buffer) that stay in HBM — a synthetic
    source with zero host->device traffic, the TPU-native analog of the
    reference benchmarking against videotestsrc.  The gradient/ball math
    is bit-identical to the host path.
    """

    kind = "videotestsrc"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.width = int(self.props.get("width", 320))
        self.height = int(self.props.get("height", 240))
        self.format = str(self.props.get("format", "RGB"))
        self.num_buffers = int(self.props.get("num_buffers", -1))
        self.pattern = str(self.props.get("pattern", "smpte"))
        self.rate = parse_fraction(self.props.get("framerate", (30, 1)))
        self.device = bool(self.props.get("device", False))
        self.batch = int(self.props.get("batch", 1))

    def configure(self, in_caps, out_pads):
        if self.device:
            c = video_bpp(self.format)
            spec = TensorsSpec.from_string(
                f"{c}:{self.width}:{self.height}:{self.batch}", "uint8"
            )
            caps = Caps.tensors(spec)
        else:
            caps = Caps.new(
                MediaType.VIDEO,
                format=self.format,
                width=self.width,
                height=self.height,
                framerate=self.rate,
            )
        self.out_caps = {p: caps for p in out_pads}
        return self.out_caps

    def _frame(self, i: int) -> np.ndarray:
        c = video_bpp(self.format)
        h, w = self.height, self.width
        if self.pattern == "black":
            f = np.zeros((h, w, c), np.uint8)
        elif self.pattern == "white":
            f = np.full((h, w, c), 255, np.uint8)
        elif self.pattern == "random":
            rng = np.random.default_rng(i)
            f = rng.integers(0, 256, size=(h, w, c), dtype=np.uint8)
        elif self.pattern == "ball":
            f = np.zeros((h, w, c), np.uint8)
            cy = (i * 7) % h
            cx = (i * 11) % w
            yy, xx = np.ogrid[:h, :w]
            mask = (yy - cy) ** 2 + (xx - cx) ** 2 <= (min(h, w) // 8) ** 2
            f[mask] = 255
        else:  # smpte-ish deterministic gradient
            yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
            base = (xx * 255 // max(1, w - 1) + yy + i) % 256
            f = np.stack([(base + 85 * k) % 256 for k in range(c)], axis=-1).astype(np.uint8)
        return f

    def _device_batch_fn(self):
        import jax
        import jax.numpy as jnp

        h, w, c = self.height, self.width, video_bpp(self.format)
        pattern = self.pattern

        def one(i):
            yy, xx = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
            if pattern == "black":
                return jnp.zeros((h, w, c), jnp.uint8)
            if pattern == "white":
                return jnp.full((h, w, c), 255, jnp.uint8)
            if pattern == "random":
                key = jax.random.PRNGKey(0)
                return jax.random.randint(
                    jax.random.fold_in(key, i), (h, w, c), 0, 256, jnp.int32
                ).astype(jnp.uint8)
            if pattern == "ball":
                cy = (i * 7) % h
                cx = (i * 11) % w
                mask = (yy - cy) ** 2 + (xx - cx) ** 2 <= (min(h, w) // 8) ** 2
                f = jnp.zeros((h, w), jnp.uint8)
                f = jnp.where(mask, jnp.uint8(255), f)
                return jnp.broadcast_to(f[:, :, None], (h, w, c))
            # smpte-ish gradient — bit-identical to the host _frame math
            base = (xx * 255 // max(1, w - 1) + yy + i) % 256
            return jnp.stack(
                [(base + 85 * k) % 256 for k in range(c)], axis=-1
            ).astype(jnp.uint8)

        @jax.jit
        def make(i0):
            return jax.vmap(one)(i0 + jnp.arange(self.batch))

        return make

    def generate(self):
        num = self.num_buffers if self.num_buffers >= 0 else 1 << 62
        frame_ns = int(1e9 * self.rate[1] / max(1, self.rate[0]))
        if self.device:
            make = self._device_batch_fn()
            # num-buffers counts FRAMES (host-path contract); the device
            # path emits full batches and truncates the tail batch so the
            # total frame count matches exactly.  The frame index wraps at
            # 2^30 (int32-safe under jit; patterns repeat anyway at far
            # shorter periods, so the seam is invisible).
            emitted = 0
            i = 0
            while emitted < num:
                arr = make((i * self.batch) % (1 << 30))
                take = min(self.batch, num - emitted)
                if take < self.batch:
                    arr = arr[:take]
                yield Buffer([arr], pts=emitted * frame_ns)
                emitted += take
                i += 1
            return
        for i in range(num):
            yield Buffer([self._frame(i)], pts=i * frame_ns)


@register_element("audiotestsrc")
class AudioTestSrc(SourceElement):
    """Deterministic audio: sine wave.  Props: ``freq``, ``samplesperbuffer``,
    ``num-buffers``, ``rate``, ``channels``, ``format`` (S16LE/F32LE/U8).

    TPU-first extension (same shape as videotestsrc's): ``device=true``
    synthesizes the sine **on device** as a jitted XLA program and emits
    batched float32 ``other/tensors`` windows ``[batch, samplesperbuffer]``
    that stay in HBM — zero host->device traffic.  In device mode
    ``num-buffers`` counts WINDOWS (the frame analog), channels=1, and the
    format is float32.
    """

    kind = "audiotestsrc"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.freq = float(self.props.get("freq", 440.0))
        self.spb = int(self.props.get("samplesperbuffer", 1024))
        self.num_buffers = int(self.props.get("num_buffers", -1))
        self.sample_rate = int(self.props.get("rate", 44100))
        self.channels = int(self.props.get("channels", 1))
        self.format = str(self.props.get("format", "S16LE"))
        self.device = bool(self.props.get("device", False))
        self.batch = int(self.props.get("batch", 1))

    def configure(self, in_caps, out_pads):
        if self.device:
            spec = TensorsSpec.from_string(
                f"{self.spb}:{self.batch}", "float32")
            caps = Caps.tensors(spec)
        else:
            caps = Caps.new(
                MediaType.AUDIO,
                format=self.format,
                rate=self.sample_rate,
                channels=self.channels,
            )
        self.out_caps = {p: caps for p in out_pads}
        return self.out_caps

    def _device_batch_fn(self):
        import jax
        import jax.numpy as jnp

        spb, rate, freq = self.spb, self.sample_rate, self.freq

        def one(n0, j):  # batch row j -> [spb] float32 sine
            # Exact int32 sample index folded by the sample rate: for
            # integer freq, n -> n+rate shifts phase by whole cycles (sin
            # unchanged), and n < rate keeps float32 phase math exact.
            # n0 < rate (caller folds with Python ints — no overflow) and
            # j*spb <= batch*spb, so the sum stays well within int32.
            n = jnp.mod(n0 + j * spb + jnp.arange(spb, dtype=jnp.int32), rate)
            return jnp.sin(2 * jnp.pi * freq * n.astype(jnp.float32) / rate)

        @jax.jit
        def make(n0):
            return jax.vmap(lambda j: one(n0, j))(jnp.arange(self.batch))

        return make

    def generate(self):
        num = self.num_buffers if self.num_buffers >= 0 else 1 << 62
        if self.device:
            make = self._device_batch_fn()
            emitted = 0
            i = 0
            while emitted < num:
                # Base sample index folded by `rate` in exact Python ints
                # (exact wrap: see _device_batch_fn).
                arr = make((i * self.batch * self.spb) % self.sample_rate)
                take = min(self.batch, num - emitted)
                if take < self.batch:
                    arr = arr[:take]
                pts = int(1e9 * emitted * self.spb / self.sample_rate)
                yield Buffer([arr], pts=pts)
                emitted += take
                i += 1
            return
        t0 = 0
        for i in range(num):
            n = np.arange(t0, t0 + self.spb, dtype=np.float64)
            wave = np.sin(2 * np.pi * self.freq * n / self.sample_rate)
            if self.format == "S16LE":
                samples = (wave * 32767).astype(np.int16)
            elif self.format == "U8":
                samples = ((wave * 0.5 + 0.5) * 255).astype(np.uint8)
            else:
                samples = wave.astype(np.float32)
            frame = np.repeat(samples[:, None], self.channels, axis=1)
            pts = int(1e9 * t0 / self.sample_rate)
            t0 += self.spb
            yield Buffer([frame], pts=pts)


@register_element("filesrc")
class FileSrc(SourceElement):
    """Whole-file byte source (``application/octet-stream``).

    Props: ``location``, ``blocksize`` (0 = whole file in one buffer).
    """

    kind = "filesrc"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.location = str(self.props.get("location", ""))
        self.blocksize = int(self.props.get("blocksize", 0))

    def configure(self, in_caps, out_pads):
        caps = Caps.new(MediaType.OCTET)
        self.out_caps = {p: caps for p in out_pads}
        return self.out_caps

    def generate(self):
        with open(self.location, "rb") as f:
            data = f.read()
        if self.blocksize <= 0:
            yield Buffer([np.frombuffer(data, np.uint8)])
            return
        for off in range(0, len(data), self.blocksize):
            yield Buffer([np.frombuffer(data[off : off + self.blocksize], np.uint8)])


#: IIO scan-element wire formats: name -> (numpy dtype, is_signed)
_IIO_FORMATS = {
    "s16le": np.dtype("<i2"), "u16le": np.dtype("<u2"),
    "s32le": np.dtype("<i4"), "u32le": np.dtype("<u4"),
    "s8": np.dtype("i1"), "u8": np.dtype("u1"),
    "f32le": np.dtype("<f4"), "f64le": np.dtype("<f8"),
}


@register_element("tensor_src_iio")
class TensorSrcIIO(SourceElement):
    """Industrial-I/O sensor source (reference: ``gsttensor_srciio.c``).

    The reference reads buffered scans from an IIO character device
    (``/dev/iio:deviceN``): interleaved per-channel raw samples, converted
    to processed values via each channel's scale/offset, ``buffer-capacity``
    samples per emitted buffer, paced by a trigger.  This element keeps
    those semantics against any byte stream:

    * ``device=<path>`` — a file, FIFO, or char device of interleaved raw
      records; ``device=tcp://host:port`` — the same records over a socket
      (sensors are remote in a TPU-pod deployment).
    * ``scan-format`` (default ``s16le``) — per-channel wire format;
      ``channels`` — channels per record; processed value =
      ``(raw + offset) * scale`` (IIO convention; default offset 0 scale 1).
    * ``buffer-capacity`` samples per emitted ``[capacity, channels]``
      float32 tensor; short tail reads are dropped (a partial scan never
      violates the negotiated caps).
    * ``trigger=data`` (default) emits as soon as a full scan is read;
      ``trigger=timer`` paces emission at ``frequency`` Hz (the reference's
      sysfs-trigger analog).
    * With no ``device``, a pluggable ``sampler`` callable (or the builtin
      deterministic pseudo-sensor) generates samples — the hermetic-test
      mode, also used when no sensor bus exists.
    """

    kind = "tensor_src_iio"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.frequency = float(self.props.get("frequency", 100.0))
        self.capacity = int(self.props.get("buffer_capacity", 16))
        self.channels = int(self.props.get("channels", 3))
        self.num_buffers = int(self.props.get("num_buffers", 16))
        self.sampler = self.props.get("sampler")  # callable i -> np[channels]
        self.device = str(self.props.get("device", "") or "")
        fmt = str(self.props.get("scan_format", "s16le")).lower()
        if fmt not in _IIO_FORMATS:
            raise ElementError(
                f"{self.name}: unknown scan-format {fmt!r} "
                f"(one of {sorted(_IIO_FORMATS)})")
        self.scan_dtype = _IIO_FORMATS[fmt]
        self.scale = float(self.props.get("scale", 1.0))
        self.offset = float(self.props.get("offset", 0.0))
        self.trigger = str(self.props.get("trigger", "data")).lower()
        if self.trigger not in ("data", "timer"):
            raise ElementError(
                f"{self.name}: trigger must be data|timer, got {self.trigger!r}")
        self._fd = None
        self._sock = None
        self._is_fifo = False
        self._saw_data = False

    def configure(self, in_caps, out_pads):
        spec = TensorsSpec.from_string(
            f"{self.channels}:{self.capacity}", "float32"
        )
        caps = Caps.tensors(spec)
        self.out_caps = {p: caps for p in out_pads}
        return self.out_caps

    # -- device backend ----------------------------------------------------
    def start(self) -> None:
        if not self.device:
            return
        if self.device.startswith("tcp://"):
            import socket as _socket

            host, port = self.device[6:].rsplit(":", 1)
            try:
                sock = _socket.create_connection((host, int(port)), timeout=5.0)
            except OSError as e:
                raise ElementError(
                    f"{self.name}: cannot reach sensor stream "
                    f"{self.device}: {e}") from e
            # Short timeout: _read_scan polls the stop event between
            # recv()s, so a paused sender never blocks pipeline shutdown.
            sock.settimeout(0.2)
            self._sock = sock
            self._fd = None
        else:
            import os as _os

            try:
                # O_NONBLOCK: FIFOs/char devices must never block shutdown —
                # _read_scan polls the stop event between reads.  Harmless
                # for regular files.
                self._fd = _os.open(self.device,
                                    _os.O_RDONLY | _os.O_NONBLOCK)
                import stat as _stat

                self._is_fifo = _stat.S_ISFIFO(_os.fstat(self._fd).st_mode)
            except OSError as e:
                raise ElementError(
                    f"{self.name}: cannot open device {self.device!r}: {e}"
                ) from e

    def stop(self) -> None:
        fd = getattr(self, "_fd", None)
        if fd is not None:
            import os as _os

            try:
                _os.close(fd)
            except OSError:
                pass
            self._fd = None
        sock = getattr(self, "_sock", None)
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
            self._sock = None

    def _read_scan(self, stop) -> Optional[np.ndarray]:
        """One full buffered scan: [capacity, channels] processed float32,
        or None at EOF / short tail / stop.  Both paths poll the stop
        event so a stalled sensor never blocks pipeline shutdown."""
        import os as _os
        import select as _select
        import socket as _socket

        need = self.capacity * self.channels * self.scan_dtype.itemsize
        parts, got = [], 0
        if getattr(self, "_fd", None) is not None:
            fd = self._fd
            while got < need:
                if stop.is_set():
                    return None
                r, _, _ = _select.select([fd], [], [], 0.2)
                if not r:
                    continue
                try:
                    chunk = _os.read(fd, need - got)
                except BlockingIOError:
                    continue
                except OSError:
                    return None
                if chunk == b"":
                    # FIFO before any writer connects reads as EOF: keep
                    # waiting for the sensor until data has flowed once.
                    if self._is_fifo and not self._saw_data:
                        if stop.wait(0.05):
                            return None
                        continue
                    return None  # real EOF
                self._saw_data = True
                parts.append(chunk)
                got += len(chunk)
        else:  # socket: accumulate with stop-aware timeouts
            while got < need:
                if stop.is_set():
                    return None
                try:
                    chunk = self._sock.recv(need - got)
                except _socket.timeout:
                    continue
                except OSError:
                    return None
                if not chunk:
                    return None  # sender closed
                parts.append(chunk)
                got += len(chunk)
        data = b"".join(parts)
        raw = np.frombuffer(data, self.scan_dtype).astype(np.float32)
        raw = raw.reshape(self.capacity, self.channels)
        return (raw + np.float32(self.offset)) * np.float32(self.scale)

    def generate(self):
        import time as _time

        stop = getattr(self, "_stop_event", threading.Event())
        num = self.num_buffers if self.num_buffers >= 0 else 1 << 62
        period = (self.capacity / self.frequency) if self.frequency > 0 else 0.0
        next_t = _time.monotonic()
        if self.device:
            for i in range(num):
                if stop.is_set():
                    return
                scan = self._read_scan(stop)
                if scan is None:
                    return  # sensor stream ended: EOS
                if self.trigger == "timer":
                    next_t += period
                    delay = next_t - _time.monotonic()
                    if delay > 0 and stop.wait(delay):
                        return
                pts = int(1e9 * i * self.capacity / max(self.frequency, 1e-9))
                yield Buffer([scan], pts=pts)
            return
        i = 0
        for _ in range(num):
            rows = []
            for _ in range(self.capacity):
                if callable(self.sampler):
                    rows.append(np.asarray(self.sampler(i), np.float32))
                else:
                    # synthetic: deterministic pseudo-sensor
                    rows.append(
                        np.sin(np.arange(self.channels) + i / self.frequency).astype(
                            np.float32
                        )
                    )
                i += 1
            yield Buffer([np.stack(rows)])


#: v4l2src format name -> (fourcc, bytes per pixel)
_V4L2_FORMATS = {"RGB": ("RGB3", 3), "BGR": ("BGR3", 3),
                 "GRAY8": ("GREY", 1), "YUY2": ("YUYV", 2)}


@register_element("v4l2src")
class V4L2Src(SourceElement):
    """Camera capture — the literal ``v4l2src`` of the north-star
    pipeline (``v4l2src ! tensor_converter ! tensor_filter ! ...``,
    SURVEY §7 design stance).

    Two backends behind one element:

    * ``/dev/videoN`` (a char device): the NATIVE ioctl/mmap streaming
      ring in native/src/nnstpu.cpp (``nns_v4l2_*``) — REQBUFS(MMAP) +
      QBUF/DQBUF, driver-owned buffers, select()-paced.  Construction
      fails loudly when the node is not a streaming capture device.
    * a FIFO / regular file of raw frames (``width*height*bpp`` bytes
      each): the hermetic-test and replay backend, same polling
      discipline as tensor_src_iio (O_NONBLOCK + stop-event checks, so
      a stalled producer never blocks pipeline shutdown).

    Props: ``device`` (default ``/dev/video0``), ``width``/``height``/
    ``format`` (RGB/BGR/GRAY8/YUY2) — caps are fixed at pipeline
    construction, so a driver that substitutes another mode fails
    loudly at start() naming what it offered (silent substitution
    would feed skewed or never-arriving frames downstream); row-padded
    strides (``bytesperline > width*bpp``) are repacked through the
    native stride stripper.  ``num-buffers``, ``framerate``,
    ``io-mode`` (``auto`` | ``native`` | ``raw``).  Emits host video
    frames ``[H, W, bpp]`` uint8; ``tensor_converter`` downstream turns
    them into ``other/tensors`` exactly as it does for videotestsrc.
    """

    kind = "v4l2src"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.device = str(self.props.get("device", "/dev/video0"))
        self.width = int(self.props.get("width", 640))
        self.height = int(self.props.get("height", 480))
        self.format = str(self.props.get("format", "RGB")).upper()
        if self.format not in _V4L2_FORMATS:
            raise ElementError(
                f"{self.name}: format must be one of "
                f"{sorted(_V4L2_FORMATS)}, got {self.format!r}")
        self.num_buffers = int(self.props.get("num_buffers", -1))
        self.rate = parse_fraction(self.props.get("framerate", (30, 1)))
        self.io_mode = str(self.props.get("io_mode", "auto")).lower()
        if self.io_mode not in ("auto", "native", "raw"):
            raise ElementError(
                f"{self.name}: io-mode must be auto|native|raw, "
                f"got {self.io_mode!r}")
        self.n_bufs = int(self.props.get("n_bufs", 4))
        self._cap = None   # native backend handle
        self._fd = None    # raw backend fd
        self._is_fifo = False
        self._saw_data = False

    def configure(self, in_caps, out_pads):
        caps = Caps.new(
            MediaType.VIDEO,
            format=self.format,
            width=self.width,
            height=self.height,
            framerate=self.rate,
        )
        self.out_caps = {p: caps for p in out_pads}
        return self.out_caps

    def _frame_bytes(self) -> int:
        return self.width * self.height * _V4L2_FORMATS[self.format][1]

    def start(self) -> None:
        import os as _os
        import stat as _stat

        try:
            st = _os.stat(self.device)
        except OSError as e:
            raise ElementError(
                f"{self.name}: cannot stat device {self.device!r}: {e}"
            ) from e
        use_native = (self.io_mode == "native"
                      or (self.io_mode == "auto"
                          and _stat.S_ISCHR(st.st_mode)))
        if use_native:
            from .. import native

            fourcc, _ = _V4L2_FORMATS[self.format]
            try:
                cap = native.V4L2Capture(self.device, self.width,
                                         self.height, fourcc,
                                         n_bufs=self.n_bufs)
            except RuntimeError as e:
                raise ElementError(f"{self.name}: {e}") from e
            # Caps were negotiated at pipeline construction, BEFORE the
            # device opened — a driver substituting format or geometry
            # cannot flow downstream, so it must fail LOUDLY here (the
            # silent alternative: every frame skipped or row-sheared).
            # The error names what the driver offered so the pipeline
            # string can be corrected.
            if (cap.pixfmt != fourcc or cap.width != self.width
                    or cap.height != self.height):
                got = (f"{cap.pixfmt} {cap.width}x{cap.height}")
                cap.close()
                raise ElementError(
                    f"{self.name}: device negotiated {got}, pipeline "
                    f"caps want {fourcc} {self.width}x{self.height} — "
                    "set width/height/format to a mode the device "
                    "supports")
            self._cap = cap
            return
        try:
            self._fd = _os.open(self.device, _os.O_RDONLY | _os.O_NONBLOCK)
            self._is_fifo = _stat.S_ISFIFO(_os.fstat(self._fd).st_mode)
        except OSError as e:
            raise ElementError(
                f"{self.name}: cannot open device {self.device!r}: {e}"
            ) from e

    def stop(self) -> None:
        if self._cap is not None:
            self._cap.close()
            self._cap = None
        if self._fd is not None:
            import os as _os

            try:
                _os.close(self._fd)
            except OSError:
                pass
            self._fd = None

    def _read_raw_frame(self, stop) -> Optional[np.ndarray]:
        """One raw frame from the FIFO/file backend, or None at
        EOF/stop (same polling discipline as tensor_src_iio)."""
        import os as _os
        import select as _select

        need = self._frame_bytes()
        parts, got = [], 0
        while got < need:
            if stop.is_set():
                return None
            r, _, _ = _select.select([self._fd], [], [], 0.2)
            if not r:
                continue
            try:
                chunk = _os.read(self._fd, need - got)
            except BlockingIOError:
                continue
            except OSError:
                return None
            if chunk == b"":
                if self._is_fifo and not self._saw_data:
                    if stop.wait(0.05):
                        return None
                    continue
                return None  # real EOF; a short tail frame is dropped
            self._saw_data = True
            parts.append(chunk)
            got += len(chunk)
        return np.frombuffer(b"".join(parts), np.uint8)

    def generate(self):
        stop = getattr(self, "_stop_event", threading.Event())
        num = self.num_buffers if self.num_buffers >= 0 else 1 << 62
        frame_ns = int(1e9 * self.rate[1] / max(1, self.rate[0]))
        bpp = _V4L2_FORMATS[self.format][1]
        need = self._frame_bytes()
        for i in range(num):
            if stop.is_set():
                return
            if self._cap is not None:
                raw = None
                while raw is None:
                    if stop.is_set():
                        return
                    raw = self._cap.capture(timeout_ms=200)
                row = self.width * bpp
                if self._cap.stride > row:
                    # driver pads rows (bytesperline > width*bpp):
                    # repack through the native stride stripper
                    from .. import native

                    raw = native.strip_stride(raw, self.height, row,
                                              self._cap.stride)
                if raw.nbytes < need:
                    continue  # driver hiccup: skip the short frame
                raw = raw[:need]
            else:
                raw = self._read_raw_frame(stop)
                if raw is None:
                    return  # EOF
            yield Buffer([raw.reshape(self.height, self.width, bpp)],
                         pts=i * frame_ns)
