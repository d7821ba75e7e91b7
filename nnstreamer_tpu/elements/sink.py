"""Sink elements.

Reference analogs: ``tensor_sink`` (gsttensor_sink.c — appsink-like terminal
emitting new-data signals), ``fakesink``, ``filesink`` (SURVEY §2.2, §4:
"tensor_sink + checksum/golden compare as deterministic sink").

``tensor_sink`` is where device buffers come home: ``pop()`` returns host
numpy arrays by default (one `device_get` at the pipeline edge), or the raw
jax Arrays with ``to_host=False`` for zero-copy handoff into app JAX code.

``fetch_depth`` (config knob / pipeline knob / ``fetch-depth`` prop) is the
sink-side twin of ``dispatch_depth``: up to that many popped-to-be buffers
resolve D2H / deferred ``host_post`` in a background pool concurrently, so
the fetch of buffer N overlaps the dispatch of buffer N+1 instead of being
paid serially inside ``pop()``.  Emission order stays FIFO — the pull queue
holds futures in arrival order whatever order they finish.  docs/FETCH.md.
"""

from __future__ import annotations

import queue as _queue
import threading as _threading
import time as _time
from typing import Callable, List, Optional

import numpy as np

from ..core.buffer import Buffer
from ..core.log import STALL_FLOOR_S as _STALL_FLOOR_S
from ..core.log import logger, metrics
from ..core.registry import register_element
from ..core.meta_keys import META_TENANT, META_TRACE_ID
from ..utils import locks, tracing
from .base import SinkElement

log = logger(__name__)


def _release_credit(buf) -> None:
    """Free an appsrc max-inflight admission slot: called at REAL
    delivery (pop/callback) or when a drop-mode sink discards the buffer
    — never at mere sink arrival, which async dispatch reaches before
    the batch's H2D/compute has actually happened."""
    credit = getattr(buf, "meta", {}).get("_inflight_credit")
    if credit is not None:
        credit.release()


@register_element("tensor_sink")
class TensorSink(SinkElement):
    """Terminal sink with app-facing pull queue + callbacks.

    Props: ``max-buffers`` (queue bound; oldest dropped when exceeded and
    ``drop=true``), ``emit-signals`` kept for reference familiarity.
    """

    kind = "tensor_sink"
    sync_policy = "any"
    #: residency planner (pipeline/residency.py): the pull API hands the
    #: app whatever tensors arrive — reduced geometry included
    admits_reduced_payload = True

    #: nns-tsan lock discipline (lint --threads verifies statically,
    #: NNS_TPU_TSAN=1 verifies live — docs/ANALYSIS.md "Threads pass")
    _GUARDED_BY = {"_pool": "_win_lock", "_pool_stopped": "_win_lock",
                   "_outstanding": "_win_lock", "_win_peak": "_win_lock"}

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        cap = int(self.props.get("max_buffers", 1024))
        self.drop = bool(self.props.get("drop", False))
        # accepted for reference familiarity (both the reference's
        # "emit-signal" and appsink's "emit-signals" spellings); callbacks
        # fire regardless
        self.emit_signals = bool(self.props.get(
            "emit_signal", self.props.get("emit_signals", True)))
        self._q: _queue.Queue = _queue.Queue(maxsize=cap)
        self._callbacks: List[Callable[[Buffer], None]] = []
        self.to_host = bool(self.props.get("to_host", True))
        # fetch window (docs/FETCH.md): prop > pipeline knob > config
        self._fetch_depth_prop = int(self.props.get("fetch_depth", 0))
        self._pool = None  # lazy fetch_depth-wide resolver pool
        self._pool_stopped = False  # stop() ran: never mint a new pool
        self._outstanding = 0  # submitted-but-unmaterialized window
        # counter shared with pool threads (nns-tsan tracked: the
        # fetch-window gauge race IS the escaped bug that motivated
        # the threads pass — docs/ANALYSIS.md)
        self._win_lock = locks.make_lock("TensorSink._win_lock")
        self._win_peak = 0  # high-water window depth this run
        self._parked = None  # not-yet-done Future seen by try_pop

    def connect_new_data(self, cb: Callable[[Buffer], None]) -> None:
        """Reference: g_signal_connect(sink, "new-data", ...)."""
        self._callbacks.append(cb)

    def process(self, pad, buf: Buffer):
        # frames split per tenant when the buffer carries one (wire meta /
        # appsrc tenant= / traced pipeline default) — the trace-off
        # throughput source for per-tenant accounting
        metrics.count(f"{self.name}.frames",
                      tenant=buf.meta.get(META_TENANT))
        # appsrc max-inflight credits release at POP (materialized
        # delivery), not here: stage dispatch is async, so a buffer
        # "arrives" as a device future milliseconds after admission
        # while its H2D/compute still queues behind earlier batches —
        # an arrival-time release would never bound that backlog
        # (measured: p50 e2e 7x the bound x service product).  Dropped
        # buffers release in the discard branch below.
        # Snapshot once: a callback registered mid-stream must not observe
        # half of this method's gating (connect_new_data is a public API
        # with no start-only restriction) — it takes effect next buffer.
        callbacks = list(self._callbacks)
        # <= not <: a bounded queue holding cap buffers still prefetches
        # the one about to block in put() — put() is the backpressure, so
        # outstanding copies stay <= cap+1.  Gating at < cap made every
        # buffer that arrived at a full (small) queue pay a synchronous
        # D2H RTT at pop — a periodic ~1-RTT stall per cap pops.
        prefetch_cap = min(16, self._q.maxsize or 16)
        if (self.to_host and not callbacks and not self.drop
                and self._q.qsize() <= prefetch_cap):
            # The app will pop host arrays: start the D2H now so the copy
            # overlaps the queue dwell time instead of being paid inside
            # pop() — a D2H fetch roundtrip per buffer off the pull
            # path.  Gated: a drop=true sink may never
            # pop this buffer, and a deeply backed-up unbounded queue
            # (>16 deep) would turn prefetch into unbounded host copies +
            # wasted transfer, so those cases pay the copy lazily at pop.
            for t in buf.tensors:
                if hasattr(t, "copy_to_host_async"):
                    t.copy_to_host_async()
            # Hand the materialization (D2H wait + deferred host_post) to
            # the fetch window: up to fetch_depth buffers resolve on the
            # pool concurrently, NOT on the stage thread (would stall the
            # pipeline) and NOT the pull thread (was round-2's out.proc
            # hotspot).  pop() collects finished results in FIFO order —
            # the pull queue holds futures in arrival order.  Only when
            # there is something to overlap: an already-host numpy buffer
            # with no deferred host_post resolves for free at pop, and
            # submitting it would mint a pool + pay a future round-trip
            # per buffer in host-only pipelines.
            if buf.on_device or "_host_post" in buf.meta:
                buf = self._submit_fetch(buf)
        if callbacks:
            buf = buf.resolve()
            _release_credit(buf)  # callback consumers take delivery here
        for cb in callbacks:
            cb(buf)
        stop = getattr(self, "_stop_event", None)
        while True:
            try:
                self._q.put(buf, timeout=0.1)
                return []
            except _queue.Full:
                if self.drop:
                    try:
                        dropped = self._q.get_nowait()
                    except _queue.Empty:
                        pass
                    else:
                        _release_credit(dropped)  # never popped: free now
                elif stop is not None and stop.is_set():
                    return []  # pipeline stopping: shed instead of deadlocking
                # else: keep blocking — backpressure to the pipeline

    # -- fetch window (docs/FETCH.md) ---------------------------------------
    @property
    def fetch_depth(self) -> int:
        """Resolved fetch-window width: the element's own ``fetch-depth``
        prop wins, then the pipeline knob the runner attached
        (``_fetch_depth``), then the config default."""
        d = self._fetch_depth_prop
        if d <= 0:
            d = int(getattr(self, "_fetch_depth", 0) or 0)
        if d <= 0:
            from ..core.config import get_config

            d = get_config().fetch_depth
        return max(1, d)

    def _fetch_pool(self):
        # under _win_lock: check-then-create must be atomic with stop()
        # (a stage thread descheduled between check and create would mint
        # a pool stop() never learns about — leaked non-daemon workers)
        with self._win_lock:
            if self._pool is None and not self._pool_stopped:
                from concurrent.futures import ThreadPoolExecutor

                self._pool = ThreadPoolExecutor(
                    self.fetch_depth,
                    thread_name_prefix=f"{self.name}-fetch")
            return self._pool

    def _fetch_done(self, fut) -> None:
        with self._win_lock:  # runs on pool threads, racing _submit_fetch
            locks.assert_guarded(self, "_outstanding")
            self._outstanding -= 1
            # gauge write INSIDE the lock: writes are then ordered by
            # acquisition, so the live series stays truthful as the
            # window drains — an idle scrape reads 0, never a stale
            # depth from a submit/done interleaving
            metrics.gauge(f"{self.name}.fetch_window",
                          float(max(0, self._outstanding)))

    def _submit_fetch(self, buf: Buffer):
        """Submit one buffer's materialization into the fetch window;
        returns the Future (or the buffer unchanged when the pool is
        already shut down — the pop path materializes lazily then)."""
        cell = {"dur": 0.0}

        def job(b=buf, cell=cell):
            t1 = _time.perf_counter()
            out = b.to_host()
            cell["dur"] = _time.perf_counter() - t1
            return out

        pool = self._fetch_pool()
        if pool is None:  # stop() ran: shed to the pop path's lazy to_host
            return buf
        try:
            fut = pool.submit(job)
        except RuntimeError:  # pool shut down mid-stop: shed to lazy path
            return buf
        tid = buf.meta.get(META_TRACE_ID)
        fut._nns_tid = tid
        fut._nns_cell = cell
        # the admission credit must survive a FAILED resolution: pop()'s
        # failure path releases it explicitly (deterministic, vs waiting
        # on the _InflightCredit GC safety net) so a streaming app that
        # catches the error can keep pushing
        fut._nns_credit = buf.meta.get("_inflight_credit")
        # count + gauge + peak under ONE lock hold, BEFORE registering the
        # done-callback: a fast resolve may run _fetch_done inline inside
        # add_done_callback, and gauge writes outside the lock could then
        # land after the drain's 0 — a stale nonzero depth forever
        with self._win_lock:
            self._outstanding += 1
            depth = max(1, self._outstanding)
            metrics.gauge(f"{self.name}.fetch_window", float(depth))
            if depth > self._win_peak:
                self._win_peak = depth
                metrics.gauge(f"{self.name}.fetch_window_peak",
                              float(depth))
        fut.add_done_callback(self._fetch_done)
        tracer = getattr(self, "_trace_rec", None)
        if tracer is not None:
            tracer.record("fetch.window", self.name, tid,
                          _time.monotonic_ns(), 0, depth=depth)
        return fut

    # -- app API -----------------------------------------------------------
    def pop(self, timeout: float = 30.0, check: Optional[Callable] = None) -> Buffer:
        deadline = _time.monotonic() + timeout
        buf = self._parked  # a Future try_pop saw mid-flight goes first
        while buf is None:
            try:
                buf = self._q.get(timeout=0.1)
                break
            except _queue.Empty:
                if check:
                    check()
                if _time.monotonic() > deadline:
                    raise TimeoutError(f"no buffer at sink {self.name!r} in {timeout}s")
        # pop's timeout bounds ARRIVAL; materialization gets its own full
        # budget (the pre-resolver to_host() here was unbounded — a slow
        # D2H must not start failing because the queue wait ate
        # the deadline).  A materialization timeout PARKS the item so the
        # frame is retried by the next pop/try_pop, never dropped.
        try:
            out = self._materialize(buf, timeout)
        except TimeoutError:
            self._parked = buf
            raise
        self._parked = None
        _release_credit(out)  # materialized delivery: admission slot frees
        return out

    def try_pop(self) -> Optional[Buffer]:
        """Non-blocking poll: None when no FINISHED buffer is ready.  A
        still-resolving background buffer is parked (single-consumer pull
        API) and returned by the next pop/try_pop once done."""
        import concurrent.futures as _cf

        item = self._parked
        if item is None:
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                return None
        if isinstance(item, _cf.Future) and not item.done():
            self._parked = item
            return None
        self._parked = None
        out = self._materialize(item, 30.0)
        _release_credit(out)
        return out

    def _materialize(self, item, timeout: float) -> Buffer:
        # set by this pipeline's runner iff ITS trace_mode != off
        tracer = getattr(self, "_trace_rec", None)
        if tracer is not None:
            # host-fetch span: the D2H / deferred host_post cost the app's
            # pop() pays (the last hop of the per-buffer timeline)
            t0 = _time.monotonic_ns()
            out = self._materialize_inner(item, timeout)
            ten = out.meta.get(META_TENANT)
            args = {} if ten is None else {"tenant": ten}
            tracer.record("fetch", self.name,
                          out.meta.get(META_TRACE_ID), t0,
                          _time.monotonic_ns() - t0, **args)
            return out
        return self._materialize_inner(item, timeout)

    def _materialize_inner(self, item, timeout: float) -> Buffer:
        import concurrent.futures as _cf

        if isinstance(item, _cf.Future):  # background-resolved host buffer
            t0 = _time.perf_counter()
            tid = getattr(item, "_nns_tid", None)
            try:
                out = item.result(timeout=timeout)
            except _cf.TimeoutError:
                # Post-mortem: the timeout carries the buffer's trace id
                # and dumps the flight-recorder ring, exactly like
                # watchdog fires (no-op when tracing is off).
                tracing.dump_recent_to_log(
                    log, reason=f"fetch/host_post resolution timeout at "
                                f"sink {self.name!r} (trace id {tid})")
                # builtin TimeoutError is pop()'s documented contract (and
                # the two are distinct types on py3.10)
                raise TimeoutError(
                    f"host_post resolution at sink {self.name!r} exceeded "
                    f"{timeout}s (trace id {tid})") from None
            except Exception as e:  # noqa: BLE001 - annotate + re-raise
                tracing.dump_recent_to_log(
                    log, reason=f"fetch/host_post resolution FAILED at "
                                f"sink {self.name!r} (trace id {tid}): "
                                f"{e!r}")
                # the buffer is gone, its admission credit must not be:
                # an app that catches this and keeps streaming would
                # otherwise wedge after max_inflight failures (release()
                # is idempotent; the GC safety net stays the backstop)
                credit = getattr(item, "_nns_credit", None)
                if credit is not None:
                    credit.release()
                raise
            wait = _time.perf_counter() - t0
            dur = getattr(item, "_nns_cell", {"dur": 0.0})["dur"]
            # d2h-wait accounting (the output-side half of the stall
            # split; appsrc counts the h2d side): time the PULL actually
            # blocked, vs fetch time that overlapped pipeline work
            metrics.count(f"{self.name}.d2h_wait_ms", wait * 1e3)
            if wait > _STALL_FLOOR_S:
                metrics.count(f"{self.name}.d2h_stalls")
            metrics.count(f"{self.name}.fetch_overlap_ms",
                          max(0.0, dur - wait) * 1e3)
            return out
        if not self.to_host:
            return item
        t0 = _time.perf_counter()
        out = item.to_host()
        wait = _time.perf_counter() - t0
        metrics.count(f"{self.name}.d2h_wait_ms", wait * 1e3)
        if wait > _STALL_FLOOR_S:
            metrics.count(f"{self.name}.d2h_stalls")
        return out

    def stop(self) -> None:
        with self._win_lock:  # atomic with _fetch_pool's check-then-create
            self._pool_stopped = True  # racing process() must not mint a pool
            pool, self._pool = self._pool, None
        if pool is not None:
            # wait=False + no cancel: already-submitted window entries
            # still resolve, so buffers queued before EOS stay poppable
            pool.shutdown(wait=False)
        super().stop()

    @property
    def depth(self) -> int:
        return self._q.qsize()


@register_element("fakesink")
class FakeSink(SinkElement):
    """Discard everything (but count it)."""

    kind = "fakesink"
    #: residency planner: discarded payloads admit any geometry
    admits_reduced_payload = True

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.count = 0
        self.sync = bool(self.props.get("sync", False))
        self.last: Optional[Buffer] = None

    def process(self, pad, buf):
        # Block until device work for this buffer really finished — without
        # this, "throughput" would measure XLA's async dispatch queue.
        buf.block_until_ready()
        _release_credit(buf)  # ready = really delivered for a fakesink
        self.count += 1
        self.last = buf
        metrics.count(f"{self.name}.frames",
                      tenant=buf.meta.get(META_TENANT))
        return []


@register_element("filesink")
class FileSink(SinkElement):
    """Append raw tensor bytes to a file (reference: filesink in SSAT golden
    tests)."""

    kind = "filesink"

    def __init__(self, props=None, name=None):
        super().__init__(props, name)
        self.location = str(self.props.get("location", "out.bin"))
        self._f = None

    def start(self):
        self._f = open(self.location, "wb")

    def stop(self):
        if self._f:
            self._f.close()
            self._f = None

    def process(self, pad, buf):
        for t in buf.resolve().tensors:
            self._f.write(np.asarray(t).tobytes())
        _release_credit(buf)  # bytes on disk = delivered
        return []
