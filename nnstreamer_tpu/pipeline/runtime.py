"""Async pipeline executor.

Reference analog: GStreamer's streaming model — every pad push runs on a
streaming thread, ``queue`` elements create stage boundaries, backpressure is
"push blocks until downstream returns" (SURVEY §1: "There is no 'scheduler'
layer — scheduling *is* GStreamer").  The TPU build supplies that analog
explicitly:

* each planned **stage** (an element, or a fused group of device elements —
  see plan.py) runs on its own runner thread with ONE bounded input queue;
* upstream pushes block when the queue is full → backpressure;
* EOS/error/caps events travel in-band through the same queues;
* device stages keep payloads as jax Arrays in HBM between stages (zero-copy),
  and the driver thread never blocks on device completion except at sinks —
  XLA's async dispatch overlaps H2D/compute/D2H exactly where the reference
  relied on GStreamer thread concurrency.

The executor is deliberately thread-based, not asyncio: stages do real
blocking work (device dispatch, host preprocessing) and the GIL is released
inside numpy/JAX, so threads give true overlap with far less machinery.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple, Union

from ..core.buffer import Buffer, Event, batch_signature
from ..core.caps import Caps, MediaType
from ..core.config import get_config
from ..core.log import Timer, logger, metrics
from ..core.registry import KIND_ELEMENT, get as registry_get
from ..elements.base import Element, SinkElement, SourceElement, SRC
from ..utils import locks, tracing
from ..utils.armor import META_POISON as _META_POISON
from .graph import PipelineGraph
from .parser import parse as parse_launch
from .plan import Stage, plan_stages

log = logger(__name__)

#: in-band shutdown sentinel: Pipeline.stop() closes every stage queue with
#: one of these, so blocked getters wake instantly (no polling)
_POISON = object()


class PipelineError(RuntimeError):
    pass


class _StageQueue:
    """Bounded stage input queue with stop-aware blocking.

    Replaces the seed's ``queue.Queue`` + 0.1 s timeout polling: putters
    and getters block on condition variables, and :meth:`close` (called by
    ``Pipeline.stop()``) wakes every waiter at once — shutdown latency
    drops from worst-case ~100 ms per hop to ~0, and idle stages burn no
    CPU.  ``close`` also appends a ``(None, _POISON)`` item past the
    capacity bound so a getter that arrives later still returns
    immediately.

    TWO condition variables over one lock (queue.Queue's design), not one
    shared cv: a single cv needs ``notify_all`` on every put/get to be
    lost-wakeup-safe (a ``notify`` intended for a getter can land on a
    blocked putter, who re-waits without passing it on) — and that wakes
    every blocked producer per buffer, N-1 of which immediately re-block.
    With ``_not_empty``/``_not_full`` each put/get wakes exactly the ONE
    waiter that can make progress; ``notify_all`` survives only in
    :meth:`close`, where waking everyone is the point."""

    #: nns-tsan lock discipline (lint --threads verifies statically,
    #: NNS_TPU_TSAN=1 verifies live — docs/ANALYSIS.md "Threads pass")
    _GUARDED_BY = {"_dq": "_lock", "_closed": "_lock"}

    def __init__(self, capacity: int):
        self._dq: Deque = collections.deque()
        self._cap = max(1, capacity)
        self._lock = locks.make_lock("StageQueue._lock")
        self._not_empty = locks.make_condition(self._lock,
                                               name="StageQueue._not_empty")
        self._not_full = locks.make_condition(self._lock,
                                              name="StageQueue._not_full")
        self._closed = False

    def put(self, item) -> bool:
        """Block until space (backpressure); False = pipeline stopping and
        the item was shed."""
        with self._lock:
            while len(self._dq) >= self._cap:
                if self._closed:
                    return False
                self._not_full.wait()
            if self._closed:
                return False
            self._dq.append(item)
            self._not_empty.notify()
            return True

    def get(self, timeout: Optional[float] = None):
        """Block until an item arrives; ``(None, _POISON)`` once closed and
        drained; None on timeout (used by the batch linger wait)."""
        with self._lock:
            while not self._dq:
                if self._closed:
                    return (None, _POISON)
                if not self._not_empty.wait(timeout=timeout):
                    return None
            item = self._dq.popleft()
            self._not_full.notify()
            return item

    def get_nowait(self):
        """Non-blocking get; None when empty (the opportunistic drain)."""
        with self._lock:
            if not self._dq:
                return None
            item = self._dq.popleft()
            self._not_full.notify()
            return item

    def close(self) -> None:
        with self._lock:
            if not self._closed:
                self._closed = True
                self._dq.append((None, _POISON))
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def qsize(self) -> int:
        with self._lock:
            return len(self._dq)

    def tenant_depths(self) -> Dict[str, int]:
        """Queued-buffer count per tenant (``meta['_tenant']``) — the
        sampler's per-tenant ``queue_depth`` source.  Cold path: scans a
        snapshot of the deque (bounded by capacity) under the lock."""
        with self._lock:
            items = list(self._dq)
        depths: Dict[str, int] = {}
        for it in items:
            if not (isinstance(it, tuple) and len(it) == 2):
                continue
            buf = it[1]
            if isinstance(buf, Buffer):
                ten = buf.meta.get(tracing.META_TENANT)
                if ten is not None:
                    depths[ten] = depths.get(ten, 0) + 1
        return depths


class _Port:
    """Destination of an edge: a stage's queue + the pad name inside it."""

    def __init__(self, stage: "_Runner", pad: str):
        self.stage = stage
        self.pad = pad


class _Runner:
    """One streaming thread driving one planned stage."""

    def __init__(self, pipeline: "Pipeline", stage: Stage, capacity: int):
        self.pipeline = pipeline
        self.stage = stage
        self.element = stage.element
        self.queue = _StageQueue(capacity)
        self.out_ports: Dict[str, List[_Port]] = {}
        self.thread = threading.Thread(
            target=self._run, name=f"nns-{self.element.name}", daemon=True
        )
        # Elements with their own receiver threads (query client) emit
        # downstream asynchronously, not just from process() returns.
        if getattr(self.element, "wants_async_emit", False):
            self.element._async_emit = self._emit
        self.in_pads: List[str] = []
        self._eos_pads: set = set()
        self._pending: Dict[str, List[Buffer]] = {}
        # Adaptive micro-batching: only device stages the planner marked
        # batchable drain >1 buffer; batch_max=1 keeps the exact seed path.
        # No ladder-top clamp anymore: bucket_for() LADDER-ROUNDS above
        # the top bucket (multiples of it), so a batch_max past the top
        # drains bigger dispatches with a still-bounded program census —
        # pipeline/batching.ladder() mirrors the exact compiled set.
        self.batch_max = pipeline.batch_max if stage.batchable else 1
        self.batch_linger_s = pipeline.batch_linger_ms / 1e3
        if stage.batchable:
            # elements build their BatchRunner lazily; hand them the
            # pipeline's bucket ladder the same way _async_emit is attached
            self.element._batch_buckets = pipeline.batch_buckets
            if pipeline.adaptive_buckets and self.batch_max > 1:
                # Adaptive ladder (docs/BATCHING.md "Adaptive ladder"):
                # per-stage, warm-startable, budget-closed.  Attached to
                # the ELEMENT like _batch_buckets; the lazy BatchRunner
                # reads it at first batched dispatch.
                from .batching import AdaptiveLadder, ladder as _ladder

                self.element._batch_ladder = AdaptiveLadder(
                    _ladder(self.batch_max, pipeline.batch_buckets),
                    budget=pipeline._ladder_budget,
                    warm=pipeline.bucket_ladders.get(self.element.name),
                    name=self.element.name)
        # In-flight dispatch window: a batching device stage may hold this
        # many dispatched-but-unemitted micro-batches, so the next drain
        # overlaps the previous (async) dispatch instead of waiting behind
        # the downstream feed.  Emission order is the FIFO deque's.
        self.dispatch_depth = (max(1, pipeline.dispatch_depth)
                               if self.batch_max > 1 else 1)
        self._inflight: Deque[Tuple[list, int, int]] = collections.deque()
        # Hot-path metric names built ONCE (the seed built f-strings per
        # buffer in _run_stream/_emit).
        name = self.element.name
        self._nm = name
        self._m_in = f"{name}.in"
        self._m_out = f"{name}.out"
        self._m_dropped = f"{name}.dropped"
        self._m_proc = f"{name}.proc"
        self._m_push = f"{name}.push"
        self._m_occupancy = f"{name}.batch_occupancy"
        self._m_qwait = f"{name}.queue_wait"
        self._m_e2e = f"{name}.e2e_latency"
        self._m_restarts = f"{name}.restarts"
        self._restarts = 0  # elastic in-place restarts taken so far
        #: _drain_batch pushback held ACROSS a restart: a carried item
        #: (often the EOS event) popped before the fault must survive
        #: re-entry, or a restarted stage would drop it and hang the
        #: pipeline waiting for an EOS nobody holds anymore
        self._carry = None
        #: buffers in the hands of process()/process_batch() right now —
        #: what a restart actually loses (counted into .dropped)
        self._proc_n = 0
        # Flight recorder (docs/OBSERVABILITY.md): None when trace_mode is
        # off — every instrumentation site below reduces to one pointer
        # check, and no meta stamps are written (the untraced code path).
        self._tr = tracing.recorder if pipeline.trace_mode != "off" else None
        # Attached to the ELEMENT the same way _batch_buckets is, so the
        # sink's fetch span and the lazy BatchRunner's shard span follow
        # THIS pipeline's trace_mode, not whatever another pipeline in the
        # process switched the global recorder to.
        self.element._trace_rec = self._tr
        # nns-xray registry handle (None = off): the fused program,
        # BatchRunner buckets, and framework jit paths read it at build
        # time.  A folded device source wraps a FusedElement that is NOT
        # in pipeline.elements — forward both handles to it.
        self.element._xray = pipeline._xray_reg
        fused_inner = getattr(self.element, "fused", None)
        if fused_inner is not None:
            fused_inner._xray = pipeline._xray_reg
            fused_inner._trace_rec = self._tr
        self._is_sink = isinstance(self.element, SinkElement)
        self._last_sink_ns = 0  # sampler reads: staleness watermark
        self._max_pts = None  # watermark_pts gauge is a high-water mark
        self._gauge_tenants: set = set()  # tenants with a depth gauge

    # -- wiring ------------------------------------------------------------
    def connect(self, out_pad: str, port: _Port) -> None:
        self.out_ports.setdefault(out_pad, []).append(port)

    # -- data plane --------------------------------------------------------
    def feed(self, pad: str, item: Union[Buffer, Event]) -> None:
        """Blocking put (backpressure point); sheds the item when the
        pipeline is stopping."""
        if self._tr is not None and isinstance(item, Buffer):
            # Queue-wait span start, keyed by the CONSUMING stage so
            # fan-out is exact: a tee'd buffer shares one meta dict
            # across branches, but each branch's consumer pops only its
            # own stamp.  The stamp map is rebuilt (copy + own entry)
            # rather than mutated in place so two buffers that INHERITED
            # one map (meta copies of a shared frame) fed into the same
            # stage never overwrite each other's start time.
            stamps = item.meta.get(tracing.META_ENQUEUE_NS)
            base = stamps if isinstance(stamps, dict) else {}
            item.meta[tracing.META_ENQUEUE_NS] = {
                **base, self._nm: time.monotonic_ns()}
        self.queue.put((pad, item))

    def _emit(self, outs: List[Tuple[str, Union[Buffer, Event]]]) -> None:
        for out_pad, item in outs:
            ports = self.out_ports.get(out_pad, [])
            if not ports and isinstance(item, Buffer):
                metrics.count(self._m_dropped)
                continue
            for port in ports:
                # Deferred host-post buffers stay lazy all the way to sinks
                # (resolved in the app thread); any mid-pipeline host element
                # needs the real payload now.
                if (
                    isinstance(item, Buffer)
                    and "_host_post" in item.meta
                    and not isinstance(port.stage.element, SinkElement)
                ):
                    item = item.resolve()
                port.stage.feed(port.pad, item)

    def _broadcast(self, item) -> None:
        for ports in self.out_ports.values():
            for port in ports:
                port.stage.feed(port.pad, item)

    # -- main loop ---------------------------------------------------------
    def _run(self) -> None:
        el = self.element
        while True:
            try:
                if isinstance(el, SourceElement):
                    self._run_source()
                else:
                    self._run_stream()
                return
            except Exception as e:  # noqa: BLE001 - must not kill process
                if (self.stage.restartable
                        and not isinstance(el, SourceElement)
                        # restart ONLY faults raised inside process()/
                        # process_batch() (_proc_n is set around exactly
                        # those calls): an exception while handling an
                        # already-consumed EVENT (EOS -> finalize) has
                        # irreversibly eaten it, and re-entering the
                        # loop would block on an empty queue forever
                        # instead of broadcasting EOS
                        and self._proc_n > 0
                        and self._restarts
                        < self.pipeline.max_stage_restarts
                        and not self.pipeline._stopping.is_set()):
                    # Elastic stage restart (docs/SERVING.md "Elastic
                    # serving"): a pure/stateless stage holds no cross-
                    # buffer state, so re-entering its loop after an
                    # exception loses exactly the one buffer that
                    # triggered it.  Prior in-flight batches completed
                    # fine — deliver them first so ordering holds.
                    self._restarts += 1
                    metrics.count(self._m_restarts)
                    metrics.count(self._m_dropped, max(1, self._proc_n))
                    self._proc_n = 0
                    log.warning(
                        "stage %s failed (%r); restarting in place "
                        "(%d/%d)", el.name, e, self._restarts,
                        self.pipeline.max_stage_restarts)
                    try:
                        self._flush_inflight()
                    except Exception:  # noqa: BLE001
                        log.exception(
                            "in-flight flush failed for %s", el.name)
                    continue
                log.exception("stage %s failed", el.name)
                self.pipeline._record_error(el.name, e)
                try:
                    # Batches dispatched BEFORE the failing one completed
                    # fine and are still held in the in-flight window —
                    # deliver them (downstream queues are open on this
                    # path) before the error/EOS, exactly what
                    # dispatch_depth=1 would have done.
                    self._flush_inflight()
                except Exception:  # noqa: BLE001 - must still broadcast
                    log.exception("in-flight flush failed for %s", el.name)
                self._broadcast(Event.error(e))
                self._broadcast(Event.eos())
                return

    def _run_source(self) -> None:
        el = self.element
        tr = self._tr
        for item in el.generate():
            if self.pipeline._stopping.is_set():
                break
            if tr is not None:
                buf = item[1] if isinstance(item, tuple) else item
                if isinstance(buf, Buffer):
                    # INGRESS: the per-buffer trace id is born here and
                    # rides Buffer.meta through every derived buffer
                    # downstream (with_tensors copies meta; the runner
                    # back-fills fresh Buffers — see _propagate_trace).
                    tid = buf.meta.get(tracing.META_TRACE_ID)
                    if tid is None:
                        tid = tracing.next_trace_id()
                        buf.meta[tracing.META_TRACE_ID] = tid
                    t = time.monotonic_ns()
                    buf.meta[tracing.META_INGRESS_NS] = t
                    # the pipeline's default tenant is stamped HERE —
                    # inside the traced branch only, so the off path
                    # stays stamp-free (an element-level tenant, e.g.
                    # appsrc tenant= or the query wire meta, is app data
                    # and rides regardless of trace mode)
                    ten = buf.meta.get(tracing.META_TENANT)
                    if ten is None and self.pipeline.tenant is not None:
                        ten = self.pipeline.tenant
                        buf.meta[tracing.META_TENANT] = ten
                    if ten is None:
                        tr.record("ingress", self._nm, tid, t, 0,
                                  pts=buf.pts)
                    else:
                        tr.record("ingress", self._nm, tid, t, 0,
                                  pts=buf.pts, tenant=ten)
            with Timer(self._m_push):
                self._emit([(SRC, item)] if not isinstance(item, tuple) else [item])
            metrics.count(self._m_out)
        self._emit(el.finalize())
        self._broadcast(Event.eos())

    def _drain_batch(self, pad: str, first: Buffer):
        """Opportunistically drain up to batch_max-1 more already-queued
        compatible buffers (same pad, same tensor signature).  No waiting
        by default — latency is never traded for occupancy unless
        batch_linger_ms > 0.  Returns (batch, carry): ``carry`` is the
        first non-stackable item popped (an event, another pad, a
        different spec), which must be handled AFTER the batch so stream
        order is preserved."""
        batch = [first]
        sig = batch_signature(first)
        deadline = None
        while len(batch) < self.batch_max:
            nxt = self.queue.get_nowait()
            if nxt is None:
                if self.batch_linger_s <= 0.0:
                    break
                if deadline is None:
                    deadline = time.monotonic() + self.batch_linger_s
                remaining = deadline - time.monotonic()
                if remaining <= 0.0:
                    break
                nxt = self.queue.get(timeout=remaining)
                if nxt is None:
                    break
            npad, nitem = nxt
            if (nitem is _POISON or isinstance(nitem, Event)
                    or npad != pad or batch_signature(nitem) != sig):
                return batch, nxt
            batch.append(nitem)
        return batch, None

    def _emit_oldest_inflight(self) -> None:
        outs, n, t_disp = self._inflight.popleft()
        if self._tr is not None and t_disp:
            first = next((o for _, o in outs if isinstance(o, Buffer)),
                         None)
            tid = first.meta.get(tracing.META_TRACE_ID) \
                if first is not None else None
            ten = first.meta.get(tracing.META_TENANT) \
                if first is not None else None
            args = {"rows": n}
            if ten is not None:
                args["tenant"] = ten
            self._tr.record("inflight", self._nm, tid, t_disp,
                            time.monotonic_ns() - t_disp, **args)
        self._emit(outs)
        metrics.count(self._m_out, n)

    # -- tracing helpers ---------------------------------------------------
    def _propagate_trace(self, ins: List[Buffer], outs) -> None:
        """Back-fill trace meta onto output buffers an element built from
        scratch (with_tensors already copies meta).  Row-aligned when the
        element emitted one output per input (the batch contract);
        otherwise every output inherits the first input's identity
        (fan-out: tee/demux branches share the frame's trace id)."""
        if not outs:
            return
        aligned = len(outs) == len(ins)
        for i, (_, o) in enumerate(outs):
            if not isinstance(o, Buffer):
                continue
            src = ins[i] if aligned else ins[0]
            if tracing.META_TRACE_ID not in o.meta:
                o.meta[tracing.META_TRACE_ID] = \
                    src.meta.get(tracing.META_TRACE_ID)
            if (tracing.META_INGRESS_NS not in o.meta
                    and tracing.META_INGRESS_NS in src.meta):
                o.meta[tracing.META_INGRESS_NS] = \
                    src.meta[tracing.META_INGRESS_NS]

    def _trace_queue_wait(self, buf: Buffer, end_ns: int) -> Optional[int]:
        """Record the queue-wait span for one consumed buffer; returns its
        trace id.  Pops THIS stage's entry from the per-branch stamp map
        (see :meth:`feed`), so fan-out branches each get their exact wait
        and nothing double-counts."""
        tid = buf.meta.get(tracing.META_TRACE_ID)
        stamps = buf.meta.get(tracing.META_ENQUEUE_NS)
        tq = None
        if isinstance(stamps, dict):
            tq = stamps.pop(self._nm, None)
            if not stamps:
                # drained map: drop the key so delivered buffers (and
                # wire-encoded responses) stay as clean as pre-fan-out
                buf.meta.pop(tracing.META_ENQUEUE_NS, None)
        if tq is not None and end_ns >= tq:
            ten = buf.meta.get(tracing.META_TENANT)
            if ten is None:
                self._tr.record("queue", self._nm, tid, tq, end_ns - tq)
            else:
                self._tr.record("queue", self._nm, tid, tq, end_ns - tq,
                                tenant=ten)
            metrics.observe_latency(self._m_qwait, (end_ns - tq) / 1e9,
                                    tenant=ten)
        return tid

    def _trace_sink_delivery(self, buf: Buffer, end_ns: int) -> None:
        """End-to-end span + staleness/watermark state at sink delivery.
        A tenant on the buffer splits the e2e histogram per tenant and
        puts the span on the tenant's own Chrome-trace track."""
        self._last_sink_ns = end_ns
        if buf.pts is not None and (self._max_pts is None
                                    or buf.pts > self._max_pts):
            # high-water mark, matching the exposed HELP text: mux/tee
            # fan-in can deliver pts out of order
            self._max_pts = buf.pts
            metrics.gauge(f"{self._nm}.watermark_pts", float(buf.pts))
        ts0 = buf.meta.get(tracing.META_INGRESS_NS)
        if ts0 is not None and end_ns >= ts0:
            ten = buf.meta.get(tracing.META_TENANT)
            metrics.observe_latency(self._m_e2e, (end_ns - ts0) / 1e9,
                                    tenant=ten)
            tid = buf.meta.get(tracing.META_TRACE_ID)
            if ten is None:
                self._tr.record("e2e", self._nm, tid, ts0, end_ns - ts0)
            else:
                self._tr.record("e2e", self._nm, tid, ts0, end_ns - ts0,
                                tenant=ten)

    def _trace_batch(self, batch: List[Buffer], outs, tdr0: int,
                     dt: float) -> None:
        """Spans for one micro-batch: per-member queue waits, the batch
        formation window (first buffer in hand -> dispatch), and the
        dispatch span LINKING every member row's trace id — so the
        amortized device time (``per_row_ns``) is attributable per row
        even though XLA saw one program call."""
        tr = self._tr
        tids = [self._trace_queue_wait(b, tdr0) for b in batch]
        n = len(batch)
        dur = int(dt * 1e9)
        disp0 = time.monotonic_ns() - dur
        # per-tenant stage-latency split: each member row's tenant gets
        # the amortized per-row time (the batch's base .proc observation
        # already happened in the caller)
        tens = [b.meta.get(tracing.META_TENANT) for b in batch]
        for ten in tens:
            if ten is not None:
                metrics.observe_latency_labeled(self._m_proc, dt / n, ten)
        if n > 1:
            # row-aligned tenants list (like trace_ids): dominant-span
            # attribution credits each tenant its share of the span
            extra = {"tenants": tens} if any(t is not None
                                             for t in tens) else {}
            tr.record("batch", self._nm, tids[0], tdr0,
                      max(0, disp0 - tdr0), trace_ids=tids, rows=n,
                      **extra)
            tr.record("stage", self._nm, tids[0], disp0, dur,
                      trace_ids=tids, rows=n, per_row_ns=dur // n,
                      **extra)
        else:
            ten = batch[0].meta.get(tracing.META_TENANT)
            if ten is None:
                tr.record("stage", self._nm, tids[0], disp0, dur)
            else:
                tr.record("stage", self._nm, tids[0], disp0, dur,
                          tenant=ten)
        self._propagate_trace(batch, outs)

    def _flush_inflight(self) -> None:
        while self._inflight:
            self._emit_oldest_inflight()

    # -- nns-armor: poison-pill quarantine (docs/ROBUSTNESS.md) ------------
    def _invoke(self, el, pad: str, batch: List[Buffer]):
        """The stage invoke, armored when ``Pipeline(quarantine=...)`` /
        ``nan_guard`` is configured: an exception (or a NaN/Inf output
        under nan_guard) quarantines the triggering request(s) to the
        DLQ and substitutes typed ``abort_reason=poison`` terminators —
        the pipeline keeps serving instead of restarting/failing.
        Sinks keep the pre-armor semantics (a send failure is not a
        poisoned request)."""
        n = len(batch)
        armor = self.pipeline._armor
        if armor is None or self._is_sink:
            return (el.process_batch(pad, batch) if n > 1
                    else el.process(pad, batch[0]))
        try:
            outs = (el.process_batch(pad, batch) if n > 1
                    else el.process(pad, batch[0]))
        except Exception as e:  # noqa: BLE001 - the quarantine contract
            return self._poison_outs(armor, pad, batch, e)
        if armor.nan_guard and outs:
            outs = self._nan_screen(armor, batch, outs)
        return outs

    def _poison_outs(self, armor, pad: str, batch: List[Buffer],
                     err: BaseException):
        """A failed invoke becomes poison terminators — but only for the
        buffers that actually poison.  A failed micro-BATCH is re-invoked
        one buffer at a time (batchable stages are pure by the planner's
        own rules, so re-running the innocent rows is safe): one
        malicious tenant's pill must not quarantine — and breaker-
        penalize — every request that happened to share its dispatch."""
        from ..utils import armor as _armor_mod

        el = self.element
        outs = []
        for b in batch:
            row_err = err
            if len(batch) > 1:
                try:
                    row_outs = el.process(pad, b)
                except Exception as e:  # noqa: BLE001 - the real pill
                    row_err = e
                else:
                    if armor.nan_guard and row_outs:
                        # the retry path must not bypass the screen the
                        # batched path would have applied
                        row_outs = self._nan_screen(armor, [b],
                                                    row_outs)
                    outs.extend(row_outs)
                    continue
            metrics.count(f"{self._nm}.poisoned")
            armor.quarantine(b, error=row_err, stage=self._nm)
            outs.append((SRC, _armor_mod.poison_terminator(b, row_err)))
        return outs

    def _nan_screen(self, armor, batch: List[Buffer], outs):
        """nan_guard: replace non-finite stage outputs with poison
        terminators — row-aligned to inputs when the element honored
        the one-output-per-input batch contract, counting BUFFER
        outputs only (an interleaved event must not shift which source
        request gets quarantined and breaker-penalized)."""
        from ..utils import armor as _armor_mod

        n_buf_outs = sum(1 for _, o in outs if isinstance(o, Buffer))
        aligned = n_buf_outs == len(batch)
        screened = []
        row = 0
        for out_pad, o in outs:
            if not isinstance(o, Buffer):
                screened.append((out_pad, o))
                continue
            if armor.nonfinite(o):
                src = batch[row] if aligned else batch[0]
                err = FloatingPointError(
                    "non-finite stage output (nan_guard)")
                metrics.count(f"{self._nm}.poisoned")
                armor.quarantine(src, error=err, stage=self._nm)
                screened.append(
                    (SRC, _armor_mod.poison_terminator(src, err)))
            else:
                screened.append((out_pad, o))
            row += 1
        return screened

    def _run_stream(self) -> None:
        el = self.element
        all_policy = el.sync_policy == "all" and len(self.in_pads) > 1
        batching = self.batch_max > 1 and not all_policy
        depth = self.dispatch_depth if batching else 1
        # pushback lives on self (not a local) so an elastic restart
        # re-enters with the carried item — losing it would lose an EOS
        while True:
            if self._carry is not None:
                pad, item = self._carry
                self._carry = None
            else:
                nxt = None
                if self._inflight:
                    # Dispatch window open: only keep batches in flight
                    # while more work is ALREADY queued — before blocking,
                    # emit everything held, or idle streams would pay the
                    # window as pure latency.
                    nxt = self.queue.get_nowait()
                    if nxt is None:
                        self._flush_inflight()
                if nxt is None:
                    nxt = self.queue.get()
                pad, item = nxt
            if item is _POISON:
                # stop(): downstream queues are already closed, so the
                # flush sheds — but a future clean-shutdown path stays
                # correct if close semantics ever change.
                self._flush_inflight()
                return
            if isinstance(item, Event):
                # Events are ordering fences: everything dispatched before
                # the event arrived must be emitted before it is handled.
                self._flush_inflight()
                if item.kind == "eos":
                    self._eos_pads.add(pad)
                    if all_policy:
                        self._try_groups()
                    if self._eos_pads >= set(self.in_pads):
                        self._emit(el.finalize())
                        self._broadcast(Event.eos())
                        return
                    continue
                if item.kind == "error":
                    self._broadcast(item)
                    continue
                self._emit(el.on_event(pad, item))
                continue
            if (not self._is_sink and not all_policy
                    and isinstance(item, Buffer)
                    and item.meta.get(_META_POISON)):
                # a poison terminator is an ANSWER riding to the sink
                # (utils/armor.py), never work: forward it untouched so
                # downstream stages cannot crash on its empty payload.
                # NOT on sync_policy="all" stages: skipping the pairing
                # logic would permanently misalign the other pads'
                # streams — a collator fed a terminator pairs (and may
                # fail loudly) instead of silently merging off-by-one.
                self._flush_inflight()
                metrics.count(self._m_in)
                self._emit([(SRC, item)])
                metrics.count(self._m_out)
                continue
            if all_policy:
                metrics.count(self._m_in)
                self._pending.setdefault(pad, []).append(item)
                self._try_groups()
                continue
            tr = self._tr
            if batching:
                tdr0 = time.monotonic_ns() if tr is not None else 0
                batch, self._carry = self._drain_batch(pad, item)
                n = len(batch)
                metrics.count(self._m_in, n)
                # real cumulative histogram (ladder-shaped buckets), not
                # just the quantile reservoir: the adaptive ladder and
                # Prometheus read the same occupancy stream
                metrics.observe_bucketed(self._m_occupancy, float(n))
                t0 = time.perf_counter()
                self._proc_n = n
                outs = self._invoke(el, pad, batch)
                self._proc_n = 0
                # PER-BUFFER proc time: the .proc series must keep one
                # meaning whether batching is on or off (same rule the
                # filter applies to its .invoke series)
                dt = time.perf_counter() - t0
                metrics.observe_latency(self._m_proc, dt / n)
                if tr is not None:
                    self._trace_batch(batch, outs, tdr0, dt)
                if depth > 1:
                    # Software pipeline: XLA dispatch is async, so the
                    # runner loops back to drain the NEXT micro-batch
                    # while this one executes; emission (which may block
                    # on a full downstream queue) is deferred FIFO until
                    # the window fills.
                    self._inflight.append(
                        (outs, n,
                         time.monotonic_ns() if tr is not None else 0))
                    while len(self._inflight) >= depth:
                        self._emit_oldest_inflight()
                else:
                    self._emit(outs)
                    metrics.count(self._m_out, n)
                if self._carry is not None and self._carry[1] is _POISON:
                    self._flush_inflight()
                    return
                continue
            metrics.count(self._m_in)
            self._proc_n = 1
            if tr is None:
                with Timer(self._m_proc):
                    outs = self._invoke(el, pad, [item])
            else:
                now0 = time.monotonic_ns()
                tid = self._trace_queue_wait(item, now0)
                ten = item.meta.get(tracing.META_TENANT)
                t0 = time.perf_counter()
                outs = self._invoke(el, pad, [item])
                dt = time.perf_counter() - t0
                metrics.observe_latency(self._m_proc, dt, tenant=ten)
                dur = int(dt * 1e9)
                if ten is None:
                    tr.record("stage", self._nm, tid, now0, dur)
                else:
                    tr.record("stage", self._nm, tid, now0, dur,
                              tenant=ten)
                self._propagate_trace([item], outs)
                if self._is_sink:
                    self._trace_sink_delivery(item, now0 + dur)
            self._proc_n = 0
            self._emit(outs)
            metrics.count(self._m_out)

    def _try_groups(self) -> None:
        """Collate one buffer per pad (slowest-pad sync; reference:
        tensor_mux sync-mode=slowest).  A pad keeps pairing from its pending
        queue after EOS — data queued before EOS must still pair up.  Once
        any pad is EOS'd AND drained no complete group can ever form again,
        so remaining unpairable buffers are dropped: emitting a partial
        group would violate the element's negotiated caps (e.g. a 2-tensor
        mux emitting 1 tensor)."""
        el = self.element
        while True:
            dead = [
                p
                for p in self.in_pads
                if p in self._eos_pads and not self._pending.get(p)
            ]
            if dead:
                n = sum(len(v) for v in self._pending.values())
                if n:
                    metrics.count(self._m_dropped, n)
                    self._pending.clear()
                return
            if not all(self._pending.get(p) for p in self.in_pads):
                return
            group = {p: self._pending[p].pop(0) for p in self.in_pads}
            tr = self._tr
            if tr is None:
                with Timer(self._m_proc):
                    outs = el.process_group(group)
            else:
                members = list(group.values())
                now0 = time.monotonic_ns()
                tids = [self._trace_queue_wait(b, now0) for b in members]
                t0 = time.perf_counter()
                outs = el.process_group(group)
                dt = time.perf_counter() - t0
                metrics.observe_latency(self._m_proc, dt)
                # collation span LINKS every contributing pad's trace id
                # (the mux/collator fan-in analog of the batch linkage)
                tr.record("stage", self._nm, tids[0], now0, int(dt * 1e9),
                          trace_ids=tids)
                self._propagate_trace([members[0]], outs)
                if self._is_sink:
                    self._trace_sink_delivery(
                        members[0], now0 + int(dt * 1e9))
            self._emit(outs)
            metrics.count(self._m_out)


#: tensor_filter ``framework=`` names that resolve to the llm framework
#: (mirrors analysis/tracecheck.py; kept literal so the hot import path
#: stays free of filters/llm.py)
_LLM_FRAMEWORKS = ("llm", "llamacpp", "llama.cpp")


def _llm_tp_alias(graph: PipelineGraph) -> int:
    """Largest deprecated ``custom=tp:N`` option on any llm tensor_filter
    in the graph (1 = none).  The alias is promoted to
    ``Pipeline(model_parallel=N)`` at construction so the filter runs on
    the pipeline's shared mesh instead of minting a private one."""
    tp = 1
    for node in graph.nodes.values():
        if node.kind != "tensor_filter":
            continue
        if str(node.props.get("framework", "")).lower() \
                not in _LLM_FRAMEWORKS:
            continue
        from ..filters.base import parse_custom_options

        opts = parse_custom_options(str(node.props.get("custom", "")))
        try:
            tp = max(tp, int(opts.get("tp", 1)))
        except (TypeError, ValueError):
            pass  # non-literal tp: the filter's own open() will reject it
    return tp


class Pipeline:
    """Build + run a pipeline graph.

    Accepts a pipeline description string or a parsed PipelineGraph.
    ``fuse=True`` lets the planner merge adjacent device-capable elements
    into single jitted XLA stages.  ``queue_capacity`` bounds each stage's
    input queue (backpressure); ``batch_max`` > 1 additionally lets device
    stages drain up to that many already-queued same-spec buffers into ONE
    bucketed XLA dispatch (``batch_buckets`` bounds the compiled batch
    sizes, ``batch_linger_ms`` optionally waits for stragglers — see
    docs/BATCHING.md).  ``adaptive_buckets`` lets each batchable stage
    refine its OWN ladder online from observed drain occupancies
    (persistent skew mints an exact bucket under a hard census budget —
    docs/BATCHING.md "Adaptive ladder"), and ``bucket_ladders`` warm-
    starts those ladders from a previous run's :meth:`ladder_snapshot`
    export so steady-state deployments compile the refined ladder at
    warmup.  ``data_parallel`` shards those bucketed dispatches
    over the ``data`` axis of a local device mesh (0 = every local device,
    1 = single-device dispatch, N = exactly N chips; the mesh is built
    lazily at :meth:`start`, off the streaming threads, and only
    shard-eligible stages see it), and ``dispatch_depth`` opens an
    in-flight window so a runner drains the next micro-batch while the
    previous one is still executing — see BATCHING.md "Sharded dispatch".
    ``model_parallel`` adds the second mesh axis: the SAME pipeline mesh
    grows a ``model`` dimension (1 = off, N = exactly N ways, 0 = absorb
    every local device ``data`` doesn't claim — see
    ``pipeline/plan.mesh_plan``), shardable stages place their parameters
    per their models' ``param_pspecs`` (sharded over ``model``, replicated
    otherwise), and the llm filter runs tensor-parallel on the shared mesh
    — including its paged KV block pool, sharded over ``model`` on the
    head dim (``custom=tp:N`` is a deprecated alias promoted to this
    knob).  ``NNS_TPU_MODEL_PARALLEL`` / ini ``model_parallel`` configure
    it globally; see docs/BATCHING.md "2-D sharded dispatch".
    ``fetch_depth`` is the OUTPUT-side twin: up to that many sink buffers
    resolve D2H / deferred host_post concurrently on a background pool, so
    fetches overlap the next dispatch instead of serializing in ``pop()``;
    ``donate_ingress`` donates host-fed (appsrc) input buffers to the
    fused program so steady-state H2D reuses HBM; ``reduce_outputs`` lets
    the HBM-residency planner auto-select a model's reduced output (e.g.
    deeplab's native-stride class map) when every downstream consumer's
    caps admit it — see docs/FETCH.md.  The plan is exposed as
    ``Pipeline.residency``.
    ``trace_mode`` (``off``/``ring``/``full``) switches on the per-buffer
    flight recorder: span events for every stage/queue/batch/dispatch
    keyed by trace ids assigned at source ingress, dumped with
    :meth:`dump_trace` as Perfetto-loadable Chrome trace JSON and to the
    log on watchdog fires / stage errors — docs/OBSERVABILITY.md.
    ``xray`` switches on nns-xray predicted-vs-actual reconciliation
    (utils/xray.py): every jit entry point registers its compiles with a
    live program census reconciled against the deep lint's prediction
    (census-drift warnings with signature diffs), per-stage ``mfu`` /
    ``roofline_fraction`` / ``pad_waste_flops`` land in Prometheus and a
    ``device:<stage>`` track in the Chrome trace, and an HBM ledger is
    reconciled per category against the static estimate —
    :meth:`explain` / ``python -m nnstreamer_tpu.tools.doctor`` join it
    all into one report (docs/OBSERVABILITY.md "Predicted vs actual").
    ``tenant`` sets a default tenant identity stamped at source ingress
    (traced runs only) so latency histograms, queue-depth gauges, and
    Chrome-trace tracks split per tenant; ``slo`` attaches a per-tenant
    SLO policy (:mod:`nnstreamer_tpu.utils.slo`) evaluated continuously
    while the pipeline runs, with :meth:`slo_report` as the on-demand
    verdict — docs/SERVING.md "Front door".
    Defaults come from :func:`get_config`.

    ``quarantine`` / ``nan_guard`` / ``journal_replay`` are the
    nns-armor knobs (docs/ROBUSTNESS.md): a DLQ directory (or policy)
    that turns stage-crashing poison-pill requests into quarantined
    records + typed ``abort_reason=poison`` answers with a per-tenant
    repeat-offender circuit breaker; an opt-in NaN/Inf output screen;
    and the restart flag asking every journaled query serversrc to
    re-admit its accepted-but-unanswered WAL entries exactly once.
    ``validate=True`` runs the full static analyzer (caps propagation,
    topology/deadlock, jit-purity — see docs/ANALYSIS.md) over the parsed
    graph before anything is instantiated and raises
    :class:`~nnstreamer_tpu.analysis.PipelineLintError` carrying EVERY
    error at once, instead of the runtime's one-failure-per-start loop.
    ``validate="deep"`` additionally abstractly executes every device
    stage (``jax.eval_shape`` — zero dispatch) so shape/dtype contract
    violations and tracing failures raise HERE too, with this pipeline's
    own batch/sharding knobs feeding the static HBM/recompile budgets
    (docs/ANALYSIS.md "Deep pass").
    """

    def __init__(
        self,
        graph: Union[str, PipelineGraph],
        *,
        fuse: bool = True,
        queue_capacity: Optional[int] = None,
        batch_max: Optional[int] = None,
        batch_buckets: Optional[List[int]] = None,
        batch_linger_ms: Optional[float] = None,
        adaptive_buckets: Optional[bool] = None,
        bucket_ladders: Optional[Dict[str, List[int]]] = None,
        data_parallel: Optional[int] = None,
        model_parallel: Optional[int] = None,
        dispatch_depth: Optional[int] = None,
        fetch_depth: Optional[int] = None,
        donate_ingress: Optional[bool] = None,
        reduce_outputs: Optional[bool] = None,
        trace_mode: Optional[str] = None,
        tenant: Optional[str] = None,
        xray: Optional[bool] = None,
        slo=None,
        max_stage_restarts: Optional[int] = None,
        quarantine=None,
        nan_guard: bool = False,
        journal_replay: bool = False,
        validate: Union[bool, str] = False,
    ):
        if validate:
            # Lint BEFORE strict validation: the analyzer reports every
            # problem at once where parse/validate stop at the first.
            # Strings are parsed ONCE (leniently) and the same graph flows
            # on to graph.validate() below.
            from ..analysis import analyze

            deep = validate == "deep"
            kw = dict(queue_capacity=queue_capacity, deep=deep)
            if deep:
                # the deep pass budgets with THIS pipeline's knobs, not
                # just the global config defaults
                kw.update(batch_max=batch_max, batch_buckets=batch_buckets,
                          adaptive_buckets=adaptive_buckets,
                          data_parallel=data_parallel,
                          model_parallel=model_parallel,
                          dispatch_depth=dispatch_depth)
            if isinstance(graph, str):
                source = graph
                graph = parse_launch(graph, validate=False)
                report = analyze(graph, **kw)
                report.source = source
                report.raise_if_errors()
            else:
                analyze(graph, **kw).raise_if_errors()
        if isinstance(graph, str):
            graph = parse_launch(graph)
        graph.validate()
        # Start the native-lib build (if any) now, off the streaming threads.
        from ..native import prewarm

        prewarm()
        cfg = get_config()
        self.graph = graph
        self.fuse = fuse
        self.capacity = queue_capacity or cfg.queue_capacity
        self.batch_max = max(
            1, batch_max if batch_max is not None else cfg.batch_max)
        self.batch_buckets = list(
            batch_buckets if batch_buckets is not None else cfg.batch_buckets
        ) or None
        self.batch_linger_ms = float(
            batch_linger_ms if batch_linger_ms is not None
            else cfg.batch_linger_ms)
        # Adaptive bucket ladder (docs/BATCHING.md "Adaptive ladder"):
        # per-stage ladders refined from observed occupancies, warm-started
        # from a previous run's ladder_snapshot() export.
        self.adaptive_buckets = bool(
            adaptive_buckets if adaptive_buckets is not None
            else cfg.adaptive_buckets)
        self.bucket_ladders: Dict[str, List[int]] = dict(
            bucket_ladders if bucket_ladders is not None
            else cfg.bucket_ladders)
        self.data_parallel = max(0, int(
            data_parallel if data_parallel is not None
            else cfg.data_parallel))
        self.model_parallel = max(0, int(
            model_parallel if model_parallel is not None
            else cfg.model_parallel))
        self.dispatch_depth = max(1, int(
            dispatch_depth if dispatch_depth is not None
            else cfg.dispatch_depth))
        self.fetch_depth = max(1, int(
            fetch_depth if fetch_depth is not None else cfg.fetch_depth))
        self.donate_ingress = bool(
            donate_ingress if donate_ingress is not None
            else cfg.donate_ingress)
        self.reduce_outputs = bool(
            reduce_outputs if reduce_outputs is not None
            else cfg.reduce_outputs)
        # elastic stage restarts (docs/SERVING.md "Elastic serving"):
        # pure/stateless stages may be restarted in place this many
        # times after an exception before the pipeline fails for real
        self.max_stage_restarts = max(0, int(
            max_stage_restarts if max_stage_restarts is not None
            else cfg.max_stage_restarts))
        self.trace_mode = str(
            trace_mode if trace_mode is not None else cfg.trace_mode)
        if self.trace_mode not in ("off", "ring", "full"):
            raise PipelineError(
                f"trace_mode must be off|ring|full, got {self.trace_mode!r}")
        # default tenant: stamped onto buffers at source ingress when
        # tracing is active (the off path stays stamp-free — see
        # _Runner._run_source and docs/SERVING.md "Front door")
        self.tenant = None if tenant is None else str(tenant)
        # nns-xray predicted-vs-actual reconciliation (utils/xray.py,
        # docs/OBSERVABILITY.md "Predicted vs actual"): when on, every
        # jit entry point registers its compiles with the process-wide
        # program registry, per-stage device time/MFU is attributed, and
        # a reconciler daemon checks the HBM ledger against the deep
        # lint's estimate.  Off = elements hold None, one pointer check
        # per hook (the trace_mode=off discipline).
        self.xray = bool(xray if xray is not None else cfg.xray)
        self._xray_reg = None
        if self.xray:
            from ..utils import xray as _xray_mod

            self._xray_reg = _xray_mod.registry
        # slo policy parsed HERE so a bad config fails at construction
        # (a ValueError naming every schema problem), not inside start()
        # after stage threads are already running
        self._slo_policy = None
        self._slo_engine = None
        if slo is not None:
            from ..utils.slo import load_policy

            try:
                self._slo_policy = load_policy(slo)
            except (ValueError, OSError) as e:
                raise PipelineError(str(e)) from e
        if self.trace_mode != "off":
            # the flight recorder is process-wide (like core.log.metrics);
            # an off pipeline never touches it
            tracing.recorder.configure(self.trace_mode,
                                       cfg.trace_ring_capacity)
        self._stopping = threading.Event()
        self._errors: List[Tuple[str, BaseException]] = []
        self._err_lock = threading.Lock()
        self._started = False

        # nns-armor (docs/ROBUSTNESS.md): ``quarantine=`` (a DLQ
        # directory path / policy dict / QuarantinePolicy) turns a
        # poison-pill request — one whose stage invoke raises — into a
        # quarantined DLQ record + a typed ``abort_reason=poison``
        # answer, with the pipeline serving on; ``nan_guard=True``
        # additionally treats NaN/Inf stage outputs as poison (pays a
        # host check per output).  Repeat offenders trip a per-tenant
        # circuit breaker that flips the query front door's
        # ``tenant_admission`` override to shed.  ``journal_replay=True``
        # asks every journaled serversrc to re-admit its
        # accepted-but-unanswered WAL entries at start().
        self._armor = None
        if quarantine is not None or nan_guard:
            from ..utils import armor as _armor

            policy = _armor.QuarantinePolicy.of(quarantine) \
                if quarantine is not None else _armor.QuarantinePolicy()
            try:
                self._armor = _armor.Armor(
                    policy, nan_guard=nan_guard,
                    apply_admission=self._breaker_admission,
                    recorder=(tracing.recorder
                              if self.trace_mode != "off" else None))
            except ValueError as e:
                raise PipelineError(str(e)) from e
        self._journal_replay = bool(journal_replay)

        # Deprecated ``custom=tp:N`` alias (the llm filter's pre-2-D
        # private-mesh knob): promote it to the pipeline-owned
        # model_parallel BEFORE any element opens, so the filter lands on
        # the shared mesh instead of minting its own.  An explicit
        # pipeline model_parallel (0 or >1) wins over the alias.
        tp_alias = _llm_tp_alias(graph)
        if tp_alias > 1:
            if self.model_parallel == 1:
                log.warning(
                    "tensor_filter llm custom=tp:%d is deprecated — "
                    "promoted to Pipeline(model_parallel=%d); the filter "
                    "now runs tensor-parallel on the pipeline's shared "
                    "(data x model) mesh", tp_alias, tp_alias)
                self.model_parallel = tp_alias
            else:
                log.warning(
                    "custom=tp:%d ignored: the pipeline's explicit "
                    "model_parallel=%d wins (tp: is a deprecated alias)",
                    tp_alias, self.model_parallel)

        # THE pipeline mesh (2-D placement): built lazily, at most once,
        # by _shared_mesh() — from start() for sharded micro-batching, or
        # earlier from a TP consumer's _mesh_provider call during open().
        self._mesh_obj = None
        self._mesh_built = False
        self._mesh_lock = threading.Lock()
        #: resolved (data, model) axis sizes once the mesh is built
        self.mesh_shape: Tuple[int, int] = (1, 1)

        # 1. instantiate elements
        self.elements: Dict[int, Element] = {}
        for node in graph.nodes.values():
            if node.kind == "capsfilter":
                el = _CapsFilter(node.caps)
            else:
                cls = registry_get(KIND_ELEMENT, node.kind)
                el = cls(dict(node.props), name=node.name or f"{node.kind}{node.id}")
            self.elements[node.id] = el
            # 2-D placement: every element gets a lazy accessor to the
            # shared mesh BEFORE negotiation opens any framework — the
            # llm filter's TP path reads it at open() (None unless
            # model_parallel is configured, so dp-only/single-device
            # pipelines stay backend-free here)
            el._mesh_provider = self._model_mesh
            # armor + journal attach (the _trace_rec pattern): the llm
            # serve loop quarantines through el._armor, journaled
            # serversrcs honor the pipeline-level replay flag
            el._armor = self._armor
            # nns-learn: a tensor_trainer with swap-to=<stage> hot-swaps
            # its refreshed params into that serving stage at each epoch
            # boundary through this callback (docs/TRAINING.md)
            el._swap_cb = self.swap_params
            if self._journal_replay:
                el._journal_replay = True

        # 2. HBM-residency pre-pass: mark filters whose downstream
        # consumers ALL admit reduced output geometry, so negotiation
        # below can switch them to the model's reduced variant — "fetch
        # the smaller thing" by default (pipeline/residency.py,
        # docs/FETCH.md).  Runs BEFORE negotiation: it changes the specs.
        from . import residency as _residency

        if self.reduce_outputs:
            _residency.mark_reduced_admissible(graph, self.elements)

        # 3. caps negotiation in topo order
        self._negotiate()

        # 4. plan stages (fusion pass + ingress donation)
        self.stages: List[Stage] = plan_stages(
            graph, self.elements, fuse=fuse,
            donate_ingress=self.donate_ingress)

        # 4b. adaptive-ladder variant budget: the SAME arithmetic the deep
        # analyzer prices the worst-case census with (plan.py), resolved
        # against the planned batchable-stage count — so runtime minting
        # can never exceed what the static report already charged.
        from .batching import ladder as _ladder_fn
        from .plan import adaptive_variant_budget

        self._ladder_budget = adaptive_variant_budget(
            len(_ladder_fn(self.batch_max, self.batch_buckets)),
            sum(1 for s in self.stages if s.batchable),
            cfg.max_compiled_variants)

        # 5. residency plan: what crosses to host per sink edge (logged;
        # exposed as Pipeline.residency for apps/bench/tests)
        self.residency = _residency.plan_residency(
            graph, self.elements, self.stages)
        if self.residency.fetch or self.residency.reduced_outputs:
            log.info("%s", self.residency.render())
        # sinks read the pipeline's fetch window width (same attach
        # pattern as _batch_buckets)
        for el in self.elements.values():
            if isinstance(el, SinkElement):
                el._fetch_depth = self.fetch_depth

        # 6. wire runners
        self._runners: Dict[int, _Runner] = {}
        node_to_stage: Dict[int, Stage] = {}
        for st in self.stages:
            for nid in st.node_ids:
                node_to_stage[nid] = st
        stage_runner: Dict[int, _Runner] = {}
        for st in self.stages:
            r = _Runner(self, st, self.capacity)
            stage_runner[id(st)] = r
            for nid in st.node_ids:
                self._runners[nid] = r
        for e in graph.edges:
            src_stage = node_to_stage[e.src]
            dst_stage = node_to_stage[e.dst]
            if src_stage is dst_stage:
                continue  # fused-internal edge
            r_src = stage_runner[id(src_stage)]
            r_dst = stage_runner[id(dst_stage)]
            out_pad = src_stage.external_out_pad(e)
            in_pad = dst_stage.external_in_pad(e)
            r_src.connect(out_pad, _Port(r_dst, in_pad))
            r_dst.in_pads.append(in_pad)

        self._by_name: Dict[str, Element] = {}
        for nid, el in self.elements.items():
            node = graph.nodes[nid]
            if node.name:
                self._by_name[node.name] = el
            self._by_name.setdefault(el.name, el)

        # A non-source element with no input link can never receive a
        # buffer — almost always a missing '!' between two elements (the
        # parser accepts gst-launch's multi-chain juxtaposition, so this
        # is only detectable once element classes are known).  Fail at
        # construction instead of hanging the first pull.
        from ..elements.base import SourceElement

        for nid, el in self.elements.items():
            if isinstance(el, SourceElement):
                continue
            if not self.graph.in_edges(nid):
                raise PipelineError(
                    f"element {el.name!r} ({self.graph.nodes[nid].kind}) "
                    "has no input link — missing '!' before it?")

    # -- negotiation -------------------------------------------------------
    def _negotiate(self) -> None:
        out_caps: Dict[Tuple[int, str], Caps] = {}
        for node in self.graph.topo_order():
            el = self.elements[node.id]
            in_caps: Dict[str, Caps] = {}
            for e in self.graph.in_edges(node.id):
                in_caps[e.dst_pad] = out_caps.get((e.src, e.src_pad), Caps.any())
            out_pads = sorted({e.src_pad for e in self.graph.out_edges(node.id)}) or [SRC]
            produced = el.configure(in_caps, out_pads)
            for pad in out_pads:
                out_caps[(node.id, pad)] = produced.get(pad, Caps.any())

    # -- control plane -----------------------------------------------------
    def start(self) -> "Pipeline":
        if getattr(self, "_dead", False):
            raise PipelineError(
                "pipeline failed startup validation and was stopped; "
                "build a new Pipeline")
        if self._started:
            return self
        self._started = True
        for el in self.elements.values():
            el._stop_event = self._stopping  # lets blocking sinks shed on stop
            el.start()
        # Reject typo'd properties like gst_parse_launch ("no property X in
        # element"): by now every element (and its lazy start()-time
        # readers) consulted what it understands.
        unknown = {
            el.name: sorted(u)
            for el in self.elements.values()
            if (u := el.unknown_props())
        }
        if unknown:
            self.stop()
            self._dead = True  # elements stopped: this instance is done
            raise PipelineError(
                f"unknown element properties (typo?): {unknown}")
        try:
            mesh = self._build_mesh()
        except Exception:
            # Same contract as the unknown-props failure above: elements
            # already started, so a half-started pipeline must be torn
            # down NOW (serve threads, sockets, opened models) — and a
            # retried start() must not silently return a dead instance.
            self.stop()
            self._dead = True
            raise
        if mesh is not None:
            # Attached to the ELEMENT the same way _batch_buckets is: the
            # element's lazy BatchRunner reads it at first batched
            # dispatch.  Only shard-eligible stages ever see it.
            from ..parallel.mesh import mesh_axis_size

            replicas = mesh_axis_size(mesh, "data")
            for r in {id(r): r for r in self._runners.values()}.values():
                if r.stage.shardable and r.batch_max > 1:
                    r.element._shard_mesh = mesh
                    lad = getattr(r.element, "_batch_ladder", None)
                    if lad is not None:
                        # minted sizes must stay replica-aligned so
                        # shard_bucket_for's rounding is a no-op on them
                        # (2-D mesh rounding still applies)
                        lad.align = max(1, replicas)
        if self._xray_reg is not None:
            # census expectations BEFORE any streaming thread can compile:
            # the predicted budgets use the same shared arithmetic the
            # deep lint prices with (ladder / adaptive budget / shard
            # rounding), so runtime drift is measured against the exact
            # static promise.
            self._install_xray_expectations(
                self.mesh_shape[0] if self._mesh_built else 1)
        for r in {id(r): r for r in self._runners.values()}.values():
            r.thread.start()
        if self.trace_mode != "off":
            # queue-depth / backpressure / staleness gauges, sampled off
            # the streaming threads (docs/OBSERVABILITY.md); daemon +
            # stop-event bound, so teardown never waits on it
            self._sampler = threading.Thread(
                target=self._sample_loop, name="nns-sampler", daemon=True)
            self._sampler.start()
        if self._slo_policy is not None:
            # continuous SLO evaluation off the live histograms: burn-rate
            # / breach gauges per tenant (utils/slo.py).  Requires tracing
            # (the e2e histograms only fill when trace_mode != off).
            self._slo_loop().start()
        if self._xray_reg is not None:
            # the predicted-vs-actual loop: MFU/roofline gauges + the HBM
            # ledger reconciled against the deep-lint estimate, on the
            # SLO engine's cadence; stopped AND joined by stop()
            from ..utils.xray import XrayReconciler

            self._xray_recon = XrayReconciler(self)
            self._xray_recon.start()
        return self

    @property
    def mesh(self):
        """THE pipeline mesh (None before start()/first TP open, or when
        the plan resolves to a single device)."""
        return self._mesh_obj

    def _model_mesh(self):
        """Mesh provider handed to elements (the llm filter's TP path):
        the shared pipeline mesh when a >1 ``model`` axis is configured,
        else None — dp-only and single-device pipelines never touch the
        device backend through this accessor."""
        if self.model_parallel == 1:
            return None
        return self._shared_mesh()

    def _shared_mesh(self):
        """Build (at most once) THE pipeline mesh from the resolved
        ``(data, model)`` plan (``pipeline/plan.mesh_plan`` — the same
        arithmetic the deep lint budgets with).  Returns None when the
        plan degenerates to a single device; raises
        :class:`PipelineError` on an over-ask the host cannot supply."""
        with self._mesh_lock:
            if self._mesh_built:
                return self._mesh_obj
            import jax

            from ..parallel.mesh import make_mesh
            from .plan import mesh_plan

            devs = jax.devices()
            dp, mp = mesh_plan(self.data_parallel, self.model_parallel,
                               self.batch_max, len(devs))
            if dp * mp > len(devs):
                if mp == 1:
                    raise PipelineError(
                        f"data_parallel={dp} needs {dp} local devices, "
                        f"have {len(devs)}")
                raise PipelineError(
                    f"data_parallel={dp} x model_parallel={mp} needs "
                    f"{dp * mp} local devices, have {len(devs)}")
            self.mesh_shape = (dp, mp)
            if dp == 1 and mp == 1:
                self._mesh_obj = None
            else:
                try:
                    self._mesh_obj = make_mesh(
                        data=dp, model=mp, devices=devs[:dp * mp])
                except ValueError as e:
                    raise PipelineError(str(e)) from e
            self._mesh_built = True
            return self._mesh_obj

    def _build_mesh(self):
        """Resolve the 2-D placement to the pipeline mesh, or None for
        single-device dispatch.  Built HERE — on the app thread driving
        start(), never a streaming thread — and lazily: a pipeline with
        no shard-eligible stage (or batch_max=1, or data_parallel=1) and
        no model_parallel config never touches the device backend for
        this feature.  (A TP llm filter may have forced the build
        earlier, at open() — the memoized mesh is reused.)"""
        dp_wanted = (self.batch_max > 1 and self.data_parallel != 1
                     and any(s.shardable for s in self.stages))
        mp_wanted = self.model_parallel != 1
        if not (dp_wanted or mp_wanted or self._mesh_built):
            return None
        return self._shared_mesh()

    def _install_xray_expectations(self, replicas: int) -> None:
        """Install the predicted census for every stage that can compile
        (docs/OBSERVABILITY.md "Predicted vs actual") — the SAME shared
        arithmetic the deep lint prices with: the bucket ladder (plus
        replica rounding under a data mesh) for batchable stages, the
        adaptive mint budget when ladders refine online, and a
        2-program allowance for the single-buffer path (static spec +
        the truncated-tail shape a non-aligned device source can mint).
        invoke-dynamic filters get NO expectation — the lint calls them
        recompile-unbounded, so the live census records without judging.
        The llm serve loop and device aggregator install their own
        (serving_plan / AGGREGATOR_PROGRAMS) at build time."""
        from .batching import ladder as _ladder_fn, shard_bucket_for

        reg = self._xray_reg
        for r in {id(r): r for r in self._runners.values()}.values():
            el = r.element
            target = getattr(el, "fused", el)  # folded-source inner chain
            nm = target.name
            if r.stage.batchable and r.batch_max > 1:
                lad = _ladder_fn(r.batch_max, self.batch_buckets)
                if getattr(el, "_batch_ladder", None) is not None:
                    # adaptive: minted sizes are legal anywhere, the
                    # budget is the closed bound (plan arithmetic)
                    reg.expect(nm, "batch", budget=self._ladder_budget,
                               note="adaptive ladder budget")
                else:
                    allow = set(lad)
                    if replicas > 1:
                        allow |= {shard_bucket_for(b, replicas,
                                                   self.batch_buckets)
                                  for b in lad}
                    reg.expect(nm, "batch", budget=len(allow),
                               allow=allow, note="static bucket ladder")
                reg.expect(nm, "stage", budget=2,
                           note="single-buffer program (+ tail shape)")
            elif (getattr(el, "kind", "") == "fused"
                  or (getattr(el, "kind", "") == "tensor_filter"
                      and not getattr(el, "invoke_dynamic", False))):
                reg.expect(nm, "stage", budget=2,
                           note="single-buffer program (+ tail shape)")

    def stop(self) -> None:
        self._stopping.set()
        if self._slo_engine is not None:
            self._slo_engine.stop()
        recon = getattr(self, "_xray_recon", None)
        if recon is not None:
            recon.stop()  # joins: the thread-shutdown audit counts it
        runners = {id(r): r for r in self._runners.values()}.values()
        # Close every stage queue first: blocked getters receive _POISON
        # and blocked putters shed immediately, so join() below is not
        # racing 0.1 s polls (seed worst case: ~100 ms PER HOP).
        for r in runners:
            r.queue.close()
        for r in runners:
            if r.thread.ident is not None:  # start() may have failed part-way
                r.thread.join(timeout=5.0)
        for el in self.elements.values():
            try:
                el.stop()
            except Exception:  # noqa: BLE001
                log.exception("stop() failed for %s", el.name)
        # the sampler exits on _stopping; JOIN it so stop() returning
        # means every pipeline-owned thread is actually gone (the
        # shutdown audit's contract — daemon status is not cleanup)
        sampler = getattr(self, "_sampler", None)
        if sampler is not None and sampler.is_alive():
            sampler.join(timeout=2.0)

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until every stage thread finished (sources EOS'd and all
        buffers drained)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        for r in {id(r): r for r in self._runners.values()}.values():
            t = None if deadline is None else max(0.0, deadline - time.monotonic())
            r.thread.join(timeout=t)
            if r.thread.is_alive():
                raise PipelineError(f"stage {r.element.name} did not finish")
        self.check()

    def check(self) -> None:
        with self._err_lock:
            if self._errors:
                name, exc = self._errors[0]
                raise PipelineError(f"stage {name} failed: {exc!r}") from exc

    def _breaker_admission(self, tenant: str, engage: bool) -> None:
        """The armor circuit breaker's lever: flip ``tenant``'s admission
        override to shed on every query-server core of this pipeline
        (PR 11's autoscaler map, reused — docs/ROBUSTNESS.md)."""
        for el in self.elements.values():
            core = getattr(el, "_core", None)
            if core is not None and hasattr(core, "tenant_admission"):
                if engage:
                    # "shed-all": unconditional, unlike the autoscaler's
                    # backlog-conditional "shed" — a poison spewer must
                    # not keep crashing invokes just because the queue
                    # has room
                    core.tenant_admission[tenant] = "shed-all"
                else:
                    core.tenant_admission.pop(tenant, None)

    def _record_error(self, name: str, exc: BaseException) -> None:
        with self._err_lock:
            self._errors.append((name, exc))
        # Post-mortem: every stall/crash report carries the recent span
        # timeline when the flight recorder is on (no-op otherwise).
        tracing.dump_recent_to_log(
            log, reason=f"stage {name} failed: {exc!r}")

    # -- observability -----------------------------------------------------
    def ladder_snapshot(self) -> Dict[str, List[int]]:
        """Export every adaptive stage's CURRENT bucket ladder (base +
        minted sizes) keyed by stage name — feed it back via
        ``Pipeline(bucket_ladders=...)`` / ``Config.bucket_ladders``
        (``NNS_TPU_BUCKET_LADDERS``, ini ``[ladders]``) so a steady-state
        run compiles the refined ladder at warmup instead of re-learning
        it.  Empty when ``adaptive_buckets`` is off."""
        out: Dict[str, List[int]] = {}
        for r in {id(r): r for r in self._runners.values()}.values():
            lad = getattr(r.element, "_batch_ladder", None)
            if lad is not None:
                out[r.element.name] = lad.export()
        return out

    def sample_queues(self) -> None:
        """One sampler tick: queue-depth / in-flight-window gauges per
        stage, staleness watermark per sink (seconds since last delivery).
        Public so apps can sample on their own cadence without the
        tracer's thread."""
        now = time.monotonic_ns()
        for r in {id(r): r for r in self._runners.values()}.values():
            metrics.gauge(f"{r._nm}.queue_depth", float(r.queue.qsize()))
            # per-tenant split of the same gauge; tenants seen on a
            # previous tick but absent now are zeroed, so an idle
            # tenant's labeled depth reads 0, not its last backlog
            depths = r.queue.tenant_depths()
            for ten in r._gauge_tenants.difference(depths):
                metrics.gauge(f"{r._nm}.queue_depth", 0.0, tenant=ten)
            for ten, depth in depths.items():
                metrics.gauge(f"{r._nm}.queue_depth", float(depth),
                              tenant=ten)
            r._gauge_tenants.update(depths)
            if r.dispatch_depth > 1:
                metrics.gauge(f"{r._nm}.inflight_window",
                              float(len(r._inflight)))
            if r._is_sink and r._last_sink_ns:
                metrics.gauge(f"{r._nm}.staleness_s",
                              (now - r._last_sink_ns) / 1e9)

    def _sample_loop(self, period_s: float = 0.1) -> None:
        while not self._stopping.wait(period_s):
            try:
                self.sample_queues()
            except Exception:  # noqa: BLE001 - sampler must never die loud
                log.exception("queue sampler tick failed")

    def explain(self) -> dict:
        """The predicted-vs-actual doctor report (utils/xray.explain):
        plan + mesh, residency, the compiled-program census (deep-lint
        budgets vs the live program set + any drift), the HBM ledger per
        category (measured vs the deep-lint estimate), per-stage
        device-time/MFU attribution, and the SLO verdict when an engine
        is attached.  JSON-serializable; render with
        ``utils.xray.render_report`` or via
        ``python -m nnstreamer_tpu.tools.doctor`` — see
        docs/OBSERVABILITY.md "Predicted vs actual".  Works on any
        pipeline; census/MFU columns fill only under
        ``Pipeline(xray=True)``."""
        from ..utils import xray as _xray_mod

        return _xray_mod.explain(self)

    def dump_trace(self, path: str) -> int:
        """Write the flight recorder's current contents as Chrome
        trace-event JSON (Perfetto / chrome://tracing); returns the span
        count.  See docs/OBSERVABILITY.md and
        ``python -m nnstreamer_tpu.tools.trace``."""
        return tracing.dump_chrome(tracing.recorder.events(), path)

    def _slo_loop(self):
        """Build (once) the SLO engine bound to this pipeline's sinks.
        ``slo=`` accepts an :class:`~nnstreamer_tpu.utils.slo.SLOPolicy`,
        a config dict, or a JSON file path (utils/slo.py) — parsed and
        validated at construction."""
        if self._slo_engine is None:
            from ..utils.slo import SLOEngine, SLOPolicy

            sinks = [el.name for el in self.elements.values()
                     if isinstance(el, SinkElement)]
            self._slo_engine = SLOEngine(
                self._slo_policy or SLOPolicy(), sinks=sinks)
        return self._slo_engine

    def slo_report(self) -> dict:
        """Per-tenant SLO verdict evaluated NOW off the live labeled
        histograms (docs/SERVING.md "Front door"): measured p50/p99/fps
        vs each tenant's objectives, shed counts, error-budget burn rate,
        and — for breaching tenants — the dominant offending span kind
        attributed from the flight-recorder ring.  Requires
        ``trace_mode != off`` for latency/throughput objectives (the e2e
        histograms are only fed when tracing is on)."""
        return self._slo_loop().report()

    # -- elastic serving: drain / handover ---------------------------------
    def serve_streams(self) -> Dict[int, dict]:
        """Continuous-serving streams live on this pipeline:
        ``stream_id -> {"state", "tenant", "slot", "blocks",
        "element"}`` (docs/SERVING.md "Elastic serving")."""
        out: Dict[int, dict] = {}
        for el in self.elements.values():
            table_fn = getattr(el, "serve_streams", None)
            if table_fn is None:
                continue
            try:
                table = table_fn()
            except Exception:  # noqa: BLE001 - discovery must not throw
                continue
            for sid, info in table.items():
                out[sid] = {**info, "element": el.name}
        return out

    def drain_stream(self, stream_id: int, timeout: float = 30.0) -> dict:
        """Serialize one live continuous-serving stream OFF this
        pipeline: its paged KV blocks, slot state, and request meta
        become a host-value snapshot (the trainer/checkpoint.py
        serialization substrate — persist it with
        ``trainer.checkpoint.save_stream_snapshot``), and its slot +
        blocks return to the pool's free list.  :meth:`adopt_stream` on
        another pipeline (or this one, after a versioned-config
        restart) continues the stream — bit-identically for greedy
        decode — so recompile-requiring config changes become
        drain → restart → adopt instead of dropped traffic.  The move
        is host-side values only; neither pipeline's 3-program decode
        census is touched (span: ``elastic.drain``)."""
        for el in self.elements.values():
            table_fn = getattr(el, "serve_streams", None)
            if table_fn is None:
                continue
            try:
                owned = stream_id in table_fn()
            except Exception:  # noqa: BLE001
                continue
            if owned:
                return el.drain_serve_stream(stream_id, timeout)
        raise PipelineError(
            f"no live serve stream {stream_id} on this pipeline "
            f"(known: {sorted(self.serve_streams())})")

    def adopt_stream(self, snapshot: dict, timeout: float = 30.0) -> int:
        """Re-admit a drained stream (:meth:`drain_stream`'s snapshot,
        or one loaded via ``trainer.checkpoint.load_stream_snapshot``)
        into this pipeline's continuous-serving filter.  Returns the
        stream id; the remaining tokens flow to THIS pipeline's sinks
        (span: ``elastic.adopt``)."""
        last_err: Optional[Exception] = None
        for el in self.elements.values():
            adopt_fn = getattr(el, "adopt_serve_stream", None)
            if adopt_fn is None:
                continue
            fw = getattr(el, "fw", None)
            if fw is None or not getattr(fw, "continuous", False):
                continue
            try:
                return adopt_fn(snapshot, timeout=timeout)
            except Exception as e:  # noqa: BLE001 - try other filters
                last_err = e
        if last_err is not None:
            raise PipelineError(
                f"adopt_stream failed: {last_err}") from last_err
        raise PipelineError(
            "no continuous-serving filter on this pipeline to adopt "
            "into (need tensor_filter framework=llm "
            "custom=serve:continuous)")

    # -- nns-learn: train-while-serve param hot-swap -----------------------
    def swap_params(self, stage: str, tree_or_ckpt) -> int:
        """Hot-swap updated parameters into a LIVE serving stage
        (docs/TRAINING.md): ``tree_or_ckpt`` is a param pytree (e.g. a
        trainer's ``export_params()``) or a checkpoint path
        (``trainer/checkpoint.py``).  The swap is a VALUE move executed
        at a dispatch boundary — same tree structure, same per-leaf
        avals, so the stage's compiled programs are untouched and
        NOTHING recompiles (census pinned by nns-xray); a no-op swap is
        bit-identical, a real one serves the new weights from the next
        dispatch.  Returns the stage's new param version (the
        ``<stage>.param_version`` gauge / ``learn.swap`` span twin).

        Raises :class:`PipelineError` for a stage that cannot swap: a
        FUSED chain (its program bakes params into the composed closure
        at build time — run the serving filter unfused, e.g. between
        host elements or with ``fuse=False``) or a framework without a
        parametric dispatch path."""
        el = self.element(stage)
        nid = next((k for k, v in self.elements.items() if v is el), None)
        runner = self._runners.get(nid) if nid is not None else None
        if runner is not None and runner.element is not el:
            raise PipelineError(
                f"stage {stage!r} is fused into {runner.element.name!r} — "
                "the fused program captures params at build time, so a "
                "swap would silently not take; keep hot-swappable "
                "serving filters unfused (fuse=False, or a graph where "
                "the filter is not part of a linear device chain)")
        if runner is not None and runner.batch_max > 1 \
                and runner.stage.batchable:
            # same trap as fusion: the BatchRunner's bucket programs are
            # built from pure_fn() closures that SNAPSHOT params — a
            # swap would bump the version yet keep serving old weights
            raise PipelineError(
                f"stage {stage!r} runs micro-batched (batch_max="
                f"{runner.batch_max}) — bucketed dispatch captures "
                "params at build time, so a swap would silently not "
                "take; run the hot-swappable serving stage with "
                "batch_max=1 (or an llm serve:continuous stage, whose "
                "loop swaps at chunk boundaries)")
        swap = getattr(el, "swap_params", None)
        if swap is None:
            raise PipelineError(
                f"element {stage!r} ({getattr(el, 'kind', '?')}) has no "
                "swappable parameters")
        tree = tree_or_ckpt
        if isinstance(tree_or_ckpt, str):
            from ..trainer.checkpoint import load_checkpoint

            tree, _opt, _step = load_checkpoint(tree_or_ckpt)
        try:
            return int(swap(tree))
        except PipelineError:
            raise
        except Exception as e:  # noqa: BLE001 - typed to the caller
            raise PipelineError(
                f"swap_params({stage!r}) failed: {e}") from e

    def __enter__(self) -> "Pipeline":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- app I/O -----------------------------------------------------------
    def element(self, name: str) -> Element:
        try:
            return self._by_name[name]
        except KeyError:
            raise KeyError(f"no element named {name!r}") from None

    def push(self, name: str, data, pts: Optional[int] = None) -> None:
        el = self.element(name)
        if not hasattr(el, "push"):
            raise PipelineError(f"element {name!r} is not an app source")
        el.push(data, pts=pts)
        self.check()

    def eos(self, name: Optional[str] = None) -> None:
        """Signal end-of-stream on one (or every) app source."""
        targets = [self.element(name)] if name else [
            el for el in self.elements.values() if hasattr(el, "signal_eos")
        ]
        for el in targets:
            if hasattr(el, "signal_eos"):
                el.signal_eos()

    def pull(self, name: str, timeout: float = 30.0):
        el = self.element(name)
        if not hasattr(el, "pop"):
            raise PipelineError(f"element {name!r} is not a pullable sink")
        out = el.pop(timeout=timeout, check=self.check)
        return out


class _CapsFilter(Element):
    """Pseudo-element for inline caps constraints (``video/x-raw,width=...``).

    A capsfilter is a negotiation-time CONSTRAINT, not a runtime
    transform: once :meth:`configure` proved the intersection, every
    buffer passes through untouched.  It therefore exposes the identity
    as its :meth:`device_fn` — so the planner fuses straight THROUGH
    dtype/shape pins instead of splitting the chain on them.  Before
    this, the idiomatic quantized-boundary pin
    (``transform ! other/tensors,types=uint8 ! tensor_filter``) left the
    transform (and any decoder tail behind a post-filter pin) OUTSIDE
    the fused filter dispatch: three stages, two queue hops.  The fused
    identity costs nothing — XLA folds it
    away — and bit-identity with the split path is pinned by tests.
    """

    kind = "capsfilter"

    def __init__(self, caps: Optional[Caps]):
        super().__init__({}, name="capsfilter")
        self.filter_caps = caps or Caps.any()

    def configure(self, in_caps, out_pads):
        self.in_caps = dict(in_caps)
        src = next(iter(in_caps.values()), Caps.any())
        merged = src.intersect(self.filter_caps)
        if merged is None:
            raise PipelineError(
                f"caps filter {self.filter_caps} incompatible with upstream {src}"
            )
        self.out_caps = {p: merged for p in out_pads}
        return self.out_caps

    def process(self, pad, buf):
        return [(SRC, buf)]

    def device_fn(self, in_spec):
        # Identity, provable at plan time: the constraint was enforced at
        # negotiation, so inside a fused program this element is a no-op.
        # The out spec is the MERGED caps' spec when one was negotiated
        # (it may be more specific than upstream's), else the input spec.
        caps = self.out_caps.get(SRC) if self.out_caps else None
        spec = getattr(caps, "spec", None)
        return (lambda arrays: arrays), (spec or in_spec)
