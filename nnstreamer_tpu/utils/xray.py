"""nns-xray: predicted-vs-actual reconciliation for the running pipeline.

The deep lint (docs/ANALYSIS.md "Deep pass") makes *static promises* — a
closed compiled-program census, an HBM high-water estimate, fetch-bound
verdicts — and until now the runtime was *trusted* to honor them: an
unpredicted recompile, an HBM estimate drifting 2x from silicon, or a
stage running at 4% MFU was invisible until a chip sweep regressed.  This
module closes the loop:

* **Program registry / census drift** — every jit entry point (BatchRunner
  bucket programs, FusedElement chains, the jax tensor_filter path, the
  llm 3-program serve loop, the device-aggregator ring) registers its
  compiles with the process-wide :data:`registry` — stage, abstract
  signature, trigger shape, compile wall time — and the registry
  reconciles the live program set against the deep lint's predicted
  census CONTINUOUSLY: the prediction arithmetic is the SAME shared code
  (``pipeline/batching.ladder``, ``plan.adaptive_variant_budget``,
  ``serving_plan()['programs']``, ``tracecheck.AGGREGATOR_PROGRAMS``), so
  an unpredicted signature fires a ``census-drift`` warning carrying the
  field-level signature diff (reusing
  :func:`~nnstreamer_tpu.core.caps.explain_mismatch`) plus a
  flight-recorder ring dump, and ``<stage>.compiles`` /
  ``xray.census_drift`` land in Prometheus.

* **Device-time / MFU attribution** — per-dispatch FLOPs/bytes from the
  compiled program's cost analysis (``jit(fn).lower(...).cost_analysis()``
  — a trace, never an extra backend compile) joined with measured dispatch
  wall time yield per-stage ``mfu`` and ``roofline_fraction`` gauges and
  price the bucket ladder's pad waste in FLOPs
  (``<stage>.pad_waste_flops``), with a ``device:<stage>`` track emitted
  into the Chrome/Perfetto trace beside the host spans.  On async
  backends the measured time is the host-side dispatch window (sinks are
  where the pipeline blocks); on the CPU proxy it is compute.

* **HBM ledger** — a live per-category ledger (params / KV pool /
  aggregator rings / dispatch-window activations; device
  ``memory_stats()`` where the backend provides them, model-side
  accounting elsewhere) reconciled against the deep-lint estimate
  (:meth:`ResourceReport.by_category`), warning past
  ``Config.xray_hbm_tolerance``.

* :func:`explain` / ``python -m nnstreamer_tpu.tools.doctor`` — one
  report joining plan, residency, mesh, census, SLO verdicts, and the
  measured ledger into predicted-vs-actual columns with a
  machine-readable JSON twin for CI.

**Zero overhead when off** (the PR 5 ``record()``-raises discipline):
instrumentation sites hold ``element._xray`` — ``None`` unless
``Pipeline(xray=True)`` / ``NNS_TPU_XRAY=1`` — so the disabled hot path
is ONE pointer check: no wrapper objects, no meta, no cost_analysis
calls.  Pinned structurally by tests/test_xray.py (registry methods
monkeypatched to raise under an xray-off run).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

from ..core.log import logger, metrics
from . import locks

log = logger(__name__)

#: HBM ledger categories — the deep lint's StageResource fields, measured
#: live (docs/OBSERVABILITY.md "Predicted vs actual").  ``train_state``
#: (nns-learn) = trainer optimizer moments + the device-resident
#: streaming sample window, reconciled against
#: ``trainer/subplugin.train_plan``'s eval_shape-abstracted estimate.
HBM_CATEGORIES: Tuple[str, ...] = ("params", "kv_pool", "agg_rings",
                                   "activations", "train_state",
                                   "prng_state")

#: ledger categories below this are never drift-warned: transient
#: windows (activations) legitimately read 0 between dispatches, and
#: byte-level noise on tiny stages is not an estimate failure
HBM_WARN_FLOOR = 1 << 20

#: peak dense bf16 matmul TFLOP/s of one chip, keyed by the lowercased
#: ``device_kind`` jax reports (Google Cloud TPU documentation, per
#: generation; a v5e chip reports "TPU v5 lite", a v5p chip "TPU v5").
#: ``Config.peak_tflops`` overrides.  The CPU row makes MFU numbers on the
#: host *indicative only* (the gauge still proves the attribution
#: plumbing end to end); an accelerator missing from the table is an
#: error, not a default.
_PEAK_TFLOPS_BY_KIND: Dict[str, float] = {
    "tpu v2": 45.0, "tpu v3": 123.0, "tpu v4": 275.0,
    "tpu v5 lite": 197.0, "tpu v5e": 197.0,
    "tpu v5": 459.0, "tpu v5p": 459.0,
    "cpu": 0.1,
}

_peak_cache: Dict[str, float] = {}


def peak_flops() -> float:
    """Peak FLOP/s of one local device — ``Config.peak_tflops`` when set
    (``NNS_TPU_PEAK_TFLOPS``), else the device_kind table above.  Raises
    ``KeyError`` for a non-CPU device the table does not list."""
    from ..core.config import get_config

    cfg = get_config()
    if cfg.peak_tflops > 0:
        return cfg.peak_tflops * 1e12
    got = _peak_cache.get("flops")
    if got is not None:
        return got
    import jax

    dev = jax.devices()[0]
    kind = "cpu" if dev.platform == "cpu" else str(dev.device_kind).lower()
    if kind not in _PEAK_TFLOPS_BY_KIND:
        raise KeyError(
            f"no peak FLOP/s for device_kind {dev.device_kind!r}: add it to "
            "utils/xray._PEAK_TFLOPS_BY_KIND with its source, or set "
            "NNS_TPU_PEAK_TFLOPS")
    val = _peak_cache["flops"] = _PEAK_TFLOPS_BY_KIND[kind] * 1e12
    return val


def peak_bw() -> float:
    """Peak HBM bandwidth (bytes/s) — the residency planner's calibrated
    :data:`~nnstreamer_tpu.pipeline.residency.HBM_GBPS` roofline constant,
    so static fetch pricing and live roofline attribution use one number."""
    from ..pipeline.residency import HBM_GBPS

    return HBM_GBPS * 1e9


# ---------------------------------------------------------------------------
# abstract signatures
# ---------------------------------------------------------------------------

def abstract_signature(args, kwargs) -> Tuple:
    """The call's abstract signature: one descriptor per pytree leaf —
    ``("t", shape, dtype, weak)`` for array-likes, ``("py", typename)``
    for raw python scalars (which jit weak-types: the classic
    numpy-scalar-vs-python-int census trap is exactly this difference)."""
    import jax

    sig = []
    for x in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append(("t", tuple(int(d) for d in shape), str(dtype),
                        bool(getattr(x, "weak_type", False))))
        else:
            sig.append(("py", type(x).__name__))
    return tuple(sig)


def render_leaf(leaf: Tuple) -> str:
    if leaf[0] == "py":
        return f"py:{leaf[1]}"
    _, shape, dtype, weak = leaf
    return f"{list(shape)}{dtype}" + ("~weak" if weak else "")


def render_signature(sig: Tuple) -> str:
    return ", ".join(render_leaf(leaf) for leaf in sig)


def _sig_tensors(sig: Tuple):
    """TensorsSpec view of an all-array signature (None when any leaf is
    a raw python scalar — those have no spec representation)."""
    from ..core.types import TensorSpec, TensorsSpec

    specs = []
    for leaf in sig:
        if leaf[0] != "t":
            return None
        _, shape, dtype, _ = leaf
        try:
            specs.append(TensorSpec.from_shape(tuple(shape) or (1,), dtype))
        except Exception:  # noqa: BLE001 - exotic dtypes fall back
            return None
    return TensorsSpec(tuple(specs))


def explain_signature_drift(actual: Tuple, predicted: Optional[Tuple]) -> str:
    """Field-level diff between a drifted abstract signature and the
    stage's predicted/baseline one — :func:`explain_mismatch` for the
    shape/dtype part, leaf-by-leaf for what caps cannot express (weak
    typing, raw python scalars, arity)."""
    if predicted is None:
        return "no predicted signature to diff against"
    if len(actual) != len(predicted):
        return (f"arity {len(actual)} ⊄ predicted {len(predicted)} "
                f"([{render_signature(actual)}] vs "
                f"[{render_signature(predicted)}])")
    a_spec, p_spec = _sig_tensors(actual), _sig_tensors(predicted)
    if a_spec is not None and p_spec is not None \
            and not a_spec.is_compatible(p_spec):
        from ..core.caps import Caps, explain_mismatch

        return explain_mismatch(Caps.tensors(a_spec), Caps.tensors(p_spec))
    for i, (la, lp) in enumerate(zip(actual, predicted)):
        if la != lp:
            return (f"arg {i}: {render_leaf(la)} ⊄ predicted "
                    f"{render_leaf(lp)}")
    return "same abstract signature recompiled"


def _cache_size(fn) -> int:
    try:
        return int(fn._cache_size())
    except Exception:  # noqa: BLE001 - non-jit callables have no cache
        return -1


def _cost_of(fn, args, kwargs) -> Tuple[float, float]:
    """(flops, bytes accessed) for one signature from the lowered
    program's cost analysis — ``lower()`` TRACES (no backend compile, no
    dispatch, and jit's own cache is untouched, so zero-recompile pins
    keep holding).  Best-effort: attribution must never take a pipeline
    down."""
    try:
        ca = fn.lower(*args, **kwargs).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return (float(ca.get("flops", 0.0) or 0.0),
                float(ca.get("bytes accessed", 0.0) or 0.0))
    except Exception:  # noqa: BLE001
        return 0.0, 0.0


# ---------------------------------------------------------------------------
# tracked programs
# ---------------------------------------------------------------------------

class TrackedProgram:
    """A jitted callable with its compiles registered and its dispatches
    attributed.  Cache growth (``jit._cache_size``) is the compile
    detector — it catches signatures the call site never meant to mint
    (the numpy-scalar ``_set_tok`` trap) exactly where a
    wrap-at-build-time scheme would miss them.

    ``rows`` pins the trigger batch dim (bucket programs whose stacking
    happens inside the program); ``rows_from_leading`` derives it from
    the first array leaf (sharded programs, stacked on host).  ``rec``
    may be a FlightRecorder or a zero-arg callable resolving to one (the
    llm serve loop's recorder attaches after construction)."""

    def __init__(self, fn: Callable, reg: "ProgramRegistry", stage: str,
                 kind: str, rec=None, rows: Optional[int] = None,
                 rows_from_leading: bool = False, devices: int = 1):
        self._fn = fn
        self._reg = reg
        self.stage = stage
        self.kind = kind
        self._rec = rec
        self._rows = rows
        self._rows_leading = rows_from_leading
        #: chips this program executes across (a sharded/TP program's
        #: cost analysis covers the GLOBAL work — MFU/roofline divide
        #: the aggregate peak, not one chip's)
        self.devices = max(1, int(devices))
        self._known = _cache_size(fn)
        #: latest compiled signature's cost (per dispatch)
        self.flops = 0.0
        self.bytes_ = 0.0
        #: post-warmup dispatch stats (compile calls excluded: their wall
        #: time is compile, not device work)
        self.disp_ns = 0
        self.disp_n = 0

    def __getattr__(self, name):
        # drop-in transparency: cache-size pins, .lower() cost probes,
        # and anything else callers read off a jitted fn pass through
        # (__dict__ access keeps a half-built instance from recursing)
        fn = self.__dict__.get("_fn")
        if fn is None:
            raise AttributeError(name)
        return getattr(fn, name)

    def __call__(self, *args, **kwargs):
        fn = self._fn
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        n = _cache_size(fn)
        if n != self._known:
            self._known = n
            sig = abstract_signature(args, kwargs)
            flops, bts = _cost_of(fn, args, kwargs)
            if flops:
                self.flops = flops
            if bts:
                self.bytes_ = bts
            rows = self._rows
            if rows is None and self._rows_leading:
                rows = next((leaf[1][0] for leaf in sig
                             if leaf[0] == "t" and leaf[1]), None)
            self._reg.register(self.stage, self.kind, sig,
                               compile_s=dt, flops=flops, bytes_=bts,
                               rows=rows)
        else:
            self.disp_ns += int(dt * 1e9)
            self.disp_n += 1
            rec = self._rec() if callable(self._rec) else self._rec
            if rec is not None and rec.active:
                # the DEVICE track: one span per dispatch on its own
                # `device:<stage>` Perfetto thread, beside the host spans
                dur = int(dt * 1e9)
                rec.record("device", f"device:{self.stage}", None,
                           time.monotonic_ns() - dur, dur,
                           program=self.kind, flops=self.flops)
        return out


class ProgramRegistry:
    """Process-wide live compiled-program census (one per process, like
    ``core.log.metrics``).  ``expect()`` installs the deep lint's
    predicted budget per ``(stage, kind)``; ``track()`` wraps a jitted
    fn; ``register()`` records one compile and fires ``census-drift``
    when the live set escapes the prediction."""

    #: nns-tsan lock discipline (lint --threads verifies statically,
    #: NNS_TPU_TSAN=1 verifies live — docs/ANALYSIS.md "Threads pass")
    _GUARDED_BY = {"_expected": "_lock", "_live": "_lock",
                   "_trackers": "_lock", "_drifts": "_lock",
                   "_drift_dumped": "_lock"}

    def __init__(self):
        self._lock = locks.make_lock("ProgramRegistry._lock")
        #: (stage, kind) -> (budget, allow-set or None, note)
        self._expected: Dict[Tuple[str, str],
                             Tuple[int, Optional[FrozenSet[int]], str]] = {}
        #: (stage, kind) -> {"compiles": int, "sigs": {sig: info}}
        self._live: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._trackers: List[TrackedProgram] = []
        self._drifts: List[Dict[str, Any]] = []
        #: (stage, kind) keys whose first drift already warned + dumped
        self._drift_dumped: set = set()

    # -- install -----------------------------------------------------------
    def expect(self, stage: str, kind: str, budget: int = 0,
               allow=None, note: str = "") -> None:
        """Declare the predicted census for ``(stage, kind)``: at most
        ``budget`` compiled programs (0 = unbounded, mirroring the deep
        lint's ``recompile-unbounded`` verdict), optionally constrained
        to trigger batch dims in ``allow`` (the bucket ladder).

        Installing an expectation RESETS the key's live compile count:
        the registry is process-wide (like ``core.log.metrics``), and a
        second pipeline re-using a stage name must be measured against
        its own warmup, not a predecessor's accumulated census.  Within
        one pipeline's lifetime the count only grows — a mid-run
        ``reload_model`` recompile counts toward the budget by design
        (the deep lint does not model reloads; the drift IS the
        signal).  The corollary of the shared registry (exactly the
        metrics registry's semantics): two CONCURRENT pipelines whose
        stages share auto-generated names share census keys too — give
        elements distinct ``name=`` props when running xray pipelines
        side by side, or the later start() re-bases the earlier one's
        counts."""
        with self._lock:
            self._expected[(stage, kind)] = (
                int(budget), frozenset(allow) if allow else None, note)
            self._live.pop((stage, kind), None)
            # a fresh expectation also retires the key's PAST drift
            # verdicts (and re-arms its warn+dump): a new pipeline's
            # explain()/doctor must not inherit a stopped predecessor's
            # findings (the reconciler's gauge twin corrects on its
            # next tick)
            self._drifts = [d for d in self._drifts
                            if (d["stage"], d["kind"]) != (stage, kind)]
            self._drift_dumped.discard((stage, kind))

    def track(self, fn: Callable, stage: str, kind: str, rec=None,
              rows: Optional[int] = None,
              rows_from_leading: bool = False,
              devices: int = 1) -> Callable:
        """Wrap a jitted fn so its compiles register here.  Idempotent —
        re-wrapping a tracked program returns it unchanged (reload paths
        re-run their build hooks).  The registry holds trackers WEAKLY:
        a stopped pipeline's programs (and the params their closures
        capture) release normally; dead refs are pruned at the next
        stats read."""
        if isinstance(fn, TrackedProgram):
            return fn
        tp = TrackedProgram(fn, self, stage, kind, rec=rec, rows=rows,
                            rows_from_leading=rows_from_leading,
                            devices=devices)
        import weakref

        with self._lock:
            self._trackers.append(weakref.ref(tp))
        return tp

    # -- the census --------------------------------------------------------
    def register(self, stage: str, kind: str, sig: Tuple, *,
                 compile_s: float = 0.0, flops: float = 0.0,
                 bytes_: float = 0.0, rows: Optional[int] = None) -> None:
        """Record one compile.  Fires ``census-drift`` when the live
        program set escapes the installed expectation — count past the
        budget, or a trigger batch dim outside the predicted ladder."""
        key = (stage, kind)
        with self._lock:
            ent = self._live.setdefault(key, {"compiles": 0, "sigs": {}})
            ent["compiles"] += 1
            compiles = ent["compiles"]
            baseline = next(iter(ent["sigs"]), None)
            if sig not in ent["sigs"]:
                ent["sigs"][sig] = {
                    "compile_s": compile_s, "flops": flops,
                    "bytes": bytes_, "rows": rows,
                    "ts": time.monotonic(),
                }
            exp = self._expected.get(key)
        metrics.count(f"{stage}.compiles")
        if exp is None:
            return
        budget, allow, _note = exp
        reason = None
        if allow is not None and rows is not None and rows not in allow:
            reason = (f"trigger batch dim {rows} is not in the predicted "
                      f"bucket ladder {sorted(allow)}")
        elif budget and compiles > budget:
            reason = (f"{compiles} compiled program(s) exceed the "
                      f"predicted census of {budget}")
        if reason is not None:
            self._fire_drift(stage, kind, sig, baseline, reason)

    #: recorded drift records are bounded: a recompile STORM (the exact
    #: pathology the census catches) must not grow the process-wide
    #: singleton without limit — past the cap only the counter advances
    MAX_DRIFT_RECORDS = 512

    def _fire_drift(self, stage: str, kind: str, sig: Tuple,
                    baseline: Optional[Tuple], reason: str) -> None:
        diff = explain_signature_drift(sig, baseline)
        drift = {
            "stage": stage, "kind": kind, "reason": reason,
            "signature": render_signature(sig),
            "predicted_signature": (render_signature(baseline)
                                    if baseline is not None else None),
            "diff": diff,
        }
        with self._lock:
            if len(self._drifts) < self.MAX_DRIFT_RECORDS:
                self._drifts.append(drift)
            # warn + ring dump ONCE per key (the watchdog discipline): a
            # storm minting hundreds of programs must not pay a full
            # flight-recorder dump per compile inside the dispatch path
            first = (stage, kind) not in self._drift_dumped
            self._drift_dumped.add((stage, kind))
        # counter, named DISTINCTLY from the reconciler's
        # `xray.census_drift` gauge twin: one raw name rendered as both
        # families would flip type between scrapes once publish() runs
        metrics.count("xray.census_drifts")
        from . import tracing

        if tracing.recorder.active:
            tracing.recorder.record("xray.drift", stage, None,
                                    time.monotonic_ns(), 0,
                                    program=kind, reason=reason)
        if not first:
            log.debug("census-drift (repeat): %s/%s: %s", stage, kind,
                      reason)
            return
        log.warning(
            "census-drift: stage %s (%s): %s — signature [%s]; diff vs "
            "predicted: %s", stage, kind, reason,
            drift["signature"], diff)
        # the post-mortem window rides the FIRST drift per key, like
        # watchdog fires
        tracing.dump_recent_to_log(
            log, reason=f"census-drift at {stage}/{kind}: {reason}")

    # -- accessors ---------------------------------------------------------
    def has_compiles(self) -> bool:
        """True once any tracked program compiled — the 'pipeline has
        actually done device work' signal the ledger's under-prediction
        warn gates on (an idle pipeline's unallocated pool is not
        drift)."""
        with self._lock:
            return bool(self._live)

    def drifts(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(d) for d in self._drifts]

    def drift_count(self) -> int:
        with self._lock:
            return len(self._drifts)

    def census(self) -> Dict[str, Dict[str, Any]]:
        """Predicted-vs-live join, keyed ``"<stage>/<kind>"``: the doctor
        report's census table."""
        with self._lock:
            expected = dict(self._expected)
            live = {k: (v["compiles"],
                        [render_signature(s) for s in v["sigs"]])
                    for k, v in self._live.items()}
        out: Dict[str, Dict[str, Any]] = {}
        for key in sorted(set(expected) | set(live)):
            stage, kind = key
            budget, allow, note = expected.get(key, (0, None, ""))
            compiles, sigs = live.get(key, (0, []))
            out[f"{stage}/{kind}"] = {
                "stage": stage, "kind": kind,
                "predicted": budget or None,
                "allow": sorted(allow) if allow else None,
                "live_compiles": compiles,
                "live_signatures": sigs,
                "within": (not budget) or compiles <= budget,
                "note": note,
            }
        return out

    def stage_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-stage device-time attribution aggregated over trackers:
        dispatch count, summed wall time, FLOPs/bytes throughput, and
        the derived ``mfu`` / ``roofline_fraction``."""
        with self._lock:
            # prune dead weakrefs (stopped pipelines' programs)
            self._trackers = [r for r in self._trackers
                              if r() is not None]
            trackers = [r() for r in self._trackers]
        pk, bw = peak_flops(), peak_bw()
        agg: Dict[str, Dict[str, float]] = {}
        for tp in trackers:
            if tp is None or tp.disp_n == 0:
                continue
            st = agg.setdefault(tp.stage, {
                "dispatches": 0.0, "device_ns": 0.0,
                "flops_total": 0.0, "bytes_total": 0.0,
                "peak_flop_time": 0.0, "ideal_s": 0.0})
            secs = tp.disp_ns / 1e9
            dev = max(1, tp.devices)
            st["dispatches"] += tp.disp_n
            st["device_ns"] += tp.disp_ns
            st["flops_total"] += tp.flops * tp.disp_n
            st["bytes_total"] += tp.bytes_ * tp.disp_n
            # a sharded/TP program's cost analysis covers the GLOBAL
            # work spread over `devices` chips: utilization denominates
            # in the AGGREGATE peak available during the measured time,
            # and the ideal (roofline) time divides both rooflines by
            # the participating chip count
            st["peak_flop_time"] += pk * dev * secs
            st["ideal_s"] += max(
                tp.flops / (pk * dev) if pk else 0.0,
                tp.bytes_ / (bw * dev) if bw else 0.0) * tp.disp_n
        for st in agg.values():
            secs = st["device_ns"] / 1e9
            if secs <= 0:
                st["mfu"] = st["roofline_fraction"] = 0.0
                continue
            st["mfu"] = (st["flops_total"] / st["peak_flop_time"]
                         if st["peak_flop_time"] else 0.0)
            st["roofline_fraction"] = min(1.0, st["ideal_s"] / secs)
        return agg

    def publish(self) -> None:
        """One reconciler tick's gauge export: per-stage ``mfu`` /
        ``roofline_fraction`` plus the census-drift total."""
        for stage, st in self.stage_stats().items():
            metrics.gauge(f"{stage}.mfu", st["mfu"])
            metrics.gauge(f"{stage}.roofline_fraction",
                          st["roofline_fraction"])
        metrics.gauge("xray.census_drift", float(self.drift_count()))

    def reset(self) -> None:
        with self._lock:
            self._expected.clear()
            self._live.clear()
            self._trackers.clear()
            self._drifts.clear()
            self._drift_dumped.clear()


#: THE process-wide registry (``Pipeline(xray=True)`` hands it to every
#: instrumentation site as ``element._xray``; off pipelines hold None)
registry = ProgramRegistry()


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------

def measure_hbm(pipeline) -> Dict[str, int]:
    """Model-side live accounting per category, plus raw device
    ``memory_stats()`` where the backend provides them (TPU; CPU/PJRT
    hosts return nothing).  Bytes are process-global — under a >1
    ``model`` axis divide params/pool by M to compare per chip."""
    out: Dict[str, int] = {c: 0 for c in HBM_CATEGORIES}
    for el in {id(e): e for e in pipeline.elements.values()}.values():
        # a stopped (or never-started) tensor_filter holds fw=None —
        # param_bytes() would lazily RELOAD the framework (multi-GiB
        # checkpoints, never close()d again) just to read a byte count
        if not (hasattr(el, "fw") and el.fw is None):
            try:
                out["params"] += int(el.param_bytes() or 0)
            except Exception:  # noqa: BLE001 - accounting probe only
                pass
        fw = getattr(el, "fw", None)
        loop = getattr(fw, "_serve", None) if fw is not None else None
        if loop is not None:
            out["kv_pool"] += int(getattr(loop, "_pool_nbytes", 0) or 0)
            # sampler per-slot PRNG key state (temperature > 0 loops;
            # 0 for greedy — serving_plan's prng_state_bytes twin)
            out["prng_state"] += int(
                getattr(loop, "_prng_nbytes", 0) or 0)
        ring = getattr(el, "_ring", None)
        if ring is not None and hasattr(ring, "nbytes"):
            out["agg_rings"] += int(ring.nbytes)
        train_fn = getattr(el, "train_state_bytes", None)
        if train_fn is not None:
            try:
                out["train_state"] += int(train_fn() or 0)
            except Exception:  # noqa: BLE001 - accounting probe only
                pass
    act = 0
    for r in {id(r): r for r in pipeline._runners.values()}.values():
        try:
            # lock-free snapshot of a deque the stage thread mutates:
            # CPython raises RuntimeError if an append lands mid-copy —
            # skip the sample rather than take a lock onto the hot path
            items = list(r._inflight)
        except RuntimeError:
            continue
        for item in items:
            for _pad, o in item[0]:
                tensors = getattr(o, "tensors", None)
                if tensors:
                    act += sum(int(getattr(t, "nbytes", 0) or 0)
                               for t in tensors)
    out["activations"] = act
    try:
        import jax

        stats = [d.memory_stats() for d in jax.local_devices()]
        in_use = sum(int((s or {}).get("bytes_in_use", 0)) for s in stats)
        if in_use:
            out["device_bytes_in_use"] = in_use
    except Exception:  # noqa: BLE001 - stats are a bonus, not a contract
        pass
    return out


def predicted_hbm(pipeline) -> Optional[Dict[str, int]]:
    """The deep lint's per-category estimate for this pipeline's own
    knobs (cached on the pipeline; None when the deep pass cannot run —
    e.g. an unparsable graph mid-refactor)."""
    rep = getattr(pipeline, "_xray_deep", False)
    if rep is False:
        rep = None
        try:
            from ..analysis import analyze

            got = analyze(pipeline.graph, deep=True,
                          batch_max=pipeline.batch_max,
                          batch_buckets=pipeline.batch_buckets,
                          adaptive_buckets=pipeline.adaptive_buckets,
                          data_parallel=pipeline.data_parallel,
                          model_parallel=pipeline.model_parallel,
                          dispatch_depth=pipeline.dispatch_depth)
            rep = getattr(got, "resources", None)
        except Exception:  # noqa: BLE001 - prediction is best-effort
            log.exception("xray: deep-lint prediction failed")
        pipeline._xray_deep = rep
    if rep is None:
        return None
    return rep.by_category()


class XrayReconciler:
    """The continuous predicted-vs-actual loop (0.5 s daemon, the SLO
    engine's cadence): publishes per-stage MFU/roofline gauges, the HBM
    ledger (measured + predicted + ratio per category), and warns ONCE
    per category when the ratio escapes ``Config.xray_hbm_tolerance``.
    ``Pipeline.stop()`` stops AND joins it — the thread-shutdown audit
    counts it like the sampler and the SLO engine."""

    def __init__(self, pipeline, period_s: float = 0.5):
        self.pipeline = pipeline
        self.period_s = period_s
        self._stop = threading.Event()
        self._warned: set = set()
        self._act_peak = 0
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "XrayReconciler":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="nns-xray", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - must never die loud
                log.exception("xray reconciler tick failed")

    def tick(self) -> None:
        registry.publish()
        measured = measure_hbm(self.pipeline)
        # the window is transient: reconcile its PEAK against the
        # high-water estimate, not whatever instant the tick landed on
        self._act_peak = max(self._act_peak, measured["activations"])
        measured["activations"] = self._act_peak
        predicted = predicted_hbm(self.pipeline)
        from ..core.config import get_config

        tol = float(get_config().xray_hbm_tolerance)
        for cat in HBM_CATEGORIES:
            m = measured.get(cat, 0)
            metrics.gauge(f"xray.hbm.{cat}", float(m))
            p = (predicted or {}).get(cat, 0)
            if not predicted or not p:
                continue
            metrics.gauge(f"xray.hbm_predicted.{cat}", float(p))
            ratio = m / p
            metrics.gauge(f"xray.hbm_drift.{cat}", ratio)
            if cat in self._warned:
                continue
            # either direction, each gated on ITS side's noise floor: an
            # over-use warns when the measurement is real, an
            # over-PREDICTION warns when the estimate was (a dead probe
            # measuring 0 against a 500 MiB estimate is exactly the
            # drift the ledger exists to surface) — but only once the
            # pipeline has compiled something, so an idle serve loop's
            # not-yet-allocated pool is not flagged before first traffic
            if (m > p * tol and m > HBM_WARN_FLOOR) or \
                    (p > m * tol and p > HBM_WARN_FLOOR
                     and registry.has_compiles()):
                self._warned.add(cat)
                log.warning(
                    "hbm-drift: category %s measured %.1f MiB vs deep-lint "
                    "estimate %.1f MiB (%.2fx, tolerance %gx) — the static "
                    "budget no longer describes this pipeline; re-check "
                    "the lint's resource report (docs/ANALYSIS.md)",
                    cat, m / 2**20, p / 2**20, ratio, tol)


# ---------------------------------------------------------------------------
# the doctor report
# ---------------------------------------------------------------------------

def explain(pipeline) -> Dict[str, Any]:
    """One predicted-vs-actual report for a (running or finished)
    pipeline: plan + mesh, residency, census (predicted budgets vs live
    program set + drifts), HBM ledger per category, per-stage device-time
    attribution, and the SLO verdict when an engine is attached.  JSON-
    serializable — the doctor CLI's machine-readable twin."""
    from ..core.config import get_config

    plan = {
        "stages": [{
            "stage": s.element.name,
            "elements": [pipeline.graph.nodes[n].kind for n in s.node_ids],
            "batchable": s.batchable, "shardable": s.shardable,
            "restartable": s.restartable,
        } for s in pipeline.stages],
        "batch_max": pipeline.batch_max,
        "dispatch_depth": pipeline.dispatch_depth,
        "fetch_depth": pipeline.fetch_depth,
        "adaptive_buckets": pipeline.adaptive_buckets,
    }
    mesh = {"data": pipeline.mesh_shape[0], "model": pipeline.mesh_shape[1]}
    res = pipeline.residency
    residency = {
        "resident_edges": res.resident_edges,
        "reduced_outputs": list(res.reduced_outputs),
        "fetch": [{"sink": e.sink, "producer": e.producer,
                   "bytes_per_buffer": e.bytes_per_buffer,
                   "reduced": e.reduced} for e in res.fetch],
    }
    census = {
        "programs": registry.census(),
        "drift": registry.drifts(),
        "drift_total": registry.drift_count(),
    }
    tol = float(get_config().xray_hbm_tolerance)
    measured = measure_hbm(pipeline)
    recon = getattr(pipeline, "_xray_recon", None)
    if recon is not None:
        measured["activations"] = max(measured["activations"],
                                      recon._act_peak)
    predicted = predicted_hbm(pipeline)
    hbm: Dict[str, Any] = {"tolerance": tol, "categories": {}}
    for cat in HBM_CATEGORIES:
        m = measured.get(cat, 0)
        p = (predicted or {}).get(cat) if predicted else None
        hbm["categories"][cat] = {
            "predicted": p, "measured": m,
            "ratio": (m / p) if p else None,
            # over-use is the failure the budget exists to catch;
            # under-use (a transient window that never filled) is fine,
            # and byte-level noise below the reconciler's warn floor
            # never fails a gate (a 0-byte estimate vs a few live KiB)
            "ok": (p is None) or m <= max(p * tol, HBM_WARN_FLOOR),
        }
    if "device_bytes_in_use" in measured:
        hbm["device_bytes_in_use"] = measured["device_bytes_in_use"]
    slo = None
    if pipeline._slo_policy is not None:
        try:
            slo = pipeline.slo_report()
        except Exception:  # noqa: BLE001 - verdict is best-effort here
            pass
    ok = (census["drift_total"] == 0
          and all(c["ok"] for c in hbm["categories"].values()))
    return {
        "xray": pipeline.xray,
        "plan": plan, "mesh": mesh, "residency": residency,
        "census": census, "hbm": hbm,
        "device_time": registry.stage_stats(),
        "slo": slo, "ok": ok,
    }


def _mib(n) -> str:
    return "-" if n is None else f"{n / 2**20:.2f} MiB"


def render_report(rep: Dict[str, Any]) -> str:
    """Human rendering of :func:`explain` — the predicted-vs-actual
    columns the doctor CLI prints."""
    lines = [
        "pipeline doctor — predicted vs actual",
        f"  plan: {len(rep['plan']['stages'])} stage(s), "
        f"batch_max={rep['plan']['batch_max']}, "
        f"dispatch_depth={rep['plan']['dispatch_depth']}, "
        f"mesh (data={rep['mesh']['data']}, model={rep['mesh']['model']})",
        f"  residency: {rep['residency']['resident_edges']} device-"
        f"resident edge(s), {len(rep['residency']['fetch'])} fetch "
        "edge(s)",
        "  census (compiled programs, predicted vs live):",
    ]
    progs = rep["census"]["programs"]
    if not progs:
        lines.append("    (no tracked programs — xray off or nothing "
                     "compiled)")
    for key in sorted(progs):
        e = progs[key]
        pred = e["predicted"] if e["predicted"] else "unbounded"
        mark = "OK" if e["within"] else "DRIFT"
        lines.append(f"    {key}: predicted {pred}, live "
                     f"{e['live_compiles']} [{mark}]")
    for d in rep["census"]["drift"]:
        lines.append(f"    drift: {d['stage']}/{d['kind']}: {d['reason']}"
                     f" — {d['diff']}")
    lines.append(f"  hbm ledger (tolerance {rep['hbm']['tolerance']:g}x):")
    for cat, c in rep["hbm"]["categories"].items():
        ratio = "-" if c["ratio"] is None else f"{c['ratio']:.2f}x"
        mark = "OK" if c["ok"] else "DRIFT"
        lines.append(f"    {cat}: predicted {_mib(c['predicted'])}, "
                     f"measured {_mib(c['measured'])} ({ratio}) [{mark}]")
    if "device_bytes_in_use" in rep["hbm"]:
        lines.append(f"    device bytes_in_use: "
                     f"{_mib(rep['hbm']['device_bytes_in_use'])}")
    if rep["device_time"]:
        lines.append("  device time (measured dispatch attribution):")
        for stage in sorted(rep["device_time"]):
            st = rep["device_time"][stage]
            lines.append(
                f"    {stage}: {int(st['dispatches'])} dispatch(es), "
                f"{st['device_ns'] / 1e6:.1f} ms, mfu {st['mfu']:.4f}, "
                f"roofline {st['roofline_fraction']:.4f}")
    if rep["slo"] is not None:
        ok = rep["slo"].get("ok")
        lines.append(f"  slo: {'green' if ok else 'BREACHING'} "
                     f"(breaches: {rep['slo'].get('breaches')})")
    lines.append(f"  verdict: {'OK' if rep['ok'] else 'DRIFT'} "
                 f"(census drift {rep['census']['drift_total']})")
    return "\n".join(lines)


def verdict_lines(rep: Dict[str, Any]) -> List[str]:
    """The timing-insensitive verdict subset the CI gate pins against
    ``tools/xray_baseline.txt``: expectation keys + per-category HBM
    verdicts + the drift total — deterministic for a fixed pipeline,
    regardless of which bucket programs a given run's occupancies
    happened to compile."""
    lines = [f"census drift {rep['census']['drift_total']}"]
    for key in sorted(rep["census"]["programs"]):
        e = rep["census"]["programs"][key]
        if e["predicted"]:
            lines.append(
                f"{key}: {'within budget' if e['within'] else 'OVER'}")
    for cat in HBM_CATEGORIES:
        c = rep["hbm"]["categories"][cat]
        lines.append(f"hbm {cat}: {'ok' if c['ok'] else 'DRIFT'}")
    lines.append(f"doctor: {'OK' if rep['ok'] else 'DRIFT'}")
    return lines
