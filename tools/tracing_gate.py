#!/usr/bin/env python
"""Tracing gate (ISSUE 5 / docs/OBSERVABILITY.md), run by check_tier1.py:

1. **ring e2e**: a backlogged batching pipeline (a small model behind
   a deep queue) runs with ``trace_mode=ring``; the dumped
   Chrome JSON must schema-validate (monotonic ts), contain at least one
   batched dispatch span LINKING >1 member-row trace ids, and
   ``metrics_text()`` must expose bucketed histogram series (with
   ``# HELP``/``# TYPE``) for stage latency, queue wait, and end-to-end
   pipeline latency — the acceptance-criteria surface.

2. **off-mode instrumentation pin**: with ``trace_mode=off`` the recorder
   is STRUCTURALLY bypassed — ``FlightRecorder.record`` is monkeypatched
   to raise and the pipeline must still complete, proving the off path is
   the untraced code path (one pointer check per hook site), not "tracing
   that discards".

3. **off-mode overhead ≤ 2%**: because (2) pins that the ONLY off-mode
   cost is the per-hook ``is not None`` guard, the overhead is computed
   deterministically: measured guard cost (ns, microbenched) × a
   conservative hook-site count per buffer, against the measured
   per-buffer service time of the backlogged phase.  A direct wall-clock
   A/B of the same code was tried first and rejected: identical off-mode
   phases measured 3-20% apart on this shared host (thread scheduling +
   occupancy dynamics), i.e. the noise floor exceeds the bound being
   checked, so an A/B assert could only ever test the weather.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DIMS = 64
N = 512
DESC = (
    f"appsrc name=src caps=other/tensors,dimensions={DIMS},types=float32 ! "
    f"tensor_filter framework=jax model=scaler custom=scale:1.5,dims:{DIMS} "
    "name=f ! tensor_sink name=out"
)


_FRAMES = [np.full((DIMS,), float(i % 7), np.float32) for i in range(8)]


def _window(p) -> float:
    """One backlogged push+pull window (the bench_batching shape:
    concurrent pusher, puller measures)."""

    def pusher():
        for i in range(N):
            p.push("src", _FRAMES[i % len(_FRAMES)])

    t = threading.Thread(target=pusher, daemon=True)
    t0 = time.perf_counter()
    t.start()
    for _ in range(N):
        p.pull("out", timeout=120)
    wall = time.perf_counter() - t0
    t.join()
    return wall


def _warm(p) -> None:
    for i in range(64):  # compile every bucket
        p.push("src", _FRAMES[i % len(_FRAMES)])
    for _ in range(64):
        p.pull("out", timeout=120)


def run_phase(trace_mode: str, reps: int = 5, tenant=None) -> float:
    """Best-of-``reps`` wall of the backlogged phase in one pipeline."""
    import nnstreamer_tpu as nt

    p = nt.Pipeline(DESC, queue_capacity=64, batch_max=8,
                    trace_mode=trace_mode, tenant=tenant)
    with p:
        _warm(p)
        walls = [_window(p) for _ in range(reps)]
        p.eos()
        p.wait(timeout=60)
    return min(walls)


#: off-mode hook sites a buffer can cross per stage hop (feed stamp guard,
#: loop-top recorder check, inflight-emit guard, sink materialize getattr,
#: per-member batch guards, plus the nns-weave query send/recv/reply and
#: slot-timeline guards a distributed buffer crosses) — deliberately
#: over-counted; the real number is ~2-3 per hop
HOOKS_PER_BUFFER = 20


def measure_guard_ns(iters: int = 500_000) -> float:
    """Cost of ONE off-mode hook: the ``is not None`` pointer check every
    instrumentation site reduces to.  Empty-loop baseline subtracted."""
    tr = None
    t0 = time.perf_counter()
    for _ in range(iters):
        if tr is not None:
            raise RuntimeError  # pragma: no cover - tr is None
    t1 = time.perf_counter()
    for _ in range(iters):
        pass
    t2 = time.perf_counter()
    return max(1e-3, ((t1 - t0) - (t2 - t1)) / iters * 1e9)


def gate_ring() -> list:
    from nnstreamer_tpu.core.log import metrics
    from nnstreamer_tpu.utils.profiler import metrics_text
    from nnstreamer_tpu.utils.tracing import recorder, validate_chrome

    problems = []
    metrics.reset()
    recorder.clear()
    run_phase("ring", reps=1)
    path = os.path.join(tempfile.gettempdir(), "nns_tracing_gate.json")
    from nnstreamer_tpu.utils.tracing import dump_chrome

    dump_chrome(recorder.events(), path)
    with open(path) as f:
        obj = json.load(f)
    schema = validate_chrome(obj)
    if schema:
        problems += [f"chrome schema: {p}" for p in schema[:5]]
    linked = [e for e in obj["traceEvents"]
              if isinstance(e, dict)
              and len((e.get("args") or {}).get("trace_ids") or []) > 1]
    if not linked:
        problems.append("no batched dispatch span links >1 trace ids "
                        "(backlog did not coalesce, or linkage broke)")
    text = metrics_text()
    for series in ("nnstpu_f_proc_bucket{le=",
                   "nnstpu_f_queue_wait_bucket{le=",
                   "nnstpu_out_e2e_latency_bucket{le=",
                   "# TYPE nnstpu_f_proc histogram",
                   "# HELP nnstpu_f_queue_wait",
                   "# TYPE nnstpu_out_e2e_latency histogram"):
        if series not in text:
            problems.append(f"/metrics missing {series!r}")
    return problems


def gate_off_pin() -> list:
    from nnstreamer_tpu.utils.tracing import FlightRecorder, recorder

    recorder.configure("off")

    def boom(*a, **k):
        raise AssertionError("recorder.record ran with trace_mode=off")

    orig = FlightRecorder.record
    FlightRecorder.record = boom
    try:
        # tenant= set deliberately: tenant threading (ISSUE 8) must add
        # no stamps and touch no recorder on the off path
        run_phase("off", reps=1, tenant="gate")
    except Exception as e:  # noqa: BLE001 - report, don't crash the gate
        return [f"off-mode instrumentation pin: {e!r}"]
    finally:
        FlightRecorder.record = orig
    return []


def gate_off_overhead(limit: float = 0.02) -> list:
    """Deterministic off-mode overhead bound: hooks/buffer x guard cost
    vs per-buffer service time of the backlogged phase (see module
    docstring for why this beats a wall-clock A/B here)."""
    per_buffer_s = run_phase("off", reps=5) / N
    guard_ns = measure_guard_ns()
    pct = (HOOKS_PER_BUFFER * guard_ns * 1e-9) / per_buffer_s
    print(f"tracing gate: off-mode overhead {pct * 100:.4f}% "
          f"({HOOKS_PER_BUFFER} hooks x {guard_ns:.1f}ns guard vs "
          f"{per_buffer_s * 1e6:.1f}us/buffer; limit {limit * 100:.0f}%)")
    if pct > limit:
        return [f"off-mode overhead {pct * 100:.4f}% > {limit * 100:.0f}%"]
    return []


def main() -> int:
    problems = gate_ring() + gate_off_pin() + gate_off_overhead()
    if problems:
        for p in problems:
            print(f"tracing gate: {p}", file=sys.stderr)
        return 1
    print("tracing gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
