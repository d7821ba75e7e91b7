#!/usr/bin/env python
"""nns-tsan off-mode overhead sentinel (ISSUE 17).

With ``NNS_TPU_TSAN`` unset the lock factories in
``nnstreamer_tpu.utils.locks`` return PLAIN ``threading`` primitives and
``assert_guarded`` early-outs on the module ``_active`` flag, so the
sanitizer's entire off-mode cost reduces to that one flag check per
guarded-field hook site.  Like tools/tracing_gate.py (whose off-mode
methodology this copies), the ≤2% bound is checked deterministically —
measured early-out cost (ns, microbenched) × a conservative hook-site
count per buffer, against the measured per-buffer service time of a
backlogged batching pipeline — because wall-clock A/B of identical
phases on this shared host disagrees by more than the bound itself.

Two pins, both required for a passing row:

1. **structural**: the factories hand back ``threading.Lock`` (not
   ``TrackedLock``), and the process-wide order graph's hooks are
   monkeypatched to raise while the pipeline runs to completion —
   proving the off path never enters the sanitizer, rather than
   "sanitizing and discarding".
2. **arithmetic**: guard_ns × HOOKS_PER_BUFFER ≤ 2% of per-buffer
   service time.

Prints one ``{"metric": ...}`` JSON line; exits non-zero if either pin
fails.
"""

from __future__ import annotations

import json
import os
import sys
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

#: off-mode hook sites a buffer can cross end to end (assert_guarded
#: calls on the sink/queue hot paths plus every factory-made lock's
#: enter/exit, were they all guarded) — deliberately over-counted the
#: same way tracing_gate.HOOKS_PER_BUFFER is; the real number is ~1-3
HOOKS_PER_BUFFER = 16

BOUND_PCT = 2.0


def measure_guard_ns(iters: int = 200_000) -> float:
    """Cost of ONE off-mode hook: a real ``assert_guarded`` call that
    early-outs on ``_active`` being false.  Empty-loop baseline
    subtracted; floored so the ratio below can never divide by zero."""
    from nnstreamer_tpu.utils import locks

    assert not locks._active, "run this tool with NNS_TPU_TSAN unset"

    class _Obj:
        _GUARDED_BY = {"x": "_lock"}

    o = _Obj()
    ag = locks.assert_guarded
    t0 = time.perf_counter()
    for _ in range(iters):
        ag(o, "x")
    t1 = time.perf_counter()
    for _ in range(iters):
        pass
    t2 = time.perf_counter()
    return max(1e-3, ((t1 - t0) - (t2 - t1)) / iters * 1e9)


def _window(p) -> float:
    """One backlogged push+pull window (the tracing_gate phase shape)."""

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


def measure_service_us(reps: int = 3) -> float:
    """Best-of-``reps`` per-buffer service time (µs) of the backlogged
    phase, run with the structural pin armed: every order-graph hook
    raises, so completing at all proves the off path bypasses the
    sanitizer entirely."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.utils import locks

    def _bomb(*a, **k):  # pragma: no cover - reaching it IS the failure
        raise AssertionError("off-mode pipeline entered the sanitizer")

    saved = (locks.graph.before_acquire, locks.graph.acquired,
             locks.graph.released)
    locks.graph.before_acquire = _bomb
    locks.graph.acquired = _bomb
    locks.graph.released = _bomb
    try:
        p = nt.Pipeline(DESC, queue_capacity=64, batch_max=8)
        with p:
            for i in range(64):  # warm every bucket
                p.push("src", _FRAMES[i % len(_FRAMES)])
            for _ in range(64):
                p.pull("out", timeout=120)
            walls = [_window(p) for _ in range(reps)]
            p.eos()
            p.wait(timeout=60)
    finally:
        (locks.graph.before_acquire, locks.graph.acquired,
         locks.graph.released) = saved
    return min(walls) / N * 1e6


def main() -> int:
    os.environ.pop("NNS_TPU_TSAN", None)
    os.environ.pop("NNS_TPU_TSAN_RAISE", None)
    from nnstreamer_tpu.utils import locks

    structurally_off = (
        not locks.enabled()
        and type(locks.make_lock("overhead.probe")) is type(threading.Lock())
        and not isinstance(locks.make_rlock("overhead.rprobe"),
                           locks.TrackedRLock))
    guard_ns = measure_guard_ns()
    service_us = measure_service_us()
    pct = guard_ns * HOOKS_PER_BUFFER / (service_us * 1e3) * 100.0
    row = {
        "metric": "tsan_off_overhead_pct",
        "value": round(pct, 4),
        "unit": "%",
        "bound_pct": BOUND_PCT,
        "guard_ns": round(guard_ns, 2),
        "hooks_per_buffer": HOOKS_PER_BUFFER,
        "service_us_per_buffer": round(service_us, 2),
        "structurally_off": structurally_off,
    }
    print(json.dumps(row), flush=True)
    if not structurally_off:
        print("tsan_overhead: factories returned tracked primitives "
              "with NNS_TPU_TSAN unset", file=sys.stderr)
        return 1
    if pct > BOUND_PCT:
        print(f"tsan_overhead: {pct:.3f}% exceeds the {BOUND_PCT}% "
              "off-mode bound", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
