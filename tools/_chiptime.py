"""Wall-clock timing of jitted programs that syncs by fetching bytes.

A D2H fetch of an output synchronizes with the device on every backend,
so that is how this helper ends a timed region (whether
``block_until_ready`` alone suffices is a property of the machine —
chip_smoke.py's ``machine`` line measures it).

Strategy: dispatch N calls back-to-back (PJRT executes in launch order
on the device stream), fetch a FEW BYTES of the last call's output once,
and subtract the separately measured fetch RTT.  One roundtrip per
measurement, not per call.
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np


_RTT_S: float | None = None


def fetch_rtt_s(force: bool = False) -> float:
    """Median RTT of a tiny D2H fetch (the per-measurement constant to
    subtract)."""
    global _RTT_S
    if _RTT_S is not None and not force:
        return _RTT_S
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: x + 1)
    out = tiny(jnp.zeros((4,), jnp.int32))
    np.asarray(out)  # warm the program + path
    samples = []
    for _ in range(5):
        out = tiny(out)
        t0 = time.perf_counter()
        np.asarray(out)
        samples.append(time.perf_counter() - t0)
    _RTT_S = float(np.median(samples))
    return _RTT_S


def chip_time_ms(fn: Callable, *args, iters: int = 8,
                 fetch: Callable | None = None) -> float:
    """Average per-call device ms of ``fn(*args)``.

    ``fetch(out)`` must map the call's output to a SMALL array whose
    value depends on the full computation (default: the output itself —
    only safe for small outputs).  The fetched array is pulled once for
    the whole batch of calls.
    """
    fetch = fetch or (lambda o: o)
    rtt = fetch_rtt_s()
    np.asarray(fetch(fn(*args)))  # compile + warm + sync
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    np.asarray(fetch(out))
    total = time.perf_counter() - t0
    return max(0.0, (total - rtt)) / iters * 1e3
