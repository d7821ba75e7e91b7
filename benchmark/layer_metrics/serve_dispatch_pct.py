"""The host time of the dispatch that ENDS each host gap, as a share of
the part of the window the ring covers: the chip has nothing from the
gap's start until that jitted call has returned, but
``serve_host_gap_pct`` stops where the call begins.  For a gap that a
prefill chunk ends it is the ``serve.prefill_chunk`` span's duration
(the span times the async dispatch); for one that a decode chunk or a
speculative round ends, the ``dispatch_ns`` its ``serve.decode``/
``serve.spec_verify`` span carries since PR 38 (``t_dec`` → the call's
return).  It lies AFTER the gap, so

    ``serve_host_gap_pct + serve_dispatch_pct``

is the program's own reckoning of ``device_idle.serve`` — an UPPER
estimate of the part it names: a program starts on the chip before the
call that queued it has returned, so the sum overstates that idle by
about 0.3 of a point in ``decode_c32`` (a shared-clock capture read
``serve.prefill_chunk`` at 9.6 % of the idle time where the whole calls
would be 12–18 %: PERF.md §5, PR 38).  What the device trace reads
beyond it is the gaps between programs inside an iteration.

A program whose decode spans carry no ``dispatch_ns`` gives nothing to
read (half a sum would read as a gain)."""

from benchmark.ring_spans import (DISPATCHES, ITERATION_ENDS, clipped_ns,
                                  covered_window, host_gaps)


def dispatch_intervals(spans):
    """``(start, start + host time)`` of the dispatch that ends each host
    gap; ``None`` where a decode that ends one says no ``dispatch_ns``."""
    host_ns = {}
    for kind, ts, dur, a in spans:
        if kind in ITERATION_ENDS:
            host_ns[ts] = a.get("dispatch_ns")
        elif kind in DISPATCHES:
            host_ns[ts] = dur
    out = []
    for _end, start in host_gaps(spans):
        if host_ns.get(start) is None:
            return None
        out.append((start, start + host_ns[start]))
    return out


def read(obs):
    w = covered_window(obs)
    iv = dispatch_intervals(obs.get("spans", []))
    if not w or not iv:
        return None
    return 100.0 * clipped_ns(iv, *w) / (w[1] - w[0])
