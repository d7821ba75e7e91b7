"""Arithmetic over the program's ring spans — the plain tuples
``(kind, start_ns, dur_ns, args)`` of ``adapter.ring_spans()`` — shared by
the serve-loop readers under ``layer_metrics/``.  Everything is cut on the
measured window ``obs["window_ns"]``, on the spans' own clock.

The ring is bounded (262,144 spans since PR 27; 65,536 before) and a
traced pipeline also records several spans for every token it delivers,
so by the time a 45 s window is read its first part may have been evicted
(PERF.md §6, PR 26: the ring of 65,536 held the last 63 iterations of
≈ 130; the ring of 262,144 holds a whole window up to ≈ 1,400 tokens/s).
A share is therefore taken of the part of the window the ring still
COVERS, never of the whole window.

**What that part is.**  In a ``--trace 1`` run whose ring has lost the
window's first half, the covered part (≈ 22–45 s of the window) is the
stretch in which ``benchmark/trace.py``'s
``stop_trace()`` converts the device trace (24–39 s), and the serve
loop's Python runs about half as slow again meanwhile: median
``serve.emit`` 48 ms against 32 ms in an undisturbed profile, shares of
11.9–12.5 where the profile says 9.6 (PERF.md §6, PR 26, chip runs).
Every reader built on this module then reads the SLOWED second half of the
window; with the ring of 262,144 it reads the whole window, of which that
half is still slowed.  Compare readings taken over the same part with
each other, not with an untraced run, and read the baselines again once
``stop_trace`` has moved out of the window (ROADMAP W11f)."""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

#: a decode iteration ends where one of these closes (materialization)
ITERATION_ENDS = ("serve.decode", "serve.spec_verify")
#: the first of these after it is the next work handed to the device
DISPATCHES = ("serve.prefill_chunk",) + ITERATION_ENDS


def window_of(obs) -> Optional[Tuple[int, int]]:
    lo, hi = obs.get("window_ns") or (None, None)
    return (lo, hi) if lo is not None and hi is not None and hi > lo \
        else None


def covered_window(obs) -> Optional[Tuple[int, int]]:
    """The window, cut at its front to what the ring still holds: from the
    earliest surviving iteration end on.  Such a span is appended the
    moment it closes and eviction is oldest-first, so every span that
    ended after it is still there, and what lies before it is dropped
    whole instead of being counted in part."""
    w = window_of(obs)
    ends = [ts + dur for kind, ts, dur, _a in obs.get("spans", [])
            if kind in ITERATION_ENDS]
    if not w or not ends:
        return None
    lo = max(w[0], min(ends))
    return (lo, w[1]) if w[1] > lo else None


def clipped_ns(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> int:
    """Total length of ``(start, end)`` intervals inside ``[lo, hi)``."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in intervals)


def share_of_window(obs, kinds: Sequence[str]) -> Optional[float]:
    """Percent of the covered window taken by spans of ``kinds`` (spans of
    one thread that do not overlap: a sum, not a union); a span that
    straddles an edge counts for its part inside.  ``None`` where the
    program recorded none."""
    w = covered_window(obs)
    iv = [(ts, ts + dur) for kind, ts, dur, _a in obs.get("spans", [])
          if kind in kinds]
    if not w or not iv:
        return None
    return 100.0 * clipped_ns(iv, *w) / (w[1] - w[0])


def host_gaps(spans) -> List[Tuple[int, int]]:
    """The stretches in which the serve loop had nothing dispatched: from
    the close of one iteration's ``serve.decode``/``serve.spec_verify``
    (its tokens are on the host, the device queue is empty) to the start
    of the next dispatch, be it a prefill chunk or the next decode."""
    ends = sorted(ts + dur for kind, ts, dur, _a in spans
                  if kind in ITERATION_ENDS)
    starts = sorted(ts for kind, ts, _dur, _a in spans if kind in DISPATCHES)
    gaps, i = [], 0
    for k, end in enumerate(ends):
        while i < len(starts) and starts[i] < end:
            i += 1
        if i == len(starts):
            break
        if k + 1 < len(ends) and starts[i] >= ends[k + 1]:
            continue   # no dispatch before the next close: not a gap of ours
        gaps.append((end, starts[i]))
    return gaps


def durations_ms_ending_in_window(obs, kind: str) -> List[float]:
    """Durations (ms) of the spans of ``kind`` that END inside the window:
    a request's wait counts where it was admitted, its prefill where its
    first token left."""
    w = window_of(obs)
    if not w:
        return []
    return [dur / 1e6 for k, ts, dur, _a in obs.get("spans", [])
            if k == kind and w[0] <= ts + dur < w[1]]
