"""Share of the window in which the serve loop had nothing dispatched to
the device: summed over iterations, from the close of ``serve.decode`` (or
``serve.spec_verify``) to the start of the next ``serve.prefill_chunk`` or
``serve.decode``, as a share of the part of the window the ring still
covers.  The host-side twin of ``device_idle.serve``, which also counts
the gaps between programs inside an iteration.

Read from the part of the window the ring still holds, which in a traced
run is its slowed second half (``benchmark/ring_spans.py``, ROADMAP W11f):
11.9–12.5 there, where an undisturbed profile puts ``serve.emit``, which
is nearly all of this gap, at 9.6 (PERF.md §5, §6)."""

from benchmark.ring_spans import clipped_ns, covered_window, host_gaps


def read(obs):
    w = covered_window(obs)
    gaps = host_gaps(obs.get("spans", []))
    if not w or not gaps:
        return None
    return 100.0 * clipped_ns(gaps, *w) / (w[1] - w[0])
