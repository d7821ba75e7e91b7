"""Share of the deliveries that were made with the next decode chunk
already dispatched (``serve.emit`` ring spans with ``ahead`` = 1, since
PR 32), over the deliveries that closed in the part of the window the
ring still covers.  A count, not a time: how often the serve loop's
run-ahead rule engaged.  The rest were made with nothing queued on the
chip — a stream ended in the chunk and no request was waiting — and
those are what ``serve_host_gap_pct`` still sees.  A chunk whose idle
delivery stopped when the freed callers' requests came in has one span
of each kind.

A program that records no ``ahead`` on its ``serve.emit`` spans (before
PR 32) gives nothing to read."""

from benchmark.ring_spans import covered_window


def read(obs):
    w = covered_window(obs)
    if not w:
        return None
    ahead = [a["ahead"] for kind, ts, dur, a in obs.get("spans", [])
             if kind == "serve.emit" and "ahead" in a
             and w[0] <= ts + dur < w[1]]
    return 100.0 * sum(1 for x in ahead if x) / len(ahead) if ahead else None
