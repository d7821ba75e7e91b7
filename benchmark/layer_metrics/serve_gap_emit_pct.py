"""The part of the serve loop's host gap spent delivering tokens: summed
over ``ring_spans.host_gaps`` (close of a ``serve.decode``/
``serve.spec_verify`` → start of the next dispatch, nothing queued on the
chip), the stretch of each gap that ``serve.emit`` spans cover, as a share
of the part of the window the ring covers.  That is the IDLE delivery —
a chunk that ended streams with no request waiting answers the freed
callers before anything is dispatched — found by where the span lies,
which also catches a delivery with nothing dispatched behind it; not
``serve_emit_pct``, most of which runs under the next chunk.

``serve_host_gap_pct − serve_gap_emit_pct − serve_admit_pct`` is the
loop's bookkeeping inside the gap (settling the chunk, the loop's tail,
preparing the prefill chunk: ``serve.iter``'s self time) wherever
admission runs in the gap, as it does in a closed loop: a request comes
when a stream ends.  ROADMAP S3's next step (stop the idle delivery once
the freed caller's request is in) should move this and nothing else of
the gap."""

from benchmark.ring_spans import clipped_ns, covered_window, host_gaps


def read(obs):
    """Percent of the covered window that lies both in a host gap and in
    a ``serve.emit`` span (one thread's spans: they do not overlap).
    ``None`` where there is no gap to look in or no delivery at all."""
    w = covered_window(obs)
    spans = obs.get("spans", [])
    gaps = host_gaps(spans)
    mine = sorted((ts, ts + dur) for kind, ts, dur, _a in spans
                  if kind == "serve.emit")
    if not w or not gaps or not mine:
        return None
    pieces, i = [], 0
    for g_lo, g_hi in gaps:            # both lists ascend and are disjoint
        while i < len(mine) and mine[i][1] <= g_lo:
            i += 1
        j = i
        while j < len(mine) and mine[j][0] < g_hi:
            pieces.append((max(mine[j][0], g_lo), min(mine[j][1], g_hi)))
            j += 1
    return 100.0 * clipped_ns(pieces, *w) / (w[1] - w[0])
