"""What delivering one token costs the serve thread: Σ durations of the
``serve.emit`` spans that closed in the part of the window the ring
covers ÷ Σ their ``tokens``, in microseconds.  Python per token — the
push downstream, two histograms, the stream's books — sharing the
interpreter lock with the sink's thread and the callers': ROADMAP S9's
number (0.13 ms a token in PR 32, 0.19 in PR 34's 64-slot cell, 0.21 in
an idle delivery with the callers' threads awake, PR 37), and the ceiling
on every step made shorter or slot added.  ``serve_emit_pct`` is this
times the token rate."""

from benchmark.ring_spans import covered_window


def read(obs):
    w = covered_window(obs)
    if not w:
        return None
    emits = [(dur, a["tokens"]) for kind, ts, dur, a in obs.get("spans", [])
             if kind == "serve.emit" and "tokens" in a
             and w[0] <= ts + dur < w[1]]
    tokens = sum(n for _dur, n in emits)
    return sum(dur for dur, _n in emits) / 1e3 / tokens if tokens else None
