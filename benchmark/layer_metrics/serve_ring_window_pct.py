"""Share of the measured window that the program's ring still covers when
the window is read: ``ring_spans.covered_window`` (from the earliest
surviving close of a ``serve.decode``/``serve.spec_verify`` on) ÷ the
window.  Every share the serve-loop readers take is of that part, and the
roofline readers need the traced stretch in the middle of the window to
lie inside it (``latent_attn_roofline.decode_spans_of_trace``).

100 where the ring kept the loop's whole window.  Since PR 38 the
program's recorder keeps a lane a stage and evicts from the longest, so
the spans the runtime writes for every token no longer evict the loop's; before it this read ≈ 89, 69,
49 and 49 in the four serving cells (ROADMAP W11h).  A reading under 100
says the loop's own lane wrapped or the capacity was cut: the guard."""

from benchmark.ring_spans import covered_window, window_of


def read(obs):
    w, c = window_of(obs), covered_window(obs)
    if not w or not c:
        return None
    return 100.0 * (c[1] - c[0]) / (w[1] - w[0])
