"""95th percentile of the time from a request's admission to its first
token leaving the serve loop (``serve.prefill`` ring spans), over the
requests whose first token left in the window: the prefill half of the
time to first token.

Read from the part of the window the ring still holds, which in a traced
run is its slowed second half (``benchmark/ring_spans.py``, ROADMAP W11f):
44.4–47.1 ms read; the host's part of it is stretched, the prefill
chunk's device time is not (PERF.md §6)."""

from benchmark.ring_spans import durations_ms_ending_in_window
from benchmark.stats import percentile


def read(obs):
    took = durations_ms_ending_in_window(obs, "serve.prefill")
    return percentile(took, 95) if took else None
