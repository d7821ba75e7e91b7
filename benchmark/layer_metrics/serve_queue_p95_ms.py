"""95th percentile of the time a request waited between ``submit()`` and
its admission into a slot (``serve.queue`` ring spans), over the requests
admitted in the window: the queue half of the time to first token.

Read from the part of the window the ring still holds, which in a traced
run is its slowed second half (``benchmark/ring_spans.py``, ROADMAP W11f):
a request waits out the emit loop it was freed in, and that loop is half
as long again there (10.2–11.0 ms read; PERF.md §6)."""

from benchmark.ring_spans import durations_ms_ending_in_window
from benchmark.stats import percentile


def read(obs):
    waits = durations_ms_ending_in_window(obs, "serve.queue")
    return percentile(waits, 95) if waits else None
