"""Share of the window (of the part of it the ring still covers) the
serve loop spent delivering a materialized
chunk's tokens downstream one by one (``serve.emit`` ring spans): the chip
has nothing queued while it runs.

Read from the part of the window the ring still holds, which in a traced
run is its slowed second half (``benchmark/ring_spans.py``, ROADMAP W11f):
11.5–12.3 there, with a median ``serve.emit`` of 48 ms, against 9.6 and
32 ms in an undisturbed profile (PERF.md §6)."""

from benchmark.ring_spans import share_of_window


def read(obs):
    return share_of_window(obs, ("serve.emit",))
