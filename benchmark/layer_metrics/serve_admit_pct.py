"""Share of the window (of the part of it the ring still covers) the
serve loop spent before it could dispatch:
draining the hand-off queue, control commands and reaping
(``serve.intake``) and the admission pass over the waiting prompts
(``serve.admit_pass``).

Read from the part of the window the ring still holds, which in a traced
run is its slowed second half (``benchmark/ring_spans.py``, ROADMAP W11f):
0.030–0.031 there; an undisturbed profile has 0.0013 s of 3.0 s under these
phases, 0.04 (PERF.md §5, §6)."""

from benchmark.ring_spans import share_of_window


def read(obs):
    return share_of_window(obs, ("serve.intake", "serve.admit_pass"))
