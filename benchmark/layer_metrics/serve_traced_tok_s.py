"""Tokens a second of THIS traced run: the tokens the benchmark pulled
inside the window ÷ the window, on the benchmark's own clock.  A traced
run's result line has no ``serve_tok_s`` (per-layer metrics only), so the
ledger could not say what the program's spans and the profiler cost when
on; beside the untraced ``serve_tok_s`` of the same PR this is that cost
(PERF.md §6, PR 38: the spans and ``stop_trace()`` apart).

Counts ``observed["decoded"]``, which leaves out each stream's first
token (the driver charges a prompt's prefill there instead): 1 token in
512–1,024 of the committed cells, so this reads 0.1–0.2 % under what
``serve_tok_s`` would in the same window."""


def read(obs):
    decoded, seconds = obs.get("decoded"), obs.get("window_s")
    if not decoded or not seconds:
        return None
    return len(decoded) / seconds
