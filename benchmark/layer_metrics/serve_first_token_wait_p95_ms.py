"""95th percentile of the ``serve.first_token`` spans that closed in the
window: how long the serve thread waited for a newly live stream's first
token, which since PR 37 its prefill program samples — the wait is for
that program (16.5 ms of device time for one 32-token chunk), with the
decode chunk already queued behind it.  The largest part of
``ttft_p95_ms`` (≈ 70 % in ``decode_c32``), and the part a prefill kernel
for ``T > 1`` would shorten; ``serve_queue_p95_ms`` and the admission are
the rest of ``serve_admit_to_first_p95_ms`` + queue."""

from benchmark.ring_spans import durations_ms_ending_in_window
from benchmark.stats import percentile


def read(obs):
    waits = durations_ms_ending_in_window(obs, "serve.first_token")
    return percentile(waits, 95) if waits else None
