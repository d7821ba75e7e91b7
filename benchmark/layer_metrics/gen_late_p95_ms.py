"""How late the benchmark's own generator pushed: 95th percentile of push
time minus due time over the requests due in the window.  The generator
shares the process and the interpreter lock with the serve loop, so a
starved generator must not read as a fast server."""

from benchmark.stats import percentile


def read(obs):
    late = obs.get("late_ms")
    return percentile(late, 95) if late else None
