"""95th percentile, over every batch of the window, of the time from a
batch's admission at ``appsrc`` to the pull of its labels, on the
benchmark's clock."""

from benchmark.stats import percentile


def read(obs):
    lat = obs.get("batch_ms")
    return percentile(lat, 95) if lat else None
