"""The whole step's share of the chips' bf16 peak: FLOPs the mathematics
needs for every prompt and output token the window processed, each at its
own context, as the serve driver sums them from ``flops_per_token`` of
the configuration's model module (``observed["flops_in_window"]``), over
the window and the peak.  Any serve cell can be added to its list."""

from benchmark.peaks import mfu_percent


def read(obs):
    return mfu_percent(obs)
