"""The whole step's share of the chips' bf16 peak: FLOPs the mathematics
needs for every prompt and output token the window processed (each at its
own context, ``benchmark/flops.py``) over the window and the peak."""

from benchmark.peaks import mfu_percent


def read(obs):
    return mfu_percent(obs)
