"""The whole step's share of the chips' bf16 peak: frames labelled in the
window x the FLOPs of one forward pass from the layer shapes
(``benchmark/flops.py``) over the window and the peak."""

from benchmark.peaks import mfu_percent


def read(obs):
    return mfu_percent(obs)
