"""The whole step's share of the chips' bf16 peak: frames labelled in the
window x the FLOPs of one forward pass, ``flops_per_frame`` of the
configuration's model module (the stream driver's
``observed["flops_in_window"]``), over the window and the peak.  Any
stream cell can be added to its list."""

from benchmark.peaks import mfu_percent


def read(obs):
    return mfu_percent(obs)
