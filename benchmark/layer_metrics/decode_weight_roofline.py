"""The decode program's share of the memory roofline, weights only: decode
steps in the traced span x the int8 bytes of the matrices over the HBM
peak, divided by the device time of that program's executions.  The true
bytes are more (cache, activations), so this is a floor and cannot pass
100 %.

Tied to ``models/dense_decoder.py``: the bytes are that architecture's
(``flops.decoder_weight_bytes``: seven int8 matrices a block and the
head, every one read by every token), 7.11 GB for ``mistral_7b``.  A
configuration of another architecture is not added to this reader's
``workloads``; it brings a reader with counts of its own."""

from benchmark import flops
from benchmark.trace import program_totals

#: how the profiler names the serve loop's decode program (PERF.md)
PROGRAM = "decode_chunk"


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    steps = obs.get("decode_steps_per_call")
    if not t or not peaks or not steps:
        return None
    calls, seconds = program_totals(t, PROGRAM)
    if not calls or not seconds:
        return None
    need = (calls * steps * flops.decoder_weight_bytes(obs["cfg"])
            / peaks["hbm_bytes_per_s"])
    return 100.0 * need / seconds
