"""The decode program's share of the memory roofline, weights only, where
experts are sparse and all held: decode steps in the traced span x the
bytes a step has to stream — the experts its rows were routed to, and
every other matrix of the layers and the head once
(``models/conv_moe_decoder.py step_weight_bytes``) — over the HBM peak,
divided by the device time of the decode program's executions.  It is the
memory roofline of the WHOLE step: what shows the convolution mixers, the
router, the sort and the scatter once the expert kernel is near its own.
The true bytes are more (cache, state, activations), so this is a floor
and cannot pass 100 %.

**Steps are counted from the expert product's executions the trace
holds** (``op_calls``, one a sparse layer a step:
``conv_moe_expert_roofline.executions``), not from the decode calls x
steps a call: a chunk cut by the trace's edge then counts for the steps
of it the trace holds.  Experts hit a step are the program's own count
(``moe_experts_hit`` of the ``serve.decode`` spans).

Tied to ``models/conv_moe_decoder.py``."""

from benchmark.layer_metrics.conv_moe_expert_roofline import (
    executions, hits_per_execution)
from benchmark.models import conv_moe_decoder as model
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans"):
        return None
    cfg = obs["cfg"]
    hits = hits_per_execution(obs)
    n, _ = executions(t, cfg)
    _, seconds = program_totals(t, PROGRAM)
    if not hits or not n or not seconds:
        return None
    layers = model.n_expert_layers(cfg)
    need = n / layers * model.step_weight_bytes(cfg, hits * layers)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
