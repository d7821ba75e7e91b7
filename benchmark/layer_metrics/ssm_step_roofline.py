"""The decode program's share of the memory roofline where state-space
layers carry a float32 state a slot: decode steps in the traced span x
the bytes a step has to move — every matrix of the layers and the head
once, each live row's state and filter columns read and written, and the
K/V of the live context (``models/ssm_hybrid_decoder.py step_bytes``) —
over the HBM peak, divided by the device time of the decode program's
executions.  It is the memory roofline of the WHOLE step: what shows the
projections, the feed-forwards and the head beside the update kernel.
Activations are left out, so this is a floor and cannot pass 100 %.

**Steps are counted from the update kernel's executions the trace holds**
(``op_calls``, ``ssm_kernel_layers`` a step: the layers the
``serve.decode`` spans say took the kernel), not from the decode calls x
steps a call: a chunk cut by the trace's edge then counts for the steps
of it the trace holds.  Live rows a step are the spans' ``occupancy``
(``ssm_update_roofline.live_rows``); the context they sum to, the mean
context of the tokens pulled while the profiler ran (prompt length +
stream index) times those rows.

Tied to ``models/ssm_hybrid_decoder.py``."""

from benchmark.layer_metrics.conv_moe_expert_roofline import executions
from benchmark.layer_metrics.latent_attn_roofline import \
    decode_spans_of_trace
from benchmark.layer_metrics.ssm_update_roofline import is_kernel, live_rows
from benchmark.models import ssm_hybrid_decoder as model
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans") or not obs.get("decoded"):
        return None
    cfg = obs["cfg"]
    rows = live_rows(obs)
    layers = [a["ssm_kernel_layers"] for a in decode_spans_of_trace(obs)[0]
              if a.get("ssm_kernel_layers")]
    lo, hi = t["host_span"]
    pulled = [c for at, c in obs["decoded"] if lo <= at < hi]
    n, _ = executions(t, cfg, is_kernel)
    if not rows or not layers or not pulled or not n:
        return None
    _, seconds = program_totals(t, PROGRAM)
    if not seconds:
        return None
    context = rows * sum(pulled) / len(pulled)
    need = n / layers[0] * model.step_bytes(cfg, rows, context)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
