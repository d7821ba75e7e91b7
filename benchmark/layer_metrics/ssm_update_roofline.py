"""The state-space decode update kernel's share of the memory roofline:
the bytes its executions in the traced span had to move — for each, the
float32 state of the rows it covers read and written, and their ``x``,
``dt``, ``z``, ``B``, ``C`` and ``y``, with ``A`` and ``D`` once
(``models/ssm_hybrid_decoder.py ssm_update_bytes``: 41.9 MB at 64 rows) —
over the HBM peak, divided by the device time of those executions.

One execution is one state-space layer of one decode step.  The rows it
covers are the live rows of the decode calls that stand for the traced
stretch (``occupancy`` of their ``serve.decode`` spans:
``latent_attn_roofline.decode_spans_of_trace``): a row that is not live
moves no tile.  Those spans say how many layers took the kernel
(``ssm_kernel_layers``, docs/OBSERVABILITY.md); where they do not (a
program without the kernel's census) or say none, nothing is read.
**The numerator counts the executions the trace holds** (``op_calls``),
not the decode calls: see ``conv_moe_expert_roofline``.

The kernel is found by name: the custom call ``ssm_update``
(``nnstreamer_tpu/ops/ssm.py``; the ``pallas_call``'s ``name`` is part of
the yardstick).  The bytes leave out nothing the kernel must move but the
padding of its small operands, so this cannot pass 100 %.

Tied to ``models/ssm_hybrid_decoder.py``."""

from benchmark.layer_metrics.conv_moe_expert_roofline import executions
from benchmark.layer_metrics.latent_attn_roofline import \
    decode_spans_of_trace
from benchmark.models import ssm_hybrid_decoder as model


def is_kernel(op: str, cfg: dict) -> bool:
    return op.startswith("%ssm_update") and op.endswith(" custom-call")


def live_rows(obs):
    """Mean live rows of the decode calls that stand for the traced
    stretch, where their spans say the update took the kernel; else
    None."""
    spans = [a for a in decode_spans_of_trace(obs)[0]
             if a.get("ssm_kernel_layers") and a.get("occupancy")]
    return sum(a["occupancy"] for a in spans) / len(spans) \
        if spans else None


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans"):
        return None
    rows = live_rows(obs)
    n, seconds = executions(t, obs["cfg"], is_kernel)
    if not rows or not n or not seconds:
        return None
    need = n * model.ssm_update_bytes(obs["cfg"], rows)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
