"""The grouped expert product's share of the memory roofline, for any
model of the ``serve`` kind whose module names its counts under the
common names (``expert_bytes``, ``n_expert_layers``,
``experts_per_token``): the bytes of the held experts that the product's
executions in the traced span HAD to stream (an expert that no live row
was routed to need not be read) over the HBM peak, divided by the device
time of those executions.

The model's module is resolved through the configuration's ``model``
(``benchmark/models/<model>.py``), not imported by name, so one reader
serves every such model.  One execution is one expert layer of one
decode step; experts hit an execution come from the program's own count
(``moe_experts_hit`` of the ``serve.decode`` spans, summed there over
the chunk's steps and the expert layers: a span's count over ``chunk x
expert layers`` is the mean of its executions), taken from the spans
that stand for the traced stretch
(``latent_attn_roofline.decode_spans_of_trace``).  The numerator counts
the executions the trace holds (``op_calls``), so a decode chunk cut by
the trace's edge counts only by its executions inside it
(``conv_moe_expert_roofline.executions``).

The product is the custom call named ``ragged-dot…`` whose rows are the
decode step's routed pairs, ``slots x experts a token`` (a prefill
chunk's have another row count and are left out on both sides of the
division); its name and result shape are part of the yardstick.  The
bytes leave out the activations, so this is a floor and cannot pass
100 %."""

import importlib

from benchmark.layer_metrics.conv_moe_expert_roofline import executions
from benchmark.layer_metrics.latent_attn_roofline import \
    decode_spans_of_trace


def model_of(cfg: dict):
    """The configuration's model module."""
    return importlib.import_module(f"benchmark.models.{cfg['model']}")


def is_expert_product(op: str, cfg: dict) -> bool:
    rows = cfg["serve"]["slots"] * model_of(cfg).experts_per_token(cfg)
    return ("ragged-dot" in op and op.endswith(" custom-call")
            and f"[{rows}," in op)


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans"):
        return None
    cfg = obs["cfg"]
    model = model_of(cfg)
    per = [a["moe_experts_hit"] / a["chunk"]
           for a in decode_spans_of_trace(obs)[0]
           if "moe_experts_hit" in a and a.get("chunk")]
    n, seconds = executions(t, cfg, is_expert_product)
    if not per or not n or not seconds:
        return None
    hits = sum(per) / len(per) / model.n_expert_layers(cfg)
    need = n * hits * model.expert_bytes(cfg)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
