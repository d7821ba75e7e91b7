"""The grouped expert product's share of the memory roofline where every
expert of a layer is held: the bytes of the experts that the product's
executions in the traced span HAD to stream (an expert that no live row
was routed to need not be read) over the HBM peak, divided by the device
time of those executions.

One execution is one sparse layer of one decode step.  Experts hit an
execution come from the program's own count: ``serve.decode`` carries
``moe_experts_hit``, summed over the chunk's steps and the sparse layers
(docs/OBSERVABILITY.md), so a span's count over ``chunk x sparse layers``
is the mean of its executions; the spans are those closed while the
profiler ran or, where the ring has dropped them (the cell runs past
3,000 tokens/s), those of the same window it still holds
(``latent_attn_roofline.decode_spans_of_trace``).  One expert is
``models/conv_moe_decoder.py expert_bytes`` (three bfloat16 matrices,
18.9 MB at 2048 x 1536).

**The numerator counts the executions the trace holds** (``op_calls`` of
the kernel), not the decode calls (``module_calls``): a chunk cut by the
trace's edge has only part of its executions in the trace, and counted
whole it reads up to a chunk in twenty too high (PERF.md section 7).

The product is the custom call named ``ragged-dot…`` whose rows are the
decode step's routed pairs, ``slots x experts a token`` (a prefill
chunk's have another row count and are left out on both sides of the
division).  The bytes leave out the activations, so this is a floor and
cannot pass 100 %.

Tied to ``models/conv_moe_decoder.py``."""

from benchmark.layer_metrics.latent_attn_roofline import \
    decode_spans_of_trace
from benchmark.models import conv_moe_decoder as model


def is_expert_product(op: str, cfg: dict) -> bool:
    rows = cfg["serve"]["slots"] * cfg["num_experts_per_tok"]
    return ("ragged-dot" in op and op.endswith(" custom-call")
            and f"[{rows}," in op)


def executions(t: dict, cfg: dict, is_op=is_expert_product):
    """``(executions, device seconds)`` in the trace of the operations
    ``is_op(name, cfg)`` picks (the expert product's by default)."""
    names = [n for n in t.get("ops", {}) if is_op(n, cfg)]
    return (sum(t.get("op_calls", {}).get(n, 0) for n in names),
            sum(t["ops"][n] for n in names))


def hits_per_execution(obs):
    """Mean experts hit a sparse layer a decode step, over the decode
    calls that stand for the traced stretch."""
    cfg = obs["cfg"]
    per = [a["moe_experts_hit"] / a["chunk"]
           for a in decode_spans_of_trace(obs)[0]
           if "moe_experts_hit" in a and a.get("chunk")]
    return sum(per) / len(per) / model.n_expert_layers(cfg) if per else None


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans"):
        return None
    hits = hits_per_execution(obs)
    n, seconds = executions(t, obs["cfg"])
    if not hits or not n or not seconds:
        return None
    need = n * hits * model.expert_bytes(obs["cfg"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
