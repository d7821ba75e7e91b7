"""The grouped expert product's share of the memory roofline where a
token's choices may be identity experts: the bytes of the held experts
that the decode steps of the traced span HAD to stream (an expert no live
row was routed to need not be read; an identity pair reads none) over the
HBM peak, divided by the device time of the three grouped products.

As ``moe_expert_roofline`` does for its model: ``moe_experts_hit`` of the
``serve.decode`` spans closed while the profiler ran (summed there over
the chunk's steps and the expert layers; where the ring has dropped them
already, of the spans of the same window it still holds:
``latent_attn_roofline.decode_spans_of_trace``), times the decode calls
the trace holds, times one expert (``models/scmoe_latent_decoder.py
expert_bytes``: three bfloat16 matrices, 75.5 MB at 6144 x 2048); the
products are the custom calls named ``ragged-dot`` whose rows are the
decode step's pairs, ``slots x moe_topk`` (identity pairs and pairs of
experts held elsewhere ride along as rows no group owns).  The bytes
leave out the activations, so this is a floor and cannot pass 100 %.

Tied to ``models/scmoe_latent_decoder.py``."""

from benchmark.layer_metrics.latent_attn_roofline import \
    decode_spans_of_trace
from benchmark.models import scmoe_latent_decoder as model
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def is_expert_product(op: str, cfg: dict) -> bool:
    rows = cfg["serve"]["slots"] * cfg["moe_topk"]
    return ("ragged-dot" in op and op.endswith(" custom-call")
            and f"[{rows}," in op)


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans"):
        return None
    hits = [a["moe_experts_hit"] for a in decode_spans_of_trace(obs)[0]
            if "moe_experts_hit" in a]
    per_call = sum(hits) / len(hits) if hits else None
    calls, _ = program_totals(t, PROGRAM)
    seconds = sum(s for name, s in t["ops"].items()
                  if is_expert_product(name, obs["cfg"]))
    if not per_call or not calls or not seconds:
        return None
    need = calls * per_call * model.expert_bytes(obs["cfg"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
