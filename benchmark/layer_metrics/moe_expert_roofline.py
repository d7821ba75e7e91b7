"""The grouped expert product's share of the memory roofline: the bytes of
the held experts that the decode steps of the traced span HAD to stream
(an expert that no live row was routed to need not be read) over the HBM
peak, divided by the device time of the three grouped products.

Experts hit come from the program's own count: ``serve.decode`` carries
``moe_experts_hit``, summed over the chunk's steps and the sparse layers
(docs/OBSERVABILITY.md).  The profiler's clock is not the host's, so, as
``paged_attn_roofline`` does, the hits per decode call are taken from the
spans closed while the profiler ran and multiplied by the decode calls
the trace holds.  One expert is ``models/moe_hybrid_decoder.py
expert_bytes`` (three bfloat16 matrices, 75.5 MB at 6144 x 2048).

The products are found by name and shape, the only thing the trace's
reduction keeps of an operation: custom calls named ``ragged-dot`` whose
rows are the decode step's routed pairs, ``slots x experts a token`` (a
prefill chunk's have another row count and are left out on both sides of
the division).  The bytes leave out the activations (a few MB), so this
is a floor and cannot pass 100 %.

Tied to ``models/moe_hybrid_decoder.py``."""

from benchmark.models import moe_hybrid_decoder as model
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def is_expert_product(op: str, cfg: dict) -> bool:
    rows = cfg["serve"]["slots"] * cfg["num_experts_per_tok"]
    return ("ragged-dot" in op and op.endswith(" custom-call")
            and f"[{rows}," in op)


def hits_per_call(obs):
    """Mean ``moe_experts_hit`` of the decode calls closed while the
    profiler ran."""
    t = obs["trace"]
    lo, hi = t["host_span"]
    to_ns = obs["window_ns"][0] - int(obs["window"][0] * 1e9)
    lo_ns, hi_ns = int(lo * 1e9) + to_ns, int(hi * 1e9) + to_ns
    hits = [a["moe_experts_hit"] for kind, ts, dur, a in obs["spans"]
            if kind == "serve.decode" and lo_ns <= ts + dur < hi_ns
            and "moe_experts_hit" in a]
    return sum(hits) / len(hits) if hits else None


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("host_span") \
            or not obs.get("spans"):
        return None
    per_call = hits_per_call(obs)
    calls, _ = program_totals(t, PROGRAM)
    seconds = sum(s for name, s in t["ops"].items()
                  if is_expert_product(name, obs["cfg"]))
    if not per_call or not calls or not seconds:
        return None
    need = calls * per_call * model.expert_bytes(obs["cfg"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
