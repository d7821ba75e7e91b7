"""The paged-attention kernel's share of the memory roofline where layers
keep different state: per decoded token the K and V bytes its attention
had to read, ``sum over layers of min(context, window of the layer)``
positions (the whole context on a full layer) x 4,096 B a position (K and
V, 8 KV heads x 128 x bfloat16), over the HBM peak, divided by the
kernel's device time — both layer kinds, which the trace cannot tell
apart (one kernel, one result shape).

Bytes per decode call are taken as ``paged_attn_roofline`` takes them:
from the tokens pulled and the ``serve.decode`` spans closed while the
profiler ran, each token at the context it was produced at (prompt length
+ stream index), multiplied by the decode calls the trace holds.

Tied to ``models/moe_hybrid_decoder.py``, whose ``kv_bytes_attended``
does the count."""

from benchmark.models import moe_hybrid_decoder as model
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def is_kernel(op: str, cfg: dict) -> bool:
    """The custom call whose result is the decode step's attention output
    ``[slots, heads, head_dim]``, named for the kernel."""
    shape = (f"[{cfg['serve']['slots']},{cfg['num_attention_heads']},"
             f"{cfg['head_dim']}]")
    return op.endswith(" custom-call") and shape in op \
        and "paged_attention" in op


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not obs.get("decoded") or not t.get(
            "host_span"):
        return None
    lo, hi = t["host_span"]
    to_ns = obs["window_ns"][0] - int(obs["window"][0] * 1e9)
    lo_ns, hi_ns = int(lo * 1e9) + to_ns, int(hi * 1e9) + to_ns
    decodes = sum(1 for kind, ts, dur, _a in obs["spans"]
                  if kind == "serve.decode" and lo_ns <= ts + dur < hi_ns)
    need = sum(model.kv_bytes_attended(obs["cfg"], c)
               for at, c in obs["decoded"] if lo <= at < hi)
    calls, _ = program_totals(t, PROGRAM)
    seconds = sum(s for name, s in t["ops"].items()
                  if is_kernel(name, obs["cfg"]))
    if not decodes or not need or not calls or not seconds:
        return None
    return 100.0 * (calls * need / decodes / peaks["hbm_bytes_per_s"]) \
        / seconds
