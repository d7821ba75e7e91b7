"""The paged-attention kernel's share of the memory roofline: the K and V
bytes the decode steps of the traced span had to read (every decoded token
at the context it was produced at, known from outside: prompt length +
stream index) over the HBM peak, divided by the kernel's device time.

The profiler's clock is not the host's, so the two ends of the traced span
are not cut on the token log.  Instead the bytes per decode call are taken
from the tokens pulled and the ``serve.decode`` spans closed while the
profiler ran (host clock, same stretch of the window, same contexts), and
multiplied by the decode calls the trace holds.

Tied to ``models/dense_decoder.py``: ``flops.kv_bytes_per_token`` counts
every layer's K and V alike, at every context, and ``is_kernel`` finds the
kernel by that architecture's shapes.  A configuration whose layers keep
different state (a window, a latent, a recurrent state) is not added to
this reader's ``workloads``; it brings a reader of its own."""

from benchmark import flops
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def is_kernel(op: str, cfg: dict) -> bool:
    """The profiler names a Pallas call ``%closed_call.N ... custom-call``
    and nothing else; the paged kernel is the custom call whose result is
    the decode step's attention output ``[slots, heads, head_dim]``
    (PERF.md asks the tracing issue for a name of its own)."""
    shape = (f"[{cfg['serve']['slots']},{cfg['num_attention_heads']},"
             f"{cfg['head_dim']}]")
    return op.endswith(" custom-call") and shape in op


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
    contexts = sum(c for at, c in obs["decoded"] if lo <= at < hi)
    calls, _ = program_totals(t, PROGRAM)
    seconds = sum(s for name, s in t["ops"].items()
                  if is_kernel(name, obs["cfg"]))
    if not decodes or not contexts or not calls or not seconds:
        return None
    per_call = contexts * flops.kv_bytes_per_token(obs["cfg"]) / decodes
    return 100.0 * (calls * per_call / peaks["hbm_bytes_per_s"]) / seconds
