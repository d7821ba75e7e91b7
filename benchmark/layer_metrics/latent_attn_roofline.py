"""The paged latent-attention kernel's share of the memory roofline: per
decoded token the cache bytes its attention had to read — ``context``
positions x 576 values (the normed latent and the one rotated key all
heads share) x 2 B a block, 8 blocks: each row ONCE, for scores and
values alike — over the HBM peak, divided by the kernel's device time.
The pool pads a row to 640 values; the padding is not counted, so it
lowers the share.  Memory bounds the kernel: 121 FLOP a byte read
against the chip's 240.

Bytes per decode call are taken as ``paged_attn_roofline`` takes them:
from the tokens pulled and the ``serve.decode`` spans closed while the
profiler ran, each token at the context it was produced at (prompt length
+ stream index), multiplied by the decode calls the trace holds.  This
cell runs past the speed at which the program's ring still holds the
traced stretch by the time the window is read (PERF.md §3: ≈ 1,900
tokens/s), so where no such span is left the decode calls of the stretch
are counted from its tokens instead: tokens pulled ÷ (live rows x steps a
call), both from the ``serve.decode`` spans of the same window that the
ring does hold (:func:`decode_spans_of_trace`).

The kernel is found by name and result shape: the custom call
``paged_latent_attention`` whose result is ``[slots, heads,
kv_lora_rank]`` (the ``pallas_call``'s ``name`` and ``out_shape``).  A
program without that kernel gives nothing to read.

Tied to ``models/scmoe_latent_decoder.py``, whose
``latent_bytes_attended`` does the count."""

from benchmark.models import scmoe_latent_decoder as model
from benchmark.trace import program_totals

PROGRAM = "decode_chunk"


def is_kernel(op: str, cfg: dict) -> bool:
    shape = (f"[{cfg['serve']['slots']},{cfg['num_attention_heads']},"
             f"{cfg['kv_lora_rank']}]")
    return op.endswith(" custom-call") and shape in op \
        and "paged_latent_attention" in op


def decode_spans_of_trace(obs):
    """``(args of the serve.decode spans that stand for the traced
    stretch, whether they ARE the stretch's)``: those closed while the
    profiler ran, or, where the ring has already dropped them, those of
    the measured window that it still holds (a closed loop at full
    occupancy: the same rows, steps and router a call)."""
    lo, hi = obs["trace"]["host_span"]
    to_ns = obs["window_ns"][0] - int(obs["window"][0] * 1e9)
    lo_ns, hi_ns = int(lo * 1e9) + to_ns, int(hi * 1e9) + to_ns
    decodes = [(ts + dur, a) for kind, ts, dur, a in obs.get("spans", [])
               if kind == "serve.decode"]
    inside = [a for end, a in decodes if lo_ns <= end < hi_ns]
    if inside:
        return inside, True
    w_lo, w_hi = obs["window_ns"]
    return [a for end, a in decodes if w_lo <= end < w_hi], False


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not obs.get("decoded") or not t.get(
            "host_span"):
        return None
    lo, hi = t["host_span"]
    pulled = [c for at, c in obs["decoded"] if lo <= at < hi]
    spans, exact = decode_spans_of_trace(obs)
    if exact:
        decodes = len(spans)
    else:
        per_call = [a["occupancy"] * a["chunk"] for a in spans
                    if a.get("occupancy") and a.get("chunk")]
        decodes = len(pulled) * len(per_call) / sum(per_call) \
            if per_call else 0
    need = sum(model.latent_bytes_attended(obs["cfg"], c) for c in pulled)
    calls, _ = program_totals(t, PROGRAM)
    seconds = sum(s for name, s in t["ops"].items()
                  if is_kernel(name, obs["cfg"]))
    if not decodes or not need or not calls or not seconds:
        return None
    return 100.0 * (calls * need / decodes / peaks["hbm_bytes_per_s"]) \
        / seconds
