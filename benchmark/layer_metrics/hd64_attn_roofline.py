"""The paged-attention kernel's share of the memory roofline at a head
width under the 128 lanes: per execution (one attention layer of one
decode step) the K and V bytes its live rows had to read — ``context``
positions x 2,048 B a position (K and V, 8 KV heads x 64 x bfloat16) a
row — over the HBM peak, divided by the kernel's device time.  The
convolution layers read no cache and run no such kernel.

The kernel is found by name and result shape: the custom call
``paged_attention`` whose result is ``[slots, heads, 128]`` — the narrow
heads go through the kernel two KV heads to a 128-lane row and come back
128 lanes wide (``nnstreamer_tpu/ops/attention.py paged_attention``; the
``pallas_call``'s ``name`` and ``out_shape`` are part of the yardstick).

Bytes an execution: the mean over the tokens pulled while the profiler
ran, each at the context it was produced at (prompt length + stream
index: ``models/conv_moe_decoder.py kv_bytes_attended`` over the
attention layers), times the live rows a step (``occupancy`` of the
``serve.decode`` spans of the stretch:
``latent_attn_roofline.decode_spans_of_trace``).  **Multiplied by the
executions the trace holds** (``op_calls``), not by the decode calls: see
``conv_moe_expert_roofline``.

Tied to ``models/conv_moe_decoder.py``."""

from benchmark.layer_metrics.conv_moe_expert_roofline import executions
from benchmark.layer_metrics.latent_attn_roofline import \
    decode_spans_of_trace
from benchmark.models import conv_moe_decoder as model


def is_kernel(op: str, cfg: dict) -> bool:
    shape = f"[{cfg['serve']['slots']},{cfg['num_attention_heads']},128]"
    return op.endswith(" custom-call") and "paged_attention" in op \
        and shape in op


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not obs.get("decoded") or not t.get(
            "host_span"):
        return None
    cfg = obs["cfg"]
    lo, hi = t["host_span"]
    pulled = [c for at, c in obs["decoded"] if lo <= at < hi]
    rows = [a["occupancy"] for a in decode_spans_of_trace(obs)[0]
            if a.get("occupancy")]
    n, seconds = executions(t, cfg, is_kernel)
    if not pulled or not rows or not n or not seconds:
        return None
    a_row = sum(model.kv_bytes_attended(cfg, c) for c in pulled) \
        / len(pulled) / model.n_attention_layers(cfg)
    need = n * a_row * sum(rows) / len(rows)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
