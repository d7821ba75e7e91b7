#!/usr/bin/env python
"""Benchmarks for the five BASELINE.md configs.

Default (no args) = config #1, the headline: MobileNet-v1 classification
pipeline, frames/sec/chip.  BASELINE.json KPI: "frames/sec/chip on
tensor_filter pipeline; p50 per-frame latency".  North star: >=2000 fps
aggregate on a v5e-8 => 250 fps/chip is parity (vs_baseline =
fps_per_chip / 250).

    appsrc -> tensor_transform(typecast+normalize) -> tensor_filter(jax,
    mobilenet_v1, bfloat16) -> tensor_decoder(image_labeling) -> tensor_sink

Frames stream through in batches (the TPU-native move the reference can't
make: its tflite path is frame-at-a-time); transform+filter+decoder fuse
into one jitted XLA program, so normalization rides the MXU with the convs
and only argmax indices come home.

Other configs (--config): detection (#2 SSD + bounding boxes), pose (#3),
segmentation (deeplab + fused image_segment decode), audio (#4 speech
commands / wav2vec2+ctc), llm (#5 token streaming, tokens/sec).

Prints ONE JSON line per config run:
{"metric", "value", "unit", "vs_baseline", ...extras}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# Called from main(), NOT at import: `import bench` (the tests do) must
# stay free of backend side effects.
from nnstreamer_tpu.core.platform import (enable_compilation_cache,
                                           require_tpu)

# 8-deep in-flight window: measured +29% classification fps over 4 (RTT
# and host post-processing hide behind more batches); 16 adds only +2%.
_SOURCE_QUEUE_CAPACITY = 8

#: Peak dense-matmul throughput per chip by device kind (bf16 FLOP/s) —
#: public spec-sheet numbers, used only for the MFU report field.
_PEAK_FLOPS = {
    "tpu v5 lite": 197e12, "tpu v5e": 197e12,
    "tpu v5p": 459e12, "tpu v5": 459e12,
    "tpu v4": 275e12, "tpu v6 lite": 918e12, "tpu v6e": 918e12,
}


def _peak_flops_per_chip():
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for k, v in _PEAK_FLOPS.items():
        if kind.startswith(k):
            return v
    return None


_FLOPS_CACHE: dict = {}


def _fused_stage_flops(p):
    """FLOPs of the pipeline's fused XLA program per batch, from the
    compiled executable's own cost analysis (no hand-counted model tables).
    None when there is no fused stage or the backend can't report it.
    Memoized per (program, input spec): lower().compile() would otherwise
    repeat the 20-40s fused-stage compile per bench config just to read a
    report-only cost field."""
    try:
        import jax.numpy as jnp

        for s in p.stages:
            el = s.element
            fn = getattr(el, "_fn", None)
            in_spec = getattr(el, "_in_spec", None)
            if fn is None or in_spec is None:
                continue
            key = (id(fn), tuple((t.shape, str(t.dtype)) for t in in_spec))
            if key in _FLOPS_CACHE:
                fl = _FLOPS_CACHE[key][1]
            else:
                args = tuple(jnp.zeros(t.shape, t.dtype) for t in in_spec)
                ca = fn.lower(args).compile().cost_analysis()
                if isinstance(ca, list):
                    ca = ca[0] if ca else {}
                fl = float(ca.get("flops", 0.0))
                # Keep fn alive in the cache entry: id() keys are only
                # stable while the object lives — a freed fn's address can
                # be recycled by a different config's program.
                _FLOPS_CACHE[key] = (fn, fl)
            if fl > 0:
                return fl
            # e.g. a fused pure-preprocess stage: keep looking for the
            # model's fused stage.
    except Exception:  # noqa: BLE001 - report field only, never fail a bench
        return None
    return None


def _add_mfu(r: dict, p, batch: int) -> dict:
    """mfu = achieved model FLOP/s / chip peak (VERDICT r1 item #9)."""
    flops = _fused_stage_flops(p)
    peak = _peak_flops_per_chip()
    if flops and peak:
        r["flops_per_batch"] = round(flops)
        r["mfu"] = round((r["value"] / batch) * flops / peak, 4)
    return r


def _stage_breakdown() -> dict:
    """p50 ms of each pipeline stage's processing timer for the run."""
    from nnstreamer_tpu.core.log import metrics as _m

    snap = _m.snapshot()
    out = {}
    for name, v in snap.items():
        if name.endswith(".proc.p50") or name.endswith(".push.p50"):
            out[name.rsplit(".p50", 1)[0]] = round(v * 1e3, 2)
    return out


def _stats(lat, batch, batches, wall, metric, baseline_fps, unit,
           e2e=None):
    """``lat`` is per-batch SERVICE time (inter-completion gaps at steady
    state); ``e2e`` optionally carries push->pull round-trip times, which
    under deep pipelining include queue wait and are reported separately."""
    fps = batch * batches / wall
    lat_ms = sorted(x * 1e3 for x in lat)
    r = {
        "metric": metric,
        "value": round(fps, 1),
        "unit": unit,
        "vs_baseline": round(fps / baseline_fps, 3),
        "p50_batch_ms": round(lat_ms[len(lat_ms) // 2], 2),
        "p99_batch_ms": round(lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))], 2),
        "batch": batch,
        "batches": batches,
        "wall_s": round(wall, 3),
    }
    if e2e:
        e2e_ms = sorted(x * 1e3 for x in e2e)
        r["p50_e2e_ms"] = round(e2e_ms[len(e2e_ms) // 2], 2)
    return r


def _pipeline_bench(desc: str, make_frame, batch: int, batches: int,
                    warmup: int, metric: str, baseline_fps: float,
                    unit: str = "frames/sec", pulls_per_push: int = 1) -> dict:
    import nnstreamer_tpu as nt

    frames = [make_frame(i) for i in range(4)]
    push_ts = {}
    lat = []

    from nnstreamer_tpu.core.log import metrics as _metrics

    _metrics.reset()  # per-bench stage timers (global registry otherwise
    # accumulates across --config all runs and mixes pipelines)

    # Deep in-flight window: fused chains are ONE async stage, so queue
    # capacity bounds how many batches pipeline H2D/compute/D2H.  Keep total
    # pushed bytes modest — host->TPU links are burst-friendly; a short,
    # deeply-pipelined run measures the framework, not the transport's
    # sustained cap.
    p = nt.Pipeline(desc, fuse=True, queue_capacity=16)
    with p:
        for i in range(warmup):  # first push triggers XLA compile
            p.push("src", frames[i % len(frames)])
            for _ in range(pulls_per_push):
                p.pull("out", timeout=600)

        rtt_ms = _fetch_rtt_ms()  # in-session link probe (tail attribution)

        def pusher():
            for i in range(batches):
                # e2e clock starts at ADMISSION (push return): under an
                # infinite offered load the client-side wait to be
                # admitted is unbounded by Little's law whatever the
                # framework does — what max-inflight bounds (and what
                # this measures) is admission->delivery time INSIDE the
                # pipeline.  The pre-push write keeps the reader from
                # KeyErroring if delivery races the post-push overwrite
                # (it would read the conservative earlier stamp).
                push_ts[i] = time.perf_counter()
                p.push("src", frames[i % len(frames)])
                push_ts[i] = time.perf_counter()

        t = threading.Thread(target=pusher, daemon=True)
        t0 = time.perf_counter()
        t.start()
        e2e = []
        prev = None
        for i in range(batches):
            for _ in range(pulls_per_push):
                p.pull("out", timeout=600)
            now = time.perf_counter()
            if prev is not None:
                # Gap from the FIRST completion on: the initial pull includes
                # pipeline-fill latency, which is not a steady-state sample.
                lat.append(now - prev)
            e2e.append(now - push_ts[i])  # includes queue wait when pipelined
            prev = now
        t1 = time.perf_counter()
        t.join()
        p.eos()
        p.wait(timeout=60)

    wall = t1 - t0
    if not lat:  # --batches 1 leaves no steady-state gap; report the wall
        lat = [wall]
    r = _stats(lat, batch, batches, wall, metric, baseline_fps, unit,
               e2e=e2e)
    _add_mfu(r, p, batch)
    r["stages"] = _stage_breakdown()
    _attribute_rtt_tail(r, lat, rtt_ms)
    _attach_fetch_stats(r)
    return r


def bench_classification(batch: int, batches: int, size: int, warmup: int,
                         source: str = "videotestsrc") -> dict:
    """The stock image-classification example.  Default source is the
    TPU-native videotestsrc (pattern generated ON DEVICE, like the
    reference benchmarking against videotestsrc — zero H2D in the loop);
    --source appsrc feeds uint8 camera-style frames from the host instead,
    measuring the ingest transport along with the pipeline."""
    import numpy as np

    if source == "videotestsrc":
        total = _source_total_frames(batch, batches, warmup)
        desc = (
            f"videotestsrc device=true batch={batch} "
            f"num-buffers={total} width={size} height={size} name=src ! "
            "tensor_transform mode=arithmetic option=typecast:float32,add:-127.5,div:127.5 ! "
            f"tensor_filter framework=jax model=mobilenet_v1 custom=size:{size},batch:{batch} name=f ! "
            # Bounded sink queue: results must NOT pile up ahead of the
            # measuring pull loop, or the loop measures dequeue, not the
            # pipeline (backpressure holds the stages to steady state).
            f"tensor_decoder mode=image_labeling ! tensor_sink name=out max-buffers={_SOURCE_QUEUE_CAPACITY}"
        )
        return _source_driven_bench(
            desc, batch, batches, warmup,
            "mobilenet_v1_pipeline_fps_per_chip", 250.0, source,
        )
    rng = np.random.default_rng(0)
    # Host-fed ingest is bound by the H2D link once it saturates: deep
    # in-flight windows then only ADD latency (every queued batch waits
    # behind the ones ahead of it).  Bound
    # admission end-to-end (appsrc max-inflight) and keep batches small
    # enough that bound x batch-time stays interactive — throughput is
    # the link's either way.
    batch = min(batch, 64)
    # 4 = one batch in H2D flight + one computing + two resolving in the
    # sink's async fetch window (fetch_depth default 2): with ingress
    # donation reusing the steady-state device buffers and the window
    # overlapping D2H with the next dispatch, the old inflight=2 left the
    # link idle one service time per pull (the 57-rtt_stall row).  The
    # h2d/d2h wait split in the row shows where the remaining stalls live.
    inflight = 4
    desc = (
        f"appsrc name=src caps=other/tensors,dimensions=3:{size}:{size}:{batch},types=uint8 "
        f"max-inflight={inflight} ! "
        "tensor_transform mode=arithmetic option=typecast:float32,add:-127.5,div:127.5 ! "
        f"tensor_filter framework=jax model=mobilenet_v1 custom=size:{size},batch:{batch} name=f ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out"
    )
    r = _pipeline_bench(
        desc,
        lambda i: rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8),
        batch, batches, warmup,
        "mobilenet_v1_pipeline_fps_per_chip", 250.0,
    )
    r["source"] = source
    r["max_inflight"] = inflight
    return r


def _quant_mobilenet_file(size: int = 224, classes: int = 1001,
                          batch: int = 256) -> str:
    """Emit a fully-quantized MobileNet-v1-shaped .tflite (uint8
    activations, int8 per-axis weights, int32 biases — the reference's
    canonical ``mobilenet_v1_..._quant`` class, random weights standing
    in for the zero-egress checkpoint).  Runs through models/tflite.py's
    INTEGER execution: every conv/dw/fc hits the MXU as int8."""
    import os
    import tempfile

    import numpy as np

    from nnstreamer_tpu.models import tflite_build

    # v2 in the name: bump when this generator's topology/scales change,
    # or a stale cached file from an earlier code state gets benchmarked;
    # classes is part of the key for the same reason
    path = os.path.join(
        tempfile.gettempdir(),
        f"nnstpu_bench_mnq_v2_{size}_{batch}_{classes}.tflite")
    if os.path.exists(path):
        return path
    rng = np.random.default_rng(42)
    s_act, z_act = 0.05, 128

    m = tflite_build.ModelWriter()
    x = m.add_input([batch, size, size, 3], dtype=np.uint8,
                    quant_scale=[s_act], quant_zero_point=[z_act])

    def qconv(h, cin, cout, k, stride, hw, dw=False):
        if dw:
            w = rng.integers(-127, 128, (1, k, k, cin)).astype(np.int8)
            ax, nscale = 3, cin
            kind, fan = "DEPTHWISE_CONV_2D", k * k
        else:
            w = rng.integers(-127, 128, (cout, k, k, cin)).astype(np.int8)
            ax, nscale = 0, cout
            kind, fan = "CONV_2D", k * k * cin
        # unit-variance-ish dequantized weights keep activations in range
        sw = [2.0 / (127.0 * np.sqrt(fan))] * nscale
        wi = m.add_const(w, f"w{hw}_{cin}_{cout}", quant_scale=sw,
                         quant_zero_point=[0] * nscale, quant_axis=ax)
        bi = m.add_const(np.zeros((cout if not dw else cin,), np.int32),
                         f"b{hw}_{cin}_{cout}",
                         quant_scale=[s_act * sw[0]] * nscale,
                         quant_zero_point=[0] * nscale, quant_axis=0)
        oh = -(-hw // stride)
        return m.add_op(kind, [h, wi, bi],
                        [batch, oh, oh, cout if not dw else cin],
                        out_dtype=np.uint8,
                        options={"padding": "SAME",
                                 "stride": (stride, stride),
                                 "act": "relu6"},
                        quant_scale=[s_act], quant_zero_point=[z_act]), oh

    h, hw = qconv(x, 3, 32, 3, 2, size)
    cin = 32
    for cout, stride in ((64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                         (512, 2), (512, 1), (512, 1), (512, 1), (512, 1),
                         (512, 1), (1024, 2), (1024, 1)):
        h, hw = qconv(h, cin, cin, 3, stride, hw, dw=True)
        h, hw = qconv(h, cin, cout, 1, 1, hw)
        cin = cout
    axes = m.add_const(np.asarray([1, 2], np.int32), "mean_axes")
    h = m.add_op("MEAN", [h, axes], [batch, cin], out_dtype=np.uint8,
                 options={"keep_dims": False},
                 quant_scale=[s_act], quant_zero_point=[z_act])
    fw = rng.integers(-127, 128, (classes, cin)).astype(np.int8)
    fwi = m.add_const(fw, "fcw",
                      quant_scale=[2.0 / (127.0 * np.sqrt(cin))],
                      quant_zero_point=[0])
    fbi = m.add_const(np.zeros((classes,), np.int32), "fcb",
                      quant_scale=[s_act * 2.0 / (127.0 * np.sqrt(cin))],
                      quant_zero_point=[0])
    y = m.add_op("FULLY_CONNECTED", [h, fwi, fbi], [batch, classes],
                 out_dtype=np.uint8, options={"act": None},
                 quant_scale=[0.1], quant_zero_point=[128])
    with open(path, "wb") as f:
        f.write(m.finish(outputs=[y]))
    return path


def bench_classification_quant(batch: int, batches: int, size: int,
                               warmup: int) -> dict:
    """Quantized-classification row (VERDICT r4 Next #2 'done when'): a
    fully-quantized MobileNet-v1-shaped .tflite through the pipeline —
    uint8 frames into the filter behind an explicit dtype-boundary caps
    pin (the idiomatic way to pin the wire dtype at a quantized
    boundary), int8 MXU inside, logits dequantized and decoded on the
    way out.  The ISSUE 10 fusion-gap row: the caps pin and the
    dequant/decoder tail used to split the graph into THREE dispatch
    stages (0.2217 vs 0.247 MFU on the float twin of the same graph);
    the planner now fuses straight through the pin, so the whole front
    is ONE program — ``fused_stage`` carries the '+'-joined proof."""
    path = _quant_mobilenet_file(size, batch=batch)
    total = _source_total_frames(batch, batches, warmup)
    desc = (
        f"videotestsrc device=true batch={batch} num-buffers={total} "
        f"width={size} height={size} name=src ! "
        f"other/tensors,num_tensors=1,dimensions=3:{size}:{size}:{batch},"
        "types=uint8,format=static ! "
        f"tensor_filter framework=jax model={path} name=f ! "
        "tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-128.0,mul:0.1 name=deq ! "
        "tensor_decoder mode=image_labeling ! "
        f"tensor_sink name=out max-buffers={_SOURCE_QUEUE_CAPACITY}"
    )
    r = _source_driven_bench(
        desc, batch, batches, warmup,
        "mobilenet_v1_quant_pipeline_fps_per_chip", 250.0, "videotestsrc")
    r["int_exec"] = True
    r["fused_stage"] = max(
        (s.rsplit(".", 1)[0] for s in r.get("stages", {})),
        key=lambda s: s.count("+"), default="")
    return r


def _drain_batches() -> int:
    """Batches pulled (and discarded) before timing starts: must exceed the
    total queue slots across stages, or batches pre-computed during the
    first compile leak into the measured window."""
    return 4 * _SOURCE_QUEUE_CAPACITY + 8


def _source_total_frames(batch: int, batches: int, warmup: int) -> int:
    """num-buffers for a free-running source: warmup + drain + measured."""
    return (warmup + _drain_batches() + batches) * batch


def _source_driven_bench(desc: str, batch: int, batches: int, warmup: int,
                         metric: str, baseline_fps: float, source: str,
                         pulls_per_batch: int = 1) -> dict:
    """Benchmark a pipeline whose source free-runs (no app pushes): pull
    `batches` batch-buffers off the sink and measure wall time.  The
    caller builds desc with num-buffers=_source_total_frames(...) and this
    runner burns warmup+_drain_batches() pulls before timing.
    ``pulls_per_batch`` accounts for decoders that un-batch."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics

    _metrics.reset()  # per-bench stage timers
    p = nt.Pipeline(desc, fuse=True, queue_capacity=_SOURCE_QUEUE_CAPACITY)
    lat = []
    with p:
        # Link probe BEFORE the drain pulls: probing after would let the
        # free-running source refill the prefetch queue during the
        # ~5-RTT probe, leaking pre-computed batches into the measured
        # window (the exact hazard _drain_batches() guards against).
        rtt_ms = _fetch_rtt_ms()
        for _ in range((warmup + _drain_batches()) * pulls_per_batch):
            p.pull("out", timeout=600)  # compile + drain pre-buffered
        t0 = time.perf_counter()
        prev = t0
        for _ in range(batches):
            for _ in range(pulls_per_batch):
                p.pull("out", timeout=600)
            now = time.perf_counter()
            lat.append(now - prev)
            prev = now
        t1 = time.perf_counter()
        p.wait(timeout=120)
    wall = t1 - t0
    r = _stats(lat, batch, batches, wall, metric, baseline_fps, "frames/sec")
    r["source"] = source
    _add_mfu(r, p, batch)
    r["stages"] = _stage_breakdown()
    _attribute_rtt_tail(r, lat, rtt_ms)
    _attach_fetch_stats(r)
    if p.residency.reduced_outputs:
        r["reduced_outputs"] = list(p.residency.reduced_outputs)
    return r


def _attach_fetch_stats(r: dict) -> None:
    """Fetch-engine accounting (docs/FETCH.md): the h2d/d2h stall split
    (appsrc admission wait vs sink materialization wait — the two sides
    ``rtt_stalls`` used to conflate), the fetch time that OVERLAPPED
    pipeline work instead of blocking a pull, and the async fetch window
    depth.  Summed across elements from the run's metric snapshot."""
    from nnstreamer_tpu.core.log import metrics as _m

    snap = _m.snapshot()
    fields = {
        "h2d_wait_ms": "h2d_wait_ms", "rtt_stalls_h2d": "h2d_stalls",
        "d2h_wait_ms": "d2h_wait_ms", "rtt_stalls_d2h": "d2h_stalls",
        "fetch_overlap_ms": "fetch_overlap_ms",
    }
    for out_key, metric in fields.items():
        total = sum(v for k, v in snap.items()
                    if k.endswith("." + metric))
        r[out_key] = round(total, 1)
    depth = max((v for k, v in snap.items()
                 if k.endswith(".fetch_window_peak")), default=0.0)
    r["fetch_window_depth"] = int(depth)


def _attribute_rtt_tail(r: dict, lat, rtt_ms: float) -> None:
    """Attribute the latency tail (VERDICT r4 Weak #5): the
    consumer periodically drains the sink's prefetch
    queue and one pull waits a REAL fetch roundtrip — a link event, not
    device work.  A stall is a sample at least half an RTT ABOVE the
    median service time (an absolute 0.5*RTT cut would flag 100% of
    samples on any config whose steady-state step exceeds it), so a
    p99 ~= p50 + fetch_rtt_ms is self-evidencing against the same
    session's link."""
    import numpy as np

    p50_ms = float(np.percentile(lat, 50)) * 1e3 if lat else 0.0
    cut_ms = p50_ms + 0.5 * rtt_ms
    stalls = [l for l in lat if l * 1e3 > cut_ms]
    r["fetch_rtt_ms"] = round(rtt_ms, 2)
    r["rtt_stalls"] = len(stalls)
    r["rtt_stall_ms_total"] = round(sum(stalls) * 1e3, 1)


def _fetch_rtt_ms() -> float:
    """Median small-fetch roundtrip to the device (the quantum a pull
    pays whenever it catches the prefetcher; measured by fetching
    bytes).  Single
    source of truth lives in tools/_chiptime.py — bench runs from the
    repo root, where `tools` is importable."""
    from tools._chiptime import fetch_rtt_s

    return fetch_rtt_s(force=True) * 1e3


def bench_detection(batch: int, batches: int, size: int, warmup: int,
                    model: str = "ssd_mobilenet") -> dict:
    """Config #2 names both SSD-MobileNet AND YOLOv5; ``model`` selects
    (all drive the same bounding_boxes decode, yolo via option1).
    ``yolov5s`` is the REAL-geometry CSP detector (~17 GF/frame @640,
    models/yolo.py apply_v5s) and runs at 640x640 / batch 32 by default;
    the plain ``yolov5`` name is the toy-backbone stand-in kept for cheap
    tests (its row is labeled _toy)."""
    if model == "yolov5s":
        if size is None:  # unset: real geometry means 640
            size = 640
        # 64 measured best (r5): MFU 0.199 model-only vs 0.172 at 32;
        # the [B,25200,96] f32 head transient bounds HBM above that
        batch = min(batch, 64)
    size = size or 224
    total = _source_total_frames(batch, batches, warmup)
    fmt = ("yolov5" if model in ("yolov5", "yolov5s")
           else model if model == "yolov8" else "ssd")
    # input convention per family: SSD-mobilenet [-1,1]; YOLO [0,1]
    norm = ("typecast:float32,div:255.0" if fmt != "ssd"
            else "typecast:float32,add:-127.5,div:127.5")
    desc = (
        f"videotestsrc device=true batch={batch} num-buffers={total} "
        f"width={size} height={size} pattern=ball name=src ! "
        f"tensor_transform mode=arithmetic option={norm} ! "
        f"tensor_filter framework=jax model={model} custom=size:{size},classes:91,batch:{batch} name=f ! "
        f"tensor_decoder mode=bounding_boxes option1={fmt} option3=0.5 "
        f"option4={size}:{size} option6=16 option7=device option9=tensors ! "
        f"tensor_sink name=out max-buffers={_SOURCE_QUEUE_CAPACITY}"
    )
    # option6=16: the synthetic scene holds <=2 objects; 16 kept rows
    # bound the per-frame D2H payload honestly (the [B,M,7] packed
    # payload is what crosses D2H per batch)
    # option7=device fuses threshold + greedy NMS into the XLA program
    # (ops/nms.nms_jax); option9=tensors ships the final detections as
    # tensors with NO host canvas — the classification recipe (indices,
    # not payloads) applied to detection.  The overlay path stays golden-
    # tested; this measures the headless serving contract.
    label = model + ("_toy" if model in ("yolov5", "yolov8") else "")
    r = _source_driven_bench(
        desc, batch, batches, warmup,
        f"{label}_detection_fps_per_chip", 250.0, "videotestsrc",
    )
    r["decode_output"] = "tensors"
    r["input_size"] = size
    return r


def _bench_llm_continuous(p, rng, max_new: int, prompt_len: int,
                          streams: int, model: str, quant: str,
                          shared_prefix: int = 0, draft: str = "",
                          spec_k: int = 4,
                          temperature: float = 0.0) -> dict:
    """Continuous batching: stagger ``streams`` prompts into the RUNNING
    decode loop; report aggregate tokens/sec plus the late joiner's
    first-token latency (the metric continuous batching exists for —
    a static group would hold it until the whole running group ends).

    Token accounting uses the serve loop's per-token ``emit_t`` meta, not
    pull times: tokens queue at the sink while a pull blocks, so wall
    clocks around pulls would count tokens generated outside the window.
    The late joiner's first token is identified by stream identity (the
    SECOND buffer arriving with stream_index 0), not by pull order —
    stream 0's whole first chunk precedes the joiner's admission."""
    import numpy as np

    import nnstreamer_tpu as nt

    def tagged(base):  # distinguishes streams at the shared sink
        b = nt.Buffer([base])
        b.meta["bench_stream"] = tagged.n
        tagged.n += 1
        return b
    tagged.n = 0

    # prefix-sharing rows: every prompt = one shared preamble + its own
    # suffix (docs/SERVING.md §4b) — joiners after stream 0's prefill
    # hit the prefix cache, so their admission reservation and
    # first-token prefill collapse to ~the suffix
    pre = (rng.integers(1, 400, (shared_prefix,), dtype=np.int32)
           if shared_prefix else None)

    def prompt():
        suf = rng.integers(1, 400, (prompt_len,), dtype=np.int32)
        return suf if pre is None else np.concatenate([pre, suf])

    from nnstreamer_tpu.core.log import metrics as _metrics
    snap0 = _metrics.snapshot()

    with p:
        p.push("src", tagged(prompt()))
        first = p.pull("out", timeout=2100)  # stream 0 live (+compile)
        t_join = time.monotonic()
        p.push("src", tagged(prompt()))
        for _ in range(streams - 2):
            p.push("src", tagged(prompt()))
        total = streams * max_new - 1
        bufs = [p.pull("out", timeout=900) for _ in range(total)]
        p.eos()
        p.wait(timeout=120)
    join = next(b for b in bufs
                if b.meta["bench_stream"] == 1
                and b.meta["stream_index"] == 0)
    join_ms = (join.meta["emit_t"] - t_join) * 1e3
    # generation-window throughput: emission timestamps of every token
    # after stream 0's first (which carries compile + weight gen)
    emits = sorted(b.meta["emit_t"] for b in bufs)
    wall = emits[-1] - first.meta["emit_t"]
    tps = len(emits) / wall
    # Full-occupancy rate: the window where every slot is live (last
    # stream's first token -> first stream's last token).  The headline
    # window necessarily includes the stagger ramp (stream 0 decoding
    # alone until the joiners land), which is the SCENARIO's shape, not
    # the loop's ceiling — this field isolates the loop.
    firsts, lasts = {}, {}
    for b in [first] + bufs:
        s = b.meta["bench_stream"]
        t = b.meta["emit_t"]
        firsts[s] = min(firsts.get(s, t), t)
        lasts[s] = max(lasts.get(s, t), t)
    lo, hi = max(firsts.values()), min(lasts.values())
    occ = [b for b in [first] + bufs if lo <= b.meta["emit_t"] <= hi]
    occ_tps = (len(occ) - 1) / (hi - lo) if hi > lo and len(occ) > 1 else 0.0
    # Late-join decomposition: a joiner waits for the RUNNING chunk to
    # finish (admission is quantized to chunk boundaries), pays its own
    # bucketed prefill, and its first token crosses the link once — so
    # join_ms ~= chunk_ms + prefill + fetch RTT.  Carrying the session's
    # measured RTT and chunk time makes a slow link's inflated
    # join latency self-evidencing (VERDICT r4 Next #3 honesty clause).
    chunk_ms = 0.0
    s0 = sorted(b.meta["emit_t"] for b in [first] + bufs
                if b.meta["bench_stream"] == 0)
    if len(s0) > 9:
        # stream 0's first two chunk boundaries (chunk tokens emit
        # together; the gap between bursts is one chunk's decode time)
        gaps = np.diff(np.asarray(s0[:17]))
        chunk_ms = float(np.max(gaps)) * 1e3
    row = {
        "metric": (f"{model}_{quant or 'bf16'}_continuous_tokens_per_sec"
                   f"_{streams}_streams"
                   + (f"_prefix{shared_prefix}" if shared_prefix else "")
                   + (f"_spec_k{spec_k}" if draft else "")
                   + ("_sampled" if temperature > 0.0 else "")),
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / 20.0, 3),
        "streams": streams,
        "max_new": max_new,
        "late_join_first_token_ms": round(join_ms, 1),
        "decode_chunk_ms": round(chunk_ms, 1),
        "fetch_rtt_ms": round(_fetch_rtt_ms(), 2),
        "full_occupancy_tokens_per_sec": round(occ_tps, 1),
        "wall_s": round(wall, 3),
    }
    if temperature > 0.0:
        row["temperature"] = temperature
    snap1 = _metrics.snapshot()

    def delta(name):
        return snap1.get(name, 0.0) - snap0.get(name, 0.0)

    if shared_prefix:
        row["shared_prefix"] = shared_prefix
        row["prefix_hits"] = int(delta("llm.serve.prefix_hits"))
        row["prefix_hit_blocks"] = int(delta("llm.serve.prefix_hit_blocks"))
        row["cow_forks"] = int(delta("llm.serve.cow_forks"))
    if draft:
        acc = delta("llm.serve.spec_accepted")
        rej = delta("llm.serve.spec_rejected")
        row["spec_draft"] = draft
        row["spec_k"] = spec_k
        row["spec_accept_rate"] = round(acc / (acc + rej), 3) \
            if acc + rej else 0.0
        row["spec_rounds"] = int(delta("llm.serve.spec_rounds"))
    return row


def bench_segmentation(batch: int, batches: int, size: int,
                       warmup: int, native: bool = False) -> dict:
    """Segmentation family: deeplab + fused image_segment decode (device
    argmax -> u8 class ids; 1 byte/pixel D2H, no host palette gather —
    the wav2vec2 decode-on-edge treatment; overlay compositing stays
    golden-tested and runs only where something displays it).

    The full-res row is bound by D2H bandwidth wherever the link is
    slower than the program: the u8
    map is already the minimal full-resolution payload (H*W bytes/frame),
    so fps ~= link_bw / (H*W) regardless of compute — the per-stage
    breakdown in the row shows it.  ``native=True`` ships the class map
    at the model's output stride instead (custom=upsample:0, 256x smaller
    — full res is only a bilinear blow-up of this decision), which is the
    link-bound serving shape.
    """
    total = _source_total_frames(batch, batches, warmup)
    up = ",upsample:0" if native else ""
    desc = (
        f"videotestsrc device=true batch={batch} num-buffers={total} "
        f"width={size} height={size} pattern=smpte name=src ! "
        "tensor_transform mode=arithmetic option=typecast:float32,div:255.0 ! "
        f"tensor_filter framework=jax model=deeplab_mobilenet "
        f"custom=size:{size},batch:{batch}{up} name=f ! "
        f"tensor_decoder mode=image_segment option1=classmap ! "
        f"tensor_sink name=out max-buffers={_SOURCE_QUEUE_CAPACITY}"
    )
    metric = ("deeplab_segmentation_native_stride_fps_per_chip"
              if native else "deeplab_segmentation_fps_per_chip")
    r = _source_driven_bench(
        desc, batch, batches, warmup, metric, 250.0, "videotestsrc",
    )
    r["decode_output"] = "classmap" + ("_native_stride" if native else "")
    return r


def bench_pose(batch: int, batches: int, size: int, warmup: int) -> dict:
    total = _source_total_frames(batch, batches, warmup)
    desc = (
        f"videotestsrc device=true batch={batch} num-buffers={total} "
        f"width={size} height={size} pattern=ball name=src ! "
        "tensor_transform mode=arithmetic option=typecast:float32,div:255.0 ! "
        f"tensor_filter framework=jax model=posenet custom=size:{size},batch:{batch} name=f ! "
        f"tensor_decoder mode=pose_estimation option2={size}:{size} "
        f"option3=0.3 option4=tensors ! "
        f"tensor_sink name=out max-buffers={_SOURCE_QUEUE_CAPACITY}"
    )
    # option4=tensors: keypoint coordinates cross the sink edge (O(B*K)
    # floats), not skeleton canvases (O(B*H*W) pixels) — host-work
    # elimination per the classification recipe.
    r = _source_driven_bench(
        desc, batch, batches, warmup,
        "posenet_pipeline_fps_per_chip", 250.0, "videotestsrc",
    )
    r["decode_output"] = "tensors"
    return r


def bench_audio(batch: int, batches: int, warmup: int,
                source: str = "audiotestsrc",
                model: str = "speech_commands") -> dict:
    """Config #4 names both speech-command AND wav2vec2; ``model`` selects
    (wav2vec2 emits per-frame vocab logits via flexible output)."""
    import numpy as np

    samples = 16000  # 1s windows @16kHz
    mopts = f"dtype:float32,batch:{batch}"
    if model == "wav2vec2":
        mopts += f",samples:{samples}"
    # wav2vec2 decodes on-edge: mode=ctc fuses a device argmax into the
    # same XLA program, so D2H is [B,T] ids, not [B,T,vocab] logits
    # (a vocab-times-larger D2H fetch per buffer).
    dec = "tensor_decoder mode=ctc ! " if model == "wav2vec2" else ""
    if source == "audiotestsrc":
        # Device-generated windows (the audio analog of the videotestsrc
        # device source): zero H2D in the loop, measures the pipeline.
        total = _source_total_frames(batch, batches, warmup)
        desc = (
            f"audiotestsrc device=true batch={batch} num-buffers={total} "
            f"samplesperbuffer={samples} rate=16000 name=src ! "
            f"tensor_filter framework=jax model={model} "
            f"custom={mopts} name=f ! {dec}"
            f"tensor_sink name=out max-buffers={_SOURCE_QUEUE_CAPACITY}"
        )
        r = _source_driven_bench(
            desc, batch, batches, warmup,
            f"{model}_windows_per_sec_per_chip", 250.0, source,
        )
        r["unit"] = "windows/sec"
        return r
    rng = np.random.default_rng(0)
    desc = (
        f"appsrc name=src caps=other/tensors,dimensions={samples}:{batch},types=float32 ! "
        f"tensor_filter framework=jax model={model} custom={mopts} name=f ! "
        f"{dec}tensor_sink name=out"
    )
    r = _pipeline_bench(
        desc,
        lambda i: rng.standard_normal((batch, samples)).astype(np.float32),
        batch, batches, warmup,
        f"{model}_windows_per_sec_per_chip", 250.0,
        unit="windows/sec",
    )
    r["source"] = source
    return r


def _text_vocab_file(model: str) -> str:
    """Emit a .gguf carrying a SentencePiece vocab sized to ``model``'s
    embedding table (specials + byte fallback + ASCII chars + merge
    pieces, padded to the model vocab) — the text-path bench tokenizes
    through the same models/tokenizer.py machinery a real checkpoint's
    embedded vocab uses."""
    import os
    import tempfile

    import numpy as np

    from nnstreamer_tpu.models import gguf as _gguf
    from nnstreamer_tpu.models import llama as _llama
    from nnstreamer_tpu.models.tokenizer import toy_vocab

    vs = (_llama.PRESETS[model].vocab if model in _llama.PRESETS
          else 32000)
    merges = {"th": -1.0, "▁th": -0.9, "▁the": -0.4, "qu": -1.2,
              "ick": -1.1, "▁qu": -1.0, "▁quick": -0.5, "ox": -1.3,
              "▁f": -1.6, "▁fox": -0.6, "er": -0.9, "ov": -1.4,
              "▁ov": -1.2, "▁over": -0.7, "mp": -1.5, "ju": -1.4,
              "▁ju": -1.3, "▁jump": -0.8, "▁jumps": -0.7}
    tok = toy_vocab(merges)
    pad = vs - tok.n_vocab
    tok = toy_vocab(merges, n_normal_pad=max(0, pad))
    path = os.path.join(tempfile.gettempdir(),
                        f"nnstpu_bench_vocab_{model}.gguf")
    meta = {"general.architecture": "llama"}
    meta.update(tok.to_gguf_meta())
    _gguf.write(path, meta, {"pad": np.zeros((1,), np.float32)})
    return path


def bench_llm(batches: int, warmup: int, model: str = "llama_small",
              max_new: int | None = None, prompt_len: int = 32,
              quant: str = "", streams: int = 1,
              serve: str = "", text: bool = False,
              shared_prefix: int = 0, draft: str = "",
              spec_k: int = 4, temperature: float = 0.0) -> dict:
    """Config #5: tokens/sec through the llm filter (jitted prefill +
    lax.scan decode).  vs_baseline compares against the reference's
    llama.cpp CPU path order of magnitude (~20 tok/s).

    ``model=llama2_7b`` runs the REAL 7B shape: weights generated directly
    in bfloat16 on device (13.5 GB — fits one v5e chip; zero-egress stands
    in for a checkpoint upload), max_seq capped to bound the KV cache, and
    a wide stream chunk so the per-chunk fetch roundtrip amortizes over
    the lax.scan.
    """
    import numpy as np

    import nnstreamer_tpu as nt

    rng = np.random.default_rng(0)
    if (shared_prefix or draft or temperature > 0.0) \
            and serve != "continuous":
        # these rows only exist on the serve loop; silently dropping the
        # flags would record a mislabeled plain-decode artifact
        raise SystemExit("--llm-prefix/--llm-draft/--llm-temperature "
                         "require --llm-serve continuous")
    if max_new is None:
        # continuous default decodes longer so the steady full-occupancy
        # phase dominates the stagger ramp in the headline window (the
        # ramp is the scenario's shape; full_occupancy_tokens_per_sec
        # isolates it); an EXPLICIT max_new is always honored
        max_new = 128 if serve == "continuous" else 64
    custom = f"max_new:{max_new}"
    if model == "llama2_7b":
        # Multi-stream: the KV cache scales with streams (bf16 rows x
        # max_seq x B) AND XLA materializes layout-change copies of it,
        # so size it to the workload — 8 streams at max_seq:1024 blew a
        # 16 GB chip's HBM by 0.2 GB on the cache copies alone.
        max_seq = (1024 if streams == 1 and serve != "continuous"
                   else max(256, 1 << (shared_prefix + prompt_len
                                       + max_new).bit_length()))
        # continuous serving shortens the chunk: admission is quantized
        # to chunk boundaries, so 8 tokens (~150 ms at 7B int8) bounds a
        # late joiner's wait while the per-chunk roundtrip overhead stays
        # a few percent.  Static modes cover max_new in ONE chunk — the
        # decode is a single lax.scan roundtrip, so the fetch RTT is paid
        # once, not per 32 tokens.
        chunk = 8 if serve == "continuous" else max(32, max_new)
        custom += (f",param_dtype:bfloat16,max_seq:{max_seq},"
                   f"stream_chunk:{chunk}")
    if quant:
        # weight-only int8: halves HBM bytes/token on the decode step
        custom += f",quant:{quant}"
    if text:
        # REAL tokenizer in the loop: SentencePiece encode on the prompt,
        # per-piece decode on every emitted token (stop_eos:0 keeps the
        # token count fixed — random weights sampling the eos id early
        # would shrink the measured window, not the per-token rate)
        custom += f",tokenizer:{_text_vocab_file(model)},stop_eos:0"
    n_streams = max(2, streams)
    if serve == "continuous":
        # admission granularity = one chunk; slots sized to the stream mix.
        # The paged-KV pool is sized to the WORKLOAD, not the worst case:
        # every stream reserves ceil((T + max_new) / block_size) blocks at
        # admission, so this pool admits all slots concurrently while a
        # max_seq-worst-case pool at x64 would hold ~1.6x the HBM for
        # rows no stream can ever write.
        block_size = 16
        full_len = shared_prefix + prompt_len
        need = -(-(full_len + max_new) // block_size)
        custom += (f",serve:continuous,slots:{n_streams}"
                   f",block_size:{block_size}"
                   f",kv_blocks:{n_streams * need}")
        if draft:
            # speculative decoding (docs/SERVING.md §4c): preset draft
            # priced beside the target.  temperature 0 = greedy accept
            # (bit-identical stream); >0 = rejection sampling (§4d) —
            # SAME fused verify program, accept math swaps in-body.
            custom += f",draft:{draft},spec_k:{spec_k}," \
                      f"temperature:{temperature}"
        elif temperature > 0.0:
            # sampled serve row (docs/SERVING.md §4d): per-slot seeded
            # PRNG rides the standing loop — same program census
            custom += f",temperature:{temperature}"
    # invoke-dynamic only for the continuous path: the committed static
    # rows were measured without it, and it must stay that way so this
    # commit reproduces the artifact's exact pipelines.  The '!' before
    # the sink stays OUTSIDE the conditional: interpolating it with the
    # option left the static pipelines with an UNLINKED sink (the parser
    # reads bare juxtaposition as a new gst-launch chain), which hung
    # every static llm row's first pull in the r4 sweeps until the
    # runtime learned to reject inputless non-sources at construction.
    dyn = "invoke-dynamic=true " if serve == "continuous" else ""
    desc = (
        "appsrc name=src ! "
        f"tensor_filter framework=llm model={model} custom={custom} "
        f"{dyn}! tensor_sink name=out"
    )
    p = nt.Pipeline(desc)
    if serve == "continuous":
        return _bench_llm_continuous(p, rng, max_new, prompt_len,
                                     n_streams, model, quant,
                                     shared_prefix=shared_prefix,
                                     draft=draft, spec_k=spec_k,
                                     temperature=temperature)
    toks = 0
    with p:
        # streams>1: N concurrent prompts decode in ONE lax.scan loop.
        # The decode step is weight-bandwidth-bound (the full parameter
        # set streams through the MXU once per step regardless of B), so
        # aggregate tokens/sec scales nearly linearly with streams —
        # the TPU-native serving win the per-request reference can't make.
        if text:
            if streams != 1:
                raise SystemExit("--llm-text measures the single-stream "
                                 "text contract (streams must be 1)")
            words = b"the quick brown fox jumps over the lazy dog "
            prompt = np.frombuffer(
                (words * (prompt_len // 8 + 1))[:prompt_len * 4], np.uint8)
        else:
            prompt = rng.integers(1, 400, (streams, prompt_len),
                                  dtype=np.int32)
        for _ in range(warmup):
            p.push("src", prompt)
            for _ in range(max_new):
                # generous: the FIRST pull carries device weight gen +
                # the scan-program compile
                p.pull("out", timeout=2100)
        t0 = time.perf_counter()
        for _ in range(batches):
            p.push("src", prompt)
            for _ in range(max_new):
                p.pull("out", timeout=900)
                toks += 1
        wall = time.perf_counter() - t0
        p.eos()
        p.wait(timeout=60)
    tps = toks * streams / wall
    return {
        "metric": (f"{model}_{quant}_tokens_per_sec_per_chip" if quant
                   else f"{model}_tokens_per_sec_per_chip")
                  + (f"_x{streams}_streams" if streams > 1 else "")
                  + ("_text" if text else ""),
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / 20.0, 3),
        "max_new": max_new,
        "prompt_len": prompt_len,
        "wall_s": round(wall, 3),
    }


def bench_prefix_spec(batches: int, warmup: int,
                      model: str = "llama_small",
                      prefix_len: int = 512, suffix_len: int = 8,
                      spec_k: int = 4) -> dict:
    """ISSUE 15 A/B: prefix-sharing admission-to-first-token + the
    speculative-decoding round structure (docs/SERVING.md §4b/§4c).

    Arm 1 (prefix): serial shared-prefix streams against a warm
    continuous loop, ``prefix_cache:1`` vs ``prefix_cache:0`` — the
    cache-hit arm prefills only the non-shared suffix, so
    admission-to-first-token collapses (the ≥5x tentpole target; this
    IS visible on the CPU proxy, where prefill chunks are real compute).

    Arm 2 (speculation): decode tok/s with ``draft:<same preset>``
    (identical params → accept rate 1, the trained-draft agreement
    CEILING) vs plain decode.  The CPU proxy can NOT show the silicon
    win: the same-preset draft's propose steps cost exactly one
    target-step each here, while on silicon the row's real draft
    (llama_tiny vs 7B int8, llm7b_spec_k4) reads ~0.2% of the target's
    HBM bytes per step — the roofline projection
    ``(accept*k + 1) / (1 + k*draft_cost_ratio)`` rides the row
    (BENCH_LEARN_r01 precedent: proxy number + silicon rationale)."""
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics
    from nnstreamer_tpu.models import llama as _llama

    rng = np.random.default_rng(0)
    max_new = 16
    base = (f"max_new:{max_new},serve:continuous,slots:2,stream_chunk:2,"
            f"temperature:0.0,block_size:16,prefill_chunk:32,kv_blocks:0")
    pre = rng.integers(1, 400, (prefix_len,), dtype=np.int32)

    def admission_ms(prefix_cache: int) -> float:
        desc = ("appsrc name=src ! "
                f"tensor_filter framework=llm model={model} "
                f"custom={base},prefix_cache:{prefix_cache} "
                "invoke-dynamic=true ! tensor_sink name=out")
        lat = []
        with nt.Pipeline(desc) as p:
            # stream 0: compile warm-up + (hit arm) cache population
            p.push("src", np.concatenate(
                [pre, rng.integers(1, 400, (suffix_len,), np.int32)]))
            for _ in range(max_new):
                p.pull("out", timeout=2100)
            for i in range(warmup + batches):
                prompt = np.concatenate(
                    [pre, rng.integers(1, 400, (suffix_len,), np.int32)])
                t0 = time.monotonic()
                p.push("src", prompt)
                bufs = [p.pull("out", timeout=900)
                        for _ in range(max_new)]
                if i >= warmup:
                    first = next(b for b in bufs
                                 if b.meta["stream_index"] == 0)
                    lat.append((first.meta["emit_t"] - t0) * 1e3)
            p.eos()
            p.wait(timeout=60)
        lat.sort()
        return lat[len(lat) // 2]

    hit_ms = admission_ms(1)
    cold_ms = admission_ms(0)

    # -- arm 2: speculation round structure --------------------------------
    spec_new, streams, plen = 64, 2, 12

    def decode_tps(spec: bool) -> tuple:
        extra = f",draft:{model},spec_k:{spec_k}" if spec else ""
        desc = ("appsrc name=src ! "
                f"tensor_filter framework=llm model={model} "
                f"custom=max_new:{spec_new},serve:continuous,slots:"
                f"{streams},stream_chunk:4,temperature:0.0,block_size:16,"
                f"kv_blocks:0,prefix_cache:0{extra} "
                "invoke-dynamic=true ! tensor_sink name=out")
        a0 = _metrics.snapshot().get("llm.serve.spec_accepted", 0.0)
        r0 = _metrics.snapshot().get("llm.serve.spec_rejected", 0.0)
        with nt.Pipeline(desc) as p:
            p.push("src", rng.integers(1, 400, (plen,), np.int32))
            first = p.pull("out", timeout=2100)  # compile + stream 0 live
            for _ in range(streams - 1):
                p.push("src", rng.integers(1, 400, (plen,), np.int32))
            bufs = [p.pull("out", timeout=900)
                    for _ in range(streams * spec_new - 1)]
            p.eos()
            p.wait(timeout=60)
        emits = sorted(b.meta["emit_t"] for b in bufs)
        wall = emits[-1] - first.meta["emit_t"]
        snap = _metrics.snapshot()
        acc = snap.get("llm.serve.spec_accepted", 0.0) - a0
        rej = snap.get("llm.serve.spec_rejected", 0.0) - r0
        rate = acc / (acc + rej) if acc + rej else 0.0
        return len(emits) / wall, rate

    plain_tps, _ = decode_tps(False)
    spec_tps, accept_rate = decode_tps(True)

    # silicon roofline projection for the REAL row (llm7b_spec_k4:
    # llama_tiny draft against the int8 7B target): per decode step the
    # draft reads its own params, the target reads quantized params —
    # cost ratio c from the same estimates serving_plan() prices
    tiny = _llama.PRESETS["llama_tiny"]
    big = _llama.PRESETS["llama2_7b"]
    c = (_llama.param_bytes_estimate(tiny, param_dtype="float32")
         / _llama.param_bytes_estimate(big, quant="int8",
                                       param_dtype="bfloat16"))
    projected = {
        f"accept_{int(a * 100)}": round((a * spec_k + 1)
                                        / (1 + spec_k * c), 2)
        for a in (0.5, 0.7, 0.9)}

    speedup = cold_ms / hit_ms if hit_ms else 0.0
    return {
        "metric": f"{model}_prefix_hit_admission_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / 5.0, 3),  # the ≥5x tentpole bar
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "admission_first_token_hit_ms": round(hit_ms, 1),
        "admission_first_token_cold_ms": round(cold_ms, 1),
        "spec_tokens_per_sec": round(spec_tps, 1),
        "plain_tokens_per_sec": round(plain_tps, 1),
        "spec_speedup_vs_plain": round(spec_tps / plain_tps, 3)
        if plain_tps else 0.0,
        "spec_k": spec_k,
        "spec_accept_rate": round(accept_rate, 3),
        "spec_draft_cost_ratio_7b_int8": round(c, 4),
        "spec_projected_speedup_7b": projected,
        "spec_proxy_caveat": (
            "same-preset draft on the CPU proxy: every propose step "
            "costs one full target step, so the measured ratio is the "
            "structural floor — the silicon row (llm7b_spec_k4, "
            "llama_tiny draft vs int8 7B) pays ~{:.2%} of the target's "
            "HBM bytes per draft step; projection = "
            "(accept*k+1)/(1+k*cost_ratio)".format(c)),
    }


def bench_gqa_sampling(batches: int, warmup: int,
                       model: str = "llama_tiny",
                       spec_k: int = 3) -> dict:
    """ISSUE 16 A/B: the three decode hot-loop changes in one row —
    grouped-GQA kernel traffic, the fused speculative verify's host
    transfer budget, and the sampled serve loop's overhead.

    Arm 1 (kernel): flash attention on the SAME [B,S,Hkv,D] K/V fed
    grouped vs pre-repeated to [B,S,H,D].  On the CPU proxy the Pallas
    kernel runs interpreted and per-call trace overhead dominates the
    wall (measured ratio ~1x despite the repeated layout running
    H/Hkv x the grid) — the A/B here only pins that grouped is never
    SLOWER; the silicon claim rides the projection below, which is pure
    ``serving_plan`` arithmetic (decode K/V bytes scale with n_kv_heads,
    tests/test_kernels_gqa.py pins the kernel's DMA structure).

    Arm 2 (sampling): continuous-serve tokens/sec at temperature 0.9 vs
    greedy on identical prompts — the per-slot seeded sampler
    (docs/SERVING.md §4d) compiles into the standing decode program, so
    its cost is a few fused element-wise ops per step, not a program
    swap.  The tiny CPU preset EXAGGERATES the sampler's share (its
    model step is microseconds; sort/cumsum over the vocab is
    comparable) — the silicon delta is llm7b_sampled_x32 vs
    llm7b_int8_continuous_x32, where the 7B step dwarfs it.

    Arm 3 (fused verify): sampled speculative serve (rejection
    sampling through the SAME fused [slots, k+1] verify program), plus
    the per-round host-transfer ledger the fusion buys: the loop now
    downloads exactly the emitted rows + accept counts, where the
    unfused round also shipped the proposals down and the tok/tok_prev
    state back up (tests/test_sampling.py pins proposals-never-leave).

    Silicon projection: llama2_7b at n_kv_heads 8 (the production 70B
    GQA geometry on the 7B shape) vs its stock 32 at int8 weights,
    32 streams x 1024 live context tokens — decode is HBM-roofline
    bound (ROADMAP S3), so projected tok/s scales with
    step bytes: (params + kv_mha) / (params + kv_gqa)."""
    import dataclasses

    import numpy as np

    import jax
    import jax.numpy as jnp

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics
    from nnstreamer_tpu.filters.llm import serving_plan
    from nnstreamer_tpu.models import llama as _llama
    from nnstreamer_tpu.ops import attention as _att

    rng = np.random.default_rng(0)
    on_cpu = jax.default_backend() == "cpu"

    # -- arm 1: grouped vs repeated kernel ---------------------------------
    b, s, h, hkv, d = 1, 256, 8, 2, 32
    kk = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kk[0], (b, s, h, d), jnp.float32)
    kt = jax.random.normal(kk[1], (b, s, hkv, d), jnp.float32)
    vt = jax.random.normal(kk[2], (b, s, hkv, d), jnp.float32)
    krep = jnp.repeat(kt, h // hkv, axis=2)
    vrep = jnp.repeat(vt, h // hkv, axis=2)

    def kernel_ms(kx, vx) -> float:
        def once():
            jax.block_until_ready(_att.flash_attention(
                q, kx, vx, causal=True, block_q=64, block_k=64,
                interpret=on_cpu or None))
        once()  # trace/compile warm-up
        reps = max(2, min(batches, 4 if on_cpu else 32))
        t0 = time.perf_counter()
        for _ in range(reps):
            once()
        return (time.perf_counter() - t0) / reps * 1e3

    grouped_ms = kernel_ms(kt, vt)
    repeated_ms = kernel_ms(krep, vrep)

    # -- arms 2+3: serve-loop tok/s (greedy / sampled / sampled spec) ------
    max_new, streams, plen = 32, 2, 12

    def serve_tps(temp: float, spec: bool) -> tuple:
        extra = f",draft:{model},spec_k:{spec_k}" if spec else ""
        desc = ("appsrc name=src ! "
                f"tensor_filter framework=llm model={model} "
                f"custom=max_new:{max_new},serve:continuous,slots:"
                f"{streams},stream_chunk:4,temperature:{temp},seed:3,"
                f"block_size:16,kv_blocks:0,prefix_cache:0{extra} "
                "invoke-dynamic=true ! tensor_sink name=out")
        a0 = _metrics.snapshot().get("llm.serve.spec_accepted", 0.0)
        r0 = _metrics.snapshot().get("llm.serve.spec_rejected", 0.0)
        with nt.Pipeline(desc) as p:
            p.push("src", rng.integers(1, 400, (plen,), np.int32))
            first = p.pull("out", timeout=2100)  # compile + stream 0 live
            for _ in range(streams - 1):
                p.push("src", rng.integers(1, 400, (plen,), np.int32))
            bufs = [p.pull("out", timeout=900)
                    for _ in range(streams * max_new - 1)]
            p.eos()
            p.wait(timeout=60)
        emits = sorted(bf.meta["emit_t"] for bf in bufs)
        wall = emits[-1] - first.meta["emit_t"]
        snap = _metrics.snapshot()
        acc = snap.get("llm.serve.spec_accepted", 0.0) - a0
        rej = snap.get("llm.serve.spec_rejected", 0.0) - r0
        rate = acc / (acc + rej) if acc + rej else 0.0
        return len(emits) / wall, rate

    greedy_tps, _ = serve_tps(0.0, False)
    sampled_tps, _ = serve_tps(0.9, False)
    spec_tps, accept_rate = serve_tps(0.9, True)

    # fused-verify host ledger, per round at [slots, k+1] (int32):
    # fused = emitted rows + accept counts; the unfused structure also
    # downloaded the k proposals and re-uploaded tok/tok_prev/positions
    fused_bytes = streams * (spec_k + 1) * 4 + streams * 4
    unfused_bytes = (fused_bytes + streams * spec_k * 4
                     + 3 * streams * 4)

    # -- silicon projection: 7B int8 decode step bytes, MHA vs GQA-8 -------
    big = _llama.PRESETS["llama2_7b"]
    gqa = dataclasses.replace(big, n_kv_heads=8)
    p_mha = serving_plan(big, slots=32, dtype="bfloat16")
    p_gqa = serving_plan(gqa, slots=32, dtype="bfloat16")
    param = _llama.param_bytes_estimate(big, quant="int8",
                                        param_dtype="bfloat16")
    live_ctx = 32 * 1024  # 32 streams x 1024 live context tokens
    step_mha = param + live_ctx * p_mha["decode_bytes_per_ctx_token"]
    step_gqa = param + live_ctx * p_gqa["decode_bytes_per_ctx_token"]
    proj = step_mha / step_gqa

    return {
        "metric": "gqa_grouped_decode_projected_speedup_7b",
        "value": round(proj, 2),
        "unit": "x",
        "vs_baseline": round(proj / 1.3, 3),  # the >=1.3x tentpole bar
        "kv_groups_7b_gqa8": p_gqa["kv_groups"],
        "decode_bytes_per_ctx_token_mha": p_mha[
            "decode_bytes_per_ctx_token"],
        "decode_bytes_per_ctx_token_gqa8": p_gqa[
            "decode_bytes_per_ctx_token"],
        "param_bytes_7b_int8": int(param),
        "projection_live_ctx_tokens": live_ctx,
        "flash_grouped_ms": round(grouped_ms, 1),
        "flash_repeated_ms": round(repeated_ms, 1),
        "kernel_ab_ratio": round(repeated_ms / grouped_ms, 2)
        if grouped_ms else 0.0,
        "kernel_proxy_caveat": (
            "interpreted Pallas on the CPU proxy: per-call trace "
            "overhead dominates the wall, so the A/B only pins that the "
            "grouped layout is never slower — on silicon the win is the "
            "K/V DMA traffic ratio (kv_groups), priced by serving_plan "
            "and pinned by tests/test_kernels_gqa.py"),
        "greedy_tokens_per_sec": round(greedy_tps, 1),
        "sampled_tokens_per_sec": round(sampled_tps, 1),
        "sampler_overhead_pct": round(
            (greedy_tps / sampled_tps - 1) * 100, 1)
        if sampled_tps else 0.0,
        "sampler_proxy_caveat": (
            "tiny-preset CPU proxy: the model step is microseconds, so "
            "the compiled-in sampler's vocab-length sort/cumsum reads "
            "as tens of percent — at 7B the same ops are noise against "
            "the HBM-bound step (llm7b_sampled_x32 vs "
            "llm7b_int8_continuous_x32 measures it)"),
        "spec_sampled_tokens_per_sec": round(spec_tps, 1),
        "spec_k": spec_k,
        "spec_accept_rate": round(accept_rate, 3),
        "fused_verify_host_bytes_per_round": fused_bytes,
        "unfused_verify_host_bytes_per_round": unfused_bytes,
        "verify_host_transfer_reduction": round(
            unfused_bytes / fused_bytes, 2),
    }


def bench_batching(batches: int, warmup: int, batch_max: int = 8,
                   dims: int = 256) -> dict:
    """Adaptive micro-batching row: a BACKLOGGED small-model pipeline
    (appsrc -> tensor_filter -> tensor_sink) where per-dispatch overhead
    dominates compute.  ``batch_max=8`` lets the filter stage drain the
    backlog into bucketed vmapped dispatches (one XLA call per <=8
    buffers); the row reports the throughput ratio vs the seed's
    one-dispatch-per-buffer path (``batch_max=1``) on identical input.
    ``vs_baseline`` is speedup/2.0: 1.0 = the >=2x acceptance bar.
    Backend-agnostic by design — dispatch overhead exists on every
    backend, so this row is meaningful on CPU too."""
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics
    from nnstreamer_tpu.utils.profiler import metrics_text

    n = max(384, 3 * batches)
    desc = (
        f"appsrc name=src caps=other/tensors,dimensions={dims},"
        "types=float32 ! "
        f"tensor_filter framework=jax model=scaler "
        f"custom=scale:1.5,dims:{dims} name=f ! "
        "tensor_sink name=out"
    )
    frames = [np.full((dims,), float(i % 7), np.float32) for i in range(8)]

    def run(bmax: int):
        _metrics.reset()
        # same queue capacity for both runs: the comparison isolates the
        # drain->one-dispatch mechanism, not queue depth
        p = nt.Pipeline(desc, queue_capacity=64, batch_max=bmax)
        walls = []
        with p:
            for i in range(max(64, 8 * warmup)):  # compile every bucket
                p.push("src", frames[i % len(frames)])
            for _ in range(max(64, 8 * warmup)):
                p.pull("out", timeout=120)

            # best-of-3 windows: scheduling noise on a shared host easily
            # costs 2x on a sub-second window, and the row's claim is the
            # MECHANISM's steady-state ratio, not the noise floor
            for _ in range(3):
                def pusher():
                    for i in range(n):
                        p.push("src", frames[i % len(frames)])

                t = threading.Thread(target=pusher, daemon=True)
                t0 = time.perf_counter()
                t.start()
                for _ in range(n):
                    p.pull("out", timeout=120)
                walls.append(time.perf_counter() - t0)
                t.join()
            p.eos()
            p.wait(timeout=60)
        snap = _metrics.snapshot()
        occ = {k.rsplit(".", 1)[1]: round(v, 2)
               for k, v in snap.items() if k.startswith("f.batch_occupancy.")}
        return n / min(walls), occ, "batch_occupancy" in metrics_text()

    fps_batched, occ, visible = run(batch_max)
    fps_single, _, _ = run(1)
    speedup = fps_batched / fps_single
    return {
        "metric": f"adaptive_batching_speedup_batch{batch_max}_vs_1",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / 2.0, 3),
        "fps_batched": round(fps_batched, 1),
        "fps_unbatched": round(fps_single, 1),
        "batch_max": batch_max,
        "buffers": n,
        "dims": dims,
        "batch_occupancy": occ,
        "occupancy_in_metrics_text": visible,
    }


def bench_adaptive(batches: int, warmup: int, batch_max: int = 8,
                   burst: int = 6, dims: int = 1280,
                   layers: int = 32) -> dict:
    """Adaptive-ladder A/B (ISSUE 10 acceptance): a compute-bound MLP
    stage driven at a SKEWED steady occupancy — bursts of ``burst`` (6)
    same-spec buffers, two bursts pipelined so every drain catches a full
    burst without linger waits.  The static ladder pads every 6-drain to
    bucket 8 (+33% wasted rows of real matmul work); the adaptive ladder
    (``adaptive_buckets=True``) observes the skew and mints an exact
    6-bucket, so steady state dispatches exactly what arrived.  The row
    reports the throughput ratio (``vs_baseline`` = speedup/1.2: 1.0 =
    the >=1.2x acceptance bar), the measured pad-waste counters for both
    runs, and the refined ladder snapshot.  Backend-agnostic: pad rows
    cost real compute on CPU and TPU alike (CPU proxy acceptable per the
    acceptance)."""
    import jax.numpy as jnp
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.filters.custom_easy import register_custom_easy

    w = (np.random.default_rng(11).standard_normal((dims, dims))
         .astype(np.float32) * (0.9 / np.sqrt(dims)))

    def mlp(ins):
        x = ins[0]
        for _ in range(layers):
            x = jnp.tanh(x @ w)
        return [x]

    spec = TensorsSpec.from_string(str(dims), "float32")
    register_custom_easy("bench-adaptive-mlp", mlp, in_spec=spec,
                         out_spec=spec, jax_traceable=True)
    desc = (
        f"appsrc name=src caps=other/tensors,dimensions={dims},"
        "types=float32 ! "
        "tensor_filter framework=custom-easy model=bench-adaptive-mlp "
        "name=f ! tensor_sink name=out"
    )
    frames = [np.full((dims,), float(i % 7) * 0.1, np.float32)
              for i in range(8)]
    n_bursts = max(64, batches // 2)
    warm_bursts = max(40, 8 * warmup)  # past MINT_AFTER: the ladder is
    #                                    refined before the timed window

    def run(adaptive: bool):
        _metrics.reset()
        p = nt.Pipeline(desc, queue_capacity=64, batch_max=batch_max,
                        data_parallel=1, adaptive_buckets=adaptive)
        walls = []
        with p:
            def cycle(n):
                # two bursts pipelined: while burst k computes, burst k+1
                # is already queued, so each drain catches exactly
                # `burst` rows with NO linger wait
                k = 0
                for _ in range(2):
                    for _ in range(burst):
                        p.push("src", frames[k % 8]); k += 1
                for _ in range(n - 2):
                    for _ in range(burst):
                        p.pull("out", timeout=300)
                    for _ in range(burst):
                        p.push("src", frames[k % 8]); k += 1
                for _ in range(2 * burst):
                    p.pull("out", timeout=300)

            cycle(warm_bursts)
            for _ in range(3):  # best-of-3: the mechanism, not the noise
                t0 = time.perf_counter()
                cycle(n_bursts)
                walls.append(time.perf_counter() - t0)
            snap = _metrics.snapshot()
            ladders = p.ladder_snapshot()
            p.eos()
            p.wait(timeout=60)
        occ = {k.rsplit(".", 1)[1]: round(v, 2) for k, v in snap.items()
               if k.startswith("f.batch_occupancy.")}
        return (n_bursts * burst / min(walls),
                snap.get("f.batch_pad_waste", 0.0), occ, ladders)

    fps_adaptive, waste_adaptive, occ_a, ladders = run(True)
    fps_static, waste_static, occ_s, _ = run(False)
    speedup = fps_adaptive / fps_static
    return {
        "metric": f"adaptive_ladder_speedup_burst{burst}_vs_static",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup / 1.2, 3),
        "fps_adaptive": round(fps_adaptive, 1),
        "fps_static": round(fps_static, 1),
        "pad_waste_adaptive": waste_adaptive,
        "pad_waste_static": waste_static,
        "ladders": ladders,
        "batch_occupancy": occ_a,
        "batch_occupancy_static": occ_s,
        "burst": burst, "batch_max": batch_max,
        "dims": dims, "layers": layers,
    }


def bench_asr_stream(batches: int, warmup: int, chunk: int = 4000,
                     window: int = 16000) -> dict:
    """Windowed streaming-ASR A/B (ISSUE 10 acceptance): the
    examples/asr_streaming_window.py pipeline — device-generated audio
    chunks -> tensor_aggregator -> speech_commands — with the window
    carry HOST-side (np.concatenate per window, a full fetch round trip)
    vs DEVICE-RESIDENT (``device=true``: HBM ring, in-program appends,
    zero d2h between windows, 3-program census).  Reports windows/sec
    for the device ring and the host/device ratio.  On a device the
    host path pays a D2H fetch per chunk; the CPU proxy only
    shows the copy/dispatch savings — the row still pins the MECHANISM
    (ring windows bit-identical, resident edge counted)."""
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics

    stride = chunk
    n_windows = max(32, batches)
    chunks = (n_windows - 1) * stride // chunk + window // chunk
    desc = (
        f"audiotestsrc device=true num-buffers={{n}} "
        f"samplesperbuffer={chunk} rate=16000 freq=880 name=src ! "
        f"tensor_aggregator frames_in={chunk} frames_out={window} "
        f"frames_flush={stride} frames_dim=0 name=agg {{dev}}! "
        "tensor_filter framework=jax model=speech_commands "
        "custom=dtype:float32 name=f ! tensor_sink name=out"
    )

    def run(dev: str):
        _metrics.reset()
        warm = max(8, warmup * 4)
        total = chunks + warm
        p = nt.Pipeline(desc.format(n=total, dev=dev),
                        queue_capacity=_SOURCE_QUEUE_CAPACITY)
        with p:
            for _ in range(warm):  # compile + drain pre-buffered windows
                p.pull("out", timeout=300)
            t0 = time.perf_counter()
            outs = [p.pull("out", timeout=300) for _ in range(n_windows)]
            wall = time.perf_counter() - t0
            p.wait(timeout=120)
        head = np.asarray(outs[0].tensors[0])
        return n_windows / wall, head, p.residency.resident_edges

    fps_dev, head_dev, resident = run("device=true ")
    fps_host, head_host, _ = run("")
    return {
        "metric": "asr_streaming_window_windows_per_sec",
        "value": round(fps_dev, 1),
        "unit": "windows/sec",
        "vs_baseline": round(fps_dev / max(1e-9, fps_host), 3),
        "fps_host_aggregator": round(fps_host, 1),
        "speedup_device_vs_host": round(fps_dev / max(1e-9, fps_host), 3),
        "window": window, "chunk": chunk, "windows": n_windows,
        "resident_edges": resident,
        "first_window_scores_match": bool(
            np.array_equal(head_dev, head_host)),
    }


def bench_train_stream(batches: int, warmup: int, in_dim: int = 64,
                       hidden: int = 256, classes: int = 8,
                       bs: int = 32, epochs: int = 3) -> dict:
    """nns-learn A/B (ISSUE 14 acceptance, docs/TRAINING.md): the SAME
    jitted masked update step fed by (a) the device-resident streaming
    window (per-sample in-program appends, no host epoch accumulation)
    vs (b) the legacy host-accumulated epoch (stack + pad per
    minibatch).  Reports samples/sec for the device path and the ratio;
    the paths are bit-identical by test, so this is pure pipeline
    mechanics.  ``host_bytes_held`` contrasts the resident host memory:
    the host path keeps the WHOLE epoch as numpy, the streaming path one
    [batch-size] HBM window — on a device the host path
    additionally pays an H2D per minibatch where the window is already
    resident.  The row also carries the checkpoint-resume contract:
    fsync'd write time and a save→load→train-one-epoch continuation
    checked BITWISE against the uninterrupted run."""
    import numpy as np

    from nnstreamer_tpu.trainer.subplugin import JaxTrainer

    n = max(256, batches * 8)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((n, in_dim)).astype(np.float32)
    ys = rng.integers(0, classes, (n, 1)).astype(np.int32)
    model = f"mlp:{in_dim}:{hidden}:{hidden}:{classes}"
    props = {"model": model, "batch_size": bs, "learning_rate": 0.01}

    def epoch(tr):
        for i in range(n):
            tr.push_data([xs[i]], [ys[i]], False)
        return tr.train_epoch()

    def run(host: bool):
        tr = JaxTrainer()
        tr.open(dict(props, host_accumulate="true" if host else "false"))
        epoch(tr)  # warmup: compiles land here
        times = []
        for _ in range(epochs):
            t0 = time.perf_counter()
            epoch(tr)
            times.append(time.perf_counter() - t0)
        return tr, n * len(times) / sum(times)

    tr_dev, sps_dev = run(False)
    tr_host, sps_host = run(True)

    # checkpoint-resume row: fsync'd write, then a fresh trainer resumes
    # and must continue BITWISE where the uninterrupted twin lands
    import os
    import tempfile

    import jax

    ck = os.path.join(tempfile.mkdtemp(), "bench.ckpt")
    t0 = time.perf_counter()
    tr_dev.save(ck)
    ckpt_ms = (time.perf_counter() - t0) * 1e3
    resumed = JaxTrainer()
    resumed.open(dict(props, model_load_path=ck))
    epoch(resumed)
    epoch(tr_dev)
    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(resumed.params),
                        jax.tree_util.tree_leaves(tr_dev.params)))
    return {
        "metric": "train_stream_device_vs_host_speedup",
        "value": round(sps_dev / max(1e-9, sps_host), 3),
        "unit": "x",
        "vs_baseline": round(sps_dev / max(1e-9, sps_host), 3),
        "samples_per_sec_device": round(sps_dev, 1),
        "samples_per_sec_host": round(sps_host, 1),
        "samples": n, "batch_size": bs, "epochs": epochs,
        "model": model,
        "census": tr_dev.compile_counts(),
        "train_state_bytes": tr_dev.train_state_bytes(),
        "host_bytes_held_host_path": n * (xs[0].nbytes + ys[0].nbytes),
        "host_bytes_held_device_path": 0,
        "ckpt_write_ms": round(ckpt_ms, 2),
        "resume_bit_identical": bool(identical),
    }


def bench_sharded(batches: int, warmup: int, replicas: int = 4,
                  batch_max: int = 32, dims: int = 640,
                  layers: int = 40) -> dict:
    """Mesh-sharded micro-batching row (ISSUE 3 acceptance): a BACKLOGGED
    compute-bound pipeline (appsrc -> jax-traceable MLP filter ->
    tensor_sink) where per-dispatch compute, not overhead, bounds
    throughput.  ``data_parallel=4, dispatch_depth=2`` shards each
    bucketed micro-batch over a 4-chip ``data`` mesh and software-
    pipelines the drain; the row reports the throughput ratio vs the
    single-device lockstep path (``data_parallel=1, dispatch_depth=1``)
    on identical input, plus the per-replica placement counters from
    metrics_text().  ``vs_baseline`` is speedup/1.5: 1.0 = the >=1.5x
    acceptance bar.  On CPU the 8-virtual-device host platform is the
    mesh proxy (main() pins the XLA flag when JAX_PLATFORMS=cpu)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.filters.custom_easy import register_custom_easy
    from nnstreamer_tpu.utils.profiler import metrics_text

    if len(jax.devices()) < replicas:
        raise SystemExit(
            f"--config sharded needs {replicas} local devices, have "
            f"{len(jax.devices())} (CPU proxy: XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")

    w = (np.random.default_rng(3).standard_normal((dims, dims))
         .astype(np.float32) * (0.9 / np.sqrt(dims)))

    def mlp(ins):
        x = ins[0]
        for _ in range(layers):
            x = jnp.tanh(x @ w)
        return [x]

    spec = TensorsSpec.from_string(str(dims), "float32")
    register_custom_easy("bench-shard-mlp", mlp, in_spec=spec,
                         out_spec=spec, jax_traceable=True)
    desc = (
        f"appsrc name=src caps=other/tensors,dimensions={dims},"
        "types=float32 ! "
        "tensor_filter framework=custom-easy model=bench-shard-mlp "
        "name=f ! tensor_sink name=out"
    )
    frames = [np.full((dims,), float(i % 7) * 0.1, np.float32)
              for i in range(8)]
    n = max(256, 2 * batches)

    def run(dp: int, depth: int):
        _metrics.reset()
        # same queue capacity + batch_max both runs: the comparison
        # isolates shard + window, not queue depth or drain size
        p = nt.Pipeline(desc, queue_capacity=64, batch_max=batch_max,
                        data_parallel=dp, dispatch_depth=depth)
        walls = []
        with p:
            for i in range(max(64, 8 * warmup)):  # compile every bucket
                p.push("src", frames[i % len(frames)])
            for _ in range(max(64, 8 * warmup)):
                p.pull("out", timeout=300)
            # best-of-3 windows, as the batching row: the claim is the
            # mechanism's steady-state ratio, not scheduler noise
            for _ in range(3):
                def pusher():
                    for i in range(n):
                        p.push("src", frames[i % len(frames)])

                t = threading.Thread(target=pusher, daemon=True)
                t0 = time.perf_counter()
                t.start()
                for _ in range(n):
                    p.pull("out", timeout=300)
                walls.append(time.perf_counter() - t0)
                t.join()
            p.eos()
            p.wait(timeout=60)
        snap = _metrics.snapshot()
        repl = {k.rsplit(".", 1)[1]: round(v, 1) for k, v in snap.items()
                if k.startswith("f.shard_rows.")}
        visible = "shard_rows" in metrics_text() if repl else False
        return (n / min(walls), repl, snap.get("f.shard_dispatch", 0.0),
                visible)

    fps_sharded, repl, dispatches, visible = run(replicas, 2)
    fps_single, _, _, _ = run(1, 1)
    speedup = fps_sharded / fps_single
    return {
        "metric": f"mesh_sharded_batching_speedup_dp{replicas}_vs_1",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / 1.5, 3),
        "fps_sharded_dp_depth2": round(fps_sharded, 1),
        "fps_single_device_depth1": round(fps_single, 1),
        "data_parallel": replicas,
        "dispatch_depth": 2,
        "batch_max": batch_max,
        "buffers": n,
        "dims": dims,
        "mlp_layers": layers,
        "shard_dispatches": dispatches,
        "per_replica_rows": repl,
        "replica_counters_in_metrics_text": visible,
        "methodology": (
            "backlogged appsrc->filter->sink; best-of-3 steady-state "
            "windows after warmup; identical input + queue depth + "
            "batch_max both runs; CPU host-device proxy when "
            "JAX_PLATFORMS=cpu (xla_force_host_platform_device_count=8)"),
    }


def bench_tp(batches: int, warmup: int, model: str = "llama_small",
             ways: int = 2, max_new: int = 32, prompt_len: int = 16) -> dict:
    """2-D placement A/B row (ISSUE 9): tokens/sec of the llm decode
    under ``Pipeline(model_parallel=M)`` vs ``model_parallel=1`` on the
    SAME prompt — the filter rides the pipeline's shared ``(data x
    model)`` mesh, params + KV sharded per ``param_pspecs``.  On the CPU
    host-device proxy TP buys no wall-clock (the "chips" share one
    socket's caches), so like the fetch row this records the MECHANISM's
    ratio for the next chip sweep, where the decode's weight-bandwidth
    bound is what an M-way split actually divides.  The row decodes at
    the serving dtype (bf16): GSPMD's reduced collective order can flip
    a near-tie bf16 argmax, so ``greedy_ids_identical`` is informational
    here — the bitwise identity contract is pinned at f32 by
    tests/test_model_parallel.py (the mesh gate)."""
    import jax
    import numpy as np

    import nnstreamer_tpu as nt

    if len(jax.devices()) < ways:
        raise SystemExit(
            f"--config tp needs {ways} local devices, have "
            f"{len(jax.devices())} (CPU proxy: XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 400, (1, prompt_len), dtype=np.int32)
    desc = (
        "appsrc name=src ! "
        f"tensor_filter framework=llm model={model} "
        f"custom=max_new:{max_new},temperature:0.0,stream_chunk:8 "
        "invoke-dynamic=true ! tensor_sink name=out"
    )

    def run(mp: int):
        p = nt.Pipeline(desc, model_parallel=mp)
        ids = []
        toks = 0
        with p:
            for _ in range(max(1, warmup)):
                p.push("src", prompt)
                for _ in range(max_new):
                    p.pull("out", timeout=900)
            t0 = time.perf_counter()
            for _ in range(batches):
                p.push("src", prompt)
                for _ in range(max_new):
                    ids.append(int(p.pull("out", timeout=900)
                                   .tensors[0][0]))
                    toks += 1
            wall = time.perf_counter() - t0
            p.eos()
            p.wait(timeout=60)
        assert p.mesh_shape == (1, mp)
        return toks / wall, ids

    tps_tp, ids_tp = run(ways)
    tps_1, ids_1 = run(1)
    ratio = tps_tp / tps_1
    return {
        "metric": f"{model}_decode_tp{ways}_vs_tp1_tokens_per_sec",
        "value": round(tps_tp, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(ratio, 3),
        "speedup_vs_tp1": round(ratio, 3),
        "tokens_per_sec_tp1": round(tps_1, 1),
        "model_parallel": ways,
        "greedy_ids_identical_bf16": ids_tp == ids_1,
        "max_new": max_new,
        "prompt_len": prompt_len,
        "batches": batches,
        "methodology": (
            "same prompt/pipeline both runs at the serving dtype (bf16; "
            "near-tie argmax may flip under GSPMD reduction order — f32 "
            "bit-identity is pinned by tests/test_model_parallel.py); "
            "CPU host-device proxy when JAX_PLATFORMS=cpu "
            "(xla_force_host_platform_device_count=8); the chip sweep "
            "measures the real weight-bandwidth split"),
    }


def bench_tp_grid(batches: int, warmup: int, dp: int = 2, mp: int = 2,
                  dims: int = 512, layers: int = 12,
                  batch_max: int = 32) -> dict:
    """dp x tp grid row (ISSUE 9): the backlogged sharded-micro-batching
    pipeline of ``--config sharded``, but with a ``param_pspecs``-carrying
    MLP so the 2-D mesh places weights over ``model`` WHILE the batch dim
    shards over ``data`` — (dp=2, model=2) vs dp-only (dp=4) on the same
    4 chips.  The per-chip param bytes drop ~2x on the 2-D run (the
    placement counters prove it); fps ratio is the grid tradeoff the
    next chip sweep reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    need = dp * mp
    if len(jax.devices()) < need:
        raise SystemExit(
            f"--config tp_grid needs {need} local devices, have "
            f"{len(jax.devices())} (CPU proxy: XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")

    rng = np.random.default_rng(3)
    w1 = (rng.standard_normal((layers, dims, dims)).astype(np.float32)
          * (0.9 / np.sqrt(dims)))

    @register_model("bench-tp-grid-mlp")
    def _build(opts):
        from jax.sharding import PartitionSpec as P

        params = {"w": jnp.asarray(w1)}

        def apply_fn(p, x):
            def body(x, wl):
                return jnp.tanh(x @ wl), None
            x, _ = jax.lax.scan(body, x, p["w"])
            return x

        spec = TensorsSpec.from_string(str(dims), "float32")
        # layer-stacked mat: OUT dim shards over model (Megatron column
        # split; XLA re-gathers between layers — the grid row's point is
        # placement, not a tuned TP block)
        return ModelBundle(apply_fn, params, spec, spec,
                           param_pspecs={"w": P(None, None, "model")})

    desc = (
        f"appsrc name=src caps=other/tensors,dimensions={dims},"
        "types=float32 ! "
        "tensor_filter framework=jax model=bench-tp-grid-mlp name=f ! "
        "tensor_sink name=out"
    )
    n = max(256, 2 * batches)
    frames = [np.full((dims,), float(i % 5) * 0.2, np.float32)
              for i in range(8)]

    def run(run_dp: int, run_mp: int):
        _metrics.reset()
        p = nt.Pipeline(desc, queue_capacity=64, batch_max=batch_max,
                        data_parallel=run_dp, model_parallel=run_mp,
                        dispatch_depth=2)
        walls = []
        with p:
            for i in range(max(64, 8 * warmup)):
                p.push("src", frames[i % len(frames)])
            for _ in range(max(64, 8 * warmup)):
                p.pull("out", timeout=300)
            for _ in range(3):
                def pusher():
                    for i in range(n):
                        p.push("src", frames[i % len(frames)])

                t = threading.Thread(target=pusher, daemon=True)
                t0 = time.perf_counter()
                t.start()
                for _ in range(n):
                    p.pull("out", timeout=300)
                walls.append(time.perf_counter() - t0)
                t.join()
            p.eos()
            p.wait(timeout=60)
        snap = _metrics.snapshot()
        return n / min(walls), {
            "shards": snap.get("f.param_shards", 0.0),
            "replicas": snap.get("f.param_replicas", 0.0),
            "rows": {k.rsplit(".", 1)[1]: round(v, 1)
                     for k, v in snap.items()
                     if k.startswith("f.shard_rows.")},
        }

    fps_grid, place_grid = run(dp, mp)
    fps_dp, place_dp = run(dp * mp, 1)
    ratio = fps_grid / fps_dp
    return {
        "metric": f"sharded_grid_dp{dp}xtp{mp}_vs_dp{dp * mp}_fps",
        "value": round(fps_grid, 1),
        "unit": "frames/sec",
        "vs_baseline": round(ratio, 3),
        "fps_dp_only": round(fps_dp, 1),
        "speedup_vs_dp_only": round(ratio, 3),
        "data_parallel": dp,
        "model_parallel": mp,
        "param_leaves_sharded": place_grid["shards"],
        "per_chip_rows_grid": place_grid["rows"],
        "per_chip_rows_dp_only": place_dp["rows"],
        "batch_max": batch_max,
        "dims": dims,
        "mlp_layers": layers,
        "buffers": n,
        "methodology": (
            "same 4 chips both runs: (data=2, model=2) with weights "
            "sharded over model vs (data=4) with weights replicated; "
            "identical input/queue/batch_max; CPU host-device proxy when "
            "JAX_PLATFORMS=cpu — per-chip weight HBM halves on the grid "
            "run, fps ratio is the tradeoff the chip sweep reads"),
    }


def bench_fetch(batches: int, warmup: int, dims: int = 1 << 16) -> dict:
    """Async-fetch-engine A/B row (ISSUE 7): a host-fed pipeline whose
    sink payload is LARGE (``dims`` float32 = 256 KB/buffer each way), so
    the pull path pays a real materialization per buffer.  A = the fetch
    engine on (``fetch_depth=2`` + ingress donation), B = the serial path
    (``fetch_depth=1``, no donation); identical input, queue depth, and
    admission bound both runs.  The row carries the h2d/d2h stall split,
    the overlapped-fetch milliseconds, and the window depth — on a
    device the overlap hides the fetch RTT behind the next
    dispatch; on CPU (where D2H is a memcpy) the ratio is ~1.0 and the
    row documents the accounting, not a speedup.  ``vs_baseline`` is
    speedup/1.0."""
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics

    desc = (
        f"appsrc name=src caps=other/tensors,dimensions={dims},"
        "types=float32 max-inflight=4 ! "
        "tensor_transform mode=arithmetic option=typecast:float32,"
        "div:255.0 ! "
        f"tensor_filter framework=jax model=scaler "
        f"custom=scale:1.5,dims:{dims} name=f ! "
        "tensor_sink name=out"
    )
    frames = [np.full((dims,), float(i % 7), np.float32) for i in range(8)]
    n = max(128, batches)

    def run(depth: int, donate: bool):
        _metrics.reset()
        p = nt.Pipeline(desc, queue_capacity=16, fetch_depth=depth,
                        donate_ingress=donate)
        walls = []
        with p:
            for i in range(max(16, 4 * warmup)):
                p.push("src", frames[i % len(frames)])
                p.pull("out", timeout=120)
            for _ in range(3):  # best-of-3: the mechanism, not the noise
                def pusher():
                    for i in range(n):
                        p.push("src", frames[i % len(frames)])

                t = threading.Thread(target=pusher, daemon=True)
                t0 = time.perf_counter()
                t.start()
                for _ in range(n):
                    p.pull("out", timeout=120)
                walls.append(time.perf_counter() - t0)
                t.join()
            p.eos()
            p.wait(timeout=60)
        stats: dict = {}
        _attach_fetch_stats(stats)
        donated = any(getattr(s.element, "_ingress_put", False)
                      for s in p.stages)
        return n / min(walls), stats, donated

    fps_on, stats_on, donated = run(2, True)
    fps_off, stats_off, _ = run(1, False)
    speedup = fps_on / fps_off
    return {
        "metric": "async_fetch_speedup_depth2_donate_vs_serial",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "fps_fetch_engine": round(fps_on, 1),
        "fps_serial": round(fps_off, 1),
        "fetch_depth": 2,
        "donation_planned": donated,
        "payload_bytes": dims * 4,
        "buffers": n,
        "engine_stats": stats_on,
        "serial_stats": stats_off,
        "methodology": (
            "backlogged appsrc->transform+filter->sink, 256 KB payloads "
            "both ways; best-of-3 steady-state windows after warmup; "
            "identical input/queues/admission both runs; A = "
            "fetch_depth=2 + donate_ingress, B = fetch_depth=1 no "
            "donation"),
    }


def bench_link() -> dict:
    """Link-calibration row (VERDICT r4 Weak #4): raw H2D/D2H bandwidth
    and small-fetch RTT for THIS session, measured with the same sync
    discipline as the sweep rows — so every "link-bound" claim
    (segmentation full-res, appsrc, wav2vec2 history) is checkable
    against the same session's measured link instead of a remembered
    number.  ``vs_baseline`` compares D2H against the ~13 MB/s the r3/r4
    sessions saw.
    """
    import jax
    import numpy as np

    dev = jax.devices()[0]
    rtt_s = _fetch_rtt_ms() / 1e3

    mb = 32
    x = np.random.default_rng(0).integers(
        0, 255, mb << 20, dtype=np.uint8)
    n = 3
    # warm the tiny-slice gather program OUTSIDE the timed region at the
    # REAL payload shape (XLA caches programs per shape — a smaller warm
    # array would leave the 32 MB gather's compile inside the timing)
    warm = jax.device_put(x, dev)
    np.asarray(warm[:4])
    t0 = time.perf_counter()
    y = None
    for _ in range(n):
        y = jax.device_put(x, dev)
    np.asarray(y[:4])  # one roundtrip drains the transfer queue
    h2d_s = max(1e-9, (time.perf_counter() - t0 - rtt_s) / n)

    # jax caches the host copy of an array after its first fetch, so a
    # repeated np.asarray(z) measures the CACHE, not the link — pull n
    # DISTINCT device arrays, one fetch each
    plus1 = jax.jit(lambda a: a + 1)
    zs = [jax.block_until_ready(plus1(y)) for _ in range(n)]
    np.asarray(zs[0][:4])  # ensure all device work drained pre-t0
    t0 = time.perf_counter()
    for z in zs:
        np.asarray(z)
    d2h_s = max(1e-9, (time.perf_counter() - t0) / n - rtt_s)

    d2h_mbps = mb / d2h_s
    return {
        "metric": "link_calibration_d2h_mbps",
        "value": round(d2h_mbps, 1),
        "unit": "MB/s",
        "vs_baseline": round(d2h_mbps / 13.0, 3),
        "h2d_mbps": round(mb / h2d_s, 1),
        "d2h_mbps": round(d2h_mbps, 1),
        "fetch_rtt_ms": round(rtt_s * 1e3, 2),
        "payload_mb": mb,
    }


def _trace_off_guard_ns(iters: int = 200_000) -> float:
    """Measured cost of the tracing-off hot-path hook (one ``is not
    None`` pointer check per buffer per site — see utils/tracing.py):
    recorded in every bench row so the "off mode is free" claim stays a
    number, not an assertion.  Empty-loop baseline subtracted."""
    tr = None
    t0 = time.perf_counter()
    for _ in range(iters):
        if tr is not None:
            raise RuntimeError  # pragma: no cover - tr is None
    t1 = time.perf_counter()
    for _ in range(iters):
        pass
    t2 = time.perf_counter()
    return max(0.0, ((t1 - t0) - (t2 - t1)) / iters * 1e9)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="classification",
                    choices=["classification", "classification_quant",
                             "detection", "pose", "segmentation", "audio",
                             "llm", "llm7b", "link", "batching", "adaptive",
                             "asr_stream", "train_stream", "sharded",
                             "tp", "tp_grid", "fetch", "prefix_spec",
                             "gqa_sampling", "all"])
    # classification defaults to 256: the r3 on-chip session measured 2x
    # the fps AND 2x the MFU of batch 64 (30,137 fps / 0.175 MFU vs
    # 15,116 / 0.088) at a still-interactive 5.4 ms p50 — deeper batches
    # are the TPU-native lever.  Other configs keep 64 (detection/pose
    # host NMS+draw work scales with batch).
    ap.add_argument("--batch", type=int, default=None)
    # 128 batches ≈ 1.2s measured window: short runs (32) showed ±30%
    # run-to-run variance from scheduling spikes; 128 is ±2%.
    ap.add_argument("--batches", type=int, default=128)
    # None = per-config default (224; yolov5s detection 640) so an
    # EXPLICIT --size always wins
    ap.add_argument("--size", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--llm-model", default="llama_small")
    ap.add_argument("--llm-quant", default="", choices=["", "int8", "int4"],
                    help="weight-only quantization for llm/llm7b configs")
    ap.add_argument("--llm-streams", type=int, default=1,
                    help="concurrent prompts decoded in one batched scan "
                         "(aggregate tokens/sec reported)")
    ap.add_argument("--llm-prefix", type=int, default=0,
                    help="llm/llm7b continuous: every stream's prompt "
                         "shares an N-token prefix (prefix-sharing row; "
                         "0 = independent prompts)")
    ap.add_argument("--llm-draft", default="",
                    help="llm/llm7b continuous: speculative-decoding "
                         "draft preset (e.g. llama_tiny)")
    ap.add_argument("--llm-spec-k", type=int, default=4,
                    help="proposals per speculative round (with "
                         "--llm-draft)")
    ap.add_argument("--llm-temperature", type=float, default=0.0,
                    help="llm/llm7b continuous: sampled serving "
                         "(per-slot seeded temperature/top-k/top-p, "
                         "docs/SERVING.md §4d); 0 = greedy")
    ap.add_argument("--llm-serve", default="", choices=["", "continuous"],
                    help="continuous: staggered prompts join a RUNNING "
                         "decode loop (reports late-join latency too)")
    ap.add_argument("--llm-text", action="store_true",
                    help="text-in/text-out contract: SentencePiece encode "
                         "+ per-piece decode in the measured loop")
    ap.add_argument("--tp-ways", type=int, default=2,
                    help="tp config: model_parallel ways for the A/B "
                         "(vs model_parallel=1)")
    ap.add_argument("--source", default="videotestsrc",
                    choices=["videotestsrc", "appsrc"],
                    help="classification config: device-generated test "
                         "frames (default) or host-fed appsrc frames")
    ap.add_argument("--seg-native", action="store_true",
                    help="segmentation: ship the class map at the model's "
                         "native output stride (custom=upsample:0) instead "
                         "of full resolution")
    ap.add_argument("--audio-source", default="audiotestsrc",
                    choices=["audiotestsrc", "appsrc"],
                    help="audio config: device-generated windows (default) "
                         "or host-fed appsrc windows")
    ap.add_argument("--audio-model", default="speech_commands",
                    choices=["speech_commands", "wav2vec2"])
    ap.add_argument("--detection-model", default="ssd_mobilenet",
                    choices=["ssd_mobilenet", "yolov5", "yolov8",
                             "yolov5s"])
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="wrap the measured phase in the flight recorder "
                         "(trace_mode=ring) and write the Chrome trace "
                         "artifact next to the BENCH json — load in "
                         "Perfetto (docs/OBSERVABILITY.md)")
    args = ap.parse_args()
    if (args.config in ("sharded", "tp", "tp_grid")
            and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # CPU proxy for the local mesh: 8 virtual host devices.  Must be
        # set before the backend initializes (just below), and
        # only on CPU — a real TPU host keeps its real devices.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    enable_compilation_cache()
    # Every row names the device it ran on; no TPU and no explicit
    # JAX_PLATFORMS=cpu means no run — CPU numbers never print under a
    # `..._per_chip` metric by accident.
    device = require_tpu("bench.py")

    # Batch 256 across the vision configs: the r3 on-chip sessions showed
    # 2x fps AND 2x MFU over batch 64 on classification once host work was
    # off the pull path; with tensors/classmap decode output the other
    # configs get the same treatment.  Segmentation stays shallower (the
    # u8 classmap is still H*W bytes/frame of D2H).
    batch = args.batch if args.batch is not None else 256
    cls_batch = args.batch if args.batch is not None else 256
    runners = {
        "classification": lambda: bench_classification(
            cls_batch, args.batches, args.size or 224, args.warmup,
            args.source),
        "classification_quant": lambda: bench_classification_quant(
            cls_batch, args.batches, args.size or 224, args.warmup),
        "detection": lambda: bench_detection(
            batch, args.batches, args.size, args.warmup,
            args.detection_model),
        "pose": lambda: bench_pose(
            batch, args.batches, args.size or 224, args.warmup),
        "segmentation": lambda: bench_segmentation(
            max(8, batch // 4), args.batches,
            min(args.size or 224, 224),
            args.warmup, native=args.seg_native),
        # audio DEFAULTS to 64: wav2vec2's attention tiles WORSE at 256
        # (measured 5.7k vs 15.4k windows/s), and speech_commands is
        # RTT-bound either way; an explicit --batch still wins
        "audio": lambda: bench_audio(
            args.batch if args.batch is not None else 64, args.batches,
            args.warmup, args.audio_source, args.audio_model),
        "llm": lambda: bench_llm(max(1, args.batches // 8), 1,
                                 model=args.llm_model,
                                 quant=args.llm_quant,
                                 streams=args.llm_streams,
                                 serve=args.llm_serve,
                                 text=args.llm_text,
                                 shared_prefix=args.llm_prefix,
                                 draft=args.llm_draft,
                                 spec_k=args.llm_spec_k,
                                 temperature=args.llm_temperature),
        "llm7b": lambda: bench_llm(2, 1, model="llama2_7b",
                                   quant=args.llm_quant,
                                   streams=args.llm_streams,
                                   serve=args.llm_serve,
                                   text=args.llm_text,
                                   shared_prefix=args.llm_prefix,
                                   draft=args.llm_draft,
                                   spec_k=args.llm_spec_k,
                                   temperature=args.llm_temperature),
        "link": bench_link,
        "batching": lambda: bench_batching(args.batches, args.warmup),
        "adaptive": lambda: bench_adaptive(args.batches, args.warmup),
        "asr_stream": lambda: bench_asr_stream(args.batches, args.warmup),
        "train_stream": lambda: bench_train_stream(args.batches,
                                                   args.warmup),
        "sharded": lambda: bench_sharded(args.batches, args.warmup),
        "tp": lambda: bench_tp(max(1, args.batches // 16), args.warmup,
                               model=args.llm_model, ways=args.tp_ways),
        "tp_grid": lambda: bench_tp_grid(args.batches, args.warmup),
        "fetch": lambda: bench_fetch(args.batches, args.warmup),
        "prefix_spec": lambda: bench_prefix_spec(
            max(4, args.batches // 16), args.warmup,
            model=args.llm_model, spec_k=args.llm_spec_k),
        "gqa_sampling": lambda: bench_gqa_sampling(
            max(2, args.batches // 32), args.warmup),
    }
    todo = list(runners) if args.config == "all" else [args.config]
    if args.config == "all":
        todo.remove("llm7b")  # 7B needs ~14 GB HBM free; run explicitly
        todo.remove("sharded")  # needs >=4 local devices; run explicitly
        todo.remove("tp")  # needs >=2 local devices; run explicitly
        todo.remove("tp_grid")  # needs >=4 local devices; run explicitly
    guard_ns = round(_trace_off_guard_ns(), 2)
    if args.trace:
        # Pipelines built inside the rows read the shared config, so the
        # flip covers the whole measured phase.
        from nnstreamer_tpu.core.config import get_config
        from nnstreamer_tpu.utils.tracing import recorder

        get_config().trace_mode = "ring"
    for name in todo:
        if args.trace:
            recorder.clear()
        row = runners[name]()
        if args.trace:
            from nnstreamer_tpu.utils.tracing import dump_chrome

            out = args.trace
            if len(todo) > 1:  # one artifact per row: prefix the BASENAME
                d, base = os.path.split(args.trace)
                out = os.path.join(d, f"{name}_{base}")
            row["trace"] = out
            row["trace_spans"] = dump_chrome(recorder.events(), out)
            row["trace_mode"] = "ring"
        # tracing-off overhead: one pointer check per hook site per
        # buffer; recorded so the row carries the claim as a number
        row["trace_off_guard_ns"] = guard_ns
        row.update(device)
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
