#!/usr/bin/env python
"""The wide TPU smoke: drives the chip-facing paths the hermetic CPU suite
cannot (tests/conftest.py forces the virtual CPU mesh).  chip_smoke.py at
the repo root is the quick one (main path, full-width models); this one
is broader and shallower.  Needs a TPU unless JAX_PLATFORMS=cpu asks for
a functional CPU run.

    python tools/smoke_tpu.py

Checks: Pallas flash-attention numerics against plain XLA on the real
backend, the fused classification pipeline, device-NMS detection, LLM
token streaming, int4 Pallas-kernel decode, wav2vec2 + ctc
decode-on-edge, .tflite file ingestion (float + fully-quantized integer
execution), and a query offload roundtrip.  Prints one PASS/FAIL line
each and exits nonzero on any failure.
"""

from __future__ import annotations

import os
import sys
import traceback

# Runnable as `python tools/smoke_tpu.py` without an installed package.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nnstreamer_tpu.core.platform import (enable_compilation_cache,
                                          require_tpu)


def _check(name, fn):
    try:
        fn()
        print(f"PASS {name}")
        return True
    except Exception:  # noqa: BLE001 - report and continue
        print(f"FAIL {name}")
        traceback.print_exc()
        return False


def kernel_numerics():
    import numpy as np
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.attention import (attention_reference,
                                              flash_attention)

    rng = np.random.default_rng(0)
    for s, causal in ((512, True), (1024, False)):
        q = jnp.asarray(rng.standard_normal((2, s, 4, 128)).astype(
            np.float32)).astype(jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((2, s, 4, 128)).astype(
            np.float32)).astype(jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((2, s, 4, 128)).astype(
            np.float32)).astype(jnp.bfloat16)
        a = np.asarray(flash_attention(q, k, v, causal=causal).astype(
            jnp.float32))
        b = np.asarray(attention_reference(q, k, v, causal=causal).astype(
            jnp.float32))
        err = float(np.max(np.abs(a - b)))
        assert err < 0.05, f"flash vs xla mismatch {err} at S={s}"


def classification_pipeline():
    import nnstreamer_tpu as nt

    p = nt.Pipeline(
        "videotestsrc device=true batch=16 num-buffers=64 width=224 "
        "height=224 name=src ! "
        "tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        "tensor_filter framework=jax model=mobilenet_v1 "
        "custom=size:224,batch:16 ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out "
        "max-buffers=4")
    with p:
        for _ in range(4):
            b = p.pull("out", timeout=600)
        assert len(b.meta["label"]) == 16
        p.wait(timeout=120)


def detection_device_nms():
    import numpy as np

    import nnstreamer_tpu as nt

    p = nt.Pipeline(
        "videotestsrc device=true batch=8 num-buffers=16 width=128 "
        "height=128 pattern=ball name=src ! "
        "tensor_transform mode=arithmetic option=typecast:float32,div:255.0 ! "
        "tensor_filter framework=jax model=ssd_mobilenet "
        "custom=size:128,classes:11,batch:8 ! "
        "tensor_decoder mode=bounding_boxes option3=0.3 option4=128:128 "
        "option7=device ! tensor_sink name=out")
    with p:
        b = p.pull("out", timeout=600)
        assert np.asarray(b.tensors[0]).shape == (8, 128, 128, 4)
        assert len(b.meta["detections"]) == 8
        p.wait(timeout=120)


def llm_stream():
    import nnstreamer_tpu as nt

    p = nt.Pipeline(
        "appsrc name=src ! tensor_filter framework=llm model=llama_tiny "
        "custom=max_new:6,stream_chunk:3 invoke-dynamic=true ! "
        "tensor_sink name=out")
    with p:
        p.push("src", "smoke")
        toks = [p.pull("out", timeout=600) for _ in range(6)]
        assert toks[-1].meta.get("stream_last") is True
        p.eos()
        p.wait(timeout=60)


def llm_int4_kernel_stream():
    """r5 path: weight-only int4 decode through the Pallas nibble-unpack
    kernel (ops/int4_matmul.py) — llama_small's dims tile (d2/F %128==0)
    so the REAL kernel engages on the chip, not the XLA fallback.
    Determinism asserted across two identical runs."""
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.ops.int4_matmul import kernel_enabled

    assert kernel_enabled()

    def run():
        p = nt.Pipeline(
            "appsrc name=src ! tensor_filter framework=llm "
            "model=llama_small custom=max_new:6,quant:int4,stream_chunk:3 "
            "invoke-dynamic=true ! tensor_sink name=out")
        with p:
            p.push("src", np.array([1, 7, 3, 9], np.int32))
            ids = [int(np.asarray(p.pull("out", timeout=600).tensors[0])
                       .ravel()[0]) for _ in range(6)]
            p.eos()
            p.wait(timeout=60)
        return ids

    a, b = run(), run()
    assert a == b, f"int4 decode not deterministic: {a} vs {b}"
    assert all(0 <= t < 2048 for t in a)


def wav2vec2_ctc_decode_on_edge():
    """Round-3 path: the ctc decoder's device argmax fuses with wav2vec2,
    so only [B, T] ids cross D2H instead of [B, T, vocab] logits."""
    import numpy as np

    import nnstreamer_tpu as nt

    p = nt.Pipeline(
        "audiotestsrc device=true batch=16 num-buffers=64 "
        "samplesperbuffer=16000 rate=16000 name=src ! "
        "tensor_filter framework=jax model=wav2vec2 "
        "custom=dtype:float32,batch:16,samples:16000 ! "
        "tensor_decoder mode=ctc ! tensor_sink name=out max-buffers=4")
    fused = [s for s in p.stages if "+" in s.element.name]
    assert fused and "tensor_decoder" in fused[0].element.name
    with p:
        b = p.pull("out", timeout=600)
        assert np.asarray(b.tensors[0]).dtype == np.int32
        assert "tokens" in b.meta and len(b.meta["tokens"]) == 16
        p.wait(timeout=120)


def tflite_file_ingestion():
    """Round-3 path: a real .tflite file parsed into the fused program."""
    import os
    import tempfile

    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.models import tflite_build

    rng = np.random.default_rng(0)
    mw = tflite_build.ModelWriter()
    x = mw.add_input([8, 32, 32, 3])
    w = mw.add_const(rng.standard_normal((16, 3, 3, 3)).astype(
        np.float32) * 0.2)
    b = mw.add_const(np.zeros((16,), np.float32))
    y = mw.add_op("CONV_2D", [x, w, b], [8, 16, 16, 16],
                  options={"padding": "SAME", "stride": (2, 2),
                           "act": "relu"})
    y = mw.add_op("MEAN", [y, mw.add_const(np.array([1, 2], np.int32))],
                  [8, 16])
    y = mw.add_op("SOFTMAX", [y], [8, 16])
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "m.tflite")
        with open(path, "wb") as f:
            f.write(mw.finish(outputs=[y]))
        p = nt.Pipeline(
            f"appsrc name=src caps=other/tensors,dimensions=3:32:32:8,"
            f"types=float32 ! tensor_filter framework=jax model={path} ! "
            "tensor_sink name=out")
        with p:
            p.push("src", rng.standard_normal((8, 32, 32, 3)).astype(
                np.float32))
            out = np.asarray(p.pull("out", timeout=600).tensors[0])
            assert out.shape == (8, 16)
            np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)
            p.eos()
            p.wait(timeout=60)


def tflite_quantized_graph():
    """Fully-quantized (uint8-activation) .tflite on the chip: integer IO
    contract, INTEGER execution inside (r5 — native int8 conv on the
    MXU with per-op requantization, models/tflite.py _run_op_int)."""
    import os
    import tempfile

    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.models import tflite_build

    rng = np.random.default_rng(5)
    wf = rng.standard_normal((16, 3, 3, 3)).astype(np.float32) * 0.2
    s_in, s_out = 1.0 / 255.0, 6.0 / 255.0
    sw = np.abs(wf).max(axis=(1, 2, 3)) / 127.0
    wq = np.clip(np.round(wf / sw[:, None, None, None]),
                 -127, 127).astype(np.int8)
    mw = tflite_build.ModelWriter()
    x = mw.add_input([8, 32, 32, 3], dtype=np.uint8,
                     quant_scale=[s_in], quant_zero_point=[0])
    w = mw.add_const(wq, "wq", quant_scale=list(sw),
                     quant_zero_point=[0] * 16, quant_axis=0)
    b = mw.add_const(np.zeros((16,), np.int32), "bq",
                     quant_scale=list(s_in * sw),
                     quant_zero_point=[0] * 16, quant_axis=0)
    y = mw.add_op("CONV_2D", [x, w, b], [8, 16, 16, 16],
                  out_dtype=np.uint8,
                  options={"padding": "SAME", "stride": (2, 2),
                           "act": "relu6"},
                  quant_scale=[s_out], quant_zero_point=[0])
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "q.tflite")
        with open(path, "wb") as f:
            f.write(mw.finish(outputs=[y]))
        p = nt.Pipeline(
            f"appsrc name=src caps=other/tensors,dimensions=3:32:32:8,"
            f"types=uint8 ! tensor_filter framework=jax model={path} ! "
            "tensor_sink name=out")
        with p:
            p.push("src", rng.integers(0, 256, (8, 32, 32, 3),
                                       dtype=np.uint8))
            out = np.asarray(p.pull("out", timeout=600).tensors[0])
            assert out.dtype == np.uint8 and out.shape == (8, 16, 16, 16)
            assert int(out.max()) > 0  # relu6 range actually exercised
            p.eos()
            p.wait(timeout=60)


def query_roundtrip():
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.filters.custom_easy import register_custom_easy

    spec = TensorsSpec.from_string("4", "float32")
    register_custom_easy("smoke-double", lambda ins: [ins[0] * 2],
                         in_spec=spec, out_spec=spec)
    srv = nt.Pipeline(
        "tensor_query_serversrc name=ssrc port=0 id=99 ! "
        "tensor_filter framework=custom-easy model=smoke-double ! "
        "tensor_query_serversink id=99")
    with srv:
        port = srv.element("ssrc").bound_port
        cli = nt.Pipeline(
            f"appsrc name=src ! tensor_query_client port={port} "
            "timeout=30 ! tensor_sink name=out")
        with cli:
            cli.push("src", np.ones(4, np.float32))
            out = cli.pull("out", timeout=30)
            np.testing.assert_allclose(out.tensors[0], 2.0)
            cli.eos("src")
            cli.wait(timeout=15)


def main() -> int:
    import argparse
    import json
    import time

    import jax

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write a machine-readable record of the run")
    args = ap.parse_args()
    enable_compilation_cache()
    device = require_tpu("tools/smoke_tpu.py")

    # Claim the output path BEFORE burning minutes of device time on the
    # checks — but via a sibling temp file renamed at the end, so an
    # unwritable path fails here while a crash mid-run can't truncate a
    # previous good record.
    json_tmp = args.json + ".tmp" if args.json else None
    json_file = open(json_tmp, "w") if json_tmp else None

    devices = jax.devices()
    print(f"backend: {devices}")
    checks = [
        ("flash-attention kernel numerics (real backend)", kernel_numerics),
        ("fused classification pipeline", classification_pipeline),
        ("device-NMS detection pipeline", detection_device_nms),
        ("LLM token streaming", llm_stream),
        ("LLM int4 Pallas-kernel decode", llm_int4_kernel_stream),
        ("wav2vec2 + ctc decode-on-edge", wav2vec2_ctc_decode_on_edge),
        (".tflite file ingestion", tflite_file_ingestion),
        (".tflite fully-quantized graph", tflite_quantized_graph),
        ("tensor_query offload roundtrip", query_roundtrip),
    ]
    results = []
    for name, fn in checks:
        t0 = time.monotonic()
        passed = _check(name, fn)
        results.append({"check": name, "pass": passed,
                        "seconds": round(time.monotonic() - t0, 2)})
    ok = all(r["pass"] for r in results)
    if json_file is not None:
        with json_file as f:
            json.dump({
                "ok": ok,
                "backend": [str(d) for d in devices],
                **device,
                "unix_time": int(time.time()),
                "checks": results,
            }, f, indent=1)
            f.write("\n")
        os.replace(json_tmp, args.json)
    print("SMOKE", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
