"""Model-FILE ingestion: run real .tflite / .onnx / .gguf files through
tensor_filter, the reference's default usage shape (model=<file>).

No foreign runtimes involved: each format parses directly into a jittable
JAX program over the file's actual weights, so ingested models fuse into
the pipeline's XLA program like any zoo model.  This example builds tiny
files in-process (the same writers the test suite uses — stand-ins for
files you'd export from TF/torch/llama.cpp) and streams through each.

    JAX_PLATFORMS=cpu python examples/model_files.py
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import nnstreamer_tpu as nt  # noqa: E402
from nnstreamer_tpu.models import gguf, llama, tflite_build  # noqa: E402


def tflite_demo(td: str) -> None:
    rng = np.random.default_rng(0)
    mw = tflite_build.ModelWriter()
    x = mw.add_input([1, 16, 16, 3])
    w = mw.add_const(rng.standard_normal((8, 3, 3, 3)).astype(np.float32) * 0.2)
    b = mw.add_const(np.zeros((8,), np.float32))
    y = mw.add_op("CONV_2D", [x, w, b], [1, 8, 8, 8],
                  options={"padding": "SAME", "stride": (2, 2),
                           "act": "relu"})
    y = mw.add_op("MEAN", [y, mw.add_const(np.array([1, 2], np.int32))],
                  [1, 8])
    y = mw.add_op("SOFTMAX", [y], [1, 8])
    path = os.path.join(td, "tiny.tflite")
    with open(path, "wb") as f:
        f.write(mw.finish(outputs=[y]))

    p = nt.Pipeline(
        f"appsrc name=src caps=other/tensors,dimensions=3:16:16:1,"
        f"types=float32 ! tensor_filter framework=jax model={path} ! "
        "tensor_sink name=out")
    with p:
        p.push("src", rng.standard_normal((1, 16, 16, 3)).astype(np.float32))
        probs = np.asarray(p.pull("out", timeout=60).tensors[0])
        p.eos()
        p.wait(timeout=30)
    print(f".tflite  -> probs sum={probs.sum():.3f} argmax={probs.argmax()}")


def gguf_demo(td: str) -> None:
    cfg = llama.LlamaConfig(vocab=128, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_hidden=128, max_seq=64)
    params = llama.init_params(cfg, seed=1)
    # export in llama.cpp's own layout (names, fastest-first dims,
    # interleaved RoPE) — what a real .gguf from the wild looks like
    path = os.path.join(td, "model.gguf")
    gguf.export_llama(path, params, cfg)

    p = nt.Pipeline(
        "appsrc name=src caps=other/tensors,dimensions=1:1,types=int32,"
        "format=flexible ! "
        f"tensor_filter framework=llm model={path} "
        "custom=max_new:8,param_dtype:float32,dtype:float32 ! "
        "tensor_sink name=out")
    with p:
        p.push("src", np.array([[1, 17, 9]], np.int32))
        toks = [int(np.asarray(p.pull("out", timeout=120).tensors[0])
                    .ravel()[0]) for _ in range(8)]
        p.eos()
        p.wait(timeout=30)
    print(f".gguf    -> streamed tokens {toks}")


def onnx_demo(td: str) -> None:
    try:
        import torch
        import torch.nn as nn
        from torch.onnx._internal.torchscript_exporter import (
            onnx_proto_utils)
    except ImportError:
        print(".onnx    -> skipped (torch not available)")
        return
    # torch's exporter works without the `onnx` package if the optional
    # onnxscript post-step is skipped
    onnx_proto_utils._add_onnxscript_fn = lambda b, c: b
    torch.manual_seed(0)
    m = nn.Sequential(nn.Conv2d(3, 4, 3, stride=2, padding=1), nn.ReLU(),
                      nn.Flatten(), nn.Linear(4 * 8 * 8, 10),
                      nn.Softmax(dim=1))
    m.eval()
    xt = torch.randn(1, 3, 16, 16)
    path = os.path.join(td, "torch.onnx")
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        torch.onnx.export(m, xt, path, opset_version=13, dynamo=False)

    p = nt.Pipeline(
        f"appsrc name=src caps=other/tensors,dimensions=16:16:3:1,"
        f"types=float32 ! tensor_filter framework=jax model={path} ! "
        "tensor_sink name=out")
    with p:
        p.push("src", xt.numpy())
        probs = np.asarray(p.pull("out", timeout=60).tensors[0])
        p.eos()
        p.wait(timeout=30)
    with torch.no_grad():
        want = m(xt).numpy()
    print(f".onnx    -> max |jax - torch| = {np.abs(probs - want).max():.2e}")


def main() -> None:
    with tempfile.TemporaryDirectory() as td:
        tflite_demo(td)
        onnx_demo(td)
        gguf_demo(td)


if __name__ == "__main__":
    main()
