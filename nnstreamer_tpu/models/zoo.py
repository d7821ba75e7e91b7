"""Model zoo: named JAX models loadable by ``tensor_filter framework=jax``.

Reference analog: the reference loads vendor model *files* (.tflite/.pb/
.onnx) through per-SDK sub-plugins (SURVEY §2.4).  Here a "model" is a pure
JAX program: ``ModelBundle(apply_fn, params, in_spec, out_spec)``.  The zoo
maps pipeline-string names (``model=mobilenet_v1``) to builder functions;
foreign checkpoints enter by converting weights into these bundles (utils/
import_torch.py), and arbitrary user models enter via ``module.path:attr``
import strings or by passing a bundle object programmatically.

Builders take an options dict (the filter's ``custom=`` string, parsed) so
pipelines can pick variants: ``custom=width:0.5,classes:10``.
"""

from __future__ import annotations

import dataclasses
import importlib
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.types import TensorsSpec


@dataclasses.dataclass
class ModelBundle:
    """A runnable model: pure apply + pytree of params + IO specs."""

    apply_fn: Callable  # (params, *inputs) -> output | tuple(outputs)
    params: object
    in_spec: Optional[TensorsSpec]
    out_spec: Optional[TensorsSpec]
    #: optional per-model sharding hints: pytree matching params of
    #: jax.sharding.PartitionSpec, used by the parallel runner
    param_pspecs: object = None
    name: str = "model"
    #: optional text tokenizer carried by the checkpoint itself (GGUF
    #: tokenizer.ggml.* vocab -> models/tokenizer.py); the llm framework
    #: uses it in place of its byte-level fallback
    tokenizer: object = None
    #: optional REDUCED output variant for the HBM-residency planner
    #: (pipeline/residency.py, docs/FETCH.md): a thunk returning a bundle
    #: that shares THIS bundle's params (read at call time, so device
    #: placement/replication survives) but emits a smaller output — e.g.
    #: deeplab's native-stride score map vs the full-res bilinear blow-up.
    #: The planner selects it only when every downstream consumer admits
    #: arbitrary tensor geometry.  None = no reduced form exists, or the
    #: caller pinned the output explicitly.
    reduced_variant: Optional[Callable[[], "ModelBundle"]] = None
    #: human description of the reduced variant (logged when selected)
    reduced_desc: str = ""


_builders: Dict[str, Callable[[Dict[str, str]], ModelBundle]] = {}
_lock = threading.Lock()


def register_model(name: str, builder=None):
    """``@register_model("mobilenet_v1")`` on a builder(opts)->ModelBundle."""

    def do(b):
        with _lock:
            _builders[name] = b
        return b

    return do(builder) if builder is not None else do


def model_names() -> List[str]:
    _ensure_builtin()
    with _lock:
        return sorted(_builders)


_builtin_loaded = False


def _ensure_builtin():
    global _builtin_loaded
    if _builtin_loaded:
        return
    for mod in (
        "nnstreamer_tpu.models.testmodels",
        "nnstreamer_tpu.models.mobilenet",
        "nnstreamer_tpu.models.ssd",
        "nnstreamer_tpu.models.yolo",
        "nnstreamer_tpu.models.posenet",
        "nnstreamer_tpu.models.segment",
        "nnstreamer_tpu.models.audio",
        "nnstreamer_tpu.models.llama",
    ):
        importlib.import_module(mod)
    _builtin_loaded = True


def build(name: str, opts: Optional[Dict[str, str]] = None) -> ModelBundle:
    """Resolve a model name to a bundle.

    Accepts zoo names, ``pkg.mod:attr`` import strings (attr may be a bundle
    or a builder), or a ModelBundle instance.
    """
    if isinstance(name, ModelBundle):
        return name
    _ensure_builtin()
    opts = dict(opts or {})
    key = str(name)
    with _lock:
        b = _builders.get(key)
    if b is not None:
        return b(opts)
    # Model FILES (the reference's default tensor_filter path: model=<file>).
    import os

    is_ckpt_dir = os.path.isdir(key) and (
        os.path.exists(os.path.join(key, "model.safetensors.index.json"))
        or os.path.exists(os.path.join(key, "model.safetensors")))
    if key.endswith((".tflite", ".onnx", ".safetensors", ".npz", ".gguf",
                     ".safetensors.index.json")) or is_ckpt_dir:
        if not os.path.exists(key):
            raise KeyError(f"model file not found: {key}")
        if key.endswith(".tflite"):
            from . import tflite

            return tflite.load_bundle(key, opts)
        if key.endswith(".onnx"):
            from . import onnx

            return onnx.load_bundle(key, opts)
        from . import llama

        return llama.build_from_checkpoint(key, opts)
    if ":" in key:
        mod_name, attr = key.split(":", 1)
        mod = importlib.import_module(mod_name)
        obj = getattr(mod, attr)
        if isinstance(obj, ModelBundle):
            return obj
        if callable(obj):
            out = obj(opts)
            if isinstance(out, ModelBundle):
                return out
    raise KeyError(f"unknown model {name!r}; zoo has {model_names()}")
