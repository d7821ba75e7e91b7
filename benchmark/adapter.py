"""Where the benchmark touches the program: the two pipeline strings, and
the zoo hook through which benchmark-made weights enter as a checkpoint's
tree would.  Everything else under ``benchmark/`` is the yardstick and
imports nothing of ``nnstreamer_tpu``, but for the ``register`` function
of a model's module (``manifest.py``): a new architecture's registration
lives there, these two stay here.
"""

from __future__ import annotations

import functools


def register_decoder(name: str, cfg: dict, tree) -> None:
    """Zoo entry ``name``: the program's decoder over ``tree``."""
    from nnstreamer_tpu.core.types import TensorFormat, TensorsSpec
    from nnstreamer_tpu.models import llama
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    def build(opts):
        lcfg = llama.LlamaConfig(
            vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            ffn_hidden=cfg["intermediate_size"],
            max_seq=int(opts.get("max_seq", cfg["serve"]["max_seq"])),
            rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"])
        if lcfg.head_dim != cfg["head_dim"]:
            raise ValueError("the program derives head_dim as dim / heads")
        dtype = opts.get("dtype", cfg["precision"]["compute"])
        bundle = ModelBundle(
            apply_fn=lambda p, t: llama.forward(p, t, lcfg,
                                                compute_dtype=dtype),
            params=tree,
            in_spec=TensorsSpec.from_string("1:1", "int32").replace(
                format=TensorFormat.FLEXIBLE),
            out_spec=TensorsSpec.from_string(
                f"{lcfg.vocab}:1:1", "float32").replace(
                format=TensorFormat.FLEXIBLE),
            param_pspecs=llama.param_pspecs(quant="int8"), name=name)
        bundle.config = lcfg
        return bundle

    register_model(name, build)


def forget(name: str) -> None:
    """Drops a registered entry, and with it the registry's hold on the
    weights, so a process that reads several seeds frees each in turn."""
    from nnstreamer_tpu.models.zoo import register_model

    def gone(_opts):
        raise KeyError(f"{name} was forgotten")

    register_model(name, gone)


def serve_pipeline(name: str, cfg: dict, *, max_new: int, kv_blocks: int,
                   traced: bool, options: list):
    """The serving path a user types.  Only what sizes the deployment is
    passed, and ``options``, what the model's module says the string has
    to state of the model (its weight quantization); ``prefill_chunk``,
    ``prefill_budget`` and ``stream_chunk`` stay at the program's
    defaults, so a PR that finds better ones is measured."""
    import nnstreamer_tpu as nt

    s = cfg["serve"]
    custom = ",".join([
        f"max_new:{max_new}", f"max_seq:{s['max_seq']}",
        f"dtype:{cfg['precision']['compute']}", *options,
        "serve:continuous", f"slots:{s['slots']}",
        f"block_size:{s['block_size']}", f"kv_blocks:{kv_blocks}",
        "temperature:0.0"])
    return nt.Pipeline(
        f"appsrc name=src ! tensor_filter framework=llm model={name} "
        f"custom={custom} invoke-dynamic=true name=f ! tensor_sink name=out",
        trace_mode="ring" if traced else None)


def request_buffer(prompt, tag: int):
    import nnstreamer_tpu as nt

    b = nt.Buffer([prompt])
    b.meta["bench_req"] = tag
    return b


def ring_spans(stage: str = "llm.serve"):
    """The program's ring spans as plain tuples
    ``(kind, start_ns, dur_ns, args)`` on ``time.monotonic_ns``."""
    from nnstreamer_tpu.utils import tracing

    return [(e.kind, e.ts, e.dur, dict(e.args or {}))
            for e in tracing.recorder.events() if e.stage == stage]


def register_mobilenet_v1(name: str, cfg: dict, tree) -> None:
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.models import mobilenet
    from nnstreamer_tpu.models.zoo import ModelBundle, register_model

    def build(opts):
        size = int(opts.get("size", cfg["image_size"]))
        batch = int(opts.get("batch", 1))
        classes = cfg["num_classes"]
        return ModelBundle(
            apply_fn=functools.partial(
                mobilenet.apply,
                compute_dtype=opts.get("dtype",
                                       cfg["precision"]["compute"])),
            params=tree,
            in_spec=TensorsSpec.from_string(f"3:{size}:{size}:{batch}",
                                            "float32"),
            out_spec=TensorsSpec.from_string(f"{classes}:{batch}",
                                             "float32"),
            param_pspecs=mobilenet.param_pspecs(), name=name)

    register_model(name, build)


def stream_pipeline(name: str, cfg: dict, mix: dict):
    """Upstream's image-classification example: normalise, classify,
    label — one fused stage behind an app source."""
    import nnstreamer_tpu as nt

    size, batch = cfg["image_size"], mix["batch"]
    if mix["feed"] == "host":
        src = (f"appsrc name=src caps=other/tensors,dimensions=3:{size}:"
               f"{size}:{batch},types=uint8 "
               f"max-inflight={mix['max_inflight']}")
    else:
        raise ValueError(f"feed {mix['feed']!r} has no source yet")
    return nt.Pipeline(
        f"{src} ! tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        f"tensor_filter framework=jax model={name} "
        f"custom=size:{size},batch:{batch} name=f ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out "
        f"max-buffers={mix['sink_buffers']}")
