"""The model modules bind what was there and rewrite nothing: for a seed,
at toy size, the weights, the traffic and the reference's output are
bit-identical to the parent's, and the two pipeline strings are the
parent's byte for byte."""

import hashlib

import numpy as np
import pytest

from conftest import ROOT, TOY_CLASSIFIER, TOY_DECODER, TOY_MIXES

from benchmark import run
from benchmark.manifest import Manifest
from benchmark.traffic import ServeTraffic, stream_frames

#: taken on the parent, commit 65329870cd724787e23c897fe3ba847968902280
#: (PR 27), with jax 0.9.0 on the CPU, seed 2**31 + 28; the float32 ones
#: may move with jax's or the CPU's arithmetic, the others may not
PARENT = {
    "decoder_tree":
        "60fdccccf7cd482b1f1b91559ffa730ac3fa7771ad0243443d0e3f513ce32c55",
    "closed_prompts":
        "f0f0c09b43d1cec53f4a1e3fde1035dd3469a4f1a20327517ab6eb17621c7bac",
    "served_gaps":
        "aaa16661e5c9a7d15f70bf95db0634b2da8e35ecdb2866eb723171f2dbd39b86",
    "control_gaps":
        "b66e4f1ea06c0bea81364a2f014f85c7ae18e4bf9fcae837f449d21e586b5f1d",
    "mobilenet_v1_tree":
        "a58222edbfd741f3b2fcb40409f5188d9987e6954baf14cf3d3530a01123521b",
    "stream_frames":
        "cd9c433e7dc9227ba69287681cd3f74e715181e631af49344cb677cce92b046b",
    "logits":
        "5eba512d5d58ff36cd2ceeb8e096a56e8686e29521b32ae2b145bb106eb2352a",
    "control_logits":
        "0b815e43cc4d8d383c690ffbc1f5fd0f021790103d0f5697b98ae21ed850527c",
}
SEED = 2**31 + 28
PARENT_PIPELINES = {
    "mistral_7b.decode_c32":
        "appsrc name=src ! tensor_filter framework=llm model=bench_decoder "
        "custom=max_new:512,max_seq:4096,dtype:bfloat16,quant:int8,"
        "serve:continuous,slots:32,block_size:16,kv_blocks:1088,"
        "temperature:0.0 invoke-dynamic=true name=f ! tensor_sink name=out",
    "mobilenet_v1.hostfed_b4096":
        "appsrc name=src caps=other/tensors,dimensions=3:224:224:4096,"
        "types=uint8 max-inflight=4 ! tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! tensor_filter "
        "framework=jax model=bench_mobilenet_v1 custom=size:224,batch:4096 "
        "name=f ! tensor_decoder mode=image_labeling ! tensor_sink name=out "
        "max-buffers=4",
}


def sha(tree) -> str:
    """Over every leaf's path, type, shape and bytes."""
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def decoder_digests() -> dict:
    m, cfg, mix = Manifest(ROOT), TOY_DECODER, TOY_MIXES["toy_closed"]
    model, reference = m.model(cfg), m.reference(cfg)
    tree = model.weights(cfg, SEED)
    traffic = ServeTraffic(mix, cfg["vocab_size"], SEED, 1.5)
    stream = traffic.closed_prompts()
    prompts = [next(stream).prompt for _ in range(8)]
    width = traffic.max_prompt + traffic.max_new
    toks = np.zeros((2, width), np.int32)
    for b, p in enumerate(prompts[:2]):
        toks[b] = (np.arange(width) * 7 + 3 + b) % cfg["vocab_size"]
        toks[b, :len(p)] = p
    return {
        "decoder_tree": sha(tree), "closed_prompts": sha(prompts),
        "served_gaps": sha([np.asarray(a) for a in reference.served_gaps(
            tree, toks, cfg)]),
        "control_gaps": sha([np.asarray(a) for a in reference.control_gaps(
            tree, toks, cfg, **model.CONTROL)]),
    }


def classifier_digests() -> dict:
    m, cfg, mix = Manifest(ROOT), TOY_CLASSIFIER, TOY_MIXES["toy_hostfed"]
    model, reference = m.model(cfg), m.reference(cfg)
    tree = model.weights(cfg, SEED)
    pool = stream_frames(mix, cfg["image_size"], SEED)
    rows = pool[0][:16]
    return {
        "mobilenet_v1_tree": sha(tree), "stream_frames": sha(pool),
        "logits": sha(reference.logits_in_blocks(tree, rows)),
        "control_logits": sha(reference.logits_in_blocks(
            tree, rows, **model.CONTROL)),
    }


@pytest.mark.parametrize("digests", [decoder_digests, classifier_digests])
def test_a_seed_gives_what_it_gave_on_the_parent(digests):
    got = digests()
    assert got == {k: PARENT[k] for k in got}


@pytest.mark.parametrize("cell", sorted(PARENT_PIPELINES))
def test_pipeline_strings_are_the_parents(cell, monkeypatch):
    import nnstreamer_tpu as nt

    typed = []
    monkeypatch.setattr(nt, "Pipeline",
                        lambda text, **_kw: typed.append(text))
    m = Manifest(ROOT)
    ctx = run.Context(m, m.cell(cell), 1, 45, False)
    run.make_driver(ctx).pipeline()
    assert typed == [PARENT_PIPELINES[cell]]
