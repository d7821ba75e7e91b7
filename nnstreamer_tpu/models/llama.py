"""Llama-family decoder-only LM — benchmark config #5 (token streaming).

Reference analog: the reference's LLM capability is the llama.cpp
sub-plugin (``ext/nnstreamer/tensor_filter/tensor_filter_llamacpp.cc``,
SURVEY §2.4 [UNVERIFIED]) — prompt in, generated tokens streamed out as
flexible tensors, with the KV cache and sampling living inside the wrapped
C++ runtime.  Here the whole decode loop is a JAX program designed for TPU:

* **Stacked layers + ``lax.scan``**: all L transformer blocks live in one
  pytree with a leading layer axis, so XLA compiles ONE block and scans it —
  compile time stays flat as the model deepens, and remat slots in cleanly.
* **KV cache as a functional carry**: ``[L, B, S_max, H_kv, D]`` bf16
  buffers updated with ``lax.dynamic_update_slice`` at the decode position;
  one fused XLA program per decode step, weights resident in HBM.  The
  serving path's block pool (``[L, n_blocks, bs, H_kv, D]``,
  :func:`forward_paged`) is a carry of the LAYER scan too: each layer
  scatters its rows into the whole pool in place and attends over it in
  HBM, so no layer is ever sliced out of the pool or written back.
* **GQA** (n_kv_heads <= n_heads), **RoPE**, **RMSNorm**, **SwiGLU** — the
  Llama-2/3 block, dims kept multiples of 128 so matmuls tile onto the MXU.
* **TP via GSPMD**: ``param_pspecs`` shard attention heads and FFN hidden
  over the ``model`` mesh axis; jit with those shardings and XLA inserts the
  all-reduces on ICI (no hand-written collectives).
* **Sequence parallel**: :func:`forward_seq_parallel` runs the full forward
  under ``shard_map`` over the ``seq`` axis with ring attention
  (parallel/ring.py) — long-context prefill where no chip ever holds the
  whole sequence.

No egress in this environment, so weights are deterministic-random; real
checkpoints enter by filling the same pytree layout.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.types import TensorFormat, TensorsSpec
from .moe import ExpertsConfig
from .zoo import ModelBundle, register_model


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is: its attention (``window`` 0 = causal over all
    positions, w = over the last w, the token itself included; ``rope``:
    whether q and k are rotated; ``latent``: the cache holds one
    compressed row a token for all heads, ``LlamaConfig``'s latent
    sizes) and its feed-forward (``dense`` SwiGLU of ``ffn_hidden``, or
    ``experts``: ``LlamaConfig.experts``).

    ``shortcut`` names the two ends of an expert branch that runs BESIDE
    the layers between them: a kind that ``open``s takes the branch off
    its post-attention norm (``LlamaConfig.experts`` on the same input
    its dense FFN reads), a kind that ``close``s adds the branch's
    result to its output.  A published layer of two attention blocks
    and two dense FFNs with its experts across them is two such
    sub-layers, and the walk carries the open branch's tensor from one
    to the other.

    ``conv``: the layer's mixer is no attention at all but a gated short
    convolution (:func:`_conv_mixer`): its state is the last ``conv_taps
    - 1`` columns of its own input product, a fixed size a stream
    whatever its context, and lives in no block — cache class
    ``"conv"``."""

    window: int = 0
    rope: bool = True
    ffn: str = "dense"
    latent: bool = False
    shortcut: str = ""
    conv: bool = False

    def __post_init__(self):
        if self.ffn not in ("dense", "experts"):
            raise ValueError(f"ffn kind {self.ffn!r} (dense, experts)")
        if self.window < 0:
            raise ValueError(f"window {self.window}")
        if self.shortcut not in ("", "open", "close"):
            raise ValueError(f"shortcut {self.shortcut!r} (open, close)")
        if self.latent and (self.window or not self.rope):
            raise ValueError("latent attention is built causal over all "
                             "positions with its shared key rotated")
        if self.shortcut and self.ffn != "dense":
            raise ValueError("a shortcut's ends are dense-FFN layers: the "
                             "expert branch is the shortcut itself")
        if self.conv and (self.window or self.latent or self.shortcut
                          or not self.rope):
            raise ValueError("a convolution layer has no attention to "
                             "give a window, a latent cache or a rotation "
                             "(leave them at their defaults), and no "
                             "shortcut end is built on one")

    @property
    def name(self) -> str:
        """The key of this kind's stack under ``params["layers"]``."""
        if self.conv:
            return f"conv.{self.ffn}"
        attn = "latent" if self.latent else \
            f"window{self.window}" if self.window else "full"
        return ".".join([attn, "rope" if self.rope else "nope", self.ffn]
                        + ([self.shortcut] if self.shortcut else []))

    @property
    def cache(self) -> str:
        """The class of state this layer keeps for a stream: one pool leaf
        set (and one numbering of layers) a class."""
        return "conv" if self.conv else "latent" if self.latent \
            else "win" if self.window else "full"


@dataclasses.dataclass(frozen=True)
class WalkPlan:
    """How the layer walk is laid out in a program: the first ``prefix``
    layers one by one, then ``n_periods`` turns of a scan whose body
    holds ``period`` layers — ``prefix + period`` copies of the block,
    however deep the model."""

    prefix: int
    period: int
    n_periods: int


def walk_plan(kinds) -> WalkPlan:
    """The plan with the fewest block copies: the shortest prefix +
    period such that the layers after the prefix repeat with that period
    (leading layers of another kind, say one dense layer before the
    sparse ones, go in the prefix with the rest of their period)."""
    L = len(kinds)
    best = None
    for s in range(L + 1):
        rest = L - s
        for p in range(1, rest + 1):
            if rest % p or any(kinds[s + i] != kinds[s + i % p]
                               for i in range(rest)):
                continue
            if best is None or (s + p, p) < best[0]:
                best = ((s + p, p), WalkPlan(s, p, rest // p))
            break
    if best is None or best[1].n_periods == 1:
        return WalkPlan(L, 0, 0)   # nothing repeats: all in the prefix
    return best[1]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_hidden: int = 11008
    max_seq: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    #: head width where it is not ``dim / n_heads`` (0 = derived)
    head_size: int = 0
    #: RMSNorm over each head of q and k (a learned gain of head width),
    #: before the rotation
    qk_norm: bool = False
    #: one :class:`LayerKind` a layer; () = every layer full, rotated,
    #: dense — the program such a config compiles to is the one it
    #: compiled to before patterns existed (a pattern of that one kind
    #: is normalised to ())
    pattern: Tuple[LayerKind, ...] = ()
    experts: Optional[ExpertsConfig] = None
    #: latent attention (``LayerKind.latent``): the query's and the
    #: cache's compressed widths, a head's unrotated, rotated and value
    #: widths, and what the two normed compressions are multiplied by
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0
    #: taps of a convolution layer's depthwise causal filter
    #: (``LayerKind.conv``); a stream's state is the ``conv_taps - 1``
    #: columns before its next token
    conv_taps: int = 3

    def __post_init__(self):
        pat = tuple(self.pattern)
        if pat and len(pat) != self.n_layers:
            raise ValueError(f"pattern names {len(pat)} layers, the "
                             f"model has {self.n_layers}")
        if pat and all(k == LayerKind() for k in pat):
            pat = ()
        object.__setattr__(self, "pattern", pat)
        if any(k.ffn == "experts" or k.shortcut == "open" for k in pat) \
                and self.experts is None:
            raise ValueError("a layer of ffn kind 'experts', or one that "
                             "opens a shortcut, needs LlamaConfig.experts")
        if any(k.latent for k in pat) and not (
                self.q_lora_rank > 0 and self.kv_lora_rank > 0
                and self.qk_nope_dim > 0 and self.v_head_dim > 0
                and self.qk_rope_dim > 0 and self.qk_rope_dim % 2 == 0):
            raise ValueError("a latent-attention layer needs q_lora_rank, "
                             "kv_lora_rank, qk_nope_dim, qk_rope_dim "
                             "(even) and v_head_dim")
        if any(k.conv for k in pat):
            if self.conv_taps < 2:
                raise ValueError(f"conv_taps {self.conv_taps}: a "
                                 "convolution layer needs two or more")
            if all(k.conv for k in pat):
                raise ValueError("a model of convolution layers only has "
                                 "no paged cache: the paged path reads "
                                 "its block size off an attention pool")
        is_open = False
        for l, k in enumerate(pat):
            if not k.shortcut:
                continue
            if (k.shortcut == "open") == is_open:
                raise ValueError(
                    f"layer {l} would {k.shortcut} a shortcut that is "
                    f"{'open already' if is_open else 'not open'}")
            is_open = not is_open
        if is_open:
            raise ValueError("the last shortcut opened is never closed")

    @property
    def head_dim(self) -> int:
        return self.head_size or self.dim // self.n_heads

    @property
    def kv_lane_pack(self) -> int:
        """KV heads a row of the K/V pools holds side by side
        (ops/attention.py ``kv_lane_pack``: 2 for heads of 64) in a
        patterned model; 1 in the one-kind decoder, whose pool shards by
        KV head under tensor parallelism (:func:`paged_cache_pspecs`: a
        packed row would hold heads of two chips).  ``patterned`` is the
        predicate by which :func:`tp_divisibility_problems` refuses
        tensor parallelism, so a pool is packed exactly where it is never
        sharded.  This is the ONE place the layout is decided: the pool
        is born in it (:func:`init_paged_cache`) and
        ``ops/attention.py paged_attention`` reads what it is handed."""
        from ..ops.attention import kv_lane_pack

        return kv_lane_pack(self.n_kv_heads, self.head_dim) \
            if self.patterned else 1

    @property
    def kinds(self) -> Tuple[LayerKind, ...]:
        return self.pattern or (LayerKind(),) * self.n_layers

    @property
    def n_window_layers(self) -> int:
        return sum(1 for k in self.pattern if k.window)

    @property
    def n_latent_layers(self) -> int:
        return sum(1 for k in self.pattern if k.latent)

    @property
    def n_conv_layers(self) -> int:
        return sum(1 for k in self.pattern if k.conv)

    @property
    def n_full_layers(self) -> int:
        return (self.n_layers - self.n_window_layers - self.n_latent_layers
                - self.n_conv_layers)

    @property
    def has_shortcut(self) -> bool:
        return any(k.shortcut for k in self.pattern)

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches a token: the normed latent and
        the one rotated key all heads share."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def max_window(self) -> int:
        return max((k.window for k in self.pattern), default=0)

    @property
    def patterned(self) -> bool:
        """Whether the layers are walked by :func:`_walk_pattern` over
        per-kind stacks (a pattern, or q/k norm, which the one-kind
        layout has no leaves for)."""
        return bool(self.pattern) or self.qk_norm


def refuse_pattern(cfg: LlamaConfig, what: str) -> None:
    """One wording for every path that computes only the one-kind
    decoder: it refuses a patterned model instead of serving it wrong."""
    if cfg.patterned:
        raise NotImplementedError(
            f"{what} computes the one-kind decoder only (every layer "
            "full attention over K and V per head, rotated, dense FFN, "
            f"no q/k norm); this model has {pattern_traits(cfg)} — "
            "serve it with serve:continuous or run llama.forward")


def pattern_traits(cfg: LlamaConfig) -> str:
    """What makes ``cfg`` a patterned model, for a refusal's reason."""
    kinds = cfg.pattern
    traits = [name for name, has in (
        ("window layers", any(k.window for k in kinds)),
        ("unrotated layers", any(not k.rope for k in kinds)),
        ("sparse experts", cfg.experts is not None),
        ("latent attention (one cache row for all heads)",
         any(k.latent for k in kinds)),
        ("a shortcut expert branch across sub-layers", cfg.has_shortcut),
        ("convolution layers (state owned by the slot, in no block)",
         any(k.conv for k in kinds)),
        ("q/k norm", cfg.qk_norm)) if has]
    return "a layer pattern: " + ", ".join(traits or ["mixed layers"])


def window_ring_blocks(cfg: LlamaConfig, block_size: int,
                       prefill_chunk: int) -> int:
    """Blocks a slot's ring holds for the window layers (0 without any):
    the window, one prefill chunk written ahead of it, and one block
    for where the window starts inside a block.  A window layer never
    holds more for a slot, whatever the context (docs/SERVING.md)."""
    if not cfg.max_window:
        return 0
    bs = max(1, int(block_size))
    return (-(-cfg.max_window // bs) + -(-int(prefill_chunk) // bs) + 1)


#: Named size presets.  ``llama2_7b`` is the reference benchmark config #5
#: shape; the tiny presets serve tests and the CPU-mesh dry run.
PRESETS: Dict[str, LlamaConfig] = {
    "llama2_7b": LlamaConfig(),
    "llama_tiny": LlamaConfig(
        vocab=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=256, max_seq=256,
    ),
    "llama_small": LlamaConfig(
        vocab=2048, dim=512, n_layers=4, n_heads=8, n_kv_heads=4,
        ffn_hidden=1024, max_seq=1024,
    ),
    # the patterned walk at toy size: window-window-window-full twice,
    # layer 0 dense and the rest sparse (16 experts, 4 a token, a shared
    # one, this process holding experts 4..7), heads wider than dim/heads,
    # q/k norm, no rotation on the full layers
    "hybrid_moe_tiny": LlamaConfig(
        vocab=512, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        ffn_hidden=192, max_seq=256, rope_theta=1e6, head_size=32,
        qk_norm=True,
        pattern=tuple(
            LayerKind(window=0 if l % 4 == 3 else 8, rope=l % 4 != 3,
                      ffn="dense" if l == 0 else "experts")
            for l in range(8)),
        experts=ExpertsConfig(n_experts=16, top_k=4, hidden=32, shared=1,
                              scale=2.5, held_first=4, held_count=4),
    ),
    # latent attention and a shortcut expert branch at toy size: two
    # published layers = four sub-layers of period 2 (open, close), a
    # softmax router over 16 routed + 8 identity experts, 4 a token,
    # this process holding experts 4..7
    "latent_shortcut_tiny": LlamaConfig(
        vocab=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=1,
        ffn_hidden=128, max_seq=256, rope_theta=1e7,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, q_lora_scale=2.0 ** 0.5, kv_lora_scale=2.0 ** 0.5,
        pattern=tuple(
            LayerKind(latent=True, shortcut="close" if l % 2 else "open")
            for l in range(4)),
        experts=ExpertsConfig(n_experts=16, top_k=4, hidden=32,
                              scoring="softmax", norm_topk=False,
                              scale=6.0, zero_experts=8, held_first=4,
                              held_count=4),
    ),
    # gated short-convolution layers beside attention at toy size: ten
    # layers in the order conv conv | attn conv conv conv | attn conv
    # conv conv, the first two dense and the rest sparse (16 experts, 4 a
    # token, every one held, the chosen weights renormalised with an
    # epsilon), q/k norm, heads of width 16
    "conv_moe_tiny": LlamaConfig(
        vocab=512, dim=64, n_layers=10, n_heads=4, n_kv_heads=2,
        ffn_hidden=192, max_seq=256, rope_theta=1e6, qk_norm=True,
        pattern=tuple(
            LayerKind(conv=l < 2 or l % 4 != 2,
                      ffn="dense" if l < 2 else "experts")
            for l in range(10)),
        experts=ExpertsConfig(n_experts=16, top_k=4, hidden=32,
                              norm_eps=1e-6),
    ),
}


def init_params(cfg: LlamaConfig, seed: int = 0, dtype="float32") -> Dict:
    """Deterministic-random params; layer weights stacked on a leading axis.

    ``dtype`` is the storage dtype of the generated weights.  7B-scale runs
    pass ``bfloat16`` so the full parameter set is materialized directly on
    device at 2 bytes/param (13.5 GB — fits one v5e chip's HBM; an f32
    intermediate would not), standing in for a real checkpoint upload the
    zero-egress environment can't do.  Real checkpoints enter by filling
    the same pytree layout.
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(seed), 3)

    def norm_init(key, shape, fan_in):
        scale = np.sqrt(2.0 / max(1, fan_in)).astype(np.float32)
        return jax.random.normal(key, shape, dt) * scale.astype(dt)

    L, D, H, Hkv, F = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.ffn_hidden)
    hd = cfg.head_dim
    if cfg.patterned:
        return {
            "embed": norm_init(k_embed, (cfg.vocab, D), D) * 0.5,
            "layers": _init_stacks(cfg, k_layers, norm_init),
            "ln_out": np.ones((D,), np.float32),
            "lm_head": norm_init(k_out, (D, cfg.vocab), D),
        }
    ks = jax.random.split(k_layers, 7)
    layers = {
        "wq": norm_init(ks[0], (L, D, H * hd), D),
        "wk": norm_init(ks[1], (L, D, Hkv * hd), D),
        "wv": norm_init(ks[2], (L, D, Hkv * hd), D),
        "wo": norm_init(ks[3], (L, H * hd, D), H * hd),
        "w_gate": norm_init(ks[4], (L, D, F), D),
        "w_up": norm_init(ks[5], (L, D, F), D),
        "w_down": norm_init(ks[6], (L, F, D), F),
        "ln_attn": np.ones((L, D), np.float32),
        "ln_mlp": np.ones((L, D), np.float32),
    }
    return {
        "embed": norm_init(k_embed, (cfg.vocab, D), D) * 0.5,
        "layers": layers,
        "ln_out": np.ones((D,), np.float32),
        "lm_head": norm_init(k_out, (D, cfg.vocab), D),
    }


def stack_shapes(cfg: LlamaConfig, kind: LayerKind) -> Dict[str, tuple]:
    """Leaf name -> shape of ONE layer of ``kind`` (a stack adds the
    leading axis): the checkpoint layout of a patterned model.  Matrices
    are ``[in, out]``; ``ln_*``, ``q_norm``/``k_norm``, ``w_router`` and
    ``router_bias`` are float32 whatever the weights' type."""
    D, H, Hkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {"ln_attn": (D,), "ln_mlp": (D,)}
    if kind.conv:
        # a convolution layer: ``w_in`` makes the two gates and the value
        # (thirds b | c | v of its output), ``w_conv`` holds the filter,
        # a tap a row (the published [D, taps] transposed: D on the
        # lanes), ``w_out`` writes into the stream
        out.update(w_in=(D, 3 * D), w_conv=(cfg.conv_taps, D), w_out=(D, D))
    elif kind.latent:
        # a latent layer: the query and the cache each go through a
        # normed compression; ``wkv_a`` makes the latent and the one
        # rotated key, ``wkv_b`` expands the latent to every head's
        # unrotated key and value
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        out.update(wq_a=(D, rq), q_a_norm=(rq,), wq_b=(rq, H * (dn + dr)),
                   wkv_a=(D, rkv + dr), kv_a_norm=(rkv,),
                   wkv_b=(rkv, H * (dn + dv)), wo=(H * dv, D))
    else:
        out.update(wq=(D, H * hd), wk=(D, Hkv * hd), wv=(D, Hkv * hd),
                   wo=(H * hd, D))
        if cfg.qk_norm:
            out.update(q_norm=(hd,), k_norm=(hd,))
    if kind.ffn == "dense":
        F = cfg.ffn_hidden
        out.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
    if kind.ffn == "experts" or kind.shortcut == "open":
        ex = cfg.experts
        E, Fe, Fs = ex.n_held, ex.hidden, ex.shared * ex.hidden
        out.update(w_router=(D, ex.n_router), router_bias=(ex.n_router,),
                   we_gate=(E, D, Fe), we_up=(E, D, Fe), we_down=(E, Fe, D))
        if Fs:
            out.update(ws_gate=(D, Fs), ws_up=(D, Fs), ws_down=(Fs, D))
    return out


#: leaves kept in float32 (gains, and the router: see models/moe.py)
F32_LEAVES = ("ln_attn", "ln_mlp", "q_norm", "k_norm", "q_a_norm",
              "kv_a_norm", "w_router", "router_bias")


def kind_layers(cfg: LlamaConfig) -> Dict[str, List[int]]:
    """Kind name -> the layers of that kind, in order: layer ``l`` is
    entry ``kind_layers(cfg)[cfg.kinds[l].name].index(l)`` of its
    kind's stack."""
    out: Dict[str, List[int]] = {}
    for l, kind in enumerate(cfg.kinds):
        out.setdefault(kind.name, []).append(l)
    return out


def _init_stacks(cfg: LlamaConfig, key, norm_init) -> Dict:
    """``params["layers"]`` of a patterned model: one stack a kind,
    ``{kind.name: {leaf: [n_layers_of_kind, ...]}}``."""
    import jax

    by_name = {k.name: k for k in cfg.kinds}
    stacks = {}
    for i, (name, layers) in enumerate(sorted(kind_layers(cfg).items())):
        shapes = stack_shapes(cfg, by_name[name])
        ks = jax.random.split(jax.random.fold_in(key, i), len(shapes))
        n = len(layers)
        stack = {}
        for k, (leaf, shape) in zip(ks, sorted(shapes.items())):
            if leaf == "router_bias":
                # small and non-zero, so the choice is seen to use it
                stack[leaf] = 0.02 * jax.random.normal(
                    k, (n,) + shape, "float32")
            elif leaf == "w_router":
                stack[leaf] = jax.random.normal(
                    k, (n,) + shape, "float32") * np.float32(
                        shape[0] ** -0.5)
            elif leaf in F32_LEAVES:
                stack[leaf] = np.ones((n,) + shape, np.float32)
            else:
                stack[leaf] = norm_init(k, (n,) + shape, shape[-2])
        stacks[name] = stack
    return stacks


def _init_params_quant(cfg: LlamaConfig, seed: int, gen_dtype,
                       qmat, q2d, suffix: str, groups=None) -> Dict:
    """Generate-then-quantize one matrix at a time.

    ``quantize_*(init_params(cfg))`` needs the full-precision tree AND
    the growing quantized tree resident together — at 7B that transient
    (13.5 GB bf16 + quantized outputs) overflows a 16 GB v5e chip, which
    the round-3 on-chip session hit as RESOURCE_EXHAUSTED.  Here each big
    mat is generated, quantized (donated), and freed before the next is
    drawn: peak HBM ~ final quantized tree + ONE bf16 mat.  Draws the
    identical RNG stream as :func:`init_params` — key order and shapes
    here are the single place that invariant lives for BOTH int8 and
    int4 — so the result is exactly
    ``quantize_*(init_params(cfg, seed, gen_dtype))`` (asserted by tests
    on the small presets)."""
    import jax
    import jax.numpy as jnp

    _refuse_quant(cfg)
    dt = jnp.dtype(gen_dtype)
    k_embed, k_layers, k_out = jax.random.split(jax.random.PRNGKey(seed), 3)

    def norm_init(key, shape, fan_in):
        scale = np.sqrt(2.0 / max(1, fan_in)).astype(np.float32)
        return jax.random.normal(key, shape, dt) * scale.astype(dt)

    L, D, H, Hkv, F = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.ffn_hidden)
    hd = cfg.head_dim
    ks = jax.random.split(k_layers, 7)
    shapes = {
        "wq": ((L, D, H * hd), D),
        "wk": ((L, D, Hkv * hd), D),
        "wv": ((L, D, Hkv * hd), D),
        "wo": ((L, H * hd, D), H * hd),
        "w_gate": ((L, D, F), D),
        "w_up": ((L, D, F), D),
        "w_down": ((L, F, D), F),
    }
    import jax.numpy as _jnp

    qlay: Dict = {
        "ln_attn": np.ones((L, D), np.float32),
        "ln_mlp": np.ones((L, D), np.float32),
    }
    key_of = {name: ks[i] for i, name in enumerate(_QUANT_MATS)}
    if groups is None:
        groups = tuple((name, (name,)) for name in _QUANT_MATS)
    for gname, members in groups:
        # quantize each member with ITS ORIGINAL DONATED — per-output-
        # channel scales make member-wise quantization exactly equal to
        # quantizing the concatenation, so fused groups concatenate the
        # PACKED outputs (0.5-1 byte/param) and the one-bf16-mat peak
        # holds for fused layouts too
        qs = []
        for name in members:
            shape, fan = shapes[name]
            qs.append(qmat(norm_init(key_of[name], shape, fan)))
        if len(qs) == 1:
            q, s = qs[0]
        else:
            q = _jnp.concatenate([p for p, _ in qs], axis=-1)
            s = _jnp.concatenate([sc for _, sc in qs], axis=-1)
        qlay[gname + suffix] = q
        qlay[gname + "_s"] = s
    q, s = q2d(norm_init(k_out, (D, cfg.vocab), D))
    return {
        "embed": norm_init(k_embed, (cfg.vocab, D), D) * 0.5,
        "layers": qlay,
        "ln_out": np.ones((D,), np.float32),
        "lm_head" + suffix: q,
        "lm_head_s": s,
    }


def init_params_int8(cfg: LlamaConfig, seed: int = 0,
                     gen_dtype="bfloat16") -> Dict:
    """int8 per-mat generate-quantize-donate init (see
    :func:`_init_params_quant`)."""
    return _init_params_quant(cfg, seed, gen_dtype, _qmat_layered(),
                              _qmat_2d(), "_q")


def init_params_int4(cfg: LlamaConfig, seed: int = 0,
                     gen_dtype="bfloat16") -> Dict:
    """int4 generate-quantize-pack-donate init (see
    :func:`_init_params_quant`), grouped per ``_INT4_GROUPS`` — members
    quantize one at a time (donated) and only the PACKED nibbles
    concatenate, so the one-bf16-mat HBM peak holds."""
    import jax

    from ..ops import int4_matmul as _i4

    q2d = jax.jit(_i4.quantize_int4, donate_argnums=(0,))
    return _init_params_quant(cfg, seed, gen_dtype, _qmat4_layered(),
                              q2d, "_p", groups=_INT4_GROUPS)


def load_checkpoint(path: str, cfg: Optional[LlamaConfig] = None,
                    dtype="bfloat16") -> Tuple[Dict, LlamaConfig]:
    """Fill the documented pytree layout from a REAL checkpoint file.

    ``path``: a ``.safetensors`` file, a HF sharded checkpoint directory /
    ``*.safetensors.index.json``, an ``.npz`` (models/checkpoint.py), or a
    llama.cpp ``.gguf`` (models/gguf.py — F32/F16/BF16, config from the
    ``llama.*`` metadata keys, RoPE layout converted).
    Accepts HF ``model.layers.N.self_attn.q_proj.weight`` naming (weights
    transposed from [out,in] linear layout to this module's [in,out]
    matmul layout — no RoPE re-permutation is needed because :func:`_rope`
    uses the same rotate-half convention HF checkpoints are stored for) or
    this module's own stacked naming (``layers.wq`` etc., the npz
    round-trip).  Per-layer tensors are stacked on the leading layer axis
    for the ``lax.scan`` block.

    ``cfg=None`` reads a HF ``config.json`` next to the checkpoint; without
    one, dims are inferred from tensor shapes with head_dim assumed 128
    (the Llama convention) — pass an explicit cfg when that's wrong.
    Returns ``(params, cfg)``; weights cast to ``dtype`` (norms stay f32,
    matching :func:`init_params`).
    """
    import os

    from . import checkpoint as ckpt

    if cfg is not None:
        refuse_pattern(cfg, "load_checkpoint (the HF/gguf name mapping)")
    dt = _resolve_param_dtype(dtype)
    if path.endswith(".gguf"):
        params, cfg, _tok = _load_gguf(path, cfg, dt)
        return params, cfg
    tensors = ckpt.load_tensors(path)

    if "embed" in tensors and "layers.wq" in tensors:  # native stacked npz
        if cfg is None:
            cfg = _read_config_json(path) or _infer_config_native(tensors)
        params = {
            "embed": np.asarray(tensors["embed"]).astype(dt),
            "layers": {k.split(".", 1)[1]:
                       np.asarray(tensors[k]).astype(
                           np.float32 if k.startswith("layers.ln") else dt)
                       for k in tensors if k.startswith("layers.")},
            "ln_out": np.asarray(tensors["ln_out"]).astype(np.float32),
            "lm_head": np.asarray(tensors["lm_head"]).astype(dt),
        }
        return params, cfg

    if cfg is None:
        cfg = _infer_config_hf(path, tensors)

    def get(name):
        if name not in tensors:
            raise ckpt.CheckpointError(
                f"{path}: missing tensor {name!r} "
                f"(have {len(tensors)} tensors, e.g. "
                f"{sorted(tensors)[:3]})")
        return np.asarray(tensors[name])

    def stack_T(fmt):
        return np.stack([get(fmt.format(i)).T.astype(dt)
                         for i in range(cfg.n_layers)])

    def stack_f32(fmt):
        return np.stack([get(fmt.format(i)).astype(np.float32)
                         for i in range(cfg.n_layers)])

    p = "model.layers.{}."
    layers = {
        "wq": stack_T(p + "self_attn.q_proj.weight"),
        "wk": stack_T(p + "self_attn.k_proj.weight"),
        "wv": stack_T(p + "self_attn.v_proj.weight"),
        "wo": stack_T(p + "self_attn.o_proj.weight"),
        "w_gate": stack_T(p + "mlp.gate_proj.weight"),
        "w_up": stack_T(p + "mlp.up_proj.weight"),
        "w_down": stack_T(p + "mlp.down_proj.weight"),
        "ln_attn": stack_f32(p + "input_layernorm.weight"),
        "ln_mlp": stack_f32(p + "post_attention_layernorm.weight"),
    }
    embed = get("model.embed_tokens.weight").astype(dt)
    if "lm_head.weight" in tensors:
        lm_head = get("lm_head.weight").T.astype(dt)
    else:  # tied embeddings
        lm_head = np.ascontiguousarray(embed.T)
    params = {
        "embed": embed,
        "layers": layers,
        "ln_out": get("model.norm.weight").astype(np.float32),
        "lm_head": lm_head,
    }
    _check_shapes(params, cfg, path)
    return params, cfg


def _np_bf16():
    from ..core.types import bfloat16

    return bfloat16


def _resolve_param_dtype(dtype) -> np.dtype:
    """ONE home for the checkpoint param-dtype rule (bfloat16 through the
    core.types alias, anything else verbatim) — load_checkpoint and the
    gguf bundle path must never drift apart here."""
    if dtype == "float32":
        return np.dtype("float32")
    if dtype == "bfloat16":
        return _np_bf16()
    return np.dtype(dtype)


def _rope_permute(w: np.ndarray, n_heads: int) -> np.ndarray:
    """ggml/Meta interleaved-pair RoPE layout -> rotate-half layout (the
    permutation HF applies converting Meta checkpoints; models/llama.py's
    _rope is rotate-half).  ``w``: [n_heads*head_dim, in_features]."""
    out, dim2 = w.shape
    hd = out // n_heads
    return np.ascontiguousarray(
        w.reshape(n_heads, hd // 2, 2, dim2).swapaxes(1, 2).reshape(
            out, dim2))


def _load_gguf(path: str, cfg: Optional[LlamaConfig],
               dt) -> Tuple[Dict, LlamaConfig]:
    """llama.cpp GGUF -> the stacked pytree (reference: the llamacpp
    sub-plugin's model format, SURVEY §2.4)."""
    from . import gguf

    meta, tensors = gguf.read(path)

    def get(name):
        if name not in tensors:
            raise gguf.GGUFError(
                f"{path}: missing tensor {name!r} (have e.g. "
                f"{sorted(tensors)[:3]})")
        return np.asarray(tensors[name])

    if cfg is None:
        arch = str(meta.get("general.architecture", "llama"))

        def m(key, default=None):
            v = meta.get(f"{arch}.{key}", default)
            if v is None:
                raise gguf.GGUFError(
                    f"{path}: metadata {arch}.{key} missing and no cfg "
                    "given")
            return v

        vocab = get("token_embd.weight").shape[0]
        n_heads = int(m("attention.head_count"))
        ctx = int(m("context_length", 4096))
        if ctx > 8192:
            from ..core.log import logger

            logger(__name__).warning(
                "%s: clamping context_length %d to 8192 (KV-cache HBM "
                "budget); pass custom=max_seq:%d to tensor_filter to "
                "raise it", path, ctx, ctx)
        cfg = LlamaConfig(
            vocab=vocab,
            dim=int(m("embedding_length")),
            n_layers=int(m("block_count")),
            n_heads=n_heads,
            n_kv_heads=int(m("attention.head_count_kv", n_heads)),
            ffn_hidden=int(m("feed_forward_length")),
            max_seq=min(ctx, 8192),
            rope_theta=float(m("rope.freq_base", 10000.0)),
            norm_eps=float(m("attention.layer_norm_rms_epsilon", 1e-5)),
        )

    def stack(fmt, heads=None):
        mats = []
        for i in range(cfg.n_layers):
            w = get(fmt.format(i))
            if heads is not None:
                w = _rope_permute(w, heads)
            mats.append(w.T.astype(dt))
        return np.stack(mats)

    def stack_norm(fmt):
        return np.stack([get(fmt.format(i)).astype(np.float32)
                         for i in range(cfg.n_layers)])

    p = "blk.{}."
    layers = {
        "wq": stack(p + "attn_q.weight", heads=cfg.n_heads),
        "wk": stack(p + "attn_k.weight", heads=cfg.n_kv_heads),
        "wv": stack(p + "attn_v.weight"),
        "wo": stack(p + "attn_output.weight"),
        "w_gate": stack(p + "ffn_gate.weight"),
        "w_up": stack(p + "ffn_up.weight"),
        "w_down": stack(p + "ffn_down.weight"),
        "ln_attn": stack_norm(p + "attn_norm.weight"),
        "ln_mlp": stack_norm(p + "ffn_norm.weight"),
    }
    embed = get("token_embd.weight").astype(dt)
    if "output.weight" in tensors:
        lm_head = get("output.weight").T.astype(dt)
    else:  # tied embeddings
        lm_head = np.ascontiguousarray(embed.T)
    params = {
        "embed": embed,
        "layers": layers,
        "ln_out": get("output_norm.weight").astype(np.float32),
        "lm_head": lm_head,
    }
    _check_shapes(params, cfg, path)
    # the vocab rode along in the SAME metadata parse — build the
    # tokenizer here instead of re-reading the file; returned alongside
    # the weights so build_from_checkpoint can attach it to the bundle
    tok = None
    if "tokenizer.ggml.tokens" in meta:
        from .tokenizer import SentencePieceTokenizer

        tok = SentencePieceTokenizer.from_gguf_meta(meta)
    return params, cfg, tok


def _read_config_json(path: str) -> Optional[LlamaConfig]:
    """HF-style config.json next to (or inside) ``path``, if present."""
    import json
    import os

    base = path if os.path.isdir(path) else os.path.dirname(path)
    cfg_path = os.path.join(base, "config.json")
    if not os.path.exists(cfg_path):
        return None
    with open(cfg_path) as f:
        c = json.load(f)
    return LlamaConfig(
        vocab=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c.get("num_key_value_heads",
                         c["num_attention_heads"]),
        ffn_hidden=c["intermediate_size"],
        max_seq=min(c.get("max_position_embeddings", 4096), 8192),
        rope_theta=float(c.get("rope_theta", 10000.0)),
        norm_eps=float(c.get("rms_norm_eps", 1e-5)),
    )


def _infer_config_hf(path: str, tensors: Dict) -> LlamaConfig:
    cfg = _read_config_json(path)
    if cfg is not None:
        return cfg
    # shape inference: head_dim is 128 by Llama convention
    from . import checkpoint as ckpt

    try:
        vocab, dim = tensors["model.embed_tokens.weight"].shape
        layer_ids = [int(k.split(".")[2]) for k in tensors
                     if k.startswith("model.layers.")]
        n_layers = 1 + max(layer_ids)
        ffn = tensors["model.layers.0.mlp.gate_proj.weight"].shape[0]
        kv_out = tensors["model.layers.0.self_attn.k_proj.weight"].shape[0]
    except (KeyError, ValueError) as e:
        raise ckpt.CheckpointError(
            f"{path}: not a Llama-family checkpoint (no config.json and "
            f"HF tensor names absent: {e}; have e.g. "
            f"{sorted(tensors)[:3]})") from e
    hd = 128 if dim % 128 == 0 and dim >= 128 else 64
    return LlamaConfig(vocab=vocab, dim=dim, n_layers=n_layers,
                       n_heads=dim // hd, n_kv_heads=kv_out // hd,
                       ffn_hidden=ffn)


def _infer_config_native(tensors: Dict) -> LlamaConfig:
    L, D, qout = tensors["layers.wq"].shape
    vocab = tensors["embed"].shape[0]
    F = tensors["layers.w_gate"].shape[2]
    kvout = tensors["layers.wk"].shape[2]
    hd = 128 if D % 128 == 0 and D >= 128 else 64
    if qout % hd:
        hd = qout  # degenerate tiny models: one head
    return LlamaConfig(vocab=vocab, dim=D, n_layers=L, n_heads=qout // hd,
                       n_kv_heads=kvout // hd, ffn_hidden=F)


def _check_shapes(params: Dict, cfg: LlamaConfig, path: str) -> None:
    L, D, H, Hkv, F = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.ffn_hidden)
    hd = cfg.head_dim
    want = {
        ("embed",): (cfg.vocab, D),
        ("layers", "wq"): (L, D, H * hd),
        ("layers", "wk"): (L, D, Hkv * hd),
        ("layers", "wv"): (L, D, Hkv * hd),
        ("layers", "wo"): (L, H * hd, D),
        ("layers", "w_gate"): (L, D, F),
        ("layers", "w_up"): (L, D, F),
        ("layers", "w_down"): (L, F, D),
        ("lm_head",): (D, cfg.vocab),
    }
    for keys, shape in want.items():
        node = params
        for k in keys:
            node = node[k]
        if tuple(node.shape) != shape:
            raise ValueError(
                f"{path}: {'.'.join(keys)} has shape {tuple(node.shape)}, "
                f"config wants {shape} — wrong config for this checkpoint?")


_QUANT_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@functools.cache
def _qmat_layered():
    """jit: [L, in, out] weights -> (int8 [L, in, out], f32 [L, 1, out])
    per-output-channel scales; input donated so the full-precision buffer
    frees as soon as its int8 replacement lands."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def qmat(w):
        def one(wl):
            w32 = wl.astype(jnp.float32)
            s = jnp.maximum(jnp.abs(w32).max(axis=0, keepdims=True) / 127.0,
                            1e-8)
            q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
            return q, s
        return jax.lax.map(one, w)

    return qmat


@functools.cache
def _qmat_2d():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def qmat2d(w):  # [D, vocab]
        w32 = w.astype(jnp.float32)
        s = jnp.maximum(jnp.abs(w32).max(axis=0, keepdims=True) / 127.0,
                        1e-8)
        q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
        return q, s

    return qmat2d


@functools.cache
def _qmat4_layered():
    """jit: [L, in, out] weights -> (packed int4 [L, in/2, out] int8,
    f32 [L, 1, out] scales); input donated."""
    import jax

    from ..ops import int4_matmul as _i4

    @functools.partial(jax.jit, donate_argnums=(0,))
    def qmat(w):
        return jax.lax.map(_i4.quantize_int4, w)

    return qmat


#: int4 fused-mat grouping: per-call fixed cost halves the Pallas
#: kernel's throughput on the 4096-out mats (8.4 MB/call measured
#: 176 GB/s vs 373 at >=22 MB), so q/k/v and gate/up quantize into ONE
#: packed mat each — per-output-channel scales make the concatenation
#: exactly equal to quantizing separately.
_INT4_GROUPS = (("wqkv", ("wq", "wk", "wv")), ("wo", ("wo",)),
                ("wgu", ("w_gate", "w_up")), ("w_down", ("w_down",)))


def quantize_int4_params(params: Dict) -> Dict:
    """Weight-only int4 with per-output-channel scales, nibble-packed
    for the Pallas decode kernel (ops/int4_matmul.py): 0.5 bytes/param
    on the seven big mats + lm_head -> ~3.4 GB/token at 7B vs 6.5 int8.
    q/k/v and gate/up fuse into single packed mats (_INT4_GROUPS).
    Same on-device, donated discipline as :func:`quantize_int8`.
    """
    import jax
    import jax.numpy as jnp

    from ..ops import int4_matmul as _i4

    qmat = _qmat4_layered()
    q2d = jax.jit(_i4.quantize_int4, donate_argnums=(0,))
    lay = params["layers"]
    qlay: Dict = {"ln_attn": lay["ln_attn"], "ln_mlp": lay["ln_mlp"]}
    for name, members in _INT4_GROUPS:
        # member-wise quantize with each ORIGINAL donated (the 7B HBM
        # discipline: full-precision mats free as their packed
        # replacements land); per-output-channel scales make this
        # exactly equal to quantizing the concatenation, so only the
        # tiny packed nibbles + scales concatenate
        qs = [qmat(jnp.asarray(lay[k])) for k in members]
        if len(qs) == 1:
            p, s = qs[0]
        else:
            p = jnp.concatenate([q for q, _ in qs], axis=-1)
            s = jnp.concatenate([sc for _, sc in qs], axis=-1)
        qlay[name + "_p"] = p
        qlay[name + "_s"] = s  # [L, 1, out]
    p, s = q2d(jnp.asarray(params["lm_head"]))
    return {
        "embed": params["embed"],
        "layers": qlay,
        "ln_out": params["ln_out"],
        "lm_head_p": p,
        "lm_head_s": s,  # [1, vocab]
    }


def quantize_int8(params: Dict) -> Dict:
    """Weight-only int8 with per-output-channel scales.

    The decode step is HBM-bandwidth-bound (every generated token streams
    the full parameter set through the MXU); storing the seven big layer
    mats + lm_head as int8 halves bytes/token vs bf16.  Consumption is
    scale-AFTER-dot (see :func:`_mm`): the int8->bf16 convert fuses into
    the dot's operand read so dequant costs no extra HBM traffic, which
    premultiplying the scale would break.  Norms and the embedding table
    (gather — tiny per-token traffic) stay full precision.

    Quantization runs ON DEVICE via jit: 7B params are materialized in
    HBM (13.5 GB bf16) and must never round-trip to the host — a numpy
    path would pull the full set over D2H and expand it to f32.  The
    lax.map over the layer axis keeps the f32 transient to ONE layer's
    mat, and input donation releases each original right as its int8
    replacement lands.
    """
    import jax.numpy as jnp

    qmat, qmat2d = _qmat_layered(), _qmat_2d()
    lay = params["layers"]
    qlay: Dict = {"ln_attn": lay["ln_attn"], "ln_mlp": lay["ln_mlp"]}
    for k in _QUANT_MATS:
        q, s = qmat(jnp.asarray(lay[k]))
        qlay[k + "_q"] = q
        qlay[k + "_s"] = s  # [L, 1, out]
    q, s = qmat2d(jnp.asarray(params["lm_head"]))
    return {
        "embed": params["embed"],
        "layers": qlay,
        "ln_out": params["ln_out"],
        "lm_head_q": q,
        "lm_head_s": s,  # [1, vocab]
    }


def _refuse_quant(cfg: LlamaConfig) -> None:
    if cfg.patterned:
        raise ValueError(
            "quant:int8|int4 is weight-only quantization of the one-kind "
            "decoder's seven matrices; a patterned model's stacks "
            "(expert matrices, latent projections) have no quantized "
            f"layout yet, and this model has {pattern_traits(cfg)}")


def _apply_quant(params: Dict, opts: Dict) -> Dict:
    """Shared ``custom=quant:...`` handling for the zoo builders."""
    quant = str(opts.get("quant", "")).lower()
    if quant == "int8":
        return quantize_int8(params)
    if quant == "int4":
        return quantize_int4_params(params)
    if quant:
        raise ValueError(f"unsupported quant {quant!r} (int8, int4)")
    return params


def _mm(h, lp: Dict, key: str, dt):
    """``h @ W`` for a layer dict that stores ``key`` either full-precision
    or as int8+scale leaves (``key_q``/``key_s``).

    Quantized mats are applied SCALE-AFTER-DOT: ``(h @ q.astype(dt)) * s``,
    exact algebra for per-output-channel scales.  The int8->bf16 convert
    fuses into the dot's operand read, so the weights stream through the
    MXU at 1 byte/param; premultiplying the scale instead
    (``h @ (q.astype(dt) * s)``) forces XLA to materialize a full bf16
    copy of every mat in HBM.  int8 values are integers <= 127, exactly
    representable in bf16, so postscale is also the more accurate order.
    """
    if key + "_q" in lp:
        return (h @ lp[key + "_q"].astype(dt)) * lp[key + "_s"].astype(dt)
    if key + "_p" in lp:  # int4 nibble-packed (ops/int4_matmul.py)
        from ..ops.int4_matmul import matmul_int4

        B, T, D = h.shape
        y = matmul_int4(h.reshape(B * T, D), lp[key + "_p"],
                        lp[key + "_s"])
        return y.reshape(B, T, -1)
    return h @ lp[key].astype(dt)


def _lm_head(params: Dict, x, dt):
    import jax.numpy as jnp

    if "lm_head_q" in params:
        # scale-after-dot (see _mm); scales are f32 so the output is
        # promoted to f32 by the multiply itself
        y = x @ params["lm_head_q"].astype(dt)
        return y.astype(jnp.float32) * params["lm_head_s"]
    if "lm_head_p" in params:
        from ..ops.int4_matmul import matmul_int4

        # out_dtype=f32: logits must not round through bf16 — near-tie
        # greedy argmax has to match the int8/dense paths' precision
        B, T, D = x.shape
        y = matmul_int4(x.reshape(B * T, D), params["lm_head_p"],
                        params["lm_head_s"], out_dtype=jnp.float32)
        return y.reshape(B, T, -1)
    return (x @ params["lm_head"].astype(dt)).astype(jnp.float32)


def param_pspecs(quant: bool = False) -> Dict:
    """TP shardings over the ``model`` mesh axis: split heads / FFN hidden
    on the contraction-free dim, so each matmul is local and XLA all-reduces
    the block output once (Megatron layout, GSPMD-inserted collectives).
    ``quant=True``/``"int8"`` returns specs matching the
    :func:`quantize_int8` pytree, ``quant="int4"`` the
    :func:`quantize_int4_params` pytree (scales follow their mat's OUT
    axis; in-sharded mats keep scales replicated since scales are
    per-output-channel)."""
    from jax.sharding import PartitionSpec as P

    if not quant:
        return {
            "embed": P(None, None),
            "layers": {
                "wq": P(None, None, "model"),
                "wk": P(None, None, "model"),
                "wv": P(None, None, "model"),
                "wo": P(None, "model", None),
                "w_gate": P(None, None, "model"),
                "w_up": P(None, None, "model"),
                "w_down": P(None, "model", None),
                "ln_attn": P(None, None),
                "ln_mlp": P(None, None),
            },
            "ln_out": P(None),
            "lm_head": P(None, "model"),
        }
    # int8 stores q-mats under _q; int4 packs nibbles under _p (with
    # q|k|v and gate|up FUSED along the out axis, _INT4_GROUPS) — the
    # [L, in(/2), out] axis meaning is shared, so out-sharded mats split
    # 'model' on the last axis either way (int4 TP runs through the
    # shardable XLA reference path of the kernel; the in-program q/k/v
    # split of a sharded fused mat reshards via GSPMD).
    if str(quant) == "int4":
        out_sharded = {"wqkv": True, "wo": False, "wgu": True,
                       "w_down": False}
        suffix = "_p"
    else:
        out_sharded = {"wq": True, "wk": True, "wv": True, "wo": False,
                       "w_gate": True, "w_up": True, "w_down": False}
        suffix = "_q"
    lay = {"ln_attn": P(None, None), "ln_mlp": P(None, None)}
    for k, on_out in out_sharded.items():
        lay[k + suffix] = (P(None, None, "model") if on_out
                           else P(None, "model", None))
        lay[k + "_s"] = (P(None, None, "model") if on_out
                         else P(None, None, None))
    return {
        "embed": P(None, None),
        "layers": lay,
        "ln_out": P(None),
        "lm_head" + suffix: P(None, "model"),
        "lm_head_s": P(None, "model"),
    }


def _rmsnorm(x, w, eps, scale: float = 1.0):
    """``scale`` (a latent layer's ``*_lora_scale``) multiplies in float32,
    before the cast: in bfloat16 sqrt(12) would sit 0.13 % high on every
    cached row."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps))
    if scale != 1.0:
        inv = inv * jnp.float32(scale)
    return (x32 * inv).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding. x: [B, T, H, D_head]; positions: [B, T] or [T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * freqs  # [B, T, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(x, n_rep: int):
    import jax.numpy as jnp

    if n_rep == 1:
        return x
    B, T, Hkv, D = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (B, T, Hkv, n_rep, D)
    ).reshape(B, T, Hkv * n_rep, D)


def _paged_rows(pool_shape, paged_tables, pos_offset, T, layer, park):
    """Where the ``T`` new rows of each sequence go in a pool viewed flat
    as ``[L * n_blocks, bs, ...]``: ``(flat block [B, T], offset in the
    block [B, T])``, looked up through the row's block table.  A parked
    or overshooting position, or a table entry outside the layer's own
    ``[0, n_blocks)``, resolves to the ``L * n_blocks`` sentinel and the
    write DROPS.  ``park`` is given for a ring table, which has no such
    edge of its own."""
    import jax.numpy as jnp

    n_layers, n_blocks, bs = pool_shape[:3]
    max_blocks = paged_tables.shape[1]
    ring = park is not None
    edge = park if ring else max_blocks * bs
    idx = pos_offset[:, None] + jnp.arange(T)[None, :]  # [B, T]
    slot_blk = (idx // bs) % max_blocks if ring \
        else jnp.clip(idx // bs, 0, max_blocks - 1)
    entry = jnp.take_along_axis(paged_tables, slot_blk, axis=1)
    valid = ((idx >= 0) & (idx < edge)
             & (entry >= 0) & (entry < n_blocks))
    blk = jnp.where(valid, layer * n_blocks + entry,
                    n_layers * n_blocks)  # sentinel -> dropped
    return blk, idx % bs


def _paged_view(pool_shape, paged_tables, pos_offset, T, layer, park):
    """What a layer attends in the flat pool: ``(context lengths [B],
    flat tables [B, max_blocks])``.  Context = everything written so far
    incl. this suffix; a parked row (pos >= the edge) gets len 0 — the
    paged kernels then issue ZERO block DMAs for it, which is the whole
    traffic story.  Sentinel entries clip inside the layer BEFORE the
    offset: clipped after it they would name another layer's block."""
    import jax.numpy as jnp

    _, n_blocks, bs = pool_shape[:3]
    edge = park if park is not None else paged_tables.shape[1] * bs
    lens = jnp.where(pos_offset + T <= edge,
                     pos_offset + T, 0).astype(jnp.int32)
    return lens, layer * n_blocks + jnp.clip(paged_tables, 0, n_blocks - 1)


def _latent_attention(cfg: LlamaConfig, lp, h, positions, pool=None,
                      pos_offset=None, paged_tables=None, layer=None):
    """Latent attention of one layer on the normed input ``h`` [B, T, D]
    -> (the heads' outputs [B, T, H * v_head_dim], the pool).

    ``c_q = q_lora_scale * N(h wq_a)``, ``q = c_q wq_b`` -> per head an
    unrotated part ``q_C`` and a rotated ``q_R``; ``[c | k_R] = h wkv_a``,
    ``c <- kv_lora_scale * N(c)``, ``k_R`` rotated and shared by all
    heads; ``[k_C | v] = c wkv_b`` per head; scores ``(q_C . k_C + q_R .
    k_R) / sqrt(nope + rope)``.  **The cache row of a token is ``c | k_R``**
    (``latent_width`` values, zero-padded to the pool's lanes), written
    into the whole pool viewed flat like K and V are (:func:`_block`).

    Two forms of the same numbers.  Over the pool (a decode step and a
    prefill chunk alike) attention stays in the latent space: ``q~_h =
    q_C,h (wkv_b^K,h)^T`` scores against ``c`` directly and ``o_h = (P c)
    wkv_b^V,h`` — a decode step's paged kernel streams each cached row
    once for both products, a chunk takes the kernel's plain reference
    (ops/attention.py ``paged_latent_attention``).  The cacheless forward
    EXPANDS: its own rows' ``c`` go through ``wkv_b`` to K and V per head
    and attention runs per head, the form the model is published in (a
    query-row pair then costs ``2 (nope + rope + v)`` FLOP a head against
    the latent space's ``2 (2 r + rope)``, and the expansion is paid once
    a row: the cheaper form from about a hundred queries a row on, which
    a chunk of ``prefill_chunk`` queries over a whole block table never
    is)."""
    import jax
    import jax.numpy as jnp

    B, T, _ = h.shape
    dt = h.dtype
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = (dn + dr) ** -0.5
    cq = _rmsnorm(_mm(h, lp, "wq_a", dt), lp["q_a_norm"], cfg.norm_eps,
                  cfg.q_lora_scale)
    q = _mm(cq, lp, "wq_b", dt).reshape(B, T, H, dn + dr)
    q_c, q_r = q[..., :dn], _rope(q[..., dn:], positions, cfg.rope_theta)
    kva = _mm(h, lp, "wkv_a", dt)
    c = _rmsnorm(kva[..., :r], lp["kv_a_norm"], cfg.norm_eps,
                 cfg.kv_lora_scale)
    k_r = _rope(kva[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    wkv_b = lp["wkv_b"].astype(dt).reshape(r, H, dn + dv)
    wk, wv = wkv_b[..., :dn], wkv_b[..., dn:]

    if paged_tables is None:
        with jax.named_scope("attention"), jax.named_scope("attn.latent"):
            k_c = jnp.einsum("bsr,rhn->bshn", c, wk)
            v = jnp.einsum("bsr,rhv->bshv", c, wv)
            s = jnp.einsum("bqhn,bkhn->bhqk", q_c, k_c,
                           preferred_element_type=jnp.float32) \
                + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r,
                             preferred_element_type=jnp.float32)
            causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
            s = jnp.where(causal[None, None], s * scale, jnp.float32(-1e30))
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            attn = jnp.einsum("bhqk,bkhv->bqhv", p.astype(dt), v)
        return attn.reshape(B, T, H * dv), pool

    from ..ops.attention import paged_latent_attention

    pool_shape = pool.shape  # [L, n_blocks, bs, lanes]
    flat = (pool_shape[0] * pool_shape[1],) + pool_shape[2:]
    with jax.named_scope("kv_write"):
        blk, off = _paged_rows(pool_shape, paged_tables, pos_offset, T,
                               layer, None)
        row = jnp.concatenate([c, k_r], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, pool_shape[-1] - row.shape[-1])))
        c_flat = pool.reshape(flat).at[blk, off].set(
            row.astype(pool.dtype), mode="drop")
    with jax.named_scope("attention"), jax.named_scope("attn.latent"):
        lens, tables = _paged_view(pool_shape, paged_tables, pos_offset, T,
                                   layer, None)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_c, wk)
        o_lat = paged_latent_attention(
            jnp.concatenate([q_lat, q_r], axis=-1), c_flat, tables,
            lens, v_width=r, scale=scale).astype(dt)
        attn = jnp.einsum("bqhr,rhv->bqhv", o_lat, wv)
    return attn.reshape(B, T, H * dv), c_flat.reshape(pool_shape)


def _conv_mixer(cfg: LlamaConfig, lp, h, state=None, slots=None,
                pos_offset=None, layer=None, live=None, n_valid=None):
    """The gated short convolution of one layer on the normed input ``h``
    [B, T, D] -> (what it adds to the stream [B, T, D], the state).

    ``[b | c | v] = h w_in``; ``u = b * v``; ``z_t = sum_j w_conv[j] *
    u_{t - (taps - 1) + j}`` (depthwise, causal, ``u`` before the
    stream's first token zero); ``out = (c * z) w_out``.  What a stream
    carries from one call to the next is its last ``taps - 1`` columns
    of ``u``: a fixed size whatever its context.

    One computation for the three forms: the ``T`` new columns are
    appended to the ``taps - 1`` carried ones and the filter runs over
    that.  Without ``state`` (the cacheless forward) the carried columns
    are zeros.  With it — the pool's leaf ``[conv layers, slots, taps -
    1, D]``, viewed flat like the block pools so that no layer is ever
    sliced out of it — row ``i`` reads the columns of slot ``slots[i]``,
    zeros where ``pos_offset[i] == 0`` (a stream's first chunk: whatever
    the slot's last stream left is never read), and writes back the
    ``taps - 1`` columns that end at its ``n_valid[i]``-th new one: a
    prefill chunk is padded to a fixed ``T``, and the state after it is
    the state after its last REAL token, a value.  ``n_valid`` None =
    all ``T`` (a decode step).  A row ``live`` does not mark writes
    nothing: a parked slot decodes garbage, and the slot may be in the
    middle of its next stream's prefill."""
    import jax
    import jax.numpy as jnp

    B, T, D = h.shape
    dt = h.dtype
    past_n = cfg.conv_taps - 1
    bcv = _mm(h, lp, "w_in", dt)
    u = bcv[..., :D] * bcv[..., 2 * D:]
    if state is None:
        past = jnp.zeros((B, past_n, D), dt)
    else:
        if slots is None or slots.shape != (B,):
            raise ValueError(
                "a convolution layer's state is kept by slot: the tables "
                "need \"slot\", one id a row ([B] int32), got "
                f"{None if slots is None else slots.shape}")
        n_slots = state.shape[1]
        flat = state.reshape((-1,) + state.shape[2:])
        # an id past the leaf names no stream's state: it reads NaN (so
        # every logit of the row shows it) and, below, writes nothing —
        # it is never folded onto another slot's columns
        rows = jnp.where((slots >= 0) & (slots < n_slots),
                         layer * n_slots + slots, flat.shape[0])
        past = jnp.where(
            (pos_offset == 0)[:, None, None], 0,
            flat.at[rows].get(mode="fill", fill_value=jnp.nan)).astype(dt)
    ext = jnp.concatenate([past, u], axis=1)        # [B, past_n + T, D]
    w = lp["w_conv"].astype(jnp.float32)
    z = sum(w[j] * ext[:, j:j + T].astype(jnp.float32)
            for j in range(cfg.conv_taps))
    out = _mm(bcv[..., D:2 * D] * z.astype(dt), lp, "w_out", dt)
    if state is None:
        return out, None
    if n_valid is None:
        tail = ext[:, T:]
    else:
        tail = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
            e, n, past_n, axis=0))(ext, jnp.broadcast_to(n_valid, (B,)))
    with jax.named_scope("state_write"):
        # a row that is not live resolves past the leaf and DROPS
        rows = jnp.where(live, rows, flat.shape[0])
        flat = flat.at[rows].set(tail.astype(flat.dtype), mode="drop")
    return out, flat.reshape(state.shape)


def _block(cfg: LlamaConfig, lp, x, positions, kv=None, pos_offset=None,
           attn_fn=None, paged_tables=None, layer=None, kind=None,
           park=None, live=None, stats_out=None, branch_io=None,
           slots=None, n_valid=None):
    """One transformer block.  ``kv=(k_cache, v_cache)`` enables cached
    decode (x is the new suffix, written at ``pos_offset``); ``attn_fn``
    overrides plain causal attention (ring attention under shard_map);
    ``paged_tables`` ([B, max_blocks] int32) switches ``kv`` to the WHOLE
    block pool ([L, n_blocks, bs, Hkv, hd], every layer) with per-row
    positions, of which this block writes and reads layer ``layer`` (a
    traced scalar) — the continuous-serving paged path.

    The sections carry ``jax.named_scope`` names (``attention``,
    ``kv_write``, ``mlp``): metadata only — the scope lands in every
    operation's ``op_name`` in the HLO, so a device trace can be summed
    by section instead of recognised by result shape (PERF.md §3).

    ``kind`` (a :class:`LayerKind`; None = full, rotated, dense) is what
    THIS layer is in a patterned model.  A window layer's paged cache is
    a ring: ``paged_tables`` is then the slots' ring table and ``park``
    the position from which a row is parked (the full table's span —
    a ring has no such edge of its own).  An expert layer appends its
    routing counts (models/moe.py ``moe_ffn``, over the rows ``live``
    marks) to ``stats_out``.  A latent layer's ``kv`` is its class's one
    pool, as a 1-tuple (:func:`_latent_attention`).  ``branch_io`` is a
    one-entry list holding the open shortcut branch's tensor: a kind
    that opens writes it, a kind that closes reads it (the walk carries
    it between).  A convolution layer's ``kv`` is its class's state
    leaf, as a 1-tuple, ``slots`` [B] the slot each row is and
    ``n_valid`` how many of the ``T`` columns are real tokens
    (:func:`_conv_mixer`)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, T, D = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    window = kind.window if kind is not None else 0
    attn_scope = ("attn.window" if window else "attn.full") \
        if kind is not None else None

    h = _rmsnorm(x, lp["ln_attn"], cfg.norm_eps)
    if kind is not None and kind.conv:
        with jax.named_scope("mixer.conv"):
            out, state = _conv_mixer(
                cfg, lp, h, kv[0] if kv is not None else None, slots,
                pos_offset, layer, live, n_valid)
        return _feed_forward(cfg, lp, x + out, kind, live, stats_out,
                             branch_io), (state,)
    if kind is not None and kind.latent:
        attn, c = _latent_attention(
            cfg, lp, h, positions, kv[0] if kv is not None else None,
            pos_offset, paged_tables, layer)
        x = x + _mm(attn, lp, "wo", dt)
        return _feed_forward(cfg, lp, x, kind, live, stats_out,
                             branch_io), (c,)
    if "wqkv_p" in lp:  # int4 fused q|k|v (one kernel call per layer)
        qkv = _mm(h, lp, "wqkv", dt)
        q = qkv[..., :H * hd].reshape(B, T, H, hd)
        k = qkv[..., H * hd:(H + Hkv) * hd].reshape(B, T, Hkv, hd)
        v = qkv[..., (H + Hkv) * hd:].reshape(B, T, Hkv, hd)
    else:
        q = _mm(h, lp, "wq", dt).reshape(B, T, H, hd)
        k = _mm(h, lp, "wk", dt).reshape(B, T, Hkv, hd)
        v = _mm(h, lp, "wv", dt).reshape(B, T, Hkv, hd)
    if cfg.qk_norm:
        q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    if kind is None or kind.rope:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)

    mask = None
    if paged_tables is not None:
        # Block-pool write + paged attention, on the whole pool viewed
        # flat as [L * n_blocks, bs, Hkv, hd] (a reshape of the leading
        # dims, no data moves): layer l's block j is flat block
        # l * n_blocks + j, so neither the write nor the read takes a
        # layer out of the pool (forward_paged says why).  Writes scatter
        # each new K/V row into (flat block, offset) looked up through the
        # row's block table; a parked/overshooting position, or a table
        # entry outside the layer's own [0, n_blocks), resolves to the
        # L * n_blocks sentinel and the write DROPS — idle slots decode
        # garbage without touching live blocks, recycled blocks can't be
        # written through a stale (cleared) table, and nothing lands in
        # the NEXT layer's blocks, which is where n_blocks would point.
        from ..ops.attention import paged_attention

        k_pool, v_pool = kv  # [L, n_blocks, bs, Hkv, hd]
        pool_shape = k_pool.shape
        flat = (pool_shape[0] * pool_shape[1],) + pool_shape[2:]
        ring = park is not None
        if pool_shape[3:] != (Hkv, hd):
            # narrow heads, stored several to a lane row: the new rows
            # are the same numbers in the same order
            k = k.reshape((B, T) + pool_shape[3:])
            v = v.reshape((B, T) + pool_shape[3:])
        with jax.named_scope("kv_write"):
            blk, off = _paged_rows(pool_shape, paged_tables, pos_offset, T,
                                   layer, park)
            k_flat = k_pool.reshape(flat).at[blk, off].set(
                k.astype(k_pool.dtype), mode="drop")
            v_flat = v_pool.reshape(flat).at[blk, off].set(
                v.astype(v_pool.dtype), mode="drop")
        with jax.named_scope("attention"):
            lens, tables = _paged_view(pool_shape, paged_tables, pos_offset,
                                       T, layer, park)
            if kind is None:
                attn = paged_attention(q, k_flat, v_flat, tables,
                                       lens).astype(dt)
            else:
                with jax.named_scope(attn_scope):
                    attn = paged_attention(
                        q, k_flat, v_flat, tables, lens, window=window,
                        ring=ring).astype(dt)
        kv = (k_flat.reshape(pool_shape), v_flat.reshape(pool_shape))
        # falls through to the shared wo/residual/MLP tail below
    elif kv is not None:
        k_cache, v_cache = kv  # [B, S_max, Hkv, hd]
        if getattr(pos_offset, "ndim", 0) == 1:
            # Per-row positions ([B] int32, T==1): each batch row writes
            # its own cache slot row — the continuous-batching decode,
            # where concurrent streams sit at different depths.  An
            # out-of-range row position (an idle slot parked at max_seq)
            # drops the write (jax scatter default), so idle slots decode
            # garbage without corrupting live rows.
            k_cache = k_cache.at[jnp.arange(B), pos_offset].set(
                k[:, 0].astype(k_cache.dtype), mode="drop")
            v_cache = v_cache.at[jnp.arange(B), pos_offset].set(
                v[:, 0].astype(v_cache.dtype), mode="drop")
            q_pos = pos_offset[:, None] + jnp.arange(T)  # [B, T]
        else:
            k_cache = lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, pos_offset, 0, 0))
            v_cache = lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, pos_offset, 0, 0))
            q_pos = (pos_offset + jnp.arange(T))[None, :]  # [1, T]
        kv = (k_cache, v_cache)
        k_all, v_all = k_cache.astype(dt), v_cache.astype(dt)
        S = k_all.shape[1]
        # Rows beyond the filled prefix are masked by key-position validity
        # (consumed only by the masked decode path below).
        k_pos = jnp.arange(S)
        mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]
        # [B or 1, 1, T, S]
    else:
        k_all, v_all = k, v

    # Static pos_offset=0 means "prefill into an empty cache": the fresh
    # k/v ARE the filled cache rows, so attention reduces to causal
    # attention over the prompt — the flash kernel's case — instead of a
    # masked sweep over all S_max cache rows.
    prefill = (paged_tables is None and kv is not None
               and type(pos_offset) is int and pos_offset == 0)

    if paged_tables is not None:
        pass  # paged attention computed above; shared tail below
    elif attn_fn is not None:
        with jax.named_scope("attention"):
            attn = attn_fn(q, _repeat_kv(k_all, H // Hkv),
                           _repeat_kv(v_all, H // Hkv))
    elif window:
        from ..ops.attention import attention_reference

        with jax.named_scope("attention"), jax.named_scope(attn_scope):
            attn = attention_reference(q, k, v, causal=True, window=window)
    elif kv is None or prefill:
        # Blockwise flash kernel (Pallas; falls back to plain XLA attention
        # internally when T doesn't tile into its blocks).  K/V go in
        # UNREPEATED — the kernel shares each streamed block across the
        # query-head group, and the XLA fallback repeats internally.
        from ..ops.attention import flash_attention

        with jax.named_scope("attention"):
            attn = flash_attention(q, k, v, causal=True)
    else:
        with jax.named_scope("attention"):
            kr = _repeat_kv(k_all, H // Hkv)
            vr = _repeat_kv(v_all, H // Hkv)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                           preferred_element_type=jnp.float32)
            s = s * (1.0 / np.sqrt(hd))
            s = jnp.where(mask, s, jnp.float32(-1e30))
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            attn = jnp.einsum("bhqk,bkhd->bqhd", p.astype(dt), vr)

    out = _mm(attn.reshape(B, T, H * hd), lp, "wo", dt)
    x = x + out
    return _feed_forward(cfg, lp, x, kind, live, stats_out, branch_io), kv


def _feed_forward(cfg: LlamaConfig, lp, x, kind, live, stats_out,
                  branch_io):
    """The second half of a block: ``x + FFN(N(x))``, the FFN dense or
    sparse experts as ``kind`` says.  A kind that opens a shortcut also
    sends the SAME normed input through the experts and leaves their
    result in ``branch_io[0]`` — nothing reads it before the closing
    kind adds it to its own output, so the branch runs beside whatever
    lies between."""
    import jax
    import jax.nn as jnn

    from .moe import moe_ffn

    dt = x.dtype
    shortcut = kind.shortcut if kind is not None else ""
    with jax.named_scope("mlp"):
        h = _rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
        if kind is not None and kind.ffn == "experts":
            y, stats = moe_ffn(h, lp, cfg.experts, dt, live=live)
            if stats_out is not None:
                stats_out.append(stats)
            return x + y
        if shortcut == "open":
            with jax.named_scope("moe.shortcut"):
                branch_io[0], stats = moe_ffn(h, lp, cfg.experts, dt,
                                              live=live)
            if stats_out is not None:
                stats_out.append(stats)
        if "wgu_p" in lp:  # int4 fused gate|up
            F = lp["wgu_p"].shape[-1] // 2
            gu = _mm(h, lp, "wgu", dt)
            gate = jnn.silu(gu[..., :F])
            up = gu[..., F:]
        else:
            gate = jnn.silu(_mm(h, lp, "w_gate", dt))
            up = _mm(h, lp, "w_up", dt)
        x = x + _mm(gate * up, lp, "w_down", dt)
        if shortcut == "close":
            with jax.named_scope("moe.shortcut"):
                x = x + branch_io[0]
    return x


def forward(params, tokens, cfg: LlamaConfig, compute_dtype="bfloat16"):
    """Full-sequence forward -> logits [B, T, vocab] (training/eval path)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(compute_dtype)
    B, T = tokens.shape
    x = jnp.asarray(params["embed"]).astype(dt)[tokens]
    positions = jnp.arange(T)

    if cfg.patterned:
        def step(carry, lp, kind, _slot):
            x, branch = carry
            io = [branch]
            x, _ = _block(cfg, lp, x, positions, kind=kind, branch_io=io)
            return x, io[0]

        x, _ = _walk_pattern(cfg, params["layers"], (x, _no_branch(cfg, x)),
                             step)
        x = _rmsnorm(x, params["ln_out"], cfg.norm_eps)
        return _lm_head(params, x, dt)

    def body(x, lp):
        x, _ = _block(cfg, lp, x, positions)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return _lm_head(params, x, dt)


def _no_branch(cfg: LlamaConfig, x):
    """What the walk carries for the shortcut branch before any is open:
    a tensor of ``x``'s shape where the model has shortcuts (a scan's
    carry keeps one structure; after a branch closed, its stale value
    rides on unread until the next one opens), else nothing."""
    import jax.numpy as jnp

    return jnp.zeros_like(x) if cfg.has_shortcut else None


def _walk_pattern(cfg: LlamaConfig, stacks, carry, step):
    """Threads ``carry`` through every layer of a patterned model:
    ``step(carry, lp, kind, slot) -> carry`` with ``lp`` the layer's
    leaves taken out of its kind's stack and ``slot`` the layer's index
    among the layers of its cache class (``LayerKind.cache``: full,
    window or latent) — its layer in that class's cache pool.  What one
    sub-layer opens and a later one closes (a shortcut's branch) is part
    of ``carry``, beside ``x`` and the pools.  Laid out by
    :func:`walk_plan`: the
    prefix one by one, then a scan over the periods, so a deep model is
    ``prefix + period`` copies of the block, not ``n_layers``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    kinds, plan = cfg.kinds, walk_plan(cfg.kinds)
    # where[l]: layer l's entry in its kind's stack, and its layer in its
    # attention class's pool
    where, seen = [], {}
    for kind in kinds:
        pair = []
        for key in (kind.name, "cache:" + kind.cache):
            pair.append(seen.get(key, 0))
            seen[key] = pair[-1] + 1
        where.append(tuple(pair))

    def take(name, i):
        """Layer ``i`` of a kind's stack; the expert matrices stay whole
        and go with the index (models/moe.py says why)."""
        from .moe import STACKED_LEAVES

        def one(a):
            if isinstance(i, int):
                return a[i]
            return lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

        lp = {leaf: a if leaf in STACKED_LEAVES else one(a)
              for leaf, a in stacks[name].items()}
        lp["_layer"] = i
        return lp

    s0, p = plan.prefix, plan.period
    for kind, (i, slot) in zip(kinds[:s0], where[:s0]):
        carry = step(carry, take(kind.name, i), kind, slot)
    if not plan.n_periods:
        return carry

    def body(carry, t):
        # layer j of period t: its place in the first period, plus t
        # times what one period adds (n_periods >= 2: the second period
        # is there to read that off)
        for j in range(p):
            (i0, slot0), (i1, slot1) = where[s0 + j], where[s0 + p + j]
            kind = kinds[s0 + j]
            carry = step(carry, take(kind.name, i0 + t * (i1 - i0)), kind,
                         slot0 + t * (slot1 - slot0))
        return carry, None

    carry, _ = lax.scan(body, carry,
                        jnp.arange(plan.n_periods, dtype=jnp.int32))
    return carry


def init_cache(cfg: LlamaConfig, batch: int, dtype="bfloat16"):
    """KV cache pytree: k/v of [L, B, S_max, H_kv, head_dim]."""
    import jax.numpy as jnp

    shape = (cfg.n_layers, batch, cfg.max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_pspecs() -> Dict:
    from jax.sharding import PartitionSpec as P

    return {"k": P(None, None, None, "model", None),
            "v": P(None, None, None, "model", None)}


# -- block-paged KV cache (continuous serving) ------------------------------

def init_paged_cache(cfg: LlamaConfig, n_blocks: int, block_size: int,
                     dtype="bfloat16", win_blocks: int = 0, slots: int = 0):
    """Block-pool KV cache: k/v of [L, n_blocks, block_size, H_kv, head_dim].

    The pool replaces the dense per-slot [L, B, S_max, ...] cache for
    continuous serving: streams own BLOCKS (via a per-slot block table),
    not S_max rows, so per-decode-step HBM traffic scales with the sum of
    live sequence lengths (ops/attention.py paged kernel) and a short
    stream stops paying for the longest one.

    Layers lead and blocks follow, so the two leading dims merge into
    one flat block axis without moving data: :func:`forward_paged`
    addresses layer ``l``'s block ``j`` as flat block ``l * n_blocks +
    j`` and never takes a layer out of the pool.  Host code indexes
    ``pool["k"][:, ids]`` (every layer of a block: CoW fork, drain,
    adopt).

    A model with window layers gets a pool a layer CLASS: ``k``/``v``
    hold the full-attention layers (``n_blocks`` blocks each, handed out
    by the allocator as before), ``k_win``/``v_win`` the window layers
    (``win_blocks`` blocks each: ``slots`` rings of
    :func:`window_ring_blocks`, which no allocator touches).  Latent
    layers keep ``c``: ``[latent layers, n_blocks, block_size, lanes]``,
    one row a token for all heads (:func:`_latent_attention`), in the
    ALLOCATOR's blocks like ``k``/``v`` (block ``j`` holds the same
    positions in every leaf but the rings) and merged flat the same way;
    ``lanes`` is ``latent_width`` padded as the kernel wants it
    (ops/attention.py ``latent_pool_width``).  Heads narrower than the
    128 lanes are stored ``kv_lane_pack`` to a row — ``[..., block_size,
    H_kv / pack, head_dim * pack]``, the same numbers in the same order,
    the layout the paged kernel reads (``LlamaConfig.kv_lane_pack``).
    Convolution layers keep ``conv``: ``[conv layers, slots, conv_taps -
    1, dim]``, the columns each of the ``slots`` streams carries
    (:func:`_conv_mixer`) — owned by the slot like a ring, in no block
    and in no table; such a model refuses ``slots`` < 1.  A class the
    model has no layer of has no leaf."""
    import jax.numpy as jnp

    from ..ops.attention import latent_pool_width

    pack = cfg.kv_lane_pack
    tail = (block_size, cfg.n_kv_heads // pack, cfg.head_dim * pack)
    pool = {}
    if cfg.n_full_layers:
        shape = (cfg.n_full_layers, n_blocks) + tail
        pool.update(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    if cfg.n_window_layers:
        wshape = (cfg.n_window_layers, max(1, int(win_blocks))) + tail
        pool["k_win"] = jnp.zeros(wshape, dtype)
        pool["v_win"] = jnp.zeros(wshape, dtype)
    if cfg.n_latent_layers:
        pool["c"] = jnp.zeros(
            (cfg.n_latent_layers, n_blocks, block_size,
             latent_pool_width(cfg.latent_width)), dtype)
    if cfg.n_conv_layers:
        if int(slots) < 1:
            raise ValueError(
                "a model with convolution layers keeps their state by "
                "slot: init_paged_cache needs slots >= 1 (the streams the "
                f"pool serves), got {slots}")
        pool["conv"] = jnp.zeros(
            (cfg.n_conv_layers, int(slots), cfg.conv_taps - 1, cfg.dim),
            dtype)
    return pool


#: cache class (``LayerKind.cache``) -> its leaves of the pool
POOL_LEAVES = {"full": ("k", "v"), "win": ("k_win", "v_win"),
               "latent": ("c",), "conv": ("conv",)}
#: the leaves a SLOT owns for good (a window class's rings, the
#: convolution layers' state): no allocator hands them out, no table but
#: the slot's own names them, and no other stream can resume from them
SLOT_LEAVES = POOL_LEAVES["win"] + POOL_LEAVES["conv"]


def allocated_leaves(pool) -> List[str]:
    """The pool leaves whose blocks the allocator hands out (all but
    ``SLOT_LEAVES``), in a fixed order: what a CoW fork, a drain and
    an adopt copy block by block."""
    return sorted(leaf for leaf in pool if leaf not in SLOT_LEAVES)


def paged_cache_pspecs() -> Dict:
    """TP sharding of the block pool over the ``model`` mesh axis: the
    K/V head dim (axis 3 of ``[L, n_blocks, block_size, H_kv, hd]``)
    splits exactly like the dense cache's (:func:`cache_pspecs`), so a
    ``model_parallel=M`` serving loop holds ``pool_bytes / M`` per chip
    and each chip's attention reads only its own heads' blocks.
    Requires ``n_kv_heads % M == 0``
    (:func:`tp_divisibility_problems` reports the violation; the deep
    lint surfaces it statically).

    The spec deliberately omits the trailing ``None``: GSPMD normalizes
    output specs by trimming trailing unsharded dims, and the serving
    loop DONATES the pool through its programs — an untrimmed input spec
    would compare unequal to the donated output's and cost one spurious
    recompile, breaking the 3-program census the compile-counter pin
    protects."""
    from jax.sharding import PartitionSpec as P

    return {"k": P(None, None, None, "model"),
            "v": P(None, None, None, "model")}


def tp_divisibility_problems(cfg: LlamaConfig, tp: int) -> List[str]:
    """Dims tensor parallelism over ``tp`` ways cannot split evenly —
    empty when the geometry is TP-clean.  ONE home for the arithmetic
    the runtime's setup error (filters/llm.py) and the deep lint's
    static ``model-divisibility`` diagnostic must agree on."""
    if tp <= 1:
        return []
    if cfg.patterned:
        probs = ["a patterned model (layer pattern, sparse experts, q/k "
                 "norm) has no tensor-parallel layout: its experts divide "
                 "by expert parallelism (ROADMAP M2), its stacks have no "
                 "param_pspecs"]
        if cfg.n_latent_layers:
            probs.append("a latent layer caches ONE row a token for all "
                         "heads: the pool has no KV-head axis to shard "
                         "(ROADMAP M5)")
        return probs
    probs: List[str] = []
    if (cfg.n_heads * cfg.head_dim) % tp:
        probs.append(f"attention out dim n_heads*head_dim="
                     f"{cfg.n_heads * cfg.head_dim}")
    if (cfg.n_kv_heads * cfg.head_dim) % tp:
        probs.append(f"kv out dim n_kv_heads*head_dim="
                     f"{cfg.n_kv_heads * cfg.head_dim}")
    if cfg.ffn_hidden % tp:
        probs.append(f"ffn_hidden={cfg.ffn_hidden}")
    if cfg.vocab % tp:
        probs.append(f"vocab={cfg.vocab} (lm_head out)")
    if cfg.n_kv_heads % tp:
        probs.append(f"n_kv_heads={cfg.n_kv_heads} "
                     "(the KV cache/pool shards the head axis)")
    return probs


def conv_state_bytes(cfg: LlamaConfig, slots: int,
                     dtype="bfloat16") -> int:
    """Bytes of the convolution layers' state for ``slots`` streams (the
    ``conv`` leaf of :func:`init_paged_cache`); 0 without such layers,
    and for no stream."""
    itemsize = 2 if str(dtype) in ("bfloat16", "float16") else 4
    return (cfg.n_conv_layers * int(slots) * (cfg.conv_taps - 1)
            * cfg.dim * itemsize)


def paged_cache_bytes(cfg: LlamaConfig, n_blocks: int, block_size: int,
                      dtype="bfloat16", win_blocks: int = 0,
                      slots: int = 0) -> int:
    """Static HBM footprint of :func:`init_paged_cache` (k + v of the
    full and window classes, the latent class's rows at their padded
    width, the convolution layers' state of ``slots`` streams), without
    building anything — the deep-lint resource
    report prices the pool through this, so the arithmetic lives next to
    the allocation."""
    from ..ops.attention import latent_pool_width

    itemsize = 2 if str(dtype) in ("bfloat16", "float16") else 4
    blocks = cfg.n_full_layers * n_blocks
    if cfg.n_window_layers:
        blocks += cfg.n_window_layers * max(1, int(win_blocks))
    latent = cfg.n_latent_layers * n_blocks * (
        latent_pool_width(cfg.latent_width) if cfg.n_latent_layers else 0)
    return (2 * blocks * cfg.n_kv_heads * cfg.head_dim
            + latent) * block_size * itemsize \
        + conv_state_bytes(cfg, slots, dtype)


def resolve_config(model: str, opts: Dict) -> Optional[LlamaConfig]:
    """The preset + ``custom=`` override arithmetic of :func:`_build`,
    WITHOUT building weights — static analysis (deep lint) resolves the
    serving config through this so pricing a 7B pool never materializes
    7B params.  None for checkpoint paths (their config lives in the
    file; static passes must not open it)."""
    if model not in PRESETS:
        return None
    cfg = PRESETS[model]
    overrides = {}
    for field in ("vocab", "dim", "n_layers", "n_heads", "n_kv_heads",
                  "ffn_hidden", "max_seq"):
        if field in opts:
            overrides[field] = int(opts[field])
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def param_bytes_estimate(cfg: LlamaConfig, quant: str = "",
                         param_dtype: str = "float32") -> int:
    """Static parameter-set HBM footprint for one replica, by arithmetic
    (no weights built): the seven big layer mats + lm_head at the quant
    width (int8 1 B + f32 scales, int4 0.5 B + scales, else the param
    dtype's width), embed at param dtype, norms f32."""
    if cfg.patterned:
        if str(quant):
            _refuse_quant(cfg)
        itemsize = 2 if str(param_dtype) in ("bfloat16", "float16") else 4
        total = 2 * cfg.vocab * cfg.dim * itemsize + 4 * cfg.dim
        for kind in cfg.kinds:
            for leaf, shape in stack_shapes(cfg, kind).items():
                total += int(np.prod(shape)) * (
                    4 if leaf in F32_LEAVES else itemsize)
        return total
    L, D, H, Hkv, F = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.ffn_hidden)
    hd = cfg.head_dim
    big_elems = L * (D * H * hd + 2 * D * Hkv * hd + H * hd * D
                     + 2 * D * F + F * D)
    head_elems = D * cfg.vocab
    out_channels = L * (H * hd + 2 * Hkv * hd + D + 2 * F + D)
    itemsize = 2 if str(param_dtype) in ("bfloat16", "float16") else 4
    q = str(quant).lower()
    if q == "int8":
        mats = big_elems + head_elems
        scales = 4 * (out_channels + cfg.vocab)
    elif q == "int4":
        mats = (big_elems + head_elems) // 2
        scales = 4 * (out_channels + cfg.vocab)
    else:
        mats = (big_elems + head_elems) * itemsize
        scales = 0
    embed = cfg.vocab * D * itemsize
    norms = 4 * (2 * L * D + D)
    return mats + scales + embed + norms


def param_bytes_split(cfg: LlamaConfig, quant: str = "",
                      param_dtype: str = "float32") -> Tuple[int, int]:
    """Static ``(sharded, replicated)`` byte split of
    :func:`param_bytes_estimate` under the :func:`param_pspecs` TP
    layout: the big layer mats + lm_head (and their scales) carry a
    ``model`` axis and divide by the mesh's model size per chip; embed
    and the norms replicate.  The deep lint prices a
    ``model_parallel=M`` pipeline's per-chip params as
    ``sharded / M + replicated``."""
    total = param_bytes_estimate(cfg, quant=quant, param_dtype=param_dtype)
    itemsize = 2 if str(param_dtype) in ("bfloat16", "float16") else 4
    replicated = cfg.vocab * cfg.dim * itemsize \
        + 4 * (2 * cfg.n_layers * cfg.dim + cfg.dim)
    return total - replicated, replicated


def block_size_of(pool) -> int:
    """Positions a block of ``pool`` holds (axis 2 of every leaf that is
    made of blocks: all but the convolution layers' state)."""
    return next(a for leaf, a in pool.items()
                if leaf not in POOL_LEAVES["conv"]).shape[2]


def forward_paged(params, tokens, pool, block_tables, pos,
                  cfg: LlamaConfig, compute_dtype="bfloat16",
                  logit_off=None, with_stats=False, n_valid=None):
    """Forward a suffix against the block-paged KV pool.

    ``tokens``: [B, T] (T == 1 for the continuous decode step, B == 1 with
    T == prefill_chunk for a chunked-prefill step); ``pool``: the
    :func:`init_paged_cache` pytree; ``block_tables``: [B, max_blocks]
    int32 (entries >= n_blocks are unallocated sentinels); ``pos``: [B]
    int32 — the position token 0 of each row writes at (a parked row
    passes ``max_blocks * block_size`` or larger and neither writes nor
    attends).  Every shape here is static in (B, T, pool, max_blocks):
    stream join/leave/retire only changes VALUES, which is what pins the
    continuous loop at zero recompiles.

    The pool is a CARRY of the layer scan, next to ``x``, and the scan
    yields no per-layer outputs: layer ``l`` scatters its new rows into
    the whole pool and attends over the whole pool, addressing its own
    blocks through the flat view ``[L * n_blocks, bs, H_kv, hd]`` at
    ``l * n_blocks + block`` (:func:`_block`).  A scan's outputs are a
    fresh buffer, so a pool scanned in as ``xs`` and collected as ``ys``
    is rebuilt on every call: each layer sliced out, 64 KB written into
    that copy, the layer written into a second pool, and the second pool
    copied back over the caller's carry — 2.28 GB moved per decode step
    in the 7B serving cell (PERF.md §6, PR 27).  Carried, the donated
    pool is updated in place from the caller's argument to its result;
    the returned pytree has the argument's shapes and
    :func:`paged_cache_pspecs` (the reshape merges the two unsharded
    leading dims only).

    ``logit_off`` (traced scalar): return logits for ONLY that suffix
    position — [B, 1, vocab].  A chunked-prefill step needs one
    position's logits (the last REAL token; pad rows fill the chunk
    tail), and slicing before the lm_head keeps the vocab matmul at one
    row instead of T.

    ``with_stats``: also return the expert layers' routing counts of
    this step, int32 [5] (models/moe.py; None for a model without
    experts).

    ``n_valid`` (traced; a scalar or [B]): how many of the ``T`` columns
    are real tokens, the rest a chunk's padding (None = all).  State that
    is not addressed by position — a convolution layer's
    (:func:`_conv_mixer`) — is taken there; K/V rows of the padding are
    written as before and never attended."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(compute_dtype)
    B, T = tokens.shape
    x = jnp.asarray(params["embed"]).astype(dt)[tokens]
    positions = pos[:, None] + jnp.arange(T)[None, :]

    if cfg.patterned:
        # A pool a layer class (POOL_LEAVES) and a table for the
        # allocator's blocks and one for the rings: ``block_tables`` is
        # then ``{"full": [B, max_blocks], "win": [B, ring]}`` (the array
        # alone where no layer has a window), with ``"slot": [B]``, the
        # slot each row is, where convolution layers keep their state by
        # slot.  Every pool is a carry of the walk, like the one pool
        # below.
        from .moe import N_STATS, merge_stats

        tabs = block_tables if isinstance(block_tables, dict) \
            else {"full": block_tables}
        park = tabs["full"].shape[1] * block_size_of(pool)
        live = pos < park

        def step(carry, lp, kind, slot):
            x, pl, stats, branch = carry
            got, io = [], [branch]
            # a latent layer's rows live in the allocator's blocks, as
            # the full class's do: one table for both
            ring = kind.cache == "win"
            leaves = POOL_LEAVES[kind.cache]
            x, kv = _block(
                cfg, lp, x, positions, kv=tuple(pl[n] for n in leaves),
                pos_offset=pos, paged_tables=tabs["win" if ring else "full"],
                layer=slot, kind=kind, park=park if ring else None,
                live=live, stats_out=got, branch_io=io,
                slots=tabs.get("slot"), n_valid=n_valid)
            pl = dict(pl, **dict(zip(leaves, kv)))
            if got:
                stats = merge_stats(stats, got[0])
            return x, pl, stats, io[0]

        stats0 = jnp.zeros((N_STATS,), jnp.int32) if cfg.experts else None
        x, pool, stats, _ = _walk_pattern(
            cfg, params["layers"],
            (x, dict(pool), stats0, _no_branch(cfg, x)), step)
        x = _rmsnorm(x, params["ln_out"], cfg.norm_eps)
        if logit_off is not None:
            x = lax.dynamic_slice_in_dim(x, logit_off, 1, axis=1)
        out = _lm_head(params, x, dt), pool
        return out + (stats,) if with_stats else out

    def body(carry, layer):
        x, kc, vc = carry
        lp, l = layer
        x, (kc, vc) = _block(cfg, lp, x, positions, kv=(kc, vc),
                             pos_offset=pos, paged_tables=block_tables,
                             layer=l)
        return (x, kc, vc), None

    n_layers = pool["k"].shape[0]
    (x, k_new, v_new), _ = jax.lax.scan(
        body, (x, pool["k"], pool["v"]),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    x = _rmsnorm(x, params["ln_out"], cfg.norm_eps)
    if logit_off is not None:
        x = lax.dynamic_slice_in_dim(x, logit_off, 1, axis=1)
    out = _lm_head(params, x, dt), {"k": k_new, "v": v_new}
    return out + (None,) if with_stats else out


def forward_cached(params, tokens, cache, pos_offset, cfg: LlamaConfig,
                   compute_dtype="bfloat16"):
    """Forward a suffix with KV cache: prefill (T=prompt) and decode (T=1)
    are the SAME program at different T -> two XLA compilations total.

    ``pos_offset`` may be a scalar (all rows at the same depth — the
    single-stream path) or a [B] int32 vector (each row at its own depth
    — the continuous-batching decode; requires T == 1)."""
    import jax
    import jax.numpy as jnp

    refuse_pattern(cfg, "forward_cached (the dense per-slot cache)")
    dt = jnp.dtype(compute_dtype)
    B, T = tokens.shape
    x = jnp.asarray(params["embed"]).astype(dt)[tokens]
    if getattr(pos_offset, "ndim", 0) == 1:  # per-row positions ([B])
        positions = pos_offset[:, None] + jnp.arange(T)[None, :]
    else:
        positions = pos_offset + jnp.arange(T)[None, :]

    def body(x, layer):
        lp, kc, vc = layer
        x, (kc, vc) = _block(cfg, lp, x, positions, kv=(kc, vc),
                             pos_offset=pos_offset)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = _rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return _lm_head(params, x, dt), {"k": k_new, "v": v_new}


def forward_seq_parallel(mesh, params, tokens, cfg: LlamaConfig,
                         compute_dtype="bfloat16"):
    """Sequence-parallel full forward: tokens sharded [B, T/seq] over the
    ``seq`` mesh axis, ring attention rotating K/V shards over ICI.

    No device ever materializes the full sequence — the long-context path
    the reference cannot express (SURVEY §2.9: SP "absent in reference").
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring import ring_attention_local

    refuse_pattern(cfg, "forward_seq_parallel (ring attention)")
    n_seq = int(mesh.shape.get("seq", 1))
    if n_seq <= 1:
        return forward(params, tokens, cfg, compute_dtype)

    dt = jnp.dtype(compute_dtype)

    def local_fwd(params, tokens):
        B, Tl = tokens.shape
        my = lax.axis_index("seq")
        positions = my * Tl + jnp.arange(Tl)
        x = jnp.asarray(params["embed"]).astype(dt)[tokens]

        def attn_fn(q, k, v):
            return ring_attention_local(q, k, v, axis_name="seq", causal=True)

        def body(x, lp):
            x, _ = _block(cfg, lp, x, positions, attn_fn=attn_fn)
            return x, None

        x, _ = lax.scan(body, x, params["layers"])
        x = _rmsnorm(x, params["ln_out"], cfg.norm_eps)
        return _lm_head(params, x, dt)

    fn = jax.shard_map(
        local_fwd, mesh=mesh,
        in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq", None),
        check_vma=False,
    )
    return jax.jit(fn)(params, tokens)


def filter_logits(logits, temperature: float, top_k: int = 0,
                  top_p: float = 1.0):
    """Apply the sampler chain's logit filters: [.., vocab] -> [.., vocab].

    ``temperature`` scales, ``top_k`` (0 = off) keeps the k highest
    logits, ``top_p`` (1.0 = off) keeps the smallest set whose
    probability mass reaches p (nucleus); masked positions go to -inf.
    All knobs are STATIC (Python) values baked into the compiled
    program — masking is where/inf over the fixed vocab axis, so the
    MXU shape never changes and no host roundtrip happens mid-decode.
    ``softmax(filter_logits(...))`` is the exact sampling distribution,
    which is what speculative rejection sampling needs on both the
    draft and target sides (filters/llm.py verify).  Caller must have
    temperature > 0.
    """
    import jax
    import jax.numpy as jnp

    logits = logits / temperature
    neg = jnp.asarray(-jnp.inf, logits.dtype)
    if top_k and 0 < top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p < 1.0:
        sort = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sort, axis=-1)
        # exclusive cumulative mass before each sorted position; the first
        # position where it already reaches p is cut (the kept set is the
        # smallest prefix with mass >= p).  Position 0 is never cut, so
        # the top token survives any top_p — including a degenerate
        # top_p<=0, where exclusive mass 0 >= p would otherwise mask
        # EVERY logit and categorical would return id 0 unconditionally.
        cut = ((jnp.cumsum(probs, axis=-1) - probs) >= top_p) \
            & (jnp.arange(sort.shape[-1]) > 0)
        kept = jnp.where(cut, jnp.asarray(jnp.inf, logits.dtype), sort)
        thresh = jnp.min(kept, axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, neg, logits)
    return logits


def sample_token(logits, key, temperature: float, top_k: int = 0,
                 top_p: float = 1.0):
    """logits [B, vocab] -> token ids [B], one shared PRNG key.

    Reference analog: llama.cpp's sampler chain
    (tensor_filter_llamacpp.cc, SURVEY §2.4 [UNVERIFIED]).  Filter
    semantics live in :func:`filter_logits`.
    """
    import jax
    import jax.numpy as jnp

    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_token_per_slot(logits, keys, temperature: float, top_k: int = 0,
                          top_p: float = 1.0):
    """logits [B, vocab] + per-slot keys [B, 2] uint32 -> token ids [B].

    The continuous-serving sampler: each slot draws from its OWN PRNG
    stream, so a slot's emitted tokens are a pure function of its slot
    key and token positions — independent of which other slots share
    the batch.  Join/leave churn changes the VALUES in ``keys``, never
    a shape, so the compiled decode program is reused as-is
    (filters/llm.py census pins).
    """
    import jax
    import jax.numpy as jnp

    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(logits, temperature, top_k, top_p)
    draw = jax.vmap(lambda kd, lg: jax.random.categorical(kd, lg, axis=-1))
    return draw(keys, logits).astype(jnp.int32)


def generate_scan(params, prompt, cfg: LlamaConfig, max_new: int,
                  temperature: float = 0.0, seed: int = 0,
                  compute_dtype="bfloat16", top_k: int = 0,
                  top_p: float = 1.0):
    """Whole generation as ONE jitted program (prefill + lax.scan decode):
    the throughput path for benchmarking — no host round-trip per token."""
    import jax
    import jax.numpy as jnp

    B, T = prompt.shape
    cache = init_cache(cfg, B, dtype=compute_dtype)
    logits, cache = forward_cached(params, prompt, cache, 0, cfg, compute_dtype)
    key = jax.random.PRNGKey(seed)
    tok0 = sample_token(logits[:, -1], key, temperature, top_k, top_p)

    def step(carry, i):
        tok, cache, key = carry
        key, sub = jax.random.split(key)
        logits, cache = forward_cached(params, tok[:, None], cache, T + i,
                                       cfg, compute_dtype)
        nxt = sample_token(logits[:, -1], sub, temperature, top_k, top_p)
        return (nxt, cache, key), tok

    (_, _, _), toks = jax.lax.scan(
        step, (tok0, cache, key), jnp.arange(max_new))
    return jnp.moveaxis(toks, 0, 1)  # [B, max_new]


# -- zoo builders ---------------------------------------------------------

def _build(preset: str, opts: Dict[str, str]) -> ModelBundle:
    cfg = PRESETS[preset]
    overrides = {}
    for field in ("vocab", "dim", "n_layers", "n_heads", "n_kv_heads",
                  "ffn_hidden", "max_seq"):
        if field in opts:
            overrides[field] = int(opts[field])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    seed = int(opts.get("seed", 0))
    dtype = opts.get("dtype", "bfloat16")
    # param_dtype=bfloat16 generates weights directly at 2 bytes/param on
    # device (required to fit 7B in one chip's HBM); default float32 keeps
    # the test presets' numerics unchanged.
    quant = str(opts.get("quant", "")).lower()
    if quant and cfg.patterned:
        _refuse_quant(cfg)
    if quant in ("int8", "int4"):
        # per-mat generate+quantize+donate: the full-precision tree is
        # never resident, so quantized 7B fits where generate-everything-
        # then-quantize OOMs a 16 GB chip
        init_q = init_params_int8 if quant == "int8" else init_params_int4
        params = init_q(cfg, seed=seed,
                        gen_dtype=opts.get("param_dtype", "float32"))
    else:
        params = init_params(cfg, seed=seed,
                             dtype=opts.get("param_dtype", "float32"))
        params = _apply_quant(params, opts)

    def apply_fn(params, tokens):
        return forward(params, tokens, cfg, compute_dtype=dtype)

    # Token streams are variable-length: FLEXIBLE format, spec per buffer.
    in_spec = TensorsSpec.from_string("1:1", "int32").replace(
        format=TensorFormat.FLEXIBLE)
    out_spec = TensorsSpec.from_string(f"{cfg.vocab}:1:1", "float32").replace(
        format=TensorFormat.FLEXIBLE)
    bundle = ModelBundle(
        apply_fn=apply_fn, params=params, in_spec=in_spec, out_spec=out_spec,
        # a patterned model's stacks have no TP layout
        # (tp_divisibility_problems refuses model_parallel > 1)
        param_pspecs=None if cfg.patterned else param_pspecs(quant=quant),
        name=preset,
    )
    bundle.config = cfg  # used by the llm framework for the decode loop
    return bundle


def build_from_checkpoint(path: str, opts: Dict[str, str]) -> ModelBundle:
    """Zoo entry for REAL weights: ``model=/path/llama.safetensors``.

    Same bundle contract as :func:`_build` but params come from
    :func:`load_checkpoint`; ``custom=param_dtype:...,max_seq:N`` apply.
    """
    pdt = opts.get("param_dtype", "bfloat16")
    if path.endswith(".gguf"):
        # gguf path: the tokenizer parses out of the SAME metadata read
        params, cfg, tok = _load_gguf(path, None, _resolve_param_dtype(pdt))
    else:
        params, cfg = load_checkpoint(path, dtype=pdt)
        tok = None
    if "max_seq" in opts:
        cfg = dataclasses.replace(cfg, max_seq=int(opts["max_seq"]))
    dtype = opts.get("dtype", "bfloat16")
    quant = str(opts.get("quant", "")).lower()
    params = _apply_quant(params, opts)

    def apply_fn(params, tokens):
        return forward(params, tokens, cfg, compute_dtype=dtype)

    in_spec = TensorsSpec.from_string("1:1", "int32").replace(
        format=TensorFormat.FLEXIBLE)
    out_spec = TensorsSpec.from_string(f"{cfg.vocab}:1:1", "float32").replace(
        format=TensorFormat.FLEXIBLE)
    bundle = ModelBundle(
        apply_fn=apply_fn, params=params, in_spec=in_spec, out_spec=out_spec,
        param_pspecs=param_pspecs(quant=quant), name=path,
        tokenizer=tok,
    )
    bundle.config = cfg
    return bundle


for _name in PRESETS:
    register_model(_name, functools.partial(_build, _name))
register_model("llama", functools.partial(_build, "llama_tiny"))
