"""LLM token-streaming framework for tensor_filter.

Reference analog: the llama.cpp sub-plugin
(``ext/nnstreamer/tensor_filter/tensor_filter_llamacpp.cc``, SURVEY §2.4
[UNVERIFIED]): ``tensor_filter framework=llamacpp`` takes a prompt buffer
and streams generated tokens downstream as flexible tensors.  Here the
runtime is JAX, not a wrapped C++ library:

* prefill and per-token decode are TWO jitted XLA programs (same function,
  two sequence lengths — see models/llama.py ``forward_cached``); weights
  and KV cache never leave HBM between tokens;
* multi-chip: ``Pipeline(model_parallel=N)`` hands the filter the
  pipeline's shared ``(data x model)`` mesh and params/KV shard over the
  ``model`` axis per the model's ``param_pspecs`` — XLA places the TP
  all-reduces on ICI (config #5's multi-chip token streaming).
  ``custom=tp:N`` is the deprecated pre-2-D alias: inside a pipeline it
  is promoted to ``model_parallel=N`` at construction; a standalone
  framework still builds a private ``(model=tp, data=1)`` mesh;
* tokens are pushed downstream from a generator in bursts of
  ``stream_chunk`` (default 8): each burst is ONE jitted lax.scan over the
  device (one host roundtrip per burst — over a remote chip this is the
  difference between ~5 and ~100s of tok/s); ``stream_chunk:1`` restores
  strict per-token delivery at per-token roundtrip cost.

Pipeline usage::

    appsrc name=prompt ! tensor_filter framework=llm model=llama_tiny
        custom=max_new:32,temperature:0.0 invoke-dynamic=true !
        tensor_sink name=tokens

Input: one uint8 tensor (UTF-8 prompt bytes) or int32 token ids ``[T]`` /
``[B, T]``.  Output per token: ``[B]`` int32 token ids + uint8 piece bytes
(batch 1 only), as FLEXIBLE tensors.  Tokenization uses the checkpoint's
own SentencePiece vocab when the model file carries one (GGUF
``tokenizer.ggml.*`` -> models/tokenizer.py) and falls back to byte-level
ids otherwise; with a real vocab, generation stops at the model's EOS
token like the reference sub-plugin.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from ..core.config import get_config
from ..core.log import logger, metrics
from ..core.registry import register_filter
from ..core.types import TensorFormat, TensorsSpec
from ..models import llama
from ..models.zoo import build as build_model
from ..utils import elastic, tracing
from ..core.meta_keys import (META_ABORT_REASON, META_QUERY_CONN,
                              META_ENQUEUE_NS, META_STREAM_ABORTED,
                              META_STREAM_ID, META_STREAM_INDEX,
                              META_STREAM_LAST, META_TRACE_ID)
from ..core.meta_keys import META_TENANT as _META_TENANT
from .base import (Framework, FrameworkError, parse_custom_options,
                   place_swapped_params)
from .kv_blocks import BlockManager

#: buffer-meta keys that must NOT ride a drain snapshot: the queue-stamp
#: map is the source pipeline's tracer plumbing, and the query
#: connection id routes sends on the SOURCE pipeline's server core — a
#: stale cid on the adopting side would deliver the stream's tokens to
#: whatever client holds that id there (the adopting deployment's front
#: door re-associates delivery; callers may re-stamp snapshot["meta"]
#: before adopt_stream).
_SNAPSHOT_META_DROP = (META_ENQUEUE_NS, META_QUERY_CONN)
#: the flight-recorder track (and profiler-annotation prefix) of the
#: continuous loop's spans — docs/OBSERVABILITY.md
_SERVE_STAGE = "llm.serve"
#: (trace id, admission ns, first prefilled position) of a request admitted
#: while tracing was off: its later spans carry no id, and it gets no
#: serve.prefill span
_NO_TRACE = (None, None, 0)

log = logger(__name__)


class _Tail:
    """What one stream is still owed of a settled decode chunk, BY VALUE:
    its slot may be seated again before these tokens have left."""

    __slots__ = ("meta", "emit", "start", "toks", "last", "time", "sid",
                 "tenant", "done")

    def __init__(self, meta, emit, start, toks, last, sid, tenant,
                 time_rec):
        self.meta, self.emit = meta, emit
        self.start = start    # stream index of toks[0]
        self.toks = toks      # the chunk's tokens this stream emits
        self.last = last      # toks[-1] ends the stream
        self.sid, self.tenant = sid, tenant
        self.time = time_rec  # the slot's timeline record (or None)
        self.done = 0         # tokens of toks that have left


class _Settled:
    """One decode chunk after settling (``_ContinuousLoop`` step 5): the
    host's books are closed on it, its tokens have yet to leave."""

    __slots__ = ("iter", "rows", "retired")

    def __init__(self, it, rows):
        self.iter, self.rows = it, rows
        self.retired = sum(1 for r in rows if r.last)

    def order(self, ending_first: bool):
        """``(tail, j, may_wait)`` for every token still owed, in the
        order they leave: token j of every stream before token j+1 of
        any; with ``ending_first`` the streams that end here go before
        that, each whole, and ``may_wait`` marks what follows them and
        every other stream's first token of the chunk.  Lazy: the caller
        advances ``tail.done`` as it goes and may stop."""
        if ending_first:
            for r in self.rows:
                if r.last:
                    for j in range(r.done, len(r.toks)):
                        yield r, j, False
        for j in range(max((len(r.toks) for r in self.rows), default=0)):
            for r in self.rows:
                if r.done == j and j < len(r.toks):
                    yield r, j, ending_first and j > 0


def _next_bucket(t: int) -> int:
    """Smallest power-of-two >= t (min 32): bounds distinct prefill
    compilations at log2(max_seq) programs for arbitrary prompt mixes."""
    b = 32
    while b < t:
        b <<= 1
    return b


#: Per-slot PRNG draw tags (docs/SERVING.md §4d).  Every device-side
#: draw folds (absolute token position, tag) into the slot's own key;
#: the tag separates the four draw kinds one position can host — the
#: non-spec sample, the draft proposal, the k accept uniforms, and the
#: residual/bonus resample.
TAG_SAMPLE, TAG_DRAFT, TAG_ACCEPT, TAG_FINAL = 100, 101, 102, 103


def _fold_slot_keys(keys, p, tag):
    """Per-draw derived keys ([B, 2] uint32 slot keys + [B] absolute
    positions -> [B, 2]): fold the position, then the draw tag."""
    import jax

    kk = jax.vmap(jax.random.fold_in)(keys, p)
    return jax.vmap(lambda kd: jax.random.fold_in(kd, tag))(kk)


def _commit_first_token(logits, tok, keys, base_key, adm_no, slot, T,
                        temperature: float, top_k: int = 0,
                        top_p: float = 1.0):
    """The tail of the prefill program: sample an admitted stream's first
    token from the prompt's last ``logits`` ([1, vocab]) and commit it.

    The stream's slot key is ``fold_in(base_key, adm_no)`` and the token,
    which sits at position ``T``, is drawn with that key folded at ``(T,
    TAG_SAMPLE)`` like every later one (an argmax at temperature 0).  Both
    are written into row ``slot`` of ``tok`` [B] and ``keys`` [B, 2];
    ``slot == B`` (out of range) commits nothing, which is how a chunk
    that is not a prompt's last goes through the same program.  Returns
    ``(first, tok, keys)``; ``first`` is int32[4] — the token, the slot
    key's two words, and whether the logits were all finite — so that ONE
    fetch brings home what the host mirrors."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sampler"):
        slot_key = jax.random.fold_in(base_key, adm_no)
        kft = jax.random.fold_in(jax.random.fold_in(slot_key, T), TAG_SAMPLE)
        first = llama.sample_token(logits, kft, temperature, top_k, top_p)
        tok = tok.at[slot].set(first[0], mode="drop")
        keys = keys.at[slot].set(slot_key, mode="drop")
        out = jnp.concatenate([
            first, jax.lax.bitcast_convert_type(slot_key, jnp.int32),
            jnp.isfinite(logits).all()[None].astype(jnp.int32)])
    return out, tok, keys


def spec_rejection_commit(pt, dprobs, props, keys, pos, live):
    """Standard speculative rejection sampling, vectorized per slot.

    ``pt`` [B, k+1, V]: the TARGET's filtered sampling distributions
    over (last committed token + k proposals); ``dprobs`` [B, k, V]:
    the DRAFT distributions each proposal was drawn from; ``props``
    [B, k]: the proposals; ``keys`` [B, 2]: slot base keys; ``pos``
    [B]: absolute positions (the fold anchor); ``live`` [B] bool:
    parked-row mask (parked rows commit nothing).

    Accepts proposal i iff ``u_i * q(x_i) < p(x_i)`` (u ~ U[0,1) from
    the slot key folded at (pos, TAG_ACCEPT)), keeps the longest
    accepted prefix, and resamples the first rejection from the
    normalized residual ``max(p - q, 0)`` — or the bonus distribution
    ``pt[k]`` when all k accept (padding q with a zero row makes that
    fall out of the same gather).  Emitted tokens are distributed
    EXACTLY as sampling the target one token at a time, which is the
    marginal tests/test_sampling.py chi-squares this helper against.

    Returns ``(em, acc)``: ``em`` [B, k+1] the emitted-token rows
    (accepted proposals, then the residual/bonus token at column
    ``acc``); ``acc`` [B] the accept counts.
    """
    import jax
    import jax.numpy as jnp

    k_spec = props.shape[1]
    q_x = jnp.take_along_axis(dprobs, props[:, :, None], axis=2)[:, :, 0]
    pt_x = jnp.take_along_axis(
        pt[:, :k_spec], props[:, :, None], axis=2)[:, :, 0]
    ka = _fold_slot_keys(keys, pos, TAG_ACCEPT)
    u = jax.vmap(lambda kd: jax.random.uniform(kd, (k_spec,)))(ka)
    ok = (u * q_x < pt_x).astype(jnp.int32)
    acc = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)
    acc = jnp.where(live, acc, 0)
    # residual at the first rejected position; padding q with a zero
    # row makes acc == k fall through to the bonus distribution pt[k]
    # automatically.  A residual with (numerically) zero mass can only
    # mean p == q at that position — fall back to pt itself.
    qpad = jnp.concatenate([dprobs, jnp.zeros_like(dprobs[:, :1])], axis=1)
    resid = jnp.maximum(pt - qpad, 0.0)
    r_at = jnp.take_along_axis(resid, acc[:, None, None], axis=1)[:, 0]
    pt_at = jnp.take_along_axis(pt, acc[:, None, None], axis=1)[:, 0]
    rsum = jnp.sum(r_at, axis=-1, keepdims=True)
    r = jnp.where(rsum > 1e-20, r_at, pt_at)
    kf = _fold_slot_keys(keys, pos, TAG_FINAL)
    final = jax.vmap(jax.random.categorical)(
        kf, jnp.log(jnp.maximum(r, 1e-38))).astype(jnp.int32)
    # emitted rows: accepted proposals then the final (residual/bonus)
    # token at column ``acc``
    em = jnp.concatenate([props, jnp.zeros_like(props[:, :1])], axis=1)
    col = jnp.arange(k_spec + 1)[None, :]
    em = jnp.where(col == acc[:, None], final[:, None], em)
    return em, acc


def serving_plan(cfg, *, slots: int, block_size: int = 16,
                 kv_blocks: int = 0, prefill_chunk: int = 32,
                 dtype: str = "bfloat16", draft_cfg=None,
                 spec_k: int = 4, temperature: float = 0.0) -> Dict[str, int]:
    """Static sizing of the paged-KV serving state, WITHOUT building
    anything — one home for the arithmetic :class:`_ContinuousLoop` and
    the deep lint's resource report (analysis/tracecheck.py) must agree
    on, so pricing a 7B pool never materializes 7B params.

    Returns a dict:

    * ``max_blocks`` — block-table width per slot.  Prefill pads prompts
      to ``prefill_chunk`` multiples, so the table must span the largest
      padded prompt (its final chunk's END position), not just
      ``max_seq`` — otherwise that chunk's context length would clamp to
      zero mid-prefill.  The extra entries stay sentinel forever.
      (Prefix sharing keeps this bound: a cache-hit prompt starts its
      suffix prefill at a ``prefill_chunk`` multiple, so the padded END
      position never exceeds the cold-path's.)
    * ``n_blocks`` — pool size.  ``kv_blocks`` 0 = worst case
      (``slots * ceil(max_seq/block_size)``: admission never defers on
      blocks); larger is clamped (a slot can't use more than its table).
    * ``pool_bytes`` — HBM the k+v block pool occupies
      (:func:`~nnstreamer_tpu.models.llama.paged_cache_bytes`).
    * ``draft_pool_bytes`` — the draft model's block pool when
      speculative decoding is configured (``draft_cfg`` non-None): the
      draft shares the allocator, block tables, and ``n_blocks`` with
      the target, so its pool is the same geometry at the draft's
      (L, H_kv, hd) — 0 without a draft.
    * ``decode_bytes_per_ctx_token`` — per-decode-step HBM traffic the
      paged attention kernel reads PER LIVE CONTEXT TOKEN: K + V rows
      across every layer at the model's ``n_kv_heads`` — NOT
      ``n_heads``.  The kernel DMAs each K/V block once per query-head
      GROUP (ops/attention.py), so a GQA config's decode traffic is
      ``n_kv_heads/n_heads`` of the repeated-layout figure; predicted
      step bytes = (sum of live context lengths, block-rounded) x this
      coefficient.  nns-xray's roofline attribution and the deep lint
      consume it — pricing with ``n_heads`` here is exactly the stale
      over-prediction the reconciliation regression pins.
    * ``kv_groups`` — ``n_heads // n_kv_heads``, the per-block DMA
      sharing factor of the grouped kernel (1 = plain MHA, no win).
    * ``prng_state_bytes`` — the sampler's per-slot PRNG key state
      (one uint32[2] counter key per slot) carried device-resident when
      ``temperature > 0``; 0 for greedy loops.  Tiny, but the xray HBM
      ledger reconciles measured-vs-predicted by category, so an
      unpriced resident buffer is a drift seed.
    * ``win_ring`` / ``win_blocks`` — a model with window layers keeps
      their K/V in a second pool that no allocator touches: every slot
      owns a RING of ``win_ring`` blocks
      (:func:`~nnstreamer_tpu.models.llama.window_ring_blocks`: the
      window, one prefill chunk, one block of slack), ``win_blocks =
      slots * win_ring`` in all; both 0 without window layers.
      ``pool_bytes`` counts both pools, and
      ``decode_bytes_per_ctx_token`` only the full-attention layers (a
      window layer reads at most its window, whatever the context).
    * latent layers (``LayerKind.latent``) keep one row a token for all
      heads, ``latent_width`` values, in the allocator's blocks:
      ``pool_bytes`` counts them at the pool's padded width (what HBM
      holds), ``decode_bytes_per_ctx_token`` at ``latent_width`` (what
      the mathematics reads: the padding is the kernel's cost).
    * ``conv_state_bytes`` — convolution layers (``LayerKind.conv``) keep
      ``conv_taps - 1`` columns a stream a layer whatever its context,
      owned by the slot like a ring: ``slots`` streams' worth, counted in
      ``pool_bytes``; 0 without such layers.  A decode step reads and
      writes all of it, which no context length changes.
    * ``programs`` — compiled XLA signatures the standing loop ever
      uses.  Without speculation: the ``[slots]``-row paged decode
      chunk, the ``[1, prefill_chunk]`` prefill step, and the slot-token
      setter (3).  With a draft model the decode chunk is REPLACED by
      the propose/verify pair and the draft gets its own prefill step:
      target prefill, draft prefill, draft propose (k draft steps + the
      refresh step as ONE scan), target verify (a ``[slots, k+1]``-wide
      paged step that commits tokens/positions in-program), and the
      slot-token setter (5).  Every shape is static in admission state —
      stream join/leave/complete AND accept/reject ratios change VALUES
      only — which is why this census is CLOSED (the compile-counter
      pins in tests/test_llm_continuous.py and tests/test_spec_decode
      .py).  Sampling (``temperature > 0``) swaps program BODIES (the
      sampler is compiled in, per-slot keys ride as values), never the
      count.
    """
    import math

    from ..models import llama as _llama

    bs = max(1, int(block_size))
    C = max(1, int(prefill_chunk))
    itemsize = 2 if str(dtype) in ("bfloat16", "float16") else 4
    hd = cfg.head_dim
    win_ring = _llama.window_ring_blocks(cfg, bs, C)
    pad_max = math.ceil((cfg.max_seq - 1) / C) * C
    # Speculation: the final rounds dispatch the fixed [slots, k+1]-wide
    # verify (and the k-step propose scan) even when fewer tokens remain,
    # so positions reach up to max_seq-1 + k.  The table must SPAN them
    # or forward_paged's stale-table clamp zeroes the whole row's context
    # and the committed tokens go bit-wrong near max_seq.  The extra
    # entries stay sentinel: overrun writes drop, and causal masking
    # keeps every COMMITTED token's logits independent of the dropped
    # tail — bit-identity holds right up to the last token.
    seq_span = cfg.max_seq + (max(1, int(spec_k))
                              if draft_cfg is not None else 0)
    max_blocks = math.ceil(max(seq_span, pad_max) / bs)
    worst = int(slots) * math.ceil(cfg.max_seq / bs)
    n_blocks = min(int(kv_blocks), worst) if kv_blocks else worst
    return {
        "max_blocks": max_blocks,
        "n_blocks": n_blocks,
        "pool_bytes": _llama.paged_cache_bytes(
            cfg, n_blocks, bs, dtype=dtype,
            win_blocks=int(slots) * win_ring, slots=int(slots)),
        "win_ring": win_ring,
        "win_blocks": int(slots) * win_ring,
        "conv_state_bytes": _llama.conv_state_bytes(cfg, slots, dtype),
        "draft_pool_bytes": (
            _llama.paged_cache_bytes(draft_cfg, n_blocks, bs, dtype=dtype)
            if draft_cfg is not None else 0),
        # K + V, every layer, at the KV-head count — the grouped kernel's
        # per-context-token decode read (ops/attention.py shares each
        # block DMA across the whole query-head group)
        "decode_bytes_per_ctx_token": (
            2 * cfg.n_full_layers * cfg.n_kv_heads * hd
            + cfg.n_latent_layers * cfg.latent_width) * itemsize,
        "kv_groups": cfg.n_heads // cfg.n_kv_heads,
        "prng_state_bytes": (int(slots) * 2 * 4
                             if float(temperature) > 0.0 else 0),
        "programs": 5 if draft_cfg is not None else 3,
    }


class ByteTokenizer:
    """Byte-level tokenizer: id = byte + n_special.  Deterministic, no vocab
    file.  ids 0..n_special-1 are special (0=pad, 1=bos, 2=eos)."""

    n_special = 3
    bos = 1
    eos = 2

    def encode(self, text_bytes: bytes) -> List[int]:
        return [self.bos] + [b + self.n_special for b in text_bytes]

    def decode_piece(self, token_id: int) -> bytes:
        if token_id < self.n_special:
            return b""
        b = token_id - self.n_special
        return bytes([b]) if b < 256 else b""


@register_filter("llm", aliases=("llamacpp", "llama.cpp"))
class LLMFramework(Framework):
    """Streaming generation.  ``custom=`` options:

    ``max_new:N`` (default 32), ``temperature:F`` (0 = greedy), ``seed:N``,
    ``top_k:N`` / ``top_p:F`` (sampler truncation, compiled into the
    decode program — llama.cpp's sampler-chain analog),
    ``tokenizer:PATH`` (a .gguf whose ``tokenizer.ggml.*`` vocab is used
    for text; defaults to the model file's own vocab when it has one,
    byte-level otherwise),
    ``stream_chunk:N`` (tokens decoded per device roundtrip, default 8;
    1 = strict per-token streaming),
    ``tp:N`` (DEPRECATED alias of ``Pipeline(model_parallel=N)`` —
    promoted to the pipeline knob at construction so the filter runs on
    the shared ``(data x model)`` mesh; kept for standalone frameworks,
    which build a private ``model``-axis mesh),
    ``serve:continuous`` + ``slots:N`` (continuous batching: a standing
    decode loop over a block-paged KV cache that admits queued prompts
    into free slots via chunked prefill — see :class:`_ContinuousLoop`),
    ``block_size:N`` (KV pool block granularity, default 16),
    ``kv_blocks:N`` (pool size in blocks; default 0 = worst-case
    ``slots * ceil(max_seq/block_size)``; smaller pools defer admission
    instead of overflowing),
    ``prefill_chunk:N`` (tokens per chunked-prefill step, default 32) and
    ``prefill_budget:N`` (prefill tokens interleaved per decode
    iteration, default one chunk),
    ``quant:int8`` / ``quant:int4`` (weight-only quantization; int4 is
    nibble-packed and decodes through the Pallas kernel in
    ops/int4_matmul.py on TPU),
    ``dtype:bfloat16|float32``, plus any model-builder options
    (``dim:…``, ``n_layers:…``) forwarded to the zoo.
    """

    name = "llm"
    streaming = True

    def __init__(self):
        super().__init__()
        self.bundle = None
        self.cfg: Optional[llama.LlamaConfig] = None
        self.tokenizer = ByteTokenizer()
        self.max_new = 32
        self.temperature = 0.0
        self.top_k = 0
        self.top_p = 1.0
        self.seed = 0
        self.stop_eos = False
        self.mesh = None
        self._fwd = None
        self.continuous = False
        self.prefix_cache = True
        self.draft_name = ""
        self.draft_bundle = None
        self.draft_cfg = None
        self.spec_k = 4
        self._serve: Optional["_ContinuousLoop"] = None
        self._serve_lock = threading.Lock()

    def open(self, props: Dict[str, object]) -> None:
        super().open(props)
        model = str(props.get("model") or "llama_tiny")
        opts = parse_custom_options(str(props.get("custom", "")))
        self.max_new = int(opts.pop("max_new", 32))
        self.temperature = float(opts.pop("temperature", 0.0))
        self.top_k = int(opts.pop("top_k", 0))
        self.top_p = float(opts.pop("top_p", 1.0))
        self.seed = int(opts.pop("seed", 0))
        tok_path = opts.pop("tokenizer", None)
        stop_opt = opts.pop("stop_eos", None)
        # Tokens decoded per device roundtrip (stream granularity): tokens
        # still stream downstream one-by-one, in bursts of this size.
        self.chunk = max(1, int(opts.pop("stream_chunk", 8)))
        tp = int(opts.pop("tp", 1))
        # serve:continuous — a standing decode loop with ``slots:N`` rows:
        # prompts are admitted into free slots of a RUNNING per-row-
        # position decode (each stream at its own depth), so a late
        # client never waits for earlier streams to finish the way a
        # static group would make it.  Modern "continuous batching"; no
        # reference analog.
        self.continuous = str(opts.pop("serve", "")).lower() == "continuous"
        self.slots = int(opts.pop("slots", 4))
        # Paged-KV serving knobs (see _ContinuousLoop): pool granularity,
        # pool size (0 = worst case: no admission ever defers), chunked-
        # prefill step and the per-iteration prefill token budget.
        self.block_size = max(1, int(opts.pop("block_size", 16)))
        self.kv_blocks = max(0, int(opts.pop("kv_blocks", 0)))
        self.prefill_chunk = max(1, int(opts.pop("prefill_chunk", 32)))
        self.prefill_budget = max(
            1, int(opts.pop("prefill_budget", self.prefill_chunk)))
        # Prefix sharing (docs/SERVING.md §4b): hash token-block chains
        # so a shared system prompt / few-shot preamble prefills ONCE
        # and maps copy-on-write into every stream's block table.
        # Host-only behavior (refcounts, the hash index) — no compiled
        # signature changes, so it is runtime-safe to flip.
        self.prefix_cache = str(opts.pop("prefix_cache", "1")).lower() \
            not in ("0", "false", "no")
        # Speculative decoding (docs/SERVING.md §4c): ``draft:<preset>``
        # builds a small draft model that proposes ``spec_k`` tokens per
        # round; the target verifies them in ONE fixed-shape
        # [slots, k+1]-wide paged step.  Greedy (temperature:0):
        # acceptance is exact prefix match against the target's own
        # argmax, so the emitted stream is bit-identical to plain
        # decode.  Sampled (temperature>0): standard speculative
        # rejection sampling — each proposal is accepted with
        # min(1, p_target/p_draft) and rejections resample from the
        # normalized residual, so every emitted token is distributed
        # EXACTLY as non-speculative sampling (docs/SERVING.md §4d).
        self.draft_name = str(opts.pop("draft", "") or "")
        self.spec_k = max(1, int(opts.pop("spec_k", 4)))
        draft_seed = int(opts.pop("draft_seed", 0))
        # Elastic-serving knobs (docs/SERVING.md "Elastic serving"):
        # admit_timeout bounds how long a prompt may sit at the
        # admission queue's head waiting for capacity before it is
        # rejected with a typed abort (0 = wait forever, the pre-elastic
        # behavior); stream_idle_timeout is the grace between a stream
        # being marked orphaned (its connection died —
        # utils/elastic.cancel_stream) and its slot + KV blocks being
        # reaped back to the free list.
        self.admit_timeout = max(0.0, float(opts.pop("admit_timeout",
                                                     30.0)))
        self.stream_idle_timeout = max(
            0.0, float(opts.pop("stream_idle_timeout", 5.0)))
        # nns-armor (docs/ROBUSTNESS.md): ``nan_guard:1`` checks every
        # admitted prompt's final prefill logits for NaN/Inf — a
        # poisoned request is quarantined (DLQ, when the pipeline
        # configured one) and answered with a typed
        # ``abort_reason=poison`` terminator instead of decoding
        # garbage (or crashing the loop) from corrupt activations.
        # Pays one [1, vocab] host fetch per admitted prompt.
        self.nan_guard = str(opts.pop("nan_guard", "0")).lower() \
            in ("1", "true", "yes")
        self.dtype = opts.get("dtype", "bfloat16")
        try:
            self.bundle = build_model(model, opts)
        except KeyError as e:
            raise FrameworkError(str(e)) from e
        self.cfg = getattr(self.bundle, "config", None)
        if self.cfg is None:
            raise FrameworkError(
                f"model {model!r} has no LlamaConfig; the llm framework needs "
                "a decoder-LM bundle (models/llama.py)"
            )
        self.draft_bundle = None
        self.draft_cfg = None
        if self.cfg.patterned:
            # what is not built for a patterned model (layer pattern,
            # sparse experts, q/k norm) refuses here, with the reason,
            # instead of serving it wrong (docs/SERVING.md §4e)
            if not self.continuous:
                raise FrameworkError(
                    "a patterned model is served by serve:continuous "
                    "only: the per-request stream path keeps a dense "
                    "per-slot cache (forward_cached), which computes the "
                    "one-kind decoder")
            if self.draft_name:
                raise FrameworkError(
                    "draft: with a patterned target is not built: the "
                    "k+1-wide verify step writes k+1 positions into a "
                    "window layer's ring, and a rejected tail would have "
                    "overwritten rows the window still needs; over a "
                    "latent pool its k+1 queries take the gather "
                    "reference, not the kernel; a convolution layer's "
                    "state is advanced by every token the step is fed, "
                    "so a rejected tail would already have moved it "
                    "past the accepted prefix, and nothing keeps the "
                    "columns to step back to; this target has "
                    f"{llama.pattern_traits(self.cfg)} "
                    "(self-drafting from a prediction head: ROADMAP M1)")
        if self.draft_name:
            if not self.continuous:
                raise FrameworkError(
                    "draft: (speculative decoding) requires "
                    "serve:continuous — the per-request stream path has "
                    "no standing verify loop")
            # temperature > 0 composes with the draft: verify switches
            # from exact-prefix-match to speculative rejection sampling
            # (distribution-equivalent to the non-spec sampler, see
            # docs/SERVING.md §4d) — no guard needed here.
            if self.draft_name not in llama.PRESETS:
                raise FrameworkError(
                    f"draft model {self.draft_name!r} must be a preset "
                    "zoo name (the deep lint prices the draft's params "
                    "statically; a checkpoint path cannot be)")
            # the draft MUST share the target's token space and position
            # span: vocab/max_seq are overridden onto the draft preset so
            # its proposals are target token ids at target positions
            self.draft_bundle = build_model(self.draft_name, {
                "vocab": str(self.cfg.vocab),
                "max_seq": str(self.cfg.max_seq),
                "seed": str(draft_seed),
                "param_dtype": str(opts.get("param_dtype", "float32")),
            })
            self.draft_cfg = self.draft_bundle.config
        # Tokenizer priority: explicit custom=tokenizer:PATH, then the
        # model file's own embedded vocab, then the byte-level fallback.
        if tok_path is not None:
            from ..models.tokenizer import load_gguf_tokenizer

            tok = load_gguf_tokenizer(str(tok_path))
            if tok is None:
                raise FrameworkError(
                    f"tokenizer file {tok_path!r} carries no "
                    "tokenizer.ggml.tokens vocab")
            self.tokenizer = tok
        elif getattr(self.bundle, "tokenizer", None) is not None:
            self.tokenizer = self.bundle.tokenizer
        n_tok = getattr(self.tokenizer, "n_vocab", 0)
        if n_tok > self.cfg.vocab:
            # XLA CLAMPS out-of-range embedding gathers instead of
            # raising — a vocab bigger than the model would silently
            # generate from wrong embeddings
            raise FrameworkError(
                f"tokenizer vocab ({n_tok}) exceeds model vocab "
                f"({self.cfg.vocab}); wrong tokenizer for this model")
        # EOS terminates generation when a real vocab is in play (the
        # llama.cpp contract); byte-level ids keep fixed-length decode so
        # synthetic-model tests and benches stay deterministic.
        # Override with custom=stop_eos:0/1.
        stop = stop_opt
        if stop is None:
            self.stop_eos = not isinstance(self.tokenizer, ByteTokenizer)
        else:
            self.stop_eos = str(stop).lower() not in ("0", "false", "no")
        self._setup(tp)

    def _setup(self, tp: int) -> None:
        import jax

        from ..parallel.mesh import make_mesh, mesh_axis_size
        from ..parallel.sharding import shard_params

        cfg = self.cfg
        params = self.bundle.params

        mesh = None
        provider = getattr(self, "_mesh_provider", None)
        if provider is not None:
            # Pipeline-owned 2-D mesh (runtime.Pipeline._model_mesh): a
            # configured model_parallel — or the deprecated custom=tp:
            # alias, promoted at Pipeline construction — resolves to ONE
            # shared (data x model) mesh for the whole pipeline; None
            # when the pipeline runs model_parallel=1.
            try:
                mesh = provider()
            except Exception as e:
                from ..pipeline.runtime import PipelineError

                if isinstance(e, PipelineError):
                    # a pipeline-level placement error (over-asked
                    # dp x mp, non-divisible plan): propagate as-is —
                    # wrapping it in FrameworkError would make
                    # _load_framework try other frameworks and report
                    # "no framework could open", burying the real cause
                    raise
                raise FrameworkError(str(e)) from e
            if mesh is not None and mesh_axis_size(mesh, "model") <= 1:
                mesh = None
        if mesh is None and tp > 1:
            # standalone/legacy path (framework embedded outside a
            # pipeline): a private (model=tp, data=1) mesh, kept so
            # direct LLMFramework users keep working
            if len(jax.devices()) < tp:
                raise FrameworkError(
                    f"tp:{tp} needs {tp} devices, have {len(jax.devices())}")
            mesh = make_mesh(model=tp, data=1,
                             devices=jax.devices()[:tp])
        if mesh is not None:
            ways = mesh_axis_size(mesh, "model")
            problems = llama.tp_divisibility_problems(cfg, ways)
            if self.draft_cfg is not None:
                problems += [
                    f"draft {p}" for p in
                    llama.tp_divisibility_problems(self.draft_cfg, ways)]
            if problems:
                # fail with the dims named instead of a GSPMD/device_put
                # reshape error mid-shard (the deep lint reports the same
                # arithmetic statically — model-divisibility)
                raise FrameworkError(
                    f"model geometry does not divide model_parallel="
                    f"{ways}: " + "; ".join(problems))
            self.mesh = mesh
            # the bundle's pspecs match ITS pytree (quantized trees have
            # different leaves than llama.param_pspecs()'s default)
            pspecs = self.bundle.param_pspecs or llama.param_pspecs()
            params = shard_params(mesh, params, pspecs)
            self.bundle.params = params
            if self.draft_bundle is not None:
                # the draft shards over the same mesh — its pspecs match
                # its own (unquantized) pytree
                dspecs = self.draft_bundle.param_pspecs \
                    or llama.param_pspecs()
                self.draft_bundle.params = shard_params(
                    mesh, self.draft_bundle.params, dspecs)
            # pallas_call has no GSPMD partitioning rule: int4 and paged-
            # attention programs traced for this sharded mesh must take
            # their shardable XLA reference paths.  Refcounted disables,
            # taken LAST in the TP block (nothing after them throws) and
            # released in close(), so a failed open can't leak a disabled
            # kernel and two TP filters don't clobber each other.
            from ..ops import attention as _attn
            from ..ops import int4_matmul as _i4

            _i4.disable_kernel()
            _attn.disable_paged_kernel()
            self._int4_disabled = True

        def fwd(params, tokens, cache, pos):
            return llama.forward_cached(params, tokens, cache, pos, cfg,
                                        compute_dtype=self.dtype)

        # Prefill program (only ever called with pos=0).  pos is STATIC so
        # the trace sees a Python int and models/llama.py's prefill branch
        # (flash attention over the prompt, not a masked sweep over all
        # max_seq cache rows) actually compiles in; a traced pos would make
        # `type(pos_offset) is int` False at trace time.  Cache donated so
        # prefill writes in place.
        self._fwd = jax.jit(fwd, static_argnums=(3,), donate_argnums=(2,))

        temperature = self.temperature
        top_k, top_p = self.top_k, self.top_p

        def decode_chunk(params, tok, cache, key, pos0, length):
            """`length` decode steps as ONE program (lax.scan): the host sees
            one D2H fetch per chunk, not per token."""
            import jax.numpy as jnp
            from jax import lax

            def step(carry, i):
                tok, cache, key = carry
                key, sub = jax.random.split(key)
                logits, cache = llama.forward_cached(
                    params, tok[:, None], cache, pos0 + i, cfg,
                    compute_dtype=self.dtype)
                nxt = llama.sample_token(logits[:, -1], sub, temperature,
                                         top_k, top_p)
                return (nxt, cache, key), nxt

            (tok, cache, key), toks = lax.scan(
                step, (tok, cache, key), jnp.arange(length))
            return jnp.moveaxis(toks, 0, 1), tok, cache, key  # [B, length]

        self._decode_chunk = jax.jit(
            decode_chunk, static_argnames=("length",), donate_argnums=(2,))
        self._wrap_stream_xray()

    def attach_xray(self, registry, stage, rec=None):
        super().attach_xray(registry, stage, rec)
        self._wrap_stream_xray()

    def _wrap_stream_xray(self) -> None:
        """nns-xray: the per-request stream path's programs are recorded
        UNBOUNDED (no expectation) — prompt-length bucketing bounds them
        in practice, but the deep lint calls invoke-dynamic stages
        recompile-unbounded and the live census mirrors that verdict.
        The serve loop's closed 3-program census registers separately
        (_ContinuousLoop)."""
        xr = getattr(self, "_xray", None)
        if xr is None:
            return
        stage = getattr(self, "_xray_stage", "llm")
        rec = getattr(self, "_xray_rec", None)
        if getattr(self, "_fwd", None) is not None:
            self._fwd = xr.track(self._fwd, stage, "llm.prefill", rec=rec)
        if getattr(self, "_decode_chunk", None) is not None:
            self._decode_chunk = xr.track(self._decode_chunk, stage,
                                          "llm.decode", rec=rec)

    def close(self) -> None:
        if self._serve is not None:
            self._serve.shutdown()
            self._serve = None
        if getattr(self, "_int4_disabled", False):
            from ..ops import attention as _attn
            from ..ops import int4_matmul as _i4

            _i4.enable_kernel()
            _attn.enable_paged_kernel()
            self._int4_disabled = False
        self.bundle = None
        self.draft_bundle = None
        self._fwd = None
        self._decode_chunk = None

    # -- continuous serving ------------------------------------------------
    def submit(self, inputs: Sequence, meta: Dict, emit) -> int:
        """Queue one prompt into the standing decode loop
        (``custom=serve:continuous``).  ``emit(tensors, meta)`` is called
        from the serve thread once per generated token, carrying the
        request's meta plus stream_index/stream_last.  Returns the
        minted stream id (also stamped into every emitted token's meta
        — the :meth:`drain_stream`/utils.elastic handle)."""
        # Lock the lazy creation: two first-submits racing from different
        # threads must not spawn two serve loops (duplicate slot caches,
        # split streams) — the framework API stays safe outside the
        # single-runner pipeline assumption.
        if self._serve is None:
            with self._serve_lock:
                if self._serve is None:
                    self._serve = _ContinuousLoop(self)
        return self._serve.submit(self._to_tokens(inputs[0]), meta, emit)

    def drain(self, timeout: float = 600.0) -> bool:
        """Block until every admitted stream has finished (EOS path)."""
        return self._serve is None or self._serve.drain(timeout)

    # -- elastic serving: drain/adopt (docs/SERVING.md "Elastic serving")
    def serve_streams(self) -> Dict[int, Dict]:
        """Live/queued continuous-serving streams of THIS framework:
        ``stream_id -> {"state", "tenant", "slot", "blocks"}``."""
        if self._serve is None:
            return {}
        return self._serve.stream_table()

    def drain_stream(self, stream_id: int, timeout: float = 30.0) -> Dict:
        """Serialize one live (or still-queued) stream OFF the standing
        loop: its paged KV blocks, slot state, and request meta become a
        host-value snapshot (trainer/checkpoint.py's serialization
        substrate), and its slot + blocks return to the free list.
        Greedy continuation after :meth:`adopt_stream` is bit-identical
        to an undrained run; sampled (temperature > 0) streams carry
        their per-slot PRNG key in the snapshot (``prng_key``), so a
        same-seed continuation is ALSO bit-identical — the key is a
        pure function of (framework seed, admission number) and every
        draw folds in the absolute token position, never the slot or
        wall-clock step (docs/SERVING.md §4d)."""
        self._refuse_two_pool("drain_stream")
        if self._serve is None:
            raise FrameworkError("no continuous serve loop is running")
        return self._serve.drain_stream(int(stream_id), timeout)

    def _refuse_two_pool(self, what: str) -> None:
        if self.cfg is not None and self.cfg.n_window_layers:
            raise FrameworkError(
                f"{what} of a two-pool slot is not built: a snapshot "
                "carries the full-attention layers' blocks only, and a "
                "window layer's ring (its last window of K/V, per slot) "
                "has no place in it; continuing from such a snapshot "
                "would attend an empty window")
        if self.cfg is not None and self.cfg.n_conv_layers:
            raise FrameworkError(
                f"{what} of a slot that owns convolution state is not "
                "built: a snapshot carries the allocator's blocks only, "
                "and the convolution layers' last columns (per slot, in "
                "no block) have no place in it; continuing from such a "
                "snapshot would filter from zeros")

    def snapshot_problems(self, snapshot: Dict) -> List[str]:
        """Compatibility problems adopting ``snapshot`` here (empty =
        adoptable).  The drain/adopt contract: same model geometry,
        compute dtype, and block size — everything else (slots,
        kv_blocks, prefill knobs) may differ between the pipelines."""
        import dataclasses as _dc

        problems: List[str] = []
        if not isinstance(snapshot, dict):
            return ["snapshot must be a dict (drain_stream's return)"]
        if snapshot.get("version") not in (1, 2):
            problems.append(
                f"snapshot version {snapshot.get('version')!r} "
                "unsupported (expected 1 or 2)")
            return problems
        if self.draft_name and snapshot.get("kind") == "live" \
                and "tok_prev" not in snapshot:
            # the speculative refresh step re-feeds the second-to-last
            # committed token; a pre-speculation (v1) snapshot does not
            # carry it — still adoptable by any non-speculating loop
            problems.append(
                "snapshot predates speculative decoding (no tok_prev); "
                "adopt it on a loop without draft:, or re-drain from a "
                "current pipeline")
        if snapshot.get("cfg") != _dc.asdict(self.cfg):
            problems.append("model geometry differs from the snapshot's")
        if snapshot.get("kind") == "live":
            if str(snapshot.get("dtype")) != str(self.dtype):
                problems.append(
                    f"compute dtype {snapshot.get('dtype')!r} != "
                    f"{self.dtype!r} (KV block contents are dtype-exact)")
            if int(snapshot.get("block_size", -1)) != self.block_size:
                problems.append(
                    f"block_size {snapshot.get('block_size')!r} != "
                    f"{self.block_size} (block contents do not re-chunk)")
        return problems

    def adopt_stream(self, snapshot: Dict, emit,
                     timeout: float = 30.0) -> int:
        """Re-admit a drained stream into THIS framework's standing loop
        (creating it on first use, exactly like :meth:`submit`): its KV
        blocks are copied back into the pool, its slot state restored,
        and decode continues — ``emit(tensors, meta)`` receives the
        remaining tokens with ``stream_index`` continuing where the
        drained pipeline stopped.  Returns the stream id (stable across
        the handover unless it collides with a live local id)."""
        self._refuse_two_pool("adopt_stream")
        problems = self.snapshot_problems(snapshot)
        if problems:
            raise FrameworkError(
                "cannot adopt stream snapshot: " + "; ".join(problems))
        if self._serve is None:
            with self._serve_lock:
                if self._serve is None:
                    self._serve = _ContinuousLoop(self)
        return self._serve.adopt_stream(snapshot, emit, timeout)

    def swap_params(self, tree) -> Optional[int]:
        """Hot-swap the live weights (nns-learn train-while-serve).  With
        a standing serve loop the swap executes as a control command AT
        A CHUNK BOUNDARY — the drain/adopt discipline: every slot's host
        bookkeeping is consistent, the three compiled loop programs take
        params as arguments, and aval-identical leaves mean the census
        stays closed (zero recompiles, pinned by test) — and returns the
        loop's new param version.  Without a loop the stream path reads
        ``bundle.params`` per request, so the next request serves the
        new weights (returns None)."""
        if self.bundle is None:
            raise FrameworkError("framework is not open")
        if self._serve is not None:
            return self._serve.swap_params(tree)
        self.bundle.params = place_swapped_params(self.bundle.params, tree)
        return None

    def get_model_info(self):
        flex_in = TensorsSpec.from_string("1", "uint8").replace(
            format=TensorFormat.FLEXIBLE)
        flex_out = TensorsSpec.from_string("1", "int32").replace(
            format=TensorFormat.FLEXIBLE)
        return flex_in, flex_out

    def param_bytes(self) -> int:
        """Live parameter bytes (quantized trees included — nibble-packed
        int4 leaves report their packed nbytes).  Feeds the deep pass
        AND nns-xray's measured HBM ledger — without it an llm
        pipeline's ledger read 0 params against a priced estimate, which
        is exactly the under-prediction drift the reconciler warns on."""
        bundle = getattr(self, "bundle", None)
        if bundle is None or bundle.params is None:
            return 0
        from .base import tree_param_bytes

        total = tree_param_bytes(bundle.params)
        draft = getattr(self, "draft_bundle", None)
        if draft is not None and draft.params is not None:
            # the speculative-decoding draft lives in HBM beside the
            # target for the stage lifetime — the deep lint prices it
            # (draft params in the resource report), so the measured
            # side must include it or the ledger ratio drifts
            total += tree_param_bytes(draft.params)
        return total

    # -- tokenization ------------------------------------------------------
    def _to_tokens(self, arr: np.ndarray) -> np.ndarray:
        arr = np.asarray(arr)
        if arr.dtype == np.uint8:
            ids = self.tokenizer.encode(arr.tobytes())
            return np.asarray([ids], np.int32)
        toks = arr.astype(np.int32)
        if toks.ndim == 1:
            toks = toks[None, :]
        if toks.ndim != 2:
            raise FrameworkError(f"prompt must be [T] or [B,T], got {arr.shape}")
        return toks

    # -- generation --------------------------------------------------------
    def _gen_tokens(self, prompt: np.ndarray) -> Iterator[np.ndarray]:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        B, T = prompt.shape
        if T >= cfg.max_seq:
            raise FrameworkError(
                f"prompt length {T} >= max_seq {cfg.max_seq}")
        cache = llama.init_cache(cfg, B, dtype=self.dtype)
        if self.mesh is not None:
            from ..parallel.sharding import shard_params as _sp
            cache = _sp(self.mesh, cache, llama.cache_pspecs())
        params = self.bundle.params
        # Prompt-length bucketing (SURVEY §7 "dynamic shapes vs XLA static
        # shapes"): the prefill program compiles per SHAPE, so serving
        # mixed-length prompts would compile per length.  Right-pad to the
        # next bucket: causal attention keeps real tokens from seeing pad
        # rows, decode overwrites cache row `pos` before any later
        # position can attend it, and the sampled logit is read at the
        # REAL last position — numerics are untouched (asserted by test).
        P = T
        if get_config().shape_bucketing:
            P = min(_next_bucket(T), cfg.max_seq - 1)
        if P > T:
            prompt = np.pad(prompt, ((0, 0), (0, P - T)))
        logits, cache = self._fwd(params, jnp.asarray(prompt), cache, 0)
        key = jax.random.PRNGKey(self.seed)
        # At least one token is always safe: prefill wrote cache[0:P]
        # (real rows 0:T; rows T..P-1 hold pad-token K/V that stay hidden
        # behind the decode mask until sequentially overwritten) and the
        # first sample needs no further cache write.  Subsequent decode
        # steps feed at positions T..T+n-2, each of which must stay
        # < max_seq.
        n = max(1, min(self.max_new, cfg.max_seq - T))
        # EOS termination (batch-1 streams; batched rows finish at their
        # own depths, so callers slice on ids themselves)
        eos = getattr(self.tokenizer, "eos", -1) if self.stop_eos else -1
        tok = llama.sample_token(logits[:, T - 1], key, self.temperature,
                                 self.top_k, self.top_p)
        first = np.asarray(tok)
        yield first
        if B == 1 and int(first[0]) == eos:
            return
        done = 1
        pos = T
        while done < n:
            # Chunked decode; a shorter tail chunk costs one extra compile
            # (two cached programs total: full chunk + tail).  n's clamp
            # already guarantees every decode position stays < max_seq.
            length = min(self.chunk, n - done)
            toks, tok, cache, key = self._decode_chunk(
                params, tok, cache, key, pos, length=length)
            host = np.asarray(toks)  # ONE roundtrip per chunk
            for j in range(length):
                yield host[:, j]
                if B == 1 and int(host[0, j]) == eos:
                    return
            done += length
            pos += length

    def invoke_stream(self, inputs: Sequence) -> Iterator[List[np.ndarray]]:
        """Yield one output list per generated token: [ids [B] int32,
        piece bytes uint8] — flexible tensors, the reference's streaming
        contract.  Batched prompts ([B, T], B>1 — e.g. stacked by a
        ``tensor_query_serversrc max-batch=N``) yield [ids [B]] only: a
        per-row variable-length piece tensor is not batch-leading, so
        byte decoding is the consumer's job (ids are the contract; the
        query serversink row-splits ids back to each client)."""
        prompt = self._to_tokens(inputs[0])
        for ids in self._gen_tokens(prompt):
            metrics.count("llm.tokens", ids.shape[0])
            if ids.shape[0] != 1:
                yield [ids]
                continue
            piece = np.frombuffer(
                self.tokenizer.decode_piece(int(ids[0])), np.uint8)
            yield [ids, piece.copy()]

    def invoke(self, inputs: Sequence) -> List[np.ndarray]:
        """Non-streaming: all generated ids as one [B, N] tensor + the
        decoded bytes (batch-1 only; batched yields carry ids alone)."""
        chunks = [outs[0] for outs in self.invoke_stream(inputs)]
        ids = np.stack(chunks, axis=1)
        text = b"".join(self.tokenizer.decode_piece(int(t)) for t in ids[0])
        return [ids, np.frombuffer(text, np.uint8).copy()]


class _ContinuousLoop:
    """Standing decode loop for ``custom=serve:continuous`` over a
    block-paged KV cache.

    **The pool.**  One thread owns a fixed block pool
    ``[L, n_blocks, block_size, H_kv, hd]`` (models/llama.py
    ``init_paged_cache``) and schedules; its block manager ``self.kv``
    (filters/kv_blocks.py) owns the host side — a free list of block ids,
    reference counts, the prefix index and a per-slot block table
    ``[slots, max_blocks]`` whose entries map a stream's
    logical block j to a pool block (``n_blocks`` = unallocated
    sentinel).  Host state in the manager, device state in the loop: a
    copy-on-write fork is chosen by one and copied by the other.  The
    paged decode step (``forward_paged`` →
    ops/attention.py ``paged_attention``) streams ONLY each stream's live
    blocks (several a DMA wave, scores and P x V on the MXU), so per-step
    HBM traffic scales with the *sum of live sequence
    lengths* instead of ``slots × max_seq`` — a short stream stops paying
    cache bandwidth for the longest one, which is what lets full-
    occupancy throughput keep scaling past 8 streams.

    **Admission = reservation.**  A prompt is admitted when a slot AND
    ``ceil((T + max_new) / block_size)`` free blocks exist — the blocks a
    stream could ever write are reserved up front, so a LIVE stream can
    never stall mid-decode on an empty free list (no allocation
    deadlock; an undersized ``kv_blocks`` pool defers *admission*
    instead).  Reservation holds capacity, not bandwidth: the attention
    kernel still reads only ``ceil(len/block_size)`` blocks per row.
    Tables change only at admit/retire, on the host.

    **Chunked prefill.**  An admitted prompt pads to a multiple of
    ``prefill_chunk`` (waste < one chunk — vs the old power-of-two
    bucketing's up-to-2x; counted in ``llm.serve.prefill_pad_waste``)
    and prefills CHUNK BY CHUNK straight into its reserved blocks,
    interleaved between decode chunks under ``prefill_budget`` tokens
    per iteration — a long prompt no longer parks the whole loop behind
    one monolithic batch-1 prefill + cache-copy, which is what a late
    joiner's first-token latency was made of.

    **Prefix sharing (copy-on-write).**  With ``prefix_cache`` on
    (default), every full prompt block's token CHAIN hash indexes its
    pool block after prefill.  A new prompt walks the index: matched
    leading blocks map into its table with a reference count bump
    instead of a reservation — the shared system prompt / few-shot
    preamble that a million streams repeat is prefilled ONCE, and a
    cache-hit prompt's admission cost collapses to ~the non-shared
    suffix.  Blocks free only at refcount 0; cached blocks at refcount
    0 REST IN THE FREE LIST (content + index intact), so the cache
    never costs admission capacity and eviction is simply allocation.
    A matched block the suffix prefill would partially rewrite is
    copy-on-write FORKED first (``llm.serve.cow_forks``).  All host
    values — no compiled signature changes.

    **Speculative decoding.**  With ``draft:<preset>`` a small draft
    model proposes ``spec_k`` tokens per round (one scan; its paged
    pool shares this allocator's tables block-for-block) and the
    target verifies them in ONE fixed-shape ``[slots, spec_k+1]``-wide
    paged step — a k-wide prefill chunk that ALSO accepts and commits
    in-program: greedy loops take the longest proposal prefix matching
    the target's own argmax plus the target's bonus token (bit-
    identical to plain greedy decode at every accept rate); sampled
    loops run speculative rejection sampling (distribution-equivalent
    to the non-spec sampler).  1..k+1 tokens per TARGET dispatch; the
    host reads back only the accept count + emitted rows and the
    census grows to exactly 5 programs (serving_plan).

    **Fixed decode signature.**  Every program — the per-chunk paged
    decode ``[slots]``-row scan (or the propose/verify pair), the
    ``[1, prefill_chunk]`` prefill steps — takes (pool, tables,
    positions) with shapes static in every admission-state dimension;
    stream join/leave/complete, cache hits, CoW forks, and accept/
    reject ratios change VALUES only.  Warm once, recompile never
    (pinned by the compile-counter tests in tests/test_llm_continuous
    .py and tests/test_spec_decode.py and priced by the deep lint's
    resource report).  Idle slots decode garbage parked at position
    ``max_blocks * block_size`` — their table lookups resolve to the
    sentinel, writes drop, context length is 0, and the paged kernel
    issues ZERO block DMAs for them: an idle slot costs FLOPs, not HBM
    bandwidth.
    """

    def __init__(self, fw: LLMFramework):
        import queue as _q
        import threading

        import jax
        import jax.numpy as jnp
        from jax import lax

        self.fw = fw
        cfg, temperature = fw.cfg, fw.temperature
        bs = fw.block_size
        # Pool/table sizing shared with the deep lint (serving_plan's
        # docstring carries the rationale): table spans the largest
        # chunk-padded prompt, pool defaults to the worst case.
        plan = serving_plan(cfg, slots=fw.slots, block_size=bs,
                            kv_blocks=fw.kv_blocks,
                            prefill_chunk=fw.prefill_chunk, dtype=fw.dtype,
                            draft_cfg=fw.draft_cfg, spec_k=fw.spec_k,
                            temperature=temperature)
        self.max_blocks = plan["max_blocks"]
        self.n_blocks = plan["n_blocks"]
        #: window layers: blocks of a slot's ring, and of the whole
        #: window pool (0 = the model has no window layer, one pool)
        self.win_ring = plan["win_ring"]
        self.win_blocks = plan["win_blocks"]
        #: whether the decode chunk returns the expert layers' routing
        #: counts, as extra rows of its token matrix
        self._moe = cfg.experts is not None
        #: the host side of the paged cache (filters/kv_blocks.py): free
        #: list, refcounts, block and ring tables, prefix chain index.
        #: The scheduler below calls it; the pools stay device state of
        #: the serve thread.
        self.kv = BlockManager(
            slots=fw.slots, block_size=bs, prefill_chunk=fw.prefill_chunk,
            n_blocks=self.n_blocks, max_blocks=self.max_blocks,
            win_ring=self.win_ring, win_blocks=self.win_blocks,
            prefix_cache=fw.prefix_cache, count=metrics.count,
            conv_state_bytes=plan["conv_state_bytes"])
        self.sentinel = self.kv.sentinel  # unallocated table entry
        self.park = self.kv.park  # idle-slot position
        #: per-slot host bookkeeping the serve thread mutates in place
        #: (it binds them to locals); born here so that pool_stats(),
        #: stream_table() and the crash terminator read them whenever
        #: they are called.  Positions: parked = idle.
        self._pos = np.full((fw.slots,), self.park, np.int32)
        self._live_slots: list = [None] * fw.slots  # (meta, emit)
        #: per-slot stream id / tenant / original prompt tokens — the
        #: elastic surface (cancel lookup, quota accounting, drain
        #: snapshots); set at admission, cleared by retire()
        self._slot_sid: list = [None] * fw.slots
        self._slot_tenant: list = [None] * fw.slots
        self._slot_prompt: list = [None] * fw.slots
        #: per-slot serving timeline (docs/OBSERVABILITY.md "Distributed
        #: tracing"): enqueue/admit/first-token/last-emit stamps
        #: (monotonic seconds) feeding the TTFT / ITL / phase-split
        #: histograms.  Values are MILLISECONDS (the ``_ms`` series are
        #: reservoir-quantile sources; the seconds-scaled fixed bucket
        #: ladder saturates for them).  None for adopted streams — their
        #: enqueue happened in another process, so TTFT is unknowable.
        self._slot_time: list = [None] * fw.slots
        self._pending: "_q.Queue" = _q.Queue()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        # Guards the idle decision: without it, submit() could clear
        # _idle and THEN enqueue while the serve loop, between those two
        # steps, observes an empty queue and sets _idle — drain() would
        # return with a live request pending and EOS would cut it off.
        self._idle_lock = threading.Lock()
        self._error: Optional[BaseException] = None
        #: admission-order queue (drained from _pending; entries are
        #: ``(prompt, meta, emit, t_enqueued)``) + per-slot prefill-in-
        #: progress states; BOTH crash-visible: a request in either is
        #: in neither _pending nor a live slot, and a loop failure must
        #: abort it instead of stranding its client
        self._waiting: list = []
        self._admitting: list = []
        #: the decode chunk that is settled (slots, blocks and counters
        #: say it happened) while some of its tokens have not left yet:
        #: set from settling to the end of its delivery, which may be put
        #: off until the next chunk is dispatched (step 5).  Crash-visible
        #: like the two above: a stream that retired in it is in no slot.
        self._undelivered: Optional[_Settled] = None
        # -- elastic serving state (docs/SERVING.md "Elastic serving") --
        #: control commands (drain/adopt) from app threads, processed at
        #: chunk boundaries; each is a dict with an Event the caller
        #: waits on.  deque append/popleft are GIL-atomic.
        import collections as _collections

        self._ctl: "_collections.deque" = _collections.deque()
        #: stream_id -> (reason, reap_deadline): marked dead by
        #: utils/elastic.cancel_stream (the serversink's dead-connection
        #: backchannel); the slot + blocks are reaped at the first chunk
        #: boundary past the deadline (stream_idle_timeout grace, so a
        #: drain/handover can still pick the stream up)
        self._cancelled: Dict[int, tuple] = {}
        #: per-tenant cap on total reserved KV blocks (None = uncapped);
        #: a host-value quota the autoscaler raises/lowers at runtime —
        #: admission SKIPS (not blocks) over-quota tenants so one capped
        #: tenant never head-of-line-blocks the rest
        self._tenant_quota: Dict[str, Optional[int]] = {}
        #: stream ids this loop registered with utils/elastic (cleaned
        #: up on retire/abort/shutdown so the process-wide registry
        #: never leaks entries)
        self._owned_sids: set = set()
        #: per-swap version counter (nns-learn train-while-serve): bumps
        #: once per executed hot-swap, published as llm.serve.param_version
        self.param_version = 0

        # -- per-slot PRNG (docs/SERVING.md §4d) ------------------------
        # Slot keys ride slot state the way tok_prev does: every draw
        # folds (absolute token position, draw tag) into the slot's own
        # key, so a stream's sampled tokens are a pure function of
        # (framework seed, admission number, position) — independent of
        # batch composition, accept history, and wall-clock step.  Churn
        # changes key VALUES only; the compiled programs never see a new
        # signature, and drain/adopt carries the key in the snapshot.
        self._sampled = temperature > 0.0
        slot_keys = _fold_slot_keys  # module level so tests drive it raw

        def decode_chunk(params, tok, pool, tables, pos, keys, length):
            """``length`` paged decode steps as ONE program (lax.scan):
            every slot advances at its own depth through its own blocks.
            ``pos`` arrives fresh from host bookkeeping each call, so a
            parked row can never creep toward int32 wraparound.  ONE
            signature for greedy and sampled loops: at temperature 0
            the per-slot key folds are dead code XLA drops."""
            def step(carry, _):
                tok, pool, p = carry
                logits, pool, stats = llama.forward_paged(
                    params, tok[:, None], pool, tables, p, cfg,
                    compute_dtype=fw.dtype, with_stats=True)
                with jax.named_scope("sampler"):
                    kstep = slot_keys(keys, p + 1, TAG_SAMPLE)
                    nxt = llama.sample_token_per_slot(
                        logits[:, -1], kstep, temperature, fw.top_k,
                        fw.top_p)
                return (nxt, pool, p + 1), (nxt, stats)

            (tok, pool, _), (toks, stats) = lax.scan(
                step, (tok, pool, pos), None, length=length)
            toks = jnp.moveaxis(toks, 0, 1)  # [B, length]
            if stats is not None:
                # the expert layers' counts of each step ride home as
                # five extra rows of the token matrix: the fetch that
                # brings the chunk's tokens brings them, no sync of
                # their own
                toks = jnp.concatenate([toks, stats.T], axis=0)
            return toks, tok, pool

        self._decode = jax.jit(
            decode_chunk, static_argnames=("length",), donate_argnums=(2,))

        def prefill_step(params, toks, pool, table, ctl, tok, keys,
                         base_key):
            """One [1, prefill_chunk] prefill chunk written directly into
            the slot's blocks, and on a prompt's final chunk its first
            token sampled and committed (``_commit_first_token``): the
            serve thread issues no program of its own between this one and
            the decode chunk.  ``ctl`` is int32[4], one transfer for the
            chunk's four host values: its first position; ``logit_off``,
            the offset of its last REAL token (on the final chunk the
            prompt's last, before it the chunk's last column); the
            stream's admission number; and its slot on the final chunk,
            ``slots`` (out of range: nothing is committed) before it.
            ONE signature for every chunk, greedy and sampled."""
            pos0, logit_off, adm_no, slot = ctl[:1], ctl[1], ctl[2], ctl[3]
            logits, pool = llama.forward_paged(
                params, toks, pool, table, pos0, cfg,
                compute_dtype=fw.dtype, logit_off=logit_off,
                # the chunk's real tokens end at ``logit_off``: where
                # state that no position addresses is taken
                n_valid=logit_off + 1 if cfg.n_conv_layers else None)
            # the first token's position is the prompt's length
            first, tok, keys = _commit_first_token(
                logits[:, 0], tok, keys, base_key, adm_no, slot,
                pos0[0] + logit_off + 1, temperature, fw.top_k, fw.top_p)
            return first, tok, keys, pool

        # Under tensor parallelism the carried tok/keys go in replicated
        # (committed up front, _run_inner) and must come back spelt the
        # same way, or the second call mints a second signature (the
        # compiler returns a rank-2 replica as P(None, None), not P()).
        rep = None
        if fw.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(fw.mesh, PartitionSpec())
        self._prefill = jax.jit(prefill_step, donate_argnums=(2, 5, 6),
                                out_shardings=(rep, rep, rep, None))
        # slot-vector updates by value where no program commits them: an
        # adopted stream's token, the speculative loop's tok_prev and
        # position twins (slot index and value traced: ONE program)
        self._set_tok = jax.jit(lambda a, i, v: a.at[i].set(v),
                                donate_argnums=(0,))
        # -- speculative decoding (custom=draft:<preset>,spec_k:K) ------
        # The draft model shares the allocator, block tables, sentinel,
        # and n_blocks with the target: block id j holds target K/V in
        # the target pool and draft K/V in the draft pool, so a prefix-
        # cache hit shares BOTH models' cache rows and a CoW fork copies
        # both.  Three extra programs, all static-shaped — accept/reject
        # ratios are host VALUES: the census stays closed at 5.
        self._spec = fw.draft_bundle is not None
        if self._spec:
            dcfg = fw.draft_cfg
            k_spec = fw.spec_k
            park_bound = self.max_blocks * bs  # static python int

            def draft_prefill_step(dparams, toks, dpool, table, pos0):
                """The draft's twin of the target prefill chunk: writes
                the chunk's draft K/V into the SAME reserved blocks of
                the draft pool (logits discarded — ``logit_off=0``
                keeps the draft lm_head at one row)."""
                _, dpool = llama.forward_paged(
                    dparams, toks, dpool, table, pos0, dcfg,
                    compute_dtype=fw.dtype, logit_off=0)
                return dpool

            self._draft_prefill = jax.jit(draft_prefill_step,
                                          donate_argnums=(2,))

            def propose(dparams, tok_prev, tok, dpool, tables, pos, keys):
                """One speculative round's draft side: re-feed the
                PREVIOUS token at ``pos - 1`` (the refresh step — after
                a fully-accepted round the draft pool has a hole at the
                last committed position; recomputing it from identical
                context is bit-exact and keeps the pool hole-free), then
                ``k`` draft steps from ``tok``.  Greedy loops take the
                draft's argmax; sampled loops draw each proposal from
                the FILTERED draft distribution with the slot key folded
                at the proposal's absolute position, and return those
                distributions [B, k, vocab] so verify can run rejection
                sampling.  Parked rows stay parked: the refresh position
                is clamped to the park value so their table lookups
                still resolve to the sentinel and the paged kernel
                issues zero DMAs."""
                rpos = jnp.where(pos >= park_bound, pos, pos - 1)
                _, dpool = llama.forward_paged(
                    dparams, tok_prev[:, None], dpool, tables, rpos,
                    dcfg, compute_dtype=fw.dtype)

                def step(carry, _):
                    t, dpool, p = carry
                    logits, dpool = llama.forward_paged(
                        dparams, t[:, None], dpool, tables, p, dcfg,
                        compute_dtype=fw.dtype)
                    if temperature > 0.0:
                        filt = llama.filter_logits(
                            logits[:, -1], temperature, fw.top_k, fw.top_p)
                        probs = jax.nn.softmax(filt, axis=-1)
                        kstep = slot_keys(keys, p + 1, TAG_DRAFT)
                        nxt = jax.vmap(jax.random.categorical)(
                            kstep, filt).astype(jnp.int32)
                    else:
                        probs = jnp.zeros(
                            (logits.shape[0], 1), jnp.float32)  # unused
                        nxt = jnp.argmax(logits[:, -1],
                                         axis=-1).astype(jnp.int32)
                    return (nxt, dpool, p + 1), (nxt, probs)

                (_, dpool, _), (props, dprobs) = lax.scan(
                    step, (tok, dpool, pos), None, length=k_spec)
                return (jnp.moveaxis(props, 0, 1),
                        jnp.moveaxis(dprobs, 0, 1), dpool)

            self._propose = jax.jit(propose, donate_argnums=(3,))

            def verify(params, tok, tok_prev, props, dprobs, pool,
                       tables, pos, keys):
                """One speculative round's target side, FUSED: ONE
                fixed-shape ``[B, k+1]``-wide paged step over (last
                committed token + the k proposals), then accept/commit
                IN-PROGRAM — greedy loops take the longest proposal
                prefix matching the target's own argmax; sampled loops
                run standard speculative rejection sampling (accept
                x_i with min(1, p/q); resample rejections from the
                normalized residual max(p-q, 0)), which emits tokens
                distributed EXACTLY as the non-spec sampler.  The new
                tok/tok_prev/positions are computed here as device
                values, so the host reads back only the per-slot accept
                count + the emitted-token rows — no per-round
                accept-mask round-trip, no tok re-upload.  Parked rows
                pass through untouched."""
                toks = jnp.concatenate([tok[:, None], props], axis=1)
                logits, pool = llama.forward_paged(
                    params, toks, pool, tables, pos, cfg,
                    compute_dtype=fw.dtype)
                live = pos < park_bound
                if temperature > 0.0:
                    filt = llama.filter_logits(
                        logits, temperature, fw.top_k, fw.top_p)
                    pt = jax.nn.softmax(filt, axis=-1)  # [B, k+1, V]
                    em, acc = spec_rejection_commit(
                        pt, dprobs, props, keys, pos, live)
                else:
                    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    ok = (props == g[:, :k_spec]).astype(jnp.int32)
                    acc = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)
                    acc = jnp.where(live, acc, 0)
                    # g[j] == props[j] for j < acc, and g[acc] is the
                    # bonus/correction token: g IS the emitted row
                    em = g
                # the last emitted token: em[acc] (the final/residual
                # draw in sampled loops, the target argmax in greedy)
                new_tok = jnp.take_along_axis(
                    em, acc[:, None], axis=1)[:, 0]
                prev_cand = jnp.take_along_axis(
                    em, jnp.maximum(acc - 1, 0)[:, None], axis=1)[:, 0]
                new_prev = jnp.where(acc > 0, prev_cand, tok)
                tok2 = jnp.where(live, new_tok, tok)
                prev2 = jnp.where(live, new_prev, tok_prev)
                pos2 = jnp.where(live, pos + acc + 1, pos)
                return em, acc, tok2, prev2, pos2, pool

            self._verify = jax.jit(verify, donate_argnums=(5,))
        xr = getattr(fw, "_xray", None)
        if xr is not None:
            # nns-xray: the standing loop's predicted census IS
            # serving_plan()'s fixed program set (plan["programs"] == 3:
            # decode chunk, prefill step, slot-token setter — the same
            # arithmetic the deep lint prices serve:continuous with), so
            # each program expects exactly ONE compile; anything more —
            # e.g. a numpy-scalar _set_tok argument minting a 4th
            # signature — fires census-drift with the signature diff.
            # Keyed by the owning ELEMENT's stage name (the attach_xray
            # handoff) + ".serve", so two serve loops in one process
            # never collide on one budget.
            stage = f"{getattr(fw, '_xray_stage', None) or 'llm'}.serve"
            rec = lambda: getattr(fw, "_trace_rec", None)  # noqa: E731
            # TP: the paged decode executes across the mesh's model
            # axis — MFU/roofline divide by the participating chips
            devs = 1
            if fw.mesh is not None:
                from ..parallel.mesh import mesh_axis_size

                devs = max(1, mesh_axis_size(fw.mesh, "model"))
            xr.expect(stage, "prefill", budget=1,
                      note="serving_plan fixed prefill signature")
            xr.expect(stage, "set_tok", budget=1,
                      note="serving_plan slot-token setter")
            self._prefill = xr.track(self._prefill, stage, "prefill",
                                     rec=rec, devices=devs)
            self._set_tok = xr.track(self._set_tok, stage, "set_tok",
                                     rec=rec)
            if self._spec:
                # speculation swaps the decode chunk for the draft
                # propose + target verify pair and adds the draft's
                # prefill twin — serving_plan()["programs"] == 5, each
                # expecting exactly one compile
                xr.expect(stage, "draft_prefill", budget=1,
                          note="serving_plan draft prefill twin")
                xr.expect(stage, "propose", budget=1,
                          note="serving_plan draft propose scan")
                xr.expect(stage, "verify", budget=1,
                          note="serving_plan k+1-wide verify step")
                self._draft_prefill = xr.track(
                    self._draft_prefill, stage, "draft_prefill", rec=rec,
                    devices=devs)
                self._propose = xr.track(self._propose, stage, "propose",
                                         rec=rec, devices=devs)
                self._verify = xr.track(self._verify, stage, "verify",
                                        rec=rec, devices=devs)
            else:
                xr.expect(stage, "decode", budget=1,
                          note="serving_plan fixed decode signature")
                self._decode = xr.track(self._decode, stage, "decode",
                                        rec=rec, devices=devs)
        self._thread = threading.Thread(
            target=self._run, name="llm-serve", daemon=True)
        self._thread.start()

    # -- producer side -----------------------------------------------------
    def submit(self, prompt, meta: Dict, emit) -> int:
        # Every stream gets a process-unique id minted HERE (server-
        # authoritative: a client-supplied meta value is overwritten) and
        # registered with utils/elastic so downstream failure detectors
        # (the query serversink's dead-connection path) can cancel it by
        # value.  The id rides every emitted token's meta.
        import functools as _ft

        meta = dict(meta)
        sid = elastic.next_stream_id()
        meta[elastic.META_STREAM_ID] = sid
        # The error check lives INSIDE the lock: the crash handler drains
        # _pending and sets _idle under the same lock, so a submit cannot
        # slip a request into a dead loop's queue between its own error
        # check and its put (that request would never be dequeued or
        # aborted — a hung client).
        with self._idle_lock:
            if self._error is not None:
                raise FrameworkError(
                    f"continuous serve loop died: {self._error!r}")
            self._idle.clear()
            self._owned_sids.add(sid)
            elastic.register_stream(
                sid, _ft.partial(self._mark_cancel, sid))
            self._pending.put((prompt, meta, emit, time.monotonic()))
        self._wake.set()
        return sid

    def drain(self, timeout: float) -> bool:
        return self._idle.wait(timeout)

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=30)
        gc.unfreeze()  # _run_inner froze what was alive at warm-up
        # control callers blocked on a drain/adopt that raced the stop
        # get a prompt named error instead of riding out their timeout
        while self._ctl:
            cmd = self._ctl.popleft()
            cmd["error"] = "serve loop stopped"
            cmd["ev"].set()
        # the process-wide stream registry must not keep pointing at a
        # dead loop (stale cancel callbacks); owned ids are whatever
        # retire/abort did not already clean up
        for sid in list(self._owned_sids):
            elastic.unregister_stream(sid)
        self._owned_sids.clear()

    # -- elastic control surface -------------------------------------------
    def _mark_cancel(self, sid: int, reason: str = "cancelled",
                     force: bool = False) -> None:
        """The utils/elastic backchannel: mark one stream dead.  Reaped
        at the first chunk boundary past the ``stream_idle_timeout``
        grace (``force=True`` skips the grace).  Idempotent: an earlier
        (sooner) deadline is never extended."""
        grace = 0.0 if force else self.fw.stream_idle_timeout
        deadline = time.monotonic() + grace
        prev = self._cancelled.get(sid)
        if prev is None or deadline < prev[1]:
            self._cancelled[sid] = (reason, deadline)
            metrics.count("llm.serve.cancelled")
        self._wake.set()

    def set_tenant_quota(self, tenant: str,
                         max_blocks: Optional[int]) -> None:
        """Cap (or uncap, with None) a tenant's total reserved KV
        blocks.  A host-value move: admission enforces it on the next
        iteration, nothing recompiles — this is the autoscaler's
        ``kv_quota`` action."""
        if max_blocks is None:
            self._tenant_quota.pop(tenant, None)
        else:
            self._tenant_quota[tenant] = max(0, int(max_blocks))
        self._wake.set()

    def pool_stats(self) -> Dict[str, int]:
        """Allocator accounting snapshot (soak/chaos assertions): free
        and total block counts plus live stream count.  Reads host-side
        ints the serve thread mutates — values are a consistent-enough
        snapshot for accounting at quiesce points (post-drain)."""
        return {
            **self.kv.stats(),
            "live_streams": sum(
                1 for s in self._live_slots if s is not None),
            "win_blocks_live": self.kv.win_blocks_live(self._pos),
        }

    def stream_table(self) -> Dict[int, Dict]:
        """``stream_id -> {"state", "tenant", "slot", "blocks"}`` for
        every stream this loop owns (queued, admitting, or live)."""
        out: Dict[int, Dict] = {}
        for ent in list(self._waiting):
            sid = ent[1].get(elastic.META_STREAM_ID)
            if sid is not None:
                out[sid] = {"state": "queued", "slot": None, "blocks": 0,
                            "tenant": ent[1].get(_META_TENANT)}
        for st in list(self._admitting):
            sid = st["meta"].get(elastic.META_STREAM_ID)
            if sid is not None:
                out[sid] = {"state": "admitting", "slot": st["slot"],
                            "blocks": len(
                                self.kv.slot_blocks[st["slot"]]),
                            "tenant": st["meta"].get(_META_TENANT)}
        for s, slot in enumerate(self._live_slots):
            sid = self._slot_sid[s]
            if slot is None or sid is None:
                continue
            out[sid] = {"state": "live", "slot": s,
                        "blocks": len(self.kv.slot_blocks[s]),
                        "tenant": slot[0].get(_META_TENANT)}
        return out

    def _ctl_call(self, cmd: Dict, timeout: float):
        """Enqueue one control command and wait for the serve thread to
        execute it at a chunk boundary."""
        cmd["ev"] = threading.Event()
        cmd["deadline"] = time.monotonic() + timeout
        with self._idle_lock:
            if self._error is not None:
                raise FrameworkError(
                    f"continuous serve loop died: {self._error!r}")
            self._idle.clear()
            self._ctl.append(cmd)
        self._wake.set()
        if not cmd["ev"].wait(timeout + 1.0):
            raise FrameworkError(
                f"serve-loop {cmd['kind']} command timed out "
                f"after {timeout}s")
        if cmd.get("error"):
            raise FrameworkError(cmd["error"])
        return cmd.get("result")

    def drain_stream(self, sid: int, timeout: float = 30.0) -> Dict:
        return self._ctl_call({"kind": "drain", "sid": int(sid)}, timeout)

    def adopt_stream(self, snapshot: Dict, emit,
                     timeout: float = 30.0) -> int:
        return self._ctl_call(
            {"kind": "adopt", "snapshot": snapshot, "emit": emit},
            timeout)

    def swap_params(self, tree, timeout: float = 30.0) -> int:
        """Enqueue a param hot-swap, executed at the next chunk boundary
        (nns-learn train-while-serve); returns the new param version."""
        return self._ctl_call({"kind": "swap", "tree": tree}, timeout)

    # -- serve thread ------------------------------------------------------
    def _emit_token(self, emit, meta: Dict, token_id: int, index: int,
                    last: bool, extra: Optional[Dict] = None) -> None:
        out_meta = dict(meta)
        if extra:
            out_meta.update(extra)
        out_meta[META_STREAM_INDEX] = index
        # Serving telemetry: when THIS token left the decode loop
        # (monotonic seconds).  Lets consumers measure generation-window
        # throughput precisely instead of inferring it from pull times,
        # which lag emission by queue dwell.
        out_meta["emit_t"] = time.monotonic()
        if last:
            out_meta[META_STREAM_LAST] = True
        piece = self.fw.tokenizer.decode_piece(token_id)
        emit([np.asarray([token_id], np.int32),
              np.frombuffer(piece, np.uint8).copy()], out_meta)
        metrics.count("llm.tokens")

    @staticmethod
    def _mark_emit(tt: Optional[Dict], tenant) -> None:
        """One emitted token's wall stamp on its stream's timeline record
        ``tt``: first emission observes TTFT (enqueue → first token, the
        client-visible number), later ones observe the inter-token gap.
        Chunked decode materializes a whole chunk at once, so intra-chunk
        ITL samples are ~0 and the chunk boundary carries the gap — that
        IS the emission timeline a streaming client sees."""
        if tt is None:
            return  # adopted stream (or warmup): no local enqueue
        now = time.monotonic()
        if tt["first"] is None:
            tt["first"] = tt["last"] = now
            metrics.observe_latency(
                "llm.serve.ttft_ms", (now - tt["enq"]) * 1e3, tenant=tenant)
        else:
            metrics.observe_latency(
                "llm.serve.itl_ms", (now - tt["last"]) * 1e3, tenant=tenant)
            tt["last"] = now

    def _close_stream(self, sid, tenant, tt: Optional[Dict]) -> None:
        """The delivering half of a retirement (the settling half is
        ``vacate`` in ``_run_inner``): the stream's last token has left,
        so it leaves the registry and its phase splits are observed from
        the stamps delivery took."""
        if sid is not None:
            elastic.unregister_stream(sid)
            self._owned_sids.discard(sid)
            self._cancelled.pop(sid, None)
        if tt is not None and tt["first"] is not None:
            # time queued, time from admission to first token (prefill +
            # first dispatch), time spent decoding
            metrics.observe_latency(
                "llm.serve.queue_ms", (tt["admit"] - tt["enq"]) * 1e3,
                tenant=tenant)
            metrics.observe_latency(
                "llm.serve.prefill_ms", (tt["first"] - tt["admit"]) * 1e3,
                tenant=tenant)
            metrics.observe_latency(
                "llm.serve.decode_ms", (tt["last"] - tt["first"]) * 1e3,
                tenant=tenant)

    def _deliver(self, rec, ahead: bool, until: int = 0) -> None:
        """Hand the settled chunk's tokens downstream and close the books
        of the streams that end in it.  ``ahead``: the next decode chunk
        was dispatched before this began, so the chip works underneath.

        ``until`` > 0 is the delivery made with NOTHING queued on the
        chip, because streams ended in the chunk and no request was
        waiting.  The streams that end leave first, each whole — their
        callers' next requests are what the idle chip is waiting for —
        then every other stream's first token of the chunk, so that no
        stream's gap at the boundary grows by the admission to come; from
        there on the delivery stops as soon as ``until`` requests are
        queued (as many as streams ended: the freed callers have all
        answered) and leaves the rest for after the next dispatch.
        Without ``until``: token j of every stream before token j+1 of
        any, to the end, picking up where an earlier delivery stopped
        (``_Tail.done``) — also what the crash terminator relies on."""
        ch = self._undelivered
        if ch is None:
            return
        sp = None
        if rec is not None:
            sp = tracing.span(rec, "serve.emit", _SERVE_STAGE, None,
                              iter=ch.iter).begin()
        n_tok = n_end = 0
        queued = self._pending.qsize
        for r, j, may_wait in ch.order(ending_first=until > 0):
            if may_wait and queued() >= until:
                break
            last = r.last and j + 1 == len(r.toks)
            self._emit_token(r.emit, r.meta, r.toks[j], r.start + j, last)
            r.done = j + 1
            n_tok += 1
            self._mark_emit(r.time, r.tenant)
            if last:
                n_end += 1
                self._close_stream(r.sid, r.tenant, r.time)
        else:
            self._undelivered = None
        if ahead:
            metrics.count("llm.serve.deliver_ahead")
        if sp is not None:
            sp.end(tokens=n_tok, retired=n_end, ahead=int(ahead))

    def _run(self) -> None:
        try:
            self._run_inner()
        except BaseException as e:  # noqa: BLE001 - daemon thread: report
            log.exception("continuous serve loop died")

            def abort(meta, emit, idx=0):
                try:
                    self._emit_token(
                        emit, {**meta, META_STREAM_ABORTED: True}, 0, idx,
                        True)
                except Exception:  # noqa: BLE001
                    pass
                sid = meta.get(elastic.META_STREAM_ID)
                if sid is not None:
                    elastic.unregister_stream(sid)
                    self._owned_sids.discard(sid)

            # Terminate every live, mid-prefill, waiting, and queued
            # stream so no client hangs to its timeout waiting on a dead
            # loop.  The queue drain + idle-set run under _idle_lock,
            # pairing with submit(): no request can enter the queue
            # after the drain.
            import queue as _q

            # A settled chunk first: the streams in it get the tokens they
            # are still owed, in order and before any terminator; one that
            # retired in it is in no slot below, so if its tail cannot
            # leave either it gets its abort here.
            ch = self._undelivered
            if ch is not None:
                try:
                    self._deliver(None, False)
                except Exception:  # noqa: BLE001 - downstream may be gone
                    self._undelivered = None
                for r in ch.rows:
                    if r.last and r.done < len(r.toks):
                        abort(r.meta, r.emit, r.start + r.done)
            for slot in list(self._live_slots):
                if slot is not None:
                    abort(slot[0], slot[1], 1 << 30)
            for st in list(self._admitting):
                abort(st["meta"], st["emit"])
            for ent in list(self._waiting):
                abort(ent[1], ent[2])
            with self._idle_lock:
                self._error = e
                while True:
                    try:
                        ent = self._pending.get_nowait()
                    except _q.Empty:
                        break
                    abort(ent[1], ent[2])
                # control callers (drain/adopt) blocked on their events
                # must see the crash, not their timeout
                while self._ctl:
                    cmd = self._ctl.popleft()
                    cmd["error"] = f"continuous serve loop died: {e!r}"
                    cmd["ev"].set()
                self._idle.set()

    def _run_inner(self) -> None:
        import dataclasses as _dc
        import functools as _ft
        import queue as _q

        import jax
        import jax.numpy as jnp

        fw, cfg = self.fw, self.fw.cfg
        B, bs, C = fw.slots, fw.block_size, fw.prefill_chunk
        params = fw.bundle.params
        pool = llama.init_paged_cache(cfg, self.n_blocks, bs,
                                      dtype=fw.dtype,
                                      win_blocks=self.win_blocks,
                                      slots=B)
        d_params = draft_pool = None
        if self._spec:
            d_params = fw.draft_bundle.params
            # the draft pool mirrors the target's (n_blocks, block_size)
            # at the draft's own (L, H_kv, hd): ONE allocator, ONE table
            # set steers both — block id j holds both models' K/V for
            # the same token positions
            draft_pool = llama.init_paged_cache(
                fw.draft_cfg, self.n_blocks, bs, dtype=fw.dtype)
        if fw.mesh is not None:
            # Tensor parallelism: the block pool shards over `model` on
            # the K/V head dim exactly like the dense cache, so a
            # model_parallel=M loop holds pool_bytes/M per chip and the
            # pool composes with the same allocator/tables (host-side
            # ints, replicated).  Geometry was validated at _setup
            # (n_kv_heads % M == 0, tp_divisibility_problems).
            from ..parallel.sharding import shard_params as _sp

            pool = _sp(fw.mesh, pool, llama.paged_cache_pspecs())
            if draft_pool is not None:
                draft_pool = _sp(fw.mesh, draft_pool,
                                 llama.paged_cache_pspecs())
        # published like the allocator bookkeeping below: tests and
        # post-mortems read the pool's actual placement off the loop
        self._pool_sharding = getattr(next(iter(pool.values())), "sharding",
                                      None)
        # the MEASURED pool footprint (global bytes; /M per chip under
        # TP; target + draft pools) — nns-xray's HBM ledger reconciles
        # this against the deep lint's serving_plan pool_bytes +
        # draft_pool_bytes estimate
        from .base import tree_param_bytes as _tree_bytes

        self._pool_nbytes = _tree_bytes(pool) + (
            _tree_bytes(draft_pool) if draft_pool is not None else 0)
        # Device carries tok/pool (+ per-slot PRNG keys, and positions
        # under speculation) between chunks (materializing them per
        # chunk costs a D2H fetch each).  EVERYTHING ELSE is
        # host bookkeeping: positions advance deterministically (+length
        # per chunk for live rows, parked otherwise) and block tables
        # change only at admit/retire, so both live as numpy and ride to
        # the device as tiny async H2D args — never a fetch.  They are
        # mutated IN PLACE between dispatches, so every call below hands
        # the program a .copy(): dispatch is asynchronous and the client
        # may read (the CPU client aliases) the host buffer after the
        # call returns.
        tok = jnp.zeros((B,), jnp.int32)
        tok_prev = jnp.zeros((B,), jnp.int32) if self._spec else None
        # Per-slot PRNG state (docs/SERVING.md §4d): each slot's base
        # key is fold_in(PRNGKey(seed), admission number) — a pure
        # function of (seed, admission order), NOT of stream ids (those
        # are process-global and would differ between two same-seed
        # runs in one process, breaking bit-reproducibility).  The
        # prefill program derives it on a prompt's final chunk, writes
        # it into the device twin and sends it home with the first
        # token (step 4 mirrors it into keys_h for drain snapshots);
        # every per-token draw then folds (absolute position, tag)
        # inside the compiled programs.
        base_key = jax.random.PRNGKey(fw.seed)
        adm_no = 0
        keys_h = np.zeros((B, 2), np.uint32)
        keys_dev = jnp.asarray(keys_h)
        # the measured PRNG slot-state footprint the xray HBM ledger
        # reconciles against serving_plan's prng_state_bytes
        self._prng_nbytes = int(keys_h.nbytes) if self._sampled else 0
        # Speculative loops also carry positions as a device twin: the
        # fused verify commits pos += accepted+1 in-program, so the
        # host never re-uploads positions per round.  The host numpy
        # `pos` below stays authoritative for admission/drain
        # bookkeeping; park/admission/adopt events push its per-slot
        # values into pos_dev through the existing _set_tok signature.
        pos_dev = jnp.full((B,), self.park, jnp.int32) \
            if self._spec else None
        _rep = None
        if fw.mesh is not None:
            # Commit the carried device state to the mesh UP FRONT: the
            # first decode otherwise traces against single-device inputs
            # while every later call sees mesh-replicated outputs — one
            # avoidable extra signature that would break the fixed-
            # census pin TP must preserve (the compile-counter pin).
            from ..parallel.sharding import replicate as _rep

            tok = _rep(fw.mesh, tok)
            base_key = _rep(fw.mesh, base_key)
            keys_dev = _rep(fw.mesh, keys_dev)
            if tok_prev is not None:
                tok_prev = _rep(fw.mesh, tok_prev)
            if pos_dev is not None:
                pos_dev = _rep(fw.mesh, pos_dev)

        def push_keys() -> None:
            """Rebuild the device key vector from the host mirror — an
            adopt-event VALUE move (replicated under TP); admissions
            never touch it, their keys are written in-program."""
            nonlocal keys_dev
            keys_dev = jnp.asarray(keys_h.copy())
            if fw.mesh is not None:
                keys_dev = _rep(fw.mesh, keys_dev)

        def fresh_slot_key() -> np.ndarray:
            """The next admission number's key, fetched (a host wait on
            the device): for an adopted snapshot that carries none."""
            nonlocal adm_no
            k = np.asarray(jax.random.fold_in(base_key, adm_no), np.uint32)
            adm_no += 1
            return k

        kv = self.kv  # block manager: tables, free list, prefix chain
        pos = self._pos  # parked = idle
        #: host mirrors of the carried token state (the last committed
        #: token and the one before it) per slot — the speculative
        #: round's accept/commit writes them and rebuilds the device
        #: vectors by value; drain snapshots read tok_prev from here.
        tok_h = np.zeros((B,), np.int32)
        tok_prev_h = np.zeros((B,), np.int32)
        remaining = np.zeros((B,), np.int64)
        sidx = np.zeros((B,), np.int64)
        slots = self._live_slots  # (meta, emit) per live slot
        eos = getattr(fw.tokenizer, "eos", -1) if fw.stop_eos else -1

        def begin(kind: str, tid=None, /, **args):
            """Open one span of this loop on the ring and on the
            profiler's clock (``tracing.span``).  Callers test ``rec is
            not None`` first: with ``trace_mode=off`` nothing is built."""
            return tracing.span(rec, kind, _SERVE_STAGE, tid,
                                **args).begin()

        def decode_closed(kind: str, sp_wait, **args) -> None:
            """The chunk (or speculative round) materialized: its ring
            span opened at the dispatch in step 3, so it is recorded from
            stamps — ``dispatch_ns`` of it the host's time inside the
            jitted call; the blocking wait alone was its profiler
            annotation (held: never a ring span of its own)."""
            sp_wait.end(hold=True)
            rec.record(kind, _SERVE_STAGE, None, t_dec,
                       sp_wait.ts + sp_wait.dur - t_dec, iter=it,
                       occupancy=int(live.sum()), wait_ns=sp_wait.dur,
                       dispatch_ns=dispatch_ns, **args)

        def cow_copy(src: int, dst: int) -> None:
            """The device half of a copy-on-write fork the manager chose
            at admission: block ``src``'s rows (target AND draft pool)
            copied into the private block ``dst`` — an eager value move
            like adopt's scatter; none of the compiled programs is
            touched.

            Trade-off (shared with adopt): the eager ``.at[].set`` holds
            the old pool alive across the update, so XLA materializes a
            transient second pool buffer — at most one fork per
            admission, off the decode dispatch path.  A donated jitted
            fork would avoid the spike but mint a program the closed
            census (serving_plan/tracecheck/xray) would have to price;
            revisit if silicon pools sized to the HBM edge OOM here."""
            sp = begin("serve.cow_fork") if rec is not None else None
            src_i = np.asarray([src], np.int32)
            new_i = np.asarray([dst], np.int32)
            for pl in (pool, draft_pool):
                # every leaf the allocator's blocks live in: K and V, a
                # latent class's rows
                for leaf in llama.allocated_leaves(pl or {}):
                    pl[leaf] = pl[leaf].at[:, new_i].set(pl[leaf][:, src_i])
            if sp is not None:
                sp.end(src=int(src), dst=int(dst))

        def holder(s: int) -> tuple:
            """``(stream id, tenant, timeline record)`` of the stream in
            slot ``s``: what ``_close_stream`` needs of it."""
            return (self._slot_sid[s], self._slot_tenant[s],
                    self._slot_time[s])

        def vacate(s: int) -> None:
            """The settling half of a retirement: slot ``s`` and its
            blocks are free for the next admission."""
            nonlocal pos_dev
            kv.release(s)
            pos[s] = self.park
            if pos_dev is not None:
                # re-park the device twin too: the fused verify carries
                # positions on device, and a retired row must stop
                # advancing (same int32[B] _set_tok signature — no new
                # program)
                pos_dev = self._set_tok(
                    pos_dev, np.int32(s),
                    jnp.asarray(np.int32(self.park)))
            slots[s] = None
            remaining[s] = 0
            sidx[s] = 0
            self._slot_time[s] = None
            self._slot_sid[s] = None
            self._slot_tenant[s] = None
            self._slot_prompt[s] = None
            # here and not with the books: the slot may be seated again,
            # and gauged 1, before its old stream's tail has left
            metrics.gauge(f"llm.serve.slot{s}.occupied", 0.0)

        def retire(s: int) -> None:
            """Both halves at once, for a stream that is owed nothing."""
            who = holder(s)
            vacate(s)
            self._close_stream(*who)

        def settle(host: np.ndarray) -> _Settled:
            """The settling half of step 5, over the materialized chunk
            ``host[B, chunk]``: everything the host must know before it
            can dispatch again, as vector tests — for every row that
            decoded, how many of the chunk's tokens it emits (what it had
            left, cut at the first ``eos``) and whether that ends it; from
            that ``remaining``, ``sidx``, the last two tokens a row keeps,
            and for a row that ends the slot and its blocks.  What is left
            per TOKEN — the emission itself, its stamp, the registry and
            the phase histograms of a stream that ended — is the record
            returned, by value."""
            # remaining == 0 on a dispatched row: retired at its first
            # token in step 4, the chunk's row is garbage
            rows = np.flatnonzero(live & (remaining > 0))
            mine = host[rows]
            n = np.minimum(remaining[rows], mine.shape[1])
            ends = n == remaining[rows]
            if eos >= 0:
                hit = mine == eos
                cut = np.where(hit.any(axis=1), hit.argmax(axis=1) + 1,
                               mine.shape[1] + 1)
                ends |= cut <= n
                n = np.minimum(n, cut)
            at = np.arange(len(rows))
            tok_prev_h[rows] = np.where(
                n > 1, mine[at, np.maximum(n - 2, 0)], tok_h[rows])
            tok_h[rows] = mine[at, n - 1]
            start = sidx[rows]
            sidx[rows] += n
            remaining[rows] -= n
            tails = []
            for s, n_s, first, end, toks in zip(
                    rows.tolist(), n.tolist(), start.tolist(),
                    ends.tolist(), mine.tolist()):
                tails.append(_Tail(*slots[s], first, toks[:n_s], end,
                                   *holder(s)))
                if end:
                    vacate(s)
            return _Settled(it, tails)

        def free_slots() -> list:
            """Slots no stream holds: not live, not mid-prefill."""
            busy = {st["slot"] for st in self._admitting}
            return [s for s in range(B) if slots[s] is None
                    and remaining[s] == 0 and s not in busy]

        def slot_of(sid) -> Optional[int]:
            if sid is None:
                return None
            for s in range(B):
                if self._slot_sid[s] == sid:
                    return s
            return None

        def reject(meta: Dict, emit, reason: str, idx: int = 0) -> None:
            """Typed stream abort: a ``stream_aborted`` terminator whose
            ``abort_reason`` names the policy that fired, plus registry
            cleanup — the elastic twin of the crash terminator."""
            try:
                self._emit_token(
                    emit, {**meta, META_STREAM_ABORTED: True,
                           META_ABORT_REASON: reason}, 0, idx, True)
            except Exception:  # noqa: BLE001 - downstream may be gone too
                pass
            sid = meta.get(elastic.META_STREAM_ID)
            if sid is not None:
                elastic.unregister_stream(sid)
                self._owned_sids.discard(sid)
                self._cancelled.pop(sid, None)

        # Warm EVERY program the loop uses before admitting real work:
        # first-use costs (trace + compile + program upload) land on the
        # first requests' critical path otherwise.  llama.cpp servers warm up
        # the same way.  Warmup allocates real blocks (exercising the
        # allocator), writes garbage through them, and frees them —
        # nothing real can attend it (the slot re-parks).
        kv.reserve(0, min(C, self.n_blocks * bs))
        # the prefill commits nothing (slot B): keys_dev stays keys_h's twin
        _first_w, tok, keys_dev, pool = self._prefill(
            params, jnp.zeros((1, C), jnp.int32), pool,
            kv.tabs(slice(0, 1)), np.asarray([0, C - 1, 0, B], np.int32),
            tok, keys_dev, base_key)
        tok = self._set_tok(tok, np.int32(0), jnp.asarray(np.int32(0)))
        if self._spec:
            # every slot is parked: the propose/verify warm-ups compile
            # their (only) signatures, write nothing (sentinel tables),
            # and DMA nothing.  pos_dev rides through verify and comes
            # back all-parked (the in-program live mask passes parked
            # rows through untouched).
            draft_pool = self._draft_prefill(
                d_params, jnp.zeros((1, C), jnp.int32), draft_pool,
                kv.tabs(slice(0, 1)), pos[:1] * 0)
            props_w, dprobs_w, draft_pool = self._propose(
                d_params, tok_prev, tok, draft_pool, kv.tabs(),
                pos_dev, keys_dev)
            em_w, acc_w, tok, tok_prev, pos_dev, pool = self._verify(
                params, tok, tok_prev, props_w, dprobs_w, pool,
                kv.tabs(), pos_dev, keys_dev)
            np.asarray(em_w)
        else:
            toks_w, tok, pool = self._decode(
                params, tok, pool, kv.tabs(), pos.copy(), keys_dev,
                length=fw.chunk)
            np.asarray(toks_w)
        kv.release(0)

        # Everything alive now lives as long as the loop does: modules,
        # jax's internals, the compiled programs.  A full collection
        # walks all of it — 50-160 ms a pause on the serving cells, two
        # to seven of them in a 45 s window, each inside the delivery
        # loop with nothing queued on the chip (my chip runs, PR 29) —
        # while the garbage the loop makes is young and small.  Frozen,
        # those objects are walked no more; shutdown() thaws them.
        gc.collect()
        gc.freeze()

        n_iter = 0  # iterations that progressed: the spans' `iter`
        while not self._stop.is_set():
            progressed = False
            # Phase spans (docs/OBSERVABILITY.md): serve.iter is the
            # parent; intake, admit_pass, prefill_chunk, first_token and
            # emit tile it.  With trace_mode=off `rec` is None and every
            # site below is one pointer test.  The three spans open
            # before the iteration knows whether it will do anything are
            # HELD and reach the ring only if it progressed — an idle
            # loop spinning at 50 Hz must not evict the flight recorder.
            it = n_iter + 1
            rec = getattr(fw, "_trace_rec", None)
            if rec is not None:
                if rec.active:
                    sp_iter = begin("serve.iter", iter=it)
                    sp_phase = begin("serve.intake", iter=it)
                    n_taken = -len(self._waiting)
                else:
                    rec = None
            # 0. drain the thread-handoff queue into the admission-order
            # list (FIFO preserved when the head defers on capacity)
            while True:
                try:
                    self._waiting.append(self._pending.get_nowait())
                except _q.Empty:
                    break
            if rec is not None:
                n_taken += len(self._waiting)

            # 0b. control commands (Pipeline.drain_stream/adopt_stream):
            # executed HERE, at a chunk boundary, where every slot's
            # host bookkeeping is consistent.  Both are host-side value
            # moves plus eager gather/scatter on the pool — none of the
            # three compiled loop programs is touched, so the census pin
            # holds across drain/adopt (tests/test_elastic.py).
            # A command ends, moves or reads a stream, so the chunk whose
            # delivery step 5 put off leaves first: a drain's snapshot
            # equals what its client has received.
            if self._ctl:
                self._deliver(rec, False)
            deferred_cmds = []
            while self._ctl:
                cmd = self._ctl.popleft()
                if time.monotonic() > cmd["deadline"]:
                    cmd["error"] = (f"{cmd['kind']} timed out inside the "
                                    "serve loop")
                    cmd["ev"].set()
                    continue
                if cmd["kind"] == "drain":
                    sid = cmd["sid"]
                    s = slot_of(sid)
                    if s is not None and slots[s] is None:
                        s = None  # mid-prefill: not drainable yet
                    wi = next(
                        (i for i, ent in enumerate(self._waiting)
                         if ent[1].get(elastic.META_STREAM_ID) == sid),
                        None)
                    if s is not None:
                        sp = (begin("elastic.drain")
                              if rec is not None else None)
                        ids, n_shared = kv.used(s, int(pos[s]))
                        meta, _emit_cb = slots[s]
                        cmd["result"] = {
                            # v2: adds tok_prev (the speculative
                            # refresh step's input) + shared_blocks;
                            # v1 snapshots stay adoptable (the gather
                            # below MATERIALIZES every block — shared
                            # ones included — as host copies, so a
                            # snapshot never aliases pool blocks
                            # another live stream still holds)
                            "version": 2, "kind": "live",
                            META_STREAM_ID: sid,
                            "cfg": _dc.asdict(cfg), "dtype": fw.dtype,
                            "block_size": bs, "pos": int(pos[s]),
                            "remaining": int(remaining[s]),
                            "sidx": int(sidx[s]),
                            "tok": int(np.asarray(tok)[s]),
                            "tok_prev": int(tok_prev_h[s]),
                            "shared_blocks": n_shared,
                            "greedy": fw.temperature == 0.0,
                            # per-slot PRNG key (docs/SERVING.md §4d):
                            # same-seed sampled continuation after
                            # adopt_stream is bit-identical because
                            # draws fold the absolute position, not the
                            # slot or step
                            "prng_key": [int(v) for v in keys_h[s]],
                            "meta": {k: v for k, v in meta.items()
                                     if k not in _SNAPSHOT_META_DROP},
                            "prompt": np.asarray(self._slot_prompt[s]),
                            # valid cache rows [0, pos) gathered to
                            # host, whole blocks at a time — a COPY,
                            # never an alias (np.asarray of a device
                            # gather materializes); one entry a pool
                            # leaf: blocks_k and blocks_v, or a latent
                            # class's blocks_c
                            **{"blocks_" + leaf: np.asarray(
                                pool[leaf][:, ids])
                               for leaf in llama.allocated_leaves(pool)},
                        }
                        nb = len(kv.slot_blocks[s])
                        retire(s)
                        if sp is not None:
                            sp.end(stream_id=sid, state="live", blocks=nb)
                        progressed = True
                        cmd["ev"].set()
                    elif wi is not None:
                        sp = (begin("elastic.drain")
                              if rec is not None else None)
                        ent = self._waiting.pop(wi)
                        cmd["result"] = {
                            "version": 2, "kind": "queued",
                            META_STREAM_ID: sid,
                            "cfg": _dc.asdict(cfg), "dtype": fw.dtype,
                            "block_size": bs,
                            "greedy": fw.temperature == 0.0,
                            "meta": {k: v for k, v in ent[1].items()
                                     if k not in _SNAPSHOT_META_DROP},
                            "prompt": np.asarray(ent[0]),
                        }
                        elastic.unregister_stream(sid)
                        self._owned_sids.discard(sid)
                        self._cancelled.pop(sid, None)
                        if sp is not None:
                            sp.end(stream_id=sid, state="queued",
                                   blocks=0)
                        progressed = True
                        cmd["ev"].set()
                    elif any(st["meta"].get(elastic.META_STREAM_ID)
                             == sid for st in self._admitting):
                        # mid-prefill: goes live within a few
                        # iterations — re-check then
                        deferred_cmds.append(cmd)
                    else:
                        cmd["error"] = (f"unknown or already-finished "
                                        f"stream {sid}")
                        cmd["ev"].set()
                elif cmd["kind"] == "adopt":
                    snap = cmd["snapshot"]
                    sid = int(snap.get(META_STREAM_ID, 0))
                    if sid <= 0 or sid in elastic.live_stream_ids():
                        # cross-process snapshots may collide with a
                        # live local id — remint, the snapshot id is
                        # only a continuity hint
                        sid = elastic.next_stream_id()
                    meta = dict(snap.get("meta") or {})
                    meta[elastic.META_STREAM_ID] = sid
                    if snap.get("kind") == "queued":
                        sp = (begin("elastic.adopt")
                              if rec is not None else None)
                        self._owned_sids.add(sid)
                        elastic.register_stream(
                            sid, _ft.partial(self._mark_cancel, sid))
                        self._waiting.append(
                            (np.asarray(snap["prompt"], np.int32), meta,
                             cmd["emit"], time.monotonic()))
                        if sp is not None:
                            sp.end(stream_id=sid, state="queued",
                                   blocks=0)
                        cmd["result"] = sid
                        progressed = True
                        cmd["ev"].set()
                        continue
                    p_next = int(snap["pos"])
                    rem = int(snap["remaining"])
                    need_tok = p_next + rem
                    need = kv.blocks_for(need_tok)
                    freeslots = free_slots()
                    if not freeslots:
                        cmd["error"] = "no free slot to adopt into"
                    elif need > self.max_blocks:
                        cmd["error"] = (f"stream needs {need} blocks > "
                                        f"table span {self.max_blocks}")
                    elif len(kv.free) < need:
                        cmd["error"] = (
                            f"insufficient free KV blocks "
                            f"({len(kv.free)} free, {need} needed)")
                    else:
                        sp = (begin("elastic.adopt")
                              if rec is not None else None)
                        s = freeslots[0]
                        blocks = kv.reserve(s, need_tok)
                        ids, _ = kv.used(s, p_next)
                        # eager scatter of the snapshot's cache rows
                        # into the newly reserved pool blocks (a value
                        # move — the compiled census is untouched)
                        for leaf in llama.allocated_leaves(pool):
                            pool[leaf] = pool[leaf].at[:, ids].set(
                                jnp.asarray(np.asarray(
                                    snap["blocks_" + leaf])))
                        # jnp.asarray: the jit fast path keys on arg
                        # TYPE, not just aval — a raw numpy scalar here
                        # would mint a 4th signature and break the
                        # 3-program census pin
                        tok = self._set_tok(tok, np.int32(s),
                                            jnp.asarray(
                                                np.int32(snap["tok"])))
                        tok_h[s] = int(snap["tok"])
                        tok_prev_h[s] = int(snap.get("tok_prev", 0))
                        if self._spec:
                            # the refresh step re-feeds tok_prev at
                            # pos-1; adopting into a spec loop requires
                            # it (snapshot_problems gates v1 snapshots
                            # out).  The DRAFT pool stays unwritten for
                            # the adopted rows — proposals degrade
                            # until positions rewrite, greedy
                            # continuation is target-decided and stays
                            # bit-identical.
                            tok_prev = self._set_tok(
                                tok_prev, np.int32(s),
                                jnp.asarray(np.int32(
                                    snap.get("tok_prev", 0))))
                            pos_dev = self._set_tok(
                                pos_dev, np.int32(s),
                                jnp.asarray(np.int32(p_next)))
                        # sampled streams continue their own PRNG
                        # stream: the snapshot key (if present) slots
                        # in; pre-sampling snapshots get a fresh one
                        pk = snap.get("prng_key")
                        keys_h[s] = (np.asarray(pk, np.uint32)
                                     if pk is not None
                                     else fresh_slot_key())
                        push_keys()
                        pos[s] = p_next
                        remaining[s] = rem
                        sidx[s] = int(snap["sidx"])
                        slots[s] = (meta, cmd["emit"])
                        self._slot_sid[s] = sid
                        self._slot_tenant[s] = meta.get(_META_TENANT)
                        self._slot_prompt[s] = (
                            np.asarray(snap["prompt"], np.int32)
                            if snap.get("prompt") is not None else
                            np.zeros((1, 0), np.int32))
                        self._owned_sids.add(sid)
                        elastic.register_stream(
                            sid, _ft.partial(self._mark_cancel, sid))
                        metrics.gauge(f"llm.serve.slot{s}.occupied", 1.0)
                        if sp is not None:
                            sp.end(stream_id=sid, state="live", slot=s,
                                   blocks=len(blocks))
                        cmd["result"] = sid
                        progressed = True
                    cmd["ev"].set()
                elif cmd["kind"] == "swap":
                    # nns-learn param hot-swap (docs/TRAINING.md): a pure
                    # VALUE move executed where drain/adopt execute — the
                    # decode/prefill programs take params as arguments,
                    # so aval-identical leaves re-use the standing
                    # 3-program census (zero recompiles, pinned by test).
                    # Placement copies onto the live leaves' shardings
                    # (TP pspecs carry over) with FRESH buffers, so a
                    # trainer donating its own tree can't invalidate us.
                    sp = begin("learn.swap") if rec is not None else None
                    try:
                        params = place_swapped_params(params, cmd["tree"])
                    except Exception as e:  # noqa: BLE001 - caller's error
                        cmd["error"] = str(e)
                        if sp is not None:
                            sp.end(hold=True)
                    else:
                        fw.bundle.params = params
                        self.param_version += 1
                        metrics.count("llm.serve.param_swaps")
                        metrics.gauge("llm.serve.param_version",
                                      float(self.param_version))
                        if sp is not None:
                            sp.end(version=self.param_version)
                        cmd["result"] = self.param_version
                        progressed = True
                    cmd["ev"].set()
                else:
                    cmd["error"] = f"unknown command {cmd['kind']!r}"
                    cmd["ev"].set()
            if deferred_cmds:
                self._ctl.extend(deferred_cmds)

            # 0c. reap orphaned streams: a stream marked dead
            # (utils/elastic.cancel_stream — the serversink's dead-
            # connection backchannel) gets stream_idle_timeout of grace
            # (a drain/handover may still pick it up), then its slot +
            # KV blocks return to the free list and a typed terminator
            # goes downstream instead of the pool leaking capacity
            # until max_new runs out.  Queued marks are consumed by the
            # admission scan below.
            if self._cancelled:
                now_m = time.monotonic()
                for sid, (reason, deadline) in list(
                        self._cancelled.items()):
                    if now_m < deadline:
                        continue
                    # the stream's tokens first, then its terminator
                    self._deliver(rec, False)
                    s = slot_of(sid)
                    st = next(
                        (st for st in self._admitting
                         if st["meta"].get(elastic.META_STREAM_ID)
                         == sid), None)
                    if st is not None:
                        # mid-prefill: drop the prefill state first so
                        # step 2 cannot keep writing into freed blocks
                        self._admitting.remove(st)
                        s = st["slot"]
                    if s is not None:
                        sp = (begin("serve.reap", slot=s, stream_id=sid,
                                    reason=reason)
                              if rec is not None else None)
                        nb = len(kv.slot_blocks[s])
                        live_slot = slots[s] is not None
                        meta, emit_cb = (slots[s] if live_slot
                                         else (st["meta"], st["emit"]))
                        metrics.count("llm.serve.reaped")
                        metrics.count("llm.serve.reaped_blocks", nb)
                        # mid-prefill streams emitted nothing: their
                        # terminator is index 0, not the slot's stale
                        # previous-occupant counter
                        reject(meta, emit_cb, reason,
                               idx=int(sidx[s]) if live_slot else 0)
                        retire(s)
                        if sp is not None:
                            sp.end(blocks=nb)
                        progressed = True
                    elif not any(
                            ent[1].get(elastic.META_STREAM_ID) == sid
                            for ent in self._waiting):
                        # already finished/unknown: clear the mark
                        self._cancelled.pop(sid, None)

            if rec is not None:
                sp_intake = sp_phase.end(hold=True, n=n_taken)
                sp_phase = begin("serve.admit_pass", iter=it)
                n_looked = 0
                n_admitted = -len(self._admitting)

            # 1. admission: move waiting prompts into free slots while a
            # slot AND the stream's full block reservation are available.
            # Host-only bookkeeping — no device work yet.  Strict FIFO
            # for capacity deferral (a huge prompt waits rather than
            # being overtaken forever) with two elastic carve-outs: an
            # entry stuck past admit_timeout is rejected with a TYPED
            # abort instead of wedging every tenant queued behind it,
            # and a tenant over its kv-block quota is SKIPPED — tenant-
            # attributed deferral must not head-of-line-block the rest.
            kv.prune(e[1].get(elastic.META_STREAM_ID)
                     for e in self._waiting)
            wi = 0
            while wi < len(self._waiting):
                prompt, meta, emit, t_enq = self._waiting[wi]
                if rec is not None:
                    n_looked += 1
                sid = meta.get(elastic.META_STREAM_ID)
                mark = self._cancelled.get(sid)
                if mark is not None and time.monotonic() >= mark[1]:
                    # grace expired (same deadline the reap path honors
                    # — a drain/handover may still claim the stream
                    # inside it, queued or live)
                    self._waiting.pop(wi)
                    reject(meta, emit, mark[0])
                    progressed = True
                    continue
                T = prompt.shape[1]
                if T >= cfg.max_seq:
                    # reject oversize prompts with a terminated stream
                    self._waiting.pop(wi)
                    reject(meta, emit, "prompt-oversize")
                    progressed = True
                    continue
                n = max(1, min(fw.max_new, cfg.max_seq - T))
                if T + n > self.n_blocks * bs:
                    # the reservation exceeds the WHOLE pool: no amount
                    # of retiring ever satisfies it, so deferring would
                    # wedge the loop (head-of-line FIFO) — reject like
                    # the oversize case instead
                    self._waiting.pop(wi)
                    reject(meta, emit, "reservation-impossible")
                    progressed = True
                    continue
                overdue = (fw.admit_timeout > 0 and
                           time.monotonic() - t_enq > fw.admit_timeout)
                tenant = meta.get(_META_TENANT)
                quota = (self._tenant_quota.get(tenant)
                         if tenant is not None else None)
                # Quota charges LOGICAL blocks (per reference): a tenant
                # pays for every block its streams MAP, shared or not —
                # a shared prefix neither lets it exceed its cap for
                # free nor double-charges the physical pool (the free-
                # list check below is the physical side and charges the
                # non-shared suffix only).
                logical = kv.blocks_for(T + n)
                if quota is not None and logical + kv.held(
                        s for s in range(B)
                        if self._slot_tenant[s] == tenant) > quota:
                    if overdue:
                        self._waiting.pop(wi)
                        metrics.count("llm.serve.admit_timeouts")
                        reject(meta, emit, "admit-timeout")
                        progressed = True
                        continue
                    metrics.count("llm.serve.quota_deferred")
                    wi += 1  # skip: quota deferral is tenant-scoped
                    continue
                # the prefix lookup comes BEFORE the capacity check: a
                # cache hit shrinks the physical reservation to about
                # the non-shared suffix, prefilled from plan.p0
                plan = kv.lookup(sid, prompt[0], T, n)
                freeslots = free_slots()
                if not freeslots or not kv.fits(plan):
                    if overdue:
                        # head-of-line fix: a wedged/dead/huge stream at
                        # the queue head times out instead of blocking
                        # every tenant behind it forever
                        self._waiting.pop(wi)
                        metrics.count("llm.serve.admit_timeouts")
                        reject(meta, emit, "admit-timeout")
                        progressed = True
                        continue
                    break  # pool full: defer admission, never overflow
                if rec is not None:
                    # request-bound spans carry the request's trace id
                    # (stamped at ingress while tracing is on)
                    tid = meta.get(META_TRACE_ID)
                    sp = begin("serve.admit", tid, iter=it)
                    t_admit = sp.ts
                else:
                    t_admit = time.monotonic_ns()
                self._waiting.pop(wi)
                s = freeslots[0]
                p0, shared, phys = plan.p0, plan.shared, plan.phys
                forked = kv.admit(s, plan)
                if forked is not None:
                    cow_copy(*forked)
                self._slot_sid[s] = sid
                self._slot_tenant[s] = tenant
                self._slot_prompt[s] = prompt[:, :T].copy()
                self._slot_time[s] = {"enq": t_enq, "admit": t_admit / 1e9,
                                      "first": None, "last": None}
                if shared and rec is not None:
                    rec.record("serve.prefix_hit", _SERVE_STAGE, None,
                               t_admit, time.monotonic_ns() - t_admit,
                               slot=s, blocks=shared, tokens=p0)
                # chunk-multiple padding (replaces the old power-of-two
                # prompt bucketing on this path: waste < one chunk);
                # only the suffix [p0, P) is prefilled
                P = p0 + -(-(T - p0) // C) * C
                if P > T:
                    prompt = np.pad(prompt, ((0, 0), (0, P - T)))
                metrics.count("llm.serve.prefill_tokens", P - p0)
                metrics.count("llm.serve.prefill_pad_waste", P - T)
                self._admitting.append({
                    "slot": s, "prompt": prompt.astype(np.int32), "T": T,
                    "P": P, "p": p0, "n": n, "meta": meta, "emit": emit,
                    "first": None, "hashes": plan.hashes,
                    "last_tok": int(prompt[0, T - 1])})
                if rec is not None:
                    # what this request's later spans need of its admission
                    self._admitting[-1]["trace"] = (tid, t_admit, p0)
                    sp.end(slot=s, tokens=T, blocks=phys, shared=shared)
                    # the request's wait, from the stamp submit() took:
                    # ends where its serve.prefill will start
                    enq_ns = int(t_enq * 1e9)
                    rec.record("serve.queue", _SERVE_STAGE, tid, enq_ns,
                               t_admit - enq_ns, tid=tid, slot=s,
                               tokens=T, blocks=phys, shared=shared)
                progressed = True
            if rec is not None:
                sp_admit = sp_phase.end(
                    hold=True, looked=n_looked,
                    admitted=n_admitted + len(self._admitting))

            # 2. chunked prefill: dispatch up to prefill_budget tokens of
            # [1, C] prefill chunks straight into the admitting streams'
            # blocks (async — no host sync here).  A prompt's final chunk
            # samples and commits its first token in the same program
            # (tok[s], keys_dev[s]): from its dispatch to the decode
            # chunk's (step 3) this thread waits on the device for nothing
            # and issues no program of its own.  With no live decode the
            # budget is waived: there is nothing to interleave with, and
            # finishing the prompt sooner IS the latency win.
            budget = fw.prefill_budget if (remaining > 0).any() else 1 << 30
            newly_live = []  # (slot, state) — first token syncs in step 4
            for st in list(self._admitting):
                while budget > 0 and st["p"] < st["P"]:
                    s, p = st["slot"], st["p"]
                    final = p + C >= st["P"]
                    if rec is not None:
                        sp = begin("serve.prefill_chunk",
                                   st.get("trace", _NO_TRACE)[0], iter=it,
                                   slot=s, pos=p, final=bool(final))
                    # last REAL token's offset within this chunk
                    # (intermediate chunks are all real tokens; their
                    # logits are unused, their slot-owned state is not)
                    off = st["T"] - 1 - p if final else C - 1
                    # the stream's slot key is fold_in(seed key, admission
                    # number) and its first token, at position T, folds
                    # (T, sample tag) like every later draw: the whole
                    # stream a pure function of (seed, admission number,
                    # positions).  Only the final chunk commits (slot B
                    # is out of range); its number is spent below, once
                    # the stream is known to go live.
                    first, tok, keys_dev, pool = self._prefill(
                        params, jnp.asarray(st["prompt"][:, p:p + C]),
                        pool, kv.tabs(slice(s, s + 1)),
                        np.asarray([p, off, adm_no, s if final else B],
                                   np.int32),
                        tok, keys_dev, base_key)
                    if self._spec:
                        # the draft's prefill twin writes the chunk's
                        # draft K/V into the SAME blocks of the draft
                        # pool — a later prefix hit shares both models'
                        # rows
                        draft_pool = self._draft_prefill(
                            d_params,
                            jnp.asarray(st["prompt"][:, p:p + C]),
                            draft_pool, kv.tabs(slice(s, s + 1)),
                            np.asarray([p], np.int32))
                    st["p"] = p + C
                    budget -= C
                    poisoned = False
                    if final and fw.nan_guard:
                        # the guard's price: a wait for the prefill with
                        # nothing else queued on the chip
                        first = np.asarray(first)
                        poisoned = not first[3]
                    if rec is not None:
                        sp.end(sampled=int(final and not poisoned))
                    progressed = True
                    if final:
                        if poisoned:
                            # poison pill: the prompt's own prefill
                            # produced non-finite logits — quarantine
                            # it (DLQ + breaker accounting through the
                            # pipeline's armor) and answer the client
                            # with the typed poison terminator; the
                            # loop keeps serving every other stream
                            # (what the program wrote into the slot's
                            # rows no stream reads: the slot re-parks)
                            err = FloatingPointError(
                                "non-finite prefill logits (nan_guard)")
                            armor_obj = getattr(fw, "_armor", None)
                            if armor_obj is not None:
                                from ..core.buffer import Buffer as _Buf

                                armor_obj.quarantine(
                                    _Buf([st["prompt"][:, :st["T"]]
                                          .copy()],
                                         meta=dict(st["meta"])),
                                    error=err, stage="llm.serve")
                            metrics.count("llm.serve.poisoned")
                            # the slot's last stream may still be owed
                            # its tail (step 5): that leaves first
                            self._deliver(rec, False)
                            self._admitting.remove(st)
                            reject(st["meta"], st["emit"], "poison")
                            retire(s)
                            progressed = True
                            break
                        st["first"] = first
                        adm_no += 1
                        metrics.count("llm.serve.first_token_in_prefill")
                        tok_prev_h[s] = st["last_tok"]
                        if self._spec:
                            # the round's refresh step re-feeds the
                            # LAST PROMPT token at T-1 (bit-exact
                            # rewrite); must be device-resident before
                            # this iteration's propose dispatch — and
                            # the device position twin goes live at T
                            tok_prev = self._set_tok(
                                tok_prev, np.int32(s),
                                jnp.asarray(np.int32(st["last_tok"])))
                            pos_dev = self._set_tok(
                                pos_dev, np.int32(s),
                                jnp.asarray(np.int32(st["T"])))
                        # register the prompt's full blocks in the
                        # prefix index (content is in-flight on device;
                        # pool donation chains order any reader after
                        # this prefill)
                        kv.register(s, st["hashes"])
                        pos[s] = st["T"]
                        remaining[s] = st["n"] - 1
                        sidx[s] = 1
                        # provisional occupancy for EVERY newly-live
                        # stream (n==1 included): between leaving
                        # _admitting and its step-4 first-token emission
                        # the stream must be visible to the crash
                        # terminator, and slots[] is the only place it
                        # looks.  Step 4 retires n==1/EOS immediately.
                        slots[s] = (st["meta"], st["emit"])
                        newly_live.append(st)
                        self._admitting.remove(st)
                        metrics.gauge(f"llm.serve.slot{s}.occupied", 1.0)
                        break

            # 3. dispatch one chunk of per-row paged decode for the live
            # slots (still async).  The chunk length is ALWAYS fw.chunk:
            # a variable tail would compile a fresh 7B program per
            # distinct value.  Streams that finish mid-chunk keep
            # decoding garbage until chunk end (writes stay inside their
            # reserved blocks or drop; outputs are never emitted).
            live = remaining > 0
            toks_dev = None
            em_dev = acc_dev = None
            if live.any():
                t_dec = time.monotonic_ns()
                if self._spec:
                    # one speculative round: draft proposes k tokens,
                    # the target verifies AND COMMITS them in ONE
                    # [slots, k+1]-wide paged step — tok/tok_prev/
                    # positions come back as device values (async
                    # futures; rebinding them here is free), so the
                    # host never re-uploads token state per round.
                    # Step 4's retires re-park pos_dev AFTER this
                    # rebind, so a first-token EOS still wins.
                    props_dev, dprobs_dev, draft_pool = self._propose(
                        d_params, tok_prev, tok, draft_pool, kv.tabs(),
                        pos_dev, keys_dev)
                    (em_dev, acc_dev, tok, tok_prev, pos_dev,
                     pool) = self._verify(
                        params, tok, tok_prev, props_dev, dprobs_dev,
                        pool, kv.tabs(), pos_dev, keys_dev)
                    metrics.count("llm.serve.spec_rounds")
                else:
                    toks_dev, tok, pool = self._decode(
                        params, tok, pool, kv.tabs(), pos.copy(),
                        keys_dev, length=fw.chunk)
                    pos[live] += fw.chunk  # parked rows stay parked
                if rec is not None:
                    # the jitted call(s) returned: the chip got this
                    # iteration's work somewhere in here (serve_dispatch_pct)
                    dispatch_ns = time.monotonic_ns() - t_dec
                progressed = True
            metrics.gauge("llm.serve.occupancy", float(live.sum()))
            metrics.gauge("llm.serve.free_blocks", float(len(kv.free)))
            metrics.gauge("llm.serve.waiting",
                          float(len(self._waiting) + len(self._admitting)))

            # 4. materialize + emit the admitted first tokens — this wait
            # is for the PREFILL program, and the device already has the
            # chunk behind it; the late joiner's first token leaves here,
            # one dispatch (not one drained queue) after submit.  The
            # slot's key comes home in the same fetch.
            for st in newly_live:
                s = st["slot"]
                if rec is not None:
                    tid, t_adm, p0 = st.get("trace", _NO_TRACE)
                    sp = begin("serve.first_token", tid, iter=it, slot=s)
                fetched = np.asarray(st["first"])
                first = int(fetched[0])
                tok_h[s] = first
                keys_h[s] = fetched[1:3].view(np.uint32)
                first_last = st["n"] == 1 or first == eos
                self._emit_token(st["emit"], st["meta"], first, 0,
                                 first_last)
                self._mark_emit(self._slot_time[s], self._slot_tenant[s])
                if rec is not None:
                    sp.end()
                    if t_adm is not None:
                        # admission -> first token left the loop, from the
                        # stamps the histogram llm.serve.prefill_ms
                        # observes at retirement; starts where serve.queue
                        # ended
                        rec.record("serve.prefill", _SERVE_STAGE, tid,
                                   t_adm, max(0, int(
                                       self._slot_time[s]["first"] * 1e9)
                                       - t_adm),
                                   tid=tid, slot=s,
                                   chunks=(st["P"] - p0) // C)
                if first_last:
                    # n==1 or EOS on token 0: the in-flight chunk's row
                    # decodes garbage that step 5 skips via remaining==0
                    retire(s)

            # 4b. what step 5 put off of the last chunk's delivery (all of
            # it, or the rest once the freed callers' requests were in)
            # leaves now: the chip has the next chunk (or, when every row
            # retired and nothing went live, nothing left to do)
            if self._undelivered is not None:
                self._deliver(rec, toks_dev is not None)
                progressed = True

            # 5. wait for the chunk, settle it, and deliver its tokens now
            # or under the next chunk
            if toks_dev is not None:
                if rec is not None:
                    sp = begin("serve.decode.wait", iter=it)
                host = np.asarray(toks_dev)  # ONE roundtrip per chunk
                moe_args = {}
                if self._moe:
                    # rows B..B+4: per step, over the expert layers and
                    # the live rows — routed pairs the held experts
                    # computed, held experts hit, most pairs on one,
                    # identity pairs (no expert computed them) — and,
                    # over all rows, the times the grouped kernel
                    # streamed an expert's matrices
                    moe = host[B:]
                    host = host[:B]
                    moe_args = {"moe_pairs": int(moe[0].sum()),
                                "moe_experts_hit": int(moe[1].sum()),
                                "moe_max_per_expert": int(moe[2].max()),
                                "moe_zero_pairs": int(moe[3].sum()),
                                "moe_weight_passes": int(moe[4].sum())}
                if rec is not None:
                    # the decode span closes HERE, at materialization:
                    # the jit call above only enqueued the async
                    # dispatch, so a span closed there would time host
                    # dispatch (~us) and hide the actual device time —
                    # the number the trace exists to attribute
                    decode_closed("serve.decode", sp, chunk=fw.chunk,
                                  **moe_args)
                self._undelivered = settled = settle(host)
                # Delivery is Python per token (33 ms for 255 tokens on
                # the 7B cell, my chip runs, PR 30) with nothing queued on
                # the chip, so it waits until the next chunk is dispatched
                # (step 4b) — unless streams ended here and no request is
                # queued: then the callers most likely to send the next
                # one are waiting on this very delivery (a caller whose
                # next turn follows its last answer), and a chunk
                # dispatched now would make their requests wait it out.
                # So they are answered first, and the rest of the delivery
                # fills the time until their requests are in.
                if settled.retired and not (
                        self._waiting or self._admitting
                        or not self._pending.empty()):
                    self._deliver(rec, False, until=settled.retired)

            # 5b. speculative emit: the fused verify already accepted
            # and COMMITTED on device (tok/tok_prev/pos_dev rebound at
            # dispatch); the host materializes only the per-slot accept
            # count + the emitted-token rows — one [B] + one [B, k+1]
            # D2H per round, no accept-mask round-trip, no proposal
            # fetch, no token re-upload.  Everything after the first
            # rejection is discarded (its K/V rows get overwritten
            # before they can ever be attended, the same overwrite-
            # before-attend discipline chunked prefill relies on).
            # Host mirrors (tok_h/tok_prev_h/pos) update from the same
            # values, so drain snapshots stay exact.
            if em_dev is not None:
                if rec is not None:
                    sp = begin("serve.spec_verify.wait", iter=it)
                em_host = np.asarray(em_dev)    # [B, k+1]
                acc_host = np.asarray(acc_dev)  # [B] — one sync
                if rec is not None:
                    decode_closed("serve.spec_verify", sp, k=fw.spec_k)
                    sp = begin("serve.emit", iter=it)
                n_tok = n_ret = 0
                K = fw.spec_k
                for s in np.flatnonzero(live):
                    s = int(s)
                    if remaining[s] == 0:
                        continue  # retired at its first token (EOS)
                    meta, emit = slots[s]
                    acc = int(acc_host[s])
                    metrics.count("llm.serve.spec_accepted", acc)
                    metrics.count("llm.serve.spec_rejected", K - acc)
                    if K:
                        # accept rate = accepted drafts / proposed (the
                        # +1 bonus/fallback token is not a draft)
                        metrics.gauge("llm.serve.spec_accept_rate",
                                      acc / K)
                        ten = self._slot_tenant[s]
                        if ten is not None:
                            metrics.gauge("llm.serve.spec_accept_rate",
                                          acc / K, tenant=ten)
                    emitted = []
                    finished = False
                    for j in range(acc + 1):
                        tokid = int(em_host[s, j])
                        last = remaining[s] == 1 or tokid == eos
                        # accepted draft tokens vs the target-sampled
                        # bonus/fallback token: the accept/reject path's
                        # pipeline-native surface (tensor_if
                        # compared_value=META_VALUE, tensor_demux
                        # by-meta= — docs/SERVING.md §4c)
                        self._emit_token(
                            emit, meta, tokid, int(sidx[s]), bool(last),
                            extra={"spec_draft": 1 if j < acc else 0})
                        self._mark_emit(self._slot_time[s],
                                        self._slot_tenant[s])
                        emitted.append(tokid)
                        sidx[s] += 1
                        remaining[s] -= 1
                        if last:
                            # retire() re-parks pos_dev, overriding the
                            # in-program advance for this row — device
                            # tok/tok_prev keep stale values there,
                            # which parked rows never read
                            retire(s)
                            finished = True
                            break
                    n_tok += len(emitted)
                    n_ret += finished
                    if not finished:
                        pos[s] += len(emitted)
                        seq = [int(tok_h[s])] + emitted
                        tok_h[s] = seq[-1]
                        tok_prev_h[s] = seq[-2]
                if rec is not None:
                    sp.end(tokens=n_tok, retired=n_ret, ahead=0)

            if rec is not None:
                # blocks live in each pool: what the allocator has handed
                # out, and the ring entries that hold a live stream's rows
                sp_iter.end(hold=True, live=int(live.sum()),
                            waiting=len(self._waiting)
                            + len(self._admitting),
                            full_blocks=self.n_blocks - len(kv.free),
                            win_blocks=kv.win_blocks_live(pos))
                if progressed:
                    n_iter = it
                    sp_iter.commit()
                    sp_intake.commit()
                    sp_admit.commit()

            if not progressed:
                with self._idle_lock:
                    if self._pending.empty() and not self._waiting \
                            and not self._admitting and not self._ctl \
                            and not (remaining > 0).any():
                        self._idle.set()
                self._wake.wait(0.02)
                self._wake.clear()
        # stopped with a chunk settled and its delivery put off: the
        # streams that ended in it are in no slot, their tails leave now
        self._deliver(None, False)
