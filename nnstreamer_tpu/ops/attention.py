"""Flash (blockwise, online-softmax) attention as a Pallas TPU kernel.

The reference delegates attention to whatever runtime it wraps (llama.cpp's
internal kernels for the LLM filter — SURVEY §5.7); the TPU build owns the
kernel.  This is the memory-bound case Pallas exists for: the naive path
materializes the [S, S] score matrix in HBM; the flash kernel never does.

Kernel structure (VMEM-bounded for any sequence length):

* q is tiled into ``block_q`` rows via BlockSpec (pipelined by Pallas);
* k/v stay in HBM (``memory_space=ANY``) and are streamed through a
  double-buffered VMEM scratch ``block_k`` rows at a time with explicit
  async DMA — so VMEM use is O(block_q·d + 2·block_k·d), independent of S;
* the softmax running max/sum ride in registers across k blocks;
* causal q-blocks stop their kv stream at the diagonal — skipped blocks are
  never even fetched from HBM.

Layouts: q is [B, S, H, D] (heads after seq, matching models/llama.py);
k/v are [B, S, Hkv, D] with ``H % Hkv == 0`` — GQA/MQA K/V arrive
UNREPEATED.  The kernel grid runs one cell per (batch, kv-head) and keeps
the whole query-head group resident against each streamed K/V block, so a
block is DMA'd into VMEM once per group instead of once per query head:
grouped decode/prefill HBM traffic is ``Hkv/H`` of the repeated layout's.
On non-TPU backends the public entry falls back to
:func:`attention_reference` (compiled XLA, which performs the repeat
internally so it stays a bit-faithful twin) unless ``interpret=True`` is
passed explicitly (tests do, for bit-faithful kernel coverage on CPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# pallas_call has no GSPMD partitioning rule, so a paged-attention
# program traced for a sharded (tensor-parallel) mesh must take the
# shardable XLA reference path instead — sharding is invisible at trace
# time, so the caller that builds TP programs (filters/llm.py) disables
# the kernel for the lifetime of its filter.  Same REFCOUNTED contract
# as ops/int4_matmul.py: concurrent TP filters must not clobber each
# other's save/restore, and a filter that dies mid-open must not leak a
# disabled kernel process-wide.
import threading as _threading

_disable_lock = _threading.Lock()
_disable_count = 0


def disable_paged_kernel() -> None:
    global _disable_count
    with _disable_lock:
        _disable_count += 1


def enable_paged_kernel() -> None:
    global _disable_count
    with _disable_lock:
        _disable_count = max(0, _disable_count - 1)


def paged_kernel_enabled() -> bool:
    return _disable_count == 0


def _repeat_kv_heads(x, n_rep: int):
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D]; query head i reads kv head
    i // n_rep (the models/llama.py ``_repeat_kv`` layout)."""
    if n_rep == 1:
        return x
    b, s, hkv, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, hkv, n_rep, d)).reshape(
            b, s, hkv * n_rep, d)


def attention_reference(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None, window: int = 0):
    """Plain-XLA attention (the flash kernel's semantics, materialized).

    ``window`` > 0 (causal only): query at position p attends positions
    ``p - window + 1 .. p``, the token itself included.

    Accepts grouped K/V (``k.shape[2]`` dividing ``q.shape[2]``) and
    repeats internally — XLA fuses the broadcast into the einsum, so the
    repeated tree is never a real HBM allocation here.  This keeps the
    reference the bit-faithful twin of the grouped kernel.
    """
    d = q.shape[-1]
    h, hkv = q.shape[2], k.shape[2]
    if h != hkv:
        k = _repeat_kv_heads(k, h // hkv)
        v = _repeat_kv_heads(v, h // hkv)
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # kv may be longer than q (prefix/cache): align q to the BACK of kv.
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        keep = kpos <= qpos
        if window:
            keep &= kpos > qpos - window
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _flash_kernel(q_ref, k_hbm, v_hbm, o_ref, *, block_k: int, causal: bool,
                  scale: float, q_offset: int):
    """One (batch*kv-head, q-block) grid cell.

    q_ref/o_ref: VMEM [block_q, G, d] tiles holding the WHOLE query-head
    group for this kv head (G = H // Hkv; G == 1 is plain MHA); k_hbm/v_hbm:
    the full [B*Hkv, Skv, d] arrays left in HBM — kv blocks are DMA'd
    through a 2-slot VMEM scratch ONCE per group, and all G query heads
    score against the resident block.  That single sharing is the whole
    GQA win: grouped HBM traffic is Hkv/H of the repeated layout's.
    """
    block_q, grp, d = q_ref.shape
    rows = block_q * grp
    skv = k_hbm.shape[1]
    nk = skv // block_k
    i = pl.program_id(0)
    j = pl.program_id(1)

    # flatten the group into the row dim: row r = q_row * G + g, so the
    # MXU sees one [block_q*G, d] x [d, block_k] contraction per block
    q = q_ref[:].astype(jnp.float32).reshape(rows, d) * scale
    qpos = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, grp, block_k), 0).reshape(rows, block_k)
    kpos = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, grp, block_k), 2).reshape(rows, block_k)

    if causal:
        # The last row of this q block attends up to j*block_q + block_q - 1
        # + q_offset; kv blocks past it are never fetched.
        last_k = j * block_q + block_q - 1 + q_offset
        upper = jnp.minimum(last_k // block_k + 1, nk)
    else:
        upper = nk

    def scoped(kbuf, vbuf, ksem, vsem):
        def kdma(slot, kb):
            return pltpu.make_async_copy(
                k_hbm.at[i, pl.ds(kb * block_k, block_k), :], kbuf.at[slot],
                ksem.at[slot])

        def vdma(slot, kb):
            return pltpu.make_async_copy(
                v_hbm.at[i, pl.ds(kb * block_k, block_k), :], vbuf.at[slot],
                vsem.at[slot])

        kdma(0, 0).start()
        vdma(0, 0).start()

        def body(kb, carry):
            m, l, acc = carry
            slot = jax.lax.rem(kb, 2)
            nxt = jax.lax.rem(kb + 1, 2)

            @pl.when(kb + 1 < upper)
            def _():  # prefetch next kv block while computing this one
                kdma(nxt, kb + 1).start()
                vdma(nxt, kb + 1).start()

            kdma(slot, kb).wait()
            vdma(slot, kb).wait()
            kblk = kbuf[slot].astype(jnp.float32)
            vblk = vbuf[slot].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if causal:
                abs_q = qpos + j * block_q + q_offset
                abs_k = kpos + kb * block_k
                s = jnp.where(abs_k <= abs_q, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # exp(-inf - -inf) would be nan; clamp the shift for masked rows
            shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - shift)
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m, shift) - shift)
            l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((rows, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
        acc0 = jnp.zeros((rows, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
        o_ref[:] = (acc / jnp.maximum(l, 1e-30)).reshape(
            block_q, grp, d).astype(o_ref.dtype)

    pl.run_scoped(
        scoped,
        kbuf=pltpu.VMEM((2, block_k, d), k_hbm.dtype),
        vbuf=pltpu.VMEM((2, block_k, d), v_hbm.dtype),
        ksem=pltpu.SemaphoreType.DMA((2,)),
        vsem=pltpu.SemaphoreType.DMA((2,)),
    )


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Blockwise attention for [B, S, H, D] q and [B, S, Hkv, D] k/v.

    ``Hkv`` may divide ``H`` (GQA/MQA) — pass K/V UNREPEATED; the kernel
    shares each streamed K/V block across the whole query-head group, and
    the XLA fallback repeats internally, so both paths emit identical
    values from the grouped layout.

    Uses the Pallas kernel on TPU backends (or anywhere when
    ``interpret=True`` is forced); otherwise — including non-tiling shapes —
    falls back to :func:`attention_reference`.

    TPU-kernel shape requirements (else the XLA fallback runs): ``S_q`` a
    multiple of ``block_q``, ``S_kv`` of ``block_k``, and head dim ``D`` a
    multiple of 128 (Mosaic DMA lane tiling).  Llama-2-7B's head_dim=128
    qualifies; the toy test presets (head_dim 32/64) intentionally fall back.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    hkv = k.shape[2]
    scale_v = (d ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = False
        if jax.default_backend() != "tpu":
            # Interpreter mode is for tests; production non-TPU backends get
            # the compiled XLA path.
            return attention_reference(q, k, v, causal=causal, scale=scale_v)
    if (
        sq % block_q
        or skv % block_k
        or k.shape != v.shape
        or h % hkv
        # Mosaic DMA slices must align the minor dim to the 128-lane tiling;
        # interpreter mode has no such constraint.
        or (not interpret and d % 128)
    ):
        return attention_reference(q, k, v, causal=causal, scale=scale_v)

    grp = h // hkv
    # q: [B, S, H, D] -> [B*Hkv, S, G, D] (query head h = kv_head*G + g,
    # the models/llama.py _repeat_kv layout); k/v: [B, S, Hkv, D] ->
    # [B*Hkv, S, D] — one grid row per (batch, kv-head) so a K/V block is
    # fetched once for all G query heads of its group.
    qf = q.reshape(b, sq, hkv, grp, d).transpose(0, 2, 1, 3, 4).reshape(
        b * hkv, sq, grp, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)

    kernel = functools.partial(
        _flash_kernel,
        block_k=block_k,
        causal=causal,
        scale=scale_v,
        q_offset=skv - sq,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, grp, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # kv stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, block_q, grp, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, sq, grp, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hkv, sq, grp, d).transpose(0, 2, 1, 3, 4).reshape(
        b, sq, h, d)


# ---------------------------------------------------------------------------
# Paged (block-pool) attention — the continuous-serving decode path
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_pool, v_pool, block_tables, context_lens,
                              *, scale: Optional[float] = None,
                              window: int = 0, ring: bool = False):
    """Plain-XLA paged attention (the kernel's semantics, materialized).

    ``q``: [B, T, H, D] query suffix (T=1 decode, T=C prefill chunk);
    ``k_pool``/``v_pool``: [n_blocks, block_size, H_kv, D] shared block
    pool; ``block_tables``: [B, max_blocks] int32 — row b's logical block
    j lives in pool block ``block_tables[b, j]`` (entries >= n_blocks are
    unallocated sentinels); ``context_lens``: [B] int32 — tokens
    attendable per row INCLUDING the suffix (the suffix's K/V must
    already be written into the pool).  Query t of row b sits at absolute
    position ``context_lens[b] - T + t``.

    Gathers each row's full table (B x max_blocks x block_size reads —
    correct everywhere, traffic-optimal nowhere; the TPU kernel below is
    the path that only touches live blocks) and applies EXACTLY the dense
    masked-decode formulation from models/llama.py so paged and dense
    caches emit identical greedy tokens.

    ``window`` > 0: query at position p attends ``p - window + 1 .. p``
    only.  ``ring``: the table is a ring of ``R = max_blocks`` entries —
    logical block j lives at entry ``j % R`` and an entry holds the
    LATEST logical block written to it; the caller sizes R so that every
    position a query of this call may attend is still there (models/
    llama.py ``window_ring_blocks``).
    """
    B, T, H, D = q.shape
    n_blocks, bs, hkv, d_pool = k_pool.shape
    # a pool that stores several narrow KV heads to a lane row
    # (:func:`kv_lane_pack`) is the same numbers in the same order
    hkv = hkv * d_pool // D
    scale_v = (D ** -0.5) if scale is None else scale
    dt = q.dtype
    # Sentinel entries clip to a real block: their logical positions sit
    # at/after the allocated extent, so the position mask hides them.
    tbl = jnp.clip(block_tables, 0, n_blocks - 1)
    k_all = k_pool[tbl].reshape(B, -1, hkv, D).astype(dt)
    v_all = v_pool[tbl].reshape(B, -1, hkv, D).astype(dt)
    if H != hkv:  # GQA: mirror the dense path's repeat-then-einsum order
        rep = H // hkv
        S = k_all.shape[1]
        k_all = jnp.broadcast_to(
            k_all[:, :, :, None, :], (B, S, hkv, rep, D)).reshape(B, S, H, D)
        v_all = jnp.broadcast_to(
            v_all[:, :, :, None, :], (B, S, hkv, rep, D)).reshape(B, S, H, D)
    q_pos = (context_lens[:, None] - T) + jnp.arange(T)[None, :]  # [B, T]
    if ring:
        # entry r holds logical block hi - ((hi - r) mod R), hi the block
        # of the row's last written position; negative = never written
        R = block_tables.shape[1]
        hi = (jnp.maximum(context_lens, 1) - 1) // bs  # [B]
        r = jnp.arange(R)[None, :]
        held = hi[:, None] - jnp.mod(hi[:, None] - r, R)  # [B, R]
        k_pos = (held[:, :, None] * bs
                 + jnp.arange(bs)[None, None, :]).reshape(B, -1)[:, None, :]
    else:
        k_pos = jnp.arange(k_all.shape[1])[None, None, :]
    mask = k_pos <= q_pos[:, :, None]
    if ring:
        mask &= k_pos >= 0
    if window:
        mask &= k_pos > q_pos[:, :, None] - window
    mask = mask[:, None]  # [B, 1, T, S]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_all,
                   preferred_element_type=jnp.float32) * scale_v
    s = jnp.where(mask, s, jnp.float32(-1e30))
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(dt), v_all)


def kv_lane_pack(hkv: int, d: int) -> int:
    """KV heads a pool row holds side by side so that it fills the 128
    lanes a DMA and an MXU operand tile by: ``128 / d`` for heads of 64,
    32, ... where the KV heads divide by it, else 1.  A pool ``[blocks,
    bs, hkv, d]`` stored as ``[blocks, bs, hkv / pack, d * pack]`` is the
    same numbers in the same order; on a TPU the two are DIFFERENT tiled
    layouts (a minor dimension of 64 is padded to the lanes), so a pool
    that is to be read by the kernel is BORN packed
    (``models/llama.py init_paged_cache``) and :func:`paged_attention`
    never repacks one: that is a copy of the whole pool a step (0.61 s of
    2.84 s busy on the chip, PR 35)."""
    pack = 128 // d if 0 < d < 128 and 128 % d == 0 else 1
    return pack if hkv % pack == 0 else 1


def _paged_wave_blocks(bs: int, hkv: int, d: int, itemsize: int,
                       window: int = 0) -> int:
    """Blocks of K (and of V) in flight per buffer slot.

    A block ``[bs, hkv, D]`` is the matrix ``[bs * hkv, D]`` it already is
    in memory, and a wave of W of them is one ``[W * bs * hkv, D]`` MXU
    operand.  Aim at ~1024 score columns a wave (8 blocks of 16 x 8 heads,
    0.5 MB of K + V outstanding per slot in bf16), never more than 16
    places, and keep K + V x two slots inside 4 MiB of VMEM.  A window
    that fits those limits is ONE wave a row: ``window`` positions touch
    ``ceil(window / bs) + 1`` blocks at most."""
    rows = bs * hkv
    most = min(16, (4 << 20) // (4 * rows * d * itemsize))
    span = -(-window // bs) + 1
    if window and span <= most:
        return span
    return max(1, min(1024 // rows, most))


def _paged_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sem, state, *, scale: float, hkv: int,
                  window: int = 0, ring: int = 0):
    """One stream (batch row) per grid cell.

    The whole point of paging: the kv stream for row ``b`` is
    ``ceil(len/bs)`` DMA'd blocks — idle and short rows fetch nothing
    beyond their own live prefix, so per-step HBM traffic is the SUM of
    live lengths, not B x S_max.  ``tbl_ref``/``len_ref`` are
    scalar-prefetched SMEM (available before the body runs, so the block
    ids can steer the DMAs); k/v pools stay in HBM (ANY), each block read
    as the ``[bs * hkv, D]`` matrix it is in memory.

    Blocks stream in WAVES of ``W = kbuf.shape[1]`` through a 2-slot VMEM
    scratch: the next wave's W copies of K and W of V are in flight while
    this one is computed on, and a row's LAST wave overlaps the next
    row's first (the scratch, its semaphores and ``state`` — the slot
    that wave went to, and whether it was started — outlive a grid step;
    the grid runs in order).  A place of a row's last wave whose block is
    not live for the row issues no copy: it keeps whatever an earlier
    wave left there and its columns are masked by position.

    Per wave the scores of ALL query heads against ALL of the wave's rows
    are one MXU product ``q[H, D] x K[W * bs * hkv, D]^T``: column ``c``
    is token ``c // hkv`` of the wave under KV head ``c % hkv``, and head
    ``h`` keeps only the columns of its own KV head ``h // G`` — no
    per-head broadcast of the block; the other columns ride MXU rows that
    were idle anyway and enter P x V (the second product) as exact zeros.

    ``window`` > 0 starts the stream at the block that holds position
    ``L - window``: a window layer reads ``ceil(window/bs) + 1`` blocks
    at most, whatever the context.  ``ring`` > 0 is the table's width
    when it is a ring: logical block i lives at entry ``i % ring``.
    """
    H, D = q_ref.shape
    _, W, rows, _ = kbuf.shape  # rows = bs * hkv
    bs = rows // hkv
    G = H // hkv
    cols = W * rows
    b = pl.program_id(0)
    B = pl.num_programs(0)

    def stream(row):
        # (first block, one past the last) that `row` attends, its
        # length and the first position attended
        L = len_ref[row]
        nb = (L + bs - 1) // bs  # live blocks only: the traffic contract
        lo = jnp.maximum(L - window, 0) if window else 0
        return (lo // bs if window else 0), nb, L, lo

    b0, nb, L, lo = stream(b)
    n_waves = (nb - b0 + W - 1) // W  # 0 for an idle row (L == 0)

    def each_live_place(row, slot, first, end, op):
        # place w of the slot <-> logical block first + w of `row`, for
        # the places whose block is live
        def place(w, carry):
            i = first + w
            e = tbl_ref[row, jax.lax.rem(i, ring) if ring else i]
            op(pltpu.make_async_copy(
                k_hbm.at[e], kbuf.at[slot, w], sem.at[0, slot]))
            op(pltpu.make_async_copy(
                v_hbm.at[e], vbuf.at[slot, w], sem.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(end - first, 0, W), place, None)

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    @pl.when(b == 0)
    def _():
        # a place no copy has filled yet meets p == 0 in P x V, and
        # 0 x whatever VMEM held is NaN where that was NaN; K needs no
        # such care, its stale columns are masked before the max
        vbuf[...] = jnp.zeros_like(vbuf)
        state[0] = 0  # the slot this row's first wave goes to
        state[1] = 0  # ... and whether the row before has started it

    slot0 = state[0]

    @pl.when(state[1] == 0)
    def _():
        each_live_place(b, slot0, b0, nb, start)

    nxt = jnp.minimum(b + 1, B - 1)
    nxt_b0, nxt_nb, _, _ = stream(nxt)
    nxt_nb = jnp.where(b + 1 < B, nxt_nb, 0)

    q = q_ref[...]  # [H, D], the compute dtype (the reference's own)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 0)
    own = (col % hkv) == (head // G)  # head h reads kv head h // G
    tok = col // hkv  # the column's token within the wave

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(slot0 + j, 2)
        # what streams in while this wave is computed on: the row's next
        # wave, or after its last the next row's first
        more = j + 1 < n_waves
        each_live_place(jnp.where(more, b, nxt), 1 - slot,
                        jnp.where(more, b0 + (j + 1) * W, nxt_b0),
                        jnp.where(more, nb, nxt_nb), start)
        first = b0 + j * W
        each_live_place(b, slot, first, nb, wait)
        s = jax.lax.dot_general(
            q, kbuf[slot].reshape(cols, D).astype(q.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, cols]
        # the single query sits at position L-1 and attends positions
        # < L (>= lo under a window); the final block is partially valid
        keep = own & (tok < L - first * bs)
        if window:
            keep &= tok >= lo - first * bs
        s = jnp.where(keep, s, -jnp.inf)
        # every wave run holds a live position, so m_new is finite
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), vbuf[slot].reshape(cols, D).astype(q.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((H, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_waves, body, (m0, l0, acc0))
    # L == 0 (idle slot): l stays 0 and the row emits zeros — finite
    # garbage the serve loop never reads
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # an idle row starts nothing for the row after it, which then starts
    # its own first wave, in the slot this one would have used
    state[0] = jax.lax.rem(slot0 + n_waves, 2)
    state[1] = (n_waves > 0).astype(jnp.int32)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    window: int = 0, ring: bool = False):
    """Attention over a block-paged KV pool (continuous LLM serving).

    Shapes as in :func:`paged_attention_reference`.  The Pallas kernel
    runs on TPU (or under ``interpret=True``) for the decode shape
    (T == 1) when head dim tiles the 128-lane DMA; prefill chunks
    (T > 1) and non-TPU backends take the reference path.  Per-row HBM
    traffic on the kernel path is ``ceil(context_len / block_size)``
    blocks — the reason paged decode scales with the sum of live
    sequence lengths instead of B x S_max; with ``window`` > 0 it is the
    blocks that intersect ``[p - window + 1, p]`` (``ring``: looked up
    through a ring table, :func:`paged_attention_reference`).  Products
    are in the query's dtype with float32 accumulation, the softmax
    statistics in float32: the reference's own precision.

    **Heads narrower than the 128 lanes** (64, 32, ...) go through the
    SAME kernel with ``pack`` KV heads to a lane row
    (:func:`kv_lane_pack`): the pool is stored ``[n_blocks, bs, hkv /
    pack, D * pack]`` — position t's row j holds KV heads ``j * pack .. j
    * pack + pack - 1`` side by side — and that is the pool the kernel is
    handed, ``hkv / pack`` packed heads wide.  A query head goes in as
    ``D * pack`` lanes that are zero but for the ``D`` its own KV head
    occupies in the packed row, so its score against the row is its score
    against that head alone; the kernel's rule for which columns a head
    keeps (``column % packed heads == head // packed group``) is then the
    right one unchanged, and ``P x V`` leaves each head ``pack`` results
    side by side of which the wrapper keeps its own.  Every K/V byte is
    read once, as at 128 lanes; the MXU multiplies the zero lanes, which
    it had to spare.  The kernel's result is ``[B, H, D * pack]``.  The
    pool's own layout decides, and nothing here repacks one: a pool of
    narrow heads stored one head to a row takes the reference on a chip,
    as before (``D % 128``).
    """
    B, T, H, D = q.shape
    n_blocks, bs, hkv_p, d_p = k_pool.shape
    pack = d_p // D                 # KV heads the pool holds to a row
    hkv = hkv_p * pack
    scale_v = (D ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = False
        if jax.default_backend() != "tpu":
            return paged_attention_reference(
                q, k_pool, v_pool, block_tables, context_lens, scale=scale_v,
                window=window, ring=ring)
    itemsize = k_pool.dtype.itemsize
    if pack > 1:
        if not paged_kernel_enabled() or T != 1 or H % hkv \
                or k_pool.shape != v_pool.shape:
            return paged_attention_reference(
                q, k_pool, v_pool, block_tables, context_lens, scale=scale_v,
                window=window, ring=ring)
        # query head h reads KV head h // G: lane group (h // G) % pack
        lane = (jnp.arange(H) // (H // hkv)) % pack
        own = (lane[:, None] == jnp.arange(pack)[None, :])[None, None, :, :,
                                                           None]
        out = paged_attention(
            jnp.where(own, q[:, :, :, None, :], 0).reshape(
                B, T, H, pack * D),
            k_pool, v_pool, block_tables, context_lens, scale=scale_v,
            interpret=interpret, window=window, ring=ring)
        return jnp.sum(jnp.where(own, out.reshape(B, T, H, pack, D), 0),
                       axis=3)
    if (
        not paged_kernel_enabled()  # TP traces need the shardable path
        or T != 1
        or H % hkv
        or k_pool.shape != v_pool.shape
        # Mosaic DMA tiling: lanes (the flash kernel's constraint), and a
        # block's rows must fill whole sublane tiles of the wave buffer
        or (not interpret and (D % 128 or (bs * hkv) % (32 // itemsize)))
    ):
        return paged_attention_reference(
            q, k_pool, v_pool, block_tables, context_lens, scale=scale_v,
            window=window, ring=ring)

    # sentinel entries must not index past the pool when a DMA is (never)
    # issued for them; clip on host side of the call
    tbl = jnp.clip(block_tables, 0, n_blocks - 1).astype(jnp.int32)
    wave = _paged_wave_blocks(bs, hkv, D, itemsize, int(window))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0)),
        # two slots of one wave each; scratch outlives a grid step
        scratch_shapes=[
            pltpu.VMEM((2, wave, bs * hkv, D), k_pool.dtype),
            pltpu.VMEM((2, wave, bs * hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    # [n_blocks, bs, hkv, D] is row-major [n_blocks, bs * hkv, D]: a
    # bitcast, the pool is not moved
    flat = (n_blocks, bs * hkv, D)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale_v, hkv=hkv,
                          window=int(window),
                          ring=block_tables.shape[1] if ring else 0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        # a row's last wave overlaps the next row's first: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(tbl, context_lens.astype(jnp.int32), q[:, 0],
      k_pool.reshape(flat), v_pool.reshape(flat))
    return out[:, None]


# ---------------------------------------------------------------------------
# Paged latent attention — decode over a pool whose value is a prefix of
# its key
# ---------------------------------------------------------------------------

def latent_pool_width(width: int) -> int:
    """Columns a latent pool's row takes for ``width`` cached values: the
    next multiple of the 128 lanes a DMA and an MXU operand tile by (576
    -> 640).  The columns past ``width`` are zero for good — the pool is
    born zero and every write pads with zeros — so they add nothing to a
    score; they are the padding the kernel pays for in bytes."""
    return -(-int(width) // 128) * 128


def paged_latent_attention_reference(q, pool, block_tables, context_lens, *,
                                     v_width: int,
                                     scale: Optional[float] = None):
    """Plain-XLA attention in the latent space (the kernel's semantics,
    materialized).

    ``q``: [B, T, H, Dq] — per head the query already multiplied into the
    latent space, then its rotated part (``q~ | q_R``); ``pool``:
    [n_blocks, block_size, W] with ``W >= Dq``: a cached token's row is
    its normed latent, then the one rotated key all heads share, then
    zeros (:func:`latent_pool_width`).  Every head scores against the
    SAME row (one "KV head"), and the value is the row's first
    ``v_width`` columns.  ``block_tables``/``context_lens`` as in
    :func:`paged_attention_reference`.  Returns [B, T, H, v_width]."""
    B, T, H, Dq = q.shape
    n_blocks, bs, _ = pool.shape
    scale_v = (Dq ** -0.5) if scale is None else scale
    dt = q.dtype
    tbl = jnp.clip(block_tables, 0, n_blocks - 1)
    rows = pool[tbl].reshape(B, -1, pool.shape[-1])[..., :Dq].astype(dt)
    q_pos = (context_lens[:, None] - T) + jnp.arange(T)[None, :]  # [B, T]
    mask = jnp.arange(rows.shape[1])[None, None, :] <= q_pos[:, :, None]
    s = jnp.einsum("bqhd,bkd->bhqk", q, rows,
                   preferred_element_type=jnp.float32) * scale_v
    s = jnp.where(mask[:, None], s, jnp.float32(-1e30))
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkd->bqhd", p.astype(dt), rows[..., :v_width])


def _latent_wave_blocks(bs: int) -> int:
    """Blocks in flight per buffer slot: ~512 score columns a wave, never
    more than 32 places (2 slots x 32 x 20 KB of VMEM at block 16, width
    640).  Twice the K/V kernel's wave: a full wave costs one wait and no
    loop (:func:`_paged_latent_kernel`), so the longer wave halves what
    the scalar core spends a cached token; on the chip the cell's mix of
    contexts reads 151 us a call against 160 at 16 places and the slope
    0.150 us a token of context against 0.201."""
    return max(1, min(512 // bs, 32))


def _paged_latent_kernel(tbl_ref, len_ref, q_ref, c_hbm, o_ref, cbuf, sem,
                         state, *, scale: float):
    """One stream (batch row) per grid cell, as :func:`_paged_kernel`:
    blocks stream in waves of ``W = cbuf.shape[1]`` through two VMEM
    slots, a row's last wave overlapping the next row's first, and an
    idle row (length 0) fetches nothing.

    What differs: ONE pool.  A block is ``[bs, Wd]`` and every query head
    reads every row of it, so a wave's scores are the plain product
    ``q[H, Wd] x C[W * bs, Wd]^T`` with no column masked by head, and the
    value is the same buffer's first ``Dv`` columns: the second product
    ``P[H, W * bs] x C[:, :Dv]`` reads what the first one's DMA brought."""
    H, Wd = q_ref.shape
    Dv = o_ref.shape[-1]
    _, W, bs, _ = cbuf.shape
    cols = W * bs
    b = pl.program_id(0)
    B = pl.num_programs(0)

    def stream(row):
        L = len_ref[row]
        return (L + bs - 1) // bs, L  # live blocks only

    nb, L = stream(b)
    n_waves = (nb + W - 1) // W  # 0 for an idle row

    def copy(row, slot, first, w):
        return pltpu.make_async_copy(
            c_hbm.at[tbl_ref[row, first + w]], cbuf.at[slot, w], sem.at[slot])

    def each_live_place(row, slot, first, end, op):
        def place(w, carry):
            op(copy(row, slot, first, w))
            return carry

        jax.lax.fori_loop(0, jnp.clip(end - first, 0, W), place, None)

    # The scalar core issues every copy and every wait, in blocks of code
    # of their own between the products, so none of it overlaps them:
    # timed alone, a call's copies took as long as its arithmetic and the
    # two ADDED.  So a FULL wave (all but a row's last) takes the short
    # way: its W starts unrolled, with no loop and no bound to clip, and
    # ONE wait for the whole slot's bytes, which is what W copies on one
    # semaphore add up to.
    def start(row, slot, first, end):
        full = first + W <= end

        @pl.when(full)
        def _():
            for w in range(W):
                copy(row, slot, first, w).start()

        @pl.when(jnp.logical_not(full))
        def _():
            each_live_place(row, slot, first, end, lambda c: c.start())

    def wait(row, slot, first, end):
        full = first + W <= end

        @pl.when(full)
        def _():
            # a descriptor of the slot's shape: only its byte count is read
            pltpu.make_async_copy(c_hbm.at[pl.ds(0, W)], cbuf.at[slot],
                                  sem.at[slot]).wait()

        @pl.when(jnp.logical_not(full))
        def _():
            each_live_place(row, slot, first, end, lambda c: c.wait())

    @pl.when(b == 0)
    def _():
        # a place no copy has filled yet meets p == 0 in P x V, and
        # 0 x whatever VMEM held is NaN where that was NaN
        cbuf[...] = jnp.zeros_like(cbuf)
        state[0] = 0  # the slot this row's first wave goes to
        state[1] = 0  # ... and whether the row before has started it

    slot0 = state[0]

    @pl.when(state[1] == 0)
    def _():
        start(b, slot0, 0, nb)

    nxt = jnp.minimum(b + 1, B - 1)
    nxt_nb, _ = stream(nxt)
    nxt_nb = jnp.where(b + 1 < B, nxt_nb, 0)

    q = q_ref[...]  # [H, Wd], the compute dtype
    tok = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(slot0 + j, 2)
        more = j + 1 < n_waves
        start(jnp.where(more, b, nxt), 1 - slot,
              jnp.where(more, (j + 1) * W, 0), jnp.where(more, nb, nxt_nb))
        first = j * W
        wait(b, slot, first, nb)
        rows = cbuf[slot].reshape(cols, Wd).astype(q.dtype)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, cols]
        s = jnp.where(tok < L - first * bs, s, -jnp.inf)
        # every wave run holds a live position, so m_new is finite
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), rows[:, :Dv],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((H, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, Dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_waves, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    state[0] = jax.lax.rem(slot0 + n_waves, 2)
    state[1] = (n_waves > 0).astype(jnp.int32)


def paged_latent_attention(q, pool, block_tables, context_lens, *,
                           v_width: int, scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Decode attention over a block-paged LATENT pool (shapes as in
    :func:`paged_latent_attention_reference`): per cached token one row of
    ``Dq`` values that is the key of every head and, in its first
    ``v_width`` columns, the value of every head.

    The Pallas kernel runs on TPU (or under ``interpret=True``) for the
    decode shape (T == 1) over a pool whose rows are lane-padded
    (:func:`latent_pool_width`); prefill chunks (T > 1) and non-TPU
    backends take the reference.  Per-row HBM traffic is
    ``ceil(context_len / block_size)`` blocks, each read ONCE for both
    products.  Products in the query's dtype with float32 accumulation,
    softmax statistics in float32."""
    B, T, H, Dq = q.shape
    n_blocks, bs, Wd = pool.shape
    scale_v = (Dq ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = False
        if jax.default_backend() != "tpu":
            return paged_latent_attention_reference(
                q, pool, block_tables, context_lens, v_width=v_width,
                scale=scale_v)
    if (
        not paged_kernel_enabled()
        or T != 1
        or (not interpret and (Wd % 128 or v_width % 128
                               or bs % (32 // pool.dtype.itemsize)))
    ):
        return paged_latent_attention_reference(
            q, pool, block_tables, context_lens, v_width=v_width,
            scale=scale_v)

    tbl = jnp.clip(block_tables, 0, n_blocks - 1).astype(jnp.int32)
    # a full wave's wait is sized as a slice of the pool: never wider
    wave = min(_latent_wave_blocks(bs), n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, H, Wd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((None, H, v_width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, wave, bs, Wd), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    # the query's columns past Dq meet the pool's zero padding
    qp = jnp.pad(q[:, 0], ((0, 0), (0, 0), (0, Wd - Dq)))
    out = pl.pallas_call(
        functools.partial(_paged_latent_kernel, scale=scale_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        # a row's last wave overlaps the next row's first: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(tbl, context_lens.astype(jnp.int32), qp, pool)
    return out[:, None]
