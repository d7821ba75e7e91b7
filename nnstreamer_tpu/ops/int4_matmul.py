"""Weight-only int4 (w4a16) matmul as a Pallas TPU kernel.

Why a kernel: the 7B decode step is bound by streaming the weights from
HBM, so bytes/token is the lever.
Nibble-packing weights halves bytes, but XLA cannot consume a packed
buffer in one pass — the natural two-dot formulation fuses each nibble's
unpack into its own dot and reads every packed byte TWICE (measured
271 GB/s effective = no win over int8).  The kernel streams each packed
block through VMEM once and runs both MXU dots against the resident
block.

Mosaic on this backend legalizes NO i8 vector arithmetic (arith.shli/
subi on i8 fail) and materializes i32 temporaries in VMEM, so the
unpack must be cheap in i32 ops.  The packing is chosen to need exactly
two: with byte ``t = 16*hi + (lo+8)`` (hi signed [-8,7] in the high
nibble, lo stored BIASED unsigned in the low nibble),

    M := t & 15          = lo + 8        (1 i32 op)
    T := t (sign-extend) = 16*hi + M

so   W_lo = M - 8  and  W_hi = (T - M) / 16, and the matmul

    y = h_lo @ W_lo + h_hi @ W_hi
      = (h_lo - h_hi/16) @ M  +  (h_hi/16) @ T  -  8 * rowsum(h_lo)

moves ALL the correction arithmetic to the tiny activation side
(computed in XLA outside the kernel): per packed byte the kernel does
one extend, one mask, and two converts, then two MXU dots.  Measured
422 GB/s effective on chip (86% of the measured read limit) = 7.7
ms/token at 7B vs 12.9 for int8.  The ``h_lo - h_hi/16`` mix rounds in
bf16 (~0.6% output rel err, well under int4's ~3% per-weight
quantization noise).

Reference analog: llama.cpp's Q4 weight blocks
(tensor_filter_llamacpp.cc, SURVEY §2.4 [UNVERIFIED]) — its entire
reason to exist is fast quantized decode on the host; this is the
TPU-native counterpart.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Kernel applies only to decode-shaped activations: at large B*T the
#: f32 accumulator [B, F] would blow VMEM, and prefill amortizes weight
#: reads anyway, so the XLA reference path is the right tool there.
_MAX_KERNEL_ROWS = 32

# pallas_call has no GSPMD partitioning rule, so a program traced for a
# sharded (tensor-parallel) mesh must use the shardable XLA reference
# path instead — sharding is invisible at trace time, so the caller that
# builds TP programs (filters/llm.py) disables the kernel for the
# lifetime of its filter.  REFCOUNTED, not a bare flag: two concurrent
# TP filters must not clobber each other's save/restore, and a filter
# that dies mid-open must not leak a disabled kernel process-wide.
import threading as _threading

_disable_lock = _threading.Lock()
_disable_count = 0


def disable_kernel() -> None:
    global _disable_count
    with _disable_lock:
        _disable_count += 1


def enable_kernel() -> None:
    global _disable_count
    with _disable_lock:
        _disable_count = max(0, _disable_count - 1)


def kernel_enabled() -> bool:
    return _disable_count == 0


def pack_int4(wq):
    """[Din, F] int8 values in [-8, 7] -> [Din/2, F] packed int8.

    Split-halves layout: logical rows 0:Din/2 land in the LOW nibble
    (stored biased, +8), rows Din/2:Din in the HIGH nibble (signed) —
    no interleave, so the activation splits into two contiguous halves.
    """
    d = wq.shape[0]
    if d % 2:
        raise ValueError(f"contraction dim must be even, got {d}")
    lo = wq[: d // 2].astype(jnp.int32)
    hi = wq[d // 2:].astype(jnp.int32)
    return (((hi & 0xF) << 4) | ((lo + 8) & 0xF)).astype(jnp.int8)


def unpack_int4(packed):
    """Inverse of :func:`pack_int4` -> [Din, F] int8 in [-8, 7]."""
    t32 = packed.astype(jnp.int32)
    lo = (t32 & 15) - 8
    hi = jax.lax.shift_right_arithmetic(t32, 4)
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)


def quantize_int4(w):
    """[Din, F] float -> (packed [Din/2, F] int8, scale [1, F] f32).

    Symmetric per-output-channel: q = round(w/s) clipped to [-7, 7]
    (the -8 code is left unused so the grid stays symmetric)."""
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.abs(w32).max(axis=0, keepdims=True) / 7.0, 1e-8)
    q = jnp.clip(jnp.round(w32 / s), -7, 7).astype(jnp.int8)
    return pack_int4(q), s


def matmul_int4_reference(h, packed, scale, out_dtype=None):
    """Plain-XLA semantics of the kernel: shardable under GSPMD (the TP
    path) and the right choice for prefill (reads packed bytes twice,
    which amortizes over many rows)."""
    d2 = packed.shape[0]
    dt = h.dtype
    t32 = packed.astype(jnp.int32)
    lo = ((t32 & 15) - 8).astype(dt)
    hi = jax.lax.shift_right_arithmetic(t32, 4).astype(dt)
    y = h[..., :d2] @ lo + h[..., d2:] @ hi
    return (y.astype(jnp.float32) * scale).astype(out_dtype or dt)


def _int4_kernel(ha_ref, hb_ref, p_ref, s_ref, o_ref, acc_ref):
    """One (F-block, contraction-block) grid step: two i32 VPU ops + two
    converts per packed byte, both nibble dots against the resident
    block.  Grid dim 0 tiles F (VMEM-bounded — a [B, 32000] f32
    accumulator plus unpack temps blew the 16 MB budget at B=32); dim 1
    walks the contraction, accumulating in the revisited scratch."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t32 = p_ref[...].astype(jnp.int32)
    dt = ha_ref.dtype
    M = (t32 & 15).astype(dt)   # lo + 8
    T = t32.astype(dt)          # 16*hi + lo + 8
    acc_ref[...] += (
        jnp.dot(ha_ref[...], M, preferred_element_type=jnp.float32)
        + jnp.dot(hb_ref[...], T, preferred_element_type=jnp.float32))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _pick_fb(F: int, B: int, block_d2: int) -> int:
    """Largest 128-multiple divisor of F whose per-block VMEM footprint
    fits; 0 if none.  Calibrated against Mosaic's observed scoped-vmem
    accounting for the 2-D grid: packed int8 block x2 pipeline buffers
    PLUS one materialized bf16 nibble plane (bd*fb*4 total) plus the
    f32 accumulator / output blocks.  Observed anchors: [512, 11008]
    full-F at B=1 OOM'd at 21.95 MB (bd*fb*4 = 22.5 MB -> must split);
    [128, 32000] at B=32 OOM'd at 19.2 MB; the split shapes compile."""
    budget = 14 << 20
    per_elem = block_d2 * 4 + B * 6
    fb_max = budget // per_elem  # no floor: fb=0 -> caller falls back
    best = 0
    for fb in range(128, F + 1, 128):
        if F % fb == 0 and fb <= fb_max:
            best = fb
    return best


def _pick_blocks(d2: int, F: int, B: int, block_d2):
    """(block_d2, fb) for the kernel grid.  Bigger contraction blocks
    amortize per-grid-step cost — measured 2x mat throughput at B=16
    for 512 vs 128 — so auto mode takes the largest of 512/256/128 that
    divides d2 and still leaves a VMEM-fitting F block."""
    cands = (block_d2,) if block_d2 else (512, 256, 128)
    for bd in cands:
        if d2 % bd == 0:
            fb = _pick_fb(F, B, bd)
            if fb:
                return bd, fb
    return 0, 0


def matmul_int4(h, packed, scale, *, block_d2: Optional[int] = None,
                interpret: Optional[bool] = None, out_dtype=None):
    """``h @ unpack(packed) * scale`` -> [B, F] in ``out_dtype``
    (default ``h.dtype``).

    h: [B, Din] (bf16/f32); packed: [Din/2, F] int8 (:func:`pack_int4`
    layout); scale: [1, F] f32.  Uses the Pallas kernel on TPU for
    decode-shaped B (or anywhere with ``interpret=True``); other
    backends, large B, non-tiling shapes, and refcount-disabled kernel
    states (TP traces, :func:`disable_kernel`) get
    :func:`matmul_int4_reference`.
    """
    B, din = h.shape
    d2, F = packed.shape
    if din != 2 * d2:
        raise ValueError(f"h dim {din} != 2 * packed rows {d2}")
    odt = out_dtype or h.dtype
    if interpret is None:
        interpret = False
        if jax.default_backend() != "tpu":
            return matmul_int4_reference(h, packed, scale, out_dtype=odt)
    bd, fb = _pick_blocks(d2, F, B, block_d2)  # (0, 0) -> fall back
    if not kernel_enabled() or not fb or B > _MAX_KERNEL_ROWS:
        return matmul_int4_reference(h, packed, scale, out_dtype=odt)

    hlo, hhi = h[:, :d2], h[:, d2:]
    hb = (hhi.astype(jnp.float32) * 0.0625).astype(h.dtype)
    ha = hlo - hb
    out = pl.pallas_call(
        _int4_kernel,
        grid=(F // fb, d2 // bd),
        in_specs=[
            pl.BlockSpec((B, bd), lambda i, j: (0, j)),   # h_lo - h_hi/16
            pl.BlockSpec((B, bd), lambda i, j: (0, j)),   # h_hi / 16
            pl.BlockSpec((bd, fb), lambda i, j: (j, i)),  # packed block
            pl.BlockSpec((1, fb), lambda i, j: (0, i)),   # scales
        ],
        out_specs=pl.BlockSpec((B, fb), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, F), odt),
        scratch_shapes=[pltpu.VMEM((B, fb), jnp.float32)],
        interpret=interpret,
        name="int4_matmul",
    )(ha, hb, packed, scale)
    # the -8 * rowsum(h_lo) bias correction, applied at full precision
    # outside the kernel (a [B,1] x [1,F] outer product is negligible)
    bias = -8.0 * jnp.sum(hlo.astype(jnp.float32), axis=1, keepdims=True)
    return out + (bias * scale).astype(out.dtype)
