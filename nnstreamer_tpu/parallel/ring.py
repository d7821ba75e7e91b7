"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

The reference has **no** sequence parallelism (SURVEY §2.9 — its nearest
analog is ``tensor_aggregator`` windowing); long context is first-class
here.  Design: blockwise attention with an online (flash-style) softmax,
where K/V blocks rotate around the ring of ``seq``-axis devices via
``lax.ppermute`` while every device keeps its resident Q shard.  Each hop
overlaps the collective with the local block matmul, so the ICI transfer
hides behind MXU work — the standard TPU ring-attention recipe (Liu et al.,
"Ring Attention with Blockwise Transformers"; see PAPERS.md).

Shapes (per device, inside ``shard_map``): q/k/v ``[B, T_local, H, D]``.
Global sequence length = ``T_local * mesh.shape['seq']``.  Causal masking
uses global token positions derived from ``lax.axis_index('seq')``.

Public entry points:

* :func:`ring_attention` — host-level: shard_map'd over a mesh.
* :func:`ring_attention_local` — the per-device body (usable inside a
  larger shard_map'd transformer like models/llama.py).
"""

from __future__ import annotations

import functools
from typing import Optional


def _block_attn(q, k, v, mask, scale):
    """One (q-shard × kv-block) attention piece with stable running stats.

    Returns (o_unnorm, m, l): unnormalized weighted values, running rowmax,
    running denominator — the flash-attention accumulator triple.
    """
    import jax.numpy as jnp

    # [B, H, Tq, Tk] scores in f32 for numerical stability.
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    # Guard fully-masked rows (m = -inf) -> exp(0)=1 rows scaled to 0 by l.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def ring_attention_local(q, k, v, *, axis_name: str = "seq",
                         causal: bool = True, scale: Optional[float] = None):
    """Per-device ring attention body. Call inside shard_map/pmap.

    q,k,v: ``[B, T_local, H, D]`` shards along the sequence axis.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, Tl, H, D = q.shape
    scale = scale if scale is not None else (1.0 / (D ** 0.5))
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)

    q_pos = my * Tl + jnp.arange(Tl)  # global positions of resident Q rows

    def make_mask(kv_chunk):
        if not causal:
            return None
        k_pos = kv_chunk * Tl + jnp.arange(Tl)
        # [Tq, Tk] -> broadcast to [B,H,Tq,Tk]
        return (q_pos[:, None] >= k_pos[None, :])[None, None]

    def step(carry, _):
        k_blk, v_blk, kv_chunk, o_acc, m_acc, l_acc = carry
        o, m, l = _block_attn(q, k_blk, v_blk, make_mask(kv_chunk), scale)
        # Merge running stats (flash-attention combine).  Guards: a fully
        # masked accumulator/block has m = -inf; exp(-inf - -inf) would be
        # NaN, so rescale factors collapse to 0 for -inf sources.
        m_new = jnp.maximum(m_acc, m)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        a = jnp.where(jnp.isfinite(m_acc), jnp.exp(m_acc - m_safe), 0.0)
        b = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l_acc * a + l * b
        o_new = (o_acc * a[..., None].transpose(0, 2, 1, 3)
                 + o * b[..., None].transpose(0, 2, 1, 3))
        # Rotate K/V to the next device on the ring (ICI neighbor hop).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        kv_nxt = (kv_chunk - 1) % n
        return (k_nxt, v_nxt, kv_nxt, o_new, m_new, l_new), None

    o0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    carry = (k, v, my, o0, m0, l0)
    carry, _ = lax.scan(step, carry, None, length=n)
    _, _, _, o, m, l = carry
    l = jnp.maximum(l, 1e-20)
    out = o / l[..., None].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_attention(mesh, q, k, v, *, causal: bool = True,
                   scale: Optional[float] = None):
    """Host-level ring attention over ``mesh``'s ``seq`` axis.

    Inputs are global ``[B, T, H, D]`` arrays (host or device); output is the
    exact full attention result, computed without any device ever holding
    more than ``T / seq_size`` keys.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P(None, "seq", None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention_local, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)(q, k, v)
