"""The selective state-space (Mamba-1) decode step's state update as one
Pallas TPU kernel, and its ``jax.numpy`` twin.

One decode step of a state-space layer, for each row ``b`` of the batch,
reads its slot's state ``s`` ``[N, E]`` (float32: ``N`` the state size, the
``E`` channels on the lanes), and with the row's inputs — the convolved
``x`` and the gate ``z`` ``[E]``, the step size ``dt`` ``[E]`` (float32,
after the softplus), ``b`` and ``c`` ``[N]`` — computes::

    s'  = exp(dt * a) * s + (dt * x) (x) b      # a = -exp(A_log), [N, E]
    y   = (sum_n s'[n] * c[n] + d * x) * silu(z)

and writes ``s'`` back over ``s`` (in the leaf's own type: float32 as the
serving path allocates it; the arithmetic is float32 whatever it is).
Under XLA the update and the reduction over ``N`` are free to become two
fusions that each read the state; the kernel reads a slot's tile once,
applies the discretisation, the update, the ``c`` contraction, the ``d``
skip and the ``z`` gate in one pass, and writes the tile back in place
(``input_output_aliases``): at 64 rows of the benchmark's state-space
cell, 41.9 MB a layer a call.

**Which rows write.**  A row that is not ``live`` (a parked slot, or one in
the middle of its next stream's prefill) writes nothing and returns zeros;
a row whose slot id lies outside the leaf names no stream's state: it
returns NaN (every logit of the row shows it) and writes nothing.  A
``fresh`` live row (a stream's first token at position 0) reads zeros in
place of whatever the slot's last stream left.  The live rows of one call
name distinct slots (the serve loop's rows are its slots).

The grid walks the rows in order, the state's block chosen by the row's
slot (scalar-prefetched).  A row that writes nothing is handed the block of
the nearest row that does, so that it neither fetches a tile nor writes one
back: a pipelined block is written back only when the next step names
another, and the first step of a run of one block copies the tile it read
into the result where no row of the run updates it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the ``pallas_call``'s name: what a device trace shows the kernel under
KERNEL = "ssm_update"


def ssm_update_reference(state, layer, slots, live, fresh, x, dt, z, b, c,
                         a, d):
    """The update in ``jax.numpy`` (the kernel's twin; the path off the
    chip).  ``state`` ``[layers, slots, N, E]`` float32, ``layer`` a traced
    int32 scalar, ``slots``/``live``/``fresh`` ``[B]``, ``x``, ``z`` ``[B,
    E]`` in the compute dtype, ``dt`` ``[B, E]`` and ``b``, ``c`` ``[B, N]``
    float32, ``a`` ``[N, E]`` and ``d`` ``[E]`` float32 -> ``(y [B, E]`` in
    ``x``'s dtype, the state)."""
    L, S, N, E = state.shape
    ok = (slots >= 0) & (slots < S)
    write = live & ok
    flat = state.reshape(L * S, N, E)
    rows = jnp.where(ok, layer * S + slots, L * S)
    s0 = flat.at[rows].get(mode="fill", fill_value=0.0).astype(jnp.float32)
    s0 = jnp.where(fresh[:, None, None], 0.0, s0)
    x32 = x.astype(jnp.float32)
    s = jnp.exp(dt[:, None, :] * a[None]) * s0 \
        + (dt * x32)[:, None, :] * b[:, :, None]
    y = jnp.sum(s * c[:, :, None], axis=1) + d * x32
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = jnp.where(write[:, None], y, 0.0)
    y = jnp.where(ok[:, None], y, jnp.nan)
    flat = flat.at[jnp.where(write, rows, L * S)].set(s.astype(flat.dtype),
                                                      mode="drop")
    return y.astype(x.dtype), flat.reshape(state.shape)


def _kernel(blk_ref, flag_ref, layer_ref, x_ref, dt_ref, z_ref, b_ref,
            c_ref, a_ref, d_ref, s_ref, y_ref, o_ref):
    """One row: ``s_ref``/``o_ref`` its slot's ``[N, E]`` tile, read and
    written back; ``flag_ref[row]`` bit 0 live, bit 1 fresh, bit 2 the
    first step of a run of one block."""
    flag = flag_ref[pl.program_id(0)]
    live = (flag & 1) != 0
    fresh = (flag & 2) != 0
    first = (flag & 4) != 0

    def update(s0):
        x = x_ref[...].astype(jnp.float32)          # [1, E]
        dt = dt_ref[...]                            # [1, E]
        s = jnp.exp(dt * a_ref[...]) * s0 + (dt * x) * b_ref[...]
        o_ref[...] = s.astype(o_ref.dtype)
        y = jnp.sum(s * c_ref[...], axis=0, keepdims=True) + d_ref[...] * x
        z = z_ref[...].astype(jnp.float32)
        y_ref[...] = (y * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)

    @pl.when(live & fresh)
    def _():
        update(jnp.zeros(s_ref.shape, jnp.float32))

    @pl.when(live & jnp.logical_not(fresh))
    def _():
        update(s_ref[...].astype(jnp.float32))

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(jnp.logical_not(live) & first)
    def _():
        o_ref[...] = s_ref[...]


def ssm_update(state, layer, slots, live, fresh, x, dt, z, b, c, a, d, *,
               interpret: Optional[bool] = None):
    """The update of :func:`ssm_update_reference` (same arguments, same
    result): the Pallas kernel on a TPU or under ``interpret=True``, the
    twin on other backends unless asked."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return ssm_update_reference(state, layer, slots, live, fresh, x,
                                        dt, z, b, c, a, d)
        interpret = False
    L, S, N, E = state.shape
    B = x.shape[0]
    ok = (slots >= 0) & (slots < S)
    write = live & ok
    ids = jnp.clip(slots, 0, S - 1).astype(jnp.int32)
    # a row that writes nothing takes the block of the last row before it
    # that does (of the first that does, before any)
    prev = lax.cummax(jnp.where(write, jnp.arange(B), -1))
    blk = ids[jnp.where(prev >= 0, prev, jnp.argmax(write))]
    first = jnp.concatenate([jnp.ones((1,), bool), blk[1:] != blk[:-1]])
    flag = (write.astype(jnp.int32) | (fresh & write).astype(jnp.int32) << 1
            | first.astype(jnp.int32) << 2)
    lay = jnp.reshape(layer, (1,)).astype(jnp.int32)

    row = pl.BlockSpec((None, 1, E), lambda i, *_: (i, 0, 0))
    col = pl.BlockSpec((None, N, 1), lambda i, *_: (i, 0, 0))
    tile = pl.BlockSpec((None, None, N, E),
                        lambda i, blk, flag, lay: (lay[0], blk[i], 0, 0))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row, row, row, col, col,
                      pl.BlockSpec((N, E), lambda i, *_: (0, 0)),
                      pl.BlockSpec((1, E), lambda i, *_: (0, 0)),
                      tile],
            out_specs=[row, tile]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, E), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (argument 10, after the three prefetched scalars)
        # is updated where it lies
        input_output_aliases={10: 1},
        # the steps in order: a run of one block spans consecutive rows
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL,
    )(blk, flag, lay, x[:, None], dt.astype(jnp.float32)[:, None],
      z[:, None], b.astype(jnp.float32)[:, :, None],
      c.astype(jnp.float32)[:, :, None], a.astype(jnp.float32),
      d.astype(jnp.float32)[None], state)
    y = jnp.where(ok[:, None], y[:, 0], jnp.nan)
    return y, state

