"""Grouped SwiGLU feed-forward: every row through ITS group's three matrices.

``xs`` [M, D] holds rows sorted by group; ``groups`` [G] says how many
rows each group owns, in order (rows past their sum belong to none).  For
the rows of group ``g``

    y = (silu(x @ we_gate[g]) * (x @ we_up[g])) @ we_down[g]

This is the routed-expert product of ``models/moe.py``: a group is an
expert, a row a routed (token, expert) pair.  Group sizes are VALUES; the
shapes, and so the compiled program, do not depend on them.

Two implementations, one public function (:func:`grouped_swiglu`) that
picks between them from the backend and the static shapes alone:

* :func:`grouped_swiglu_reference` — three ``jax.lax.ragged_dot`` calls.
  On a TPU, XLA lowers each to a grouped matmul whose row tile it derives
  from the static row count M (``ragged_dot_tiling`` in the compiled
  text: 512 rows at M = 512, 256 at M = 768 or 256, 128 at M = 384) and a
  visit of a group multiplies that WHOLE tile by the group's matrix.
* the Pallas kernel ``ragged-dot-swiglu`` — row tiles of ``ROW_TILE``
  rows, the three matrices of a group streamed ONCE per (group, row tile)
  visit in tiles that are contiguous in HBM, the gate/up results kept in
  VMEM (float32 accumulators; the activation is rounded to the operand
  type once, before the down product, as the reference rounds it).

**Which, and why (v5e: 197 TFLOP/s, 819 GB/s).**  A group's three bf16
matrices at D x F = 6144 x 2048 are 75.5 MB: 92 us to stream.  A visit's
MXU time is that of ``max(tile rows, 128)`` rows — under 128 rows a
128 x 128 weight tile still has to be loaded, so it costs the same — i.e.
3 x 2 x rows x D x F / 197e12: 49 us at <= 128 rows, 98 us at 256, 196 us
at 512.  The two are equal at ~240 rows.  A decode step routes a handful
of rows to a group (4 and 1 in the benchmark's two sparse cells), so at
XLA's tile the product is bound by the MXU multiplying padding (4 useful
rows in 512: 41 % of the HBM roofline, PERF.md section 5), and at 128
rows or fewer it is bound by the stream.  With hundreds of rows a group
(a long prefill chunk of a model that holds all its experts) the big
tile is the right one: each matrix is read once and the MXU is fed whole
tiles.  Hence the rule: the kernel where a group EXPECTS at most
``MAX_EXPECTED_ROWS`` rows (``expect``, a static number the caller
derives from its shapes: rows / router outputs), the reference
otherwise — one algorithm, a row tile that fits the row count, the count
visible in the shape.

**Dropless.**  A group larger than the row tile is walked in several
visits, its matrices streamed again for each.  A group of size 0 is in no
visit and costs no DMA.  The grid is ``(visits, stream steps)`` with a
STATIC number of visits, ``live + M / ROW_TILE - 1``: at most ``live``
groups are non-empty (a promise of the caller's: ``models/moe.py`` writes
one layer's ``held`` sizes into a stack-wide vector of zeros), and rows
sorted by group cross each of the ``M / ROW_TILE - 1`` tile boundaries at
most once.  Steps past the last real visit repeat its block indices, so
they move no data and compute nothing.

**The weights are reached through the index map**, never through a
slice: the caller hands over a kind's whole stack viewed as ``n * held``
groups (models/moe.py says why) and the map picks ``we_*[group of the
visit]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a visit: the MXU's own height.  Fewer rows make a visit no
#: cheaper (see the module docstring), more make it dearer, and the
#: larger the tile the fewer groups straddle a boundary
ROW_TILE = 128
#: the kernel where a group expects at most this many rows: an eighth of
#: the ~240 at which a visit's MXU time passes its streaming time
MAX_EXPECTED_ROWS = 32
#: weight bytes a stream step moves at most (a tile of gate and one of
#: up, or one of down; as many again are in flight under it).  Measured
#: alone on a v5e (PERF.md section 5): [1024, 2048] x 2 and [512, 6144]
#: tiles, 8.4 and 6.3 MB a step, stream an expert in 100.9 us against
#: 99.2 us for the copies with no product; smaller tiles pay more steps,
#: larger ones (1536 rows) 102.8 us
_STEP_BYTES = 8 << 20
_VMEM_LIMIT = 96 << 20


def grouped_swiglu_reference(xs, we_gate, we_up, we_down, groups):
    """Three grouped products, XLA's own (the kernel's semantics)."""
    dt = xs.dtype
    gate = jax.lax.ragged_dot(xs, we_gate, groups)
    up = jax.lax.ragged_dot(xs, we_up, groups)
    return jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(dt), we_down,
                              groups, preferred_element_type=jnp.float32)


def _stream_tile(rows: int, row_bytes: int) -> int:
    """Rows a stream step takes of a ``[rows, ...]`` weight whose row (of
    every matrix the step reads) is ``row_bytes``: the most that divide
    ``rows``, are whole lane tiles and stay within ``_STEP_BYTES``."""
    best = 128
    for t in range(128, rows + 1, 128):
        if rows % t == 0 and t * row_bytes <= _STEP_BYTES:
            best = t
    return best


def visits(groups, rows: int, tile: int, n_visits: int):
    """The (group, row tile) pairs a grouped product over ``rows`` rows
    has to make, in order, as ``n_visits`` entries (a static bound):
    ``(group, tile, first row, one past the last row)`` of each visit and
    how many are real.  Entries past the real ones repeat the last."""
    G = groups.shape[0]
    groups = groups.astype(jnp.int32)
    ends = jnp.cumsum(groups)
    starts = ends - groups
    first = starts // tile
    count = jnp.where(groups > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(count)           # visits of groups 0..g
    real = jnp.minimum(upto[-1], n_visits)
    v = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32),
                    jnp.maximum(real - 1, 0))
    g = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1), G - 1)
    t = jnp.minimum(first[g] + v - (upto[g] - count[g]), rows // tile - 1)
    return (g.astype(jnp.int32), t.astype(jnp.int32), starts[g], ends[g],
            real.astype(jnp.int32))


def _swiglu_kernel(vg, vt, lo, hi, real, x_ref, wg_ref, wu_ref, wd_ref,
                   o_ref, gate, up, act, *, nk: int):
    """Grid cell (visit v, stream step s).  Steps ``0..nk-1`` add a
    ``[tk, F]`` tile of gate and of up to the float32 accumulators; the
    last of them turns the accumulators into the activation, rows of
    other groups zeroed; steps ``nk..`` add a ``[tf, D]`` tile of down
    to the row tile's output block, which stays in VMEM for as long as
    consecutive visits share the row tile."""
    v, s = pl.program_id(0), pl.program_id(1)
    tm = x_ref.shape[0]
    nf, _, tf = act.shape

    @pl.when(v < real[0])
    def _():
        @pl.when((s == 0) & ((v == 0) | (vt[v] != vt[jnp.maximum(v - 1, 0)])))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(s < nk)
        def _():
            x = x_ref[...]
            g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)

            @pl.when(s == 0)
            def _():
                gate[...] = g
                up[...] = u

            @pl.when(s > 0)
            def _():
                gate[...] += g
                up[...] += u

        @pl.when(s == nk - 1)
        def _():
            row = vt[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, 1), 0)
            mine = (row >= lo[v]) & (row < hi[v])
            a = jnp.where(mine, jax.nn.silu(gate[...]) * up[...], 0.0)
            for j in range(nf):
                act[j] = a[:, j * tf:(j + 1) * tf].astype(act.dtype)

        @pl.when(s >= nk)
        def _():
            o_ref[...] += jnp.dot(act[s - nk], wd_ref[...],
                                  preferred_element_type=jnp.float32)


def _kernel_call(xs, we_gate, we_up, we_down, groups, *, live: int,
                 interpret: bool, tiles=None):
    """The kernel over shapes the rule has accepted -> (y, visits made).
    ``tiles`` = (row tile, rows of a gate/up tile, rows of a down tile)
    overrides the derived ones: how they were chosen on the chip, and how
    the CPU tests reach several stream steps at small sizes."""
    M, D = xs.shape
    G, _, F = we_gate.shape
    item = xs.dtype.itemsize
    tm, tk, tf = tiles or (ROW_TILE, _stream_tile(D, 2 * F * item),
                           _stream_tile(F, D * item))
    nk, nf = D // tk, F // tf
    n_visits = min(live, G, M) + M // tm - 1
    vg, vt, lo, hi, real = visits(groups, M, tm, n_visits)

    def on(kind):
        # block index of each operand at (visit, step); a step past the
        # real visits sits where the last real step sat.  A block is
        # fetched during the step before the one whose index differs, so
        # while gate and up stream, the down operand stays on the visit
        # BEFORE's last tile: its first tile is then fetched under the
        # last gate/up step (moved at step 0 with theirs, nothing would
        # stream under that step, the longest of a visit)
        def index(v, s, vg, vt, lo, hi, real):
            done = v >= real[0]
            k = jnp.where(done, nk - 1, jnp.minimum(s, nk - 1))
            before = jnp.maximum(v - 1, 0)
            down = jnp.where(done | (s >= nk), v, before)
            f = jnp.where(done | (s < nk), jnp.where(v > 0, nf - 1, 0),
                          s - nk)
            return {"x": (vt[v], k), "up": (vg[v], k, 0),
                    "down": (vg[down], f, 0), "out": (vt[v], 0)}[kind]
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_visits, nk + nf),
        in_specs=[
            pl.BlockSpec((tm, tk), on("x")),
            pl.BlockSpec((None, tk, F), on("up")),
            pl.BlockSpec((None, tk, F), on("up")),
            pl.BlockSpec((None, tf, D), on("down")),
        ],
        out_specs=pl.BlockSpec((tm, D), on("out")),
        scratch_shapes=[
            pltpu.VMEM((tm, F), jnp.float32),
            pltpu.VMEM((tm, F), jnp.float32),
            pltpu.VMEM((nf, tm, tf), xs.dtype),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_swiglu_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        # an output block gathers the visits of its row tile: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged-dot-swiglu",
    )(vg, vt, lo, hi, real.reshape(1), xs, we_gate, we_up, we_down)
    return y, real


def takes_kernel(xs, we_gate, we_down, *, expect: float,
                 interpret: bool) -> bool:
    """The path rule, from static shapes alone (module docstring)."""
    M, D = xs.shape
    F = we_gate.shape[-1]
    return bool(
        xs.dtype == we_gate.dtype == we_down.dtype
        and (interpret or xs.dtype == jnp.bfloat16)
        and D % 128 == 0 and F % 128 == 0 and M % ROW_TILE == 0
        and expect <= MAX_EXPECTED_ROWS)


def grouped_swiglu(xs, we_gate, we_up, we_down, groups, *,
                   live: Optional[int] = None,
                   expect: Optional[float] = None,
                   interpret: Optional[bool] = None):
    """``xs`` [M, D], ``we_gate``/``we_up`` [G, D, F], ``we_down``
    [G, F, D], ``groups`` [G] int32 -> (``y`` [M, D] float32, ``passes``
    int32 scalar).  Rows past the groups are left unwritten: do not read
    them.  ``passes`` counts the (group, row tile) visits the kernel made,
    i.e. how many times a group's three matrices were streamed; 0 where
    the reference ran.

    ``live`` (static): at most this many groups are non-empty (default
    G).  ``expect`` (static): rows a non-empty group is expected to hold
    (default ``M / live``).  The kernel runs on a TPU (or under
    ``interpret=True``) for bf16 operands whose ``D`` and ``F`` are whole
    lane tiles, ``M`` whole row tiles and ``expect`` at most
    ``MAX_EXPECTED_ROWS``; the reference otherwise."""
    live = min(we_gate.shape[0], live or we_gate.shape[0])
    expect = xs.shape[0] / live if expect is None else expect
    asked = interpret is not None   # a test's, or a chipless compile's
    interpret = bool(interpret)
    if (asked or jax.default_backend() == "tpu") and takes_kernel(
            xs, we_gate, we_down, expect=expect, interpret=interpret):
        return _kernel_call(xs, we_gate, we_up, we_down, groups, live=live,
                            interpret=interpret)
    return (grouped_swiglu_reference(xs, we_gate, we_up, we_down, groups),
            jnp.zeros((), jnp.int32))
