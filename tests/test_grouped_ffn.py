"""The grouped SwiGLU kernel (``ops/grouped_ffn.py``) against its reference
— three ``jax.lax.ragged_dot`` calls — in interpret mode on the CPU: group
sizes are values (zeros among them, one group owning every row, groups
that straddle a row tile), the weights are a kind's whole stack with the
layer a traced index, rows past the groups are never read by
``models/moe.py``, the path rule reads static shapes only, and the pass
count is the one numpy makes.  What Mosaic says of the kernel is
``tests/test_kernels_tpu_compile.py``; silicon is chip_smoke's kernel leg.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import moe
from nnstreamer_tpu.ops import grouped_ffn as GF

TM = GF.ROW_TILE
#: the kernel keeps gate and up in float32 where the reference rounds
#: each to bf16 before the activation: a few bf16 steps of the output
TOL = 0.02


def _operands(seed, M, D, F, G, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)

    def arr(shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * scale).astype(dtype)

    return (arr((M, D)), arr((G, D, F), D ** -0.5), arr((G, D, F), D ** -0.5),
            arr((G, F, D), F ** -0.5))


def _passes(sizes, tile):
    """Row tiles each non-empty group overlaps, summed: numpy's count."""
    ends = np.cumsum(sizes)
    return int(sum((e - 1) // tile - (e - s) // tile + 1
                   for s, e in zip(sizes, ends) if s))


def _check(xs, ws, sizes, **kw):
    groups = jnp.asarray(sizes, jnp.int32)
    got, passes = jax.jit(functools.partial(
        GF.grouped_swiglu, interpret=True, **kw))(xs, *ws, groups)
    want = GF.grouped_swiglu_reference(xs, *ws, groups)
    n = int(np.sum(sizes))
    got, want = np.asarray(got)[:n], np.asarray(want)[:n]
    assert got.dtype == np.float32 and np.isfinite(got).all()
    if n:
        assert np.abs(got - want).max() < TOL * np.abs(want).max()
    return int(passes)


SIZES = {
    "zeros_among_them": [3, 0, 5, 0, 0, 17, 10, 0, 1, 0, 0, 7],
    "every_group_empty": [0] * 12,
    "one_group_owns_every_row": [0, 0, 0, 2 * TM] + [0] * 8,
    "straddles_a_tile": [TM - 3, 8, 0, 0, TM - 5, 0, 0, 0, 0, 0, 0, 0],
    "ends_on_a_boundary": [TM, 0, TM] + [0] * 9,
    "one_row_each": [1] * 12,
    "the_last_group_only": [0] * 11 + [9],
}


@pytest.mark.parametrize("name", list(SIZES))
def test_kernel_is_the_reference(name):
    """Every row through its own group's matrices, whatever the sizes; the
    visits made are numpy's count of (group, row tile) pairs."""
    sizes = SIZES[name]
    xs, *ws = _operands(1, 2 * TM, 256, 128, len(sizes))
    assert _check(xs, ws, sizes) == _passes(sizes, TM)


@pytest.mark.parametrize("seed", range(4))
def test_random_sizes_and_their_pass_count(seed):
    rng = np.random.default_rng(seed)
    G, M = 10, 3 * TM
    sizes = rng.multinomial(rng.integers(0, M + 1), np.full(G, 1 / G))
    sizes[rng.integers(0, G, 3)] = 0
    xs, *ws = _operands(seed, M, 128, 256, G)
    assert _check(xs, ws, sizes, expect=4) == _passes(sizes, TM)


@pytest.mark.parametrize("tiles", [(16, 128, 128), (32, 384, 256),
                                   (128, 128, 256)])
def test_several_stream_steps_and_small_row_tiles(tiles):
    """Three gate/up steps and two down steps a visit (the derived tiles
    take a small matrix in one), row tiles of 16 and 32 that the groups
    straddle: the accumulators, the activation's slices and the output
    block that gathers a tile's visits."""
    sizes = [5, 0, 30, 1, 0, 64, 0, 20]
    xs, *ws = _operands(7, TM, 384, 256, len(sizes))
    groups = jnp.asarray(sizes, jnp.int32)
    got, passes = jax.jit(functools.partial(
        GF._kernel_call, live=len(sizes), interpret=True, tiles=tiles))(
            xs, *ws, groups)
    want = np.asarray(GF.grouped_swiglu_reference(xs, *ws, groups))[:120]
    assert np.abs(np.asarray(got)[:120] - want).max() \
        < TOL * np.abs(want).max()
    assert int(passes) == _passes(sizes, tiles[0])


def test_live_bounds_the_grid_not_the_answer():
    """``live`` promises how many groups can be non-empty: the grid has
    ``live + tiles - 1`` visits, and the answer is the reference's."""
    sizes = [0] * 20 + [4, 0, 9, TM, 2] + [0] * 15
    xs, *ws = _operands(2, 2 * TM, 128, 128, len(sizes))
    assert _check(xs, ws, sizes, live=5, expect=4) == _passes(sizes, TM)


def test_the_stack_view_compiles_once_for_all_layers():
    """A kind's stack as ``n * E`` groups, the layer's sizes written at
    ``[l * E, (l + 1) * E)`` of a vector of zeros, ``l`` a traced value."""
    n, E, M = 3, 4, TM
    xs, *ws = _operands(3, M, 128, 128, n * E)
    sizes = jnp.asarray([5, 0, 11, 2], jnp.int32)

    @jax.jit
    def layer(l):
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((n * E,), jnp.int32), sizes, (l * E,))
        return GF.grouped_swiglu(xs, *ws, groups, live=E, interpret=True)

    for l in range(n):
        got, passes = layer(jnp.int32(l))
        alone = GF.grouped_swiglu_reference(
            xs, *(w[l * E:(l + 1) * E] for w in ws), sizes)
        assert np.abs(np.asarray(got)[:18] - np.asarray(alone)[:18]).max() \
            < TOL * np.abs(np.asarray(alone)[:18]).max()
        assert int(passes) == 3
    assert layer._cache_size() == 1


def _layer_weights(seed, D, F, n_router, held):
    rng = np.random.default_rng(seed)

    def arr(shape, scale, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * scale).astype(dtype)

    return {"w_router": arr((D, n_router), 1.0, jnp.float32),
            "router_bias": jnp.zeros((n_router,), jnp.float32),
            "we_gate": arr((held, D, F), D ** -0.5),
            "we_up": arr((held, D, F), D ** -0.5),
            "we_down": arr((held, F, D), F ** -0.5)}


@pytest.mark.parametrize("top_k,tokens,zero", [(8, 16, 0), (12, 32, 16)],
                         ids=["top8_of_32", "top12_of_32_and_16_identity"])
def test_moe_ffn_never_reads_rows_past_the_groups(monkeypatch, top_k,
                                                  tokens, zero):
    """Both cells' ``D / F`` = 3 and their ``top_k`` at small sizes, a
    quarter of the experts held: ``moe_ffn`` over the kernel — the rows
    past the held groups poisoned — is ``moe_ffn`` over the reference, and
    its fifth count is the kernel's passes."""
    D, F, E, held = 384, 128, 32, 8
    ex = moe.ExpertsConfig(
        n_experts=E, top_k=top_k, hidden=F, held_first=8, held_count=held,
        zero_experts=zero, scoring="softmax" if zero else "sigmoid")
    lp = _layer_weights(4, D, F, E + zero, held)
    h = jnp.asarray(np.random.default_rng(5).standard_normal(
        (tokens, 1, D)).astype(np.float32)).astype(jnp.bfloat16)
    want, wstats = moe.moe_ffn(h, lp, ex, jnp.bfloat16)
    assert int(wstats[4]) == 0   # the CPU's own path: ragged_dot

    kernel = functools.partial(GF.grouped_swiglu, interpret=True)

    def poisoned(xs, *ws_groups, **kw):
        y, passes = kernel(xs, *ws_groups, **kw)
        row = jnp.arange(xs.shape[0])[:, None]
        return jnp.where(row < ws_groups[-1].sum(), y, jnp.nan), passes

    monkeypatch.setattr(GF, "grouped_swiglu", poisoned)
    got, stats = moe.moe_ffn(h, lp, ex, jnp.bfloat16)
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOL * np.abs(want).max()
    assert [int(v) for v in stats[:4]] == [int(v) for v in wstats[:4]]
    assert 0 < int(stats[1]) <= int(stats[4]) <= int(stats[1]) + \
        tokens * top_k // TM - 1


S = jax.ShapeDtypeStruct


@pytest.mark.parametrize("M,G,expect,dtype,takes", [
    (512, 112, 4, jnp.bfloat16, True),     # K-EXAONE decode: 64 x top-8
    (768, 64, 1, jnp.bfloat16, True),      # LongCat decode: 64 x top-12
    (256, 112, 2, jnp.bfloat16, True),     # their prefill chunks of 32
    (384, 64, 0.5, jnp.bfloat16, True),
    (16384, 128, 128, jnp.bfloat16, False),  # every expert held, 2048 tokens
    (512, 112, 4, jnp.float32, False),     # the float32 tests' operands
    (500, 112, 4, jnp.bfloat16, False),    # rows that are no whole tiles
], ids=["k_exaone_decode", "longcat_decode", "k_exaone_prefill",
        "longcat_prefill", "whole_set_long_prefill", "float32", "ragged_rows"])
def test_the_path_rule_reads_static_shapes(M, G, expect, dtype, takes):
    D, F = 6144, 2048
    assert GF.takes_kernel(S((M, D), dtype), S((G, D, F), dtype),
                           S((G, F, D), dtype), expect=expect,
                           interpret=False) is takes


def test_off_the_tpu_it_is_three_ragged_dots_to_the_bit():
    sizes = jnp.asarray(SIZES["zeros_among_them"], jnp.int32)
    xs, *ws = _operands(6, 2 * TM, 256, 128, 12)
    got, passes = GF.grouped_swiglu(xs, *ws, sizes, live=12, expect=1)
    want = GF.grouped_swiglu_reference(xs, *ws, sizes)
    n = int(sizes.sum())
    assert np.array_equal(np.asarray(got)[:n], np.asarray(want)[:n])
    assert int(passes) == 0
    text = str(jax.make_jaxpr(functools.partial(
        GF.grouped_swiglu, live=12, expect=1))(xs, *ws, sizes))
    assert text.count("ragged_dot_general") == 3 and "pallas_call" not in text
