"""The expert layer's combine (``models/moe.py combine``): each token's
routed outputs, weighted, summed into its row by reading the rows where
the sort put them.  It is the scatter-add it replaced to float32
rounding, at any row count; no row past the held groups is read, a token
reads its own pairs' rows only, and the serve loop's
``moe_combine_dense_layers`` counts the combines of a decode step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import llama, moe


def _pairs(seed, n, k, n_experts, held_first, held_count, width):
    """What ``moe_ffn`` hands its combine for ``n`` tokens routed to ``k``
    distinct experts each, of which ``[held_first, held_first +
    held_count)`` are held: the grouped product's rows in sorted order
    (the rows past the held groups NaN, as a kernel may leave them), the
    sort's permutation, which pairs are held and their weights."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(n_experts, k, replace=False)
                    for _ in range(n)])
    local = idx - held_first
    held = (local >= 0) & (local < held_count)
    order = np.argsort(np.where(held, local, held_count).reshape(n * k),
                       kind="stable")
    w = rng.random((n, k)).astype(np.float32)
    w = w / w.sum(-1, keepdims=True) * 2.5
    y = rng.standard_normal((n * k, width)).astype(np.float32)
    y[~held.reshape(n * k)[order]] = np.nan
    return (jnp.asarray(y), jnp.asarray(order, jnp.int32), jnp.asarray(held),
            jnp.asarray(w))


def _scatter(y, order, held, w):
    """The sum as the scatter-add the combine replaced."""
    n, k = w.shape
    hs = held.reshape(n * k)[order]
    wp = w.reshape(n * k)[order]
    return jnp.zeros((n, y.shape[1]), jnp.float32).at[order // k].add(
        jnp.where(hs[:, None], y * wp[:, None], 0.0))


def _check(args):
    got = np.asarray(jax.jit(moe.combine)(*args))
    want = np.asarray(_scatter(*args))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    assert moe.combine_calls(jax.jit(moe.combine).trace(*args).jaxpr) == 1


#: the four expert cells' decode combines cut to 16 rows of 96: top-k and
#: the routed experts, a share of them held (the rest held elsewhere; LFM2,
#: which holds all 64, holds 48 here, so that rows past the groups exist)
CELLS = {
    "ax_k1": (8, 192, 0, 12),
    "k_exaone": (8, 128, 16, 16),
    "longcat": (12, 512, 0, 16),
    "lfm2": (4, 64, 0, 48),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_combine_is_the_scatter(cell):
    """At a cell's top-k and share of held experts the combine equals the
    scatter-add to float32 rounding, every row past the held groups NaN:
    the result is finite, and the program holds one combine."""
    k, n_experts, held_first, held_count = CELLS[cell]
    args = _pairs(7, 16, k, n_experts, held_first, held_count, 96)
    assert 0 < int(args[2].sum()) < 16 * k   # some pairs held elsewhere
    _check(args)


@pytest.mark.parametrize("n", [1, 129, 1024])
def test_any_row_count_is_the_scatter(n):
    """One row, past a hundred and past a thousand (the cacheless forward
    over a long sequence): the same sum, and no form of its own."""
    _check(_pairs(3, n, 4, 64, 8, 16, 32))


def test_a_token_reads_only_its_own_rows():
    """A NaN in one token's held rows (a fault of that row's input) makes
    that token's row NaN and reaches no other: the others are the
    scatter-add's rows to float32 rounding."""
    y, order, held, w = _pairs(11, 8, 4, 16, 0, 16, 64)
    bad = 3
    rows = np.flatnonzero(np.asarray(order) // 4 == bad)
    y = y.at[rows[0], 5].set(jnp.nan)
    got = np.asarray(jax.jit(moe.combine)(y, order, held, w))
    want = np.asarray(_scatter(y, order, held, w))
    assert np.isnan(got[bad, 5]) and np.isnan(want[bad, 5])
    others = np.arange(8) != bad
    assert np.isfinite(got[others]).all()
    assert np.abs(got[others] - want[others]).max() <= \
        1e-6 * np.abs(want[others]).max()


@pytest.mark.parametrize("preset,tokens,combines", [
    ("hybrid_moe_tiny", 1, 7), ("hybrid_moe_tiny", 129, 7),
    ("latent_shortcut_tiny", 1, 2)],
    ids=["decode_step", "long_forward", "shortcut"])
def test_a_programs_combines_are_counted(preset, tokens, combines):
    """``hybrid_moe_tiny``'s 7 expert layers and ``latent_shortcut_tiny``'s
    two shortcut branches, walked as the programs walk them: one combine
    each, whatever the row count."""
    cfg = dataclasses.replace(llama.PRESETS[preset], max_seq=512)
    params = jax.eval_shape(lambda: llama.init_params(cfg, 0, "float32"))
    toks = jax.ShapeDtypeStruct((1, tokens), jnp.int32)
    jaxpr = jax.jit(lambda p, t: llama.forward(p, t, cfg)).trace(
        params, toks).jaxpr
    assert moe.combine_calls(jaxpr) == combines
