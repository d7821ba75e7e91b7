"""GQA/MQA-grouped attention kernels: value + traffic contracts.

The grouped kernels (ops/attention.py) take K/V UNREPEATED at
``[*, Hkv, *]`` and share each streamed block across the whole
query-head group.  Three things must hold, and each gets pinned here:

1. **Values**: the grouped layout equals feeding the SAME kernel a
   pre-repeated ``Hkv == H`` layout (the pre-refactor data path) at
   every ratio, including MQA — bit for bit in the flash kernel: the
   refactor moved bytes, not math.  (Vs the materialized XLA reference
   it is allclose, not bitwise: blockwise online softmax re-associates
   the reduction.)
2. **Stream count**: the flash grid is ``(B * Hkv, Sq / block_q)`` —
   one K/V stream per (batch, KV head), NOT per query head — and the
   paged grid is ``(B,)``; K/V operands ride ANY memory space (the
   kernel's own DMAs stream them), so HBM reads scale with ``Hkv``.
3. **DMA structure**: a flash grid cell issues exactly one
   double-buffered K stream and one V stream (6 ``make_async_copy`` call
   sites: 2 warm starts + 2 prefetches + 2 waits), with NO
   per-query-head DMA loop — the count is invariant in H/Hkv.  Interpret
   mode traces the cell body once, so call-site counting is exact.  The
   paged kernel streams a row's blocks in waves of W (up to W K copies
   and W V copies a buffer slot, two slots), each copy a whole
   ``[bs * Hkv, D]`` block, one per place whose block is live for the
   row: what is pinned is what runs — a row STARTS two copies per live
   block, an idle row none, each is waited for once — and that every
   copy is Hkv-sized.

The paged kernel computes a wave's scores and its P x V as one MXU
product each over all of the wave's ``[bs * Hkv]`` rows (a head keeps the
columns of its own KV head), so the grouped and the repeated layout sum
in different orders: they agree to the tolerance each holds against the
XLA reference, not bitwise.  ``TestPagedWaves`` runs it on bf16 pools at
the serving cells' shapes around every wave boundary, with every block
that is not live filled with NaN.

Plus the prediction side: ``serving_plan``'s
``decode_bytes_per_ctx_token`` must price the pool at ``n_kv_heads``
(the grouped kernel's actual traffic), not ``n_heads`` — the stale
over-prediction nns-xray's reconciliation flagged.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import llama
from nnstreamer_tpu.ops import attention as A
from nnstreamer_tpu.filters.llm import serving_plan

jax.config.update("jax_platform_name", "cpu")

RATIOS = [1, 2, 4, 8]  # H / Hkv group sizes; 8 with H=8 is MQA (Hkv=1)
H = 8


def _repeat(x, rep):
    """models/llama.py's GQA layout: query head h = kv_head * rep + g."""
    b, s, hkv, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, hkv, rep, d)).reshape(b, s, hkv * rep, d)


def _flash_inputs(hkv, *, b=2, s=256, d=32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, s, H, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.float32)
    return q, k, v


class _PallasCapture:
    """Wrap ``pl.pallas_call`` (and ``pltpu.make_async_copy``) through the
    module under test, recording the grid actually launched, the number
    of DMA call sites traced, and — counted as the interpreted kernel
    RUNS, through a debug callback beside each ``start()`` and ``wait()``
    — the source shape of every copy a grid cell really started and how
    many it waited for."""

    def __init__(self):
        self.grids = []
        self.dma_calls = 0
        self.started = []
        self.waited = 0

    def install(self, monkeypatch):
        real_call = A.pl.pallas_call
        real_dma = A.pltpu.make_async_copy
        cap = self

        class Copy:
            def __init__(self, src, dst, sem):
                self.copy, self.src = real_dma(src, dst, sem), src.shape

            def start(self):
                jax.debug.callback(lambda: cap.started.append(self.src))
                return self.copy.start()

            def wait(self):
                def count():
                    cap.waited += 1
                jax.debug.callback(count)
                return self.copy.wait()

        def spy_call(*args, **kw):
            if "grid" in kw:
                self.grids.append(tuple(kw["grid"]))
            elif "grid_spec" in kw:
                self.grids.append(tuple(kw["grid_spec"].grid))
            return real_call(*args, **kw)

        def spy_dma(*args, **kw):
            self.dma_calls += 1
            return Copy(*args, **kw)

        monkeypatch.setattr(A.pl, "pallas_call", spy_call)
        monkeypatch.setattr(A.pltpu, "make_async_copy", spy_dma)
        return self


class TestFlashGrouped:
    @pytest.mark.parametrize("rep", RATIOS)
    def test_bit_identical_to_repeated_layout(self, rep):
        hkv = H // rep
        q, k, v = _flash_inputs(hkv)
        grouped = A.flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
        repeated = A.flash_attention(
            q, _repeat(k, rep), _repeat(v, rep), causal=True,
            block_q=64, block_k=64, interpret=True)
        assert np.array_equal(np.asarray(grouped), np.asarray(repeated))
        ref = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(grouped), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("rep", RATIOS)
    def test_kv_streams_scale_with_hkv_not_h(self, rep, monkeypatch):
        hkv = H // rep
        b, s, bq = 2, 256, 64
        cap = _PallasCapture().install(monkeypatch)
        q, k, v = _flash_inputs(hkv, b=b, s=s)
        A.flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=64, interpret=True)
        # one grid row per (batch, KV head): stream count is b * hkv —
        # constant H, shrinking hkv => fewer K/V streams, same output
        assert cap.grids == [(b * hkv, s // bq)]
        # exactly one double-buffered K + one V stream per cell (2 warm
        # starts + 2 prefetches + 2 waits), no per-query-head DMA loop
        assert cap.dma_calls == 6


class TestPagedGrouped:
    def _pool_case(self, hkv, *, d=32, bs=16, n_blocks=32, seed=1):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
        lens = jnp.asarray([5, 0, bs * 3, bs * 8], jnp.int32)  # one idle
        b = lens.shape[0]
        q = jax.random.normal(kq, (b, 1, H, d), jnp.float32)
        k_pool = jax.random.normal(kk, (n_blocks, bs, hkv, d), jnp.float32)
        v_pool = jax.random.normal(kv, (n_blocks, bs, hkv, d), jnp.float32)
        tbl = jnp.arange(b * 8, dtype=jnp.int32).reshape(b, 8) % n_blocks
        return q, k_pool, v_pool, tbl, lens

    def _repeat_pool(self, pool, rep):
        n, bs, hkv, d = pool.shape
        return jnp.broadcast_to(
            pool[:, :, :, None, :], (n, bs, hkv, rep, d)).reshape(
                n, bs, hkv * rep, d)

    @pytest.mark.parametrize("rep", RATIOS)
    def test_equal_to_repeated_pool(self, rep):
        hkv = H // rep
        q, kp, vp, tbl, lens = self._pool_case(hkv)
        grouped = A.paged_attention(q, kp, vp, tbl, lens, interpret=True)
        repeated = A.paged_attention(
            q, self._repeat_pool(kp, rep), self._repeat_pool(vp, rep),
            tbl, lens, interpret=True)
        # a wave is W * bs * Hkv score columns wide, so the two layouts
        # re-associate the sums: equal to the tolerance, not the bit
        np.testing.assert_allclose(
            np.asarray(grouped), np.asarray(repeated), atol=2e-5, rtol=2e-5)
        ref = A.paged_attention_reference(q, kp, vp, tbl, lens)
        live = np.asarray(lens) > 0  # an idle row emits zeros, unread
        np.testing.assert_allclose(
            np.asarray(grouped)[live], np.asarray(ref)[live],
            atol=2e-5, rtol=2e-5)
        assert not np.asarray(grouped)[~live].any()

    @pytest.mark.parametrize("rep", RATIOS)
    def test_one_stream_per_row(self, rep, monkeypatch):
        hkv = H // rep
        cap = _PallasCapture().install(monkeypatch)
        q, kp, vp, tbl, lens = self._pool_case(hkv)
        bs, d = kp.shape[1], kp.shape[3]
        jax.block_until_ready(
            A.paged_attention(q, kp, vp, tbl, lens, interpret=True))
        jax.effects_barrier()
        # one grid cell per batch row regardless of head layout
        assert cap.grids == [(q.shape[0],)]
        # the row streams ceil(len/bs) blocks of K and as many of V: the
        # idle row starts no copy, no row one for a block it does not hold
        live_blocks = int(np.sum(-(-np.asarray(lens) // bs)))
        assert len(cap.started) == 2 * live_blocks
        # and every copy is a whole block of its OWN Hkv-sized pool: the
        # traffic scales with Hkv, no copy is per query head
        assert set(cap.started) == {(bs * hkv, d)}
        # every copy started is waited for once (a row's first wave is
        # started by the row before it, unless that one was idle)
        assert cap.waited == len(cap.started)
        # three copy SITES for K and three for V — a row's own first
        # wave, the wave streamed in behind the compute, the wait — each
        # run once per live place: none per query head
        assert cap.dma_calls == 6


#: the serving cells' geometry: Mistral-7B (32/8 heads) and K-EXAONE
#: (64/8), head_dim 128, blocks of 16: a block is a [128, 128] matrix and
#: a wave is 8 of them = 128 tokens
CELL_BS, CELL_D, CELL_WAVE = 16, 128, 128
CELL_MODES = {"full": (0, False), "window": (128, False),
              "window_ring": (128, True)}


@functools.partial(jax.jit, static_argnames=("window", "ring"))
def _cell_kernel(q, kp, vp, tbl, lens, window, ring):
    return A.paged_attention(q, kp, vp, tbl, lens, interpret=True,
                             window=window, ring=ring)


def _cell_case(heads, mode, lens, seed):
    """bf16 query, pools and a shuffled table (a ring where the mode has
    one) for rows at ``lens``; the table as numpy, to say which blocks
    are live."""
    h, hkv = heads
    window, ring = CELL_MODES[mode]
    b = len(lens)
    width = window // CELL_BS + 3 if ring else 64
    n_blocks = b * width
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, CELL_D)).astype(jnp.bfloat16)
    kp, vp = (jax.random.normal(
        k, (n_blocks, CELL_BS, hkv, CELL_D)).astype(jnp.bfloat16)
        for k in ks[1:])
    tbl = np.random.RandomState(seed).permutation(n_blocks).astype(
        np.int32).reshape(b, width)
    return q, kp, vp, tbl, np.asarray(lens, np.int32), window, ring


def _assert_is_reference(got, q, kp, vp, tbl, lens, window, ring):
    ref = np.asarray(A.paged_attention_reference(
        q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens), window=window,
        ring=ring), np.float32)
    rows = lens > 0
    # one bf16 step of an output of size ~1 is 0.0078
    np.testing.assert_allclose(got[rows], ref[rows], atol=2e-2)
    assert not got[~rows].any()  # an idle row emits zeros


class TestPagedWaves:
    """The wave pipeline at the cells' shapes, bf16 pools, interpreted."""

    @pytest.mark.parametrize("ctx", [1, 15, 16, 17, CELL_WAVE - 1, CELL_WAVE,
                                     CELL_WAVE + 1, 544, 800])
    @pytest.mark.parametrize("mode", list(CELL_MODES))
    @pytest.mark.parametrize("heads", [(32, 8), (64, 8)],
                             ids=["mistral32x8", "exaone64x8"])
    def test_matches_reference_and_skips_dead_blocks(self, heads, mode, ctx):
        """Contexts on both sides of a block's and of a wave's edge, and
        at the cells' deepest, against the XLA reference; then the same
        call on a pool whose every block that no row holds live is NaN:
        the last wave's spare places (and a window's blocks before its
        start) must neither be fetched nor reach the result."""
        bs = CELL_BS
        assert A._paged_wave_blocks(bs, heads[1], CELL_D, 2) * bs == CELL_WAVE
        case = _cell_case(heads, mode, [ctx, 0, 77, ctx], seed=ctx)
        q, kp, vp, tbl, lens, window, ring = case
        args = (jnp.asarray(tbl), jnp.asarray(lens), window, ring)
        clean = np.asarray(_cell_kernel(q, kp, vp, *args), np.float32)
        _assert_is_reference(clean, *case)

        # the blocks a row attends: those that intersect [lo, L)
        live = np.zeros(kp.shape[0], bool)
        for r, L in enumerate(lens):
            lo = max(L - window, 0) if window else 0
            for i in range(lo // bs, -(-int(L) // bs)):
                live[tbl[r, i % tbl.shape[1] if ring else i]] = True
        dead = jnp.asarray(~live)[:, None, None, None]
        nan = jnp.asarray(jnp.nan, jnp.bfloat16)
        dirty = np.asarray(_cell_kernel(
            q, jnp.where(dead, nan, kp), jnp.where(dead, nan, vp), *args),
            np.float32)
        assert np.isfinite(dirty).all()
        assert np.array_equal(dirty, clean)

    @pytest.mark.parametrize("heads,mode,lens", [
        ((32, 8), "full", [1, 0, 129, 300, 0, 0, 17, 544, 16]),
        ((64, 8), "window_ring", [200, 0, 129, 3, 0, 800]),
        ((32, 8), "window", [0, 0, 5, 400]),
        ((32, 32), "full", [33, 0, 0, 64, 1])],
        ids=["full", "window_ring", "window_after_idle", "mha"])
    def test_pipeline_holds_when_copies_land_late(self, heads, mode, lens):
        """The TPU interpreter with every copy carried out only when it is
        WAITED for, scratch that starts as NaN, and its race detector on:
        a wave (a row's next, or the next row's first, started a grid step
        early) that is computed on before its own waits, or a place read
        that nothing ever filled, shows."""
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpret)

        case = _cell_case(heads, mode, lens, seed=0)
        q, kp, vp, tbl, lens, window, ring = case
        got = np.asarray(A.paged_attention(
            q, kp, vp, jnp.asarray(tbl), jnp.asarray(lens), window=window,
            ring=ring, interpret=A.pltpu.InterpretParams(
                dma_execution_mode="on_wait", uninitialized_memory="nan",
                detect_races=True)), np.float32)
        assert not tpu_interpret.races.races_found
        _assert_is_reference(got, *case)


class TestPagedNarrowHeads:
    """Heads narrower than the 128 lanes: the same kernel, two (four) KV
    heads to a lane row (ops/attention.py), interpreted."""

    @staticmethod
    def _case(heads, d, lens, seed):
        h, hkv = heads
        b, width = len(lens), 68       # 68 blocks of 16: contexts to 1,088
        n_blocks = b * width
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, 1, h, d)).astype(jnp.bfloat16)
        # stored as models/llama.py init_paged_cache stores them: `pack`
        # KV heads to a row of 128 lanes
        pack = A.kv_lane_pack(hkv, d)
        kp, vp = (jax.random.normal(
            k, (n_blocks, CELL_BS, hkv // pack, d * pack)).astype(
                jnp.bfloat16) for k in ks[1:])
        tbl = np.random.RandomState(seed).permutation(n_blocks).astype(
            np.int32).reshape(b, width)
        return q, kp, vp, tbl, np.asarray(lens, np.int32), 0, False

    @pytest.mark.parametrize("ctx", [1, 15, 16, 17, 255, 256, 257, 1056])
    @pytest.mark.parametrize("heads,d", [((32, 8), 64), ((32, 4), 32)],
                             ids=["conv_cell32x8x64", "heads_of_32"])
    def test_matches_reference_and_skips_dead_blocks(self, heads, d, ctx):
        """Contexts on both sides of a block's and of a wave's edge (16
        blocks of 16 a wave at four packed heads), the cell's deepest, and
        an idle row, against the XLA reference at the head's own width;
        then on a pool whose every block no row holds live is NaN."""
        pack = 128 // d
        assert A._paged_wave_blocks(CELL_BS, heads[1] // pack, 128, 2) \
            * CELL_BS == 256
        case = self._case(heads, d, [ctx, 0, 77, ctx], seed=ctx)
        q, kp, vp, tbl, lens, window, ring = case
        args = (jnp.asarray(tbl), jnp.asarray(lens), window, ring)
        clean = np.asarray(_cell_kernel(q, kp, vp, *args), np.float32)
        assert clean.shape == (4, 1, heads[0], d)
        _assert_is_reference(clean, *case)
        # the packed pool IS the pool of narrow heads, in the same order:
        # the reference reads either layout to the same bits
        plain = (kp.shape[0], CELL_BS, heads[1], d)
        ref = np.asarray(A.paged_attention_reference(
            q, kp.reshape(plain), vp.reshape(plain), *args[:2]), np.float32)
        assert np.array_equal(ref, np.asarray(A.paged_attention_reference(
            q, kp, vp, *args[:2]), np.float32))
        live = np.zeros(kp.shape[0], bool)
        for r, L in enumerate(lens):
            live[tbl[r, :-(-int(L) // CELL_BS)]] = True
        dead = jnp.asarray(~live)[:, None, None, None]
        nan = jnp.asarray(jnp.nan, jnp.bfloat16)
        dirty = np.asarray(_cell_kernel(
            q, jnp.where(dead, nan, kp), jnp.where(dead, nan, vp), *args),
            np.float32)
        assert np.isfinite(dirty).all()
        assert np.array_equal(dirty, clean)

    @pytest.mark.parametrize("mode", ["window", "window_ring"])
    def test_a_window_and_a_ring_pack_the_same_way(self, mode):
        window, ring = CELL_MODES[mode]
        lens = np.asarray([200, 0, 129, 3, 800], np.int32)
        b, width = len(lens), window // CELL_BS + 3 if ring else 64
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (b, 1, 32, 64)).astype(jnp.bfloat16)
        kp, vp = (jax.random.normal(
            k, (b * width, CELL_BS, 4, 128)).astype(jnp.bfloat16)
            for k in ks[1:])
        tbl = np.random.RandomState(5).permutation(b * width).astype(
            np.int32).reshape(b, width)
        got = np.asarray(_cell_kernel(q, kp, vp, jnp.asarray(tbl),
                                      jnp.asarray(lens), window, ring),
                         np.float32)
        _assert_is_reference(got, q, kp, vp, tbl, lens, window, ring)

    def test_heads_that_do_not_pack_take_the_reference(self):
        """KV heads that do not fill a lane row (3 heads of 64), a width
        that does not divide 128, or a pool of narrow heads stored one
        head to a row (8 of 64: nothing repacks it at the kernel's door)
        are not handed to the kernel on a chip (its DMA tiles by 128
        lanes): the call is the reference's."""
        for hkv, d in ((3, 64), (2, 48), (8, 64)):
            q = jnp.ones((2, 1, 2 * hkv, d), jnp.bfloat16)
            kp = jnp.ones((8, 16, hkv, d), jnp.bfloat16)
            text = jax.jit(lambda q, kp: A.paged_attention(
                q, kp, kp, jnp.zeros((2, 4), jnp.int32),
                jnp.ones((2,), jnp.int32), interpret=False)).lower(
                    q, kp).as_text()
            assert "tpu_custom_call" not in text


class TestServingPlanTraffic:
    """decode_bytes_per_ctx_token must track n_kv_heads — pricing GQA
    traffic at n_heads is the stale prediction the xray reconciliation
    regression exists to catch."""

    def test_gqa_prices_kv_heads_not_q_heads(self):
        dense = llama.PRESETS["llama2_7b"]  # n_kv_heads == n_heads == 32
        gqa = dataclasses.replace(dense, n_kv_heads=8)
        p_dense = serving_plan(dense, slots=4, dtype="bfloat16")
        p_gqa = serving_plan(gqa, slots=4, dtype="bfloat16")
        assert p_dense["kv_groups"] == 1
        assert p_gqa["kv_groups"] == 4
        # traffic coefficient shrinks by exactly the group factor
        assert (p_dense["decode_bytes_per_ctx_token"]
                == 4 * p_gqa["decode_bytes_per_ctx_token"])
        # and matches the closed form: K+V rows over all layers at Hkv
        assert p_gqa["decode_bytes_per_ctx_token"] == (
            2 * gqa.n_layers * gqa.n_kv_heads * gqa.head_dim * 2)

    def test_prng_state_priced_only_when_sampled(self):
        cfg = llama.PRESETS["llama_tiny"]
        greedy = serving_plan(cfg, slots=6, dtype="float32")
        sampled = serving_plan(cfg, slots=6, dtype="float32",
                               temperature=0.8)
        assert greedy["prng_state_bytes"] == 0
        assert sampled["prng_state_bytes"] == 6 * 2 * 4  # uint32[2]/slot
