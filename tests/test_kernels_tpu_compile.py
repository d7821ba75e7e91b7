"""Chipless pre-check: the Pallas kernels must COMPILE for a TPU v5e at
llama2_7b decode/prefill shapes and at the sparse serving cells' own.

The installed libtpu can compile for a v5e topology without one
(``jax.experimental.topologies`` — a compile-only client, no device is
opened), so Mosaic's verdict on a kernel body costs seconds here instead
of a chip call.  The CPU suite's interpret-mode kernel tests cannot see
what Mosaic refuses: PR 6's paged kernel passed them for fifteen PRs and
never compiled.  Numerics on silicon are chip_smoke.py's kernel leg.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nnstreamer_tpu.models import llama
from nnstreamer_tpu.ops import attention as A
from nnstreamer_tpu.ops import int4_matmul as I4

CFG = llama.PRESETS["llama2_7b"]
H, D = CFG.n_heads, CFG.head_dim
HEADS = [pytest.param(H, id="mha32"), pytest.param(8, id="gqa8")]


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot build a v5e topology: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _abstract(v5e, make):
    """``make()``'s pytree as shapes placed on the described chip (nothing
    is built: there is no device to hold an array)."""
    return jax.tree_util.tree_map(
        lambda x: v5e(x.shape, x.dtype), jax.eval_shape(make))


@pytest.fixture(scope="module")
def decode_programs(v5e):
    """``_decode_case(v5e, cell)`` jitted, its arguments, and its program
    compiled for v5e, made once for every test of this file that reads it
    (a 64-slot program takes 15-40 s to compile on a CPU host).  The
    caller steers ``jax.default_backend`` to the TPU before the first
    call."""
    made = {}

    def program(cell):
        if cell not in made:
            fn, params, pool, args = _decode_case(v5e, cell)
            jitted = jax.jit(fn, donate_argnums=(1,))
            args = (params, pool) + tuple(args)
            made[cell] = jitted, args, jitted.lower(*args).compile()
        return made[cell]

    return program


def _compile(name, fn, *args):
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    assert "tpu_custom_call" in text, \
        "lowered without the Pallas kernel (a shape gate took the reference)"
    # the kernel's own name: what a device trace shows it under (the HLO
    # instruction becomes %<name>.N) instead of %closed_call.N
    assert f'kernel_name = "{name}"' in text
    lowered.compile()  # Mosaic runs here; a refused kernel raises
    return lowered


@pytest.mark.parametrize("hkv", HEADS)
@pytest.mark.parametrize("slots", [4, 16, 64])
def test_paged_attention_compiles(v5e, hkv, slots):
    bs, n_blocks, max_blocks = 16, 256, 16
    _compile(
        "paged_attention",
        lambda q, k, v, t, n: A.paged_attention(q, k, v, t, n,
                                                interpret=False),
        v5e((slots, 1, H, D), jnp.bfloat16),
        v5e((n_blocks, bs, hkv, D), jnp.bfloat16),
        v5e((n_blocks, bs, hkv, D), jnp.bfloat16),
        v5e((slots, max_blocks), jnp.int32), v5e((slots,), jnp.int32))


def test_paged_attention_compiles_at_the_serving_cells_table(v5e):
    """32 slots of 32/8 heads over the whole pool (32 layers x 1088
    blocks) with a table wide enough for 4,096 tokens, the cell's
    ``max_seq``: a wave of 8 blocks of [128, 128], two slots of K and of
    V, steered through an SMEM table of 32 x 256 entries."""
    slots, hkv, bs, n_blocks, max_blocks = 32, 8, 16, 32 * 1088, 256
    assert A._paged_wave_blocks(bs, hkv, D, 2) == 8
    _compile(
        "paged_attention",
        lambda q, k, v, t, n: A.paged_attention(q, k, v, t, n,
                                                interpret=False),
        v5e((slots, 1, H, D), jnp.bfloat16),
        v5e((n_blocks, bs, hkv, D), jnp.bfloat16),
        v5e((n_blocks, bs, hkv, D), jnp.bfloat16),
        v5e((slots, max_blocks), jnp.int32), v5e((slots,), jnp.int32))


@pytest.mark.parametrize("hkv", HEADS)
@pytest.mark.parametrize("seq", [128, 512])
def test_flash_attention_compiles(v5e, hkv, seq):
    _compile(
        "flash_attention",
        lambda q, k, v: A.flash_attention(q, k, v, causal=True,
                                          interpret=False),
        v5e((1, seq, H, D), jnp.bfloat16),
        v5e((1, seq, hkv, D), jnp.bfloat16),
        v5e((1, seq, hkv, D), jnp.bfloat16))


@pytest.mark.parametrize("din,fout", [
    (CFG.dim, 3 * CFG.dim), (CFG.dim, CFG.dim),
    (CFG.dim, 2 * CFG.ffn_hidden), (CFG.ffn_hidden, CFG.dim),
    (CFG.dim, CFG.vocab)])
@pytest.mark.parametrize("rows", [1, 16])
def test_matmul_int4_compiles(v5e, din, fout, rows):
    _compile(
        "int4_matmul",
        lambda h, p, s: I4.matmul_int4(h, p, s, interpret=False),
        v5e((rows, din), jnp.bfloat16), v5e((din // 2, fout), jnp.int8),
        v5e((1, fout), jnp.float32))


def test_serve_decode_step_engages_paged_kernel(v5e, monkeypatch):
    """The serve loop's decode program (``forward_paged``, T == 1) at
    llama2_7b width, int8 tree, depth cut to 2: it must carry the paged
    kernel — not the reference the shape gates fall back to — and compile."""
    import dataclasses

    # the kernels route to their references unless the LIVE backend is a
    # TPU; tracing for the v5e topology needs them to believe it is
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(CFG, n_layers=2, max_seq=256)
    slots, bs, n_blocks, max_blocks = 4, 16, 24, 16

    params = _abstract(
        v5e, lambda: llama.init_params_int8(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(cfg, n_blocks, bs))
    lowered = _compile(
        "paged_attention",
        lambda p, tok, pool, tables, pos: llama.forward_paged(
            p, tok, pool, tables, pos, cfg),
        params, v5e((slots, 1), jnp.int32), pool,
        v5e((slots, max_blocks), jnp.int32), v5e((slots,), jnp.int32))
    # the block's sections are named scopes: every operation's op_name
    # carries its section, so a device trace sums by section, not by shape
    text = lowered.as_text(debug_info=True)
    for scope in ("attention", "kv_write", "mlp"):
        assert f'loc("{scope}/' in text, scope
    assert "attention/paged_attention" in text


# -- the KV pool is carried, not moved (PR 27) ------------------------------

#: the benchmark's serving cell: Mistral-7B geometry (GQA 32/8, ffn 14336),
#: 32 slots, a pool of 1088 blocks of 16, tables of 4096 / 16 entries;
#: depth cut to 4
POOL_CFG = dict(n_layers=4, n_kv_heads=8, ffn_hidden=14336, max_seq=4096)
POOL_SLOTS, POOL_BS, POOL_BLOCKS, POOL_MAX_BLOCKS = 32, 16, 1088, 256


def _decode_program(cfg, v5e):
    """``filters/llm.py decode_chunk``'s shape: 8 decode steps, the pool
    carried through the step scan as well."""
    def decode_chunk(params, pool, tok, tables, pos):
        def step(carry, _):
            tok, pool, p = carry
            logits, pool = llama.forward_paged(
                params, tok[:, None], pool, tables, p, cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, pool, p + 1), nxt

        (tok, pool, _), toks = jax.lax.scan(
            step, (tok, pool, pos), None, length=8)
        return toks, tok, pool

    return decode_chunk, (
        v5e((POOL_SLOTS,), jnp.int32),
        v5e((POOL_SLOTS, POOL_MAX_BLOCKS), jnp.int32),
        v5e((POOL_SLOTS,), jnp.int32))


def _prefill_step(cfg, temperature=0.0, top_k=0, top_p=1.0):
    """``filters/llm.py prefill_step``: one prefill chunk, and at its end
    the first token sampled and committed by the loop's own
    ``_commit_first_token`` (greedy, as every cell runs it, unless asked)."""
    from nnstreamer_tpu.filters.llm import _commit_first_token

    def prefill_step(params, pool, toks, table, ctl, tok, keys, base_key):
        pos0, logit_off, adm_no, slot = ctl[:1], ctl[1], ctl[2], ctl[3]
        logits, pool = llama.forward_paged(
            params, toks, pool, table, pos0, cfg, logit_off=logit_off,
            n_valid=logit_off + 1 if cfg.n_conv_layers else None)
        first, tok, keys = _commit_first_token(
            logits[:, 0], tok, keys, base_key, adm_no, slot,
            pos0[0] + logit_off + 1, temperature, top_k, top_p)
        return first, tok, keys, pool

    return prefill_step


#: what the loop donates to ``prefill_step``: the pool, ``tok``, ``keys``
PREFILL_DONATED = (1, 5, 6)


def _prefill_args(v5e, slots, table):
    """One [1, 32] chunk over ``table``, its four host values (first
    position, last real token's offset, admission number, slot), then the
    carried ``tok`` and slot keys and the seed's key."""
    return (v5e((1, 32), jnp.int32), table, v5e((4,), jnp.int32),
            v5e((slots,), jnp.int32), v5e((slots, 2), jnp.uint32),
            v5e((2,), jnp.uint32))


def _prefill_program(cfg, v5e, **sampler):
    return _prefill_step(cfg, **sampler), _prefill_args(
        v5e, POOL_SLOTS, v5e((1, POOL_MAX_BLOCKS), jnp.int32))


def _sampled_prefill_program(cfg, v5e):
    return _prefill_program(cfg, v5e, temperature=0.8, top_k=40, top_p=0.9)


@pytest.mark.parametrize(
    "program", [_decode_program, _prefill_program, _sampled_prefill_program],
    ids=["decode_chunk", "prefill_step", "prefill_step_sampled"])
def test_serve_program_never_moves_the_pool(v5e, monkeypatch, decode_programs,
                                           program):
    """The compiled serve programs write the new K/V rows into the donated
    pool in place: no operation copies the pool, slices a layer out of it
    or writes a layer back, and the program holds no second pool among its
    temporaries.  (A pool scanned as the layer loop's inputs and outputs
    makes both programs do all three: 2.28 GB of copies per decode step
    at full depth.)  The prefill chunk ends in the first token's sampler
    and commit (PR 37): its temporaries are 645,120 B greedy and 876,032 B
    sampled where the chunk alone has 322,560 B (chipless compile, PR 37;
    ``-rP`` prints them)."""
    import dataclasses
    import math
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(CFG, **POOL_CFG)
    params = _abstract(
        v5e, lambda: llama.init_params_int8(cfg, 0, "bfloat16"))
    pool = _abstract(
        v5e, lambda: llama.init_paged_cache(cfg, POOL_BLOCKS, POOL_BS))
    if program is _decode_program:
        compiled = decode_programs("dense")[2]
    else:
        fn, args = program(cfg, v5e)
        compiled = jax.jit(fn, donate_argnums=PREFILL_DONATED).lower(
            params, pool, *args).compile()

    layer_elems = math.prod(pool["k"].shape[1:])
    moved = {layer_elems, cfg.n_layers * layer_elems}
    # every instruction line, the bodies of fusions included, so that a
    # fusion whose root (or whose operand of a bitcast root) moves the
    # pool is found like a bare operation
    inst = re.compile(r"= \w+\[([\d,]+)\]\S* "
                      r"(copy|dynamic-slice|dynamic-update-slice)\(")
    movers = [line.strip()[:160] for line in compiled.as_text().splitlines()
              for m in [inst.search(line)] if m
              and math.prod(map(int, m.group(1).split(","))) in moved]
    assert not movers, "the pool is moved:\n" + "\n".join(movers)

    mem = compiled.memory_analysis()
    layer_bytes = 2 * layer_elems * pool["k"].dtype.itemsize  # K + V
    assert mem.temp_size_in_bytes < layer_bytes
    assert mem.alias_size_in_bytes >= cfg.n_layers * layer_bytes
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(name, getattr(mem, name))


def _chunk_kernel_layers(fn, donated, *args):
    """The attention layers of ``fn``'s program that took the chunk kernel,
    counted on its trace as the serve loop counts them."""
    return A.chunk_kernel_calls(
        jax.jit(fn, donate_argnums=donated).trace(*args).jaxpr)


@pytest.mark.parametrize("T,rows", [(32, 1), (5, 4), (256, 1)],
                         ids=["prefill_chunk", "spec_verify",
                              "prefill_chunk_256"])
@pytest.mark.parametrize("heads,hd", [((32, 8), 128), ((64, 8), 128),
                                      ((32, 8), 64), ((20, 1), 128)],
                         ids=["mistral32x8", "exaone64x8", "conv_cell32x8x64",
                              "ssm_cell20x1"])
def test_paged_chunk_attention_compiles(v5e, heads, hd, T, rows):
    """The chunk kernel alone at the cells' head layouts over a table of
    4,096 / 16 entries: a prefill chunk of 32 and a speculative verify of
    ``k + 1`` = 5 a row over 4 rows, and a chunk of 256, whose queries do
    not fit VMEM at once (a 64-query chunk of 64 heads did not compile
    whole) and go in tiles.  Its result is 4-D, the queries grouped by KV
    head and the chunk front-padded to whole tiles, and its name is not
    the decode kernel's: no reader of the decode kernel counts it."""
    import re

    h, hkv = heads
    pack = A.kv_lane_pack(hkv, hd)
    pool = v5e((2 * CONV_BLOCKS, 16, hkv // pack, hd * pack), jnp.bfloat16)
    lowered = _compile(
        A.CHUNK_KERNEL,
        lambda q, k, v, t, n: A.paged_attention(q, k, v, t, n,
                                                interpret=False),
        v5e((rows, T, h, hd), jnp.bfloat16), pool, pool,
        v5e((rows, 256), jnp.int32), v5e((rows,), jnp.int32))
    text = lowered.compile().as_text()
    groups = hkv // pack
    got = re.search(rf"%{A.CHUNK_KERNEL}[.\d]* = bf16\[{rows},{groups},"
                    rf"(\d+),128\]", text)
    padded, left = divmod(int(got.group(1)), h // groups)
    assert not left and (padded == T if T <= 32 else T <= padded < 1.25 * T)
    assert "%paged_attention" not in text
    # the pool goes to the kernel as it is stored: nothing copies it
    assert not re.search(rf"= bf16\[{2 * CONV_BLOCKS},\S* copy\(", text)


def test_prefill_chunk_takes_the_chunk_kernel(v5e, monkeypatch):
    """``prefill_step`` at ``decode_c32``'s shapes (Mistral geometry, 32
    slots, tables of 256 entries; depth cut to 4): every layer's attention
    is the chunk kernel, and no operation gathers the row's table of
    ``[256, 16, 8, 128]`` K or V (the gather reference did, twice a
    layer, with the transposes, relayouts and GQA broadcasts of 4,096
    positions behind it).  ``-rP`` prints the temporaries."""
    import dataclasses

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(CFG, **POOL_CFG)
    params = _abstract(
        v5e, lambda: llama.init_params_int8(cfg, 0, "bfloat16"))
    pool = _abstract(
        v5e, lambda: llama.init_paged_cache(cfg, POOL_BLOCKS, POOL_BS))
    fn, args = _prefill_program(cfg, v5e)
    assert _chunk_kernel_layers(fn, PREFILL_DONATED, params, pool,
                                *args) == cfg.n_layers
    lowered = jax.jit(fn, donate_argnums=PREFILL_DONATED).lower(
        params, pool, *args)
    assert f'kernel_name = "{A.CHUNK_KERNEL}"' in lowered.as_text()
    compiled = lowered.compile()
    table = f"{POOL_MAX_BLOCKS},{POOL_BS},8,128]"
    gathers = [line.strip()[:160] for line in compiled.as_text().splitlines()
               if table in line.split("=", 1)[-1].split("(", 1)[0]]
    assert not gathers, "\n".join(gathers)
    mem = compiled.memory_analysis()
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(name, getattr(mem, name))


# -- window and full attention in one paged cache, sparse experts (PR 29) ---

#: the benchmark's patterned serving cell: 64 slots, 64 query heads over 8
#: KV heads of 128, window 128, blocks of 16, a ring of 8 + 2 + 1 blocks
HYB_SLOTS, HYB_H, HYB_HKV, HYB_RING = 64, 64, 8, 11


@pytest.mark.parametrize("window,ring", [(0, False), (128, False),
                                         (128, True)],
                         ids=["full", "window", "window_ring"])
def test_paged_attention_compiles_with_a_window(v5e, window, ring):
    """q [64, 64, 128] over 8 KV heads: without a window, with one over an
    ordinary table, and with one over a slot's ring (the table's width is
    the modulus)."""
    bs, n_blocks = 16, HYB_SLOTS * HYB_RING
    width = HYB_RING if ring else 256
    _compile(
        "paged_attention",
        lambda q, k, v, t, n: A.paged_attention(
            q, k, v, t, n, interpret=False, window=window, ring=ring),
        v5e((HYB_SLOTS, 1, HYB_H, D), jnp.bfloat16),
        v5e((n_blocks, bs, HYB_HKV, D), jnp.bfloat16),
        v5e((n_blocks, bs, HYB_HKV, D), jnp.bfloat16),
        v5e((HYB_SLOTS, width), jnp.int32), v5e((HYB_SLOTS,), jnp.int32))


def _hybrid_cfg(L):
    """The patterned configuration's first ``L`` layers at published
    widths: layer 0 dense, then 16 held experts of 2048 out of 128."""
    from nnstreamer_tpu.models.moe import ExpertsConfig

    return llama.LlamaConfig(
        vocab=19200, dim=6144, n_layers=L, n_heads=HYB_H,
        n_kv_heads=HYB_HKV, ffn_hidden=18432, max_seq=4096, rope_theta=1e6,
        head_size=128, qk_norm=True,
        pattern=tuple(llama.LayerKind(
            window=0 if l % 4 == 3 else 128, rope=l % 4 != 3,
            ffn="dense" if l == 0 else "experts") for l in range(L)),
        experts=ExpertsConfig(n_experts=128, top_k=8, hidden=2048, shared=1,
                              scale=2.5, held_first=0, held_count=16))


def test_patterned_decode_step_compiles_at_published_widths(v5e,
                                                            monkeypatch):
    """One period of the benchmark's patterned configuration — hidden 6144,
    64/8 heads of 128, a dense layer of 18432, three sparse layers of 16
    held experts of 2048 out of 128 routed, window 128 — as the decode
    step of 64 slots: both pools carried, the paged kernel in it twice
    over (window layers through their ring), the grouped expert product a
    custom call, and NO expert matrix taken out of its stack (a slice that
    feeds a custom call is materialized: 0.4 GB a matrix a layer, which is
    why ``moe_ffn`` takes the whole stack)."""
    import math

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L = 4
    cfg = _hybrid_cfg(L)
    bs, n_blocks = 16, HYB_SLOTS * 50
    params = _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(
        cfg, n_blocks, bs, win_blocks=HYB_SLOTS * HYB_RING))
    assert llama.window_ring_blocks(cfg, bs, 32) == HYB_RING
    tables = {"full": v5e((HYB_SLOTS, 256), jnp.int32),
              "win": v5e((HYB_SLOTS, HYB_RING), jnp.int32)}
    compiled = jax.jit(
        lambda p, tok, pool, tb, pos: llama.forward_paged(
            p, tok, pool, tb, pos, cfg, with_stats=True),
        donate_argnums=(2,)).lower(
            params, v5e((HYB_SLOTS, 1), jnp.int32), pool, tables,
            v5e((HYB_SLOTS,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("%paged_attention") >= L
    assert "%ragged-dot" in text
    stack = 3 * 16 * 6144 * 2048 * 2   # one layer's experts, one matrix
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < stack, mem.temp_size_in_bytes
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes   # both pools in place


# -- latent attention, shortcut experts, identity experts (PR 33) -----------

#: the benchmark's latent serving cell: 64 slots, 64 heads on ONE cache row
#: of 512 + 64 values, 8 attention blocks (4 published layers of two), a
#: pool of 64 x ceil(1056 / 16) blocks of 16, tables of 4096 / 16 entries
LAT_SLOTS, LAT_BLOCKS, LAT_MAX_BLOCKS = 64, 64 * 66, 256


def _latent_cfg():
    from nnstreamer_tpu.models.moe import ExpertsConfig

    return llama.LlamaConfig(
        vocab=16384, dim=6144, n_layers=8, n_heads=64, n_kv_heads=1,
        ffn_hidden=12288, max_seq=4096, rope_theta=1e7,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128, q_lora_scale=2.0, kv_lora_scale=12 ** 0.5,
        pattern=tuple(llama.LayerKind(
            latent=True, shortcut="close" if l % 2 else "open")
            for l in range(8)),
        experts=ExpertsConfig(n_experts=512, top_k=12, hidden=2048,
                              scoring="softmax", norm_topk=False, scale=6.0,
                              zero_experts=256, held_first=0, held_count=16))


def _sparse_decode_chunk(cfg):
    """``filters/llm.py decode_chunk`` for a model with experts: 8 steps,
    the pools carried, the routing counts riding home under the tokens."""
    def decode_chunk(params, pool, tok, tables, pos):
        def step(carry, _):
            tok, pool, p = carry
            logits, pool, stats = llama.forward_paged(
                params, tok[:, None], pool, tables, p, cfg, with_stats=True)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, pool, p + 1), (nxt, stats)

        (tok, pool, _), (toks, stats) = jax.lax.scan(
            step, (tok, pool, pos), None, length=8)
        return jnp.concatenate([toks.T, stats.T], axis=0), tok, pool

    return decode_chunk


def _the_grouped_kernel_is_the_expert_product(text, rows, width=6144):
    """The compiled text holds the expert product as the repo's kernel —
    a custom call named ``ragged-dot…`` whose result is ``[rows, width]``,
    what the roofline readers look for — and none of XLA's own, whose
    row tile (``ragged_dot_tiling``) is sized to the static row count."""
    import re

    assert re.search(rf"%ragged-dot-swiglu[.\d]* = f32\[{rows},{width}\]",
                     text)
    assert "ragged_dot_tiling" not in text


def _the_combine_is_no_scatter(text, width):
    """No scatter into ``f32[rows, width]`` under ``moe.experts`` is left
    in the compiled text: each token's routed outputs are gathered where
    the sort put them and summed into its row (``models/moe.py combine``),
    where a scatter-add ran at ≈ 15 % of its memory floor on a v5e."""
    import re

    scatters = [line.strip()[:200] for line in text.splitlines()
                if re.search(rf"= f32\[\d+,{width}\]\S* scatter\(", line)
                and "moe.experts" in line]
    assert not scatters, "the combine is a scatter:\n" + "\n".join(scatters)


def test_paged_latent_attention_compiles(v5e):
    """The latent decode kernel alone at the cell's shapes: 64 query heads
    of 576 on one pool row padded to 640, the value its first 512 columns."""
    _compile(
        "paged_latent_attention",
        lambda q, pool, tbl, lens: A.paged_latent_attention(
            q, pool, tbl, lens, v_width=512, scale=192 ** -0.5,
            interpret=False),
        v5e((LAT_SLOTS, 1, 64, 576), jnp.bfloat16),
        v5e((8 * LAT_BLOCKS, 16, A.latent_pool_width(576)), jnp.bfloat16),
        v5e((LAT_SLOTS, LAT_MAX_BLOCKS), jnp.int32),
        v5e((LAT_SLOTS,), jnp.int32))


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_step"])
def test_latent_serve_programs_compile_at_the_cells_shapes(
        v5e, monkeypatch, program):
    """Both programs of the latent cell at its own sizes — published
    widths, 8 sub-layers walked as a scan of four periods of two, 16 held
    experts of 512 routed + 256 identity, 64 slots, the latent pool of
    4,224 blocks carried and updated in place: they fit the chip, the
    decode step holds the latent kernel (the prefill chunk takes its plain
    reference and holds none) and the grouped expert product, and neither
    slices an expert matrix out of its stack.  The bytes go to PERF.md §4
    (``-rP`` prints them)."""
    import math

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _latent_cfg()
    assert llama.walk_plan(cfg.kinds) == llama.WalkPlan(0, 2, 4)
    params = _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(cfg, LAT_BLOCKS, 16))
    assert {k: v.shape for k, v in pool.items()} == {
        "c": (8, LAT_BLOCKS, 16, 640)}

    if program == "decode_chunk":
        fn, donated, args = _sparse_decode_chunk(cfg), (1,), (
            v5e((LAT_SLOTS,), jnp.int32),
            v5e((LAT_SLOTS, LAT_MAX_BLOCKS), jnp.int32),
            v5e((LAT_SLOTS,), jnp.int32))
    else:
        fn, donated, args = _prefill_step(cfg), PREFILL_DONATED, \
            _prefill_args(v5e, LAT_SLOTS,
                          v5e((1, LAT_MAX_BLOCKS), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=donated).lower(
        params, pool, *args).compile()
    text = compiled.as_text()
    assert ("%paged_latent_attention" in text) == (program == "decode_chunk")
    assert A.CHUNK_KERNEL not in text   # a latent pool has no chunk kernel
    assert "%ragged-dot" in text
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(pool["c"].shape) * 2
    assert mem.alias_size_in_bytes >= pool_bytes     # the pool in place
    stack = 4 * 16 * 6144 * 2048 * 2   # the kind's experts, one matrix
    assert mem.temp_size_in_bytes < stack, mem.temp_size_in_bytes
    # 64 x top-12 rows a decode step, 32 x 12 a prefill chunk; PERF.md
    # section 4's temporaries (XLA's grouped product, PR 33) do not grow
    _the_grouped_kernel_is_the_expert_product(
        text, 768 if program == "decode_chunk" else 384)
    _the_combine_is_no_scatter(text, 6144)
    assert mem.temp_size_in_bytes <= (
        511_662_592 if program == "decode_chunk" else 6_582_784)
    bytes_limit = 16_909_336_064
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < bytes_limit
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(program, name, getattr(mem, name))


# -- the grouped expert kernel whose row tile fits decode (PR 34) -----------

@pytest.mark.parametrize("rows,groups,expect", [
    (512, 112, 4), (768, 64, 1), (256, 112, 2), (384, 64, 0.5)],
    ids=["k_exaone_decode", "longcat_decode", "k_exaone_prefill",
         "longcat_prefill"])
def test_grouped_swiglu_compiles(v5e, rows, groups, expect):
    """``ops/grouped_ffn.py`` alone at the four shapes the serve programs
    of the two sparse cells call it with (64 slots x top-8 / top-12 and a
    prefill chunk of 32; 7 x 16 / 4 x 16 groups of 6144 x 2048): the rule
    sends all four to the kernel, Mosaic takes it, and the compiled call
    is named and shaped as the roofline readers expect."""
    from nnstreamer_tpu.ops import grouped_ffn as GF

    D, F = 6144, 2048
    lowered = _compile(
        "ragged-dot-swiglu",
        lambda x, g, u, d, n: GF.grouped_swiglu(
            x, g, u, d, n, live=16, expect=expect, interpret=False),
        v5e((rows, D), jnp.bfloat16), v5e((groups, D, F), jnp.bfloat16),
        v5e((groups, D, F), jnp.bfloat16), v5e((groups, F, D), jnp.bfloat16),
        v5e((groups,), jnp.int32))
    _the_grouped_kernel_is_the_expert_product(
        lowered.compile().as_text(), rows)


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_step"])
def test_hybrid_serve_programs_compile_at_the_cells_shapes(
        v5e, monkeypatch, decode_programs, program):
    """Both programs of the patterned cell at its own sizes — 8 layers
    ``LLLG LLLG``, layer 0 dense, 7 x 16 held experts, 64 slots, 3,200
    blocks and the rings: the expert product is the grouped kernel over
    ``[512, 6144]`` rows a decode step (``[256, 6144]`` a prefill chunk),
    none of XLA's is left, both pools are updated in place and the
    temporaries are no larger than PERF.md section 4's (``decode_chunk``
    1.53 GB with XLA's product, chipless compile, PR 29; ``prefill_step``
    588,947,456 B while it copied the attention weights transposed,
    nearly all of that; 14,501,888 B since the projections of a chunk
    keep their weights' layout): the chunk copies no weight."""
    import math
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _hybrid_cfg(8)
    params = _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(
        cfg, 3200, 16, win_blocks=HYB_SLOTS * HYB_RING))
    if program == "decode_chunk":
        compiled = decode_programs("hybrid")[2]
    else:
        fn, donated, args = _prefill_step(cfg), PREFILL_DONATED, \
            _prefill_args(v5e, HYB_SLOTS, {
                "full": v5e((1, 256), jnp.int32),
                "win": v5e((1, HYB_RING), jnp.int32)})
        compiled = jax.jit(fn, donate_argnums=donated).lower(
            params, pool, *args).compile()
    text = compiled.as_text()
    _the_grouped_kernel_is_the_expert_product(
        text, 512 if program == "decode_chunk" else 256)
    _the_combine_is_no_scatter(text, 6144)
    assert ("%paged_attention" in text) == (program == "decode_chunk")
    # a prefill chunk's two full layers take the chunk kernel; its six
    # window layers keep the gather reference over their ring
    assert (f"%{A.CHUNK_KERNEL}" in text) == (program == "prefill_step")
    if program == "prefill_step":
        assert _chunk_kernel_layers(fn, donated, params, pool, *args) == 2
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes <= (
        1_530_000_000 if program == "decode_chunk" else 15_000_000), \
        mem.temp_size_in_bytes
    weight = re.compile(r"= bf16\[(8192,6144|6144,8192|1024,6144)\]\S* "
                        r"(copy|transpose)\(")
    assert not [line for line in text.splitlines() if weight.search(line)]
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(program, name, getattr(mem, name))


# -- convolution state beside the paged cache, heads of 64 (PR 35) ----------

#: the convolution cell: 64 slots, 32 query heads over 8 KV heads of 64,
#: 4,224 blocks of 16, tables of 4,096 / 16 entries
CONV_SLOTS, CONV_BLOCKS, CONV_MAX_BLOCKS = 64, 4224, 256


def _conv_cfg():
    from nnstreamer_tpu.models.moe import ExpertsConfig

    return llama.LlamaConfig(
        vocab=65536, dim=2048, n_layers=10, n_heads=32, n_kv_heads=8,
        ffn_hidden=11776, max_seq=4096, rope_theta=1e6, qk_norm=True,
        pattern=tuple(
            llama.LayerKind(conv=l < 2 or l % 4 != 2,
                            ffn="dense" if l < 2 else "experts")
            for l in range(10)),
        experts=ExpertsConfig(n_experts=64, top_k=4, hidden=1536,
                              norm_eps=1e-6))


@pytest.mark.parametrize("slots,hkv,hd", [
    (64, 8, 64), (4, 8, 64), (64, 4, 32)],
    ids=["the_cell", "four_slots", "heads_of_32"])
def test_paged_attention_compiles_at_narrow_heads(v5e, slots, hkv, hd):
    """Heads narrower than the 128 lanes go through the same kernel, two
    (four) KV heads to a lane row: the pool of the convolution cell — 2
    layers x 4,224 blocks of [16, 8, 64] — is handed over as [16 x 4, 128]
    blocks, STORED that way (a pool stored ``[16, 8, 64]`` is another tiled
    layout on the chip: repacking it copied the whole pool twice a step,
    my first chip run), and the call is named as at 128 lanes, its result
    ``[slots, heads, 128]``."""
    import re

    pack = A.kv_lane_pack(hkv, hd)
    assert pack * hd == 128
    pool = v5e((2 * CONV_BLOCKS, 16, hkv // pack, 128), jnp.bfloat16)
    lowered = _compile(
        "paged_attention",
        lambda q, k, v, t, n: A.paged_attention(q, k, v, t, n,
                                                interpret=False),
        v5e((slots, 1, 32, hd), jnp.bfloat16), pool, pool,
        v5e((slots, CONV_MAX_BLOCKS), jnp.int32), v5e((slots,), jnp.int32))
    text = lowered.compile().as_text()
    assert re.search(rf"%paged_attention[.\d]* = bf16\[{slots},32,128\]",
                     text)
    # the pool goes to the kernel as it is stored: nothing repacks it
    assert not re.search(rf"= bf16\[{2 * CONV_BLOCKS},\S* copy\(", text)


def test_grouped_swiglu_compiles_with_every_expert_held(v5e):
    """The grouped kernel at the convolution cell's shapes: 64 slots x
    top-4 rows (and a prefill chunk's 32 x 4) over 8 layers x 64 experts of
    2048 x 1536, all of a layer's 64 live."""
    from nnstreamer_tpu.ops import grouped_ffn as GF

    D, F, G = 2048, 1536, 8 * 64
    assert GF._stream_tile(D, 2 * F * 2) == 1024
    assert GF._stream_tile(F, D * 2) == 1536
    for rows in (256, 128):
        lowered = _compile(
            "ragged-dot-swiglu",
            lambda x, g, u, d, n: GF.grouped_swiglu(
                x, g, u, d, n, live=64, expect=rows / 64, interpret=False),
            v5e((rows, D), jnp.bfloat16), v5e((G, D, F), jnp.bfloat16),
            v5e((G, D, F), jnp.bfloat16), v5e((G, F, D), jnp.bfloat16),
            v5e((G,), jnp.int32))
        _the_grouped_kernel_is_the_expert_product(
            lowered.compile().as_text(), rows, D)


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_step"])
def test_conv_serve_programs_compile_at_the_cells_shapes(
        v5e, monkeypatch, decode_programs, program):
    """Both programs of the convolution cell at its own sizes — published
    widths, 10 layers (two leading conv + dense, then two periods of
    attention, conv, conv, conv, all sparse), 64 experts top-4 every one
    held, 64 slots, the K/V pool of 4,224 blocks and the slots' state
    carried and updated in place: they fit the chip; the decode step holds
    the paged kernel in its narrow-head form (the prefill chunk the chunk
    kernel, packed alike) and both hold the grouped expert product; no
    operation copies the state leaf.  The bytes go to PERF.md section 4
    (``-rP`` prints them)."""
    import math
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _conv_cfg()
    assert llama.walk_plan(cfg.kinds) == llama.WalkPlan(2, 4, 2)
    params = _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(
        cfg, CONV_BLOCKS, 16, slots=CONV_SLOTS))
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, CONV_BLOCKS, 16, 4, 128), "v": (2, CONV_BLOCKS, 16, 4, 128),
        "conv": (8, CONV_SLOTS, 2, 2048)}

    if program == "decode_chunk":
        compiled = decode_programs("conv")[2]
    else:
        fn, donated, args = _prefill_step(cfg), PREFILL_DONATED, \
            _prefill_args(v5e, CONV_SLOTS, {
                "full": v5e((1, CONV_MAX_BLOCKS), jnp.int32),
                "slot": v5e((1,), jnp.int32)})
        compiled = jax.jit(fn, donate_argnums=donated).lower(
            params, pool, *args).compile()
    text = compiled.as_text()
    assert ("%paged_attention" in text) == (program == "decode_chunk")
    # the stage's two attention layers take the chunk kernel, packed
    assert (f"%{A.CHUNK_KERNEL}" in text) == (program == "prefill_step")
    if program == "prefill_step":
        assert _chunk_kernel_layers(fn, donated, params, pool, *args) == 2
    _the_grouped_kernel_is_the_expert_product(
        text, 256 if program == "decode_chunk" else 128, 2048)
    _the_combine_is_no_scatter(text, 2048)
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * 2 for x in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes     # all leaves in place
    # no operation copies the state leaf, flat or by layer (its rows are
    # gathered, 64 at a time, and written by scatters in place)
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= bf16\[(512|8,64),2,2048\]\S* copy\(", line)
              # ... nor a K/V pool, whole (2 layers x 4,224 blocks)
              or re.search(r"= bf16\[(8448|2,4224),\S* copy\(", line)]
    assert not copies, "a pool leaf is copied:\n" + "\n".join(copies)
    bytes_limit = 16_909_336_064
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < bytes_limit
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(program, name, getattr(mem, name))


# -- the decode programs stay as they were ----------------------------------

def _decode_case(v5e, cell):
    """The decode program of a serving cell's geometry (as the tests above
    build it) and its abstract arguments."""
    import dataclasses

    if cell == "dense":
        cfg = dataclasses.replace(CFG, **POOL_CFG)
        params = _abstract(
            v5e, lambda: llama.init_params_int8(cfg, 0, "bfloat16"))
        fn, args = _decode_program(cfg, v5e)
        return fn, params, _abstract(v5e, lambda: llama.init_paged_cache(
            cfg, POOL_BLOCKS, POOL_BS)), args
    cfg, slots, blocks, extra = {
        "hybrid": (_hybrid_cfg(8), HYB_SLOTS, 3200,
                   dict(win_blocks=HYB_SLOTS * HYB_RING)),
        "latent": (_latent_cfg(), LAT_SLOTS, LAT_BLOCKS, {}),
        "axk": (_axk1_cfg(), AXK_SLOTS, AXK_BLOCKS, {}),
        "conv": (_conv_cfg(), CONV_SLOTS, CONV_BLOCKS,
                 dict(slots=CONV_SLOTS)),
        "ssm": (_ssm_cfg(), SSM_SLOTS, SSM_BLOCKS,
                dict(slots=SSM_SLOTS))}[cell]
    table = v5e((slots, 256), jnp.int32)
    by_slot = {"full": table, "slot": v5e((slots,), jnp.int32)}
    tables = {"hybrid": {"full": table,
                         "win": v5e((slots, HYB_RING), jnp.int32)},
              "latent": table, "axk": table, "conv": by_slot, "ssm": by_slot}
    chunk = _dense_decode_chunk if cell == "ssm" else _sparse_decode_chunk
    return (chunk(cfg),
            _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16")),
            _abstract(v5e, lambda: llama.init_paged_cache(
                cfg, blocks, 16, **extra)),
            (v5e((slots,), jnp.int32), tables[cell],
             v5e((slots,), jnp.int32)))


def _kernel_bodies(jaxpr):
    """Every Pallas call of a program, traced: its name, grid, block index
    maps and body (no source locations, unlike the serialized bodies)."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            grid = eqn.params["grid_mapping"]
            yield "\n".join([eqn.params["name"], str(grid)] + [
                str(b.index_map_jaxpr) for b in grid.block_mappings] + [
                str(eqn.params["compiler_params"]), str(eqn.params["jaxpr"])])
        else:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from _kernel_bodies(inner)


#: sha256 of each serving geometry's decode program: its text as lowered
#: for v5e with the kernels' serialized bodies blanked (they carry source
#: locations), then the traced bodies of its Pallas kernels.  The expert
#: layers' combine, row gathers in place of a scatter-add, moved the three
#: with experts (hybrid, latent, conv); dense kept its pin.
DECODE_PINS = dict(
    dense="432702e967facb32ae0bacd72e4ebc6d765147cb0c5b3bcdcb435d5a7c62ed34",
    hybrid="cbae257752cbd793de0cd8a70daa5cad7e168d88acf085a40c7c667493e88152",
    latent="c0c84f65c69307008b455e19652fe0180b31c0663a35d34ed429d97545017e47",
    conv="26b4434c5948d8fded0149af761932614fadd3a674f57d3a84ec606c722e4b0f")


@pytest.mark.parametrize("cell", sorted(DECODE_PINS))
def test_decode_programs_are_pinned(v5e, monkeypatch, cell):
    """The decode step of ``decode_c32`` and of the three 64-slot cells is
    the program their accepted numbers were measured on: neither XLA's part
    nor a kernel's body moved.  A change meant to move one updates its pin
    here (the assertion prints the new digest)."""
    import hashlib
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, params, pool, args = _decode_case(v5e, cell)
    jitted = jax.jit(fn, donate_argnums=(1,))
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""',
                  jitted.lower(params, pool, *args).as_text())
    bodies = list(_kernel_bodies(jitted.trace(params, pool, *args).jaxpr))
    assert bodies, "the decode program calls no Pallas kernel"
    digest = hashlib.sha256("\n".join([text] + bodies).encode()).hexdigest()
    assert digest == DECODE_PINS[cell], digest


@pytest.mark.parametrize("cell,combines", [
    ("hybrid", 7), ("latent", 4), ("conv", 8), ("axk", 7), ("dense", 0),
    ("ssm", 0)])
def test_decode_programs_count_their_combines(v5e, monkeypatch, cell,
                                              combines):
    """What the serve loop reports as ``moe_combine_dense_layers``: the
    expert combines of a decode step, counted on the program's trace (8
    steps a chunk) — every expert layer of the four sparse geometries,
    none where there are no experts."""
    from nnstreamer_tpu.models.moe import combine_calls

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, params, pool, args = _decode_case(v5e, cell)
    jaxpr = jax.jit(fn, donate_argnums=(1,)).trace(params, pool, *args).jaxpr
    assert combine_calls(jaxpr) == 8 * combines


#: temporaries of each decode program's chipless v5e compile, in bytes,
#: that its q/k/v products keep under: the programs that copied the
#: weights transposed held 18,608,640 (dense, 4 layers), 1,519,780,352
#: (hybrid), 22,133,760 (conv) and 113,265,152 (ssm); reading them where
#: they lie, 565,248, 16,971,776, 12,745,216 and 86,792,704
QKV_TEMP_BOUNDS = dict(dense=1 << 20, hybrid=64 << 20, conv=16 << 20,
                       ssm=96 << 20)


@pytest.mark.parametrize("cell", sorted(QKV_TEMP_BOUNDS))
def test_decode_programs_read_qkv_where_they_lie(monkeypatch, decode_programs,
                                                cell):
    """The decode program of every geometry whose attention layers are
    paged reads each layer's q, k and v weights where they lie: no
    operation copies or transposes a ``w[qkv]`` stack, and none takes a
    layer's projection out of one (its slice fuses into the product).
    Without the barrier on the three products XLA copied each stack
    transposed once a chunk and sliced a layer out of that copy in every
    layer of every step: ≈ 10 % of ``decode_c32``'s device time on a v5e.
    ``-rP`` prints the temporaries."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _jitted, (params, *_), compiled = decode_programs(cell)
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if re.fullmatch(r"w[qkv](_q)?", str(getattr(path[-1], "key", ""))):
            dims = ",".join(map(str, leaf.shape))
            shapes |= {dims, ",".join(map(str, (1,) + leaf.shape[1:]))}
    assert shapes, "no q/k/v weight stack in the parameters"
    # an operation whose result is a stack or a layer's slice of one: a
    # copy or transpose, a fusion, or a slice that is a fusion's root
    # (a slice that is not a root is fused into its product)
    inst = re.compile(r"^(ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(")
    moved = [line.strip()[:160] for line in compiled.as_text().splitlines()
             for m in [inst.match(line.strip())] if m
             and m.group(2) in shapes
             and (m.group(3) in ("copy", "copy-start", "transpose", "fusion")
                  or m.group(1) and "slice" in m.group(3))]
    assert not moved, "a q/k/v weight is moved:\n" + "\n".join(moved)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(cell, "temp_size_in_bytes", temp)
    assert temp < QKV_TEMP_BOUNDS[cell], temp


# -- state-space layers beside the paged cache, MQA 20/1 -------------------

#: the state-space cell: 64 slots, 20 query heads over 1 KV head of 128,
#: 4,224 blocks of 16, tables of 4,096 / 16 entries
SSM_SLOTS, SSM_BLOCKS = 64, 4224


def _ssm_cfg():
    """The published Jamba2-3B geometry, whole: 28 layers, attention at
    layers 7 and 21, Mamba layers of 5120 channels, a state of 16."""
    return llama.LlamaConfig(
        vocab=65536, dim=2560, n_layers=28, n_heads=20, n_kv_heads=1,
        ffn_hidden=8192, max_seq=4096, norm_eps=1e-6, ssm_expand=2,
        ssm_state=16, ssm_dt_rank=160, ssm_conv_taps=4,
        pattern=tuple(llama.LayerKind(rope=False) if l % 14 == 7
                      else llama.LayerKind(ssm=True) for l in range(28)))


def _dense_decode_chunk(cfg):
    """``filters/llm.py decode_chunk`` for a model without experts: 8
    steps, the pools carried."""
    def decode_chunk(params, pool, tok, tables, pos):
        def step(carry, _):
            tok, pool, p = carry
            logits, pool = llama.forward_paged(
                params, tok[:, None], pool, tables, p, cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, pool, p + 1), nxt

        (tok, pool, _), toks = jax.lax.scan(
            step, (tok, pool, pos), None, length=8)
        return toks.T, tok, pool

    return decode_chunk


@pytest.mark.parametrize("slots", [64, 4])
def test_paged_attention_compiles_at_20_heads_over_1(v5e, slots):
    """The decode kernel at the state-space cell's attention: 20 query
    heads over ONE KV head of 128 (a block's 16 rows fill the bfloat16
    sublane tile on their own), its result ``[slots, 20, 128]``."""
    import re

    pool = v5e((2 * SSM_BLOCKS, 16, 1, 128), jnp.bfloat16)
    lowered = _compile(
        "paged_attention",
        lambda q, k, v, t, n: A.paged_attention(q, k, v, t, n,
                                                interpret=False),
        v5e((slots, 1, 20, 128), jnp.bfloat16), pool, pool,
        v5e((slots, 256), jnp.int32), v5e((slots,), jnp.int32))
    assert re.search(rf"%paged_attention[.\d]* = bf16\[{slots},20,128\]",
                     lowered.compile().as_text())


@pytest.mark.parametrize("rows", [64, 1])
def test_ssm_update_compiles(v5e, rows):
    """The update kernel at the cell's widths, over the whole state leaf
    (26 layers x 64 slots x [16, 5120] float32), which it updates where
    it lies: donated, the leaf is aliased to the result and no operation
    copies it."""
    import re

    from nnstreamer_tpu.ops import ssm as S

    lowered = jax.jit(
        lambda st, l, sl, lv, fr, x, dt, z, b, c, a, d: S.ssm_update(
            st, l, sl, lv, fr, x, dt, z, b, c, a, d, interpret=False),
        donate_argnums=(0,)).lower(
        v5e((26, SSM_SLOTS, 16, 5120), jnp.float32), v5e((), jnp.int32),
        v5e((rows,), jnp.int32), v5e((rows,), jnp.bool_),
        v5e((rows,), jnp.bool_), v5e((rows, 5120), jnp.bfloat16),
        v5e((rows, 5120), jnp.float32), v5e((rows, 5120), jnp.bfloat16),
        v5e((rows, 16), jnp.float32), v5e((rows, 16), jnp.float32),
        v5e((16, 5120), jnp.float32), v5e((5120,), jnp.float32))
    assert f'kernel_name = "{S.KERNEL}"' in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    assert f"%{S.KERNEL}" in text
    assert not re.search(r"= f32\[26,64,16,5120\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_step"])
def test_ssm_serve_programs_compile_at_the_cells_shapes(
        v5e, monkeypatch, decode_programs, program):
    """Both programs of the state-space cell at its own sizes — the
    published widths and all 28 layers (two periods of 14: thirteen Mamba
    layers and one unrotated attention layer), 64 slots, the K/V pool of
    4,224 blocks of [16, 1, 128] and the slots' state carried and updated
    in place.  The ``ssm`` leaf is allocated at its logical size, 64 x
    8,519,680 B: [16, 5120] float32 tiles fill (8, 128) tiles with no
    lane padding.  The decode step runs the update kernel in every Mamba
    layer and the paged kernel in both attention layers; the prefill
    chunk scans (no update kernel) and takes the chunk kernel in both; no
    operation copies a state leaf.  The bytes go to PERF.md section 4
    (``-rP`` prints them)."""
    import math
    import re

    from nnstreamer_tpu.ops import ssm as S

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _ssm_cfg()
    assert llama.walk_plan(cfg.kinds) == llama.WalkPlan(0, 14, 2)
    params = _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(
        cfg, SSM_BLOCKS, 16, slots=SSM_SLOTS))
    assert {k: (v.shape, v.dtype) for k, v in pool.items()} == {
        "k": ((2, SSM_BLOCKS, 16, 1, 128), jnp.bfloat16),
        "v": ((2, SSM_BLOCKS, 16, 1, 128), jnp.bfloat16),
        "ssm": ((26, SSM_SLOTS, 16, 5120), jnp.float32),
        "ssm_conv": ((26, SSM_SLOTS, 3 * 5120), jnp.bfloat16)}
    assert math.prod(pool["ssm"].shape) * 4 == SSM_SLOTS * 8_519_680 \
        == llama.ssm_state_bytes(cfg, SSM_SLOTS)

    if program == "decode_chunk":
        jitted, args, compiled = decode_programs("ssm")
    else:
        jitted = jax.jit(_prefill_step(cfg), donate_argnums=PREFILL_DONATED)
        args = (params, pool) + _prefill_args(v5e, SSM_SLOTS, {
            "full": v5e((1, 256), jnp.int32), "slot": v5e((1,), jnp.int32)})
        compiled = jitted.lower(*args).compile()
    jaxpr = jitted.trace(*args).jaxpr
    text = compiled.as_text()
    decode = program == "decode_chunk"
    # 26 Mamba layers a step, 8 steps a chunk
    assert A.kernel_calls(jaxpr, S.KERNEL) == (26 * 8 if decode else 0)
    assert (f"%{S.KERNEL}" in text) == decode
    assert ("%paged_attention" in text) == decode
    assert A.chunk_kernel_calls(jaxpr) == (0 if decode else 2)
    # the state leaf's layout: (8, 128) tiles over [16, 5120], no padding
    assert re.search(r"f32\[26,64,16,5120\]\{3,2,1,0:T\(8,128\)\}", text)
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes     # all leaves in place
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= (f32|bf16)\[(26,64|1664),\S* copy\(", line)
              or re.search(r"= bf16\[(8448|2,4224),\S* copy\(", line)]
    assert not copies, "a pool leaf is copied:\n" + "\n".join(copies)
    bytes_limit = 16_909_336_064
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < bytes_limit
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(program, name, getattr(mem, name))


# -- latent attention under YaRN before grouped-routing experts ------------

#: the A.X-K1 share: 64 slots, 64 heads on ONE cache row of 512 + 64
#: values, 8 latent layers, 4,224 blocks of 16, tables of 4096 / 16
AXK_SLOTS, AXK_BLOCKS = 64, 4224


def _axk1_cfg():
    """The benchmark's A.X-K1 share at published widths: layer 0 dense
    (18,432), then 7 expert layers of 12 held experts of 2,048 out of 192
    in 8 groups of 24 (4 kept, 8 a token) beside a shared expert; YaRN
    factor 32 over an original 4,096."""
    from nnstreamer_tpu.models.moe import ExpertsConfig

    return llama.LlamaConfig(
        vocab=20480, dim=7168, n_layers=8, n_heads=64, n_kv_heads=1,
        ffn_hidden=18432, max_seq=4096, norm_eps=1e-6,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128,
        rope_scaling=llama.RopeScaling(32.0, 4096, 32.0, 1.0, 1.0, 1.0),
        pattern=tuple(llama.LayerKind(latent=True,
                                      ffn="dense" if l == 0 else "experts")
                      for l in range(8)),
        experts=ExpertsConfig(n_experts=192, top_k=8, hidden=2048, shared=1,
                              scale=2.5, norm_eps=1e-20, n_group=8,
                              topk_group=4, held_first=0, held_count=12))


def _weights_moved(text, params, leaves):
    """Operations of the compiled ``text`` whose result is a whole stack
    of one of ``leaves`` or one layer's slice of it: a copy or transpose,
    a fusion, or a slice that is a fusion's root (a slice that is not a
    root fuses into its product)."""
    import re

    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if str(getattr(path[-1], "key", "")) in leaves and leaf.ndim > 2:
            shapes |= {",".join(map(str, leaf.shape)),
                       ",".join(map(str, (1,) + leaf.shape[1:]))}
    assert shapes, "none of the leaves in the parameters"
    inst = re.compile(r"^(ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(")
    return [line.strip()[:160] for line in text.splitlines()
            for m in [inst.match(line.strip())] if m
            and m.group(2) in shapes
            and (m.group(3) in ("copy", "copy-start", "transpose", "fusion")
                 or m.group(1) and "slice" in m.group(3))]


#: argument and temporary bytes of the two programs' chipless v5e compile
#: (the decode program's temporaries are nearly all the latent
#: projections' transposed copies, below)
AXK_BYTES = dict(decode_chunk=(11_744_482_304, 555_579_904),
                 prefill_step=(11_744_419_840, 1_973_760))
#: what the shared latent path moves in LongCat's programs too, whose
#: pins hold them: the absorbed form's products read ``wq_b``, ``wkv_a``
#: and ``wkv_b`` head-major, and XLA copies those stacks transposed once
#: a chunk and slices a layer out of the copy in every layer of every
#: step (PERF.md section 7)
LATENT_MOVED = ("wq_b", "wkv_a", "wkv_b")


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_step"])
def test_grouped_latent_serve_programs_compile_at_the_cells_shapes(
        v5e, monkeypatch, program):
    """Both programs of the A.X-K1 cell at its own sizes — published
    widths, layer 0 then the 7 expert layers as a scan, 64 slots, the
    latent pool of 4,224 blocks carried and updated in place: they fit
    the chip, the decode step holds the latent kernel and both hold the
    grouped expert product over ``[512, 7168]`` / ``[256, 7168]`` rows
    (the rule: 2.7 and 1.3 rows a router output), the routing counts ride
    home in six rows, and no weight of the dense layer, the router, the
    experts or the shared expert, nor ``wq_a`` or ``wo``, is copied or
    sliced out of its stack; the three latent projections the shared
    latent path moves (``LATENT_MOVED``) are the only ones moved.  The
    argument and temporary bytes are pinned (PERF.md section 4; ``-rP``
    prints them)."""
    import math

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _axk1_cfg()
    assert llama.walk_plan(cfg.kinds) == llama.WalkPlan(1, 1, 7)
    params = _abstract(v5e, lambda: llama.init_params(cfg, 0, "bfloat16"))
    pool = _abstract(v5e, lambda: llama.init_paged_cache(cfg, AXK_BLOCKS, 16))
    assert {k: v.shape for k, v in pool.items()} == {
        "c": (8, AXK_BLOCKS, 16, 640)}
    assert math.prod(pool["c"].shape) * 2 == 692_060_160

    table = v5e((AXK_SLOTS, 256), jnp.int32)
    if program == "decode_chunk":
        fn, donated, args = _sparse_decode_chunk(cfg), (1,), (
            v5e((AXK_SLOTS,), jnp.int32), table, v5e((AXK_SLOTS,), jnp.int32))
        out = jax.eval_shape(fn, params, pool, *args)
        assert out[0].shape == (AXK_SLOTS + 6, 8)   # tokens, then 6 counts
    else:
        fn, donated, args = _prefill_step(cfg), PREFILL_DONATED, \
            _prefill_args(v5e, AXK_SLOTS, v5e((1, 256), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=donated).lower(
        params, pool, *args).compile()
    text = compiled.as_text()
    decode = program == "decode_chunk"
    assert ("%paged_latent_attention" in text) == decode
    _the_grouped_kernel_is_the_expert_product(
        text, 512 if decode else 256, width=7168)
    _the_combine_is_no_scatter(text, 7168)
    moved = _weights_moved(text, params, (
        "wq_a", "wo", "w_gate", "w_up", "w_down", "we_gate", "we_up",
        "we_down", "ws_gate", "ws_up", "ws_down"))
    assert not moved, "a weight is moved:\n" + "\n".join(moved)
    assert _weights_moved(text, params, LATENT_MOVED)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(pool["c"].shape) * 2
    bytes_limit = 16_909_336_064
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < bytes_limit
    for name in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        print(program, name, getattr(mem, name))
    assert (mem.argument_size_in_bytes, mem.temp_size_in_bytes) \
        == AXK_BYTES[program]
