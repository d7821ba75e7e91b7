"""Chipless pre-check: the three Pallas kernels must COMPILE for a TPU v5e
at llama2_7b decode/prefill shapes.

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

    def abstract(make):
        return jax.tree_util.tree_map(
            lambda x: v5e(x.shape, x.dtype), jax.eval_shape(make))

    params = abstract(lambda: llama.init_params_int8(cfg, 0, "bfloat16"))
    pool = abstract(lambda: llama.init_paged_cache(cfg, n_blocks, bs))
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
