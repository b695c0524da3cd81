"""Compile-only checks of the serving path's Pallas kernels for a TPU v5e,
at llama3.2-1b widths and at the GLM-4-9B benchmark cells' paged-attention
shapes, against a described (not attached) v5e:2x2 chip.

Nothing runs: each test lowers a kernel with ``interpret=False`` and
compiles it with the TPU compiler, which refuses what the chip would (a
block that overflows VMEM, a slice not aligned to the tiling). The
topology is described inside a module-scoped fixture, so only the worker
that runs this file loads the TPU compiler. The CPU parity case at the
end checks the column-tiled seal kernels against ``kernels/ref.py`` in
interpret mode.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.seal import (_block_shape, seal_bits_pallas, seal_pallas,
                                unseal_bits_pallas, unseal_pallas)

CFG = get_arch("llama3.2-1b")
GLM = get_arch("glm4-9b")
# the GLM-4-9B cells (benchmarks/onchip): 64 slots, request capacity 5120
# (320 pages of 16 tokens a slot), a 1.95 GiB bf16 pool of 6390 pages
CELL_SLOTS, CELL_PAGES_PER_SLOT, CELL_NUM_PAGES = 64, 5120 // 16, 6390
PAGE = 16                               # tokens per KV page
PAGES_PER_SLOT = 2048 // PAGE           # request_capacity 2048
SLOTS = 16
# one page row of the swap / transfer payload: every layer's K (or V) of
# one page, [L * KVH * Pg * D]
PAGE_ROW = CFG.num_layers * CFG.num_kv_heads * PAGE * CFG.head_dim


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_attention_text(one_chip, cfg, slots, pages_per_slot, num_pages):
    rep = cfg.num_heads // cfg.num_kv_heads
    q = _sds((slots, cfg.num_kv_heads, rep, cfg.head_dim), jnp.bfloat16,
             one_chip)
    pool = _sds((num_pages, cfg.num_kv_heads, PAGE, cfg.head_dim),
                jnp.bfloat16, one_chip)
    bt = _sds((slots, pages_per_slot), jnp.int32, one_chip)
    sl = _sds((slots,), jnp.int32, one_chip)
    return _compiled_text(
        lambda *a: paged_attention_pallas(*a, interpret=False),
        q, pool, pool, bt, sl)


def test_paged_attention_compiles_for_v5e(one_chip):
    """The decode kernel at the GLM-4-9B cells' shapes: KVH 2, rep 16,
    head_dim 128, 64 slots of 320 pages."""
    text = _paged_attention_text(one_chip, GLM, CELL_SLOTS,
                                 CELL_PAGES_PER_SLOT, CELL_NUM_PAGES)
    assert "tpu_custom_call" in text


def test_lane_packed_paged_attention_compiles_for_v5e(one_chip):
    """llama3.2-1b's 64-wide heads, read two positions to a 128-lane row
    (chip_smoke.py serves it through the same kernel)."""
    text = _paged_attention_text(one_chip, CFG, SLOTS, PAGES_PER_SLOT,
                                 SLOTS * PAGES_PER_SLOT + 1)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["seal", "unseal"])
def test_activation_seal_compiles_for_v5e(one_chip, direction):
    key = _sds((), jnp.uint32, one_chip)
    ctr = _sds((), jnp.uint32, one_chip)
    if direction == "seal":
        x = _sds((SLOTS, CFG.d_model), jnp.bfloat16, one_chip)
        text = _compiled_text(
            lambda x, k, c: seal_pallas(x, k, c, interpret=False),
            x, key, ctr)
    else:
        c8 = _sds((SLOTS, CFG.d_model), jnp.uint8, one_chip)
        sc = _sds((SLOTS, 1), jnp.float32, one_chip)
        text = _compiled_text(
            lambda c8, sc, k, c: unseal_pallas(c8, sc, k, c,
                                               interpret=False),
            c8, sc, key, ctr)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["seal", "unseal"])
def test_page_seal_bits_compiles_for_v5e(one_chip, direction):
    """A whole slot's pages ([128, 131072] bf16 rows) through the lossless
    page cipher: the swap-out / transfer payload of a 2048-token request."""
    key = _sds((), jnp.uint32, one_chip)
    ctr = _sds((), jnp.uint32, one_chip)
    if direction == "seal":
        x = _sds((PAGES_PER_SLOT, PAGE_ROW), jnp.bfloat16, one_chip)
        text = _compiled_text(
            lambda x, k, c: seal_bits_pallas(x, k, c, interpret=False),
            x, key, ctr)
    else:
        u = _sds((PAGES_PER_SLOT, PAGE_ROW), jnp.uint16, one_chip)
        text = _compiled_text(
            lambda u, k, c: unseal_bits_pallas(u, k, c, interpret=False),
            u, key, ctr)
    assert "tpu_custom_call" in text


def test_column_tiled_seal_matches_ref_in_interpret_mode():
    """Rows and columns both tiled, neither a multiple of its block: the
    edge blocks' keystream indices must still be the global ones."""
    rows, cols = 300, 1100
    bR, bC = _block_shape(rows, cols)
    assert bC < cols and cols % bC and rows % bR
    key, ctr = jnp.uint32(0xBEEF), jnp.uint32(5)
    x = jnp.asarray(np.random.RandomState(0).randn(rows, cols),
                    jnp.bfloat16)
    c = seal_bits_pallas(x, key, ctr)
    assert (c == ref.seal_bits_ref(x, key, ctr)).all()
    assert (unseal_bits_pallas(c, key, ctr) == x).all()
    c8, sc = seal_pallas(x, key, ctr)
    c8_ref, sc_ref = ref.seal_ref(x, key, ctr)
    assert (c8 == c8_ref).all() and (sc == sc_ref).all()
    assert (unseal_pallas(c8, sc, key, ctr)
            == ref.unseal_ref(c8_ref, sc_ref, key, ctr)).all()
