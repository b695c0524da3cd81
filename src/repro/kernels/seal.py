"""Sealing kernel: fused int8 quantization + counter-mode keystream XOR.

This is the TPU-native analogue of the paper's AES-128 boundary encryption
(Sec. VI-D): every activation tensor crossing a trust-domain boundary is
(1) quantized to int8 with per-row scales — 4x boundary-traffic compression,
the distributed-optimization trick the 30 Mbps WAN / DCN link begs for — and
(2) XORed with a keystream generated in-register from (key, step counter,
element index) by a squares-RNG/xorshift ARX mix.

Layout: x [rows, cols] -> cipher uint8 [rows, cols] + scales f32 [rows, 1].
The grid tiles rows AND columns (``_block_shape``): a block holds at most
``BLOCK_ELEMS`` elements whatever the row width, so a [128, 131072] KV page
payload stays inside VMEM as well as a [4, 2048] activation does. The
per-row scales need the whole row, so ``seal_pallas`` reduces them in one
jnp pass before the kernel; the kernel then quantizes and ciphers each
block, with the element index (and so the keystream) taken from the
block's global row and column offsets. Key and counter are scalars in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# elements per block: 512 KiB per uint32 temporary of the keystream, a few
# MiB of VMEM in all with double-buffered operands
BLOCK_ELEMS = 128 * 1024
BLOCK_ROWS = 256                 # a multiple of 32, uint8's sublane tile
LANES = 128
_M1 = np.uint32(0x9E3779B9)      # golden-ratio odd constants (Weyl / squares)
_M2 = np.uint32(0x85EBCA6B)
_M3 = np.uint32(0xC2B2AE35)


def keystream_u32(key: jnp.ndarray, counter: jnp.ndarray, idx: jnp.ndarray):
    """Per-element 32-bit keystream: ARX mix of (key, counter, index).

    key: uint32 scalar; counter: uint32 scalar; idx: uint32 array.
    Identical code runs inside the Pallas kernel and in the jnp oracle.
    """
    x = idx * _M1
    x = x ^ (key + counter * _M2)
    x = (x ^ (x >> 16)) * _M2
    x = (x ^ (x >> 13)) * _M3
    x = x ^ (x >> 16)
    # second squares round for diffusion
    x = x * (key | np.uint32(1)) + counter
    x = (x ^ (x >> 15)) * _M1
    return x ^ (x >> 17)


def row_scales(x: jax.Array) -> jax.Array:
    """Per-row int8 quantization scales [rows, 1] (f32)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    return jnp.maximum(absmax, 1e-6) / 127.0


def _block_shape(rows: int, cols: int):
    """(bR, bC) tile of at most BLOCK_ELEMS elements: whole rows up to
    BLOCK_ROWS, then whole lane groups of columns. A dim is either taken
    whole or tiled by a multiple of its TPU tile (32 rows, 128 lanes);
    edge blocks past the array are padded on read and dropped on write."""
    bR = rows if rows <= BLOCK_ROWS else BLOCK_ROWS
    bC = max(LANES, BLOCK_ELEMS // bR // LANES * LANES)
    return bR, (cols if cols <= bC else bC)


def _global_index(block, cols: int):
    """uint32 element index ``row * cols + col`` of every element of the
    current (row-block, column-block) grid step."""
    bR, bC = block
    row = pl.program_id(0).astype(jnp.uint32) * jnp.uint32(bR) \
        + jax.lax.broadcasted_iota(jnp.uint32, block, 0)
    col = pl.program_id(1).astype(jnp.uint32) * jnp.uint32(bC) \
        + jax.lax.broadcasted_iota(jnp.uint32, block, 1)
    return row * jnp.uint32(cols) + col


def _tiled_call(kernel, arrays, key, counter, out_shape, *, row_inputs=(),
                interpret: bool):
    """Run ``kernel`` over a (row-block, column-block) grid: ``arrays`` are
    [rows, cols] operands tiled like the output, ``row_inputs`` [rows, 1]
    operands tiled by rows only, then the key and counter scalars in SMEM."""
    rows, cols = out_shape.shape
    bR, bC = _block_shape(rows, cols)
    tile = pl.BlockSpec((bR, bC), lambda i, j: (i, j))
    row_tile = pl.BlockSpec((bR, 1), lambda i, j: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(kernel, cols=cols),
        grid=(pl.cdiv(rows, bR), pl.cdiv(cols, bC)),
        in_specs=[tile] * len(arrays) + [row_tile] * len(row_inputs)
        + [smem, smem],
        out_specs=tile,
        out_shape=out_shape,
        interpret=interpret,
    )(*arrays, *row_inputs, key.reshape(1).astype(jnp.uint32),
      counter.reshape(1).astype(jnp.uint32))


def _seal_kernel(x_ref, scale_ref, key_ref, ctr_ref, out_ref, *, cols: int):
    x = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale_ref[...]), -127, 127).astype(jnp.int32)
    ks = keystream_u32(key_ref[0], ctr_ref[0], _global_index(x.shape, cols))
    ks8 = (ks >> 24).astype(jnp.int32) & 0xFF      # one byte per element
    out_ref[...] = ((q & 0xFF) ^ ks8).astype(jnp.uint8)


def _unseal_kernel(c_ref, scale_ref, key_ref, ctr_ref, out_ref, *, cols: int):
    c = c_ref[...].astype(jnp.int32)
    ks = keystream_u32(key_ref[0], ctr_ref[0], _global_index(c.shape, cols))
    q = c ^ ((ks >> 24).astype(jnp.int32) & 0xFF)
    # sign-extend the low byte back to int8 range
    q = jnp.where(q >= 128, q - 256, q).astype(jnp.float32)
    out_ref[...] = (q * scale_ref[...]).astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# Lossless bit-seal (two-tier KV swap): bitcast + keystream XOR, no quantize
# ---------------------------------------------------------------------------
# The activation seal above trades precision for 4x boundary compression —
# fine for hidden states re-entering a matmul, fatal for swapped KV pages
# that must restore BIT-EXACTLY (the engine's swap-preemption contract is a
# stream identical to an undisturbed run). seal_bits keeps the same
# counter-mode keystream discipline but ciphers the raw float bits:
# unseal(seal(x)) == x to the last mantissa bit, for f32 and bf16 alike.

def uint_dtype_of(dtype) -> jnp.dtype:
    """The same-width unsigned dtype a float array bitcasts to."""
    return {2: jnp.dtype(jnp.uint16), 4: jnp.dtype(jnp.uint32)}[
        jnp.dtype(dtype).itemsize]


def _bits_kernel(x_ref, key_ref, ctr_ref, out_ref, *, cols: int):
    x = x_ref[...]
    udt = uint_dtype_of(x.dtype)
    u = x if x.dtype == udt else jax.lax.bitcast_convert_type(x, udt)
    ks = keystream_u32(key_ref[0], ctr_ref[0],
                       _global_index(x.shape, cols)).astype(udt)
    c = u ^ ks
    out_ref[...] = c if out_ref.dtype == udt \
        else jax.lax.bitcast_convert_type(c, out_ref.dtype)


def seal_bits_pallas(x: jax.Array, key: jax.Array, counter: jax.Array,
                     *, interpret: bool = True):
    """x: [rows, cols] float -> cipher uintN [rows, cols] (same bit width).
    XOR is an involution, so the keystream pass is its own inverse and the
    round trip is exact — no scales, no clipping, no rounding."""
    out = jax.ShapeDtypeStruct(x.shape, uint_dtype_of(x.dtype))
    return _tiled_call(_bits_kernel, (x,), key, counter, out,
                       interpret=interpret)


def unseal_bits_pallas(cipher: jax.Array, key: jax.Array, counter: jax.Array,
                       *, out_dtype=jnp.bfloat16, interpret: bool = True):
    assert cipher.dtype == uint_dtype_of(out_dtype), (cipher.dtype, out_dtype)
    out = jax.ShapeDtypeStruct(cipher.shape, out_dtype)
    return _tiled_call(_bits_kernel, (cipher,), key, counter, out,
                       interpret=interpret)


def seal_pallas(x: jax.Array, key: jax.Array, counter: jax.Array,
                *, interpret: bool = True):
    """x: [rows, cols] float -> (cipher uint8 [rows, cols], scales [rows, 1])."""
    scales = row_scales(x)
    cipher = _tiled_call(_seal_kernel, (x,), key, counter,
                         jax.ShapeDtypeStruct(x.shape, jnp.uint8),
                         row_inputs=(scales,), interpret=interpret)
    return cipher, scales


def unseal_pallas(cipher: jax.Array, scales: jax.Array, key: jax.Array,
                  counter: jax.Array, *, out_dtype=jnp.bfloat16,
                  interpret: bool = True):
    return _tiled_call(_unseal_kernel, (cipher,), key, counter,
                       jax.ShapeDtypeStruct(cipher.shape, out_dtype),
                       row_inputs=(scales,), interpret=interpret)
