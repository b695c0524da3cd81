"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .seal import keystream_u32, uint_dtype_of


# ---------------------------------------------------------------------------
# seal / unseal
# ---------------------------------------------------------------------------
def seal_ref(x: jax.Array, key: jax.Array, counter: jax.Array):
    """Oracle for seal_pallas: int8 quantize + keystream XOR."""
    rows, cols = x.shape
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int32)
    idx = (jnp.arange(rows, dtype=jnp.uint32)[:, None] * jnp.uint32(cols)
           + jnp.arange(cols, dtype=jnp.uint32)[None, :])
    ks = keystream_u32(key.astype(jnp.uint32).reshape(()),
                       counter.astype(jnp.uint32).reshape(()), idx)
    ks8 = (ks >> 24).astype(jnp.int32) & 0xFF
    cipher = ((q & 0xFF) ^ ks8).astype(jnp.uint8)
    return cipher, scale


def unseal_ref(cipher: jax.Array, scales: jax.Array, key: jax.Array,
               counter: jax.Array, out_dtype=jnp.bfloat16):
    rows, cols = cipher.shape
    idx = (jnp.arange(rows, dtype=jnp.uint32)[:, None] * jnp.uint32(cols)
           + jnp.arange(cols, dtype=jnp.uint32)[None, :])
    ks = keystream_u32(key.astype(jnp.uint32).reshape(()),
                       counter.astype(jnp.uint32).reshape(()), idx)
    ks8 = (ks >> 24).astype(jnp.int32) & 0xFF
    q = cipher.astype(jnp.int32) ^ ks8
    q = jnp.where(q >= 128, q - 256, q).astype(jnp.float32)
    return (q * scales).astype(out_dtype)


# ---------------------------------------------------------------------------
# seal_bits / unseal_bits — lossless bitcast+XOR oracle (KV swap tier)
# ---------------------------------------------------------------------------
def _bits_keystream(shape, key, counter, udt):
    rows, cols = shape
    idx = (jnp.arange(rows, dtype=jnp.uint32)[:, None] * jnp.uint32(cols)
           + jnp.arange(cols, dtype=jnp.uint32)[None, :])
    ks = keystream_u32(key.astype(jnp.uint32).reshape(()),
                       counter.astype(jnp.uint32).reshape(()), idx)
    return ks.astype(udt)


def seal_bits_ref(x: jax.Array, key: jax.Array, counter: jax.Array):
    """Oracle for seal_bits_pallas: bitcast float -> uintN, XOR keystream.
    Exactly invertible (XOR involution) — the swap tier's round trip must
    restore KV pages bit-for-bit."""
    udt = uint_dtype_of(x.dtype)
    u = x if x.dtype == udt else jax.lax.bitcast_convert_type(x, udt)
    return u ^ _bits_keystream(x.shape, key, counter, udt)


def unseal_bits_ref(cipher: jax.Array, key: jax.Array, counter: jax.Array,
                    out_dtype=jnp.bfloat16):
    udt = uint_dtype_of(out_dtype)
    assert cipher.dtype == udt, (cipher.dtype, out_dtype)
    u = cipher ^ _bits_keystream(cipher.shape, key, counter, udt)
    return u if jnp.dtype(out_dtype) == udt \
        else jax.lax.bitcast_convert_type(u, out_dtype)


# ---------------------------------------------------------------------------
# paged decode attention — page-gather oracle
# ---------------------------------------------------------------------------
def paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens):
    """Single-token attention over a block-table-indexed paged KV cache.

    q: [B, H, D] (one decode token per batch row);
    k_pages, v_pages: [num_pages, KVH, page_size, D] shared page pools;
    block_tables: [B, max_pages] int32 — page ids of each row's sequence, in
    order (unused tail entries point at the reserved null page 0);
    seq_lens: [B] int32 — valid tokens per row (token t of row b lives in
    page ``block_tables[b, t // page_size]`` at offset ``t % page_size``).

    Gathers each row's pages into a contiguous [max_pages * page_size] view
    and runs masked softmax attention in f32 — this is both the allclose
    target for the Pallas kernel and the jnp path the models use off-TPU
    (the gather touches max_pages * page_size tokens of every row, live or
    idle, where the kernel reads only each row's live pages).
    """
    B, H, D = q.shape
    KVH, Pg = k_pages.shape[1], k_pages.shape[2]
    rep = H // KVH
    MP = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    # [B, MP, KVH, Pg, D] -> [B, KVH, MP * Pg, D]
    k = jnp.transpose(k_pages[block_tables], (0, 2, 1, 3, 4)
                      ).reshape(B, KVH, MP * Pg, D)
    v = jnp.transpose(v_pages[block_tables], (0, 2, 1, 3, 4)
                      ).reshape(B, KVH, MP * Pg, D)
    qf = q.reshape(B, KVH, rep, D).astype(jnp.float32)
    s = jnp.einsum("bgrd,bgsd->bgrs", qf, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(MP * Pg)[None, :] < seq_lens[:, None]       # [B, S]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention (causal, optional sliding window) — naive oracle
# ---------------------------------------------------------------------------
def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q, k, v: [B, H, S, D] (MHA; GQA handled by the wrapper). f32 math."""
    B, H, S, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((S, k.shape[2]), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
