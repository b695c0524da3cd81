"""Jitted public wrappers around the Pallas kernels.

On TPU the kernels compile natively; on the CPU, where the tests run, they
execute in ``interpret=True`` mode — same kernel body, Python-interpreted —
which is the validation path the tests exercise against the ref.py oracles.
Any other backend is an error rather than a silent interpreter run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .seal import (seal_bits_pallas, seal_pallas, unseal_bits_pallas,
                   unseal_pallas)
from .flash_attention import flash_attention_pallas
from .paged_attention import paged_attention_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels compile for tpu and are "
                           f"interpreted on cpu; backend {backend!r} is "
                           f"neither")
    return backend == "cpu"


# ---------------------------------------------------------------------------
# Sealing
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_kernel",))
def seal(x, key, counter, use_kernel: bool = False):
    """Quantize+encrypt a 2D activation. Returns (cipher u8, scales f32)."""
    if use_kernel:
        return seal_pallas(x, key, counter, interpret=_interpret())
    return ref.seal_ref(x, key, counter)


@functools.partial(jax.jit, static_argnames=("use_kernel", "out_dtype"))
def unseal(cipher, scales, key, counter, out_dtype=jnp.bfloat16,
           use_kernel: bool = False):
    if use_kernel:
        return unseal_pallas(cipher, scales, key, counter,
                             out_dtype=out_dtype, interpret=_interpret())
    return ref.unseal_ref(cipher, scales, key, counter, out_dtype)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def seal_bits(x, key, counter, use_kernel: bool = False):
    """Losslessly cipher a 2D float array -> uintN of the same bit width.
    Unlike ``seal`` there is no quantization: ``unseal_bits`` restores the
    input bit-exactly (the KV swap tier's correctness contract)."""
    if use_kernel:
        return seal_bits_pallas(x, key, counter, interpret=_interpret())
    return ref.seal_bits_ref(x, key, counter)


@functools.partial(jax.jit, static_argnames=("use_kernel", "out_dtype"))
def unseal_bits(cipher, key, counter, out_dtype=jnp.bfloat16,
                use_kernel: bool = False):
    if use_kernel:
        return unseal_bits_pallas(cipher, key, counter, out_dtype=out_dtype,
                                  interpret=_interpret())
    return ref.unseal_bits_ref(cipher, key, counter, out_dtype)


# ---------------------------------------------------------------------------
# Flash attention (GQA-aware wrapper)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "window", "use_kernel"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    use_kernel: bool = False):
    """q: [B, S, H, D]; k, v: [B, S, KVH, D]. Returns [B, S, H, D]."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    kk = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vv = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = kk.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
    vf = vv.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
    if use_kernel:
        out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                     interpret=_interpret())
    else:
        out = ref.flash_attention_ref(
            qf.reshape(B, H, S, D), kf.reshape(B, H, -1, D),
            vf.reshape(B, H, -1, D), causal=causal, window=window,
        ).reshape(B * H, S, D)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Paged decode attention (GQA-aware wrapper)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_kernel",))
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    use_kernel: bool = False):
    """q: [B, H, D] (one decode token per row); k_pages, v_pages:
    [num_pages, KVH, page_size, D]; block_tables: [B, max_pages] int32;
    seq_lens: [B] int32. Returns [B, H, D].

    use_kernel=True routes to the Pallas kernel, which copies only each
    row's live pages (interpret mode on the CPU); the default is the jnp
    page-gather oracle, the CPU path. Rows with ``seq_lens == 0`` differ
    between the two and are finite in both."""
    if use_kernel:
        B, H, D = q.shape
        KVH = k_pages.shape[1]
        rep = H // KVH
        out = paged_attention_pallas(
            q.reshape(B, KVH, rep, D), k_pages, v_pages,
            block_tables, seq_lens, interpret=_interpret())
        return out.reshape(B, H, D)
    return ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                   seq_lens)
