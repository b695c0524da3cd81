"""Paged decode attention (Pallas, TPU target), bounded by each row's length.

One decode token per batch row attends over a block-table-indexed paged KV
cache. The pools stay in HBM; the kernel copies the pages a row holds, and
no others, into VMEM:

- Grid ``(B,)``, one step per row. ``block_tables`` (flattened) and
  ``seq_lens`` are scalar-prefetch arguments in SMEM.
- Row ``b`` holds ``ceil(seq_lens[b] / Pg)`` live pages. They are streamed
  in blocks of ``ppb`` pages by a loop whose trip count is the row's own,
  so a row with ``seq_lens == 0`` (an idle cache slot) copies nothing and
  reads zeros. A page ``[KVH, Pg, D]`` is contiguous in the
  ``[N, KVH, Pg, D]`` pool, so each is one DMA. Two buffers alternate:
  block ``i + 1``'s copies run while block ``i`` is computed.
- ``ppb`` is the most pages whose K and V buffers, double-buffered, fit
  ``BUFFER_BYTES`` of VMEM (at most the table's width).
- Per KV head, all ``rep`` query heads take one dot against the block's
  keys. K goes to the MXU in the pool's dtype with f32 accumulation (a
  bf16 product is exact in f32, so the scores are the oracle's). The
  online softmax (running max and sum), the probabilities and the P·V
  accumulation are f32; V is widened to f32 for that dot. Positions past
  ``seq_lens[b]`` in a row's last block are masked, and their V rows
  zeroed, so whatever the buffer held before cannot leak in.
- A head narrower than the 128 lanes (``D = 64``) cannot be sliced out of
  the HBM pool by a DMA, so pages are read through the lane-dense view
  ``[N, KVH, Pg / f, f * D]``: ``f`` positions to a row, position
  ``f * r + g`` in row ``r``, lane group ``g``. Query row ``g * rep + i``
  carries head ``i`` in lane group ``g`` and zeros elsewhere, so each lane
  group keeps its own softmax over its positions; the kernel returns the
  unnormalised sums with their max and total, and the groups merge after
  it. With ``D >= 128`` there is one group and the merge is a division.

Off-TPU the same body runs in ``interpret=True`` mode. The parity target is
``ref.paged_attention_ref`` on every row with ``seq_lens >= 1``
(tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# VMEM for the K and V page buffers, both slots of each
BUFFER_BYTES = 1 << 20


def lane_pack(head_dim: int, page_size: int) -> int:
    """Positions per 128-lane row: a head narrower than the lanes is read
    through a lane-dense view of the page, ``f`` positions to a row."""
    f = LANES // head_dim if head_dim < LANES else 1
    return f if f > 1 and LANES % head_dim == 0 and page_size % f == 0 \
        else 1


def pages_per_block(page_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages copied per block: the most whose K and V buffers, two slots
    each, fit ``BUFFER_BYTES``; never more than a row's table holds."""
    page_bytes = kv_heads * page_size * head_dim * itemsize
    return max(1, min(max_pages, BUFFER_BYTES // (4 * page_bytes)))


def _paged_kernel(bt_ref, sl_ref, q_ref, k_hbm, v_hbm, acc_ref, m_ref, l_ref,
                  k_buf, v_buf, sems, *, page_size: int, max_pages: int,
                  ppb: int, pack: int, scale: float):
    b = pl.program_id(0)
    kv_heads, rows, width = q_ref.shape[1:]
    rep, head_dim = rows // pack, width // pack
    page_rows = page_size // pack
    block_rows = ppb * page_rows
    length = sl_ref[b]
    live_pages = jnp.minimum(pl.cdiv(length, page_size), max_pages)
    num_blocks = pl.cdiv(live_pages, ppb)

    def pages_in(blk):
        return jnp.minimum(ppb, live_pages - blk * ppb)

    def copies(slot, j, page):
        dst = pl.ds(j * page_rows, page_rows)
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, :, dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, :, dst],
                                      sems.at[1, slot]))

    def start(blk, slot):
        def one(j, carry):
            page = bt_ref[b * max_pages + blk * ppb + j]
            for c in copies(slot, j, page):
                c.start()
            return carry
        jax.lax.fori_loop(0, pages_in(blk), one, 0)

    def wait(blk, slot):
        # every copy of a block has one page's size, so each wait takes
        # one page off the slot's semaphores
        def one(j, carry):
            for c in copies(slot, 0, 0):
                c.wait()
            return carry
        jax.lax.fori_loop(0, pages_in(blk), one, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(num_blocks > 0)
    def _():
        start(0, 0)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < num_blocks)
        def _():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        # buffer row c, lane group g holds position base + pack * c + g;
        # query row r looks at lane group r // rep
        base = blk * ppb * page_size
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
        s_live = (base + pack * iota((rows, block_rows), 1)
                  + iota((rows, block_rows), 0) // rep) < length
        v_live = (base + pack * iota((block_rows, width), 0)
                  + iota((block_rows, width), 1) // head_dim) < length
        for h in range(kv_heads):
            q = q_ref[0, h]                                  # [rows, W]
            k = k_buf[slot, h].astype(q.dtype)               # [block_rows, W]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(s_live, s, NEG_INF)
            m_prev, l_prev = m_ref[0, h], l_ref[0, h]        # [rows, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_ref[0, h] = m_new
            l_ref[0, h] = l_prev * corr + p.sum(axis=1, keepdims=True)
            v = jnp.where(v_live, v_buf[slot, h].astype(jnp.float32), 0.0)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[0, h] = acc_ref[0, h] * corr + pv
        return carry

    jax.lax.fori_loop(0, num_blocks, block, 0)


def paged_attention_pallas(q, k_pages, v_pages, block_tables, seq_lens, *,
                           interpret: bool = True):
    """q: [B, KVH, rep, D]; k_pages, v_pages: [N, KVH, Pg, D];
    block_tables: [B, MP] int32; seq_lens: [B] int32. Returns q-shaped."""
    B, KVH, rep, D = q.shape
    N, _, Pg, _ = k_pages.shape
    MP = block_tables.shape[1]
    f = lane_pack(D, Pg)
    R, W = Pg // f, f * D
    ppb = pages_per_block(Pg, KVH, D, jnp.dtype(k_pages.dtype).itemsize, MP)
    # query row g * rep + i holds head i in lane group g and zeros elsewhere
    dtype = jnp.promote_types(q.dtype, k_pages.dtype)
    q_rows = jnp.zeros((B, KVH, f * rep, W), dtype)
    for g in range(f):
        q_rows = q_rows.at[:, :, g * rep:(g + 1) * rep,
                           g * D:(g + 1) * D].set(q.astype(dtype))
    row_block = pl.BlockSpec((1, KVH, f * rep, W),
                             lambda b, bt, sl: (b, 0, 0, 0))
    stat_block = pl.BlockSpec((1, KVH, f * rep, 1),
                              lambda b, bt, sl: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_block, pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[row_block, stat_block, stat_block],
        scratch_shapes=[
            pltpu.VMEM((2, KVH, ppb * R, W), k_pages.dtype),
            pltpu.VMEM((2, KVH, ppb * R, W), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # [K|V, slot]
        ],
    )
    stat = jax.ShapeDtypeStruct((B, KVH, f * rep, 1), jnp.float32)
    kernel = functools.partial(_paged_kernel, page_size=Pg, max_pages=MP,
                               ppb=ppb, pack=f, scale=1.0 / math.sqrt(D))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KVH, f * rep, W), jnp.float32),
                   stat, stat],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.reshape(-1), seq_lens, q_rows,
      k_pages.reshape(N, KVH, R, W), v_pages.reshape(N, KVH, R, W))
    # merge the lane groups' partial softmaxes: group g's output is its
    # own lanes of its own query rows
    groups = jnp.arange(f)
    acc = acc.reshape(B, KVH, f, rep, f, D)[:, :, groups, :, groups]
    m = m.reshape(B, KVH, f, rep, 1).transpose(2, 0, 1, 3, 4)
    l = l.reshape(B, KVH, f, rep, 1).transpose(2, 0, 1, 3, 4)
    w = jnp.exp(m - m.max(axis=0))
    out = (w * acc).sum(axis=0) / jnp.maximum((w * l).sum(axis=0), 1e-30)
    return out.astype(q.dtype)
